"""Short-convolution layer: device self time a step, device 0, of
everything the ``conv`` layers' mixers run (scope ``attn/conv``: the
layer's norm, the projection to [B C x], both gates and the
convolution, the out-projection and the residual add; forward,
recomputed forward and backward).  An earlier line gives the parts.
A program without the scope leaves the metric out."""

from benchmarks.lib.harness import log
from benchmarks.lib.scopes_step import path_ms

LAYER = "attn/conv"
PARTS = ("attn.qkv", "shortconv/conv", "attn.out")


def read(ctx):
    whole = path_ms(ctx, LAYER)
    if whole is None:
        return None
    log("shortconv.ms_per_step", **{
        part: f"{path_ms(ctx, LAYER, part) or 0:.3f}" for part in PARTS})
    return whole
