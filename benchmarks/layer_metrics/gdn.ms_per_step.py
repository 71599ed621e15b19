"""Linear-attention layer: device self time a step, device 0, of
everything the Gated DeltaNet layers run (scope ``attn/gdn``: the
layer's norm, the two in-projections, the short convolution with its L2
norms, the step and the decay, the chunked recurrence, the output norm
and gate, the out-projection and the residual add; forward, recomputed
forward and backward).  What a rematerialised slab of the recurrence
runs in the backward pass wears ``gdn/scan`` without the layer's name
before it, and counts here too.  An earlier line gives the parts."""

from benchmarks.lib.harness import log
from benchmarks.lib.readings import per_step_ms
from benchmarks.lib.scopes_step import classified, on_path

LAYER = "attn/gdn"
PARTS = ("attn.qkv", "attn.out", "gdn/conv", "gdn/gate", "gdn/scan")


def _is_the_layers(path):
    return on_path(path, LAYER) or on_path(path, "gdn/")


def read(ctx):
    found = classified(ctx)
    if found is None:
        return None
    mine = [(path, s) for _, path, s in found.values()
            if _is_the_layers(path)]
    if not mine:
        return None
    log("gdn.ms_per_step", **{
        part: f"{per_step_ms(ctx, sum(s for path, s in mine if part in path)):.3f}"
        for part in PARTS})
    return per_step_ms(ctx, sum(s for _, s in mine))
