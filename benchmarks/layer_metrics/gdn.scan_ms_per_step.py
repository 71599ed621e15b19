"""Kernels: the part of ``gdn.ms_per_step`` under ``gdn/scan``, the
chunked scalar-decay delta rule (``ops/gdn.py``: a chunk's pair
matrices from one product a key head and the mask of decays, the
unit-triangular systems, the chunk's own pairs applied and what meets
the carried state; forward, recomputed forward and backward), device 0.
An earlier line gives its four children."""

from benchmarks.lib.harness import log
from benchmarks.lib.scopes_step import path_ms

CHILDREN = ("gdn.pairs", "kda.solve", "gdn.intra", "gdn.inter")


def read(ctx):
    whole = path_ms(ctx, "gdn/scan")
    if whole is None:
        return None
    log("gdn.scan_ms_per_step", **{
        child: f"{path_ms(ctx, 'gdn/scan', child) or 0:.3f}"
        for child in CHILDREN})
    return whole
