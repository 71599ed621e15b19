"""Kernels: the part of ``kda.ms_per_step`` under ``kda/scan``, the
chunked recurrence (``ops/kda.py``: the pair weights, the triangular
solve, the scans over slabs and chunks; forward, recomputed forward and
backward), device 0.  An earlier line gives the other parts."""

from benchmarks.lib.harness import log
from benchmarks.lib.readings import per_step_ms
from benchmarks.lib.scopes_hybrid import scope_seconds


def read(ctx):
    seconds = scope_seconds(ctx, "kda/scan")
    if seconds is None:
        return None
    log("kda.scan_ms_per_step", **{
        part: f"{per_step_ms(ctx, scope_seconds(ctx, part) or 0):.3f}"
        for part in ("kda/conv", "kda/gate", "mla/latent")})
    return per_step_ms(ctx, seconds)
