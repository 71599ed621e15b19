"""Kernels: the least time the chip could take for the RECURRENCE of
the Gated DeltaNet layers in a step, whatever implements it (a token a
value head three products of 2 x 128 x 128 forward and twice that
backward; q, k, v, g, beta in and o out once a pass, float32, three
passes: ``lib/counts_gdn.py``; the larger of FLOPs over peak FLOP/s and
bytes over peak HBM bytes/s), over the time under ``gdn/scan``: a later
kernel is read against the same work, as ``kda.scan_roofline`` is."""

from benchmarks.lib.harness import log
from benchmarks.lib.scopes_step import path_ms


def read(ctx):
    ms = path_ms(ctx, "gdn/scan")
    required = ctx["facts"].get("gdn_scan_flops_bytes")
    if ms is None or required is None or ctx["peaks"] is None:
        return None
    by_compute = required[0] / ctx["peaks"]["flops_per_s"]
    by_memory = required[1] / ctx["peaks"]["hbm_bytes_per_s"]
    least_ms = 1e3 * max(by_compute, by_memory)
    log("gdn.scan_roofline", least_ms=f"{least_ms:.3f}",
        bound="compute" if by_compute >= by_memory else "memory")
    return 100 * least_ms / ms
