"""Kernels: the least time the chip could take for the grouped expert
products of a step (the larger of required FLOPs over peak FLOP/s and
required bytes over peak HBM bytes/s, at the rows the program's
``expert_load`` counted) over the time the grouped-matmul kernels took
(the ``ragged-dot`` instructions and whatever else computes under
``moe/experts``: the activation between the products).  An earlier line
of the run says which of the two bounds."""

from benchmarks.lib.harness import log
from benchmarks.lib.scopes import scope_seconds


def read(ctx):
    seconds = scope_seconds(ctx, "moe/experts")
    required = ctx["facts"].get("expert_flops_bytes")
    if seconds is None or required is None or ctx["peaks"] is None:
        return None
    flops, nbytes = required
    by_compute = flops / ctx["peaks"]["flops_per_s"]
    by_memory = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    log("moe.experts_roofline", bound="compute" if by_compute >= by_memory
        else "memory", least_ms=f"{1e3 * max(by_compute, by_memory):.3f}",
        rows_a_step=f"{ctx['facts']['expert_rows']:.0f}")
    return 100 * max(by_compute, by_memory) * ctx["window"].iterations \
        / seconds
