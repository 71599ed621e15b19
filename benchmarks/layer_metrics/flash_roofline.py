"""Kernels: the least time the chip could take for what the flash
kernels have to do in a step (the larger of required FLOPs over peak
FLOP/s and required bytes over peak HBM bytes/s) over the time they
took.  An earlier line of the run says which of the two bounds."""

from benchmarks.lib.harness import log
from benchmarks.lib.readings import kernel_seconds

KERNELS = "pallas_call"


def read(ctx):
    seconds = kernel_seconds(ctx, KERNELS)
    if seconds is None or ctx["peaks"] is None:
        return None
    flops, nbytes = ctx["facts"]["flash_flops_bytes"]
    by_compute = flops / ctx["peaks"]["flops_per_s"]
    by_memory = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    log("flash_roofline", bound="compute" if by_compute >= by_memory
        else "memory", least_ms=f"{1e3 * max(by_compute, by_memory):.3f}")
    return 100 * max(by_compute, by_memory) * ctx["window"].iterations \
        / seconds
