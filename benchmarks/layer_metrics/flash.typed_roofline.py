"""Kernels: the least time the chip could take for the attention cores
of a step, each layer at its own kind's length (window or full) and
with key-value tensors at the key-value heads' width, summed over the
kinds (for each, the larger of required FLOPs over peak FLOP/s and
required bytes over peak HBM bytes/s), over the time the flash kernels
under ``attn/`` took."""

from benchmarks.lib.harness import log
from benchmarks.lib.scopes import scope_seconds


def read(ctx):
    seconds = scope_seconds(ctx, "attn/",
                            among=set(ctx["facts"].get("kernels", ())))
    required = ctx["facts"].get("flash_typed_flops_bytes")
    if seconds is None or required is None or ctx["peaks"] is None:
        return None
    least = 0.0
    for kind, (flops, nbytes) in sorted(required.items()):
        by_compute = flops / ctx["peaks"]["flops_per_s"]
        by_memory = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
        log("flash.typed_roofline", kind=kind,
            bound="compute" if by_compute >= by_memory else "memory",
            least_ms=f"{1e3 * max(by_compute, by_memory):.3f}")
        least += max(by_compute, by_memory)
    return 100 * least * ctx["window"].iterations / seconds
