"""Kernels: the least time the chip could take for the attention cores
of the full-attention layers of a step (kind ``full`` of the driver's
``flash_typed_flops_bytes``: the larger of required FLOPs over peak
FLOP/s and required bytes over peak HBM bytes/s, key-value tensors at
the key-value heads' width), over the time the flash kernels under
``attn/full`` took, which is ``flash.full_ms_per_step``'s.  It counts
no other layer's Pallas calls, so a cell whose other mixers run
kernels of their own under ``attn/`` can read it.  The forward's
second run under remat is in the time and not in the required work.
A program or a cell without the scope or the fact leaves it out."""

from benchmarks.lib.harness import log
from benchmarks.lib.scopes import scope_seconds


def read(ctx):
    seconds = scope_seconds(ctx, "attn/full",
                            among=set(ctx["facts"].get("kernels", ())))
    required = (ctx["facts"].get("flash_typed_flops_bytes") or {}).get("full")
    if not seconds or required is None or ctx["peaks"] is None:
        return None
    flops, nbytes = required
    by_compute = flops / ctx["peaks"]["flops_per_s"]
    by_memory = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    log("flash.full_roofline",
        bound="compute" if by_compute >= by_memory else "memory",
        least_ms=f"{1e3 * max(by_compute, by_memory):.3f}")
    return 100 * max(by_compute, by_memory) * ctx["window"].iterations \
        / seconds
