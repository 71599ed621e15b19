"""Kernels: device time a step of the flash forward and backward Pallas
kernels of the sliding-attention layers (scope ``attn/sliding``), device
0; the forward's second run under remat counts here, its operations do
not count as required."""

from benchmarks.lib.readings import per_step_ms
from benchmarks.lib.scopes import scope_seconds


def read(ctx):
    seconds = scope_seconds(ctx, "attn/sliding",
                            among=set(ctx["facts"].get("kernels", ())))
    return None if seconds is None else per_step_ms(ctx, seconds)
