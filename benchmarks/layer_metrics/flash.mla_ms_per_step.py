"""Kernels: device time a step of the flash forward and backward Pallas
kernels under ``attn/mla`` (keys 192 wide, values 128), device 0."""

from benchmarks.lib.readings import per_step_ms
from benchmarks.lib.scopes import scope_seconds


def read(ctx):
    seconds = scope_seconds(ctx, "attn/mla",
                            among=set(ctx["facts"].get("kernels", ())))
    return None if seconds is None else per_step_ms(ctx, seconds)
