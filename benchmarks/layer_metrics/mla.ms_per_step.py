"""Latent-attention layer: device self time a step, device 0, of
everything under ``attn/mla`` (the query and latent projections, the
copy of the shared key part out to the heads, the flash kernels, the
output projection; forward, recomputed forward and backward)."""

from benchmarks.lib.readings import per_step_ms
from benchmarks.lib.scopes import scope_seconds


def read(ctx):
    seconds = scope_seconds(ctx, "attn/mla")
    return None if seconds is None else per_step_ms(ctx, seconds)
