"""Linear-attention layer: device self time a step, device 0, of
everything under ``attn/kda`` (all the KDA layers: projections, short
convolution, gates, the chunked recurrence, the output gate and
projection; forward, recomputed forward and backward).  By the scope in
each instruction's ``op_name`` (``lib/scopes.py``)."""

from benchmarks.lib.readings import per_step_ms
from benchmarks.lib.scopes import scope_seconds


def read(ctx):
    seconds = scope_seconds(ctx, "attn/kda")
    return None if seconds is None else per_step_ms(ctx, seconds)
