"""Step program: device time a step, device 0, of the dense MLP of the
layers that lead (scope ``mlp/dense``: three products and the
activation, forward, recomputed forward and backward)."""

from benchmarks.lib.readings import per_step_ms
from benchmarks.lib.scopes_mixed import scope_seconds


def read(ctx):
    seconds = scope_seconds(ctx, "mlp/dense")
    return None if seconds is None else per_step_ms(ctx, seconds)
