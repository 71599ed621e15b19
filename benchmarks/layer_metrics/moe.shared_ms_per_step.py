"""Expert layer: device time a step, device 0, of the expert every
token meets (scope ``moe/shared``: its three dense products and its
activation, forward, recomputed forward and backward).  It is not part
of ``moe.ms_per_step``, which reads the routed layer's three scopes."""

from benchmarks.lib.readings import per_step_ms
from benchmarks.lib.scopes_mixed import scope_seconds


def read(ctx):
    seconds = scope_seconds(ctx, "moe/shared")
    return None if seconds is None else per_step_ms(ctx, seconds)
