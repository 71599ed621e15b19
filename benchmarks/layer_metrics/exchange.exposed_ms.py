"""Exchange: the part of ``exchange.total_ms`` during which no other
operation ran on that device -- the only part that can move the rate."""

from benchmarks.lib.readings import per_step_ms


def read(ctx):
    if ctx["trace"] is None:
        return None
    return per_step_ms(
        ctx, ctx["trace"]["devices"][0]["collective_exposed_s"])
