"""Exchange: time per step, device 0, covered by all-gather,
reduce-scatter and all-reduce operations (an asynchronous one from its
start to the end of its done)."""

from benchmarks.lib.readings import per_step_ms


def read(ctx):
    if ctx["trace"] is None:
        return None
    return per_step_ms(ctx, ctx["trace"]["devices"][0]["collective_s"])
