"""Step program: device self time a step, device 0, of batch-norm's
reductions (scope ``bn/stats``: mean and mean square over the batch,
the variance, the running statistics, and their backward)."""

from benchmarks.lib.scopes_step import path_ms


def read(ctx):
    return path_ms(ctx, "bn/stats")
