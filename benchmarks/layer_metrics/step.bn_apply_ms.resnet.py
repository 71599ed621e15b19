"""Step program: device self time a step, device 0, of batch-norm's
normalisation (scope ``bn/apply``: scale, shift and the cast back, and
their backward)."""

from benchmarks.lib.scopes_step import path_ms


def read(ctx):
    return path_ms(ctx, "bn/apply")
