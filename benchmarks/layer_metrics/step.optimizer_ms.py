"""Step program: device self time a step, device 0, of the optimizer's
update and its application to the parameters (scope
``step/optimizer``)."""

from benchmarks.lib.scopes_step import path_ms


def read(ctx):
    return path_ms(ctx, "step/optimizer")
