"""Step program: device self time a step, device 0, of the ops that
wear none of the program's scopes.  Also prints the ``[scopes]`` table:
every path of the vocabulary that took time, by phase, ms a step."""

from benchmarks.lib.scopes_step import table, unscoped_ms


def read(ctx):
    table(ctx)
    return unscoped_ms(ctx)
