"""Host feed: what ``next(iterator)`` costs an iteration (dataset
indexing, the list of examples): the ``feed/pull`` spans that began in
it, summed; median over the window's iterations."""

from benchmarks.lib.host_share import per_iteration_ms


def read(ctx):
    return per_iteration_ms(ctx, "feed/pull")
