"""Host feed: the converter (the stack to one array per field) and the
batch policy: the ``feed/convert`` spans that began in the iteration,
summed; median over the window's iterations."""

from benchmarks.lib.host_share import per_iteration_ms


def read(ctx):
    return per_iteration_ms(ctx, "feed/convert")
