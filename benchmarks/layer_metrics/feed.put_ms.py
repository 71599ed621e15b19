"""Host feed: ``put_window`` (the owned-buffer copy, the window-level
stack, the ``device_put`` calls): the ``feed/put`` spans that began in
the iteration, summed; median over the window's iterations."""

from benchmarks.lib.host_share import per_iteration_ms


def read(ctx):
    return per_iteration_ms(ctx, "feed/put")
