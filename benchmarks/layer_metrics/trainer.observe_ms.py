"""Entry point: every extension's per-iteration ``observe`` hook, where
``LogReport`` reads ``float(loss)`` and so where a serial loop waits
for the device: the ``trainer/observe`` span; median over the window's
iterations."""

from benchmarks.lib.host_share import per_iteration_ms


def read(ctx):
    return per_iteration_ms(ctx, "trainer/observe")
