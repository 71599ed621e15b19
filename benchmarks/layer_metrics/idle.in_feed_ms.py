"""Device: idle time of device 0 an iteration that falls, on the tied
clock (``lib/clock.py``), within a ``step/host`` span: the device waits
for its batch.  Median over the window's iterations; the three
``idle.*`` add up to the idle time behind ``device.idle_pct.resnet``."""

from benchmarks.lib.host_share import idle_ms


def read(ctx):
    return idle_ms(ctx, "feed")
