"""Host feed (iterator, converter, host-to-device copy): the median
``step/host`` span of the updater inside the window."""

import numpy as np

from benchmarks.lib.readings import span_ms


def read(ctx):
    spans = span_ms(ctx, "step/host")
    return float(np.median(spans)) if spans else None
