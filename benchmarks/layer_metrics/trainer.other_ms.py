"""Entry point (``Trainer.run`` and its extensions): the median, over
the window's iterations, of the iteration's length less the updater's
``step/host`` + ``step/dispatch`` + ``step/retire`` spans."""

import numpy as np

from benchmarks.lib.readings import outside_step_spans_ms


def read(ctx):
    if not ctx["spans"]:
        return None
    return float(np.median(outside_step_spans_ms(ctx)))
