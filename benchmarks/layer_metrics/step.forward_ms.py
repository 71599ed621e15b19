"""Step program: device self time a step, device 0, of the ops JAX
traced as the forward pass (``jvp(`` and no ``transpose(`` in the op
name the trace carries; ``lib/scopes_step.py``)."""

from benchmarks.lib.scopes_step import phase_ms


def read(ctx):
    return phase_ms(ctx, "forward")
