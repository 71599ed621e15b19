"""Step program, ResNet cells: required FLOPs per step (analytic,
forward + backward, nothing recomputed) over chips x peak x the time
the step program ran on the device, from the trace."""

from benchmarks.lib.readings import mfu_pct as read  # noqa: F401
