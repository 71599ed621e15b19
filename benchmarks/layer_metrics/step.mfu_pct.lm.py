"""Step program, decoder cells: required FLOPs per step (6 x matmul
parameters a token, tied head once, position table not; causal
attention at half of T^2; nothing recomputed) over chips x peak x the
time the step program ran on the device, from the trace."""

from benchmarks.lib.readings import mfu_pct as read  # noqa: F401
