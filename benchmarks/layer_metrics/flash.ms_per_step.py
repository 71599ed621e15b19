"""Kernels: device time of the flash forward and backward Pallas
kernels per step, device 0 (the forward runs again in the backward pass
under full remat; that time counts here, its operations do not count as
required)."""

from benchmarks.lib.readings import kernel_seconds, per_step_ms

KERNELS = "pallas_call"     # every Pallas kernel of the step is flash's


def read(ctx):
    seconds = kernel_seconds(ctx, KERNELS)
    return None if seconds is None else per_step_ms(ctx, seconds)
