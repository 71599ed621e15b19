"""Short-convolution layer: the part of ``shortconv.ms_per_step`` under
``shortconv/conv``: the gate ``B``, the causal depthwise convolution of
3 taps and the gate ``C`` (``ops/recurrent.py`` ``gated_short_conv``,
whatever implements them: one Pallas kernel a pass, or the plain
slices), forward, recomputed forward and backward, device 0."""

from benchmarks.lib.scopes_step import path_ms


def read(ctx):
    return path_ms(ctx, "shortconv/conv")
