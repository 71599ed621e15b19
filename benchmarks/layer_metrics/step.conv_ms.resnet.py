"""Step program: device self time a step, device 0, of the
convolutions (scope ``resnet/conv``: the weight's cast and the
convolution, forward and backward; a fusion counts under its root, so a
convolution's fusion carries what the compiler fused into it)."""

from benchmarks.lib.scopes_step import path_ms


def read(ctx):
    return path_ms(ctx, "resnet/conv")
