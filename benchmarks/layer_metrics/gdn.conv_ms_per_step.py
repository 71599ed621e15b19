"""Linear-attention layer: the part of ``gdn.ms_per_step`` under
``gdn/conv``: the causal depthwise convolution of 4 taps over q, k and
v, the SiLU, the split and the L2 norms of q and k (forward, recomputed
forward and backward), device 0.  The same op as the Kimi cell's
``kda/conv`` and the Nemotron cell's ``ssm/conv`` (``ops/recurrent.py``
``causal_conv_silu``)."""

from benchmarks.lib.scopes_step import path_ms


def read(ctx):
    return path_ms(ctx, "gdn/conv")
