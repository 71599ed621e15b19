"""Kernels: the least time the chip could take for the doubly gated
convolutions of a step, whatever implements them (``B``, ``C`` and
``x`` in and ``y`` out ONE forward pass, the cotangent, ``B``, ``C``
and ``x`` in and three cotangents out one backward pass, float32 as
the mixer states: ``lib/counts_lfm2.py``; bytes over peak HBM bytes/s,
the operator has no matrix product), over the time under
``shortconv/conv``.  The block's checkpoint runs the forward pass a
second time; that pass is not required work, so a step that makes it
reads at most 11/15 of what its kernels reach."""

from benchmarks.lib.harness import log
from benchmarks.lib.scopes_step import path_ms


def read(ctx):
    ms = path_ms(ctx, "shortconv/conv")
    required = ctx["facts"].get("shortconv_bytes")
    if ms is None or required is None or ctx["peaks"] is None:
        return None
    least_ms = 1e3 * required / ctx["peaks"]["hbm_bytes_per_s"]
    log("shortconv.conv_roofline", least_ms=f"{least_ms:.3f}",
        bound="memory")
    return 100 * least_ms / ms
