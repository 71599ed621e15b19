"""Step program: device self time a step, device 0, of the backward
pass: the transposed ops (``transpose(`` in the op name) and what
``jax.checkpoint`` runs again for them (``rematted_computation``); an
earlier line of the run gives the two apart, and what is in no phase
because the compiler named it (the ``ragged-dot`` kernels, its own
copies)."""

from benchmarks.lib.harness import log
from benchmarks.lib.scopes_step import phase_ms


def read(ctx):
    backward = phase_ms(ctx, "backward")
    if backward is None:
        return None
    recompute, update, unnamed = (
        phase_ms(ctx, ph) or 0.0 for ph in ("recompute", "update", "unnamed"))
    log("step.backward_ms", backward=f"{backward:.3f}",
        recompute=f"{recompute:.3f}", update=f"{update:.3f}",
        unnamed=f"{unnamed:.3f}")
    return backward + recompute
