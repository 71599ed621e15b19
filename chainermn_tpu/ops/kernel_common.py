"""What every Pallas kernel of this package shares: the platform a
program is being traced for, and the chip's two numbers its tiles are
counted against."""

from __future__ import annotations

import contextvars
import functools

import jax

__all__ = ["LANE", "VMEM_BUDGET", "interpret_kernels", "tracing_for_mesh"]

LANE = 128  # TPU lane width: the trailing dim of a vector tile

# What one kernel may ask of the v5e's 128 MiB of VMEM
# (``vmem_limit_bytes``; without it the compiler's scoped default is 16).
VMEM_BUDGET = 100 * 2 ** 20

# Platform of the devices the program being traced was built for; set
# by :func:`tracing_for_mesh` around a shard_map body.  ``None`` outside
# one: the process default backend decides.
_TRACE_PLATFORM = contextvars.ContextVar(
    "chainermn_tpu_trace_platform", default=None)


def tracing_for_mesh(mesh, fn):
    """Wrap ``fn`` (a ``shard_map`` body over ``mesh``) so kernels traced
    inside it compile for the platform of ``mesh``'s devices, not for
    the process's default backend — a step built on TPU devices holds
    the compiled kernel whatever ``jax.default_backend()`` says."""
    platform = mesh.devices.flat[0].platform

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        token = _TRACE_PLATFORM.set(platform)
        try:
            return fn(*args, **kwargs)
        finally:
            _TRACE_PLATFORM.reset(token)

    return traced


def interpret_kernels() -> bool:
    """True when Pallas kernels traced now must run in the interpreter:
    the target platform (see :func:`tracing_for_mesh`) is not a TPU."""
    return (_TRACE_PLATFORM.get() or jax.default_backend()) != "tpu"
