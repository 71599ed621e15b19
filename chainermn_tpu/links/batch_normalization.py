"""Cross-replica (synchronised) batch normalisation.

TPU-native analogue of ``MultiNodeBatchNormalization`` (reference:
``chainermn/links/batch_normalization.py`` + its FunctionNode impl;
unverified — mount empty, see SURVEY.md).

The reference computed batch statistics with an explicit allreduce inside
``forward`` and a matching hand-written allreduce in ``backward`` so that
small per-GPU batches still normalise over the *global* batch.  Here the
statistics are ``lax.pmean``s over the data-parallel mesh axis inside the
(traced) forward; the backward collective falls out of autodiff — ``pmean``
carries its own transpose rule, so no hand-written backward exists at all.

Functional, like everything in this package: parameters and running
statistics are explicit pytrees; ``train=False`` uses running stats and
touches no collective (inference needs no communication, matching the
reference's use of ``chainer.using_config('train', False)``).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax.numpy as jnp
from jax import lax

from chainermn_tpu.utils.telemetry import device_scope

__all__ = [
    "BatchNormState",
    "init_batch_norm",
    "multi_node_batch_normalization",
]


class BatchNormState(NamedTuple):
    """Running statistics (the reference's ``avg_mean``/``avg_var`` persistent
    values — see also ``extensions.AllreducePersistentValues`` which averages
    these across ranks before evaluation/checkpoint)."""

    mean: jnp.ndarray
    var: jnp.ndarray
    n: jnp.ndarray  # update counter (reference kept ``N`` for lr of stats)


def init_batch_norm(size: int, dtype=jnp.float32):
    """Returns ``(params, state)`` for a ``size``-channel BN layer."""
    params = {
        "gamma": jnp.ones((size,), dtype),
        "beta": jnp.zeros((size,), dtype),
    }
    state = BatchNormState(
        mean=jnp.zeros((size,), dtype),
        var=jnp.ones((size,), dtype),
        n=jnp.zeros((), jnp.int32),
    )
    return params, state


def multi_node_batch_normalization(
    params,
    state: BatchNormState,
    x,
    axis_name: Optional[str] = None,
    *,
    eps: float = 2e-5,
    decay: float = 0.9,
    train: bool = True,
):
    """Normalise ``x`` over batch (and any spatial) dims with statistics
    averaged across ``axis_name``.

    Args:
      x: ``(batch, ..., channels)`` — channels last; all leading dims are
        reduced (NHWC conv activations or (batch, features) both work).
      axis_name: data-parallel mesh axis; ``None`` degenerates to local BN
        (what the reference did when ``comm.size == 1``).
      train: use (and update) batch statistics vs. running statistics.

    Returns ``(y, new_state)``; ``new_state is state`` when ``train=False``.
    """
    gamma, beta = params["gamma"], params["beta"]
    reduce_axes = tuple(range(x.ndim - 1))
    # Statistics and the normalisation math run in fp32 regardless of the
    # activation dtype: E[x²]−E[x]² cancels catastrophically in bf16 (can
    # go negative → NaN rsqrt), and fp32 gamma/beta would otherwise
    # silently promote the output.  The result is cast back to x.dtype so
    # a bf16 model stays bf16 through the conv stack.
    x32 = x.astype(jnp.float32)

    if not train:
        with device_scope("bn/apply"):
            inv = lax.rsqrt(state.var + eps) * gamma
            return (x32 * inv + (beta - state.mean * inv)).astype(
                x.dtype), state

    # Global batch statistics: local moments, then mean over the mesh axis.
    # (Mean-of-means is exact because every device holds the same local
    # batch size — the same assumption the reference's allreduce/size made.)
    # The two passes over the activations, each under its name: the
    # reductions (``bn/stats``) and the normalisation (``bn/apply``).
    with device_scope("bn/stats"):
        mean = jnp.mean(x32, axis=reduce_axes)
        sq_mean = jnp.mean(jnp.square(x32), axis=reduce_axes)
        if axis_name is not None:
            mean = lax.pmean(mean, axis_name)
            sq_mean = lax.pmean(sq_mean, axis_name)
        var = sq_mean - jnp.square(mean)

    with device_scope("bn/apply"):
        inv = lax.rsqrt(var + eps) * gamma
        y = (x32 * inv + (beta - mean * inv)).astype(x.dtype)

    # Running stats with the reference's unbiased-variance correction.
    m = x.size // x.shape[-1]
    if axis_name is not None:
        m = m * lax.axis_size(axis_name)
    adjust = m / max(m - 1.0, 1.0)
    with device_scope("bn/stats"):
        new_state = BatchNormState(
            mean=decay * state.mean + (1.0 - decay) * mean,
            var=decay * state.var + (1.0 - decay) * var * adjust,
            n=state.n + 1,
        )
    return y, new_state
