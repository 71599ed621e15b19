"""Mamba-2's state-space recurrence, chunked (the SSD form of
arXiv:2405.21060, sections 5-7).

A head keeps a matrix state ``S`` (``P x N``: head width by state size,
zero at the start of a sequence); every token decays it by ONE scalar a
head, adds the token's outer product and reads it::

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T        a < 0,  dt_t > 0
    y_t = S_t C_t

``B`` and ``C`` come in ``G`` groups: head ``j`` reads group
``j // (H / G)``.  The skip ``D x_t`` is the layer's, not the op's.

:func:`ssd_recurrent` is that, a token at a time: the yardstick of the
tests.  :func:`ssd_chunked` is what the model runs, as XLA batched
products: chunks of ``CHUNK`` tokens, and with ``cum`` the running sum
of ``dt a`` from the CHUNK'S OWN START (float32, inclusive)

- ``ssm.intra``   ``Y = ((C B^T) . L . dt_s) X`` with ``L_ts = exp(cum_t -
  cum_s)`` for ``s <= t`` and 0 above the diagonal,
- ``ssm.states``  what the chunk adds to the state by its end:
  ``sum_s exp(cum_end - cum_s) dt_s x_s B_s^T``,
- ``ssm.carry``   ``S_k = exp(cum_end) S_{k-1} + that``: the only
  sequential part, ``T / CHUNK`` steps of an elementwise update,
- ``ssm.inter``   what the state at the chunk's start gives each token:
  ``exp(cum_t) S_{k-1} C_t``.

**Every exponent is a difference of running sums that is <= 0** and
nothing is divided by a decay; the pairs above the diagonal, whose
difference is positive and can pass what a float32 exponent holds, are
set to ``-inf`` before the exponential and not masked after it.

Backward is autodiff.  The sequence is cut into slabs of ``SLAB``
chunks whose body is under ``jax.checkpoint`` (``ops/recurrent.py``):
the backward pass keeps the state at each slab's start
(``ssm/state_bytes_kept``) and a slab's inputs; a slab's ``L`` and
masked products (``C x C`` a chunk and head) and its chunks' states
live only while that slab is differentiated.

No Pallas kernel: importing this module adds no kernel to trace or
lower to any step.  Trace-time counters (``utils.metrics`` registry, a
call): ``ssm/chunks`` (chunks a sequence) and ``ssm/state_bytes_kept``.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from chainermn_tpu.ops.recurrent import scan_slabs, slab_size
from chainermn_tpu.utils.metrics import get_registry
from chainermn_tpu.utils.telemetry import device_scope

__all__ = ["ssd_chunked", "ssd_recurrent"]

_HI = lax.Precision.HIGHEST

# The two sizes are the op's own and no caller's: a shorter sequence is
# one shorter chunk, and a test reaches several slabs through T.
CHUNK = 128  # tokens a chunk (the published ``chunk_size``)
SLAB = 2     # chunks differentiated at a time.  Read on the chip: a
# layer's forward and backward at 2 x 8,192 tokens and 64 heads of 64
# took 15.8 ms at 1 and at 2, 19.3 at 4, 29.4 at 8 and 32.2 at 16 and 64
# (forward alone the same at each; PERF.md, PR 40)


def ssd_recurrent(x, dt, a, B, C):
    """The recurrence a token at a time.  ``x`` ``(b, T, H, P)``, ``dt``
    ``(b, T, H)``, ``a`` ``(H,)``, ``B`` and ``C`` ``(b, T, G, N)``.
    Returns ``y`` ``(b, T, H, P)`` in the inputs' widest float dtype
    (float64 where the caller enabled it)."""
    dtype = jnp.result_type(x.dtype, jnp.float32)
    x, dt, a, B, C = (v.astype(dtype) for v in (x, dt, a, B, C))
    b, T, H, P = x.shape
    rep = H // B.shape[2]

    def step(S, inp):
        x_t, dt_t, B_t, C_t = inp
        B_t, C_t = (jnp.repeat(v, rep, axis=1) for v in (B_t, C_t))
        S = jnp.exp(dt_t * a)[..., None, None] * S \
            + (dt_t[..., None] * x_t)[..., None] * B_t[..., None, :]
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_t, precision=_HI)

    S0 = jnp.zeros((b, H, P, B.shape[-1]), dtype)
    _, y = lax.scan(step, S0, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)


def _slab(S, xs):
    """One slab of ``n`` chunks of ``c`` tokens from the state ``S``
    ``(b, H, P, N)``: ``x`` ``(b, H, n, c, P)``, ``dt`` and ``dA``
    (``dt a``) ``(b, H, n, c)``, ``B`` and ``C`` ``(b, G, n, c, N)``.
    Returns the state after the slab and ``y`` ``(b, H, n, c, P)``."""
    x, dt, dA, B, C = xs
    b, H, n, c, P = x.shape
    G, N = B.shape[1], B.shape[-1]
    by_group = lambda v: v.reshape(b, G, H // G, *v.shape[2:])
    # a scan's and a checkpoint's body start a name stack of their own:
    # the recurrence's scope is named here again, so that a child is
    # never the first name an op wears
    with device_scope("ssm/scan"):
        with device_scope("ssm.intra"):
            cum = jnp.cumsum(dA, axis=-1)
            below = jnp.tril(jnp.ones((c, c), bool))
            L = jnp.exp(jnp.where(
                below, cum[..., :, None] - cum[..., None, :], -jnp.inf))
            pairs = jnp.einsum("bgntk,bgnsk->bgnts", C, B)
            weights = (pairs[:, :, None] * by_group(L)).reshape(
                b, H, n, c, c) * dt[..., None, :]
            y = weights @ x
        with device_scope("ssm.states"):
            to_end = jnp.exp(cum[..., -1:] - cum) * dt
            added = jnp.einsum(
                "bgjncp,bgnck->bgjnpk", by_group(x * to_end[..., None]), B
            ).reshape(b, H, n, P, N)
            decay = jnp.exp(cum[..., -1])

        def chunk(S, part):
            # (a scan's body: both names again)
            with device_scope("ssm/scan"), device_scope("ssm.carry"):
                added_k, decay_k = part
                return decay_k[..., None, None] * S + added_k, S

        S, at_start = lax.scan(chunk, S, (
            jnp.moveaxis(added, 2, 0), jnp.moveaxis(decay, 2, 0)))
        with device_scope("ssm.inter"):
            read = jnp.einsum(
                "bgjnpk,bgnck->bgjncp",
                by_group(jnp.moveaxis(at_start, 0, 2)), C
            ).reshape(b, H, n, c, P)
            y = y + jnp.exp(cum)[..., None] * read
    return S, y


def ssd_chunked(x, dt, a, B, C):
    """:func:`ssd_recurrent` in chunks (module docstring): the same
    arguments and result, float32 inside whatever the inputs' dtype.
    ``T`` divides by ``CHUNK`` (or is one shorter chunk); the largest
    divisor of the chunk count that is at most ``SLAB`` is
    differentiated at a time."""
    f32 = jnp.float32
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    chunk = min(CHUNK, T)
    if T % chunk or H % G:
        raise ValueError(
            f"a sequence of {T} is not whole chunks of {chunk}, or "
            f"{H} heads are not whole groups of {G}")
    n_chunks = T // chunk
    slab = slab_size(n_chunks, SLAB)
    n_slabs = n_chunks // slab
    reg = get_registry()
    reg.inc("ssm/chunks", n_chunks)
    reg.inc("ssm/state_bytes_kept", n_slabs * b * H * P * N * 4)

    def slabs(v):
        # (b, T, heads, ...) -> (slabs, b, heads, chunks a slab, chunk, ...)
        v = v.astype(f32).reshape(
            b, n_slabs, slab, chunk, v.shape[2], *v.shape[3:])
        return jnp.moveaxis(v, 4, 2).swapaxes(0, 1)

    dt = dt.astype(f32)
    xs = tuple(slabs(v) for v in (x, dt, dt * a.astype(f32), B, C))
    # the carry takes its varying mesh axes from the inputs
    S0 = jnp.zeros((b, H, P, N), f32) + jnp.sum(xs[0][0] * 0)
    # (slabs, b, H, chunks, chunk, P) -> (b, T, H P) -> (b, T, H, P): what
    # a block's checkpoint keeps is named with the channels flat, as the
    # layer's gate and norm read it; a head of half a lane tile would be
    # kept tokens-minor and turned there (scan_slabs)
    return scan_slabs(_slab, S0, xs, lambda y: jnp.moveaxis(
        y.swapaxes(0, 1), 2, 4).reshape(b, T, H * P)).reshape(b, T, H, P)
