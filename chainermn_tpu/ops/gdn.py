"""Gated DeltaNet's recurrence, chunked (Gated Delta Networks,
arXiv:2412.06464, section 3): the delta rule of ``ops/kda.py`` with the
decay ONE scalar a head, and fewer key heads than value heads.

A value head keeps a matrix state ``S`` (``d_k x d_v``, zero at the
start of a sequence); every token decays all of it by one number,
applies the delta rule and reads it::

    S_t = e^{g_t} S_{t-1} - b_t k_t (k_t^T e^{g_t} S_{t-1}) + b_t k_t v_t^T
    o_t = S_t^T q_t                                        g_t <= 0

``q`` and ``k`` come in ``H_k`` key heads and ``v``, ``g`` and ``b`` in
``H_v`` value heads: value head ``j`` reads key head ``j // (H_v /
H_k)``.  :func:`gdn_recurrent` is that, a token at a time: the yardstick
of the tests.  :func:`gdn_chunked` is what the model runs: chunks of
``CHUNK`` tokens in the WY/UT form ``ops/kda.py``'s docstring derives.
With ``G_t`` the running sum of ``g`` inside the chunk, a scalar a row,

    (I + Diag(b) A) U = Diag(b) (V - (K e^G) S_0)
    A  = (K K^T) . D  strictly below the diagonal,   D_ti = e^{G_t - G_i}
    A' = (Q K^T) . D  on and below it
    O = (Q e^G) S_0 + A' U,   S_C = e^{G_C} S_0 + (K e^{G_C - G})^T U

Because the decay is a scalar, ``K K^T`` and ``Q K^T`` are ONE product a
chunk and KEY head on the MXU (``gdn.pairs``), and ``D`` is a ``C x C``
mask a chunk and VALUE head, the shape ``ops/ssd.py``'s intra-chunk
term has: no pair-by-pair weight a channel exists in any pass (KDA's
decay is a vector over the key channels, which is what its two Pallas
kernels are for).  ``b``, ``G``, ``D`` and the system are a value
head's.  **Every exponent is a difference of running sums that is <=
0** and nothing is divided by a decay; the pairs above the diagonal,
whose difference is positive, are set to ``-inf`` before the
exponential and not masked after it.

The unit lower triangular systems are ``ops/kda.py``'s :func:`solve` as
it stands (``kda.solve``: ``T = (I + Diag(b) A)^-1`` by float32
substitution in its Pallas kernel, the systems on the lanes, then ``T
R`` at ``Precision.HIGHEST`` for the two right-hand sides ``Diag(b) V``
and ``Diag(b) K e^G``; its VJP inverts nothing again).  That kernel is
the only one here; everything else is XLA's batched products at the
chip's default precision over float32 arrays.  Between chunks a
``lax.scan`` carries ``S`` (``gdn.inter``: what meets the state;
``gdn.intra``: ``A' U``).  The sequence is cut into slabs of ``SLAB``
chunks whose body is under ``jax.checkpoint`` (``ops/recurrent.py``):
the backward pass keeps the state at each slab's start
(``gdn/state_bytes_kept``) and a slab's inputs.

Trace-time counters (``utils.metrics`` registry, a call):
``gdn/chunks`` (chunks a sequence), ``gdn/state_bytes_kept`` and
``gdn/systems_inverted`` (systems a pass: a chunk and value head each).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from chainermn_tpu.ops.kda import solve
from chainermn_tpu.ops.recurrent import scan_slabs, slab_size
from chainermn_tpu.utils.metrics import get_registry
from chainermn_tpu.utils.telemetry import device_scope

__all__ = ["gdn_chunked", "gdn_recurrent"]

_HI = lax.Precision.HIGHEST

# The two sizes are the op's own and no caller's: a shorter sequence is
# one shorter chunk, and a test reaches several slabs through T.
CHUNK = 64   # tokens a chunk (the published kernel's, and KDA's)
SLAB = 4     # chunks differentiated at a time (KDA's, read on the chip
# there: PERF.md, PR 32)


def gdn_recurrent(q, k, v, g, beta):
    """The recurrence a token at a time.  ``q``, ``k`` ``(B, T, H_k,
    d_k)``, ``v`` ``(B, T, H_v, d_v)``, ``g`` and ``beta`` ``(B, T,
    H_v)``; ``g <= 0`` is the log of the decay.  Returns ``o`` ``(B, T,
    H_v, d_v)`` in float32."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    B, T, Hk, dk = k.shape
    Hv = v.shape[2]

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x                   # (B, heads, ...)
        q_t, k_t = (jnp.repeat(y, Hv // Hk, axis=1) for y in (q_t, k_t))
        S = jnp.exp(g_t)[..., None, None] * S
        u = b_t[..., None] * (v_t - jnp.einsum(
            "bhkv,bhk->bhv", S, k_t, precision=_HI))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=_HI)

    S0 = jnp.zeros((B, Hv, dk, v.shape[-1]), f32)
    _, o = lax.scan(step, S0, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _chunk_parts(q, k, v, g, beta):
    """What a chunk gives the scan over the states, none of it a
    function of a state: ``(U_v, W, Q_g, A', K_end, decay_C)`` with
    ``U = U_v - W S_0``.  ``q``, ``k`` ``(B, H_k, 1, N, C, d_k)``, the
    others ``(B, H_k, rep, N, C[, d_v])``: a key head's ``rep`` value
    heads beside it."""
    C = k.shape[-2]
    # a scan's and a checkpoint's body start a name stack of their own:
    # the recurrence's scope is named here again, so that a child is
    # never the first name an op wears
    with device_scope("gdn/scan"), device_scope("gdn.pairs"):
        G = jnp.cumsum(g, axis=-1)                  # (B, Hk, rep, N, C)
        # one product a chunk and key head for both pair matrices
        pairs = jnp.einsum("...tc,...ic->...ti",
                           jnp.concatenate([k, q], axis=-2), k)
        below = jnp.tril(jnp.ones((C, C), bool))
        D = jnp.exp(jnp.where(
            below, G[..., :, None] - G[..., None, :], -jnp.inf))
        A = jnp.tril(pairs[..., :C, :] * D, -1)
        A_q = pairs[..., C:, :] * D
    e_G = jnp.exp(G)[..., None]
    dv = v.shape[-1]
    with device_scope("gdn/scan"), device_scope("kda.solve"):
        rhs = beta[..., None] * jnp.concatenate([v, k * e_G], axis=-1)
        solved = solve(beta[..., None] * A, rhs)
    G_end = G[..., -1:]
    return (solved[..., :dv], solved[..., dv:], q * e_G, A_q,
            k * jnp.exp(G_end - G)[..., None], jnp.exp(G_end[..., 0]))


def _slab(S, xs):
    """One slab of chunks from the state ``S`` ``(B, H_k, rep, d_k,
    d_v)``: ``xs`` as :func:`_chunk_parts` takes them.  Returns the
    state after the slab and ``o`` ``(B, H_k, rep, N, C, d_v)``."""
    parts = _chunk_parts(*xs)

    def chunk(S, part):
        u_v, w, q_g, a_q, k_end, decay = part
        # (a scan's body: both names again)
        with device_scope("gdn/scan"), device_scope("gdn.inter"):
            u = u_v - w @ S
            o = q_g @ S
        with device_scope("gdn/scan"), device_scope("gdn.intra"):
            o = o + a_q @ u
        with device_scope("gdn/scan"), device_scope("gdn.inter"):
            S = decay[..., None, None] * S + jnp.swapaxes(k_end, -1, -2) @ u
        return S, o

    S, o = lax.scan(chunk, S, tuple(jnp.moveaxis(p, 3, 0) for p in parts))
    return S, jnp.moveaxis(o, 0, 3)


def gdn_chunked(q, k, v, g, beta):
    """:func:`gdn_recurrent` in chunks (module docstring): the same
    arguments and result, float32 inside whatever the inputs' dtype.
    ``T`` divides by ``CHUNK`` (or is one shorter chunk) and the value
    heads are whole groups a key head; the largest divisor of the chunk
    count that is at most ``SLAB`` is differentiated at a time."""
    f32 = jnp.float32
    B, T, Hk, dk = k.shape
    Hv, dv = v.shape[2:]
    chunk = min(CHUNK, T)
    if T % chunk or Hv % Hk:
        raise ValueError(
            f"a sequence of {T} is not whole chunks of {chunk}, or {Hv} "
            f"value heads are not whole groups of {Hk} key heads")
    rep = Hv // Hk
    n_chunks = T // chunk
    slab = slab_size(n_chunks, SLAB)
    n_slabs = n_chunks // slab
    reg = get_registry()
    reg.inc("gdn/chunks", n_chunks)
    reg.inc("gdn/state_bytes_kept", n_slabs * B * Hv * dk * dv * 4)
    reg.inc("gdn/systems_inverted", B * Hv * n_chunks)

    def slabs(x, group):
        # (B, T, heads, ...) -> (slabs, B, H_k, group, chunks a slab,
        # chunk, ...): a key head's value heads on an axis of their own
        x = x.astype(f32).reshape(
            B, n_slabs, slab, chunk, Hk, group, *x.shape[3:])
        return jnp.moveaxis(x, (4, 5), (2, 3)).swapaxes(0, 1)

    xs = (slabs(q, 1), slabs(k, 1), slabs(v, rep), slabs(g, rep),
          slabs(beta, rep))
    # the carry takes its varying mesh axes from the inputs
    S0 = jnp.zeros((B, Hk, rep, dk, dv), f32) \
        + jnp.sum(xs[1][0] * 0) + jnp.sum(xs[2][0] * 0)
    # (slabs, B, H_k, rep, chunks, chunk, dv) -> (B, T, H_v, dv)
    return scan_slabs(_slab, S0, xs, lambda o: jnp.moveaxis(
        o.swapaxes(0, 1), (2, 3), (4, 5)).reshape(B, T, Hv, dv))
