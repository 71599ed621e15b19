"""Kimi Delta Attention's recurrence, chunked (arXiv:2510.26692, §3).

A head keeps a matrix state ``S`` (``d_k x d_v``, zero at the start of a
sequence) and every token decays it a channel, applies the delta rule
and reads it::

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t                      a_t = exp(g_t),  g_t <= 0

:func:`kda_recurrent` is that, a token at a time: the yardstick of the
tests.  :func:`kda_chunked` is what the model runs: chunks of ``CHUNK``
tokens, inside a chunk the WY/UT form.  With ``G_t`` the running sum of
``g`` inside the chunk, ``u_t = b_t (v_t - S_{t-1}^T (a_t . k_t))`` and
``S_t = Diag(a_t) S_{t-1} + k_t u_t^T``, so for the chunk's ``C`` rows

    (I + Diag(b) A) U = Diag(b) (V - (K . exp(G)) S_0),
    A_ti = sum_c k_tc k_ic exp(G_tc - G_ic)   (i < t, else 0)
    O = (Q . exp(G)) S_0 + A' U,   A'_ti = sum_c q_tc k_ic exp(G_tc - G_ic)  (i <= t)
    S_C = Diag(exp(G_C)) S_0 + (K . exp(G_C - G))^T U

The unit lower triangular system is solved once a chunk for the two
right-hand sides ``Diag(b) V`` and ``Diag(b) (K . exp(G))``, which no
state enters (:func:`solve`): ``T = (I + Diag(b) A)^-1`` by float32
substitution in a Pallas kernel, then one product ``T R`` at
``Precision.HIGHEST`` for both right-hand sides.  The kernel lays the
SYSTEMS on the lanes, 128 to a vector (a slab step of 32 heads and 4
chunks is exactly one): XLA's own kernel for the solve took the 32
heads for its lanes and a quarter of the vector unit did the work.  A
count that is no multiple of 128 is padded inside.  Between chunks a
``lax.scan`` carries ``S`` through three products of chunk-sized
blocks.

**Every exponent is a difference of running sums that is <= 0**, and
nothing is divided by a decay: with decays drawn as published a chunk's
running sum passes -200 and ``exp(-G)`` is not a float32.  ``A`` and
``A'`` are therefore built from sub-blocks of ``SUB`` rows, as the
published kernel does: a pair of rows of the same sub-block takes its
own exponent ``G_t - G_i`` (masked to the pairs that are read before
``exp``); a row of sub-block ``a`` meets the rows of earlier sub-blocks
through the reference point ``R_a``, the running sum just before ``a``,
as ``(k_t . exp(G_t - R_a)) . (k_i . exp(R_a - G_i))``, both factors
<= 1.  A product of two such factors that underflows is a pair whose
true weight is below float32 too.

Backward is autodiff through the chunked form, except through the
solve, whose ``jax.custom_vjp`` keeps ``T`` and ``X`` and inverts
nothing again: ``dR = T^T dX`` (``HIGHEST``) and ``dN`` the strictly
lower part of ``-dR X^T``, where autodiff through a triangular solve
would solve a second time.  The sequence is cut into slabs of ``SLAB``
chunks; the outer scan over the slabs has its body under
``jax.checkpoint``, so what is kept for the backward pass is the state
at each slab's start (``kda/state_bytes_kept``) and a slab's own
inputs; a slab's pair-by-pair exponents (``SUB`` times the size of its
keys), its ``T`` and its ``X`` live only while that slab is
differentiated.  A layer inverts its systems once in the forward pass
and once when the slab is recomputed (and once more where the block's
own checkpoint reruns the layer).

Trace-time counters (``utils.metrics`` registry, a call): ``kda/chunks``
(chunks a sequence), ``kda/state_bytes_kept`` and
``kda/systems_inverted`` (systems a pass: a chunk and head each).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.ops.pallas_attention import interpret_kernels
from chainermn_tpu.utils.metrics import get_registry
from chainermn_tpu.utils.telemetry import device_scope

__all__ = ["kda_chunked", "kda_recurrent"]

_HI = lax.Precision.HIGHEST

# The three sizes are the op's own and no caller's: a shorter sequence
# is one shorter chunk, and a test reaches several slabs through T.
CHUNK = 64   # tokens a chunk (the published kernel's)
SUB = 16     # rows a sub-block of the pair weights
SLAB = 4     # chunks differentiated at a time.  Read on the chip: a
# layer's forward and backward at 16,384 tokens and 32 heads of 128
# took 157 ms at 4 against 212 at 16 and 193 at 64 (PERF.md, PR 32)


def kda_recurrent(q, k, v, g, beta):
    """The recurrence a token at a time.  ``q``, ``k``, ``g``
    ``(B, T, H, d_k)``, ``v`` ``(B, T, H, d_v)``, ``beta`` ``(B, T, H)``;
    ``g <= 0`` is the log of the decay.  Returns ``o`` ``(B, T, H, d_v)``
    in float32."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    B, T, H, dk = k.shape

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x                   # (B, H, ...)
        S = jnp.exp(g_t)[..., None] * S
        u = b_t[..., None] * (v_t - jnp.einsum(
            "bhkv,bhk->bhv", S, k_t, precision=_HI))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=_HI)

    S0 = jnp.zeros((B, H, dk, v.shape[-1]), f32)
    _, o = lax.scan(step, S0, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _pair_weights(q, k, G, g, sub):
    """``(A, A')`` of a chunk (module docstring): ``q``, ``k``, ``G``,
    ``g`` ``(..., C, d_k)``, results ``(..., C, C)``; ``A`` strictly
    lower triangular, ``A'`` with its diagonal."""
    C, dk = k.shape[-2:]
    n = C // sub
    lead = k.shape[:-2]
    by_sub = lambda x: x.reshape(*lead, n, sub, dk)
    qs, ks, Gs = by_sub(q), by_sub(k), by_sub(G)
    # R_a: the running sum just before sub-block a
    ref = Gs[..., 0, :] - by_sub(g)[..., 0, :]                # (..., n, dk)
    row = jnp.exp(Gs - ref[..., None, :])                     # <= 1
    before = jnp.arange(C)[None, :] < (jnp.arange(n) * sub)[:, None]
    col = jnp.exp(jnp.where(
        before[..., None],
        ref[..., :, None, :] - G[..., None, :, :], -jnp.inf))  # (..., n, C, dk)
    kcol = k[..., None, :, :] * col
    off_kk = jnp.einsum("...atc,...aic->...ati", ks * row, kcol)
    off_qk = jnp.einsum("...atc,...aic->...ati", qs * row, kcol)
    # the pairs inside a sub-block, each with its own exponent
    t, i = jnp.arange(sub)[:, None], jnp.arange(sub)[None, :]
    pair = jnp.exp(jnp.where(
        (t >= i)[..., None],
        Gs[..., :, None, :] - Gs[..., None, :, :], -jnp.inf))
    in_kk = jnp.where(t > i, jnp.einsum(
        "...atc,...aic,...atic->...ati", ks, ks, pair), 0.0)
    in_qk = jnp.einsum("...atc,...aic,...atic->...ati", qs, ks, pair)
    own = jnp.eye(n, dtype=k.dtype)[:, None, :, None]         # (n, 1, n, 1)

    def whole(off, inside):
        # sub-block a's rows: the columns before it, and its own
        placed = inside[..., :, :, None, :] * own             # (n, sub, n, sub)
        return (off.reshape(*lead, n, sub, n, sub) + placed).reshape(
            *lead, C, C)

    return whole(off_kk, in_kk), whole(off_qk, in_qk)


_LANES = 128     # systems a vector: one a lane


def _inverse_kernel(n_ref, t_ref):
    """``(I + N)^-1`` of ``_LANES`` systems side by side: ``n_ref`` and
    ``t_ref`` ``(C, C, _LANES)``, row, column, system.  Row by row
    ``T[i] = e_i - sum_{j<i} N[i, j] T[j]``, ``T[j]`` a ``(C, _LANES)``
    tile and ``N[i, j]`` a lane vector spread over it: float32
    multiplies and subtractions on the vector unit.  The diagonal of
    ``N`` and what is above it are never read."""
    C = n_ref.shape[0]
    column = lax.broadcasted_iota(jnp.int32, (C, _LANES), 0)

    def row(i, carry):
        def term(j, acc):
            return acc - n_ref[i, pl.ds(j, 1), :] * t_ref[j]

        t_ref[i] = lax.fori_loop(
            0, i, term, (column == i).astype(jnp.float32))
        return carry

    # a traced bound: with a static one the loop is a scan, whose carry
    # check the interpreter trips under shard_map's varying axes
    lax.fori_loop(jnp.int32(0), C, row, 0)


def _inverse(N):
    """``(I + N)^-1`` for ``N`` ``(systems, C, C)`` strictly lower
    triangular (what is on and above the diagonal is ignored), in
    float32 on the vector unit, ``_LANES`` systems a kernel step."""
    S, C, _ = N.shape
    padded = -(-S // _LANES) * _LANES
    # the systems go on the lanes; a padded lane inverts the identity
    by_lane = jnp.pad(jnp.moveaxis(N, 0, -1),
                      ((0, 0), (0, 0), (0, padded - S)))
    block = pl.BlockSpec((C, C, _LANES), lambda s: (0, 0, s))
    T = pl.pallas_call(
        _inverse_kernel, grid=(padded // _LANES,),
        in_specs=[block], out_specs=block,
        out_shape=jax.ShapeDtypeStruct(
            by_lane.shape, jnp.float32, vma=jax.typeof(N).vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret_kernels())(by_lane)
    return jnp.moveaxis(T[..., :S], -1, 0)


@jax.custom_vjp
def solve(N, R):
    """``X`` of ``(I + N) X = R`` for ``N`` ``(..., C, C)``, of which
    only the part strictly below the diagonal is read, and ``R``
    ``(..., C, n)``: the systems are inverted once, side by side on the
    lanes in float32, and the inverse is applied at full precision.
    The backward pass applies the same inverse transposed and inverts
    nothing."""
    return _solve_fwd(N, R)[0]


def _solve_fwd(N, R):
    C = N.shape[-1]
    T = _inverse(N.reshape(-1, C, C)).reshape(N.shape)
    X = jnp.matmul(T, R, precision=_HI)
    return X, (T, X)


def _solve_bwd(kept, dX):
    T, X = kept
    dR = jnp.matmul(jnp.swapaxes(T, -1, -2), dX, precision=_HI)
    dN = -jnp.tril(jnp.matmul(
        dR, jnp.swapaxes(X, -1, -2), precision=_HI), -1)
    return dN, dR


solve.defvjp(_solve_fwd, _solve_bwd)


def _chunk_parts(q, k, v, g, beta, sub):
    """What a chunk gives the scan over the states, none of it a
    function of a state: ``(U_v, W, Q_g, A', K_end, decay_C)`` with
    ``U = U_v - W S_0``."""
    with device_scope("kda.pairs"):
        G = jnp.cumsum(g, axis=-2)
        A, A_q = _pair_weights(q, k, G, g, sub)
    dv = v.shape[-1]
    with device_scope("kda.solve"):
        rhs = beta[..., None] * jnp.concatenate(
            [v, k * jnp.exp(G)], axis=-1)
        solved = solve(beta[..., None] * A, rhs)
    G_end = G[..., -1:, :]
    return (solved[..., :dv], solved[..., dv:], q * jnp.exp(G), A_q,
            k * jnp.exp(G_end - G), jnp.exp(G_end[..., 0, :]))


def _slab(S, xs, sub):
    """One slab of chunks from the state ``S`` ``(B, H, d_k, d_v)``:
    ``xs`` are ``q, k, v, g, beta`` as ``(B, H, N, C, ...)``.  Returns
    the state after the slab and ``o`` ``(B, H, N, C, d_v)``."""
    parts = _chunk_parts(*xs, sub)

    def chunk(S, part):
        u_v, w, q_g, a_q, k_end, decay = part
        # what meets the state (inter-chunk) and the chunk's own pairs
        # (intra-chunk), each under its name
        with device_scope("kda.inter"):
            u = u_v - w @ S
            o = q_g @ S
        with device_scope("kda.intra"):
            o = o + a_q @ u
        with device_scope("kda.inter"):
            S = decay[..., None] * S + jnp.swapaxes(k_end, -1, -2) @ u
        return S, o

    S, o = lax.scan(chunk, S, tuple(jnp.moveaxis(p, 2, 0) for p in parts))
    return S, jnp.moveaxis(o, 0, 2)


def kda_chunked(q, k, v, g, beta):
    """:func:`kda_recurrent` in chunks (module docstring): the same
    arguments and result, float32 inside whatever the inputs' dtype.
    ``T`` divides by ``CHUNK`` (or is one shorter chunk that divides by
    ``SUB``); the largest divisor of the chunk count that is at most
    ``SLAB`` is differentiated at a time."""
    f32 = jnp.float32
    B, T, H, dk = k.shape
    dv = v.shape[-1]
    chunk = min(CHUNK, T)
    sub = min(SUB, chunk)
    if T % chunk or chunk % sub:
        raise ValueError(
            f"a sequence of {T} is not whole chunks of {chunk} in "
            f"sub-blocks of {sub}")
    n_chunks = T // chunk
    slab = min(SLAB, n_chunks)
    while n_chunks % slab:
        slab -= 1
    n_slabs = n_chunks // slab
    reg = get_registry()
    reg.inc("kda/chunks", n_chunks)
    reg.inc("kda/state_bytes_kept", n_slabs * B * H * dk * dv * 4)
    reg.inc("kda/systems_inverted", B * H * n_chunks)

    def slabs(x):
        # (B, T, H, ...) -> (slabs, B, H, chunks a slab, chunk, ...)
        x = x.astype(f32).reshape(B, n_slabs, slab, chunk, H, *x.shape[3:])
        return jnp.moveaxis(x, 4, 2).swapaxes(0, 1)

    xs = tuple(slabs(x) for x in (q, k, v, g, beta))
    # the carry takes its varying mesh axes from the inputs
    S0 = jnp.zeros((B, H, dk, dv), f32) + jnp.sum(xs[1][0] * 0)
    _, o = lax.scan(jax.checkpoint(lambda S, x: _slab(S, x, sub)), S0, xs)
    # (slabs, B, H, chunks, chunk, dv) -> (B, T, H, dv)
    return jnp.moveaxis(o.swapaxes(0, 1), 2, 4).reshape(B, T, H, dv)
