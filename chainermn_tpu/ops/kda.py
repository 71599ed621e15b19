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
own exponent ``G_t - G_i`` (held at 0 for the pairs that are not read,
which are masked out of what is written); a row of sub-block ``a``
meets the rows of earlier sub-blocks through the reference point
``R_a``, the running sum just before ``a``, as ``(k_t . exp(G_t -
R_a)) . (k_i . exp(R_a - G_i))``, both factors <= 1.  A product of two
such factors that underflows is a pair whose true weight is below
float32 too.

**No pair-by-pair weight is stored in HBM in any pass.**  A Pallas
kernel (:func:`_pairs_kernel`) reads ``q``, ``k`` and ``G`` of
``_BLOCKS`` chunk x head blocks a grid step and writes their ``A`` and
``A'``: a sub-block's own pairs are float32 multiplies, exponentials
and a reduction over ``d_k`` on the vector and cross-lane units, a
local column of all the chunk's sub-blocks at a time, and the products
through a reference point go to the MXU at the precision XLA's default
gives a float32 einsum.  Its ``jax.custom_vjp`` keeps ``q``, ``k`` and
``G`` and nothing else; the backward kernel
(:func:`_pairs_vjp_kernel`) makes every weight again in VMEM and gives
``dq``, ``dk`` and ``dG``.  A kernel is traced and lowered to Mosaic
once a call site at every process start, whatever the compile cache
holds, and a step has sixteen sites of this pair, so the bodies are
kept small: a loop over the blocks, inside it ``_COLUMNS`` columns a
loop turn and the three earlier sub-blocks' products written once each
(PERF.md §6, PR 37).

Backward is autodiff through the chunked form, except through the
solve, whose ``jax.custom_vjp`` keeps ``T`` and ``X`` and inverts
nothing again: ``dR = T^T dX`` (``HIGHEST``) and ``dN`` the strictly
lower part of ``-dR X^T``, where autodiff through a triangular solve
would solve a second time.  The sequence is cut into slabs of ``SLAB``
chunks; the outer scan over the slabs has its body under
``jax.checkpoint``, so what is kept for the backward pass is the state
at each slab's start (``kda/state_bytes_kept``) and a slab's own
inputs; what lives only while that slab is differentiated is its
``A`` and ``A'`` (``C x C`` a chunk and head, half the size of its
keys), its ``T`` and its ``X``: the pair-by-pair exponents, ``SUB``
times the size of its keys, exist in no pass outside a kernel's
registers.  A layer makes its pair weights and inverts its systems
once in the forward pass and once when the slab is recomputed, and the
backward kernel makes the weights a third time: a block's checkpoint
that keeps ``RECURRENT_RESIDUAL_NAMES`` (``scan_slabs``; the model's
does) reruns the layer around the scan and not the scan.

Trace-time counters (``utils.metrics`` registry, a call): ``kda/chunks``
(chunks a sequence), ``kda/state_bytes_kept`` and
``kda/systems_inverted`` (systems a pass: a chunk and head each) and
``kda/pair_blocks_in_vmem`` (chunk x head blocks a pass whose pair
weights the kernel makes: every one, so it equals the systems).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.ops.kernel_common import interpret_kernels
from chainermn_tpu.ops.recurrent import scan_slabs, slab_size
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


_LANES = 128     # a vector's lanes
_BLOCKS = 8      # chunk x head blocks a grid step of the pair kernels:
# the backward's tiles, 8 x (3 x 32 + 2 x 16 in, 3 x 32 out) KiB twice
# over for the pipeline, are 3.5 MiB of VMEM


def _mxu(x, interpret):
    """An operand of a cross-sub-block product as XLA's default
    precision hands a float32 one to the MXU: rounded to bfloat16 on
    the chip, float32 in the interpreter, as XLA's CPU backend keeps
    it.  The accumulator is float32 on both.  (Read on the chip, PR
    37: this stands 3e-5 rms of an output's largest entry from the
    jnp form it replaced, ``Precision.HIGHEST`` 3e-4.)"""
    return x if interpret else x.astype(jnp.bfloat16)


def _nt(x, y):
    """``x @ y^T``, float32 out."""
    return lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _own_column(n, rows, sub, j):
    """``(n, rows, n * sub)`` mask of each sub-block's own column ``j``
    in (some of) its rows of a chunk's pair matrix."""
    shape = (n, rows, n * sub)
    return (lax.broadcasted_iota(jnp.int32, shape, 2)
            == lax.broadcasted_iota(jnp.int32, shape, 0) * sub + j)


_COLUMNS = 8     # columns of the own pairs a loop turn (see _own_pairs)


def _own_pairs(refs, b, body, init):
    """The pairs inside the sub-blocks of block ``b``, a local column
    ``j`` of all ``n`` sub-blocks at a time: ``body(j, first, tiles,
    w, decay, acc)`` with ``tiles`` the rows ``first:`` of each of
    ``refs`` ``(n, sub - first, width)`` (``G`` last), ``w`` ``k_j
    exp(G_t - G_j)`` on those rows and ``decay`` the exponential alone;
    where ``t < j`` the exponent is held at 0 and the caller masks what
    it reads.  The columns of a sub-block's upper half meet its lower
    half's rows only, so they run on half the tile; ``acc``'s leaves
    are ``(n, sub, ...)`` and split the same way.

    A column's reductions over the lanes keep the three cross-lane
    units busy for 43 cycles and their results come 35 to 48 cycles
    after they are asked for; only columns of one loop turn overlap, so
    a turn takes ``_COLUMNS`` of them: 77 cycles a column at 1, 60 at
    4, 47 at 8 by the compiler's own schedule (PERF.md §6, PR 37)."""
    k_ref, G_ref = refs[-2:]
    half = G_ref.shape[2] // 2
    columns_a_turn = min(_COLUMNS, half)

    def columns(first, acc):
        tiles = [ref[b, :, first:, :] for ref in refs]
        G = tiles[-1]

        def turn(i, acc):
            for j in range(columns_a_turn):
                j = first + i * columns_a_turn + j
                decay = jnp.exp(jnp.minimum(
                    G - G_ref[b, :, pl.ds(j, 1), :], 0.0))
                w = k_ref[b, :, pl.ds(j, 1), :] * decay
                acc = body(j, first, tiles, w, decay, acc)
            return acc

        return lax.fori_loop(
            jnp.int32(0), half // columns_a_turn, turn, acc)

    acc = columns(0, init)
    lower = columns(half, jax.tree.map(lambda x: x[:, half:], acc))
    return jax.tree.map(
        lambda x, low: jnp.concatenate([x[:, :half], low], axis=1),
        acc, lower)


def _off_factors(G_ref, b, a):
    """Sub-block ``a``'s rows against the columns before it, through
    the reference point ``R_a`` (the running sum of the row just before
    ``a``): ``row`` ``(sub, d_k)`` and ``col`` ``(a, sub, d_k)``, both
    <= 1."""
    sub = G_ref.shape[2]
    ref = G_ref[b, a - 1, sub - 1:, :]                          # (1, d_k)
    return jnp.exp(G_ref[b, a] - ref), jnp.exp(ref - G_ref[b, :a])


def _lower(C, diagonal):
    """``(C, C)`` mask of the pairs that are read: a row's earlier
    columns, and its own for ``A'``."""
    row = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    column = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    return row >= column if diagonal else row > column


def _pairs_kernel(interpret, q_ref, k_ref, G_ref, a_ref, aq_ref):
    """``A`` and ``A'`` of ``_BLOCKS`` chunk x head blocks: ``q_ref``,
    ``k_ref``, ``G_ref`` ``(_BLOCKS, n, sub, d_k)``, the results
    ``(_BLOCKS, C, C)``.  Every pair weight lives in vector registers
    only."""
    _, n, sub, dk = k_ref.shape
    C = n * sub

    def block(b, carry):
        def column(j, first, tiles, w, decay, acc):
            # a reduction over d_k a row, put on the column's lane
            q_rows, k_rows, _ = tiles
            own = _own_column(n, sub - first, sub, j)
            return tuple(
                jnp.where(own, jnp.sum(x * w, -1, keepdims=True), into)
                for x, into in zip((k_rows, q_rows), acc))

        zero = jnp.zeros((n, sub, C), jnp.float32)
        in_kk, in_qk = _own_pairs(
            (q_ref, k_ref, G_ref), b, column, (zero, zero))
        a_ref[b] = jnp.where(_lower(C, False), in_kk.reshape(C, C), 0.0)
        aq_ref[b] = jnp.where(_lower(C, True), in_qk.reshape(C, C), 0.0)

        for a in range(1, n):
            row, col = _off_factors(G_ref, b, a)
            rows = slice(a * sub, (a + 1) * sub)
            off = _nt(_mxu(jnp.concatenate(
                [k_ref[b, a] * row, q_ref[b, a] * row]), interpret),
                _mxu((k_ref[b, :a] * col).reshape(a * sub, dk), interpret))
            a_ref[b, rows, :a * sub] += off[:sub]
            aq_ref[b, rows, :a * sub] += off[sub:]
        return carry

    # traced bounds, as in _inverse_kernel
    lax.fori_loop(jnp.int32(0), k_ref.shape[0], block, 0)


def _pairs_vjp_kernel(interpret, q_ref, k_ref, G_ref, da_ref, daq_ref,
                      dq_ref, dk_ref, dG_ref):
    """The cotangents of ``q``, ``k`` and ``G`` from those of ``A`` and
    ``A'``: every pair weight is made again in vector registers from
    ``q``, ``k`` and ``G``, and the reference points carry no
    cotangent (a product through one does not depend on it)."""
    _, n, sub, width = k_ref.shape
    C = n * sub

    def block(b, carry):
        q, k = q_ref[b], k_ref[b]
        da = jnp.where(_lower(C, False), da_ref[b], 0.0).reshape(n, sub, C)
        daq = jnp.where(_lower(C, True), daq_ref[b], 0.0).reshape(n, sub, C)

        def column(j, first, tiles, w, decay, acc):
            q_rows, k_rows, _ = tiles
            own = _own_column(n, sub - first, sub, j)
            c_kk, c_qk = (
                jnp.sum(jnp.where(own, x[:, first:], 0.0), -1, keepdims=True)
                for x in (da, daq))
            # row j's share, each written once: with m the cotangent
            # of w, dk_j = sum_t m decay and dG_j = -k_j dk_j
            share = jnp.sum(
                (c_kk * k_rows + c_qk * q_rows) * decay, 1, keepdims=True)
            dk_ref[b, :, pl.ds(j, 1), :] = share
            dG_ref[b, :, pl.ds(j, 1), :] = -k_ref[b, :, pl.ds(j, 1), :] * share
            return acc[0] + c_qk * w, acc[1] + c_kk * w

        zero = jnp.zeros((n, sub, width), jnp.float32)
        dq, dk = _own_pairs(
            (q_ref, k_ref, G_ref), b, column, (zero, zero))
        # the rows' shares: dG_t = sum_j m w = k_t dk_t + q_t dq_t
        dq_ref[b] = dq
        dk_ref[b] += dk
        dG_ref[b] += k * dk + q * dq

        for a in range(1, n):
            row, col = _off_factors(G_ref, b, a)
            rows, before = slice(a * sub, (a + 1) * sub), a * sub
            k_a, q_a, k_col = k_ref[b, a], q_ref[b, a], k_ref[b, :a] * col
            d_off = jnp.concatenate(
                [da_ref[b, rows, :before], daq_ref[b, rows, :before]])
            d_lhs = jnp.dot(
                _mxu(d_off, interpret),
                _mxu(k_col.reshape(before, width), interpret),
                preferred_element_type=jnp.float32)
            d_kcol = jnp.dot(
                _mxu(d_off.T, interpret),
                _mxu(jnp.concatenate([k_a * row, q_a * row]), interpret),
                preferred_element_type=jnp.float32).reshape(a, sub, width)
            d_k, d_q = d_lhs[:sub], d_lhs[sub:]
            dk_ref[b, a] += d_k * row
            dq_ref[b, a] += d_q * row
            dG_ref[b, a] += (d_k * k_a + d_q * q_a) * row
            dk_ref[b, :a] += d_kcol * col
            dG_ref[b, :a] -= d_kcol * k_col
        return carry

    lax.fori_loop(jnp.int32(0), k_ref.shape[0], block, 0)


def _pairs_call(kernel, interpret, ins, outs):
    """``kernel`` over the leading axis of ``ins`` in steps of
    ``_BLOCKS``; ``outs`` are the results' trailing shapes."""
    S = ins[0].shape[0]
    spec = lambda shape: pl.BlockSpec(
        (_BLOCKS, *shape), lambda s: (s,) + (0,) * len(shape))
    return pl.pallas_call(
        functools.partial(kernel, interpret), grid=(S // _BLOCKS,),
        in_specs=[spec(x.shape[1:]) for x in ins],
        out_specs=[spec(shape) for shape in outs],
        out_shape=[jax.ShapeDtypeStruct(
            (S, *shape), jnp.float32, vma=jax.typeof(ins[0]).vma)
            for shape in outs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret)(*ins)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pairs(q, k, G, interpret):
    """``(A, A')`` of the blocks ``q``, ``k``, ``G``
    ``(blocks, n, sub, d_k)`` (whole steps of ``_BLOCKS``, whole lane
    tiles), ``(blocks, C, C)`` each.  What the backward pass keeps is
    ``q``, ``k`` and ``G``; ``interpret`` is the forward trace's
    :func:`interpret_kernels` for both kernels."""
    return _pairs_fwd(q, k, G, interpret)[0]


def _pairs_fwd(q, k, G, interpret):
    C = k.shape[1] * k.shape[2]
    return tuple(_pairs_call(
        _pairs_kernel, interpret, (q, k, G), [(C, C)] * 2)), (q, k, G)


def _pairs_bwd(interpret, kept, cts):
    return tuple(_pairs_call(
        _pairs_vjp_kernel, interpret, (*kept, *cts),
        [kept[0].shape[1:]] * 3))


_pairs.defvjp(_pairs_fwd, _pairs_bwd)


def _pair_weights(q, k, G):
    """``(A, A')`` of a chunk (module docstring): ``q``, ``k``, ``G``
    ``(..., C, d_k)``, results ``(..., C, C)``; ``A`` strictly lower
    triangular, ``A'`` with its diagonal.  A chunk shorter than
    ``CHUNK``, a ``d_k`` that is no whole lane tile and a count of
    blocks that is no whole kernel step are padded here: rows that
    decay no further with keys and queries of 0, channels and blocks of
    0."""
    *lead, C, dk = k.shape
    S = math.prod(lead)
    rows, rest = (0, CHUNK - C), ((0, -S % _BLOCKS), (0, 0), (0, -dk % _LANES))

    def blocks(x, mode):
        x = x.reshape(S, C, dk)
        if rows[1]:
            x = jnp.pad(x, ((0, 0), rows, (0, 0)), mode=mode)
        if any(after for _, after in rest):
            x = jnp.pad(x, rest)
        return x.reshape(x.shape[0], CHUNK // SUB, SUB, x.shape[-1])

    A, A_q = _pairs(blocks(q, "constant"), blocks(k, "constant"),
                    blocks(G, "edge"), interpret_kernels())
    return (A[:S, :C, :C].reshape(*lead, C, C),
            A_q[:S, :C, :C].reshape(*lead, C, C))


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


def _chunk_parts(q, k, v, g, beta):
    """What a chunk gives the scan over the states, none of it a
    function of a state: ``(U_v, W, Q_g, A', K_end, decay_C)`` with
    ``U = U_v - W S_0``.  ``A`` and ``A'`` come from
    :func:`_pairs_kernel` through :func:`_pair_weights`, whose VJP
    keeps ``q``, ``k`` and ``G`` only."""
    # a scan's and a checkpoint's body start a name stack of their own:
    # where the kernels are interpreted their reducers' computations
    # carry that stack alone, so the recurrence's scope is named here
    # again and the child is never the first name an op wears
    with device_scope("kda/scan"), device_scope("kda.pairs"):
        G = jnp.cumsum(g, axis=-2)
        A, A_q = _pair_weights(q, k, G)
    dv = v.shape[-1]
    with device_scope("kda.solve"):
        rhs = beta[..., None] * jnp.concatenate(
            [v, k * jnp.exp(G)], axis=-1)
        solved = solve(beta[..., None] * A, rhs)
    G_end = G[..., -1:, :]
    return (solved[..., :dv], solved[..., dv:], q * jnp.exp(G), A_q,
            k * jnp.exp(G_end - G), jnp.exp(G_end[..., 0, :]))


def _slab(S, xs):
    """One slab of chunks from the state ``S`` ``(B, H, d_k, d_v)``:
    ``xs`` are ``q, k, v, g, beta`` as ``(B, H, N, C, ...)``.  Returns
    the state after the slab and ``o`` ``(B, H, N, C, d_v)``."""
    parts = _chunk_parts(*xs)

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
    slab = slab_size(n_chunks, SLAB)
    n_slabs = n_chunks // slab
    reg = get_registry()
    reg.inc("kda/chunks", n_chunks)
    reg.inc("kda/state_bytes_kept", n_slabs * B * H * dk * dv * 4)
    reg.inc("kda/systems_inverted", B * H * n_chunks)
    reg.inc("kda/pair_blocks_in_vmem", B * H * n_chunks)

    def slabs(x):
        # (B, T, H, ...) -> (slabs, B, H, chunks a slab, chunk, ...)
        x = x.astype(f32).reshape(B, n_slabs, slab, chunk, H, *x.shape[3:])
        return jnp.moveaxis(x, 4, 2).swapaxes(0, 1)

    xs = tuple(slabs(x) for x in (q, k, v, g, beta))
    # the carry takes its varying mesh axes from the inputs
    S0 = jnp.zeros((B, H, dk, dv), f32) + jnp.sum(xs[1][0] * 0)
    # (slabs, B, H, chunks, chunk, dv) -> (B, T, H, dv)
    return scan_slabs(_slab, S0, xs, lambda o: jnp.moveaxis(
        o.swapaxes(0, 1), 2, 4).reshape(B, T, H, dv))
