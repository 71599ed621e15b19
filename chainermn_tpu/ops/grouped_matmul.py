"""The experts' grouped products as Pallas kernels of this repo's own:
``rows[g's rows] @ w[g]`` for consecutive groups of rows, and both of
its backward passes.

What ``lax.ragged_dot`` lowers to on a TPU is the compiler's kernel and
nobody's to tune; read on a v5e it ran the Mellum cell's products at a
sixth of their roofline (PERF.md section 6, PR 47).  The kernels here
follow the grouped matmul JAX ships
(``jax.experimental.pallas.ops.tpu.megablox``: the rows in tiles, a tile
that straddles groups visited once a group, the groups' bounds
prefetched as scalars) and differ where the chip's readings said to:

- the tiles are a rule of the shapes that the chip's readings wrote
  (:func:`tile_sizes`) and the VMEM a kernel asks for is counted from
  them (``vmem_limit_bytes``; the
  compiler's default of 16 MiB refuses a whole expert's weights);
- a tile that lies whole inside its group, which most do, is stored or
  multiplied as it is: only a tile that straddles a bound pays for the
  masks;
- the visits are counted with compares and two running sums over the
  groups (no ``repeat``, no histogram), a handful of small fusions a
  call, and the grid stops at the last visit: the rows past the last
  group cost nothing and come back undefined.

One module: :func:`grouped_matmul` with a ``custom_vjp``.  Forward
``rows (R, K) x w (G, K, N)``; the rows' backward is the same kernel
with ``w`` transposed in its index map (no transposed copy in HBM); the
weights' backward is the transposed grouped product
``rows^T x dy -> (G, K, N)``, which leaves the rows outside a group out
BY THE GROUP'S BOUNDS and never by a product with zero, so what lies
past the last group may be anything, NaN included, and an empty group's
``dw`` is exact zeros.  Operands as they come (bfloat16 in the cells),
float32 accumulation in VMEM, results in the operands' dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.ops.kernel_common import LANE, VMEM_BUDGET
from chainermn_tpu.parallel._compat import pcast

__all__ = ["grouped_matmul", "tile_sizes"]

# What the tiles of one kernel may fill: the pipeline's double buffers,
# the float32 accumulator and the product before it is added.  Half of
# what a kernel may ask for; the other half is the compiler's own.
_TILE_BYTES = VMEM_BUDGET // 2


def _gmm_bytes(tm, tk, tn, wire, k_steps):
    """VMEM of the forward kernel's tiles: rows, weights and result
    double-buffered, the product in float32 (twice where K is walked in
    steps: the accumulator)."""
    return (2 * wire * (tm * tk + tk * tn + tm * tn)
            + 4 * tm * tn * (2 if k_steps > 1 else 1))


def _tgmm_bytes(tm, tk, tn, wire):
    """VMEM of the weights' backward: both row tiles and the result
    double-buffered, the float32 accumulator, and a straddling tile's
    masked copies."""
    return (2 * wire * (tm * tk + tm * tn + tk * tn) + 4 * tk * tn
            + (4 + wire) * tm * (tk + tn))


def _split(width: int, steps: int) -> int:
    """``width`` in ``steps`` tiles of whole lanes (the last may be
    short)."""
    if steps == 1:
        return width
    return pl.cdiv(pl.cdiv(width, steps), LANE) * LANE


def tile_sizes(R: int, G: int, K: int, N: int, wire: int = 2):
    """``(tm, tk, tn)`` for ``R`` rows in ``G`` groups against
    ``(K, N)`` weights of ``wire`` bytes an element: one rule for the
    forward product, for the rows' backward (called with ``K`` and ``N``
    swapped) and for the weights' backward.

    Rows: 128, whatever ``R / G``.  A bound between two groups costs
    one more visit of a whole tile, and a larger tile bought nothing on
    the chip at any of the five cells' shapes (a v5e, PERF.md section
    6, PR 47: Mellum's layer of 16 groups of 2,040 rows 13.5 ms at 128,
    13.6 at 256, 14.1 at 512, 16.0 at 1,024; the four cells of 320 to
    800 rows a group within 3 % between 128 and 256 and 9 to 25 % worse
    at 512), while a kernel's body, which Mosaic unrolls, and with it
    the step's compile and its executable grow with the tile.  So
    ``G`` decides nothing yet: a reading at groups of other sizes
    lands here.  ``N`` whole and ``K`` whole where the tiles fit VMEM,
    which they do in every cell (the widest, Nemotron's 2,688 x 1,920,
    counts 45 MiB of the 50 for its weights' backward): a group's
    weights then cross the HBM once a product, and halving either cost
    Mellum 4 to 30 % a product.  Else ``N`` is split down to 512 lanes,
    then ``K`` walked in steps.  No cell runs that path, only the tests
    and one chip reading; it is kept for the public widths that
    ``lax.ragged_dot`` served and whole weights cannot: DeepSeek-V3's
    experts (7,168 x 2,048) take ``N`` in tiles of 512, Mixtral-8x7B's
    (4,096 x 14,336) ``N`` in tiles of 1,280 up and ``K`` in two steps
    of 7,168 down."""
    tm = min(128, R)
    n_steps = k_steps = 1

    def fits():
        tk, tn = _split(K, k_steps), _split(N, n_steps)
        return max(_gmm_bytes(tm, tk, tn, wire, k_steps),
                   _tgmm_bytes(tm, tk, tn, wire)) <= _TILE_BYTES

    while not fits() and _split(N, n_steps) > 512:
        n_steps += 1
    while not fits() and _split(K, k_steps) > LANE:
        k_steps += 1
    return tm, _split(K, k_steps), _split(N, n_steps)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _visits(group_sizes, R: int, tm: int, every_group: bool):
    """What the grid walks: ``(bounds, group, tile, n)``.  Visit ``v <
    n`` multiplies row tile ``tile[v]`` for group ``group[v]``, whose
    rows are ``[bounds[g], bounds[g + 1])``; a tile that holds rows of
    several groups is visited once for each, consecutively.  A group of
    no rows has no visit, or with ``every_group`` one (its result has to
    be written).  Static length ``tiles + G - 1``, the most there can
    be.

    Jitted and NOT inlined, unlike the kernels: a call site then traces
    and lowers one call where it would these thirty small ops again, at
    each of a step's 96 to 144 sites (read in the sandbox, PERF.md
    section 6, PR 47: inlined they were half of what the kernels add to
    tracing and lowering Laguna's step).  What they run is a few small
    fusions, which wear the first site's op name; the compiler inlines
    the calls and shares what sites with one ``group_sizes`` repeat."""
    G, tiles = group_sizes.shape[0], pl.cdiv(R, tm)
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    count = jnp.where(sizes > 0, (ends + tm - 1) // tm - first,
                      1 if every_group else 0)
    v_end = jnp.cumsum(count)
    v = jnp.arange(tiles + G - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.sum(v[:, None] >= v_end[None, :], axis=1, dtype=jnp.int32),
        G - 1)
    tile = jnp.clip(first[group] + v - (v_end - count)[group], 0, tiles - 1)
    bounds = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return bounds, group, tile, v_end[-1]


def _in_group(bounds, group, tile, v, tm):
    """For visit ``v``: whether its tile lies whole inside its group,
    and which of the tile's rows ``(tm, 1)`` are the group's."""
    g = group[v]
    lo, hi, top = bounds[g], bounds[g + 1], tile[v] * tm
    row = top + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (lo <= top) & (top + tm <= hi), (row >= lo) & (row < hi)


def _gmm_kernel(bounds, group, tile, x_ref, w_ref, out_ref, *acc,
                tm, tk, K, transpose_w):
    v, k, k_steps = pl.program_id(1), pl.program_id(2), pl.cdiv(K, tk)
    x, w = x_ref[...], w_ref[...]
    if K % tk:
        # the last step's tile reaches past K: what lies there is not
        # the operands'
        left = K - k * tk
        x = jnp.where(lax.broadcasted_iota(jnp.int32, x.shape, 1) < left,
                      x, jnp.zeros_like(x))
        w = jnp.where(lax.broadcasted_iota(
            jnp.int32, w.shape, 1 if transpose_w else 0) < left,
            w, jnp.zeros_like(w))
    part = lax.dot_general(
        x, w, (((1,), (1 if transpose_w else 0,)), ((), ())),
        preferred_element_type=jnp.float32)

    def store(y):
        whole, mine = _in_group(bounds, group, tile, v, tm)

        @pl.when(whole)
        def _():
            out_ref[...] = y.astype(out_ref.dtype)

        @pl.when(jnp.logical_not(whole))
        def _():
            # the block stays in VMEM between a tile's visits: the
            # other groups' rows are kept as they were left
            out_ref[...] = jnp.where(
                mine, y, out_ref[...].astype(jnp.float32)
            ).astype(out_ref.dtype)

    if k_steps == 1:
        store(part)
        return
    acc_ref, = acc

    @pl.when(k == 0)
    def _():
        acc_ref[...] = part

    @pl.when(k > 0)
    def _():
        acc_ref[...] += part

    @pl.when(k == k_steps - 1)
    def _():
        store(acc_ref[...])


def _tgmm_kernel(bounds, group, tile, x_ref, dy_ref, out_ref, acc_ref, *, tm):
    v, n = pl.program_id(2), pl.num_programs(2)
    g = group[v]
    opens = (v == 0) | (group[jnp.maximum(v - 1, 0)] != g)
    closes = (v == n - 1) | (group[jnp.minimum(v + 1, n - 1)] != g)
    whole, mine = _in_group(bounds, group, tile, v, tm)

    def add(x, dy):
        acc_ref[...] += lax.dot_general(
            x, dy, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(opens)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(whole)
    def _():
        add(x_ref[...], dy_ref[...])

    @pl.when(jnp.logical_not(whole) & (bounds[g + 1] > bounds[g]))
    def _():
        # by the bounds and on both sides: a row outside the group may
        # hold anything, and 0 * NaN is NaN
        x, dy = x_ref[...], dy_ref[...]
        add(jnp.where(mine, x, jnp.zeros_like(x)),
            jnp.where(mine, dy, jnp.zeros_like(dy)))

    @pl.when(closes)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _vma(*arrays):
    return frozenset().union(*(jax.typeof(a).vma for a in arrays))


def _params(need: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=min(VMEM_BUDGET, max(32 * 2 ** 20, 2 * need)))


# Each call under a ``jax.jit`` that is inlined where it is bound, as
# ``ops/recurrent.py`` has it: a layer binds each product at both of
# its buffer's rungs, forward, under the block's remat and backward, and
# the jit's cache serves every bind after the first of a shape while
# each site keeps its own scope and phase in its op name.
def _once(*static):
    return functools.partial(jax.jit, inline=True, static_argnames=static)


@_once("tiles", "transpose_w", "interpret")
def _gmm(rows, w, group_sizes, *, tiles, transpose_w, interpret):
    """``rows (R, K)`` times ``w[g]`` a group: ``w`` is ``(G, K, N)``,
    or ``(G, N, K)`` with ``transpose_w``."""
    (R, K), (tm, tk, tn) = rows.shape, tiles
    N = w.shape[1] if transpose_w else w.shape[2]
    k_steps, wire = pl.cdiv(K, tk), rows.dtype.itemsize
    bounds, group, tile, n = _visits(group_sizes, R, tm, every_group=False)
    if transpose_w:
        w_spec = pl.BlockSpec(
            (None, tn, tk), lambda j, v, k, b, g, t: (g[v], j, k))
    else:
        w_spec = pl.BlockSpec(
            (None, tk, tn), lambda j, v, k, b, g, t: (g[v], k, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tk=tk, K=K,
                          transpose_w=transpose_w),
        out_shape=jax.ShapeDtypeStruct(
            (R, N), rows.dtype, vma=_vma(rows, w, group_sizes)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda j, v, k, b, g, t: (t[v], k)),
                w_spec],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, v, k, b, g, t: (t[v], j)),
            grid=(pl.cdiv(N, tn), n, k_steps),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]
            * (k_steps > 1)),
        compiler_params=_params(_gmm_bytes(tm, tk, tn, wire, k_steps)),
        cost_estimate=pl.CostEstimate(
            flops=2 * R * K * N, transcendentals=0,
            bytes_accessed=wire * (R * K * pl.cdiv(N, tn) + w.size + R * N)),
        name="grouped_matmul_rows" if transpose_w else "grouped_matmul",
        interpret=interpret)(bounds, group, tile, rows, w)


@_once("tiles", "interpret")
def _tgmm(rows, dy, group_sizes, *, tiles, interpret):
    """``rows[g's rows]^T (K, .) x dy[g's rows] (., N)`` a group:
    ``(G, K, N)``."""
    (R, K), N, G = rows.shape, dy.shape[1], group_sizes.shape[0]
    (tm, tk, tn), wire = tiles, rows.dtype.itemsize
    bounds, group, tile, n = _visits(group_sizes, R, tm, every_group=True)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct(
            (G, K, N), rows.dtype, vma=_vma(rows, dy, group_sizes)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda j, k, v, b, g, t: (t[v], k)),
                pl.BlockSpec((tm, tn), lambda j, k, v, b, g, t: (t[v], j))],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda j, k, v, b, g, t: (g[v], k, j)),
            grid=(pl.cdiv(N, tn), pl.cdiv(K, tk), n),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=_params(_tgmm_bytes(tm, tk, tn, wire)),
        cost_estimate=pl.CostEstimate(
            flops=2 * R * K * N, transcendentals=0,
            bytes_accessed=wire * (R * K * pl.cdiv(N, tn)
                                   + R * N * pl.cdiv(K, tk) + G * K * N)),
        name="grouped_matmul_weights",
        interpret=interpret)(bounds, group, tile, rows, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped(rows, w, group_sizes, tiles, interpret):
    return _gmm(rows, w, group_sizes, tiles=tiles, transpose_w=False,
                interpret=interpret)


def _grouped_fwd(rows, w, group_sizes, tiles, interpret):
    return _grouped(rows, w, group_sizes, tiles, interpret), \
        (rows, w, group_sizes)


def _grouped_bwd(tiles, interpret, kept, dy):
    rows, w, group_sizes = kept
    tm, tk, tn = tiles
    d_rows = _gmm(dy, w, group_sizes, tiles=(tm, tn, tk), transpose_w=True,
                  interpret=interpret)
    dw = _tgmm(rows, dy, group_sizes, tiles=tiles, interpret=interpret)
    return d_rows, dw, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(rows, w, group_sizes, *, tiles=None, interpret=False):
    """``rows[g's rows] @ w[g]`` for consecutive groups of rows:
    ``rows`` ``(R, K)`` sorted by group, ``w`` ``(G, K, N)`` of the
    same dtype, ``group_sizes`` ``(G,)`` integers that are not negative
    and sum to ``R`` at most.  ``(R, N)`` in ``rows``' dtype, each
    product accumulated in float32.

    The rows past ``sum(group_sizes)`` are read by nothing, and come
    back UNDEFINED in the result and in the rows' cotangent (whatever
    the output's buffer held: mask them by a ``where``, never by a
    product).  The weights' cotangent leaves them out by the groups'
    bounds, so they may hold anything, and a group of no rows gets
    zeros.

    ``tiles`` ``(tm, tk, tn)`` overrides :func:`tile_sizes` (a test's, a
    reading's); ``interpret`` runs the kernels in the Pallas
    interpreter, off the TPU."""
    (R, K), N = rows.shape, w.shape[2]
    if tiles is None:
        tiles = tile_sizes(R, w.shape[0], K, N, rows.dtype.itemsize)
    # under shard_map every operand varies over the axes any does, as
    # AD's own rules have it: the weights are retyped OUTSIDE the
    # custom_vjp, so their cotangent is summed over the axes the rows
    # vary on and they do not by that cast's transpose
    over = tuple(_vma(rows, w, group_sizes))
    rows, w, group_sizes = (pcast(a, over, to="varying")
                            for a in (rows, w, group_sizes))
    return _grouped(rows, w, group_sizes, tuple(tiles), interpret)
