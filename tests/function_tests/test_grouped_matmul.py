"""The grouped-matmul kernels (``ops/grouped_matmul.py``) in the Pallas
interpreter against ``lax.ragged_dot`` and a plain loop over the groups:
the result, the rows' cotangent and the weights' cotangent, at the edges
the dropless layer meets on the chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from chainermn_tpu.ops import grouped_matmul as gm
from chainermn_tpu.ops.grouped_matmul import grouped_matmul, tile_sizes
from chainermn_tpu.ops.kernel_common import _TRACE_PLATFORM
from chainermn_tpu.parallel.expert import grouped_dense


def _loop(rows, w, sizes):
    """Group by group, a product each; zeros past the last group."""
    parts, start = [], 0
    for g, n in enumerate(sizes):
        parts.append(rows[start:start + n] @ w[g])
        start += n
    parts.append(jnp.zeros((rows.shape[0] - start, w.shape[2]), rows.dtype))
    return jnp.concatenate(parts)


def _operands(R, K, N, G, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(R, K).astype(np.float32)),
            jnp.asarray(rng.randn(G, K, N).astype(np.float32) * K ** -0.5),
            jnp.asarray(rng.randn(R, N).astype(np.float32)))


# R, K, N, the groups' sizes, the tiles (tm, tk, tn), NaN past the end
CASES = {
    "even-groups": (128, 32, 48, [32, 32, 32, 32], (32, 32, 48), False),
    "a-group-of-no-rows": (128, 32, 48, [40, 0, 50, 38], (32, 32, 48), False),
    "first-and-last-group-empty":
        (96, 32, 16, [0, 50, 0, 0, 46, 0], (32, 32, 16), False),
    "nan-past-the-last-group":
        (192, 32, 48, [30, 41, 22], (32, 32, 48), True),
    "nan-and-the-last-group-ends-a-tile":
        (192, 32, 48, [30, 41, 25], (32, 32, 48), True),
    "a-tile-straddles-three-groups":
        (128, 32, 48, [10, 20, 30, 68], (64, 32, 48), False),
    "k-not-whole-tiles-nemotron-2688":
        (96, 336, 24, [40, 30, 26], (32, 128, 24), False),
    "n-in-two-tiles-and-rows-not-whole-tiles":
        (100, 32, 200, [33, 40, 20], (32, 32, 128), True),
    "every-group-empty": (64, 32, 16, [0, 0, 0], (32, 32, 16), True),
    "one-tile-holds-every-group":
        (64, 32, 48, [5, 0, 7, 9], (64, 32, 48), True),
}


@pytest.mark.parametrize("case", CASES)
def test_against_ragged_dot_and_the_loop(case):
    """y, dx over the rows held and every dw against both references.
    Past the last group the kernel's rows and cotangent hold NaN where
    the case says so, the references' zeros: the held rows agree, every
    dw is finite and the loop's, an empty group's dw is exact zeros."""
    R, K, N, sizes, tiles, poison = CASES[case]
    rows, w, dy = _operands(R, K, N, len(sizes))
    n = sum(sizes)
    past = jnp.arange(R)[:, None] >= n
    clean = lambda a: jnp.where(past, 0, a)
    dirty = (lambda a: jnp.where(past, jnp.nan, a)) if poison else clean
    group_sizes = jnp.asarray(sizes, jnp.int32)

    y, pull = jax.vjp(lambda r, w: grouped_matmul(
        r, w, group_sizes, tiles=tiles, interpret=True), dirty(rows), w)
    dx, dw = pull(dirty(dy))
    assert np.isfinite(np.asarray(dw)).all()
    for g, size in enumerate(sizes):
        if size == 0:
            assert not np.asarray(dw[g]).any()

    for reference in (
            lambda r, w: lax.ragged_dot(r, w, group_sizes),
            lambda r, w: _loop(r, w, sizes)):
        y_ref, pull_ref = jax.vjp(reference, clean(rows), w)
        dx_ref, dw_ref = pull_ref(clean(dy))
        for got, ref in ((y, y_ref), (dx, dx_ref)):
            np.testing.assert_allclose(
                np.asarray(got)[:n], np.asarray(ref)[:n],
                rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(dw), np.asarray(dw_ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_gradients_through_the_gated_layer(dtype):
    """``jax.grad`` through ``silu(a) * b @ w2``, the experts' network
    as the layer calls it, against the loop's: rows, and all three
    weights.  In bfloat16 the result comes back in bfloat16 from a
    float32 accumulation, as ``preferred_element_type=rows.dtype``
    gives."""
    R, D, F, sizes = 96, 64, 32, [30, 0, 41, 20]
    rows, w1, _ = _operands(R, D, F, len(sizes))
    _, w3, _ = _operands(R, D, F, len(sizes), seed=1)
    _, w2, ct = _operands(R, F, D, len(sizes), seed=2)
    rows, w1, w3, w2 = (a.astype(dtype) for a in (rows, w1, w3, w2))
    n, group_sizes = sum(sizes), jnp.asarray(sizes, jnp.int32)

    def loss(product, rows, w1, w3, w2):
        out = product(
            jax.nn.silu(product(rows, w1)) * product(rows, w3), w2)
        assert out.dtype == dtype
        held = jnp.arange(R)[:, None] < n
        return jnp.sum(jnp.where(held, out, 0).astype(jnp.float32) * ct)

    kernel = lambda r, w: grouped_matmul(
        r, w, group_sizes, tiles=(32,) + w.shape[1:], interpret=True)
    got = jax.grad(lambda *a: loss(kernel, *a), (0, 1, 2, 3))(
        rows, w1, w3, w2)
    ref = jax.grad(lambda *a: loss(lambda r, w: _loop(r, w, sizes), *a),
                   (0, 1, 2, 3))(rows, w1, w3, w2)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    for g, r in zip(got, ref):
        assert g.dtype == dtype
        g, r = (np.asarray(a.astype(jnp.float32)) for a in (g, r))
        np.testing.assert_allclose(g[:n] if g.shape[0] == R else g,
                                   r[:n] if r.shape[0] == R else r,
                                   rtol=tol, atol=tol * np.abs(r).max())


def test_the_rule_s_own_tiles_run():
    """No ``tiles``: the rule's, here a block of all the rows."""
    R, K, N, sizes = 64, 32, 16, [20, 30]
    rows, w, _ = _operands(R, K, N, len(sizes))
    y = grouped_matmul(rows, w, jnp.asarray(sizes, jnp.int32), interpret=True)
    np.testing.assert_allclose(
        np.asarray(y)[:50], np.asarray(_loop(rows, w, sizes))[:50],
        rtol=2e-5, atol=2e-5)


def test_visits_walk_every_tile_of_every_group_once():
    """The grid's plan: each group's rows covered by consecutive visits
    of whole tiles, a shared tile visited once a group, nothing after
    the last group."""
    sizes, R, tm = [10, 20, 0, 30, 68, 5], 192, 64
    for every_group in (False, True):
        bounds, group, tile, n = (np.asarray(a) for a in gm._visits(
            jnp.asarray(sizes, jnp.int32), R, tm, every_group))
        assert bounds.tolist() == [0, 10, 30, 30, 60, 128, 133]
        assert len(group) == len(tile) == R // tm + len(sizes) - 1
        visits = list(zip(group[:n].tolist(), tile[:n].tolist()))
        assert visits == [(0, 0), (1, 0)] + [(2, 0)] * every_group + [
            (3, 0), (4, 0), (4, 1), (5, 2)]


# the five typed cells' expert layers at their first rung: rows of the
# buffer, held experts, model width, experts' width (Nemotron's padded)
CELLS = {
    "mellum": (65536, 16, 2304, 896),
    "laguna": (32768, 32, 2048, 512),
    "kimi": (8192, 8, 2304, 1024),
    "nemotron": (12288, 8, 2688, 1920),
    "qwen3-next": (20480, 32, 2048, 512),
}
TILES = {
    "mellum": ((128, 2304, 896), (128, 896, 2304)),
    "laguna": ((128, 2048, 512), (128, 512, 2048)),
    "kimi": ((128, 2304, 1024), (128, 1024, 2304)),
    "nemotron": ((128, 2688, 1920), (128, 1920, 2688)),
    "qwen3-next": ((128, 2048, 512), (128, 512, 2048)),
}


@pytest.mark.parametrize("cell", CELLS)
def test_tile_rule_at_the_cells_shapes(cell):
    """The rule is a pure function of ``(R, G, K, N)``: rows tiles of
    128 (the chip read no gain from more at any cell's ``R / G``), the
    weights whole where VMEM holds them, as it does in all five."""
    R, G, D, F = CELLS[cell]
    assert (tile_sizes(R, G, D, F), tile_sizes(R, G, F, D)) == TILES[cell]
    for K, N in ((D, F), (F, D)):
        tm, tk, tn = tile_sizes(R, G, K, N)
        assert R % tm == 0 and tk % 128 == 0 and tn % 128 == 0
        assert max(gm._gmm_bytes(tm, tk, tn, 2, -(-K // tk)),
                   gm._tgmm_bytes(tm, tk, tn, 2)) <= gm._TILE_BYTES


def test_tile_rule_splits_what_vmem_cannot_hold():
    """Weights too large for VMEM: N in lane-whole tiles first, then K
    in steps; few rows: the one block that holds them."""
    assert tile_sizes(131072, 8, 4096, 14336) == (128, 4096, 1280)
    tm, tk, tn = tile_sizes(131072, 8, 16384, 14336)
    assert (tm, tn) == (128, 512) and tk < 16384 and tk % 128 == 0
    assert gm._tgmm_bytes(tm, tk, tn, 2) <= gm._TILE_BYTES
    assert tile_sizes(48, 4, 32, 16) == (48, 32, 16)


def test_grouped_dense_is_ragged_dot_off_the_tpu_and_the_kernel_on_it():
    """The platform the step is traced for decides, as for every
    kernel: the CPU keeps ``lax.ragged_dot``'s lowered text."""
    rows, w, _ = _operands(64, 32, 16, 2)
    sizes = jnp.asarray([20, 30], jnp.int32)
    here = str(jax.make_jaxpr(grouped_dense)(rows, w, sizes))
    assert "ragged_dot" in here and "pallas_call" not in here
    token = _TRACE_PLATFORM.set("tpu")
    try:
        # (a function of its own: a trace is kept by function and types)
        there = str(jax.make_jaxpr(lambda *a: grouped_dense(*a))(
            rows, w, sizes))
    finally:
        _TRACE_PLATFORM.reset(token)
    assert "pallas_call" in there and "ragged_dot" not in there


def _sharded_loss(product, check_vma):
    """Rows, their groups' sizes and a cotangent sharded over ``data``,
    the weights replicated on it, as a data-parallel group of expert
    layers has them: the members' losses summed."""
    from jax.sharding import Mesh, PartitionSpec as P

    from chainermn_tpu.parallel._compat import shard_map

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))

    def member(rows, w, sizes, ct):
        held = jnp.arange(rows.shape[0])[:, None] < jnp.sum(sizes)
        out = jnp.where(held, product(rows, w, sizes[0]), 0)
        return lax.psum(jnp.sum(out * ct), "data")

    return shard_map(member, mesh=mesh, out_specs=P(), check_vma=check_vma,
                     in_specs=(P("data"), P(), P("data"), P("data")))


def test_under_shard_map_the_weights_cotangent_is_summed_over_the_members():
    """The kernels inside ``shard_map``: the weights' cotangent is the
    sum over the members whose rows met them.  (Without the check of
    varying axes: the interpreter's index maps trip it on a prefetched
    scalar, which the chip's lowering never evaluates.)"""
    R, K, N = 64, 32, 16
    rows, w, ct = _operands(2 * R, K, N, 2)
    sizes = jnp.asarray([[20, 30], [40, 9]], jnp.int32)
    kernel = lambda r, w, s: grouped_matmul(
        r, w, s, tiles=(32, K, N), interpret=True)
    got, ref = (jax.grad(lambda r, w: _sharded_loss(product, False)(
        r, w, sizes, ct), (0, 1))(rows, w)
        for product in (kernel, lax.ragged_dot))
    # a member's rows past its last group: their cotangent is undefined
    held = np.concatenate([np.arange(R) < 50, np.arange(R) < 49])
    for g, r in zip(got, ref):
        g, r = (np.asarray(a)[held] if a.shape[0] == 2 * R else np.asarray(a)
                for a in (g, r))
        np.testing.assert_allclose(g, r, rtol=2e-5, atol=2e-5)


def test_under_shard_map_the_kernels_types_vary_as_their_operands_do():
    """Traced for the chip under ``shard_map``'s check of varying axes
    (traced only: nothing here can lower a kernel for a TPU): the
    ``custom_vjp`` hands back a weights' cotangent of the replicated
    weights' own type, its sum over ``data`` the transpose of the cast
    that retyped them."""
    R, K, N = 64, 32, 16
    rows, w, ct = _operands(2 * R, K, N, 2)
    sizes = jnp.asarray([[20, 30], [40, 9]], jnp.int32)
    token = _TRACE_PLATFORM.set("tpu")
    try:
        text = str(jax.make_jaxpr(jax.grad(
            lambda r, w: _sharded_loss(grouped_dense, True)(r, w, sizes, ct),
            (0, 1)))(rows, w))
    finally:
        _TRACE_PLATFORM.reset(token)
    assert text.count("pallas_call") == 3 and "ragged_dot" not in text
    assert "psum" in text
