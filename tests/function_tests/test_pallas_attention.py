"""Pallas flash attention vs the XLA oracle (interpret mode on CPU —
the same kernel code path that compiles on TPU)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.ops.pallas_attention import (
    _block_needed,
    _bwd_vmem_bytes,
    _visit_plan,
    flash_attention,
    flash_attention_supported,
)
from chainermn_tpu.parallel.ring_attention import (
    _lse_attention_pair,
    local_attention,
)

B, T, H, D = 2, 64, 2, 16


def qkv(seed=0, t=T, d=D, dv=None):
    rng = np.random.RandomState(seed)
    mk = lambda width: jnp.asarray(
        rng.randn(B, t, H, width).astype(np.float32) * 0.5)
    return mk(d), mk(d), mk(dv or d)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_oracle(causal):
    q, k, v = qkv()
    ref = local_attention(q, k, v, causal=causal)
    out = flash_attention(
        q, k, v, causal=causal, block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("d,dv", [(D, D), (24, 16)],
                         ids=["one-width", "values-narrower"])
@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_oracle(causal, d, dv):
    """dq, dk and dv of the one backward kernel; the second case has
    keys and values of different widths (latent attention's 192 + 128:
    dq and dk take the keys' width, dv the values')."""
    q, k, v = qkv(1, d=d, dv=dv)

    def loss_flash(q, k, v):
        o = flash_attention(
            q, k, v, causal=causal, block_q=32, block_k=32, interpret=True)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = local_attention(q, k, v, causal=causal)
        return jnp.sum(o * jnp.cos(o))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("bwd_q,bwd_k,window", [
    (16, 32, None), (32, 16, None), (64, 64, None),
    # with a window the backward grid is a band of its own width (key
    # blocks of bwd_k outer, the needed query blocks of bwd_q inner)
    (16, 32, 24), (32, 16, 24), (16, 32, 48), (32, 16, 5),
    # one key block for the whole length, and one query block
    (8, 64, None), (64, 8, 24), (16, 16, 48)])
def test_bwd_block_retune_grads_exact(bwd_q, bwd_k, window):
    """The backward kernel tiled independently of the forward must give
    the same gradients for ANY valid tiling — the correctness side of
    the bwd block retune lever (the OPT cells' flash.ms_per_step
    reads the perf side)."""
    g_default = _retune_grads(window, None, None)
    g_retuned = _retune_grads(window, bwd_q, bwd_k)
    for a, b in zip(g_retuned, g_default):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6)


@functools.cache
def _retune_grads(window, bq, bk):
    """The gradients at one backward tiling; the default tiling's are
    every case's yardstick, made once a window."""
    def f(q, k, v):
        o = flash_attention(
            q, k, v, causal=True, window=window, block_q=32, block_k=32,
            bwd_block_q=bq, bwd_block_k=bk, interpret=True)
        return jnp.sum(o * jnp.cos(o))
    return jax.grad(f, argnums=(0, 1, 2))(*qkv(3))


def test_global_offsets_match_sliced_oracle():
    """Sequence-sharded callers pass global offsets: attending a local q
    block against a k block from elsewhere in the sequence must equal the
    corresponding slice of full causal attention."""
    q, k, v = qkv(2)
    out = flash_attention(
        q, k, v, causal=True, q_offset=128, k_offset=64,
        block_q=32, block_k=32, interpret=True)
    ref = local_attention(q, k, v, causal=True, q_offset=128, k_offset=64)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_bf16_inputs():
    q, k, v = qkv(3)
    q, k, v = q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), \
        v.astype(jnp.bfloat16)
    out = flash_attention(
        q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
    ref = local_attention(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32), causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(ref),
        rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("window", [None, 24])
def test_bf16_grads(window):
    """bf16 on the wire, float32 accumulators (dq's over the whole
    query length): gradients against the float32 oracle."""
    q, k, v = (x.astype(jnp.bfloat16) for x in qkv(5))

    def loss(f):
        return lambda q, k, v: jnp.sum(
            f(q, k, v, causal=True, window=window).astype(jnp.float32) ** 2)

    g_flash = jax.grad(loss(functools.partial(
        flash_attention, block_q=16, block_k=32, interpret=True)),
        argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(local_attention), argnums=(0, 1, 2))(
        *(x.astype(jnp.float32) for x in (q, k, v)))
    for a, b in zip(g_flash, g_ref):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(a, dtype=np.float32), np.asarray(b),
            rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("window,q_off,k_off", [
    (None, 0, 0), (24, 0, 0), (None, 40, 16), (None, 0, 64), (8, 200, 0)],
    ids=["causal", "window", "offsets", "no-key-allowed",
         "band-before-the-keys"])
def test_lse_cotangent_grads_match_oracle(window, q_off, k_off):
    """The ring's call form: ``lse`` returned and differentiated, its
    cotangent folded into ``delta``.  In the last two cases no query
    may meet any key: the pair still hands back gradients, all zero."""
    q, k, v = qkv(6)

    def loss(f):
        def inner(q, k, v):
            o, lse = f(q, k, v)
            return jnp.sum(o * jnp.cos(o)) + jnp.sum(jnp.sin(lse * 1e-3))
        return inner

    g_flash = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, q_offset=q_off,
        k_offset=k_off, block_q=16, block_k=32, return_lse=True,
        interpret=True)), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(lambda q, k, v: _lse_attention_pair(
        q, k, v, causal=True, window=window, q_offset=q_off,
        k_offset=k_off)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)
    if not _rows_with_a_key(T, T, q_off, k_off, window).any():
        for a in g_flash:
            np.testing.assert_array_equal(np.asarray(a), 0.0)


def test_supported_predicate():
    assert flash_attention_supported(256, 256)
    assert flash_attention_supported(64, 64, block_q=32, block_k=32)
    assert not flash_attention_supported(100, 128)
    with pytest.raises(ValueError):
        q, k, v = qkv()
        flash_attention(q[:, :33], k, v, interpret=True)


def test_fit_block():
    from chainermn_tpu.ops.pallas_attention import _fit_block

    assert _fit_block(8192, 1024) == 1024
    assert _fit_block(2048, 1024) == 1024
    # non-power-of-two requests round down, not collapse to 8 rows
    assert _fit_block(8192, 1000) == 512
    # non-power-of-two lengths shrink the block until it tiles
    assert _fit_block(1536, 1024) == 512
    assert _fit_block(384, 128) == 128
    # whole-axis single block for short sequences
    assert _fit_block(1000, 1024) == 1000
    assert _fit_block(64, 1024) == 64
    # explicit small requests are honored below the 128 floor
    assert _fit_block(64, 32) == 32
    # 8-aligned but only tileable by degenerate blocks -> XLA fallback
    assert _fit_block(1032, 1024) is None
    # not sublane-aligned
    assert _fit_block(100, 1024) is None


def test_fully_masked_rows_zero_partial_rows_exact():
    """k_offset ahead of q_offset: rows with some valid K must match the
    oracle exactly; rows with NO valid K return zeros (documented
    divergence — the oracle returns a meaningless uniform average)."""
    q, k, v = qkv(4)
    out = flash_attention(
        q, k, v, causal=True, q_offset=0, k_offset=48,
        block_q=32, block_k=32, interpret=True)
    ref = local_attention(q, k, v, causal=True, q_offset=0, k_offset=48)
    # global q positions 48..63 see K positions 48..63 (partially masked)
    np.testing.assert_allclose(
        np.asarray(out[:, 48:]), np.asarray(ref[:, 48:]),
        rtol=2e-5, atol=2e-5)
    # positions 0..47 precede every K position: zeros
    np.testing.assert_array_equal(np.asarray(out[:, :48]), 0.0)

    # gradients: zero rows contribute nothing, valid rows match oracle
    def loss(f):
        def inner(q, k, v):
            o = f(q, k, v)
            return jnp.sum(o[:, 48:] * jnp.cos(o[:, 48:]))
        return inner

    g_flash = jax.grad(
        loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, q_offset=0, k_offset=48,
            block_q=32, block_k=32, interpret=True)),
        argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        loss(lambda q, k, v: local_attention(
            q, k, v, causal=True, q_offset=0, k_offset=48)),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)


# ------------------------------------------------------------------ #
# the visit plan: which block pairs each grid walks
# ------------------------------------------------------------------ #


def _rows_with_a_key(tq, tk, q_off, k_off, window):
    qpos = q_off + np.arange(tq)[:, None]
    kpos = k_off + np.arange(tk)[None, :]
    allow = qpos >= kpos
    if window is not None:
        allow &= qpos - kpos < window
    return allow.any(axis=1)


# (T_q, T_k, block_q, block_k, window, q_offset, k_offset); blocks of 16
# or 32 against windows below, at, one and a half times and far above a
# block; both block orders; unequal lengths at offsets that put the
# band's clamped steps at the first query block and the last key block,
# rows with no key at all, and a band wholly outside the keys
PLAN_CASES = [
    (64, 64, 16, 16, 5, 0, 0),
    (64, 64, 16, 16, 16, 0, 0),
    (64, 64, 16, 16, 24, 0, 0),
    (64, 64, 16, 16, 1000, 0, 0),
    (64, 64, 16, 16, 1, 0, 0),
    (64, 64, 16, 32, 24, 0, 0),
    (64, 64, 32, 16, 24, 0, 0),
    (64, 64, 8, 32, 5, 0, 0),
    (32, 64, 16, 16, 24, 96, 64),
    (64, 32, 16, 16, 24, 64, 64),
    (64, 32, 16, 32, 40, 70, 64),
    (32, 64, 16, 16, None, 80, 64),
    (64, 64, 16, 16, 24, 0, 40),
    (64, 64, 16, 16, 8, 200, 0),
    (64, 64, 16, 16, None, 0, 64),
]


@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
@pytest.mark.parametrize("tq,tk,bq,bk,window,q_off,k_off", PLAN_CASES)
def test_band_grid_matches_oracle(tq, tk, bq, bk, window, q_off, k_off,
                                  traced):
    """Forward, lse and gradients over the band's grid against the XLA
    oracle at the same global positions; offsets as Python ints (the
    band placed exactly) and as traced scalars under jit (the ring's
    call form: the band given room to sit anywhere).  Rows with no
    allowed key are the documented divergence: zeros and lse ~ -1e30."""
    rng = np.random.RandomState(7)
    mk = lambda t: jnp.asarray(rng.randn(B, t, H, D).astype(np.float32) * .5)
    q, k, v = mk(tq), mk(tk), mk(tk)
    live = _rows_with_a_key(tq, tk, q_off, k_off, window)
    rows = jnp.asarray(live, jnp.float32)[None, :, None, None]

    def flash(q, k, v, q_off, k_off):
        return flash_attention(
            q, k, v, causal=True, window=window, q_offset=q_off,
            k_offset=k_off, block_q=bq, block_k=bk, return_lse=True,
            interpret=True)

    def oracle(q, k, v, q_off, k_off):
        return local_attention(q, k, v, causal=True, window=window,
                               q_offset=q_off, k_offset=k_off)

    def loss(f):
        def inner(q, k, v, q_off, k_off):
            o = f(q, k, v, q_off, k_off)
            o = (o[0] if isinstance(o, tuple) else o) * rows
            return jnp.sum(o * jnp.cos(o))
        return inner

    offs = (q_off, k_off)
    if traced:
        flash = jax.jit(flash)
        offs = tuple(jnp.int32(x) for x in offs)
    out, lse = flash(q, k, v, *offs)
    ref = oracle(q, k, v, q_off, k_off)
    np.testing.assert_allclose(
        np.asarray(out)[:, live], np.asarray(ref)[:, live],
        rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(out)[:, ~live], 0.0)
    assert (np.asarray(lse)[:, ~live] < -1e29).all()
    assert np.isfinite(np.asarray(lse)[:, live]).all()

    grad = jax.grad(loss(flash), argnums=(0, 1, 2))
    g_flash = (jax.jit(grad) if traced else grad)(q, k, v, *offs)
    g_ref = jax.grad(loss(oracle), argnums=(0, 1, 2))(q, k, v, q_off, k_off)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)


def _brute_force_pairs(tq, tk, bq, bk, causal, window, q_off, k_off):
    """Block pairs (q block, k block) holding an allowed position."""
    qpos = q_off + np.arange(tq)[:, None]
    kpos = k_off + np.arange(tk)[None, :]
    allow = np.ones((tq, tk), bool)
    if causal:
        allow &= qpos >= kpos
        if window is not None:
            allow &= qpos - kpos < window
    blocks = allow.reshape(tq // bq, bq, tk // bk, bk).any(axis=(1, 3))
    return {(int(i), int(j)) for i, j in zip(*np.nonzero(blocks))}


def _walk(plan, bq, bk, causal, window, q_off, k_off):
    """Every step of ``plan``'s grid as the kernels read it: the pairs
    computed (unclamped index in range and the predicate true), with
    the block each step's operands were copied from."""
    pairs, copies = [], 0
    for o in range(plan.n_outer):
        was = None
        for s in range(plan.width):
            u, blk = (int(x) for x in plan.inner(o, s, q_off, k_off))
            assert 0 <= blk < plan.n_inner
            copies += blk != was
            was = blk
            i, j = (o, u) if plan.outer == "q" else (u, o)
            if 0 <= u < plan.n_inner and bool(_block_needed(
                    i, j, q_off, k_off, bq, bk, causal, window)):
                assert blk == u, "a computed step reads its own block"
                pairs.append((i, j))
    return pairs, copies


@pytest.mark.parametrize("causal,window,width,pairs", [
    (True, 1024, 2, 15), (True, 512, 2, 15), (True, None, 8, 36),
    (False, None, 8, 64)])
@pytest.mark.parametrize("outer", ["q", "k"])
def test_visit_plan_on_the_cells_shapes(causal, window, width, pairs,
                                        outer):
    """8,192 tokens in 1,024-wide blocks, the typed cells' kernels: a
    windowed grid is 8 x 2 for 15 computed pairs a head where it was
    8 x 8, and a causal one keeps its extent."""
    plan = _visit_plan(8192, 8192, 1024, 1024, causal, window, (0, 0),
                       outer)
    assert (plan.width, plan.steps) == (width, 8 * width)
    assert plan.pairs_computed == plan.pairs_needed == pairs
    got, copies = _walk(plan, 1024, 1024, causal, window, 0, 0)
    assert len(got) == pairs
    # steps with nothing to compute stay on the block beside them
    assert copies <= pairs
    # wherever the band sits it touches at most three blocks here
    anywhere = _visit_plan(8192, 8192, 1024, 1024, causal, window, None,
                           outer)
    assert anywhere.width == (3 if window else 8)
    assert anywhere.pairs_computed is None


def test_visit_plan_walks_exactly_the_needed_pairs():
    """Over a sweep of small shapes, both grids and both kinds of
    offsets: the unclamped pairs the walk computes are, each once and
    in increasing order, the block pairs that hold an allowed
    position."""
    n = 0
    for tq, tk in [(32, 32), (16, 48), (48, 16), (64, 64)]:
        for bq, bk in [(8, 8), (8, 16), (16, 8), (16, 16)]:
            for causal, window in [(False, None), (True, None), (True, 1),
                                   (True, 7), (True, 8), (True, 12),
                                   (True, 20), (True, 500)]:
                for q_off, k_off in [(0, 0), (32, 0), (0, 24), (18, 16),
                                     (300, 0)]:
                    want = _brute_force_pairs(tq, tk, bq, bk, causal,
                                              window, q_off, k_off)
                    for outer in ("q", "k"):
                        for offsets in ((q_off, k_off), None):
                            plan = _visit_plan(tq, tk, bq, bk, causal,
                                               window, offsets, outer)
                            got, _ = _walk(plan, bq, bk, causal, window,
                                           q_off, k_off)
                            assert got == sorted(want, key=(
                                (lambda p: p) if outer == "q"
                                else (lambda p: p[::-1]))), (
                                tq, tk, bq, bk, causal, window, q_off,
                                k_off, outer, offsets)
                            if offsets:
                                assert plan.pairs_computed == len(want)
                                assert plan.pairs_needed == len(want)
                            n += 1
    assert n == 4 * 4 * 8 * 5 * 4


@pytest.mark.parametrize("window,steps,pairs,bwd_steps,bwd_pairs", [
    (512, 16, 15, 32, 31), (1024, 16, 15, 48, 45), (None, 64, 36, 64, 36)])
def test_grid_counters_after_a_traced_call(window, steps, pairs, bwd_steps,
                                           bwd_pairs):
    """``flash/grid_steps`` and ``flash/pairs_computed`` (a head, one
    addition for each kernel call site as it is traced) at the typed
    cells' shapes: forward alone, then forward (traced twice) and the
    one backward kernel, which ``flash/backward_fused_sites`` counts
    and which walks a band in 512-wide blocks of its own (16 key blocks
    of 2 or 3 query blocks each)."""
    from chainermn_tpu.utils.metrics import MetricsRegistry, set_registry

    s = jax.ShapeDtypeStruct((1, 8192, 2, 128), jnp.bfloat16)

    def attn(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               interpret=True)

    reg = MetricsRegistry(enabled=True)
    prev = set_registry(reg)
    try:
        jax.eval_shape(attn, s, s, s)
        assert reg.counter("flash/grid_steps").value == steps
        assert reg.counter("flash/pairs_computed").value == pairs
        assert reg.counter("flash/backward_fused_sites").value == 0
        jax.eval_shape(jax.grad(
            lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)), s, s, s)
        assert reg.counter("flash/grid_steps").value \
            == 2 * steps + bwd_steps
        assert reg.counter("flash/pairs_computed").value \
            == 2 * pairs + bwd_pairs
        assert reg.counter("flash/backward_fused_sites").value == 1
    finally:
        set_registry(prev)


# the cells' backward shapes (T_q, D, D_v, block_q, block_k) with the
# VMEM the chip's compiler needed for each, MiB (bisection of
# vmem_limit_bytes in compiles for a described v5e, PR 44)
@pytest.mark.parametrize("shape,compiler_needs", [
    ((2048, 64, 64, 1024, 1024), 13.3),       # OPT, one chip and four
    ((8192, 128, 128, 1024, 1024), 20.2),     # Mellum, Laguna, Nemotron
    ((8192, 128, 128, 512, 1024), 15.1),
    ((16384, 192, 128, 1024, 1024), 46.6),    # Kimi's latent layer
    ((16384, 256, 256, 512, 1024), 44.0),     # Qwen3-Next
    ((16384, 256, 256, 1024, 1024), 49.5)])
def test_backward_vmem_limit_follows_the_shapes(shape, compiler_needs):
    """The limit the backward kernel asks for is derived from its
    shapes: above what the compiler needed, within a quarter over."""
    asked = _bwd_vmem_bytes(*shape, jnp.bfloat16) / 2 ** 20
    assert compiler_needs < asked < 1.25 * compiler_needs + 8


def test_backward_refuses_a_length_its_accumulator_cannot_hold():
    """dq's float32 accumulator spans the query length in VMEM: a length
    past the budget is refused by name as the backward is traced, not
    by the chip's compiler."""
    s = jax.ShapeDtypeStruct((1, 131072, 1, 128), jnp.bfloat16)
    attn = lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=True).astype(jnp.float32).sum()
    jax.eval_shape(attn, s, s, s)
    with pytest.raises(ValueError, match="dq's accumulator over 131072"):
        jax.eval_shape(jax.grad(attn, argnums=(0, 1, 2)), s, s, s)
