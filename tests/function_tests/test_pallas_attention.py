"""Pallas flash attention vs the XLA oracle (interpret mode on CPU —
the same kernel code path that compiles on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.ops.pallas_attention import (
    flash_attention,
    flash_attention_supported,
)
from chainermn_tpu.parallel.ring_attention import local_attention

B, T, H, D = 2, 64, 2, 16


def qkv(seed=0, t=T):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(
        rng.randn(B, t, H, D).astype(np.float32) * 0.5)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_oracle(causal):
    q, k, v = qkv()
    ref = local_attention(q, k, v, causal=causal)
    out = flash_attention(
        q, k, v, causal=causal, block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_oracle(causal):
    q, k, v = qkv(1)

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


@pytest.mark.parametrize("bwd_q,bwd_k", [(16, 32), (32, 16), (64, 64)])
def test_bwd_block_retune_grads_exact(bwd_q, bwd_k):
    """Backward kernels tiled independently of the forward must give
    the same gradients for ANY valid tiling — the correctness side of
    the bwd block retune lever (the OPT cells' flash.ms_per_step
    reads the perf side)."""
    q, k, v = qkv(3)

    def loss(bq, bk):
        def f(q, k, v):
            o = flash_attention(
                q, k, v, causal=True, block_q=32, block_k=32,
                bwd_block_q=bq, bwd_block_k=bk, interpret=True)
            return jnp.sum(o * jnp.cos(o))
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    g_default = loss(None, None)
    g_retuned = loss(bwd_q, bwd_k)
    for a, b in zip(g_retuned, g_default):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6)


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
