"""Sliding-window (Mistral-style) causal attention: every path — XLA
core, Pallas kernel (fwd+bwd), ring schedule (both layouts), Ulysses,
the flagship forward, and the KV-cached decode — must match a dense
oracle with an explicit band mask."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models import (
    TransformerConfig,
    init_transformer,
    make_forward_fn,
    shard_params,
)
from chainermn_tpu.ops.pallas_attention import flash_attention
from chainermn_tpu.parallel import MeshConfig
from chainermn_tpu.parallel.ring_attention import (
    local_attention,
    ring_attention,
)


W = 5
VOCAB, B, T = 64, 4, 16


def dense_banded_oracle(q, k, v, window):
    """Explicit band-mask softmax attention (the ground truth)."""
    s = jnp.einsum("bthd,bshd->bhts", q, k) * (q.shape[-1] ** -0.5)
    tq, tk = q.shape[1], k.shape[1]
    qpos = jnp.arange(tq)[:, None]
    kpos = jnp.arange(tk)[None, :]
    allow = (qpos >= kpos) & (qpos - kpos < window)
    s = jnp.where(allow[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bshd->bthd", p, v)


def qkv(seed=0, t=T, h=4, d=8):
    r = np.random.RandomState(seed)
    return tuple(jnp.asarray(r.randn(B, t, h, d), jnp.float32)
                 for _ in range(3))


def test_local_attention_window_matches_oracle():
    q, k, v = qkv()
    got = local_attention(q, k, v, causal=True, window=W)
    ref = dense_banded_oracle(q, k, v, W)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="causal"):
        local_attention(q, k, v, window=W)


# blocks of 8 keys: a window inside one block, of exactly one, of one
# and a half, and wider than the sequence; then blocks that differ
@pytest.mark.parametrize("window,block_q,block_k", [
    (W, 8, 8), (8, 8, 8), (12, 8, 8), (100, 8, 8), (W, 8, 16),
    (12, 16, 8)])
def test_flash_kernel_window_fwd_bwd(window, block_q, block_k):
    """Kernel (interpret mode) vs oracle, values AND grads — the grid
    walks the band's blocks alone and must not drop in-window
    contributions."""
    q, k, v = qkv(t=32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, window=window,
                            block_q=block_q, block_k=block_k,
                            interpret=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(dense_banded_oracle(q, k, v, window) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(loss_flash(q, k, v)),
                               float(loss_ref(q, k, v)),
                               rtol=1e-4, atol=1e-4)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
def test_flash_kernel_window_with_offsets(traced):
    """The offset+window band arithmetic (the ring-flash pairing's
    riskiest inequality): kernel with global offsets vs the XLA core at
    the same global positions, values and grads; the offsets as Python
    ints, and traced under jit as the ring passes them."""
    q, k, v = qkv(t=32)
    # staggered but never fully-masked: every q row keeps >=1 in-window
    # key (fully-masked rows are the documented kernel/XLA divergence)
    q_off, k_off = 66, 64

    def flash(q, k, v, q_off, k_off):
        o = flash_attention(
            q, k, v, causal=True, window=W, q_offset=q_off,
            k_offset=k_off, block_q=8, block_k=8, interpret=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    if traced:
        loss_flash = lambda q, k, v: jax.jit(flash)(
            q, k, v, jnp.int32(q_off), jnp.int32(k_off))
    else:
        loss_flash = lambda q, k, v: flash(q, k, v, q_off, k_off)

    def loss_ref(q, k, v):
        o = local_attention(q, k, v, causal=True, window=W,
                            q_offset=q_off, k_offset=k_off)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    np.testing.assert_allclose(float(loss_flash(q, k, v)),
                               float(loss_ref(q, k, v)),
                               rtol=1e-4, atol=1e-4)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_ring_window_larger_blocks_matches_oracle():
    """Ring with T_blk=32 (kernel-eligible block sizes) and windows both
    smaller and larger than a shard — exercises the truncated ring."""
    from jax.sharding import PartitionSpec as P

    t = 128
    q, k, v = qkv(t=t)
    mc = MeshConfig(seq=4, data=2)
    for w in (8, 48, 100):
        ref = dense_banded_oracle(q, k, v, w)
        got = jax.jit(jax.shard_map(
            lambda q, k, v, w=w: ring_attention(
                q, k, v, axis_name="seq", causal=True, window=w),
            mesh=mc.mesh, in_specs=P(None, "seq"),
            out_specs=P(None, "seq")))(q, k, v)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4,
            err_msg=f"window={w}")


@pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
def test_ring_window_matches_oracle(layout):
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu.parallel.ring_attention import zigzag_indices

    q, k, v = qkv()
    ref = dense_banded_oracle(q, k, v, W)
    mc = MeshConfig(seq=4, data=2)
    if layout == "zigzag":
        perm = zigzag_indices(4, T).reshape(-1)
        q, k, v = (t[:, perm] for t in (q, k, v))
        ref = ref[:, perm]
    got = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(
            q, k, v, axis_name="seq", causal=True, window=W,
            layout=layout),
        mesh=mc.mesh, in_specs=P(None, "seq"),
        out_specs=P(None, "seq")))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def window_cfg(**kw):
    base = dict(
        vocab_size=VOCAB, d_model=32, n_heads=4, d_head=8, d_ff=64,
        n_layers=2, max_seq=T, attention="local", dtype="float32",
        remat=False, attention_window=W,
    )
    base.update(kw)
    return TransformerConfig(**base)


@pytest.mark.parametrize("axes,kw", [
    (dict(seq=4, data=2), dict(attention="ring")),
    (dict(seq=2, data=4), dict(attention="ulysses")),
], ids=["ring", "ulysses"])
def test_windowed_model_sharded_matches_single(axes, kw):
    cfg = window_cfg(**kw)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, VOCAB, (B, T)), jnp.int32)
    one = MeshConfig(data=1, devices=jax.devices()[:1])
    ref = make_forward_fn(one, window_cfg())(params, toks)
    # and the window genuinely changes the full-causal output
    full = make_forward_fn(one, window_cfg(attention_window=0))(
        params, toks)
    assert not np.allclose(np.asarray(ref), np.asarray(full), atol=1e-3)

    mc = MeshConfig(**axes)
    out = make_forward_fn(mc, cfg)(shard_params(mc, cfg, params), toks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


def test_windowed_decode_matches_forward():
    from tests.model_tests.test_decoding import (
        _cached_logits_all_positions)

    cfg = window_cfg()
    mc = MeshConfig(data=1, devices=jax.devices()[:1])
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
    toks = jnp.asarray(
        np.random.RandomState(1).randint(0, VOCAB, (B, T)), jnp.int32)
    full = make_forward_fn(mc, cfg)(params, toks)
    cached = _cached_logits_all_positions(cfg, params, toks, mc)
    np.testing.assert_allclose(np.asarray(cached), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


def test_negative_window_rejected():
    with pytest.raises(ValueError, match="attention_window"):
        window_cfg(attention_window=-1)
