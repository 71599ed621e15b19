"""GQA/MQA (beyond-reference): n_kv_heads < n_heads shares K/V heads
across query-head groups.  Semantics oracle: a GQA model must produce
bit-matching logits to an MHA model whose K/V projections are the GQA
ones repeated per group; and sharded runs (TP over heads, ring over seq)
must match the single-device GQA run."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chainermn_tpu.models import (
    TransformerConfig,
    init_transformer,
    make_forward_fn,
    make_train_step,
    shard_params,
)
from chainermn_tpu.parallel import MeshConfig


VOCAB, B, T = 64, 8, 16


def gqa_cfg(**kw):
    base = dict(
        vocab_size=VOCAB, d_model=32, n_heads=4, n_kv_heads=2, d_head=8,
        d_ff=64, n_layers=2, max_seq=T, attention="local",
        dtype="float32", remat=False,
    )
    base.update(kw)
    return TransformerConfig(**base)


def tokens(seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, VOCAB, (B, T + 1)),
        jnp.int32)


def one_chip(cfg, params, toks):
    mc = MeshConfig(data=1, devices=jax.devices()[:1])
    return make_forward_fn(mc, cfg)(params, toks)


def to_mha_params(cfg, params):
    """Repeat each kv head over its query-head group => equivalent MHA."""
    rep = cfg.n_heads // cfg.kv_heads

    def convert(blk):
        wq = blk["wq"]                       # (P, L, D, H, Dh)
        wkv = jnp.repeat(blk["wkv"], rep, axis=-2)  # (P, L, D, 2, H, Dh)
        wqkv = jnp.concatenate([wq[:, :, :, None], wkv], axis=3)
        return {k: v for k, v in blk.items() if k not in ("wq", "wkv")} \
            | {"wqkv": wqkv}

    blocks = params["blocks"]
    return dict(params, blocks=convert(blocks))


def test_invalid_head_grouping_raises():
    with pytest.raises(ValueError, match="multiple"):
        gqa_cfg(n_heads=4, n_kv_heads=3)


def test_matches_mha_with_repeated_kv():
    cfg = gqa_cfg()
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    toks = tokens()[:, :T]
    got = one_chip(cfg, params, toks)

    mha = gqa_cfg(n_kv_heads=0)
    ref = one_chip(mha, to_mha_params(cfg, params), toks)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("axes,attn", [
    (dict(model=2, data=4), "local"),
    (dict(seq=4, data=2), "ring"),
    # ulysses with seq(4) > n_kv_heads(2): the over-split path
    # replicates shared heads up to lcm for the exchange
    (dict(seq=4, data=2), "ulysses"),
    (dict(pipe=2, model=2, data=2), "local"),
], ids=str)
def test_sharded_matches_single_device(axes, attn):
    pipe = axes.get("pipe", 1)
    cfg = gqa_cfg(
        attention=attn,
        num_microbatches=2 if pipe > 1 else 1,
    )
    params = init_transformer(jax.random.PRNGKey(0), cfg, pipe_size=pipe)
    toks = tokens()[:, :T]

    ref_params = params if pipe == 1 else dict(
        params, blocks=jax.tree.map(
            lambda a: a.reshape(1, -1, *a.shape[2:]), params["blocks"]))
    ref = one_chip(gqa_cfg(), ref_params, toks)

    mc = MeshConfig(**axes)
    out = make_forward_fn(mc, cfg)(shard_params(mc, cfg, params), toks)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_mqa_tp_mesh_raises_clear_error():
    """MQA (1 kv head) cannot shard over model=2 — the error must be an
    actionable ValueError at build time, not a GSPMD placement failure."""
    cfg = gqa_cfg(n_kv_heads=1)
    mc = MeshConfig(model=2, data=4)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="n_kv_heads"):
        shard_params(mc, cfg, params)
    with pytest.raises(ValueError, match="n_kv_heads"):
        make_forward_fn(mc, cfg)


def test_negative_kv_heads_rejected():
    with pytest.raises(ValueError, match="n_kv_heads"):
        gqa_cfg(n_heads=8, n_kv_heads=-2)


def test_grouped_ring_and_ulysses_match_repeated_kv():
    """The attention cores read shared heads in place: grouped K/V into
    ring/ulysses must equal MHA cores fed group-repeated K/V."""
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu.parallel import MeshConfig as MC
    from chainermn_tpu.parallel.ring_attention import (
        local_attention, ring_attention)
    from chainermn_tpu.parallel.ulysses import ulysses_attention

    B, T, H, G, D = 2, 16, 4, 2, 8
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(B, T, H, D), jnp.float32)
    k = jnp.asarray(r.randn(B, T, G, D), jnp.float32)
    v = jnp.asarray(r.randn(B, T, G, D), jnp.float32)
    k_rep = jnp.repeat(k, H // G, axis=2)
    v_rep = jnp.repeat(v, H // G, axis=2)

    ref = local_attention(q, k_rep, v_rep, causal=True)
    got_local = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got_local), np.asarray(ref), rtol=1e-5, atol=1e-5)

    # ring: any ring size; ulysses: S | G moves true-width K/V, S > G
    # (over-split, G=2 on seq=4) replicates shared heads up to lcm —
    # both boundary sides must reproduce the oracle
    for fn, axes in ((ring_attention, dict(seq=4, data=2)),
                     (ulysses_attention, dict(seq=2, data=4)),
                     (ulysses_attention, dict(seq=4, data=2))):
        mc = MC(**axes)
        got = jax.jit(jax.shard_map(
            lambda q, k, v: fn(q, k, v, axis_name="seq", causal=True),
            mesh=mc.mesh,
            in_specs=P(None, "seq"), out_specs=P(None, "seq"),
        ))(q, k, v)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4,
            err_msg=f"{fn.__name__} {axes}")

    # MQA (G=1) on seq=4: maximal surplus factor, still exact
    k1, v1 = k[:, :, :1], v[:, :, :1]
    ref1 = local_attention(q, jnp.repeat(k1, H, axis=2),
                           jnp.repeat(v1, H, axis=2), causal=True)
    mc = MC(seq=4, data=2)
    got = jax.jit(jax.shard_map(
        lambda q, k, v: ulysses_attention(
            q, k, v, axis_name="seq", causal=True),
        mesh=mc.mesh,
        in_specs=P(None, "seq"), out_specs=P(None, "seq"),
    ))(q, k1, v1)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref1), rtol=2e-4, atol=2e-4,
        err_msg="ulysses MQA over-split")


def test_mqa_train_step_learns():
    """MQA (1 kv head): a few train steps reduce loss and touch wkv."""
    cfg = gqa_cfg(n_kv_heads=1)
    mc = MeshConfig(data=8)
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
    opt = optax.adam(1e-2)
    opt_state = jax.jit(opt.init)(params)
    step = make_train_step(mc, cfg, opt)
    toks = tokens()
    wkv0 = np.asarray(params["blocks"]["wkv"])
    losses = []
    for _ in range(10):
        params, opt_state, loss = step(
            params, opt_state, toks[:, :T], toks[:, 1:])
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    assert not np.allclose(np.asarray(params["blocks"]["wkv"]), wkv0)
