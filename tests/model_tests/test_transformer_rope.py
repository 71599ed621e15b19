"""RoPE (pos_embedding="rope"): relative-position property at the core,
sharded paths (ring/zigzag/ulysses/TP) equal to the single-device oracle,
cached decode equal to the full forward, and 1F1B schedule equivalence —
rope must be a drop-in for the learned table on every path."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chainermn_tpu.models import (
    TransformerConfig,
    apply_rope,
    init_transformer,
    make_forward_fn,
    make_train_step,
    shard_params,
)
from chainermn_tpu.parallel import MeshConfig


VOCAB, B, T = 64, 8, 16


def rope_cfg(**kw):
    base = dict(
        vocab_size=VOCAB, d_model=32, n_heads=4, d_head=8, d_ff=64,
        n_layers=2, max_seq=T, attention="local", dtype="float32",
        remat=False, pos_embedding="rope",
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


def test_odd_d_head_rejected():
    with pytest.raises(ValueError, match="even d_head"):
        rope_cfg(d_head=7)


def test_no_pos_param():
    cfg = rope_cfg()
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    assert "pos" not in params


def test_relative_position_property():
    """QK scores after rope depend only on position DIFFERENCES: shifting
    all absolute positions by a constant leaves every dot unchanged."""
    r = np.random.RandomState(0)
    q = jnp.asarray(r.randn(2, 6, 4, 8), jnp.float32)
    k = jnp.asarray(r.randn(2, 6, 4, 8), jnp.float32)
    pos = jnp.arange(6)

    def scores(shift):
        qq = apply_rope(q, pos + shift)
        kk = apply_rope(k, pos + shift)
        return jnp.einsum("bthd,bshd->bhts", qq, kk)

    np.testing.assert_allclose(
        np.asarray(scores(0)), np.asarray(scores(37)),
        rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("axes,kw", [
    (dict(seq=4, data=2), dict(attention="ring")),
    (dict(seq=4, data=2), dict(attention="ring", seq_layout="zigzag")),
    (dict(seq=2, data=4), dict(attention="ulysses")),
    (dict(model=4, data=2), {}),
], ids=["ring", "ring-zigzag", "ulysses", "tp"])
def test_sharded_matches_single_device(axes, kw):
    cfg = rope_cfg(**kw)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    toks = tokens()[:, :T]
    ref = one_chip(rope_cfg(), params, toks)
    mc = MeshConfig(**axes)
    out = make_forward_fn(mc, cfg)(shard_params(mc, cfg, params), toks)
    got = np.asarray(out)
    if kw.get("seq_layout") == "zigzag":
        from chainermn_tpu.parallel.ring_attention import zigzag_indices

        perm = zigzag_indices(axes["seq"], T).reshape(-1)
        # zigzag configs consume/produce permuted token order; compare in
        # the permuted frame
        ref = np.asarray(ref)[:, perm]
        toks_p = np.asarray(toks)[:, perm]
        out_p = make_forward_fn(mc, cfg)(
            shard_params(mc, cfg, params), jnp.asarray(toks_p))
        got = np.asarray(out_p)
        np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4)
        return
    np.testing.assert_allclose(got, np.asarray(ref), rtol=3e-4, atol=3e-4)


def test_cached_decode_matches_forward():
    from tests.model_tests.test_decoding import (
        _cached_logits_all_positions)

    cfg = rope_cfg(n_kv_heads=2)
    mc = MeshConfig(data=1, devices=jax.devices()[:1])
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
    toks = tokens()[:B // 2, :T]
    full = make_forward_fn(mc, cfg)(params, toks)
    cached = _cached_logits_all_positions(cfg, params, toks, mc)
    np.testing.assert_allclose(
        np.asarray(cached), np.asarray(full), rtol=2e-4, atol=2e-4)


def test_1f1b_rope_matches_gpipe():
    mc = MeshConfig(pipe=2, data=4)
    toks = tokens()
    x, y = toks[:, :T], toks[:, 1:]
    results = {}
    for sched in ("gpipe", "1f1b"):
        cfg = rope_cfg(n_layers=2, pipeline_schedule=sched,
                       num_microbatches=2)
        params = shard_params(
            mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg, 2))
        opt = optax.sgd(0.1)
        opt_state = jax.jit(opt.init)(params)
        step = make_train_step(mc, cfg, opt)
        losses = []
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, x, y)
            losses.append(float(loss))
        results[sched] = losses
    np.testing.assert_allclose(
        results["gpipe"], results["1f1b"], rtol=1e-5, atol=1e-6)
