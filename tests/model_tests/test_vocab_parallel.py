"""Vocab-parallel embedding + LM head (Megatron-style vocab TP): the
sharded-vocab forward, loss, gradients (especially the weight-tied
embed shards), training trajectory, and decode must all match the
replicated-embedding oracle — the M× smaller head is an implementation
detail, not a semantics change."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from chainermn_tpu.models import (
    TransformerConfig,
    init_transformer,
    make_forward_fn,
    make_generate_fn,
    make_train_step,
    shard_params,
)
from chainermn_tpu.models.transformer import lm_loss, param_specs
from chainermn_tpu.parallel import MeshConfig


VOCAB, B, T = 64, 8, 16


def tiny_cfg(**kw):
    base = dict(
        vocab_size=VOCAB, d_model=32, n_heads=4, d_head=8, d_ff=64,
        n_layers=2, max_seq=T, attention="local", dtype="float32",
        remat=False,
    )
    base.update(kw)
    return TransformerConfig(**base)


def tokens(seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, VOCAB, (B, T + 1)),
        jnp.int32)


def _grads(cfg, mc, params, x, y):
    specs = param_specs(cfg)
    fn = jax.jit(jax.shard_map(
        lambda p, xx, yy: jax.value_and_grad(
            lambda q: jax.lax.pmean(
                lm_loss(cfg, q, xx, yy),
                ("data", "expert", "seq")))(p),
        mesh=mc.mesh,
        in_specs=(specs, P(("data", "expert"), "seq"),
                  P(("data", "expert"), "seq")),
        out_specs=(P(), specs)))
    loss, g = fn(params, x, y)
    return float(loss), jax.tree.map(np.asarray, g)


def test_forward_matches_replicated():
    cfg_vp = tiny_cfg(vocab_parallel=True)
    host = init_transformer(jax.random.PRNGKey(0), cfg_vp)
    toks = tokens()[:, :T]

    one = MeshConfig(data=1, devices=jax.devices()[:1])
    ref = make_forward_fn(one, tiny_cfg())(
        shard_params(one, tiny_cfg(), host), toks)

    mc = MeshConfig(model=4, data=2)
    out = make_forward_fn(mc, cfg_vp)(
        shard_params(mc, cfg_vp, host), toks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_loss_and_grads_match_replicated():
    """Same mesh, vocab_parallel on vs off: loss equal and every grad
    equal — the embed grad comes back as (V/M, D) shards that must
    concatenate to the replicated run's full (V, D) gradient."""
    toks = tokens(1)
    x, y = toks[:, :T], toks[:, 1:]
    mc = MeshConfig(model=4, data=2)
    host = init_transformer(jax.random.PRNGKey(1), tiny_cfg())

    l_rep, g_rep = _grads(
        tiny_cfg(), mc, shard_params(mc, tiny_cfg(), host), x, y)
    cfg_vp = tiny_cfg(vocab_parallel=True)
    l_vp, g_vp = _grads(
        cfg_vp, mc, shard_params(mc, cfg_vp, host), x, y)

    assert abs(l_rep - l_vp) < 1e-5
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-6), g_rep, g_vp)


@pytest.mark.parametrize("sched,axes,kw", [
    ("gpipe", dict(model=4, data=2), {}),
    ("1f1b", dict(pipe=2, model=2, data=2), {}),
    ("gpipe", dict(model=4, data=2), dict(fsdp=True)),
    ("gpipe", dict(expert=2, model=2, data=2),
     dict(moe=True, n_experts=4, router_top_k=2)),
], ids=["gpipe", "1f1b", "fsdp", "moe-top2"])
def test_train_step_matches_replicated(sched, axes, kw):
    toks = tokens(2)
    x, y = toks[:, :T], toks[:, 1:]
    mc = MeshConfig(**axes)
    pipe = axes.get("pipe", 1)

    losses = {}
    for vp in (False, True):
        cfg = tiny_cfg(
            n_layers=4, vocab_parallel=vp, pipeline_schedule=sched,
            num_microbatches=2 if pipe > 1 else 1, **kw)
        params = shard_params(
            mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg, pipe))
        opt = optax.adam(1e-2)
        st = jax.jit(opt.init)(params)
        step = make_train_step(mc, cfg, opt)
        p, s, ls = params, st, []
        for _ in range(3):
            p, s, loss = step(p, s, x, y)
            ls.append(float(loss))
        losses[vp] = ls
    np.testing.assert_allclose(losses[True], losses[False],
                               rtol=1e-5, atol=1e-6)


def test_generate_matches_replicated():
    cfg_vp = tiny_cfg(vocab_parallel=True)
    host = init_transformer(jax.random.PRNGKey(3), cfg_vp)
    p = tokens(4)[:, :4]

    one = MeshConfig(data=1, devices=jax.devices()[:1])
    ref = make_generate_fn(one, tiny_cfg(), max_len=12)(
        shard_params(one, tiny_cfg(), host), p)

    mc = MeshConfig(model=4, data=2)
    got = make_generate_fn(mc, cfg_vp, max_len=12)(
        shard_params(mc, cfg_vp, host), p)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_bf16_train_tracks_replicated_bf16():
    """The production dtype: _vp_head's custom VJP casts the logits
    cotangent to bf16 for both grad matmuls — the loss trajectory must
    track the replicated-head bf16 run within bf16 noise."""
    toks = tokens(8)
    x, y = toks[:, :T], toks[:, 1:]
    mc = MeshConfig(model=4, data=2)

    losses = {}
    for vp in (False, True):
        cfg = tiny_cfg(dtype="bfloat16", vocab_parallel=vp)
        # fresh deterministic init per run: the donated step buffers
        # may alias a shared host array (see the DP-vs-single test)
        params = shard_params(
            mc, cfg, init_transformer(jax.random.PRNGKey(2), cfg))
        opt = optax.sgd(0.1)
        st = jax.jit(opt.init)(params)
        step = make_train_step(mc, cfg, opt)
        p, s, ls = params, st, []
        for _ in range(5):
            p, s, loss = step(p, s, x, y)
            ls.append(float(loss))
        losses[vp] = ls
    assert np.isfinite(losses[True]).all()
    np.testing.assert_allclose(losses[True], losses[False],
                               rtol=0.03, atol=0.02)


def test_int8_generate_matches_replicated_int8():
    """Weight-only int8 decode under vocab TP: the sharded rows and
    their dequant scales ride one psum; tokens match the replicated
    int8 run exactly."""
    from chainermn_tpu.models import quantize_params_int8

    cfg_vp = tiny_cfg(vocab_parallel=True)
    host = quantize_params_int8(
        cfg_vp, init_transformer(jax.random.PRNGKey(5), cfg_vp))
    p = tokens(6)[:, :4]

    one = MeshConfig(data=1, devices=jax.devices()[:1])
    ref = make_generate_fn(one, tiny_cfg(), max_len=12, quantized=True)(
        shard_params(one, tiny_cfg(), host), p)

    mc = MeshConfig(model=4, data=2)
    got = make_generate_fn(mc, cfg_vp, max_len=12, quantized=True)(
        shard_params(mc, cfg_vp, host), p)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_loss_chunk_composes_with_vocab_parallel():
    """loss_chunk + vocab_parallel COMPOSE (r4): live logits shrink to
    (B, chunk, V/M).  Loss AND every gradient — including the embed
    shards' — must equal the plain replicated-head run on the same
    mesh; chunk == T pins the C=1 edge."""
    toks = tokens(5)
    x, y = toks[:, :T], toks[:, 1:]
    mc = MeshConfig(model=4, data=2)
    host = init_transformer(jax.random.PRNGKey(4), tiny_cfg())

    l_rep, g_rep = _grads(
        tiny_cfg(), mc, shard_params(mc, tiny_cfg(), host), x, y)
    for chunk in (4, T):
        cfg = tiny_cfg(vocab_parallel=True, loss_chunk=chunk)
        l_c, g_c = _grads(cfg, mc, shard_params(mc, cfg, host), x, y)
        assert abs(l_rep - l_c) < 1e-5, (chunk, l_rep, l_c)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                a, b, rtol=1e-5, atol=1e-6), g_rep, g_c)


def test_loss_chunk_vocab_parallel_needs_divisible_T():
    cfg = tiny_cfg(vocab_parallel=True, loss_chunk=5)  # 5 does not | 16
    mc = MeshConfig(model=4, data=2)
    with pytest.raises(ValueError, match="divide the local sequence"):
        make_train_step(mc, cfg, optax.sgd(0.1))(
            shard_params(mc, cfg,
                         init_transformer(jax.random.PRNGKey(0), cfg)),
            jax.jit(optax.sgd(0.1).init)(
                shard_params(mc, cfg, init_transformer(
                    jax.random.PRNGKey(0), cfg))),
            tokens()[:, :T], tokens()[:, 1:])


def test_vocab_parallel_validation():
    cfg = tiny_cfg(vocab_parallel=True, vocab_size=62)
    with pytest.raises(ValueError, match="divisible"):
        make_forward_fn(MeshConfig(model=4, data=2), cfg)
