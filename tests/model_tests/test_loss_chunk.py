"""Chunked-vocab cross-entropy (``loss_chunk``): the LM head + loss in
token chunks via a custom VJP must be a pure memory/scheduling choice —
loss and every gradient (crucially the psum'd weight-tied embedding
cotangent) equal the whole-shard-logits path."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from chainermn_tpu.models import (
    TransformerConfig,
    init_transformer,
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


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_chunked_matches_whole_shard_single_device(chunk):
    """fp32 single device: chunk size must not change loss or grads
    beyond summation-order noise (chunk=T exercises the C=1 edge)."""
    toks = tokens()
    x, y = toks[:, :T], toks[:, 1:]
    one = MeshConfig(data=1, devices=jax.devices()[:1])
    params = init_transformer(jax.random.PRNGKey(0), tiny_cfg())

    l0, g0 = _grads(tiny_cfg(), one, params, x, y)
    lc, gc = _grads(tiny_cfg(loss_chunk=chunk), one, params, x, y)
    assert abs(l0 - lc) < 1e-6
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-6), g0, gc)


def test_chunked_embed_grad_psum_under_dp():
    """The single end-of-scan psum in _head_nll_bwd must reproduce the
    whole-shard path's embed gradient when the batch spans a real data
    axis (the vma-discipline correctness check)."""
    toks = tokens(1)
    x, y = toks[:, :T], toks[:, 1:]
    cfg = tiny_cfg(loss_chunk=4)
    mc = MeshConfig(data=8)
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(1), cfg))
    l_dp, g_dp = _grads(cfg, mc, params, x, y)

    one = MeshConfig(data=1, devices=jax.devices()[:1])
    ref = init_transformer(jax.random.PRNGKey(1), tiny_cfg())
    l_1, g_1 = _grads(tiny_cfg(), one, ref, x, y)

    assert abs(l_dp - l_1) < 1e-5
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-6), g_dp, g_1)


def test_chunked_train_step_matches_seq_sharded():
    """Sequence-sharded mesh: loss_chunk divides the LOCAL shard length
    (T/seq); the chunked train step tracks the whole-shard one."""
    toks = tokens(2)
    x, y = toks[:, :T], toks[:, 1:]
    mc = MeshConfig(seq=4, data=2)

    losses = {}
    for chunk in (0, 2):
        cfg = tiny_cfg(attention="ring", loss_chunk=chunk)
        params = shard_params(
            mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
        opt = optax.sgd(0.1)
        st = jax.jit(opt.init)(params)
        step = make_train_step(mc, cfg, opt)
        p, s, ls = params, st, []
        for _ in range(3):
            p, s, loss = step(p, s, x, y)
            ls.append(float(loss))
        losses[chunk] = ls
    np.testing.assert_allclose(losses[2], losses[0], rtol=1e-5, atol=1e-6)


def test_chunked_rides_1f1b_schedule():
    """loss_chunk applies inside the 1F1B in-schedule loss_fn too."""
    toks = tokens(3)
    x, y = toks[:, :T], toks[:, 1:]
    mc = MeshConfig(pipe=2, data=4)

    losses = {}
    for chunk in (0, 4):
        cfg = tiny_cfg(
            n_layers=4, pipeline_schedule="1f1b", num_microbatches=2,
            loss_chunk=chunk)
        params = shard_params(
            mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg, 2))
        opt = optax.sgd(0.1)
        st = jax.jit(opt.init)(params)
        step = make_train_step(mc, cfg, opt)
        p, s, ls = params, st, []
        for _ in range(3):
            p, s, loss = step(p, s, x, y)
            ls.append(float(loss))
        losses[chunk] = ls
    np.testing.assert_allclose(losses[4], losses[0], rtol=1e-5, atol=1e-6)


def test_loss_chunk_validation():
    with pytest.raises(ValueError, match="loss_chunk"):
        tiny_cfg(loss_chunk=-1)
    # non-divisor surfaces as a trace-time ValueError, not a shape error
    toks = tokens()
    x, y = toks[:, :T], toks[:, 1:]
    one = MeshConfig(data=1, devices=jax.devices()[:1])
    cfg = tiny_cfg(loss_chunk=5)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="must divide"):
        _grads(cfg, one, params, x, y)
