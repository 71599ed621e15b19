"""ZeRO-3 / FSDP (``TransformerConfig(fsdp=True)``): parameters, grads
and optimiser state shard over ``data`` at rest; each layer all-gathers
its weights just-in-time and AD reduce-scatters the grads.  Sharding is
an implementation detail — training must match the dense (replicated)
run numerically on every mesh it composes with."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chainermn_tpu.models import (
    TransformerConfig,
    init_transformer,
    make_generate_fn,
    make_train_step,
    shard_params,
)
from chainermn_tpu.parallel import MeshConfig
from chainermn_tpu.training import shard_opt_state


VOCAB, B, T = 64, 8, 16


def tiny_cfg(**kw):
    base = dict(
        vocab_size=VOCAB, d_model=32, n_heads=4, d_head=8, d_ff=64,
        n_layers=2, max_seq=T, attention="local", dtype="float32",
        remat=False,
    )
    base.update(kw)
    return TransformerConfig(**base)


def _tokens(seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, VOCAB, (B, T + 1)),
        jnp.int32)


def _train(cfg, mc, steps=3):
    params = shard_params(
        mc, cfg,
        init_transformer(jax.random.PRNGKey(0), cfg,
                         mc.mesh.shape.get("pipe", 1)))
    opt = optax.adam(1e-2)
    opt_state = shard_opt_state(opt, params)
    step = make_train_step(mc, cfg, opt)
    toks = _tokens()
    losses = []
    for _ in range(steps):
        params, opt_state, loss = step(
            params, opt_state, toks[:, :T], toks[:, 1:])
        losses.append(float(loss))
    if cfg.fsdp:
        # moments must STAY shard-width through the jitted update
        assert opt_state[0].mu["blocks"]["w1"].sharding.spec == \
            params["blocks"]["w1"].sharding.spec
    return losses, jax.tree.map(
        lambda a: np.asarray(jax.device_get(a)), params)


# every parallel axis FSDP has to compose with: pure DP, TP+SP (ring),
# EP/MoE, GPipe, and the 1F1B schedule
CASES = [
    (dict(data=8), {}),
    (dict(data=2, model=2, seq=2), dict(attention="ring")),
    (dict(data=4, expert=2), dict(moe=True, n_experts=4)),
    (dict(data=2, pipe=2, model=2),
     dict(n_layers=4, num_microbatches=2)),
    (dict(data=4, pipe=2),
     dict(n_layers=4, num_microbatches=2, pipeline_schedule="1f1b")),
    (dict(data=4, pipe=2),
     dict(n_layers=8, num_microbatches=2,
          pipeline_schedule="interleaved", virtual_pipe=2)),
]


@pytest.mark.parametrize(
    "axes,extra", CASES, ids=[str(a) for a, _ in CASES])
def test_fsdp_matches_dense(axes, extra):
    mc = MeshConfig(**axes)
    dense = tiny_cfg(**extra)
    losses_d, params_d = _train(dense, mc)
    losses_f, params_f = _train(
        dataclasses.replace(dense, fsdp=True), mc)
    np.testing.assert_allclose(losses_f, losses_d, rtol=1e-5, atol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            a, b, rtol=2e-5, atol=2e-5),
        params_f, params_d)


def test_fsdp_at_rest_sharding():
    """The point of ZeRO-3: each device holds 1/N of every matrix (and
    its grads/moments follow).  Check the placed arrays' local shards."""
    mc = MeshConfig(data=8)
    cfg = tiny_cfg(fsdp=True)
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
    w1 = params["blocks"]["w1"]           # (1, L, D, F)
    local = w1.addressable_shards[0].data.shape
    assert local == (1, cfg.n_layers, cfg.d_model // 8, cfg.d_ff), local
    wo = params["blocks"]["wo"]           # (1, L, H, Dh, D)
    assert wo.addressable_shards[0].data.shape[-1] == cfg.d_model // 8
    # embeddings and norms stay replicated
    assert params["embed"].addressable_shards[0].data.shape == \
        params["embed"].shape
    assert params["blocks"]["ln1"].addressable_shards[0].data.shape == \
        params["blocks"]["ln1"].shape
    # ZeRO-3's other 2/3: optimiser moments must be shard-width too —
    # plain jit(init) would replicate them (zeros_like carries no data
    # dependence for sharding propagation); shard_opt_state pins them
    opt_state = shard_opt_state(optax.adam(1e-2), params)
    mu_w1 = opt_state[0].mu["blocks"]["w1"]
    assert mu_w1.addressable_shards[0].data.shape == \
        (1, cfg.n_layers, cfg.d_model // 8, cfg.d_ff)


def test_fsdp_bf16_wire_dtype_trains():
    """bf16 gathers/reduce-scatters (the allreduce_grad_dtype analogue)
    stay close to the fp32-wire run and the loss still falls."""
    mc = MeshConfig(data=8)
    losses_f, _ = _train(tiny_cfg(fsdp=True), mc)
    losses_b, _ = _train(
        tiny_cfg(fsdp=True, fsdp_wire_dtype="bfloat16"), mc)
    assert losses_b[-1] < losses_b[0]
    np.testing.assert_allclose(losses_b, losses_f, rtol=0.05, atol=0.05)


def test_fsdp_decode_raises():
    mc = MeshConfig(data=8)
    with pytest.raises(ValueError, match="fsdp is a training-path"):
        make_generate_fn(mc, tiny_cfg(fsdp=True), max_len=T)


def test_fsdp_wire_dtype_requires_fsdp():
    with pytest.raises(ValueError, match="fsdp=False"):
        tiny_cfg(fsdp_wire_dtype="bfloat16")


def test_fsdp_dmodel_divisibility():
    mc = MeshConfig(data=8)
    cfg = tiny_cfg(fsdp=True, d_model=36)
    with pytest.raises(ValueError, match="divisible by the data"):
        make_train_step(mc, cfg, optax.adam(1e-2))


def test_moe_fsdp_at_rest_sharding():
    """MoE expert stacks also rest at 1/N d_model width (loss parity
    with dense is CASES[2] in test_fsdp_matches_dense)."""
    mc = MeshConfig(data=2, expert=2, devices=jax.devices()[:4])
    cfg = tiny_cfg(moe=True, n_experts=4, fsdp=True)
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
    w1 = params["blocks"]["w1"]           # (pipe, L, E, D, F)
    assert w1.addressable_shards[0].data.shape[3] == cfg.d_model // 2, \
        w1.addressable_shards[0].data.shape
