"""Transformer interleaved-1F1B schedule: virtual_pipe>1 must match the
GPipe train step numerically (same math, interleaved schedule), with the
forward path and weight-tied grads intact, and must train."""

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


def tiny_cfg(**kw):
    base = dict(
        vocab_size=VOCAB, d_model=32, n_heads=4, d_head=8, d_ff=64,
        n_layers=8, max_seq=T, attention="local", dtype="float32",
        remat=False, num_microbatches=4,
    )
    base.update(kw)
    return TransformerConfig(**base)


def tokens(seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, VOCAB, (B, T + 1)),
        jnp.int32)


def test_virtual_pipe_requires_interleaved():
    with pytest.raises(ValueError, match="interleaved"):
        tiny_cfg(virtual_pipe=2)


@pytest.mark.parametrize("axes,V,M", [
    (dict(pipe=2, data=4), 2, 2),
    (dict(pipe=2, data=4), 4, 2),
    (dict(pipe=4, data=2), 2, 4),
    (dict(pipe=2, model=2, data=2), 2, 4),
])
def test_interleaved_step_matches_gpipe(axes, V, M):
    pipe = axes["pipe"]
    mc = MeshConfig(**axes)
    toks = tokens()
    x, y = toks[:, :T], toks[:, 1:]

    results = {}
    for sched, v in (("gpipe", 1), ("interleaved", V)):
        cfg = tiny_cfg(pipeline_schedule=sched, virtual_pipe=v,
                       num_microbatches=M)
        params = shard_params(
            mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg, pipe))
        opt = optax.sgd(0.1)
        opt_state = jax.jit(opt.init)(params)
        step = make_train_step(mc, cfg, opt)
        p, s, losses = params, opt_state, []
        for _ in range(3):
            p, s, loss = step(p, s, x, y)
            losses.append(float(loss))
        results[sched] = (p, losses)

    # identical losses every step => identical grads through the
    # schedule (embed/pos/ln_f replicated leaves compare directly)
    np.testing.assert_allclose(
        results["gpipe"][1], results["interleaved"][1],
        rtol=1e-5, atol=1e-6)
    for leaf in ("embed", "pos", "ln_f"):
        np.testing.assert_allclose(
            np.asarray(results["interleaved"][0][leaf]),
            np.asarray(results["gpipe"][0][leaf]),
            rtol=1e-4, atol=1e-5, err_msg=leaf)
    # block params: gpipe blocks are (pipe, L/pipe, ...), interleaved
    # (pipe, V, L/(pipe*V), ...) with virtual-stage assignment — compare
    # layer-by-layer through the packing map g = c*pipe + s
    gp_blocks = jax.tree.map(
        lambda a: np.asarray(a), results["gpipe"][0]["blocks"])
    il_blocks = jax.tree.map(
        lambda a: np.asarray(a), results["interleaved"][0]["blocks"])
    lpc = tiny_cfg().n_layers // (pipe * V)
    lps = tiny_cfg().n_layers // pipe

    def layer_from_gpipe(tree, g_layer):
        return jax.tree.map(
            lambda a: a[g_layer // lps, g_layer % lps], tree)

    def layer_from_interleaved(tree, g_layer):
        g = g_layer // lpc          # virtual stage
        return jax.tree.map(
            lambda a: a[g % pipe, g // pipe, g_layer % lpc], tree)

    for L in range(tiny_cfg().n_layers):
        a = layer_from_gpipe(gp_blocks, L)
        b = layer_from_interleaved(il_blocks, L)
        for x1, x2 in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_allclose(
                x1, x2, rtol=1e-4, atol=1e-5,
                err_msg=f"layer {L}")


def test_interleaved_moe_matches_gpipe():
    """EP + interleaved PP: the Switch balancing loss and its gradients
    must ride the interleaved schedule — loss trajectory must match the
    GPipe schedule (which differentiates loss + 0.01*aux)."""
    pipe, V, M = 2, 2, 2
    mc = MeshConfig(pipe=pipe, expert=2, data=2)
    toks = tokens()
    x, y = toks[:, :T], toks[:, 1:]

    results = {}
    for sched, v in (("gpipe", 1), ("interleaved", V)):
        cfg = tiny_cfg(pipeline_schedule=sched, virtual_pipe=v,
                       num_microbatches=M, moe=True, n_experts=4)
        params = shard_params(
            mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg, pipe))
        opt = optax.sgd(0.1)
        opt_state = jax.jit(opt.init)(params)
        step = make_train_step(mc, cfg, opt)
        p, s, losses = params, opt_state, []
        for _ in range(3):
            p, s, loss = step(p, s, x, y)
            losses.append(float(loss))
        results[sched] = (p, losses)

    np.testing.assert_allclose(
        results["gpipe"][1], results["interleaved"][1],
        rtol=1e-4, atol=1e-5,
        err_msg="MoE interleaved loss trajectory diverges from GPipe "
                "(aux gradients lost or double-counted in the schedule)")


def test_interleaved_forward_matches_single_device():
    """The chunk-looped forward path reproduces the unpipelined oracle."""
    pipe, V = 2, 2
    cfg = tiny_cfg(pipeline_schedule="interleaved", virtual_pipe=V,
                   num_microbatches=2)
    params = init_transformer(jax.random.PRNGKey(0), cfg, pipe)
    toks = tokens()[:, :T]

    # repack interleaved (pipe, V, lpc, ...) into the flat oracle layout
    lpc = cfg.n_layers // (pipe * V)
    flat = jax.tree.map(
        lambda a: a.swapaxes(0, 1).reshape(1, -1, *a.shape[3:]),
        params["blocks"])
    oracle_params = dict(params, blocks=flat)
    one = MeshConfig(data=1, devices=jax.devices()[:1])
    ref = make_forward_fn(one, tiny_cfg())(oracle_params, toks)

    mc = MeshConfig(pipe=pipe, data=4)
    out = make_forward_fn(mc, cfg)(shard_params(mc, cfg, params), toks)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_interleaved_trains():
    cfg = tiny_cfg(pipeline_schedule="interleaved", virtual_pipe=2,
                   num_microbatches=4)
    mc = MeshConfig(pipe=4, data=2)
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg, 4))
    opt = optax.adam(1e-2)
    opt_state = jax.jit(opt.init)(params)
    step = make_train_step(mc, cfg, opt)
    toks = tokens()
    losses = []
    for _ in range(10):
        params, opt_state, loss = step(
            params, opt_state, toks[:, :T], toks[:, 1:])
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
