"""Flagship transformer: every parallelism axis, checked against the
single-device oracle (the multi-axis run must be numerically identical —
SPMD sharding is an implementation detail, not a semantics change)."""

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
        n_layers=2, max_seq=T, attention="local", dtype="float32",
        remat=False,
    )
    base.update(kw)
    return TransformerConfig(**base)


def tokens(seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, VOCAB, (B, T + 1)),
        jnp.int32)


def oracle_logits(cfg, params, toks):
    one = MeshConfig(data=1, devices=jax.devices()[:1])
    return make_forward_fn(one, cfg)(params, toks)


MESHES = [
    dict(data=8),
    dict(model=4, data=2),
    dict(seq=4, data=2),
    dict(pipe=2, data=4),
    dict(pipe=2, model=2, seq=2, data=1),
]


@pytest.mark.parametrize(
    "axes", MESHES, ids=[str(m) for m in MESHES])
def test_forward_matches_oracle(axes):
    pipe = axes.get("pipe", 1)
    cfg = tiny_cfg(
        attention="ring" if axes.get("seq", 1) > 1 else "local",
        num_microbatches=2 if pipe > 1 else 1,
    )
    params = init_transformer(jax.random.PRNGKey(0), cfg, pipe_size=pipe)
    toks = tokens()[:, :T]

    ref_params = params if pipe == 1 else dict(
        params, blocks=jax.tree.map(
            lambda a: a.reshape(1, -1, *a.shape[2:]), params["blocks"]))
    ref = oracle_logits(tiny_cfg(), ref_params, toks)

    mc = MeshConfig(**axes)
    out = make_forward_fn(mc, cfg)(shard_params(mc, cfg, params), toks)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_remat_policy_grad_equivalence():
    """remat_policy='dots' must be a pure scheduling choice: grads equal
    the remat='full' and remat=False paths bit-for-bit (fp32)."""
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu.models.transformer import lm_loss, param_specs

    toks = tokens()
    x, y = toks[:, :T], toks[:, 1:]
    batch_spec = P(("data", "expert"), "seq")
    one = MeshConfig(data=1, devices=jax.devices()[:1])
    grads = {}
    for name, kw in (("none", dict(remat=False)),
                     ("full", dict(remat=True)),
                     ("dots", dict(remat=True, remat_policy="dots"))):
        cfg = tiny_cfg(**kw)
        params = init_transformer(jax.random.PRNGKey(0), cfg)
        specs = param_specs(cfg)
        grad_fn = jax.jit(jax.shard_map(
            lambda p, xx, yy: jax.grad(
                lambda q: lm_loss(cfg, q, xx, yy))(p),
            mesh=one.mesh,
            in_specs=(specs, batch_spec, batch_spec),
            out_specs=specs))
        grads[name] = grad_fn(params, x, y)
    for name in ("full", "dots"):
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6),
            grads["none"], grads[name])

    with pytest.raises(ValueError, match="remat_policy"):
        tiny_cfg(remat_policy="everything")


def test_ulysses_matches_oracle():
    cfg = tiny_cfg(attention="ulysses")
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    toks = tokens()[:, :T]
    ref = oracle_logits(tiny_cfg(), params, toks)
    mc = MeshConfig(seq=4, data=2)
    out = make_forward_fn(mc, cfg)(shard_params(mc, cfg, params), toks)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_moe_runs_and_balances():
    cfg = tiny_cfg(moe=True, n_experts=4)
    mc = MeshConfig(expert=4, data=2)
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
    logits = make_forward_fn(mc, cfg)(params, tokens()[:, :T])
    assert logits.shape == (B, T, VOCAB)
    assert np.isfinite(np.asarray(logits)).all()


def test_moe_top2_trains_and_matches_balance():
    """GShard-style top-2 routing composes with EP: the train step runs
    on an expert mesh, loss decreases, aux stays finite."""
    cfg = tiny_cfg(moe=True, n_experts=4, router_top_k=2)
    mc = MeshConfig(expert=4, data=2)
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
    opt = optax.adam(1e-2)
    opt_state = jax.jit(opt.init)(params)
    step = make_train_step(mc, cfg, opt)
    toks = tokens()
    x, y = toks[:, :T], toks[:, 1:]
    losses = []
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state, x, y)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.8, losses
    with pytest.raises(ValueError, match="router_top_k"):
        tiny_cfg(moe=True, n_experts=4, router_top_k=5)


@pytest.mark.parametrize("axes", [
    dict(data=8),
    dict(pipe=2, model=2, seq=2),
    dict(expert=2, model=2, data=2),
])
def test_train_step_reduces_loss(axes):
    pipe = axes.get("pipe", 1)
    cfg = tiny_cfg(
        attention="ring" if axes.get("seq", 1) > 1 else "local",
        moe=axes.get("expert", 1) > 1,
        n_experts=4,
        num_microbatches=2 if pipe > 1 else 1,
    )
    mc = MeshConfig(**axes)
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg, pipe))
    opt = optax.adam(1e-2)
    opt_state = jax.jit(opt.init)(params)
    step = make_train_step(mc, cfg, opt)
    toks = tokens()
    x, y = toks[:, :T], toks[:, 1:]
    losses = []
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state, x, y)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.8, losses


def test_grads_match_data_parallel_vs_single():
    """DP-sharded batch gives the same gradient as one device seeing the
    whole batch — the multi_node_mean_grad equivalence (SURVEY §3.1)."""
    cfg = tiny_cfg()
    toks = tokens(3)
    x, y = toks[:, :T], toks[:, 1:]
    opt = optax.sgd(0.1)

    def run(mc):
        # fresh deterministic init per run: the donated step buffers may
        # alias a shared host array, so runs must not reuse one pytree
        p = shard_params(
            mc, cfg, init_transformer(jax.random.PRNGKey(1), cfg))
        st = jax.jit(opt.init)(p)
        p2, _, loss = make_train_step(mc, cfg, opt)(p, st, x, y)
        return jax.tree.map(np.asarray, p2), float(loss)

    p_dp, l_dp = run(MeshConfig(data=8))
    p_1, l_1 = run(MeshConfig(data=1, devices=jax.devices()[:1]))
    assert abs(l_dp - l_1) < 1e-5
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5),
        p_dp, p_1)


def test_flash_attention_matches_oracle():
    """attention="flash" (Pallas kernel, interpreted off-TPU) must equal
    the XLA local-attention oracle through the full model."""
    cfg = tiny_cfg(attention="flash")
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    toks = tokens()[:, :T]
    ref = oracle_logits(tiny_cfg(), params, toks)
    mc = MeshConfig(data=8)
    out = make_forward_fn(mc, cfg)(shard_params(mc, cfg, params), toks)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_flash_unsupported_length_is_an_error():
    """attention="flash" never swaps in the XLA attention silently: a
    length the kernel cannot tile is refused (name "local" instead)."""
    cfg = tiny_cfg(attention="flash", max_seq=12)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    mc = MeshConfig(data=1, devices=jax.devices()[:1])
    with pytest.raises(ValueError, match='attention="local"'):
        make_forward_fn(mc, cfg)(
            shard_params(mc, cfg, params), tokens()[:1, :12])


def test_flash_bwd_block_override_train_step_exact():
    """flash_bwd_block_q/k retune the backward kernels' tiling only:
    a train step (loss AND updated params) must be bit-comparable to
    the default tiling — adoption of a sweep winner is purely a perf
    decision."""
    import optax

    from chainermn_tpu.models import make_train_step

    toks = tokens()[:, :T + 1]

    def one_step(cfg):
        mc = MeshConfig(data=1, devices=jax.devices()[:1])
        params = shard_params(
            mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
        opt = optax.sgd(1e-2)
        st = jax.jit(opt.init)(params)
        step = make_train_step(mc, cfg, opt)
        params, st, loss = step(params, st, toks[:, :T], toks[:, 1:])
        return jax.tree.map(np.asarray, params), float(loss)

    p_a, l_a = one_step(tiny_cfg(attention="flash"))
    p_b, l_b = one_step(tiny_cfg(attention="flash",
                                 flash_bwd_block_q=16,
                                 flash_bwd_block_k=32))
    assert l_a == l_b
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-6,
                                                atol=2e-7),
        p_a, p_b)


def test_zigzag_ring_matches_oracle():
    """seq_layout="zigzag": tokens fed through the zigzag permutation
    must yield (after un-permuting) the same logits as the contiguous
    oracle — position embeddings and causal masking follow the layout."""
    from chainermn_tpu.parallel.ring_attention import zigzag_indices

    S = 4
    cfg = tiny_cfg(attention="ring", seq_layout="zigzag")
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    toks = tokens()[:, :T]
    ref = oracle_logits(tiny_cfg(), params, toks)

    perm = zigzag_indices(S, T).reshape(-1)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(T)
    mc = MeshConfig(seq=S, data=2)
    out = make_forward_fn(mc, cfg)(
        shard_params(mc, cfg, params), toks[:, perm])
    np.testing.assert_allclose(
        np.asarray(out)[:, inv], np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_zigzag_requires_ring():
    cfg = tiny_cfg(attention="ulysses", seq_layout="zigzag")
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    mc = MeshConfig(seq=4, data=2)
    with pytest.raises(ValueError, match="zigzag"):
        make_forward_fn(mc, cfg)(
            shard_params(mc, cfg, params), tokens()[:, :T])
