"""A transformer whose layers differ by SHAPE as well as by kind: query
heads and rotary share by ``AttentionKind``, a gate a head, layers that
lead the periods with a dense SwiGLU, then sparse layers with a shared
expert and a sigmoid router.  The config's validation, the parameter
tree (no leaf for heads a layer does not have), the rotary frequencies
and the gates by hand, the layer scan against an unrolled stack, meshes
against one device, the dropless layer at both ends of imbalance, the
counters, and the refusal of the paths that do not implement these
fields."""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from chainermn_tpu.models import (
    AttentionKind,
    TransformerConfig,
    expert_choices,
    expert_load,
    init_transformer,
    make_forward_fn,
    make_generate_fn,
    make_train_step,
    shard_params,
)
from chainermn_tpu.models import transformer as tr
from chainermn_tpu.parallel import MeshConfig
from chainermn_tpu.parallel.expert import route_top_k
from chainermn_tpu.training import shard_opt_state

VOCAB, B, T = 64, 4, 64
SLIDING = AttentionKind("sliding", window=16, rope_theta=1e4, n_heads=8)
FULL = AttentionKind("full", rope_theta=5e5, yarn_factor=64,
                     yarn_original_max=32, yarn_beta_fast=64,
                     attention_factor=1.4158883083359672, n_heads=4,
                     rotary_share=0.5)


def mixed_cfg(**kw):
    base = dict(
        vocab_size=VOCAB, d_model=32, n_heads=4, n_kv_heads=2, d_head=8,
        d_ff=16, n_layers=5, max_seq=T, attention="local", dtype="float32",
        pos_embedding="rope", leading_layers=(FULL,),
        layer_pattern=(SLIDING,) * 3 + (FULL,), attn_gate="per_head",
        dense_act="swiglu", dense_d_ff=48,
        moe=True, n_experts=8, router_top_k=2, moe_dispatch="dropless",
        expert_act="swiglu", experts_held=(2, 4), router_score="sigmoid",
        router_scale=2.5, shared_expert_d_ff=24, tie_embeddings=False)
    base.update(kw)
    return TransformerConfig(**base)


def tokens(seed=0):
    t = jnp.asarray(np.random.RandomState(seed).randint(
        0, VOCAB, (B, T + 1)), jnp.int32)
    return t[:, :-1], t[:, 1:]


def host_params(cfg, seed=0):
    return jax.tree.map(np.asarray, init_transformer(
        jax.random.PRNGKey(seed), cfg))


@functools.cache
def one_step(cfg, **mesh):
    """Loss and update of one SGD step; kept a (config, mesh), because
    the parity cases each ask for the same one-device step again and
    every asking compiles it."""
    n = int(np.prod(list(mesh.values())))
    mc = MeshConfig(devices=jax.devices()[:n], **mesh)
    before = host_params(cfg)
    params = shard_params(mc, cfg, before)
    opt = optax.sgd(1.0)
    params, _, loss = make_train_step(mc, cfg, opt)(
        params, shard_opt_state(opt, params), *tokens())
    return float(loss), jax.tree.map(
        lambda a, b: b - np.asarray(a), params, before)


# -- the config ------------------------------------------------------- #

@pytest.mark.parametrize("kw,match", [
    (dict(n_layers=4), "whole periods"),
    (dict(n_layers=1), "whole periods"),
    (dict(layer_pattern=()), "layer_pattern, which is empty"),
    (dict(leading_layers=("full",)), "AttentionKind"),
    (dict(leading_mlp="moe"), "leading_mlp"),
    (dict(dense_act="gelu"), "dense_act"),
    (dict(attn_gate="elementwise"), "attn_gate"),
    (dict(router_score="tanh"), "router_score"),
    (dict(shared_expert_d_ff=-1), ">= 0"),
    (dict(moe_dispatch="capacity", expert_act="relu", experts_held=()),
     "dropless expert layer"),
    (dict(layer_pattern=(dataclasses.replace(SLIDING, n_heads=3),) * 4),
     "multiple of n_kv_heads"),
    (dict(leading_layers=(dataclasses.replace(FULL, rotary_share=0.4),)),
     "even number"),
])
def test_config_validation(kw, match):
    with pytest.raises(ValueError, match=match):
        mixed_cfg(**kw)


@pytest.mark.parametrize("kw", [
    dict(name="h", n_heads=-1), dict(name="r", rotary_share=-0.1),
    dict(name="r", rotary_share=1.5)])
def test_attention_kind_validation(kw):
    with pytest.raises(ValueError):
        AttentionKind(**kw)


def test_no_leaf_for_heads_a_layer_does_not_have():
    """A 4-head layer's ``wq`` has 4 heads in the tree beside an 8-head
    layer's 8: one stack over the periods for each position of the
    pattern, and the layers that lead as single blocks."""
    cfg = mixed_cfg(n_layers=9)       # one leading + two periods
    assert cfg.blocks_by_position
    # shapes and structure are all that is read: no value is drawn
    params = jax.eval_shape(
        lambda: init_transformer(jax.random.PRNGKey(0), cfg))
    shapes = jax.tree.map(lambda a: a.shape, params)
    assert len(shapes["blocks"]) == 4 and len(shapes["leading"]) == 1
    for j in range(3):
        blk = shapes["blocks"][j]
        assert blk["wq"] == (1, 2, 32, 8, 8) and blk["wg"] == (1, 2, 32, 8)
        assert blk["wo"] == (1, 2, 8, 8, 32)
    full = shapes["blocks"][3]
    assert full["wq"] == (1, 2, 32, 4, 8) and full["wg"] == (1, 2, 32, 4)
    assert full["wkv"] == (1, 2, 32, 2, 2, 8)
    assert full["router"] == (1, 2, 32, 8)          # all 8 columns
    assert full["w1"] == full["w3"] == (1, 2, 4, 32, 16)     # 4 held
    assert full["ws1"] == full["ws3"] == (1, 2, 32, 24)
    assert full["ws2"] == (1, 2, 24, 32)
    lead = shapes["leading"][0]
    assert lead["wq"] == (32, 4, 8) and lead["wg"] == (32, 4)
    assert lead["w1"] == lead["w3"] == (32, 48) and lead["w2"] == (48, 32)
    assert "router" not in lead and "ws1" not in lead
    assert jax.tree.structure(tr.param_specs(cfg)) \
        == jax.tree.structure(params)
    # the optimizer's state follows: nothing is padded to 8 heads
    moments = jax.eval_shape(optax.adamw(1e-3).init, params)[0].mu
    assert jax.tree.map(lambda a: a.shape, moments) == shapes


def test_kinds_of_one_shape_keep_the_single_stack():
    """Kinds that differ only in window and rotary constants (a
    Mellum-shaped config) build the one ``(pipe, layers, ...)`` stack
    they built before these fields; an OPT-shaped one too."""
    same = tuple(dataclasses.replace(k, n_heads=0)
                 for k in (SLIDING,) * 3 + (FULL,))
    cfg = mixed_cfg(layer_pattern=same, leading_layers=(), n_layers=8,
                    attn_gate="", shared_expert_d_ff=0)
    assert not cfg.blocks_by_position
    blocks = jax.eval_shape(
        lambda: init_transformer(jax.random.PRNGKey(0), cfg))["blocks"]
    assert blocks["wq"].shape == (1, 8, 32, 4, 8)
    assert sorted(blocks) == ["ln1", "ln2", "router", "w1", "w2", "w3",
                              "wkv", "wo", "wq"]
    opt = TransformerConfig(vocab_size=VOCAB, d_model=32, n_heads=4,
                            d_head=8, d_ff=64, n_layers=2, max_seq=T)
    assert sorted(jax.eval_shape(lambda: init_transformer(
        jax.random.PRNGKey(0), opt))["blocks"]) \
        == ["ln1", "ln2", "w1", "w2", "wo", "wqkv"]


# -- rotary over part of the head, by hand --------------------------- #

def test_yarn_frequencies_over_the_rotated_half_by_hand():
    """The published full-attention parameters: theta 500000, factor 64
    over 4,096, beta_fast 64, beta_slow 1, half of a 128-wide head
    rotated.  The ramp is computed over the R = 64 rotated dimensions:
    c(n) = 64 ln(4096 / (2 pi n)) / (2 ln 500000): c(64) = 5.66 -> lo 5;
    c(1) = 15.80 -> hi 16."""
    kind = AttentionKind("full", rope_theta=500000.0, yarn_factor=64,
                         yarn_original_max=4096, yarn_beta_fast=64,
                         yarn_beta_slow=1, rotary_share=0.5,
                         attention_factor=1.4158883083359672)
    c64 = 64 * math.log(4096 / (2 * math.pi * 64)) / (2 * math.log(5e5))
    c1 = 64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(5e5))
    assert (math.floor(c64), math.ceil(c1)) == (5, 16)
    assert kind.rotary_dim(128) == 64
    got = kind.inv_freq(128)
    assert got.shape == (32,)
    base = [500000 ** (-2 * i / 64) for i in range(32)]
    for i in (0, 3, 5):
        assert got[i] == pytest.approx(base[i], rel=1e-12)
    for i in (16, 20, 31):
        assert got[i] == pytest.approx(base[i] / 64, rel=1e-12)
    # dimension 8: r = 3/11 -> (3/11)/64 + 8/11 of its frequency
    assert got[8] == pytest.approx(
        base[8] * ((3 / 11) / 64 + 8 / 11), rel=1e-12)
    # the published attention factor is 0.1 ln(64) + 1
    assert kind.attention_factor == pytest.approx(0.1 * math.log(64) + 1)
    # a sliding layer: the whole head, plain, theta 10000
    plain = AttentionKind("sliding", window=512).inv_freq(128)
    assert plain.shape == (64,)
    assert plain[1] == pytest.approx(10000 ** (-2 / 128))


def test_apply_rope_rotates_the_leading_part_of_the_head():
    x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 3, 16), jnp.float32)
    pos = jnp.arange(8)
    kind = AttentionKind("p", rope_theta=5e5, rotary_share=0.5)
    freqs = kind.inv_freq(16)
    assert freqs.shape == (4,)
    got = np.asarray(tr.apply_rope(x, pos, inv_freq=freqs, scale=1.25))
    # the other half passes through untouched
    np.testing.assert_array_equal(got[..., 8:], np.asarray(x[..., 8:]))
    # the first 8 dimensions: rotate-half within them, by hand
    ang = np.arange(8)[:, None] * freqs[None, :]
    cos, sin = 1.25 * np.cos(ang)[:, None, :], 1.25 * np.sin(ang)[:, None, :]
    x1, x2 = np.asarray(x[..., :4]), np.asarray(x[..., 4:8])
    np.testing.assert_allclose(got[..., :4], x1 * cos - x2 * sin,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[..., 4:8], x1 * sin + x2 * cos,
                               rtol=1e-5, atol=1e-6)


# -- the router ------------------------------------------------------- #

def test_sigmoid_gates_sum_to_the_scale_and_probs_are_a_distribution():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(50, 32), jnp.float32)
    w = jnp.asarray(rng.randn(32, 16), jnp.float32)
    probs, top_i, gates = route_top_k(x, w, 4, "sigmoid", 2.5)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 2.5, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(probs.sum(-1)), 1.0, rtol=1e-6)
    s = 1 / (1 + np.exp(-np.asarray(x) @ np.asarray(w)))
    want = np.argsort(-s, axis=-1)[:, :4]
    np.testing.assert_array_equal(np.asarray(top_i), want)
    picked = np.take_along_axis(s, want, axis=-1)
    np.testing.assert_allclose(
        np.asarray(gates), 2.5 * picked / picked.sum(-1, keepdims=True),
        rtol=1e-5)
    # the softmax router is what it was, and takes the scale too
    _, _, plain = route_top_k(x, w, 4)
    np.testing.assert_allclose(np.asarray(plain.sum(-1)), 1.0, rtol=1e-6)
    _, _, scaled = route_top_k(x, w, 4, scale=2.0)
    np.testing.assert_allclose(np.asarray(scaled), 2 * np.asarray(plain),
                               rtol=1e-6)
    with pytest.raises(ValueError, match="router score"):
        route_top_k(x, w, 4, "tanh")


# -- the layers ------------------------------------------------------- #

def test_leading_layers_and_period_scan_equal_the_unrolled_stack():
    """Nine layers = one leading and two periods scanned by position,
    against the same blocks applied one by one in Python."""
    cfg = mixed_cfg(n_layers=9, remat=False)
    mc = MeshConfig(devices=jax.devices()[:1], data=1)
    params = shard_params(mc, cfg, host_params(cfg, 1))
    x, _ = tokens()

    def unrolled(params, tok):
        h = tr._embed(cfg, params, tok)
        h, _ = tr._block(cfg, h, params["leading"][0], FULL, sparse=False)
        for i in range(8):
            blk = jax.tree.map(lambda a: a[0, i // 4],
                               params["blocks"][i % 4])
            h, _ = tr._block(cfg, h, blk, cfg.layer_pattern[i % 4])
        h = tr._rms_norm(h, params["ln_f"])
        return tr._lm_head(cfg.compute_dtype, h, params["head"])

    want = jax.jit(jax.shard_map(
        unrolled, mesh=mc.mesh, in_specs=(tr.param_specs(cfg), P()),
        out_specs=P(), check_vma=False))(params, x)
    got = make_forward_fn(mc, cfg)(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def _plain_layer(cfg, blk, h, kind, sparse):
    """One layer in plain numpy-style jnp, every expert computed for
    every token: the equations, not the program's moves."""
    def norm(x, s):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + 1e-6) * s

    def swiglu(x, w1, w3, w2):
        return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2

    b, t, d = h.shape
    heads, dh = blk["wq"].shape[1], cfg.d_head
    x = norm(h, blk["ln1"])
    q = (x @ blk["wq"].reshape(d, -1)).reshape(b, t, heads, dh)
    kv = (x @ blk["wkv"].reshape(d, -1)).reshape(b, t, 2, 2, dh)
    rope = dict(inv_freq=kind.inv_freq(dh), scale=kind.attention_factor)
    q = tr.apply_rope(q, jnp.arange(t), **rope)
    k = jnp.repeat(tr.apply_rope(kv[:, :, 0], jnp.arange(t), **rope),
                   heads // 2, axis=2)
    v = jnp.repeat(kv[:, :, 1], heads // 2, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * dh ** -.5
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    allow = (i >= j) & ((i - j < kind.window) if kind.window else True)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(
        jnp.where(allow, s, -jnp.inf), -1), v)
    o = o * jax.nn.sigmoid(x @ blk["wg"])[..., None]
    h = h + o.reshape(b, t, -1) @ blk["wo"].reshape(-1, d)
    x = norm(h, blk["ln2"])
    if not sparse:
        return h + swiglu(x, blk["w1"], blk["w3"], blk["w2"])
    score = jax.nn.sigmoid(x @ blk["router"])
    top_s, top_i = jax.lax.top_k(score, cfg.router_top_k)
    gates = cfg.router_scale * top_s / top_s.sum(-1, keepdims=True)
    gate_of = (jax.nn.one_hot(top_i, cfg.n_experts)
               * gates[..., None]).sum(-2)
    first, held = cfg.experts_held
    y = swiglu(x, blk["ws1"], blk["ws3"], blk["ws2"])
    for e in range(held):
        y = y + gate_of[..., first + e, None] * swiglu(
            x, blk["w1"][e], blk["w3"][e], blk["w2"][e])
    return h + y


def test_each_layer_computes_its_equations():
    """A leading layer (full attention over half-rotated heads, gate,
    dense SwiGLU) and a sliding sparse one (window, 8 heads, sigmoid
    router scaled 2.5, the held experts' part plus the shared expert)
    against the equations written out."""
    cfg = mixed_cfg()
    mc = MeshConfig(devices=jax.devices()[:1], data=1)
    params = host_params(cfg, 3)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, T, 32))
    for blk, kind, sparse in (
            (params["leading"][0], FULL, False),
            (jax.tree.map(lambda a: a[0, 0], params["blocks"][0]),
             SLIDING, True),
            (jax.tree.map(lambda a: a[0, 0], params["blocks"][3]),
             FULL, True)):
        got, _ = jax.jit(jax.shard_map(
            lambda h, blk: tr._block(cfg, h, blk, kind, sparse=sparse),
            mesh=mc.mesh, in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False))(h, blk)
        want = _plain_layer(cfg, blk, h, kind, sparse)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("held,rows", [((0, 4), "all"), ((4, 4), "none")],
                         ids=["every-row-to-held-experts", "no-row-here"])
def test_dropless_at_both_ends_of_imbalance(held, rows):
    """A router whose weights are zero scores every expert 0.5, and the
    top-k takes experts 0 and 1 for every token.  Held here, they get
    all N x k rows (every token routed to the same held experts: the
    greatest imbalance there is) and nothing is dropped; held elsewhere,
    no row is routed here and the layer is its shared expert alone."""
    cfg = mixed_cfg(experts_held=held)
    mc = MeshConfig(devices=jax.devices()[:1], data=1)
    blk = jax.tree.map(lambda a: a[0, 0], host_params(cfg, 5)["blocks"][0])
    blk = dict(blk, router=np.zeros_like(blk["router"]))
    h = jax.random.normal(jax.random.PRNGKey(6), (2, T, 32))
    got, _, chosen = jax.jit(jax.shard_map(
        lambda h, blk: tr._mlp(cfg, h, blk, with_chosen=True),
        mesh=mc.mesh, in_specs=(P(), P()), out_specs=(P(), P(), P()),
        check_vma=False))(h, blk)
    assert (np.sort(np.asarray(chosen), -1) == [0, 1]).all()
    x = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-6)

    def swiglu(w1, w3, w2):
        return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2

    want = h + swiglu(blk["ws1"], blk["ws3"], blk["ws2"])
    if rows == "all":
        # two equal scores normalised and scaled: 1.25 each
        want = want + 1.25 * sum(swiglu(
            blk["w1"][e], blk["w3"][e], blk["w2"][e]) for e in (0, 1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("mesh,fsdp", [
    (dict(expert=4), False), (dict(expert=2, model=2), False),
    (dict(data=2), True)], ids=["expert4", "expert2-model2", "fsdp-data2"])
def test_meshes_match_one_device(mesh, fsdp):
    """The exchange over the expert axis with the shared expert local,
    TP-split heads, gates and products, and the FSDP layout of a tree
    of several stacks give one device's loss and gradients."""
    cfg = mixed_cfg(fsdp=fsdp)
    base = dict(data=2) if fsdp else dict(data=1)
    loss1, grads1 = one_step(dataclasses.replace(cfg, fsdp=False), **base)
    loss, grads = one_step(cfg, **mesh)
    assert loss == pytest.approx(loss1, rel=2e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(grads1)):
        np.testing.assert_allclose(
            a, b, rtol=2e-3, atol=2e-5 * max(1.0, np.abs(b).max()),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("kw,mesh", [
    (dict(n_layers=9), dict(pipe=2)),
    (dict(num_microbatches=2), dict(data=1)),
    (dict(pipeline_schedule="1f1b"), dict(data=1)),
    (dict(leading_layers=(), n_layers=8), dict(pipe=2)),
], ids=["pipe2", "microbatches", "1f1b", "stacks-by-position-pipe2"])
def test_a_pipelined_mesh_is_refused_with_a_sentence(kw, mesh):
    cfg = mixed_cfg(**kw)
    n = int(np.prod(list(mesh.values())))
    mc = MeshConfig(devices=jax.devices()[:n], **mesh)
    with pytest.raises(ValueError, match="unpipelined mesh only"):
        make_train_step(mc, cfg, optax.sgd(1.0))


def test_reshard_train_state_moves_a_tree_of_several_stacks():
    """A state trained on one device continues on another mesh: the
    stacks by position regroup like one stack, the leading blocks are
    placed as they are, and the next step's loss is the same."""
    cfg = mixed_cfg(n_layers=9)
    opt = optax.adamw(1e-3)
    x, y = tokens()

    one = MeshConfig(devices=jax.devices()[:1], data=1)
    # the one-device step and the seeds built once: each
    # ``make_train_step`` is a program of its own to compile
    step, host = make_train_step(one, cfg, opt), host_params(cfg)

    def after_one_step():
        params = shard_params(one, cfg, host)
        return step(params, shard_opt_state(opt, params), x, y)

    params, state, _ = after_one_step()
    want = float(step(params, state, x, y)[2])
    params, state, _ = after_one_step()
    four = MeshConfig(devices=jax.devices()[:4], expert=2, model=2)
    params, state = tr.reshard_train_state(four, cfg, opt, params, state)
    assert params["blocks"][3]["wq"].shape == (1, 2, 32, 4, 8)
    got = float(make_train_step(four, cfg, opt)(params, state, x, y)[2])
    assert got == pytest.approx(want, rel=2e-5)


# -- counters --------------------------------------------------------- #

@pytest.mark.parametrize("mesh", [dict(data=1), dict(data=2, expert=2)],
                         ids=["one", "data2-expert2"])
def test_expert_load_has_a_row_for_each_sparse_layer(mesh):
    cfg = mixed_cfg()
    n = int(np.prod(list(mesh.values())))
    mc = MeshConfig(devices=jax.devices()[:n], **mesh)
    params = shard_params(mc, cfg, host_params(cfg))
    x, _ = tokens()
    load = np.asarray(expert_load(mc, cfg, params, x))
    # the leading dense layer has no row
    assert load.shape == (cfg.n_layers - 1, cfg.n_experts)
    assert (load.sum(axis=1) == cfg.router_top_k * B * T).all()
    chosen = np.asarray(expert_choices(mc, cfg, params, x))
    assert chosen.shape == (cfg.n_layers - 1, B, T, cfg.router_top_k)
    assert (np.diff(np.sort(chosen, axis=-1), axis=-1) > 0).all()
    assert (np.bincount(chosen[0].ravel(), minlength=8) == load[0]).all()
    # leading layers that are sparse are counted too
    sparse = dataclasses.replace(cfg, leading_mlp="sparse")
    params = shard_params(mc, sparse, host_params(sparse))
    assert np.asarray(expert_load(mc, sparse, params, x)).shape == (5, 8)


# -- the paths that do not implement these fields say so ------------- #

_PLAIN = dict(layer_pattern=(), leading_layers=(), attn_gate="",
              dense_act="relu", moe=False, expert_act="relu",
              experts_held=(), router_score="softmax", router_scale=1.0,
              shared_expert_d_ff=0, moe_dispatch="capacity",
              tie_embeddings=True, n_layers=4)


@pytest.mark.parametrize("kw,named", [
    ({}, "leading_layers"),
    (dict(leading_layers=(), n_layers=4), "AttentionKind.n_heads"),
    (dict(_PLAIN, attn_gate="per_head"), "attn_gate"),
    (dict(_PLAIN, dense_act="swiglu"), "dense_act='swiglu'"),
    (dict(_PLAIN, moe=True, moe_dispatch="dropless",
          shared_expert_d_ff=8), "shared_expert_d_ff"),
    (dict(_PLAIN, moe=True, moe_dispatch="dropless",
          router_score="sigmoid"), "router_score='sigmoid'"),
], ids=["leading-layers", "heads-by-kind", "gate", "dense-swiglu",
        "shared-expert", "sigmoid-router"])
def test_decoding_and_serving_refuse_training_only_fields(kw, named):
    from chainermn_tpu.serving.engine import TransformerAdapter

    cfg = mixed_cfg(**kw)
    assert named in cfg.training_only
    mc = MeshConfig(devices=jax.devices()[:1], data=1)
    with pytest.raises(ValueError, match="decoding does not implement") \
            as err:
        make_generate_fn(mc, cfg, max_len=T)
    assert named in str(err.value)
    with pytest.raises(ValueError, match="serving engine does not "
                       "implement") as err:
        TransformerAdapter(mc, cfg)
    assert named in str(err.value)
