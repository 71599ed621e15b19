"""A transformer whose layers differ by kind (``layer_pattern``), with
the dropless expert layer holding a share of the router's experts and
an untied head: the config's validation, the rotary frequencies by
hand, the layer scan over periods against an unrolled stack, meshes
against one device, the counters, and the refusal of the paths that do
not implement these fields."""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chainermn_tpu.models import (
    AttentionKind,
    TransformerConfig,
    expert_buffer_rows,
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
from chainermn_tpu.training import shard_opt_state

VOCAB, B, T = 64, 4, 64
SLIDING = AttentionKind("sliding", window=16, rope_theta=5e5)
FULL = AttentionKind("full", rope_theta=5e5, yarn_factor=16,
                     yarn_original_max=32, attention_factor=1.2772588722239782)


def typed_cfg(**kw):
    base = dict(
        vocab_size=VOCAB, d_model=32, n_heads=4, n_kv_heads=2, d_head=8,
        d_ff=16, n_layers=4, max_seq=T, attention="local", dtype="float32",
        pos_embedding="rope", layer_pattern=(SLIDING,) * 3 + (FULL,),
        moe=True, n_experts=8, router_top_k=2, moe_dispatch="dropless",
        expert_act="swiglu", experts_held=(2, 4), tie_embeddings=False)
    base.update(kw)
    return TransformerConfig(**base)


def tokens(seed=0):
    t = jnp.asarray(np.random.RandomState(seed).randint(
        0, VOCAB, (B, T + 1)), jnp.int32)
    return t[:, :-1], t[:, 1:]


@functools.cache
def one_step(cfg, **mesh):
    """Loss and update of one SGD step; kept a (config, mesh), because
    the parity cases each ask for the same one-device step again and
    every asking compiles it."""
    n = int(np.prod(list(mesh.values())))
    mc = MeshConfig(devices=jax.devices()[:n], **mesh)
    params = shard_params(mc, cfg, init_transformer(
        jax.random.PRNGKey(0), cfg, pipe_size=mesh.get("pipe", 1)))
    before = jax.tree.map(np.asarray, params)
    opt = optax.sgd(1.0)
    params, _, loss = make_train_step(mc, cfg, opt)(
        params, shard_opt_state(opt, params), *tokens())
    return float(loss), jax.tree.map(
        lambda a, b: b - np.asarray(a), params, before)


# -- the config ------------------------------------------------------- #

@pytest.mark.parametrize("kw,match", [
    (dict(pos_embedding="learned"), "rope"),
    (dict(attention_window=8), "each kind's own"),
    (dict(n_layers=6), "whole periods"),
    (dict(layer_pattern=("sliding", "full")), "AttentionKind"),
    (dict(moe_dispatch="sorted"), "moe_dispatch"),
    (dict(expert_act="gelu"), "expert_act"),
    (dict(moe_dispatch="capacity"), "dropless"),
    (dict(moe_dispatch="capacity", expert_act="relu"), "experts_held"),
    (dict(experts_held=(6, 4)), "not a range"),
    (dict(moe=False), "dropless"),
])
def test_config_validation(kw, match):
    with pytest.raises(ValueError, match=match):
        typed_cfg(**kw)


@pytest.mark.parametrize("kw", [
    dict(name=""), dict(name="a/b"), dict(name="w", window=-1),
    dict(name="t", rope_theta=1.0),
    dict(name="y", yarn_factor=4.0), dict(name="y", yarn_factor=0.5,
                                          yarn_original_max=64)])
def test_attention_kind_validation(kw):
    with pytest.raises(ValueError):
        AttentionKind(**kw)


def test_an_opt_shaped_config_builds_the_tree_it_did():
    cfg = TransformerConfig(vocab_size=VOCAB, d_model=32, n_heads=4,
                            d_head=8, d_ff=64, n_layers=2, max_seq=T)
    assert cfg.training_only == []
    # names and shapes are all these two read: no value is drawn
    params = jax.eval_shape(
        lambda: init_transformer(jax.random.PRNGKey(0), cfg))
    assert sorted(params) == ["blocks", "embed", "ln_f", "pos"]
    assert sorted(params["blocks"]) == ["ln1", "ln2", "w1", "w2", "wo", "wqkv"]


def test_parameter_tree_of_the_share():
    cfg = typed_cfg()
    params = jax.eval_shape(
        lambda: init_transformer(jax.random.PRNGKey(0), cfg))
    blocks = jax.tree.map(lambda a: a.shape, params["blocks"])
    assert blocks["router"] == (1, 4, 32, 8)          # all 8 columns
    assert blocks["w1"] == blocks["w3"] == (1, 4, 4, 32, 16)   # 4 held
    assert blocks["w2"] == (1, 4, 4, 16, 32)
    assert params["head"].shape == params["embed"].shape == (VOCAB, 32)
    assert "pos" not in params
    assert jax.tree.structure(tr.param_specs(cfg)) \
        == jax.tree.structure(params)


# -- rotary frequencies by hand -------------------------------------- #

def test_plain_rope_frequencies():
    got = AttentionKind("s", rope_theta=500000.0).inv_freq(128)
    assert got.shape == (64,)
    assert got[0] == 1.0
    assert got[1] == pytest.approx(500000 ** (-2 / 128))
    assert got[63] == pytest.approx(500000 ** (-126 / 128))


def test_yarn_frequencies_by_hand():
    """The published full-attention parameters: theta 500000, factor
    16 over 8,192, beta_fast 32, beta_slow 1, head_dim 128.
    c(n) = 128 ln(8192 / (2 pi n)) / (2 ln 500000): c(32) = 18.08 ->
    lo 18; c(1) = 34.98 -> hi 35.  Dimensions up to 18 keep their
    frequency, from 35 on it is divided by 16, linear in between."""
    kind = AttentionKind("full", rope_theta=500000.0, yarn_factor=16,
                         yarn_original_max=8192, yarn_beta_fast=32,
                         yarn_beta_slow=1,
                         attention_factor=1.2772588722239782)
    c32 = 128 * math.log(8192 / (2 * math.pi * 32)) / (2 * math.log(5e5))
    c1 = 128 * math.log(8192 / (2 * math.pi)) / (2 * math.log(5e5))
    assert (math.floor(c32), math.ceil(c1)) == (18, 35)
    got = kind.inv_freq(128)
    base = [500000 ** (-2 * i / 128) for i in range(64)]
    for i in (0, 7, 18):
        assert got[i] == pytest.approx(base[i], rel=1e-12)
    for i in (35, 50, 63):
        assert got[i] == pytest.approx(base[i] / 16, rel=1e-12)
    # dimension 20: r = 2/17 -> (2/17)/16 + 15/17 of its frequency
    assert got[20] == pytest.approx(
        base[20] * ((2 / 17) / 16 + 15 / 17), rel=1e-12)
    assert got[26] == pytest.approx(
        base[26] * ((8 / 17) / 16 + 9 / 17), rel=1e-12)
    # the published attention factor is 0.1 ln(16) + 1
    assert kind.attention_factor == pytest.approx(0.1 * math.log(16) + 1)


def test_apply_rope_with_a_kinds_frequencies_and_factor():
    x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 2, 8), jnp.float32)
    pos = jnp.arange(8)
    plain = tr.apply_rope(x, pos, theta=5e5)
    same = tr.apply_rope(x, pos, inv_freq=SLIDING.inv_freq(8))
    np.testing.assert_allclose(np.asarray(same), np.asarray(plain),
                               rtol=1e-6, atol=1e-6)
    scaled = tr.apply_rope(x, pos, inv_freq=SLIDING.inv_freq(8), scale=1.25)
    np.testing.assert_allclose(np.asarray(scaled), 1.25 * np.asarray(plain),
                               rtol=1e-6, atol=1e-6)


# -- the layer scan over periods ------------------------------------- #

def test_period_scan_equals_the_unrolled_stack():
    """Eight layers = two periods scanned, each layer under its own
    kind, against the same blocks applied one by one in Python."""
    cfg = typed_cfg(n_layers=8, remat=False)
    mc = MeshConfig(devices=jax.devices()[:1], data=1)
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(1), cfg))
    x, _ = tokens()

    def unrolled(params, tok):
        h = tr._embed(cfg, params, tok)
        for i in range(cfg.n_layers):
            blk = jax.tree.map(lambda a: a[0, i], params["blocks"])
            h, _ = tr._block(cfg, h, blk, cfg.layer_pattern[i % 4])
        h = tr._rms_norm(h, params["ln_f"])
        return tr._lm_head(cfg.compute_dtype, h, params["head"])

    from jax.sharding import PartitionSpec as P
    want = jax.jit(jax.shard_map(
        unrolled, mesh=mc.mesh, in_specs=(tr.param_specs(cfg), P()),
        out_specs=P(), check_vma=False))(params, x)
    got = make_forward_fn(mc, cfg)(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_a_window_shorter_than_the_sequence_matters():
    """The sliding layers really are windowed: widening the window to
    the whole sequence changes the loss."""
    windowed, _ = one_step(typed_cfg())
    wide = dataclasses.replace(SLIDING, window=0)
    full, _ = one_step(typed_cfg(layer_pattern=(wide,) * 3 + (FULL,)))
    assert abs(windowed - full) > 1e-4


@pytest.mark.parametrize("mesh", [
    dict(expert=4), dict(expert=2, model=2), dict(pipe=2)],
    ids=["expert4", "expert2-model2", "pipe2"])
def test_meshes_match_one_device(mesh):
    """The exchange over the expert axis, the TP-split grouped products
    and pipeline stages of whole periods give one device's loss and
    gradients (``n_layers`` 8 so that a stage holds a whole period)."""
    cfg = typed_cfg(n_layers=8) if "pipe" in mesh else typed_cfg()
    if "pipe" in mesh:
        cfg = dataclasses.replace(cfg, num_microbatches=2)
    loss1, grads1 = one_step(cfg, data=1)
    loss, grads = one_step(cfg, **mesh)
    assert loss == pytest.approx(loss1, rel=2e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(grads1)):
        b = b.reshape(a.shape)
        np.testing.assert_allclose(
            a, b, rtol=2e-3, atol=2e-5 * max(1.0, np.abs(b).max()),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("schedule", ["1f1b"])
def test_untied_head_through_the_1f1b_schedule(schedule):
    cfg = typed_cfg(n_layers=8, num_microbatches=2)
    loss1, grads1 = one_step(cfg, data=1)
    loss, grads = one_step(
        dataclasses.replace(cfg, pipeline_schedule=schedule), pipe=2)
    assert loss == pytest.approx(loss1, rel=2e-5)
    for name in ("head", "embed"):
        np.testing.assert_allclose(grads[name], grads1[name],
                                   rtol=2e-3, atol=2e-6)


# -- counters --------------------------------------------------------- #

@pytest.mark.parametrize("mesh", [dict(data=1), dict(data=2, expert=2)],
                         ids=["one", "data2-expert2"])
def test_expert_load_sums_to_k_times_tokens(mesh):
    cfg = typed_cfg()
    n = int(np.prod(list(mesh.values())))
    mc = MeshConfig(devices=jax.devices()[:n], **mesh)
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
    x, _ = tokens()
    load = np.asarray(expert_load(mc, cfg, params, x))
    assert load.shape == (cfg.n_layers, cfg.n_experts)
    assert (load.sum(axis=1) == cfg.router_top_k * B * T).all()
    chosen = np.asarray(expert_choices(mc, cfg, params, x))
    assert chosen.shape == (cfg.n_layers, B, T, cfg.router_top_k)
    # a token's k choices are k different experts
    assert (np.diff(np.sort(chosen, axis=-1), axis=-1) > 0).all()
    assert (np.bincount(chosen[0].ravel(), minlength=8) == load[0]).all()


@pytest.mark.parametrize("mesh", [dict(data=1), dict(data=1, expert=2)],
                         ids=["one", "expert2"])
def test_expert_buffer_rows_names_the_rung_each_layer_takes(mesh):
    """2 of 8 experts held: the sorted buffer has half the (token,
    choice) rows or all of them, by the rows held, a layer; counted by
    the function the layer itself calls, for the member that holds
    most."""
    from chainermn_tpu.parallel.expert import _buffer_rungs

    cfg = typed_cfg(experts_held=(2, 2))
    n = int(np.prod(list(mesh.values())))
    mc = MeshConfig(devices=jax.devices()[:n], **mesh)
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
    x, _ = tokens()
    rows = np.asarray(expert_buffer_rows(mc, cfg, params, x))
    assert rows.shape == (cfg.n_layers, 2)
    held = np.asarray(expert_load(mc, cfg, params, x))[:, 2:4].sum(axis=1)
    rungs = _buffer_rungs(cfg.router_top_k * B * T // n, 2, cfg.n_experts)
    assert len(rungs) == 2
    if n == 1:
        assert (rows[:, 0] == held).all()
    else:   # the fullest member holds at least its share of them
        assert (rows[:, 0] <= held).all() and (rows[:, 0] * n >= held).all()
    assert (rows[:, 1] == np.where(
        rows[:, 0] <= rungs[0], rungs[0], rungs[1])).all()


def test_expert_load_needs_the_dropless_layer():
    cfg = TransformerConfig(
        vocab_size=VOCAB, d_model=32, n_heads=4, d_head=8, d_ff=16,
        n_layers=2, max_seq=T, attention="local", moe=True, n_experts=4)
    mc = MeshConfig(devices=jax.devices()[:1], data=1)
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
    with pytest.raises(ValueError, match="dropless"):
        expert_load(mc, cfg, params, tokens()[0])


# -- the paths that do not implement these fields say so ------------- #

@pytest.mark.parametrize("cfg,named", [
    (typed_cfg(), "layer_pattern"),
    (typed_cfg(layer_pattern=(), experts_held=()), "dropless"),
    (typed_cfg(layer_pattern=(), moe=False, expert_act="relu",
               experts_held=(), moe_dispatch="capacity"),
     "tie_embeddings=False"),
    (typed_cfg(layer_pattern=(), tie_embeddings=True), "experts_held"),
], ids=["layer-pattern", "dropless", "untied-head", "experts-held"])
def test_decoding_and_serving_refuse_training_only_fields(cfg, named):
    from chainermn_tpu.serving.engine import TransformerAdapter

    mc = MeshConfig(devices=jax.devices()[:1], data=1)
    with pytest.raises(ValueError, match="decoding does not implement") \
            as err:
        make_generate_fn(mc, cfg, max_len=T)
    assert named in str(err.value)
    with pytest.raises(ValueError, match="serving engine does not "
                       "implement") as err:
        TransformerAdapter(mc, cfg)
    assert named in str(err.value)
