"""The seam between the model and its mixers (``models/mixers.py``): a
mixer the table has never heard of trains with no edit of
``models/transformer.py``; each of the five records names the leaves its
``init`` and its ``specs`` really return; and the arrows point one way
(``transformer -> mixers``)."""

import ast
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from chainermn_tpu.models import (
    AttentionKind,
    TransformerConfig,
    init_transformer,
    make_train_step,
    mixers,
    param_specs,
    shard_params,
)
from chainermn_tpu.parallel import MeshConfig
from chainermn_tpu.training import shard_opt_state

VOCAB, B, T = 64, 4, 16


def toy_cfg(pattern, **kw):
    base = dict(
        vocab_size=VOCAB, d_model=32, n_heads=4, n_kv_heads=2, d_head=8,
        d_ff=48, n_layers=len(pattern), max_seq=T, attention="local",
        dtype="float32", pos_embedding="rope", layer_pattern=pattern)
    base.update(kw)
    return TransformerConfig(**base)


def tokens():
    t = jnp.asarray(np.random.RandomState(0).randint(
        0, VOCAB, (B, T + 1)), jnp.int32)
    return t[:, :-1], t[:, 1:]


# -- a mixer the model has never heard of ------------------------------ #

def _fake_apply(cfg, x, blk, kind):
    # one product to heads of ``out_width``, then the model's own ``wo``
    # back; no inner scope: ``attn/<kind.name>`` is ``_attention``'s
    o = jnp.einsum("btd,dhv->bthv", x, blk["w"].astype(x.dtype))
    return o.reshape(*x.shape[:2], -1) @ blk["wo"].reshape(
        -1, x.shape[-1]).astype(x.dtype)


FAKE = mixers.Mixer(
    leaves=("w",),
    init=lambda key, ks, cfg, kind: {"w": mixers._dense_init(
        ks[0], (cfg.d_model, cfg.heads_of(kind), 6), cfg.d_model)},
    specs=lambda cfg, kind, mha: {"w": P("pipe")},
    apply=_fake_apply,
    out_width=lambda cfg, kind: 6)


def _losses(mc, cfg, host, steps=3):
    params = shard_params(mc, cfg, host)
    opt = optax.adamw(1e-2)
    state = shard_opt_state(opt, params)
    step = make_train_step(mc, cfg, opt)
    out = []
    for _ in range(steps):
        params, state, loss = step(params, state, *tokens())
        out.append(float(loss))
    return out, params


def test_a_mixer_put_into_the_table_trains_with_no_edit_of_the_model(
        monkeypatch):
    with pytest.raises(ValueError, match=r"mixer 'fake' not in \(softmax, "
                       "mla, kda, mamba2, gdn\\)"):
        AttentionKind("f", mixer="fake")
    monkeypatch.setitem(mixers.MIXERS, "fake", FAKE)
    kind = AttentionKind("f", mixer="fake", n_heads=2)
    cfg = toy_cfg((kind, AttentionKind("full")))
    assert cfg.mixers == ["fake"] and cfg.blocks_by_position
    assert kind.tree == ("both", "fake")
    # on the host: the step donates what ``shard_params`` placed
    host = jax.tree.map(np.asarray, init_transformer(
        jax.random.PRNGKey(0), cfg))
    fake = host["blocks"][0]
    assert {k: v.shape[2:] for k, v in fake.items()
            if k in ("ln1", "w", "wo")} == {
        "ln1": (32,), "w": (32, 2, 6), "wo": (2, 6, 32)}
    assert "wqkv" not in fake and "wq" not in fake
    specs = param_specs(cfg)["blocks"][0]
    assert specs["w"] == specs["wo"] == P("pipe")   # whole, like its leaves
    assert set(specs) == set(fake)

    one, params = _losses(
        MeshConfig(devices=jax.devices()[:1], data=1), cfg, host)
    two, _ = _losses(MeshConfig(devices=jax.devices()[:2], data=2), cfg, host)
    assert np.isfinite(one).all() and one[-1] < one[0]
    np.testing.assert_allclose(one, two, rtol=1e-5)
    # the step moved the mixer's own leaf and the projection back
    for name in ("w", "wo"):
        assert float(jnp.abs(
            params["blocks"][0][name] - fake[name]).max()) > 1e-4


def test_a_fake_mixer_is_held_to_the_meshes_the_unsplit_mixers_run_on(
        monkeypatch):
    monkeypatch.setitem(mixers.MIXERS, "fake", FAKE)
    cfg = toy_cfg((AttentionKind("f", mixer="fake"),) * 2)
    with pytest.raises(ValueError, match="the fake layers run whole on a "
                       "device"):
        make_train_step(MeshConfig(devices=jax.devices()[:2], model=2),
                        cfg, optax.sgd(1.0))


# -- a record says what it builds -------------------------------------- #

KINDS = {
    "softmax": AttentionKind("full", n_heads=4, qk_norm=True),
    "mla": AttentionKind("mla", mixer="mla", kv_latent=16, d_shared_key=4,
                         d_value=6),
    "kda": AttentionKind("kda", mixer="kda"),
    "mamba2": AttentionKind("m", mixer="mamba2", n_heads=4, ssm_head_dim=8,
                            ssm_state=16, ssm_groups=2),
    "gdn": AttentionKind("gdn", mixer="gdn", n_heads=4, key_heads=2,
                         d_key=16, d_value=8),
}


def test_the_table_holds_the_five_in_the_order_the_messages_print():
    assert tuple(mixers.MIXERS) == tuple(KINDS)


@pytest.mark.parametrize("name", list(KINDS))
def test_leaves_init_and_specs_agree(name):
    """What ``_MIXER_LEAVES`` only implied: the leaves a record names are
    the keys its ``init`` returns and the keys its ``specs`` returns.
    Softmax attention's depend on the config (fused or grouped heads, a
    gate, a norm on q and k): each variant's are among the record's, and
    together they are all of them."""
    mixer, kind = mixers.MIXERS[name], KINDS[name]
    variants = [toy_cfg((kind,))]
    if name == "softmax":
        variants = [
            toy_cfg((kind,), attn_gate="per_head"),          # wq, wkv
            toy_cfg((kind,), n_kv_heads=4)]                  # wqkv
    seen = set()
    for cfg in variants:
        key = jax.random.PRNGKey(0)
        made = jax.eval_shape(
            lambda: mixer.init(key, jax.random.split(key, 6), cfg, kind))
        mha = cfg.kv_heads == cfg.heads_of(kind)
        assert set(made) == set(mixer.specs(cfg, kind, mha))
        assert set(made) <= set(mixer.leaves)
        seen |= set(made)
        # and the model's block has them, its norm and ``wo`` beside them
        blocks = jax.eval_shape(
            lambda: init_transformer(key, cfg))["blocks"]
        assert set(blocks) - {"ln2", "w1", "w2"} == set(made) | {
            "ln1", "wo"}
        assert blocks["wo"].shape[2:] == (
            cfg.heads_of(kind), mixer.out_width(cfg, kind), cfg.d_model)
    assert seen == set(mixer.leaves)


@pytest.mark.parametrize("name,shaping", [
    ("softmax", (True,)),           # qk_norm
    ("mla", (16, 4, 6)),            # kv_latent, d_shared_key, d_value
    ("kda", (4,)),                  # conv_taps
    ("mamba2", (8, 16, 2, 4)),      # head dim, state, groups, conv_taps
    ("gdn", (2, 16, 8, 4)),         # key_heads, d_key, d_value, conv_taps
])
def test_a_kinds_tree_names_its_mixer_and_what_shapes_its_leaves(
        name, shaping):
    kind = KINDS[name]
    assert kind.tree == ("both", name) + shaping
    assert mixers.MIXERS[name].tree(kind) == shaping
    assert dataclasses.replace(kind, part="mlp").tree == ("mlp",)


# -- the arrows point one way ------------------------------------------ #

def test_mixers_imports_nothing_of_the_model():
    with open(mixers.__file__) as f:
        tree = ast.parse(f.read())
    imported = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            # a relative import's module, and the names taken from it
            base = "." * node.level + (node.module or "")
            imported += [base] + [f"{base}.{a.name}" for a in node.names]
    assert imported, "no imports read"
    assert not [m for m in imported if "transformer" in m.split(".")], (
        "models/mixers.py imports models/transformer.py")
    # every import of the package is of ops, parallel or utils
    assert {m.split(".")[1] for m in imported
            if m.startswith("chainermn_tpu.")} <= {"ops", "parallel", "utils"}
