"""A transformer whose linear layers are Gated DeltaNet (the scalar-decay
delta rule of ``ops/gdn.py``, fewer key heads than value heads) beside
softmax attention with a norm on q and k, a gate an element and a
quarter of each head rotated; zero-centred norm scales; a gated shared
expert.  The parameter tree, the traced toy step (scopes, counters, no
pair weight a channel), each new field against what it generalises, and
every refusal by name."""

import re

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
    make_generate_fn,
    make_train_step,
    param_specs,
    shard_params,
)
from chainermn_tpu.models import transformer as tr
from chainermn_tpu.parallel import MeshConfig
from chainermn_tpu.training import shard_opt_state
from chainermn_tpu.utils.metrics import MetricsRegistry, set_registry

VOCAB, B, T = 64, 2, 128
GDN = AttentionKind("gdn", mixer="gdn", n_heads=4, key_heads=2, d_key=32,
                    d_value=8)
FULL = AttentionKind("full", n_heads=4, rotary_share=0.25, rope_theta=1e7,
                     qk_norm=True)


def gdn_cfg(**kw):
    base = dict(
        vocab_size=VOCAB, d_model=32, n_heads=4, n_kv_heads=2, d_head=16,
        d_ff=16, n_layers=4, max_seq=T, attention="local", dtype="float32",
        pos_embedding="rope", layer_pattern=(GDN, GDN, GDN, FULL),
        attn_gate="per_element", norm_scale="zero_centred",
        moe=True, n_experts=8, router_top_k=2, moe_dispatch="dropless",
        expert_act="swiglu", experts_held=(2, 4), shared_expert_d_ff=24,
        shared_expert_gate=True, tie_embeddings=False)
    base.update(kw)
    return TransformerConfig(**base)


def one_chip():
    return MeshConfig(devices=jax.devices()[:1], data=1)


def tokens(b=B):
    t = jnp.asarray(np.random.RandomState(0).randint(
        0, VOCAB, (b, T + 1)), jnp.int32)
    return t[:, :-1], t[:, 1:]


# -- the tree ---------------------------------------------------------- #

def test_the_tree_has_each_kinds_leaves_at_their_shapes():
    cfg = gdn_cfg()
    assert cfg.blocks_by_position and cfg.mixers == ["gdn"]
    shapes = jax.eval_shape(
        lambda: init_transformer(jax.random.PRNGKey(0), cfg))
    gdn, full = shapes["blocks"][0], shapes["blocks"][3]
    lead = (1, 1)
    assert {k: v.shape[2:] for k, v in gdn.items() if k in
            tr.mixers.MIXERS["gdn"].leaves + ("wo", "ln1")} == {
        # [q | k | v | z] and [b | a]; the convolution over q, k and v
        "w_in": (32, 2 * 2 * 32 + 2 * 4 * 8), "w_ba": (32, 8),
        "conv": (2 * 2 * 32 + 4 * 8, 4), "a_log": (4,), "dt_bias": (4,),
        "o_norm": (8,), "wo": (4, 8, 32), "ln1": (32,)}
    assert gdn["w_in"].shape[:2] == lead and "wg" not in gdn
    assert {k: full[k].shape[2:] for k in
            ("wq", "wkv", "wg", "q_norm", "k_norm", "wo")} == {
        "wq": (32, 4, 16), "wkv": (32, 2, 2, 16), "wg": (32, 4, 16),
        "q_norm": (16,), "k_norm": (16,), "wo": (4, 16, 32)}
    assert full["wsg"].shape[2:] == gdn["wsg"].shape[2:] == (32, 1)
    specs = param_specs(cfg)
    assert jax.tree.structure(specs) == jax.tree.structure(shapes)
    assert specs["blocks"][0]["w_in"] == P("pipe")
    # zero-centred scales are seeded 0; the linear layer's output norm
    # is plain and seeded 1
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    for leaf in ("ln1", "ln2", "q_norm", "k_norm"):
        assert not np.asarray(params["blocks"][3][leaf]).any()
    assert not np.asarray(params["ln_f"]).any()
    assert (np.asarray(params["blocks"][0]["o_norm"]) == 1).all()
    plain = init_transformer(jax.random.PRNGKey(0),
                             gdn_cfg(norm_scale="plain"))
    assert (np.asarray(plain["blocks"][3]["q_norm"]) == 1).all()


# -- the step, traced once -------------------------------------------- #

@pytest.fixture(scope="module")
def traced():
    """The toy step lowered once: its text and the counters its trace
    left."""
    cfg, mc, opt = gdn_cfg(), one_chip(), optax.sgd(0.1)
    shapes = jax.eval_shape(
        lambda: init_transformer(jax.random.PRNGKey(0), cfg))
    reg = MetricsRegistry(enabled=True)
    prev = set_registry(reg)
    try:
        lowered = make_train_step(mc, cfg, opt).lower(
            shapes, jax.eval_shape(opt.init, shapes), *tokens())
    finally:
        set_registry(prev)
    return lowered.as_text(debug_info=True), reg


def test_toy_step_holds_no_pair_weight_a_channel(traced):
    """Three Gated DeltaNet layers of 2 sequences x 4 value heads x 2
    chunks: every chunk and value head a system, no KDA pair kernel, and
    in the lowered step no array with a chunk's rows, a chunk's columns
    AND the key channels in it (KDA's ``(C, C, d_k)`` exponents; here
    the pairs are ``(C, C)`` from one product a key head)."""
    text, reg = traced
    assert reg.counter("gdn/chunks").value == 3 * 2
    assert reg.counter("gdn/systems_inverted").value == 3 * 2 * 4 * 2
    assert reg.counter("gdn/state_bytes_kept").value \
        == 3 * 1 * 2 * 4 * 32 * 8 * 4
    assert reg.counter("kda/pair_blocks_in_vmem").value == 0
    shapes = set(re.findall(r"tensor<([0-9x]+)xf32>", text))
    assert any(s.endswith("x64x64") for s in shapes)        # the pairs
    assert any(s.endswith("x64x32") for s in shapes)        # the keys
    assert not [s for s in shapes if re.search(
        r"(^|x)64x64x32$|(^|x)64x32x64$|(^|x)32x64x64$", s)]


def test_toy_step_wears_every_new_scope(traced):
    from chainermn_tpu.utils.telemetry import (
        DEVICE_SCOPES_GDN, classify_op_name)

    text, _ = traced
    for scope in DEVICE_SCOPES_GDN + ("attn/gdn", "attn/full", "kda.solve",
                                      "attn.gate", "moe/shared"):
        assert scope in text, scope
    assert classify_op_name(
        "jit(step)/transpose(jvp(step/layers))/while/body/checkpoint/"
        "attn/gdn/gdn/scan/while/body/checkpoint/rematted_computation/"
        "gdn/scan/kda.solve/pallas_call") == (
        "recompute", ("step/layers", "attn/gdn", "gdn/scan", "kda.solve"))
    assert classify_op_name(
        "jit(step)/jvp(step/layers)/attn/full/attn.qk_norm/mul") == (
        "forward", ("step/layers", "attn/full", "attn.qk_norm"))


def test_the_step_trains_and_decay_acts_on_the_stored_scale():
    """Three steps lower the loss; and under AdamW's weight decay alone
    (no gradient) a zero-centred scale's stored ``w`` is what shrinks:
    the applied scale ``1 + w`` is pulled to 1 and not to 0."""
    # one layer of each mixer, not three and one: that the loss falls is
    # not asked of the depth, and each position is a body to compile
    cfg, mc = gdn_cfg(layer_pattern=(GDN, FULL), n_layers=2), one_chip()
    params = shard_params(mc, cfg, init_transformer(
        jax.random.PRNGKey(0), cfg))
    opt = optax.adamw(3e-3)
    state = shard_opt_state(opt, params)
    step = make_train_step(mc, cfg, opt)
    losses = []
    for _ in range(3):
        params, state, loss = step(params, state, *tokens())
        losses.append(float(loss))
    assert losses[2] < losses[1] < losses[0]
    w = {"ln_f": jnp.full((4,), 0.5)}
    decay = optax.adamw(1e-1, weight_decay=1.0)
    updates, _ = decay.update(jax.tree.map(jnp.zeros_like, w),
                              decay.init(w), w)
    after = optax.apply_updates(w, updates)["ln_f"]
    x = jnp.arange(1.0, 5.0)[None]
    zero = gdn_cfg()
    np.testing.assert_allclose(after, 0.45, rtol=1e-6)
    np.testing.assert_allclose(
        tr._norm(zero, x, after), tr._rms_norm(x, 1.45, zero.norm_eps))
    np.testing.assert_allclose(
        tr._norm(gdn_cfg(norm_scale="plain"), x, after),
        tr._rms_norm(x, 0.45))


# -- each field against what it generalises --------------------------- #

def _layer(cfg, fn, h, blk):
    mc = one_chip()
    return jax.jit(jax.shard_map(
        lambda h, blk: fn(cfg, h, blk), mesh=mc.mesh,
        in_specs=(P(), P()), out_specs=P(), check_vma=False))(h, blk)


def test_an_element_gate_of_equal_elements_is_the_gate_a_head():
    """``attn_gate="per_element"`` with every element of a head given
    the head's one column is ``"per_head"``; and the q/k norm at a scale
    of zero (``1 + 0``) and ``rotary_share`` 0.25 are the layer's own."""
    cfg = gdn_cfg()
    blk = jax.tree.map(lambda a: a[0, 0], init_transformer(
        jax.random.PRNGKey(1), cfg)["blocks"][3])
    h = jax.random.normal(jax.random.PRNGKey(2), (B, T, 32))
    per_head = jax.random.normal(jax.random.PRNGKey(3), (32, 4)) * 0.3
    attend = lambda c, h, b: tr._attention(c, h, b, FULL)   # noqa: E731
    want = _layer(gdn_cfg(attn_gate="per_head"), attend, h,
                  dict(blk, wg=per_head))
    got = _layer(cfg, attend, h, dict(blk, wg=jnp.broadcast_to(
        per_head[..., None], (32, 4, 16))))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    differs = _layer(cfg, attend, h, blk)
    assert float(jnp.abs(differs - want).max()) > 1e-3
    # a scale that is not zero moves the result: the norm is applied
    scaled = _layer(cfg, attend, h, dict(blk, q_norm=blk["q_norm"] + 0.5))
    assert float(jnp.abs(scaled - differs).max()) > 1e-4


def test_the_shared_experts_gate_is_one_scalar_a_token():
    """At ``wsg`` 0 the sigmoid is a half for every token: the shared
    expert adds half of what the ungated layer's adds."""
    cfg = gdn_cfg()
    blk = jax.tree.map(lambda a: a[0, 0], init_transformer(
        jax.random.PRNGKey(1), cfg)["blocks"][0])
    h = jax.random.normal(jax.random.PRNGKey(2), (B, T, 32))
    mlp = lambda c, h, b: tr._mlp(c, h, b)[0]      # noqa: E731
    ungated = {k: v for k, v in blk.items() if k != "wsg"}
    want = _layer(gdn_cfg(shared_expert_gate=False), mlp, h, ungated)
    none = _layer(gdn_cfg(shared_expert_d_ff=0, shared_expert_gate=False),
                  mlp, h, {k: v for k, v in ungated.items()
                           if not k.startswith("ws")})
    half = _layer(cfg, mlp, h, dict(blk, wsg=jnp.zeros((32, 1))))
    np.testing.assert_allclose(half - none, 0.5 * (want - none),
                               rtol=1e-4, atol=1e-6)
    # and a gate that differs by token moves it off that half
    seeded = _layer(cfg, mlp, h, blk)
    assert float(jnp.abs(seeded - half).max()) > 1e-4


# -- refusals, by name -------------------------------------------------- #

@pytest.mark.parametrize("kw,match", [
    (dict(mixer="gdn", n_heads=4, key_heads=0, d_key=16, d_value=8),
     "gdn needs its own n_heads"),
    (dict(mixer="gdn", n_heads=4, key_heads=3, d_key=16, d_value=8),
     "whole groups of value heads a key head"),
    (dict(mixer="gdn", n_heads=4, key_heads=2, d_key=16, d_value=8,
          conv_taps=0), "gdn needs conv_taps >= 1"),
    (dict(mixer="gdn", n_heads=4, key_heads=2, d_key=16, d_value=8,
          qk_norm=True), "qk_norm are the softmax mixer's"),
    (dict(mixer="kda", qk_norm=True), "qk_norm are the softmax mixer's"),
])
def test_kind_validation(kw, match):
    with pytest.raises(ValueError, match=match):
        AttentionKind("x", **kw)
    assert tr.MIXERS == ("softmax", "mla", "kda", "mamba2", "gdn",
                         "shortconv")


@pytest.mark.parametrize("kw,match", [
    (dict(attn_gate="per_token"),
     r"not in \('', per_head, per_element\)"),
    (dict(norm_scale="centred"), r"not in \(plain, zero_centred\)"),
    (dict(shared_expert_d_ff=0), "shared_expert_gate gates the shared"),
    (dict(layer_pattern=(GDN,) * 4), "attn_gate is softmax attention's"),
])
def test_config_validation(kw, match):
    with pytest.raises(ValueError, match=match):
        gdn_cfg(**kw)


@pytest.mark.parametrize("mesh,kw,match", [
    (dict(seq=2), {}, "the gdn layers run whole on a device"),
    (dict(model=2), {}, "seq, model and pipe mesh axes must be 1"),
    (dict(pipe=2), dict(n_layers=8), "the gdn layers run whole"),
    (dict(data=1), dict(attention="ring"), "attention='flash' or 'local'"),
    (dict(data=2), dict(fsdp=True),
     "fsdp=True is not implemented for the gdn layers"),
], ids=["seq", "model", "pipe", "ring", "fsdp"])
def test_meshes_and_paths_the_mixer_cannot_run_are_refused(mesh, kw, match):
    n = int(np.prod(list(mesh.values())))
    mc = MeshConfig(devices=jax.devices()[:n], **mesh)
    with pytest.raises(ValueError, match=match):
        make_train_step(mc, gdn_cfg(**kw), optax.sgd(1.0))


@pytest.mark.parametrize("named", [
    "AttentionKind.mixer=gdn", "AttentionKind.qk_norm", "attn_gate",
    "norm_scale='zero_centred'", "shared_expert_gate"])
def test_decoding_and_serving_refuse_the_new_fields(named):
    from chainermn_tpu.serving.engine import TransformerAdapter

    cfg = gdn_cfg()
    assert named in cfg.training_only
    with pytest.raises(ValueError, match="decoding does not implement") \
            as err:
        make_generate_fn(one_chip(), cfg, max_len=T)
    assert named in str(err.value)
    with pytest.raises(ValueError, match="serving engine does not "
                       "implement") as err:
        TransformerAdapter(one_chip(), cfg)
    assert named in str(err.value)
