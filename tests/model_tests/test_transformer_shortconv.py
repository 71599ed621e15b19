"""A transformer whose mixer is the doubly gated short convolution
(``ops/recurrent.py`` ``gated_short_conv``: no heads, no positions, no
recurrence) in three layers of four and q/k-normed softmax attention
over grouped key-value heads in the fourth, a leading layer whose kind
is the convolution with a dense SwiGLU, a sigmoid router with a
selection bias and NO shared expert, a tied head over held experts.  The
parameter tree, the traced toy step (scopes, counters, the kernel in the
layer), the layer against the operator written out, and every refusal
by name."""

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

VOCAB, B, T = 64, 2, 64
CONV = AttentionKind("conv", mixer="shortconv", conv_taps=3)
FULL = AttentionKind("full", rope_theta=1e6, qk_norm=True)


def conv_cfg(**kw):
    base = dict(
        vocab_size=VOCAB, d_model=32, n_heads=4, n_kv_heads=2, d_head=8,
        d_ff=16, n_layers=5, max_seq=T, attention="local", dtype="float32",
        pos_embedding="rope", norm_eps=1e-5,
        leading_layers=(CONV,), layer_pattern=(FULL, CONV, CONV, CONV),
        dense_act="swiglu", dense_d_ff=48,
        moe=True, n_experts=8, router_top_k=2, moe_dispatch="dropless",
        expert_act="swiglu", router_score="sigmoid",
        router_bias="selection", experts_held=(2, 4), tie_embeddings=True)
    base.update(kw)
    return TransformerConfig(**base)


def one_chip():
    return MeshConfig(devices=jax.devices()[:1], data=1)


def tokens(b=B, t=T):
    x = jnp.asarray(np.random.RandomState(0).randint(
        0, VOCAB, (b, t + 1)), jnp.int32)
    return x[:, :-1], x[:, 1:]


# -- the tree ---------------------------------------------------------- #

def test_the_tree_has_each_kinds_leaves_at_their_shapes():
    """The sixth record of the table: ``w_in`` to [B | C | x], the taps
    a channel, and ``wo`` as one d_model x d_model matrix laid out by
    the config's heads; no head, no gate, no norm of its own.  The
    leading layer is the convolution with the dense SwiGLU; the tied
    head adds no leaf; the bias runs with no shared expert beside it."""
    cfg = conv_cfg()
    assert tr.MIXERS[-1] == "shortconv" and len(tr.MIXERS) == 6
    assert cfg.mixers == ["shortconv"] and cfg.blocks_by_position
    params = jax.eval_shape(
        lambda: init_transformer(jax.random.PRNGKey(0), cfg))
    assert "head" not in params
    lead, = params["leading"]
    assert {k: v.shape for k, v in lead.items()} == {
        "ln1": (32,), "w_in": (32, 96), "conv": (32, 3), "wo": (4, 8, 32),
        "ln2": (32,), "w1": (32, 48), "w3": (32, 48), "w2": (48, 32)}
    full, conv = params["blocks"][0], params["blocks"][1]
    assert {k: v.shape[2:] for k, v in conv.items()} == {
        "ln1": (32,), "w_in": (32, 96), "conv": (32, 3), "wo": (4, 8, 32),
        "ln2": (32,), "router": (32, 8), "router_bias": (8,),
        "w1": (4, 32, 16), "w3": (4, 32, 16), "w2": (4, 16, 32)}
    assert full["q_norm"].shape[2:] == full["k_norm"].shape[2:] == (8,)
    assert full["wq"].shape[2:] == (32, 4, 8)
    assert full["wkv"].shape[2:] == (32, 2, 2, 8)
    assert not {"ws1", "ws2", "ws3", "wsg"} & set(conv)
    specs = param_specs(cfg)["blocks"][1]
    assert specs["w_in"] == specs["conv"] == P("pipe")
    assert set(specs) == set(conv)
    # 4 taps are another tree; the kind has no other field of its own
    assert CONV.tree == ("both", "shortconv", 3)
    assert AttentionKind("c", mixer="shortconv").tree[-1] == 4


def test_out_projection_needs_heads_that_divide_the_width():
    cfg = conv_cfg(n_heads=4, n_kv_heads=4, d_model=30, d_head=8)
    with pytest.raises(ValueError, match="do not divide d_model=30"):
        init_transformer(jax.random.PRNGKey(0), cfg)


# -- the layer against the operator written out ------------------------ #

def _written_out(cfg, h, blk):
    """``h + W_out (C . conv3(B . x))`` with ``[B C x] = RMSNorm(h)
    W_in``, a token at a time."""
    u = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + cfg.norm_eps) \
        * blk["ln1"]
    b, c, x = jnp.split(u @ blk["w_in"], 3, axis=-1)
    z = b * x
    t, taps = h.shape[1], blk["conv"].shape[-1]
    rows = []
    for i in range(t):
        acc = 0.0
        for j in range(taps):
            at = i - (taps - 1) + j
            if at >= 0:
                acc = acc + blk["conv"][:, j] * z[:, at]
        rows.append(c[:, i] * acc)
    y = jnp.stack(rows, axis=1)
    return h + y @ blk["wo"].reshape(-1, h.shape[-1])


def _layer(cfg, h, blk):
    mc = one_chip()
    return jax.jit(jax.shard_map(
        lambda h, blk: tr._attention(cfg, h, blk, CONV), mesh=mc.mesh,
        in_specs=(P(), P()), out_specs=P()))(h, blk)


def test_the_layer_is_the_operator_written_out():
    cfg = conv_cfg()
    blk = init_transformer(jax.random.PRNGKey(1), cfg)["leading"][0]
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 32))
    np.testing.assert_allclose(_layer(cfg, h, blk), _written_out(cfg, h, blk),
                               rtol=2e-5, atol=2e-5)


def test_the_layer_at_whole_tiles_runs_the_kernels(monkeypatch):
    """A lane tile of channels and a block of tokens: the layer's
    convolution is the Pallas kernel (interpreted here), forward and
    backward, under the step's shard_map, and its result and every
    gradient are those of the plain form at the same shape."""
    from chainermn_tpu.ops import recurrent

    cfg = conv_cfg(d_model=128, n_heads=4, d_head=32,
                   max_seq=recurrent.TOKENS)
    blk = init_transformer(jax.random.PRNGKey(3), cfg)["leading"][0]
    h = jax.random.normal(
        jax.random.PRNGKey(4), (1, recurrent.TOKENS, 128))

    def value_and_grads(h, blk):
        mc = one_chip()
        return jax.jit(jax.shard_map(
            jax.value_and_grad(lambda h, blk: jnp.sum(jnp.sin(
                tr._attention(cfg, h, blk, CONV))), (0, 1)), mesh=mc.mesh,
            in_specs=(P(), P()), out_specs=(P(), (P(), P()))))(h, blk)

    assert "pallas_call" in str(jax.make_jaxpr(value_and_grads)(h, blk))
    fused = value_and_grads(h, blk)
    monkeypatch.setattr(recurrent, "_kernel_blocks", lambda *a: 0)
    # (a new function: make_jaxpr keeps a trace by function and shapes)
    assert "pallas_call" not in str(
        jax.make_jaxpr(lambda h, blk: value_and_grads(h, blk))(h, blk))
    plain = value_and_grads(h, blk)
    for got, want in zip(jax.tree.leaves(fused), jax.tree.leaves(plain)):
        np.testing.assert_allclose(
            got, want, rtol=1e-4, atol=1e-5 * float(jnp.abs(want).max()))


# -- the step, traced once -------------------------------------------- #

@pytest.fixture(scope="module")
def traced():
    cfg, mc, opt = conv_cfg(), one_chip(), optax.sgd(0.1)
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


def test_toy_step_wears_the_new_scope_and_counts_its_sites(traced):
    from chainermn_tpu.utils.telemetry import (
        DEVICE_SCOPES_SHORTCONV, classify_op_name, device_scope)

    text, reg = traced
    assert DEVICE_SCOPES_SHORTCONV == ("shortconv/conv",)
    for scope in DEVICE_SCOPES_SHORTCONV + (
            "attn/conv", "attn/full", "attn.qkv", "attn.out",
            "attn.qk_norm", "mlp/dense", "moe/route"):
        assert scope in text, scope
    assert "moe/shared" not in text
    # a call a trace of the layer: the leading layer and the scanned
    # stack's three positions, each forward and in the block's remat
    sites = reg.counter("shortconv/sites").value
    assert sites >= 4 and sites % 4 == 0
    assert reg.counter("shortconv/bytes_kept").value \
        == sites * (B * T * 96 + 32 * 3) * 4
    assert classify_op_name(
        "jit(step)/transpose(jvp(step/layers))/while/body/checkpoint/"
        "attn/conv/shortconv/conv/pallas_call") == (
        "backward", ("step/layers", "attn/conv", "shortconv/conv"))
    with device_scope("shortconv/conv"):
        pass
    with pytest.raises(ValueError, match="DEVICE_SCOPES"):
        device_scope("shortconv/gate")


def test_the_step_trains_and_holds_the_bias():
    """Three AdamW steps lower the loss, move the convolution's taps,
    the projection and the tied embedding, and leave the selection bias
    where it was seeded: no shared expert stands beside it."""
    cfg, mc = conv_cfg(), one_chip()
    host = init_transformer(jax.random.PRNGKey(5), cfg)
    host["blocks"] = tuple(
        dict(blk, router_bias=0.01 * jax.random.normal(
            jax.random.PRNGKey(6 + i), blk["router_bias"].shape))
        for i, blk in enumerate(host["blocks"]))
    host = jax.tree.map(np.asarray, host)
    params = shard_params(mc, cfg, host)
    opt = optax.adamw(1e-2)
    state = shard_opt_state(opt, params)
    step = make_train_step(mc, cfg, opt)
    losses = []
    for _ in range(3):
        params, state, loss = step(params, state, *tokens())
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    for name in ("conv", "w_in", "wo"):
        assert float(jnp.abs(
            params["blocks"][1][name] - host["blocks"][1][name]).max()) > 1e-4
    assert float(jnp.abs(params["embed"] - host["embed"]).max()) > 1e-4
    for got, was in zip(params["blocks"], host["blocks"]):
        np.testing.assert_array_equal(got["router_bias"], was["router_bias"])


# -- refusals, by name -------------------------------------------------- #

@pytest.mark.parametrize("kw,match", [
    (dict(mixer="shortconv", conv_taps=0), "shortconv needs conv_taps >= 1"),
    (dict(mixer="shortconv", qk_norm=True),
     "qk_norm are the softmax mixer's"),
    (dict(mixer="shortconv", window=8), "takes no positions"),
    (dict(mixer="shortconv", yarn_factor=2.0, yarn_original_max=64),
     "mixer='shortconv' takes no positions"),
    (dict(mixer="longconv"), "gdn, shortconv"),
])
def test_kind_validation(kw, match):
    with pytest.raises(ValueError, match=match):
        AttentionKind("x", **kw)


@pytest.mark.parametrize("mesh,kw,match", [
    (dict(seq=2), {}, "the shortconv layers run whole on a device"),
    (dict(model=2), {}, "seq, model and pipe mesh axes must be 1"),
    (dict(pipe=2), {}, "the shortconv layers run whole"),
    (dict(data=1), dict(attention="ring"), "attention='flash' or 'local'"),
    (dict(data=2), dict(fsdp=True),
     "fsdp=True is not implemented for the shortconv layers"),
], ids=["seq", "model", "pipe", "ring", "fsdp"])
def test_meshes_and_paths_the_mixer_cannot_run_are_refused(mesh, kw, match):
    n = int(np.prod(list(mesh.values())))
    mc = MeshConfig(devices=jax.devices()[:n], **mesh)
    with pytest.raises(ValueError, match=match):
        make_train_step(mc, conv_cfg(**kw), optax.sgd(1.0))


@pytest.mark.parametrize("named", [
    "AttentionKind.mixer=shortconv", "AttentionKind.qk_norm",
    "leading_layers", "router_bias", "experts_held"])
def test_decoding_and_serving_refuse_the_new_fields(named):
    from chainermn_tpu.serving.engine import TransformerAdapter

    cfg = conv_cfg()
    assert named in cfg.training_only
    with pytest.raises(ValueError, match="decoding does not implement") \
            as err:
        make_generate_fn(one_chip(), cfg, max_len=T)
    assert named in str(err.value)
    with pytest.raises(ValueError, match="serving engine does not "
                       "implement") as err:
        TransformerAdapter(one_chip(), cfg)
    assert named in str(err.value)
