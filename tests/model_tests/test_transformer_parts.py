"""A transformer whose layers are ONE part each: a mixer alone or a
feed-forward part alone behind one norm and one residual add, with the
Mamba-2 state-space mixer (``ops/ssd.py``) among the mixers, softmax
layers that rotate nothing, and non-gated ReLU^2 experts beside a wider
shared one.  The trees, the layer's equations against plain ``jnp``,
the data and expert axes, and every refusal by name."""

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
    expert_choices,
    expert_load,
    init_transformer,
    make_generate_fn,
    make_train_step,
    param_specs,
    shard_params,
)
from chainermn_tpu.models import transformer as tr
from chainermn_tpu.ops.ssd import ssd_recurrent
from chainermn_tpu.parallel import MeshConfig
from chainermn_tpu.training import shard_opt_state

VOCAB, B, T = 64, 2, 128
M = AttentionKind("mamba2", part="mixer", mixer="mamba2", n_heads=4,
                  ssm_head_dim=8, ssm_state=16, ssm_groups=2)
A = AttentionKind("full", part="mixer", rotary_share=0.0)
E = AttentionKind("experts", part="mlp")
PATTERN = (M, E, M, E, M, A, E, M, E)


def parts_cfg(**kw):
    base = dict(
        vocab_size=VOCAB, d_model=32, n_heads=4, n_kv_heads=2, d_head=8,
        d_ff=24, n_layers=len(PATTERN), max_seq=T, attention="local",
        dtype="float32", pos_embedding="rope", norm_eps=1e-5,
        layer_pattern=PATTERN, moe=True, n_experts=8, router_top_k=2,
        moe_dispatch="dropless", expert_act="relu2", experts_held=(2, 4),
        router_score="sigmoid", router_scale=2.5, router_bias="selection",
        shared_expert_d_ff=48, tie_embeddings=False)
    base.update(kw)
    return TransformerConfig(**base)


def one_chip():
    return MeshConfig(devices=jax.devices()[:1], data=1)


def tokens(batch=B, key=1):
    tok = jax.random.randint(jax.random.PRNGKey(key), (batch, T + 1), 0,
                             VOCAB)
    return tok[:, :-1], tok[:, 1:]


@pytest.fixture(scope="module")
def host():
    return jax.tree.map(np.asarray, init_transformer(
        jax.random.PRNGKey(0), parts_cfg()))


# -- the tree ---------------------------------------------------------- #

def test_a_layer_of_one_part_has_only_its_parts_leaves(host):
    """One stack a position; a mixer's layer has ``ln1`` and its
    mixer's leaves and no MLP leaf, an expert layer ``ln2`` and its
    MLP's and neither ``wo`` nor a second norm; ReLU^2 experts have no
    gate matrix; the shared expert is twice an expert's width."""
    cfg = parts_cfg()
    assert cfg.blocks_by_position and cfg.parts_alone
    assert cfg.mixers == ["mamba2"]
    blocks = host["blocks"]
    assert len(blocks) == 9
    mamba = {"ln1", "w_in", "conv", "conv_b", "a_log", "dt_bias", "d_skip",
             "o_norm", "wo"}
    experts = {"ln2", "router", "router_bias", "w1", "w2", "ws1", "ws2"}
    for kind, blk in zip(PATTERN, blocks):
        assert set(blk) == {M: mamba, A: {"ln1", "wq", "wkv", "wo"},
                            E: experts}[kind]
    # [z | x B C | dt] = 32 + (32 + 2 x 2 x 16) + 4 columns
    assert blocks[0]["w_in"].shape == (1, 1, 32, 32 + 96 + 4)
    assert blocks[0]["conv"].shape == (1, 1, 96, 4)
    assert blocks[0]["conv_b"].shape == (1, 1, 96)
    assert blocks[0]["wo"].shape == (1, 1, 4, 8, 32)
    assert blocks[0]["o_norm"].shape == (1, 1, 32)
    assert blocks[1]["w1"].shape == (1, 1, 4, 32, 24)
    assert blocks[1]["ws1"].shape == (1, 1, 32, 48)
    assert blocks[1]["router"].shape == (1, 1, 32, 8)
    # the published initialisers, not zeros
    assert (np.exp(blocks[0]["a_log"]) >= 1).all() \
        and (np.exp(blocks[0]["a_log"]) <= 16).all()
    step = np.log1p(np.exp(blocks[0]["dt_bias"]))
    assert (step >= 1e-4).all() and (step <= 0.1 + 1e-6).all()
    assert (blocks[0]["d_skip"] == 1).all()
    specs = param_specs(cfg)
    assert jax.tree.structure(specs) == jax.tree.structure(
        jax.tree.map(lambda a: P(), host))
    assert all(set(s) == set(b) for s, b in zip(specs["blocks"], blocks))


def test_both_parts_stays_the_default_and_its_tree_is_unchanged():
    """A kind that names no part is a mixer then an MLP with two norms,
    as before: the same leaves from the same keys."""
    plain = TransformerConfig(vocab_size=VOCAB, d_model=32, n_heads=4,
                              d_head=8, d_ff=16, n_layers=2, max_seq=T)
    typed = dataclasses.replace(
        plain, pos_embedding="rope",
        layer_pattern=(AttentionKind("full"),))
    a = init_transformer(jax.random.PRNGKey(3), plain)["blocks"]
    b = init_transformer(jax.random.PRNGKey(3), typed)["blocks"]
    assert set(a) == {"ln1", "ln2", "wqkv", "wo", "w1", "w2"} == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    assert not typed.parts_alone and not typed.blocks_by_position


# -- the layers' equations --------------------------------------------- #

def _mamba_by_hand(cfg, kind, h, blk):
    """The Mamba-2 layer in plain ``jnp`` with the token-by-token
    recurrence."""
    heads, p, g, n = kind.n_heads, kind.ssm_head_dim, kind.ssm_groups, \
        kind.ssm_state
    inner = heads * p
    u = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + cfg.norm_eps) \
        * blk["ln1"]
    proj = u @ blk["w_in"]
    z, xbc, dt = proj[..., :inner], proj[..., inner:-heads], \
        proj[..., -heads:]
    t = h.shape[1]
    padded = jnp.pad(xbc, ((0, 0), (3, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, j:j + t] * blk["conv"][:, j]
                          for j in range(4)) + blk["conv_b"])
    x = xbc[..., :inner].reshape(*h.shape[:2], heads, p)
    b_in = xbc[..., inner:inner + g * n].reshape(*h.shape[:2], g, n)
    c_out = xbc[..., inner + g * n:].reshape(*h.shape[:2], g, n)
    dt = jax.nn.softplus(dt + blk["dt_bias"])
    y = ssd_recurrent(x, dt, -jnp.exp(blk["a_log"]), b_in, c_out) \
        + blk["d_skip"][:, None] * x
    y = y.reshape(*h.shape[:2], inner) * jax.nn.silu(z)
    y = y.reshape(*h.shape[:2], g, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + cfg.norm_eps) \
        * blk["o_norm"].reshape(g, -1)
    return h + y.reshape(*h.shape[:2], inner) @ blk["wo"].reshape(inner, -1)


def _in_mesh(fn, *args):
    mc = one_chip()
    return jax.jit(jax.shard_map(
        fn, mesh=mc.mesh, in_specs=tuple(P() for _ in args),
        out_specs=P(), check_vma=False))(*args)


def test_mamba2_layer_is_its_equations(host):
    cfg = parts_cfg()
    blk = jax.tree.map(lambda a: jnp.asarray(a[0, 0]), host["blocks"][0])
    # a bias and a skip that matter
    blk["conv_b"] = 0.3 * jax.random.normal(jax.random.PRNGKey(8), (96,))
    blk["d_skip"] = 1 + 0.5 * jax.random.normal(jax.random.PRNGKey(9), (4,))
    h = jax.random.normal(jax.random.PRNGKey(5), (B, T, 32))
    got = _in_mesh(lambda h, blk: tr._block(cfg, h, blk, M)[0], h, blk)
    want = _mamba_by_hand(cfg, M, h, blk)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # one residual add and nothing after the mixer
    assert float(jnp.abs(got - h).mean()) > 0.01


@pytest.mark.parametrize("act", ["relu", "relu2", "swiglu"])
def test_dense_and_grouped_activations(act):
    """``_gated`` and the dropless layer's grouped path, by hand."""
    cfg = parts_cfg(expert_act=act, experts_held=(0, 8), router_bias="",
                    router_score="softmax", router_scale=1.0,
                    shared_expert_d_ff=0, router_top_k=8)
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (B, T, 32))
    w1, w3 = (jax.random.normal(k, (32, 24)) * 32 ** -.5 for k in ks[1:3])
    w2 = jax.random.normal(ks[3], (24, 32)) * 24 ** -.5

    def by_hand(x, w1, w3, w2):
        y = x @ w1
        y = {"relu": jax.nn.relu(y), "relu2": jnp.square(jax.nn.relu(y)),
             "swiglu": jax.nn.silu(y) * (x @ w3)}[act]
        return y @ w2

    got = _in_mesh(lambda *a: tr._gated(cfg, act, *a), x, w1, w3, w2)
    np.testing.assert_allclose(got, by_hand(x, w1, w3, w2), rtol=2e-5,
                               atol=2e-5)
    # every expert chosen by every token with gates that sum to one:
    # identical experts give the dense result back through the grouped
    # products
    blk = {"ln2": jnp.ones((32,)), "router": jnp.zeros((32, 8)),
           "w1": jnp.broadcast_to(w1, (8, 32, 24)),
           "w2": jnp.broadcast_to(w2, (8, 24, 32))}
    if act == "swiglu":
        blk["w3"] = jnp.broadcast_to(w3, (8, 32, 24))
    out = _in_mesh(lambda x, blk: tr._mlp(cfg, x, blk)[0], x, blk)
    normed = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(out - x, by_hand(normed, w1, w3, w2),
                               rtol=2e-4, atol=2e-5)


def test_a_kind_that_rotates_nothing_is_rope_left_out(host, monkeypatch):
    """``rotary_share=0``: the layer equals the rotating one with
    ``apply_rope`` taken out, and differs from it with rotary in."""
    cfg = parts_cfg()
    blk = jax.tree.map(lambda a: jnp.asarray(a[0, 0]), host["blocks"][5])
    h = jax.random.normal(jax.random.PRNGKey(6), (B, T, 32))
    rotating = dataclasses.replace(A, rotary_share=1.0)
    run = lambda kind: _in_mesh(
        lambda h, blk: tr._block(cfg, h, blk, kind)[0], h, blk)
    still, turned = run(A), run(rotating)
    assert float(jnp.abs(still - turned).max()) > 1e-3
    monkeypatch.setattr(tr.mixers, "apply_rope", lambda x, *a, **k: x)
    np.testing.assert_array_equal(still, run(rotating))
    assert A.rotary_dim(8) == 0


# -- the step, the routers' rows, the open axes ------------------------ #

def test_step_trains_and_only_router_layers_have_rows(host):
    cfg = parts_cfg()
    mc = one_chip()
    params = shard_params(mc, cfg, host)
    chosen = expert_choices(mc, cfg, params, tokens()[0])
    assert chosen.shape == (4, B, T, 2)     # four E layers of nine
    load = expert_load(mc, cfg, params, tokens()[0])
    assert load.shape == (4, 8) and (load.sum(1) == B * T * 2).all()
    opt = optax.adamw(1e-2)
    state = shard_opt_state(opt, params)
    step = make_train_step(mc, cfg, opt)
    losses = []
    for _ in range(4):
        params, state, loss = step(params, state, *tokens())
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    # the selection bias is held
    np.testing.assert_array_equal(params["blocks"][1]["router_bias"],
                                  host["blocks"][1]["router_bias"])


def test_data_and_expert_axes_stay_open():
    """Two data members, each an expert group of two that shares its
    experts out, against two data members that hold them whole: the same
    loss and the same update."""
    # one layer of each part and mixer, not nine: what the open axes
    # are asked does not depend on the depth; seeded once for both meshes
    cfg = parts_cfg(experts_held=(0, 8), layer_pattern=(M, E, A, E),
                    n_layers=4)
    whole = jax.tree.map(np.asarray, jax.jit(lambda: init_transformer(
        jax.random.PRNGKey(0), cfg))())

    def one_step(**mesh):
        n = int(np.prod(list(mesh.values())))
        mc = MeshConfig(devices=jax.devices()[:n], **mesh)
        params = shard_params(mc, cfg, whole)
        opt = optax.sgd(1.0)
        params, _, loss = make_train_step(mc, cfg, opt)(
            params, shard_opt_state(opt, params), *tokens(4))
        return float(loss), jax.tree.map(
            lambda a, b: b - np.asarray(a), params, whole)

    loss1, delta1 = one_step(data=2)
    loss4, delta4 = one_step(data=2, expert=2)
    assert loss4 == pytest.approx(loss1, rel=2e-5)
    for a, b in zip(jax.tree.leaves(delta1), jax.tree.leaves(delta4)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5)


# -- refusals, by name -------------------------------------------------- #

@pytest.mark.parametrize("mesh,kw,match", [
    (dict(seq=2), {}, "seq, model and pipe mesh axes must be 1"),
    (dict(model=2), {}, "seq, model and pipe mesh axes must be 1"),
    (dict(data=1), dict(attention="ring"), "attention='flash' or 'local'"),
    (dict(data=2), dict(fsdp=True),
     "fsdp=True is not implemented for the mamba2 layers"),
    (dict(model=2), dict(layer_pattern=(A, E), n_layers=2),
     r"a mixer or a feed-forward part alone \(AttentionKind.part\)"),
    (dict(data=2), dict(layer_pattern=(A, E), n_layers=2, fsdp=True),
     r"a mixer or a feed-forward part alone \(AttentionKind.part\)"),
    (dict(pipe=2), dict(layer_pattern=(A, E), n_layers=4),
     "AttentionKind.part|unpipelined mesh"),
], ids=["seq", "model", "ring", "fsdp", "parts-model", "parts-fsdp",
        "parts-pipe"])
def test_meshes_and_paths_not_implemented_are_refused(mesh, kw, match):
    n = int(np.prod(list(mesh.values())))
    mc = MeshConfig(devices=jax.devices()[:n], **mesh)
    with pytest.raises(ValueError, match=match):
        make_train_step(mc, parts_cfg(**kw), optax.sgd(1.0))


@pytest.mark.parametrize("kw,named", [
    ({}, "AttentionKind.mixer=mamba2"),
    ({}, "AttentionKind.part"),
    ({}, "AttentionKind.rotary_share=0"),
    (dict(layer_pattern=(), moe=False, expert_act="relu", experts_held=(),
          router_score="softmax", router_scale=1.0, router_bias="",
          shared_expert_d_ff=0, moe_dispatch="capacity", n_layers=2,
          dense_act="relu2"), "dense_act='relu2'"),
], ids=["mamba2", "part-alone", "no-rotary", "dense-relu2"])
def test_decoding_and_serving_refuse_the_new_fields(kw, named):
    from chainermn_tpu.serving.engine import TransformerAdapter

    cfg = parts_cfg(**kw)
    assert named in cfg.training_only
    with pytest.raises(ValueError, match="decoding does not implement") \
            as err:
        make_generate_fn(one_chip(), cfg, max_len=T)
    assert named in str(err.value)
    with pytest.raises(ValueError, match="serving engine does not "
                       "implement") as err:
        TransformerAdapter(one_chip(), cfg)
    assert named in str(err.value)


@pytest.mark.parametrize("kw,match", [
    (dict(name="m", mixer="mamba"),
     r"not in \(softmax, mla, kda, mamba2, gdn, shortconv\)"),
    (dict(name="m", part="ffn"), r"not in \(both, mixer, mlp\)"),
    (dict(name="m", mixer="mamba2"), "mamba2 needs its own n_heads"),
    (dict(name="m", mixer="mamba2", n_heads=4, ssm_head_dim=8, ssm_state=16,
          ssm_groups=3), "whole groups of heads"),
    (dict(name="m", mixer="mamba2", n_heads=4, ssm_head_dim=8, ssm_state=16,
          ssm_groups=2, conv_taps=0), "mamba2 needs conv_taps >= 1"),
    (dict(name="m", mixer="mamba2", n_heads=4, ssm_head_dim=8, ssm_state=16,
          ssm_groups=2, window=8), "takes no positions"),
])
def test_attention_kind_names_what_it_refuses(kw, match):
    """The mixers and the parts are named once (``MIXERS``, ``PARTS``):
    the message reads the tuple the check uses."""
    with pytest.raises(ValueError, match=match):
        AttentionKind(**kw)
    assert tr.MIXERS[:4] == ("softmax", "mla", "kda", "mamba2")


@pytest.mark.parametrize("kw,match", [
    (dict(expert_act="gelu"), r"not in \(relu, relu2, swiglu\)"),
    (dict(dense_act="gelu"), r"not in \(relu, relu2, swiglu\)"),
    (dict(expert_act="relu2", moe_dispatch="capacity", experts_held=(),
          router_score="softmax", router_scale=1.0, router_bias="",
          shared_expert_d_ff=0), 'expert_act="relu2" is implemented by'),
    # the gate is the softmax layers' (since PR 42 also beside other
    # mixers); a pattern with no softmax layer has nothing to gate
    (dict(attn_gate="per_head", layer_pattern=(M, E), n_layers=2),
     "attn_gate is softmax attention's"),
])
def test_config_validation(kw, match):
    with pytest.raises(ValueError, match=match):
        parts_cfg(**kw)


# -- the experts' width in whole tiles ---------------------------------- #

@pytest.mark.parametrize("width,padded", [
    (1856, 1920), (192, 256), (896, 896), (1024, 1024), (512, 512),
    (24, 24)])
def test_expert_width_is_padded_only_where_it_is_not_whole_lanes(
        width, padded):
    """1,856 (14.5 tiles of 128 lanes) goes to 1,920; the accepted
    cells' widths (896, 512, 1,024) and a test's width under one tile
    stay, and ``w`` is handed back as it is."""
    w1, w2 = jnp.ones((2, 8, width)), jnp.ones((2, width, 8))
    p1, p2 = tr._whole_tiles(w1, 2), tr._whole_tiles(w2, 1)
    assert p1.shape == (2, 8, padded) and p2.shape == (2, padded, 8)
    if padded == width:
        assert p1 is w1 and p2 is w2
    else:
        assert float(p1[..., width:].sum() + p2[:, width:].sum()) == 0


def test_padded_experts_give_the_unpadded_result_and_gradient():
    """A width of 192 runs through the grouped products at 256: the
    layer's output and every weight's gradient are those of the
    unpadded experts, computed by hand."""
    cfg = parts_cfg(d_ff=192, experts_held=(0, 8), router_bias="",
                    shared_expert_d_ff=0)
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    x = jax.random.normal(ks[0], (B, T, 32))
    blk = {"ln2": jnp.ones((32,)),
           "router": jax.random.normal(ks[1], (32, 8)) * 32 ** -.5,
           "w1": jax.random.normal(ks[2], (8, 32, 192)) * 32 ** -.5,
           "w2": jax.random.normal(ks[3], (8, 192, 32)) * 192 ** -.5}

    def by_hand(x, blk):
        u = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
        s = jax.nn.sigmoid(u @ blk["router"])
        top_s, top_i = jax.lax.top_k(s, 2)
        gates = 2.5 * top_s / top_s.sum(-1, keepdims=True)
        gate_of = (jax.nn.one_hot(top_i, 8) * gates[..., None]).sum(-2)
        y = jnp.square(jax.nn.relu(jnp.einsum("btd,edf->btef", u,
                                              blk["w1"])))
        return x + jnp.einsum("btef,efd,bte->btd", y, blk["w2"], gate_of)

    def program(x, blk):
        return _in_mesh(lambda x, blk: tr._mlp(cfg, x, blk)[0], x, blk)

    np.testing.assert_allclose(program(x, blk), by_hand(x, blk),
                               rtol=2e-4, atol=2e-5)
    loss = lambda fn: lambda blk: jnp.sum(jnp.sin(fn(x, blk)))  # noqa: E731
    got, want = jax.grad(loss(program))(blk), jax.grad(loss(by_hand))(blk)
    for k in ("w1", "w2", "router"):
        assert got[k].shape == blk[k].shape
        np.testing.assert_allclose(got[k], want[k], rtol=2e-3, atol=2e-5)
