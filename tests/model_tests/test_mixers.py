"""The seam between the model and its mixers (``models/mixers.py``): a
mixer the table has never heard of trains with no edit of
``models/transformer.py``; each of the five records names the leaves its
``init`` and its ``specs`` really return; and the arrows point one way
(``transformer -> mixers``)."""

import ast
import collections
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
                       "mla, kda, mamba2, gdn, shortconv\\)"):
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
    "shortconv": AttentionKind("conv", mixer="shortconv", conv_taps=3),
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


# -- what a block keeps of its recurrence ------------------------------ #

RECURRENCES = {"kda": "kda", "gdn": "gdn", "mamba2": "ssd"}
LONG = 64   # two slabs of two chunks of 16
# the (slabs, B, ...) states and the (B, T, H, d_v) output, float32
# (Mamba-2's with its heads of half a lane tile flat, as its gate reads it)
KEPT = {
    "kda": [(2, 2, 4, 8, 8), (2, LONG, 4, 8)],
    "gdn": [(2, 2, 2, 2, 16, 8), (2, LONG, 4, 8)],
    "mamba2": [(2, 2, 4, 8, 16), (2, LONG, 4 * 8)],
    "softmax": [],
}
_BLOCKS = {}


def _census(jaxpr, found=None):
    """``{primitive name: equations}`` over ``jaxpr`` and every jaxpr
    nested in it (a scan's body counts once, as it is traced once)."""
    found = collections.Counter() if found is None else found
    for eqn in jaxpr.eqns:
        found[eqn.primitive.name] += 1
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _census(sub, found)
    return found


def _one_block(name):
    """One toy block of the mixer, differentiated under the config's
    ``checkpoint_fn`` and under plain ``jax.checkpoint`` (which keeps
    no name: the three passes): ``(what the first saves besides its
    arguments, the census of its gradient's jaxpr, the gradients), (the
    second's census and gradients), the registry the first trace
    counted into``.  Once a mixer."""
    if name in _BLOCKS:
        return _BLOCKS[name]
    import contextlib
    import importlib
    import io

    from chainermn_tpu.models.transformer import _block
    from chainermn_tpu.ops.pallas_attention import tracing_for_mesh
    from chainermn_tpu.utils.metrics import MetricsRegistry, set_registry

    kind = KINDS[name]
    cfg = toy_cfg((kind,), max_seq=LONG, remat=True)
    mc = MeshConfig(devices=jax.devices()[:1], data=1)
    blk = jax.tree.map(lambda a: a[0, 0], init_transformer(
        jax.random.PRNGKey(0), cfg)["blocks"])
    h = jax.random.normal(jax.random.PRNGKey(1), (2, LONG, cfg.d_model))

    def under(checkpoint, saved=None):
        def body(h, blk):
            fn = checkpoint(lambda h, blk: _block(cfg, h, blk, kind=kind))
            if saved is not None:
                out = io.StringIO()
                with contextlib.redirect_stdout(out):
                    jax.ad_checkpoint.print_saved_residuals(fn, h, blk)
                saved.extend(
                    line.split()[0] for line in out.getvalue().splitlines()
                    if "from the argument" not in line
                    and "from a constant" not in line)   # rotary's table
            return jax.grad(lambda *a: jnp.mean(fn(*a)[0] ** 2), (0, 1))(
                h, blk)

        traced = jax.jit(jax.shard_map(
            tracing_for_mesh(mc.mesh, body), mesh=mc.mesh,
            in_specs=(P(), P()), out_specs=P(),
            check_vma=False)).trace(h, blk)
        return _census(traced.jaxpr.jaxpr), traced.lower().compile()(h, blk)

    op = RECURRENCES.get(name)
    if op:
        op = importlib.import_module(f"chainermn_tpu.ops.{op}")
    patch = pytest.MonkeyPatch()
    reg = MetricsRegistry(enabled=True)
    prev = set_registry(reg)
    try:
        if op:
            patch.setattr(op, "CHUNK", 16)
            patch.setattr(op, "SLAB", 2)
        saved = []
        kept = (saved,) + under(cfg.checkpoint_fn, saved)
        set_registry(MetricsRegistry())
        plain = under(jax.checkpoint)
    finally:
        set_registry(prev)
        patch.undo()
    _BLOCKS[name] = kept, plain, reg
    return _BLOCKS[name]


@pytest.mark.parametrize("name", list(KEPT))
def test_a_block_keeps_its_recurrences_states_and_output(name):
    """Under ``checkpoint_fn`` a block with a recurrence saves its
    arguments and exactly two tensors more
    (``RECURRENT_RESIDUAL_NAMES``): the float32 state at each slab's
    start and the op's float32 output as the layer reads it.  A
    softmax block keeps what it kept: nothing."""
    (saved, _, _), _, _ = _one_block(name)
    assert saved == ["f32[%s]" % ",".join(map(str, shape))
                     for shape in KEPT[name]]


@pytest.mark.parametrize("name,kernels", [
    ("kda", (7, 5)),        # pairs and inversion a pass, the pairs' VJP
    ("gdn", (3, 2)),        # the inversion a pass
    ("mamba2", (0, 0)),
])
def test_a_blocks_gradient_runs_its_recurrence_forward_twice(name, kernels):
    """The slab scan with its chunk scan inside: forward, the block's
    recompute and the slab's recompute under plain ``jax.checkpoint``;
    with the two names kept the block's recompute is gone, scan and
    kernels, and no other scan stands in for it."""
    (_, kept, _), (plain, _), _ = _one_block(name)
    # slab scans 3 -> 2; chunk scans 3 and their backward's 1 -> 2 and 1
    assert (plain["scan"], kept["scan"]) == (7, 5)
    assert (plain["pallas_call"], kept["pallas_call"]) == kernels


@pytest.mark.parametrize("name,atol", [
    ("kda", 5e-8),      # largest gap read: 1.9e-8 (1.2e-8 past rtol)
    ("gdn", 5e-8),      # 2.3e-8 (1.3e-8 past rtol)
    ("mamba2", 0.0),    # equal to the last bit
])
def test_keeping_the_recurrences_residuals_changes_no_gradient(name, atol):
    """Scheduling, not arithmetic: the gradients under the policy are
    plain ``jax.checkpoint``'s, entry by entry to 1e-6 of the entry and
    a float32 rounding of the largest (0.14 to 0.24 here: the compiler
    fuses the two programs differently around the two Pallas kernels'
    layers; Mamba-2's come out equal to the last bit)."""
    (_, _, kept), (_, plain), _ = _one_block(name)
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(plain)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.isfinite(a).all() and np.abs(a).max() > 0
        np.testing.assert_allclose(a, b, rtol=1e-6 if atol else 0, atol=atol)


@pytest.mark.parametrize("name", list(KEPT))
def test_residual_bytes_kept_is_counted_from_the_shapes(name):
    """The kept output by ``scan_slabs``, the kept states by the op:
    each tensor is counted in one place."""
    _, _, reg = _one_block(name)
    states, out = (
        4 * int(np.prod(shape)) for shape in KEPT[name] or [(0,), (0,)])
    op = {"mamba2": "ssm"}.get(name, name)
    assert reg.counter("recurrent/residual_bytes_kept").value == out
    assert reg.counter(f"{op}/state_bytes_kept").value == states


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
