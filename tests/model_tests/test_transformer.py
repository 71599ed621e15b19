"""Flagship transformer: every parallelism axis, checked against the
single-device oracle (the multi-axis run must be numerically identical —
SPMD sharding is an implementation detail, not a semantics change)."""

import collections
import functools
from functools import partial

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


@functools.cache
def _one_device_forward(cfg):
    """The oracle's program, built once a config: eight cases ask for
    ``tiny_cfg()``'s, and each ``make_forward_fn`` compiles its own."""
    return make_forward_fn(
        MeshConfig(data=1, devices=jax.devices()[:1]), cfg)


def oracle_logits(cfg, params, toks):
    return _one_device_forward(cfg)(params, toks)


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


def _mesh(**axes):
    n = int(np.prod(list(axes.values()) or [1]))
    return MeshConfig(devices=jax.devices()[:n], **(axes or dict(data=1)))


_LM_GRADS = {}


def _lm_grad(cfg, x, y, **axes):
    """``(gradient of lm_loss on the mesh, the census of its traced
    program)``; the blocks run under whatever ``cfg.checkpoint_fn`` is
    when called.  Kept a (config, mesh, ``checkpoint_fn`` in force):
    every case feeds ``tokens()``, and the remat cases ask for the same
    ``remat=False`` and ``remat=True`` gradients once a policy."""
    key = (cfg, tuple(sorted(axes.items())),
           TransformerConfig.__dict__["checkpoint_fn"])
    if key not in _LM_GRADS:
        traced, params = _lm_grad_traced(cfg, x, y, **axes)
        _LM_GRADS[key] = (traced.lower().compile()(params, x, y),
                          _census(traced.jaxpr.jaxpr))
    return _LM_GRADS[key]


def _lm_grad_traced(cfg, x, y, **axes):
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu.models.transformer import lm_loss, param_specs
    from chainermn_tpu.ops.pallas_attention import tracing_for_mesh

    mc = _mesh(**axes)
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
    specs = param_specs(cfg)
    batch_spec = P(("data", "expert"), "seq")
    grad_fn = jax.shard_map(
        tracing_for_mesh(mc.mesh, lambda p, xx, yy: jax.grad(
            lambda q: lm_loss(cfg, q, xx, yy))(p)),
        mesh=mc.mesh, in_specs=(specs, batch_spec, batch_spec),
        out_specs=specs)
    return jax.jit(grad_fn).trace(params, x, y), params


def _same(a, b):
    jax.tree.map(lambda u, v: np.testing.assert_array_equal(
        np.asarray(u), np.asarray(v)), a, b)


@pytest.mark.parametrize("attention,kernels", [("local", 0), ("flash", 2)])
def test_remat_policy_grad_equivalence(attention, kernels):
    """remat_policy is a pure scheduling choice: grads equal the
    remat=False path (fp32), and under every setting the traced gradient
    holds the kernels of remat=False (forward, backward) and no more."""
    toks = tokens()
    x, y = toks[:, :T], toks[:, 1:]
    grads = {}
    for name, kw in (("none", dict(remat=False)),
                     ("full", dict(remat=True)),
                     ("dots", dict(remat=True, remat_policy="dots"))):
        grads[name], census = _lm_grad(
            tiny_cfg(attention=attention, **kw), x, y)
        assert census["pallas_call"] == kernels, name
    for name in ("full", "dots"):
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6),
            grads["none"], grads[name])

    with pytest.raises(ValueError, match="remat_policy"):
        tiny_cfg(remat_policy="everything")


def _typed_kw():
    from chainermn_tpu.models import AttentionKind

    return dict(pos_embedding="rope", layer_pattern=(
        AttentionKind("sliding", window=8, rope_theta=5e5),
        AttentionKind("full", rope_theta=5e5)))


def _gated_kw():
    """A leading layer, then periods whose kinds differ in query heads
    (2 = the key-value heads: fused q/k/v; 4: grouped), every layer with
    the gate a head between the kernel and the output projection."""
    from chainermn_tpu.models import AttentionKind

    full = AttentionKind("full", rope_theta=5e5, rotary_share=0.5)
    return dict(pos_embedding="rope", n_layers=3, n_kv_heads=2,
                attn_gate="per_head", leading_layers=(full,),
                layer_pattern=(AttentionKind(
                    "sliding", window=8, rope_theta=5e5, n_heads=2), full))


_PLAIN = property(lambda self: jax.checkpoint)


@pytest.mark.parametrize("policy", ["full", "dots"])
@pytest.mark.parametrize("layers", ["uniform", "window+full",
                                    "leading+gated+heads"])
def test_flash_forward_runs_once_under_remat(monkeypatch, layers, policy):
    """The block's checkpoint keeps the kernel's own ``o`` and ``lse``:
    the traced gradient of the scanned blocks holds two kernels a
    layer kind (forward, backward), where plain ``jax.checkpoint`` holds
    three (the forward again), and the gradients are those of
    ``remat=False`` and of plain ``jax.checkpoint`` to the last bit."""
    kw = dict(attention="flash", **(
        _gated_kw() if "gated" in layers
        else _typed_kw() if "+" in layers else {}))
    # the gate's backward reads the kernel's o, which is the saved one
    kinds = len(kw.get("layer_pattern", (None,))) \
        + len(kw.get("leading_layers", ()))
    toks = tokens()
    x, y = toks[:, :T], toks[:, 1:]
    cfg = tiny_cfg(remat=True, remat_policy=policy, **kw)
    kept, n_kept = _lm_grad(cfg, x, y)
    none, n_none = _lm_grad(tiny_cfg(**kw), x, y)
    # plain ``jax.checkpoint`` reads no policy (``checkpoint_fn`` is the
    # field's one reader), so both policies are held to one gradient
    # under it, made once a layer set: the ``"full"`` config's, which
    # under it is this config's program, equation for equation
    monkeypatch.setattr(TransformerConfig, "checkpoint_fn", _PLAIN)
    full = tiny_cfg(remat=True, remat_policy="full", **kw)
    plain, n_plain = _lm_grad(full, x, y)
    if cfg != full:
        assert str(_lm_grad_traced(cfg, x, y)[0].jaxpr) \
            == str(_lm_grad_traced(full, x, y)[0].jaxpr)
    assert [n["pallas_call"] for n in (n_none, n_kept, n_plain)] == [
        2 * kinds, 2 * kinds, 3 * kinds]
    _same(kept, none)
    _same(kept, plain)


@pytest.mark.parametrize("policy", ["full", "dots"])
def test_ring_block_remats_as_before(monkeypatch, policy):
    """``attention="ring"`` is left out of the named save (a block's
    policy reaches into the checkpoint around each pair and would keep
    every pair's output): its traced gradient is the one plain
    ``jax.checkpoint`` (``"dots"``: the dots policy with ``attn_out``)
    gives, equation for equation.  Here the pairs are XLA's; the kernel
    in the ring is counted in ``test_tpu_compile.py``."""
    cp = jax.checkpoint_policies
    before = jax.checkpoint if policy == "full" else partial(
        jax.checkpoint, policy=cp.save_from_both_policies(
            cp.dots_with_no_batch_dims_saveable,
            cp.save_only_these_names("attn_out")))
    cfg = tiny_cfg(attention="ring", remat=True, remat_policy=policy)
    toks = tokens()
    x, y = toks[:, :T], toks[:, 1:]
    kept, n_kept = _lm_grad(cfg, x, y, seq=4, data=2)
    monkeypatch.setattr(TransformerConfig, "checkpoint_fn",
                        property(lambda self: before))
    plain, n_plain = _lm_grad(cfg, x, y, seq=4, data=2)
    assert n_kept == n_plain and n_kept["ppermute"]
    _same(kept, plain)


def _saved_by_one_block(cfg, T, **axes):
    """What ``jax.ad_checkpoint.print_saved_residuals`` lists for one
    block under ``cfg.checkpoint_fn``, the block's arguments left out.
    Traced inside the mesh's ``shard_map``: the block reads axis sizes."""
    import contextlib
    import io

    from jax.sharding import PartitionSpec as P

    from chainermn_tpu.models.transformer import _block
    from chainermn_tpu.ops.pallas_attention import tracing_for_mesh

    mc = _mesh(**axes)
    blk = jax.tree.map(lambda a: a[0, 0], init_transformer(
        jax.random.PRNGKey(0), cfg)["blocks"])
    h = jnp.ones((2, T, cfg.d_model), cfg.compute_dtype)
    out = io.StringIO()

    def body(h, blk):
        fn = cfg.checkpoint_fn(partial(_block, cfg, kind=None))
        with contextlib.redirect_stdout(out):
            jax.ad_checkpoint.print_saved_residuals(fn, h, blk)
        return fn(h, blk)[0]

    jax.make_jaxpr(jax.shard_map(
        tracing_for_mesh(mc.mesh, body), mesh=mc.mesh,
        in_specs=(P(None, "seq"), P()), out_specs=P(None, "seq"),
        check_vma=False))(h, blk)
    return [line for line in out.getvalue().splitlines()
            if "from the argument" not in line]


@pytest.mark.parametrize("attention,axes,saved", [
    ("flash", {}, ["bf16[8,16,8]", "f32[8,16]"]),   # (B·H, T, D), (B·H, T)
    ("local", {}, []),
    ("ring", dict(seq=4), []),
])
def test_what_one_block_saves_under_full_remat(attention, axes, saved):
    """Under ``remat_policy="full"`` a block keeps its arguments and,
    where it runs the kernel, ``o`` as the kernel wrote it in the
    compute dtype and the fp32 log-sum-exp WITHOUT its 128 lanes:
    nothing else of the attention (no q3/k3/v3), and nothing at all of
    the XLA attention or of the ring's pairs."""
    lines = _saved_by_one_block(
        tiny_cfg(attention=attention, remat=True, dtype="bfloat16"), T,
        **axes)
    assert [line.split()[0] for line in lines] == saved, lines
    assert all("pallas_attention.py" in line for line in lines), lines
    if saved:
        assert "flash_lse" in lines[1], lines


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
    """flash_bwd_block_q/k retune the backward kernel's tiling only:
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
