"""A transformer whose layers differ in their MIXER: Kimi Delta
Attention (the chunked recurrence of ``ops/kda.py``), latent attention
without rotary through flash kernels whose values are narrower than
their keys, and a router whose selection bias steers the choice and not
the gate.  The op against the recurrence a token at a time, the kernels
against plain scores, the parameter trees, the counters, the bias held
fixed, and every refusal by name."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chainermn_tpu.models import (
    AttentionKind,
    TransformerConfig,
    init_transformer,
    make_generate_fn,
    make_train_step,
    param_specs,
    shard_params,
)
from chainermn_tpu.ops import kda
from chainermn_tpu.ops.kda import kda_chunked, kda_recurrent
from chainermn_tpu.ops.pallas_attention import flash_attention
from chainermn_tpu.parallel import MeshConfig
from chainermn_tpu.parallel.expert import route_top_k
from chainermn_tpu.parallel.ring_attention import local_attention
from chainermn_tpu.training import shard_opt_state
from chainermn_tpu.utils.metrics import MetricsRegistry, set_registry

VOCAB, B, T = 64, 2, 128
KDA = AttentionKind("kda", mixer="kda")
MLA = AttentionKind("mla", mixer="mla", kv_latent=16, d_shared_key=8,
                    d_value=16)


def hybrid_cfg(**kw):
    base = dict(
        vocab_size=VOCAB, d_model=32, n_heads=4, d_head=8, d_ff=16,
        n_layers=5, max_seq=T, attention="local", dtype="float32",
        pos_embedding="rope", norm_eps=1e-5, leading_layers=(KDA,),
        layer_pattern=(KDA, KDA, MLA, KDA), dense_act="swiglu",
        dense_d_ff=48, moe=True, n_experts=8, router_top_k=2,
        moe_dispatch="dropless", expert_act="swiglu", experts_held=(2, 4),
        router_score="sigmoid", router_scale=2.446,
        router_bias="selection", shared_expert_d_ff=24,
        tie_embeddings=False)
    base.update(kw)
    return TransformerConfig(**base)


def one_chip():
    return MeshConfig(devices=jax.devices()[:1], data=1)


def small_cfg():
    """One layer of each mixer and no leading one, every expert held:
    what the open axes and the held bias are asked does not depend on
    the depth, and a step of two layer bodies compiles in under half
    the time of five (PR 45: 101 s and 48 s of a 1,319 s tier-1 run)."""
    return hybrid_cfg(experts_held=(0, 8), leading_layers=(),
                      layer_pattern=(KDA, MLA), n_layers=2)


@pytest.fixture(scope="module")
def small_host():
    """Seeded weights of ``small_cfg()``, made once, as numpy (a donated
    step cannot delete them)."""
    return jax.tree.map(np.asarray, jax.jit(lambda: init_transformer(
        jax.random.PRNGKey(0), small_cfg()))())


def tokens(b=B):
    t = jnp.asarray(np.random.RandomState(0).randint(
        0, VOCAB, (b, T + 1)), jnp.int32)
    return t[:, :-1], t[:, 1:]


# -- the recurrence --------------------------------------------------- #

def _draw(seed, t, decay, b=2, h=3, d=16):
    """q, k, v, g, beta as the layer hands them over.  ``published``:
    exp(A) uniform in [1, 16] times a softplus that reaches 8, so a
    chunk's running sum passes -200 and exp(-G) is no float32;
    ``mild``: decays near 1, so the state carries across chunks."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    a = jax.random.uniform(ks[3], (h,), minval=1.0, maxval=16.0)
    soft = jax.nn.softplus(2 * jax.random.normal(ks[4], (b, t, h, d)))
    g = -a[:, None] * (soft if decay == "published" else soft * 1e-3)
    return (unit(jax.random.normal(ks[0], (b, t, h, d))) * d ** -0.5,
            unit(jax.random.normal(ks[1], (b, t, h, d))),
            jax.random.normal(ks[2], (b, t, h, d)), g,
            jax.nn.sigmoid(jax.random.normal(ks[5], (b, t, h))))


def _weighted(fn):
    return lambda weight, *a: jnp.sum(fn(*a) * weight)


def _with_grads(fn):
    """``fn``'s values and its five gradients under a weight, as one
    program."""
    return jax.jit(lambda weight, *a: (fn(*a), jax.grad(
        _weighted(fn), argnums=(1, 2, 3, 4, 5))(weight, *a)))


# compiled once a length: the two decays of a chunk count run the same
# two programs, and the token-by-token form run eagerly dispatches
# every token's ops, its gradient every token's again
_CHUNKED, _RECURRENT = _with_grads(kda_chunked), _with_grads(kda_recurrent)


@pytest.mark.parametrize("decay", ["published", "mild"])
@pytest.mark.parametrize("chunks", [1, 4, 8])
def test_chunked_recurrence_equals_the_recurrence(chunks, decay):
    """Values and all five gradients against the recurrence a token at
    a time, at one chunk, at four (one slab) and at eight (two slabs of
    four), every value finite in float32 under the published decays."""
    args = _draw(chunks, 64 * chunks, decay)
    if decay == "published":
        assert float(jnp.cumsum(args[3][:, :64], axis=1).min()) < -200
    weight = jnp.cos(jnp.arange(args[2].size, dtype=jnp.float32)).reshape(
        args[2].shape)

    (want, want_grads), (got, got_grads) = (
        _RECURRENT(weight, *args), _CHUNKED(weight, *args))
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    if decay == "mild":     # the state is not forgotten within a chunk
        assert float(jnp.abs(want[:, -1]).mean()) > 0.05
    for got, want in zip(got_grads, want_grads):
        assert bool(jnp.isfinite(got).all())
        np.testing.assert_allclose(
            got, want, rtol=2e-3, atol=2e-4 * float(jnp.abs(want).max()))


def test_recurrence_counters_and_shapes_it_refuses():
    reg = MetricsRegistry(enabled=True)
    prev = set_registry(reg)
    try:
        args = _draw(0, 512, "mild")
        # a function of its own: a trace cached for these shapes would
        # count nothing
        jax.eval_shape(lambda *a: kda_chunked(*a), *args)
    finally:
        set_registry(prev)
    assert reg.counter("kda/chunks").value == 8
    # the state at the start of each of two slabs: B x H x d x d floats
    assert reg.counter("kda/state_bytes_kept").value == 2 * 2 * 3 * 16 * 16 * 4
    # a system a chunk and head, each inverted once a pass
    assert reg.counter("kda/systems_inverted").value == 2 * 3 * 8
    # and every one of them takes its pair weights from the kernel
    assert reg.counter("kda/pair_blocks_in_vmem").value == 2 * 3 * 8
    with pytest.raises(ValueError, match="whole chunks"):
        kda_chunked(*_draw(0, 96, "mild"))


# -- the pair weights of a chunk --------------------------------------- #

def _pair_weights_jnp(q, k, G, g, sub):
    """What ``ops/kda.py`` ran until its kernel pair (PR 37), and the
    kernels' yardstick since: ``(A, A')`` of a chunk from tensors that
    hold every pair's exponent, ``col`` and ``kcol`` ``(..., n, C,
    d_k)`` and ``pair`` ``(..., n, sub, sub, d_k)``.  ``q``, ``k``,
    ``G``, ``g`` ``(..., C, d_k)``, results ``(..., C, C)``."""
    C, dk = k.shape[-2:]
    n = C // sub
    lead = k.shape[:-2]
    by_sub = lambda x: x.reshape(*lead, n, sub, dk)
    qs, ks, Gs = by_sub(q), by_sub(k), by_sub(G)
    # R_a: the running sum just before sub-block a
    ref = Gs[..., 0, :] - by_sub(g)[..., 0, :]                # (..., n, dk)
    row = jnp.exp(Gs - ref[..., None, :])                     # <= 1
    before = jnp.arange(C)[None, :] < (jnp.arange(n) * sub)[:, None]
    col = jnp.exp(jnp.where(
        before[..., None],
        ref[..., :, None, :] - G[..., None, :, :], -jnp.inf))  # (..., n, C, dk)
    kcol = k[..., None, :, :] * col
    off_kk = jnp.einsum("...atc,...aic->...ati", ks * row, kcol)
    off_qk = jnp.einsum("...atc,...aic->...ati", qs * row, kcol)
    # the pairs inside a sub-block, each with its own exponent
    t, i = jnp.arange(sub)[:, None], jnp.arange(sub)[None, :]
    pair = jnp.exp(jnp.where(
        (t >= i)[..., None],
        Gs[..., :, None, :] - Gs[..., None, :, :], -jnp.inf))
    in_kk = jnp.where(t > i, jnp.einsum(
        "...atc,...aic,...atic->...ati", ks, ks, pair), 0.0)
    in_qk = jnp.einsum("...atc,...aic,...atic->...ati", qs, ks, pair)
    own = jnp.eye(n, dtype=k.dtype)[:, None, :, None]         # (n, 1, n, 1)

    def whole(off, inside):
        # sub-block a's rows: the columns before it, and its own
        placed = inside[..., :, :, None, :] * own             # (n, sub, n, sub)
        return (off.reshape(*lead, n, sub, n, sub) + placed).reshape(
            *lead, C, C)

    return whole(off_kk, in_kk), whole(off_qk, in_qk)


def _one_chunk(c, decay, h, d=16):
    """q, k, g of one chunk of ``c`` tokens in each of ``h`` heads, as
    ``_chunk_parts`` sees them: ``(h, c, d)``."""
    q, k, _, g, _ = (jnp.moveaxis(x[0], 1, 0) for x in _draw(
        c + h, c, decay, b=1, h=h, d=d))
    return q, k, g


@functools.cache
def _both_forms(c):
    """The kernel's form and the stored one, each jitted; once a chunk
    length, so that the two decays of a shape (and the underflow case)
    run one compiled program and not one each."""
    sub = min(kda.SUB, c)
    return (jax.jit(lambda q, k, g: kda._pair_weights(
                q, k, jnp.cumsum(g, axis=-2))),
            jax.jit(lambda q, k, g: _pair_weights_jnp(
                q, k, jnp.cumsum(g, axis=-2), g, sub)))


@functools.cache
def _both_forms_grads(c):
    """``dq``, ``dk``, ``dg`` of both forms under two weights handed in
    as arguments, jitted once a chunk length as well."""
    def loss(fn):
        return lambda weights, *a: sum(
            jnp.sum(x * w) for x, w in zip(fn(*a), weights))

    return tuple(jax.jit(jax.grad(loss(fn), argnums=(1, 2, 3)))
                 for fn in _both_forms(c))


# a full chunk, a short one (padded inside), a count of blocks that is
# no whole kernel step, and keys as wide as the lanes
PAIR_SHAPES = pytest.mark.parametrize("c,h,d", [
    (64, 3, 16), (32, 5, 16), (16, 129, 16), (64, 2, 128)],
    ids=["full", "short", "h129", "lanes128"])


@pytest.mark.parametrize("decay", ["published", "mild"])
@PAIR_SHAPES
def test_pair_kernel_equals_the_jnp_form(c, h, d, decay):
    """``A`` and ``A'`` from the kernel, float32, against the form that
    stores every pair's exponent."""
    q, k, g = _one_chunk(c, decay, h, d)
    if decay == "published" and c == 64:
        assert float(jnp.cumsum(g, axis=1).min()) < -200
    kernel, plain = _both_forms(c)
    got, want = kernel(q, k, g), plain(q, k, g)
    for a, b in zip(got, want):
        assert a.shape == b.shape == (h, c, c)
        np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-6 * float(jnp.abs(b).max()))
    assert not np.triu(got[0]).any() and not np.triu(got[1], 1).any()
    assert np.diagonal(got[1], axis1=1, axis2=2).any()


@pytest.mark.parametrize("decay", ["published", "mild"])
@PAIR_SHAPES
def test_pair_kernel_vjp_equals_autodiff_through_the_jnp_form(c, h, d, decay):
    """``dq``, ``dk`` and ``dg`` from the backward kernel, which makes
    the pair weights again from q, k and ``G``, against autodiff
    through the stored form; cotangents stand above the diagonal too,
    where neither may read them."""
    q, k, g = _one_chunk(c, decay, h, d)
    weights = [f(jnp.arange(h * c * c, dtype=jnp.float32)).reshape(h, c, c)
               for f in (jnp.cos, jnp.sin)]
    kernel, plain = _both_forms_grads(c)
    got, want = kernel(weights, q, k, g), plain(weights, q, k, g)
    for a, b in zip(got, want):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=2e-6 * float(jnp.abs(b).max()))


def test_pair_weights_stay_lower_and_finite_when_exponents_underflow():
    """Decays a thousand times the published ones: every weight but a
    row's own and its neighbours' is below float32.  ``A`` stays
    strictly lower, ``A'`` lower, every entry and every cotangent
    finite, and the diagonal of ``A'`` is q . k."""
    q, k, g = _one_chunk(64, "published", 3)
    g = 1e3 * g
    assert float(jnp.cumsum(g, axis=1).min()) < -1e5
    kernel, _ = _both_forms(64)
    A, A_q = kernel(q, k, g)
    assert bool(jnp.isfinite(A).all()) and bool(jnp.isfinite(A_q).all())
    assert not np.triu(A).any() and not np.triu(A_q, 1).any()
    np.testing.assert_allclose(
        np.diagonal(A_q, axis1=1, axis2=2), jnp.sum(q * k, -1), rtol=1e-5,
        atol=1e-7)
    grads = jax.jit(jax.grad(
        lambda *a: sum(jnp.sum(x) for x in kernel(*a)),
        argnums=(0, 1, 2)))(q, k, g)
    assert all(bool(jnp.isfinite(x).all()) for x in grads)


def _equations(jaxpr):
    """Equations of a jaxpr and of every jaxpr inside it."""
    count = 0
    for eqn in jaxpr.eqns:
        count += 1
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    count += _equations(inner)
    return count


def test_pair_kernel_bodies_stay_small_to_lower():
    """A Pallas kernel is traced and lowered to Mosaic once a call site
    at every process start, whatever the compile cache holds, and the
    Kimi step has 12 + 4 sites of this pair: the seconds a site follow
    the body's size.  The bodies hold eight columns a loop turn and
    the three earlier sub-blocks' products once each (531 and 868
    equations: 0.05 to 0.11 s a site in the sandbox); with every
    sub-block and row written out they would be several thousand."""
    x = jax.ShapeDtypeStruct((kda._BLOCKS, 4, 16, 128), jnp.float32)
    text = jax.make_jaxpr(jax.grad(
        lambda q, k, G: sum(jnp.sum(a) for a in kda._pairs(q, k, G, True)),
        argnums=(0, 1, 2)))(x, x, x)
    bodies = [eqn.params["jaxpr"] for eqn in text.jaxpr.eqns
              if eqn.primitive.name == "pallas_call"]
    assert len(bodies) == 2
    forward, backward = (_equations(body) for body in bodies)
    assert forward <= 600 and backward <= 1000, (forward, backward)


# -- the solve inside a chunk ----------------------------------------- #

@functools.cache
def _layers_systems(c):
    """``Diag(beta) A`` of one chunk of ``c`` tokens in each of 129
    heads, as the layer makes it."""
    @jax.jit
    def made(q, k, g, beta):
        return beta[..., None] * kda._pair_weights(
            q, k, jnp.cumsum(g, axis=-2))[0]

    q, k, _, g, beta = (jnp.moveaxis(x[0], 1, 0) for x in _draw(
        c, c, "mild", b=1, h=129))
    return made(q, k, g, beta)


def _systems(seed, systems, c, entries):
    """``N`` ``(systems, c, c)`` and two right-hand sides side by side.
    ``layer``: as the layer makes it; ``near_one``: entries of size 0.9
    to 1 and either sign, where the inverse's entries grow (to 1e3 at
    16 rows, 1e8 at 64).  On and above the diagonal stands garbage that
    no result may depend on."""
    rs = np.random.RandomState(seed)
    if entries == "layer":
        below = np.asarray(_layers_systems(c)[:systems])
    else:
        below = rs.uniform(0.9, 1.0, (systems, c, c)) * rs.choice(
            [-1.0, 1.0], (systems, c, c))
    garbage = 1e6 * rs.standard_normal((systems, c, c))
    return (jnp.asarray(np.where(np.tri(c, k=-1, dtype=bool), below,
                                 garbage), jnp.float32),
            jnp.asarray(rs.standard_normal((systems, c, 2 * 16)),
                        jnp.float32))


@jax.jit
def _xla_solve(N, R):
    """What ``solve`` replaced: XLA's triangular solve of ``I + N``."""
    return jax.lax.linalg.triangular_solve(
        jnp.tril(N, -1) + jnp.eye(N.shape[-1]), R, left_side=True,
        lower=True, unit_diagonal=True)


# one trace a shape, whatever the entries
_solve = jax.jit(kda.solve)


@pytest.mark.parametrize("entries", ["layer", "near_one"])
@pytest.mark.parametrize("c", [64, 16])
@pytest.mark.parametrize("systems", [1, 127, 128, 129])
def test_solve_equals_xlas_triangular_solve(systems, c, entries):
    """One lane short of a vector, a whole vector, one lane over (the
    padding) and a lone system, at the chunk's 64 rows and at 16."""
    N, R = _systems(systems + c, systems, c, entries)
    got, want = _solve(N, R), _xla_solve(N, R)
    assert got.shape == want.shape == R.shape
    size = jnp.abs(want).max(axis=(1, 2), keepdims=True)
    if entries == "near_one":
        assert float(size.max()) > (1e6 if c == 64 else 10)
    np.testing.assert_array_less(jnp.abs(got - want) / size, 1e-5)


@pytest.mark.parametrize("systems,c,entries", [
    (129, 16, "layer"), (3, 64, "layer"), (5, 16, "near_one")])
def test_solve_vjp_equals_autodiff_through_xlas(systems, c, entries):
    """Both arguments' cotangents: the inverse applied transposed, and
    the strictly lower part of ``-dR X^T``; none where ``N`` is not
    read."""
    N, R = _systems(7 * systems + c, systems, c, entries)
    weight = jnp.cos(jnp.arange(R.size, dtype=jnp.float32)).reshape(R.shape)
    loss = lambda fn: lambda N, R: jnp.sum(fn(N, R) * weight)
    got = jax.jit(jax.grad(loss(kda.solve), argnums=(0, 1)))(N, R)
    want = jax.jit(jax.grad(loss(_xla_solve), argnums=(0, 1)))(N, R)
    assert not np.triu(got[0]).any()
    for g, w in zip(got, want):
        size = jnp.abs(w).max(axis=(1, 2), keepdims=True)
        np.testing.assert_array_less(jnp.abs(g - w) / size, 2e-5)


# -- the flash kernels at a value width of their own ------------------ #

@pytest.mark.parametrize("t,block", [(64, 64), (256, 128)],
                         ids=["one-block", "four-pairs"])
def test_flash_kernels_with_keys_wider_than_values(t, block):
    """Keys 192 wide, values 128: forward and the three gradients
    against plain scores, interpreted; the scale is the key width's."""
    ks = jax.random.split(jax.random.PRNGKey(t), 4)
    q = jax.random.normal(ks[0], (1, t, 2, 192))
    k = jax.random.normal(ks[1], (1, t, 2, 192))
    v = jax.random.normal(ks[2], (1, t, 2, 128))
    w = jax.random.normal(ks[3], (1, t, 2, 128))
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=block, block_k=block, interpret=True)
    plain = lambda q, k, v: local_attention(q, k, v, causal=True)
    got = flash(q, k, v)
    assert got.shape == (1, t, 2, 128)
    np.testing.assert_allclose(got, plain(q, k, v), rtol=2e-5, atol=2e-5)
    grad = lambda f: jax.grad(
        lambda q, k, v: jnp.sum(f(q, k, v) * w), argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(grad(flash), grad(plain)):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    with pytest.raises(ValueError, match="q and k widths differ"):
        flash_attention(q, v, v, causal=True, interpret=True)


# -- the parameter trees ---------------------------------------------- #

def test_each_mixer_has_its_own_tree():
    cfg = hybrid_cfg()
    assert cfg.blocks_by_position and cfg.mixers == ["kda", "mla"]
    params = jax.eval_shape(
        lambda: init_transformer(jax.random.PRNGKey(0), cfg))
    shape = lambda blk: {k: v.shape[2:] for k, v in blk.items()}
    kda, mla = shape(params["blocks"][0]), shape(params["blocks"][2])
    mixer = lambda blk: {k: v for k, v in blk.items() if k not in (
        "ln1", "ln2", "router", "router_bias", "w1", "w2", "w3", "ws1",
        "ws2", "ws3")}
    assert mixer(kda) == {
        "wqkv": (32, 3, 4, 8), "conv": (3, 4, 8, 4), "wf_a": (32, 8),
        "wf_b": (8, 4, 8), "a_log": (4,), "dt_bias": (4, 8),
        "wbeta": (32, 4), "wg_a": (32, 8), "wg_b": (8, 4, 8),
        "o_norm": (8,), "wo": (4, 8, 32)}
    assert mixer(mla) == {
        "wq": (32, 4, 16), "wkva": (32, 24), "kv_norm": (16,),
        "wkvb": (16, 4, 24), "wo": (4, 16, 32)}
    assert kda["router_bias"] == (8,)
    # the leading layer: KDA with the dense MLP, no router
    lead = params["leading"][0]
    assert lead["w1"].shape == (32, 48) and "router" not in lead
    assert jax.tree.structure(params) == jax.tree.structure(
        param_specs(cfg), is_leaf=lambda s: isinstance(
            s, jax.sharding.PartitionSpec))
    # the same mixer at every position keeps the single stack
    alike = hybrid_cfg(layer_pattern=(KDA,) * 4)
    assert not alike.blocks_by_position


@pytest.mark.parametrize("kw", [
    dict(mixer="mamba"), dict(mixer="mla"), dict(mixer="kda", conv_taps=0),
    dict(mixer="kda", window=8), dict(mixer="mla", kv_latent=8,
                                      yarn_factor=2, yarn_original_max=8)])
def test_attention_kind_validation(kw):
    with pytest.raises(ValueError):
        AttentionKind("x", **kw)


def test_the_published_initialisers(small_host):
    blk = small_host["blocks"][0]       # the KDA layer
    a = np.exp(np.asarray(blk["a_log"]))
    assert a.min() >= 1 and a.max() <= 16
    dt = np.asarray(jax.nn.softplus(blk["dt_bias"]))
    assert dt.min() >= 1e-3 * 0.99 and dt.max() <= 1e-1 * 1.01


# -- the selection bias ------------------------------------------------ #

def test_selection_bias_flips_a_choice_and_leaves_the_gates_to_the_scores():
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 6))
    probs, top, gates = route_top_k(x, w, 2, "sigmoid", 2.446)
    s = np.asarray(jax.nn.sigmoid(x @ w))
    # lift the expert that came third for token 0 past the second
    order = np.argsort(-s[0])
    bias = np.zeros(6, np.float32)
    bias[order[2]] = s[0, order[1]] - s[0, order[2]] + 1e-3
    probs_b, top_b, gates_b = route_top_k(
        x, w, 2, "sigmoid", 2.446, jnp.asarray(bias))
    assert sorted(np.asarray(top_b[0])) == sorted(order[[0, 2]])
    assert sorted(np.asarray(top[0])) == sorted(order[[0, 1]])
    # the gates are the winners' scores without the bias
    chosen = np.take_along_axis(s, np.asarray(top_b), axis=1)
    np.testing.assert_allclose(
        gates_b, 2.446 * chosen / chosen.sum(1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(probs_b, probs)
    # softmax scores take it too
    _, top_s, gates_s = route_top_k(x, w, 2, "softmax", 1.0,
                                    jnp.asarray(bias) * 10)
    assert order[2] in np.asarray(top_s[0])
    assert float(gates_s.sum(1).max()) <= 1 + 1e-5


def test_the_bias_is_held_fixed_by_gradient_and_by_weight_decay(small_host):
    """Three AdamW steps with a weight decay that moves every other
    leaf: the bias stays to the last bit, and it decides choices (the
    loss differs from the unbiased router's)."""
    cfg, mc, host = small_cfg(), one_chip(), small_host
    bias = lambda p: [np.asarray(b["router_bias"]) for b in p["blocks"]]
    host = dict(host, blocks=tuple(dict(
        b, router_bias=b["router_bias"] + 0.3 * (np.arange(8) % 3).astype(
            np.float32)) for b in host["blocks"]))
    before = bias(host)
    opt = optax.adamw(1e-3, weight_decay=0.1)
    params = shard_params(mc, cfg, host)
    state = shard_opt_state(opt, params)
    step = make_train_step(mc, cfg, opt)
    losses = []
    for _ in range(3):
        params, state, loss = step(params, state, *tokens())
        losses.append(float(loss))
    assert all(np.array_equal(a, b) for a, b in zip(bias(params), before))
    moved = np.abs(np.asarray(params["blocks"][0]["router"])
                   - np.asarray(host["blocks"][0]["router"])).max()
    assert moved > 0 and np.isfinite(losses).all()
    unbiased = jax.tree_util.tree_map_with_path(
        lambda path, a: a * 0 if "router_bias" in str(path) else a, host)
    params = shard_params(mc, cfg, unbiased)
    _, _, loss0 = step(params, shard_opt_state(opt, params), *tokens())
    assert abs(float(loss0) - losses[0]) > 1e-4


def test_toy_step_makes_every_chunks_pair_weights_in_the_kernel():
    """The toy step as it is traced: four KDA layers (the leading one
    and the period's three) of 2 sequences x 4 heads x 2 chunks, each
    block's pair weights from the kernel and each block a system."""
    cfg, mc = hybrid_cfg(), one_chip()
    opt = optax.sgd(0.1)
    shapes = jax.eval_shape(
        lambda: init_transformer(jax.random.PRNGKey(0), cfg))
    reg = MetricsRegistry(enabled=True)
    prev = set_registry(reg)
    try:
        make_train_step(mc, cfg, opt).trace(
            shapes, jax.eval_shape(opt.init, shapes), *tokens())
    finally:
        set_registry(prev)
    assert reg.counter("kda/pair_blocks_in_vmem").value == 4 * 2 * 4 * 2
    assert reg.counter("kda/systems_inverted").value == 4 * 2 * 4 * 2


def test_hold_selection_bias_serves_a_step_of_ones_own():
    """Outside ``make_train_step`` (a step around ``optimizer.update``
    as ``training/updater.py`` has one): the wrapped optimizer leaves
    every ``router_bias`` leaf to the bit, moves the rest as the plain
    one does, and its state is the plain one's."""
    from chainermn_tpu.models.transformer import hold_selection_bias

    params = {"blocks": ({"router": jnp.ones((4, 8)),
                          "router_bias": jnp.arange(8.0)},),
              "embed": jnp.ones((8, 4))}
    grads = jax.tree.map(jnp.zeros_like, params)
    plain = optax.adamw(1e-2, weight_decay=0.1)
    held = hold_selection_bias(plain)
    state = held.init(params)
    assert jax.tree.structure(state) == jax.tree.structure(
        plain.init(params))
    want, _ = plain.update(grads, state, params)
    got, _ = held.update(grads, state, params)
    assert float(jnp.abs(want["blocks"][0]["router_bias"]).max()) > 0
    assert not np.asarray(got["blocks"][0]["router_bias"]).any()
    for leaf in ("router",):
        np.testing.assert_array_equal(
            got["blocks"][0][leaf], want["blocks"][0][leaf])
    np.testing.assert_array_equal(got["embed"], want["embed"])


# -- refusals, by name -------------------------------------------------- #

@pytest.mark.parametrize("mesh,kw,match", [
    (dict(seq=2), {}, "seq, model and pipe mesh axes must be 1"),
    (dict(model=2), {}, "seq, model and pipe mesh axes must be 1"),
    (dict(pipe=2), dict(leading_layers=(), n_layers=8),
     "seq, model and pipe mesh axes must be 1"),
    (dict(data=1), dict(attention="ring"), "attention='flash' or 'local'"),
    (dict(data=1), dict(attention="ulysses"),
     "attention='flash' or 'local'"),
    (dict(data=2), dict(fsdp=True), "fsdp=True is not implemented for the "
     "kda/mla layers"),
], ids=["seq", "model", "pipe", "ring", "ulysses", "fsdp"])
def test_meshes_and_paths_the_mixers_cannot_run_are_refused(mesh, kw, match):
    n = int(np.prod(list(mesh.values())))
    mc = MeshConfig(devices=jax.devices()[:n], **mesh)
    with pytest.raises(ValueError, match=match):
        make_train_step(mc, hybrid_cfg(**kw), optax.sgd(1.0))


def test_data_and_expert_axes_stay_open(small_host):
    """Two data members, each an expert group of two that shares its
    experts out, against two data members that hold them whole (the
    balancing loss is a data member's own): the same loss and the same
    update."""
    def one_step(**mesh):
        n = int(np.prod(list(mesh.values())))
        mc = MeshConfig(devices=jax.devices()[:n], **mesh)
        cfg = small_cfg()
        params = shard_params(mc, cfg, small_host)
        opt = optax.sgd(1.0)
        params, _, loss = make_train_step(mc, cfg, opt)(
            params, shard_opt_state(opt, params), *tokens(4))
        return float(loss), jax.tree.map(
            lambda a, b: b - np.asarray(a), params, small_host)

    loss1, delta1 = one_step(data=2)
    loss4, delta4 = one_step(data=2, expert=2)
    assert loss4 == pytest.approx(loss1, rel=2e-5)
    for a, b in zip(jax.tree.leaves(delta1), jax.tree.leaves(delta4)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5)


@pytest.mark.parametrize("kw,named", [
    ({}, "AttentionKind.mixer=kda/mla"),
    ({}, "router_bias"),
    ({}, "norm_eps"),
], ids=["mixers", "selection-bias", "norm-eps"])
def test_decoding_and_serving_refuse_the_new_fields(kw, named):
    from chainermn_tpu.serving.engine import TransformerAdapter

    cfg = hybrid_cfg(**kw)
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
    (dict(router_bias="learned"), "router_bias"),
    (dict(router_bias="selection", moe_dispatch="capacity",
          expert_act="relu", experts_held=(), router_score="softmax",
          router_scale=1.0, shared_expert_d_ff=0), "dropless expert layer"),
    (dict(attn_gate="per_head"), "attn_gate is softmax attention's"),
])
def test_config_validation(kw, match):
    with pytest.raises(ValueError, match=match):
        hybrid_cfg(**kw)
