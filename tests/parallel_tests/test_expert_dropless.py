"""The dropless expert dispatch (``parallel.expert``): against a dense
application of the chosen experts, under the worst imbalance, with a
share of the experts held, and across an expert axis."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from chainermn_tpu.communicators._mesh_utils import make_world_mesh
from chainermn_tpu.parallel.expert import (
    expert_parallel_moe,
    expert_parallel_moe_dropless,
    grouped_dense,
    route_top_k,
)

AX = "world"
D, F = 8, 16


def _grouped_fn(p, rows, sizes):
    return grouped_dense(
        jax.nn.silu(grouped_dense(rows, p["w1"], sizes))
        * grouped_dense(rows, p["w3"], sizes), p["w2"], sizes)


@jax.custom_vjp
def _poison(x, n):
    """Rows from ``n`` on made NaN, and so is their cotangent: what the
    chip's grouped kernels may leave past the last group."""
    return jnp.where(jnp.arange(x.shape[0])[:, None] < n, x, jnp.nan)


_poison.defvjp(lambda x, n: (_poison(x, n), n),
               lambda n, g: (_poison(g, n), None))


def _poisoned_fn(p, rows, sizes):
    n = jnp.sum(sizes)
    return _poison(_grouped_fn(p, _poison(rows, n), sizes), n)


def _experts(rng, n):
    return {k: jnp.asarray(rng.randn(n, *s).astype(np.float32) * 0.3)
            for k, s in (("w1", (D, F)), ("w3", (D, F)), ("w2", (F, D)))}


def _dense(x, router_w, experts, top_k, first=0):
    """Every token through its chosen experts one by one (those held:
    ``experts`` are numbers ``first ...`` of the router's)."""
    probs = np.asarray(jax.nn.softmax(jnp.asarray(x) @ router_w, -1))
    held = experts["w1"].shape[0]
    out = np.zeros_like(x)
    for i in range(x.shape[0]):
        chosen = np.argsort(-probs[i], kind="stable")[:top_k]
        norm = probs[i, chosen].sum() if top_k > 1 else 1.0
        for e in chosen:
            if first <= e < first + held:
                p = {k: v[e - first] for k, v in experts.items()}
                y = (jax.nn.silu(x[i] @ p["w1"]) * (x[i] @ p["w3"])) \
                    @ p["w2"]
                out[i] += np.asarray(y) * probs[i, e] / norm
    return out


def _run(mesh, x, router_w, experts, top_k, first=0, shard=False,
         expert_fn=_grouped_fn):
    fn = jax.jit(jax.shard_map(
        lambda xs, rw, ep: expert_parallel_moe_dropless(
            xs, rw, ep, expert_fn, top_k=top_k, first_expert=first,
            axis_name=AX),
        mesh=mesh,
        in_specs=(P(AX) if shard else P(), P(), P(AX) if shard else P()),
        out_specs=(P(AX) if shard else P(), P(),
                   P(AX) if shard else P())))
    return fn(x, router_w, experts)


@pytest.fixture(scope="module")
def one():
    return make_world_mesh(axis_name=AX, devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def four():
    return make_world_mesh(axis_name=AX, devices=jax.devices()[:4])


@pytest.mark.parametrize("top_k", [1, 2, 4])
def test_matches_dense_application(one, top_k):
    rng = np.random.RandomState(3)
    x = rng.randn(64, D).astype(np.float32)
    router_w = rng.randn(D, 8).astype(np.float32)
    experts = _experts(rng, 8)
    out, aux, chosen = _run(one, x, router_w, experts, top_k)
    np.testing.assert_allclose(
        np.asarray(out), _dense(x, router_w, experts, top_k),
        rtol=1e-4, atol=1e-5)
    assert chosen.shape == (64, top_k) and float(aux) > 0


@pytest.mark.parametrize("top_k", [1, 2])
def test_every_token_to_one_held_expert_drops_nothing(one, top_k):
    """The worst imbalance: the router sends every token's first choice
    to expert 5.  The capacity dispatch at its default factor drops most
    of them; the dropless one computes every row."""
    rng = np.random.RandomState(4)
    x = np.abs(rng.randn(96, D)).astype(np.float32) + 0.5
    router_w = rng.randn(D, 8).astype(np.float32) * 0.01
    router_w[:, 5] = 4.0
    experts = _experts(rng, 8)
    out, _, chosen = _run(one, x, router_w, experts, top_k)
    assert (np.asarray(chosen)[:, 0] == 5).all()
    ref = _dense(x, router_w, experts, top_k)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)
    assert (np.abs(ref).sum(axis=1) > 0).all()

    # the capacity path on the same input loses rows: the control
    def relu_fn(p, tokens):
        return jax.nn.relu(tokens @ p["w1"]) @ p["w2"]

    dropped, _ = jax.jit(jax.shard_map(
        lambda xs, rw, ep: expert_parallel_moe(
            xs, rw, ep, relu_fn, axis_name=AX, top_k=top_k),
        mesh=one, in_specs=(P(), P(), P()), out_specs=(P(), P())))(
            x, router_w, {k: experts[k] for k in ("w1", "w2")})
    assert (np.abs(np.asarray(dropped)).sum(axis=1) < 1e-6).sum() > 32


def test_shares_add_up_to_the_whole_layer(one):
    """Four members each holding 4 of 16 experts: their partial results,
    gates normalised over all k chosen, add up to the uncut layer."""
    rng = np.random.RandomState(5)
    x = rng.randn(64, D).astype(np.float32)
    router_w = rng.randn(D, 16).astype(np.float32)
    experts = _experts(rng, 16)
    whole, aux, _ = _run(one, x, router_w, experts, 4)
    parts = []
    for first in (0, 4, 8, 12):
        share = {k: v[first:first + 4] for k, v in experts.items()}
        part, aux_part, _ = _run(one, x, router_w, share, 4, first=first)
        np.testing.assert_allclose(
            np.asarray(part), _dense(x, router_w, share, 4, first),
            rtol=1e-4, atol=1e-5)
        # the balancing loss is over all 16 columns on every member
        assert float(aux_part) == pytest.approx(float(aux), rel=1e-6)
        parts.append(np.asarray(part))
    np.testing.assert_allclose(sum(parts), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("first,held", [(0, 8), (4, 4)])
def test_expert_axis_exchange_matches_one_device(one, four, first, held):
    """Tokens and experts split over four members, rows travelling by
    all-to-all: the same result, the same gradients."""
    rng = np.random.RandomState(6)
    x = rng.randn(64, D).astype(np.float32)
    router_w = rng.randn(D, 8).astype(np.float32)
    experts = _experts(rng, held)

    def loss(mesh, shard, fn):
        def f(x, rw, ep):
            out, aux, _ = _run(mesh, x, rw, ep, 2, first=first, shard=shard,
                               expert_fn=fn)
            return jnp.sum(out * out) + aux
        return jax.value_and_grad(f, argnums=(0, 1, 2))(x, router_w, experts)

    # across the axis the kernels' undefined rows also travel home
    (l1, g1), (l4, g4) = (loss(one, False, _grouped_fn),
                          loss(four, True, _poisoned_fn))
    assert float(l4) == pytest.approx(float(l1), rel=1e-5)
    for a, b in zip(jax.tree.leaves(g4), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_router_is_float32_whatever_the_input():
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(32, D), jnp.bfloat16)
    router_w = jnp.asarray(rng.randn(D, 8), jnp.float32)
    probs, top_i, gates = route_top_k(x, router_w, 2)
    assert probs.dtype == gates.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-6)
    ref = jax.nn.softmax(x.astype(jnp.float32) @ router_w, axis=-1)
    np.testing.assert_allclose(np.asarray(probs), np.asarray(ref),
                               rtol=1e-5, atol=1e-7)


def test_held_range_outside_the_router_is_an_error(one):
    rng = np.random.RandomState(8)
    with pytest.raises(ValueError, match="held"):
        _run(one, rng.randn(8, D).astype(np.float32),
             rng.randn(D, 8).astype(np.float32), _experts(rng, 4), 2, first=6)


def test_rows_past_the_last_group_never_reach_the_result(one):
    """On the chip the grouped kernels leave the rows past the last
    group undefined, forward and backward (the CPU's zero them, so a
    fault here shows only there: NaN from the second step on).  An
    expert network that poisons those rows both ways gives the same
    result and the same finite gradients."""
    rng = np.random.RandomState(9)
    x = rng.randn(64, D).astype(np.float32)
    router_w = rng.randn(D, 16).astype(np.float32)
    experts = _experts(rng, 4)         # 4 of 16 held: most rows are past

    def loss(fn):
        def f(x, rw, ep):
            out, aux, _ = jax.shard_map(
                lambda xs, rw, ep: expert_parallel_moe_dropless(
                    xs, rw, ep, fn, top_k=4, first_expert=4, axis_name=AX),
                mesh=one, in_specs=(P(), P(), P()),
                out_specs=(P(), P(), P()))(x, rw, ep)
            return jnp.sum(out * out) + aux
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))(
            x, router_w, experts)

    (l0, g0), (l1, g1) = loss(_grouped_fn), loss(_poisoned_fn)
    assert np.isfinite(float(l1)) and float(l1) == pytest.approx(float(l0))
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------- #
# the ladder of buffer sizes
# --------------------------------------------------------------------- #

# 256 tokens at 4 choices of 32 experts, 4 held: 128 rows expected here,
# so the buffer has 256, 512 or all 1,024 rows
LN, LK, LE, LG = 256, 4, 32, 4
LADDER = (256, 512, 1024)


def _routed(n_held, rng):
    """Tokens and a router under which exactly ``n_held`` (token,
    choice) rows fall to the held experts 0..3: a token chooses either
    all four of them (expert 0 first), or expert 0 and three experts
    not held, or none held."""
    n_all = max(0, -(-(n_held - LN) // 3))
    n_one = n_held - 4 * n_all
    assert 0 <= n_one <= LN - n_all
    x = rng.randn(LN, D).astype(np.float32) * 0.1
    x[:, :3] = 0
    x[:n_all, 1] = 1.0
    x[n_all:n_all + n_one, 0] = 1.0
    x[n_all + n_one:, 2] = 1.0
    router_w = rng.randn(D, LE).astype(np.float32) * 0.01
    router_w[:3] = 0
    router_w[0, [0, 4, 5, 6]] = router_w[1, [0, 1, 2, 3]] = \
        router_w[2, [4, 5, 6, 7]] = 8.0, 6.0, 5.0, 4.0
    return x, router_w


def _part_at(C, expert_fn=_grouped_fn):
    """The layer as :func:`expert_parallel_moe_dropless` runs it, but
    for its buffer: ``C`` rows whatever the count (None: its own)."""
    from chainermn_tpu.parallel import expert as ep

    def layer(x, router_w, experts):
        was = ep._buffer_rungs
        if C is not None:
            ep._buffer_rungs = lambda *a: (C,)
        try:
            return expert_parallel_moe_dropless(
                x, router_w, experts, expert_fn, top_k=LK, axis_name=AX)[:2]
        finally:
            ep._buffer_rungs = was
    return layer


@functools.lru_cache(maxsize=None)
def _grads_fn(mesh, C, expert_fn):
    """Loss and gradients of :func:`_part_at`'s layer, compiled once
    for all the cases that share its shapes."""
    def f(x, rw, ep):
        out, aux = jax.shard_map(
            _part_at(C, expert_fn), mesh=mesh, in_specs=(P(), P(), P()),
            out_specs=(P(), P()))(x, rw, ep)
        return jnp.sum(out * jnp.cos(out)) + aux, out
    return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True))


def _loss_and_grads(mesh, C, x, router_w, experts, expert_fn=_grouped_fn):
    (_, out), grads = _grads_fn(mesh, C, expert_fn)(x, router_w, experts)
    return np.asarray(out), jax.tree.leaves(grads)


def _same(got, want):
    out, grads = got
    ref, ref_grads = want
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
    for a, b in zip(grads, ref_grads, strict=True):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("cell,rows,held,of,rungs", [
    ("mellum", 16384 * 8, 16, 64, (65536, 131072)),
    ("laguna", 16384 * 8, 32, 256, (32768, 65536, 131072)),
    ("nemotron", 16384 * 6, 8, 128, (12288, 24576, 98304)),
    ("kimi", 16384 * 8, 8, 256, (8192, 16384, 131072)),
    ("every expert held", 16384 * 8, 64, 64, (131072,)),
    ("half held", 4096, 8, 16, (4096,)),
    ("toy: whole tiles, none above the rows", 200, 1, 16, (128, 200)),
    ("this file's", LN * LK, LG, LE, LADDER),
])
def test_buffer_rungs_by_hand(cell, rows, held, of, rungs):
    from chainermn_tpu.parallel.expert import _buffer_rungs, buffer_rows

    assert _buffer_rungs(rows, held, of) == rungs
    # the smallest rung that holds the count: at it, one over, none
    for i, r in enumerate(rungs):
        assert int(buffer_rows(np.int32(r), rows, held, of)) == r
        if i + 1 < len(rungs):
            assert int(buffer_rows(np.int32(r + 1), rows, held, of)) \
                == rungs[i + 1]
    assert int(buffer_rows(np.int32(0), rows, held, of)) == rungs[0]
    np.testing.assert_array_equal(
        buffer_rows(np.asarray([[0, rungs[0]], [rows, 1]], np.int32),
                    rows, held, of),
        [[rungs[0], rungs[0]], [rows, rungs[0]]])


@pytest.mark.parametrize("n_held,rung", [
    (0, 256), (1, 256), (256, 256), (257, 512), (512, 512), (513, 1024),
    (1024, 1024)],
    ids=["none held", "one row", "at the first rung", "one over it",
         "at the second rung", "one over it: the full buffer",
         "every row held"])
def test_each_rung_is_the_full_buffers_layer(one, n_held, rung):
    """The routing forces the rung: exactly ``n_held`` rows fall to
    the held experts.  Result and gradients (tokens, router, every
    expert leaf) are the full buffer's whatever the rung, and the rung
    taken is the one the counter's function names."""
    from chainermn_tpu.parallel.expert import buffer_rows

    rng = np.random.RandomState(11)
    x, router_w = _routed(n_held, rng)
    experts = _experts(rng, LG)
    chosen = np.asarray(_run(one, x, router_w, experts, LK)[2])
    assert (chosen < LG).sum() == n_held
    assert int(buffer_rows(np.int32(n_held), LN * LK, LG, LE)) == rung
    full = _loss_and_grads(one, LN * LK, x, router_w, experts)
    _same(_loss_and_grads(one, None, x, router_w, experts), full)
    # and that rung by itself, with no switch around it
    _same(_loss_and_grads(one, rung, x, router_w, experts), full)


def test_every_token_to_one_held_expert_takes_the_last_rung(one):
    """Every token's first choice ONE held expert and its other three
    held too: all 1,024 rows are held, four times the first rung, and
    every one is computed where the capacity path at its default
    factor drops most."""
    from chainermn_tpu.parallel.expert import buffer_rows

    rng = np.random.RandomState(12)
    x, router_w = _routed(LN * LK, rng)
    experts = _experts(rng, LG)
    out, _, chosen = _run(one, x, router_w, experts, LK)
    assert (np.asarray(chosen)[:, 0] == 0).all()
    assert (np.asarray(chosen) < LG).all()
    assert int(buffer_rows(np.int32(LN * LK), LN * LK, LG, LE)) == LN * LK
    ref = _dense(x, router_w, experts, LK)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)
    assert (np.abs(ref).sum(axis=1) > 0).all()

    dropped, _ = jax.jit(jax.shard_map(
        lambda xs, rw, ep: expert_parallel_moe(
            xs, rw, ep, lambda p, t: jax.nn.relu(t @ p["w1"]) @ p["w2"],
            axis_name=AX, top_k=LK),
        mesh=one, in_specs=(P(), P(), P()), out_specs=(P(), P())))(
            x, router_w, {k: jnp.concatenate(
                [experts[k], jnp.zeros((LE - LG,) + experts[k].shape[1:])])
                for k in ("w1", "w2")})
    assert (np.abs(np.asarray(dropped)).sum(axis=1) < 1e-6).sum() > LN // 2


@pytest.mark.parametrize("n_held", [100, 300])
def test_poisoned_rows_of_a_compact_rung_reach_nothing(one, n_held):
    """At a compact rung the buffer still ends in rows of choices not
    held here (156 of 256, 212 of 512), which the chip's grouped
    kernels leave undefined both ways: NaN there reaches neither the
    result nor a gradient."""
    rng = np.random.RandomState(13)
    x, router_w = _routed(n_held, rng)
    experts = _experts(rng, LG)
    clean = _loss_and_grads(one, None, x, router_w, experts)
    assert np.abs(clean[0]).sum() > 0
    _same(_loss_and_grads(one, None, x, router_w, experts, _poisoned_fn),
          clean)
    _same(clean, _loss_and_grads(one, LN * LK, x, router_w, experts))


def test_ladder_under_checkpoint_inside_a_scan(one):
    """As the model runs it: the layer the last part of a rematerialised
    block inside a ``lax.scan`` over layers, differentiated under
    ``jit``.  The two layers' routers differ, so they take different
    rungs in one program."""
    rng = np.random.RandomState(14)
    x, rw_small = _routed(40, rng)
    rw_large = rw_small.copy()
    rw_large[0, [1, 2, 3]] = 7.0, 6.5, 6.2  # the 40 tokens: all four held
    rw_large[2, [0, 1]] = 9.0, 8.5          # and two of every other's
    routers = np.stack([rw_small, rw_large])
    experts = jax.tree.map(lambda *a: jnp.stack(a),
                           _experts(rng, LG), _experts(rng, LG))

    def stack(layer):
        def f(x, routers, experts):
            @jax.checkpoint
            def block(h, blk):
                out, aux = jax.shard_map(
                    layer, mesh=one, in_specs=(P(), P(), P()),
                    out_specs=(P(), P()))(h, *blk)
                return h + out, aux
            h, aux = jax.lax.scan(block, x, (routers, experts))
            return jnp.sum(h * h) + aux.sum()
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))(
            x, routers, experts)

    (l0, g0), (l1, g1) = stack(_part_at(LN * LK)), stack(_part_at(None))
    assert float(l1) == pytest.approx(float(l0), rel=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("poisoned", [False, True],
                         ids=["clean", "poisoned"])
def test_exchange_with_a_compact_buffer(one, four, poisoned):
    """Four members, 64 tokens each, 8 of 32 experts held by the group:
    the members agree on one rung (the fullest member's), rows travel
    by all-to-all from a compact buffer, and result and gradients are
    one device's with the full buffer."""
    from chainermn_tpu.parallel.expert import _buffer_rungs

    rng = np.random.RandomState(15)
    x = rng.randn(256, D).astype(np.float32)
    router_w = rng.randn(D, 32).astype(np.float32)
    # member 0's tokens lean on the held experts, the others' do not
    x[:64, 0], router_w[0, 8:16] = 3.0, 1.0
    experts = _experts(rng, 8)
    assert len(_buffer_rungs(64 * 4, 8, 32)) == 2

    def loss(mesh, shard, fn):
        def f(x, rw, ep):
            out, aux, _ = _run(mesh, x, rw, ep, 4, first=8, shard=shard,
                               expert_fn=fn)
            return jnp.sum(out * out) + aux
        return jax.value_and_grad(f, argnums=(0, 1, 2))(x, router_w, experts)

    l4, g4 = loss(four, True, _poisoned_fn if poisoned else _grouped_fn)
    l1, g1 = loss(one, False, _grouped_fn)
    assert float(l4) == pytest.approx(float(l1), rel=1e-5)
    for a, b in zip(jax.tree.leaves(g4), jax.tree.leaves(g1), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("held,branches", [(8, 0), (2, 2)],
                         ids=["every expert held", "a share held"])
def test_conditional_only_where_a_share_is_held(one, held, branches):
    """A member that holds every expert has the one rung: its lowered
    program has no conditional (and counts one rung a call site)."""
    from chainermn_tpu.utils.metrics import MetricsRegistry, set_registry

    rng = np.random.RandomState(16)
    x = rng.randn(256, D).astype(np.float32)
    router_w = rng.randn(D, 8).astype(np.float32)
    experts = _experts(rng, held)

    def f(x, rw, ep):
        out, aux, _ = jax.shard_map(
            lambda xs, rw, ep: expert_parallel_moe_dropless(
                xs, rw, ep, _grouped_fn, top_k=2, axis_name=AX),
            mesh=one, in_specs=(P(), P(), P()),
            out_specs=(P(), P(), P()))(x, rw, ep)
        return jnp.sum(out * out) + aux

    reg = MetricsRegistry(enabled=True)
    prev = set_registry(reg)
    try:
        text = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(
            x, router_w, experts).as_text()
    finally:
        set_registry(prev)
    assert ("case" in text or "conditional" in text) == bool(branches)
    # the ladder, once for the call site as it is traced
    assert reg.counter("moe/buffer_rungs").value == (branches or 1)
