"""The dropless expert dispatch (``parallel.expert``): against a dense
application of the chosen experts, under the worst imbalance, with a
share of the experts held, and across an expert axis."""

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
