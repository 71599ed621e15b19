"""ZeRO-1 sharded-optimizer-state tests (beyond-reference: the reference
replicated optimizer state on every rank; `zero1_optimizer` shards it over
the data axis via psum_scatter/all_gather — see
chainermn_tpu/training/optimizers.py).

Checks: (a) numerical equivalence with the replicated pmean+inner path for
elementwise optimizers, (b) odd leaf sizes exercise the padding lanes,
(c) optimizer state is genuinely 1/N-sized per replica, (d) params stay
replicated across steps, (e) bf16 wire mode, (f) double-buffering
composition through create_multi_node_optimizer."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from chainermn_tpu import create_communicator, create_multi_node_optimizer
from chainermn_tpu.training.optimizers import (
    cross_replica_mean,
    zero1_init,
    zero1_optimizer,
)


AX = "world"


@pytest.fixture()
def comm():
    return create_communicator("tpu_xla", axis_name=AX)


def _params():
    # odd sizes on purpose: 5*3=15 and 7 are not multiples of 8 devices
    r = np.random.RandomState(0)
    return {
        "w": jnp.asarray(r.randn(5, 3), jnp.float32),
        "b": jnp.asarray(r.randn(7), jnp.float32),
        "s": jnp.asarray(r.randn(), jnp.float32),
    }


def _grads_per_rank(n):
    r = np.random.RandomState(1)
    return {
        "w": jnp.asarray(r.randn(n, 5, 3), jnp.float32),
        "b": jnp.asarray(r.randn(n, 7), jnp.float32),
        "s": jnp.asarray(r.randn(n), jnp.float32),
    }


def _run_steps(comm, opt, params, grads_per_rank, n_steps=3):
    """Run ``n_steps`` updates inside shard_map (per-rank grads vary);
    return final params, world-stacked (so replication is observable)."""

    def body(params, grads):
        grads = jax.tree.map(lambda g: g[0], grads)  # drop shard dim
        state = opt.init(params)

        def one(carry, _):
            params, state = carry
            updates, state = opt.update(grads, state, params)
            return (optax.apply_updates(params, updates), state), None

        (params, _), _ = jax.lax.scan(one, (params, state), None, n_steps)
        return jax.tree.map(lambda p: p[None], params)

    f = jax.jit(jax.shard_map(
        body, mesh=comm.mesh, in_specs=(P(), P(AX)), out_specs=P(AX)))
    return f(params, grads_per_rank)


@pytest.mark.parametrize("inner", ["adam", "sgd_momentum", "adamw"])
def test_matches_replicated_path(comm, inner):
    n = comm.size
    make = {
        "adam": lambda: optax.adam(1e-2),
        "sgd_momentum": lambda: optax.sgd(1e-2, momentum=0.9),
        # adamw exercises the params-dependent (weight decay) path
        "adamw": lambda: optax.adamw(1e-2, weight_decay=1e-2),
    }[inner]
    params, grads = _params(), _grads_per_rank(n)

    ref = _run_steps(
        comm, optax.chain(cross_replica_mean(AX), make()), params, grads)
    got = _run_steps(comm, zero1_optimizer(make(), AX), params, grads)

    for k in params:
        r, g = np.asarray(ref[k]), np.asarray(got[k])
        # params must remain replicated across ranks
        for i in range(1, n):
            np.testing.assert_array_equal(g[i], g[0])
        np.testing.assert_allclose(g[0], r[0], rtol=2e-5, atol=2e-6)


def test_state_is_sharded(comm):
    n = comm.size
    params = _params()

    def init_shapes(params):
        state = zero1_optimizer(optax.adam(1e-2), AX).init(params)
        # adam state: (ScaleByAdamState(count, mu, nu), EmptyState)
        mu = state[0].mu
        return jax.tree.map(lambda m: jnp.zeros(m.shape + (0,)), mu)

    f = jax.jit(jax.shard_map(
        init_shapes, mesh=comm.mesh, in_specs=P(), out_specs=P()))
    shapes = jax.tree.map(lambda z: z.shape[:-1], f(params))
    # each leaf's moment shard is ceil(size/n) elements, flat
    assert shapes["w"] == (-(-15 // n),)
    assert shapes["b"] == (-(-7 // n),)
    assert shapes["s"] == (-(-1 // n),)


def test_bf16_wire(comm):
    n = comm.size
    params, grads = _params(), _grads_per_rank(n)
    ref = _run_steps(
        comm, optax.chain(cross_replica_mean(AX), optax.adam(1e-2)),
        params, grads)
    got = _run_steps(
        comm, zero1_optimizer(optax.adam(1e-2), AX,
                              wire_dtype=jnp.bfloat16),
        params, grads)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(got[k])[0], np.asarray(ref[k])[0],
            rtol=2e-2, atol=2e-2)


def test_persistent_state_across_jit_boundaries(comm):
    """The real-training pattern: state initialised once with zero1_init,
    carried world-stacked through separate jitted step calls; a DP
    least-squares regression must converge and recover the true weights."""
    n = comm.size
    r = np.random.RandomState(0)
    w_true = r.randn(4, 3).astype(np.float32)
    x = r.randn(n, 16, 4).astype(np.float32)
    y = np.einsum("rbi,ij->rbj", x, w_true)

    params = {"w": jnp.zeros((4, 3))}
    opt = create_multi_node_optimizer(
        optax.adam(5e-2), comm, zero1=True)
    state = zero1_init(opt, params, comm.mesh, AX)
    # adam mu shard: ceil(12/n) per member, world-stacked with member axis
    assert state[0].mu["w"].shape == (n, -(-12 // n))
    assert state[0].count.shape == (n,)

    def step(params, state, x, y):
        x, y, state = x[0], y[0], jax.tree.map(lambda s: s[0], state)

        def loss_fn(p):
            return jnp.mean((x @ p["w"] - y) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, state = opt.update(grads, state, params)
        return (optax.apply_updates(params, updates),
                jax.tree.map(lambda s: s[None], state),
                jax.lax.pmean(loss, AX))

    f = jax.jit(jax.shard_map(
        step, mesh=comm.mesh,
        in_specs=(P(), P(AX), P(AX), P(AX)),
        out_specs=(P(), P(AX), P())))
    x, y = jnp.asarray(x), jnp.asarray(y)
    for _ in range(300):
        params, state, loss = f(params, state, x, y)
    assert float(loss) < 1e-3
    np.testing.assert_allclose(params["w"], w_true, atol=0.05)


def test_create_multi_node_optimizer_zero1_double_buffering(comm):
    n = comm.size
    params, grads = _params(), _grads_per_rank(n)
    ref = _run_steps(
        comm,
        create_multi_node_optimizer(
            optax.sgd(1e-1), comm, double_buffering=True),
        params, grads)
    got = _run_steps(
        comm,
        create_multi_node_optimizer(
            optax.sgd(1e-1), comm, double_buffering=True, zero1=True),
        params, grads)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(got[k])[0], np.asarray(ref[k])[0],
            rtol=2e-5, atol=2e-6)
