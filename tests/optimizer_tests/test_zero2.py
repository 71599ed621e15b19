"""ZeRO-2 (``zero2_optimizer``): bucketed reduce-scatter gradient
exchange + 1/N optimizer-state shards.

The load-bearing claims, in order of strength:

- the member-major bucket layout makes every per-element cross-member
  sum happen in the SAME order as ``zero1_optimizer``'s per-leaf
  scatter, so ZeRO-2 training is bitwise-identical to ZeRO-1 in the
  parameters (the state may differ by an ULP where XLA picks a
  different reduce algorithm for the differently-shaped buffer);
- against the pure-DP oracle (``cross_replica_mean`` + inner) the
  match is within the established zero1 tolerance, with params exactly
  replicated across ranks;
- a single-device mesh and leaves smaller than the world (a scalar and
  a 7-element bias on 8 devices) are exact degenerate cases;
- bucket size is a pure performance knob: any ``bucket_bytes`` yields
  the same numbers.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from chainermn_tpu import create_communicator, create_multi_node_optimizer
from chainermn_tpu.training.optimizers import (
    Zero2Transformation,
    _zero2_buckets,
    cross_replica_mean,
    zero1_init,
    zero1_optimizer,
    zero2_optimizer,
)


AX = "world"


@pytest.fixture()
def comm():
    return create_communicator("tpu_xla", axis_name=AX)


def _params():
    # odd sizes on purpose: 5*3=15 and 7 are not multiples of 8 devices,
    # and the scalar leaf is SMALLER than the world (7 pad lanes)
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
    def body(params, grads):
        grads = jax.tree.map(lambda g: g[0], grads)
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
        "adamw": lambda: optax.adamw(1e-2, weight_decay=1e-2),
    }[inner]
    params, grads = _params(), _grads_per_rank(n)

    ref = _run_steps(
        comm, optax.chain(cross_replica_mean(AX), make()), params, grads)
    got = _run_steps(comm, zero2_optimizer(make(), AX), params, grads)

    for k in params:
        r, g = np.asarray(ref[k]), np.asarray(got[k])
        for i in range(1, n):
            np.testing.assert_array_equal(g[i], g[0])
        np.testing.assert_allclose(g[0], r[0], rtol=2e-5, atol=2e-6)


def _train(comm, make_opt, sharded, n_steps=4):
    """An 8-rank DP least-squares regression; returns (params, state)
    after ``n_steps``.  ``sharded`` runs the world-stacked ZeRO carry,
    else the replicated-state oracle."""
    n = comm.size
    params = {"w": jnp.zeros((4, 3)), "b": jnp.zeros((7,)),
              "s": jnp.zeros(())}
    r = np.random.RandomState(0)
    w_true = jnp.asarray(r.randn(4, 3), jnp.float32)
    x = jnp.asarray(r.randn(n, 16, 4), jnp.float32)
    y = jnp.einsum("rbi,ij->rbj", x, w_true)
    opt = make_opt()
    if sharded:
        state = zero1_init(opt, params, comm.mesh, AX)
        st_spec = P(AX)
    else:
        state = opt.init(params)
        st_spec = P()

    def step(params, state, x, y):
        x, y = x[0], y[0]
        if sharded:
            state = jax.tree.map(lambda s: s[0], state)

        def loss_fn(p):
            pred = x @ p["w"] + p["b"][:3] + p["s"]
            return jnp.mean((pred - y) ** 2)

        grads = jax.grad(loss_fn)(params)
        updates, state = opt.update(grads, state, params)
        if sharded:
            state = jax.tree.map(lambda s: s[None], state)
        return optax.apply_updates(params, updates), state

    f = jax.jit(jax.shard_map(
        step, mesh=comm.mesh,
        in_specs=(P(), st_spec, P(AX), P(AX)), out_specs=(P(), st_spec)))
    for _ in range(n_steps):
        params, state = f(params, state, x, y)
    return params, state


def test_bitwise_matches_zero1(comm):
    """The central ZeRO-2 claim: the member-major bucket exchange
    computes the SAME per-element sums in the SAME order as the ZeRO-1
    per-leaf scatter, so training trajectories agree bitwise in the
    parameters."""
    z1_p, z1_s = _train(comm, lambda: zero1_optimizer(
        optax.adam(1e-2), AX), True)
    z2_p, z2_s = _train(comm, lambda: zero2_optimizer(
        optax.adam(1e-2), AX), True)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), z1_p, z2_p)
    # the moments agree to the last ulp or one past it (XLA may lower
    # the differently-shaped scatter with a different reduce schedule)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-6, atol=0),
        z1_s, z2_s)


def test_matches_dp_oracle(comm):
    """ZeRO-2 vs the replicated-state pure-DP oracle, trained through
    jitted steps (un-gated: no scan carry, no replicated-loss grads)."""
    dp_p, _ = _train(comm, lambda: optax.chain(
        cross_replica_mean(AX), optax.adam(1e-2)), False)
    z2_p, _ = _train(comm, lambda: zero2_optimizer(
        optax.adam(1e-2), AX), True)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6),
        dp_p, z2_p)


def test_bucket_bytes_is_pure_perf_knob(comm):
    """Any bucket split computes identical numbers: 64-byte buckets
    (every leaf its own bucket) vs the single default bucket."""
    ref_p, ref_s = _train(comm, lambda: zero2_optimizer(
        optax.adam(1e-2), AX), True)
    tiny_p, tiny_s = _train(comm, lambda: zero2_optimizer(
        optax.adam(1e-2), AX, bucket_bytes=64), True)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), ref_p, tiny_p)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-6, atol=0),
        ref_s, tiny_s)


def test_state_is_sharded(comm):
    n = comm.size
    params = _params()

    def init_shapes(params):
        state = zero2_optimizer(optax.adam(1e-2), AX).init(params)
        mu = state[0].mu
        return jax.tree.map(lambda m: jnp.zeros(m.shape + (0,)), mu)

    f = jax.jit(jax.shard_map(
        init_shapes, mesh=comm.mesh, in_specs=P(), out_specs=P()))
    shapes = jax.tree.map(lambda z: z.shape[:-1], f(params))
    assert shapes["w"] == (-(-15 // n),)
    assert shapes["b"] == (-(-7 // n),)
    assert shapes["s"] == (-(-1 // n),)


def test_single_device_mesh():
    """World 1: the scatter/gather degenerate to identity.  ZeRO-2
    matches ZeRO-1 bitwise (identical exchange semantics) and the bare
    inner optimizer to the last ulp (XLA fuses the flat-shard program
    differently from the tree-shaped one, so exact bit equality with
    the inner is not a contract)."""
    mesh = Mesh(np.asarray(jax.devices()[:1]), (AX,))
    params = _params()
    grads = jax.tree.map(lambda p: jnp.ones_like(p) * 0.25, params)

    def run(opt):
        def body(params):
            state = opt.init(params)
            for _ in range(3):
                updates, state = opt.update(grads, state, params)
                params = optax.apply_updates(params, updates)
            return params

        return jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P(),), out_specs=P()))(params)

    ref = run(optax.adam(1e-2))
    z1 = run(zero1_optimizer(optax.adam(1e-2), AX))
    z2 = run(zero2_optimizer(optax.adam(1e-2), AX))
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), z1, z2)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=3e-7, atol=0), ref, z2)


# --------------------------------------------------------------------- #
# bucket construction + factory wiring
# --------------------------------------------------------------------- #


def test_bucket_construction():
    leaves = [jnp.zeros((64,), jnp.float32),
              jnp.zeros((64,), jnp.float32),
              jnp.zeros((8,), jnp.bfloat16),
              jnp.zeros((64,), jnp.float32)]
    # dtype groups split buckets; fp32 leaves pack in first-occurrence
    # order until the PER-MEMBER shard byte budget runs out: each fp32
    # leaf is ceil(64/8)*4 = 32 shard bytes, so two fit per 64-byte
    # bucket
    buckets = _zero2_buckets(leaves, 8, bucket_bytes=64)
    assert [(str(dt), idxs) for dt, idxs in buckets] == [
        ("float32", [0, 1]), ("float32", [3]), ("bfloat16", [2])]
    one = _zero2_buckets(leaves, 8, bucket_bytes=None)
    assert [(str(dt), idxs) for dt, idxs in one] == [
        ("float32", [0, 1, 3]), ("bfloat16", [2])]


def test_factory_mutual_exclusion(comm):
    with pytest.raises(ValueError, match="mutually exclusive"):
        create_multi_node_optimizer(
            optax.adam(1e-2), comm, zero1=True, zero2=True)


def test_factory_returns_zero2_transformation(comm):
    opt = create_multi_node_optimizer(optax.adam(1e-2), comm, zero2=True)
    assert isinstance(opt, Zero2Transformation)
    assert not opt.overlap


def test_factory_plan_is_ignored_under_zero2(comm):
    class FakePlan:
        strategy = "fused/flat/native"
        program = None

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        create_multi_node_optimizer(
            optax.adam(1e-2), comm, zero2=True, plan=FakePlan())
    assert any("zero1/zero2" in str(x.message) for x in w)


def test_updater_detects_zero2(comm):
    import chainermn_tpu as cmn
    from chainermn_tpu.models import init_mlp, mlp_apply, \
        softmax_cross_entropy

    rng = np.random.RandomState(0)
    data = [(rng.randn(6).astype(np.float32), np.int32(i % 3))
            for i in range(64)]
    it = cmn.SerialIterator(data, 16, shuffle=True, seed=7)
    params = init_mlp(jax.random.PRNGKey(0), [6, 12, 3])
    opt = create_multi_node_optimizer(optax.adam(5e-2), comm, zero2=True)
    upd = cmn.StandardUpdater(it, opt, lambda p, x, y:
                              softmax_cross_entropy(mlp_apply(p, x), y),
                              params, comm)
    assert upd.sharding == "zero2"
    assert upd.zero1          # the world-stacked carry convention
    upd.update()
    assert upd.status()["sharding"] == "zero2"
    n = comm.size
    assert any(m.ndim >= 1 and m.shape[0] == n
               for m in jax.tree.leaves(upd.opt_state))
