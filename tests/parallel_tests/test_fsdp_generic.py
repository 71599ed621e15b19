"""Generic FSDP utilities (``parallel.fsdp``): dim selection, at-rest
specs, just-in-time gather — driven end-to-end on a hand-rolled MLP the
way a user model would, and checked against the replicated oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from chainermn_tpu.parallel import (
    MeshConfig,
    fsdp_dims,
    fsdp_gather,
    fsdp_specs,
)
from chainermn_tpu.training import shard_opt_state


def test_fsdp_dims_selection():
    params = {
        "w1": jnp.zeros((16, 64)),      # -> dim 1 (largest divisible)
        "w2": jnp.zeros((64, 16)),      # -> dim 0
        "b": jnp.zeros((7,)),           # 7 % 8 != 0 -> None
        "tiny": jnp.zeros((8,)),        # 8 == axis_size < min_size*8 -> None
        "scalar": jnp.zeros(()),        # -> None
    }
    dims = fsdp_dims(params, 8)
    assert dims == {"w1": 1, "w2": 0, "b": None, "tiny": None,
                    "scalar": None}


def test_fsdp_dims_skips_taken_dims():
    params = {"w": jnp.zeros((64, 64))}
    dims = fsdp_dims(params, 8, specs={"w": P("model", None)})
    assert dims == {"w": 1}
    with pytest.raises(ValueError, match="already sharded"):
        fsdp_specs(params, {"w": 0}, base_specs={"w": P("model", None)})


def test_fsdp_dims_skips_leaves_already_on_axis():
    # a leaf whose base spec already uses the FSDP axis (on any dim)
    # cannot take an FSDP dim — the axis may appear only once in a
    # PartitionSpec.  fsdp_dims(axis=...) skips it up front; without
    # axis=, fsdp_specs is the backstop that refuses the duplicate.
    params = {"w": jnp.zeros((64, 64)), "v": jnp.zeros((64, 64))}
    specs = {"w": P("data", None), "v": P("model", None)}
    dims = fsdp_dims(params, 8, specs=specs, axis="data")
    assert dims == {"w": None, "v": 1}
    out = fsdp_specs(params, dims, base_specs=specs)
    assert out == {"w": P("data", None), "v": P("model", "data")}
    with pytest.raises(ValueError, match="already appears"):
        fsdp_specs(params, {"w": 1, "v": None}, base_specs=specs)


def _mlp_init():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    return {
        "w1": jax.random.normal(k1, (16, 64), jnp.float32) * 0.25,
        "b1": jnp.zeros((64,)),
        "w2": jax.random.normal(k2, (64, 4), jnp.float32) * 0.125,
    }


def _train(use_fsdp, wire_dtype=None, steps=4):
    mc = MeshConfig(data=8)
    mesh = mc.mesh
    params = _mlp_init()
    dims = fsdp_dims(params, 8) if use_fsdp else jax.tree.map(
        lambda _: None, params)
    specs = fsdp_specs(params, dims)
    params = jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        params, specs)
    opt = optax.adam(1e-2)
    opt_state = shard_opt_state(opt, params)

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(32, 16), jnp.float32)
    y = jnp.asarray(rng.randn(32, 4), jnp.float32)

    def loss_fn(p, xb, yb):
        full = fsdp_gather(p, dims, "data", wire_dtype=wire_dtype)
        h = jax.nn.relu(xb @ full["w1"] + full["b1"])
        return jnp.mean((h @ full["w2"] - yb) ** 2)

    # the make_train_step pattern: only the grad needs manual SPMD (the
    # gather wants a bound axis name); the elementwise optimiser update
    # runs under plain jit where XLA propagates the grads' shardings
    grad_fn = jax.shard_map(
        lambda p, xb, yb: jax.value_and_grad(
            lambda q: jax.lax.pmean(loss_fn(q, xb, yb), "data"))(p),
        mesh=mesh,
        in_specs=(specs, P("data"), P("data")),
        out_specs=(P(), specs),
    )

    @jax.jit
    def step(p, s, xb, yb):
        loss, g = grad_fn(p, xb, yb)
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s, loss
    losses = []
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, x, y)
        losses.append(float(loss))
    return losses, jax.tree.map(
        lambda a: np.asarray(jax.device_get(a)), params), params


def test_fsdp_mlp_matches_replicated():
    losses_d, final_d, _ = _train(False)
    losses_f, final_f, placed = _train(True)
    np.testing.assert_allclose(losses_f, losses_d, rtol=1e-5, atol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            a, b, rtol=2e-5, atol=2e-5),
        final_f, final_d)


def test_fsdp_mlp_at_rest_and_moments_sharded():
    _, _, placed = _train(True, steps=1)
    # w1 (16, 64) shards dim 1; each device holds 64/8 columns
    assert placed["w1"].addressable_shards[0].data.shape == (16, 8)
    opt_state = shard_opt_state(optax.adam(1e-2), placed)
    assert opt_state[0].mu["w1"].addressable_shards[0].data.shape \
        == (16, 8)


def test_fsdp_mlp_bf16_wire_trains():
    losses, _, _ = _train(True, wire_dtype=jnp.bfloat16, steps=6)
    assert losses[-1] < losses[0]


def test_shard_opt_state_bare_array_params():
    """A bare jax.Array as the whole params 'tree': the state paths'
    EMPTY suffix must match it (regression: the suffix walk used to
    stop before the empty suffix and silently replicated the moments)."""
    mc = MeshConfig(data=8)
    p = jax.device_put(jnp.zeros((16, 64)),
                       NamedSharding(mc.mesh, P(None, "data")))
    state = shard_opt_state(optax.adam(1e-2), p)
    assert state[0].mu.addressable_shards[0].data.shape == (16, 8)
