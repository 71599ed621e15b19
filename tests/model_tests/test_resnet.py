"""ResNet: shape/finiteness plus the cross-replica-BN equivalence — a
data-sharded forward with ``axis_name="data"`` must match one device
seeing the whole batch (the MultiNodeBatchNormalization contract)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from chainermn_tpu.models import ResNetConfig, init_resnet, resnet_apply
from chainermn_tpu.parallel import MeshConfig

CFG = ResNetConfig(depth=50, num_classes=10, width=8, dtype="float32")
B, HW = 16, 32


def images(seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randn(B, HW, HW, 3), jnp.float32)


@pytest.fixture(scope="module")
def seeded():
    """``(params, state)`` at their seeds, made once and as one compiled
    program: op by op the 50-layer net's init and forward took 38 s of
    a 1,319 s tier-1 run (PR 45)."""
    return jax.jit(lambda: init_resnet(jax.random.PRNGKey(0), CFG))()


def test_forward_shape_and_state(seeded):
    params, state = seeded
    logits, new_state = jax.jit(
        lambda p, s, x: resnet_apply(CFG, p, s, x))(params, state, images())
    assert logits.shape == (B, 10)
    assert np.isfinite(np.asarray(logits)).all()
    # every BN layer's running stats were updated exactly once
    n = jax.tree.leaves(jax.tree.map(lambda s: s.n, new_state,
                                     is_leaf=lambda x: hasattr(x, "n")))
    assert all(int(x) == 1 for x in n)


def test_eval_mode_uses_running_stats(seeded):
    params, state = seeded
    logits, new_state = jax.jit(lambda p, s, x: resnet_apply(
        CFG, p, s, x, train=False))(params, state, images())
    assert jax.tree.all(jax.tree.map(
        lambda a, b: (np.asarray(a) == np.asarray(b)).all(),
        state, new_state))
    assert np.isfinite(np.asarray(logits)).all()


def test_sync_bn_matches_single_device(seeded):
    params, state = seeded
    x = images(1)

    ref, ref_state = jax.jit(lambda p, s, xx: resnet_apply(
        CFG, p, s, xx, train=True))(params, state, x)

    mc = MeshConfig(data=8)
    sharded = jax.jit(
        jax.shard_map(
            lambda p, s, xx: resnet_apply(
                CFG, p, s, xx, train=True, axis_name="data"),
            mesh=mc.mesh,
            in_specs=(P(), P(), P("data")),
            out_specs=(P("data"), P()),
        ))
    out, out_state = sharded(params, state, x)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
        ref_state, out_state)
