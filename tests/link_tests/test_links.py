"""Link-layer tests — analogue of the reference's ``link_tests`` battery
(sync BN numerical parity vs single-device BN over the whole batch;
MultiNodeChainList forward/backward vs a local sequential run).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from chainermn_tpu.communicators._mesh_utils import make_world_mesh
from chainermn_tpu.links import (
    MultiNodeChainList,
    init_batch_norm,
    multi_node_batch_normalization,
)


AX = "world"


@pytest.fixture(scope="module")
def mesh():
    return make_world_mesh(axis_name=AX)


def smap(mesh, fn, in_specs, out_specs):
    return jax.jit(
        jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs))


class TestMultiNodeBatchNorm:
    def _local_bn(self, params, x, eps=2e-5):
        mean = x.mean(axis=tuple(range(x.ndim - 1)))
        var = x.var(axis=tuple(range(x.ndim - 1)))
        inv = params["gamma"] / np.sqrt(var + eps)
        return (x - mean) * inv + params["beta"]

    @pytest.mark.parametrize("shape", [(32, 6), (16, 4, 4, 3)])
    def test_matches_global_batch(self, mesh, shape):
        """BN over an 8-way-sharded batch == BN over the whole batch."""
        n = mesh.devices.size
        rng = np.random.RandomState(0)
        x = rng.randn(*shape).astype(np.float32) * 3 + 1
        params, state = init_batch_norm(shape[-1])

        def fn(xs):
            y, new_state = multi_node_batch_normalization(
                params, state, xs, axis_name=AX)
            return y, new_state

        y, new_state = smap(
            mesh, fn, in_specs=P(AX), out_specs=(P(AX), P()))(x)
        np.testing.assert_allclose(
            np.asarray(y), self._local_bn(params, x), rtol=2e-4, atol=2e-5)
        # running stats moved toward the global batch stats
        exp_mean = 0.1 * x.mean(axis=tuple(range(x.ndim - 1)))
        np.testing.assert_allclose(np.asarray(new_state.mean), exp_mean,
                                   rtol=1e-4, atol=1e-5)
        assert int(new_state.n) == 1
        assert x.shape[0] % n == 0

    def test_inference_uses_running_stats_no_collective(self, mesh):
        params, state = init_batch_norm(5)
        state = state._replace(mean=jnp.full((5,), 2.0),
                               var=jnp.full((5,), 4.0))
        x = np.random.RandomState(1).randn(8, 5).astype(np.float32)
        # train=False path never touches axis_name → runs outside shard_map
        y, new_state = multi_node_batch_normalization(
            params, state, jnp.asarray(x), axis_name=None, train=False)
        np.testing.assert_allclose(
            np.asarray(y), (x - 2.0) / np.sqrt(4.0 + 2e-5),
            rtol=1e-4, atol=1e-5)
        assert new_state is state

    def test_gradients_flow(self, mesh):
        params, state = init_batch_norm(4)
        x = np.random.RandomState(2).randn(16, 4).astype(np.float32)

        def loss(p, xs):
            y, _ = multi_node_batch_normalization(p, state, xs, axis_name=AX)
            return jax.lax.pmean(jnp.sum(y**2) , AX)

        g = smap(mesh, jax.grad(loss), in_specs=(P(), P(AX)),
                 out_specs=P())(params, x)
        assert np.isfinite(np.asarray(g["gamma"])).all()
        assert np.isfinite(np.asarray(g["beta"])).all()


def _dense_init(shape, seed):
    def init(key):
        del key
        rng = np.random.RandomState(seed)
        return {"w": jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.1),
                "b": jnp.zeros((shape[1],), jnp.float32)}
    return init


def _dense_apply(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


class TestMultiNodeChainList:
    def _build(self, n_stage=3):
        mn = MultiNodeChainList(axis_name=AX)
        dims = [6, 5, 4, 3][: n_stage + 1]
        for i in range(n_stage):
            mn.add_link(
                _dense_init((dims[i], dims[i + 1]), seed=i), _dense_apply,
                owner=i,
                rank_in=None if i == 0 else i - 1,
                rank_out=None if i == n_stage - 1 else i + 1)
        return mn

    def test_forward_matches_sequential(self, mesh):
        mn = self._build()
        params = mn.init(jax.random.key(0))
        x = np.random.RandomState(3).randn(4, 6).astype(np.float32)

        y = smap(mesh, lambda xs: mn.apply(params, xs),
                 in_specs=P(), out_specs=P())(x)

        ref = jnp.asarray(x)
        for p in params:
            ref = _dense_apply(p, ref)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_backward_matches_sequential(self, mesh):
        mn = self._build()
        params = mn.init(jax.random.key(0))
        x = np.random.RandomState(4).randn(4, 6).astype(np.float32)

        def dist_loss(ps, xs):
            return jnp.sum(mn.apply(ps, xs) ** 2)

        def local_loss(ps, xs):
            h = xs
            for p in ps:
                h = _dense_apply(p, h)
            return jnp.sum(h**2)

        g = smap(mesh,
                 lambda ps, xs: mn.reduce_grads(jax.grad(dist_loss)(ps, xs)),
                 in_specs=(P(), P()), out_specs=P())(params, x)
        g_ref = jax.grad(local_loss)(params, jnp.asarray(x))
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_multi_input_component(self, mesh):
        """Branch/join DAG: rank 0 fans out to ranks 1 and 2; rank 3 joins
        with rank_in=[1, 2] — the reference's list-valued rank_in."""
        mn = MultiNodeChainList(axis_name=AX)
        mn.add_link(_dense_init((4, 4), 0), _dense_apply,
                    owner=0, rank_out=[1, 2])
        mn.add_link(_dense_init((4, 4), 1), _dense_apply,
                    owner=1, rank_in=0, rank_out=3)
        mn.add_link(_dense_init((4, 4), 2), _dense_apply,
                    owner=2, rank_in=0, rank_out=3)
        mn.add_link(
            _dense_init((4, 4), 3),
            lambda p, a, b: _dense_apply(p, a + b),
            owner=3, rank_in=[1, 2])
        params = mn.init(jax.random.key(0))
        x = np.random.RandomState(5).randn(2, 4).astype(np.float32)

        y = smap(mesh, lambda xs: mn.apply(params, xs),
                 in_specs=P(), out_specs=P())(x)

        h0 = _dense_apply(params[0], jnp.asarray(x))
        h1 = _dense_apply(params[1], h0)
        h2 = _dense_apply(params[2], h0)
        ref = _dense_apply(params[3], h1 + h2)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_unconsumed_message_raises(self, mesh):
        mn = MultiNodeChainList(axis_name=AX)
        mn.add_link(_dense_init((4, 4), 0), _dense_apply,
                    owner=0, rank_out=1)
        mn.add_link(_dense_init((4, 4), 1), _dense_apply,
                    owner=1, rank_in=None)  # never consumes 0→1
        params = mn.init(jax.random.key(0))
        with pytest.raises(ValueError, match="unconsumed"):
            smap(mesh, lambda xs: mn.apply(params, xs),
                 in_specs=P(), out_specs=P())(
                     np.zeros((2, 4), np.float32))

    def test_missing_message_raises(self, mesh):
        mn = MultiNodeChainList(axis_name=AX)
        mn.add_link(_dense_init((4, 4), 0), _dense_apply,
                    owner=0, rank_in=7)
        params = mn.init(jax.random.key(0))
        with pytest.raises(ValueError, match="no pending message"):
            smap(mesh, lambda xs: mn.apply(params, xs),
                 in_specs=P(), out_specs=P())(
                     np.zeros((2, 4), np.float32))
