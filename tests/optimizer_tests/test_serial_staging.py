"""The serial feed's staging ring under ``StandardUpdater.update()``:
batches go to ``device_put`` uncopied and their host buffers are
recycled, so training must read every batch to the end before its
buffer is rewritten — on the CPU backend, where a sharded
``device_put`` really aliases the host array."""

import jax
import numpy as np
import optax
import pytest

import chainermn_tpu as cmn
from chainermn_tpu.models import init_mlp, mlp_apply, softmax_cross_entropy
from chainermn_tpu.training import default_converter


@pytest.fixture()
def comm():
    return cmn.create_communicator("tpu_xla")


def _dataset(n, dim=6, classes=3, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(dim).astype(np.float32),
             np.asarray(i % classes, np.int32)) for i in range(n)]


def _loss_fn(p, x, y):
    return softmax_cross_entropy(mlp_apply(p, x), y)


def _make(comm, n=104, batch_size=16, **kw):
    # 104 / 16: six full batches and a ragged one of 8 every epoch
    it = cmn.SerialIterator(_dataset(n), batch_size, shuffle=True, seed=7)
    opt = cmn.create_multi_node_optimizer(optax.sgd(0.05), comm)
    return cmn.StandardUpdater(
        it, opt, _loss_fn, init_mlp(jax.random.PRNGKey(0), [6, 12, 3]),
        comm, **kw)


def test_cpu_device_put_aliases_a_small_host_batch(comm):
    """What makes the cases below a test of anything: the device array
    of a batch this size IS the host buffer here."""
    upd = _make(comm)
    host = np.ones((16, 6), np.float32)
    dev = jax.block_until_ready(jax.device_put(host, upd._batch_sharding))
    host[:] = 7
    assert float(np.asarray(dev).max()) == 7.0


@pytest.mark.parametrize("steps_per_execution", [1, 2])
@pytest.mark.parametrize("max_inflight", [1, 2])
def test_staged_feed_trains_bitwise_like_a_fresh_stack(
        comm, max_inflight, steps_per_execution):
    """3 x n + 1 updates with nobody reading a loss in between (the
    host runs as far ahead as ``max_inflight`` lets it), across epoch
    ends with their ragged batch: the recycled ring gives the same
    parameters, bit for bit, as a converter that stacks fresh."""
    kw = dict(max_inflight=max_inflight,
              steps_per_execution=steps_per_execution)
    staged = _make(comm, **kw)
    fresh = _make(comm, converter=lambda b: default_converter(b), **kw)
    assert isinstance(staged.converter, cmn.StagingConverter)
    assert not isinstance(fresh.converter, cmn.StagingConverter)
    n = staged.staging_buffers_needed
    assert n == (max_inflight + 1 if steps_per_execution == 1
                 else steps_per_execution + 1)
    assert staged.converter._n_buffers == n
    for _ in range(3 * n + 1):
        staged.update()
        fresh.update()
    assert staged.iteration == fresh.iteration
    assert staged.epoch == fresh.epoch >= 1     # a ragged tail went by
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), staged.params, fresh.params)


def test_explicit_converter_is_kept_and_prefetch_keeps_its_own(comm):
    conv = lambda b: default_converter(b)    # noqa: E731
    assert _make(comm, converter=conv).converter is conv
    pre = _make(comm, prefetch=2)
    assert pre.converter is default_converter
    assert not pre._feed_retires
    pre.iterator.close()


@pytest.mark.parametrize("max_inflight, n_buffers, refused", [
    (1, 2, False), (2, 2, True), (2, 3, False), (3, 3, True)])
def test_users_ring_is_held_to_max_inflight_plus_one(
        comm, max_inflight, n_buffers, refused):
    conv = cmn.StagingConverter(n_buffers=n_buffers)
    if refused:
        with pytest.raises(
                ValueError,
                match=rf"needs >= {max_inflight + 1} buffers "
                      rf"\(max_inflight \+ 1"):
            _make(comm, converter=conv, max_inflight=max_inflight)
    else:
        upd = _make(comm, converter=conv, max_inflight=max_inflight)
        assert upd.converter is conv and upd._feed_retires
        upd.update()
