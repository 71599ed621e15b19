"""Iterator tests — analogue of the reference's ``iterator_tests``."""

import numpy as np
import pytest

import chainermn_tpu as cmn
from chainermn_tpu import (SerialIterator, StagingConverter,
                           create_communicator,
                           create_multi_node_iterator,
                           create_synchronized_iterator)
from chainermn_tpu.iterators.prefetch import (apply_batch_policy,
                                              pull_batch, put_window)
from chainermn_tpu.training import default_converter
from chainermn_tpu.utils.metrics import MetricsRegistry, set_registry
from chainermn_tpu.utils.telemetry import TraceRecorder, set_recorder


@pytest.fixture()
def comm():
    return create_communicator("tpu_xla")


class TestSerialIterator:
    def test_epoch_bookkeeping(self):
        it = SerialIterator(list(range(10)), 4)
        b1 = next(it)
        assert len(b1) == 4 and not it.is_new_epoch
        next(it)
        b3 = next(it)
        assert len(b3) == 2 and it.is_new_epoch
        next(it)
        assert it.epoch == 1

    def test_no_repeat_stops(self):
        it = SerialIterator(list(range(6)), 4, repeat=False)
        batches = list(it)
        assert [len(b) for b in batches] == [4, 2]

    def test_shuffle_covers_everything(self):
        it = SerialIterator(list(range(20)), 5, shuffle=True, seed=0)
        seen = []
        for _ in range(4):
            seen += next(it)
        assert sorted(seen) == list(range(20))

    def test_epoch_detail(self):
        it = SerialIterator(list(range(8)), 4)
        assert it.epoch_detail == 0.0
        next(it)
        assert it.epoch_detail == 0.5

    def test_reset(self):
        it = SerialIterator(list(range(8)), 4)
        next(it); next(it); next(it)
        it.reset()
        assert it.epoch == 0 and it.epoch_detail == 0.0


class TestSerialIteratorArrayFastPath:
    """Numpy datasets gather batches with ONE fancy index per field and
    yield pre-stacked arrays the converter passes through untouched."""

    def test_ndarray_dataset_matches_list_path(self):
        rng = np.random.RandomState(0)
        X = rng.randn(20, 5).astype(np.float32)
        fast = SerialIterator(X, 6, shuffle=True, seed=1)
        slow = SerialIterator([X[i] for i in range(20)], 6,
                              shuffle=True, seed=1)
        for _ in range(5):       # crosses the epoch boundary
            bf, bs = next(fast), next(slow)
            assert isinstance(bf, np.ndarray)
            assert isinstance(bs, list)
            np.testing.assert_array_equal(bf, np.stack(bs))
        assert fast.epoch == slow.epoch
        assert fast.epoch_detail == slow.epoch_detail

    def test_tuple_of_field_arrays(self):
        rng = np.random.RandomState(0)
        X = rng.randn(20, 5).astype(np.float32)
        Y = np.arange(20, dtype=np.int32)
        it = SerialIterator((X, Y), 6, shuffle=True, seed=1)
        assert it.dataset_length == 20          # examples, not fields
        assert it.epoch_detail == 0.0
        bx, by = next(it)
        assert bx.shape == (6, 5) and by.shape == (6,)
        np.testing.assert_array_equal(X[by], bx)   # rows stay aligned
        assert it.epoch_detail == 6 / 20

    def test_list_of_arrays_is_not_columns(self):
        # a LIST of arrays holds examples (generic path), even when the
        # leading dims happen to agree — only tuples declare columns
        rows = [np.full(4, i, np.float32) for i in range(4)]
        it = SerialIterator(rows, 2)
        batch = next(it)
        assert isinstance(batch, list) and len(batch) == 2
        np.testing.assert_array_equal(batch[0], rows[0])

    def test_fast_path_state_dict_round_trip(self):
        rng = np.random.RandomState(0)
        X = rng.randn(20, 5).astype(np.float32)
        a = SerialIterator((X,), 6, shuffle=True, seed=1)
        next(a)
        st = a.state_dict()
        b = SerialIterator((X,), 6, shuffle=True, seed=9)
        b.load_state_dict(st)
        np.testing.assert_array_equal(next(a)[0], next(b)[0])


class TestConverters:
    def test_default_converter_passthrough(self):
        X = np.zeros((4, 3), np.float32)
        assert default_converter(X)[0] is X
        out = default_converter((X, np.arange(4)))
        assert out[0] is X

    def test_default_converter_tuple_of_example_tuples(self):
        # a TUPLE batch of example tuples is examples, not columns —
        # only all-ndarray tuples are pre-stacked fields
        batch = tuple((np.full(3, i, np.float32), np.int32(i))
                      for i in range(4))
        x, y = default_converter(batch)
        assert x.shape == (4, 3) and y.shape == (4,)
        np.testing.assert_array_equal(y, np.arange(4))
        for got, want in zip(StagingConverter()(batch),
                             default_converter(batch)):
            np.testing.assert_array_equal(got, want)

    def test_default_converter_stacks_examples(self):
        batch = [(np.full(3, i, np.float32), np.int32(i))
                 for i in range(4)]
        x, y = default_converter(batch)
        assert x.shape == (4, 3) and y.shape == (4,)
        np.testing.assert_array_equal(y, np.arange(4))
        with pytest.raises(ValueError):
            default_converter([])
        with pytest.raises(ValueError):
            default_converter(())

    def test_staging_converter_matches_default(self):
        batch = [(np.full(3, i, np.float32), np.int32(i))
                 for i in range(4)]
        sc = StagingConverter(n_buffers=2)
        for got, want in zip(sc(batch), default_converter(batch)):
            np.testing.assert_array_equal(got, want)
            assert got.dtype == want.dtype

    def test_staging_converter_reuses_buffers(self):
        batch = [np.full(3, i, np.float32) for i in range(4)]
        sc = StagingConverter(n_buffers=2)
        a1, a2, a3 = sc(batch)[0], sc(batch)[0], sc(batch)[0]
        assert a1 is not a2          # previous batch stays valid
        assert a1 is a3              # ring of 2 rotates back
        # shape change (ragged tail) allocates its own buffer
        tail = sc(batch[:3])[0]
        assert tail.shape == (3, 3)
        np.testing.assert_array_equal(sc(batch)[0], a2)

    def test_staging_converter_validates(self):
        with pytest.raises(ValueError):
            StagingConverter(n_buffers=1)
        with pytest.raises(ValueError):
            StagingConverter()([])


class TestMultiNodeIterator:
    def test_single_process_passthrough(self, comm):
        base = SerialIterator(list(range(8)), 4)
        it = create_multi_node_iterator(base, comm)
        assert next(it) == [0, 1, 2, 3]
        assert it.batch_size == 4  # attribute forwarding

    def test_synchronized_iterator_reseeds(self, comm):
        a = SerialIterator(list(range(30)), 10, shuffle=True, seed=111)
        b = SerialIterator(list(range(30)), 10, shuffle=True, seed=222)
        a = create_synchronized_iterator(a, comm, seed=5)
        b = create_synchronized_iterator(b, comm, seed=5)
        assert next(a) == next(b)  # identical shuffle order after sync


def _mlp_updater(comm, **kw):
    """Batches of 16 (x fp32[6], y int32, both ndarrays) through a
    small MLP: 448 bytes a batch."""
    import jax
    import optax

    from chainermn_tpu.models import (init_mlp, mlp_apply,
                                      softmax_cross_entropy)

    rng = np.random.RandomState(0)
    data = [(rng.randn(6).astype(np.float32), np.asarray(i % 3, np.int32))
            for i in range(96)]
    return cmn.StandardUpdater(
        cmn.SerialIterator(data, 16, shuffle=False),
        cmn.create_multi_node_optimizer(optax.sgd(0.05), comm),
        lambda p, x, y: softmax_cross_entropy(mlp_apply(p, x), y),
        init_mlp(jax.random.PRNGKey(0), [6, 12, 3]), comm, **kw)


class TestSerialStagingRing:
    """The serial feed under ``StandardUpdater``: the default converter
    becomes a ring of ``max_inflight + 1`` host buffers that
    ``put_window`` hands to ``device_put`` as they are."""

    @pytest.fixture()
    def recorder(self):
        rec = TraceRecorder(capacity=4096, enabled=True, rank=0)
        prev = set_recorder(rec)
        yield rec
        set_recorder(prev)

    @pytest.fixture()
    def registry(self):
        reg = MetricsRegistry(enabled=True)
        prev = set_registry(reg)
        yield reg
        set_registry(prev)

    @pytest.mark.parametrize("max_inflight", [1, 2])
    def test_addresses_repeat_and_nothing_fresh_after_first_lap(
            self, comm, recorder, registry, max_inflight):
        upd = _mlp_updater(comm, max_inflight=max_inflight)
        n = max_inflight + 1
        batch_bytes = 16 * (6 * 4 + 4)
        seen = []
        pull = upd._next_arrays
        upd._next_arrays = lambda: seen.append(pull()) or seen[-1]
        fresh_after = []
        for _ in range(3 * n):
            upd.update()
            fresh_after.append(
                registry.counter("feed/staging_fresh_bytes").value)
        addr = [[a.ctypes.data for a in arrays] for arrays in seen]
        assert len({tuple(a) for a in addr}) == n
        assert addr[n:] == addr[:-n]            # period n
        reused = [e["meta"]["reused"] for e in recorder.events()
                  if e["name"] == "feed/convert"]
        assert reused == [0] * n + [batch_bytes] * (2 * n)
        assert fresh_after[n - 1:] == [n * batch_bytes] * (2 * n + 1)
        assert registry.counter("feed/staging_reused_bytes").value \
            == 2 * n * batch_bytes

    def test_serial_put_window_gets_the_ring_buffer_uncopied(
            self, comm, monkeypatch):
        """The copy is gone where the updater vouches, and only there:
        ``device_put`` sees the ring's own memory in the serial feed
        and a copy of it in the prefetched one."""
        import jax

        put = []        # the host batches device_put was given
        real = jax.device_put

        def spy(a, s=None):
            if isinstance(a, np.ndarray) and a.ndim:
                put.append(a)
            return real(a, s)

        monkeypatch.setattr(jax, "device_put", spy)
        serial = _mlp_updater(comm)
        del put[:]              # whatever building the updater put
        serial.update()
        ring = serial.converter
        assert put and all(ring.owns_buffers((a,)) for a in put)
        pre = _mlp_updater(comm, prefetch=2)
        del put[:]
        pre.update()
        pre.iterator.close()
        ring = pre.iterator._converter
        assert isinstance(ring, cmn.StagingConverter)
        assert put and not any(ring.owns_buffers((a,)) for a in put)

    def test_ragged_and_mixed_columns_count_as_fresh(self, registry):
        sc = StagingConverter(n_buffers=2)
        mixed = [(np.full(3, i, np.float32), i) for i in range(4)]
        for _ in range(3):
            x, y = pull_batch(iter([mixed]), sc, 1, True)
        # x recycles from the third call on; the python ints never do
        assert sc.last_reused_bytes == x.nbytes
        assert sc.last_fresh_bytes == y.nbytes
        passed = np.zeros((4, 3), np.float32)
        assert pull_batch(iter([passed]), sc, 1, True)[0] is passed
        assert sc.last_reused_bytes == sc.last_fresh_bytes == 0
        assert registry.counter("feed/staging_fresh_bytes").value \
            == 2 * x.nbytes + 3 * y.nbytes
        # any other converter counts nothing
        pull_batch(iter([mixed]), default_converter, 1, True)
        assert registry.counter("feed/staging_fresh_bytes").value \
            == 2 * x.nbytes + 3 * y.nbytes


class TestOwnsBuffersByMemory:
    def test_dropped_remainder_view_is_recognised(self):
        """250 examples over a world of 8: ``apply_batch_policy``
        returns the view ``a[:248]`` of the ring buffer, which the
        prefetched feed must still copy before ``device_put``."""
        sc = StagingConverter(n_buffers=2)
        batch = [np.full(3, i, np.float32) for i in range(250)]
        (whole,) = sc(batch)
        (kept,) = apply_batch_policy((whole,), 8, drop_remainder=True)
        assert kept.shape == (248, 3) and kept is not whole
        assert np.shares_memory(kept, whole)
        assert sc.owns_buffers((kept,))
        assert sc.owns_buffers((kept[3:5].reshape(-1),))   # view of view
        assert sc.owns_buffers((whole,))
        assert not sc.owns_buffers((np.array(kept), np.stack(batch)))

    def test_put_window_copies_the_view(self, comm):
        from jax.sharding import NamedSharding, PartitionSpec as P

        sc = StagingConverter(n_buffers=2)
        n = 31 * comm.size + 2
        batch = [np.full(3, i, np.float32) for i in range(n)]
        arrays = pull_batch(iter([batch]), sc, comm.size, True)
        sharding = NamedSharding(comm.mesh, P(comm.axis_name))
        (dev,), k, tail = put_window([arrays], None, sharding, sharding,
                                     converter=sc)
        want = np.array(arrays[0])
        sc(batch)[0][:] = -1        # lap the ring of 2
        sc(batch)[0][:] = -1
        assert k == 1 and tail is None
        np.testing.assert_array_equal(np.asarray(dev), want)
