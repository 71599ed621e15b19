"""Backward-overlapped gradient exchange — the ``overlap`` plan family
through the full stack: the ``ops.fused.overlap_exchange`` lowering
(parity, schedules, the non-float wire exemption), the updater's
final-microbatch peel under accumulation, the compiled-HLO overlap
proof (``assert_overlap_collectives`` passes the overlap program and
rejects the window-end one), and composition with
prefetch/steps_per_execution (bitwise loss trajectories) and ZeRO-1.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import chainermn_tpu as cmn
from chainermn_tpu.models import init_mlp, mlp_apply, softmax_cross_entropy
from chainermn_tpu.ops import fused as F
from chainermn_tpu.parallel._compat import shard_map
from chainermn_tpu.utils import (
    assert_overlap_collectives,
    collective_stats,
)


@pytest.fixture()
def comm():
    return cmn.create_communicator("tpu_xla")


@pytest.fixture()
def mesh():
    return Mesh(np.array(jax.devices()), ("d",))


def _world_exchange(mesh, exchange):
    """Run ``exchange`` on each member's slice of a world-stacked tree."""
    def body(g):
        local = jax.tree.map(lambda a: a[0], g)
        red = exchange(local)
        return jax.tree.map(lambda a: a[None], red)

    return jax.jit(shard_map(body, mesh=mesh, in_specs=P("d"),
                             out_specs=P("d")))


def _stacked_tree(n=8, seed=0):
    """World-stacked mixed-dtype tree: rank-varying floats, a
    rank-identical int leaf (its mean is exact — the wire-exemption
    probe), and an empty leaf."""
    rng = np.random.RandomState(seed)
    ints = (rng.rand(1, 33) * 70000).astype(np.int32)
    return {
        "w1": rng.randn(n, 257, 3).astype(np.float32),
        "b1": rng.randn(n, 19).astype(np.float32),
        "idx": np.broadcast_to(ints, (n, 33)).copy(),
        "w2": rng.randn(n, 1500).astype(np.float32),
        "empty": np.zeros((n, 0), np.float32),
    }


def _assert_tree_close(got, want, rtol=1e-6, atol=1e-6):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(g, np.float64), np.asarray(w, np.float64),
            rtol=rtol, atol=atol)


class TestOverlapExchangeOp:
    def _ref(self, mesh, tree):
        fn = _world_exchange(mesh, lambda g: jax.tree.map(
            lambda a: jax.lax.pmean(a, "d") if a.size else a, g))
        return fn(tree)

    @pytest.mark.parametrize("schedule", [
        None,                                               # derived
        ({"leaves": 4, "mode": "eager", "via": "ar"},),     # one bucket
        ({"leaves": 1, "mode": "eager", "via": "rs"},       # mixed modes
         {"leaves": 2, "mode": "deferred", "via": "ar"},
         {"leaves": 1, "mode": "eager", "via": "rs"}),
    ], ids=["derived", "single_bucket", "mixed_modes"])
    def test_parity_vs_per_leaf(self, mesh, schedule):
        tree = _stacked_tree()
        got = _world_exchange(mesh, lambda g: F.overlap_exchange(
            g, "d", schedule=schedule, bucket_bytes=2048))(tree)
        _assert_tree_close(got, self._ref(mesh, tree))

    def test_nonfloat_wire_exemption_is_exact(self, mesh):
        """int32 leaves must NOT be cast to the bf16 wire: a bf16
        round-trip of values past 2**8 silently drops low bits."""
        tree = _stacked_tree()
        got = _world_exchange(mesh, lambda g: F.overlap_exchange(
            g, "d", bucket_bytes=1024, wire_dtype=jnp.bfloat16))(tree)
        assert got["idx"].dtype == np.int32
        np.testing.assert_array_equal(np.asarray(got["idx"]),
                                      tree["idx"])
        # floats carry the documented wire tolerance
        _assert_tree_close(got, self._ref(mesh, tree), rtol=5e-2,
                           atol=5e-2)

    def test_single_leaf_pytree(self, mesh):
        """Single-bucket/single-leaf tree: no anchors, one exchange."""
        rng = np.random.RandomState(1)
        tree = rng.randn(8, 101).astype(np.float32)
        got = _world_exchange(mesh, lambda g: F.overlap_exchange(
            g, "d"))(tree)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(self._ref(mesh, tree)),
                                   rtol=1e-6, atol=1e-6)

    def test_schedule_mismatch_raises(self, mesh):
        tree = _stacked_tree()
        with pytest.raises(ValueError, match="payload signature"):
            _world_exchange(mesh, lambda g: F.overlap_exchange(
                g, "d",
                schedule=({"leaves": 2, "mode": "eager"},)))(tree)

    @pytest.mark.parametrize("entry,err", [
        ({"leaves": 0, "mode": "eager"}, "positive leaf count"),
        ({"leaves": 1, "mode": "lazy"}, "mode"),
        ({"leaves": 1, "mode": "eager", "via": "nccl"}, "via"),
    ])
    def test_bad_schedule_entries_raise(self, entry, err):
        with pytest.raises(ValueError, match=err):
            F._normalize_schedule((entry,))

    def test_build_schedule_covers_leaves_and_wire_itemsize(self):
        sds = [jax.ShapeDtypeStruct((4096,), jnp.float32),
               jax.ShapeDtypeStruct((10,), jnp.float32),
               jax.ShapeDtypeStruct((0,), jnp.float32),
               jax.ShapeDtypeStruct((4096,), jnp.float32)]
        native = F.build_overlap_schedule(sds, bucket_bytes=16384)
        assert sum(e["leaves"] for e in native) == 3    # empty skipped
        # bf16 wire halves the float bytes, so the same bucket size
        # packs MORE leaves per bucket (fewer buckets)
        bf16 = F.build_overlap_schedule(sds, 16384, "bfloat16")
        assert len(bf16) <= len(native)
        assert sum(e["leaves"] for e in bf16) == 3

    def test_plan_allreduce_dispatches_overlap(self, mesh):
        tree = _stacked_tree()
        plan = {"strategy": "overlap", "bucket_bytes": 2048,
                "wire_dtype": None,
                "schedule": [{"leaves": 4, "mode": "eager",
                              "via": "rs"}]}
        got = _world_exchange(mesh, lambda g: F.plan_allreduce(
            g, "d", plan))(tree)
        _assert_tree_close(got, self._ref(mesh, tree))


# ----------------------------------------------------------------- #
# training stack
# ----------------------------------------------------------------- #

_N, _DIM, _H, _C = 512, 24, 48, 5


def _dataset(seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(_N, _DIM).astype(np.float32)
    Y = (rng.rand(_N) * _C).astype(np.int32)
    return X, Y


def _loss_fn(p, x, y):
    return softmax_cross_entropy(mlp_apply(p, x), y)


def _params(depth=4):
    return init_mlp(jax.random.PRNGKey(0),
                    [_DIM] + [_H] * depth + [_C])


def _make(comm, overlap, accum=4, depth=4, batch=32, seed=3,
          bucket=2048, **kw):
    X, Y = _dataset()
    it = cmn.SerialIterator((X, Y), batch, shuffle=True, seed=seed)
    opt_kw = {k: kw.pop(k) for k in ("plan", "zero1",
                                     "allreduce_grad_dtype")
              if k in kw}
    opt = cmn.create_multi_node_optimizer(
        optax.sgd(0.05), comm, overlap=overlap, bucket_bytes=bucket,
        **opt_kw)
    return cmn.StandardUpdater(it, opt, _loss_fn, _params(depth), comm,
                               accum_steps=accum, **kw)


def _compile_window(upd, n_steps=1, accum=4):
    arrays, k, _tail = upd._assemble_host_window()
    fn = upd._get_step(len(arrays), n_steps, accum)
    carry = (upd.params, upd.state, upd.opt_state)
    return fn.lower(carry, *arrays).compile()


def _losses(upd, n):
    out = []
    for _ in range(n):
        upd.update()
        out.append(float(upd.observation["main/loss"]))
    return out


class TestOverlapTraining:
    def test_parity_vs_window_end(self, comm):
        a, b = _make(comm, True), _make(comm, False)
        la, lb = _losses(a, 5), _losses(b, 5)
        # same data, same accumulation order; only the exchange
        # lowering differs (rs→ag vs fused all-reduce) — fp32
        # collective-reduction-order tolerance, nothing more
        np.testing.assert_allclose(la, lb, rtol=1e-4, atol=1e-5)
        jax.tree.map(
            lambda x, y: np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=2e-4, atol=1e-5),
            a.params, b.params)

    def test_accum_one_trains_and_proves(self, comm):
        u = _make(comm, True, accum=1)
        losses = _losses(u, 3)
        assert np.isfinite(losses).all()
        # What a CPU compile can count: the exchange stays a per-bucket
        # STREAM (several collectives, none joined into one arena, none
        # looped).  WHERE XLA:CPU's scheduler prints them relative to
        # the last backward dot is that compiler's choice for its
        # synchronous collectives — the "under the backward" timing
        # claim belongs to a four-chip trace (ROADMAP S3).
        rep = assert_overlap_collectives(_compile_window(u, 1, 1),
                                         min_frac=0.0)
        assert rep["total"] >= 4

    def test_overlap_proof_accum_window(self, comm):
        rep = assert_overlap_collectives(
            _compile_window(_make(comm, True)), min_frac=0.0)
        assert rep["total"] >= 4

    def test_window_end_fails_the_proof(self, comm):
        """The PR 4 window-end exchange (default 4 MiB bucket: the
        whole grad tree rides one arena, whose concat joins every
        leaf) really does cluster after the backward — the proof must
        reject it, or it proves nothing."""
        with pytest.raises(AssertionError, match="cluster"):
            assert_overlap_collectives(
                _compile_window(_make(comm, False, bucket=None)))

    def test_no_inscan_exchange_with_peel(self, comm):
        """The peel must not leak collectives INTO the M-1 scan: the
        stream fires once per window, under the final backward only."""
        stats = collective_stats(_compile_window(_make(comm, True)))
        assert sum(s.looped for s in stats.values()) == 0

    def test_composition_bitwise_prefetch_spe(self, comm):
        """overlap × prefetch × steps_per_execution: identical data
        through identical programs — the loss trajectory per consumed
        microbatch must be BITWISE equal across pipeline knobs."""
        # max_inflight=1 keeps the observed loss CURRENT (the default
        # prefetch pipelining reports the retired window's loss, which
        # lags — a display offset, not a numeric difference)
        base = _make(comm, True, accum=2)
        pf = _make(comm, True, accum=2, prefetch=2, max_inflight=1)
        spe = _make(comm, True, accum=2, steps_per_execution=2,
                    prefetch=2, max_inflight=1)
        try:
            lb = _losses(base, 4)                    # 4 windows of M=2
            lp = _losses(pf, 4)
            ls = _losses(spe, 2)                     # 2 double-windows
        finally:
            pf.finalize()
            spe.finalize()
        assert lb == lp, (lb, lp)
        # spe=2 reports the mean of each 2-window dispatch
        want = [(lb[0] + lb[1]) / 2, (lb[2] + lb[3]) / 2]
        np.testing.assert_allclose(ls, want, rtol=0, atol=1e-7)

    def test_zero1_overlap_trains_at_parity(self, comm):
        a = _make(comm, True, zero1=True)
        b = _make(comm, False, zero1=True)
        la, lb = _losses(a, 4), _losses(b, 4)
        # ZeRO-1's exchange is identical in both arms (per-leaf
        # psum_scatter); the peel only reorders the schedule, not the
        # math — bitwise
        assert la == lb, (la, lb)
        rep = assert_overlap_collectives(_compile_window(a),
                                         min_bytes=64)
        assert rep["frac"] >= 0.5

    def test_overlap_true_with_window_end_plan_raises(self, comm):
        from chainermn_tpu.utils import autotune

        plan = autotune.Plan(strategy="fused_flat", bucket_bytes=4096)
        with pytest.raises(ValueError, match="overlap"):
            cmn.create_multi_node_optimizer(optax.sgd(0.1), comm,
                                            plan=plan, overlap=True)

    def test_static_overlap_plan_without_comm_probes(self, comm):
        """overlap=True with plan=None must not tune: the analytic
        schedule is derived at trace time, no probes, no cache."""
        u = _make(comm, True)
        cell = u.optimizer.plan_cell
        assert cell.plan.strategy == "overlap"
        assert cell.plan.n_probes == 0
        assert cell.plan.schedule is None       # derived at trace time
