"""``create_multi_node_optimizer(plan=...)`` — the plan-driven exchange
through the full training stack: auto-tuning at init, training parity
with the default fused optimizer, the updater's ``main/exchange_time``
observation feeding the drift guard, and the plan riding the snapshot
so a resumed run compiles the identical exchange program.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import chainermn_tpu as cmn
from chainermn_tpu.models import init_mlp, mlp_apply, softmax_cross_entropy
from chainermn_tpu.training._resume import (
    collect_train_state,
    restore_train_state,
)
from chainermn_tpu.training.optimizers import PlannedOptimizer
from chainermn_tpu.utils import autotune


@pytest.fixture()
def comm():
    return cmn.create_communicator("tpu_xla")


def _dataset(n=128, dim=6, classes=3, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(dim).astype(np.float32), np.int32(i % classes))
            for i in range(n)]


def _loss_fn(p, x, y):
    return softmax_cross_entropy(mlp_apply(p, x), y)


def _params():
    return init_mlp(jax.random.PRNGKey(0), [6, 12, 3])


@pytest.fixture()
def scratch_cache(tmp_path, monkeypatch):
    """Route the default plan cache (what plan='auto' consults) to a
    per-test scratch file — auto-tuning stays hermetic and fast."""
    path = str(tmp_path / "plans.json")
    monkeypatch.setenv(autotune.PLAN_CACHE_ENV, path)
    return path


def _make(comm, plan="auto", batch=16, **kw):
    it = cmn.SerialIterator(_dataset(), batch, repeat=True, shuffle=True,
                            seed=7)
    if plan is None:
        opt = cmn.create_multi_node_optimizer(optax.sgd(0.05), comm)
    else:
        opt = cmn.create_multi_node_optimizer(
            optax.sgd(0.05), comm, plan=plan)
    return cmn.StandardUpdater(it, opt, _loss_fn, _params(), comm, **kw)


def _assert_params_close(a, b, rtol=1e-5, atol=1e-6):
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=rtol, atol=atol), a, b)


class TestPlannedOptimizer:
    def test_auto_resolves_at_init_and_trains_at_parity(self, comm,
                                                        scratch_cache):
        planned = _make(comm)
        baseline = _make(comm, plan=None)
        cell = planned.optimizer.plan_cell
        assert isinstance(planned.optimizer, PlannedOptimizer)
        assert cell.plan is not None
        assert cell.plan.strategy in ("per_leaf", "fused_flat",
                                      "reduce_scatter")
        for _ in range(4):
            planned.update()
            baseline.update()
        # native-wire plans compute elementwise-identical reductions of
        # the same members (tight parity with the default fused path);
        # a tuned bf16-wire plan carries the documented wire tolerance
        if cell.plan.wire_dtype:
            _assert_params_close(planned.params, baseline.params,
                                 rtol=3e-2, atol=3e-2)
        else:
            _assert_params_close(planned.params, baseline.params)

    def test_explicit_plan_skips_tuning(self, comm):
        plan = autotune.Plan(strategy="reduce_scatter",
                             bucket_bytes=2048, wire_dtype=None,
                             measured_ms=1.0, key="pinned")
        upd = _make(comm, plan=plan)
        cell = upd.optimizer.plan_cell
        assert cell.plan.strategy == "reduce_scatter"
        assert cell.plan.n_probes == 0
        upd.update()
        assert upd.iteration > 0

    def test_plan_dict_accepted(self, comm):
        upd = _make(comm, plan={"strategy": "fused_flat",
                                      "bucket_bytes": 4096,
                                      "wire_dtype": None})
        upd.update()
        assert upd.optimizer.plan_cell.plan.bucket_bytes == 4096

    def test_auto_without_comm_raises(self):
        with pytest.raises(ValueError, match="comm"):
            cmn.create_multi_node_optimizer(
                optax.sgd(0.1), axis_name="world", plan="auto")

    def test_plan_with_zero1_falls_back_with_one_warning(self, comm,
                                                         monkeypatch):
        """plan='auto' must be safe to set globally: under zero1 the
        plan is ignored in favour of the analytic reduce-scatter path,
        with ONE RuntimeWarning per process (not an error, not a
        per-construction nag)."""
        import warnings as _warnings

        from chainermn_tpu.training import optimizers as _opt

        monkeypatch.setattr(_opt, "_ZERO1_PLAN_WARNED", False)
        with _warnings.catch_warnings(record=True) as rec:
            _warnings.simplefilter("always")
            opt = cmn.create_multi_node_optimizer(
                optax.sgd(0.1), comm, zero1=True, plan="auto")
            cmn.create_multi_node_optimizer(
                optax.sgd(0.1), comm, zero1=True, plan="auto")
        warned = [w for w in rec if issubclass(w.category,
                                               RuntimeWarning)]
        assert len(warned) == 1
        assert "zero1" in str(warned[0].message)
        # the fallback is the full ZeRO-1 transformation, and it trains
        from chainermn_tpu.training.optimizers import Zero1Transformation

        assert isinstance(opt, Zero1Transformation)
        it = cmn.SerialIterator(_dataset(), 16, repeat=True,
                                shuffle=True, seed=7)
        upd = cmn.StandardUpdater(it, opt, _loss_fn, _params(), comm)
        upd.update()
        assert np.isfinite(float(upd.observation["main/loss"]))

    def test_bad_plan_string_raises(self, comm):
        with pytest.raises(ValueError, match="auto"):
            cmn.create_multi_node_optimizer(
                optax.sgd(0.1), comm, plan="fastest")

    def test_unresolved_plan_fails_loudly_in_update(self, comm):
        opt = cmn.create_multi_node_optimizer(
            optax.sgd(0.1), comm, plan=autotune.PlanCell())
        from jax.sharding import PartitionSpec as P

        def step(g):
            # chain-shaped state: [planned mean, sgd] — the planned
            # reducer raises before the inner state is ever touched
            u, _ = opt.update(g, (optax.EmptyState(),
                                  optax.EmptyState()), None)
            return u

        with pytest.raises(RuntimeError, match="unresolved"):
            jax.jit(jax.shard_map(
                step, mesh=comm.mesh, in_specs=P("world"),
                out_specs=P("world")))(jnp.ones((comm.size, 4)))


class TestExchangeObservation:
    def test_exchange_time_observed(self, comm, scratch_cache):
        upd = _make(comm, exchange_probe_every=2)
        upd.update()
        assert "main/exchange_time" not in upd.observation
        upd.update()      # 2nd window: probe fires
        assert upd.observation["main/exchange_time"] > 0
        # the observation fed the drift guard
        cell = upd.optimizer.plan_cell
        assert cell.observed_s == \
            upd.observation["main/exchange_time"]

    def test_drift_guard_fires_and_retune_recovers(self, comm,
                                                   scratch_cache):
        upd = _make(comm, exchange_probe_every=1)
        cell = upd.optimizer.plan_cell
        # pretend the plan was tuned on a much faster machine: the
        # observed probe time will depart by far more than the factor
        cell.plan.measured_ms = 1e-6
        upd.update()
        assert cell.drifted
        # optional re-tune: adopts a freshly measured plan, after which
        # the observation slate is clean
        newplan = cell.retune(comm, upd.params,
                              cache_path=scratch_cache,
                              trials=1, warmup=1)
        assert cell.plan is newplan and not cell.drifted

    def test_retune_auto_invalidates_step_cache(self, comm,
                                                scratch_cache):
        """A plan change (retune / any resolve) bumps the cell's
        generation; the updater notices on its next update() and
        recompiles — no manual reach into the private step cache."""
        upd = _make(comm)
        upd.update()
        assert len(upd._step_cache) > 0
        upd.optimizer.plan_cell.resolve(autotune.Plan(
            strategy="per_leaf", bucket_bytes=1, measured_ms=1.0,
            key="swapped"))
        upd.update()      # clears + recompiles with the new plan
        assert upd._plan_generation == upd.optimizer.plan_cell.generation
        # the freshly compiled program is the only cached one
        assert len(upd._step_cache) == 1

    def test_probe_requires_planned_optimizer(self, comm):
        with pytest.raises(ValueError, match="planned optimizer"):
            _make(comm, plan=None, exchange_probe_every=1)

    def test_negative_probe_interval_rejected(self, comm,
                                               scratch_cache):
        with pytest.raises(ValueError, match=">= 0"):
            _make(comm, exchange_probe_every=-1)


class TestPlanRidesSnapshot:
    def test_collect_and_restore_roundtrip(self, comm, scratch_cache):
        writer = _make(comm)
        writer.update()
        state = collect_train_state(writer, None)
        assert state["exchange_plan"] == \
            writer.optimizer.plan_cell.plan.to_dict()

        # the reader tuned into a DIFFERENT plan (cache moved, machine
        # differs): restore must adopt the writer's and invalidate the
        # compiled steps so the resumed program is identical
        reader = _make(comm)
        reader.optimizer.plan_cell.resolve(autotune.Plan(
            strategy="per_leaf", bucket_bytes=1, measured_ms=9.9,
            key="different"))
        reader.update()
        assert len(reader._step_cache) > 0
        restore_train_state(state, reader, None)
        assert reader.optimizer.plan_cell.plan.to_dict() == \
            state["exchange_plan"]
        assert len(reader._step_cache) == 0
        reader.update()       # recompiles with the writer's plan

    def test_restore_same_plan_keeps_step_cache(self, comm,
                                                scratch_cache):
        upd = _make(comm)
        upd.update()
        state = collect_train_state(upd, None)
        n_cached = len(upd._step_cache)
        assert n_cached > 0
        restore_train_state(state, upd, None)
        # identical plan: nothing invalidated, no recompile storm
        assert len(upd._step_cache) == n_cached

    def test_restore_exec_identical_plan_keeps_step_cache(
            self, comm, scratch_cache):
        """Only the executable fields (strategy, bucket, wire) decide
        program identity: a snapshot plan differing solely in meta
        (timings, timestamps) must NOT force a recompile at resume."""
        upd = _make(comm)
        upd.update()
        state = collect_train_state(upd, None)
        n_cached = len(upd._step_cache)
        twin = dict(state["exchange_plan"])
        twin["measured_ms"] = 123.456
        twin["meta"] = {"created": "some-other-day"}
        restore_train_state(dict(state, exchange_plan=twin), upd, None)
        assert len(upd._step_cache) == n_cached

    def test_resume_is_bitwise_with_snapshot_plan(self, comm,
                                                  scratch_cache):
        """The acceptance property: resume never re-tunes into a
        different program.  Two fresh updaters restored from the same
        (params, plan) state must produce bit-identical params."""
        writer = _make(comm)
        for _ in range(2):
            writer.update()
        state = collect_train_state(writer, None)
        params = jax.tree.map(np.asarray, writer.params)

        def resume_and_step():
            upd = _make(comm)
            upd.params = upd.comm.bcast_data(
                jax.tree.map(jnp.asarray, params))
            # a resumed run may have tuned a different plan locally...
            upd.optimizer.plan_cell.resolve(autotune.Plan(
                strategy="per_leaf", bucket_bytes=1, key="local"))
            restore_train_state(state, upd, None)
            upd.update()
            return jax.tree.map(np.asarray, upd.params)

        a, b = resume_and_step(), resume_and_step()
        jax.tree.map(np.testing.assert_array_equal, a, b)

    def test_snapshot_without_plan_is_clean(self, comm):
        upd = _make(comm, plan=None)
        upd.update()
        state = collect_train_state(upd, None)
        assert "exchange_plan" not in state
        restore_train_state(state, upd, None)     # no-op, no crash
