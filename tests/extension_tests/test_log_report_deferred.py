"""``LogReport.observe`` reads a device value when the device has
finished it, never the moment it was dispatched: what it leaves unread,
in what order it reads, and that every sum, log entry and checkpoint is
bit for bit what an eager ``float()`` an iteration gives."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import chainermn_tpu as cmn
from chainermn_tpu.models import init_mlp, mlp_apply, softmax_cross_entropy
from chainermn_tpu.utils.metrics import MetricsRegistry, set_registry
from chainermn_tpu.utils.telemetry import TraceRecorder, set_recorder


class _Device:
    """Stand-in for a device scalar: answers ``is_ready()`` as told and
    counts the reads (``__float__``), each of which would have waited
    where it is not ready."""

    def __init__(self, value, ready=False, refuses=False):
        self.value, self.ready, self.refuses = value, ready, refuses
        self.reads = self.waits = 0

    def is_ready(self):
        return self.ready

    def __float__(self):
        self.reads += 1
        self.waits += not self.ready
        if self.refuses:
            raise TypeError("only size-1 arrays can be converted")
        return self.value


class _EagerLogReport(cmn.LogReport):
    """``observe`` as it was before it deferred: ``float()`` of every
    value in the iteration that brought it."""

    def observe(self, trainer):
        for k, v in trainer.observation.items():
            try:
                f = float(v)
            except (TypeError, ValueError):
                continue
            self._accum[k] = self._accum.get(k, 0.0) + f
        self._count += 1


def _trainer(tmp_path):
    return types.SimpleNamespace(
        observation={}, out=str(tmp_path), elapsed_time=1.5,
        updater=types.SimpleNamespace(iteration=0, epoch=0))


def _observe(report, trainer, **observation):
    trainer.observation = observation
    trainer.updater.iteration += 1
    report.observe(trainer)


# sums whose value depends on the order of the additions
_LOSSES = [0.1, 1e8, 0.2, -1e8, 1e-8, 0.3, 3.0, 1e16, -1e16, 0.7, 0.05, 2.5]


class TestObserveDoesNotWait:
    @pytest.mark.parametrize("n", [1, 3, 40])
    def test_nothing_unready_is_read(self, tmp_path, n):
        report, trainer = cmn.LogReport(), _trainer(tmp_path)
        losses = [_Device(float(i)) for i in range(n)]
        for loss in losses:
            _observe(report, trainer, **{"main/loss": loss,
                                         "main/host_time": 0.25})
        assert [v.reads for v in losses] == [0] * n
        assert report.pending == n
        assert report._accum == {} and report._count == 0

    def test_host_values_are_read_at_once(self, tmp_path):
        report, trainer = cmn.LogReport(), _trainer(tmp_path)
        _observe(report, trainer, a=0.5, b=np.float32(2.0), c=3, d="text")
        assert report.pending == 0 and not report._pending
        assert report._accum == {"a": 0.5, "b": 2.0, "c": 3.0}
        assert report._count == 1

    def test_ready_device_value_is_read_at_once(self, tmp_path):
        report, trainer = cmn.LogReport(), _trainer(tmp_path)
        loss = _Device(0.75, ready=True)
        _observe(report, trainer, **{"main/loss": loss})
        assert (loss.reads, loss.waits) == (1, 0)
        assert report.pending == 0 and report._accum == {"main/loss": 0.75}

    @pytest.mark.parametrize("order", [(0, 1, 2), (2, 1, 0), (1, 0, 2),
                                       (1, 2, 0)])
    def test_ready_prefix_in_arrival_order(self, tmp_path, order):
        """Values may become ready in any order; they are read oldest
        first, and one that is ready behind one that is not stays."""
        report, trainer = cmn.LogReport(), _trainer(tmp_path)
        losses = [_Device(v) for v in (0.1, 1e16, -1e16)]
        for loss in losses:
            _observe(report, trainer, **{"main/loss": loss})
        for i in order:
            losses[i].ready = True
            _observe(report, trainer, tick=0.0)
            prefix = 0
            while prefix < 3 and losses[prefix].ready:
                prefix += 1
            assert [v.reads for v in losses] == \
                [1] * prefix + [0] * (3 - prefix)
            assert report.pending == 3 - prefix
        assert all(v.waits == 0 for v in losses)
        # (0.1 + 1e16) - 1e16, not 0.1 + (1e16 - 1e16)
        assert report._accum["main/loss"] == (0.0 + 0.1 + 1e16) + -1e16

    @pytest.mark.parametrize("lag", [1, 2])
    @pytest.mark.parametrize("interval", [8, 176])
    def test_holds_only_the_unready_tail(self, tmp_path, lag, interval):
        """A device that finishes each loss ``lag`` iterations after it
        was dispatched: the FIFO holds ``lag`` observations however long
        the interval, and no read ever waits."""
        report, trainer = cmn.LogReport(), _trainer(tmp_path)
        losses = []
        for i in range(interval):
            if i >= lag:
                losses[i - lag].ready = True
            losses.append(_Device(float(i)))
            _observe(report, trainer, **{"main/loss": losses[-1],
                                         "main/host_time": 0.5})
            assert len(report._pending) == report.pending == min(i + 1, lag)
        assert sum(v.waits for v in losses) == 0
        assert report._count == interval - lag

    @pytest.mark.parametrize("reader", ["__call__", "state_dict"])
    def test_whatever_needs_the_sums_reads_everything(self, tmp_path,
                                                      reader):
        report, trainer = cmn.LogReport(), _trainer(tmp_path)
        losses = [_Device(v) for v in (1.0, 2.0, 4.0)]
        for loss in losses:
            _observe(report, trainer, **{"main/loss": loss})
        assert report.pending == 3
        if reader == "__call__":
            report(trainer)
            assert report.log[-1]["main/loss"] == 7.0 / 3
        else:
            st = report.state_dict()
            assert st["accum"] == {"main/loss": 7.0} and st["count"] == 3
        assert [v.reads for v in losses] == [1, 1, 1]
        assert report.pending == 0 and not report._pending

    def test_load_state_dict_drops_what_the_old_timeline_left(
            self, tmp_path):
        report, trainer = cmn.LogReport(), _trainer(tmp_path)
        _observe(report, trainer, **{"main/loss": 1.0})
        saved = report.state_dict()
        stale = _Device(64.0)
        _observe(report, trainer, **{"main/loss": stale})
        report.load_state_dict(saved)
        assert report.pending == 0 and stale.reads == 0
        assert report.state_dict() == saved

    def test_a_real_device_scalar(self, tmp_path):
        report, trainer = cmn.LogReport(), _trainer(tmp_path)
        loss = jnp.float32(0.5) * 3
        _observe(report, trainer, **{"main/loss": loss, "vector":
                                     jnp.arange(3.0)})
        jax.block_until_ready(loss)
        _observe(report, trainer, tick=0.0)
        assert report.pending == 0
        assert report._accum == {"main/loss": 1.5, "tick": 0.0}


def _schedule(name, i, n):
    """The iteration at which the loss of iteration ``i`` is ready."""
    return {"at_once": i, "a_step_late": i + 1, "two_steps_late": i + 2,
            "only_at_the_trigger": n,
            "uneven": i + (0, 3, 1, 0, 2)[i % 5]}[name]


class TestSameAsAnEagerRead:
    @pytest.mark.parametrize("schedule", [
        "at_once", "a_step_late", "two_steps_late", "only_at_the_trigger",
        "uneven"])
    def test_entries_and_state_bit_for_bit(self, tmp_path, schedule):
        """Python floats, device scalars ready and not, a value
        ``float()`` refuses, a key that arrives late and one that is a
        host value in some iterations and a device value in others:
        two intervals and a checkpoint in the middle of the second."""
        n = len(_LOSSES)
        reports = {"deferred": cmn.LogReport(), "eager": _EagerLogReport()}
        trainers = {k: _trainer(tmp_path / k) for k in reports}
        states = {}
        for name, report in reports.items():
            trainer = trainers[name]
            os.makedirs(trainer.out)
            for lap in range(2):
                made = []
                for i, loss in enumerate(_LOSSES):
                    made.append(_Device(loss + lap))
                    for j, v in enumerate(made):
                        v.ready = _schedule(schedule, j, n) <= i
                    obs = {"main/loss": made[-1],
                           "main/host_time": 0.001 * (i + 1) / 3,
                           "refused": _Device(9.0, ready=i % 2 == 0,
                                              refuses=True),
                           "mixed": _Device(0.1 * i, ready=True)
                           if i % 3 else 0.1 * i,
                           "text": "not a number"}
                    if i >= 4:
                        obs["late"] = np.float32(1.0) / (i + 1)
                    _observe(report, trainer, **obs)
                    if lap == 1 and i == 6:
                        states[name] = report.state_dict()
                trainer.observation["validation/loss"] = 0.125
                report(trainer)
        eager, deferred = reports["eager"], reports["deferred"]
        assert len(eager.log) == 2
        for want, got in zip(eager.log, deferred.log):
            assert list(got.items()) == list(want.items())
        assert list(states["deferred"]["accum"].items()) == \
            list(states["eager"]["accum"].items())
        assert states["deferred"]["count"] == states["eager"]["count"] == 7
        assert states["deferred"]["log"] == states["eager"]["log"]
        with open(f"{trainers['deferred'].out}/log") as f:
            written = f.read()
        with open(f"{trainers['eager'].out}/log") as f:
            assert written == f.read()

    @pytest.mark.parametrize("updater_kw", [
        {}, {"max_inflight": 2}, {"prefetch": 2},
        {"steps_per_execution": 2}], ids=str)
    def test_trainer_run_writes_the_same_log(self, tmp_path, updater_kw):
        """A real ``StandardUpdater`` on the CPU: ``out/log`` under the
        deferred read is the eager read's, key for key; the losses and
        counts bit for bit (the host's timings differ run to run)."""
        logs = {}
        for name, cls in (("deferred", cmn.LogReport),
                          ("eager", _EagerLogReport)):
            trainer = _mlp_trainer(tmp_path / name, **updater_kw)
            trainer.extend(cls(trigger=(3, "iteration")))
            trainer.run()
            with open(tmp_path / name / "log") as f:
                logs[name] = json.load(f)
        assert len(logs["eager"]) >= 2
        for want, got in zip(logs["eager"], logs["deferred"]):
            assert list(got) == list(want)
            for key in ("main/loss", "iteration", "epoch"):
                assert got[key] == want[key]
        assert len(logs["deferred"]) == len(logs["eager"])


def _mlp_trainer(out, epochs=2, **updater_kw):
    rng = np.random.RandomState(0)
    data = [(rng.randn(6).astype(np.float32), np.int32(i % 3))
            for i in range(64)]
    comm = cmn.create_communicator("tpu_xla")
    it = cmn.SerialIterator(data, 16, shuffle=True, seed=3)
    params = init_mlp(jax.random.PRNGKey(0), [6, 12, 3])
    opt = cmn.create_multi_node_optimizer(optax.sgd(0.05), comm)

    def loss_fn(p, x, y):
        return softmax_cross_entropy(mlp_apply(p, x), y)

    upd = cmn.StandardUpdater(it, opt, loss_fn, params, comm, **updater_kw)
    return cmn.Trainer(upd, (epochs, "epoch"), out=str(out))


class TestWhatTheTracingSays:
    def test_span_carries_pending_and_counters_add_up(self, tmp_path):
        """The ``trainer/observe`` span says how many device values the
        hooks left unread; of the values observed, the registry counts
        each once, as read in the ``observe`` that brought it or as
        deferred."""
        rec = TraceRecorder(capacity=4096, enabled=True, rank=0)
        reg = MetricsRegistry(enabled=True)
        prev_rec, prev_reg = set_recorder(rec), set_registry(reg)
        try:
            trainer = _mlp_trainer(tmp_path, epochs=1)
            report = cmn.LogReport(trigger=(2, "iteration"))
            trainer.extend(report)
            left = []
            trainer.extend(lambda t: left.append(report.pending),
                           trigger=(1, "iteration"), name="peek",
                           priority=60)
            values = []
            trainer.extend(lambda t: values.append(len(t.observation)),
                           trigger=(1, "iteration"), name="count")
            trainer.run()
        finally:
            set_recorder(prev_rec)
            set_registry(prev_reg)
        observes = [e for e in rec.events()
                    if e["name"] == "trainer/observe"]
        assert len(observes) == 4
        assert [e["meta"]["pending"] for e in observes] == left
        assert all(p in (0, 1) for p in left)
        read = reg.counter("trainer/observe_read").value
        deferred = reg.counter("trainer/observe_deferred").value
        assert read + deferred == sum(values) == 16

    def test_counters_by_what_was_ready(self, tmp_path):
        reg = MetricsRegistry(enabled=True)
        prev = set_registry(reg)
        try:
            report, trainer = cmn.LogReport(), _trainer(tmp_path)
            _observe(report, trainer, a=1.0, b=_Device(2.0, ready=True))
            first = _Device(3.0)
            _observe(report, trainer, a=1.0, b=first)
            _observe(report, trainer, a=1.0, b=_Device(4.0, ready=True),
                     c=0.5)
            first.ready = True
            _observe(report, trainer, a=1.0)
        finally:
            set_registry(prev)
        assert reg.counter("trainer/observe_read").value == 2 + 1
        assert reg.counter("trainer/observe_deferred").value == 2 + 3
        assert report.pending == 0 and report._count == 4
