"""TrainingWatchdog: step-boundary heartbeats arm a monitor thread that
must stay silent on a healthy run, fire a structured stall report within
one check interval of a stall crossing the threshold, and shut down
cleanly with the trainer (no leaked threads)."""

import json
import threading
import time

import jax
import numpy as np
import optax
import pytest

import chainermn_tpu as cmn
from chainermn_tpu.extensions import TrainingWatchdog
from chainermn_tpu.models import init_mlp, mlp_apply, softmax_cross_entropy


def _dataset(n=64, dim=6, classes=3, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(dim).astype(np.float32), np.int32(i % classes))
            for i in range(n)]


def _make_trainer(comm, out, epochs=2):
    it = cmn.SerialIterator(_dataset(), 16, shuffle=True, seed=3)
    params = init_mlp(jax.random.PRNGKey(0), [6, 12, 3])
    opt = cmn.create_multi_node_optimizer(optax.sgd(0.05), comm)

    def loss_fn(p, x, y):
        return softmax_cross_entropy(mlp_apply(p, x), y)

    upd = cmn.StandardUpdater(it, opt, loss_fn, params, comm)
    return cmn.Trainer(upd, (epochs, "epoch"), out=str(out))


class TestWatchdogUnit:
    def test_stall_fires_within_one_check_interval(self, tmp_path):
        reports = []
        wd = TrainingWatchdog(stall_timeout=0.4, check_interval=0.1,
                              on_stall=reports.append,
                              report_path=str(tmp_path / "stall.json"))
        wd.start()
        try:
            before_beat = time.monotonic()
            wd.heartbeat(iteration=7)
            # the wait is the TEST's patience, not the claim: on a host
            # shared with five other xdist workers the monitor thread
            # can wake late
            deadline = time.monotonic() + 60.0
            while not reports and time.monotonic() < deadline:
                time.sleep(0.02)
            seen_after = time.monotonic() - before_beat
            # the same missed heartbeat, several wakes later: still ONE
            time.sleep(0.3)
        finally:
            wd.stop()
        assert wd.stall_count == 1 and len(reports) == 1
        rep = reports[0]
        assert rep["kind"] == "local-stall"
        assert rep["iteration"] == 7
        # fired on the monitor's own clock at its first wake past the
        # timeout: no sooner than the timeout (the report rounds to the
        # millisecond, so it may read the timeout itself) and no later
        # than this thread saw it; how late a wake comes is the host's
        assert 0.4 <= rep["seconds_since_heartbeat"] <= seen_after + 0.001
        # the structured report carries every thread's Python stack
        assert any("MainThread" in k for k in rep["threads"])
        on_disk = json.load(open(tmp_path / "stall.json"))
        assert on_disk["kind"] == "local-stall"

    def test_stall_report_embeds_merged_metrics(self, tmp_path):
        """A hung job's last Prometheus state ships with the diagnosis:
        the stall report carries the merged metrics snapshot and its
        exposition text alongside the trace tail."""
        from chainermn_tpu.utils.metrics import (
            MetricsRegistry,
            set_registry,
        )

        prev = set_registry(MetricsRegistry(enabled=True))
        try:
            reports = []
            wd = TrainingWatchdog(stall_timeout=0.2, check_interval=0.05,
                                  on_stall=reports.append,
                                  report_path=str(tmp_path / "s.json"))
            wd.start()
            try:
                wd.heartbeat(iteration=3)   # records watchdog/heartbeats
                deadline = time.monotonic() + 1.0
                while not reports and time.monotonic() < deadline:
                    time.sleep(0.02)
            finally:
                wd.stop()
            rep = reports[0]
            assert rep["metrics_enabled"] is True
            assert rep["metrics"]["watchdog/heartbeats"]["value"] == 1
            assert "watchdog/stalls" in rep["metrics"]
            assert "watchdog_heartbeats" in rep["metrics_prom"]
            assert 'rank="merged"' in rep["metrics_prom"]
            # and the on-disk report serialized it too
            on_disk = json.load(open(tmp_path / "s.json"))
            assert on_disk["metrics"]["watchdog/heartbeats"]["value"] == 1
        finally:
            set_registry(prev)

    def test_stall_report_metrics_disabled_registry(self, tmp_path):
        """Registry off (the production default): the report still
        carries the keys, empty — never an exception path."""
        reports = []
        wd = TrainingWatchdog(stall_timeout=0.15, check_interval=0.05,
                              on_stall=reports.append,
                              report_path=str(tmp_path / "s.json"))
        wd.start()
        try:
            wd.heartbeat(iteration=1)
            deadline = time.monotonic() + 1.0
            while not reports and time.monotonic() < deadline:
                time.sleep(0.02)
        finally:
            wd.stop()
        assert reports[0]["metrics_enabled"] is False
        assert reports[0]["metrics"] == {}

    def test_not_armed_before_first_heartbeat(self, tmp_path):
        """Compile time before step 1 must never false-fire."""
        wd = TrainingWatchdog(stall_timeout=0.1, check_interval=0.05,
                              report_path=str(tmp_path / "s.json"))
        wd.start()
        time.sleep(0.3)
        wd.stop()
        assert wd.stall_count == 0

    def test_one_report_per_stall_episode(self, tmp_path):
        reports = []
        wd = TrainingWatchdog(stall_timeout=0.15, check_interval=0.05,
                              on_stall=reports.append,
                              report_path=str(tmp_path / "s.json"))
        wd.start()
        try:
            wd.heartbeat(iteration=1)
            time.sleep(0.5)          # one long stall, many check ticks
            assert wd.stall_count == 1
            wd.heartbeat(iteration=2)  # recovery re-arms the reporter
            time.sleep(0.4)
        finally:
            wd.stop()
        assert wd.stall_count == 2
        assert [r["iteration"] for r in reports] == [1, 2]

    def test_peer_stall_reported_once_per_episode(self, tmp_path):
        """A permanently dead peer must produce ONE peer-stall report,
        not a stack dump every check interval for the rest of the job;
        a recovered peer re-arms its slot."""
        reports = []
        wd = TrainingWatchdog(stall_timeout=0.2, check_interval=0.05,
                              on_stall=reports.append,
                              report_path=str(tmp_path / "s.json"))
        ages = {"now": {1: 9.9}}
        wd._peer_ages = lambda: dict(ages["now"])
        wd.start()
        try:
            deadline = time.monotonic() + 0.6
            while time.monotonic() < deadline:  # this rank stays healthy
                wd.heartbeat(iteration=1)
                time.sleep(0.02)
            assert len(reports) == 1, reports
            assert reports[0]["kind"] == "peer-stall"
            assert reports[0]["stalled_peers"] == {1: 9.9}
            # peer recovers, then stalls again -> a second report
            ages["now"] = {1: 0.0}
            t0 = time.monotonic()
            while time.monotonic() - t0 < 0.2:
                wd.heartbeat(iteration=2)
                time.sleep(0.02)
            ages["now"] = {1: 7.7}
            t0 = time.monotonic()
            while time.monotonic() - t0 < 0.3 and len(reports) < 2:
                wd.heartbeat(iteration=3)
                time.sleep(0.02)
        finally:
            wd.stop()
        assert len(reports) == 2
        # peer-only reports never consumed the local stall episode
        assert all(r["kind"] == "peer-stall" for r in reports)

    def test_never_published_peer_is_aged_from_monitor_start(
            self, tmp_path, monkeypatch):
        """A rank wedged BEFORE its first heartbeat (the PJRT-init hang
        class) never appears in the KV directory — survivors must age
        it from monitor start and report it, not treat it as
        invisible."""
        from types import SimpleNamespace

        reports = []
        wd = TrainingWatchdog(stall_timeout=0.2, check_interval=0.05,
                              on_stall=reports.append,
                              report_path=str(tmp_path / "s.json"))
        wd.comm = SimpleNamespace(inter_size=2, inter_rank=0)
        fake_kv = SimpleNamespace(key_value_dir_get=lambda prefix: [
            ("watchdog/hb/0", "5,123.0")])  # only OUR rank ever beat
        monkeypatch.setattr(TrainingWatchdog, "_kv",
                            property(lambda self: fake_kv))
        wd.start()
        try:
            deadline = time.monotonic() + 0.6
            while not reports and time.monotonic() < deadline:
                wd.heartbeat(iteration=0)
                time.sleep(0.02)
        finally:
            wd.stop()
        assert reports, "never-published peer was never detected"
        assert reports[0]["kind"] == "peer-stall"
        assert 1 in reports[0]["stalled_peers"]

    def test_bad_config_rejected(self):
        with pytest.raises(ValueError):
            TrainingWatchdog(stall_timeout=0)
        with pytest.raises(ValueError):
            TrainingWatchdog(stall_timeout=10, check_interval=-1)

    def test_on_stall_exception_swallowed(self, tmp_path):
        def boom(report):
            raise RuntimeError("metrics push failed")

        wd = TrainingWatchdog(stall_timeout=0.1, check_interval=0.05,
                              on_stall=boom,
                              report_path=str(tmp_path / "s.json"))
        wd.start()
        try:
            wd.heartbeat()
            time.sleep(0.35)
        finally:
            wd.stop()
        assert wd.stall_count >= 1  # survived the callback crash


class TestWatchdogTrainer:
    def test_healthy_run_no_report_and_no_thread_leak(self, comm,
                                                      tmp_path):
        before = {t.ident for t in threading.enumerate()}
        trainer = _make_trainer(comm, tmp_path)
        wd = TrainingWatchdog(stall_timeout=60, comm=comm)
        trainer.extend(wd)
        trainer.run()
        assert wd.stall_count == 0
        assert wd.report_path == str(tmp_path / "stall_report.json")
        leaked = [t for t in threading.enumerate()
                  if t.ident not in before
                  and t.name == "training-watchdog"]
        assert not leaked, "finalize did not stop the monitor thread"

    def test_stalled_step_reports_with_iteration(self, comm, tmp_path):
        trainer = _make_trainer(comm, tmp_path)
        reports = []
        wd = TrainingWatchdog(stall_timeout=0.3, check_interval=0.1,
                              on_stall=reports.append)
        trainer.extend(wd)

        @cmn.training.make_extension(trigger=(1, "iteration"), priority=5)
        def stall(tr):
            if tr.updater.iteration == 3:
                time.sleep(0.8)  # wedge one step past the threshold

        trainer.extend(stall)
        trainer.run()
        assert wd.stall_count == 1
        assert reports[0]["iteration"] == 3
        assert reports[0]["kind"] == "local-stall"
        report = json.load(open(tmp_path / "stall_report.json"))
        assert report["seconds_since_heartbeat"] > 0.3
