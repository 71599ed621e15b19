"""Flight recorder (utils/telemetry): ring-buffer bound, Chrome-trace
schema round-trip, multi-shard merge, disabled-path zero cost, the
trainer extensions (StragglerReport / MetricsExport), and the
failure-path contract — a FaultPlan delay-rank drill must produce a
stall report carrying the recorder's ring tail."""

import json
import time

import jax
import numpy as np
import optax
import pytest

import chainermn_tpu as cmn
from chainermn_tpu.extensions import TrainingWatchdog
from chainermn_tpu.models import init_mlp, mlp_apply, softmax_cross_entropy
from chainermn_tpu.testing import FaultInjector, FaultPlan
from chainermn_tpu.utils.telemetry import (
    MetricsExport,
    StragglerReport,
    TraceRecorder,
    get_recorder,
    merge_traces,
    set_recorder,
)


@pytest.fixture()
def recorder():
    """Fresh enabled recorder installed as the global one (the
    instrumented subsystems all record into get_recorder()); the
    previous global is restored afterwards."""
    rec = TraceRecorder(capacity=4096, enabled=True, rank=0)
    prev = set_recorder(rec)
    yield rec
    set_recorder(prev)


def _dataset(n=64, dim=6, classes=3, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(dim).astype(np.float32), np.int32(i % classes))
            for i in range(n)]


def _make_trainer(comm, out, epochs=2, **updater_kw):
    it = cmn.SerialIterator(_dataset(), 16, shuffle=True, seed=3)
    params = init_mlp(jax.random.PRNGKey(0), [6, 12, 3])
    opt = cmn.create_multi_node_optimizer(optax.sgd(0.05), comm)

    def loss_fn(p, x, y):
        return softmax_cross_entropy(mlp_apply(p, x), y)

    upd = cmn.StandardUpdater(it, opt, loss_fn, params, comm,
                              **updater_kw)
    return cmn.Trainer(upd, (epochs, "epoch"), out=str(out))


# ---------------------------------------------------------------------- #
# ring buffer
# ---------------------------------------------------------------------- #

class TestRing:
    def test_bound_enforced_oldest_dropped(self):
        rec = TraceRecorder(capacity=8, enabled=True, rank=0)
        for i in range(30):
            rec.record(f"ev{i}", 0.001)
        assert len(rec) == 8
        assert rec.dropped == 22
        names = [e["name"] for e in rec.events()]
        assert names == [f"ev{i}" for i in range(22, 30)]

    def test_tail_returns_newest(self):
        rec = TraceRecorder(capacity=100, enabled=True, rank=0)
        for i in range(10):
            rec.record(f"ev{i}", 0.001, step=i)
        tail = rec.tail(3)
        assert [e["name"] for e in tail] == ["ev7", "ev8", "ev9"]
        assert tail[-1]["step"] == 9
        # n <= 0 is the opt-out, not a whole-ring dump
        assert rec.tail(0) == [] and rec.tail(-1) == []

    def test_phase_stats_survive_ring_wrap(self):
        rec = TraceRecorder(capacity=4, enabled=True, rank=0)
        for _ in range(100):
            rec.record("phase", 0.01)
        stats = rec.drain_phase_stats()
        assert stats["phase"]["count"] == 100
        assert stats["phase"]["total_s"] == pytest.approx(1.0)
        # drained: the next interval starts clean
        assert rec.drain_phase_stats() == {}

    def test_phase_channels_independent_and_filtered(self):
        """open_phase_channel gives a consumer its own interval state:
        a name filter keeps it from accumulating spans it will never
        drain, and draining it leaves the default channel untouched."""
        rec = TraceRecorder(capacity=64, enabled=True, rank=0)
        rec.open_phase_channel("goodput", names=["step/dispatch"])
        rec.record("step/dispatch", 0.01)
        rec.record("prefetch/slot_wait", 0.5)
        mine = rec.drain_phase_stats(channel="goodput")
        assert list(mine) == ["step/dispatch"]     # filter held
        assert mine["step/dispatch"]["count"] == 1
        # the default channel still has BOTH intervals in full
        shared = rec.drain_phase_stats()
        assert shared["step/dispatch"]["count"] == 1
        assert shared["prefetch/slot_wait"]["count"] == 1
        # and the private channel's next interval starts clean
        assert rec.drain_phase_stats(channel="goodput") == {}

    def test_unknown_phase_channel_raises(self):
        rec = TraceRecorder(capacity=8, enabled=True, rank=0)
        with pytest.raises(KeyError):
            rec.drain_phase_stats(channel="typo")

    def test_bad_capacity_rejected(self):
        with pytest.raises(ValueError):
            TraceRecorder(capacity=0)

    def test_malformed_env_capacity_degrades_not_crashes(self,
                                                         monkeypatch):
        """A typo'd CHAINERMN_TPU_TRACE_CAPACITY runs at package import
        — it must fall back to the default, never break `import
        chainermn_tpu`."""
        from chainermn_tpu.utils import telemetry as T

        monkeypatch.setenv("CHAINERMN_TPU_TRACE_CAPACITY", "64k")
        assert T._from_env().capacity == 65536
        monkeypatch.setenv("CHAINERMN_TPU_TRACE_CAPACITY", "0")
        assert T._from_env().capacity == 65536
        monkeypatch.setenv("CHAINERMN_TPU_TRACE_CAPACITY", "128")
        assert T._from_env().capacity == 128


# ---------------------------------------------------------------------- #
# disabled path
# ---------------------------------------------------------------------- #

class TestDisabled:
    def test_span_returns_shared_singleton(self):
        """Zero allocation when disabled: every span() call hands back
        the SAME no-op object, and nothing reaches the ring."""
        rec = TraceRecorder(enabled=False)
        a = rec.span("x", cat="step", step=1, k=2)
        b = rec.span("y")
        assert a is b
        with a:
            pass
        rec.record("z", 1.0)
        rec.instant("i")
        rec.counter("c", 3)
        assert len(rec) == 0
        assert rec.drain_phase_stats() == {}
        # no per-thread stack either: that is a live span's
        assert not hasattr(rec._local, "stack")
        # metadata called ``name`` (trainer/extension) changes nothing
        assert rec.span("trainer/extension", name="LogReport") is a

    def test_enable_disable_toggle(self):
        rec = TraceRecorder(enabled=False)
        rec.enable()
        with rec.span("x"):
            pass
        rec.disable()
        with rec.span("y"):
            pass
        assert [e["name"] for e in rec.events()] == ["x"]

    @pytest.mark.parametrize("prefetch", [0, 2],
                             ids=["serial", "prefetch"])
    def test_trainer_and_updater_build_nothing(self, comm, tmp_path,
                                               monkeypatch, prefetch):
        """Disabled, a whole ``Trainer.run`` (the updater's spans, the
        feed's on either thread, the trainer's own) constructs no live
        span, no ``TraceAnnotation`` and no per-thread stack."""
        from chainermn_tpu.utils import telemetry

        def refuse(*a, **k):
            raise AssertionError("built on the disabled path")

        monkeypatch.setattr(telemetry._LiveSpan, "__init__", refuse)
        monkeypatch.setattr(telemetry, "_trace_annotation", refuse)
        rec = TraceRecorder(enabled=False)
        monkeypatch.setattr(rec, "_live_stack", refuse)
        prev = set_recorder(rec)
        try:
            trainer = _make_trainer(comm, tmp_path, epochs=1,
                                    prefetch=prefetch)
            trainer.extend(cmn.LogReport(trigger=(2, "iteration")))
            trainer.run()
        finally:
            set_recorder(prev)
        assert trainer.updater.iteration == 4
        assert len(rec) == 0 and not hasattr(rec._local, "stack")


# ---------------------------------------------------------------------- #
# a span knows what caused it
# ---------------------------------------------------------------------- #

class TestParent:
    def test_nested_span_records_parent_and_inherits_step(self):
        rec = TraceRecorder(enabled=True, rank=0)
        with rec.span("outer", step=7):
            with rec.span("inner"):
                with rec.span("innermost", step=9):
                    pass
            with rec.span("second"):
                pass
        with rec.span("alone"):
            pass
        by = {e["name"]: e for e in rec.events()}
        assert "parent" not in by["outer"] and "parent" not in by["alone"]
        assert by["inner"]["parent"] == ["outer", by["outer"]["t0"]]
        assert by["second"]["parent"] == ["outer", by["outer"]["t0"]]
        assert by["innermost"]["parent"] == ["inner", by["inner"]["t0"]]
        # step: inherited when given none, kept when given
        assert by["inner"]["step"] == 7 and by["second"]["step"] == 7
        assert by["innermost"]["step"] == 9
        assert "step" not in by["alone"]
        # the stack unwound
        assert rec._live_stack() == []

    def test_parent_is_per_thread(self):
        import threading

        rec = TraceRecorder(enabled=True, rank=0)
        inside = threading.Event()
        done = threading.Event()

        def other():
            inside.wait(5)
            with rec.span("worker", step=3):
                with rec.span("worker-child"):
                    pass
            done.set()

        th = threading.Thread(target=other)
        th.start()
        with rec.span("main", step=1):
            inside.set()
            assert done.wait(5)
            with rec.span("main-child"):
                pass
        th.join(5)
        assert not th.is_alive()
        by = {e["name"]: e for e in rec.events()}
        # a span live on another thread is nobody's parent here
        assert "parent" not in by["worker"] and by["worker"]["step"] == 3
        assert by["worker-child"]["parent"][0] == "worker"
        assert by["main-child"]["parent"][0] == "main"
        assert by["main-child"]["step"] == 1
        assert by["worker"]["tid"] != by["main"]["tid"]

    def test_span_unwinds_on_exception(self):
        rec = TraceRecorder(enabled=True, rank=0)
        with pytest.raises(StopIteration):
            with rec.span("outer"):
                with rec.span("inner"):
                    raise StopIteration
        assert rec._live_stack() == []
        with rec.span("after"):
            pass
        assert "parent" not in rec.events()[-1]

    def test_metadata_may_be_called_name(self):
        rec = TraceRecorder(enabled=True, rank=0)
        with rec.span("trainer/extension", name="LogReport"):
            pass
        assert rec.events()[0]["meta"] == {"name": "LogReport"}

    def test_self_time_is_span_less_children(self):
        """What ``parent`` is for: a layer's own time."""
        rec = TraceRecorder(enabled=True, rank=0)
        with rec.span("step/host", step=0):
            time.sleep(0.01)
            with rec.span("feed/put"):
                time.sleep(0.02)
        host, = [e for e in rec.events() if e["name"] == "step/host"]
        children = [e for e in rec.events()
                    if e.get("parent") == ["step/host", host["t0"]]]
        own = host["dur"] - sum(c["dur"] for c in children)
        assert [c["name"] for c in children] == ["feed/put"]
        assert 0.009 < own < host["dur"] - 0.019


# ---------------------------------------------------------------------- #
# export: Chrome trace schema + merge
# ---------------------------------------------------------------------- #

class TestExport:
    def test_chrome_schema_round_trip(self, tmp_path):
        rec = TraceRecorder(enabled=True, rank=3)
        with rec.span("step/host", cat="step", step=7, k=4):
            time.sleep(0.002)
        rec.instant("watchdog/heartbeat", cat="watchdog", step=7)
        rec.counter("prefetch/occupancy", 2)
        path = str(tmp_path / "trace.json")
        rec.export_chrome(path)

        doc = json.load(open(path))
        assert doc["displayTimeUnit"] == "ms"
        assert doc["metadata"]["rank"] == 3
        events = doc["traceEvents"]
        # lane labels: process_name metadata carries the rank mapping
        meta = [e for e in events if e["ph"] == "M"]
        assert any(e["name"] == "process_name"
                   and e["args"]["name"] == "rank 3" for e in meta)
        assert all(e["pid"] == 3 for e in events)
        by_name = {e["name"]: e for e in events if e["ph"] != "M"}
        span = by_name["step/host"]
        assert span["ph"] == "X" and span["cat"] == "step"
        assert span["dur"] >= 2e3          # microseconds
        assert span["args"]["step"] == 7 and span["args"]["k"] == 4
        assert by_name["watchdog/heartbeat"]["ph"] == "i"
        counter = by_name["prefetch/occupancy"]
        assert counter["ph"] == "C" and counter["args"]["value"] == 2.0
        # a counter recorded with a step keeps it alongside the value
        rec.counter("stepped", 5, step=9)
        stepped = [e for e in rec.chrome_events()
                   if e["name"] == "stepped"][0]
        assert stepped["args"] == {"step": 9, "value": 5.0}
        # ts is wall-anchored microseconds: recent, monotone-ish
        assert span["ts"] == pytest.approx(time.time() * 1e6, rel=0.01)

    def test_merge_traces_distinct_pids(self, tmp_path):
        paths = []
        for rank in range(3):
            rec = TraceRecorder(enabled=True, rank=rank)
            with rec.span("step/host", cat="step", step=1):
                pass
            p = str(tmp_path / f"trace.{rank}.json")
            rec.export_chrome(p)
            paths.append(p)
        out = str(tmp_path / "merged.json")
        doc = merge_traces(paths, out=out)
        assert json.load(open(out)) == doc
        pids = {e["pid"] for e in doc["traceEvents"]}
        assert pids == {0, 1, 2}
        # every rank's lane is labelled
        labels = {e["args"]["name"] for e in doc["traceEvents"]
                  if e["ph"] == "M" and e["name"] == "process_name"}
        assert labels == {"rank 0", "rank 1", "rank 2"}

    def test_merge_accepts_bare_event_array_shard(self, tmp_path):
        """The other standard Chrome form — a bare JSON event array
        (external tracers emit it) — must merge, not AttributeError."""
        rec = TraceRecorder(enabled=True, rank=0)
        with rec.span("ours"):
            pass
        p0 = str(tmp_path / "ours.json")
        rec.export_chrome(p0)
        p1 = str(tmp_path / "bare.json")
        with open(p1, "w") as f:
            json.dump([{"name": "theirs", "ph": "X", "ts": 1.0,
                        "dur": 2.0, "pid": 7, "tid": 0}], f)
        doc = merge_traces([p0, p1])
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"ours", "theirs"} <= names
        assert {e["pid"] for e in doc["traceEvents"]} == {0, 7}

    def test_merge_shifts_colliding_pids(self, tmp_path):
        paths = []
        for i in range(2):                 # both shards claim pid 0
            rec = TraceRecorder(enabled=True, rank=0)
            with rec.span(f"shard{i}"):
                pass
            p = str(tmp_path / f"t{i}.json")
            rec.export_chrome(p)
            paths.append(p)
        doc = merge_traces(paths)
        pids = {e["pid"] for e in doc["traceEvents"]}
        assert len(pids) == 2, "colliding shards must not overlay lanes"

    def test_export_tolerates_concurrent_appends(self):
        """Exports snapshot the ring: a recorder thread (prefetch
        worker, watchdog monitor) appending mid-export must never fault
        the export — the crash-dump path runs exactly while other
        threads are still alive and recording."""
        import threading

        rec = TraceRecorder(capacity=512, enabled=True, rank=0)
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                rec.record("bg", 0.001)

        th = threading.Thread(target=hammer, daemon=True)
        th.start()
        try:
            for _ in range(200):
                rec.chrome_events()
                rec.events()
                rec.tail(16)
        finally:
            stop.set()
            th.join()

    def test_jsonl_exports(self, tmp_path):
        stream = str(tmp_path / "live.jsonl")
        rec = TraceRecorder(enabled=True, rank=0, stream_path=stream)
        with rec.span("a", cat="step"):
            pass
        rec.instant("b")
        rec.close()
        live = [json.loads(l) for l in open(stream)]
        assert [e["name"] for e in live] == ["a", "b"]
        dumped = str(tmp_path / "dump.jsonl")
        rec.export_jsonl(dumped)
        again = [json.loads(l) for l in open(dumped)]
        assert [e["name"] for e in again] == ["a", "b"]
        # close() ends the stream for good: a straggler thread's event
        # after close must not silently reopen the file
        rec.instant("after-close")
        assert len(open(stream).readlines()) == 2


# ---------------------------------------------------------------------- #
# instrumentation: the stack records into the recorder
# ---------------------------------------------------------------------- #

class TestInstrumentation:
    def test_updater_step_phases_recorded(self, comm, recorder,
                                          tmp_path):
        trainer = _make_trainer(comm, tmp_path, epochs=1)
        trainer.run()
        names = {e["name"] for e in recorder.events()}
        assert {"step/host", "step/dispatch", "step/retire"} <= names
        cats = {e["cat"] for e in recorder.events()}
        assert "step" in cats

    def test_prefetch_spans_and_occupancy(self, comm, recorder,
                                          tmp_path):
        trainer = _make_trainer(comm, tmp_path, epochs=1, prefetch=2)
        trainer.run()
        names = {e["name"] for e in recorder.events()}
        assert {"prefetch/slot_wait", "feed/pull", "feed/convert",
                "feed/put", "prefetch/occupancy"} <= names
        # worker-side spans carry the worker's tid, consumer spans the
        # main thread's — the trace separates the two lanes
        tid_of = {}
        for e in recorder.events():
            tid_of.setdefault(e["name"], set()).add(e.get("tid"))
        for worker_span in ("feed/pull", "feed/convert", "feed/put"):
            assert tid_of[worker_span].isdisjoint(
                tid_of["prefetch/slot_wait"])
        # the consumer's wait is the step/host span's child and shares
        # its step; the worker's spans have no parent on their thread
        by_name = {}
        for e in recorder.events():
            by_name.setdefault(e["name"], []).append(e)
        assert all(e["parent"][0] == "step/host" and "step" in e
                   for e in by_name["prefetch/slot_wait"])
        assert all("parent" not in e for e in by_name["feed/put"])

    @pytest.mark.parametrize("prefetch", [0, 2],
                             ids=["serial", "prefetch"])
    def test_both_feeds_emit_the_same_feed_spans(self, comm, recorder,
                                                 tmp_path, prefetch):
        """One window contract, one set of names: each batch is one
        ``feed/pull`` and one ``feed/convert``, each window one
        ``feed/put``, whichever thread did the work."""
        trainer = _make_trainer(comm, tmp_path, epochs=1,
                                prefetch=prefetch)
        trainer.run()
        events = recorder.events()
        count = {n: sum(1 for e in events if e["name"] == n)
                 for n in ("feed/pull", "feed/convert", "feed/put",
                           "step/host")}
        # 64 examples in batches of 16: four windows; a prefetching
        # worker may have pulled ahead of the stop
        assert count["step/host"] == 4
        assert count["feed/put"] >= 4
        assert count["feed/convert"] == count["feed/put"]
        assert count["feed/pull"] >= count["feed/convert"]
        put = next(e for e in events if e["name"] == "feed/put")
        pull = next(e for e in events if e["name"] == "feed/pull")
        conv = next(e for e in events if e["name"] == "feed/convert")
        assert pull["meta"]["n"] == 16
        assert conv["meta"]["bytes"] == 16 * (6 * 4 + 4)
        assert put["meta"] == {"k": 1, "bytes": 16 * (6 * 4 + 4)}
        # the worker's old names for the same work are gone
        assert {e["name"] for e in events if e["name"].startswith(
            "prefetch/")} <= {"prefetch/slot_wait", "prefetch/occupancy"}

    def test_serial_feed_spans_lie_under_step_host(self, comm, recorder,
                                                   tmp_path):
        trainer = _make_trainer(comm, tmp_path, epochs=1)
        trainer.run()
        events = recorder.events()
        hosts = {(e["name"], e["t0"]): e for e in events
                 if e["name"] == "step/host"}
        main_tid = next(iter(hosts.values()))["tid"]
        for name in ("feed/pull", "feed/convert", "feed/put"):
            found = [e for e in events if e["name"] == name
                     and e["tid"] == main_tid]
            assert len(found) == 4, name
            for e in found:
                host = hosts[tuple(e["parent"])]
                assert e["step"] == host["step"]
                assert host["t0"] <= e["t0"] \
                    and e["t0"] + e["dur"] <= host["t0"] + host["dur"]
        # the three children leave the span little of its own
        host = next(iter(hosts.values()))
        inside = sum(e["dur"] for e in events
                     if e.get("parent") == ["step/host", host["t0"]])
        assert 0 < inside <= host["dur"]

    def test_fused_window_pulls_k_batches_under_one_put(
            self, comm, recorder, tmp_path):
        trainer = _make_trainer(comm, tmp_path, epochs=1,
                                steps_per_execution=2)
        trainer.run()
        events = recorder.events()
        puts = [e for e in events if e["name"] == "feed/put"]
        assert [e["meta"]["k"] for e in puts] == [2, 2]
        assert [e["step"] for e in puts] == [0, 2]
        pulls = [e for e in events if e["name"] == "feed/pull"]
        assert [e["step"] for e in pulls[:4]] == [0, 0, 2, 2]

    def test_retire_names_the_window_it_blocked_on(self, comm, recorder,
                                                   tmp_path):
        """Serial updater, one window in flight: the first retire blocks
        on nothing, each later one on its predecessor's window."""
        trainer = _make_trainer(comm, tmp_path, epochs=1)
        trainer.run()
        retires = [e for e in recorder.events()
                   if e["name"] == "step/retire"]
        assert [e["step"] for e in retires] == [0, 1, 2, 3]
        assert [e["meta"]["retired"] for e in retires] == [None, 0, 1, 2]

    def test_retire_under_two_in_flight(self, comm, recorder, tmp_path):
        trainer = _make_trainer(comm, tmp_path, epochs=1, max_inflight=2)
        trainer.run()
        retires = [e for e in recorder.events()
                   if e["name"] == "step/retire"]
        assert [e["meta"]["retired"] for e in retires] == \
            [None, None, 0, 1]

    def test_trainer_observe_once_per_iteration(self, comm, recorder,
                                                tmp_path):
        trainer = _make_trainer(comm, tmp_path, epochs=1)
        trainer.extend(cmn.LogReport(trigger=(2, "iteration")))
        trainer.run()
        observes = [e for e in recorder.events()
                    if e["name"] == "trainer/observe"]
        assert [e["step"] for e in observes] == [0, 1, 2, 3]
        assert all(e["cat"] == "trainer" and "parent" not in e
                   for e in observes)
        # it follows its own iteration's dispatch and retire
        for obs in observes:
            retire = next(e for e in recorder.events()
                          if e["name"] == "step/retire"
                          and e["step"] == obs["step"])
            assert obs["t0"] >= retire["t0"] + retire["dur"]

    def test_trainer_extension_once_per_fired_extension(
            self, comm, recorder, tmp_path):
        trainer = _make_trainer(comm, tmp_path, epochs=1)
        trainer.extend(cmn.LogReport(trigger=(2, "iteration")))
        seen = []
        trainer.extend(lambda t: seen.append(t.updater.iteration),
                       trigger=(1, "iteration"), name="every")
        trainer.run()
        fired = [(e["meta"]["name"], e["step"])
                 for e in recorder.events()
                 if e["name"] == "trainer/extension"]
        # step is the iteration update() began at: LogReport (priority
        # 50, after the default 100) fires after the updates that began
        # at 1 and 3
        assert fired == [("every", 0), ("every", 1), ("LogReport", 1),
                         ("every", 2), ("every", 3), ("LogReport", 3)]
        assert seen == [1, 2, 3, 4]

    def test_spans_on_the_profilers_host_plane(self, comm, recorder,
                                               tmp_path):
        """Under a ``jax.profiler`` trace with the host tracer on, the
        program's spans lie on the profiler's own clock: found on the
        host plane under their names, with their ``step``."""
        import glob

        from jax.profiler import ProfileData

        trainer = _make_trainer(comm, tmp_path / "out", epochs=1)
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = 1
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path / "trace"),
                                 profiler_options=options)
        try:
            trainer.run()
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(str(
            tmp_path / "trace/plugins/profile/*/*.xplane.pb"))
        found = {}
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in ("step/host", "feed/put",
                                   "trainer/observe"):
                        stats = {k: v for k, v in ev.stats}
                        found.setdefault(ev.name, []).append(
                            stats.get("step"))
        assert sorted(found["step/host"]) == [0, 1, 2, 3]
        assert sorted(found["feed/put"]) == [0, 1, 2, 3]
        assert sorted(found["trainer/observe"]) == [0, 1, 2, 3]

    def test_checkpoint_spans_recorded(self, comm, recorder, tmp_path):
        from chainermn_tpu.utils.serialization import (load_state,
                                                       save_state)

        path = str(tmp_path / "snap")
        save_state(path, {"a": np.arange(8), "b": np.float32(3.0)})
        load_state(path)
        names = [e["name"] for e in recorder.events()]
        assert "checkpoint/save" in names and "checkpoint/load" in names
        save_ev = next(e for e in recorder.events()
                       if e["name"] == "checkpoint/save")
        assert save_ev["meta"]["n_leaves"] == 2
        assert save_ev["meta"]["nbytes"] > 0

    def test_profiled_communicator_records_comm_spans(self, comm,
                                                      recorder):
        from chainermn_tpu.utils.profiling import (Profiler,
                                                   profiled_communicator)

        pc = profiled_communicator(comm, Profiler())
        pc.bcast_obj({"x": 1})
        spans = [e for e in recorder.events() if e["cat"] == "comm"]
        assert spans and spans[0]["name"] == "comm.bcast_obj"

    def test_watchdog_heartbeat_instants(self, recorder):
        wd = TrainingWatchdog(stall_timeout=60)
        wd.heartbeat(iteration=5)
        ev = recorder.events()[-1]
        assert ev["name"] == "watchdog/heartbeat"
        assert ev["ph"] == "i" and ev["step"] == 5


# ---------------------------------------------------------------------- #
# failure paths
# ---------------------------------------------------------------------- #

class TestFailurePaths:
    def test_stall_report_embeds_ring_tail_under_delay_drill(
            self, comm, recorder, tmp_path):
        """The acceptance drill: a FaultPlan delay-rank stall past the
        watchdog threshold must produce a stall report whose
        ``trace_tail`` carries the flight recorder's timeline of the
        steps leading up to the stall."""
        trainer = _make_trainer(comm, tmp_path, epochs=2)
        reports = []
        wd = TrainingWatchdog(stall_timeout=0.3, check_interval=0.1,
                              on_stall=reports.append)
        trainer.extend(wd)
        plan = FaultPlan(delay_at_iteration=3, delay_rank=0,
                         delay_seconds=0.8)
        injector = FaultInjector(plan, comm=comm)
        trainer.extend(injector)
        trainer.run()

        assert ("delay", 3) in injector.fired
        assert wd.stall_count >= 1
        rep = reports[0]
        assert rep["kind"] == "local-stall"
        assert rep["trace_enabled"] is True
        tail = rep["trace_tail"]
        assert tail, "stall report carried no flight-recorder tail"
        tail_names = {e["name"] for e in tail}
        # the tail shows the step phases that ran BEFORE the stall —
        # the timeline half of the post-mortem
        assert {"step/host", "step/retire"} & tail_names
        assert {"watchdog/heartbeat"} & tail_names
        # and the on-disk report carries it too
        on_disk = json.load(open(tmp_path / "stall_report.json"))
        assert on_disk["trace_tail"]

    def test_stall_report_tail_empty_when_disabled(self, tmp_path):
        prev = set_recorder(TraceRecorder(enabled=False))
        try:
            reports = []
            wd = TrainingWatchdog(stall_timeout=0.15, check_interval=0.05,
                                  on_stall=reports.append,
                                  report_path=str(tmp_path / "s.json"))
            wd.start()
            try:
                wd.heartbeat(iteration=1)
                deadline = time.monotonic() + 0.8
                while not reports and time.monotonic() < deadline:
                    time.sleep(0.02)
            finally:
                wd.stop()
            assert reports and reports[0]["trace_tail"] == []
            assert reports[0]["trace_enabled"] is False
        finally:
            set_recorder(prev)

    def test_except_hook_dumps_trace(self, recorder, tmp_path,
                                     monkeypatch):
        from chainermn_tpu.extensions import global_except_hook as geh

        with recorder.span("step/host", cat="step", step=1):
            pass
        # a not-yet-existing directory is created, not silently skipped
        monkeypatch.setenv("CHAINERMN_TPU_TRACE_DIR",
                           str(tmp_path / "made" / "later"))
        geh._dump_trace(rank=0)
        doc = json.load(
            open(tmp_path / "made" / "later" / "trace_crash.rank0.json"))
        assert any(e.get("name") == "step/host"
                   for e in doc["traceEvents"])

    def test_add_hook_preserves_trace_dir(self, monkeypatch):
        from chainermn_tpu.extensions import global_except_hook as geh
        from chainermn_tpu.extensions import add_global_except_hook

        monkeypatch.setattr(geh, "_installed", True)  # don't touch sys
        monkeypatch.setattr(geh, "_trace_dir", ".")
        add_global_except_hook(trace_dir="/logs/traces")
        assert geh._trace_dir == "/logs/traces"
        add_global_except_hook()   # a later no-arg call must not clobber
        assert geh._trace_dir == "/logs/traces"


# ---------------------------------------------------------------------- #
# trainer extensions
# ---------------------------------------------------------------------- #

class TestStragglerReport:
    def test_trainer_run_observes_skew(self, comm, recorder, tmp_path):
        trainer = _make_trainer(comm, tmp_path, epochs=1)
        sr = StragglerReport(comm)
        trainer.extend(sr, trigger=(1, "epoch"))
        trainer.run()
        assert sr.last_report is not None
        assert sr.last_report["max_skew"] >= 1.0
        assert "step/host" in sr.last_report["phases"]
        # single process: perfectly balanced by construction
        assert sr.last_report["max_skew"] == pytest.approx(1.0)
        # rank 0 writes the jsonl attribution series
        lines = open(tmp_path / "straggler.jsonl").read().splitlines()
        assert json.loads(lines[-1])["phases"]

    def test_cross_rank_attribution_math(self, recorder):
        """Slowest rank + skew per phase, with divergent key sets (the
        ObservationAggregator convention): aggregate over reporting
        ranks only."""

        class FakeComm:
            inter_rank = 0

            def allgather_obj(self, obj):
                # rank 0 = obj (drained from the live recorder),
                # rank 1 twice as slow, rank 2 missing one phase
                return [
                    {"step/host": 0.1, "step/retire": 0.2},
                    {"step/host": 0.2, "step/retire": 0.2},
                    {"step/retire": 0.2},
                ]

        sr = StragglerReport(FakeComm(), recorder=recorder, write=False)
        sr()
        host = sr.last_report["phases"]["step/host"]
        assert host["slowest_rank"] == 1
        assert host["skew"] == pytest.approx(0.2 / 0.15)
        assert host["ranks"] == 2
        retire = sr.last_report["phases"]["step/retire"]
        assert retire["skew"] == pytest.approx(1.0)
        assert retire["ranks"] == 3
        assert sr.last_report["max_skew"] == pytest.approx(0.2 / 0.15)

    def test_per_phase_tail_percentiles(self, recorder):
        """Phases gain p50/p99 from the shared metrics lattice — the
        drained stats carry per-phase histograms, ranks' histograms
        bucket-sum, and tail skew attributes the worst p99 to a rank
        (exact here: the sample counts sit under the histogram cap)."""
        durations = [0.001 * (1 + i % 10) for i in range(200)]
        for d in durations:
            recorder.record("step/host", d)

        class FakeComm:
            inter_rank = 0

            def allgather_obj(self, obj):
                # rank 1 reports an identical distribution: merged
                # percentiles equal the local ones and tail skew is 1
                return [obj, obj]

        sr = StragglerReport(FakeComm(), recorder=recorder, write=False)
        sr()
        host = sr.last_report["phases"]["step/host"]
        assert host["p50_s"] == pytest.approx(
            float(np.percentile(durations, 50)), rel=1e-9)
        assert host["p99_s"] == pytest.approx(
            float(np.percentile(durations, 99)), rel=1e-9)
        assert host["slowest_rank_p99"] in (0, 1)
        assert host["skew_p99"] == pytest.approx(1.0)
        # means/skew attribution unchanged alongside the tails
        assert host["skew"] == pytest.approx(1.0)

    def test_tail_skew_attributes_slow_rank(self, recorder):
        """A rank whose distribution has the same mean but a heavier
        tail is exactly what the mean-based skew misses and the p99
        skew catches."""
        from chainermn_tpu.utils.metrics import Histogram

        recorder.record("step/host", 0.01)

        def row(vals):
            h = Histogram()
            for v in vals:
                h.observe(v)
            return {"step/host": {
                "mean": sum(vals) / len(vals), "hist": h.to_snapshot()}}

        balanced = [0.01] * 100
        # same 0.01 mean, but 2% of the samples at 10x: the rank's own
        # p99 lands on the 0.1 s tail while the merged fleet p99 (tail
        # mass diluted to 1%) stays near 0.01 s
        heavy = [0.8 / 98] * 98 + [0.1] * 2

        class FakeComm:
            inter_rank = 0

            def allgather_obj(self, obj):
                return [row(balanced), row(heavy)]

        sr = StragglerReport(FakeComm(), recorder=recorder, write=False)
        sr()
        host = sr.last_report["phases"]["step/host"]
        assert host["skew"] == pytest.approx(1.0, abs=1e-6)
        assert host["slowest_rank_p99"] == 1
        assert host["skew_p99"] > 1.5

    def test_phase_filter_drains_only_its_names(self, recorder):
        class FakeComm:
            inter_rank = 0

            def allgather_obj(self, obj):
                return [obj]

        recorder.record("step/host", 0.1)
        recorder.record("prefetch/slot_wait", 0.5)
        sr = StragglerReport(FakeComm(), recorder=recorder,
                             phases=["step/host"], write=False)
        sr()
        assert list(sr.last_report["phases"]) == ["step/host"]
        # the filtered-out phase still accumulates for OTHER consumers
        # (a second report with a disjoint filter, a later drain)
        left = recorder.drain_phase_stats()
        assert "prefetch/slot_wait" in left
        assert "step/host" not in left


class TestMetricsExport:
    def test_appends_jsonl_series(self, comm, tmp_path):
        trainer = _make_trainer(comm, tmp_path, epochs=2)
        trainer.extend(MetricsExport())
        trainer.run()
        lines = [json.loads(l)
                 for l in open(tmp_path / "metrics.jsonl")]
        assert len(lines) == trainer.updater.iteration
        assert lines[-1]["iteration"] == trainer.updater.iteration
        for entry in lines:
            assert {"iteration", "epoch", "elapsed_time", "ts",
                    "main/loss", "main/step_time"} <= set(entry)
        # append-only across runs: a second trainer continues the file
        trainer2 = _make_trainer(comm, tmp_path, epochs=1)
        trainer2.extend(MetricsExport())
        trainer2.run()
        more = open(tmp_path / "metrics.jsonl").read().splitlines()
        assert len(more) > len(lines)

    def test_keys_filter(self, comm, tmp_path):
        trainer = _make_trainer(comm, tmp_path, epochs=1)
        trainer.extend(MetricsExport(keys=["main/loss"]))
        trainer.run()
        entry = json.loads(
            open(tmp_path / "metrics.jsonl").readline())
        assert "main/loss" in entry
        assert "main/step_time" not in entry


class TestMergeTraceDiscovery:
    """PR 7 satellite: merge_traces accepts a directory or glob and
    sorts shards by recorded rank BEFORE pid assignment, so the same
    shard set always yields the same Perfetto lanes regardless of
    filesystem listing order."""

    def _shards(self, tmp_path, ranks):
        for i, rank in enumerate(ranks):
            rec = TraceRecorder(enabled=True, rank=rank)
            with rec.span(f"work.{rank}", cat="step"):
                pass
            # file names deliberately NOT in rank order
            rec.export_chrome(str(tmp_path / f"shard_{i}.json"))

    def test_directory_input_sorts_by_rank(self, tmp_path):
        self._shards(tmp_path, [2, 0, 1])
        doc = merge_traces(str(tmp_path))
        ranks = [m["rank"] for m in doc["metadata"]["merged_from"]]
        assert ranks == [0, 1, 2]
        assert {e["pid"] for e in doc["traceEvents"]} == {0, 1, 2}

    def test_glob_input_matches_directory(self, tmp_path):
        self._shards(tmp_path, [1, 0])
        via_glob = merge_traces(str(tmp_path / "shard_*.json"))
        via_dir = merge_traces(str(tmp_path))
        assert via_glob["traceEvents"] == via_dir["traceEvents"]

    def test_colliding_pids_shift_deterministically(self, tmp_path):
        """Two rankless same-pid shards: the basename-sorted SECOND
        one is shifted, however the paths are listed."""
        for name in ("zzz.json", "aaa.json"):
            with open(tmp_path / name, "w") as f:
                json.dump([{"name": name, "ph": "X", "ts": 1.0,
                            "dur": 1.0, "pid": 5, "tid": 0}], f)
        doc = merge_traces([str(tmp_path / "zzz.json"),
                            str(tmp_path / "aaa.json")])
        by_name = {e["name"]: e["pid"] for e in doc["traceEvents"]}
        assert by_name == {"aaa.json": 5, "zzz.json": 6}

    def test_explicit_sequence_still_rank_sorted(self, tmp_path):
        self._shards(tmp_path, [1, 0])
        paths = [str(tmp_path / "shard_0.json"),   # rank 1 first
                 str(tmp_path / "shard_1.json")]
        doc = merge_traces(paths)
        ranks = [m["rank"] for m in doc["metadata"]["merged_from"]]
        assert ranks == [0, 1]

    def test_empty_glob_or_missing_dir_raises(self, tmp_path):
        """A typo'd glob or missing directory must not succeed with an
        empty merged document."""
        with pytest.raises(FileNotFoundError, match="no trace shards"):
            merge_traces(str(tmp_path / "rnk*.json"))
        with pytest.raises(FileNotFoundError, match="no trace shards"):
            merge_traces(str(tmp_path / "does-not-exist"))


class TestRequestTraceStore:
    """PR 13: tail-based retention of per-request causal traces — the
    trace half of the exemplar link."""

    def _trace(self, tid, status="ok", e2e=0.05, spans=None):
        return {"trace_id": tid, "rid": f"r-{tid}", "status": status,
                "e2e": e2e,
                "spans": spans if spans is not None else
                [{"name": "prefill", "t0": 0.0, "dur": 0.01}]}

    def test_non_ok_always_kept_ok_dropped_at_rate_zero(self):
        from chainermn_tpu.utils.telemetry import RequestTraceStore

        store = RequestTraceStore(capacity=16, sample_rate=0.0)
        assert store.offer(self._trace("a", status="timeout"))
        assert store.offer(self._trace("b", status="shed"))
        assert not store.offer(self._trace("c", status="ok"))
        assert store.get("a")["status"] == "timeout"
        assert store.get("c") is None
        assert store.snapshot()["offered"] == 3
        assert store.snapshot()["kept"] == 2

    def test_slo_violating_ok_kept(self):
        from chainermn_tpu.utils.telemetry import RequestTraceStore

        store = RequestTraceStore(capacity=16, sample_rate=0.0,
                                  slo_e2e=0.1)
        assert store.offer(self._trace("slow", e2e=0.5))
        assert not store.offer(self._trace("fast", e2e=0.05))
        tr = store.get("slow")
        assert tr["slo_violated"] is True

    def test_sampling_is_deterministic_and_near_rate(self):
        from chainermn_tpu.utils.telemetry import RequestTraceStore

        store = RequestTraceStore(capacity=4096, sample_rate=0.3)
        ids = [f"trace-{i}" for i in range(2000)]
        picks = [store.would_sample(t) for t in ids]
        assert picks == [store.would_sample(t) for t in ids]  # stable
        frac = sum(picks) / len(picks)
        assert 0.25 < frac < 0.35
        # rate 1.0 keeps everything, 0.0 nothing
        assert RequestTraceStore(sample_rate=1.0).would_sample("x")
        assert not RequestTraceStore(sample_rate=0.0).would_sample("x")

    def test_capacity_bound_drops_oldest(self):
        from chainermn_tpu.utils.telemetry import RequestTraceStore

        store = RequestTraceStore(capacity=3, sample_rate=0.0)
        for i in range(5):
            store.offer(self._trace(f"t{i}", status="timeout"))
        assert len(store) == 3
        assert store.get("t0") is None and store.get("t1") is None
        assert [t["trace_id"] for t in store.traces()] \
            == ["t2", "t3", "t4"]
        assert [t["trace_id"] for t in store.traces(2)] == ["t3", "t4"]

    def test_chrome_export_merges_with_recorder_shards(self, tmp_path):
        from chainermn_tpu.utils.telemetry import RequestTraceStore

        store = RequestTraceStore(capacity=8, sample_rate=0.0, rank=0)
        store.offer(self._trace(
            "victim", status="timeout",
            spans=[{"name": "prefill", "t0": 1.0, "dur": 0.02},
                   {"name": "decode_round", "t0": 1.1, "dur": 0.01},
                   {"name": "timeout", "t0": 1.2, "dur": 0.0}]))
        doc = store.to_chrome()
        names = [e.get("name") for e in doc["traceEvents"]]
        assert {"prefill", "decode_round", "timeout"} <= set(names)
        # every span event carries its trace id for Perfetto search
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert all(e["args"]["trace_id"] == "victim" for e in spans)
        # merge-compatible with a recorder shard: one fused document
        rec = TraceRecorder(enabled=True, rank=0)
        with rec.span("serve/decode_round", cat="serve"):
            pass
        p1 = str(tmp_path / "engine.json")
        p2 = str(tmp_path / "requests.json")
        rec.export_chrome(p1)
        store.export_chrome(p2)
        merged = merge_traces([p1, p2])
        merged_names = [e.get("name") for e in merged["traceEvents"]]
        assert "serve/decode_round" in merged_names
        assert "timeout" in merged_names
        # same-rank shards get distinct pid lanes (no overlay)
        pid_shifts = [m["pid_shift"]
                      for m in merged["metadata"]["merged_from"]]
        assert pid_shifts[1] > 0

    def test_single_trace_chrome_export(self):
        from chainermn_tpu.utils.telemetry import RequestTraceStore

        store = RequestTraceStore(capacity=8, sample_rate=0.0)
        store.offer(self._trace("a", status="timeout"))
        store.offer(self._trace("b", status="timeout"))
        doc = store.to_chrome("a")
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len(spans) == 1
        assert doc["metadata"]["request_traces"] == 1
        # an exemplar can outlive its trace (capacity eviction):
        # the export degrades to an empty document, never raises
        doc = store.to_chrome("evicted-id")
        assert doc["metadata"]["request_traces"] == 0

    def test_validation(self):
        from chainermn_tpu.utils.telemetry import RequestTraceStore

        with pytest.raises(ValueError):
            RequestTraceStore(capacity=0)
        with pytest.raises(ValueError):
            RequestTraceStore(sample_rate=1.5)
