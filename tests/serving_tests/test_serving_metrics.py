"""Serving metrics & SLO surface: the engine's request records carry
the derived latency fields, the ``serve/*`` registry wiring records at
the points that hold the timestamps, and ``SLOReport`` percentiles
over a run reproduce raw numpy within rounding (the equivalence
``bench_serving``'s dedup leans on)."""

import numpy as np
import pytest

from chainermn_tpu.serving import ServingEngine, SLOReport
from chainermn_tpu.utils.metrics import (
    Histogram,
    MetricsRegistry,
    set_registry,
)


@pytest.fixture(scope="module")
def engine(mini_adapter, mini_params):
    return ServingEngine(mini_adapter, mini_params, n_slots=8,
                         horizon=160, max_prompt=16, block=8,
                         round_tokens=4)


@pytest.fixture()
def registry():
    reg = MetricsRegistry(enabled=True)
    prev = set_registry(reg)
    yield reg
    set_registry(prev)


def _run_trace(engine, rng, n=12):
    engine.reset()
    for _ in range(n):
        prompt = rng.randint(0, 64, rng.randint(2, 12))
        engine.submit(prompt, max_new=int(rng.randint(4, 16)))
    comps = engine.run(max_steps=2000)
    assert len(comps) == n
    return comps


class TestRequestRecords:
    def test_records_expose_derived_fields(self, engine):
        comps = _run_trace(engine, np.random.RandomState(0))
        recs = engine.request_records()
        assert [r.rid for r in recs] == [c.rid for c in comps]
        for r in recs:
            assert r.queue_wait == r.t_admit - r.t_submit >= 0
            assert r.ttft == r.t_first - r.t_submit > 0
            assert r.e2e == r.t_done - r.t_submit >= r.ttft
            assert r.tpot == (r.t_done - r.t_first) \
                / max(r.n_generated - 1, 1) >= 0

    def test_reset_clears_records(self, engine):
        _run_trace(engine, np.random.RandomState(1), n=4)
        assert len(engine.request_records()) == 4
        engine.reset()
        assert engine.request_records() == []

    def test_record_history_bounded(self, mini_adapter, mini_params):
        """A long-running server must not grow the completion list
        without bound: the ring keeps the newest record_history."""
        eng = ServingEngine(mini_adapter, mini_params, n_slots=8,
                            horizon=160, max_prompt=16, block=8,
                            round_tokens=4, record_history=5)
        comps = _run_trace(eng, np.random.RandomState(6), n=8)
        recs = eng.request_records()
        assert len(recs) == 5
        assert [r.rid for r in recs] == [c.rid for c in comps[-5:]]


class TestRegistryWiring:
    def test_serve_metrics_recorded_at_lifecycle_points(self, engine,
                                                        registry):
        n = 10
        _run_trace(engine, np.random.RandomState(2), n=n)
        snap = engine.metrics_snapshot()
        assert snap["serve/submitted"]["value"] == n
        assert snap["serve/admits"]["value"] == n
        assert snap["serve/evictions"]["value"] == n
        for name in ("serve/queue_wait", "serve/ttft", "serve/tpot",
                     "serve/e2e"):
            assert snap[name]["type"] == "histogram"
            assert snap[name]["count"] == n, name
        # histograms hold the SAME numbers the request records derive
        recs = engine.request_records()
        h = Histogram.from_snapshot(snap["serve/ttft"])
        assert h.percentile(50) == pytest.approx(
            float(np.percentile([r.ttft for r in recs], 50)))
        assert snap["serve/generated_tokens"]["value"] \
            == sum(r.n_generated for r in recs)
        # queue depth gauge saw the initial burst
        assert snap["serve/queue_depth"]["max"] >= 1

    def test_disabled_registry_records_nothing_but_records_live(
            self, engine):
        comps = _run_trace(engine, np.random.RandomState(3), n=4)
        assert engine.metrics_snapshot() == {}
        assert len(engine.request_records()) == len(comps) == 4


class TestSLOReport:
    def test_percentiles_reproduce_numpy(self, engine):
        comps = _run_trace(engine, np.random.RandomState(4), n=16)
        slo = SLOReport(percentiles=(50, 95, 99))
        slo.add_arm("run", engine.request_records())
        s = slo.summary()["run"]
        for field in ("queue_wait", "ttft", "tpot", "e2e"):
            vals = [getattr(c, field) for c in comps]
            assert s[field]["count"] == len(vals)
            for q in (50, 95, 99):
                assert s[field][f"p{q}"] == pytest.approx(
                    float(np.percentile(vals, q)), rel=1e-9), \
                    (field, q)

    def test_multi_arm_render_and_json(self, engine, tmp_path):
        slo = SLOReport(percentiles=(50, 99))
        records = {}
        _run_trace(engine, np.random.RandomState(5), n=6)
        records["continuous"] = engine.request_records()
        slo.add_arm("continuous", records["continuous"])
        engine.gang = True
        try:
            _run_trace(engine, np.random.RandomState(5), n=6)
        finally:
            engine.gang = False
        records["static"] = engine.request_records()
        slo.add_arm("static", records["static"])
        assert slo.arms == ("continuous", "static")
        table = slo.render()
        for token in ("continuous", "static", "ttft", "p99_ms"):
            assert token in table
        import json

        path = slo.write_json(str(tmp_path / "slo.json"))
        doc = json.load(open(path))
        assert set(doc["arms"]) == {"continuous", "static"}
        # each arm reports ITS OWN six records and nothing of the
        # other's.  (Which arm waited longer is the host's clock on six
        # requests that all fit the eight slots at once: not asserted.)
        for arm, recs in records.items():
            for field in ("queue_wait", "ttft"):
                got = doc["arms"][arm][field]
                assert got["count"] == 6
                assert got["p50"] == pytest.approx(float(np.percentile(
                    [getattr(r, field) for r in recs], 50)), rel=1e-9)

    def test_dict_records_accepted(self):
        slo = SLOReport(percentiles=(50,))
        slo.add_arm("a", [{"queue_wait": 0.1, "ttft": 0.2,
                           "tpot": 0.01, "e2e": 0.5}])
        assert slo.summary()["a"]["e2e"]["p50"] == pytest.approx(0.5)


class TestSLOSkipsAndAttainment:
    """Shed and timed-out records have no TTFT (or none of the latency
    fields at all): the report must skip-count them per arm — never
    observe a None — and the SLO column must score goodput from
    fully-served records only."""

    def test_none_and_missing_fields_skip_counted(self):
        from chainermn_tpu.serving import ShedCompletion

        served = [{"queue_wait": 0.01, "ttft": 0.1 * (i + 1),
                   "tpot": 0.01, "e2e": 0.2 * (i + 1)}
                  for i in range(4)]
        timed_out = {"queue_wait": 0.01, "ttft": None, "tpot": None,
                     "e2e": 0.9, "status": "timeout"}
        shed = ShedCompletion("s0", np.zeros(2, np.int32),
                              "queue_full", 0.0, 0.1)
        slo = SLOReport(percentiles=(50,))
        slo.add_arm("mix", served + [timed_out, shed])
        s = slo.summary()["mix"]
        # percentiles over the PRESENT values only, numpy-identical
        assert s["ttft"]["count"] == 4
        assert s["ttft"]["p50"] == pytest.approx(float(np.percentile(
            [r["ttft"] for r in served], 50)))
        assert s["e2e"]["count"] == 5       # timeout rows have e2e
        # the skips are REPORTED, per field
        assert s["skipped"] == {"queue_wait": 1, "ttft": 2,
                                "tpot": 2, "e2e": 1}
        assert slo.skipped("mix")["ttft"] == 2

    def test_partial_completion_properties_skip_not_raise(self):
        """An engine Completion evicted before its first token has
        t_admit/t_first None — its derived properties must read as
        None (skipped), not raise out of the report."""
        from chainermn_tpu.serving import Completion

        c = Completion(rid="r", prompt=np.zeros(2, np.int32),
                       tokens=np.zeros(0, np.int32), t_submit=1.0,
                       t_admit=None, t_first=None, t_done=2.0,
                       slot=0, status="timeout")
        assert c.queue_wait is None and c.ttft is None \
            and c.tpot is None
        assert c.e2e == pytest.approx(1.0)
        slo = SLOReport(percentiles=(50,))
        slo.add_arm("a", [c])
        assert slo.summary()["a"]["skipped"]["ttft"] == 1

    def test_attainment_and_goodput_scalar_target(self):
        recs = [
            {"e2e": 0.2, "n_generated": 10},                  # attains
            {"e2e": 0.9, "n_generated": 10},                  # late
            {"e2e": 0.1, "n_generated": 7,
             "status": "timeout"},                            # not ok
            {"e2e": None, "n_generated": 0, "status": "shed"},
        ]
        slo = SLOReport(percentiles=(50,))
        slo.add_arm("arm", recs, slo=0.5)
        s = slo.summary()["arm"]["slo"]
        assert s["scored"] == 4 and s["attained"] == 1
        assert s["attainment"] == pytest.approx(0.25)
        assert s["goodput_tokens"] == 10
        assert s["shed"] == 1
        assert "attained" in slo.render() and "goodput" in slo.render()

    def test_attainment_callable_target_with_exemption(self):
        recs = [{"rid": "a", "e2e": 0.2, "n_generated": 5},
                {"rid": "b", "e2e": 0.2, "n_generated": 5},
                {"rid": "c", "e2e": 0.2, "n_generated": 5}]
        targets = {"a": 0.5, "b": 0.1, "c": None}   # c exempt
        slo = SLOReport(percentiles=(50,))
        slo.add_arm("arm", recs, slo=lambda r: targets[r["rid"]])
        s = slo.summary()["arm"]["slo"]
        assert s["scored"] == 2 and s["attained"] == 1
        assert s["goodput_tokens"] == 5

    def test_unscored_batch_leaves_scored_arm_consistent(self):
        """Accumulating a batch WITHOUT slo= into a previously scored
        arm folds its latencies in but leaves the slo block untouched
        — attainment and shed counts must cover one population."""
        slo = SLOReport(percentiles=(50,))
        slo.add_arm("a", [{"e2e": 0.2, "n_generated": 3}], slo=0.5)
        before = dict(slo.summary()["a"]["slo"])
        slo.add_arm("a", [{"e2e": 0.4, "n_generated": 9},
                          {"e2e": None, "status": "shed"}])
        after = slo.summary()["a"]
        assert after["slo"] == before
        assert after["e2e"]["count"] == 2       # latencies DID fold in

    def test_unscored_arm_has_no_slo_block(self):
        slo = SLOReport(percentiles=(50,))
        slo.add_arm("a", [{"e2e": 0.1}])
        assert "slo" not in slo.summary()["a"]
        # json round-trips with the new blocks
        doc = slo.to_dict()
        assert doc["arms"]["a"]["skipped"]["ttft"] == 1
