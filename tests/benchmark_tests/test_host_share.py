"""``benchmarks/lib/host_share.py`` and the readers over it: the host's
share of an iteration from the program's finer spans, and the device's
idle time put down to them -- on a job made by hand, on the trace and
spans of a chip run of PR 24 kept under ``traces/``, and in a
rehearsal on the CPU."""

import argparse
import gzip
import json
import os

import pytest

from benchmarks import run as bench
from benchmarks.lib import cells, host_share, trace

from test_clock import OFFSET, Window, _job, _ns

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "resnet50-trainer-b256"
SPAN_METRICS = ("feed.pull_ms", "feed.convert_ms", "feed.put_ms",
                "trainer.observe_ms")
IDLE_METRICS = ("idle.in_feed_ms", "idle.in_observe_ms",
                "idle.in_other_ms")


def _ctx(spans, window, device_modules):
    """The readers' context over a device whose ops are its programs."""
    planes = {"/device:TPU:0": {"XLA Modules": device_modules,
                                "XLA Ops": device_modules}}
    return {"spans": spans, "window": window,
            "trace": trace.reduce(planes, window.iterations)}


def _hand_ctx(**job):
    spans, window, device = _job(**job)
    # the program before the window ended 50 us before it opened
    before = ("jit_step", _ns(window.t_open - 0.1), _ns(window.t_open - 50e-6))
    return _ctx(spans, window, [before] + device["modules"])


def test_idle_split_by_hand():
    """Every iteration: 170 ms of feed with the device idle, 300 us
    from the dispatch to the step's start, 100 ms of step, then the
    host learns of its end 150 us later (100 us in the last iteration,
    which sets the tie) and takes 200 us more to end the iteration.  On
    the tied clock the host's spans lie 100 us early."""
    ctx = _hand_ctx(launch_us=(300,) * 4, learn_us=(150, 150, 150, 100))
    split = host_share.idle_split_ms(ctx)
    assert ctx["clock"]["offset_s"] == pytest.approx(OFFSET + 100e-6,
                                                     abs=2e-9)
    assert split["feed"] == pytest.approx(170.0, abs=1e-5)
    # 150 us less the tie's 100 and the 2 us of the small program
    assert split["observe"] == pytest.approx(0.048, abs=1e-5)
    # dispatch 2 ms with the device busy after 300 us; 100 us between
    # retire and observe busy too; the tail's 200 us; the tie's 100 us
    assert split["other"] == pytest.approx(0.600, abs=1e-5)
    device = ctx["trace"]["devices"][0]
    idle_ms = 1e3 * (device["window_s"] - device["busy_s"]) / 4
    # medians against a mean: the first and the last iteration are cut
    # by the window's own ends and not by the host's
    assert sum(split.values()) == pytest.approx(idle_ms, abs=0.1)
    for part in ("feed", "observe", "other"):
        assert host_share.idle_ms(ctx, part) == split[part]


def test_a_wide_bracket_that_moves_nothing_is_good_enough():
    """Every step waits 40 ms on the device for its batch, so the
    bracket is 40 ms wide; but wherever in it the offset lies, the feed
    spans stay clear of the busy device and the observe spans cover all
    of it but its first 2.1 ms: the split moves by 2.1 ms between the
    bracket's ends, under 1 % of the iteration, and is reported."""
    ctx = _hand_ctx(launch_us=(40000,) * 4, learn_us=(150, 150, 150, 100))
    split = host_share.idle_split_ms(ctx)
    assert ctx["clock"]["bracket_s"] == pytest.approx(40.1e-3, abs=1e-8)
    assert split["feed"] == pytest.approx(170.0, abs=1e-5)
    # at the upper end the host's spans lie 100 us early: from the
    # observe's start (2.0 ms after the dispatch began) to the step's,
    # and the 48 us after it
    assert split["observe"] == pytest.approx(38.0 + 0.048, abs=1e-5)
    assert split["other"] == pytest.approx(2.0 + 0.3, abs=1e-5)


def test_a_wide_bracket_that_moves_the_split_is_not_reported():
    """The same 40 ms, but the observe hook is entered only 30 ms after
    the dispatch (a slow extension before it, say): at the bracket's
    lower end the observe span would begin inside the step, at its
    upper end 10 ms before it.  10 ms is more than 1 % of the
    iteration, so nothing is reported."""
    spans, window, device = _job(launch_us=(40000,) * 4)
    for ev in spans:
        if ev["name"] == "trainer/observe":
            ev["t0"] += 0.0279
            ev["dur"] -= 0.0279
    before = ("jit_step", _ns(window.t_open - 0.1),
              _ns(window.t_open - 50e-6))
    ctx = _ctx(spans, window, [before] + device["modules"])
    assert host_share.idle_split_ms(ctx) is None
    assert ctx["clock"]["bracket_s"] > 0.03


def test_idle_split_needs_a_tie_both_spans_and_a_trace():
    spans, window, device = _job()
    for ev in spans:        # the two sides contradict each other
        if ev["name"] == "step/retire" and ev["meta"]["retired"] == 11:
            ev["t0"] -= 0.5
    before = ("jit_step", _ns(window.t_open - 0.1),
              _ns(window.t_open - 50e-6))
    assert host_share.idle_split_ms(
        _ctx(spans, window, [before] + device["modules"])) is None
    ctx = _hand_ctx()
    older = dict(ctx, spans=[ev for ev in ctx["spans"]
                             if ev["name"] != "trainer/observe"])
    older.pop("clock", None)
    assert host_share.idle_split_ms(older) is None
    assert host_share.idle_ms(dict(ctx, trace=None, clock=None),
                              "feed") is None


def test_idle_split_that_does_not_add_up_is_not_reported():
    """One iteration in four idles 40 ms longer: the medians no longer
    add up to the idle time an iteration within 1 % of it."""
    spans, window, device = _job()
    late = [dict(ev) for ev in spans]
    modules = list(device["modules"])
    shift = 0.040
    for ev in late:
        if ev["t0"] >= window.ends[2]:
            ev["t0"] += shift
    modules = [(n, s + int(shift * 1e9), e + int(shift * 1e9))
               if s >= _ns(window.ends[2]) else (n, s, e)
               for n, s, e in modules]
    ends = window.ends[:3] + [window.ends[3] + shift]
    before = ("jit_step", _ns(window.t_open - 0.1),
              _ns(window.t_open - 50e-6))
    ctx = _ctx(late, Window(window.t_open, window.t_close + shift, ends),
               [before] + modules)
    assert host_share.idle_split_ms(ctx) is None


def _recorded_ctx():
    stem = os.path.join(HERE, "traces", CELL)
    with gzip.open(stem + ".pr24.json.gz", "rt") as f:
        planes = json.load(f)
    with open(stem + ".spans.json") as f:
        kept = json.load(f)
    with open(stem + ".pr24.expected.json") as f:
        expected = json.load(f)
    window = Window(kept["window"]["t_open"], kept["window"]["t_close"],
                    kept["window"]["ends"])
    assert window.iterations == expected["iterations"]
    return {"spans": kept["spans"], "window": window,
            "trace": trace.reduce(planes, window.iterations)}, expected


def test_readers_on_a_chip_run_of_pr24():
    """The trace and the spans of one traced chip run, kept by
    ``benchmarks/tools/keep_spans.py``: every new reader gives the
    number that run's own result line gave."""
    ctx, expected = _recorded_ctx()
    for name in SPAN_METRICS + IDLE_METRICS:
        got = cells.readers(CELL)[name](ctx)
        assert got == pytest.approx(expected["metrics"][name]), name
    for end in ("offset_s", "lowest_s"):
        assert ctx["clock"][end] == pytest.approx(
            expected["clock"][end], abs=1e-9)
    assert ctx["clock"]["pairs"] == expected["clock"]["pairs"]


def test_a_chip_runs_idle_split_adds_up_and_its_spans_nest():
    ctx, expected = _recorded_ctx()
    split = host_share.idle_split_ms(ctx)
    device = ctx["trace"]["devices"][0]
    n = ctx["window"].iterations
    iteration_ms = 1e3 * device["window_s"] / n
    idle_ms = iteration_ms * expected["idle_share_worst"]
    assert sum(split.values()) == pytest.approx(
        idle_ms, abs=0.01 * iteration_ms)
    # the feed's three spans are the feed's one span, within 2 ms
    m = expected["metrics"]
    assert m["feed.pull_ms"] + m["feed.convert_ms"] + m["feed.put_ms"] \
        == pytest.approx(m["feed.host_ms"], abs=2.0)
    # and in the spans themselves: children of step/host, sharing its
    # step, on its thread
    hosts = {(ev["name"], ev["t0"]): ev for ev in ctx["spans"]
             if ev["name"] == "step/host"}
    feeds = [ev for ev in ctx["spans"] if ev["name"].startswith("feed/")]
    assert len(feeds) == 3 * len(hosts)
    for ev in feeds:
        host = hosts[tuple(ev["parent"])]
        assert (ev["step"], ev["tid"]) == (host["step"], host["tid"])
    # each retire names the iteration before its own
    retires = [ev for ev in ctx["spans"] if ev["name"] == "step/retire"]
    assert all(ev["meta"]["retired"] == ev["step"] - 1 for ev in retires)


def test_span_sums_per_iteration():
    """Two pulls in each iteration of a fused window, one on a worker's
    thread: summed by the iteration they began in, median over the
    window's; a name nobody recorded gives nothing."""
    window = Window(10.0, 10.9, [10.3, 10.6, 10.9])
    spans = [{"name": "feed/pull", "t0": t, "dur": d, "tid": tid}
             for t, d, tid in ((10.01, 0.010, 1), (10.05, 0.020, 2),
                               (10.31, 0.011, 1), (10.35, 0.021, 2),
                               (10.61, 0.015, 1), (10.65, 0.025, 2),
                               (9.99, 0.5, 1), (10.95, 0.5, 1))]
    ctx = {"spans": spans, "window": window}
    assert host_share.per_iteration_ms(ctx, "feed/pull") == \
        pytest.approx(32.0)
    assert host_share.per_iteration_ms(ctx, "feed/put") is None


def test_rehearsal_reports_the_span_metrics_and_no_idle_split(
        monkeypatch, tmp_path):
    """``--rehearse --trace 1`` on the CPU: the four metrics read from
    spans alone are there and add up to the feed's one span; the three
    that need a device plane are not.  (A scratch directory of its own:
    ``test_rehearsal.py`` rehearses the same cell, maybe at this moment
    in another worker.)"""
    monkeypatch.setattr(bench, "ROOT", str(tmp_path))
    result = bench.measure(argparse.Namespace(
        workload=CELL, seed=11, seconds=30, trace=1, rehearse=True))
    metrics = result["metrics"]
    for name in SPAN_METRICS:
        assert metrics[name]["value"] > 0 and metrics[name]["unit"] == "ms"
    for name in IDLE_METRICS:
        assert name not in metrics
    parts = sum(metrics[n]["value"] for n in SPAN_METRICS[:3])
    assert parts <= metrics["feed.host_ms"]["value"] * 1.05
    assert parts >= metrics["feed.host_ms"]["value"] * 0.7


@pytest.mark.parametrize("name", SPAN_METRICS + IDLE_METRICS)
def test_new_metric_is_found_by_name(name):
    entry = next(m for m in cells.manifest()["per_layer"]
                 if m["name"] == name)
    assert entry["workloads"] == [CELL] and entry["unit"] == "ms"
    assert entry["source"] == "program_span"
    assert name in cells.readers(CELL)
    assert all(name not in cells.readers(w["name"])
               for w in cells.manifest()["workloads"] if w["name"] != CELL)
