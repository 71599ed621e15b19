"""``benchmarks/lib/clock.py``: the program's clock tied to the device
trace's from the program's own sync points, on planes and spans made by
hand with a known offset between the two clocks."""

import random

import pytest

from benchmarks.lib import clock

OFFSET = 4321.0625          # perf_counter = device seconds + OFFSET
STEP_S, FEED_S = 0.100, 0.170


class Window:
    def __init__(self, t_open, t_close, ends):
        self.t_open, self.t_close, self.ends = t_open, t_close, ends
        self.iterations = len(ends)


def _ns(host_s):
    return int(round((host_s - OFFSET) * 1e9))


def _job(steps=(10, 11, 12, 13), launch_us=(400, 300, 500, 350),
         learn_us=(120, 90, 150, 110), waits=True, retire_lag=1):
    """A serial trainer's window: each iteration feeds for 170 ms,
    dispatches a step that starts on the device ``launch_us`` later and
    runs 100 ms, retires the window ``retire_lag`` iterations back, and
    (``waits``) leaves ``trainer/observe`` ``learn_us`` after the step
    ended on the device -- or 1 ms after it began, having read an older
    loss.  Returns ``(spans, window, device)``."""
    t = 100.0
    t_open = t
    spans, modules, ends, run_end = [], [], [], {}
    modules.append(("jit_step", _ns(t - 0.2), _ns(t - 0.1)))   # before it
    for i, step in enumerate(steps):
        spans.append({"name": "step/host", "t0": t, "dur": FEED_S,
                      "step": step})
        t += FEED_S
        spans.append({"name": "step/dispatch", "t0": t, "dur": 0.002,
                      "step": step, "meta": {"k": 1}})
        start = t + launch_us[i] * 1e-6
        run_end[step] = start + STEP_S
        modules.append(("jit_step", _ns(start), _ns(run_end[step])))
        modules.append(("jit_atleast_1d", _ns(run_end[step] + 5e-6),
                        _ns(run_end[step] + 7e-6)))
        t += 0.002
        retired = steps[i - retire_lag] if i >= retire_lag else None
        # the window it blocks on ended long ago in a serial loop
        spans.append({"name": "step/retire", "t0": t, "dur": 1e-5,
                      "step": step,
                      "meta": {"inflight": 2, "retired": retired}})
        t += 1e-4
        end = run_end[step] + learn_us[i] * 1e-6 if waits else t + 1e-3
        spans.append({"name": "trainer/observe", "t0": t, "dur": end - t,
                      "step": step})
        t = end + 2e-4
        ends.append(t)
    hi = max(e for _, _, e in modules)
    t_close = run_end[steps[-1]] + 250e-6 if not waits else t + 1e-4
    device = {"modules": [m for m in modules if m[1] >= _ns(t_open)],
              "step_program": "jit_step", "hi": hi, "lo": _ns(t_open)}
    return spans, Window(t_open, t_close, ends), device


def test_recovers_a_known_offset(capsys):
    spans, window, device = _job()
    tie = clock.tie(spans, window, device)
    # from above the smallest latency of learning (90 us), from below
    # the smallest launch delay (300 us)
    assert tie["offset_s"] == pytest.approx(OFFSET + 90e-6, abs=2e-9)
    assert tie["bracket_s"] == pytest.approx(390e-6, abs=4e-9)
    assert OFFSET <= tie["offset_s"] <= OFFSET + tie["bracket_s"]
    # 1 close + 3 retires that blocked on a window + 4 observes, 4 starts
    assert tie["pairs"] == "8+4"
    line = capsys.readouterr().out
    assert line.startswith("[clock] offset_s=4321.06259")
    assert "bracket_us=390.0" in line and "pairs=8+4" in line


def test_a_wide_bracket_is_handed_out_with_both_its_ends(capsys):
    """A step that waits 40 ms on the device for its batch: the bound
    from below is that loose.  The offset is still the bound from
    above; what to make of the width is the reader's to decide."""
    spans, window, device = _job(launch_us=(40000, 41000, 43000, 40500))
    tie = clock.tie(spans, window, device)
    assert tie["offset_s"] == pytest.approx(OFFSET + 90e-6, abs=2e-9)
    assert tie["lowest_s"] == pytest.approx(OFFSET - 40e-3, abs=2e-9)
    assert tie["bracket_s"] == pytest.approx(40.09e-3, abs=4e-9)
    assert "bracket_us=40090.0" in capsys.readouterr().out


def test_empty_bracket_is_no_tie(capsys):
    """A retire that claims to have learned of a step's end before the
    step could have begun: the two sides contradict each other."""
    spans, window, device = _job()
    for ev in spans:
        if ev["name"] == "step/retire" and ev["meta"]["retired"] == 11:
            ev["t0"] -= 0.5
    assert clock.tie(spans, window, device) is None
    assert "offset_s=None" in capsys.readouterr().out


def test_pairs_by_step_not_by_order():
    """Two windows in flight: a retire names the window two back, and
    the spans arrive in no order.  Paired by order alone (each retire
    with the execution before its own) the retires would read
    ``-launch - 100 ms`` and empty the bracket."""
    spans, window, device = _job(retire_lag=2)
    random.Random(5).shuffle(spans)
    tie = clock.tie(spans, window, device)
    assert tie["offset_s"] == pytest.approx(OFFSET + 90e-6, abs=2e-9)
    assert tie["pairs"] == "7+4"
    # the same spans under steps the device never ran pair with nothing
    for ev in spans:
        if ev["name"] == "step/retire" and ev["meta"]["retired"]:
            ev["meta"]["retired"] += 100
    assert clock.tie(spans, window, device)["pairs"] == "5+4"


def test_an_observe_that_did_not_wait_is_no_sync_point(capsys):
    """Behind a pipelined updater ``LogReport`` reads an older loss:
    the observe spans end while their step still runs, and the tie
    comes from the retires and the close alone."""
    spans, window, device = _job(waits=False)
    tie = clock.tie(spans, window, device)
    assert "observe_pairs=4 not used" in capsys.readouterr().out
    assert tie["pairs"] == "4+4"
    # the close learned that the device's last program (7 us past the
    # step) had ended 243 us late
    assert tie["offset_s"] == pytest.approx(OFFSET + 243e-6, abs=2e-9)


def test_dispatches_and_executions_must_match():
    spans, window, device = _job()
    fewer = [ev for ev in spans
             if not (ev["name"] == "step/dispatch" and ev["step"] == 12)]
    assert clock.tie(fewer, window, device) is None
    assert clock.tie([], window, device) is None


def test_spans_of_an_older_program_still_tie_from_the_close():
    """No ``retired``, no ``trainer/observe``: the close and the
    dispatches are enough for a tie (the readers of the idle split then
    find no ``trainer/observe`` and report nothing)."""
    spans, window, device = _job()
    old = [dict(ev, meta={"inflight": 2}) if ev["name"] == "step/retire"
           else ev for ev in spans if ev["name"] != "trainer/observe"]
    tie = clock.tie(old, window, device)
    assert tie["pairs"] == "1+4"
    assert tie["lowest_s"] <= OFFSET <= tie["offset_s"] <= OFFSET + 2e-3


def test_of_works_the_tie_out_once(capsys):
    spans, window, device = _job()
    ctx = {"trace": {"devices": [device]}, "spans": spans,
           "window": window}
    assert clock.of(ctx) is clock.of(ctx)
    assert capsys.readouterr().out.count("[clock]") == 1
    assert clock.of({"trace": None, "spans": spans,
                     "window": window}) is None
