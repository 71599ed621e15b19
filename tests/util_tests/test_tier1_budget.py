"""Tier-1 runtime budget meta-test (ISSUE 15).

The tier-1 gate runs ``pytest -m 'not slow'`` under the ROADMAP's
``timeout -k 10 870`` — and at PR 14 the suite had quietly grown to
~960s, so the timeout truncated the tail and only the DOTS_PASSED
accounting papered over it.  This meta-test pins the budget
arithmetic against the recorded profile (``tests/tier1_budget.json``)
so it cannot silently regress again:

- the manifest's ``budget_s`` must equal the timeout in the ROADMAP's
  tier-1 command (neither can drift alone);
- the recorded ``-m 'not slow'`` wall time, minus what the
  slow-marking removed, must fit the budget with headroom;
- every manifest ``slow_marked`` nodeid must STILL be deselected by
  ``-m 'not slow'`` — un-marking a heavy drill fails here instead of
  re-breaching the timeout at the margin.

The budget arithmetic is BOX-SPEED-AWARE (ISSUE 18): the recorded
wall times came from one machine, and a 2.2×-slower box re-recording
them would read as a budget breach when nothing regressed.  The
manifest stores a ``calibration.reference_probe_s`` — the wall time
of a small fixed CPU workload on the recording box — and the fit
assertion scales the budget by ``max(1, probe_now / reference)``: a
slower box's inflated recording is environmental and still fits,
while on the recording box (scale 1) the check is exactly as strict
as before.  The scale never drops below 1 — a faster box must not
LOOSEN the guarantee the 870s timeout actually enforces.

What this cannot catch: a NEW slow test added after the recording.
The recording is refreshed whenever the manifest is (instructions in
its ``_comment``); the headroom term is the buffer that makes the
window between refreshes safe.
"""

import json
import os
import re
import subprocess
import sys
import time

_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", ".."))
_MANIFEST = os.path.join(_ROOT, "tests", "tier1_budget.json")


def _manifest():
    with open(_MANIFEST) as f:
        return json.load(f)


def _probe_s():
    """Wall time of a fixed CPU workload — the box-speed yardstick.

    Deliberately a mix of BLAS and element-wise numpy (the suite's own
    profile is jitted XLA-on-CPU, which leans on both); best-of-3 so a
    scheduler hiccup cannot masquerade as a slow box."""
    import numpy as np

    rng = np.random.RandomState(0)
    a = rng.rand(384, 384)
    best = float("inf")
    for _ in range(3):
        b = a.copy()
        t0 = time.perf_counter()
        for _ in range(100):
            b = np.tanh(b @ b.T / 384.0 + 0.1)
        best = min(best, time.perf_counter() - t0)
    return best


def _box_scale(m):
    ref = m["calibration"]["reference_probe_s"]
    return max(1.0, _probe_s() / ref)


def test_budget_matches_roadmap_timeout():
    roadmap = open(os.path.join(_ROOT, "ROADMAP.md")).read()
    m = re.search(r"timeout -k 10 (\d+)", roadmap)
    assert m, "ROADMAP.md tier-1 command lost its timeout"
    assert int(m.group(1)) == _manifest()["budget_s"], (
        "ROADMAP tier-1 timeout and tests/tier1_budget.json budget_s "
        "disagree — update them together")


def test_recorded_profile_fits_budget_with_headroom():
    m = _manifest()
    projected = (m["recorded_total_s"]
                 - sum(m["slow_marked"].values()))
    scale = _box_scale(m)
    assert projected + m["headroom_s"] <= m["budget_s"] * scale, (
        f"projected tier-1 wall {projected:.0f}s + headroom "
        f"{m['headroom_s']}s exceeds the {m['budget_s']}s budget "
        f"(box-speed scale {scale:.2f}) — mark more heavy tests slow "
        "(and re-record the manifest)")
    # the pre-marking recording really did breach (or crowd) the
    # budget — the slow-marking must be doing real work, not pinning
    # a vacuous inequality
    assert m["recorded_total_s"] + m["headroom_s"] > m["budget_s"] \
        or sum(m["slow_marked"].values()) > 100


def test_slow_marked_drills_stay_deselected():
    """One collect-only pass over the files the manifest names: every
    slow_marked nodeid must collect WITHOUT the marker filter and
    disappear UNDER it."""
    m = _manifest()
    # the serial recording's list and the driver's run's (PR 42)
    marked = dict(m["slow_marked"], **{
        k: v for k, v in m["driver_run"]["slow_marked"].items()
        if "::" in k})
    m = dict(m, slow_marked=marked)
    files = sorted({nodeid.split("::")[0]
                    for nodeid in m["slow_marked"]})

    def collected(extra):
        proc = subprocess.run(
            [sys.executable, "-m", "pytest", "--collect-only", "-q",
             "-p", "no:cacheprovider", "-p", "no:randomly", *extra,
             *files],
            capture_output=True, text=True, timeout=300, cwd=_ROOT)
        assert proc.returncode in (0, 5), proc.stdout[-2000:]
        return proc.stdout

    unfiltered = collected([])
    filtered = collected(["-m", "not slow"])
    for nodeid in m["slow_marked"]:
        assert nodeid in unfiltered, (
            f"{nodeid} no longer exists — refresh "
            "tests/tier1_budget.json")
        assert nodeid not in filtered, (
            f"{nodeid} lost its slow marker — it costs "
            f"{m['slow_marked'][nodeid]}s of the tier-1 budget")
