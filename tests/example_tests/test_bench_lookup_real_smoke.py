"""bench_lookup_real.py must work end-to-end before its first live
TPU window (the round-4 lesson from bench_quality: a bench's first
execution must never be a rare live window).  Drives the real flow at
reduced steps (20: what is pinned is the harness, not the number): docs
corpus -> BPE + LM training -> three generate.py --lookup-k
measurements (trained quote + two held-out) -> acceptance record."""

import json
import os
import subprocess
import sys

_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", ".."))


def test_bench_lookup_real_smoke_end_to_end():
    env = dict(os.environ)
    for k in list(env):
        if k.startswith(("TPU_", "LIBTPU", "PJRT_", "JAX_")):
            env.pop(k)
    # the suite conftest pins an 8-virtual-device XLA_FLAGS; the bench
    # children run --mesh data=1 and need the plain host config
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench_lookup_real.py"),
         "--platform", "cpu", "--steps", "20", "--timeouts", "240"],
        capture_output=True, text=True, timeout=280, cwd=_ROOT, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("{")][-1]
    rec = json.loads(line)
    assert rec["metric"] == "lookup_real_text_mean_accepted"
    assert rec["workload"] == "quote-trained"
    # the machinery produced a real measurement (the acceptance VALUE
    # depends on training; the smoke pins the harness, not the number)
    assert rec["value"] is not None and 0.0 <= rec["value"] <= rec["k"]
    assert rec["heldout_accepted"] is not None
    assert not rec.get("cached"), "smoke must be a live run"
