"""bench_quality.py must WORK end-to-end before its first chip run
(discovering a harness bug there would waste the chip budget).
This drives the real smoke config: corpus synthesis -> BPE train ->
half-run with checkpoint -> resume (marker asserted by the harness) ->
held-out byte perplexity, all in fresh interpreters."""

import json
import os
import subprocess
import sys

_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", ".."))


def test_bench_quality_smoke_end_to_end():
    env = dict(os.environ)
    for k in list(env):
        if k.startswith(("TPU_", "LIBTPU", "PJRT_", "JAX_")):
            env.pop(k)
    # the suite's conftest pins an 8-virtual-device XLA_FLAGS for the
    # in-process mesh tests; the bench's train children run --mesh
    # data=1 and must see the plain host device config
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench_quality.py"),
         "--platform", "cpu", "--timeouts", "240"],
        capture_output=True, text=True, timeout=280, cwd=_ROOT, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("{")][-1]
    rec = json.loads(line)
    assert rec["metric"] == "lm_quality_heldout_byte_ppl"
    # learning happened: better than byte-uniform (256), and the
    # interruption+resume path demonstrably ran
    assert rec["value"] is not None and 1.0 < rec["value"] < 256.0
    assert rec["resume_verified"] is True
    assert not rec.get("cached"), "smoke must be a live run"
