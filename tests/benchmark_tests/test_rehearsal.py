"""The harness driven end to end at toy sizes on the CPU: every cell's
driver through ``measure()`` (what ``--rehearse`` runs), the control
that has to come out not correct, and a timed path broken underneath.

No TPU library is loaded here: the described-device compiles stay in
``tests/function_tests/test_tpu_compile.py``.
"""

import argparse
import json
import os
import subprocess
import sys

import pytest

from benchmarks import run as bench
from benchmarks.lib import cells
from benchmarks.tools import limits

CELLS = [w["name"] for w in cells.manifest()["workloads"]]
LM_CELL = "opt-1.3b-l8-train-4x2048"


def _args(workload, trace=0, seed=7, seconds=0.5):
    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace, rehearse=True)


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_runs_the_cell(workload):
    """Program in its stated precision against the float32 reference:
    correct, nothing compiled inside the window, every end-to-end metric
    of the cell reported and none 0."""
    result = bench.measure(_args(workload))
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    wanted = {m["name"] for m in cells.end_to_end(workload)}
    assert set(result["metrics"]) == wanted
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["memory_peak_bytes"] > 0


def test_rehearsal_with_trace_reads_the_programs_spans():
    """``--trace 1``: the profiler writes a trace that is read back, the
    window is cut at the traffic file's ``trace_steps``, and the readers
    that need no device plane report."""
    workload = "resnet50-trainer-b256"
    result = bench.measure(_args(workload, trace=1, seconds=30))
    _, _, traffic = cells.load_cell(workload, rehearse=True)
    assert result["attempted"] == traffic["trace_steps"]
    assert result["metrics"]["feed.host_ms"]["value"] > 0
    assert "trainer.other_ms" in result["metrics"]
    assert "device.hbm_gib.resnet" in result["metrics"]
    # nothing read from a CPU run stands under a device metric's name
    assert "device.idle_pct.resnet" not in result["metrics"]
    assert "step.mfu_pct.resnet" not in result["metrics"]
    assert "busy_s" not in result["device"]


def test_same_seed_same_inputs():
    a = bench.measure(_args(LM_CELL, seed=2 ** 31 + 11))
    b = bench.measure(_args(LM_CELL, seed=2 ** 31 + 11))
    c = bench.measure(_args(LM_CELL, seed=12))
    assert a["compared"] == b["compared"]
    assert a["compared"] != c["compared"]


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 5, 12])
def test_control_in_lower_precision_is_not_correct(seed):
    """The plain reference in the nearest precision below the stated
    bf16 (float8_e4m3 operands), put in the program's place, is outside
    the limit of ``grad_norm_gap`` (at this toy size, with this size's
    limits; the cell's own size is read on the chip with the same tool,
    PERF.md)."""
    _, config, _ = cells.load_cell(LM_CELL, rehearse=True)
    got = limits.control_gaps(LM_CELL, seed, "float8_e4m3fn", rehearse=True)
    assert got["grad_norm_gap"] > config["check"]["limits"]["grad_norm_gap"]
    # and the same reference rounded to the STATED precision is inside
    stated = limits.control_gaps(LM_CELL, seed, "bfloat16", rehearse=True)
    assert all(stated[k] <= v
               for k, v in config["check"]["limits"].items()), stated


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    """The timed path broken underneath: the optimizer's update is
    computed and thrown away, so the parameters never change."""
    import optax

    monkeypatch.setattr(optax, "apply_updates", lambda params, updates: params)
    result = bench.measure(_args(LM_CELL))
    assert result["correct"] is False
    assert result["compared"]["delta_norm_gap"] == pytest.approx(1.0)


def test_part_of_the_batch_left_out_is_not_correct(monkeypatch):
    """The timed path broken underneath: the loss sees half the batch."""
    from chainermn_tpu.models import transformer

    whole = transformer.lm_loss
    monkeypatch.setattr(
        transformer, "lm_loss",
        lambda cfg, p, x, y: whole(cfg, p, x[:2], y[:2]))
    result = bench.measure(_args(LM_CELL))
    assert result["correct"] is False


def _run_cli(*extra, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(cells.HERE, "run.py"), "--workload",
         LM_CELL, "--seed", "1", "--seconds", "0.5", "--trace", "0", *extra],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))


def _result_lines(stdout):
    out = []
    for line in stdout.splitlines():
        try:
            out.append(json.loads(line))
        except ValueError:
            pass
    return [o for o in out if isinstance(o, dict) and "correct" in o]


def test_no_tpu_no_result():
    done = _run_cli()
    assert done.returncode != 0
    assert "needs a TPU" in done.stderr
    assert not _result_lines(done.stdout)


def test_rehearse_flag_never_prints_the_result_line():
    done = _run_cli("--rehearse")
    assert done.returncode != 0
    assert "[window]" in done.stdout
    assert not _result_lines(done.stdout)
