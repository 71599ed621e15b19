"""Shared plumbing for the root-level benchmark scripts.

The bench scripts that are left (ROADMAP D1 names the cell whose PR
deletes each; this file goes with the last of them) share three pieces:

- the per-chip peak bf16 FLOP/s table (MFU denominator); a device that
  is not in the table is an error, not a default,
- the child-process runner: the measurement runs in ONE child under a
  hard timeout while the parent stays off JAX (a chip belongs to one
  process at a time — a parent that had touched JAX would hold it and
  starve its own child).  A child that produces no result makes the
  parent print the diagnosis and exit non-zero, and
- the run history (``BENCH_HISTORY.json``, git-ignored, written at run
  time): every successful unpinned run is appended with a timestamp so
  ``--check`` can score a fresh record against prior runs of the same
  workload on the same device.  The history is evidence for the
  sentinel only — it is never served in place of a live measurement.
"""

import datetime
import importlib.util
import json
import os
import subprocess

_HERE = os.path.dirname(os.path.abspath(__file__))
HISTORY_PATH = os.path.join(_HERE, "BENCH_HISTORY.json")


# Peak dense bf16 FLOP/s per chip by device_kind substring (public
# specs; v5e: Google Cloud documentation, "TPU v5e").
PEAK_FLOPS = [
    ("v6", 918e12),       # Trillium
    ("v5p", 459e12),
    ("v5 lite", 197e12),  # v5e reports as "TPU v5 lite"
    ("v5e", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]


def peak_flops(device_kind: str) -> float:
    dk = device_kind.lower()
    for key, peak in PEAK_FLOPS:
        if key in dk:
            return peak
    raise ValueError(
        f"no peak FLOP/s on record for device_kind {device_kind!r}: a "
        "utilization against an unknown peak is not a measurement — "
        "add the device to _bench_common.PEAK_FLOPS with its source")


def pin_platform(platform: str) -> None:
    """The bench CHILD's first call: pin its JAX platform before any
    backend init, then place the compile cache before the first jit."""
    if platform:
        os.environ["JAX_PLATFORMS"] = platform
        import jax

        jax.config.update("jax_platforms", platform)
    from chainermn_tpu.utils import enable_compile_cache

    enable_compile_cache()


def _regression():
    """``chainermn_tpu/utils/regression.py`` loaded by path: it is pure
    stdlib, and importing it through the package would import jax into
    the parent, which must stay off the chip."""
    spec = importlib.util.spec_from_file_location(
        "_cmn_regression",
        os.path.join(_HERE, "chainermn_tpu", "utils", "regression.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def record_measurement(result: dict) -> None:
    """Append a successful measurement to the run history with a
    timestamp (the ``--check`` sentinel's evidence).

    Single-writer by convention (one bench holds the chip at a time);
    the write goes through a sibling tmp file + rename so an
    interrupted run cannot leave invalid JSON behind.
    """
    if result.get("value") is None:
        return
    history = {"runs": _regression().load_history(HISTORY_PATH)}
    entry = dict(result)
    entry.setdefault(
        "timestamp",
        datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"))
    history["runs"].append(entry)
    tmp = HISTORY_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(history, f, indent=1)
        f.write("\n")
    os.replace(tmp, HISTORY_PATH)


def run_check(record: dict, match=None, direction="higher"):
    """The perf-regression sentinel hook (``bench_programs.py --check`` —
    any bench script can pass ``check=True`` through
    ``run_child_with_retries``): score ``record`` against the run
    history's PRIOR runs of the same metric and workload
    (``utils/regression.py`` noise-aware bounds) and return the
    machine-readable verdict block.  Called BEFORE the record is
    appended, so a run never anchors its own bound.  The record's own
    ``device_kind`` joins the workload match: a TPU run is never
    scored against a CPU-measured baseline (or vice versa) — cross-
    device numbers are different workloads, not history."""
    regression = _regression()
    match = dict(match or {})
    if record.get("device_kind") is not None:
        match.setdefault("device_kind", record["device_kind"])
    return regression.check_record(
        record, regression.load_history(HISTORY_PATH),
        match=match or None, direction=direction)


def run_child_with_retries(cmd, cwd, timeouts, metric, unit,
                           record=True, match=None, check=False,
                           check_direction="higher") -> int:
    """Run ``cmd`` under per-attempt timeouts until one prints a
    ``BENCH_RESULT`` line; print exactly one JSON line.

    The exit code is 0 only for a live result with a value.  A child
    that never prints a result (crash, timeout) makes the parent print
    ``value: null`` with the attempts' diagnosis and return 1 — with or
    without ``check`` — and so does a child whose result carries
    ``value: null``.

    With ``record`` (the default for unpinned runs), a success is
    appended to the run history.  Callers that pin a platform (CPU
    smoke tests) MUST pass ``record=False``: a toy run must not sit in
    the history a hardware run is scored against.  ``match``
    (workload-defining fields, e.g. ``{"batch": 256}``) restricts the
    sentinel to prior runs of the SAME workload.

    ``check=True`` runs the perf-regression sentinel: the fresh record
    is scored against prior same-workload runs (:func:`run_check`)
    before being recorded, the verdict rides the printed JSON under
    ``"check"``, and the exit code is also 1 on a ``"regression"``
    verdict (``no_history`` is evidence, not a failure).  A
    platform-pinned smoke run (``record=False``) is ``"smoke"``: never
    scored against the hardware history its records are excluded from
    (a strict CI gate keys on ``pass``/``improved`` only).
    ``check_direction`` names which way is better for the metric:
    ``"higher"`` (throughput, speedup ratios — the default) or
    ``"lower"`` (overhead ratios, latencies).
    """
    errors = []
    for attempt, budget in enumerate(timeouts):
        try:
            proc = subprocess.run(
                cmd, timeout=budget, capture_output=True, text=True,
                cwd=cwd)
        except subprocess.TimeoutExpired:
            errors.append(
                f"attempt {attempt + 1}: timed out after {budget}s")
            continue
        for line in reversed(proc.stdout.splitlines()):
            if not line.startswith("BENCH_RESULT "):
                continue
            rec = json.loads(line[len("BENCH_RESULT "):])
            rc = 0 if rec.get("value") is not None else 1
            entry = dict(rec)
            if check:
                if not record:
                    # a platform-pinned smoke run: its records are
                    # deliberately kept OUT of the history, so scoring
                    # it AGAINST that history would gate smoke runs on
                    # a foreign-device baseline — non-gating
                    rec["check"] = {
                        "verdict": "smoke",
                        "metric": metric,
                        "direction": check_direction,
                        "note": "platform-pinned smoke run — not "
                                "scored against the hardware history "
                                "it is excluded from",
                    }
                else:
                    # scored BEFORE record_measurement appends it: a
                    # run must never anchor its own bound
                    rec["check"] = run_check(
                        rec, match, direction=check_direction)
                verdict = rec["check"].get("verdict")
                if verdict in ("regression", "no_result"):
                    rc = 1
                if verdict == "regression":
                    # STAMPED so the sentinel's history excludes the
                    # run: N CI re-runs of a real regression must not
                    # pull the baseline down until the gate
                    # self-normalizes green
                    entry["check_verdict"] = verdict
            if record:
                # the record without the full verdict block (a history
                # entry is evidence, not a judgement)
                record_measurement(entry)
            print(json.dumps(rec))
            return rc
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()
        errors.append(
            f"attempt {attempt + 1}: rc={proc.returncode}, "
            f"last output: {' | '.join(tail[-3:]) if tail else '<none>'}")
    rec = {
        "metric": metric,
        "value": None,
        "unit": unit,
        "vs_baseline": None,
        "error": "; ".join(errors)[-1800:],
    }
    if check:
        rec["check"] = {"verdict": "no_result", "metric": metric,
                        "direction": check_direction}
    print(json.dumps(rec))
    return 1
