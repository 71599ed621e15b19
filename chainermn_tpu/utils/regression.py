"""Perf regression sentinel — noise-aware verdicts over bench history.

``_bench_common`` appends every successful unpinned bench record to a
run history (``BENCH_HISTORY.json``, written at run time).  Given a fresh bench record and the history of prior runs
of the same metric (and the same workload — batch size, sequence
length; a toy debug run must never anchor the bound), it computes a
**noise-aware acceptance bound** and emits a machine-readable verdict.

The bound is deliberately simple and robust (the history is short —
a handful of runs per metric — so anything distributional would be
noise fit to noise):

- baseline = **median** of the matching history values (robust to the
  one outlier a bursty host records);
- sigma = the scaled median absolute deviation (``1.4826 × MAD``, the
  robust stdev estimator; 0 for n < 2);
- the allowed slack is ``max(rel_slack × |median|, noise_k × sigma)``
  — a floor of ``rel_slack`` (default 5%) so a perfectly repeatable
  history doesn't flag measurement jitter, widened by the history's
  OWN observed noise when it is the larger term.

For a higher-is-better metric (throughput, speedup ratios — the
default), ``value < median − slack`` is a ``"regression"``,
``value > median + slack`` is ``"improved"``, anything between is
``"pass"``; ``direction="lower"`` mirrors the bounds for
cost metrics.  Fewer than ``min_history`` matching runs is
``"no_history"`` — evidence, not a verdict (green for gating: a new
bench's first run cannot fail against nothing).

``bench_programs.py --check`` (any script passing ``check=True`` through
``_bench_common.run_child_with_retries``) self-verifies: the fresh
record is scored against history BEFORE it is appended (a run must
not anchor its own bound), the verdict rides the printed JSON line
under ``"check"``, and the process exits 1 on ``"regression"`` so a
CI step can gate on it.

Pure stdlib, importable without jax.
"""

from __future__ import annotations

import json
import statistics
from typing import Dict, List, Optional, Sequence

__all__ = [
    "check_record",
    "check_value",
    "history_values",
    "load_history",
    "noise_bounds",
]

#: MAD → stdev scale for normally-distributed noise.
MAD_SCALE = 1.4826

#: Defaults: 5% relative slack floor, 3-sigma noise widening, and at
#: least 2 matching prior runs before a verdict is more than evidence.
REL_SLACK = 0.05
NOISE_K = 3.0
MIN_HISTORY = 2

#: Timestamped history entries older than this never anchor a bound:
#: a verdict against a baseline measured on weeks-old code is not a
#: verdict about this tree.  Legacy un-timestamped entries pass (the leniency that
#: retires itself).
MAX_HISTORY_AGE_DAYS = 14.0


def load_history(path: str) -> List[dict]:
    """The run list from a ``BENCH_HISTORY.json``-shaped file
    (``{"runs": [...]}``); an unreadable/absent file is an empty
    history, never a crash — the sentinel must degrade to
    ``no_history``, not kill a bench."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return []
    runs = doc.get("runs", []) if isinstance(doc, dict) else doc
    return [r for r in runs if isinstance(r, dict)]


def history_values(runs: Sequence[dict], metric: str,
                   match: Optional[dict] = None,
                   max_age_days: Optional[float] =
                   MAX_HISTORY_AGE_DAYS) -> List[float]:
    """Values of prior runs of ``metric`` whose recorded workload
    fields agree with ``match`` (a run that predates the recording of
    a matched field passes — the leniency covers legacy entries and
    retires itself).  Runs the sentinel itself scored ``regression``
    are skipped
    (``"check_verdict": "regression"``): a sustained real regression
    re-run by CI must not pull the baseline down until the gate
    self-normalizes green (an INTENTIONAL perf change re-anchors by
    recording a run without ``--check``, or by editing the
    history).  Timestamped runs older than ``max_age_days`` are
    skipped too (``None`` disables the cutoff)."""
    import datetime

    now = datetime.datetime.now(datetime.timezone.utc)
    out = []
    for run in runs:
        if run.get("metric") != metric or run.get("value") is None:
            continue
        if run.get("check_verdict") == "regression":
            continue
        if match and any(k in run and run[k] != v
                         for k, v in match.items()):
            continue
        ts = run.get("timestamp")
        if ts is not None and max_age_days is not None:
            try:
                age = now - datetime.datetime.fromisoformat(ts)
            except (TypeError, ValueError):
                age = None
            if age is not None \
                    and age.total_seconds() > max_age_days * 86400:
                continue
        try:
            out.append(float(run["value"]))
        except (TypeError, ValueError):
            continue
    return out


def noise_bounds(values: Sequence[float],
                 rel_slack: float = REL_SLACK,
                 noise_k: float = NOISE_K) -> dict:
    """``{median, sigma, slack, lower, upper}`` over a non-empty
    history (see module docstring for the bound construction)."""
    vals = [float(v) for v in values]
    if not vals:
        raise ValueError("noise_bounds over an empty history")
    med = statistics.median(vals)
    if len(vals) >= 2:
        mad = statistics.median(abs(v - med) for v in vals)
        sigma = MAD_SCALE * mad
    else:
        sigma = 0.0
    slack = max(rel_slack * abs(med), noise_k * sigma)
    return {"median": med, "sigma": sigma, "slack": slack,
            "lower": med - slack, "upper": med + slack}


def check_value(value: float, values: Sequence[float], *,
                direction: str = "higher",
                rel_slack: float = REL_SLACK,
                noise_k: float = NOISE_K,
                min_history: int = MIN_HISTORY) -> dict:
    """Score one fresh ``value`` against its history; returns the
    machine-readable verdict block (see module docstring)."""
    if direction not in ("higher", "lower"):
        raise ValueError(
            f"direction={direction!r} must be 'higher' or 'lower'")
    n = len(values)
    if n < min_history:
        return {"verdict": "no_history", "n_history": n,
                "min_history": min_history, "direction": direction}
    b = noise_bounds(values, rel_slack=rel_slack, noise_k=noise_k)
    value = float(value)
    if direction == "higher":
        verdict = ("regression" if value < b["lower"]
                   else "improved" if value > b["upper"] else "pass")
    else:
        verdict = ("regression" if value > b["upper"]
                   else "improved" if value < b["lower"] else "pass")
    margin = ((value - b["median"]) / abs(b["median"]) * 100.0
              if b["median"] else None)
    return {
        "verdict": verdict,
        "direction": direction,
        "n_history": n,
        "baseline_median": b["median"],
        "baseline_sigma": b["sigma"],
        "slack": b["slack"],
        "lower_bound": b["lower"],
        "upper_bound": b["upper"],
        "margin_pct": None if margin is None else round(margin, 2),
    }


def check_record(record: dict, history: Sequence[dict], *,
                 match: Optional[dict] = None,
                 direction: str = "higher",
                 rel_slack: float = REL_SLACK,
                 noise_k: float = NOISE_K,
                 min_history: int = MIN_HISTORY,
                 max_age_days: Optional[float] =
                 MAX_HISTORY_AGE_DAYS) -> dict:
    """Score one bench record dict against a run history (the
    ``load_history`` shape).  A record with ``value: null`` scores
    ``"no_result"`` — the bench itself failed; the sentinel reports
    it rather than comparing nothing."""
    metric = record.get("metric")
    if record.get("value") is None:
        return {"verdict": "no_result", "metric": metric,
                "direction": direction}
    values = history_values(history, metric, match=match,
                            max_age_days=max_age_days)
    out = check_value(record["value"], values, direction=direction,
                      rel_slack=rel_slack, noise_k=noise_k,
                      min_history=min_history)
    out["metric"] = metric
    out["value"] = float(record["value"])
    if match:
        out["match"] = dict(match)
    return out
