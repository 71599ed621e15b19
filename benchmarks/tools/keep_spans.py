"""Run one cell traced and keep what the host-share readers read, as
fixtures for ``tests/benchmark_tests/``: the trace cut down as
``trim_trace.py`` cuts it (``<workload>.json.gz``), the program's spans
with the window's host-clock times (``<workload>.spans.json``), and the
numbers the run itself printed (``<workload>.expected.json``).

    python benchmarks/tools/keep_spans.py <workload> <seed> <out_dir> [--rehearse]
"""

import argparse
import gzip
import json
import os
import sys
import time

_T_PROCESS = time.perf_counter()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench    # noqa: E402
from benchmarks.lib import cells, clock, trace    # noqa: E402


def window_times(window):
    return {"t_open": window.t_open, "t_close": window.t_close,
            "ends": list(window.ends)}


def main():
    workload, seed, out = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    os.makedirs(out, exist_ok=True)
    keep = {}
    args = argparse.Namespace(
        workload=workload, seed=seed, trace=1,
        rehearse="--rehearse" in sys.argv,
        seconds=cells.manifest()["run_seconds"])
    result = bench.measure(args, _T_PROCESS, keep=keep)
    outcome = keep["outcome"]
    planes = trace.load(os.path.join(
        ROOT, ".bench_scratch", workload, "trace"))
    with gzip.open(os.path.join(out, workload + ".json.gz"), "wt") as f:
        json.dump(planes, f)
    with open(os.path.join(out, workload + ".spans.json"), "w") as f:
        json.dump({"window": window_times(outcome.window),
                   "spans": outcome.spans}, f)
    # worked out again here: ``measure`` keeps its readers' context
    summary = None if args.rehearse else trace.reduce(
        planes, outcome.window.iterations)
    expected = {
        "iterations": outcome.window.iterations,
        "recorded": f"{workload} --trace 1 through benchmarks/tools/"
                    f"keep_spans.py, seed {seed}, "
                    f"{result['device']['kind']}: every number as that "
                    "run's own result line and [clock] line gave them",
        "metrics": {k: v["value"] for k, v in result["metrics"].items()},
    }
    if summary is not None:     # a rehearsal has no device plane
        first = summary["devices"][0]
        expected.update(
            window_s=summary["window_s"], busy_s=summary["busy_s"],
            step_program_s=first["step_program_s"],
            idle_share_worst=summary["idle_share_worst"],
            collective_s=first["collective_s"],
            collective_exposed_s=first["collective_exposed_s"],
            kernel_s={},
            clock=clock.tie(outcome.spans, outcome.window, first))
    with open(os.path.join(out, workload + ".expected.json"), "w") as f:
        json.dump(expected, f, indent=1)
    print(json.dumps({k: v for k, v in result.items()
                      if k != "breakdown"}))


if __name__ == "__main__":
    main()
