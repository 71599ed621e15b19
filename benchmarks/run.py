"""Run one cell of the benchmark once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chips.  Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.  The
last line of standard output is the one JSON object the driver reads;
every line before it is for people.  ``--rehearse`` drives the same
control flow at toy sizes on whatever backend JAX has, never prints the
result line and exits non-zero.
"""

import time

_T_PROCESS = time.perf_counter()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.lib import cells, trace    # noqa: E402
from benchmarks.lib.harness import CompileCounter, Run, log    # noqa: E402
from benchmarks.lib.peaks import peaks    # noqa: E402


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10)
    p.add_argument("--trace", type=int, choices=[0, 1], default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="toy sizes on whatever backend JAX has; no result "
                        "line, non-zero exit")
    return p.parse_args(argv)


def measure(args, t_process=None, keep=None):
    """Everything but the result line: returns the result object.
    ``keep``, a dict, is given the driver's ``Outcome`` (for
    ``tools/limits.py``, which reads more than the line carries)."""
    import jax
    import numpy as np

    from chainermn_tpu.utils import enable_compile_cache

    t_process = time.perf_counter() if t_process is None else t_process
    cell, config, traffic = cells.load_cell(args.workload, args.rehearse)
    found = jax.devices()
    device = {"platform": found[0].platform, "kind": found[0].device_kind,
              "count": len(found)}
    if device["platform"] != "tpu" and not args.rehearse:
        raise SystemExit(f"the benchmark needs a TPU; JAX found {device}")
    if len(found) < cell["chips"]:
        raise SystemExit(f"{cell['name']} needs {cell['chips']} chips; "
                         f"JAX found {len(found)}")
    # before the first jit; None on the CPU, where nothing is cached
    log("start", cell=cell["name"], seed=args.seed,
        cache_dir=enable_compile_cache(), **device)

    scratch = os.path.join(ROOT, ".bench_scratch", cell["name"])
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(scratch)
    run = Run(cell=cell, config=config, traffic=traffic,
              devices=found[:cell["chips"]], seed=args.seed,
              seconds=args.seconds,
              trace_dir=os.path.join(scratch, "trace") if args.trace else "",
              scratch=scratch, compiles=CompileCounter(),
              t_process=t_process)
    run.mark("imports and the runtime")
    outcome = cells.driver(traffic["driver"])(run)
    window = outcome.window
    if keep is not None:
        keep["outcome"] = outcome

    failed = window.failed()
    correct = bool(outcome.correct and failed == 0
                   and window.compiles_inside == 0)
    setup_s = window.t_open - t_process - run.reference_s
    intervals = window.intervals_ms
    log("window", seconds=f"{window.seconds:.3f}",
        iterations=window.iterations, rate=f"{window.rate:.2f}",
        interval_ms_median=f"{np.median(intervals):.3f}",
        interval_samples=len(intervals),
        compiles_inside=f"{window.compiles_inside} (limit 0)",
        not_finite=f"{failed} (limit 0)", setup_s=f"{setup_s:.2f}",
        reference_s=f"{run.reference_s:.2f}")

    result = {"correct": correct, "attempted": window.iterations,
              "failed": failed, "metrics": {},
              "compared": {k: v[0] for k, v in outcome.compared.items()},
              "device": dict(device,
                             memory_peak_bytes=outcome.memory_peak_bytes)}
    if not args.trace:
        values = {traffic["rate_metric"]: window.rate,
                  traffic["tail_metric"]: float(
                      np.percentile(intervals, 90)),
                  "setup_s": setup_s}
        for m in cells.end_to_end(cell["name"]):
            result["metrics"][m["name"]] = {
                "value": values[m["name"]], "unit": m["unit"]}
        return result

    # the CPU has no device planes to reduce: a rehearsal proves that a
    # trace is written and read back, the readers then find nothing
    planes = trace.load(run.trace_dir)
    summary = None if args.rehearse else trace.reduce(
        planes, window.iterations)
    ctx = {"cell": cell, "config": config, "traffic": traffic,
           "window": window, "spans": outcome.spans, "trace": summary,
           "facts": outcome.facts, "chips": cell["chips"],
           "memory_peak_bytes": outcome.memory_peak_bytes,
           "peaks": None if args.rehearse else peaks(device["kind"])}
    units = {m["name"]: m["unit"] for m in cells.manifest()["per_layer"]}
    for name, read in cells.readers(cell["name"]).items():
        value = read(ctx)
        if value is not None:      # a reader that found nothing to read
            result["metrics"][name] = {"value": float(value),
                                       "unit": units[name]}
    if summary is not None:
        result["device"].update(busy_s=summary["busy_s"],
                                window_s=summary["window_s"])
        result["breakdown"] = trace.breakdown(
            summary, outcome.facts.get("kernels"))
    return result


def main():
    args = parse()
    result = measure(args, _T_PROCESS)
    if args.rehearse:
        log("rehearsal", **{k: v for k, v in result.items()
                            if k != "breakdown"})
        sys.exit("rehearsal at toy sizes: not a measurement")
    print(json.dumps(result))


if __name__ == "__main__":
    main()
