"""One traced run of a cell with the per-layer metrics that have a
reader and no manifest entry yet (``tools/pending_per_layer.json``)
read beside the accepted ones: the ``[scopes]`` table (every path of
the program's ``DEVICE_SCOPES`` by phase, ms a step) on the lines
before, the result object last, as ``run.py --trace 1`` prints it.

    python benchmarks/tools/scope_table.py --workload <cell> --seed <n> \\
        [--seconds <s>] [--fixture <out.json>]

``--fixture`` also writes what ``lib/scopes_step.py`` read, cut to what
a test needs: each instruction's op name and self time, the window's
iterations and the values of the pending metrics (kept under
``tests/benchmark_tests/traces/``).  No benchmark run calls this.
"""

import argparse
import json
import os
import sys
import time

_T_PROCESS = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench    # noqa: E402
from benchmarks.lib import cells, op_names, trace    # noqa: E402

PENDING = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "pending_per_layer.json")


def pending():
    with open(PENDING) as f:
        return json.load(f)["per_layer"]


def add_pending():
    """The pending entries, appended to the manifest this process holds
    (the file on disk is not touched); those it already has are left."""
    have = {m["name"] for m in cells.manifest()["per_layer"]}
    cells.manifest()["per_layer"].extend(
        m for m in pending() if m["name"] not in have)


def main(argv=None):
    own = argparse.ArgumentParser(add_help=False)
    own.add_argument("--fixture")
    own, rest = own.parse_known_args(argv)
    args = bench.parse(rest + ["--trace", "1"])
    add_pending()
    keep = {}
    result = bench.measure(args, _T_PROCESS, keep)
    if args.rehearse:
        sys.exit("rehearsal at toy sizes: not a measurement")
    if own.fixture:
        window = keep["outcome"].window
        summary = trace.reduce(trace.load(window.run.trace_dir),
                               window.iterations)
        names = op_names.from_xplane(trace.xplane_path(window.run.trace_dir))
        mine = {m["name"] for m in pending()}
        with open(own.fixture, "w") as f:
            json.dump({
                "cell": args.workload, "seed": args.seed,
                "iterations": window.iterations,
                "ops": {ins: [names.get(ins), s] for ins, s in
                        summary["op_self_s"].items()},
                "expected": {k: v["value"] for k, v in
                             result["metrics"].items() if k in mine},
            }, f, indent=0)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
