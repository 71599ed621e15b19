"""Read the numbers a cell's limits are set from, at the cell's own
size, many seeds in one process (set-up is long, the readings need no
measured window):

    python benchmarks/tools/limits.py --workload <name> --seeds 1,2,3 \\
        [--sound] [--control float8_e4m3fn]

``--sound`` drives the benchmark itself for two seconds a seed and
prints the gaps between the program and the plain reference.
``--control`` puts the plain reference, computed in that lower precision,
in the program's place and prints its gaps.  The benchmark's own runs
never run this; the driver's check does not either.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench    # noqa: E402
from benchmarks.lib import cells, check    # noqa: E402
from benchmarks.lib.harness import CompileCounter, Run, log    # noqa: E402


def control_readings(workload, seed, precision, rehearse=False):
    """``(control's, reference's)`` readings for one seed: the reference
    in ``precision`` and the reference proper, on the cell's own weights
    and first batches."""
    import jax

    cell, config, traffic = cells.load_cell(workload, rehearse)
    run = Run(cell=cell, config=config, traffic=traffic,
              devices=jax.devices()[:cell["chips"]], seed=seed, seconds=0,
              trace_dir="", scratch="", compiles=CompileCounter())
    driver = cells.module("drivers", traffic["driver"])
    reference, make_params, batches, _ = driver.reference_job(run)
    sound = reference.follow(config, make_params, batches)
    control = reference.follow(config, make_params, batches, precision)
    return control, sound


def control_gaps(workload, seed, precision, rehearse=False):
    return {k: v[0] for k, v in check.gaps(*control_readings(
        workload, seed, precision, rehearse)).items()}


def report(kind, seed, dump, got, reference, **fields):
    """One line a seed; with ``--dump`` also every leaf's gap, as a JSON
    line, for choosing which number to compare."""
    log(kind, seed=seed, **fields,
        **{k: v[0] for k, v in check.gaps(got, reference).items()})
    if dump:
        with open(dump, "a") as f:
            f.write(json.dumps({
                "kind": kind, "seed": seed, "losses": got["losses"],
                "reference_losses": reference["losses"],
                "grad": check.leaf_gaps(got["grad_norms"],
                                        reference["grad_norms"]),
                "delta": check.leaf_gaps(got["delta_norms"],
                                         reference["delta_norms"]),
                "reference_grad_norms": reference["grad_norms"]}) + "\n")


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--sound", action="store_true")
    p.add_argument("--control", default="")
    p.add_argument("--dump", default="", help="append per-leaf gaps here")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    for seed in (int(s) for s in args.seeds.split(",")):
        if args.sound:
            keep = {}
            result = bench.measure(argparse.Namespace(
                workload=args.workload, seed=seed, seconds=2, trace=0,
                rehearse=args.rehearse), keep=keep)
            report("sound", seed, args.dump, *keep["outcome"].readings,
                   correct=result["correct"])
        if args.control:
            report("control", seed, args.dump, *control_readings(
                args.workload, seed, args.control, args.rehearse),
                precision=args.control)


if __name__ == "__main__":
    main()
