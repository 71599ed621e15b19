"""The builder's trial of PR 24, not part of any cell: what the
program's tracing costs, and what the profiler's host tracer would
bring at level 1.

    python benchmarks/tools/host_tracer_trial.py <workload> <seed> <seconds> [--rehearse]

In one process, on one machine: the cell untraced with the program's
recorder disabled, enabled, enabled, disabled (median iteration each);
then traced as the harness traces it (host tracer off); then traced
with ``host_tracer_level=1``.  Of the last it prints the host planes'
events by name, whether the program's annotations are among them with
their ``step``, and the offset between the two clocks read directly
from an annotation and its span -- the check of ``lib/clock.py``'s tie,
which has only the sync points to go by.
"""

import argparse
import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench    # noqa: E402
from benchmarks.lib import clock, harness, trace    # noqa: E402
from benchmarks.lib.harness import log    # noqa: E402

REHEARSE = "--rehearse" in sys.argv


def _trace_dir(workload):
    return os.path.join(ROOT, ".bench_scratch", workload, "trace")


def _start_trace_level_1(self):
    import jax

    if not self.traced:
        return
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 1
    options.python_tracer_level = 0
    jax.profiler.start_trace(self.trace_dir, profiler_options=options)


def _phase(name, workload, seed, seconds, traced, recorder_on):
    from chainermn_tpu.utils.telemetry import get_recorder

    recorder = get_recorder()
    recorder.clear()
    (recorder.enable if recorder_on else recorder.disable)()
    keep = {}
    result = bench.measure(argparse.Namespace(
        workload=workload, seed=seed, seconds=seconds, trace=traced,
        rehearse=REHEARSE), keep=keep)
    recorder.disable()
    window = keep["outcome"].window
    intervals = window.intervals_ms
    log("trial", run=name, correct=result["correct"],
        iterations=window.iterations,
        interval_ms_median=f"{np.median(intervals):.3f}",
        interval_ms_p90=f"{np.percentile(intervals, 90):.3f}",
        spans_recorded=len(recorder),
        metrics=json.dumps({k: round(v["value"], 3)
                            for k, v in result["metrics"].items()}))
    gc.collect()
    return result, keep["outcome"]


def _planes(trace_dir, outcome):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(trace.xplane_path(trace_dir))
    log("trial", xplane_bytes=os.path.getsize(trace.xplane_path(trace_dir)))
    spans = {(ev["name"], ev.get("step")): ev for ev in outcome.spans
             if "dur" in ev}
    offsets = []
    for plane in data.planes:
        for line in plane.lines:
            names = {}
            for ev in line.events:
                names[ev.name[:48]] = names.get(ev.name[:48], 0) + 1
                if ev.name in ("step/host", "step/dispatch", "feed/put",
                               "trainer/observe"):
                    step = {k: v for k, v in ev.stats}.get("step")
                    span = spans.get((ev.name, step))
                    if span is not None:
                        offsets.append(span["t0"] - ev.start_ns / 1e9)
            common = sorted(names.items(), key=lambda kv: -kv[1])[:8]
            log("trial", plane=plane.name, line=line.name,
                events=sum(names.values()),
                transposes=sum(n for k, n in names.items()
                               if "Transpose" in k),
                common=json.dumps(common))
    return offsets


def main():
    workload, seed, seconds = \
        sys.argv[1], int(sys.argv[2]), float(sys.argv[3])
    for i, on in enumerate((False, True, True, False)):
        _phase(f"untraced recorder {'on' if on else 'off'}", workload,
               seed + i, seconds, 0, on)
    _, outcome = _phase("traced, host tracer off", workload, seed + 4,
                        seconds, 1, True)
    _planes(_trace_dir(workload), outcome)

    harness.Run.start_trace = _start_trace_level_1
    _, outcome = _phase("traced, host tracer level 1", workload,
                        seed + 5, seconds, 1, True)
    offsets = _planes(_trace_dir(workload), outcome)
    tie = None
    if not REHEARSE:    # the CPU has no device plane to tie to
        summary = trace.reduce(trace.load(_trace_dir(workload)),
                               outcome.window.iterations)
        tie = clock.tie(outcome.spans, outcome.window,
                        summary["devices"][0])
    if offsets:
        log("trial", annotation_pairs=len(offsets),
            offset_s_from_annotations=f"{np.median(offsets):.6f}",
            offset_spread_us=f"{(max(offsets) - min(offsets)) * 1e6:.1f}",
            tie_less_annotations_us=None if tie is None else
            f"{(tie['offset_s'] - np.median(offsets)) * 1e6:.1f}")
    else:
        log("trial", annotation_pairs=0)


if __name__ == "__main__":
    main()
