"""What the host did with each iteration, from the program's finer
spans (``feed/pull``, ``feed/convert``, ``feed/put``,
``trainer/observe``), and the device's idle time put down to them on
the tied clock (``clock.py``).  A program without these spans (an older
commit) gives every reader here nothing to read."""

import numpy as np

from benchmarks.lib import clock, trace
from benchmarks.lib.harness import log


def per_iteration_ms(ctx, name):
    """Median over the window's iterations of the summed durations (ms)
    of the spans called ``name`` that began in the iteration, whichever
    thread recorded them; None where the window holds no such span."""
    w = ctx["window"]
    edges = np.asarray([w.t_open] + w.ends)
    inside = np.zeros(len(w.ends))
    found = False
    for ev in ctx["spans"]:
        if ev["name"] == name and "dur" in ev:
            k = np.searchsorted(edges, ev["t0"], side="right") - 1
            if 0 <= k < len(inside):
                inside[k] += ev["dur"]
                found = True
    return float(np.median(inside)) * 1e3 if found else None


def _within(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def _covered(intervals, cover):
    """Length of the part of ``intervals`` inside ``cover`` (both
    disjoint and sorted)."""
    return trace._length(intervals) - trace._length(
        trace._subtract(intervals, cover))


def _split_at(ctx, offset_s):
    """The three parts (ms, medians over the window's iterations) with
    the host's spans moved onto the device's clock by ``offset_s``, or
    None where the window holds no ``step/host`` or no
    ``trainer/observe`` span."""
    w, device = ctx["window"], ctx["trace"]["devices"][0]
    lo, hi = device["lo"], device["hi"]

    def on_device(name):
        return trace._union(
            ((ev["t0"] - offset_s) * 1e9,
             (ev["t0"] + ev["dur"] - offset_s) * 1e9)
            for ev in ctx["spans"] if ev["name"] == name and "dur" in ev
            and w.t_open <= ev["t0"] < w.t_close)

    feed, observe = on_device("step/host"), on_device("trainer/observe")
    if not feed or not observe:
        return None
    idle = trace._subtract([[lo, hi]], device["busy"])
    cuts = np.clip([(t - offset_s) * 1e9 for t in w.ends[:-1]], lo, hi)
    edges = [lo] + list(np.maximum.accumulate(cuts)) + [hi]
    parts = {"feed": [], "observe": [], "other": []}
    for a, b in zip(edges, edges[1:]):
        gaps = _within(idle, a, b)
        in_feed, in_observe = _covered(gaps, feed), _covered(gaps, observe)
        parts["feed"].append(in_feed)
        parts["observe"].append(in_observe)
        parts["other"].append(trace._length(gaps) - in_feed - in_observe)
    return {k: float(np.median(v)) / 1e6 for k, v in parts.items()}


def idle_split_ms(ctx):
    """``{"feed", "observe", "other"}``: device 0's idle time inside
    the window that falls, on the tied clock, within a ``step/host``
    span, within a ``trainer/observe`` span, and under neither -- each
    the median (ms) over the window's iterations, which on the device's
    clock are cut where the host ended them.  Read at both ends of the
    clock's bracket: None where a part differs between the two by more
    than 1 % of the iteration (the tie is too loose for this run's
    spans), and None where the three are apart from the idle time an
    iteration behind ``device.idle_pct.*`` by more than that.  None too
    without a trace, a tie, or either kind of span."""
    tie = clock.of(ctx)
    if tie is None:
        return None
    split = _split_at(ctx, tie["offset_s"])
    if split is None:
        return None
    other_end = _split_at(ctx, tie["lowest_s"])
    device, n = ctx["trace"]["devices"][0], ctx["window"].iterations
    allowed_ms = 0.01 * 1e3 * device["window_s"] / n
    idle_ms = 1e3 * (device["window_s"] - device["busy_s"]) / n
    moves = max(abs(split[k] - other_end[k]) for k in split)
    apart = abs(sum(split.values()) - idle_ms)
    log("idle", **{k: f"{v:.3f}" for k, v in split.items()},
        idle_ms=f"{idle_ms:.3f}", apart_ms=f"{apart:.3f}",
        moves_across_bracket_ms=f"{moves:.3f}",
        allowed_ms=f"{allowed_ms:.3f}")
    return split if max(moves, apart) <= allowed_ms else None


def idle_ms(ctx, part):
    """One part of the split, which is worked out once for the three
    readers of a run."""
    if "idle_split" not in ctx:
        ctx["idle_split"] = idle_split_ms(ctx)
    split = ctx["idle_split"]
    return None if split is None else split[part]
