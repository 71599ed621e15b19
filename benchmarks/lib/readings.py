"""Arithmetic that several per-layer readers share.  A reader is a file
of its own under ``layer_metrics/``; what it reads is decided there,
how it is computed is here, once."""

import numpy as np

_STEP_SPANS = ("step/host", "step/dispatch", "step/retire")


def mfu_pct(ctx):
    """Required operations of the window's iterations over what the
    chips could do in the time the step program ran on them (its
    executions on the device's ``XLA Modules`` line, mean over the
    devices).  The layer's own number: a device that waits for the host
    between steps lowers the end-to-end rate, not this.  Recomputed
    operations do not count."""
    if ctx["trace"] is None:
        return None
    w = ctx["window"]
    devices = ctx["trace"]["devices"]
    ran_s = sum(d["step_program_s"] for d in devices) / len(devices)
    return 100 * ctx["facts"]["flops_per_unit"] * w.units * w.iterations / (
        ctx["chips"] * ctx["peaks"]["flops_per_s"] * ran_s)


def idle_pct(ctx):
    if ctx["trace"] is None:
        return None
    return 100 * ctx["trace"]["idle_share_worst"]


def hbm_gib(ctx):
    return ctx["memory_peak_bytes"] / 2 ** 30


def span_ms(ctx, name):
    """Durations (ms) of the program's spans called ``name`` that began
    inside the window."""
    w = ctx["window"]
    return [1e3 * ev["dur"] for ev in ctx["spans"]
            if ev["name"] == name and "dur" in ev
            and w.t_open <= ev["t0"] < w.t_close]


def outside_step_spans_ms(ctx):
    """Per iteration of the window: its length less the updater's own
    three spans that began in it -- the trainer's loop, its extensions
    and whatever else the host did."""
    w = ctx["window"]
    edges = np.asarray([w.t_open] + w.ends)
    inside = np.zeros(len(w.ends))
    for ev in ctx["spans"]:
        if ev["name"] in _STEP_SPANS and "dur" in ev:
            k = np.searchsorted(edges, ev["t0"], side="right") - 1
            if 0 <= k < len(inside):
                inside[k] += ev["dur"]
    return w.intervals_ms - 1e3 * inside


def per_step_ms(ctx, seconds):
    return 1e3 * seconds / ctx["window"].iterations


def kernel_seconds(ctx, pattern):
    """Device time on device 0, inside the window, of the Pallas kernels
    whose name contains ``pattern``."""
    if ctx["trace"] is None:
        return None
    wanted = {ins for ins, kernel in ctx["facts"]["kernels"].items()
              if pattern in kernel}
    ops = ctx["trace"]["devices"][0]["ops"]
    found = [e - s for n, s, e in ops if n in wanted]
    return sum(found) / 1e9 if found else None
