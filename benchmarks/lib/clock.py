"""One clock for the program's spans and the device trace.

The program's spans are on ``time.perf_counter`` (seconds); the reduced
trace is in nanoseconds since the profiler's session began, and with
the host tracer off (``harness.Run.start_trace``) the trace holds no
host event to tie the two.  They are tied from the program's own sync
points instead.  ``offset_s`` is what a device time has to be moved by:
``perf_counter = device_ns / 1e9 + offset_s``.

From above, the moments at which the host LEARNS that a device program
ended -- each later than that program's end by a latency that is never
negative:

- the end of a ``step/retire`` span whose ``retired`` names the
  iteration of the window it blocked on;
- ``Window.t_close``, taken after one ``block_until_ready``;
- the end of a ``trainer/observe`` span, IF an ``observe`` hook read the
  loss of the step just dispatched (``LogReport`` behind a serial
  updater does).  Behind a pipelined updater it reads an older loss and
  returns while the step still runs: such a pair lies below what
  causality allows (next paragraph) by the rest of a step, and then
  none of the ``trainer/observe`` pairs is used.

From below, causality: execution ``k`` of the step program cannot start
on the device before the ``step/dispatch`` span of iteration ``k``
began.  That side is only as tight as the device is ready: where the
step waits on the device for a batch whose copy ``device_put`` left in
flight (the serial ResNet cell: 36-48 ms, PERF.md, PR 24), the bracket
is that wide.  The offset handed out is therefore the bound from above,
which is late by no more than the latency of learning, and the lower
end comes with it: a reader that puts device time down to host spans
reads at both ends and reports only what does not depend on where in
the bracket the offset lies (``host_share.idle_split_ms``).

Host and device are paired by the spans' ``step``, never by order
alone: the window's dispatch spans, by ascending ``step``, are the
window's executions of the step program, and a ``retired`` or an
observe's ``step`` is looked up among them.
"""

from benchmarks.lib.harness import log

_DISPATCH = ("step/dispatch", "step/accum_window")


def _end(ev):
    return ev["t0"] + ev["dur"]


def tie(spans, window, device):
    """``{"offset_s", "lowest_s", "bracket_s", "pairs"}`` for device
    0's entry of the trace summary (``trace.reduce()["devices"][0]``):
    the offset (the bound from above), the bound from below and their
    distance.  None where the spans cannot be paired with the step
    program's executions or the bracket is empty (the two sides
    contradict each other).  Prints one ``[clock]`` line either way."""
    runs = sorted((s, e) for n, s, e in device["modules"]
                  if n == device["step_program"])
    dispatched = {}
    for ev in spans:
        if ev["name"] in _DISPATCH and "step" in ev \
                and window.t_open <= ev["t0"] < window.t_close:
            dispatched.setdefault(ev["step"], ev)
    if not runs or len(dispatched) != len(runs):
        log("clock", offset_s=None,
            why=f"{len(dispatched)} dispatch spans in the window for "
                f"{len(runs)} executions of the step program")
        return None
    run_of = dict(zip(sorted(dispatched), runs))

    lower = [dispatched[k]["t0"] - run_of[k][0] / 1e9 for k in run_of]
    upper = [window.t_close - device["hi"] / 1e9]
    observed = []
    for ev in spans:
        if "dur" not in ev:
            continue
        if ev["name"] == "step/retire":
            run = run_of.get((ev.get("meta") or {}).get("retired"))
            if run is not None:
                upper.append(_end(ev) - run[1] / 1e9)
        elif ev["name"] == "trainer/observe":
            run = run_of.get(ev.get("step"))
            if run is not None:
                observed.append(_end(ev) - run[1] / 1e9)
    lo = max(lower)
    # an observe that did not wait for its step is no sync point
    waited = bool(observed) and min(observed) >= lo
    if waited:
        upper += observed
    hi = min(upper)
    pairs = f"{len(upper)}+{len(lower)}"
    fields = dict(bracket_us=f"{(hi - lo) * 1e6:.1f}", pairs=pairs,
                  observe_pairs=f"{len(observed)} "
                                f"{'used' if waited else 'not used'}")
    if hi < lo:
        log("clock", offset_s=None, **fields)
        return None
    log("clock", offset_s=f"{hi:.6f}", **fields)
    return {"offset_s": hi, "lowest_s": lo, "bracket_s": hi - lo,
            "pairs": pairs}


def of(ctx):
    """The tie of a traced run, worked out once for all its readers."""
    if "clock" not in ctx:
        ctx["clock"] = None if ctx["trace"] is None else tie(
            ctx["spans"], ctx["window"], ctx["trace"]["devices"][0])
    return ctx["clock"]
