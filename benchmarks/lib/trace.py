"""From the profiler's ``.xplane.pb`` to numbers: device busy time,
per-op self time, kernels, collectives and their exposed part, idle gaps
named by the host annotation that encloses them.

Read with nothing but ``jax.profiler.ProfileData``, and from the device
planes alone: the host tracer is off in a traced run (PERF.md, PR 23: in
the ResNet cell it recorded 916,168 ``Transpose`` events of the feed's
host-side relayout and slowed the iteration from 0.31 to 0.84 s).  The
window is therefore found on the device's own clock: it holds the last
``iterations`` executions of the step program, and opens where the last
program before them ended.
"""

import glob
import os
import re

# as the TPU compiler names them: a reduce-scatter runs as an op called
# ``reduce_scatter.N`` after its ``op_name``, and asynchronous exchanges
# as ``async-collective-start.N`` / ``async-collective-done.N``
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "reduce_scatter", "collective-permute", "all-to-all",
               "async-collective")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
DEVICE_PLANE = "/device:TPU:"


def kernel_instructions(hlo_text):
    """``{instruction name: kernel name}`` for every Pallas kernel
    (``tpu_custom_call``) in a compiled program's text.  The trace names
    device events by instruction; the kernel's own name is in the
    instruction's ``op_name`` metadata."""
    found = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = re.search(r"%?([\w.\-]+) = ", line)
        kernel = re.search(r'op_name="([^"]*)"', line)
        if name:
            found[name.group(1)] = kernel.group(1) if kernel else ""
    return found


def xplane_path(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def instruction(event_name):
    """The HLO instruction's own name: the profiler names a device event
    by the instruction's whole text (``%fusion.3 = bf16[...] fusion(...``)."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def module(event_name):
    """``jit_step(4828622200550264098)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def load(trace_dir):
    """``{plane name: {line name: [(name, start_ns, end_ns)]}}``, cut to
    what the reduction reads: each device's ``XLA Ops`` line under the
    instructions' names and its ``XLA Modules`` line under the
    programs' names."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path(trace_dir))
    planes = {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        lines = {}
        for line in plane.lines:
            rename = {_OPS_LINE: instruction, _MODULES_LINE: module}.get(
                line.name)
            if rename is not None:
                lines[line.name] = [
                    (rename(ev.name), ev.start_ns,
                     ev.start_ns + ev.duration_ns) for ev in line.events]
        planes[plane.name] = lines
    return planes


def structure(trace_dir, top=6):
    """For a person looking at a trace by hand: every plane and line,
    how many events, the commonest names."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane_path(trace_dir)).planes:
        for line in plane.lines:
            names = {}
            for ev in line.events:
                names[ev.name[:60]] = names.get(ev.name[:60], 0) + 1
            common = sorted(names.items(), key=lambda kv: -kv[1])[:top]
            out.append((plane.name, line.name, sum(names.values()), common))
    return out


def _union(intervals):
    """Merged, sorted, disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals):
    return sum(e - s for s, e in intervals)


def _subtract(intervals, cover):
    """The part of the (disjoint, sorted) ``intervals`` outside the
    (disjoint, sorted) ``cover``."""
    out, j = [], 0
    for s, e in intervals:
        while j < len(cover) and cover[j][1] <= s:
            j += 1
        k, at = j, s
        while k < len(cover) and cover[k][0] < e:
            if cover[k][0] > at:
                out.append([at, cover[k][0]])
            at = max(at, cover[k][1])
            k += 1
        if at < e:
            out.append([at, e])
    return out


def _clip(events, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def _self_times(events):
    """``{name: ns}`` with each event's time less the events nested in
    it (a ``while`` encloses its body's ops on the same line)."""
    out, stack = {}, []
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        if stack:
            parent = stack[-1]
            out[parent[0]] -= min(e, parent[2]) - s
        out[name] = out.get(name, 0) + (e - s)
        stack.append((name, s, e))
    return out


def is_collective(name):
    return name.startswith(COLLECTIVES)


def _collective_intervals(events):
    """One interval per collective: a synchronous op is its own event;
    an asynchronous pair runs from its ``-start`` to the end of the
    ``-done`` that follows it (pairs of one kind retire in order)."""
    out, open_starts = [], {}
    for name, s, e in sorted(events, key=lambda ev: ev[1]):
        if not is_collective(name):
            continue
        kind = next(c for c in COLLECTIVES if name.startswith(c))
        if "-start" in name:
            open_starts.setdefault(kind, []).append(s)
        elif "-done" in name:
            starts = open_starts.get(kind)
            out.append((starts.pop(0) if starts else s, e))
        else:
            out.append((s, e))
    return out


def _window(modules, iterations):
    """``(lo, hi, step program)`` on one device's clock: the step
    program is the one that took most time; the window holds its last
    ``iterations`` executions and whatever ran after them, and opens
    where the last program before them ended."""
    total = {}
    for name, s, e in modules:
        total[name] = total.get(name, 0) + (e - s)
    step = max(total, key=total.get)
    runs = sorted(ev for ev in modules if ev[0] == step)
    if len(runs) <= iterations:
        raise ValueError(
            f"the trace holds {len(runs)} executions of {step}; the window's "
            f"{iterations} and one before them are needed")
    first = runs[-iterations]
    lo = max(e for _, s, e in modules if e <= first[1])
    return lo, max(e for _, _, e in modules), step


def reduce(planes, iterations):
    """The summary every per-layer reader gets.  Seconds throughout."""
    devices = []
    for name in sorted(p for p in planes if p.startswith(DEVICE_PLANE)):
        lines = planes[name]
        lo, hi, step = _window(lines.get(_MODULES_LINE, []), iterations)
        ops = _clip(lines.get(_OPS_LINE, []), lo, hi)
        modules = _clip(lines[_MODULES_LINE], lo, hi)
        busy = _union((s, e) for _, s, e in ops)
        coll = _union(_collective_intervals(ops))
        other = _union((s, e) for n, s, e in ops if not is_collective(n)
                       and n.split(".")[0] != "while")
        devices.append({
            "name": name, "ops": ops, "busy": busy, "lo": lo, "hi": hi,
            "step_program": step,
            "modules": modules,
            # how long the step program itself was on the device
            "step_program_s": sum(
                e - b for n, b, e in modules if n == step) / 1e9,
            "window_s": (hi - lo) / 1e9,
            "busy_s": _length(busy) / 1e9,
            "collective_s": _length(coll) / 1e9,
            "collective_exposed_s": _length(_subtract(coll, other)) / 1e9,
        })
    if not devices or not any(d["ops"] for d in devices):
        raise ValueError("no operation ran on a device inside the window: "
                         f"planes {sorted(planes)}")

    first = devices[0]
    self_ns = _self_times(first["ops"])
    # an idle gap is named by the programs on either side of it: inside
    # Trainer.run() the benchmark can write no finer name
    gaps = {}
    programs = sorted(first["modules"], key=lambda ev: ev[1])
    for s, e in _subtract([[first["lo"], first["hi"]]], first["busy"]):
        # by the gap's middle: an op can begin a few ns before the
        # program's own event, and a program of no ops lies inside a gap
        mid = (s + e) // 2
        before = [m for m in programs if m[1] <= mid]
        after = [m for m in programs if m[1] > mid]
        inside = before and before[-1][2] > mid
        label = (f"inside {before[-1][0]}" if inside else
                 f"{before[-1][0] if before else 'window opens'} -> "
                 f"{after[0][0] if after else 'window closes'}")
        gaps[label] = gaps.get(label, 0) + (e - s)
    return {
        "window_s": sum(d["window_s"] for d in devices) / len(devices),
        "busy_s": sum(d["busy_s"] for d in devices) / len(devices),
        "idle_share_worst": max(1 - d["busy_s"] / d["window_s"]
                                for d in devices),
        "devices": devices,
        "op_self_s": {k: v / 1e9 for k, v in self_ns.items()},
        "idle_gaps_s": {k: v / 1e9 for k, v in gaps.items()},
    }


def breakdown(summary, kernels=None, top=10):
    """The contract's ``breakdown``: the device ops that took most
    (self) time under stable names (instruction numbers dropped,
    kernels by their own name) and the idle time by the programs on
    either side of the gap."""
    kernels = kernels or {}
    by_name = {}
    for name, s in summary["op_self_s"].items():
        stable = kernels.get(name) or re.sub(r"[.\d]+$", "", name)
        by_name[stable] = by_name.get(stable, 0) + s
    def ranked(d):
        return [[k, v] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": ranked(by_name),
            "idle_gaps": ranked(summary["idle_gaps_s"])}
