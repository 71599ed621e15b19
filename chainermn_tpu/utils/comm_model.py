"""Communication-volume model: per-collective bytes/step from compiled HLO.

The reference's scaling story (ChainerMN's ~90%-efficiency ImageNet
claims, SURVEY.md §6) was argued from measured multi-node runs; this
container has ONE real chip, so the equivalent evidence chain here is
analytic: walk a compiled step's HLO for collective ops, count the bytes
each moves, convert to wire time with the standard ring formulas and the
interconnect's published bandwidth, and compare against the measured
single-chip step time.  The four-chip cell reads ``exchange.exposed_ms``.

Axis attribution: a composed-mesh HLO doesn't name mesh axes, so
:func:`axis_collective_report` compiles the SAME step on single-active-
axis virtual meshes (e.g. ``data=8``, then ``model=8``) — every
collective in that program belongs to that axis.  This is exact for the
per-axis *volume model* because collective volume depends only on the
axis being reduced/gathered over, not on which other axes exist.

Wire-cost conventions (ring algorithms, ``n`` = axis size, ``s`` =
tensor bytes): all-reduce moves ``2s(n-1)/n`` per device, all-gather and
reduce-scatter ``s(n-1)/n`` (s = the FULL tensor), all-to-all
``s(n-1)/n``, collective-permute ``s``.  XLA may pick tree variants on
real topologies; ring is the bandwidth-optimal baseline the model uses.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass
from typing import Dict, Optional

__all__ = [
    "CollectiveStats",
    "LinkParams",
    "PRIMITIVE_WIRE_KINDS",
    "collective_stats",
    "stablehlo_collective_stats",
    "primitive_cost",
    "program_cost",
    "wire_bytes_per_device",
    "axis_collective_report",
    "choose_accum_steps",
    "choose_bucket_bytes",
    "choose_gather_prefetch_depth",
    "choose_prefetch_depth",
    "fused_collective_budget",
    "overlap_exposed_time",
    "assert_fused_collectives",
    "assert_accum_collectives",
    "assert_overlap_collectives",
]

# Interconnect defaults for choose_bucket_bytes: per-collective launch
# latency and per-device ring bandwidth.  ICI-flavoured (TPU v4/v5
# publish ~100 GB/s per link; a few microseconds to get a collective
# onto the wire) — pass measured values for other fabrics (DCN: ~25 us,
# ~12.5 GB/s per NIC).
_DEFAULT_LATENCY_S = 2e-6
_DEFAULT_BANDWIDTH = 90e9


@dataclass(frozen=True)
class LinkParams:
    """Interconnect constants the analytic models consume: per-collective
    launch latency (seconds) and per-device ring bandwidth (bytes/s).

    The defaults baked into :func:`choose_bucket_bytes` /
    :func:`choose_accum_steps` are PUBLISHED ICI numbers; this carrier
    exists so the measured autotuner (``utils/autotune.py``) can hand
    those models constants fitted from its own probe timings on the
    live machine — the plan then both picks the exchange strategy
    empirically AND recalibrates every later analytic decision
    (``choose_bucket_bytes``, ``choose_accum_steps``) to the real
    fabric.
    """

    latency_s: float = _DEFAULT_LATENCY_S
    bandwidth_bytes_per_s: float = _DEFAULT_BANDWIDTH

    @classmethod
    def from_probes(cls, samples) -> "LinkParams":
        """Least-squares fit of ``t = launches * alpha + wire_bytes /
        beta`` over probe timings.

        ``samples`` is an iterable of ``(n_launches, wire_bytes,
        seconds)`` rows — one per timed exchange candidate (the
        autotuner knows each candidate's collective count and ring
        bytes analytically, and measures its wall time).  Solves the
        2-unknown normal equations for ``alpha`` (latency) and
        ``1/beta`` (inverse bandwidth); a degenerate or unphysical fit
        (fewer than 2 distinct rows, singular system, non-positive
        constants) falls back to the published defaults — measured
        constants must never be WORSE than no measurement.
        """
        rows = [(float(k), float(b), float(t)) for k, b, t in samples
                if t > 0 and (k > 0 or b > 0)]
        if len(rows) < 2:
            return cls()
        # normal equations for t ~ k*alpha + b*inv_beta
        skk = sum(k * k for k, _, _ in rows)
        sbb = sum(b * b for _, b, _ in rows)
        skb = sum(k * b for k, b, _ in rows)
        skt = sum(k * t for k, _, t in rows)
        sbt = sum(b * t for _, b, t in rows)
        det = skk * sbb - skb * skb
        if abs(det) < 1e-30:
            return cls()
        alpha = (skt * sbb - sbt * skb) / det
        inv_beta = (sbt * skk - skt * skb) / det
        if alpha <= 0 or inv_beta <= 0:
            return cls()
        return cls(latency_s=alpha, bandwidth_bytes_per_s=1.0 / inv_beta)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
          "collective-permute")

# one HLO instruction: "%name = SHAPE kind(...)" where SHAPE is a single
# "f32[8,16]{...}" or a tuple "(f32[8]{..}, bf16[4,4]{..})"; -start
# variants are the async halves (count those, skip -done duplicates)
_INSTR_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[^\]]*\]\S*)\s+"
    r"(" + "|".join(_KINDS) + r")(-start)?\(")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^=]*?\})\}")
# iota form: replica_groups=[num_groups,group_size]<=[...]
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")


def _shape_bytes(shape_str: str, is_start: bool = False) -> int:
    shapes = _SHAPE_RE.findall(shape_str)
    if is_start and len(shapes) >= 2:
        # async start ops carry (operands, results, context...) in one
        # tuple; counting the whole tuple would double the volume.
        # Element 1 is the result buffer (element 0 the operand).
        shapes = shapes[1:2]
    total = 0
    for dtype, dims in shapes:
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str) -> Optional[int]:
    m = _GROUPS_RE.search(line)
    if m:
        first = m.group(1).split("}")[0].lstrip("{")
        ids = [t for t in first.split(",") if t.strip()]
        return len(ids) or None
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        return int(m.group(2)) or None
    return None


@dataclass
class CollectiveStats:
    """Aggregate of one collective kind in one compiled program."""

    kind: str
    count: int = 0
    bytes: int = 0              # summed tensor bytes across call sites
    group_size: Optional[int] = None   # replica-group size (if uniform)
    looped: int = 0             # call sites inside a while-loop body:
    #                             they run once PER TRIP, so a per-window
    #                             count must treat them separately (the
    #                             accumulation proof hinges on this)
    async_depth: int = 0        # async -start/-done pairs with at least
    #                             one OTHER instruction scheduled between
    #                             the halves: collectives the backend
    #                             actually runs concurrently with compute
    #                             (sync lowerings — XLA:CPU today — and
    #                             back-to-back start;done pairs score 0)

    def wire_bytes(self, axis_size: Optional[int] = None) -> float:
        n = axis_size or self.group_size
        if n is None or n < 1:
            # never guess: a silently-wrong group size corrupts the
            # whole wire-volume evidence chain
            raise ValueError(
                "replica group size unknown (unparsed or non-uniform "
                "replica_groups); pass axis_size explicitly")
        full = self.bytes
        if self.kind == "reduce-scatter":
            # HLO records the SCATTERED output shape (1/n of the full
            # tensor); the wire formulas want the full tensor
            full = self.bytes * n
        return wire_bytes_per_device(self.kind, full, n)


def wire_bytes_per_device(kind: str, tensor_bytes: float, n: int) -> float:
    """Ring-algorithm bytes each device moves for ``tensor_bytes`` of
    payload over an ``n``-member group (see module docstring)."""
    if n <= 1:
        return 0.0
    frac = (n - 1) / n
    if kind == "all-reduce":
        return 2.0 * tensor_bytes * frac
    if kind in ("all-gather", "reduce-scatter", "all-to-all"):
        return tensor_bytes * frac
    if kind == "collective-permute":
        return float(tensor_bytes)
    raise ValueError(f"unknown collective kind {kind!r}")


# ---------------------------------------------------------------------
# per-primitive cost terms for the collective-plan IR
# (``ops.plan_ir``): maps each wire primitive to its ring wire-bytes
# formula so the pattern autotuner's pruning covers all-to-all and
# ppermute/send_recv, not just the allreduce strategy space
# ---------------------------------------------------------------------

PRIMITIVE_WIRE_KINDS = {
    "all_reduce": "all-reduce",
    "reduce_scatter": "reduce-scatter",
    "all_gather": "all-gather",
    "all_to_all": "all-to-all",
    "ppermute": "collective-permute",
    "send_recv": "collective-permute",
}


def primitive_cost(op: str, tensor_bytes: float, axis_size: int, *,
                   launches: int = 1, link: Optional[LinkParams] = None) \
        -> float:
    """Modeled seconds for one plan-IR primitive step moving
    ``tensor_bytes`` of payload over an ``axis_size``-member group in
    ``launches`` separate collective launches.  Non-wire primitives
    (``fuse`` / ``cast_wire`` / ``barrier``) cost zero — they are
    on-device data movement the wire model does not see."""
    kind = PRIMITIVE_WIRE_KINDS.get(op)
    if kind is None:
        return 0.0
    link = link or LinkParams()
    wire = wire_bytes_per_device(kind, float(tensor_bytes),
                                 int(axis_size))
    return (max(int(launches), 1) * link.latency_s
            + wire / link.bandwidth_bytes_per_s)


def program_cost(steps, tensor_bytes: float, axis_sizes: Dict[str, int],
                 *, link: Optional[LinkParams] = None) -> float:
    """Modeled seconds for a whole plan-IR program: the sum of its
    steps' :func:`primitive_cost` terms.  ``steps`` is an iterable of
    dict-likes with ``op``, ``axis`` (a role key into ``axis_sizes``),
    and optional ``launches`` / ``bytes_scale`` (wire-dtype shrink)
    enrichments the autotuner derives from the payload signature."""
    total = 0.0
    for st in steps:
        n = int(axis_sizes.get(st.get("axis") or "main", 1))
        total += primitive_cost(
            st["op"], float(tensor_bytes) * float(
                st.get("bytes_scale", 1.0)),
            n, launches=int(st.get("launches", 1)), link=link)
    return total


# computation header: "%name (params) -> type {" (possibly "ENTRY %...")
# — instruction lines carry "name = " before the first "(", headers
# never do, which is how the two are told apart
_COMP_HEADER_RE = re.compile(r"^\s*(?:ENTRY\s+)?%([\w.\-]+)\s*\(")
# computations an instruction hands control to (while bodies/conditions,
# fusions, reducers, conditionals, async wrappers)
_COMP_REF_RE = re.compile(
    r"(?:body|condition|to_apply|calls|true_computation|"
    r"false_computation)=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
# body AND condition both execute once per trip (the condition once
# more); a collective in either is a per-iteration collective
_WHILE_PARTS_RE = re.compile(
    r"=[^=]*\bwhile\(.*?(?:body|condition)=%?([\w.\-]+)"
    r"(?:.*?(?:body|condition)=%?([\w.\-]+))?")


def _split_computations(text: str) -> Dict[str, list]:
    """HLO module text -> {computation name: [instruction lines]}.
    Lines outside any recognised computation land under ``""``."""
    comps: Dict[str, list] = {}
    current = ""
    for line in text.splitlines():
        head = _COMP_HEADER_RE.match(line)
        if head is not None and "=" not in line.split("(", 1)[0]:
            current = head.group(1)
            comps.setdefault(current, [])
            continue
        if line.strip().startswith("}"):
            current = ""
            continue
        comps.setdefault(current, []).append(line)
    return comps


def _loop_body_computations(comps: Dict[str, list]) -> set:
    """Names of computations reachable from any ``while`` body or
    condition — a collective there executes once per trip, not once
    per call."""
    refs: Dict[str, set] = {}
    bodies: set = set()
    for name, lines in comps.items():
        refs[name] = set()
        for line in lines:
            w = _WHILE_PARTS_RE.search(line)
            if w:
                bodies.update(g for g in w.groups() if g)
            refs[name].update(_COMP_REF_RE.findall(line))
            for blob in _BRANCHES_RE.findall(line):
                refs[name].update(
                    t.strip().lstrip("%") for t in blob.split(",")
                    if t.strip())
    reach, frontier = set(), list(bodies)
    while frontier:
        c = frontier.pop()
        if c in reach:
            continue
        reach.add(c)
        frontier.extend(refs.get(c, ()))
    return reach


_LHS_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_DONE_RE = re.compile(r"(" + "|".join(_KINDS) + r")-done\(")


def _hlo_texts(compiled) -> list:
    """The optimised (scheduled) HLO module texts of a
    ``jax.stages.Compiled`` — instruction order in each computation is
    the backend's execution schedule, which is what the overlap proof
    reads."""
    try:
        return [m.to_string() for m in compiled.runtime_executable()
                .hlo_modules()]
    except Exception:
        return [compiled.as_text()]


def collective_stats(compiled) -> Dict[str, CollectiveStats]:
    """Parse a ``jax.stages.Compiled``'s HLO for collectives.

    Returns ``{kind: CollectiveStats}``.  Bytes are the OUTPUT tensor
    sizes at each call site (for all-gather that is the gathered size,
    matching the wire formulas' conventions); async ``-start``/``-done``
    pairs are counted once.  A collective inside a ``while`` body (e.g.
    a pipeline scan) appears once in HLO but runs per iteration — such
    call sites are tallied in ``.looped`` (as well as ``.count``), so
    callers can scale by the trip count, and
    :func:`assert_accum_collectives` can prove a scan body exchanges
    NOTHING.

    Async depth: every ``-start`` whose matching ``-done`` is scheduled
    with at least one other instruction between the halves bumps its
    kind's ``.async_depth`` — the count of collectives the backend
    actually overlaps with other work, as opposed to merely emitting
    (:func:`assert_overlap_collectives` reads this alongside the
    schedule-position evidence).
    """
    out: Dict[str, CollectiveStats] = {}
    for text in _hlo_texts(compiled):
        comps = _split_computations(text)
        looped_comps = _loop_body_computations(comps)
        for comp_name, lines in comps.items():
            in_loop = comp_name in looped_comps
            pending: Dict[str, tuple] = {}    # lhs -> (stats, instr_idx)
            n_instr = 0
            for line in lines:
                lhs = _LHS_RE.match(line)
                if lhs is not None:
                    n_instr += 1
                if pending and _DONE_RE.search(line):
                    for name in list(pending):
                        # exact-token match: HLO names may contain
                        # [\w.-], and XLA's ".N" suffixing makes one
                        # start's name a PREFIX of another's — a \b
                        # boundary would pop %all-reduce-start on the
                        # done line of %all-reduce-start.1
                        if re.search(r"%" + re.escape(name)
                                     + r"(?![\w.\-])", line):
                            st, s_idx = pending.pop(name)
                            if n_instr - s_idx > 1:
                                st.async_depth += 1
                            break
                m = _INSTR_RE.search(line)
                if not m:
                    continue
                shape_str, kind = m.group(1), m.group(2)
                g = _group_size(line)
                if g == 1:
                    # singleton replica groups come from size-1 mesh axes
                    # (the one-code-path-for-every-mesh-shape discipline);
                    # they move zero wire bytes — skip, don't pollute
                    continue
                st = out.setdefault(kind, CollectiveStats(kind))
                st.count += 1
                st.looped += int(in_loop)
                st.bytes += _shape_bytes(shape_str,
                                         is_start=bool(m.group(3)))
                if g is not None:
                    st.group_size = g if st.group_size in (None, g) else -1
                if m.group(3) and lhs is not None:
                    pending[lhs.group(1)] = (st, n_instr)
    return out


_SHLO_KIND = {
    "all_reduce": "all-reduce", "all_gather": "all-gather",
    "reduce_scatter": "reduce-scatter", "all_to_all": "all-to-all",
    "collective_permute": "collective-permute",
}
_SHLO_RE = re.compile(
    r"stablehlo\.(" + "|".join(_SHLO_KIND) + r")\"?[(<]")
_SHLO_TENSOR_RE = re.compile(r"tensor<([0-9x]*)x?([a-z][a-z0-9]*)>")
_SHLO_DTYPE_BYTES = {
    "i1": 1, "i8": 1, "ui8": 1, "f8e4m3": 1, "f8e5m2": 1,
    "i16": 2, "ui16": 2, "f16": 2, "bf16": 2,
    "i32": 4, "ui32": 4, "f32": 4,
    "i64": 8, "ui64": 8, "f64": 8,
}
_SHLO_GROUPS_RE = re.compile(
    r"replica_groups\s*=\s*dense<[^>]*>\s*:\s*tensor<([0-9]+)x([0-9]+)x")
_SHLO_FUNC_RE = re.compile(r"func\.func\b[^@]*@([\w$.\-]+)\s*\(")
_SHLO_CALL_RE = re.compile(r"\bcall\s+@([\w$.\-]+)")


def stablehlo_collective_stats(lowered_text: str) \
        -> Dict[str, CollectiveStats]:
    """Like :func:`collective_stats` but over ``fn.lower(...).as_text()``
    (StableHLO) — the program JAX hands the compiler, BEFORE backend
    legalisation.  This is the dtype-true view: XLA:CPU widens bf16
    collectives to f32 (no bf16 kernels), so wire-compression modelling
    must read StableHLO; the optimised-HLO parser remains the
    backend-truth cross-check for counts.  Caveat: pre-optimisation,
    so collectives that XLA would DCE still show up here.
    """
    out: Dict[str, CollectiveStats] = {}
    lines = lowered_text.splitlines()
    # Loop attribution needs TWO mechanisms in StableHLO: the while op
    # carries cond/body as INLINE regions (a brace-depth interval — a
    # stack of [depth-before-the-while, region-has-opened] entries,
    # nesting-safe, opened-flag surviving the pretty form whose region
    # braces open on later lines), but jax outlines scan bodies into
    # private func.funcs the while region merely `call`s — so functions
    # transitively reachable from any in-while call site are looped
    # too.  Structural pre-pass; the collective pass below reads it.
    depth = 0
    while_stack: list = []
    cur_fn = ""
    line_ctx = []                     # (enclosing fn, inline-in-while)
    fn_calls: Dict[str, set] = {}     # fn -> {callee}
    looped_seed = set()               # callees called from a while
    for line in lines:
        fm = _SHLO_FUNC_RE.search(line)
        if fm:
            cur_fn = fm.group(1)
            while_stack = []
        in_while = bool(while_stack)
        if "stablehlo.while" in line:
            while_stack.append([depth, "{" in line])
        depth += line.count("{") - line.count("}")
        for entry in while_stack:
            if depth > entry[0]:
                entry[1] = True
        while while_stack and while_stack[-1][1] \
                and depth <= while_stack[-1][0]:
            while_stack.pop()
        cm = _SHLO_CALL_RE.search(line)
        if cm:
            fn_calls.setdefault(cur_fn, set()).add(cm.group(1))
            if in_while:
                looped_seed.add(cm.group(1))
        line_ctx.append((cur_fn, in_while))
    looped_fns, frontier = set(), list(looped_seed)
    while frontier:
        f = frontier.pop()
        if f in looped_fns:
            continue
        looped_fns.add(f)
        frontier.extend(fn_calls.get(f, ()))
    for i, line in enumerate(lines):
        m = _SHLO_RE.search(line)
        if not m:
            continue
        kind = _SHLO_KIND[m.group(1)]
        gm = _SHLO_GROUPS_RE.search(line)
        gsize = int(gm.group(2)) if gm else None
        if gsize == 1:
            continue        # size-1 mesh axis: zero-wire no-op
        # Result type: region-carrying ops (all_reduce/reduce_scatter
        # wrap their reduction computation in `({ ... })`) put the
        # `(operand) -> result` signature on the line that CLOSES the
        # region, not the op line — and the op line's last tensor<>
        # would be the replica_groups attribute (i64!).  Scan forward
        # to the signature line when `->` isn't present here.
        sig = line
        if "->" not in sig:
            for j in range(i + 1, min(i + 50, len(lines))):
                if "}) :" in lines[j] and "->" in lines[j]:
                    sig = lines[j]
                    break
            else:
                continue
        tail = sig.split("->", 1)[1]
        shapes = _SHLO_TENSOR_RE.findall(tail)
        if not shapes:
            continue
        dims_s, dtype = shapes[0]
        if dtype not in _SHLO_DTYPE_BYTES:
            continue
        n = 1
        for d in dims_s.split("x"):
            if d:
                n *= int(d)
        fn, inline_in_while = line_ctx[i]
        st = out.setdefault(kind, CollectiveStats(kind))
        st.count += 1
        st.looped += int(inline_in_while or fn in looped_fns)
        st.bytes += n * _SHLO_DTYPE_BYTES[dtype]
        if gsize is not None:
            st.group_size = gsize if st.group_size in (None, gsize) \
                else -1
    return out


def choose_bucket_bytes(
    total_bytes: float,
    axis_size: int,
    latency_s: float = _DEFAULT_LATENCY_S,
    bandwidth_bytes_per_s: float = _DEFAULT_BANDWIDTH,
    min_bucket: int = 256 * 1024,
    link: Optional[LinkParams] = None,
) -> int:
    """Principled fused-allreduce bucket size from the latency-bandwidth
    model — the ``allreduce_grad_dtype``-era tuning knob made analytic.

    With ``k = ceil(G/b)`` buckets over ``G`` total gradient bytes, the
    exposed cost the bucket size controls is

        ``T(b) = (G/b) * alpha  +  2 b (n-1)/(n * beta)``

    — every bucket pays launch latency ``alpha``, while only the *last*
    bucket's ring time ``2b(n-1)/(n*beta)`` is exposed once buckets
    pipeline against compute/each other (one big bucket maximally delays
    the first byte; per-leaf buckets pay latency hundreds of times —
    exactly the regime this subsystem replaces).  Minimising T gives

        ``b* = sqrt( G * alpha * n * beta / (2 (n-1)) )``

    clamped to ``[min_bucket, G]``.  Defaults model ICI; pass measured
    ``latency_s``/``bandwidth_bytes_per_s`` for other interconnects, or
    a :class:`LinkParams` via ``link`` (e.g. ``plan.link`` from the
    measured autotuner) which overrides both.
    """
    if link is not None:
        latency_s = link.latency_s
        bandwidth_bytes_per_s = link.bandwidth_bytes_per_s
    if total_bytes <= 0:
        return min_bucket
    if axis_size <= 1:
        return max(min_bucket, int(total_bytes))
    frac = 2.0 * (axis_size - 1) / axis_size
    b_star = (total_bytes * latency_s * bandwidth_bytes_per_s / frac) ** 0.5
    return int(min(max(b_star, min_bucket), total_bytes))


def choose_prefetch_depth(host_time_s: float, device_time_s: float,
                          jitter: float = 0.5, min_depth: int = 2,
                          max_depth: int = 8) -> int:
    """Slot count for the prefetch ring (``PrefetchIterator(depth=...)``)
    from the measured host-assembly vs device-step times (the updater's
    ``main/host_time`` / ``main/device_time``, or the flight
    recorder's ``step/host`` / ``step/retire`` spans).

    The pipeline model: one background worker assembles windows at rate
    ``1/h`` while the device consumes at ``1/d``.  With ``rho = h/d``:

    - **device-bound** (``rho <= 1``): the worker outruns the consumer,
      so two slots — one being consumed, one staged — already hide ALL
      host work; extra depth only adds host memory.  Depth stays at
      ``min_depth`` (= 2, classic double buffering).
    - **host-bound** (``rho > 1``): no depth makes a single worker
      faster — the pipe throughput is pinned at ``1/h`` — but depth
      absorbs *burstiness*: a slow pull (page-cache miss, decode spike)
      up to ``depth - 1`` windows long passes without stalling the
      device, as long as the mean keeps up.  Budget ``ceil(rho)`` slots
      of steady-state lag plus ``jitter`` × that for variance, clamped
      to ``max_depth`` (each slot pins a full device-put batch).

    Returns an int in ``[min_depth, max_depth]``.
    """
    if host_time_s < 0 or device_time_s < 0:
        raise ValueError(
            f"need host_time_s >= 0 and device_time_s >= 0, got "
            f"{host_time_s} / {device_time_s}")
    if min_depth < 1 or max_depth < min_depth:
        raise ValueError(f"bad depth bounds [{min_depth}, {max_depth}]")
    if device_time_s == 0:
        # a zero device time is real profiler output, not an error: a
        # fully-overlapped pipeline measures ~0 exposed device wait, and
        # a first-iteration probe may not have retired anything yet.
        # host == 0 too -> no evidence either way, classic double
        # buffering; host > 0 -> the host-bound limit (rho -> inf).
        return min_depth if host_time_s == 0 else max_depth
    rho = host_time_s / device_time_s
    if rho <= 1.0 + 1e-9:          # tolerance: fp noise must not flip regimes
        return min_depth
    depth = -(-int(rho * (1.0 + jitter) * 1000) // 1000)  # ceil, fp-safe
    return max(min_depth, min(depth + 1, max_depth))


def choose_gather_prefetch_depth(
    layer_bytes: float,
    axis_size: int,
    layer_compute_s: float,
    latency_s: float = _DEFAULT_LATENCY_S,
    bandwidth_bytes_per_s: float = _DEFAULT_BANDWIDTH,
    link: Optional[LinkParams] = None,
    min_window: int = 1,
    max_window: int = 4,
) -> int:
    """ZeRO-3 layer-gather prefetch window from the latency-bandwidth
    model (``ShardedState.auto_window`` / ``LayerGatherStream(window=)``).

    A window of ``W`` means layer ``i``'s all-gather is issued ``W``
    layers ahead, so it has ``W`` layers' compute to hide behind.  One
    gather of a layer's ``s = layer_bytes`` params over ``n`` devices
    costs ``t_g = alpha + s (n-1) / (n * beta)`` on the ring; the
    smallest window that fully hides it is ``1 + ceil(t_g / t_c)`` for
    per-layer compute ``t_c`` (the ``+1`` is the layer currently being
    consumed — classic double buffering at ``t_g <= t_c``).  Clamped to
    ``[min_window, max_window]``: each extra slot keeps one more layer's
    FULL params resident, which is exactly the memory ZeRO-3 exists to
    shed.  Defaults model ICI; a :class:`LinkParams` via ``link`` (e.g.
    ``LinkParams(**plan.link)`` from the measured autotuner) overrides
    both scalars.
    """
    if link is not None:
        latency_s = link.latency_s
        bandwidth_bytes_per_s = link.bandwidth_bytes_per_s
    if layer_bytes < 0 or layer_compute_s < 0:
        raise ValueError(
            f"need layer_bytes >= 0 and layer_compute_s >= 0, got "
            f"{layer_bytes} / {layer_compute_s}")
    if min_window < 1 or max_window < min_window:
        raise ValueError(f"bad window bounds [{min_window}, {max_window}]")
    if axis_size <= 1:
        return min_window          # nothing to gather, nothing to hide
    t_g = latency_s + layer_bytes * (axis_size - 1) / (
        axis_size * bandwidth_bytes_per_s)
    if layer_compute_s == 0:
        # no compute measured yet (first-step probe): nothing to hide
        # behind, so take the deepest window the memory budget allows.
        return max_window
    depth = 1 + math.ceil(t_g / layer_compute_s - 1e-9)
    return max(min_window, min(depth, max_window))


def choose_accum_steps(
    grad_bytes: float,
    axis_size: int,
    microbatch_time_s: float,
    latency_s: float = _DEFAULT_LATENCY_S,
    bandwidth_bytes_per_s: float = _DEFAULT_BANDWIDTH,
    bucket_bytes: Optional[int] = None,
    comm_fraction: float = 0.05,
    max_accum: int = 64,
    link: Optional[LinkParams] = None,
) -> int:
    """Accumulation window ``M`` for ``StandardUpdater(accum_steps=M)``
    from the bytes/step-vs-interconnect model.

    With window-fused accumulation the gradient exchange fires once per
    ``M`` microbatches, so its amortised per-microbatch cost is
    ``T_ex / M`` where (ring formula, fused buckets)

        ``T_ex = ceil(G/b) * alpha + 2 G (n-1) / (n * beta)``

    (``G`` gradient bytes, ``b`` bucket size, ``alpha`` launch latency,
    ``beta`` per-device ring bandwidth, ``n`` axis size).  This picks
    the smallest ``M`` that pushes the amortised exchange below
    ``comm_fraction`` of the measured microbatch compute time
    (``main/step_time`` with ``accum_steps=1``, or an estimate), clamped
    to ``[1, max_accum]`` — past that point accumulation buys
    vanishing wall-clock and only delays parameter updates (the
    statistical large-batch trade-off is the user's call; see
    ``docs/PIPELINE.md``).

    Returns 1 when the axis doesn't span multiple members (nothing to
    amortise) or there are no gradient bytes.  ``link`` (a
    :class:`LinkParams`, e.g. from the measured autotuner) overrides
    ``latency_s``/``bandwidth_bytes_per_s`` with measured constants.
    """
    if link is not None:
        latency_s = link.latency_s
        bandwidth_bytes_per_s = link.bandwidth_bytes_per_s
    if grad_bytes < 0:
        raise ValueError(f"grad_bytes {grad_bytes} must be >= 0")
    if microbatch_time_s <= 0:
        raise ValueError(
            f"microbatch_time_s {microbatch_time_s} must be > 0")
    if comm_fraction <= 0:
        raise ValueError(f"comm_fraction {comm_fraction} must be > 0")
    if max_accum < 1:
        raise ValueError(f"max_accum {max_accum} must be >= 1")
    if axis_size <= 1 or grad_bytes == 0:
        return 1
    b = bucket_bytes or choose_bucket_bytes(
        grad_bytes, axis_size, latency_s, bandwidth_bytes_per_s)
    n_buckets = fused_collective_budget(int(grad_bytes), int(b))
    t_ex = n_buckets * latency_s + 2.0 * grad_bytes * (axis_size - 1) / (
        axis_size * bandwidth_bytes_per_s)
    m = math.ceil(t_ex / (comm_fraction * microbatch_time_s))
    return max(1, min(m, max_accum))


def fused_collective_budget(total_bytes: int, bucket_bytes: int,
                            n_dtype_groups: int = 1) -> int:
    """Upper bound on collectives the fused lowering may emit for
    ``total_bytes`` of gradients in ``n_dtype_groups`` dtype groups:
    each group independently emits ``ceil(group_bytes/bucket)``, and
    splitting ``total_bytes`` over ``g`` groups adds at most ``g - 1``
    ragged buckets over the single-group ``ceil(total/bucket)``."""
    if bucket_bytes <= 0:
        raise ValueError(f"bucket_bytes {bucket_bytes} must be positive")
    return -(-int(total_bytes) // int(bucket_bytes)) \
        + max(0, n_dtype_groups - 1)


def assert_fused_collectives(stats: Dict[str, "CollectiveStats"],
                             total_bytes: int, bucket_bytes: int,
                             n_dtype_groups: int = 1,
                             kinds=("all-reduce",)) -> int:
    """Assert a compiled program's collective stats respect the fused
    budget: across ``kinds``, at most
    :func:`fused_collective_budget` call sites (the per-leaf baseline
    emits one per leaf — hundreds for a transformer grad tree).
    Returns the observed count."""
    budget = fused_collective_budget(total_bytes, bucket_bytes,
                                     n_dtype_groups)
    count = sum(stats[k].count for k in kinds if k in stats)
    if count > budget:
        raise AssertionError(
            f"fused lowering emitted {count} {'+'.join(kinds)} "
            f"collectives, budget is {budget} "
            f"(= ceil({total_bytes}/{bucket_bytes}) + "
            f"{max(0, n_dtype_groups - 1)} ragged group buckets)")
    return count


def assert_accum_collectives(
    stats: Dict[str, "CollectiveStats"],
    total_bytes: int,
    bucket_bytes: int,
    n_dtype_groups: int = 1,
    kinds=("all-reduce", "reduce-scatter", "all-gather"),
    extra: int = 1,
) -> int:
    """Assert a compiled accumulation step exchanges gradients ONCE per
    window — the M→1 proof for ``StandardUpdater(accum_steps=M)``.

    Two conditions, read off :func:`collective_stats` of the compiled
    steady-state step:

    - **no looped exchange**: zero ``kinds`` call sites inside a
      ``while`` body.  The microbatch scan runs M trips per window; a
      collective there fires M times — exactly the per-microbatch
      regime accumulation exists to retire.
    - **window budget**: total ``kinds`` call sites (all top-level, by
      the first condition, hence once per window) stay within
      :func:`fused_collective_budget` plus ``extra`` — ``extra``
      defaults to 1 for the scalar loss mean the updater reports
      (4 wire bytes; not a gradient exchange).

    Returns the observed per-window count.  Apply to a
    ``steps_per_execution == 1`` program: an outer fused-step scan
    legitimately wraps the per-window exchange in a while body of its
    own, which this check would (rightly, conservatively) reject.
    """
    looped = sum(stats[k].looped for k in kinds if k in stats)
    if looped:
        raise AssertionError(
            f"accumulation scan still exchanges per microbatch: "
            f"{looped} {'+'.join(kinds)} call site(s) inside a while "
            f"body (want 0 — the window-end exchange must sit outside "
            f"the scan)")
    budget = fused_collective_budget(total_bytes, bucket_bytes,
                                     n_dtype_groups) + extra
    count = sum(stats[k].count for k in kinds if k in stats)
    if count > budget:
        raise AssertionError(
            f"accumulation window emitted {count} {'+'.join(kinds)} "
            f"collectives, budget is {budget} "
            f"(= ceil({total_bytes}/{bucket_bytes}) + "
            f"{max(0, n_dtype_groups - 1)} ragged group buckets + "
            f"{extra} extra)")
    return count


# backward compute markers for the overlap proof: the matmul-shaped ops
# a training step's forward/backward is made of.  Elementwise optimiser
# math lowers to fusions without any of these, so "the last dot" is a
# faithful end-of-backward marker in the schedule.
_COMPUTE_RE = re.compile(
    r"=\s*[^ ]+\s+(?:dot|convolution)\(|"
    r"custom-call.*(?:matmul|convolution)")


def assert_overlap_collectives(
    compiled,
    kinds=("all-reduce", "reduce-scatter", "all-gather"),
    min_bytes: int = 256,
    min_frac: float = 0.5,
) -> dict:
    """Prove, from the compiled schedule, that the gradient exchange
    runs UNDER the backward pass — the overlap analogue of
    :func:`assert_fused_collectives` / :func:`assert_accum_collectives`.

    XLA prints each computation of an optimised module in execution-
    schedule order, so position is evidence: an exchange collective
    scheduled BEFORE the computation's last matmul-shaped op
    (``dot``/``convolution``/a matmul custom-call) starts while
    backward compute still remains — wire time that can hide.  The
    window-end lowerings place every exchange collective after the
    last backward op; the overlap lowering interleaves them.

    Args:
      compiled: a ``jax.stages.Compiled`` training step (apply to a
        ``steps_per_execution == 1`` program; under an outer fused-step
        scan the while body is the computation measured).
      kinds: collective kinds that constitute the exchange.
      min_bytes: ignore call sites smaller than this (the reported
        scalar loss pmean is 4 bytes and always sits at the window end
        by construction — it is not a gradient exchange).
      min_frac: minimum fraction of exchange collectives that must
        start inside the backward region.

    Returns ``{"inside": n, "total": n, "frac": f, "async_depth": d}``
    (``async_depth`` summed over ``kinds`` — nonzero only on backends
    that emit async start/done pairs).  Raises ``AssertionError`` when
    fewer than ``min_frac`` of the exchange collectives start inside
    the backward region, or when no exchange collective is found at
    all (nothing to prove).
    """
    kinds = tuple(kinds)
    inside = total = 0
    any_compute = False
    for text in _hlo_texts(compiled):
        for comp_name, lines in _split_computations(text).items():
            coll_idx = []
            last_compute = None
            for i, line in enumerate(lines):
                if _COMPUTE_RE.search(line):
                    last_compute = i
                    any_compute = True
                    continue
                m = _INSTR_RE.search(line)
                if not m or m.group(2) not in kinds:
                    continue
                if _group_size(line) == 1:
                    continue
                if _shape_bytes(m.group(1),
                                is_start=bool(m.group(3))) < min_bytes:
                    continue
                coll_idx.append(i)
            total += len(coll_idx)
            # a collective in a compute-free computation counts as
            # OUTSIDE: the accum window-end shape puts every backward
            # dot inside the scan body and the exchange in the entry —
            # maximal non-overlap, not missing evidence
            if last_compute is not None:
                inside += sum(1 for i in coll_idx if i < last_compute)
    if total == 0 or not any_compute:
        missing = ("no matmul-shaped backward op" if total
                   else f"no {'+'.join(kinds)} exchange collective of "
                        f">= {min_bytes} bytes")
        raise AssertionError(
            f"nothing to prove overlap on: {missing} in the compiled "
            f"program (wrong program, or min_bytes too high)")
    stats = collective_stats(compiled)
    async_depth = sum(stats[k].async_depth for k in kinds if k in stats)
    frac = inside / total
    if frac < min_frac:
        raise AssertionError(
            f"exchange collectives cluster after the backward pass: "
            f"{inside}/{total} ({frac:.0%}) start inside the backward "
            f"region, need >= {min_frac:.0%} — the lowering is not "
            f"overlapping (window-end join, or the scheduler sank the "
            f"collectives)")
    return {"inside": inside, "total": total, "frac": frac,
            "async_depth": async_depth}


def overlap_exposed_time(
    bucket_wire_bytes,
    axis_size: int,
    t_bwd_s: float,
    latency_s: float = _DEFAULT_LATENCY_S,
    bandwidth_bytes_per_s: float = _DEFAULT_BANDWIDTH,
    modes=None,
    launches_per_bucket: int = 2,
    link: Optional[LinkParams] = None,
) -> float:
    """EXPOSED wire seconds of a backward-overlapped exchange — the
    overlap-aware cost model behind the schedule search.

    Buckets arrive in stream order (index 0 = the reverse-layer bucket
    whose gradients the backward produces FIRST).  Modeling gradient
    production as uniform in bytes over ``t_bwd_s``, eager bucket ``i``
    becomes ready at ``t_bwd_s × (cumulative bytes through i) /
    (total bytes)``; a ``deferred`` bucket is ready only when the
    backward finishes.  The wire serialises buckets (one fabric): each
    starts at ``max(ready, wire_free)`` and holds the wire for

        ``t_wire = launches_per_bucket · α + 2·b·(n-1)/(n·β)``

    (ring all-reduce bytes; reduce-scatter→all-gather moves the same
    total).  The exposed cost is ``max(0, finish − t_bwd_s)`` — per
    bucket, wire time is only paid where ``T_wire`` exceeds the
    remaining backward compute, which is the ``max(0, T_wire −
    T_bwd_remaining)`` shape the window-end model lacks.  A window-end
    exchange is the degenerate all-``deferred`` schedule: exposed =
    full ``T_ex``.

    Args:
      bucket_wire_bytes: per-bucket wire byte counts, stream order.
      axis_size: reduction-axis size ``n``.
      t_bwd_s: backward wall time the stream can hide under.
      modes: per-bucket ``"eager"``/``"deferred"`` (default all eager).
      launches_per_bucket: collective launches per bucket — a scalar
        (2 for rs→ag, 1 for a lone all-reduce) or a per-bucket
        sequence, so mixed-``via`` schedules price their launch costs
        truthfully.
      link: measured :class:`LinkParams` override (e.g. ``plan.link``).

    Returns exposed seconds (0.0 = the exchange fully hides).
    """
    if link is not None:
        latency_s = link.latency_s
        bandwidth_bytes_per_s = link.bandwidth_bytes_per_s
    buckets = [float(b) for b in bucket_wire_bytes]
    if not buckets or axis_size <= 1:
        return 0.0
    if t_bwd_s < 0:
        raise ValueError(f"t_bwd_s {t_bwd_s} must be >= 0")
    if modes is None:
        modes = ["eager"] * len(buckets)
    if len(modes) != len(buckets):
        raise ValueError(
            f"{len(modes)} modes for {len(buckets)} buckets")
    if isinstance(launches_per_bucket, (int, float)):
        launches = [float(launches_per_bucket)] * len(buckets)
    else:
        launches = [float(x) for x in launches_per_bucket]
        if len(launches) != len(buckets):
            raise ValueError(
                f"{len(launches)} launch counts for {len(buckets)} "
                f"buckets")
    total = sum(buckets) or 1.0
    frac = 2.0 * (axis_size - 1) / axis_size
    cum = 0.0
    order = []                  # (ready_s, t_wire_s), stream order
    deferred = []
    for b, mode, k in zip(buckets, modes, launches):
        cum += b
        t_wire = k * latency_s + b * frac / bandwidth_bytes_per_s
        if mode == "deferred":
            deferred.append((t_bwd_s, t_wire))
        elif mode == "eager":
            order.append((t_bwd_s * cum / total, t_wire))
        else:
            raise ValueError(f"unknown bucket mode {mode!r}")
    wire_free = 0.0
    for ready, t_wire in order + deferred:
        wire_free = max(ready, wire_free) + t_wire
    return max(0.0, wire_free - t_bwd_s)


def axis_collective_report(build_step, axes_sizes, n_devices=8):
    """Per-mesh-axis collective volume for one training step.

    Args:
      build_step: ``build_step(mesh_axes: dict) -> (fn, args)`` — builds
        the jitted step for a mesh with the given axis sizes (every
        other axis 1) and returns it unlowered with example args.
      axes_sizes: e.g. ``{"data": 8, "model": 8}`` — each axis is
        activated ALONE at its size (the single-active-axis trick).
      n_devices: virtual devices available.

    Returns ``{axis: {"stats": {kind: CollectiveStats}, "axis_size": n,
    "wire_bytes_per_device": float}}``.
    """
    report = {}
    for axis, n in axes_sizes.items():
        if n > n_devices:
            raise ValueError(f"{axis}={n} exceeds {n_devices} devices")
        fn, args = build_step({axis: n})
        compiled = fn.lower(*args).compile()
        stats = collective_stats(compiled)
        report[axis] = {
            "axis_size": n,
            "stats": stats,
            "wire_bytes_per_device": sum(
                s.wire_bytes(n) for s in stats.values()),
        }
    return report
