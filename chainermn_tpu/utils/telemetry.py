"""Flight recorder — structured span tracing for the whole training stack.

The Profiler (:mod:`chainermn_tpu.utils.profiling`) answers *"how much
time does phase X cost on average"*; it is a flat name→stats table with
no ordering, no per-event timestamps, and no cross-rank story.  The
ROADMAP's next levers (backward-overlapped exchange, elastic training)
need the question it cannot answer: *"what was each rank doing, when,
overlapped with what"* — a timeline.  HiCCL and the overlapping-
allreduce literature (PAPERS.md 2408.05962 / 2508.13397) both assume
exactly this per-collective, per-phase telemetry; SURVEY §5 names it as
the capability the reference out-sourced to external tracers.

Three layers:

- :class:`TraceRecorder` — a bounded ring buffer of structured span
  events (name, category, t0/duration, step, rank, thread, metadata,
  parent).  Near-zero cost when disabled: ``span()`` returns a shared
  no-op context manager (no allocation, one attribute read).  A live
  span knows the live span that encloses it on its thread (``parent``,
  and ``step`` is inherited from it), so a layer's self time is its
  span less its children; and it enters a
  ``jax.profiler.TraceAnnotation`` of the same name, so a profile taken
  with the host tracer on shows the program's spans on the profiler's
  own clock, beside the device's ops.  Exports:

  * **Chrome trace-event JSON** (:meth:`export_chrome`) — load the file
    at https://ui.perfetto.dev (or ``chrome://tracing``).  Ranks map to
    pids, threads to tids, so a merged multi-process trace renders as
    one timeline with a lane per rank; :func:`merge_traces` fuses
    per-rank shard files into that single document.
  * **streaming JSONL** (``stream_path=``) — every completed event is
    appended as one JSON line the moment it retires, so a SIGKILL'd
    process still leaves its timeline on disk up to the kill point
    (:meth:`export_jsonl` dumps the ring after the fact).

- :class:`StragglerReport` — a trainer extension that allgathers each
  process's per-phase mean durations and reports, per phase, the
  slowest rank and the skew ratio (slowest / mean) —
  ``main/straggler_skew`` is the max skew over phases.  This is the
  cross-rank attribution the overlap work needs before it can claim a
  win: "step time is X" becomes "rank 3's host phase is 2.1× the mean".

- :class:`MetricsExport` — a JSONL time-series appender for
  ``trainer.observation``: one line per trigger with iteration, epoch,
  wall clock and every float-valued observation, flushed per line so a
  crash keeps the series.

Failure-path integration (wired in the respective modules): the
:class:`~chainermn_tpu.extensions.TrainingWatchdog` stall report embeds
the recorder's ring tail (``trace_tail``), and
:func:`~chainermn_tpu.extensions.add_global_except_hook` dumps the
trace next to the crash — post-mortems come with a timeline of the
seconds before death, not just stacks.

The global recorder starts DISABLED.  Enable explicitly
(``get_recorder().enable()``), or set ``CHAINERMN_TPU_TRACE=1`` in the
environment (optionally ``CHAINERMN_TPU_TRACE_CAPACITY`` /
``CHAINERMN_TPU_TRACE_STREAM=<path>``) before import.  See
docs/OBSERVABILITY.md for the Perfetto workflow.

This module must stay importable without jax (the rank lookup is lazy):
it is imported by the iterator/prefetch layer, which keeps its imports
light.
"""

from __future__ import annotations

import collections
import functools
import json
import os
import re
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

from chainermn_tpu.utils.metrics import Histogram, append_jsonl

__all__ = [
    "DEVICE_SCOPES",
    "MetricsExport",
    "RequestTraceStore",
    "SpanEvent",
    "StragglerReport",
    "TraceRecorder",
    "classify_op_name",
    "device_scope",
    "get_recorder",
    "merge_traces",
    "set_recorder",
]

# Chrome trace-event phase codes used here: "X" complete (span with
# duration), "i" instant, "C" counter, "M" metadata.
_PH_SPAN, _PH_INSTANT, _PH_COUNTER = "X", "i", "C"


def _default_rank() -> int:
    """The process rank for the pid mapping — lazy so the module imports
    without jax (and before distributed init)."""
    try:
        import jax

        return int(jax.process_index())
    except Exception:
        return 0


@functools.lru_cache(maxsize=1)
def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, or None without jax — looked
    up at the first LIVE span, so the module imports without jax and a
    disabled recorder never gets here."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return None
    return TraceAnnotation


class SpanEvent:
    """One recorded event.  ``dur`` is seconds for spans, ``None`` for
    instants, and carries the counter value for counter events.
    ``parent`` is ``(name, t0)`` of the live span that enclosed this one
    on its thread, or None."""

    __slots__ = ("name", "cat", "ph", "t0", "dur", "step", "tid", "meta",
                 "parent")

    def __init__(self, name, cat, ph, t0, dur, step, tid, meta,
                 parent=None):
        self.name = name
        self.cat = cat
        self.ph = ph
        self.t0 = t0
        self.dur = dur
        self.step = step
        self.tid = tid
        self.meta = meta
        self.parent = parent

    def to_dict(self) -> dict:
        d = {"name": self.name, "cat": self.cat, "ph": self.ph,
             "t0": self.t0}
        if self.dur is not None:
            d["dur"] = self.dur
        if self.step is not None:
            d["step"] = self.step
        if self.tid is not None:
            d["tid"] = self.tid
        if self.meta:
            d["meta"] = self.meta
        if self.parent is not None:
            d["parent"] = list(self.parent)
        return d


class _NullSpan:
    """The disabled-path context manager: ONE shared instance, so a
    disabled recorder allocates nothing per span (pinned by test)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **meta):
        return self


_NULL_SPAN = _NullSpan()


class _LiveSpan:
    __slots__ = ("_rec", "_name", "_cat", "_step", "_meta", "_t0",
                 "_parent", "_stack", "_annotation")

    def __init__(self, rec, name, cat, step, meta):
        self._rec = rec
        self._name = name
        self._cat = cat
        self._step = step
        self._meta = meta

    def __enter__(self):
        # what caused it: the live span that encloses this one on this
        # thread, whose step it shares unless it was given its own
        stack = self._stack = self._rec._live_stack()
        if stack:
            enclosing = stack[-1]
            self._parent = (enclosing._name, enclosing._t0)
            if self._step is None:
                self._step = enclosing._step
        else:
            self._parent = None
        stack.append(self)
        # the same span on the profiler's clock, for any profile taken
        # with the host tracer on (a no-op outside a profiler session)
        annotation = _trace_annotation()
        if annotation is None:
            self._annotation = None
        else:
            self._annotation = (
                annotation(self._name) if self._step is None
                else annotation(self._name, step=self._step))
            self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set(self, **meta):
        """Attach metadata discovered inside the block (measured values,
        outcome flags); merged into the event on exit."""
        if self._meta is None:
            self._meta = meta
        else:
            self._meta.update(meta)
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        if self._stack and self._stack[-1] is self:
            self._stack.pop()
        self._rec._append(SpanEvent(
            self._name, self._cat, _PH_SPAN, self._t0, t1 - self._t0,
            self._step, threading.get_ident(), self._meta, self._parent))
        return False


class TraceRecorder:
    """Bounded flight recorder of structured span events.

    Args:
      capacity: ring length — oldest events drop when full.  65536
        events ≈ a few MB; at ~6 spans per training step that is hours
        of history.
      enabled: start recording immediately (default False — the
        instrumented hot paths pay one attribute read and nothing else
        until :meth:`enable` is called).
      rank: the pid this recorder's events map to in the Chrome export.
        Default: ``jax.process_index()`` resolved lazily at export
        time, so construction never touches jax.
      stream_path: when set, every completed event is ALSO appended to
        this file as one JSON line at record time (crash-durable
        streaming export; the ring is unaffected).

    Thread-safe: spans may open/close on any thread (the prefetch
    worker, checkpoint writer and watchdog monitor all record); the
    thread id rides each event and becomes the Chrome tid.
    """

    def __init__(self, capacity: int = 65536, enabled: bool = False,
                 rank: Optional[int] = None,
                 stream_path: Optional[str] = None):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = int(capacity)
        self.enabled = bool(enabled)
        self._rank = rank
        self.stream_path = stream_path
        self._ring: collections.deque = collections.deque(
            maxlen=self.capacity)
        self._lock = threading.Lock()
        self._stream_file = None
        # phase-stats accumulators: one independent CHANNEL per
        # consumer, stored as [name_filter_or_None, {name: [n, tot, mx,
        # Histogram]}]; the default "" channel (no filter) feeds
        # StragglerReport, and open_phase_channel() gives other
        # consumers (GoodputReport) their own interval state so a drain
        # on one never steals another's feed
        self._phase_channels: Dict[str, list] = {"": [None, {}]}
        self._thread_names: Dict[int, str] = {}
        # each thread's stack of live spans (see _LiveSpan.__enter__)
        self._local = threading.local()
        # wall-clock anchor: perf_counter is monotonic but arbitrary;
        # the pair lets exports (and merge across processes) place
        # events on the wall clock
        self._anchor_wall = time.time()
        self._anchor_perf = time.perf_counter()
        self.dropped = 0          # events displaced by ring wrap

    # ------------------------------------------------------------------ #
    # recording
    # ------------------------------------------------------------------ #

    @property
    def rank(self) -> int:
        if self._rank is None:
            self._rank = _default_rank()
        return self._rank

    @rank.setter
    def rank(self, value: int) -> None:
        self._rank = int(value)

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def __len__(self) -> int:
        return len(self._ring)

    def span(self, name: str, /, cat: str = "default",
             step: Optional[int] = None, **meta):
        """Context manager timing a block into the ring.  Disabled →
        returns the shared no-op singleton (zero allocation).  The
        event records the live span enclosing it on this thread as its
        ``parent`` and, given no ``step``, takes that span's.  ``name``
        is positional-only, so metadata may itself be called ``name``."""
        if not self.enabled:
            return _NULL_SPAN
        return _LiveSpan(self, name, cat, step, meta or None)

    def _live_stack(self) -> list:
        """This thread's stack of live spans, innermost last."""
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    def record(self, name: str, duration: float, cat: str = "default",
               step: Optional[int] = None, t0: Optional[float] = None,
               **meta) -> None:
        """Record an already-measured span (duration seconds; ``t0`` on
        the ``time.perf_counter`` clock, default now-minus-duration)."""
        if not self.enabled:
            return
        if t0 is None:
            t0 = time.perf_counter() - duration
        self._append(SpanEvent(name, cat, _PH_SPAN, t0, float(duration),
                               step, threading.get_ident(), meta or None))

    def instant(self, name: str, cat: str = "default",
                step: Optional[int] = None, **meta) -> None:
        """Zero-duration marker (heartbeats, plan changes, faults)."""
        if not self.enabled:
            return
        self._append(SpanEvent(name, cat, _PH_INSTANT,
                               time.perf_counter(), None, step,
                               threading.get_ident(), meta or None))

    def counter(self, name: str, value: float, cat: str = "counter",
                step: Optional[int] = None) -> None:
        """Sampled value rendered as a counter track in Perfetto
        (prefetch occupancy, queue depths)."""
        if not self.enabled:
            return
        self._append(SpanEvent(name, cat, _PH_COUNTER,
                               time.perf_counter(), float(value), step,
                               threading.get_ident(), None))

    def _append(self, ev: SpanEvent) -> None:
        tid = ev.tid
        if tid is not None and tid not in self._thread_names:
            self._thread_names[tid] = threading.current_thread().name
        if len(self._ring) == self.capacity:
            self.dropped += 1
        self._ring.append(ev)      # deque.append is atomic
        if ev.ph == _PH_SPAN:
            with self._lock:
                for flt, accs in self._phase_channels.values():
                    if flt is not None and ev.name not in flt:
                        continue
                    acc = accs.get(ev.name)
                    if acc is None:
                        # the histogram rides the shared metrics
                        # lattice, so StragglerReport's cross-rank
                        # merge is a bucket sum
                        acc = accs[ev.name] = [0, 0.0, ev.dur,
                                               Histogram()]
                    acc[0] += 1
                    acc[1] += ev.dur
                    acc[2] = max(acc[2], ev.dur)
                    acc[3].observe(ev.dur)
        if self.stream_path is not None:
            self._stream(ev)

    def _stream(self, ev: SpanEvent) -> None:
        with self._lock:
            if self.stream_path is None:    # closed under our feet
                return
            try:
                if self._stream_file is None:
                    self._stream_file = open(self.stream_path, "a")
                self._stream_file.write(
                    json.dumps(ev.to_dict(), default=str) + "\n")
                self._stream_file.flush()
            except OSError:
                # a full disk must degrade the stream, never training
                if self._stream_file is not None:
                    try:
                        self._stream_file.close()
                    except OSError:
                        pass
                self.stream_path = None
                self._stream_file = None

    def clear(self) -> None:
        self._ring.clear()
        with self._lock:
            for chan in self._phase_channels.values():
                chan[1].clear()
        self.dropped = 0

    # ------------------------------------------------------------------ #
    # introspection
    # ------------------------------------------------------------------ #

    def tail(self, n: int = 64) -> List[dict]:
        """The newest ``n`` events as JSON-safe dicts — what the
        watchdog embeds in a stall report and the except hook dumps on
        crash: the timeline of the seconds before things went wrong.
        ``n <= 0`` means none (the opt-out, not the whole ring)."""
        if n <= 0:
            return []
        return [ev.to_dict() for ev in list(self._ring)[-n:]]

    def events(self) -> List[dict]:
        # list(deque) is a C-atomic snapshot: concurrent appends from
        # other threads (prefetch worker, watchdog monitor) must never
        # fault an export with "deque mutated during iteration"
        return [ev.to_dict() for ev in list(self._ring)]

    def open_phase_channel(self, key: str,
                           names: Optional[Sequence[str]] = None
                           ) -> str:
        """Register an INDEPENDENT phase-stats accumulator.  A channel
        sees every span recorded after it opens (restricted to
        ``names`` when given — a consumer with a fixed name list should
        pass it, so the channel neither pays accumulation cost nor
        retains histograms for spans it will never drain); draining one
        channel never touches another, so interval consumers with
        overlapping name sets (``StragglerReport`` on the default
        channel, ``GoodputReport`` on its own) each get the full feed.
        Idempotent for the same arguments (re-opening replaces the
        filter); returns ``key``."""
        flt = None if names is None else frozenset(names)
        with self._lock:
            chan = self._phase_channels.get(key)
            if chan is None:
                self._phase_channels[key] = [flt, {}]
            else:
                chan[0] = flt
        return key

    def drain_phase_stats(self, names: Optional[Sequence[str]] = None,
                          channel: str = "") -> Dict[str, dict]:
        """Per-span-name ``{count, total_s, max_s, hist}`` accumulated
        on ``channel`` since its last drain, then reset (``hist`` is a
        duration :class:`~chainermn_tpu.utils.metrics.Histogram`
        snapshot on the shared lattice — the per-phase distribution
        behind :class:`StragglerReport`'s tail percentiles).  Survives
        ring wrap (accumulated at record time), so interval statistics
        stay exact however small the ring.

        ``names`` drains ONLY those span names, leaving the rest
        accumulating; ``channel`` selects which consumer's accumulator
        to drain (default: the shared one ``StragglerReport`` uses).
        An unknown channel raises — :meth:`open_phase_channel` is the
        one registration point, and a typo'd key silently returning
        ``{}`` forever is exactly the bug that must not ship."""
        with self._lock:
            chan = self._phase_channels.get(channel)
            if chan is None:
                raise KeyError(
                    f"unknown phase channel {channel!r} — call "
                    f"open_phase_channel first (open: "
                    f"{sorted(self._phase_channels)})")
            accs = chan[1]
            if names is None:
                drained = dict(accs)
                accs.clear()
            else:
                drained = {}
                for name in names:
                    acc = accs.pop(name, None)
                    if acc is not None:
                        drained[name] = acc
        return {name: {"count": a[0], "total_s": a[1], "max_s": a[2],
                       "hist": a[3].to_snapshot()}
                for name, a in drained.items()}

    # ------------------------------------------------------------------ #
    # export
    # ------------------------------------------------------------------ #

    def _ts_us(self, t0: float) -> float:
        """perf_counter → wall-clock microseconds (the Chrome ``ts``
        axis; wall-anchored so independently-exported per-rank shards
        land on one comparable timeline, modulo host clock skew)."""
        return (t0 - self._anchor_perf + self._anchor_wall) * 1e6

    def chrome_events(self) -> List[dict]:
        """The ring as Chrome trace-event dicts (rank → pid, thread →
        tid), prefixed with the process/thread-name metadata events
        Perfetto uses to label the lanes."""
        pid = self.rank
        events: List[dict] = [{
            "ph": "M", "pid": pid, "tid": 0, "name": "process_name",
            "args": {"name": f"rank {pid}"},
        }]
        ring = list(self._ring)     # atomic snapshot (see events())
        tids = sorted({ev.tid for ev in ring if ev.tid is not None})
        tid_map = {ident: i for i, ident in enumerate(tids)}
        for ident in tids:
            events.append({
                "ph": "M", "pid": pid, "tid": tid_map[ident],
                "name": "thread_name",
                "args": {"name": self._thread_names.get(
                    ident, f"thread-{ident}")},
            })
        for ev in ring:
            rec = {
                "name": ev.name,
                "cat": ev.cat,
                "ph": ev.ph,
                "pid": pid,
                "tid": tid_map.get(ev.tid, 0),
                "ts": self._ts_us(ev.t0),
            }
            if ev.ph == _PH_SPAN:
                rec["dur"] = ev.dur * 1e6
            args = dict(ev.meta) if ev.meta else {}
            if ev.step is not None:
                args["step"] = ev.step
            if ev.ph == _PH_COUNTER:
                args["value"] = ev.dur
            if args:
                rec["args"] = args
            events.append(rec)
        return events

    def export_chrome(self, path: str) -> str:
        """Write the Perfetto-loadable Chrome trace JSON document."""
        doc = {
            "traceEvents": self.chrome_events(),
            "displayTimeUnit": "ms",
            "metadata": {
                "rank": self.rank,
                "capacity": self.capacity,
                "dropped": self.dropped,
                "anchor_wall_s": self._anchor_wall,
            },
        }
        with open(path, "w") as f:
            json.dump(doc, f, default=str)
        return path

    def export_jsonl(self, path: str) -> str:
        """Dump the ring as JSON lines (one event per line) — the
        after-the-fact form of the ``stream_path`` live export."""
        with open(path, "w") as f:
            for ev in list(self._ring):     # atomic snapshot
                f.write(json.dumps(ev.to_dict(), default=str) + "\n")
        return path

    def close(self) -> None:
        """End the streaming export: close the file AND clear
        ``stream_path``, so a straggler thread recording afterwards
        (prefetch worker, watchdog monitor) cannot silently reopen the
        file a reader already treated as end-of-stream."""
        with self._lock:
            self.stream_path = None
            if self._stream_file is not None:
                try:
                    self._stream_file.close()
                except OSError:
                    pass
                self._stream_file = None


def merge_traces(paths, out: Optional[str] = None) -> dict:
    """Fuse per-rank Chrome trace shards into ONE Perfetto document.

    ``paths`` may be a sequence of shard files, a DIRECTORY (every
    ``*.json`` inside), or a GLOB pattern (``"traces/rank*.json"``).
    However they arrive, shards are sorted deterministically by their
    recorded rank (``metadata.rank``; rankless shards sort after, by
    file name) BEFORE pid assignment — so the same shard set always
    produces the same Perfetto pid lanes, regardless of listing order
    (callers used to have to pre-sort paths themselves to keep pids
    stable across merges).

    Each shard keeps its own pid lane (rank → pid).  If two shards
    claim the same pid — e.g. single-process drills exporting twice —
    the later shard's pids are shifted past every pid already taken,
    so lanes never silently overlay.  Events merge in shard order;
    Perfetto sorts by ``ts`` itself (shards are wall-clock anchored).

    Returns the merged document; writes it to ``out`` when given.
    """
    import glob as _glob

    if isinstance(paths, (str, os.PathLike)):
        root = os.fspath(paths)
        if os.path.isdir(root):
            paths = [os.path.join(root, f) for f in os.listdir(root)
                     if f.endswith(".json")]
        else:
            paths = _glob.glob(root)
        if not paths:
            # a typo'd glob or empty/missing directory must not
            # succeed with an empty Perfetto doc (an explicit path
            # list still raises at open(), as it always did)
            raise FileNotFoundError(
                f"merge_traces: no trace shards found at {root!r}")

    shards: List[tuple] = []
    for path in paths:
        with open(path) as f:
            doc = json.load(f)
        rank = (doc.get("metadata", {}).get("rank")
                if isinstance(doc, dict) else None)
        shards.append((path, rank, doc))
    shards.sort(key=lambda s: (s[1] is None,
                               s[1] if isinstance(s[1], int) else 0,
                               os.path.basename(s[0])))

    merged: List[dict] = []
    meta: List[dict] = []
    used_pids: set = set()
    for path, rank, doc in shards:
        # both standard Chrome forms: object with traceEvents, or a
        # bare event array
        events = (doc.get("traceEvents", []) if isinstance(doc, dict)
                  else doc if isinstance(doc, list) else [])
        shard_pids = {ev.get("pid", 0) for ev in events}
        shift = 0
        if shard_pids & used_pids:
            shift = (max(used_pids) + 1) - min(shard_pids)
        used_pids |= {p + shift for p in shard_pids}
        for ev in events:
            if shift:
                ev = dict(ev)
                ev["pid"] = ev.get("pid", 0) + shift
            merged.append(ev)
        meta.append({"path": os.path.basename(path),
                     "pid_shift": shift,
                     **({} if rank is None else {"rank": rank})})
    doc = {"traceEvents": merged, "displayTimeUnit": "ms",
           "metadata": {"merged_from": meta}}
    if out is not None:
        with open(out, "w") as f:
            json.dump(doc, f, default=str)
    return doc


# ---------------------------------------------------------------------- #
# per-request causal traces
# ---------------------------------------------------------------------- #

class RequestTraceStore:
    """Tail-based retention of per-request causal traces.

    The flight recorder's ring answers *"what was this process doing"*;
    a serving operator's question is *"what happened to THIS request"*.
    The engine assembles one span timeline per request (``queue_wait``,
    ``admit``, ``prefill``/``chunk_prefill``, sampled ``decode_round``\\ s,
    the terminal ``evict``/``shed``) and OFFERS the finished trace
    here.  Retention is tail-based — the retention the exemplar link
    needs, because exemplars point at tails:

    - any non-``"ok"`` terminal status (shed / timeout / cancelled /
      quarantined) is ALWAYS kept;
    - an ok request that violated its end-to-end SLO target
      (``slo_e2e``) is ALWAYS kept;
    - remaining ok requests are kept at ``sample_rate``, decided
      DETERMINISTICALLY from the trace id (crc32 hash — the same
      request keeps or drops identically on every rank and replay).

    Capacity-bounded (oldest retained trace drops first), thread-safe,
    and exportable: :meth:`to_chrome` renders retained traces as a
    Chrome/Perfetto document on the same wall-anchored timeline as
    :meth:`TraceRecorder.export_chrome`, so :func:`merge_traces` fuses
    request lanes with the process timeline.  ``/tracez``
    (:mod:`chainermn_tpu.utils.statusz`) serves :meth:`traces` live.
    """

    def __init__(self, capacity: int = 256, sample_rate: float = 0.0,
                 slo_e2e: Optional[float] = None,
                 rank: Optional[int] = None):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        if not 0.0 <= sample_rate <= 1.0:
            raise ValueError(
                f"sample_rate={sample_rate} not in [0, 1]")
        self.capacity = int(capacity)
        self.sample_rate = float(sample_rate)
        self.slo_e2e = slo_e2e
        self._rank = rank
        self._traces: "collections.OrderedDict[str, dict]" = \
            collections.OrderedDict()
        self._lock = threading.Lock()
        self.offered = 0
        self.kept = 0
        # wall anchor for Chrome export (the TraceRecorder convention:
        # span t0 is on the perf_counter clock, exports are wall-based)
        self._anchor_wall = time.time()
        self._anchor_perf = time.perf_counter()

    def __len__(self) -> int:
        return len(self._traces)

    @property
    def rank(self) -> int:
        if self._rank is None:
            self._rank = _default_rank()
        return self._rank

    def would_sample(self, trace_id: str) -> bool:
        """The deterministic ok-path sampling decision for
        ``trace_id`` (hash-based, not RNG-based — replayable)."""
        if self.sample_rate >= 1.0:
            return True
        if self.sample_rate <= 0.0:
            return False
        import zlib

        h = zlib.crc32(str(trace_id).encode()) % 1_000_000
        return h / 1_000_000.0 < self.sample_rate

    def offer(self, trace: dict) -> bool:
        """Offer a finished request trace ``{"trace_id", "rid",
        "status", "spans": [{"name", "t0", "dur", ...}], ...}``;
        returns whether it was retained.  The tail-based verdict and
        its inputs are stamped onto the trace (``slo_violated``,
        ``sampled``) so a reader knows WHY a trace is present."""
        status = trace.get("status", "ok")
        e2e = trace.get("e2e")
        violated = bool(self.slo_e2e is not None and e2e is not None
                        and e2e > self.slo_e2e)
        trace["slo_violated"] = violated
        keep = status != "ok" or violated
        if not keep:
            keep = self.would_sample(trace.get("trace_id", ""))
            trace["sampled"] = keep
        if not keep:
            with self._lock:
                self.offered += 1
            return False
        with self._lock:
            # the retention counters share the lock with the dict:
            # two engines may offer into one store concurrently
            self.offered += 1
            self.kept += 1
            self._traces[str(trace.get("trace_id"))] = trace
            while len(self._traces) > self.capacity:
                self._traces.popitem(last=False)
        return True

    def get(self, trace_id: str) -> Optional[dict]:
        """The retained trace for ``trace_id`` (``None`` if it was
        dropped, sampled out, or never offered) — the resolution step
        of the exemplar link: histogram p99 → exemplar trace id →
        this."""
        with self._lock:
            return self._traces.get(str(trace_id))

    def traces(self, n: Optional[int] = None) -> List[dict]:
        """The newest ``n`` retained traces (all by default), oldest
        first.  A negative ``n`` reads as "all" — never the
        everything-BUT-the-oldest slice ``vals[-n:]`` would give."""
        with self._lock:
            vals = list(self._traces.values())
        if n is None or int(n) < 0:
            return vals
        return vals[len(vals) - min(int(n), len(vals)):]

    def clear(self) -> None:
        with self._lock:
            self._traces.clear()

    def snapshot(self) -> dict:
        """Retention counters for ``/statusz``."""
        return {
            "capacity": self.capacity,
            "sample_rate": self.sample_rate,
            "slo_e2e": self.slo_e2e,
            "offered": self.offered,
            "kept": self.kept,
            "retained": len(self._traces),
        }

    # -- export -------------------------------------------------------- #

    def _ts_us(self, t0: float) -> float:
        return (t0 - self._anchor_perf + self._anchor_wall) * 1e6

    def to_chrome(self, trace_id: Optional[str] = None) -> dict:
        """Retained traces (or just ``trace_id``) as a Chrome
        trace-event document: pid = rank (the process's lane, same as
        the TraceRecorder export), one tid LANE PER REQUEST labelled
        with its rid/trace id, spans wall-anchored — feed it to
        :func:`merge_traces` next to the recorder shards and the
        request rows line up under the engine timeline."""
        pid = self.rank
        with self._lock:
            if trace_id is not None:
                # an exemplar can outlive its trace (capacity
                # eviction) — the export degrades to an empty
                # document, the get()-returns-None contract
                tr = self._traces.get(str(trace_id))
                rows = [] if tr is None else [tr]
            else:
                rows = list(self._traces.values())
        events: List[dict] = [{
            "ph": "M", "pid": pid, "tid": 0, "name": "process_name",
            "args": {"name": f"rank {pid} requests"},
        }]
        for tid, tr in enumerate(rows, start=1):
            events.append({
                "ph": "M", "pid": pid, "tid": tid,
                "name": "thread_name",
                "args": {"name": f"req {tr.get('rid')} "
                                 f"[{tr.get('trace_id')}]"},
            })
            for span in tr.get("spans", ()):
                rec = {
                    "name": span["name"],
                    "cat": "request",
                    "ph": _PH_SPAN,
                    "pid": pid,
                    "tid": tid,
                    "ts": self._ts_us(span["t0"]),
                    "dur": float(span.get("dur", 0.0)) * 1e6,
                }
                args = {k: v for k, v in span.items()
                        if k not in ("name", "t0", "dur")}
                args["trace_id"] = tr.get("trace_id")
                rec["args"] = args
                events.append(rec)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "metadata": {"rank": pid, "request_traces": len(rows)},
        }

    def export_chrome(self, path: str,
                      trace_id: Optional[str] = None) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome(trace_id), f, default=str)
        return path


# ---------------------------------------------------------------------- #
# device scopes
# ---------------------------------------------------------------------- #

# Every name the step programs put on the device's ops, and no other:
# the one vocabulary ``classify_op_name`` reads back out of a profile's
# op names (docs/OBSERVABILITY.md has what an operator reads off each).
# ``attn/<kind>`` stands for the family: ``<kind>`` is the layer's
# ``AttentionKind.name``, or ``full`` / ``sliding`` by
# ``attention_window`` where the layers are all alike.  The dotted names
# are children, worn inside ``attn/<kind>`` and ``kda/scan``: dotted so
# that a reader which looks for ``attn/...`` or ``kda/...`` still finds
# the layer and not its part.
DEVICE_SCOPES = (
    "step/embed", "step/layers", "step/head", "step/optimizer",
    "fsdp/gather",
    "attn/<kind>",
    "attn.qkv", "attn.rope", "attn.kv_repeat", "attn.core", "attn.gate",
    "attn.out",
    "mla/latent",
    "kda/conv", "kda/gate", "kda/scan",
    "kda.pairs", "kda.solve", "kda.intra", "kda.inter",
    "mlp/dense",
    "moe/route", "moe/experts", "moe/combine", "moe/shared",
    "resnet/conv", "bn/stats", "bn/apply",
)
# any name of the list; a kind is whatever ``AttentionKind`` lets
# through, the placeholder itself apart
_ANY_SCOPE = "|".join(
    r"attn/[^/()<>\s]+" if s == "attn/<kind>" else re.escape(s)
    for s in DEVICE_SCOPES)
# a scope stands between the delimiters of a name stack: ``/`` and the
# brackets of a transformation
_SCOPE_AT = re.compile(r"(?:^|[/(])(" + _ANY_SCOPE + r")(?=$|[/)])")


def device_scope(name: str):
    """``jax.named_scope(name)`` for a name of ``DEVICE_SCOPES`` (any
    ``attn/<kind>``), a ``ValueError`` for another: a scope nobody can
    read back is not added by accident.  Trace-time only: it names the
    ops traced under it (through differentiation and remat) and costs
    no host call and no device op when the program runs."""
    if not re.fullmatch(_ANY_SCOPE, name):
        raise ValueError(
            f"{name!r} is not in DEVICE_SCOPES: add it there (and to "
            "docs/OBSERVABILITY.md) before an op wears it")
    import jax

    return jax.named_scope(name)


def classify_op_name(op_name: str):
    """``(phase, path)`` of one device op from the name stack JAX wrote
    into its ``op_name`` (``jit(step)/transpose(jvp(step/layers))/while/
    body/closed_call/checkpoint/attn/full/attn.qkv/dot_general``).

    ``phase``: ``"recompute"`` under ``rematted_computation`` (what
    ``jax.checkpoint`` runs again in the backward pass: JAX 0.9 keeps a
    block's transposed ops beside it, under ``checkpoint/`` alone, so
    the two are told apart), else ``"backward"`` under ``transpose(``,
    else ``"forward"`` under ``jvp(``, else ``"update"``: what is not
    differentiated -- the optimizer, and whatever of the model depends
    on no parameter (rotary tables, masks).  ``"unnamed"`` where the
    name holds no ``jit(`` and so no name stack at all: the compiler's
    own names (``ragged-dot-none``, the grouped-matmul kernels it makes
    of ``lax.ragged_dot``) and ops it adds of its own.  ``path``: the
    scopes of ``DEVICE_SCOPES`` in the name, outermost first, each
    once (a remat region's ops carry the stack they were first traced
    under inside the transposed one: ``step/layers`` twice); empty where
    the op wears none."""
    if "jit(" not in op_name:
        phase = "unnamed"
    elif "rematted_computation" in op_name:
        phase = "recompute"
    elif "transpose(" in op_name:
        phase = "backward"
    elif "jvp(" in op_name:
        phase = "forward"
    else:
        phase = "update"
    return phase, tuple(dict.fromkeys(_SCOPE_AT.findall(op_name)))


# ---------------------------------------------------------------------- #
# global recorder
# ---------------------------------------------------------------------- #

def _from_env() -> TraceRecorder:
    enabled = os.environ.get("CHAINERMN_TPU_TRACE", "") not in ("", "0")
    try:
        capacity = int(os.environ.get(
            "CHAINERMN_TPU_TRACE_CAPACITY", 65536))
        if capacity < 1:
            raise ValueError(capacity)
    except ValueError:
        # observability must never kill training: a typo'd env var
        # (runs at package import) degrades to the default, not a crash
        capacity = 65536
    stream = os.environ.get("CHAINERMN_TPU_TRACE_STREAM") or None
    return TraceRecorder(capacity=capacity, enabled=enabled,
                         stream_path=stream)


_GLOBAL = _from_env()


def get_recorder() -> TraceRecorder:
    """The process-global flight recorder every instrumented subsystem
    records into (disabled by default — see module docstring)."""
    return _GLOBAL


def set_recorder(recorder: TraceRecorder) -> TraceRecorder:
    """Swap the global recorder (tests, custom capacities); returns the
    previous one so callers can restore it."""
    global _GLOBAL
    prev = _GLOBAL
    _GLOBAL = recorder
    return prev


# ---------------------------------------------------------------------- #
# trainer extensions
# ---------------------------------------------------------------------- #

class StragglerReport:
    """Cross-rank straggler attribution from the flight recorder.

    On each trigger: drain this process's per-phase duration stats
    accumulated since the last fire, ``allgather_obj`` them, and for
    every phase any rank reported compute the mean-of-means, the
    slowest rank, and the skew ratio (slowest rank's mean / cross-rank
    mean; 1.0 = perfectly balanced) — plus, because the drained stats
    carry per-phase duration histograms on the shared metrics lattice
    (:mod:`chainermn_tpu.utils.metrics`), the MERGED cross-rank p50
    and p99 per phase and a tail-skew attribution (``slowest_rank_p99``
    / ``skew_p99``): stragglers live in tails, which a mean hides.
    Processes may report divergent
    phase sets (rank-0-only extensions, mid-epoch joins) — each phase
    aggregates over the ranks that actually reported it, the
    :class:`~chainermn_tpu.extensions.ObservationAggregator`
    convention.

    Observes ``main/straggler_skew`` — the max skew over phases — so
    LogReport/PrintReport track it like any metric; the full per-phase
    attribution lands in :attr:`last_report` and (rank 0, optional)
    ``<out>/straggler.jsonl``.

    Args:
      comm: communicator (``allgather_obj`` + rank identity).
      recorder: flight recorder to drain (default the global one).
      phases: restrict attribution to these span names (default: every
        span name recorded in the interval).
      write: append each report as a JSON line to
        ``<trainer.out>/straggler.jsonl`` on rank 0.
    """

    trigger = (1, "epoch")
    priority = 85   # before LogReport (50): the observation must exist
    # when the log entry for the same tick is assembled

    def __init__(self, comm, recorder: Optional[TraceRecorder] = None,
                 phases: Optional[Sequence[str]] = None,
                 write: bool = True):
        self.comm = comm
        self.recorder = recorder
        self.phases = None if phases is None else set(phases)
        self.write = write
        self.last_report: Optional[dict] = None

    def _recorder(self) -> TraceRecorder:
        return self.recorder if self.recorder is not None \
            else get_recorder()

    def __call__(self, trainer=None) -> None:
        rec = self._recorder()
        # a phase filter drains ONLY its names, so reports with
        # disjoint filters on different triggers never steal each
        # other's accumulated intervals
        local = rec.drain_phase_stats(
            None if self.phases is None else sorted(self.phases))
        rows = {name: {"mean": s["total_s"] / max(s["count"], 1),
                       "hist": s["hist"]}
                for name, s in local.items()}
        # collective: every process calls, even with an empty interval
        gathered = self.comm.allgather_obj(rows)
        phases: Dict[str, dict] = {}
        worst = 1.0
        for name in sorted(set().union(*(d.keys() for d in gathered))
                           if gathered else ()):
            # rows may be bare floats (older shards / hand-built test
            # fakes) or the {"mean", "hist"} dicts recorded here
            per_rank = {}
            hists = {}
            for r, d in enumerate(gathered):
                if name not in d:
                    continue
                val = d[name]
                if isinstance(val, dict):
                    per_rank[r] = val["mean"]
                    if val.get("hist") is not None:
                        hists[r] = val["hist"]
                else:
                    per_rank[r] = float(val)
            mean = sum(per_rank.values()) / len(per_rank)
            slowest_rank = max(per_rank, key=per_rank.get)
            skew = (per_rank[slowest_rank] / mean) if mean > 0 else 1.0
            phases[name] = {
                "mean_s": mean,
                "slowest_rank": slowest_rank,
                "slowest_s": per_rank[slowest_rank],
                "skew": skew,
                "ranks": len(per_rank),
            }
            if hists:
                # tail attribution on the shared lattice: the merged
                # cross-rank distribution's p50/p99 (bucket-wise sum —
                # exact while the combined samples fit the cap), plus
                # which rank owns the worst p99 and how far its tail
                # sits from the fleet's — stragglers live in tails,
                # not means
                merged = Histogram()
                for h in hists.values():
                    merged.merge(h)
                p50, p99 = merged.percentile(50), merged.percentile(99)
                rank_p99 = {r: Histogram.from_snapshot(h).percentile(99)
                            for r, h in hists.items()}
                slowest_p99 = max(rank_p99, key=rank_p99.get)
                phases[name].update({
                    "p50_s": p50,
                    "p99_s": p99,
                    "slowest_rank_p99": slowest_p99,
                    "skew_p99": (rank_p99[slowest_p99] / p99
                                 if p99 else 1.0),
                })
            worst = max(worst, skew)
        self.last_report = {
            "iteration": (trainer.updater.iteration
                          if trainer is not None else None),
            "phases": phases,
            "max_skew": worst,
        }
        if trainer is not None:
            trainer.observation["main/straggler_skew"] = worst
        rec.instant("straggler/report", cat="telemetry",
                    max_skew=round(worst, 4))
        if (self.write and trainer is not None
                and getattr(self.comm, "inter_rank", 0) == 0):
            try:
                path = os.path.join(getattr(trainer, "out", "."),
                                    "straggler.jsonl")
                # atomic per line (metrics.append_jsonl): a SIGKILL
                # mid-flush must never tear the series' last line
                append_jsonl(path, self.last_report)
            except OSError:
                pass


class MetricsExport:
    """JSONL time-series appender for ``trainer.observation``.

    Each trigger appends ONE line — iteration, epoch, elapsed wall
    clock, wall timestamp, and every float-coercible observation
    (optionally filtered by ``keys``) — to ``<trainer.out>/<filename>``.
    Each line lands via the atomic single-write append
    (:func:`chainermn_tpu.utils.metrics.append_jsonl`), so the series
    survives a crash — including a SIGKILL mid-write — with no torn
    last line.  The structured, machine-readable sibling of LogReport's
    interval-averaged ``log`` (which rewrites the whole file each
    fire): this one is append-only and per-tick, the format scrapers
    and dashboards want.
    """

    trigger = (1, "iteration")
    priority = 45   # after ObservationAggregator (90) and the straggler
    # report (85) so aggregated/derived values are in the dict

    def __init__(self, path: Optional[str] = None,
                 filename: str = "metrics.jsonl",
                 keys: Optional[Sequence[str]] = None):
        self.path = path
        self.filename = filename
        self.keys = None if keys is None else list(keys)
        self._dir_made = False

    def initialize(self, trainer) -> None:
        if self.path is None:
            self.path = os.path.join(
                getattr(trainer, "out", "."), self.filename)

    def __call__(self, trainer) -> None:
        if self.path is None:       # used without initialize()
            self.initialize(trainer)
        obs = trainer.observation
        keys = self.keys if self.keys is not None else list(obs)
        entry = {
            "iteration": trainer.updater.iteration,
            "epoch": trainer.updater.epoch,
            "elapsed_time": trainer.elapsed_time,
            "ts": time.time(),
        }
        for k in keys:
            if k not in obs:
                continue
            try:
                entry[k] = float(obs[k])
            except (TypeError, ValueError):
                continue
        try:
            if not self._dir_made:
                os.makedirs(os.path.dirname(self.path) or ".",
                            exist_ok=True)
                self._dir_made = True
            append_jsonl(self.path, entry)
        except OSError:
            pass                    # observability must never kill training

    def finalize(self, trainer=None) -> None:
        pass                        # nothing held open between lines
