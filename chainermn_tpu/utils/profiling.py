"""Profiling/tracing subsystem — a first-class facility the reference
never had (SURVEY §5: its practice was external ``nvprof``/MPI tracing;
the only instrumentation surface was pure_nccl's CUDA stream usage).

Three layers:

- :class:`Profiler` — named duration/counter registry with
  ``time_block(name)`` context timing and a stats table.  Durations are
  *host-observed* (dispatch → value materialisation), which is what the
  user can act on under async dispatch.
- :func:`profiled_communicator` — wraps any communicator so every eager
  collective (``allreduce``, ``bcast_obj``, ...) is timed into a
  profiler, with payload byte counts — the per-collective duration
  metrics SURVEY §5 prescribes.
- :func:`trace` — delegates to ``jax.profiler`` for full XLA/TPU traces
  viewable in TensorBoard/XProf (device-side truth; the Profiler is the
  cheap always-on layer).

Plus :class:`ProfileReport`, a trainer extension printing the table on a
trigger (rank-0 convention, like the reference's LogReport usage).
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Dict, Optional

import jax
import numpy as np

__all__ = [
    "Profiler",
    "ProfileReport",
    "get_profiler",
    "profiled_communicator",
    "trace",
]


@dataclass
class _Stat:
    count: int = 0
    total: float = 0.0
    maximum: float = 0.0
    bytes: int = 0

    def add(self, seconds: float, nbytes: int = 0) -> None:
        self.count += 1
        self.total += seconds
        self.maximum = max(self.maximum, seconds)
        self.bytes += nbytes


@dataclass
class Profiler:
    """Named timing registry.  Thread-compatible (single-writer per name)."""

    stats: Dict[str, _Stat] = field(default_factory=dict)
    enabled: bool = True

    def record(self, name: str, seconds: float, nbytes: int = 0) -> None:
        if not self.enabled:
            return
        self.stats.setdefault(name, _Stat()).add(seconds, nbytes)

    @contextlib.contextmanager
    def time_block(self, name: str, nbytes: int = 0, sync=None):
        """Time a block.  ``sync`` (optional callable or array) is invoked /
        materialised before the clock stops, so async-dispatched device
        work is actually included.

        Disabled → truly zero-cost: no clock reads, and crucially no
        ``device_get`` materialisation — a disabled profiler must never
        collapse the async-dispatch overlap it exists to measure."""
        if not self.enabled:
            yield {}
            return
        t0 = time.perf_counter()
        box = {}
        try:
            yield box
        finally:
            out = box.get("out", sync)
            if callable(out):
                out()
            elif out is not None:
                _materialise(out)
            self.record(name, time.perf_counter() - t0, nbytes)

    def summary(self) -> str:
        if not self.stats:
            return "(no profile data)"
        rows = [("name", "count", "total_s", "mean_ms", "max_ms", "MB")]
        for name in sorted(self.stats):
            s = self.stats[name]
            rows.append((
                name, str(s.count), f"{s.total:.3f}",
                f"{1e3 * s.total / max(s.count, 1):.2f}",
                f"{1e3 * s.maximum:.2f}",
                f"{s.bytes / 1e6:.1f}"))
        widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
        return "\n".join(
            "  ".join(c.ljust(w) for c, w in zip(r, widths)) for r in rows)

    def reset(self) -> None:
        self.stats.clear()


_GLOBAL = Profiler()


def get_profiler() -> Profiler:
    """The default process-global profiler."""
    return _GLOBAL


def _nbytes(x) -> int:
    try:
        return int(jax.tree.reduce(
            lambda a, b: a + b,
            jax.tree.map(lambda v: v.size * v.dtype.itemsize
                         if hasattr(v, "dtype") else 0, x), 0))
    except Exception:
        return 0


def _materialise(out) -> None:
    """Wait for async-dispatched results (the sync anchor
    ``time_block``'s finally performs) — used when only the flight
    recorder is timing, so its span still covers real completion."""
    jax.block_until_ready(out)


_COLLECTIVES = (
    "bcast", "allreduce", "allgather", "alltoall", "gather", "scatter",
    "reduce_scatter", "send", "bcast_obj", "allgather_obj", "gather_obj",
    "allreduce_obj", "scatter_obj", "send_obj", "recv_obj", "barrier",
    "bcast_data", "multi_node_mean_grad",
)


class _ProfiledCommunicator:
    """Transparent proxy timing every eager collective into a profiler.

    Host-observed wall time per call: dispatch, any XLA execution it
    forces, and result materialisation (obj collectives are host-blocking
    already; array collectives are materialised to close the async gap).
    The jitted in-step collectives (``ops.*`` inside shard_map) are NOT
    routed here — those belong to XLA's domain; use :func:`trace` to see
    them.  This matches what the reference could observe per NCCL call.

    Every timed call is also recorded as a ``cat="comm"`` span into the
    flight recorder (:mod:`chainermn_tpu.utils.telemetry`), so eager
    collectives land on the same timeline as the step phases.
    """

    def __init__(self, comm, profiler: Optional[Profiler] = None,
                 prefix: str = "comm."):
        self._comm = comm
        self._profiler = profiler or get_profiler()
        self._prefix = prefix

    def __getattr__(self, name):
        attr = getattr(self._comm, name)
        if name not in _COLLECTIVES or not callable(attr):
            return attr
        profiler, label = self._profiler, self._prefix + name
        from chainermn_tpu.utils.telemetry import get_recorder

        def timed(*args, **kwargs):
            recorder = get_recorder()
            if not profiler.enabled and not recorder.enabled:
                return attr(*args, **kwargs)   # zero accounting overhead
            nbytes = _nbytes(args)
            # recorder span OUTER: time_block materialises the output in
            # its finally, so the inner exit must be the profiler's for
            # both timers to cover the same (synced) interval
            with recorder.span(label, cat="comm", nbytes=nbytes), \
                    profiler.time_block(label, nbytes=nbytes) as box:
                out = attr(*args, **kwargs)
                box["out"] = out
                if not profiler.enabled:
                    # the disabled time_block skips its sync anchor; the
                    # recorder span must still cover real completion
                    _materialise(out)
            return out

        # cache the wrapper on the instance: __getattr__ only fires for
        # missing attributes, so every later access skips the closure
        # rebuild (enabled-ness is re-checked inside per call)
        self.__dict__[name] = timed
        return timed

    @property
    def profiler(self) -> Profiler:
        return self._profiler

    def __repr__(self) -> str:
        return f"ProfiledCommunicator({self._comm!r})"


def profiled_communicator(comm, profiler: Optional[Profiler] = None):
    """Wrap ``comm`` so every collective is timed (see module docstring)."""
    return _ProfiledCommunicator(comm, profiler)


@contextlib.contextmanager
def trace(logdir: str, *, host_tracer_level: int = 2):
    """Full device trace via ``jax.profiler`` (TensorBoard/XProf format).

    The device-side complement to :class:`Profiler`: shows per-HLO and
    per-collective device time, fusion decisions, and ICI traffic on real
    TPUs.  Usage::

        with profiling.trace("/tmp/trace"):
            train_some_steps()
    """
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = host_tracer_level
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class ProfileReport:
    """Trainer extension: print (rank 0) and reset the profiler table.

    With ``comm`` given on a MULTI-process job, the table is aggregated
    across processes first — count/total/bytes summed, max-of-max — via
    ``allgather_obj``, so the printed stats reflect the WORLD, not rank
    0's local view (processes may hold divergent name sets —
    rank-0-only extensions — each name aggregates over the ranks that
    reported it, the ObservationAggregator convention).  The allgather
    is COLLECTIVE: every process must extend the trainer with this
    report on the same trigger (the ObservationAggregator deployment
    shape).  A report registered on rank 0 only must pass
    ``aggregate=False`` to keep the old local-table-with-rank-0-print
    behaviour; single-process worlds skip the collective entirely
    either way.
    """

    trigger = (1, "epoch")
    priority = 60

    def __init__(self, profiler: Optional[Profiler] = None, comm=None,
                 reset: bool = True, aggregate: bool = True):
        self.profiler = profiler or get_profiler()
        self.comm = comm
        self.reset = reset
        self.aggregate = aggregate

    def _aggregate(self) -> Profiler:
        """World-wide stats table (or the local one without a comm /
        on a single process / with ``aggregate=False``)."""
        if self.comm is None or not self.aggregate or \
                getattr(self.comm, "inter_size", 1) <= 1:
            return self.profiler
        gathered = self.comm.allgather_obj({
            name: (s.count, s.total, s.maximum, s.bytes)
            for name, s in self.profiler.stats.items()})
        agg = Profiler()
        for d in gathered:
            for name, (count, total, maximum, nbytes) in d.items():
                st = agg.stats.setdefault(name, _Stat())
                st.count += count
                st.total += total
                st.maximum = max(st.maximum, maximum)
                st.bytes += nbytes
        return agg

    def __call__(self, trainer) -> None:
        table = self._aggregate()
        if self.comm is None or self.comm.rank == 0:
            world = "" if self.comm is None else \
                f", {getattr(self.comm, 'inter_size', 1)} process(es)"
            print(f"[profile @ iter {trainer.updater.iteration}{world}]")
            print(table.summary())
        if self.reset:
            self.profiler.reset()
