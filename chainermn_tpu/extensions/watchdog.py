"""Runtime watchdog — surface a hung collective or dead peer in seconds.

The reference had NO failure detection (SURVEY §5: fault tolerance was
checkpoint + full restart); a rank wedged inside a collective stalled
the whole job silently until an operator noticed.
:class:`TrainingWatchdog` is the runtime subsystem: a daemon monitor thread fed step-boundary heartbeats that, on
a stall longer than the threshold,

1. dumps ALL thread stacks via :mod:`faulthandler` (the C-level-safe
   dump — works even when the main thread is wedged inside a collective
   that never returns to the interpreter),
2. writes a structured JSON **stall report** (rank, iteration, seconds
   stalled, per-thread Python stacks from ``sys._current_frames``, peer
   heartbeat ages) next to the trainer output,
3. optionally escalates crash-don't-deadlock: drops the coordination
   heartbeat (``jax.distributed.shutdown``) so peers fail fast, then
   ``os._exit`` — the same abort semantics as
   :func:`~chainermn_tpu.extensions.add_global_except_hook`.

Cross-process detection: with ``comm=`` given on a multi-process job,
every heartbeat also publishes a ``watchdog/hb/<rank>`` key to the JAX
coordination-service KV store (overwritten in place — O(world) keys
total), and the monitor reads ALL ranks' keys each check.  A peer whose
key stops advancing past the threshold is reported as stalled/dead in
the local report even when THIS process is healthy — survivors learn of
a dead rank in seconds instead of blocking forever in the next
collective.

The monitor thread never takes the GIL hostage: it sleeps in
``threading.Event.wait`` and wakes at ``check_interval`` (default
``stall_timeout / 4``, so a stall is caught within one check interval
of crossing the threshold).
"""

from __future__ import annotations

import faulthandler
import json
import os
import sys
import threading
import time
import traceback
from typing import Callable, Optional

__all__ = ["TrainingWatchdog"]

_KV_PREFIX = "watchdog/hb"
_KV_METRICS_PREFIX = "watchdog/metrics"


def _thread_stacks() -> dict:
    """Python-level stacks of every live thread, keyed by thread name —
    the structured half of the stall report (faulthandler's dump is the
    unstructured, crash-safe half)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = {}
    for ident, frame in sys._current_frames().items():
        label = f"{names.get(ident, 'unknown')}-{ident}"
        out[label] = traceback.format_stack(frame)
    return out


class TrainingWatchdog:
    """Trainer extension: stall detection with stack-dump reports.

    Args:
      stall_timeout: seconds without a step-boundary heartbeat before
        the stall machinery fires.  Budget it above the slowest healthy
        step (first-step compiles count — the watchdog only arms at the
        FIRST heartbeat, so compile-before-step-1 never false-fires).
      check_interval: monitor wake period; default ``stall_timeout / 4``
        (a stall is reported within one interval of crossing the
        threshold).
      comm: optional communicator.  On a multi-process job its presence
        turns on the cross-process KV heartbeats described in the
        module docstring; single-process worlds skip the KV traffic.
      escalate: after reporting, abort the process (crash-don't-
        deadlock): ``jax.distributed.shutdown()`` best-effort, then
        ``os._exit(exit_code)``.  Default False — report-only, because
        a stalled *peer* is the peer's problem to die of; set True on
        jobs where a silent wedge is worse than a restart.
      on_stall: callback ``fn(report_dict)`` invoked after the report is
        written (tests, metrics push, custom escalation).  Exceptions
        from it are swallowed — the watchdog must never be the thing
        that crashes a healthy job.
      report_path: where the JSON stall report lands; default
        ``<trainer.out>/stall_report.json`` (or CWD when used without a
        trainer).
      exit_code: the ``os._exit`` status used by escalation.
      trace_tail_events: how many flight-recorder events the stall
        report embeds (``trace_tail`` key) — the timeline of what this
        process was doing in the seconds before it stopped beating,
        alongside the stacks that show where it is stuck NOW.  Uses the
        global :func:`chainermn_tpu.utils.telemetry.get_recorder`;
        empty when tracing is disabled.  Heartbeats are also recorded
        as instant events, so the trace itself shows the beat cadence.
      metrics_publish_interval: minimum seconds between KV publishes of
        this rank's metrics snapshot (``watchdog/metrics/<rank>``,
        overwritten in place; multi-process + enabled registry only).
        The stall report embeds a MERGED metrics snapshot
        (``metrics`` / ``metrics_prom`` keys): the local registry
        folded with every peer's last published snapshot — computed
        without any collective, because a hung job cannot run one —
        so the job's last Prometheus state ships with the diagnosis.

    Use::

        wd = TrainingWatchdog(stall_timeout=300, comm=comm)
        trainer.extend(wd)          # heartbeats every iteration

    or drive it manually around any loop: ``wd.start()`` /
    ``wd.heartbeat()`` / ``wd.stop()``.
    """

    trigger = (1, "iteration")
    # runs FIRST on its tick: the heartbeat must mark the step boundary
    # before heavyweight extensions (evaluators, checkpoint writes) eat
    # wall clock that a tight threshold would misread as a stall
    priority = 1000

    def __init__(self, stall_timeout: float = 300.0,
                 check_interval: Optional[float] = None,
                 comm=None, escalate: bool = False,
                 on_stall: Optional[Callable[[dict], None]] = None,
                 report_path: Optional[str] = None,
                 exit_code: int = 42,
                 trace_tail_events: int = 64,
                 metrics_publish_interval: float = 2.0):
        if stall_timeout <= 0:
            raise ValueError("stall_timeout must be > 0")
        self.stall_timeout = float(stall_timeout)
        self.check_interval = (float(check_interval) if check_interval
                               else self.stall_timeout / 4.0)
        if self.check_interval <= 0:
            raise ValueError("check_interval must be > 0")
        self.comm = comm
        self.escalate = escalate
        self.on_stall = on_stall
        self.report_path = report_path
        self.exit_code = exit_code
        self.trace_tail_events = int(trace_tail_events)
        self.metrics_publish_interval = float(metrics_publish_interval)
        self._metrics_published_m = None
        self.stall_count = 0          # reports fired (monotonic)
        self.last_report: Optional[dict] = None
        self._beats = 0
        self._last_beat: Optional[float] = None   # armed at first beat
        self._iteration = None
        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._reported_current_stall = False
        self._reported_peers: set = set()
        self._peer_seen: dict = {}  # rank -> (beats, reader-monotonic)
        self._started_m = None      # monitor start (never-published age)

    # ------------------------------------------------------------------ #
    # KV heartbeat plumbing (cross-process)
    # ------------------------------------------------------------------ #

    @property
    def _kv(self):
        """The coordination-service client, or None outside a
        multi-process distributed world (single-process jobs need no
        cross-process heartbeats)."""
        if self.comm is None or getattr(self.comm, "inter_size", 1) <= 1:
            return None
        from jax._src import distributed

        return distributed.global_state.client

    def _publish_beat(self) -> None:
        kv = self._kv
        if kv is None:
            return
        from chainermn_tpu.communicators._obj_channel import kv_overwrite

        try:
            # one attempt, no retry sleeps (kv_overwrite's contract) —
            # this runs on the training main thread every iteration
            kv_overwrite(kv, f"{_KV_PREFIX}/{self.comm.inter_rank}",
                         f"{self._beats},{time.time()}")
        except Exception:
            # best-effort: a dropped beat degrades detection quality by
            # one interval, it must never kill training
            pass

    def _publish_metrics(self) -> None:
        """Best-effort KV publish of this rank's metrics snapshot, so a
        SURVIVOR's stall report can merge a dead peer's last state.
        Throttled (``metrics_publish_interval``); multi-process worlds
        with an enabled registry only — everyone else pays one branch."""
        kv = self._kv
        if kv is None:
            return
        from chainermn_tpu.utils.metrics import get_registry

        reg = get_registry()
        if not reg.enabled:
            return
        now_m = time.monotonic()
        if self._metrics_published_m is not None and \
                now_m - self._metrics_published_m \
                < self.metrics_publish_interval:
            return
        self._metrics_published_m = now_m
        from chainermn_tpu.communicators._obj_channel import kv_overwrite

        try:
            kv_overwrite(kv, f"{_KV_METRICS_PREFIX}/{self.comm.inter_rank}",
                         json.dumps(reg.snapshot(), default=float))
        except Exception:
            pass    # observability must never kill training

    def _merged_metrics(self):
        """The local registry snapshot folded with every peer's last
        KV-published snapshot — a merged fleet view computed WITHOUT a
        collective (a hung job cannot run one).  Returns the merged
        snapshot dict (empty when the registry is disabled and no peer
        published)."""
        from chainermn_tpu.utils.metrics import (
            MetricsRegistry,
            get_registry,
        )

        merged = MetricsRegistry(enabled=True)
        merged.load(get_registry().snapshot())
        kv = self._kv
        if kv is not None:
            try:
                entries = kv.key_value_dir_get(_KV_METRICS_PREFIX)
            except Exception:
                entries = []
            me = self.comm.inter_rank
            for key, value in entries:
                try:
                    rank = int(str(key).rsplit("/", 1)[-1])
                    if rank == me:
                        continue    # local registry is fresher
                    merged.load(json.loads(value))
                except (ValueError, TypeError):
                    continue
        return merged.snapshot()

    def _peer_ages(self) -> dict:
        """``{rank: seconds_since_the_READER_last_saw_its_beat_counter
        _advance}`` for every rank that has published, read non-blocking
        from the KV directory.

        Ages are measured on THIS process's monotonic clock from the
        moment the peer's published beat count last CHANGED — never by
        differencing the publisher's wall clock against ours, so
        cross-host clock skew cannot fabricate (or mask) a stalled
        peer.  First sight of a rank counts as an advance: a peer dead
        on arrival is reported one threshold after we first see it.

        A rank that has NEVER published is aged from the moment this
        monitor started: the motivating hang class (PJRT/plugin init
        wedging before step 1) never reaches a first heartbeat, and a
        peer invisible to the detector would be exactly the silent
        stall the watchdog exists to surface.

        Returns ``None`` (distinct from "no peers") when the KV read
        itself failed — the caller must keep its episode state rather
        than mistake a transport blip for every peer recovering."""
        kv = self._kv
        if kv is None:
            return {}
        try:
            entries = kv.key_value_dir_get(_KV_PREFIX)
        except Exception:
            return None
        now_m = time.monotonic()
        ages = {}
        for key, value in entries:
            try:
                rank = int(str(key).rsplit("/", 1)[-1])
                beats = int(str(value).split(",")[0])
            except (ValueError, IndexError):
                continue
            seen = self._peer_seen.get(rank)
            if seen is None or seen[0] != beats:
                self._peer_seen[rank] = (beats, now_m)
                ages[rank] = 0.0
            else:
                ages[rank] = round(now_m - seen[1], 3)
        if self._started_m is not None:
            for rank in range(getattr(self.comm, "inter_size", 0)):
                if rank not in ages and rank != self.comm.inter_rank:
                    ages[rank] = round(now_m - self._started_m, 3)
        return ages

    # ------------------------------------------------------------------ #
    # heartbeat + monitor
    # ------------------------------------------------------------------ #

    def heartbeat(self, iteration=None) -> None:
        """Mark a step boundary; arms the watchdog on the first call."""
        self._beats += 1
        self._iteration = iteration
        self._last_beat = time.monotonic()
        self._reported_current_stall = False
        from chainermn_tpu.utils.metrics import get_registry
        from chainermn_tpu.utils.telemetry import get_recorder

        get_recorder().instant("watchdog/heartbeat", cat="watchdog",
                               step=iteration, beats=self._beats)
        get_registry().inc("watchdog/heartbeats")
        self._publish_beat()
        self._publish_metrics()

    def start(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return
        if self._started_m is None:
            self._started_m = time.monotonic()
        self._stop_evt.clear()
        self._thread = threading.Thread(
            target=self._monitor, name="training-watchdog", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop_evt.set()
        th = self._thread
        if th is not None:
            th.join(timeout=self.check_interval + 5)
        self._thread = None

    def _monitor(self) -> None:
        while not self._stop_evt.wait(self.check_interval):
            last = self._last_beat
            if last is None:        # not armed yet (still compiling)
                continue
            stalled_s = time.monotonic() - last
            peer_ages = self._peer_ages()
            if peer_ages is None:
                # KV read blip: keep per-peer episode state untouched
                # (clearing it would re-report every still-dead peer on
                # the next successful read), detect local stalls only
                peer_ages, stalled_peers, new_peers = {}, {}, {}
            else:
                stalled_peers = {
                    r: a for r, a in peer_ages.items()
                    if a > self.stall_timeout
                    and (self.comm is None or r != self.comm.inter_rank)}
                # one report per stall EPISODE, locally and per peer: a
                # permanently dead peer must not re-dump stacks and
                # rewrite the report every check interval for the rest
                # of the job
                self._reported_peers &= set(stalled_peers)  # re-arm
                new_peers = {r: a for r, a in stalled_peers.items()
                             if r not in self._reported_peers}
            local_stall = stalled_s > self.stall_timeout
            local_to_report = local_stall \
                and not self._reported_current_stall
            if not local_to_report and not new_peers:
                continue
            self._reported_peers |= set(new_peers)
            self._fire(local_stall, stalled_s, peer_ages, new_peers)

    # ------------------------------------------------------------------ #
    # stall handling
    # ------------------------------------------------------------------ #

    def _fire(self, local_stall, stalled_s, peer_ages, stalled_peers):
        if local_stall:
            # peer-only reports must not consume the local episode: a
            # local stall beginning later (no beat in between) still
            # deserves its own report
            self._reported_current_stall = True
        self.stall_count += 1
        try:
            from chainermn_tpu.utils.metrics import get_registry

            get_registry().inc("watchdog/stalls")
        except Exception:
            pass    # the stall path must survive a broken metrics layer
        rank = getattr(self.comm, "inter_rank", 0) if self.comm else 0
        report = {
            "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "rank": rank,
            "kind": "local-stall" if local_stall else "peer-stall",
            "seconds_since_heartbeat": round(stalled_s, 3),
            "stall_timeout_s": self.stall_timeout,
            "iteration": self._iteration,
            "beats": self._beats,
            "peer_heartbeat_ages_s": peer_ages,
            "stalled_peers": stalled_peers,
            "threads": _thread_stacks(),
            "escalating": bool(self.escalate and local_stall),
        }
        # the flight recorder's ring tail: what this process was DOING
        # in the seconds before the beats stopped — the timeline half of
        # the post-mortem (the stacks above are the "stuck now" half)
        try:
            from chainermn_tpu.utils.telemetry import get_recorder

            recorder = get_recorder()
            report["trace_tail"] = recorder.tail(self.trace_tail_events)
            report["trace_enabled"] = recorder.enabled
        except Exception:
            report["trace_tail"] = []
            report["trace_enabled"] = False
        # the job's last Prometheus state, merged across ranks from the
        # KV-published snapshots (no collective — see _merged_metrics):
        # a hung job ships its metrics with the diagnosis
        try:
            from chainermn_tpu.utils.metrics import (
                get_registry as _get_reg,
                to_prometheus,
            )

            snap = self._merged_metrics()
            report["metrics"] = snap
            report["metrics_prom"] = to_prometheus(
                snap, labels={"rank": "merged"})
            report["metrics_enabled"] = _get_reg().enabled
        except Exception:
            report["metrics"] = {}
            report["metrics_prom"] = ""
            report["metrics_enabled"] = False
        # the installed burn-rate alert state (utils/alerts.py): a
        # stall that follows minutes of SLO burn should say so in the
        # same document as the stacks
        try:
            from chainermn_tpu.utils.alerts import get_installed

            mgr = get_installed()
            report["alerts"] = None if mgr is None else mgr.state()
        except Exception:
            report["alerts"] = None
        self.last_report = report
        path = self.report_path or "stall_report.json"
        try:
            with open(path, "w") as f:
                json.dump(report, f, indent=1)
        except OSError:
            pass
        # the crash-safe dump: C-level faulthandler walks every thread
        # even if the interpreter state is wedged mid-collective
        sys.stderr.write(
            f"\n[chainermn_tpu watchdog] rank {rank}: "
            f"{report['kind']} — no step-boundary heartbeat for "
            f"{stalled_s:.1f}s (threshold {self.stall_timeout}s, "
            f"iteration {self._iteration}); report at {path}\n")
        try:
            faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        except Exception:
            pass
        sys.stderr.flush()
        if self.on_stall is not None:
            try:
                self.on_stall(report)
            except Exception:
                pass
        if self.escalate and local_stall:
            self._abort()

    def _abort(self) -> None:
        """Crash-don't-deadlock: mirror the global except hook's MPI_Abort
        analogue so surviving peers fail fast instead of blocking."""
        try:
            import jax

            if jax.process_count() > 1:
                jax.distributed.shutdown()
        except Exception:
            pass
        os._exit(self.exit_code)

    # ------------------------------------------------------------------ #
    # trainer extension protocol
    # ------------------------------------------------------------------ #

    def initialize(self, trainer) -> None:
        if self.report_path is None:
            self.report_path = os.path.join(
                getattr(trainer, "out", "."), "stall_report.json")
        self.start()

    def __call__(self, trainer) -> None:
        self.heartbeat(iteration=trainer.updater.iteration)

    def finalize(self, trainer=None) -> None:
        self.stop()
