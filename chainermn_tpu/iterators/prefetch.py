"""Overlapped input pipeline: prefetching host→device feed.

The reference closed the input-pipeline gap on GPUs with
``MultiprocessIterator`` workers plus pure_nccl's double-buffer threads
(SURVEY §3.1); the single-controller JAX port reopened it — every
``StandardUpdater.update()`` paid iterator pull → convert → ``np.stack``
→ ``jax.device_put`` → dispatch in series, with the devices idle during
host assembly.  This module is the TPU-native answer: a bounded
slot-ring (depth-k) background worker that pulls, converts, stacks the
NEXT fused window and issues its ``jax.device_put`` onto the mesh
sharding *ahead of consumption*, so steady-state step time is
``max(host, device)`` instead of ``host + device``.

Three layers, lowest first:

- :func:`default_converter` / :class:`StagingConverter` — batch → tuple
  of stacked host arrays.  The staging variant stacks each column
  directly into a small ring of preallocated buffers reused across
  steps when shapes repeat (no per-element ``np.asarray`` copy, no
  per-step allocation).
- :func:`apply_batch_policy` — the world-size divisibility policy
  (drop-remainder or raise), shared verbatim with the synchronous
  updater path so both feeds are bitwise-identical.
- :class:`PrefetchIterator` — the slot-ring worker.  Yields
  :class:`DeviceWindow` records (device-resident, sharding-placed
  fused windows) instead of raw batches; propagates worker exceptions
  on ``next()``; shuts down cleanly; and implements
  ``state_dict``/``load_state_dict`` by draining in-flight slots and
  rewinding the base iterator to the oldest unconsumed pull, so
  checkpoint semantics match the serial path exactly.

``utils.comm_model.choose_prefetch_depth`` picks the slot count from
the measured host-assembly / device-step ratio; ``docs/PIPELINE.md``
explains when overlap helps.
"""

from __future__ import annotations

import queue
import threading
import time
import warnings
from typing import Callable, Optional

import numpy as np

from chainermn_tpu.utils.metrics import get_registry
from chainermn_tpu.utils.telemetry import get_recorder

__all__ = [
    "DeviceWindow",
    "PrefetchIterator",
    "StagingConverter",
    "apply_batch_policy",
    "assemble_window",
    "default_converter",
    "pull_batch",
    "put_window",
]


def default_converter(batch):
    """Batch → tuple of stacked host arrays (Chainer's concat_examples).

    Accepts three batch shapes:

    - ``list`` of examples (the generic iterator protocol): each example
      a scalar/array (→ one stacked column) or a tuple/list of fields
      (→ one stacked column per field).  ``np.stack`` coerces elements
      itself — no per-element ``np.asarray`` pre-pass (that was a second
      copy for non-ndarray examples).
    - ``np.ndarray``: an already-stacked batch (the
      :class:`~chainermn_tpu.SerialIterator` numpy fast path) — passed
      through as a single column, zero copies.
    - ``tuple`` whose elements are ALL ``np.ndarray``: already-stacked
      per-field columns (fast-path tuple datasets,
      :class:`NativeBatchIterator`) — passed through.  A tuple holding
      anything else (e.g. a tuple of example-tuples) is a batch of
      examples and stacks like a list.
    """
    if not len(batch):
        raise ValueError("empty batch")
    if isinstance(batch, np.ndarray):
        return (batch,)
    if isinstance(batch, tuple) and all(
            isinstance(col, np.ndarray) for col in batch):
        # all-ndarray tuple = pre-stacked columns; any other tuple is a
        # batch of examples (e.g. a tuple of example-tuples) and takes
        # the stacking path below, as it always did
        return batch
    first = batch[0]
    if isinstance(first, (tuple, list)):
        cols = list(zip(*batch))
        return tuple(np.stack(col) for col in cols)
    return (np.stack(batch),)


class StagingConverter:
    """:func:`default_converter` minus the per-step allocation.

    Stacks each column directly into a preallocated staging buffer
    (``np.stack(col, out=buf)``) reused across steps when the column's
    (length, element shape, dtype) repeat — steady-state training hits
    the same shapes every step, so after the ring's first lap batch
    assembly is one pass into warm pages instead of allocate (and
    first-touch every page) + copy.

    Buffers rotate through a ring of ``n_buffers`` per column key, so
    the last ``n_buffers - 1`` returned batches stay valid; the ring
    holds ``n_buffers × batch bytes`` of host memory for as long as the
    converter lives.  Who may recycle a buffer that ``device_put`` was
    given UNCOPIED (see :func:`put_window`):

    - the serial feed of an unfused ``StandardUpdater``, because its
      ``update()`` retires a window (its step has run, so its batch
      has been read to the end) before the ring comes round to its
      buffer — the updater sizes the ring ``max_inflight + 1`` for
      that (``StandardUpdater.staging_buffers_needed``).  A converter
      given to such an updater is the updater's alone: any other
      caller advances the ring behind its back;
    - nobody else: :class:`PrefetchIterator`'s worker cannot see a
      window retire, so :func:`put_window` copies what this converter
      owns there (and the lone batch of a fused serial window), and
      the ring only has to cover the unstacked window
      (``steps_per_execution + 1``; the prefetcher's default is
      ``max(depth, steps_per_execution + 1) + 3``).

    ``last_reused_bytes`` / ``last_fresh_bytes`` say where the latest
    batch landed: in recycled ring memory, or in memory allocated for
    it (a ring buffer's first use; a mixed, ragged or non-array column,
    which falls back to a plain ``np.stack``).  :func:`pull_batch`
    puts them on the ``feed/convert`` span and into the metrics
    registry.  Already-stacked array batches (fast-path iterators) pass
    through untouched, same as :func:`default_converter`, and count as
    neither.
    """

    def __init__(self, n_buffers: int = 4):
        if n_buffers < 2:
            raise ValueError("need at least 2 staging buffers "
                             "(one filling, one in flight)")
        self._n_buffers = n_buffers
        self._rings: dict = {}      # key -> [buffers...]
        self._turn: dict = {}       # key -> next ring index
        self.last_reused_bytes = 0
        self.last_fresh_bytes = 0

    def _staging(self, key, shape, dtype):
        ring = self._rings.get(key)
        if ring is None:
            ring = self._rings[key] = []
            self._turn[key] = 0
        i = self._turn[key]
        if len(ring) <= i:
            ring.append(np.empty(shape, dtype))
            self.last_fresh_bytes += ring[i].nbytes
        else:
            self.last_reused_bytes += ring[i].nbytes
        self._turn[key] = (i + 1) % self._n_buffers
        return ring[i]

    def owns_buffers(self, arrays) -> bool:
        """True if any of ``arrays`` lies in this converter's ring
        memory (will be overwritten on ring wrap-around): a ring buffer
        itself or a view of one — :func:`apply_batch_policy`'s
        ``a[:keep]`` when it drops a remainder.  Memory, not identity.
        The feed uses this to copy such arrays before a transfer that
        may outlive the ring's lap — see :func:`put_window`."""
        bufs = {id(b) for ring in self._rings.values() for b in ring}
        for a in arrays:
            while a is not None:
                if id(a) in bufs:
                    return True
                a = getattr(a, "base", None)
        return False

    def _stack(self, col_idx, col):
        first = col[0]
        if isinstance(first, np.ndarray) and all(
                isinstance(v, np.ndarray)
                and v.shape == first.shape and v.dtype == first.dtype
                for v in col):
            key = (col_idx, len(col), first.shape, first.dtype)
            buf = self._staging(key, (len(col),) + first.shape,
                                first.dtype)
            return np.stack(col, out=buf)
        # mixed / non-array elements (python scalars, ragged): let numpy
        # decide the result dtype exactly as default_converter would
        out = np.stack(col)
        self.last_fresh_bytes += out.nbytes
        return out

    def __call__(self, batch):
        if not len(batch):
            raise ValueError("empty batch")
        self.last_reused_bytes = self.last_fresh_bytes = 0
        if isinstance(batch, np.ndarray):
            return (batch,)
        if isinstance(batch, tuple) and all(
                isinstance(col, np.ndarray) for col in batch):
            return batch
        first = batch[0]
        if isinstance(first, (tuple, list)):
            cols = list(zip(*batch))
            return tuple(self._stack(i, col) for i, col in enumerate(cols))
        return (self._stack(0, batch),)


def apply_batch_policy(arrays, world_size: int, drop_remainder: bool):
    """World-size divisibility policy, shared by the serial and
    prefetched feeds (identical batches → bitwise-identical training)."""
    if arrays[0].shape[0] % world_size:
        if not drop_remainder:
            raise ValueError(
                f"global batch {arrays[0].shape[0]} not divisible by "
                f"world size {world_size}")
        keep = (arrays[0].shape[0] // world_size) * world_size
        if keep == 0:
            raise ValueError(
                f"batch of {arrays[0].shape[0]} examples cannot be "
                f"sharded over {world_size} devices — raise batch_size "
                f"to at least the world size")
        arrays = tuple(a[:keep] for a in arrays)
    return arrays


def _n_examples(batch) -> int:
    if isinstance(batch, tuple) and batch \
            and isinstance(batch[0], np.ndarray):
        return len(batch[0])        # pre-stacked columns
    return len(batch)


def pull_batch(iterator, converter, world_size: int,
               drop_remainder: bool):
    """Pull one batch, convert it, apply the divisibility policy: the
    ONE place either feed obtains a batch, so the serial feed (under the
    updater's ``step/host`` span) and the prefetch worker (on its own
    thread) record the same two spans around the same work —
    ``feed/pull`` is ``next(iterator)`` (dataset indexing, the list of
    examples), ``feed/convert`` the converter's stack to one array per
    field and :func:`apply_batch_policy`.

    ``feed/convert`` carries ``bytes`` (out) and ``reused``: the bytes
    of this batch that a :class:`StagingConverter` stacked into
    recycled ring memory — 0 on a ring's first lap, for mixed or
    ragged columns, for batches that arrive stacked and under any other
    converter.  The metrics registry counts the same as
    ``feed/staging_reused_bytes`` beside ``feed/staging_fresh_bytes``
    (what the converter had to allocate): in steady state the second
    stands still."""
    tracer = get_recorder()
    with tracer.span("feed/pull", cat="input") as span:
        batch = next(iterator)
        if tracer.enabled:
            span.set(n=_n_examples(batch))
    with tracer.span("feed/convert", cat="input") as span:
        arrays = apply_batch_policy(converter(batch), world_size,
                                    drop_remainder)
        staging = isinstance(converter, StagingConverter)
        if tracer.enabled:
            span.set(bytes=sum(a.nbytes for a in arrays),
                     reused=converter.last_reused_bytes if staging else 0)
    if staging:
        reg = get_registry()
        reg.inc("feed/staging_reused_bytes", converter.last_reused_bytes)
        reg.inc("feed/staging_fresh_bytes", converter.last_fresh_bytes)
    return arrays


def assemble_window(pull_fn, n_steps: int):
    """THE window-fill contract, shared by the serial updater feed and
    the prefetch worker (one definition → the prefetch-on/off bitwise
    parity cannot drift): fill up to ``n_steps`` same-shape batches
    from ``pull_fn``; stop early on iterator exhaustion or a ragged
    (end-of-epoch partial) batch, which can't stack — the ragged batch
    rides along as the pending tail.  Returns ``(window, pending)``;
    the FIRST pull's StopIteration propagates."""
    first = pull_fn()
    window, pending = [first], None
    while len(window) < n_steps:
        try:
            nxt = pull_fn()
        except StopIteration:
            break
        if any(a.shape != b.shape for a, b in zip(nxt, first)):
            pending = nxt
            break
        window.append(nxt)
    return window, pending


def put_window(window, pending, batch_sharding, stacked_sharding,
               converter=None, source=None, *, caller_retires=False):
    """Transfer an assembled window: single batches go up under the
    per-example sharding, multi-step windows are stacked with the
    leading scan axis unsharded.  Returns ``(arrays, k, tail)`` —
    shared by both feeds, like :func:`assemble_window`, and timed as
    one ``feed/put`` span in both (the owned-buffer copy, the
    window-level stack and the ``device_put`` calls; whether the copy
    itself has ended when ``device_put`` returns is the runtime's).

    Aliasing hazard: sharded ``device_put`` of a host array can DEFER
    the per-shard copy until first use, silently aliasing the source —
    and ``block_until_ready`` does NOT force it (the alias counts as
    ready; measured on the CPU backend); on the TPU it returns while
    the runtime still reads the host array.  Harmless for arrays nobody
    mutates (fast-path fancy-index gathers, fresh ``np.stack``
    outputs), fatal for a converter's recycled staging buffer — the
    ring wraps and rewrites a window already handed downstream — the
    same goes for an iterator recycling its own output buffers
    (:class:`NativeBatchIterator` slot views).  When ``converter`` or
    ``source`` (the batch iterator) advertises its buffers
    (``owns_buffers``, see :class:`StagingConverter`), those arrays are
    COPIED before the transfer — a fresh batch-sized allocation, which
    is why staging pays off under ``prefetch=`` only for fused windows,
    whose window-level stack is the copy.  A custom converter or
    iterator that reuses memory without advertising it must copy
    itself.

    ``caller_retires=True`` is the caller's guarantee for the
    CONVERTER's buffers, and lets them go to ``device_put`` as they
    are: every window handed over here has been read to the end before
    the converter's ring comes round to its buffer.  The serial feed
    gives it where one ``update()`` fills one ring buffer
    (``StandardUpdater.__init__``), on every backend and with no wait
    on the transferred array:

    - with ``n`` buffers, the one filled in ``update()`` number *u* is
      next written in ``update()`` *u + n*;
    - by the end of ``update()`` *u + n - 1* the updater's retire loop
      has blocked on every window older than the newest
      ``max_inflight``: window *u* is among them when
      ``n >= max_inflight + 1``;
    - a retired window has run its step, so its batch was read to the
      end — whether the runtime copied it late (the TPU) or aliased it
      and never copied (the CPU backend).

    The prefetch worker cannot see a window retire and never passes
    it.  ``source``'s buffers are copied either way: the iterator
    recycles those on its own schedule."""
    import jax

    probes = [p for p in (
        None if caller_retires else getattr(converter, "owns_buffers", None),
        getattr(source, "owns_buffers", None)) if p is not None]

    def _safe(arrays):
        if not probes:
            return arrays
        return tuple(
            np.array(a) if any(p((a,)) for p in probes) else a
            for a in arrays)

    k = len(window)
    tracer = get_recorder()
    with tracer.span("feed/put", cat="input", k=k) as span:
        if k == 1:
            arrays = tuple(jax.device_put(a, batch_sharding)
                           for a in _safe(window[0]))
        else:
            # the window-level np.stack already copies out of any
            # staging buffers, so the stacked transfer can stay fully
            # lazy
            arrays = tuple(
                jax.device_put(np.stack(cols), stacked_sharding)
                for cols in zip(*window))
        tail = None if pending is None else tuple(
            jax.device_put(a, batch_sharding) for a in _safe(pending))
        if tracer.enabled:
            span.set(bytes=sum(a.nbytes for batch in window for a in batch)
                     + sum(a.nbytes for a in pending or ()))
    return arrays, k, tail


class DeviceWindow:
    """One prefetched fused window, already on device.

    ``arrays``: tuple of device arrays — sharded ``(batch, ...)`` when
    ``k == 1``, ``(k, batch/k-per-step, ...)`` stacked windows (leading
    scan axis unsharded) when ``k > 1``.  ``tail``: the ragged
    end-of-epoch batch that could not stack into the window (device
    arrays, single-step sharding), or None.  The epoch bookkeeping is
    the base iterator's state AFTER the window's final pull — what the
    serial path would observe at the same consumption point.
    """

    __slots__ = ("arrays", "k", "tail", "epoch", "is_new_epoch",
                 "epoch_detail")

    def __init__(self, arrays, k, tail, epoch, is_new_epoch,
                 epoch_detail):
        self.arrays = arrays
        self.k = k
        self.tail = tail
        self.epoch = epoch
        self.is_new_epoch = is_new_epoch
        self.epoch_detail = epoch_detail

    @property
    def n_iterations(self) -> int:
        """Training iterations this window advances (k + ragged tail)."""
        return self.k + (1 if self.tail is not None else 0)


class PrefetchIterator:
    """Bounded slot-ring prefetcher: background host assembly + ahead-of-
    consumption ``jax.device_put``.

    Wraps a batch iterator (``SerialIterator`` protocol) and yields
    :class:`DeviceWindow` records: the next ``steps_per_execution``-deep
    fused window, converted, stacked, divisibility-policed, and ALREADY
    transferred onto the communicator's mesh sharding — all done by a
    daemon worker thread up to ``depth`` windows ahead of the consumer.

    Semantics contract (pinned by ``tests/iterator_tests/test_prefetch``):

    - the window/tail stream is identical to what ``StandardUpdater``'s
      serial path assembles (same converter → same policy → same
      stacking), so training with prefetch on vs off is bitwise equal;
    - a worker exception is re-raised from ``next()`` (not swallowed in
      a background thread, the reference MultiprocessIterator's classic
      failure mode);
    - ``close()`` joins the worker — no leaked threads;
    - ``state_dict()`` drains in-flight slots and rewinds the base
      iterator to the oldest UNCONSUMED pull before snapshotting, so a
      checkpoint resumes exactly where the consumer stood, not where
      the read-ahead had raced to.  The discarded lookahead is re-pulled
      after the rewind (the restored RNG makes the replay identical).

    Args:
      iterator: base batch iterator (``next``/``epoch``/``epoch_detail``;
        ``state_dict``/``load_state_dict`` required only for resume).
      comm: communicator supplying ``mesh``/``axis_name``/``size`` for
        sharding placement and the divisibility policy.
      converter: batch → tuple of host arrays; default a
        :class:`StagingConverter` with ``depth + 3`` buffers.
      steps_per_execution: fused window size — the updater wires its
        FULL dispatch window here, ``steps_per_execution ×
        accum_steps`` when gradient accumulation is on (the feed is
        agnostic to how the window splits into optimiser updates).
      depth: slot-ring length — windows prefetched ahead.  See
        ``utils.comm_model.choose_prefetch_depth``.
      drop_remainder: the divisibility policy switch.
      join_timeout: seconds ``state_dict``/``reset``/``close`` wait for
        the worker to stop.  A base iterator blocked inside ``next()``
        (streaming source with no data) cannot observe the stop flag;
        after the timeout ``state_dict``/``reset`` raise instead of
        hanging the trainer, and ``close`` warns and abandons the
        daemon worker (it exits on its own once the pull unblocks).
    """

    def __init__(self, iterator, comm, converter: Optional[Callable] = None,
                 steps_per_execution: int = 1, depth: int = 2,
                 drop_remainder: bool = True, join_timeout: float = 60.0):
        import jax  # deferred: keep module import light
        from jax.sharding import NamedSharding, PartitionSpec as P

        if depth < 1:
            raise ValueError("prefetch depth must be >= 1")
        if steps_per_execution < 1:
            raise ValueError("steps_per_execution must be >= 1")
        self._base = iterator
        self._comm = comm
        # ring sizing: during window assembly up to steps_per_execution
        # + 1 (pending) converted batches are live BEFORE the window
        # stack copies them, on top of the depth + inflight single-step
        # windows whose staging buffers device_put may still be reading
        self._converter = converter if converter is not None else \
            StagingConverter(
                n_buffers=max(depth, steps_per_execution + 1) + 3)
        if isinstance(self._converter, StagingConverter) and \
                self._converter._n_buffers < steps_per_execution + 1:
            # an undersized ring recycles buffers still referenced IN
            # the unstacked window — duplicated batches, no error
            raise ValueError(
                f"StagingConverter(n_buffers="
                f"{self._converter._n_buffers}) is too small for "
                f"steps_per_execution={steps_per_execution}: the ring "
                f"must hold the whole unstacked window "
                f"(>= steps_per_execution + 1 buffers)")
        self._n_steps = steps_per_execution
        self.depth = depth
        self._drop_remainder = drop_remainder
        self.join_timeout = join_timeout
        self._batch_sharding = NamedSharding(comm.mesh, P(comm.axis_name))
        self._stacked_sharding = NamedSharding(
            comm.mesh, P(None, comm.axis_name))
        self._can_rewind = (hasattr(iterator, "state_dict")
                            and hasattr(iterator, "load_state_dict"))

        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._buffer: list = []        # drained-but-unconsumed items
        self._spill: list = []         # worker's undelivered item on halt
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self._finished = False

        self.epoch = getattr(iterator, "epoch", 0)
        self.is_new_epoch = getattr(iterator, "is_new_epoch", False)
        self._epoch_detail = float(getattr(iterator, "epoch_detail", 0.0))

    # ------------------------------------------------------------------ #
    # worker side
    # ------------------------------------------------------------------ #

    def _snapshot(self):
        return self._base.state_dict() if self._can_rewind else None

    def _pull(self):
        return pull_batch(self._base, self._converter, self._comm.size,
                          self._drop_remainder)

    def _to_device(self, window, pending):
        arrays, k, tail = put_window(
            window, pending, self._batch_sharding, self._stacked_sharding,
            converter=self._converter, source=self._base)
        return DeviceWindow(
            arrays, k, tail,
            epoch=getattr(self._base, "epoch", 0),
            is_new_epoch=getattr(self._base, "is_new_epoch", False),
            epoch_detail=float(getattr(self._base, "epoch_detail", 0.0)))

    def _deliver(self, item) -> bool:
        """Put with stop-polling; on halt the item goes to the spill
        list instead of being dropped (its pre-pull snapshot is the
        rewind point when the consumer checkpoints mid-flight)."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        self._spill.append(item)
        return False

    def _worker(self):
        try:
            while not self._stop.is_set():
                # the window's spans (feed/pull, feed/convert,
                # feed/put) are recorded where the work is, in the
                # helpers this worker shares with the serial feed
                snap = self._snapshot()
                try:
                    window, pending = assemble_window(
                        self._pull, self._n_steps)
                except StopIteration:
                    self._deliver(("stop", None, snap))
                    return
                rec = self._to_device(window, pending)
                if not self._deliver(("window", rec, snap)):
                    return
        except BaseException as e:  # noqa: BLE001 — propagate on next()
            self._deliver(("error", e, None))

    # ------------------------------------------------------------------ #
    # consumer side
    # ------------------------------------------------------------------ #

    def _ensure_worker(self):
        if self._thread is None and not self._finished \
                and self._error is None:
            self._thread = threading.Thread(
                target=self._worker, name="PrefetchIterator-worker",
                daemon=True)
            self._thread.start()

    def _take(self):
        if self._buffer:
            return self._buffer.pop(0)
        while True:
            try:
                return self._q.get(timeout=0.1)
            except queue.Empty:
                if self._thread is None or not self._thread.is_alive():
                    # the worker may have delivered its final item in
                    # the race window between our timeout and its exit —
                    # re-check the queue before declaring it dead
                    try:
                        return self._q.get_nowait()
                    except queue.Empty:
                        pass
                    if self._spill:
                        return self._spill.pop(0)
                    raise RuntimeError(
                        "prefetch worker exited without a result")

    def __iter__(self):
        return self

    def __next__(self) -> DeviceWindow:
        if self._error is not None:
            raise self._error
        if self._finished:
            raise StopIteration
        self._ensure_worker()
        tracer = get_recorder()
        with tracer.span("prefetch/slot_wait", cat="input"):
            kind, rec, _snap = self._take()
        # occupancy AFTER the take: ~depth when device-bound, ~0 when
        # host-bound — the docs/PIPELINE.md diagnostic as a Perfetto
        # counter track
        tracer.counter("prefetch/occupancy", self.buffered)
        if kind == "error":
            self._error = rec
            self._join()
            raise rec
        if kind == "stop":
            self._finished = True
            self._join()
            raise StopIteration
        self.epoch = rec.epoch
        self.is_new_epoch = rec.is_new_epoch
        self._epoch_detail = rec.epoch_detail
        return rec

    next = __next__

    @property
    def epoch_detail(self) -> float:
        """Consumed position (NOT the read-ahead position — the worker
        may have raced several windows past this)."""
        return self._epoch_detail

    @property
    def buffered(self) -> int:
        """Windows currently staged ahead of the consumer.  ~depth when
        the pipeline is device-bound (worker outruns the consumer), ~0
        when host-bound — the cheap live diagnostic for which side to
        optimise (``docs/PIPELINE.md``)."""
        return self._q.qsize() + len(self._buffer)

    @property
    def repeat(self) -> bool:
        return getattr(self._base, "repeat", True)

    # wrapper-owned attribute names: everything assigned in __init__ /
    # consumer bookkeeping.  Anything else reads AND writes through to
    # the base iterator, so the codebase's blessed mutate-then-reset
    # patterns (create_synchronized_iterator's ``it._rng = ...``, the
    # resize-on-resume ``it.dataset = new; it.reset()``) keep working
    # through the wrapper instead of landing on it and silently
    # diverging from the base.
    _OWN_ATTRS = frozenset((
        "_base", "_comm", "_converter", "_n_steps", "depth",
        "_drop_remainder", "_batch_sharding", "_stacked_sharding",
        "_can_rewind", "_q", "_buffer", "_spill", "_stop", "_thread",
        "_error", "_finished", "epoch", "is_new_epoch", "_epoch_detail",
        "join_timeout",
    ))

    def __getattr__(self, name):
        # only fires for names not set on the wrapper — no recursion
        return getattr(self._base, name)

    def __setattr__(self, name, value):
        if name in self._OWN_ATTRS or "_base" not in self.__dict__ \
                or not hasattr(self._base, name):
            object.__setattr__(self, name, value)
        else:
            setattr(self._base, name, value)

    # ------------------------------------------------------------------ #
    # shutdown / halt
    # ------------------------------------------------------------------ #

    def _join(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _halt(self):
        """Stop the worker and collect everything it produced, in order:
        drained queue items first (older), then the spilled in-flight
        item (newer).  Leaves the iterator restartable.  Raises
        RuntimeError after ``join_timeout`` if the worker never stops —
        a base iterator blocked inside ``next()`` can't see the stop
        flag, and hanging the caller (a checkpoint extension, shutdown)
        would be strictly worse than failing loudly."""
        if self._thread is None:
            return
        self._stop.set()
        deadline = time.monotonic() + self.join_timeout
        while self._thread.is_alive():
            try:
                self._buffer.append(self._q.get(timeout=0.05))
            except queue.Empty:
                pass
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"prefetch worker did not stop within "
                    f"{self.join_timeout}s — the base iterator's "
                    f"next() appears to be blocked (streaming source "
                    f"with no data?); raise join_timeout or unblock "
                    f"the source before checkpointing")
        self._thread.join()
        self._thread = None
        while True:
            try:
                self._buffer.append(self._q.get_nowait())
            except queue.Empty:
                break
        self._buffer.extend(self._spill)
        self._spill = []
        self._stop = threading.Event()

    def close(self):
        """Join the worker and drop buffered lookahead.  Idempotent; the
        iterator restarts its worker on the next ``next()`` (after a
        rewindable base is restored, the replay is identical).  A worker
        stuck in a blocked ``next(base)`` is abandoned with a warning
        rather than hanging shutdown — it is a daemon and exits once
        the pull unblocks (the set stop flag is the first thing it
        sees)."""
        try:
            self._halt()
        except RuntimeError as e:
            warnings.warn(f"PrefetchIterator.close: {e}", RuntimeWarning)
            return
        if self._can_rewind and self._buffer:
            # don't strand the lookahead: rewind so a later next() (or a
            # plain consumer of the base iterator) sees the unconsumed
            # batches again
            self._rewind_to(self._oldest_snapshot())
        self._buffer = []

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __del__(self):  # pragma: no cover — belt and braces
        try:
            self._stop.set()
        except Exception:
            pass

    # ------------------------------------------------------------------ #
    # resume protocol
    # ------------------------------------------------------------------ #

    def _oldest_snapshot(self):
        """Base-iterator state as of the oldest UNCONSUMED pull.  An
        error sentinel at the head carries no snapshot (the failed pull
        never completed) — keep the exception sticky instead of losing
        it with the drained buffer, and fall back to the live base
        state (the stream is broken at exactly this point anyway)."""
        for kind, rec, snap in self._buffer:
            if kind == "error":
                self._error = rec
                return self._snapshot()
            return snap
        return self._snapshot()

    def _rewind_to(self, st):
        if st is None:
            return
        # deep-copy arrays: load_state_dict may alias them (SerialIterator
        # keeps the order array and shuffles it in place) and the caller
        # holds this dict as the checkpoint payload
        self._base.load_state_dict({
            k: (v.copy() if isinstance(v, np.ndarray) else v)
            for k, v in st.items()})

    def state_dict(self) -> dict:
        """Drain in-flight slots, rewind the base iterator to the
        consumer's position, and return ITS state — exactly the dict the
        serial path would have produced at this consumption point, so
        a snapshot taken under prefetch restores into either path."""
        if not self._can_rewind:
            # no rewind protocol: the snapshot can't be exact, but the
            # CURRENT run must not lose the already-pulled lookahead —
            # keep it buffered (``_take`` serves the buffer first)
            self._halt()
            return {"non_resumable": True}
        self._halt()
        st = self._oldest_snapshot()
        self._rewind_to(st)          # discard lookahead; worker replays
        self._buffer = []
        self._finished = False       # the replayed stream re-derives it
        return st

    def load_state_dict(self, st: dict) -> None:
        self._halt()
        self._buffer = []
        self._error = None
        self._finished = False
        if st and not st.get("non_resumable") and self._can_rewind:
            self._rewind_to(st)
        self.epoch = getattr(self._base, "epoch", 0)
        self.is_new_epoch = getattr(self._base, "is_new_epoch", False)
        self._epoch_detail = float(
            getattr(self._base, "epoch_detail", 0.0))

    def reset(self):
        self._halt()
        self._buffer = []
        self._error = None
        self._finished = False
        self._base.reset()
        self.epoch = getattr(self._base, "epoch", 0)
        self.is_new_epoch = getattr(self._base, "is_new_epoch", False)
        self._epoch_detail = float(
            getattr(self._base, "epoch_detail", 0.0))
