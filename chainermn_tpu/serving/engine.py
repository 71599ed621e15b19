"""Slot-based continuous batching engine over the block-paged KV cache.

Every decode mode in ``models.decoding`` serves ONE static batch per
``generate`` call: rows enter together, and the while-loop exits when
the LAST row finishes — a slot whose row hit EOS idles until the whole
batch drains, and a request that arrives mid-call waits for the next
batch.  Under ragged, continuously-arriving traffic (the ROADMAP's
millions-of-users scenario) both wastes are unbounded.  This engine
replaces the batch with SLOTS:

- a request **queue** with a scheduler policy hook (FCFS or
  shortest-prompt-first built in, or any callable);
- **admission**: a freed slot is refilled mid-stream — the new
  request's prompt is prefilled into pool blocks
  (:mod:`~chainermn_tpu.serving.kv_blocks`) and copy-on-admit
  gathered into the slot's contiguous cache lane;
- **per-row eviction**: a slot leaves the moment ITS row is done
  (EOS or token budget), not when the last row is;
- a **ragged decode round** program advancing every live slot up to
  ``round_tokens`` positions off its OWN position clock — the ONE
  compiled program property of the static cache is preserved (the
  cache stays the dense ``_make_cache`` layout and every program
  shape is fixed), but rows are origin-0 (token ``i`` lives at lane
  position ``i``) and carry per-row ``position`` / ``length`` /
  ``end`` vectors instead of sharing a global clock.  No shared
  horizon ever binds (``prompt_len - 1 + max_new <= horizon - 1`` by
  submit validation), so the old block-aligned rebase shift — and its
  prewarm and mid-serve stalls — is gone entirely;
- **chunked prefill inside the round**: admission stages a prompt one
  fixed-shape chunk per scheduler step through the adapter's
  chunk-attends-cache ``verify`` surface while other rows keep
  decoding, so a long co-scheduled prompt no longer moves a short
  prompt's TTFT; and **per-row speculation as a round mode**: with a
  ``draft_adapter`` attached, all-greedy rounds draft ``spec_k``
  tokens per row and verify them in one target pass, committing a
  DIFFERENT number of tokens per row (accepted prefix + one) — the
  ragged clocks are what let acceptance raggedness ride at all.

The engine is MODEL-AGNOSTIC: a decode adapter supplies
``make_cache`` / ``prefill`` / ``step`` (plus ``verify`` for the
chunk-attends-cache paths) and sharding specs (see
:class:`~chainermn_tpu.serving.minilm.MiniLMAdapter` for the protocol
example and :class:`TransformerAdapter` for the flagship).  Decoding
is greedy by default — which is what makes the engine's exactness
guarantee testable: every admitted request's tokens are
token-identical to its solo static decode, independent of what shares
its rounds (pinned in ``tests/serving_tests/test_engine.py``).  That
guarantee survives the production decode tier: PREFIX SHARING
(``prefix_sharing=True``) changes which physical blocks hold the KV,
never its attended content, and per-request KEYED SAMPLING
(``submit(sampling=...)``) moves only the opted-in rows off argmax —
greedy rows stay the pinned oracle while sampled rows pin by
(key, params) replay instead (:mod:`~chainermn_tpu.serving.sampling`).

Single-controller: results are fetched by host indexing into the
sharded token buffer, so every shard must be addressable from this
process (the 8-device CPU mesh and single-host TPU slices; multi-host
serving needs a fetch collective and is future work).

**Overload and failure.**  Requests carry optional ``deadline`` /
``timeout``, ``priority`` and ``tenant``; an attached
:class:`~chainermn_tpu.serving.admission.AdmissionController` bounds
the queue (with priority displacement), enforces per-tenant in-flight
token quotas, and fast-rejects requests whose predicted completion
would breach their deadline — each reject is a typed
:class:`~chainermn_tpu.serving.admission.ShedCompletion`, never an
unbounded queue.  Deadlines are enforced engine-side regardless:
expired queued requests shed ``"timeout"``, expired ACTIVE rows are
evicted mid-stream with their partial tokens and ``status="timeout"``;
:meth:`ServingEngine.cancel` drains a queued copy or frees the slot.
A failure in a per-request program (stage/admit) or in the shared
decode round quarantines the attributable (or newest-admitted)
request and keeps the remaining slots serving — see
docs/SERVING.md "Overload and admission" and docs/RESILIENCE.md.
"""

from __future__ import annotations

import collections
import dataclasses
import os
import re
import time
import uuid
from typing import Callable, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from chainermn_tpu.parallel._compat import pcast
from chainermn_tpu.utils.metrics import get_registry
from chainermn_tpu.utils.programs import (
    get_accountant,
    get_ledger,
    ledger_jit,
    weakref_root,
)
from chainermn_tpu.utils.telemetry import RequestTraceStore, get_recorder

from . import kv_blocks as kvb
from .admission import AdmissionController, ShedCompletion
from .prefix_cache import RefcountedBlockPool
from .sampling import SamplingParams, fold_keys, sample_tokens

__all__ = ["Completion", "Request", "ServingEngine", "TransformerAdapter"]


def _vary(x, *axes):
    """Type ``x`` varying over ``axes`` (those it is not varying on
    yet)."""
    return pcast(x, axes, to="varying")


@dataclasses.dataclass(eq=False)     # identity equality: ndarray fields
class Request:
    """One queued generation request (host-side).

    ``priority`` is a smaller-is-more-important class index (0 is the
    most important); ``deadline`` is an ABSOLUTE ``time.perf_counter``
    timestamp (``submit(timeout=...)`` converts); ``tenant`` names the
    quota bucket the request's ``max_new`` tokens count against.

    ``trace_id`` is the request's causal-trace identity: caller-
    propagated through ``submit(trace_id=...)`` (a front-end carrying
    a distributed-tracing id) or engine-generated when request tracing
    is on; it rides every ``serve/*`` histogram observation as the
    exemplar and names the retained timeline in the engine's
    :class:`~chainermn_tpu.utils.telemetry.RequestTraceStore`.
    ``spans`` is that timeline while the request is live — ``None``
    whenever tracing is off (the disabled path allocates nothing
    per request, pinned by test)."""

    rid: str
    prompt: np.ndarray          # (P,) int32
    max_new: int                # token budget (eos may end the row early)
    t_submit: float = 0.0
    t_admit: Optional[float] = None
    t_first: Optional[float] = None
    priority: int = 0
    tenant: Optional[str] = None
    deadline: Optional[float] = None
    trace_id: Optional[str] = None
    spans: Optional[list] = None
    #: per-request sampling policy (``None`` = greedy, the exactness
    #: oracle; see :mod:`~chainermn_tpu.serving.sampling`)
    sampling: Optional[SamplingParams] = None


@dataclasses.dataclass(eq=False)
class Completion:
    """A finished request: ``tokens`` are the GENERATED tokens only
    (first EOS kept when one was emitted, budget-truncated otherwise —
    the ``make_generate_fn`` convention).  The derived latency fields
    (``queue_wait`` / ``ttft`` / ``tpot`` / ``e2e``) are THE request
    record — ``ServingEngine.request_records()`` hands these back so
    callers (``SLOReport``, ``bench_serving``) stop recomputing them
    from raw timestamps.

    ``status`` is ``"ok"`` for a request served to EOS/budget;
    ``"timeout"`` / ``"cancelled"`` / ``"quarantined"`` rows were
    evicted MID-stream and carry whatever tokens they had generated
    (possibly none).  Such rows may never have produced a first token,
    so ``t_admit``/``t_first`` — and the latencies derived from them —
    can be ``None``; ``SLOReport`` skip-counts those instead of
    poisoning percentiles."""

    rid: str
    prompt: np.ndarray
    tokens: np.ndarray
    t_submit: float
    t_admit: Optional[float]
    t_first: Optional[float]
    t_done: float
    slot: int
    status: str = "ok"
    detail: str = ""
    trace_id: Optional[str] = None

    @property
    def n_generated(self) -> int:
        return int(self.tokens.shape[0])

    @property
    def queue_wait(self) -> Optional[float]:
        """Submit → admission into a decode slot (where static
        batching bleeds)."""
        if self.t_admit is None:
            return None
        return self.t_admit - self.t_submit

    @property
    def ttft(self) -> Optional[float]:
        """Time-to-first-token: submit → first generated token on host."""
        if self.t_first is None:
            return None
        return self.t_first - self.t_submit

    @property
    def tpot(self) -> Optional[float]:
        """Time-per-output-token after the first (the decode steady
        state): ``(t_done - t_first) / (n_generated - 1)``."""
        if self.t_first is None:
            return None
        return (self.t_done - self.t_first) / max(self.n_generated - 1, 1)

    @property
    def e2e(self) -> float:
        """Submit → eviction with every token on host."""
        return self.t_done - self.t_submit


class TransformerAdapter:
    """Decode adapter binding the flagship transformer
    (``models.decoding``) to the serving engine.

    Shards like ``make_generate_fn``: batch over ``data×expert``,
    heads over ``model``, layers+cache over ``pipe``; params via
    ``param_specs``.  MoE configs
    are rejected — router capacity depends on batch composition, which
    would break the engine's token-identity guarantee — and ``seq``
    meshes are rejected like every ``pos_offset`` path.
    """

    batch_axes = ("data", "expert")

    def __init__(self, mesh_cfg, cfg, *, quantized: bool = False):
        from chainermn_tpu.models.decoding import _decode_preamble

        if cfg.training_only:
            raise ValueError(
                "the serving engine does not implement "
                f"{', '.join(cfg.training_only)}: these fields exist on "
                "the training path only (make_train_step); serving needs "
                "a cache per attention kind and a sparse decode step "
                "first")
        if cfg.moe:
            raise ValueError(
                "MoE decode under continuous batching is not supported: "
                "router capacity couples rows, so a request's tokens "
                "would depend on what shares its rounds — the exactness "
                "guarantee the engine is built on")
        if mesh_cfg.mesh.shape.get("seq", 1) != 1:
            raise ValueError(
                "continuous batching drives per-row position origins "
                "(pos_offset), which seq-KV decode does not support: "
                "use a seq=1 mesh (shard batch/heads/layers instead)")
        # validates fsdp-off, pipe divisibility; local sizes for caches
        _, _, self._kv_heads_local, self._layers_local = \
            _decode_preamble(mesh_cfg, cfg, 0)
        self.mesh_cfg = mesh_cfg
        self.cfg = cfg
        self.quantized = quantized

    def param_specs(self):
        from chainermn_tpu.models import param_specs

        return param_specs(self.cfg, quantized=self.quantized)

    def cache_specs(self):
        spec = P("pipe", self.batch_axes, None, "model")
        n = 4 if self.cfg.kv_cache_dtype == "int8" else 2
        return (spec,) * n

    def make_cache(self, rows, kv_len, batch_varying=True):
        from chainermn_tpu.models.decoding import _make_cache

        return _make_cache(self.cfg, rows, kv_len, self._kv_heads_local,
                           self._layers_local,
                           batch_varying=batch_varying)

    def step(self, params, caches, tok, t, pos_offset):
        from chainermn_tpu.models.decoding import _decode_step

        return _decode_step(self.cfg, params, caches, tok, t,
                            pos_offset=pos_offset)

    def prefill(self, params, caches, toks, pos_offset):
        from chainermn_tpu.models.decoding import _decode_step

        _, caches = _decode_step(self.cfg, params, caches, toks, 0,
                                 with_logits=False,
                                 chunk_attends_cache=True,
                                 pos_offset=pos_offset)
        return caches

    def verify(self, params, caches, tok_chunk, t, pos_offset,
               with_logits=True):
        """Chunk step at positions ``[t, t+C)`` attending the cache —
        the speculative verify pass (logits for every chunk position)
        and, without logits, the prefix-sharing suffix prefill.  Rides
        ``_decode_step``'s chunk path, so it carries the same vma
        requirement as every ``TransformerConfig`` program."""
        from chainermn_tpu.models.decoding import _decode_step

        logits, caches = _decode_step(
            self.cfg, params, caches, tok_chunk, t,
            all_logits=with_logits, with_logits=with_logits,
            chunk_attends_cache=True, pos_offset=pos_offset)
        return (logits if with_logits else None), caches

    def step_ragged(self, params, caches, tok, t):
        """Per-row-position decode step (the ragged-round engine
        contract; see ``MiniLMAdapter.step_ragged``): ``tok`` (B,),
        ``t`` (B,) — row ``b``'s token sits at cache position ``t[b]``
        of its origin-0 row (so there is no ``pos_offset``; a vector
        position makes ``_decode_step`` scatter K/V per row).  Returns
        ``(logits (B, V), caches)``."""
        return self.step(params, caches, tok, t, None)

    def verify_ragged(self, params, caches, tok_chunk, t,
                      with_logits=True):
        """Chunk step at per-row start positions: row ``b``'s chunk
        occupies ``[t[b], t[b]+C)`` (ragged speculation)."""
        return self.verify(params, caches, tok_chunk, t, None,
                           with_logits=with_logits)


def _fcfs(queue: Sequence[Request], engine) -> Request:
    return queue[0]


def _spf(queue: Sequence[Request], engine) -> Request:
    """Shortest-prompt-first.  Ties break by SUBMIT ORDER explicitly
    (the queue is submission-ordered), so a seeded trace admits
    identically on every run — pinned by test."""
    return min(enumerate(queue),
               key=lambda t: (t[1].prompt.shape[0], t[0]))[1]


def _deadline(queue: Sequence[Request], engine) -> Request:
    """Deadline-aware: admit the request whose deadline is TIGHTEST
    relative to its predicted remaining service time (least slack
    first), within priority classes (class 0 always outranks class 1).

    Slack is ``(deadline - now) - predictor.predict_remaining(max_new)``
    via the attached admission controller's service-time predictor;
    without a controller (or while the predictor is cold) it degrades
    to earliest-deadline-first.  Deadline-less requests sort after all
    deadlined ones of their class, in submit order.  Every tie breaks
    by submit order — deterministic across runs of one seeded trace
    (pinned by test)."""
    now = time.perf_counter()
    ctrl = getattr(engine, "admission", None)
    pred = ctrl.predictor if ctrl is not None else None

    def key(t):
        i, r = t
        if r.deadline is None:
            return (r.priority, 1, 0.0, i)
        rem = pred.predict_remaining(r.max_new) if pred is not None \
            else None
        slack = (r.deadline - now) - (rem if rem is not None else 0.0)
        return (r.priority, 0, slack, i)

    return min(enumerate(queue), key=key)[1]


def _wfq(queue: Sequence[Request], engine) -> Request:
    """Weighted fair queuing across tenants: the attached admission
    controller's deficit-round-robin pick (tenant weights, quantum
    state) within the most important priority class present.  Requires
    a controller — WFQ without per-tenant state is FCFS wearing a
    costume."""
    ctrl = getattr(engine, "admission", None)
    if ctrl is None:
        raise ValueError(
            "policy 'wfq' needs an AdmissionController attached "
            "(engine.admission) to hold the per-tenant DRR state")
    return ctrl.wfq_pick(queue)


_POLICIES = {"fcfs": _fcfs, "spf": _spf, "deadline": _deadline,
             "wfq": _wfq}


def _trace_store_from_env() -> Optional[RequestTraceStore]:
    """The env-gated default request-trace store (the TraceRecorder /
    MetricsRegistry discipline: off unless ``CHAINERMN_TPU_REQUEST_
    TRACE=1``; a typo'd knob degrades to the default, never crashes)."""
    if os.environ.get("CHAINERMN_TPU_REQUEST_TRACE", "") in ("", "0"):
        return None

    def _num(name, default, conv):
        try:
            return conv(os.environ[name])
        except (KeyError, ValueError, TypeError):
            return default

    cap = max(_num("CHAINERMN_TPU_REQUEST_TRACE_CAPACITY", 256, int), 1)
    rate = min(max(
        _num("CHAINERMN_TPU_REQUEST_TRACE_SAMPLE", 0.05, float), 0.0),
        1.0)
    slo = _num("CHAINERMN_TPU_REQUEST_TRACE_SLO", None, float)
    return RequestTraceStore(capacity=cap, sample_rate=rate,
                             slo_e2e=slo)


class ServingEngine:
    """Continuous-batching scheduler around one decode adapter.

    Args:
      adapter: decode backend (``MiniLMAdapter`` / ``TransformerAdapter``).
      params: model parameters (host or device); placed replicated /
        per ``adapter.param_specs()`` once at construction.
      n_slots: concurrent decode rows; must divide evenly over the
        mesh's batch shards.
      horizon: the dense cache's position capacity.  Rows are
        origin-0 and carry their own position clocks in
        ``[0, horizon)``; submit validation guarantees
        ``prompt_len - 1 + max_new <= horizon - 1``, so no rebase
        machinery exists — a freed slot simply restarts at 0.
      max_prompt: longest admissible prompt; rounded up to a block
        multiple internally (``Pq``) — every prompt stages into
        ``ceil(P/block)`` pool blocks and admission gathers ONE
        fixed-shape ``Pq`` chunk into lane positions ``[0, Pq)``, so
        admission is ONE compiled program, not one per length.
      block: position-block size of the staging pool.
      pool_blocks: staging-pool capacity in blocks (default: one full
        ``Pq`` chunk per slot).  A staged request holds only
        ``ceil(P/block)`` blocks — its real footprint — so a deep
        ragged queue stages many more requests than slots.
      eos_id / pad_id: early-stop token semantics, exactly
        ``make_generate_fn``'s (first EOS kept, frozen rows emit pad).
      round_tokens: decode-round length — positions advanced per
        dispatch; the host observes the per-row done bitmap between
        rounds (larger = less dispatch overhead, more post-EOS waste).
      prefill_chunk: chunked-admission budget in BLOCKS — while other
        rows are decoding, a staging prompt advances at most this many
        prompt blocks per scheduler step through the adapter's
        ``verify`` chunk-attends-cache surface (one fixed-shape
        program for every chunk of every split, so chunked admission
        never retraces).  With NO live rows the whole prompt stages in
        one step regardless (nothing to interleave with).  Default 1
        block; adapters without ``verify`` fall back to the monolithic
        prefill program.
      draft_adapter / draft_params: attach a DRAFT model and turn
        per-row speculative draft/verify into a round MODE: all-greedy
        rounds draft ``spec_k`` tokens per row with the draft model,
        verify them in one target ``verify_ragged`` pass, and commit a
        per-row accepted-prefix-plus-one token count — token-identical
        to greedy decode whatever the draft proposes.  Rounds with a
        SAMPLED row live fall back to per-token rounds (keyed-replay
        sampling and speculative commits do not compose).  The draft
        adapter must share the target's mesh/batch axes.
      spec_k: draft tokens per speculative round (>= 1).
      policy: ``"fcfs"``, ``"spf"``, or ``callable(queue, engine) ->
        Request`` choosing the next admission from the queue.
      gang: static-batching mode — admit only when EVERY slot is free
        (the whole gang drains before the next forms).  This is the
        bench's baseline arm: same programs, same dispatch granularity,
        only the scheduling differs.
      prefill_ahead: stage up to this many queued requests' prompts
        into the pool while slots are still busy (0 disables; default
        ``n_slots``).  Admission of a staged request skips the prefill
        compute — only the copy-on-admit gather remains.
      record_history: how many completed requests
        :meth:`request_records` retains (a bounded ring — a
        long-running server must not grow a completion list without
        bound; completions returned from :meth:`step` are unaffected).
        0 disables retention.
      policy: ``"fcfs"``, ``"spf"``, ``"deadline"`` (least slack vs
        predicted service time, within priority classes), or
        ``callable(queue, engine) -> Request``.
      admission: optional
        :class:`~chainermn_tpu.serving.admission.AdmissionController`
        — queue bound + priority displacement, per-tenant in-flight
        token quotas, predictive deadline shedding.  Host-side only
        and swappable between runs (``engine.admission = ...``, like
        ``gang``); ``None`` admits everything, bounded only by
        deadlines the requests themselves carry.
      epoch: the serving epoch this engine admits for (the elastic
        membership epoch — docs/SERVING.md "Epoch drains").  A submit
        carrying an OLDER epoch is shed ``"stale_epoch"``; during a
        :meth:`drain` every submit is shed ``"draining"`` with a
        ``retry_after`` from the predictor's queue-drain estimate;
        :meth:`complete_drain` re-opens admission under the new epoch.
      traces: a
        :class:`~chainermn_tpu.utils.telemetry.RequestTraceStore` —
        turns ON per-request causal tracing: every request gets a
        ``trace_id`` (caller-propagated or generated), its lifecycle
        spans (``queue_wait``/``admit``/``prefill`` or
        ``chunk_prefill``/sampled ``decode_round``/terminal) are
        assembled into a
        timeline offered to the store at eviction/shed (tail-based
        retention there), and every ``serve/*`` histogram observation
        carries the trace id as its EXEMPLAR — a p99 on the dashboard
        resolves to the offending request's trace.  Default ``None``
        (off; the per-request cost is zero allocations, pinned by
        test) unless ``CHAINERMN_TPU_REQUEST_TRACE=1`` is set, which
        builds a store from ``CHAINERMN_TPU_REQUEST_TRACE_CAPACITY``
        / ``_SAMPLE`` / ``_SLO``.
      trace_decode_every: per-request decode-round span sampling — a
        traced request's FIRST round is always in its timeline (the
        TTFT cause), later rounds every N-th (a 1000-token decode must
        not be a 1000-span trace).
      prefix_sharing: copy-on-write prefix sharing over the staging
        pool (docs/SERVING.md "Prefix sharing"; default ON).  Staged
        blocks are refcounted and content-addressed by token prefix:
        requests sharing a prompt prefix hold ONE physical copy of
        its full blocks and prefill only their divergent suffix, and
        a completed request's full blocks stay cached for the next
        arrival (LRU-reclaimed under pool pressure).  Greedy decode
        stays token-bitwise identical to the private-KV path (pinned);
        ``False`` restores strictly private per-request blocks.
    """

    def __init__(self, adapter, params, *, n_slots: int, horizon: int,
                 max_prompt: int, block: int = 16,
                 pool_blocks: Optional[int] = None, eos_id: int = -1,
                 pad_id: int = 0, round_tokens: int = 4,
                 policy: Union[str, Callable] = "fcfs",
                 gang: bool = False,
                 prefill_ahead: Optional[int] = None,
                 default_max_new: int = 32,
                 record_history: int = 4096,
                 admission: Optional[AdmissionController] = None,
                 epoch: int = 0,
                 traces: Optional[RequestTraceStore] = None,
                 trace_decode_every: int = 4,
                 prefix_sharing: bool = True,
                 prefill_chunk: int = 1,
                 draft_adapter=None, draft_params=None,
                 spec_k: int = 4):
        mesh = adapter.mesh_cfg.mesh
        if not callable(getattr(adapter, "step_ragged", None)):
            raise ValueError(
                f"{type(adapter).__name__} has no step_ragged: the "
                "ragged decode round advances every row at its own "
                "position, which the adapter must implement (see "
                "MiniLMAdapter.step_ragged for the contract)")
        if (draft_adapter is None) != (draft_params is None):
            raise ValueError(
                "draft_adapter and draft_params come together — give "
                "both (speculative round mode) or neither")
        if draft_adapter is not None:
            if spec_k < 1:
                raise ValueError(f"spec_k={spec_k} must be >= 1")
            if draft_adapter.mesh_cfg.mesh is not mesh \
                    or tuple(draft_adapter.batch_axes) \
                    != tuple(adapter.batch_axes):
                raise ValueError(
                    "draft_adapter must share the target adapter's "
                    "mesh and batch axes (its cache rides the same "
                    "slot sharding)")
            if not callable(getattr(adapter, "verify_ragged", None)):
                raise ValueError(
                    f"{type(adapter).__name__} has no verify_ragged: "
                    "per-row speculation verifies each row's draft "
                    "chunk at its own start position")
        shards = 1
        for a in adapter.batch_axes:
            shards *= mesh.shape.get(a, 1)
        if n_slots < 1 or n_slots % shards:
            raise ValueError(
                f"n_slots={n_slots} must be a positive multiple of the "
                f"batch shard count {shards} (mesh axes "
                f"{adapter.batch_axes})")
        if block < 1 or max_prompt < 1:
            raise ValueError(
                f"block={block} and max_prompt={max_prompt} must be >= 1")
        self._pq = kvb.blocks_needed(max_prompt, block) * block
        if horizon < self._pq + 1:
            raise ValueError(
                f"horizon={horizon} must exceed the padded prompt "
                f"chunk {self._pq}")
        self._w = self._pq // block
        if pool_blocks is None:
            pool_blocks = n_slots * self._w
        if pool_blocks < self._w:
            raise ValueError(
                f"pool_blocks={pool_blocks} cannot stage even one "
                f"{self._w}-block prompt chunk")
        if eos_id >= 0 and pad_id < 0:
            raise ValueError(f"pad_id={pad_id} must be >= 0 with eos")
        if round_tokens < 1:
            raise ValueError(f"round_tokens={round_tokens} must be >= 1")
        if prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk={prefill_chunk} must be >= 1 (blocks)")
        self.set_policy(policy)
        self.adapter = adapter
        self.n_slots = n_slots
        self.horizon = horizon
        self.max_prompt = max_prompt
        self.block = block
        self.eos_id = eos_id
        self.pad_id = pad_id
        self.round_tokens = round_tokens
        self.gang = gang
        self.prefill_ahead = n_slots if prefill_ahead is None \
            else prefill_ahead
        self.default_max_new = default_max_new
        self.admission = admission
        self.epoch = int(epoch)
        if traces is None:
            traces = _trace_store_from_env()
        self.traces = traces
        if trace_decode_every < 1:
            raise ValueError(
                f"trace_decode_every={trace_decode_every} must be >= 1")
        self.trace_decode_every = int(trace_decode_every)
        if record_history < 0:
            raise ValueError(
                f"record_history={record_history} must be >= 0")
        self.record_history = record_history
        self._n_local = n_slots // shards
        self._n_shards = shards
        self._mesh = mesh
        self._params = jax.device_put(
            params, jax.tree.map(
                lambda s: NamedSharding(mesh, s), adapter.param_specs(),
                is_leaf=lambda x: isinstance(x, P)))
        self.prefix_sharing = bool(prefix_sharing)
        # chunked (and suffix-resumed) prefill needs the adapter's
        # chunk-attends-cache verify surface; without it staging falls
        # back to one monolithic prefill per prompt (prefix hits still
        # share blocks, they just re-prefill the whole chunk)
        self._can_suffix = hasattr(adapter, "verify")
        self.prefill_chunk = min(int(prefill_chunk), self._w)
        self._chunk_tokens = self.prefill_chunk * block
        self.draft_adapter = draft_adapter
        self.spec_k = int(spec_k)
        if draft_adapter is not None:
            self._draft_params = jax.device_put(
                draft_params, jax.tree.map(
                    lambda s: NamedSharding(mesh, s),
                    draft_adapter.param_specs(),
                    is_leaf=lambda x: isinstance(x, P)))
        self._alloc = RefcountedBlockPool(pool_blocks, block,
                                          share=self.prefix_sharing)
        self._build_programs()
        # reusable host staging for the admit path.  These buffers are
        # REWRITTEN per admission; everything handed to a jitted call
        # is copied first (_staging_copy) — a deferred sharded
        # device_put may alias host memory and block_until_ready does
        # not force the copy (the iterators.prefetch.put_window
        # hazard), so the transfer could still be reading the buffer
        # when the next admission rewrites it.
        self._lprompt_staging = np.zeros((self._pq,), np.int32)
        self._ids_staging = np.zeros((self._w,), np.int32)
        self.reset()

    # ------------------------------------------------------------------ #
    # compiled programs
    # ------------------------------------------------------------------ #

    def _shard_base(self):
        idx = 0
        for a in self.adapter.batch_axes:
            idx = idx * lax.axis_size(a) + lax.axis_index(a)
        return idx * self._n_local

    def _build_programs(self):
        ad = self.adapter
        mesh = self._mesh
        bax = ad.batch_axes
        cspecs = tuple(ad.cache_specs())

        def pool_spec(s):
            t = tuple(s)
            if len(t) <= kvb.ROW_AXIS:
                return P(*t)
            return P(*(t[:kvb.ROW_AXIS] + (None,)
                       + t[kvb.ROW_AXIS + 1:]))

        pool_specs = tuple(pool_spec(s) for s in cspecs)
        row_spec = P(bax)            # (n_slots,) and (n_slots, horizon)
        pspecs = ad.param_specs()
        S, H, R = self._n_local, self.horizon, self.round_tokens
        eos, pad, pq = self.eos_id, self.pad_id, self._pq

        def init_body():
            caches = tuple(_vary(c, *bax)
                           for c in ad.make_cache(S, H))
            buf = _vary(jnp.zeros((S, H), jnp.int32), *bax)
            return caches, buf

        self._init_fn = ledger_jit(jax.shard_map(
            init_body, mesh=mesh, in_specs=(),
            out_specs=(cspecs, row_spec)), label="serve/init")

        def pool_body():
            comps = ad.make_cache(1, pq, batch_varying=False)
            return tuple(
                jnp.zeros((c.shape[0], self._alloc.n_blocks, self.block)
                          + c.shape[3:], c.dtype)
                for c in comps)

        self._pool_init_fn = ledger_jit(jax.shard_map(
            pool_body, mesh=mesh, in_specs=(), out_specs=pool_specs),
            label="serve/pool_init")

        rows = jnp.arange(S)

        def ragged_step(params, caches, buf, pos, done, end, sample):
            """One ragged position per LIVE row: read each row's token
            at its OWN position, step, write the next token at
            ``pos + 1``, advance.  Done (and empty) rows re-step their
            frozen position — the rewrite is value-identical (same
            token, same attended prefix), which is what makes the
            frozen rows free instead of needing a gather/compact."""
            pc = jnp.clip(pos, 0, H - 1)
            tok = jnp.take_along_axis(buf, pc[:, None], axis=1)[:, 0]
            logits, caches = ad.step_ragged(params, caches, tok, pc)
            nxt = sample(logits, pos)
            new_done = done
            if eos >= 0:
                new_done = new_done | (nxt == eos)
            new_done = new_done | ((pos + 1) >= end)
            # live rows never clip (pos + 1 <= end <= H - 1); done
            # rows route their write OUT of bounds instead of onto a
            # clamped live position
            wpos = jnp.where(done, H, jnp.clip(pos + 1, 0, H - 1))
            buf = buf.at[rows, wpos].set(nxt, mode="drop")
            pos = jnp.where(done, pos, pos + 1)
            return caches, buf, pos, new_done

        def round_body(params, caches, buf, pos, done, end):
            def greedy(logits, _pos):
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)

            def one(carry, _):
                carry = ragged_step(params, *carry, end, greedy)
                return carry, None

            (caches, buf, pos, done), _ = lax.scan(
                one, (caches, buf, pos, done), None, length=R)
            return caches, buf, pos, done

        self._round_fn = ledger_jit(
            jax.shard_map(
                round_body, mesh=mesh,
                in_specs=(pspecs, cspecs, row_spec, row_spec, row_spec,
                          row_spec),
                out_specs=(cspecs, row_spec, row_spec, row_spec)),
            label="serve/round", donate_argnums=(1, 2))

        def round_sampled_body(params, caches, buf, pos, done, end,
                               temp, topk, topp, keys):
            # the greedy round plus per-request keyed sampling: rows
            # with temperature 0 take the argmax values the greedy
            # program computes; sampled rows draw with the key folded
            # by their OWN token index — under origin-0 lanes that IS
            # ``pos + 1`` (the new token's row-local index), the same
            # stream the lockstep engine folded as ``t + 1 - offset``,
            # so keyed replay stays bit-identical across the redesign
            def sample(logits, pos):
                step_keys = fold_keys(keys, pos + 1)
                return sample_tokens(logits, step_keys, temp, topk,
                                     topp)

            def one(carry, _):
                carry = ragged_step(params, *carry, end, sample)
                return carry, None

            (caches, buf, pos, done), _ = lax.scan(
                one, (caches, buf, pos, done), None, length=R)
            return caches, buf, pos, done

        self._round_sampled_fn = ledger_jit(
            jax.shard_map(
                round_sampled_body, mesh=mesh,
                in_specs=(pspecs, cspecs, row_spec, row_spec, row_spec,
                          row_spec, row_spec, row_spec, row_spec,
                          row_spec),
                out_specs=(cspecs, row_spec, row_spec, row_spec)),
            label="serve/round_sampled", donate_argnums=(1, 2))

        def admit_body(caches, buf, pools, flat, prompt, slot):
            # position-level gather: the staged prompt is LEFT-aligned
            # in the pool (shareable block identity) and lands
            # LEFT-aligned in its lane too — origin-0 rows, token i at
            # position i, so admission is a straight gather at dst 0
            ls = slot - self._shard_base()
            ok = (ls >= 0) & (ls < S)
            lsc = jnp.clip(ls, 0, S - 1)
            caches = tuple(
                kvb.insert_chunk(c, kvb.gather_positions(pc, flat),
                                 lsc, 0, ok)
                for c, pc in zip(caches, pools))
            cur = lax.dynamic_slice(buf, (lsc, 0), (1, pq))
            row = jnp.where(ok, prompt[None], cur)
            buf = lax.dynamic_update_slice(buf, row, (lsc, 0))
            return caches, buf

        self._admit_fn = ledger_jit(
            jax.shard_map(
                admit_body, mesh=mesh,
                in_specs=(cspecs, row_spec, pool_specs, P(), P(), P()),
                out_specs=(cspecs, row_spec)),
            label="serve/admit", donate_argnums=(0, 1))

        C = self._chunk_tokens
        M = pq + C                  # materialized staging-row width

        def chunk_prefill_body(params, pools, flat, toks, t, ids,
                               valid):
            # ONE fixed-shape program for EVERY prefill chunk: the
            # chunk start ``t`` is a traced scalar, so every chunk of
            # every (prefix, suffix) split — block-aligned or resumed
            # mid-block after a sub-block copy — reuses one compile
            # (the per-split suffix-prefill retrace family this
            # replaces is dead).  Gather the row's staged content
            # ([0, t) real: shared prefix + earlier chunks + any
            # copied partial block), chunk-step ``toks`` at positions
            # [t, t+C) through the verify surface, and scatter back
            # the block-aligned window covering the chunk.
            caches = tuple(kvb.gather_positions(pc, flat)
                           for pc in pools)
            _, caches = ad.verify(params, caches, toks[None], t,
                                  jnp.zeros((1,), jnp.int32),
                                  with_logits=False)
            t0 = (t // self.block) * self.block
            # t <= pq - 1 so t0 + C + block <= pq + C = M: the window
            # slice never clamps (which would misalign it with ids)
            window = tuple(
                lax.dynamic_slice_in_dim(c, t0, C + self.block,
                                         axis=kvb.POS_AXIS)
                for c in caches)
            return tuple(
                kvb.scatter_chunk(pc, kvb.chunk_to_blocks(w, self.block),
                                  ids, valid)
                for pc, w in zip(pools, window))

        if self._can_suffix:
            self._chunk_prefill_fn = ledger_jit(
                jax.shard_map(
                    chunk_prefill_body, mesh=mesh,
                    in_specs=(pspecs, pool_specs, P(), P(), P(), P(),
                              P()),
                    out_specs=pool_specs),
                label="serve/chunk_prefill", donate_argnums=(1,))
        else:
            # no chunk-attends-cache surface: monolithic left-aligned
            # prefill per prompt (the pre-chunking fallback)
            def prefill_body(params, pools, prompt, ids, valid):
                caches = ad.make_cache(1, pq, batch_varying=False)
                caches = ad.prefill(params, caches, prompt[None],
                                    jnp.zeros((1,), jnp.int32))
                return tuple(
                    kvb.scatter_chunk(
                        pc, kvb.chunk_to_blocks(c, self.block), ids,
                        valid)
                    for pc, c in zip(pools, caches))

            self._prefill_fn = ledger_jit(
                jax.shard_map(
                    prefill_body, mesh=mesh,
                    in_specs=(pspecs, pool_specs, P(), P(), P()),
                    out_specs=pool_specs),
                label="serve/prefill", donate_argnums=(1,))

        def fork_body(pools, src, dst):
            # copy-on-write: duplicate one physical block so a row can
            # write privately while other holders keep the original
            # (also the sub-block fork's device copy)
            return tuple(kvb.copy_block(pc, src, dst, jnp.asarray(True))
                         for pc in pools)

        self._fork_fn = ledger_jit(
            jax.shard_map(
                fork_body, mesh=mesh,
                in_specs=(pool_specs, P(), P()), out_specs=pool_specs),
            label="serve/fork", donate_argnums=(0,))

        if self.draft_adapter is not None:
            self._build_spec_programs(mesh, bax, row_spec, pspecs,
                                      cspecs)

    def _build_spec_programs(self, mesh, bax, row_spec, pspecs,
                             cspecs):
        """The speculative round MODE's programs: draft-lane init and
        prefill, plus the draft/verify round itself."""
        ad, d_ad = self.adapter, self.draft_adapter
        S, H, K = self._n_local, self.horizon, self.spec_k
        eos, pq = self.eos_id, self._pq
        d_pspecs = d_ad.param_specs()
        d_cspecs = tuple(d_ad.cache_specs())
        rows = jnp.arange(S)

        def draft_init_body():
            return tuple(_vary(c, *bax) for c in d_ad.make_cache(S, H))

        self._draft_init_fn = ledger_jit(jax.shard_map(
            draft_init_body, mesh=mesh, in_specs=(),
            out_specs=d_cspecs), label="serve/draft_init")

        def draft_prefill_body(d_params, d_caches, prompt, slot):
            # the draft model has no staging pool: its cache is
            # per-slot only, rebuilt by one monolithic prefill of the
            # LEFT-aligned prompt row at each admit
            ls = slot - self._shard_base()
            ok = (ls >= 0) & (ls < S)
            lsc = jnp.clip(ls, 0, S - 1)
            comps = d_ad.make_cache(1, pq, batch_varying=False)
            comps = d_ad.prefill(d_params, comps, prompt[None],
                                 jnp.zeros((1,), jnp.int32))
            return tuple(
                kvb.insert_chunk(c, nc.astype(c.dtype), lsc, 0, ok)
                for c, nc in zip(d_caches, comps))

        self._draft_prefill_fn = ledger_jit(
            jax.shard_map(
                draft_prefill_body, mesh=mesh,
                in_specs=(d_pspecs, d_cspecs, P(), P()),
                out_specs=d_cspecs),
            label="serve/draft_prefill", donate_argnums=(1,))

        def round_spec_body(params, d_params, caches, d_caches, buf,
                            pos, done, end):
            # one speculative round: K ragged draft steps, ONE target
            # verify pass over each row's (K+1)-token chunk at its own
            # start, per-row accepted-prefix commit.  Committed tokens
            # come ONLY from the target's logits, so greedy token
            # identity holds whatever the draft proposes; stale
            # draft/target K/V beyond a row's commit point is
            # rewritten by that position's next step before anything
            # attends it (the same written-before-attended argument
            # the ragged round rests on).
            def draft_one(carry, _):
                d_caches, buf, dpos = carry
                pc = jnp.clip(dpos, 0, H - 1)
                tok = jnp.take_along_axis(buf, pc[:, None],
                                          axis=1)[:, 0]
                logits, d_caches = d_ad.step_ragged(
                    d_params, d_caches, tok, pc)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                wpos = jnp.where(done, H,
                                 jnp.clip(dpos + 1, 0, H - 1))
                buf = buf.at[rows, wpos].set(nxt, mode="drop")
                dpos = jnp.where(done, dpos, dpos + 1)
                return (d_caches, buf, dpos), None

            (d_caches, buf, _), _ = lax.scan(
                draft_one, (d_caches, buf, pos), None, length=K)

            j1 = jnp.arange(K + 1)
            cpos = jnp.clip(pos[:, None] + j1[None, :], 0, H - 1)
            chunk = jnp.take_along_axis(buf, cpos, axis=1)
            logits, caches = ad.verify_ragged(
                params, caches, chunk, jnp.clip(pos, 0, H - 1))
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # accepted = longest drafted prefix the target agrees
            # with; commit that prefix plus the target's one bonus
            # token, clipped to the row's remaining budget
            match = jnp.cumprod(
                (chunk[:, 1:] == g[:, :K]).astype(jnp.int32), axis=1)
            a = jnp.sum(match, axis=1)
            c = jnp.minimum(a + 1, jnp.maximum(end - pos, 1))
            if eos >= 0:
                iseos = g == eos
                first = jnp.where(iseos.any(axis=1),
                                  jnp.argmax(iseos, axis=1), K + 1)
                c = jnp.minimum(c, first + 1)
            # commit: scatter the c target tokens at pos+1..pos+c;
            # uncommitted lanes route out of bounds (a clamped write
            # could collide with a committed one nondeterministically)
            wmask = (~done[:, None]) & (j1[None, :] < c[:, None])
            wpos = jnp.where(wmask, pos[:, None] + 1 + j1[None, :], H)
            buf = buf.at[rows[:, None], wpos].set(g, mode="drop")
            pos2 = jnp.where(done, pos, pos + c)
            new_done = done | (pos2 >= end)
            if eos >= 0:
                hit = jnp.take_along_axis(
                    g, jnp.clip(c - 1, 0, K)[:, None], axis=1)[:, 0] \
                    == eos
                new_done = new_done | ((~done) & hit)
            acc = jnp.where(done, 0, a).astype(jnp.int32)
            com = jnp.where(done, 0, c).astype(jnp.int32)
            return caches, d_caches, buf, pos2, new_done, acc, com

        self._round_spec_fn = ledger_jit(
            jax.shard_map(
                round_spec_body, mesh=mesh,
                in_specs=(pspecs, d_pspecs, cspecs, d_cspecs, row_spec,
                          row_spec, row_spec, row_spec),
                out_specs=(cspecs, d_cspecs, row_spec, row_spec,
                           row_spec, row_spec, row_spec)),
            label="serve/round_spec", donate_argnums=(2, 3, 4))

    # ------------------------------------------------------------------ #
    # lifecycle
    # ------------------------------------------------------------------ #

    def reset(self) -> None:
        """(Re)initialize device and scheduler state, keeping the
        compiled programs — benches reuse one engine across arms."""
        self._caches, self._buf = self._init_fn()
        self._pools = self._pool_init_fn()
        if not self._buf.is_fully_addressable:
            raise RuntimeError(
                "ServingEngine needs every shard addressable from this "
                "process (single-controller serving); multi-host result "
                "fetch is not implemented")
        self._alloc = RefcountedBlockPool(self._alloc.n_blocks,
                                          self.block,
                                          share=self.prefix_sharing)
        self._queue: collections.deque = collections.deque()
        self._staged = {}           # rid -> (flat (Pq,), prompt_row (Pq,))
        self._chunking = {}         # rid -> in-flight chunk-prefill job
        self._slot_req: List[Optional[Request]] = [None] * self.n_slots
        # per-row ragged clocks, origin-0 lanes: token i at position i.
        # _pos = the row's CURRENT position (its token there is the
        # next step's input), _plen = prompt length, _end = the last
        # position the row may reach (_plen - 1 + max_new <= H - 1 by
        # submit validation).  Empty slots: pos 0, done.
        self._pos = np.zeros((self.n_slots,), np.int32)
        self._plen = np.zeros((self.n_slots,), np.int32)
        self._end = np.zeros((self.n_slots,), np.int32)
        self._done = np.ones((self.n_slots,), bool)
        # per-slot sampling state (zeros = greedy row); the sampled
        # round program runs only while a sampled row is live
        self._s_temp = np.zeros((self.n_slots,), np.float32)
        self._s_topk = np.zeros((self.n_slots,), np.int32)
        self._s_topp = np.ones((self.n_slots,), np.float32)
        self._s_keys = np.zeros((self.n_slots, 2), np.uint32)
        self._n_sampled_active = 0
        self._slot_status: List[str] = ["ok"] * self.n_slots
        self._slot_detail: List[str] = [""] * self.n_slots
        if self.draft_adapter is not None:
            self._draft_caches = self._draft_init_fn()
        self._pending_first: set = set()
        self._pending_shed: List[ShedCompletion] = []
        self._tenant_tokens: collections.Counter = collections.Counter()
        self._charged: set = set()      # rids counted in _tenant_tokens
        self._next_rid = 0
        self.admit_log: List[str] = []
        self._records: collections.deque = collections.deque(
            maxlen=self.record_history)
        self.n_rounds = 0
        self._round_capacity = 0        # token-slots offered by rounds
        self.spec_drafted = 0           # draft tokens proposed (spec mode)
        self.spec_accepted = 0          # draft tokens the target accepted
        self.n_chunk_prefills = 0       # prompt chunks staged into rounds
        self.useful_tokens = 0
        self.wasted_tokens = 0          # partial tokens of non-ok rows
        self.prefill_seconds = 0.0      # staging wall time (bench lever)
        self.peak_staged = 0            # concurrently staged rows HWM
        self.n_shed: collections.Counter = collections.Counter()
        self.n_timeouts = 0
        self.n_cancelled = 0
        self.n_quarantined = 0
        self.n_drains = 0
        self._draining = False          # epoch persists across reset()

    # ------------------------------------------------------------------ #
    # public API
    # ------------------------------------------------------------------ #

    def warm(self) -> None:
        """Compile the staging programs ahead of serving: dispatch the
        chunk-prefill program (or the monolithic fallback) once with
        an all-invalid scatter — every block write is dropped, so the
        pool content round-trips unchanged — and, when a draft model
        is attached, the draft-prefill program at an out-of-range
        slot.  The round programs compile on their first natural use;
        staging is the one program whose first compile would otherwise
        land inside a latency-sensitive admit window.  (The rebase
        prewarm this replaces is gone with the rebase program itself:
        ragged rows never share a horizon, so nothing ever shifts.)"""
        row = np.zeros((self._pq,), np.int32)
        if self._can_suffix:
            nw = self._chunk_tokens // self.block + 1
            self._pools = self._chunk_prefill_fn(
                self._params, self._pools,
                np.zeros((self._pq + self._chunk_tokens,), np.int32),
                np.zeros((self._chunk_tokens,), np.int32),
                np.int32(0), np.full((nw,), -1, np.int32),
                np.zeros((nw,), bool))
        else:
            self._pools = self._prefill_fn(
                self._params, self._pools, row,
                np.full((self._w,), -1, np.int32),
                np.zeros((self._w,), bool))
        if self.draft_adapter is not None:
            self._draft_caches = self._draft_prefill_fn(
                self._draft_params, self._draft_caches, row,
                np.int32(-1))

    def mark_steady(self) -> None:
        """Declare this engine's programs steady-state in the program
        ledger: the caller asserts warmup traffic has compiled every
        program it intends to serve with, so any further ``serve/*``
        compile is a retrace-storm signal (``compile/
        steady_retraces``, the ``retrace_storm_rule`` feed).  Call
        after the warmup pass; a deliberate rebuild (resize, engine
        swap) should ``get_ledger().forget("serve/")`` — the rebuilt
        programs are new executables, so their compiles must be
        re-recorded even at previously-seen signatures — then
        re-warm and re-mark.  (Not automatic on construction:
        coexisting engines legitimately share these labels, and a
        second engine's construction must not invalidate the first's
        recorded programs.)  A colocated
        :class:`~chainermn_tpu.serving.SpeculativeDecoder` has its
        own ``mark_steady`` for its ``spec/`` scope — this one covers
        ``serve/`` only."""
        get_ledger().mark_steady("serve/")

    def register_memory(self, accountant=None,
                        prefix: str = "serving") -> None:
        """Register this engine's device-buffer roots with the memory
        accountant: ``<prefix>_params``, ``<prefix>_caches`` (the
        per-slot KV lanes + token buffer), ``<prefix>_pool`` (the
        block-paged staging pool — the prefix cache lives inside it).
        Roots are held via weakref (``programs.weakref_root``), so
        registration never pins a retired engine; a dead root samples
        as 0 bytes."""
        acc = accountant if accountant is not None else get_accountant()
        acc.register(f"{prefix}_params", weakref_root(self, "_params"))
        acc.register(f"{prefix}_caches",
                     weakref_root(self, "_caches", "_buf"))
        acc.register(f"{prefix}_pool", weakref_root(self, "_pools"))

    def set_policy(self, policy: Union[str, Callable]) -> None:
        """Swap the admission policy (host-side only — no recompile)."""
        if callable(policy):
            self._policy = policy
        elif policy in _POLICIES:
            self._policy = _POLICIES[policy]
        else:
            raise ValueError(
                f"policy {policy!r} not in {sorted(_POLICIES)} and not "
                "callable")

    def submit(self, prompt, max_new: Optional[int] = None,
               request_id: Optional[str] = None, *,
               priority: int = 0, tenant: Optional[str] = None,
               deadline: Optional[float] = None,
               timeout: Optional[float] = None,
               epoch: Optional[int] = None,
               trace_id: Optional[str] = None,
               sampling: Optional[SamplingParams] = None
               ) -> Union[str, ShedCompletion]:
        """Queue one request; returns its id — or, when the attached
        admission controller rejects it (queue full, tenant over
        quota, deadline predicted unmeetable), the reason-coded
        :class:`ShedCompletion` instead of letting it age in the
        queue.  The reject is also appended to
        :meth:`request_records` and counted in ``serve/shed_*``.

        ``deadline`` is an absolute ``time.perf_counter`` timestamp;
        ``timeout`` is the relative convenience form (seconds from
        now) — give at most one.  ``priority`` is
        smaller-is-more-important (class 0 beats class 1).

        ``epoch`` (optional) is the serving epoch the CALLER believes
        is current: a mismatch with :attr:`epoch` is shed
        ``"stale_epoch"`` — a front-end that slept through a resize
        must re-learn the world, not have its request served under
        assumptions that moved.  While :meth:`drain` is in progress
        every submit is shed ``"draining"`` with the predicted
        ``retry_after``.

        ``trace_id`` propagates a caller-side causal-trace identity
        (a distributed-tracing id from the front-end); with request
        tracing enabled (``traces=``) one is generated when absent.
        It becomes the exemplar on every ``serve/*`` histogram
        observation this request feeds and names its retained
        timeline in ``engine.traces``.

        ``sampling`` (a
        :class:`~chainermn_tpu.serving.sampling.SamplingParams`)
        switches THIS request to keyed temperature/top-k/top-p
        sampling; ``None`` keeps the greedy path — the exactness
        oracle — even when sampled requests share its rounds.  A
        sampled request replays bit-identically from its
        ``(seed, params, prompt)`` under any scheduling."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if not 1 <= prompt.shape[0] <= self.max_prompt:
            raise ValueError(
                f"prompt length {prompt.shape[0]} not in "
                f"[1, {self.max_prompt}]")
        max_new = self.default_max_new if max_new is None else int(max_new)
        if not 1 <= max_new <= self.horizon - self._pq:
            raise ValueError(
                f"max_new={max_new} not in [1, horizon - padded prompt "
                f"= {self.horizon - self._pq}]")
        now = time.perf_counter()
        if timeout is not None:
            if deadline is not None:
                raise ValueError("give deadline= OR timeout=, not both")
            if timeout <= 0:
                raise ValueError(f"timeout={timeout} must be > 0")
            deadline = now + timeout
        if request_id is None:
            request_id = f"r{self._next_rid}"
            self._next_rid += 1
        if any(r.rid == request_id for r in self._queue) \
                or any(r is not None and r.rid == request_id
                       for r in self._slot_req):
            raise ValueError(f"request id {request_id!r} already live")
        if sampling is not None and not isinstance(sampling,
                                                   SamplingParams):
            raise ValueError(
                f"sampling= takes a SamplingParams, got "
                f"{type(sampling).__name__}")
        req = Request(request_id, prompt, max_new, t_submit=now,
                      priority=int(priority), tenant=tenant,
                      deadline=deadline, sampling=sampling)
        if self.traces is not None:
            req.trace_id = (str(trace_id) if trace_id is not None
                            else uuid.uuid4().hex[:16])
            req.spans = []
        elif trace_id is not None:
            # no retention, but the identity still rides the records
            # and exemplars — a front-end's trace id is never dropped
            req.trace_id = str(trace_id)
        reg = get_registry()
        # serve/submitted counts the SCORED request stream — it is
        # the burn-rate rules' total feed, so protective "overload"
        # sheds (excluded from serve/shed_total below for the same
        # reason) must not dilute it either: counting them as
        # zero-bad traffic would drive the bad fraction down and
        # self-extinguish the alert mid-burst (protection flapping at
        # the short-window period).  It is incremented on every path
        # out of this method EXCEPT the overload shed.
        if self._draining:
            reg.inc("serve/submitted")
            # checked FIRST: during the handover window a front-end
            # that already learned the NEW epoch is early, not wrong —
            # it gets the transient "draining" + retry_after, never the
            # terminal re-learn-the-world verdict below
            return self._finish_shed(req, "draining",
                                     retry_after=self._retry_after())
        if epoch is not None and int(epoch) != self.epoch:
            reg.inc("serve/submitted")
            if int(epoch) < self.epoch:
                return self._finish_shed(
                    req, "stale_epoch",
                    detail=f"submit epoch {int(epoch)} vs engine epoch "
                           f"{self.epoch}")
            # a NEWER epoch: the ENGINE is the stale party (its
            # complete_drain hasn't run yet) — transient, retry
            return self._finish_shed(
                req, "draining", retry_after=self._retry_after(),
                detail=f"engine epoch {self.epoch} behind submit epoch "
                       f"{int(epoch)}")
        if self.admission is not None:
            admit, reason, victim = self.admission.check_submit(
                req, list(self._queue), self._tenant_tokens,
                n_slots=self.n_slots,
                ahead_tokens=self._ahead_tokens(req))
            if victim is not None:
                # a lower-priority queued request makes room; its shed
                # record flows out of the next step()
                self._shed_from_queue(victim, "queue_full",
                                      detail=f"displaced by {req.rid}")
            if not admit:
                # transient rejects carry a come-back hint, each from
                # its own clock: queue_full drains with the backlog
                # (predictor estimate), over_quota with the TENANT's
                # own in-flight drain (how long until enough of its
                # budget retires for this request to fit), and an
                # "overload" protective shed resolves with the
                # burn-rate alert's window (the operator-configured
                # hint — the backlog estimate would read ~0 off an
                # empty queue and invite a retry storm
                # mid-protection).  Only deadline is a terminal
                # verdict with no clock at all.
                if reason == "queue_full":
                    after = self._retry_after()
                elif reason == "over_quota":
                    after = self._quota_retry_after(req)
                elif reason == "overload":
                    after = self.admission.overload_retry_after
                else:
                    after = None
                if reason != "overload":
                    reg.inc("serve/submitted")
                return self._finish_shed(req, reason,
                                         retry_after=after)
        reg.inc("serve/submitted")
        self._queue.append(req)
        self._tenant_tokens[tenant] += max_new
        self._charged.add(request_id)
        get_recorder().counter("serve/queue_depth", len(self._queue),
                               cat="serve")
        reg.set("serve/queue_depth", len(self._queue))
        return request_id

    def cancel(self, request_id: str) -> bool:
        """Cancel a live request: a queued copy is drained (staged
        blocks freed, a ``ShedCompletion(reason="cancelled")`` flows
        out of the next :meth:`step`); an ACTIVE row is evicted on the
        next step with its partial tokens and
        ``status="cancelled"`` — the slot frees immediately after.
        Returns False when the id is not live (already completed,
        shed, or never submitted) — cancellation races are normal, not
        errors."""
        for req in list(self._queue):
            if req.rid == request_id:
                self._shed_from_queue(req, "cancelled")
                return True
        for s in range(self.n_slots):
            req = self._slot_req[s]
            if req is not None and req.rid == request_id:
                if self._done[s]:
                    # already finished (or already timed out /
                    # quarantined), just awaiting eviction — too late
                    # to cancel; don't relabel a served completion
                    return False
                self._done[s] = True
                self._slot_status[s] = "cancelled"
                return True
        return False

    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self._slot_req)

    @property
    def idle(self) -> bool:
        return (not self._queue and self.n_active == 0
                and not self._pending_shed)

    def step(self) -> List[Union[Completion, ShedCompletion]]:
        """One scheduler iteration: evict finished/expired rows, admit
        from the queue (shedding what can no longer make its
        deadline), run one decode round.  Returns this iteration's
        terminal records — served :class:`Completion`\\ s (``status``
        ``"ok"`` or a mid-stream ``"timeout"`` / ``"cancelled"`` /
        ``"quarantined"``) and queue-side :class:`ShedCompletion`\\ s.

        A decode-round failure does NOT crash the engine: the
        newest-admitted live request is quarantined (evicted next
        step with ``status="quarantined"``) and the remaining slots
        keep serving — unless the failure consumed the round's donated
        buffers, in which case the device state is gone and a
        ``RuntimeError`` propagates."""
        rec = get_recorder()
        out: List[Union[Completion, ShedCompletion]] = []
        self._evict_phase(out, rec)
        self._admit_phase(rec)
        if self._pending_shed:          # queue sheds from this tick
            out.extend(self._pending_shed)
            self._pending_shed.clear()
        n_live = sum(1 for s in range(self.n_slots)
                     if self._slot_req[s] is not None
                     and not self._done[s])
        if n_live:
            rt0 = time.perf_counter()
            spec = (self.draft_adapter is not None
                    and not self._n_sampled_active)
            cap = (self.spec_k + 1) if spec else self.round_tokens
            try:
                with rec.span("serve/decode_round", cat="serve",
                              step=int(self.n_rounds),
                              tokens=cap, active=self.n_active):
                    if self._n_sampled_active:
                        # keyed-sampling round; greedy rows inside it
                        # still take the argmax values.  The sampling
                        # arrays are rewritten per admission, so the
                        # jitted call gets copies (the staging-buffer
                        # aliasing discipline)
                        self._caches, self._buf, pos_dev, done_dev = \
                            self._round_sampled_fn(
                                self._params, self._caches, self._buf,
                                self._staging_copy(self._pos),
                                self._staging_copy(self._done),
                                self._staging_copy(self._end),
                                self._staging_copy(self._s_temp),
                                self._staging_copy(self._s_topk),
                                self._staging_copy(self._s_topp),
                                self._staging_copy(self._s_keys))
                    elif spec:
                        # speculative round MODE: per-row draft/verify
                        # with ragged accepted-token counts.  Sampled
                        # rows force the per-token fallback above —
                        # spec acceptance is defined against the
                        # target's argmax
                        (self._caches, self._draft_caches, self._buf,
                         pos_dev, done_dev, acc_dev, com_dev) = \
                            self._round_spec_fn(
                                self._params, self._draft_params,
                                self._caches, self._draft_caches,
                                self._buf,
                                self._staging_copy(self._pos),
                                self._staging_copy(self._done),
                                self._staging_copy(self._end))
                        drafted = self.spec_k * n_live
                        accepted = int(np.sum(np.array(acc_dev)))
                        self.spec_drafted += drafted
                        self.spec_accepted += accepted
                        reg0 = get_registry()
                        reg0.inc("serve/spec_drafted", drafted)
                        reg0.inc("serve/spec_accepted", accepted)
                    else:
                        # all-greedy per-token rounds
                        self._caches, self._buf, pos_dev, done_dev = \
                            self._round_fn(
                                self._params, self._caches, self._buf,
                                self._staging_copy(self._pos),
                                self._staging_copy(self._done),
                                self._staging_copy(self._end))
                    # np.array, not asarray: the host mirrors are
                    # mutated by admissions, and jax arrays view out
                    # read-only
                    self._pos = np.array(pos_dev)
                    self._done = np.array(done_dev)  # the round's sync
            except Exception as err:        # noqa: BLE001 — harden
                self._on_round_failure(err, rec)
            else:
                self.n_rounds += 1
                self._round_capacity += cap * self.n_slots
                now = time.perf_counter()
                if self.traces is not None:
                    # per-round spans are SAMPLED into request
                    # timelines (every Nth round), except a request's
                    # first round — the TTFT cause is always on its
                    # trace
                    sampled = (self.n_rounds
                               % self.trace_decode_every == 0)
                    for s in range(self.n_slots):
                        r = self._slot_req[s]
                        if r is None or r.spans is None:
                            continue
                        if sampled or s in self._pending_first:
                            self._rspan(r, "decode_round", rt0,
                                        now - rt0,
                                        round=self.n_rounds,
                                        tokens=cap)
                reg = get_registry()
                for s in self._pending_first:
                    req = self._slot_req[s]
                    req.t_first = now
                    # TTFT lands here — the first moment the request's
                    # first generated token is host-observable
                    reg.observe("serve/ttft", now - req.t_submit,
                                exemplar=req.trace_id)
                    if self.admission is not None:
                        self.admission.predictor.observe_ttft(
                            now - req.t_submit)
                        if req.t_admit is not None:
                            # queue-free service TTFT: admit -> first
                            # token, the predictor's service-side
                            # evidence (wait is predicted separately)
                            self.admission.predictor \
                                .observe_service_ttft(now - req.t_admit)
                self._pending_first.clear()
        rec.counter("serve/active_slots", self.n_active, cat="serve")
        return out

    def _on_round_failure(self, err, rec) -> None:
        """Quarantine-and-continue: the shared decode round cannot
        attribute a failure to one row, so the NEWEST-admitted live
        request (the thing that most recently changed the batch) is
        evicted ``status="quarantined"`` and the round retries next
        step with the remaining rows.  A persistent fault therefore
        drains the batch one quarantine per step — degraded, never
        hung.  If the failure consumed the round's donated buffers the
        device state is unrecoverable and the error propagates."""
        state = (self._caches, self._buf)
        if self.draft_adapter is not None:
            state = state + (self._draft_caches,)
        for leaf in jax.tree.leaves(state):
            if getattr(leaf, "is_deleted", lambda: False)():
                raise RuntimeError(
                    "decode round failed after its donated buffers "
                    "were consumed — engine state is lost; reset() "
                    "and resubmit") from err
        live = [s for s in range(self.n_slots)
                if self._slot_req[s] is not None and not self._done[s]]
        victim = max(live,
                     key=lambda s: (self._slot_req[s].t_admit or 0.0, s))
        self._done[victim] = True
        self._slot_status[victim] = "quarantined"
        self._slot_detail[victim] = f"{type(err).__name__}: {err}"
        rec.counter("serve/round_failures", 1, cat="serve")
        get_registry().inc("serve/round_failures")

    def run(self, max_steps: Optional[int] = None) -> List[Completion]:
        """Drive :meth:`step` until queue and slots drain."""
        out: List[Completion] = []
        steps = 0
        while not self.idle:
            out.extend(self.step())
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return out

    # ------------------------------------------------------------------ #
    # epoch drains (docs/SERVING.md "Epoch drains")
    # ------------------------------------------------------------------ #

    @property
    def draining(self) -> bool:
        return self._draining

    def drain(self, *, timeout: Optional[float] = None,
              max_steps: Optional[int] = None
              ) -> List[Union[Completion, ShedCompletion]]:
        """Retire every ACTIVE row ahead of an epoch change (a live
        resize, a rolling restart) without restarting the fleet:

        - admission STOPS — queued requests hold their place, every new
          submit is shed ``"draining"`` with the predictor's
          ``retry_after`` estimate;
        - active rows finishing naturally complete ``"ok"``; with
          ``timeout`` the rest are timeout-evicted at the deadline with
          their partial tokens (a verified PREFIX of the solo decode —
          the engine's ordinary mid-stream eviction);
        - decode rounds keep running until the slots are empty, then
          this returns the terminal records produced along the way.

        The engine stays in drain mode afterwards;
        :meth:`complete_drain` re-opens admission under the new epoch
        (typically after ``ResizeController`` re-formed the world).
        ``max_steps`` bounds the loop for drills."""
        if timeout is not None and timeout <= 0:
            raise ValueError(f"timeout={timeout} must be > 0")
        self._draining = True
        self.n_drains += 1
        get_registry().inc("serve/drains")
        if timeout is not None:
            dl = time.perf_counter() + timeout
            for s in range(self.n_slots):
                req = self._slot_req[s]
                if req is not None and not self._done[s]:
                    req.deadline = dl if req.deadline is None \
                        else min(req.deadline, dl)
        out: List[Union[Completion, ShedCompletion]] = []
        steps = 0
        with get_recorder().span("serve/drain", cat="serve",
                                 active=self.n_active,
                                 queued=len(self._queue)):
            while self.n_active:
                out.extend(self.step())
                steps += 1
                if max_steps is not None and steps >= max_steps:
                    break
        return out

    def complete_drain(self, epoch: Optional[int] = None) -> None:
        """Re-open admission after a :meth:`drain`, optionally bumping
        to the NEW serving epoch (the agreed membership epoch).  Queued
        requests kept their place and admit normally from the next
        :meth:`step`; epochs only move forward."""
        if epoch is not None:
            if int(epoch) < self.epoch:
                raise ValueError(
                    f"epoch={epoch} would move backwards (engine is at "
                    f"{self.epoch}) — epochs only advance")
            self.epoch = int(epoch)
        self._draining = False

    def export_queue(self) -> List[Request]:
        """Remove and return every QUEUED request (submit order,
        timestamps intact) — the carry-over half of surviving a resize:
        drain the old engine, export its queue, and
        :meth:`import_queue` into the engine rebuilt for the new world
        so waiting requests keep their place instead of being shed.
        Staged pool blocks are freed (the new engine re-prefills
        against its own pool)."""
        reqs = list(self._queue)
        for r in reqs:
            self._staged.pop(r.rid, None)
            self._chunking.pop(r.rid, None)
            self._alloc.free_row(r.rid)
            self._release_tokens(r)
        self._queue.clear()
        get_recorder().counter("serve/queue_depth", 0, cat="serve")
        get_registry().set("serve/queue_depth", 0)
        return reqs

    def import_queue(self, reqs: Sequence[Request]) -> None:
        """Adopt requests exported from another engine (see
        :meth:`export_queue`); submit order and ``t_submit`` are
        preserved so queue-wait metrics stay honest across the
        handover.

        All-or-nothing: every rid is validated against this engine's
        live set BEFORE anything is adopted, so a collision raises
        with the queue untouched — a failover caller can fall back to
        per-request re-dispatch without first unwinding a partial
        import."""
        live = {q.rid for q in self._queue}
        live.update(a.rid for a in self._slot_req if a is not None)
        for r in reqs:
            if r.rid in live:
                raise ValueError(f"request id {r.rid!r} already live")
            live.add(r.rid)
        for r in reqs:
            self._queue.append(r)
            self._tenant_tokens[r.tenant] += r.max_new
            self._charged.add(r.rid)
            # auto-assigned rids ("r<n>") from the old engine share this
            # engine's namespace: advance the counter past them, or the
            # n-th native submit regenerates an imported rid and raises
            # "already live" at an ordinary caller
            m = re.fullmatch(r"r(\d+)", r.rid)
            if m:
                self._next_rid = max(self._next_rid,
                                     int(m.group(1)) + 1)
        get_recorder().counter("serve/queue_depth", len(self._queue),
                               cat="serve")
        get_registry().set("serve/queue_depth", len(self._queue))

    def import_prefixes(self, prefixes: Sequence[np.ndarray]) -> int:
        """Warm the prefix cache with token prefixes exported from
        another engine (see
        :func:`~chainermn_tpu.serving.prefix_cache.prefix_snapshot`) —
        the rejoin half of a fleet failover: a restarted replica
        re-prefills the snapshot's prefixes ONCE (as ordinary 1-token
        requests, paying compute but no retrace) so subsequent traffic
        hits its cache and the router's prefix-placement signal
        survives the restart.  Must be called idle; returns the number
        of newly cached blocks.

        Prefixes that don't fit (shorter than one full block after
        clipping to ``max_prompt``) or are already cached are
        skipped — importing is best-effort by design."""
        if not self.idle:
            raise ValueError("import_prefixes needs an idle engine")
        before = self._alloc.n_cached
        warmed = 0
        for i, p in enumerate(prefixes):
            p = np.asarray(p, np.int32).reshape(-1)
            end = min(int(p.shape[0]), self.max_prompt - 1)
            end = (end // self.block) * self.block
            if end < self.block:
                continue
            p = p[:end]
            if len(self._alloc._trie.lookup_run(p)) * self.block \
                    >= end:
                continue
            res = self.submit(p, max_new=1,
                              request_id=f"__warm{i}__")
            if isinstance(res, ShedCompletion):
                continue
            warmed += 1
        if warmed:
            self.run()
        return self._alloc.n_cached - before

    def stats(self) -> dict:
        issued = self._round_capacity
        out = {
            "rounds": self.n_rounds,
            "spec_drafted": self.spec_drafted,
            "spec_accepted": self.spec_accepted,
            "chunk_prefills": self.n_chunk_prefills,
            "useful_tokens": self.useful_tokens,
            "wasted_tokens": self.wasted_tokens,
            "slot_utilization": (self.useful_tokens / issued
                                 if issued else 0.0),
            "pool_utilization": self._alloc.utilization,
            "queue_depth": len(self._queue),
            "shed": dict(self.n_shed),
            "timeouts": self.n_timeouts,
            "cancelled": self.n_cancelled,
            "quarantined": self.n_quarantined,
            "epoch": self.epoch,
            "draining": self._draining,
            "drains": self.n_drains,
            "prefill_seconds": self.prefill_seconds,
            "peak_staged": self.peak_staged,
        }
        out.update(self._alloc.stats())    # prefix_* / peak_blocks_used
        return out

    def request_records(self) -> List[Completion]:
        """The newest completed requests (up to ``record_history``,
        oldest dropped; cleared by :meth:`reset`), in eviction order —
        the :class:`Completion` the engine already built at eviction,
        with the derived ``queue_wait`` / ``ttft`` / ``tpot`` /
        ``e2e`` latency fields, so SLO consumers (``SLOReport``,
        ``bench_serving``) never recompute them."""
        return list(self._records)

    def metrics_snapshot(self) -> dict:
        """The ``serve/*`` slice of the global metrics registry —
        per-request queue-wait/TTFT/TPOT/e2e histograms plus
        submit/admit/evict counters recorded at the points that
        hold the timestamps.  Empty when the registry is disabled
        (``CHAINERMN_TPU_METRICS=1`` or
        ``utils.metrics.get_registry().enable()`` turn it on);
        :meth:`request_records` is the always-on per-request form."""
        return get_registry().snapshot(prefix="serve/")

    # ------------------------------------------------------------------ #
    # request-scoped tracing (docs/OBSERVABILITY.md "Request tracing")
    # ------------------------------------------------------------------ #

    def _rspan(self, req: Request, name: str, t0: float, dur: float,
               **meta) -> None:
        """Append one span to a TRACED request's timeline.  Untraced
        requests (``spans is None`` — tracing off) fall through the
        first check with zero allocations."""
        if req.spans is None:
            return
        span = {"name": name, "t0": t0, "dur": dur}
        if meta:
            span.update(meta)
        req.spans.append(span)

    def _offer_trace(self, req: Request, comp) -> None:
        """Hand a finished request's timeline to the trace store —
        tail-based retention there decides whether it survives
        (non-ok and SLO-violating always, ok sampled)."""
        if req.spans is None or self.traces is None:
            return
        trace = {
            "trace_id": req.trace_id,
            "rid": req.rid,
            "status": comp.status,
            "queue_wait": getattr(comp, "queue_wait", None),
            "ttft": getattr(comp, "ttft", None),
            "e2e": getattr(comp, "e2e", None),
            "n_generated": comp.n_generated,
            "spans": req.spans,
        }
        reason = getattr(comp, "reason", None)
        if reason is not None:
            trace["reason"] = reason
        self.traces.offer(trace)

    # ------------------------------------------------------------------ #
    # phases
    # ------------------------------------------------------------------ #

    def _evict_phase(self, out: List[Completion], rec) -> None:
        now = time.perf_counter()
        for s in range(self.n_slots):
            req = self._slot_req[s]
            if req is None:
                continue
            if (not self._done[s] and req.deadline is not None
                    and now >= req.deadline):
                # deadline expired MID-stream: evict with the partial
                # tokens rather than burn more rounds on a miss
                self._done[s] = True
                self._slot_status[s] = "timeout"
            if not self._done[s]:
                continue
            status = self._slot_status[s]
            detail = self._slot_detail[s]
            et0 = time.perf_counter()
            with rec.span("serve/evict", cat="serve", rid=req.rid,
                          slot=s, status=status):
                row = np.asarray(self._buf[s])
                # origin-0 lane: generated tokens live at positions
                # [plen, pos]; a mid-stream eviction (timeout/cancel/
                # quarantine) has only decoded up to the row's OWN
                # position, which is all the clock there is
                gen = row[int(self._plen[s]): int(self._pos[s]) + 1]
                if self.eos_id >= 0:
                    hits = np.nonzero(gen == self.eos_id)[0]
                    if hits.size:
                        gen = gen[:int(hits[0]) + 1]
                self._slot_req[s] = None
                self._pos[s] = 0
                self._plen[s] = 0
                self._end[s] = 0
                if req.sampling is not None:
                    self._s_temp[s] = 0.0
                    self._s_topk[s] = 0
                    self._s_topp[s] = 1.0
                    self._s_keys[s] = 0
                    self._n_sampled_active -= 1
                self._slot_status[s] = "ok"
                self._slot_detail[s] = ""
                self._pending_first.discard(s)
                if status == "ok":
                    self.useful_tokens += int(gen.shape[0])
                else:
                    self.wasted_tokens += int(gen.shape[0])
            comp = Completion(
                rid=req.rid, prompt=req.prompt, tokens=np.array(gen),
                t_submit=req.t_submit, t_admit=req.t_admit,
                t_first=req.t_first, t_done=time.perf_counter(),
                slot=s, status=status, detail=detail,
                trace_id=req.trace_id)
            self._release_tokens(req)
            self._records.append(comp)
            if req.spans is not None:
                if status != "ok":
                    # the terminal cause gets its own mark on the
                    # timeline (the span a "why did this time out"
                    # reader looks for first)
                    self._rspan(req, status, comp.t_done, 0.0,
                                **({"detail": detail} if detail
                                   else {}))
                self._rspan(req, "evict", et0, comp.t_done - et0,
                            slot=s, status=status,
                            tokens=comp.n_generated)
                self._offer_trace(req, comp)
            reg = get_registry()
            reg.inc("serve/evictions")
            reg.inc("serve/generated_tokens", comp.n_generated)
            if status == "ok":
                # only fully-served rows feed the latency
                # distributions — a truncated timeout row would bias
                # the predictor (and the dashboard) optimistic
                reg.observe("serve/tpot", comp.tpot,
                            exemplar=req.trace_id)
                reg.observe("serve/e2e", comp.e2e,
                            exemplar=req.trace_id)
                if self.admission is not None:
                    self.admission.predictor.observe_tpot(comp.tpot)
            elif status == "timeout":
                self.n_timeouts += 1
                reg.inc("serve/timeouts")
            elif status == "cancelled":
                self.n_cancelled += 1
                reg.inc("serve/cancelled")
            elif status == "quarantined":
                self.n_quarantined += 1
                reg.inc("serve/quarantined")
            out.append(comp)

    def _release_tokens(self, req: Request) -> None:
        if req.rid in self._charged:
            self._charged.discard(req.rid)
            self._tenant_tokens[req.tenant] -= req.max_new
            if self._tenant_tokens[req.tenant] <= 0:
                del self._tenant_tokens[req.tenant]

    def _backlog_tokens(self) -> int:
        """The live token backlog a capacity shed quotes: queued
        budgets plus active rows' remaining budgets."""
        backlog = sum(r.max_new for r in self._queue)
        for s in range(self.n_slots):
            if self._slot_req[s] is not None and not self._done[s]:
                backlog += max(int(self._end[s]) - int(self._pos[s]),
                               0)
        return backlog

    def _ahead_tokens(self, req: Request) -> Optional[int]:
        """Queued token budget the ADMISSION POLICY would serve before
        ``req`` — the deadline feasibility check's honest wait basis.

        The controller's predictor used to charge every arrival the
        WHOLE queue's drain; under any policy that can serve the new
        request early (deadline slack, short prompt, priority) that
        over-states its wait and sheds feasible requests — observed as
        ``--max-queue 0`` traffic shedding "deadline" off a backlog it
        would never stand behind.  This conditions the wait on the
        request's predicted queue POSITION: sum only requests the
        policy ranks ahead of it.  FCFS keeps the whole queue
        (position = tail); a custom callable policy returns ``None``
        (unknown ordering — fall back to the conservative whole-queue
        charge)."""
        if self._policy is _fcfs:
            return sum(int(r.max_new) for r in self._queue)
        if self._policy is _spf:
            plen = int(req.prompt.shape[0])
            return sum(int(r.max_new) for r in self._queue
                       if int(r.prompt.shape[0]) <= plen)
        if self._policy is _deadline:
            now = time.perf_counter()
            ctrl = self.admission
            pred = ctrl.predictor if ctrl is not None else None

            def key(i, r):
                if r.deadline is None:
                    return (r.priority, 1, 0.0, i)
                rem = pred.predict_remaining(r.max_new) \
                    if pred is not None else None
                slack = (r.deadline - now) \
                    - (rem if rem is not None else 0.0)
                return (r.priority, 0, slack, i)

            mine = key(len(self._queue), req)
            return sum(int(r.max_new)
                       for i, r in enumerate(self._queue)
                       if key(i, r) < mine)
        if self._policy is _wfq:
            return sum(int(r.max_new) for r in self._queue
                       if int(r.priority) <= int(req.priority))
        return None

    def _retry_after(self) -> Optional[float]:
        """Predicted seconds until the current backlog drains (the
        retry-after a capacity shed carries); ``None`` without an
        admission controller or while its predictor is cold."""
        if self.admission is None:
            return None
        return self.admission.retry_after(self._backlog_tokens(),
                                          self.n_slots)

    def _quota_retry_after(self, req: Request) -> Optional[float]:
        """The quota shed's come-back hint: predicted seconds until
        enough of the TENANT's in-flight budget drains for this
        request to fit under its quota.  The drain rate is the pool's
        aggregate (``n_slots / TPOT``) — an upper bound on how fast
        the tenant's own rows can retire, so the hint errs early, not
        late.  ``None`` while the predictor is cold."""
        if self.admission is None:
            return None
        quota = self.admission.quota_for(req.tenant)
        if quota is None:
            return None
        over = self._tenant_tokens[req.tenant] + req.max_new - quota
        if over <= 0:
            return None
        return self.admission.retry_after(int(over), self.n_slots)

    def _finish_shed(self, req: Request, reason: str,
                     detail: str = "",
                     retry_after: Optional[float] = None
                     ) -> ShedCompletion:
        """Terminal bookkeeping for a request that will never be
        served: tenant tokens released, record appended, metrics
        counted.  Returns the typed reject."""
        self._release_tokens(req)
        shed = ShedCompletion(
            rid=req.rid, prompt=req.prompt, reason=reason,
            t_submit=req.t_submit, t_shed=time.perf_counter(),
            max_new=req.max_new, priority=req.priority,
            tenant=req.tenant, detail=detail, retry_after=retry_after,
            trace_id=req.trace_id)
        if req.spans is not None:
            self._rspan(req, "queue_wait", req.t_submit,
                        shed.t_shed - req.t_submit)
            self._rspan(req, "shed", shed.t_shed, 0.0, reason=reason,
                        **({"detail": detail} if detail else {}))
            self._offer_trace(req, shed)
        self._records.append(shed)
        self.n_shed[reason] += 1
        reg = get_registry()
        # the taxonomy is DISJOINT: queue-side terminations count in
        # serve/shed_<reason> only; serve/timeouts / serve/cancelled /
        # serve/quarantined count mid-stream evictions only — their
        # sum with serve/shed_total is every unserved request once.
        # Protective "overload" sheds are EXCLUDED from shed_total:
        # that counter is the burn-rate rules' documented bad feed,
        # and counting the alert's own deliberate sheds into it would
        # make the alert self-sustaining (below-tier traffic keeps
        # arriving → keeps being shed → keeps burning the budget),
        # never auto-resolving after the real cause stops
        if reason != "overload":
            reg.inc("serve/shed_total")
        reg.inc("serve/shed_" + reason)
        return shed

    def _shed_from_queue(self, req: Request, reason: str,
                         detail: str = "") -> ShedCompletion:
        self._queue.remove(req)
        self._staged.pop(req.rid, None)
        self._chunking.pop(req.rid, None)
        self._alloc.free_row(req.rid)
        shed = self._finish_shed(
            req, reason, detail,
            retry_after=(self._retry_after()
                         if reason == "queue_full" else None))
        self._pending_shed.append(shed)
        get_recorder().counter("serve/queue_depth", len(self._queue),
                               cat="serve")
        get_registry().set("serve/queue_depth", len(self._queue))
        return shed

    def _pick(self) -> Request:
        req = self._policy(list(self._queue), self)
        if req not in self._queue:
            raise ValueError(
                f"policy returned a request not in the queue: {req!r}")
        return req

    def _scan_queue_deadlines(self) -> None:
        """Shed queued requests that expired (``"timeout"``) or — with
        an admission controller — can no longer meet their deadline
        per the live prediction (``"deadline"``), instead of letting
        them age in the queue."""
        if not self._queue:
            return
        now = time.perf_counter()
        for req in list(self._queue):
            reason = None
            if req.deadline is not None and now >= req.deadline:
                reason = "timeout"
            elif self.admission is not None:
                reason = self.admission.check_queued(req, now)
            if reason is not None:
                self._shed_from_queue(req, reason)

    def _admit_phase(self, rec) -> None:
        self._scan_queue_deadlines()
        if self._draining:
            # drain mode: no admissions, no speculative prefill — the
            # queue holds (deadlines above still enforced) until
            # complete_drain() re-opens under the new epoch
            return
        # idle is judged ONCE, at phase start: rows admitted later in
        # this same phase have not decoded yet, so synchronous staging
        # while idle delays nothing — and keeps gang batches forming
        # whole and cold-start admission in strict policy order
        idle = not any(self._slot_req[s] is not None
                       and not self._done[s]
                       for s in range(self.n_slots))
        # advance in-flight chunked stagings FIRST, in queue order:
        # one chunk each per round while decode rows are live (the
        # long prompt pays its own staging across rounds), straight
        # to completion when the device would otherwise sit idle
        self._advance_chunks(rec, all_chunks=idle)
        free = [s for s in range(self.n_slots)
                if self._slot_req[s] is None]
        if self.gang and len(free) < self.n_slots:
            free = []                   # static batching: whole gang only
        skip: set = set()
        while free and self._queue:
            cands = [r for r in self._queue if r.rid not in skip]
            if not cands:
                break
            req = self._policy(cands, self)
            if req not in self._queue:
                raise ValueError(
                    "policy returned a request not in the queue: "
                    f"{req!r}")
            try:
                staged = self._ensure_staged(req, rec, idle=idle)
            except Exception as err:    # noqa: BLE001 — harden
                # prefill failed for THIS request: quarantine it and
                # keep admitting others — one poison prompt must not
                # stall the queue (_shed_from_queue frees its blocks)
                self._check_state_alive(err)
                self._shed_from_queue(
                    req, "quarantined",
                    detail=f"stage: {type(err).__name__}: {err}")
                continue
            if staged == "pool_full":
                break                   # pool full until slots drain
            if staged == "chunking":
                # mid-chunking: later-queued requests must not wait
                # behind its remaining chunks (TTFT independence) —
                # skip it and keep admitting
                skip.add(req.rid)
                continue
            slot = free.pop(0)
            self._queue.remove(req)
            at0 = time.perf_counter()
            try:
                with rec.span("serve/admit", cat="serve", rid=req.rid,
                              slot=slot):
                    flat, prompt_row = self._staged.pop(req.rid)
                    self._caches, self._buf = self._admit_fn(
                        self._caches, self._buf, self._pools, flat,
                        prompt_row, np.int32(slot))
                    if self.draft_adapter is not None:
                        # rebuild the slot's draft lane from the
                        # left-aligned prompt row (the draft model has
                        # no staging pool)
                        self._draft_caches = self._draft_prefill_fn(
                            self._draft_params, self._draft_caches,
                            prompt_row, np.int32(slot))
                    # refcount-aware: the row lets go, but blocks the
                    # trie (or other rows) hold stay resident — that
                    # retention IS the prefix cache
                    self._alloc.free_row(req.rid)
            except Exception as err:    # noqa: BLE001 — harden
                self._check_state_alive(err)
                self._alloc.free_row(req.rid)
                self._pending_shed.append(self._finish_shed(
                    req, "quarantined",
                    detail=f"admit: {type(err).__name__}: {err}"))
                free.insert(0, slot)    # the slot was never filled
                continue
            p = int(req.prompt.shape[0])
            self._pos[slot] = p - 1
            self._plen[slot] = p
            # p - 1 + max_new <= Pq - 1 + max_new <= H - 1 by submit
            # validation: a row's end never needs a shared horizon
            self._end[slot] = p - 1 + req.max_new
            self._done[slot] = False
            self._slot_req[slot] = req
            if req.sampling is not None:
                sp = req.sampling
                self._s_temp[slot] = sp.temperature
                self._s_topk[slot] = sp.top_k
                self._s_topp[slot] = sp.top_p
                self._s_keys[slot] = np.asarray(sp.key())
                self._n_sampled_active += 1
            self._pending_first.add(slot)
            req.t_admit = time.perf_counter()
            if self.admission is not None:
                # settle the WFQ pick's token cost only now that the
                # admission actually LANDED (a failed stage leaves the
                # request queued and must not be charged twice)
                self.admission.wfq_charge(req)
            self.admit_log.append(req.rid)
            if req.spans is not None:
                self._rspan(req, "queue_wait", req.t_submit,
                            req.t_admit - req.t_submit)
                self._rspan(req, "admit", at0, req.t_admit - at0,
                            slot=slot)
            rec.counter("serve/queue_depth", len(self._queue),
                        cat="serve")
            reg = get_registry()
            reg.inc("serve/admits")
            reg.observe("serve/queue_wait", req.t_admit - req.t_submit,
                        exemplar=req.trace_id)
            reg.set("serve/queue_depth", len(self._queue))
        if self.prefill_ahead:
            budget = self.prefill_ahead
            for req in list(self._queue):
                if budget <= 0:
                    break
                if req.rid in self._staged \
                        or req.rid in self._chunking:
                    continue
                try:
                    if self._stage_traced(req, rec, steal=False,
                                          idle=idle) == "pool_full":
                        break
                except Exception as err:    # noqa: BLE001 — harden
                    self._check_state_alive(err)
                    self._shed_from_queue(
                        req, "quarantined",
                        detail=f"stage: {type(err).__name__}: {err}")
                    continue
                budget -= 1

    def _check_state_alive(self, err) -> None:
        """Donated-buffer guard for the harden paths: if a failed
        program call consumed its donated inputs, the device state is
        unrecoverable — propagate instead of serving garbage."""
        for leaf in jax.tree.leaves(
                (self._caches, self._buf, self._pools)):
            if getattr(leaf, "is_deleted", lambda: False)():
                raise RuntimeError(
                    "serving program failed after its donated buffers "
                    "were consumed — engine state is lost; reset() "
                    "and resubmit") from err

    # ------------------------------------------------------------------ #
    # staging / paging
    # ------------------------------------------------------------------ #

    def _staging_copy(self, buf: np.ndarray) -> np.ndarray:
        """The one copy the admit path owes: staging buffers are
        rewritten per admission, and a deferred sharded ``device_put``
        may alias host memory without ``block_until_ready`` forcing the
        copy (see ``iterators.prefetch.put_window``)."""
        return np.array(buf)

    def _stage(self, req: Request, rec, steal: bool,
               idle: bool = True) -> str:
        """Begin (and possibly finish) staging ``req``'s prompt into
        pool blocks.  With prefix sharing the cached leading full
        blocks are REFERENCED, a mid-block divergence forks the
        matching sub-block prefix onto a fresh block with a device
        copy (``copy_block`` — no recompute), and only tokens from the
        divergence point on are prefilled.  Prefill runs in
        fixed-shape CHUNKS of ``prefill_chunk`` blocks through the
        adapter's verify surface: with live decode rows the remaining
        chunks interleave one per round (``_advance_chunks``) so a
        long prompt never stalls co-scheduled requests; with the
        device otherwise idle every chunk runs now.  ``steal`` frees
        queue-tail stagings to make room (admission path only;
        prefill-ahead never steals).  Staging is LEFT-aligned — token
        ``i`` in block ``i // block`` — which is both what makes block
        content addressable by token prefix AND the lane layout
        origin-0 rows decode from: admission is a straight gather.

        Returns ``"ready"`` (staged, admission can gather),
        ``"chunking"`` (chunks still in flight), or ``"pool_full"``."""
        P_len = int(req.prompt.shape[0])
        n_real = kvb.blocks_needed(P_len, self.block)
        plan = self._alloc.stage(req.rid, req.prompt)
        while plan is None and steal:
            victims = [r for r in reversed(list(self._queue))
                       if (r.rid in self._staged
                           or r.rid in self._chunking)
                       and r is not req]
            if not victims:
                return "pool_full"
            victim = victims[0]
            self._alloc.free_row(victim.rid)
            self._staged.pop(victim.rid, None)
            self._chunking.pop(victim.rid, None)
            plan = self._alloc.stage(req.rid, req.prompt)
        if plan is None:
            return "pool_full"
        reg = get_registry()
        pt0 = time.perf_counter()
        with rec.span("serve/prefill", cat="serve", rid=req.rid,
                      blocks=plan.n_new, shared=plan.n_shared):
            st = self._lprompt_staging
            st[:] = max(self.pad_id, 0)
            st[:P_len] = req.prompt
            prompt_row = self._staging_copy(st)
            if plan.copy_src is not None:
                # sub-block fork-with-copy: the row diverges MID-block
                # from a cached child — device-copy the whole cached
                # block onto this row's first fresh block and resume
                # prefill at the divergence point, instead of
                # recomputing the matched sub-block prefix
                ft0 = time.perf_counter()
                with rec.span("serve/fork", cat="serve", rid=req.rid,
                              src=int(plan.copy_src),
                              copied=plan.n_copied):
                    self._pools = self._fork_fn(
                        self._pools, np.int32(plan.copy_src),
                        np.int32(plan.table[plan.n_shared]))
                # the transient ref stage() took on the source block
                # (so the steal loop above could not reclaim it before
                # the copy) is released only now
                self._alloc.copy_done(plan.copy_src)
                reg.inc("serve/prefix_forks")
                self._rspan(req, "fork", ft0,
                            time.perf_counter() - ft0,
                            copied=plan.n_copied)
            if plan.n_new and not self._can_suffix:
                # no chunk-attends-cache surface: monolithic prefill
                # of the whole left-aligned row, scatter only this
                # row's fresh blocks (never a shared one)
                ids_np = self._ids_staging
                ids_np[:] = -1
                ids_np[plan.n_shared:n_real] = \
                    plan.table[plan.n_shared:]
                ids_row = self._staging_copy(ids_np)
                self._pools = self._prefill_fn(
                    self._params, self._pools, prompt_row,
                    ids_row, ids_row >= 0)
            elif plan.n_new:
                start = plan.n_shared * self.block + plan.n_copied
                if start < P_len:
                    job = self._build_chunk_job(req, plan, P_len,
                                                n_real, start,
                                                prompt_row)
                    self._chunking[req.rid] = job
            # plan.n_new == 0: the whole prompt is cached full blocks —
            # no prefill compute at all, admission is just the gather
        dur = time.perf_counter() - pt0
        self.prefill_seconds += dur
        if plan.n_shared or plan.n_copied:
            reg.inc("serve/prefix_hits", plan.n_shared)
            reg.set("serve/prefix_blocks_shared",
                    self._alloc.n_shared_blocks)
        self._rspan(req, "prefill", pt0, dur, blocks=plan.n_new,
                    shared=plan.n_shared)
        if req.rid in self._chunking:
            # a fresh job runs its first chunk NOW (it owes this
            # round's chunk budget), and every remaining chunk too
            # when the device was idle at phase start — a solo submit
            # still stages fully, and therefore admits and decodes,
            # in its first step
            self._run_job(self._chunking[req.rid], rec,
                          all_chunks=idle)
            if req.rid in self._chunking:
                return "chunking"
            return "ready"
        self._finalize_stage(req, P_len, prompt_row)
        return "ready"

    def _build_chunk_job(self, req: Request, plan, P_len: int,
                         n_real: int, start: int,
                         prompt_row: np.ndarray) -> dict:
        """Precompute one prompt's chunk-prefill schedule: the (M,)
        flat gather index over its staged blocks, and per chunk the
        start position, padded token slice, and scatter ids for the
        ``C + block``-wide window the fixed-shape program writes back.
        Because the chunk width is a block multiple, every chunk of a
        job keeps the same sub-block offset — one compile serves every
        chunk of every (prefix, suffix) split."""
        C, blk = self._chunk_tokens, self.block
        fm = np.full((self._pq + C,), -1, np.int32)
        intra = np.arange(blk, dtype=np.int32)
        for j in range(n_real):
            w = min(blk, P_len - j * blk)
            fm[j * blk:j * blk + w] = plan.table[j] * blk + intra[:w]
        nw = C // blk + 1
        starts, toks, ids = [], [], []
        t = start
        while t < P_len:
            starts.append(t)
            tk = np.full((C,), max(self.pad_id, 0), np.int32)
            w = min(C, P_len - t)
            tk[:w] = req.prompt[t:t + w]
            toks.append(tk)
            idr = np.full((nw,), -1, np.int32)
            wb0 = t // blk
            for j in range(nw):
                wb = wb0 + j
                if wb < plan.n_shared or wb >= n_real:
                    continue            # shared or beyond the prompt
                if wb * blk >= t + C:
                    continue            # unwritten trailing window
                idr[j] = plan.table[wb]
            ids.append(idr)
            t += C
        return {"req": req, "fm": fm, "starts": starts, "toks": toks,
                "ids": ids, "next": 0, "p_len": P_len,
                "prompt_row": prompt_row}

    def _run_job(self, job: dict, rec, all_chunks: bool) -> None:
        """Dispatch the job's next chunk (or every remaining chunk)
        through the fixed-shape chunk-prefill program; finalize the
        staging when the last chunk lands.  Compiles caused by this
        request carry its trace id as the ledger exemplar."""
        req = job["req"]
        n = len(job["starts"]) - job["next"] if all_chunks else 1
        led = get_ledger()
        prev = led.exemplar
        led.exemplar = req.trace_id
        pt0 = time.perf_counter()
        try:
            for _ in range(n):
                k = job["next"]
                t = job["starts"][k]
                idr = job["ids"][k]
                with rec.span("serve/chunk_prefill", cat="serve",
                              rid=req.rid, start=int(t), chunk=k,
                              of=len(job["starts"])):
                    self._pools = self._chunk_prefill_fn(
                        self._params, self._pools,
                        self._staging_copy(job["fm"]),
                        self._staging_copy(job["toks"][k]),
                        np.int32(t), self._staging_copy(idr),
                        idr >= 0)
                job["next"] += 1
        finally:
            led.exemplar = prev
        dur = time.perf_counter() - pt0
        self.prefill_seconds += dur
        self._rspan(req, "chunk_prefill", pt0, dur, chunks=n)
        self.n_chunk_prefills += n
        get_registry().inc("serve/chunk_prefills", n)
        if job["next"] == len(job["starts"]):
            self._chunking.pop(req.rid, None)
            self._finalize_stage(req, job["p_len"],
                                 job["prompt_row"])

    def _advance_chunks(self, rec, all_chunks: bool) -> None:
        """Advance every in-flight chunk job (queue order).  A failed
        chunk quarantines ITS request only; the others keep going."""
        if not self._chunking:
            return
        for rid in [r.rid for r in self._queue
                    if r.rid in self._chunking]:
            job = self._chunking[rid]
            try:
                self._run_job(job, rec, all_chunks)
            except Exception as err:    # noqa: BLE001 — harden
                self._check_state_alive(err)
                self._shed_from_queue(
                    job["req"], "quarantined",
                    detail=f"stage: {type(err).__name__}: {err}")

    def _finalize_stage(self, req: Request, P_len: int,
                        prompt_row: np.ndarray) -> None:
        """The staged row is complete: publish it to the prefix cache
        and record the admission gather index."""
        if self.prefix_sharing:
            self._alloc.insert_cached(req.rid, req.prompt)
        flat = self._alloc.flat_gather_index(req.rid, self._pq, P_len,
                                             align="left")
        self._staged[req.rid] = (flat, prompt_row)
        self.peak_staged = max(self.peak_staged, len(self._staged))

    def _stage_traced(self, req: Request, rec, steal: bool,
                      idle: bool = True) -> str:
        """:meth:`_stage` with the request's trace id as the program
        ledger's exemplar: a compile caused by THIS request (the
        ``serve/chunk_prefill`` program's one compile, on whichever
        request reaches it first cold) links its ``compile/seconds``
        exemplar straight to the request's retained timeline — the
        same trace-id hop the latency exemplars ride."""
        led = get_ledger()
        prev = led.exemplar
        led.exemplar = req.trace_id
        try:
            return self._stage(req, rec, steal=steal, idle=idle)
        finally:
            led.exemplar = prev

    def _ensure_staged(self, req: Request, rec,
                       idle: bool = True) -> str:
        if req.rid in self._staged:
            return "ready"
        if req.rid in self._chunking:
            return "chunking"
        return self._stage_traced(req, rec, steal=True, idle=idle)

    def fork_block(self, row_id, idx: int) -> int:
        """Copy-on-write fork of a STAGED row's ``idx``-th block: if
        the block has other holders (the trie, another row) the row
        gets a fresh physical copy — device content duplicated, table
        and staged gather index repointed — and the shared original is
        never written.  Already-private blocks are left alone.
        Returns the block id the row holds afterwards.  This is the
        write-path guard primitive; the steady-state staging plan
        forks implicitly (divergent suffixes always land on fresh
        blocks), so the engine itself only needs this when a caller
        mutates staged content in place."""
        src = self._alloc.table(row_id)[idx]
        new = self._alloc.fork_for_write(row_id, idx)
        if new is None:
            return src
        self._pools = self._fork_fn(self._pools, np.int32(src),
                                    np.int32(new))
        if row_id in self._staged:
            req = next((r for r in self._queue if r.rid == row_id),
                       None)
            if req is not None:
                flat = self._alloc.flat_gather_index(
                    row_id, self._pq, req.prompt.shape[0],
                    align="left")
                self._staged[row_id] = (flat, self._staged[row_id][1])
        if row_id in self._chunking:
            # an in-flight chunk job gathers through its own flat map:
            # repoint the forked block's positions there too
            job = self._chunking[row_id]
            blk = self.block
            w = min(blk, job["p_len"] - idx * blk)
            job["fm"][idx * blk:idx * blk + w] = \
                new * blk + np.arange(w, dtype=np.int32)
            for k in range(len(job["ids"])):
                m = job["ids"][k] == src
                job["ids"][k][m] = new
        get_registry().inc("serve/prefix_forks")
        return new
