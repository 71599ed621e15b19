"""Fused bucketed gradient all-reduce, with hierarchical 2-stage lowering.

ChainerMN's single biggest perf lever was ``PureNcclCommunicator``'s
``batched_copy`` path: pack every gradient into one flat arena, all-reduce
the arena in a compressed dtype (``allreduce_grad_dtype``), and split the
reduction over the intra-/inter-node link hierarchy.  The JAX port's
:func:`chainermn_tpu.training.optimizers.cross_replica_mean` historically
issued one ``lax.pmean`` **per pytree leaf** — hundreds of small
collectives per step, each paying full launch latency.  This module is the
TPU-native ``batched_copy``:

- **flatten**: the grad pytree is flattened and grouped by dtype (mixed
  fp32/bf16 trees never share a buffer, so no silent up/down-casts);
- **bucket** (hybrid, the DDP-bucketing shape): leaves of at least
  ``bucket_bytes`` become *direct* buckets — one collective on the leaf
  itself, zero copies (a reshape is free); the small remainder is
  concatenated into a flat arena split at exact ``bucket_bytes``
  boundaries (the last bucket ragged, leaves freely straddling bucket
  edges).  One collective per bucket: latency amortises over the bucket
  while buckets stay small enough for XLA to overlap with neighbouring
  compute, and pack/unpack copies are only ever paid for the small
  leaves that actually need fusing;
- **compress**: with ``wire_dtype`` (bf16 recommended) buckets cross the
  wire compressed and every leaf is re-cast to its original dtype on
  unpack — the reference's fp16 allreduce, casts fused by XLA;
- **hierarchical**: given an ``inter_axis_name`` (the communicator
  reports ``inter_size > 1``), each bucket lowers as
  reduce-scatter(intra) → all-reduce(inter) → all-gather(intra) over the
  2-D mesh instead of one flat all-reduce: the DCN stage moves
  ``1/intra_size`` of the bytes, which is where multi-host bandwidth is
  won (HiCCL, arXiv:2408.05962; arXiv:2508.13397).

Collective-count guarantee: each direct leaf holds at least one full
bucket's bytes and emits exactly one collective, and the arena emits
``ceil(arena_bytes / bucket_bytes)``, so a single-dtype tree emits at
most ``ceil(total_bytes / bucket_bytes)`` collectives — the budget
:func:`chainermn_tpu.utils.comm_model.fused_collective_budget` bounds
and the tests pin on compiled HLO.
``utils/comm_model.choose_bucket_bytes`` picks ``bucket_bytes`` from the
interconnect's latency–bandwidth model.
"""

from __future__ import annotations

from typing import Any, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chainermn_tpu.parallel._compat import (
    all_gather_invariant as _all_gather_invariant,
    axis_size as _axis_size,
    pcast as _pcast,
)

__all__ = [
    "DEFAULT_BUCKET_BYTES",
    "PLAN_STRATEGIES",
    "FusedSpec",
    "flatten_buckets",
    "unflatten_buckets",
    "fused_allreduce",
    "fused_pmean",
    "hierarchical_allreduce",
    "reduce_scatter_allgather",
    "build_overlap_schedule",
    "overlap_exchange",
    "plan_allreduce",
]

# 4 MiB: large enough that per-collective latency is noise against wire
# time, small enough to leave XLA overlap room; choose_bucket_bytes()
# refines this from the interconnect's latency-bandwidth model.
DEFAULT_BUCKET_BYTES = 4 * 1024 * 1024


class FusedSpec(NamedTuple):
    """Static unpack plan produced by :func:`flatten_buckets`.

    Buckets are emitted dtype-group-major, direct before arena within a
    group: for each ``(wire_dtype, direct_members, arena_members,
    n_arena_buckets)`` group entry, ``len(direct_members)`` singleton
    buckets (one whole leaf each) are followed by ``n_arena_buckets``
    arena slices whose concatenation unpacks to ``arena_members`` in
    order.  Members are ``(leaf_index, shape, orig_dtype)``;
    ``treedef`` restores the pytree; ``empties`` are zero-size leaves
    (never packed).
    """

    treedef: Any
    groups: Tuple[Tuple[Any,
                        Tuple[Tuple[int, Tuple[int, ...], Any], ...],
                        Tuple[Tuple[int, Tuple[int, ...], Any], ...],
                        int], ...]
    empties: Tuple[Tuple[int, Tuple[int, ...], Any], ...]
    n_leaves: int


def _bucket_elems(bucket_bytes: int, itemsize: int) -> int:
    if bucket_bytes <= 0:
        raise ValueError(f"bucket_bytes {bucket_bytes} must be positive")
    # CEIL division: a bucket of `per` elements holds >= bucket_bytes,
    # so direct leaves (size >= per) really carry a full bucket's bytes
    # and the arena splits into <= ceil(arena_bytes/bucket_bytes) slices
    # — floor would break the fused_collective_budget guarantee for
    # bucket_bytes that aren't a multiple of itemsize (choose_bucket_bytes
    # returns arbitrary sqrt-derived ints), at the price of buckets
    # overshooting bucket_bytes by at most itemsize-1 bytes.
    return -(-bucket_bytes // itemsize)


def _member(leaves, i):
    return (i, tuple(leaves[i].shape), jnp.dtype(leaves[i].dtype))


def _wire_dtype_for(dtype, wire_dtype):
    """The dtype a leaf actually crosses the wire in — the ONE copy of
    the non-float exemption rule: compression applies to FLOAT leaves
    under a FLOAT wire dtype only (an int32 or bool round-tripped
    through bf16's 8 mantissa bits is silently corrupted, and the
    reduction itself would run in the wrong arithmetic); everything
    else rides its native dtype."""
    dtype = jnp.dtype(dtype)
    if wire_dtype is not None and jnp.issubdtype(dtype, jnp.floating) \
            and jnp.issubdtype(jnp.dtype(wire_dtype), jnp.floating):
        return jnp.dtype(wire_dtype)
    return dtype


def flatten_buckets(
    grads,
    bucket_bytes: int = DEFAULT_BUCKET_BYTES,
    wire_dtype=None,
) -> Tuple[List[jax.Array], FusedSpec]:
    """Flatten a grad pytree into dtype-grouped flat buckets.

    Returns ``(buckets, spec)``: a list of 1-D arrays in the wire dtype
    — whole-leaf *direct* buckets (wire size ≥ ``bucket_bytes``; packed
    copy-free) followed, per dtype group, by arena slices of exactly
    ``bucket_bytes`` (last one ragged) covering the small leaves — plus
    the static :class:`FusedSpec` that :func:`unflatten_buckets` needs
    to invert the packing.  Zero-size leaves ride the spec only.
    """
    leaves, treedef = jax.tree.flatten(grads)
    by_dtype: dict = {}
    empties = []
    for i, leaf in enumerate(leaves):
        if leaf.size == 0:
            empties.append(_member(leaves, i))
            continue
        by_dtype.setdefault(jnp.dtype(leaf.dtype), []).append(i)

    buckets: List[jax.Array] = []
    groups = []
    for dtype, idxs in by_dtype.items():
        wire = _wire_dtype_for(dtype, wire_dtype)
        per = _bucket_elems(bucket_bytes, wire.itemsize)

        def _wire(v):
            return v if v.dtype == wire else v.astype(wire)

        direct = [i for i in idxs if leaves[i].size >= per]
        small = [i for i in idxs if leaves[i].size < per]
        for i in direct:
            buckets.append(_wire(leaves[i].reshape(-1)))
        n_arena = 0
        if small:
            flat = [_wire(leaves[i].reshape(-1)) for i in small]
            vec = flat[0] if len(flat) == 1 else jnp.concatenate(flat)
            n_arena = -(-vec.size // per)
            for b in range(n_arena):
                buckets.append(vec[b * per: (b + 1) * per])
        groups.append((
            wire,
            tuple(_member(leaves, i) for i in direct),
            tuple(_member(leaves, i) for i in small),
            n_arena,
        ))
    return buckets, FusedSpec(treedef, tuple(groups), tuple(empties),
                              len(leaves))


def unflatten_buckets(buckets: Sequence[jax.Array], spec: FusedSpec):
    """Invert :func:`flatten_buckets`: re-split buckets into leaves,
    re-cast each to its original dtype, and rebuild the pytree."""
    out: List[Optional[jax.Array]] = [None] * spec.n_leaves
    pos = 0

    def _restore(flat, i, shape, dtype):
        leaf = flat.reshape(shape)
        out[i] = leaf.astype(dtype) if leaf.dtype != dtype else leaf

    for wire, direct, arena, n_arena in spec.groups:
        for i, shape, dtype in direct:
            _restore(buckets[pos], i, shape, dtype)
            pos += 1
        if n_arena:
            chunk = buckets[pos] if n_arena == 1 else jnp.concatenate(
                list(buckets[pos: pos + n_arena]))
            pos += n_arena
            off = 0
            for i, shape, dtype in arena:
                size = int(np.prod(shape, dtype=np.int64)) if shape else 1
                _restore(chunk[off: off + size], i, shape, dtype)
                off += size
    for i, shape, dtype in spec.empties:
        # zero-size leaves were never packed; restore empties in place
        out[i] = jnp.zeros(shape, dtype)
    return spec.treedef.unflatten(out)


def hierarchical_allreduce(
    x: jax.Array,
    intra_axis_name: str,
    inter_axis_name: str,
    op: str = "mean",
) -> jax.Array:
    """Two-stage all-reduce of one flat bucket over a 2-D mesh:
    reduce-scatter(intra) → all-reduce(inter) → all-gather(intra).

    Wire math (ring formulas, ``s`` bucket bytes, ``k`` intra size,
    ``m`` inter size): the flat all-reduce moves ``2s(km-1)/km`` per
    device with every byte on the *slowest* link; the 2-stage form keeps
    the two ``s(k-1)/k`` halves on the fast intra links and crosses the
    slow inter links with only ``2(s/k)(m-1)/m`` — the inter (DCN)
    traffic shrinks by the intra degree.  The mean's divide runs on the
    1/k-sized shard, before the gather.
    """
    if op not in ("sum", "mean"):
        raise ValueError(f"unsupported hierarchical op {op!r}")
    if x.ndim != 1:
        raise ValueError(f"hierarchical_allreduce wants a flat bucket, "
                         f"got shape {x.shape}")
    if not jnp.issubdtype(x.dtype, jnp.floating):
        # non-float buckets (int/bool — the packer's wire exemption):
        # psum_scatter rejects bool outright, and the shard-side
        # true-divide would round ints through float32.  Route them
        # through the same pmean/psum the per-leaf and fused-flat
        # paths use, so every strategy agrees exactly on non-float data.
        red = lax.pmean if op == "mean" else lax.psum
        return red(x, (intra_axis_name, inter_axis_name))
    k = _axis_size(intra_axis_name)
    size = x.shape[0]
    pad = -size % k
    if pad:
        x = jnp.pad(x, (0, pad))
    shard = lax.psum_scatter(x, intra_axis_name, tiled=True)
    shard = lax.psum(shard, inter_axis_name)
    if op == "mean":
        world = k * _axis_size(inter_axis_name)
        shard = shard / jnp.asarray(world, shard.dtype)
    full = _all_gather_invariant(shard, intra_axis_name, tiled=True)
    return full[:size] if pad else full


def fused_allreduce(
    grads,
    axis_name: str,
    op: str = "mean",
    bucket_bytes: int = DEFAULT_BUCKET_BYTES,
    wire_dtype=None,
    inter_axis_name: Optional[str] = None,
):
    """All-reduce a grad pytree in fused flat buckets — one collective
    per ``bucket_bytes`` of wire traffic instead of one per leaf.

    Args:
      grads: pytree of per-device gradient arrays (inside ``shard_map``).
      axis_name: mesh axis to reduce over — the *intra* axis when
        ``inter_axis_name`` is given.
      op: ``"mean"`` (gradient averaging) or ``"sum"``.
      bucket_bytes: max wire bytes per arena bucket, and the threshold
        above which a leaf rides its own copy-free direct bucket
        (:func:`chainermn_tpu.utils.comm_model.choose_bucket_bytes`
        picks a principled value).
      wire_dtype: compressed wire dtype (e.g. ``jnp.bfloat16``); leaves
        re-cast to their original dtype on unpack.
      inter_axis_name: second mesh axis for the hierarchical 2-stage
        lowering (reduce-scatter intra → all-reduce inter → all-gather
        intra).  ``None`` = flat single-axis all-reduce.

    Emits at most
    :func:`chainermn_tpu.utils.comm_model.fused_collective_budget`
    ``(total_bytes, bucket_bytes, n_dtype_groups)`` collectives — the
    per-leaf baseline emits one per leaf.
    """
    if op not in ("sum", "mean"):
        raise ValueError(f"unsupported fused allreduce op {op!r}")
    buckets, spec = flatten_buckets(grads, bucket_bytes, wire_dtype)
    if not buckets:
        return grads

    if inter_axis_name is not None:
        reduced = [hierarchical_allreduce(b, axis_name, inter_axis_name,
                                          op=op)
                   for b in buckets]
    else:
        red = lax.pmean if op == "mean" else lax.psum
        reduced = [red(b, axis_name) for b in buckets]
    return unflatten_buckets(reduced, spec)


def fused_pmean(grads, axis_name: str, **kwargs):
    """:func:`fused_allreduce` with ``op="mean"`` — the gradient
    hot-path spelling."""
    return fused_allreduce(grads, axis_name, op="mean", **kwargs)


# --------------------------------------------------------------------- #
# plan-driven execution (utils/autotune.py picks the strategy)
# --------------------------------------------------------------------- #

# The exchange-strategy space the measured autotuner searches.  Each
# names ONE lowering of "mean a grad pytree over the axis":
#   per_leaf        — one pmean per leaf (the historical baseline; wins
#                     for tiny trees where packing costs more than it
#                     amortises)
#   fused_flat      — dtype-grouped flat buckets, one all-reduce each
#   hierarchical    — fused buckets, each lowered reduce-scatter(intra)
#                     → all-reduce(inter) → all-gather(intra) over a
#                     2-D mesh (needs ``inter_axis_name``)
#   reduce_scatter  — fused buckets, each lowered reduce-scatter →
#                     all-gather over the ONE axis: same ring bytes as
#                     an all-reduce but two launches per bucket, which
#                     some fabrics/backends schedule better (and the
#                     shard-side divide halves the divide work)
#   overlap         — reverse-leaf-ordered CONTIGUOUS buckets, each
#                     exchanged as soon as the backward pass produces
#                     its gradients (:func:`overlap_exchange`): wire
#                     time hides under the remaining backward compute
#                     instead of running serially after it
PLAN_STRATEGIES = ("per_leaf", "fused_flat", "hierarchical",
                   "reduce_scatter", "overlap")


def _ensure_varying(x, axis_name):
    """Retype ``x`` varying over ``axis_name`` if the vma type system
    considers it invariant: psum_scatter of N identical copies divided
    by N is still the right mean, so both typings reduce correctly."""
    return _pcast(x, axis_name, to="varying")


def reduce_scatter_allgather(
    x: jax.Array,
    axis_name: str,
    op: str = "mean",
) -> jax.Array:
    """Reduce one flat bucket over a SINGLE axis as reduce-scatter →
    all-gather — the two halves of a ring all-reduce issued explicitly.

    Same per-device ring bytes as ``lax.pmean`` (``2s(n-1)/n``), but two
    collective launches per bucket and the mean's divide runs on the
    1/n shard.  Whether this beats the fused all-reduce is a backend
    scheduling question — exactly what the measured autotuner settles.
    """
    if op not in ("sum", "mean"):
        raise ValueError(f"unsupported reduce_scatter op {op!r}")
    if x.ndim != 1:
        raise ValueError(f"reduce_scatter_allgather wants a flat bucket, "
                         f"got shape {x.shape}")
    if not jnp.issubdtype(x.dtype, jnp.floating):
        # non-float buckets: psum_scatter rejects bool, and the
        # shard-side true-divide rounds ints through float32 — use the
        # same pmean/psum as the per-leaf/fused paths (exact agreement)
        red = lax.pmean if op == "mean" else lax.psum
        return red(x, axis_name)
    n = _axis_size(axis_name)
    size = x.shape[0]
    pad = -size % n
    if pad:
        x = jnp.pad(x, (0, pad))
    shard = lax.psum_scatter(_ensure_varying(x, axis_name), axis_name,
                             tiled=True)
    if op == "mean":
        shard = shard / jnp.asarray(n, shard.dtype)
    full = _all_gather_invariant(shard, axis_name, tiled=True)
    return full[:size] if pad else full


# --------------------------------------------------------------------- #
# backward-overlapped exchange (strategy "overlap")
# --------------------------------------------------------------------- #
#
# The window-end lowerings above share one structural property that
# kills compute/comm overlap: the arena concat (and, under accum, the
# microbatch scan) JOINS every gradient leaf, so the first collective
# cannot start until the LAST leaf of the backward pass exists — the
# compiled schedule clusters all exchange collectives after the last
# backward op.  The overlap lowering removes every cross-bucket join:
# leaves are walked in REVERSE flatten order (backward produces the
# last layer's gradients first, so reversed pytree order ≈ production
# order), packed into contiguous runs of ~bucket_bytes, and each
# bucket's reduce-scatter→all-gather (or all-reduce) depends ONLY on
# that bucket's leaves.  The scheduler is then free — and, measured on
# the compiled HLO (``assert_overlap_collectives``), actually does —
# to start bucket k's collective while the backward is still producing
# bucket k+1's gradients.
#
# Bucket-boundary anchors: each bucket's wire vector is threaded
# through ``lax.optimization_barrier`` together with a 1-element token
# of the PREVIOUS bucket's reduced output.  This pins the stream order
# (bucket k's collective cannot be hoisted before bucket k-1's) and,
# critically, stops XLA's collective combiner from re-fusing the
# buckets into one window-end collective — which would silently
# reintroduce the join this lowering exists to remove.


def _normalize_schedule(schedule) -> Tuple[Tuple[int, str, str], ...]:
    """Coerce a schedule carrier (dicts from a JSON plan, tuples, or
    lists) to ``((n_leaves, mode, via), ...)`` and validate it."""
    out = []
    for entry in schedule:
        if isinstance(entry, dict):
            leaves = entry.get("leaves")
            mode = entry.get("mode", "eager")
            via = entry.get("via", "rs")
        else:
            seq = tuple(entry)
            leaves = seq[0]
            mode = seq[1] if len(seq) > 1 else "eager"
            via = seq[2] if len(seq) > 2 else "rs"
        if not isinstance(leaves, int) or leaves < 1:
            raise ValueError(
                f"schedule entry wants a positive leaf count, got "
                f"{leaves!r}")
        if mode not in ("eager", "deferred"):
            raise ValueError(
                f"schedule mode {mode!r} not one of ('eager', "
                f"'deferred')")
        if via not in ("rs", "ar"):
            raise ValueError(
                f"schedule via {via!r} not one of ('rs', 'ar')")
        out.append((leaves, mode, via))
    if not out:
        raise ValueError("empty overlap schedule")
    return tuple(out)


def build_overlap_schedule(
    grads,
    bucket_bytes: int = DEFAULT_BUCKET_BYTES,
    wire_dtype=None,
) -> Tuple[dict, ...]:
    """Derive the default (all-eager) overlap schedule for a grad
    pytree: the REVERSED non-empty-leaf sequence is cut into contiguous
    buckets of at least ``bucket_bytes`` wire bytes (floats count at
    the compressed ``wire_dtype`` itemsize; the last bucket is ragged).

    Returns a tuple of ``{"leaves": k, "mode": "eager", "via": "rs"}``
    dicts — the JSON-stable form a
    :class:`~chainermn_tpu.utils.autotune.Plan` persists — whose leaf
    counts sum to the tree's non-empty leaf count.  Leaf *sizes* (not
    structure) drive the boundaries, so the same helper serves
    ``jax.ShapeDtypeStruct`` trees (the autotuner's candidate builder).
    """
    if bucket_bytes <= 0:
        raise ValueError(f"bucket_bytes {bucket_bytes} must be positive")

    def _size(leaf) -> int:
        return int(np.prod(leaf.shape, dtype=np.int64)) if leaf.shape \
            else 1

    leaves = [l for l in jax.tree.leaves(grads) if _size(l)]
    schedule = []
    run, run_bytes = 0, 0
    for leaf in reversed(leaves):
        run += 1
        run_bytes += _size(leaf) * \
            _wire_dtype_for(leaf.dtype, wire_dtype).itemsize
        if run_bytes >= bucket_bytes:
            schedule.append({"leaves": run, "mode": "eager", "via": "rs"})
            run, run_bytes = 0, 0
    if run:
        schedule.append({"leaves": run, "mode": "eager", "via": "rs"})
    if not schedule:
        # every leaf empty: a 1-bucket schedule keeps callers branch-free
        schedule.append({"leaves": 1, "mode": "eager", "via": "rs"})
    return tuple(schedule)


def overlap_exchange(
    grads,
    axis_name: str,
    op: str = "mean",
    schedule=None,
    bucket_bytes: int = DEFAULT_BUCKET_BYTES,
    wire_dtype=None,
    inter_axis_name: Optional[str] = None,
):
    """Exchange a grad pytree in reverse-leaf-ordered contiguous
    buckets, each emitted as its gradients become available — the
    backward-overlapped lowering (strategy ``"overlap"``).

    Args:
      grads: pytree of per-device gradients (inside ``shard_map``).
        The exchange collectives carry per-bucket dependencies only, so
        a bucket's collective can start while the backward pass is
        still producing the NEXT bucket's gradients — provided the
        caller's program keeps those gradients join-free (the
        ``StandardUpdater`` peels the window-final microbatch out of
        its accumulation scan for exactly this reason).
      axis_name: mesh axis to reduce over.
      op: ``"mean"`` or ``"sum"``.
      schedule: bucket plan over the REVERSED non-empty-leaf sequence —
        ``({"leaves": k, "mode": "eager"|"deferred",
        "via": "rs"|"ar"}, ...)`` (dicts or tuples).  ``eager`` buckets
        stream in reverse-layer order under the backward; ``deferred``
        buckets are held and exchanged after the eager stream (the
        window-end regime, per bucket).  ``via`` picks
        reduce-scatter→all-gather (``rs``, the default — the ZeRO-
        friendly two-launch form) or a single all-reduce (``ar``).
        ``None`` derives the all-eager default from ``bucket_bytes``
        (:func:`build_overlap_schedule`).
      bucket_bytes / wire_dtype: as :func:`fused_allreduce`; the
        non-float wire exemption applies identically (ints and bools
        never cross the wire compressed).
      inter_axis_name: when given, each bucket lowers hierarchically
        over the 2-D mesh (:func:`hierarchical_allreduce`) instead of
        ``via`` — the stream/anchor structure is unchanged.

    Dtype runs: a bucket may span leaves of several dtypes; each
    maximal same-wire-dtype run inside the bucket is packed (and, for
    multi-leaf runs, concatenated) into one flat vector per collective.
    Only ADJACENT leaves ever share a concat, so no bucket waits on
    gradients produced far from its own — the arena packer's global
    concat is exactly the join this lowering exists to avoid.
    """
    if op not in ("sum", "mean"):
        raise ValueError(f"unsupported overlap exchange op {op!r}")
    leaves, treedef = jax.tree.flatten(grads)
    order = [i for i in range(len(leaves) - 1, -1, -1)
             if leaves[i].size != 0]
    if not order:
        return grads
    if schedule is None:
        schedule = build_overlap_schedule(grads, bucket_bytes, wire_dtype)
    sched = _normalize_schedule(schedule)
    n_sched = sum(k for k, _, _ in sched)
    if n_sched != len(order):
        raise ValueError(
            f"overlap schedule covers {n_sched} leaves, grad tree has "
            f"{len(order)} non-empty leaves — the plan was tuned for a "
            f"different payload signature")

    def _wire_of(dtype):
        return _wire_dtype_for(dtype, wire_dtype)

    # cut the reversed leaf order into (bucket, mode, via) groups
    buckets = []
    pos = 0
    for k, mode, via in sched:
        buckets.append((order[pos: pos + k], mode, via))
        pos += k

    out: List[Optional[jax.Array]] = list(leaves)
    red = lax.pmean if op == "mean" else lax.psum
    tok = None

    def _exchange_bucket(idxs, via):
        nonlocal tok
        # maximal same-wire-dtype runs of ADJACENT leaves
        runs = []
        for i in idxs:
            w = _wire_of(leaves[i].dtype)
            if runs and runs[-1][0] == w:
                runs[-1][1].append(i)
            else:
                runs.append((w, [i]))
        for w, run in runs:
            flat = [leaves[i].reshape(-1) for i in run]
            flat = [v if v.dtype == w else v.astype(w) for v in flat]
            vec = flat[0] if len(flat) == 1 else jnp.concatenate(flat)
            if tok is not None:
                # bucket-boundary anchor: pin the stream order and keep
                # the collective combiner from re-joining the buckets
                vec, tok = lax.optimization_barrier((vec, tok))
            if inter_axis_name is not None:
                r = hierarchical_allreduce(vec, axis_name,
                                           inter_axis_name, op=op)
            elif via == "rs":
                r = reduce_scatter_allgather(vec, axis_name, op=op)
            else:
                r = red(vec, axis_name)
            tok = r[:1]
            off = 0
            for i in run:
                size = leaves[i].size
                piece = r[off: off + size].reshape(leaves[i].shape)
                out[i] = piece if piece.dtype == leaves[i].dtype \
                    else piece.astype(leaves[i].dtype)
                off += size

    for idxs, mode, via in buckets:
        if mode == "eager":
            _exchange_bucket(idxs, via)
    for idxs, mode, via in buckets:
        if mode == "deferred":
            _exchange_bucket(idxs, via)
    return treedef.unflatten(out)


def _plan_fields(plan) -> Tuple[str, int, Optional[str]]:
    """Normalise a plan carrier (``utils.autotune.Plan``, a plain dict,
    or anything with the three attributes) to
    ``(strategy, bucket_bytes, wire_dtype_name)``."""
    if isinstance(plan, dict):
        strategy = plan.get("strategy")
        bucket = plan.get("bucket_bytes")
        wire = plan.get("wire_dtype")
    else:
        strategy = getattr(plan, "strategy", None)
        bucket = getattr(plan, "bucket_bytes", None)
        wire = getattr(plan, "wire_dtype", None)
    if strategy not in PLAN_STRATEGIES:
        raise ValueError(
            f"plan strategy {strategy!r} not one of {PLAN_STRATEGIES}")
    return strategy, int(bucket or DEFAULT_BUCKET_BYTES), wire


def _plan_schedule(plan):
    """The plan's overlap ``schedule`` (or None for the derived
    default) — tolerated on any carrier shape ``_plan_fields`` takes."""
    if isinstance(plan, dict):
        return plan.get("schedule")
    return getattr(plan, "schedule", None)


def plan_allreduce(
    grads,
    axis_name: str,
    plan,
    op: str = "mean",
    inter_axis_name: Optional[str] = None,
):
    """Exchange a grad pytree according to a tuned plan — the execution
    half of :mod:`chainermn_tpu.utils.autotune`.

    ``plan`` carries ``(strategy, bucket_bytes, wire_dtype)`` — a
    :class:`~chainermn_tpu.utils.autotune.Plan`, its ``to_dict()`` form,
    or any object with those attributes.  ``strategy`` is one of
    :data:`PLAN_STRATEGIES`; ``hierarchical`` requires
    ``inter_axis_name`` to be bound by the enclosing ``shard_map``
    (plans are keyed by mesh signature, so a hierarchical plan only ever
    reaches a mesh that has the second axis).
    """
    strategy, bucket_bytes, wire_name = _plan_fields(plan)
    wire = jnp.dtype(wire_name) if wire_name else None

    if strategy == "per_leaf":
        red = lax.pmean if op == "mean" else lax.psum

        def one(g):
            if g.size == 0:
                return g
            # same non-float exemption as the fused packer: ints/bools
            # never cross the wire compressed
            if wire is not None and jnp.issubdtype(g.dtype, jnp.floating):
                return red(g.astype(wire), axis_name).astype(g.dtype)
            return red(g, axis_name).astype(g.dtype)

        return jax.tree.map(one, grads)

    if strategy == "fused_flat":
        return fused_allreduce(grads, axis_name, op=op,
                               bucket_bytes=bucket_bytes, wire_dtype=wire)
    if strategy == "hierarchical":
        if inter_axis_name is None:
            raise ValueError(
                "plan strategy 'hierarchical' needs inter_axis_name (a "
                "second mesh axis bound by the enclosing shard_map); "
                "this plan was tuned for a 2-D mesh signature")
        return fused_allreduce(grads, axis_name, op=op,
                               bucket_bytes=bucket_bytes, wire_dtype=wire,
                               inter_axis_name=inter_axis_name)
    if strategy == "overlap":
        return overlap_exchange(grads, axis_name, op=op,
                                schedule=_plan_schedule(plan),
                                bucket_bytes=bucket_bytes,
                                wire_dtype=wire,
                                inter_axis_name=inter_axis_name)

    # reduce_scatter: fused buckets, each lowered rs -> ag over the axis
    buckets, spec = flatten_buckets(grads, bucket_bytes, wire)
    if not buckets:
        return grads
    reduced = [reduce_scatter_allgather(b, axis_name, op=op)
               for b in buckets]
    return unflatten_buckets(reduced, spec)
