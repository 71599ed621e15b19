"""Multi-node optimizer — analogue of ``chainermn.create_multi_node_optimizer``
and ``_DoubleBufferingOptimizer`` (reference: ``chainermn/optimizers.py``,
unverified — mount empty, see SURVEY.md).

The SURVEY §7 "hard part (a)": ChainerMN wrapped a mutable Chainer Optimizer
in an attribute-forwarding proxy that allreduced ``model.grads`` before
delegating.  JAX optimisers (optax) are pure gradient transformations inside
a jitted step — so the multi-node optimizer becomes a *transformation
stack*: ``[cast → cross-replica mean → cast back → inner optimiser]``.
There is no "first update broadcasts the weights" special case either:
parameters start replicated (``comm.bcast_data`` at init), which is the
first-call ``bcast_data(model)`` of the reference moved to where TPU wants
it.

Double buffering: the reference overlapped iteration *i*'s allreduce with
iteration *i+1*'s fwd/bwd using a worker thread and applied 1-step-stale
averaged grads.  On TPU the *overlap* is XLA's job (async collectives get
scheduled over independent compute automatically); what we preserve is the
**semantics** — applying 1-iteration-stale averaged gradients — because that
staleness is what unlocks the overlap window when the collective is on the
critical path.  Implemented as pure optax state (previous reduced grads),
no threads.
"""

from __future__ import annotations

import warnings
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

__all__ = [
    "PlannedOptimizer",
    "Zero1Transformation",
    "Zero2Transformation",
    "cross_replica_mean",
    "create_multi_node_optimizer",
    "shard_opt_state",
    "zero1_optimizer",
    "zero1_init",
    "zero2_optimizer",
    "DoubleBufferState",
]


def cross_replica_mean(
    axis_name: str,
    dtype=None,
    fused: bool = False,
    bucket_bytes: Optional[int] = None,
    inter_axis_name: Optional[str] = None,
) -> optax.GradientTransformation:
    """Optax transform: mean gradients across ``axis_name``.

    ``dtype`` is the ``allreduce_grad_dtype`` analogue — cast to (e.g.)
    bfloat16 for the wire, cast back after.  XLA fuses both casts into the
    collective's neighbourhood (the reference needed custom CuPy kernels for
    this; here it's free).

    ``fused=True`` routes the mean through
    :func:`chainermn_tpu.ops.fused_allreduce` — the grad pytree is packed
    into dtype-grouped flat buckets of ``bucket_bytes`` and reduced with
    one collective per bucket instead of one per leaf (the reference's
    ``batched_copy`` arena).  ``inter_axis_name`` additionally lowers each
    bucket hierarchically (reduce-scatter intra → all-reduce inter →
    all-gather intra) when the mesh has a second, slower axis.  The fused
    fp32 path is bit-identical to the per-leaf mean (elementwise sums over
    the same members); the compressed path carries the documented bf16
    tolerance.

    Semantics note (idempotency): under shard_map's varying-axes tracking,
    ``pmean`` of an already cross-replica-reduced (invariant) gradient is an
    identity, while ``pmean`` of a device-varying gradient is the true mean.
    So this transform is safe in both regimes: as the sole reducer when the
    user differentiates a *local* loss with grads entering as data, and as a
    no-op safety net when the step differentiates a ``pmean``'d loss (the
    StandardUpdater pattern, where shard_map AD already psums cotangents of
    replicated params).  "Mean of a mean is the mean" — the reference's
    allreduce had the same idempotent shape.

    Only meaningful inside ``shard_map`` (manual SPMD). Under plain
    ``pjit``/``jit`` with a batch-sharded loss *mean*, XLA already inserts
    the collective — then this transform must NOT be added (it would have
    no axis to reduce over).
    """

    def init(params):
        del params
        return optax.EmptyState()

    def update(grads, state, params=None):
        del params
        if fused:
            from chainermn_tpu.ops import fused as _fused

            return _fused.fused_allreduce(
                grads, axis_name, op="mean",
                bucket_bytes=bucket_bytes or _fused.DEFAULT_BUCKET_BYTES,
                wire_dtype=dtype,
                inter_axis_name=inter_axis_name,
            ), state

        def reduce_one(g):
            if dtype is not None and g.dtype != dtype:
                return jax.lax.pmean(g.astype(dtype), axis_name).astype(g.dtype)
            return jax.lax.pmean(g, axis_name)

        return jax.tree.map(reduce_one, grads), state

    return optax.GradientTransformation(init, update)


class PlannedOptimizer(NamedTuple):
    """A multi-node optimizer whose gradient exchange follows a TUNED
    plan (``utils/autotune.py``) instead of per-call kwargs.

    Structurally an ``optax.GradientTransformation`` (``init`` /
    ``update``); the extra ``plan_cell`` is the mutable
    :class:`~chainermn_tpu.utils.autotune.PlanCell` consumers read —
    ``StandardUpdater`` observes exchange times into it, the snapshot
    machinery persists ``plan_cell.plan`` so a resumed run compiles
    the identical exchange program (bitwise resume), never re-tunes
    into a different one.
    """

    init: Callable
    update: Callable
    plan_cell: Any


def _planned_mean(
    axis_name: str,
    cell,
    inter_axis_name: Optional[str] = None,
) -> optax.GradientTransformation:
    """Optax transform: mean gradients across ``axis_name`` following
    the resolved plan in ``cell`` (strategy × bucket size × wire dtype
    picked by measurement, not defaults).  The plan must be resolved
    BEFORE tracing — ``PlannedOptimizer.init`` does that eagerly."""

    def init(params):
        del params
        return optax.EmptyState()

    def update(grads, state, params=None):
        del params
        plan = cell.plan
        if plan is None:
            raise RuntimeError(
                "exchange plan unresolved — call the planned "
                "optimizer's init(params) eagerly (outside jit) first; "
                "plan='auto' tunes there, where real probe programs "
                "can run")
        from chainermn_tpu.ops import fused as _fused

        return _fused.plan_allreduce(
            grads, axis_name, plan,
            inter_axis_name=inter_axis_name), state

    return optax.GradientTransformation(init, update)


class AccumState(NamedTuple):
    step: jnp.ndarray          # micro-step counter (same on all members)
    acc: optax.Updates         # running SUM of incoming (reduced) grads
    inner: Any


def _grad_accumulation(
    inner: optax.GradientTransformation, every: int,
    axis_name: Optional[str] = None,
) -> optax.GradientTransformation:
    """Gradient accumulation around ``inner``: parameters move every
    ``every`` calls with the mean of the accumulated grads.

    Not ``optax.MultiSteps``: its internal ``lax.cond`` branches return
    the incoming-typed updates on emit ticks but zeros typed from a
    fresh ``eval_shape`` on skip ticks, which shard_map's varying-axes
    typing rejects.  Here both branches type their outputs from the SAME
    values (``zeros_like`` of the accumulated mean / the untouched
    state), so the cond stays well-typed in every vma regime.  The
    factory feeds this transform already-reduced grads (post-pmean, or
    zero1 shards), so the accumulator is replication-typed (or
    shard-width); the value of accumulation is the ``every``×-larger
    global batch under fixed HBM — the cross-replica collectives still
    run per micro-step.  For the window-fused variant that also cuts
    collectives (and wire bytes) by ``every``×, use
    ``StandardUpdater(accum_steps=...)`` instead: the updater scans
    LOCAL microbatch gradients and lets this optimizer stack's reducer
    fire once per window.
    """

    def init(params):
        return AccumState(
            jnp.zeros((), jnp.int32),
            jax.tree.map(jnp.zeros_like, params),
            inner.init(params),
        )

    def update(grads, state, params=None):
        acc = jax.tree.map(lambda a, g: a + g, state.acc, grads)
        # the emit predicate must be replication-typed for the lax.cond
        # (a varying pred would force every output varying): the counter
        # is identical on all members by construction, but a world-
        # stacked zero1 carry types it varying — a scalar pmean restores
        # the invariant typing at negligible cost
        step = state.step
        if axis_name is not None:
            if axis_name in jax.typeof(step).vma:
                # the counter is identical on every member; pmax is an
                # EXACT int32 way to restore the replication typing the
                # cond predicate needs (a float pmean would lose integer
                # precision past 2**24 micro-steps)
                step = jax.lax.pmax(step, axis_name)
        emit = (step + 1) % every == 0
        mean = jax.tree.map(lambda a: a / every, acc)

        def do(mean, acc, inner_state):
            upd, new_inner = inner.update(mean, inner_state, params)
            return upd, jax.tree.map(jnp.zeros_like, acc), new_inner

        def skip(mean, acc, inner_state):
            # zeros typed from the SAME value the do branch feeds inner
            # (dtype and vma both match updates = inner.update(mean, ...))
            return jax.tree.map(jnp.zeros_like, mean), acc, inner_state

        upd, acc, new_inner = jax.lax.cond(
            emit, do, skip, mean, acc, state.inner)
        return upd, AccumState(state.step + 1, acc, new_inner)

    return optax.GradientTransformation(init, update)


class DoubleBufferState(NamedTuple):
    prev_grads: optax.Updates


def _double_buffer() -> optax.GradientTransformation:
    """Apply the *previous* step's (already reduced) grads; stash current.

    Matches the reference's pipelined-SGD semantics: weights at step t are
    updated with mean grads from step t-1 (step 0 applies the zero init),
    giving the scheduler a full step of slack to overlap the allreduce with
    compute.
    """

    def init(params):
        return DoubleBufferState(
            prev_grads=jax.tree.map(jnp.zeros_like, params))

    def update(grads, state, params=None):
        del params
        return state.prev_grads, DoubleBufferState(prev_grads=grads)

    return optax.GradientTransformation(init, update)


# --------------------------------------------------------------------- #
# ZeRO-1: optimizer-state sharding over the data axis
# --------------------------------------------------------------------- #


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


from chainermn_tpu.parallel._compat import (
    all_gather_invariant as _all_gather_invariant,
    pcast as _pcast,
)
from chainermn_tpu.utils.programs import ledger_jit


def _ensure_varying(x, axis_name):
    """Mark ``x`` varying over ``axis_name`` if the type system considers
    it invariant (pre-reduced grads): psum_scatter of N identical copies
    divided by N is still the right mean, so both typings are correct."""
    return _pcast(x, axis_name, to="varying")


def _leaf_shard(leaf, idx, n: int):
    """This replica's 1-D shard of ``leaf`` (zero-padded to n·s)."""
    flat = leaf.reshape(-1)
    s = _ceil_div(flat.size, n)
    flat = jnp.pad(flat, (0, s * n - flat.size))
    return jax.lax.dynamic_slice(flat, (idx * s,), (s,))


class Zero1Transformation(NamedTuple):
    """An ``optax.GradientTransformation`` (structurally) whose distinct
    TYPE marks the ZeRO-1 state layout, so consumers that must carry the
    state differently (``StandardUpdater``: world-stacked, sharded over
    the data axis) can detect it instead of asking the user to repeat a
    ``zero1=True`` flag that could silently disagree.

    ``overlap`` marks that the owner asked for the backward-overlapped
    exchange: ZeRO-1's per-leaf ``psum_scatter``s are already join-free
    (each depends only on its own gradient leaf — the property the
    overlap lowering builds for the fused paths), so the flag's whole
    job is telling ``StandardUpdater`` to peel the window-final
    microbatch out of its accumulation scan, putting a backward pass
    in the outer program for those scatters to hide under."""

    init: Callable
    update: Callable
    overlap: bool = False


def zero1_optimizer(
    inner: optax.GradientTransformation,
    axis_name: str,
    wire_dtype=None,
    overlap: bool = False,
) -> optax.GradientTransformation:
    """ZeRO-1: shard ``inner``'s optimiser state across ``axis_name``.

    Beyond-reference (the reference replicated optimiser state on every
    rank, as every DP framework of its era did).  TPU-native mechanics —
    the whole thing is three collectives XLA schedules over ICI:

    - grads:    ``psum_scatter`` (mean) — each replica receives only its
                1/N slice of the averaged gradients, *cheaper on the wire
                than the pmean allreduce it replaces* (reduce-scatter is
                the first half of an allreduce);
    - update:   ``inner`` runs on the 1/N gradient shard with 1/N-sized
                state (Adam moments etc. cost ``2·P/N`` instead of ``2·P``);
    - params:   ``all_gather`` of the updated shard's *updates* (the
                second half of the allreduce), applied identically
                everywhere so parameters stay replicated.

    Must run inside ``shard_map`` with ``axis_name`` in scope — the same
    contract as :func:`cross_replica_mean` (init too: state shapes are
    per-shard).  ``inner`` must be *elementwise* (adam/sgd/adamw/...);
    transforms that mix elements across the tree (``clip_by_global_norm``)
    would see only the local shard and silently mis-normalise — compose
    those *before* this wrapper at full gradient width if needed.

    Each leaf is flattened and zero-padded to a multiple of the axis size;
    padded lanes run through ``inner`` (elementwise ⇒ garbage-in-padding
    stays in padding) and are dropped on the gather.
    """

    def init(params):
        n = jax.lax.axis_size(axis_name)
        idx = jax.lax.axis_index(axis_name)
        shards = jax.tree.map(lambda p: _leaf_shard(p, idx, n), params)
        return inner.init(shards)

    def update(grads, state, params=None):
        n = jax.lax.axis_size(axis_name)
        idx = jax.lax.axis_index(axis_name)

        def scatter_mean(g):
            flat = _ensure_varying(g.reshape(-1), axis_name)
            s = _ceil_div(flat.size, n)
            flat = jnp.pad(flat, (0, s * n - flat.size))
            if wire_dtype is not None and flat.dtype != wire_dtype:
                flat = flat.astype(wire_dtype)
                red = jax.lax.psum_scatter(flat, axis_name, tiled=True)
                return (red / n).astype(g.dtype)
            return jax.lax.psum_scatter(flat, axis_name, tiled=True) / n

        grad_shards = jax.tree.map(scatter_mean, grads)
        param_shards = None if params is None else jax.tree.map(
            lambda p: _leaf_shard(p, idx, n), params)
        upd_shards, state = inner.update(grad_shards, state, param_shards)

        def gather(u, ref):
            # all_gather_invariant: Varying -> Invariant, so the gathered
            # updates (identical on every member by construction) type as
            # replicated and the updated params stay invariant — the same
            # contract as the pmean path.  Its transpose is dynamic_slice,
            # exactly ZeRO's backward.
            if wire_dtype is not None and u.dtype != wire_dtype:
                full = _all_gather_invariant(
                    u.astype(wire_dtype), axis_name, tiled=True
                ).astype(u.dtype)
            else:
                full = _all_gather_invariant(u, axis_name, tiled=True)
            return full[: ref.size].reshape(ref.shape)

        return jax.tree.map(gather, upd_shards, grads), state

    return Zero1Transformation(init, update, overlap=bool(overlap))


# --------------------------------------------------------------------- #
# ZeRO-2: gradient + optimizer-state sharding over the data axis
# --------------------------------------------------------------------- #


class Zero2Transformation(NamedTuple):
    """Type-marks the ZeRO-2 layout the same way
    :class:`Zero1Transformation` marks ZeRO-1 — the optimizer STATE
    layout is identical (world-stacked 1/N flat shards; ``zero1_init``
    and the elastic/serialization machinery apply unchanged), what
    differs is the gradient exchange: per-BUCKET reduce-scatters over
    dtype-grouped leaf buckets instead of one collective per leaf, so
    the full-width averaged gradient never materializes and each
    bucket's scatter is join-free (depends only on its own leaves —
    the property the PR 7 backward-overlap stream needs).
    ``StandardUpdater`` carries ZeRO-2 state exactly like ZeRO-1."""

    init: Callable
    update: Callable
    overlap: bool = False


def _zero2_buckets(leaves, n: int, bucket_bytes: Optional[int]):
    """Join-free exchange buckets over flattened-order ``leaves``:
    grouped by dtype (a collective reduces one dtype), split so one
    bucket's PER-MEMBER shard stays under ``bucket_bytes`` (``None`` =
    one bucket per dtype).  Deterministic from tree order alone, so
    every member builds the identical program."""
    by_dtype: dict = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(jnp.dtype(leaf.dtype), []).append(i)
    buckets = []
    for dt, idxs in by_dtype.items():
        cur, cur_b = [], 0
        for i in idxs:
            b = _ceil_div(leaves[i].size, n) * dt.itemsize
            if cur and bucket_bytes is not None \
                    and cur_b + b > bucket_bytes:
                buckets.append((dt, cur))
                cur, cur_b = [], 0
            cur.append(i)
            cur_b += b
        if cur:
            buckets.append((dt, cur))
    return buckets


def zero2_optimizer(
    inner: optax.GradientTransformation,
    axis_name: str,
    wire_dtype=None,
    overlap: bool = False,
    bucket_bytes: Optional[int] = None,
) -> optax.GradientTransformation:
    """ZeRO-2: shard gradients AND ``inner``'s optimiser state across
    ``axis_name``.

    ZeRO-1 (:func:`zero1_optimizer`) already never materializes the
    full averaged gradient — its per-leaf ``psum_scatter`` IS the
    exchange.  ZeRO-2 keeps the exact same state layout (flat 1/N
    shards per leaf — ``zero1_init``, ``relayout_state`` and the
    shard-only snapshots all apply verbatim) and upgrades the exchange
    to the BUCKETED form: leaves are packed member-major into
    dtype-grouped buckets (each leaf padded to ``n·s`` and reshaped
    ``(n, s)``, buckets concatenated along the shard axis), one
    reduce-scatter per bucket, then sliced back into per-leaf shards.
    Per-element the sums cross the same members in the same order, so
    the fp32 shards are BITWISE identical to ZeRO-1's — the win is
    collective count (L leaves → B buckets) plus join-free buckets the
    backward-overlap stream can hide one at a time.

    Same contract as :func:`zero1_optimizer`: run inside ``shard_map``,
    ``inner`` must be elementwise, padded lanes stay garbage-in-padding.
    ``bucket_bytes`` caps one bucket's per-member shard bytes
    (``utils.comm_model.choose_bucket_bytes`` picks a principled value);
    ``None`` packs each dtype whole.
    """

    def init(params):
        n = jax.lax.axis_size(axis_name)
        idx = jax.lax.axis_index(axis_name)
        shards = jax.tree.map(lambda p: _leaf_shard(p, idx, n), params)
        return inner.init(shards)

    def update(grads, state, params=None):
        n = jax.lax.axis_size(axis_name)
        idx = jax.lax.axis_index(axis_name)
        leaves, treedef = jax.tree.flatten(grads)
        widths = [_ceil_div(l.size, n) for l in leaves]

        # -- bucketed reduce-scatter: the gradient exchange ---------- #
        shard_leaves = [None] * len(leaves)
        for dt, idxs in _zero2_buckets(leaves, n, bucket_bytes):
            mats = []
            for i in idxs:
                flat = _ensure_varying(leaves[i].reshape(-1), axis_name)
                flat = jnp.pad(flat, (0, widths[i] * n - flat.size))
                mats.append(flat.reshape(n, widths[i]))
            buf = (mats[0] if len(mats) == 1
                   else jnp.concatenate(mats, axis=1)).reshape(-1)
            if wire_dtype is not None and buf.dtype != wire_dtype:
                red = jax.lax.psum_scatter(
                    buf.astype(wire_dtype), axis_name, tiled=True)
                red = (red / n).astype(dt)
            else:
                red = jax.lax.psum_scatter(buf, axis_name,
                                           tiled=True) / n
            off = 0
            for i in idxs:
                shard_leaves[i] = red[off:off + widths[i]]
                off += widths[i]
        grad_shards = treedef.unflatten(shard_leaves)

        param_shards = None if params is None else jax.tree.map(
            lambda p: _leaf_shard(p, idx, n), params)
        upd_shards, state = inner.update(grad_shards, state,
                                         param_shards)

        # -- bucketed gather of the updates -------------------------- #
        upd_leaves = jax.tree.leaves(upd_shards)
        out = [None] * len(leaves)
        for dt, idxs in _zero2_buckets(upd_leaves, n, bucket_bytes):
            cat = (upd_leaves[idxs[0]] if len(idxs) == 1
                   else jnp.concatenate([upd_leaves[i] for i in idxs]))
            if wire_dtype is not None and cat.dtype != wire_dtype:
                full = _all_gather_invariant(
                    cat.astype(wire_dtype), axis_name,
                    tiled=True).astype(dt)
            else:
                full = _all_gather_invariant(cat, axis_name, tiled=True)
            mat = full.reshape(n, cat.size)
            off = 0
            for i in idxs:
                ref = leaves[i]
                out[i] = mat[:, off:off + widths[i]].reshape(
                    -1)[: ref.size].reshape(ref.shape)
                off += widths[i]
        return treedef.unflatten(out), state

    return Zero2Transformation(init, update, overlap=bool(overlap))


def shard_opt_state(optimizer, params):
    """Initialise ``optimizer``'s state with the PARAMS' shardings.

    ``jax.jit(optimizer.init)(params)`` silently replicates the state:
    ``zeros_like`` has no data dependence on its input, so XLA's
    sharding propagation never reaches the moment buffers — under an
    FSDP/ZeRO-3 param layout that re-materialises ``2·P`` of replicated
    Adam state and forfeits the sharding's memory win (and forces a
    reshard on the first update).  This helper pins ``out_shardings``
    instead: each state leaf whose shape matches a param leaf gets that
    param's sharding (elementwise optimiser state mirrors the param
    tree leaf-for-leaf), scalars and unmatched leaves replicate.

    Works for any placed param pytree (transformer, ResNet, custom);
    falls back to plain ``jit(init)`` for uncommitted host arrays.

    Matching: optax's params-shaped state (``mu``/``nu``/trace/...)
    mirrors the param tree structurally, so each state leaf's tree path
    *ends with* some param leaf's full path (``mu.blocks.w1`` ↔
    ``blocks.w1``) — longest matching path suffix with an equal shape
    wins; scalars and unmatched leaves replicate.  No shape-only
    fallback: two same-shape params can carry different shardings
    (fsdp w1/w2 with d_ff == d_model), and guessing would pin a
    transposed layout that costs a hidden reshard every update —
    replicated is the safe default for state a path can't identify.
    """
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    from jax.tree_util import tree_flatten_with_path

    p_paths, _ = tree_flatten_with_path(params)
    by_path, mesh = {}, None
    for path, p in p_paths:
        sh = getattr(p, "sharding", None)
        if sh is None or not hasattr(sh, "mesh"):
            continue
        mesh = mesh if mesh is not None else sh.mesh
        by_path[tuple(str(k) for k in path)] = (p.shape, sh)
    if mesh is None:
        return ledger_jit(optimizer.init,
                          label="train/opt_init")(params)
    replicated = NamedSharding(mesh, P())
    shapes = jax.eval_shape(optimizer.init, params)
    s_paths, treedef = tree_flatten_with_path(shapes)

    def pick(path, sd):
        keys = tuple(str(k) for k in path)
        # longest suffix first, INCLUDING the empty suffix — a bare
        # jax.Array params "tree" has the empty path as its only key
        for start in range(len(keys) + 1):
            hit = by_path.get(keys[start:])
            if hit is not None and hit[0] == sd.shape:
                return hit[1]
        return replicated

    out_shardings = treedef.unflatten(
        [pick(path, sd) for path, sd in s_paths])
    return ledger_jit(optimizer.init, label="train/opt_init",
                      out_shardings=out_shardings)(params)


def zero1_init(tx, params, mesh, axis_name: str):
    """Initialise a :func:`zero1_optimizer`-wrapped transformation whose
    state must persist *across* jit/shard_map boundaries.

    ``tx.init`` needs the mesh axis in scope (state shapes are per-shard),
    so ``jax.jit(tx.init)(params)`` does not work for ZeRO.  This helper
    runs init inside ``shard_map`` and returns **world-stacked** state
    (leading axis = member index along ``axis_name``, the same convention
    as the eager communicator collectives): every leaf — including rank-0
    leaves like adam's ``count`` — gets a leading member axis so one
    uniform ``P(axis_name)`` spec moves it through any boundary.

    Step functions receive the stacked state with ``in_specs
    P(axis_name)`` (each member sees its own ``(1, ...)`` slice), drop the
    member axis with ``jax.tree.map(lambda x: x[0], state)``, run
    ``tx.update``, re-stack with ``jax.tree.map(lambda x: x[None], st)``
    and return it under ``out_specs P(axis_name)``.
    """
    from jax.sharding import PartitionSpec as P

    def body(p):
        state = tx.init(p)
        # member axis on every leaf; varying-typed so P(axis_name) is
        # always a legal (and shape-unambiguous) out_spec
        return jax.tree.map(
            lambda x: _ensure_varying(jnp.asarray(x), axis_name)[None],
            state)

    f = ledger_jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(), out_specs=P(axis_name)),
        label="train/opt_init")
    return f(params)


# one-time (per process) warning for plan= under ZeRO-1 — the fallback
# must be visible, not a silent downgrade, but not a per-step nag either
_ZERO1_PLAN_WARNED = False


def create_multi_node_optimizer(
    actual_optimizer: optax.GradientTransformation,
    comm=None,
    double_buffering: bool = False,
    zero1: bool = False,
    zero2: bool = False,
    accum_steps: int = 1,
    axis_name: Optional[str] = None,
    allreduce_grad_dtype=None,
    fused: bool = True,
    bucket_bytes: Optional[int] = None,
    inter_axis_name: Optional[str] = None,
    plan=None,
    overlap: Any = False,
) -> optax.GradientTransformation:
    """Wrap an optax optimiser with cross-replica gradient averaging.

    Args:
      actual_optimizer: any ``optax.GradientTransformation`` (the reference
        wrapped any Chainer ``Optimizer`` the same way).
      comm: communicator whose ``axis_name`` defines the reduction axis
        (or pass ``axis_name`` directly).
      double_buffering: apply 1-step-stale reduced grads (overlap window —
        reference's ``_DoubleBufferingOptimizer``).
      zero1: shard optimiser state over the reduction axis
        (:func:`zero1_optimizer`); replaces the pmean with a
        reduce-scatter/all-gather pair.  With ``double_buffering`` the
        stale-grad stash is also sharded (1/N memory).
      zero2: ZeRO-2 (:func:`zero2_optimizer`) — same optimiser-state
        layout as ``zero1`` (the updater/elastic/snapshot machinery is
        shared), with the gradient exchange bucketed: dtype-grouped
        join-free reduce-scatters instead of one collective per leaf,
        so gradients too live at 1/N width between scatter and gather.
        Mutually exclusive with ``zero1``; ``bucket_bytes`` caps the
        per-member bucket shard.
      accum_steps: gradient accumulation — parameters update every
        ``accum_steps`` calls with the mean of the accumulated grads
        (global batch = ``world × local_batch × accum_steps``; the
        large-batch recipe's missing piece when HBM caps the per-step
        batch).  The accumulator sits after the cross-replica reduction,
        so it holds *reduced* (replication-typed) grads — carryable with
        plain replicated out_specs in every regime — and, under zero1,
        1/world-width shards.  Double buffering composes at the emit
        level (staleness counts real updates, not micro-steps).  NOTE:
        the collectives still fire per micro-step here; prefer
        ``StandardUpdater(accum_steps=...)`` (window-fused exchange,
        M→1 collectives per window) unless grads really do arrive one
        external call at a time.  Don't stack both: each would divide
        by its own window.
      allreduce_grad_dtype: wire dtype for the mean (bf16 recommended).
      fused: pack the grad pytree into flat dtype-grouped buckets and
        reduce one bucket per collective
        (:func:`chainermn_tpu.ops.fused_allreduce`) instead of one
        collective per leaf — the default, and numerically identical to
        per-leaf pmean in fp32.  Ignored under ``zero1`` (whose
        reduce-scatter/all-gather pair already amortises per-leaf).
      bucket_bytes: fused bucket size;
        :func:`chainermn_tpu.utils.comm_model.choose_bucket_bytes` picks
        a principled value from the latency-bandwidth model (default
        4 MiB).
      inter_axis_name: second (slower, e.g. DCN) mesh axis for the
        hierarchical 2-stage bucket lowering; the step's ``shard_map``
        must bind both axes.  Typically wired by the communicator when
        ``comm.inter_size > 1``.
      plan: drive the gradient exchange from a MEASURED plan
        (``utils/autotune.py``) instead of the kwargs above.
        ``"auto"`` tunes at ``init(params)`` time (eager, outside jit
        — the ``StandardUpdater`` contract): cache warm-start when the
        (mesh, payload, version) signature matches, otherwise a live
        probe search whose winner rank 0 broadcasts; a
        :class:`~chainermn_tpu.utils.autotune.Plan` (or its dict form,
        e.g. restored from a snapshot) skips tuning entirely.  Returns
        a :class:`PlannedOptimizer` carrying the ``plan_cell``; the
        ``fused``/``bucket_bytes``/``allreduce_grad_dtype`` kwargs are
        superseded by the plan's strategy/bucket/wire fields.
        Hierarchical candidates enter the search only when
        ``inter_axis_name`` is given (the step must bind the axis).
        Under ``zero1`` the plan is IGNORED with a one-time warning:
        ZeRO-1's reduce-scatter/all-gather pair is a different exchange
        family the planner does not drive, and the analytic path is
        the correct fallback — so ``plan="auto"`` is safe to set
        globally across a fleet where some jobs shard their optimizer
        state.
      overlap: fire the gradient exchange DURING the backward pass
        instead of after it (the backward-overlapped lowering,
        ``ops.fused.overlap_exchange``): the grad pytree is cut into
        reverse-layer-ordered buckets and each bucket's
        reduce-scatter→all-gather is emitted as soon as its gradients
        exist, so XLA hides wire time under the remaining backward
        compute (``utils.comm_model.assert_overlap_collectives`` is
        the HLO proof).  ``True`` with ``plan=None`` builds a static
        overlap plan (analytic schedule from ``bucket_bytes`` /
        ``allreduce_grad_dtype``); with ``plan="auto"`` the autotuner
        searches the *schedule* dimension (bucket boundaries ×
        eager/deferred per bucket) and the winner stays in the overlap
        family; ``"auto"`` (with ``plan="auto"``) lets measurement
        pick between the overlap and window-end families.  Under
        ``zero1`` the per-leaf reduce-scatters are already join-free,
        so the flag only marks the transformation for the updater's
        final-microbatch peel.  ``StandardUpdater`` detects overlap
        from the plan and restructures its accumulation scan so the
        window-final microbatch's backward sits in the outer program —
        otherwise the scan would join every gradient and there would
        be nothing to overlap under.
    """
    ax = axis_name or (comm.axis_name if comm is not None else None)
    if ax is None:
        raise ValueError("need comm or axis_name")
    if accum_steps < 1:
        raise ValueError(f"accum_steps {accum_steps} must be >= 1")
    if zero1 and zero2:
        raise ValueError(
            "zero1=True and zero2=True are mutually exclusive — "
            "ZeRO-2 subsumes ZeRO-1's state sharding; pick one")
    if plan is not None and (zero1 or zero2):
        # graceful fallback, not an error: plan="auto" must be safe to
        # set globally.  ZeRO-1's reduce-scatter/all-gather pair is its
        # own (analytic, per-leaf, join-free) exchange; the plan would
        # drive an exchange that never runs.
        global _ZERO1_PLAN_WARNED
        if not _ZERO1_PLAN_WARNED:
            _ZERO1_PLAN_WARNED = True
            warnings.warn(
                "create_multi_node_optimizer: plan= is ignored under "
                "zero1/zero2 — ZeRO exchanges gradients through its "
                "own reduce-scatter/all-gather pair, so the analytic "
                "path is used instead of the tuned plan (warning shown "
                "once per process)", RuntimeWarning, stacklevel=2)
        plan = None
    inner = actual_optimizer
    if double_buffering:
        inner = optax.chain(_double_buffer(), inner)
    if accum_steps > 1:
        inner = _grad_accumulation(inner, accum_steps, axis_name=ax)
    if zero2:
        # accumulation INSIDE zero2: the accumulator holds 1/N shards
        return zero2_optimizer(inner, ax,
                               wire_dtype=allreduce_grad_dtype,
                               overlap=bool(overlap),
                               bucket_bytes=bucket_bytes)
    if zero1:
        # accumulation INSIDE zero1: the accumulator holds 1/N shards
        return zero1_optimizer(inner, ax,
                               wire_dtype=allreduce_grad_dtype,
                               overlap=bool(overlap))
    if overlap and plan is None:
        if overlap is not True:
            # overlap="auto" means "let the MEASUREMENT pick between
            # the overlap and window-end families" — without
            # plan="auto" no measurement ever runs, and silently
            # forcing the static overlap plan would contradict the
            # request
            raise ValueError(
                f"overlap={overlap!r} asks the measured search to "
                f"choose between the overlap and window-end families, "
                f"which needs plan='auto'; pass overlap=True for the "
                f"static (untuned) overlap plan")
        # static overlap plan: analytic schedule derived from
        # bucket_bytes at trace time, no tuning, no comm needed
        from chainermn_tpu.ops import fused as _fused
        from chainermn_tpu.utils import autotune as _autotune

        plan = _autotune.Plan(
            strategy="overlap",
            bucket_bytes=bucket_bytes or _fused.DEFAULT_BUCKET_BYTES,
            wire_dtype=(jnp.dtype(allreduce_grad_dtype).name
                        if allreduce_grad_dtype is not None else None),
        )
    if plan is not None:
        from chainermn_tpu.utils import autotune as _autotune

        if isinstance(plan, _autotune.PlanCell):
            cell = plan
        elif isinstance(plan, str):
            if plan != "auto":
                raise ValueError(
                    f"plan={plan!r}: expected 'auto', a Plan, or a "
                    f"plan dict")
            if comm is None:
                raise ValueError(
                    "plan='auto' needs comm — the autotuner probes on "
                    "its mesh and broadcasts the winner from rank 0")
            cell = _autotune.PlanCell()
        else:
            cell = _autotune.PlanCell(_autotune.Plan.from_any(plan))
        if overlap is True and cell.plan is not None \
                and cell.plan.strategy != "overlap":
            raise ValueError(
                f"overlap=True with an explicit plan of strategy "
                f"{cell.plan.strategy!r}: the plan drives the exchange, "
                f"so a window-end plan cannot satisfy the overlap "
                f"request — pass an 'overlap' plan, plan='auto', or "
                f"drop overlap=")
        chained = optax.chain(
            _planned_mean(ax, cell, inter_axis_name=inter_axis_name),
            inner)

        # the plan executes inside the USER's shard_map: hierarchical
        # is only runnable when that program binds the second axis.
        # Recorded on the cell so a later drift retune() tunes under
        # the SAME constraint (including the overlap-family one).
        cell.tune_kwargs = dict(
            inter_axis_name=inter_axis_name,
            allow_hierarchical=(
                None if inter_axis_name is not None else False),
            overlap=overlap if overlap else False)

        def planned_init(params):
            if cell.plan is None:
                cell.resolve(_autotune.autotune_plan(
                    comm, params, **cell.tune_kwargs))
            return chained.init(params)

        return PlannedOptimizer(planned_init, chained.update, cell)
    return optax.chain(
        cross_replica_mean(ax, allreduce_grad_dtype, fused=fused,
                           bucket_bytes=bucket_bytes,
                           inter_axis_name=inter_axis_name), inner)
