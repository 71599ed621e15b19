"""Pipeline parallelism with micro-batching (GPipe schedule).

Reference parity-plus: ChainerMN's pipeline (``MultiNodeChainList`` +
blocking p2p) kept exactly ONE activation in flight — fill/drain bubbles
were unmitigated (SURVEY.md §3.3).  This module adds the micro-batched
schedule the reference lacked: ``M`` micro-batches stream through ``S``
stages in ``M + S - 1`` ticks, bubble fraction ``(S-1)/(M+S-1)``.

TPU-native shape: ONE SPMD program over the ``pipe`` mesh axis —

- stage parameters are *sharded* over the axis (device ``s`` holds only
  stage ``s``'s weights: true memory scaling, unlike the replicated
  ``MultiNodeChainList``);
- activation hand-off is ``lax.ppermute`` (ICI neighbour copy);
- the tick loop is ``lax.scan`` — compiled once, no Python per tick;
- backward needs no hand-written reverse schedule: the transpose of
  (scan ∘ ppermute) IS the reverse-order pipeline, with grads flowing
  stage ``s`` ← ``s+1`` automatically.

Composition: wrap in ``shard_map`` with the batch dim also sharded over
``data`` and weights over ``model`` — the schedule is orthogonal to
TP/DP/SP because it only touches the ``pipe`` axis.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.parallel._compat import pcast

__all__ = ["stack_stage_params", "pipeline_apply", "pipeline_train_1f1b",
           "pipeline_train_interleaved", "unstack_stage_params"]


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _replicate_from(x, axis_name, src):
    """Broadcast ``x`` from rank ``src`` with grad-correct transpose.

    Forward: masked psum (zeros off ``src`` ⇒ the sum IS the broadcast).
    Backward: under the SPMD convention every rank seeds the same cotangent
    (each differentiates its identical copy of the loss), so the raw psum
    transpose would hand ``src`` the cotangent summed over all ranks —
    scaling pipeline-stage grads by the axis size.  The custom rule takes
    the *mean* of the cotangents instead, restoring the logical gradient.
    """
    idx = lax.axis_index(axis_name)
    return lax.psum(
        jnp.where(idx == src, x, jnp.zeros_like(x)), axis_name)


def _replicate_fwd(x, axis_name, src):
    return _replicate_from(x, axis_name, src), None


def _replicate_bwd(axis_name, src, _, ct):
    idx = lax.axis_index(axis_name)
    g = lax.pmean(ct, axis_name)
    return (jnp.where(idx == src, g, jnp.zeros_like(g)),)


_replicate_from.defvjp(_replicate_fwd, _replicate_bwd)


def _edge_send(act, axis_name, perm, shift, wrap, plan):
    """One stage-edge hand-off — a raw ``lax.ppermute``, or the
    collective-plan IR lowering when a tuned ``pipeline_edge`` plan is
    supplied.  ``perm`` is the prebuilt legacy permutation for exactly
    the same (shift, wrap) edge, so both paths move identical data."""
    if plan is None:
        return lax.ppermute(act, axis_name, perm=perm)
    from chainermn_tpu.ops import plan_ir

    return plan_ir.lower_pipeline_edge(
        plan_ir.ensure_program(plan, "pipeline_edge"), act,
        axis_name=axis_name, shift=shift, wrap=wrap)


def _with_dummy_aux(stage_fn, with_aux):
    """Normalise ``stage_fn`` to the ``(mb, aux)`` shape.  The dummy aux
    must DERIVE from mb so its vma matches the varying cotangent seeded
    in the backward slot (a bare constant zero would type-clash with
    ``ct_a`` inside ``jax.vjp``)."""
    if with_aux:
        return stage_fn
    return lambda p, mb: (stage_fn(p, mb),
                          jnp.sum(mb * 0, dtype=jnp.float32))


def stack_stage_params(params_list):
    """Stack per-stage pytrees along a new leading ``stage`` axis (to be
    sharded over ``pipe``).  All stages must share one structure — the
    homogeneous-stack contract that lets stage weights shard instead of
    replicate (heterogeneous graphs: use ``links.MultiNodeChainList``)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_list)


def unstack_stage_params(stacked):
    """Inverse of :func:`stack_stage_params` (host-side convenience)."""
    n = jax.tree.leaves(stacked)[0].shape[0]
    return [jax.tree.map(lambda a: a[i], stacked) for i in range(n)]


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x,
    *,
    axis_name: str = "pipe",
    num_microbatches: int,
    remat: bool = True,
    with_aux: bool = False,
    checkpoint_fn: Callable = None,
    edge_plan=None,
):
    """Run the GPipe schedule.  Call INSIDE ``shard_map`` over ``axis_name``.

    Args:
      stage_fn: ``stage_fn(params, mb) -> mb`` — one stage's computation;
        must preserve the micro-batch's shape/dtype (chainable stages).
      stage_params: THIS device's stage weights — pass the stacked params
        into shard_map with the leading stage axis sharded over
        ``axis_name`` and a leading axis of size 1 here (it is squeezed).
      x: full local batch ``(B, ...)`` with ``B % num_microbatches == 0``;
        replicated over the pipe axis (only stage 0 reads it).
      num_microbatches: ``M``; larger M shrinks the bubble
        ``(S-1)/(M+S-1)`` at the cost of smaller per-tick matmuls — keep
        micro-batches big enough to fill the MXU.
      remat: rematerialise each stage application in backward (GPipe's
        memory trick: store only stage boundaries, recompute inside).
      checkpoint_fn: override the remat wrapper (e.g. a policied
        ``jax.checkpoint`` saving matmul outputs); ignores ``remat``.
      with_aux: ``stage_fn`` returns ``(mb, aux_scalar)``; per-microbatch
        aux values from REAL ticks (not drain garbage) are summed over
        stages and averaged over micro-batches, and the call returns
        ``(out, aux)`` — how the Switch-MoE balancing loss survives
        pipelining instead of being dropped.
      edge_plan: a tuned Plan from
        ``autotune_pattern_plan(pattern="pipeline_edge")``, its
        ``.program`` dict, or an ``ops.plan_ir.PlanProgram`` — lowers
        every stage-edge hand-off through the collective-plan IR
        instead of the raw ``lax.ppermute``.

    Returns the full batch output ``(B, ...)``, replicated over the pipe
    axis (masked psum from the last stage — so downstream loss code is
    identical with and without pipelining).  With ``with_aux``:
    ``(output, aux)``.
    """
    S = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    M = num_microbatches

    # squeeze the sharded leading stage axis (shard size 1 per device)
    params = jax.tree.map(
        lambda a: jnp.squeeze(a, axis=0), stage_params)

    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    mbs = x.reshape(M, B // M, *x.shape[1:])

    raw_fn = stage_fn if with_aux else (
        lambda p, mb: (stage_fn(p, mb), jnp.zeros((), jnp.float32)))
    if checkpoint_fn is None:
        checkpoint_fn = jax.checkpoint if remat else (lambda f: f)
    fn = checkpoint_fn(raw_fn)

    up_perm = [(i, i + 1) for i in range(S - 1)]

    def tick(carry, t):
        act, outputs, aux_acc = carry
        # neighbour hand-off: device s receives device s-1's last output
        recv = _edge_send(act, axis_name, up_perm, 1, False,
                          edge_plan) if S > 1 else act
        # stage 0 injects micro-batch t (clamped; ticks ≥ M push don't-care
        # values that drain past the last stage after the loop window)
        xt = mbs[jnp.minimum(t, M - 1)]
        inp = jnp.where(stage == 0, xt, recv)
        out, aux = fn(params, inp)
        # stage s is working on micro-batch t-s during ticks s..s+M-1;
        # fill/drain ticks push don't-care values whose aux must not count
        active = (t >= stage) & (t - stage < M)
        aux_acc = aux_acc + jnp.where(active, aux, 0.0)
        # last stage banks micro-batch t-(S-1) once the pipe is full
        idx = jnp.clip(t - (S - 1), 0, M - 1)
        updated = lax.dynamic_update_index_in_dim(outputs, out, idx, 0)
        outputs = jnp.where(t >= S - 1, updated, outputs)
        return (out, outputs, aux_acc), None

    # initial carries are zeros that must carry the UNION of the input's
    # varying axes (data/seq/... under composition) plus the pipe axis —
    # deriving them from mbs inherits the vma, the multiply folds away
    act0 = pcast(mbs[0] * 0, (axis_name,), to="varying")
    outs0 = pcast(mbs * 0, (axis_name,), to="varying")
    aux0 = jnp.sum(act0 * 0, dtype=jnp.float32)
    (_, outputs, aux_acc), _ = lax.scan(
        tick, (act0, outs0, aux0), jnp.arange(M + S - 1))

    # broadcast the last stage's accumulator so downstream loss code is
    # identical with and without pipelining (grad-correct custom transpose;
    # also runs for S=1, where the free psum marks the result replicated)
    outputs = _replicate_from(outputs, axis_name, S - 1)
    out = outputs.reshape(B, *x.shape[1:])
    if not with_aux:
        return out
    # total aux = sum over stages (psum) of each stage's M real ticks,
    # averaged over micro-batches to match the unpipelined batch-mean
    aux = lax.psum(aux_acc, axis_name) / M
    return out, aux


def pipeline_train_1f1b(
    stage_fn: Callable,
    loss_fn: Callable,
    stage_params,
    loss_params,
    x,
    targets,
    *,
    axis_name: str = "pipe",
    num_microbatches: int,
    with_aux: bool = False,
    aux_weight: float = 1.0,
    edge_plan=None,
):
    """One-forward-one-backward (1F1B) pipelined training step.

    Why a separate entry point: 1F1B's point is that each micro-batch's
    backward starts as soon as its forward clears the last stage, capping
    in-flight activations at ``O(S)`` instead of GPipe's ``O(M)``.  That
    is only possible when the LOSS lives inside the schedule (the last
    stage seeds cotangents itself) — with an outer loss, every forward
    must finish first and the memory cap is lost.  So this function
    computes loss AND gradients in one scheduled SPMD program, instead of
    returning activations for an outer ``jax.grad``.

    Schedule: ``M + 2(S-1)`` ticks, each with a forward slot and a
    backward slot.  Stage ``s`` forwards micro-batch ``t − s`` and
    backwards micro-batch ``t − (2S−2−s)`` (active-masked); in steady
    state every stage alternates 1F/1B.  Stage inputs are stashed in a
    ``2S−1``-slot ring buffer — the ``O(S)`` activation memory — and each
    backward slot recomputes its stage forward via ``jax.vjp`` on the
    stashed input (the remat trade GPipe makes too).  Bubble fraction
    ``2(S−1)/(M+2(S−1))``, the same fill/drain cost as GPipe — the win is
    memory, not bubbles (interleaved/looping schedules would shrink the
    bubble; see README roadmap).

    Args:
      stage_fn: ``stage_fn(params, mb) -> mb`` (shape-preserving).
      loss_fn: ``loss_fn(loss_params, y, tgt) -> scalar`` — applied to
        the LAST stage's output per micro-batch (head + loss; its
        parameter gradients flow too).
      stage_params: this device's stage weights, leading axis 1 (as in
        :func:`pipeline_apply`).
      loss_params: pytree used by ``loss_fn`` (e.g. final norm + output
        head), replicated over the mesh.
      x: full local batch ``(B, ...)``; ``targets``: ``(B, ...)``.
      with_aux: ``stage_fn`` returns ``(mb, aux_scalar)``; each stage's
        per-micro-batch aux (the Switch-MoE balancing loss) is summed
        over stages, averaged over micro-batches, and returned — AND its
        gradient flows: every backward slot seeds its own stage's aux
        cotangent with ``aux_weight``, so ``stage_grads`` differentiates
        ``mean_mb(loss) + aux_weight * aux`` exactly like the GPipe path
        differentiating ``loss + aux_weight * pipeline_apply(...)[1]``.
      aux_weight: the coefficient the aux term carries in the training
        objective (gradient-side only; the RETURNED aux is unweighted so
        callers can report/compose it like ``pipeline_apply`` does).
      edge_plan: as :func:`pipeline_apply` — lowers both the activation
        (up) and cotangent (down) stage edges through the
        collective-plan IR.

    Returns ``(loss, stage_grads, loss_grads, dx)`` — loss is the mean
    over micro-batches (replicated); ``stage_grads`` matches
    ``stage_params`` (this stage's shard, leading axis 1); ``loss_grads``
    matches ``loss_params`` (replicated); ``dx`` is ``∂loss/∂x`` for the
    layers feeding the pipeline (replicated).  With ``with_aux``:
    ``(loss, aux, stage_grads, loss_grads, dx)``.
    """
    S = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    M = num_microbatches
    is_last = stage == S - 1

    params = jax.tree.map(lambda a: jnp.squeeze(a, axis=0), stage_params)

    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    mbs = x.reshape(M, B // M, *x.shape[1:])
    tgts = targets.reshape(M, B // M, *targets.shape[1:])

    raw_fn = _with_dummy_aux(stage_fn, with_aux)

    K = 2 * S - 1  # stash ring depth: max in-flight per stage is 2(S−1)+1
    up_perm = [(i, i + 1) for i in range(S - 1)]
    down_perm = [(i + 1, i) for i in range(S - 1)]

    def masked_add(acc, new, active):
        return jax.tree.map(
            lambda a, n: a + jnp.where(active, n, jnp.zeros_like(n)),
            acc, new)

    def tick(carry, t):
        act, ct, stash, gp, glp, dx_bank, loss_acc, aux_acc = carry

        # ---- forward slot: stage s forwards micro-batch t − s -------- #
        m_f = t - stage
        fwd_active = (m_f >= 0) & (m_f < M)
        recv = _edge_send(act, axis_name, up_perm, 1, False,
                          edge_plan) if S > 1 else act
        inp = jnp.where(stage == 0, mbs[jnp.clip(m_f, 0, M - 1)], recv)
        y, aux_f = raw_fn(params, inp)
        stash = jnp.where(
            fwd_active,
            lax.dynamic_update_index_in_dim(stash, inp, m_f % K, 0),
            stash)
        aux_acc = aux_acc + jnp.where(fwd_active, aux_f, 0.0)

        # ---- backward slot: stage s backwards t − (2S−2−s) ----------- #
        m_b = t - (2 * S - 2 - stage)
        bwd_active = (m_b >= 0) & (m_b < M)
        ct_recv = _edge_send(ct, axis_name, down_perm, -1, False,
                             edge_plan) if S > 1 else ct
        inp_b = stash[jnp.clip(m_b, 0, M - 1) % K]
        tgt_b = tgts[jnp.clip(m_b, 0, M - 1)]

        def composite(p, lp, xin):
            yy, aux = raw_fn(p, xin)
            return yy, loss_fn(lp, yy, tgt_b), aux

        (_, l_b, a_b), vjp = jax.vjp(
            composite, params, loss_params, inp_b)
        # the last stage seeds its own cotangent from the in-schedule
        # loss; earlier stages consume the downstream stage's dx
        ct_y = jnp.where(is_last, jnp.zeros_like(ct_recv), ct_recv)
        # + l_b*0: the cotangent must carry l_b's full varying-axes set
        # (data/seq/... under composition), not just the pipe axis
        ct_l = jnp.where(is_last, 1.0, 0.0).astype(l_b.dtype) + l_b * 0
        # EVERY stage seeds its own aux cotangent (each stage's layers
        # own their balancing loss); inactive-tick garbage is masked out
        # of gp below, and the dx it pollutes only reaches inactive
        # upstream slots (the schedule dependency argument).  Built from
        # the aux primal so dtype AND vma match it exactly.
        ct_a = jnp.asarray(aux_weight, a_b.dtype) + a_b * 0
        dp, dlp, dx = vjp((ct_y, ct_l, ct_a))

        gp = masked_add(gp, dp, bwd_active)
        # loss_params are REPLICATED, so the shard_map transpose has
        # already psummed dlp over the pipe axis (every device sees the
        # global value = the last stage's contribution, since only its
        # ct_l is 1).  Bank it on the last stage only; the closing psum
        # then counts it exactly once.
        glp = masked_add(glp, dlp, bwd_active & is_last)
        bank = bwd_active & (stage == 0)
        dx_bank = jnp.where(
            bank,
            lax.dynamic_update_index_in_dim(
                dx_bank, dx, jnp.clip(m_b, 0, M - 1), 0),
            dx_bank)
        loss_acc = loss_acc + jnp.where(
            bwd_active & is_last, l_b, 0.0)

        return (y, dx, stash, gp, glp, dx_bank, loss_acc, aux_acc), None

    # zero carries derived from real tensors so they inherit the varying
    # mesh axes (vma discipline, as in pipeline_apply)
    mb0 = pcast(mbs[0] * 0, (axis_name,), to="varying")
    stash0 = jnp.broadcast_to(mb0, (K, *mb0.shape)) * 1
    gp0 = jax.tree.map(lambda a: a * 0, params)
    glp0 = jax.tree.map(
        lambda a: pcast(a * 0, (axis_name,), to="varying"), loss_params)
    dx0 = pcast(mbs * 0, (axis_name,), to="varying")
    loss0 = jnp.sum(mb0 * 0, dtype=jnp.float32)

    (_, _, _, gp, glp, dx_bank, loss_acc, aux_acc), _ = lax.scan(
        tick, (mb0, mb0, stash0, gp0, glp0, dx0, loss0, loss0),
        jnp.arange(M + 2 * (S - 1)))

    # loss / loss-param grads / input grads live on single stages (last,
    # last, first) with zeros elsewhere — psum replicates them exactly
    loss = lax.psum(loss_acc, axis_name) / M
    glp = jax.tree.map(lambda a: lax.psum(a, axis_name) / M, glp)
    dx = lax.psum(dx_bank, axis_name).reshape(B, *x.shape[1:]) / M
    gp = jax.tree.map(lambda a: a[None] / M, gp)  # restore stage axis
    if not with_aux:
        return loss, gp, glp, dx
    # same convention as pipeline_apply: stage-sum / micro-batch mean
    aux = lax.psum(aux_acc, axis_name) / M
    return loss, aux, gp, glp, dx


# --------------------------------------------------------------------- #
# Interleaved 1F1B (virtual pipeline stages)
# --------------------------------------------------------------------- #


def _interleaved_tables(S: int, V: int, M: int):
    """Static tick tables for the interleaved 1F1B schedule.

    Device ``s`` holds ``V`` model chunks; virtual stage ``g = c·S + s``
    is chunk ``c`` on device ``s``.  Per Megatron's schedule, device
    ``s``'s forward slot ``k`` handles micro-batch
    ``(k // (S·V))·S + k % S`` of chunk ``(k % (S·V)) // S``; backward
    slots mirror it with chunks reversed, delayed by the warmup
    ``(S−s−1)·2 + (V−1)·S``.  Staggering device ``s``'s slot sequence by
    ``s`` ticks makes EVERY data dependency (chain, ring wrap, and the
    last virtual stage's same-tick loss seed) exactly one ring hop one
    tick earlier — verified by assertion below, so a schedule bug fails
    loudly at trace time instead of silently mis-wiring activations.

    Returns ``(T, f_act, f_m, f_c, b_act, b_m, b_c, K)`` — tick count,
    ``(S, T)`` activity/micro-batch/chunk tables, and the stash ring
    depth (exact max in-flight per chunk, so ``m % K`` slots never
    collide).
    """
    import numpy as np

    if M % S:
        raise ValueError(
            f"interleaved schedule needs micro-batches ({M}) divisible "
            f"by the pipe axis ({S})")
    SV, MV = S * V, M * V
    T = 2 * (S - 1) + (V - 1) * S + MV
    f_act = np.zeros((S, T), bool)
    b_act = np.zeros((S, T), bool)
    f_m = np.zeros((S, T), np.int32)
    f_c = np.zeros((S, T), np.int32)
    b_m = np.zeros((S, T), np.int32)
    b_c = np.zeros((S, T), np.int32)
    for s in range(S):
        w = (S - s - 1) * 2 + (V - 1) * S
        for t in range(T):
            k = t - s
            if 0 <= k < MV:
                p = k % SV
                f_act[s, t] = True
                f_m[s, t] = (k // SV) * S + p % S
                f_c[s, t] = p // S
            j = t - s - w
            if 0 <= j < MV:
                p = j % SV
                b_act[s, t] = True
                b_m[s, t] = (j // SV) * S + p % S
                b_c[s, t] = V - 1 - p // S

    # self-verify every dependency = one ring hop, one tick earlier
    # (explicit raise, not assert: the fail-loudly promise must survive
    # python -O)
    def _dep(cond, what, s, t):
        if not cond:
            raise RuntimeError(
                f"interleaved schedule: {what} dependency broken at "
                f"device {s} tick {t} (S={S} V={V} M={M})")

    for s in range(S):
        for t in range(T):
            if f_act[s, t] and not (s == 0 and f_c[s, t] == 0):
                ps, pc = (s - 1) % S, f_c[s, t] - (1 if s == 0 else 0)
                _dep(f_act[ps, t - 1] and f_m[ps, t - 1] == f_m[s, t]
                     and f_c[ps, t - 1] == pc, "forward", s, t)
            if b_act[s, t] and not (s == S - 1 and b_c[s, t] == V - 1):
                ns = (s + 1) % S
                nc = b_c[s, t] + (1 if s == S - 1 else 0)
                _dep(b_act[ns, t - 1] and b_m[ns, t - 1] == b_m[s, t]
                     and b_c[ns, t - 1] == nc, "backward", s, t)
            if b_act[s, t] and s == S - 1 and b_c[s, t] == V - 1:
                # loss seed: forward of the same (m, chunk) this tick or
                # earlier on this device
                m = b_m[s, t]
                _dep(any(f_act[s, tt] and f_m[s, tt] == m
                         and f_c[s, tt] == V - 1
                         for tt in range(t + 1)), "loss-seed", s, t)

    # exact stash requirement: max concurrent (t_fwd..t_bwd) intervals
    # per (device, chunk); in-flight micro-batches are consecutive, so a
    # ring of that depth indexed by m % K cannot collide
    K = 1
    for s in range(S):
        for c in range(V):
            events = []
            for t in range(T):
                if f_act[s, t] and f_c[s, t] == c:
                    events.append((t, 1))
                if b_act[s, t] and b_c[s, t] == c:
                    events.append((t + 1, -1))
            live = peak = 0
            for t, d in sorted(events):
                live += d
                peak = max(peak, live)
            K = max(K, peak)
    return T, f_act, f_m, f_c, b_act, b_m, b_c, K


def pipeline_train_interleaved(
    stage_fn: Callable,
    loss_fn: Callable,
    stage_params,
    loss_params,
    x,
    targets,
    *,
    axis_name: str = "pipe",
    num_microbatches: int,
    num_chunks: int,
    with_aux: bool = False,
    aux_weight: float = 1.0,
    edge_plan=None,
):
    """Interleaved 1F1B (Megatron virtual pipeline stages), one SPMD scan.

    Each device holds ``num_chunks`` (V) model chunks instead of one
    contiguous stage; micro-batches traverse the ``S·V`` virtual stages
    by looping the ring ``V`` times.  The fill/drain bubble shrinks from
    ``2(S−1)`` model-ticks to ``(2(S−1) + (V−1)S)/V`` — the interleaving
    trade: ~``V``× less bubble for ``V``× the activation stash and ring
    traffic.  ``V = 1`` reduces exactly to :func:`pipeline_train_1f1b`'s
    schedule.

    Args:
      stage_fn: ``stage_fn(chunk_params, mb) -> mb`` — ONE chunk's
        computation (shape-preserving).
      loss_fn: ``loss_fn(loss_params, y, tgt) -> scalar`` on the LAST
        virtual stage's output.
      stage_params: this device's chunk weights with leading axes
        ``(1, V, ...)`` — axis 0 is the sharded pipe axis, axis 1 the
        local chunk axis (global virtual stage ``g = c·S + s``; pack
        with ``blocks.reshape(V, S, ...).swapaxes(0, 1)`` so chunk ``c``
        of device ``s`` holds the right layer slice).
      x / targets: full local batch ``(B, ...)``.
      with_aux / aux_weight: as in :func:`pipeline_train_1f1b` —
        ``stage_fn`` returns ``(mb, aux_scalar)`` per CHUNK; auxes sum
        over all ``S·V`` virtual stages, average over micro-batches,
        and their gradients flow with weight ``aux_weight``.
      edge_plan: as :func:`pipeline_apply` — the interleaved ring's
        wrap-around edges lower through the collective-plan IR.

    Returns ``(loss, stage_grads, loss_grads, dx)`` with the same
    conventions as :func:`pipeline_train_1f1b` (``(loss, aux, ...)``
    with ``with_aux``).
    """
    S = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    M, V = num_microbatches, num_chunks
    is_last_dev = stage == S - 1

    params = jax.tree.map(lambda a: jnp.squeeze(a, axis=0), stage_params)
    pv = jax.tree.leaves(params)[0].shape[0]
    if pv != V:
        raise ValueError(
            f"stage_params chunk axis is {pv}, expected num_chunks={V}")

    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    mbs = x.reshape(M, B // M, *x.shape[1:])
    tgts = targets.reshape(M, B // M, *targets.shape[1:])

    raw_fn = _with_dummy_aux(stage_fn, with_aux)

    T, f_act, f_m, f_c, b_act, b_m, b_c, K = _interleaved_tables(
        int(S), V, M)
    tbl = [jnp.asarray(a) for a in (f_act, f_m, f_c, b_act, b_m, b_c)]
    up_perm = [(i, (i + 1) % S) for i in range(S)]
    down_perm = [((i + 1) % S, i) for i in range(S)]

    def chunk_params(c):
        return jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, c, 0, keepdims=False),
            params)

    def tick(carry, t):
        act, ct, stash, gp, glp, dx_bank, loss_acc, aux_acc = carry
        fa, fm, fc, ba, bm, bc = (a[stage, t] for a in tbl)

        # ---- forward slot ------------------------------------------- #
        recv = _edge_send(act, axis_name, up_perm, 1, True,
                          edge_plan) if S > 1 else act
        inject = (stage == 0) & (fc == 0)
        inp = jnp.where(inject, mbs[fm], recv)
        y, aux_f = raw_fn(chunk_params(fc), inp)
        stash = jnp.where(
            fa,
            lax.dynamic_update_index_in_dim(
                stash, inp[None], fc * K + fm % K, 0),
            stash)
        aux_acc = aux_acc + jnp.where(fa, aux_f, 0.0)

        # ---- backward slot ------------------------------------------ #
        ct_recv = _edge_send(ct, axis_name, down_perm, -1, True,
                             edge_plan) if S > 1 else ct
        inp_b = stash[bc * K + bm % K]
        tgt_b = tgts[bm]
        seed = is_last_dev & (bc == V - 1)

        def composite(p, lp, xin):
            yy, aux = raw_fn(p, xin)
            return yy, loss_fn(lp, yy, tgt_b), aux

        (_, l_b, a_b), vjp = jax.vjp(
            composite, chunk_params(bc), loss_params, inp_b)
        ct_y = jnp.where(seed, jnp.zeros_like(ct_recv), ct_recv)
        ct_l = jnp.where(seed, 1.0, 0.0).astype(l_b.dtype) + l_b * 0
        # every virtual stage seeds its own aux cotangent (see 1F1B);
        # built from the aux primal so dtype and vma match it exactly
        ct_a = jnp.asarray(aux_weight, a_b.dtype) + a_b * 0
        dpc, dlp, dx = vjp((ct_y, ct_l, ct_a))

        gp = jax.tree.map(
            lambda G, d: G.at[bc].add(
                jnp.where(ba, d, jnp.zeros_like(d))), gp, dpc)
        glp = jax.tree.map(
            lambda G, d: G + jnp.where(ba & seed, d, jnp.zeros_like(d)),
            glp, dlp)
        bank = ba & (stage == 0) & (bc == 0)
        dx_bank = jnp.where(
            bank,
            lax.dynamic_update_index_in_dim(dx_bank, dx, bm, 0),
            dx_bank)
        loss_acc = loss_acc + jnp.where(ba & seed, l_b, 0.0)
        return (y, dx, stash, gp, glp, dx_bank, loss_acc, aux_acc), None

    mb0 = pcast(mbs[0] * 0, (axis_name,), to="varying")
    stash0 = jnp.broadcast_to(mb0, (V * K, *mb0.shape)) * 1
    gp0 = jax.tree.map(lambda a: a * 0, params)
    glp0 = jax.tree.map(
        lambda a: pcast(a * 0, (axis_name,), to="varying"), loss_params)
    dx0 = pcast(mbs * 0, (axis_name,), to="varying")
    loss0 = jnp.sum(mb0 * 0, dtype=jnp.float32)

    (_, _, _, gp, glp, dx_bank, loss_acc, aux_acc), _ = lax.scan(
        tick, (mb0, mb0, stash0, gp0, glp0, dx0, loss0, loss0),
        jnp.arange(T))

    loss = lax.psum(loss_acc, axis_name) / M
    glp = jax.tree.map(lambda a: lax.psum(a, axis_name) / M, glp)
    dx = lax.psum(dx_bank, axis_name).reshape(B, *x.shape[1:]) / M
    gp = jax.tree.map(lambda a: a[None] / M, gp)  # restore pipe axis
    if not with_aux:
        return loss, gp, glp, dx
    aux = lax.psum(aux_acc, axis_name) / M
    return loss, aux, gp, glp, dx
