"""Expert parallelism — Switch-style MoE with all-to-all token dispatch.

Absent from the reference; built on the alltoall primitive the reference
exposed as its most general collective (SURVEY.md §2: "EP — alltoall is the
building block").  Shape of the strategy:

- tokens live data-sharded over the ``expert`` mesh axis (the axis does
  double duty: between MoE blocks it is an extra data axis, inside them it
  is the expert home grid — the standard TPU MoE layout);
- a linear router picks top-k experts per token (k=1: Switch; k>1:
  GShard-style with renormalised gates); tokens are packed into
  per-expert capacity slots by a dispatch one-hot, so every shape
  stays static for XLA (dropped overflow tokens pass through as zeros —
  the residual connection carries them, standard Switch semantics);
- ONE ``all_to_all`` ships slots to the experts' home devices, the expert
  FFNs run batched (vmap over local experts → one big MXU matmul), and the
  inverse ``all_to_all`` brings results home to be gate-combined;
- the load-balancing auxiliary loss (fraction·probability product) is
  returned for the trainer to add — ``psum``'d so it is the global value.

:func:`expert_parallel_moe_dropless` is the second dispatch: no capacity
and no ``(N, E, cap)`` tensor.  The (token, choice) rows are sorted by
expert, the experts held here run as grouped products over exactly the
rows routed to them (:func:`grouped_dense`), and no token is dropped
whatever the imbalance.  It can be told that it holds only a contiguous
share of the router's experts: it then routes over all of them and
returns its own experts' part of the result, through a sorted buffer
no larger than the rows it holds need (:func:`_buffer_rungs`).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.ops.grouped_matmul import grouped_matmul
from chainermn_tpu.ops.kernel_common import interpret_kernels
from chainermn_tpu.utils.metrics import get_registry
from chainermn_tpu.utils.telemetry import device_scope

__all__ = [
    "buffer_rows",
    "expert_parallel_moe",
    "expert_parallel_moe_dropless",
    "grouped_dense",
    "route_top_k",
]


def _a2a(v, axis_name: str, split_axis: int, concat_axis: int, plan):
    if plan is None:
        return lax.all_to_all(v, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)
    from chainermn_tpu.ops import plan_ir

    return plan_ir.lower_moe_all_to_all(
        plan_ir.ensure_program(plan, "moe_all_to_all"), v,
        axis_name=axis_name, split_axis=split_axis,
        concat_axis=concat_axis)


def expert_parallel_moe(
    x,
    router_w,
    expert_params,
    expert_fn: Callable,
    *,
    axis_name: str = "expert",
    capacity_factor: float = 1.25,
    top_k: int = 1,
    a2a_plan=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k mixture-of-experts over the ``expert`` mesh axis.
    Call INSIDE ``shard_map``.

    ``top_k=1`` is Switch routing (gate = the raw winning probability);
    ``top_k>1`` is GShard-style: each token visits its k best experts
    and the k gates are renormalised to sum to one.  Later choices
    queue behind earlier ones for capacity slots (rank-0 assignments
    are never dropped in favour of someone's rank-1).

    ``a2a_plan`` (a tuned Plan from
    ``autotune_pattern_plan(pattern="moe_all_to_all")``, its
    ``.program`` dict, or an ``ops.plan_ir.PlanProgram``) lowers BOTH
    all-to-alls through the collective-plan IR — single-shot vs
    axis-split chunked candidates, optional wire dtype with the
    non-float exemption.  The dispatch/combine directions reuse one
    program; the call site supplies each direction's split/concat
    axes.

    Args:
      x: ``(N, D)`` local tokens (flatten batch×seq first).
      router_w: ``(D, E)`` router weights, replicated; ``E`` = global
        expert count = axis size × local experts.
      expert_params: pytree with leading local-expert axis ``E_local``
        (shard the global ``(E, ...)`` stack over ``axis_name``).
      expert_fn: ``expert_fn(params_one_expert, tokens) -> tokens`` — the
        per-expert network, vmapped over local experts here.
      capacity_factor: slots per expert = ``cf · k · N / E`` (rounded up).
      top_k: experts per token (static; 1 ≤ k ≤ E).

    Returns ``(out, aux_loss)``: ``out`` is ``(N, D)`` with overflow
    tokens zeroed; ``aux_loss`` the global Switch balancing loss (scalar).
    """
    S = lax.axis_size(axis_name)
    N, D = x.shape
    E = router_w.shape[-1]
    if E % S:
        raise ValueError(f"{E} experts not divisible by axis size {S}")
    if not 1 <= top_k <= E:
        raise ValueError(f"top_k={top_k} must be in [1, E={E}]")
    e_local = E // S
    cap = max(1, math.ceil(capacity_factor * top_k * N / E))

    # --- route (local, no comm) -------------------------------------- #
    # routing/dispatch bookkeeping is fp32 regardless of compute dtype:
    # a bf16 cumsum counts token queue positions exactly only up to 256,
    # after which capacity slots collide and dispatch silently corrupts
    logits = (x @ router_w).astype(jnp.float32)         # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = lax.top_k(probs, top_k)              # (N, k)
    if top_k == 1:
        gates = top_p                                   # raw Switch gate
    else:
        gates = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    onehots = jax.nn.one_hot(top_i, E, dtype=jnp.float32)   # (N, k, E)

    # position of each assignment within its expert's queue, rank by
    # rank (k is tiny and static — unrolled); drop past capacity.
    # dispatch (0/1) fills slots with raw tokens; combine carries the
    # gate weights for the weighted sum home.
    counts = jnp.zeros((E,), jnp.float32)
    dispatch = jnp.zeros((N, E, cap), jnp.float32)
    combine = jnp.zeros((N, E, cap), jnp.float32)
    for r in range(top_k):
        oh = onehots[:, r]                              # (N, E)
        pos = (jnp.cumsum(oh, axis=0) - 1.0 + counts) * oh
        keep = pos < cap
        slot = jax.nn.one_hot(
            pos.astype(jnp.int32), cap, dtype=jnp.float32)
        d_r = oh[..., None] * slot * keep[..., None]    # (N, E, C)
        dispatch = dispatch + d_r
        combine = combine + d_r * gates[:, r][:, None, None]
        counts = counts + oh.sum(axis=0)
    dispatch = dispatch.astype(x.dtype)
    combine = combine.astype(x.dtype)

    # --- dispatch all-to-all ------------------------------------------ #
    slots = jnp.einsum("nec,nd->ecd", dispatch, x)      # (E, C, D)
    if S > 1:
        # (E, C, D) → (E_local, S·C, D): chunk e-dim to peers, stack their
        # slot blocks — every expert now holds its global token queue
        slots = _a2a(slots, axis_name, 0, 1, a2a_plan)

    # --- expert compute (batched over local experts) ------------------ #
    hidden = jax.vmap(expert_fn)(expert_params, slots)  # (E_local, S·C, D)

    # --- combine all-to-all (inverse) --------------------------------- #
    if S > 1:
        hidden = _a2a(hidden, axis_name, 1, 0, a2a_plan)
    out = jnp.einsum("ecd,nec->nd", hidden, combine)

    # --- Switch load-balancing loss (global) -------------------------- #
    # fractions use the PRIMARY (rank-0) choice only — the Switch
    # definition, which GShard's top-2 aux shares; k=1 is unchanged
    frac_tokens = onehots[:, 0].mean(axis=0)            # (E,)
    frac_probs = probs.mean(axis=0)                     # (E,)
    if S > 1:
        frac_tokens = lax.pmean(frac_tokens, axis_name)
        frac_probs = lax.pmean(frac_probs, axis_name)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return out, aux


# --------------------------------------------------------------------- #
# dropless dispatch
# --------------------------------------------------------------------- #


def route_top_k(x, router_w, top_k: int, score: str = "softmax",
                scale: float = 1.0, bias=None):
    """``(probs, top_i, gates)`` of a linear router, in float32 whatever
    the compute dtype (input, product and score): a choice that flips
    between two near-equal experts moves a whole expert's output, which
    rounding the logits to bf16 does far more often.

    ``score="softmax"``: ``probs`` is the softmax over the experts and
    ``gates`` the winners' probabilities renormalised over the k chosen
    (``top_k == 1`` keeps the raw Switch gate, as the capacity dispatch
    does).  ``score="sigmoid"``: every expert is scored on its own,
    ``s = sigmoid(logits)``; the k largest win, ``gates`` are their
    scores normalised over the k chosen, and ``probs`` is ``s`` over its
    sum across the experts, the distribution the balancing loss needs.
    ``scale`` multiplies the gates (a routed scaling factor).

    ``bias`` ``(E,)``: a selection bias.  The k experts with the largest
    ``score + bias`` win; ``gates`` are the winners' scores WITHOUT it,
    and ``probs`` does not see it either: it steers the choice and not
    the mixture."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if score == "sigmoid":
        s = jax.nn.sigmoid(logits)                      # (N, E)
        top_s, top_i = _top_k_biased(s, top_k, bias)    # (N, k)
        gates = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
        probs = s / jnp.sum(s, axis=-1, keepdims=True)
    elif score == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)         # (N, E)
        top_p, top_i = _top_k_biased(probs, top_k, bias)  # (N, k)
        gates = top_p if top_k == 1 else \
            top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    else:
        raise ValueError(f"router score {score!r} not in (softmax, sigmoid)")
    if scale != 1.0:
        gates = gates * scale
    return probs, top_i, gates


def _top_k_biased(scores, top_k, bias):
    """``(values, indices)`` of the k largest ``scores + bias`` a row;
    the values are the winners' ``scores``."""
    if bias is None:
        return lax.top_k(scores, top_k)
    _, top_i = lax.top_k(scores + bias.astype(scores.dtype), top_k)
    return jnp.take_along_axis(scores, top_i, axis=-1), top_i


def grouped_dense(rows, w, group_sizes):
    """``rows[g's rows] @ w[g]`` for consecutive groups of rows:
    ``rows`` ``(R, K)`` sorted by group, ``w`` ``(G, K, M)``,
    ``group_sizes`` ``(G,)`` int32.  The work is that of the rows
    really there, and rows past the last group come back undefined,
    forward and in the rows' cotangent (see :func:`_experts_of_rows`).

    Traced for a TPU it is this repo's own Pallas kernels
    (``ops/grouped_matmul.py``: tiles read off the shapes, the weights'
    cotangent bounded by the groups and not by a zero cotangent);
    anywhere else ``lax.ragged_dot``, which the TPU compiler would
    lower to a grouped-matmul kernel of its own.  The chip decided
    (PERF.md section 6, PR 47): one Mellum layer's products with the
    SwiGLU between, forward twice (the block's remat) and both backward
    passes, 39.2 ms through ``lax.ragged_dot``, 24.0 with the experts'
    width padded to whole pairs of lane tiles, 13.5 through the
    kernels; the other four cells' layers 2.6 to 5.0 ms for 4.5 to
    10.5, and every one of the five cells' steps faster (Mellum's by
    28 %, the others' by 1 to 6).

    What is done to ``rows`` and to the result around it (a cast, an
    activation, the cotangent's mask) passes over all ``R`` rows, held
    or not: the dropless layer keeps ``R`` near the rows held
    (:func:`_buffer_rungs`)."""
    if interpret_kernels():
        return lax.ragged_dot(rows, w, group_sizes,
                              preferred_element_type=rows.dtype)
    return grouped_matmul(rows, w, group_sizes)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_out(x, order, inv, k):
    """Row ``r`` of the result is token ``order[r] // k``: the (token,
    choice) rows in sorted order.  ``order`` is a permutation of the
    ``N*k`` choices and ``inv`` its inverse, so the transpose is a
    gather too (by ``inv``, then a sum over each token's k choices)
    where AD's own would be a scatter-add of ``N*k`` rows."""
    return x[order // k]


def _rows_out_fwd(x, order, inv, k):
    return x[order // k], inv


def _rows_out_bwd(k, inv, g):
    return g[inv].reshape(-1, k, g.shape[-1]).sum(axis=1), None, None


_rows_out.defvjp(_rows_out_fwd, _rows_out_bwd)


@jax.custom_vjp
def _rows_back(ys, order, inv):
    """The inverse move: sorted rows back to (token, choice) order."""
    return ys[inv]


def _rows_back_fwd(ys, order, inv):
    return ys[inv], order


def _rows_back_bwd(order, g):
    return g[order], None, None


_rows_back.defvjp(_rows_back_fwd, _rows_back_bwd)


# The compact buffer's two moves, each the other's transpose: row r of
# the buffer stands for token tok[r], and a token has as many rows
# there as it chose experts held here (none, often).


def _sum_into(rows, tok, n_tokens: int):
    """``out[t] = sum of rows[r] over the r with tok[r] == t``, ``(n_tokens,
    D)``: a scatter-add of the buffer's rows and no ``(N*k, D)``
    tensor.  Summed in float32 as the full buffer's sum over a token's
    k choices is; AD's transpose is the gather ``g[tok]``."""
    out = jnp.zeros((n_tokens, rows.shape[1]), jnp.float32).at[tok].add(
        rows.astype(jnp.float32))
    return out.astype(rows.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _take(x, tok, n_tokens: int):
    """``x[tok]``, ``x`` ``(n_tokens, D)``: the tokens of the buffer's
    rows, a gather of as many rows as the buffer has.  The transpose is
    :func:`_sum_into` where AD's own would add the cotangent's rows up
    in their own dtype."""
    return x[tok]


def _take_fwd(x, tok, n_tokens):
    return x[tok], tok


def _take_bwd(n_tokens, tok, g):
    return _sum_into(g, tok, n_tokens), None


_take.defvjp(_take_fwd, _take_bwd)


def _sort_by_group(key, n_groups: int):
    """``(order, sizes)``: the stable sort of ``key`` (values in
    ``[0, n_groups]``; ``n_groups`` marks a row of no group, sorted
    last) and each group's row count."""
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    # a compare and a sum: a scatter-add of one a row costs six times
    # as much on the chip (1.18 against 0.21 ms at 131,072 rows and 8
    # groups, PERF.md section 6, PR 41)
    sizes = jnp.sum(
        key[:, None] == jnp.arange(n_groups, dtype=key.dtype)[None, :],
        axis=0, dtype=jnp.int32)
    return order, sizes


def _inverse(order):
    """The inverse of a permutation."""
    return jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))


@jax.custom_vjp
def _no_cotangent_past(rows, n):
    """``rows``, with the cotangent of the rows from ``n`` on zeroed."""
    return rows


def _no_cotangent_past_bwd(n, g):
    return jnp.where(
        jnp.arange(g.shape[0], dtype=jnp.int32)[:, None] < n, g, 0), None


_no_cotangent_past.defvjp(lambda rows, n: (rows, n), _no_cotangent_past_bwd)


def _experts_of_rows(expert_fn, expert_params, rows, sizes):
    """``expert_fn`` over the rows of the groups.  The grouped kernels
    leave the rows past the last group undefined, forward and backward
    alike.  Forward, those rows of the result are undefined too: they
    belong to choices not held here, which the combine leaves out.
    Backward, their cotangent is dropped here, before it reaches the
    tokens."""
    return expert_fn(
        expert_params, _no_cotangent_past(rows, jnp.sum(sizes)), sizes)


def _exchange(rows, sizes, expert_fn, expert_params, axis_name, S, cap):
    """The grouped products of the rows each member sorted for the
    whole group's experts, run where the experts live: one all-to-all
    out (a member's rows for peer ``s`` are consecutive, since experts
    are numbered peer by peer), the counts with them, a local re-sort
    by expert, and the inverse all-to-all home.  Every peer's slot
    holds ``cap`` rows, the most one member can route to one peer, so
    nothing is dropped."""
    R, D = rows.shape
    e_local = sizes.shape[0] // S
    peer_sizes = sizes.reshape(S, e_local)
    peer_rows = peer_sizes.sum(axis=1)
    peer_start = jnp.cumsum(peer_rows) - peer_rows
    j = jnp.arange(cap, dtype=jnp.int32)
    # a slot past the peer's rows repeats some other row: no expert
    # reads it there and no cotangent comes back for it
    send = rows[jnp.clip(peer_start[:, None] + j[None, :], 0, R - 1)]
    recv = lax.all_to_all(send, axis_name, 0, 0, tiled=True)
    recv_sizes = lax.all_to_all(peer_sizes, axis_name, 0, 0, tiled=True)
    # slot j of peer p holds a row of my expert e when j falls in p's
    # e-th run; past p's rows it holds nothing
    ends = jnp.cumsum(recv_sizes, axis=1)               # (S, e_local)
    key = jnp.sum(j[None, :, None] >= ends[:, None, :], axis=-1)
    order, mine = _sort_by_group(key.reshape(-1), e_local)
    inv = _inverse(order)
    ys = _experts_of_rows(
        expert_fn, expert_params, recv.reshape(S * cap, D)[order], mine)
    home = lax.all_to_all(ys[inv].reshape(S, cap, D), axis_name, 0, 0,
                          tiled=True)
    # sorted row r went to peer s_r as its slot r - peer_start[s_r]
    r = jnp.arange(R, dtype=jnp.int32)
    s_r = jnp.sum(r[:, None] >= (peer_start + peer_rows)[None, :], axis=-1)
    s_r = jnp.minimum(s_r, S - 1)
    slot = jnp.clip(r - peer_start[s_r], 0, cap - 1)
    return home.reshape(S * cap, D)[s_r * cap + slot]


# rows of one tile of the grouped kernels: a rung is whole tiles
_ROW_TILE = 128


def _buffer_rungs(n_rows: int, held: int, of: int) -> Tuple[int, ...]:
    """The static sizes the sorted buffer may take, ascending, for
    ``n_rows`` (token, choice) rows of which the ``held`` experts of
    the router's ``of`` get ``n_rows * held / of`` if the router is
    even: twice that, four times that, and ``n_rows`` itself, which
    holds whatever the router does.  Whole tiles, none above ``n_rows``,
    none twice: a member that holds every expert has the one rung.

    Four times is not decoration: the rows held grow 1.6-fold inside a
    run of the Kimi cell and grew 2.4-fold at a learning rate the
    Nemotron cell tried (PERF.md section 6, PRs 32 and 40).  The rule
    is read off the layer's own shapes; if a chip says other multiples
    are better, change it here."""
    rungs = {n_rows}
    for times in (2, 4):
        rows = -(-times * n_rows * held // of)
        rungs.add(min(-(-rows // _ROW_TILE) * _ROW_TILE, n_rows))
    return tuple(sorted(rungs))


def buffer_rows(rows_held, n_rows: int, held: int, of: int):
    """The rows of the smallest rung of :func:`_buffer_rungs` that
    holds ``rows_held`` (an int32 array of any shape, on the device or
    not): the buffer the layer works on at that count."""
    rungs = _buffer_rungs(n_rows, held, of)
    return jnp.asarray(rungs, jnp.int32)[_rung_of(rows_held, rungs)]


def _rung_of(rows_held, rungs):
    return sum(((rows_held > r).astype(jnp.int32) for r in rungs[:-1]),
               jnp.zeros_like(rows_held, jnp.int32))


def _held_part(experts, k: int, C: int, x, expert_params, gates, order,
               sizes, held):
    """The held experts' part of the layer's result ``(N, D)`` through
    a sorted buffer of ``C`` rows, ``C >= sum(sizes)``: the held rows
    are the first of the sorted order, so ``order[:C]`` names them all
    and the rest of the buffer is rows of choices not held, which the
    grouped kernels leave undefined and the sum home leaves out.
    ``C == N*k`` is the whole order: two gathers, each the other's
    inverse (the only rung that needs the order's).  A smaller ``C``
    takes ``C`` rows and adds ``C`` rows home, and no tensor in it has
    ``N*k`` rows."""
    N, D = x.shape
    n = jnp.sum(sizes)
    whole = C == N * k
    with device_scope("moe/route"):
        if whole:
            inv = _inverse(order)
            rows = _rows_out(x, order, inv, k)          # (N*k, D)
        else:
            first = order[:C]
            tok = first // k
            rows = _take(x, tok, N)                     # (C, D)
    with device_scope("moe/experts"):
        ys = experts(expert_params, rows, sizes)
    with device_scope("moe/combine"):
        # a where, not a product by a zero gate: the rows of choices
        # not held here are whatever the grouped kernels left there
        if whole:
            ys = _rows_back(ys, order, inv).reshape(N, k, D)
            return jnp.sum(jnp.where(held.reshape(N, k, 1), ys, 0)
                           * gates[..., None].astype(ys.dtype), axis=1)
        here = jnp.arange(C, dtype=jnp.int32) < n
        gate = jnp.where(here, gates.reshape(-1)[first], 0)
        return _sum_into(jnp.where(here[:, None], ys, 0)
                         * gate[:, None].astype(ys.dtype), tok, N)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _laddered(part, rungs, rung, x, expert_params, gates, *counts):
    """``part(rungs[rung], x, expert_params, gates, *counts)``, the rung
    chosen on the device.  One ``custom_vjp`` around the switch: AD's
    own partial evaluation of a conditional has EVERY branch return
    every branch's residuals, zero-filled where not taken, so a small
    rung would write and hold the full rung's.  Here the residuals are
    the arguments, the same at every rung, and the backward pass
    switches on the saved rung and differentiates that rung's function
    inside its branch."""
    return lax.switch(rung, [partial(part, C) for C in rungs],
                      x, expert_params, gates, *counts)


def _laddered_fwd(part, rungs, rung, x, expert_params, gates, *counts):
    out = _laddered(part, rungs, rung, x, expert_params, gates, *counts)
    return out, (rung, x, expert_params, gates, counts)


def _laddered_bwd(part, rungs, res, g):
    rung, x, expert_params, gates, counts = res

    def pull(C, x, expert_params, gates, g):
        return jax.vjp(lambda *diff: part(C, *diff, *counts),
                       x, expert_params, gates)[1](g)

    grads = lax.switch(rung, [partial(pull, C) for C in rungs],
                       x, expert_params, gates, g)
    # the compiler otherwise moves what consumes the branches' results
    # into them: each layer's conditional then returns the gradient of
    # the whole STACK of layers, all but its own slice zeros, and a
    # step holds as many stacks as it has layers (read in the Mellum
    # cell's compiled step: 13.28 -> 16.01 GiB without this line)
    grads = jax.tree.map(lax.optimization_barrier, grads)
    return (None, *grads, *(None for _ in counts))


_laddered.defvjp(_laddered_fwd, _laddered_bwd)


def expert_parallel_moe_dropless(
    x,
    router_w,
    expert_params,
    expert_fn: Callable,
    *,
    top_k: int,
    first_expert: int = 0,
    score: str = "softmax",
    scale: float = 1.0,
    bias=None,
    axis_name: str = "expert",
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k mixture of experts without capacity: every (token, choice)
    whose expert is held by this ``axis_name`` group is computed.
    Call INSIDE ``shard_map``.

    The router has ``E = router_w.shape[-1]`` outputs and a token's k
    gates are normalised over its k choices among all ``E``.  The group
    holds the ``G`` consecutive experts from ``first_expert`` (``G`` the
    leading axis of ``expert_params`` times the axis size); with
    ``G < E`` the result is the held experts' part of the layer's
    output, and what the absent experts would have added is left out.
    Shapes are static: the sorted buffer has the rows of one rung of a
    short ladder (:func:`_buffer_rungs`: twice and four times the rows
    an even router would send here, and all ``N*k``, the most that can
    be routed here), the smallest that holds the rows this step routed
    here, chosen on the device; moves, grouped products and the sum
    home work on that many rows.  A group that holds every expert has
    the one rung and no conditional.  With an axis of size ``S > 1`` member
    ``r`` holds experts ``first_expert + [r*G/S, (r+1)*G/S)`` and rows
    travel by all-to-all.

    Args:
      x: ``(N, D)`` local tokens.
      router_w: ``(D, E)`` router weights, replicated.
      expert_params: pytree with leading local-expert axis ``G/S``.
      expert_fn: ``expert_fn(params, rows, group_sizes) -> rows``: the
        experts' network over rows sorted by local expert
        (:func:`grouped_dense` products).
      top_k: experts per token (static; 1 <= k <= E).
      score, scale, bias: the router's score function, the factor its
        gates carry and its selection bias (:func:`route_top_k`).

    Returns ``(out, aux, chosen)``: ``out`` ``(N, D)``; ``aux`` the
    global balancing loss ``E * sum_e f_e * P_e`` over all ``E`` columns
    (f from the first choice); ``chosen`` ``(N, k)`` int32, the experts
    each local token chose, held here or not.
    """
    S = lax.axis_size(axis_name)
    N, D = x.shape
    E = router_w.shape[-1]
    G = jax.tree.leaves(expert_params)[0].shape[0] * S
    if not 1 <= top_k <= E:
        raise ValueError(f"top_k={top_k} must be in [1, E={E}]")
    if not 0 <= first_expert <= E - G:
        raise ValueError(
            f"experts [{first_expert}, {first_expert + G}) held, of {E}")

    with device_scope("moe/route"):
        probs, top_i, gates = route_top_k(
            x, router_w, top_k, score, scale, bias)
        choice = top_i.reshape(-1) - first_expert       # (N*k,)
        held = (choice >= 0) & (choice < G)
        order, sizes = _sort_by_group(jnp.where(held, choice, G), G)

    if S == 1:
        experts = partial(_experts_of_rows, expert_fn)
    else:
        def experts(expert_params, rows, sizes):
            return _exchange(rows, sizes, expert_fn, expert_params,
                             axis_name, S, N * min(top_k, G // S))
    part = partial(_held_part, experts, top_k)
    rungs = _buffer_rungs(N * top_k, G, E)
    get_registry().inc("moe/buffer_rungs", len(rungs))
    if len(rungs) == 1:
        out = part(rungs[0], x, expert_params, gates, order, sizes, held)
    else:
        rung = _rung_of(jnp.sum(sizes), rungs)
        if S > 1:
            # the branches exchange rows: one rung for the whole group
            rung = lax.pmax(rung, axis_name)
        out = _laddered(part, rungs, rung, x, expert_params, gates, order,
                        sizes, held)

    frac_tokens = jax.nn.one_hot(top_i[:, 0], E, dtype=jnp.float32).mean(0)
    frac_probs = probs.mean(axis=0)
    if S > 1:
        frac_tokens = lax.pmean(frac_tokens, axis_name)
        frac_probs = lax.pmean(frac_probs, axis_name)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return out, aux, top_i
