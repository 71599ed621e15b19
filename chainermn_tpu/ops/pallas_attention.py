"""Pallas flash attention — the hot-op TPU kernel.

The reference's only hand-written device code was CuPy pack/unpack
kernels (``_memory_utility.py``); XLA makes those unnecessary (SURVEY §2
native inventory), so the Pallas budget goes where the FLOPs are:
attention.  This kernel backs the flagship transformer's
``attention="flash"`` path and the per-block math of
:func:`chainermn_tpu.parallel.ring_attention.ring_attention`
(``use_flash=True``).

Design (flash-attention v2 schedule, TPU-shaped):

- 3-D grid ``(B·H, T_q/block_q, width)`` with the K dimension
  innermost and ``arbitrary`` semantics: the Pallas pipeline
  double-buffers each K/V block's HBM→VMEM DMA behind the previous
  block's math, and only ``block_k`` tokens of K/V ever sit in VMEM (so
  context length is bounded by HBM, not the 16 MB of VMEM);
- **the grid visits the block pairs the mask needs**
  (:func:`_visit_plan`, one function for the forward kernel and the
  backward's): for a query block the needed key blocks are one
  contiguous run.  With a ``window`` the innermost extent ``width`` is
  the band's width in
  blocks (2 of 8 at 8,192 tokens, 1,024-wide blocks and a window of
  512 or 1,024) and step ``s`` reads block ``first + s``; without one
  it stays ``T_k/block_k`` and the K/V index map holds at the run's
  last block, so the steps past the diagonal issue no copy.  The
  ``pl.when`` predicate is still the guard, on the unclamped block
  index: a step held at an edge computes nothing;
- **online softmax** in fp32 VMEM scratch (running max ``m``,
  normaliser ``l``, accumulator) — no (T, T) score matrix in HBM;
- matmuls via ``jnp.dot(..., preferred_element_type=float32)`` so bf16
  inputs hit the MXU at full rate with fp32 accumulation;
- the value width may differ from the key width (latent attention:
  keys of 128 + 64 channels, values of 128): q and k are ``(…, D)``,
  v, o and their cotangents ``(…, Dv)``, the scale comes from ``D``,
  and every block, accumulator and output takes its own tensor's
  width — V is not padded to ``D`` and q/k are not split;
- causal masking in *global* positions: ``q_offset``/``k_offset`` are
  the scalar-prefetch operand (SMEM, read by the index maps and the
  kernels), so they may be **traced values** (ring attention's
  rotating block offsets): the band's width does not depend on them,
  only its place, and traced offsets get the widest band a window can
  touch (3 blocks above) where Python ints get the exact one;
- optionally returns the softmax log-sum-exp, with its own VJP path, so
  sequence-sharded callers can combine per-shard partial attentions
  exactly (``o = Σ o_i·exp(lse_i − lse)``);
- backward = ONE recompute kernel off the saved lse (:func:`_bwd_kernel`:
  key blocks outer, the needed query blocks inner): P, dP and dS once a
  block pair and five products, dk and dv accumulated over a key block's
  steps and dq in a float32 VMEM accumulator that spans the query length
  for the whole ``B·H`` row, so the backward's HBM stays O(T) and its
  query length is bounded by VMEM (:func:`_bwd_vmem_bytes` derives the
  limit the kernel asks the compiler for, and refuses a length past the
  budget by name);
- ``interpret=True`` runs the identical kernels on CPU (how the test
  suite exercises them on the virtual pod).
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.ops.kernel_common import (
    LANE as _LANE,
    VMEM_BUDGET as _VMEM_BUDGET,
    interpret_kernels,
    tracing_for_mesh,
)
from chainermn_tpu.utils.metrics import get_registry
from chainermn_tpu.utils.telemetry import device_scope

__all__ = ["flash_attention", "flash_attention_supported",
           "interpret_kernels", "tracing_for_mesh", "FLASH_RESIDUAL_NAMES"]

# The names on the forward kernel's two residual outputs (see
# ``_flash_fwd``): a ``jax.checkpoint`` whose policy saves them keeps
# the kernel out of its backward recompute.
FLASH_RESIDUAL_NAMES = ("flash_out", "flash_lse")

_NEG = -1e30


def _bcast(vec, n=_LANE):
    return jnp.broadcast_to(vec[:, None], (vec.shape[0], n))


def _positions(off, base, count):
    return off + base + jax.lax.broadcasted_iota(
        jnp.int32, (count, 1), 0)[:, 0]


# --------------------------------------------------------------------- #
# which block pairs the mask needs
# --------------------------------------------------------------------- #


def _block_needed(i, j, q_off, k_off, Bq, Bk, causal, window):
    """Does (q block ``i``, k block ``j``) hold an allowed (query, key)
    position?  Python ints or traced scalars."""
    if not causal:
        # a tautology that must stay TRACED in the kernels: an
        # unconditioned kernel body trips the hlo-interpreter's vma
        # check under shard_map (jax bug); pl.when(cond) routes
        # discharge safely.
        return j >= 0
    # K blocks entirely in this q block's future contribute nothing
    needed = q_off + (i + 1) * Bq - 1 >= k_off + j * Bk
    if window is not None:
        # nor do K blocks entirely BEFORE the window of every q row
        needed &= k_off + (j + 1) * Bk - 1 >= q_off + i * Bq - (window - 1)
    return needed


class _VisitPlan(NamedTuple):
    """The block pairs one kernel's grid visits, derived from the mask.

    The grid is ``(B·H, n_outer, width)``: for outer block ``o`` the
    needed inner blocks are the contiguous run ``[lo, hi] = run(o,
    q_off, k_off)`` (unclamped: it may leave ``[0, n_inner)`` at the
    sequence's edges), and step ``s`` stands for the inner block
    ``first(o) + s`` — see :meth:`inner`.  ``pairs_computed`` (steps
    whose in-kernel predicate holds) and ``pairs_needed`` (block pairs
    with an allowed position) are per head, and ``None`` where the
    offsets are traced."""
    outer: str            # "q": key blocks innermost; "k": the backward's
    n_outer: int
    n_inner: int
    width: int
    narrow: bool          # width < n_inner: steps start at the band
    run: Callable
    pairs_computed: Optional[int]
    pairs_needed: Optional[int]

    @property
    def steps(self) -> int:
        return self.n_outer * self.width

    def inner(self, o, s, q_off, k_off):
        """``(u, blk)`` of grid step ``(o, s)``: the inner block index
        the step stands for, and the block its operands are copied
        from.  ``u`` is what the kernel's predicate and positions use;
        ``blk`` is ``u`` held inside the run and the tensor, so a step
        with nothing to compute maps to the block beside it and the
        pipeline issues no copy for it."""
        lo, hi = self.run(o, q_off, k_off)
        u = (s + jnp.maximum(lo, 0)) if self.narrow else s
        return u, jnp.clip(jnp.clip(u, lo, hi), 0, self.n_inner - 1)


def _visit_plan(T_q, T_k, block_q, block_k, causal, window,
                offsets=None, outer="q") -> _VisitPlan:
    """The plan of the forward grid (``outer="q"``: key blocks
    innermost) or of the backward's (``outer="k"``).  ``offsets`` is
    ``(q_offset, k_offset)`` as Python ints, or ``None`` where they are
    traced: the band's width does not depend on them, only its place,
    so the extent is then the most blocks a band can touch wherever it
    sits."""
    nq, nk = T_q // block_q, T_k // block_k
    n_outer, n_inner = (nq, nk) if outer == "q" else (nk, nq)
    b_outer, b_inner = ((block_q, block_k) if outer == "q"
                        else (block_k, block_q))
    reach = None if window is None else window - 1

    def run(o, q_off, k_off):
        # first and last position, relative to the inner tensor's
        # start, that any row of outer block ``o`` is allowed to meet
        if not causal:
            return 0, n_inner - 1
        if outer == "q":
            last = q_off - k_off + (o + 1) * block_q - 1
            lo = 0 if reach is None else (
                q_off - k_off + o * block_q - reach) // block_k
            return lo, last // block_k
        first = k_off - q_off + o * block_k
        hi = n_inner - 1 if reach is None else (
            k_off - q_off + (o + 1) * block_k - 1 + reach) // block_q
        return first // block_q, hi

    held = None             # each run inside the tensor, static offsets
    if offsets is not None:
        held = [(max(lo, 0), min(hi, n_inner - 1)) for lo, hi in
                (run(o, *offsets) for o in range(n_outer))]
    width = n_inner
    if reach is not None:
        if held is None:
            width = -(-(b_outer + reach - 1) // b_inner) + 1
        else:
            width = max(1, max(hi - lo + 1 for lo, hi in held))
        width = min(width, n_inner)
    narrow = width < n_inner
    computed = needed = None
    if held is not None:
        needed = sum(max(0, hi - lo + 1) for lo, hi in held)
        # outer block o's steps stand for first .. first + width - 1
        computed = sum(
            max(0, min(hi, first + width - 1) - max(lo, first) + 1)
            for lo, hi in held for first in [lo if narrow else 0])
    return _VisitPlan(outer, n_outer, n_inner, width, narrow, run,
                      computed, needed)


def _count_visits(plan):
    """``flash/grid_steps`` and ``flash/pairs_computed`` (a head, added
    once for every kernel call site as it is traced): how often the
    grid engages — 16 steps for 15 computed pairs where a windowed
    kernel at 8 x 8 blocks once took 64."""
    reg = get_registry()
    reg.inc("flash/grid_steps", plan.steps)
    if plan.pairs_computed is not None:
        reg.inc("flash/pairs_computed", plan.pairs_computed)


# --------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------- #


def _step(plan, offs_ref, Bq, Bk, causal, window):
    """What grid step ``(o, s)`` stands for: ``(first, last, i, j,
    q_off, k_off, needed)``.  ``first``/``last`` mark the steps that
    initialise and write the outer block's output (every outer block
    has both, needed pairs or none); the inner index is the UNCLAMPED
    one, so a step whose operands were held at the run's or the
    tensor's edge computes nothing and no pair is counted twice."""
    o, s = pl.program_id(1), pl.program_id(2)
    q_off, k_off = offs_ref[0], offs_ref[1]
    u, _ = plan.inner(o, s, q_off, k_off)
    i, j = (o, u) if plan.outer == "q" else (u, o)
    needed = _block_needed(i, j, q_off, k_off, Bq, Bk, causal, window)
    if plan.narrow:
        needed &= u < plan.n_inner
    return s == 0, s == plan.width - 1, i, j, q_off, k_off, needed


def _fwd_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, l_ref, m_ref, *, scale, causal, window, plan):
    Bq, D = q_ref.shape[1:]
    Bk = k_ref.shape[1]
    first, last, i, j, q_off, k_off, needed = _step(
        plan, offs_ref, Bq, Bk, causal, window)

    @pl.when(first)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        l_ref[...] = jnp.zeros_like(l_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)

    @pl.when(needed)
    def _():
        # dots take the refs' NATIVE dtype (bf16 in production) with
        # fp32 accumulation — casting operands to fp32 first would run
        # every matmul at the MXU's fp32 rate, ~4x slower (measured:
        # the whole train-step attention share dropped ~2x when these
        # casts were removed); softmax statistics stay fp32 throughout
        q = q_ref[0]
        kb = k_ref[0]
        vb = v_ref[0]
        s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32) * scale
        allow = None
        if causal:
            qpos = _positions(q_off, i * Bq, Bq)
            kpos = _positions(k_off, j * Bk, Bk)
            allow = qpos[:, None] >= kpos[None, :]
            if window is not None:
                allow &= (qpos[:, None] - kpos[None, :]) < window
            s = jnp.where(allow, s, _NEG)
        m = m_ref[:, 0]
        l = l_ref[:, 0]
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        if allow is not None:
            # explicit zero: for a fully-masked row m_new == _NEG and
            # exp(s - m_new) == 1, which would silently average this
            # block's V rows into the output
            p = jnp.where(allow, p, 0.0)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p.astype(vb.dtype), vb, preferred_element_type=jnp.float32)
        l_ref[...] = _bcast(l * alpha + p.sum(axis=-1))
        m_ref[...] = _bcast(m_new)

    @pl.when(last)
    def _():
        l = l_ref[:, 0]
        safe = jnp.maximum(l, 1e-30)   # fully-masked rows stay finite
        o_ref[0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)
        lse_ref[0] = _bcast(m_ref[:, 0] + jnp.log(safe))


# --------------------------------------------------------------------- #
# backward (recompute off the saved lse, flash style)
# --------------------------------------------------------------------- #


def _recompute_p(q, kb, scale, lse, causal, window, q_off, k_off, i, j,
                 Bq, Bk):
    s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = _positions(q_off, i * Bq, Bq)
        kpos = _positions(k_off, j * Bk, Bk)
        allow = qpos[:, None] >= kpos[None, :]
        if window is not None:
            allow &= (qpos[:, None] - kpos[None, :]) < window
        s = jnp.where(allow, s, _NEG)
        return jnp.where(allow, jnp.exp(s - lse[:, None]), 0.0)
    return jnp.exp(s - lse[:, None])


def _bwd_kernel(offs_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *, scale,
                causal, window, plan):
    """dq, dk and dv from one walk of the block pairs (k outer, q
    inner): P, dP and dS once a pair, five products.  ``dk_acc`` and
    ``dv_acc`` live for one key block; ``dq_acc`` holds the WHOLE query
    length in float32, ``(T_q / Bq, Bq, D)``, across the key blocks of
    one ``B·H`` row: zeroed at the row's first step, cast and written
    at its last (the two inner grid axes are ``arbitrary``)."""
    Bk = k_ref.shape[1]
    Bq = q_ref.shape[1]
    first, last, i, j, q_off, k_off, needed = _step(   # k outer, q inner
        plan, offs_ref, Bq, Bk, causal, window)

    @pl.when(first & (j == 0))
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(first)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(needed)
    def _():
        # native-dtype (bf16) dot operands, fp32 accumulation — see the
        # forward kernel's note; p/ds cast to the wire dtype for the MXU
        # (the standard flash-v2 backward numerics)
        kb = k_ref[0]
        vb = v_ref[0]
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0]
        delta = delta_ref[0][:, 0]
        p = _recompute_p(q, kb, scale, lse, causal, window, q_off, k_off,
                         i, j, Bq, Bk)                   # (Bq, Bk)
        dv_acc[...] += jnp.dot(p.astype(do.dtype).T, do,
                               preferred_element_type=jnp.float32)
        dp = jnp.dot(do, vb.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None]) * scale).astype(q.dtype)
        dk_acc[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        dq_acc[i] += jnp.dot(ds, kb, preferred_element_type=jnp.float32)

    @pl.when(last)
    def _():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(last & (j == plan.n_outer - 1))
    def _():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


# --------------------------------------------------------------------- #
# pallas_call plumbing
# --------------------------------------------------------------------- #


def _outer_spec(block, width):
    """Blocks that follow the grid's outer axis (the output's side)."""
    return pl.BlockSpec((1, block, width), lambda b, o, s, offs: (b, o, 0))


def _inner_spec(plan, block, width):
    """Blocks that follow ``plan``'s walk of the inner axis; the offsets
    are the scalar-prefetch operand, so the map may read them."""
    return pl.BlockSpec(
        (1, block, width),
        lambda b, o, s, offs: (b, plan.inner(o, s, offs[0], offs[1])[1], 0))


def _sds(shape, dtype, like):
    """ShapeDtypeStruct inheriting ``like``'s varying-mesh-axes set, so the
    kernel composes under shard_map's check_vma discipline."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _call(kernel, plan, BH, in_specs, out_specs, out_shape, scratch_shapes,
          interpret, outer_semantics="parallel", vmem_limit_bytes=None):
    """One kernel over ``plan``'s grid, the offsets prefetched to SMEM
    ahead of the index maps.  The kernel wears ``attn.core`` (forward
    and backward alike); the relayouts and the backward's ``delta``
    around it do not."""
    _count_visits(plan)
    kernel = pl.pallas_call(
        functools.partial(kernel, plan=plan),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, plan.n_outer, plan.width),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", outer_semantics, "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret)

    def scoped(*operands):
        with device_scope("attn.core"):
            return kernel(*operands)

    return scoped


def _fwd(q3, k3, v3, offs, static_offs, scale, causal, window, block_q,
         block_k, interpret):
    BH, Tq, D = q3.shape
    Tk, Dv = v3.shape[1:]
    plan = _visit_plan(Tq, Tk, block_q, block_k, causal, window,
                       static_offs)
    q_spec, k_spec = _outer_spec(block_q, D), _inner_spec(plan, block_k, D)
    o, lse = _call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          window=window),
        plan, BH,
        in_specs=[q_spec, k_spec, _inner_spec(plan, block_k, Dv)],
        out_specs=[_outer_spec(block_q, Dv), _outer_spec(block_q, _LANE)],
        out_shape=[
            _sds((BH, Tq, Dv), q3.dtype, q3),
            _sds((BH, Tq, _LANE), jnp.float32, q3),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
        ],
        interpret=interpret,
    )(offs, q3, k3, v3)
    return o, lse[..., 0]


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12))
def _flash(q3, k3, v3, offs, static_offs, scale, causal, window, block_q,
           block_k, bwd_block_q, bwd_block_k, interpret):
    return _fwd(q3, k3, v3, offs, static_offs, scale, causal, window,
                block_q, block_k, interpret)


def _flash_fwd(q3, k3, v3, offs, static_offs, scale, causal, window,
               block_q, block_k, bwd_block_q, bwd_block_k, interpret):
    o, lse = _fwd(q3, k3, v3, offs, static_offs, scale, causal, window,
                  block_q, block_k, interpret)
    # named so that an enclosing jax.checkpoint can keep them: they are
    # the only residuals the forward kernel produces, and a policy that
    # saves both (TransformerConfig.checkpoint_fn) leaves the backward
    # pass with its one kernel alone.  The name sits on the (BH, Tq)
    # slice of lse, not on the kernel's 128-lane copy.  Inert under any other
    # policy, plain jax.checkpoint and no checkpoint at all.
    o = checkpoint_name(o, FLASH_RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, FLASH_RESIDUAL_NAMES[1])
    return (o, lse), (q3, k3, v3, offs, o, lse)


def _bwd_vmem_bytes(Tq, D, Dv, block_q, block_k, dtype):
    """The VMEM the backward kernel needs, from its shapes alone, and so
    the limit it asks the compiler for (``vmem_limit_bytes``)."""
    lanes = lambda width: -(-width // _LANE) * _LANE
    d, dv, wire = lanes(D), lanes(Dv), jnp.dtype(dtype).itemsize
    # dq over the whole query length: the accumulator, the out block twice
    held = Tq * d * (4 + 2 * wire)
    # q, do in; k, v in; dk, dv out: double-buffered.  lse and delta at
    # 128 lanes of float32.  dk's and dv's accumulators.
    tiles = 2 * wire * (block_q + 2 * block_k) * (d + dv)
    tiles += 2 * 2 * block_q * _LANE * 4 + block_k * (d + dv) * 4
    # s / P, dP, dS in float32; P and dS again on the wire
    temps = block_q * block_k * (3 * 4 + 2 * wire)
    need = held + tiles + temps
    if need > _VMEM_BUDGET:
        raise ValueError(
            f"flash backward: dq's accumulator over {Tq} queries of width "
            f"{D} and the kernel's tiles need {need / 2**20:.0f} MiB of "
            f"VMEM, over the {_VMEM_BUDGET // 2**20} a kernel may ask for; "
            "shard the sequence (attention=\"ring\") or use "
            "local_attention")
    return need


def _flash_bwd(static_offs, scale, causal, window, fwd_block_q,
               fwd_block_k, block_q, block_k, interpret, res, cts):
    # the backward kernel tiles on its OWN block sizes (a retune is
    # read in the OPT cells' flash.ms_per_step); the fwd blocks arrive
    # first in the nondiff tuple and are unused here
    q3, k3, v3, offs, o, lse = res
    do, dlse = cts
    BH, Tq, D = q3.shape
    Tk, Dv = v3.shape[1:]
    # d s_ij = p_ij (dp_ij − delta_i) from o's cotangent, plus p_ij·dlse_i
    # from lse's — both fold into one "delta_eff = delta − dlse" term.
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # (BH,Tq)
    delta = delta - dlse.astype(jnp.float32)
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (_LANE,))
    lse3 = jnp.broadcast_to(lse[..., None], lse.shape + (_LANE,))
    get_registry().inc("flash/backward_fused_sites")

    # k outer / q inner grid; dq's accumulator spans the query length
    plan = _visit_plan(Tq, Tk, block_q, block_k, causal, window,
                       static_offs, outer="k")
    nq = Tq // block_q
    k_spec, q_spec = _outer_spec(block_k, D), _inner_spec(plan, block_q, D)
    v_spec = _outer_spec(block_k, Dv)
    qvec_spec = _inner_spec(plan, block_q, _LANE)
    dq, dk, dv = _call(
        functools.partial(_bwd_kernel, scale=scale, causal=causal,
                          window=window),
        plan, BH,
        in_specs=[q_spec, k_spec, v_spec, _inner_spec(plan, block_q, Dv),
                  qvec_spec, qvec_spec],
        out_specs=[
            pl.BlockSpec((1, nq, block_q, D),
                         lambda b, o, s, offs: (b, 0, 0, 0)),
            k_spec, v_spec],
        out_shape=[
            _sds((BH, nq, block_q, D), q3.dtype, q3),
            _sds((BH, Tk, D), k3.dtype, k3),
            _sds((BH, Tk, Dv), v3.dtype, v3),
        ],
        scratch_shapes=[
            pltpu.VMEM((nq, block_q, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, Dv), jnp.float32),
        ],
        interpret=interpret, outer_semantics="arbitrary",
        vmem_limit_bytes=_bwd_vmem_bytes(Tq, D, Dv, block_q, block_k,
                                         q3.dtype),
    )(offs, q3, k3, v3, do, lse3, delta)
    d_offs = jnp.zeros(offs.shape, jax.dtypes.float0)
    return dq.reshape(BH, Tq, D), dk, dv, d_offs


_flash.defvjp(_flash_fwd, _flash_bwd)


# The backward kernel's blocks under a window.  Without one the forward's
# 1,024 x 1,024 is the fastest tiling the chip has shown at every head
# width the cells run, 256 + 256 included; a band no wider than 1,024
# crosses a key block's 1,024 queries in two half-masked blocks, and at
# 512 x 512 the kernel computes half (a window of 512) or three quarters
# (1,024) of that.  Read on the chip, the backward of one layer at 2 x
# 8,192 tokens and 128-wide heads (PR 44): window 512 and 64 heads 21.1
# -> 15.3 ms, window 1,024 and 32 heads 10.6 -> 10.0; blocks of 2,048
# or 256 lose everywhere.  A caller's own tiling stands.
_BAND_BWD_BLOCK = 512


def _fit_block(T: int, want: int) -> Optional[int]:
    """Pick the block size for a length-``T`` axis given requested size
    ``want``; ``None`` means "not worth the kernel — fall back to XLA".

    - ``T`` must be sublane-aligned (multiple of 8, the fp32 min tile);
    - ``T <= want``: the whole axis is one block;
    - otherwise: the largest power-of-two block <= ``want`` that tiles
      ``T``.  The search floor is 128 — or ``want`` rounded down to a
      power of two, when the caller explicitly requests smaller blocks —
      because blocks below ~128 rows leave the MXU mostly idle, at which
      point the XLA fallback beats a degenerate kernel launch (so e.g.
      T=1032, 8-aligned but only tileable by 8, reports unsupported).
    """
    if T % 8:
        return None
    want = min(want, T)
    if T <= want:
        return T
    b = 1 << (want.bit_length() - 1)   # round down to a power of two
    floor = min(128, b)                # honor explicitly-small requests
    while b >= floor:
        if T % b == 0:
            return b
        b //= 2
    return None


def flash_attention_supported(T_q: int, T_k: int, block_q: int = 1024,
                              block_k: int = 1024) -> bool:
    """Shapes the kernel handles (callers fall back to XLA otherwise):
    8-aligned lengths that are either a single block or tileable by a
    power-of-two block no smaller than 128 (see :func:`_fit_block`)."""
    return (_fit_block(T_q, block_q) is not None
            and _fit_block(T_k, block_k) is not None)


def flash_attention(q, k, v, *, causal: bool = False, window=None,
                    q_offset=0,
                    k_offset=0, block_q: int = 1024, block_k: int = 1024,
                    bwd_block_q: Optional[int] = None,
                    bwd_block_k: Optional[int] = None,
                    return_lse: bool = False, interpret: bool = False):
    """Flash attention over ``(B, T, H, D)`` tensors; ``v`` may be
    ``(B, T, H, Dv)`` with a width of its own, and the output then has
    that width (the scale is ``D ** -0.5``, the key width's).

    ``q_offset``/``k_offset`` are *global* position offsets of the local
    blocks for sequence-sharded callers — python ints or traced int
    scalars (they ride to the kernel and its index maps in SMEM; python
    ints also size a windowed grid exactly); masking follows global
    positions exactly like
    :func:`...parallel.ring_attention.local_attention`, with one
    deliberate divergence: a query row whose ENTIRE K range is masked
    (when ``k_offset > q_offset``, or with ``window`` when the K range
    lies entirely before the row's window) returns **zeros** and an
    lse of ≈``-1e30``, where the XLA oracle returns the meaningless
    uniform-softmax mean of V.  Zeros/-inf are the correct identities for
    callers that combine per-shard partials via lse.

    With ``return_lse=True`` returns ``(out, lse)`` where ``lse`` is
    ``(B, T, H)`` fp32 — both outputs are differentiable.

    ``bwd_block_q``/``bwd_block_k`` tile the backward kernel (its query
    and key blocks) independently of the forward (default: the forward
    blocks, and at most ``_BAND_BWD_BLOCK`` under a window no wider
    than two of those); gradients are exact for any valid tiling (the
    OPT cells' ``flash_roofline`` reads a retune).
    """
    B, Tq, H, D = q.shape
    Tk, Dv = k.shape[1], v.shape[-1]
    if k.shape[-1] != D:
        raise ValueError(f"q and k widths differ: {D} and {k.shape[-1]}")
    if window is not None and not causal:
        raise ValueError("window requires causal=True (sliding causal "
                         "window attention)")
    if window is not None and window < 1:
        raise ValueError(f"window {window} must be >= 1")
    bq, bk = _fit_block(Tq, block_q), _fit_block(Tk, block_k)
    if bq is None or bk is None:
        raise ValueError(
            f"sequence lengths ({Tq}, {Tk}) unsupported: lengths must be "
            "multiples of 8 and either fit in one block or be tileable "
            "by a power-of-two block >= 128 — gate on "
            "flash_attention_supported() and fall back to "
            "local_attention")
    if not (bwd_block_q or bwd_block_k) and window is not None \
            and window <= 2 * _BAND_BWD_BLOCK:
        bwd_block_q = min(block_q, _BAND_BWD_BLOCK)
        bwd_block_k = min(block_k, _BAND_BWD_BLOCK)
    # a bwd override that doesn't tile THIS shape falls back to the
    # forward blocks rather than erroring: the knob is a perf hint
    # (often adopted from a sweep at another sequence length) and must
    # never turn a supported shape into a trace-time failure
    bwd_bq = (_fit_block(Tq, bwd_block_q) or bq) if bwd_block_q else bq
    bwd_bk = (_fit_block(Tk, bwd_block_k) or bk) if bwd_block_k else bk
    block_q, block_k = bq, bk
    offs = jnp.asarray(
        jnp.stack([jnp.asarray(q_offset, jnp.int32),
                   jnp.asarray(k_offset, jnp.int32)]))
    # offsets known as the program is traced place the band exactly;
    # traced ones (the ring's pairs) leave it room to sit anywhere
    static_offs = None
    if all(isinstance(x, (int, np.integer)) for x in (q_offset, k_offset)):
        static_offs = (int(q_offset), int(k_offset))
    to3 = lambda x: x.transpose(0, 2, 1, 3).reshape(
        B * H, x.shape[1], x.shape[3])
    o, lse = _flash(to3(q), to3(k), to3(v), offs, static_offs, D ** -0.5,
                    causal, None if window is None else int(window),
                    block_q, block_k, bwd_bq, bwd_bk, interpret)
    o = o.reshape(B, H, Tq, Dv).transpose(0, 2, 1, 3)
    if return_lse:
        return o, lse.reshape(B, H, Tq).transpose(0, 2, 1)
    return o
