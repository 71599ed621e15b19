"""Generic FSDP / ZeRO-3 utilities for ARBITRARY parameter pytrees.

Beyond-reference (the reference replicated parameters on every rank, as
every DP framework of its era did).  The flagship transformer has its
own purpose-built layout (``TransformerConfig(fsdp=True)`` — one
d_model-dim rule, see ``models/transformer._fsdp_dims``); this module is
the same mechanics for *user* models driven through shard_map:

- :func:`fsdp_dims` picks, per leaf, which axis to shard over the data
  axis (largest dim divisible by the axis size, skipping dims an
  existing spec already claims);
- :func:`fsdp_specs` turns that choice into ``PartitionSpec``s for
  ``device_put`` / shard_map ``in_specs`` (the at-rest 1/N layout);
- :func:`fsdp_gather` is the just-in-time all-gather to call INSIDE the
  step right before the params are used.  Its AD transpose is a
  ``psum_scatter`` — ZeRO's gradient reduce-scatter falls out of
  autodiff, no hand-written backward.

Optimiser state follows automatically: run the optimiser on the
*sharded* params/grads (its elementwise state mirrors their width) and
initialise it with :func:`...training.shard_opt_state` so the moments
take the params' shardings.

TPU mechanics: the gather is one ``lax.all_gather`` per leaf per use —
XLA schedules the HBM-resident shards' ICI transfers behind the
previous layer's compute exactly like any other collective, and a
``wire_dtype`` of bf16 halves both the gather and the reduce-scatter
bytes (the ``allreduce_grad_dtype`` analogue).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from chainermn_tpu.ops.fused import _wire_dtype_for
from chainermn_tpu.ops.plan_ir import _pin

__all__ = ["fsdp_dims", "fsdp_specs", "fsdp_gather"]


def _mentions_axis(entries, axis: str) -> bool:
    """Whether a PartitionSpec's entries use ``axis`` on any dim (an
    entry is ``None``, an axis name, or a tuple of axis names)."""
    return any(axis == a or (isinstance(a, tuple) and axis in a)
               for a in entries)


def fsdp_dims(params, axis_size: int, specs=None, min_size: int = 2,
              axis: Optional[str] = None):
    """Choose, per leaf, the dim FSDP shards over the data axis.

    Returns a pytree of ``Optional[int]`` matching ``params``: the
    LARGEST dim whose length is divisible by ``axis_size`` (ties →
    first), or
    ``None`` when no dim fits or every candidate is shorter than
    ``min_size * axis_size`` (sharding a tiny vector buys nothing and
    costs a collective).  ``specs`` (a matching PartitionSpec tree, e.g.
    TP/EP shardings) marks dims that are already claimed — those are
    skipped so the layouts compose.  Pass ``axis`` (the FSDP mesh axis
    name you'll give :func:`fsdp_specs`) to also SKIP any leaf whose
    spec already mentions that axis on some dim — a mesh axis can
    appear in a PartitionSpec only once, so such a leaf cannot take an
    FSDP dim at all.
    """
    spec_tree = specs if specs is not None else jax.tree.map(
        lambda _: None, params)

    def pick(leaf, spec) -> Optional[int]:
        shape = jnp.shape(leaf)
        taken = () if spec is None else tuple(spec)
        if axis is not None and _mentions_axis(taken, axis):
            return None
        best = None
        for d, n in enumerate(shape):
            if d < len(taken) and taken[d] is not None:
                continue
            if n % axis_size or n < min_size * axis_size:
                continue
            if best is None or n > shape[best]:
                best = d
        return best

    return jax.tree.map(pick, params, spec_tree)


def fsdp_specs(params, dims, axis: str = "data", base_specs=None):
    """PartitionSpec tree for the at-rest layout: ``base_specs`` (or
    fully-replicated) with ``axis`` inserted at each leaf's chosen dim."""
    if base_specs is None:
        base_specs = jax.tree.map(lambda _: P(), params)

    def build(leaf, dim, spec):
        if dim is None:
            return spec
        full = list(spec) + [None] * (dim + 1 - len(spec))
        if full[dim] is not None:
            raise ValueError(
                f"fsdp dim {dim} already sharded as {spec}; pass this "
                "spec to fsdp_dims so it picks a free dim")
        if _mentions_axis(full, axis):
            # same mesh axis on a DIFFERENT dim would make a duplicate-
            # axis PartitionSpec that only fails later inside
            # NamedSharding with a far less actionable error; backstop —
            # fsdp_dims(..., axis=...) skips such leaves up front
            raise ValueError(
                f"mesh axis {axis!r} already appears in {spec}; pass "
                f"axis={axis!r} (and this spec) to fsdp_dims so it "
                "skips the leaf, or shard FSDP over a different axis")
        full[dim] = axis
        return P(*full)

    return jax.tree.map(build, params, dims, base_specs)


def fsdp_gather(params, dims, axis_name: str = "data", wire_dtype=None,
                *, plan=None, inter_axis_name: Optional[str] = None):
    """All-gather the FSDP-sharded leaves back to full width — call
    INSIDE shard_map, just before the params are consumed.  Grads
    reduce-scatter through the gather's transpose automatically.

    ``wire_dtype`` (e.g. ``jnp.bfloat16``) casts before the gather and
    back after it, so the collective AND the gradient reduce-scatter
    (the cast's transpose converts the cotangent to ``wire_dtype``
    before the scatter, back to the param dtype after) move half the
    bytes while forward/backward compute still sees the params' own
    dtype.  Non-float leaves (int/bool step counters, embedding ids)
    are exempt — rounding them through bf16 is silent corruption, the
    same hazard ``flatten_buckets`` guards against.  The only numerics
    change vs ``None`` is the wire-dtype rounding of the moved FLOAT
    values — the ``allreduce_grad_dtype`` analogue.

    ``plan`` (a tuned :class:`~chainermn_tpu.utils.autotune.Plan` from
    ``autotune_pattern_plan(pattern="fsdp_gather")``, its ``.program``
    dict, or an ``ops.plan_ir.PlanProgram``) switches the lowering to
    the collective-plan IR: fused/hierarchical candidates instead of
    the one-gather-per-leaf default.  Hierarchical programs need
    ``inter_axis_name`` bound to the mesh's outer axis.
    """
    if plan is not None:
        from chainermn_tpu.ops import plan_ir

        return plan_ir.lower_fsdp_gather(
            plan_ir.ensure_program(plan, "fsdp_gather"), params, dims,
            axis_name=axis_name, inter_axis_name=inter_axis_name)

    wd = None if wire_dtype is None else jnp.dtype(wire_dtype)

    def gather(leaf, dim):
        if dim is None:
            return leaf
        if leaf.size == 0:
            # XLA rejects an all_gather over an empty dim; the gathered
            # value is fully determined by the (still empty) shape
            shape = list(leaf.shape)
            shape[dim] *= lax.axis_size(axis_name)
            return jnp.zeros(tuple(shape), leaf.dtype)
        orig = leaf.dtype
        eff = orig if wd is None else _wire_dtype_for(orig, wd)
        narrowed = eff != orig
        if narrowed:
            # barriers pin BOTH casts against the collective: without
            # them XLA commutes the elementwise converts across the
            # all-gather (sinking the narrow-cast / hoisting the
            # cast-back) and the wire silently widens to the param
            # dtype — verified in HLO: f32-wide gathers barrier-less.
            # optimization_barrier transposes to itself, so the
            # gradient reduce-scatter stays at wire_dtype too.
            leaf = _pin(leaf.astype(eff))
        out = lax.all_gather(leaf, axis_name, axis=dim, tiled=True)
        if narrowed:
            out = _pin(out).astype(orig)
        return out

    return jax.tree.map(gather, params, dims)
