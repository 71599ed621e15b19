"""Collective-plan IR — one searched, cached exchange plan for every
communication pattern.

Until now only the optimizer gradient exchange had measured plans
(``utils.autotune``); FSDP all-gathers, MoE all-to-all, ring-attention
ppermutes and pipeline send/recv were hard-coded lowerings that could
not be tuned per topology.  This module is the HiCCL/GC3 style split of
*what* a pattern exchanges from *how* the wire moves it:

- a **payload descriptor** (:class:`LeafDesc`) records, per leaf, the
  dtype / local shape / layout (the dim a gather reassembles along);
- a **program** (:class:`PlanProgram`) is a list of primitive
  :class:`PlanStep`\\ s — ``reduce_scatter``, ``all_gather``,
  ``all_reduce``, ``all_to_all``, ``ppermute``, ``send_recv``,
  ``fuse``, ``cast_wire``, ``barrier`` — over SYMBOLIC mesh-axis roles
  (``"main"``, ``"inter"``) bound to concrete axis names at lowering;
- the **interpreter** (:class:`_Lowering`) lowers a program to
  ``jax.lax`` collectives inside the caller's ``shard_map``.

Programs are plain data (JSON-stable dicts), so they ride the existing
plan cache / rank-0-broadcast / drift-guard machinery unchanged:
``utils.autotune.autotune_pattern_plan`` enumerates the candidate
programs below, probes them on the live mesh, and persists the winner
under a ``plan_key(variant="plan-ir/<pattern>/...")`` entry.

Correctness invariants the interpreter maintains:

- every *native* (no ``cast_wire``) program is pure data movement —
  candidates of one pattern are BITWISE equal to the legacy lowering;
- ``cast_wire`` applies the ONE non-float exemption rule
  (:func:`chainermn_tpu.ops.fused._wire_dtype_for`): int/bool leaves
  ride their native dtype, and both casts are pinned against the
  collective with ``lax.optimization_barrier`` so XLA cannot widen the
  wire back (the fsdp_gather hazard);
- ``fuse`` groups lanes by dtype (stacking equal shapes, else
  ravel-concat) and the interpreter un-fuses — and restores original
  dtypes — after the last step, so callers always get back the exact
  tree structure they passed in.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .fused import _wire_dtype_for

__all__ = [
    "PRIMITIVES",
    "PATTERNS",
    "LeafDesc",
    "PlanStep",
    "PlanProgram",
    "step",
    "describe_payload",
    "describe_state_payload",
    "ensure_program",
    "lower_fsdp_gather",
    "lower_moe_all_to_all",
    "lower_ring_permute",
    "lower_pipeline_edge",
    "enumerate_fsdp_gather_programs",
    "enumerate_moe_a2a_programs",
    "enumerate_ring_permute_programs",
    "enumerate_pipeline_edge_programs",
    "enumerate_pattern_programs",
]

def _pin(x):
    """Keep XLA from widening a wire cast back to the source dtype
    across this point."""
    return lax.optimization_barrier(x)


# the primitive step vocabulary — a program is a sequence of these
PRIMITIVES = ("reduce_scatter", "all_gather", "all_reduce", "all_to_all",
              "ppermute", "send_recv", "fuse", "cast_wire", "barrier")

# the ported call-site patterns (each names a candidate enumerator
# below and a `comm/plan_<pattern>` span at its lowering entry point)
PATTERNS = ("fsdp_gather", "moe_all_to_all", "ring_permute",
            "pipeline_edge")

# primitives that put bytes on the wire (everything else is on-device
# data movement) — comm_model.primitive_cost mirrors this split
WIRE_PRIMITIVES = ("reduce_scatter", "all_gather", "all_reduce",
                   "all_to_all", "ppermute", "send_recv")


# --------------------------------------------------------------------- #
# payload descriptors
# --------------------------------------------------------------------- #


@dataclass(frozen=True)
class LeafDesc:
    """Per-leaf payload signature: local shape, dtype, and layout —
    the dim a gather/scatter reassembles along (``None`` for leaves
    with no distributed dim, e.g. all-to-all operands whose axes are
    relabeled rather than widened)."""

    shape: Tuple[int, ...]
    dtype: str
    layout: Optional[int] = None

    @property
    def size(self) -> int:
        n = 1
        for s in self.shape:
            n *= int(s)
        return n

    @property
    def nbytes(self) -> int:
        return self.size * jnp.dtype(self.dtype).itemsize

    def to_dict(self) -> dict:
        return {"shape": list(self.shape), "dtype": self.dtype,
                "layout": self.layout}


def describe_payload(tree, layouts=None) -> Tuple[LeafDesc, ...]:
    """Flattened-order payload descriptors for ``tree``; ``layouts``
    (a matching pytree of Optional[int], e.g. ``fsdp_dims``' output)
    supplies per-leaf layout dims."""
    leaves, treedef = jax.tree.flatten(tree)
    lay: Sequence[Optional[int]]
    if layouts is None:
        lay = [None] * len(leaves)
    else:
        lay = treedef.flatten_up_to(layouts)
    return tuple(
        LeafDesc(shape=tuple(int(s) for s in jnp.shape(leaf)),
                 dtype=str(jnp.dtype(getattr(leaf, "dtype",
                                             jnp.asarray(leaf).dtype))),
                 layout=(None if d is None else int(d)))
        for leaf, d in zip(leaves, lay))


def describe_state_payload(layouts, world: Optional[int] = None
                           ) -> Tuple[LeafDesc, ...]:
    """Payload descriptors for the LOCAL (per-member) shard payload a
    sharded-state exchange moves, derived straight from per-leaf layout
    signatures (``parallel.sharded_state.LeafLayout`` objects or their
    record dicts + shape/dtype) — never from live arrays, so plans can
    be tuned before any state is materialized.

    Kind mapping: ``fsdp`` → the dim-sharded local slice with
    ``layout`` = the shard dim (what ``lower_fsdp_gather`` widens);
    ``shard`` → the flat ``(ceil(size/world),)`` ZeRO shard, gathered
    along axis 0; ``rep``/``stack`` → the full leaf, no distributed
    dim (rides the exchange unchanged).
    """
    descs = []
    for spec in layouts:
        get = (spec.get if isinstance(spec, dict)
               else lambda k, _s=spec: getattr(_s, k, None))
        kind = get("kind")
        shape = tuple(int(s) for s in (get("shape") or ()))
        dtype = str(get("dtype") or "float32")
        w = int(world if world is not None else get("world") or 1)
        if kind == "fsdp":
            d = int(get("dim"))
            if shape[d] % w:
                raise ValueError(
                    f"fsdp leaf dim {d} (length {shape[d]}) not "
                    f"divisible by world {w}")
            local = list(shape)
            local[d] //= w
            descs.append(LeafDesc(tuple(local), dtype, layout=d))
        elif kind == "shard":
            size = int(get("size"))
            descs.append(LeafDesc((-(-size // w),), dtype, layout=0))
        elif kind in ("rep", "stack"):
            descs.append(LeafDesc(shape, dtype, layout=None))
        else:
            raise ValueError(f"unknown layout kind {kind!r}")
    return tuple(descs)


# --------------------------------------------------------------------- #
# steps & programs
# --------------------------------------------------------------------- #


@dataclass(frozen=True)
class PlanStep:
    """One primitive of a plan program.  ``axis`` is a SYMBOLIC role
    (``"main"`` / ``"inter"``) bound to a concrete mesh-axis name at
    lowering; ``params`` are static op parameters (sorted key/value
    pairs — hashable, JSON-stable)."""

    op: str
    axis: Optional[str] = None
    params: Tuple[Tuple[str, Any], ...] = ()

    def __post_init__(self):
        if self.op not in PRIMITIVES:
            raise ValueError(
                f"unknown plan primitive {self.op!r}; expected one of "
                f"{PRIMITIVES}")

    def get(self, key: str, default=None):
        for k, v in self.params:
            if k == key:
                return v
        return default

    def replaced(self, **updates) -> "PlanStep":
        merged = dict(self.params)
        merged.update(updates)
        return PlanStep(self.op, self.axis,
                        tuple(sorted(merged.items())))

    def to_dict(self) -> dict:
        return {"op": self.op, "axis": self.axis,
                "params": dict(self.params)}

    @classmethod
    def from_dict(cls, d: dict) -> "PlanStep":
        return cls(op=d["op"], axis=d.get("axis"),
                   params=tuple(sorted((d.get("params") or {}).items())))


def step(op: str, axis: Optional[str] = None, **params) -> PlanStep:
    """Shorthand constructor: ``step("all_gather", axis="main")``."""
    return PlanStep(op, axis, tuple(sorted(params.items())))


@dataclass
class PlanProgram:
    """A candidate exchange program for one pattern: the searched /
    cached artifact.  ``label`` names the candidate in plan-cache
    metadata and bench reports (e.g. ``"fused/hier/native"``)."""

    pattern: str
    label: str
    steps: Tuple[PlanStep, ...] = ()

    def __post_init__(self):
        if self.pattern not in PATTERNS:
            raise ValueError(
                f"unknown plan pattern {self.pattern!r}; expected one "
                f"of {PATTERNS}")
        self.steps = tuple(self.steps)

    @property
    def wire_dtype(self) -> Optional[str]:
        for st in self.steps:
            if st.op == "cast_wire":
                return st.get("dtype")
        return None

    def to_dict(self) -> dict:
        return {"pattern": self.pattern, "label": self.label,
                "steps": [st.to_dict() for st in self.steps]}

    @classmethod
    def from_dict(cls, d: dict) -> "PlanProgram":
        return cls(pattern=d["pattern"], label=d.get("label", "?"),
                   steps=tuple(PlanStep.from_dict(s)
                               for s in d.get("steps", ())))


def ensure_program(obj, pattern: Optional[str] = None) -> PlanProgram:
    """Coerce a program carrier to a :class:`PlanProgram`: accepts a
    PlanProgram, its dict form, or a tuned ``autotune.Plan`` (whose
    ``program`` field holds the dict).  ``pattern`` cross-checks the
    carrier against the call site consuming it — a cached MoE program
    fed to ``fsdp_gather`` must fail loudly, not lower garbage."""
    prog = getattr(obj, "program", None)
    if prog is not None and not isinstance(obj, PlanProgram):
        obj = prog
    if isinstance(obj, dict):
        obj = PlanProgram.from_dict(obj)
    if not isinstance(obj, PlanProgram):
        raise TypeError(
            f"cannot build a PlanProgram from {type(obj).__name__}")
    if pattern is not None and obj.pattern != pattern:
        raise ValueError(
            f"plan program is for pattern {obj.pattern!r}, but this "
            f"call site lowers {pattern!r}")
    return obj


# --------------------------------------------------------------------- #
# the interpreter
# --------------------------------------------------------------------- #


@dataclass
class _Bucket:
    """One fused lane: dtype-grouped members of the input lanes.
    ``mode`` is ``"stack"`` (equal shapes — cheap axis-0 stack) or
    ``"concat"`` (ravel + concatenate)."""

    mode: str
    members: List[int]
    shapes: List[Tuple[int, ...]] = field(default_factory=list)


class _Lowering:
    """Executes a program's steps over a list of *lanes* (arrays).

    Fused lanes always carry a leading world axis (size 1 at fuse
    time); every ``all_gather`` step widens it with ``tiled=True`` at
    axis 0, so hierarchical two-stage gathers compose by construction
    (row-major (inter, intra) device order — exactly the flat gather
    over the combined axis).  Un-fusing distributes the accumulated
    factor back onto each member's layout dim."""

    def __init__(self, lanes: Sequence, descs: Sequence[LeafDesc],
                 axes: Dict[str, Optional[str]]):
        self.lanes = [jnp.asarray(x) for x in lanes]
        self.descs = list(descs)
        self.axes = axes
        self.origs = [x.dtype for x in self.lanes]
        self.buckets: Optional[List[_Bucket]] = None
        self.gather_factor = 1

    # ---- helpers ---------------------------------------------------- #

    def _axis(self, st: PlanStep) -> str:
        role = st.axis or "main"
        name = self.axes.get(role)
        if name is None:
            raise ValueError(
                f"program step {st.op!r} names axis role {role!r} but "
                f"the call site bound no such axis (got {self.axes})")
        return name

    @staticmethod
    def _perm(size: int, shift: int, wrap: bool):
        if shift not in (1, -1):
            raise ValueError(f"send_recv shift must be ±1, got {shift}")
        if shift == 1:
            perm = [(i, i + 1) for i in range(size - 1)]
            return perm + ([(size - 1, 0)] if wrap else [])
        perm = [(i + 1, i) for i in range(size - 1)]
        return perm + ([(0, size - 1)] if wrap else [])

    @staticmethod
    def _resized(lane, dim: int, new_len: int):
        # XLA rejects collectives whose gather/scatter dim is empty, so
        # zero-size lanes never hit the wire: their post-collective
        # value is fully determined by the (empty) output shape
        shape = list(lane.shape)
        shape[dim] = new_len
        return jnp.zeros(tuple(shape), lane.dtype)

    # ---- primitives ------------------------------------------------- #

    def _cast_wire(self, st: PlanStep):
        wd = st.get("dtype")
        if wd is None:
            return
        for i, lane in enumerate(self.lanes):
            eff = _wire_dtype_for(lane.dtype, jnp.dtype(wd))
            if eff != lane.dtype:
                # barrier pins the narrow-cast against the collective:
                # without it XLA sinks the convert across the wire op
                # and the transfer silently widens to the source dtype
                self.lanes[i] = _pin(lane.astype(eff))

    def _fuse(self, st: PlanStep):
        if self.buckets is not None:
            raise ValueError("fuse applied twice in one program")
        groups: Dict[str, List[int]] = {}
        for i, lane in enumerate(self.lanes):
            groups.setdefault(str(lane.dtype), []).append(i)
        buckets: List[_Bucket] = []
        fused_lanes = []
        for _dt, idxs in groups.items():
            shapes = [tuple(self.lanes[i].shape) for i in idxs]
            if len(set(shapes)) == 1:
                vec = jnp.stack([self.lanes[i] for i in idxs])
                buckets.append(_Bucket("stack", idxs, shapes))
            else:
                vec = jnp.concatenate(
                    [self.lanes[i].reshape(-1) for i in idxs])
                buckets.append(_Bucket("concat", idxs, shapes))
            fused_lanes.append(vec[None])   # leading world axis, size 1
        self.buckets = buckets
        self.lanes = fused_lanes

    def _all_gather(self, st: PlanStep):
        name = self._axis(st)
        size = lax.axis_size(name)
        if self.buckets is not None:
            self.lanes = [
                self._resized(lane, 0, lane.shape[0] * size)
                if lane.size == 0
                else lax.all_gather(lane, name, axis=0, tiled=True)
                for lane in self.lanes]
            self.gather_factor *= size
            return
        out = []
        for lane, desc in zip(self.lanes, self.descs):
            dim = desc.layout if desc.layout is not None else 0
            if lane.size == 0:
                out.append(self._resized(lane, dim,
                                         lane.shape[dim] * size))
            else:
                out.append(lax.all_gather(lane, name, axis=dim,
                                          tiled=True))
        self.lanes = out

    def _reduce(self, st: PlanStep, scatter: bool):
        name = self._axis(st)
        op = st.get("op", "add")
        if op not in ("add", "mean"):
            raise ValueError(f"reduce op {op!r} not in (add, mean)")
        out = []
        for lane, desc in zip(self.lanes,
                              self.descs if self.buckets is None
                              else [None] * len(self.lanes)):
            if not scatter:
                red = lane if lane.size == 0 else \
                    (lax.pmean if op == "mean" else lax.psum)(lane, name)
            else:
                dim = 0
                if desc is not None and desc.layout is not None:
                    dim = desc.layout
                if lane.shape[dim] % lax.axis_size(name):
                    raise ValueError(
                        f"reduce_scatter dim {dim} (length "
                        f"{lane.shape[dim]}) not divisible by axis "
                        f"{name!r} size {lax.axis_size(name)}")
                if lane.size == 0:
                    red = self._resized(
                        lane, dim,
                        lane.shape[dim] // lax.axis_size(name))
                else:
                    red = lax.psum_scatter(lane, name,
                                           scatter_dimension=dim,
                                           tiled=True)
                    if op == "mean":
                        red = red / lax.axis_size(name)
            out.append(red)
        self.lanes = out

    def _all_to_all(self, st: PlanStep):
        if self.buckets is not None:
            raise ValueError(
                "all_to_all on fused lanes is not supported — it "
                "relabels a per-lane axis; fuse has no meaning here")
        name = self._axis(st)
        sa = int(st.get("split_axis", 0))
        ca = int(st.get("concat_axis", 0))
        chunks = int(st.get("chunks", 1))
        chunk_axis = st.get("chunk_axis")
        out = []
        for lane in self.lanes:
            if lane.size == 0:
                size = lax.axis_size(name)
                moved = self._resized(lane, sa, lane.shape[sa] // size)
                out.append(self._resized(moved, ca,
                                         moved.shape[ca] * size))
                continue
            if chunks <= 1:
                out.append(lax.all_to_all(lane, name, split_axis=sa,
                                          concat_axis=ca, tiled=True))
                continue
            d = int(chunk_axis if chunk_axis is not None
                    else lane.ndim - 1)
            if d == sa or d == ca:
                raise ValueError(
                    f"all_to_all chunk_axis {d} collides with "
                    f"split/concat axes ({sa}, {ca}) — chunked results "
                    "would interleave wrong")
            if lane.shape[d] % chunks:
                raise ValueError(
                    f"all_to_all chunk axis {d} (length "
                    f"{lane.shape[d]}) not divisible by {chunks}")
            pieces = jnp.split(lane, chunks, axis=d)
            moved = [lax.all_to_all(p, name, split_axis=sa,
                                    concat_axis=ca, tiled=True)
                     for p in pieces]
            out.append(jnp.concatenate(moved, axis=d))
        self.lanes = out

    def _permute(self, st: PlanStep):
        name = self._axis(st)
        size = lax.axis_size(name)
        perm = self._perm(size, int(st.get("shift", 1)),
                          bool(st.get("wrap", True)))
        if not perm:                       # degenerate 1-device edge
            return
        self.lanes = [lane if lane.size == 0
                      else lax.ppermute(lane, name, perm=perm)
                      for lane in self.lanes]

    def _barrier(self, _st: PlanStep):
        self.lanes = list(_pin(tuple(self.lanes)))

    # ---- finalization ----------------------------------------------- #

    def _merge_world(self, piece, layout: Optional[int]):
        """Fold the leading gathered factor into the member's layout
        dim — block order matches ``lax.all_gather(tiled=True)``."""
        f = piece.shape[0]
        if f == 1:
            return piece[0]
        if layout is None:
            raise ValueError(
                "program gathered fused lanes but a member has no "
                "layout dim to reassemble along")
        d = int(layout)
        moved = jnp.moveaxis(piece, 0, d)
        shape = list(moved.shape)
        shape[d: d + 2] = [shape[d] * shape[d + 1]]
        return moved.reshape(shape)

    def _unfuse(self):
        if self.buckets is None:
            return
        restored: List[Any] = [None] * len(self.descs)
        for lane, bucket in zip(self.lanes, self.buckets):
            if bucket.mode == "stack":
                for j, i in enumerate(bucket.members):
                    restored[i] = self._merge_world(
                        lane[:, j], self.descs[i].layout)
            else:
                off = 0
                for i, shape in zip(bucket.members, bucket.shapes):
                    size = 1
                    for s in shape:
                        size *= s
                    piece = lane[:, off: off + size]
                    piece = piece.reshape((lane.shape[0],) + shape)
                    restored[i] = self._merge_world(
                        piece, self.descs[i].layout)
                    off += size
        self.lanes = restored
        self.buckets = None

    def _restore_dtypes(self):
        out = []
        for lane, orig in zip(self.lanes, self.origs):
            if lane.dtype != orig:
                # the cast-back twin of _cast_wire's barrier: without
                # it XLA hoists the widen above the collective
                lane = _pin(lane).astype(orig)
            out.append(lane)
        self.lanes = out

    _DISPATCH = {
        "cast_wire": _cast_wire,
        "fuse": _fuse,
        "all_gather": _all_gather,
        "all_to_all": _all_to_all,
        "ppermute": _permute,
        "send_recv": _permute,
        "barrier": _barrier,
    }

    def run(self, steps: Sequence[PlanStep]) -> List:
        for st in steps:
            if st.op == "all_reduce":
                self._reduce(st, scatter=False)
            elif st.op == "reduce_scatter":
                self._reduce(st, scatter=True)
            else:
                self._DISPATCH[st.op](self, st)
        self._unfuse()
        self._restore_dtypes()
        return self.lanes


def lower_program(program, lanes, descs, axes: Dict[str, Optional[str]]):
    """Low-level entry: run ``program`` over explicit lanes/descs with
    ``axes`` binding symbolic roles to mesh-axis names.  The pattern
    entry points below are the supported surface; this exists for
    tests and custom patterns."""
    program = ensure_program(program)
    return _Lowering(lanes, descs, axes).run(program.steps)


# --------------------------------------------------------------------- #
# pattern entry points (the four ported call sites)
# --------------------------------------------------------------------- #


def _recorder():
    from chainermn_tpu.utils.telemetry import get_recorder

    return get_recorder()


def lower_fsdp_gather(program, params, dims, *,
                      axis_name: str = "data",
                      inter_axis_name: Optional[str] = None):
    """Lower an ``fsdp_gather`` plan: all-gather the sharded leaves
    (``dims`` marks each leaf's gather dim, ``None`` = untouched) back
    to full width, per the program's strategy.  Call INSIDE shard_map —
    the just-in-time per-layer gather, exactly like the legacy path;
    AD still reduce-scatters through the gather's transpose."""
    program = ensure_program(program, "fsdp_gather")
    leaves, treedef = jax.tree.flatten(params)
    dim_list = treedef.flatten_up_to(dims)
    idxs = [i for i, d in enumerate(dim_list) if d is not None]
    if not idxs:
        return params
    lanes = [leaves[i] for i in idxs]
    descs = [LeafDesc(tuple(int(s) for s in leaves[i].shape),
                      str(leaves[i].dtype), int(dim_list[i]))
             for i in idxs]
    with _recorder().span("comm/plan_fsdp_gather", cat="comm",
                          label=program.label, n_leaves=len(idxs)):
        out = _Lowering(lanes, descs,
                        {"main": axis_name,
                         "inter": inter_axis_name}).run(program.steps)
    for i, lane in zip(idxs, out):
        leaves[i] = lane
    return treedef.unflatten(leaves)


def lower_moe_all_to_all(program, x, *, axis_name: str,
                         split_axis: int, concat_axis: int):
    """Lower one MoE dispatch/combine all-to-all.  The direction's
    split/concat axes come from the call site (dispatch: 0→1,
    combine: 1→0) and override the program's placeholders; chunking
    (``chunks``/``chunk_axis``) stays the program's choice."""
    program = ensure_program(program, "moe_all_to_all")
    steps = tuple(
        st.replaced(split_axis=int(split_axis),
                    concat_axis=int(concat_axis))
        if st.op == "all_to_all" else st for st in program.steps)
    desc = LeafDesc(tuple(int(s) for s in x.shape), str(x.dtype), None)
    with _recorder().span("comm/plan_moe_all_to_all", cat="comm",
                          label=program.label, split=int(split_axis)):
        out = _Lowering([x], [desc],
                        {"main": axis_name, "inter": None}).run(steps)
    return out[0]


def lower_ring_permute(program, operands, *, axis_name: str):
    """Lower one ring-attention rotation step: shift every operand
    (the K/V blocks) one position around the ring, fused into a single
    wire transfer or as separate ppermutes per the program."""
    program = ensure_program(program, "ring_permute")
    lanes = list(operands)
    descs = [LeafDesc(tuple(int(s) for s in x.shape), str(x.dtype),
                      None) for x in lanes]
    with _recorder().span("comm/plan_ring_permute", cat="comm",
                          label=program.label, n_operands=len(lanes)):
        out = _Lowering(lanes, descs,
                        {"main": axis_name,
                         "inter": None}).run(program.steps)
    return tuple(out)


def lower_pipeline_edge(program, x, *, axis_name: str, shift: int = 1,
                        wrap: bool = False):
    """Lower one pipeline stage hand-off (``send_recv`` neighbour
    copy).  Direction and wrap-around come from the call site (GPipe
    up edge: ``shift=1, wrap=False``; 1F1B down edge: ``shift=-1``;
    interleaved edges wrap) and override the program's placeholders."""
    program = ensure_program(program, "pipeline_edge")
    steps = tuple(
        st.replaced(shift=int(shift), wrap=bool(wrap))
        if st.op in ("send_recv", "ppermute") else st
        for st in program.steps)
    desc = LeafDesc(tuple(int(s) for s in x.shape), str(x.dtype), None)
    with _recorder().span("comm/plan_pipeline_edge", cat="comm",
                          label=program.label, shift=int(shift)):
        out = _Lowering([x], [desc],
                        {"main": axis_name, "inter": None}).run(steps)
    return out[0]


# --------------------------------------------------------------------- #
# candidate enumerators (the per-pattern search spaces)
# --------------------------------------------------------------------- #

# Enumerator contract: the FIRST program is the legacy-equivalent
# native baseline — the autotuner's parity anchor (bitwise reference
# for every native candidate, tolerance reference for wire ones).


def _wire_variants(wire_dtypes) -> List[Tuple[str, List[PlanStep]]]:
    out: List[Tuple[str, List[PlanStep]]] = []
    for wd in wire_dtypes:
        if wd is None:
            out.append(("native", []))
        else:
            wd = str(jnp.dtype(wd))
            out.append((wd, [step("cast_wire", dtype=wd)]))
    return out


def enumerate_fsdp_gather_programs(
        *, allow_hierarchical: bool = False,
        wire_dtypes: Sequence = (None,)) -> List[PlanProgram]:
    """FSDP gather candidates: {per-leaf, fused} × {flat, hierarchical
    two-stage} × wire dtypes.  Hierarchical gathers intra (``main``)
    then inter — row-major (inter, intra) block order, identical to
    the flat gather over the combined axis tuple."""
    progs = []
    tiers = [("flat", [step("all_gather", axis="main")])]
    if allow_hierarchical:
        tiers.append(("hier", [step("all_gather", axis="main"),
                               step("all_gather", axis="inter")]))
    for wire_label, pre in _wire_variants(wire_dtypes):
        for tier_label, gathers in tiers:
            for fused in (False, True):
                steps_ = list(pre)
                if fused:
                    steps_.append(step("fuse"))
                steps_ += gathers
                kind = "fused" if fused else "per_leaf"
                label = f"{kind}/{tier_label}/{wire_label}"
                progs.append(PlanProgram("fsdp_gather", label,
                                         tuple(steps_)))
    # baseline first: per_leaf/flat/native must lead regardless of
    # the wire_dtypes ordering the caller passed
    progs.sort(key=lambda p: p.label != "per_leaf/flat/native")
    return progs


def enumerate_moe_a2a_programs(
        shape: Sequence[int], *, split_axis: int = 0,
        concat_axis: int = 1, max_chunks: int = 8,
        wire_dtypes: Sequence = (None,)) -> List[PlanProgram]:
    """MoE all-to-all candidates: the single-shot transfer vs
    axis-split chunked variants (k transfers over a dim not involved
    in the relabel — bitwise-identical, trades launches for pipelining
    room) × wire dtypes."""
    shape = tuple(int(s) for s in shape)
    chunk_axis = None
    for d in range(len(shape) - 1, -1, -1):
        if d != split_axis and d != concat_axis and shape[d] > 1:
            chunk_axis = d
            break
    progs = []
    for wire_label, pre in _wire_variants(wire_dtypes):
        progs.append(PlanProgram(
            "moe_all_to_all", f"single/{wire_label}",
            tuple(pre + [step("all_to_all", axis="main",
                              split_axis=split_axis,
                              concat_axis=concat_axis)])))
        if chunk_axis is None:
            continue
        k = 2
        while k <= max_chunks and shape[chunk_axis] % k == 0 \
                and shape[chunk_axis] // k >= 1:
            progs.append(PlanProgram(
                "moe_all_to_all", f"split{k}/{wire_label}",
                tuple(pre + [step("all_to_all", axis="main",
                                  split_axis=split_axis,
                                  concat_axis=concat_axis,
                                  chunks=k, chunk_axis=chunk_axis)])))
            k *= 2
    progs.sort(key=lambda p: p.label != "single/native")
    return progs


def enumerate_ring_permute_programs(
        *, wire_dtypes: Sequence = (None,)) -> List[PlanProgram]:
    """Ring-rotation candidates: one ppermute per operand (legacy —
    K and V each launch a collective) vs fused (stack K/V, one wire
    transfer, unstack) × wire dtypes."""
    progs = []
    for wire_label, pre in _wire_variants(wire_dtypes):
        progs.append(PlanProgram(
            "ring_permute", f"separate/{wire_label}",
            tuple(pre + [step("ppermute", axis="main",
                              shift=1, wrap=True)])))
        progs.append(PlanProgram(
            "ring_permute", f"fused/{wire_label}",
            tuple(pre + [step("fuse"),
                         step("ppermute", axis="main",
                              shift=1, wrap=True)])))
    progs.sort(key=lambda p: p.label != "separate/native")
    return progs


def enumerate_pipeline_edge_programs(
        *, wire_dtypes: Sequence = (None,)) -> List[PlanProgram]:
    """Pipeline stage-edge candidates: the native neighbour copy vs
    wire-compressed variants (activation bytes halved over the hop —
    the allreduce_grad_dtype trade applied to the pipe edge)."""
    progs = []
    for wire_label, pre in _wire_variants(wire_dtypes):
        progs.append(PlanProgram(
            "pipeline_edge", f"direct/{wire_label}",
            tuple(pre + [step("send_recv", axis="main",
                              shift=1, wrap=False)])))
    progs.sort(key=lambda p: p.label != "direct/native")
    return progs


def enumerate_pattern_programs(pattern: str, **kwargs) -> List[PlanProgram]:
    """Dispatch to the pattern's enumerator — the autotuner's single
    entry point (``kwargs`` are the enumerator's own)."""
    table = {
        "fsdp_gather": enumerate_fsdp_gather_programs,
        "moe_all_to_all": enumerate_moe_a2a_programs,
        "ring_permute": enumerate_ring_permute_programs,
        "pipeline_edge": enumerate_pipeline_edge_programs,
    }
    if pattern not in table:
        raise ValueError(
            f"unknown pattern {pattern!r}; expected one of {PATTERNS}")
    return table[pattern](**kwargs)
