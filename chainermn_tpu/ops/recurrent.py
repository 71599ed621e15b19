"""What the token mixers with a short convolution share (``ops/kda.py``,
``ops/ssd.py``, ``ops/gdn.py`` and the layers of ``models/mixers.py``):
the short causal convolution, in its two forms, and the loop over slabs
of chunks whose body is rematerialised, with the two names
(``RECURRENT_RESIDUAL_NAMES``) by which a checkpoint around the layer
keeps what the loop's backward pass reads.  One of each: a repair to
either lands in every layer that has it.

The convolution with SiLU (:func:`causal_conv_silu`) stands in front of
a recurrence; the doubly gated one (:func:`gated_short_conv`, ``C *
conv(B * x)`` with no activation and no bias) is a mixer by itself, with
no recurrence behind it.  Both are bound by the bytes of their float32
tensors, so each has two forms and the input's shape alone chooses.
Where the channels (and every part they are split into) are whole lane
tiles and the tokens whole blocks of ``TOKENS``, a Pallas kernel reads
its input once and writes each part once, and a second kernel is its
backward pass (``jax.custom_vjp``): what is kept is the input, the taps
and the bias; what lies between (the pre-activation; the gated product
and the convolution's sum) is computed again from the tile in VMEM, and
the input's cotangent and the taps' and bias's gradients leave in one
pass over the cotangent and the input.  Any other shape takes the plain
sum over taps, which autodiff differentiates.  Off the TPU the kernels
run in the interpreter, as the flash and KDA kernels do.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.ops.kernel_common import interpret_kernels
from chainermn_tpu.parallel._compat import pcast
from chainermn_tpu.utils.metrics import get_registry

__all__ = ["causal_conv_silu", "gated_short_conv", "scan_slabs",
           "slab_size", "RECURRENT_RESIDUAL_NAMES"]

RECURRENT_RESIDUAL_NAMES = ("recurrent_state", "recurrent_out")

TOKENS = 1024   # tokens a kernel step; the op's own, as ops/kda.py's CHUNK
_LANES = 128    # channels a lane tile
_ROWS = 8       # tokens a sublane tile: the halo, and a turn of the loop
_WIDEST = 256   # most channels a kernel step (two lane tiles in flight)
_UNROLL = 4     # tiles a turn of a kernel's loop


def causal_conv_silu(y, w, bias=None, split=None):
    """``SiLU(conv(y) + bias)``: a causal depthwise convolution along
    the token axis (axis 1 of ``y``, ``(B, T, *channels)``) with one
    weight a channel a tap (``w``: ``(*channels, taps)``),
    ``conv(y)_t = sum_j w_j y_(t - taps + 1 + j)``: the last tap meets
    the token itself, nothing reaches back past the sequence's start.
    All in float32.

    With ``split`` the result is the tuple of the parts it names, in
    the order of the flattened channels, and no tensor holds them side
    by side: a size gives ``(B, T, size)``, a pair ``(heads, width)``
    gives ``(B, T, heads, width)``.  Without, it has ``y``'s shape.

    Lane-aligned float32 shapes (:func:`_kernel_blocks`) run the fused
    kernels and keep ``y``, ``w`` and ``bias`` for a backward pass of
    their own; any other shape is the plain sum over taps.  The kernels
    write a part of heads one lane tile wide head by head, tokens on
    the sublanes under each head, which is how the recurrences' slabs
    read it: the ``(B, T, heads, width)`` handed back is that array
    with two axes swapped, and no pass regroups a tile."""
    B, T = y.shape[:2]
    flat = y.reshape(B, T, -1)
    named = tuple(split) if split else (flat.shape[-1],)
    sizes = tuple(math.prod(p) if isinstance(p, tuple) else p for p in named)
    if sum(sizes) != flat.shape[-1]:
        raise ValueError(f"split {named} does not sum to the "
                         f"{flat.shape[-1]} channels of {y.shape}")
    taps = w.shape[-1]
    w = w.reshape(-1, taps)
    lanes = _kernel_blocks(T, sizes, taps)
    if lanes and y.dtype == w.dtype == jnp.float32:
        plan = _Plan(sizes, tuple(isinstance(p, tuple) and p[1] == _LANES
                                  for p in named), lanes, interpret_kernels())
        # the kernels' cotangents vary over the mesh axes y does; a
        # replicated parameter is retyped here, so that its gradient's
        # sum over those axes is the cast's transpose and autodiff's
        over = tuple(jax.typeof(flat).vma)
        operands = (pcast(w.T, over, to="varying"),
                    None if bias is None else pcast(
                        bias.reshape(1, -1), over, to="varying"))
        parts = [jnp.swapaxes(part, 1, 2) if headed else part
                 for part, headed in zip(_fused(flat, *operands, plan),
                                         plan.by_head)]
    else:
        out = _plain(flat, w, None if bias is None else bias.reshape(-1))
        edges = [sum(sizes[:p]) for p in range(len(sizes) + 1)]
        parts = [out[..., lo:hi] for lo, hi in zip(edges, edges[1:])]
    if not split:
        return parts[0].reshape(y.shape)
    return tuple(part.reshape(B, T, *p) if isinstance(p, tuple) else part
                 for part, p in zip(parts, named))


def _plain(y, w, bias):
    """The sum over taps as written, ``y`` ``(B, T, C)``, ``w``
    ``(C, taps)``: a padded copy and ``taps`` shifted slices of it."""
    T, taps = y.shape[1], w.shape[-1]
    padded = jnp.pad(y, ((0, 0), (taps - 1, 0), (0, 0)))
    out = sum(padded[:, j:j + T] * w[:, j] for j in range(taps))
    return jax.nn.silu(out if bias is None else out + bias)


def _kernel_blocks(T, sizes, taps):
    """The channels a kernel step takes, or 0 where the kernels do not
    apply: every part whole lane tiles, the tokens whole blocks, the
    taps inside one sublane tile.  The widest whole number of lane
    tiles up to ``_WIDEST`` that divides every part, so a step's
    channels lie in one part."""
    if T % TOKENS or taps > _ROWS or any(s % _LANES for s in sizes):
        return 0
    return math.gcd(*sizes, _WIDEST)


# --------------------------------------------------------------------- #
# the kernels: a step is TOKENS tokens of `lanes` channels, walked in
# sublane tiles of _ROWS tokens; a tap's shift is a rotation of the
# tile along its sublanes, the rows that wrapped taken from the
# neighbouring tile's rotation
# --------------------------------------------------------------------- #


def _tap_rows(w_ref):
    """The taps of this step's channels, each spread over a tile."""
    return [jnp.broadcast_to(w_ref[j:j + 1, :], (_ROWS, w_ref.shape[1]))
            for j in range(w_ref.shape[0])]


def _step_values(refs, biased, at_start):
    """What every tile of a step needs: the taps and the bias (0.0
    without) spread over a tile, the tile's row numbers, and the
    ``_ROWS`` tokens before the block, zeros at a sequence's start."""
    halo_ref, _, w_ref = refs[:3]
    return (_tap_rows(w_ref), _tap_rows(refs[3])[0] if biased else 0.0,
            lax.broadcasted_iota(jnp.int32, (_ROWS, w_ref.shape[1]), 0),
            jnp.where(at_start, 0.0, halo_ref[0]))


def _rotations(tiles, shifts):
    """Each tile (or the one) rotated along its sublanes by its shift:
    row ``t`` of the result is row ``t - shift`` of the tile."""
    if not isinstance(tiles, list):
        tiles = [tiles] * len(shifts)
    return [pltpu.roll(tile, s, 0) if s else tile
            for tile, s in zip(tiles, shifts)]


def _walk(tiles, turn, carry):
    """``turn(tile, carry) -> carry`` over ``tiles`` tiles in order,
    ``_UNROLL`` a turn of the loop: a tile's chain of operations is one
    long dependency, and the scheduler fills its waits with the next
    tile's.  (A traced bound: with a static one the loop is a scan,
    whose carry check the interpreter trips under shard_map's varying
    axes.)"""
    def turns(k, carry):
        for u in range(_UNROLL):
            carry = turn(k * _UNROLL + u, carry)
        return carry

    return lax.fori_loop(jnp.int32(0), tiles // _UNROLL, turns, carry)


def _rows(g):
    """Tokens of tile ``g`` of a block."""
    return pl.ds(pl.multiple_of(g * _ROWS, _ROWS), _ROWS)


def _back(taps):
    """How far back each tap reads (tap ``j`` reads ``t - back[j]``)."""
    return [taps - 1 - j for j in range(taps)]


def _walk_forward(tiles, w, row, before, read, put):
    """The convolution's sum over a block, first tile to last, for both
    forward kernels: ``read(rows)`` is the convolution's input at those
    tokens, ``before`` its ``_ROWS`` tokens before the block, and
    ``put(rows, conv)`` is handed the sum over taps."""
    back = _back(len(w))

    def turn(g, weighed_before):
        rows = _rows(g)
        tile = read(rows)
        # a tap is the same on every sublane: weigh, then rotate
        weighed = _rotations([w[j] * tile for j in range(len(w))], back)
        put(rows, sum(jnp.where(row >= back[j], weighed[j], weighed_before[j])
                      for j in range(len(w))))
        return weighed

    _walk(tiles, turn, _rotations([w[j] * before for j in range(len(w))],
                                  back))


def _walk_back(tiles, w, row, before, read, d_conv, put, after_ref, dw_ref,
               biased=False):
    """A block's tiles last to first, for both backward kernels.
    ``read`` and ``before`` as in :func:`_walk_forward`;
    ``d_conv(rows, shifted)`` is handed the input as each tap met it
    and returns the cotangent of the convolution's sum there (of the
    pre-activation, with a bias); ``put(rows, d_in)`` is handed the
    input's cotangent.  ``after_ref`` holds what the block after this
    one left, its first tile's ``d_conv`` rotated for each tap, and
    takes this block's; ``dw_ref`` gains the taps' (and the bias's)
    sums a sublane."""
    taps = len(w)
    back = _back(taps)
    ahead = [(_ROWS - s) % _ROWS for s in back]     # rotation for t + back

    def tile(g):
        """Tile ``g`` of the input; -1 is the one before the block.
        (Always through the select: binary operations inside a kernel
        drop their operands' varying mesh axes, a plain read keeps
        them, and a loop's carry has to leave with the type it came
        with.)"""
        rows = _rows(jnp.maximum(g, 0))
        return jnp.where(g >= 0, read(rows), before)

    def turn(k, carry):
        rolled, d_after, sums = carry
        g = tiles - 1 - k
        rows = _rows(g)
        rolled_before = _rotations(tile(g - 1), back)
        shifted = [jnp.where(row >= back[j], rolled[j], rolled_before[j])
                   for j in range(taps)]
        d = d_conv(rows, shifted)
        d_rolled = _rotations(d, ahead)
        put(rows, sum(
            w[j] * jnp.where(row < _ROWS - back[j], d_rolled[j], d_after[j])
            for j in range(taps)))
        sums = [s + d * x for s, x in zip(sums, shifted)] \
            + ([sums[-1] + d] if biased else [])
        return rolled_before, d_rolled, sums

    _, d_first, sums = _walk(tiles, turn, (
        _rotations(tile(tiles - 1), back),
        [after_ref[j] for j in range(taps)],
        [jnp.zeros(row.shape, jnp.float32)] * (taps + biased)))
    for j in range(taps):
        after_ref[j] = d_first[j]
    for j in range(taps + biased):
        dw_ref[0, j] += sums[j]


def _tile(ref, rows):
    """Tokens ``rows`` of a part's block as one tile: the block is
    ``(1, tokens, lanes)``, or head by head ``(1, heads, tokens,
    _LANES)``, the heads then side by side on the tile's lanes."""
    if len(ref.shape) == 3:
        return ref[0, rows, :]
    return jnp.concatenate(
        [ref[0, h, rows, :] for h in range(ref.shape[1])], axis=-1)


def _put(ref, rows, tile):
    """:func:`_tile`'s inverse: ``tile`` written to tokens ``rows``."""
    if len(ref.shape) == 3:
        ref[0, rows, :] = tile
    else:
        for h in range(ref.shape[1]):
            ref[0, h, rows, :] = tile[:, h * _LANES:(h + 1) * _LANES]


def _part_of(c, edges):
    """For each part, whether channel step ``c`` lies in it."""
    return [(c >= lo) & (c < hi) for lo, hi in zip(edges, edges[1:])]


def _fwd_kernel(*refs, edges, biased):
    """One step forward.  ``refs``: the ``_ROWS`` tokens before the
    block, the block, the taps, the bias if any, then a block of each
    part, of which the one that holds this step's channels is
    written."""
    y_ref = refs[1]
    at_start = pl.program_id(1) == 0

    # every read below is inside a pl.when: at the kernel's top level
    # the interpreter's discharge trips shard_map's check of varying
    # axes (as ops/pallas_attention.py's kernels note)
    def walk(out_ref):
        w, bias, row, before = _step_values(refs, biased, at_start)

        def put(rows, conv):
            pre = conv + bias
            _put(out_ref, rows, pre * jax.nn.sigmoid(pre))

        _walk_forward(y_ref.shape[1] // _ROWS, w, row, before,
                      lambda rows: y_ref[0, rows, :], put)

    for inside, out_ref in zip(_part_of(pl.program_id(2), edges),
                               refs[3 + biased:]):
        pl.when(inside)(functools.partial(walk, out_ref))


def _bwd_kernel(*refs, edges, biased):
    """One step backward, the token blocks taken last to first.
    ``refs``: the ``_ROWS`` tokens of ``y`` before the block, the block
    of ``y``, the taps, the bias if any, a block of each part's
    cotangent; then the block of ``y``'s cotangent, the taps' (and the
    bias's) gradient of this batch entry and these channels a sublane,
    summed over the token blocks; then what the block after this one
    left: its first tile's ``d_pre``, rotated for each tap."""
    n = len(edges) - 1
    y_ref, taps = refs[1], refs[2].shape[0]
    dy_ref, dw_ref, after_ref = refs[3 + biased + n:]
    at_start = pl.program_id(2) == pl.num_programs(2) - 1

    @pl.when(pl.program_id(2) == 0)                 # the LAST token block
    def _():
        # nothing after a sequence's end, and the sums start
        after_ref[...] = jnp.zeros_like(after_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def walk(ct_ref):
        w, bias, row, before = _step_values(refs, biased, at_start)

        def d_pre(rows, shifted):
            pre = sum(w[j] * shifted[j] for j in range(taps)) + bias
            sig = jax.nn.sigmoid(pre)
            return _tile(ct_ref, rows) * (sig * (1 + pre * (1 - sig)))

        def put(rows, dy):
            dy_ref[0, rows, :] = dy

        _walk_back(y_ref.shape[1] // _ROWS, w, row, before,
                   lambda rows: y_ref[0, rows, :], d_pre, put,
                   after_ref, dw_ref, biased)

    for inside, ct_ref in zip(_part_of(pl.program_id(1), edges),
                              refs[3 + biased:3 + biased + n]):
        pl.when(inside)(functools.partial(walk, ct_ref))


def _part_step(c, lo, hi):
    """Channel step ``c`` of all as a step of the part ``[lo, hi)``,
    held at the part's nearer edge outside it."""
    return jnp.clip(c - lo, 0, hi - lo - 1)


def _operand_specs(lanes, taps, biased, channel, token):
    """Block specifications of ``y``'s halo, ``y``, the taps and the
    bias, ``channel`` and ``token`` giving a grid point's steps."""
    per = TOKENS // _ROWS
    return [
        pl.BlockSpec((1, _ROWS, lanes), lambda *g: (
            g[0], jnp.maximum(token(*g) * per - 1, 0), channel(*g))),
        pl.BlockSpec((1, TOKENS, lanes),
                     lambda *g: (g[0], token(*g), channel(*g))),
        pl.BlockSpec((taps, lanes), lambda *g: (0, channel(*g))),
    ] + [pl.BlockSpec((1, lanes), lambda *g: (0, channel(*g)))] * biased


class _Plan(NamedTuple):
    """What the two kernels are built from, beside the arrays: the
    parts' channels, which of them leave head by head, the channels a
    step (:func:`_kernel_blocks`), and the forward trace's
    :func:`interpret_kernels` for both kernels."""
    sizes: tuple
    by_head: tuple
    lanes: int
    interpret: bool

    @property
    def edges(self):
        """The parts' edges in channel steps."""
        return tuple(sum(self.sizes[:p]) // self.lanes
                     for p in range(len(self.sizes) + 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused(y, w, bias, plan):
    """The parts of ``SiLU(conv(y) + bias)`` for ``y`` ``(B, T, C)``,
    ``w`` ``(taps, C)`` and ``bias`` ``(1, C)`` or None: ``(B, T,
    size)``, or ``(B, size // _LANES, T, _LANES)`` where
    ``plan.by_head`` says so.  The backward pass keeps the three
    arguments."""
    return _forward(y, w, bias, plan=plan)


def _part_spec(headed, lanes, index):
    """A part's (or its cotangent's) block specification: ``index``
    gives a grid point's ``(batch, token block, channel step)``."""
    if not headed:
        return pl.BlockSpec((1, TOKENS, lanes), index)

    def by_head(*g):
        b, i, c = index(*g)
        return b, c, i, 0

    return pl.BlockSpec((1, lanes // _LANES, TOKENS, _LANES), by_head)


def _like(y, shape):
    """A float32 result that varies over the mesh axes ``y`` does."""
    return jax.ShapeDtypeStruct(shape, jnp.float32, vma=jax.typeof(y).vma)


# Both calls under a ``jax.jit`` that is inlined where it is bound: a
# layer binds the forward twice (the block's remat) and a stack of
# layers as often again, and a kernel's body is traced at every bind;
# the jit's cache serves every bind after the first of a shape, and
# inlining keeps each site's own scope and phase in its op name.
_ONCE = functools.partial(jax.jit, inline=True, static_argnames="plan")


@_ONCE
def _forward(y, w, bias, *, plan):
    B, T, C = y.shape
    sizes, by_head, lanes, interpret = plan
    edges, biased = plan.edges, bias is not None
    # the channel steps innermost and in order: a part's block stays
    # where it is while the steps are in another part, so it is
    # written back once, when it is whole
    parts = pl.pallas_call(
        functools.partial(_fwd_kernel, edges=edges, biased=biased),
        grid=(B, T // TOKENS, C // lanes),
        in_specs=_operand_specs(lanes, w.shape[0], biased,
                                lambda b, i, c: c, lambda b, i, c: i),
        out_specs=[_part_spec(
            headed, lanes, lambda b, i, c, lo=lo, hi=hi: (
                b, i, _part_step(c, lo, hi)))
            for headed, lo, hi in zip(by_head, edges, edges[1:])],
        out_shape=[_like(y, (B, size // _LANES, T, _LANES) if headed
                         else (B, T, size))
                   for headed, size in zip(by_head, sizes)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)(*((y, y, w) + (bias,) * biased))
    return tuple(parts)


@_ONCE
def _backward(y, w, bias, cts, *, plan):
    B, T, C = y.shape
    sizes, by_head, lanes, interpret = plan
    edges, biased = plan.edges, bias is not None
    taps, blocks = w.shape[0], T // TOKENS

    def token(b, c, i):
        return blocks - 1 - i

    def ct_spec(headed, lo, hi):
        # outside its part a cotangent's block stays put: no fetch
        def index(b, c, i):
            inside = (c >= lo) & (c < hi)
            return (b, jnp.where(inside, token(b, c, i), 0),
                    _part_step(c, lo, hi))
        return _part_spec(headed, lanes, index)

    dy, sums = pl.pallas_call(
        functools.partial(_bwd_kernel, edges=edges, biased=biased),
        grid=(B, C // lanes, blocks),
        in_specs=_operand_specs(lanes, taps, biased,
                                lambda b, c, i: c, token)
        + [ct_spec(*part) for part in zip(by_head, edges, edges[1:])],
        out_specs=[
            pl.BlockSpec((1, TOKENS, lanes),
                         lambda b, c, i: (b, token(b, c, i), c)),
            pl.BlockSpec((1, taps + biased, _ROWS, lanes),
                         lambda b, c, i: (b, 0, 0, c))],
        out_shape=[_like(y, y.shape),
                   _like(y, (B, taps + biased, _ROWS, C))],
        scratch_shapes=[pltpu.VMEM((taps, _ROWS, lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)(*((y, y, w) + (bias,) * biased + tuple(cts)))
    sums = jnp.sum(sums, axis=(0, 2))
    return dy, sums[:taps], sums[taps:] if biased else None


_fused.defvjp(
    lambda y, w, bias, plan: (_forward(y, w, bias, plan=plan), (y, w, bias)),
    lambda plan, kept, cts: _backward(*kept, cts, plan=plan))


# --------------------------------------------------------------------- #
# the doubly gated convolution: C * conv(B * x), one pass each way
# --------------------------------------------------------------------- #


def gated_short_conv(bcx, w):
    """``C * conv(B * x)`` for ``bcx = [B | C | x]`` ``(batch, T, 3 *
    channels)`` side by side as one projection leaves them, and one
    weight a channel a tap (``w``: ``(channels, taps)``): both gates and
    the causal depthwise convolution of :func:`causal_conv_silu`
    (``conv(z)_t = sum_j w_j z_(t - taps + 1 + j)``, the last tap on the
    token itself, nothing before the sequence's start), with no
    activation and no bias.  All in float32; ``(batch, T, channels)``.

    Lane-aligned float32 shapes (:func:`_kernel_blocks`) run one Pallas
    kernel forward, which reads the three parts where they lie and
    writes the result, and one backward, which reads them and the
    cotangent and writes the three parts' cotangents and the taps'
    sums: ``bcx`` and ``w`` are what is kept.  Any other shape is the
    three slices, two products and the sum over taps as written."""
    C, taps = w.shape
    if bcx.shape[-1] != 3 * C:
        raise ValueError(f"{bcx.shape} is not [B | C | x] of {C} channels")
    registry = get_registry()
    registry.inc("shortconv/sites")
    # what the op's backward keeps: its two arguments
    registry.inc("shortconv/bytes_kept", bcx.nbytes + w.nbytes)
    if _kernel_blocks(bcx.shape[1], (C,), taps) \
            and bcx.dtype == w.dtype == jnp.float32:
        over = tuple(jax.typeof(bcx).vma)
        return _gated(bcx, pcast(w.T, over, to="varying"),
                      interpret_kernels())
    return _gated_plain(bcx, w)


def _gated_plain(bcx, w):
    """The operator as written: three slices, the gate ``B``, a padded
    copy and ``taps`` shifted slices of it, the gate ``C``."""
    (C, taps), T = w.shape, bcx.shape[1]
    b, c, x = (bcx[..., i * C:(i + 1) * C] for i in range(3))
    padded = jnp.pad(b * x, ((0, 0), (taps - 1, 0), (0, 0)))
    return c * sum(padded[:, j:j + T] * w[:, j] for j in range(taps))


def _gated_fwd_kernel(b_halo, x_halo, b_ref, c_ref, x_ref, w_ref, out_ref):
    """One step forward: a block of ``B``, ``C`` and ``x``, the
    ``_ROWS`` tokens of ``B`` and ``x`` before it, the taps; the block
    of the result."""
    at_start = pl.program_id(1) == 0

    # inside a pl.when, as every read of _fwd_kernel is and for its
    # reason (the interpreter under shard_map)
    @pl.when(pl.program_id(1) >= 0)
    def _():
        def put(rows, conv):
            out_ref[0, rows, :] = c_ref[0, rows, :] * conv

        _walk_forward(
            b_ref.shape[1] // _ROWS, _tap_rows(w_ref),
            lax.broadcasted_iota(jnp.int32, (_ROWS, w_ref.shape[1]), 0),
            jnp.where(at_start, 0.0, b_halo[0] * x_halo[0]),
            lambda rows: b_ref[0, rows, :] * x_ref[0, rows, :], put)


def _gated_bwd_kernel(b_halo, x_halo, b_ref, c_ref, x_ref, w_ref, ct_ref,
                      db_ref, dc_ref, dx_ref, dw_ref, after_ref):
    """One step backward, the token blocks taken last to first, as
    :func:`_bwd_kernel` takes them: the operands of the forward step
    and the block of the result's cotangent; then the blocks of the
    three parts' cotangents, the taps' gradient of this batch entry and
    these channels a sublane, summed over the token blocks; then what
    the block after this one left: its first tile's cotangent of the
    convolution's sum, rotated for each tap."""
    at_start = pl.program_id(2) == pl.num_programs(2) - 1

    @pl.when(pl.program_id(2) == 0)                 # the LAST token block
    def _():
        after_ref[...] = jnp.zeros_like(after_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(pl.program_id(2) >= 0)
    def _():
        w = _tap_rows(w_ref)

        def d_sum(rows, shifted):
            ct = ct_ref[0, rows, :]
            dc_ref[0, rows, :] = ct * sum(
                w[j] * shifted[j] for j in range(len(w)))
            return ct * c_ref[0, rows, :]

        def put(rows, d_gated):
            db_ref[0, rows, :] = d_gated * x_ref[0, rows, :]
            dx_ref[0, rows, :] = d_gated * b_ref[0, rows, :]

        _walk_back(
            b_ref.shape[1] // _ROWS, w,
            lax.broadcasted_iota(jnp.int32, (_ROWS, w_ref.shape[1]), 0),
            jnp.where(at_start, 0.0, b_halo[0] * x_halo[0]),
            lambda rows: b_ref[0, rows, :] * x_ref[0, rows, :], d_sum, put,
            after_ref, dw_ref)


def _gated_specs(steps, taps, channel, token):
    """Block specifications of the halos of ``B`` and ``x``, of ``B``,
    ``C`` and ``x`` and of the taps: :func:`_operand_specs`' for each
    part, all five read out of the one ``[B | C | x]``, part ``p``'s
    channels starting ``p * steps`` channel steps in."""
    (b_halo, b, w), (_, c, _), (x_halo, x, _) = (
        _operand_specs(_LANES, taps, False,
                       lambda *g, p=p: channel(*g) + p * steps, token)
        for p in range(3))
    return [b_halo, x_halo, b, c, x, w]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gated(bcx, w, interpret):
    """``C * conv(B * x)`` for ``bcx`` ``(batch, T, 3 C)`` and ``w``
    ``(taps, C)``, a lane tile of channels a step (seven blocks are in
    flight going back).  The backward pass keeps both."""
    return _gated_forward(bcx, w, interpret=interpret)


_ONCE_GATED = functools.partial(jax.jit, inline=True,
                                static_argnames="interpret")


@_ONCE_GATED
def _gated_forward(bcx, w, *, interpret):
    (B, T, _), (taps, C) = bcx.shape, w.shape
    steps = C // _LANES
    return pl.pallas_call(
        _gated_fwd_kernel,
        grid=(B, T // TOKENS, steps),
        in_specs=_gated_specs(steps, taps,
                              lambda b, i, c: c, lambda b, i, c: i),
        out_specs=pl.BlockSpec((1, TOKENS, _LANES), lambda b, i, c: (b, i, c)),
        out_shape=_like(bcx, (B, T, C)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret)(*((bcx,) * 5 + (w,)))


@_ONCE_GATED
def _gated_backward(bcx, w, ct, *, interpret):
    (B, T, _), (taps, C) = bcx.shape, w.shape
    steps, blocks = C // _LANES, T // TOKENS

    def token(b, c, i):
        return blocks - 1 - i

    block = pl.BlockSpec((1, TOKENS, _LANES),
                         lambda b, c, i: (b, token(b, c, i), c))
    db, dc, dx, sums = pl.pallas_call(
        _gated_bwd_kernel,
        grid=(B, steps, blocks),
        in_specs=_gated_specs(steps, taps, lambda b, c, i: c, token)
        + [block],
        out_specs=[block] * 3 + [pl.BlockSpec(
            (1, taps, _ROWS, _LANES), lambda b, c, i: (b, 0, 0, c))],
        out_shape=[_like(bcx, (B, T, C))] * 3
        + [_like(bcx, (B, taps, _ROWS, C))],
        scratch_shapes=[pltpu.VMEM((taps, _ROWS, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)(*((bcx,) * 5 + (w, ct)))
    # side by side again, as the projection's backward reads them
    return (jnp.concatenate([db, dc, dx], axis=-1),
            jnp.sum(sums, axis=(0, 2)))


_gated.defvjp(
    lambda bcx, w, interpret: (
        _gated_forward(bcx, w, interpret=interpret), (bcx, w)),
    lambda interpret, kept, ct: _gated_backward(
        *kept, ct, interpret=interpret))


def slab_size(n_chunks: int, most: int) -> int:
    """The largest divisor of ``n_chunks`` that is at most ``most``:
    how many chunks are differentiated at a time."""
    slab = min(most, n_chunks)
    while n_chunks % slab:
        slab -= 1
    return slab


def scan_slabs(slab_fn, state, xs, out):
    """``out(ys)`` of ``lax.scan`` of ``slab_fn(state, x) -> (state,
    y)`` over the leading (slab) axis of ``xs``, the body under
    ``jax.checkpoint``: what the backward pass keeps is the state at
    each slab's start and a slab's own inputs; everything else of a
    slab lives only while that slab is differentiated.  ``out`` lays
    the stacked ``ys`` out as the op hands them on.

    The state as it enters each slab and the op's output wear
    ``RECURRENT_RESIDUAL_NAMES``, outside the slab's checkpoint: a
    checkpoint around the op whose policy keeps those names
    (``TransformerConfig.checkpoint_fn``) does not run this scan a
    second time for what the backward pass reads of it.  The output's
    bytes are counted a call as it is traced
    (``recurrent/residual_bytes_kept``); the states' are the op's own
    ``*/state_bytes_kept``, kept with or without the names."""
    # a function of this call's own: jax.checkpoint keeps a trace by
    # function and shapes, and kernels inside are traced for the
    # platform this call is traced for
    slab = jax.checkpoint(lambda S, x: slab_fn(S, x))
    _, ys = lax.scan(
        lambda S, x: slab(checkpoint_name(S, RECURRENT_RESIDUAL_NAMES[0]), x),
        state, xs)
    y = checkpoint_name(out(ys), RECURRENT_RESIDUAL_NAMES[1])
    get_registry().inc("recurrent/residual_bytes_kept", y.nbytes)
    return y
