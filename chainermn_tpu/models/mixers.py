"""The token mixers of the flagship transformer: which exist, and for
each what only it knows -- the checks of its :class:`AttentionKind`
fields, its parameter tree, its initialisers, its partition specs and
its forward.  One table, :data:`MIXERS`, one record a mixer;
``models/transformer.py`` reads the table and names no mixer, so a new
one costs its op under ``ops/``, a record here and its scopes
(``utils/telemetry.py``).

The arrows point one way: ``transformer -> mixers -> ops, parallel``.
Nothing here imports ``models/transformer.py``; the config is
duck-typed (``cfg.d_model``, ``cfg.heads_of(kind)`` ...), as the kind
is there.  The helpers both sides need live here too and are
re-exported there: the norms, the two initialisers, ``apply_rope``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from chainermn_tpu.ops.gdn import gdn_chunked
from chainermn_tpu.ops.kda import kda_chunked
from chainermn_tpu.ops.recurrent import causal_conv_silu, gated_short_conv
from chainermn_tpu.ops.ssd import ssd_chunked
from chainermn_tpu.ops.pallas_attention import (
    flash_attention,
    flash_attention_supported,
    interpret_kernels,
)
from chainermn_tpu.parallel.ring_attention import (
    _block_positions,
    broadcast_kv,
    local_attention,
    ring_attention,
)
from chainermn_tpu.parallel.tensor import (
    column_parallel_dense,
    row_parallel_dense,
)
from chainermn_tpu.parallel.ulysses import ulysses_attention
from chainermn_tpu.utils.telemetry import device_scope

# KDA's L2 norm of q and k: y * rsqrt(sum(y^2) + eps).  The published
# config file carries no key for it; a benchmark driver holds its
# reference's value to this one
KDA_L2_NORM_EPS = 1e-6

# the parts of a layer an :class:`AttentionKind` may have: the check
# and its message read these (the mixers it may name are ``MIXERS``'
# keys, at the end of this file)
PARTS = ("both", "mixer", "mlp")

@dataclass(frozen=True)
class AttentionKind:
    """One kind of layer of a model whose layers differ: which parts it
    has, its mixer, and for the mixer what only it has -- softmax
    attention its window, rotary parameters and query heads; latent
    attention its latent rank and the widths of its shared key part and
    its values; the delta-rule layers their convolution (the
    scalar-decay one also its key heads and the two head widths); the
    state-space layer its heads' width, state size, groups and
    convolution; the short-convolution layer its taps.
    ``TransformerConfig.layer_pattern`` is a tuple of these, one per
    layer of a period (``leading_layers`` one per layer before them).
    Every field is read by the training path alone: by
    ``models/transformer.py``'s ``_init_block``, ``_block_specs``,
    ``_block`` and ``_attention``, and through them by the ``check``,
    ``tree``, ``out_width``, ``init``, ``specs`` and ``apply`` of the
    mixer's record in ``MIXERS``."""
    name: str                  # names the layer's scope: ``attn/<name>``
    part: str = "both"         # "both": a mixer, then an MLP, each
    # behind its own norm and residual add | "mixer": the mixer alone |
    # "mlp": the feed-forward part alone (the config's: sparse where
    # ``moe``), and no mixer field is read.  A layer of one part holds
    # that part's leaves and ONE norm (``ln1`` a mixer's, ``ln2`` an
    # MLP's) and adds to the residual stream once
    mixer: str = "softmax"     # "softmax": the config's attention core
    # over q/k/v heads of d_head, rotated as below | "mla": multi-head
    # latent attention without rotary (``mla_use_nope``): queries of
    # d_head + d_shared_key straight from the input, keys and values
    # from one normed latent of rank ``kv_latent``, the last
    # ``d_shared_key`` key channels one vector shared by every head,
    # values ``d_value`` wide; causal softmax with the scale of the
    # whole key width, through the flash kernels (``attention="flash"``)
    # or XLA (``"local"``) | "kda": Kimi Delta Attention
    # (``ops/kda.py``): q, k, v through a causal depthwise convolution
    # of ``conv_taps`` and SiLU, q and k L2-normed a head, a decay a
    # channel and a step size a head from the input, the delta rule
    # over a ``d_head x d_head`` state a head, a per-head RMSNorm and
    # a sigmoid gate on the way out | "mamba2": the Mamba-2 state-space
    # layer (``ops/ssd.py``): one projection to a gate ``z``, to ``x``
    # (``n_heads`` heads of ``ssm_head_dim``), ``B`` and ``C``
    # (``ssm_groups`` groups of ``ssm_state``) and to a step a head;
    # ``x``, ``B``, ``C`` through a causal depthwise convolution of
    # ``conv_taps`` with bias and SiLU; a scalar decay a head over a
    # ``ssm_head_dim x ssm_state`` state a head, a skip ``D x``, the
    # gate ``SiLU(z)``, then an RMSNorm over each group's channels |
    # "gdn": Gated DeltaNet (``ops/gdn.py``): one projection to q and k
    # (``key_heads`` heads of ``d_key``), v and an output gate ``z``
    # (``n_heads`` value heads of ``d_value``), one to a step and a
    # decay's input a value head; q, k, v through a causal depthwise
    # convolution of ``conv_taps`` and SiLU, q and k L2-normed a head;
    # the delta rule with ONE scalar decay a value head over a ``d_key
    # x d_value`` state, value head j reading key head j // (n_heads /
    # key_heads); a per-head RMSNorm with one plain scale for all
    # heads, THEN the gate ``SiLU(z)`` (norm first, gate after) |
    # "shortconv": the doubly gated short convolution
    # (``ops/recurrent.py`` ``gated_short_conv``): one projection to
    # ``[B | C | x]`` of ``d_model`` channels each, ``C * conv(B * x)``
    # with a causal depthwise convolution of ``conv_taps``, no
    # activation, no bias, no norm, no heads and no state past
    # ``conv_taps - 1`` tokens.
    # Of the six only softmax takes positions: window, rotary and YaRN
    # fields and ``qk_norm`` are its own
    kv_latent: int = 0         # mla: rank of the key-value latent
    d_shared_key: int = 0      # mla: key channels shared by the heads
    d_value: int = 0           # mla: value head width; 0 => d_head.
    # gdn: a value head's width (its ``n_heads`` count the value heads)
    key_heads: int = 0         # gdn: heads of q and k, each serving
    # n_heads / key_heads value heads
    d_key: int = 0             # gdn: a key head's width
    conv_taps: int = 4         # kda, mamba2, gdn, shortconv: taps of the
    # short convolution
    qk_norm: bool = False      # softmax: an RMSNorm with a learned scale
    # over each head of q and of k (``q_norm``, ``k_norm``, one scale of
    # d_head each for all heads), before any rotation
    ssm_head_dim: int = 0      # mamba2: channels a head (its ``n_heads``
    # are this kind's own: the config's are softmax attention's)
    ssm_state: int = 0         # mamba2: the state's size N a channel
    ssm_groups: int = 0        # mamba2: groups of B and C; head j reads
    # group j // (n_heads / ssm_groups)
    window: int = 0            # 0 => full causal; W>0 => (t-W, t]
    rope_theta: float = 10000.0
    n_heads: int = 0           # 0 => the config's n_heads.  Else this
    # kind's own query heads over the config's key-value heads: its
    # layers' wq, wo and gate have that many and no more
    rotary_share: float = 1.0  # the leading part of each head that is
    # rotated (a partial rotary factor); the rest passes through.  The
    # frequencies, YaRN's ramp included, are those of a head of that
    # many dimensions.  0 => nothing is rotated: a softmax layer that
    # takes no positions at all
    # YaRN (Peng et al., arXiv:2309.00071), as published configs state
    # it: frequencies whose wavelength exceeds the original context are
    # divided by ``yarn_factor``, those that turn often within it are
    # kept, with a linear blend between ``yarn_beta_fast`` and
    # ``yarn_beta_slow`` turns; cos and sin are multiplied by
    # ``attention_factor``.  ``yarn_factor == 0`` => plain rope.
    yarn_factor: float = 0.0
    yarn_original_max: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    attention_factor: float = 1.0

    def __post_init__(self):
        if not self.name or "/" in self.name:
            raise ValueError(f"attention kind name {self.name!r}")
        if self.mixer not in MIXERS:
            raise ValueError(
                f"{self.name}: mixer {self.mixer!r} not in "
                f"({', '.join(MIXERS)})")
        if self.part not in PARTS:
            raise ValueError(
                f"{self.name}: part {self.part!r} not in "
                f"({', '.join(PARTS)})")
        mixer = MIXERS[self.mixer]
        mixer.check(self)
        if not mixer.takes_positions and (
                self.window or self.yarn_factor or self.qk_norm):
            raise ValueError(
                f"{self.name}: window, rotary fields and qk_norm are the "
                f"softmax mixer's; mixer={self.mixer!r} takes no positions")
        if self.window < 0:
            raise ValueError(f"{self.name}: window {self.window} < 0")
        if self.rope_theta <= 1:
            raise ValueError(f"{self.name}: rope_theta {self.rope_theta}")
        if self.yarn_factor and (self.yarn_factor < 1
                                 or self.yarn_original_max < 1):
            raise ValueError(
                f"{self.name}: yarn needs factor >= 1 and the original "
                f"context, got {self.yarn_factor}, {self.yarn_original_max}")
        if self.n_heads < 0:
            raise ValueError(f"{self.name}: n_heads {self.n_heads} < 0")
        if not 0 <= self.rotary_share <= 1:
            raise ValueError(
                f"{self.name}: rotary_share {self.rotary_share} not in [0, 1]")

    @property
    def tree(self):
        """What of this kind decides its layer's parameter tree, beside
        the query heads."""
        if self.part == "mlp":
            return ("mlp",)
        return (self.part, self.mixer) + MIXERS[self.mixer].tree(self)

    def rotary_dim(self, d_head: int) -> int:
        """How many leading dimensions of a head are rotated."""
        return int(d_head * self.rotary_share)

    def inv_freq(self, d_head: int):
        """The ``rotary_dim / 2`` rotary frequencies, as float64 numpy
        (constants of the compiled step)."""
        d_head = self.rotary_dim(d_head)
        half = d_head // 2
        base = self.rope_theta ** (-np.arange(half, dtype=np.float64) / half)
        if not self.yarn_factor:
            return base

        def turns_at(n):   # the dimension that turns n times in the context
            return d_head * math.log(self.yarn_original_max / (
                2 * math.pi * n)) / (2 * math.log(self.rope_theta))

        lo = max(math.floor(turns_at(self.yarn_beta_fast)), 0)
        hi = min(math.ceil(turns_at(self.yarn_beta_slow)), d_head - 1)
        ramp = np.clip((np.arange(half) - lo) / max(hi - lo, 1e-3), 0, 1)
        return ramp * base / self.yarn_factor + (1 - ramp) * base


# --------------------------------------------------------------------- #
# what both sides need: the norms, the initialisers, the rotary
# --------------------------------------------------------------------- #


def _norm_init(cfg, shape):
    """A learned norm scale at its seed: 1, or 0 where the config's
    norms add 1 to what they store (``norm_scale``)."""
    fill = jnp.zeros if cfg.norm_scale == "zero_centred" else jnp.ones
    return fill(shape, jnp.float32)


def _dense_init(k, shape, fan_in):
    return jax.random.normal(k, shape, jnp.float32) * (fan_in ** -0.5)


def _rms_norm(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * r * scale).astype(x.dtype)


def _norm(cfg, x, w):
    """The RMSNorm of a learned scale stored as ``w``, as the config's
    ``norm_scale`` reads it: ``w`` itself, or ``1 + w``."""
    return _rms_norm(x, 1.0 + w if cfg.norm_scale == "zero_centred" else w,
                     cfg.norm_eps)


def apply_rope(x, positions, theta: float = 10000.0, inv_freq=None,
               scale: float = 1.0):
    """Rotary embedding (rotate-half convention) on ``x`` (..., T, H, D)
    at absolute ``positions`` — ``(T,)`` shared across the batch, or
    ``(B, T)`` per-row (left-padded decoding gives each row its own
    position origin).  Rotations are absolute per token but the QK dot
    depends only on position DIFFERENCES — so sharded callers (ring
    shards, zigzag layouts, KV caches) just pass each token's own
    global position and relative attention falls out, with no position
    parameters to learn or extend.

    ``inv_freq`` replaces ``theta``'s frequencies and ``scale``
    multiplies cos and sin.  Fewer than ``d_head/2`` of them rotate the
    leading ``2·len(inv_freq)`` dimensions of each head (rotate-half
    within that part) and pass the rest through: a partial rotary.

    The trig tables are (T, d_head/2) — negligible next to the T² score
    matrix, so they are recomputed per call (the layer-invariant parts
    are XLA CSE-hoistable) instead of threading a cache through every
    stage signature."""
    half = x.shape[-1] // 2
    if inv_freq is None:
        freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    else:
        # a kind's own frequencies (AttentionKind.inv_freq) and the
        # factor its cos and sin carry (YaRN's attention factor)
        freqs = jnp.asarray(inv_freq, jnp.float32)
        if freqs.shape[0] < half:
            half = freqs.shape[0]
            return jnp.concatenate([
                apply_rope(x[..., :2 * half], positions, inv_freq=inv_freq,
                           scale=scale), x[..., 2 * half:]], axis=-1)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., T, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    cos = cos[..., None, :].astype(x.dtype)           # (..., T, 1, half)
    sin = sin[..., None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _require_flash(T):
    """``attention="flash"`` as asked or not at all: no silent stand-in,
    a run that asked for the kernel and got the XLA attention would be
    measured as the kernel."""
    if lax.axis_size("seq") != 1:
        raise ValueError(
            'attention="flash" covers only the unsharded-sequence '
            'case (mesh seq axis is '
            f'{lax.axis_size("seq")}); use attention="ring" to '
            "shard the sequence")
    if not flash_attention_supported(T, T):
        raise ValueError(
            f'attention="flash" cannot tile a sequence of {T}: '
            "lengths must be multiples of 8 and either fit one "
            "block or divide by a power-of-two block >= 128 "
            '(flash_attention_supported); use attention="local" '
            "for the XLA path")


def _exchanged_or_local_core(cfg, q, k, v, win):
    """The attention core where it is not the flash kernel alone: the
    ring, Ulysses' exchange, or XLA's own attention."""
    T = q.shape[1]
    if cfg.attention == "ring":
        # flagship long-context path: ring schedule with the Pallas
        # kernel as the per-pair compute whenever the local block shape
        # fits the kernel (interpret mode keeps one config working on
        # non-TPU backends); XLA einsum blocks otherwise
        use_flash = flash_attention_supported(T, T)
        if cfg.seq_layout == "zigzag":
            # each zigzag half-run must itself fit the kernel's blocks
            use_flash = flash_attention_supported(T // 2, T // 2)
        return ring_attention(q, k, v, axis_name="seq", causal=True,
                              window=win,
                              remat=cfg.remat, use_flash=use_flash,
                              bwd_block_q=cfg.flash_bwd_block_q or None,
                              bwd_block_k=cfg.flash_bwd_block_k or None,
                              layout=cfg.seq_layout,
                              interpret=interpret_kernels())
    if cfg.attention == "ulysses":
        # after the head<->seq exchange each device holds the FULL
        # sequence for its head subset — the flash kernel slots straight
        # in (static zero offsets), falling back to the XLA path when
        # the full length doesn't fit the kernel's block contract
        T_full = T * lax.axis_size("seq")
        if flash_attention_supported(T_full, T_full):
            fa = partial(flash_attention,
                         bwd_block_q=cfg.flash_bwd_block_q or None,
                         bwd_block_k=cfg.flash_bwd_block_k or None,
                         interpret=interpret_kernels())
            return ulysses_attention(q, k, v, axis_name="seq", causal=True,
                                     window=win,
                                     attn_fn=fa)
        return ulysses_attention(q, k, v, axis_name="seq", causal=True,
                                 window=win)
    if cfg.attention == "local":
        return local_attention(q, k, v, causal=True,
                               window=win)
    raise ValueError(cfg.attention)


# --------------------------------------------------------------------- #
# the mixers: for each its checks, its leaves at their seeds, its forward
# --------------------------------------------------------------------- #


def _softmax_init(key, ks, cfg, kind):
    D, Dh, H = cfg.d_model, cfg.d_head, cfg.heads_of(kind)
    block = {}
    if cfg.kv_heads == H:
        block["wqkv"] = _dense_init(ks[0], (D, 3, H, Dh), D)
    else:
        # GQA/MQA: Hkv shared K/V heads, each serving H/Hkv query heads
        # (consecutive grouping: query head h reads kv head h//(H/Hkv))
        block["wq"] = _dense_init(ks[0], (D, H, Dh), D)
        block["wkv"] = _dense_init(ks[5], (D, 2, cfg.kv_heads, Dh), D)
    if cfg.attn_gate:
        block["wg"] = _dense_init(
            jax.random.fold_in(key, 7),
            (D, H) + (Dh,) * (cfg.attn_gate == "per_element"), D)
    if kind is not None and kind.qk_norm:
        block["q_norm"] = _norm_init(cfg, (Dh,))
        block["k_norm"] = _norm_init(cfg, (Dh,))
    return block


def _softmax_specs(cfg, kind, mha: bool):
    if mha:
        blk = {"wqkv": P("pipe", None, None, None, "model", None)}
    else:
        blk = {"wq": P("pipe", None, None, "model", None),
               "wkv": P("pipe", None, None, None, "model", None)}
    if cfg.attn_gate:
        # (D, H) a head, (D, H, d_head) an element
        blk["wg"] = P("pipe", None, None, "model")
    if kind is not None and kind.qk_norm:
        blk["q_norm"] = blk["k_norm"] = P("pipe")
    return blk


def _softmax_mixer(cfg, x, blk, kind):
    """Softmax attention on the normed input ``x`` (its norm wears
    ``attn.qkv`` too: the record's ``norm_scope``): column-parallel QKV
    (heads sharded over ``model``), seq-parallel core (ring/Ulysses over
    ``seq``), row-parallel output.  ``kind`` is the layer's
    :class:`AttentionKind` under a ``layer_pattern``: its window and
    rotary parameters then stand in for the config's (None: an untyped
    layer)."""
    cd = cfg.compute_dtype
    win = (kind.window if kind else cfg.attention_window) or None
    B, T, D = x.shape
    with device_scope("attn.qkv"):
        if "wqkv" in blk:
            Hl = blk["wqkv"].shape[2]      # local heads = H / model-axis size
            qkv = column_parallel_dense(
                x, blk["wqkv"].reshape(D, -1).astype(cd))
            qkv = qkv.reshape(B, T, 3, Hl, cfg.d_head)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            gate = column_parallel_dense(
                x, blk["wg"].reshape(D, -1).astype(cd)) \
                if "wg" in blk else None
        else:
            # GQA/MQA: H/Hkv query heads share each K/V head.  K/V stay at
            # their natural (shared) width all the way through the attention
            # cores — the ring rotates and Ulysses exchanges Hkv-head blocks
            # (ICI traffic shrinks by H/Hkv) and the grouped einsums read the
            # shared heads in place.  Local (per model-rank) grouping equals
            # global grouping because both H and Hkv shard over the same
            # axis: global query head r·Hl+i reads kv head r·Hkvl + i//rep
            # for rep = Hl/Hkvl = H/Hkv (mesh divisibility is validated at
            # shard/jit build time by _check_mesh).
            Hl = blk["wq"].shape[1]
            Hkvl = blk["wkv"].shape[2]
            # ONE fused projection dot, like the MHA wqkv path: concatenating
            # the (local-shard) weights along the output dim reads the
            # activations once instead of twice — the concat costs one
            # weight-sized copy, far less than the saved (B,T,D) re-read at
            # training shapes, and removes a dispatch on the decode path.
            # The at-rest params stay separate (their TP/FSDP specs differ).
            dq = Hl * cfg.d_head
            dkv = 2 * Hkvl * cfg.d_head
            # the gate's projection (Hl more columns a head, or Hl x
            # d_head an element) rides it too
            fused = jnp.concatenate(
                [blk["wq"].reshape(D, -1), blk["wkv"].reshape(D, -1)]
                + ([blk["wg"].reshape(D, -1)] if "wg" in blk else []),
                axis=1).astype(cd)
            qkv = column_parallel_dense(x, fused)
            q = qkv[..., :dq].reshape(B, T, Hl, cfg.d_head)
            kv = qkv[..., dq:dq + dkv].reshape(B, T, 2, Hkvl, cfg.d_head)
            k, v = kv[:, :, 0], kv[:, :, 1]
            gate = qkv[..., dq + dkv:] if "wg" in blk else None
    if "q_norm" in blk:
        with device_scope("attn.qk_norm"):
            # over each head's d_head, one scale for all heads
            q = _norm(cfg, q, blk["q_norm"])
            k = _norm(cfg, k, blk["k_norm"])
    if cfg.pos_embedding == "rope" and (kind is None or kind.rotary_share):
        with device_scope("attn.rope"):
            # rotate by each local token's GLOBAL position BEFORE any ring
            # rotation / Ulysses exchange — relative attention then holds
            # across shard boundaries by construction
            pos = _block_positions(
                lax.axis_index("seq"), T, lax.axis_size("seq"),
                cfg.seq_layout if cfg.attention == "ring" else "contiguous")
            rope = dict(theta=cfg.rope_theta) if kind is None else dict(
                inv_freq=kind.inv_freq(cfg.d_head),
                scale=kind.attention_factor)
            q = apply_rope(q, pos, **rope)
            k = apply_rope(k, pos, **rope)
    if cfg.attention == "flash":
        # Pallas kernel: compiled when the step was built for TPU
        # devices, interpreted otherwise (interpret_kernels).  The
        # kernels wear ``attn.core`` themselves (forward, backward); the
        # relayouts around them stay the layer's own
        _require_flash(T)
        with device_scope("attn.kv_repeat"):
            # kernel wants matching head counts
            k, v = broadcast_kv(k, v, q.shape[2] // k.shape[2])
        o = flash_attention(
            q, k, v, causal=True,
            window=win,
            bwd_block_q=cfg.flash_bwd_block_q or None,
            bwd_block_k=cfg.flash_bwd_block_k or None,
            interpret=interpret_kernels())
    else:
        with device_scope("attn.core"):
            o = _exchanged_or_local_core(cfg, q, k, v, win)
    if gate is not None:
        with device_scope("attn.gate"):
            # o_j <- sigmoid(x W_g)_j o_j, one scalar a local query head
            # or one an element of it.  Its backward reads the core's o,
            # which the block's checkpoint already keeps where the core
            # is the flash kernel
            gate = jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)
            o = o * (gate.reshape(o.shape) if blk["wg"].ndim == 3
                     else gate[..., None])
    # named for the "dots" remat policy, which saves it as the input of
    # the output projection's backward.  It never kept the flash kernel
    # out of the recompute (the kernel's residuals are its own o and
    # lse: FLASH_RESIDUAL_NAMES, saved by checkpoint_fn); counted in the
    # traced gradient, what it spares a layer is the p·v product under
    # "local", the exchange back under "ulysses", and a transpose (for a
    # second copy of o) under "flash" and "ring"
    o = checkpoint_name(o, "attn_out")
    with device_scope("attn.out"):
        return row_parallel_dense(
            o.reshape(B, T, -1), blk["wo"].reshape(-1, D).astype(cd))


def _mla_check(kind):
    if kind.kv_latent < 1 or kind.d_shared_key < 0 or kind.d_value < 0:
        raise ValueError(
            f"{kind.name}: mla needs kv_latent >= 1 and widths >= 0, "
            f"got {kind.kv_latent}, {kind.d_shared_key}, {kind.d_value}")


def _mla_init(key, ks, cfg, kind):
    D, Dh, H = cfg.d_model, cfg.d_head, cfg.heads_of(kind)
    L, Ds, Dv = kind.kv_latent, kind.d_shared_key, kind.d_value or Dh
    return {
        "wq": _dense_init(ks[0], (D, H, Dh + Ds), D),
        "wkva": _dense_init(ks[5], (D, L + Ds), D),
        "kv_norm": _norm_init(cfg, (L,)),
        "wkvb": _dense_init(
            jax.random.fold_in(key, 11), (L, H, Dh + Dv), L),
    }


def _mla_mixer(cfg, x, blk, kind):
    """Latent attention without rotary on the normed input ``x``: the
    layer's contribution to the residual stream.  The shared key part is
    copied out to the heads ahead of the kernel (as ``broadcast_kv``
    does for grouped heads); the kernel takes keys of ``d_head +
    d_shared_key`` and values of ``d_value`` as they are."""
    cd = cfg.compute_dtype
    B, T, D = x.shape
    H, L, Ds, Dn = (blk["wq"].shape[1], kind.kv_latent, kind.d_shared_key,
                    cfg.d_head)
    with device_scope("attn.qkv"):
        q = (x @ blk["wq"].reshape(D, -1).astype(cd)).reshape(
            B, T, H, Dn + Ds)
    with device_scope("mla/latent"):
        down = x @ blk["wkva"].astype(cd)
        latent = _norm(cfg, down[..., :L], blk["kv_norm"])
        up = (latent @ blk["wkvb"].reshape(L, -1).astype(cd)).reshape(
            B, T, H, -1)
    with device_scope("attn.kv_repeat"):
        k = jnp.concatenate([up[..., :Dn], jnp.broadcast_to(
            down[:, :, None, L:], (B, T, H, Ds))], axis=-1)
        v = up[..., Dn:]
    if cfg.attention == "flash":
        _require_flash(T)
        o = flash_attention(
            q, k, v, causal=True,
            bwd_block_q=cfg.flash_bwd_block_q or None,
            bwd_block_k=cfg.flash_bwd_block_k or None,
            interpret=interpret_kernels())
    else:
        with device_scope("attn.core"):
            o = local_attention(q, k, v, causal=True)
    o = checkpoint_name(o, "attn_out")
    with device_scope("attn.out"):
        return o.reshape(B, T, -1) @ blk["wo"].reshape(-1, D).astype(cd)


def _check_conv_taps(kind):
    if kind.conv_taps < 1:
        raise ValueError(
            f"{kind.name}: {kind.mixer} needs conv_taps >= 1, got "
            f"{kind.conv_taps}")


def _kda_init(key, ks, cfg, kind):
    D, Dh, H = cfg.d_model, cfg.d_head, cfg.heads_of(kind)
    # the two-matrix projections of the decay and of the output
    # gate go through a rank of d_head
    R, taps = Dh, kind.conv_taps
    kk = iter(jax.random.split(jax.random.fold_in(key, 12), 8))
    block = {}
    block["wqkv"] = _dense_init(ks[0], (D, 3, H, Dh), D)
    block["conv"] = _dense_init(next(kk), (3, H, Dh, taps), taps)
    block["wf_a"] = _dense_init(next(kk), (D, R), D)
    block["wf_b"] = _dense_init(next(kk), (R, H, Dh), R)
    # the published initialisers: exp(a_log) uniform in [1, 16]; the
    # step's bias the inverse softplus of a log-uniform [1e-3, 1e-1]
    block["a_log"] = jnp.log(jax.random.uniform(
        next(kk), (H,), jnp.float32, 1.0, 16.0))
    dt = jnp.exp(jax.random.uniform(
        next(kk), (H, Dh), jnp.float32, math.log(1e-3), math.log(1e-1)))
    block["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
    block["wbeta"] = _dense_init(next(kk), (D, H), D)
    block["wg_a"] = _dense_init(next(kk), (D, R), D)
    block["wg_b"] = _dense_init(next(kk), (R, H, Dh), R)
    block["o_norm"] = jnp.ones((Dh,), jnp.float32)
    return block


def _l2_unit(y):
    """``y`` over its L2 norm along the last axis (a head's channels):
    the delta-rule mixers' norm of q and k."""
    return y * lax.rsqrt(
        jnp.sum(y * y, axis=-1, keepdims=True) + KDA_L2_NORM_EPS)


def _kda_mixer(cfg, x, blk, kind):
    """Kimi Delta Attention on the normed input ``x``: the layer's
    contribution to the residual stream.  Projections in the compute
    dtype with float32 results; convolution, norms, gates and the
    recurrence (``ops/kda.py``) in float32.  ``kda/conv`` holds the
    convolution (``ops/recurrent.py``: at whole lane tiles and token
    blocks one Pallas kernel forward that hands back q, k and v, one
    backward; the plain sum over taps otherwise) and the L2 norms after
    it; ``kda/scan`` holds the whole op, its Pallas kernel for the
    chunks' unit-triangular systems included.  Off the TPU the kernels
    are interpreted, as the flash kernels are."""
    cd, f32 = cfg.compute_dtype, jnp.float32
    B, T, D = x.shape
    H, Dh = blk["wqkv"].shape[2:]

    def project(*ws):
        y = x
        for w in ws:
            y = jnp.dot(y.astype(cd), w.reshape(w.shape[0], -1).astype(cd),
                        preferred_element_type=f32)
        return y

    with device_scope("attn.qkv"):
        qkv = project(blk["wqkv"]).reshape(B, T, 3, H, Dh)
    with device_scope("kda/conv"):
        q, k, v = causal_conv_silu(qkv, blk["conv"], split=((H, Dh),) * 3)
        q, k = _l2_unit(q) * Dh ** -0.5, _l2_unit(k)
    with device_scope("kda/gate"):
        # the recurrence's two gates: the log of the decay a channel
        # (<= 0) and the step size a head
        g = -jnp.exp(blk["a_log"])[:, None] * jax.nn.softplus(
            project(blk["wf_a"], blk["wf_b"]).reshape(B, T, H, Dh)
            + blk["dt_bias"])
        beta = jax.nn.sigmoid(project(blk["wbeta"]))
    with device_scope("kda/scan"):
        o = kda_chunked(q, k, v, g, beta)
    with device_scope("kda/gate"):
        # the way out: RMSNorm over each head with one scale for all,
        # times a sigmoid gate from the input
        o = _rms_norm(o, blk["o_norm"], cfg.norm_eps) * jax.nn.sigmoid(
            project(blk["wg_a"], blk["wg_b"]).reshape(B, T, H, Dh))
    o = checkpoint_name(o.astype(cd), "attn_out")
    with device_scope("attn.out"):
        return o.reshape(B, T, -1) @ blk["wo"].reshape(-1, D).astype(cd)


def _mamba2_check(kind):
    _check_conv_taps(kind)
    if min(kind.n_heads, kind.ssm_head_dim, kind.ssm_state,
           kind.ssm_groups) < 1 or kind.n_heads % kind.ssm_groups:
        raise ValueError(
            f"{kind.name}: mamba2 needs its own n_heads, ssm_head_dim, "
            "ssm_state and ssm_groups >= 1 and whole groups of heads, "
            f"got {kind.n_heads}, {kind.ssm_head_dim}, "
            f"{kind.ssm_state}, {kind.ssm_groups}")


def _mamba2_init(key, ks, cfg, kind):
    D, H, Dv = cfg.d_model, cfg.heads_of(kind), kind.ssm_head_dim
    # one projection to [z | x B C | dt]; the published initialisers:
    # exp(a_log) uniform in [1, 16], the step's bias the inverse
    # softplus of a log-uniform [1e-3, 1e-1] floored at 1e-4, D = 1
    inner, taps = H * Dv, kind.conv_taps
    conv = inner + 2 * kind.ssm_groups * kind.ssm_state
    kk = iter(jax.random.split(jax.random.fold_in(key, 13), 3))
    block = {}
    block["w_in"] = _dense_init(ks[0], (D, inner + conv + H), D)
    block["conv"] = _dense_init(next(kk), (conv, taps), taps)
    block["conv_b"] = jnp.zeros((conv,), jnp.float32)
    block["a_log"] = jnp.log(jax.random.uniform(
        next(kk), (H,), jnp.float32, 1.0, 16.0))
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        next(kk), (H,), jnp.float32, math.log(1e-3), math.log(1e-1))),
        1e-4)
    block["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
    block["d_skip"] = jnp.ones((H,), jnp.float32)
    block["o_norm"] = jnp.ones((inner,), jnp.float32)
    return block


def _mamba2_mixer(cfg, x, blk, kind):
    """Mamba-2 on the normed input ``x``: the layer's contribution to
    the residual stream.  Projections in the compute dtype with float32
    results; the convolution, the step, the decay, the recurrence's
    arrays (``ops/ssd.py``) and the gated norm in float32.  The
    convolution (``ops/recurrent.py``) hands back x, B and C apart: at
    whole lane tiles and token blocks it is a Pallas kernel with a
    backward kernel of its own, the layer's only ones."""
    cd, f32 = cfg.compute_dtype, jnp.float32
    B, T, D = x.shape
    H, P = blk["wo"].shape[:2]
    G, N = kind.ssm_groups, kind.ssm_state
    inner, grouped = H * P, G * N
    with device_scope("attn.qkv"):
        # one product to [z | x B C | dt]
        proj = jnp.dot(x.astype(cd), blk["w_in"].astype(cd),
                       preferred_element_type=f32)
        z, xbc, dt = (proj[..., :inner], proj[..., inner:-H],
                      proj[..., -H:])
    with device_scope("ssm/conv"):
        # flat parts: x's heads are half a lane tile wide, so the
        # kernel has no head-by-head form for them
        xs, b_in, c_out = causal_conv_silu(
            xbc, blk["conv"], blk["conv_b"], split=(inner, grouped, grouped))
        xs = xs.reshape(B, T, H, P)
        b_in, c_out = b_in.reshape(B, T, G, N), c_out.reshape(B, T, G, N)
    with device_scope("ssm/gate"):
        # the step a head (no clamp: time_step_limit (0, inf)) and the
        # decay's rate a head (< 0)
        dt = jax.nn.softplus(dt + blk["dt_bias"])
        a = -jnp.exp(blk["a_log"])
    with device_scope("ssm/scan"):
        y = ssd_chunked(xs, dt, a, b_in, c_out)
    with device_scope("ssm/gate"):
        # the skip, the gate, then an RMSNorm over each group's channels
        # with a scale a channel (gate first, norm after)
        y = (y + blk["d_skip"][:, None] * xs).reshape(B, T, inner) \
            * jax.nn.silu(z)
        y = _rms_norm(y.reshape(B, T, G, inner // G),
                      blk["o_norm"].reshape(G, -1), cfg.norm_eps)
    o = checkpoint_name(y.reshape(B, T, inner).astype(cd), "attn_out")
    with device_scope("attn.out"):
        return o @ blk["wo"].reshape(-1, D).astype(cd)


def _gdn_check(kind):
    _check_conv_taps(kind)
    if min(kind.n_heads, kind.key_heads, kind.d_key,
           kind.d_value) < 1 or kind.n_heads % kind.key_heads:
        raise ValueError(
            f"{kind.name}: gdn needs its own n_heads (value heads), "
            "key_heads, d_key and d_value >= 1 and whole groups of "
            f"value heads a key head, got {kind.n_heads}, "
            f"{kind.key_heads}, {kind.d_key}, {kind.d_value}")


def _gdn_init(key, ks, cfg, kind):
    D, H, Dv = cfg.d_model, cfg.heads_of(kind), kind.d_value
    # one projection to [q | k | v | z], one to [b | a]; A_log and
    # dt_bias a value head, seeded as KDA's and Mamba-2's are
    keys, taps = kind.key_heads * kind.d_key, kind.conv_taps
    kk = iter(jax.random.split(jax.random.fold_in(key, 14), 4))
    block = {}
    block["w_in"] = _dense_init(ks[0], (D, 2 * keys + 2 * H * Dv), D)
    block["w_ba"] = _dense_init(next(kk), (D, 2 * H), D)
    block["conv"] = _dense_init(
        next(kk), (2 * keys + H * Dv, taps), taps)
    block["a_log"] = jnp.log(jax.random.uniform(
        next(kk), (H,), jnp.float32, 1.0, 16.0))
    dt = jnp.exp(jax.random.uniform(
        next(kk), (H,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    block["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
    block["o_norm"] = jnp.ones((Dv,), jnp.float32)
    return block


def _gdn_mixer(cfg, x, blk, kind):
    """Gated DeltaNet on the normed input ``x``: the layer's
    contribution to the residual stream.  Projections in the compute
    dtype with float32 results; convolution, L2 norms, gates and the
    recurrence (``ops/gdn.py``) in float32.  ``gdn/conv`` holds the
    convolution (``ops/recurrent.py``, which hands back q, k and v
    apart; a Pallas kernel forward and one backward at whole lane tiles
    and token blocks) and the L2 norms after it; ``gdn/scan`` holds the
    whole op, ``ops/kda.py``'s Pallas kernel for the chunks'
    unit-triangular systems included (all interpreted off the TPU)."""
    cd, f32 = cfg.compute_dtype, jnp.float32
    B, T, D = x.shape
    Hv, Dv = blk["wo"].shape[:2]
    Hk, Dk = kind.key_heads, kind.d_key
    keys, values = Hk * Dk, Hv * Dv
    with device_scope("attn.qkv"):
        # two products: to [q | k | v | z] and to [b | a]
        proj = jnp.dot(x.astype(cd), blk["w_in"].astype(cd),
                       preferred_element_type=f32)
        ba = jnp.dot(x.astype(cd), blk["w_ba"].astype(cd),
                     preferred_element_type=f32)
        qkv, z = proj[..., :2 * keys + values], proj[..., 2 * keys + values:]
    with device_scope("gdn/conv"):
        q, k, v = causal_conv_silu(
            qkv, blk["conv"], split=((Hk, Dk), (Hk, Dk), (Hv, Dv)))
        q, k = _l2_unit(q) * Dk ** -0.5, _l2_unit(k)
    with device_scope("gdn/gate"):
        # the recurrence's two gates, a scalar a value head each: the
        # step size and the log of the decay (<= 0)
        beta = jax.nn.sigmoid(ba[..., :Hv])
        g = -jnp.exp(blk["a_log"]) * jax.nn.softplus(
            ba[..., Hv:] + blk["dt_bias"])
    with device_scope("gdn/scan"):
        o = gdn_chunked(q, k, v, g, beta)
    with device_scope("gdn/gate"):
        # the way out: RMSNorm over each head with one plain scale for
        # all, THEN the gate SiLU(z) (norm first, gate after)
        o = _rms_norm(o, blk["o_norm"], cfg.norm_eps) \
            * jax.nn.silu(z.reshape(B, T, Hv, Dv))
    o = checkpoint_name(o.astype(cd), "attn_out")
    with device_scope("attn.out"):
        return o.reshape(B, T, -1) @ blk["wo"].reshape(-1, D).astype(cd)


def _shortconv_out_width(cfg, kind):
    """The mixer has no heads; ``wo``'s head axis bends for it here and
    nowhere else: the layer's head count (the config's, or the kind's
    own) cuts ``d_model``'s channels into that many equal runs, so that
    ``wo`` ``(heads, d_model / heads, d_model)`` is one ``(d_model,
    d_model)`` matrix reshaped."""
    heads = cfg.heads_of(kind)
    if cfg.d_model % heads:
        raise ValueError(
            f"{kind.name}: shortconv's out-projection is d_model x d_model "
            f"laid out by {heads} heads, which do not divide "
            f"d_model={cfg.d_model}")
    return cfg.d_model // heads


def _shortconv_init(key, ks, cfg, kind):
    D, taps = cfg.d_model, kind.conv_taps
    # one projection to [B | C | x], each d_model wide
    return {"w_in": _dense_init(ks[0], (D, 3 * D), D),
            "conv": _dense_init(
                jax.random.fold_in(key, 16), (D, taps), taps)}


def _shortconv_mixer(cfg, x, blk, kind):
    """The doubly gated short convolution on the normed input ``x``:
    the layer's contribution to the residual stream, ``W_out (C *
    conv(B * x))`` with ``[B | C | x] = x W_in``.  Projections in the
    compute dtype with a float32 result; both gates and the convolution
    in float32 under ``shortconv/conv`` (``ops/recurrent.py``: at whole
    lane tiles and token blocks one Pallas kernel forward and one
    backward, the three slices and the sum over taps otherwise)."""
    cd = cfg.compute_dtype
    with device_scope("attn.qkv"):
        bcx = jnp.dot(x.astype(cd), blk["w_in"].astype(cd),
                      preferred_element_type=jnp.float32)
    with device_scope("shortconv/conv"):
        y = gated_short_conv(bcx, blk["conv"])
    o = checkpoint_name(y.astype(cd), "attn_out")
    with device_scope("attn.out"):
        return o @ blk["wo"].reshape(-1, x.shape[-1]).astype(cd)


# --------------------------------------------------------------------- #
# the table
# --------------------------------------------------------------------- #


@dataclass(frozen=True)
class Mixer:
    """What the model asks of a mixer.  ``models/transformer.py`` makes
    the layer's norm ``ln1`` and its output projection ``wo`` (heads of
    ``out_width`` back to ``d_model``) and opens the layer's scope
    ``attn/<kind.name>``; the rest is the mixer's.  A flag is off
    unless the record turns it on."""
    leaves: tuple          # the leaves it may hold beside ``ln1``, ``wo``
    init: Callable         # (key, ks, cfg, kind) -> {leaf: array}: the
    # leaves of one layer at their seeds (``key``: the layer's, ``ks``:
    # its six splits, as ``_init_block`` hands them to the MLP too)
    specs: Callable        # (cfg, kind, mha) -> {leaf: PartitionSpec} in
    # a stack of blocks, ``(pipe, layers)`` leading; the same keys
    apply: Callable        # (cfg, x, blk, kind) -> the layer's addition
    # to the residual stream from its normed input ``x``, under
    # whatever inner scopes it names
    out_width: Callable    # (cfg, kind) -> the width of a head on its
    # way out (``wo``'s middle axis)
    check: Callable = lambda kind: None   # raises for AttentionKind
    # fields it cannot take
    tree: Callable = lambda kind: ()      # the fields of the kind that
    # shape its parameter tree
    takes_positions: bool = False    # window, rotary fields and qk_norm
    # are read
    takes_attn_gate: bool = False    # the config's ``attn_gate`` adds
    # its ``wg``
    groups_kv_heads: bool = False    # its query heads are groups over
    # the config's key-value heads, and must be whole groups
    splits_heads: bool = False       # its heads, ``wo``'s with them,
    # split over the ``model`` axis; else every leaf is whole on every
    # member (``_check_mesh`` keeps that axis at 1)
    norm_scope: str = ""             # the inner scope the ops of the
    # layer's norm wear


def _unsplit(leaves, **record):
    """A mixer whose leaves are whole on every member of ``model``: the
    stack's pipe axis and no other."""
    return Mixer(
        leaves=leaves, **record,
        specs=lambda cfg, kind, mha: {name: P("pipe") for name in leaves})


# in the order the messages print
MIXERS = {
    "softmax": Mixer(
        leaves=("wqkv", "wq", "wkv", "wg", "q_norm", "k_norm"),
        init=_softmax_init, specs=_softmax_specs, apply=_softmax_mixer,
        out_width=lambda cfg, kind: cfg.d_head,
        tree=lambda kind: (kind.qk_norm,),
        takes_positions=True, takes_attn_gate=True, groups_kv_heads=True,
        splits_heads=True, norm_scope="attn.qkv"),
    "mla": _unsplit(
        ("wq", "wkva", "kv_norm", "wkvb"),
        init=_mla_init, apply=_mla_mixer, check=_mla_check,
        out_width=lambda cfg, kind: kind.d_value or cfg.d_head,
        tree=lambda kind: (kind.kv_latent, kind.d_shared_key, kind.d_value),
        groups_kv_heads=True),
    "kda": _unsplit(
        ("wqkv", "conv", "wf_a", "wf_b", "a_log", "dt_bias", "wbeta",
         "wg_a", "wg_b", "o_norm"),
        init=_kda_init, apply=_kda_mixer, check=_check_conv_taps,
        out_width=lambda cfg, kind: cfg.d_head,
        tree=lambda kind: (kind.conv_taps,),
        groups_kv_heads=True),
    "mamba2": _unsplit(
        ("w_in", "conv", "conv_b", "a_log", "dt_bias", "d_skip", "o_norm"),
        init=_mamba2_init, apply=_mamba2_mixer, check=_mamba2_check,
        out_width=lambda cfg, kind: kind.ssm_head_dim,
        tree=lambda kind: (kind.ssm_head_dim, kind.ssm_state,
                           kind.ssm_groups, kind.conv_taps)),
    "gdn": _unsplit(
        ("w_in", "w_ba", "conv", "a_log", "dt_bias", "o_norm"),
        init=_gdn_init, apply=_gdn_mixer, check=_gdn_check,
        out_width=lambda cfg, kind: kind.d_value,
        tree=lambda kind: (kind.key_heads, kind.d_key, kind.d_value,
                           kind.conv_taps)),
    "shortconv": _unsplit(
        ("w_in", "conv"),
        init=_shortconv_init, apply=_shortconv_mixer, check=_check_conv_taps,
        out_width=_shortconv_out_width,
        tree=lambda kind: (kind.conv_taps,)),
}
