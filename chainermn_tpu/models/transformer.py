"""Flagship transformer — the model that composes every parallelism axis.

The reference had no transformer (its biggest model was ResNet-50 and an
LSTM seq2seq); this is the "beyond-reference" flagship required by the task
spec: ONE decoder-only LM whose single SPMD step exercises

- **DP**    batch over ``data`` (+ ``expert`` between MoE blocks),
- **TP**    Megatron column→row pairs over ``model``
            (:mod:`chainermn_tpu.parallel.tensor`),
- **SP/CP** ring attention or Ulysses all-to-all over ``seq``
            (:mod:`parallel.ring_attention` / :mod:`parallel.ulysses`),
- **PP**    GPipe micro-batching over ``pipe`` (:mod:`parallel.pipeline`),
- **EP**    Switch-MoE all-to-all over ``expert`` (:mod:`parallel.expert`).

Design rules (TPU-first):
- one code path for every mesh shape — axes of size 1 cost nothing, so the
  single-chip model IS the 5-axis model with a trivial mesh;
- mixed precision: params fp32, matmuls bf16 (MXU native), loss fp32;
- layers are a homogeneous stack scanned with ``lax.scan`` (compile time
  independent of depth) and grouped ``(pipe_stages, layers_per_stage)`` so
  stage weights *shard* over ``pipe``;
- everything is plain pytrees + pure functions (jit/shard_map transparent).
"""

from __future__ import annotations

from contextlib import nullcontext
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from chainermn_tpu.ops.pallas_attention import (
    FLASH_RESIDUAL_NAMES,
    tracing_for_mesh,
)
from chainermn_tpu.ops.recurrent import RECURRENT_RESIDUAL_NAMES
from chainermn_tpu.parallel.expert import (
    buffer_rows,
    expert_parallel_moe,
    expert_parallel_moe_dropless,
    grouped_dense,
)
from chainermn_tpu.parallel.fsdp import fsdp_gather
from chainermn_tpu.parallel.pipeline import (
    pipeline_apply,
    pipeline_train_1f1b,
    pipeline_train_interleaved,
)
from chainermn_tpu.parallel.ring_attention import _block_positions
from chainermn_tpu.parallel._compat import (
    all_gather_invariant as _all_gather_invariant,
)
from chainermn_tpu.parallel.tensor import (
    column_parallel_dense,
    row_parallel_dense,
)
from chainermn_tpu.utils.telemetry import device_scope

from . import mixers
# defined in ``models/mixers.py``; what imports them from here finds
# them here (decoding, the benchmark's drivers, the tests)
from .mixers import (  # noqa: F401
    KDA_L2_NORM_EPS,
    AttentionKind,
    _dense_init,
    _norm,
    _norm_init,
    _rms_norm,
    apply_rope,
)

__all__ = [
    "AttentionKind",
    "TransformerConfig",
    "apply_rope",
    "expert_buffer_rows",
    "expert_choices",
    "expert_load",
    "init_transformer",
    "transformer_forward",
    "param_specs",
    "make_forward_fn",
    "make_train_step",
    "hold_selection_bias",
]

# the mixers an :class:`AttentionKind` may name, in the order of the
# table (``mixers.MIXERS``, which the code here reads)
MIXERS = tuple(mixers.MIXERS)


def _mixer(cfg, kind):
    """The table's record of the mixer of a layer of ``kind``."""
    return mixers.MIXERS[cfg.mixer_of(kind)]


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_kv_heads: int = 0    # 0 => n_heads (MHA); fewer => GQA, 1 => MQA
    d_head: int = 64
    d_ff: int = 2048
    n_layers: int = 4          # total; must divide by mesh pipe size
    max_seq: int = 2048
    attention: str = "ring"    # "ring" | "ulysses" | "local" | "flash"
    flash_bwd_block_q: int = 0  # 0 = kernel default; >0 retunes the
    # flash BACKWARD kernel's tiling independently of the forward
    # (gradients are tiling-exact; flash.ms_per_step in the OPT cells
    # of benchmarks/ reads a pair on the chip, this knob adopts it)
    flash_bwd_block_k: int = 0
    attention_window: int = 0  # 0 => full causal; W>0 => sliding causal
    # window (token t attends to (t-W, t]): Mistral-style local
    # attention; the flash kernels' grids walk the band's blocks alone
    # and the ring schedule stops at the window's reach, so
    # long-context FLOPs and grid steps scale with W not T
    pos_embedding: str = "learned"  # "learned" (absolute table, the
    # "pos" param) | "rope" (rotary on q/k per block — no position
    # parameters; the long-context default: relative by construction,
    # composes with ring/zigzag sharding because each shard rotates by
    # its own global positions before any K/V movement)
    rope_theta: float = 10000.0
    layer_pattern: tuple = ()  # () => every layer alike, by the three
    # fields above.  Else the :class:`AttentionKind` of each layer of
    # one period (e.g. sliding x3, full x1): layer l is of kind
    # ``layer_pattern[l % len]``, each with its own window and rotary
    # frequencies; the layer scan then runs over whole periods with the
    # period's kinds unrolled in its body (a window is a static argument
    # of the kernel).  Needs pos_embedding="rope"; training path only.
    leading_layers: tuple = ()  # the :class:`AttentionKind` of each layer
    # that comes BEFORE the periods of ``layer_pattern`` (a model whose
    # first layers differ from the rest: a dense MLP before the sparse
    # ones).  They run ahead of the layer scan, each under the block's
    # checkpoint, and live in the tree beside the scanned stack
    # (``params["leading"]``, one block per layer at its own shapes);
    # ``n_layers`` counts them.  Unpipelined meshes; training path only.
    leading_mlp: str = "dense"  # "dense" | "sparse": the leading layers'
    # MLP where the model is sparse (moe=True); the periods' is sparse
    seq_layout: str = "contiguous"  # "contiguous" | "zigzag" (ring only):
    # zigzag = Striped-ring causal load balance; feed tokens permuted by
    # parallel.ring_attention.zigzag_indices (targets through the same
    # permutation) — position embeddings follow the layout automatically
    moe: bool = False          # Switch-MoE MLP in every block
    n_experts: int = 8         # global expert count (moe=True)
    router_top_k: int = 1      # experts per token: 1 = Switch, 2 =
    # GShard-style top-2 with renormalised gates (capacity scales by k)
    capacity_factor: float = 1.25
    moe_dispatch: str = "capacity"  # "capacity": slots of
    # cf*k*N/E per expert through dense dispatch/combine one-hots,
    # overflow dropped | "dropless": rows sorted by expert and grouped
    # products over the rows really routed (parallel.expert); training
    # path only
    expert_act: str = "relu"   # "relu": w2(relu(w1 x)) | "relu2":
    # w2(relu(w1 x)^2), no gate matrix either | "swiglu":
    # w2(silu(w1 x) * w3 x), the gated expert (the last two: dropless
    # dispatch only)
    experts_held: tuple = ()   # () => all n_experts.  (first, count):
    # the mesh's expert group holds only experts [first, first+count)
    # of the n_experts the router scores -- one member's share of an
    # expert-parallel deployment.  Expert weights have ``count`` rows,
    # the router keeps n_experts columns, gates are normalised over the
    # k chosen among all of them, and the layer returns the held
    # experts' part of its result (dropless dispatch only)
    router_score: str = "softmax"  # "softmax" | "sigmoid": how the
    # dropless layer's router scores the experts (route_top_k)
    router_scale: float = 1.0  # multiplies the chosen gates (a routed
    # scaling factor; dropless dispatch only)
    router_bias: str = ""      # "" | "selection": a bias an expert
    # (``router_bias``, (E,), beside ``router``) added to the scores for
    # the CHOICE of the k experts only; the gates are the winners'
    # scores without it.  No gradient reaches it (stop_gradient in
    # ``_mlp``), and the optimizer must not move it either (AdamW's
    # weight decay would): ``make_train_step`` wraps its optimizer in
    # ``hold_selection_bias``, and any other step that updates these
    # parameters has to do the same.  Its own update rule, from the
    # experts' load, is not implemented.  Dropless dispatch, training
    # path only
    shared_expert_d_ff: int = 0  # >0 => beside the routed experts, one
    # expert of this width that every token meets (its activation is
    # ``expert_act``), ungated, whole on every member of the expert
    # group for the member's own tokens (dropless dispatch only)
    shared_expert_gate: bool = False  # True => the shared expert's
    # result is multiplied by sigmoid(u . w_s), one scalar a token from
    # the MLP's normed input (``wsg``, (d_model, 1)); training path only
    dense_act: str = "relu"    # the dense MLP: "relu": w2(relu(w1 x)) |
    # "relu2": w2(relu(w1 x)^2) | "swiglu": w2(silu(w1 x) * w3 x); the
    # last two training path only
    dense_d_ff: int = 0        # 0 => d_ff.  Width of the dense MLP where
    # d_ff is the experts' (a sparse model's leading dense layers)
    attn_gate: str = ""        # "" | "per_head" | "per_element":
    # o_j <- sigmoid(x W_g)_j o_j between the attention core and the
    # output projection of every SOFTMAX layer, from the layer's normed
    # input (``wg``, riding the fused q/k/v product): one scalar a query
    # head (``wg`` has H columns) or one an element of the head (H x
    # d_head columns); training path only
    tie_embeddings: bool = True  # False => a separate output matrix
    # ``head`` (vocab, d_model) beside ``embed``; training path only
    num_microbatches: int = 1  # GPipe M (>1 only useful when pipe > 1)
    pipeline_schedule: str = "gpipe"  # "gpipe" | "1f1b" | "interleaved"
    virtual_pipe: int = 1      # V model chunks per pipe device (Megatron
    # interleaved schedule: bubble ÷~V for V× activation stash + ring
    # traffic); >1 requires pipeline_schedule="interleaved"
    fsdp: bool = False         # ZeRO-3 / FSDP: shard the d_model dim of
    # every block matrix over ``data`` at rest; each scanned layer
    # all-gathers its weights just-in-time inside the block, and the
    # gather's AD transpose is a reduce-scatter, so gradients and
    # optimiser state land shard-width too — the BLOCK matrices' params
    # + grads + moments cost 1/N_data per device.  The embedding table
    # and norm scales stay replicated (depth scales the block stack,
    # not the embed).  Training-path feature; decoding expects
    # replicated/TP layouts (gathering per generated token would put a
    # collective on the per-token critical path).
    fsdp_wire_dtype: str = ""  # "" => gather/reduce-scatter in the
    # param dtype (fp32 — bit-comparable with fsdp=False); "bfloat16"
    # halves the per-layer gather + grad reduce-scatter wire bytes (the
    # allreduce_grad_dtype analogue for the FSDP path)
    vocab_parallel: bool = False  # Megatron-style vocab TP: the tied
    # embedding's vocab dim shards over ``model``.  The LM head computes
    # only its (B, T, V/M) logits slice — the step's biggest matmul and
    # its two grad matmuls shrink M× per device — and the cross-entropy
    # reduces over vocab shards with three tiny collectives (pmax of
    # the max, psum of the exp-sum, psum of the owner's target logit);
    # the embedding lookup becomes a masked local gather + one (B,T,D)
    # psum.  Embed param + grad + moments also land at V/M per device.
    loss_chunk: int = 0  # 0 => one whole-shard (B, T, V) logits tensor
    # (fp32, XLA fuses log-softmax into its consumers); N>0 => the LM
    # head + cross-entropy run in token chunks of N via a custom VJP
    # that never materialises full logits and recomputes them per chunk
    # in backward (one psum for the accumulated embed grad).  Must
    # divide the per-shard sequence length.  Composes with
    # vocab_parallel (live logits (B, chunk, V/M) — both savings
    # multiply; see _vp_head_nll).  No cell sets it yet: the trade is
    # step.mfu_pct.lm against device.hbm_gib.lm in the OPT cells.
    kv_cache_dtype: str = ""  # decode-time KV cache storage: "" =>
    # compute dtype; "int8" => values int8 with a per-(token, head)
    # absmax scale — halves cache HBM traffic and doubles the context
    # a chip's memory holds.  Long-context decode is cache-bound, not
    # weight-bound, so this is the serving twin of weight-only int8
    # (quantize_params_int8); the two compose.  Training never reads
    # this field.
    remat: bool = True
    remat_policy: str = "full"  # "full" | "dots": what the block's
    # checkpoint keeps besides the block's input.  Both keep the flash
    # kernel's o and lse where the block runs it, and a recurrence's
    # slab-start states and output where it scans one (checkpoint_fn);
    # "dots" also keeps the matmul outputs (jax
    # dots_with_no_batch_dims_saveable) and the attention output, and
    # recomputes only the cheap elementwise/norm ops — at 16.0 GB of
    # temporaries for the 300M model at 8 x 2,048 (sandbox compile,
    # PR 21) it fits no cell
    norm_eps: float = 1e-6     # the RMSNorms' epsilon; the training path
    # reads it (decoding and serving keep 1e-6 and refuse another)
    norm_scale: str = "plain"  # "plain": y = x / rms(x) * w, w seeded 1 |
    # "zero_centred": y = x / rms(x) * (1 + w), w seeded 0 and STORED as
    # w, so that weight decay pulls the scale to 1 and not to 0: a
    # parameterisation, not a constant.  Every norm with a learned scale
    # (a layer's, the last, q/k, the latent's) but the recurrent
    # mixers' output norm, which stays plain; training path only
    dtype: str = "bfloat16"    # compute dtype (params stay fp32)

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def n_experts_held(self) -> int:
        return self.experts_held[1] if self.experts_held else self.n_experts

    def heads_of(self, kind) -> int:
        """Query heads of a layer of ``kind`` (None: an untyped layer)."""
        return (kind.n_heads if kind else 0) or self.n_heads

    @staticmethod
    def mixer_of(kind) -> str:
        """Mixer of a layer of ``kind`` (None: an untyped layer)."""
        return kind.mixer if kind else "softmax"

    @staticmethod
    def part_of(kind) -> str:
        """The parts a layer of ``kind`` has (None: an untyped layer)."""
        return kind.part if kind else "both"

    @staticmethod
    def _mixing(kinds):
        """Those of ``kinds`` whose layers have a mixer."""
        return tuple(k for k in kinds if k.part != "mlp")

    @property
    def leading_sparse(self) -> bool:
        return self.moe and self.leading_mlp == "sparse"

    @property
    def blocks_by_position(self) -> bool:
        """Whether the layers of a period differ in their parameter
        TREE (a kind with query heads or a mixer of its own).  One
        stacked array a leaf cannot hold them: ``params["blocks"]`` is
        then a tuple with one stack over the periods for each position
        of the pattern, ``(pipe, periods/pipe, ...)`` a leaf, each with
        its own leaves at their own shapes."""
        return len({(k.part != "mlp" and self.heads_of(k), k.tree)
                    for k in self.layer_pattern}) > 1

    @property
    def mixers(self):
        """The mixers in use beside softmax attention, by name."""
        return sorted({k.mixer for k in self._mixing(
            self.layer_pattern + self.leading_layers)} - {"softmax"})

    @property
    def parts_alone(self) -> bool:
        """Whether some layer is a mixer or a feed-forward part alone."""
        return any(k.part != "both"
                   for k in self.layer_pattern + self.leading_layers)

    @property
    def training_only(self):
        """The fields in use that only the training path implements, by
        name: decoding and serving refuse a config that sets any."""
        return [name for name, on in (
            ("layer_pattern", bool(self.layer_pattern)),
            ("leading_layers", bool(self.leading_layers)),
            ("AttentionKind.n_heads", any(
                k.n_heads for k in self.layer_pattern + self.leading_layers)),
            ("AttentionKind.mixer=" + "/".join(self.mixers),
             bool(self.mixers)),
            ("AttentionKind.part", self.parts_alone),
            ("AttentionKind.rotary_share=0", any(
                not k.rotary_share
                for k in self.layer_pattern + self.leading_layers)),
            ("attn_gate", bool(self.attn_gate)),
            ("AttentionKind.qk_norm", any(
                k.qk_norm for k in self.layer_pattern + self.leading_layers)),
            ("norm_scale='zero_centred'", self.norm_scale != "plain"),
            ("shared_expert_gate", self.shared_expert_gate),
            ("router_bias", bool(self.router_bias)),
            ("norm_eps", self.norm_eps != 1e-6),
            ("experts_held", bool(self.experts_held)),
            ("moe_dispatch='dropless'",
             self.moe and self.moe_dispatch == "dropless"),
            ("shared_expert_d_ff", bool(self.shared_expert_d_ff)),
            ("router_score='sigmoid'", self.router_score == "sigmoid"),
            ("dense_act='swiglu'", self.dense_act == "swiglu"),
            ("dense_act='relu2'", self.dense_act == "relu2"),
            ("tie_embeddings=False", not self.tie_embeddings)) if on]

    @property
    def checkpoint_fn(self):
        """The configured ``jax.checkpoint`` wrapper (identity when
        ``remat=False``).  Under either policy it keeps the flash
        kernel's two residual outputs (``FLASH_RESIDUAL_NAMES``: ``o``
        as the kernel wrote it and the ``(B·H, T)`` log-sum-exp), so the
        backward pass launches the one backward kernel and does not run
        the forward kernel again: a custom call is invisible to the dots policy, and
        plain ``jax.checkpoint`` rebuilds every residual.  A block
        without the kernel has no such names and remats as before.

        It keeps as well what a chunked recurrence's backward pass reads
        of its forward scan (``RECURRENT_RESIDUAL_NAMES``,
        ``ops/recurrent.py`` ``scan_slabs``: the float32 state at each
        slab's start and the op's float32 output), so a KDA, Gated
        DeltaNet or Mamba-2 layer runs its recurrence forward twice a
        step (the forward pass, and slab by slab inside the backward
        scan) and the block's recompute stops at the scan's inputs: the
        kept bytes a call are ``recurrent/residual_bytes_kept`` (the
        output) and the op's ``*/state_bytes_kept``.  A block without a
        recurrence has no such names either.

        Not under ``attention="ring"``: a block's policy reaches into
        the ``jax.checkpoint`` the ring puts around each pair, and would
        keep every pair's output (three kernels fewer in the 300M step
        on ``seq=4`` for 2.8 GiB more a device, sandbox compile, PR 29).
        Memory that grows with the ring is the wrong default at the
        lengths the ring is for; its pairs keep rematerialising.  The
        recurrences' names are inside no pair and stay kept."""
        if not self.remat:
            return lambda f: f
        cp = jax.checkpoint_policies
        kept = () if self.attention == "ring" else FLASH_RESIDUAL_NAMES
        kept += RECURRENT_RESIDUAL_NAMES
        if self.remat_policy == "dots":
            # "attn_out": see _attention
            policy = cp.save_from_both_policies(
                cp.dots_with_no_batch_dims_saveable,
                cp.save_only_these_names(*kept, "attn_out"))
        else:
            policy = cp.save_only_these_names(*kept)
        return partial(jax.checkpoint, policy=policy)

    def __post_init__(self):
        if self.attention_window < 0:
            raise ValueError(
                f"attention_window {self.attention_window} must be >= 0")
        if self.pos_embedding not in ("learned", "rope"):
            raise ValueError(
                f"pos_embedding {self.pos_embedding!r} not in "
                "(learned, rope)")
        if self.pos_embedding == "rope" and self.d_head % 2:
            raise ValueError(
                f"rope needs an even d_head, got {self.d_head}")
        if self.remat_policy not in ("full", "dots"):
            raise ValueError(
                f"remat_policy {self.remat_policy!r} not in (full, dots)")
        if self.kv_cache_dtype not in ("", "int8"):
            raise ValueError(
                f"kv_cache_dtype {self.kv_cache_dtype!r} not in "
                "('', 'int8')")
        if self.loss_chunk < 0:
            raise ValueError(
                f"loss_chunk={self.loss_chunk} must be >= 0")
        if self.layer_pattern:
            if not all(isinstance(k, AttentionKind)
                       for k in self.layer_pattern):
                raise ValueError("layer_pattern holds AttentionKind values")
            if self.pos_embedding != "rope":
                raise ValueError(
                    'layer_pattern gives each kind its rotary parameters: '
                    'it needs pos_embedding="rope"')
            if self.attention_window:
                raise ValueError(
                    "with a layer_pattern the window is each kind's own; "
                    f"attention_window={self.attention_window} is set too")
            scanned = self.n_layers - len(self.leading_layers)
            if scanned < 1 or scanned % len(self.layer_pattern):
                raise ValueError(
                    f"n_layers={self.n_layers} less the "
                    f"{len(self.leading_layers)} leading is not whole "
                    f"periods of the {len(self.layer_pattern)}-layer pattern")
            if self.seq_layout != "contiguous":
                raise ValueError("layer_pattern needs contiguous shards")
            if not all(isinstance(k, AttentionKind)
                       for k in self.leading_layers):
                raise ValueError("leading_layers holds AttentionKind values")
            for k in self._mixing(self.layer_pattern + self.leading_layers):
                if mixers.MIXERS[k.mixer].groups_kv_heads \
                        and self.heads_of(k) % self.kv_heads:
                    raise ValueError(
                        f"{k.name}: n_heads={k.n_heads} must be a multiple "
                        f"of n_kv_heads={self.kv_heads}")
                if k.rotary_share and (
                        k.rotary_dim(self.d_head) % 2
                        or not k.rotary_dim(self.d_head)):
                    raise ValueError(
                        f"{k.name}: rotary_share {k.rotary_share} of "
                        f"d_head={self.d_head} is not an even number of "
                        "dimensions")
        elif self.leading_layers:
            raise ValueError(
                "leading_layers come before the periods of a "
                "layer_pattern, which is empty")
        if self.leading_mlp not in ("dense", "sparse"):
            raise ValueError(
                f"leading_mlp {self.leading_mlp!r} not in (dense, sparse)")
        if self.dense_act not in ("relu", "relu2", "swiglu"):
            raise ValueError(
                f"dense_act {self.dense_act!r} not in (relu, relu2, swiglu)")
        if self.attn_gate not in ("", "per_head", "per_element"):
            raise ValueError(
                f"attn_gate {self.attn_gate!r} not in "
                "('', per_head, per_element)")
        if self.norm_scale not in ("plain", "zero_centred"):
            raise ValueError(
                f"norm_scale {self.norm_scale!r} not in "
                "(plain, zero_centred)")
        if self.shared_expert_gate and not self.shared_expert_d_ff:
            raise ValueError(
                "shared_expert_gate gates the shared expert, which "
                "shared_expert_d_ff=0 leaves out")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(
                f"router_score {self.router_score!r} not in "
                "(softmax, sigmoid)")
        if min(self.dense_d_ff, self.shared_expert_d_ff) < 0:
            raise ValueError("dense_d_ff and shared_expert_d_ff are >= 0")
        if self.moe_dispatch not in ("capacity", "dropless"):
            raise ValueError(
                f"moe_dispatch {self.moe_dispatch!r} not in "
                "(capacity, dropless)")
        if self.expert_act not in ("relu", "relu2", "swiglu"):
            raise ValueError(
                f"expert_act {self.expert_act!r} not in "
                "(relu, relu2, swiglu)")
        dropless = self.moe and self.moe_dispatch == "dropless"
        if self.expert_act != "relu" and not dropless:
            raise ValueError(
                f'expert_act="{self.expert_act}" is implemented by the '
                "dropless expert layer only (moe=True, "
                "moe_dispatch='dropless')")
        if self.router_bias not in ("", "selection"):
            raise ValueError(
                f"router_bias {self.router_bias!r} not in ('', selection)")
        if (self.router_score != "softmax" or self.router_scale != 1.0
                or self.shared_expert_d_ff or self.router_bias) \
                and not dropless:
            raise ValueError(
                "router_score, router_scale, router_bias and "
                "shared_expert_d_ff are the dropless expert layer's "
                "(moe=True, moe_dispatch='dropless')")
        if self.attn_gate and self.layer_pattern and not any(
                mixers.MIXERS[k.mixer].takes_attn_gate for k in self._mixing(
                    self.layer_pattern + self.leading_layers)):
            raise ValueError(
                f"attn_gate is softmax attention's; the {self.mixers} "
                "layers have none (the recurrent mixers' output gates "
                "are their own)")
        if self.experts_held:
            if not dropless:
                raise ValueError(
                    "experts_held needs moe=True, moe_dispatch='dropless'")
            first, count = self.experts_held
            if not (0 <= first and 1 <= count
                    and first + count <= self.n_experts):
                raise ValueError(
                    f"experts_held={self.experts_held} is not a range "
                    f"(first, count) within n_experts={self.n_experts}")
        if self.moe and not 1 <= self.router_top_k <= self.n_experts:
            raise ValueError(
                f"router_top_k={self.router_top_k} must be in "
                f"[1, n_experts={self.n_experts}]")
        if self.virtual_pipe < 1:
            raise ValueError(
                f"virtual_pipe={self.virtual_pipe} must be >= 1")
        if self.virtual_pipe > 1 and self.pipeline_schedule != "interleaved":
            raise ValueError(
                f"virtual_pipe={self.virtual_pipe} needs "
                'pipeline_schedule="interleaved" (got '
                f"{self.pipeline_schedule!r})")
        if not 0 <= self.n_kv_heads <= self.n_heads:
            raise ValueError(
                f"n_kv_heads={self.n_kv_heads} must be in "
                f"[0, n_heads={self.n_heads}] (0 means MHA)")
        if self.n_heads % self.kv_heads:
            raise ValueError(
                f"n_heads={self.n_heads} must be a multiple of "
                f"n_kv_heads={self.kv_heads}")
        if self.fsdp_wire_dtype:
            try:
                ok = jnp.issubdtype(
                    jnp.dtype(self.fsdp_wire_dtype), jnp.floating)
            except TypeError:
                ok = False
            if not ok:
                raise ValueError(
                    f"fsdp_wire_dtype {self.fsdp_wire_dtype!r} must "
                    "name a floating dtype (weights/grads travel in "
                    "it; an integer cast would zero them)")
        if self.fsdp_wire_dtype and not self.fsdp:
            raise ValueError("fsdp_wire_dtype is set but fsdp=False")


# --------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------- #


def _init_block(key, cfg: TransformerConfig, kind=None, sparse=None):
    """One layer's parameters at its own shapes: ``kind`` gives the
    parts it has, the mixer and the query heads (None: both parts,
    softmax attention at the config's), ``sparse`` the MLP (None: the
    config's ``moe``).  A part the layer lacks has no leaf."""
    part = cfg.part_of(kind)
    ks = jax.random.split(key, 6)
    block = {}
    if part != "mlp":
        block.update(_init_mixer(key, ks, cfg, kind))
    if part != "mixer":
        block.update(_init_mlp(
            key, ks, cfg, cfg.moe if sparse is None else sparse))
    return block


def _init_mixer(key, ks, cfg: TransformerConfig, kind):
    """The mixer's norm, its leaves and the projection back from its
    heads (``ks``: the layer's six keys)."""
    D, H = cfg.d_model, cfg.heads_of(kind)
    mixer = _mixer(cfg, kind)
    Dv = mixer.out_width(cfg, kind)  # the width of a head on its way out
    return {
        "ln1": _norm_init(cfg, (D,)),
        "wo": _dense_init(ks[1], (H, Dv, D), H * Dv),
        **mixer.init(key, ks, cfg, kind),
    }


def _init_mlp(key, ks, cfg: TransformerConfig, sparse: bool):
    """The MLP's norm and leaves (``ks``: the layer's six keys)."""
    D = cfg.d_model
    F = cfg.d_ff if sparse else cfg.dense_d_ff or cfg.d_ff
    block = {"ln2": _norm_init(cfg, (D,))}
    gated = cfg.expert_act if sparse else cfg.dense_act
    if sparse:
        # the router scores every expert; the weights are of those held
        E, G = cfg.n_experts, cfg.n_experts_held
        block["router"] = _dense_init(ks[2], (D, E), D)
        if cfg.router_bias:
            block["router_bias"] = jnp.zeros((E,), jnp.float32)
        block["w1"] = _dense_init(ks[3], (G, D, F), D)
        block["w2"] = _dense_init(ks[4], (G, F, D), F)
        if gated == "swiglu":
            block["w3"] = _dense_init(
                jax.random.fold_in(key, 6), (G, D, F), D)
        Fs = cfg.shared_expert_d_ff
        if Fs:
            block["ws1"] = _dense_init(jax.random.fold_in(key, 8), (D, Fs), D)
            block["ws2"] = _dense_init(
                jax.random.fold_in(key, 9), (Fs, D), Fs)
            if gated == "swiglu":
                block["ws3"] = _dense_init(
                    jax.random.fold_in(key, 10), (D, Fs), D)
            if cfg.shared_expert_gate:
                block["wsg"] = _dense_init(
                    jax.random.fold_in(key, 15), (D, 1), D)
    else:
        block["w1"] = _dense_init(ks[3], (D, F), D)
        block["w2"] = _dense_init(ks[4], (F, D), F)
        if gated == "swiglu":
            block["w3"] = _dense_init(jax.random.fold_in(key, 6), (D, F), D)
    return block


def _stack_blocks(blocks, cfg: TransformerConfig, pipe_size: int):
    """Layers of one shape, in order, as one ``(pipe, L/pipe, ...)``
    array a leaf (``(pipe, V, L/(pipe*V), ...)`` under ``virtual_pipe``)."""
    V = cfg.virtual_pipe
    n = len(blocks)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)
    if V > 1:
        lpc = n // (pipe_size * V)  # layers per chunk
        return jax.tree.map(
            lambda a: a.reshape(V, pipe_size, lpc, *a.shape[1:])
            .swapaxes(0, 1), stacked)
    return jax.tree.map(
        lambda a: a.reshape(pipe_size, n // pipe_size, *a.shape[1:]),
        stacked)


def init_transformer(key, cfg: TransformerConfig, pipe_size: int = 1):
    """Parameter pytree.  Blocks are stacked ``(pipe_size, L/pipe, ...)``
    — the leading axis shards over ``pipe``, the second is scanned
    locally.  With ``virtual_pipe = V > 1`` the block stack is
    ``(pipe_size, V, L/(pipe·V), ...)``: chunk ``c`` of device ``s`` is
    virtual stage ``g = c·pipe + s`` holding the ``g``-th layer slice
    (Megatron interleaved assignment).

    Where the layers of a period differ in shape
    (``cfg.blocks_by_position``) ``blocks`` is a tuple of such stacks,
    one per position of the pattern, each over the periods; the
    ``leading_layers`` are a tuple of single blocks under ``leading``."""
    V = cfg.virtual_pipe
    n_lead, n = len(cfg.leading_layers), len(cfg.layer_pattern)
    scanned = cfg.n_layers - n_lead
    if scanned % (pipe_size * V * (n if cfg.blocks_by_position else 1)):
        raise ValueError(
            f"{scanned} layers not divisible by "
            f"pipe·virtual_pipe = {pipe_size}·{V}"
            + (f" in whole periods of {n}" if cfg.blocks_by_position
               else ""))
    k_emb, k_pos, k_blocks = jax.random.split(key, 3)
    keys = jax.random.split(k_blocks, cfg.n_layers)
    blocks = [
        _init_block(k, cfg, cfg.layer_pattern[i % n] if n else None)
        for i, k in enumerate(keys[n_lead:])
    ]
    if cfg.blocks_by_position:
        stacked = tuple(_stack_blocks(blocks[j::n], cfg, pipe_size)
                        for j in range(n))
    else:
        stacked = _stack_blocks(blocks, cfg, pipe_size)
    D = cfg.d_model
    params = {
        "embed": jax.random.normal(
            k_emb, (cfg.vocab_size, D), jnp.float32) * 0.02,
        "blocks": stacked,
        "ln_f": _norm_init(cfg, (D,)),
    }
    if n_lead:
        params["leading"] = tuple(
            _init_block(k, cfg, kind, cfg.leading_sparse)
            for k, kind in zip(keys, cfg.leading_layers))
    if cfg.pos_embedding == "learned":
        params["pos"] = jax.random.normal(
            k_pos, (cfg.max_seq, D), jnp.float32) * 0.02
    if not cfg.tie_embeddings:
        params["head"] = jax.random.normal(
            jax.random.fold_in(k_emb, 1), (cfg.vocab_size, D),
            jnp.float32) * 0.02
    return params


def regroup_blocks(blocks, from_pipe: int, to_pipe: int,
                   from_virtual: int = 1, to_virtual: int = 1):
    """Regroup a block stack between pipeline layouts.

    Checkpoints store blocks grouped for whatever pipe mesh TRAINED
    them — ``(P, L/P, *base)``, or ``(P, V, L/(P·V), *base)`` when the
    interleaved schedule's ``virtual_pipe = V > 1`` (chunk ``c`` of
    device ``s`` is virtual stage ``g = c·P + s`` holding the ``g``-th
    contiguous layer slice, see :func:`init_transformer`).  This
    flattens to global layer order and regroups for the target layout,
    so a checkpoint trained on any (pipe, virtual) grouping resumes or
    decodes on any other — the training-side analogue of
    ``generate.py``'s decode-mesh regrouping.
    """

    def leaf(a):
        if from_virtual > 1:
            if a.shape[0] != from_pipe or a.shape[1] != from_virtual:
                raise ValueError(
                    f"block leaf {a.shape} does not match from_pipe="
                    f"{from_pipe}, from_virtual={from_virtual}")
            base = a.shape[3:]
            # (P, V, lpc) -> (V, P, lpc) -> layer order g·lpc + i
            layers = a.swapaxes(0, 1).reshape(-1, *base)
        else:
            if a.shape[0] != from_pipe:
                raise ValueError(
                    f"block leaf {a.shape} does not match "
                    f"from_pipe={from_pipe}")
            base = a.shape[2:]
            layers = a.reshape(-1, *base)
        L = layers.shape[0]
        if L % (to_pipe * to_virtual):
            raise ValueError(
                f"{L} layers not divisible by to_pipe·to_virtual = "
                f"{to_pipe}·{to_virtual}")
        if to_virtual > 1:
            lpc = L // (to_pipe * to_virtual)
            return layers.reshape(
                to_virtual, to_pipe, lpc, *base).swapaxes(0, 1)
        return layers.reshape(to_pipe, L // to_pipe, *base)

    return jax.tree.map(leaf, blocks)


def reshard_train_state(mc, cfg: TransformerConfig, optimizer, params,
                        opt_state, from_pipe: int = 1,
                        from_virtual: int = 1):
    """Re-lay a full training state (params + optax state) onto a
    different mesh: **elastic resume**.

    The reference could only restart a checkpoint at the identical
    world size (`chainermn/extensions/checkpoint.py` — same-world-size
    agreement); here the logical state is mesh-independent, so a run
    snapshotted on one topology continues on another — different data/
    model/seq axis sizes, a different pipe grouping (blocks regrouped
    via :func:`regroup_blocks`), or a different at-rest layout
    (``fsdp`` on/off) — with the same loss trajectory.

    ``params``/``opt_state`` may be device arrays from a live run on
    any previous mesh or host arrays from ``utils.serialization.
    load_state``.  Optimiser moments are param-shaped: every
    param-structured subtree inside the optax state is regrouped the
    same way (``optax.tree_map_params``), then each leaf is placed with
    the sharding ``optimizer.init``'s propagation assigns on the new
    mesh.  Returns ``(params, opt_state)`` living on ``mc``.
    """
    import numpy as _np

    to_pipe = mc.mesh.shape.get("pipe", 1)
    host_params = jax.tree.map(_np.asarray, params)
    host_opt = jax.tree.map(_np.asarray, opt_state)

    def regroup(leaf_or_tree):
        return regroup_blocks(leaf_or_tree, from_pipe, to_pipe,
                              from_virtual, cfg.virtual_pipe)

    new_params = shard_params(
        mc, cfg, dict(host_params, blocks=regroup(host_params["blocks"])))

    # params-structured flag tree: True on blocks leaves (the only
    # leaves whose grouping is mesh-dependent)
    flags = {k: jax.tree.map(lambda _: k == "blocks", v)
             for k, v in host_params.items()}
    host_opt = optax.tree_map_params(
        optimizer,
        lambda leaf, is_block: regroup(leaf) if is_block else leaf,
        host_opt, flags)
    # template via shard_opt_state, not plain jit(init): zeros_like has
    # no data dependence on params, so propagation would replicate the
    # moments — under fsdp that forfeits the shard-width residency
    from chainermn_tpu.training.optimizers import shard_opt_state

    template = shard_opt_state(optimizer, new_params)
    mesh_devs = set(mc.mesh.devices.flat)

    def place(h, t):
        sh = t.sharding
        if set(sh.device_set) != mesh_devs:
            # input-independent leaves (e.g. adam's count scalar) come
            # out of jit on the default device, not the mesh: replicate
            sh = jax.sharding.NamedSharding(mc.mesh, P())
        return jax.device_put(h, sh)

    new_opt = jax.tree.map(place, host_opt, template)
    return new_params, new_opt


def _fsdp_dims(mha: bool, sparse: bool):
    """Leaf → axis (into the BASE per-layer shapes, i.e. after scan has
    stripped the pipe/chunk/layer prefixes) that FSDP shards over
    ``data``, for a block with fused q/k/v (``mha``) or grouped heads,
    and a ``sparse`` or a dense MLP; a leaf the block lacks is ignored.
    One rule everywhere: **the d_model dim** — it exists in every matrix
    leaf and is never claimed by TP (``model`` shards head/ff dims) or
    EP (``expert`` shards the expert dim), so the two shardings compose
    without collisions.  Norm scales are omitted."""
    dims = {"wo": 2, "wg": 0}
    dims.update({"wqkv": 0} if mha else {"wq": 0, "wkv": 0})
    if sparse:
        dims.update({"router": 0, "w1": 1, "w2": 2, "w3": 1,
                     "ws1": 0, "ws2": 1, "ws3": 0, "wsg": 0})
    else:
        dims.update({"w1": 0, "w2": 1, "w3": 0})
    return dims


def _fsdp_gather(cfg: TransformerConfig, blk):
    """All-gather one layer's FSDP-sharded leaves along ``data`` (call
    inside the block, i.e. once per layer per use).  AD transposes each
    gather into a ``psum_scatter``, which IS ZeRO's gradient
    reduce-scatter — no hand-written backward.  Mechanics live in
    :func:`...parallel.fsdp.fsdp_gather`; this only binds the
    transformer's dim map (norm scales get ``None`` → pass through)."""
    dims = _fsdp_dims("wqkv" in blk, "router" in blk)
    # gather and wire cast; by transposition the reduce-scatter too
    with device_scope("fsdp/gather"):
        return fsdp_gather(blk, {k: dims.get(k) for k in blk},
                           "data", cfg.fsdp_wire_dtype or None)


def _mixer_specs(cfg: TransformerConfig, kind, mha: bool):
    """A mixer's norm and leaves in a stack of blocks."""
    mixer = _mixer(cfg, kind)
    # ``wo`` splits with the heads, or is whole like the mixer's leaves
    return {
        "ln1": P("pipe"),
        "wo": P("pipe", None, "model", None, None) if mixer.splits_heads
        else P("pipe"),
        **mixer.specs(cfg, kind, mha),
    }


def _mlp_specs(cfg: TransformerConfig, sparse: bool):
    """An MLP's norm and leaves in a stack of blocks."""
    blk = {"ln2": P("pipe")}
    gated = (cfg.expert_act if sparse else cfg.dense_act) == "swiglu"
    if sparse:
        blk["router"] = P("pipe")
        if cfg.router_bias:
            blk["router_bias"] = P("pipe")
        blk["w1"] = P("pipe", None, "expert", None, "model")
        blk["w2"] = P("pipe", None, "expert", "model", None)
        if gated:
            blk["w3"] = blk["w1"]
        if cfg.shared_expert_d_ff:
            blk["ws1"] = P("pipe", None, None, "model")
            blk["ws2"] = P("pipe", None, "model", None)
            if gated:
                blk["ws3"] = blk["ws1"]
            if cfg.shared_expert_gate:
                blk["wsg"] = P("pipe")
    else:
        blk["w1"] = P("pipe", None, None, "model")
        blk["w2"] = P("pipe", None, "model", None)
        if gated:
            blk["w3"] = blk["w1"]
    return blk


def _block_specs(cfg: TransformerConfig, kind, sparse: bool,
                 quantized: bool = False):
    """PartitionSpecs of one stack of blocks of one shape (``kind``'s
    parts and query heads, a ``sparse`` or a dense MLP), with the
    stack's leading ``(pipe, layers)`` axes."""
    part = cfg.part_of(kind)
    mha = cfg.kv_heads == cfg.heads_of(kind)
    blk = {}
    if part != "mlp":
        blk.update(_mixer_specs(cfg, kind, mha))
    if part != "mixer":
        blk.update(_mlp_specs(cfg, sparse))
    if cfg.virtual_pipe > 1:
        # blocks carry an extra local chunk axis after pipe: (pipe, V,
        # layers_per_chunk, ...) — replicate over it, shift the rest
        blk = {k: P(v[0], None, *v[1:]) for k, v in blk.items()}
    if cfg.fsdp and not quantized:
        # ZeRO-3 at-rest layout: "data" lands on each matrix's d_model
        # dim (see _fsdp_dims).  Skipped for quantized (decode) trees —
        # decoding wants resident weights, not per-token gathers.
        prefix = 2 + (1 if cfg.virtual_pipe > 1 else 0)
        for name, dim in _fsdp_dims(mha, sparse).items():
            if name not in blk:
                continue
            full = list(blk[name])
            idx = prefix + dim
            full += [None] * (idx + 1 - len(full))
            if full[idx] is not None:
                # not an assert: under ``python -O`` a silently-ignored
                # collision would emit an overlapping PartitionSpec
                raise ValueError(
                    f"FSDP dim collision on {name!r}: dim {dim} already "
                    f"sharded as {full[idx]!r} in {P(*full)}; fix "
                    "_fsdp_dims so FSDP lands on a free dim")
            full[idx] = "data"
            blk[name] = P(*full)
    if quantized:
        from .quantization import base_layout, scale_spec

        prefix = 2 + (1 if cfg.virtual_pipe > 1 else 0)
        for name, (base_rank, base_axes) in base_layout(cfg.moe).items():
            if name in blk and name not in ("router",):
                blk[name + "_scale"] = scale_spec(
                    blk[name], base_rank, base_axes, prefix + base_rank)
    return blk


def param_specs(cfg: TransformerConfig, quantized: bool = False):
    """PartitionSpec pytree matching :func:`init_transformer`'s output.

    TP shards head/ff dims over ``model``, EP shards experts over
    ``expert``, PP shards the stage axis over ``pipe``; embeddings and
    norms replicate.  With ``quantized=True`` the tree additionally
    carries ``<name>_scale`` specs matching
    :func:`...quantization.quantize_params_int8`'s output (the weight's
    spec with its contraction axes dropped).
    """
    kinds = cfg.layer_pattern
    if cfg.blocks_by_position:
        blk = tuple(_block_specs(cfg, k, cfg.moe, quantized) for k in kinds)
    else:
        blk = _block_specs(
            cfg, kinds[0] if kinds else None, cfg.moe, quantized)
    emb = P("model") if cfg.vocab_parallel else P()
    specs = {
        "embed": emb,
        "blocks": blk,
        "ln_f": P(),
    }
    if cfg.leading_layers:
        # single blocks, on every pipeline member alike: a stack's specs
        # less its two leading axes
        specs["leading"] = tuple(
            {k: P(*v[2:]) for k, v in _block_specs(
                cfg, kind, cfg.leading_sparse, quantized).items()}
            for kind in cfg.leading_layers)
    if quantized:
        specs["embed_scale"] = emb
    if cfg.pos_embedding == "learned":
        specs["pos"] = P()
    if not cfg.tie_embeddings:
        specs["head"] = emb
    return specs


# --------------------------------------------------------------------- #
# forward (call INSIDE shard_map over the 5-axis mesh)
# --------------------------------------------------------------------- #


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _lm_head(cd, h, embed):
    """Weight-tied LM head: compute-dtype operands on the MXU, fp32
    accumulation and fp32 logits (stable softmax).  With ``cd=bf16``
    this runs the single biggest matmul of the step at the MXU's native
    rate instead of ~1/4 of it — naively ``h.fp32 @ embed.fp32`` makes
    the head (and, worse, its TWO transposed gradient matmuls) fp32."""
    return jnp.einsum("btd,vd->btv", h.astype(cd), embed.astype(cd),
                      preferred_element_type=jnp.float32)


def _psum_over_vma(grad, exclude: tuple = ()):
    """Shared tail of every custom-VJP head backward: psum ``grad``
    over the mesh axes its local partial is varying on (size-1 axes
    and the single-device oracle fold to identity), excluding
    ``exclude`` (a vocab-shard axis whose per-member gradients are
    distinct and must NOT be summed).  custom_vjp hides the einsum
    transpose's linearity from the vma checker, so the reduction must
    be explicit."""
    vma = tuple(a for a in jax.typeof(grad).vma if a not in exclude)
    return lax.psum(grad, vma) if vma else grad


def _lm_head_fwd(cd, h, embed):
    return _lm_head(cd, h, embed), (h, embed)


def _lm_head_bwd(cd, res, g):
    # the logit cotangent is (softmax - onehot)/N — unit-scale, safe in
    # bf16 — so both grad matmuls ride the MXU too; accumulation stays
    # fp32 and grads leave at their primal dtypes (embed's is fp32)
    h, embed = res
    gl = g.astype(cd)
    dh = jnp.einsum("btv,vd->btd", gl, embed.astype(cd),
                    preferred_element_type=jnp.float32).astype(h.dtype)
    dw = jnp.einsum("btv,btd->vd", gl, h.astype(cd),
                    preferred_element_type=jnp.float32).astype(embed.dtype)
    # embed is replicated over every mesh axis; its true cotangent is
    # the SUM of the per-member partials, which the standard einsum
    # transpose would emit as shard_map's automatic psum (see
    # _psum_over_vma's contract)
    dw = _psum_over_vma(dw)
    return dh, dw


_lm_head.defvjp(_lm_head_fwd, _lm_head_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _head_nll(cd, chunk, h, embed, targets):
    """Sum of next-token NLL over the local shard, head applied in token
    chunks of ``chunk`` so the full ``(B, T, V)`` fp32 logits are never
    resident — live logits memory is ``(B, chunk, V)``.

    The classic chunked-vocab cross-entropy:
    forward keeps only the per-chunk NLL partial sums; backward
    recomputes each chunk's logits, forms ``(softmax - onehot)·g``
    in-registers (XLA fuses the one-hot iota-compare into the subtract),
    and accumulates the embed cotangent across chunks in an fp32 scan
    carry so the vma psum over the data-like axes fires ONCE at the end
    — a per-chunk psum would multiply the (V, D) all-reduce volume by
    the chunk count.  Matmul operands ride the MXU at ``cd`` with fp32
    accumulation, exactly like :func:`_lm_head`."""
    B, T, D = h.shape
    if T % chunk:
        raise ValueError(
            f"loss_chunk={chunk} must divide the local sequence length "
            f"{T} (global seq / seq-axis size)")
    C = T // chunk
    hc = h.reshape(B, C, chunk, D).transpose(1, 0, 2, 3)
    tc = targets.reshape(B, C, chunk).transpose(1, 0, 2)
    ew = embed.astype(cd)

    def body(acc, ht):
        hh, tt = ht
        logits = jnp.einsum("bcd,vd->bcv", hh.astype(cd), ew,
                            preferred_element_type=jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(
            logp, tt[..., None], axis=-1).sum(dtype=jnp.float32)
        return acc + nll, None

    # derive the carry seed from h so it inherits h's varying axes
    # (scan requires carry-in and carry-out vma types to match)
    acc0 = jnp.sum(h * 0, dtype=jnp.float32)
    out, _ = lax.scan(body, acc0, (hc, tc))
    return out


def _head_nll_fwd(cd, chunk, h, embed, targets):
    # residuals are just the primal inputs — no logits saved
    return _head_nll(cd, chunk, h, embed, targets), (h, embed, targets)


def _head_nll_bwd(cd, chunk, res, g):
    h, embed, targets = res
    B, T, D = h.shape
    V = embed.shape[0]
    C = T // chunk
    hc = h.reshape(B, C, chunk, D).transpose(1, 0, 2, 3)
    tc = targets.reshape(B, C, chunk).transpose(1, 0, 2)
    ew = embed.astype(cd)
    g32 = g.astype(jnp.float32)

    def body(dw, ht):
        hh, tt = ht
        hcd = hh.astype(cd)
        logits = jnp.einsum("bcd,vd->bcv", hcd, ew,
                            preferred_element_type=jnp.float32)
        p = jax.nn.softmax(logits, axis=-1)
        dl = ((p - jax.nn.one_hot(tt, V, dtype=p.dtype)) * g32).astype(cd)
        dh_c = jnp.einsum("bcv,vd->bcd", dl, ew,
                          preferred_element_type=jnp.float32).astype(h.dtype)
        dw = dw + jnp.einsum("bcv,bcd->vd", dl, hcd,
                             preferred_element_type=jnp.float32)
        return dw, dh_c

    dw0 = jnp.zeros((V, D), jnp.float32) \
        + jnp.sum(h * 0, dtype=jnp.float32) + g32 * 0
    dw, dhc = lax.scan(body, dw0, (hc, tc))
    dh = dhc.transpose(1, 0, 2, 3).reshape(B, T, D)
    dw = dw.astype(embed.dtype)
    # single psum for the whole accumulated embed cotangent — a
    # per-chunk psum would multiply the (V, D) all-reduce volume by C
    dw = _psum_over_vma(dw)
    return dh, dw, None


_head_nll.defvjp(_head_nll_fwd, _head_nll_bwd)


def _vp_shard_index(Vl: int, tokens, axis_name: str):
    """Vocab-ownership arithmetic, in ONE place: member r owns rows
    [r·Vl, (r+1)·Vl).  Returns ``(ok, idx)`` — whether each token's row
    lives on THIS member, and its clipped local index (only meaningful
    where ``ok``; callers mask)."""
    loc = tokens - lax.axis_index(axis_name) * Vl
    return (loc >= 0) & (loc < Vl), jnp.clip(loc, 0, Vl - 1)


def _vp_embed_lookup(embed_local, tokens, axis_name: str = "model",
                     scale_local=None):
    """Vocab-parallel embedding gather: member r holds vocab rows
    [r·Vl, (r+1)·Vl); out-of-shard tokens contribute zero and ONE psum
    assembles the full (..., D) rows — Megatron's VocabParallelEmbedding
    shape.  AD's transpose scatter-adds each member's cotangent rows
    into its own shard only (the masked gather keeps it local).
    ``scale_local`` (the int8 path's per-row dequant scales, sharded
    like the rows) applies BEFORE the psum so quantized lookups still
    cost a single collective."""
    ok, idx = _vp_shard_index(embed_local.shape[0], tokens, axis_name)
    rows = embed_local[idx]
    if scale_local is not None:
        rows = rows.astype(scale_local.dtype) \
            * scale_local[idx][..., None]
    return lax.psum(jnp.where(ok[..., None], rows, 0), axis_name)


@partial(jax.custom_jvp, nondiff_argnums=(1,))
def _stop_pmax(x, axis_name):
    """``pmax`` with a pinned zero tangent: jax has no differentiation
    rule for pmax, and the softmax max anchor genuinely carries no
    gradient (the lse derivative is exact without it), so declare that
    instead of tracing into the primitive."""
    return lax.pmax(x, axis_name)


@_stop_pmax.defjvp
def _stop_pmax_jvp(axis_name, primals, tangents):
    (x,) = primals
    out = lax.pmax(x, axis_name)
    return out, jnp.zeros_like(out)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _vp_head(cd, axis_name, h, embed_local):
    """Local vocab-shard logits slice with :func:`_lm_head`'s dtype
    discipline: compute-dtype operands on the MXU, fp32 accumulation —
    including BOTH transposed gradient matmuls, which a plain einsum
    would run as fp32 dots against the fp32 logits cotangent."""
    return jnp.einsum("btd,vd->btv", h.astype(cd),
                      embed_local.astype(cd),
                      preferred_element_type=jnp.float32)


def _vp_head_fwd(cd, axis_name, h, embed_local):
    return _vp_head(cd, axis_name, h, embed_local), (h, embed_local)


def _vp_head_bwd(cd, axis_name, res, g):
    h, embed_local = res
    gl = g.astype(cd)
    # h is replicated over the vocab axis but consumed by per-shard
    # slices: its true cotangent is the SUM of the members' partials
    # (the psum shard_map AD would insert for the plain einsum)
    dh = lax.psum(
        jnp.einsum("btv,vd->btd", gl, embed_local.astype(cd),
                   preferred_element_type=jnp.float32).astype(h.dtype),
        axis_name)
    dw = jnp.einsum("btv,btd->vd", gl, h.astype(cd),
                    preferred_element_type=jnp.float32
                    ).astype(embed_local.dtype)
    # the embed SHARD's cotangent psums over the batch-like axes it is
    # invariant on — but NOT over the vocab axis (each member's shard
    # gradient is distinct; summing them would be wrong)
    dw = _psum_over_vma(dw, exclude=(axis_name,))
    return dh, dw


_vp_head.defvjp(_vp_head_fwd, _vp_head_bwd)


def _vp_nll_sum(cd, h, embed_local, targets, axis_name: str = "model"):
    """Vocab-parallel cross-entropy NLL **sum** (Megatron-style).

    Each member computes only its (B, T, V/M) logits slice — the head
    matmul and both of its grad matmuls shrink M× — and the softmax
    reduces across shards with three query-sized collectives: pmax of
    the row max (under stop_gradient: it only anchors the exp), psum of
    the exp-sum, psum of the owner's target logit."""
    logits = _vp_head(cd, axis_name, h, embed_local)
    m = _stop_pmax(jnp.max(lax.stop_gradient(logits), axis=-1),
                   axis_name)                             # (B, T)
    se = lax.psum(
        jnp.sum(jnp.exp(logits - m[..., None]), axis=-1), axis_name)
    lse = jnp.log(se) + m                                 # (B, T)
    ok, idx = _vp_shard_index(embed_local.shape[0], targets, axis_name)
    tl = jnp.take_along_axis(logits, idx[..., None], axis=-1)[..., 0]
    tl = lax.psum(jnp.where(ok, tl, 0.0), axis_name)
    return jnp.sum(lse - tl)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _vp_head_nll(cd, axis_name, chunk, h, embed_local, targets):
    """Token-chunked **and** vocab-parallel NLL sum — the composition
    of :func:`_head_nll` and :func:`_vp_nll_sum`: live logits shrink to
    ``(B, chunk, V/M)`` (both savings multiply), each chunk pays the
    three query-sized shard reductions, and backward recomputes
    per-chunk while accumulating the embed-SHARD cotangent in an fp32
    scan carry so its cross-axis psum fires once — never per chunk."""
    B, T, D = h.shape
    if T % chunk:
        raise ValueError(
            f"loss_chunk={chunk} must divide the local sequence length "
            f"{T} (global seq / seq-axis size)")
    C = T // chunk
    Vl = embed_local.shape[0]
    hc = h.reshape(B, C, chunk, D).transpose(1, 0, 2, 3)
    tc = targets.reshape(B, C, chunk).transpose(1, 0, 2)
    ew = embed_local.astype(cd)

    def body(acc, ht):
        hh, tt = ht
        logits = jnp.einsum("bcd,vd->bcv", hh.astype(cd), ew,
                            preferred_element_type=jnp.float32)
        m = _stop_pmax(jnp.max(lax.stop_gradient(logits), axis=-1),
                       axis_name)
        se = lax.psum(
            jnp.sum(jnp.exp(logits - m[..., None]), axis=-1), axis_name)
        lse = jnp.log(se) + m
        ok, idx = _vp_shard_index(Vl, tt, axis_name)
        tl = jnp.take_along_axis(logits, idx[..., None], axis=-1)[..., 0]
        tl = lax.psum(jnp.where(ok, tl, 0.0), axis_name)
        return acc + jnp.sum(lse - tl, dtype=jnp.float32), None

    # seed from h so the carry inherits h's varying axes and stays
    # model-invariant, exactly like the unchunked path's output
    acc0 = jnp.sum(h * 0, dtype=jnp.float32)
    out, _ = lax.scan(body, acc0, (hc, tc))
    return out


def _vp_head_nll_fwd(cd, axis_name, chunk, h, embed_local, targets):
    return _vp_head_nll(cd, axis_name, chunk, h, embed_local, targets), \
        (h, embed_local, targets)


def _vp_head_nll_bwd(cd, axis_name, chunk, res, g):
    h, embed_local, targets = res
    B, T, D = h.shape
    Vl = embed_local.shape[0]
    C = T // chunk
    hc = h.reshape(B, C, chunk, D).transpose(1, 0, 2, 3)
    tc = targets.reshape(B, C, chunk).transpose(1, 0, 2)
    ew = embed_local.astype(cd)
    g32 = g.astype(jnp.float32)

    def body(dw, ht):
        hh, tt = ht
        hcd = hh.astype(cd)
        logits = jnp.einsum("bcd,vd->bcv", hcd, ew,
                            preferred_element_type=jnp.float32)
        # recompute the global softmax's denominator (same two
        # query-sized collectives as forward)
        m = lax.pmax(jnp.max(logits, axis=-1), axis_name)
        se = lax.psum(
            jnp.sum(jnp.exp(logits - m[..., None]), axis=-1), axis_name)
        lse = jnp.log(se) + m
        p = jnp.exp(logits - lse[..., None])   # local slice, global sm
        ok, idx = _vp_shard_index(Vl, tt, axis_name)
        onehot = jax.nn.one_hot(idx, Vl, dtype=p.dtype) * ok[..., None]
        dl = ((p - onehot) * g32).astype(cd)
        # h is model-invariant but consumed per shard slice: its true
        # cotangent sums the members' partials (see _vp_head_bwd) —
        # cast BEFORE the psum so the bf16 wire volume matches it too
        dh_c = lax.psum(
            jnp.einsum("bcv,vd->bcd", dl, ew,
                       preferred_element_type=jnp.float32
                       ).astype(h.dtype), axis_name)
        dw = dw + jnp.einsum("bcv,bcd->vd", dl, hcd,
                             preferred_element_type=jnp.float32)
        return dw, dh_c

    # carry seed carries BOTH h's and the shard's varying axes so the
    # accumulated dw types like the body's output
    dw0 = jnp.zeros((Vl, D), jnp.float32) \
        + jnp.sum(h * 0, dtype=jnp.float32) \
        + jnp.sum(embed_local * 0, dtype=jnp.float32) + g32 * 0
    dw, dhc = lax.scan(body, dw0, (hc, tc))
    dh = dhc.transpose(1, 0, 2, 3).reshape(B, T, D)
    dw = dw.astype(embed_local.dtype)
    # single psum over the batch-like axes, NOT the vocab axis (each
    # member's shard gradient is distinct) — once, never per chunk
    dw = _psum_over_vma(dw, exclude=(axis_name,))
    return dh, dw, None


_vp_head_nll.defvjp(_vp_head_nll_fwd, _vp_head_nll_bwd)


def _shard_nll_sum(cfg, h_normed, embed, targets):
    """Local-shard NLL **sum** through the configured head path:
    ``vocab_parallel`` reduces over model-axis vocab shards,
    ``loss_chunk > 0`` takes the chunked custom-VJP head, and the two
    COMPOSE (live logits ``(B, chunk, V/M)``); else the whole shard's
    logits materialise once through :func:`_lm_head`."""
    if cfg.vocab_parallel:
        if cfg.loss_chunk > 0:
            return _vp_head_nll(cfg.compute_dtype, "model",
                                cfg.loss_chunk, h_normed, embed, targets)
        return _vp_nll_sum(cfg.compute_dtype, h_normed, embed, targets)
    chunk = cfg.loss_chunk
    if chunk > 0:
        # chunk == T is the C=1 edge of the chunked path; a chunk that
        # does not divide T (including chunk > T) raises in _head_nll
        return _head_nll(cfg.compute_dtype, chunk, h_normed, embed,
                         targets)
    logits = _lm_head(cfg.compute_dtype, h_normed, embed)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(
        logp, targets[..., None], axis=-1).sum(dtype=jnp.float32)


def _attention(cfg: TransformerConfig, h, blk, kind=None):
    """Pre-LN token mixing: ``h + mixer(norm(h))``, the mixer the record
    of ``MIXERS`` that ``kind`` names (``models/mixers.py``; softmax
    attention for an untyped layer, ``kind`` None, by the config's
    window and rotary parameters).  The layer's ops carry
    ``attn/<kind.name>`` in their ``op_name``; where the layers are all
    alike, ``attn/sliding`` under an ``attention_window`` and
    ``attn/full`` without one.  The mixer names its inner scopes."""
    name = kind.name if kind is not None else (
        "sliding" if cfg.attention_window else "full")
    mixer = _mixer(cfg, kind)
    norm_scope = device_scope(mixer.norm_scope) if mixer.norm_scope \
        else nullcontext()
    with device_scope(f"attn/{name}"):
        with norm_scope:
            x = _norm(cfg, h, blk["ln1"])
        return h + mixer.apply(cfg, x, blk, kind)


def _gated(cfg: TransformerConfig, act: str, x, w1, w3, w2):
    """A dense MLP as a column→row TP pair: ``w2(relu(w1 x))``,
    ``w2(relu(w1 x)^2)`` or the gated ``w2(silu(w1 x) * w3 x)``, by
    ``act``."""
    cd = cfg.compute_dtype
    y = column_parallel_dense(x, w1.astype(cd))
    if act == "swiglu":
        y = jax.nn.silu(y) * column_parallel_dense(x, w3.astype(cd))
    else:
        y = _relu_or_its_square(act, y)
    return row_parallel_dense(y, w2.astype(cd))


def _whole_tiles(w, axis: int):
    """Held experts' weights ``(G, D, F)`` / ``(G, F, D)`` with the
    experts' width ``F`` (``axis``) zero-padded to whole 128-lane tiles;
    ``w`` itself where it is whole lanes already (896, 512, 1,024: four
    of the five cells) or under one tile (a test's).  The padded units
    are exact zeros through every activation here (``relu(0)``, its
    square, ``silu(0) * 0``) and meet zero rows of ``w2``, and the pad's
    transpose drops their gradient.  Read on the chip through the
    kernels of ``ops/grouped_matmul.py`` (PERF.md section 6, PR 47):
    one Nemotron layer's grouped products, forward twice and both
    backward passes, 4.32 ms at its width of 1,856 (14.5 tiles: the
    weights' backward pays for the half tile), 3.93 at 1,920 and 4.14
    at 2,048, the whole PAIR of tiles that XLA's kernel for
    ``lax.ragged_dot`` wanted (PR 40: 21.6, 22.0 and 12.8 ms) and that
    nothing asks for since that kernel is off the TPU's path."""
    width = w.shape[axis]
    if width < 128 or width % 128 == 0:
        return w
    widths = [(0, 0)] * w.ndim
    widths[axis] = (0, -width % 128)
    return jnp.pad(w, widths)


def _relu_or_its_square(act: str, y):
    y = jax.nn.relu(y)
    return jnp.square(y) if act == "relu2" else y


def _mlp(cfg: TransformerConfig, h, blk, with_chosen=False, sparse=None):
    """Pre-LN MLP: dense (column→row TP pair, one psum) or Switch-MoE
    (expert all-to-alls; experts' FFNs are themselves TP-split), by
    ``sparse`` (None: the config's ``moe``).  ``with_chosen`` (dropless
    dispatch) also returns the ``(B, T, k)`` experts each token chose,
    for :func:`expert_choices`.  The dense MLP and the shared expert
    carry ``mlp/dense`` and ``moe/shared`` in their ``op_name`` (the
    dropless layer names its own parts)."""
    cd = cfg.compute_dtype
    sparse = cfg.moe if sparse is None else sparse
    x = _norm(cfg, h, blk["ln2"])
    if with_chosen and not (sparse and cfg.moe_dispatch == "dropless"):
        raise ValueError("the choices are read from the dropless layer")
    if not sparse:
        with device_scope("mlp/dense"):
            out = h + _gated(cfg, cfg.dense_act, x, blk["w1"],
                             blk.get("w3"), blk["w2"])
        return out, jnp.zeros((), jnp.float32)
    B, T, D = x.shape
    if cfg.moe_dispatch == "dropless":
        def grouped_fn(p, rows, sizes):
            # the experts' FFNs over rows sorted by expert, TP-split
            # like the dense pair: column (no exchange), row (one psum)
            y = grouped_dense(rows, p["w1"], sizes)
            if cfg.expert_act == "swiglu":
                y = jax.nn.silu(y) * grouped_dense(rows, p["w3"], sizes)
            else:
                y = _relu_or_its_square(cfg.expert_act, y)
            return lax.psum(grouped_dense(y, p["w2"], sizes), "model")

        out, aux, chosen = expert_parallel_moe_dropless(
            x.reshape(B * T, D),
            blk["router"],
            {k: _whole_tiles(blk[k].astype(cd), axis=1 if k == "w2" else 2)
             for k in ("w1", "w2", "w3") if k in blk},
            grouped_fn,
            top_k=cfg.router_top_k,
            first_expert=cfg.experts_held[0] if cfg.experts_held else 0,
            score=cfg.router_score,
            scale=cfg.router_scale,
            # no gradient reaches the selection bias: its update rule is
            # not the loss's
            bias=lax.stop_gradient(blk["router_bias"])
            if "router_bias" in blk else None,
            axis_name="expert",
        )
        out = out.reshape(B, T, D)
        if "ws1" in blk:
            # the expert every token meets: whole on each member of the
            # expert group, for the member's own tokens; gated by one
            # sigmoid scalar a token where the block has ``wsg``
            with device_scope("moe/shared"):
                shared = _gated(cfg, cfg.expert_act, x, blk["ws1"],
                                blk.get("ws3"), blk["ws2"])
                if "wsg" in blk:
                    shared = shared * jax.nn.sigmoid(jnp.dot(
                        x, blk["wsg"].astype(cd),
                        preferred_element_type=jnp.float32)).astype(cd)
                out = out + shared
        if with_chosen:
            return h + out, aux, chosen.reshape(B, T, -1)
        return h + out, aux

    def expert_fn(p, tokens):
        y = jax.nn.relu(column_parallel_dense(tokens, p["w1"]))
        return row_parallel_dense(y, p["w2"])

    out, aux = expert_parallel_moe(
        x.reshape(B * T, D),
        blk["router"].astype(cd),
        {"w1": blk["w1"].astype(cd), "w2": blk["w2"].astype(cd)},
        expert_fn,
        axis_name="expert",
        capacity_factor=cfg.capacity_factor,
        top_k=cfg.router_top_k,
    )
    return h + out.reshape(B, T, D), aux


def _block(cfg: TransformerConfig, h, blk, kind=None, with_chosen=False,
           sparse=None):
    """One layer: the parts its ``kind`` has, each ``h + part(norm(h))``.
    Returns ``(h, aux)``, with the chosen experts third under
    ``with_chosen`` (None from a layer that has no MLP)."""
    if cfg.fsdp:
        blk = _fsdp_gather(cfg, blk)
    part = cfg.part_of(kind)
    if part != "mlp":
        h = _attention(cfg, h, blk, kind)
    if part == "mixer":
        aux = jnp.zeros((), jnp.float32)
        return (h, aux, None) if with_chosen else (h, aux)
    return _mlp(cfg, h, blk, with_chosen, sparse)


def _scan_layers(cfg: TransformerConfig, layer_fn, carry, blocks):
    """``lax.scan`` of ``layer_fn(carry, blk, kind) -> (carry, y)`` over
    the leading (layer) axis of ``blocks``.  Under a ``layer_pattern``
    one compiled body cannot serve every layer (a kind's window is a
    static argument of the kernel), so the scan runs over whole periods
    and its body unrolls the period's kinds.  One stack keeps its
    ``(layers, ...)`` layout and is only viewed as ``(periods, kinds,
    ...)`` here; where the kinds differ in shape ``blocks`` is a tuple
    of stacks over the periods, one per position
    (``cfg.blocks_by_position``).  ``y`` comes back stacked by layer
    either way; a layer whose ``y`` is None has no row (a layer without
    a router has chosen no experts)."""
    kinds = cfg.layer_pattern
    if not kinds:
        return lax.scan(lambda c, blk: layer_fn(c, blk, None), carry, blocks)
    n = len(kinds)
    if isinstance(blocks, tuple):
        by_period = blocks
    else:
        layers = jax.tree.leaves(blocks)[0].shape[0]
        if layers % n:
            raise ValueError(
                f"{layers} layers on this pipeline stage are not whole "
                f"periods of the {n}-layer pattern")
        by_period = jax.tree.map(
            lambda a: a.reshape(layers // n, n, *a.shape[1:]), blocks)

    def period(c, blks):
        ys = []
        for j, kind in enumerate(kinds):
            blk = blks[j] if isinstance(blks, tuple) else jax.tree.map(
                lambda a: a[j], blks)
            c, y = layer_fn(c, blk, kind)
            if y is not None:
                ys.append(y)
        return c, jax.tree.map(lambda *a: jnp.stack(a), *ys) if ys else None

    carry, ys = lax.scan(period, carry, by_period)
    return carry, ys if ys is None else jax.tree.map(
        lambda a: a.reshape(-1, *a.shape[2:]), ys)


def _stage(cfg: TransformerConfig, stage_params, h):
    """One pipeline stage = scan over its ``layers_per_stage`` blocks,
    returning ``(h, aux)`` — the summed MoE balancing loss of the
    stage's layers rides the schedule via ``pipeline_apply(with_aux=
    True)`` instead of being dropped."""

    def body(carry, blk, kind):
        h, aux = carry
        out, a = _block(cfg, h, blk, kind)
        return (out, aux + a), None

    aux0 = jnp.sum(h * 0, dtype=jnp.float32)
    (h, aux), _ = _scan_layers(cfg, body, (h, aux0), stage_params)
    return h, aux


def _embed(cfg: TransformerConfig, params, tokens):
    """Token rows (+ the learned positions' rows) in the compute dtype;
    by transposition the scatter-add into the embedding's gradient."""
    cd = cfg.compute_dtype
    B, T = tokens.shape
    r = lax.axis_index("seq")

    with device_scope("step/embed"):
        if cfg.vocab_parallel:
            h = _vp_embed_lookup(params["embed"], tokens)  # (B, T, D) fp32
        else:
            h = params["embed"][tokens]                    # (B, T, D) fp32
        if cfg.pos_embedding == "rope":
            return h.astype(cd)       # rotations happen inside attention
        if cfg.seq_layout == "zigzag":
            # position rows follow the zigzag permutation of this shard
            return (h + params["pos"][
                _block_positions(r, T, lax.axis_size("seq"), "zigzag")]
            ).astype(cd)
        return (h + lax.dynamic_slice_in_dim(
            params["pos"], r * T, T, axis=0)).astype(cd)


def transformer_backbone(cfg: TransformerConfig, params, tokens):
    """Embedding → block stack → final norm.  Call INSIDE shard_map.

    Args:
      params: local shards per :func:`param_specs` (blocks carry the
        ``(pipe_local=1, layers_per_stage, ...)`` leading axes).
      tokens: ``(B_local, T_local)`` int32 — batch sharded over
        ``("data","expert")``, sequence over ``seq``.

    Returns the normed ``(B_local, T_local, d_model)`` hidden states and
    the summed MoE aux loss (zero when ``moe=False`` or pipelined).
    The weight-tied LM head is applied by :func:`transformer_forward`
    (whole-shard logits) or :func:`lm_loss` (optionally chunked)."""
    if cfg.seq_layout == "zigzag" and cfg.attention != "ring":
        raise ValueError(
            'seq_layout="zigzag" is a ring-attention layout; '
            f'attention={cfg.attention!r} expects contiguous shards')
    h = _embed(cfg, params, tokens)
    S = lax.axis_size("pipe")
    # the stack of blocks under one name: what wears no inner scope there
    # is the stack's own (the scan's slicing and its saved activations,
    # the residual adds, the MLP's norm)
    with device_scope("step/layers"):
        if cfg.virtual_pipe > 1:
            # forward-only traversal of the V chunk rings: chunk c of every
            # device runs as one GPipe pass; the next chunk's pass consumes
            # its output (virtual stage order g = c·S + s is preserved).
            # The interleaved schedule proper only matters when backward
            # timing is involved — make_train_step uses it.
            aux = jnp.zeros((), jnp.float32)
            for c in range(cfg.virtual_pipe):
                chunk = jax.tree.map(lambda a: a[:, c], params["blocks"])
                h, a = pipeline_apply(
                    partial(_stage, cfg),
                    chunk,
                    h,
                    axis_name="pipe",
                    num_microbatches=cfg.num_microbatches,
                    remat=cfg.remat,
                    with_aux=True,
                    checkpoint_fn=cfg.checkpoint_fn,
                )
                aux = aux + a
        elif S > 1 or cfg.num_microbatches > 1:
            h, aux = pipeline_apply(
                partial(_stage, cfg),
                params["blocks"],
                h,
                axis_name="pipe",
                num_microbatches=cfg.num_microbatches,
                remat=cfg.remat,
                with_aux=True,
                checkpoint_fn=cfg.checkpoint_fn,
            )
        else:
            blocks = jax.tree.map(
                lambda a: jnp.squeeze(a, axis=0), params["blocks"])

            def body(carry, blk, kind):
                h, aux = carry
                fn = cfg.checkpoint_fn(partial(_block, cfg, kind=kind))
                h, a = fn(h, blk)
                return (h, aux + a), None

            # block params are pipe-sharded (varying) even at pipe size 1, so
            # the carry must be marked pipe-varying going in; the closing psum
            # over the size-1 axis is a free re-replication (vma discipline).
            # aux derives from h so it inherits the batch axes' variance too.
            vary = partial(lax.pcast, axis_name=("pipe",), to="varying")
            aux = jnp.sum(h * 0, dtype=jnp.float32)
            # the layers that lead run ahead of the scan, each under the
            # same checkpoint (their blocks are not pipe-sharded)
            for blk, kind in zip(params.get("leading", ()),
                                 cfg.leading_layers):
                h, a = cfg.checkpoint_fn(partial(
                    _block, cfg, kind=kind, sparse=cfg.leading_sparse))(h, blk)
                aux = aux + a
            (h, aux), _ = _scan_layers(
                cfg, body, (vary(h), vary(aux)), blocks)
            h = lax.psum(h, "pipe")
            aux = lax.psum(aux, "pipe")

    with device_scope("step/head"):
        return _norm(cfg, h, params["ln_f"]), aux


def _head_matrix(cfg: TransformerConfig, params):
    """The output matrix: the embedding itself when tied."""
    return params["embed"] if cfg.tie_embeddings else params["head"]


def transformer_forward(cfg: TransformerConfig, params, tokens):
    """``(B_local, T_local, vocab)`` fp32 logits + MoE aux loss.

    Whole-shard logits through the weight-tied head (fp32 for a stable
    softmax, compute-dtype matmul operands — see :func:`_lm_head`);
    decoding and forward-only callers want the actual logits tensor, so
    ``loss_chunk`` does not apply here and ``vocab_parallel`` gathers
    the vocab shards back to full width (training's loss path never
    pays that gather — see :func:`_vp_nll_sum`)."""
    h, aux = transformer_backbone(cfg, params, tokens)
    with device_scope("step/head"):
        if cfg.vocab_parallel:
            # _vp_head, not _lm_head: the latter's custom VJP psums the
            # embed cotangent over every varying axis, which would
            # wrongly sum the DISTINCT vocab shards over model
            logits = _vp_head(cfg.compute_dtype, "model", h,
                              _head_matrix(cfg, params))
            # invariant gather: the full logits are identical on every
            # model member, and the vma type must say so for out_specs
            return _all_gather_invariant(
                logits, "model", axis=2, tiled=True), aux
        return _lm_head(
            cfg.compute_dtype, h, _head_matrix(cfg, params)), aux


# coefficient of the Switch-MoE balancing loss in the training objective
# (identical across the GPipe/1F1B/interleaved paths so the schedules
# optimise the same function)
_AUX_WEIGHT = 0.01


def lm_loss(cfg: TransformerConfig, params, inputs, targets):
    """Local-shard mean next-token cross-entropy (+0.01·aux)."""
    h, aux = transformer_backbone(cfg, params, inputs)
    # logits and loss, with the head's custom VJPs
    with device_scope("step/head"):
        nll_sum = _shard_nll_sum(
            cfg, h, _head_matrix(cfg, params), targets)
        return nll_sum / targets.size + _AUX_WEIGHT * aux


def expert_choices(mesh_cfg, cfg: TransformerConfig, params, tokens):
    """``(sparse layers, B, T, router_top_k)`` int32: the experts every
    token chose in every layer that has a router (a dense layer and a
    layer that is a mixer alone have no row), held here or not.  A forward pass through the step's own blocks and
    router (dropless dispatch), for counters and comparisons.
    Unpipelined meshes only."""
    _check_mesh(mesh_cfg, cfg)
    if mesh_cfg.mesh.shape.get("pipe", 1) > 1 or cfg.virtual_pipe > 1:
        raise ValueError("expert_choices reads an unpipelined layer stack")

    def fwd(params, tokens):
        h = _embed(cfg, params, tokens)
        blocks = jax.tree.map(
            lambda a: jnp.squeeze(a, axis=0), params["blocks"])
        leading = []
        for blk, kind in zip(params.get("leading", ()), cfg.leading_layers):
            out = _block(cfg, h, blk, kind, with_chosen=cfg.leading_sparse,
                         sparse=cfg.leading_sparse)
            h = out[0]
            leading += out[2:]      # a dense layer chose nothing

        def body(h, blk, kind):
            h, _, chosen = _block(cfg, h, blk, kind, with_chosen=True)
            return h, chosen

        vary = partial(lax.pcast, axis_name=("pipe",), to="varying")
        _, chosen = _scan_layers(cfg, body, vary(h), blocks)
        chosen = lax.psum(chosen, "pipe")
        return jnp.concatenate([jnp.stack(leading), chosen]) \
            if leading else chosen

    return jax.jit(jax.shard_map(
        tracing_for_mesh(mesh_cfg.mesh, fwd), mesh=mesh_cfg.mesh,
        in_specs=(param_specs(cfg), _BATCH_SPEC),
        out_specs=P(None, *_BATCH_SPEC),
    ))(params, tokens)


def expert_load(mesh_cfg, cfg: TransformerConfig, params, tokens):
    """``(sparse layers, n_experts)`` int32: the rows the router of
    every layer that has one sent to each expert for these tokens (all
    ``router_top_k`` choices of each token): what the grouped products
    of a step have to do."""
    chosen = expert_choices(mesh_cfg, cfg, params, tokens)
    return jax.vmap(lambda c: jnp.zeros((cfg.n_experts,), jnp.int32).at[
        c.reshape(-1)].add(1))(chosen)


def expert_buffer_rows(mesh_cfg, cfg: TransformerConfig, params, tokens):
    """``(sparse layers, 2)`` int32: of these tokens' (token, choice)
    rows, those that fall to the experts held here, and the rows of the
    sorted buffer the dropless layer then works on (the rung of
    ``parallel.expert``'s ladder it takes, by the function the layer
    itself calls), a layer.  Where the tokens are split over several
    members of the mesh, the member that holds most."""
    chosen = expert_choices(mesh_cfg, cfg, params, tokens)
    shape = mesh_cfg.mesh.shape
    members, seqs = shape["data"] * shape["expert"], shape["seq"]
    layers, B, T, k = chosen.shape
    first, held = cfg.experts_held or (0, cfg.n_experts)
    here = (chosen >= first) & (chosen < first + held)
    here = here.reshape(layers, members, B // members, seqs, T // seqs, k)
    count = here.sum(axis=(2, 4, 5), dtype=jnp.int32).max(axis=(1, 2))
    # one expert group sorts a member's tokens for all its experts
    return jnp.stack([count, buffer_rows(
        count, (B // members) * (T // seqs) * k, held, cfg.n_experts)],
        axis=1)


# --------------------------------------------------------------------- #
# jitted entry points
# --------------------------------------------------------------------- #

_BATCH_SPEC = P(("data", "expert"), "seq")


def _make_1f1b_grad(cfg: TransformerConfig):
    """Build the 1F1B value-and-grad body (call inside shard_map).

    Decomposition: embedding runs outside the schedule (its input grads
    come back as the schedule's ``dx``); the transformer stack is the
    pipelined stage function; final norm + weight-tied LM head + softmax
    cross-entropy form the in-schedule ``loss_fn`` whose parameter
    gradients (``ln_f`` and the head side of ``embed``) flow through the
    schedule's ``loss_params`` path.
    """
    cd = cfg.compute_dtype

    if cfg.moe:
        # _stage already returns (h, aux); the schedule's with_aux path
        # carries the Switch balancing loss AND its gradients (every
        # stage seeds its own aux cotangent at _AUX_WEIGHT)
        stage_fn = partial(_stage, cfg)
    else:
        def stage_fn(p, mb):
            h, _ = _stage(cfg, p, mb)
            return h

    def grad_body(params, inputs, targets):
        B, T = inputs.shape
        r = lax.axis_index("seq")

        def embed_fn(ep):
            with device_scope("step/embed"):
                if cfg.vocab_parallel:
                    h = _vp_embed_lookup(ep["embed"], inputs)
                else:
                    h = ep["embed"][inputs]
                if cfg.pos_embedding == "rope":
                    return h.astype(cd)
                pos = lax.dynamic_slice_in_dim(
                    ep["pos"], r * T, T, axis=0)
                return (h + pos).astype(cd)

        ep = {"embed": params["embed"]}
        if cfg.pos_embedding == "learned":
            ep["pos"] = params["pos"]
        h, vjp_embed = jax.vjp(embed_fn, ep)

        def loss_fn(lp, y, tgt):
            with device_scope("step/head"):
                hN = _norm(cfg, y, lp["ln_f"])
                return _shard_nll_sum(
                    cfg, hN, lp["embed"], tgt) / tgt.size

        lp = {"ln_f": params["ln_f"], "embed": _head_matrix(cfg, params)}
        aux_kw = dict(with_aux=True, aux_weight=_AUX_WEIGHT) \
            if cfg.moe else {}
        if cfg.pipeline_schedule == "interleaved":
            out = pipeline_train_interleaved(
                stage_fn, loss_fn, params["blocks"], lp, h, targets,
                axis_name="pipe", num_microbatches=cfg.num_microbatches,
                num_chunks=cfg.virtual_pipe, **aux_kw)
        else:
            out = pipeline_train_1f1b(
                stage_fn, loss_fn, params["blocks"], lp, h, targets,
                axis_name="pipe", num_microbatches=cfg.num_microbatches,
                **aux_kw)
        if cfg.moe:
            loss, aux, g_blocks, g_lp, dx = out
            # report the same scalar the GPipe path's lm_loss computes
            loss = loss + _AUX_WEIGHT * aux
        else:
            loss, g_blocks, g_lp, dx = out
        (d_ep,) = vjp_embed(dx)

        grads = {"blocks": g_blocks, "ln_f": g_lp["ln_f"]}
        if cfg.tie_embeddings:
            # weight tying: embedding grads = lookup side + head side
            grads["embed"] = d_ep["embed"] + g_lp["embed"]
        else:
            grads["embed"], grads["head"] = d_ep["embed"], g_lp["embed"]
        if cfg.pos_embedding == "learned":
            grads["pos"] = d_ep["pos"]
        # Normalisation: every parameter is REPLICATED over the
        # data-like axes, so the shard_map transposes inside the manual
        # vjp calls have already PSUMMED each gradient over
        # (data, expert, seq) — the GPipe path folds the 1/N into the
        # differentiated pmean; here the grads come back as global sums
        # and need the explicit 1/N to become the global mean.
        axes = ("data", "expert", "seq")
        n = (lax.axis_size("data") * lax.axis_size("expert")
             * lax.axis_size("seq"))
        loss = lax.pmean(loss, axes)
        grads = jax.tree.map(lambda g: g / n, grads)
        return loss, grads

    return grad_body


def _check_mesh(mesh_cfg, cfg: TransformerConfig):
    """Config↔mesh divisibility checks with actionable messages (instead
    of opaque GSPMD placement errors deep inside jit)."""
    mp = mesh_cfg.mesh.shape.get("model", 1)
    sp = mesh_cfg.mesh.shape.get("seq", 1)
    if cfg.n_heads % mp:
        raise ValueError(
            f"n_heads={cfg.n_heads} must be divisible by the model mesh "
            f"axis ({mp})")
    for kind in cfg.layer_pattern + cfg.leading_layers:
        if cfg.heads_of(kind) % mp:
            raise ValueError(
                f"{kind.name}: n_heads={kind.n_heads} must be divisible "
                f"by the model mesh axis ({mp})")
    if cfg.mixers:
        names = "/".join(cfg.mixers)
        axes = {a: mesh_cfg.mesh.shape.get(a, 1)
                for a in ("seq", "model", "pipe")}
        if max(axes.values()) > 1:
            raise ValueError(
                f"the {names} layers run whole on a device: their "
                "recurrence and latent are not split over heads, "
                "sequence or stages yet, so the seq, model and pipe "
                f"mesh axes must be 1 (got {axes}); data and expert "
                "are open")
        if cfg.attention not in ("flash", "local"):
            raise ValueError(
                f"the {names} layers run with attention='flash' or "
                f"'local' (got {cfg.attention!r}): the ring and Ulysses "
                "exchanges move softmax attention's q/k/v heads only")
        if cfg.fsdp:
            raise ValueError(
                f"fsdp=True is not implemented for the {names} layers: "
                "_fsdp_dims has no dims for their leaves")
    if cfg.parts_alone:
        axes = {a: mesh_cfg.mesh.shape.get(a, 1)
                for a in ("seq", "model", "pipe")}
        if max(axes.values()) > 1 or cfg.fsdp:
            raise ValueError(
                "layers that are a mixer or a feed-forward part alone "
                "(AttentionKind.part) are proved on data and expert "
                "meshes only: the seq, model and pipe mesh axes must be "
                f"1 and fsdp off (got {axes}, fsdp={cfg.fsdp})")
    if (cfg.leading_layers or cfg.blocks_by_position) and (
            mesh_cfg.mesh.shape.get("pipe", 1) > 1 or cfg.virtual_pipe > 1
            or cfg.num_microbatches > 1
            or cfg.pipeline_schedule != "gpipe"):
        raise ValueError(
            "leading_layers and layer kinds with query heads of their own "
            "(a stack per position of the pattern) run on an unpipelined "
            "mesh only: no pipeline schedule gives its first stage the "
            "leading layers or splits a tuple of stacks into stages yet "
            "(pipe axis 1, num_microbatches 1, virtual_pipe 1, gpipe)")
    if cfg.kv_heads % mp:
        raise ValueError(
            f"n_kv_heads={cfg.kv_heads} must be divisible by the model "
            f"mesh axis ({mp}); raise n_kv_heads or shrink the model "
            "axis (shared kv heads shard over the same axis as query "
            "heads)")
    if cfg.attention == "ulysses" and sp > 1 and any(
            (cfg.heads_of(k) // mp) % sp
            for k in (None,) + cfg.layer_pattern + cfg.leading_layers):
        raise ValueError(
            f"attention='ulysses' splits query heads over the seq axis: "
            f"n_heads/model ({cfg.n_heads}/{mp}) must be divisible by "
            f"the seq mesh axis ({sp}).  Shared kv heads need NOT "
            "divide — they replicate up to lcm for the exchange — and "
            "ring attention keeps them at true width if the surplus "
            "factor matters")
    if cfg.vocab_parallel and cfg.vocab_size % mp:
        raise ValueError(
            f"vocab_parallel shards the vocab dim over the model axis: "
            f"vocab_size={cfg.vocab_size} must be divisible by {mp}")
    ep = mesh_cfg.mesh.shape.get("expert", 1)
    if cfg.moe and cfg.n_experts_held % ep:
        raise ValueError(
            f"the {cfg.n_experts_held} experts held must divide over the "
            f"expert mesh axis ({ep})")
    dp = mesh_cfg.mesh.shape.get("data", 1)
    if cfg.fsdp and cfg.d_model % dp:
        raise ValueError(
            f"fsdp shards every matrix's d_model dim over the data "
            f"axis: d_model={cfg.d_model} must be divisible by the "
            f"data mesh axis ({dp})")


def shard_params(mesh_cfg, cfg: TransformerConfig, params):
    """Place a host-initialised param pytree per :func:`param_specs`.

    The reference's ``comm.bcast_data(model)`` moment: after this, every
    device holds exactly its shard (replicated leaves on all).  Handles
    both plain and int8-quantized (``quantize_params_int8``) trees."""
    _check_mesh(mesh_cfg, cfg)
    return jax.tree.map(
        lambda a, s: jax.device_put(a, mesh_cfg.sharding(*s)),
        params, param_specs(cfg, quantized="embed_scale" in params))


def make_forward_fn(mesh_cfg, cfg: TransformerConfig):
    """``fn(params, tokens) -> logits`` — jittable, shard_map'd over the
    full mesh.  Single-chip (all axes 1) and 5-axis runs share this path."""

    _check_mesh(mesh_cfg, cfg)

    def fwd(params, tokens):
        logits, _ = transformer_forward(cfg, params, tokens)
        return logits

    return jax.jit(
        jax.shard_map(
            tracing_for_mesh(mesh_cfg.mesh, fwd),
            mesh=mesh_cfg.mesh,
            in_specs=(param_specs(cfg), _BATCH_SPEC),
            out_specs=P(("data", "expert"), "seq"),
        ))


def hold_selection_bias(optimizer):
    """``optimizer`` with every leaf named ``router_bias`` out of its
    reach: their updates are zero whatever it computes (no gradient
    reaches the selection bias, but AdamW's weight decay would move
    it).  ``init`` and the state's tree are ``optimizer``'s own, so a
    state made for one fits the other.  :func:`make_train_step` applies
    it where ``cfg.router_bias`` is set; a step of one's own around
    ``optimizer.update`` has to."""
    def update(grads, state, params=None):
        updates, state = optimizer.update(grads, state, params)
        return jax.tree_util.tree_map_with_path(
            lambda path, u: jnp.zeros_like(u) if any(
                getattr(k, "key", None) == "router_bias"
                for k in path) else u, updates), state

    return optax.GradientTransformation(optimizer.init, update)


def make_train_step(mesh_cfg, cfg: TransformerConfig, optimizer):
    """Full jitted SPMD train step over all five axes.

    ``step(params, opt_state, inputs, targets) -> (params, opt_state,
    loss)``; inputs/targets ``(B, T)`` globally, sharded per
    ``_BATCH_SPEC``.  The loss is pmean'd over the batch-like axes inside
    the differentiated function, so shard_map AD inserts the gradient
    psums exactly where ChainerMN ran ``multi_node_mean_grad`` (SURVEY
    §3.1) — and leaves sharded (TP/PP/EP) parameter grads local.

    Only grad computation needs manual SPMD (the parallel modules want
    bound axis names); the optimiser update is elementwise, so it runs
    under plain jit where XLA propagates the grads' shardings through
    arbitrary optax state pytrees (which ``param_specs`` could not
    describe structurally).

    With ``cfg.pipeline_schedule == "1f1b"`` the pipelined portion runs
    the 1F1B schedule (:func:`...parallel.pipeline.pipeline_train_1f1b`)
    — the loss moves INSIDE the schedule (final norm + tied head become
    its ``loss_params``) so each micro-batch's backward starts as soon
    as it clears the last stage, capping in-flight activations at O(S)
    instead of GPipe's O(M).
    """
    _check_mesh(mesh_cfg, cfg)
    specs = param_specs(cfg)
    if cfg.router_bias:
        optimizer = hold_selection_bias(optimizer)

    if cfg.pipeline_schedule in ("1f1b", "interleaved"):
        grad_body = _make_1f1b_grad(cfg)
    elif cfg.pipeline_schedule == "gpipe":
        grad_body = lambda p, x, y: jax.value_and_grad(
            lambda q: lax.pmean(
                lm_loss(cfg, q, x, y), ("data", "expert", "seq")))(p)
    else:
        raise ValueError(
            f"pipeline_schedule must be gpipe|1f1b|interleaved, "
            f"got {cfg.pipeline_schedule!r}")

    grad_fn = jax.shard_map(
        tracing_for_mesh(mesh_cfg.mesh, grad_body),
        mesh=mesh_cfg.mesh,
        in_specs=(specs, _BATCH_SPEC, _BATCH_SPEC),
        out_specs=(P(), specs),
    )

    def step(params, opt_state, inputs, targets):
        loss, grads = grad_fn(params, inputs, targets)
        with device_scope("step/optimizer"):
            updates, new_state = optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
        return new_params, new_state, loss

    return jax.jit(step, donate_argnums=(0, 1))
