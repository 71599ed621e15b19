"""Parallelism strategies — the beyond-reference heart of the TPU build.

The reference (ChainerMN) shipped data parallelism plus hand-wired
model/pipeline parallelism (``MultiNodeChainList``); TP/SP/CP/EP did not
exist there (SURVEY.md §2 "Parallelism-strategy coverage").  This package
supplies all of them, designed for the TPU mesh from the start:

- :mod:`chainermn_tpu.parallel.mesh` — named-axis mesh configuration
  (``data`` × ``model`` × ``pipe`` × ``seq`` × ``expert``), the single
  source of truth every strategy composes over.
- :mod:`chainermn_tpu.parallel.tensor` — tensor parallelism: Megatron-style
  column/row-parallel matmuls as sharding rules (XLA inserts the
  all-reduces) plus explicit shard_map forms.
- :mod:`chainermn_tpu.parallel.pipeline` — pipeline parallelism with
  micro-batching (GPipe fill-drain over ``ppermute`` + ``lax.scan``);
  stage parameters sharded over the ``pipe`` axis. The reference's
  pipeline had ONE activation in flight — micro-batching is the upgrade.
- :mod:`chainermn_tpu.parallel.ring_attention` — context parallelism:
  blockwise ring attention over the ``seq`` axis (K/V blocks rotate along
  the ICI ring while online-softmax accumulates).
- :mod:`chainermn_tpu.parallel.ulysses` — sequence parallelism by
  head↔sequence all-to-all (DeepSpeed-Ulysses style).
- :mod:`chainermn_tpu.parallel.expert` — expert parallelism: token
  dispatch/combine all-to-alls around per-device experts.
- :mod:`chainermn_tpu.parallel.sharded_state` — the unified sharded-state
  layer: per-leaf :class:`LeafLayout` signatures shared by ZeRO-1/2/3,
  :class:`ShardedState` (ZeRO-3 residency + tuned ``fsdp_gather`` plans)
  and :class:`LayerGatherStream` (JIT per-layer gathers with a prefetch
  window).
"""

from chainermn_tpu.parallel.mesh import MeshConfig
from chainermn_tpu.parallel.pipeline import (
    pipeline_apply,
    pipeline_train_1f1b,
    pipeline_train_interleaved,
    stack_stage_params,
)
from chainermn_tpu.parallel.ring_attention import (
    local_attention,
    ring_attention,
    zigzag_indices,
)
from chainermn_tpu.parallel.tensor import (
    column_parallel_dense,
    row_parallel_dense,
)
from chainermn_tpu.parallel.ulysses import ulysses_attention
from chainermn_tpu.parallel.expert import (
    expert_parallel_moe,
    expert_parallel_moe_dropless,
)
from chainermn_tpu.parallel.fsdp import fsdp_dims, fsdp_gather, fsdp_specs
from chainermn_tpu.parallel.sharded_state import (
    LayerGatherStream,
    LeafLayout,
    ShardedState,
    gather_state_leaves,
    shard_state_leaves,
    state_layout_table,
)

__all__ = [
    "LayerGatherStream",
    "LeafLayout",
    "MeshConfig",
    "ShardedState",
    "column_parallel_dense",
    "expert_parallel_moe",
    "expert_parallel_moe_dropless",
    "fsdp_dims",
    "fsdp_gather",
    "fsdp_specs",
    "gather_state_leaves",
    "local_attention",
    "pipeline_apply",
    "pipeline_train_1f1b",
    "pipeline_train_interleaved",
    "ring_attention",
    "row_parallel_dense",
    "shard_state_leaves",
    "stack_stage_params",
    "state_layout_table",
    "ulysses_attention",
    "zigzag_indices",
]
