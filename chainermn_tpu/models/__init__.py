"""Model zoo covering the reference's example models (MNIST MLP, ImageNet
ResNet-50, seq2seq NMT) re-built TPU-first, plus the flagship transformer
exercising every parallelism axis."""

from .convnets import ConvNetConfig, convnet_apply, init_convnet
from .decoding import (
    make_beam_search_fn,
    make_generate_fn,
    make_lookup_generate_fn,
    make_speculative_generate_fn,
)
from .quantization import quantize_params_int8
from .mlp import accuracy, init_mlp, mlp_apply, softmax_cross_entropy
from .resnet import ResNetConfig, init_resnet, resnet_apply
from .seq2seq import (
    Seq2seqConfig,
    init_seq2seq,
    seq2seq_loss,
    seq2seq_translate,
)
from .transformer import (
    AttentionKind,
    TransformerConfig,
    apply_rope,
    expert_buffer_rows,
    expert_choices,
    expert_load,
    init_transformer,
    make_forward_fn,
    make_train_step,
    param_specs,
    regroup_blocks,
    reshard_train_state,
    shard_params,
    transformer_backbone,
    transformer_forward,
)

__all__ = [
    "AttentionKind",
    "ConvNetConfig",
    "ResNetConfig",
    "convnet_apply",
    "init_convnet",
    "Seq2seqConfig",
    "TransformerConfig",
    "apply_rope",
    "expert_buffer_rows",
    "expert_choices",
    "expert_load",
    "init_seq2seq",
    "seq2seq_loss",
    "seq2seq_translate",
    "init_resnet",
    "resnet_apply",
    "accuracy",
    "init_mlp",
    "init_transformer",
    "make_beam_search_fn",
    "make_forward_fn",
    "make_generate_fn",
    "make_lookup_generate_fn",
    "make_speculative_generate_fn",
    "make_train_step",
    "mlp_apply",
    "param_specs",
    "quantize_params_int8",
    "regroup_blocks",
    "reshard_train_state",
    "shard_params",
    "softmax_cross_entropy",
    "transformer_backbone",
    "transformer_forward",
]
