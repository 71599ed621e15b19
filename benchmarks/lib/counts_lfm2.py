"""Operations and bytes a decoder REQUIRES whose token mixer is a doubly
gated short convolution in three layers of four and softmax attention
over grouped key-value heads with q/k norms in the fourth; dense SwiGLU
layers lead, then sparse SwiGLU experts under a sigmoid router with a
selection bias and no shared expert, of which this chip holds a share
(``configs/lfm2-*.json``); a head tied to the embedding, over a slice of
the vocabulary.  From shapes and from the rows the routers really sent
here; as in ``counts.py``, what the program recomputed, padded or
chunked does not count.
"""

from benchmarks.lib.counts_typed import (    # noqa: F401  (the same here)
    causal_pairs, expert_params, expert_train_flops, held_rows,
    load_imbalance,
)

_EL = 2     # bytes of a bf16 element
_F32 = 4    # the convolution and its gates are float32


def layers(cfg):
    """``[(mixer, mlp)]`` of the layers run: ``layer_types`` from
    ``layers_first`` on, dense where the model's own layer index is
    under ``num_dense_layers``."""
    first = cfg["layers_first"]
    mixers = cfg["layer_types"][first:first + cfg["num_hidden_layers"]]
    return [(m, "dense" if first + i < cfg["num_dense_layers"] else "sparse")
            for i, m in enumerate(mixers)]


def count(cfg, what):
    """Layers whose mixer or feed-forward is ``what``."""
    return sum(what in layer for layer in layers(cfg))


def conv_matmul_params(cfg):
    """One short-convolution layer's matrices: the projection to
    [B C x] and the out-projection."""
    return 4 * cfg["hidden_size"] ** 2


def conv_params(cfg):
    """With the convolution's weights, a channel a tap."""
    return conv_matmul_params(cfg) + cfg["hidden_size"] * cfg["conv_L_cache"]


def full_matmul_params(cfg):
    d, h, kv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    return d * h * dh + 2 * d * kv * dh + h * dh * d


def full_params(cfg):
    """With the q and k norms' scales."""
    return full_matmul_params(cfg) + 2 * cfg["head_dim"]


def mlp_dense_params(cfg, mlp):
    """What every token meets of a layer's feed-forward: the dense
    SwiGLU whole; of a sparse layer the router."""
    d = cfg["hidden_size"]
    if mlp == "dense":
        return 3 * d * cfg["intermediate_size"]
    return d * cfg["router_experts"]


_MIXER_MATMULS = {"conv": conv_matmul_params,
                  "full_attention": full_matmul_params}
_MIXER_PARAMS = {"conv": conv_params, "full_attention": full_params}


def dense_matmul_params(cfg):
    """Matmul operands every token meets: each layer's mixer, its dense
    feed-forward or its router, and the tied head over the rows of the
    vocabulary held here, once.  The embedding's own use is a gather
    and the convolution is not a matrix."""
    return sum(_MIXER_MATMULS[m](cfg) + mlp_dense_params(cfg, mlp)
               for m, mlp in layers(cfg)) \
        + cfg["vocabulary"] * cfg["hidden_size"]


def params(cfg):
    """Every parameter ``init_transformer`` builds for this share: the
    tied embedding once, the last norm, each layer's two norms, its
    mixer, its feed-forward (a sparse layer's selection bias, one an
    expert the router scores, among them)."""
    d = cfg["hidden_size"]
    sparse = count(cfg, "sparse")
    return (cfg["vocabulary"] * d + d
            + sum(_MIXER_PARAMS[m](cfg) + mlp_dense_params(cfg, mlp) + 2 * d
                  for m, mlp in layers(cfg))
            + sparse * cfg["router_experts"]
            + sparse * cfg["num_experts"] * expert_params(cfg))


def attention_train_flops_per_seq(cfg, seq, backward=2.0):
    """The attention layer's core, one sequence: forward QK^T and PV, 2
    FLOPs a channel a scored pair and query head; ``backward`` times
    that going back."""
    return (1 + backward) * causal_pairs(seq) * 2 * 2 \
        * cfg["num_attention_heads"] * cfg["head_dim"]


def train_flops_per_step(cfg, batch, seq, rows):
    """``rows``: held rows of one step, all sparse layers.  6 x matmul
    parameters a token and the attention pairs; nothing recomputed.
    The convolution and its gates (2 + 2 x taps operations a channel a
    token forward) are not matmul work and are left out, as the other
    files leave theirs."""
    return (6 * dense_matmul_params(cfg) * batch * seq
            + count(cfg, "full_attention") * batch
            * attention_train_flops_per_seq(cfg, seq)
            + expert_train_flops(cfg, rows))


def expert_step_flops_and_bytes(cfg, rows):
    """As ``counts_typed.py``'s: the grouped products' operations; in
    each of the three passes the held weights and the rows in and out
    moved once."""
    weights = count(cfg, "sparse") * cfg["num_experts"] \
        * expert_params(cfg) * _EL
    moved = 2 * rows * cfg["hidden_size"] * _EL
    return expert_train_flops(cfg, rows), 3 * (weights + moved)


def shortconv_step_bytes(cfg, batch, seq):
    """The bytes the doubly gated convolutions of one step have to
    move, all ``conv`` layers, whatever implements them, float32 as the
    mixer states: ONE forward pass reads ``B``, ``C`` and ``x`` and
    writes ``y`` (4 tensors of batch x seq x hidden); one backward pass
    reads the cotangent, ``B``, ``C`` and ``x`` and writes three
    cotangents (7).  The taps and their sums are a few KB.  A forward
    pass the block's checkpoint runs again is not required work."""
    a_tensor = batch * seq * cfg["hidden_size"] * _F32
    return count(cfg, "conv") * (4 + 7) * a_tensor


def flash_step_flops_and_bytes(cfg, batch, seq):
    """``{"full": (flops, bytes)}`` of the attention layers' flash
    kernels in one step, in ``counts_typed.py``'s form: the pairs'
    operations; q, o, do and dq at the query heads' width and k, v, dk
    and dv at the key-value heads', the fp32 log-sum-exp once each way."""
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    n = count(cfg, "full_attention") * batch
    a_sequence = 6 * seq * (h + kv) * dh * _EL + 2 * seq * h * 4
    return {"full": (n * attention_train_flops_per_seq(cfg, seq),
                     n * a_sequence)}
