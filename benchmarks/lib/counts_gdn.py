"""Operations and bytes a decoder REQUIRES whose token mixers are Gated
DeltaNet in all layers but every ``full_attention_interval``-th, which
is gated softmax attention over grouped key-value heads, and whose every
layer has sparse SwiGLU experts, of which this chip holds a share,
beside a gated shared expert (``configs/qwen3-next-*.json``); a head
over a slice of the vocabulary.  From shapes and from the rows the
routers really sent here; as in ``counts.py``, what the program
recomputed, padded or chunked does not count.
"""

from benchmarks.lib.counts_typed import (    # noqa: F401  (the same here)
    causal_pairs, expert_params, expert_train_flops, held_rows,
    load_imbalance,
)

_EL = 2     # bytes of a bf16 element
_F32 = 4    # the recurrence's arrays are float32


def layers(cfg):
    """The mixer of each layer run."""
    n = cfg["full_attention_interval"]
    return ["full" if (i + 1) % n == 0 else "linear"
            for i in range(cfg["num_hidden_layers"])]


def count(cfg, mixer):
    return layers(cfg).count(mixer)


def _linear_widths(cfg):
    """``(keys, values, value heads)``: the channels of q (and of k), of
    v (and of z), and the value heads."""
    return (cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"],
            cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"],
            cfg["linear_num_value_heads"])


def linear_matmul_params(cfg):
    """One Gated DeltaNet layer's matrices: the projections to
    [q k v z] and to [b a], and the out-projection."""
    d = cfg["hidden_size"]
    keys, values, heads = _linear_widths(cfg)
    return d * (2 * keys + 2 * values) + d * 2 * heads + values * d


def linear_params(cfg):
    """All of one Gated DeltaNet layer's mixer: the matrices, the
    convolution's weights, ``A_log`` and ``dt_bias`` a value head, the
    output norm's one scale of a head's width, and the layer's norm."""
    keys, values, heads = _linear_widths(cfg)
    return linear_matmul_params(cfg) \
        + (2 * keys + values) * cfg["linear_conv_kernel_dim"] \
        + 2 * heads + cfg["linear_value_head_dim"] + cfg["hidden_size"]


def full_matmul_params(cfg):
    """The attention layer's matrices: q with its gate an element, k, v
    and the out-projection."""
    d, h, kv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    return d * h * 2 * dh + 2 * d * kv * dh + h * dh * d


def full_params(cfg):
    """With the q and k norms' scales and the layer's norm."""
    return full_matmul_params(cfg) + 2 * cfg["head_dim"] \
        + cfg["hidden_size"]


def experts_dense_params(cfg):
    """What every token meets of a layer's second part: the router, the
    shared expert and its gate."""
    d = cfg["hidden_size"]
    return d * cfg["router_experts"] \
        + 3 * d * cfg["shared_expert_intermediate_size"] + d


def experts_params(cfg):
    """All of one layer's second part here, with its norm."""
    return experts_dense_params(cfg) \
        + cfg["num_experts"] * expert_params(cfg) + cfg["hidden_size"]


_MIXER_MATMULS = {"linear": linear_matmul_params, "full": full_matmul_params}
_MIXER_PARAMS = {"linear": linear_params, "full": full_params}


def dense_matmul_params(cfg):
    """Matmul operands every token meets: each layer's mixer, router and
    shared expert, and the output matrix over the rows of the vocabulary
    held here.  The embedding is a gather and the convolution is not a
    matrix."""
    return sum(_MIXER_MATMULS[m](cfg) + experts_dense_params(cfg)
               for m in layers(cfg)) \
        + cfg["vocabulary"] * cfg["hidden_size"]


def params(cfg):
    """Every parameter ``init_transformer`` builds for this share."""
    d = cfg["hidden_size"]
    return sum(_MIXER_PARAMS[m](cfg) + experts_params(cfg)
               for m in layers(cfg)) + 2 * cfg["vocabulary"] * d + d


def attention_train_flops_per_seq(cfg, seq, backward=2.0):
    """The attention layer's core, one sequence: forward QK^T and PV, 2
    FLOPs a channel a scored pair and query head; ``backward`` times
    that going back."""
    return (1 + backward) * causal_pairs(seq) * 2 * 2 \
        * cfg["num_attention_heads"] * cfg["head_dim"]


def gdn_scan_train_flops_per_token(cfg):
    """The RECURRENCE's work a token, all value heads of one layer,
    whatever the chunking: forward three products of 2 x d_k x d_v
    (k^T S, the rank-one update, q^T S), backward twice that."""
    return 3 * 3 * 2 * cfg["linear_key_head_dim"] \
        * cfg["linear_value_head_dim"] * cfg["linear_num_value_heads"]


def train_flops_per_step(cfg, batch, seq, rows):
    """``rows``: held rows of one step, all layers.  6 x matmul
    parameters a token, the attention pairs and the recurrence's count;
    nothing recomputed."""
    return (6 * dense_matmul_params(cfg) * batch * seq
            + count(cfg, "full") * batch
            * attention_train_flops_per_seq(cfg, seq)
            + count(cfg, "linear") * batch * seq
            * gdn_scan_train_flops_per_token(cfg)
            + expert_train_flops(cfg, rows))


def expert_step_flops_and_bytes(cfg, rows):
    """As ``counts_typed.py``'s: the grouped products' operations; in
    each of the three passes the held weights and the rows in and out
    moved once."""
    weights = cfg["num_hidden_layers"] * cfg["num_experts"] \
        * expert_params(cfg) * _EL
    moved = 2 * rows * cfg["hidden_size"] * _EL
    return expert_train_flops(cfg, rows), 3 * (weights + moved)


def gdn_scan_step_flops_and_bytes(cfg, batch, seq):
    """``(flops, bytes)`` of the recurrences of one step, all Gated
    DeltaNet layers, whatever implements them: the count above; q and k
    (a key head), v (a value head), g and beta (a scalar a value head)
    in and o out once a pass, float32 as the op takes them, three passes
    (forward; backward reads them and o's cotangent again and writes a
    cotangent for each)."""
    keys, values, heads = _linear_widths(cfg)
    tokens = batch * seq * count(cfg, "linear")
    a_pass = tokens * (2 * keys + 2 * values + 2 * heads) * _F32
    return tokens * gdn_scan_train_flops_per_token(cfg), 3 * a_pass


def flash_step_flops_and_bytes(cfg, batch, seq):
    """``{"full": (flops, bytes)}`` of the attention layers' flash
    kernels in one step, in ``counts_typed.py``'s form: the pairs'
    operations; q, o, do and dq at the query heads' width and k, v, dk
    and dv at the key-value heads', the fp32 log-sum-exp once each way."""
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    n = count(cfg, "full") * batch
    a_sequence = 6 * seq * (h + kv) * dh * _EL + 2 * seq * h * 4
    return {"full": (n * attention_train_flops_per_seq(cfg, seq),
                     n * a_sequence)}
