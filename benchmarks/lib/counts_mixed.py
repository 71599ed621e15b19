"""Operations and bytes a decoder REQUIRES whose layers differ by kind
and by shape (``configs/laguna-*.json``): each layer its own query
heads over shared key-value heads with a gate a head, window or full
attention, leading layers with a dense SwiGLU, then sparse layers with
a shared expert beside the routed ones of which this chip holds a
share, a head over a slice of the vocabulary.  From shapes and from the
rows the routers really sent here; as in ``counts.py``, what the
program recomputed or padded does not count.
"""

from benchmarks.lib.counts_typed import (    # noqa: F401  (the same here)
    causal_pairs, expert_params, expert_train_flops, held_rows,
    load_imbalance, window_of,
)

_EL = 2     # bytes of a bf16 element


def layers(cfg):
    """``[(attention kind, query heads, mlp kind)]`` of the layers run."""
    n = cfg["num_hidden_layers"]
    return list(zip(cfg["layer_types"][:n],
                    cfg["num_attention_heads_per_layer"][:n],
                    cfg["mlp_layer_types"][:n]))


def sparse_layers(cfg):
    return sum(mlp == "sparse" for _, _, mlp in layers(cfg))


def attention_params(cfg, heads):
    """One layer's q, k, v and output projections and its gate."""
    d, hkv, dh = (cfg["hidden_size"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    return d * heads * dh + 2 * d * hkv * dh + heads * dh * d + d * heads


def mlp_dense_params(cfg, mlp):
    """What every token meets of a layer's MLP: the dense SwiGLU whole;
    of a sparse layer the router and the shared expert."""
    d = cfg["hidden_size"]
    if mlp == "dense":
        return 3 * d * cfg["intermediate_size"]
    return d * cfg["router_experts"] \
        + 3 * d * cfg["shared_expert_intermediate_size"]


def dense_matmul_params(cfg):
    """Matmul operands every token meets: each layer's attention
    projections and gate at its own head count, its dense MLP or its
    router and shared expert, and the output matrix over the rows of
    the vocabulary held here.  The embedding is a gather."""
    return sum(attention_params(cfg, heads) + mlp_dense_params(cfg, mlp)
               for _, heads, mlp in layers(cfg)) \
        + cfg["vocabulary"] * cfg["hidden_size"]


def params(cfg):
    """Every parameter ``init_transformer`` builds for this share."""
    d = cfg["hidden_size"]
    return (dense_matmul_params(cfg) + cfg["vocabulary"] * d
            + sparse_layers(cfg) * cfg["num_experts"] * expert_params(cfg)
            + (2 * cfg["num_hidden_layers"] + 1) * d)


def attention_train_flops_per_seq(cfg, seq, kind, heads):
    """One layer of ``kind`` with ``heads`` query heads, one sequence,
    forward and backward: QK^T and PV forward, four products backward,
    each 2 x head_dim a scored pair and query head."""
    return 6 * causal_pairs(seq, window_of(cfg, kind)) * 2 \
        * heads * cfg["head_dim"]


def train_flops_per_step(cfg, batch, seq, rows):
    """``rows``: held rows of one step, all sparse layers."""
    attention = sum(attention_train_flops_per_seq(cfg, seq, kind, heads)
                    for kind, heads, _ in layers(cfg))
    return (6 * dense_matmul_params(cfg) * batch * seq
            + batch * attention + expert_train_flops(cfg, rows))


def expert_step_flops_and_bytes(cfg, rows):
    """What the grouped products have to do in a step: the operations
    above; and, in each of the three passes (forward, the backward for
    the rows, the backward for the weights), the held weights and the
    rows in and out moved once."""
    weights = sparse_layers(cfg) * cfg["num_experts"] \
        * expert_params(cfg) * _EL
    moved = 2 * rows * cfg["hidden_size"] * _EL
    return expert_train_flops(cfg, rows), 3 * (weights + moved)


def flash_step_flops_and_bytes(cfg, batch, seq):
    """``{kind: (flops, bytes)}`` of the attention cores of one step:
    the operations above at each layer's own heads; q, o, do and dq at
    that layer's query heads and k, v, dk and dv at the key-value
    heads (forward q, k, v in and o out; backward q, k, v, o, do in and
    dq, dk, dv out), the fp32 log-sum-exp once each way."""
    hkv, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    kv_sized = batch * seq * hkv * dh * _EL
    out = {}
    for kind, heads, _ in layers(cfg):
        flops, nbytes = out.get(kind, (0, 0))
        q_sized = batch * seq * heads * dh * _EL
        lse = batch * seq * heads * 4
        out[kind] = (
            flops + batch * attention_train_flops_per_seq(
                cfg, seq, kind, heads),
            nbytes + 6 * q_sized + 6 * kv_sized + 2 * lse)
    return out
