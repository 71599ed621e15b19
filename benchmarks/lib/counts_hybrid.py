"""Operations and bytes a decoder REQUIRES whose token mixers differ by
layer (``configs/kimi-linear-*.json``): Kimi Delta Attention, a
recurrence over a matrix state a head, in three layers of four; latent
attention without rotary, keys wider than values, in the fourth; a
leading layer with a dense SwiGLU, then sparse layers with a shared
expert beside the routed ones of which this chip holds a share; a head
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
    """``[(mixer, mlp)]`` of the layers run; the config counts layers
    from 1."""
    lin = cfg["linear_attn_config"]
    return [("kda" if layer in lin["kda_layers"] else "mla",
             "dense" if layer <= cfg["first_k_dense_replace"] else "sparse")
            for layer in range(1, cfg["num_hidden_layers"] + 1)]


def count(cfg, what):
    """Layers whose mixer or MLP is ``what``."""
    return sum(what in layer for layer in layers(cfg))


def kda_matmul_params(cfg):
    """One KDA layer's matrices: q, k, v and output projections, the
    decay's and the output gate's two-matrix projections, the step's."""
    d, lin = cfg["hidden_size"], cfg["linear_attn_config"]
    h, dh = lin["num_heads"], lin["head_dim"]
    return 4 * d * h * dh + 2 * (d * dh + dh * h * dh) + d * h


def kda_params(cfg):
    """All of one KDA layer's mixer: the matrices, the convolution's
    weights (q, k and v channels x taps), ``a_log`` a head, ``dt_bias``
    a channel, the output norm's one scale."""
    lin = cfg["linear_attn_config"]
    h, dh = lin["num_heads"], lin["head_dim"]
    return kda_matmul_params(cfg) + 3 * h * dh \
        * lin["short_conv_kernel_size"] + h + h * dh + dh


def mla_matmul_params(cfg):
    d, h, rank = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["kv_lora_rank"])
    dn, ds, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return d * h * (dn + ds) + d * (rank + ds) + rank * h * (dn + dv) \
        + h * dv * d


def mla_params(cfg):
    return mla_matmul_params(cfg) + cfg["kv_lora_rank"]


def mlp_dense_params(cfg, mlp):
    """What every token meets of a layer's MLP: the dense SwiGLU whole;
    of a sparse layer the router and the shared expert."""
    d = cfg["hidden_size"]
    if mlp == "dense":
        return 3 * d * cfg["intermediate_size"]
    return d * cfg["router_experts"] + 3 * d \
        * cfg["moe_intermediate_size"] * cfg["num_shared_experts"]


def dense_matmul_params(cfg):
    """Matmul operands every token meets: each layer's mixer, its dense
    MLP or its router and shared expert, and the output matrix over the
    rows of the vocabulary held here.  The embedding is a gather and
    the convolution is not a matrix."""
    mixer = {"kda": kda_matmul_params(cfg), "mla": mla_matmul_params(cfg)}
    return sum(mixer[m] + mlp_dense_params(cfg, mlp)
               for m, mlp in layers(cfg)) \
        + cfg["vocabulary"] * cfg["hidden_size"]


def params(cfg):
    """Every parameter ``init_transformer`` builds for this share (the
    selection bias, one an expert the router scores, among them)."""
    d = cfg["hidden_size"]
    sparse = count(cfg, "sparse")
    return (count(cfg, "kda") * kda_params(cfg)
            + count(cfg, "mla") * mla_params(cfg)
            + sum(mlp_dense_params(cfg, mlp) for _, mlp in layers(cfg))
            + sparse * cfg["router_experts"]
            + sparse * cfg["num_experts"] * expert_params(cfg)
            + 2 * cfg["vocabulary"] * d
            + (2 * cfg["num_hidden_layers"] + 1) * d)


def mla_train_flops_per_seq(cfg, seq, backward=2.0):
    """One MLA layer's attention core, one sequence: forward QK^T over
    the whole key width and PV over the value width, 2 FLOPs a channel
    a scored pair and head; ``backward`` times that going back (2: the
    four products dV, dP, dQ, dK; 2.5 with the scores a flash kernel's
    backward has to form again)."""
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] \
        + cfg["v_head_dim"]
    return (1 + backward) * causal_pairs(seq) * 2 * width \
        * cfg["num_attention_heads"]


def kda_scan_train_flops_per_token(cfg):
    """The RECURRENCE's work a token, all heads of one layer, whatever
    the chunking: forward three products of 2 x d_k x d_v (k^T S, the
    rank-one update, q^T S), backward twice that."""
    lin = cfg["linear_attn_config"]
    return 3 * 3 * 2 * lin["head_dim"] ** 2 * lin["num_heads"]


def train_flops_per_step(cfg, batch, seq, rows):
    """``rows``: held rows of one step, all sparse layers.  6 x matmul
    parameters a token, the MLA pairs and the recurrence's count;
    nothing recomputed."""
    return (6 * dense_matmul_params(cfg) * batch * seq
            + count(cfg, "mla") * batch * mla_train_flops_per_seq(cfg, seq)
            + count(cfg, "kda") * batch * seq
            * kda_scan_train_flops_per_token(cfg)
            + expert_train_flops(cfg, rows))


def expert_step_flops_and_bytes(cfg, rows):
    """As ``counts_mixed.py``'s: the grouped products' operations; in
    each of the three passes the held weights and the rows in and out
    moved once."""
    weights = count(cfg, "sparse") * cfg["num_experts"] \
        * expert_params(cfg) * _EL
    moved = 2 * rows * cfg["hidden_size"] * _EL
    return expert_train_flops(cfg, rows), 3 * (weights + moved)


def kda_scan_step_flops_and_bytes(cfg, batch, seq):
    """``(flops, bytes)`` of the recurrences of one step, all KDA
    layers: the count above; q, k, v, g (a channel) and beta (a head)
    in and o out once a pass, float32 as the op takes them, three
    passes (forward; backward reads them and o's cotangent again and
    writes a cotangent for each)."""
    lin = cfg["linear_attn_config"]
    h, dh = lin["num_heads"], lin["head_dim"]
    tokens = batch * seq * count(cfg, "kda")
    a_pass = tokens * h * (5 * dh + 1) * _F32
    return tokens * kda_scan_train_flops_per_token(cfg), 3 * a_pass


def flash_mla_step_flops_and_bytes(cfg, batch, seq):
    """``(flops, bytes)`` of the MLA layers' flash kernels in one step:
    the pairs' operations with the backward's second scoring (2.5);
    q and k at the whole key width and v, o and their cotangents at the
    value width, every head its own (forward q, k, v in and o out;
    backward q, k, v, o, do in and dq, dk, dv out), the fp32
    log-sum-exp once each way."""
    h = cfg["num_attention_heads"]
    dk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    n = count(cfg, "mla") * batch
    a_token = h * ((2 * dk + 2 * dv) + (2 * dk + 3 * dv)
                   + (2 * dk + dv)) * _EL + 2 * h * 4
    return (n * mla_train_flops_per_seq(cfg, seq, backward=2.5),
            n * seq * a_token)
