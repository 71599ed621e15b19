"""Operations and bytes a decoder whose layers differ by kind REQUIRES
(window and full attention with grouped key-value heads, a sparse expert
layer of which this chip holds a share, a head over a slice of the
vocabulary), from shapes and from the rows the routers really sent
here.  As in ``counts.py``, what the program recomputed or padded does
not count.
"""

_EL = 2     # bytes of a bf16 element


def layer_kinds(cfg):
    """The kind of each layer that is run."""
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def period(kinds):
    """The shortest prefix that ``kinds`` repeats."""
    for n in range(1, len(kinds) + 1):
        if len(kinds) % n == 0 and all(
                k == kinds[i % n] for i, k in enumerate(kinds)):
            return list(kinds[:n])


def causal_pairs(seq, window=None):
    """(query, key) pairs a causal layer scores in one sequence:
    position t sees ``(t - window, t]``, itself included."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def window_of(cfg, kind):
    return cfg["sliding_window"] if kind == "sliding_attention" else None


def dense_matmul_params(cfg):
    """Matmul operands every token meets: each layer's q, k, v and
    output projections and its router, and the output matrix over the
    rows of the vocabulary held here.  The embedding is a gather."""
    d, h, hkv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    per_layer = d * h * dh + 2 * d * hkv * dh + h * dh * d \
        + d * cfg["router_experts"]
    return cfg["num_hidden_layers"] * per_layer + cfg["vocabulary"] * d


def expert_params(cfg):
    """One gated expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def params(cfg):
    """Every parameter ``init_transformer`` builds for this share."""
    d = cfg["hidden_size"]
    return (dense_matmul_params(cfg) + cfg["vocabulary"] * d
            + cfg["num_hidden_layers"] * cfg["num_experts"]
            * expert_params(cfg)
            + (2 * cfg["num_hidden_layers"] + 1) * d)


def attention_train_flops_per_seq(cfg, seq, kind):
    """One layer of ``kind``, one sequence, forward and backward: QK^T
    and PV forward, four products backward, each 2 x head_dim a scored
    pair and query head."""
    return 6 * causal_pairs(seq, window_of(cfg, kind)) * 2 \
        * cfg["num_attention_heads"] * cfg["head_dim"]


def held_rows(cfg, load):
    """Rows the routers sent to the experts held here, summed over the
    layers: ``load`` is the program's ``expert_load``, ``(layers, E)``."""
    first = cfg["experts_first"]
    return sum(sum(layer[first:first + cfg["num_experts"]])
               for layer in load)


def expert_train_flops(cfg, rows):
    """The grouped products of ``rows`` (token, expert) rows, forward
    and backward (6 FLOPs a parameter met)."""
    return 6 * rows * expert_params(cfg)


def train_flops_per_step(cfg, batch, seq, rows):
    """``rows``: held rows of one step, all layers (``held_rows``)."""
    attention = sum(attention_train_flops_per_seq(cfg, seq, kind)
                    for kind in layer_kinds(cfg))
    return (6 * dense_matmul_params(cfg) * batch * seq
            + batch * attention + expert_train_flops(cfg, rows))


def expert_step_flops_and_bytes(cfg, rows):
    """What the grouped products have to do in a step: the operations
    above; and, in each of the three passes (forward, the backward for
    the rows, the backward for the weights), the held weights and the
    rows in and out moved once: forward reads W and x and writes y, the
    rows' backward reads W and dy and writes dx, the weights' backward
    reads x and dy and writes dW."""
    weights = cfg["num_hidden_layers"] * cfg["num_experts"] \
        * expert_params(cfg) * _EL
    moved = 2 * rows * cfg["hidden_size"] * _EL
    return expert_train_flops(cfg, rows), 3 * (weights + moved)


def flash_step_flops_and_bytes(cfg, batch, seq):
    """``{kind: (flops, bytes)}`` of the attention cores of one step:
    the operations above; q, o, do and dq at the query heads' width and
    k, v, dk and dv at the key-value heads' (forward q, k, v in and o
    out; backward q, k, v, o, do in and dq, dk, dv out), the fp32
    log-sum-exp once each way."""
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q_sized = batch * seq * h * dh * _EL
    kv_sized = batch * seq * hkv * dh * _EL
    lse = batch * seq * h * 4
    out = {}
    for kind in layer_kinds(cfg):
        flops, nbytes = out.get(kind, (0, 0))
        out[kind] = (
            flops + batch * attention_train_flops_per_seq(cfg, seq, kind),
            nbytes + 6 * q_sized + 6 * kv_sized + 2 * lse)
    return out


def load_imbalance(load):
    """Largest expert's rows over the mean expert's, in the worst layer."""
    return max(max(layer) * len(layer) / sum(layer) for layer in load)
