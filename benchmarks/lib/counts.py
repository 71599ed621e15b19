"""Operations and bytes the algorithms REQUIRE, from shapes alone.

What the compiler emitted or the program recomputed (remat, the flash
backward's second pass over QK^T) does not count: a utilisation is
required work over peak, so a program cannot raise it by doing more.
"""

_RESNET_STAGES = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


def resnet_forward_macs(depth=50, image=224, classes=1000, width=64):
    """Multiply-accumulates of one forward pass of one image: every
    convolution and the classifier (He et al. Table 1; the stride of a
    down-sampling bottleneck sits on its 3x3 convolution, as in this
    repo's model and torchvision's "v1.5", which is what makes it
    4.09e9 and not the paper's 3.8e9)."""
    def conv(hw_out, k, cin, cout):
        return hw_out * hw_out * k * k * cin * cout

    hw = image // 2
    macs = conv(hw, 7, 3, width)
    hw //= 2                                   # 3x3 max-pool, stride 2
    cin = width
    for i, n_blocks in enumerate(_RESNET_STAGES[depth]):
        cmid, cout = width * 2 ** i, width * 2 ** i * 4
        for j in range(n_blocks):
            stride = 2 if (j == 0 and i > 0) else 1
            macs += conv(hw, 1, cin, cmid)
            hw //= stride
            macs += conv(hw, 3, cmid, cmid) + conv(hw, 1, cmid, cout)
            if j == 0:
                macs += conv(hw, 1, cin, cout)
            cin = cout
    return macs + cin * classes


def resnet_train_flops_per_image(cfg):
    """Forward + backward (twice the forward), 2 FLOPs a MAC."""
    return 3 * 2 * resnet_forward_macs(
        cfg["depth"], cfg["image_size"], cfg["num_classes"], cfg["width"])


def decoder_matmul_params(cfg):
    """Parameters that are matmul operands: the blocks' four matrices
    and the tied head once.  The embedding look-up and the position
    table are gathers/adds, not matmuls."""
    d, h, dh, f = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["head_dim"], cfg["ffn_dim"])
    per_layer = 3 * d * h * dh + h * dh * d + 2 * d * f
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * d


def decoder_params(cfg):
    """Every parameter ``init_transformer`` builds for this
    configuration (norm scales and the position table included)."""
    d = cfg["hidden_size"]
    return (decoder_matmul_params(cfg) + cfg["max_position_embeddings"] * d
            + (2 * cfg["num_hidden_layers"] + 1) * d)


def attention_train_flops_per_seq(cfg, seq):
    """Causal attention of one sequence in every layer, forward and
    backward: QK^T and PV forward, four products backward, each
    2 * (T^2 / 2) * head_dim per head."""
    h, dh = cfg["num_attention_heads"], cfg["head_dim"]
    return cfg["num_hidden_layers"] * 6 * (seq * seq // 2) * 2 * h * dh


def decoder_train_flops_per_token(cfg, seq):
    return (6 * decoder_matmul_params(cfg)
            + attention_train_flops_per_seq(cfg, seq) / seq)


def flash_step_flops_and_bytes(cfg, batch, seq, bytes_per_el=2):
    """What the flash forward and backward kernels have to do in one
    training step on one device holding ``batch`` sequences: the
    operations above, and each operand and result moved once (forward
    q, k, v in and o out; backward q, k, v, o, do in and dq, dk, dv
    out; the fp32 log-sum-exp once each way)."""
    h, dh, n = cfg["num_attention_heads"], cfg["head_dim"], \
        cfg["num_hidden_layers"]
    flops = batch * attention_train_flops_per_seq(cfg, seq)
    tensor = batch * seq * h * dh * bytes_per_el
    lse = batch * seq * h * 4
    return flops, n * (12 * tensor + 2 * lse)
