"""Cells that drive the jitted train step of a decoder whose token mixer
is a doubly gated short convolution in three layers of four and softmax
attention with q/k norms over grouped key-value heads in the fourth
(``configs/lfm2-*.json``, ``model_type: lfm2_moe``: ``C * conv(B * x)``
with no recurrence behind it; dense SwiGLU layers that lead, then SwiGLU
experts under a sigmoid router with a selection bias and no shared
expert, of which this chip holds a share; a head tied to the embedding),
through the same path as ``train_step.py``: ``init -> shard_params ->
shard_opt_state -> make_train_step``, tokens resident on the device,
one step in flight.

What is this file's own: how the file maps to ``TransformerConfig``
(``_kinds``, ``_program_config``), the required counts
(``lib/counts_lfm2.py``) and the facts handed to the readers
(``_load_facts``).  The window, the check and the trace are
``train_step_ssm.drive``'s, called and not pasted.
"""

from benchmarks.lib import cells, counts_lfm2, counts_typed, scopes, \
    scopes_mixed

_SSM = cells.module("drivers", "train_step_ssm")
reference_job = _SSM.reference_job

# what the program's layers are, of the file's keys that say so
_AS_PROGRAMMED = {
    "conv_bias": False, "norm_topk_prob": True, "use_expert_bias": True,
    "tie_word_embeddings": True,
}


def _kinds(cfg):
    """``{layer type: AttentionKind}``; the layers' scopes are
    ``attn/conv`` and ``attn/full``.  A program from before the short
    convolution refuses the mixer's name (a ``ValueError`` of its
    table's), handed on as the ``TypeError`` ``drive`` reports at once."""
    from chainermn_tpu.models import AttentionKind

    try:
        conv = AttentionKind(
            name="conv", mixer="shortconv", conv_taps=cfg["conv_L_cache"])
    except ValueError as e:
        raise TypeError(str(e))
    return {
        "conv": conv,
        "full_attention": AttentionKind(
            name="full", rope_theta=cfg["rope_parameters"]["rope_theta"],
            qk_norm=True)}


def _program_config(cfg, job):
    """The configuration and the job in the program's own terms.  Every
    field not named here stays at the program's default."""
    from chainermn_tpu.models import TransformerConfig

    differ = {k: cfg[k] for k, v in _AS_PROGRAMMED.items() if cfg[k] != v}
    if differ or cfg["rope_parameters"]["rope_type"] != "default":
        raise SystemExit(f"the program's layers are {_AS_PROGRAMMED} with "
                         f"plain rotary; the file has {differ}")
    kinds = _kinds(cfg)
    every = counts_lfm2.layers(cfg)
    leading = [layer for layer in every if layer[1] == "dense"]
    rest = every[len(leading):]
    if every[:len(leading)] != leading or not rest:
        raise SystemExit("dense layers lead and sparse ones follow")
    pattern = counts_typed.period([mixer for mixer, _ in rest])
    return TransformerConfig(
        vocab_size=cfg["vocabulary"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"],
        n_layers=cfg["num_hidden_layers"], max_seq=job["seq"],
        dtype=cfg["compute_dtype"], attention=cfg["attention"],
        pos_embedding="rope", norm_eps=cfg["norm_eps"],
        leading_layers=tuple(kinds[mixer] for mixer, _ in leading),
        layer_pattern=tuple(kinds[mixer] for mixer in pattern),
        dense_act="swiglu", dense_d_ff=cfg["intermediate_size"],
        moe=True, n_experts=cfg["router_experts"],
        router_top_k=cfg["num_experts_per_tok"],
        moe_dispatch="dropless", expert_act="swiglu",
        router_score="sigmoid", router_bias="selection",
        router_scale=float(cfg["routed_scaling_factor"]),
        experts_held=(cfg["experts_first"], cfg["num_experts"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        loss_chunk=job.get("loss_chunk", 0))


def _load_facts(cfg, batch, seq, load, devices, text):
    """The readers' facts: those that rest on the rows really routed
    (``load`` is ``(sparse layers, E)`` for a whole step, the mean over
    the pool's batches; the kernels' required work is one device's
    share, the readers time device 0), the convolutions' and the flash
    kernels' required work and the scopes of the compiled text."""
    rows = counts_lfm2.held_rows(cfg, load)
    flops = counts_lfm2.train_flops_per_step(cfg, batch, seq, rows)

    def a_device(flops_bytes):
        return tuple(v / devices for v in flops_bytes)

    return {"flops_per_unit": flops / (batch * seq),
            "expert_load": load,
            "expert_rows": rows / devices,
            "routed_rows": batch * seq * cfg["num_experts_per_tok"]
            * counts_lfm2.count(cfg, "sparse"),
            "expert_flops_bytes": a_device(
                counts_lfm2.expert_step_flops_and_bytes(cfg, rows)),
            "shortconv_bytes": counts_lfm2.shortconv_step_bytes(
                cfg, batch, seq) / devices,
            "flash_typed_flops_bytes": {
                kind: a_device(v) for kind, v in
                counts_lfm2.flash_step_flops_and_bytes(
                    cfg, batch, seq).items()},
            "load_imbalance": counts_lfm2.load_imbalance(load),
            "scopes": scopes.instruction_scopes(text),
            "scopes_mixed": scopes_mixed.instruction_scopes(text)}


def run(run):
    return _SSM.drive(run, _program_config, _load_facts)
