"""Cells that drive the jitted train step of a decoder whose token
mixers are Gated DeltaNet in three layers of four and gated softmax
attention in the fourth (``configs/qwen3-next-*.json``, ``model_type:
qwen3_next``: a delta rule with one scalar decay a value head, fewer key
heads than value heads; q/k norms, a quarter of each head rotated, a
gate an element; zero-centred norms; a softmax router over all experts,
of which this chip holds a share, beside a gated shared expert), through
the same path as ``train_step.py``: ``init -> shard_params ->
shard_opt_state -> make_train_step``, tokens resident on the device,
one step in flight.

What is this file's own: how the file maps to ``TransformerConfig``
(``_kinds``, ``_program_config``), the required counts
(``lib/counts_gdn.py``) and the facts handed to the readers
(``_load_facts``).  The window, the check and the trace are
``train_step_ssm.drive``'s, called and not pasted.
"""

from benchmarks.lib import cells, counts_gdn, scopes, scopes_mixed

_SSM = cells.module("drivers", "train_step_ssm")
reference_job = _SSM.reference_job

# what the program's layers are, of the file's keys that say so
_AS_PROGRAMMED = {
    "hidden_act": "silu", "norm_topk_prob": True, "decoder_sparse_step": 1,
    "mlp_only_layers": [], "rope_scaling": None,
    "use_sliding_window": False,
}


def _kinds(cfg):
    """``{mixer: AttentionKind}``; the layers' scopes are ``attn/gdn``
    and ``attn/full``.  A program from before the scalar-decay delta
    rule raises ``TypeError`` here (``key_heads``: a field it lacks)."""
    from chainermn_tpu.models import AttentionKind

    return {
        "linear": AttentionKind(
            name="gdn", mixer="gdn",
            n_heads=cfg["linear_num_value_heads"],
            key_heads=cfg["linear_num_key_heads"],
            d_key=cfg["linear_key_head_dim"],
            d_value=cfg["linear_value_head_dim"],
            conv_taps=cfg["linear_conv_kernel_dim"]),
        "full": AttentionKind(
            name="full", n_heads=cfg["num_attention_heads"],
            rope_theta=cfg["rope_theta"],
            rotary_share=cfg["partial_rotary_factor"], qk_norm=True)}


def _program_config(cfg, job):
    """The configuration and the job in the program's own terms.  Every
    field not named here stays at the program's default."""
    from chainermn_tpu.models import TransformerConfig
    from chainermn_tpu.models.transformer import KDA_L2_NORM_EPS

    differ = {k: cfg[k] for k, v in _AS_PROGRAMMED.items() if cfg[k] != v}
    if differ:
        raise SystemExit(f"the program's layers are {_AS_PROGRAMMED}; "
                         f"the file has {differ}")
    if cfg["l2_norm_eps"] != KDA_L2_NORM_EPS:
        raise SystemExit(f"the file's l2_norm_eps {cfg['l2_norm_eps']} is "
                         f"not the program's {KDA_L2_NORM_EPS}")
    kinds = _kinds(cfg)
    pattern = counts_gdn.layers(cfg)[:cfg["full_attention_interval"]]
    return TransformerConfig(
        vocab_size=cfg["vocabulary"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"],
        n_layers=cfg["num_hidden_layers"], max_seq=job["seq"],
        dtype=cfg["compute_dtype"], attention=cfg["attention"],
        pos_embedding="rope", norm_eps=cfg["rms_norm_eps"],
        norm_scale="zero_centred", attn_gate="per_element",
        layer_pattern=tuple(kinds[m] for m in pattern),
        moe=True, n_experts=cfg["router_experts"],
        router_top_k=cfg["num_experts_per_tok"],
        moe_dispatch="dropless", expert_act="swiglu",
        shared_expert_d_ff=cfg["shared_expert_intermediate_size"],
        shared_expert_gate=True,
        experts_held=(cfg["experts_first"], cfg["num_experts"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        loss_chunk=job.get("loss_chunk", 0))


def _load_facts(cfg, batch, seq, load, devices, text):
    """The readers' facts: those that rest on the rows really routed
    (``load`` is ``(layers, E)`` for a whole step, the mean over the
    pool's batches; the kernels' required work is one device's share,
    the readers time device 0), the recurrence's and the flash kernels'
    required work and the scopes of the compiled text."""
    rows = counts_gdn.held_rows(cfg, load)
    flops = counts_gdn.train_flops_per_step(cfg, batch, seq, rows)

    def a_device(flops_bytes):
        return tuple(v / devices for v in flops_bytes)

    return {"flops_per_unit": flops / (batch * seq),
            "expert_load": load,
            "expert_rows": rows / devices,
            "routed_rows": batch * seq * cfg["num_experts_per_tok"]
            * cfg["num_hidden_layers"],
            "expert_flops_bytes": a_device(
                counts_gdn.expert_step_flops_and_bytes(cfg, rows)),
            "gdn_scan_flops_bytes": a_device(
                counts_gdn.gdn_scan_step_flops_and_bytes(cfg, batch, seq)),
            "flash_typed_flops_bytes": {
                kind: a_device(v) for kind, v in
                counts_gdn.flash_step_flops_and_bytes(
                    cfg, batch, seq).items()},
            "load_imbalance": counts_gdn.load_imbalance(load),
            "scopes": scopes.instruction_scopes(text),
            "scopes_mixed": scopes_mixed.instruction_scopes(text)}


def run(run):
    return _SSM.drive(run, _program_config, _load_facts)
