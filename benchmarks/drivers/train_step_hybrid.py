"""Cells that drive the jitted train step of a decoder whose token
mixers differ by layer (``configs/kimi-linear-*.json``: Kimi Delta
Attention in three layers of four and latent attention without rotary
in the fourth, a leading layer with a dense SwiGLU, then sparse layers
with a shared expert and a sigmoid router with a selection bias, of
whose experts this chip holds a share), through the same path as
``train_step.py``: ``init -> shard_params -> shard_opt_state ->
make_train_step``, tokens resident on the device, one step in flight.

What is this file's own: how the file maps to ``TransformerConfig``
(``_kinds``, ``_program_config``), the required counts
(``lib/counts_hybrid.py``) and the facts handed to the readers
(``_load_facts``: ``scopes_hybrid`` beside ``scopes`` and
``scopes_mixed``).  ``reference_job`` and the count of flipped choices
are ``train_step_typed.py``'s, imported.  ``run`` is a COPY of
``train_step_typed.run`` (the third, after ``train_step_mixed.run``):
the window, the check and the trace step for step, differing in the
early ``SystemExit`` for a program from before the mixers, the compiled
text handed to ``_load_facts`` and two keys of the file in one log
line.  It is pasted and not called because that ``run`` reads its
module's own ``_program_config``/``_load_facts`` and keeps the compiled
text to itself, and an accepted driver is not this PR's to edit: until
ROADMAP D16 gives the drivers one ``run(run, program_config,
load_facts)``, a repair to the window or the check lands in all three.
"""

import numpy as np

from benchmarks.lib import (
    cells, check, counts_hybrid, counts_typed, scopes, scopes_hybrid,
    scopes_mixed,
)
from benchmarks.lib.harness import (
    Outcome, Window, build_optimizer, first_gradient_norms, log,
    program_bytes, same_layout,
)
from benchmarks.lib.trace import kernel_instructions
from benchmarks.reference.common import delta_norms

_TYPED = cells.module("drivers", "train_step_typed")
reference_job = _TYPED.reference_job


def _kinds(cfg):
    """``{mixer: AttentionKind}``, named as the file names them (the
    layers' scopes are ``attn/kda`` and ``attn/mla``).  A program from
    before the mixers raises ``TypeError`` here."""
    from chainermn_tpu.models import AttentionKind

    lin = cfg["linear_attn_config"]
    if cfg["q_lora_rank"] is not None or not cfg["mla_use_nope"]:
        raise SystemExit("the program's latent attention projects q "
                         "straight from the input and rotates nothing")
    kinds = {
        "kda": AttentionKind(
            name="kda", mixer="kda", n_heads=lin["num_heads"],
            conv_taps=lin["short_conv_kernel_size"]),
        "mla": AttentionKind(
            name="mla", mixer="mla", n_heads=cfg["num_attention_heads"],
            kv_latent=cfg["kv_lora_rank"],
            d_shared_key=cfg["qk_rope_head_dim"],
            d_value=cfg["v_head_dim"])}
    # the one number of the KDA layer that the program holds as a
    # constant and the reference reads from the file
    from chainermn_tpu.models.transformer import KDA_L2_NORM_EPS
    if cfg["l2_norm_eps"] != KDA_L2_NORM_EPS:
        raise SystemExit(f"the file's l2_norm_eps {cfg['l2_norm_eps']} is "
                         f"not the program's {KDA_L2_NORM_EPS}")
    return kinds


def _program_config(cfg, job):
    """The configuration and the job in the program's own terms.  Every
    field not named here stays at the program's default."""
    from chainermn_tpu.models import TransformerConfig

    lin = cfg["linear_attn_config"]
    if cfg["qk_nope_head_dim"] != lin["head_dim"]:
        raise SystemExit("one d_head serves both mixers: KDA's head and "
                         "MLA's unshared key part")
    if cfg["moe_router_activation_func"] != "sigmoid" \
            or not cfg["moe_renormalize"] or cfg["num_expert_group"] != 1 \
            or cfg["moe_layer_freq"] != 1 or cfg["hidden_act"] != "silu":
        raise SystemExit("the program's router is a sigmoid over one "
                         "group with the chosen gates renormalised, "
                         "every later layer sparse, experts SwiGLU")
    kinds = _kinds(cfg)
    every = counts_hybrid.layers(cfg)
    leading = [layer for layer in every if layer[1] == "dense"]
    rest = every[len(leading):]
    if every[:len(leading)] != leading or not rest:
        raise SystemExit("dense layers lead and sparse ones follow")
    pattern = counts_typed.period([mixer for mixer, _ in rest])
    return TransformerConfig(
        vocab_size=cfg["vocabulary"], d_model=cfg["hidden_size"],
        n_heads=lin["num_heads"], d_head=lin["head_dim"],
        d_ff=cfg["moe_intermediate_size"],
        n_layers=cfg["num_hidden_layers"], max_seq=job["seq"],
        dtype=cfg["compute_dtype"], attention=cfg["attention"],
        pos_embedding="rope", norm_eps=cfg["rms_norm_eps"],
        leading_layers=tuple(kinds[mixer] for mixer, _ in leading),
        layer_pattern=tuple(kinds[mixer] for mixer in pattern),
        dense_act="swiglu", dense_d_ff=cfg["intermediate_size"],
        moe=True, n_experts=cfg["router_experts"],
        router_top_k=cfg["num_experts_per_token"],
        moe_dispatch="dropless", expert_act="swiglu",
        router_score="sigmoid", router_bias="selection",
        router_scale=cfg["routed_scaling_factor"],
        shared_expert_d_ff=cfg["moe_intermediate_size"]
        * cfg["num_shared_experts"],
        experts_held=(cfg["experts_first"], cfg["num_experts"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        loss_chunk=job.get("loss_chunk", 0))


def _load_facts(cfg, batch, seq, load, devices, text):
    """The readers' facts: those that rest on the rows really routed
    (``load`` is ``(sparse layers, E)`` for a whole step, the mean over
    the pool's batches; the kernels' required work is one device's
    share, the readers time device 0), the mixers' required work and
    the scopes of the compiled text."""
    rows = counts_hybrid.held_rows(cfg, load)
    flops = counts_hybrid.train_flops_per_step(cfg, batch, seq, rows)

    def a_device(flops_bytes):
        return tuple(v / devices for v in flops_bytes)

    return {"flops_per_unit": flops / (batch * seq),
            "expert_load": load,
            "expert_rows": rows / devices,
            "expert_flops_bytes": a_device(
                counts_hybrid.expert_step_flops_and_bytes(cfg, rows)),
            "kda_scan_flops_bytes": a_device(
                counts_hybrid.kda_scan_step_flops_and_bytes(
                    cfg, batch, seq)),
            "flash_mla_flops_bytes": a_device(
                counts_hybrid.flash_mla_step_flops_and_bytes(
                    cfg, batch, seq)),
            "load_imbalance": counts_hybrid.load_imbalance(load),
            "scopes": scopes.instruction_scopes(text),
            "scopes_mixed": scopes_mixed.instruction_scopes(text),
            "scopes_hybrid": scopes_hybrid.instruction_scopes(text)}


def run(run):
    import jax

    from chainermn_tpu.models import (
        expert_choices, expert_load, init_transformer, make_train_step,
        shard_params,
    )
    from chainermn_tpu.parallel import MeshConfig
    from chainermn_tpu.training import shard_opt_state

    cfg, job = run.config, run.traffic
    batch, seq, pool = job["batch"], job["seq"], job["distinct_batches"]
    n_check, n_warm = job["check_steps"], job["warmup_steps"]
    on_tpu = run.devices[0].platform == "tpu"

    try:
        pcfg = _program_config(cfg, job)
    except TypeError as e:
        # a program from before these fields: say so at once, before the
        # plain reference has spent its minutes
        raise SystemExit(f"this program cannot describe {cfg['model_type']}"
                         f": {e}")
    mc = MeshConfig(devices=run.devices, **job["mesh"])
    tok_sharding = mc.sharding(("data", "expert"), "seq")
    reference, make_params, ref_batches, batches = reference_job(run)
    run.mark("tokens")
    # the plain reference first, before the program's state exists; the
    # experts its router chooses on the first batch with it
    ref, ref_chosen = run.timed_reference(lambda: (
        reference.follow(cfg, make_params, ref_batches),
        np.asarray(reference.expert_choices(
            cfg, make_params(), ref_batches[0][0]))))
    log("reference", seconds=f"{run.reference_s:.2f}", losses=ref["losses"])
    run.mark("reference")
    batches = [tuple(jax.device_put(t, tok_sharding) for t in b)
               for b in batches]

    def placed(params):
        """The reference's layout -> the program's, on its mesh: each
        stack of blocks gains the leading pipeline axis (the blocks
        that lead have none)."""
        params = dict(params, blocks=jax.tree.map(
            lambda a: a[None], params["blocks"]))
        return shard_params(mc, pcfg, params)

    shapes = jax.eval_shape(lambda k: init_transformer(k, pcfg), run.key(0))

    opt = build_optimizer(cfg["optimizer"])
    params = placed(make_params())
    same_layout(params, shapes, "init_transformer")
    # not judged: written down beside the gaps, so that a loss gap is
    # known to be routing (choices that flipped) or arithmetic
    differ = _TYPED._choices_differ_share(
        expert_choices(mc, pcfg, params, batches[0][0]), ref_chosen)
    log("choices", differ_share=f"{differ:.3g}",
        of=f"{ref_chosen.size} (token, layer, choice) of step 1")
    opt_state = shard_opt_state(opt, params)
    run.mark("state placed")
    compiled = make_train_step(mc, pcfg, opt).lower(
        params, opt_state, *batches[0]).compile()
    text = compiled.as_text()
    kernels = kernel_instructions(text)
    # the interpreter or XLA's attention standing in for the kernel is a
    # failure on the chip (the CPU rehearsal interprets by design)
    if any("pallas_call" in k for k in kernels.values()) != on_tpu:
        raise SystemExit(f"flash kernel in the compiled step: "
                         f"{sorted(set(kernels.values()))} on "
                         f"{run.devices[0].platform}")
    memory = program_bytes(compiled)
    run.mark("step compiled")
    log("program", kernels=len(kernels), mesh=dict(mc.mesh.shape),
        bytes_per_device=memory)

    state = [params, opt_state]
    del params, opt_state

    def dispatch(i):
        state[0], state[1], loss = compiled(
            state[0], state[1], *batches[i % pool])
        return loss

    seen = {"losses": []}
    for i in range(n_check):
        seen["losses"].append(float(dispatch(i)))
        if i == 0:
            seen["grad_norms"] = first_gradient_norms(
                state[1], cfg["optimizer"]["first_gradient"])
    seen["delta_norms"] = delta_norms(state[0], placed(make_params()))
    compared = check.gaps(seen, ref)
    correct = check.judge(compared, cfg["check"]["limits"], log)
    run.mark("checked")

    i = n_check
    for _ in range(n_warm - 1):
        jax.block_until_ready(dispatch(i))
        i += 1
    run.start_trace()
    jax.block_until_ready(dispatch(i))
    i += 1

    window = Window(run, batch * seq)
    run.mark("warm")
    window.open()
    pending = dispatch(i)
    while True:
        # time is looked at when an iteration has ended, and step i+1 is
        # dispatched before the host waits for step i
        last = window.last(in_flight=1)
        if not last:
            i += 1
            following = dispatch(i)
        pending.block_until_ready()
        window.end_iteration(pending)
        if last:
            break
        pending = following
    window.close(state[0])
    run.stop_trace()

    # after the window and outside the trace: the rows each expert is
    # sent, on the pool's batches with the parameters as they are now
    load = np.mean([np.asarray(expert_load(mc, pcfg, state[0], b[0]))
                    for b in batches], axis=0)
    facts = _load_facts(cfg, batch, seq, load.tolist(), len(run.devices),
                        text)
    routed = batch * seq * cfg["num_experts_per_token"] \
        * counts_hybrid.count(cfg, "sparse")
    log("experts", rows_here_a_step=f"{facts['expert_rows']:.0f}",
        of=routed, share_here=f"{facts['expert_rows'] / routed:.4f}",
        load_imbalance=f"{facts['load_imbalance']:.3f}")
    return Outcome(
        correct=correct, window=window, memory_peak_bytes=memory,
        compared=compared, readings=(seen, ref),
        facts=dict(facts, kernels=kernels, choices_differ_share=differ))
