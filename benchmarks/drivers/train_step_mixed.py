"""Cells that drive the jitted train step of a decoder whose layers
differ by kind AND by shape (``configs/laguna-*.json``: query heads and
rotary share by kind, a gate a head, leading dense layers, then sparse
layers with a shared expert and a sigmoid router, of whose experts this
chip holds a share), through the same path as ``train_step.py``:
``init -> shard_params -> shard_opt_state -> make_train_step``, tokens
resident on the device, one step in flight.

The window, the check and the trace are ``train_step_typed.py``'s, step
for step; its ``reference_job`` and its count of flipped choices are
used as they are.  What differs: how the file maps to
``TransformerConfig``, the required counts (``lib/counts_mixed.py``)
and the facts handed to the readers (``scopes_mixed`` beside
``scopes``).
"""

import dataclasses

import numpy as np

from benchmarks.lib import (
    cells, check, counts_mixed, counts_typed, scopes, scopes_mixed,
)
from benchmarks.lib.harness import (
    Outcome, Window, build_optimizer, first_gradient_norms, log,
    program_bytes, same_layout,
)
from benchmarks.lib.trace import kernel_instructions
from benchmarks.reference.common import delta_norms

_TYPED = cells.module("drivers", "train_step_typed")
reference_job = _TYPED.reference_job


def _attention_kind(cfg, kind, heads):
    """``train_step_typed.py``'s kind (window, rotary constants, YaRN),
    with the layer's own query heads and rotary share."""
    return dataclasses.replace(
        _TYPED._attention_kind(cfg, kind), n_heads=heads,
        rotary_share=cfg["rope_parameters"][kind]["partial_rotary_factor"])


def _program_config(cfg, job):
    """The configuration and the job in the program's own terms.  Every
    field not named here stays at the program's default."""
    from chainermn_tpu.models import TransformerConfig

    if not cfg["gating"] or cfg.get("moe_apply_router_weight_on_input"):
        raise SystemExit("the program's layer has the gate a head and "
                         "gates the experts' output")
    every = counts_mixed.layers(cfg)
    leading = [layer for layer in every if layer[2] == "dense"]
    rest = every[len(leading):]
    if every[:len(leading)] != leading or not rest \
            or any(mlp != "sparse" for _, _, mlp in rest):
        raise SystemExit("dense layers lead and sparse ones follow")
    pattern = counts_typed.period(rest)
    kinds = [_attention_kind(cfg, kind, heads) for kind, heads, _ in every]
    return TransformerConfig(
        vocab_size=cfg["vocabulary"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"],
        n_layers=cfg["num_hidden_layers"], max_seq=job["seq"],
        dtype=cfg["compute_dtype"], attention=cfg["attention"],
        pos_embedding="rope",
        leading_layers=tuple(kinds[:len(leading)]),
        layer_pattern=tuple(kinds[len(leading):][:len(pattern)]),
        attn_gate="per_head", dense_act="swiglu",
        dense_d_ff=cfg["intermediate_size"],
        moe=True, n_experts=cfg["router_experts"],
        router_top_k=cfg["num_experts_per_tok"], moe_dispatch="dropless",
        expert_act="swiglu", router_score="sigmoid",
        router_scale=cfg["moe_routed_scaling_factor"],
        shared_expert_d_ff=cfg["shared_expert_intermediate_size"],
        experts_held=(cfg["experts_first"], cfg["num_experts"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        loss_chunk=job.get("loss_chunk", 0))


def _load_facts(cfg, batch, seq, load, devices, text):
    """The readers' facts: those that rest on the rows really routed
    (``load`` is ``(sparse layers, E)`` for a whole step, the mean over
    the pool's batches; the kernels' required work is one device's
    share, the readers time device 0) and the scopes of the compiled
    text."""
    rows = counts_mixed.held_rows(cfg, load)
    flops = counts_mixed.train_flops_per_step(cfg, batch, seq, rows)

    def a_device(flops_bytes):
        return tuple(v / devices for v in flops_bytes)

    return {"flops_per_unit": flops / (batch * seq),
            "expert_load": load,
            "expert_rows": rows / devices,
            "expert_flops_bytes": a_device(
                counts_mixed.expert_step_flops_and_bytes(cfg, rows)),
            "flash_typed_flops_bytes": {
                kind: a_device(v) for kind, v in
                counts_mixed.flash_step_flops_and_bytes(
                    cfg, batch, seq).items()},
            "load_imbalance": counts_mixed.load_imbalance(load),
            "scopes": scopes.instruction_scopes(text),
            "scopes_mixed": scopes_mixed.instruction_scopes(text)}


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
    routed = batch * seq * cfg["num_experts_per_tok"] \
        * counts_mixed.sparse_layers(cfg)
    log("experts", rows_here_a_step=f"{facts['expert_rows']:.0f}",
        of=routed, share_here=f"{facts['expert_rows'] / routed:.4f}",
        load_imbalance=f"{facts['load_imbalance']:.3f}")
    return Outcome(
        correct=correct, window=window, memory_peak_bytes=memory,
        compared=compared, readings=(seen, ref),
        facts=dict(facts, kernels=kernels, choices_differ_share=differ))
