"""Cells that drive the jitted train step of a decoder whose layers
differ by kind and whose MLP is a sparse expert layer of which this
chip holds a share (``configs/mellum2-*.json``), through the same path
as ``train_step.py``: ``init -> shard_params -> shard_opt_state ->
make_train_step``, tokens resident on the device, one step in flight.

The window, the check and the trace are ``train_step.py``'s, step for
step; its ``reference_job`` is used as it is.  What differs: how the
file maps to ``TransformerConfig``, the required counts
(``lib/counts_typed.py``) and the facts handed to the readers.  After
the window the program's own ``expert_load`` counts, on the pool's
batches and with the final parameters, the rows each expert was sent:
the grouped products' required work is counted at those rows.
"""

import dataclasses

import numpy as np

from benchmarks.lib import cells, check, counts_typed, scopes
from benchmarks.lib.harness import (
    Outcome, Window, build_optimizer, first_gradient_norms, log,
    program_bytes, same_layout,
)
from benchmarks.lib.trace import kernel_instructions
from benchmarks.reference.common import delta_norms

_KIND_NAMES = {"sliding_attention": "sliding", "full_attention": "full"}


def _attention_kind(cfg, kind):
    from chainermn_tpu.models import AttentionKind

    rope = cfg["rope_parameters"][kind]
    yarn = {}
    if rope["rope_type"] == "yarn":
        yarn = dict(yarn_factor=rope["factor"],
                    yarn_original_max=rope[
                        "original_max_position_embeddings"],
                    yarn_beta_fast=rope["beta_fast"],
                    yarn_beta_slow=rope["beta_slow"],
                    attention_factor=rope["attention_factor"])
    elif rope["rope_type"] != "default":
        raise SystemExit(f"rope_type {rope['rope_type']!r}")
    return AttentionKind(
        name=_KIND_NAMES[kind], rope_theta=rope["rope_theta"],
        window=counts_typed.window_of(cfg, kind) or 0, **yarn)


def _program_config(cfg, job):
    """The configuration and the job in the program's own terms.  Every
    field not named here stays at the program's default."""
    from chainermn_tpu.models import TransformerConfig

    if cfg["hidden_act"] != "silu" or not cfg["norm_topk_prob"]:
        raise SystemExit("the program's gated expert is SwiGLU with the "
                         "chosen gates renormalised")
    pattern = counts_typed.period(counts_typed.layer_kinds(cfg))
    return TransformerConfig(
        vocab_size=cfg["vocabulary"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_head=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"],
        n_layers=cfg["num_hidden_layers"], max_seq=job["seq"],
        dtype=cfg["compute_dtype"], attention=cfg["attention"],
        pos_embedding="rope",
        layer_pattern=tuple(_attention_kind(cfg, k) for k in pattern),
        moe=True, n_experts=cfg["router_experts"],
        router_top_k=cfg["num_experts_per_tok"], moe_dispatch="dropless",
        expert_act="swiglu",
        experts_held=(cfg["experts_first"], cfg["num_experts"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        loss_chunk=job.get("loss_chunk", 0))


def reference_job(run):
    """``train_step.py``'s, with the token ids drawn from the slice of
    the vocabulary held here."""
    sliced = dataclasses.replace(run, config=dict(
        run.config, vocab_size=run.config["vocabulary"]))
    return cells.module("drivers", "train_step").reference_job(sliced)


def _choices_differ_share(program, reference):
    """The share of (token, layer, choice) triples on which two routers
    differ: ``program`` ``(layers, B, T, k)``, ``reference``
    ``(B, T, layers, k)``, each token's k choices as a set."""
    program = np.moveaxis(np.asarray(program), 0, 2)
    reference = np.asarray(reference)
    same = (program[..., :, None] == reference[..., None, :]).any(-1)
    return float(1 - same.mean())


def _load_facts(cfg, batch, seq, load, devices):
    """The readers' facts that rest on the rows really routed: ``load``
    is ``(layers, E)`` for a whole step, the mean over the pool's
    batches.  The kernels' required work is one device's share (the
    readers time device 0)."""
    rows = counts_typed.held_rows(cfg, load)
    flops = counts_typed.train_flops_per_step(cfg, batch, seq, rows)

    def a_device(flops_bytes):
        return tuple(v / devices for v in flops_bytes)

    return {"flops_per_unit": flops / (batch * seq),
            "expert_load": load,
            "expert_rows": rows / devices,
            "expert_flops_bytes": a_device(
                counts_typed.expert_step_flops_and_bytes(cfg, rows)),
            "flash_typed_flops_bytes": {
                kind: a_device(v) for kind, v in
                counts_typed.flash_step_flops_and_bytes(
                    cfg, batch, seq).items()},
            "load_imbalance": counts_typed.load_imbalance(load)}


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

    pcfg = _program_config(cfg, job)
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
        """The reference's layout -> the program's, on its mesh: the
        block stack gains the leading pipeline axis."""
        params = dict(params, blocks=jax.tree.map(
            lambda a: a[None], params["blocks"]))
        return shard_params(mc, pcfg, params)

    shapes = jax.eval_shape(lambda k: init_transformer(k, pcfg), run.key(0))

    opt = build_optimizer(cfg["optimizer"])
    params = placed(make_params())
    same_layout(params, shapes, "init_transformer")
    # not judged: written down beside the gaps, so that a loss gap is
    # known to be routing (choices that flipped) or arithmetic
    differ = _choices_differ_share(
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
    facts = _load_facts(cfg, batch, seq, load.tolist(), len(run.devices))
    log("experts", rows_here_a_step=f"{facts['expert_rows']:.0f}",
        of=batch * seq * cfg["num_experts_per_tok"]
        * cfg["num_hidden_layers"],
        load_imbalance=f"{facts['load_imbalance']:.3f}")
    return Outcome(
        correct=correct, window=window, memory_peak_bytes=memory,
        compared=compared, readings=(seen, ref),
        facts=dict(facts, kernels=kernels,
                   scopes=scopes.instruction_scopes(text),
                   choices_differ_share=differ))
