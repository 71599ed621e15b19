"""Cells that drive the flagship transformer's jitted train step
directly, as ``chip_smoke.py``'s ``run_transformer`` proved it on the
chip: ``init -> shard_params -> shard_opt_state -> make_train_step``,
tokens resident on the device, the host doing nothing but dispatch.

The window keeps one step in flight: it dispatches step *i+1* and then
blocks on the loss of step *i*.  The first steps, which the plain
reference has followed beforehand, go through the same compiled object
and the same call.
"""

import importlib

from benchmarks.lib import check, counts
from benchmarks.lib.harness import (
    Outcome, Window, build_optimizer, first_gradient_norms, log,
    program_bytes, same_layout,
)
from benchmarks.lib.trace import kernel_instructions
from benchmarks.reference.common import delta_norms


def _program_config(cfg, job):
    """The configuration and the job in the program's own terms.  Every
    field not named here stays at the program's default, so a PR that
    changes or derives a default moves the cell."""
    from chainermn_tpu.models import TransformerConfig

    return TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], d_head=cfg["head_dim"],
        d_ff=cfg["ffn_dim"], n_layers=cfg["num_hidden_layers"],
        max_seq=cfg["max_position_embeddings"], dtype=cfg["compute_dtype"],
        attention=cfg["attention"], fsdp=job.get("fsdp", False))


def reference_job(run):
    """What the plain reference follows: its module, the seeded weights
    and the first batches of the pool every batch of the run comes from
    (made on the device from the seed in one call).  ``limits.py`` gives
    the same to the control."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    cfg, job = run.config, run.traffic
    reference = importlib.import_module(
        f"benchmarks.reference.{cfg['reference']}")
    # a cell on several chips is there because its state does not fit
    # one: the plain reference is spread over the same chips by the
    # compiler alone (each leaf split along its longest axis that
    # divides, the tokens on every chip), its code unchanged
    mesh = Mesh(np.asarray(run.devices), ("reference",))
    everywhere = NamedSharding(mesh, P())

    def split(shape):
        axes = [a for a in range(len(shape) > 2, len(shape))
                if shape[a] % len(run.devices) == 0]
        spec = [None] * len(shape)
        if axes and len(run.devices) > 1:
            spec[max(axes, key=shape.__getitem__)] = "reference"
        return NamedSharding(mesh, P(*spec))

    tokens = jax.jit(lambda k: jax.random.randint(
        k, (job["distinct_batches"], job["batch"], job["seq"] + 1), 0,
        cfg["vocab_size"], jnp.int32), out_shardings=everywhere)(run.key(1))
    pool = [(tokens[i, :, :-1], tokens[i, :, 1:])
            for i in range(job["distinct_batches"])]
    init = jax.jit(
        lambda k: reference.init(k, cfg),
        out_shardings=jax.tree.map(lambda s: split(s.shape), jax.eval_shape(
            lambda k: reference.init(k, cfg), run.key(0))))

    def make_params():
        return init(run.key(0))

    return reference, make_params, pool[:job["check_steps"]], pool


def run(run):
    import jax

    from chainermn_tpu.models import (
        init_transformer, make_train_step, shard_params,
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
    # the plain reference first, before the program's state exists
    ref = run.timed_reference(
        lambda: reference.follow(cfg, make_params, ref_batches))
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
    opt_state = shard_opt_state(opt, params)
    run.mark("state placed")
    compiled = make_train_step(mc, pcfg, opt).lower(
        params, opt_state, *batches[0]).compile()
    kernels = kernel_instructions(compiled.as_text())
    # the interpreter or XLA's attention standing in for the kernel is a
    # failure on the chip (the CPU rehearsal interprets by design)
    if bool(kernels) != on_tpu:
        raise SystemExit(f"flash kernel in the compiled step: "
                         f"{bool(kernels)} on {run.devices[0].platform}")
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
    return Outcome(
        correct=correct, window=window, memory_peak_bytes=memory,
        compared=compared, readings=(seen, ref),
        facts={"flops_per_unit":
               counts.decoder_train_flops_per_token(cfg, seq),
               "kernels": kernels,
               "flash_flops_bytes": counts.flash_step_flops_and_bytes(
                   cfg, batch // mc.mesh.shape["data"], seq)})
