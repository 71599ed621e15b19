"""Single-chip DP-lever overheads: allreduce_grad_dtype + double_buffering.

SCALING.md's volume model claims two levers: bf16 gradient wire (halves
DP allreduce bytes) and double buffering (overlaps the allreduce with
the next step's compute).  Their wire/overlap BENEFITS need >1 chip;
their single-chip OVERHEADS are measurable today and bound the levers'
cost side: the bf16 cast pair per gradient leaf, and double buffering's
extra gradient-stash reads/writes.  This records ResNet-50 step times
for baseline / grad_dtype=bfloat16 / double_buffering on one chip,
through the SAME ``create_multi_node_optimizer`` users call.

value = double_buffering step overhead vs baseline (ratio; 1.0 = free);
extras carry each config's ms and the grad-dtype ratio.  Hermetic child
+ cached-fallback pattern (the TPU init hang), like every bench here.
"""

import argparse
import json
import os
import sys
import time

from _bench_common import pin_platform, run_child_with_retries

METRIC = "dp_lever_overhead_single_chip"
UNIT = "x"


def _time_steps(step, carry, x, y, warmup, iters):
    import jax

    for _ in range(warmup):
        carry, loss = step(carry, x, y)
    if warmup:
        jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        carry, loss = step(carry, x, y)
    jax.block_until_ready(loss)
    return (time.perf_counter() - t0) / iters * 1e3


def run(batch=256, image=224, warmup=2, iters=6, dtype="bfloat16"):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import chainermn_tpu as cmn
    from chainermn_tpu.models import (
        ResNetConfig, init_resnet, resnet_apply, softmax_cross_entropy,
    )

    comm = cmn.create_communicator("tpu_xla")
    cfg = ResNetConfig(depth=50, num_classes=1000, dtype=dtype)

    kx, ky = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (batch, image, image, 3),
                          jnp.dtype(dtype))
    y = jax.random.randint(ky, (batch,), 0, cfg.num_classes)
    sh = jax.sharding.NamedSharding(comm.mesh, P(comm.axis_name))
    x, y = jax.device_put(x, sh), jax.device_put(y, sh)

    def build_step(**opt_kw):
        params, state = init_resnet(jax.random.PRNGKey(0), cfg)
        opt = cmn.create_multi_node_optimizer(
            optax.sgd(0.1, momentum=0.9), comm, **opt_kw)
        opt_state = jax.jit(opt.init)(params)

        def loss_fn(p, s, xx, yy):
            logits, ns = resnet_apply(
                cfg, p, s, xx, train=True, axis_name=comm.axis_name)
            return jax.lax.pmean(
                softmax_cross_entropy(logits, yy), comm.axis_name), ns

        def body(carry, xx, yy):
            p, s, os_ = carry
            (loss, ns), g = jax.value_and_grad(
                loss_fn, has_aux=True)(p, s, xx, yy)
            u, os_ = opt.update(g, os_, p)
            return (optax.apply_updates(p, u), ns, os_), loss

        step = jax.jit(jax.shard_map(
            body, mesh=comm.mesh,
            in_specs=((P(), P(), P()), P(comm.axis_name),
                      P(comm.axis_name)),
            out_specs=((P(), P(), P()), P())), donate_argnums=(0,))
        return step, (params, state, opt_state)

    results = {}
    for name, kw in (
        ("baseline", {}),
        ("grad_bf16", {"allreduce_grad_dtype": "bfloat16"}),
        ("double_buffering", {"double_buffering": True}),
    ):
        step, carry = build_step(**kw)
        results[name] = _time_steps(step, carry, x, y, warmup, iters)

    base = results["baseline"]
    ratio = round(results["double_buffering"] / base, 4)
    return {
        "metric": METRIC,
        "value": ratio,
        "unit": UNIT,
        "vs_baseline": ratio,
        "double_buffering_ms": round(results["double_buffering"], 2),
        "grad_bf16_ms": round(results["grad_bf16"], 2),
        "grad_bf16_ratio": round(results["grad_bf16"] / base, 4),
        "baseline_ms": round(base, 2),
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": comm.size,
        "batch": batch, "image": image, "dtype": dtype,
    }


def main(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", action="store_true")
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--image", type=int, default=224)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--iters", type=int, default=6)
    p.add_argument("--platform", default=None)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--dp-devices", type=int, default=0,
                   help="force an N-virtual-device mesh (CPU only): "
                        "the communicator then spans N devices and the "
                        "double-buffering row measures real DP overlap "
                        "scheduling, not just single-chip overhead")
    p.add_argument("--timeouts", type=int, nargs="+", default=[600])
    args = p.parse_args(argv)

    if args.child:
        if args.dp_devices > 1:
            # must land before any backend init in this interpreter
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count="
                f"{args.dp_devices}")
        pin_platform(args.platform)
        print("BENCH_RESULT " + json.dumps(run(
            batch=args.batch, image=args.image, warmup=args.warmup,
            iters=args.iters, dtype=args.dtype)))
        return 0

    here = os.path.abspath(__file__)
    cmd = [sys.executable, here, "--child",
           "--batch", str(args.batch), "--image", str(args.image),
           "--warmup", str(args.warmup), "--iters", str(args.iters),
           "--dtype", args.dtype]
    if args.dp_devices:
        cmd += ["--dp-devices", str(args.dp_devices)]
    if args.platform:
        cmd += ["--platform", args.platform]
    return run_child_with_retries(
        cmd, os.path.dirname(here), args.timeouts, METRIC, UNIT,
        record=args.platform is None and not args.dp_devices,
        match={"batch": args.batch, "image": args.image,
               "dtype": args.dtype})


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
