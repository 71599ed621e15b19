"""Headline benchmark: ResNet-50 training throughput, images/sec/chip.

Reference baseline: ChainerMN's 15-min-ImageNet recipe (Akiba et al.,
arXiv:1711.04325) sustained 1.28M*90/900s over 1024 P100s ≈ **125
images/sec/chip** (see BASELINE.md).  ``vs_baseline`` is ours / 125.

Always prints exactly ONE JSON line:
  {"metric", "value", "unit", "vs_baseline", ...extras}
Extras on success: "mfu" (model FLOPs utilisation vs the chip's peak
bf16 FLOPs), "device_kind", "step_time_ms", "batch", "flops_per_step".
On failure "value"/"vs_baseline" are null and an "error" field carries
the diagnosis — the TPU backend on this host can hang inside
``jax.devices()``, so the measurement runs in a child process under a
hard timeout with bounded retries; a hang becomes a recorded error
instead of an external rc=124 with no JSON at all.
"""

import argparse
import json
import os
import sys
import time

from _bench_common import peak_flops, pin_platform, run_child_with_retries

BASELINE_IMG_S_PER_CHIP = 125.0
METRIC = "resnet50_train_images_per_sec_per_chip"
UNIT = "images/sec/chip"

# ResNet-50 @ 224x224: ~4.09e9 MACs forward per image => 8.18e9 FLOPs;
# a train step (fwd + bwd ~= 2x fwd) is ~3x forward.  Decides which
# reading of the compiled step's own cost analysis is per step.  Conv
# FLOPs scale with spatial area, so other --image sizes scale by (image/224)².
_ANALYTIC_TRAIN_FLOPS_PER_IMAGE_224 = 3 * 2 * 4.089e9


def _analytic_train_flops_per_image(image: int) -> float:
    return _ANALYTIC_TRAIN_FLOPS_PER_IMAGE_224 * (image / 224.0) ** 2


def make_step(mc, cfg, opt, steps_per_call=1):
    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu.models import resnet_apply, softmax_cross_entropy
    from chainermn_tpu.training import fuse_steps

    def loss_fn(params, state, x, y):
        logits, new_state = resnet_apply(
            cfg, params, state, x, train=True, axis_name="data")
        nll = softmax_cross_entropy(logits, y)
        return jax.lax.pmean(nll, "data"), new_state

    def sharded_grad(params, state, x, y):
        # pmean'd loss + replicated params => shard_map AD already psums
        # parameter cotangents across the axis; grads arrive as the
        # global mean.  An explicit grad pmean here would be a SECOND
        # full-size all-reduce per step (verified by HLO collective
        # counts — it exactly doubled the DP wire volume).
        (loss, new_state), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, state, x, y)
        return loss, new_state, grads

    grad_fn = jax.shard_map(
        sharded_grad, mesh=mc.mesh,
        in_specs=(P(), P(), P("data"), P("data")),
        out_specs=(P(), P(), P()),
    )

    def step(carry, x, y):
        params, state, opt_state = carry
        loss, new_state, grads = grad_fn(params, state, x, y)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), new_state,
                opt_state), loss

    # Keep ``steps_per_call`` steps resident on device as one XLA
    # program, amortising the per-dispatch host cost (what that cost is
    # on this chip has not been measured — ROADMAP S2).
    fused = fuse_steps(step, steps_per_call) if steps_per_call > 1 else step
    return jax.jit(fused, donate_argnums=(0,))


def run(batch=256, image=224, warmup=2, iters=6, steps_per_call=8):
    import jax
    import jax.numpy as jnp
    import optax

    from chainermn_tpu.models import ResNetConfig, init_resnet
    from chainermn_tpu.parallel import MeshConfig

    cfg = ResNetConfig(depth=50, num_classes=1000, dtype="bfloat16")
    mc = MeshConfig(data=1, devices=jax.devices()[:1])
    params, state = init_resnet(jax.random.PRNGKey(0), cfg)
    opt = optax.sgd(0.1, momentum=0.9)
    opt_state = jax.jit(opt.init)(params)

    kx, ky = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (batch, image, image, 3), jnp.bfloat16)
    y = jax.random.randint(ky, (batch,), 0, cfg.num_classes)
    x = jax.device_put(x, mc.sharding("data"))
    y = jax.device_put(y, mc.sharding("data"))

    step = make_step(mc, cfg, opt, steps_per_call)
    carry = (params, state, opt_state)

    flops = float(
        step.lower(carry, x, y).compile().cost_analysis()["flops"])
    # XLA's HLO cost analysis counts a while/scan body ONCE (ignoring
    # trip count) — but don't bake that in: take whichever reading
    # (body-once vs body-times-trip-count) agrees with the analytic
    # ResNet-50 FLOP estimate.
    analytic = _analytic_train_flops_per_image(image) * batch
    flops_per_step = min([flops, flops / steps_per_call],
                         key=lambda c: abs(c - analytic))

    for _ in range(warmup):
        carry, loss = step(carry, x, y)
    if warmup:
        jax.block_until_ready(loss)

    t0 = time.perf_counter()
    for _ in range(iters):
        carry, loss = step(carry, x, y)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0

    n_steps = iters * steps_per_call
    img_s = batch * n_steps / dt
    step_ms = dt / n_steps * 1e3
    dev = jax.devices()[0]
    kind = dev.device_kind
    # a CPU smoke run has no utilization to report; an accelerator the
    # peak table does not know is an error (peak_flops raises)
    mfu = None if dev.platform == "cpu" else (
        flops_per_step * n_steps / dt / peak_flops(kind))
    return {
        "metric": METRIC,
        "value": round(img_s, 2),
        "unit": UNIT,
        "vs_baseline": round(img_s / BASELINE_IMG_S_PER_CHIP, 3),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "device_kind": kind,
        "step_time_ms": round(step_ms, 2),
        "batch": batch,
        "image": image,
        "steps_per_call": steps_per_call,
        "flops_per_step": flops_per_step,
    }


def _child_main(args):
    pin_platform(args.platform)
    result = run(batch=args.batch, image=args.image,
                 warmup=args.warmup, iters=args.iters,
                 steps_per_call=args.steps_per_call)
    print("BENCH_RESULT " + json.dumps(result))


def _parent_main(args):
    """Run the measurement in a child under a hard timeout; print one
    JSON line, and exit non-zero when the child produced no result."""
    here = os.path.abspath(__file__)
    cmd = [sys.executable, here, "--child",
           "--batch", str(args.batch), "--image", str(args.image),
           "--warmup", str(args.warmup), "--iters", str(args.iters),
           "--steps-per-call", str(args.steps_per_call)]
    if args.platform:
        cmd += ["--platform", args.platform]
    return run_child_with_retries(
        cmd, os.path.dirname(here), args.timeouts, METRIC, UNIT,
        record=args.platform is None,
        match={"batch": args.batch, "image": args.image},
        check=args.check)


def _parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", action="store_true",
                   help="internal: run the measurement in-process")
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--image", type=int, default=224)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--iters", type=int, default=6)
    p.add_argument("--steps-per-call", type=int, default=8,
                   help="training steps fused into one XLA call "
                        "(lax.scan) to amortise dispatch latency")
    p.add_argument("--platform", default=None,
                   help="pin JAX platform in the child (e.g. cpu for a "
                        "smoke test)")
    p.add_argument("--check", action="store_true",
                   help="perf-regression sentinel: score the fresh "
                        "record against the run history's prior "
                        "same-workload runs (noise-aware bounds, "
                        "utils/regression.py); the verdict rides the "
                        "JSON line under 'check' and the exit code is "
                        "1 on a regression verdict")
    p.add_argument("--timeouts", type=int, nargs="+", default=[420],
                   help="per-attempt child timeouts in seconds")
    return p.parse_args(argv)


if __name__ == "__main__":
    args = _parse_args(sys.argv[1:])
    if args.child:
        _child_main(args)
    else:
        sys.exit(_parent_main(args))
