"""Long-context attention microbench: Pallas flash kernel vs XLA einsum.

Measures a causal 8k-context attention forward+backward on one chip and
reports the speedup of the kernel path over the einsum path (the
per-pair compute that the ring schedule multiplies across the ``seq``
mesh axis — if the kernel wins here, the composed ring wins too).

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline"} where
value = kernel-path images of speedup (xla_ms / flash_ms) and
vs_baseline uses 1.0 (parity with the einsum path) as the baseline.
Same child-process timeout/retry pattern as bench.py (the TPU backend
init on this host can hang).
"""

import argparse
import json
import os
import sys
import time

from _bench_common import pin_platform, run_child_with_retries

METRIC = "flash_attention_8k_speedup_vs_xla"
UNIT = "x"


def run(batch=4, seq=8192, heads=8, d_head=128, iters=20, warmup=3):
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.ops.pallas_attention import flash_attention
    from chainermn_tpu.parallel.ring_attention import local_attention

    interpret = jax.default_backend() != "tpu"
    kx = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (batch, seq, heads, d_head)
    q, k, v = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in kx)

    def time_path(fn):
        loss = lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) ** 2)
        step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        # one unconditional warmup step: ``g`` must exist for the sync
        # below even at warmup=0 (compile cost lands here either way)
        g = step(q, k, v)
        for _ in range(max(0, warmup - 1)):
            g = step(q, k, v)
        jax.block_until_ready(g)
        t0 = time.perf_counter()
        for _ in range(iters):
            g = step(q, k, v)
        jax.block_until_ready(g)
        return (time.perf_counter() - t0) / iters * 1e3

    flash_ms = time_path(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=interpret))
    xla_ms = time_path(
        lambda q, k, v: local_attention(q, k, v, causal=True))
    speedup = xla_ms / flash_ms
    return {
        "metric": METRIC,
        "value": round(speedup, 3),
        "unit": UNIT,
        "vs_baseline": round(speedup, 3),
        "flash_ms": round(flash_ms, 2),
        "xla_ms": round(xla_ms, 2),
        "batch": batch, "seq": seq,
        "config": f"B{batch} T{seq} H{heads} D{d_head} causal bf16 fwd+bwd",
    }


SWEEP_METRIC = "flash_attention_bwd_block_retune_speedup"


def run_sweep(batch=4, seq=8192, heads=8, d_head=128, iters=10,
              warmup=2):
    """The r5 bwd-block retune lever: time fwd+bwd at the 1024/1024
    default vs a grid of independent backward tilings (the dq kernel's
    q-outer pass and the dkv kernel's k-outer revisit peak at
    different shapes).  value = best retuned time over default (>1 =
    the retune wins; the winning pair is in the record and becomes the
    kernel default in a follow-up).  Gradients are tiling-exact
    (tests/function_tests/test_pallas_attention.py), so adoption is
    purely a perf decision."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.ops.pallas_attention import flash_attention

    interpret = jax.default_backend() != "tpu"
    kx = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (batch, seq, heads, d_head)
    q, k, v = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in kx)

    def time_cfg(bq, bk):
        def fn(q, k, v):
            return flash_attention(q, k, v, causal=True,
                                   bwd_block_q=bq, bwd_block_k=bk,
                                   interpret=interpret)
        loss = lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) ** 2)
        step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        g = step(q, k, v)  # unconditional: warmup=0 must not NameError
        for _ in range(max(0, warmup - 1)):
            g = step(q, k, v)
        jax.block_until_ready(g)
        t0 = time.perf_counter()
        for _ in range(iters):
            g = step(q, k, v)
        jax.block_until_ready(g)
        return (time.perf_counter() - t0) / iters * 1e3

    base_ms = time_cfg(None, None)          # fwd default 1024/1024
    grid = [(256, 1024), (512, 1024), (512, 512), (1024, 512),
            (1024, 256), (2048, 512), (512, 2048)]
    rows = {}
    for bq, bk in grid:
        bq, bk = min(bq, seq), min(bk, seq)  # clamp at smoke scales
        key = f"{bq}x{bk}"
        if key not in rows:
            rows[key] = round(time_cfg(bq, bk), 2)
    best_key = min(rows, key=rows.get)
    speedup = base_ms / rows[best_key]
    return {
        "metric": SWEEP_METRIC,
        "value": round(speedup, 3),
        "unit": "x",
        "vs_baseline": round(speedup, 3),
        "default_ms": round(base_ms, 2),
        "best_bwd_blocks": best_key,
        "best_ms": rows[best_key],
        "sweep_ms": rows,
        "batch": batch, "seq": seq,
        "config": f"B{batch} T{seq} H{heads} D{d_head} causal bf16 "
                  f"bwd-retune",
    }


def main(argv):
    p = argparse.ArgumentParser()
    p.add_argument("--child", action="store_true")
    p.add_argument("--seq", type=int, default=8192)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--sweep", action="store_true",
                   help="bwd-block retune sweep instead of the "
                        "flash-vs-XLA row")
    p.add_argument("--timeouts", type=int, nargs="+", default=[420])
    p.add_argument("--platform", default=None)
    args = p.parse_args(argv)

    if args.child:
        pin_platform(args.platform)
        fn = run_sweep if args.sweep else run
        print("BENCH_RESULT " + json.dumps(
            fn(batch=args.batch, seq=args.seq, iters=args.iters)))
        return 0

    here = os.path.abspath(__file__)
    cmd = [sys.executable, here, "--child", "--seq", str(args.seq),
           "--batch", str(args.batch), "--iters", str(args.iters)]
    if args.sweep:
        cmd += ["--sweep"]
    if args.platform:
        cmd += ["--platform", args.platform]
    metric = SWEEP_METRIC if args.sweep else METRIC
    return run_child_with_retries(
        cmd, os.path.dirname(here), args.timeouts, metric, UNIT,
        record=args.platform is None,
        match={"batch": args.batch, "seq": args.seq})


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
