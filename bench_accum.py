"""Microbatched accumulation benchmark: window-fused vs per-microbatch
gradient exchange.

Both arms consume the SAME stream of M microbatches per dispatch on the
8-device mesh, run the same forward/backward per microbatch, and differ
only in where the cross-replica gradient exchange fires:

- **per-micro** — ``StandardUpdater(steps_per_execution=M)``: the
  classic fused window; every microbatch's step carries its own
  (fused, bucketed) all-reduce inside the scan body, so the wire sees M
  exchanges per window — ChainerMN's one-allreduce-per-batch cadence,
  here with dispatch latency already amortised so the collective cost
  itself is what remains.
- **window** — ``StandardUpdater(accum_steps=M)``: local gradients
  accumulate across the microbatch scan (fp32 accumulator, no
  collective in the loop body) and the optimizer's fused exchange fires
  ONCE at the window end — collective launches and wire bytes cut M×.

Before timing, the window arm is parity-probed against a single
M×-larger-batch updater (the accumulation correctness claim), and the
M→1 collective claim is proven from both arms' compiled HLO via
``collective_stats``/``assert_accum_collectives`` — the observed counts
ride in the result record.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", ...}:
value = window steps/sec ÷ per-micro steps/sec (unit "x", 1.0 = no
win; steps = microbatches, so the denominator work is identical).
Same hermetic child-process timeout/retry pattern as bench.py.
"""

import argparse
import json
import os
import sys
import time

from _bench_common import pin_platform, run_child_with_retries

METRIC = "accum_window_exchange_speedup"
UNIT = "x"


def run(batch=8, dim=512, hidden=2048, classes=10, n_examples=4096,
        accum_steps=4, warmup=3, iters=20, rounds=3):
    import jax
    import numpy as np
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.models import (init_mlp, mlp_apply,
                                      softmax_cross_entropy)
    from chainermn_tpu.utils import (assert_accum_collectives,
                                     collective_stats)

    comm = cmn.create_communicator("tpu_xla")
    rng = np.random.RandomState(0)
    X = rng.randn(n_examples, dim).astype(np.float32)
    Y = (rng.rand(n_examples) * classes).astype(np.int32)

    def loss_fn(p, x, y):
        return softmax_cross_entropy(mlp_apply(p, x), y)

    params0 = init_mlp(jax.random.PRNGKey(0), [dim, hidden, classes])
    grad_bytes = sum(l.size * l.dtype.itemsize
                     for l in jax.tree.leaves(params0))

    def make(accum, spe=1, batch_size=None, seed=11):
        it = cmn.SerialIterator((X, Y), batch_size or batch,
                                shuffle=True, seed=seed)
        opt = cmn.create_multi_node_optimizer(optax.sgd(0.05), comm)
        return cmn.StandardUpdater(
            it, opt, loss_fn, params0, comm,
            accum_steps=accum, steps_per_execution=spe)

    # -- correctness: window-fused accumulation == one M×-larger batch - #
    a, b = make(accum_steps), make(1, batch_size=batch * accum_steps)
    for _ in range(2):
        a.update()
        b.update()
    for pa, pb in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_allclose(np.asarray(pa), np.asarray(pb),
                                   rtol=1e-4, atol=1e-5)

    # -- proof: M→1 collectives per window, read off compiled HLO ------ #
    def window_stats(upd, n_steps, accum):
        arrays, k, _tail = upd._assemble_host_window()
        fn = upd._get_step(len(arrays), n_steps, accum)
        carry = (upd.params, upd.state, upd.opt_state)
        return collective_stats(fn.lower(carry, *arrays).compile())

    w_stats = window_stats(make(accum_steps), 1, accum_steps)
    window_collectives = assert_accum_collectives(
        w_stats, grad_bytes, 4 << 20)
    m_stats = window_stats(make(1, spe=accum_steps), accum_steps, 1)
    looped = sum(s.looped for s in m_stats.values())
    toplevel = sum(s.count - s.looped for s in m_stats.values())
    if not looped:
        raise AssertionError(
            "per-microbatch arm shows no in-scan collectives — the "
            "baseline is not exchanging per microbatch; measurement "
            "would be meaningless")
    per_micro_collectives = looped * accum_steps + toplevel

    # -- timing: identical microbatch streams, best-of-rounds ---------- #
    def timed_arm(accum, spe):
        upd = make(accum, spe=spe)
        for _ in range(warmup):
            upd.update()
            float(upd.observation["main/loss"])
        jax.block_until_ready(upd.params)
        start_iter = upd.iteration
        t0 = time.perf_counter()
        for _ in range(iters):
            upd.update()
            float(upd.observation["main/loss"])
        jax.block_until_ready(upd.params)
        dt = time.perf_counter() - t0
        return (upd.iteration - start_iter) / dt

    best = {"window": 0.0, "per_micro": 0.0}
    for _ in range(rounds):
        best["window"] = max(best["window"],
                             timed_arm(accum_steps, 1))
        best["per_micro"] = max(best["per_micro"],
                                timed_arm(1, accum_steps))

    speedup = best["window"] / best["per_micro"]
    return {
        "metric": METRIC,
        "value": round(speedup, 3),
        "unit": UNIT,
        "vs_baseline": round(speedup, 3),
        "per_micro_steps_per_s": round(best["per_micro"], 2),
        "window_steps_per_s": round(best["window"], 2),
        "collectives_per_window": {
            "per_micro": per_micro_collectives,
            "window_fused": window_collectives,
        },
        "in_scan_collective_sites_per_micro_arm": looped,
        "grad_bytes": grad_bytes,
        "accum_steps": accum_steps,
        "batch": batch,
        "dim": dim,
        "hidden": hidden,
        "n_devices": jax.device_count(),
        "device_kind": jax.devices()[0].device_kind,
    }


def _child_main(args):
    env_platform = os.environ.get("JAX_PLATFORMS", "")
    if args.platform == "cpu" or (
            args.platform is None and env_platform.startswith("cpu")):
        # fake the multi-chip world BEFORE backend init (same trick as
        # tests/conftest.py) so the exchange is real, not size-1
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count"
                        f"={args.devices}").strip()
    pin_platform(args.platform)
    result = run(batch=args.batch, dim=args.dim, hidden=args.hidden,
                 accum_steps=args.accum_steps, warmup=args.warmup,
                 iters=args.iters, rounds=args.rounds)
    print("BENCH_RESULT " + json.dumps(result))


def _parent_main(args):
    here = os.path.abspath(__file__)
    cmd = [sys.executable, here, "--child",
           "--batch", str(args.batch), "--dim", str(args.dim),
           "--hidden", str(args.hidden),
           "--accum-steps", str(args.accum_steps),
           "--warmup", str(args.warmup), "--iters", str(args.iters),
           "--rounds", str(args.rounds), "--devices", str(args.devices)]
    if args.platform:
        cmd += ["--platform", args.platform]
    return run_child_with_retries(
        cmd, os.path.dirname(here), args.timeouts, METRIC, UNIT,
        record=args.platform is None,
        match={"batch": args.batch, "dim": args.dim,
               "hidden": args.hidden,
               "accum_steps": args.accum_steps})


def _parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", action="store_true")
    p.add_argument("--batch", type=int, default=8,
                   help="global microbatch size (1/device keeps compute "
                        "small so the exchange cost is what's measured)")
    p.add_argument("--dim", type=int, default=512)
    p.add_argument("--hidden", type=int, default=2048)
    p.add_argument("--accum-steps", type=int, default=4,
                   help="microbatches per accumulation window (M)")
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--iters", type=int, default=20,
                   help="timed updates per round (each consumes M "
                        "microbatches in both arms)")
    p.add_argument("--rounds", type=int, default=3,
                   help="interleaved timing rounds (best round counts)")
    p.add_argument("--devices", type=int, default=8,
                   help="virtual device count for the cpu platform")
    p.add_argument("--platform", default=None)
    p.add_argument("--timeouts", type=int, nargs="+", default=[480])
    return p.parse_args(argv)


if __name__ == "__main__":
    args = _parse_args(sys.argv[1:])
    if args.child:
        _child_main(args)
    else:
        sys.exit(_parent_main(args))
