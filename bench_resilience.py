"""Resilience-layer overhead benchmark: watchdog + checkpoint checksums.

The resilience subsystem (docs/RESILIENCE.md) must be cheap enough to
leave ON in production: per-step it adds one watchdog heartbeat (a
timestamp write + optional KV publish), and per checkpoint it adds the
CRC32 walk over every payload.  This bench measures both against the
same training loop on the 8-device CPU mesh and reports the combined
overhead as a fraction of step time — the acceptance bar is <2%.

Protocol — the per-step costs are tiny (microseconds against a
multi-ms step), so differencing two noisy end-to-end loops would
measure scheduler jitter, not the subsystem.  Both costs are timed
DIRECTLY and amortised into a measured step time:

- heartbeat cost: wall time of many armed ``TrainingWatchdog.heartbeat``
  calls (the per-iteration hot path: timestamp + counters + the KV
  publish branch);
- checksum cost: ``save_state`` wall time with the CRC walk vs with it
  stubbed out, on a real train-state pytree, divided by the checkpoint
  cadence;
- step time: best steps/sec of the real training loop on the 8-device
  mesh (a two-arm plain-vs-guarded ratio is also recorded as an
  end-to-end sanity cross-check — it must sit at ~1.0 within noise).

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", ...}:
value = combined watchdog+checksum overhead as percent of step time
(unit "%"; the acceptance bar is <2).  One child under a timeout, the
parent off JAX (``_bench_common``).
"""

import argparse
import json
import os
import sys
import time

from _bench_common import pin_platform, run_child_with_retries

METRIC = "resilience_watchdog_checksum_overhead"
UNIT = "%"


def run(batch=256, dim=256, hidden=1024, classes=10, n_examples=4096,
        warmup=3, iters=40, rounds=3, ckpt_interval=50):
    import jax
    import numpy as np
    import optax

    import chainermn_tpu as cmn
    import chainermn_tpu.utils.serialization as ser
    from chainermn_tpu.extensions import TrainingWatchdog
    from chainermn_tpu.models import (init_mlp, mlp_apply,
                                      softmax_cross_entropy)

    comm = cmn.create_communicator("tpu_xla")
    rng = np.random.RandomState(0)
    X = rng.randn(n_examples, dim).astype(np.float32)
    Y = (rng.rand(n_examples) * classes).astype(np.int32)

    def loss_fn(p, x, y):
        return softmax_cross_entropy(mlp_apply(p, x), y)

    params0 = init_mlp(jax.random.PRNGKey(0), [dim, hidden, classes])

    def make_updater():
        it = cmn.SerialIterator((X, Y), batch, shuffle=True, seed=11)
        opt = cmn.create_multi_node_optimizer(optax.sgd(0.05), comm)
        return cmn.StandardUpdater(it, opt, loss_fn, params0, comm)

    def timed_arm(with_watchdog):
        upd = make_updater()
        wd = None
        if with_watchdog:
            wd = TrainingWatchdog(stall_timeout=300, comm=comm)
            wd.start()
        for _ in range(warmup):
            upd.update()
            float(upd.observation["main/loss"])
            if wd:
                wd.heartbeat(iteration=upd.iteration)
        jax.block_until_ready(upd.params)
        t0 = time.perf_counter()
        for _ in range(iters):
            upd.update()
            float(upd.observation["main/loss"])
            if wd:
                wd.heartbeat(iteration=upd.iteration)
        jax.block_until_ready(upd.params)
        dt = time.perf_counter() - t0
        if wd:
            wd.stop()
        return iters / dt

    best = {"plain": 0.0, "guarded": 0.0}
    for r in range(rounds):
        # alternate arm order so neither side systematically inherits a
        # warmer cache/scheduler state
        order = (False, True) if r % 2 == 0 else (True, False)
        for guarded in order:
            key = "guarded" if guarded else "plain"
            best[key] = max(best[key], timed_arm(guarded))

    # ---- heartbeat cost, measured directly (the per-step hot path) ----
    wd = TrainingWatchdog(stall_timeout=300, comm=comm)
    wd.start()
    n_hb = 20000
    t0 = time.perf_counter()
    for i in range(n_hb):
        wd.heartbeat(iteration=i)
    hb_s = (time.perf_counter() - t0) / n_hb
    wd.stop()

    # ---- checksum side: CRC walk share of a real checkpoint save ----
    upd = make_updater()
    upd.update()
    state = {"params": upd.params, "opt_state": upd.opt_state}
    import tempfile

    tmpdir = tempfile.mkdtemp(prefix="resil_bench_")

    def time_save(tag):
        best_s = float("inf")
        for i in range(3):
            t0 = time.perf_counter()
            ser.save_state(os.path.join(tmpdir, f"s_{tag}_{i}"), state)
            best_s = min(best_s, time.perf_counter() - t0)
        return best_s

    save_crc_s = time_save("crc")
    real_crc = ser._leaf_crc
    try:
        ser._leaf_crc = lambda arr: 0
        save_nocrc_s = time_save("nocrc")
    finally:
        ser._leaf_crc = real_crc

    step_plain_ms = 1e3 / best["plain"]
    step_guarded_ms = 1e3 / best["guarded"]
    hb_pct = hb_s * 1e3 / step_plain_ms * 100.0
    crc_ms = max(save_crc_s - save_nocrc_s, 0.0) * 1e3
    crc_per_step_pct = (crc_ms / ckpt_interval) / step_plain_ms * 100.0
    total_overhead_pct = hb_pct + crc_per_step_pct

    end_to_end_ratio = best["guarded"] / best["plain"]
    # the end-to-end arms are the SANITY CROSS-CHECK on the analytic
    # headline: if they disagree by more than scheduler noise, say so
    # IN THE RECORD instead of silently certifying the analytic number
    # (a real guarded-path regression must not hide under it)
    consistent = abs(1.0 - end_to_end_ratio) <= 0.15
    rec = {
        "metric": METRIC,
        "value": round(total_overhead_pct, 4),
        "unit": UNIT,
        "vs_baseline": round(total_overhead_pct, 4),
        "plain_steps_per_s": round(best["plain"], 2),
        "guarded_steps_per_s": round(best["guarded"], 2),
        "end_to_end_ratio": round(end_to_end_ratio, 4),
        "end_to_end_consistent": consistent,
        "step_plain_ms": round(step_plain_ms, 3),
        "step_guarded_ms": round(step_guarded_ms, 3),
        "heartbeat_us": round(hb_s * 1e6, 3),
        "heartbeat_pct": round(hb_pct, 4),
        "save_with_crc_ms": round(save_crc_s * 1e3, 3),
        "save_without_crc_ms": round(save_nocrc_s * 1e3, 3),
        "crc_walk_ms": round(crc_ms, 3),
        "ckpt_interval_steps": ckpt_interval,
        "crc_per_step_pct": round(crc_per_step_pct, 4),
        "total_overhead_pct": round(total_overhead_pct, 3),
        "batch": batch,
        "dim": dim,
        "hidden": hidden,
        "n_devices": jax.device_count(),
        "device_kind": jax.devices()[0].device_kind,
    }
    if not consistent:
        rec["end_to_end_note"] = (
            "plain-vs-guarded end-to-end ratio is outside the ±15% "
            "noise band — treat value as the analytic per-component "
            "overhead only and re-measure the cross-check on a quiet "
            "host before trusting it")
    return rec


def _child_main(args):
    env_platform = os.environ.get("JAX_PLATFORMS", "")
    if args.platform == "cpu" or (
            args.platform is None and env_platform.startswith("cpu")):
        # fake the multi-chip world BEFORE backend init (same trick as
        # tests/conftest.py) so the mesh is the suite's 8-device one
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count"
                        f"={args.devices}").strip()
    pin_platform(args.platform)
    result = run(batch=args.batch, dim=args.dim, hidden=args.hidden,
                 warmup=args.warmup, iters=args.iters,
                 rounds=args.rounds, ckpt_interval=args.ckpt_interval)
    print("BENCH_RESULT " + json.dumps(result))


def _parent_main(args):
    here = os.path.abspath(__file__)
    cmd = [sys.executable, here, "--child",
           "--batch", str(args.batch), "--dim", str(args.dim),
           "--hidden", str(args.hidden), "--warmup", str(args.warmup),
           "--iters", str(args.iters), "--rounds", str(args.rounds),
           "--ckpt-interval", str(args.ckpt_interval),
           "--devices", str(args.devices)]
    if args.platform:
        cmd += ["--platform", args.platform]
    return run_child_with_retries(
        cmd, os.path.dirname(here), args.timeouts, METRIC, UNIT,
        record=args.platform is None,
        match={"batch": args.batch, "dim": args.dim})


def _parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", action="store_true")
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--dim", type=int, default=256)
    p.add_argument("--hidden", type=int, default=1024)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--iters", type=int, default=40)
    p.add_argument("--rounds", type=int, default=3,
                   help="interleaved timing rounds (best round counts)")
    p.add_argument("--ckpt-interval", type=int, default=50,
                   help="steps per checkpoint, for amortising the CRC "
                        "walk into per-step overhead")
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
