"""Flight-recorder overhead benchmark: recorder-on vs recorder-off.

Always-on tracing is only defensible if it is effectively free.  Both
arms run the SAME StandardUpdater training loop (MLP, 8-device mesh,
watchdog-style heartbeat per step so the instant-event path is
exercised too); the "on" arm records every step's spans (host /
dispatch / retire, ~5 events per update) into an enabled
:class:`~chainermn_tpu.utils.telemetry.TraceRecorder` ring, the "off"
arm leaves the global recorder disabled — the production default, whose
per-span cost is one attribute read on a shared no-op singleton.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", ...}:
value = recorder-off steps/sec ÷ recorder-on steps/sec ("x"; 1.0 = the
recorder is free).  ``overhead_pct`` = (value − 1) × 100 and
``within_bar`` reports the <1% acceptance bar the docs promise
(docs/OBSERVABILITY.md).  Arms are interleaved best-of-rounds so a
noisy host cannot fake an overhead.  Same hermetic child-process
timeout/retry pattern as bench.py.
"""

import argparse
import json
import os
import sys
import time

from _bench_common import pin_platform, run_child_with_retries

METRIC = "telemetry_recorder_overhead"
UNIT = "x"
BAR_PCT = 1.0


def run(batch=8, dim=512, hidden=2048, classes=10, n_examples=4096,
        warmup=3, iters=30, rounds=3):
    import jax
    import numpy as np
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.models import (init_mlp, mlp_apply,
                                      softmax_cross_entropy)
    from chainermn_tpu.utils.telemetry import (TraceRecorder,
                                               get_recorder,
                                               set_recorder)

    comm = cmn.create_communicator("tpu_xla")
    rng = np.random.RandomState(0)
    X = rng.randn(n_examples, dim).astype(np.float32)
    Y = (rng.rand(n_examples) * classes).astype(np.int32)

    def loss_fn(p, x, y):
        return softmax_cross_entropy(mlp_apply(p, x), y)

    params0 = init_mlp(jax.random.PRNGKey(0), [dim, hidden, classes])

    def make(seed=11):
        it = cmn.SerialIterator((X, Y), batch, shuffle=True, seed=seed)
        opt = cmn.create_multi_node_optimizer(optax.sgd(0.05), comm)
        return cmn.StandardUpdater(it, opt, loss_fn, params0, comm)

    def timed_arm(enabled):
        rec = TraceRecorder(enabled=enabled)
        prev = set_recorder(rec)
        try:
            upd = make()
            from chainermn_tpu.extensions import TrainingWatchdog

            wd = TrainingWatchdog(stall_timeout=3600)
            for _ in range(warmup):
                upd.update()
                wd.heartbeat(iteration=upd.iteration)
                float(upd.observation["main/loss"])
            jax.block_until_ready(upd.params)
            start_iter = upd.iteration
            t0 = time.perf_counter()
            for _ in range(iters):
                upd.update()
                wd.heartbeat(iteration=upd.iteration)
                float(upd.observation["main/loss"])
            jax.block_until_ready(upd.params)
            dt = time.perf_counter() - t0
            n_events = len(rec)
            return (upd.iteration - start_iter) / dt, n_events
        finally:
            set_recorder(prev)

    best = {"on": 0.0, "off": 0.0}
    events_on = 0
    for r in range(rounds):
        # alternate arm order so monotone host drift (cache growth,
        # thermal) cannot systematically tax whichever arm runs second
        order = (False, True) if r % 2 == 0 else (True, False)
        for enabled in order:
            steps_per_s, n_events = timed_arm(enabled)
            key = "on" if enabled else "off"
            best[key] = max(best[key], steps_per_s)
            if enabled:
                events_on = n_events

    ratio = best["off"] / best["on"]
    overhead_pct = (ratio - 1.0) * 100.0
    assert events_on > 0, "recorder-on arm recorded no events"
    return {
        "metric": METRIC,
        "value": round(ratio, 4),
        "unit": UNIT,
        "vs_baseline": round(ratio, 4),
        "overhead_pct": round(overhead_pct, 3),
        "bar_pct": BAR_PCT,
        "within_bar": bool(overhead_pct < BAR_PCT),
        "off_steps_per_s": round(best["off"], 2),
        "on_steps_per_s": round(best["on"], 2),
        "events_recorded_on_arm": events_on,
        "batch": batch,
        "dim": dim,
        "hidden": hidden,
        "iters": iters,
        "n_devices": jax.device_count(),
        "device_kind": jax.devices()[0].device_kind,
    }


def _child_main(args):
    env_platform = os.environ.get("JAX_PLATFORMS", "")
    if args.platform == "cpu" or (
            args.platform is None and env_platform.startswith("cpu")):
        # fake the multi-chip world BEFORE backend init (same trick as
        # tests/conftest.py) so the step is a real sharded program
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count"
                        f"={args.devices}").strip()
    pin_platform(args.platform)
    result = run(batch=args.batch, dim=args.dim, hidden=args.hidden,
                 warmup=args.warmup, iters=args.iters,
                 rounds=args.rounds)
    print("BENCH_RESULT " + json.dumps(result))


def _parent_main(args):
    here = os.path.abspath(__file__)
    cmd = [sys.executable, here, "--child",
           "--batch", str(args.batch), "--dim", str(args.dim),
           "--hidden", str(args.hidden),
           "--warmup", str(args.warmup), "--iters", str(args.iters),
           "--rounds", str(args.rounds), "--devices", str(args.devices)]
    if args.platform:
        cmd += ["--platform", args.platform]
    return run_child_with_retries(
        cmd, os.path.dirname(here), args.timeouts, METRIC, UNIT,
        record=args.platform is None,
        match={"batch": args.batch, "dim": args.dim,
               "hidden": args.hidden, "iters": args.iters})


def _parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", action="store_true")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--dim", type=int, default=512)
    p.add_argument("--hidden", type=int, default=2048)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--iters", type=int, default=60,
                   help="timed updates per arm per round (sized so a "
                        "1%% bar is resolvable against host noise)")
    p.add_argument("--rounds", type=int, default=4,
                   help="order-alternating interleaved timing rounds "
                        "(best per arm counts)")
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
