"""Metrics-registry overhead benchmark: registry-on vs registry-off.

The metrics layer (``utils/metrics.py``) only earns its always-on
wiring — engine admit/evict histograms, updater step-time histogram,
checkpoint/watchdog counters — if recording is effectively free.  Both
arms run the SAME StandardUpdater training loop on the 8-device mesh
with the same per-step instrument calls (the updater's built-in
``train/step_time`` observe + ``train/iterations`` inc, plus an
explicit counter/gauge/histogram triple per step so every instrument
type's record path is on the measured line); the "on" arm records into
an enabled :class:`~chainermn_tpu.utils.metrics.MetricsRegistry`, the
"off" arm leaves it disabled — the production default, whose record
path is one attribute read and an early return (the instrument getters
hand back a shared no-op singleton, pinned allocation-free by
``tests/util_tests/test_metrics.py``).

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", ...}:
value = registry-off steps/sec ÷ registry-on steps/sec ("x"; 1.0 = the
registry is free).  ``overhead_pct`` = (value − 1) × 100 and
``within_bar`` reports the <1% acceptance bar the docs promise
(docs/OBSERVABILITY.md "Metrics").  Arms are interleaved
order-alternating best-of-rounds so a noisy host cannot fake an
overhead.  One child under a timeout, the parent off JAX
(``_bench_common``).
"""

import argparse
import json
import os
import sys
import time

from _bench_common import pin_platform, run_child_with_retries

METRIC = "metrics_registry_overhead"
UNIT = "x"
BAR_PCT = 1.0


def run(batch=8, dim=512, hidden=2048, classes=10, n_examples=4096,
        warmup=3, iters=60, rounds=4):
    import jax
    import numpy as np
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.models import (init_mlp, mlp_apply,
                                      softmax_cross_entropy)
    from chainermn_tpu.utils.metrics import (MetricsRegistry,
                                             get_registry, set_registry)

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

    def one_step(upd, i):
        upd.update()            # built-in: train/step_time + iterations
        reg = get_registry()    # explicit: one of each instrument type
        reg.inc("bench/steps")
        reg.set("bench/queue_depth", i % 7)
        reg.observe("bench/latency", 1e-3 * (1 + i % 5))
        float(upd.observation["main/loss"])

    def timed_arm(enabled):
        prev = set_registry(MetricsRegistry(enabled=enabled))
        try:
            upd = make()
            for i in range(warmup):
                one_step(upd, i)
            jax.block_until_ready(upd.params)
            start_iter = upd.iteration
            t0 = time.perf_counter()
            for i in range(iters):
                one_step(upd, i)
            jax.block_until_ready(upd.params)
            dt = time.perf_counter() - t0
            reg = get_registry()
            n_instruments = len(reg)
            hist_count = (reg.snapshot().get("train/step_time", {})
                          .get("count", 0))
            return ((upd.iteration - start_iter) / dt, n_instruments,
                    hist_count)
        finally:
            set_registry(prev)

    best = {"on": 0.0, "off": 0.0}
    instruments_on = hist_on = 0
    for r in range(rounds):
        # alternate arm order so monotone host drift (cache growth,
        # thermal) cannot systematically tax whichever arm runs second
        order = (False, True) if r % 2 == 0 else (True, False)
        for enabled in order:
            steps_per_s, n_instruments, hist_count = timed_arm(enabled)
            key = "on" if enabled else "off"
            best[key] = max(best[key], steps_per_s)
            if enabled:
                instruments_on = n_instruments
                hist_on = hist_count
            else:
                assert n_instruments == 0, \
                    "disabled registry grew instruments"

    ratio = best["off"] / best["on"]
    overhead_pct = (ratio - 1.0) * 100.0
    assert instruments_on >= 5, instruments_on
    assert hist_on == warmup + iters, hist_on
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
        "instruments_on_arm": instruments_on,
        "step_time_observations": hist_on,
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
