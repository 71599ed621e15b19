"""Measured exchange-plan autotuner benchmark: does the tuned plan
actually win, and does the cache actually eliminate probing?

Two claims, each asserted structurally and reported in ONE JSON line:

1. **The tuned plan is the measured optimum.**  The autotuner
   enumerates {per-leaf, fused-flat, hierarchical 2-stage,
   reduce-scatter→all-gather} × a bucket grid × wire dtype on a
   transformer-shaped grad pytree, prunes with the analytic cost model,
   and times the survivors on the live mesh.  The bench then re-times
   the WINNER fresh (interleaved min-of-rounds, same harness as
   bench_fused_allreduce) and reports ``value`` = worst-candidate time
   / tuned time (the cost of picking wrong, ≥1.3× on the default
   workload) plus ``tuned_vs_best`` = fresh tuned time / best recorded
   candidate time (≈1.0 — the tuner picked the real optimum, within
   noise).

2. **A second run is served ENTIRELY from the plan cache.**  The same
   (mesh, payload, version) signature is tuned again against the same
   scratch cache file: the bench asserts ``from_cache=True`` and
   ``n_probes == 0`` — zero probe executions — and that the served
   plan is bit-identical to the first run's winner.

Workload note: same latency-dominated regime as bench_fused_allreduce
(deep-narrow transformer grad tree, 500+ leaves, a few MB — where real
ICI training sits, scaled to this host's CPU fabric).  Same hermetic
child-process timeout/retry pattern as bench.py.
"""

import argparse
import json
import os
import sys
import tempfile
import time

from _bench_common import pin_platform, run_child_with_retries

METRIC = "autotune_tuned_vs_worst_speedup"
UNIT = "x"


def make_local_grad_tree(rng, n_layers, d_model, vocab, dtype):
    """LOCAL (per-rank) transformer-shaped grad pytree — the payload
    signature the autotuner keys and probes against."""
    def leaf(*shape):
        return rng.randn(*shape).astype(dtype)

    tree = {"embed": leaf(vocab, d_model)}
    for i in range(n_layers):
        tree[f"layer_{i:02d}"] = {
            "wq": leaf(d_model, d_model), "wk": leaf(d_model, d_model),
            "wv": leaf(d_model, d_model), "wo": leaf(d_model, d_model),
            "w1": leaf(d_model, 4 * d_model), "w2": leaf(4 * d_model, d_model),
            "ln1": leaf(d_model), "ln2": leaf(d_model),
        }
    return tree


def run(n_layers=64, d_model=32, vocab=4096, trials=3, rounds=3,
        iters=3, top_k=6):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    import chainermn_tpu as cmn
    from chainermn_tpu.utils import autotune

    comm = cmn.create_communicator("tpu_xla")
    n = comm.size
    devices = np.asarray(jax.devices())
    # fake the multi-host shape on one host (same trick as
    # bench_fused_allreduce) so hierarchical candidates join the space
    hier_mesh = None
    if n % 2 == 0 and n >= 4:
        hier_mesh = Mesh(devices.reshape(2, n // 2),
                         ("inter", comm.axis_name))

    rng = np.random.RandomState(0)
    tree = make_local_grad_tree(rng, n_layers, d_model, vocab, np.float32)
    leaves = jax.tree.leaves(tree)
    total_bytes = sum(l.size * l.dtype.itemsize for l in leaves)

    cache_path = os.path.join(tempfile.mkdtemp(prefix="autotune_bench_"),
                              "plan_cache.json")

    # -- first run: live probe search --------------------------------- #
    t0 = time.perf_counter()
    plan = autotune.autotune_plan(
        comm, tree, hier_mesh=hier_mesh, cache_path=cache_path,
        trials=trials, top_k=top_k)
    tune_s = time.perf_counter() - t0
    assert not plan.from_cache and plan.n_probes > 0
    ok = [t for t in plan.meta["timings"] if t["parity_ok"]]
    best = min(ok, key=lambda t: t["ms"])
    worst = max(ok, key=lambda t: t["ms"])

    # -- fresh re-time of the tuned plan (interleaved vs worst) ------- #
    # data placed SHARDED per arm mesh, exactly like the tuner's
    # probes — feeding raw host arrays would add a transfer/reshard to
    # every timed call and skew the comparison with the tuning medians
    raw = autotune._probe_tree(tree, n, seed=1)

    def probe_arm(entry):
        cand = {"strategy": entry["strategy"],
                "bucket_bytes": entry["bucket_bytes"],
                "wire_dtype": entry["wire_dtype"]}
        hier = entry["strategy"] == "hierarchical"
        mesh = hier_mesh if hier else comm.mesh
        axes = ("inter", comm.axis_name) if hier else (comm.axis_name,)
        fn = autotune.build_exchange_fn(
            mesh, comm.axis_name, cand,
            inter_axis_name="inter" if hier else None)
        return fn, autotune._place(raw, mesh, axes)

    arms = {"tuned": probe_arm({"strategy": plan.strategy,
                                "bucket_bytes": plan.bucket_bytes,
                                "wire_dtype": plan.wire_dtype}),
            "worst": probe_arm(worst)}
    # "matches the best candidate" must compare like with like: re-time
    # the best recorded candidate in the SAME interleaved arm harness
    # (the tuning-phase median uses a different blocking discipline).
    # When the tuner's winner IS the best candidate the ratio is 1.0
    # by construction — the claim holds structurally.
    best_is_tuned = (best["strategy"] == plan.strategy
                     and best["bucket_bytes"] == plan.bucket_bytes
                     and best["wire_dtype"] == plan.wire_dtype)
    if not best_is_tuned:
        arms["best"] = probe_arm(best)
    for fn, data in arms.values():
        jax.block_until_ready(fn(data))          # compile + warm
    times = {name: float("inf") for name in arms}
    for _ in range(rounds):
        for name, (fn, data) in arms.items():
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(data)
            jax.block_until_ready(out)
            times[name] = min(times[name],
                              (time.perf_counter() - t0) / iters * 1e3)

    # -- second run: must be served entirely from the cache ----------- #
    plan2 = autotune.autotune_plan(
        comm, tree, hier_mesh=hier_mesh, cache_path=cache_path,
        trials=trials, top_k=top_k)
    assert plan2.from_cache, "second run was not served from the cache"
    assert plan2.n_probes == 0, \
        f"cache hit still ran {plan2.n_probes} probe executions"
    assert plan2.to_dict() == plan.to_dict(), \
        "cached plan differs from the tuned plan"

    speedup = times["worst"] / times["tuned"]
    best_ms = times["tuned"] if best_is_tuned else times["best"]
    return {
        "metric": METRIC,
        "value": round(speedup, 3),
        "unit": UNIT,
        "vs_baseline": round(speedup, 3),
        "tuned_ms": round(times["tuned"], 3),
        "worst_ms": round(times["worst"], 3),
        "tuned_vs_best": round(times["tuned"] / best_ms, 3),
        "tuned_strategy": plan.strategy,
        "tuned_bucket_bytes": plan.bucket_bytes,
        "tuned_wire_dtype": plan.wire_dtype or "native",
        "best_candidate": f"{best['strategy']}/b{best['bucket_bytes']}"
                          f"/{best['wire_dtype'] or 'native'}",
        "worst_candidate": f"{worst['strategy']}/b{worst['bucket_bytes']}"
                           f"/{worst['wire_dtype'] or 'native'}",
        "n_candidates": plan.meta["n_enumerated"],
        "n_probed": plan.meta["n_probed"],
        "first_run_probes": plan.n_probes,
        "second_run_probes": plan2.n_probes,
        "second_run_cached": plan2.from_cache,
        "tune_seconds": round(tune_s, 2),
        "measured_latency_us": round(plan.link["latency_s"] * 1e6, 2),
        "measured_bandwidth_gbps": round(
            plan.link["bandwidth_bytes_per_s"] / 1e9, 4),
        "n_devices": n,
        "n_leaves": len(leaves),
        "total_mb": round(total_bytes / 2**20, 2),
        "n_leaves_config": f"{n_layers}x{d_model}",
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
    result = run(n_layers=args.n_layers, d_model=args.d_model,
                 vocab=args.vocab, trials=args.trials,
                 rounds=args.rounds, iters=args.iters, top_k=args.top_k)
    print("BENCH_RESULT " + json.dumps(result))


def _parent_main(args):
    here = os.path.abspath(__file__)
    cmd = [sys.executable, here, "--child",
           "--n-layers", str(args.n_layers),
           "--d-model", str(args.d_model), "--vocab", str(args.vocab),
           "--trials", str(args.trials), "--rounds", str(args.rounds),
           "--iters", str(args.iters), "--top-k", str(args.top_k),
           "--devices", str(args.devices)]
    if args.platform:
        cmd += ["--platform", args.platform]
    return run_child_with_retries(
        cmd, os.path.dirname(here), args.timeouts, METRIC, UNIT,
        record=args.platform is None,
        match={"n_leaves_config": f"{args.n_layers}x{args.d_model}"})


def _parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", action="store_true")
    p.add_argument("--n-layers", type=int, default=64)
    p.add_argument("--d-model", type=int, default=32)
    p.add_argument("--vocab", type=int, default=4096)
    p.add_argument("--trials", type=int, default=3,
                   help="autotuner probe trials per candidate")
    p.add_argument("--rounds", type=int, default=3,
                   help="fresh re-time rounds (best round counts)")
    p.add_argument("--iters", type=int, default=3)
    p.add_argument("--top-k", type=int, default=6,
                   help="candidates surviving cost-model pruning")
    p.add_argument("--devices", type=int, default=8,
                   help="virtual device count for --platform cpu")
    p.add_argument("--platform", default=None)
    p.add_argument("--timeouts", type=int, nargs="+", default=[480])
    return p.parse_args(argv)


if __name__ == "__main__":
    args = _parse_args(sys.argv[1:])
    if args.child:
        _child_main(args)
    else:
        sys.exit(_parent_main(args))
