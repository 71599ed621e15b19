"""Collective-plan IR benchmark: does the searched program win, and
does the plan cache eliminate probing for IR patterns?

Two patterns, one JSON line:

1. **FSDP gather** — a deep-narrow transformer param tree (500+
   leaves, latency-dominated) tuned over {per-leaf, fused} × wire
   dtype.  The tuned program and the worst recorded candidate are
   re-timed fresh in the same interleaved min-of-rounds harness as
   bench_autotune; ``fsdp_speedup`` = worst / tuned.
2. **MoE all-to-all** — an ``(E, C, D)`` slots exchange tuned over
   {single-shot, axis-split chunked} × wire dtype; ``moe_speedup``
   likewise.

``value`` is the SMALLER of the two speedups — the claim is that the
search pays on every ported pattern, not just the friendliest one.

The cache claim is asserted structurally for both patterns: a second
``autotune_pattern_plan`` call against the same scratch cache must
return ``from_cache=True`` with ``n_probes == 0`` (zero probe
executions) and a bit-identical program.
"""

import argparse
import json
import os
import sys
import tempfile
import time

from _bench_common import pin_platform, run_child_with_retries

METRIC = "plan_ir_tuned_vs_worst_speedup"
UNIT = "x"


def make_local_param_tree(rng, n_layers, d_model, vocab, dtype):
    """LOCAL (per-rank) transformer-shaped param shards, every leaf
    gathered at dim 0."""
    def leaf(*shape):
        return rng.randn(*shape).astype(dtype)

    tree = {"embed": leaf(vocab, d_model)}
    for i in range(n_layers):
        tree[f"layer_{i:02d}"] = {
            "wq": leaf(d_model, d_model), "wk": leaf(d_model, d_model),
            "wv": leaf(d_model, d_model), "wo": leaf(d_model, d_model),
            "w1": leaf(d_model, 4 * d_model),
            "w2": leaf(4 * d_model, d_model),
            "ln1": leaf(d_model), "ln2": leaf(d_model),
        }
    return tree


def _retime_arms(arms, rounds, iters):
    """Interleaved min-of-rounds over {name: (fn, data)} arms."""
    import jax

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
    return times


def _tune_and_race(comm, pattern, payload, cache_path, *, trials,
                   rounds, iters, top_k, enum_kw, tune_kw, probe_kw):
    """Tune one pattern, re-time tuned vs worst candidate fresh, and
    assert the second tuning is 100% cache-served."""
    import jax
    import numpy as np

    from chainermn_tpu.ops import plan_ir
    from chainermn_tpu.utils import autotune

    t0 = time.perf_counter()
    plan = autotune.autotune_pattern_plan(
        comm, payload, pattern=pattern, cache_path=cache_path,
        trials=trials, top_k=top_k, **tune_kw)
    tune_s = time.perf_counter() - t0
    assert not plan.from_cache and plan.n_probes > 0
    ok = [t for t in plan.meta["timings"] if t["parity_ok"]]
    worst = max(ok, key=lambda t: t["ms"])

    by_label = {p.label: p for p in plan_ir.enumerate_pattern_programs(
        pattern, **enum_kw)}
    n = comm.size
    raw = autotune._probe_tree(payload, n, seed=1)
    data = autotune._place(raw, comm.mesh, (comm.axis_name,))

    def arm(program):
        return (autotune.build_pattern_probe_fn(
            comm.mesh, comm.axis_name, pattern, program, **probe_kw),
            data)

    times = _retime_arms(
        {"tuned": arm(plan_ir.ensure_program(plan, pattern)),
         "worst": arm(by_label[worst["label"]])}, rounds, iters)

    plan2 = autotune.autotune_pattern_plan(
        comm, payload, pattern=pattern, cache_path=cache_path,
        trials=trials, top_k=top_k, **tune_kw)
    assert plan2.from_cache, f"{pattern}: second run missed the cache"
    assert plan2.n_probes == 0, \
        f"{pattern}: cache hit still ran {plan2.n_probes} probes"
    assert plan2.program == plan.program, \
        f"{pattern}: cached program differs from the tuned one"

    return {
        "speedup": times["worst"] / times["tuned"],
        "tuned_ms": times["tuned"],
        "worst_ms": times["worst"],
        "tuned_label": plan.strategy,
        "worst_label": worst["label"],
        "n_enumerated": plan.meta["n_enumerated"],
        "n_probed": plan.meta["n_probed"],
        "first_run_probes": plan.n_probes,
        "second_run_probes": plan2.n_probes,
        "second_run_cached": plan2.from_cache,
        "tune_seconds": tune_s,
    }


def run(n_layers=48, d_model=32, vocab=2048, capacity=16, slot_dim=64,
        trials=3, rounds=3, iters=3, top_k=6):
    import jax
    import numpy as np

    import chainermn_tpu as cmn

    comm = cmn.create_communicator("tpu_xla")
    n = comm.size

    rng = np.random.RandomState(0)
    tree = make_local_param_tree(rng, n_layers, d_model, vocab,
                                 np.float32)
    leaves = jax.tree.leaves(tree)
    dims = jax.tree.map(lambda _: 0, tree)
    slots = rng.randn(n, capacity, slot_dim).astype(np.float32)

    cache_path = os.path.join(
        tempfile.mkdtemp(prefix="plan_ir_bench_"), "plan_cache.json")

    fsdp = _tune_and_race(
        comm, "fsdp_gather", tree, cache_path, trials=trials,
        rounds=rounds, iters=iters, top_k=top_k,
        enum_kw={"wire_dtypes": (None, "bfloat16")},
        tune_kw={"dims": dims, "wire_dtypes": (None, "bfloat16")},
        probe_kw={"dims": dims})
    moe = _tune_and_race(
        comm, "moe_all_to_all", slots, cache_path, trials=trials,
        rounds=rounds, iters=iters, top_k=top_k,
        enum_kw={"shape": slots.shape, "split_axis": 0,
                 "concat_axis": 1},
        tune_kw={"split_axis": 0, "concat_axis": 1},
        probe_kw={"split_axis": 0, "concat_axis": 1})

    value = min(fsdp["speedup"], moe["speedup"])
    result = {
        "metric": METRIC,
        "value": round(value, 3),
        "unit": UNIT,
        "vs_baseline": round(value, 3),
        "fsdp_speedup": round(fsdp["speedup"], 3),
        "moe_speedup": round(moe["speedup"], 3),
        "n_devices": n,
        "n_leaves": len(leaves),
        "total_mb": round(sum(l.size * l.dtype.itemsize
                              for l in leaves) / 2**20, 2),
        "slots_shape": "x".join(str(s) for s in slots.shape),
        "n_leaves_config": f"{n_layers}x{d_model}",
        "device_kind": jax.devices()[0].device_kind,
    }
    for name, r in (("fsdp", fsdp), ("moe", moe)):
        for k in ("tuned_ms", "worst_ms", "tune_seconds"):
            result[f"{name}_{k}"] = round(r[k], 3)
        for k in ("tuned_label", "worst_label", "n_enumerated",
                  "n_probed", "first_run_probes", "second_run_probes",
                  "second_run_cached"):
            result[f"{name}_{k}"] = r[k]
    return result


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
                 vocab=args.vocab, capacity=args.capacity,
                 slot_dim=args.slot_dim, trials=args.trials,
                 rounds=args.rounds, iters=args.iters,
                 top_k=args.top_k)
    print("BENCH_RESULT " + json.dumps(result))


def _parent_main(args):
    here = os.path.abspath(__file__)
    cmd = [sys.executable, here, "--child",
           "--n-layers", str(args.n_layers),
           "--d-model", str(args.d_model), "--vocab", str(args.vocab),
           "--capacity", str(args.capacity),
           "--slot-dim", str(args.slot_dim),
           "--trials", str(args.trials), "--rounds", str(args.rounds),
           "--iters", str(args.iters), "--top-k", str(args.top_k),
           "--devices", str(args.devices)]
    if args.platform:
        cmd += ["--platform", args.platform]
    return run_child_with_retries(
        cmd, os.path.dirname(here), args.timeouts, METRIC, UNIT,
        record=args.platform is None,
        match={"n_leaves_config": f"{args.n_layers}x{args.d_model}"},
        check=args.check)


def _parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", action="store_true")
    p.add_argument("--n-layers", type=int, default=48)
    p.add_argument("--d-model", type=int, default=32)
    p.add_argument("--vocab", type=int, default=2048)
    p.add_argument("--capacity", type=int, default=16,
                   help="MoE slots per expert (C of the E,C,D payload)")
    p.add_argument("--slot-dim", type=int, default=64,
                   help="MoE slot feature dim (D of the E,C,D payload)")
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
    p.add_argument("--check", action="store_true",
                   help="perf-regression sentinel: score the fresh "
                        "record against the run history's prior "
                        "same-workload runs; the verdict rides the "
                        "JSON line under 'check' and the exit code is "
                        "1 on a regression verdict")
    return p.parse_args(argv)


if __name__ == "__main__":
    args = _parse_args(sys.argv[1:])
    if args.child:
        _child_main(args)
    else:
        sys.exit(_parent_main(args))
