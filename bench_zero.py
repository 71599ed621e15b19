"""Unified sharded-state benchmark: what does ZeRO-3 actually buy per
chip, and does the tuned layer-gather exchange win?

Two claims, one JSON line:

1. **Resident bytes per chip** — a transformer param tree is held two
   ways: pure DP (params, grads, and adam state replicated on every
   chip) and ZeRO-3 (``ShardedState.place`` + ``shard_opt_state`` +
   sharded grads — everything 1/world at rest).  Both are registered
   with the ``MemoryAccountant`` and SAMPLED, not asserted from
   arithmetic; ``value`` = DP bytes/chip ÷ ZeRO-3 bytes/chip (the
   ISSUE's acceptance floor is 2×; with every leaf dim-shardable it
   lands near the world size).
2. **Tuned vs worst exchange** — ``ShardedState.tune_gather_plan``
   searches the ``fsdp_gather`` plan-IR programs for this layout; the
   winner and the worst parity-clean candidate are re-timed fresh in
   the interleaved min-of-rounds harness (``exchange_speedup`` =
   worst / tuned, same discipline as bench_plan_ir).

The cache claim is asserted structurally: a second ``ShardedState``
tuning against the same scratch cache must come back ``from_cache=True``
with ``n_probes == 0`` and a bit-identical program.
"""

import argparse
import json
import os
import sys
import tempfile
import time

from _bench_common import pin_platform, run_child_with_retries

METRIC = "zero3_resident_bytes_reduction"
UNIT = "x"


def make_param_tree(rng, n_layers, d_model, vocab, dtype):
    """FULL (global) transformer-shaped params; every dim a multiple of
    the world so ``fsdp_dims`` shards every leaf."""
    def leaf(*shape):
        return rng.randn(*shape).astype(dtype) * 0.02

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


def _measure_resident_bytes(comm, params, optimizer):
    """Accountant-sampled resident param+grad+opt bytes per chip for
    pure DP vs ZeRO-3 — the gauges /programz would show, not pencil
    arithmetic."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu.parallel.sharded_state import ShardedState
    from chainermn_tpu.training.optimizers import shard_opt_state
    from chainermn_tpu.utils.programs import MemoryAccountant

    n = comm.size
    acc = MemoryAccountant()

    sharded = ShardedState(params, comm)
    sharded.place(params)
    sharded.init_opt_state(optimizer)
    sharded.register_memory(acc, prefix="zero3")
    z3_grads = jax.tree.map(
        lambda p, s: jax.device_put(jnp.zeros_like(p),
                                    NamedSharding(comm.mesh, s)),
        params, sharded.specs)
    acc.register("zero3_grads", z3_grads)

    dp_params = jax.tree.map(
        lambda p: jax.device_put(p, NamedSharding(comm.mesh, P())),
        params)
    acc.register("dp_params", dp_params)
    acc.register("dp_opt_state", shard_opt_state(optimizer, dp_params))
    acc.register("dp_grads", jax.tree.map(jnp.zeros_like, dp_params))

    sample = acc.sample()
    z3 = sum(sample[k] for k in
             ("zero3_params", "zero3_opt_state", "zero3_grads")) / n
    dp = sum(sample[k] for k in
             ("dp_params", "dp_opt_state", "dp_grads")) / n
    # analytic per-chip claim off the layout table: params + opt state
    # (sharded.local_bytes) plus grads, which mirror the param layout
    predicted = sharded.local_bytes() + sum(
        l.local_bytes() for l in sharded.layouts()["params"])
    return sharded, dp, z3, predicted


def _race_exchange(comm, sharded, cache_path, *, trials, rounds, iters,
                   top_k):
    """Tune the layer-gather plan through the sharded-state surface,
    re-time tuned vs the worst parity-clean candidate, and assert the
    second tuning is 100% cache-served."""
    import numpy as np

    from chainermn_tpu.ops import plan_ir
    from chainermn_tpu.parallel.sharded_state import ShardedState
    from chainermn_tpu.utils import autotune

    t0 = time.perf_counter()
    plan = sharded.tune_gather_plan(comm, cache_path=cache_path,
                                    trials=trials, top_k=top_k)
    tune_s = time.perf_counter() - t0
    assert not plan.from_cache and plan.n_probes > 0
    ok = [t for t in plan.meta["timings"] if t["parity_ok"]]
    worst = max(ok, key=lambda t: t["ms"])

    by_label = {p.label: p for p in plan_ir.enumerate_pattern_programs(
        "fsdp_gather", wire_dtypes=(None,))}
    raw = autotune._probe_tree(sharded.local_template(), comm.size,
                               seed=1)
    data = autotune._place(raw, comm.mesh, (comm.axis_name,))

    def arm(program):
        return (autotune.build_pattern_probe_fn(
            comm.mesh, comm.axis_name, "fsdp_gather", program,
            dims=sharded.dims), data)

    times = _retime_arms(
        {"tuned": arm(plan_ir.ensure_program(plan, "fsdp_gather")),
         "worst": arm(by_label[worst["label"]])}, rounds, iters)

    again = ShardedState(sharded.params, comm).tune_gather_plan(
        comm, cache_path=cache_path, trials=trials, top_k=top_k)
    assert again.from_cache, "second tuning missed the plan cache"
    assert again.n_probes == 0, \
        f"cache hit still ran {again.n_probes} probes"
    assert again.program == plan.program, \
        "cached program differs from the tuned one"

    return {
        "speedup": times["worst"] / times["tuned"],
        "tuned_ms": times["tuned"],
        "worst_ms": times["worst"],
        "tuned_label": plan.strategy,
        "worst_label": worst["label"],
        "n_enumerated": plan.meta["n_enumerated"],
        "n_probed": plan.meta["n_probed"],
        "first_run_probes": plan.n_probes,
        "second_run_probes": again.n_probes,
        "second_run_cached": again.from_cache,
        "tune_seconds": tune_s,
    }


def run(n_layers=8, d_model=256, vocab=4096, trials=3, rounds=3,
        iters=3, top_k=6):
    import jax
    import numpy as np
    import optax

    import chainermn_tpu as cmn

    comm = cmn.create_communicator("tpu_xla")
    n = comm.size

    rng = np.random.RandomState(0)
    params = make_param_tree(rng, n_layers, d_model, vocab, np.float32)
    n_params = sum(l.size for l in jax.tree.leaves(params))

    sharded, dp_bytes, z3_bytes, predicted = _measure_resident_bytes(
        comm, params, optax.adam(1e-3))
    reduction = dp_bytes / z3_bytes
    assert reduction >= 2.0, (
        f"ZeRO-3 resident bytes/chip only {reduction:.2f}x below pure "
        f"DP — the sharded-state layer is not shedding state")

    cache_path = os.path.join(
        tempfile.mkdtemp(prefix="zero_bench_"), "plan_cache.json")
    race = _race_exchange(comm, sharded, cache_path, trials=trials,
                          rounds=rounds, iters=iters, top_k=top_k)

    result = {
        "metric": METRIC,
        "value": round(reduction, 3),
        "unit": UNIT,
        "vs_baseline": round(reduction, 3),
        "dp_bytes_per_chip": int(dp_bytes),
        "zero3_bytes_per_chip": int(z3_bytes),
        "zero3_predicted_bytes_per_chip": int(predicted),
        "exchange_speedup": round(race["speedup"], 3),
        "n_devices": n,
        "n_params": int(n_params),
        "model_config": f"{n_layers}x{d_model}x{vocab}",
        "device_kind": jax.devices()[0].device_kind,
    }
    for k in ("tuned_ms", "worst_ms", "tune_seconds"):
        result[f"exchange_{k}"] = round(race[k], 3)
    for k in ("tuned_label", "worst_label", "n_enumerated", "n_probed",
              "first_run_probes", "second_run_probes",
              "second_run_cached"):
        result[f"exchange_{k}"] = race[k]
    return result


def _child_main(args):
    env_platform = os.environ.get("JAX_PLATFORMS", "")
    if args.platform == "cpu" or (
            args.platform is None and env_platform.startswith("cpu")):
        # fake the multi-chip world BEFORE backend init (same trick as
        # tests/conftest.py) so the sharding is real, not size-1
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
        match={"model_config":
               f"{args.n_layers}x{args.d_model}x{args.vocab}"},
        check=args.check)


def _parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", action="store_true")
    p.add_argument("--n-layers", type=int, default=8)
    p.add_argument("--d-model", type=int, default=256)
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
