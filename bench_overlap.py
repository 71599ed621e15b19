"""Backward-overlapped vs window-end gradient exchange benchmark.

Both arms run the same microbatch stream through the same model on the
8-device mesh and differ only in the exchange lowering:

- **window** — the PR 4 shape: ``create_multi_node_optimizer()``
  defaults (fused dtype-grouped arena buckets), one window-end exchange
  whose arena concat JOINS every gradient leaf — the compiled schedule
  clusters the exchange collectives after the last backward op
  (``assert_overlap_collectives`` rejecting this arm is asserted below:
  a baseline that accidentally overlaps would void the measurement).
- **overlap** — ``overlap=True`` with a schedule-bearing plan: the
  schedule-aware AUTOTUNED one (``autotune_plan(overlap=True,
  t_bwd_s=<measured>)`` — bucket boundaries × eager/deferred ×
  rs-vs-ar per bucket, probed live, ranked by modeled exposed wire
  time under the measured backward) or the analytic leaf-aligned
  ``ar`` stream, whichever a short IN-STEP probe times faster —
  isolated probes cannot price the in-step cast/copy costs this
  backend exposes (XLA:CPU widens bf16 collectives to f32, so the
  "compressed" wire is pure cast overhead here), and the honest arm is
  the better of the two, with both timings recorded.  The winner's
  reverse-layer bucket stream fires under the backward pass
  (``assert_overlap_collectives`` passing this arm — with the
  schedule-position evidence and ``async_depth`` — is the overlap
  proof).

A synchronous-collective backend note, so the recorded number is read
for what it is: XLA:CPU emits no async start/done pairs
(``async_depth`` 0), every rank's thread executes its share of every
collective serially, and schedule position alone cannot hide wire
time the way a TPU's async collectives do.  What the CPU mesh DOES
measure is the lowering half of the win: the window-end arena pays a
pack + unpack copy of the whole gradient tree, while the overlap
stream's contiguous reverse-layer buckets ride leaf storage directly
— real steps/sec, biggest where the exchange dominates compute (the
default small-batch config).  The schedule half (wire under compute)
is what ``assert_overlap_collectives`` proves structurally.

The plan-cache round-trip is asserted for the schedule-bearing plan (a
second ``autotune_plan`` call must serve from cache with ZERO probes),
and a ``StragglerReport`` runs over each arm's timed spans so per-phase
skew rides the record alongside the throughput.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", ...}:
value = overlap steps/sec ÷ window steps/sec (unit "x").  Same
hermetic child-process timeout/retry pattern as bench.py.
"""

import argparse
import json
import os
import sys
import tempfile
import time

from _bench_common import pin_platform, run_child_with_retries

METRIC = "overlap_exchange_speedup"
UNIT = "x"


def run(batch=8, dim=768, hidden=768, n_layers=8, classes=10,
        n_examples=4096, accum_steps=1, warmup=4, iters=24, rounds=3,
        trials=2, top_k=6, min_frac=0.5):
    import jax
    import numpy as np
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.models import (init_mlp, mlp_apply,
                                      softmax_cross_entropy)
    from chainermn_tpu.utils import (
        StragglerReport,
        TraceRecorder,
        assert_overlap_collectives,
        autotune_plan,
        set_recorder,
    )

    comm = cmn.create_communicator("tpu_xla")
    rng = np.random.RandomState(0)
    X = rng.randn(n_examples, dim).astype(np.float32)
    Y = (rng.rand(n_examples) * classes).astype(np.int32)

    def loss_fn(p, x, y):
        return softmax_cross_entropy(mlp_apply(p, x), y)

    params0 = init_mlp(jax.random.PRNGKey(0),
                       [dim] + [hidden] * n_layers + [classes])
    grad_bytes = sum(l.size * l.dtype.itemsize
                     for l in jax.tree.leaves(params0))

    def make(opt_kw):
        it = cmn.SerialIterator((X, Y), batch, shuffle=True, seed=11)
        opt = cmn.create_multi_node_optimizer(optax.sgd(0.05), comm,
                                              **opt_kw)
        return cmn.StandardUpdater(it, opt, loss_fn, params0, comm,
                                   accum_steps=accum_steps)

    # -- hiding budget: measured wall time of the window arm's step --- #
    probe = make({})
    probe.update()                                  # compile
    jax.block_until_ready(probe.params)
    t0 = time.perf_counter()
    for _ in range(2):
        probe.update()
    jax.block_until_ready(probe.params)
    t_bwd_s = (time.perf_counter() - t0) / (2 * accum_steps)

    # -- schedule-aware autotune + plan-cache round-trip -------------- #
    cache = os.path.join(tempfile.mkdtemp(prefix="bench_overlap_"),
                         "plans.json")
    tuned = autotune_plan(comm, params0, overlap=True, t_bwd_s=t_bwd_s,
                          cache_path=cache, trials=trials, top_k=top_k)
    again = autotune_plan(comm, params0, overlap=True, t_bwd_s=t_bwd_s,
                          cache_path=cache, trials=trials, top_k=top_k)
    if not (again.from_cache and again.n_probes == 0
            and again.schedule == tuned.schedule):
        raise AssertionError(
            f"schedule-bearing plan did not round-trip the cache: "
            f"from_cache={again.from_cache} n_probes={again.n_probes}")

    # -- in-step selection: tuned plan vs analytic leaf-aligned stream  #
    from chainermn_tpu.ops.fused import build_overlap_schedule
    from chainermn_tpu.utils.autotune import Plan

    max_leaf = max(l.size * l.dtype.itemsize
                   for l in jax.tree.leaves(params0))
    analytic = Plan(
        strategy="overlap", bucket_bytes=max_leaf,
        schedule=[dict(e, via="ar") for e in
                  build_overlap_schedule(params0, max_leaf)])

    def quick_steps(plan_arm):
        upd = make({"plan": plan_arm, "overlap": True})
        for _ in range(2):
            upd.update()
        jax.block_until_ready(upd.params)
        q = max(4, iters // 4)
        t0 = time.perf_counter()
        for _ in range(q):
            upd.update()
        jax.block_until_ready(upd.params)
        return q * accum_steps / (time.perf_counter() - t0)

    quick = {"tuned": quick_steps(tuned),
             "analytic_leaf_stream": quick_steps(analytic)}
    plan_source = max(quick, key=quick.get)
    plan = tuned if plan_source == "tuned" else analytic

    # -- proofs: overlap arm overlaps, window arm does NOT ------------ #
    def compile_window(upd):
        arrays, _k, _tail = upd._assemble_host_window()
        fn = upd._get_step(len(arrays), 1, accum_steps)
        carry = (upd.params, upd.state, upd.opt_state)
        return fn.lower(carry, *arrays).compile()

    overlap_kw = {"plan": plan, "overlap": True}
    rep = assert_overlap_collectives(compile_window(make(overlap_kw)),
                                     min_frac=min_frac)
    # the baseline's fraction is REPORTED, not gated: under an accum
    # scan it is structurally 0 (every backward dot lives in the while
    # body), but at accum_steps=1 XLA's slice-of-concat simplification
    # can partially un-join the arena and overlap some buckets on its
    # own — that is the real PR 4 baseline, and hiding it would
    # overstate the win
    base_rep = assert_overlap_collectives(compile_window(make({})),
                                          min_frac=0.0)

    # -- timing: interleaved rounds, best-of, skew recorded ----------- #
    recorder = TraceRecorder(capacity=1 << 16, enabled=True,
                             rank=getattr(comm, "rank", 0))
    prev = set_recorder(recorder)
    straggler = StragglerReport(comm, recorder=recorder, write=False)
    skew = {}
    try:
        def timed_arm(name, opt_kw):
            upd = make(opt_kw)
            for _ in range(warmup):
                upd.update()
            jax.block_until_ready(upd.params)
            recorder.drain_phase_stats(None)        # fresh interval
            start_iter = upd.iteration
            t0 = time.perf_counter()
            for _ in range(iters):
                upd.update()
            jax.block_until_ready(upd.params)
            dt = time.perf_counter() - t0
            straggler(None)
            skew[name] = straggler.last_report["max_skew"]
            return (upd.iteration - start_iter) / dt

        best = {"window": 0.0, "overlap": 0.0}
        for _ in range(rounds):
            best["window"] = max(best["window"],
                                 timed_arm("window", {}))
            best["overlap"] = max(best["overlap"],
                                  timed_arm("overlap", overlap_kw))
    finally:
        set_recorder(prev)

    speedup = best["overlap"] / best["window"]
    return {
        "metric": METRIC,
        "value": round(speedup, 3),
        "unit": UNIT,
        "vs_baseline": round(speedup, 3),
        "window_steps_per_s": round(best["window"], 2),
        "overlap_steps_per_s": round(best["overlap"], 2),
        "overlap_proof": {k: round(v, 4) if isinstance(v, float) else v
                          for k, v in rep.items()},
        "window_end_frac": round(base_rep["frac"], 4),
        "plan": {
            "source": plan_source,
            "strategy": plan.strategy,
            "bucket_bytes": plan.bucket_bytes,
            "wire_dtype": plan.wire_dtype,
            "n_buckets": len(plan.schedule or []),
            "modes": [e["mode"] for e in plan.schedule or []],
            "via": [e["via"] for e in plan.schedule or []],
        },
        "in_step_probe_steps_per_s": {k: round(v, 2)
                                      for k, v in quick.items()},
        "plan_cache_roundtrip": True,
        "t_bwd_s": round(t_bwd_s, 5),
        "straggler_skew": {k: round(v, 4) for k, v in skew.items()},
        "grad_bytes": grad_bytes,
        "accum_steps": accum_steps,
        "batch": batch,
        "dim": dim,
        "hidden": hidden,
        "n_layers": n_layers,
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
                 n_layers=args.n_layers, accum_steps=args.accum_steps,
                 warmup=args.warmup, iters=args.iters,
                 rounds=args.rounds, trials=args.trials,
                 top_k=args.top_k, min_frac=args.min_frac)
    print("BENCH_RESULT " + json.dumps(result))


def _parent_main(args):
    here = os.path.abspath(__file__)
    cmd = [sys.executable, here, "--child",
           "--batch", str(args.batch), "--dim", str(args.dim),
           "--hidden", str(args.hidden),
           "--n-layers", str(args.n_layers),
           "--accum-steps", str(args.accum_steps),
           "--warmup", str(args.warmup), "--iters", str(args.iters),
           "--rounds", str(args.rounds), "--trials", str(args.trials),
           "--top-k", str(args.top_k),
           "--min-frac", str(args.min_frac),
           "--devices", str(args.devices)]
    if args.platform:
        cmd += ["--platform", args.platform]
    return run_child_with_retries(
        cmd, os.path.dirname(here), args.timeouts, METRIC, UNIT,
        record=args.platform is None,
        match={"batch": args.batch, "dim": args.dim,
               "hidden": args.hidden, "n_layers": args.n_layers,
               "accum_steps": args.accum_steps})


def _parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", action="store_true")
    p.add_argument("--batch", type=int, default=8,
                   help="1 example/device: the exchange-dominated "
                        "regime where the lowering difference is "
                        "what's measured")
    p.add_argument("--dim", type=int, default=768)
    p.add_argument("--hidden", type=int, default=768,
                   help="sub-arena-bucket layer width: every leaf "
                        "rides the window arm's arena, so the baseline "
                        "really is the clustered window-end join")
    p.add_argument("--n-layers", type=int, default=8)
    p.add_argument("--accum-steps", type=int, default=1,
                   help="microbatches per window (the peel regime; "
                        "bench_accum.py owns the M-amortisation claim)")
    p.add_argument("--warmup", type=int, default=4)
    p.add_argument("--iters", type=int, default=24,
                   help="timed updates per round per arm")
    p.add_argument("--rounds", type=int, default=3,
                   help="interleaved timing rounds (best round counts)")
    p.add_argument("--trials", type=int, default=2,
                   help="autotune probe repetitions per candidate")
    p.add_argument("--top-k", type=int, default=6)
    p.add_argument("--min-frac", type=float, default=0.5,
                   help="overlap-proof floor: fraction of exchange "
                        "collectives that must start inside the "
                        "backward region")
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
