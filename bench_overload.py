"""Overload benchmark: SLO-driven admission control + deadline
scheduling vs plain FCFS, on goodput-under-SLO.

The trace is open-loop Poisson at λ > capacity — the normal state of a
popular service, and the regime where "accept everything, serve in
arrival order" collapses: the queue grows without bound, every
request's wait inflates past its deadline, and capacity is spent
generating tokens nobody is still waiting for.  Both arms run the SAME
engine, programs, model and request trace; only the overload policy
differs:

- **fcfs** — the PR 8 engine as it was: unbounded queue, no
  deadlines enforced, first-come-first-served.  Every request is
  eventually served (high raw throughput!), mostly too late.
- **shed** — requests carry a deadline (arrival + a per-request SLO
  target calibrated from the unloaded service time), an
  ``AdmissionController`` fast-rejects what the live TTFT/TPOT
  service-time prediction says cannot make it (plus a bounded queue),
  and the ``"deadline"`` policy admits tightest-slack-first.

The scoreboard is ``SLOReport``'s attainment/goodput column: a request
counts iff it was FULLY served within its target, and goodput is the
attained requests' tokens over the arm's makespan.  Raw tokens/s is
reported too — shedding deliberately LOSES that metric; the point is
it wins the one users feel.  Token identity of everything served is
verified against an engine-independent plain-loop oracle (exact for
completions, prefix for mid-stream timeouts) — admission control must
change WHO is served, never WHAT.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", ...}:
value = shed/fcfs goodput-under-SLO ratio (unit "x", >1 means the
admission layer wins).  One child under a timeout, the parent off JAX
(``_bench_common``).
"""

import argparse
import json
import os
import sys
import time

from _bench_common import pin_platform, run_child_with_retries

METRIC = "serving_overload_goodput_shed_vs_fcfs"
UNIT = "x"


def _make_trace(rng, args):
    """(arrival_offset_s, prompt, max_new) per request."""
    import numpy as np

    gaps = rng.exponential(args.arrival_ms / 1e3, args.requests)
    arrivals = np.cumsum(gaps)
    return [
        (float(arrivals[i]),
         rng.randint(0, args.vocab,
                     rng.randint(args.min_prompt, args.max_prompt + 1)),
         int(rng.randint(args.min_new, args.max_new + 1)))
        for i in range(args.requests)
    ]


def _make_oracle(adapter, params):
    """Plain-loop greedy decode over the adapter's pure step/prefill —
    no engine code, no shard_map (the tests' oracle, inlined)."""
    import jax.numpy as jnp
    import numpy as np

    cache = {}

    def run(prompt, max_new):
        key = (bytes(np.asarray(prompt, np.int32)), int(max_new))
        if key in cache:
            return cache[key]
        prompt = np.asarray(prompt, np.int32)
        p = prompt.shape[0]
        caches = adapter.make_cache(1, p + max_new)
        offs = jnp.zeros((1,), jnp.int32)
        if p > 1:
            caches = adapter.prefill(
                params, caches, jnp.asarray(prompt[None, :p - 1]), offs)
        tok = jnp.asarray(prompt[-1:], jnp.int32)
        out = []
        for t in range(p - 1, p - 1 + max_new):
            logits, caches = adapter.step(params, caches, tok,
                                          jnp.int32(t), offs)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            out.append(int(tok[0]))
        cache[key] = np.asarray(out, np.int32)
        return cache[key]

    return run


def _replay(engine, trace, deadlines=None):
    """Open-loop replay.  ``deadlines``: per-request relative SLO
    budget in seconds (the shed arm submits with ``timeout=``); None =
    no deadlines (the fcfs arm).  Returns (terminal_records,
    makespan_s) — completions AND sheds, makespan from first arrival
    to the last terminal event."""
    terminals = []
    t0 = time.perf_counter() - trace[0][0]
    pending = list(enumerate(trace))
    from chainermn_tpu.serving import ShedCompletion

    while pending or not engine.idle:
        now = time.perf_counter() - t0
        while pending and pending[0][1][0] <= now:
            i, (_, prompt, max_new) = pending.pop(0)
            kw = {}
            if deadlines is not None:
                kw["timeout"] = deadlines[i]
            r = engine.submit(prompt, max_new=max_new, **kw)
            if isinstance(r, ShedCompletion):
                terminals.append(r)
        if not engine.idle:
            terminals.extend(engine.step())
        elif pending:
            time.sleep(min(1e-3, max(0.0, pending[0][1][0] - now)))
    t_end = max(getattr(c, "t_done", None) or c.t_shed
                for c in terminals)
    return terminals, t_end - t0 - trace[0][0]


def _calibrate(engine, trace):
    """Two unloaded waves: the first eats every compile (prefill /
    admit / round via ``warm()``) and is DISCARDED; the
    second measures the warmed, no-queue TTFT/TPOT that the SLO
    targets (and the predictor prior) are derived from — a target
    calibrated against compile time would be generous enough to make
    overload invisible."""
    import numpy as np

    wave = [(t[1], min(t[2], 8)) for t in trace[:engine.n_slots]]
    for p, n in wave:
        engine.submit(p, max_new=n)
    engine.run(max_steps=2000)
    engine.warm()
    engine.reset()
    for p, n in wave:
        engine.submit(p, max_new=n)
    comps = engine.run(max_steps=2000)
    ttft = float(np.median([c.ttft for c in comps]))
    tpot = float(np.median([c.tpot for c in comps]))
    records = [(c.ttft, c.tpot) for c in comps]
    engine.reset()
    return ttft, tpot, records


def _score(arm, records, slo_by_rid, makespan, percentiles=(50, 99)):
    from chainermn_tpu.serving import SLOReport

    slo = SLOReport(percentiles=percentiles)
    slo.add_arm(arm, records,
                slo=lambda r: slo_by_rid.get(getattr(r, "rid", None)))
    s = slo.summary()[arm]
    score = s["slo"]
    tokens = sum(getattr(r, "n_generated", 0) for r in records)
    return {
        "goodput_tokens_per_sec": score["goodput_tokens"] / makespan,
        "attainment": score["attainment"],
        "attained": score["attained"],
        "scored": score["scored"],
        "shed": score["shed"],
        "goodput_tokens": score["goodput_tokens"],
        "raw_tokens_per_sec": tokens / makespan,
        "e2e_p50_ms": (s["e2e"]["p50"] or 0.0) * 1e3,
        "makespan_s": makespan,
    }


def _verify_tokens(records, trace, oracle):
    """Engine-independent identity check: exact tokens for fully
    served requests, oracle-prefix for mid-stream timeouts.  Returns
    (checked, mismatches)."""
    import numpy as np

    by_idx = {f"r{i}": (t[1], t[2]) for i, t in enumerate(trace)}
    checked = mismatches = 0
    for r in records:
        status = getattr(r, "status", "shed")
        if status == "shed" or r.rid not in by_idx:
            continue
        prompt, max_new = by_idx[r.rid]
        want = oracle(prompt, max_new)
        if status == "ok":
            checked += 1
            if not np.array_equal(r.tokens, want):
                mismatches += 1
        elif status == "timeout":
            checked += 1
            if not np.array_equal(r.tokens, want[:r.n_generated]):
                mismatches += 1
    return checked, mismatches


def run(args):
    import jax
    import numpy as np

    from chainermn_tpu.parallel import MeshConfig
    from chainermn_tpu.serving import (
        AdmissionController, MiniLMAdapter, MiniLMConfig, ServingEngine,
        ServiceTimePredictor, init_minilm,
    )

    cfg = MiniLMConfig(
        vocab_size=args.vocab, d_model=args.d_model,
        n_heads=args.heads, d_head=args.d_model // args.heads,
        d_ff=2 * args.d_model, n_layers=args.n_layers,
        max_pos=args.horizon)
    n_dev = min(args.slots, jax.device_count())
    mc = MeshConfig(data=n_dev, devices=jax.devices()[:n_dev])
    params = init_minilm(jax.random.PRNGKey(0), cfg)
    adapter = MiniLMAdapter(mc, cfg)
    engine = ServingEngine(
        adapter, params, n_slots=args.slots, horizon=args.horizon,
        max_prompt=args.max_prompt, block=args.block,
        round_tokens=args.round_tokens)

    rng = np.random.RandomState(args.seed)
    trace = _make_trace(rng, args)

    cal_ttft, cal_tpot, cal_records = _calibrate(engine, trace)
    # per-request SLO target: headroom × the UNLOADED service time —
    # generous when nothing queues, fatal once the backlog inflates
    # waits past headroom×service (which λ > capacity guarantees)
    slo_rel = [args.slo_headroom * (cal_ttft + cal_tpot * (n - 1))
               for _, _, n in trace]
    slo_by_rid = {f"r{i}": s for i, s in enumerate(slo_rel)}
    # offered vs serviceable load: the overload claim, made explicit
    mean_new = float(np.mean([n for _, _, n in trace]))
    offered = mean_new / (args.arrival_ms / 1e3)
    capacity = args.slots / cal_tpot

    def make_controller():
        pred = ServiceTimePredictor(quantile=args.quantile)
        for t, p in cal_records:
            pred.observe_ttft(t)
            # calibration ran unloaded (no queue), so its TTFT IS the
            # queue-free service time: prime the split predictor's
            # service stream too, and the deadline check models the
            # LIVE queue instead of inheriting calibration-era waits
            pred.observe_service_ttft(t)
            pred.observe_tpot(p)
        return AdmissionController(
            max_queue=args.max_queue or None, predictor=pred)

    arms = {}
    order = ("fcfs", "shed")
    for rnd in range(args.rounds):
        for arm in (order if rnd % 2 == 0 else order[::-1]):
            engine.reset()
            if arm == "shed":
                # fresh controller per round: every round starts from
                # the same calibration prior, then learns live
                engine.admission = make_controller()
                engine.set_policy("deadline")
                records, makespan = _replay(engine, trace,
                                            deadlines=slo_rel)
            else:
                engine.admission = None
                engine.set_policy("fcfs")
                records, makespan = _replay(engine, trace)
            assert len(records) == args.requests, (arm, len(records))
            stats = _score(arm, records, slo_by_rid, makespan)
            stats["timeouts"] = engine.stats()["timeouts"]
            stats["shed_reasons"] = engine.stats()["shed"]
            if arm not in arms or stats["goodput_tokens_per_sec"] \
                    > arms[arm]["goodput_tokens_per_sec"]:
                arms[arm] = stats
                arms[arm]["records"] = records
    engine.admission = None

    oracle = _make_oracle(adapter, params)
    checked = mismatches = 0
    for arm in order:
        c, m = _verify_tokens(arms[arm].pop("records"), trace, oracle)
        checked += c
        mismatches += m

    f, s = arms["fcfs"], arms["shed"]
    ratio = (s["goodput_tokens_per_sec"]
             / max(f["goodput_tokens_per_sec"], 1e-9))
    return {
        "metric": METRIC,
        "value": round(ratio, 3),
        "unit": UNIT,
        "vs_baseline": round(ratio, 3),
        "shed_goodput_tokens_per_sec":
            round(s["goodput_tokens_per_sec"], 1),
        "fcfs_goodput_tokens_per_sec":
            round(f["goodput_tokens_per_sec"], 1),
        "shed_raw_tokens_per_sec": round(s["raw_tokens_per_sec"], 1),
        "fcfs_raw_tokens_per_sec": round(f["raw_tokens_per_sec"], 1),
        "shed_attainment": round(s["attainment"], 3),
        "fcfs_attainment": round(f["attainment"], 3),
        "shed_attained": s["attained"],
        "fcfs_attained": f["attained"],
        "shed_count": s["shed"],
        "shed_timeouts": s["timeouts"],
        "shed_reasons": s["shed_reasons"],
        "shed_makespan_s": round(s["makespan_s"], 3),
        "fcfs_makespan_s": round(f["makespan_s"], 3),
        "fcfs_e2e_p50_ms": round(f["e2e_p50_ms"], 1),
        "shed_e2e_p50_ms": round(s["e2e_p50_ms"], 1),
        "token_checks": checked,
        "token_identity_mismatches": mismatches,
        "offered_tokens_per_sec": round(offered, 1),
        "capacity_tokens_per_sec_est": round(capacity, 1),
        "overloaded": bool(offered > capacity),
        "cal_ttft_ms": round(cal_ttft * 1e3, 2),
        "cal_tpot_ms": round(cal_tpot * 1e3, 3),
        "slo_headroom": args.slo_headroom,
        "quantile": args.quantile,
        "max_queue": args.max_queue,
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": jax.device_count(),
        "requests": args.requests,
        "slots": args.slots,
        "horizon": args.horizon,
        "block": args.block,
        "max_prompt": args.max_prompt,
        "min_new": args.min_new,
        "max_new": args.max_new,
        "round_tokens": args.round_tokens,
        "arrival_ms": args.arrival_ms,
        "d_model": args.d_model,
        "n_layers": args.n_layers,
        "seed": args.seed,
        "rounds": args.rounds,
    }


def _child_main(args):
    env_platform = os.environ.get("JAX_PLATFORMS", "")
    if args.platform == "cpu" or (
            args.platform is None and env_platform.startswith("cpu")):
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count"
                        f"={args.devices}").strip()
    pin_platform(args.platform)
    print("BENCH_RESULT " + json.dumps(run(args)))


def main(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", action="store_true")
    p.add_argument("--requests", type=int, default=100)
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--horizon", type=int, default=288)
    p.add_argument("--block", type=int, default=16)
    p.add_argument("--max-prompt", type=int, default=32)
    p.add_argument("--min-prompt", type=int, default=4)
    p.add_argument("--min-new", type=int, default=8)
    p.add_argument("--max-new", type=int, default=48)
    p.add_argument("--round-tokens", type=int, default=4)
    p.add_argument("--arrival-ms", type=float, default=1.0,
                   help="Poisson mean interarrival; the default "
                        "offers well over the mesh's service rate "
                        "(λ > capacity — the regime under test)")
    p.add_argument("--slo-headroom", type=float, default=4.0,
                   help="per-request SLO = headroom x unloaded "
                        "service time (calibrated each run)")
    p.add_argument("--quantile", type=float, default=75.0,
                   help="service-time predictor percentile")
    p.add_argument("--max-queue", type=int, default=16,
                   help="shed arm queue bound (0 = unbounded)")
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rounds", type=int, default=3,
                   help="interleaved replay rounds per arm (best "
                        "goodput round counts)")
    p.add_argument("--devices", type=int, default=8)
    p.add_argument("--platform", default=None)
    p.add_argument("--timeouts", type=int, nargs="+", default=[900])
    args = p.parse_args(argv)

    if args.child:
        _child_main(args)
        return 0

    here = os.path.abspath(__file__)
    cmd = [sys.executable, here, "--child"]
    for name in ("requests", "slots", "horizon", "block", "max_prompt",
                 "min_prompt", "min_new", "max_new", "round_tokens",
                 "max_queue", "vocab", "d_model", "heads", "n_layers",
                 "seed", "rounds", "devices"):
        cmd += [f"--{name.replace('_', '-')}",
                str(getattr(args, name))]
    cmd += ["--arrival-ms", str(args.arrival_ms),
            "--slo-headroom", str(args.slo_headroom),
            "--quantile", str(args.quantile)]
    if args.platform:
        cmd += ["--platform", args.platform]
    return run_child_with_retries(
        cmd, os.path.dirname(here), args.timeouts, METRIC, UNIT,
        record=args.platform is None,
        match={"requests": args.requests, "slots": args.slots,
               "horizon": args.horizon, "d_model": args.d_model,
               "n_layers": args.n_layers, "max_new": args.max_new,
               "arrival_ms": args.arrival_ms, "seed": args.seed})


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
