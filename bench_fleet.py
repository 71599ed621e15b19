"""Fleet benchmark: prefix-aware routing vs cache-oblivious routing
over N serving-engine replicas, with a kill-one-replica resilience
arm.

The trace is open-loop Poisson at ~N× a single engine's capacity —
the fleet's reason to exist — and SHARED-PREFIX-HEAVY (requests draw
from a small set of long system prompts with short divergent
suffixes, the multi-tenant chat shape).  Three placement arms run the
SAME replicas, programs, model and request trace; only the routing
signal differs:

- **prefix** — ``FleetRouter``'s production placement: requests
  route to the replica whose ``PrefixTrie`` already caches their
  prompt's leading blocks (least-loaded fallback), so one replica
  serves each system prompt from cache instead of every replica
  re-prefilling every prompt.
- **oblivious** — least-loaded only, cache-blind: the load balancer
  most fleets actually deploy, and the baseline the prefix signal
  must beat on goodput-under-SLO.
- **round_robin** — the naive baseline.

The scoreboard is goodput-under-SLO (``SLOReport``: a request counts
iff FULLY served within its target, calibrated against unloaded
service time), with the prefix/oblivious ratio as the headline value.

The **kill arm** re-runs the prefix placement with a scripted
``FaultPlan`` replica crash mid-trace and reports the failover's
recovery time (seconds from the crash until every pre-crash request
reached a terminal record) plus the two integrity invariants the
drills pin: every fleet id delivered exactly once, and every fully
served request token-bitwise-identical to the engine-independent solo
oracle — failover changes WHERE a request is served, never WHAT.

Zero steady-state recompiles post-warm is asserted FLEET-WIDE (the
``ProgramLedger`` invariant: ragged traffic, failover re-dispatch and
queue migration must all reuse the warmed programs) and reported as
``steady_retraces``.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", ...}:
value = prefix/oblivious goodput-under-SLO ratio (unit "x", >1 means
the prefix signal wins).  One child under a timeout, the parent off JAX
(``_bench_common``).
"""

import argparse
import json
import os
import sys
import time

from _bench_common import pin_platform, run_child_with_retries

METRIC = "serving_fleet_goodput_prefix_vs_oblivious"
UNIT = "x"


def _make_trace(rng, args):
    """(arrival_offset_s, prompt, max_new) per request; prompts share
    ``--shared-prefixes`` long system prompts with short divergent
    suffixes."""
    import numpy as np

    shared = [rng.randint(0, args.vocab, args.shared_prefix)
              for _ in range(args.shared_prefixes)]
    gaps = rng.exponential(args.arrival_ms / 1e3, args.requests)
    arrivals = np.cumsum(gaps)
    trace = []
    for i in range(args.requests):
        base = shared[int(rng.randint(len(shared)))]
        suffix = rng.randint(
            0, args.vocab, int(rng.randint(1, args.max_suffix + 1)))
        prompt = np.concatenate([base, suffix]).astype(np.int32)
        trace.append((float(arrivals[i]), prompt,
                      int(rng.randint(args.min_new, args.max_new + 1))))
    return trace


def _make_oracle(adapter, params):
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


def _replay(router, trace, deadlines):
    """Open-loop fleet replay.  Returns (terminal_records, makespan_s,
    recovery_s) — recovery_s is the time from the first failover until
    every request submitted BEFORE it reached a terminal record (None
    when nothing failed over)."""
    from chainermn_tpu.serving import ShedCompletion

    terminals = []
    fids = []
    t0 = time.perf_counter() - trace[0][0]
    pending = list(enumerate(trace))
    t_failover = None
    pre_kill = None
    recovery = None
    while pending or not router.idle:
        now = time.perf_counter() - t0
        while pending and pending[0][1][0] <= now:
            i, (_, prompt, max_new) = pending.pop(0)
            r = router.submit(prompt, max_new, timeout=deadlines[i])
            if isinstance(r, ShedCompletion):
                terminals.append(r)
            else:
                fids.append(r)
        if not router.idle:
            terminals.extend(router.step())
        elif pending:
            time.sleep(min(1e-3, max(0.0, pending[0][1][0] - now)))
        if t_failover is None and router.n_failovers > 0:
            t_failover = time.perf_counter()
            pre_kill = set(fids)
        if t_failover is not None and recovery is None:
            done = {t.rid for t in terminals}
            if pre_kill <= done:
                recovery = time.perf_counter() - t_failover
    t_end = max(getattr(c, "t_done", None) or c.t_shed
                for c in terminals)
    return terminals, t_end - t0 - trace[0][0], recovery


def _calibrate(engines, trace):
    """Warm EVERY replica through its full serving surface (prefill /
    admit / decode / ``warm()``), then measure the unloaded TTFT/TPOT
    on one replica — the SLO targets and predictor priors."""
    import numpy as np

    wave = [(t[1], min(t[2], 8)) for t in trace[:engines[0].n_slots]]
    records = None
    for eng in engines:
        for _ in range(2):
            for p, n in wave:
                eng.submit(p, max_new=n)
            comps = eng.run(max_steps=2000)
        eng.warm()
        eng.reset()
        records = [(c.ttft, c.tpot) for c in comps]
    ttft = float(np.median([t for t, _ in records]))
    tpot = float(np.median([p for _, p in records]))
    return ttft, tpot, records


def _score(arm, records, slo_by_rid, makespan):
    from chainermn_tpu.serving import SLOReport

    slo = SLOReport(percentiles=(50, 99))
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
        "raw_tokens_per_sec": tokens / makespan,
        "makespan_s": makespan,
    }


def _verify(records, trace_by_fid, oracle):
    """(delivered_once, checked, mismatches): exactly-once delivery
    plus token identity (exact for ok, oracle-prefix for timeouts)."""
    import numpy as np

    seen = set()
    once = True
    checked = mismatches = 0
    for r in records:
        if r.rid in seen:
            once = False
        seen.add(r.rid)
        if getattr(r, "status", "shed") not in ("ok", "timeout") \
                or r.rid not in trace_by_fid:
            continue
        prompt, max_new = trace_by_fid[r.rid]
        want = oracle(prompt, max_new)
        checked += 1
        got = np.asarray(r.tokens)
        ref = want if r.status == "ok" else want[:got.shape[0]]
        if not np.array_equal(got, ref):
            mismatches += 1
    return once, checked, mismatches


def run(args):
    import jax
    import numpy as np

    from chainermn_tpu.parallel import MeshConfig
    from chainermn_tpu.serving import (
        AdmissionController, FleetRouter, MiniLMAdapter, MiniLMConfig,
        ServingEngine, ServiceTimePredictor, init_minilm,
    )
    from chainermn_tpu.testing import FaultInjector, FaultPlan
    from chainermn_tpu.utils.programs import get_ledger

    cfg = MiniLMConfig(
        vocab_size=args.vocab, d_model=args.d_model,
        n_heads=args.heads, d_head=args.d_model // args.heads,
        d_ff=2 * args.d_model, n_layers=args.n_layers,
        max_pos=args.horizon)
    n_dev = min(args.slots, jax.device_count())
    mc = MeshConfig(data=n_dev, devices=jax.devices()[:n_dev])
    params = init_minilm(jax.random.PRNGKey(0), cfg)
    adapter = MiniLMAdapter(mc, cfg)
    engines = [
        ServingEngine(adapter, params, n_slots=args.slots,
                      horizon=args.horizon, max_prompt=args.max_prompt,
                      block=args.block, round_tokens=args.round_tokens,
                      pool_blocks=args.pool_blocks)
        for _ in range(args.replicas)]

    rng = np.random.RandomState(args.seed)
    trace = _make_trace(rng, args)

    cal_ttft, cal_tpot, cal_records = _calibrate(engines, trace)
    get_ledger().mark_steady("serve/")
    slo_rel = [args.slo_headroom * (cal_ttft + cal_tpot * (n - 1))
               for _, _, n in trace]
    mean_new = float(np.mean([n for _, _, n in trace]))
    offered = mean_new / (args.arrival_ms / 1e3)
    capacity_one = args.slots / cal_tpot

    def primed_controller():
        pred = ServiceTimePredictor(quantile=args.quantile)
        for t, p in cal_records:
            pred.observe_ttft(t)
            pred.observe_service_ttft(t)
            pred.observe_tpot(p)
        return AdmissionController(predictor=pred)

    oracle = _make_oracle(adapter, params)
    rounds_by_arm = {}
    order = ("oblivious", "round_robin", "prefix", "kill")
    names = [f"replica{i}" for i in range(args.replicas)]
    for rnd in range(args.rounds):
        for arm in order:
            for eng in engines:
                eng.reset()
                eng.admission = primed_controller()
            placement = "prefix" if arm == "kill" else arm
            router = FleetRouter(engines, names=names,
                                 placement=placement)
            if arm == "kill":
                inj = FaultInjector(FaultPlan(
                    fleet_kill_at_step=args.kill_at_step,
                    fleet_kill_replica=args.replicas - 1))
                inj.attach_fleet(router)
            records, makespan, recovery = _replay(router, trace,
                                                 slo_rel)
            assert len(records) == args.requests, (arm, len(records))
            if arm == "kill":
                assert router.n_failovers >= 1, \
                    "kill arm: the scripted crash never fired — " \
                    "lower --kill-at-step"
            trace_by_fid = {f"f{i}": (t[1], t[2])
                            for i, t in enumerate(trace)}
            slo_by_rid = {f"f{i}": s for i, s in enumerate(slo_rel)}
            once, checked, mism = _verify(records, trace_by_fid,
                                          oracle)
            stats = _score(arm, records, slo_by_rid, makespan)
            stats.update(delivered_once=once, token_checks=checked,
                         token_mismatches=mism,
                         recovery_s=recovery,
                         failovers=router.n_failovers,
                         migrated=router.n_migrated,
                         retries=router.n_retries,
                         prefix_hit_rate=float(np.mean(
                             [e._alloc.stats()["prefix_hit_rate"]
                              for e in engines])))
            rounds_by_arm.setdefault(arm, []).append(stats)
    for eng in engines:
        eng.admission = None
    steady_retraces = get_ledger().steady_retraces("serve/")

    # median round per arm (by goodput): replaying wall-clock traces
    # on a shared host is noisy, and best-of just crowns the luckiest
    # round — the median is the honest per-arm representative, and
    # integrity fields below still aggregate over EVERY round
    arms = {}
    for arm, rounds in rounds_by_arm.items():
        rounds = sorted(rounds,
                        key=lambda s: s["goodput_tokens_per_sec"])
        arms[arm] = rounds[(len(rounds) - 1) // 2]

    p, o, rr, k = (arms["prefix"], arms["oblivious"],
                   arms["round_robin"], arms["kill"])
    ratio = (p["goodput_tokens_per_sec"]
             / max(o["goodput_tokens_per_sec"], 1e-9))
    every_round = [s for rounds in rounds_by_arm.values()
                   for s in rounds]
    integrity_ok = bool(
        all(s["delivered_once"] for s in every_round)
        and sum(s["token_mismatches"] for s in every_round) == 0)
    return {
        "metric": METRIC,
        "value": round(ratio, 3),
        "unit": UNIT,
        "vs_baseline": round(ratio, 3),
        "prefix_goodput_tokens_per_sec":
            round(p["goodput_tokens_per_sec"], 1),
        "oblivious_goodput_tokens_per_sec":
            round(o["goodput_tokens_per_sec"], 1),
        "round_robin_goodput_tokens_per_sec":
            round(rr["goodput_tokens_per_sec"], 1),
        "prefix_vs_round_robin": round(
            p["goodput_tokens_per_sec"]
            / max(rr["goodput_tokens_per_sec"], 1e-9), 3),
        "prefix_attainment": round(p["attainment"], 3),
        "oblivious_attainment": round(o["attainment"], 3),
        "round_robin_attainment": round(rr["attainment"], 3),
        "prefix_hit_rate_prefix_arm": round(p["prefix_hit_rate"], 3),
        "prefix_hit_rate_oblivious_arm":
            round(o["prefix_hit_rate"], 3),
        "kill_goodput_tokens_per_sec":
            round(k["goodput_tokens_per_sec"], 1),
        "kill_recovery_s": (None if k["recovery_s"] is None
                            else round(k["recovery_s"], 3)),
        "kill_failovers": k["failovers"],
        "kill_migrated": k["migrated"],
        "kill_retries": k["retries"],
        "kill_delivered_once": all(
            s["delivered_once"] for s in rounds_by_arm["kill"]),
        "kill_token_mismatches": sum(
            s["token_mismatches"] for s in rounds_by_arm["kill"]),
        "integrity_ok": integrity_ok,
        "token_checks": sum(s["token_checks"] for s in every_round),
        "token_identity_mismatches": sum(s["token_mismatches"]
                                         for s in every_round),
        "steady_retraces": steady_retraces,
        "offered_tokens_per_sec": round(offered, 1),
        "capacity_tokens_per_sec_one_replica":
            round(capacity_one, 1),
        "overloaded_vs_fleet": bool(
            offered > args.replicas * capacity_one),
        "cal_ttft_ms": round(cal_ttft * 1e3, 2),
        "cal_tpot_ms": round(cal_tpot * 1e3, 3),
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": jax.device_count(),
        "replicas": args.replicas,
        "requests": args.requests,
        "slots": args.slots,
        "horizon": args.horizon,
        "block": args.block,
        "max_prompt": args.max_prompt,
        "pool_blocks": args.pool_blocks,
        "shared_prefixes": args.shared_prefixes,
        "shared_prefix": args.shared_prefix,
        "max_suffix": args.max_suffix,
        "min_new": args.min_new,
        "max_new": args.max_new,
        "round_tokens": args.round_tokens,
        "arrival_ms": args.arrival_ms,
        "slo_headroom": args.slo_headroom,
        "kill_at_step": args.kill_at_step,
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
    p.add_argument("--replicas", type=int, default=2)
    p.add_argument("--requests", type=int, default=80)
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--horizon", type=int, default=160)
    p.add_argument("--block", type=int, default=8)
    p.add_argument("--max-prompt", type=int, default=48)
    p.add_argument("--shared-prefixes", type=int, default=16,
                   help="distinct shared system prompts in the trace; "
                        "sized so ONE replica's pool cannot cache the "
                        "whole set — prefix-aware routing partitions "
                        "it across the fleet, cache-oblivious routing "
                        "replicates and thrashes")
    p.add_argument("--shared-prefix", type=int, default=40,
                   help="tokens per shared system prompt")
    p.add_argument("--max-suffix", type=int, default=7)
    p.add_argument("--min-new", type=int, default=4)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--round-tokens", type=int, default=4)
    p.add_argument("--pool-blocks", type=int, default=128,
                   help="KV pool blocks per replica — deliberately "
                        "bounded so the shared-prefix working set "
                        "only fits fleet-wide, not per-replica")
    p.add_argument("--arrival-ms", type=float, default=5.0,
                   help="Poisson mean interarrival; the default "
                        "loads the fleet to roughly its PREFILL-"
                        "inclusive capacity — queues form but a "
                        "steady state exists, so SLO attainment is "
                        "decided by service time (where prefix hits "
                        "pay off), not queue-position lottery")
    p.add_argument("--slo-headroom", type=float, default=6.0)
    p.add_argument("--quantile", type=float, default=75.0)
    p.add_argument("--kill-at-step", type=int, default=3,
                   help="fleet step at which the kill arm crashes "
                        "the last replica")
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rounds", type=int, default=3,
                   help="replay rounds per arm (median goodput "
                        "counts; integrity aggregates every round)")
    p.add_argument("--devices", type=int, default=8)
    p.add_argument("--platform", default=None)
    p.add_argument("--timeouts", type=int, nargs="+", default=[900])
    args = p.parse_args(argv)

    if args.child:
        _child_main(args)
        return 0

    here = os.path.abspath(__file__)
    cmd = [sys.executable, here, "--child"]
    for name in ("replicas", "requests", "slots", "horizon", "block",
                 "max_prompt", "shared_prefixes", "shared_prefix",
                 "max_suffix", "min_new", "max_new", "round_tokens",
                 "pool_blocks", "kill_at_step", "vocab", "d_model",
                 "heads", "n_layers", "seed", "rounds", "devices"):
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
        match={"replicas": args.replicas,
               "requests": args.requests, "slots": args.slots,
               "horizon": args.horizon, "d_model": args.d_model,
               "n_layers": args.n_layers,
               "arrival_ms": args.arrival_ms,
               "seed": args.seed})


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
