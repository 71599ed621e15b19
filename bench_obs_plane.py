"""Ops-plane overhead benchmark: the full request-scoped observability
plane ON vs OFF around the same serving loop.

The PR 13 ops plane only earns its always-on wiring if it is
effectively free: the ON arm serves a fixed request trace with an
enabled metrics registry (exemplar-carrying observes), a
RequestTraceStore retaining EVERY request's span timeline
(sample_rate 1.0 — the worst case), and a burn-rate AlertManager
ticked every scheduler step (rate-limited to its production
evaluation interval, 50 ms here — the windows are minutes long, so a
tick from the tight loop is one clock compare); the OFF arm is the
production default (disabled registry's no-op singletons,
``traces=None`` — the allocation-free path pinned by
tests/serving_tests/test_obs_plane.py).  Requests generate 24–48
tokens each, so the fixed per-request bookkeeping (span timeline,
exemplar observes, trace hand-off) amortizes the way real decode
traffic amortizes it.  Both arms run the SAME warmed engine and the
same seeded trace; generated token counts are asserted identical, so
the plane cannot buy speed by changing the work.

During the ON warmup pass a StatuszServer is attached to the LIVE
engine on an ephemeral port and all four endpoints (`/healthz`,
`/metricsz`, `/statusz`, `/tracez`) are fetched mid-decode — their
status codes ride the result JSON, and the `serve/ttft` p99 exemplar
is resolved against the trace store (``exemplar_resolves``).

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", ...}:
value = plane-off tokens/s ÷ plane-on tokens/s ("x"; 1.0 = free).
``overhead_pct`` = (value − 1) × 100, ``within_bar`` reports the <1%
bar (docs/OBSERVABILITY.md "Request tracing").

Measurement shape: this box's load comes in multi-second bursts that
swamp any single serve, so best-of-rounds does NOT converge here the
way it does for the longer train-step loops.  Instead each round
times the two arms BACK-TO-BACK (order-alternating, ``--reps``
consecutive serves per timed block so a block outlasts scheduler
jitter) and the reported value is the MEDIAN of the per-round
off/on ratios — a burst taxes both members of a pair, and the median
discards the pairs a burst straddled.  The model is sized so a
decode round costs milliseconds (d_model 128, 3 layers): against a
sub-ms toy round the plane's fixed per-event cost reads 10–100×
its production weight, which would make the bar meaningless in the
other direction.  Same hermetic child-process pattern as
bench_metrics_registry.py.
"""

import argparse
import json
import os
import sys
import time

from _bench_common import pin_platform, run_child_with_retries

METRIC = "obs_plane_overhead"
UNIT = "x"
BAR_PCT = 1.0


def run(requests=24, slots=8, horizon=160, max_prompt=16, block=8,
        min_new=24, max_new=48, round_tokens=4, rounds=8, reps=2):
    import statistics
    import urllib.request

    import jax
    import numpy as np

    from chainermn_tpu.parallel import MeshConfig
    from chainermn_tpu.serving import (
        MiniLMAdapter,
        MiniLMConfig,
        ServingEngine,
        init_minilm,
    )
    from chainermn_tpu.utils.alerts import AlertManager, LatencyRule
    from chainermn_tpu.utils.metrics import (
        MetricsRegistry,
        get_registry,
        set_registry,
    )
    from chainermn_tpu.utils.statusz import StatuszServer
    from chainermn_tpu.utils.telemetry import RequestTraceStore

    cfg = MiniLMConfig(vocab_size=256, d_model=128, n_heads=4,
                       d_head=32, d_ff=512, n_layers=3,
                       max_pos=horizon + 96)
    params = init_minilm(jax.random.PRNGKey(0), cfg)
    adapter = MiniLMAdapter(MeshConfig(data=jax.device_count()), cfg)
    engine = ServingEngine(adapter, params, n_slots=slots,
                           horizon=horizon, max_prompt=max_prompt,
                           block=block, round_tokens=round_tokens)
    rng = np.random.RandomState(7)
    trace = [(rng.randint(0, cfg.vocab_size,
                          rng.randint(2, max_prompt + 1)),
              int(rng.randint(min_new, max_new + 1)))
             for _ in range(requests)]

    def make_plane():
        store = RequestTraceStore(capacity=4 * requests,
                                  sample_rate=1.0)
        rule = LatencyRule("slow-ttft", histogram="serve/ttft",
                           above=0.5, budget=0.05,
                           windows=((10.0, 1.0, 14.4),))
        mgr = AlertManager([rule], min_interval=0.05)
        return store, mgr

    def serve(on, statusz_probe=False):
        """One full serve of the trace; returns (tokens, seconds,
        extras).  The caller owns the registry swap."""
        extras = {}
        store, mgr = make_plane() if on else (None, None)
        engine.reset()
        engine.traces = store
        srv = None
        try:
            if statusz_probe:
                srv = StatuszServer().attach_engine(engine)
                srv.start()
            for p, n in trace:
                engine.submit(p, max_new=n)
            done = []
            t0 = time.perf_counter()
            steps = 0
            while not engine.idle:
                done.extend(engine.step())
                steps += 1
                if on:
                    mgr.tick()
                if srv is not None and steps == 2:
                    # mid-decode, slots live: the four endpoints must
                    # answer from the RUNNING engine
                    codes = {}
                    for path in ("/healthz", "/metricsz", "/statusz",
                                 "/tracez"):
                        with urllib.request.urlopen(srv.url(path),
                                                    timeout=10) as r:
                            codes[path] = r.status
                    extras["statusz_endpoints"] = codes
                if steps > 100 * requests:
                    raise RuntimeError("serving loop did not drain")
            dt = time.perf_counter() - t0
            tokens = sum(c.n_generated for c in done
                         if c.status == "ok")
            assert len(done) == requests, (len(done), requests)
            if on:
                reg = get_registry()
                ex = reg.histogram("serve/ttft").exemplar_for(99)
                extras["exemplar_resolves"] = bool(
                    ex is not None and store.get(ex[0]) is not None)
                extras["traces_retained"] = len(store)
                extras["alert_ticks"] = mgr.ticks
            return tokens, dt, extras
        finally:
            if srv is not None:
                srv.stop()
            engine.traces = None

    def measure(on, tokens_ref):
        """One timed block: ``reps`` consecutive serves under one
        registry swap; returns aggregate tokens/s."""
        prev = set_registry(MetricsRegistry(enabled=on))
        try:
            tokens = 0
            total = 0.0
            for _ in range(reps):
                tk, dt, _ = serve(on)
                assert tk == tokens_ref, (tk, tokens_ref)
                tokens += tk
                total += dt
            return tokens / total
        finally:
            set_registry(prev)

    # warmup both arms (compiles, first-touch paging); the ON warmup
    # doubles as the live statusz endpoint proof
    prev = set_registry(MetricsRegistry(enabled=False))
    try:
        tokens_ref, _, _ = serve(False)
    finally:
        set_registry(prev)
    prev = set_registry(MetricsRegistry(enabled=True))
    try:
        tokens_on, _, probe = serve(True, statusz_probe=True)
    finally:
        set_registry(prev)
    assert tokens_on == tokens_ref, (tokens_on, tokens_ref)
    assert probe["statusz_endpoints"] == {
        "/healthz": 200, "/metricsz": 200, "/statusz": 200,
        "/tracez": 200}, probe
    assert probe["exemplar_resolves"], probe
    assert probe["traces_retained"] == requests, probe

    pairs = []
    rates = {True: [], False: []}
    for r in range(rounds):
        # the two arms of a pair run back-to-back (order-alternating)
        # so a load burst taxes both; the median over rounds discards
        # the pairs a burst straddled
        order = (False, True) if r % 2 == 0 else (True, False)
        rate = {}
        for on in order:
            rate[on] = measure(on, tokens_ref)
            rates[on].append(rate[on])
        pairs.append(rate[False] / rate[True])

    ratio = statistics.median(pairs)
    overhead_pct = (ratio - 1.0) * 100.0
    return {
        "metric": METRIC,
        "value": round(ratio, 4),
        "unit": UNIT,
        "vs_baseline": round(ratio, 4),
        "overhead_pct": round(overhead_pct, 3),
        "bar_pct": BAR_PCT,
        "within_bar": bool(overhead_pct < BAR_PCT),
        "pair_ratios": [round(p, 4) for p in sorted(pairs)],
        "off_tokens_per_s": round(max(rates[False]), 1),
        "on_tokens_per_s": round(max(rates[True]), 1),
        "tokens_per_run": tokens_ref,
        "statusz_endpoints": probe["statusz_endpoints"],
        "exemplar_resolves": probe["exemplar_resolves"],
        "traces_retained": probe["traces_retained"],
        "requests": requests,
        "slots": slots,
        "max_new": max_new,
        "round_tokens": round_tokens,
        "rounds": rounds,
        "reps": reps,
        "n_devices": jax.device_count(),
        "device_kind": jax.devices()[0].device_kind,
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
    result = run(requests=args.requests, slots=args.slots,
                 horizon=args.horizon, max_prompt=args.max_prompt,
                 block=args.block, min_new=args.min_new,
                 max_new=args.max_new, round_tokens=args.round_tokens,
                 rounds=args.rounds, reps=args.reps)
    print("BENCH_RESULT " + json.dumps(result))


def _parent_main(args):
    here = os.path.abspath(__file__)
    cmd = [sys.executable, here, "--child",
           "--requests", str(args.requests),
           "--slots", str(args.slots),
           "--horizon", str(args.horizon),
           "--max-prompt", str(args.max_prompt),
           "--block", str(args.block),
           "--min-new", str(args.min_new),
           "--max-new", str(args.max_new),
           "--round-tokens", str(args.round_tokens),
           "--rounds", str(args.rounds),
           "--reps", str(args.reps),
           "--devices", str(args.devices)]
    if args.platform:
        cmd += ["--platform", args.platform]
    return run_child_with_retries(
        cmd, os.path.dirname(here), args.timeouts, METRIC, UNIT,
        record=args.platform is None,
        match={"requests": args.requests, "slots": args.slots,
               "max_new": args.max_new, "rounds": args.rounds})


def _parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", action="store_true")
    p.add_argument("--requests", type=int, default=24)
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--horizon", type=int, default=160)
    p.add_argument("--max-prompt", type=int, default=16)
    p.add_argument("--block", type=int, default=8)
    p.add_argument("--min-new", type=int, default=24)
    p.add_argument("--max-new", type=int, default=48)
    p.add_argument("--round-tokens", type=int, default=4)
    p.add_argument("--rounds", type=int, default=8,
                   help="order-alternating paired timing rounds (the "
                        "median per-round off/on ratio counts)")
    p.add_argument("--reps", type=int, default=2,
                   help="consecutive serves per timed block — a block "
                        "must outlast scheduler jitter")
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
