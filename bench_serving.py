"""Serving-engine latency/throughput benchmark: continuous batching vs
static batching under a Poisson arrival trace.

Both arms run the SAME engine, programs, model, and request trace —
only the scheduling differs: the continuous arm admits into any freed
slot mid-stream (per-row eviction, FCFS), the static arm is the
engine's ``gang`` mode (a batch admits only when every slot is free
and drains completely before the next forms — exactly today's
one-``generate``-call-per-batch serving).  The measured difference is
therefore attributable to request-level scheduling alone, not to
dispatch granularity or model speed.

The trace is open-loop: requests arrive at Poisson times with ragged
prompt lengths and token budgets, replayed against the wall clock.
Reported: aggregate generated tokens/sec per arm (the ratio is the
headline), p50/p99 time-to-first-token (arrival → first token on
host — queueing included, which is where static batching bleeds), and
slot utilization.  Percentiles come through the SLO layer
(``ServingEngine.request_records()`` → ``SLOReport``'s shared-lattice
histograms) and are asserted equal to the raw numpy math each run —
the dashboard number IS the bench number.  Token identity across the
two arms is verified
per request and recorded (the engine's exactness guarantee: scheduling
must never change anyone's tokens).

The model is the serving engine's MiniLM reference backend (the
engine machinery under test is identical to the flagship's).  Prints ONE JSON line {"metric",
"value", "unit", "vs_baseline", ...}: value = continuous/static
tokens-per-sec ratio (unit "x", >1 means continuous batching wins).
One child under a timeout, the parent off JAX (``_bench_common``).

**Decode-tier arms** (ISSUE 14; ``--decode-tier 0`` skips them) ride
the same record:

- *prefix-share*: a shared-system-prompt trace staged with prefix
  sharing ON vs OFF — same engine, same programs, sharing is the only
  difference; token identity between the modes is verified
  per-request.  Reported: prefill-time ratio, row-held peak pool
  blocks both ways, and the trie hit rate (also surfaced as an
  ``SLOReport`` extras column).
- *sampled*: the trace under per-request keyed temperature/top-k/top-p
  — tokens/s plus a full second run asserting bit-identical keyed
  replay.
- *speculative*: MiniLM draft/verify vs target-only decode (single
  device; CPU is compute-bound, so this is the MACHINERY-COST floor —
  the HBM win needs hardware; bench_decode's lever table tells that
  story).  Reported: tokens/s both ways, their ratio, and the
  acceptance rate for a cheap random draft and the self-draft
  ceiling.

**Ragged-round arms** (``--ragged-tier 0`` skips them):

- *ragged-ttft*: the TTFT-independence claim, measured: short prompts
  admitted mid-stream next to chunk-staged LONG prompts vs the same
  shorts with no longs at all — the short-prompt TTFT p50 must not
  move beyond the noise bar (asserted in-run; ``--ttft-noise-bar``).
  A lockstep arm (one chunk = the whole prompt, the old monolithic
  staging shape) runs the same co-admit trace for the
  ragged-vs-lockstep ratio.
- *engine-spec*: per-row speculative ROUNDS (``draft_adapter=`` on
  the engine) vs plain ragged rounds over the same trace — tokens/s
  ratio, per-row acceptance rate, and per-request token identity
  (which must hold at ANY acceptance).
"""

import argparse
import json
import os
import sys
import time

from _bench_common import pin_platform, run_child_with_retries

METRIC = "serving_continuous_vs_static_tokens_per_sec"
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


def _replay(engine, trace):
    """Open-loop replay: submit each request at its arrival offset,
    stepping the engine in between.  Returns (completions, makespan_s)
    with the clock starting at the first arrival."""
    completions = []
    t0 = time.perf_counter() - trace[0][0]
    pending = list(trace)
    while pending or not engine.idle:
        now = time.perf_counter() - t0
        while pending and pending[0][0] <= now:
            _, prompt, max_new = pending.pop(0)
            engine.submit(prompt, max_new=max_new)
        if not engine.idle:
            completions.extend(engine.step())
        elif pending:
            time.sleep(min(1e-3, max(0.0, pending[0][0] - now)))
    t_end = max(c.t_done for c in completions)
    return completions, t_end - t0 - trace[0][0]


def _arm_stats(arm, completions, makespan):
    """Percentiles through the SLO layer (the engine's request records
    + ``SLOReport``'s shared-lattice histograms), asserted equal to the
    ad-hoc numpy math this bench used to carry — the dedup is only
    safe if the recorded numbers do not move."""
    import numpy as np

    from chainermn_tpu.serving import SLOReport

    slo = SLOReport(percentiles=(50, 99))
    slo.add_arm(arm, completions)
    s = slo.summary()[arm]
    # under the histogram's exact-sample cap the SLO percentiles must
    # reproduce numpy's to float rounding — the equivalence the dedup
    # (and the SLO layer's credibility) rests on.  Past the cap (a
    # --requests > 512 run) the histogram deliberately switches to
    # interpolated bucket quantiles, so only the exact path is pinned.
    if slo.histograms(arm)["ttft"].exact:
        ttft = np.asarray([c.ttft for c in completions])
        for q in (50, 99):
            want = float(np.percentile(ttft, q))
            assert abs(s["ttft"][f"p{q}"] - want) \
                <= 1e-9 * max(1.0, want), q
    tokens = int(sum(c.n_generated for c in completions))
    return {
        "tokens_per_sec": tokens / makespan,
        "ttft_p50_ms": s["ttft"]["p50"] * 1e3,
        "ttft_p99_ms": s["ttft"]["p99"] * 1e3,
        "queue_wait_p50_ms": s["queue_wait"]["p50"] * 1e3,
        "tpot_p50_ms": s["tpot"]["p50"] * 1e3,
        "makespan_s": makespan,
        "tokens": tokens,
    }


def _prefix_arm(engine, args, rng):
    """Prefix sharing ON vs OFF over a shared-system-prompt trace."""
    import numpy as np

    from chainermn_tpu.serving import SLOReport

    n_shared = min(args.shared_prefix, args.max_prompt - 1)
    shared = rng.randint(0, args.vocab, n_shared)
    # the system-prompt workload: every prompt opens with the shared
    # prefix; every third request is an exact repeat of one FULL
    # (block-aligned) prompt — retry/dedup traffic, the full-hit case
    # where sharing skips the prefill dispatch entirely
    repeat = np.concatenate(
        [shared, rng.randint(0, args.vocab,
                             args.max_prompt - n_shared)]) \
        .astype(np.int32)
    trace = []
    for i in range(args.prefix_requests):
        if i and i % 3 == 0:
            p = repeat
        else:
            extra = rng.randint(1, args.max_prompt - n_shared + 1)
            p = np.concatenate(
                [shared, rng.randint(0, args.vocab, extra)]) \
                .astype(np.int32)
        trace.append((p, int(rng.randint(args.min_new,
                                         args.max_new // 2 + 1))))
    out = {}
    tokens_by_mode = {}
    for mode in (True, False):
        engine.prefix_sharing = mode
        # warm pass compiles the per-split suffix programs; then
        # best-of-rounds over the measured passes (the same
        # scheduler-noise rejection the headline arms use)
        for measured in (0, 1, 2):
            engine.reset()
            for p, n in trace:
                engine.submit(p, max_new=n)
            t0 = time.perf_counter()
            comps = engine.run(max_steps=20000)
            makespan = time.perf_counter() - t0
            if not measured:
                continue
            s = engine.stats()
            tokens = sum(c.n_generated for c in comps)
            key = "share" if mode else "private"
            if measured == 1 or s["prefill_seconds"] < \
                    out[f"prefix_{key}_prefill_s"]:
                out[f"prefix_{key}_prefill_s"] = round(
                    s["prefill_seconds"], 4)
                out[f"prefix_{key}_tokens_per_sec"] = round(
                    tokens / makespan, 1)
            tokens_by_mode[mode] = {
                c.rid: np.asarray(c.tokens) for c in comps}
            out[f"prefix_{key}_peak_row_blocks"] = s["peak_row_blocks"]
            out[f"prefix_{key}_peak_staged"] = s["peak_staged"]
            # pool pressure PER STAGED REQUEST — the sharing drop is
            # ~P_shared/P; at a saturated pool the absolute peak
            # instead converts into more requests staged ahead
            out[f"prefix_{key}_blocks_per_staged"] = round(
                s["peak_row_blocks"] / max(s["peak_staged"], 1), 3)
            if mode:
                out["prefix_hit_rate"] = round(s["prefix_hit_rate"], 4)
                # the dashboard form: hit rate as an SLOReport extras
                # column next to the latency percentiles
                slo = SLOReport(percentiles=(50, 99)).add_arm(
                    "prefix-share", engine.request_records(),
                    extras={"prefix_hit_rate": s["prefix_hit_rate"]})
                assert slo.summary()["prefix-share"]["extras"][
                    "prefix_hit_rate"] == s["prefix_hit_rate"]
    engine.prefix_sharing = True
    engine.reset()
    out["prefix_prefill_speedup"] = round(
        out["prefix_private_prefill_s"]
        / max(out["prefix_share_prefill_s"], 1e-9), 3)
    out["prefix_pool_pressure_drop"] = round(
        out["prefix_private_blocks_per_staged"]
        / max(out["prefix_share_blocks_per_staged"], 1e-9), 3)
    out["prefix_token_identity_mismatches"] = sum(
        not np.array_equal(tokens_by_mode[True][r],
                           tokens_by_mode[False][r])
        for r in tokens_by_mode[True])
    return out


def _sampled_arm(engine, args, rng):
    """Keyed sampling throughput + bit-identical replay."""
    import numpy as np

    from chainermn_tpu.serving import SamplingParams

    trace = [(rng.randint(0, args.vocab,
                          rng.randint(args.min_prompt,
                                      args.max_prompt + 1)),
              int(rng.randint(args.min_new, args.max_new // 2 + 1)))
             for _ in range(args.prefix_requests)]
    sps = [SamplingParams(temperature=0.8, top_k=min(32, args.vocab),
                          top_p=0.95, seed=1000 + i)
           for i in range(len(trace))]
    runs = []
    makespans = []
    for _ in range(2):
        engine.reset()
        for (p, n), sp in zip(trace, sps):
            engine.submit(p, max_new=n, sampling=sp)
        t0 = time.perf_counter()
        comps = engine.run(max_steps=20000)
        makespans.append(time.perf_counter() - t0)
        runs.append({c.rid: np.asarray(c.tokens) for c in comps})
    tokens = sum(t.shape[0] for t in runs[1].values())
    return {
        "sampled_tokens_per_sec": round(tokens / min(makespans), 1),
        "sampled_replay_mismatches": sum(
            not np.array_equal(runs[0][r], runs[1][r])
            for r in runs[0]),
    }


def _spec_arm(args, rng):
    """Draft/verify speculative decode vs target-only, single device
    (the machinery-cost floor on a compute-bound CPU)."""
    import jax
    import numpy as np

    from chainermn_tpu.parallel import MeshConfig
    from chainermn_tpu.serving import (
        MiniLMAdapter, MiniLMConfig, SpeculativeDecoder, init_minilm,
    )

    # the decoder's own position span, NOT the serving engine's
    # horizon — a clamped position table would silently degrade the
    # model both arms run on
    max_pos = args.max_prompt + args.spec_new + args.spec_k + 2
    t_cfg = MiniLMConfig(
        vocab_size=args.vocab, d_model=args.d_model,
        n_heads=args.heads, d_head=args.d_model // args.heads,
        d_ff=2 * args.d_model, n_layers=args.n_layers,
        max_pos=max_pos)
    d_cfg = MiniLMConfig(
        vocab_size=args.vocab, d_model=max(args.d_model // 4, 8),
        n_heads=2, d_head=max(args.d_model // 8, 4),
        d_ff=args.d_model // 2, n_layers=1,
        max_pos=max_pos)
    mc = MeshConfig(data=1, devices=jax.devices()[:1])
    t_params = init_minilm(jax.random.PRNGKey(0), t_cfg)
    d_params = init_minilm(jax.random.PRNGKey(1), d_cfg)
    target = MiniLMAdapter(mc, t_cfg)
    prompts = [rng.randint(0, args.vocab,
                           rng.randint(args.min_prompt,
                                       args.max_prompt + 1))
               for _ in range(args.spec_prompts)]
    out = {}
    for name, (da, dp) in (
            ("spec", (MiniLMAdapter(mc, d_cfg), d_params)),
            ("spec_selfdraft", (target, t_params))):
        dec = SpeculativeDecoder(
            da, dp, target, t_params, k=args.spec_k,
            max_prompt=args.max_prompt,
            horizon=args.max_prompt + args.spec_new)
        dec.generate(prompts[0], 4)            # compile both paths
        dec.target_decode(prompts[0], 4)
        drafted = accepted = 0
        t0 = time.perf_counter()
        spec_tokens = []
        for p in prompts:
            res = dec.generate(p, args.spec_new)
            spec_tokens.append(res.tokens)
            drafted += res.drafted
            accepted += res.accepted
        t_spec = time.perf_counter() - t0
        t0 = time.perf_counter()
        ref_tokens = [dec.target_decode(p, args.spec_new)
                      for p in prompts]
        t_ref = time.perf_counter() - t0
        n_tok = sum(t.shape[0] for t in spec_tokens)
        out[f"{name}_tokens_per_sec"] = round(n_tok / t_spec, 1)
        out[f"{name}_acceptance_rate"] = round(
            accepted / max(drafted, 1), 4)
        out[f"{name}_vs_target_only"] = round(
            (n_tok / t_spec) / (n_tok / t_ref), 3)
        out[f"{name}_identity_mismatches"] = sum(
            not np.array_equal(a, b)
            for a, b in zip(spec_tokens, ref_tokens))
    out["spec_target_tokens_per_sec"] = round(
        sum(t.shape[0] for t in ref_tokens) / t_ref, 1)
    out["spec_k"] = args.spec_k
    return out


def _ragged_arm(args, rng):
    """TTFT independence under chunked co-admission, plus the
    ragged-vs-lockstep staging comparison.

    Scenario per pass: half the slots decode long-running background
    rows; then LONG prompts arrive (staged one chunk per round) and
    short prompts arrive right behind them.  Measured: the shorts'
    TTFT p50 with the longs present vs the same shorts with no longs
    at all (same engine, same background).  The lockstep engine stages
    a whole prompt as ONE chunk — the monolithic shape chunking
    replaced — over the identical co-admit trace."""
    import jax
    import numpy as np

    from chainermn_tpu.parallel import MeshConfig
    from chainermn_tpu.serving import (
        MiniLMAdapter, MiniLMConfig, ServingEngine, init_minilm,
    )

    blk = args.block
    long_p = (max(args.long_prompt, 2 * blk) // blk) * blk
    bg_new = 48
    horizon = long_p + bg_new + blk
    cfg = MiniLMConfig(
        vocab_size=args.vocab, d_model=args.d_model,
        n_heads=args.heads, d_head=args.d_model // args.heads,
        d_ff=2 * args.d_model, n_layers=args.n_layers,
        max_pos=horizon)
    n_dev = min(args.slots, jax.device_count())
    mc = MeshConfig(data=n_dev, devices=jax.devices()[:n_dev])
    params = init_minilm(jax.random.PRNGKey(0), cfg)
    adapter = MiniLMAdapter(mc, cfg)

    n_bg = args.slots // 2
    n_long = args.slots - n_bg
    bg = [rng.randint(0, args.vocab, blk) for _ in range(n_bg)]
    longs = [rng.randint(0, args.vocab, long_p)
             for _ in range(n_long)]
    shorts = [rng.randint(0, args.vocab,
                          rng.randint(args.min_prompt, blk + 1))
              for _ in range(args.ragged_requests)]

    def one_pass(eng, with_longs):
        eng.reset()
        for p in bg:
            eng.submit(p, max_new=bg_new)
        for _ in range(2):
            eng.step()              # background rows are mid-decode
        if with_longs:
            for p in longs:
                eng.submit(p, max_new=8)
        rids = {eng.submit(p, max_new=8) for p in shorts}
        comps = eng.run(max_steps=20000)
        ttfts = [c.ttft for c in comps if c.rid in rids]
        assert len(ttfts) == len(shorts)
        return float(np.percentile(np.asarray(ttfts), 50)), eng.stats()

    out = {}
    engines = {
        "ragged": ServingEngine(
            adapter, params, n_slots=args.slots, horizon=horizon,
            max_prompt=long_p, block=blk,
            round_tokens=args.round_tokens, prefill_chunk=1),
        "lockstep": ServingEngine(
            adapter, params, n_slots=args.slots, horizon=horizon,
            max_prompt=long_p, block=blk,
            round_tokens=args.round_tokens,
            prefill_chunk=long_p // blk),
    }
    for eng in engines.values():
        eng.warm()
    solo = coadmit = lockstep = float("inf")
    for _ in range(max(args.rounds, 1)):
        p50, _ = one_pass(engines["ragged"], with_longs=False)
        solo = min(solo, p50)
        p50, st = one_pass(engines["ragged"], with_longs=True)
        coadmit = min(coadmit, p50)
        out["ragged_chunk_prefills"] = st["chunk_prefills"]
        p50, _ = one_pass(engines["lockstep"], with_longs=True)
        lockstep = min(lockstep, p50)
    out["ragged_short_ttft_solo_p50_ms"] = round(solo * 1e3, 2)
    out["ragged_short_ttft_coadmit_p50_ms"] = round(coadmit * 1e3, 2)
    out["lockstep_short_ttft_coadmit_p50_ms"] = round(
        lockstep * 1e3, 2)
    ratio = coadmit / max(solo, 1e-9)
    out["ragged_ttft_coadmit_ratio"] = round(ratio, 3)
    out["ragged_vs_lockstep_short_ttft"] = round(
        lockstep / max(coadmit, 1e-9), 3)
    # the independence ASSERT: long-prompt co-admission must not move
    # the short-prompt TTFT p50 beyond the noise bar
    assert ratio <= args.ttft_noise_bar, (
        f"short-prompt TTFT p50 moved {ratio:.2f}x under long-prompt "
        f"co-admission (bar {args.ttft_noise_bar}x) — chunked "
        "admission is not isolating TTFT")
    return out


def _engine_spec_arm(args, rng):
    """Per-row speculative rounds (the engine's draft_adapter= mode)
    vs plain ragged rounds over one trace: tokens/s ratio, per-row
    acceptance, token identity at any acceptance."""
    import jax
    import numpy as np

    from chainermn_tpu.parallel import MeshConfig
    from chainermn_tpu.serving import (
        MiniLMAdapter, MiniLMConfig, ServingEngine, init_minilm,
    )

    horizon = args.max_prompt + args.max_new + args.spec_k + 2
    t_cfg = MiniLMConfig(
        vocab_size=args.vocab, d_model=args.d_model,
        n_heads=args.heads, d_head=args.d_model // args.heads,
        d_ff=2 * args.d_model, n_layers=args.n_layers,
        max_pos=horizon)
    d_cfg = MiniLMConfig(
        vocab_size=args.vocab, d_model=max(args.d_model // 4, 8),
        n_heads=2, d_head=max(args.d_model // 8, 4),
        d_ff=args.d_model // 2, n_layers=1, max_pos=horizon)
    n_dev = min(args.slots, jax.device_count())
    mc = MeshConfig(data=n_dev, devices=jax.devices()[:n_dev])
    t_params = init_minilm(jax.random.PRNGKey(0), t_cfg)
    d_params = init_minilm(jax.random.PRNGKey(1), d_cfg)
    target = MiniLMAdapter(mc, t_cfg)
    trace = [(rng.randint(0, args.vocab,
                          rng.randint(args.min_prompt,
                                      args.max_prompt + 1)),
              int(rng.randint(args.min_new, args.max_new // 2 + 1)))
             for _ in range(args.prefix_requests)]
    out = {}
    tokens_by_mode = {}
    for mode, kwargs in (
            ("plain", {}),
            ("spec", {"draft_adapter": MiniLMAdapter(mc, d_cfg),
                      "draft_params": d_params,
                      "spec_k": args.spec_k})):
        eng = ServingEngine(
            target, t_params, n_slots=args.slots,
            horizon=horizon, max_prompt=args.max_prompt,
            block=args.block, round_tokens=args.round_tokens,
            **kwargs)
        eng.warm()
        best = float("inf")
        for _ in range(max(args.rounds, 1)):
            eng.reset()
            for p, n in trace:
                eng.submit(p, max_new=n)
            t0 = time.perf_counter()
            comps = eng.run(max_steps=20000)
            best = min(best, time.perf_counter() - t0)
        tokens = sum(c.n_generated for c in comps)
        tokens_by_mode[mode] = {
            c.rid: np.asarray(c.tokens) for c in comps}
        out[f"engine_{mode}_tokens_per_sec"] = round(tokens / best, 1)
        if mode == "spec":
            st = eng.stats()
            out["engine_spec_acceptance_rate"] = round(
                st["spec_accepted"] / max(st["spec_drafted"], 1), 4)
    out["engine_spec_vs_plain"] = round(
        out["engine_spec_tokens_per_sec"]
        / max(out["engine_plain_tokens_per_sec"], 1e-9), 3)
    out["engine_spec_identity_mismatches"] = sum(
        not np.array_equal(tokens_by_mode["plain"][r],
                           tokens_by_mode["spec"][r])
        for r in tokens_by_mode["plain"])
    return out


def run(args):
    import jax
    import numpy as np

    from chainermn_tpu.parallel import MeshConfig
    from chainermn_tpu.serving import (
        MiniLMAdapter, MiniLMConfig, ServingEngine, init_minilm,
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

    # warmup: a mini trace compiles round/admit; warm() covers the
    # chunked-prefill program across its splits so no compile lands
    # mid-measurement in either arm
    for p, n in [(trace[0][1], 4), (trace[1][1], 4)]:
        engine.submit(p, max_new=n)
    engine.run(max_steps=200)
    engine.warm()

    # interleaved rounds, best round per arm: the 2-core container's
    # scheduler noise swamps a single ~0.3 s replay
    arms = {}
    per_arm_tokens = {}
    order = (("continuous", False), ("static", True))
    for rnd in range(args.rounds):
        for arm, gang in (order if rnd % 2 == 0 else order[::-1]):
            engine.reset()
            engine.gang = gang
            comps, makespan = _replay(engine, trace)
            assert len(comps) == args.requests, (arm, len(comps))
            # the engine's own per-request records carry the derived
            # queue_wait/ttft/tpot fields — same objects the replay
            # collected, exposed the way SLO consumers get them
            records = engine.request_records()
            assert len(records) == len(comps)
            stats = _arm_stats(arm, records, makespan)
            stats["slot_utilization"] = \
                engine.stats()["slot_utilization"]
            if arm not in arms or stats["tokens_per_sec"] \
                    > arms[arm]["tokens_per_sec"]:
                arms[arm] = stats
                per_arm_tokens[arm] = {
                    c.rid: np.asarray(c.tokens) for c in comps}

    # exactness across scheduling: every request's tokens must be
    # identical under both arms (requests get the same rids in
    # submission order after each reset)
    mismatches = sum(
        not np.array_equal(per_arm_tokens["continuous"][r],
                           per_arm_tokens["static"][r])
        for r in per_arm_tokens["continuous"])

    extra = {}
    if args.decode_tier:
        # the headline loop leaves whichever arm ran LAST on the
        # engine — the decode-tier arms measure CONTINUOUS batching
        engine.gang = False
        extra.update(_prefix_arm(engine, args,
                                 np.random.RandomState(args.seed + 1)))
        extra.update(_sampled_arm(engine, args,
                                  np.random.RandomState(args.seed + 2)))
        extra.update(_spec_arm(args,
                               np.random.RandomState(args.seed + 3)))
    if args.ragged_tier:
        extra.update(_ragged_arm(args,
                                 np.random.RandomState(args.seed + 4)))
        extra.update(_engine_spec_arm(
            args, np.random.RandomState(args.seed + 5)))

    ratio = arms["continuous"]["tokens_per_sec"] \
        / arms["static"]["tokens_per_sec"]
    return {
        **extra,
        "metric": METRIC,
        "value": round(ratio, 3),
        "unit": UNIT,
        "vs_baseline": round(ratio, 3),
        "continuous_tokens_per_sec":
            round(arms["continuous"]["tokens_per_sec"], 1),
        "static_tokens_per_sec":
            round(arms["static"]["tokens_per_sec"], 1),
        "continuous_ttft_p50_ms":
            round(arms["continuous"]["ttft_p50_ms"], 1),
        "continuous_ttft_p99_ms":
            round(arms["continuous"]["ttft_p99_ms"], 1),
        "static_ttft_p50_ms": round(arms["static"]["ttft_p50_ms"], 1),
        "static_ttft_p99_ms": round(arms["static"]["ttft_p99_ms"], 1),
        "continuous_queue_wait_p50_ms":
            round(arms["continuous"]["queue_wait_p50_ms"], 1),
        "static_queue_wait_p50_ms":
            round(arms["static"]["queue_wait_p50_ms"], 1),
        "continuous_tpot_p50_ms":
            round(arms["continuous"]["tpot_p50_ms"], 2),
        "static_tpot_p50_ms": round(arms["static"]["tpot_p50_ms"], 2),
        "continuous_slot_utilization":
            round(arms["continuous"]["slot_utilization"], 3),
        "static_slot_utilization":
            round(arms["static"]["slot_utilization"], 3),
        "token_identity_mismatches": mismatches,
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
        # fake the multi-chip world BEFORE backend init (same trick as
        # tests/conftest.py) so the slot sharding is real, not size-1
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
    p.add_argument("--requests", type=int, default=40)
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--horizon", type=int, default=288)
    p.add_argument("--block", type=int, default=16)
    p.add_argument("--max-prompt", type=int, default=32)
    p.add_argument("--min-prompt", type=int, default=4)
    p.add_argument("--min-new", type=int, default=8)
    p.add_argument("--max-new", type=int, default=96)
    p.add_argument("--round-tokens", type=int, default=4)
    p.add_argument("--arrival-ms", type=float, default=2.0,
                   help="Poisson mean interarrival (open-loop trace); "
                        "the default saturates the mesh so throughput "
                        "measures service rate and TTFT includes the "
                        "queueing static batching inflicts")
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--decode-tier", type=int, default=1,
                   help="run the ISSUE 14 arms (prefix-share, "
                        "sampled, speculative); 0 skips them")
    p.add_argument("--prefix-requests", type=int, default=24,
                   help="requests in the shared-prefix and sampled "
                        "arms")
    p.add_argument("--shared-prefix", type=int, default=16,
                   help="tokens of common system prompt in the "
                        "prefix-share arm (block-aligned shares best)")
    p.add_argument("--spec-k", type=int, default=4)
    p.add_argument("--spec-prompts", type=int, default=6)
    p.add_argument("--spec-new", type=int, default=48,
                   help="tokens per prompt in the speculative arm")
    p.add_argument("--ragged-tier", type=int, default=1,
                   help="run the ragged-round arms (TTFT independence "
                        "+ in-engine speculation); 0 skips them")
    p.add_argument("--ragged-requests", type=int, default=12,
                   help="short prompts per TTFT-independence pass")
    p.add_argument("--long-prompt", type=int, default=96,
                   help="long co-admitted prompt length (block-"
                        "rounded) in the ragged-ttft arm")
    p.add_argument("--ttft-noise-bar", type=float, default=1.75,
                   help="max allowed short-prompt TTFT p50 ratio "
                        "(co-admit / solo) before the independence "
                        "assert trips")
    p.add_argument("--rounds", type=int, default=3,
                   help="interleaved replay rounds per arm (best round "
                        "counts — scheduler-noise rejection)")
    p.add_argument("--devices", type=int, default=8,
                   help="virtual device count for the cpu platform")
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
                 "vocab", "d_model", "heads", "n_layers", "seed",
                 "rounds", "devices", "decode_tier", "prefix_requests",
                 "shared_prefix", "spec_k", "spec_prompts",
                 "spec_new", "ragged_tier", "ragged_requests",
                 "long_prompt"):
        cmd += [f"--{name.replace('_', '-')}",
                str(getattr(args, name))]
    cmd += ["--arrival-ms", str(args.arrival_ms),
            "--ttft-noise-bar", str(args.ttft_noise_bar)]
    if args.platform:
        cmd += ["--platform", args.platform]
    return run_child_with_retries(
        cmd, os.path.dirname(here), args.timeouts, METRIC, UNIT,
        record=args.platform is None,
        match={"requests": args.requests, "slots": args.slots,
               "horizon": args.horizon, "d_model": args.d_model,
               "n_layers": args.n_layers, "max_new": args.max_new,
               "seed": args.seed})


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
