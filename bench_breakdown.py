"""Per-component step-time breakdown for the flagship transformer.

The jit-subtraction method (no profiler trace): each architectural
component is
compiled and timed as its OWN jitted program (with the same remat
policy, dtypes, and shard_map wrapping as inside the full step), and
the full step anchors the total.  Components deliberately overlap the
step (attention+MLP+head+opt ≈ fwd_bwd + opt ≈ step); the residuals
between those sums and the anchors measure what decomposition hides
(fusion across boundaries, dispatch overhead).

Per component it also records XLA ``cost_analysis`` FLOPs and
bytes-accessed, so each can be placed on the v5e roofline
(peak 197 Tbf16FLOP/s, ~819 GB/s HBM => ridge ~240 FLOPs/byte).

Output: one JSON line per component (``BREAKDOWN <json>``) and a final
``{"metric": "transformer_step_breakdown", ...}`` summary line; the
whole record is also written to chiprun_out/breakdown_raw.json.
Not a driver gate — a diagnostic run via ``python bench_breakdown.py``.
"""

import argparse
import json
import os
import sys
import time

from _bench_common import peak_flops, pin_platform

HERE = os.path.dirname(os.path.abspath(__file__))
RAW_PATH = os.path.join(HERE, "chiprun_out", "breakdown_raw.json")

# v5e HBM bandwidth (public spec): the roofline's other axis
HBM_GBPS = {"v5 lite": 819.0, "v5e": 819.0, "v4": 1228.0, "v5p": 2765.0}


def _hbm_gbps(kind: str) -> float:
    k = kind.lower()
    for key, bw in HBM_GBPS.items():
        if key in k:
            return bw
    raise ValueError(f"no HBM bandwidth on record for {kind!r}")


def _cost(compiled):
    ca = compiled.cost_analysis()
    return float(ca.get("flops", 0)), float(ca.get("bytes accessed", 0))


def _time(fn, args, warmup=2, iters=8):
    """Compile, time ``iters`` calls, return (ms/call, flops, bytes)."""
    import jax

    compiled = fn.lower(*args).compile()
    flops, bts = _cost(compiled)

    for _ in range(warmup):
        out = compiled(*args)
    if warmup:
        jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = compiled(*args)
    jax.block_until_ready(out)
    ms = (time.perf_counter() - t0) / iters * 1e3
    return ms, flops, bts


def run(batch=8, seq=2048, d_model=1024, n_layers=24, n_heads=16,
        n_kv_heads=0, attention="flash", remat_policy="full",
        warmup=2, iters=8):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu.models import (
        TransformerConfig, init_transformer, make_train_step,
        param_specs, shard_params,
    )
    from chainermn_tpu.models.transformer import (
        _attention, _block, _lm_head, _mlp, _rms_norm,
    )
    from chainermn_tpu.parallel import MeshConfig

    cfg = TransformerConfig(
        vocab_size=32000, d_model=d_model, n_heads=n_heads,
        n_kv_heads=n_kv_heads, d_head=d_model // n_heads,
        d_ff=4 * d_model, n_layers=n_layers, max_seq=seq,
        attention=attention, dtype="bfloat16",
        remat=remat_policy != "none",
        remat_policy=remat_policy if remat_policy != "none" else "full",
    )
    cd = cfg.compute_dtype
    mc = MeshConfig(data=1, devices=jax.devices()[:1])
    mesh = mc.mesh
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
    opt = optax.adamw(3e-4)
    opt_state = jax.jit(opt.init)(params)
    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size,
                                         (batch, seq + 1)), jnp.int32)
    x, y = toks[:, :seq], toks[:, 1:]
    specs = param_specs(cfg)
    rows = {}

    def add(name, ms, flops, bts, note=""):
        dev = jax.devices()[0]
        # a CPU smoke run has no utilization to report; an accelerator
        # the peak tables do not know is an error
        peak = bw = None
        if dev.platform != "cpu":
            peak = peak_flops(dev.device_kind)
            bw = _hbm_gbps(dev.device_kind)
        row = {
            "ms": round(ms, 2),
            "flops": flops, "bytes": bts,
            "intensity_flops_per_byte":
                round(flops / bts, 1) if bts else None,
            "achieved_tflops": round(flops / (ms / 1e3) / 1e12, 1)
                if ms and flops else None,
            "achieved_gbps": round(bts / (ms / 1e3) / 1e9, 1)
                if ms and bts else None,
            "mfu": round(flops / (ms / 1e3) / peak, 3)
                if ms and flops and peak else None,
            "hbm_util": round(bts / (ms / 1e3) / 1e9 / bw, 3)
                if ms and bts and bw else None,
        }
        if note:
            row["note"] = note
        rows[name] = row
        print("BREAKDOWN " + json.dumps({"component": name, **row}),
              flush=True)

    # ---- anchor: the full train step (donates params: thread the
    # carry instead of re-passing deleted buffers) ---------------------- #
    step = make_train_step(mc, cfg, opt)
    compiled = step.lower(params, opt_state, x, y).compile()
    s_fl, s_bt = _cost(compiled)
    p2, o2 = params, opt_state
    for _ in range(warmup):
        p2, o2, loss = compiled(p2, o2, x, y)
    if warmup:
        jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        p2, o2, loss = compiled(p2, o2, x, y)
    jax.block_until_ready(loss)
    add("full_step", (time.perf_counter() - t0) / iters * 1e3, s_fl, s_bt)
    del p2, o2
    # re-materialise the donated trees for the component programs
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
    opt_state = jax.jit(opt.init)(params)

    # ---- forward-only and forward+backward --------------------------- #
    from chainermn_tpu.models.transformer import lm_loss

    def fwd(p, xx, yy):
        return lax.pmean(lm_loss(cfg, p, xx, yy),
                         ("data", "expert", "seq"))

    tok_spec = P(("data", "expert"), "seq")
    sm = lambda f, outs: jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(specs, tok_spec, tok_spec),
        out_specs=outs))
    ms, fl, bt = _time(sm(fwd, P()), (params, x, y), warmup, iters)
    add("fwd_only", ms, fl, bt)
    ms, fl, bt = _time(
        sm(lambda p, xx, yy: jax.value_and_grad(fwd)(p, xx, yy),
           (P(), specs)),
        (params, x, y), warmup, iters)
    add("fwd_bwd", ms, fl, bt,
        "full step minus this = optimizer + donation overhead")

    # ---- per-component stacks (same remat wrapper as the real step) -- #
    blocks = jax.tree.map(lambda a: jnp.squeeze(a, 0), params["blocks"])
    bspecs = jax.tree.map(lambda s: P(*s[1:]), specs["blocks"])
    h0 = jax.random.normal(
        jax.random.PRNGKey(1), (batch, seq, d_model), cd)

    def stack(layer_fn):
        def f(blks, h):
            vary = lambda t: lax.pcast(t, ("pipe",), to="varying")

            def body(carry, blk):
                out = cfg.checkpoint_fn(layer_fn)(carry, blk)
                return out, None

            out, _ = lax.scan(body, vary(h), blks)
            return lax.pmean(
                jnp.mean(lax.psum(out, "pipe").astype(jnp.float32)),
                ("data", "expert", "seq"))

        def g(blks, h):
            l, grads = jax.value_and_grad(f)(blks, h)
            return l, grads

        return jax.jit(jax.shard_map(
            g, mesh=mesh,
            in_specs=(bspecs, P(("data", "expert"), "seq")),
            out_specs=(P(), bspecs)))

    def attn_only(h, blk):
        return _attention(cfg, h, blk)

    def mlp_only(h, blk):
        out, _aux = _mlp(cfg, h, blk)
        return out

    ms, fl, bt = _time(stack(attn_only), (blocks, h0), warmup, iters)
    add("attention_stack", ms, fl, bt,
        f"{n_layers} pre-LN attention sublayers, fwd+bwd, remat")
    ms, fl, bt = _time(stack(mlp_only), (blocks, h0), warmup, iters)
    add("mlp_stack", ms, fl, bt,
        f"{n_layers} pre-LN MLP sublayers, fwd+bwd, remat")

    # ---- LM head + loss (the vocab-32k matmul pair) ------------------ #
    def head_loss(p, h, yy):
        hN = _rms_norm(h, p["ln_f"])
        logits = _lm_head(cd, hN, p["embed"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(
            logp, yy[..., None], axis=-1).squeeze(-1)
        return lax.pmean(nll.mean(), ("data", "expert", "seq"))

    hp = {"ln_f": params["ln_f"], "embed": params["embed"]}
    hspecs = {"ln_f": P(), "embed": P()}
    ms, fl, bt = _time(
        jax.jit(jax.shard_map(
            lambda p, h, yy: jax.value_and_grad(head_loss)(p, h, yy),
            mesh=mesh,
            in_specs=(hspecs, P(("data", "expert"), "seq"),
                      tok_spec),
            out_specs=(P(), hspecs))),
        (hp, h0, y), warmup, iters)
    add("lm_head_loss", ms, fl, bt,
        "final norm + weight-tied head + softmax xent, fwd+bwd")

    # ---- chunked-vocab variant: same math
    # through _head_nll's custom VJP — never materialises the full
    # (B, T, 32k) fp32 logits, recomputes per chunk in backward.  The
    # lm_head_loss row above is its control; the live delta decides
    # whether loss_chunk becomes the large-vocab default. ------------- #
    from chainermn_tpu.models.transformer import _head_nll

    for chunk in (256, 512):
        if seq % chunk:   # CPU smoke configs run tiny seqs
            continue

        def head_loss_chunked(p, h, yy, _c=chunk):
            hN = _rms_norm(h, p["ln_f"])
            nll = _head_nll(cd, _c, hN, p["embed"], yy) / yy.size
            return lax.pmean(nll, ("data", "expert", "seq"))

        ms, fl, bt = _time(
            jax.jit(jax.shard_map(
                lambda p, h, yy: jax.value_and_grad(
                    head_loss_chunked)(p, h, yy),
                mesh=mesh,
                in_specs=(hspecs, P(("data", "expert"), "seq"),
                          tok_spec),
                out_specs=(P(), hspecs))),
            (hp, h0, y), warmup, iters)
        add(f"lm_head_loss_chunked_{chunk}", ms, fl, bt,
            f"loss_chunk={chunk}: chunked custom-VJP head, no full "
            "logits tensor; compare against lm_head_loss")

    # ---- embedding lookup -------------------------------------------- #
    def embed_fn(p, xx):
        return lax.pmean(jnp.mean(p["embed"][xx].astype(jnp.float32)),
                         ("data", "expert", "seq"))

    ms, fl, bt = _time(
        jax.jit(jax.shard_map(
            lambda p, xx: jax.value_and_grad(embed_fn)(p, xx),
            mesh=mesh,
            in_specs=({"embed": P()}, tok_spec),
            out_specs=(P(), {"embed": P()}))),
        ({"embed": params["embed"]}, x), warmup, iters)
    add("embed", ms, fl, bt, "token lookup fwd + scatter-add bwd")

    # ---- optimizer update -------------------------------------------- #
    grads = jax.tree.map(jnp.zeros_like, params)

    def opt_fn(g, s, p):
        import optax as _ox

        u, s2 = opt.update(g, s, p)
        return _ox.apply_updates(p, u), s2

    ms, fl, bt = _time(jax.jit(opt_fn), (grads, opt_state, params),
                       warmup, iters)
    add("optimizer", ms, fl, bt, "adamw update + apply, undonated")

    # ---- summary ----------------------------------------------------- #
    comp_sum = sum(rows[k]["ms"] for k in
                   ("attention_stack", "mlp_stack", "lm_head_loss",
                    "embed", "optimizer"))
    record = {
        "metric": "transformer_step_breakdown",
        "config": {"batch": batch, "seq": seq, "d_model": d_model,
                   "n_layers": n_layers, "n_heads": n_heads,
                   "n_kv_heads": n_kv_heads, "attention": attention,
                   "remat_policy": remat_policy},
        "device_kind": jax.devices()[0].device_kind,
        "components": rows,
        "component_sum_ms": round(comp_sum, 2),
        "decomposition_residual_ms":
            round(rows["full_step"]["ms"] - comp_sum, 2),
    }
    os.makedirs(os.path.dirname(RAW_PATH), exist_ok=True)
    with open(RAW_PATH, "w") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    return record


def analyze(batch=8, seq=2048, d_model=1024, n_layers=24, n_heads=16,
            n_kv_heads=0, attention="flash", remat_policy="full",
            vocab=32000, loss_chunk=0, record=True):
    """First-principles roofline for the train step: closed-form FLOPs
    and HBM bytes (every term itemised in the output), each TPU
    generation's step-time floor ``max(flops/peak, bytes/bw)``, and
    the MFU ceiling that floor implies.  Backend-independent on
    purpose: XLA ``cost_analysis`` on a non-TPU backend counts
    scan/while bodies ONCE (measured here: a 300M step reported 4.8
    TFLOPs where the per-layer arithmetic alone is ~33), so an
    abstract-compile approach silently lies off-chip — arithmetic
    doesn't."""
    D, L, V, B, T = d_model, n_layers, vocab, batch, seq
    kv = n_kv_heads or n_heads
    tokens = B * T
    N_block = L * (D * D * (1 + 2 * kv / n_heads)   # q + k + v projs
                   + D * D                          # wo
                   + 8 * D * D)                     # mlp w1 + w2
    N = N_block + V * D                             # + tied embed/head
    # matmul flops: 2 MACs per weight per token, fwd; bwd doubles
    # (grad wrt inputs + wrt weights); full remat re-runs fwd once,
    # `dots` saves matmul outputs so recompute is ~elementwise (~0)
    rec = {"full": 1.0, "dots": 0.15, "none": 0.0}[remat_policy]
    fwd_mm = 2.0 * tokens * N
    # flash attention core, causal: QK^T + PV = 4·B·T²·D·(1/2), fwd
    fwd_attn = 2.0 * L * B * T * T * D
    F = (3.0 + rec) * (fwd_mm + fwd_attn)
    flops_terms = {
        "matmul_fwd": fwd_mm, "attention_fwd": fwd_attn,
        "bwd_factor": 2.0, "remat_recompute_factor": rec,
    }
    # HBM bytes: fp32 params/grads/moments, bf16 activations
    p4 = N * 4.0
    bytes_terms = {
        # fwd + bwd + recompute read the (fp32) weights
        "param_reads": (2.0 + rec) * p4,
        "grad_write_read": 2.0 * p4,
        # adamw: read p/m/v, write p/m/v (+ grad read counted above)
        "optimizer": 6.0 * p4,
        # full remat saves only the L layer-boundary activations
        # (write fwd + read bwd); `dots` saves matmul outputs (~6
        # D-wide tensors per layer: qkv, attn-out, wo, w1, w2 +
        # norms); no remat saves every intermediate incl. the 4D-wide
        # MLP hidden (~10 D-widths/layer, rough — flash keeps the T²
        # score internals out of HBM either way)
        "activation_checkpoints":
            (2.0 * L * B * T * D * 2)
            * {"full": 1.0, "dots": 6.0, "none": 10.0}[remat_policy],
        # the fp32 logits tensor: written fwd, read in bwd (XLA fuses
        # log-softmax into consumers but the (B,T,V) buffer itself is
        # resident unless loss_chunk skips it)
        "logits": 0.0 if loss_chunk else 2.0 * tokens * V * 4.0,
        "embed_io": tokens * D * 2.0 * 2,      # lookup out + grad in
    }
    Bt = float(sum(bytes_terms.values()))
    F = float(F)
    out = {
        "metric": "transformer_step_roofline",
        "config": {"batch": batch, "seq": seq, "d_model": d_model,
                   "n_layers": n_layers, "n_heads": n_heads,
                   "n_kv_heads": n_kv_heads, "attention": attention,
                   "remat_policy": remat_policy, "vocab": vocab,
                   "loss_chunk": loss_chunk},
        "n_params": int(N),
        "flops": F, "bytes": Bt,
        "flops_terms": {k: float(v) for k, v in flops_terms.items()},
        "bytes_terms": {k: round(v / 1e9, 2) for k, v
                        in bytes_terms.items()},
        "bytes_unit_note": "bytes_terms in GB",
        "intensity_flops_per_byte": round(F / Bt, 1),
        "rooflines": {},
    }
    for kind, peak, bw in (("v5e", 197e12, 819e9),
                           ("v4", 275e12, 1228e9),
                           ("v5p", 459e12, 2765e9)):
        t_c, t_m = F / peak, Bt / bw
        t = max(t_c, t_m)
        out["rooflines"][kind] = {
            "t_compute_ms": round(t_c * 1e3, 1),
            "t_memory_ms": round(t_m * 1e3, 1),
            "bound": "memory" if t_m > t_c else "compute",
            "step_floor_ms": round(t * 1e3, 1),
            "tokens_per_sec_ceiling": round(tokens / t),
            "mfu_ceiling": round(min(1.0, t_c / t), 3),
        }
    # merge into the raw record without clobbering a measured breakdown
    if record:
        try:
            with open(RAW_PATH) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            rec = {}
        rec["roofline"] = out
        os.makedirs(os.path.dirname(RAW_PATH), exist_ok=True)
        with open(RAW_PATH, "w") as f:
            json.dump(rec, f, indent=1)
            f.write("\n")
    return out


def main(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--d-model", type=int, default=1024)
    p.add_argument("--n-layers", type=int, default=24)
    p.add_argument("--n-heads", type=int, default=16)
    p.add_argument("--n-kv-heads", type=int, default=0)
    p.add_argument("--attention", default="flash")
    p.add_argument("--remat-policy", default="full",
                   choices=["full", "dots", "none"])
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--iters", type=int, default=8)
    p.add_argument("--platform", default=None)
    p.add_argument("--analyze-only", action="store_true",
                   help="no execution: closed-form first-principles "
                        "FLOPs/bytes (every term itemised) + per-TPU "
                        "roofline floors and MFU ceilings")
    p.add_argument("--no-record", action="store_true",
                   help="analyze-only: print without touching "
                        "the raw record (tests use this)")
    args = p.parse_args(argv)
    pin_platform(args.platform)
    if args.analyze_only:
        print(json.dumps(analyze(
            batch=args.batch, seq=args.seq, d_model=args.d_model,
            n_layers=args.n_layers, n_heads=args.n_heads,
            n_kv_heads=args.n_kv_heads, attention=args.attention,
            remat_policy=args.remat_policy,
            record=not args.no_record)))
        return 0
    record = run(batch=args.batch, seq=args.seq, d_model=args.d_model,
                 n_layers=args.n_layers, n_heads=args.n_heads,
                 n_kv_heads=args.n_kv_heads, attention=args.attention,
                 remat_policy=args.remat_policy, warmup=args.warmup,
                 iters=args.iters)
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
