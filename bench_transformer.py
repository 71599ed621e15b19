"""Flagship transformer single-chip training benchmark: tokens/sec + MFU.

The reference had no transformer; its perf story was ResNet-50 images/s
(bench.py).  This measures the beyond-reference flagship — a decoder LM
with the Pallas flash-attention kernel — so the long-context path has a
recorded number too.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", ...}:
value = training tokens/sec on one chip, vs_baseline uses the chip's
peak-MFU-50% token rate as 1.0 (i.e. vs_baseline ≈ mfu/0.5, an
absolute-efficiency yardstick rather than a reference number, since the
reference never trained transformers).  Same hermetic child-process
timeout/retry pattern as bench.py (the TPU backend init can hang).
"""

import argparse
import json
import re
import os
import sys
import time

from _bench_common import peak_flops, pin_platform, run_child_with_retries

METRIC = "transformer_train_tokens_per_sec_per_chip"
UNIT = "tokens/sec/chip"


def run(batch=8, seq=2048, d_model=1024, n_layers=24, n_heads=16,
        n_kv_heads=0, warmup=3, iters=10, attention="flash",
        remat_policy="full", loss_chunk=0, bwd_blocks="",
        mu_dtype=""):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from chainermn_tpu.models import (
        TransformerConfig, init_transformer, make_train_step, shard_params,
    )
    from chainermn_tpu.parallel import MeshConfig

    bwd_bq, bwd_bk = ((int(v) for v in bwd_blocks.split("x"))
                      if bwd_blocks else (0, 0))
    cfg = TransformerConfig(
        vocab_size=32000, d_model=d_model, n_heads=n_heads,
        n_kv_heads=n_kv_heads, d_head=d_model // n_heads,
        d_ff=4 * d_model, n_layers=n_layers, max_seq=seq,
        attention=attention, dtype="bfloat16",
        # remat: the production setting — without it this 335M config's
        # activations alone overflow a 16G-HBM chip (20.3G requested) at
        # the default batch; --remat-policy none turns it off for
        # smaller batches.  MFU still counts model FLOPs (6PT), not the
        # recompute.
        remat=remat_policy != "none",
        remat_policy=remat_policy if remat_policy != "none" else "full",
        loss_chunk=loss_chunk,
        # "QxK" adopts a bench_attention --sweep winner at step scale
        flash_bwd_block_q=bwd_bq, flash_bwd_block_k=bwd_bk,
    )
    mc = MeshConfig(data=1, devices=jax.devices()[:1])
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
    # mu_dtype="bfloat16" halves the first-moment HBM traffic (the
    # roofline puts Adam state at 9.2 GB/step = an 11 ms floor on
    # v5e); the second moment stays fp32 (sqrt-precision-sensitive)
    opt = optax.adamw(3e-4, mu_dtype=mu_dtype or None)
    opt_state = jax.jit(opt.init)(params)
    step = make_train_step(mc, cfg, opt)

    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size,
                                         (batch, seq + 1)), jnp.int32)
    x, y = toks[:, :seq], toks[:, 1:]

    n_params = sum(p.size for p in jax.tree.leaves(params))
    tokens_per_step = batch * seq
    # 6·P·T dense-training estimate + exact attention term
    # (12·L·D·T²·B fwd+bwd ≈ included below as 2·fwd)
    attn_flops = 3 * 2 * 2 * n_layers * batch * seq * seq * d_model
    flops_per_step = 6 * n_params * tokens_per_step + attn_flops

    for _ in range(warmup):
        params, opt_state, loss = step(params, opt_state, x, y)
    if warmup:
        jax.block_until_ready(loss)

    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt_state, loss = step(params, opt_state, x, y)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0

    tok_s = tokens_per_step * iters / dt
    dev = jax.devices()[0]
    kind = dev.device_kind
    # a CPU smoke run has no utilization to report; an accelerator the
    # peak table does not know is an error (peak_flops raises)
    mfu = None if dev.platform == "cpu" else (
        flops_per_step * iters / dt / peak_flops(kind))
    return {
        "metric": METRIC,
        "value": round(tok_s, 1),
        "unit": UNIT,
        "vs_baseline": round(mfu / 0.5, 3) if mfu is not None else None,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "device_kind": kind,
        "step_time_ms": round(dt / iters * 1e3, 2),
        "batch": batch, "seq": seq,
        "d_model": d_model, "n_layers": n_layers,
        "n_params": int(n_params),
        "attention": attention,
        "n_kv_heads": n_kv_heads,
        "remat_policy": remat_policy,
        "loss_chunk": loss_chunk,
        "bwd_blocks": bwd_blocks,
        "mu_dtype": mu_dtype,
        "loss": round(float(loss), 3),
    }


def _child_main(args):
    pin_platform(args.platform)
    result = run(batch=args.batch, seq=args.seq, d_model=args.d_model,
                 n_layers=args.n_layers, n_heads=args.n_heads,
                 n_kv_heads=args.n_kv_heads, warmup=args.warmup,
                 iters=args.iters, attention=args.attention,
                 remat_policy=args.remat_policy,
                 loss_chunk=args.loss_chunk,
                 bwd_blocks=args.bwd_blocks,
                 mu_dtype=args.mu_dtype)
    print("BENCH_RESULT " + json.dumps(result))


def _parent_main(args):
    here = os.path.abspath(__file__)
    cmd = [sys.executable, here, "--child",
           "--batch", str(args.batch), "--seq", str(args.seq),
           "--d-model", str(args.d_model),
           "--n-layers", str(args.n_layers),
           "--n-heads", str(args.n_heads),
           "--n-kv-heads", str(args.n_kv_heads),
           "--warmup", str(args.warmup), "--iters", str(args.iters),
           "--attention", args.attention,
           "--remat-policy", args.remat_policy,
           "--loss-chunk", str(args.loss_chunk)]
    if args.bwd_blocks:
        cmd += ["--bwd-blocks", args.bwd_blocks]
    if args.mu_dtype:
        cmd += ["--mu-dtype", args.mu_dtype]
    if args.platform:
        cmd += ["--platform", args.platform]
    return run_child_with_retries(
        cmd, os.path.dirname(here), args.timeouts, METRIC, UNIT,
        record=args.platform is None,
        match={"batch": args.batch, "seq": args.seq,
               "d_model": args.d_model, "n_layers": args.n_layers,
               "attention": args.attention,
               "loss_chunk": args.loss_chunk,
               "bwd_blocks": args.bwd_blocks,
               "mu_dtype": args.mu_dtype})


def _parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", action="store_true")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--d-model", type=int, default=1024)
    p.add_argument("--n-layers", type=int, default=24)
    p.add_argument("--n-heads", type=int, default=16)
    p.add_argument("--n-kv-heads", type=int, default=0)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--attention", default="flash",
                   choices=["flash", "local", "ring", "ulysses"])
    p.add_argument("--mu-dtype", default="",
                   help="optax mu_dtype override, e.g. bfloat16: "
                        "halves Adam first-moment HBM traffic")
    p.add_argument("--bwd-blocks", default="",
                   help='"QxK" flash backward-kernel tiling override '
                        "(adopt a bench_attention --sweep winner at "
                        "full step scale)")
    p.add_argument("--loss-chunk", type=int, default=0,
                   help="chunked-vocab cross-entropy chunk size "
                        "(0 = whole-shard logits); A/B the SPEED.md "
                        "candidate on hardware")
    p.add_argument("--remat-policy", default="full",
                   choices=["full", "dots", "none"])
    p.add_argument("--platform", default=None)
    p.add_argument("--timeouts", type=int, nargs="+", default=[480])
    args = p.parse_args(argv)
    if args.bwd_blocks and not re.fullmatch(r"\d+x\d+",
                                            args.bwd_blocks):
        p.error(f'--bwd-blocks must look like "512x1024", '
                f'got {args.bwd_blocks!r}')
    return args


if __name__ == "__main__":
    args = _parse_args(sys.argv[1:])
    if args.child:
        _child_main(args)
    else:
        sys.exit(_parent_main(args))
