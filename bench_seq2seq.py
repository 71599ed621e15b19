"""Seq2seq NMT training throughput: real (non-pad) target tokens/sec.

BASELINE.md config 3 — the reference's ``examples/seq2seq`` exercised
*variable-length* batches, whose distributed property was that ragged
per-rank gradients still allreduce cleanly.  Here raggedness enters as
pad + mask (static shapes, one compiled program for every batch; see
``models/seq2seq.py``), so the measured quantity is throughput of REAL
target tokens through the masked LSTM encoder-decoder train step.

No upstream number exists for this config (the reference published only
ResNet figures), so ``vs_baseline`` uses a 100k-tokens/sec yardstick —
order-of-magnitude for a 2×256-unit LSTM NMT step on one chip.  One
child under a timeout, the parent off JAX (``_bench_common``).
"""

import argparse
import json
import os
import sys
import time

from _bench_common import pin_platform, run_child_with_retries

METRIC = "seq2seq_train_real_tokens_per_sec"
UNIT = "tokens/sec"
_YARDSTICK = 100_000.0


def run(batch=256, vocab=8000, units=256, layers=2, max_src=48,
        max_tgt=48, warmup=2, iters=6, steps_per_call=4):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from chainermn_tpu.models import (
        Seq2seqConfig, init_seq2seq, seq2seq_loss,
    )
    from chainermn_tpu.models.seq2seq import EOS, PAD
    from chainermn_tpu.training import fuse_steps

    cfg = Seq2seqConfig(src_vocab=vocab, tgt_vocab=vocab, d_embed=units,
                        d_hidden=units, n_layers=layers)
    params = init_seq2seq(jax.random.PRNGKey(0), cfg)

    # variable-length synthetic batch: lengths uniform in [25%, 100%] of
    # max — the raggedness profile the reference example exercised
    rng = np.random.RandomState(1)

    def ragged(T):
        toks = rng.randint(3, vocab, size=(batch, T)).astype(np.int32)
        lens = rng.randint(max(T // 4, 2), T + 1, size=batch)
        mask = np.arange(T)[None, :] < lens[:, None]
        return np.where(mask, toks, PAD), lens

    src, _ = ragged(max_src)
    tgt, tgt_lens = ragged(max_tgt)
    # tgt contract: each sequence ENDS with EOS
    tgt[np.arange(batch), tgt_lens - 1] = EOS
    real_tokens = int(tgt_lens.sum())
    src, tgt = jnp.asarray(src), jnp.asarray(tgt)

    opt = optax.adam(1e-3)

    def step(carry, src, tgt):
        params, opt_state = carry
        loss, grads = jax.value_and_grad(
            lambda p: seq2seq_loss(cfg, p, src, tgt))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state), loss

    fused = fuse_steps(step, steps_per_call) if steps_per_call > 1 else step
    stepj = jax.jit(fused, donate_argnums=(0,))
    carry = (params, jax.jit(opt.init)(params))

    for _ in range(warmup):
        carry, loss = stepj(carry, src, tgt)
    if warmup:
        jax.block_until_ready(loss)

    t0 = time.perf_counter()
    for _ in range(iters):
        carry, loss = stepj(carry, src, tgt)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0

    n_steps = iters * steps_per_call
    tok_s = real_tokens * n_steps / dt
    return {
        "metric": METRIC,
        "value": round(tok_s, 1),
        "unit": UNIT,
        "vs_baseline": round(tok_s / _YARDSTICK, 3),
        "device_kind": jax.devices()[0].device_kind,
        "step_time_ms": round(dt / n_steps * 1e3, 2),
        "batch": batch,
        "real_tokens_per_batch": real_tokens,
        "pad_fraction": round(1 - real_tokens / (batch * max_tgt), 3),
        "units": units,
        "layers": layers,
        "vocab": vocab,
    }


def _child_main(args):
    pin_platform(args.platform)
    result = run(batch=args.batch, vocab=args.vocab, units=args.units,
                 layers=args.layers, max_src=args.max_src,
                 max_tgt=args.max_tgt, warmup=args.warmup,
                 iters=args.iters, steps_per_call=args.steps_per_call)
    print("BENCH_RESULT " + json.dumps(result))


def main(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", action="store_true")
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--vocab", type=int, default=8000)
    p.add_argument("--units", type=int, default=256)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--max-src", type=int, default=48)
    p.add_argument("--max-tgt", type=int, default=48)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--iters", type=int, default=6)
    p.add_argument("--steps-per-call", type=int, default=4)
    p.add_argument("--platform", default=None)
    p.add_argument("--timeouts", type=int, nargs="+", default=[420])
    args = p.parse_args(argv)
    if args.child:
        _child_main(args)
        return 0
    here = os.path.abspath(__file__)
    cmd = [sys.executable, here, "--child",
           "--batch", str(args.batch), "--vocab", str(args.vocab),
           "--units", str(args.units), "--layers", str(args.layers),
           "--max-src", str(args.max_src), "--max-tgt", str(args.max_tgt),
           "--warmup", str(args.warmup), "--iters", str(args.iters),
           "--steps-per-call", str(args.steps_per_call)]
    if args.platform:
        cmd += ["--platform", args.platform]
    return run_child_with_retries(
        cmd, os.path.dirname(here), args.timeouts, METRIC, UNIT,
        record=args.platform is None,
        match={"batch": args.batch, "units": args.units,
               "layers": args.layers, "vocab": args.vocab})


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
