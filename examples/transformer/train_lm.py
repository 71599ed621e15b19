"""Flagship transformer LM training — every parallel axis from one CLI.

The reference had no transformer (it predates them); this example is
the integration showcase its `examples/` directory played for the DP
era: one script that composes DP × TP × PP × SP × EP on a single
`MeshConfig`, with the trainer/checkpoint stack around it.

Synthetic data with learnable structure (an affine next-token rule
plus noise) so the loss measurably falls within a smoke run — the same
role the reference's synthetic/MNIST data played.

Examples (virtual 8-device pod — export the fake-device flag first):

    export JAX_PLATFORMS=cpu
    export XLA_FLAGS=--xla_force_host_platform_device_count=8

    # DP only
    python train_lm.py --platform cpu --mesh data=8 --steps 30
    # 2-way tensor x 2-way sequence (ring attention) x 2-way data
    python train_lm.py --platform cpu --mesh data=2,model=2,seq=2 \
        --attention ring --steps 30
    # 2-stage 1F1B pipeline x 4-way data, GQA + RoPE
    python train_lm.py --platform cpu --mesh pipe=2,data=4 \
        --schedule 1f1b --n-kv-heads 2 --pos-embedding rope --steps 30
    # Switch-MoE over a 2-way expert axis
    python train_lm.py --platform cpu --mesh data=4,expert=2 --moe
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def parse_mesh(spec: str):
    axes = {}
    for part in filter(None, spec.split(",")):
        k, _, v = part.partition("=")
        axes[k.strip()] = int(v)
    return axes


def check_text_args(path, vocab, seq, tokenized=False):
    """Fail fast on --text-file misconfiguration: called right after
    argument parsing, BEFORE the mesh/params/compile work, so a typo'd
    path or too-small vocab costs seconds, not a full model setup."""
    if vocab < 256 and not tokenized:
        raise SystemExit(
            f"--text-file is byte-level: --vocab {vocab} must be >= 256"
            " (or pass --tokenizer-vocab for a subword vocabulary)")
    if not os.path.exists(path):
        raise SystemExit(f"--text-file {path}: no such file")
    if os.path.getsize(path) < seq + 1:
        raise SystemExit(
            f"{path}: {os.path.getsize(path)} bytes < seq+1 = {seq + 1}")


def _text_windows(data, batch, seq, steps, seed):
    rng = np.random.RandomState(seed)
    for _ in range(steps):
        starts = rng.randint(0, data.size - seq, batch)
        x = np.stack([data[s:s + seq + 1] for s in starts]).astype(
            np.int32)
        yield x[:, :-1], x[:, 1:]


def load_text(path, vocab, seq):
    """Byte corpus split 90/10 into train/held-out ranges (held-out =
    the file's TAIL, never sampled by training, so the reported
    perplexity is honest).  A tail too small for one window folds into
    training and disables eval."""
    check_text_args(path, vocab, seq)
    with open(path, "rb") as f:
        data = np.frombuffer(f.read(), np.uint8)
    cut = int(0.9 * data.size)
    # either side too small for one window => no split, no eval
    if cut < seq + 1 or data.size - cut < seq + 1:
        return data, None
    return data[:cut], data[cut:]


# byte-level real-data contract: bytes ARE the tokens (ids 0-255, so
# --vocab must be >= 256; spare ids go unused); each batch row is a
# random contiguous (seq+1)-byte window over the TRAIN split.  The
# reference's examples consumed real files the same minimal way (no
# tokenizer dependency).  The corpus is read ONCE (load_text) and the
# train/held-out arrays passed around — re-reading between training
# and eval could silently split different file contents.


def load_text_tokenized(path, tok_vocab, seq, ckpt_dir):
    """--tokenizer-vocab path: split the RAW BYTES 90/10 first (the
    held-out text is the same regardless of tokenizer choices), train
    a byte-level BPE on the train split only (training it on held-out
    bytes would leak tail statistics into the vocabulary), then encode
    both sides.  Merges persist as ``bpe.json`` beside the checkpoint;
    a resume loads them instead of retraining — token ids must mean
    the same thing across runs or the resumed model is garbage."""
    from chainermn_tpu.datasets import BPETokenizer, train_bpe

    check_text_args(path, 256, seq, tokenized=True)
    with open(path, "rb") as f:
        raw = f.read()
    cut = int(0.9 * len(raw))
    bpe_path = os.path.join(ckpt_dir, "bpe.json") if ckpt_dir else None
    if bpe_path and os.path.exists(bpe_path):
        tok = BPETokenizer.load(bpe_path)
        if tok.vocab_size > tok_vocab:
            raise SystemExit(
                f"{bpe_path} holds {tok.vocab_size} ids > "
                f"--tokenizer-vocab {tok_vocab}: stale tokenizer from "
                "an earlier run — delete the file or match the flag")
        print(f"loaded tokenizer {bpe_path} ({tok.vocab_size} ids; "
              "delete the file to retrain)")
    else:
        t0 = time.perf_counter()
        tok = train_bpe(raw[:cut], tok_vocab)
        print(f"trained BPE: {tok.vocab_size} ids "
              f"({time.perf_counter() - t0:.1f}s)")
        if bpe_path:
            os.makedirs(ckpt_dir, exist_ok=True)
            tok.save(bpe_path)
            print(f"saved {bpe_path}")
    train = np.asarray(tok.encode(raw[:cut]), np.int32)
    held = np.asarray(tok.encode(raw[cut:]), np.int32)
    if train.size < seq + 1:
        raise SystemExit(
            f"{path}: {train.size} train tokens < seq+1 = {seq + 1}")
    if held.size < seq + 1:
        held = None
    return train, held, tok


def make_batches(vocab, batch, seq, steps, seed=0):
    """Sequences following tok[t+1] = (a*tok[t] + b) % vocab with 10%
    noise — enough structure that a few dozen steps visibly cut loss."""
    rng = np.random.RandomState(seed)
    a, b = 7, 3
    for _ in range(steps):
        x = np.empty((batch, seq + 1), np.int32)
        x[:, 0] = rng.randint(0, vocab, batch)
        for t in range(seq):
            nxt = (a * x[:, t] + b) % vocab
            noise = rng.randint(0, vocab, batch)
            take = rng.rand(batch) < 0.1
            x[:, t + 1] = np.where(take, noise, nxt)
        yield x[:, :-1], x[:, 1:]


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--mesh", default="data=8",
                   help="comma list, e.g. data=2,model=2,seq=2")
    p.add_argument("--attention", default="local",
                   choices=["local", "flash", "ring", "ulysses"])
    p.add_argument("--schedule", default="gpipe",
                   choices=["gpipe", "1f1b", "interleaved"])
    p.add_argument("--pos-embedding", default="learned",
                   choices=["learned", "rope"])
    p.add_argument("--n-kv-heads", type=int, default=0)
    p.add_argument("--window", type=int, default=0)
    p.add_argument("--text-file", default=None,
                   help="train on a REAL text file, byte-level tokens "
                        "(needs --vocab >= 256); default is synthetic "
                        "data")
    p.add_argument("--tokenizer-vocab", type=int, default=0,
                   help="with --text-file: train/load a byte-level BPE "
                        "subword vocabulary of up to this many ids "
                        "(0 = raw bytes).  Merges persist as bpe.json "
                        "beside --checkpoint and round-trip through "
                        "generate.py --tokenizer; held-out perplexity "
                        "is then reported per token AND per byte")
    p.add_argument("--loss-chunk", type=int, default=0,
                   help="chunked-vocab cross-entropy chunk size "
                        "(0 = whole-shard logits)")
    p.add_argument("--vocab-parallel", action="store_true",
                   help="shard the tied embedding's vocab dim over the "
                        "model axis (Megatron vocab TP)")
    p.add_argument("--moe", action="store_true")
    p.add_argument("--router-top-k", type=int, default=1,
                   help="experts per token (1=Switch, 2=GShard top-2)")
    p.add_argument("--seq-layout", default="contiguous",
                   choices=["contiguous", "zigzag"])
    p.add_argument("--fsdp", action="store_true",
                   help="ZeRO-3/FSDP: shard params+grads+optimiser "
                        "state over the data axis (d_model must divide "
                        "by it); weights all-gather per layer")
    p.add_argument("--vocab", type=int, default=128)
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--n-heads", type=int, default=4)
    p.add_argument("--n-layers", type=int, default=4)
    p.add_argument("--seq", type=int, default=32)
    p.add_argument("--batchsize", type=int, default=32)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--checkpoint", default=None,
                   help="directory for a final-state snapshot (resumes "
                        "from it if one exists; for in-run periodic + "
                        "preemption checkpoints see "
                        "extensions.MultiNodeCheckpointer)")
    p.add_argument("--platform", default=None)
    args = p.parse_args()
    if args.tokenizer_vocab and not args.text_file:
        raise SystemExit("--tokenizer-vocab needs --text-file")
    if args.tokenizer_vocab and args.tokenizer_vocab <= 256:
        raise SystemExit(
            f"--tokenizer-vocab {args.tokenizer_vocab} must exceed 256 "
            "(ids 0-255 are the raw bytes; merges come on top)")
    if args.text_file:
        # fail fast, before the mesh/compile work
        check_text_args(args.text_file, args.vocab, args.seq,
                        tokenized=bool(args.tokenizer_vocab))

    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)

    import jax

    from chainermn_tpu.utils import enable_compile_cache

    enable_compile_cache()      # before the first jit

    import jax.numpy as jnp
    import optax

    from chainermn_tpu.models import (
        TransformerConfig, init_transformer, make_train_step,
        shard_params,
    )
    from chainermn_tpu.parallel import MeshConfig
    from chainermn_tpu.training import shard_opt_state
    from chainermn_tpu.utils.serialization import load_state, save_state

    tok = tok_train = tok_held = None
    if args.text_file and args.tokenizer_vocab:
        # before cfg: the learned vocabulary decides the model's vocab
        tok_train, tok_held, tok = load_text_tokenized(
            args.text_file, args.tokenizer_vocab, args.seq,
            args.checkpoint)
        vocab = max(args.vocab, -(-tok.vocab_size // 128) * 128)
        if vocab != args.vocab:
            print(f"model vocab {vocab} (tokenizer {tok.vocab_size} "
                  "ids, padded up to a 128-multiple for clean "
                  "sharding and MXU tiling)")
            args.vocab = vocab

    axes = parse_mesh(args.mesh)
    mc = MeshConfig(**axes)
    pipe = axes.get("pipe", 1)
    V = 2 if args.schedule == "interleaved" else 1
    cfg = TransformerConfig(
        vocab_size=args.vocab, d_model=args.d_model,
        n_heads=args.n_heads, d_head=args.d_model // args.n_heads,
        n_kv_heads=args.n_kv_heads, d_ff=4 * args.d_model,
        n_layers=args.n_layers, max_seq=args.seq,
        attention=args.attention,
        attention_window=args.window,
        pos_embedding=args.pos_embedding,
        seq_layout=args.seq_layout,
        moe=args.moe, n_experts=max(2 * axes.get("expert", 1), 2),
        router_top_k=args.router_top_k if args.moe else 1,
        loss_chunk=args.loss_chunk,
        vocab_parallel=args.vocab_parallel,
        num_microbatches=2 if pipe > 1 else 1,
        pipeline_schedule=args.schedule, virtual_pipe=V,
        fsdp=args.fsdp,
        dtype="float32", remat=False,
    )
    opt = optax.adamw(args.lr)
    start = 0
    ckpt_file = (os.path.join(args.checkpoint, "lm_state.npz")
                 if args.checkpoint else None)
    saved = (load_state(ckpt_file)
             if ckpt_file and os.path.exists(ckpt_file) else None)
    saved_pipe = int(saved.get("pipe", pipe)) if saved else pipe
    saved_v = int(saved.get("virtual_pipe", V)) if saved else V
    if saved is not None and (saved_pipe, saved_v) != (pipe, V):
        # elastic resume: the checkpoint was grouped for a different
        # pipe mesh — regroup the block stack and re-lay params + Adam
        # state onto THIS mesh (reference parity was identical world
        # size only; see models.reshard_train_state).  No fresh init on
        # this path: a second full state resident next to the resharded
        # one would double peak memory exactly where large models hurt.
        from chainermn_tpu.models import reshard_train_state

        params, opt_state = reshard_train_state(
            mc, cfg, opt, saved["params"], saved["opt"],
            from_pipe=saved_pipe, from_virtual=saved_v)
        print(f"regrouped checkpoint pipe={saved_pipe}/V={saved_v} "
              f"-> pipe={pipe}/V={V}")
    else:
        params = shard_params(
            mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg, pipe))
        # pins the state's shardings to the params' (with --fsdp the
        # Adam moments land shard-width; plain jit(init) would
        # replicate them)
        opt_state = shard_opt_state(opt, params)
        if saved is not None:
            # same grouping: re-place on the mesh via device_put against
            # the freshly built (correctly sharded) state, NOT bare
            # jnp.asarray — with --fsdp that would re-materialise params
            # AND both Adam moments replicated, forfeiting exactly the
            # residency the flag buys
            def replace_like(saved_tree, like_tree):
                return jax.tree.map(
                    lambda saved_leaf, like: jax.device_put(
                        jnp.asarray(saved_leaf), like.sharding),
                    saved_tree, like_tree)

            params = replace_like(saved["params"], params)
            opt_state = replace_like(saved["opt"], opt_state)
    if saved is not None:
        start = int(saved["step"])
        print(f"resumed at step {start}")
    step = make_train_step(mc, cfg, opt)
    if start >= args.steps:
        print(f"nothing to do: resumed step {start} >= --steps "
              f"{args.steps}")
        return None

    # zigzag layout contract: the model expects tokens permuted by
    # zigzag_indices (device r holds chunks r and 2S-1-r, balancing the
    # causal ring); inputs AND targets permute identically, so the
    # next-token alignment is preserved
    perm = None
    if args.seq_layout == "zigzag":
        from chainermn_tpu.parallel import zigzag_indices

        perm = zigzag_indices(axes.get("seq", 1), args.seq).reshape(-1)

    heldout = None
    if args.text_file:
        if tok is not None:
            train_data, heldout = tok_train, tok_held
        else:
            train_data, heldout = load_text(
                args.text_file, args.vocab, args.seq)
        batches = _text_windows(
            train_data, args.batchsize, args.seq,
            args.steps - start, seed=start)
    else:
        batches = make_batches(args.vocab, args.batchsize, args.seq,
                               args.steps - start, seed=start)
    first = last = None
    t0 = time.perf_counter()
    for i, (x, y) in enumerate(batches):
        if perm is not None:
            x, y = x[:, perm], y[:, perm]
        params, opt_state, loss = step(
            params, opt_state, jnp.asarray(x), jnp.asarray(y))
        loss = float(loss)
        if first is None:
            first = loss
        last = loss
        if (start + i) % 10 == 0:
            print(f"step {start + i:4d}  loss {loss:.4f}")
    print(f"loss {first:.4f} -> {last:.4f} over {args.steps - start} "
          f"steps ({time.perf_counter() - t0:.1f}s) on mesh {mc}")

    if not np.isfinite(last):
        # never persist a diverged state — a resume would train from it
        # (and a held-out eval of diverged params would just print nan)
        raise SystemExit("non-finite loss")

    if args.text_file:
        # held-out perplexity on the file's tail (never sampled by
        # training) — the honest generalisation number for the run.
        # With a tokenizer, report per-token AND per-byte: per-byte
        # (exp of total nll over decoded byte count) is the number
        # comparable across vocabularies, byte-level runs included.
        if heldout is None:
            print("held-out eval skipped: file too small for a 90/10 "
                  "split at this --seq")
        else:
            from chainermn_tpu.models import make_forward_fn

            fwd = make_forward_fn(mc, cfg)
            total_nll = total_tokens = total_bytes = 0.0
            for x, y in _text_windows(
                    heldout, args.batchsize, args.seq, 4, seed=99):
                if perm is not None:
                    x, y = x[:, perm], y[:, perm]
                logp = np.asarray(jax.nn.log_softmax(
                    fwd(params, jnp.asarray(x)), axis=-1))
                total_nll += float(-np.take_along_axis(
                    logp, np.asarray(y)[..., None], axis=-1).sum())
                total_tokens += y.size
                total_bytes += (tok.n_bytes(y.reshape(-1))
                                if tok is not None else y.size)
            tok_ppl = float(np.exp(total_nll / total_tokens))
            byte_ppl = float(np.exp(total_nll / total_bytes))
            if tok is not None:
                print(f"held-out token perplexity {tok_ppl:.2f} "
                      f"(uniform over the {tok.vocab_size} tokenizer "
                      f"ids would be {tok.vocab_size}); "
                      f"byte perplexity {byte_ppl:.2f} at "
                      f"{total_bytes / total_tokens:.2f} bytes/token")
            else:
                print(f"held-out byte perplexity {byte_ppl:.2f} "
                      f"(uniform would be {args.vocab})")
    if ckpt_file:
        save_state(ckpt_file, {
            "params": jax.tree.map(np.asarray, params),
            "opt": jax.tree.map(np.asarray, opt_state),
            "step": args.steps,
            # the pipe grouping this state was SAVED with, so a resume
            # on a different mesh knows how to regroup (elastic resume)
            "pipe": pipe,
            "virtual_pipe": V,
        })
        print(f"saved {ckpt_file}")
    return last


if __name__ == "__main__":
    main()
