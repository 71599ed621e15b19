"""Text generation with the flagship transformer — KV cache, beam
search, and weight-only int8 from one CLI.

The reference's only generation path was the seq2seq example's greedy
LSTM loop; this is its transformer-era counterpart.  Runs from a
checkpoint written by ``train_lm.py`` (so `train → generate` is a
complete loop) or from random init for a smoke run.

Examples (virtual pod or real chip):

    # greedy, from a train_lm.py checkpoint
    python generate.py --checkpoint ck --prompt 5,11,2 --max-len 32
    # temperature sampling, 2-way tensor-parallel mesh
    python generate.py --mesh data=4,model=2 --temperature 0.8
    # beam search over int8-quantized weights
    python generate.py --beam 4 --int8
"""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from train_lm import parse_mesh  # noqa: E402  (sibling example)


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--mesh", default="data=-1",
                   help="decode meshes shard batch (data/expert), "
                        "heads (model), and layers + KV cache (pipe — "
                        "S-phase hand-off, S-fold model capacity); "
                        "seq must be 1")
    p.add_argument("--vocab", type=int, default=128)
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--n-heads", type=int, default=4)
    p.add_argument("--n-kv-heads", type=int, default=0)
    p.add_argument("--n-layers", type=int, default=4)
    p.add_argument("--pos-embedding", default="learned",
                   choices=["learned", "rope"])
    p.add_argument("--max-len", type=int, default=32)
    p.add_argument("--prompt", default="1,2,3",
                   help="comma-separated token ids (one sequence, "
                        "repeated across the batch)")
    p.add_argument("--tokenizer", default=None,
                   help="bpe.json written by train_lm.py "
                        "--tokenizer-vocab: enables --prompt-text and "
                        "decodes generated ids back to text (pass the "
                        "same --vocab the training run printed)")
    p.add_argument("--prompt-text", default=None,
                   help="text prompt, encoded with --tokenizer "
                        "(overrides --prompt)")
    p.add_argument("--prompt-file", default=None,
                   help="file with ONE prompt per line — text (with "
                        "--tokenizer) or comma-separated ids; rows may "
                        "have different lengths (right-aligned with "
                        "padding, decoded via prompt_lens); the batch "
                        "is the line count")
    p.add_argument("--batchsize", type=int, default=8)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-k", type=int, default=0,
                   help="sample from the k best tokens only (0 = off)")
    p.add_argument("--top-p", type=float, default=1.0,
                   help="nucleus sampling mass cutoff (1.0 = off)")
    p.add_argument("--eos-id", type=int, default=-1,
                   help="early stopping: rows that emit this token "
                        "freeze (later positions = --pad-id) and "
                        "generation exits when every row is done")
    p.add_argument("--pad-id", type=int, default=0)
    p.add_argument("--beam", type=int, default=0,
                   help="beam size; 0 = greedy/sampling")
    p.add_argument("--speculative-k", type=int, default=0,
                   help="speculative decoding: draft proposes k tokens "
                        "per round (0 = off); output is token-identical "
                        "to plain greedy")
    p.add_argument("--draft-layers", type=int, default=0,
                   help="draft model depth (default n_layers/2)")
    p.add_argument("--lookup-k", type=int, default=0,
                   help="prompt-lookup decoding: propose k tokens from "
                        "the last n-gram's most recent earlier "
                        "occurrence in the context — speculative "
                        "decoding with NO draft model; output is "
                        "token-identical to plain greedy")
    p.add_argument("--lookup-ngram", type=int, default=2)
    p.add_argument("--int8", action="store_true",
                   help="weight-only int8 decode")
    p.add_argument("--kv-int8", action="store_true",
                   help="int8 KV cache with per-(token, head) scales: "
                        "half the cache HBM (the long-context decode "
                        "bound); composes with --int8 weights")
    p.add_argument("--vocab-parallel", action="store_true",
                   help="shard the tied embedding over the model axis "
                        "(serving-side Megatron vocab TP: V/M embed "
                        "rows resident per device)")
    p.add_argument("--checkpoint", default=None,
                   help="train_lm.py checkpoint dir to load params from")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--platform", default=None)
    args = p.parse_args()

    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)

    import jax

    from chainermn_tpu.utils import enable_compile_cache

    enable_compile_cache()      # before the first jit

    import jax.numpy as jnp

    from chainermn_tpu.models import (
        TransformerConfig, init_transformer, make_beam_search_fn,
        make_generate_fn, quantize_params_int8, shard_params,
    )
    from chainermn_tpu.parallel import MeshConfig
    from chainermn_tpu.utils.serialization import load_state

    mc = MeshConfig(**parse_mesh(args.mesh))
    cfg = TransformerConfig(
        vocab_size=args.vocab, d_model=args.d_model,
        n_heads=args.n_heads, d_head=args.d_model // args.n_heads,
        n_kv_heads=args.n_kv_heads, d_ff=4 * args.d_model,
        n_layers=args.n_layers, max_seq=args.max_len,
        attention="local", pos_embedding=args.pos_embedding,
        vocab_parallel=args.vocab_parallel,
        kv_cache_dtype="int8" if args.kv_int8 else "",
        dtype="float32", remat=False,
    )

    ckpt_file = (os.path.join(args.checkpoint, "lm_state.npz")
                 if args.checkpoint else None)
    pipe = mc.mesh.shape.get("pipe", 1)
    if ckpt_file and os.path.exists(ckpt_file):
        from chainermn_tpu.models import regroup_blocks

        saved = load_state(ckpt_file)
        params = jax.tree.map(jnp.asarray, saved["params"])
        # checkpoints store blocks grouped for whatever pipe mesh
        # TRAINED them ((P0, L/P0, ...), or (P0, V0, lpc, ...) from an
        # interleaved run — the snapshot records its grouping): regroup
        # to this decode mesh's pipe size (a pipe-trained checkpoint
        # must decode on a pipe=1 mesh too, and vice versa).  Legacy
        # snapshots without the metadata are plain-grouped: P0 is the
        # blocks' leading dim.
        first = jax.tree.leaves(params["blocks"])[0]
        saved_pipe = int(saved.get("pipe", first.shape[0]))
        saved_v = int(saved.get("virtual_pipe", 1))
        params = dict(params, blocks=regroup_blocks(
            params["blocks"], saved_pipe, pipe, saved_v, 1))
        print(f"loaded {ckpt_file}")
        ckpt_loaded = True
    else:
        params = init_transformer(
            jax.random.PRNGKey(args.seed), cfg, pipe)
        ckpt_loaded = False
    if args.int8:
        params = quantize_params_int8(cfg, params)
    # keep the pre-shard host tree ONLY when the speculative draft will
    # slice layers from it (that must happen BEFORE sharding — on a
    # multi-process mesh the sharded leaves are not fully addressable
    # from any single host); otherwise let it free after placement
    host_params = params if args.speculative_k > 0 else None
    params = shard_params(mc, cfg, params)

    tok = None
    if args.tokenizer:
        from chainermn_tpu.datasets import BPETokenizer

        tok = BPETokenizer.load(args.tokenizer)

    def check_ids(ids, what):
        if not ids or any(not 0 <= t < args.vocab for t in ids):
            raise SystemExit(
                f"{what}: prompt ids must be in [0, {args.vocab}) "
                f"and non-empty")
        return ids

    def parse_int_ids(text, what):
        try:
            return [int(t) for t in text.split(",") if t.strip()]
        except ValueError:
            raise SystemExit(
                f"{what}: expected comma-separated token ids (got "
                f"{text[:40]!r}) — for text prompts pass --tokenizer")

    prompt_lens = None
    if args.prompt_file is not None:
        rows = []
        with open(args.prompt_file) as f:
            for i, ln in enumerate(f):
                if not ln.strip():
                    continue          # blank lines skipped, numbering
                ln = ln.rstrip("\r\n")  # CRLF-safe; numbering physical
                rows.append(check_ids(
                    tok.encode(ln) if tok is not None else
                    parse_int_ids(ln, f"line {i + 1}"),
                    f"line {i + 1}"))
        if not rows:
            raise SystemExit(f"{args.prompt_file}: no prompts in file")
        dshard = mc.mesh.shape.get("data", 1) \
            * mc.mesh.shape.get("expert", 1)
        if len(rows) % dshard:
            raise SystemExit(
                f"{args.prompt_file}: {len(rows)} prompts do not "
                f"divide over the mesh's data×expert axes ({dshard}) "
                "— pad the file or pick a smaller --mesh")
        P_len = max(len(r) for r in rows)
        prompt_lens = np.asarray([len(r) for r in rows])
        prompt = np.zeros((len(rows), P_len), np.int32)
        for b, r in enumerate(rows):      # right-aligned
            prompt[b, P_len - len(r):] = r
        prompt = jnp.asarray(prompt)
    else:
        if args.prompt_text is not None:
            if tok is None:
                raise SystemExit("--prompt-text needs --tokenizer")
            toks = tok.encode(args.prompt_text)
        else:
            toks = parse_int_ids(args.prompt, "--prompt")
        check_ids(toks, "--prompt")
        prompt = jnp.asarray(
            np.tile(np.asarray(toks, np.int32), (args.batchsize, 1)))

    def show(ids, label="generated"):
        print(f"{label}:", list(map(int, ids)))
        if tok is not None:
            print(f"{label} text:", repr(tok.decode_text(ids)))

    if args.lookup_k > 0 and (args.speculative_k > 0 or args.beam > 0):
        raise SystemExit(
            "--lookup-k is its own decode mode; drop --speculative-k/"
            "--beam")
    if args.lookup_k > 0 and (args.temperature > 0 or args.top_k > 0
                              or args.top_p < 1.0):
        raise SystemExit(
            "--lookup-k is exact-GREEDY decoding; --temperature/"
            "--top-k/--top-p have no effect there — drop them (for "
            "sampled speculation use --speculative-k)")

    def show_batch(out_np):
        """Per-row display for ragged batches, first row otherwise."""
        if prompt_lens is not None:
            for b in range(out_np.shape[0]):
                start = prompt.shape[1] - int(prompt_lens[b])
                show(out_np[b, start:].tolist(), label=f"row {b}")
        else:
            show(out_np[0].tolist())

    if args.lookup_k > 0:
        from chainermn_tpu.models import make_lookup_generate_fn

        lk = make_lookup_generate_fn(
            mc, cfg, k=args.lookup_k, ngram=args.lookup_ngram,
            max_len=args.max_len, eos_id=args.eos_id,
            pad_id=args.pad_id, quantized=args.int8, with_stats=True)
        out, mean_acc = lk(params, prompt, prompt_lens=prompt_lens)
        print(f"prompt-lookup k={args.lookup_k} "
              f"ngram={args.lookup_ngram}: mean accepted "
              f"proposals/round {float(mean_acc):.2f} "
              f"(~{float(mean_acc) + 1:.2f} tokens per target read)")
        show_batch(np.asarray(out))
    elif args.speculative_k > 0:
        import dataclasses

        from chainermn_tpu.models import make_speculative_generate_fn

        d_layers = args.draft_layers or max(1, args.n_layers // 2)
        d_cfg = dataclasses.replace(cfg, n_layers=d_layers)
        if ckpt_loaded and pipe == 1:
            # truncated draft: the checkpoint's FIRST d_layers blocks
            # with the shared embed/norms — a real (if crude) draft
            # whose acceptance reflects the trained model, unlike a
            # random init that can only demonstrate the mechanics
            d_tree = dict(host_params, blocks=jax.tree.map(
                lambda a: np.asarray(a)[:, :d_layers],
                host_params["blocks"]))
            d_params = shard_params(mc, d_cfg, d_tree)
            host_params = d_tree = None    # release the host copies
            d_quant = args.int8
            note = "draft = target's first layers"
        else:
            d_params = shard_params(mc, d_cfg, init_transformer(
                jax.random.PRNGKey(args.seed + 1), d_cfg, pipe))
            host_params = None          # unused on this branch: free it
            d_quant = False
            note = "random draft (mechanics demo — expect ~1 tok/round)"
        print(f"speculative k={args.speculative_k}, {d_layers}-layer "
              f"draft: {note}")
        spec = make_speculative_generate_fn(
            mc, cfg, d_cfg, k=args.speculative_k, max_len=args.max_len,
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, eos_id=args.eos_id, pad_id=args.pad_id,
            quantized=args.int8, draft_quantized=d_quant,
            with_stats=True)
        out, mean_acc = spec(params, d_params, prompt,
                             key=jax.random.PRNGKey(args.seed),
                             prompt_lens=prompt_lens)
        print(f"mean accepted proposals/round: {float(mean_acc):.2f} "
              f"of k={args.speculative_k} "
              f"(~{float(mean_acc) + 1:.2f} tokens per target read)")
        show_batch(np.asarray(out))
    elif args.beam > 0:
        bs = make_beam_search_fn(
            mc, cfg, beam_size=args.beam, max_len=args.max_len,
            eos_id=args.eos_id, length_penalty=0.6,
            quantized=args.int8)
        out, scores = bs(params, prompt, prompt_lens=prompt_lens)
        out_np, sc = np.asarray(out), np.asarray(scores)
        if prompt_lens is not None:
            for b in range(out_np.shape[0]):    # best beam per row
                start = prompt.shape[1] - int(prompt_lens[b])
                show(out_np[b, 0, start:].tolist(),
                     label=f"row {b} best (score {sc[b, 0]:+.3f})")
        else:
            for k in range(args.beam):
                show(out_np[0, k].tolist(),
                     label=f"beam {k} (score {sc[0, k]:+.3f})")
    else:
        gen = make_generate_fn(
            mc, cfg, max_len=args.max_len,
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, eos_id=args.eos_id, pad_id=args.pad_id,
            quantized=args.int8)
        out = gen(params, prompt, key=jax.random.PRNGKey(args.seed),
                  prompt_lens=prompt_lens)
        show_batch(np.asarray(out))
    return out


if __name__ == "__main__":
    main()
