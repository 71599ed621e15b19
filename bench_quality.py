"""Quality-bar run: the LM example trained on a book-scale corpus to a
held-out-perplexity target, WITH a mid-run interruption + resume.

The reference's examples were judged by train-to-accuracy runs (15-min
ImageNet etc.); this is the transformer-LM counterpart, packaged as a
bench:

1. generate a deterministic pseudo-book corpus (Zipf word frequencies,
   sentence/paragraph structure — enough statistical texture that
   held-out perplexity is a real generalisation number);
2. train `examples/transformer/train_lm.py` with a BPE tokenizer for
   HALF the steps, checkpointing;
3. re-launch for the full step count — the run must print
   ``resumed at step N/2`` (interrupted ≡ uninterrupted is separately
   pinned by tests/extension_tests/test_resume_equivalence.py);
4. record held-out token+byte perplexity, wall-clock per phase, corpus
   size — the README results row.

``value`` is the held-out BYTE perplexity (comparable across
tokenizers); ``vs_baseline`` is uniform-byte perplexity (256) over it —
how many times better than knowing nothing.  Same hermetic
child-process pattern as the other benches.
"""

import argparse
import json
import os
import random
import subprocess
import sys
import time

from _bench_common import run_child_with_retries

METRIC = "lm_quality_heldout_byte_ppl"
UNIT = "perplexity"
_HERE = os.path.dirname(os.path.abspath(__file__))
_TRAIN = os.path.join(_HERE, "examples", "transformer", "train_lm.py")

_WORDS = (
    "the of and a to in is was he for it with as his on be at by had "
    "not are but from or have an they which one you were all her she "
    "there would their we him been has when who will no more if out so "
    "said what up its about into than them can only other time new some "
    "could these two may first then do any like my now over such our "
    "man me even most made after also did many off before must well "
    "back through years where much your way down should because each "
    "just those people how too little state good very make world still "
    "see own men work long here get both between life being under "
    "never day same another know while last might us great old year "
    "come since against go came right used take three").split()


def make_corpus(path: str, target_bytes: int, seed: int = 0) -> int:
    """Deterministic pseudo-book text: Zipf-weighted words, sentences
    of 4-18 words, paragraphs of 3-8 sentences."""
    rng = random.Random(seed)
    weights = [1.0 / (i + 1) for i in range(len(_WORDS))]
    with open(path, "w") as f:
        written = 0
        while written < target_bytes:
            para = []
            for _ in range(rng.randint(3, 8)):
                words = rng.choices(_WORDS, weights,
                                    k=rng.randint(4, 18))
                s = " ".join(words)
                para.append(s[0].upper() + s[1:] + ".")
            text = " ".join(para) + "\n\n"
            f.write(text)
            written += len(text)
    return written


def _run_train(args_list, platform, timeout_s=1400):
    """One train_lm phase with its OWN timeout and process-group kill:
    if the outer bench timeout fired instead, it would kill only the
    direct child and orphan train_lm still holding the TPU device."""
    import signal

    env = dict(os.environ)
    if platform:
        env["JAX_PLATFORMS"] = platform
    t0 = time.perf_counter()
    proc = subprocess.Popen(
        [sys.executable, _TRAIN] + args_list
        + (["--platform", platform] if platform else []),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=_HERE, start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.communicate()
        raise RuntimeError(
            f"train_lm phase timed out after {timeout_s}s "
            "(process group killed)")
    dt = time.perf_counter() - t0
    if proc.returncode != 0:
        raise RuntimeError(
            f"train_lm failed rc={proc.returncode}:\n"
            f"{(err or out)[-2000:]}")
    return out, dt


def run(corpus_mb=4.0, steps=400, tok_vocab=8192, d_model=256,
        n_layers=4, seq=256, batch=16, workdir=None, platform=None):
    import shutil
    import tempfile

    own_workdir = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="lm_quality_")
    try:
        return _run_quality(workdir, corpus_mb, steps, tok_vocab,
                            d_model, n_layers, seq, batch, platform)
    finally:
        if own_workdir:
            # checkpoints with Adam moments would otherwise pile up
            shutil.rmtree(workdir, ignore_errors=True)


def _run_quality(workdir, corpus_mb, steps, tok_vocab, d_model,
                 n_layers, seq, batch, platform):
    corpus = os.path.join(workdir, "corpus.txt")
    ck = os.path.join(workdir, "ck")
    n_bytes = make_corpus(corpus, int(corpus_mb * 1e6))

    common = ["--mesh", "data=1", "--text-file", corpus,
              "--tokenizer-vocab", str(tok_vocab),
              "--checkpoint", ck,
              "--d-model", str(d_model), "--n-layers", str(n_layers),
              "--n-heads", str(max(4, d_model // 64)),
              "--seq", str(seq), "--batchsize", str(batch)]
    half = steps // 2
    out_a, dt_a = _run_train(common + ["--steps", str(half)], platform)
    out_b, dt_b = _run_train(common + ["--steps", str(steps)], platform)
    # the synthetic corpus's word list bounds how many merges BPE can
    # actually reach — record the ids REACHED, not just the budget
    ids_line = next((ln for ln in out_a.splitlines()
                     if ln.startswith("trained BPE:")), "")
    ids_reached = int(ids_line.split(":")[1].split("ids")[0]) \
        if ids_line else None
    if f"resumed at step {half}" not in out_b:
        raise RuntimeError(
            f"resume marker missing from phase B output:\n{out_b[-1500:]}")
    line = next((ln for ln in out_b.splitlines()
                 if ln.startswith("held-out token perplexity")), None)
    if line is None:
        raise RuntimeError(f"no held-out ppl line:\n{out_b[-1500:]}")
    token_ppl = float(line.split("perplexity")[1].split("(")[0])
    byte_ppl = float(line.split("byte perplexity")[1].split("at")[0])
    bytes_per_tok = float(line.rsplit("at", 1)[1].split("bytes")[0])
    return {
        "metric": METRIC,
        "value": round(byte_ppl, 3),
        "unit": UNIT,
        # how many times better than byte-uniform; >1 is learning,
        # real runs land far above
        "vs_baseline": round(256.0 / byte_ppl, 1),
        "token_ppl": round(token_ppl, 2),
        "bytes_per_token": round(bytes_per_tok, 2),
        "corpus_bytes": n_bytes,
        "tokenizer_vocab": tok_vocab,
        "tokenizer_ids_reached": ids_reached,
        "steps": steps, "seq": seq, "batch": batch,
        "d_model": d_model, "n_layers": n_layers,
        "wall_s_phase_a": round(dt_a, 1),
        "wall_s_phase_b": round(dt_b, 1),
        "resume_verified": True,
    }


def main(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", action="store_true")
    p.add_argument("--full", action="store_true",
                   help="the chip-scale quality run (4 MB corpus, BPE "
                        "budget 8k — the ids actually reached on the "
                        "synthetic corpus are recorded — ~3M-param "
                        "model); default is a smoke config any "
                        "platform can finish in minutes")
    p.add_argument("--platform", default=None)
    p.add_argument("--timeouts", type=int, nargs="+", default=[3000])
    args = p.parse_args(argv)

    size = (dict(corpus_mb=4.0, steps=600, tok_vocab=8192, d_model=256,
                 n_layers=4, seq=256, batch=16) if args.full else
            dict(corpus_mb=0.3, steps=40, tok_vocab=512, d_model=64,
                 n_layers=2, seq=64, batch=8))

    if args.child:
        # this middle process only launches train_lm / generate: it
        # stays off JAX, so the grandchild that needs the chip gets it
        print("BENCH_RESULT " + json.dumps(
            run(platform=args.platform, **size)))
        return 0

    here = os.path.abspath(__file__)
    cmd = [sys.executable, here, "--child"] \
        + (["--full"] if args.full else [])
    if args.platform:
        cmd += ["--platform", args.platform]
    return run_child_with_retries(
        cmd, os.path.dirname(here), args.timeouts, METRIC, UNIT,
        record=args.platform is None,
        match={"steps": size["steps"],
               "tokenizer_vocab": size["tok_vocab"]})


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
