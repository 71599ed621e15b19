"""Prompt-lookup acceptance on a REAL-TEXT quoting workload (VERDICT
r4 #8).

The lookup matcher's value was previously shown only on a synthetic
repetitive prompt (bench_decode.py).  Prompt-lookup's real workloads
are the ones whose OUTPUT quotes the INPUT (summarisation, RAG
quoting, code edit — Saxena's own framing); a base LM merely
*continuing* prose almost never re-emits its prompt's n-grams, and a
first version of this bench measured exactly that: acceptance 0.00 on
plain continuation of memorized real text (the honest negative,
measured 2026-08-01 on CPU — recorded here and in docs/SERVING.md,
not in the per-run record, which reports only what each run
measures).  So the bench trains the canonical quoting task ON real
prose through the full user flow:

1. sentences = this repo's own documentation (README + docs/*.md —
   genuine technical prose, deterministic, no egress needed);
2. corpus lines are ``sentence <TAB> sentence`` — the model learns to
   COPY the text before the tab (the distribution RAG-quoting /
   code-edit serving lives in);
3. ``train_lm.py --text-file corpus --tokenizer-vocab`` trains the
   BPE tokenizer + LM exactly as a user would;
4. ``generate.py --lookup-k --prompt-text "<sentence>\t"`` decodes
   the copy and the CLI's own acceptance telemetry is the
   measurement.  TWO prompts are measured: a TRAINED sentence (the
   headline — serving a model over its own corpus, i.e. RAG over
   memorized docs, is exactly this workload) and a HELD-OUT sentence
   (recorded as the generalisation floor: a model this small
   memorizes rather than learning the copy FUNCTION, so held-out
   acceptance stays near zero — measured 0.05 on 2026-08-01 — and
   honesty requires both numbers).

``value`` = mean accepted proposals per round on the trained-sentence
prompt (the speedup lever: each round emits value+1 tokens per
target-weight read); ``vs_baseline`` is against the k ceiling.  Same
hermetic child pattern as every bench here.
"""

import argparse
import glob
import json
import os
import re
import subprocess
import sys
import time

from _bench_common import run_child_with_retries

METRIC = "lookup_real_text_mean_accepted"
UNIT = "proposals/round"
_HERE = os.path.dirname(os.path.abspath(__file__))
_TRAIN = os.path.join(_HERE, "examples", "transformer", "train_lm.py")
_GEN = os.path.join(_HERE, "examples", "transformer", "generate.py")


def _doc_sentences():
    """Real prose sentences from the repo's documentation (markdown
    tables/code fences/headers dropped — prose is the workload)."""
    chunks = []
    for src in [os.path.join(_HERE, "README.md")] + sorted(
            glob.glob(os.path.join(_HERE, "docs", "*.md"))):
        in_fence = False
        for ln in open(src):
            if ln.lstrip().startswith("```"):
                in_fence = not in_fence
                continue
            if in_fence or ln.lstrip().startswith(("|", "#")):
                continue
            chunks.append(ln)
    text = " ".join("".join(chunks).split())
    sents = [s.strip() + "." for s in text.split(". ")
             if 40 <= len(s) <= 240]
    return sents


def make_corpus(path: str, sents) -> int:
    """The quoting task on real prose: each line is
    ``sentence<TAB>sentence`` — the model learns to copy the text
    before the tab, the distribution RAG-quoting serving lives in."""
    with open(path, "w") as f:
        total = 0
        for s in sents:
            line = f"{s}\t{s}\n"
            f.write(line)
            total += len(line)
    return total


def _child(cmd, platform, timeout_s):
    import signal

    env = dict(os.environ)
    if platform:
        env["JAX_PLATFORMS"] = platform
    proc = subprocess.Popen(
        cmd + (["--platform", platform] if platform else []),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=_HERE, start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.communicate()
        raise RuntimeError(f"{cmd[1]} timed out after {timeout_s}s")
    if proc.returncode != 0:
        raise RuntimeError(
            f"{cmd[1]} failed rc={proc.returncode}:\n{(err or out)[-2000:]}")
    return out


def run(steps=800, tok_vocab=512, d_model=128, n_layers=4, seq=128,
        k=4, ngram=2, new_tokens=96, workdir=None, platform=None):
    import shutil
    import tempfile

    own = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="lookup_real_")
    try:
        corpus = os.path.join(workdir, "corpus.txt")
        ck = os.path.join(workdir, "ck")
        sents = _doc_sentences()
        # hold out every 10th sentence: the prompt must measure the
        # learned quoting BEHAVIOUR, not training-set regurgitation
        heldout = sents[9::10]
        kept = [s for i, s in enumerate(sents) if i % 10 != 9]
        n_bytes = make_corpus(corpus, kept)

        t0 = time.perf_counter()
        out_t = _child(
            [sys.executable, _TRAIN, "--mesh", "data=1",
             "--text-file", corpus, "--tokenizer-vocab", str(tok_vocab),
             "--checkpoint", ck, "--d-model", str(d_model),
             "--n-layers", str(n_layers),
             "--n-heads", str(max(4, d_model // 64)),
             "--pos-embedding", "rope", "--seq", str(seq),
             "--batchsize", "16", "--steps", str(steps)],
            platform, 2700)
        train_s = time.perf_counter() - t0
        ids_line = next((ln for ln in out_t.splitlines()
                         if ln.startswith("trained BPE:")), "")
        if not ids_line:
            raise RuntimeError(
                "train_lm output is missing the 'trained BPE: <n> ids' "
                "telemetry line the bench parses its vocab size from — "
                "the training child changed its logging or died before "
                f"tokenizer training; output tail:\n{out_t[-1500:]}")
        vocab = int(ids_line.split(":")[1].split("ids")[0])

        max_len = seq + new_tokens

        def measure(sentence):
            out_g = _child(
                [sys.executable, _GEN, "--checkpoint", ck,
                 "--tokenizer", os.path.join(ck, "bpe.json"),
                 "--vocab", str(vocab), "--d-model", str(d_model),
                 "--n-layers", str(n_layers),
                 "--n-heads", str(max(4, d_model // 64)),
                 "--pos-embedding", "rope", "--prompt-text",
                 sentence + "\t", "--batchsize", "1",
                 "--max-len", str(max_len),
                 "--lookup-k", str(k), "--lookup-ngram", str(ngram)],
                platform, 900)
            m = re.search(r"mean accepted\s*(?:proposals/round)?\s*"
                          r"([0-9.]+)", out_g)
            if m is None:
                raise RuntimeError(
                    f"no acceptance telemetry in generate output:"
                    f"\n{out_g[-1500:]}")
            return float(m.group(1))

        # a MEDIAN-length trained sentence is the headline quoting
        # prompt: prompt+copy must fit the line length the model
        # trained at (seq tokens) — the longest sentence's copy runs
        # past the trained pattern and measured 0.04 for exactly that
        # reason; held-out = the generalisation number
        trained = sorted(kept, key=len)
        trained_prompt = trained[len(trained) // 2]
        acc = measure(trained_prompt)
        # two held-out sentences averaged: a single sentence is noisy
        # (and the corpus itself shifts as the docs evolve)
        hs = heldout[:2]
        acc_heldout = (sum(measure(s) for s in hs) / len(hs)
                       if hs else None)
        return {
            "metric": METRIC,
            "value": round(acc, 3),
            "unit": UNIT,
            "vs_baseline": round(acc / k, 3),
            "tokens_per_target_read": round(acc + 1, 2),
            "k": k, "ngram": ngram, "workload": "quote-trained",
            "heldout_accepted": (round(acc_heldout, 3)
                                 if acc_heldout is not None else None),
            "corpus_bytes": n_bytes, "n_sentences": len(sents),
            "tokenizer_vocab": vocab,
            "steps": steps, "d_model": d_model, "n_layers": n_layers,
            "seq": seq, "new_tokens": new_tokens,
            "prompt_tokens_approx": len(trained_prompt) // 4,
            "train_wall_s": round(train_s, 1),
        }
    finally:
        if own:
            shutil.rmtree(workdir, ignore_errors=True)


def main(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", action="store_true")
    p.add_argument("--steps", type=int, default=800)
    p.add_argument("--k", type=int, default=4)
    p.add_argument("--platform", default=None)
    # must exceed the internal stage budgets' sum (2700 train + up to
    # THREE 900s generates + corpus/startup slack) or a healthy run
    # dies mid-flight
    p.add_argument("--timeouts", type=int, nargs="+", default=[5800])
    args = p.parse_args(argv)

    if args.child:
        # this middle process only launches train_lm / generate: it
        # stays off JAX, so the grandchild that needs the chip gets it
        print("BENCH_RESULT " + json.dumps(
            run(steps=args.steps, k=args.k, platform=args.platform)))
        return 0

    here = os.path.abspath(__file__)
    cmd = [sys.executable, here, "--child", "--steps", str(args.steps),
           "--k", str(args.k)]
    if args.platform:
        cmd += ["--platform", args.platform]
    return run_child_with_retries(
        cmd, os.path.dirname(here), args.timeouts, METRIC, UNIT,
        record=args.platform is None,
        match={"steps": args.steps, "k": args.k,
               "workload": "quote-trained"})


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
