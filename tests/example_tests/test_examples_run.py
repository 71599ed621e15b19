"""Every example script must RUN end-to-end on the virtual CPU pod —
the reference's examples were its de-facto integration suite (run under
``mpiexec`` in CI, SURVEY.md §4); these are ours, exercised exactly as a
user would launch them (fresh interpreter, CLI flags, tiny settings)."""

import os
import shutil
import subprocess
import sys

import pytest

_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", ".."))


def _example_env():
    env = dict(os.environ)
    for k in list(env):
        if k.startswith(("TPU_", "LIBTPU", "PJRT_", "JAX_")):
            env.pop(k)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = _ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _run_example(relpath, args, timeout=280, check=True):
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, relpath), "--platform", "cpu",
         *args],
        capture_output=True, text=True, timeout=timeout, cwd=_ROOT,
        env=_example_env())
    if not check:
        return proc
    assert proc.returncode == 0, (
        f"{relpath} failed rc={proc.returncode}\n--- stdout ---\n"
        f"{proc.stdout[-3000:]}\n--- stderr ---\n{proc.stderr[-3000:]}")
    return proc.stdout


@pytest.mark.parametrize("relpath,args", [
    ("examples/mnist/train_mnist.py",
     ["--epoch", "1", "--batchsize", "64"]),
    ("examples/mnist/train_mnist_model_parallel.py",
     ["--epoch", "1", "--batchsize", "64"]),
    ("examples/seq2seq/seq2seq.py",
     ["--epoch", "1", "--batchsize", "32", "--unit", "32"]),
    # tier-1 budget: googlenet (~28 s) and the lars variant (~90 s)
    # are slow-marked since ISSUE 15, and since ISSUE 46 the plain
    # ResNet-50 launch (49 s alone, 60 under six workers, no argument
    # that makes it lighter): `test_imagenet_real_npz_path[npz-native]`
    # launches this script with the same --tiny ResNet-50 through the
    # same Trainer, evaluator and report end to end in tier-1, and the
    # `resnet50-trainer-b256` cell runs the stack on the chip for every
    # PR.  The plain large-batch recipe stays tier-1's one launch of
    # its script.  `-m slow` (or `-m ''`) runs the full matrix
    pytest.param(
        "examples/imagenet/train_imagenet.py",
        ["--tiny", "--epoch", "1", "--batchsize", "64"],
        marks=pytest.mark.slow),
    pytest.param(
        "examples/imagenet/train_imagenet.py",
        ["--tiny", "--epoch", "1", "--batchsize", "64",
         "--arch", "googlenet"],
        marks=pytest.mark.slow),
    ("examples/imagenet/train_imagenet_large_batch.py",
     ["--tiny", "--epoch", "1", "--batchsize", "64"]),
    pytest.param(
        "examples/imagenet/train_imagenet_large_batch.py",
        ["--tiny", "--epoch", "1", "--batchsize", "64",
         "--optimizer", "lars", "--steps-per-execution", "2",
         "--resumable"],
        marks=pytest.mark.slow),
    ("examples/transformer/train_lm.py",
     ["--mesh", "data=8", "--steps", "12"]),
    ("examples/transformer/train_lm.py",
     ["--mesh", "data=2,model=2,seq=2", "--attention", "ring",
      "--n-kv-heads", "2", "--pos-embedding", "rope", "--steps", "8"]),
    ("examples/transformer/train_lm.py",
     ["--mesh", "pipe=2,data=4", "--schedule", "1f1b", "--steps", "8"]),
], ids=["mnist-dp", "mnist-mp", "seq2seq", "imagenet-resnet",
        "imagenet-googlenet", "imagenet-large-batch",
        "imagenet-large-batch-lars", "lm-dp", "lm-tp-sp-ring",
        "lm-pipe-1f1b"])
def test_example_runs(relpath, args, tmp_path):
    out = []
    if ("--out" not in args and "model_parallel" not in relpath
            and "train_lm" not in relpath):
        out = ["--out", str(tmp_path / "out")]
    _run_example(relpath, args + out)


@pytest.mark.parametrize("extra", [
    [], ["--beam", "3", "--int8"],
    ["--mesh", "data=4,model=2", "--n-kv-heads", "2",
     "--pos-embedding", "rope", "--temperature", "0.8"],
], ids=["greedy", "beam-int8", "tp-sampling"])
def test_generate_example(extra):
    out = _run_example("examples/transformer/generate.py",
                       ["--max-len", "16"] + extra)
    if "--beam" in extra:
        assert "beam 0" in out and "beam 2" in out
    else:
        assert "generated:" in out


def test_elastic_resume_across_meshes(tmp_path):
    """A checkpoint trained on a pure-DP mesh resumes on a pipelined
    mesh (blocks regrouped, Adam state re-laid) and keeps training —
    the reference could only restart at the identical world size."""
    ck = str(tmp_path / "ck")
    first = _run_example(
        "examples/transformer/train_lm.py",
        ["--mesh", "data=8", "--steps", "6", "--checkpoint", ck])
    assert "saved" in first
    out = _run_example(
        "examples/transformer/train_lm.py",
        ["--mesh", "pipe=2,data=4", "--steps", "12",
         "--checkpoint", ck])
    assert "regrouped checkpoint pipe=1/V=1 -> pipe=2/V=1" in out, out
    assert "resumed at step 6" in out, out


def test_generate_text_prompt_without_tokenizer_is_clean_error(tmp_path):
    """A text prompt file without --tokenizer must exit with a message
    pointing at --tokenizer, not a raw int() ValueError traceback."""
    pf = tmp_path / "prompts.txt"
    pf.write_text("the quick brown fox\njumps over the lazy dog\n")
    proc = _run_example("examples/transformer/generate.py",
                        ["--prompt-file", str(pf)], check=False)
    assert proc.returncode != 0
    assert "--tokenizer" in proc.stderr
    assert "Traceback" not in proc.stderr, proc.stderr[-2000:]


@pytest.fixture(scope="module")
def dp_checkpoint(tmp_path_factory):
    """``train_lm.py --mesh data=8 --steps 10 --checkpoint``, launched
    once: the round trip through ``generate.py`` and the resume both
    begin with this very launch, and go on from a copy of their own."""
    ck = tmp_path_factory.mktemp("dp") / "ck"
    _run_example("examples/transformer/train_lm.py",
                 ["--mesh", "data=8", "--steps", "10",
                  "--checkpoint", str(ck)])
    return ck


def test_train_then_generate_roundtrip(tmp_path, dp_checkpoint):
    ck = str(shutil.copytree(dp_checkpoint, tmp_path / "ck"))
    out = _run_example("examples/transformer/generate.py",
                       ["--checkpoint", ck, "--vocab", "128",
                        "--max-len", "16"])
    assert "loaded" in out and "generated:" in out


def _epoch_rows(out):
    """Parse PrintReport lines 'epoch=N  main/loss=X ...' into
    {epoch: {field: float}}."""
    rows = {}
    for line in out.splitlines():
        if not line.startswith("epoch="):
            continue
        kv = dict(part.split("=", 1) for part in line.split())
        rows[int(kv.pop("epoch"))] = {
            k: float(v) for k, v in kv.items()}
    return rows


@pytest.mark.slow
def test_large_batch_interrupted_resume_matches_straight_run(tmp_path):
    """Example-scale resume equivalence (not just unit scale): stopping
    the large-batch recipe after epoch 1 and re-launching to epoch 2
    must reproduce the uninterrupted run's epoch-2 training loss —
    iterator position/RNG, LR-schedule step, and LogReport history all
    restored through the example's own --resumable path.

    Slow-marked (ISSUE 15 tier-1 budget): three full example launches
    (~104s) — resume equivalence itself stays tier-1-gated at unit
    scale (optimizer_tests/test_accum_resume.py, the checkpoint
    suite); this drill is the example-scale composition."""
    base = ["--tiny", "--batchsize", "64", "--resumable"]
    straight = _run_example(
        "examples/imagenet/train_imagenet_large_batch.py",
        base + ["--epoch", "2", "--out", str(tmp_path / "straight")])

    _run_example(
        "examples/imagenet/train_imagenet_large_batch.py",
        base + ["--epoch", "1", "--out", str(tmp_path / "resumed")])
    snaps = [f for f in os.listdir(tmp_path / "resumed")
             if f.startswith("snapshot_iter_")]
    assert snaps, "epoch-1 run wrote no snapshots — resume untestable"
    resumed = _run_example(
        "examples/imagenet/train_imagenet_large_batch.py",
        base + ["--epoch", "2", "--out", str(tmp_path / "resumed")])
    # guard against a vacuous pass: the relaunch is CLI-identical to
    # the straight run, so without this marker a silently-inert resume
    # path would retrain from scratch bit-identically and still match
    assert "resumed at iteration" in resumed, resumed[-1500:]

    a, b = _epoch_rows(straight), _epoch_rows(resumed)
    assert 2 in a and 2 in b, (a, b)
    for field in ("main/loss", "validation/loss", "validation/accuracy"):
        assert abs(a[2][field] - b[2][field]) <= 1e-5 * max(
            1.0, abs(a[2][field])), \
            f"epoch-2 {field}: straight {a[2][field]} vs resumed " \
            f"{b[2][field]} — resume diverged at example scale"


def test_pipe_trained_checkpoint_decodes_anywhere(tmp_path):
    """A pipe=2-trained checkpoint must decode on the default pipe=1
    mesh AND on a pipe=2 decode mesh (block regrouping is mesh-to-mesh,
    and PP-decode's stage-sharded step produces identical tokens)."""
    ck = str(tmp_path / "ck")
    _run_example("examples/transformer/train_lm.py",
                 ["--mesh", "pipe=2,data=4", "--steps", "8",
                  "--checkpoint", ck])
    outs = []
    for mesh in ("data=-1", "pipe=2,data=4"):
        out = _run_example("examples/transformer/generate.py",
                           ["--checkpoint", ck, "--vocab", "128",
                            "--max-len", "16", "--mesh", mesh])
        assert "loaded" in out and "generated:" in out
        outs.append(out[out.index("generated:"):])
    assert outs[0] == outs[1], "pipe decode diverges from pipe=1 decode"


def test_interleaved_trained_checkpoint_decodes(tmp_path):
    """An interleaved-trained checkpoint stores blocks (P, V, lpc, ...);
    decode must regroup via the recorded pipe/virtual metadata instead
    of a blind (pipe, -1) reshape (which would keep the wrong rank and
    scramble chunk-major layer order)."""
    ck = str(tmp_path / "ck")
    _run_example("examples/transformer/train_lm.py",
                 ["--mesh", "pipe=2,data=4", "--schedule", "interleaved",
                  "--steps", "8", "--checkpoint", ck])
    out = _run_example("examples/transformer/generate.py",
                       ["--checkpoint", ck, "--vocab", "128",
                        "--max-len", "16"])
    assert "loaded" in out and "generated:" in out


def test_lm_real_text_path(tmp_path):
    """The --text-file path must actually be exercised: a generated
    text file with strong byte structure trains end-to-end and the
    loss falls well below uniform-over-bytes entropy."""
    import math

    txt = tmp_path / "corpus.txt"
    # highly repetitive corpus: next-byte entropy far below ln(256)
    txt.write_bytes(b"the quick brown fox jumps over the lazy dog. "
                    * 800)
    out = _run_example(
        "examples/transformer/train_lm.py",
        ["--mesh", "data=8", "--steps", "30", "--vocab", "256",
         "--text-file", str(txt)])
    loss_line = next((ln for ln in out.splitlines()
                      if ln.startswith("loss ") and "->" in ln), None)
    assert loss_line, f"no loss summary line in output:\n{out[-1500:]}"
    last = float(loss_line.split("->")[1].split("over")[0])
    assert last < math.log(256) * 0.6, \
        f"byte LM barely learned the repetitive corpus: loss {last}"
    # the held-out tail (never trained on) must also be well-modelled
    ppl_line = next((ln for ln in out.splitlines()
                     if ln.startswith("held-out byte perplexity")), None)
    assert ppl_line, f"no held-out ppl line in output:\n{out[-1500:]}"
    ppl = float(ppl_line.split("perplexity")[1].split("(")[0])
    assert ppl < 100, f"held-out perplexity {ppl} barely beats uniform"


def test_lm_bpe_tokenizer_path(tmp_path):
    """--tokenizer-vocab: the BPE subword path trains end-to-end,
    persists bpe.json beside the checkpoint, reports BOTH token and
    byte perplexity, beats the byte-level run at equal steps on the
    byte-ppl scale (each step sees bytes-per-token times more text),
    and round-trips through generate.py --prompt-text."""
    txt = tmp_path / "corpus.txt"
    txt.write_bytes(b"the quick brown fox jumps over the lazy dog. "
                    b"a stitch in time saves nine for the early bird. "
                    * 500)
    ck = str(tmp_path / "ck")
    common = ["--mesh", "data=8", "--steps", "30", "--d-model", "32",
              "--n-layers", "2", "--text-file", str(txt)]
    out = _run_example(
        "examples/transformer/train_lm.py",
        common + ["--tokenizer-vocab", "512", "--checkpoint", ck])
    assert (tmp_path / "ck" / "bpe.json").exists()
    line = next(ln for ln in out.splitlines()
                if ln.startswith("held-out token perplexity"))
    byte_ppl = float(line.split("byte perplexity")[1].split("at")[0])
    out_bytes = _run_example(
        "examples/transformer/train_lm.py", common + ["--vocab", "256"])
    bl = next(ln for ln in out_bytes.splitlines()
              if ln.startswith("held-out byte perplexity"))
    byte_baseline = float(bl.split("perplexity")[1].split("(")[0])
    assert byte_ppl < byte_baseline, \
        f"BPE byte-ppl {byte_ppl} did not beat byte-level {byte_baseline}"
    # resume reuses the persisted merges rather than retraining
    out2 = _run_example(
        "examples/transformer/train_lm.py",
        common + ["--tokenizer-vocab", "512", "--checkpoint", ck,
                  "--steps", "32"])
    assert "loaded tokenizer" in out2 and "resumed at step 30" in out2
    # vocab printed by training (tokenizer ids padded to 128-multiple)
    vocab = next(ln for ln in out.splitlines()
                 if ln.startswith("model vocab")).split()[2]
    gen = _run_example(
        "examples/transformer/generate.py",
        ["--checkpoint", ck, "--tokenizer", str(tmp_path / "ck" /
                                                "bpe.json"),
         "--prompt-text", "the quick brown", "--vocab", vocab,
         "--d-model", "32", "--n-layers", "2", "--max-len", "16"])
    assert "generated text:" in gen and "the quick brown" in gen
    # variable-length batch: one prompt per line, right-aligned with
    # prompt_lens under the hood, per-row decoded text out
    pf = tmp_path / "prompts.txt"
    pf.write_text("the quick brown\na stitch in time saves\n" * 4)
    gen = _run_example(
        "examples/transformer/generate.py",
        ["--checkpoint", ck, "--tokenizer", str(tmp_path / "ck" /
                                                "bpe.json"),
         "--prompt-file", str(pf), "--vocab", vocab,
         "--d-model", "32", "--n-layers", "2", "--max-len", "16"])
    assert "row 0 text: 'the quick brown" in gen
    assert "row 7 text: 'a stitch in time saves" in gen


def test_mnist_real_npz_path(tmp_path):
    """The --mnist-npz file path must actually be exercised: a generated
    mnist.npz-shaped fixture trains end-to-end and beats chance."""
    import numpy as np

    rng = np.random.RandomState(0)
    protos = rng.randn(10, 784).astype("float32") * 40 + 128

    def split(n):
        y = (np.arange(n) % 10).astype("int64")
        x = np.clip(protos[y] + 25 * rng.randn(n, 784), 0, 255)
        return x.astype("uint8"), y

    x_train, y_train = split(1280)
    x_test, y_test = split(256)
    npz = tmp_path / "mnist.npz"
    np.savez(npz, x_train=x_train, y_train=y_train,
             x_test=x_test, y_test=y_test)
    out = _run_example(
        "examples/mnist/train_mnist.py",
        ["--epoch", "2", "--batchsize", "64", "--mnist-npz", str(npz),
         "--out", str(tmp_path / "out")])
    acc = float(out.strip().splitlines()[-1].split()[-1])
    assert acc > 0.5, f"npz-trained accuracy {acc} no better than chance"


# tier-1 budget (ISSUE 15): the serial-loader arm (~23s) is
# slow-marked; the native arm keeps the whole --train-npz file path
# AND the C++ iterator gated in tier-1
@pytest.mark.parametrize("loader", [
    pytest.param("serial", marks=pytest.mark.slow), "native",
], ids=["npz-serial", "npz-native"])
def test_imagenet_real_npz_path(tmp_path, loader):
    """--train-npz feeds real (generated) image files end-to-end; with
    --loader native the C++ NativeBatchIterator drives the SAME
    training loop through StandardUpdater."""
    import numpy as np

    rng = np.random.RandomState(0)
    n, image, classes = 256, 32, 8
    y = (np.arange(n) % classes).astype("int32")
    protos = rng.randn(classes, 8).astype("float32")
    x = 0.3 * rng.randn(n, image, image, 3).astype("float32")
    x[np.arange(n), :8, 0, 0] += protos[y]
    npz = tmp_path / "imagenet.npz"
    np.savez(npz, x=x, y=y)
    _run_example(
        "examples/imagenet/train_imagenet.py",
        ["--tiny", "--epoch", "1", "--batchsize", "64",
         "--train-npz", str(npz), "--loader", loader,
         "--out", str(tmp_path / "out")])


def test_train_lm_checkpoint_resume(tmp_path, dp_checkpoint):
    """--checkpoint writes a resumable state; a second run restores it."""
    shutil.copytree(dp_checkpoint, tmp_path / "ck")
    out = _run_example("examples/transformer/train_lm.py",
                       ["--mesh", "data=8", "--steps", "14",
                        "--checkpoint", str(tmp_path / "ck")])
    assert "resumed at step 10" in out
    # resuming past --steps is a clean no-op, not a crash
    out = _run_example("examples/transformer/train_lm.py",
                       ["--mesh", "data=8", "--steps", "14",
                        "--checkpoint", str(tmp_path / "ck")])
    assert "nothing to do" in out
