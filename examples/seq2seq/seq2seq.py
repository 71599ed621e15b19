"""Seq2seq NMT data-parallel training — analogue of the reference's
``examples/seq2seq/seq2seq.py`` (mpiexec-launched encoder-decoder NMT;
unverified — mount empty, see SURVEY.md).

The reference trained WMT en↔fr with ragged minibatches; its distributed
point was that *variable-length* gradients still allreduce. Zero-egress
environment → a synthetic "reverse translation" task (target = reversed
source) with genuinely variable lengths; the converter pads each batch to
ONE static shape so the whole run is a single compiled program (the
TPU-first answer to raggedness — see models/seq2seq.py docstring).
"""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def make_dataset(n=2048, vocab=50, min_len=3, max_len=16, seed=0):
    """(src, tgt) int32 pairs, tgt = reversed(src) + EOS, variable length."""
    from chainermn_tpu.models.seq2seq import EOS

    rng = np.random.RandomState(seed)
    pairs = []
    for _ in range(n):
        length = rng.randint(min_len, max_len + 1)
        src = rng.randint(3, vocab, size=length).astype(np.int32)
        tgt = np.concatenate([src[::-1], [EOS]]).astype(np.int32)
        pairs.append((src, tgt))
    return pairs[: n * 9 // 10], pairs[n * 9 // 10:]


def make_converter(max_src, max_tgt):
    """Pad a ragged batch to ONE static shape (jit compiles once)."""
    from chainermn_tpu.models.seq2seq import PAD

    def convert(batch):
        srcs, tgts = zip(*batch)
        src = np.full((len(batch), max_src), PAD, np.int32)
        tgt = np.full((len(batch), max_tgt), PAD, np.int32)
        for i, (s, t) in enumerate(zip(srcs, tgts)):
            src[i, : len(s)] = s
            tgt[i, : len(t)] = t
        return src, tgt

    return convert


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--communicator", default="tpu_xla")
    p.add_argument("--batchsize", type=int, default=64)
    p.add_argument("--epoch", type=int, default=8)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--unit", type=int, default=128)
    p.add_argument("--platform", default=None)
    p.add_argument("--out", default="result")
    args = p.parse_args()

    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)

    import jax

    from chainermn_tpu.utils import enable_compile_cache

    enable_compile_cache()      # before the first jit

    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.models.seq2seq import (
        Seq2seqConfig, init_seq2seq, seq2seq_loss, seq2seq_translate,
    )

    comm = cmn.create_communicator(args.communicator)
    if comm.rank == 0:
        print(f"world: {comm.size} devices, {comm.inter_size} processes")

    VOCAB, MAX_SRC, MAX_TGT = 50, 16, 17
    train, test = make_dataset(vocab=VOCAB, max_len=MAX_SRC)
    train = cmn.scatter_dataset(train, comm, shuffle=True, seed=0)
    test = cmn.scatter_dataset(test, comm)
    convert = make_converter(MAX_SRC, MAX_TGT)

    cfg = Seq2seqConfig(
        src_vocab=VOCAB, tgt_vocab=VOCAB,
        d_embed=args.unit, d_hidden=args.unit, n_layers=2)
    params = init_seq2seq(jax.random.PRNGKey(0), cfg)
    opt = cmn.create_multi_node_optimizer(optax.adam(args.lr), comm)

    def loss_fn(params, src, tgt):
        return seq2seq_loss(cfg, params, src, tgt)

    train_it = cmn.SerialIterator(train, args.batchsize, shuffle=True, seed=1)
    test_it = cmn.SerialIterator(test, args.batchsize, repeat=False)

    updater = cmn.StandardUpdater(
        train_it, opt, loss_fn, params, comm, converter=convert)
    trainer = cmn.Trainer(updater, (args.epoch, "epoch"), out=args.out)

    def metrics_fn(params, src, tgt):
        return {"loss": seq2seq_loss(cfg, params, src, tgt)}

    evaluator = cmn.create_multi_node_evaluator(
        cmn.Evaluator(test_it, metrics_fn, comm, converter=convert), comm)
    trainer.extend(evaluator, trigger=(1, "epoch"))
    log = cmn.LogReport(trigger=(1, "epoch"))
    trainer.extend(log)
    if comm.rank == 0:
        trainer.extend(cmn.PrintReport(
            ["epoch", "main/loss", "validation/loss", "elapsed_time"],
            log_report=log))

    trainer.run()

    # greedy-decode a few validation pairs (the reference printed BLEU;
    # for the synthetic reverse task exact-match is the honest metric)
    src, tgt = convert(test[:64])
    out = np.asarray(seq2seq_translate(
        cfg, updater.params, src, max_len=MAX_TGT))
    match = float(np.mean(np.all(out == tgt, axis=1)))
    if comm.rank == 0:
        print(f"greedy exact-match on {len(src)} held-out pairs: {match:.3f}")
    return match


if __name__ == "__main__":
    main()
