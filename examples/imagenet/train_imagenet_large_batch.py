"""Large-batch ResNet-50 recipe — the "15-minute ImageNet" configuration
(BASELINE.md config 5; reference: Akiba, Suzuki, Fukuda,
arXiv:1711.04325, built on ChainerMN's fp16 allreduce + double-buffering
optimizer; reference code paths ``chainermn/optimizers.py``
``_DoubleBufferingOptimizer`` — unverified, mount empty, see SURVEY.md).

The recipe, TPU-native:

- **linear LR scaling**: lr = base_lr × (global_batch / 256)
  (Goyal et al.; the paper trained batch 32k at lr 12.5-equivalent);
- **gradual warmup**: LR ramps linearly from base_lr to the scaled LR
  over the first ``--warmup-epochs`` epochs, then polynomial/cosine
  decay — avoids early divergence at large batch;
- **low-precision allreduce**: ``allreduce_grad_dtype=bfloat16`` — the
  bf16 analogue of the paper's fp16 gradient exchange (cast is fused
  into the XLA collective; no CuPy packing kernels needed);
- **double buffering**: 1-step-stale averaged gradients
  (``double_buffering=True``) so the gradient collective of step *i*
  overlaps step *i+1*'s fwd/bwd — the paper's overlap trick as pure
  optax state instead of threads+streams;
- **layer-wise adaptive rates**: ``--optimizer lars`` (You et al. 2017,
  the optimizer that pushed ResNet-50 past batch 32k) or ``lamb``;
  composes inside ``create_multi_node_optimizer`` like any inner optax
  transformation;
- **fused dispatch**: ``--steps-per-execution N`` runs N steps per XLA
  call (``fuse_steps``) to amortise host dispatch latency;
- **preemption safety**: ``--resumable`` adds the checkpointer + the
  SIGTERM ``PreemptionCheckpointer`` so a reclaimed TPU slice saves at
  the signal and the restarted job resumes where it stopped.

Runnable end-to-end on the virtual CPU pod with ``--tiny --platform
cpu`` (the schedule/staleness composition is what matters; throughput
needs chips).
"""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from train_imagenet import make_dataset  # noqa: E402  (sibling example)


def make_lr_schedule(base_lr, global_batch, warmup_epochs, total_epochs,
                     steps_per_epoch):
    """Linear-scaling + gradual-warmup + cosine-decay schedule."""
    import optax

    scaled = base_lr * global_batch / 256.0
    warmup_steps = max(int(warmup_epochs * steps_per_epoch), 1)
    decay_steps = max(
        int((total_epochs - warmup_epochs) * steps_per_epoch), 1)
    return optax.join_schedules(
        [optax.linear_schedule(base_lr, scaled, warmup_steps),
         optax.cosine_decay_schedule(scaled, decay_steps)],
        boundaries=[warmup_steps])


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--communicator", default="tpu_xla")
    p.add_argument("--batchsize", type=int, default=1024,
                   help="global batch (the paper used 32k over 1024 GPUs)")
    p.add_argument("--epoch", type=int, default=4)
    p.add_argument("--base-lr", type=float, default=0.1)
    p.add_argument("--warmup-epochs", type=float, default=1.0)
    p.add_argument("--no-double-buffering", action="store_true")
    p.add_argument("--optimizer", default="sgd",
                   choices=["sgd", "lars", "lamb"],
                   help="inner optimizer; lars/lamb are the layer-wise "
                        "adaptive large-batch recipes")
    p.add_argument("--steps-per-execution", type=int, default=1,
                   help="train steps fused into one XLA dispatch")
    p.add_argument("--resumable", action="store_true",
                   help="periodic + preemption (SIGTERM) checkpoints "
                        "under --out, with automatic resume")
    p.add_argument("--grad-dtype", default="bfloat16")
    p.add_argument("--train-npz", default=None)
    p.add_argument("--platform", default=None)
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--out", default="result_large_batch")
    args = p.parse_args()

    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)

    import jax

    from chainermn_tpu.utils import enable_compile_cache

    enable_compile_cache()      # before the first jit

    import jax.numpy as jnp
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.models import (
        ResNetConfig, accuracy, init_resnet, resnet_apply,
        softmax_cross_entropy,
    )

    comm = cmn.create_communicator(args.communicator)
    if comm.rank == 0:
        print(f"world: {comm.size} devices, {comm.inter_size} processes")

    if args.tiny:
        image, classes, n = 32, 8, 512
        batch = min(args.batchsize, 128)
        cfg = ResNetConfig(depth=50, num_classes=classes, width=8,
                           dtype="float32")
    else:
        image, classes, n = 224, 1000, 50000
        batch = args.batchsize
        cfg = ResNetConfig(depth=50, num_classes=classes)

    data = make_dataset(n, image, classes, npz=args.train_npz)
    from chainermn_tpu.datasets import SubDataset

    split = len(data) * 9 // 10
    train = cmn.scatter_dataset(
        SubDataset(data, np.arange(split)), comm, shuffle=True, seed=0)
    test = cmn.scatter_dataset(
        SubDataset(data, np.arange(split, len(data))), comm)

    # the iterator batch IS the global batch (the updater shards it over
    # the whole mesh), and the trainer's epoch unit is the ITERATOR's
    # epoch (one sweep of this process's shard) — both the LR scaling
    # and the schedule's step count must use those same definitions
    steps_per_epoch = max(len(train) // batch, 1)
    schedule = make_lr_schedule(
        args.base_lr, batch, args.warmup_epochs, args.epoch,
        steps_per_epoch)

    params, state = init_resnet(jax.random.PRNGKey(0), cfg)

    def loss_fn(params, state, x, y):
        logits, new_state = resnet_apply(
            cfg, params, state, x, train=True, axis_name=comm.axis_name)
        return softmax_cross_entropy(logits, y), new_state

    grad_dtype = jnp.dtype(args.grad_dtype) if args.grad_dtype else None
    inner = {
        # LARS defaults per You et al. / MLPerf: trust ratio over
        # weight-decayed grads, momentum 0.9
        "lars": lambda: optax.lars(
            schedule, weight_decay=1e-4, momentum=0.9),
        "lamb": lambda: optax.lamb(schedule, weight_decay=1e-4),
        "sgd": lambda: optax.sgd(schedule, momentum=0.9),
    }[args.optimizer]()
    opt = cmn.create_multi_node_optimizer(
        inner,
        comm,
        double_buffering=not args.no_double_buffering,
        allreduce_grad_dtype=grad_dtype,
    )

    train_it = cmn.SerialIterator(train, batch, shuffle=True, seed=1)
    test_it = cmn.SerialIterator(test, batch, repeat=False)

    updater = cmn.StandardUpdater(
        train_it, opt, loss_fn, params, comm, state=state,
        steps_per_execution=args.steps_per_execution)
    trainer = cmn.Trainer(updater, (args.epoch, "epoch"), out=args.out)

    if args.resumable:
        cp = cmn.extensions.create_multi_node_checkpointer(
            comm, args.out)
        resumed_at = cp.maybe_load(updater, trainer)
        if resumed_at is not None and comm.rank == 0:
            # explicit marker so resume tests can't pass vacuously
            # (a silently-inert checkpoint path would retrain from
            # scratch bit-identically on deterministic configs)
            print(f"resumed at iteration {resumed_at}")
        trainer.extend(cp, trigger=(max(steps_per_epoch, 1), "iteration"))
        trainer.extend(cmn.extensions.PreemptionCheckpointer(cp, comm))

    def metrics_fn(bundle, x, y):
        params, state = bundle
        logits, _ = resnet_apply(cfg, params, state, x, train=False)
        return {"loss": softmax_cross_entropy(logits, y),
                "accuracy": accuracy(logits, y)}

    evaluator = cmn.create_multi_node_evaluator(
        cmn.Evaluator(
            test_it, metrics_fn, comm,
            get_params=lambda tr: (tr.updater.params, tr.updater.state)),
        comm)
    trainer.extend(evaluator, trigger=(1, "epoch"))
    log = cmn.LogReport(trigger=(1, "epoch"))
    trainer.extend(log)
    if comm.rank == 0:
        trainer.extend(cmn.PrintReport(
            ["epoch", "main/loss", "validation/loss",
             "validation/accuracy", "elapsed_time"], log_report=log))

    trainer.run()
    if comm.rank == 0 and log.log:
        last = log.log[-1]
        print(f"final validation accuracy: "
              f"{last.get('validation/accuracy', float('nan')):.4f}")
    return log


if __name__ == "__main__":
    main()
