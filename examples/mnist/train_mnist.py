"""MNIST MLP data-parallel training — analogue of the reference's
``examples/mnist/train_mnist.py`` (mpiexec-launched DP MLP; unverified —
mount empty, see SURVEY.md).

Launch model shift: no ``mpiexec -n N`` — ONE process drives all local
devices (run under `XLA_FLAGS=--xla_force_host_platform_device_count=8
python examples/mnist/train_mnist.py --platform cpu` to simulate a pod
slice, or plainly on a TPU host).  Multi-host pods launch the same script
per host (jax.distributed).

Uses a synthetic MNIST-shaped dataset when torchvision/real data is
unavailable (zero-egress environments); pass --mnist-npz to point at a
downloaded mnist.npz.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def make_dataset(npz_path=None, n=4096, seed=0):
    import numpy as np

    if npz_path and os.path.exists(npz_path):
        d = np.load(npz_path)
        train = list(zip(d["x_train"].astype("float32") / 255.0,
                         d["y_train"].astype("int32")))
        test = list(zip(d["x_test"].astype("float32") / 255.0,
                        d["y_test"].astype("int32")))
        return train, test
    # synthetic, linearly-separable-ish 10-class images
    rng = np.random.RandomState(seed)
    protos = rng.randn(10, 784).astype("float32")
    xs = []
    for i in range(n):
        c = i % 10
        xs.append((protos[c] + 0.3 * rng.randn(784).astype("float32"),
                   np.int32(c)))
    return xs[: n * 9 // 10], xs[n * 9 // 10:]


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--communicator", default="tpu_xla")
    p.add_argument("--batchsize", type=int, default=128)
    p.add_argument("--epoch", type=int, default=3)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--platform", default=None,
                   help="force jax platform (cpu for the virtual pod)")
    p.add_argument("--mnist-npz", default=None)
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
    from chainermn_tpu.models import (accuracy, init_mlp, mlp_apply,
                                      softmax_cross_entropy)

    comm = cmn.create_communicator(args.communicator)
    if comm.rank == 0:
        print(f"world: {comm.size} devices, {comm.inter_size} processes")

    train, test = make_dataset(args.mnist_npz)
    train = cmn.scatter_dataset(train, comm, shuffle=True, seed=0)
    test = cmn.scatter_dataset(test, comm)

    train_it = cmn.SerialIterator(train, args.batchsize, shuffle=True, seed=1)
    test_it = cmn.SerialIterator(test, args.batchsize, repeat=False)

    params = init_mlp(jax.random.PRNGKey(0), [784, 256, 256, 10])
    opt = cmn.create_multi_node_optimizer(optax.sgd(args.lr), comm)

    def loss_fn(params, x, y):
        return softmax_cross_entropy(mlp_apply(params, x), y)

    def metrics_fn(params, x, y):
        logits = mlp_apply(params, x)
        return {"loss": softmax_cross_entropy(logits, y),
                "accuracy": accuracy(logits, y)}

    updater = cmn.StandardUpdater(train_it, opt, loss_fn, params, comm)
    trainer = cmn.Trainer(updater, (args.epoch, "epoch"), out=args.out)

    evaluator = cmn.create_multi_node_evaluator(
        cmn.Evaluator(test_it, metrics_fn, comm), comm)
    trainer.extend(evaluator, trigger=(1, "epoch"))
    log = cmn.LogReport(trigger=(1, "epoch"))
    trainer.extend(log)
    if comm.rank == 0:  # rank-0-only printing, the reference's convention
        trainer.extend(cmn.PrintReport(
            ["epoch", "main/loss", "validation/loss", "validation/accuracy",
             "elapsed_time"], log_report=log))

    trainer.run()
    if comm.rank == 0 and log.log:
        last = log.log[-1]
        print(f"final validation accuracy: "
              f"{last.get('validation/accuracy', float('nan')):.4f}")
    return log


if __name__ == "__main__":
    main()
