"""ImageNet ResNet-50 data-parallel training — analogue of the reference's
``examples/imagenet/train_imagenet.py`` + ``models/resnet50.py``
(mpiexec-launched DP ResNet; unverified — mount empty, see SURVEY.md).

The headline BASELINE.md config: DP ResNet-50, cross-replica BN, bf16
compute (the fp16-allreduce analogue is ``--grad-dtype bfloat16`` on the
multi-node optimizer).  Zero-egress environment → synthetic ImageNet-shaped
data by default; pass ``--train-npz`` with ``x``/``y`` arrays for real
images.  ``--tiny`` shrinks everything for the virtual-pod smoke run.
"""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


class SyntheticImages:
    """Lazy ImageNet-shaped dataset: images are generated per __getitem__
    (a full list would be ~30 GB at 50k × 224²×3 fp32), deterministically
    from the index so every process sees the same logical dataset."""

    def __init__(self, n, image, classes, seed=0):
        self.n, self.image, self.classes = n, image, classes
        self.protos = np.random.RandomState(seed).randn(
            classes, 8).astype("float32")

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        i = int(i)
        c = i % self.classes
        rng = np.random.RandomState(1_000_003 + i)
        # class signal in a low-dim projection so tiny runs can learn it
        x = 0.3 * rng.randn(self.image, self.image, 3).astype("float32")
        x[:8, 0, 0] += self.protos[c]
        return x, np.int32(c)


def make_dataset(n, image, classes, npz=None, seed=0):
    if npz and os.path.exists(npz):
        d = np.load(npz)
        return list(zip(d["x"].astype("float32"), d["y"].astype("int32")))
    return SyntheticImages(n, image, classes, seed)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--communicator", default="tpu_xla")
    p.add_argument("--arch", default="resnet50",
                   choices=["resnet50", "resnet101", "resnet152",
                            "alex", "nin", "vgg16", "googlenet"],
                   help="model architecture (reference --arch parity)")
    p.add_argument("--batchsize", type=int, default=256,
                   help="global batch size")
    p.add_argument("--epoch", type=int, default=2)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--grad-dtype", default=None,
                   help="allreduce_grad_dtype analogue, e.g. bfloat16")
    p.add_argument("--train-npz", default=None)
    p.add_argument("--loader", default="serial",
                   choices=["serial", "native"],
                   help="'native': the C++ slot-ring prefetch loader "
                        "(chainermn_tpu.native.NativeBatchIterator) "
                        "assembles batches in worker threads ahead of "
                        "the step — the reference's multithreaded "
                        "chainer.iterators analogue; materialises this "
                        "process's shard as field arrays")
    p.add_argument("--platform", default=None)
    p.add_argument("--tiny", action="store_true",
                   help="32px/width-8 model on 512 images (CPU smoke run)")
    p.add_argument("--out", default="result")
    args = p.parse_args()

    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)

    import jax
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.utils import enable_compile_cache

    enable_compile_cache()      # before the first jit

    from chainermn_tpu.models import (
        ResNetConfig, init_resnet, resnet_apply, softmax_cross_entropy,
        accuracy,
    )

    comm = cmn.create_communicator(args.communicator)
    if comm.rank == 0:
        print(f"world: {comm.size} devices, {comm.inter_size} processes")

    from chainermn_tpu.models import (
        ConvNetConfig, convnet_apply, init_convnet,
    )

    resnet = args.arch.startswith("resnet")
    if args.tiny:
        image, classes, n = 32, 8, 512
        # tiny smoke runs use the GAP head: the reference flatten heads
        # need near-native input sizes (32px collapses to 0 spatial)
        cfg = (ResNetConfig(depth=50, num_classes=classes, width=8,
                            dtype="float32") if resnet
               else ConvNetConfig(arch=args.arch, num_classes=classes,
                                  dtype="float32", head="gap"))
    else:
        image, classes, n = 224, 1000, 50000
        cfg = (ResNetConfig(depth=int(args.arch[6:]), num_classes=classes)
               if resnet
               else ConvNetConfig(arch=args.arch, num_classes=classes,
                                  image_size=image))

    from chainermn_tpu.datasets import SubDataset

    data = make_dataset(n, image, classes, npz=args.train_npz)
    split = len(data) * 9 // 10
    train = SubDataset(data, np.arange(split))
    test = SubDataset(data, np.arange(split, len(data)))
    train = cmn.scatter_dataset(train, comm, shuffle=True, seed=0)
    test = cmn.scatter_dataset(test, comm)

    if resnet:
        params, state = init_resnet(jax.random.PRNGKey(0), cfg)

        def loss_fn(params, state, x, y):
            logits, new_state = resnet_apply(
                cfg, params, state, x, train=True,
                axis_name=comm.axis_name)
            return softmax_cross_entropy(logits, y), new_state
    else:
        params, state = init_convnet(jax.random.PRNGKey(0), cfg), None

        if args.arch == "googlenet":
            # Inception recipe: main + 0.3·(aux_4a + aux_4d)
            def loss_fn(params, x, y):
                logits, a1, a2 = convnet_apply(
                    cfg, params, x, with_aux=True)
                return (softmax_cross_entropy(logits, y)
                        + 0.3 * (softmax_cross_entropy(a1, y)
                                 + softmax_cross_entropy(a2, y)))
        else:
            def loss_fn(params, x, y):
                return softmax_cross_entropy(
                    convnet_apply(cfg, params, x), y)

    opt = cmn.create_multi_node_optimizer(
        optax.sgd(args.lr, momentum=0.9), comm,
        allreduce_grad_dtype=args.grad_dtype)

    converter = None
    if args.loader == "native":
        from chainermn_tpu import native
        from chainermn_tpu.native import NativeBatchIterator

        if not native.native_available():
            # asked for by name: the pure-python stand-in would be
            # measured as the C++ loader
            raise SystemExit(
                "--loader native: the C++ loader could not be built "
                f"({native._build_error}); use --loader serial")
        # the native loader batches memory-resident field arrays:
        # materialise this process's scattered shard once up front —
        # bounded, because a full-size synthetic shard would be tens of
        # GB (SyntheticImages is lazy for exactly that reason)
        est = len(train) * image * image * 3 * 4
        if est > 4 << 30:
            raise SystemExit(
                f"--loader native materialises the local shard "
                f"(~{est / 2**30:.0f} GB here): use --tiny or point "
                "--train-npz at a real on-disk dataset")
        xs = np.stack([train[i][0] for i in range(len(train))])
        ys = np.asarray([train[i][1] for i in range(len(train))],
                        np.int32)
        train_it = NativeBatchIterator(
            [xs, ys], args.batchsize, shuffle=True, seed=1)
        # COPY out of the loader's recycled slot: the updater may hold
        # several batches at once (steps_per_execution windows) and the
        # C++ prefetch threads reuse slots as soon as they're released
        converter = lambda b: tuple(np.array(a) for a in b)
    else:
        train_it = cmn.SerialIterator(
            train, args.batchsize, shuffle=True, seed=1)
    test_it = cmn.SerialIterator(test, args.batchsize, repeat=False)

    updater_kw = {} if converter is None else {"converter": converter}
    updater = cmn.StandardUpdater(
        train_it, opt, loss_fn, params, comm, state=state, **updater_kw)
    trainer = cmn.Trainer(updater, (args.epoch, "epoch"), out=args.out)

    def metrics_fn(bundle, x, y):
        params, state = bundle
        if resnet:
            logits, _ = resnet_apply(cfg, params, state, x, train=False)
        else:
            logits = convnet_apply(cfg, params, x)
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
