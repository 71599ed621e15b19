"""Cells that train through the ChainerMN-parity path, wired as
``examples/imagenet/train_imagenet.py`` wires it:
``create_communicator -> scatter_dataset -> create_multi_node_optimizer
-> SerialIterator -> StandardUpdater -> Trainer.run``.

One ``Trainer.run()`` holds everything: the first iterations, which the
plain reference has followed beforehand; the warm-up; and the window.
The benchmark's only hand inside it is one extension that runs last in
every iteration and synchronises nothing until it closes the window.
"""

import importlib
import os

import numpy as np

from benchmarks.lib import check, counts
from benchmarks.lib.harness import (
    Outcome, Window, build_optimizer, first_gradient_norms, log,
    program_bytes, same_layout,
)
from benchmarks.reference.common import delta_norms


def _images(run, n, image, classes):
    """Seeded synthetic fp32 images and labels in host memory."""
    rng = np.random.default_rng(run.seed)
    xs = rng.standard_normal((n, image, image, 3), dtype=np.float32)
    xs *= np.float32(0.3)
    ys = rng.integers(0, classes, n, dtype=np.int32)
    return xs, ys


def _bn_state(shapes):
    """The program's batch-norm running statistics at their initial
    values (mean 0, variance 1, count 0), built to its own structure."""
    import jax
    import jax.numpy as jnp

    def leaf(path, s):
        fill = 1 if jax.tree_util.keystr(path[-1:]) == ".var" else 0
        return jnp.full(s.shape, fill, s.dtype)

    return jax.tree_util.tree_map_with_path(leaf, shapes)


def reference_job(run):
    """What the plain reference follows: its module, the seeded weights
    and the first batches, on the rows the serial feed will bring (no
    shuffle: the set is random already).  ``limits.py`` gives the same
    to the control."""
    import jax

    cfg, job = run.config, run.traffic
    reference = importlib.import_module(
        f"benchmarks.reference.{cfg['reference']}")
    batch = job["batch"]
    xs, ys = _images(run, job["dataset_images"], cfg["image_size"],
                     cfg["num_classes"])

    def make_params():
        return jax.jit(lambda k: reference.init(k, cfg))(run.key(0))

    batches = [(xs[i * batch:(i + 1) * batch], ys[i * batch:(i + 1) * batch])
               for i in range(job["check_steps"])]
    return reference, make_params, batches, (xs, ys)


def run(run):
    import jax

    import chainermn_tpu as cmn
    from chainermn_tpu.models import (
        ResNetConfig, init_resnet, resnet_apply, softmax_cross_entropy,
    )
    from chainermn_tpu.utils.telemetry import get_recorder

    cfg, job = run.config, run.traffic
    batch, n_check, n_warm = job["batch"], job["check_steps"], \
        job["warmup_steps"]
    reference, make_params, batches, (xs, ys) = reference_job(run)
    run.mark("images")
    # the plain reference first, before the program's state exists
    ref = run.timed_reference(
        lambda: reference.follow(cfg, make_params, batches))
    log("reference", seconds=f"{run.reference_s:.2f}", losses=ref["losses"])
    run.mark("reference")

    rcfg = ResNetConfig(depth=cfg["depth"], num_classes=cfg["num_classes"],
                        width=cfg["width"], dtype=cfg["dtype"])
    shapes, state_shapes = jax.eval_shape(
        lambda k: init_resnet(k, rcfg), run.key(0))
    params = make_params()
    same_layout(params, shapes, "init_resnet")
    comm = cmn.create_communicator("tpu_xla", devices=run.devices)
    train = cmn.scatter_dataset(list(zip(xs, ys)), comm)

    def loss_fn(params, state, x, y):
        logits, new_state = resnet_apply(
            rcfg, params, state, x, train=True, axis_name=comm.axis_name)
        return softmax_cross_entropy(logits, y), new_state

    opt = cmn.create_multi_node_optimizer(
        build_optimizer(cfg["optimizer"]), comm)
    updater = cmn.StandardUpdater(
        cmn.SerialIterator(train, batch, shuffle=False), opt, loss_fn,
        params, comm, state=_bn_state(state_shapes))
    del params
    trainer = cmn.Trainer(
        updater, (2 ** 62, "iteration"),
        out=os.path.join(run.scratch, "trainer"))
    trainer.extend(cmn.LogReport(
        trigger=(job["log_interval_iterations"], "iteration")))
    run.mark("trainer built")

    window = Window(run, batch)
    seen = {"losses": []}
    verdict = {}
    recorder = get_recorder()

    def stopwatch(trainer):
        it = trainer.updater.iteration
        loss = trainer.observation["main/loss"]
        if it <= n_check:
            seen["losses"].append(float(loss))
            if it == 1:
                run.mark("first iteration")
                seen["grad_norms"] = first_gradient_norms(
                    trainer.updater.opt_state,
                    cfg["optimizer"]["first_gradient"])
                verdict["memory"] = max(
                    program_bytes(step.lower(
                        *_step_args(updater, xs[:batch], ys[:batch])).compile())
                    for step in updater._step_cache.values())
            if it == n_check:
                seen["delta_norms"] = delta_norms(
                    trainer.updater.params, make_params())
                verdict["compared"] = check.gaps(seen, ref)
                verdict["correct"] = check.judge(
                    verdict["compared"], cfg["check"]["limits"], log)
                run.mark("checked")
        elif it == n_check + n_warm - 1:
            run.start_trace()
        elif it == n_check + n_warm:
            if run.traced:
                recorder.enable()
                recorder.clear()
            run.mark("warm")
            window.open()
        elif it > n_check + n_warm:
            window.end_iteration(loss)
            if window.last():
                window.close(trainer.updater.params)
                trainer.stop("window closed")

    trainer.extend(stopwatch, trigger=(1, "iteration"), name="stopwatch",
                   priority=0)
    trainer.run()
    run.stop_trace()
    spans = recorder.events() if run.traced else []
    recorder.disable()
    return Outcome(
        correct=verdict["correct"], window=window,
        memory_peak_bytes=verdict["memory"], spans=spans,
        compared=verdict["compared"], readings=(seen, ref),
        facts={"flops_per_unit": counts.resnet_train_flops_per_image(cfg)})


def _step_args(updater, *batch):
    """Shapes and shardings of the step program's arguments, for its
    memory analysis (the updater does not publish its compiled step)."""
    import jax

    def spec(a, sharding=None):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=sharding or a.sharding)

    carry = jax.tree.map(
        spec, (updater.params, updater.state, updater.opt_state))
    return (carry,) + tuple(
        spec(a, updater._batch_sharding) for a in batch)
