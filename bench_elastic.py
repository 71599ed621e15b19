"""Elastic-resume cost benchmark: re-layout resume vs same-topology
resume, across snapshot sizes.

The elastic layer (docs/RESILIENCE.md "Elastic resume") promises that a
resize resume — read the minimal covering shard set, re-slice every
ZeRO-1 leaf onto the new world — costs about one extra host-side pass
over the optimizer state on top of the exact resume's CRC-checked load.
This bench measures both arms against real ZeRO-1 MLP train states on
the virtual pod:

- **exact arm** — ``maybe_load`` at the SAME world the snapshot was
  saved under (world=8): the bitwise path, CRC walk + tree restore.
- **relayout arm** — ``maybe_load`` of the same snapshot at world=4:
  the re-layout path (topology compare, per-leaf concat/unpad/re-pad/
  re-split, plan invalidation) on top of the identical load.

Both arms run best-of-rounds at two snapshot sizes (``--dim`` scaled
down ×4 for the small point) so the cost's scaling with state size is
recorded, not assumed.  Prints ONE JSON line {"metric", "value",
"unit", "vs_baseline", ...}: value = relayout resume time ÷ exact
resume time at the LARGE size ("x"; ~1 = re-layout is as cheap as the
exact path).  One child under a timeout, the parent off JAX
(``_bench_common``).
"""

import argparse
import json
import os
import sys
import time

from _bench_common import pin_platform, run_child_with_retries

METRIC = "elastic_relayout_resume_cost"
UNIT = "x"


def _make_updater(comm, dim, hidden, classes, batch, n_examples):
    import jax
    import numpy as np
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.models import (init_mlp, mlp_apply,
                                      softmax_cross_entropy)

    rng = np.random.RandomState(0)
    X = rng.randn(n_examples, dim).astype(np.float32)
    Y = (rng.rand(n_examples) * classes).astype(np.int32)
    it = cmn.SerialIterator((X, Y), batch, shuffle=True, seed=11)
    params = init_mlp(jax.random.PRNGKey(0), [dim, hidden, classes])
    opt = cmn.create_multi_node_optimizer(
        optax.adam(5e-2), comm, zero1=True)

    def loss_fn(p, x, y):
        return softmax_cross_entropy(mlp_apply(p, x), y)

    return cmn.StandardUpdater(it, opt, loss_fn, params, comm)


def _measure_size(dim, hidden, batch, rounds, tmpdir):
    """One snapshot size: save a trained ZeRO-1 state at world=8, time
    exact resume at 8 and re-layout resume at 4 (best of rounds)."""
    import jax
    import numpy as np

    import chainermn_tpu as cmn
    from chainermn_tpu.extensions import create_multi_node_checkpointer

    classes, n_examples = 10, max(4 * batch, 512)
    comm8 = cmn.create_communicator("tpu_xla")
    upd = _make_updater(comm8, dim, hidden, classes, batch, n_examples)
    upd.update()
    jax.block_until_ready(upd.params)
    path = os.path.join(tmpdir, f"snap_d{dim}")
    cp = create_multi_node_checkpointer(comm8, path, elastic=True)
    cp.save(upd)
    state_bytes = int(sum(
        np.asarray(l).nbytes
        for l in jax.tree.leaves((jax.device_get(upd.params),
                                  jax.device_get(upd.opt_state)))))

    comm4 = cmn.create_communicator(
        "tpu_xla", devices=jax.devices()[:4])
    # one throwaway load: first-touch costs (module imports, allocator
    # growth) must not be billed to whichever arm runs first
    warm = create_multi_node_checkpointer(comm8, path, elastic=True)
    warm.maybe_load(_make_updater(comm8, dim, hidden, classes, batch,
                                  n_examples))
    best = {"exact": float("inf"), "relayout": float("inf")}
    for _ in range(rounds):
        for arm, comm in (("exact", comm8), ("relayout", comm4)):
            loader = create_multi_node_checkpointer(comm, path,
                                                    elastic=True)
            fresh = _make_updater(comm, dim, hidden, classes, batch,
                                  n_examples)
            t0 = time.perf_counter()
            resumed = loader.maybe_load(fresh)
            dt = time.perf_counter() - t0
            assert resumed == 1, resumed
            assert loader.last_resume_mode == arm, \
                (arm, loader.last_resume_mode)
            best[arm] = min(best[arm], dt)
    return {
        "dim": dim,
        "hidden": hidden,
        "state_mb": round(state_bytes / 1e6, 3),
        "exact_resume_ms": round(best["exact"] * 1e3, 3),
        "relayout_resume_ms": round(best["relayout"] * 1e3, 3),
        "ratio": round(best["relayout"] / best["exact"], 4),
    }


def run(dim=256, hidden=1024, batch=64, rounds=3):
    import tempfile

    import jax

    tmpdir = tempfile.mkdtemp(prefix="bench_elastic_")
    sizes = sorted({max(dim // 4, 8), dim})
    points = [_measure_size(d, max(hidden * d // dim, 8), batch,
                            rounds, tmpdir)
              for d in sizes]
    head = points[-1]       # the large size is the headline
    return {
        "metric": METRIC,
        "value": head["ratio"],
        "unit": UNIT,
        "vs_baseline": head["ratio"],
        "exact_resume_ms": head["exact_resume_ms"],
        "relayout_resume_ms": head["relayout_resume_ms"],
        "relayout_overhead_ms": round(
            head["relayout_resume_ms"] - head["exact_resume_ms"], 3),
        "sizes": points,
        "saved_world": 8,
        "resume_world": 4,
        "rounds": rounds,
        "dim": dim,
        "hidden": hidden,
        "batch": batch,
        "n_devices": jax.device_count(),
        "device_kind": jax.devices()[0].device_kind,
    }


def _child_main(args):
    env_platform = os.environ.get("JAX_PLATFORMS", "")
    if args.platform == "cpu" or (
            args.platform is None and env_platform.startswith("cpu")):
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count"
                        f"={args.devices}").strip()
    pin_platform(args.platform)
    result = run(dim=args.dim, hidden=args.hidden, batch=args.batch,
                 rounds=args.rounds)
    print("BENCH_RESULT " + json.dumps(result))


def _parent_main(args):
    here = os.path.abspath(__file__)
    cmd = [sys.executable, here, "--child",
           "--dim", str(args.dim), "--hidden", str(args.hidden),
           "--batch", str(args.batch), "--rounds", str(args.rounds),
           "--devices", str(args.devices)]
    if args.platform:
        cmd += ["--platform", args.platform]
    return run_child_with_retries(
        cmd, os.path.dirname(here), args.timeouts, METRIC, UNIT,
        record=args.platform is None,
        match={"dim": args.dim, "hidden": args.hidden,
               "batch": args.batch})


def _parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--child", action="store_true")
    p.add_argument("--dim", type=int, default=256,
                   help="large-size MLP input width (the small point "
                        "runs at dim/4)")
    p.add_argument("--hidden", type=int, default=1024)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--rounds", type=int, default=3,
                   help="best-of-rounds per arm per size")
    p.add_argument("--devices", type=int, default=8,
                   help="virtual device count for the cpu platform")
    p.add_argument("--platform", default=None)
    p.add_argument("--timeouts", type=int, nargs="+", default=[480])
    return p.parse_args(argv)


if __name__ == "__main__":
    args = _parse_args(sys.argv[1:])
    if args.child:
        _child_main(args)
    else:
        sys.exit(_parent_main(args))
