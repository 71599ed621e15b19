"""Live-elastic cost benchmark: shard-only covering sets vs full
per-rank sets, async-vs-sync snapshot step-time hit, and the live
resize pause.

Three promises of the in-run survival layer (docs/RESILIENCE.md
"Scale-free snapshots" / "Live elastic training"), measured instead of
assumed on the 8-device virtual pod:

- **shard-only set cost** — one trained ZeRO-1 state saved both ways:
  the full-state-per-rank layout (every rank's file holds the complete
  gathered state — what an 8-process world writes today; the 8 files
  are really written so the wall time is IO, not arithmetic) vs the
  shard-only covering set (8 member parts, root carries replicated
  leaves once).  Headline value = full-set aggregate bytes ÷ shard-set
  aggregate bytes ("x"; ~world for ZeRO-dominated states, lower when
  replicated params dominate).
- **async snapshot hit** — the same training loop checkpointing every
  iteration, sync writes vs async double-buffered streaming; reported
  as async/sync mean step time (<1 = the stream really left the loop).
- **resize pause** — a live 8→4 shrink and 4→8 grow through
  ``ResizeController.resize`` (drain, host re-layout, rebind; the
  first post-resize step's recompile is reported separately, as a
  restart would pay it too).

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", ...}.
One child under a timeout, the parent off JAX (``_bench_common``).
"""

import argparse
import json
import os
import sys
import time

from _bench_common import pin_platform, run_child_with_retries

METRIC = "live_elastic_shard_set_cost"
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


def _dir_bytes(path, prefix):
    return sum(os.path.getsize(os.path.join(path, f))
               for f in os.listdir(path) if f.startswith(prefix))


def _measure_set_cost(comm, upd, tmpdir, rounds):
    """Full per-rank set (every rank file = the complete state; all 8
    really written) vs the shard-only covering set, best of rounds."""
    import jax

    from chainermn_tpu.extensions import create_multi_node_checkpointer
    from chainermn_tpu.utils.serialization import save_state

    world = comm.size
    jax.block_until_ready(upd.params)
    best = {"full": float("inf"), "shard": float("inf")}
    sizes = {}
    for r in range(rounds):
        full_dir = os.path.join(tmpdir, f"full{r}")
        cp_full = create_multi_node_checkpointer(comm, full_dir,
                                                 elastic=True)
        t0 = time.perf_counter()
        cp_full.save(upd)          # rank 0's file, the real save path
        state = {"iteration": upd.iteration, "world_size": 1,
                 "params": upd.params, "opt_state": upd.opt_state}
        topo = cp_full._topology(upd)
        for rank in range(1, world):   # the other ranks' identical files
            save_state(os.path.join(full_dir,
                                    f"snapshot_iter_{upd.iteration}"
                                    f".{rank}"),
                       state, topology=topo)
        best["full"] = min(best["full"], time.perf_counter() - t0)

        shard_dir = os.path.join(tmpdir, f"shard{r}")
        cp_shard = create_multi_node_checkpointer(
            comm, shard_dir, elastic=True, shard_only=True)
        t0 = time.perf_counter()
        cp_shard.save(upd)
        best["shard"] = min(best["shard"], time.perf_counter() - t0)
        sizes = {"full_set_bytes": _dir_bytes(full_dir, "snapshot"),
                 "shard_set_bytes": _dir_bytes(shard_dir, "snapshot")}
    return {
        "world": world,
        "full_set_bytes": sizes["full_set_bytes"],
        "shard_set_bytes": sizes["shard_set_bytes"],
        "bytes_ratio": round(
            sizes["full_set_bytes"] / sizes["shard_set_bytes"], 4),
        "full_set_write_ms": round(best["full"] * 1e3, 3),
        "shard_set_write_ms": round(best["shard"] * 1e3, 3),
        "write_time_ratio": round(best["full"] / best["shard"], 4),
    }


def _measure_async_hit(comm, dim, hidden, classes, batch, n_examples,
                       tmpdir, iters, rounds):
    """Per-iteration-checkpoint cost, sync vs async writes, two views:

    - ``save_call_*`` — what the training loop BLOCKS on per save()
      call (sync: device→host copy + full file write; async: the copy
      into the double buffer + join of the long-finished previous
      stream).  This is the half a CPU mesh can measure honestly.
    - ``loop_*`` — whole-loop step time.  XLA:CPU computes on the same
      cores the writer thread streams on, so the overlap win is NOT
      expected to show here (the
      wire/IO-hiding half needs hardware whose compute does not share
      the writer's cores); the figure is recorded so the CPU-mesh
      overhead is known, not hidden.

    First save of each arm excluded — it pays the compile either way.
    """
    import jax

    from chainermn_tpu.extensions import create_multi_node_checkpointer

    best = {"sync": (float("inf"), float("inf")),
            "async": (float("inf"), float("inf"))}
    for r in range(rounds):
        for arm, is_async in (("sync", False), ("async", True)):
            upd = _make_updater(comm, dim, hidden, classes, batch,
                                n_examples)
            cp = create_multi_node_checkpointer(
                comm, os.path.join(tmpdir, f"hit_{arm}{r}"),
                async_write=is_async)
            upd.update()               # compile
            cp.save(upd)               # arm the pipeline
            save_s = 0.0
            t0 = time.perf_counter()
            for _ in range(iters):
                upd.update()
                s0 = time.perf_counter()
                cp.save(upd)
                save_s += time.perf_counter() - s0
            cp.finalize()
            jax.block_until_ready(upd.params)
            loop = (time.perf_counter() - t0) / iters
            best[arm] = (min(best[arm][0], save_s / iters),
                         min(best[arm][1], loop))
    return {
        "save_call_sync_ms": round(best["sync"][0] * 1e3, 3),
        "save_call_async_ms": round(best["async"][0] * 1e3, 3),
        "save_call_ratio": round(best["async"][0] / best["sync"][0], 4),
        "loop_sync_step_ms": round(best["sync"][1] * 1e3, 3),
        "loop_async_step_ms": round(best["async"][1] * 1e3, 3),
        "loop_step_ratio": round(best["async"][1] / best["sync"][1], 4),
        "ckpt_iters": iters,
    }


def _measure_resize_pause(comm_factory, opt_factory, dim, hidden,
                          classes, batch, n_examples, tmpdir):
    import time as _t

    import chainermn_tpu as cmn
    from chainermn_tpu.training.elastic import ResizeController

    comm8 = comm_factory(8)
    upd = _make_updater(comm8, dim, hidden, classes, batch, n_examples)
    trainer = cmn.Trainer(upd, (10_000, "iteration"),
                          out=os.path.join(tmpdir, "resize_out"))
    ctrl = ResizeController(comm_factory, opt_factory)
    for _ in range(2):
        upd.update()
    rows = []
    for world in (4, 8):
        ctrl.resize(trainer, world)
        t0 = _t.perf_counter()
        upd.update()               # the new world's first (compiling) step
        first_step = _t.perf_counter() - t0
        rows.append({"world": world,
                     "pause_ms": round(
                         ctrl.resizes[-1]["pause_s"] * 1e3, 3),
                     "first_step_ms": round(first_step * 1e3, 3)})
    return {"resizes": rows}


def run(dim=256, hidden=1024, batch=64, iters=8, rounds=3):
    import tempfile

    import jax

    import chainermn_tpu as cmn
    import optax

    tmpdir = tempfile.mkdtemp(prefix="bench_live_elastic_")
    classes, n_examples = 10, max(4 * batch, 512)

    def comm_factory(n):
        return cmn.create_communicator("tpu_xla",
                                       devices=jax.devices()[:n])

    def opt_factory(comm):
        return cmn.create_multi_node_optimizer(
            optax.adam(5e-2), comm, zero1=True)

    comm8 = comm_factory(8)
    upd = _make_updater(comm8, dim, hidden, classes, batch, n_examples)
    upd.update()
    set_cost = _measure_set_cost(comm8, upd, tmpdir, rounds)
    async_hit = _measure_async_hit(comm8, dim, hidden, classes, batch,
                                   n_examples, tmpdir, iters, rounds)
    pause = _measure_resize_pause(comm_factory, opt_factory, dim,
                                  hidden, classes, batch, n_examples,
                                  tmpdir)
    return {
        "metric": METRIC,
        "value": set_cost["bytes_ratio"],
        "unit": UNIT,
        "vs_baseline": set_cost["bytes_ratio"],
        **set_cost,
        **async_hit,
        **pause,
        "note": ("full set = complete state per rank (the documented "
                 "N-process layout; all files really written), shard "
                 "set = per-member 1/N parts + one root"),
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
                 iters=args.iters, rounds=args.rounds)
    print("BENCH_RESULT " + json.dumps(result))


def _parent_main(args):
    here = os.path.abspath(__file__)
    cmd = [sys.executable, here, "--child",
           "--dim", str(args.dim), "--hidden", str(args.hidden),
           "--batch", str(args.batch), "--iters", str(args.iters),
           "--rounds", str(args.rounds), "--devices", str(args.devices)]
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
    p.add_argument("--dim", type=int, default=256)
    p.add_argument("--hidden", type=int, default=1024)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--iters", type=int, default=8,
                   help="checkpoint-per-iteration steps per async arm")
    p.add_argument("--rounds", type=int, default=3,
                   help="best-of-rounds per arm")
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
