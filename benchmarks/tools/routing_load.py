"""Where a sparse cell's routed rows go at its seeded weights, and which
layers send them there, at the cell's own size (no step, no window):

    python benchmarks/tools/routing_load.py --workload <name> --seeds 1,2,3

For each seed and each sparse layer, the rows the router sends to the
experts on the pool's first batch (the program's ``expert_load``): the
largest expert's rows over the mean expert's (what ``moe.load_imbalance``
takes the worst layer of) and the rows that fall to the experts held
here.  Then the same with one thing taken out of the parameters at a
time: the selection bias zeroed (where the file has one), and for each
mixer the configuration has, the output projection ``wo`` of that
mixer's layers zeroed, so that those layers add nothing to the residual
stream.  The benchmark's own runs never run this; the driver's check
does not either.

``--steps 5,13,25`` then follows the weights as run: the loads after
that many steps of the cell's own optimizer on the pool's batches in
rotation (a cell reads its loads after its window: 3 checked, 2 warmed
and the window's steps, 8 of them when traced).
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import cells    # noqa: E402
from benchmarks.lib.harness import (    # noqa: E402
    CompileCounter, Run, build_optimizer, log,
)


def _edited(params, pcfg, zero_bias=False, silent_mixer=None):
    """The reference's parameters with the selection bias zeroed and/or
    the ``wo`` of every layer of one mixer zeroed."""
    def block(blk, kind):
        blk = dict(blk)
        if zero_bias and "router_bias" in blk:
            blk["router_bias"] = blk["router_bias"] * 0
        if getattr(kind, "mixer", "softmax") == silent_mixer:
            blk["wo"] = blk["wo"] * 0
        return blk

    out = dict(params)
    if "leading" in params:
        out["leading"] = tuple(block(b, k) for b, k in zip(
            params["leading"], pcfg.leading_layers))
    if isinstance(params["blocks"], tuple):     # a stack a position
        out["blocks"] = tuple(block(b, k) for b, k in zip(
            params["blocks"], pcfg.layer_pattern))
    elif silent_mixer is None:
        out["blocks"] = block(params["blocks"], None)
    else:
        raise SystemExit("one stack holds every kind: no mixer of its "
                         "own to silence")
    return out


def loads(workload, seed, rehearse=False, steps=()):
    """``(sparse layers' mixers, (first, held), [(variant, load)])`` for
    one seed: ``load`` ``(sparse layers, experts)`` on the pool's first
    batch with the seeded weights, as run and with one thing taken out
    at a time; with ``steps``, also after each of those counts of
    training steps from the weights as run."""
    import jax
    import numpy as np

    from chainermn_tpu.models import (
        expert_load, make_train_step, shard_params,
    )
    from chainermn_tpu.parallel import MeshConfig
    from chainermn_tpu.training import shard_opt_state

    cell, config, traffic = cells.load_cell(workload, rehearse)
    driver = cells.module("drivers", traffic["driver"])
    pcfg = driver._program_config(config, traffic)
    devices = jax.devices()[:cell["chips"]]
    mc = MeshConfig(devices=devices, **traffic["mesh"])
    mixer = lambda kind: getattr(kind, "mixer", "softmax")
    lead = len(pcfg.leading_layers)
    kinds = list(pcfg.leading_layers) + list(pcfg.layer_pattern) * (
        (pcfg.n_layers - lead) // len(pcfg.layer_pattern))
    sparse = [mixer(k) for i, k in enumerate(kinds)
              if i >= lead or pcfg.leading_mlp == "sparse"]
    variants = [("as_run", {})]
    if pcfg.router_bias:
        variants.append(("bias_zeroed", dict(zero_bias=True)))
    if len(set(map(mixer, kinds))) > 1:
        variants += [(f"{m}_layers_silent", dict(silent_mixer=m))
                     for m in sorted(set(map(mixer, kinds)))]

    run = Run(cell=cell, config=config, traffic=traffic, devices=devices,
              seed=seed, seconds=0, trace_dir="", scratch="",
              compiles=CompileCounter())
    _, make_params, _, batches = driver.reference_job(run)
    batches = [tuple(jax.device_put(t, mc.sharding(
        ("data", "expert"), "seq")) for t in b) for b in batches]
    # one compilation for every variant and step count
    count = jax.jit(lambda params: expert_load(
        mc, pcfg, params, batches[0][0]))
    load = lambda params: np.asarray(count(params))

    def placed(**edit):
        params = _edited(make_params(), pcfg, **edit)
        return shard_params(mc, pcfg, dict(params, blocks=jax.tree.map(
            lambda a: a[None], params["blocks"])))

    out = [(name, load(placed(**edit))) for name, edit in variants]
    if steps:
        opt = build_optimizer(config["optimizer"])
        params = placed()
        state = shard_opt_state(opt, params)
        step = make_train_step(mc, pcfg, opt)
        done = 0
        for n in sorted(steps):
            for i in range(done, n):
                params, state, _ = step(params, state,
                                        *batches[i % len(batches)])
            done = n
            out.append((f"after_{n}_steps", load(params)))
    return sparse, pcfg.experts_held or (0, pcfg.n_experts), out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--steps", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    steps = [int(n) for n in args.steps.split(",") if n]
    for seed in (int(s) for s in args.seeds.split(",")):
        sparse, (first, held), found = loads(
            args.workload, seed, args.rehearse, steps)
        for name, load in found:
            log("load", seed=seed, variant=name, layers=",".join(sparse),
                imbalance=" ".join(
                    f"{row.max() * len(row) / row.sum():.3f}"
                    for row in load),
                rows_held=" ".join(
                    str(int(row[first:first + held].sum())) for row in load),
                rows_held_if_even=int(load[0].sum() * held / load.shape[1]))


if __name__ == "__main__":
    main()
