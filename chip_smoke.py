"""The quickest proof that chainermn_tpu still starts on the chip.

One process drives the system's main paths once, through the entry
points a user calls, at the full width of models the repo supports
(depth as published too; weights and data are random, from ``--seed``):

- ``train-transformer``: the dense 300M GQA decoder, 8 x 2048 tokens,
  ``init_transformer -> shard_params -> make_train_step`` with AdamW;
- ``train-resnet``: ResNet-50 bf16 at 224 px, batch 256, through the
  ChainerMN-parity path (``create_communicator -> scatter_dataset ->
  create_multi_node_optimizer -> StandardUpdater -> Trainer.run``);
- ``serve``: the 8-layer d_model-1024 decoder through
  ``TransformerAdapter -> ServingEngine``, greedy tokens checked
  against ``make_generate_fn``.

``--chips 4`` runs instead the data-parallel ResNet, the FSDP
transformer and the ring-attention transformer over four chips, each
against its one-chip twin, and nothing else.

Nothing is caught: a phase that fails ends the run with a traceback and
a non-zero exit code.  Without a TPU the script exits non-zero before
any phase; ``--tiny`` is the CPU rehearsal of the control flow and never
prints the result line.  The last line of a passing run is the JSON
object the driver reads.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
LM_BATCH, LM_SEQ = 8, 2048
# "dots" (README's fastest July setting) needs 16.0 GB of temporaries +
# 3.6 GB of arguments at this size — more than one v5e chip holds;
# "full" needs 6.7 + 3.6 GB (sandbox compile for the described chip,
# tests/function_tests/test_tpu_compile.py keeps the check)
LM_REMAT_POLICY = "full"
LOSS_RTOL = 2e-2    # bf16 compute: same batch, different partitioning


def log(phase, **fields):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def peak_bytes(devices):
    """Per-device ``peak_bytes_in_use`` (None where the backend keeps no
    memory statistics, i.e. the CPU rehearsal)."""
    stats = [d.memory_stats() for d in devices]
    return [s["peak_bytes_in_use"] if s else None for s in stats]


# ------------------------------------------------------------------ #
# train-transformer
# ------------------------------------------------------------------ #

def transformer_config(tiny, **overrides):
    from chainermn_tpu.models import TransformerConfig

    if tiny:
        kw = dict(vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2,
                  d_head=16, d_ff=256, n_layers=2, max_seq=128)
    else:
        kw = dict(vocab_size=32000, d_model=1024, n_heads=16,
                  n_kv_heads=4, d_head=64, d_ff=4096, n_layers=24,
                  max_seq=LM_SEQ)
    kw.update(attention="flash", dtype="bfloat16", remat=True,
              remat_policy=LM_REMAT_POLICY)
    kw.update(overrides)
    return TransformerConfig(**kw)


def transformer_optimizer():
    import optax

    return optax.adamw(3e-4)


def lm_tokens(cfg, tiny, seed):
    batch, seq = (4, 128) if tiny else (LM_BATCH, LM_SEQ)
    toks = np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    return toks[:, :seq], toks[:, 1:]


def run_transformer(name, mesh_axes, devices, tiny, seed, n_steps=3,
                    **cfg_overrides):
    """Warm-up + ``n_steps`` timed steps of the 300M train step on one
    fixed batch.  Returns the per-step losses (warm-up first)."""
    import jax

    from chainermn_tpu.models import (
        init_transformer, make_train_step, shard_params,
    )
    from chainermn_tpu.parallel import MeshConfig, zigzag_indices
    from chainermn_tpu.training import shard_opt_state

    cfg = transformer_config(tiny, **cfg_overrides)
    mc = MeshConfig(devices=devices, **mesh_axes)
    opt = transformer_optimizer()
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(seed), cfg))
    opt_state = shard_opt_state(opt, params)
    x, y = lm_tokens(cfg, tiny, seed)
    if cfg.attention == "ring" and cfg.seq_layout == "zigzag":
        perm = zigzag_indices(mc.axis_size("seq"), x.shape[1]).reshape(-1)
        x, y = x[:, perm], y[:, perm]
    tok_sharding = mc.sharding(("data", "expert"), "seq")
    x, y = jax.device_put(x, tok_sharding), jax.device_put(y, tok_sharding)

    step = make_train_step(mc, cfg, opt)
    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, x, y).compile()
    compile_s = time.perf_counter() - t0
    on_tpu = devices[0].platform == "tpu"
    has_kernel = "tpu_custom_call" in compiled.as_text()
    # the interpreter or the XLA attention standing in for the kernel is
    # a failure on the chip (the CPU rehearsal interprets by design)
    assert has_kernel == on_tpu, \
        f"flash kernel in the compiled step: {has_kernel} on " \
        f"{devices[0].platform}"
    mem = compiled.memory_analysis()
    log(name, compile_s=f"{compile_s:.2f}", kernel=has_kernel,
        remat_policy=cfg.remat_policy, mesh=dict(mc.mesh.shape),
        args_bytes=mem.argument_size_in_bytes,
        temp_bytes=mem.temp_size_in_bytes)

    if len(devices) > 1:
        check_every_device_holds(name, devices, params, cfg.fsdp)

    losses, times = [], []
    for i in range(n_steps + 1):
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, x, y)
        jax.block_until_ready((params, loss))
        times.append(time.perf_counter() - t0)
        losses.append(float(loss))
    log(name, warmup_step_s=f"{times[0]:.4f}",
        step_s=[round(t, 4) for t in times[1:]],
        tokens_per_step=x.size,
        losses=[round(v, 4) for v in losses],
        peak_bytes_in_use=peak_bytes(devices))
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    return losses


def check_every_device_holds(name, devices, params, sharded):
    """Each device holds its share of the parameters: a replica of every
    replicated leaf and (``sharded``) 1/N of every sharded one — code
    that has only met one real chip may have put everything on the
    first."""
    import jax

    per_dev = {d: 0 for d in devices}
    for leaf in jax.tree.leaves(params):
        for sh in leaf.addressable_shards:
            per_dev[sh.device] += sh.data.nbytes
    sizes = list(per_dev.values())
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(params))
    log(name, param_bytes_total=total, param_bytes_per_device=sizes)
    assert min(sizes) > 0 and max(sizes) == min(sizes), sizes
    if sharded:
        # the block stack (almost all of the model) is 1/N per device
        assert sizes[0] < total * 0.5, (sizes, total)
    else:
        assert sizes[0] == total, (sizes, total)
    in_use = [d.memory_stats() for d in devices]
    if all(in_use):     # the CPU rehearsal keeps no memory statistics
        in_use = [s["bytes_in_use"] for s in in_use]
        log(name, bytes_in_use=in_use)
        assert all(b >= n for b, n in zip(in_use, sizes)), (in_use, sizes)


# ------------------------------------------------------------------ #
# train-resnet
# ------------------------------------------------------------------ #

def run_resnet(name, devices, tiny, seed, n_iters, must_fall=True):
    """ResNet-50 through the ChainerMN-parity path exactly as
    ``examples/imagenet/train_imagenet.py`` wires it; returns the
    per-iteration losses from ``LogReport``."""
    import jax
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.models import (
        ResNetConfig, init_resnet, resnet_apply, softmax_cross_entropy,
    )

    if tiny:
        image, classes, batch = 32, 8, 16
        cfg = ResNetConfig(depth=50, num_classes=classes, width=8)
    else:
        image, classes, batch = 224, 1000, 256
        cfg = ResNetConfig(depth=50, num_classes=classes)
    comm = cmn.create_communicator("tpu_xla", devices=devices)
    assert comm.size == len(devices), (comm.size, len(devices))

    # two batches' worth of seeded images, visited n_iters/2 times each
    rng = np.random.RandomState(seed)
    n = 2 * batch
    xs = 0.3 * rng.standard_normal(
        (n, image, image, 3)).astype(np.float32)
    ys = rng.randint(0, classes, n).astype(np.int32)
    train = cmn.scatter_dataset(list(zip(xs, ys)), comm)

    params, state = init_resnet(jax.random.PRNGKey(seed), cfg)

    def loss_fn(params, state, x, y):
        logits, new_state = resnet_apply(
            cfg, params, state, x, train=True, axis_name=comm.axis_name)
        return softmax_cross_entropy(logits, y), new_state

    opt = cmn.create_multi_node_optimizer(
        optax.sgd(0.1, momentum=0.9), comm)
    train_it = cmn.SerialIterator(train, batch, shuffle=False)
    updater = cmn.StandardUpdater(
        train_it, opt, loss_fn, params, comm, state=state)
    trainer = cmn.Trainer(
        updater, (n_iters, "iteration"),
        out=os.path.join(HERE, "chiprun_out", "chip_smoke", name))
    log_report = cmn.LogReport(trigger=(1, "iteration"))
    trainer.extend(log_report)
    ends = []

    def stopwatch(trainer):
        jax.block_until_ready(trainer.updater.params)
        ends.append(time.perf_counter())

    trainer.extend(stopwatch, trigger=(1, "iteration"), name="stopwatch")
    t0 = time.perf_counter()
    trainer.run()

    losses = [e["main/loss"] for e in log_report.log]
    assert len(losses) == n_iters, (len(losses), n_iters)
    iter_s = np.diff([t0] + ends)
    log(name, world=comm.size, batch=batch,
        first_iter_s=f"{iter_s[0]:.2f}",    # compile included
        iter_s=[round(float(t), 4) for t in iter_s[1:]],
        losses=[round(v, 4) for v in losses],
        peak_bytes_in_use=peak_bytes(devices))
    assert np.isfinite(losses).all(), losses
    if must_fall:
        assert min(losses[-2:]) < losses[0], f"loss did not fall: {losses}"
    if len(devices) > 1:
        check_every_device_holds(name, devices, updater.params, False)
    return losses


# ------------------------------------------------------------------ #
# serve
# ------------------------------------------------------------------ #

def run_serve(devices, tiny, seed):
    import jax

    from chainermn_tpu.models import (
        init_transformer, make_generate_fn, shard_params,
    )
    from chainermn_tpu.parallel import MeshConfig
    from chainermn_tpu.serving import ServingEngine, TransformerAdapter

    horizon, pmax, new = 512, 64, 16
    # bench_decode.py's decoder: the training widths at 8 layers
    cfg = transformer_config(
        tiny, max_seq=horizon, attention="local", pos_embedding="rope",
        remat=False, **({} if tiny else {"n_layers": 8}))
    mc = MeshConfig(data=1, devices=devices)
    host = init_transformer(jax.random.PRNGKey(seed), cfg)

    rng = np.random.RandomState(seed)
    lens = [5, 64, 17, 33, 48, 9]          # more requests than slots
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]

    # the static oracle: one ragged right-aligned batch through generate
    batch = np.zeros((len(lens), pmax), np.int32)
    for b, p in enumerate(prompts):
        batch[b, pmax - p.shape[0]:] = p
    gen = make_generate_fn(mc, cfg, max_len=pmax + new)
    t0 = time.perf_counter()
    ref = np.asarray(gen(shard_params(mc, cfg, host), batch,
                         prompt_lens=np.asarray(lens)))[:, pmax:]
    log("serve", static_generate_s=f"{time.perf_counter() - t0:.2f}")

    eng = ServingEngine(TransformerAdapter(mc, cfg), host, n_slots=4,
                        horizon=horizon, max_prompt=pmax, block=16,
                        round_tokens=8)

    def wave():
        """Submit every prompt, run to completion, check every request
        against the oracle; returns the seconds it took."""
        t0 = time.perf_counter()
        rids = [eng.submit(p, max_new=new) for p in prompts]
        comps = {c.rid: c for c in eng.run(max_steps=2000)}
        dt = time.perf_counter() - t0
        assert sorted(comps) == sorted(rids), (sorted(comps), rids)
        for b, rid in enumerate(rids):
            c = comps[rid]
            # the engine quarantines a request whose round raised
            assert c.status == "ok", (rid, c.status, c.detail)
            np.testing.assert_array_equal(
                c.tokens, ref[b],
                err_msg=f"request {rid} (prompt {lens[b]}) diverged "
                        "from make_generate_fn's static decode")
        return dt

    t0 = time.perf_counter()
    eng.warm()
    warm_up_s = time.perf_counter() - t0
    first_s = wave()        # round programs compile inside this one
    warm_s = wave()         # the warm engine: no compile in the window
    log("serve", requests=len(lens), prompt_lens=lens, new_tokens=new,
        tokens_equal_static=True, engine_warm_s=f"{warm_up_s:.2f}",
        first_wave_s=f"{first_s:.2f}",     # compile included
        warm_wave_s=f"{warm_s:.4f}",
        warm_tokens_per_s=f"{len(lens) * new / warm_s:.1f}",
        rounds=eng.n_rounds, peak_bytes_in_use=peak_bytes(devices))


# ------------------------------------------------------------------ #

def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=[1, 4],
                   help="4: the multi-chip phases and their one-chip "
                        "comparisons, nothing else")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--tiny", action="store_true",
                   help="rehearse the control flow at toy sizes on "
                        "whatever backend JAX has; never prints the "
                        "result line and exits non-zero")
    args = p.parse_args()

    import jax

    from chainermn_tpu.utils import enable_compile_cache

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" and not args.tiny:
        sys.exit(f"chip_smoke needs a TPU; JAX found {device}")
    # before the first jit (None on the CPU: the rehearsal compiles afresh)
    log("start", cache_dir=enable_compile_cache(), **device)
    if len(devices) < args.chips:
        sys.exit(f"--chips {args.chips} needs {args.chips} devices; "
                 f"JAX found {len(devices)}")
    devices = devices[:args.chips]
    one = devices[:1]
    t_start = time.perf_counter()

    if args.chips == 1:
        run_transformer("train-transformer", dict(data=1), one,
                        args.tiny, args.seed)
        run_resnet("train-resnet", one, args.tiny, args.seed, n_iters=8)
        run_serve(one, args.tiny, args.seed)
    else:
        ref = run_resnet("resnet-1chip", one, args.tiny, args.seed,
                         n_iters=2, must_fall=False)
        dp = run_resnet("resnet-dp4", devices, args.tiny, args.seed,
                        n_iters=8)
        np.testing.assert_allclose(dp[0], ref[0], rtol=LOSS_RTOL)
        ref = run_transformer("transformer-1chip-flash", dict(data=1),
                              one, args.tiny, args.seed, n_steps=1)
        fsdp = run_transformer("transformer-fsdp-data4", dict(data=4),
                               devices, args.tiny, args.seed, n_steps=2,
                               fsdp=True)
        np.testing.assert_allclose(fsdp[0], ref[0], rtol=LOSS_RTOL)
        ring = run_transformer("transformer-ring-seq4",
                               dict(data=1, seq=4), devices, args.tiny,
                               args.seed, n_steps=2, attention="ring")
        np.testing.assert_allclose(ring[0], ref[0], rtol=LOSS_RTOL)

    log("done", total_s=f"{time.perf_counter() - t_start:.1f}")
    if args.tiny:
        sys.exit("rehearsal at toy sizes: not a chip check")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
