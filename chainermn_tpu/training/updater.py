"""StandardUpdater — the jitted data-parallel train step.

Replaces the reference's ``Updater → optimizer.update(lossfun) →
loss.backward() → comm.multi_node_mean_grad(model)`` hot loop (SURVEY §3.1)
with its TPU shape: ONE jitted SPMD program per step containing forward,
backward, cross-replica grad mean, and the optimiser update — so XLA can
fuse and overlap the collective with compute (what pure_nccl needed streams
and double-buffer threads for).

The global batch enters sharded over the communicator's mesh axis; params
stay replicated; optimiser state is replicated too, EXCEPT under ZeRO-1
(detected from the transformation type), where it is carried
world-stacked and sharded over the axis; the ``multi-node optimizer``'s
``cross_replica_mean`` supplies the ``pmean``.
"""

from __future__ import annotations

import collections
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from chainermn_tpu.iterators.prefetch import (
    PrefetchIterator,
    StagingConverter,
    assemble_window,
    default_converter,
    pull_batch,
    put_window,
)
from chainermn_tpu.utils.metrics import get_registry
from chainermn_tpu.utils.programs import (
    get_accountant,
    get_ledger,
    ledger_jit,
    weakref_root,
)
from chainermn_tpu.utils.telemetry import device_scope, get_recorder

__all__ = ["StandardUpdater", "default_converter", "fuse_steps"]


def fuse_steps(step_fn, n_steps: int, *, scan_batches: bool = False,
               unroll: int = 1):
    """Fuse ``n_steps`` training steps into ONE XLA program.

    Each host→device dispatch costs fixed latency; running the step under
    ``lax.scan`` amortises that cost over ``n_steps`` and lets XLA keep
    the whole loop resident on device — the TPU-native analogue of
    "steps_per_execution" loops.  The reference had no equivalent: its
    hot loop crossed the host every iteration by construction
    (``trainer.run()`` → ``optimizer.update`` per batch, SURVEY §3.1).

    Args:
      step_fn: ``step_fn(carry, *batch) -> (carry, metrics)`` — one
        training step in scan form.  ``carry`` is the full mutable train
        state pytree (params, opt state, model state, ...).
      n_steps: number of steps fused per call.
      scan_batches: if True, every ``batch`` leaf must have a leading
        axis of size ``n_steps`` and each step consumes one slice (the
        "pull K batches, stack, execute" loop); if False the same batch
        is re-used by every fused step (synthetic-data benchmarks).
      unroll: forwarded to ``lax.scan``.

    Returns ``fused(carry, *batch) -> (carry, metrics)`` where every
    ``metrics`` leaf gains a leading ``n_steps`` axis.  Wrap the result
    in ``jax.jit`` (donating the carry) before use.
    """
    from jax import lax

    def fused(carry, *batch):
        if scan_batches:
            return lax.scan(
                lambda c, b: step_fn(c, *b), carry, batch,
                length=n_steps, unroll=unroll)
        return lax.scan(
            lambda c, _: step_fn(c, *batch), carry, None,
            length=n_steps, unroll=unroll)

    return fused


class StandardUpdater:
    """Drives ``iterator → converter → jitted sharded step``.

    Args:
      iterator: yields local batches (list of examples).
      optimizer: optax transformation — normally the output of
        ``create_multi_node_optimizer`` so grads get pmean'd in-step.
      loss_fn: ``loss_fn(params, *batch_arrays) -> scalar`` local-shard loss;
        with ``state`` given, ``loss_fn(params, state, *batch_arrays) ->
        (scalar, new_state)`` instead (the Chainer "links hold mutable
        state" pattern — BN running stats — made explicit and threaded
        through the step).
      params: initial pytree (will be replicated via ``comm.bcast_data``).
      comm: communicator providing mesh + axis for batch sharding.
      converter: batch → tuple of stacked host arrays.  The default
        upgrades, in the serial feed as under ``prefetch``, to a
        :class:`~chainermn_tpu.StagingConverter` ring the updater sizes
        itself (``staging_buffers_needed`` × batch bytes of host
        memory, held for the run): unfused, each batch is stacked into
        a recycled buffer that goes to ``device_put`` uncopied, and
        ``update()``'s retire loop keeps the reuse safe (see
        ``__init__``, docs/PIPELINE.md).  An explicit converter is kept
        as given; a ``StagingConverter`` smaller than that is refused.
      state: optional non-trainable model state pytree.  Must come out of
        ``loss_fn`` cross-replica reduced (e.g. sync-BN ``pmean``'d
        statistics) so it stays replicated.
      steps_per_execution: fuse this many steps into one XLA call via
        :func:`fuse_steps` — ``update()`` pulls that many batches,
        stacks them, and runs the whole window on device, amortising
        per-dispatch latency.  ``iteration`` advances by the window
        size; ``main/loss`` reports the window mean.
      prefetch: overlap host assembly with device compute — wrap the
        iterator in a :class:`~chainermn_tpu.PrefetchIterator` of this
        slot depth (``True`` → depth 2), whose background worker pulls,
        converts, stacks AND ``device_put``s the next window while the
        current one computes.  ``self.iterator`` becomes the prefetcher
        (its ``state_dict`` drains in-flight slots, so checkpointing is
        unchanged).  0/False (default) keeps the serial feed.  See
        ``utils.comm_model.choose_prefetch_depth`` and
        ``docs/PIPELINE.md``.
      max_inflight: dispatched-but-unretired step-window cap.  Each
        ``update()`` dispatches without blocking, then retires the
        OLDEST outstanding window(s) until at most this many remain —
        donation recycles the carry buffers, so memory stays bounded
        while dispatch runs ahead of the device.  Defaults to 2 with
        ``prefetch`` (one computing + one dispatched behind it), else 1
        (each update waits for its predecessor — the natural async-
        dispatch overlap, now measured instead of destroyed).  Fixed at
        construction: the serial feed's staging ring is sized from it.
      accum_steps: microbatched gradient accumulation with a
        window-fused exchange.  Each optimiser update consumes
        ``accum_steps`` microbatches inside ONE jitted donated-carry
        scan: every microbatch runs forward/backward on its *local*
        shard only (no per-microbatch cross-replica traffic — the mean
        moves OUT of the differentiated loss), local gradients
        accumulate in ``accum_dtype``, and the single window-end
        exchange happens inside the multi-node optimiser —
        ``cross_replica_mean``'s fused bucketed all-reduce (bf16 wire /
        hierarchical 2-stage exactly as configured there), or ZeRO-1's
        reduce-scatter/all-gather pair — so collective launches and
        wire bytes drop by ``accum_steps``× while the effective global
        batch grows by the same factor under fixed HBM.
        Correctness-equivalent to a single ``accum_steps``×-larger
        batch (equal-sized microbatches; mean of means).  The optimizer
        MUST be a multi-node one (``create_multi_node_optimizer``): in
        this mode its reducer is the ONLY gradient exchange, not a
        safety net.  ``iteration`` keeps counting microbatches (epoch
        arithmetic is the iterator's), so triggers fire on data
        consumed; parameters move once per ``accum_steps`` iterations.
        Composes multiplicatively with ``steps_per_execution``: one
        dispatch carries ``steps_per_execution × accum_steps``
        microbatches (``steps_per_execution`` optimiser updates).  A
        stateful ``loss_fn`` still updates (and, per its contract,
        cross-replica reduces) model state every microbatch.
        ``utils.comm_model.choose_accum_steps`` picks a principled M;
        ``utils.comm_model.assert_accum_collectives`` proves the M→1
        collective count from the compiled HLO.  See docs/PIPELINE.md.
        With a backward-overlapped optimizer
        (``create_multi_node_optimizer(overlap=...)``) the window-final
        microbatch is peeled out of the scan so the per-bucket exchange
        streams UNDER its backward pass
        (``assert_overlap_collectives`` is the proof; the peel reorders
        no accumulation arithmetic, and the overlap path composes
        bitwise with ``prefetch``/``steps_per_execution``).
      accum_dtype: gradient accumulator dtype (default float32 — wider
        than bf16 params so M summed microbatch grads don't lose
        mantissa).  The accumulated mean is cast back to each param
        leaf's dtype before the exchange, so the wire format is
        unchanged.
      exchange_probe_every: every this-many ``update()`` calls, re-time
        the optimizer's tuned exchange program in isolation (one extra
        exchange on a zeros grad tree, compiled once) and observe the
        wall time as ``main/exchange_time`` (span
        ``step/exchange_probe``) — the window-end exchange cost the
        in-step fusion otherwise hides.  The observation also feeds the
        plan's drift guard (``plan_cell.observe``): when it departs
        from the plan's tuned time by the cell's ``drift_factor``,
        ``plan_cell.drifted`` flips and the owner may
        ``plan_cell.retune`` (see ``docs/TUNING.md``).  Requires a
        planned optimizer (``create_multi_node_optimizer(plan=...)``);
        0 (default) disables the probe.

    Timing observations (flight-recorder spans in parentheses; the
    spans are the per-event record, see docs/OBSERVABILITY.md):
    ``main/host_time`` (``step/host``, and under it ``feed/pull``,
    ``feed/convert``, ``feed/put``) is iterator pull + convert + stack
    + ``device_put`` — for a prefetched feed, the residual wait for the
    next ready window; ``main/device_time`` (``step/retire``) is the
    exposed wait retiring windows past ``max_inflight``, i.e. blocking
    on the PREVIOUS window's result so steady-state timing stays
    overlapped; ``main/step_time`` is their per-iteration sum (the old
    value timed only the async dispatch call — it measured neither).

    ZeRO-1 optimizers (``create_multi_node_optimizer(..., zero1=True)``)
    are detected from the transformation's type: their state is
    initialised per-shard via ``zero1_init`` and carried WORLD-STACKED
    (leading axis = mesh member) across steps, sharded over the data
    axis instead of replicated.
    """

    def __init__(
        self,
        iterator,
        optimizer: optax.GradientTransformation,
        loss_fn: Callable,
        params,
        comm,
        converter: Callable = default_converter,
        drop_remainder: bool = True,
        state=None,
        steps_per_execution: int = 1,
        prefetch: int = 0,
        max_inflight: Optional[int] = None,
        accum_steps: int = 1,
        accum_dtype=None,
        exchange_probe_every: int = 0,
    ):
        self.optimizer = optimizer
        self.comm = comm
        self.converter = converter
        self.loss_fn = loss_fn
        self.drop_remainder = drop_remainder
        if steps_per_execution < 1:
            raise ValueError("steps_per_execution must be >= 1")
        self.steps_per_execution = steps_per_execution
        if accum_steps < 1:
            raise ValueError("accum_steps must be >= 1")
        self.accum_steps = accum_steps
        self.accum_dtype = jnp.dtype(
            accum_dtype if accum_dtype is not None else jnp.float32)
        # one dispatch = steps_per_execution optimizer updates, each
        # consuming accum_steps microbatches: the window the feed
        # (serial or prefetched) assembles and stacks
        self.window_steps = steps_per_execution * accum_steps

        self.prefetch = 2 if prefetch is True else int(prefetch or 0)
        if self.prefetch < 0:
            raise ValueError("prefetch depth must be >= 0")
        if isinstance(iterator, PrefetchIterator) and not self.prefetch:
            # a pre-built prefetcher implies prefetch mode — adopting it
            # beats the opaque crash of feeding DeviceWindows to the
            # serial converter path
            self.prefetch = iterator.depth
        if max_inflight is None:
            max_inflight = 2 if self.prefetch else 1
        if max_inflight < 1:
            raise ValueError("max_inflight must be >= 1")
        self.max_inflight = max_inflight
        # The serial feed vouches for the converter's staging ring
        # (put_window's caller_retires) where one update() fills one
        # ring buffer, i.e. window_steps == 1: the buffer of update u
        # is next written in update u + n, and by the end of update
        # u + n - 1 the retire loop below has blocked on every window
        # older than the newest max_inflight, so n >= max_inflight + 1
        # buffers mean window u has run its step — its batch was read
        # to the end, copied late (TPU) or aliased (CPU backend) —
        # before its buffer is rewritten.  A fused window's own
        # np.stack is its copy; there the ring only has to hold the
        # unstacked window (window_steps + 1) and put_window keeps
        # copying the rare lone batch of an epoch's end.  The prefetch
        # worker cannot see retirement and never vouches.
        self._feed_retires = not self.prefetch and self.window_steps == 1
        if not self.prefetch and converter is default_converter:
            # as under prefetch=: the default converter upgrades to a
            # staging ring (sized by the rule above, not a user's
            # knob); an explicit converter is kept as given
            self.converter = converter = StagingConverter(
                n_buffers=self.staging_buffers_needed)
        needed = self.staging_buffers_needed
        if isinstance(converter, StagingConverter) and \
                converter._n_buffers < needed:
            why = (f"max_inflight + 1 with max_inflight={max_inflight}: "
                   f"the serial feed hands its buffers to device_put "
                   f"uncopied and recycles one only after its window "
                   f"has retired") if self._feed_retires else (
                   f"window + 1 to hold a steps_per_execution × "
                   f"accum_steps = {self.window_steps} window unstacked")
            raise ValueError(
                f"StagingConverter(n_buffers={converter._n_buffers}) is "
                f"too small: needs >= {needed} buffers ({why})")
        self._inflight: collections.deque = collections.deque()
        # the iteration each in-flight window was dispatched at, for the
        # step/retire span's ``retired``
        self._inflight_steps: collections.deque = collections.deque()
        if self.prefetch:
            if isinstance(iterator, PrefetchIterator):
                # a pre-built prefetcher must agree with this updater's
                # window contract, or training silently runs a different
                # schedule than the constructor arguments claim
                if iterator._n_steps != self.window_steps:
                    raise ValueError(
                        f"PrefetchIterator was built with steps_per_"
                        f"execution={iterator._n_steps}, updater wants "
                        f"a {self.window_steps}-deep window "
                        f"(steps_per_execution × accum_steps)")
                if iterator._drop_remainder != drop_remainder:
                    raise ValueError(
                        "PrefetchIterator and updater disagree on "
                        "drop_remainder")
                self.prefetch = iterator.depth
                self.iterator = iterator
            else:
                self.iterator = PrefetchIterator(
                    iterator, comm,
                    # the default converter upgrades to a StagingConverter
                    # sized for the ring; an explicit converter is kept
                    converter=(None if converter is default_converter
                               else converter),
                    steps_per_execution=self.window_steps,
                    depth=self.prefetch,
                    drop_remainder=drop_remainder)
        else:
            self.iterator = iterator

        # first-update weight broadcast of the reference, done at init
        self.params = comm.bcast_data(params)
        self.state = None if state is None else comm.bcast_data(state)
        from .optimizers import (
            Zero1Transformation,
            Zero2Transformation,
            zero1_init,
        )

        # sharding mode from the transformation TYPE (never a repeated
        # flag): ZeRO-2 carries its state exactly like ZeRO-1 (world-
        # stacked 1/N shards — zero1_init and the P(ax) opt spec apply
        # verbatim), so self.zero1 stays the "world-stacked ZeRO carry"
        # switch for both
        self.sharding = (
            "zero2" if isinstance(optimizer, Zero2Transformation)
            else "zero1" if isinstance(optimizer, Zero1Transformation)
            else None)
        self.zero1 = self.sharding in ("zero1", "zero2")
        if self.zero1:
            self.opt_state = zero1_init(
                optimizer, self.params, comm.mesh, comm.axis_name)
        else:
            self.opt_state = optimizer.init(self.params)

        if exchange_probe_every < 0:
            raise ValueError("exchange_probe_every must be >= 0")
        if exchange_probe_every and \
                getattr(optimizer, "plan_cell", None) is None:
            raise ValueError(
                "exchange_probe_every needs a planned optimizer "
                "(create_multi_node_optimizer(plan=...)): the probe "
                "re-times the tuned exchange program, and the "
                "observation feeds its drift guard")
        self.exchange_probe_every = exchange_probe_every
        self._exchange_probe = None     # (plan, warmed fn, data factory)
        self._updates_done = 0
        # plan-cell generation this updater's compiled steps were built
        # against; update() compares and invalidates on change, so a
        # drift retune (or restored snapshot) can never leave training
        # silently running the old exchange program
        cell = getattr(optimizer, "plan_cell", None)
        self._plan_generation = None if cell is None else cell.generation

        self.iteration = 0
        self.epoch_detail = 0.0
        self.previous_epoch_detail = 0.0
        self.observation = {}
        self._last_retired = None

        self._step_cache = {}
        self._batch_sharding = NamedSharding(comm.mesh, P(comm.axis_name))
        # fused windows: leading n_steps axis is scanned, axis 1 sharded
        self._stacked_sharding = NamedSharding(
            comm.mesh, P(None, comm.axis_name))

    def _get_step(self, n_batch_args: int, n_steps: int = 1,
                  accum: int = 1):
        """Jitted SPMD step, built per batch arity (x,) vs (x, y) vs ...,
        per fused window size ``n_steps`` (see ``steps_per_execution``)
        and per accumulation depth ``accum`` (see ``accum_steps``; batch
        arrays then carry a leading ``n_steps * accum`` axis)."""
        key = (n_batch_args, n_steps, accum)
        if key in self._step_cache:
            return self._step_cache[key]
        ax = self.comm.axis_name
        optimizer, loss_fn = self.optimizer, self.loss_fn

        stateful = self.state is not None
        zero1 = self.zero1
        accum_dtype = self.accum_dtype
        # Backward-overlapped exchange (plan strategy "overlap", or a
        # zero1 transformation built with overlap=True): the window-
        # final microbatch is PEELED out of the accumulation scan.  A
        # scan is one opaque while op — every gradient leaf becomes
        # available only when the whole loop retires, so an exchange
        # after it cannot start under any backward.  With the last
        # microbatch unrolled in the outer program, each exchange
        # bucket depends only on its own (accumulated + final) leaves
        # and the scheduler streams the bucket collectives under the
        # final backward (assert_overlap_collectives proves it).  The
        # peel re-orders no float math — the same M microbatch grads
        # accumulate in the same order; only the exchange lowering
        # differs from the window-end path (wire tolerance documented
        # on cross_replica_mean).
        # The step cache key need not carry this flag: a plan change
        # bumps the cell generation and update() clears the cache.
        plan = getattr(getattr(optimizer, "plan_cell", None), "plan",
                       None)
        overlap_peel = accum > 1 and (
            getattr(plan, "strategy", None) == "overlap"
            or getattr(optimizer, "overlap", False))
        from chainermn_tpu.parallel._compat import pcast as _pcast

        def step(carry, *batch):
            params, state, opt_state = carry
            if zero1:
                # world-stacked ZeRO state: this member's shard arrives
                # with a leading length-1 member axis — peel it for the
                # update, restack for the carry (zero1_init convention)
                opt_state = jax.tree.map(lambda s: s[0], opt_state)

            if accum == 1:
                def global_loss(p):
                    # pmean INSIDE the differentiated function: the
                    # reported loss is the global mean, and shard_map's
                    # AD psums the cotangents of the replicated params
                    # so grads leave as the global mean too; the
                    # multi-node optimizer's idempotent
                    # cross_replica_mean / ZeRO reduce-scatter settles
                    # the exchange either way (this is where ChainerMN's
                    # multi_node_mean_grad went).
                    if stateful:
                        loss, new_model_state = loss_fn(p, state, *batch)
                        return jax.lax.pmean(loss, ax), new_model_state
                    return jax.lax.pmean(loss_fn(p, *batch), ax), state

                (loss, new_model_state), grads = jax.value_and_grad(
                    global_loss, has_aux=True)(params)
            else:
                # Microbatch accumulation: a donated-carry scan of LOCAL
                # forward/backward passes — no collective of any kind
                # inside the loop body (assert_accum_collectives pins
                # this on the compiled HLO).  Differentiating the raw
                # local loss (the mean moved OUT of the differentiated
                # function) keeps cotangents device-local; the pcast
                # makes that explicit by differentiating w.r.t. the
                # varying retype of params.
                p_local = jax.tree.map(
                    lambda x: _pcast(x, ax, to="varying"), params)

                def micro(mcarry, mb):
                    acc, st = mcarry

                    def local_loss(p):
                        if stateful:
                            loss, new_st = loss_fn(p, st, *mb)
                            return loss, new_st
                        return loss_fn(p, *mb), st

                    (mloss, new_st), g = jax.value_and_grad(
                        local_loss, has_aux=True)(p_local)
                    # accumulate in accum_dtype (fp32 default): M summed
                    # bf16 microbatch grads would lose low-order bits
                    acc = jax.tree.map(
                        lambda a, gg: a + gg.astype(a.dtype), acc, g)
                    return (acc, new_st), mloss

                acc0 = jax.tree.map(
                    lambda p: _pcast(jnp.zeros(p.shape, accum_dtype),
                                     ax, to="varying"), params)
                if overlap_peel:
                    # scan the first M-1 microbatches, unroll the final
                    # one: its backward lands in the OUTER program,
                    # where the optimizer's per-bucket exchange can
                    # start while earlier layers' grads are still being
                    # produced (see the overlap_peel note above)
                    (acc, mid_state), micro_losses = jax.lax.scan(
                        micro, (acc0, state),
                        tuple(b[:-1] for b in batch))
                    (acc, new_model_state), last_loss = micro(
                        (acc, mid_state), tuple(b[-1] for b in batch))
                    micro_losses = jnp.concatenate(
                        [micro_losses, last_loss[None]])
                else:
                    (acc, new_model_state), micro_losses = jax.lax.scan(
                        micro, (acc0, state), batch)
                # local mean over the window, cast back to wire dtype;
                # STILL device-local — the optimizer's reducer performs
                # the single window-end cross-replica mean (fused
                # buckets / bf16 wire / hierarchical 2-stage for
                # cross_replica_mean, reduce-scatter for ZeRO-1)
                grads = jax.tree.map(
                    lambda a, p: (a / accum).astype(p.dtype), acc, params)
                # one scalar pmean per WINDOW for the reported loss (4
                # wire bytes — the `extra` assert_accum_collectives
                # allows); sits after the scan, never inside it
                loss = jax.lax.pmean(jnp.mean(micro_losses), ax)
            with device_scope("step/optimizer"):
                updates, new_state = optimizer.update(
                    grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
            if zero1:
                new_state = jax.tree.map(lambda s: s[None], new_state)
            # loss is already the global mean (ObservationAggregator
            # semantics for the train loss come for free inside the step)
            return (new_params, new_model_state, new_state), loss

        fused = step if n_steps == 1 else fuse_steps(
            step, n_steps, scan_batches=True)
        if accum > 1 and n_steps > 1:
            # the feed stacks a flat (n_steps * accum)-deep window; the
            # outer fused-step scan consumes one accum-deep microbatch
            # block per optimiser update
            inner = fused

            def fused(carry, *batch):  # noqa: F811 — deliberate re-wrap
                return inner(carry, *(
                    b.reshape((n_steps, accum) + b.shape[1:])
                    for b in batch))

        window = n_steps * accum
        # batch specs: the window's leading scan axis is a scan axis,
        # not a sharded one — only the per-example axis splits.
        # ZeRO-1 state is world-stacked: its leading member axis shards
        # over the data axis (each member holds its own 1/N slice).
        opt_spec = P(ax) if self.zero1 else P()
        # the program ledger's cache-miss hook rides every step
        # program: the steady window, the accum-group/single-step tail
        # programs, and each distinct ragged tail shape record their
        # compiles (and signature diffs) under ONE label — exactly the
        # per-shape attribution the epoch-tail recompile story needs
        fn = ledger_jit(
            jax.shard_map(
                fused,
                mesh=self.comm.mesh,
                in_specs=((P(), P(), opt_spec),) + (P(*(
                    (None, ax) if window > 1 else (ax,))),) * n_batch_args,
                out_specs=((P(), P(), opt_spec), P()),
            ),
            label="train/step",
            donate_argnums=(0,),
        )
        self._step_cache[key] = fn
        return fn

    @property
    def epoch(self) -> int:
        return getattr(self.iterator, "epoch", 0)

    def status(self) -> dict:
        """The training-progress block for a ``/statusz`` surface
        (``StatuszServer.add_section("train", updater)``): where the
        loop is — iteration/epoch, the world it runs over, and how
        much work is in flight — read-only and cheap enough to serve
        per scrape."""
        return {
            "iteration": int(self.iteration),
            "epoch": int(self.epoch),
            "world_size": int(getattr(self.comm, "inter_size", 1)),
            "steps_per_execution": int(self.steps_per_execution),
            "inflight_windows": len(self._inflight),
            "zero1": bool(self.zero1),
            "sharding": self.sharding,
        }

    def mark_steady(self) -> None:
        """Declare the training step programs steady-state in the
        program ledger (call after step 1 has compiled the steady
        window): any further ``train/`` compile — a shape leak in the
        feed, a plan-change recompile outside a declared retune —
        counts as ``compile/steady_retraces`` and feeds the
        retrace-storm alert.  Epoch tails are part of steady training
        only if their shapes repeat; the first epoch's tail compiles
        BEFORE marking if tails are expected (run one full epoch
        first, or accept the one attributed event)."""
        get_ledger().mark_steady("train/")

    def register_memory(self, accountant=None,
                        prefix: str = "train") -> None:
        """Register the training state's device-buffer roots with the
        memory accountant: ``<prefix>_params``, ``<prefix>_opt_state``
        (the full or ZeRO-sharded optimizer state), ``<prefix>_state``
        (model state, when carried).  Weakref-held
        (``programs.weakref_root``) — registration never pins a
        retired updater; dead roots sample as 0."""
        acc = accountant if accountant is not None else get_accountant()
        acc.register(f"{prefix}_params", weakref_root(self, "params"))
        acc.register(f"{prefix}_opt_state",
                     weakref_root(self, "opt_state"))
        if self.state is not None:
            acc.register(f"{prefix}_state", weakref_root(self, "state"))

    def rebind_world(self, comm, optimizer) -> None:
        """Re-bind this updater to a NEW communicator/mesh mid-run — the
        live-resize half of ``training/elastic.py`` (the
        ``ResizeController`` calls this at the paused step boundary,
        after re-laying the train state for the new world).

        Everything derived from the old mesh is rebuilt or dropped: the
        compiled step cache (its programs baked the old mesh), the batch
        shardings, the exchange-probe program, and the plan-generation
        watermark (the fresh optimizer re-tunes for the new topology).
        A prefetching feed is closed — returning its unconsumed
        lookahead to the base iterator — and re-wrapped over the new
        communicator, so the data position is exactly where a
        save/restart at this boundary would resume.  The caller owes:
        draining in-flight windows FIRST (the old mesh's buffers must
        retire before the world changes) and installing the re-laid
        ``params`` / ``opt_state`` / ``state`` afterwards."""
        from .optimizers import Zero1Transformation, Zero2Transformation

        if isinstance(self.iterator, PrefetchIterator):
            base = self.iterator._base
            depth = self.iterator.depth
            # the prefetcher's RESOLVED converter, not the updater's: a
            # pre-built feed may carry its own (e.g. a custom
            # StagingConverter) while self.converter sits at the
            # default — rebuilding with the wrong one would convert
            # post-resize batches differently and break trajectory
            # equivalence.  Reuse is safe: in-flight windows are
            # drained by the caller and close() joins the worker.
            conv = self.iterator._converter
            self.iterator.close()
            self.iterator = PrefetchIterator(
                base, comm,
                converter=conv,
                steps_per_execution=self.window_steps,
                depth=depth,
                drop_remainder=self.drop_remainder)
        self.comm = comm
        self.optimizer = optimizer
        was_sharding = self.sharding
        self.sharding = (
            "zero2" if isinstance(optimizer, Zero2Transformation)
            else "zero1" if isinstance(optimizer, Zero1Transformation)
            else None)
        self.zero1 = self.sharding in ("zero1", "zero2")
        if self.sharding != was_sharding:
            raise ValueError(
                f"rebind_world cannot switch sharding mode mid-run "
                f"({was_sharding!r} -> {self.sharding!r}): the carried "
                f"optimizer state's layout would not match the new "
                f"transformation")
        cell = getattr(optimizer, "plan_cell", None)
        if self.exchange_probe_every and cell is None:
            raise ValueError(
                "rebind_world: exchange_probe_every is set but the new "
                "optimizer is not a planned one "
                "(create_multi_node_optimizer(plan=...))")
        self._plan_generation = None if cell is None else cell.generation
        self._exchange_probe = None
        self._step_cache = {}
        # the rebuilt step programs are NEW executables: drop the
        # program ledger's train/ signature memory (and any steady
        # declaration) so the post-resize recompile is re-recorded —
        # even when the new world returns to a previously-seen shape
        get_ledger().forget("train/")
        self._inflight.clear()
        self._inflight_steps.clear()
        self._batch_sharding = NamedSharding(comm.mesh, P(comm.axis_name))
        self._stacked_sharding = NamedSharding(
            comm.mesh, P(None, comm.axis_name))

    def finalize(self):
        """Release the feed: joins a prefetching iterator's worker and
        returns its unconsumed lookahead to the base iterator.  The
        trainer calls this when ``run()`` exits; safe to call more than
        once, and the feed restarts transparently if training resumes.
        Only the updater-owned prefetch wrap is closed — a user-supplied
        iterator's own ``close`` (a file handle, a stream) is not the
        updater's to call."""
        if isinstance(self.iterator, PrefetchIterator):
            self.iterator.close()

    @property
    def staging_buffers_needed(self) -> int:
        """Ring size a :class:`StagingConverter` needs under this
        updater: ``max_inflight + 1`` where the serial feed hands its
        buffers to ``device_put`` uncopied (``window_steps == 1``; see
        ``__init__``), else ``window_steps + 1`` for the unstacked
        window."""
        return (self.max_inflight if self._feed_retires
                else self.window_steps) + 1

    def _next_arrays(self):
        """Pull one batch, convert, apply the divisibility policy."""
        return pull_batch(self.iterator, self.converter, self.comm.size,
                          self.drop_remainder)

    def _assemble_host_window(self):
        """The serial feed: pull, convert, stack and ``device_put`` the
        next fused window on the calling thread, via the SAME
        ``assemble_window``/``put_window`` helpers the prefetch worker
        runs — one window contract, so the prefetch-on/off bitwise
        parity cannot drift.  Returns ``(arrays, k, tail)`` in exactly
        the layout :class:`PrefetchIterator` delivers ready-made.

        With a :class:`StagingConverter` (the default converter's
        upgrade) and ``window_steps == 1`` the batch is stacked into a
        ring of ``max_inflight + 1`` host buffers the feed keeps for
        the run (that many × batch bytes, instead of one transient
        batch) and goes to ``device_put`` as it is.  ``update()``'s
        retire loop is what makes the reuse safe (``__init__``); a
        caller that drives this method itself and keeps the returned
        arrays across more than ``max_inflight`` further calls sees
        them rewritten where the backend aliases host memory (the
        CPU's)."""
        window, pending = assemble_window(
            self._next_arrays, self.window_steps)
        return put_window(window, pending, self._batch_sharding,
                          self._stacked_sharding, converter=self.converter,
                          source=self.iterator,
                          caller_retires=self._feed_retires)

    def _dispatch_window(self, carry, arrays, k):
        """Run a ``k``-microbatch window through CACHED programs only.

        The steady window (``k == window_steps``) runs the one fused/
        accumulating executable.  A shorter tail-of-epoch window is
        FLUSHED through the ``n_steps=1`` programs instead — full
        ``accum_steps`` groups through the single-update accumulating
        program, leftovers as plain single steps — so a partial window
        never compiles a one-off ``(k, ...)`` shape (the first epoch
        end used to pay a fresh steady-state-sized XLA compile for a
        shape that recurs at most once per epoch).  Returns
        ``(carry, losses, weights, n_updates)`` — ``weights`` holds the
        microbatch count behind each loss element, so the observed
        window loss can stay an unbiased per-microbatch mean when
        M-deep window means mix with single-step losses.
        """
        M, n_args = self.accum_steps, len(arrays)
        if k == self.window_steps and k > 1:
            carry, loss = self._get_step(
                n_args, self.steps_per_execution, M)(carry, *arrays)
            return (carry, [jnp.atleast_1d(loss)],
                    [M] * self.steps_per_execution,
                    self.steps_per_execution)
        if k == 1:
            # put_window delivers a lone microbatch unstacked
            carry, loss = self._get_step(n_args, 1, 1)(carry, *arrays)
            return carry, [jnp.atleast_1d(loss)], [1], 1
        losses, weights, n_updates = [], [], 0
        q = k // M if M > 1 else 0
        for i in range(q):
            seg = tuple(a[i * M:(i + 1) * M] for a in arrays)
            carry, loss = self._get_step(n_args, 1, M)(carry, *seg)
            losses.append(jnp.atleast_1d(loss))
            weights.append(M)
            n_updates += 1
        for j in range(q * M, k):
            # leftover microbatches (including the whole window when
            # accum is off) run as plain single steps: each is a full
            # optimizer update, exactly what an unfused updater would do
            seg = tuple(a[j] for a in arrays)
            carry, loss = self._get_step(n_args, 1, 1)(carry, *seg)
            losses.append(jnp.atleast_1d(loss))
            weights.append(1)
            n_updates += 1
        return carry, losses, weights, n_updates

    def _probe_exchange_time(self) -> float:
        """Time one isolated execution of the tuned exchange program on
        a zeros grad tree — the ``main/exchange_time`` observation.
        The program is built (and warmed) once per plan; a plan change
        (drift re-tune, snapshot restore) rebuilds it."""
        from chainermn_tpu.utils import autotune as _autotune

        cell = self.optimizer.plan_cell
        plan = cell.plan
        if plan is None:
            raise RuntimeError(
                "exchange probe with an unresolved plan — init ran?")
        if self._exchange_probe is None \
                or self._exchange_probe[0] is not plan:
            fn, make_data = _autotune.build_plan_probe(
                self.comm, plan, self.params)
            self._exchange_probe = (plan, fn, make_data)
        _, fn, make_data = self._exchange_probe
        # the probe tree is rebuilt per probe (and dropped after), so
        # no gradient-tree-sized buffer stays pinned between probes
        data = make_data()
        # drain in-flight training windows BEFORE the timer starts: the
        # probe must measure the exchange in isolation, not the queued
        # windows it would otherwise sit behind (a spuriously inflated
        # observation would trip the drift guard every probe).  Blocks
        # without popping, so the retire bookkeeping is untouched.
        for pending in self._inflight:
            jax.block_until_ready(pending)
        t0 = time.perf_counter()
        jax.block_until_ready(fn(data))
        dt = time.perf_counter() - t0
        cell.observe(dt)
        return dt

    def update(self):
        # -- plan-change barrier: recompile steps that baked in a now-
        # replaced exchange plan (drift retune / snapshot restore) ---- #
        cell = getattr(self.optimizer, "plan_cell", None)
        if cell is not None and cell.generation != self._plan_generation:
            self._step_cache.clear()
            self._plan_generation = cell.generation
            get_recorder().instant("step/plan_change", cat="step",
                                   step=self.iteration,
                                   generation=cell.generation)
        tracer = get_recorder()

        # -- host phase: obtain the next device-resident window -------- #
        t0 = time.perf_counter()
        with tracer.span("step/host", cat="step", step=self.iteration,
                         prefetch=bool(self.prefetch)):
            if self.prefetch:
                rec = next(self.iterator)   # DeviceWindow, pre-transferred
                arrays, k, tail = rec.arrays, rec.k, rec.tail
            else:
                arrays, k, tail = self._assemble_host_window()
        host_time = time.perf_counter() - t0

        # -- dispatch (non-blocking under JAX async dispatch) ----------- #
        # the accumulation window IS the dispatch when accum is on — the
        # span name keeps the two regimes distinguishable in the trace
        dispatch_span = ("step/accum_window" if self.accum_steps > 1
                         else "step/dispatch")
        carry = (self.params, self.state, self.opt_state)
        with tracer.span(dispatch_span, cat="step", step=self.iteration,
                         k=k, accum_steps=self.accum_steps):
            carry, losses, weights, n_updates = self._dispatch_window(
                carry, arrays, k)
        n_iters = k
        if tail is not None:
            # Ragged tail batch runs as a plain single step.  Its batch
            # shape differs from the steady-state one, so jit compiles
            # ONE extra executable the first time each distinct tail
            # shape appears (then cached) — a deliberate trade: padding
            # the tail instead would need a mask threaded through every
            # user loss_fn.  Only non-repeating epoch ends produce
            # ragged tails; steady training never pays this.
            carry, tail_loss = self._get_step(len(tail), 1)(carry, *tail)
            losses.append(jnp.atleast_1d(tail_loss))
            weights.append(1)
            n_iters += 1
            n_updates += 1
        loss = losses[0] if len(losses) == 1 else jnp.concatenate(losses)
        if loss.size == 1 or len(set(weights)) == 1:
            # equal weights (the steady state): plain mean is unbiased
            window_loss = jnp.mean(loss)
        else:
            # mixed M-deep window means and single-step losses (epoch
            # tails under accumulation): weight each element by the
            # microbatches behind it so the reported loss stays the
            # per-microbatch mean the unfused path would log
            w = jnp.asarray(weights, loss.dtype)
            window_loss = jnp.dot(loss, w) / w.sum()
        self.params, self.state, self.opt_state = carry

        # -- retire: block on the oldest window(s) past max_inflight ---- #
        # (the PREVIOUS window in steady state — never the one just
        # dispatched — so the measured device wait is the exposed cost,
        # not the full step latency, and the pipeline stays overlapped;
        # donated carries bound memory to max_inflight windows)
        # the weighted window loss derives from every dispatched
        # program's output, so blocking on it retires the whole window
        self._inflight.append(window_loss)
        self._inflight_steps.append(self.iteration)
        t0 = time.perf_counter()
        with tracer.span("step/retire", cat="step", step=self.iteration,
                         inflight=len(self._inflight)) as retire_span:
            retired_step = None
            while len(self._inflight) > self.max_inflight:
                retired = self._inflight.popleft()
                retired_step = self._inflight_steps.popleft()
                jax.block_until_ready(retired)
                self._last_retired = retired
            # the iteration whose window this span learned had ended on
            # the device (None: it blocked on nothing) -- one of the
            # moments that tie this clock to a device trace's
            retire_span.set(retired=retired_step)
        device_time = time.perf_counter() - t0

        self.iteration += n_iters
        self.previous_epoch_detail = self.epoch_detail
        self.epoch_detail = getattr(
            self.iterator, "epoch_detail", self.iteration)
        if self.max_inflight > 1 and self._last_retired is not None:
            # pipelined: report the RETIRED window's loss (already
            # materialised), so a consumer that calls float() on it
            # every iteration (FailOnNonNumber, ObservationAggregator,
            # a user's own hook) never stalls the pipeline on the
            # in-flight window.  Lags by max_inflight updates.
            obs_loss = self._last_retired
        else:
            # one window in flight: the loss of the window just
            # dispatched, still being computed.  Reading it waits out
            # the step; LogReport.observe keeps it until it is ready
            # (is_ready()) and so may look every iteration for nothing.
            obs_loss = window_loss
        self.observation = {
            "main/loss": obs_loss,
            "main/host_time": host_time / n_iters,
            "main/device_time": device_time / n_iters,
            "main/step_time": (host_time + device_time) / n_iters,
        }
        # the step-time DISTRIBUTION (not just this tick's value): the
        # metrics registry's lattice histogram feeds p50/p99 step-time
        # SLOs and the Prometheus exposition; no-op while disabled
        reg = get_registry()
        reg.observe("train/step_time", (host_time + device_time) / n_iters)
        reg.inc("train/iterations", n_iters)
        if self.accum_steps > 1:
            # wall time per OPTIMIZER update (the window), vs step_time's
            # per-microbatch denominator — the pair makes the
            # amortisation visible (accum_time ≈ accum_steps × step_time
            # means the exchange really left the microbatch loop)
            accum_time = (host_time + device_time) / max(n_updates, 1)
            self.observation["main/accum_time"] = accum_time
        self._updates_done += 1
        if self.exchange_probe_every and \
                self._updates_done % self.exchange_probe_every == 0:
            # span covers drain + isolated run; the isolated measurement
            # itself rides the metadata
            with tracer.span("step/exchange_probe", cat="step",
                             step=self.iteration) as probe_span:
                exchange_time = self._probe_exchange_time()
                probe_span.set(exchange_s=round(exchange_time, 6))
            self.observation["main/exchange_time"] = exchange_time
