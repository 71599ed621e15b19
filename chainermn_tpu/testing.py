"""Testing helpers — the distributed-test tooling the reference made its
users assemble by hand (SURVEY §4: ChainerMN tests ran under a real
``mpiexec -n 2`` and simply skipped when the world was too small; there
was no fake cluster).  JAX can fake both halves, and this module
packages the two tricks this repo's own suite runs on:

- :func:`ensure_virtual_pod` — an N-device virtual CPU "pod" in ONE
  process (every collective/sharding/pipeline schedule runs for real);
- :func:`run_multiprocess` — real multi-process JAX clusters on
  localhost, the TPU-native ``mpiexec -n N`` for the code paths that
  only exist across processes (object transport, checkpoint agreement,
  preemption flag reduce);
- :class:`FaultPlan` / :class:`FaultInjector` / :func:`corrupt_file` —
  the deterministic fault-injection harness: every recovery path the
  resilience layer promises (kill→resume, corrupted-latest fallback,
  watchdog stall detection, NaN abort) is exercised under an INJECTED
  fault scripted by iteration number, not by luck (docs/RESILIENCE.md).
"""

from __future__ import annotations

import dataclasses
import json
import os
import random
import signal as _signal
import socket
import subprocess
import sys
import time
from typing import Optional, Sequence

__all__ = ["FaultInjector", "FaultPlan", "corrupt_file",
           "ensure_virtual_pod", "free_port",
           "run_multiprocess"]


def ensure_virtual_pod(n_devices: int = 8) -> None:
    """Pin this process's JAX to an ``n_devices`` virtual CPU pod.

    MUST run before the first backend use (the first ``jax.devices()``
    locks the platform) — call it at the top of a test conftest or
    script entry point.  Idempotent if the pod is already configured;
    raises if the backend was already initialised differently (too late
    to change) or ends up with fewer devices.

    Both layers are set because the env var alone is too late once
    ``jax`` has been imported: ``XLA_FLAGS`` is read at backend init,
    and ``jax.config`` still decides the platform until then.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n_devices}"
        ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")
    if jax.device_count() < n_devices:
        raise RuntimeError(
            f"virtual pod has {jax.device_count()} devices, wanted "
            f"{n_devices} — ensure_virtual_pod must run before the "
            "first JAX backend use (jax.devices() locks the platform "
            "and XLA_FLAGS)")


def free_port() -> int:
    """An OS-assigned free TCP port (for the cluster coordinator)."""
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def run_multiprocess(
    worker: str,
    args: Sequence[str] = (),
    *,
    nprocs: int = 2,
    timeout: float = 180,
    pythonpath: Optional[str] = None,
):
    """Run ``worker`` (a Python file) as an ``nprocs``-process JAX CPU
    cluster on localhost — the ``mpiexec -n N`` replacement for tests.

    Each worker process receives
    ``<worker> <coordinator_addr> <nprocs> <process_id> *args`` and
    should begin with::

        import chainermn_tpu, sys
        addr, n, i = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
        chainermn_tpu.init_distributed(
            coordinator_address=addr, num_processes=n, process_id=i)
        comm = chainermn_tpu.create_communicator("tpu_xla")

    The environment is scrubbed of TPU/JAX/XLA settings and each
    worker is pinned to one CPU device (env var + a ``jax.config``
    bootstrap before the worker's code runs).
    Returns the list of captured outputs; raises ``RuntimeError`` with
    every worker's output on any non-zero exit or on timeout (the usual
    symptom of a cross-process collective deadlock).
    """
    addr = f"localhost:{free_port()}"
    env = dict(os.environ)
    for k in list(env):
        if k.startswith(("TPU_", "LIBTPU", "PJRT_", "JAX_", "XLA_")):
            env.pop(k)
    env["JAX_PLATFORMS"] = "cpu"
    if pythonpath:
        env["PYTHONPATH"] = (
            pythonpath + os.pathsep + env.get("PYTHONPATH", ""))

    bootstrap = (
        "import sys, runpy, jax; "
        "jax.config.update('jax_platforms', 'cpu'); "
        "sys.argv = sys.argv[1:]; "
        "runpy.run_path(sys.argv[0], run_name='__main__')"
    )
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", bootstrap, worker, addr, str(nprocs),
             str(i), *map(str, args)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env)
        for i in range(nprocs)
    ]
    outputs, codes = [], []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outputs.append(out)
            codes.append(p.returncode)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        for p in procs:
            out, _ = p.communicate()
            outputs.append(out)
        raise RuntimeError(
            f"multiprocess worker timed out after {timeout}s (likely a "
            "cross-process collective deadlock)\n"
            + "\n---\n".join(outputs)) from None
    if any(codes):
        raise RuntimeError(
            "multiprocess workers failed:\n" + "\n".join(
                f"--- worker {i} rc={codes[i]} ---\n{outputs[i]}"
                for i in range(nprocs)))
    return outputs


def corrupt_file(path: str, n_bytes: int = 8, offset: Optional[int] = None,
                 seed: int = 0) -> list:
    """Deterministically flip ``n_bytes`` bytes of ``path`` in place.

    The corrupt-shard fault: XORs each chosen byte with a non-zero mask
    drawn from ``random.Random(seed)``, so the damage is reproducible
    and guaranteed to change the bytes (an XOR with 0 would be a no-op
    "corruption" that CRCs rightly ignore).  With ``offset=None`` the
    positions land in the middle half of the file — inside payload data
    for an uncompressed npz, past the zip local headers — which is
    exactly the damage ``verify_state`` must catch.  Returns the list of
    flipped offsets (for assertions/logging).
    """
    size = os.path.getsize(path)
    if size == 0:
        raise ValueError(f"{path} is empty — nothing to corrupt")
    rng = random.Random(seed)
    if offset is not None:
        positions = [min(offset + i, size - 1) for i in range(n_bytes)]
    else:
        lo, hi = size // 4, max(size // 4 + 1, 3 * size // 4)
        positions = sorted(rng.randrange(lo, hi) for _ in range(n_bytes))
    with open(path, "r+b") as f:
        for pos in positions:
            f.seek(pos)
            old = f.read(1)
            f.seek(pos)
            f.write(bytes([old[0] ^ rng.randrange(1, 256)]))
    return positions


@dataclasses.dataclass
class FaultPlan:
    """A deterministic fault script, keyed by iteration number.

    Every field is a plain scalar so a plan serialises through
    :meth:`to_json` / :meth:`from_json` and can be handed to a child
    process on its command line — the kill→resume drills run the faulty
    phase in a real subprocess and compare its resumed continuation
    against an uninterrupted run bitwise.

    Faults (all optional; fire at the step boundary AFTER the named
    iteration completes, where train state is consistent):

    - ``kill_at_iteration`` — ``SIGKILL`` self: the hard crash (spot
      reclamation without notice, OOM killer).  Nothing flushes.
    - ``sigterm_at_iteration`` — ``SIGTERM`` self: the preemption
      notice; with an async checkpointer on the same tick the signal
      lands MID-write, exercising the join-on-crash path.
      ``sigterm_rank`` (default ``None`` = every rank) restricts the
      signal to ONE rank — the real preemption shape, where a single
      host gets the notice and the rest learn of it through
      ``PreemptionCheckpointer``'s collective flag OR-reduce.
    - ``corrupt_at_iteration`` + ``corrupt_path`` — flip
      ``corrupt_n_bytes`` bytes of that file (:func:`corrupt_file`).
    - ``delay_at_iteration`` + ``delay_rank`` + ``delay_seconds`` —
      stall ONE rank past a watchdog threshold.
    - ``nan_at_iteration`` — poison the updater's params with NaN so
      the NEXT step's loss is non-finite (drives ``FailOnNonNumber``).
    - ``resize_at_iteration`` + ``resize_to`` — the shrink/grow drill:
      checkpoint through the injector's ``checkpointer`` (topology
      stamped) and stop the trainer cleanly, recording that the relaunch
      should run at world size ``resize_to``.  The driving test then
      rebuilds the job on the new topology and resumes through the
      checkpointer's elastic re-layout path (docs/RESILIENCE.md
      "Elastic resume").
    - ``resize_live_at_iteration`` + ``resize_live_to`` — the LIVE
      resize drill: arm the injector's ``resize_controller``
      (``training/elastic.ResizeController``) at that iteration's step
      boundary.  The controller runs at the very end of the same tick
      (priority 0 < the injector's 1), so the world changes at exactly
      the boundary a save/restart would have used — and training
      continues in the same process.
    - ``save_stall_after_files`` + ``save_stall_seconds`` — slow the
      checkpointer's per-file write hook: after the Nth file of a set
      lands, each further file waits ``save_stall_seconds`` first.
      Composed with ``kill_at_iteration`` on an async shard-only save,
      the SIGKILL deterministically lands MID-stream, leaving a partial
      covering set — the crash-during-shard-only-save drill
      (docs/RESILIENCE.md).

    Serving faults (applied by :meth:`FaultInjector.attach_engine` to a
    ``ServingEngine``, keyed by DECODE-ROUND / staging-call count
    instead of trainer iteration; each fires once):

    - ``serve_delay_at_round`` + ``serve_delay_seconds`` — stall the
      named decode round (a slow device / preempted host): deadlines
      keep being enforced, so the drill shows timeouts and shedding,
      not a hang.
    - ``serve_raise_at_round`` — the round dispatch raises (adapter
      step failure): the engine must quarantine the newest-admitted
      row and keep the remaining slots serving.
    - ``serve_exhaust_pool_at_admit`` — before the Nth staging call,
      hoard EVERY free pool block (fragmentation / leak shape);
      admission backpressures while active slots keep decoding.  The
      hoard is released after ``serve_exhaust_pool_rounds`` further
      decode rounds (recovery half of the drill).

    Fleet faults (applied by :meth:`FaultInjector.attach_fleet` to a
    ``serving.fleet.FleetRouter``, keyed by FLEET STEP count; replicas
    are named by index):

    - ``fleet_kill_at_step`` + ``fleet_kill_replica`` — that replica's
      next heartbeat at/after the step raises (host crash): the router
      must fail over — migrate its queue, re-dispatch its active rows
      from their committed prefixes — and the drill's requests must
      all still complete exactly once, token-identical to the oracle.
    - ``fleet_slow_at_step`` + ``fleet_slow_replica`` +
      ``fleet_slow_seconds`` + ``fleet_slow_steps`` — stall that
      replica's heartbeat for N consecutive steps (a degraded host):
      drives the suspect path and, with hedging enabled, the
      hedge-wins path.
    - ``fleet_flap_at_step`` + ``fleet_flap_replica`` +
      ``fleet_flap_count`` — kill/revive the replica
      ``fleet_flap_count`` times (crash-looping host): each rejoin's
      hold must grow under the router's flap damping until the
      replica is effectively out of rotation.
    """

    kill_at_iteration: Optional[int] = None
    sigterm_at_iteration: Optional[int] = None
    sigterm_rank: Optional[int] = None
    corrupt_at_iteration: Optional[int] = None
    corrupt_path: Optional[str] = None
    corrupt_n_bytes: int = 8
    delay_at_iteration: Optional[int] = None
    delay_rank: int = 0
    delay_seconds: float = 0.0
    nan_at_iteration: Optional[int] = None
    resize_at_iteration: Optional[int] = None
    resize_to: int = 0
    resize_live_at_iteration: Optional[int] = None
    resize_live_to: int = 0
    save_stall_after_files: Optional[int] = None
    save_stall_seconds: float = 0.0
    serve_delay_at_round: Optional[int] = None
    serve_delay_seconds: float = 0.0
    serve_raise_at_round: Optional[int] = None
    serve_exhaust_pool_at_admit: Optional[int] = None
    serve_exhaust_pool_rounds: int = 4
    fleet_kill_at_step: Optional[int] = None
    fleet_kill_replica: int = 0
    fleet_slow_at_step: Optional[int] = None
    fleet_slow_replica: int = 0
    fleet_slow_seconds: float = 0.0
    fleet_slow_steps: int = 1
    fleet_flap_at_step: Optional[int] = None
    fleet_flap_replica: int = 0
    fleet_flap_count: int = 2
    seed: int = 0

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), sort_keys=True)

    @classmethod
    def from_json(cls, payload: str) -> "FaultPlan":
        return cls(**json.loads(payload))


class FaultInjector:
    """Trainer extension applying a :class:`FaultPlan`.

    LOWEST priority (runs last on its tick, after log writers and the
    checkpointer): a kill fires only once everything that tick promised
    to persist has at least STARTED persisting — which for an async
    checkpoint write means the signal really lands mid-write.
    """

    trigger = (1, "iteration")
    priority = 1

    def __init__(self, plan: FaultPlan, comm=None, checkpointer=None,
                 resize_controller=None):
        self.plan = plan
        self.comm = comm
        # the resize action saves through a real checkpointer so the
        # stopped state is topology-stamped for the elastic relaunch
        self.checkpointer = checkpointer
        # the LIVE resize action arms this controller instead of
        # stopping the trainer (training/elastic.ResizeController)
        self.resize_controller = resize_controller
        self.fired: list = []
        if checkpointer is not None \
                and plan.save_stall_after_files is not None:
            self._attach_save_stall(checkpointer)

    def _attach_save_stall(self, checkpointer) -> None:
        """Wrap the checkpointer's per-file write hook so every file
        after the plan's Nth sleeps first — pins a concurrent SIGKILL
        mid-stream (deterministic partial covering set)."""
        plan = self.plan
        real = checkpointer._write_part
        state = {"files": 0}

        def stalled(path, tree, topology, shard_part):
            if state["files"] >= plan.save_stall_after_files:
                self.fired.append(("save_stall", state["files"]))
                time.sleep(plan.save_stall_seconds)
            real(path, tree, topology, shard_part)
            state["files"] += 1

        checkpointer._write_part = stalled

    def _rank(self) -> int:
        return getattr(self.comm, "inter_rank", 0) if self.comm else 0

    def __call__(self, trainer) -> None:
        plan = self.plan
        it = trainer.updater.iteration
        if plan.nan_at_iteration == it:
            import jax
            import jax.numpy as jnp

            trainer.updater.params = jax.tree.map(
                lambda a: a * jnp.nan, trainer.updater.params)
            self.fired.append(("nan", it))
        if (plan.delay_at_iteration == it
                and self._rank() == plan.delay_rank):
            self.fired.append(("delay", it))
            time.sleep(plan.delay_seconds)
        if plan.corrupt_at_iteration == it and plan.corrupt_path:
            corrupt_file(plan.corrupt_path, plan.corrupt_n_bytes,
                         seed=plan.seed)
            self.fired.append(("corrupt", it))
        if plan.resize_live_at_iteration == it:
            if self.resize_controller is None:
                raise RuntimeError(
                    "FaultPlan.resize_live_at_iteration needs "
                    "FaultInjector(resize_controller=...) — the live "
                    "resize is performed by a ResizeController "
                    "extension on the same tick")
            self.resize_controller.request(plan.resize_live_to)
            self.fired.append(("resize_live", it, plan.resize_live_to))
        if plan.resize_at_iteration == it:
            if self.checkpointer is None:
                raise RuntimeError(
                    "FaultPlan.resize_at_iteration needs "
                    "FaultInjector(checkpointer=...) — the resize drill "
                    "must save a topology-stamped snapshot to resume "
                    "from")
            self.checkpointer.save(trainer.updater, trainer)
            self.fired.append(("resize", it, plan.resize_to))
            trainer.stop(
                f"elastic resize drill: snapshot saved at iteration "
                f"{it}; relaunch at world={plan.resize_to}")
        if plan.sigterm_at_iteration == it and (
                plan.sigterm_rank is None
                or self._rank() == plan.sigterm_rank):
            self.fired.append(("sigterm", it))
            os.kill(os.getpid(), _signal.SIGTERM)
        if plan.kill_at_iteration == it:
            # flush stdio so the phase's progress log survives the kill
            sys.stdout.flush()
            sys.stderr.flush()
            os.kill(os.getpid(), _signal.SIGKILL)

    _FAULT_HOARD = "__fault_pool_hoard__"

    def attach_engine(self, engine):
        """Apply the plan's SERVING faults to a ``ServingEngine`` by
        wrapping its decode-round dispatch and staging path (host-side
        wrappers — no recompile, no engine code knows it is under
        test).  Round-keyed faults count ROUND DISPATCHES (including
        failed ones), pool exhaustion counts STAGING calls.  Each
        fault fires once; firings append to :attr:`fired` as
        ``("serve_<kind>", count)``.  Returns the engine."""
        plan = self.plan
        # "ticks" = round dispatches + staging attempts: the release
        # countdown must advance even when the pool hoard has idled
        # every slot (no live rows -> no rounds, but each blocked
        # admit attempt still stages)
        state = {"rounds": 0, "stages": 0, "ticks": 0,
                 "hoard_until": None}
        real_round = engine._round_fn
        real_stage = engine._stage

        def maybe_release():
            if (state["hoard_until"] is not None
                    and state["ticks"] >= state["hoard_until"]):
                engine._alloc.free_row(self._FAULT_HOARD)
                state["hoard_until"] = None
                self.fired.append(("serve_pool_release", state["ticks"]))

        def round_wrapper(*args, **kwargs):
            r = state["rounds"]
            state["rounds"] += 1
            state["ticks"] += 1
            if plan.serve_delay_at_round == r:
                self.fired.append(("serve_delay", r))
                time.sleep(plan.serve_delay_seconds)
            if plan.serve_raise_at_round == r:
                self.fired.append(("serve_raise", r))
                raise RuntimeError(
                    "injected decode-round failure "
                    "(FaultPlan.serve_raise_at_round)")
            out = real_round(*args, **kwargs)
            maybe_release()
            return out

        def stage_wrapper(req, rec, steal, idle=True):
            n = state["stages"]
            state["stages"] += 1
            state["ticks"] += 1
            if (plan.serve_exhaust_pool_at_admit == n
                    and self._FAULT_HOARD not in engine._alloc.rows()):
                # cache-only prefix blocks are reclaimable on demand,
                # so a faithful exhaustion drill must hoard them too
                reclaim = getattr(engine._alloc, "reclaim", None)
                if reclaim is not None:
                    reclaim(engine._alloc.n_blocks)
                engine._alloc.alloc(self._FAULT_HOARD,
                                    engine._alloc.n_free)
                state["hoard_until"] = (
                    state["ticks"] + plan.serve_exhaust_pool_rounds)
                self.fired.append(("serve_pool_exhaust", n))
            out = real_stage(req, rec, steal, idle=idle)
            maybe_release()
            return out

        engine._round_fn = round_wrapper
        engine._stage = stage_wrapper
        return engine

    def attach_fleet(self, router):
        """Apply the plan's FLEET faults to a
        ``serving.fleet.FleetRouter`` by wrapping its per-replica
        heartbeat (``_step_replica``) and its ``step`` (host-side
        wrappers, same discipline as :meth:`attach_engine` — the
        router never knows it is under test).  Faults key on the
        router's OWN step counter, replicas on their index.  Firings
        append to :attr:`fired` as ``("fleet_<kind>", step)``.
        Returns the router."""
        plan = self.plan
        names = [h.name for h in router.replicas]

        def target(idx):
            return names[idx] if 0 <= idx < len(names) else None

        kill_name = target(plan.fleet_kill_replica)
        slow_name = target(plan.fleet_slow_replica)
        flap_name = target(plan.fleet_flap_replica)
        state = {"killed": False, "slowed": 0,
                 "flap_kills": 0, "flap_revives": 0}
        real_step_replica = router._step_replica
        real_step = router.step

        def step_replica_wrapper(h):
            step = router.step_count
            if (plan.fleet_kill_at_step is not None
                    and h.name == kill_name and not state["killed"]
                    and step >= plan.fleet_kill_at_step):
                state["killed"] = True
                self.fired.append(("fleet_kill", step))
                raise RuntimeError(
                    "injected replica crash "
                    "(FaultPlan.fleet_kill_at_step)")
            if (plan.fleet_flap_at_step is not None
                    and h.name == flap_name
                    and state["flap_kills"] < plan.fleet_flap_count
                    and step >= plan.fleet_flap_at_step):
                state["flap_kills"] += 1
                self.fired.append(("fleet_flap_kill", step))
                raise RuntimeError(
                    "injected replica flap "
                    "(FaultPlan.fleet_flap_at_step)")
            if (plan.fleet_slow_at_step is not None
                    and h.name == slow_name
                    and step >= plan.fleet_slow_at_step
                    and state["slowed"] < plan.fleet_slow_steps):
                state["slowed"] += 1
                self.fired.append(("fleet_slow", step))
                time.sleep(plan.fleet_slow_seconds)
            return real_step_replica(h)

        def step_wrapper():
            out = real_step()
            # the flap's revive half: the crash-looping host comes
            # straight back, so the ROUTER's damping (not the host's
            # absence) is what must contain it
            if (plan.fleet_flap_at_step is not None
                    and flap_name is not None
                    and state["flap_revives"] < state["flap_kills"]):
                h = router._by_name[flap_name]
                if h.state == "dead":
                    router.revive(flap_name)
                    state["flap_revives"] += 1
                    self.fired.append(
                        ("fleet_flap_revive", router.step_count))
            return out

        router._step_replica = step_replica_wrapper
        router.step = step_wrapper
        return router
