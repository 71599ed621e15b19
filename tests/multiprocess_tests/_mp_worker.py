"""Worker program for the multi-process test harness.

The reference ran its whole suite under ``mpiexec -n 2`` (SURVEY.md §4).
The TPU-native analogue: N OS processes, each with one CPU device,
joined into one JAX distributed world via
``jax.distributed.initialize`` — exercising every ``inter_size > 1``
branch (gloo collectives, the coordination-service KV object channel,
cross-process checkpoint agreement) that single-process tests cannot
reach.

Invoked by the ``mp_run`` fixture as::

    python _mp_worker.py <coordinator_addr> <num_procs> <proc_id> <scenario>

A scenario is a function ``scenario_<name>(comm)`` below; workers exit 0
on success and print tracebacks to stderr on failure.
"""

import functools
import os
import sys
import tempfile

# Pin to CPU before any jax import (see tests/conftest.py).
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


# --------------------------------------------------------------------- #
# scenarios
# --------------------------------------------------------------------- #

def scenario_topology(comm):
    """The rank-model contract (SURVEY.md §5): rank = first owned global
    device index, inter_rank = process index, intra_rank = LOCAL index."""
    assert comm.size == jax.device_count()
    assert comm.inter_size == jax.process_count()
    assert comm.inter_rank == jax.process_index()
    own = [d for d in jax.devices() if d.process_index == jax.process_index()]
    assert comm.rank == jax.devices().index(own[0])
    # intra_rank is an index into jax.local_devices(), NOT a global id —
    # with one device per process it must be 0 on EVERY process.
    assert comm.intra_rank == 0, comm.intra_rank
    ranks = comm.allgather_obj(comm.rank)
    assert sorted(ranks) == list(range(comm.inter_size)), ranks


def scenario_obj_collectives(comm):
    import chainermn_tpu.communicators.tpu_xla as tx

    r = comm.inter_rank
    # bcast_obj: root's object everywhere
    assert comm.bcast_obj({"v": r} if r == 0 else None, root=0) == {"v": 0}
    # multi-frame path: shrink the frame so a modest payload chunks
    old = tx._OBJ_FRAME_BYTES
    tx._OBJ_FRAME_BYTES = 1024
    try:
        big = bytes(range(256)) * 40  # 10240 bytes -> 10 frames
        assert comm.bcast_obj(big if r == 0 else None, root=0) == big
        # asymmetric payload sizes across processes
        mine = "x" * (100 + 5000 * r)
        out = comm.allgather_obj(mine)
        assert [len(s) for s in out] == [100 + 5000 * p
                                         for p in range(comm.inter_size)]
    finally:
        tx._OBJ_FRAME_BYTES = old
    # allreduce_obj over nested structures
    red = comm.allreduce_obj({"loss": float(r), "n": 1}, op="sum")
    ws = comm.inter_size
    assert red == {"loss": sum(range(ws)) * 1.0, "n": ws}
    assert comm.allreduce_obj(2.0, op="mean") == 2.0
    # gather_obj: only root's process gets the list
    got = comm.gather_obj(r * 10, root=0)
    if r == 0:
        assert got == [p * 10 for p in range(ws)]
    else:
        assert got is None
    # scatter_obj
    objs = [f"piece{p}" for p in range(ws)] if r == 0 else None
    assert comm.scatter_obj(objs, root=0) == f"piece{r}"
    comm.barrier()


def scenario_p2p_obj(comm):
    from chainermn_tpu.communicators import _obj_channel

    r = comm.inter_rank
    peer_rank = 1 - r  # device rank == process rank here (1 dev/proc)
    # ordered multi-message exchange, both directions
    if r == 0:
        comm.send_obj({"msg": 1}, dest=1)
        comm.send_obj([2, "two"], dest=1)
        assert comm.recv_obj(source=1) == "reply"
    else:
        assert comm.recv_obj(source=0) == {"msg": 1}
        assert comm.recv_obj(source=0) == [2, "two"]
        comm.send_obj("reply", dest=0)
    comm.barrier()
    # multi-frame p2p: shrink the KV frame so the payload chunks
    old = _obj_channel.FRAME_BYTES
    _obj_channel.FRAME_BYTES = 512
    try:
        payload = np.arange(4096, dtype=np.int64)  # ~32 KiB pickled
        if r == 0:
            comm.send_obj(payload, dest=1)
        else:
            got = comm.recv_obj(source=0)
            np.testing.assert_array_equal(got, payload)
    finally:
        _obj_channel.FRAME_BYTES = old
    comm.barrier()
    # oversize single object raises the named error
    old_cap = _obj_channel.MAX_OBJ_BYTES
    _obj_channel.MAX_OBJ_BYTES = 100
    try:
        if r == 0:
            try:
                comm.send_obj("y" * 1000, dest=1)
            except _obj_channel.DataSizeError:
                pass
            else:
                raise AssertionError("DataSizeError not raised")
    finally:
        _obj_channel.MAX_OBJ_BYTES = old_cap
    comm.barrier()


def scenario_array_collectives(comm):
    """The jitted shard_map collectives over a process-spanning mesh."""
    ws = comm.size
    x = np.arange(ws * 3, dtype=np.float32).reshape(ws, 3)
    out = comm.allreduce(x, op="sum")
    expect = np.broadcast_to(x.sum(0), (ws, 3))
    local = np.asarray(out.addressable_shards[0].data)
    np.testing.assert_allclose(
        local, expect[comm.rank : comm.rank + 1])
    out = comm.bcast(x, root=1)
    local = np.asarray(out.addressable_shards[0].data)
    np.testing.assert_allclose(local, x[1:2])


def scenario_scatter_dataset(comm):
    from chainermn_tpu import scatter_dataset

    data = list(range(103))
    shard = scatter_dataset(data, comm, shuffle=True, seed=7)
    lens = comm.allgather_obj(len(shard))
    assert len(set(lens)) == 1, f"unequal shard lengths {lens}"
    all_idx = comm.allgather_obj(sorted(shard.indices.tolist()))
    covered = set()
    for idx in all_idx:
        covered.update(idx)
    assert covered == set(range(103))


def scenario_checkpoint(comm):
    from chainermn_tpu import create_multi_node_checkpointer

    class FakeUpdater:
        def __init__(self):
            self.iteration = 0
            self.params = {"w": np.zeros(3)}
            self.opt_state = {"m": np.zeros(3)}
            self.state = None

    # every process must agree on the directory: created by proc 0,
    # broadcast to the rest (node-local disks would each make their own)
    path = comm.bcast_obj(
        tempfile.mkdtemp(prefix="cmn_ckpt_") if comm.inter_rank == 0
        else None, root=0)
    cp = create_multi_node_checkpointer(comm, path)
    cp._cleanup = lambda keep: None  # keep both sets alive for the test
    up = FakeUpdater()
    for it in (5, 10):
        up.iteration = it
        up.params = {"w": np.full(3, float(it))}
        cp.save(up)
    # wreck iteration 10 on process 1 only -> latest COMMON set is 5
    if comm.inter_rank == 1:
        os.remove(os.path.join(path, f"snapshot_iter_10.1"))
    comm.barrier()
    fresh = FakeUpdater()
    cp2 = create_multi_node_checkpointer(comm, path)
    resumed = cp2.maybe_load(fresh)
    assert resumed == 5, f"expected agreement on 5, got {resumed}"
    np.testing.assert_allclose(fresh.params["w"], 5.0)
    comm.barrier()


def scenario_fallback_resume(comm):
    """Corruption drill across REAL processes: flip bytes in ONE rank's
    newest shard — the verified-set agreement must fall back to the
    previous complete set on EVERY process, and the damaged file must be
    quarantined (``*.corrupt``), not deleted."""
    from chainermn_tpu import create_multi_node_checkpointer
    from chainermn_tpu.testing import corrupt_file

    class FakeUpdater:
        def __init__(self):
            self.iteration = 0
            self.params = {"w": np.zeros(3)}
            self.opt_state = {"m": np.zeros(3)}
            self.state = None

    path = comm.bcast_obj(
        tempfile.mkdtemp(prefix="cmn_fbck_") if comm.inter_rank == 0
        else None, root=0)
    cp = create_multi_node_checkpointer(comm, path, history=2)
    up = FakeUpdater()
    for it in (5, 10):
        up.iteration = it
        up.params = {"w": np.full(3, float(it))}
        cp.save(up)
    # wreck iteration 10's BYTES on process 1 only (the file still
    # exists — presence-based agreement alone would wrongly pick 10)
    if comm.inter_rank == 1:
        corrupt_file(os.path.join(path, "snapshot_iter_10.1"), seed=7)
    comm.barrier()
    fresh = FakeUpdater()
    cp2 = create_multi_node_checkpointer(comm, path, history=2)
    resumed = cp2.maybe_load(fresh)
    assert resumed == 5, f"expected fallback to 5, got {resumed}"
    np.testing.assert_allclose(fresh.params["w"], 5.0)
    if comm.inter_rank == 1:
        assert os.path.exists(
            os.path.join(path, "snapshot_iter_10.1.corrupt"))
        assert not os.path.exists(
            os.path.join(path, "snapshot_iter_10.1"))
    else:
        # the healthy rank keeps its (verified) iteration-10 shard
        assert os.path.exists(
            os.path.join(path, f"snapshot_iter_10.{comm.inter_rank}"))
    comm.barrier()


def _kv_barrier(comm, channel):
    """Coordination-service barrier: works wherever the JAX distributed
    runtime does, including hosts whose CPU backend cannot run
    cross-process XLA collectives (which is also why the watchdog's own
    heartbeats ride the KV store, not a collective)."""
    channel.allgather(None, list(range(comm.inter_size)),
                      comm.inter_rank)


def scenario_watchdog_stall(comm):
    """Watchdog drill across REAL processes: rank 1 stalls past the
    threshold.  Its OWN monitor fires a local-stall report (stack dump +
    JSON) within one check interval, and the SURVIVOR (rank 0) detects
    the dead peer through the cross-process KV heartbeats.  Deliberately
    touches NO XLA collectives — failure detection must keep working
    exactly when the data plane is wedged."""
    import time

    from chainermn_tpu.communicators._obj_channel import KVObjectChannel
    from chainermn_tpu.extensions import TrainingWatchdog
    from chainermn_tpu.utils.metrics import MetricsRegistry, set_registry

    chan = KVObjectChannel(tag="wdtest")
    r = comm.inter_rank
    # enabled registry + a rank-unique marker: the survivor's stall
    # report must embed a MERGED metrics snapshot that includes the
    # DEAD peer's last KV-published state (no collective involved)
    set_registry(MetricsRegistry(enabled=True))
    from chainermn_tpu.utils.metrics import get_registry

    get_registry().inc(f"drill/rank{r}_marker")
    reports = []
    wd = TrainingWatchdog(
        stall_timeout=1.0, check_interval=0.25, comm=comm,
        on_stall=reports.append,
        report_path=os.path.join(tempfile.mkdtemp(), "stall.json"))
    wd.start()
    for i in range(4):          # healthy phase: everyone beats
        wd.heartbeat(iteration=i)
        time.sleep(0.15)
    assert not reports, f"false positive during healthy phase: {reports}"
    _kv_barrier(comm, chan)
    t0 = time.monotonic()
    if r == 1:
        time.sleep(2.6)         # the stalled rank: beats stop
    else:
        while time.monotonic() - t0 < 2.6:
            wd.heartbeat(iteration=99)
            time.sleep(0.15)
    wd.stop()
    if r == 1:
        local = [rep for rep in reports if rep["kind"] == "local-stall"]
        assert local, f"stalled rank never self-reported: {reports}"
        assert local[0]["seconds_since_heartbeat"] > 1.0
        assert local[0]["threads"], "report carries no thread stacks"
        assert os.path.exists(wd.report_path)
    else:
        peer = [rep for rep in reports if 1 in rep["stalled_peers"]]
        assert peer, (
            f"survivor never detected the stalled peer: {reports}")
        assert peer[0]["peer_heartbeat_ages_s"][1] > 1.0
        # the hung job's last Prometheus state ships with the
        # diagnosis: the merged snapshot holds BOTH ranks' markers —
        # the dead peer's via its KV-published snapshot
        assert peer[0]["metrics_enabled"] is True
        assert "drill/rank0_marker" in peer[0]["metrics"], \
            sorted(peer[0]["metrics"])
        assert "drill/rank1_marker" in peer[0]["metrics"], \
            sorted(peer[0]["metrics"])
        assert "drill_rank1_marker" in peer[0]["metrics_prom"]
    _kv_barrier(comm, chan)


def scenario_checkpoint_async(comm):
    """Async checkpointer across real processes: overlapped writes, the
    join-then-barrier GC ordering, and resume agreement."""
    from chainermn_tpu import create_multi_node_checkpointer

    class FakeUpdater:
        def __init__(self):
            self.iteration = 0
            self.params = {"w": np.zeros(3)}
            self.opt_state = {"m": np.zeros(3)}
            self.state = None

    path = comm.bcast_obj(
        tempfile.mkdtemp(prefix="cmn_ackpt_") if comm.inter_rank == 0
        else None, root=0)
    cp = create_multi_node_checkpointer(comm, path, async_write=True)
    up = FakeUpdater()
    for it in (5, 10, 15):
        up.iteration = it
        up.params = {"w": np.full(3, float(it))}
        cp.save(up)
    cp.finalize()
    comm.barrier()
    # GC: only the newest complete set remains on every process
    mine = sorted(fn for fn in os.listdir(path)
                  if fn.endswith(f".{comm.inter_rank}"))
    assert mine == ["snapshot_iter_15." + str(comm.inter_rank)], mine
    fresh = FakeUpdater()
    cp2 = create_multi_node_checkpointer(comm, path)
    assert cp2.maybe_load(fresh) == 15
    np.testing.assert_allclose(fresh.params["w"], 15.0)
    comm.barrier()


def scenario_evaluator(comm):
    from chainermn_tpu import create_multi_node_evaluator

    class LocalEval:
        name = "validation"

        def __init__(self, value):
            self._value = value

        def evaluate(self, params):
            return {"acc": self._value}

    # process r reports acc=r; the multi-node wrapper must average
    ev = create_multi_node_evaluator(LocalEval(float(comm.inter_rank)), comm)
    obs = ev.evaluate(None)
    ws = comm.inter_size
    assert abs(obs["acc"] - sum(range(ws)) / ws) < 1e-9, obs


def scenario_broadcast_iterator(comm):
    from chainermn_tpu import SerialIterator, create_multi_node_iterator

    # only the master process can see the "real" data source
    if comm.inter_rank == 0:
        base = SerialIterator(list(range(10)), batch_size=4,
                              repeat=False, shuffle=True, seed=3)
    else:
        base = SerialIterator([None] * 10, batch_size=4, repeat=False)
    it = create_multi_node_iterator(base, comm, rank_master=0)
    batches = []
    for batch in it:
        batches.append(batch)
    gathered = comm.allgather_obj(batches)
    for other in gathered[1:]:
        assert other == gathered[0], "slave batches diverge from master"
    assert sorted(sum(gathered[0], [])) == list(range(10))


def scenario_observation_aggregator(comm):
    from chainermn_tpu.extensions import ObservationAggregator

    class FakeTrainer:
        def __init__(self):
            self.observation = {}

    agg = ObservationAggregator(comm)
    tr = FakeTrainer()
    tr.observation = {"loss": float(comm.inter_rank + 1)}
    agg.observe(tr)
    ws = comm.inter_size
    expect = sum(range(1, ws + 1)) / ws
    assert abs(tr.observation["loss"] - expect) < 1e-9, tr.observation


def scenario_split(comm):
    """MPI_Comm_split analogue across processes: even/odd device split
    produces working sub-communicators whose obj collectives stay inside
    the split (the reference's split tests, SURVEY.md §4).  Run with ≥4
    processes so each subgroup spans >1 process — the whole-world
    multihost collectives would deadlock there; the KV group path must
    carry them."""
    ws = comm.size
    colors = np.arange(ws) % 2
    sub = comm.split(colors, np.arange(ws))
    expect = [i for i in range(ws) if i % 2 == comm.rank % 2]
    assert sub.size == len(expect), (sub.size, expect)
    # sub-communicator topology: my rank within my color group
    assert sub.rank == expect.index(comm.rank)
    # obj collectives scope to the subgroup (distinct KV lanes per split)
    vals = sub.allgather_obj(comm.rank)
    assert vals == expect, (vals, expect)
    # subgroup bcast: root is the subgroup's OWN rank 0 (global device
    # rank expect[0]); both halves broadcast concurrently without
    # cross-talk or deadlock
    got = sub.bcast_obj(f"from{comm.rank}" if sub.rank == 0 else None,
                        root=0)
    assert got == f"from{expect[0]}", got
    # repeated rounds: the lazy-GC key lifecycle must keep lanes ordered
    for round_no in range(3):
        red = sub.allreduce_obj({"r": float(comm.rank), "n": 1}, op="sum")
        assert red == {"r": float(sum(expect)), "n": len(expect)}, red
        sub.barrier()
    # re-created communicator with the SAME member set: the incarnation
    # counter must give it a fresh KV namespace (seq numbers restart at 0
    # and must not read the first incarnation's still-live keys)
    sub2 = comm.split(colors, np.arange(ws))
    vals2 = sub2.allgather_obj(("fresh", comm.rank))
    assert vals2 == [("fresh", p) for p in expect], vals2


def scenario_snapshot(comm):
    """multi_node_snapshot across real processes: writer rank persists
    one logical snapshot, the barrier protects readers, and
    load_snapshot restores it on EVERY process."""
    from chainermn_tpu import multi_node_snapshot
    from chainermn_tpu.extensions.snapshot import load_snapshot

    class FakeUpdater:
        def __init__(self):
            self.iteration = 7
            self.params = {"w": np.full(2, 3.25)}
            self.opt_state = {"m": np.ones(2)}
            self.state = None

    class FakeTrainer:
        def __init__(self, out):
            self.updater = FakeUpdater()
            self.out = out
            self.observation = {}

    out = comm.bcast_obj(
        tempfile.mkdtemp(prefix="cmn_snap_") if comm.inter_rank == 0
        else None, root=0)
    snap = multi_node_snapshot(comm)
    snap(FakeTrainer(out))          # writer writes snapshot_iter_7
    fresh = FakeTrainer(out)
    fresh.updater.iteration = 0
    fresh.updater.params = {"w": np.zeros(2)}
    it = load_snapshot(fresh.updater,
                       os.path.join(out, "snapshot_iter_7"), fresh)
    assert it == 7, it
    np.testing.assert_allclose(fresh.updater.params["w"], 3.25)
    comm.barrier()


def scenario_allreduce_persistent(comm):
    """BN-running-stats averaging across processes (the reference's
    AllreducePersistentValues)."""
    from chainermn_tpu.extensions import AllreducePersistentValues

    class FakeUpdater:
        def __init__(self, r):
            self.params = {"persistent": {"bn_mean": np.full(3, float(r))}}

    class FakeTrainer:
        def __init__(self, r):
            self.updater = FakeUpdater(r)

    tr = FakeTrainer(comm.inter_rank)
    AllreducePersistentValues(comm)(tr)
    ws = comm.inter_size
    np.testing.assert_allclose(
        tr.updater.params["persistent"]["bn_mean"],
        sum(range(ws)) / ws)


def scenario_dp_train(comm):
    """End-to-end: a jitted DP train step over the PROCESS-SPANNING mesh
    — per-process batches, pmean'd grads, params provably in sync (the
    reference's whole raison d'être, §3.1, across real processes)."""
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu import create_multi_node_optimizer

    ws = comm.size
    rng = np.random.RandomState(0)              # same on every process
    w_true = rng.randn(4, 2).astype(np.float32)
    xs = rng.randn(ws, 32, 4).astype(np.float32)
    ys = np.einsum("rbi,ij->rbj", xs, w_true)

    params = {"w": jnp.zeros((4, 2))}
    opt = create_multi_node_optimizer(optax.sgd(0.2), comm)
    state = jax.jit(opt.init)(params)

    def step(p, s, x, y):
        x, y = x[0], y[0]
        loss, g = jax.value_and_grad(
            lambda q: jnp.mean((x @ q["w"] - y) ** 2))(p)
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s, jax.lax.pmean(
            loss, comm.axis_name)

    f = jax.jit(jax.shard_map(
        step, mesh=comm.mesh,
        in_specs=(P(), P(), P(comm.axis_name), P(comm.axis_name)),
        out_specs=(P(), P(), P())))
    # global batch sharded over the world: this process feeds its shard
    sh = jax.sharding.NamedSharding(comm.mesh, P(comm.axis_name))
    gx = jax.device_put(jnp.asarray(xs), sh)
    gy = jax.device_put(jnp.asarray(ys), sh)
    losses = []
    for _ in range(60):
        params, state, loss = f(params, state, gx, gy)
        losses.append(float(jax.block_until_ready(loss)))
    assert losses[-1] < 1e-2, losses[-1]
    # every process must hold identical params
    w_all = comm.allgather_obj(np.asarray(params["w"]).tolist())
    for other in w_all[1:]:
        assert other == w_all[0], "params diverged across processes"


def scenario_shuffle_datablock(comm):
    """Cross-process block shuffle: unequal per-process blocks come out
    globally shuffled, balanced, and complete — the examples really move
    between processes (each block starts disjoint)."""
    from chainermn_tpu.datasets import shuffle_data_blocks

    r, n = comm.inter_rank, comm.inter_size
    # disjoint, unequal blocks: proc r holds r*100 .. r*100 + (10 - 2r)
    sizes = [10 - 2 * j for j in range(n)]
    block = list(range(r * 100, r * 100 + sizes[r]))
    out = shuffle_data_blocks(comm, block, seed=5)

    gathered = comm.allgather_obj(out)
    merged = sorted(x for row in gathered for x in row)
    expected = sorted(
        x for j in range(n) for x in range(j * 100, j * 100 + sizes[j]))
    assert merged == expected, merged
    # balanced: near-equal split of the total
    total = sum(sizes)
    assert {len(row) for row in gathered} <= {total // n, -(-total // n)}, \
        [len(x) for x in gathered]
    # actually mixed across processes: each output spans several blocks
    assert len({x // 100 for x in out}) > 1, out
    # alltoall_obj round-trip sanity on its own
    back = comm.alltoall_obj([f"{r}->{j}" for j in range(comm.inter_size)])
    assert back == [f"{j}->{r}" for j in range(comm.inter_size)], back


def scenario_zero1_checkpoint(comm):
    """ZeRO-1 over a PROCESS-SPANNING mesh: the optimizer state is not
    fully addressable by either process, so checkpointing exercises the
    gather-on-save path; resume must agree across processes."""
    import jax.numpy as jnp
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.extensions import create_multi_node_checkpointer
    from chainermn_tpu.models import init_mlp, mlp_apply, \
        softmax_cross_entropy

    path = comm.bcast_obj(
        tempfile.mkdtemp(prefix="zero1ck_")
        if comm.inter_rank == 0 else None, root=0)

    def make_updater():
        rng = np.random.RandomState(0)          # same data on all procs
        data = [(rng.randn(4).astype(np.float32), np.int32(i % 2))
                for i in range(64)]
        it = cmn.SerialIterator(data, 16, shuffle=True, seed=1)
        params = init_mlp(jax.random.PRNGKey(0), [4, 8, 2])
        opt = cmn.create_multi_node_optimizer(
            optax.adam(5e-2), comm, zero1=True)

        def loss_fn(p, x, y):
            return softmax_cross_entropy(mlp_apply(p, x), y)

        return cmn.StandardUpdater(it, opt, loss_fn, params, comm)

    upd = make_updater()
    assert upd.zero1
    # state spans both processes' devices
    leaf = jax.tree.leaves(upd.opt_state)[0]
    assert not leaf.is_fully_addressable
    for _ in range(3):
        upd.update()

    cp = create_multi_node_checkpointer(comm, path)
    cp.save(upd)

    upd2 = make_updater()
    loaded = create_multi_node_checkpointer(comm, path)
    assert loaded.maybe_load(upd2) == 3
    # params agree across processes and match the saved run
    w = comm.allgather_obj(
        np.asarray(jax.tree.leaves(upd2.params)[0]).tolist())
    assert w[0] == w[-1]
    np.testing.assert_allclose(
        np.asarray(jax.tree.leaves(upd2.params)[0]),
        np.asarray(jax.tree.leaves(upd.params)[0]), rtol=1e-6)
    # the restored run continues without error
    upd2.update()

    # async writer path: device pull + collective gather happen on the
    # main thread before the writer thread starts — must not crash or
    # deadlock on the process-spanning state
    cp_async = create_multi_node_checkpointer(
        comm, path, name="async", async_write=True)
    cp_async.save(upd2)
    cp_async.finalize()
    assert cp_async._agreed_inventory()[0] == [4]

    # writer-only snapshot: ALL ranks join the collective gather before
    # rank 0 writes (a writer-only gather would deadlock the barrier)
    from chainermn_tpu.extensions import multi_node_snapshot

    class _Tr:
        updater = upd2
        out = path
        observation = {}

    multi_node_snapshot(comm)(_Tr())
    import os

    assert os.path.exists(os.path.join(path, "snapshot_iter_4")) \
        or comm.inter_rank != 0


def scenario_preemption(comm):
    """The preemption flag is OR-reduced COLLECTIVELY: only process 0
    'receives' the signal, yet every process must checkpoint the same
    iteration and stop — exercising the ``inter_size > 1`` branch of
    ``PreemptionCheckpointer._global_flag`` with real processes."""
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.extensions import (
        PreemptionCheckpointer,
        create_multi_node_checkpointer,
    )
    from chainermn_tpu.models import init_mlp, mlp_apply, \
        softmax_cross_entropy

    # every process must agree on the directory (rank 0 decides)
    path = comm.bcast_obj(
        tempfile.mkdtemp(prefix="preempt_")
        if comm.inter_rank == 0 else None, root=0)

    rng = np.random.RandomState(0)
    data = [(rng.randn(4).astype(np.float32), np.int32(i % 2))
            for i in range(64)]
    it = cmn.SerialIterator(data, 16, shuffle=True, seed=1)
    params = init_mlp(jax.random.PRNGKey(0), [4, 8, 2])
    opt = cmn.create_multi_node_optimizer(optax.sgd(0.05), comm)

    def loss_fn(p, x, y):
        return softmax_cross_entropy(mlp_apply(p, x), y)

    upd = cmn.StandardUpdater(it, opt, loss_fn, params, comm)
    trainer = cmn.Trainer(upd, (50, "epoch"), out=path)
    cp = create_multi_node_checkpointer(comm, path)
    pre = PreemptionCheckpointer(cp, comm, signals=())
    trainer.extend(pre)

    @cmn.training.make_extension(trigger=(1, "iteration"), priority=999)
    def fake_signal(tr):
        # ONLY process 0 sees the signal; the others learn of it
        # through the collective flag reduce
        if comm.inter_rank == 0 and tr.updater.iteration == 3:
            pre.signaled = True

    trainer.extend(fake_signal)
    trainer.run()

    assert upd.iteration == 3, upd.iteration
    assert "preemption" in (trainer.stop_reason or ""), trainer.stop_reason
    # all processes agreed on the checkpointed iteration
    iters = comm.allgather_obj(cp._agreed_inventory()[0])
    assert all(x == [3] for x in iters), iters


def scenario_fsdp_train(comm):
    """ZeRO-3/FSDP over a PROCESS-SPANNING data axis: the flagship
    transformer's fsdp layout puts each process's device on a 1/N param
    shard, the per-layer gathers cross the process boundary, and the
    losses must match the replicated run exactly."""
    import dataclasses

    import jax.numpy as jnp
    import optax

    from chainermn_tpu.models import (
        TransformerConfig, init_transformer, make_train_step, shard_params,
    )
    from chainermn_tpu.parallel import MeshConfig
    from chainermn_tpu.training import shard_opt_state

    B, T = 4, 8
    dense = TransformerConfig(
        vocab_size=32, d_model=16, n_heads=2, d_head=8, d_ff=32,
        n_layers=2, max_seq=T, attention="local", dtype="float32",
        remat=False)
    mc = MeshConfig(data=comm.size, devices=jax.devices())
    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, 32, (B, T + 1)), jnp.int32)

    def train(cfg, steps=2):
        params = shard_params(
            mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
        opt = optax.adam(1e-2)
        opt_state = shard_opt_state(opt, params)
        step = make_train_step(mc, cfg, opt)
        out = []
        for _ in range(steps):
            params, opt_state, loss = step(
                params, opt_state, toks[:, :T], toks[:, 1:])
            out.append(float(jax.block_until_ready(loss)))
        return out, params

    fsdp_losses, placed = train(dataclasses.replace(dense, fsdp=True))
    # this process's device really holds only its 1/N slice at rest
    w1 = placed["blocks"]["w1"]
    assert w1.addressable_shards[0].data.shape[2] == 16 // comm.size, \
        w1.addressable_shards[0].data.shape
    dense_losses, _ = train(dense)
    np.testing.assert_allclose(fsdp_losses, dense_losses,
                               rtol=1e-5, atol=1e-5)
    # every process must agree on the loss trajectory
    all_losses = comm.allgather_obj(fsdp_losses)
    for other in all_losses[1:]:
        np.testing.assert_allclose(other, all_losses[0],
                                   rtol=1e-6, atol=1e-6)


def _gather_rows(comm, got, dtype=np.int32):
    """Reassemble a batch-sharded decode output across processes: each
    process contributes its own shard KEYED BY ITS ROW OFFSET — device
    order need not follow process order, so process index must never
    decide row placement."""
    shard = got.addressable_shards[0]
    row0 = shard.index[0].start or 0
    alls = dict(comm.allgather_obj(
        (int(row0), np.asarray(shard.data).tolist())))
    return np.concatenate(
        [np.asarray(alls[r], dtype) for r in sorted(alls)], axis=0)


def _tiny_cfg(**kw):
    """The shared tiny transformer of the data-plane scenarios — one
    definition so every scenario provably tests the same model."""
    from chainermn_tpu.models import TransformerConfig

    base = dict(vocab_size=32, d_model=16, n_heads=2, d_head=8,
                d_ff=32, n_layers=2, max_seq=8, attention="local",
                dtype="float32", remat=False)
    base.update(kw)
    return TransformerConfig(**base)


def _tiny_transformer_losses(mc, cfg, steps=2):
    """Shared driver for the TP/PP data-plane scenarios: init, shard,
    run ``steps`` train steps on the given mesh, return the losses."""
    import jax.numpy as jnp
    import optax

    from chainermn_tpu.models import (
        init_transformer, make_train_step, shard_params,
    )
    from chainermn_tpu.training import shard_opt_state

    B, T = 4, 8
    pipe = mc.mesh.shape.get("pipe", 1)
    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (B, T + 1)),
        jnp.int32)
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg, pipe))
    opt = optax.adam(1e-2)
    opt_state = shard_opt_state(opt, params)
    step = make_train_step(mc, cfg, opt)
    out = []
    for _ in range(steps):
        params, opt_state, loss = step(
            params, opt_state, toks[:, :T], toks[:, 1:])
        out.append(float(jax.block_until_ready(loss)))
    return out


@functools.cache
def _local_oracle_losses(cfg):
    """``cfg``'s losses on this process's own device, no axis sharded:
    the yardstick of the train scenarios, compiled once a launch (the
    scenarios that share a launch ask for the same one)."""
    from chainermn_tpu.parallel import MeshConfig

    return _tiny_transformer_losses(
        MeshConfig(data=1, devices=[jax.local_devices()[0]]), cfg)


@functools.cache
def _local_generate_fn(cfg, **kw):
    """``make_generate_fn`` on this process's own device: the greedy
    yardstick of the decode scenarios, built once a launch."""
    from chainermn_tpu.models import make_generate_fn
    from chainermn_tpu.parallel import MeshConfig

    return make_generate_fn(
        MeshConfig(data=1, devices=[jax.local_devices()[0]]), cfg, **kw)


def scenario_tp_train(comm):
    """Tensor parallelism ACROSS the process boundary: 2 processes × 1
    device, ``model=2`` — every layer's column→row psum is a real
    cross-process collective.  The loss trajectory must equal a
    process-LOCAL single-device oracle (same init, same data)."""
    from chainermn_tpu.parallel import MeshConfig

    assert jax.process_count() == 2 and len(jax.local_devices()) == 1
    cfg = _tiny_cfg()

    tp_losses = _tiny_transformer_losses(
        MeshConfig(model=2, data=1, devices=jax.devices()), cfg)
    # local oracle: this process's own device, no sharded axes
    oracle = _local_oracle_losses(cfg)
    np.testing.assert_allclose(tp_losses, oracle, rtol=1e-5, atol=1e-5)
    all_losses = comm.allgather_obj(tp_losses)
    for other in all_losses[1:]:
        np.testing.assert_allclose(other, all_losses[0],
                                   rtol=1e-6, atol=1e-6)


def scenario_pp_train(comm):
    """Pipeline parallelism ACROSS the process boundary: 2 processes × 2
    devices, ``MeshConfig(pipe=2, model=2)`` — pipe is the mesh-major
    axis, so each stage's ppermute activation hand-off crosses the
    process boundary while each stage's TP psum stays process-local
    (the production layout).  Also runs ``MeshConfig(model=2, data=2)``
    — the VERDICT-named shape, whose grad allreduce spans processes —
    and checks both against the process-local single-device oracle."""
    import dataclasses

    from chainermn_tpu.parallel import MeshConfig

    assert jax.process_count() == 2 and len(jax.local_devices()) == 2
    base = _tiny_cfg()
    oracle = _local_oracle_losses(base)

    for axes, cfg in (
        (dict(pipe=2, model=2, data=1),
         dataclasses.replace(base, num_microbatches=2)),
        (dict(pipe=2, model=2, data=1),
         dataclasses.replace(base, num_microbatches=2,
                             pipeline_schedule="1f1b")),
        (dict(model=2, data=2), base),
    ):
        losses = _tiny_transformer_losses(
            MeshConfig(devices=jax.devices(), **axes), cfg)
        np.testing.assert_allclose(
            losses, oracle, rtol=1e-5, atol=1e-5,
            err_msg=f"{axes} {cfg.pipeline_schedule}")
        all_losses = comm.allgather_obj(losses)
        for other in all_losses[1:]:
            np.testing.assert_allclose(other, all_losses[0],
                                       rtol=1e-6, atol=1e-6)


def scenario_decode(comm):
    """Model-parallel DECODE across the process boundary: 2 processes ×
    1 device.  Two meshes: ``seq=2`` (sequence-parallel KV — every
    generated token's pmax/psum softmax merge is a real cross-process
    collective) and ``model=2`` with ``vocab_parallel`` (the embedding
    lookup psum and the logits all-gather cross processes).  Greedy
    tokens must be IDENTICAL to the process-local single-device decode
    — sampling amplifies any logit drift into divergent sequences, so
    exact token equality is the right bar."""
    import dataclasses

    from chainermn_tpu.models import (
        init_transformer, make_generate_fn, shard_params,
    )
    from chainermn_tpu.parallel import MeshConfig

    assert jax.process_count() == 2 and len(jax.local_devices()) == 1
    base = _tiny_cfg()
    host = init_transformer(jax.random.PRNGKey(2), base)
    import jax.numpy as jnp

    prompt = jnp.asarray(
        np.random.RandomState(3).randint(0, base.vocab_size, (4, 3)),
        jnp.int32)

    one = MeshConfig(data=1, devices=[jax.local_devices()[0]])
    ref = np.asarray(
        _local_generate_fn(base, max_len=8)(
            shard_params(one, base, host), prompt))

    for name, axes, cfg in (
        ("seq-kv", dict(seq=2, data=1), base),
        ("vocab-parallel", dict(model=2, data=1),
         dataclasses.replace(base, vocab_parallel=True)),
    ):
        mc = MeshConfig(devices=jax.devices(), **axes)
        got = np.asarray(
            make_generate_fn(mc, cfg, max_len=8)(
                shard_params(mc, cfg, host), prompt))
        np.testing.assert_array_equal(
            got, ref, err_msg=f"cross-process {name} decode diverged")
        all_toks = comm.allgather_obj(got.tolist())
        assert all(t == all_toks[0] for t in all_toks[1:]), \
            f"{name}: processes disagree on generated tokens"

    # padded + eos over a cross-process data axis: the early-stop
    # while-loop's pmax flag and the per-row pad masks span the
    # boundary; tokens must equal the process-local padded oracle
    lens = np.asarray([3, 1, 2, 3])
    padded = np.full((4, 3), 7, np.int32)
    rng = np.random.RandomState(8)
    for b, L in enumerate(lens):
        padded[b, 3 - L:] = rng.randint(0, base.vocab_size, L)
    pl = jnp.asarray(padded)
    kw = dict(max_len=8, eos_id=5, pad_id=0)
    ref2 = np.asarray(
        _local_generate_fn(base, **kw)(
            shard_params(one, base, host), pl, prompt_lens=lens))
    mc = MeshConfig(data=2, devices=jax.devices())
    sh = mc.sharding(("data", "expert"))
    got = make_generate_fn(mc, base, **kw)(
        shard_params(mc, base, host), jax.device_put(pl, sh),
        prompt_lens=jax.device_put(jnp.asarray(lens, jnp.int32), sh))
    full = _gather_rows(comm, got)
    np.testing.assert_array_equal(
        full, ref2, err_msg="cross-process padded+eos decode diverged")


def scenario_speculative_decode(comm):
    """Speculative decoding ACROSS the process boundary: 2 processes ×
    1 device, ``data=2`` — the per-round acceptance pmin and the
    verify-chunk collectives run inside a cross-process while_loop.
    Tokens must equal the process-local greedy oracle, and both
    processes must agree on the acceptance statistic."""
    from chainermn_tpu.models import (
        init_transformer, make_generate_fn,
        make_speculative_generate_fn, shard_params,
    )
    from chainermn_tpu.parallel import MeshConfig

    assert jax.process_count() == 2 and len(jax.local_devices()) == 1
    cfg = _tiny_cfg(n_layers=4)
    d_cfg = _tiny_cfg(n_layers=2)
    host = init_transformer(jax.random.PRNGKey(4), cfg)
    d_host = init_transformer(jax.random.PRNGKey(5), d_cfg)
    import jax.numpy as jnp

    prompt = jnp.asarray(
        np.random.RandomState(6).randint(0, cfg.vocab_size, (4, 3)),
        jnp.int32)

    one = MeshConfig(data=1, devices=[jax.local_devices()[0]])
    ref = np.asarray(
        make_generate_fn(one, cfg, max_len=8)(
            shard_params(one, cfg, host), prompt))

    mc = MeshConfig(data=2, devices=jax.devices())
    spec = make_speculative_generate_fn(
        mc, cfg, d_cfg, k=2, max_len=8, with_stats=True)
    # the batch spans the process boundary: feed the sharded global
    # array (dp_train's pattern), reassemble the sharded output over
    # the object channel for the equality check — keyed by each
    # shard's OWN row offset, not process index (device order need
    # not follow process order)
    sh = mc.sharding(("data", "expert"))
    params = shard_params(mc, cfg, host)
    got, mean_acc = spec(params,
                         shard_params(mc, d_cfg, d_host),
                         jax.device_put(prompt, sh))
    full = _gather_rows(comm, got)
    np.testing.assert_array_equal(
        full, ref, err_msg="cross-process speculative decode diverged")
    accs = comm.allgather_obj(float(mean_acc))
    assert all(abs(a - accs[0]) < 1e-6 for a in accs), \
        f"processes disagree on acceptance: {accs}"

    # --- NONZERO accepted prefix across the mesh (VERDICT r4 #3): a
    # self-draft's proposals all verify, so the accept/commit path
    # (_commit_round with n_acc > 0) provably crosses the process
    # boundary — the random-draft phase above only witnesses the
    # all-reject corrective path
    self_spec = make_speculative_generate_fn(
        mc, cfg, cfg, k=2, max_len=8, with_stats=True)
    got_sd, acc_sd = self_spec(params, params,
                               jax.device_put(prompt, sh))
    full_sd = _gather_rows(comm, got_sd)
    np.testing.assert_array_equal(
        full_sd, ref, err_msg="self-draft speculative diverged")
    assert float(acc_sd) >= 1.0, \
        f"self-draft must accept a nonzero prefix, got {float(acc_sd)}"

    # --- padded + eos composition: ragged rows and the early-stop
    # done flags ride the same cross-process while_loop
    lens = np.asarray([3, 1, 2, 3])
    padded = np.full((4, 3), 7, np.int32)
    rng = np.random.RandomState(13)
    for b, L in enumerate(lens):
        padded[b, 3 - L:] = rng.randint(0, cfg.vocab_size, L)
    pl = jnp.asarray(padded)
    kw = dict(max_len=8, eos_id=5, pad_id=0)
    ref_pe = np.asarray(
        make_generate_fn(one, cfg, **kw)(
            shard_params(one, cfg, host), pl, prompt_lens=lens))
    spec_pe = make_speculative_generate_fn(
        mc, cfg, d_cfg, k=2, **kw)
    got_pe = spec_pe(params, shard_params(mc, d_cfg, d_host),
                     jax.device_put(pl, sh),
                     prompt_lens=jax.device_put(
                         jnp.asarray(lens, jnp.int32), sh))
    np.testing.assert_array_equal(
        _gather_rows(comm, got_pe), ref_pe,
        err_msg="cross-process speculative padded+eos diverged")


def scenario_speculative_sampling(comm):
    """Speculative SAMPLING across the process boundary: the per-round
    acceptance pmin, the shard-decorrelated PRNG fold, and the
    while-loop key carry all span processes.  Same-key runs must be
    deterministic, processes must agree on the acceptance statistic,
    and different keys must draw different sequences."""
    import dataclasses

    from chainermn_tpu.models import (
        init_transformer, make_speculative_generate_fn, shard_params,
    )
    from chainermn_tpu.parallel import MeshConfig

    assert jax.process_count() == 2 and len(jax.local_devices()) == 1
    cfg = _tiny_cfg(n_layers=4)
    d_cfg = dataclasses.replace(cfg, n_layers=2)
    host = init_transformer(jax.random.PRNGKey(11), cfg)
    d_host = dict(host, blocks=jax.tree.map(
        lambda a: a[:, :2], host["blocks"]))
    import jax.numpy as jnp

    prompt = jnp.asarray(
        np.random.RandomState(12).randint(0, cfg.vocab_size, (4, 3)),
        jnp.int32)
    mc = MeshConfig(data=2, devices=jax.devices())
    sh = mc.sharding(("data", "expert"))
    spec = make_speculative_generate_fn(
        mc, cfg, d_cfg, k=2, max_len=8, temperature=1.0,
        with_stats=True)
    params = shard_params(mc, cfg, host)
    d_params = shard_params(mc, d_cfg, d_host)
    gp = jax.device_put(prompt, sh)
    a1, acc = spec(params, d_params, gp, key=jax.random.PRNGKey(3))
    a2, _ = spec(params, d_params, gp, key=jax.random.PRNGKey(3))
    b1, _ = spec(params, d_params, gp, key=jax.random.PRNGKey(4))
    ra1, ra2, rb1 = (_gather_rows(comm, t) for t in (a1, a2, b1))
    np.testing.assert_array_equal(ra1, ra2,
                                  err_msg="same key, different tokens")
    assert not np.array_equal(ra1, rb1), "keys ignored"
    assert (ra1 >= 0).all() and (ra1 < cfg.vocab_size).all()
    np.testing.assert_array_equal(ra1[:, :3], np.asarray(prompt))
    accs = comm.allgather_obj(float(acc))
    assert all(abs(x - accs[0]) < 1e-6 for x in accs), accs

    # --- top-k/top-p composition: the truncated draft/target pair's
    # acceptance pmin crosses the boundary.  Checked here: same-key
    # determinism, vocab-range sanity, and cross-process agreement on
    # the acceptance statistic; the truncated-support and distribution
    # identities are pinned by the single-device statistical test
    # (test_sampling_filters_distribution_matches_target)
    TOPK = 6
    fspec = make_speculative_generate_fn(
        mc, cfg, d_cfg, k=2, max_len=8, temperature=1.0,
        top_k=TOPK, top_p=0.9, with_stats=True)
    f1, facc = fspec(params, d_params, gp, key=jax.random.PRNGKey(5))
    f2, _ = fspec(params, d_params, gp, key=jax.random.PRNGKey(5))
    rf1, rf2 = (_gather_rows(comm, t) for t in (f1, f2))
    np.testing.assert_array_equal(
        rf1, rf2, err_msg="filtered sampling not deterministic")
    assert (rf1 >= 0).all() and (rf1 < cfg.vocab_size).all()
    faccs = comm.allgather_obj(float(facc))
    assert all(abs(x - faccs[0]) < 1e-6 for x in faccs), faccs

    # --- ragged + eos composition under SAMPLING: per-row offsets and
    # the done flags ride the cross-process while_loop with the key
    # carry; same-key determinism and prompt preservation across the
    # boundary (per-row content exactness is pinned single-device)
    lens = np.asarray([3, 1, 2, 3])
    padded = np.full((4, 3), 7, np.int32)
    rng = np.random.RandomState(17)
    for b, L in enumerate(lens):
        padded[b, 3 - L:] = rng.randint(0, cfg.vocab_size, L)
    pl = jax.device_put(jnp.asarray(padded), sh)
    gl = jax.device_put(jnp.asarray(lens, jnp.int32), sh)
    pspec = make_speculative_generate_fn(
        mc, cfg, d_cfg, k=2, max_len=8, temperature=1.0,
        eos_id=5, pad_id=0, with_stats=True)
    p1, pacc = pspec(params, d_params, pl, key=jax.random.PRNGKey(6),
                     prompt_lens=gl)
    p2, _ = pspec(params, d_params, pl, key=jax.random.PRNGKey(6),
                  prompt_lens=gl)
    rp1, rp2 = (_gather_rows(comm, t) for t in (p1, p2))
    np.testing.assert_array_equal(
        rp1, rp2, err_msg="padded sampling not deterministic")
    np.testing.assert_array_equal(rp1[:, :3], padded)
    paccs = comm.allgather_obj(float(pacc))
    assert all(abs(x - paccs[0]) < 1e-6 for x in paccs), paccs


def scenario_lookup_decode(comm):
    """Prompt-lookup decoding ACROSS the process boundary: data=2 over
    2 single-device processes — the n-gram matcher is row-local but
    the acceptance pmin and verify-chunk collectives span processes.
    Tokens must equal the process-local greedy oracle."""
    from chainermn_tpu.models import (
        init_transformer, make_lookup_generate_fn, shard_params,
    )
    from chainermn_tpu.parallel import MeshConfig

    assert jax.process_count() == 2 and len(jax.local_devices()) == 1
    cfg = _tiny_cfg()
    host = init_transformer(jax.random.PRNGKey(7), cfg)
    import jax.numpy as jnp

    prompt = jnp.asarray(
        np.random.RandomState(9).randint(0, cfg.vocab_size, (4, 3)),
        jnp.int32)
    one = MeshConfig(data=1, devices=[jax.local_devices()[0]])
    ref = np.asarray(
        _local_generate_fn(cfg, max_len=8)(
            shard_params(one, cfg, host), prompt))

    mc = MeshConfig(data=2, devices=jax.devices())
    sh = mc.sharding(("data", "expert"))
    params = shard_params(mc, cfg, host)
    got, mean_acc = make_lookup_generate_fn(
        mc, cfg, k=2, ngram=2, max_len=8, with_stats=True)(
        params, jax.device_put(prompt, sh))
    full = _gather_rows(comm, got)
    np.testing.assert_array_equal(
        full, ref, err_msg="cross-process lookup decode diverged")
    accs = comm.allgather_obj(float(mean_acc))
    assert all(abs(a - accs[0]) < 1e-6 for a in accs), accs

    # --- padded + eos composition over the same mesh
    lens = np.asarray([3, 2, 2, 3])
    padded = np.full((4, 3), 7, np.int32)
    rng = np.random.RandomState(14)
    for b, L in enumerate(lens):
        padded[b, 3 - L:] = rng.randint(0, cfg.vocab_size, L)
    pl = jnp.asarray(padded)
    kw = dict(max_len=8, eos_id=5, pad_id=0)
    ref_pe = np.asarray(
        _local_generate_fn(cfg, **kw)(
            shard_params(one, cfg, host), pl, prompt_lens=lens))
    got_pe = make_lookup_generate_fn(mc, cfg, k=2, ngram=2, **kw)(
        params, jax.device_put(pl, sh),
        prompt_lens=jax.device_put(jnp.asarray(lens, jnp.int32), sh))
    np.testing.assert_array_equal(
        _gather_rows(comm, got_pe), ref_pe,
        err_msg="cross-process lookup padded+eos diverged")


def scenario_beam_search(comm):
    """Beam search ACROSS the process boundary: data=2 over 2
    single-device processes.  The per-step cache-reorder gather — the
    most layout-sensitive decode path (beams reindex their row's cache
    every step) — runs on batch-sharded rows, with ragged prompts'
    per-row offsets riding through the reorders.  Tokens AND scores
    must equal the process-local single-device oracle."""
    from chainermn_tpu.models import (
        init_transformer, make_beam_search_fn, shard_params,
    )
    from chainermn_tpu.parallel import MeshConfig

    assert jax.process_count() == 2 and len(jax.local_devices()) == 1
    cfg = _tiny_cfg()
    host = init_transformer(jax.random.PRNGKey(15), cfg)
    import jax.numpy as jnp

    lens = np.asarray([3, 1, 2, 3])
    padded = np.full((4, 3), 7, np.int32)
    rng = np.random.RandomState(16)
    for b, L in enumerate(lens):
        padded[b, 3 - L:] = rng.randint(0, cfg.vocab_size, L)
    pl = jnp.asarray(padded)
    kw = dict(beam_size=2, max_len=8, eos_id=5, length_penalty=0.6)

    one = MeshConfig(data=1, devices=[jax.local_devices()[0]])
    ref_t, ref_s = make_beam_search_fn(one, cfg, **kw)(
        shard_params(one, cfg, host), pl, prompt_lens=lens)

    mc = MeshConfig(data=2, devices=jax.devices())
    sh = mc.sharding(("data", "expert"))
    got_t, got_s = make_beam_search_fn(mc, cfg, **kw)(
        shard_params(mc, cfg, host), jax.device_put(pl, sh),
        prompt_lens=jax.device_put(jnp.asarray(lens, jnp.int32), sh))
    np.testing.assert_array_equal(
        _gather_rows(comm, got_t), np.asarray(ref_t),
        err_msg="cross-process beam tokens diverged")
    np.testing.assert_allclose(
        _gather_rows(comm, got_s, dtype=np.float32), np.asarray(ref_s),
        rtol=1e-5, atol=1e-5,
        err_msg="cross-process beam scores diverged")


def scenario_sp_ep_train(comm):
    """Sequence parallelism (ring attention's ppermute chain) and
    expert parallelism (Switch MoE's all-to-alls) ACROSS the process
    boundary: 2 processes x 1 device, seq=2 then expert=2 — the
    remaining collective kinds (ppermute-over-seq, all-to-all) join
    psum (tp_train) and pipe-ppermute (pp_train) in executed
    cross-process coverage.  Loss trajectories must equal the
    process-local single-device oracle."""
    import dataclasses

    from chainermn_tpu.parallel import MeshConfig

    assert jax.process_count() == 2 and len(jax.local_devices()) == 1
    base = _tiny_cfg()
    oracle = _local_oracle_losses(base)

    ring = dataclasses.replace(base, attention="ring")
    ring_losses = _tiny_transformer_losses(
        MeshConfig(seq=2, data=1, devices=jax.devices()), ring)
    np.testing.assert_allclose(ring_losses, oracle, rtol=1e-5, atol=1e-5,
                               err_msg="cross-process ring attention")
    all_ring = comm.allgather_obj(ring_losses)
    for other in all_ring[1:]:
        np.testing.assert_allclose(other, all_ring[0],
                                   rtol=1e-6, atol=1e-6)

    moe = dataclasses.replace(base, moe=True, n_experts=2)
    moe_oracle = _local_oracle_losses(moe)
    losses = _tiny_transformer_losses(
        MeshConfig(expert=2, data=1, devices=jax.devices()), moe)
    # step 1 is reduction-order-exact; later steps tolerate top-1
    # routing flips (a near-tie router logit can resolve differently
    # across mesh layouts after the first update — discrete routing,
    # not a transport bug; observed delta ~1e-3 relative)
    np.testing.assert_allclose(losses[:1], moe_oracle[:1],
                               rtol=1e-5, atol=1e-5,
                               err_msg="cross-process MoE all-to-all")
    np.testing.assert_allclose(losses, moe_oracle, rtol=5e-3,
                               err_msg="cross-process MoE diverged "
                                       "beyond routing-flip noise")

    all_losses = comm.allgather_obj(losses)
    for other in all_losses[1:]:
        np.testing.assert_allclose(other, all_losses[0],
                                   rtol=1e-6, atol=1e-6)


def scenario_vocab_tp_loss_chunk_train(comm):
    """Chunked-vocab cross-entropy COMPOSED with Megatron vocab TP,
    across the process boundary: model=2 over 2 single-device
    processes, so the per-chunk CE reductions and the vocab-sharded
    embedding/head collectives are real cross-process traffic.  Both
    features are exact rearrangements of the softmax, so the loss
    trajectory must equal a process-local single-device oracle with
    NEITHER enabled."""
    from chainermn_tpu.parallel import MeshConfig

    assert jax.process_count() == 2 and len(jax.local_devices()) == 1
    oracle = _local_oracle_losses(_tiny_cfg())
    losses = _tiny_transformer_losses(
        MeshConfig(model=2, data=1, devices=jax.devices()),
        _tiny_cfg(loss_chunk=8, vocab_parallel=True))
    np.testing.assert_allclose(losses, oracle, rtol=1e-5, atol=1e-5)
    all_losses = comm.allgather_obj(losses)
    for other in all_losses[1:]:
        np.testing.assert_allclose(other, all_losses[0],
                                   rtol=1e-6, atol=1e-6)


def scenario_alltoall_window(comm):
    """8-process alltoall_obj: the windowed pairwise-lane path (send
    look-ahead over the KV channel) must deliver every payload to the
    right peer at window sizes below, at, and above the round count
    (n-1 = 7) — window=1 being the strictly-alternating legacy
    pattern."""
    r = comm.inter_rank
    n = comm.inter_size
    assert n == 8, n
    for window in (1, 3, 8):
        sent = [{"from": r, "to": j, "w": window,
                 "pad": "x" * (50 * r + j)} for j in range(n)]
        got = comm.alltoall_obj(sent, window=window)
        assert [g["from"] for g in got] == list(range(n)), got
        assert all(g["to"] == r and g["w"] == window for g in got), got
        assert [len(g["pad"]) for g in got] == [50 * p + r
                                                for p in range(n)], got
    comm.barrier()


def scenario_elastic_membership(comm):
    """Membership epochs + generation fencing across REAL processes,
    entirely on the coordination-service KV store (no XLA collectives —
    membership must be agreeable exactly when the data plane died):
    survivors agree an epoch-numbered record collectively, fence their
    object channels to it, and a message published under the OLD
    generation is REJECTED (typed ``StaleGenerationError``) while the
    lane stays usable for current-generation traffic."""
    from chainermn_tpu.communicators._obj_channel import (
        KVObjectChannel,
        StaleGenerationError,
    )
    from chainermn_tpu.training.elastic import ElasticMembership

    me, n = comm.inter_rank, comm.inter_size
    boot = KVObjectChannel(tag="elastic-boot")
    # share the durable membership dir without array collectives
    path = boot.allgather(
        tempfile.mkdtemp(prefix="elastic_mp_") if me == 0 else None,
        list(range(n)), me)[0]

    m = ElasticMembership(comm, path=path)
    rec = m.agree()
    assert rec.epoch == 1 and rec.world_size == n, rec
    assert rec.members == list(range(n)), rec
    assert rec.rank_of(me) == me

    # rank 0 publishes BEFORE fencing — the pre-resize incarnation's
    # traffic, still sitting on the store when the new epoch starts
    chan = KVObjectChannel(tag="elastic-data")
    if me == 0:
        chan.send("stale-traffic", src=0, dst=1)
    m.fence(chan)
    assert chan.generation == rec.epoch
    if me == 0:
        # post-fence traffic rides the agreed generation
        chan.send({"epoch": rec.epoch}, src=0, dst=1)
    if me == 1:
        try:
            got = chan.recv(src=0, dst=1)
            raise AssertionError(
                f"stale-generation message was consumed: {got!r}")
        except StaleGenerationError:
            pass
        # the lane advanced past the rejected message — the fenced
        # world's own traffic is delivered normally
        assert chan.recv(src=0, dst=1) == {"epoch": 1}

    # a relaunch (fresh membership object, persisted file) bumps the
    # epoch past every incarnation that ever agreed one
    rec2 = ElasticMembership(comm, path=path).agree()
    assert rec2.epoch == 2, rec2
    rows = boot.allgather((rec.epoch, rec2.epoch), list(range(n)), me)
    assert all(r == (1, 2) for r in rows), rows


def scenario_preemption_sigterm(comm):
    """The PreemptionCheckpointer end-to-end FaultPlan drill: a REAL
    ``SIGTERM`` on ONE process only → the preemption flag OR-reduces
    collectively → both ranks save the SAME iteration and stop clean →
    resume bitwise-matches an uninterrupted run.

    Deliberately touches no cross-process XLA collectives: each process
    trains on its own local device over identical data (states are
    bitwise-identical by construction) while the flag OR-reduce,
    checkpoint agreement, and barriers ride the coordination-service KV
    channel — the preemption path must work exactly where the data
    plane cannot."""
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.communicators._obj_channel import KVObjectChannel
    from chainermn_tpu.extensions import (
        PreemptionCheckpointer,
        create_multi_node_checkpointer,
    )
    from chainermn_tpu.models import init_mlp, mlp_apply, \
        softmax_cross_entropy
    from chainermn_tpu.testing import FaultInjector, FaultPlan

    me, n = comm.inter_rank, comm.inter_size

    class KVComm:
        """Control-plane communicator facade over the KV store only."""

        def __init__(self, tag):
            self._chan = KVObjectChannel(tag=tag)

        inter_rank = property(lambda self: jax.process_index())
        inter_size = property(lambda self: jax.process_count())
        size = property(lambda self: jax.process_count())
        mesh = None

        def allgather_obj(self, obj):
            return self._chan.allgather(
                obj, list(range(self.inter_size)), self.inter_rank)

        def barrier(self):
            self.allgather_obj(None)

    boot = KVObjectChannel(tag="presig-boot")
    path = boot.allgather(
        tempfile.mkdtemp(prefix="presig_") if me == 0 else None,
        list(range(n)), me)[0]

    local = cmn.create_communicator(
        "tpu_xla", devices=jax.local_devices())
    rng = np.random.RandomState(0)      # identical data on every rank
    data = [(rng.randn(4).astype(np.float32), np.int32(i % 2))
            for i in range(64)]

    def make_trainer(out, stop=12):
        it = cmn.SerialIterator(data, 16, shuffle=True, seed=5)
        params = init_mlp(jax.random.PRNGKey(0), [4, 8, 2])
        opt = cmn.create_multi_node_optimizer(optax.sgd(0.05), local)

        def loss_fn(p, x, y):
            return softmax_cross_entropy(mlp_apply(p, x), y)

        upd = cmn.StandardUpdater(it, opt, loss_fn, params, local)
        return cmn.Trainer(upd, (stop, "iteration"),
                           out=os.path.join(path, out))

    # arm A: the uninterrupted oracle
    ref = make_trainer("ref")
    ref.run()
    assert ref.updater.iteration == 12
    ref_params = jax.tree.map(np.asarray, ref.updater.params)

    # arm B: rank 0 gets a real SIGTERM at iteration 4; everyone else
    # learns of it through the collective flag reduce on the next tick
    kv1 = KVComm("presig-cp1")
    t1 = make_trainer("drill")
    cp = create_multi_node_checkpointer(
        kv1, os.path.join(path, "ckpt"))
    t1.extend(PreemptionCheckpointer(cp, kv1))
    inj = FaultInjector(
        FaultPlan(sigterm_at_iteration=4, sigterm_rank=0), comm=kv1)
    t1.extend(inj)
    t1.run()
    if me == 0:
        assert ("sigterm", 4) in inj.fired, inj.fired
    else:
        assert not inj.fired, inj.fired
    assert "preemption" in (t1.stop_reason or ""), t1.stop_reason
    assert t1.updater.iteration == 5, t1.updater.iteration
    iters = kv1.allgather_obj(sorted(cp._local_iterations()))
    assert all(x == [5] for x in iters), iters

    # arm C: resume and finish — bitwise vs the oracle
    kv2 = KVComm("presig-cp2")
    t2 = make_trainer("resume")
    cp2 = create_multi_node_checkpointer(
        kv2, os.path.join(path, "ckpt"))
    assert cp2.maybe_load(t2.updater, t2) == 5
    assert cp2.last_resume_mode == "exact"
    t2.run()
    assert t2.updater.iteration == 12
    for a, b in zip(jax.tree.leaves(ref_params),
                    jax.tree.leaves(jax.tree.map(
                        np.asarray, t2.updater.params))):
        np.testing.assert_array_equal(
            a, b, err_msg="resumed params differ from the "
                          "uninterrupted run")
    kv2.barrier()


def scenario_resize_live(comm):
    """The LIVE-resize control plane across REAL processes, KV-only
    (the data plane may be mid-reconfiguration, so nothing here may
    ride an array collective): an intent posted by ONE rank
    (``post_resize_intent``) is seen by every rank, the OR-agreement
    resolves identically everywhere, the membership epoch bumps and
    fences channel generations so pre-resize traffic is REJECTED, and
    the consumed intent is cleared.  The mesh re-formation itself is
    single-process (tests/extension_tests/test_live_resize.py) or
    TPU-gated — this drill is the cross-process half."""
    from chainermn_tpu.communicators._obj_channel import (
        KVObjectChannel,
        StaleGenerationError,
    )
    from chainermn_tpu.training.elastic import (
        ElasticMembership,
        ResizeController,
        post_resize_intent,
    )

    me, n = comm.inter_rank, comm.inter_size
    boot = KVObjectChannel(tag="resize-boot")
    path = boot.allgather(
        tempfile.mkdtemp(prefix="resize_mp_") if me == 0 else None,
        list(range(n)), me)[0]
    membership = ElasticMembership(comm, path=path)
    ctrl = ResizeController(
        comm_factory=lambda w: comm, optimizer_factory=lambda c: None,
        membership=membership)

    # only the LAST rank posts the intent — every rank must still see
    # it (external tooling posts from wherever it runs)
    assert ctrl._kv_intent(comm) is None
    # every rank has looked before the poster posts: without this
    # barrier a slow rank reads the fast poster's intent as "stale"
    _kv_barrier(comm, boot)
    if me == n - 1:
        post_resize_intent(n, reason="mp drill")
    _kv_barrier(comm, boot)
    assert ctrl._kv_intent(comm) == n

    # the controller's boundary agreement: a rank with NO local intent
    # resolves to the same world as the poster.  KV-only here — this
    # container's CPU backend has no cross-process array collectives,
    # which is exactly the situation the control plane must survive
    mine = ctrl._kv_intent(comm) if me == n - 1 else None
    rows = boot.allgather(mine, list(range(n)), me)
    seen = [r for r in rows if r is not None]
    assert seen and max(seen) == n, rows

    # epoch + fence: the step the live resize performs before the mesh
    # re-forms — stale-generation traffic must bounce afterwards
    rec = membership.agree()
    assert rec.epoch == 1 and rec.members == list(range(n)), rec
    chan = KVObjectChannel(tag="resize-data")
    if me == 0:
        chan.send("pre-resize", src=0, dst=1)   # old-generation traffic
    membership.fence(chan)
    assert chan.generation == rec.epoch
    if me == 0:
        chan.send({"epoch": rec.epoch}, src=0, dst=1)
    if me == 1:
        try:
            got = chan.recv(src=0, dst=1)
            raise AssertionError(
                f"pre-resize message survived the fence: {got!r}")
        except StaleGenerationError:
            pass
        assert chan.recv(src=0, dst=1) == {"epoch": 1}

    # the agreed intent is consumed by EVERY rank (idempotent delete —
    # the controller clears before its collectives so no rank can
    # re-read a stale intent on its next cadence tick)
    ctrl._clear_kv_intent(comm)
    _kv_barrier(comm, boot)
    assert ctrl._kv_intent(comm) is None
    _kv_barrier(comm, boot)


SCENARIOS = {
    name[len("scenario_"):]: fn
    for name, fn in list(globals().items())
    if name.startswith("scenario_")
}


def main():
    addr, n, i, scenario = (sys.argv[1], int(sys.argv[2]), int(sys.argv[3]),
                            sys.argv[4])
    import chainermn_tpu

    chainermn_tpu.init_distributed(
        coordinator_address=addr, num_processes=n, process_id=i)
    comm = chainermn_tpu.create_communicator("tpu_xla")
    # "a+b+c": several scenarios in one launch of the processes, each
    # with its own marker (the light ones share a launch: a process's
    # start and its join cost more than their work)
    for name in scenario.split("+"):
        SCENARIOS[name](comm)
        print(f"WORKER_OK {i} {name}", flush=True)


if __name__ == "__main__":
    main()
