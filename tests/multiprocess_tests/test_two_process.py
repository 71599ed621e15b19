"""Two-process cluster tests — every ``inter_size > 1`` code path that
single-process tests cannot reach, run as real OS processes (the
reference's ``mpiexec -n 2 pytest``; SURVEY.md §4)."""

import pytest


# the scenarios whose own work is a second or two, of which a launch of
# two processes (start, join the cluster, exit) was four fifths: they
# share ONE launch, each with its own OK marker from each worker (PR 42:
# 11 launches of 5-9 s in the tier-1 run became one)
_LIGHT = ("topology",
          "obj_collectives",
          "p2p_obj",
          "array_collectives",
          "scatter_dataset",
          "evaluator",
          "broadcast_iterator",
          "observation_aggregator",
          "snapshot",
          "allreduce_persistent",
          "dp_train")


@pytest.fixture(scope="module")
def light(mp_run_shared):
    """The light scenarios, run one after the other by the same two
    processes; what each of their tests asserts is that its own ran to
    its marker on both."""
    return mp_run_shared(_LIGHT)


@pytest.fixture(scope="module")
def trains(mp_run_shared):
    """The train scenarios of two processes with a device each, one
    launch: three of the four hold their losses to the same
    process-local oracle, which the workers compile once
    (``_local_oracle_losses``)."""
    return mp_run_shared(("tp_train", "vocab_tp_loss_chunk_train",
                          "sp_ep_train", "fsdp_train"))


@pytest.fixture(scope="module")
def decodes(mp_run_shared):
    """The decode scenarios of two processes with a device each, one
    launch; ``decode`` and ``lookup_decode`` are held to
    ``make_generate_fn``'s greedy tokens of the same tiny model, plain
    and padded with an eos: those two oracle programs are built once
    (``_local_generate_fn``)."""
    return mp_run_shared(("decode", "lookup_decode", "speculative_decode",
                          "speculative_sampling", "beam_search"))


@pytest.mark.multiprocess
class TestTwoProcess:
    def test_topology_contract(self, light):
        light("topology")

    def test_obj_collectives(self, light):
        light("obj_collectives")

    def test_p2p_obj_channel(self, light):
        light("p2p_obj")

    def test_array_collectives(self, light):
        light("array_collectives")

    def test_scatter_dataset(self, light):
        light("scatter_dataset")

    def test_checkpoint_agreement_resume(self, mp_run):
        mp_run("checkpoint")

    def test_checkpoint_async(self, mp_run):
        mp_run("checkpoint_async")

    def test_fallback_resume(self, mp_run):
        # one rank's shard bytes flipped -> every process falls back to
        # the previous verified set; damaged file quarantined
        mp_run("fallback_resume")

    @pytest.mark.drill
    def test_watchdog_stall(self, mp_run):
        # rank 1 stalls past the threshold: self-report + survivor
        # detection through the cross-process KV heartbeats
        mp_run("watchdog_stall", timeout=240)

    def test_evaluator_averaging(self, light):
        light("evaluator")

    def test_broadcast_iterator(self, light):
        light("broadcast_iterator")

    def test_observation_aggregator(self, light):
        light("observation_aggregator")

    def test_split(self, mp_run):
        # 4 processes: each even/odd subgroup spans 2 processes, forcing
        # the KV group collectives (whole-world ones would deadlock)
        mp_run("split", nprocs=4)

    def test_vocab_tp_loss_chunk_train(self, trains):
        # chunked-vocab CE + vocab-parallel embedding over model=2
        # spanning processes, loss-equal to the process-local oracle
        trains("vocab_tp_loss_chunk_train")

    def test_alltoall_window(self, mp_run):
        # 8 processes: the windowed pairwise-lane alltoall at window
        # sizes below, at, and above the round count
        mp_run("alltoall_window", nprocs=8, timeout=280)

    def test_snapshot(self, light):
        light("snapshot")

    def test_allreduce_persistent(self, light):
        light("allreduce_persistent")

    def test_dp_train_step(self, light):
        light("dp_train")

    def test_preemption_collective_flag(self, mp_run):
        mp_run("preemption")

    @pytest.mark.drill
    def test_elastic_membership(self, mp_run):
        # epoch-numbered membership agreement + generation fencing over
        # the KV store only; a stale-generation message is REJECTED
        mp_run("elastic_membership", timeout=240)

    @pytest.mark.drill
    def test_preemption_sigterm_drill(self, mp_run):
        # real SIGTERM on one process -> OR-reduced collective save ->
        # both ranks stop clean -> resume bitwise-matches uninterrupted
        mp_run("preemption_sigterm", timeout=280)

    @pytest.mark.drill
    def test_resize_live_control_plane(self, mp_run):
        # live-resize coordination KV-only: one rank's posted intent
        # agreed by all -> epoch bump + generation fence rejects
        # pre-resize traffic -> intent consumed once
        mp_run("resize_live", timeout=240)

    def test_zero1_checkpoint(self, mp_run):
        mp_run("zero1_checkpoint")

    def test_fsdp_train(self, trains):
        trains("fsdp_train")

    def test_tp_train(self, trains):
        # per-layer TP psum crosses the process boundary (model=2 over
        # 2 single-device processes)
        trains("tp_train")

    def test_pp_train(self, mp_run):
        # 2 procs x 2 devices: pipe (mesh-major) ppermute crosses the
        # process boundary; model stays local; + the model=2,data=2 shape
        mp_run("pp_train", devices_per_proc=2, timeout=280)

    def test_sp_ep_train(self, trains):
        # ring-attention ppermute chain and MoE all-to-alls cross the
        # process boundary (seq=2 / expert=2 over 2 processes)
        trains("sp_ep_train")

    def test_decode(self, decodes):
        # per-token seq-KV softmax merges and vocab-parallel lookup/
        # gather collectives cross the process boundary; tokens equal
        # the process-local oracle exactly
        decodes("decode")

    def test_speculative_decode(self, decodes):
        # the acceptance pmin + verify-chunk collectives run inside a
        # cross-process while_loop; tokens equal the local oracle
        decodes("speculative_decode")

    def test_speculative_sampling(self, decodes):
        # acceptance pmin + shard-decorrelated keys + while-loop key
        # carry across the boundary; same-key determinism
        decodes("speculative_sampling")

    def test_lookup_decode(self, decodes):
        # the draft-free proposer: row-local n-gram matching, shared
        # acceptance pmin and verify chunk across the boundary; plus
        # the padded+eos composition phase
        decodes("lookup_decode")

    def test_beam_search(self, decodes):
        # the per-step cache-reorder gather over batch-sharded ragged
        # rows; tokens AND scores equal the local oracle
        decodes("beam_search")

    def test_shuffle_datablock(self, mp_run):
        mp_run("shuffle_datablock")

    def test_shuffle_datablock_four_process(self, mp_run):
        # n>2 exercises the staggered pairwise exchange rounds
        mp_run("shuffle_datablock", nprocs=4)
