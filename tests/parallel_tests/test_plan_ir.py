"""Collective-plan IR (``ops/plan_ir.py``): program data model, and the
per-pattern bitwise parity suite — every enumerated candidate of every
pattern must move EXACTLY the bytes the legacy hard-coded lowering
moved, on the 8-device CPU mesh, including empty/int/bool leaves and
single-device degenerate meshes.

Parity here is ``np.array_equal`` (bitwise), not allclose: native
candidates are pure data movement, and wire candidates are compared to
the LEGACY wire path (same cast, same exemptions), so any mismatch is a
lowering bug, not noise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import chainermn_tpu  # noqa: F401 - installs the shard_map compat shim
from chainermn_tpu.ops import plan_ir
from chainermn_tpu.parallel.expert import expert_parallel_moe
from chainermn_tpu.parallel.fsdp import fsdp_gather
from chainermn_tpu.parallel.pipeline import pipeline_apply
from chainermn_tpu.parallel.ring_attention import ring_attention
from chainermn_tpu.utils.programs import (
    ProgramLedger,
    ledger_jit,
    set_ledger,
)

AX = "world"


def flat_mesh():
    return Mesh(np.array(jax.devices()), (AX,))


def run_spmd(fn, tree, mesh=None, spec=None):
    """Run ``fn`` on per-device copies of ``tree`` (world-stacked
    leading axis) and return the (identical) per-device outputs."""
    mesh = mesh if mesh is not None else flat_mesh()
    n = int(np.prod([s for s in np.asarray(mesh.devices).shape]))
    spec = spec if spec is not None else P(AX)

    def body(g):
        local = jax.tree.map(lambda a: a[0], g)
        out = fn(local)
        return jax.tree.map(lambda a: a[None], out)

    stacked = jax.tree.map(lambda a: jnp.stack([a] * n), tree)
    # jitted, as every caller in the package is: eager shard_map on this
    # jax asserts on a zero-size output (XLA reports it replicated)
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=spec,
                                 out_specs=spec))(stacked)


def assert_bitwise(got, want, label=""):
    gl, wl = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(gl) == len(wl)
    for g, w in zip(gl, wl):
        g, w = np.asarray(g), np.asarray(w)
        assert g.shape == w.shape and g.dtype == w.dtype, \
            (label, g.shape, w.shape, g.dtype, w.dtype)
        assert np.array_equal(g, w), label


# --------------------------------------------------------------------- #
# program data model
# --------------------------------------------------------------------- #


class TestProgramData:
    def test_step_and_program_roundtrip(self):
        prog = plan_ir.PlanProgram(
            "fsdp_gather", "fused/flat/bfloat16",
            (plan_ir.step("cast_wire", dtype="bfloat16"),
             plan_ir.step("fuse"),
             plan_ir.step("all_gather", axis="main")))
        d = prog.to_dict()
        back = plan_ir.PlanProgram.from_dict(d)
        assert back == prog
        assert back.to_dict() == d
        assert prog.wire_dtype == "bfloat16"

    def test_step_validates_op(self):
        with pytest.raises(ValueError, match="unknown plan primitive"):
            plan_ir.step("bogus_op")

    def test_ensure_program_accepts_dict_and_plan_like(self):
        prog = plan_ir.enumerate_pattern_programs("ring_permute")[0]
        assert plan_ir.ensure_program(prog.to_dict()) == prog

        class PlanLike:
            program = prog.to_dict()

        assert plan_ir.ensure_program(PlanLike(), "ring_permute") == prog
        with pytest.raises(ValueError, match="pattern"):
            plan_ir.ensure_program(prog, "fsdp_gather")

    def test_describe_payload_skips_none_dims(self):
        tree = {"w": jnp.zeros((4, 8)), "s": jnp.zeros((3,))}
        descs = plan_ir.describe_payload(tree, {"w": 1, "s": None})
        by_shape = {d.shape: d for d in descs}
        assert by_shape[(4, 8)].layout == 1
        assert by_shape[(3,)].layout is None

    def test_baseline_first_contract(self):
        """The FIRST enumerated program of every pattern is the
        legacy-equivalent native baseline — the parity reference and
        the autotuner's always-probed candidate."""
        firsts = {
            "fsdp_gather": "per_leaf/flat/native",
            "moe_all_to_all": "single/native",
            "ring_permute": "separate/native",
            "pipeline_edge": "direct/native",
        }
        kw = {"moe_all_to_all": {"shape": (8, 8, 4)}}
        for pattern, label in firsts.items():
            progs = plan_ir.enumerate_pattern_programs(
                pattern, **kw.get(pattern, {}))
            assert progs[0].label == label
            assert progs[0].wire_dtype is None


# --------------------------------------------------------------------- #
# fsdp gather
# --------------------------------------------------------------------- #


def _fsdp_payload():
    key = jax.random.PRNGKey(0)
    params = {
        "w": jax.random.normal(key, (8, 4, 6), jnp.float32),
        "b": jnp.arange(16, dtype=jnp.int32).reshape(8, 2),
        "flag": jnp.array([True, False] * 4).reshape(8, 1),
        "empty": jnp.zeros((8, 0, 3), jnp.float32),
        "scale": jnp.ones((3,), jnp.float32),   # unsharded passthrough
    }
    dims = {"w": 0, "b": 0, "flag": 0, "empty": 1, "scale": None}
    return params, dims


class TestFsdpGatherParity:
    def test_flat_programs_bitwise(self):
        params, dims = _fsdp_payload()
        want = run_spmd(
            lambda p: fsdp_gather(p, dims, axis_name=AX), params)
        want_wire = run_spmd(
            lambda p: fsdp_gather(p, dims, axis_name=AX,
                                  wire_dtype=jnp.bfloat16), params)
        progs = plan_ir.enumerate_pattern_programs(
            "fsdp_gather", wire_dtypes=(None, "bfloat16"))
        assert len(progs) == 4
        for prog in progs:
            got = run_spmd(
                lambda p, prog=prog: fsdp_gather(
                    p, dims, axis_name=AX, plan=prog), params)
            ref = want if prog.wire_dtype is None else want_wire
            assert_bitwise(got, ref, prog.label)

    def test_wire_exempts_non_float_leaves(self):
        """The satellite hazard: int/bool through a bf16 wire is silent
        corruption.  Both the legacy path and every IR wire candidate
        must ship non-float leaves at their native dtype — bitwise
        equal to the no-wire gather."""
        params, dims = _fsdp_payload()
        want = run_spmd(
            lambda p: fsdp_gather(p, dims, axis_name=AX), params)
        got = run_spmd(
            lambda p: fsdp_gather(p, dims, axis_name=AX,
                                  wire_dtype=jnp.bfloat16), params)
        for k in ("b", "flag"):
            assert_bitwise(got[k], want[k], f"legacy wire {k}")
        prog = [p for p in plan_ir.enumerate_pattern_programs(
            "fsdp_gather", wire_dtypes=("bfloat16",))
            if p.label == "fused/flat/bfloat16"][0]
        got_ir = run_spmd(
            lambda p: fsdp_gather(p, dims, axis_name=AX, plan=prog),
            params)
        for k in ("b", "flag"):
            assert_bitwise(got_ir[k], want[k], f"ir wire {k}")

    def test_hierarchical_bitwise_vs_axis_tuple(self):
        """Two-stage intra→inter gather equals the flat gather over the
        combined axis tuple (row-major device order) — bitwise."""
        devs = np.array(jax.devices()).reshape(2, 4)
        hmesh = Mesh(devs, ("inter", AX))
        spec = P(("inter", AX))
        key = jax.random.PRNGKey(1)
        params = {"w": jax.random.normal(key, (8, 16, 6), jnp.float32),
                  "b": jnp.arange(16, dtype=jnp.int32)}
        dims = {"w": 1, "b": 0}
        want = run_spmd(
            lambda p: fsdp_gather(p, dims, axis_name=("inter", AX)),
            params, mesh=hmesh, spec=spec)
        progs = [p for p in plan_ir.enumerate_pattern_programs(
            "fsdp_gather", allow_hierarchical=True)
            if "hier" in p.label]
        assert len(progs) == 2
        for prog in progs:
            got = run_spmd(
                lambda p, prog=prog: fsdp_gather(
                    p, dims, axis_name=AX, plan=prog,
                    inter_axis_name="inter"),
                params, mesh=hmesh, spec=spec)
            assert_bitwise(got, want, prog.label)

    def test_single_device_mesh(self):
        mesh = Mesh(np.array(jax.devices()[:1]), (AX,))
        params, dims = _fsdp_payload()
        want = run_spmd(
            lambda p: fsdp_gather(p, dims, axis_name=AX),
            params, mesh=mesh)
        for prog in plan_ir.enumerate_pattern_programs("fsdp_gather"):
            got = run_spmd(
                lambda p, prog=prog: fsdp_gather(
                    p, dims, axis_name=AX, plan=prog),
                params, mesh=mesh)
            assert_bitwise(got, want, prog.label)

    def test_unbound_inter_axis_raises(self):
        params, dims = _fsdp_payload()
        prog = [p for p in plan_ir.enumerate_pattern_programs(
            "fsdp_gather", allow_hierarchical=True)
            if "hier" in p.label][0]
        with pytest.raises(ValueError, match="bound no such axis"):
            run_spmd(
                lambda p: fsdp_gather(p, dims, axis_name=AX, plan=prog),
                params)


# --------------------------------------------------------------------- #
# moe all-to-all
# --------------------------------------------------------------------- #


class TestMoeAllToAllParity:
    def test_programs_bitwise_both_directions(self):
        key = jax.random.PRNGKey(2)
        slots = jax.random.normal(key, (8, 4, 16), jnp.float32)

        def legacy(x):
            h = lax.all_to_all(x, AX, split_axis=0, concat_axis=1,
                               tiled=True)
            return lax.all_to_all(h * 2.0, AX, split_axis=1,
                                  concat_axis=0, tiled=True)

        want = run_spmd(legacy, slots)
        progs = plan_ir.enumerate_pattern_programs(
            "moe_all_to_all", shape=(8, 4, 16))
        assert [p.label for p in progs] == \
            ["single/native", "split2/native", "split4/native",
             "split8/native"]
        for prog in progs:
            def ir(x, prog=prog):
                h = plan_ir.lower_moe_all_to_all(
                    prog, x, axis_name=AX, split_axis=0, concat_axis=1)
                return plan_ir.lower_moe_all_to_all(
                    prog, h * 2.0, axis_name=AX, split_axis=1,
                    concat_axis=0)

            assert_bitwise(run_spmd(ir, slots), want, prog.label)

    def test_int_payload_rides_wire_natively(self):
        slots = jnp.arange(8 * 2 * 8, dtype=jnp.int32).reshape(8, 2, 8)
        want = run_spmd(
            lambda x: lax.all_to_all(x, AX, split_axis=0, concat_axis=1,
                                     tiled=True), slots)
        progs = plan_ir.enumerate_pattern_programs(
            "moe_all_to_all", shape=(8, 2, 8),
            wire_dtypes=("bfloat16",))
        for prog in progs:
            got = run_spmd(
                lambda x, prog=prog: plan_ir.lower_moe_all_to_all(
                    prog, x, axis_name=AX, split_axis=0, concat_axis=1),
                slots)
            assert_bitwise(got, want, prog.label)

    def test_expert_moe_end_to_end(self):
        """The ported call site: ``expert_parallel_moe(a2a_plan=...)``
        is bitwise identical to the legacy lowering."""
        key = jax.random.PRNGKey(3)
        k1, k2, k3 = jax.random.split(key, 3)
        D, E, N = 8, 8, 16
        x = jax.random.normal(k1, (N, D), jnp.float32)
        router_w = jax.random.normal(k2, (D, E), jnp.float32)
        expert_params = {"w": jax.random.normal(k3, (1, D, D),
                                                jnp.float32)}

        def expert_fn(p, tokens):
            return tokens @ p["w"]

        def moe(plan):
            def f(tree):
                out, aux = expert_parallel_moe(
                    tree["x"], tree["r"], tree["ep"], expert_fn,
                    axis_name=AX, a2a_plan=plan)
                return {"out": out, "aux": aux}
            return f

        tree = {"x": x, "r": router_w, "ep": expert_params}
        want = run_spmd(moe(None), tree)
        for prog in plan_ir.enumerate_pattern_programs(
                "moe_all_to_all", shape=(E, 3, D)):
            # capacity = ceil(1.25 * 16 / 8) = 3 slots
            got = run_spmd(moe(prog), tree)
            assert_bitwise(got, want, prog.label)


# --------------------------------------------------------------------- #
# ring permute
# --------------------------------------------------------------------- #


class TestRingPermuteParity:
    def test_programs_bitwise(self):
        key = jax.random.PRNGKey(4)
        kv = {"k": jax.random.normal(key, (2, 5), jnp.float32),
              "v": jnp.arange(6, dtype=jnp.int32).reshape(2, 3)}
        ring = [(i, (i + 1) % 8) for i in range(8)]
        want = run_spmd(
            lambda t: jax.tree.map(
                lambda x: lax.ppermute(x, AX, perm=ring), t), kv)
        for prog in plan_ir.enumerate_pattern_programs("ring_permute"):
            def ir(t, prog=prog):
                k, v = plan_ir.lower_ring_permute(
                    prog, (t["k"], t["v"]), axis_name=AX)
                return {"k": k, "v": v}

            assert_bitwise(run_spmd(ir, kv), want, prog.label)

    def test_ring_attention_end_to_end(self):
        key = jax.random.PRNGKey(5)
        kq, kk, kv_ = jax.random.split(key, 3)
        q = jax.random.normal(kq, (1, 4, 2, 8), jnp.float32)
        k = jax.random.normal(kk, (1, 4, 2, 8), jnp.float32)
        v = jax.random.normal(kv_, (1, 4, 2, 8), jnp.float32)
        tree = {"q": q, "k": k, "v": v}

        def attn(plan):
            return lambda t: ring_attention(
                t["q"], t["k"], t["v"], axis_name=AX, causal=True,
                permute_plan=plan)

        want = run_spmd(attn(None), tree)
        for prog in plan_ir.enumerate_pattern_programs("ring_permute"):
            assert_bitwise(run_spmd(attn(prog), tree), want, prog.label)


# --------------------------------------------------------------------- #
# pipeline edges
# --------------------------------------------------------------------- #


class TestPipelineEdgeParity:
    @pytest.mark.parametrize("shift,wrap", [(1, False), (-1, False),
                                            (1, True), (-1, True)])
    def test_programs_bitwise(self, shift, wrap):
        act = jax.random.normal(jax.random.PRNGKey(6), (3, 4),
                                jnp.float32)
        if shift == 1:
            perm = [(i, i + 1) for i in range(7)]
            perm += [(7, 0)] if wrap else []
        else:
            perm = [(i + 1, i) for i in range(7)]
            perm += [(0, 7)] if wrap else []
        want = run_spmd(lambda x: lax.ppermute(x, AX, perm=perm), act)
        for prog in plan_ir.enumerate_pattern_programs("pipeline_edge"):
            got = run_spmd(
                lambda x, prog=prog: plan_ir.lower_pipeline_edge(
                    prog, x, axis_name=AX, shift=shift, wrap=wrap), act)
            assert_bitwise(got, want, (prog.label, shift, wrap))

    def test_pipeline_apply_end_to_end(self):
        rng = np.random.RandomState(7)
        dim, B = 4, 16
        stacked = {
            "w": jnp.asarray(rng.randn(8, dim, dim).astype(np.float32)
                             * 0.3),
            "b": jnp.asarray(rng.randn(8, dim).astype(np.float32)
                             * 0.1),
        }
        x = jnp.asarray(rng.randn(B, dim).astype(np.float32))

        def stage(p, h):
            return jnp.tanh(h @ p["w"] + p["b"])

        def run_case(plan):
            mesh = flat_mesh()
            return jax.shard_map(
                lambda p, xs: pipeline_apply(
                    stage, p, xs, axis_name=AX, num_microbatches=8,
                    edge_plan=plan),
                mesh=mesh, in_specs=(P(AX), P()),
                out_specs=P())(stacked, x)

        want = run_case(None)
        for prog in plan_ir.enumerate_pattern_programs("pipeline_edge"):
            assert_bitwise(run_case(prog), want, prog.label)


# --------------------------------------------------------------------- #
# ledger invariant
# --------------------------------------------------------------------- #


class TestLedgerInvariant:
    def test_ir_lowered_program_zero_steady_retraces(self):
        """The PR 15 invariant extends to IR-lowered programs: a
        ledger-labelled jit wrapping a plan lowering compiles once and
        never retraces at steady state."""
        led = ProgramLedger(enabled=True)
        prev = set_ledger(led)
        try:
            mesh = flat_mesh()
            params, dims = _fsdp_payload()
            prog = plan_ir.enumerate_pattern_programs("fsdp_gather")[1]
            stacked = jax.tree.map(lambda a: jnp.stack([a] * 8), params)

            def body(g):
                local = jax.tree.map(lambda a: a[0], g)
                out = fsdp_gather(local, dims, axis_name=AX, plan=prog)
                return jax.tree.map(lambda a: a[None], out)

            fn = ledger_jit(
                jax.shard_map(body, mesh=mesh, in_specs=P(AX),
                              out_specs=P(AX)),
                label="plan_ir/fsdp_gather")
            for _ in range(3):
                jax.block_until_ready(fn(stacked))
            assert led.compiles("plan_ir/") == 1
            assert led.steady_retraces("plan_ir/") == 0
        finally:
            set_ledger(prev)
