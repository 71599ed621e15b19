"""Fused bucketed all-reduce (``ops/fused.py``): numerical parity with
the per-leaf path, packing-roundtrip exactness, the hierarchical 2-stage
lowering, and the collective-count budget pinned on compiled HLO.

Tolerance contract under test: the fused fp32 path computes the exact
same elementwise sums as per-leaf ``pmean`` (packing is a relayout, not
a re-association), so parity is tight; the bf16 ``wire_dtype`` path
carries the documented looser tolerance (one round-trip through an
8-bit-mantissa wire format).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from chainermn_tpu import ops
from chainermn_tpu.communicators._mesh_utils import make_world_mesh
from chainermn_tpu.ops import fused
from chainermn_tpu.utils.comm_model import (
    assert_fused_collectives,
    choose_bucket_bytes,
    collective_stats,
    fused_collective_budget,
)

AX = "world"
INTER = "inter"


@pytest.fixture(scope="module")
def mesh():
    return make_world_mesh(axis_name=AX)


def smap(mesh, fn):
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=P(AX), out_specs=P(AX)))


def stackmap(mesh, body):
    """World-stacked tree in/out; body sees one rank's local tree."""
    def outer(g):
        red = body(jax.tree.map(lambda a: a[0], g))
        return jax.tree.map(lambda a: a[None], red)
    return smap(mesh, outer)


def odd_tree(n_devices, dtype=np.float32, seed=0):
    """Mixed-shape tree with awkward sizes: scalars, odd vectors, a leaf
    big enough to straddle any small bucket, and a zero-size leaf."""
    rng = np.random.RandomState(seed)

    def leaf(*shape):
        return rng.randn(n_devices, *shape).astype(dtype)

    return {
        "scalar": leaf(),
        "tiny": leaf(3),
        "odd": leaf(17, 5),
        "mid": leaf(129),
        "big": leaf(301, 7),
        "empty": np.zeros((n_devices, 0, 4), dtype),
        "nest": {"a": leaf(11), "b": leaf(2, 2, 2)},
    }


def ref_mean(tree):
    return jax.tree.map(lambda a: np.asarray(a).mean(0), tree)


class TestPacking:
    def test_roundtrip_exact(self):
        """flatten → unflatten with no reduce is the identity — every
        leaf back bit-exact, ragged last bucket and empties included."""
        tree = jax.tree.map(lambda a: jnp.asarray(a[0]), odd_tree(1))
        for bucket in (64, 256, 1 << 20):
            buckets, spec = fused.flatten_buckets(tree, bucket_bytes=bucket)
            out = fused.unflatten_buckets(buckets, spec)
            assert jax.tree.structure(out) == jax.tree.structure(tree)
            for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
                assert a.dtype == b.dtype and a.shape == b.shape
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_bucket_count_respects_budget(self):
        """Arena slices are exact bucket_bytes (last ragged), direct
        leaves ride alone — total ≤ the advertised budget."""
        tree = jax.tree.map(lambda a: jnp.asarray(a[0]), odd_tree(1))
        total = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))
        # non-multiple-of-itemsize sizes included: choose_bucket_bytes
        # returns arbitrary sqrt-derived ints, and a floor-based element
        # threshold used to blow the budget for exactly those
        for bucket in (15, 128, 1000, 1024, 4097, 1 << 20):
            buckets, _ = fused.flatten_buckets(tree, bucket_bytes=bucket)
            assert len(buckets) <= fused_collective_budget(total, bucket)

    def test_mixed_dtypes_never_share_a_bucket(self):
        tree = {
            "w32": jnp.ones((7, 3), jnp.float32),
            "wbf": jnp.ones((5,), jnp.bfloat16),
            "more32": jnp.zeros((9,), jnp.float32),
        }
        buckets, spec = fused.flatten_buckets(tree, bucket_bytes=1 << 20)
        assert {b.dtype for b in buckets} == {jnp.dtype(jnp.float32),
                                             jnp.dtype(jnp.bfloat16)}
        out = fused.unflatten_buckets(buckets, spec)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_wire_dtype_recasts_on_unpack(self):
        tree = {"w": jnp.ones((4, 4), jnp.float32)}
        buckets, spec = fused.flatten_buckets(
            tree, bucket_bytes=1 << 20, wire_dtype=jnp.bfloat16)
        assert all(b.dtype == jnp.bfloat16 for b in buckets)
        out = fused.unflatten_buckets(buckets, spec)
        assert out["w"].dtype == jnp.float32

    def test_nonfloat_groups_exempt_from_wire_cast(self):
        """An int32/bool leaf round-tripped through bf16 is silently
        corrupted (8 mantissa bits); non-float groups must cross the
        wire in their NATIVE dtype even when wire_dtype is set."""
        tree = {
            "f32": jnp.ones((8,), jnp.float32),
            # values far past bf16's 256-integer exactness range
            "i32": jnp.asarray([1000003, -7654321, 1 << 20], jnp.int32),
            "flags": jnp.asarray([True, False, True]),
        }
        buckets, spec = fused.flatten_buckets(
            tree, bucket_bytes=1 << 20, wire_dtype=jnp.bfloat16)
        assert {jnp.dtype(b.dtype) for b in buckets} == {
            jnp.dtype(jnp.bfloat16),        # the float group, compressed
            jnp.dtype(jnp.int32),           # exempt
            jnp.dtype(jnp.bool_),           # exempt
        }
        out = fused.unflatten_buckets(buckets, spec)
        # the exempt groups survive BIT-EXACT (bf16 would have mangled
        # every one of these values)
        np.testing.assert_array_equal(np.asarray(out["i32"]),
                                      np.asarray(tree["i32"]))
        np.testing.assert_array_equal(np.asarray(out["flags"]),
                                      np.asarray(tree["flags"]))
        # a non-float wire_dtype never casts anything
        buckets, _ = fused.flatten_buckets(
            {"f": jnp.ones((4,), jnp.float32)}, bucket_bytes=1 << 20,
            wire_dtype=jnp.int16)
        assert buckets[0].dtype == jnp.float32


class TestParity:
    """fused_allreduce vs the per-leaf pmean it replaces, on the
    8-device virtual CPU mesh, small buckets to force arena splits,
    straddles, and the ragged last bucket."""

    BUCKET = 1024  # bytes — tiny on purpose: many buckets, ragged tail

    def test_fp32_matches_per_leaf(self, mesh):
        n = mesh.devices.size
        tree = odd_tree(n)
        out = stackmap(mesh, lambda g: fused.fused_allreduce(
            g, AX, bucket_bytes=self.BUCKET))(tree)
        per_leaf = stackmap(mesh, lambda g: jax.tree.map(
            lambda a: jax.lax.pmean(a, AX), g))(tree)
        want = ref_mean(tree)
        flat = zip(jax.tree.leaves(out), jax.tree.leaves(per_leaf),
                   jax.tree.leaves(want))
        for got, base, ref in flat:
            got, base = np.asarray(got)[0], np.asarray(base)[0]
            np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
            # packing is a relayout, not a re-association: the fused
            # fp32 sums are the per-leaf sums exactly
            np.testing.assert_array_equal(got, base)

    def test_sum_op(self, mesh):
        tree = odd_tree(mesh.devices.size, seed=3)
        out = stackmap(mesh, lambda g: fused.fused_allreduce(
            g, AX, op="sum", bucket_bytes=self.BUCKET))(tree)
        want = jax.tree.map(lambda a: np.asarray(a).sum(0), tree)
        for got, ref in zip(jax.tree.leaves(out), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(got)[0], ref,
                                       rtol=1e-5, atol=1e-5)

    def test_bf16_wire_within_documented_tolerance(self, mesh):
        tree = odd_tree(mesh.devices.size, seed=1)
        out = stackmap(mesh, lambda g: fused.fused_allreduce(
            g, AX, bucket_bytes=self.BUCKET,
            wire_dtype=jnp.bfloat16))(tree)
        for got, ref, orig in zip(jax.tree.leaves(out),
                                  jax.tree.leaves(ref_mean(tree)),
                                  jax.tree.leaves(tree)):
            assert np.asarray(got).dtype == orig.dtype  # re-cast back
            np.testing.assert_allclose(np.asarray(got)[0], ref,
                                       rtol=3e-2, atol=3e-2)

    def test_mixed_dtype_tree(self, mesh):
        n = mesh.devices.size
        rng = np.random.RandomState(7)
        tree = {
            "f32": rng.randn(n, 33).astype(np.float32),
            "bf16": jnp.asarray(rng.randn(n, 21), jnp.bfloat16),
            "f32b": rng.randn(n, 5, 3).astype(np.float32),
        }
        out = stackmap(mesh, lambda g: fused.fused_allreduce(
            g, AX, bucket_bytes=self.BUCKET))(tree)
        assert out["f32"].dtype == jnp.float32
        assert out["bf16"].dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out["f32"])[0], np.asarray(tree["f32"]).mean(0),
            rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(out["bf16"], dtype=np.float32)[0],
            np.asarray(tree["bf16"], dtype=np.float32).mean(0),
            rtol=5e-2, atol=5e-2)

    def test_mixed_dtype_wire_parity(self, mesh):
        """The satellite's regression pin: a mixed f32/int32 tree under
        a bf16 wire keeps ints EXACT through the collective (they used
        to come back bf16-mangled) while floats carry the documented
        wire tolerance."""
        n = mesh.devices.size
        rng = np.random.RandomState(13)
        # rank-identical ints: the mean is the value itself, so any
        # wire corruption shows as an exact-equality failure
        ints = np.broadcast_to(
            np.asarray([1000003, -999983, 1 << 22], np.int32),
            (n, 3)).copy()
        tree = {
            "f32": rng.randn(n, 37).astype(np.float32),
            "i32": ints,
        }
        out = stackmap(mesh, lambda g: fused.fused_allreduce(
            g, AX, bucket_bytes=self.BUCKET,
            wire_dtype=jnp.bfloat16))(tree)
        assert out["i32"].dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(out["i32"])[0],
                                      ints[0])
        np.testing.assert_allclose(
            np.asarray(out["f32"])[0], np.asarray(tree["f32"]).mean(0),
            rtol=3e-2, atol=3e-2)

    def test_empty_tree_is_identity(self, mesh):
        tree = {"e": np.zeros((mesh.devices.size, 0), np.float32)}
        out = stackmap(mesh, lambda g: fused.fused_allreduce(g, AX))(tree)
        assert np.asarray(out["e"]).shape == (mesh.devices.size, 0)

    def test_bad_op_raises(self):
        with pytest.raises(ValueError, match="unsupported"):
            fused.fused_allreduce({"a": jnp.ones(3)}, AX, op="max")
        with pytest.raises(ValueError, match="positive"):
            fused.flatten_buckets({"a": jnp.ones(3)}, bucket_bytes=0)


class TestHierarchical:
    """The 2-stage lowering over a 2-D (inter, intra) mesh — the
    multi-host shape faked on the 8-device CPU world."""

    @pytest.fixture(scope="class")
    def mesh2d(self):
        devs = np.asarray(jax.devices())
        assert devs.size % 2 == 0 and devs.size >= 4
        return Mesh(devs.reshape(2, devs.size // 2), (INTER, AX))

    def hmap(self, mesh2d, body):
        def outer(g):
            red = body(jax.tree.map(lambda a: a[0], g))
            return jax.tree.map(lambda a: a[None], red)
        return jax.jit(jax.shard_map(
            outer, mesh=mesh2d, in_specs=P((INTER, AX)),
            out_specs=P((INTER, AX))))

    def test_matches_flat_mean(self, mesh2d):
        n = mesh2d.devices.size
        tree = odd_tree(n, seed=5)
        out = self.hmap(mesh2d, lambda g: fused.fused_allreduce(
            g, AX, bucket_bytes=1024, inter_axis_name=INTER))(tree)
        for got, ref in zip(jax.tree.leaves(out),
                            jax.tree.leaves(ref_mean(tree))):
            np.testing.assert_allclose(np.asarray(got)[0], ref,
                                       rtol=1e-5, atol=1e-6)

    def test_sum_and_ragged_shard(self, mesh2d):
        """Bucket sizes not divisible by intra_size exercise the pad /
        unpad around psum_scatter."""
        n = mesh2d.devices.size
        rng = np.random.RandomState(11)
        tree = {"w": rng.randn(n, 13).astype(np.float32)}  # 13 % 4 != 0
        out = self.hmap(mesh2d, lambda g: fused.fused_allreduce(
            g, AX, op="sum", bucket_bytes=1 << 20,
            inter_axis_name=INTER))(tree)
        np.testing.assert_allclose(
            np.asarray(out["w"])[0], np.asarray(tree["w"]).sum(0),
            rtol=1e-5, atol=1e-5)

    def test_rejects_non_flat_input(self):
        with pytest.raises(ValueError, match="flat bucket"):
            fused.hierarchical_allreduce(jnp.ones((2, 2)), AX, INTER)


class TestCollectiveBudget:
    """The acceptance-criteria pin: a 100+-leaf grad tree lowers to
    ≤ ceil(total_bytes/bucket_bytes) all-reduces (per-leaf baseline:
    one per leaf) — asserted on compiled HLO, not on intent."""

    def big_tree(self, n, n_leaves=120, width=64):
        rng = np.random.RandomState(0)
        return {f"p{i:03d}": rng.randn(n, width).astype(np.float32)
                for i in range(n_leaves)}

    def test_fused_lowering_meets_budget(self, mesh):
        n = mesh.devices.size
        tree = self.big_tree(n)
        n_leaves = len(jax.tree.leaves(tree))
        assert n_leaves >= 100
        total = sum(a[0].size * a[0].dtype.itemsize
                    for a in jax.tree.leaves(tree))
        bucket = 8 * 1024

        fn = stackmap(mesh, lambda g: fused.fused_allreduce(
            g, AX, bucket_bytes=bucket))
        stats = collective_stats(fn.lower(tree).compile())
        observed = assert_fused_collectives(stats, total, bucket)
        budget = fused_collective_budget(total, bucket)
        assert observed <= budget < n_leaves

        baseline = stackmap(mesh, lambda g: jax.tree.map(
            lambda a: jax.lax.pmean(a, AX), g))
        base_stats = collective_stats(baseline.lower(tree).compile())
        # the point is that the fused path is structurally bounded
        # (above).  What the per-leaf baseline compiles to is XLA's
        # choice: on this jax XLA:CPU's combiner merges all 120 pmeans
        # into ONE all-reduce, so the baseline can only be asserted
        # not to undercut the fused count
        assert base_stats["all-reduce"].count >= observed

    def test_budget_violation_raises(self, mesh):
        tree = self.big_tree(mesh.devices.size, n_leaves=16)
        baseline = stackmap(mesh, lambda g: jax.tree.map(
            lambda a: jax.lax.pmean(a, AX), g))
        stats = collective_stats(baseline.lower(tree).compile())
        if stats["all-reduce"].count <= 1:
            pytest.skip("XLA merged the per-leaf baseline to one op")
        with pytest.raises(AssertionError, match="budget"):
            # budget of 1 bucket can't cover a per-leaf lowering
            assert_fused_collectives(stats, total_bytes=1, bucket_bytes=1)


class TestPlanDrivenExecution:
    """``plan_allreduce`` — the autotuner's execution half: every
    strategy must compute the same mean, from one plan carrier."""

    def _run(self, mesh, tree, plan, **kw):
        return stackmap(mesh, lambda g: fused.plan_allreduce(
            g, AX, plan, **kw))(tree)

    def test_reduce_scatter_allgather_matches_pmean(self, mesh):
        n = mesh.devices.size
        rng = np.random.RandomState(21)
        # 13 % 8 != 0: exercises the pad/unpad around psum_scatter
        x = rng.randn(n, 13).astype(np.float32)
        out = smap(mesh, lambda s: fused.reduce_scatter_allgather(
            s.reshape(-1), AX)[None])(x)
        np.testing.assert_allclose(np.asarray(out)[0], x.mean(0),
                                   rtol=1e-5, atol=1e-6)
        with pytest.raises(ValueError, match="flat bucket"):
            fused.reduce_scatter_allgather(jnp.ones((2, 2)), AX)
        with pytest.raises(ValueError, match="unsupported"):
            fused.reduce_scatter_allgather(jnp.ones(4), AX, op="max")

    @pytest.mark.parametrize("strategy", ["per_leaf", "fused_flat",
                                          "reduce_scatter"])
    def test_flat_strategies_match_reference(self, mesh, strategy):
        tree = odd_tree(mesh.devices.size, seed=8)
        plan = {"strategy": strategy, "bucket_bytes": 1024,
                "wire_dtype": None}
        out = self._run(mesh, tree, plan)
        for got, ref in zip(jax.tree.leaves(out),
                            jax.tree.leaves(ref_mean(tree))):
            np.testing.assert_allclose(np.asarray(got)[0], ref,
                                       rtol=1e-5, atol=1e-6)

    def test_hierarchical_strategy_over_2d_mesh(self):
        devs = np.asarray(jax.devices())
        mesh2d = Mesh(devs.reshape(2, devs.size // 2), (INTER, AX))
        tree = odd_tree(devs.size, seed=9)
        plan = {"strategy": "hierarchical", "bucket_bytes": 1024,
                "wire_dtype": None}

        def outer(g):
            red = fused.plan_allreduce(
                jax.tree.map(lambda a: a[0], g), AX, plan,
                inter_axis_name=INTER)
            return jax.tree.map(lambda a: a[None], red)

        out = jax.jit(jax.shard_map(
            outer, mesh=mesh2d, in_specs=P((INTER, AX)),
            out_specs=P((INTER, AX))))(tree)
        for got, ref in zip(jax.tree.leaves(out),
                            jax.tree.leaves(ref_mean(tree))):
            np.testing.assert_allclose(np.asarray(got)[0], ref,
                                       rtol=1e-5, atol=1e-6)

    def test_rs_strategies_handle_nonfloat_leaves(self, mesh):
        """Regression: the rs→ag lowering used to crash on bool buckets
        (psum_scatter rejects them) and round int buckets through its
        shard-side float divide.  Non-float buckets must route through
        the same pmean the per-leaf path uses — exact agreement."""
        n = mesh.devices.size
        rng = np.random.RandomState(31)
        ints = np.broadcast_to(
            np.asarray([1000003, -999983], np.int32), (n, 2)).copy()
        tree = {
            "f32": rng.randn(n, 19).astype(np.float32),
            "i32": ints,
            "flags": np.ones((n, 3), bool),
        }
        plans = [
            {"strategy": "reduce_scatter", "bucket_bytes": 64,
             "wire_dtype": None},
            {"strategy": "reduce_scatter", "bucket_bytes": 64,
             "wire_dtype": "bfloat16"},
        ]
        for plan in plans:
            out = self._run(mesh, tree, plan)
            assert out["i32"].dtype == jnp.int32
            assert out["flags"].dtype == jnp.bool_
            np.testing.assert_array_equal(np.asarray(out["i32"])[0],
                                          ints[0])
            np.testing.assert_array_equal(
                np.asarray(out["flags"])[0], np.ones(3, bool))
        # the hierarchical lowering shares the exemption
        devs = np.asarray(jax.devices())
        mesh2d = Mesh(devs.reshape(2, n // 2), (INTER, AX))

        def outer(g):
            red = fused.plan_allreduce(
                jax.tree.map(lambda a: a[0], g), AX,
                {"strategy": "hierarchical", "bucket_bytes": 64,
                 "wire_dtype": None}, inter_axis_name=INTER)
            return jax.tree.map(lambda a: a[None], red)

        out = jax.jit(jax.shard_map(
            outer, mesh=mesh2d, in_specs=P((INTER, AX)),
            out_specs=P((INTER, AX))))(tree)
        np.testing.assert_array_equal(np.asarray(out["i32"])[0],
                                      ints[0])
        np.testing.assert_array_equal(np.asarray(out["flags"])[0],
                                      np.ones(3, bool))

    def test_plan_object_and_attrs_accepted(self, mesh):
        """dict, Plan, and any strategy/bucket/wire-attributed object
        are all valid carriers."""
        from chainermn_tpu.utils.autotune import Plan

        tree = {"w": np.random.RandomState(2).randn(
            mesh.devices.size, 9).astype(np.float32)}
        want = np.asarray(tree["w"]).mean(0)
        for carrier in (
                Plan(strategy="fused_flat", bucket_bytes=256),
                {"strategy": "fused_flat", "bucket_bytes": 256,
                 "wire_dtype": None}):
            out = self._run(mesh, tree, carrier)
            np.testing.assert_allclose(np.asarray(out["w"])[0], want,
                                       rtol=1e-5, atol=1e-6)

    def test_hierarchical_without_inter_axis_raises(self):
        with pytest.raises(ValueError, match="inter_axis_name"):
            fused.plan_allreduce(
                {"w": jnp.ones(4)}, AX,
                {"strategy": "hierarchical", "bucket_bytes": 64,
                 "wire_dtype": None})

    def test_unknown_strategy_raises(self):
        with pytest.raises(ValueError, match="strategy"):
            fused.plan_allreduce(
                {"w": jnp.ones(4)}, AX,
                {"strategy": "warp_drive", "bucket_bytes": 64})


class TestChooseBucketBytes:
    def test_clamps_and_scales(self):
        # tiny trees: one bucket covering the whole tree (the
        # total_bytes cap binds before the min_bucket floor)
        assert choose_bucket_bytes(1024, 8) == 1024
        # clamp above: never exceeds the tree itself
        g = 10 * 1024 * 1024
        assert choose_bucket_bytes(g, 8) <= g
        # sqrt growth in G: 100x the bytes -> ~10x the bucket
        lo = choose_bucket_bytes(1e8, 8, min_bucket=1)
        hi = choose_bucket_bytes(1e10, 8, min_bucket=1)
        assert 8 < hi / lo < 12
        # slower launch latency -> bigger buckets
        assert choose_bucket_bytes(1e9, 8, latency_s=1e-4) > \
            choose_bucket_bytes(1e9, 8, latency_s=1e-6)

    def test_degenerate_worlds(self):
        assert choose_bucket_bytes(0, 8) == 256 * 1024
        # size-1 axis: no wire at all, one bucket is optimal
        assert choose_bucket_bytes(1 << 30, 1) == 1 << 30

    def test_budget_arithmetic(self):
        assert fused_collective_budget(100, 30) == 4
        assert fused_collective_budget(100, 30, n_dtype_groups=3) == 6
        assert fused_collective_budget(0, 30) == 0
        with pytest.raises(ValueError):
            fused_collective_budget(100, 0)
