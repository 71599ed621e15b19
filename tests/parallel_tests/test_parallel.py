"""Parallel-strategy tests on the virtual 8-device CPU mesh.

Every strategy is verified against a single-device oracle: pipeline vs
sequential stage application (fwd + grads), ring/Ulysses attention vs
dense softmax attention (fwd + grads, causal and not), TP dense pair vs
plain matmul, MoE vs per-token dense expert application.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from chainermn_tpu.communicators._mesh_utils import make_named_mesh, make_world_mesh
from chainermn_tpu.parallel import (
    MeshConfig,
    column_parallel_dense,
    expert_parallel_moe,
    pipeline_apply,
    ring_attention,
    row_parallel_dense,
    stack_stage_params,
)
from chainermn_tpu.parallel.ring_attention import local_attention
from chainermn_tpu.parallel.ulysses import ulysses_attention


AX = "world"


@pytest.fixture(scope="module")
def mesh():
    return make_world_mesh(axis_name=AX)


def smap(mesh, fn, in_specs, out_specs):
    return jax.jit(
        jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs))


class TestMeshConfig:
    def test_build_and_absorb(self):
        cfg = MeshConfig(data=-1, model=2, pipe=2)
        assert cfg.data == 2
        assert cfg.mesh.shape == {
            "pipe": 2, "data": 2, "expert": 1, "seq": 1, "model": 2}

    def test_all_axes_exist_at_size_one(self):
        cfg = MeshConfig(data=8)
        assert tuple(cfg.mesh.axis_names) == (
            "pipe", "data", "expert", "seq", "model")

    def test_bad_sizes_raise(self):
        with pytest.raises(ValueError):
            MeshConfig(data=3, model=3)
        with pytest.raises(ValueError):
            MeshConfig(data=-1, model=-1)


class TestTensorParallel:
    def test_column_row_pair_matches_dense(self, mesh):
        """Megatron MLP block: X·W1 → gelu → ·W2 with ONE psum."""
        n = 8
        rng = np.random.RandomState(0)
        x = rng.randn(4, 16).astype(np.float32)
        w1 = rng.randn(16, 32).astype(np.float32) * 0.1
        b1 = rng.randn(32).astype(np.float32) * 0.1
        w2 = rng.randn(32, 16).astype(np.float32) * 0.1
        b2 = rng.randn(16).astype(np.float32) * 0.1

        def tp_block(x, w1, b1, w2, b2):
            h = jax.nn.gelu(
                column_parallel_dense(x, w1, b1, axis_name=AX))
            return row_parallel_dense(h, w2, b2, axis_name=AX)

        # w1 column-sharded, b1 sharded, w2 row-sharded, b2 replicated
        out = smap(mesh, tp_block,
                   in_specs=(P(), P(None, AX), P(AX), P(AX, None), P()),
                   out_specs=P())(x, w1, b1, w2, b2)
        ref = jax.nn.gelu(x @ w1 + b1) @ w2 + b2
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
        assert n == mesh.devices.size

    def test_tp_gradients_match(self, mesh):
        rng = np.random.RandomState(1)
        x = rng.randn(4, 8).astype(np.float32)
        w1 = rng.randn(8, 16).astype(np.float32) * 0.1
        w2 = rng.randn(16, 8).astype(np.float32) * 0.1

        def tp_loss(x, w1, w2):
            h = jax.nn.gelu(column_parallel_dense(x, w1, axis_name=AX))
            y = row_parallel_dense(h, w2, axis_name=AX)
            return jnp.sum(y**2)

        g1, g2 = smap(mesh, jax.grad(tp_loss, argnums=(1, 2)),
                      in_specs=(P(), P(None, AX), P(AX, None)),
                      out_specs=(P(None, AX), P(AX, None)))(x, w1, w2)

        def ref_loss(x, w1, w2):
            return jnp.sum((jax.nn.gelu(x @ w1) @ w2) ** 2)

        r1, r2 = jax.grad(ref_loss, argnums=(1, 2))(
            jnp.asarray(x), jnp.asarray(w1), jnp.asarray(w2))
        np.testing.assert_allclose(np.asarray(g1), np.asarray(r1),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(g2), np.asarray(r2),
                                   rtol=1e-4, atol=1e-5)


def _stage_apply(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def _make_stages(n_stage, dim, seed=0):
    rng = np.random.RandomState(seed)
    return [
        {"w": jnp.asarray(rng.randn(dim, dim).astype(np.float32) * 0.3),
         "b": jnp.asarray(rng.randn(dim).astype(np.float32) * 0.1)}
        for _ in range(n_stage)
    ]


class TestPipeline:
    @pytest.mark.parametrize("microbatches", [8, 16])
    def test_forward_matches_sequential(self, mesh, microbatches):
        S = mesh.devices.size
        dim, B = 6, 32
        stages = _make_stages(S, dim)
        stacked = stack_stage_params(stages)
        x = np.random.RandomState(2).randn(B, dim).astype(np.float32)

        out = smap(
            mesh,
            lambda p, xs: pipeline_apply(
                _stage_apply, p, xs, axis_name=AX,
                num_microbatches=microbatches),
            in_specs=(P(AX), P()), out_specs=P())(stacked, x)

        ref = jnp.asarray(x)
        for p in stages:
            ref = _stage_apply(p, ref)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)

    def test_gradients_match_sequential(self, mesh):
        S = mesh.devices.size
        dim, B, M = 5, 16, 8
        stages = _make_stages(S, dim, seed=3)
        stacked = stack_stage_params(stages)
        x = np.random.RandomState(4).randn(B, dim).astype(np.float32)

        def dist_loss(p, xs):
            y = pipeline_apply(_stage_apply, p, xs, axis_name=AX,
                               num_microbatches=M)
            return jnp.sum(y**2)

        g = smap(mesh, jax.grad(dist_loss),
                 in_specs=(P(AX), P()), out_specs=P(AX))(stacked, x)

        def ref_loss(ps, xs):
            h = xs
            for p in ps:
                h = _stage_apply(p, h)
            return jnp.sum(h**2)

        g_ref = stack_stage_params(
            jax.grad(ref_loss)(stages, jnp.asarray(x)))
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)

    def test_single_stage_degenerate(self):
        """S=1 pipe axis: schedule reduces to plain micro-batched apply."""
        mesh1 = make_named_mesh({"one": 1}, devices=jax.devices()[:1])
        stages = _make_stages(1, 4, seed=5)
        stacked = stack_stage_params(stages)
        x = np.random.RandomState(6).randn(8, 4).astype(np.float32)
        out = jax.jit(jax.shard_map(
            lambda p, xs: pipeline_apply(
                _stage_apply, p, xs, axis_name="one", num_microbatches=4),
            mesh=mesh1, in_specs=(P("one"), P()), out_specs=P()))(stacked, x)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_stage_apply(stages[0], x)),
            rtol=1e-5, atol=1e-6)

    def test_batch_not_divisible_raises(self, mesh):
        stacked = stack_stage_params(_make_stages(mesh.devices.size, 4))
        with pytest.raises(ValueError, match="not divisible"):
            smap(mesh,
                 lambda p, xs: pipeline_apply(
                     _stage_apply, p, xs, axis_name=AX, num_microbatches=7),
                 in_specs=(P(AX), P()), out_specs=P())(
                     stacked, np.zeros((16, 4), np.float32))


def _qkv(shape, seed):
    rng = np.random.RandomState(seed)
    return tuple(rng.randn(*shape).astype(np.float32) * 0.5
                 for _ in range(3))


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense_attention(self, mesh, causal):
        B, T, H, D = 2, 32, 4, 8
        q, k, v = _qkv((B, T, H, D), seed=7)

        out = smap(
            mesh,
            lambda a, b, c: ring_attention(a, b, c, axis_name=AX,
                                           causal=causal),
            in_specs=(P(None, AX), P(None, AX), P(None, AX)),
            out_specs=P(None, AX))(q, k, v)
        ref = local_attention(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_match(self, mesh, causal):
        B, T, H, D = 1, 16, 2, 4
        q, k, v = _qkv((B, T, H, D), seed=8)

        def dist_loss(a, b, c):
            o = ring_attention(a, b, c, axis_name=AX, causal=causal)
            return jax.lax.psum(jnp.sum(o**2), AX)

        g = smap(mesh, jax.grad(dist_loss, argnums=(0, 1, 2)),
                 in_specs=(P(None, AX),) * 3,
                 out_specs=(P(None, AX),) * 3)(q, k, v)

        def ref_loss(a, b, c):
            return jnp.sum(local_attention(a, b, c, causal=causal) ** 2)

        g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)


class TestUlyssesAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense_attention(self, mesh, causal):
        B, T, H, D = 2, 32, 8, 4  # H divisible by 8 devices
        q, k, v = _qkv((B, T, H, D), seed=9)

        out = smap(
            mesh,
            lambda a, b, c: ulysses_attention(a, b, c, axis_name=AX,
                                              causal=causal),
            in_specs=(P(None, AX),) * 3,
            out_specs=P(None, AX))(q, k, v)
        ref = local_attention(jnp.asarray(q), jnp.asarray(k),
                              jnp.asarray(v), causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_head_divisibility_checked(self, mesh):
        q, k, v = _qkv((1, 16, 6, 4), seed=10)  # 6 heads, 8 devices
        with pytest.raises(ValueError, match="not divisible"):
            smap(mesh,
                 lambda a, b, c: ulysses_attention(a, b, c, axis_name=AX),
                 in_specs=(P(None, AX),) * 3,
                 out_specs=P(None, AX))(q, k, v)


def _expert_fn(params, tokens):
    return jax.nn.relu(tokens @ params["w1"]) @ params["w2"]


class TestExpertParallel:
    def test_matches_dense_top1(self, mesh):
        """Ample capacity + top-1: every token goes through exactly its
        argmax expert — compare against direct per-token application."""
        S = mesh.devices.size
        E, D, Dh, N = S, 8, 16, 64  # one expert per device
        rng = np.random.RandomState(11)
        x = rng.randn(N, D).astype(np.float32)
        router_w = rng.randn(D, E).astype(np.float32)
        experts = {
            "w1": jnp.asarray(rng.randn(E, D, Dh).astype(np.float32) * 0.3),
            "w2": jnp.asarray(rng.randn(E, Dh, D).astype(np.float32) * 0.3),
        }

        out, aux = smap(
            mesh,
            lambda xs, rw, ep: expert_parallel_moe(
                xs, rw, ep, _expert_fn, axis_name=AX,
                capacity_factor=float(E)),  # capacity = N: no drops
            in_specs=(P(AX), P(), P(AX)),
            out_specs=(P(AX), P()))(x, router_w, experts)

        probs = jax.nn.softmax(jnp.asarray(x) @ router_w, axis=-1)
        choice = np.asarray(probs.argmax(axis=-1))
        gate = np.asarray(probs.max(axis=-1))
        ref = np.stack([
            np.asarray(_expert_fn(
                jax.tree.map(lambda a: a[choice[i]], experts),
                jnp.asarray(x[i:i + 1])))[0] * gate[i]
            for i in range(N)
        ])
        np.testing.assert_allclose(np.asarray(out), ref,
                                   rtol=1e-3, atol=1e-4)
        assert float(aux) > 0

    def test_matches_dense_top2(self, mesh):
        """Ample capacity + top-2: every token is the gate-weighted sum
        of its two best experts with gates renormalised over the pair —
        compare against direct dense application."""
        S = mesh.devices.size
        E, D, Dh, N = S, 8, 16, 64
        rng = np.random.RandomState(21)
        x = rng.randn(N, D).astype(np.float32)
        router_w = rng.randn(D, E).astype(np.float32)
        experts = {
            "w1": jnp.asarray(rng.randn(E, D, Dh).astype(np.float32) * 0.3),
            "w2": jnp.asarray(rng.randn(E, Dh, D).astype(np.float32) * 0.3),
        }

        out, aux = smap(
            mesh,
            lambda xs, rw, ep: expert_parallel_moe(
                xs, rw, ep, _expert_fn, axis_name=AX,
                capacity_factor=float(E), top_k=2),
            in_specs=(P(AX), P(), P(AX)),
            out_specs=(P(AX), P()))(x, router_w, experts)

        probs = np.asarray(jax.nn.softmax(jnp.asarray(x) @ router_w, -1))
        order = np.argsort(-probs, axis=-1)[:, :2]       # (N, 2)
        ref = np.zeros_like(x)
        for i in range(N):
            e0, e1 = order[i]
            p0, p1 = probs[i, e0], probs[i, e1]
            y0 = np.asarray(_expert_fn(
                jax.tree.map(lambda a: a[e0], experts),
                jnp.asarray(x[i:i + 1])))[0]
            y1 = np.asarray(_expert_fn(
                jax.tree.map(lambda a: a[e1], experts),
                jnp.asarray(x[i:i + 1])))[0]
            ref[i] = (p0 * y0 + p1 * y1) / (p0 + p1)
        np.testing.assert_allclose(np.asarray(out), ref,
                                   rtol=1e-3, atol=1e-4)
        assert float(aux) > 0

    def test_top2_primary_wins_capacity(self, mesh):
        """Rank-0 assignments queue ahead of rank-1: when an expert's
        slots run out, the dropped assignments are secondaries."""
        S = mesh.devices.size
        rng = np.random.RandomState(22)
        N_local = 4
        x = rng.randn(N_local * S, 4).astype(np.float32)
        # every token's best expert is 0, second-best is 1
        router_w = np.zeros((4, S), np.float32)
        router_w[:, 0] = 10.0
        router_w[:, 1] = 5.0
        experts = {
            "w1": jnp.ones((S, 4, 8), jnp.float32),
            "w2": jnp.ones((S, 8, 4), jnp.float32),
        }
        # cap = ceil(cf·k·N/E) with cf=N_local·S/(2·N_local·S)=0.5 → half
        # the primary demand on expert 0: some primaries kept, ALL
        # secondaries on expert 0 would overflow anyway; expert 1 (pure
        # secondaries) has the same cap, so half the secondaries fit
        out, _ = smap(
            mesh,
            lambda xs, rw, ep: expert_parallel_moe(
                xs, rw, ep, _expert_fn, axis_name=AX,
                capacity_factor=0.5, top_k=2),
            in_specs=(P(AX), P(), P(AX)),
            out_specs=(P(AX), P()))(x, router_w, experts)
        # nothing NaN/Inf and at least one token got pure-primary output
        arr = np.asarray(out)
        assert np.isfinite(arr).all()
        assert (np.abs(arr).sum(axis=1) > 0).any()

    def test_capacity_drops_zero_tokens(self, mesh):
        """Tiny capacity: overflow tokens must come back as exact zeros."""
        S = mesh.devices.size
        rng = np.random.RandomState(12)
        x = rng.randn(32, 4).astype(np.float32)
        # router forces everyone to expert 0 → massive overflow
        router_w = np.zeros((4, S), np.float32)
        router_w[:, 0] = 10.0
        experts = {
            "w1": jnp.ones((S, 4, 8), jnp.float32),
            "w2": jnp.ones((S, 8, 4), jnp.float32),
        }
        out, _ = smap(
            mesh,
            lambda xs, rw, ep: expert_parallel_moe(
                xs, rw, ep, _expert_fn, axis_name=AX,
                capacity_factor=0.25),
            in_specs=(P(AX), P(), P(AX)),
            out_specs=(P(AX), P()))(x, router_w, experts)
        out = np.asarray(out)
        # cap = ceil(0.25 · 4 local tokens / 8 experts) → 1 slot per expert
        # per device; all tokens route to expert 0 → exactly 1 kept per
        # device, the rest come back as exact zeros (Switch drop semantics;
        # note a *kept* token can also legitimately output zero via relu)
        zero_rows = (np.abs(out).sum(axis=1) == 0).sum()
        assert zero_rows >= 32 - S  # every over-capacity token dropped
        nonzero_rows = (np.abs(out).sum(axis=1) > 0).sum()
        assert nonzero_rows <= S  # at most one kept slot per device

    def test_gradients_flow(self, mesh):
        S = mesh.devices.size
        rng = np.random.RandomState(13)
        x = rng.randn(16, 4).astype(np.float32)
        router_w = rng.randn(4, S).astype(np.float32)
        experts = {
            "w1": jnp.asarray(rng.randn(S, 4, 8).astype(np.float32) * 0.3),
            "w2": jnp.asarray(rng.randn(S, 8, 4).astype(np.float32) * 0.3),
        }

        def loss(ep, xs):
            out, aux = expert_parallel_moe(
                xs, router_w, ep, _expert_fn, axis_name=AX,
                capacity_factor=float(S))
            return jax.lax.psum(jnp.sum(out**2), AX) + 0.01 * aux

        g = smap(mesh, jax.grad(loss), in_specs=(P(AX), P(AX)),
                 out_specs=P(AX))(experts, x)
        for leaf in jax.tree.leaves(g):
            arr = np.asarray(leaf)
            assert np.isfinite(arr).all()
            assert np.abs(arr).sum() > 0


class TestRingFlash:
    """ring_attention(use_flash=True): Pallas per-pair kernels + exact
    log-space merge must equal full-sequence attention."""

    def _qkv(self, B=2, T=64, H=2, D=16, seed=0):
        rng = np.random.RandomState(seed)
        mk = lambda: jnp.asarray(
            rng.randn(B, T, H, D).astype(np.float32) * 0.5)
        return mk(), mk(), mk()

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, causal):
        from chainermn_tpu.parallel.ring_attention import (
            local_attention, ring_attention)

        q, k, v = self._qkv()
        ref = local_attention(q, k, v, causal=causal)
        mc = MeshConfig(seq=8)
        f = jax.jit(jax.shard_map(
            lambda q, k, v: ring_attention(
                q, k, v, axis_name="seq", causal=causal, remat=False,
                use_flash=True, block_q=8, block_k=8, interpret=True),
            mesh=mc.mesh,
            in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq")))
        out = f(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_grads_match_xla_ring(self):
        from chainermn_tpu.parallel.ring_attention import ring_attention

        q, k, v = self._qkv(seed=1)
        mc = MeshConfig(seq=8)

        def make_loss(**kw):
            def loss(q, k, v):
                o = ring_attention(q, k, v, axis_name="seq", causal=True,
                                   remat=False, **kw)
                return jax.lax.psum(
                    jnp.sum(o * jnp.cos(o)), ("seq",))
            return jax.jit(jax.shard_map(
                jax.grad(loss, argnums=(0, 1, 2)),
                mesh=mc.mesh,
                in_specs=(P(None, "seq"),) * 3,
                out_specs=(P(None, "seq"),) * 3))

        g_flash = make_loss(use_flash=True, block_q=8, block_k=8,
                            interpret=True)(q, k, v)
        g_xla = make_loss()(q, k, v)
        for a, b in zip(g_flash, g_xla):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5)


class TestZigzagRing:
    """Load-balanced (Striped/zigzag) causal ring layout: device r holds
    chunks r and 2S-1-r; the result must equal dense attention gathered
    through the same permutation, for both the einsum and kernel paths."""

    def _global_qkv(self, B=2, Tg=64, H=2, D=16, seed=0):
        rng = np.random.RandomState(seed)
        mk = lambda: jnp.asarray(
            rng.randn(B, Tg, H, D).astype(np.float32) * 0.5)
        return mk(), mk(), mk()

    @pytest.mark.parametrize("use_flash", [False, True])
    def test_matches_dense_oracle(self, use_flash):
        from chainermn_tpu.parallel.ring_attention import (
            local_attention, ring_attention, zigzag_indices)

        S, Tg = 8, 64
        q, k, v = self._global_qkv(Tg=Tg)
        perm = zigzag_indices(S, Tg).reshape(-1)      # global -> zigzag
        qz, kz, vz = (t[:, perm] for t in (q, k, v))

        mc = MeshConfig(seq=S)
        f = jax.jit(jax.shard_map(
            lambda q, k, v: ring_attention(
                q, k, v, axis_name="seq", causal=True, remat=False,
                layout="zigzag", use_flash=use_flash, block_q=8,
                block_k=8, interpret=True),
            mesh=mc.mesh,
            in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq")))
        out_z = np.asarray(f(qz, kz, vz))

        ref = np.asarray(local_attention(q, k, v, causal=True))
        # un-permute the zigzag output back to global order
        inv = np.empty_like(perm)
        inv[perm] = np.arange(Tg)
        np.testing.assert_allclose(out_z[:, inv], ref,
                                   rtol=2e-5, atol=2e-5)

    def test_grads_match_contiguous_ring(self):
        from chainermn_tpu.parallel.ring_attention import (
            ring_attention, zigzag_indices)

        S, Tg = 4, 32
        q, k, v = self._global_qkv(Tg=Tg, seed=3)
        perm = zigzag_indices(S, Tg).reshape(-1)
        inv = np.empty_like(perm)
        inv[perm] = np.arange(Tg)
        mc = MeshConfig(seq=S, data=2)

        def make_grads(layout, qkv):
            def loss(q, k, v):
                o = ring_attention(q, k, v, axis_name="seq", causal=True,
                                   remat=False, layout=layout)
                return jax.lax.psum(jnp.sum(o * jnp.sin(o)), ("seq",))
            g = jax.jit(jax.shard_map(
                jax.grad(loss, argnums=(0, 1, 2)),
                mesh=mc.mesh,
                in_specs=(P(None, "seq"),) * 3,
                out_specs=(P(None, "seq"),) * 3))(*qkv)
            return [np.asarray(t) for t in g]

        g_zig = make_grads("zigzag", (q[:, perm], k[:, perm], v[:, perm]))
        g_ref = make_grads("contiguous", (q, k, v))
        for a, b in zip(g_zig, g_ref):
            np.testing.assert_allclose(a[:, inv], b, rtol=5e-4, atol=1e-5)

    def test_bad_layout_rejected(self):
        from chainermn_tpu.parallel.ring_attention import ring_attention

        mc = MeshConfig(seq=2)
        with pytest.raises(ValueError, match="layout"):
            jax.jit(jax.shard_map(
                lambda q: ring_attention(q, q, q, axis_name="seq",
                                         layout="spiral"),
                mesh=mc.mesh, in_specs=(P(None, "seq"),),
                out_specs=P(None, "seq")))(
                    np.zeros((1, 8, 1, 4), np.float32))

    def test_zigzag_indices_cover(self):
        from chainermn_tpu.parallel.ring_attention import zigzag_indices

        idx = zigzag_indices(4, 64)
        assert idx.shape == (4, 16)
        assert sorted(idx.reshape(-1).tolist()) == list(range(64))
        # device 0 holds the first and the LAST chunk (balance property)
        assert idx[0, 0] == 0 and idx[0, -1] == 63
