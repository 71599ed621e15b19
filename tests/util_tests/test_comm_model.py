"""comm_model: the HLO collective parser must recover the KNOWN byte
volumes of hand-built collectives, and the axis report must attribute a
DP step's gradient all-reduce to the data axis at parameter-count
scale."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from chainermn_tpu.parallel import MeshConfig
from chainermn_tpu.utils import (
    axis_collective_report,
    choose_prefetch_depth,
    collective_stats,
    stablehlo_collective_stats,
    wire_bytes_per_device,
)


def _compile(fn, mesh, in_specs, out_specs, *args):
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
    )).lower(*args).compile()


def test_psum_bytes_counted():
    mc = MeshConfig(data=8)
    x = jnp.zeros((8, 128, 4), jnp.float32)
    compiled = _compile(
        lambda t: lax.psum(t, "data"), mc.mesh, P("data"), P(), x)
    stats = collective_stats(compiled)
    assert "all-reduce" in stats, stats
    st = stats["all-reduce"]
    # one all-reduce of the local (1,128,4) f32 block = 2048 bytes
    assert st.count == 1
    assert st.bytes == 128 * 4 * 4, st
    assert st.group_size == 8
    # ring wire cost: 2*s*(n-1)/n
    np.testing.assert_allclose(
        st.wire_bytes(), 2 * 2048 * 7 / 8)


def test_all_gather_and_permute_counted():
    mc = MeshConfig(data=8)
    x = jnp.zeros((8, 16), jnp.bfloat16)

    def f(t):
        g = lax.all_gather(t, "data", axis=0, tiled=True)   # (8,16) bf16
        p = lax.ppermute(t, "data",
                         perm=[(i, (i + 1) % 8) for i in range(8)])
        return jnp.reshape(
            jnp.sum(g.astype(jnp.float32))
            + jnp.sum(p.astype(jnp.float32)), (1,))

    compiled = _compile(f, mc.mesh, P("data"), P("data"), x)
    stats = collective_stats(compiled)
    # XLA may hoist the downstream f32 convert above the collective, so
    # the gathered tensor is (8,16) in bf16 OR f32 — both sizes valid
    assert stats["all-gather"].bytes in (8 * 16 * 2, 8 * 16 * 4), stats
    assert stats["all-gather"].count == 1
    assert stats["collective-permute"].count >= 1
    assert stats["collective-permute"].bytes >= 16 * 2


def test_stablehlo_region_ops_and_gather():
    """all_reduce/reduce_scatter carry a reduction REGION, so their
    result type sits on the region-closing line — the parser must not
    grab the replica_groups i64 attribute tensor instead."""
    mc = MeshConfig(data=8)
    x = jnp.zeros((8, 64, 4), jnp.float32)

    def f(t):
        s = lax.psum(t, "data")                     # all_reduce, region
        g = lax.all_gather(t, "data", axis=0, tiled=True)
        r = lax.psum_scatter(s, "data", scatter_dimension=1, tiled=True)
        return jnp.reshape(
            jnp.sum(s) + jnp.sum(g) + jnp.sum(r), (1,))

    txt = jax.jit(jax.shard_map(
        f, mesh=mc.mesh, in_specs=P("data"), out_specs=P("data"),
    )).lower(x).as_text()
    st = stablehlo_collective_stats(txt)
    # local block (1,64,4) f32 = 1024 B; all_gather result (8,64,4)
    assert st["all-reduce"].bytes == 64 * 4 * 4, st
    assert st["all-reduce"].group_size == 8
    assert st["all-gather"].bytes == 8 * 64 * 4 * 4, st
    # scattered result (1, 64/8, 4) f32
    assert st["reduce-scatter"].bytes == 8 * 4 * 4, st


def test_hlo_async_start_counts_result_only():
    """Async -start tuples carry (operand, result, context...); only
    the result buffer is the moved payload."""

    class Fake:
        def runtime_executable(self):
            raise RuntimeError("use as_text")

        def as_text(self):
            return (
                "  %ag = (f32[2,4], f32[16,4]) all-gather-start(%x), "
                "replica_groups={{0,1,2,3,4,5,6,7}}\n"
                "  %cp = (f32[2,4], f32[2,4], u32[], u32[]) "
                "collective-permute-start(%y), "
                "source_target_pairs={{0,1}}\n")

    st = collective_stats(Fake())
    assert st["all-gather"].bytes == 16 * 4 * 4, st
    assert st["collective-permute"].bytes == 2 * 4 * 4, st


def test_iota_replica_groups_and_unknown_size():
    from chainermn_tpu.utils.comm_model import CollectiveStats, _group_size

    assert _group_size("replica_groups=[8,1]<=[8]") == 1
    assert _group_size("replica_groups=[2,4]<=[8]") == 4
    assert _group_size("no groups here") is None
    st = CollectiveStats("all-reduce", count=1, bytes=100)
    with pytest.raises(ValueError, match="group size unknown"):
        st.wire_bytes()
    assert st.wire_bytes(axis_size=4) == 150.0


def test_choose_prefetch_depth():
    # device-bound: double buffering suffices no matter how cheap the
    # host is — extra depth is pure memory
    assert choose_prefetch_depth(0.0, 0.010) == 2
    assert choose_prefetch_depth(0.010, 0.010) == 2
    assert choose_prefetch_depth(0.002, 0.010) == 2
    # host-bound: budget ceil(rho * (1 + jitter)) + 1 slots of
    # burstiness absorption, clamped
    d3 = choose_prefetch_depth(0.015, 0.010)          # rho 1.5
    d6 = choose_prefetch_depth(0.030, 0.010)          # rho 3
    assert 2 < d3 <= d6 <= 8
    assert choose_prefetch_depth(1.0, 0.001) == 8     # clamps at max
    assert choose_prefetch_depth(
        1.0, 0.001, max_depth=16) == 16
    # fp-noise around the boundary must not flip regimes
    assert choose_prefetch_depth(0.010 + 1e-12, 0.010) == 2
    # zero device time is legitimate profiler output (fully-overlapped
    # pipeline, first-probe iteration): host-bound limit, not a crash
    assert choose_prefetch_depth(0.01, 0.0) == 8
    assert choose_prefetch_depth(0.01, 0.0, max_depth=5) == 5
    # both zero: no evidence either way -> classic double buffering
    assert choose_prefetch_depth(0.0, 0.0) == 2
    with pytest.raises(ValueError):
        choose_prefetch_depth(-0.01, 0.01)
    with pytest.raises(ValueError):
        choose_prefetch_depth(0.01, -0.01)
    with pytest.raises(ValueError):
        choose_prefetch_depth(0.01, 0.01, min_depth=4, max_depth=2)
    # bad bounds must raise even on the zero-guard path
    with pytest.raises(ValueError):
        choose_prefetch_depth(0.01, 0.0, min_depth=4, max_depth=2)


def test_choose_accum_steps():
    from chainermn_tpu.utils import choose_accum_steps

    # nothing to amortise on a 1-member axis / an empty grad tree
    assert choose_accum_steps(1 << 30, 1, 0.001) == 1
    assert choose_accum_steps(0, 8, 0.001) == 1
    # a fast interconnect against slow microbatches needs no window
    assert choose_accum_steps(1 << 20, 8, 1.0) == 1
    # monotone: more gradient bytes (or faster microbatches) -> deeper
    # windows; always clamped to max_accum
    m_small = choose_accum_steps(16 << 20, 8, 1e-4)
    m_big = choose_accum_steps(256 << 20, 8, 1e-4)
    assert 1 <= m_small <= m_big <= 64
    assert m_big > 1
    assert choose_accum_steps(1 << 34, 8, 1e-6) == 64       # clamps
    assert choose_accum_steps(1 << 34, 8, 1e-6, max_accum=16) == 16
    # the M the model picks must actually beat per-microbatch exchange:
    # exchange time amortised over M is <= comm_fraction of compute
    grad_bytes, n, t_micro = 64 << 20, 8, 1e-3
    m = choose_accum_steps(grad_bytes, n, t_micro, comm_fraction=0.1)
    t_ex = 2.0 * grad_bytes * (n - 1) / (n * 90e9)
    assert m >= t_ex / (0.1 * t_micro) or m == 64
    with pytest.raises(ValueError):
        choose_accum_steps(-1, 8, 1e-3)
    with pytest.raises(ValueError):
        choose_accum_steps(1 << 20, 8, 0.0)
    with pytest.raises(ValueError):
        choose_accum_steps(1 << 20, 8, 1e-3, comm_fraction=0.0)
    with pytest.raises(ValueError):
        choose_accum_steps(1 << 20, 8, 1e-3, max_accum=0)


def test_looped_collectives_and_accum_assert():
    """A collective inside a lax.scan body must be tallied as looped;
    one outside must not — and assert_accum_collectives must accept the
    window-fused shape and reject the per-microbatch shape."""
    from chainermn_tpu.utils import assert_accum_collectives

    mc = MeshConfig(data=8)
    xs = jnp.zeros((4, 8, 16), jnp.float32)     # (M, batch, dim)

    def fused_shape(t):
        # accumulate locally, exchange once AFTER the scan
        # (the local sums are varying over the axis, so the carry
        # starts varying too)
        acc, _ = lax.scan(lambda a, x: (a + jnp.sum(x, 0), 0.0),
                          lax.pcast(jnp.zeros((16,), jnp.float32),
                                    "data", to="varying"), t)
        return lax.pmean(acc, "data")

    def per_micro_shape(t):
        # exchange INSIDE the scan body: M collectives per window
        # (the carry stays invariant: it only ever adds psums)
        a0 = jnp.zeros((16,), jnp.float32)

        def body(a, x):
            g = lax.psum(jnp.sum(x, 0), "data")
            return a + g, 0.0
        acc, _ = lax.scan(body, a0, t)
        return acc

    fused = collective_stats(_compile(
        fused_shape, mc.mesh, P(None, "data"), P(), xs))
    assert fused["all-reduce"].count == 1
    assert fused["all-reduce"].looped == 0
    assert assert_accum_collectives(fused, 16 * 4, 4 << 20, extra=0) == 1

    micro = collective_stats(_compile(
        per_micro_shape, mc.mesh, P(None, "data"), P(), xs))
    assert micro["all-reduce"].count >= 1
    assert micro["all-reduce"].looped >= 1
    with pytest.raises(AssertionError, match="inside a while body"):
        assert_accum_collectives(micro, 16 * 4, 4 << 20, extra=0)

    # budget violation: a window that somehow exchanges more than the
    # fused budget must trip even with zero looped sites
    with pytest.raises(AssertionError, match="budget"):
        assert_accum_collectives(fused, 16 * 4, 4 << 20, extra=-1)

    # the StableHLO (pre-legalisation) parser must attribute loop
    # placement the same way, so dtype-true stats can't silently pass
    # the zero-looped check for a per-microbatch program
    def lower_text(fn):
        return jax.jit(jax.shard_map(
            fn, mesh=mc.mesh, in_specs=P(None, "data"), out_specs=P(),
        )).lower(xs).as_text()

    sh_fused = stablehlo_collective_stats(lower_text(fused_shape))
    assert sh_fused["all-reduce"].looped == 0
    sh_micro = stablehlo_collective_stats(lower_text(per_micro_shape))
    assert sh_micro["all-reduce"].looped >= 1, sh_micro


def test_wire_formulas():
    assert wire_bytes_per_device("all-reduce", 100, 1) == 0
    assert wire_bytes_per_device("all-reduce", 100, 4) == 150.0
    assert wire_bytes_per_device("all-gather", 100, 4) == 75.0
    assert wire_bytes_per_device("collective-permute", 100, 4) == 100.0
    with pytest.raises(ValueError):
        wire_bytes_per_device("broadcast", 1, 2)


@pytest.mark.xfail(strict=True, reason=(
    "the product's step reduces every gradient twice (ROADMAP S7): "
    "shard_map's AD psums the cotangents of the replicated params, and "
    "create_multi_node_optimizer's fused mean all-reduces them again; "
    "pmean of an invariant value is a real all-reduce in this jax"))
def test_standard_updater_resnet_dp_step_single_reduce():
    """The step ``StandardUpdater`` compiles for ResNet-50 under
    ``create_multi_node_optimizer`` on eight data-parallel devices
    must all-reduce each gradient ONCE.  The pin guarded a bench
    script's private step until PR 28; moved onto the users' step it
    reads 2.0x the parameter bytes (1.0x with a plain optax optimizer
    in the same updater).  Strict: the PR that takes the second reduce
    out takes this marker out, and the bound then keeps it out."""
    import chainermn_tpu as cmn
    from chainermn_tpu.models import (
        ResNetConfig, init_resnet, resnet_apply, softmax_cross_entropy,
    )

    # width=16 keeps the invariant (volumes are width-proportional)
    # while cutting the dominant XLA compile cost on this 1-core host
    cfg = ResNetConfig(depth=50, num_classes=100, width=16,
                       dtype="bfloat16")
    comm = cmn.create_communicator("tpu_xla", devices=jax.devices()[:8])
    params, state = init_resnet(jax.random.PRNGKey(0), cfg)

    def loss_fn(params, state, x, y):
        logits, new_state = resnet_apply(
            cfg, params, state, x, train=True, axis_name=comm.axis_name)
        return softmax_cross_entropy(logits, y), new_state

    x = np.zeros((16, 32, 32, 3), np.float32)
    y = np.zeros((16,), np.int32)
    updater = cmn.StandardUpdater(
        cmn.SerialIterator(list(zip(x, y)), 16, shuffle=False),
        cmn.create_multi_node_optimizer(
            optax.sgd(0.1, momentum=0.9), comm),
        loss_fn, params, comm, state=state)
    carry = (updater.params, updater.state, updater.opt_state)
    compiled = updater._get_step(2).lower(carry, x, y).compile()
    st = collective_stats(compiled)["all-reduce"]
    pb = sum(p.size * p.dtype.itemsize for p in jax.tree.leaves(params))
    sb = sum(p.size * p.dtype.itemsize for p in jax.tree.leaves(state))
    # fp32 grads + BN-stat pmeans, with a few % slack for loss scalars;
    # a double reduce would land at ~2x
    assert st.bytes >= pb, (st.bytes, pb)
    assert st.bytes <= (pb + sb) * 1.05, \
        f"DP step moves {st.bytes} all-reduce bytes for {pb} param " \
        f"bytes (+{sb} state) — double gradient reduce reintroduced?"


def test_axis_report_attributes_dp_gradient_allreduce():
    """A pmean-grads DP step's dominant collective must be an
    all-reduce of ~n_params floats on the data axis."""
    n_in, n_out = 64, 32
    w = jnp.zeros((n_in, n_out), jnp.float32)
    n_params = n_in * n_out

    def build(axes):
        mc = MeshConfig(**axes, devices=jax.devices()[:8])
        x = jnp.zeros((8, 4, n_in), jnp.float32)
        y = jnp.zeros((8, 4, n_out), jnp.float32)

        def step(w, x, y):
            x, y = x[0], y[0]
            g = jax.grad(lambda q: jnp.mean((x @ q - y) ** 2))(w)
            return w - 0.1 * lax.pmean(g, "data")

        fn = jax.jit(jax.shard_map(
            step, mesh=mc.mesh,
            in_specs=(P(), P("data"), P("data")), out_specs=P()))
        return fn, (w, x, y)

    report = axis_collective_report(build, {"data": 8})
    st = report["data"]["stats"]["all-reduce"]
    # the gradient all-reduce moves >= the parameter bytes; jax's vma
    # plumbing may emit a second (redundant) all-reduce when an
    # invariant output consumes the pmean — both are genuinely in the
    # compiled ENTRY, so the parser must report them (an analysis of
    # the volume would flag the duplication, not hide it)
    assert st.bytes >= n_params * 4, st
    assert st.bytes <= n_params * 4 * 2, st
    assert st.group_size == 8
    assert report["data"]["wire_bytes_per_device"] >= \
        2 * n_params * 4 * 7 / 8


def test_decode_program_parses_per_token_slices():
    """The decode factories expose their jitted program (`._jitted`) and
    the parser recovers the per-token collective slices a serving
    wire model is built on: a TP decode shows the 2-per-layer
    row-parallel psums at (B_local, 1, D) f32 — 2P whole units across
    the generation + prefill while bodies."""
    from chainermn_tpu.models import (
        TransformerConfig, init_transformer, make_generate_fn,
        shard_params,
    )

    B, P_len, MAX = 4, 5, 12
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, d_head=8, d_ff=64,
        n_layers=2, max_seq=MAX, attention="local",
        pos_embedding="rope", dtype="float32", remat=False)
    mc = MeshConfig(model=2, data=2, devices=jax.devices()[:4])
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
    prompt = jnp.zeros((B, P_len), jnp.int32)
    gen = make_generate_fn(mc, cfg, max_len=MAX)
    stats = collective_stats(
        gen._jitted.lower(params, prompt, jax.random.PRNGKey(0))
        .compile())
    st = stats["all-reduce"]
    unit = (B // 2) * cfg.d_model * 4          # (B_local, 1, D) f32
    assert st.bytes == 2 * P_len * unit, (st, unit)
    assert st.group_size == 2


# ------------------------------------------------------------------ #
# backward-overlap proof machinery (PR 7)
# ------------------------------------------------------------------ #


class _FakeCompiled:
    def __init__(self, text):
        self._text = text

    def runtime_executable(self):
        raise RuntimeError("use as_text")

    def as_text(self):
        return self._text


def test_async_depth_pairs_start_done():
    """A -start whose -done is scheduled with other instructions
    between the halves overlaps compute (async_depth 1); a
    back-to-back start;done pair overlaps nothing (0)."""
    from chainermn_tpu.utils import collective_stats as cs

    txt = """ENTRY %main (a: f32[8]) -> f32[8] {
  %ar = f32[1024]{0} all-reduce-start(%x), replica_groups={{0,1,2,3,4,5,6,7}}
  %d1 = f32[64,64]{1,0} dot(%p, %q)
  %d2 = f32[64,64]{1,0} dot(%p, %r)
  %ar.d = f32[1024]{0} all-reduce-done(%ar)
  %ag = f32[512]{0} all-gather-start(%y), replica_groups={{0,1,2,3,4,5,6,7}}
  %ag.d = f32[512]{0} all-gather-done(%ag)
}
"""
    st = cs(_FakeCompiled(txt))
    assert st["all-reduce"].async_depth == 1
    assert st["all-gather"].async_depth == 0
    # counts unaffected by the pairing bookkeeping
    assert st["all-reduce"].count == 1
    assert st["all-gather"].count == 1


def test_assert_overlap_positions_and_min_bytes():
    from chainermn_tpu.utils import assert_overlap_collectives

    def prog(collective_lines_before, after):
        body = ["ENTRY %main (a: f32[8]) -> f32[8] {"]
        body += ["  %d0 = f32[64,64]{1,0} dot(%p, %q)"]
        body += collective_lines_before
        body += ["  %d1 = f32[64,64]{1,0} dot(%p, %r)"]
        body += after
        body += ["}"]
        return _FakeCompiled("\n".join(body))

    ar = ("  %ar{i} = f32[1024]{{0}} all-reduce(%x{i}), "
          "replica_groups={{{{0,1,2,3,4,5,6,7}}}}")
    tiny = ("  %t = f32[] all-reduce(%l), "
            "replica_groups={{0,1,2,3,4,5,6,7}}")

    # 1 of 2 big collectives inside, the 4-byte loss pmean ignored
    rep = assert_overlap_collectives(
        prog([ar.format(i=0)], [ar.format(i=1), tiny]))
    assert rep == {"inside": 1, "total": 2, "frac": 0.5,
                   "async_depth": 0}
    # all big collectives after the last dot -> clustered
    with pytest.raises(AssertionError, match="cluster"):
        assert_overlap_collectives(
            prog([], [ar.format(i=0), ar.format(i=1)]))
    # nothing above the byte floor -> nothing to prove
    with pytest.raises(AssertionError, match="nothing to prove"):
        assert_overlap_collectives(prog([], [tiny]))
    # compute-free program -> nothing to prove either
    with pytest.raises(AssertionError, match="nothing to prove"):
        assert_overlap_collectives(_FakeCompiled(
            "ENTRY %main (a: f32[8]) -> f32[8] {\n"
            + ar.format(i=0) + "\n}\n"))


def test_overlap_exposed_time_model():
    from chainermn_tpu.utils import overlap_exposed_time

    buckets = [1 << 20] * 4
    n = 8
    kw = dict(latency_s=1e-5, bandwidth_bytes_per_s=1e9)
    t_wire_each = 2 * 1e-5 + 2 * (1 << 20) * (7 / 8) / 1e9
    t_ex = 4 * t_wire_each

    # no backward to hide under: eager and deferred both pay full T_ex
    assert overlap_exposed_time(buckets, n, 0.0, **kw) == \
        pytest.approx(t_ex)
    assert overlap_exposed_time(buckets, n, 0.0,
                                modes=["deferred"] * 4, **kw) == \
        pytest.approx(t_ex)

    # a long backward: the eager stream hides everything but the LAST
    # bucket (ready only when backward ends); window-end (all
    # deferred) still pays the full serial T_ex
    t_bwd = 10 * t_ex
    eager = overlap_exposed_time(buckets, n, t_bwd, **kw)
    deferred = overlap_exposed_time(buckets, n, t_bwd,
                                    modes=["deferred"] * 4, **kw)
    assert eager == pytest.approx(t_wire_each)
    assert deferred == pytest.approx(t_ex)
    assert eager < deferred

    # degenerate inputs
    assert overlap_exposed_time([], n, 1.0) == 0.0
    assert overlap_exposed_time(buckets, 1, 1.0) == 0.0
    with pytest.raises(ValueError, match="modes"):
        overlap_exposed_time(buckets, n, 1.0, modes=["eager"])
    with pytest.raises(ValueError, match="mode"):
        overlap_exposed_time(buckets, n, 1.0,
                             modes=["eager", "soon", "eager", "eager"])


def test_async_depth_dotted_suffix_names_pair_exactly():
    """XLA's .N suffixing makes one start's name a PREFIX of another's
    — the done-line match must be exact-token, or the wrong start is
    popped and the real pair orphaned."""
    from chainermn_tpu.utils import collective_stats as cs

    txt = """ENTRY %main (a: f32[8]) -> f32[8] {
  %all-reduce-start = f32[256]{0} all-reduce-start(%x), replica_groups={{0,1,2,3,4,5,6,7}}
  %all-reduce-start.1 = f32[256]{0} all-reduce-start(%y), replica_groups={{0,1,2,3,4,5,6,7}}
  %d1 = f32[64,64]{1,0} dot(%p, %q)
  %done.1 = f32[256]{0} all-reduce-done(%all-reduce-start.1)
  %d2 = f32[64,64]{1,0} dot(%p, %r)
  %done.0 = f32[256]{0} all-reduce-done(%all-reduce-start)
}
"""
    st = cs(_FakeCompiled(txt))
    # both pairs straddle at least one other instruction
    assert st["all-reduce"].async_depth == 2


def test_overlap_exposed_time_per_bucket_launches():
    """Mixed-via schedules price their launch costs truthfully: an
    all-"ar" stream (1 launch/bucket) costs one latency less per
    bucket than the rs→ag default in the latency-dominated regime."""
    from chainermn_tpu.utils import overlap_exposed_time

    buckets = [1024] * 6
    kw = dict(latency_s=1e-3, bandwidth_bytes_per_s=1e12)
    rs = overlap_exposed_time(buckets, 8, 0.0, **kw)
    ar = overlap_exposed_time(buckets, 8, 0.0,
                              launches_per_bucket=[1] * 6, **kw)
    assert rs == pytest.approx(ar + 6 * 1e-3)
    with pytest.raises(ValueError, match="launch counts"):
        overlap_exposed_time(buckets, 8, 0.0, launches_per_bucket=[1])
