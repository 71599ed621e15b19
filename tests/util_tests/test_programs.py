"""Compile-and-memory plane (ISSUE 15): the XLA program ledger —
signature-diff retrace attribution, ring bound, disabled-path
discipline, steady-state marking and the retrace-storm alert — plus
the device-memory accountant's gauges, watermarks and deterministic
cross-rank merge, the /programz surface, and GoodputReport's compile
badput category."""

import json
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.utils import programs
from chainermn_tpu.utils.alerts import AlertManager
from chainermn_tpu.utils.metrics import (
    GoodputReport,
    Histogram,
    MetricsRegistry,
    get_registry,
    set_registry,
)
from chainermn_tpu.utils.programs import (
    MemoryAccountant,
    ProgramLedger,
    abstract_signature,
    instrument,
    ledger_jit,
    retrace_storm_rule,
    set_ledger,
    signature_diff,
)
from chainermn_tpu.utils.statusz import StatuszServer
from chainermn_tpu.utils.telemetry import TraceRecorder, set_recorder


@pytest.fixture()
def ledger():
    """A fresh enabled ledger installed as the global one (the
    instrumented wrappers resolve the global per call)."""
    led = ProgramLedger(enabled=True)
    prev = set_ledger(led)
    try:
        yield led
    finally:
        set_ledger(prev)


@pytest.fixture()
def registry():
    reg = MetricsRegistry(enabled=True)
    prev = set_registry(reg)
    try:
        yield reg
    finally:
        set_registry(prev)


class TestSignatures:
    def test_leaf_signature_forms(self):
        _, sig = abstract_signature(
            (jnp.ones((2, 3), jnp.float32), 7, 2.5))
        # device arrays render dtype[shape]@sharding — sharding is
        # part of jit's cache key, so it is part of the ledger's
        assert sig[0].startswith("float32[2,3]")
        assert sig[1].startswith("py:") and sig[2].startswith("py:")
        _, sig = abstract_signature((np.ones((4,), np.int32),))
        assert sig[0] == "int32[4]"     # host arrays: no sharding

    def test_diff_none_on_first_compile(self):
        assert signature_diff(None, ("float32[2]",)) is None

    def test_diff_dtype_vs_shape_vs_type(self):
        old = ("float32[4,4]", "int32[8]", "float32[2]", "py:int")
        new = ("bfloat16[4,4]", "int32[8,2]", "py:int", "py:float")
        d = signature_diff(old, new)
        assert d["kinds"] == ["dtype", "shape", "type"]
        assert d["n_changed"] == 4
        by_leaf = {c["leaf"]: c["kind"] for c in d["changed"]}
        # a python-scalar TYPE change (py:int → py:float) is "type",
        # never a misleading array-dtype attribution
        assert by_leaf == {0: "dtype", 1: "shape", 2: "type",
                           3: "type"}

    def test_diff_structure_and_donation(self):
        d = signature_diff(("f32[2]",), ("f32[2]", "f32[4]"),
                           old_donate=(0,), new_donate=())
        assert "structure" in d["kinds"] and "donation" in d["kinds"]
        assert d["donate_from"] == [0] and d["donate_to"] == []

    def test_diff_bounds_changed_list(self):
        old = tuple(f"float32[{i}]" for i in range(32))
        new = tuple(f"float32[{i + 1}]" for i in range(32))
        d = signature_diff(old, new, max_changed=8)
        assert d["n_changed"] == 32 and len(d["changed"]) == 8


class TestLedger:
    def test_retrace_attribution(self, ledger, registry):
        f = ledger_jit(lambda x: x * 2, label="toy/double")
        f(jnp.ones((4,), jnp.float32))
        f(jnp.ones((4,), jnp.float32))      # signature hit
        f(jnp.ones((8,), jnp.float32))      # shape retrace
        f(jnp.ones((8,), jnp.bfloat16))     # dtype retrace
        assert ledger.compiles() == 3
        entries = ledger.entries()          # newest first
        assert [e["n"] for e in entries] == [3, 2, 1]
        assert entries[0]["diff"]["kinds"] == ["dtype"]
        assert entries[1]["diff"]["kinds"] == ["shape"]
        assert entries[2]["diff"] is None
        stats = ledger.label_stats()["toy/double"]
        assert stats["compiles"] == 3 and stats["calls"] == 4
        assert stats["steady_compiles"] == 0 and stats["programs"] == 3
        assert stats["compile_s"] == pytest.approx(
            ledger.total_compile_s)
        assert ledger.compile_seconds("toy/") == pytest.approx(
            ledger.total_compile_s)
        assert ledger.compile_seconds("serve/") == 0.0
        # the metrics fan-out
        assert registry.counter("compile/retraces").value == 3
        assert registry.counter(
            "compile/retraces_toy_double").value == 3
        assert registry.counter("compile/calls").value == 4
        assert registry.histogram("compile/seconds").count == 3

    def test_python_scalar_value_change_is_not_a_retrace(self, ledger,
                                                         registry):
        f = ledger_jit(lambda x, n: x + n, label="toy/scalar")
        f(jnp.ones((2,)), 1)
        f(jnp.ones((2,)), 2)    # value change, same abstract signature
        assert ledger.compiles() == 1

    def test_keyword_arguments_supported(self, ledger, registry):
        """jit callables take kwargs, so the drop-in wrapper must too
        — enabled AND disabled — and a kwarg's signature rides the
        key (same shapes, same kwarg name → one compile)."""
        f = ledger_jit(lambda x, n: x + n, label="toy/kw")
        f(jnp.ones((2,)), n=jnp.ones((2,)))
        f(jnp.ones((2,)), n=jnp.ones((2,)))
        assert ledger.compiles() == 1
        f(jnp.ones((4,)), n=jnp.ones((4,)))     # shape retrace
        assert ledger.compiles() == 2
        ledger.disable()
        out = f(jnp.zeros((2,)), n=jnp.ones((2,)))
        assert float(out.sum()) == 2.0

    def test_sharding_retrace_is_visible(self, ledger, registry):
        """jit keys on input sharding, so the ledger must too: the
        same shape/dtype arriving committed to a different layout is
        a recorded retrace whose diff says 'sharding' — the stale-
        mesh-feed storm must never read as healthy."""
        if jax.device_count() < 2:
            pytest.skip("needs a multi-device mesh")
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("d",))
        f = ledger_jit(lambda x: x + 1, label="toy/shard")
        x = jnp.ones((8, 8), jnp.float32)
        f(jax.device_put(x, NamedSharding(mesh, P())))
        f(jax.device_put(x, NamedSharding(mesh, P())))      # hit
        assert ledger.compiles() == 1
        f(jax.device_put(x, NamedSharding(mesh, P("d"))))   # relayout
        assert ledger.compiles() == 2
        assert ledger.entries()[0]["diff"]["kinds"] == ["sharding"]

    def test_treedef_only_retrace_reads_as_structure(self, ledger,
                                                     registry):
        """A dict-key rename keeps leaf count and leaf signatures
        identical but changes the treedef — the recorded diff must
        say 'structure', not render empty (an empty diff reads as 'a
        rebuild, not a shape leak' — the opposite attribution)."""
        f = ledger_jit(lambda d: d[next(iter(d))], label="toy/tree")
        f({"a": jnp.ones((2,))})
        f({"b": jnp.ones((2,))})        # same leaves, renamed key
        assert ledger.compiles() == 2
        diff = ledger.entries()[0]["diff"]
        assert diff["kinds"] == ["structure"]
        assert diff["n_changed"] == 0

    def test_failed_first_call_releases_the_claim(self, ledger,
                                                  registry):
        """A first call that raises never materialized a program: the
        signature claim is released so a later retry's compile is
        still recorded."""
        f = ledger_jit(lambda x: x.reshape((3, 3)), label="toy/boom")
        with pytest.raises(TypeError):
            f(jnp.ones((4,)))           # 4 elements can't be (3, 3)
        assert ledger.compiles() == 0
        g = ledger_jit(lambda x: x * 2, label="toy/boom")
        g(jnp.ones((4,)))               # retry shape is recorded
        assert ledger.compiles() == 1

    def test_compile_span_lands_in_recorder(self, ledger, registry):
        rec = TraceRecorder(enabled=True)
        prev = set_recorder(rec)
        try:
            f = ledger_jit(lambda x: x + 1, label="toy/span")
            f(jnp.ones((2,)))
        finally:
            set_recorder(prev)
        names = [e["name"] for e in rec.events()]
        assert "compile/toy/span" in names

    def test_exemplar_rides_compile_seconds(self, ledger, registry):
        ledger.exemplar = "trace-abc"
        f = ledger_jit(lambda x: x + 1, label="toy/exemplar")
        f(jnp.ones((2,)))
        ledger.exemplar = None
        ex = registry.histogram("compile/seconds").exemplar_for(50)
        assert ex is not None and ex[0] == "trace-abc"
        # without a staged exemplar the label itself is the link
        f(jnp.ones((4,)))
        ex = registry.histogram("compile/seconds").exemplar_for(50)
        assert ex[0] in ("trace-abc", "toy/exemplar")

    def test_ring_bound(self, ledger, registry):
        small = ProgramLedger(capacity=4, enabled=True)
        prev = set_ledger(small)
        try:
            f = ledger_jit(lambda x: x * 1, label="toy/ring")
            for n in range(1, 8):
                f(jnp.ones((n,)))
        finally:
            set_ledger(prev)
        assert len(small) == 4
        assert small.dropped == 3
        # counters survive the wrap — the seen-set is not ring-bounded
        assert small.compiles() == 7
        assert small.label_stats()["toy/ring"]["programs"] == 7

    def test_disabled_path_records_nothing(self, registry):
        led = ProgramLedger(enabled=False)
        prev = set_ledger(led)
        try:
            f = ledger_jit(lambda x: x + 1, label="toy/off")
            f(jnp.ones((2,)))
            f(jnp.ones((4,)))
        finally:
            set_ledger(prev)
        # the PR 6/9 singleton discipline: nothing allocated or
        # retained — no ring entries, no label state, no counters
        assert len(led) == 0
        assert led.label_stats() == {}
        assert led.total_compile_s == 0.0
        assert registry.counter("compile/calls").value == 0
        assert registry.histogram("compile/seconds").count == 0

    def test_attribute_delegation(self, ledger, registry):
        f = ledger_jit(lambda x: x + 1, label="toy/lower")
        compiled = f.lower(jnp.ones((2,))).compile()
        assert compiled is not None

    def test_enable_mid_run_starts_recording(self, registry):
        led = ProgramLedger(enabled=False)
        prev = set_ledger(led)
        try:
            f = ledger_jit(lambda x: x + 1, label="toy/late")
            f(jnp.ones((2,)))
            assert led.compiles() == 0
            led.enable()
            # already jit-cached, but the LEDGER never saw the
            # signature: recorded as a compile (the ledger answers
            # "would jit retrace", and for the invariant tests the
            # conservative read is the safe one)
            f(jnp.ones((2,)))
            assert led.compiles() == 1
            f(jnp.ones((2,)))
            assert led.compiles() == 1
        finally:
            set_ledger(prev)


class TestSteadyState:
    def test_mark_steady_scopes(self, ledger, registry):
        f = ledger_jit(lambda x: x + 1, label="serve/round")
        g = ledger_jit(lambda x: x - 1, label="train/step")
        f(jnp.ones((2,)))
        g(jnp.ones((2,)))
        ledger.mark_steady("serve/")
        f(jnp.ones((4,)))       # steady violation
        g(jnp.ones((4,)))       # train/ not marked: plain retrace
        assert ledger.steady_retraces() == 1
        assert ledger.steady_retraces("serve/") == 1
        assert ledger.steady_retraces("train/") == 0
        assert registry.counter("compile/steady_retraces").value == 1
        assert ledger.entries(1)[0]["steady"] is False  # train newest
        ledger.clear_steady("serve/")
        f(jnp.ones((6,)))
        assert ledger.steady_retraces() == 1    # withdrawn

    def test_forget_re_records_a_rebuild(self, ledger, registry):
        """forget(scope): a rebuilt program's compile at a
        previously-seen signature IS re-recorded (the rebind_world /
        engine-rebuild hook), counters stay monotonic, and the steady
        declaration is withdrawn so the rebuild window never counts
        as a retrace storm."""
        f = ledger_jit(lambda x: x + 1, label="train/step")
        f(jnp.ones((4,)))
        ledger.mark_steady("train/")
        assert ledger.compiles("train/") == 1
        ledger.forget("train/")
        assert not ledger.is_steady("train/step")
        # the "rebuild": a NEW jit of the same shape
        g = ledger_jit(lambda x: x + 1, label="train/step")
        g(jnp.ones((4,)))
        assert ledger.compiles("train/") == 2       # monotonic
        assert ledger.steady_retraces("train/") == 0
        entry = ledger.entries(scope="train/")[0]
        # diff reads vs the pre-rebuild signature: no change — the
        # attribution IS "a rebuild, not a shape leak"
        assert entry["diff"]["n_changed"] == 0

    def test_retrace_storm_alert_drill(self, ledger, registry):
        """The acceptance drill: an injected shape-churn workload
        fires the retrace-storm rule; the steady workload stays
        quiet.  Fake clock — hours of window history in
        microseconds."""
        rule = retrace_storm_rule(budget=0.001,
                                  windows=((600.0, 60.0, 2.0),))
        mgr = AlertManager([rule], registry=registry,
                           clock=lambda: 0.0, min_total=1)
        f = ledger_jit(lambda x: x * 2, label="serve/round")
        f(jnp.ones((4,)))               # warmup compile
        ledger.mark_steady("serve/")

        t = [0.0]
        mgr.clock = lambda: t[0]
        # steady phase: two windows of signature-identical traffic
        for _ in range(100):
            t[0] += 10.0
            f(jnp.ones((4,)))
            mgr.tick()
        assert mgr.firing() == ()

        # shape churn: every call a fresh signature — a retrace storm
        # (thirty of them, half of the long window: each is a compile,
        # and the rule fires at a 0.2 % share of either window)
        fired = []
        for n in range(5, 35):
            t[0] += 10.0
            f(jnp.ones((n,)))
            fired.extend(mgr.tick())
        assert "retrace-storm" in mgr.firing()
        assert any(e["transition"] == "fired" for e in fired)

        # the churn stops: both windows drain and the alert resolves
        resolved = []
        for _ in range(200):
            t[0] += 10.0
            f(jnp.ones((4,)))
            resolved.extend(mgr.tick())
        assert mgr.firing() == ()
        assert any(e["transition"] == "resolved" for e in resolved)


class TestMemoryAccountant:
    def test_gauges_and_watermarks(self, registry):
        acc = MemoryAccountant()
        state = {"w": jnp.ones((16, 16), jnp.float32)}
        acc.register("params", lambda: state)
        out = acc.sample(registry)
        assert out["params"] >= 16 * 16 * 4
        first = out["params"]
        g = registry.gauge("memory/params_bytes")
        assert g.last == first and g.max == first
        # shrink: last follows, watermark holds
        state["w"] = jnp.ones((4, 4), jnp.float32)
        out = acc.sample(registry)
        assert out["params"] < first
        g = registry.gauge("memory/params_bytes")
        assert g.last == out["params"] and g.max == first
        rows = {r["subsystem"]: r for r in acc.table()}
        assert rows["params"]["high_watermark"] == first
        assert rows["total"]["bytes"] == out["params"]

    def test_replication_counts_per_shard(self, registry):
        """A replicated sharded array holds one copy per device — the
        accountant reports DEVICE bytes, not logical bytes."""
        n_dev = jax.device_count()
        if n_dev < 2:
            pytest.skip("needs a multi-device mesh")
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("d",))
        x = jax.device_put(jnp.ones((8, 8), jnp.float32),
                           NamedSharding(mesh, P()))
        acc = MemoryAccountant()
        acc.register("replicated", [x])
        out = acc.sample(registry)
        assert out["replicated"] == 8 * 8 * 4 * n_dev

    def test_broken_root_degrades(self, registry):
        acc = MemoryAccountant()

        def broken():
            raise RuntimeError("boom")

        acc.register("bad", broken)
        out = acc.sample(registry)
        assert out["bad"] == 0
        rows = {r["subsystem"]: r for r in acc.table()}
        assert "boom" in rows["bad"]["error"]

    def test_cross_rank_merge_determinism(self):
        """Memory gauges merge max-of-{last,max}: folding the same
        per-rank snapshots in ANY order yields one identical merged
        registry — the rank-0-exposition safety property."""
        snaps = []
        for rank_bytes in (1024, 4096, 2048):
            reg = MetricsRegistry(enabled=True)
            reg.set("memory/params_bytes", rank_bytes)
            reg.set("memory/total_bytes", rank_bytes + 512)
            snaps.append(reg.snapshot())

        def fold(order):
            merged = MetricsRegistry(enabled=True)
            for i in order:
                merged.load(snaps[i])
            return merged.snapshot()

        import itertools

        folded = [fold(order)
                  for order in itertools.permutations(range(3))]
        assert all(f == folded[0] for f in folded)
        assert folded[0]["memory/params_bytes"]["max"] == 4096


class TestProgramz:
    def test_endpoint_serves_ledger_and_memory(self, ledger, registry):
        f = ledger_jit(lambda x: x + 1, label="serve/round")
        f(jnp.ones((2,)))
        f(jnp.ones((4,)))
        acc = MemoryAccountant()
        acc.register("pool", [jnp.ones((32,), jnp.float32)])
        srv = StatuszServer(ledger=ledger, accountant=acc,
                            registry=registry)
        srv.start()
        try:
            doc = json.loads(urllib.request.urlopen(
                srv.url("/programz"), timeout=5).read())
            assert doc["ledger"]["compiles"] == 2
            assert doc["programs"][0]["label"] == "serve/round"
            assert doc["programs"][0]["diff"]["kinds"] == ["shape"]
            mem = {r["subsystem"]: r for r in doc["memory"]}
            assert mem["pool"]["bytes"] == 128
            # the scrape refreshed the gauges too
            assert registry.gauge("memory/pool_bytes").last == 128
            # scope filter
            doc2 = json.loads(urllib.request.urlopen(
                srv.url("/programz?scope=train/"), timeout=5).read())
            assert doc2["programs"] == []
            # the route is advertised in the 404 routes list
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(srv.url("/nope"), timeout=5)
            assert exc.value.code == 404
            assert "/programz" in json.loads(
                exc.value.read())["routes"]
        finally:
            srv.stop()


class TestGoodputCompileBadput:
    def test_compile_badput_category(self, ledger, registry):
        rec = TraceRecorder(enabled=True)
        report = GoodputReport(recorder=rec, write=False,
                               registry=registry)
        report.initialize()
        # window 1: a compile happens (ledger accumulates its wall
        # time), inside a dispatch span that would otherwise bill it
        # as productive
        with rec.span("step/dispatch", cat="step"):
            f = ledger_jit(lambda x: (x * 2).sum(), label="train/step")
            jax.block_until_ready(f(jnp.ones((256, 256))))
        report()
        rep = report.last_report
        compile_s = rep["badput"]["compile_s"]
        assert compile_s > 0.0
        assert compile_s == pytest.approx(ledger.total_compile_s)
        # moved OUT of productive: productive + compile ≈ the span
        assert rep["productive_s"] >= 0.0
        assert registry.counter("goodput/compile_s").value == \
            pytest.approx(compile_s)
        # window 2: steady traffic, no compile — the category is zero
        with rec.span("step/dispatch", cat="step"):
            jax.block_until_ready(f(jnp.ones((256, 256))))
        report()
        assert report.last_report["badput"]["compile_s"] == 0.0

    def test_serving_compiles_do_not_bill_training(self, ledger,
                                                   registry):
        """A colocated serving engine's compiles (serve/*, spec/*)
        must never depress a TRAINING window's goodput — the compile
        delta is scoped to the training-side label prefixes."""
        rec = TraceRecorder(enabled=True)
        report = GoodputReport(recorder=rec, write=False,
                               registry=registry)
        report.initialize()
        g = ledger_jit(lambda x: x * 3, label="serve/round")
        jax.block_until_ready(g(jnp.ones((64, 64))))
        assert ledger.total_compile_s > 0
        report()
        assert report.last_report["badput"]["compile_s"] == 0.0

    def test_ledger_swap_resets_baseline(self, ledger, registry):
        rec = TraceRecorder(enabled=True)
        report = GoodputReport(recorder=rec, write=False,
                               registry=registry)
        report.initialize()
        f = ledger_jit(lambda x: x + 1, label="train/step")
        f(jnp.ones((2,)))
        report()
        assert report.last_report["badput"]["compile_s"] > 0
        # a fresh (cleared) ledger mid-run: the next window must not
        # difference against the stale larger baseline
        ledger.clear()
        report()
        assert report.last_report["badput"]["compile_s"] == 0.0
