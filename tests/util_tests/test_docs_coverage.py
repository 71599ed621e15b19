"""CI satellite (ISSUE 13, extended in ISSUE 15): every metric name
the stack registers at runtime — and every flight-recorder span name
it records — must appear in docs/OBSERVABILITY.md's name tables: a
counter (or a span) that ships without documentation is a dashboard
nobody can interpret.  The scan is static over the package source
(the same names the runtime registers: every ``reg.inc/observe/
set("...")`` call site, every ``rec.span/instant/counter("...")``
site), plus the dynamic families, each expanded or template-checked:
``serve/shed_<reason>`` (over ``SHED_REASONS``),
``compile/retraces_<label>`` and ``memory/<subsystem>_bytes`` (the
program-ledger/accountant families), and the ``compile/<label>`` span
family."""

import os
import re

_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", ".."))
_PKG = os.path.join(_ROOT, "chainermn_tpu")
_DOC = os.path.join(_ROOT, "docs", "OBSERVABILITY.md")

# a registry record call with a literal slash-namespaced name:
# reg.inc("serve/admits"), registry.observe('comm/kv_wait', ...), ...
_CALL = re.compile(
    r"\.(?:inc|observe|set)\(\s*\n?\s*['\"]"
    r"([a-z_]+/[a-z0-9_]+)['\"]")
# a flight-recorder record call with a literal name.  ``.record`` is
# deliberately excluded: the Profiler shares that method name
# (``prof.record(name, seconds)``, e.g. ``profiled_communicator``'s
# ``comm.<collective>`` rows; the updater's ``updater/*`` rows left
# in PR 24) and its names are a different (printed-table) namespace.
_SPAN_CALL = re.compile(
    r"\.(?:span|instant|counter)\(\s*\n?\s*['\"]"
    r"([a-z_]+/[a-z0-9_]+)['\"]")
# the dynamic families
_DYNAMIC_SHED = re.compile(r"['\"]serve/shed_['\"]\s*\+\s*reason")
_DYNAMIC_RETRACES = re.compile(
    r"['\"]compile/retraces_['\"]\s*\+\s*_slug\(label\)")
_DYNAMIC_MEMORY = re.compile(r"memory/\{_slug\(name\)\}_bytes")
_DYNAMIC_COMPILE_SPAN = re.compile(r"f\"compile/\{label\}\"")


def _walk_sources():
    for dirpath, _dirnames, filenames in os.walk(_PKG):
        if "__pycache__" in dirpath:
            continue
        for fn in filenames:
            if fn.endswith(".py"):
                yield open(os.path.join(dirpath, fn)).read()


def _registered_names():
    names = set()
    saw = {"shed": False, "retraces": False, "memory": False}
    for src in _walk_sources():
        names.update(_CALL.findall(src))
        saw["shed"] |= bool(_DYNAMIC_SHED.search(src))
        saw["retraces"] |= bool(_DYNAMIC_RETRACES.search(src))
        saw["memory"] |= bool(_DYNAMIC_MEMORY.search(src))
    for family, present in saw.items():
        assert present, (
            f"the dynamic {family} metric call site moved — update "
            "this test's dynamic-name handling alongside it")
    from chainermn_tpu.serving.admission import SHED_REASONS

    names.discard("serve/shed_")        # concat prefixes, not names
    names.discard("compile/retraces_")
    names.update(f"serve/shed_{r}" for r in SHED_REASONS)
    return names


def _span_names():
    names = set()
    saw_compile = False
    for src in _walk_sources():
        names.update(_SPAN_CALL.findall(src))
        saw_compile |= bool(_DYNAMIC_COMPILE_SPAN.search(src))
    assert saw_compile, (
        "the ledger's compile/<label> span call site moved — update "
        "this test's dynamic-name handling alongside it")
    return names


def test_scan_finds_the_known_core():
    """The scanner itself must keep working: a regression that finds
    nothing would vacuously pass the coverage checks below."""
    names = _registered_names()
    for expected in ("serve/ttft", "serve/shed_total",
                     "serve/shed_overload", "train/step_time",
                     "checkpoint/snapshots_written", "comm/kv_retries",
                     "watchdog/stalls", "alerts/fired",
                     "elastic/live_resizes", "compile/retraces",
                     "compile/seconds", "compile/steady_retraces",
                     "memory/total_bytes", "goodput/compile_s"):
        assert expected in names
    assert len(names) > 40


def test_span_scan_finds_the_known_core():
    spans = _span_names()
    for expected in ("step/host", "serve/decode_round",
                     "serve/prefill", "checkpoint/save",
                     "autotune/probe", "watchdog/heartbeat",
                     "elastic/live_resize", "straggler/report"):
        assert expected in spans
    assert len(spans) > 20


def test_every_runtime_metric_name_is_documented():
    doc = open(_DOC).read()
    missing = []
    for name in sorted(_registered_names()):
        if name in doc:
            continue
        # the doc may list a dynamic family by its template row
        if name.startswith("serve/shed_") \
                and "serve/shed_<reason>" in doc:
            continue
        missing.append(name)
    # the dynamic families must be documented as template rows
    for template in ("compile/retraces_<label>",
                     "memory/<subsystem>_bytes"):
        if template not in doc:
            missing.append(template)
    assert not missing, (
        "metric names registered at runtime but absent from "
        f"docs/OBSERVABILITY.md's name table: {missing}")


def test_every_recorder_span_name_is_documented():
    """The ISSUE 15 extension: span names are operator surface too —
    they appear in Perfetto lanes, stall-report tails and goodput
    decompositions, so the flight-recorder table must name them."""
    doc = open(_DOC).read()
    missing = [name for name in sorted(_span_names())
               if name not in doc]
    if "compile/<label>" not in doc:
        missing.append("compile/<label>")
    assert not missing, (
        "flight-recorder span names recorded at runtime but absent "
        f"from docs/OBSERVABILITY.md: {missing}")


def test_every_device_scope_is_documented_and_worn_through_the_list():
    """PR 34: the names the step programs put on the device's ops are
    operator surface as well (XProf folds a step by them), and they are
    one closed list: each is in the doc's table, and no module names a
    scope past ``device_scope``."""
    from chainermn_tpu.utils.telemetry import (
        DEVICE_SCOPES, DEVICE_SCOPES_GDN, DEVICE_SCOPES_SHORTCONV,
        DEVICE_SCOPES_SSM)

    # one vocabulary in four tuples (telemetry.py says why)
    DEVICE_SCOPES += DEVICE_SCOPES_SSM + DEVICE_SCOPES_GDN \
        + DEVICE_SCOPES_SHORTCONV
    doc = open(_DOC).read()
    missing = [s for s in DEVICE_SCOPES if f"`{s}`" not in doc]
    assert not missing, (
        f"device scopes absent from docs/OBSERVABILITY.md: {missing}")
    worn = set()
    for src in _walk_sources():
        # the one call allowed is device_scope's own
        assert len(re.findall(r"(?<!`)jax\.named_scope\(", src)) == (
            1 if "def device_scope(" in src else 0)
        worn.update(re.findall(r"device_scope\(\s*f?\"([^\"]+)\"", src))
    worn = {"attn/<kind>" if s.startswith("attn/") else s for s in worn}
    assert worn == set(DEVICE_SCOPES)
