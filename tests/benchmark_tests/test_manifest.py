"""``BENCHMARK.json`` against the contract's limits, and discovery: every
cell, configuration, traffic file, driver, reference and per-layer
reader it names is found by that name alone."""

import json
import os
import re

import pytest

from benchmarks.lib import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = cells.manifest()
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _names():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            yield group, entry["name"]
    for w in BENCH["workloads"]:
        yield "config-of", w["config"]
        yield "traffic-of", w["traffic"]
    for c in BENCH["configs"]:
        for key in c["reduced"]:
            yield "reduced", key


@pytest.mark.parametrize("group,name", sorted(set(_names())))
def test_name_is_allowed(group, name):
    assert NAME.match(name), (group, name)


def test_names_are_unique():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


# a manifest list, or a metric's ``workloads``, indexed or sliced
_BY_POSITION = re.compile(
    r"""\[["'](?:configs|workloads|per_layer|end_to_end)["']\]\s*\[\s*[-\d:]"""
    r"""|get\(["']workloads["'][^)\n]*\)\s*\[\s*[-\d:]""")


def test_no_test_indexes_a_manifest_list_by_position():
    """Entries are appended and found by name: a test that says where
    one stands (``[-1]``, ``[-6:]``, ``[:-1]``) stops the next PR that
    appends, and only a ``benchmark`` PR may then edit it (PRs 33-38)."""
    found = []
    for base, dirs, files in os.walk(os.path.join(cells.ROOT, "tests")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(base, name)) as f:
                    found += [(name, n, line.strip())
                              for n, line in enumerate(f, 1)
                              if _BY_POSITION.search(line)]
    assert not found, found


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    # 2 + 14 runs a cell at the full 24 cells, 60 s each beyond the
    # window, 2 x 90 s a cell to compile, 1200 s spare, in 43200 s
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 \
        + 1200 <= 43200
    assert len(json.dumps(BENCH)) <= 64 * 1024
    assert 1 <= len(BENCH["workloads"]) <= 24
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word


@pytest.mark.parametrize(
    "metric", BENCH["end_to_end"] + BENCH["per_layer"],
    ids=lambda m: m["name"])
def test_metric_entry(metric):
    end_to_end = metric in BENCH["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert keys <= set(metric) <= keys | {"workloads"}
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    known = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", known)) <= known
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
        return
    moved = next(m for m in BENCH["end_to_end"]
                 if m["name"] == metric["moves"])
    # every cell that reports the metric reports the one it moves
    assert set(metric.get("workloads", known)) \
        <= set(moved.get("workloads", known))
    assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
    if "roofline" in metric["name"]:
        assert metric["name"].endswith("_roofline") \
            and metric["unit"] == "%"


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_is_found_by_name(cell):
    """The cell's files, real and rehearsal, its driver, its reference
    and its readers all load; it reports ``setup_s``, another end-to-end
    metric and a per-layer metric."""
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    for rehearse in (False, True):
        _, config, traffic = cells.load_cell(cell["name"], rehearse)
        assert callable(cells.driver(traffic["driver"]))
        reference = cells.module("reference", config["reference"])
        assert callable(reference.init) and callable(reference.follow)
        assert set(config["check"]["limits"]) == {
            "loss_gap.step1", "loss_gap.step2", "loss_gap.step3",
            "grad_norm_gap", "delta_norm_gap", "grad_norm_gap.median",
            "delta_norm_gap.median"}
        reported = {m["name"] for m in cells.end_to_end(cell["name"])}
        assert {traffic["rate_metric"], traffic["tail_metric"],
                "setup_s"} == reported
        # the optimizer is the configuration's: optax's name for it, its
        # arguments, and where its state keeps the first gradient
        import optax
        block = config["optimizer"]
        assert set(block) == {"name", "args", "first_gradient"}
        assert callable(getattr(optax, block["name"]))
        assert set(block["first_gradient"]) == {"state_field", "times"}
    reported = {m["name"] for m in cells.end_to_end(cell["name"])}
    assert "setup_s" in reported and len(reported) >= 2
    readers = cells.readers(cell["name"])
    assert readers and all(callable(r) for r in readers.values())


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])
    with open(os.path.join(cells.ROOT, config["file"])) as f:
        on_disk = json.load(f)
    for key in config["reduced"]:
        assert key in on_disk["reduced"], key
        # no width may be reduced
        assert not re.search(
            r"(_dim$|_rank$|_size$|head|expansion|per_tok)", key)


def _one_update(spec):
    import jax.numpy as jnp

    from benchmarks.lib import harness

    params = {"w": jnp.ones((3,)), "b": jnp.zeros((2,))}
    grads = {"w": jnp.full((3,), 2.0), "b": jnp.full((2,), -1.0)}
    opt = harness.build_optimizer(spec)
    return opt.update(grads, opt.init(params), params)[1]


@pytest.mark.parametrize("spec,field,times", [
    ({"name": "adamw", "args": {"learning_rate": 1e-3, "b1": 0.8}},
     "mu", 5.0),
    ({"name": "sgd", "args": {"learning_rate": 0.1, "momentum": 0.9}},
     "trace", 1.0),
    ({"name": "lion", "args": {"learning_rate": 1e-4, "b1": 0.9,
                               "b2": 0.99}}, "mu", 100.0)])
def test_an_optimizer_is_data(spec, field, times):
    """Any optimizer optax has is built from the configuration's block,
    and the first gradient is read from the field of its state that the
    block names (lion's ``mu`` after one step is ``1 - b2`` of it)."""
    from benchmarks.lib import harness

    norms = harness.first_gradient_norms(
        _one_update(spec), {"state_field": field, "times": times})
    assert norms == pytest.approx(
        {"['w']": 2 * 3 ** 0.5, "['b']": 2 ** 0.5}, rel=1e-5)


def test_a_state_without_the_named_field_is_an_error():
    from benchmarks.lib import harness

    plain_sgd = _one_update({"name": "sgd", "args": {"learning_rate": 0.1}})
    with pytest.raises(SystemExit):
        harness.first_gradient_norms(
            plain_sgd, {"state_field": "trace", "times": 1.0})


def test_every_file_under_paths_has_an_allowed_name():
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in BENCH["paths"]:
        for base, dirs, files in os.walk(os.path.join(cells.ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(base, name), cells.ROOT)
                assert allowed.match(rel), rel


def test_unknown_device_kind_is_an_error():
    from benchmarks.lib.peaks import peaks

    assert peaks("TPU v5 lite")["flops_per_s"] == 197e12
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
