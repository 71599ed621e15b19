"""Everything is found by the name ``BENCHMARK.json`` gives it: a cell's
configuration and traffic files, its driver, the per-layer readers."""

import functools
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmarks")


@functools.lru_cache(maxsize=None)
def manifest():
    """``BENCHMARK.json``, read once; callers do not change it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(name, rehearse=False):
    """``(cell, config, traffic)`` of the workload ``name``.  A rehearsal
    takes the toy-size twins kept under ``rehearse/`` beside each."""
    bench = manifest()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    cell = cells[name]
    config_file = next(c["file"] for c in bench["configs"]
                       if c["name"] == cell["config"])
    traffic_file = os.path.join(HERE, "traffic", cell["traffic"] + ".json")
    config_file = os.path.join(ROOT, config_file)
    if rehearse:
        config_file, traffic_file = (
            os.path.join(os.path.dirname(p), "rehearse", os.path.basename(p))
            for p in (config_file, traffic_file))
    return cell, _json(config_file), _json(traffic_file)


def module(directory, name):
    path = os.path.join(HERE, directory, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"no {directory}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{directory}.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def driver(kind):
    """``benchmarks/drivers/<kind>.py``; its ``run(run) -> Outcome``."""
    return module("drivers", kind).run


def readers(cell_name):
    """``{metric name: read(ctx)}`` for the per-layer metrics that list
    this cell (or list none), each from
    ``benchmarks/layer_metrics/<metric>.py``."""
    return {m["name"]: module("layer_metrics", m["name"]).read
            for m in manifest()["per_layer"]
            if cell_name in m.get("workloads", [cell_name])}


def end_to_end(cell_name):
    return [m for m in manifest()["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]
