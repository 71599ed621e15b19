"""The program's vocabulary of device scopes and its one reader: every
name of ``DEVICE_SCOPES`` reaches the compiled toy step of the cell
that has the layer, forward and backward; the classifier on op names
cut from the chip's traces; ``lib/scopes_step.py`` on a map made by
hand and on a fixture cut from a chip run; the ten readers of PR 34
(in the manifest since PR 39) and the waiting room
(``tools/pending_per_layer.json``)."""

import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from benchmarks.lib import (
    cells, op_names, scopes, scopes_hybrid, scopes_mixed, scopes_step,
)
from benchmarks.lib.harness import build_optimizer
from chainermn_tpu.utils.telemetry import (
    DEVICE_SCOPES, classify_op_name, device_scope,
)

HERE = os.path.dirname(os.path.abspath(__file__))
OPT, FSDP, MELLUM, LAGUNA, KIMI, RESNET = (
    "opt-1.3b-l8-train-4x2048", "opt-1.3b-fsdp4-train-8x2048",
    "mellum2-12b-l4-ep4-train-2x8192", "laguna-xs2-l5-ep8-train-seq8192",
    "kimi-linear-l5-ep32-train-tok16384", "resnet50-trainer-b256")
# PR 34's ten, in the manifest since PR 39; whatever has a reader and no
# manifest entry yet waits in ``tools/pending_per_layer.json``
TEN = ("step.forward_ms", "step.backward_ms", "step.optimizer_ms",
       "step.head_ms", "step.unscoped_ms", "attn.proj_ms_per_step",
       "attn.glue_ms_per_step", "step.conv_ms.resnet",
       "step.bn_stats_ms.resnet", "step.bn_apply_ms.resnet")
with open(os.path.join(cells.HERE, "tools", "pending_per_layer.json")) as _f:
    PENDING = json.load(_f)["per_layer"]

_STEP = {"step/embed", "step/layers", "step/head"}
_SOFTMAX = {"attn.qkv", "attn.core", "attn.out"}
_MOE = {"moe/route", "moe/experts", "moe/combine"}
# what each cell's step wears, forward and backward alike
# (``step/optimizer`` is in every cell, in the update alone)
WORN = {
    OPT: _STEP | _SOFTMAX | {"attn/full", "mlp/dense"},
    FSDP: _STEP | _SOFTMAX | {"attn/full", "mlp/dense", "fsdp/gather"},
    MELLUM: _STEP | _SOFTMAX | _MOE | {
        "attn/full", "attn/sliding", "attn.rope", "attn.kv_repeat"},
    LAGUNA: _STEP | _SOFTMAX | _MOE | {
        "attn/full", "attn/sliding", "attn.rope", "attn.kv_repeat",
        "attn.gate", "mlp/dense", "moe/shared"},
    KIMI: _STEP | _SOFTMAX | _MOE | {
        "attn/kda", "attn/mla", "attn.kv_repeat", "mla/latent",
        "kda/conv", "kda/gate", "kda/scan", "kda.pairs", "kda.solve",
        "kda.intra", "kda.inter", "mlp/dense", "moe/shared"},
    RESNET: {"resnet/conv", "bn/stats", "bn/apply"},
}


def _lm_text(name):
    from chainermn_tpu.models import (
        init_transformer, make_train_step, param_specs)
    from chainermn_tpu.parallel import MeshConfig

    cell, cfg, job = cells.load_cell(name, rehearse=True)
    pcfg = cells.module("drivers", job["driver"])._program_config(cfg, job)
    mc = MeshConfig(devices=jax.devices()[:cell["chips"]], **job["mesh"])
    opt = build_optimizer(cfg["optimizer"])
    shapes = jax.eval_shape(
        lambda: init_transformer(jax.random.PRNGKey(0), pcfg))
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=mc.sharding(*s)),
        shapes, param_specs(pcfg))
    tok = jax.ShapeDtypeStruct((job["batch"], job["seq"]), jnp.int32)
    return make_train_step(mc, pcfg, opt).lower(
        params, jax.eval_shape(opt.init, params), tok, tok).compile(
        ).as_text()


def _resnet_text(name):
    import numpy as np

    import chainermn_tpu as cmn
    from chainermn_tpu.models import (
        ResNetConfig, init_resnet, resnet_apply, softmax_cross_entropy)

    driver = cells.module("drivers", "trainer")
    _, cfg, job = cells.load_cell(name, rehearse=True)
    rcfg = ResNetConfig(depth=cfg["depth"], num_classes=cfg["num_classes"],
                        width=cfg["width"], dtype=cfg["dtype"])
    params, state = init_resnet(jax.random.PRNGKey(0), rcfg)
    comm = cmn.create_communicator("tpu_xla", devices=jax.devices()[:1])
    xs = np.zeros((job["batch"], cfg["image_size"], cfg["image_size"], 3),
                  np.float32)
    ys = np.zeros((job["batch"],), np.int32)

    def loss_fn(params, state, x, y):
        logits, new_state = resnet_apply(
            rcfg, params, state, x, train=True, axis_name=comm.axis_name)
        return softmax_cross_entropy(logits, y), new_state

    updater = cmn.StandardUpdater(
        cmn.SerialIterator(list(zip(xs, ys)), job["batch"], shuffle=False),
        cmn.create_multi_node_optimizer(
            build_optimizer(cfg["optimizer"]), comm),
        loss_fn, params, comm, state=state)
    return updater._get_step(2, 1, 1).lower(*driver._step_args(
        updater, jax.device_put(xs), jax.device_put(ys))).compile().as_text()


_TEXTS = {}


def _text(name):
    """The cell's compiled toy step, compiled once a test process."""
    if name not in _TEXTS:
        _TEXTS[name] = (_resnet_text if name == RESNET else _lm_text)(name)
    return _TEXTS[name]


def _worn(text):
    """``{scope: phases}`` over every op name of a compiled text."""
    found = {}
    for op_name in set(re.findall(r'op_name="([^"]*)"', text)):
        phase, path = classify_op_name(op_name)
        for scope in path:
            found.setdefault(scope, set()).add(phase)
    return found


@pytest.mark.parametrize("cell", sorted(WORN))
def test_every_scope_is_in_the_compiled_toy_step(cell):
    found = _worn(_text(cell))
    for scope in WORN[cell]:
        assert {"forward", "backward"} <= found.get(scope, set()), (
            scope, found.get(scope))
    # what is not differentiated wears no ``jvp(``
    assert found["step/optimizer"] == {"update"}
    # and nothing is worn that the vocabulary does not know
    kinds = {s for s in found if s.startswith("attn/")}
    assert set(found) - kinds <= set(DEVICE_SCOPES)
    assert set(found) == WORN[cell] | {"step/optimizer"}


def test_the_cells_together_wear_the_whole_vocabulary():
    worn = set().union(*WORN.values()) | {"step/optimizer"}
    kinds = {s for s in worn if s.startswith("attn/")}
    assert kinds == {"attn/full", "attn/sliding", "attn/kda", "attn/mla"}
    assert (worn - kinds) | {"attn/<kind>"} == set(DEVICE_SCOPES)


@pytest.mark.parametrize("cell", [MELLUM, LAGUNA, KIMI])
def test_accepted_readers_read_what_they_read_without_the_new_names(cell):
    """The three accepted scope readers take the innermost name their
    own pattern knows: a text from which every name this vocabulary
    added is deleted again (the parent's, but for the names of its
    instructions) gives them the same instruction -> scope map."""
    text = _text(cell)
    added = (r"/(?:attn\.\w+|kda\.\w+|fsdp/gather|step/(?:embed|layers|"
             r"head|optimizer))(?=[/\")])")
    without = re.sub(added, "", re.sub(
        r"\((?:step/(?:embed|layers|head))\)", "()", text))
    assert not any(s in without for s in (
        "attn.qkv", "kda.pairs", "step/layers", "step/head"))
    assert scopes.instruction_scopes(text)
    for reader in (scopes, scopes_mixed, scopes_hybrid):
        assert reader.instruction_scopes(text) \
            == reader.instruction_scopes(without)


# -- the vocabulary and the classifier -------------------------------- #

def test_device_scope_refuses_a_name_outside_the_vocabulary():
    for name in DEVICE_SCOPES:
        if name != "attn/<kind>":
            with device_scope(name):
                pass
    with device_scope("attn/any_kind-2"):
        pass
    for name in ("attn/<kind>", "attn/a/b", "attn/", "rope", "moe/other",
                 "step", "kda.scan", ""):
        with pytest.raises(ValueError, match="DEVICE_SCOPES"):
            device_scope(name)


# op names as the chip's traces carry them (my chip runs, PR 34: cut
# from the ``XLA Ops`` events of the cells' traced windows)
_CHIP_NAMES = [
    # a forward fusion of the scanned stack, a child inside its layer
    ("jit(step)/jvp(step/layers)/while/body/closed_call/attn/sliding/"
     "attn.qkv/dot_general", "forward",
     ("step/layers", "attn/sliding", "attn.qkv")),
    # the flash forward kernel (a custom VJP's primal call)
    ("jit(step)/jvp(step/layers)/while/body/closed_call/attn/sliding/"
     "attn.core/pallas_call", "forward",
     ("step/layers", "attn/sliding", "attn.core")),
    # its dq / dkv kernels: the custom VJP's backward rule
    ("jit(step)/transpose(jvp(step/layers))/while/body/closed_call/"
     "checkpoint/attn/full/attn.core/pallas_call", "backward",
     ("step/layers", "attn/full", "attn.core")),
    # what the block's checkpoint runs again
    ("jit(step)/transpose(jvp(step/layers))/while/body/closed_call/"
     "checkpoint/rematted_computation/moe/route/top_k", "recompute",
     ("step/layers", "moe/route")),
    # the head's custom VJP, forward and backward
    ("jit(step)/jvp(step/head)/dot_general", "forward", ("step/head",)),
    ("jit(step)/transpose(jvp(step/head))/dot_general", "backward",
     ("step/head",)),
    ("jit(step)/transpose(jvp(step/embed))/scatter-add", "backward",
     ("step/embed",)),
    # the optimizer, and a rotary table no parameter reaches
    ("jit(step)/step/optimizer/mul", "update", ("step/optimizer",)),
    ("jit(step)/step/layers/attn/full/attn.rope/cos", "update",
     ("step/layers", "attn/full", "attn.rope")),
    # FSDP's gather and, transposed, its reduce-scatter
    ("jit(step)/jvp(step/layers)/while/body/closed_call/fsdp/gather/"
     "all_gather", "forward", ("step/layers", "fsdp/gather")),
    ("jit(step)/transpose(jvp(step/layers))/while/body/closed_call/"
     "checkpoint/fsdp/gather/reduce_scatter", "backward",
     ("step/layers", "fsdp/gather")),
    # the KDA recurrence's inverse kernel inside the slab's own remat
    ("jit(step)/transpose(jvp(step/layers))/while/body/closed_call/"
     "checkpoint/attn/kda/kda/scan/while/body/checkpoint/"
     "rematted_computation/kda.solve/pallas_call", "recompute",
     ("step/layers", "attn/kda", "kda/scan", "kda.solve")),
    # the older spelling, a scope inside the transformation's brackets
    ("jit(step)/transpose(jvp(attn/kda))/kda/scan/while/body/dot_general",
     "backward", ("attn/kda", "kda/scan")),
    # batch-norm through the updater
    ("jit(step)/jit(main)/jvp(bn/stats)/reduce_sum", "forward",
     ("bn/stats",)),
    # the compiler's own names hold no name stack: the grouped-matmul
    # kernels of lax.ragged_dot stay unscoped HERE (lib/scopes_step.py
    # puts them under moe/experts by their instruction's name)
    ("ragged-dot-none", "unnamed", ()),
    ("", "unnamed", ()),
    # a name that only looks like one of ours
    ("jit(step)/jvp()/xattn/full/mul", "forward", ()),
    ("jit(step)/jvp()/attn.qkvs/mul", "forward", ()),
]


@pytest.mark.parametrize("op_name,phase,path", _CHIP_NAMES)
def test_classify_op_name(op_name, phase, path):
    assert classify_op_name(op_name) == (phase, path)


# -- the reader on a map made by hand --------------------------------- #

class _Window:
    iterations = 4


def _ctx(names, self_s):
    return {"trace": {"op_self_s": self_s}, "window": _Window,
            "facts": {}, op_names.KEY: names}


_F, _B = "jit(step)/jvp(step/layers)/", "jit(step)/transpose(jvp(step/layers))/"
_HAND = {
    "fusion.1": _F + "attn/full/attn.qkv/dot_general",
    "fusion.2": _F + "attn/full/attn.rope/mul",
    "fusion.3": _F + "attn/full/attn.kv_repeat/broadcast_in_dim",
    "full.4": _F + "attn/full/attn.core/pallas_call",
    "fusion.5": _F + "attn/full/attn.gate/logistic",
    "fusion.6": _F + "attn/full/attn.out/dot_general",
    "copy.7": _F + "attn/full/transpose",
    "fusion.8": _B + "checkpoint/attn/sliding/attn.qkv/dot_general",
    "fusion.9": _B + "checkpoint/rematted_computation/attn/sliding/"
                     "attn.qkv/dot_general",
    "fusion.10": "jit(step)/step/optimizer/mul",
    "fusion.11": "jit(step)/jvp(step/head)/dot_general",
    "fusion.12": "jit(step)/transpose(jvp(step/embed))/scatter-add",
    "fusion.13": _F + "fsdp/gather/convert_element_type",
    "all-gather.14": _F + "fsdp/gather/all_gather",
    "ragged-dot-none.15": "ragged-dot-none",
    "fusion.16": _F + "add",
    "fusion.17": "jit(step)/jvp()/mul",
}
_SELF = {name: 0.004 * (i + 1) for i, name in enumerate(_HAND)}
_SELF["copy.18"] = 0.008           # an instruction with no op name


def _ms(*names):
    return sum(_SELF[n] for n in names) * 1e3 / 4


def test_scopes_step_on_a_hand_made_map():
    ctx = _ctx(_HAND, _SELF)
    parts = scopes_step.attention_parts(ctx)
    assert parts["whole"] == pytest.approx(_ms(
        *(n for n, op in _HAND.items() if "attn/" in op)))
    assert sum(v for k, v in parts.items() if k != "whole") \
        == pytest.approx(parts["whole"])
    assert parts["rest"] == pytest.approx(_ms("copy.7"))
    assert parts["attn.qkv"] == pytest.approx(
        _ms("fusion.1", "fusion.8", "fusion.9"))
    assert scopes_step.phase_ms(ctx, "recompute") == pytest.approx(
        _ms("fusion.9"))
    assert scopes_step.phase_ms(ctx, "backward") == pytest.approx(
        _ms("fusion.8", "fusion.12"))
    assert scopes_step.phase_ms(ctx, "update") == pytest.approx(
        _ms("fusion.10"))
    # the compiler's own name and the instruction without one
    assert scopes_step.phase_ms(ctx, "unnamed") == pytest.approx(
        _ms("ragged-dot-none.15", "copy.18"))
    assert sum(scopes_step.phase_ms(ctx, ph) for ph in scopes_step.PHASES) \
        == pytest.approx(sum(_SELF.values()) * 1e3 / 4)
    # the grouped products stand under moe/experts by instruction name
    assert scopes_step.path_ms(ctx, "moe/experts") == pytest.approx(
        _ms("ragged-dot-none.15"))
    assert scopes_step.unscoped_ms(ctx) == pytest.approx(
        _ms("fusion.17", "copy.18"))
    assert scopes_step.path_ms(ctx, "step/layers") == pytest.approx(_ms(
        *(n for n, op in _HAND.items() if "step/layers" in op)))
    assert scopes_step.path_ms(ctx, "step/layers", "attn.qkv") \
        == pytest.approx(parts["attn.qkv"])
    assert scopes_step.path_ms(ctx, "attn.qkv", "step/layers") is None
    read = {name: cells.module("layer_metrics", name).read for name in TEN}
    assert read["step.forward_ms"](ctx) == pytest.approx(_ms(
        *(n for n, op in _HAND.items() if "/jvp(" in op)))
    assert read["step.backward_ms"](ctx) == pytest.approx(
        _ms("fusion.8", "fusion.9", "fusion.12"))
    assert read["step.optimizer_ms"](ctx) == pytest.approx(_ms("fusion.10"))
    assert read["step.head_ms"](ctx) == pytest.approx(
        _ms("fusion.11", "fusion.12"))
    assert read["step.unscoped_ms"](ctx) == pytest.approx(
        _ms("fusion.17", "copy.18"))
    assert read["attn.proj_ms_per_step"](ctx) == pytest.approx(
        _ms("fusion.1", "fusion.6", "fusion.8", "fusion.9"))
    assert read["attn.glue_ms_per_step"](ctx) == pytest.approx(
        _ms("fusion.2", "fusion.3", "fusion.5", "copy.7"))
    # the gather's scope holds its collectives and what is cast for them
    assert scopes_step.path_ms(ctx, "fsdp/gather") == pytest.approx(
        _ms("fusion.13", "all-gather.14"))
    # a step without the layer: nothing to report
    for name in ("step.conv_ms.resnet", "step.bn_stats_ms.resnet",
                 "step.bn_apply_ms.resnet"):
        assert read[name](ctx) is None


def test_scopes_table_prints_every_path_by_phase(capsys):
    scopes_step.table(_ctx(_HAND, _SELF))
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("[scopes] ")]
    paths = [ln.split()[1] for ln in lines]
    assert "path=(none)" in paths and "path=moe/experts" in paths
    assert "path=step/layers>attn/full>attn.core" in paths
    total = sum(float(re.search(r"total=([\d.]+)", ln).group(1))
                for ln in lines)
    assert total == pytest.approx(sum(_SELF.values()) * 1e3 / 4, abs=0.01)


@pytest.mark.parametrize("metric", TEN + tuple(m["name"] for m in PENDING))
def test_pending_reader_finds_nothing_in_a_rehearsal(metric, monkeypatch):
    """No trace (a rehearsal), a trace whose file is gone, or a
    program without the classifier (the parent under these files):
    nothing is reported and nothing raises."""
    read = cells.module("layer_metrics", metric).read
    assert read({"trace": None, "window": _Window, "facts": {}}) is None
    assert read(_ctx(None, _SELF)) is None
    import chainermn_tpu.utils.telemetry as telemetry
    monkeypatch.delattr(telemetry, "classify_op_name")
    assert read(_ctx(_HAND, _SELF)) is None


def test_pending_entries_fit_the_manifest_form():
    """The ten are in the manifest, found by name, in the form they
    waited in; what the waiting room holds has that form and is not."""
    bench = cells.manifest()
    known = [w["name"] for w in bench["workloads"]]
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert all(m["name"] not in per_layer for m in PENDING)
    layers = {p["layer"] for p in bench["per_layer"] if p["name"] not in TEN}
    for m in [per_layer[name] for name in TEN] + PENDING:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (m["unit"], m["better"], m["source"]) == (
            "ms", "lower", "device_trace")
        assert re.match(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$", m["name"])
        assert m["layer"] in layers
        moved = next(e for e in bench["end_to_end"]
                     if e["name"] == m["moves"])
        # in the manifest's own order of cells
        assert m["workloads"] == [w for w in known if w in m["workloads"]]
        assert set(m["workloads"]) <= set(moved["workloads"])
        assert callable(cells.module("layer_metrics", m["name"]).read)


# -- the op names of a real v5e trace --------------------------------- #

def test_op_names_from_a_v5e_xplane(tmp_path):
    """``_chip_scripts/probe_xplane.py``'s trace of PR 34, whole: a
    jitted gradient of a projection and a flash kernel under scopes.
    The op name is the ``tf_op`` stat of each event's METADATA (less
    its trailing colon); a fusion carries its root's, the compiler's
    own ``copy-start`` none."""
    import gzip
    path = tmp_path / "probe.xplane.pb"
    with gzip.open(os.path.join(os.path.dirname(__file__), "traces",
                                "probe-v5e.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    names = op_names.from_xplane(str(path))
    assert len(names) == 15 and "copy-start" not in names
    assert names["convolution_tanh_fusion"] == (
        "jit(step)/jvp(attn/full)/attn.qkv/dot_general")
    assert classify_op_name(names["fusion.7"]) == (
        "backward", ("attn/full", "attn.qkv"))
    # the probe wore ``attn.core`` around a kernel that wears it itself:
    # a scope counts once
    assert classify_op_name(names["attn.core.3"]) == (
        "forward", ("attn/full", "attn.core"))
    assert {classify_op_name(names[k])[0] for k in
            ("attn.core.4", "attn.core.5")} == {"backward"}


def test_op_names_read_memoises_and_survives_a_lost_file(tmp_path):
    class window:
        class run:
            trace_dir = str(tmp_path)
    ctx = {"trace": {"op_self_s": {}}, "window": window, "facts": {}}
    assert op_names.read(ctx) is None and ctx[op_names.KEY] is None
    assert op_names.read({"trace": None}) is None


# -- the fixture cut from a chip run ---------------------------------- #

@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    HERE, "traces", "*.scopes.json"))), ids=os.path.basename)
def test_readers_on_a_fixture_cut_from_the_chip(path):
    """``tools/scope_table.py --fixture`` wrote each instruction's op
    name and self time beside what the readers gave on the chip."""
    with open(path) as f:
        fixture = json.load(f)
    assert fixture["expected"]

    class window:
        iterations = fixture["iterations"]

    ctx = {"trace": {"op_self_s": {k: v[1] for k, v in
                                   fixture["ops"].items()}},
           "window": window, "facts": {},
           op_names.KEY: {k: v[0] for k, v in fixture["ops"].items()
                          if v[0] is not None}}
    for metric, value in fixture["expected"].items():
        read = cells.module("layer_metrics", metric).read
        assert read(ctx) == pytest.approx(value, rel=1e-9), metric
