"""The LFM2 cell's own pieces at toy widths on the CPU, the cheap ones:
the eight shares of a sparse layer against the uncut reference, faults
that have to fail the check, the reference's operator against the
program's op, the configuration file against the published numbers, the
required counts against hand counts, the manifest's entries of the
cell.  The three followed steps (program in float32, the
swapped gates, the control) are ``test_lfm2_follow.py``'s: a file runs
on one worker."""

import copy
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells, counts_lfm2
from benchmarks.reference import lfm2

CELL = "lfm2-24b-l5-ep8-train-conv-seq8192"
CONFIG = "lfm2-24b-l5-ep8"
DRIVER = cells.module("drivers", "train_step_lfm2")
_SAME = lambda x: x     # noqa: E731  (the reference proper rounds nothing)


def _config(rehearse=False):
    return cells.load_cell(CELL, rehearse)[1]


# -- the shares add up ------------------------------------------------ #

def test_eight_shares_of_a_sparse_layer_add_up_to_the_uncut_layer():
    """One sparse layer of 16 experts at 4 a token under the biased
    sigmoid router, NO shared expert: the eight members' parts (each its
    2 of 16 experts; scores from the sigmoid over all 16, the choice by
    score + bias, the gates the chosen scores over their sum) add up to
    what the uncut reference gives for the whole layer; nothing is
    computed alike by every member, so nothing is counted once."""
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu.models import transformer as tr
    from chainermn_tpu.parallel import MeshConfig

    _, cfg, job = cells.load_cell(CELL, rehearse=True)
    cfg = dict(cfg, compute_dtype="float32")
    whole = dict(cfg, num_experts=16, experts_first=0)
    blk = jax.tree.map(lambda a: a[0], lfm2.init(
        jax.random.PRNGKey(5), whole)["blocks"][1])
    blk = {k: blk[k] for k in ("ln2", "router", "router_bias", "w1", "w2",
                               "w3")}
    # a bias that decides something: as large as the scores' spread
    blk["router_bias"] = 10 * blk["router_bias"]
    assert blk["w1"].shape == (16, 64, 32)
    h = jax.random.normal(jax.random.PRNGKey(6), (128, 64))
    want, _, _ = lfm2._experts(whole, _SAME, h, blk)
    x = lfm2._rms_norm(h, blk["ln2"], cfg["norm_eps"])
    s, top_i, gates = lfm2.route(whole, x, blk["router"], blk["router_bias"])
    np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-5)
    unbiased = jax.lax.top_k(s, 4)[1]
    assert float((jnp.sort(top_i) != jnp.sort(unbiased)).any(-1).mean()) \
        > 0.05

    mc = MeshConfig(devices=jax.devices()[:1], data=1)
    parts = []
    for first in range(0, 16, 2):
        pcfg = DRIVER._program_config(
            dict(cfg, num_experts=2, experts_first=first), job)
        share = dict(blk, **{k: blk[k][first:first + 2]
                             for k in ("w1", "w2", "w3")})
        out, _ = jax.jit(jax.shard_map(
            lambda h, blk, pcfg=pcfg: tr._mlp(pcfg, h, blk), mesh=mc.mesh,
            in_specs=(P(), P()), out_specs=(P(), P())))(h[None], share)
        parts.append(np.asarray(out[0] - h))
    assert len(parts) == 8
    assert sum(np.abs(p).mean() > 0 for p in parts) >= 6
    np.testing.assert_allclose(sum(parts), np.asarray(want - h),
                               rtol=2e-5, atol=2e-5)


# -- faults that must fail -------------------------------------------- #

def _one_step(cfg, params, batch):
    """The reference's first loss and first gradient's norms, traced
    afresh (a patched seam is read as the function is traced)."""
    from benchmarks.reference.common import leaf_norms, to_floats

    loss, grads = jax.jit(partial(
        lfm2.batch_loss_and_grads, cfg, "float32"))(params, *batch)
    return float(loss), to_floats(leaf_norms(grads))


@pytest.fixture(scope="module")
def sound():
    cfg = _config(rehearse=True)
    params = lfm2.init(jax.random.PRNGKey(21), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(22), (1, 129), 0, cfg["vocabulary"])
    batch = (tokens[:, :-1], tokens[:, 1:])
    return cfg, params, batch, _one_step(cfg, params, batch)


def _no_bias(params):
    zero = lambda blk: dict(blk, router_bias=0 * blk["router_bias"])  # noqa
    return dict(params, blocks=tuple(zero(b) for b in params["blocks"]))


def _big_bias(params):
    """The seeded bias made large enough to decide choices at the toy
    size (the cell's N(0, 0.01^2) flips few among 16 experts)."""
    grow = lambda blk: dict(blk, router_bias=30 * blk["router_bias"])  # noqa
    return dict(params, blocks=tuple(grow(b) for b in params["blocks"]))


FAULTS = {
    "both gates ahead of the convolution": dict(
        _short_conv=lambda rnd, b, c, x, w: _SOUND_CONV(rnd, b * c, 1.0, x, w)),
    "a tap shifted": dict(
        _short_conv=lambda rnd, b, c, x, w: _SOUND_CONV(
            rnd, b, c, x, jnp.roll(w, 1, axis=-1))),
    "a quarter of the head rotated": dict(
        inv_freq=lambda cfg: cfg["rope_parameters"]["rope_theta"] ** (
            -np.arange(0, cfg["head_dim"] // 4, 2, dtype=np.float64)
            / (cfg["head_dim"] // 4))),
    "the gates not renormalised": dict(
        route=lambda cfg, x, router, bias: (
            lambda s, i, g: (s, i, jnp.take_along_axis(s, i, axis=-1)))(
                *_SOUND_ROUTE(cfg, x, router, bias))),
    "the bias left out of the choice": dict(params=_no_bias),
}
_SOUND_CONV, _SOUND_ROUTE = lfm2._short_conv, lfm2.route


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_fails_the_check(monkeypatch, sound, fault):
    """Each of the mechanisms that are new to the benchmark, got wrong
    in the one way it is most easily got wrong, moves the first loss or
    a leaf's first gradient by a hundred times what
    ``test_lfm2_follow.py`` lets the float32 program differ from the
    reference by (1e-4): the float32 comparison reads the step that has
    it as not correct."""
    from benchmarks.lib import check

    cfg, params, batch, _ = sound
    params = _big_bias(params)
    loss, norms = _one_step(cfg, params, batch)
    change = dict(FAULTS[fault])
    params = change.pop("params", lambda p: p)(params)
    for name, fn in change.items():
        assert hasattr(lfm2, name)
        monkeypatch.setattr(lfm2, name, fn)
    got_loss, got_norms = _one_step(cfg, params, batch)
    by_leaf = check.leaf_gaps(got_norms, norms)
    assert max(abs(got_loss - loss) / loss, max(by_leaf.values())) \
        > 100 * 1e-4, (fault, got_loss, loss, max(by_leaf.values()))


def test_reference_operator_is_the_programs_op():
    """The reference's three shifted slices against ``ops/recurrent.py``
    ``gated_short_conv``, kernels (interpreted) and plain form."""
    from chainermn_tpu.ops.recurrent import TOKENS, gated_short_conv

    for t, c in ((TOKENS, 128), (40, 24)):
        ks = jax.random.split(jax.random.PRNGKey(t), 2)
        bcx = jax.random.normal(ks[0], (2, t, 3 * c))
        w = jax.random.normal(ks[1], (c, 3))
        want = jnp.stack([lfm2._short_conv(
            _SAME, *jnp.split(bcx[i], 3, axis=-1), w) for i in range(2)])
        np.testing.assert_allclose(gated_short_conv(bcx, w), want,
                                   rtol=1e-5, atol=2e-6)


def test_reference_imports_nothing_of_the_program():
    path = os.path.join(cells.HERE, "reference", "lfm2.py")
    imports = [line for line in open(path).read().splitlines()
               if line.startswith(("import ", "from "))]
    assert imports and not [i for i in imports if "chainermn" in i]


# -- the configuration file ------------------------------------------- #

PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts_per_tok": 4,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}


def test_config_keeps_every_published_number():
    """Every key of the catalog row's ``config`` is in the file under
    its own name, ``layer_types`` whole; what is changed is listed, with
    the published value and the deployment beside it; what is left out
    and assumed is said; the limits stand between two readings from the
    chip."""
    cfg = _config()
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    types = cfg["layer_types"]
    assert len(types) == 40 and types.count("full_attention") == 10
    assert types[:3] == ["conv", "conv", "full_attention"]
    assert types[2:38] == ["full_attention", "conv", "conv", "conv"] * 9
    assert set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocabulary"}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocabulary"],
            cfg["layers_first"]) == (5, 8, 8192, 1)
    assert cfg["published"]["num_hidden_layers"] == 40
    assert cfg["published"]["num_experts"] == 64
    assert cfg["published"]["num_experts_per_tok"] == 4
    assert cfg["published"]["vocab_size"] == 65536
    assert cfg["router_experts"] == 64 and cfg["experts_first"] == 0
    assert cfg["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    assert cfg["tie_word_embeddings"] is True
    assert (cfg["deployment"]["chips_a_layer"],
            cfg["deployment"]["expert_parallel"]) == (8, 8)
    assert set(cfg["left_out"]) == {"decode", "packing", "bias_update"}
    for item in ("sources", "head_dim", "tie_word_embeddings", "conv",
                 "full_attention", "norms", "dense_layers", "router",
                 "router_bias", "experts", "aux_loss", "compute_dtype",
                 "init", "optimizer"):
        assert item in cfg["assumed"]
    assert counts_lfm2.layers(cfg) == lfm2.layers(cfg) == [
        ("conv", "dense"), ("full_attention", "sparse"), ("conv", "sparse"),
        ("conv", "sparse"), ("conv", "sparse")]
    assert lfm2.layout(cfg) == (
        1, ["full_attention", "conv", "conv", "conv"])
    # the floors of the guide: a whole period after the leading layer,
    # 8 experts, an eighth of the rows
    assert cfg["vocabulary"] * 8 == 65536 and cfg["num_experts"] >= 8
    assert cfg["parameters"] == counts_lfm2.params(cfg) == 469_285_248
    assert set(cfg["check"]["limits"]) == {
        "loss_gap.step1", "loss_gap.step2", "loss_gap.step3",
        "grad_norm_gap", "grad_norm_gap.median", "delta_norm_gap",
        "delta_norm_gap.median"}
    assert set(cfg["check"]["readings"]) == {"sound", "control"}
    toy = _config(rehearse=True)
    for key in DRIVER._AS_PROGRAMMED:
        assert toy[key] == cfg[key], key
    assert toy["layer_types"] == types and toy["layers_first"] == 1


def test_driver_refuses_a_file_the_program_does_not_run():
    cfg, job = cells.load_cell(CELL, rehearse=True)[1:]
    for change in (dict(conv_bias=True), dict(norm_topk_prob=False),
                   dict(use_expert_bias=False),
                   dict(tie_word_embeddings=False),
                   dict(rope_parameters={"rope_theta": 1e6,
                                         "rope_type": "yarn"}),
                   dict(num_dense_layers=6)):
        with pytest.raises(SystemExit):
            DRIVER._program_config(dict(cfg, **change), job)
    pcfg = DRIVER._program_config(cfg, job)
    (lead,), (full, conv) = pcfg.leading_layers, pcfg.layer_pattern[:2]
    assert [k.name for k in pcfg.layer_pattern] == ["full"] + ["conv"] * 3
    assert lead == conv and (conv.mixer, conv.conv_taps, conv.n_heads) \
        == ("shortconv", 3, 0)
    assert (full.mixer, full.qk_norm, full.rotary_share, full.rope_theta) \
        == ("softmax", True, 1.0, 1e6)
    assert (pcfg.norm_scale, pcfg.norm_eps, pcfg.attn_gate) \
        == ("plain", 1e-5, "")
    assert pcfg.experts_held == (0, 2) and pcfg.n_experts == 16
    assert (pcfg.router_score, pcfg.router_bias, pcfg.router_scale,
            pcfg.shared_expert_d_ff) == ("sigmoid", "selection", 1.0, 0)
    assert (pcfg.dense_act, pcfg.dense_d_ff, pcfg.tie_embeddings) \
        == ("swiglu", 160, True)


def test_a_program_without_the_mixer_is_refused_at_once(monkeypatch):
    """The parent under these files: its table lacks the name, and the
    driver says so before the reference has spent its minutes."""
    from chainermn_tpu.models import mixers

    cfg, job = cells.load_cell(CELL, rehearse=True)[1:]
    monkeypatch.delitem(mixers.MIXERS, "shortconv")
    with pytest.raises(TypeError, match="mixer 'shortconv' not in"):
        DRIVER._program_config(cfg, job)


# what this PR wrote into ``BENCHMARK.json``, by name
THREE = ("shortconv.ms_per_step", "shortconv.conv_ms_per_step",
         "shortconv.conv_roofline")
# the attention layer's kernels over their least time, read under
# attn/full alone (the review's: the typed reader would count the
# convolution's kernels under attn/conv too)
FLASH = "flash.full_roofline"
SHARED = ("tokens_per_s", "step_ms.p90", "step.mfu_pct.lm",
          "device.idle_pct.lm", "device.hbm_gib.lm", "step.forward_ms",
          "step.backward_ms", "step.optimizer_ms", "step.head_ms",
          "step.unscoped_ms", "attn.proj_ms_per_step", "moe.ms_per_step",
          "moe.experts_roofline", "moe.load_imbalance",
          "moe.moves_ms_per_step", "mlp.dense_ms_per_step",
          "flash.full_ms_per_step")


def _by_name(entries):
    return {e["name"]: e for e in entries}


def _entries_of_the_cell_fit_the_form(bench):
    """Each entry is found by its NAME, wherever it stands."""
    cell = _by_name(bench["workloads"])[CELL]
    config = _by_name(bench["configs"])[cell["config"]]
    assert config["name"] == CONFIG and cell["chips"] == 1
    assert cell["traffic"] == "train-conv-seq8192"
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert config["source"] == _config()["source"]
    assert set(config["reduced"]) == set(_config()["reduced"])
    metrics = _by_name(bench["end_to_end"] + bench["per_layer"])
    mine = [metrics[name] for name in THREE + (FLASH,)]
    for metric in mine:
        assert set(metric) == {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "tokens_per_s"
        assert metric["source"] == "device_trace"
    assert [m["layer"] for m in mine] == [
        "short-convolution layer"] * 2 + ["kernels"] * 2
    for share in mine[2:]:
        assert (share["unit"], share["better"]) == ("%", "higher")
    for name in SHARED:
        assert CELL in metrics[name]["workloads"], name
    # what the cell has nothing for; the glue reader would call the
    # convolution glue, and the typed roofline's reader counts every
    # Pallas call under attn/, the convolution's among them
    for name in ("flash.sliding_ms_per_step", "moe.shared_ms_per_step",
                 "kda.ms_per_step", "mla.ms_per_step", "ssm.ms_per_step",
                 "gdn.ms_per_step", "attn.glue_ms_per_step",
                 "flash.typed_roofline", "images_per_s"):
        assert CELL not in metrics[name]["workloads"], name
    for text in ([config["why"], config["source"], cell["why"]]
                 + [m["layer"] for m in mine]):
        assert 1 <= len(text) <= 200 and text.isascii() and text.isprintable()
    # four-chip cells stay within a quarter of the cells
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def _with_a_cell_appended(bench):
    """A copy of the manifest as the next ``model_config`` PR leaves it:
    one more configuration, cell and per-layer metric after the last."""
    bench = copy.deepcopy(bench)
    bench["configs"].append(dict(
        _by_name(bench["configs"])[CONFIG], name="appended-l1"))
    bench["workloads"].append(dict(
        _by_name(bench["workloads"])[CELL],
        name="appended-l1-train", config="appended-l1"))
    for metric in bench["end_to_end"] + bench["per_layer"]:
        listed = metric.get("workloads", [])
        if CELL in listed and listed != [CELL]:
            listed.append("appended-l1-train")
    bench["per_layer"].append(dict(
        _by_name(bench["per_layer"])[THREE[0]],
        name="appended.ms_per_step", workloads=["appended-l1-train"]))
    return bench


@pytest.mark.parametrize("appended", [False, True],
                         ids=["as-it-is", "one-more-cell-appended"])
def test_manifest_entries_of_the_cell_fit_the_form(appended):
    bench = cells.manifest()
    _entries_of_the_cell_fit_the_form(
        _with_a_cell_appended(bench) if appended else bench)


def test_traffic_file_states_its_sizing():
    _, _, job = cells.load_cell(CELL)
    assert (job["driver"], job["batch"], job["seq"],
            job["distinct_batches"]) == ("train_step_lfm2", 4, 8192, 4)
    assert (job["check_steps"], job["warmup_steps"], job["trace_steps"]) \
        == (3, 2, 8)
    assert job["loss_chunk"] == 0 and job["mesh"] == {"data": 1}
    assert "Branch taken" in job["why"] and "GiB" in job["why"]
    toy = cells.load_cell(CELL, rehearse=True)[2]
    assert (toy["batch"], toy["seq"]) == (2, 256)


# -- required counts by hand ------------------------------------------ #

def test_parameter_count_of_the_share():
    """469,285,248 by hand (the issue's sum), from the file, from the
    shapes ``init_transformer`` builds for the driver's mapping of it,
    and from the reference's own init."""
    from chainermn_tpu.models import init_transformer

    cfg, job = cells.load_cell(CELL)[1:]
    conv = 2048 * 6144 + 2048 * 3 + 2048 * 2048
    full = 2048 * 2048 + 2048 * 2 * 512 + 2 * 64 + 2048 * 2048
    dense = 3 * 2048 * 11776
    routed = 2048 * 64 + 64 + 8 * 3 * 2048 * 1536
    assert (conv, full, dense, routed) == (
        16_783_360, 10_485_888, 72_351_744, 75_628_608)
    leading = conv + 4096 + dense
    sparse_conv = conv + 4096 + routed
    sparse_full = full + 4096 + routed
    assert (leading, sparse_conv, sparse_full) == (
        89_139_200, 92_416_064, 86_118_592)
    want = 8192 * 2048 + 2048 + leading + 3 * sparse_conv + sparse_full
    assert counts_lfm2.params(cfg) == want == 469_285_248
    assert want * 16 / 1e9 == pytest.approx(7.51, abs=0.01)
    assert want * 16 / 2 ** 30 == pytest.approx(6.99, abs=0.01)
    shapes = jax.eval_shape(lambda: init_transformer(
        jax.random.PRNGKey(0), DRIVER._program_config(cfg, job)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == want
    assert "head" not in shapes
    lead, = shapes["leading"]
    assert lead["w_in"].shape == (2048, 6144)
    assert lead["conv"].shape == (2048, 3)
    assert lead["wo"].shape == (32, 64, 2048)
    assert lead["w1"].shape == (2048, 11776)
    assert shapes["blocks"][0]["wq"].shape == (1, 1, 2048, 32, 64)
    assert shapes["blocks"][0]["wkv"].shape == (1, 1, 2048, 2, 8, 64)
    assert shapes["blocks"][0]["q_norm"].shape == (1, 1, 64)
    assert shapes["blocks"][1]["w_in"].shape == (1, 1, 2048, 6144)
    assert shapes["blocks"][1]["w1"].shape == (1, 1, 8, 2048, 1536)
    assert shapes["blocks"][1]["router"].shape == (1, 1, 2048, 64)
    assert shapes["blocks"][1]["router_bias"].shape == (1, 1, 64)
    ref = jax.eval_shape(lambda: lfm2.init(jax.random.PRNGKey(0), cfg))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(ref)) == want
    assert jax.tree.map(lambda a: a.shape[2:], shapes["blocks"]) \
        == jax.tree.map(lambda a: a.shape[1:], ref["blocks"])
    assert jax.tree.map(lambda a: a.shape, shapes["leading"]) \
        == jax.tree.map(lambda a: a.shape, ref["leading"])


def test_flops_and_bytes_of_a_step_by_hand():
    cfg = _config()
    # an even spread: each of the 8 held experts gets 32,768 * 4 / 64
    # rows in each of the 4 sparse layers
    load = [[2048] * 64] * 4
    rows = counts_lfm2.held_rows(cfg, load)
    assert rows == 4 * 8 * 2048 == 65_536
    conv = 2048 * 6144 + 2048 * 2048
    full = 2048 * 2048 + 2 * 2048 * 512 + 2048 * 2048
    dense = (4 * conv + full + 3 * 2048 * 11776 + 4 * 2048 * 64
             + 8192 * 2048)
    assert counts_lfm2.dense_matmul_params(cfg) == dense
    pairs = 8192 * 8193 // 2
    core = 3 * pairs * 2 * 2 * 32 * 64
    want = 6 * dense * 32768 + 4 * core + 6 * rows * 9_437_184
    assert counts_lfm2.train_flops_per_step(cfg, 4, 8192, rows) == want
    # the issue's reckoning, a token forward: four conv mixers 4 x 33.5
    # MFLOP, the dense feed-forward 145, the attention layer 21 + 33.5
    # of pairs, the routed experts 4 x 9.4 at the held share, the head
    # 33.5: about 405 MFLOP
    a_token = (2 * 4 * conv + 2 * 3 * 2048 * 11776 + 2 * full
               + core / 3 / 8192 + 2 * rows * 9_437_184 / 32768
               + 2 * 8192 * 2048)
    assert 2 * conv / 1e6 == pytest.approx(33.5, abs=0.1)
    assert a_token / 1e6 == pytest.approx(405, abs=3)
    flops, nbytes = counts_lfm2.expert_step_flops_and_bytes(cfg, rows)
    assert flops == 6 * rows * 9_437_184
    assert nbytes == 3 * (4 * 8 * 9_437_184 * 2 + 2 * rows * 2048 * 2)
    # four conv layers: 4 float32 tensors of 32,768 x 2,048 forward, 7
    # backward, once each
    assert counts_lfm2.shortconv_step_bytes(cfg, 4, 8192) \
        == 4 * 11 * 32768 * 2048 * 4
    (kind, (flops, nbytes)), = counts_lfm2.flash_step_flops_and_bytes(
        cfg, 4, 8192).items()
    assert kind == "full" and flops == 4 * core
    assert nbytes == 4 * (6 * 8192 * (32 + 8) * 64 * 2 + 2 * 8192 * 32 * 4)


# -- the three readers, by hand ---------------------------------------- #

def test_readers_sum_the_layers_scopes_by_hand():
    """A classified window of two steps, by hand: ``shortconv.ms_per_step``
    takes what wears ``attn/conv`` and nothing of the attention layer;
    the roofline is the required bytes' least time over the time under
    ``shortconv/conv``, under 100 at the practical bandwidth; a program
    without the scopes (the parent's) reads nothing and raises
    nothing."""
    import types

    L, C = "step/layers", "attn/conv"
    ops = {
        "a": ("forward", (L, C, "attn.qkv"), 0.010),
        "b": ("forward", (L, C, "shortconv/conv"), 0.006),
        "c": ("backward", (L, C, "shortconv/conv"), 0.010),
        "d": ("recompute", (L, C, "shortconv/conv"), 0.004),
        "e": ("backward", (L, C, "attn.out"), 0.006),
        "f": ("forward", (L, "attn/full", "attn.core"), 0.050),
        "g": ("forward", (L, "attn/full", "attn.qkv"), 0.030),
    }
    ctx = {"_scopes_step": ops, "trace": {},
           "window": types.SimpleNamespace(iterations=2),
           "facts": {"shortconv_bytes": 4.0e6 * 819.0},   # 4 ms at the peak
           "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    read = {name: cells.module("layer_metrics", name).read
            for name in THREE}
    assert read["shortconv.ms_per_step"](ctx) == pytest.approx(18.0)
    assert read["shortconv.conv_ms_per_step"](ctx) == pytest.approx(10.0)
    assert read["shortconv.conv_roofline"](ctx) == pytest.approx(40.0)
    bare = dict(ctx, _scopes_step={k: v for k, v in ops.items()
                                   if k in "fg"})
    bare["facts"] = {}
    assert [read[name](bare) for name in THREE] == [None] * 3
    assert [read[name](dict(ctx, _scopes_step=None)) for name in THREE] \
        == [None] * 3


def test_the_flash_roofline_reads_the_full_layers_kernels_alone():
    """``flash.full_roofline`` divides the ``full`` kind's least time by
    the time of the KERNELS under ``attn/full``: neither the
    convolution's kernels under ``attn/conv`` nor a fusion of the
    attention layer is in it; compute decides at this cell's shapes;
    without the fact, the scope or the peaks it reads nothing."""
    import types

    cfg = _config()
    flops, nbytes = counts_lfm2.flash_step_flops_and_bytes(
        cfg, 4, 8192)["full"]
    assert flops / 197e12 > nbytes / 819e9
    facts = {"scopes": {"flash.1": "attn/full", "flash.2": "attn/full",
                        "fusion.3": "attn/full", "conv.4": "attn/conv"},
             "kernels": {"flash.1": "", "flash.2": "", "conv.4": ""},
             "flash_typed_flops_bytes": {"full": (197e12 * 3e-3, 819e9 * 1e-3)}}
    ctx = {"facts": facts, "window": types.SimpleNamespace(iterations=2),
           "trace": {"op_self_s": {"flash.1": 0.008, "flash.2": 0.012,
                                   "fusion.3": 0.5, "conv.4": 0.5}},
           "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    read = cells.module("layer_metrics", FLASH).read
    # 3 ms least x 2 steps over the two kernels' 20 ms
    assert read(ctx) == pytest.approx(30.0)
    assert cells.module("layer_metrics", "flash.full_ms_per_step").read(
        ctx) == pytest.approx(10.0)
    for lacking in (dict(ctx, peaks=None),
                    dict(ctx, facts=dict(facts, flash_typed_flops_bytes={})),
                    dict(ctx, facts=dict(facts, scopes={"conv.4": "attn/conv"})),
                    dict(ctx, facts={"scopes": facts["scopes"]})):
        assert read(lacking) is None
