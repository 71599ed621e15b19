"""The Qwen3-Next cell's own pieces at toy widths on the CPU, the cheap
ones: the sixteen shares of an expert layer against the uncut
reference, faults that have to fail the check, the reference's
recurrence against the program's op, the configuration file against the
published numbers, the required counts against hand counts, the
manifest's entries.  The three followed steps (program in float32, the
control) are ``test_qwen3_next_follow.py``'s: a file runs on one
worker."""

import copy
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import cells, counts_gdn
from benchmarks.reference import qwen3_next

CELL = "qwen3-next-l4-ep16-train-gdn-longseq"
CONFIG = "qwen3-next-l4-ep16"
DRIVER = cells.module("drivers", "train_step_gdn")
_SAME = lambda x: x     # noqa: E731  (the reference proper rounds nothing)


def _config(rehearse=False):
    return cells.load_cell(CELL, rehearse)[1]


# -- the shares add up ------------------------------------------------ #

def test_sixteen_shares_and_the_gated_shared_expert_once_add_up():
    """One expert layer of 32 experts at 4 a token: the sixteen members'
    routed parts (each its 2 of 32 experts; gates from the softmax over
    all 32, renormalised over the 4 chosen) plus the GATED shared
    expert, which every member computes alike, counted once, add up to
    what the uncut reference gives for the whole layer."""
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu.models import transformer as tr
    from chainermn_tpu.parallel import MeshConfig

    _, cfg, job = cells.load_cell(CELL, rehearse=True)
    cfg = dict(cfg, router_experts=32, compute_dtype="float32")
    whole = dict(cfg, num_experts=32, experts_first=0)
    blk = jax.tree.map(lambda a: a[0], qwen3_next.init(
        jax.random.PRNGKey(5), whole)["blocks"][0])
    blk = {k: blk[k] for k in ("ln2", "router", "w1", "w2", "w3", "ws1",
                               "ws2", "ws3", "wsg")}
    assert blk["w1"].shape == (32, 64, 32) and blk["wsg"].shape == (64, 1)
    h = jax.random.normal(jax.random.PRNGKey(6), (128, 64))
    want, _, _ = qwen3_next._experts(whole, _SAME, h, blk)
    x = qwen3_next._norm(h, blk["ln2"], cfg["rms_norm_eps"])
    _, _, gates = qwen3_next.route(whole, x, blk["router"])
    np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-5)
    gate = qwen3_next._shared_gate(x, blk["wsg"])
    assert 0.05 < float(gate.std())      # a gate that decides something
    shared = np.asarray(gate * qwen3_next._swiglu(
        _SAME, x, blk["ws1"], blk["ws3"], blk["ws2"]))

    mc = MeshConfig(devices=jax.devices()[:1], data=1)
    routed = []
    for first in range(0, 32, 2):
        pcfg = DRIVER._program_config(
            dict(cfg, num_experts=2, experts_first=first), job)
        share = dict(blk, **{k: blk[k][first:first + 2]
                             for k in ("w1", "w2", "w3")})
        out, _ = jax.jit(jax.shard_map(
            lambda h, blk, pcfg=pcfg: tr._mlp(pcfg, h, blk), mesh=mc.mesh,
            in_specs=(P(), P()), out_specs=(P(), P())))(h[None], share)
        routed.append(np.asarray(out[0] - h) - shared)
    assert len(routed) == 16
    np.testing.assert_allclose(sum(routed) + shared, np.asarray(want - h),
                               rtol=2e-5, atol=2e-5)
    assert np.abs(sum(routed)).mean() > 0.1 * np.abs(shared).mean()


# -- faults that must fail -------------------------------------------- #

def _one_step(cfg, params, batch):
    """The reference's first loss and first gradient's norms, traced
    afresh (a patched seam is read as the function is traced)."""
    from benchmarks.reference.common import leaf_norms, to_floats

    loss, grads = jax.jit(partial(
        qwen3_next.batch_loss_and_grads, cfg, "float32"))(params, *batch)
    return float(loss), to_floats(leaf_norms(grads))


@pytest.fixture(scope="module")
def sound():
    cfg = _config(rehearse=True)
    params = qwen3_next.init(jax.random.PRNGKey(21), cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(22), (1, 129), 0, cfg["vocabulary"])
    batch = (tokens[:, :-1], tokens[:, 1:])
    return cfg, params, batch, _one_step(cfg, params, batch)


def _a_head(params):
    """The element gate's weights with every element of a head given
    the head's first: a gate that is one scalar a head."""
    blocks = list(params["blocks"])
    wg = blocks[3]["wg"]
    blocks[3] = dict(blocks[3], wg=jnp.broadcast_to(
        wg[..., :1], wg.shape))
    return dict(params, blocks=tuple(blocks))


FAULTS = {
    "key head j // 2 read as j": dict(
        _to_value_heads=lambda y, rep: jnp.tile(y, (1, rep, 1))),
    "gate before norm": dict(
        _norm_then_gate=lambda o, z, scale, eps: (
            lambda g: g * jax.lax.rsqrt(jnp.mean(
                g * g, axis=-1, keepdims=True) + eps) * scale)(
                    o * jax.nn.silu(z))),
    "1 + dropped": dict(
        _norm=lambda x, w, eps: x * jax.lax.rsqrt(jnp.mean(
            x * x, axis=-1, keepdims=True) + eps) * w),
    "the element gate read a head": dict(params=_a_head),
    "the head rotated whole": dict(
        inv_freq=lambda cfg: cfg["rope_theta"] ** (-np.arange(
            0, cfg["head_dim"], 2, dtype=np.float64) / cfg["head_dim"])),
    "the shared expert's gate dropped": dict(
        _shared_gate=lambda x, wsg: 1.0),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_fails_the_check(monkeypatch, sound, fault):
    """Each of the mechanisms that are new to the program, got wrong in
    the one way it is most easily got wrong, moves the first loss or a
    leaf's first gradient by a hundred times what
    ``test_qwen3_next_follow.py`` lets the float32 program differ from
    the reference by (1e-4): the float32 comparison reads the step that
    has it as not correct.  (At this toy size bf16's own noise is as
    large as the two smallest, the gate a head and the whole rotation,
    so the twin's bf16 limits are not the yardstick here; the cell's
    own limits are set on the chip at 16,384 tokens.)"""
    from benchmarks.lib import check

    cfg, params, batch, (loss, norms) = sound
    change = dict(FAULTS[fault])
    params = change.pop("params", lambda p: p)(params)
    for name, fn in change.items():
        assert hasattr(qwen3_next, name)
        monkeypatch.setattr(qwen3_next, name, fn)
    got_loss, got_norms = _one_step(cfg, params, batch)
    by_leaf = check.leaf_gaps(got_norms, norms)
    assert max(abs(got_loss - loss) / loss, max(by_leaf.values())) \
        > 100 * 1e-4, (fault, got_loss, loss, max(by_leaf.values()))


def test_reference_recurrence_is_the_ops_a_token_at_a_time(monkeypatch):
    """The reference's ``_delta_rule`` (rematerialised blocks, key heads
    already copied out) against ``ops/gdn.py``'s token-by-token form,
    which is what the chunked op is held to."""
    from chainermn_tpu.ops.gdn import gdn_recurrent

    monkeypatch.setattr(qwen3_next, "STATE_BLOCK", 16)
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    q, k = (jax.random.normal(ks[i], (48, 2, 8)) * 8 ** -.5 for i in (0, 1))
    v = jax.random.normal(ks[2], (48, 4, 8))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (48, 4)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (48, 4)))
    got = qwen3_next._delta_rule(
        qwen3_next._to_value_heads(q, 2), qwen3_next._to_value_heads(k, 2),
        v, g, beta)
    want = gdn_recurrent(*(x[None] for x in (q, k, v, g, beta)))[0]
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_reference_imports_nothing_of_the_program():
    path = os.path.join(cells.HERE, "reference", "qwen3_next.py")
    imports = [line for line in open(path).read().splitlines()
               if line.startswith(("import ", "from "))]
    assert imports and not [i for i in imports if "chainermn" in i]


# -- the configuration file ------------------------------------------- #

PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4,
    "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 128, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_value_head_dim": 128,
    "max_position_embeddings": 262144, "mlp_only_layers": [],
    "model_type": "qwen3_next", "moe_intermediate_size": 512,
    "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts_per_tok": 10, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def test_config_keeps_every_published_number():
    """Every key of the catalog row's ``config`` is in the file under
    its own name; what is changed is listed, with the published value
    and the deployment beside it; what is left out and assumed is
    said; the limits stand between two readings from the chip."""
    cfg = _config()
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    assert set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocabulary"}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocabulary"]) == (4, 32, 18992)
    assert cfg["published"]["num_hidden_layers"] == 48
    assert cfg["published"]["num_experts"] == 512
    assert cfg["published"]["num_experts_per_tok"] == 10
    assert cfg["published"]["vocab_size"] == 151936
    assert cfg["router_experts"] == 512 and cfg["experts_first"] == 0
    assert (cfg["deployment"]["chips_a_layer"],
            cfg["deployment"]["expert_parallel"]) == (16, 16)
    assert "mtp" in cfg["left_out"]
    for item in ("norms", "linear_projections", "linear_conv",
                 "linear_l2norm", "linear_heads", "linear_gates",
                 "linear_init", "linear_output", "full_attention",
                 "experts", "router", "shared_expert", "aux_loss", "init",
                 "optimizer"):
        assert item in cfg["assumed"]
    assert counts_gdn.layers(cfg) == qwen3_next.layers(cfg) == [
        "linear", "linear", "linear", "full"]
    # the floors of the guide: a whole period, an eighth of the rows
    assert cfg["vocabulary"] * 8 == 151936 and cfg["num_experts"] >= 8
    assert set(cfg["check"]["limits"]) == {
        "loss_gap.step1", "loss_gap.step2", "loss_gap.step3",
        "grad_norm_gap", "grad_norm_gap.median", "delta_norm_gap",
        "delta_norm_gap.median"}
    assert set(cfg["check"]["readings"]) == {"sound", "control"}
    toy = _config(rehearse=True)
    for key in DRIVER._AS_PROGRAMMED:
        assert toy[key] == cfg[key], key


def test_driver_refuses_a_file_the_program_does_not_run():
    cfg, job = cells.load_cell(CELL, rehearse=True)[1:]
    for change in (dict(hidden_act="gelu"), dict(norm_topk_prob=False),
                   dict(mlp_only_layers=[0]), dict(l2_norm_eps=1e-5),
                   dict(rope_scaling={"factor": 2}),
                   dict(use_sliding_window=True)):
        with pytest.raises(SystemExit):
            DRIVER._program_config(dict(cfg, **change), job)
    pcfg = DRIVER._program_config(cfg, job)
    gdn, full = pcfg.layer_pattern[0], pcfg.layer_pattern[3]
    assert [k.name for k in pcfg.layer_pattern] == ["gdn"] * 3 + ["full"]
    assert (gdn.mixer, gdn.n_heads, gdn.key_heads, gdn.d_key, gdn.d_value,
            gdn.conv_taps) == ("gdn", 4, 2, 16, 16, 4)
    assert (full.mixer, full.qk_norm, full.rotary_share,
            full.rope_theta) == ("softmax", True, 0.25, 1e7)
    assert (pcfg.attn_gate, pcfg.norm_scale, pcfg.shared_expert_gate) == (
        "per_element", "zero_centred", True)
    assert pcfg.experts_held == (0, 4) and pcfg.n_experts == 16
    assert (pcfg.router_score, pcfg.router_bias, pcfg.router_scale) == (
        "softmax", "", 1.0)


# what this PR wrote into ``BENCHMARK.json`` for this cell, by name
FOUR = ("gdn.ms_per_step", "gdn.conv_ms_per_step", "gdn.scan_ms_per_step",
        "gdn.scan_roofline")
SHARED = ("tokens_per_s", "step_ms.p90", "step.mfu_pct.lm",
          "device.idle_pct.lm", "device.hbm_gib.lm", "moe.ms_per_step",
          "moe.experts_roofline", "moe.load_imbalance",
          "moe.shared_ms_per_step", "moe.moves_ms_per_step",
          "step.forward_ms", "step.backward_ms", "step.optimizer_ms",
          "step.head_ms", "step.unscoped_ms", "attn.proj_ms_per_step",
          "flash.full_ms_per_step", "flash.typed_roofline")


def _by_name(entries):
    return {e["name"]: e for e in entries}


def _entries_of_the_cell_fit_the_form(bench):
    """Each entry is found by its NAME, wherever it stands."""
    cell = _by_name(bench["workloads"])[CELL]
    config = _by_name(bench["configs"])[cell["config"]]
    assert config["name"] == CONFIG and cell["chips"] == 1
    assert cell["traffic"] == "train-gdn-longseq"
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert config["source"] == _config()["source"]
    assert set(config["reduced"]) == set(_config()["reduced"])
    metrics = _by_name(bench["end_to_end"] + bench["per_layer"])
    mine = [metrics[name] for name in FOUR]
    for metric in mine:
        assert set(metric) == {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "tokens_per_s"
        assert metric["source"] == "device_trace"
    assert [m["layer"] for m in mine] == [
        metrics["kda.ms_per_step"]["layer"]] * 2 + ["kernels"] * 2
    for name in SHARED:
        assert CELL in metrics[name]["workloads"], name
    # what the cell has nothing for; and the glue reader would call the
    # recurrence glue, as in the Kimi cell
    for name in ("flash.sliding_ms_per_step", "mlp.dense_ms_per_step",
                 "kda.ms_per_step", "mla.ms_per_step", "ssm.ms_per_step",
                 "attn.glue_ms_per_step", "images_per_s"):
        assert CELL not in metrics[name]["workloads"], name
    for text in ([config["why"], config["source"], cell["why"]]
                 + [m["layer"] for m in mine]):
        assert 1 <= len(text) <= 200 and text.isascii() and text.isprintable()


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
        _by_name(bench["per_layer"])[FOUR[0]],
        name="appended.ms_per_step", workloads=["appended-l1-train"]))
    return bench


@pytest.mark.parametrize("appended", [False, True],
                         ids=["as-it-is", "a-ninth-cell-appended"])
def test_manifest_entries_of_the_cell_fit_the_form(appended):
    bench = cells.manifest()
    _entries_of_the_cell_fit_the_form(
        _with_a_cell_appended(bench) if appended else bench)


def test_traffic_file_states_its_sizing():
    _, _, job = cells.load_cell(CELL)
    assert (job["driver"], job["batch"], job["seq"],
            job["distinct_batches"]) == ("train_step_gdn", 1, 16384, 4)
    assert (job["check_steps"], job["warmup_steps"], job["trace_steps"]) \
        == (3, 2, 8)
    assert "Branch taken" in job["why"] and "GiB" in job["why"]
    toy = cells.load_cell(CELL, rehearse=True)[2]
    # the smallest that keeps two slabs of the recurrence (six chunks of
    # 64 in slabs of three) and whole blocks of the flash kernels
    assert (toy["batch"], toy["seq"]) == (1, 384)


# -- required counts by hand ------------------------------------------ #

def test_parameter_count_of_the_share():
    """625,667,136 by hand, from the file, from the shapes
    ``init_transformer`` builds for the driver's mapping of it, and from
    the reference's own init."""
    from chainermn_tpu.models import init_transformer

    cfg, job = cells.load_cell(CELL)[1:]
    linear = 2048 * 12288 + 2048 * 64 + 8192 * 4 + 32 + 32 + 128 \
        + 4096 * 2048
    full = 2048 * 8192 + 2 * 2048 * 512 + 2 * 256 + 4096 * 2048
    second = 2048 * 512 + 3 * 2048 * 512 + 2048 + 32 * 3 * 2048 * 512
    assert (linear, full, second) == (33_718_464, 27_263_488, 104_859_648)
    assert counts_gdn.linear_params(cfg) == linear + 2048
    assert counts_gdn.full_params(cfg) == full + 2048
    assert counts_gdn.experts_params(cfg) == second + 2048
    want = 3 * linear + full + 4 * second + 18_432 + 2 * 18992 * 2048
    assert counts_gdn.params(cfg) == want == 625_667_136
    assert want * 16 / 1e9 == pytest.approx(10.01, abs=0.01)
    shapes = jax.eval_shape(lambda: init_transformer(
        jax.random.PRNGKey(0), DRIVER._program_config(cfg, job)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == want
    assert shapes["blocks"][0]["w_in"].shape == (1, 1, 2048, 12288)
    assert shapes["blocks"][0]["w_ba"].shape == (1, 1, 2048, 64)
    assert shapes["blocks"][0]["conv"].shape == (1, 1, 8192, 4)
    assert shapes["blocks"][0]["wo"].shape == (1, 1, 32, 128, 2048)
    assert shapes["blocks"][3]["wq"].shape == (1, 1, 2048, 16, 256)
    assert shapes["blocks"][3]["wg"].shape == (1, 1, 2048, 16, 256)
    assert shapes["blocks"][3]["wkv"].shape == (1, 1, 2048, 2, 2, 256)
    assert shapes["blocks"][3]["q_norm"].shape == (1, 1, 256)
    assert shapes["blocks"][1]["w1"].shape == (1, 1, 32, 2048, 512)
    assert shapes["blocks"][1]["wsg"].shape == (1, 1, 2048, 1)
    ref = jax.eval_shape(
        lambda: qwen3_next.init(jax.random.PRNGKey(0), cfg))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(ref)) == want
    assert jax.tree.map(lambda a: a.shape[2:], shapes["blocks"]) \
        == jax.tree.map(lambda a: a.shape[1:], ref["blocks"])


def test_flops_and_bytes_of_a_step_by_hand():
    cfg = _config()
    # an even spread: each of the 32 held experts gets 16,384 * 10 / 512
    # rows in each of the 4 layers
    load = [[320] * 512] * 4
    rows = counts_gdn.held_rows(cfg, load)
    assert rows == 4 * 32 * 320 == 40_960
    linear = 2048 * 12288 + 2048 * 64 + 4096 * 2048
    full = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    second = 2048 * 512 + 3 * 2048 * 512 + 2048
    dense = 3 * linear + full + 4 * second + 18992 * 2048
    assert counts_gdn.dense_matmul_params(cfg) == dense
    pairs = 16384 * 16385 // 2
    core = 3 * pairs * 2 * 2 * 16 * 256
    scan = 3 * 16384 * 32 * 3 * 3 * 2 * 128 * 128
    want = 6 * dense * 16384 + core + scan + 6 * rows * 3_145_728
    assert counts_gdn.train_flops_per_step(cfg, 1, 16384, rows) == want
    # the issue's reckoning: 3.3 TFLOP of linear projections forward,
    # 2.2 TFLOP of pairs forward
    assert 2 * 3 * linear * 16384 / 1e12 == pytest.approx(3.3, abs=0.05)
    assert core / 3 / 1e12 == pytest.approx(2.2, abs=0.05)
    flops, nbytes = counts_gdn.gdn_scan_step_flops_and_bytes(cfg, 1, 16384)
    assert flops == scan
    # q and k a key head, v and o a value head, g and beta a scalar a
    # value head, float32, three passes
    assert nbytes == 3 * 3 * 16384 * (2 * 2048 + 2 * 4096 + 64) * 4
    flops, nbytes = counts_gdn.expert_step_flops_and_bytes(cfg, rows)
    assert flops == 6 * rows * 3_145_728
    assert nbytes == 3 * (4 * 32 * 3_145_728 * 2 + 2 * rows * 2048 * 2)
    (kind, (flops, nbytes)), = counts_gdn.flash_step_flops_and_bytes(
        cfg, 1, 16384).items()
    assert kind == "full" and flops == core
    assert nbytes == 6 * 16384 * (16 + 2) * 256 * 2 + 2 * 16384 * 16 * 4


# -- the four readers, by hand ---------------------------------------- #

def test_readers_sum_the_layers_scopes_by_hand():
    """A classified window of two steps, by hand: ``gdn.ms_per_step``
    takes what wears ``attn/gdn`` and what a rematerialised slab runs
    under ``gdn/scan`` alone, and nothing of the full layer or of the
    Kimi cell's ``kda/scan``; the roofline is the required work's least
    time over the time under ``gdn/scan``; a program without the scopes
    (the parent's) reads nothing and raises nothing."""
    import types

    L, G = "step/layers", "attn/gdn"
    ops = {
        "a": ("forward", (L, G, "attn.qkv"), 0.010),
        "b": ("forward", (L, G, "gdn/conv"), 0.020),
        "c": ("backward", (L, G, "gdn/scan", "kda.solve"), 0.006),
        "d": ("recompute", (L, "gdn/scan", "gdn.pairs"), 0.004),
        "e": ("forward", (L, "attn/full", "attn.core"), 0.050),
        "f": ("backward", (L, "attn/kda", "kda/scan", "kda.solve"), 0.030),
    }
    required = (0.0, 4.0e6 * 819.0)      # 4 ms at the peak's bytes/s
    ctx = {"_scopes_step": ops, "trace": {},
           "window": types.SimpleNamespace(iterations=2),
           "facts": {"gdn_scan_flops_bytes": required},
           "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    read = {name: cells.module("layer_metrics", name).read for name in FOUR}
    assert read["gdn.ms_per_step"](ctx) == pytest.approx(20.0)
    assert read["gdn.conv_ms_per_step"](ctx) == pytest.approx(10.0)
    assert read["gdn.scan_ms_per_step"](ctx) == pytest.approx(5.0)
    assert read["gdn.scan_roofline"](ctx) == pytest.approx(80.0)
    bare = dict(ctx, _scopes_step={k: v for k, v in ops.items()
                                   if k in "ef"})
    bare["facts"] = {}
    assert [read[name](bare) for name in FOUR] == [None] * 4
    assert [read[name](dict(ctx, _scopes_step=None)) for name in FOUR] \
        == [None] * 4
