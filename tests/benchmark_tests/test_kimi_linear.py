"""The Kimi Linear cell's own pieces at toy widths on the CPU: the
program against the plain reference (``reference/kimi_linear.py``), the
shares of a sparse layer against the uncut reference, the reference's
recurrence against the program's chunked op, the configuration file
against the published numbers, the required counts against hand counts,
the new scopes' readers and the control."""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import (
    cells, check, counts_hybrid, scopes, scopes_hybrid, scopes_mixed,
)
from benchmarks.lib.harness import build_optimizer, first_gradient_norms
from benchmarks.reference import kimi_linear
from benchmarks.reference.common import delta_norms

CELL = "kimi-linear-l5-ep32-train-tok16384"
CONFIG = "kimi-linear-l5-ep32"
DRIVER = cells.module("drivers", "train_step_hybrid")
_SAME = lambda x: x     # noqa: E731  (the reference proper rounds nothing)


def _config(rehearse=False):
    return cells.load_cell(CELL, rehearse)[1]


# -- program against reference --------------------------------------- #

def _program(cfg, job, dtype):
    from chainermn_tpu.models import (
        init_transformer, make_train_step, shard_params)
    from chainermn_tpu.parallel import MeshConfig
    from chainermn_tpu.training import shard_opt_state

    pcfg = DRIVER._program_config(dict(cfg, compute_dtype=dtype), job)
    mc = MeshConfig(devices=jax.devices()[:1], data=1)
    opt = build_optimizer(cfg["optimizer"])

    def placed(params):
        return shard_params(mc, pcfg, dict(params, blocks=jax.tree.map(
            lambda a: a[None], params["blocks"])))

    def follow(make_params, batches):
        params = placed(make_params())
        assert jax.tree.map(lambda a: a.shape, params) == jax.tree.map(
            lambda a: a.shape, jax.eval_shape(
                lambda k: init_transformer(k, pcfg), jax.random.PRNGKey(0)))
        state = shard_opt_state(opt, params)
        step = make_train_step(mc, pcfg, opt)
        seen = {"losses": []}
        for i, batch in enumerate(batches):
            params, state, loss = step(params, state, *batch)
            seen["losses"].append(float(loss))
            if i == 0:
                seen["grad_norms"] = first_gradient_norms(
                    state, cfg["optimizer"]["first_gradient"])
        seen["delta_norms"] = delta_norms(params, placed(make_params()))
        return seen

    return pcfg, mc, placed, follow


def test_program_follows_the_reference_in_float32():
    """Loss, first gradient and three AdamW steps on seeded weights: a
    leading KDA layer with a dense MLP, then KDA, KDA, MLA, KDA with 2 of
    8 experts held beside the shared one and a selection bias, four
    chunks of the recurrence a sequence, through the driver's own
    mapping of the file.  In float32 the two agree to rounding, so a
    wrong tap, decay, norm, scale, gate or share shows at once."""
    _, cfg, job = cells.load_cell(CELL, rehearse=True)
    assert job["seq"] == 4 * 64 and job["batch"] == 1
    pcfg, _, _, follow = _program(cfg, job, "float32")
    assert [(k.name, k.mixer) for k in pcfg.leading_layers] == [
        ("kda", "kda")]
    assert [k.name for k in pcfg.layer_pattern] == [
        "kda", "kda", "mla", "kda"]
    mla = pcfg.layer_pattern[2]
    assert (mla.kv_latent, mla.d_shared_key, mla.d_value) == (32, 8, 16)
    assert pcfg.experts_held == (0, 2) and pcfg.n_experts == 8
    assert (pcfg.router_score, pcfg.router_scale, pcfg.router_bias) == (
        "sigmoid", 2.446, "selection")
    assert pcfg.norm_eps == 1e-5 and not pcfg.tie_embeddings
    key = jax.random.PRNGKey(11)
    tokens = jax.random.randint(
        jax.random.PRNGKey(12), (3, 1, job["seq"] + 1), 0, cfg["vocabulary"])
    batches = [(tokens[i, :, :-1], tokens[i, :, 1:]) for i in range(3)]
    make = lambda: kimi_linear.init(key, cfg)
    ref = kimi_linear.follow(cfg, make, batches)
    seen = follow(make, batches)
    for name, (gap, note) in check.gaps(seen, ref).items():
        assert gap < 1e-4, (name, gap, note)
    # every leaf compared: 16 of the leading layer, three KDA sparse
    # layers of 21 and the MLA one of 15, 3 outside
    assert len(ref["grad_norms"]) == 16 + 3 * 21 + 15 + 3
    # the selection bias has no gradient and does not move
    fixed = [k for k in ref["grad_norms"] if "router_bias" in k]
    assert len(fixed) == 4
    for reading in (ref, seen):
        assert all(reading["grad_norms"][k] == 0 for k in fixed)
        assert all(reading["delta_norms"][k] == 0 for k in fixed)


def test_four_shares_and_the_shared_expert_once_add_up_to_the_layer():
    """One sparse layer: the four members' routed parts (each its 2 of 8
    experts; the choice by s + b, the gates from s, normalised over all
    k chosen and scaled by 2.446) plus the shared expert, which every
    member computes alike, counted once, add up to what the uncut
    reference gives for the whole layer."""
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu.models import transformer as tr

    _, cfg, job = cells.load_cell(CELL, rehearse=True)
    whole = dict(cfg, num_experts=8, experts_first=0)
    blk = jax.tree.map(lambda a: a[0], kimi_linear.init(
        jax.random.PRNGKey(5), whole)["blocks"][0])
    # a bias large enough to decide choices
    blk["router_bias"] = 0.2 * jax.random.normal(jax.random.PRNGKey(7), (8,))
    h = jax.random.normal(jax.random.PRNGKey(6), (job["seq"], 64))
    want, _, _ = kimi_linear._experts(whole, _SAME, h, blk)
    x = kimi_linear._rms_norm(h, blk["ln2"], cfg["rms_norm_eps"])
    _, chosen, _ = kimi_linear.route(whole, x, blk["router"],
                                     blk["router_bias"])
    _, unbiased, _ = kimi_linear.route(whole, x, blk["router"],
                                       jnp.zeros((8,)))
    assert float(jnp.mean(jnp.sort(chosen) != jnp.sort(unbiased))) > 0.02
    shared = np.asarray(kimi_linear._swiglu(
        _SAME, x, blk["ws1"], blk["ws3"], blk["ws2"]))

    routed = []
    for first in range(0, 8, 2):
        pcfg, mc, _, _ = _program(
            dict(cfg, experts_first=first), job, "float32")
        share = dict(blk, **{k: blk[k][first:first + 2]
                             for k in ("w1", "w2", "w3")})
        out, _ = jax.jit(jax.shard_map(
            lambda h, blk: tr._mlp(pcfg, h, blk), mesh=mc.mesh,
            in_specs=(P(), P()), out_specs=(P(), P())))(h[None], share)
        # the reference given the same share agrees part by part
        ref_part, _, _ = kimi_linear._experts(
            dict(cfg, experts_first=first), _SAME, h, share)
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref_part),
                                   rtol=2e-5, atol=2e-5)
        routed.append(np.asarray(out[0] - h) - shared)
    np.testing.assert_allclose(sum(routed) + shared, np.asarray(want - h),
                               rtol=2e-5, atol=2e-5)
    assert np.abs(sum(routed)).mean() > 0.1 * np.abs(shared).mean()


def test_reference_recurrence_is_the_ops_a_token_at_a_time(monkeypatch):
    """The reference's nested scan (a state kept a block, the block's
    tokens rematerialised) against the op's plain one, and by blocks
    against one block."""
    from chainermn_tpu.ops.kda import kda_recurrent

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    t, h, d = 64, 2, 8
    q, k, v = (jax.random.normal(ks[i], (t, h, d)) * d ** -.5
               for i in range(3))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (t, h, d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (t, h)))
    want = kda_recurrent(q[None], k[None], v[None], g[None], beta[None])[0]
    whole = kimi_linear._delta_rule(q, k, v, g, beta)
    monkeypatch.setattr(kimi_linear, "STATE_BLOCK", 16)
    blocks = kimi_linear._delta_rule(q, k, v, g, beta)
    np.testing.assert_allclose(whole, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(blocks, want, rtol=1e-5, atol=1e-6)


# -- the configuration file ------------------------------------------ #

def test_config_keeps_every_published_number():
    """Every number of the public config.json is in the file under its
    own key; what is changed is listed, with the published value and the
    deployment beside it."""
    cfg = _config()
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216,
        "kv_lora_rank": 512, "mla_use_nope": True,
        "model_max_length": 1048576, "model_type": "kimi_linear",
        "moe_intermediate_size": 1024, "moe_layer_freq": 1,
        "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid",
        "num_attention_heads": 32, "num_expert_group": 1,
        "num_experts_per_token": 8, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 0, "num_shared_experts": 1,
        "q_lora_rank": None, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
        "vocab_size": 163840}
    for key, value in published.items():
        assert cfg[key] == value, key
    kda = [n for n in range(1, 27) if n % 4]
    assert cfg["linear_attn_config"] == {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": kda, "num_heads": 32, "short_conv_kernel_size": 4}
    assert set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocabulary"}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocabulary"]) == (5, 8, 20480)
    assert cfg["published"]["num_hidden_layers"] == 27
    assert cfg["published"]["num_experts"] == 256
    assert cfg["published"]["num_experts_per_token"] == 8
    assert cfg["published"]["vocab_size"] == 163840
    assert cfg["router_experts"] == 256 and cfg["experts_first"] == 0
    assert (cfg["deployment"]["chips_a_layer"],
            cfg["deployment"]["expert_parallel"]) == (32, 32)
    for item in ("kda_activation", "kda_l2norm", "kda_decay", "kda_beta",
                 "kda_output", "kda_init", "mla_nope", "router",
                 "router_bias", "shared_expert", "layer_27", "aux_loss",
                 "init", "optimizer"):
        assert item in cfg["assumed"]
    # the layers run: a leading dense layer and one whole period, 3 : 1
    assert counts_hybrid.layers(cfg) == kimi_linear.layers(cfg) == [
        ("kda", "dense"), ("kda", "sparse"), ("kda", "sparse"),
        ("mla", "sparse"), ("kda", "sparse")]
    assert kimi_linear.layout(cfg) == (1, kimi_linear.layers(cfg)[1:])
    # the floors of the guide, exactly
    assert cfg["num_experts"] == 8 and cfg["vocabulary"] * 8 == 163840


# what PR 32 wrote into ``BENCHMARK.json`` for this cell, by name
SIX = ("kda.ms_per_step", "kda.scan_ms_per_step", "kda.scan_roofline",
       "mla.ms_per_step", "flash.mla_ms_per_step", "flash.mla_roofline")
SHARED = ("tokens_per_s", "step_ms.p90", "step.mfu_pct.lm",
          "device.idle_pct.lm", "device.hbm_gib.lm", "moe.ms_per_step",
          "moe.experts_roofline", "moe.load_imbalance",
          "moe.shared_ms_per_step", "moe.moves_ms_per_step",
          "mlp.dense_ms_per_step")


def _by_name(entries):
    return {e["name"]: e for e in entries}


def _entries_of_the_cell_fit_the_form(bench):
    """Each entry is found by its NAME, wherever it stands: a later PR
    appends configurations, cells and metrics after these, and its
    cell's name after this one's in the lists they share."""
    cell = _by_name(bench["workloads"])[CELL]
    config = _by_name(bench["configs"])[cell["config"]]
    assert config["name"] == CONFIG
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    metrics = _by_name(bench["end_to_end"] + bench["per_layer"])
    mine = [metrics[name] for name in SIX]
    for metric in mine:
        assert set(metric) == {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert metric["workloads"] == [CELL]
    for name in SHARED:
        assert CELL in metrics[name]["workloads"], name
    for text in ([config["why"], config["source"], cell["why"]]
                 + [m["layer"] for m in mine]):
        assert 1 <= len(text) <= 200 and text.isascii() and text.isprintable()


def _with_a_cell_appended(bench):
    """A copy of the manifest as the next ``model_config`` PR leaves it:
    a seventh configuration and its cell after the last, the cell's name
    after the last in every list this cell shares with another, and one
    per-layer metric of its own after the last."""
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
        _by_name(bench["per_layer"])[SIX[0]],
        name="appended.ms_per_step", workloads=["appended-l1-train"]))
    return bench


@pytest.mark.parametrize("appended", [False, True],
                         ids=["as-it-is", "a-seventh-cell-appended"])
def test_manifest_entries_of_the_cell_fit_the_form(appended):
    """What this cell wrote into ``BENCHMARK.json``: each sentence is 1 to
    200 printable characters on one line (the first check refused a
    configuration's ``why`` of 206), each entry has just its keys, its
    six metrics are this cell's alone and the cell is in the lists it
    shares.  The manifest is append-only by name, not by position: the
    same holds of a copy to which a later PR's configuration, cell and
    metric are appended, so a check that pins an index fails here
    before it stops that PR (``[-1]`` and ``[-6:]`` did, PRs 33-38)."""
    bench = cells.manifest()
    _entries_of_the_cell_fit_the_form(
        _with_a_cell_appended(bench) if appended else bench)


# -- required counts by hand ----------------------------------------- #

def test_parameter_count_of_the_share():
    """602,434,432 by hand, from the file, from the shapes
    ``init_transformer`` builds for the driver's mapping of it, and from
    the reference's own init."""
    from chainermn_tpu.models import init_transformer

    cfg, job = cells.load_cell(CELL)[1:]
    kda = 3 * 2304 * 4096 + 3 * 4096 * 4 + 2 * (2304 * 128 + 128 * 4096) \
        + 2304 * 32 + 32 + 4096 + 128 + 4096 * 2304
    mla = 2304 * 32 * 192 + 2304 * 576 + 512 + 512 * 32 * 256 \
        + 4096 * 2304
    assert (kda, mla) == (39_514_272, 29_114_880)
    assert counts_hybrid.kda_params(cfg) == kda
    assert counts_hybrid.mla_params(cfg) == mla
    assert counts_hybrid.mlp_dense_params(cfg, "dense") == 63_700_992
    assert counts_hybrid.mlp_dense_params(cfg, "sparse") \
        == 589_824 + 7_077_888
    assert counts_hybrid.expert_params(cfg) == 7_077_888
    # the issue's figures a layer (norms 4,608; the bias 256)
    leading = kda + 63_700_992 + 4_608
    assert leading == 103_219_872
    sparse = 589_824 + 256 + 7_077_888 + 8 * 7_077_888 + 4_608
    assert kda + sparse == 103_809_952 and mla + sparse == 93_410_560
    want = leading + 3 * 103_809_952 + 93_410_560 + 2 * 20480 * 2304 + 2304
    assert counts_hybrid.params(cfg) == want == 602_434_432
    assert want * 16 / 2 ** 30 == pytest.approx(8.98, abs=0.01)
    shapes = jax.eval_shape(lambda: init_transformer(
        jax.random.PRNGKey(0), DRIVER._program_config(cfg, job)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == want
    assert shapes["leading"][0]["wqkv"].shape == (2304, 3, 32, 128)
    assert shapes["leading"][0]["w1"].shape == (2304, 9216)
    assert shapes["blocks"][2]["wq"].shape == (1, 1, 2304, 32, 192)
    assert shapes["blocks"][2]["wkvb"].shape == (1, 1, 512, 32, 256)
    assert shapes["blocks"][2]["wo"].shape == (1, 1, 32, 128, 2304)
    assert shapes["blocks"][3]["w1"].shape == (1, 1, 8, 2304, 1024)
    assert shapes["blocks"][3]["router_bias"].shape == (1, 1, 256)
    ref = jax.eval_shape(
        lambda: kimi_linear.init(jax.random.PRNGKey(0), cfg))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(ref)) == want


def test_flops_and_bytes_of_a_step_by_hand():
    cfg = _config()
    # an even spread: each of the 8 held experts gets 16,384 * 8 / 256
    # rows in each of the 4 sparse layers
    load = [[512] * 256] * 4
    rows = counts_hybrid.held_rows(cfg, load)
    assert rows == 4 * 8 * 512 == 16_384
    kda = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
    mla = 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304
    dense = 4 * kda + mla + 63_700_992 + 4 * (589_824 + 7_077_888) \
        + 20480 * 2304
    assert counts_hybrid.dense_matmul_params(cfg) == dense
    pairs = 16384 * 16385 // 2
    mla_core = 3 * pairs * 2 * 320 * 32
    scan = 4 * 16384 * 32 * 9 * 2 * 128 * 128
    want = 6 * dense * 16384 + mla_core + scan + 6 * rows * 7_077_888
    assert counts_hybrid.train_flops_per_step(cfg, 1, 16384, rows) == want
    # the issue's figure: one MLA layer, 2.75 TFLOP forward
    assert mla_core / 3 == pytest.approx(2.75e12, rel=0.01)
    # the kernels' shares: the backward's second scoring counts there
    flops, nbytes = counts_hybrid.flash_mla_step_flops_and_bytes(
        cfg, 1, 16384)
    assert flops == 3.5 * pairs * 2 * 320 * 32
    assert nbytes == 16384 * 32 * (
        (192 + 192 + 128 + 128) + (192 + 192 + 128 + 128 + 128)
        + (192 + 192 + 128)) * 2 + 2 * 16384 * 32 * 4
    flops, nbytes = counts_hybrid.kda_scan_step_flops_and_bytes(
        cfg, 1, 16384)
    assert flops == scan
    # q, k, v, g and o a channel, beta a head, float32, three passes
    assert nbytes == 3 * 4 * 16384 * 32 * (5 * 128 + 1) * 4
    flops, nbytes = counts_hybrid.expert_step_flops_and_bytes(cfg, rows)
    assert flops == 6 * rows * 7_077_888
    assert nbytes == 3 * (4 * 8 * 7_077_888 * 2 + 2 * rows * 2304 * 2)


def test_required_work_cannot_pass_what_the_op_and_the_kernels_do():
    """No share over 100 %: the recurrence's count is below what the
    chunked form multiplies, the MLA count below the kernels'
    block-granular work, and their bytes are those of the arrays the op
    and the kernels are handed."""
    cfg = _config()
    t, h, d, c = 16384, 32, 128, 64
    flops, nbytes = counts_hybrid.kda_scan_step_flops_and_bytes(cfg, 1, t)
    # a chunk a head, forward: W S, Q S and K^T U (2 C d d each), A' U
    # (2 C C d); the pair weights and the solve on top
    chunked_forward = (t // c) * h * (3 * 2 * c * d * d + 2 * c * c * d)
    assert flops / 4 / 3 < chunked_forward
    handed = 4 * t * h * (5 * d + 1) * 4      # q, k, v, g, o; beta
    assert nbytes == 3 * handed
    flops, nbytes = counts_hybrid.flash_mla_step_flops_and_bytes(cfg, 1, t)
    blocks = (t // 1024) * (t // 1024 + 1) // 2      # computed pairs
    assert flops < 3.5 * blocks * 1024 * 1024 * 2 * 320 * h
    assert counts_hybrid.count(cfg, "kda") == 4
    assert counts_hybrid.count(cfg, "mla") == 1
    assert counts_hybrid.count(cfg, "sparse") == 4


# -- scopes ----------------------------------------------------------- #

_HLO = """
ENTRY %main {
  %fusion.1 = f32[8,8]{1,0} fusion(%p0), kind=kLoop, metadata={op_name="jit(step)/jvp()/while/body/closed_call/attn/kda/kda/conv/mul"}
  %fusion.2 = f32[8,8]{1,0} fusion(%p0), kind=kOutput, metadata={op_name="jit(step)/transpose(jvp(attn/kda))/kda/scan/while/body/dot_general"}
  %triangular-solve.3 = f32[8,8]{1,0} fusion(%p0), kind=kOutput, metadata={op_name="jit(step)/jvp()/checkpoint/attn/kda/kda/scan/triangular_solve"}
  %fusion.4 = f32[8,8]{1,0} fusion(%p0), kind=kLoop, metadata={op_name="jit(step)/jvp(attn/kda)/kda/gate/logistic"}
  %fusion.5 = bf16[8,8]{1,0} fusion(%p0), kind=kOutput, metadata={op_name="jit(step)/jvp(attn/kda)/dot_general"}
  %fusion.6 = bf16[8,8]{1,0} fusion(%p0), kind=kOutput, metadata={op_name="jit(step)/jvp(attn/mla)/mla/latent/dot_general"}
  %custom-call.7 = bf16[32,8,128]{2,1,0} custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(attn/mla)/pallas_call"}
  ROOT %fusion.9 = bf16[8,8]{1,0} fusion(%p0), kind=kLoop, metadata={op_name="jit(step)/jvp()/moe/shared/mul"}
}
"""


def test_new_scopes_and_their_readers():
    assert scopes_hybrid.instruction_scopes(_HLO) == {
        "fusion.1": "kda/conv", "fusion.2": "kda/scan",
        "triangular-solve.3": "kda/scan", "fusion.4": "kda/gate",
        "fusion.6": "mla/latent"}
    # the accepted reader takes the two layers whole
    assert scopes.instruction_scopes(_HLO) == {
        "fusion.1": "attn/kda", "fusion.2": "attn/kda",
        "triangular-solve.3": "attn/kda", "fusion.4": "attn/kda",
        "fusion.5": "attn/kda", "fusion.6": "attn/mla",
        "custom-call.7": "attn/mla"}
    facts = {"scopes": scopes.instruction_scopes(_HLO),
             "scopes_hybrid": scopes_hybrid.instruction_scopes(_HLO),
             "kernels": {"custom-call.7": "pallas_call"},
             # least times of 2 ms and 1 ms a step at the peaks below
             "kda_scan_flops_bytes": (1e9, 2e6),
             "flash_mla_flops_bytes": (1e9, 1e5)}
    trace = {"op_self_s": {"fusion.1": 0.004, "fusion.2": 0.008,
                           "triangular-solve.3": 0.032, "fusion.4": 0.004,
                           "fusion.5": 0.012, "fusion.6": 0.004,
                           "custom-call.7": 0.016, "fusion.9": 9.0}}

    class window:
        iterations = 4

    ctx = {"facts": facts, "trace": trace, "window": window,
           "peaks": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}}
    read = {m: cells.module("layer_metrics", m).read for m in SIX}
    assert read["kda.ms_per_step"](ctx) == pytest.approx(15.0)
    assert read["kda.scan_ms_per_step"](ctx) == pytest.approx(10.0)
    assert read["mla.ms_per_step"](ctx) == pytest.approx(5.0)
    assert read["flash.mla_ms_per_step"](ctx) == pytest.approx(4.0)
    # memory-bound: 2 ms of 10; compute-bound: 1 ms of 4
    assert read["kda.scan_roofline"](ctx) == pytest.approx(20.0)
    assert read["flash.mla_roofline"](ctx) == pytest.approx(25.0)
    # a program without the scopes (the parent's), a run without a
    # device trace or a device without peaks: nothing is reported and
    # nothing raises
    for bare in (dict(ctx, facts={}), dict(ctx, trace=None)):
        assert all(r(bare) is None for r in read.values())
    assert read["kda.scan_roofline"](dict(ctx, peaks=None)) is None
    # these six are among the manifest's metrics of this cell alone
    # (a later PR may append more), and each moves the rate
    mine = {m["name"]: m for m in cells.manifest()["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(SIX) <= set(mine)
    assert all(mine[n]["moves"] == "tokens_per_s" for n in SIX)


def test_every_scope_is_in_the_compiled_toy_step():
    """The program's named scopes reach the compiled text's ``op_name``,
    forward and backward, and the counters are in the registry as the
    step is traced."""
    from chainermn_tpu.models import init_transformer, make_train_step
    from chainermn_tpu.parallel import MeshConfig
    from chainermn_tpu.utils.metrics import MetricsRegistry, set_registry

    _, cfg, job = cells.load_cell(CELL, rehearse=True)
    pcfg = DRIVER._program_config(cfg, job)
    mc = MeshConfig(devices=jax.devices()[:1], data=1)
    opt = build_optimizer(cfg["optimizer"])
    params = jax.eval_shape(
        lambda: init_transformer(jax.random.PRNGKey(0), pcfg))
    tok = jax.ShapeDtypeStruct((1, job["seq"]), jnp.int32)
    reg = MetricsRegistry(enabled=True)
    prev = set_registry(reg)
    try:
        text = make_train_step(mc, pcfg, opt).lower(
            params, jax.eval_shape(opt.init, params), tok, tok).compile(
            ).as_text()
    finally:
        set_registry(prev)
    assert set(scopes.instruction_scopes(text).values()) == {
        "attn/kda", "attn/mla", "moe/route", "moe/experts", "moe/combine"}
    assert set(scopes_mixed.instruction_scopes(text).values()) == {
        "moe/shared", "mlp/dense"}
    assert set(scopes_hybrid.instruction_scopes(text).values()) == {
        "kda/conv", "kda/scan", "kda/gate", "mla/latent"}
    # four KDA layers of 4 chunks (the block's remat replays the trace,
    # it does not trace again); one state a layer (one slab) of 4 heads
    # of 16 x 16 floats
    assert reg.counter("kda/chunks").value == 4 * 4
    assert reg.counter("kda/state_bytes_kept").value \
        == 4 * 4 * 16 * 16 * 4
    # the MLA kernels count as every flash call does: one block, a step
    # each for forward (traced twice), dq and dkv
    assert reg.counter("flash/grid_steps").value == 4
    assert reg.counter("flash/pairs_computed").value == 4


# -- the control at this toy size ------------------------------------ #

@pytest.mark.parametrize("seed", [7, 12, 2 ** 31 + 5])
def test_control_in_lower_precision_is_not_correct(seed):
    """The plain reference with its matmul and convolution operands
    rounded to float8_e4m3 (the router left in float32, as in the
    program), put in the program's place, is outside the limits that
    separate at this toy size."""
    from benchmarks.tools import limits

    cfg = _config(rehearse=True)
    got = limits.control_gaps(CELL, seed, "float8_e4m3fn", rehearse=True)
    for name in ("grad_norm_gap", "grad_norm_gap.median",
                 "delta_norm_gap.median"):
        assert got[name] > cfg["check"]["limits"][name], (name, got)


# -- where the routed rows go ---------------------------------------- #

def test_routing_load_tool_takes_one_thing_out_at_a_time():
    """``tools/routing_load.py`` at the toy size: every token's choices
    are counted in every variant, the mixers named are the sparse
    layers' own, and silencing a mixer's layers moves the loads of the
    layers after them and of no layer before them."""
    from benchmarks.tools import routing_load

    cfg, job = _config(rehearse=True), cells.load_cell(CELL, True)[2]
    sparse, (first, held), found = routing_load.loads(
        CELL, 5, True, steps=(1,))
    assert sparse == ["kda", "kda", "mla", "kda"]
    assert (first, held) == (cfg["experts_first"], cfg["num_experts"])
    found = dict(found)
    assert list(found) == ["as_run", "bias_zeroed", "kda_layers_silent",
                           "mla_layers_silent", "after_1_steps"]
    rows = job["batch"] * job["seq"] * cfg["num_experts_per_token"]
    for load in found.values():
        assert load.shape == (4, cfg["router_experts"])
        assert (load.sum(1) == rows).all()
    # the first sparse layers come before the MLA layer: silencing it
    # leaves them as they were, and moves its own layer's router
    np.testing.assert_array_equal(
        found["mla_layers_silent"][:2], found["as_run"][:2])
    assert (found["mla_layers_silent"][2] != found["as_run"][2]).any()
    assert (found["kda_layers_silent"][0] != found["as_run"][0]).any()
    assert (found["bias_zeroed"] != found["as_run"]).any()
    # --steps: the weights as run, followed over the optimizer's steps
    assert (found["after_1_steps"].sum(1) == rows).all()
