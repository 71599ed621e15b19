"""The Qwen3-Next cell's three followed steps at toy widths on the CPU,
the heavy ones, in a file of their own so that one worker takes it and
the reference's three steps are computed once: the program in float32
against the plain reference (``reference/qwen3_next.py``), and the
control in the nearest precision below the stated one, which has to come
out not correct.  The untraced rehearsal (the bf16 program, the whole
driver) is ``test_rehearsal.py``'s, which takes every cell of the
manifest."""

import jax
import pytest

from benchmarks.lib import cells, check
from benchmarks.lib.harness import build_optimizer, first_gradient_norms
from benchmarks.reference import qwen3_next
from benchmarks.reference.common import delta_norms

CELL = "qwen3-next-l4-ep16-train-gdn-longseq"
DRIVER = cells.module("drivers", "train_step_gdn")


@pytest.fixture(scope="module")
def job():
    """The toy twin, seeded weights, three batches and the reference's
    reading of them."""
    _, cfg, job = cells.load_cell(CELL, rehearse=True)
    key = jax.random.PRNGKey(11)
    tokens = jax.random.randint(
        jax.random.PRNGKey(12), (3, 1, job["seq"] + 1), 0, cfg["vocabulary"])
    batches = [(tokens[i, :, :-1], tokens[i, :, 1:]) for i in range(3)]
    make = lambda: qwen3_next.init(key, cfg)    # noqa: E731
    return cfg, job, make, batches, qwen3_next.follow(cfg, make, batches)


def _program_follow(cfg, job, make_params, batches):
    from chainermn_tpu.models import (
        init_transformer, make_train_step, shard_params)
    from chainermn_tpu.parallel import MeshConfig
    from chainermn_tpu.training import shard_opt_state

    pcfg = DRIVER._program_config(dict(cfg, compute_dtype="float32"), job)
    mc = MeshConfig(devices=jax.devices()[:1], data=1)
    opt = build_optimizer(cfg["optimizer"])

    def placed(params):
        return shard_params(mc, pcfg, dict(params, blocks=jax.tree.map(
            lambda a: a[None], params["blocks"])))

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


def test_program_follows_the_reference_in_float32(job):
    """Loss, first gradient and three AdamW steps on seeded weights: one
    period (Gated DeltaNet x 3 over two slabs of three chunks against
    the reference a token at a time, then gated attention with q/k
    norms and a quarter of each head rotated), 4 of 16 experts held
    beside the gated shared one, zero-centred norms whose stored ``w``
    is what AdamW decays, through the driver's own mapping of the file.
    In float32 the two agree to rounding, so a wrong tap, decay, head
    grouping, gate, norm, scale or share shows at once."""
    cfg, traffic, make, batches, ref = job
    assert traffic["seq"] == 6 * 64 and traffic["batch"] == 1
    seen = _program_follow(cfg, traffic, make, batches)
    for name, (gap, note) in check.gaps(seen, ref).items():
        assert gap < 1e-4, (name, gap, note)
    # every leaf compared and named: three linear layers of 17, the full
    # layer's 16, 3 outside
    assert len(ref["grad_norms"]) == 3 * 17 + 16 + 3
    assert {k.split("]")[-2].strip("['") for k in ref["grad_norms"]} == {
        "embed", "head", "ln_f", "ln1", "ln2", "w_in", "w_ba", "conv",
        "a_log", "dt_bias", "o_norm", "wo", "wq", "wkv", "wg", "q_norm",
        "k_norm", "router", "w1", "w2", "w3", "ws1", "ws2", "ws3", "wsg"}
    # every leaf has a gradient and moves, the norms' stored w among
    # them
    assert all(v > 0 for v in ref["grad_norms"].values())
    assert all(v > 0 for v in ref["delta_norms"].values())


def test_control_in_lower_precision_is_not_correct(job):
    """The plain reference with its matmul and convolution operands
    rounded to float8_e4m3 (the router left in float32, as in the
    program), put in the program's place, is outside the limits that
    separate at this toy size."""
    cfg, _, make, batches, ref = job
    control = qwen3_next.follow(cfg, make, batches, "float8_e4m3fn")
    got = {k: v[0] for k, v in check.gaps(control, ref).items()}
    for name in ("grad_norm_gap.median", "delta_norm_gap",
                 "delta_norm_gap.median"):
        assert got[name] > cfg["check"]["limits"][name], (name, got)
