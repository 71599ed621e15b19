"""The LFM2 cell's three followed steps at toy widths on the CPU, the
heavy ones, in a file of their own so that one worker takes it and the
reference's three steps are computed once: the program in float32
against the plain reference (``reference/lfm2.py``), the same with the
two gates' sides swapped in the reference, which has to fail, and the
control in the nearest precision below the stated one, which has to
come out not correct.  The untraced rehearsal (the bf16 program, the
whole driver) is ``test_rehearsal.py``'s, which takes every cell of the
manifest."""

import jax
import pytest

from benchmarks.lib import cells, check
from benchmarks.lib.harness import build_optimizer, first_gradient_norms
from benchmarks.reference import lfm2
from benchmarks.reference.common import delta_norms

CELL = "lfm2-24b-l5-ep8-train-conv-seq8192"
DRIVER = cells.module("drivers", "train_step_lfm2")


@pytest.fixture(scope="module")
def job():
    """The toy twin, seeded weights, three batches and the reference's
    reading of them."""
    _, cfg, job = cells.load_cell(CELL, rehearse=True)
    key = jax.random.PRNGKey(11)
    tokens = jax.random.randint(
        jax.random.PRNGKey(12), (3, job["batch"], job["seq"] + 1), 0,
        cfg["vocabulary"])
    batches = [(tokens[i, :, :-1], tokens[i, :, 1:]) for i in range(3)]
    make = lambda: lfm2.init(key, cfg)    # noqa: E731
    return cfg, job, make, batches, lfm2.follow(cfg, make, batches)


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


@pytest.fixture(scope="module")
def seen(job):
    cfg, traffic, make, batches, _ = job
    return _program_follow(cfg, traffic, make, batches)


def test_program_follows_the_reference_in_float32(job, seen):
    """Loss, first gradient and three AdamW steps on seeded weights:
    the convolution layer with the dense SwiGLU, then one period (q/k
    normed attention over grouped heads with the whole head rotated,
    three convolution layers), 2 of 16 experts held under the biased
    sigmoid router with no shared expert, the head tied, through the
    driver's own mapping of the file.  In float32 the two agree to
    rounding, so a wrong tap, gate, order of norm and rotation, head
    grouping, bias, scale or share shows at once."""
    cfg, traffic, make, batches, ref = job
    assert (traffic["batch"], traffic["seq"]) == (2, 256)
    for name, (gap, note) in check.gaps(seen, ref).items():
        assert gap < 1e-4, (name, gap, note)
    # every leaf compared and named: the leading layer's 8, three
    # sparse convolution layers of 10, the attention layer's 12, 2
    # outside (the embedding is the head)
    assert len(ref["grad_norms"]) == 8 + 3 * 10 + 12 + 2
    assert {k.split("]")[-2].strip("['") for k in ref["grad_norms"]} == {
        "embed", "ln_f", "ln1", "ln2", "w_in", "conv", "wo", "wq", "wkv",
        "q_norm", "k_norm", "router", "router_bias", "w1", "w2", "w3"}
    # every leaf but the held bias has a gradient and moves
    for norms in (ref["grad_norms"], ref["delta_norms"]):
        assert all((v == 0) == ("router_bias" in k)
                   for k, v in norms.items()), norms


def test_gates_on_the_wrong_side_of_the_convolution_fail(
        monkeypatch, job, seen):
    """``C . conv(B . x)`` is not ``conv(B . C . x)``: the reference
    with both gates ahead of the convolution, put in the reference's
    place, reads the program as not correct by the float32 limit."""
    cfg, _, make, batches, ref = job
    sound = lfm2._short_conv
    monkeypatch.setattr(
        lfm2, "_short_conv",
        lambda rnd, b, c, x, w: sound(rnd, b * c, 1.0, x, w))
    lfm2._jitted_step.cache_clear()
    try:
        wrong = lfm2.follow(cfg, make, batches[:1])
    finally:
        lfm2._jitted_step.cache_clear()
    gaps = check.gaps(dict(seen, losses=seen["losses"][:1]),
                      dict(wrong, delta_norms=ref["delta_norms"]))
    assert max(gaps["loss_gap.step1"][0], gaps["grad_norm_gap"][0]) \
        > 100 * 1e-4, gaps


def test_control_in_lower_precision_is_not_correct(job):
    """The plain reference with its matmul and convolution operands
    rounded to float8_e4m3 (the router left in float32, as in the
    program), put in the program's place, is outside the limits that
    separate at this toy size."""
    cfg, _, make, batches, ref = job
    control = lfm2.follow(cfg, make, batches, "float8_e4m3fn")
    got = {k: v[0] for k, v in check.gaps(control, ref).items()}
    for name in cfg["check"]["separate"]:
        assert got[name] > cfg["check"]["limits"][name], (name, got)
