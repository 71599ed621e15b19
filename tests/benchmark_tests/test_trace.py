"""The reduction from a profiler trace to numbers: on a trace worked by
hand, and on a small trace recorded from the chip in PR 23 (cut by
``benchmarks/tools/trim_trace.py`` to what the reduction reads)."""

import glob
import gzip
import json
import os

import pytest

from benchmarks.lib import trace

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1000     # the hand-worked trace counts in microseconds


def _hand_trace():
    """Device 0 runs a warm-up step (0-8), then two iterations of a
    ``jit_step`` and a small ``jit_atleast_1d`` each; the window is the
    last two executions of ``jit_step`` and opens at 10, where the
    program before them ended.  Inside it: busy 20-40 (a ``while``
    holding two fusions, with a hole 28-30 that the while covers),
    42-43 (the small program), 50-80 (an asynchronous all-gather 50-62
    with a fusion under it 55-60, then a kernel 62-80), 82-83.
    Device 1: one long op per step."""
    def ev(name, s, e):
        return (name, s * US, e * US)

    return {
        "/device:TPU:0": {
            "XLA Modules": [
                ev("jit_step", 0, 8), ev("jit_atleast_1d", 9, 10),
                ev("jit_step", 20, 40), ev("jit_atleast_1d", 42, 43),
                ev("jit_step", 50, 80), ev("jit_atleast_1d", 82, 83)],
            "XLA Ops": [
                ev("fusion.0", 0, 8), ev("copy.1", 9, 10),
                ev("while.1", 20, 40), ev("fusion.1", 20, 28),
                ev("fusion.2", 30, 40), ev("copy.1", 42, 43),
                ev("all-gather-start.1", 50, 51), ev("fusion.3", 55, 60),
                ev("all-gather-done.1", 60, 62), ev("closed_call.8", 62, 80),
                ev("copy.1", 82, 83)]},
        "/device:TPU:1": {
            "XLA Modules": [ev("jit_step", 0, 8), ev("jit_step", 20, 45),
                            ev("jit_step", 50, 90)],
            "XLA Ops": [ev("fusion.0", 0, 8), ev("fusion.1", 20, 45),
                        ev("fusion.1", 50, 90)]},
    }


def test_reduction_by_hand():
    s = trace.reduce(_hand_trace(), iterations=2)
    d0, d1 = s["devices"]
    assert (d0["lo"], d0["hi"]) == (10 * US, 83 * US)
    assert (d1["lo"], d1["hi"]) == (8 * US, 90 * US)
    assert d0["step_program"] == "jit_step"
    # the step program's own executions inside the window: 20-40, 50-80
    assert d0["step_program_s"] == pytest.approx(50e-6)
    assert d1["step_program_s"] == pytest.approx(65e-6)
    # 20-40, 42-43, 50-51, 55-80, 82-83
    assert d0["busy_s"] == pytest.approx((20 + 1 + 1 + 25 + 1) * 1e-6)
    assert d1["busy_s"] == pytest.approx(65e-6)
    assert s["window_s"] == pytest.approx((73 + 82) / 2 * 1e-6)
    assert s["busy_s"] == pytest.approx((48 + 65) / 2 * 1e-6)
    assert s["idle_share_worst"] == pytest.approx(1 - 48 / 73)
    # the collective runs 50-62; the fusion hides 55-60 of it
    assert d0["collective_s"] == pytest.approx(12e-6)
    assert d0["collective_exposed_s"] == pytest.approx(7e-6)
    # self time: the while keeps only what its body does not cover
    assert s["op_self_s"]["while.1"] == pytest.approx(2e-6)
    assert s["op_self_s"]["fusion.2"] == pytest.approx(10e-6)
    # idle gaps by the programs on either side
    assert s["idle_gaps_s"] == pytest.approx({
        "window opens -> jit_step": 10e-6,
        "jit_atleast_1d -> jit_step": 7e-6,
        "jit_step -> jit_atleast_1d": (2 + 2) * 1e-6,
        "inside jit_step": 4e-6})


def test_mfu_is_read_from_the_step_programs_device_time():
    """Required operations over what the chips could do while the step
    program ran on them: two iterations of 100 units at 1e6 FLOPs a
    unit, the program on the device for (50 + 65) / 2 microseconds of a
    window of 73 to 82, two chips of 1e13 FLOP/s.  A host that keeps the
    device waiting between steps does not move it."""
    from benchmarks.lib import readings

    class Window:
        units, iterations = 100, 2

    ctx = {"trace": trace.reduce(_hand_trace(), iterations=2),
           "window": Window, "facts": {"flops_per_unit": 1e6}, "chips": 2,
           "peaks": {"flops_per_s": 1e13}}
    assert readings.mfu_pct(ctx) == pytest.approx(
        100 * 2e8 / (2 * 1e13 * 57.5e-6))
    assert readings.mfu_pct(dict(ctx, trace=None)) is None


def test_breakdown_names_are_stable():
    s = trace.reduce(_hand_trace(), iterations=2)
    b = trace.breakdown(s, {"closed_call.8": "jit(step)/pallas_call"})
    names = [n for n, _ in b["device_ops"]]
    assert "jit(step)/pallas_call" in names and "fusion" in names
    assert not any(n[-1].isdigit() for n in names)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0][0] == "window opens -> jit_step"


@pytest.mark.parametrize("name,expected", [
    ("all-gather.29", True), ("all-gather-start.1", True),
    ("reduce_scatter.67", True), ("reduce-scatter.3", True),
    ("async-collective-start.12", True), ("async-collective-done.12", True),
    ("all-reduce.4", True), ("collective-permute-start", True),
    ("slice_reduce_fusion.2", False), ("fusion.233", False),
    ("convert_reduce_fusion.8", False)])
def test_collective_names_as_the_tpu_compiler_writes_them(name, expected):
    assert trace.is_collective(name) is expected


def test_too_few_step_executions_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce(_hand_trace(), iterations=3)


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce({"/host:CPU": {"python": []}}, iterations=2)


def test_kernel_instructions_from_hlo_text():
    hlo = '''
  %closed_call.8 = (bf16[128,2048,64]{2,1,0}, f32[128,2048,128]{2,1,0}) custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp()/while/body/closed_call/pallas_call" stack_frame_id=66}
  %fusion.3 = bf16[8]{0} fusion(%c), kind=kLoop, metadata={op_name="jit(step)/mul"}
  %checkpoint.21 = bf16[128,2048,64]{2,1,0} custom-call(%d), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp())/while/body/checkpoint/pallas_call"}
'''
    assert trace.kernel_instructions(hlo) == {
        "closed_call.8": "jit(step)/jvp()/while/body/closed_call/pallas_call",
        "checkpoint.21":
            "jit(step)/transpose(jvp())/while/body/checkpoint/pallas_call"}
    assert trace.module("jit_step(4828622200550264098)") == "jit_step"
    assert trace.instruction(
        "%fusion.34 = (f32[256]{0}) fusion(bf16[2]{0} %x), kind=kOutput") \
        == "fusion.34"


RECORDED = sorted(glob.glob(os.path.join(HERE, "traces", "*.json.gz")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_reduction_on_a_trace_recorded_from_the_chip(path):
    with gzip.open(path, "rt") as f:
        planes = json.load(f)
    with open(path.replace(".json.gz", ".expected.json")) as f:
        expected = json.load(f)
    s = trace.reduce(planes, expected["iterations"])
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["window_s"] == pytest.approx(expected["window_s"])
    assert s["busy_s"] == pytest.approx(expected["busy_s"])
    # the step program is on the device for as long as its ops run, and
    # little longer
    assert s["devices"][0]["step_program_s"] == pytest.approx(
        expected["step_program_s"])
    assert s["devices"][0]["busy_s"] <= 1.001 * expected["step_program_s"]
    assert s["idle_share_worst"] == pytest.approx(
        expected["idle_share_worst"], abs=1e-9)
    d0 = s["devices"][0]
    assert d0["collective_s"] == pytest.approx(expected["collective_s"])
    assert d0["collective_exposed_s"] == pytest.approx(
        expected["collective_exposed_s"])
    for name, seconds in expected["kernel_s"].items():
        got = sum(e - b for n, b, e in d0["ops"] if n == name) / 1e9
        assert got == pytest.approx(seconds), name
    # self times add up to the busy time of the device they are from
    assert sum(s["op_self_s"].values()) == pytest.approx(d0["busy_s"])
