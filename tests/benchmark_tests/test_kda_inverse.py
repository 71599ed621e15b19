"""``kda.inverse_ms_per_step``'s reader on a sample of compiled text:
the custom calls under ``kda/scan`` are read, whoever wrote them and
whatever their name; the MLA layer's kernels and the fusions beside
them are not; a program without such a call reports nothing."""

import pytest

from benchmarks.lib import cells, scopes, scopes_hybrid
from benchmarks.lib.trace import kernel_instructions

_OTHERS = """
  %fusion.2 = f32[8,8]{1,0} fusion(%p0), kind=kOutput, metadata={op_name="jit(step)/transpose(jvp(attn/kda))/kda/scan/while/body/dot_general"}
  %multiply_bitcast_fusion.4 = f32[64,64,128]{2,1,0} fusion(%p0), kind=kLoop, metadata={op_name="jit(step)/jvp()/checkpoint/attn/kda/kda/scan/while/body/mul"}
  %custom-call.7 = bf16[32,8,128]{2,1,0} custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(attn/mla)/pallas_call"}
  %custom-call.8 = f32[4,1,32,128,128]{4,3,2,1,0} custom-call(), custom_call_target="AllocateBuffer", metadata={op_name="jit(step)/jvp()/moe/shared/broadcast_in_dim"}
  ROOT %fusion.9 = bf16[8,8]{1,0} fusion(%p0), kind=kLoop, metadata={op_name="jit(step)/jvp(attn/kda)/kda/gate/logistic"}
}
"""
# XLA's kernel, as the parent's program holds it
_XLAS = """
ENTRY %main {
  %custom-call.42 = f32[1,32,4,1,64,64]{1,4,5,3,2,0} custom-call(%p0), custom_call_target="InvertDiagBlocksLowerTriangular", metadata={op_name="jit(step)/jvp()/while/body/closed_call/attn/kda/kda/scan/while/body/closed_call/triangular_solve"}
  %custom-call.43 = f32[1,32,4,1,64,64]{1,4,5,3,2,0} custom-call(%p0), custom_call_target="InvertDiagBlocksLowerTriangular", metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/attn/kda/kda/scan/while/body/checkpoint/rematted_computation/triangular_solve"}
""" + _OTHERS
# a Pallas kernel, under the names the compiler gives its call sites
_OURS = """
ENTRY %main {
  %closed_call.29 = f32[64,64,128]{2,1,0} custom-call(%multiply_bitcast_fusion.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp()/while/body/closed_call/attn/kda/kda/scan/while/body/closed_call/pallas_call"}
  %rematted_computation.10 = f32[64,64,128]{2,1,0} custom-call(%multiply_bitcast_fusion.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/attn/kda/kda/scan/while/body/checkpoint/rematted_computation/pallas_call"}
""" + _OTHERS
_NONE = "\nENTRY %main {" + _OTHERS
_INVERSIONS = {"custom-call.42": 0.020, "custom-call.43": 0.012,
               "closed_call.29": 0.006, "rematted_computation.10": 0.002}


def _ctx(text):
    class window:
        iterations = 4

    facts = {"scopes": scopes.instruction_scopes(text),
             "scopes_hybrid": scopes_hybrid.instruction_scopes(text),
             "kernels": kernel_instructions(text)}
    trace = {"op_self_s": dict(_INVERSIONS, **{
        "fusion.2": 0.008, "multiply_bitcast_fusion.4": 0.004,
        "custom-call.7": 0.016, "custom-call.8": 0.001,
        "fusion.9": 9.0})}
    return {"facts": facts, "trace": trace, "window": window, "peaks": None}


def _read(metric, ctx):
    return cells.module("layer_metrics", metric).read(ctx)


@pytest.mark.parametrize("text,want_ms", [
    (_XLAS, 8.0), (_OURS, 2.0), (_NONE, None)],
    ids=["xlas-kernel", "pallas-kernel", "no-custom-call"])
def test_only_the_custom_calls_under_the_scan_are_read(text, want_ms):
    """32 ms of XLA's two call sites or 8 ms of a kernel's two over four
    steps; the fusions under ``kda/scan``, the MLA kernel and a buffer
    allocated under another scope add nothing, and a program whose scan
    holds no custom call reports nothing."""
    got = _read("kda.inverse_ms_per_step", _ctx(text))
    assert got == (None if want_ms is None else pytest.approx(want_ms))


@pytest.mark.parametrize("text", [_XLAS, _OURS, _NONE],
                         ids=["xlas-kernel", "pallas-kernel", "neither"])
def test_accepted_readers_are_unmoved_by_the_new_instructions(text):
    """The MLA kernels' reader takes the kernels under ``attn/mla``
    alone: 16 ms over four steps whatever stands under ``kda/scan``;
    the scan's reader keeps reading the whole scope, inversions
    included."""
    ctx = _ctx(text)
    assert _read("flash.mla_ms_per_step", ctx) == pytest.approx(4.0)
    inside = sum(s for name, s in _INVERSIONS.items()
                 if name in ctx["facts"]["scopes_hybrid"])
    assert _read("kda.scan_ms_per_step", ctx) == pytest.approx(
        1e3 * (0.008 + 0.004 + inside) / 4)


@pytest.mark.parametrize("bare", [
    lambda ctx: dict(ctx, facts={}), lambda ctx: dict(ctx, trace=None),
    lambda ctx: dict(ctx, facts={"scopes_hybrid": {}, "kernels": {}})],
    ids=["parent-without-the-scopes", "untraced-run", "no-hybrid-layer"])
def test_nothing_to_read_reports_nothing(bare):
    assert _read("kda.inverse_ms_per_step", bare(_ctx(_OURS))) is None
