"""Kernels: the part of ``kda.scan_ms_per_step`` that inverts the
chunks' unit-triangular systems (``ops/kda.py`` ``solve``): device self
time a step, device 0, of the custom calls under ``kda/scan``, forward,
recomputed and backward.  A custom call is an instruction the compiler
names ``custom-call*`` (XLA's own ``InvertDiagBlocksLowerTriangular``)
or a Pallas kernel (``tpu_custom_call``, the driver's ``kernels``,
whatever its name); the fusions around them (the pair weights, the
products that apply the inverse, the scans) are not.  The flash kernels
stand under ``attn/mla`` and are not read here."""

from benchmarks.lib.readings import per_step_ms


def read(ctx):
    scopes = ctx["facts"].get("scopes_hybrid")
    if ctx["trace"] is None or not scopes:
        return None
    kernels = ctx["facts"].get("kernels", ())
    found = [s for name, s in ctx["trace"]["op_self_s"].items()
             if scopes.get(name) == "kda/scan"
             and (name.startswith("custom-call") or name in kernels)]
    return per_step_ms(ctx, sum(found)) if found else None
