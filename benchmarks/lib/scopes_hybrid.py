"""The scopes inside the hybrid layers' ``attn/<kind>`` that
``lib/scopes.py`` does not tell apart: a KDA layer's short convolution
(``kda/conv``), its recurrence (``kda/scan``) and its gates
(``kda/gate``: the decay, the step size and the output gate), and an
MLA layer's two latent projections and their norm (``mla/latent``).
Read from the compiled program's text in the same way (a fusion counts
under its root's scope).  ``lib/scopes.py``'s own pattern takes
``attn/kda`` and ``attn/mla`` whole."""

import re

from benchmarks.lib.scopes import _LINE, _OP_NAME

SCOPE = re.compile(r"\b(kda/(?:conv|scan|gate)|mla/latent)\b")


def instruction_scopes(hlo_text):
    """``{instruction name: scope}`` for the instructions whose
    ``op_name`` carries one of this file's scopes."""
    found = {}
    for line in hlo_text.splitlines():
        name, op_name = _LINE.match(line), _OP_NAME.search(line)
        scopes = SCOPE.findall(op_name.group(1)) if name and op_name else []
        if scopes:
            found[name.group(1)] = scopes[-1]
    return found


def scope_seconds(ctx, scope):
    """Device self time on device 0, inside the window, of the
    instructions under ``scope``.  None where the run has no trace or
    the program no such scope."""
    scopes = ctx["facts"].get("scopes_hybrid")
    if ctx["trace"] is None or not scopes:
        return None
    found = [s for name, s in ctx["trace"]["op_self_s"].items()
             if scopes.get(name) == scope]
    return sum(found) if found else None


def roofline_pct(ctx, seconds, required, name):
    """The least time the chip could take for ``required`` ``(flops,
    bytes)`` a step (the larger of FLOPs over peak FLOP/s and bytes
    over peak HBM bytes/s; a line of the run says which) over
    ``seconds`` of the window.  None where either is missing."""
    from benchmarks.lib.harness import log

    if seconds is None or required is None or ctx["peaks"] is None:
        return None
    by_compute = required[0] / ctx["peaks"]["flops_per_s"]
    by_memory = required[1] / ctx["peaks"]["hbm_bytes_per_s"]
    log(name, bound="compute" if by_compute >= by_memory else "memory",
        least_ms=f"{1e3 * max(by_compute, by_memory):.3f}")
    return 100 * max(by_compute, by_memory) * ctx["window"].iterations \
        / seconds
