"""The scopes ``lib/scopes.py`` does not know: the shared expert
(``moe/shared``) and the dense MLP of a typed layer (``mlp/dense``),
read from the compiled program's text in the same way (a fusion counts
under its root's scope)."""

import re

from benchmarks.lib.scopes import _LINE, _OP_NAME

SCOPE = re.compile(r"\b(moe/shared|mlp/dense)\b")


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
    the program no such scope (the parent's)."""
    scopes = ctx["facts"].get("scopes_mixed")
    if ctx["trace"] is None or not scopes:
        return None
    found = [s for name, s in ctx["trace"]["op_self_s"].items()
             if scopes.get(name) == scope]
    return sum(found) if found else None
