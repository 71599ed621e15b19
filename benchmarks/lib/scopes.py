"""Which part of a layer a device operation belongs to, from the
compiled program's text.

The program wraps the parts of its typed layers in ``jax.named_scope``
(``attn/<kind>``, ``moe/route``, ``moe/experts``, ``moe/combine``), and
the scope's name travels in every instruction's ``op_name`` metadata,
through differentiation and remat (``transpose(jvp(moe/route))``).  The
profiler names a device event by its instruction, so: instruction ->
scope here, scope -> device time in the readers.

A fusion counts under its root's scope: the compiler gives a fusion the
``op_name`` of its root instruction, and what else was fused into it is
not told apart.  The grouped-matmul kernels the TPU compiler makes of
``lax.ragged_dot`` (``ragged-dot-*`` and their ``ragged-dot-metadata-*``)
lose their ``op_name`` to the compiler's own; the expert layer's
grouped products are the only ragged dots of the step, so they count
under ``moe/experts`` by their instruction's name.
"""

import re

SCOPE = re.compile(r"\b(attn/[A-Za-z0-9_\-]+|moe/(?:route|experts|combine))")
RAGGED_DOT = "ragged-dot"
RAGGED_SCOPE = "moe/experts"
_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def instruction_scopes(hlo_text):
    """``{instruction name: scope}`` for every instruction of a compiled
    program whose ``op_name`` carries one of the program's scopes (the
    innermost, where scopes nest)."""
    found = {}
    for line in hlo_text.splitlines():
        name = _LINE.match(line)
        if not name:
            continue
        name = name.group(1)
        if name.startswith(RAGGED_DOT):
            found[name] = RAGGED_SCOPE
            continue
        op_name = _OP_NAME.search(line)
        scopes = SCOPE.findall(op_name.group(1)) if op_name else []
        if scopes:
            found[name] = scopes[-1]
    return found


def scope_seconds(ctx, prefix, among=None):
    """Device time (self time: a loop does not count its body twice) on
    device 0, inside the window, of the instructions whose scope starts
    with ``prefix``; ``among``, a set of instruction names, narrows it
    (to the kernels, say).  None where the run has no trace or the
    program no such scope."""
    scopes = ctx["facts"].get("scopes")
    if ctx["trace"] is None or not scopes:
        return None
    wanted = {name for name, scope in scopes.items()
              if scope.startswith(prefix)
              and (among is None or name in among)}
    found = [s for name, s in ctx["trace"]["op_self_s"].items()
             if name in wanted]
    return sum(found) if found else None
