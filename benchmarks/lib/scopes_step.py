"""Where the step program's device time goes, by the program's own
vocabulary of scopes: every device op of the traced window is put in a
phase (forward, backward, recompute, update) and on a path of scopes
by the op name the trace itself carries (``lib/op_names.py``), and the
classifier is the program's (``chainermn_tpu.utils.telemetry.
classify_op_name``, which owns the vocabulary ``DEVICE_SCOPES``): a new
scope is added there, never to a pattern here.

Device self time on device 0 inside the window, as everywhere (a loop
does not count its body twice); a fusion counts under the one op name the
compiler gave it (the convolution's or the product's where it holds
one, its root's otherwise); what else was fused into it is not told
apart.  Two things the
name does not tell:

- the grouped-matmul kernels the TPU compiler makes of
  ``lax.ragged_dot`` carry the compiler's own op name
  (``ragged-dot-none``): they stand under ``moe/experts`` by their
  instruction's name, as in ``lib/scopes.py``, and in no phase
  (``unnamed``);
- an instruction the compiler added of its own (a copy, a prefetch)
  has no op name: ``unnamed`` and on no path.

Everything returns None where the run has no trace or the program has
no classifier (the parent's).
"""

from benchmarks.lib import op_names
from benchmarks.lib.harness import log
from benchmarks.lib.readings import per_step_ms
from benchmarks.lib.scopes import RAGGED_DOT, RAGGED_SCOPE

PHASES = ("forward", "backward", "recompute", "update", "unnamed")
_KEY = "_scopes_step"


def _classifier():
    """The program's ``classify_op_name``; None where it has none (the
    parent under these files)."""
    try:
        from chainermn_tpu.utils.telemetry import classify_op_name
    except ImportError:
        return None
    return classify_op_name


def classified(ctx):
    """``{instruction: (phase, path, seconds)}`` for every instruction
    with self time in the window; memoised on ``ctx``."""
    if _KEY not in ctx:
        classify = _classifier()
        names = None if classify is None else op_names.read(ctx)
        found = None
        if names is not None:
            found = {}
            for ins, s in ctx["trace"]["op_self_s"].items():
                phase, path = classify(names.get(ins, ""))
                if ins.startswith(RAGGED_DOT):
                    path = (RAGGED_SCOPE,)
                found[ins] = (phase, path, s)
        ctx[_KEY] = found
    return ctx[_KEY]


def on_path(path, *scopes):
    """Whether ``path`` wears ``scopes`` in that order (others may stand
    between).  ``"attn/"``, with its slash, stands for any kind."""
    at = 0
    for scope in scopes:
        while at < len(path) and not (
                path[at] == scope or scope.endswith("/")
                and path[at].startswith(scope)):
            at += 1
        if at == len(path):
            return False
        at += 1
    return True


def _ms(ctx, keep):
    """ms a step of the instructions ``keep(instruction, phase, path)``
    takes; None where nothing is classified or ``keep`` takes none."""
    found = classified(ctx)
    if found is None:
        return None
    kept = [s for ins, (phase, path, s) in found.items()
            if keep(ins, phase, path)]
    return per_step_ms(ctx, sum(kept)) if kept else None


def phase_ms(ctx, phase):
    """ms a step of the ops in ``phase``."""
    return _ms(ctx, lambda ins, ph, path: ph == phase)


def path_ms(ctx, *scopes):
    """ms a step of the ops that wear ``scopes`` (``on_path``), in every
    phase."""
    return _ms(ctx, lambda ins, ph, path: on_path(path, *scopes))


def unscoped_ms(ctx):
    """ms a step of the ops on no path at all."""
    return _ms(ctx, lambda ins, ph, path: not path)


def attention_parts(ctx):
    """ms a step of everything under ``attn/<kind>``, by child; what
    wears no child is ``rest`` (the relayouts to the kernel's layout and
    back, the backward's ``delta``, the residual add).  The parts sum to
    ``whole``."""
    found = classified(ctx)
    if found is None:
        return None
    parts = {"whole": 0.0, "rest": 0.0}
    for phase, path, s in found.values():
        if not on_path(path, "attn/"):
            continue
        parts["whole"] += s
        children = [p for p in path if p.startswith("attn.")]
        part = children[0] if children else "rest"
        parts[part] = parts.get(part, 0.0) + s
    return {k: per_step_ms(ctx, v) for k, v in parts.items()}


def table(ctx):
    """The ``[scopes]`` lines: every path that took time, by phase, ms
    a step, largest first; ``(none)`` is what wears no scope."""
    found = classified(ctx)
    if found is None:
        return
    rows = {}
    for phase, path, s in found.values():
        row = rows.setdefault(path, dict.fromkeys(PHASES, 0.0))
        row[phase] += s
    for path, row in sorted(rows.items(),
                            key=lambda kv: -sum(kv[1].values())):
        log("scopes", path=">".join(path) or "(none)",
            total=f"{per_step_ms(ctx, sum(row.values())):.3f}",
            **{ph: f"{per_step_ms(ctx, row[ph]):.3f}" for ph in PHASES})
