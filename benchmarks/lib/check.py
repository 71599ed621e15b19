"""The comparison that decides ``correct``: the program's first steps
against the plain reference's, number by number, each with its limit.

A gap between two per-leaf norms is measured against the reference's
norm of that leaf or of the median leaf, whichever is larger: some
leaves' gradients are all but zero, and a relative gap on them is
noise."""

import math
import statistics


def leaf_gaps(program, reference):
    """``{leaf: gap}`` between two ``{leaf: norm}`` readings."""
    if sorted(program) != sorted(reference):
        raise ValueError(
            "program and reference disagree on the parameter leaves: "
            f"{sorted(set(program) ^ set(reference))}")
    floor = statistics.median(reference.values())
    return {k: abs(program[k] - reference[k]) / max(reference[k], floor)
            for k in reference}


def _worst(by_leaf):
    leaf = max(by_leaf, key=lambda k: by_leaf[k]
               if math.isfinite(by_leaf[k]) else math.inf)
    return by_leaf[leaf], leaf


def gaps(program, reference):
    """``{number: (value, note)}`` for the numbers every training cell
    compares.  ``program`` and ``reference`` are the ``follow`` readings:
    ``losses``, ``grad_norms``, ``delta_norms``.  The two norms are taken
    by the worst leaf and by the median leaf.  Each step's loss is a
    number of its own: the first is the forward pass alone, the later
    ones also carry how far two trajectories have drifted apart."""
    if len(program["losses"]) != len(reference["losses"]):
        raise ValueError("program and reference followed different "
                         "numbers of steps")
    out = {f"loss_gap.step{i + 1}": (abs(p - r) / abs(r), f"{p:.6f} vs {r:.6f}")
           for i, (p, r) in enumerate(
               zip(program["losses"], reference["losses"]))}
    for name, key in (("grad_norm_gap", "grad_norms"),
                      ("delta_norm_gap", "delta_norms")):
        by_leaf = leaf_gaps(program[key], reference[key])
        out[name] = _worst(by_leaf)
        # the worst leaf swings by its nature (in a ResNet at seeded
        # weights it is a batch-norm scale whose gradient nearly
        # cancels); the median leaf is steady from seed to seed
        out[name + ".median"] = (statistics.median(by_leaf.values()),
                                 f"of {len(by_leaf)} leaves")
    return out


def judge(compared, limits, log):
    """Print each number beside its limit; True when all are inside.
    A number that is not finite is outside."""
    ok = True
    for name, (value, note) in compared.items():
        limit = limits[name]
        inside = math.isfinite(value) and value <= limit
        log("check", number=name, value=f"{value:.6g}", limit=limit,
            at=note, inside=inside)
        ok = ok and inside
    return ok
