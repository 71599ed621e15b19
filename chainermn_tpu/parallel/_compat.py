"""The jax (0.9) shard_map vocabulary the package uses, in ONE place.

Plain re-exports, so call sites do not churn when a name moves, plus
the two spellings this jax does not give as the package needs them:

- ``all_gather_invariant`` is not public in jax 0.9.0
  (``jax._src.lax.parallel``);
- ``pcast(..., to="varying")`` raises on a value that is ALREADY
  varying over one of the named axes.  Carry inits and replicated
  operands are retyped wherever a scan or a collective needs them
  varying, and whether the input arrives varying depends on the caller
  (a pmean'd loss is not, a per-shard gradient is) — so the package's
  ``pcast`` retypes only the axes that still need it.
"""

from jax import shard_map, typeof
from jax import lax as _lax
from jax._src.lax.parallel import all_gather_invariant
from jax.lax import axis_size


def pcast(x, axis_name, *, to):
    """``lax.pcast``; for ``to="varying"``, over the named axes ``x`` is
    not varying on yet (the identity when there are none)."""
    if to == "varying":
        names = (axis_name,) if isinstance(axis_name, str) \
            else tuple(axis_name)
        axis_name = tuple(a for a in names if a not in typeof(x).vma)
        if not axis_name:
            return x
    return _lax.pcast(x, axis_name, to=to)


__all__ = [
    "all_gather_invariant",
    "axis_size",
    "pcast",
    "shard_map",
    "typeof",
]
