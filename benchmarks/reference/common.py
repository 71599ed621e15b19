"""What the plain references share: the precision switch of the
control, and the per-leaf norms the check compares."""

import jax
import jax.numpy as jnp

# the precision a reference computes in: "float32" is the reference
# proper; the others stand in for a program that computed lower than its
# configuration states (the control), by rounding every matmul and
# convolution operand to that format's (exponent, mantissa) bits and
# multiplying in float32
PRECISIONS = {
    "float32": None,
    "bfloat16": (8, 7),
    "float8_e4m3fn": (4, 3),
}


def rounder(precision):
    """``x -> x`` rounded to ``precision``, with the gradient passed
    straight through (the rounding is the fault under test, not a
    non-differentiable op).  ``lax.reduce_precision`` and not a pair of
    ``astype``: XLA is free to drop a convert pair that only loses
    precision (``xla_allow_excess_precision``), and then the control
    would round nothing."""
    bits = PRECISIONS[precision]
    if bits is None:
        return lambda x: x
    return lambda x: x + jax.lax.stop_gradient(
        jax.lax.reduce_precision(x, *bits) - x)


def leaf_norms(tree):
    """``{path: l2 norm}`` over the leaves, as one small device tree."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for p, x in flat}


def to_floats(norms):
    return {k: float(v) for k, v in jax.device_get(norms).items()}


def delta_norms(after, before):
    """``{path: l2 norm}`` of each leaf's change, as floats."""
    return to_floats(jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))(after, before))
