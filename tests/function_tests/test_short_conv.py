"""``ops/recurrent.py``'s doubly gated short convolution: the Pallas
kernels (interpreted here) and the plain form against the operator as
three shifted slices written out in this file, forward and every
cotangent, at lane-aligned and ragged shapes, 3 and 4 taps; which
shapes take which form; what it counts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from chainermn_tpu.ops import recurrent
from chainermn_tpu.utils.metrics import MetricsRegistry, set_registry

TOKENS = recurrent.TOKENS

# (batch, tokens, channels, taps, fused): two blocks of tokens, so the
# halo crosses a block's edge and a sequence's end meets the next's
# start; one block; ragged tokens and ragged channels take the plain
# form
SHAPES = pytest.mark.parametrize("b,t,c,taps,fused", [
    (2, 2 * TOKENS, 256, 3, True), (1, TOKENS, 128, 4, True),
    (1, 3 * TOKENS, 128, 3, True), (2, 100, 128, 3, False),
    (1, TOKENS, 96, 3, False), (2, 40, 24, 4, False)],
    ids=["two-blocks-3", "one-block-4", "three-blocks-3", "ragged-tokens",
         "ragged-channels", "ragged-both-4"])


def _three_slices(bcx, w):
    """``C . conv(B . x)`` with the convolution as ``taps`` shifted
    slices: this file's own yardstick, written from the equations."""
    (c, taps), t = w.shape, bcx.shape[1]
    gate_b, gate_c, x = bcx[..., :c], bcx[..., c:2 * c], bcx[..., 2 * c:]
    z = gate_b * x
    out = jnp.zeros_like(z)
    for j in range(taps):
        back = taps - 1 - j          # tap j reads the token `back` before
        shifted = jnp.concatenate(
            [jnp.zeros_like(z[:, :back]), z[:, :t - back]], axis=1)
        out = out + shifted * w[:, j]
    return gate_c * out


def _operands(b, t, c, taps, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, t, 3 * c)),
            jax.random.normal(ks[1], (c, taps)) * 0.5,
            jax.random.normal(ks[2], (b, t, c)))


def _is_fused(*args):
    return "pallas_call" in str(
        jax.make_jaxpr(recurrent.gated_short_conv)(*args))


@SHAPES
def test_forward_is_the_three_slices(jitted, b, t, c, taps, fused):
    bcx, w, _ = _operands(b, t, c, taps)
    assert _is_fused(bcx, w) == fused
    got = jitted(recurrent.gated_short_conv)(bcx, w)
    assert got.shape == (b, t, c) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, _three_slices(bcx, w),
                               rtol=1e-5, atol=2e-6)


@SHAPES
def test_every_cotangent_is_the_three_slices(jitted, b, t, c, taps, fused):
    """The cotangents of ``[B | C | x]`` (each of the three parts) and
    of the taps for a random cotangent of the result, against autodiff
    of the written-out form."""
    bcx, w, ct = _operands(b, t, c, taps, seed=1)
    got = jitted(jax.grad(lambda a, w: jnp.sum(
        recurrent.gated_short_conv(a, w) * ct), (0, 1)))(bcx, w)
    want = jitted(jax.grad(lambda a, w: jnp.sum(
        _three_slices(a, w) * ct), (0, 1)))(bcx, w)
    for part in range(3):
        np.testing.assert_allclose(
            got[0][..., part * c:(part + 1) * c],
            want[0][..., part * c:(part + 1) * c], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5,
                               atol=1e-5 * float(jnp.abs(want[1]).max()))


def test_the_two_gates_do_not_commute_with_the_convolution():
    """``C . conv(B . x)`` is not ``conv(B . C . x)``: the gate ``C``
    meets the token itself only."""
    bcx, w, _ = _operands(1, 64, 24, 3)
    c = w.shape[0]
    moved = jnp.concatenate(
        [bcx[..., :c] * bcx[..., c:2 * c], jnp.ones_like(bcx[..., :c]),
         bcx[..., 2 * c:]], axis=-1)
    a, b = _three_slices(bcx, w), _three_slices(moved, w)
    assert float(jnp.abs(a - b).max()) > 0.1 * float(jnp.abs(a).max())


def test_nothing_reaches_back_past_a_sequences_start():
    """The first ``taps - 1`` results of a sequence do not depend on
    the batch entry before it, in the kernel's walk over blocks."""
    bcx, w, _ = _operands(2, TOKENS, 128, 3)
    other = bcx.at[0].set(7.0)
    np.testing.assert_array_equal(
        recurrent.gated_short_conv(bcx, w)[1],
        recurrent.gated_short_conv(other, w)[1])


def test_refuses_a_projection_that_is_not_three_parts():
    with pytest.raises(ValueError, match="B . C . x"):
        recurrent.gated_short_conv(jnp.zeros((1, 8, 32)), jnp.zeros((16, 3)))


def test_counts_its_sites_and_what_its_backward_keeps():
    reg = MetricsRegistry(enabled=True)
    prev = set_registry(reg)
    try:
        bcx, w, _ = _operands(1, 16, 8, 3)
        jax.make_jaxpr(recurrent.gated_short_conv)(bcx, w)
    finally:
        set_registry(prev)
    assert reg.counter("shortconv/sites").value == 1
    assert reg.counter("shortconv/bytes_kept").value \
        == bcx.nbytes + w.nbytes


def test_fused_under_shard_map_with_a_replicated_weight():
    """As the layer calls it: the projection varies over the mesh's
    data axis, the taps are replicated; their gradient is the sum over
    the axis."""
    devices = np.asarray(jax.devices()[:2])
    mesh = Mesh(devices, ("data",))
    bcx, w, ct = _operands(2, TOKENS, 128, 3, seed=2)

    def loss(a, w, ct):
        return jax.lax.psum(jnp.sum(
            recurrent.gated_short_conv(a, w) * ct), "data")

    grad = jax.jit(jax.shard_map(
        jax.grad(loss, (0, 1)), mesh=mesh,
        in_specs=(P("data"), P(), P("data")), out_specs=(P("data"), P())))
    got = grad(bcx, w, ct)
    want = jax.grad(lambda a, w: jnp.sum(_three_slices(a, w) * ct),
                    (0, 1))(bcx, w)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-4)
