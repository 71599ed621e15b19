"""``ops/recurrent.py``'s convolution in its fused form: the Pallas
kernels (interpreted here) against the plain sum over taps, forward and
every gradient, at lane-aligned shapes that stand for the three layers
that call it; and which shapes take which form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from chainermn_tpu.ops import recurrent

T = 2 * recurrent.TOKENS    # the halo crosses a block's edge
B = 2                       # and a sequence's end meets the next's start

# (channels' shape, split, bias): the KDA layer's five axes without
# bias, q, k, v by head; the Mamba-2 layer's flat x | B | C with bias
# (a part one lane tile wide beside wider ones, none by head); the
# Gated DeltaNet's flat q | k | v with fewer key heads than value
# heads; a part by head beside a flat one and heads no lane tile wide
CALLERS = pytest.mark.parametrize("channels,split,bias", [
    ((3, 2, 128), ((2, 128),) * 3, False),
    ((768,), (512, 128, 128), True),
    ((512,), ((1, 128), (1, 128), (2, 128)), False),
    ((512,), ((2, 128), 128, (2, 64)), True),
    ((256,), None, True),
], ids=["kda-five-axes", "mamba2-flat-bias", "gdn-flat", "mixed-parts",
        "unsplit-bias"])


def _operands(channels, bias, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    y = jax.random.normal(ks[0], (B, T, *channels))
    w = jax.random.normal(ks[1], (*channels, 4)) * 0.5
    b = jax.random.normal(ks[2], channels) if bias else None
    proj = jax.random.normal(ks[3], (B, T, int(np.prod(channels))))
    return y, w, b, proj


def _flat(out):
    parts = out if isinstance(out, tuple) else (out,)
    return jnp.concatenate([p.reshape(B, T, -1) for p in parts], axis=-1)


def _plain(y, w, b):
    return recurrent._plain(y.reshape(B, T, -1), w.reshape(-1, w.shape[-1]),
                            None if b is None else b.reshape(-1))


def _is_fused(fn, *args):
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


@CALLERS
def test_fused_is_the_plain_sum_over_taps(channels, split, bias):
    y, w, b, _ = _operands(channels, bias)
    fused = lambda y, w, b: recurrent.causal_conv_silu(y, w, b, split)
    assert _is_fused(fused, y, w, b)
    got = fused(y, w, b)
    if split:
        assert [p.shape for p in got] == [
            (B, T, *s) if isinstance(s, tuple) else (B, T, s) for s in split]
    else:
        assert got.shape == y.shape
    np.testing.assert_allclose(_flat(got), _plain(y, w, b),
                               rtol=1e-5, atol=2e-6)


@CALLERS
def test_fused_gradients_are_the_plain_forms(channels, split, bias):
    """``jax.grad`` of a random projection of the output with respect
    to ``y``, the taps and the bias, against the same through the plain
    form and autodiff."""
    y, w, b, proj = _operands(channels, bias, seed=1)
    args = (0, 1, 2) if bias else (0, 1)
    got = jax.grad(lambda y, w, b: jnp.sum(_flat(
        recurrent.causal_conv_silu(y, w, b, split)) * proj), args)(y, w, b)
    want = jax.grad(lambda y, w, b: jnp.sum(_plain(y, w, b) * proj),
                    args)(y, w, b)
    for g, v in zip(got, want):
        assert g.shape == v.shape
        np.testing.assert_allclose(
            g, v, rtol=1e-5, atol=2e-6 * float(jnp.abs(v).max()))


def test_nothing_crosses_a_sequences_end_or_a_parts_edge():
    """A change to one sequence's last tokens moves nothing of the next
    sequence's output or gradient, and a token block's first outputs
    see the block before it."""
    y, w, b, proj = _operands((256,), True, seed=2)
    conv = jax.jit(
        lambda y: recurrent.causal_conv_silu(y, w, b, (128, 128))[1])
    other = y.at[0, -8:].add(3.0)
    was = conv(y)
    np.testing.assert_array_equal(was[1], conv(other)[1])
    grad = jax.jit(jax.grad(lambda y: jnp.sum(conv(y) * proj[..., 128:])))
    np.testing.assert_array_equal(grad(y)[1], grad(other)[1])
    edge = recurrent.TOKENS
    moved = conv(y.at[0, edge - 1].add(3.0))
    assert float(jnp.abs(moved - was)[0, edge:edge + 3].min()) > 0
    np.testing.assert_array_equal(moved[0, edge + 3:], was[0, edge + 3:])


def test_fused_under_shard_map_sums_a_replicated_taps_gradient():
    """Inside ``shard_map`` with the batch over a mesh axis and the taps
    and the bias replicated (how every train step calls it): the
    kernels trace, and the parameters' gradients are summed over the
    axis as autodiff's are."""
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("data",))
    y, w, b, _ = _operands((256,), True, seed=3)

    def loss(conv):
        def body(y, w, b):
            q, k = conv(y, w, b)
            return jax.lax.pmean(jnp.sum(jnp.sin(q)) + jnp.sum(k * k), "data")
        return jax.shard_map(body, mesh=mesh, in_specs=(P("data"), P(), P()),
                             out_specs=P())

    def plain(y, w, b):
        out = recurrent._plain(y, w, b)
        return out[..., :128], out[..., 128:]

    fused = lambda y, w, b: recurrent.causal_conv_silu(y, w, b, (128, 128))
    got = jax.jit(jax.grad(loss(fused), (0, 1, 2)))(y, w, b)
    want = jax.jit(jax.grad(loss(plain), (0, 1, 2)))(y, w, b)
    for g, v in zip(got, want):
        np.testing.assert_allclose(
            g, v, rtol=1e-5, atol=2e-6 * float(jnp.abs(v).max()))


@pytest.mark.parametrize("shape,split,fused", [
    ((1, T, 256), None, True),
    ((1, T, 256), (128, 128), True),
    ((1, T, 3, 5), None, False),            # channels no whole lane tile
    ((1, T, 320), (128, (3, 64)), False),   # a part that is none
    ((1, T - 8, 256), None, False),         # tokens no whole block
    ((1, 9, 256), None, False),
], ids=["aligned", "aligned-split", "15-channels", "192-part",
        "ragged-tokens", "9-tokens"])
def test_the_shape_alone_chooses_the_form(shape, split, fused):
    y = jnp.zeros(shape)
    w = jnp.zeros((*shape[2:], 4))
    conv = lambda y, w: recurrent.causal_conv_silu(y, w, None, split)
    assert _is_fused(conv, y, w) is fused
    text = str(jax.make_jaxpr(jax.grad(
        lambda y, w: sum(jnp.sum(p) for p in jax.tree.leaves(conv(y, w)))
    ))(y, w))
    # forward and backward kernel, or autodiff's pad of the whole tensor
    assert text.count("pallas_call") == 2 * fused
    assert (" pad[" in text or "pad(" in text) is not fused


def test_a_split_that_misses_the_channels_is_refused():
    with pytest.raises(ValueError, match="does not sum"):
        recurrent.causal_conv_silu(
            jnp.zeros((1, 8, 256)), jnp.zeros((256, 4)), split=(128, 64))


def test_the_plain_form_splits_too():
    y, w, b, _ = _operands((3, 5), True, seed=4)
    whole = recurrent.causal_conv_silu(y[:, :9], w, b)
    parts = recurrent.causal_conv_silu(y[:, :9], w, b, split=(5, (2, 5)))
    assert [p.shape for p in parts] == [(B, 9, 5), (B, 9, 2, 5)]
    np.testing.assert_array_equal(
        jnp.concatenate([p.reshape(B, 9, -1) for p in parts], -1),
        whole.reshape(B, 9, 15))
