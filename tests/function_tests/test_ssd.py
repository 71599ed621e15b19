"""``ops/ssd.py``: the chunked state-space recurrence against the
token-by-token one in float64, forward and gradient, and what it shares
with ``ops/kda.py`` (``ops/recurrent.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.ops import recurrent, ssd
from chainermn_tpu.utils.metrics import MetricsRegistry, set_registry

B, H, P, G, N = 2, 4, 8, 2, 16


def _inputs(key, t, dtype=jnp.float32):
    """Decays drawn as published: ``exp(a_log)`` uniform in [1, 16] and
    a step whose softplus lies about in 0.05 .. 1.3, so a chunk's
    running sum passes -100 and the state matters across chunks."""
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, t, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, t, H), dtype) - 1)
    a = -jnp.exp(jax.random.uniform(ks[2], (H,), dtype, 0., np.log(16.)))
    b = jax.random.normal(ks[3], (B, t, G, N), dtype) * N ** -.5
    c = jax.random.normal(ks[4], (B, t, G, N), dtype)
    return x, dt, a, b, c


def _loss(fn):
    return lambda *args: jnp.sum(jnp.sin(fn(*args)))


# chunk counts 1 and 2, six chunks in slabs of three (four do not
# divide six) and four slabs of two
@pytest.mark.parametrize("t,chunk,slab,slabs", [
    (16, 16, 4, 1), (32, 16, 4, 1), (96, 16, 4, 2), (128, 16, 2, 4)])
def test_chunked_is_the_recurrence_in_float64(monkeypatch, jitted, t, chunk,
                                              slab, slabs):
    monkeypatch.setattr(ssd, "CHUNK", chunk)
    monkeypatch.setattr(ssd, "SLAB", slab)
    args = _inputs(jax.random.PRNGKey(t), t)
    with jax.enable_x64(True):
        exact = tuple(jnp.asarray(np.asarray(v), jnp.float64) for v in args)
        want = jitted(ssd.ssd_recurrent)(*exact)
        assert want.dtype == jnp.float64
        want_grads = jitted(jax.grad(_loss(ssd.ssd_recurrent),
                                     argnums=(0, 1, 2, 3, 4)))(*exact)
    reg = MetricsRegistry(enabled=True)
    prev = set_registry(reg)
    try:
        got = jitted(ssd.ssd_chunked)(*args)
    finally:
        set_registry(prev)
    assert got.dtype == jnp.float32 and got.shape == (B, t, H, P)
    scale = float(np.abs(want).max())
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5 * scale)
    grads = jitted(jax.grad(_loss(ssd.ssd_chunked),
                            argnums=(0, 1, 2, 3, 4)))(*args)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(
            g, np.asarray(w), atol=5e-5 * float(np.abs(w).max()))
    assert reg.counter("ssm/chunks").value == t // chunk
    assert reg.counter("ssm/state_bytes_kept").value \
        == slabs * B * H * P * N * 4


def test_no_exponent_overflows_where_a_running_sum_is_large():
    """A chunk whose running sum of ``dt a`` passes -3,000: the pairs
    above the diagonal would be ``exp(+3000)`` were they masked after
    the exponential; forward and gradient stay finite."""
    x, dt, a, b, c = _inputs(jax.random.PRNGKey(1), 128)
    dt, a = dt + 2.0, a * 0 - 16.0
    assert float(jnp.sum(dt[0, :, 0]) * a[0]) < -3000
    out = ssd.ssd_chunked(x, dt, a, b, c)
    grads = jax.grad(_loss(ssd.ssd_chunked), argnums=(0, 1, 2, 3, 4))(
        x, dt, a, b, c)
    assert all(bool(jnp.isfinite(v).all()) for v in (out, *grads))
    np.testing.assert_allclose(out, ssd.ssd_recurrent(x, dt, a, b, c),
                               atol=1e-5)


def test_a_sequence_that_is_not_whole_chunks_is_refused(monkeypatch):
    monkeypatch.setattr(ssd, "CHUNK", 16)
    args = _inputs(jax.random.PRNGKey(2), 24)
    with pytest.raises(ValueError, match="whole chunks"):
        ssd.ssd_chunked(*args)


def test_importing_the_op_brings_no_kernel():
    """No Pallas call in the op's jaxpr: an accepted cell's step gains
    no kernel to trace or lower from the import."""
    text = str(jax.make_jaxpr(ssd.ssd_chunked)(
        *_inputs(jax.random.PRNGKey(3), 128)))
    assert "pallas_call" not in text and "custom_call" not in text


@pytest.mark.parametrize("bias", [False, True])
def test_causal_conv_silu_is_the_sum_over_taps(bias):
    """The one convolution of the KDA and the Mamba-2 layers: the last
    tap on the token itself, nothing from before the sequence's start,
    whatever the channels' shape."""
    y = jax.random.normal(jax.random.PRNGKey(4), (2, 9, 3, 5))
    w = jax.random.normal(jax.random.PRNGKey(5), (3, 5, 4))
    b = jax.random.normal(jax.random.PRNGKey(6), (3, 5)) if bias else None
    got = recurrent.causal_conv_silu(y, w, b)
    want = np.zeros(y.shape, np.float32)
    for t in range(9):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += np.asarray(y[:, t - 3 + j] * w[..., j])
    want = jax.nn.silu(want + (0 if b is None else np.asarray(b)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n,most,want", [
    (1, 4, 1), (4, 4, 4), (6, 4, 3), (7, 4, 1), (64, 16, 16), (3, 16, 3)])
def test_slab_size_is_the_largest_divisor(n, most, want):
    assert recurrent.slab_size(n, most) == want


def test_kda_runs_the_shared_slab_loop(monkeypatch):
    """``kda_chunked`` scans its slabs through ``ops/recurrent.py``:
    one loop, not a copy."""
    from chainermn_tpu.ops import kda

    calls = []
    whole = recurrent.scan_slabs
    monkeypatch.setattr(kda, "scan_slabs", lambda *a: calls.append(
        jax.tree.leaves(a[2])[0].shape[0]) or whole(*a))
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    q, k, v = (jax.random.normal(ks[i], (1, 128, 2, 8)) for i in range(3))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (1, 128, 2, 8)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, 128, 2)))
    kda.kda_chunked(q, k, v, g, beta)
    assert calls == [1]     # two chunks of 64: one slab
