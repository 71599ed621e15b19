"""``ops/gdn.py``: the chunked scalar-decay delta rule against the
token-by-token one, values and gradients, and against ``ops/kda.py``'s
chunked op fed the same decay on every key channel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.ops import gdn, kda
from chainermn_tpu.utils.metrics import MetricsRegistry, set_registry

B, HK, HV, DK, DV = 2, 2, 4, 16, 8


def _inputs(key, t):
    """Decays drawn as published: ``exp(a_log)`` uniform in [1, 16] and
    a step whose softplus lies about in 0.3 .. 3, so a chunk's running
    sum passes -50 on the fastest heads and the state matters across
    chunks; q and k unit vectors a key head."""
    ks = jax.random.split(key, 6)
    unit = lambda y: y / jnp.linalg.norm(y, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (B, t, HK, DK))) * DK ** -.5
    k = unit(jax.random.normal(ks[1], (B, t, HK, DK)))
    v = jax.random.normal(ks[2], (B, t, HV, DV))
    a = jnp.exp(jax.random.uniform(ks[3], (HV,), jnp.float32, 0.,
                                   np.log(16.)))
    g = -a * jax.nn.softplus(jax.random.normal(ks[4], (B, t, HV)) + 1)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (B, t, HV)))
    return q, k, v, g, beta


def _grads(jitted, fn, args):
    return jitted(jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                           argnums=(0, 1, 2, 3, 4)))(*args)


# one short chunk; two chunks in one slab; six chunks in slabs of three
# (four do not divide six); four slabs of two
@pytest.mark.parametrize("t,chunk,slab,slabs", [
    (8, 16, 4, 1), (32, 16, 4, 1), (96, 16, 4, 2), (128, 16, 2, 4)])
def test_chunked_is_the_recurrence(monkeypatch, jitted, t, chunk, slab,
                                   slabs):
    monkeypatch.setattr(gdn, "CHUNK", chunk)
    monkeypatch.setattr(gdn, "SLAB", slab)
    args = _inputs(jax.random.PRNGKey(t), t)
    if t > chunk:
        sums = np.asarray(args[3]).reshape(B, t // chunk, chunk, HV).sum(2)
        assert sums.min() < -50
    want = jitted(gdn.gdn_recurrent)(*args)
    reg = MetricsRegistry(enabled=True)
    prev = set_registry(reg)
    try:
        got = jitted(gdn.gdn_chunked)(*args)
    finally:
        set_registry(prev)
    assert got.dtype == jnp.float32 and got.shape == (B, t, HV, DV)
    np.testing.assert_allclose(
        got, want, atol=2e-5 * float(jnp.abs(want).max()))
    for g, w in zip(_grads(jitted, gdn.gdn_chunked, args),
                    _grads(jitted, gdn.gdn_recurrent, args)):
        np.testing.assert_allclose(
            g, w, atol=5e-5 * float(jnp.abs(w).max()))
    n_chunks = -(-t // chunk)
    assert reg.counter("gdn/chunks").value == n_chunks
    assert reg.counter("gdn/systems_inverted").value == B * HV * n_chunks
    assert reg.counter("gdn/state_bytes_kept").value \
        == slabs * B * HV * DK * DV * 4


def test_chunked_is_kda_under_a_channel_constant_decay(jitted):
    """The yardstick the issue names: ``kda_chunked`` fed the scalar
    decay on all key channels and each key head copied out to its value
    heads computes the same thing (and pays for ``d_k`` exponentials a
    pair that are all alike); values and gradients, two chunks of 64."""
    t = 2 * gdn.CHUNK
    assert gdn.CHUNK == kda.CHUNK
    q, k, v, g, beta = _inputs(jax.random.PRNGKey(3), t)
    rep = HV // HK

    def through_kda(q, k, v, g, beta):
        return kda.kda_chunked(
            jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2), v,
            jnp.broadcast_to(g[..., None], (B, t, HV, DK)), beta)

    want = jitted(through_kda)(q, k, v, g, beta)
    got = jitted(gdn.gdn_chunked)(q, k, v, g, beta)
    np.testing.assert_allclose(
        got, want, atol=2e-5 * float(jnp.abs(want).max()))
    for a, w in zip(_grads(jitted, gdn.gdn_chunked, (q, k, v, g, beta)),
                    _grads(jitted, through_kda, (q, k, v, g, beta))):
        np.testing.assert_allclose(
            a, w, atol=5e-5 * float(jnp.abs(w).max()))


def test_value_head_j_reads_key_head_j_over_rep():
    """Changing key head 1 moves value heads 2 and 3 and no other."""
    q, k, v, g, beta = _inputs(jax.random.PRNGKey(4), 32)
    base = gdn.gdn_chunked(q, k, v, g, beta)
    moved = gdn.gdn_chunked(q, k.at[:, :, 1].multiply(-0.5), v, g, beta)
    differs = np.abs(np.asarray(moved - base)).max(axis=(0, 1, 3)) > 1e-6
    assert differs.tolist() == [False, False, True, True]


def test_refuses_what_it_cannot_chunk():
    q, k, v, g, beta = _inputs(jax.random.PRNGKey(5), 96)
    with pytest.raises(ValueError, match="not whole chunks of 64"):
        gdn.gdn_chunked(q, k, v, g, beta)
    with pytest.raises(ValueError, match="whole groups of 2 key heads"):
        gdn.gdn_chunked(q[:, :64], k[:, :64], v[:, :64, :3],
                        g[:, :64, :3], beta[:, :64, :3])
