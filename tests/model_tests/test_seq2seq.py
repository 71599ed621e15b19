"""Seq2seq: mask semantics (padding is invisible), convergence, and the
variable-length-gradient DP equivalence the reference's seq2seq example
existed to demonstrate."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from chainermn_tpu.models.seq2seq import (
    EOS,
    PAD,
    Seq2seqConfig,
    init_seq2seq,
    seq2seq_loss,
    seq2seq_translate,
)
from chainermn_tpu.parallel import MeshConfig


CFG = Seq2seqConfig(
    src_vocab=20, tgt_vocab=20, d_embed=16, d_hidden=16, n_layers=2)


def ragged_batch(n, max_len=8, seed=0):
    rng = np.random.RandomState(seed)
    src = np.full((n, max_len), PAD, np.int32)
    tgt = np.full((n, max_len + 1), PAD, np.int32)
    for i in range(n):
        ln = rng.randint(2, max_len + 1)
        s = rng.randint(3, 20, size=ln)
        src[i, :ln] = s
        tgt[i, :ln] = s[::-1]
        tgt[i, ln] = EOS
    return jnp.asarray(src), jnp.asarray(tgt)


def test_loss_finite_and_padding_invariant():
    params = init_seq2seq(jax.random.PRNGKey(0), CFG)
    src, tgt = ragged_batch(8)
    # one compiled program a shape: eagerly the two-layer recurrence's
    # every op is a program of its own, at both widths
    loss_fn = jax.jit(lambda p, s, t: seq2seq_loss(CFG, p, s, t))
    loss = loss_fn(params, src, tgt)
    assert np.isfinite(float(loss))

    # extra all-PAD columns must not change the loss (mask semantics)
    pad_s = jnp.full((8, 4), PAD, jnp.int32)
    pad_t = jnp.full((8, 4), PAD, jnp.int32)
    loss2 = loss_fn(
        params,
        jnp.concatenate([src, pad_s], axis=1),
        jnp.concatenate([tgt, pad_t], axis=1))
    np.testing.assert_allclose(float(loss), float(loss2), rtol=1e-5)


def test_reverse_task_converges_and_translates():
    import optax

    params = init_seq2seq(jax.random.PRNGKey(0), CFG)
    opt = optax.adam(5e-3)
    opt_state = opt.init(params)
    src, tgt = ragged_batch(32, seed=1)

    @jax.jit
    def step(p, s):
        loss, g = jax.value_and_grad(
            lambda q: seq2seq_loss(CFG, q, src, tgt))(p)
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s, loss

    losses = []
    for _ in range(150):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < 0.3 * losses[0], (losses[0], losses[-1])

    out = np.asarray(seq2seq_translate(CFG, params, src, max_len=9))
    ref = np.asarray(tgt)
    token_acc = (out == ref)[ref != PAD].mean()
    assert token_acc > 0.5, token_acc
    # PAD-after-EOS contract
    for row in out:
        hit = np.where(row == EOS)[0]
        if hit.size:
            assert (row[hit[0] + 1:] == PAD).all()


def test_dp_grads_match_single_device_on_ragged_batch():
    """The reference's 'variable-length allreduce': data-sharded ragged
    batches produce the same *weighted* global gradient as one device.
    Per-shard losses are means over unequal token counts, so the global
    loss is the token-weighted combination — exactly what a per-token
    global mean on one device computes."""
    params = init_seq2seq(jax.random.PRNGKey(2), CFG)
    src, tgt = ragged_batch(16, seed=3)
    mc = MeshConfig(data=8)

    def local_tokens(s, t):
        return (t != PAD).sum(dtype=jnp.float32)

    def sharded(p, s, t):
        ntok = local_tokens(s, t)
        w = ntok / jax.lax.psum(ntok, "data")
        loss = seq2seq_loss(CFG, p, s, t)
        g = jax.grad(
            lambda q: jax.lax.psum(seq2seq_loss(CFG, q, s, t) * w, "data")
        )(p)
        return jax.lax.psum(loss * w, "data"), g

    f = jax.jit(jax.shard_map(
        sharded, mesh=mc.mesh,
        in_specs=(P(), P("data"), P("data")),
        out_specs=(P(), P())))
    loss_dp, g_dp = f(params, src, tgt)

    loss_1, g_1 = jax.jit(jax.value_and_grad(
        lambda q: seq2seq_loss(CFG, q, src, tgt)))(params)
    np.testing.assert_allclose(float(loss_dp), float(loss_1), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-6),
        g_dp, g_1)
