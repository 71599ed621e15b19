"""Pre-norm decoder-only transformer (OPT, Zhang et al.,
arXiv:2205.01068: learned absolute positions, ReLU two-matrix MLP,
multi-head attention, tied embedding), its loss and AdamW steps in
plain float32 ``jax.numpy``: the yardstick the OPT cells' first steps
are held to.

Departures from OPT, all because this repo's ``TransformerConfig``
cannot describe them and a benchmark PR may not touch the program (the
reference has to describe the function the program computes):
- RMSNorm (eps 1e-6, scale only) where OPT has LayerNorm with bias;
- no bias on any linear layer;
- positions index the table from 0, without OPT's offset of 2.

The gradient of the batch's mean loss is the mean of the sequences'
gradients, so it is taken one sequence at a time with each block under
``jax.checkpoint`` (blocks of rows, layer by layer): the float32
attention matrices of a whole batch do not fit beside the weights.
"""

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .common import delta_norms, leaf_norms, rounder, to_floats

RMS_EPS = 1e-6


def init(key, cfg):
    """Seeded weights in the layout the program's ``init_transformer``
    uses, less its leading pipeline axis on the blocks (the driver
    checks shape for shape): N(0, 0.02) tables, N(0, 1/fan_in)
    matrices, unit norm scales."""
    d, h, dh, f, n = (cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["head_dim"], cfg["ffn_dim"],
                      cfg["num_hidden_layers"])
    k = jax.random.split(key, 6)

    def dense(key, shape, fan_in):
        return jax.random.normal(key, (n,) + shape, jnp.float32) \
            * fan_in ** -.5

    return {
        "embed": jax.random.normal(
            k[0], (cfg["vocab_size"], d), jnp.float32) * 0.02,
        "pos": jax.random.normal(
            k[1], (cfg["max_position_embeddings"], d), jnp.float32) * 0.02,
        "ln_f": jnp.ones((d,), jnp.float32),
        "blocks": {
            "ln1": jnp.ones((n, d), jnp.float32),
            "ln2": jnp.ones((n, d), jnp.float32),
            "wqkv": dense(k[2], (d, 3, h, dh), d),
            "wo": dense(k[3], (h, dh, d), h * dh),
            "w1": dense(k[4], (d, f), d),
            "w2": dense(k[5], (f, d), f),
        },
    }


def _rms_norm(x, scale):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                         + RMS_EPS) * scale


def _block(rnd, h, blk):
    t, d = h.shape
    n_heads, dh = blk["wo"].shape[0], blk["wo"].shape[1]
    x = rnd(_rms_norm(h, blk["ln1"]))
    qkv = (x @ rnd(blk["wqkv"].reshape(d, -1))).reshape(t, 3, n_heads, dh)
    q, k, v = rnd(qkv[:, 0]), rnd(qkv[:, 1]), rnd(qkv[:, 2])
    s = jnp.einsum("qhd,khd->hqk", q, k) * dh ** -.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", rnd(jax.nn.softmax(s, axis=-1)), v)
    h = h + rnd(o.reshape(t, -1)) @ rnd(blk["wo"].reshape(-1, d))
    x = rnd(_rms_norm(h, blk["ln2"]))
    y = jax.nn.relu(x @ rnd(blk["w1"]))
    return h + rnd(y) @ rnd(blk["w2"])


def sequence_loss(precision, params, tokens, targets):
    """Mean next-token cross-entropy of ONE sequence."""
    rnd = rounder(precision)
    t = tokens.shape[0]
    h = params["embed"][tokens] + params["pos"][:t]
    block = jax.checkpoint(lambda h, blk: (_block(rnd, h, blk), None))
    h, _ = lax.scan(block, h, params["blocks"])
    logits = rnd(_rms_norm(h, params["ln_f"])) @ rnd(params["embed"]).T
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def follow(cfg, make_params, batches, precision="float32"):
    """Drive AdamW from ``make_params()`` through ``batches``
    (``(tokens, targets)`` of shape ``(B, T)``, one step each; the start
    is made again at the end rather than kept beside the optimizer's
    state) and return what the check compares:
    every step's loss, the norm of each leaf's first gradient, and the
    norm of each leaf's change after the last step."""
    if cfg["optimizer"]["name"] != "adamw":
        raise SystemExit("this plain reference writes out AdamW's rule; "
                         "another optimizer is another reference file")
    opt = cfg["optimizer"]["args"]
    lr, b1, b2, eps, wd = (opt["learning_rate"], opt["b1"], opt["b2"],
                           opt["eps"], opt["weight_decay"])

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, mu, nu, count, tokens, targets):
        def one(acc, row):
            loss, grads = jax.value_and_grad(
                lambda p: sequence_loss(precision, p, *row))(params)
            return jax.tree.map(jnp.add, acc, (loss, grads)), None

        zero = (jnp.zeros(()), jax.tree.map(jnp.zeros_like, params))
        (loss, grads), _ = lax.scan(one, zero, (tokens, targets))
        n = tokens.shape[0]
        loss, grads = loss / n, jax.tree.map(lambda g: g / n, grads)
        count = count + 1
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)

        def update(p, m, v):
            m_hat = m / (1 - b1 ** count)
            v_hat = v / (1 - b2 ** count)
            return p - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + wd * p)

        return (jax.tree.map(update, params, mu, nu), mu, nu, count, loss,
                leaf_norms(grads))

    with jax.default_matmul_precision("highest"):
        params = make_params()
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        count = jnp.zeros((), jnp.float32)
        losses, grad_norms = [], None
        for tokens, targets in batches:
            params, mu, nu, count, loss, norms = step(
                params, mu, nu, count, tokens, targets)
            losses.append(float(loss))
            grad_norms = grad_norms or to_floats(norms)
        del mu, nu
        delta = delta_norms(params, make_params())
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta}
