"""LFM2-MoE (LiquidAI/LFM2-24B-A2B, ``model_type: lfm2_moe``; the
model's public config and, where it is silent, its public modelling
file as the builder remembers it): a pre-norm decoder whose token mixer
is a doubly gated short convolution (``conv``) in three layers of four
and softmax attention over grouped key-value heads with q/k norms
(``full_attention``) in the fourth; the first ``num_dense_layers`` have
a dense SwiGLU, the others sparse SwiGLU experts under a sigmoid router
with a selection bias and no shared expert; the head is the embedding.
Its loss and AdamW steps in plain float32 ``jax.numpy``: the yardstick
the cell's first steps are held to.  No kernel, no cache of keys or of
the convolution's last tokens, and nothing of ``chainermn_tpu`` is
imported.

The layers.  RMSNorm (``norm_eps``, a plain learned scale) before mixer
and before feed-forward, residual adds, a last RMSNorm before the head.
With ``u_t`` a layer's normed input:

**conv** (``conv_L_cache`` taps, ``conv_bias`` false)::

    [B C x] = u W_in             (3 x hidden, no bias, in that order)
    z = B . x
    c_t = sum_j w_j . z_(t - taps + 1 + j)    a weight a channel a tap, causal,
                                               the last tap on the token itself
    out = W_out (C . c)

no activation, no norm, no positions, no state past ``taps - 1`` tokens
(``_short_conv`` is the three middle lines, as three shifted slices).

**full_attention**, ``H = num_attention_heads`` over
``num_key_value_heads`` of ``d = head_dim``::

    q = u W_q,  k, v = u W_k, u W_v          no bias
    q, k <- RMSNorm over each head's d (one plain scale each for all heads)
    THEN the whole head rotated (rotate-half, theta rope_theta)
    causal softmax at d^-1/2, query head j reads key-value head j // (H / kv heads)
    out = W_o o

scored by blocks of ``Q_BLOCK`` queries.

**Feed-forward.**  Dense layers: ``W_2 (SiLU(W_1 v) . W_3 v)`` at
``intermediate_size``.  Others: ``s = sigmoid(v W_r)`` over the router's
experts; the ``num_experts_per_tok`` experts with the largest ``s + b``
win (``b`` the expert bias, a leaf no gradient reaches and no update
moves: ``stop_gradient`` here, skipped in ``adamw_step``); their gates
are their ``s`` (without ``b``) over the chosen ones' sum
(``norm_topk_prob``, no epsilon) times ``routed_scaling_factor``;
experts ``W_2 (SiLU(W_1 v) . W_3 v)`` at ``moe_intermediate_size``.

The chip's share (the experts ``experts_first .. + num_experts`` of
each layer, the first ``vocabulary`` rows, the layers ``layers_first ..
+ num_hidden_layers`` of ``layer_types``), the layout of the parameters
(the program's, less its leading pipeline axis) and the layer-by-layer,
sequence-by-sequence order of the batch are ``reference/laguna.py``'s,
for its reasons.

Departures from the published model, each under ``assumed`` in the
configuration's file: the tied head, the order ``[B C x]``, ``b`` seeded
and held fixed, the balancing loss, the weights' scales and AdamW.
"""

import json
import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec

from .common import delta_norms, leaf_norms, rounder, to_floats
from .laguna import _rms_norm, _rope, _swiglu

Q_BLOCK = 256       # queries scored at a time (at most: a divisor of T)
PARTS = 4           # of the sequence, each with its own prefix of keys
FIXED = "router_bias"   # the leaf no gradient reaches and no update moves
EMBED_SEED = 0.02   # the tied embedding's seeded spread


def layers(cfg):
    """``[(mixer, mlp)]`` of the layers run: ``layer_types`` from
    ``layers_first`` on, dense where the model's own layer index is
    under ``num_dense_layers``."""
    first, n = cfg["layers_first"], cfg["num_hidden_layers"]
    mixers = cfg["layer_types"][first:first + n]
    if len(mixers) != n or set(mixers) - {"conv", "full_attention"}:
        raise SystemExit(f"layers {first}..{first + n} of layer_types are "
                         f"{mixers}")
    return [(m, "dense" if first + i < cfg["num_dense_layers"] else "sparse")
            for i, m in enumerate(mixers)]


def layout(cfg):
    """``(leading, period)``: how many layers lead (the dense ones at
    the start) and the shortest pattern of mixers the others repeat."""
    every = layers(cfg)
    leading = sum(mlp == "dense" for _, mlp in every)
    if any(mlp == "dense" for _, mlp in every[leading:]) \
            or leading == len(every):
        raise SystemExit("dense layers lead and sparse ones follow")
    rest = [mixer for mixer, _ in every[leading:]]
    for n in range(1, len(rest) + 1):
        if len(rest) % n == 0 and all(
                m == rest[i % n] for i, m in enumerate(rest)):
            return leading, rest[:n]


def init(key, cfg):
    """Seeded weights in the program's layout: N(0, EMBED_SEED^2)
    embedding rows (the head is tied to them), N(0, 1/fan_in) matrices
    (the convolution's fan_in is its taps) with the projections that
    write to the residual stream (``wo``, ``w2``) scaled by 1/sqrt(2 x
    the published depth), unit norm scales; the selection bias N(0,
    0.01^2)."""
    d, e, g, v = (cfg["hidden_size"], cfg["router_experts"],
                  cfg["num_experts"], cfg["vocabulary"])
    h, kv, dh, taps = (cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"],
                       cfg["conv_L_cache"])
    depth = cfg.get("published", cfg)["num_hidden_layers"]
    residual = (2 * depth) ** -.5

    def block(key, mixer, mlp):
        k = iter(jax.random.split(key, 16))

        def dense(shape, fan_in, scale=1.0):
            return jax.random.normal(next(k), shape, jnp.float32) \
                * fan_in ** -.5 * scale

        out = {"ln1": jnp.ones((d,), jnp.float32),
               "ln2": jnp.ones((d,), jnp.float32)}
        if mixer == "conv":
            # the out-projection is hidden x hidden; the program lays it
            # out by the attention layers' head count
            out.update(w_in=dense((d, 3 * d), d), conv=dense((d, taps), taps),
                       wo=dense((h, d // h, d), d, residual))
        else:
            out.update(wq=dense((d, h, dh), d), wkv=dense((d, 2, kv, dh), d),
                       q_norm=jnp.ones((dh,), jnp.float32),
                       k_norm=jnp.ones((dh,), jnp.float32),
                       wo=dense((h, dh, d), h * dh, residual))
        if mlp == "dense":
            f = cfg["intermediate_size"]
            return dict(out, w1=dense((d, f), d), w3=dense((d, f), d),
                        w2=dense((f, d), f, residual))
        f = cfg["moe_intermediate_size"]
        return dict(out, router=dense((d, e), d),
                    router_bias=0.01 * jax.random.normal(
                        next(k), (e,), jnp.float32),
                    w1=dense((g, d, f), d), w3=dense((g, d, f), d),
                    w2=dense((g, f, d), f, residual))

    every = layers(cfg)
    leading, period = layout(cfg)
    keys = jax.random.split(key, len(every) + 1)
    blocks = [block(keys[i], *layer) for i, layer in enumerate(every)]
    scanned = blocks[leading:]
    return {
        "embed": EMBED_SEED * jax.random.normal(
            keys[-1], (v, d), jnp.float32),
        "ln_f": jnp.ones((d,), jnp.float32),
        "leading": tuple(blocks[:leading]),
        # one stack over the periods for each position of the pattern
        "blocks": tuple(
            jax.tree.map(lambda *a: jnp.stack(a), *scanned[j::len(period)])
            for j in range(len(period))),
    }


def layer_blocks(cfg, params):
    """Each layer's own block, in layer order."""
    leading, period = layout(cfg)
    n = len(period)
    return list(params["leading"]) + [
        jax.tree.map(lambda a: a[i // n], params["blocks"][i % n])
        for i in range(cfg["num_hidden_layers"] - leading)]


def _short_conv(rnd, b, c, x, w):
    """``C . conv(B . x)`` for ``b``, ``c``, ``x`` ``(T, channels)`` and
    ``w`` ``(channels, taps)``: the convolution as ``taps`` shifted
    slices of a copy padded at the sequence's start."""
    t, taps = x.shape[0], w.shape[-1]
    padded = jnp.pad(rnd(b * x), ((taps - 1, 0), (0, 0)))
    w = rnd(w)
    return c * sum(padded[j:j + t] * w[:, j] for j in range(taps))


def _conv(cfg, rnd, h, blk):
    d = h.shape[-1]
    if cfg["conv_bias"]:
        raise SystemExit("the convolution has no bias: conv_bias false")
    u = rnd(_rms_norm(h, blk["ln1"], cfg["norm_eps"]))
    b, c, x = jnp.split(u @ rnd(blk["w_in"]), 3, axis=-1)
    y = _short_conv(rnd, b, c, x, blk["conv"])
    return h + rnd(y) @ rnd(blk["wo"].reshape(-1, d))


def inv_freq(cfg):
    """A frequency for each pair of a head's dimensions: the whole head
    is rotated."""
    rope = cfg["rope_parameters"]
    if rope["rope_type"] != "default":
        raise SystemExit("plain rotary: rope_type default")
    dh = cfg["head_dim"]
    return rope["rope_theta"] ** (-np.arange(0, dh, 2, dtype=np.float64) / dh)


def _attention(cfg, rnd, h, blk):
    t, d = h.shape
    eps = cfg["norm_eps"]
    n_kv, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    n_heads = blk["wq"].shape[1]
    freqs = inv_freq(cfg)
    u = rnd(_rms_norm(h, blk["ln1"], eps))
    q = (u @ rnd(blk["wq"].reshape(d, -1))).reshape(t, n_heads, dh)
    kv = (u @ rnd(blk["wkv"].reshape(d, -1))).reshape(t, 2, n_kv, dh)
    # the norm over each head FIRST, the rotation after
    q = rnd(_rope(_rms_norm(q, blk["q_norm"], eps), freqs, 1.0))
    k = rnd(_rope(_rms_norm(kv[:, 0], blk["k_norm"], eps), freqs, 1.0))
    v = rnd(kv[:, 1])
    # query head j reads key-value head j // (heads / kv heads)
    block = math.gcd(Q_BLOCK, t)
    qb = q.reshape(t // block, block, n_kv, n_heads // n_kv, dh)

    @jax.checkpoint
    def rows(qi, start, ki, vi):
        """A block of queries from position ``start`` against the keys
        ``ki`` from position 0."""
        s = jnp.einsum("qgrd,kgd->grqk", qi, ki) * dh ** -.5
        allow = (start + jnp.arange(block))[:, None] \
            >= jnp.arange(ki.shape[0])[None, :]
        p = rnd(jax.nn.softmax(jnp.where(allow, s, -jnp.inf), axis=-1))
        return jnp.einsum("grqk,kgd->qgrd", p, vi)

    # a block meets the keys up to the end of its part of the sequence
    starts = jnp.arange(0, t, block)
    n = len(qb)
    parts = PARTS if n % PARTS == 0 else 1
    per = n // parts
    o = jnp.concatenate([
        lax.map(lambda args, end=(i + 1) * per * block: rows(
            *args, k[:end], v[:end]),
            (qb[i * per:(i + 1) * per], starts[i * per:(i + 1) * per]))
        for i in range(parts)])
    return h + rnd(o.reshape(t, -1)) @ rnd(blk["wo"].reshape(-1, d))


_MIXERS = {"conv": _conv, "full_attention": _attention}


def route(cfg, x, router, bias):
    """``(s, top_i, gates)`` of the sigmoid router, in float32 (the
    lower-precision control does not round it either): every expert's
    own score; the k experts with the largest ``s + bias``; the winners'
    ``s`` over their sum times ``routed_scaling_factor``."""
    if not (cfg["norm_topk_prob"] and cfg["use_expert_bias"]):
        raise SystemExit("the chosen gates are renormalised and the "
                         "choice is biased")
    s = jax.nn.sigmoid(x @ router)
    _, top_i = lax.top_k(s + lax.stop_gradient(bias),
                         cfg["num_experts_per_tok"])
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    return s, top_i, cfg["routed_scaling_factor"] * top_s / jnp.sum(
        top_s, axis=-1, keepdims=True)


def _experts(cfg, rnd, h, blk):
    """``(h + y, f, P)``: the held experts' part of the layer, the
    share of tokens whose first choice each expert is, and the mean of
    each expert's score over the scores' sum."""
    e, first = cfg["router_experts"], cfg["experts_first"]
    x = _rms_norm(h, blk["ln2"], cfg["norm_eps"])
    s, top_i, gates = route(cfg, x, blk["router"], blk["router_bias"])
    # (T, E): the gate of each chosen expert, 0 where not chosen
    gate_of = jnp.sum(jax.nn.one_hot(top_i, e) * gates[..., None], axis=1)
    x = rnd(x)

    @jax.checkpoint
    def term(w1, w3, w2, g):
        return g[:, None] * _swiglu(rnd, x, w1, w3, w2)

    def one(y, args):
        return y + term(*args), None

    held = lax.dynamic_slice_in_dim(
        gate_of, first, cfg["num_experts"], axis=1)
    y, _ = lax.scan(one, jnp.zeros_like(h),
                    (blk["w1"], blk["w3"], blk["w2"], held.T))
    return (h + y, jnp.mean(jax.nn.one_hot(top_i[:, 0], e), axis=0),
            jnp.mean(s / jnp.sum(s, axis=-1, keepdims=True), axis=0))


def _dense(cfg, rnd, h, blk):
    x = rnd(_rms_norm(h, blk["ln2"], cfg["norm_eps"]))
    return h + _swiglu(rnd, x, blk["w1"], blk["w3"], blk["w2"])


def batch_loss(cfg, precision, params, tokens, targets):
    """Mean cross-entropy over the vocabulary's slice, the head the
    embedding itself, + ``aux_loss_weight`` x sum over the sparse layers
    of E x sum_e f_e P_e, with f and P means over all the batch's
    tokens (f, a count of first choices, carries no gradient).  Layer
    by layer, and within a layer one sequence at a time under
    ``jax.checkpoint``."""
    if not cfg["tie_word_embeddings"]:
        raise SystemExit("the head is the embedding")
    rnd = rounder(precision)
    e = cfg["router_experts"]
    h = params["embed"][tokens]                             # (B, T, d)
    aux = 0.0
    for blk, (mixer, mlp) in zip(layer_blocks(cfg, params), layers(cfg)):
        mix = _MIXERS[mixer]
        if mlp == "dense":
            h = lax.map(jax.checkpoint(lambda h, blk=blk, mix=mix: _dense(
                cfg, rnd, mix(cfg, rnd, h, blk), blk)), h)
            continue
        h, f, p = lax.map(jax.checkpoint(
            lambda h, blk=blk, mix=mix: _experts(
                cfg, rnd, mix(cfg, rnd, h, blk), blk)), h)
        aux = aux + e * jnp.sum(
            lax.stop_gradient(jnp.mean(f, axis=0)) * jnp.mean(p, axis=0))

    @jax.checkpoint
    def sequence_nll(args):
        h, targets = args
        x = rnd(_rms_norm(h, params["ln_f"], cfg["norm_eps"]))
        logp = jax.nn.log_softmax(x @ rnd(params["embed"]).T, axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, targets[:, None], axis=-1))

    return jnp.mean(lax.map(sequence_nll, (h, targets))) \
        + cfg["aux_loss_weight"] * aux


def batch_loss_and_grads(cfg, precision, params, tokens, targets):
    return jax.value_and_grad(partial(batch_loss, cfg, precision))(
        params, tokens, targets)


def expert_choices(cfg, params, tokens):
    """``(B, T, sparse layers, k)`` int32: the experts every token
    chose in every sparse layer, in float32."""
    exact = rounder("float32")

    def one(params, tok):
        h, out = params["embed"][tok], []
        for blk, (mixer, mlp) in zip(
                layer_blocks(cfg, params), layers(cfg)):
            h = _MIXERS[mixer](cfg, exact, h, blk)
            if mlp == "dense":
                h = _dense(cfg, exact, h, blk)
                continue
            x = _rms_norm(h, blk["ln2"], cfg["norm_eps"])
            out.append(route(cfg, x, blk["router"], blk["router_bias"])[1])
            h = _experts(cfg, exact, h, blk)[0]
        return jnp.stack(out, axis=1)

    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: lax.map(partial(one, p), t))(
            params, tokens)


def _is_fixed(path):
    return any(getattr(k, "key", None) == FIXED for k in path)


def adamw_step(cfg, precision, params, mu, nu, count, tokens, targets):
    """One step of AdamW, its rule written out: ``(params, mu, nu,
    count, loss, the norm of each leaf's gradient)``.  The selection
    bias is no parameter of the loss: its gradient is zero and the
    update, weight decay included, passes it by."""
    opt = cfg["optimizer"]["args"]
    lr, b1, b2, eps, wd = (opt["learning_rate"], opt["b1"], opt["b2"],
                           opt["eps"], opt["weight_decay"])
    loss, grads = batch_loss_and_grads(
        cfg, precision, params, tokens, targets)
    norms = leaf_norms(grads)
    count = count + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)

    def update(path, p, m, v):
        if _is_fixed(path):
            return p
        m_hat = m / (1 - b1 ** count)
        v_hat = v / (1 - b2 ** count)
        return p - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + wd * p)

    return (jax.tree_util.tree_map_with_path(update, params, mu, nu),
            mu, nu, count, loss, norms)


@lru_cache(maxsize=None)
def _jitted_step(cfg_json, precision):
    """One jitted ``adamw_step`` a configuration and precision: the
    seeds of one process (``tools/limits.py``, the tests) share its
    compilation."""
    return jax.jit(partial(adamw_step, json.loads(cfg_json), precision),
                   donate_argnums=(0, 1, 2))


def follow(cfg, make_params, batches, precision="float32"):
    """Drive AdamW from ``make_params()`` through ``batches``
    (``(tokens, targets)`` of shape ``(B, T)``, one step each) and
    return what the check compares: every step's loss, the norm of each
    leaf's first gradient, and the norm of each leaf's change after the
    last step."""
    if cfg["optimizer"]["name"] != "adamw":
        raise SystemExit("this plain reference writes out AdamW's rule; "
                         "another optimizer is another reference file")
    step = _jitted_step(json.dumps(cfg, sort_keys=True), precision)
    with jax.default_matmul_precision("highest"):
        params = make_params()
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        count = jnp.zeros((), jnp.float32)
        # start ``count`` where the step hands it back, so that the
        # step compiles once (reference/qwen3_next.py says why)
        placed = getattr(jax.tree.leaves(params)[0], "sharding", None)
        if isinstance(placed, NamedSharding):
            count = jax.device_put(
                count, NamedSharding(placed.mesh, PartitionSpec()))
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
