"""Mellum 2 (JetBrains/Mellum2-12B-A2.5B-Instruct, ``model_type:
mellum``): a pre-norm decoder whose layers differ by kind -- three
``sliding_attention`` layers (window, plain rotary) then one
``full_attention`` layer (YaRN rotary) -- with grouped-query attention
and, in every layer, a sparse expert layer (softmax router over all
experts, the k largest renormalised, SwiGLU experts, no shared expert),
separate embedding and output matrices; its loss and AdamW steps in
plain float32 ``jax.numpy``: the yardstick the Mellum cell's first steps
are held to.

The chip's share.  The configuration states how many experts are held
here (``num_experts`` consecutive ones from ``experts_first``, of the
``router_experts`` the router scores) and how many rows of the
vocabulary (``vocabulary``).  The router keeps its published width and
its experts per token, the gates are normalised over all k chosen, and
the sum runs over the chosen experts that are held: what the absent
ones would have added is left out, and that partial result goes on to
the next layer.  Here every held expert is computed for every token and
masked by the routing: no sort, no grouped product.

Departures from the published model, each under ``assumed`` in the
configuration's file: no per-head q/k norm and no dropout (the config
names neither); the balancing loss is ``aux_loss_weight`` x E x sum_e
f_e P_e over all the router's columns with f from the first choice (the
config gives no coefficient); the MTP head is not in the config and is
left out.

The gradient of the batch's mean loss is taken one sequence at a time,
each layer under ``jax.checkpoint`` and attention by blocks of queries
(at 8,192 positions one layer's float32 scores are 8.6 GB).  The
balancing loss couples the sequences of a batch (f and P are means over
all its tokens), so a first, forward-only pass reads every layer's f
over the batch; f is piecewise constant, so the second pass, with that f
held fixed and each sequence's own P, gives the batch loss and its
gradient exactly.
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .common import delta_norms, leaf_norms, rounder, to_floats

Q_BLOCK = 256     # queries scored at a time
PARTS = 4         # of the sequence, each with its own prefix of keys


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_hidden_layers"])


def init(key, cfg):
    """Seeded weights in the layout the program's ``init_transformer``
    uses, less its leading pipeline axis on the blocks (the driver
    checks shape for shape): N(0, 1) embedding rows, an N(0, 0.02)
    output matrix, N(0, 1/fan_in) matrices with the two projections
    that write to the residual stream (``wo``, ``w2``) scaled by
    1/sqrt(2 x the published depth) as in Megatron's scaled init, unit
    norm scales.

    Why these: they make the router's input a token's own.  With
    0.02-rows and unit-variance output projections, what attention adds
    to every position alike (the mean of the values it sees) outweighs
    the rows, every token's router logits share that offset, and one
    expert is sent 3 to 7 times the mean: the rows routed here, and with
    them the step's time, then move with the seed by several percent.
    A trained router is held even by its balancing loss; these weights
    spread the rows about as evenly (largest expert 1.1 to 1.2 times
    the mean)."""
    d, h, hkv, dh, f, n = _dims(cfg)
    e, g, v = cfg["router_experts"], cfg["num_experts"], cfg["vocabulary"]
    k = jax.random.split(key, 9)
    depth = cfg.get("published", cfg)["num_hidden_layers"]
    residual = (2 * depth) ** -.5

    def dense(key, shape, fan_in):
        return jax.random.normal(key, (n,) + shape, jnp.float32) \
            * fan_in ** -.5

    return {
        "embed": jax.random.normal(k[0], (v, d), jnp.float32),
        "head": jax.random.normal(k[1], (v, d), jnp.float32) * 0.02,
        "ln_f": jnp.ones((d,), jnp.float32),
        "blocks": {
            "ln1": jnp.ones((n, d), jnp.float32),
            "ln2": jnp.ones((n, d), jnp.float32),
            "wq": dense(k[2], (d, h, dh), d),
            "wkv": dense(k[3], (d, 2, hkv, dh), d),
            "wo": dense(k[4], (h, dh, d), h * dh) * residual,
            "router": dense(k[5], (d, e), d),
            "w1": dense(k[6], (g, d, f), d),
            "w3": dense(k[7], (g, d, f), d),
            "w2": dense(k[8], (g, f, d), f) * residual,
        },
    }


def inv_freq(cfg, kind):
    """The head_dim / 2 rotary frequencies of a layer kind, and the
    factor its cos and sin carry.  ``default``: theta^(-2i/d).  ``yarn``:
    dimension i keeps its frequency where it turns more than beta_fast
    times within the original context, has it divided by ``factor``
    where it turns less than beta_slow times, and blends linearly in
    between."""
    rope = cfg["rope_parameters"][kind]
    dh, theta = cfg["head_dim"], rope["rope_theta"]
    base = theta ** (-np.arange(0, dh, 2, dtype=np.float64) / dh)
    if rope["rope_type"] == "default":
        return base, 1.0
    if rope["rope_type"] != "yarn":
        raise SystemExit(f"rope_type {rope['rope_type']!r}")

    def dimension(turns):
        return dh * math.log(rope["original_max_position_embeddings"] / (
            turns * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(dimension(rope["beta_fast"])), 0)
    hi = min(math.ceil(dimension(rope["beta_slow"])), dh - 1)
    ramp = np.clip((np.arange(dh // 2) - lo) / (hi - lo), 0, 1)
    return (ramp * base / rope["factor"] + (1 - ramp) * base,
            rope["attention_factor"])


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                         + eps) * scale


def _rope(x, freqs, factor):
    """Rotate-half rotary over all of the head's dimensions; ``x`` is
    ``(T, heads, head_dim)``."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(cfg, rnd, h, blk, kind):
    t, d = h.shape
    _, n_heads, n_kv, dh, _, _ = _dims(cfg)
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    freqs, factor = inv_freq(cfg, kind)
    x = rnd(_rms_norm(h, blk["ln1"], cfg["rms_norm_eps"]))
    q = (x @ rnd(blk["wq"].reshape(d, -1))).reshape(t, n_heads, dh)
    kv = (x @ rnd(blk["wkv"].reshape(d, -1))).reshape(t, 2, n_kv, dh)
    q = rnd(_rope(q, freqs, factor))
    k, v = rnd(_rope(kv[:, 0], freqs, factor)), rnd(kv[:, 1])
    # query head j reads key-value head j // (heads / kv heads)
    block = min(Q_BLOCK, t)
    qb = q.reshape(t // block, block, n_kv, n_heads // n_kv, dh)

    @jax.checkpoint
    def rows(qi, start, ki, vi, first_key):
        """A block of queries from position ``start`` against the keys
        ``ki`` from position ``first_key`` (negative: padding)."""
        qpos = start + jnp.arange(block)
        kpos = first_key + jnp.arange(ki.shape[0])
        s = jnp.einsum("qgrd,kgd->grqk", qi, ki) * dh ** -.5
        allow = (qpos[:, None] >= kpos[None, :]) & (kpos[None, :] >= 0)
        if window is not None:
            allow &= (qpos[:, None] - kpos[None, :]) < window
        p = rnd(jax.nn.softmax(jnp.where(allow, s, -jnp.inf), axis=-1))
        return jnp.einsum("grqk,kgd->qgrd", p, vi)

    # keys no query of a block can see are not scored (they would be
    # masked to nothing): a windowed block meets the window before it
    # and itself; a full one the keys up to the end of its part of the
    # sequence (PARTS parts: few shapes to compile, a third less to
    # score than all keys for every block)
    starts = jnp.arange(0, t, block)
    if window is not None and window + block < t:
        pad = ((window, 0), (0, 0), (0, 0))
        kp, vp = jnp.pad(k, pad), jnp.pad(v, pad)

        def windowed(args):
            qi, start = args
            return rows(
                qi, start,
                lax.dynamic_slice_in_dim(kp, start, window + block),
                lax.dynamic_slice_in_dim(vp, start, window + block),
                start - window)

        o = lax.map(windowed, (qb, starts))
    else:
        n = len(qb)
        parts = PARTS if n % PARTS == 0 else 1
        per = n // parts
        o = jnp.concatenate([
            lax.map(lambda args, end=(i + 1) * per * block: rows(
                *args, k[:end], v[:end], 0),
                (qb[i * per:(i + 1) * per], starts[i * per:(i + 1) * per]))
            for i in range(parts)])
    return h + rnd(o.reshape(t, -1)) @ rnd(blk["wo"].reshape(-1, d))


def _experts(cfg, rnd, h, blk):
    """``(h + y, f, P)``: the held experts' part of the layer, the
    share of tokens whose first choice each expert is, and the mean
    router probability of each."""
    k, first = cfg["num_experts_per_tok"], cfg["experts_first"]
    e = cfg["router_experts"]
    x = _rms_norm(h, blk["ln2"], cfg["rms_norm_eps"])
    # the router is float32 in the program whatever its compute dtype:
    # the lower-precision control does not round it either
    probs = jax.nn.softmax(x @ blk["router"], axis=-1)
    top_p, top_i = lax.top_k(probs, k)
    gates = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # (T, E): the gate of each chosen expert, 0 where not chosen
    gate_of = jnp.sum(jax.nn.one_hot(top_i, e) * gates[..., None], axis=1)
    x = rnd(x)

    @jax.checkpoint
    def one(y, args):
        w1, w3, w2, g = args
        mid = rnd(jax.nn.silu(x @ rnd(w1)) * (x @ rnd(w3)))
        return y + g[:, None] * (mid @ rnd(w2)), None

    held = lax.dynamic_slice_in_dim(
        gate_of, first, cfg["num_experts"], axis=1)
    y, _ = lax.scan(one, jnp.zeros_like(h),
                    (blk["w1"], blk["w3"], blk["w2"], held.T))
    return (h + y, jnp.mean(jax.nn.one_hot(top_i[:, 0], e), axis=0),
            jnp.mean(probs, axis=0))


def sequence_forward(cfg, precision, params, tokens, targets):
    """``(cross-entropy, f (layers, E), P (layers, E))`` of ONE
    sequence."""
    rnd = rounder(precision)
    h = params["embed"][tokens]
    fs, ps = [], []
    for i in range(cfg["num_hidden_layers"]):
        blk = jax.tree.map(lambda a: a[i], params["blocks"])

        @jax.checkpoint
        def layer(h, blk, kind=cfg["layer_types"][i]):
            h = _attention(cfg, rnd, h, blk, kind)
            return _experts(cfg, rnd, h, blk)

        h, f, p = layer(h, blk)
        fs.append(f)
        ps.append(p)
    x = rnd(_rms_norm(h, params["ln_f"], cfg["rms_norm_eps"]))
    logp = jax.nn.log_softmax(x @ rnd(params["head"]).T, axis=-1)
    nll = -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))
    return nll, jnp.stack(fs), jnp.stack(ps)


def batch_loss_and_grads(cfg, precision, params, tokens, targets):
    """Mean cross-entropy + ``aux_loss_weight`` x sum over layers of
    E x sum_e f_e P_e (f, P over all the batch's tokens), and its
    gradient, one sequence at a time (module docstring)."""
    e, weight = cfg["router_experts"], cfg["aux_loss_weight"]
    forward = partial(sequence_forward, cfg, precision)
    _, fs, _ = lax.map(lambda row: forward(params, *row), (tokens, targets))
    f_batch = lax.stop_gradient(jnp.mean(fs, axis=0))       # (layers, E)

    def sequence_loss(params, row):
        nll, _, p = forward(params, *row)
        return nll + weight * e * jnp.sum(f_batch * p)

    def one(acc, row):
        got = jax.value_and_grad(sequence_loss)(params, row)
        return jax.tree.map(jnp.add, acc, got), None

    zero = (jnp.zeros(()), jax.tree.map(jnp.zeros_like, params))
    (loss, grads), _ = lax.scan(one, zero, (tokens, targets))
    n = tokens.shape[0]
    return loss / n, jax.tree.map(lambda g: g / n, grads)


def expert_choices(cfg, params, tokens):
    """``(B, T, layers, k)`` int32: the experts every token chose in
    every layer, in float32 (for the share of choices on which program
    and reference differ)."""
    def one(params, tok):
        h, out = params["embed"][tok], []
        for i in range(cfg["num_hidden_layers"]):
            blk = jax.tree.map(lambda a: a[i], params["blocks"])
            h = _attention(cfg, rounder("float32"), h, blk,
                           cfg["layer_types"][i])
            x = _rms_norm(h, blk["ln2"], cfg["rms_norm_eps"])
            out.append(lax.top_k(jax.nn.softmax(x @ blk["router"], -1),
                                 cfg["num_experts_per_tok"])[1])
            h = _experts(cfg, rounder("float32"), h, blk)[0]
        return jnp.stack(out, axis=1)

    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: lax.map(partial(one, p), t))(
            params, tokens)


def follow(cfg, make_params, batches, precision="float32"):
    """Drive AdamW from ``make_params()`` through ``batches``
    (``(tokens, targets)`` of shape ``(B, T)``, one step each; the start
    is made again at the end rather than kept beside the optimizer's
    state) and return what the check compares: every step's loss, the
    norm of each leaf's first gradient, and the norm of each leaf's
    change after the last step."""
    if cfg["optimizer"]["name"] != "adamw":
        raise SystemExit("this plain reference writes out AdamW's rule; "
                         "another optimizer is another reference file")
    opt = cfg["optimizer"]["args"]
    lr, b1, b2, eps, wd = (opt["learning_rate"], opt["b1"], opt["b2"],
                           opt["eps"], opt["weight_decay"])

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, mu, nu, count, tokens, targets):
        loss, grads = batch_loss_and_grads(
            cfg, precision, params, tokens, targets)
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
