"""Qwen3-Next (Qwen/Qwen3-Next-80B-A3B-Instruct, ``model_type:
qwen3_next``; Gated Delta Networks, arXiv:2412.06464; gated attention,
arXiv:2505.06708; the model's public modelling file as the builder
remembers it): a pre-norm decoder whose token mixers are Gated DeltaNet
(``linear_attention``) in three layers of four and gated softmax
attention (``full_attention``) in the fourth, every layer's second part
a sparse expert layer beside one GATED shared expert; zero-centred
norms; separate embedding and output matrices.  Its loss and AdamW
steps in plain float32 ``jax.numpy``: the yardstick the cell's first
steps are held to.  No kernel, no chunked recurrence, no cache of keys
or states, and nothing of ``chainermn_tpu`` is imported.

**Norms.**  Every norm with a learned scale but the linear layer's
output norm is zero-centred: ``y = x / rms(x) (1 + w)`` with
``rms_norm_eps``; ``w`` is the stored leaf (what AdamW's weight decay
pulls to 0), seeded N(0, 0.05^2) and not 0.  With ``x_t`` a layer's
normed input:

**linear_attention**, ``H_k = linear_num_key_heads`` of ``d_k =
linear_key_head_dim``, ``H_v = linear_num_value_heads`` of ``d_v =
linear_value_head_dim``::

    [q k v z] = x W_in   (H_k d_k + H_k d_k + H_v d_v + H_v d_v, no bias)
    [b a] = x W_ba       (H_v + H_v)
    q, k, v <- SiLU(conv(.))     conv(y)_t = sum_j w_j . y_(t - taps + 1 + j)
                                 (a weight a channel a tap, causal, no bias)
    q <- q / |q| d_k^-1/2,  k <- k / |k|    a key head; value head j reads key head j // (H_v / H_k)
    beta_t = sigmoid(b_t),  g_t = -exp(A_log) softplus(a_t + dt_bias)   a value head, g <= 0
    S_t = e^{g_t} S_(t-1) - beta_t k_t (k_t^T e^{g_t} S_(t-1)) + beta_t k_t v_t^T,   S_0 = 0  (d_k x d_v)
    o_t = S_t^T q_t
    out_t = W_o [ RMSNorm_head(o_t; ONE plain scale of d_v for all heads) . SiLU(z_t) ]

norm first, gate after.  The recurrence is written as it stands, **a
token at a time** (``_delta_rule``): a ``lax.scan`` over blocks of
``STATE_BLOCK`` tokens whose body, an inner scan over the block's
tokens, is rematerialised, so that the backward pass keeps one state a
block and not one a token (16,384 states of 32 x 128 x 128 are 34 GB).

**full_attention**, ``H = num_attention_heads`` over ``num_key_value_heads``
of ``d = head_dim``::

    [q gate] = x W_q  (H x (d + d));   k, v = x W_k, x W_v;   no bias
    q, k <- the zero-centred RMSNorm over each head's d (one scale each for all heads)
    the first partial_rotary_factor x d dimensions of each head rotated
    (rotate-half within them, theta rope_theta), the rest passed through
    causal softmax at d^-1/2, query head j reads key-value head j // (H / kv heads)
    o <- o . sigmoid(gate)  AN ELEMENT;   out = W_o o

scored by blocks of ``Q_BLOCK`` queries.

**Every layer's second part.**  ``p = softmax(u W_r)`` over the
router's experts in float32; the ``num_experts_per_tok`` largest win,
gates ``p / sum_chosen p`` (``norm_topk_prob``); experts ``W_2 (SiLU(W_1
u) . W_3 u)``; beside them ``sigmoid(u . w_s) SwiGLU(u)``, which every
token meets.

The chip's share, the layout of the parameters (the program's, less its
leading pipeline axis) and the layer-by-layer, sequence-by-sequence
order of the batch are ``reference/laguna.py``'s, for its reasons.

Departures from the published model, each under ``assumed`` in the
configuration's file: the L2 norm's epsilon, the output norm's plain
scale, the seeds (``A_log``, ``dt_bias``, the norms' ``w``), the
balancing loss, the weights' scales and AdamW as in the Kimi file; the
multi-token prediction block is left out (``left_out``).
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
from .laguna import _rope, _swiglu

Q_BLOCK = 256       # queries scored at a time (at most: a divisor of T)
PARTS = 4           # of the sequence, each with its own prefix of keys
STATE_BLOCK = 128   # tokens between two kept states of the recurrence
# (at most: a divisor of T)
NORM_SEED = 0.05    # the zero-centred scales' seeded spread


def layers(cfg):
    """The mixer of each layer run: every ``full_attention_interval``-th
    is ``full``, the others ``linear``; every layer is sparse."""
    if cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"]:
        raise SystemExit("every layer's second part is the expert layer")
    n = cfg["full_attention_interval"]
    return ["full" if (i + 1) % n == 0 else "linear"
            for i in range(cfg["num_hidden_layers"])]


def _norm(x, w, eps):
    """The zero-centred RMSNorm: ``w`` is what is stored."""
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                         + eps) * (1.0 + w)


def init(key, cfg):
    """Seeded weights in the program's layout: N(0, 1) embedding rows,
    an N(0, 0.02) output matrix, N(0, 1/fan_in) matrices (the
    convolution's fan_in is its taps) with the projections that write
    to the residual stream (``wo``, ``w2``, ``ws2``) scaled by 1/sqrt(2
    x the published depth) (the Laguna file's ``assumed.init`` says
    why); the zero-centred norm scales N(0, NORM_SEED^2), the linear
    layer's plain output scale 1; ``exp(a_log)`` uniform in [1, 16] and
    ``dt_bias`` the inverse softplus of a log-uniform [1e-3, 1e-1]."""
    d, e, g, v = (cfg["hidden_size"], cfg["router_experts"],
                  cfg["num_experts"], cfg["vocabulary"])
    f, fs = cfg["moe_intermediate_size"], \
        cfg["shared_expert_intermediate_size"]
    hk, dk, hv, dv, taps = (
        cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
        cfg["linear_num_value_heads"], cfg["linear_value_head_dim"],
        cfg["linear_conv_kernel_dim"])
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    depth = cfg.get("published", cfg)["num_hidden_layers"]
    residual = (2 * depth) ** -.5

    def block(key, mixer):
        k = iter(jax.random.split(key, 24))

        def dense(shape, fan_in, scale=1.0):
            return jax.random.normal(next(k), shape, jnp.float32) \
                * fan_in ** -.5 * scale

        def scale(n):
            return NORM_SEED * jax.random.normal(next(k), (n,), jnp.float32)

        out = {"ln1": scale(d), "ln2": scale(d)}
        if mixer == "linear":
            dt = jnp.exp(jax.random.uniform(
                next(k), (hv,), jnp.float32, math.log(1e-3),
                math.log(1e-1)))
            out.update(
                w_in=dense((d, 2 * hk * dk + 2 * hv * dv), d),
                w_ba=dense((d, 2 * hv), d),
                conv=dense((2 * hk * dk + hv * dv, taps), taps),
                a_log=jnp.log(jax.random.uniform(
                    next(k), (hv,), jnp.float32, 1.0, 16.0)),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                o_norm=jnp.ones((dv,), jnp.float32),
                wo=dense((hv, dv, d), hv * dv, residual))
        else:
            out.update(
                wq=dense((d, h, dh), d), wkv=dense((d, 2, kv, dh), d),
                wg=dense((d, h, dh), d),
                q_norm=scale(dh), k_norm=scale(dh),
                wo=dense((h, dh, d), h * dh, residual))
        return dict(out, router=dense((d, e), d),
                    w1=dense((g, d, f), d), w3=dense((g, d, f), d),
                    w2=dense((g, f, d), f, residual),
                    ws1=dense((d, fs), d), ws3=dense((d, fs), d),
                    ws2=dense((fs, d), fs, residual),
                    wsg=dense((d, 1), d))

    every = layers(cfg)
    n = cfg["full_attention_interval"]
    keys = jax.random.split(key, len(every) + 3)
    blocks = [block(keys[i], mixer) for i, mixer in enumerate(every)]
    return {
        "embed": jax.random.normal(keys[-3], (v, d), jnp.float32),
        "head": jax.random.normal(keys[-2], (v, d), jnp.float32) * 0.02,
        "ln_f": NORM_SEED * jax.random.normal(keys[-1], (d,), jnp.float32),
        # one stack over the periods for each position of the pattern
        "blocks": tuple(
            jax.tree.map(lambda *a: jnp.stack(a), *blocks[j::n])
            for j in range(n)),
    }


def layer_blocks(cfg, params):
    """Each layer's own block, in layer order."""
    n = cfg["full_attention_interval"]
    return [jax.tree.map(lambda a: a[i // n], params["blocks"][i % n])
            for i in range(cfg["num_hidden_layers"])]


def _delta_rule(q, k, v, g, beta):
    """The recurrence of the module docstring, a token at a time: ``q``,
    ``k`` ``(T, H_v, d_k)`` (each key head already copied out to its
    value heads), ``v`` ``(T, H_v, d_v)``, ``g`` and ``beta`` ``(T,
    H_v)``; returns ``o`` ``(T, H_v, d_v)``."""
    t, h, d = k.shape

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[:, None, None] * S            # e^g S
        # S - b k (k^T S) + b k v^T = S + k (b (v - S^T k))^T
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[..., None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    @jax.checkpoint
    def block(S, xs):
        return lax.scan(token, S, xs)

    n = math.gcd(STATE_BLOCK, t)
    xs = tuple(x.reshape(t // n, n, *x.shape[1:])
               for x in (q, k, v, g, beta))
    _, o = lax.scan(block, jnp.zeros((h, d, v.shape[-1]), jnp.float32), xs)
    return o.reshape(t, h, -1)


def _to_value_heads(y, rep):
    """``(T, H_k, d)`` -> ``(T, H_k rep, d)``: value head ``j`` reads key
    head ``j // rep``."""
    return jnp.repeat(y, rep, axis=1)


def _norm_then_gate(o, z, scale, eps):
    """The linear layer's way out: the RMSNorm over each head with its
    plain scale FIRST, the gate ``SiLU(z)`` after."""
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * scale
    return o * jax.nn.silu(z)


def _linear(cfg, rnd, h, blk):
    t, d = h.shape
    hk, dk, hv, dv = (
        cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
        cfg["linear_num_value_heads"], cfg["linear_value_head_dim"])
    keys, taps = hk * dk, blk["conv"].shape[-1]
    x = rnd(_norm(h, blk["ln1"], cfg["rms_norm_eps"]))
    proj = x @ rnd(blk["w_in"])
    ba = x @ rnd(blk["w_ba"])
    qkv, z = proj[:, :2 * keys + hv * dv], proj[:, 2 * keys + hv * dv:]
    padded = jnp.pad(rnd(qkv), ((taps - 1, 0), (0, 0)))
    w = rnd(blk["conv"])
    qkv = jax.nn.silu(sum(padded[j:j + t] * w[:, j] for j in range(taps)))

    def unit(y):
        return y * lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True)
                             + cfg["l2_norm_eps"])

    q = unit(qkv[:, :keys].reshape(t, hk, dk)) * dk ** -.5
    k = unit(qkv[:, keys:2 * keys].reshape(t, hk, dk))
    v = qkv[:, 2 * keys:].reshape(t, hv, dv)
    q, k = (_to_value_heads(y, hv // hk) for y in (q, k))
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(blk["a_log"]) * jax.nn.softplus(ba[:, hv:] + blk["dt_bias"])
    o = _delta_rule(rnd(q), rnd(k), rnd(v), g, beta)
    o = _norm_then_gate(o, z.reshape(t, hv, dv), blk["o_norm"],
                        cfg["rms_norm_eps"])
    return h + rnd(o.reshape(t, -1)) @ rnd(blk["wo"].reshape(-1, d))


def inv_freq(cfg):
    """A frequency for each pair of the ``partial_rotary_factor x
    head_dim`` dimensions that are rotated."""
    if cfg["rope_scaling"] is not None:
        raise SystemExit("plain rotary: rope_scaling null")
    r = int(cfg["head_dim"] * cfg["partial_rotary_factor"])
    return cfg["rope_theta"] ** (-np.arange(0, r, 2, dtype=np.float64) / r)


def _full(cfg, rnd, h, blk):
    t, d = h.shape
    eps = cfg["rms_norm_eps"]
    n_kv, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    n_heads = blk["wq"].shape[1]
    freqs = inv_freq(cfg)
    x = rnd(_norm(h, blk["ln1"], eps))
    q = (x @ rnd(blk["wq"].reshape(d, -1))).reshape(t, n_heads, dh)
    gate = jax.nn.sigmoid(
        x @ rnd(blk["wg"].reshape(d, -1))).reshape(t, n_heads, dh)
    kv = (x @ rnd(blk["wkv"].reshape(d, -1))).reshape(t, 2, n_kv, dh)
    q = rnd(_rope(_norm(q, blk["q_norm"], eps), freqs, 1.0))
    k = rnd(_rope(_norm(kv[:, 0], blk["k_norm"], eps), freqs, 1.0))
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
    o = o.reshape(t, n_heads, dh) * gate           # an element
    return h + rnd(o.reshape(t, -1)) @ rnd(blk["wo"].reshape(-1, d))


_MIXERS = {"linear": _linear, "full": _full}


def route(cfg, x, router):
    """``(p, top_i, gates)`` of the softmax router, in float32 (the
    lower-precision control does not round it either): the probabilities
    over all the router's experts, the k largest, and their
    probabilities renormalised over the k chosen."""
    if not cfg["norm_topk_prob"]:
        raise SystemExit("the chosen gates are renormalised")
    p = jax.nn.softmax(x @ router, axis=-1)
    top_p, top_i = lax.top_k(p, cfg["num_experts_per_tok"])
    return p, top_i, top_p / jnp.sum(top_p, axis=-1, keepdims=True)


def _shared_gate(x, wsg):
    """One sigmoid scalar a token on the shared expert's result."""
    return jax.nn.sigmoid(x @ wsg)


def _experts(cfg, rnd, h, blk):
    """``(h + y, f, P)``: the held experts' part of the layer plus the
    gated shared expert, the share of tokens whose first choice each
    expert is, and the mean router probability of each."""
    e, first = cfg["router_experts"], cfg["experts_first"]
    x = _norm(h, blk["ln2"], cfg["rms_norm_eps"])
    p, top_i, gates = route(cfg, x, blk["router"])
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
    y = y + _shared_gate(x, rnd(blk["wsg"])) \
        * _swiglu(rnd, x, blk["ws1"], blk["ws3"], blk["ws2"])
    return (h + y, jnp.mean(jax.nn.one_hot(top_i[:, 0], e), axis=0),
            jnp.mean(p, axis=0))


def batch_loss(cfg, precision, params, tokens, targets):
    """Mean cross-entropy over the vocabulary's slice +
    ``aux_loss_weight`` x sum over the layers of E x sum_e f_e P_e, with
    f and P means over all the batch's tokens (f, a count of first
    choices, carries no gradient).  Layer by layer, and within a layer
    one sequence at a time under ``jax.checkpoint``."""
    rnd = rounder(precision)
    e = cfg["router_experts"]
    h = params["embed"][tokens]                             # (B, T, d)
    aux = 0.0
    for blk, mixer in zip(layer_blocks(cfg, params), layers(cfg)):
        h, f, p = lax.map(jax.checkpoint(
            lambda h, blk=blk, mix=_MIXERS[mixer]: _experts(
                cfg, rnd, mix(cfg, rnd, h, blk), blk)), h)
        aux = aux + e * jnp.sum(
            lax.stop_gradient(jnp.mean(f, axis=0)) * jnp.mean(p, axis=0))

    @jax.checkpoint
    def sequence_nll(args):
        h, targets = args
        x = rnd(_norm(h, params["ln_f"], cfg["rms_norm_eps"]))
        logp = jax.nn.log_softmax(x @ rnd(params["head"]).T, axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, targets[:, None], axis=-1))

    return jnp.mean(lax.map(sequence_nll, (h, targets))) \
        + cfg["aux_loss_weight"] * aux


def batch_loss_and_grads(cfg, precision, params, tokens, targets):
    return jax.value_and_grad(partial(batch_loss, cfg, precision))(
        params, tokens, targets)


def expert_choices(cfg, params, tokens):
    """``(B, T, layers, k)`` int32: the experts every token chose in
    every layer, in float32."""
    exact = rounder("float32")

    def one(params, tok):
        h, out = params["embed"][tok], []
        for blk, mixer in zip(layer_blocks(cfg, params), layers(cfg)):
            h = _MIXERS[mixer](cfg, exact, h, blk)
            x = _norm(h, blk["ln2"], cfg["rms_norm_eps"])
            out.append(route(cfg, x, blk["router"])[1])
            h = _experts(cfg, exact, h, blk)[0]
        return jnp.stack(out, axis=1)

    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: lax.map(partial(one, p), t))(
            params, tokens)


def adamw_step(cfg, precision, params, mu, nu, count, tokens, targets):
    """One step of AdamW, its rule written out: ``(params, mu, nu,
    count, loss, the norm of each leaf's gradient)``.  Weight decay
    acts on each leaf as it is stored: a zero-centred scale's ``w``."""
    opt = cfg["optimizer"]["args"]
    lr, b1, b2, eps, wd = (opt["learning_rate"], opt["b1"], opt["b2"],
                           opt["eps"], opt["weight_decay"])
    loss, grads = batch_loss_and_grads(
        cfg, precision, params, tokens, targets)
    norms = leaf_norms(grads)
    count = count + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)

    def update(p, m, v):
        m_hat = m / (1 - b1 ** count)
        v_hat = v / (1 - b2 ** count)
        return p - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + wd * p)

    return jax.tree.map(update, params, mu, nu), mu, nu, count, loss, norms


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
        # the step hands ``count`` back replicated over the parameters'
        # mesh: start it there, or the second step's argument differs
        # from the first's in its sharding alone and the whole step is
        # compiled a second time (a minute on the chip, and a second
        # 74 MB entry in a compile cache that holds 190: PERF.md §6)
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
