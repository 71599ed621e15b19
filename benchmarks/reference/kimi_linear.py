"""Kimi Linear (moonshotai/Kimi-Linear-48B-A3B, ``model_type:
kimi_linear``; Kimi Linear, arXiv:2510.26692, section 3, and the model's
public modelling file): a pre-norm decoder whose token mixers are Kimi
Delta Attention (KDA) in three layers of four and multi-head latent
attention (MLA) without rotary in the fourth, whose first layer has a
dense SwiGLU MLP and whose later layers have sparse experts beside one
shared expert; separate embedding and output matrices.  Its loss and
AdamW steps in plain float32 ``jax.numpy``: the yardstick the Kimi
cell's first steps are held to.  No kernel, no chunked recurrence, no
cache of keys or states, and nothing of ``chainermn_tpu`` is imported.

The layers.  RMSNorm (``rms_norm_eps``) before mixer and before MLP,
residual adds.  With ``x_t`` the normed input of a layer:

**KDA**, a head ``h`` of ``num_heads``, ``d = head_dim`` of
``linear_attn_config``::

    q_t = L2Norm(SiLU(conv(W_q x)_t)) d^-1/2    k_t = L2Norm(SiLU(conv(W_k x)_t))
    v_t = SiLU(conv(W_v x)_t)
    conv(y)_t = sum_j w_j . y_(t - taps + 1 + j)       (a weight a channel a tap, causal)
    g_t = -exp(A_h) softplus(W_f^up (W_f^down x_t) + b_dt)   in R^d,  a_t = exp(g_t)
    b_t = sigmoid(w_beta,h . x_t)
    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_(t-1) + b_t k_t v_t^T,   S_0 = 0  (d x d)
    o_t = S_t^T q_t
    out_t = W_o [ RMSNorm_head(o_t) . sigmoid(W_g^up (W_g^down x_t)) ]

The recurrence is written as it stands, **a token at a time**
(``_delta_rule``): a ``lax.scan`` over blocks of ``STATE_BLOCK`` tokens
whose body, an inner scan over the block's tokens, is rematerialised, so
that the backward pass keeps one state a block and not one a token
(16,384 states of 32 x 128 x 128 are 34 GB).

**MLA**, ``mla_use_nope``: no rotary on any part, ``q_lora_rank`` null::

    q = W_q x  (heads x (nope + rope widths));  [c, k_s] = W_kva x  (latent + rope width)
    c <- RMSNorm(c);  [k_n, v] = W_kvb c  (heads x (nope + value widths))
    k_h = [k_n,h ; k_s]  (k_s shared by every head),  scores q_h . k_h (nope + rope)^-1/2,
    causal softmax,  o_h = sum p v_h,  out = W_o o

scored by blocks of ``Q_BLOCK`` queries.

**MLP.**  Layers up to ``first_k_dense_replace``: SwiGLU of
``intermediate_size``.  Others: ``s = sigmoid(x W_r)`` over the router's
experts; the ``num_experts_per_token`` experts with the largest
``s + b`` win (``b`` the selection bias, a leaf no gradient reaches and
no update moves: ``stop_gradient`` here, skipped in ``adamw_step``);
their gates are their ``s`` (without ``b``) normalised over the chosen
and multiplied by ``routed_scaling_factor``; plus the shared expert,
ungated.  One expert group, so no group-limited choice.

The chip's share, the layout of the parameters (the program's, less its
leading pipeline axis) and the layer-by-layer, sequence-by-sequence
order of the batch are ``reference/laguna.py``'s, for its reasons.

Departures from the published model, each under ``assumed`` in the
configuration's file: SiLU after the convolution, the L2Norm's epsilon,
the d^-1/2 on q, softplus and ``b_dt``, the sigmoid output gate at the
head's rank, one norm scale of ``d`` shared by the heads; ``k_s`` and
the matching query channels unrotated; ``b`` seeded and held fixed; the
balancing loss, the weights' scales and AdamW as in the Laguna file.
"""

import json
import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax

from .common import delta_norms, leaf_norms, rounder, to_floats
from .laguna import _rms_norm, _swiglu

Q_BLOCK = 256       # queries scored at a time
PARTS = 4           # of the sequence, each with its own prefix of keys
STATE_BLOCK = 128   # tokens between two kept states of the recurrence
FIXED = "router_bias"   # the leaf no gradient reaches and no update moves


def layers(cfg):
    """``[(mixer, mlp)]`` of the layers run; the config counts layers
    from 1."""
    lin = cfg["linear_attn_config"]
    out = []
    for layer in range(1, cfg["num_hidden_layers"] + 1):
        if (layer in lin["kda_layers"]) == (layer in lin["full_attn_layers"]):
            raise SystemExit(f"layer {layer} is not one of kda and full")
        out.append((
            "kda" if layer in lin["kda_layers"] else "mla",
            "dense" if layer <= cfg["first_k_dense_replace"] else "sparse"))
    return out


def layout(cfg):
    """``(leading, period)``: how many layers lead (the dense ones at
    the start) and the shortest pattern the others repeat."""
    every = layers(cfg)
    leading = next(i for i, layer in enumerate(every)
                   if layer[1] != "dense")
    rest = every[leading:]
    for n in range(1, len(rest) + 1):
        if len(rest) % n == 0 and all(
                layer == rest[i % n] for i, layer in enumerate(rest)):
            return leading, rest[:n]


def init(key, cfg):
    """Seeded weights in the program's layout: N(0, 1) embedding rows,
    an N(0, 0.02) output matrix, N(0, 1/fan_in) matrices with the
    projections that write to the residual stream (``wo``, ``w2``,
    ``ws2``) scaled by 1/sqrt(2 x the published depth), unit norm
    scales (the Laguna file's ``assumed.init`` says why); ``exp(a_log)``
    uniform in [1, 16] and ``dt_bias`` the inverse softplus of a
    log-uniform [1e-3, 1e-1], the published initialisers; the selection
    bias N(0, 0.01^2)."""
    d, e, g, v = (cfg["hidden_size"], cfg["router_experts"],
                  cfg["num_experts"], cfg["vocabulary"])
    f = cfg["moe_intermediate_size"]
    fs = f * cfg["num_shared_experts"]
    lin = cfg["linear_attn_config"]
    h, dh, taps = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    heads, dn, ds, dv, rank = (
        cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
        cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])
    depth = cfg.get("published", cfg)["num_hidden_layers"]
    residual = (2 * depth) ** -.5

    def block(key, mixer, mlp):
        k = iter(jax.random.split(key, 24))

        def dense(shape, fan_in, scale=1.0):
            return jax.random.normal(next(k), shape, jnp.float32) \
                * fan_in ** -.5 * scale

        out = {"ln1": jnp.ones((d,), jnp.float32),
               "ln2": jnp.ones((d,), jnp.float32)}
        if mixer == "kda":
            dt = jnp.exp(jax.random.uniform(
                next(k), (h, dh), jnp.float32, math.log(1e-3),
                math.log(1e-1)))
            out.update(
                wqkv=dense((d, 3, h, dh), d),
                conv=dense((3, h, dh, taps), taps),
                wf_a=dense((d, dh), d), wf_b=dense((dh, h, dh), dh),
                a_log=jnp.log(jax.random.uniform(
                    next(k), (h,), jnp.float32, 1.0, 16.0)),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                wbeta=dense((d, h), d),
                wg_a=dense((d, dh), d), wg_b=dense((dh, h, dh), dh),
                o_norm=jnp.ones((dh,), jnp.float32),
                wo=dense((h, dh, d), h * dh, residual))
        else:
            out.update(
                wq=dense((d, heads, dn + ds), d),
                wkva=dense((d, rank + ds), d),
                kv_norm=jnp.ones((rank,), jnp.float32),
                wkvb=dense((rank, heads, dn + dv), rank),
                wo=dense((heads, dv, d), heads * dv, residual))
        if mlp == "dense":
            width = cfg["intermediate_size"]
            return dict(out, w1=dense((d, width), d),
                        w3=dense((d, width), d),
                        w2=dense((width, d), width, residual))
        return dict(out, router=dense((d, e), d),
                    router_bias=0.01 * jax.random.normal(
                        next(k), (e,), jnp.float32),
                    w1=dense((g, d, f), d), w3=dense((g, d, f), d),
                    w2=dense((g, f, d), f, residual),
                    ws1=dense((d, fs), d), ws3=dense((d, fs), d),
                    ws2=dense((fs, d), fs, residual))

    every = layers(cfg)
    leading, period = layout(cfg)
    keys = jax.random.split(key, len(every) + 2)
    blocks = [block(keys[i], mixer, mlp)
              for i, (mixer, mlp) in enumerate(every)]
    scanned = blocks[leading:]
    return {
        "embed": jax.random.normal(keys[-2], (v, d), jnp.float32),
        "head": jax.random.normal(keys[-1], (v, d), jnp.float32) * 0.02,
        "ln_f": jnp.ones((d,), jnp.float32),
        "leading": tuple(blocks[:leading]),
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


def _delta_rule(q, k, v, g, beta):
    """The recurrence of the module docstring, a token at a time:
    ``q``, ``k``, ``g`` ``(T, H, d)``, ``v`` ``(T, H, d_v)``, ``beta``
    ``(T, H)``; returns ``o`` ``(T, H, d_v)``."""
    t, h, d = k.shape

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[..., None] * S                # Diag(a_t) S
        # (I - b k k^T) S + b k v^T = S + k (b (v - S^T k))^T
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[..., None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    @jax.checkpoint
    def block(S, xs):
        return lax.scan(token, S, xs)

    n = min(STATE_BLOCK, t)
    xs = tuple(x.reshape(t // n, n, *x.shape[1:])
               for x in (q, k, v, g, beta))
    _, o = lax.scan(block, jnp.zeros((h, d, v.shape[-1]), jnp.float32), xs)
    return o.reshape(t, h, -1)


def _kda(cfg, rnd, h, blk):
    t, d = h.shape
    eps = cfg["rms_norm_eps"]
    heads, dh = blk["wqkv"].shape[2:]
    taps = blk["conv"].shape[-1]
    x = rnd(_rms_norm(h, blk["ln1"], eps))
    qkv = (x @ rnd(blk["wqkv"].reshape(d, -1))).reshape(t, 3, heads, dh)
    padded = jnp.pad(rnd(qkv), ((taps - 1, 0), (0, 0), (0, 0), (0, 0)))
    w = rnd(blk["conv"])
    qkv = jax.nn.silu(sum(padded[j:j + t] * w[..., j] for j in range(taps)))

    def unit(y):
        return y * lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True)
                             + cfg["l2_norm_eps"])

    q, k, v = unit(qkv[:, 0]) * dh ** -.5, unit(qkv[:, 1]), qkv[:, 2]
    low = rnd(x @ rnd(blk["wf_a"])) @ rnd(blk["wf_b"].reshape(dh, -1))
    g = -jnp.exp(blk["a_log"])[:, None] * jax.nn.softplus(
        low.reshape(t, heads, dh) + blk["dt_bias"])
    beta = jax.nn.sigmoid(x @ rnd(blk["wbeta"]))
    o = _delta_rule(rnd(q), rnd(k), rnd(v), g, beta)
    gate = jax.nn.sigmoid(
        rnd(x @ rnd(blk["wg_a"])) @ rnd(blk["wg_b"].reshape(dh, -1)))
    o = _rms_norm(o, blk["o_norm"], eps) * gate.reshape(t, heads, dh)
    return h + rnd(o.reshape(t, -1)) @ rnd(blk["wo"].reshape(-1, d))


def _mla(cfg, rnd, h, blk):
    t, d = h.shape
    eps = cfg["rms_norm_eps"]
    rank, dn = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    heads, width = blk["wq"].shape[1:]
    x = rnd(_rms_norm(h, blk["ln1"], eps))
    q = rnd((x @ rnd(blk["wq"].reshape(d, -1))).reshape(t, heads, width))
    down = x @ rnd(blk["wkva"])
    latent = rnd(_rms_norm(down[:, :rank], blk["kv_norm"], eps))
    up = (latent @ rnd(blk["wkvb"].reshape(rank, -1))).reshape(
        t, heads, -1)
    k = rnd(jnp.concatenate([up[..., :dn], jnp.broadcast_to(
        down[:, None, rank:], (t, heads, width - dn))], axis=-1))
    v = rnd(up[..., dn:])
    block = min(Q_BLOCK, t)

    @jax.checkpoint
    def rows(qi, start, ki, vi):
        """A block of queries from position ``start`` against the keys
        ``ki`` from position 0."""
        s = jnp.einsum("qhd,khd->hqk", qi, ki) * width ** -.5
        allow = (start + jnp.arange(block))[:, None] \
            >= jnp.arange(ki.shape[0])[None, :]
        p = rnd(jax.nn.softmax(jnp.where(allow, s, -jnp.inf), axis=-1))
        return jnp.einsum("hqk,khd->qhd", p, vi)

    # a block meets the keys up to the end of its part of the sequence
    # (PARTS parts: few shapes to compile, a third less to score than
    # all keys for every block)
    qb = q.reshape(t // block, block, heads, width)
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


_MIXERS = {"kda": _kda, "mla": _mla}


def route(cfg, x, router, bias):
    """``(s, top_i, gates)`` of the sigmoid router, in float32 (the
    lower-precision control does not round it either): every expert's
    own score; the k experts with the largest ``s + bias``; the winners'
    ``s`` normalised over the k chosen times ``routed_scaling_factor``."""
    s = jax.nn.sigmoid(x @ router)
    _, top_i = lax.top_k(s + lax.stop_gradient(bias),
                         cfg["num_experts_per_token"])
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    return s, top_i, cfg["routed_scaling_factor"] * top_s / jnp.sum(
        top_s, axis=-1, keepdims=True)


def _experts(cfg, rnd, h, blk):
    """``(h + y, f, P)``: the held experts' part of the layer plus the
    shared expert, the share of tokens whose first choice each expert
    is, and the mean of each expert's score over the scores' sum."""
    e, first = cfg["router_experts"], cfg["experts_first"]
    x = _rms_norm(h, blk["ln2"], cfg["rms_norm_eps"])
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
    y = y + _swiglu(rnd, x, blk["ws1"], blk["ws3"], blk["ws2"])
    return (h + y, jnp.mean(jax.nn.one_hot(top_i[:, 0], e), axis=0),
            jnp.mean(s / jnp.sum(s, axis=-1, keepdims=True), axis=0))


def _dense(cfg, rnd, h, blk):
    x = rnd(_rms_norm(h, blk["ln2"], cfg["rms_norm_eps"]))
    return h + _swiglu(rnd, x, blk["w1"], blk["w3"], blk["w2"])


def batch_loss(cfg, precision, params, tokens, targets):
    """Mean cross-entropy over the vocabulary's slice +
    ``aux_loss_weight`` x sum over the sparse layers of E x sum_e f_e
    P_e, with f and P means over all the batch's tokens (f, a count of
    first choices, carries no gradient).  Layer by layer, and within a
    layer one sequence at a time under ``jax.checkpoint``."""
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
        x = rnd(_rms_norm(h, params["ln_f"], cfg["rms_norm_eps"]))
        logp = jax.nn.log_softmax(x @ rnd(params["head"]).T, axis=-1)
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
            x = _rms_norm(h, blk["ln2"], cfg["rms_norm_eps"])
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
