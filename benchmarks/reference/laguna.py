"""Laguna (poolside/Laguna-XS.2, ``model_type: laguna``): a pre-norm
decoder whose layers differ by kind AND by shape -- ``full_attention``
layers (48 query heads, YaRN rotary over half of each head) and
``sliding_attention`` layers (64 query heads, window, plain rotary over
the whole head), both over 8 key-value heads, each with a sigmoid gate a
query head between the attention core and the output projection -- and
by MLP: layer 0 a dense SwiGLU, every later layer a sparse expert layer
(sigmoid router over all experts, the k largest normalised and scaled,
SwiGLU experts) beside one shared expert that every token meets;
separate embedding and output matrices.  Its loss and AdamW steps in
plain float32 ``jax.numpy``: the yardstick the Laguna cell's first steps
are held to.

The chip's share.  The configuration states how many experts are held
here (``num_experts`` consecutive ones from ``experts_first``, of the
``router_experts`` the router scores) and how many rows of the
vocabulary (``vocabulary``).  The router keeps its published width and
its experts per token, the gates are normalised over all k chosen, and
the routed sum runs over the chosen experts that are held: what the
absent ones would have added is left out, and that partial result goes
on to the next layer.  The shared expert, the dense layer, the router
and attention are whole.  Here every held expert is computed for every
token and masked by the routing: no sort, no grouped product.

Departures from the published model, each under ``assumed`` in the
configuration's file: the gate is one sigmoid scalar a head from the
layer's normed input; YaRN's ramp is computed over the rotated
dimensions; the router's score is a sigmoid with no selection bias; no
gate on the shared expert; no per-head q/k norm and no dropout; the
balancing loss is ``aux_loss_weight`` x E x sum_e f_e P_e over all the
router's columns with f from the first choice and P the mean of
s / sum(s).

The parameters are laid out as the program lays them out, less its
leading pipeline axis (the driver checks shape for shape): the layers
that lead (``mlp_layer_types`` "dense") one block each under
``leading``, the others one stack over the periods for each position of
the repeating pattern under ``blocks``, each at its own head count.

The batch goes through layer by layer, and through a layer one sequence
at a time (``lax.map``), each sequence's layer under ``jax.checkpoint``
and attention by blocks of queries: at 8,192 positions one layer's
float32 scores are 12.9 GB.  So the sum of a parameter's gradient over
the sequences is formed a layer at a time, and the whole model's
gradient exists once beside the optimizer's state: taken one sequence at
a time through all layers it would exist twice (the running sum and the
sequence's own), and 692 M parameters' state and two such copies do not
fit the chip beside the activations (a compile for a described v5e, PR
30: 16.36 of 15.75 GiB).
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


def layers(cfg):
    """``[(attention kind, query heads, mlp kind)]`` of the layers run."""
    n = cfg["num_hidden_layers"]
    return list(zip(cfg["layer_types"][:n],
                    cfg["num_attention_heads_per_layer"][:n],
                    cfg["mlp_layer_types"][:n]))


def layout(cfg):
    """``(leading, period)``: how many layers lead (the dense ones at
    the start) and the shortest pattern the others repeat."""
    every = layers(cfg)
    leading = next(i for i, layer in enumerate(every)
                   if layer[2] != "dense")
    rest = every[leading:]
    for n in range(1, len(rest) + 1):
        if len(rest) % n == 0 and all(
                layer == rest[i % n] for i, layer in enumerate(rest)):
            return leading, rest[:n]


def init(key, cfg):
    """Seeded weights in the program's layout (module docstring):
    N(0, 1) embedding rows, an N(0, 0.02) output matrix, N(0, 1/fan_in)
    matrices with the projections that write to the residual stream
    (``wo``, ``w2``, ``ws2``) scaled by 1/sqrt(2 x the published depth)
    as in Megatron's scaled init, unit norm scales: weights under which
    the router's input is a token's own and the rows spread evenly over
    the experts (the Mellum file's ``assumed.init`` says why)."""
    d, hkv, dh = (cfg["hidden_size"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    e, g, v = cfg["router_experts"], cfg["num_experts"], cfg["vocabulary"]
    f, fs = cfg["moe_intermediate_size"], \
        cfg["shared_expert_intermediate_size"]
    depth = cfg.get("published", cfg)["num_hidden_layers"]
    residual = (2 * depth) ** -.5

    def block(key, heads, mlp):
        k = iter(jax.random.split(key, 13))

        def dense(shape, fan_in, scale=1.0):
            return jax.random.normal(next(k), shape, jnp.float32) \
                * fan_in ** -.5 * scale

        out = {"ln1": jnp.ones((d,), jnp.float32),
               "ln2": jnp.ones((d,), jnp.float32),
               "wq": dense((d, heads, dh), d),
               "wkv": dense((d, 2, hkv, dh), d),
               "wg": dense((d, heads), d),
               "wo": dense((heads, dh, d), heads * dh, residual)}
        if mlp == "dense":
            width = cfg["intermediate_size"]
            return dict(out, w1=dense((d, width), d),
                        w3=dense((d, width), d),
                        w2=dense((width, d), width, residual))
        return dict(out, router=dense((d, e), d),
                    w1=dense((g, d, f), d), w3=dense((g, d, f), d),
                    w2=dense((g, f, d), f, residual),
                    ws1=dense((d, fs), d), ws3=dense((d, fs), d),
                    ws2=dense((fs, d), fs, residual))

    every = layers(cfg)
    leading, period = layout(cfg)
    keys = jax.random.split(key, len(every) + 2)
    blocks = [block(keys[i], heads, mlp)
              for i, (_, heads, mlp) in enumerate(every)]
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


def inv_freq(cfg, kind):
    """The rotary frequencies of a layer kind, one for each pair of the
    R = ``partial_rotary_factor`` x head_dim dimensions that are
    rotated, and the factor its cos and sin carry.  ``default``:
    theta^(-2i/R).  ``yarn``: dimension i keeps its frequency where it
    turns more than beta_fast times within the original context, has it
    divided by ``factor`` where it turns less than beta_slow times, and
    blends linearly in between; the ramp is computed over the R rotated
    dimensions."""
    rope = cfg["rope_parameters"][kind]
    r = int(cfg["head_dim"] * rope["partial_rotary_factor"])
    theta = rope["rope_theta"]
    base = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    if rope["rope_type"] == "default":
        return base, 1.0
    if rope["rope_type"] != "yarn":
        raise SystemExit(f"rope_type {rope['rope_type']!r}")

    def dimension(turns):
        return r * math.log(rope["original_max_position_embeddings"] / (
            turns * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(dimension(rope["beta_fast"])), 0)
    hi = min(math.ceil(dimension(rope["beta_slow"])), r - 1)
    ramp = np.clip((np.arange(r // 2) - lo) / (hi - lo), 0, 1)
    return (ramp * base / rope["factor"] + (1 - ramp) * base,
            rope["attention_factor"])


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                         + eps) * scale


def _rope(x, freqs, factor):
    """Rotate-half rotary over the first ``2 x len(freqs)`` dimensions
    of each head, the others passed through; ``x`` is ``(T, heads,
    head_dim)``."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    half = len(freqs)
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest], -1)


def _swiglu(rnd, x, w1, w3, w2):
    return rnd(jax.nn.silu(x @ rnd(w1)) * (x @ rnd(w3))) @ rnd(w2)


def _attention(cfg, rnd, h, blk, kind):
    t, d = h.shape
    n_kv, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    n_heads = blk["wq"].shape[1]
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    freqs, factor = inv_freq(cfg, kind)
    x = rnd(_rms_norm(h, blk["ln1"], cfg["rms_norm_eps"]))
    q = (x @ rnd(blk["wq"].reshape(d, -1))).reshape(t, n_heads, dh)
    kv = (x @ rnd(blk["wkv"].reshape(d, -1))).reshape(t, 2, n_kv, dh)
    gate = jax.nn.sigmoid(x @ rnd(blk["wg"]))            # (T, heads)
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
    o = o.reshape(t, n_heads, dh) * gate[..., None]
    return h + rnd(o.reshape(t, -1)) @ rnd(blk["wo"].reshape(-1, d))


def route(cfg, x, router):
    """``(s, top_i, gates)`` of the sigmoid router, in float32 (the
    lower-precision control does not round it either, as the program's
    is float32 whatever its compute dtype): every expert's own score,
    the k largest, and their scores normalised over the k chosen times
    ``moe_routed_scaling_factor``."""
    s = jax.nn.sigmoid(x @ router)
    top_s, top_i = lax.top_k(s, cfg["num_experts_per_tok"])
    return s, top_i, cfg["moe_routed_scaling_factor"] * top_s / jnp.sum(
        top_s, axis=-1, keepdims=True)


def _experts(cfg, rnd, h, blk):
    """``(h + y, f, P)``: the held experts' part of the layer plus the
    shared expert, the share of tokens whose first choice each expert
    is, and the mean of each expert's score over the scores' sum."""
    e, first = cfg["router_experts"], cfg["experts_first"]
    x = _rms_norm(h, blk["ln2"], cfg["rms_norm_eps"])
    s, top_i, gates = route(cfg, x, blk["router"])
    # (T, E): the gate of each chosen expert, 0 where not chosen
    gate_of = jnp.sum(jax.nn.one_hot(top_i, e) * gates[..., None], axis=1)
    x = rnd(x)

    # the checkpoint is around an expert's own term and not around the
    # running sum: the sum is linear in what it has so far, and a
    # checkpoint that took it in would keep a (T, d) copy an expert
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
    """Mean cross-entropy + ``aux_loss_weight`` x sum over the sparse
    layers of E x sum_e f_e P_e, with f and P means over all the batch's
    tokens (f, a count of first choices, is piecewise constant and
    carries no gradient).  Layer by layer, and within a layer one
    sequence at a time under ``jax.checkpoint`` (module docstring)."""
    rnd = rounder(precision)
    e = cfg["router_experts"]
    h = params["embed"][tokens]                             # (B, T, d)
    aux = 0.0
    for blk, (kind, _, mlp) in zip(layer_blocks(cfg, params), layers(cfg)):
        if mlp == "dense":
            h = lax.map(jax.checkpoint(lambda h, blk=blk, kind=kind: _dense(
                cfg, rnd, _attention(cfg, rnd, h, blk, kind), blk)), h)
            continue
        h, f, p = lax.map(jax.checkpoint(
            lambda h, blk=blk, kind=kind: _experts(
                cfg, rnd, _attention(cfg, rnd, h, blk, kind), blk)), h)
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
    chose in every sparse layer, in float32 (for the share of choices on
    which program and reference differ)."""
    exact = rounder("float32")

    def one(params, tok):
        h, out = params["embed"][tok], []
        for blk, (kind, _, mlp) in zip(
                layer_blocks(cfg, params), layers(cfg)):
            h = _attention(cfg, exact, h, blk, kind)
            if mlp == "dense":
                h = _dense(cfg, exact, h, blk)
                continue
            x = _rms_norm(h, blk["ln2"], cfg["rms_norm_eps"])
            out.append(route(cfg, x, blk["router"])[1])
            h = _experts(cfg, exact, h, blk)[0]
        return jnp.stack(out, axis=1)

    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: lax.map(partial(one, p), t))(
            params, tokens)


def adamw_step(cfg, precision, params, mu, nu, count, tokens, targets):
    """One step of AdamW, its rule written out: ``(params, mu, nu,
    count, loss, the norm of each leaf's gradient)``."""
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
    step = jax.jit(partial(adamw_step, cfg, precision),
                   donate_argnums=(0, 1, 2))
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
