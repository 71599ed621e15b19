"""MiniLM — the serving engine's reference decode backend.

A compact MQA causal LM (pre-LN residual blocks, learned positions,
one shared KV head) whose step/prefill functions follow the engine's
decode-adapter protocol.  It exists for two reasons:

- **A plain oracle.**  MiniLM is written with plain ``jnp`` — no
  custom VJPs, no axis-name queries, no mesh — so the engine's parity
  suite has a small backend whose every step can be read off the
  page.  The flagship path rides the same engine through
  :class:`~chainermn_tpu.serving.TransformerAdapter`.
- **Protocol example.**  The adapter surface is exactly what a decode
  backend owes the engine: ``make_cache``/``prefill``/``step`` with
  the per-row position-origin (``pos_offset``) contract, plus the
  sharding specs the engine's programs cross the jit boundary with.

Position/masking contract (shared with ``models.decoding``): a row
whose origin is ``offset`` holds its token number ``i`` at buffer/cache
position ``offset + i``; queries may only attend cache positions in
``[offset, t]``; learned-position rows index the table at
``position - offset``.  All methods are pure and equally callable
inside a ``shard_map`` body (the engine) or on plain arrays (the
tests' independent oracle).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from .kv_blocks import POS_AXIS

__all__ = ["MiniLMConfig", "init_minilm", "MiniLMAdapter"]

_NEG = -1e30   # finite attention mask (same convention as ring_attention)


@dataclasses.dataclass(frozen=True)
class MiniLMConfig:
    vocab_size: int = 256
    d_model: int = 64
    n_heads: int = 4
    d_head: int = 16
    d_ff: int = 128
    n_layers: int = 2
    max_pos: int = 512     # learned position table length (>= P + N)

    def __post_init__(self):
        if min(self.vocab_size, self.d_model, self.n_heads, self.d_head,
               self.d_ff, self.n_layers, self.max_pos) < 1:
            raise ValueError(f"all MiniLMConfig sizes must be >= 1: {self}")


def init_minilm(key, cfg: MiniLMConfig):
    """Random fp32 parameters; per-layer leaves stacked on axis 0."""
    k = jax.random.split(key, 8)
    d, hq, dh, f, layers = (cfg.d_model, cfg.n_heads, cfg.d_head,
                            cfg.d_ff, cfg.n_layers)

    def w(key, *shape):
        return jax.random.normal(key, shape, jnp.float32) \
            / np.sqrt(shape[-2] if len(shape) > 1 else 1.0)

    return {
        "embed": w(k[0], cfg.vocab_size, d) * np.sqrt(d),
        "pos": w(k[1], cfg.max_pos, d) * 0.1,
        "ln_f": jnp.ones((d,), jnp.float32),
        "blocks": {
            "ln1": jnp.ones((layers, d), jnp.float32),
            "wq": w(k[2], layers, d, hq * dh),
            "wk": w(k[3], layers, d, dh),
            "wv": w(k[4], layers, d, dh),
            "wo": w(k[5], layers, hq * dh, d),
            "ln2": jnp.ones((layers, d), jnp.float32),
            "w1": w(k[6], layers, d, f),
            "w2": w(k[7], layers, f, d),
        },
    }


def _rms(x, g):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * g


class MiniLMAdapter:
    """Decode-adapter protocol implementation for :func:`init_minilm`
    parameters.  Parameters ride replicated (``P()``); the cache and
    every per-slot array shard over the batch axes.  The mesh may
    carry model/pipe/seq axes only at size 1 (MiniLM does not split
    its own math)."""

    batch_axes = ("data", "expert")

    def __init__(self, mesh_cfg, cfg: MiniLMConfig):
        shape = mesh_cfg.mesh.shape
        bad = {a: shape[a] for a in ("model", "pipe", "seq")
               if shape.get(a, 1) != 1}
        if bad:
            raise ValueError(
                f"MiniLMAdapter shards only the batch axes "
                f"{self.batch_axes}; mesh has non-unit axes {bad}")
        self.mesh_cfg = mesh_cfg
        self.cfg = cfg

    # -- sharding surface ------------------------------------------------ #

    def param_specs(self):
        return P()     # pytree prefix: every leaf replicated

    def cache_specs(self):
        bs = P(None, self.batch_axes)   # (L, rows, kv_len, d_head)
        return (bs, bs)

    # -- cache ----------------------------------------------------------- #

    def make_cache(self, rows: int, kv_len: int, batch_varying=True):
        """Zero MQA cache pair ``(L, rows, kv_len, d_head)`` (local
        shapes; rows axis 1, positions axis 2 — the kv_blocks layout
        contract).  ``batch_varying`` exists for protocol parity with
        the transformer adapter (MiniLM carries no vma types)."""
        del batch_varying
        shape = (self.cfg.n_layers, rows, kv_len, self.cfg.d_head)
        return (jnp.zeros(shape, jnp.float32),
                jnp.zeros(shape, jnp.float32))

    # -- forward --------------------------------------------------------- #

    def _positions(self, params, idx):
        return jnp.take(params["pos"],
                        jnp.clip(idx, 0, self.cfg.max_pos - 1), axis=0)

    def step(self, params, caches, tok, t, pos_offset):
        """One token for every row: ``tok`` (B,) int32 at global
        position ``t`` (scalar), per-row origins ``pos_offset`` (B,).
        Returns ``(logits (B, V) fp32, caches)``."""
        cfg = self.cfg
        ck, cv = caches
        B = tok.shape[0]
        T = ck.shape[POS_AXIS]
        h = jnp.take(params["embed"], tok, axis=0) \
            + self._positions(params, t - pos_offset)
        blk = params["blocks"]
        kpos = jnp.arange(T)
        allow = (kpos[None, :] <= t) \
            & (kpos[None, :] >= pos_offset[:, None])         # (B, T)
        for layer in range(cfg.n_layers):
            x = _rms(h, blk["ln1"][layer])
            q = (x @ blk["wq"][layer]).reshape(B, cfg.n_heads, cfg.d_head)
            k = x @ blk["wk"][layer]                         # (B, dh)
            v = x @ blk["wv"][layer]
            ck = lax.dynamic_update_slice(
                ck, k[None, :, None, :], (layer, 0, t, 0))
            cv = lax.dynamic_update_slice(
                cv, v[None, :, None, :], (layer, 0, t, 0))
            s = jnp.einsum("bhd,btd->bht", q, ck[layer]) \
                * (cfg.d_head ** -0.5)
            s = jnp.where(allow[:, None, :], s, _NEG)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bht,btd->bhd", p, cv[layer])
            h = h + o.reshape(B, -1) @ blk["wo"][layer]
            x2 = _rms(h, blk["ln2"][layer])
            h = h + jax.nn.relu(x2 @ blk["w1"][layer]) @ blk["w2"][layer]
        logits = _rms(h, params["ln_f"]) @ params["embed"].T
        return logits.astype(jnp.float32), (ck, cv)

    def step_ragged(self, params, caches, tok, t):
        """One token for every row at PER-ROW positions: ``tok`` (B,)
        int32, ``t`` (B,) int32 — row ``b``'s token sits at cache
        position ``t[b]``.  Rows are origin-0 (ragged-round engine
        contract: token ``i`` lives at lane position ``i``), so the
        attention window is simply ``kpos <= t[b]`` and the learned
        position IS ``t[b]``.  Returns ``(logits (B, V) fp32, caches)``.

        The K/V write is a per-row scatter (rows advance raggedly, so
        no single ``dynamic_update_slice`` start exists); out-of-range
        positions drop, and a re-step of an already-written position
        overwrites it with identical values — the property the engine's
        frozen/done rows rely on."""
        cfg = self.cfg
        ck, cv = caches
        B = tok.shape[0]
        T = ck.shape[POS_AXIS]
        rows = jnp.arange(B)
        h = jnp.take(params["embed"], tok, axis=0) \
            + self._positions(params, t)
        blk = params["blocks"]
        kpos = jnp.arange(T)
        allow = kpos[None, :] <= t[:, None]                  # (B, T)
        for layer in range(cfg.n_layers):
            x = _rms(h, blk["ln1"][layer])
            q = (x @ blk["wq"][layer]).reshape(B, cfg.n_heads, cfg.d_head)
            k = x @ blk["wk"][layer]                         # (B, dh)
            v = x @ blk["wv"][layer]
            ck = ck.at[layer, rows, t].set(k, mode="drop")
            cv = cv.at[layer, rows, t].set(v, mode="drop")
            s = jnp.einsum("bhd,btd->bht", q, ck[layer]) \
                * (cfg.d_head ** -0.5)
            s = jnp.where(allow[:, None, :], s, _NEG)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bht,btd->bhd", p, cv[layer])
            h = h + o.reshape(B, -1) @ blk["wo"][layer]
            x2 = _rms(h, blk["ln2"][layer])
            h = h + jax.nn.relu(x2 @ blk["w1"][layer]) @ blk["w2"][layer]
        logits = _rms(h, params["ln_f"]) @ params["embed"].T
        return logits.astype(jnp.float32), (ck, cv)

    def verify_ragged(self, params, caches, tok_chunk, t,
                      with_logits=True):
        """Chunk step at PER-ROW start positions: ``tok_chunk`` (B, C)
        with row ``b``'s chunk occupying positions ``[t[b], t[b]+C)``
        (origin-0 rows — the ragged-round contract).  Same semantics
        as :meth:`verify` otherwise: each chunk token writes its K/V
        and attends the full cache through its own position, so one
        weights read verifies C draft positions per row even when the
        rows' clocks disagree.  Returns ``(logits (B, C, V) | None,
        caches)``."""
        cfg = self.cfg
        ck, cv = caches
        B, C = tok_chunk.shape
        T = ck.shape[POS_AXIS]
        rows = jnp.arange(B)
        j = jnp.arange(C)
        pos = t[:, None] + j[None, :]                        # (B, C)
        h = jnp.take(params["embed"], tok_chunk, axis=0) \
            + self._positions(params, pos)
        blk = params["blocks"]
        kpos = jnp.arange(T)
        allow = kpos[None, None, :] <= pos[:, :, None]       # (B, C, T)
        for layer in range(cfg.n_layers):
            x = _rms(h, blk["ln1"][layer])
            q = (x @ blk["wq"][layer]).reshape(
                B, C, cfg.n_heads, cfg.d_head)
            k = x @ blk["wk"][layer]                     # (B, C, dh)
            v = x @ blk["wv"][layer]
            ck = ck.at[layer, rows[:, None], pos].set(k, mode="drop")
            cv = cv.at[layer, rows[:, None], pos].set(v, mode="drop")
            s = jnp.einsum("bchd,btd->bhct", q, ck[layer]) \
                * (cfg.d_head ** -0.5)
            s = jnp.where(allow[:, None], s, _NEG)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhct,btd->bchd", p, cv[layer])
            h = h + o.reshape(B, C, -1) @ blk["wo"][layer]
            x2 = _rms(h, blk["ln2"][layer])
            h = h + jax.nn.relu(x2 @ blk["w1"][layer]) @ blk["w2"][layer]
        if not with_logits:
            return None, (ck, cv)
        logits = _rms(h, params["ln_f"]) @ params["embed"].T
        return logits.astype(jnp.float32), (ck, cv)

    def verify(self, params, caches, tok_chunk, t, pos_offset,
               with_logits=True):
        """Chunk step — the speculative VERIFY pass (and, without
        logits, the prefix-sharing suffix prefill): process
        ``tok_chunk`` (B, C) at global positions ``[t, t+C)``, writing
        each token's K/V and attending the FULL cache with the same
        ``[offset, position]`` validity window as :meth:`step`, so
        position ``t+i``'s logits condition on the cache through
        ``t-1`` plus chunk tokens ``<= i`` — one weights read verifies
        C draft positions.  Returns ``(logits (B, C, V) | None,
        caches)``.

        The key axis is the full cache buffer in both this and
        :meth:`step` (masked positions underflow to exact zero), which
        is what keeps chunk-verified logits token-compatible with the
        step-by-step decode they stand in for."""
        cfg = self.cfg
        ck, cv = caches
        B, C = tok_chunk.shape
        T = ck.shape[POS_AXIS]
        j = jnp.arange(C)
        h = jnp.take(params["embed"], tok_chunk, axis=0) \
            + self._positions(params,
                              t + j[None, :] - pos_offset[:, None])
        blk = params["blocks"]
        kpos = jnp.arange(T)
        allow = (kpos[None, None, :] <= (t + j)[None, :, None]) \
            & (kpos[None, None, :] >= pos_offset[:, None, None])
        for layer in range(cfg.n_layers):
            x = _rms(h, blk["ln1"][layer])
            q = (x @ blk["wq"][layer]).reshape(
                B, C, cfg.n_heads, cfg.d_head)
            k = x @ blk["wk"][layer]                     # (B, C, dh)
            v = x @ blk["wv"][layer]
            ck = lax.dynamic_update_slice(
                ck, k[None], (layer, 0, t, 0))
            cv = lax.dynamic_update_slice(
                cv, v[None], (layer, 0, t, 0))
            s = jnp.einsum("bchd,btd->bhct", q, ck[layer]) \
                * (cfg.d_head ** -0.5)
            s = jnp.where(allow[:, None], s, _NEG)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhct,btd->bchd", p, cv[layer])
            h = h + o.reshape(B, C, -1) @ blk["wo"][layer]
            x2 = _rms(h, blk["ln2"][layer])
            h = h + jax.nn.relu(x2 @ blk["w1"][layer]) @ blk["w2"][layer]
        if not with_logits:
            return None, (ck, cv)
        logits = _rms(h, params["ln_f"]) @ params["embed"].T
        return logits.astype(jnp.float32), (ck, cv)

    def prefill(self, params, caches, toks, pos_offset):
        """Fill cache positions ``[0, Tq)`` from a ``(B, Tq)`` chunk in
        one causal pass (no logits — the cache fill is the product).
        Rows are RIGHT-aligned: chunk position ``j`` holds row token
        ``j - pos_offset[b]`` (pad positions write garbage K/V that the
        validity mask keeps unread — the ``models.decoding`` padded
        contract)."""
        cfg = self.cfg
        ck, cv = caches
        B, Tq = toks.shape
        j = jnp.arange(Tq)
        h = jnp.take(params["embed"], toks, axis=0) \
            + self._positions(params, j[None, :] - pos_offset[:, None])
        blk = params["blocks"]
        allow = (j[None, None, :] <= j[None, :, None]) \
            & (j[None, None, :] >= pos_offset[:, None, None])  # (B,Tq,Tq)
        for layer in range(cfg.n_layers):
            x = _rms(h, blk["ln1"][layer])
            q = (x @ blk["wq"][layer]).reshape(
                B, Tq, cfg.n_heads, cfg.d_head)
            k = x @ blk["wk"][layer]                         # (B, Tq, dh)
            v = x @ blk["wv"][layer]
            ck = lax.dynamic_update_slice(
                ck, k[None, :, :, :], (layer, 0, 0, 0))
            cv = lax.dynamic_update_slice(
                cv, v[None, :, :, :], (layer, 0, 0, 0))
            s = jnp.einsum("bihd,bjd->bhij", q, k) * (cfg.d_head ** -0.5)
            s = jnp.where(allow[:, None], s, _NEG)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhij,bjd->bihd", p, v)
            h = h + o.reshape(B, Tq, -1) @ blk["wo"][layer]
            x2 = _rms(h, blk["ln2"][layer])
            h = h + jax.nn.relu(x2 @ blk["w1"][layer]) @ blk["w2"][layer]
        return (ck, cv)
