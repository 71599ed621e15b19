"""Autoregressive decoding with a KV cache for the flagship transformer.

Beyond-reference breadth: the reference's only generation path was the
seq2seq example's greedy LSTM translate loop (reference:
``examples/seq2seq/seq2seq.py`` ``translate``, unverified — mount empty,
see SURVEY.md).  This is the transformer equivalent, TPU-first:

- ONE jitted program: prefill + generate is a single ``lax.scan`` over
  time steps (no per-token Python dispatch, static shapes throughout —
  the token buffer and cache are ``max_len``-sized from the start);
- the KV cache is stored at the model's **shared-head width** (GQA/MQA:
  ``n_kv_heads``, not ``n_heads``) — exactly the H/Hkv memory saving
  that motivates GQA at inference; the grouped-einsum attention cores
  (:func:`...ring_attention._qk_scores`) read it in place;
- composes with DP (batch over ``data``), TP (heads over ``model``),
  PP (layers + KV cache stage-sharded over ``pipe``; see
  :func:`_decode_step` — a model too big for one chip's HBM decodes at
  ~single-chip per-token HBM cost), and SP (the KV cache's LENGTH dim
  blocked over ``seq``; see :func:`_decode_block` — a context whose
  cache exceeds one chip's HBM decodes with an R× cache budget at one
  pmax+psum of token-sized partials per step).

Greedy (``temperature=0``) or temperature sampling.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from chainermn_tpu.parallel._compat import pcast
from chainermn_tpu.parallel.ring_attention import (
    _NEG,
    _pv_mix,
    _qk_scores,
    local_attention,
)
from chainermn_tpu.parallel.tensor import (
    column_parallel_dense,
    row_parallel_dense,
)

from .transformer import (
    TransformerConfig,
    _all_gather_invariant,
    _check_mesh,
    _rms_norm,
    _vp_embed_lookup,
    apply_rope,
    param_specs,
)

__all__ = ["make_generate_fn", "make_beam_search_fn",
           "make_speculative_generate_fn", "make_lookup_generate_fn"]


def _vary(x, *axes):
    """Mark ``x`` varying over ``axes`` (no-op for already-varying) —
    block params are pipe-sharded even at pipe size 1, so everything they
    touch must carry the pipe axis in its vma type."""
    return pcast(x, axes, to="varying")


def _dense_q(dense, x, blk, name, cd):
    """``dense(x, blk[name])`` with optional weight-only int8: the int8
    tensor is only touched by a ``convert`` (which XLA fuses into the
    dot's operand load — the HBM read stays int8-sized) and the
    per-output-channel scale is applied to the dot OUTPUT (exact for
    scales constant along the contraction)."""
    from .quantization import _MOE_OVERRIDE, base_layout

    w = blk[name]
    # contraction layout comes from quantization's declaration: axis-0
    # contraction reshapes to (in, out), leading-axes contraction (wo)
    # to (..., out).  MoE-overridden names never reach this path (they
    # flow through expert_fn) — keep it that way.
    assert name not in _MOE_OVERRIDE or w.ndim == 2, \
        f"{name}: MoE-layout weight routed through _dense_q"
    flat_in = base_layout(False)[name][1] == (0,)
    w2d = w.reshape(w.shape[0], -1) if flat_in else \
        w.reshape(-1, w.shape[-1])
    y = dense(x, w2d.astype(cd))
    scale = blk.get(name + "_scale")
    if scale is not None:
        y = y * scale.reshape(-1).astype(cd)
    return y


def _decode_block(cfg: TransformerConfig, h, blk, caches, pos,
                  write_mask=None, chunk_attends_cache=False,
                  pos_offset=None):
    """One block for a CHUNK of new tokens.  ``h``: (B, Tq, D) — Tq = 1
    in the generation loop, Tq = prompt length in batched prefill;
    ``caches``: this layer's ``(ck, cv)`` pair of (B, kv_len_local,
    Hkv_local, Dh) buffers — or ``(ck, cv, ck_s, cv_s)`` with
    ``kv_cache_dtype="int8"``, where the values are int8 and the
    scales carry a trailing singleton so every write below treats
    values and scales identically; ``pos``: scalar GLOBAL position of
    the chunk's FIRST token (Tq > 1 requires ``pos == 0`` — the
    prefill contract), or a ``(B,)`` vector of PER-ROW first positions
    (the serving engine's ragged rounds: origin-0 rows, each on its
    own clock — K/V then land by per-row scatter and every row masks
    the cache at its own position).  ``write_mask`` (scalar bool) gates
    the cache update — pipe-parallel phases where this device does NOT own the
    running stage must leave their cache untouched, and masking the
    written slice here is O(written) instead of the O(cache) select a
    whole-buffer ``where`` would cost per phase.

    Sequence-parallel KV (``seq`` axis size R > 1): the cache's length
    dim holds only this member's max_len/R BLOCK of positions (member r
    owns [r·Tl, (r+1)·Tl)) — R× KV capacity for contexts whose cache
    exceeds one chip's HBM.  New K/V land on the owning member only;
    attention becomes each member's partial scores over its block
    merged by a max/sum-exp reduction over the axis (the psum twin of
    ring attention's log-space merge) — per chunk that is one pmax +
    one psum of query-sized partials, NOT a cache-sized gather.
    Returns (h, caches)."""
    cd = cfg.compute_dtype
    ck, cv, *scales = caches
    ck_s, cv_s = scales if scales else (None, None)
    x = _rms_norm(h, blk["ln1"])
    B, Tq, D = x.shape
    R = lax.axis_size("seq")
    Tl = ck.shape[1]
    if "wqkv" in blk:
        Hl = blk["wqkv"].shape[2]
        qkv = _dense_q(column_parallel_dense, x, blk, "wqkv", cd)
        qkv = qkv.reshape(B, Tq, 3, Hl, cfg.d_head)
        q, k_new, v_new = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    else:
        Hl = blk["wq"].shape[1]
        Hkvl = blk["wkv"].shape[2]
        q = _dense_q(column_parallel_dense, x, blk, "wq", cd
                     ).reshape(B, Tq, Hl, cfg.d_head)
        kv = _dense_q(column_parallel_dense, x, blk, "wkv", cd
                      ).reshape(B, Tq, 2, Hkvl, cfg.d_head)
        k_new, v_new = kv[:, :, 0], kv[:, :, 1]
    ragged = jnp.ndim(pos) == 1
    if ragged and (R > 1 or pos_offset is not None):
        raise ValueError(
            "per-row positions need a seq=1 mesh and origin-0 rows "
            "(no pos_offset)")
    # (Tq,) shared by the batch, or (B, Tq) per row
    qpos = jnp.asarray(pos)[..., None] + jnp.arange(Tq)
    if cfg.pos_embedding == "rope":
        if pos_offset is None:
            rpos = qpos
        else:
            # left-padded rows: slot s holds the row's token number
            # s - offset (clipped for the pad slots, whose K/V are
            # masked out of every real query's attention below)
            rpos = jnp.maximum(qpos[None, :] - pos_offset[:, None], 0)
        q = apply_rope(q, rpos, cfg.rope_theta)
        k_new = apply_rope(k_new, rpos, cfg.rope_theta)
    # the chunk's own K/V at compute precision — the prefill fast path
    # attends these directly (cache-dtype quantisation applies only to
    # what later steps READ BACK)
    k_raw, v_raw = k_new, v_new
    if ck_s is not None:
        # int8 KV: per-(token, head) absmax scale, trailing singleton
        def quant(t, sdtype):
            s = jnp.maximum(
                jnp.max(jnp.abs(t), axis=-1, keepdims=True) / 127.0,
                1e-8).astype(sdtype)
            # clip BEFORE the int8 cast: in bf16 the scale rounds below
            # the true absmax/127, so the max element's ratio can land
            # on +128 — out of int8 range, sign-flipping on wraparound
            # backends (same guard as quantize_params_int8)
            q8 = jnp.clip(jnp.round(t / s.astype(t.dtype)),
                          -127, 127).astype(jnp.int8)
            return q8, s

        k_new, k_sc = quant(k_new, ck_s.dtype)
        v_new, v_sc = quant(v_new, cv_s.dtype)
    else:
        k_new, v_new = k_new.astype(ck.dtype), v_new.astype(cv.dtype)
    if pos_offset is not None and R > 1:
        raise ValueError(
            "left-padded prompts (pos_offset) are not supported under "
            "sequence-parallel KV (seq axis > 1): shard batch/heads/"
            "layers instead")

    if Tq > 1 and R > 1 and chunk_attends_cache:
        # the blockwise write below assumes the chunk starts at global
        # position 0 (prefill); a mid-sequence chunk (speculative
        # verify) under seq-KV would land its rows in the wrong blocks
        # and silently corrupt the cache.  The speculative factory
        # rejects seq>1 up front — this local guard keeps any future
        # caller honest rather than relying on that distant check.
        raise ValueError(
            "chunked mid-sequence decode (Tq > 1 with "
            "chunk_attends_cache) is not supported under "
            "sequence-parallel KV (seq axis > 1): the blockwise cache "
            "write requires the prefill contract pos == 0")
    if Tq > 1 and R > 1:
        # blockwise prefill write (pos == 0): pad the chunk's time dim
        # to a block multiple, each member slices ITS block [r·Tl,
        # r·Tl+Tl) (start clamped for members wholly beyond the chunk —
        # their rows are masked invalid) and overwrites its whole local
        # cache block under the validity mask
        P_pad = -(-Tq // Tl) * Tl
        r = lax.axis_index("seq")
        start = jnp.minimum(r * Tl, P_pad - Tl)
        g = start + jnp.arange(Tl)                        # global rows
        valid = (start == r * Tl) & (g < Tq)              # (Tl,)
        if write_mask is not None:
            valid = valid & write_mask
        vmask = valid[None, :, None, None]

        def blk_write(cache, new):
            padded = jnp.pad(
                new, ((0, 0), (0, P_pad - Tq), (0, 0), (0, 0)))
            sl = lax.dynamic_slice(
                padded, (0, start, 0, 0), (B, Tl) + new.shape[2:])
            return jnp.where(vmask, sl, cache)

        ck, cv = blk_write(ck, k_new), blk_write(cv, v_new)
        if ck_s is not None:
            ck_s, cv_s = blk_write(ck_s, k_sc), blk_write(cv_s, v_sc)
    elif ragged:
        # rows advance raggedly, so no single dynamic_update_slice start
        # exists: per-row scatter.  Out-of-range positions drop — which
        # is also how a non-owning pipe stage leaves its cache alone
        wpos = qpos if write_mask is None \
            else jnp.where(write_mask, qpos, Tl)
        brow = jnp.arange(B)[:, None]

        def row_write(cache, new):
            return cache.at[brow, wpos].set(new, mode="drop")

        ck, cv = row_write(ck, k_new), row_write(cv, v_new)
        if ck_s is not None:
            ck_s, cv_s = row_write(ck_s, k_sc), row_write(cv_s, v_sc)
    else:
        if R > 1:
            # member pos // Tl owns this position; everyone computes
            # the same local slot index (pos % Tl is only meaningful on
            # the owner, but it is always in range, and non-owners'
            # writes are masked to a rewrite of the current value)
            seq_mine = (pos // Tl) == lax.axis_index("seq")
            write_mask = seq_mine if write_mask is None \
                else jnp.logical_and(write_mask, seq_mine)
            lpos = pos % Tl
        else:
            lpos = pos
        def slot_write(cache, new):
            if write_mask is not None:
                cur = lax.dynamic_slice(
                    cache, (0, lpos, 0, 0), new.shape)
                new = jnp.where(write_mask, new, cur)
            return lax.dynamic_update_slice(cache, new, (0, lpos, 0, 0))

        ck, cv = slot_write(ck, k_new), slot_write(cv, v_new)
        if ck_s is not None:
            ck_s, cv_s = slot_write(ck_s, k_sc), slot_write(cv_s, v_sc)
    if Tq > 1 and not chunk_attends_cache:
        # prefill (pos == 0): the chunk's own K/V — still in hand,
        # replicated — ARE the entire attendable set, so causal
        # attention runs directly on them: no max_len-sized cache read
        # (Tq × max_len masked scores would be mostly waste) and no
        # distributed merge even under seq-KV
        o = local_attention(q, k_raw.astype(cd), v_raw.astype(cd),
                            causal=True,
                            window=cfg.attention_window or None)
    else:
        # grouped attention of the queries against the (local block of
        # the) cache, masked to GLOBAL key positions <= each query's
        # position.  Tq > 1 lands here for mid-sequence chunks
        # (speculative verify): the chunk's K/V were just written, so
        # the cache holds everything each query may attend to.
        kk = ck.astype(cd) * ck_s.astype(cd) if ck_s is not None \
            else ck.astype(cd)
        vv = cv.astype(cd) * cv_s.astype(cd) if cv_s is not None \
            else cv.astype(cd)
        s = _qk_scores(q, kk) * (cfg.d_head ** -0.5)
        kpos = jnp.arange(Tl)
        if R > 1:
            kpos = kpos + lax.axis_index("seq") * Tl
        allow = kpos <= qpos[..., None]        # (Tq, Tl) | (B, Tq, Tl)
        if cfg.attention_window:
            # slot distance == per-row token distance (both ends shift
            # by the same pad offset), so the window needs no offset
            allow &= (qpos[..., None] - kpos) < cfg.attention_window
        if pos_offset is not None:
            # per-row validity: slots before the row's first real
            # token hold pad K/V — no query may attend them
            allow = allow[None] \
                & (kpos[None, None, :] >= pos_offset[:, None, None])
        if allow.ndim == 3:
            s = jnp.where(allow[:, None], s, _NEG)        # (B,H,Tq,Tl)
        else:
            s = jnp.where(allow[None, None], s, _NEG)     # (B,H,Tq,Tl)
        if R > 1:
            # stable distributed softmax: global max, then exp-sums and
            # value partials psum'd over the seq axis.  Members whose
            # whole block is beyond pos contribute exp(_NEG - m) ≈ 0.
            m = lax.pmax(s.max(axis=-1, keepdims=True), "seq")
            e = jnp.exp(s - m)
            n = lax.psum(e.sum(axis=-1, keepdims=True), "seq")
            o = lax.psum(_pv_mix(e, vv), "seq")
            o = (o / n).transpose(0, 2, 1, 3)             # (B,Tq,Hl,Dh)
        else:
            p = jax.nn.softmax(s, axis=-1)
            o = _pv_mix(p, vv).transpose(0, 2, 1, 3)
    h = h + _dense_q(row_parallel_dense, o.reshape(B, Tq, -1),
                     blk, "wo", cd)

    x = _rms_norm(h, blk["ln2"])
    if cfg.moe:
        # per-token top-k routing, same mode the checkpoint was TRAINED
        # with (a top-2 model decoded top-1 silently diverges from its
        # training forward); tiny per-step batches may clip at capacity
        # — acceptable at decode time
        from chainermn_tpu.parallel.expert import expert_parallel_moe

        def expert_fn(pp, tokens):
            # weights may be int8 (leading expert axis vmaps away, so
            # per-expert scales arrive as plain per-channel vectors)
            y = column_parallel_dense(tokens, pp["w1"].astype(cd))
            if "w1_scale" in pp:
                y = y * pp["w1_scale"].astype(cd)
            y = jax.nn.relu(y)
            out = row_parallel_dense(y, pp["w2"].astype(cd))
            if "w2_scale" in pp:
                out = out * pp["w2_scale"].astype(cd)
            return out

        expert_params = {
            k: blk[k]
            for k in ("w1", "w2", "w1_scale", "w2_scale") if k in blk}
        out, _ = expert_parallel_moe(
            x.reshape(B * Tq, D),
            blk["router"].astype(cd),
            expert_params,
            expert_fn,
            axis_name="expert",
            capacity_factor=cfg.capacity_factor,
            top_k=cfg.router_top_k,
        )
        h = h + out.reshape(B, Tq, D)
    else:
        y = jax.nn.relu(_dense_q(column_parallel_dense, x, blk, "w1", cd))
        h = h + _dense_q(row_parallel_dense, y, blk, "w2", cd)
    return h, ((ck, cv) if ck_s is None else (ck, cv, ck_s, cv_s))


def _decode_step(cfg: TransformerConfig, params, caches, tok, pos,
                 with_logits: bool = True, all_logits: bool = False,
                 chunk_attends_cache: bool = False, pos_offset=None):
    """Next-token logits for ``tok`` — (B,) in the generation loop, or
    a (B, Tq) chunk starting at ``pos`` (a scalar, or a (B,) vector of
    per-row starts — see :func:`_decode_block`) for batched prefill
    (Tq prompt tokens through ONE MXU-shaped pass instead of Tq per-token
    dispatches; ``with_logits=False`` skips the LM head entirely, since
    prefill only needs the cache filled).  Updates the
    (L_local, B, kv_len_local, Hkv_local, Dh) cache pair.

    MoE capacity note: chunked prefill routes all B·Tq prompt tokens
    through expert capacity together — the TRAINING forward's
    semantics (capacity scales with the token count routed at once) —
    whereas per-token stepping gives every position its own B-token
    slot budget.  At a finite ``capacity_factor`` the two can drop
    different tokens when routing clusters temporally; ample capacity
    makes them exact (see test_batched_prefill_matches_per_token).

    Pipe-parallel decode (``pipe`` axis size S > 1): device ``s`` holds
    ONLY its stage's layers and KV cache — S× model capacity — and the
    hidden state hands off stage→stage via ``ppermute`` inside a
    ``S``-phase loop.  Every device runs its local layer scan in every
    phase (SPMD lockstep; non-owning phases compute masked-out
    garbage), so per token each device reads its 1/S weight shard S
    times = ONE full model's bytes — the same HBM traffic that bounds
    single-chip decode.  PP-decode therefore costs ≈(S−1) ppermute
    latencies per token while scaling the model S×; the redundant FLOPs
    are free under the bandwidth bound.  ``S = 1`` degenerates to a
    single phase with no hand-off (one code path).
    """
    cd = cfg.compute_dtype
    S = lax.axis_size("pipe")
    stage = lax.axis_index("pipe")
    Tq = tok.shape[1] if tok.ndim == 2 else 1
    emb_scale = params.get("embed_scale")
    if cfg.vocab_parallel:
        # int8 scales (sharded like the rows) apply before the single
        # psum inside the lookup — one collective either way
        h = _vp_embed_lookup(
            params["embed"], tok, scale_local=emb_scale).astype(cd)
    else:
        h = params["embed"][tok].astype(cd)   # (B, D) or (B, Tq, D)
        if emb_scale is not None:
            # int8 embedding rows: dequantize the gathered rows only
            h = h * emb_scale[tok][..., None].astype(cd)
    if tok.ndim == 1:
        h = h[:, None, :]
    if cfg.pos_embedding == "learned":
        # per-index clipped gather, NOT dynamic_slice: a chunk that
        # overhangs the table (speculative decode's final round) must
        # corrupt only its own out-of-range rows — dynamic_slice clamps
        # the whole slice START, silently shifting every position
        idx = jnp.asarray(pos)[..., None] + jnp.arange(Tq)
        if pos_offset is not None:
            # left-padded rows: per-row token numbers (pad slots clip
            # to 0; their values are masked out of attention anyway)
            idx = idx[None, :] - pos_offset[:, None]
        rows = jnp.take(
            params["pos"],
            jnp.clip(idx, 0, params["pos"].shape[0] - 1), axis=0)
        # (Tq, D) shared by the batch, or (B, Tq, D) per row
        h = h + (rows if rows.ndim == 3 else rows[None]).astype(cd)
    h = h.astype(cd)
    h = _vary(h, "pipe")
    caches = tuple(jax.tree.map(lambda c: _vary(c, "pipe"), caches))
    blocks = jax.tree.map(lambda a: jnp.squeeze(a, 0), params["blocks"])
    if cfg.virtual_pipe > 1:
        # merge (V, layers_per_chunk) into one L axis; at pipe=1 the
        # virtual-stage order IS the layer order, so this is exact
        # (pipe>1 interleaves stages across devices — rejected in
        # _decode_preamble)
        blocks = jax.tree.map(
            lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]),
            blocks)

    h_in, out = h, h
    for p in range(S):
        mine = stage == p

        def layer(h, xs, mine=mine):
            blk, *cc = xs
            h, cc = _decode_block(
                cfg, h, blk, tuple(cc), pos,
                write_mask=None if S == 1 else mine,
                chunk_attends_cache=chunk_attends_cache,
                pos_offset=pos_offset)
            return h, cc

        out, caches = lax.scan(layer, h_in, (blocks, *caches))
        if p < S - 1:
            # exactly ONE inter-stage message per phase: the owning
            # stage's output hops to the next stage (non-receivers get
            # ppermute's zero fill, masked out by the where)
            sent = lax.ppermute(out, "pipe", [(p, p + 1)])
            h_in = jnp.where(stage == p + 1, sent, h_in)
    if not with_logits:
        # prefill: the cache fill IS the product; skip norm + head
        return None, tuple(caches)
    # only the LAST stage's output is the model's hidden state; zeros
    # elsewhere make the head a masked partial whose closing psum both
    # broadcasts the logits and re-replicates the pipe axis (free at
    # S = 1, where the mask is identity).  Generation wants only the
    # LAST position's logits (slice before the vocab matmul);
    # speculative verify (``all_logits``) needs every position's.
    h = jnp.where(stage == S - 1, out, jnp.zeros_like(out))
    h = _rms_norm(h if all_logits else h[:, -1:], params["ln_f"])
    logits = jnp.einsum(
        "btd,vd->btv", h.astype(jnp.float32),
        params["embed"].astype(jnp.float32))
    if not all_logits:
        logits = logits[:, 0]
    if emb_scale is not None:
        # per-vocab-row scale applies to the logits output channel
        # (with vocab_parallel both are the same local shard width;
        # broadcasts over (B, V) and (B, Tq, V) alike)
        logits = logits * emb_scale
    logits = lax.psum(logits, "pipe")
    if cfg.vocab_parallel:
        # samplers want full-width logits: gather the vocab shards
        # (invariant: identical on every model member afterwards)
        logits = _all_gather_invariant(
            logits, "model", axis=logits.ndim - 1, tiled=True)
    return logits, tuple(caches)


def _decode_preamble(mesh_cfg, cfg: TransformerConfig, max_len: int):
    """Shared validation for the decode factories; returns the resolved
    ``(max_len, kv_len_local, kv_heads_local, layers_local)``."""
    _check_mesh(mesh_cfg, cfg)   # head/kv divisibility, clear errors
    if cfg.training_only:
        raise ValueError(
            f"decoding does not implement {', '.join(cfg.training_only)}: "
            "a layer pattern (a cache per attention kind), an expert "
            "layer that holds a share or dispatches dropless, and an "
            "untied head exist on the training path only "
            "(make_train_step)")
    if cfg.fsdp:
        raise ValueError(
            "fsdp is a training-path layout (per-layer just-in-time "
            "weight gathers would land a collective on every generated "
            "token); decode with dataclasses.replace(cfg, fsdp=False, "
            "fsdp_wire_dtype='') and re-place the params")
    pipe = mesh_cfg.mesh.shape.get("pipe", 1)
    if pipe > 1 and cfg.virtual_pipe > 1:
        raise ValueError(
            "pipe-parallel decode with virtual_pipe > 1 is out of "
            "scope: interleaved chunks put non-contiguous layers on "
            "each device, so the S-phase hand-off loop would need "
            "V*S phases for no capacity gain over repacking — decode "
            "with the blocks repacked to virtual_pipe=1 "
            "(V-chunk axes merge exactly; see init_transformer's "
            "layout note)")
    if cfg.n_layers % pipe:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by the pipe mesh "
            f"axis ({pipe})")
    max_len = max_len or cfg.max_seq
    if max_len > cfg.max_seq:
        raise ValueError(
            f"max_len {max_len} exceeds cfg.max_seq {cfg.max_seq}")
    R = mesh_cfg.mesh.shape.get("seq", 1)
    if max_len % R:
        raise ValueError(
            f"sequence-parallel KV decode blocks the cache over the "
            f"seq axis: max_len={max_len} must be divisible by the seq "
            f"mesh axis ({R})")
    return (max_len, max_len // R,
            cfg.kv_heads // mesh_cfg.mesh.shape.get("model", 1),
            cfg.n_layers // pipe)


def _make_cache(cfg: TransformerConfig, rows: int, kv_len_local: int,
                kv_heads_local: int, layers_local: int,
                batch_varying: bool = True):
    """Zero KV cache pair ``(L_local, rows, kv_len_local, Hkv_local,
    Dh)``, typed varying over every mesh axis its contents will carry.
    ``layers_local`` = this stage's layer count — with pipe-parallel
    decode each device holds ONLY its stage's cache (the S× capacity
    win); ``kv_len_local`` = max_len / seq-axis-size — with
    sequence-parallel KV each member holds only its block of positions
    (the R× context win).  ``kv_cache_dtype="int8"`` stores values
    int8 plus fp32 per-(token, head) scales with a trailing singleton
    (so cache writes treat values and scales identically) — half the
    cache HBM, which is what bounds long-context decode.

    ``batch_varying=False`` skips the data/expert varying typing: the
    serving engine's prefill-to-pool program computes a one-row chunk
    REPLICATED across the batch shards (a single request has no batch
    parallelism to use) and writes it to a batch-replicated block
    pool, so the chunk must stay invariant over those axes."""
    axes = ["pipe", "data", "expert", "model"] if batch_varying \
        else ["pipe", "model"]
    if lax.axis_size("seq") > 1:
        # seq-varying only when the axis is real: at R == 1 the
        # single-member softmax path never psums over seq, so a varying
        # cache would leak seq variance into the logits' vma type
        axes.append("seq")
    int8 = cfg.kv_cache_dtype == "int8"
    val_dtype = jnp.int8 if int8 else cfg.compute_dtype
    shapes = [(layers_local, rows, kv_len_local, kv_heads_local,
               cfg.d_head, val_dtype)] * 2
    if int8:
        shapes += [(layers_local, rows, kv_len_local, kv_heads_local,
                    1, jnp.float32)] * 2
    return tuple(
        _vary(jnp.zeros(sh[:-1], sh[-1]), *axes) for sh in shapes)


def _validate_prompt_lens(prompt, prompt_lens):
    """Shared ``prompt_lens`` validation for the padded decode entry
    points (generate, beam search).  Returns the int32 lens array.  A
    multi-process global array cannot be fetched host-side — validate
    shape/dtype and THIS host's addressable shards (every process runs
    this same code on its own shards)."""
    P_len = prompt.shape[1]
    if isinstance(prompt_lens, jax.Array) \
            and not prompt_lens.is_fully_addressable:
        if prompt_lens.shape != (prompt.shape[0],):
            raise ValueError(
                f"prompt_lens shape {prompt_lens.shape} != "
                f"({prompt.shape[0]},)")
        if not jnp.issubdtype(prompt_lens.dtype, jnp.integer):
            raise ValueError(
                f"prompt_lens dtype {prompt_lens.dtype} must be "
                "integer")
        for sh in prompt_lens.addressable_shards:
            local = np.asarray(sh.data)
            if (local < 1).any() or (local > P_len).any():
                raise ValueError(
                    f"prompt_lens values must be in [1, {P_len}]; "
                    f"this host's shard holds {local}")
        return prompt_lens.astype(jnp.int32)
    lens = np.asarray(prompt_lens)
    if lens.shape != (prompt.shape[0],) \
            or (lens < 1).any() or (lens > P_len).any():
        raise ValueError(
            f"prompt_lens must be ({prompt.shape[0]},) ints in "
            f"[1, {P_len}] (rows RIGHT-aligned: real tokens are "
            f"prompt[b, P-lens[b]:]), got {lens}")
    return jnp.asarray(lens, jnp.int32)


def _filter_logits(logits, top_k: int, top_p: float):
    """Truncated-sampling filters on (B, V) fp32 logits: keep the
    ``top_k`` highest (0 = off) and/or the smallest set whose softmax
    mass reaches ``top_p`` (nucleus; 1.0 = off), masking the rest to
    ``_NEG``.  Both run on the sorted logits — one descending sort
    serves the two filters."""
    top_k = min(top_k, logits.shape[-1])   # k >= V is a no-op filter
    if top_k <= 0 and top_p >= 1.0:
        return logits
    srt = jnp.sort(logits, axis=-1)[:, ::-1]              # descending
    keep = jnp.ones_like(logits, bool)
    if top_k > 0:
        kth = srt[:, top_k - 1][:, None]
        keep &= logits >= kth
    if top_p < 1.0:
        probs = jax.nn.softmax(srt, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # the cutoff value: smallest sorted logit still inside the
        # nucleus (the first rank where cumulative mass reaches top_p
        # is always included, matching the usual shift-by-one rule)
        inside = (cum - probs) < top_p                    # (B, V) sorted
        n_keep = inside.sum(axis=-1)                      # >= 1
        cut = jnp.take_along_axis(
            srt, (n_keep - 1)[:, None], axis=-1)
        keep &= logits >= cut
    return jnp.where(keep, logits, _NEG)


def _validate_sampling_filters(top_k: int, top_p: float,
                               temperature: float):
    """Shared filter validation: ``top_k``/``top_p`` truncate SAMPLING
    distributions, so they require ``temperature > 0`` everywhere they
    appear (generate, speculative)."""
    if top_k < 0 or not 0.0 < top_p <= 1.0:
        raise ValueError(
            f"top_k={top_k} must be >= 0 and top_p={top_p} in (0, 1]")
    if (top_k > 0 or top_p < 1.0) and temperature <= 0.0:
        raise ValueError(
            "top_k/top_p truncate SAMPLING: set temperature > 0 "
            "(greedy decoding always takes the argmax)")


def _validate_eos_pad(cfg: TransformerConfig, eos_id: int, pad_id: int):
    """Shared eos/pad range validation for every decode factory."""
    if eos_id >= cfg.vocab_size or (eos_id >= 0
                                    and not 0 <= pad_id < cfg.vocab_size):
        raise ValueError(
            f"eos_id={eos_id} / pad_id={pad_id} must be < vocab_size "
            f"{cfg.vocab_size} (pad in range when eos is enabled)")


def _apply_eos_round(buf, pos, n_acc, k, done, eos_id, pad_id):
    """Post-commit eos bookkeeping for one speculative/lookup round.

    The round committed slots ``pos+1 .. pos+n_acc+1``.  Per row:
    everything after the FIRST committed eos becomes ``pad_id`` (the
    eos itself is kept — same convention as :func:`make_generate_fn`),
    and a row that was already done has ALL its committed slots padded
    (its proposals were garbage generated from pad context).  Exactness
    is untouched: only positions at or past a row's first eos are
    rewritten, and plain generate pads exactly those.  Returns
    ``(buf, done)``."""
    B = buf.shape[0]
    slab = lax.dynamic_slice(buf, (0, pos + 1), (B, k + 1))
    j = jnp.arange(k + 1)
    committed = j[None, :] <= n_acc                       # (1, k+1)
    is_eos = (slab == eos_id) & committed
    # first committed eos per row; k+1 = none this round
    first = jnp.min(jnp.where(is_eos, j[None, :], k + 1), axis=1)
    mask_pad = committed & (done[:, None] | (j[None, :] > first[:, None]))
    slab = jnp.where(mask_pad, pad_id, slab)
    done = done | (first <= n_acc)
    return lax.dynamic_update_slice(buf, slab, (0, pos + 1)), done


def make_generate_fn(mesh_cfg, cfg: TransformerConfig, *,
                     max_len: int = 0, temperature: float = 0.0,
                     top_k: int = 0, top_p: float = 1.0,
                     eos_id: int = -1, pad_id: int = 0,
                     quantized: bool = False,
                     with_row_state: bool = False):
    """Build ``generate(params, prompt, key=None, prompt_lens=None)
    -> (B, max_len)``.

    ``prompt``: (B, P) int32; generation fills positions P..max_len-1.
    Equal-length prompts need nothing more (the reference's translate
    contract).  **Variable-length prompts**: RIGHT-align each row (real
    tokens at ``prompt[b, P-lens[b]:]``, anything in the pad slots) and
    pass ``prompt_lens`` (B,) — each row then decodes exactly as it
    would alone: per-row RoPE/learned positions start at the row's
    first real token, and a per-row attention-validity mask keeps every
    query off the pad slots' K/V.  Not supported under seq-KV
    (``seq`` axis > 1) — shard batch/heads/layers instead; with MoE,
    pad tokens do consume router capacity during prefill.  Greedy when
    ``temperature == 0``, else temperature sampling (``key`` required)
    optionally truncated by ``top_k`` (keep the k best tokens) and/or
    ``top_p`` (nucleus: the smallest set reaching that softmax mass —
    filters compose, both applied AFTER the temperature scaling, the
    same order as HF ``generate``, so ported sampling configs truncate
    the same sets).

    ``eos_id >= 0`` enables early stopping: a row that emits it is
    frozen (later positions fill with ``pad_id``), and the loop exits
    as soon as EVERY row across the sharded batch is done — a
    ``lax.while_loop`` whose stop flag is the pmin of the shards'
    all-done bits, so real serving batches stop paying per-token HBM
    reads the moment the last row finishes rather than at ``max_len``
    (eos tokens in the PROMPT are ignored, matching the usual
    convention).  ``quantized=True`` expects int8 weight-only params
    from :func:`...quantization.quantize_params_int8` (≈half the HBM
    traffic per token).

    ``with_row_state=True`` returns ``(tokens, done, gen_len)``: the
    per-row loop state that used to stay buried in the while carry
    (only the all-rows-done scalar escaped, as the exit condition).
    ``done`` (B,) bool marks rows that stopped by emitting ``eos_id``
    (all-False when eos is disabled or a row ran to ``max_len``);
    ``gen_len`` (B,) int32 counts each row's GENERATED tokens — the
    eos token included, the frozen tail's padding excluded — i.e.
    exactly the positions ``tokens[b, P:P+gen_len[b]]`` that carry
    real output under the frozen-row padding semantics.  This is the
    per-row bookkeeping a request-level scheduler (the serving
    engine) needs from a batch: which rows finished, and where each
    row's output ends.
    """
    _validate_sampling_filters(top_k, top_p, temperature)
    _validate_eos_pad(cfg, eos_id, pad_id)
    # pad_id == eos_id is allowed (the HF GPT-2 convention sets
    # pad_token = eos_token): frozen rows then fill their tail with the
    # eos token, which is unambiguous to consumers that trim at the
    # FIRST eos — everything from it onward is end-of-sequence either
    # way.
    max_len, kv_len_local, kv_heads_local, layers_local = _decode_preamble(
        mesh_cfg, cfg, max_len)
    specs = param_specs(cfg, quantized=quantized)
    batch_spec = P(("data", "expert"))

    def _body(params, prompt, key, offsets):
        # decorrelate sampling across batch shards (same key on every
        # device would draw identical noise for different examples)
        key = jax.random.fold_in(
            key, lax.axis_index("data") * lax.axis_size("expert")
            + lax.axis_index("expert"))
        B, Plen = prompt.shape
        cache = _make_cache(cfg, B, kv_len_local, kv_heads_local,
                            layers_local)
        # with eos enabled the loop can exit before writing every
        # position: seed the buffer with pad so the unwritten tail
        # reads as padding, not as token 0
        buf = jnp.full((B, max_len), max(pad_id, 0) if eos_id >= 0
                       else 0, jnp.int32)
        buf = lax.dynamic_update_slice(buf, prompt, (0, 0))

        # batched prefill: positions 0..P-2 fill the cache in ONE
        # MXU-shaped pass (the per-token scan below starts at the last
        # prompt position, whose logits seed generation).  Left-padded
        # prompts route through the cache-attending path: its per-row
        # validity mask keeps every real query off the pad slots' K/V
        # (the chunk-local fast path has no row dimension in its mask)
        if Plen > 1:
            _, cache = _decode_step(
                cfg, params, cache, prompt[:, :Plen - 1], 0,
                with_logits=False,
                chunk_attends_cache=offsets is not None,
                pos_offset=offsets)

        def token_step(buf, caches, key, t, done):
            logits, caches = _decode_step(
                cfg, params, caches, buf[:, t], t, pos_offset=offsets)
            if temperature > 0.0:
                key, sub = jax.random.split(key)
                # temperature FIRST, filters second (the HF/common
                # convention): top_k membership is scale-invariant but
                # the nucleus set is not, so configs ported from other
                # stacks truncate identically only in this order
                nxt = jax.random.categorical(
                    sub, _filter_logits(logits / temperature,
                                        top_k, top_p))
            else:
                nxt = jnp.argmax(logits, axis=-1)
            nxt = nxt.astype(jnp.int32)
            if eos_id >= 0:
                # frozen rows emit pad; eos itself is written first
                nxt = jnp.where(done, pad_id, nxt)
                done = done | (nxt == eos_id)
            # generation starts at the LAST prompt position (prefill
            # covered the rest), so every t+1 is a generated slot
            buf = lax.dynamic_update_slice(
                buf, nxt[:, None], (0, t + 1))
            return buf, caches, key, done

        # typed varying over the batch axes so the while carry matches
        # the body's output (done is updated from batch-sharded tokens)
        done = _vary(jnp.zeros((B,), bool), "data", "expert")
        if eos_id < 0:
            def step(carry, t):
                buf, caches, key = carry
                buf, caches, key, _ = token_step(
                    buf, caches, key, t, done)
                return (buf, caches, key), None

            (buf, _, _), _ = lax.scan(
                step, (buf, cache, key),
                jnp.arange(Plen - 1, max_len - 1))
            # no eos: every row generates the full tail
            gen_len = _vary(
                jnp.full((B,), max_len - Plen, jnp.int32),
                "data", "expert")
        else:
            gen_len = _vary(jnp.zeros((B,), jnp.int32), "data", "expert")

            def cond(carry):
                buf, caches, key, t, done, gen_len = carry
                # the while condition must be mesh-invariant: keep
                # going while ANY shard still has an unfinished row —
                # pmax of the shards' not-all-done bits (done derives
                # from logits, already invariant over model/seq/pipe)
                running = lax.pmax(
                    (~jnp.all(done)).astype(jnp.int32),
                    ("data", "expert"))
                return (t < max_len - 1) & (running > 0)

            def wbody(carry):
                buf, caches, key, t, done, gen_len = carry
                # rows not frozen ENTERING the step emit a real token
                # this step (the eos itself included — it is written,
                # then freezes the row); frozen rows emit padding
                gen_len = gen_len + (~done).astype(jnp.int32)
                buf, caches, key, done = token_step(
                    buf, caches, key, t, done)
                return (buf, caches, key, t + 1, done, gen_len)

            buf, _, _, _, done, gen_len = lax.while_loop(
                cond, wbody,
                (buf, cache, key, jnp.int32(Plen - 1), done, gen_len))
        return buf, done, gen_len

    def body(params, prompt, key):
        buf, done, gen_len = _body(params, prompt, key, None)
        return (buf, done, gen_len) if with_row_state else buf

    def body_padded(params, prompt, lens, key):
        buf, done, gen_len = _body(params, prompt, key,
                                   jnp.int32(prompt.shape[1]) - lens)
        return (buf, done, gen_len) if with_row_state else buf

    out_specs = (batch_spec,) * 3 if with_row_state else batch_spec
    fn = jax.jit(jax.shard_map(
        body,
        mesh=mesh_cfg.mesh,
        in_specs=(specs, batch_spec, P()),
        out_specs=out_specs,
    ))
    lazy = {}   # the padded program compiles on first use only

    def generate(params, prompt, key=None, prompt_lens=None):
        if temperature > 0.0 and key is None:
            raise ValueError("temperature sampling needs a PRNG key")
        if key is None:
            key = jax.random.PRNGKey(0)
        if prompt_lens is None:
            return fn(params, prompt, key)
        lens = _validate_prompt_lens(prompt, prompt_lens)
        if "padded" not in lazy:
            lazy["padded"] = jax.jit(jax.shard_map(
                body_padded,
                mesh=mesh_cfg.mesh,
                in_specs=(specs, batch_spec, batch_spec, P()),
                out_specs=out_specs,
            ))
        return lazy["padded"](params, prompt, lens, key)

    # the underlying jitted program, exposed for lowering/inspection
    # (utils.comm_model parses its HLO for the decode wire model)
    generate._jitted = fn
    return generate


def make_speculative_generate_fn(mesh_cfg, cfg: TransformerConfig,
                                 draft_cfg: TransformerConfig, *,
                                 k: int = 4, max_len: int = 0,
                                 temperature: float = 0.0,
                                 top_k: int = 0, top_p: float = 1.0,
                                 eos_id: int = -1, pad_id: int = 0,
                                 quantized: bool = False,
                                 draft_quantized: bool = False,
                                 with_stats: bool = False):
    """Greedy speculative decoding: a cheap DRAFT model proposes ``k``
    tokens per round, the target verifies them in ONE (k+1)-token chunk
    forward — the accepted prefix plus the target's own next token land
    together, so each round emits 1..k+1 tokens for one read of the
    target's weights instead of one per token.  Decode is HBM-bound on
    weights; with a good draft this multiplies tokens/sec by roughly
    the mean accepted length.

    Output is **token-identical to the target's own greedy decode**
    (only verified matches are accepted; the corrective token is the
    target's argmax in an all-accepted context) — the draft affects
    speed, never content.  Acceptance is batch-min (rows advance in
    lockstep at the worst row's rate): exactness is preserved, and the
    speedup is best at the small batches latency-bound serving runs.

    ``temperature > 0`` switches to **speculative SAMPLING** (the
    Leviathan/Chen acceptance-rejection scheme): the draft SAMPLES its
    proposals, each is accepted with probability
    ``min(1, p_target/p_draft)``, and the round's last committed token
    draws from the residual ``max(0, p_t − p_d)`` on a rejection or
    from ``p_t`` outright otherwise — the output is
    **distribution-identical to sampling the target directly**, the
    draft only changes speed.  Acceptance stays the GLOBAL batch-min
    for SPMD lockstep; exactness survives the early cut because a row
    whose own rejection lies beyond the cut commits its ACCEPTED
    proposal at the cut position — per row, every committed token is
    the accept-branch/residual-branch pair whose mixture equals
    ``p_t``, independent of the other rows' outcomes (pinned by a
    statistical test against direct sampling).

    ``top_k``/``top_p`` compose with speculative sampling by
    truncating BOTH distributions (after the temperature scaling, the
    same HF order as :func:`make_generate_fn`): the draft proposes
    from its filtered distribution p_d′ and the acceptance test,
    residual, and bonus draw all run on the target's filtered p_t′ —
    the Leviathan/Chen identity holds for ANY distribution pair, so
    the output is distribution-identical to sampling the target
    directly with the same filters.

    ``eos_id >= 0`` enables early stopping with the exact
    :func:`make_generate_fn` semantics (first eos kept, tail padded
    with ``pad_id``, loop exits when every row across the sharded
    batch is done); frozen rows report full-``k`` acceptance so their
    garbage proposals never bind the batch-min.  Variable-length
    prompts: RIGHT-align the rows and pass ``prompt_lens`` to
    ``generate`` exactly as in :func:`make_generate_fn` — the per-row
    position origins and pad-slot masks thread through the draft
    steps and the verify chunks alike.

    ``draft_cfg`` must share ``vocab_size`` and ``max_seq``; pipe/TP
    meshes compose; the ``seq`` axis must be 1 (mid-sequence chunk
    writes don't block over seq-KV).  Returns
    ``generate(params, draft_params, prompt, key=None,
    prompt_lens=None) -> (B, max_len)`` (``key`` required when
    sampling), or with ``with_stats=True`` ``-> (tokens,
    mean_accepted)`` where ``mean_accepted`` (scalar fp32, in [0, k])
    is the average number of draft proposals accepted per round — the
    observability a draft needs tuning against (each round emits
    ``mean_accepted + 1`` tokens for one target chunk read).
    """
    if k < 1:
        raise ValueError(f"k={k} must be >= 1")
    if temperature < 0.0:
        raise ValueError(f"temperature {temperature} must be >= 0")
    _validate_sampling_filters(top_k, top_p, temperature)
    _validate_eos_pad(cfg, eos_id, pad_id)
    if draft_cfg.vocab_size != cfg.vocab_size:
        raise ValueError(
            f"draft vocab {draft_cfg.vocab_size} != target "
            f"{cfg.vocab_size}")
    if mesh_cfg.mesh.shape.get("seq", 1) != 1:
        raise ValueError(
            "speculative decoding writes mid-sequence chunks, which "
            "the seq-KV blockwise layout does not support: use a "
            "seq=1 mesh (shard batch/heads/layers instead)")
    max_len, kv_len_local, kv_heads_local, layers_local = \
        _decode_preamble(mesh_cfg, cfg, max_len)
    _, d_kv_len, d_kv_heads_local, d_layers_local = _decode_preamble(
        mesh_cfg, draft_cfg, max_len)
    specs = param_specs(cfg, quantized=quantized)
    d_specs = param_specs(draft_cfg, quantized=draft_quantized)
    batch_spec = P(("data", "expert"))
    # rounds may overshoot max_len by up to k+1 tokens: pad the buffer
    # and caches, slice the pad off at the end
    pad = k + 1

    def body(params, d_params, prompt, key, offsets):
        B, Plen = prompt.shape
        # decorrelate sampling across batch shards (see make_generate_fn)
        key = jax.random.fold_in(
            key, lax.axis_index("data") * lax.axis_size("expert")
            + lax.axis_index("expert"))
        t_cache = _make_cache(cfg, B, kv_len_local + pad,
                              kv_heads_local, layers_local)
        d_cache = _make_cache(draft_cfg, B, d_kv_len + pad,
                              d_kv_heads_local, d_layers_local)
        # pad-seed when eos can exit early (see make_generate_fn)
        buf = jnp.full((B, max_len + pad),
                       max(pad_id, 0) if eos_id >= 0 else 0, jnp.int32)
        buf = lax.dynamic_update_slice(buf, prompt, (0, 0))
        if Plen > 1:
            _, t_cache = _decode_step(
                cfg, params, t_cache, prompt[:, :Plen - 1], 0,
                with_logits=False,
                chunk_attends_cache=offsets is not None,
                pos_offset=offsets)
            _, d_cache = _decode_step(
                draft_cfg, d_params, d_cache, prompt[:, :Plen - 1], 0,
                with_logits=False,
                chunk_attends_cache=offsets is not None,
                pos_offset=offsets)

        def cond(carry):
            pos, done = carry[1], carry[7]
            going = pos < max_len - 1
            if eos_id >= 0:
                # mesh-invariant early exit, as in make_generate_fn
                running = lax.pmax(
                    (~jnp.all(done)).astype(jnp.int32),
                    ("data", "expert"))
                going &= running > 0
            return going

        def round_body(carry):
            (buf, pos, acc_sum, rounds, t_cache, d_cache, key,
             done) = carry
            cur = lax.dynamic_slice(buf, (0, pos), (B, 1))[:, 0]
            # --- draft proposes k tokens (greedy, or sampled from its
            # own temperature distribution) ---------------------------- #
            props, d_lps, d_ps = [], [], []
            d_cur = cur
            for j in range(k):      # static unroll, k is small
                dlog, d_cache = _decode_step(
                    draft_cfg, d_params, d_cache, d_cur, pos + j,
                    pos_offset=offsets)
                if temperature > 0.0:
                    key, sub = jax.random.split(key)
                    # temperature first, then truncation — p_d′, the
                    # draft side of the filtered acceptance pair
                    lp = jax.nn.log_softmax(_filter_logits(
                        dlog.astype(jnp.float32) / temperature,
                        top_k, top_p), -1)
                    d_cur = jax.random.categorical(sub, lp) \
                        .astype(jnp.int32)
                    d_lps.append(jnp.take_along_axis(
                        lp, d_cur[:, None], 1)[:, 0])
                    d_ps.append(jnp.exp(lp))
                else:
                    d_cur = jnp.argmax(dlog, axis=-1).astype(jnp.int32)
                props.append(d_cur)
            # one extra cache-fill step for the LAST proposal: k steps
            # yield k proposals but only k-1 of their K/V writes — after
            # a fully-accepted round pos advances past pos+k, and a
            # never-written slot there would stay a zero-K/V hole every
            # later draft query attends, silently decaying acceptance
            # (partial accepts overwrite this slot next round anyway)
            _, d_cache = _decode_step(
                draft_cfg, d_params, d_cache, d_cur, pos + k,
                with_logits=False, pos_offset=offsets)
            prop = jnp.stack(props, axis=1)               # (B, k)
            if temperature <= 0.0:
                buf, t_cache, n_acc = _verify_and_commit(
                    cfg, params, t_cache, buf, pos, cur, prop, k,
                    pos_offset=offsets,
                    done=done if eos_id >= 0 else None)
                if eos_id >= 0:
                    buf, done = _apply_eos_round(
                        buf, pos, n_acc, k, done, eos_id, pad_id)
                return (buf, pos + n_acc + 1, acc_sum + n_acc,
                        rounds + 1, t_cache, d_cache, key, done)
            # --- speculative SAMPLING verify (Leviathan/Chen) -------- #
            tlog, t_cache = _decode_step(
                cfg, params, t_cache,
                jnp.concatenate([cur[:, None], prop], axis=1), pos,
                all_logits=True, chunk_attends_cache=True,
                pos_offset=offsets)
            # temperature, then the SAME truncation as the draft side:
            # p_t′ — acceptance/residual/bonus below all run on the
            # filtered pair, whose mixture identity is what plain
            # filtered sampling produces
            t_in = tlog.astype(jnp.float32) / temperature  # (B,k+1,V)
            if top_k > 0 or top_p < 1.0:
                t_in = _filter_logits(
                    t_in.reshape(B * (k + 1), -1),
                    top_k, top_p).reshape(t_in.shape)
            t_lp = jax.nn.log_softmax(t_in, -1)            # (B,k+1,V)
            d_lp = jnp.stack(d_lps, axis=1)                  # (B, k)
            t_at_prop = jnp.take_along_axis(
                t_lp[:, :k], prop[..., None], -1)[..., 0]    # (B, k)
            key, sub = jax.random.split(key)
            u = jax.random.uniform(sub, prop.shape, minval=1e-20)
            # accept while u < p_t/p_d, in log space (u<1 makes the
            # min(1, ·) implicit); cumulative: later slots only count
            # while every earlier proposal was accepted
            acc = jnp.log(u) < (t_at_prop - d_lp)
            lead = jnp.cumprod(acc.astype(jnp.int32), axis=1)
            row_acc = lead.sum(axis=1)                       # (B,)
            if eos_id >= 0:
                # frozen rows never bind the batch-min (their padded
                # context proposes garbage); their commits pad below
                row_acc = jnp.where(done, k, row_acc)
            n_acc = lax.pmin(
                jnp.min(row_acc), ("data", "expert"))
            # the committed token at the cut position, PER ROW:
            # - rejected exactly there -> residual max(0, p_t − p_d);
            # - accepted there but cut early (another row bound the
            #   batch-min) -> commit the ACCEPTED proposal.  A fresh
            #   p_t draw here would be biased: the committed token
            #   must stay the accept-branch/residual-branch PAIR whose
            #   mixture is what equals p_t — replacing the accept
            #   branch's min(p_d, p_t) with α·p_t breaks the identity
            #   (a statistical test caught exactly this);
            # - accepted everything (n_acc == k) -> the standard bonus
            #   draw from p_t at position k.
            V = t_lp.shape[-1]
            t_p_cut = jnp.exp(lax.dynamic_slice(
                t_lp, (0, n_acc, 0), (B, 1, V))[:, 0])       # (B, V)
            d_p = jnp.stack(d_ps, axis=1)                    # (B, k, V)
            cut_lt_k = jnp.minimum(n_acc, k - 1)   # clip; unused at k
            d_p_cut = lax.dynamic_slice(
                d_p, (0, cut_lt_k, 0), (B, 1, V))[:, 0]
            resid = jnp.maximum(t_p_cut - d_p_cut, 0.0)
            rs = resid.sum(-1, keepdims=True)
            resid = jnp.where(rs > 1e-9, resid / rs, t_p_cut)
            rejected_here = (row_acc == n_acc) & (n_acc < k)
            dist = jnp.where(rejected_here[:, None], resid, t_p_cut)
            key, sub = jax.random.split(key)
            sampled = jax.random.categorical(
                sub, jnp.log(jnp.maximum(dist, 1e-30))) \
                .astype(jnp.int32)
            prop_cut = lax.dynamic_slice(
                prop, (0, cut_lt_k), (B, 1))[:, 0]
            bonus = jnp.where(row_acc > n_acc, prop_cut, sampled)
            buf = _commit_round(buf, pos, prop, bonus, n_acc, k)
            if eos_id >= 0:
                buf, done = _apply_eos_round(
                    buf, pos, n_acc, k, done, eos_id, pad_id)
            return (buf, pos + n_acc + 1, acc_sum + n_acc, rounds + 1,
                    t_cache, d_cache, key, done)

        done = _vary(jnp.zeros((B,), bool), "data", "expert")
        buf, _, acc_sum, rounds, _, _, _, _ = lax.while_loop(
            cond, round_body,
            (buf, jnp.int32(Plen - 1), jnp.int32(0), jnp.int32(0),
             t_cache, d_cache, key, done))
        mean_acc = acc_sum.astype(jnp.float32) \
            / jnp.maximum(rounds, 1).astype(jnp.float32)
        return buf[:, :max_len], mean_acc

    def body_plain(params, d_params, prompt, key):
        return body(params, d_params, prompt, key, None)

    def body_padded(params, d_params, prompt, lens, key):
        return body(params, d_params, prompt, key,
                    jnp.int32(prompt.shape[1]) - lens)

    fn = jax.jit(jax.shard_map(
        body_plain,
        mesh=mesh_cfg.mesh,
        in_specs=(specs, d_specs, batch_spec, P()),
        out_specs=(batch_spec, P()),
    ))
    lazy = {}   # the padded program compiles on first use only

    def generate(params, draft_params, prompt, key=None,
                 prompt_lens=None):
        if temperature > 0.0 and key is None:
            raise ValueError(
                "speculative sampling needs a PRNG key")
        if key is None:
            key = jax.random.PRNGKey(0)
        if prompt_lens is None:
            toks, mean_acc = fn(params, draft_params, prompt, key)
            return (toks, mean_acc) if with_stats else toks
        lens = _validate_prompt_lens(prompt, prompt_lens)
        if "padded" not in lazy:
            lazy["padded"] = jax.jit(jax.shard_map(
                body_padded,
                mesh=mesh_cfg.mesh,
                in_specs=(specs, d_specs, batch_spec, batch_spec, P()),
                out_specs=(batch_spec, P()),
            ))
        toks, mean_acc = lazy["padded"](
            params, draft_params, prompt, lens, key)
        return (toks, mean_acc) if with_stats else toks

    generate._jitted = fn
    return generate


def _commit_round(buf, pos, prop, bonus, n_acc, k):
    """Land one speculative round's outcome in ``buf``: the accepted
    prefix ``prop[:, :n_acc]`` then the ``bonus`` token — blended into
    the existing slab so positions beyond ``n_acc`` stay untouched."""
    B = prop.shape[0]
    slab = lax.dynamic_slice(buf, (0, pos + 1), (B, k + 1))
    j_idx = jnp.arange(k + 1)
    slab = jnp.where(
        j_idx[None, :] < n_acc, jnp.concatenate(
            [prop, prop[:, -1:]], axis=1),
        jnp.where(j_idx[None, :] == n_acc,
                  bonus[:, None], slab))
    return lax.dynamic_update_slice(buf, slab, (0, pos + 1))


def _verify_and_commit(cfg, params, t_cache, buf, pos, cur, prop, k,
                       pos_offset=None, done=None):
    """The GREEDY speculative round's second half, shared by every
    proposer (draft model, prompt lookup): the target verifies ``prop``
    (B, k) in ONE (k+1)-wide chunk forward, the accepted prefix plus
    the target's corrective/bonus token land in ``buf``, and acceptance
    is the GLOBAL batch-min so every data shard advances in lockstep
    (the while carry/cond need ``pos`` axis-invariant).
    ``pos_offset`` threads left-padded rows' per-row position origins
    through the verify chunk; ``done`` (B,) marks eos-frozen rows,
    which report a full-k acceptance so garbage proposed from their pad
    context never binds the batch-min (their committed tokens are
    padded afterwards by :func:`_apply_eos_round`).  Returns
    ``(buf, t_cache, n_acc)``."""
    B = cur.shape[0]
    chunk = jnp.concatenate([cur[:, None], prop], axis=1)
    tlog, t_cache = _decode_step(
        cfg, params, t_cache, chunk, pos,
        all_logits=True, chunk_attends_cache=True, pos_offset=pos_offset)
    g = jnp.argmax(tlog, axis=-1).astype(jnp.int32)   # (B, k+1)
    # g[:, j] = target's token for position pos+j+1 given the chunk
    # prefix through pos+j; prop[:, j] was the proposer's token for
    # the same position — valid to compare only while every earlier
    # proposal matched
    match = prop == g[:, :k]                          # (B, k)
    lead = jnp.cumprod(match.astype(jnp.int32), axis=1)
    row_acc = lead.sum(axis=1)
    if done is not None:
        row_acc = jnp.where(done, k, row_acc)
    n_acc = lax.pmin(jnp.min(row_acc), ("data", "expert"))
    bonus = jnp.take_along_axis(
        g, jnp.full((B, 1), n_acc), axis=1)[:, 0]
    buf = _commit_round(buf, pos, prop, bonus, n_acc, k)
    return buf, t_cache, n_acc


def make_lookup_generate_fn(mesh_cfg, cfg: TransformerConfig, *,
                            k: int = 4, ngram: int = 2,
                            max_len: int = 0,
                            eos_id: int = -1, pad_id: int = 0,
                            quantized: bool = False,
                            with_stats: bool = False):
    """Greedy prompt-lookup decoding: speculative decoding whose
    proposer is an N-GRAM MATCH against the already-generated context
    instead of a draft model (Saxena's prompt-lookup trick).  Each
    round takes the last ``ngram`` tokens, finds their most recent
    earlier occurrence in the buffer, proposes the ``k`` tokens that
    followed it, and lets the target verify the whole chunk — so
    copying-heavy workloads (summarisation, code edit, RAG quoting)
    emit several tokens per target-weight read with NO second model,
    no extra memory, and the same exact-greedy guarantee as
    :func:`make_speculative_generate_fn` (a miss costs one verify
    chunk and still emits one correct token).

    The matcher is pure vectorised compare/gather on the (B, L) token
    buffer — a few KB of integer work per round, nothing a TPU
    notices next to the verify matmuls.  Prompts must be at least
    ``ngram`` long; ``seq`` mesh axis must be 1 (same mid-sequence
    chunk contract as speculative).

    ``eos_id >= 0`` enables early stopping with the exact
    :func:`make_generate_fn` semantics (first eos kept, tail padded,
    mesh-wide early exit; frozen rows report full-``k`` acceptance so
    they never bind the batch-min).  Variable-length prompts:
    RIGHT-align and pass ``prompt_lens`` — the matcher runs over the
    padded buffer (windows touching pad slots just propose garbage,
    which verification corrects; acceptance on short rows recovers as
    their generated context grows).

    Returns ``generate(params, prompt, prompt_lens=None)``
    (``with_stats=True`` appends mean accepted proposals per round,
    the number to watch: it IS the speedup lever).
    """
    if k < 1 or ngram < 1:
        raise ValueError(f"k={k} and ngram={ngram} must be >= 1")
    _validate_eos_pad(cfg, eos_id, pad_id)
    if mesh_cfg.mesh.shape.get("seq", 1) != 1:
        raise ValueError(
            "prompt-lookup decoding writes mid-sequence chunks, which "
            "the seq-KV blockwise layout does not support: use a "
            "seq=1 mesh (shard batch/heads/layers instead)")
    max_len, kv_len_local, kv_heads_local, layers_local = \
        _decode_preamble(mesh_cfg, cfg, max_len)
    specs = param_specs(cfg, quantized=quantized)
    batch_spec = P(("data", "expert"))
    pad = k + 1
    L = max_len + pad

    def body(params, prompt, offsets):
        B, Plen = prompt.shape
        if Plen < ngram:
            raise ValueError(
                f"prompt length {Plen} < ngram {ngram}: the first "
                "lookup window would cross the buffer start")
        t_cache = _make_cache(cfg, B, kv_len_local + pad,
                              kv_heads_local, layers_local)
        # pad-seed when eos can exit early (see make_generate_fn)
        buf = jnp.full((B, L),
                       max(pad_id, 0) if eos_id >= 0 else 0, jnp.int32)
        buf = lax.dynamic_update_slice(buf, prompt, (0, 0))
        if Plen > 1:
            _, t_cache = _decode_step(
                cfg, params, t_cache, prompt[:, :Plen - 1], 0,
                with_logits=False,
                chunk_attends_cache=offsets is not None,
                pos_offset=offsets)

        # static window table: window w covers buf[w .. w+ngram-1]
        # and ENDS at position w+ngram-1
        widx = jnp.arange(L - ngram + 1)[:, None] + jnp.arange(ngram)
        ends = jnp.arange(L - ngram + 1) + ngram - 1

        def cond(carry):
            pos, done = carry[1], carry[5]
            going = pos < max_len - 1
            if eos_id >= 0:
                running = lax.pmax(
                    (~jnp.all(done)).astype(jnp.int32),
                    ("data", "expert"))
                going &= running > 0
            return going

        def round_body(carry):
            buf, pos, acc_sum, rounds, t_cache, done = carry
            cur = lax.dynamic_slice(buf, (0, pos), (B, 1))[:, 0]
            # --- lookup proposer ---------------------------------- #
            suffix = lax.dynamic_slice(
                buf, (0, pos - (ngram - 1)), (B, ngram))
            windows = buf[:, widx]                    # (B, W, ngram)
            hit = (windows == suffix[:, None, :]).all(-1) \
                & (ends[None, :] < pos)               # (B, W)
            # most recent earlier occurrence; -1 = no match, which
            # clamps src to the buffer head (proposing the first k
            # prompt tokens — an arbitrary but harmless guess:
            # verification keeps output exact regardless)
            j = jnp.max(jnp.where(hit, ends[None, :], -1), axis=1)
            src = jnp.clip(
                j[:, None] + 1 + jnp.arange(k)[None], 0, L - 1)
            prop = jnp.take_along_axis(buf, src, axis=1)  # (B, k)
            buf, t_cache, n_acc = _verify_and_commit(
                cfg, params, t_cache, buf, pos, cur, prop, k,
                pos_offset=offsets,
                done=done if eos_id >= 0 else None)
            if eos_id >= 0:
                buf, done = _apply_eos_round(
                    buf, pos, n_acc, k, done, eos_id, pad_id)
            return (buf, pos + n_acc + 1, acc_sum + n_acc,
                    rounds + 1, t_cache, done)

        done = _vary(jnp.zeros((B,), bool), "data", "expert")
        buf, _, acc_sum, rounds, _, _ = lax.while_loop(
            cond, round_body,
            (buf, jnp.int32(Plen - 1), jnp.int32(0), jnp.int32(0),
             t_cache, done))
        mean_acc = acc_sum.astype(jnp.float32) \
            / jnp.maximum(rounds, 1).astype(jnp.float32)
        return buf[:, :max_len], mean_acc

    def body_plain(params, prompt):
        return body(params, prompt, None)

    def body_padded(params, prompt, lens):
        return body(params, prompt, jnp.int32(prompt.shape[1]) - lens)

    fn = jax.jit(jax.shard_map(
        body_plain,
        mesh=mesh_cfg.mesh,
        in_specs=(specs, batch_spec),
        out_specs=(batch_spec, P()),
    ))
    lazy = {}   # the padded program compiles on first use only

    def generate(params, prompt, prompt_lens=None):
        if prompt_lens is None:
            toks, mean_acc = fn(params, prompt)
            return (toks, mean_acc) if with_stats else toks
        lens = _validate_prompt_lens(prompt, prompt_lens)
        if "padded" not in lazy:
            lazy["padded"] = jax.jit(jax.shard_map(
                body_padded,
                mesh=mesh_cfg.mesh,
                in_specs=(specs, batch_spec, batch_spec),
                out_specs=(batch_spec, P()),
            ))
        toks, mean_acc = lazy["padded"](params, prompt, lens)
        return (toks, mean_acc) if with_stats else toks

    generate._jitted = fn
    return generate


def make_beam_search_fn(mesh_cfg, cfg: TransformerConfig, *,
                        beam_size: int, max_len: int = 0,
                        eos_id: int = -1, length_penalty: float = 0.0,
                        quantized: bool = False):
    """Build ``beam_search(params, prompt) -> (tokens, scores)``.

    Jittable beam search over the KV-cached decoder (the reference's
    ``translate`` was greedy-only): ``K = beam_size`` hypotheses per
    batch element advance in lockstep; each step expands every live
    beam by the full vocab, keeps the global top-K by cumulative
    log-probability, and reorders the KV cache by beam origin (a local
    gather — beams live on the same device as their batch element, so
    DP/TP meshes compose exactly as in :func:`make_generate_fn`).

    ``eos_id >= 0`` freezes hypotheses that emit it (score kept, padded
    with ``eos_id``).  ``length_penalty`` α applies GNMT normalisation
    ``score / ((5+len)/6)^α`` for the final ranking.

    Variable-length prompts: RIGHT-align the rows and pass
    ``prompt_lens`` (B,) exactly as in :func:`make_generate_fn` — the
    per-row position origins and pad-slot masks thread through every
    beam's steps (beams share their row's offset).

    Returns ``tokens`` (B, K, max_len) sorted best-first and ``scores``
    (B, K) (length-normalised when α > 0).
    """
    if beam_size < 1:
        raise ValueError(f"beam_size {beam_size} must be >= 1")
    max_len, kv_len_local, kv_heads_local, layers_local = _decode_preamble(
        mesh_cfg, cfg, max_len)   # includes _check_mesh
    K = beam_size

    specs = param_specs(cfg, quantized=quantized)
    batch_spec = P(("data", "expert"))

    def _body(params, prompt, offsets):
        B, Plen = prompt.shape
        # -- prefill at width B (the K beams are identical inside the
        # prompt — no reason to pay K× its FLOPs or reorder gathers) --
        cache_b = _make_cache(cfg, B, kv_len_local, kv_heads_local,
                              layers_local)

        # batched prefill: positions 0..P-2 in one MXU-shaped pass
        # (padded rows route through the cache-attending path, whose
        # validity mask carries the row dimension)
        if Plen > 1:
            _, cache_b = _decode_step(
                cfg, params, cache_b, prompt[:, :Plen - 1], 0,
                with_logits=False,
                chunk_attends_cache=offsets is not None,
                pos_offset=offsets)
        # every beam inherits its batch row's pad offset
        offs_bk = None if offsets is None else jnp.repeat(offsets, K)
        # tile to beam width: flat row b·K + k holds batch b's beam k
        cache = tuple(jnp.repeat(c, K, axis=1) for c in cache_b)

        buf = jnp.zeros((B, K, max_len), jnp.int32)
        buf = lax.dynamic_update_slice(
            buf, jnp.broadcast_to(prompt[:, None], (B, K, Plen)),
            (0, 0, 0))
        # beam 0 carries the prompt; duplicates start dead so the first
        # expansion draws K distinct continuations from beam 0
        scores = _vary(
            jnp.broadcast_to(
                jnp.where(jnp.arange(K) == 0, 0.0, _NEG)[None],
                (B, K)) * 1.0,
            "data", "expert")
        finished = _vary(jnp.zeros((B, K), bool), "data", "expert")

        def step(carry, t):
            buf, scores, finished, caches = carry
            logits, caches = _decode_step(
                cfg, params, caches, buf.reshape(B * K, max_len)[:, t],
                t, pos_offset=offs_bk)
            logp = jax.nn.log_softmax(logits, axis=-1).reshape(B, K, -1)
            V = logp.shape[-1]
            # finished beams propose exactly one candidate (their score,
            # continuing with eos/pad); live beams propose the vocab
            pad_tok = jnp.int32(max(eos_id, 0))
            cand = jnp.where(
                finished[..., None], _NEG, logp) + scores[..., None]
            keep_score = jnp.where(finished, scores, _NEG)
            # candidate matrix (B, K, V+1): last column = "stay finished"
            cand = jnp.concatenate([cand, keep_score[..., None]], -1)
            flat = cand.reshape(B, K * (V + 1))
            top_scores, top_idx = lax.top_k(flat, K)
            origin = top_idx // (V + 1)                     # (B, K)
            token = top_idx % (V + 1)
            stay = token == V
            token = jnp.where(stay, pad_tok, token).astype(jnp.int32)

            # finished-ness follows the reorder, then eos/stay extend it
            new_finished = (
                jnp.take_along_axis(finished, origin, axis=1)
                | stay | (jnp.asarray(eos_id >= 0) & (token == eos_id)))

            # reorder histories + caches by beam origin (per batch row)
            buf = jnp.take_along_axis(buf, origin[..., None], axis=1)
            buf = lax.dynamic_update_slice(
                buf, token[..., None], (0, 0, t + 1))
            flat_origin = (
                jnp.arange(B)[:, None] * K + origin).reshape(-1)
            caches = tuple(
                jnp.take(c, flat_origin, axis=1) for c in caches)
            return (buf, top_scores, new_finished, caches), None

        # beam phase starts at the LAST prompt position (its logits seed
        # the first expansion); scan range [Plen-1, max_len-1)
        (buf, scores, finished, _), _ = lax.scan(
            step, (buf, scores, finished, cache),
            jnp.arange(Plen - 1, max_len - 1))

        if length_penalty > 0.0:
            # generated length per beam (position of first eos, if any)
            gen = buf[:, :, Plen:]
            if eos_id >= 0:
                is_eos = gen == eos_id
                first = jnp.where(
                    is_eos.any(-1), is_eos.argmax(-1), gen.shape[-1])
            else:
                first = jnp.full(gen.shape[:2], gen.shape[-1])
            norm = ((5.0 + first.astype(jnp.float32)) / 6.0) \
                ** length_penalty
            scores = scores / jnp.maximum(norm, 1e-6)
        order = jnp.argsort(-scores, axis=1)
        buf = jnp.take_along_axis(buf, order[..., None], axis=1)
        scores = jnp.take_along_axis(scores, order, axis=1)
        return buf, scores

    def body(params, prompt):
        return _body(params, prompt, None)

    def body_padded(params, prompt, lens):
        return _body(params, prompt,
                     jnp.int32(prompt.shape[1]) - lens)

    fn = jax.jit(jax.shard_map(
        body,
        mesh=mesh_cfg.mesh,
        in_specs=(specs, batch_spec),
        out_specs=(batch_spec, batch_spec),
    ))
    lazy = {}

    def beam_search(params, prompt, prompt_lens=None):
        if prompt_lens is None:
            return fn(params, prompt)
        lens = _validate_prompt_lens(prompt, prompt_lens)
        if "padded" not in lazy:
            lazy["padded"] = jax.jit(jax.shard_map(
                body_padded,
                mesh=mesh_cfg.mesh,
                in_specs=(specs, batch_spec, batch_spec),
                out_specs=(batch_spec, batch_spec),
            ))
        return lazy["padded"](params, prompt, lens)

    beam_search._jitted = fn
    return beam_search
