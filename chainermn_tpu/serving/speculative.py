"""Speculative draft/verify decoding over the serving adapter protocol.

Decode is HBM-bound: one read of the target's weights per token.  A
cheap DRAFT model proposes ``k`` tokens per round and the target
verifies the whole chunk in ONE pass (``adapter.verify`` — one weights
read for up to ``k + 1`` committed tokens), so tokens/sec multiplies
by roughly the mean accepted length.  ``models.decoding`` already
ships this for the flagship transformer as a single fused program;
this module is the SERVING-TIER sibling, built on the engine's
decode-adapter protocol instead of ``TransformerConfig`` internals:

- **Any adapter pair.**  Drafter and target are two decode adapters
  (``make_cache`` / ``prefill`` / ``step`` / ``verify``).  Two MiniLM
  configs are the parity suite's oracle world;
  :class:`~chainermn_tpu.serving.engine.TransformerAdapter` carries
  the same ``verify`` surface for the flagship.
- **Exactness ladder.**  Greedy target ⇒ the output is exactly the
  target-only greedy decode: only verified argmax matches commit, and
  the corrective/bonus token is the target's own argmax (the
  ``_verify_and_commit`` contract, re-pinned here per adapter).
  Sampled target (``sampling=``) runs the standard Leviathan/Chen
  reject/resample: each proposal accepts with probability
  ``min(1, p_t'/p_d')`` on the temperature/top-k/top-p-filtered pair,
  a rejection draws from the residual ``max(0, p_t' − p_d')``, a
  fully-accepted round draws the bonus from ``p_t'`` — and the whole
  run replays bit-identically from ``(seed, params, prompt)``
  (:mod:`~chainermn_tpu.serving.sampling` key-stream discipline).
- **Observability.**  ``serve/spec_drafted`` / ``serve/spec_accepted``
  count every proposal and acceptance (their ratio IS the speedup
  lever); each round emits ``serve/draft`` and ``serve/verify``
  spans.

Host-driven rounds over jitted draft/verify programs, single request
per call — the standalone/offline tier.  For continuous serving, pass
``draft_adapter=`` to :class:`~chainermn_tpu.serving.engine.ServingEngine`
and the engine runs speculation as a ROUND MODE over its ragged
per-row position clocks (per-row acceptance, same counters); the
fused batch form lives in ``models.decoding``.  See docs/SERVING.md
"Speculative serving" and "Ragged rounds".
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from chainermn_tpu.utils.metrics import get_registry
from chainermn_tpu.utils.telemetry import get_recorder

from .sampling import SamplingParams, filter_logits

__all__ = ["SpecResult", "SpeculativeDecoder"]


@dataclasses.dataclass(eq=False)
class SpecResult:
    """One speculative generation: ``tokens`` are the generated tokens
    (first EOS kept, budget-truncated — the ``make_generate_fn``
    convention); the counters quantify the draft's worth (each round
    costs one draft k-step pass plus ONE target pass and commits
    ``1..k+1`` tokens)."""

    tokens: np.ndarray
    rounds: int
    drafted: int
    accepted: int

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / self.drafted if self.drafted else 0.0

    @property
    def tokens_per_round(self) -> float:
        return (int(self.tokens.shape[0]) / self.rounds
                if self.rounds else 0.0)


class SpeculativeDecoder:
    """Draft-k / verify-in-one-pass decoding over two decode adapters.

    Args:
      draft_adapter / draft_params: the cheap proposer (e.g. a small
        :class:`~chainermn_tpu.serving.minilm.MiniLMAdapter`).
      target_adapter / target_params: the model whose decode the
        output must reproduce.  Both adapters must expose ``verify``
        (chunk step with logits) in addition to the engine protocol.
      k: proposals per round.
      max_prompt / horizon: prompt capacity and cache length —
        prompts right-align into a fixed ``max_prompt`` window (one
        compiled prefill, the engine convention) and the cache holds
        ``horizon + k + 1`` positions (rounds may overshoot by a
        chunk).
      eos_id / pad_id: early-stop semantics, exactly
        ``make_generate_fn``'s.

    Single-request calls on plain (unsharded) arrays: the adapters'
    pure functions are used directly under ``jit``, so the decoder
    runs on any jax — no mesh, no vma requirement beyond what the
    adapters themselves impose.
    """

    def __init__(self, draft_adapter, draft_params, target_adapter,
                 target_params, *, k: int = 4, max_prompt: int,
                 horizon: int, eos_id: int = -1, pad_id: int = 0):
        if k < 1:
            raise ValueError(f"k={k} must be >= 1")
        if max_prompt < 1 or horizon <= max_prompt:
            raise ValueError(
                f"need max_prompt >= 1 < horizon, got {max_prompt} / "
                f"{horizon}")
        dv = getattr(getattr(draft_adapter, "cfg", None),
                     "vocab_size", None)
        tv = getattr(getattr(target_adapter, "cfg", None),
                     "vocab_size", None)
        if dv is not None and tv is not None and dv != tv:
            raise ValueError(f"draft vocab {dv} != target vocab {tv}")
        self.draft = draft_adapter
        self.d_params = draft_params
        self.target = target_adapter
        self.t_params = target_params
        self.k = int(k)
        self.max_prompt = int(max_prompt)
        self.horizon = int(horizon)
        self.eos_id = int(eos_id)
        self.pad_id = int(pad_id)
        self._jits = {}

    # -- jitted programs (cached per shape) ---------------------------- #

    def _jit(self, name, fn):
        if name not in self._jits:
            from chainermn_tpu.utils.programs import ledger_jit

            # ledger label: the program kind only — the adapter id in
            # a ("prefill", id) key is cache identity, not a label
            kind = name[0] if isinstance(name, tuple) else name
            self._jits[name] = ledger_jit(fn, label=f"spec/{kind}")
        return self._jits[name]

    def mark_steady(self) -> None:
        """Declare this decoder's ``spec/*`` programs steady-state in
        the program ledger (the ``ServingEngine.mark_steady``
        twin — the engine's ``serve/`` scope does NOT cover these):
        call after warmup generations have compiled the draft/verify
        programs for the splits you serve, and any further ``spec/``
        compile counts as ``compile/steady_retraces`` — the
        speculative half of the retrace-storm coverage.  A rebuild
        (new adapters) should ``get_ledger().forget("spec/")``,
        re-warm, re-mark."""
        from chainermn_tpu.utils.programs import get_ledger

        get_ledger().mark_steady("spec/")

    def _prefill(self, ad, params, kv_len, row, offs):
        def body(params, row, offs):
            caches = ad.make_cache(1, kv_len)
            return ad.prefill(params, caches, row[:, :-1], offs)

        return self._jit(("prefill", id(ad)), body)(params, row, offs)

    def _draft_round(self, d_cache, cur, pos, offs):
        """k greedy proposals + the trailing cache-fill step (a
        fully-accepted round must not leave a K/V hole at the last
        proposal's position — the ``models.decoding`` lesson)."""
        def body(params, d_cache, cur, pos, offs):
            props = []
            for j in range(self.k):
                logits, d_cache = self.draft.step(
                    params, d_cache, cur, pos + j, offs)
                cur = jnp.argmax(logits, -1).astype(jnp.int32)
                props.append(cur)
            _, d_cache = self.draft.verify(
                params, d_cache, cur[:, None], pos + self.k, offs,
                with_logits=False)
            return jnp.stack(props, 1), d_cache

        return self._jit("draft", body)(self.d_params, d_cache, cur,
                                        pos, offs)

    def _draft_round_sampled(self, d_cache, cur, pos, offs, keys,
                             temp, top_k, top_p):
        """k SAMPLED proposals with their filtered log-probs p_d′ —
        the draft side of the Leviathan/Chen pair."""
        def body(params, d_cache, cur, pos, offs, keys, temp, top_k,
                 top_p):
            props, lps = [], []
            for j in range(self.k):
                logits, d_cache = self.draft.step(
                    params, d_cache, cur, pos + j, offs)
                lp = jax.nn.log_softmax(filter_logits(
                    logits.astype(jnp.float32) / temp, top_k, top_p),
                    -1)
                cur = jax.random.categorical(keys[j], lp) \
                    .astype(jnp.int32)
                props.append(cur)
                lps.append(lp[0])
            _, d_cache = self.draft.verify(
                params, d_cache, cur[:, None], pos + self.k, offs,
                with_logits=False)
            return jnp.stack(props, 1), jnp.stack(lps, 0), d_cache

        return self._jit("draft_sampled", body)(
            self.d_params, d_cache, cur, pos, offs, keys, temp, top_k,
            top_p)

    def _verify(self, t_cache, chunk, pos, offs):
        def body(params, t_cache, chunk, pos, offs):
            return self.target.verify(params, t_cache, chunk, pos,
                                      offs)

        return self._jit("verify", body)(self.t_params, t_cache, chunk,
                                         pos, offs)

    def _target_step(self, t_cache, cur, pos, offs):
        def body(params, t_cache, cur, pos, offs):
            logits, t_cache = self.target.step(params, t_cache, cur,
                                               pos, offs)
            return jnp.argmax(logits, -1).astype(jnp.int32), t_cache

        return self._jit("tstep", body)(self.t_params, t_cache, cur,
                                        pos, offs)

    # -- public API ---------------------------------------------------- #

    def _layout(self, prompt):
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if not 1 <= prompt.shape[0] <= self.max_prompt:
            raise ValueError(
                f"prompt length {prompt.shape[0]} not in "
                f"[1, {self.max_prompt}]")
        row = np.full((1, self.max_prompt), max(self.pad_id, 0),
                      np.int32)
        row[0, self.max_prompt - prompt.shape[0]:] = prompt
        offs = jnp.asarray(
            [self.max_prompt - prompt.shape[0]], jnp.int32)
        return prompt, jnp.asarray(row), offs

    def _finish(self, out, rounds, drafted, accepted):
        toks = np.asarray(out, np.int32)
        if self.eos_id >= 0:
            hits = np.nonzero(toks == self.eos_id)[0]
            if hits.size:
                toks = toks[:int(hits[0]) + 1]
        reg = get_registry()
        reg.inc("serve/spec_drafted", drafted)
        reg.inc("serve/spec_accepted", accepted)
        return SpecResult(tokens=toks, rounds=rounds, drafted=drafted,
                          accepted=accepted)

    def target_decode(self, prompt, max_new: int) -> np.ndarray:
        """The target-only greedy decode (same layout, no draft) —
        the baseline a speculative run is measured against and the
        reference its greedy output must EQUAL."""
        prompt, row, offs = self._layout(prompt)
        kv = self.horizon + self.k + 1
        t_cache = self._prefill(self.target, self.t_params, kv, row,
                                offs)
        cur = jnp.asarray(prompt[-1:], jnp.int32)
        out = []
        pos = self.max_prompt - 1
        for _ in range(max_new):
            cur, t_cache = self._target_step(t_cache, cur,
                                             jnp.int32(pos), offs)
            out.append(int(cur[0]))
            pos += 1
            if self.eos_id >= 0 and out[-1] == self.eos_id:
                break
        return np.asarray(out, np.int32)

    def generate(self, prompt, max_new: int,
                 sampling: Optional[SamplingParams] = None
                 ) -> SpecResult:
        """Speculatively decode ``max_new`` tokens (fewer on EOS).
        Greedy without ``sampling``; with it, the draft proposes from
        its filtered distribution and the Leviathan/Chen test keeps
        the output distribution exactly the target's."""
        if max_new < 1:
            raise ValueError(f"max_new={max_new} must be >= 1")
        if self.max_prompt + max_new > self.horizon:
            raise ValueError(
                f"max_new={max_new} exceeds horizon - max_prompt = "
                f"{self.horizon - self.max_prompt}")
        prompt, row, offs = self._layout(prompt)
        kv = self.horizon + self.k + 1
        rec = get_recorder()
        t_cache = self._prefill(self.target, self.t_params, kv, row,
                                offs)
        d_cache = self._prefill(self.draft, self.d_params, kv, row,
                                offs)
        cur = jnp.asarray(prompt[-1:], jnp.int32)
        pos = self.max_prompt - 1
        out = []
        rounds = drafted = accepted = 0
        if sampling is not None:
            temp = jnp.float32(sampling.temperature)
            s_topk = jnp.int32(sampling.top_k)
            s_topp = jnp.float32(sampling.top_p)
            root = sampling.key()
        while len(out) < max_new:
            rounds += 1
            with rec.span("serve/draft", cat="serve", k=self.k,
                          step=pos):
                if sampling is None:
                    props, d_cache = self._draft_round(
                        d_cache, cur, jnp.int32(pos), offs)
                    d_lp = None
                else:
                    # the round's key fan: k draft draws + the
                    # accept/residual draws, all folded from the
                    # ROUND-START token index — schedule-free replay
                    rk = jax.random.fold_in(root, len(out))
                    dkeys = jax.random.split(rk, self.k + 2)
                    props, d_lp, d_cache = self._draft_round_sampled(
                        d_cache, cur, jnp.int32(pos), offs,
                        dkeys[:self.k], temp, s_topk, s_topp)
            chunk = jnp.concatenate([cur[:, None], props], axis=1)
            with rec.span("serve/verify", cat="serve", k=self.k,
                          step=pos):
                tlog, t_cache = self._verify(t_cache, chunk,
                                             jnp.int32(pos), offs)
            props_np = np.asarray(props[0])
            drafted += self.k
            if sampling is None:
                g = np.asarray(jnp.argmax(tlog[0], -1))    # (k+1,)
                n_acc = 0
                while n_acc < self.k and props_np[n_acc] == g[n_acc]:
                    n_acc += 1
                commit = list(props_np[:n_acc]) + [int(g[n_acc])]
            else:
                t_lp = jax.nn.log_softmax(filter_logits(
                    tlog[0].astype(jnp.float32) / temp, s_topk,
                    s_topp), -1)                           # (k+1, V)
                u = jax.random.uniform(dkeys[self.k], (self.k,),
                                       minval=1e-20)
                t_at = np.asarray(jnp.take_along_axis(
                    t_lp[:self.k], jnp.asarray(props_np)[:, None],
                    1)[:, 0])
                d_at = np.asarray(jnp.take_along_axis(
                    d_lp, jnp.asarray(props_np)[:, None], 1)[:, 0])
                acc = np.asarray(jnp.log(u)) < (t_at - d_at)
                n_acc = 0
                while n_acc < self.k and acc[n_acc]:
                    n_acc += 1
                t_p = jnp.exp(t_lp[n_acc])
                if n_acc < self.k:
                    # rejected at the cut: residual max(0, p_t′−p_d′)
                    d_p = jnp.exp(d_lp[n_acc])
                    resid = jnp.maximum(t_p - d_p, 0.0)
                    rs = resid.sum()
                    dist = jnp.where(rs > 1e-9, resid / rs, t_p)
                else:
                    dist = t_p                  # bonus draw from p_t′
                tok = int(jax.random.categorical(
                    dkeys[self.k + 1],
                    jnp.log(jnp.maximum(dist, 1e-30))))
                commit = list(props_np[:n_acc]) + [tok]
            accepted += n_acc
            # land the committed tokens; stale K/V beyond the cut is
            # overwritten by the next round's chunk before any query
            # can attend it (both caches cover [pos, pos+k])
            out.extend(int(t) for t in commit)
            cur = jnp.asarray([out[-1]], jnp.int32)
            pos += n_acc + 1
            if self.eos_id >= 0 \
                    and any(t == self.eos_id for t in commit):
                break
        return self._finish(out[:max_new], rounds, drafted, accepted)
