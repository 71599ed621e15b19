"""KV-cache autoregressive decoding: step-by-step cached logits must
match the full (non-cached) forward at every position, greedy generation
must be self-consistent, and the cache must carry GQA's shared-head
width.  Covers single-device, DP+TP meshes, GQA, and virtual-pipe
packed params."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chainermn_tpu import models
from chainermn_tpu.models import (
    TransformerConfig,
    init_transformer,
    quantize_params_int8,
    regroup_blocks,
    shard_params,
)
from chainermn_tpu.models.decoding import _decode_step
from chainermn_tpu.parallel import MeshConfig


def _built_once(make):
    """Every call of a ``make_*_fn`` is a jitted program of its own,
    compiled again whatever an earlier case compiled: the cases here ask
    for the same few (one-device greedy oracle, speculative k=3, the
    train step of ``_trained_host``) dozens of times, so keep one a
    (mesh, configs, options) for the module (by hand: a ``MeshConfig``
    does not hash, so ``functools.cache`` cannot key on it).  A refusal
    is raised before anything is kept."""
    built = {}

    @functools.wraps(make)
    def cached(mc, *cfgs, **kw):
        key = (mc.pipe, mc.data, mc.expert, mc.seq, mc.model,
               tuple(d.id for d in mc.mesh.devices.flat), cfgs,
               tuple(sorted(kw.items())))
        if key not in built:
            built[key] = make(mc, *cfgs, **kw)
        return built[key]

    return cached


make_forward_fn = _built_once(models.make_forward_fn)
make_generate_fn = _built_once(models.make_generate_fn)
make_beam_search_fn = _built_once(models.make_beam_search_fn)
make_speculative_generate_fn = _built_once(
    models.make_speculative_generate_fn)
make_lookup_generate_fn = _built_once(models.make_lookup_generate_fn)
make_train_step = _built_once(models.make_train_step)


VOCAB, B, T = 64, 4, 16


def tiny_cfg(**kw):
    base = dict(
        vocab_size=VOCAB, d_model=32, n_heads=4, d_head=8, d_ff=64,
        n_layers=2, max_seq=T, attention="local", dtype="float32",
        remat=False,
    )
    base.update(kw)
    return TransformerConfig(**base)


def prompt(seed=0, length=T):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, VOCAB, (B, length)),
        jnp.int32)


_ADAM = optax.adam(1e-2)


@functools.cache
def _trained_host(cfg, seed):
    """``cfg`` from ``seed`` after 30 Adam steps, as numpy: the same
    dozen (config, seed) pairs are asked for by forty cases, and the
    steps are deterministic."""
    one = MeshConfig(data=1, devices=jax.devices()[:1])
    params = shard_params(
        one, cfg, init_transformer(jax.random.PRNGKey(seed), cfg))
    st = jax.jit(_ADAM.init)(params)
    step = make_train_step(one, cfg, _ADAM)
    x = jnp.asarray(
        (np.arange(B * (T + 1)).reshape(B, T + 1) * 7 + 3) % VOCAB,
        jnp.int32)
    for _ in range(30):
        params, st, _ = step(params, st, x[:, :T], x[:, 1:])
    return jax.tree.map(np.asarray, params)


def _cached_logits_all_positions(cfg, params, toks, mc):
    """Teacher-forced decode: feed toks one at a time through the cached
    step, collecting the logits at each position."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu.models import param_specs

    def body(params, toks):
        Bl, Tl = toks.shape
        mp = 1
        for d in ("model",):
            mp *= lax.axis_size(d)
        Hkvl = cfg.kv_heads // mp
        from chainermn_tpu.models.decoding import _make_cache

        R = lax.axis_size("seq")
        caches = _make_cache(cfg, Bl, Tl // R, Hkvl, cfg.n_layers)

        def step(caches, t):
            logits, caches = _decode_step(cfg, params, caches,
                                          toks[:, t], t)
            return caches, logits

        _, logits = lax.scan(step, caches, jnp.arange(Tl))
        return logits.transpose(1, 0, 2)      # (B, T, V)

    fn = jax.jit(jax.shard_map(
        body, mesh=mc.mesh,
        in_specs=(param_specs(cfg), P(("data", "expert"))),
        out_specs=P(("data", "expert"))))
    return fn(params, toks)


@pytest.mark.parametrize("axes,kw", [
    (dict(data=1), {}),
    (dict(data=4, model=2), {}),
    (dict(data=4, model=2), dict(n_kv_heads=2)),
    (dict(data=2, seq=2), {}),
    (dict(data=2, seq=2, model=2), dict(n_kv_heads=2)),
    (dict(data=2, seq=2), dict(attention_window=6)),
], ids=["single", "dp-tp", "gqa-tp", "seq-kv", "seq-kv-gqa-tp",
        "seq-kv-window"])
def test_cached_matches_full_forward(axes, kw):
    cfg = tiny_cfg(**kw)
    n_dev = int(np.prod(list(axes.values())))
    mc = MeshConfig(**axes, devices=jax.devices()[:n_dev])
    host = init_transformer(jax.random.PRNGKey(0), cfg)
    toks = prompt()
    # oracle on a seq=1 mesh: attention="local" under a real seq axis
    # would be shard-local, not full causal
    one = MeshConfig(data=1, devices=jax.devices()[:1])
    full = make_forward_fn(one, cfg)(shard_params(one, cfg, host), toks)
    cached = _cached_logits_all_positions(
        cfg, shard_params(mc, cfg, host), toks, mc)
    np.testing.assert_allclose(
        np.asarray(cached), np.asarray(full), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("top_k", [1, 2])
def test_moe_cached_matches_full_forward(top_k):
    """MoE decode must route the way the model was TRAINED (a top-2
    checkpoint decoded top-1 silently diverges): with ample capacity the
    teacher-forced cached logits equal the training forward for both
    router modes."""
    cfg = tiny_cfg(moe=True, n_experts=2, router_top_k=top_k,
                   capacity_factor=4.0)
    mc = MeshConfig(data=2, expert=2, devices=jax.devices()[:4])
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(3), cfg))
    toks = prompt(seed=5)
    full = make_forward_fn(mc, cfg)(params, toks)
    cached = _cached_logits_all_positions(cfg, params, toks, mc)
    np.testing.assert_allclose(
        np.asarray(cached), np.asarray(full), rtol=2e-4, atol=2e-4)


def test_greedy_generation_consistent():
    """Greedy generate: every generated token must be the argmax of the
    full forward logits over its prefix (self-consistency oracle)."""
    cfg = tiny_cfg()
    mc = MeshConfig(data=1, devices=jax.devices()[:1])
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
    Plen = 4
    p = prompt(length=Plen)
    gen = make_generate_fn(mc, cfg, max_len=12)
    out = gen(params, p)
    assert out.shape == (B, 12)
    np.testing.assert_array_equal(np.asarray(out[:, :Plen]),
                                  np.asarray(p))
    fwd = make_forward_fn(mc, cfg)
    out_np = np.asarray(out)
    for t in range(Plen, 12):
        prefix = jnp.asarray(
            np.pad(out_np[:, :t], ((0, 0), (0, T - t))), jnp.int32)
        logits = np.asarray(fwd(params, prefix))[:, t - 1]
        np.testing.assert_array_equal(out_np[:, t],
                                      logits.argmax(-1))


def test_sampling_needs_key_and_differs():
    cfg = tiny_cfg()
    mc = MeshConfig(data=1, devices=jax.devices()[:1])
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
    gen = make_generate_fn(mc, cfg, max_len=12, temperature=1.0)
    with pytest.raises(ValueError, match="PRNG"):
        gen(params, prompt(length=4))
    a = gen(params, prompt(length=4), key=jax.random.PRNGKey(1))
    b = gen(params, prompt(length=4), key=jax.random.PRNGKey(2))
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_filter_logits_masks_expected_sets():
    from chainermn_tpu.models.decoding import _NEG, _filter_logits

    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.15, 0.05]]))
    # top_k keeps exactly the k best
    out = np.asarray(_filter_logits(logits, 2, 1.0))
    assert (out[0, :2] > _NEG / 2).all() and (out[0, 2:] <= _NEG / 2).all()
    # nucleus: the first rank reaching 0.7 mass is included, rest cut
    # (0.7 sits strictly between the 0.5 and 0.8 cumulative masses, so
    # fp32 rounding of the log->softmax->cumsum roundtrip can't flip
    # membership at the boundary)
    out = np.asarray(_filter_logits(logits, 0, 0.7))
    assert (out[0, :2] > _NEG / 2).all() and (out[0, 2:] <= _NEG / 2).all()
    # k beyond the vocab is a no-op, not an index error
    np.testing.assert_array_equal(
        np.asarray(_filter_logits(logits, 99, 1.0)), np.asarray(logits))
    # off-filters are the identity
    np.testing.assert_array_equal(
        np.asarray(_filter_logits(logits, 0, 1.0)), np.asarray(logits))
    # filters compose: top_k=1 dominates a loose nucleus
    out = np.asarray(_filter_logits(logits, 1, 0.99))
    assert (out[0, 1:] <= _NEG / 2).all()
    # the sampling path filters AFTER temperature (HF convention): a
    # hot temperature flattens the distribution and WIDENS the nucleus
    # — at T=4 the 0.7-mass set grows from 2 tokens to 3
    out = np.asarray(_filter_logits(logits / 4.0, 0, 0.7))
    assert (out[0, :3] > _NEG / 2).all() and out[0, 3] <= _NEG / 2


def test_top_k1_sampling_is_greedy():
    """top_k=1 sampling must reproduce greedy token-for-token at any
    temperature (only the argmax survives the filter)."""
    cfg = tiny_cfg()
    mc = MeshConfig(data=1, devices=jax.devices()[:1])
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
    p = prompt(length=4)
    greedy = make_generate_fn(mc, cfg, max_len=12)(params, p)
    topk1 = make_generate_fn(
        mc, cfg, max_len=12, temperature=5.0, top_k=1)(
        params, p, key=jax.random.PRNGKey(7))
    np.testing.assert_array_equal(np.asarray(topk1), np.asarray(greedy))


def test_sampling_filter_validation():
    cfg = tiny_cfg()
    mc = MeshConfig(data=1, devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="temperature"):
        make_generate_fn(mc, cfg, max_len=12, top_k=5)
    with pytest.raises(ValueError, match="top_p"):
        make_generate_fn(mc, cfg, max_len=12, temperature=1.0, top_p=0.0)


def test_decode_mesh_validation():
    cfg = tiny_cfg()
    # seq-KV blocks the cache over seq: max_len must divide evenly
    with pytest.raises(ValueError, match="divisible by the seq"):
        make_generate_fn(MeshConfig(seq=2, data=4), cfg, max_len=T - 1)
    with pytest.raises(ValueError, match="max_len"):
        make_generate_fn(
            MeshConfig(data=1, devices=jax.devices()[:1]), cfg,
            max_len=T + 1)


def test_seq_kv_generate_matches_single_device():
    """Greedy generation with the KV cache blocked over the seq axis is
    token-identical to single-device decode (the R× cache capacity is
    an implementation detail, not a semantics change)."""
    cfg = tiny_cfg()
    host = init_transformer(jax.random.PRNGKey(4), cfg)
    p = prompt(seed=9, length=4)

    one = MeshConfig(data=1, devices=jax.devices()[:1])
    ref = make_generate_fn(one, cfg, max_len=12)(
        shard_params(one, cfg, host), p)

    mc = MeshConfig(data=2, seq=4)
    got = make_generate_fn(mc, cfg, max_len=12)(
        shard_params(mc, cfg, host), p)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("axes,kw", [
    (dict(data=1), {}),
    (dict(data=2, seq=2), dict(n_kv_heads=2)),
    (dict(pipe=2, data=2), {}),
    (dict(data=1), dict(moe=True, n_experts=2, capacity_factor=4.0)),
], ids=["single", "seq-kv-gqa", "pipe", "moe"])
def test_batched_prefill_matches_per_token(axes, kw):
    """Batched prefill (one multi-token chunk through _decode_step)
    must leave the cache in exactly the state the per-token scan does:
    the next step's logits are identical.

    The MoE case pins capacity_factor=4.0 DELIBERATELY: at ample
    capacity nothing drops and the two prefills are exact; at a finite
    factor chunk routing shares one B·Tq slot budget (training-forward
    semantics) while per-token stepping budgets per position, so drops
    can differ — a documented semantics choice (see _decode_step),
    not an equivalence this test could assert."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu.models import param_specs
    from chainermn_tpu.models.decoding import _decode_step, _make_cache

    cfg = tiny_cfg(**kw)
    pipe = axes.get("pipe", 1)
    n_dev = int(np.prod(list(axes.values())))
    mc = MeshConfig(**axes, devices=jax.devices()[:n_dev])
    params = shard_params(
        mc, cfg, init_transformer(jax.random.PRNGKey(6), cfg, pipe))
    toks = prompt(seed=11)

    def body(params, tk):
        Bl, Tn = tk.shape
        R = lax.axis_size("seq")
        Hkvl = cfg.kv_heads // lax.axis_size("model")
        Ll = jax.tree.leaves(params["blocks"])[0].shape[1]

        def run(batched):
            caches = _make_cache(cfg, Bl, Tn // R, Hkvl, Ll)
            if batched:
                _, caches = _decode_step(
                    cfg, params, caches, tk[:, :Tn - 1], 0,
                    with_logits=False)
            else:
                def stepf(c, t):
                    _, c = _decode_step(cfg, params, c, tk[:, t], t)
                    return c, None

                caches, _ = lax.scan(stepf, caches, jnp.arange(Tn - 1))
            logits, _ = _decode_step(
                cfg, params, caches, tk[:, Tn - 1], Tn - 1)
            return logits

        return run(True), run(False)

    fn = jax.jit(jax.shard_map(
        body, mesh=mc.mesh,
        in_specs=(param_specs(cfg), P(("data", "expert"))),
        out_specs=(P(("data", "expert")), P(("data", "expert")))))
    a, b = fn(params, toks)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-4, atol=2e-4)


def test_seq_kv_beam_matches_single_device():
    """Beam search with the length-blocked cache: token- and
    score-identical to the seq=1 oracle (the beam path reorders caches
    per step — the reorder must commute with the seq blocking)."""
    cfg = tiny_cfg()
    host = init_transformer(jax.random.PRNGKey(5), cfg)
    p = prompt(seed=10, length=4)

    one = MeshConfig(data=1, devices=jax.devices()[:1])
    ot, os_ = make_beam_search_fn(one, cfg, beam_size=2, max_len=T)(
        shard_params(one, cfg, host), p)

    mc = MeshConfig(data=2, seq=2, devices=jax.devices()[:4])
    gt, gs = make_beam_search_fn(mc, cfg, beam_size=2, max_len=T)(
        shard_params(mc, cfg, host), p)
    np.testing.assert_array_equal(np.asarray(gt), np.asarray(ot))
    np.testing.assert_allclose(np.asarray(gs), np.asarray(os_),
                               rtol=1e-5, atol=1e-6)


class TestEosEarlyStop:
    """eos_id early stopping: frozen rows pad, unfrozen rows are
    bit-identical to the no-eos run (per-row computations are
    independent), prompt eos is ignored, and the sharded while-loop's
    pmax stop flag agrees across meshes."""

    PAD = 7

    def _expected(self, ref, Plen, eos):
        exp = np.asarray(ref).copy()
        for b in range(exp.shape[0]):
            hits = np.where(exp[b, Plen:] == eos)[0]
            if hits.size:
                exp[b, Plen + hits[0] + 1:] = self.PAD
        return exp

    def _run(self, axes, n_dev):
        cfg = tiny_cfg()
        host = init_transformer(jax.random.PRNGKey(6), cfg)
        # a prompt CONTAINING candidate eos values must not freeze rows
        p = prompt(seed=20, length=4)
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        ref = np.asarray(
            make_generate_fn(one, cfg, max_len=T)(
                shard_params(one, cfg, host), p))
        # eos = a token some row actually generates mid-sequence
        eos = int(ref[0, 6])
        mc = MeshConfig(**axes, devices=jax.devices()[:n_dev])
        got = np.asarray(
            make_generate_fn(mc, cfg, max_len=T, eos_id=eos,
                             pad_id=self.PAD)(
                shard_params(mc, cfg, host), p))
        np.testing.assert_array_equal(got, self._expected(ref, 4, eos))
        return ref, p, host, cfg

    def test_single_device_freeze_and_pad(self):
        self._run(dict(data=1), 1)

    def test_sharded_batch_mesh(self):
        # rows finish at different times across shards; the pmax stop
        # flag must keep every shard stepping until the global last row
        self._run(dict(data=2, model=2), 4)

    def test_eos_never_fires_matches_plain(self):
        cfg = tiny_cfg()
        host = init_transformer(jax.random.PRNGKey(6), cfg)
        p = prompt(seed=21, length=4)
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        params = shard_params(one, cfg, host)
        ref = np.asarray(
            make_generate_fn(one, cfg, max_len=T)(params, p))
        unused = [v for v in range(VOCAB)
                  if v not in np.asarray(ref)][0]
        got = np.asarray(
            make_generate_fn(one, cfg, max_len=T, eos_id=unused,
                             pad_id=self.PAD)(params, p))
        np.testing.assert_array_equal(got, ref)

    def test_validation(self):
        cfg = tiny_cfg()
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        with pytest.raises(ValueError, match="eos_id"):
            make_generate_fn(one, cfg, max_len=T, eos_id=VOCAB)
        with pytest.raises(ValueError, match="pad_id"):
            make_generate_fn(one, cfg, max_len=T, eos_id=1,
                             pad_id=VOCAB)
        # pad MAY alias eos (HF GPT-2 convention: pad_token ==
        # eos_token) — trim-at-first-eos disambiguates, so this must
        # build without error
        make_generate_fn(one, cfg, max_len=T, eos_id=1, pad_id=1)


class TestPaddedPrompts:
    """Left-padded variable-length prompts: every row must generate
    exactly the tokens its UNPADDED solo run would — per-row position
    origins and the pad-slot attention mask together make padding
    invisible to the model."""

    def _rows_vs_solo(self, cfg, axes, n_dev):
        host = init_transformer(jax.random.PRNGKey(7), cfg)
        P_len, G = 6, 6                     # prompt slots, new tokens
        rng = np.random.RandomState(30)
        lens = np.asarray([6, 4, 2, 5])
        rows = [rng.randint(0, VOCAB, (n,)).astype(np.int32)
                for n in lens]
        padded = np.full((B, P_len), 63, np.int32)   # junk pad tokens
        for b, r in enumerate(rows):
            padded[b, P_len - lens[b]:] = r

        mc = MeshConfig(**axes, devices=jax.devices()[:n_dev])
        got = np.asarray(
            make_generate_fn(mc, cfg, max_len=P_len + G)(
                shard_params(mc, cfg, host), jnp.asarray(padded),
                prompt_lens=lens))
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        sparams = shard_params(one, cfg, host)
        for b, r in enumerate(rows):
            solo = np.asarray(
                make_generate_fn(one, cfg, max_len=lens[b] + G)(
                    sparams, jnp.tile(r, (B, 1))))
            np.testing.assert_array_equal(
                got[b, P_len:], solo[0, lens[b]:],
                err_msg=f"row {b} (len {lens[b]})")

    def test_rope_single_device(self):
        self._rows_vs_solo(tiny_cfg(pos_embedding="rope"),
                           dict(data=1), 1)

    def test_learned_positions(self):
        self._rows_vs_solo(tiny_cfg(), dict(data=1), 1)

    def test_tp_sharded_mesh(self):
        self._rows_vs_solo(tiny_cfg(pos_embedding="rope"),
                           dict(data=2, model=2), 4)

    def test_window_attention(self):
        # slot distance == per-row distance, so the sliding window
        # needs no offset — pin that claim
        self._rows_vs_solo(tiny_cfg(pos_embedding="rope",
                                    attention_window=4),
                           dict(data=1), 1)

    def test_int8_kv_cache_composes(self):
        """Padded rows with an int8 KV cache still decode row-for-row
        identically to their int8 solo runs — quantisation is
        per-(token, head), so the pad-slot masking and per-row
        position origins are orthogonal to it."""
        self._rows_vs_solo(
            tiny_cfg(pos_embedding="rope", kv_cache_dtype="int8"),
            dict(data=1), 1)

    def test_beam_search_int8_kv_padded_rows_match_solo(self):
        """Beam search × int8 KV cache × ragged prompts: the per-step
        cache-reorder gather maps uniformly over the cache tuple, so
        the int8 values AND their per-(token, head) scales follow each
        hypothesis — every row's beam TOKENS equal its int8-KV solo
        run.  Scores get a quantisation-width tolerance: the padded
        program prefills through the cache-attending path (deeper
        layers' prompt K/V derive from attention over DEQUANTIZED int8
        reads) while the solo run's fast path attends the raw chunk —
        an inherent ~1e-3 divergence on cumulative log-probs, not a
        reorder bug."""
        self._beam_padded_vs_solo(
            tiny_cfg(pos_embedding="rope", kv_cache_dtype="int8"),
            score_rtol=1e-3, score_atol=1e-2)

    def test_beam_search_padded_rows_match_solo(self):
        """Beam search with prompt_lens: every row's K hypotheses and
        scores equal its unpadded solo beam run — the per-row offsets
        ride through the beam reorder gathers untouched."""
        self._beam_padded_vs_solo(tiny_cfg(pos_embedding="rope"))

    def _beam_padded_vs_solo(self, cfg, score_rtol=1e-5,
                             score_atol=1e-5):
        host = init_transformer(jax.random.PRNGKey(7), cfg)
        P_len, G, K = 6, 6, 2
        rng = np.random.RandomState(32)
        lens = np.asarray([6, 4, 2, 5])
        rows = [rng.randint(0, VOCAB, (n,)).astype(np.int32)
                for n in lens]
        padded = np.full((B, P_len), 63, np.int32)
        for b, r in enumerate(rows):
            padded[b, P_len - lens[b]:] = r

        one = MeshConfig(data=1, devices=jax.devices()[:1])
        params = shard_params(one, cfg, host)
        toks, scores = make_beam_search_fn(
            one, cfg, beam_size=K, max_len=P_len + G)(
            params, jnp.asarray(padded), prompt_lens=lens)
        for b, r in enumerate(rows):
            st, ss = make_beam_search_fn(
                one, cfg, beam_size=K, max_len=lens[b] + G)(
                params, jnp.tile(r, (B, 1)))
            np.testing.assert_array_equal(
                np.asarray(toks)[b, :, P_len:],
                np.asarray(st)[0, :, lens[b]:],
                err_msg=f"row {b}")
            np.testing.assert_allclose(
                np.asarray(scores)[b], np.asarray(ss)[0],
                rtol=score_rtol, atol=score_atol)

    def test_equal_lens_match_plain_path(self):
        """prompt_lens = full length everywhere must reproduce the
        plain (unpadded) program token-for-token."""
        cfg = tiny_cfg(pos_embedding="rope")
        host = init_transformer(jax.random.PRNGKey(7), cfg)
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        params = shard_params(one, cfg, host)
        p = prompt(seed=31, length=5)
        gen = make_generate_fn(one, cfg, max_len=12)
        np.testing.assert_array_equal(
            np.asarray(gen(params, p,
                           prompt_lens=np.full(B, 5))),
            np.asarray(gen(params, p)))

    def test_validation(self):
        cfg = tiny_cfg()
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        gen = make_generate_fn(one, cfg, max_len=12)
        params = shard_params(
            one, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
        with pytest.raises(ValueError, match="prompt_lens"):
            gen(params, prompt(length=4), prompt_lens=np.zeros(B, int))
        with pytest.raises(ValueError, match="prompt_lens"):
            gen(params, prompt(length=4), prompt_lens=np.full(B, 9))
        with pytest.raises(ValueError, match="sequence-parallel"):
            make_generate_fn(
                MeshConfig(seq=2, data=4), cfg, max_len=16)(
                shard_params(MeshConfig(seq=2, data=4), cfg,
                             init_transformer(jax.random.PRNGKey(0),
                                              cfg)),
                prompt(length=4), prompt_lens=np.full(B, 4))


class TestSpeculative:
    """Greedy speculative decoding: the draft model affects SPEED only
    — output must be token-identical to the target's own greedy decode
    no matter how good or bad the draft is.

    Targets are TRAINED briefly first: the chunk-verify computes the
    same logits as per-token stepping up to fp reassociation, and a
    random-init model's argmax gaps sit inside that noise — a few SGD
    steps make the argmax decisive (the realistic regime; near-tie
    flips are an fp artifact, not a speculative-logic property)."""

    def _target_greedy(self, cfg, host, p, max_len):
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        return np.asarray(
            make_generate_fn(one, cfg, max_len=max_len)(
                shard_params(one, cfg, host), p))

    @pytest.mark.parametrize("k", [1, 3, 5])
    def test_perfect_draft_matches_greedy(self, k):
        """Draft == target: every proposal verifies, rounds stride k+1
        — and the tokens are exactly the greedy sequence."""
        cfg = tiny_cfg()
        host = _trained_host(cfg, 0)
        p = prompt(seed=12, length=4)
        ref = self._target_greedy(cfg, host, p, T)

        one = MeshConfig(data=1, devices=jax.devices()[:1])
        spec = make_speculative_generate_fn(one, cfg, cfg, k=k,
                                            max_len=T, with_stats=True)
        params = shard_params(one, cfg, host)
        got, mean_acc = spec(params, params, p)
        np.testing.assert_array_equal(np.asarray(got), ref)
        # a perfect draft's proposals all verify: acceptance == k
        assert float(mean_acc) == pytest.approx(k), float(mean_acc)

    def test_weak_draft_still_matches_greedy(self, ):
        """A DIFFERENT (shallower, differently-initialised) draft:
        acceptance is partial and the corrective path runs — output
        still exactly the target's greedy tokens."""
        cfg = tiny_cfg(n_layers=4)
        d_cfg = tiny_cfg(n_layers=2)
        host = _trained_host(cfg, 0)
        d_host = _trained_host(d_cfg, 9)
        p = prompt(seed=13, length=4)
        ref = self._target_greedy(cfg, host, p, T)

        one = MeshConfig(data=1, devices=jax.devices()[:1])
        spec = make_speculative_generate_fn(one, cfg, d_cfg, k=3,
                                            max_len=T, with_stats=True)
        got, mean_acc = spec(shard_params(one, cfg, host),
                             shard_params(one, d_cfg, d_host), p)
        np.testing.assert_array_equal(np.asarray(got), ref)
        assert 0.0 <= float(mean_acc) <= 3.0

    def test_tp_mesh_matches_greedy(self):
        cfg = tiny_cfg(n_layers=4)
        d_cfg = tiny_cfg(n_layers=2)
        host = _trained_host(cfg, 1)
        d_host = _trained_host(d_cfg, 8)
        p = prompt(seed=14, length=4)
        ref = self._target_greedy(cfg, host, p, T)

        mc = MeshConfig(data=2, model=2, devices=jax.devices()[:4])
        spec = make_speculative_generate_fn(mc, cfg, d_cfg, k=3,
                                            max_len=T)
        got = np.asarray(spec(shard_params(mc, cfg, host),
                              shard_params(mc, d_cfg, d_host), p))
        np.testing.assert_array_equal(got, ref)

    def test_vocab_parallel_mesh_matches_greedy(self):
        """Speculative decode over Megatron vocab TP: the verify
        chunk's (B, k+1, V/M) logits shards all-gather to full width
        before the argmax compare — tokens equal the plain (non-vp)
        greedy oracle exactly."""
        import dataclasses

        cfg = tiny_cfg(n_layers=4)
        d_cfg = tiny_cfg(n_layers=2)
        host = _trained_host(cfg, 1)
        d_host = _trained_host(d_cfg, 8)
        p = prompt(seed=14, length=4)
        ref = self._target_greedy(cfg, host, p, T)

        vp = dataclasses.replace(cfg, vocab_parallel=True)
        d_vp = dataclasses.replace(d_cfg, vocab_parallel=True)
        mc = MeshConfig(data=2, model=2, devices=jax.devices()[:4])
        got = np.asarray(make_speculative_generate_fn(
            mc, vp, d_vp, k=3, max_len=T)(
            shard_params(mc, vp, host),
            shard_params(mc, d_vp, d_host), p))
        np.testing.assert_array_equal(got, ref)

    def test_pipe_mesh_matches_greedy(self):
        """PP-decode composes: the verify chunk rides the S-phase
        ppermute hand-off with stage-masked cache writes."""
        cfg = tiny_cfg(n_layers=4)
        d_cfg = tiny_cfg(n_layers=2)
        host = _trained_host(cfg, 2)
        d_host = _trained_host(d_cfg, 7)
        p = prompt(seed=15, length=4)
        ref = self._target_greedy(cfg, host, p, T)

        mc = MeshConfig(pipe=2, data=2, devices=jax.devices()[:4])
        spec = make_speculative_generate_fn(mc, cfg, d_cfg, k=3,
                                            max_len=T)
        got = np.asarray(spec(
            shard_params(mc, cfg, dict(host, blocks=regroup_blocks(
                host["blocks"], 1, 2))),
            shard_params(mc, d_cfg, dict(d_host, blocks=regroup_blocks(
                d_host["blocks"], 1, 2))), p))
        np.testing.assert_array_equal(got, ref)

    def test_int8_matches_int8_greedy(self):
        """Weight-only int8 target + draft: tokens equal the int8
        target's own greedy decode (int8 changes the logits, so the
        oracle is the QUANTIZED greedy run)."""
        cfg = tiny_cfg(n_layers=4)
        d_cfg = tiny_cfg(n_layers=2)
        host = quantize_params_int8(cfg, _trained_host(cfg, 3))
        d_host = quantize_params_int8(d_cfg, _trained_host(d_cfg, 6))
        p = prompt(seed=16, length=4)

        one = MeshConfig(data=1, devices=jax.devices()[:1])
        ref = np.asarray(
            make_generate_fn(one, cfg, max_len=T, quantized=True)(
                shard_params(one, cfg, host), p))
        spec = make_speculative_generate_fn(
            one, cfg, d_cfg, k=3, max_len=T, quantized=True,
            draft_quantized=True)
        got = np.asarray(spec(shard_params(one, cfg, host),
                              shard_params(one, d_cfg, d_host), p))
        np.testing.assert_array_equal(got, ref)

    def test_int8_kv_cache_matches_int8_kv_greedy(self):
        """Speculative decode over an int8 KV cache: the verify
        chunk's writes quantize per-(token, head) exactly like the
        per-token oracle's, and both read back dequantized — tokens
        equal the int8-KV greedy run (that quantized run is the right
        oracle; int8-KV changes the logits)."""
        import dataclasses

        cfg = tiny_cfg(n_layers=4, kv_cache_dtype="int8")
        d_cfg = tiny_cfg(n_layers=2, kv_cache_dtype="int8")
        host = _trained_host(
            dataclasses.replace(cfg, kv_cache_dtype=""), 3)
        d_host = _trained_host(
            dataclasses.replace(d_cfg, kv_cache_dtype=""), 6)
        p = prompt(seed=19, length=4)
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        params = shard_params(one, cfg, host)
        ref = np.asarray(
            make_generate_fn(one, cfg, max_len=T)(params, p))
        got = np.asarray(make_speculative_generate_fn(
            one, cfg, d_cfg, k=3, max_len=T)(
            params, shard_params(one, d_cfg, d_host), p))
        np.testing.assert_array_equal(got, ref)

    def test_truncated_cheap_draft_speeds_and_matches(self):
        """The ``bench_decode.py --cheap-draft`` construction at test
        scale: a target whose deep-layer residual outputs are damped, a
        draft made of its first layers + shared embed/final norm.  The
        draft's function then tracks the target's (the regime a trained
        big-model draft earns — a 30-step tiny model's truncated prefix
        is NOT predictive on its own, acceptance 0.0, verified while
        writing this test), so this pins the two properties the bench
        row rests on: acceptance well above the random floor, and
        token-exact greedy output regardless."""
        cfg = tiny_cfg(n_layers=4)
        d_cfg = tiny_cfg(n_layers=2)
        host = _trained_host(cfg, 0)

        def damp(name, a):
            if name not in ("wo", "w2"):
                return a
            scale = np.where(np.arange(a.shape[1]) < 2, 1.0,
                             0.003).astype(a.dtype)
            return a * scale.reshape(1, -1, *([1] * (a.ndim - 2)))

        host = dict(host, blocks={
            k: damp(k, v) for k, v in host["blocks"].items()})
        d_host = dict(host, blocks=jax.tree.map(
            lambda a: a[:, :2], host["blocks"]))
        p = prompt(seed=17, length=4)
        ref = self._target_greedy(cfg, host, p, T)

        one = MeshConfig(data=1, devices=jax.devices()[:1])
        spec = make_speculative_generate_fn(one, cfg, d_cfg, k=4,
                                            max_len=T, with_stats=True)
        got, mean_acc = spec(shard_params(one, cfg, host),
                             shard_params(one, d_cfg, d_host), p)
        np.testing.assert_array_equal(np.asarray(got), ref)
        assert float(mean_acc) > 2.0, float(mean_acc)

    def test_sampling_distribution_matches_target(self):
        """Speculative SAMPLING must be distribution-identical to
        sampling the target directly (the Leviathan/Chen guarantee).
        First generated token vs the target's TRUE softmax (forward
        pass), 1000 samples over fixed seeds — deterministic, cannot
        flake, and tight enough to catch the batch-min-cut bug this
        test originally found (committing a fresh p_t draw instead of
        the accepted proposal at an early cut measured TV 0.156 here;
        the exact scheme measures ~0.077 against ~0.085 expected
        noise)."""
        cfg = tiny_cfg(n_layers=2)
        d_cfg = tiny_cfg(n_layers=1)
        host = _trained_host(cfg, 0)
        d_host = _trained_host(d_cfg, 9)
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        params = shard_params(one, cfg, host)
        d_params = shard_params(one, d_cfg, d_host)
        # identical rows: each call yields B exact samples of the
        # first generated token (per-row randomness is independent;
        # the shared batch-min cut only shapes later ROUND boundaries)
        row = np.random.RandomState(50).randint(0, VOCAB, 4)
        p = jnp.asarray(np.tile(row, (B, 1)), jnp.int32)
        TEMP, CALLS = 1.5, 250

        fwd = make_forward_fn(one, cfg)
        full = jnp.asarray(np.pad(np.asarray(p), ((0, 0), (0, T - 4))))
        true_p = np.exp(jax.nn.log_softmax(
            np.asarray(fwd(params, full))[0, 3] / TEMP))
        spec = make_speculative_generate_fn(
            one, cfg, d_cfg, k=2, max_len=5, temperature=TEMP)
        h = np.zeros(VOCAB)
        for i in range(CALLS):
            out = np.asarray(
                spec(params, d_params, p, key=jax.random.PRNGKey(i)))
            for b in range(B):
                h[out[b, 4]] += 1
        n = CALLS * B
        tv = 0.5 * np.abs(h / n - true_p).sum()
        noise = 0.5 * np.sqrt(2 * true_p / (np.pi * n)).sum()
        assert tv < 1.6 * noise + 0.02, (tv, noise)

    def test_sampling_runs_sharded_and_needs_key(self):
        cfg = tiny_cfg(n_layers=4)
        d_cfg = tiny_cfg(n_layers=2)
        host = _trained_host(cfg, 1)
        d_host = _trained_host(d_cfg, 8)
        mc = MeshConfig(data=2, model=2, devices=jax.devices()[:4])
        spec = make_speculative_generate_fn(
            mc, cfg, d_cfg, k=3, max_len=T, temperature=0.8,
            with_stats=True)
        params = shard_params(mc, cfg, host)
        d_params = shard_params(mc, d_cfg, d_host)
        p = prompt(seed=51, length=4)
        with pytest.raises(ValueError, match="PRNG"):
            spec(params, d_params, p)
        a, acc_a = spec(params, d_params, p, key=jax.random.PRNGKey(1))
        b, _ = spec(params, d_params, p, key=jax.random.PRNGKey(2))
        assert (np.asarray(a) < VOCAB).all()
        assert 0.0 <= float(acc_a) <= 3.0
        # prompt preserved, different keys draw different sequences
        np.testing.assert_array_equal(np.asarray(a)[:, :4], np.asarray(p))
        assert not np.array_equal(np.asarray(a), np.asarray(b))

    def test_validation(self):
        cfg = tiny_cfg()
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        with pytest.raises(ValueError, match="k="):
            make_speculative_generate_fn(one, cfg, cfg, k=0)
        with pytest.raises(ValueError, match="vocab"):
            make_speculative_generate_fn(
                one, cfg, tiny_cfg(vocab_size=VOCAB * 2))
        with pytest.raises(ValueError, match="seq"):
            make_speculative_generate_fn(
                MeshConfig(seq=2, data=4), cfg, cfg)
        with pytest.raises(ValueError, match="temperature"):
            make_speculative_generate_fn(one, cfg, cfg,
                                         temperature=-1.0)
        # filters truncate SAMPLING — greedy spec must reject them
        with pytest.raises(ValueError, match="top_k/top_p"):
            make_speculative_generate_fn(one, cfg, cfg, top_k=4)
        with pytest.raises(ValueError, match="eos_id"):
            make_speculative_generate_fn(one, cfg, cfg, eos_id=VOCAB)

    def test_eos_matches_generate_eos(self):
        """eos early stop composes with greedy speculation: output
        token-identical to make_generate_fn's eos run (first eos kept,
        tail padded), with a draft bad enough that the corrective path
        runs across the freeze boundary."""
        cfg = tiny_cfg(n_layers=4)
        d_cfg = tiny_cfg(n_layers=2)
        host = _trained_host(cfg, 0)
        d_host = _trained_host(d_cfg, 9)
        p = prompt(seed=18, length=4)
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        params = shard_params(one, cfg, host)
        d_params = shard_params(one, d_cfg, d_host)
        plain = self._target_greedy(cfg, host, p, T)
        eos, PAD = int(plain[0, 6]), 7    # row 0 emits eos mid-run
        ref = np.asarray(make_generate_fn(
            one, cfg, max_len=T, eos_id=eos, pad_id=PAD)(params, p))
        assert (ref[0] == PAD).any()      # the freeze actually fires
        got = np.asarray(make_speculative_generate_fn(
            one, cfg, d_cfg, k=3, max_len=T, eos_id=eos, pad_id=PAD)(
            params, d_params, p))
        np.testing.assert_array_equal(got, ref)

    def test_eos_sharded_mesh_matches(self):
        """Rows freeze at different times across data shards: the
        pmax'd stop flag and the frozen rows' forced-k acceptance must
        keep every shard in lockstep to the global last row."""
        cfg = tiny_cfg(n_layers=4)
        d_cfg = tiny_cfg(n_layers=2)
        host = _trained_host(cfg, 0)
        d_host = _trained_host(d_cfg, 9)
        p = prompt(seed=18, length=4)
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        plain = self._target_greedy(cfg, host, p, T)
        eos, PAD = int(plain[0, 6]), 7
        ref = np.asarray(make_generate_fn(
            one, cfg, max_len=T, eos_id=eos, pad_id=PAD)(
            shard_params(one, cfg, host), p))
        mc = MeshConfig(data=2, model=2, devices=jax.devices()[:4])
        got = np.asarray(make_speculative_generate_fn(
            mc, cfg, d_cfg, k=3, max_len=T, eos_id=eos, pad_id=PAD)(
            shard_params(mc, cfg, host),
            shard_params(mc, d_cfg, d_host), p))
        np.testing.assert_array_equal(got, ref)

    def test_padded_prompts_match_generate_padded(self):
        """Variable-length prompts ride through the draft steps and
        verify chunks: token-identical to make_generate_fn's padded
        greedy run on the same rows."""
        cfg = tiny_cfg(n_layers=4, pos_embedding="rope")
        d_cfg = tiny_cfg(n_layers=2, pos_embedding="rope")
        host = _trained_host(cfg, 0)
        d_host = _trained_host(d_cfg, 9)
        P_len = 4
        lens = np.asarray([4, 3, 2, 4])
        rng = np.random.RandomState(33)
        padded = np.full((B, P_len), 63, np.int32)
        for b, n in enumerate(lens):
            padded[b, P_len - n:] = rng.randint(0, VOCAB, (n,))
        padded = jnp.asarray(padded)
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        params = shard_params(one, cfg, host)
        d_params = shard_params(one, d_cfg, d_host)
        ref = np.asarray(make_generate_fn(one, cfg, max_len=T)(
            params, padded, prompt_lens=lens))
        got = np.asarray(make_speculative_generate_fn(
            one, cfg, d_cfg, k=3, max_len=T)(
            params, d_params, padded, prompt_lens=lens))
        np.testing.assert_array_equal(got, ref)

    def test_eos_and_padded_compose(self):
        """The full serving shape at once: ragged prompts AND eos early
        stop, still token-identical to the plain generator."""
        cfg = tiny_cfg(n_layers=4, pos_embedding="rope")
        d_cfg = tiny_cfg(n_layers=2, pos_embedding="rope")
        host = _trained_host(cfg, 0)
        d_host = _trained_host(d_cfg, 9)
        P_len = 4
        lens = np.asarray([4, 3, 2, 4])
        rng = np.random.RandomState(34)
        padded = np.full((B, P_len), 63, np.int32)
        for b, n in enumerate(lens):
            padded[b, P_len - n:] = rng.randint(0, VOCAB, (n,))
        padded = jnp.asarray(padded)
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        params = shard_params(one, cfg, host)
        d_params = shard_params(one, d_cfg, d_host)
        plain = np.asarray(make_generate_fn(one, cfg, max_len=T)(
            params, padded, prompt_lens=lens))
        eos, PAD = int(plain[0, 6]), 7
        ref = np.asarray(make_generate_fn(
            one, cfg, max_len=T, eos_id=eos, pad_id=PAD)(
            params, padded, prompt_lens=lens))
        got = np.asarray(make_speculative_generate_fn(
            one, cfg, d_cfg, k=3, max_len=T, eos_id=eos, pad_id=PAD)(
            params, d_params, padded, prompt_lens=lens))
        np.testing.assert_array_equal(got, ref)

    def test_sampling_padded_eos_runs_and_freezes(self):
        """Speculative SAMPLING × ragged prompts × eos: same-key
        determinism, prompts preserved in place, and every token after
        a row's first generated eos is pad (the distribution identity
        itself is pinned by the statistical tests; this pins the
        composition's bookkeeping)."""
        cfg = tiny_cfg(n_layers=2, pos_embedding="rope")
        d_cfg = tiny_cfg(n_layers=1, pos_embedding="rope")
        host = _trained_host(cfg, 0)
        d_host = _trained_host(d_cfg, 9)
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        params = shard_params(one, cfg, host)
        d_params = shard_params(one, d_cfg, d_host)
        P_len = 4
        lens = np.asarray([4, 3, 2, 4])
        rng = np.random.RandomState(37)
        padded = np.full((B, P_len), 63, np.int32)
        for b, n in enumerate(lens):
            padded[b, P_len - n:] = rng.randint(0, VOCAB, (n,))
        padded = jnp.asarray(padded)
        EOS, PAD = 5, 7
        spec = make_speculative_generate_fn(
            one, cfg, d_cfg, k=2, max_len=T, temperature=1.0,
            top_k=16, eos_id=EOS, pad_id=PAD)
        a = np.asarray(spec(params, d_params, padded,
                            key=jax.random.PRNGKey(3),
                            prompt_lens=lens))
        b2 = np.asarray(spec(params, d_params, padded,
                             key=jax.random.PRNGKey(3),
                             prompt_lens=lens))
        np.testing.assert_array_equal(a, b2)
        np.testing.assert_array_equal(a[:, :P_len], np.asarray(padded))
        assert (a < VOCAB).all() and (a >= 0).all()
        for b_i in range(B):
            gen = a[b_i, P_len:]
            hits = np.where(gen == EOS)[0]
            if hits.size:
                assert (gen[hits[0] + 1:] == PAD).all(), a[b_i]

    def test_sampling_filters_distribution_matches_target(self):
        """Speculative sampling with top-k/top-p must match sampling
        the target directly WITH the same filters (truncate both
        p_draft and p_target, renormalize, exact residual) — same
        statistical design as the unfiltered test."""
        from chainermn_tpu.models.decoding import _filter_logits

        cfg = tiny_cfg(n_layers=2)
        d_cfg = tiny_cfg(n_layers=1)
        host = _trained_host(cfg, 0)
        d_host = _trained_host(d_cfg, 9)
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        params = shard_params(one, cfg, host)
        d_params = shard_params(one, d_cfg, d_host)
        row = np.random.RandomState(50).randint(0, VOCAB, 4)
        p = jnp.asarray(np.tile(row, (B, 1)), jnp.int32)
        TEMP, TOPK, TOPP, CALLS = 1.5, 12, 0.9, 250

        fwd = make_forward_fn(one, cfg)
        full = jnp.asarray(np.pad(np.asarray(p), ((0, 0), (0, T - 4))))
        logits = np.asarray(fwd(params, full))[0, 3][None] / TEMP
        true_p = np.asarray(jax.nn.softmax(
            _filter_logits(jnp.asarray(logits), TOPK, TOPP)))[0]
        spec = make_speculative_generate_fn(
            one, cfg, d_cfg, k=2, max_len=5, temperature=TEMP,
            top_k=TOPK, top_p=TOPP)
        h = np.zeros(VOCAB)
        for i in range(CALLS):
            out = np.asarray(
                spec(params, d_params, p, key=jax.random.PRNGKey(i)))
            for b in range(B):
                h[out[b, 4]] += 1
        n = CALLS * B
        # every sample must live inside the target's truncated support
        assert h[true_p <= 0].sum() == 0, "sample outside the nucleus"
        tv = 0.5 * np.abs(h / n - true_p).sum()
        noise = 0.5 * np.sqrt(2 * true_p / (np.pi * n)).sum()
        assert tv < 1.6 * noise + 0.02, (tv, noise)


class TestLookupDecoding:
    """Prompt-lookup decoding: exact-greedy output no matter what the
    n-gram matcher proposes, and real acceptance on the workloads it
    exists for (repetitive/copying text)."""

    @pytest.mark.parametrize("k,ngram", [(2, 1), (4, 2), (3, 3)])
    def test_matches_greedy(self, k, ngram):
        cfg = tiny_cfg()
        host = _trained_host(cfg, 0)
        p = prompt(seed=40, length=4)
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        params = shard_params(one, cfg, host)
        ref = np.asarray(
            make_generate_fn(one, cfg, max_len=T)(params, p))
        got, acc = make_lookup_generate_fn(
            one, cfg, k=k, ngram=ngram, max_len=T, with_stats=True)(
            params, p)
        np.testing.assert_array_equal(np.asarray(got), ref)
        assert 0.0 <= float(acc) <= k

    def test_repetitive_sequence_accepts(self):
        """The trained tiny model emits short repeats ("60 60 60 60");
        with IDENTICAL rows (acceptance is batch-min — mixed batches
        clamp to the worst row) lookup proposals must land at least
        once, proving the matcher finds real earlier occurrences."""
        cfg = tiny_cfg()
        host = _trained_host(cfg, 0)
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        params = shard_params(one, cfg, host)
        row = np.random.RandomState(40).randint(0, VOCAB, 4)
        p = jnp.asarray(np.tile(row, (B, 1)), jnp.int32)
        ref = np.asarray(
            make_generate_fn(one, cfg, max_len=T)(params, p))
        got, acc = make_lookup_generate_fn(
            one, cfg, k=3, ngram=2, max_len=T, with_stats=True)(
            params, p)
        np.testing.assert_array_equal(np.asarray(got), ref)
        assert float(acc) > 0.05, float(acc)

    def test_tp_mesh_matches_greedy(self):
        cfg = tiny_cfg(n_layers=4)
        host = _trained_host(cfg, 1)
        p = prompt(seed=42, length=4)
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        ref = np.asarray(
            make_generate_fn(one, cfg, max_len=T)(
                shard_params(one, cfg, host), p))
        mc = MeshConfig(data=2, model=2, devices=jax.devices()[:4])
        got = np.asarray(make_lookup_generate_fn(
            mc, cfg, k=3, ngram=2, max_len=T)(
            shard_params(mc, cfg, host), p))
        np.testing.assert_array_equal(got, ref)

    def test_vocab_parallel_mesh_matches_greedy(self):
        """Lookup decoding over Megatron vocab TP (shared
        _verify_and_commit with speculative: the sharded verify
        logits all-gather before the argmax compare)."""
        import dataclasses

        cfg = tiny_cfg(n_layers=4)
        host = _trained_host(cfg, 1)
        p = prompt(seed=42, length=4)
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        ref = np.asarray(
            make_generate_fn(one, cfg, max_len=T)(
                shard_params(one, cfg, host), p))
        vp = dataclasses.replace(cfg, vocab_parallel=True)
        mc = MeshConfig(data=2, model=2, devices=jax.devices()[:4])
        got = np.asarray(make_lookup_generate_fn(
            mc, vp, k=3, ngram=2, max_len=T)(
            shard_params(mc, vp, host), p))
        np.testing.assert_array_equal(got, ref)

    def test_pipe_mesh_matches_greedy(self):
        """Lookup decoding over pipe-parallel decode: the verify chunk
        rides the S-phase ppermute hand-off with stage-masked cache
        writes, the matcher stays host-side row-local."""
        cfg = tiny_cfg(n_layers=4)
        host = _trained_host(cfg, 2)
        p = prompt(seed=45, length=4)
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        ref = np.asarray(
            make_generate_fn(one, cfg, max_len=T)(
                shard_params(one, cfg, host), p))
        mc = MeshConfig(pipe=2, data=2, devices=jax.devices()[:4])
        got = np.asarray(make_lookup_generate_fn(
            mc, cfg, k=3, ngram=2, max_len=T)(
            shard_params(mc, cfg, dict(host, blocks=regroup_blocks(
                host["blocks"], 1, 2))), p))
        np.testing.assert_array_equal(got, ref)

    def test_int8_weights_match_int8_greedy(self):
        """Lookup decoding over weight-only int8: exact vs the int8
        greedy oracle (int8 changes the logits, so the quantized run
        is the right reference)."""
        cfg = tiny_cfg(n_layers=4)
        host = quantize_params_int8(cfg, _trained_host(cfg, 2))
        p = prompt(seed=43, length=4)
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        params = shard_params(one, cfg, host)
        ref = np.asarray(
            make_generate_fn(one, cfg, max_len=T, quantized=True)(
                params, p))
        got = np.asarray(make_lookup_generate_fn(
            one, cfg, k=3, ngram=2, max_len=T, quantized=True)(
            params, p))
        np.testing.assert_array_equal(got, ref)

    def test_validation(self):
        cfg = tiny_cfg()
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        with pytest.raises(ValueError, match="k="):
            make_lookup_generate_fn(one, cfg, k=0)
        with pytest.raises(ValueError, match="seq"):
            make_lookup_generate_fn(MeshConfig(seq=2, data=4), cfg)
        with pytest.raises(ValueError, match="eos_id"):
            make_lookup_generate_fn(one, cfg, eos_id=VOCAB)
        # prompt shorter than the ngram window fails at trace time
        gen = make_lookup_generate_fn(one, cfg, k=2, ngram=4, max_len=T)
        params = shard_params(
            one, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
        with pytest.raises(ValueError, match="ngram"):
            gen(params, prompt(length=2))

    def test_eos_matches_generate_eos(self):
        """eos early stop composes with lookup decoding: output
        token-identical to make_generate_fn's eos run."""
        cfg = tiny_cfg(n_layers=4)
        host = _trained_host(cfg, 1)
        p = prompt(seed=44, length=4)
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        params = shard_params(one, cfg, host)
        plain = np.asarray(
            make_generate_fn(one, cfg, max_len=T)(params, p))
        eos, PAD = int(plain[0, 6]), 7
        ref = np.asarray(make_generate_fn(
            one, cfg, max_len=T, eos_id=eos, pad_id=PAD)(params, p))
        assert (ref[0] == PAD).any()
        got = np.asarray(make_lookup_generate_fn(
            one, cfg, k=3, ngram=2, max_len=T, eos_id=eos,
            pad_id=PAD)(params, p))
        np.testing.assert_array_equal(got, ref)

    def test_padded_prompts_match_generate_padded(self):
        """Ragged prompts through the lookup matcher: windows touching
        pad slots propose garbage, verification keeps the output
        token-identical to the plain padded generator."""
        cfg = tiny_cfg(n_layers=4, pos_embedding="rope")
        host = _trained_host(cfg, 1)
        P_len = 4
        lens = np.asarray([4, 3, 2, 4])
        rng = np.random.RandomState(35)
        padded = np.full((B, P_len), 63, np.int32)
        for b, n in enumerate(lens):
            padded[b, P_len - n:] = rng.randint(0, VOCAB, (n,))
        padded = jnp.asarray(padded)
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        params = shard_params(one, cfg, host)
        ref = np.asarray(make_generate_fn(one, cfg, max_len=T)(
            params, padded, prompt_lens=lens))
        got = np.asarray(make_lookup_generate_fn(
            one, cfg, k=3, ngram=2, max_len=T)(
            params, padded, prompt_lens=lens))
        np.testing.assert_array_equal(got, ref)

    def test_eos_and_padded_compose(self):
        cfg = tiny_cfg(n_layers=4, pos_embedding="rope")
        host = _trained_host(cfg, 1)
        P_len = 4
        lens = np.asarray([4, 3, 2, 4])
        rng = np.random.RandomState(36)
        padded = np.full((B, P_len), 63, np.int32)
        for b, n in enumerate(lens):
            padded[b, P_len - n:] = rng.randint(0, VOCAB, (n,))
        padded = jnp.asarray(padded)
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        params = shard_params(one, cfg, host)
        plain = np.asarray(make_generate_fn(one, cfg, max_len=T)(
            params, padded, prompt_lens=lens))
        eos, PAD = int(plain[0, 6]), 7
        ref = np.asarray(make_generate_fn(
            one, cfg, max_len=T, eos_id=eos, pad_id=PAD)(
            params, padded, prompt_lens=lens))
        got = np.asarray(make_lookup_generate_fn(
            one, cfg, k=3, ngram=2, max_len=T, eos_id=eos,
            pad_id=PAD)(params, padded, prompt_lens=lens))
        np.testing.assert_array_equal(got, ref)


def test_virtual_pipe_packed_params_decode():
    """Params packed for the interleaved schedule (pipe=1, V=2) decode
    identically to flat packing."""
    cfg_flat = tiny_cfg(n_layers=4)
    cfg_v = tiny_cfg(n_layers=4, pipeline_schedule="interleaved",
                     virtual_pipe=2, num_microbatches=1)
    mc = MeshConfig(data=1, devices=jax.devices()[:1])
    params_flat = init_transformer(jax.random.PRNGKey(0), cfg_flat)
    params_v = init_transformer(jax.random.PRNGKey(0), cfg_v)
    toks = prompt()
    a = _cached_logits_all_positions(
        cfg_flat, shard_params(mc, cfg_flat, params_flat), toks, mc)
    b = _cached_logits_all_positions(
        cfg_v, shard_params(mc, cfg_v, params_v), toks, mc)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


class TestBeamSearch:
    def test_beam1_equals_greedy(self):
        cfg = tiny_cfg()
        mc = MeshConfig(data=1, devices=jax.devices()[:1])
        params = shard_params(
            mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
        p = prompt(length=4)
        greedy = make_generate_fn(mc, cfg, max_len=12)(params, p)
        beams, scores = make_beam_search_fn(
            mc, cfg, beam_size=1, max_len=12)(params, p)
        np.testing.assert_array_equal(
            np.asarray(beams[:, 0]), np.asarray(greedy))
        assert np.isfinite(np.asarray(scores)).all()

    def test_finds_exhaustive_argmax(self):
        """Small vocab, short horizon: a wide beam must recover the true
        argmax sequence found by brute-force enumeration."""
        from itertools import product

        V, Plen, G = 6, 2, 3          # 6^3 = 216 continuations
        cfg = tiny_cfg(vocab_size=V, max_seq=Plen + G)
        mc = MeshConfig(data=1, devices=jax.devices()[:1])
        params = shard_params(
            mc, cfg, init_transformer(jax.random.PRNGKey(3), cfg))
        B = 2
        p = jnp.asarray(
            np.random.RandomState(1).randint(0, V, (B, Plen)), jnp.int32)

        # brute force: score every continuation with the full forward
        fwd = make_forward_fn(mc, cfg)
        conts = np.array(list(product(range(V), repeat=G)), np.int32)
        best = np.zeros((B, G), np.int32)
        best_score = np.full(B, -np.inf)
        for cont in conts:
            seq = np.concatenate(
                [np.asarray(p), np.tile(cont, (B, 1))], axis=1)
            logits = np.asarray(fwd(params, jnp.asarray(seq)))
            logp = jax.nn.log_softmax(jnp.asarray(logits), -1)
            s = np.zeros(B)
            for g in range(G):
                s += np.asarray(
                    logp[np.arange(B), Plen - 1 + g, seq[:, Plen + g]])
            upd = s > best_score
            best[upd] = cont
            best_score[upd] = s[upd]

        beams, scores = make_beam_search_fn(
            mc, cfg, beam_size=V * V, max_len=Plen + G)(params, p)
        np.testing.assert_array_equal(
            np.asarray(beams[:, 0, Plen:]), best)
        np.testing.assert_allclose(
            np.asarray(scores[:, 0]), best_score, rtol=1e-4, atol=1e-4)

    def test_eos_freezes_hypotheses(self):
        cfg = tiny_cfg()
        mc = MeshConfig(data=1, devices=jax.devices()[:1])
        params = shard_params(
            mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
        p = prompt(length=3)
        # every token is "eos": all beams finish immediately after one
        # expansion and scores stay frozen (finite, sorted descending)
        gen = make_beam_search_fn(
            mc, cfg, beam_size=3, max_len=10, eos_id=0,
            length_penalty=0.6)
        beams, scores = gen(params, p)
        assert beams.shape == (B, 3, 10)
        s = np.asarray(scores)
        assert (np.diff(s, axis=1) <= 1e-6).all(), s

    def test_dp_tp_mesh(self):
        cfg = tiny_cfg(n_kv_heads=2)
        mc = MeshConfig(data=4, model=2)
        params = shard_params(
            mc, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        params_one = shard_params(
            one, cfg, init_transformer(jax.random.PRNGKey(0), cfg))
        p = prompt(length=4)
        a, sa = make_beam_search_fn(
            mc, cfg, beam_size=2, max_len=10)(params, p)
        b, sb = make_beam_search_fn(
            one, cfg, beam_size=2, max_len=10)(params_one, p)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(sa), np.asarray(sb),
                                   rtol=1e-4, atol=1e-4)

    def test_int8_weights_mesh_matches_single(self):
        """Beam search over weight-only int8 on a DP+TP mesh:
        tokens+scores equal the single-device int8 beam run (int8
        changes the logits, so the quantized single-device run is the
        right oracle)."""
        cfg = tiny_cfg()
        host = quantize_params_int8(
            cfg, init_transformer(jax.random.PRNGKey(0), cfg))
        p = prompt(length=4)
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        b, sb = make_beam_search_fn(
            one, cfg, beam_size=2, max_len=10, quantized=True)(
            shard_params(one, cfg, host), p)
        mc = MeshConfig(data=2, model=2, devices=jax.devices()[:4])
        a, sa = make_beam_search_fn(
            mc, cfg, beam_size=2, max_len=10, quantized=True)(
            shard_params(mc, cfg, host), p)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(sa), np.asarray(sb),
                                   rtol=1e-4, atol=1e-4)


def test_pp_decode_matches_single_device():
    """Pipe-parallel decode: layers + KV cache stage-sharded over
    pipe=2, S-phase ppermute hand-off — generated tokens must equal the
    pipe=1 oracle exactly (greedy argmax)."""
    cfg = tiny_cfg(n_layers=4)
    toks = prompt(length=6)

    one = MeshConfig(data=1, devices=jax.devices()[:1])
    p_flat = init_transformer(jax.random.PRNGKey(0), cfg)
    oracle = make_generate_fn(one, cfg, max_len=T)(
        shard_params(one, cfg, p_flat), toks)

    mc = MeshConfig(pipe=2, data=2, model=2)
    p_pipe = init_transformer(jax.random.PRNGKey(0), cfg, 2)
    got = make_generate_fn(mc, cfg, max_len=T)(
        shard_params(mc, cfg, p_pipe), toks)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle))


def test_pp_decode_beam_and_guards():
    """Beam search rides the same pipe-parallel step; virtual-pipe and
    seq meshes stay clearly rejected."""
    cfg = tiny_cfg(n_layers=4)
    toks = prompt(length=6)
    one = MeshConfig(data=1, devices=jax.devices()[:1])
    p_flat = init_transformer(jax.random.PRNGKey(0), cfg)
    ot, os_ = make_beam_search_fn(one, cfg, beam_size=2, max_len=T)(
        shard_params(one, cfg, p_flat), toks)

    mc = MeshConfig(pipe=2, data=4)
    p_pipe = init_transformer(jax.random.PRNGKey(0), cfg, 2)
    gt, gs = make_beam_search_fn(mc, cfg, beam_size=2, max_len=T)(
        shard_params(mc, cfg, p_pipe), toks)
    np.testing.assert_array_equal(np.asarray(gt), np.asarray(ot))
    np.testing.assert_allclose(np.asarray(gs), np.asarray(os_),
                               rtol=1e-5, atol=1e-6)

    with pytest.raises(ValueError, match="virtual_pipe"):
        make_generate_fn(
            mc, tiny_cfg(n_layers=4, virtual_pipe=2,
                         pipeline_schedule="interleaved"), max_len=T)


def test_generate_row_state_pins_frozen_row_semantics():
    """``with_row_state=True`` exposes the while-carry's per-row done
    bitmap and decoded length (only the all-rows-done scalar used to
    escape, as the loop exit).  ``gen_len`` must count exactly the
    real generated tokens — the eos included, the frozen tail's
    padding excluded — and ``done`` must mark exactly the eos-stopped
    rows, pinned here against the eos-less run's prefix."""
    cfg = tiny_cfg()
    mc = MeshConfig(data=1, devices=jax.devices()[:1])
    host = init_transformer(jax.random.PRNGKey(0), cfg)
    from chainermn_tpu.models import shard_params as _sp

    params = _sp(mc, cfg, host)
    toks = prompt(length=4)
    Plen = 4
    plain = np.asarray(
        make_generate_fn(mc, cfg, max_len=T)(params, toks))
    # an eos that provably fires: row 0's own third generated token
    eos = int(plain[0, Plen + 2])
    pad = 0 if eos != 0 else 1
    gen = make_generate_fn(mc, cfg, max_len=T, eos_id=eos, pad_id=pad,
                           with_row_state=True)
    out, done, lens = (np.asarray(x) for x in gen(params, toks))
    assert out.shape == (B, T)
    assert done.shape == (B,) and done.dtype == bool
    assert lens.shape == (B,) and lens.dtype == np.int32
    assert done[0]              # the crafted eos stopped row 0
    for b in range(B):
        region = out[b, Plen:]
        n = int(lens[b])
        if done[b]:
            assert region[n - 1] == eos       # eos kept AND counted
            assert not np.any(region[:n - 1] == eos)
            assert np.all(region[n:] == pad)  # frozen tail is padding
        else:
            assert n == T - Plen              # ran to the buffer end
        # up to each row's own end, row state and tokens agree with
        # the eos-less decode (freezing never rewrites real output)
        np.testing.assert_array_equal(out[b, :Plen + n],
                                      plain[b, :Plen + n])
    # eos disabled: the scan path reports full-length rows, none done
    gen2 = make_generate_fn(mc, cfg, max_len=T, with_row_state=True)
    out2, done2, lens2 = (np.asarray(x) for x in gen2(params, toks))
    np.testing.assert_array_equal(out2, plain)
    assert not done2.any() and np.all(lens2 == T - Plen)
