"""Weight-only int8 decoding: quantized logits must track the fp path
closely, generation must run on DP+TP meshes, and the quantize transform
must satisfy its per-channel error bound."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from chainermn_tpu.models import (
    TransformerConfig,
    init_transformer,
    make_beam_search_fn,
    make_generate_fn,
    param_specs,
    quantize_params_int8,
    shard_params,
)
from chainermn_tpu.models.decoding import _decode_step, _make_cache, _vary
from chainermn_tpu.parallel import MeshConfig


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


def test_quantize_error_bound():
    cfg = tiny_cfg()
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    q = quantize_params_int8(cfg, params)
    # reconstruction error <= scale/2 per channel (round-to-nearest)
    w = np.asarray(params["blocks"]["w1"])          # (1, L, D, F)
    wq = np.asarray(q["blocks"]["w1"]).astype(np.float32)
    s = np.asarray(q["blocks"]["w1_scale"])          # (1, L, F)
    err = np.abs(wq * s[:, :, None, :] - w)
    assert (err <= s[:, :, None, :] * 0.5 + 1e-8).all()
    assert q["blocks"]["w1"].dtype == jnp.int8
    assert q["embed"].dtype == jnp.int8
    # non-quantized leaves pass through untouched
    np.testing.assert_array_equal(q["blocks"]["ln1"],
                                  params["blocks"]["ln1"])


def _decode_logits(cfg, params, toks, steps, quantized):
    """Teacher-forced cached decode of ``steps`` positions on a
    single-device mesh, with plain or quantized param specs."""
    mc = MeshConfig(data=1, devices=jax.devices()[:1])

    def body(params, toks):
        caches = _make_cache(cfg, B, T, cfg.kv_heads, cfg.n_layers)
        outs = []
        for t in range(steps):
            logits, caches = _decode_step(
                cfg, params, caches, toks[:, t], t)
            outs.append(logits)
        return jnp.stack(outs, 1)

    fn = jax.jit(jax.shard_map(
        body, mesh=mc.mesh,
        in_specs=(param_specs(cfg, quantized=quantized),
                  P(("data", "expert"))),
        out_specs=P(("data", "expert"))))
    return fn(shard_params(mc, cfg, params), toks)


def _assert_quantized_tracks_fp(cfg, seed, steps):
    params = init_transformer(jax.random.PRNGKey(seed), cfg)
    qparams = quantize_params_int8(cfg, params)
    toks = prompt(seed, steps)
    ref = _decode_logits(cfg, params, toks, steps, False)
    out = _decode_logits(cfg, qparams, toks, steps, True)
    # int8 per-channel weight error ~0.4%/layer; logits track within a
    # few percent of the logit RANGE on this tiny random model
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(out - ref))) < 0.05 * scale


@pytest.mark.parametrize("gqa", [False, True], ids=["mha", "gqa"])
def test_quantized_logits_close(gqa):
    _assert_quantized_tracks_fp(tiny_cfg(n_kv_heads=2 if gqa else 0),
                                seed=1, steps=4)


@pytest.mark.parametrize("axes", [dict(data=1), dict(data=4, model=2)],
                         ids=["single", "dp-tp"])
def test_quantized_generate_runs(axes):
    cfg = tiny_cfg(n_kv_heads=2, pos_embedding="rope")
    params = init_transformer(jax.random.PRNGKey(3), cfg)
    qparams = quantize_params_int8(cfg, params)
    mc = (MeshConfig(data=1, devices=jax.devices()[:1])
          if axes == dict(data=1) else MeshConfig(**axes))
    qparams = shard_params(mc, cfg, qparams)
    gen = make_generate_fn(mc, cfg, max_len=12, quantized=True)
    out = gen(qparams, prompt(4, 4))
    assert out.shape == (B, 12)
    assert (np.asarray(out) >= 0).all() and (np.asarray(out) < VOCAB).all()
    # prompt preserved
    np.testing.assert_array_equal(np.asarray(out[:, :4]),
                                  np.asarray(prompt(4, 4)))


def test_quantized_beam_search_runs():
    cfg = tiny_cfg()
    params = init_transformer(jax.random.PRNGKey(5), cfg)
    qparams = quantize_params_int8(cfg, params)
    mc = MeshConfig(data=1, devices=jax.devices()[:1])
    qparams = shard_params(mc, cfg, qparams)
    bs = make_beam_search_fn(mc, cfg, beam_size=3, max_len=10,
                             quantized=True)
    toks, scores = bs(qparams, prompt(6, 4))
    assert toks.shape == (B, 3, 10)
    # scores sorted best-first
    s = np.asarray(scores)
    assert (np.diff(s, axis=1) <= 1e-6).all()


def test_quantized_windowed_decode_logits_close():
    """int8 composes with sliding-window causal decode (the window mask
    lives in the attention path, orthogonal to weight storage)."""
    _assert_quantized_tracks_fp(
        tiny_cfg(n_kv_heads=2, attention_window=4, pos_embedding="rope"),
        seed=7, steps=6)


def test_moe_quantized_logits_close():
    """Expert stacks quantize per expert; router WEIGHTS stay fp (its
    inputs still carry quantization noise from earlier layers, so a
    near-tie between experts can flip routing — the tolerance below
    holds because such ties are rare, not impossible)."""
    cfg = tiny_cfg(moe=True, n_experts=2)
    params = init_transformer(jax.random.PRNGKey(9), cfg)
    q = quantize_params_int8(cfg, params)
    assert q["blocks"]["w1"].dtype == jnp.int8
    assert q["blocks"]["w1_scale"].shape == (1, 2, 2, 64)  # (pipe,L,E,F)
    assert q["blocks"]["router"].dtype == jnp.float32
    _assert_quantized_tracks_fp(cfg, seed=9, steps=4)


def test_moe_quantized_generate_runs():
    cfg = tiny_cfg(moe=True, n_experts=2)
    params = init_transformer(jax.random.PRNGKey(10), cfg)
    mc = MeshConfig(data=4, expert=2)
    qparams = shard_params(mc, cfg, quantize_params_int8(cfg, params))
    gen = make_generate_fn(mc, cfg, max_len=10, quantized=True)
    toks = jnp.asarray(
        np.random.RandomState(11).randint(0, VOCAB, (8, 4)), jnp.int32)
    out = gen(qparams, toks)
    assert out.shape == (8, 10)


class TestInt8KVCache:
    """kv_cache_dtype="int8": decode logits must track the fp-cache
    path within quantization noise, the speculative exact-greedy
    guarantee must survive (both paths read the SAME quantized cache),
    and the cache must actually be int8 with trailing-singleton
    scales."""

    def _cached_logits(self, cfg, params, toks, steps):
        mc = MeshConfig(data=1, devices=jax.devices()[:1])

        def body(params, toks):
            caches = _make_cache(cfg, B, T, cfg.kv_heads, cfg.n_layers)
            assert len(caches) == (4 if cfg.kv_cache_dtype else 2)
            if cfg.kv_cache_dtype:
                assert caches[0].dtype == jnp.int8
                assert caches[2].shape[-1] == 1
            outs = []
            for t in range(steps):
                logits, caches = _decode_step(
                    cfg, params, caches, toks[:, t], t)
                outs.append(logits)
            return jnp.stack(outs, 1)

        fn = jax.jit(jax.shard_map(
            body, mesh=mc.mesh,
            in_specs=(param_specs(cfg), P(("data", "expert"))),
            out_specs=P(("data", "expert"))))
        return fn(shard_params(mc, cfg, params), toks)

    @pytest.mark.parametrize("gqa", [False, True], ids=["mha", "gqa"])
    def test_logits_track_fp_cache(self, gqa):
        kw = dict(n_kv_heads=2 if gqa else 0)
        host = init_transformer(jax.random.PRNGKey(2), tiny_cfg(**kw))
        toks = prompt(2, 8)
        ref = self._cached_logits(tiny_cfg(**kw), host, toks, 8)
        out = self._cached_logits(
            tiny_cfg(kv_cache_dtype="int8", **kw), host, toks, 8)
        scale = float(jnp.max(jnp.abs(ref)))
        assert float(jnp.max(jnp.abs(out - ref))) < 0.05 * scale

    def test_generate_runs_on_tp_mesh(self):
        cfg = tiny_cfg(kv_cache_dtype="int8", n_kv_heads=2)
        mc = MeshConfig(data=2, model=2, devices=jax.devices()[:4])
        params = shard_params(
            mc, cfg, init_transformer(jax.random.PRNGKey(3), cfg))
        out = make_generate_fn(mc, cfg, max_len=12)(
            params, prompt(3, 4))
        assert out.shape == (B, 12)
        assert (np.asarray(out) < VOCAB).all()

    def test_seq_kv_blockwise_scales(self):
        """int8 cache + sequence-parallel KV: the blockwise prefill
        writes hit the scale arrays through the same mask machinery —
        tokens on the seq-KV mesh must equal the int8 single-device
        run exactly (quantisation is per-(token, head), so the layout
        cannot change it; fp-accuracy of int8 itself is pinned by
        test_logits_track_fp_cache)."""
        cfg8 = tiny_cfg(kv_cache_dtype="int8")
        cfg = tiny_cfg()
        host = init_transformer(jax.random.PRNGKey(4), cfg)
        p = prompt(4, 4)

        def gen(c, mc):
            return np.asarray(make_generate_fn(mc, c, max_len=12)(
                shard_params(mc, c, host), p))

        mc = MeshConfig(seq=2, data=2, devices=jax.devices()[:4])
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        # int8 tokens on the seq-KV mesh == int8 tokens on one device
        # (quantisation is per-(token, head) — layout-independent)
        np.testing.assert_array_equal(gen(cfg8, mc), gen(cfg8, one))

    def test_speculative_stays_exact_greedy(self):
        """Both the per-token and the chunk-verify paths read back the
        SAME quantized cache entries, so the exact-greedy guarantee is
        preserved under int8 KV (vs the int8-cache greedy oracle)."""
        import dataclasses

        from chainermn_tpu.models import make_speculative_generate_fn

        cfg = tiny_cfg(kv_cache_dtype="int8", n_layers=4)
        d_cfg = dataclasses.replace(cfg, n_layers=2)
        one = MeshConfig(data=1, devices=jax.devices()[:1])
        host = init_transformer(jax.random.PRNGKey(5), cfg)
        d_host = dict(host, blocks=jax.tree.map(
            lambda a: a[:, :2], host["blocks"]))
        p = prompt(5, 4)
        params = shard_params(one, cfg, host)
        ref = np.asarray(
            make_generate_fn(one, cfg, max_len=12)(params, p))
        got = np.asarray(make_speculative_generate_fn(
            one, cfg, d_cfg, k=3, max_len=12)(
            params, shard_params(one, d_cfg, d_host), p))
        np.testing.assert_array_equal(got, ref)

    def test_validation(self):
        with pytest.raises(ValueError, match="kv_cache_dtype"):
            tiny_cfg(kv_cache_dtype="fp8")

    def test_bf16_quant_never_overflows_int8(self):
        """bf16 scales round below absmax/127, so the max element's
        ratio can land on +128 — the clip keeps every cached value in
        [-127, 127] (without it, wraparound backends sign-flip the
        LARGEST K/V component of ~17% of (token, head) rows)."""
        cfg = tiny_cfg(kv_cache_dtype="int8", dtype="bfloat16")
        mc = MeshConfig(data=1, devices=jax.devices()[:1])
        params = shard_params(
            mc, cfg, init_transformer(jax.random.PRNGKey(6), cfg))

        def body(params, toks):
            caches = _make_cache(cfg, B, T, cfg.kv_heads, cfg.n_layers)
            _, caches = _decode_step(cfg, params, caches, toks, 0,
                                     with_logits=False)
            # the cache is typed varying over every mesh axis: reduce
            # to invariant scalars for a P() output
            axes = ("pipe", "data", "expert", "model")
            return jnp.stack([
                jnp.stack((lax.pmin(jnp.min(c.astype(jnp.int32)), axes),
                           lax.pmax(jnp.max(c.astype(jnp.int32)), axes)))
                for c in caches[:2]])

        fn = jax.jit(jax.shard_map(
            body, mesh=mc.mesh,
            in_specs=(param_specs(cfg), P(("data", "expert"))),
            out_specs=P()))
        stats = np.asarray(fn(params, prompt(6, T)))
        assert stats.min() >= -127 and stats.max() <= 127, stats
