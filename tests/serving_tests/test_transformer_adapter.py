"""The flagship transformer behind the serving engine: token identity
against ``make_generate_fn``'s own ragged static decode on a DP×TP
mesh — per-token ragged rounds and per-row speculative rounds."""

import numpy as np
import pytest

import jax

from chainermn_tpu.parallel import MeshConfig
from chainermn_tpu.serving import ServingEngine, TransformerAdapter


VOCAB, PMAX, NEW = 64, 8, 10


def _cfg():
    from chainermn_tpu.models import TransformerConfig

    return TransformerConfig(
        vocab_size=VOCAB, d_model=32, n_heads=4, d_head=8, d_ff=64,
        n_layers=2, max_seq=64, attention="local",
        pos_embedding="rope", dtype="float32", remat=False)


def _engine_matches_static_generate(speculative):
    from chainermn_tpu.models import (
        init_transformer, make_generate_fn, shard_params,
    )

    cfg = _cfg()
    mc = MeshConfig(data=4, model=2)
    host = init_transformer(jax.random.PRNGKey(0), cfg)
    params = shard_params(mc, cfg, host)

    rng = np.random.RandomState(0)
    lens = [3, 8, 5, 6]
    prompts = [rng.randint(0, VOCAB, n).astype(np.int32) for n in lens]

    # static oracle: one ragged right-aligned batch through generate
    max_len = PMAX + NEW
    batch = np.zeros((4, PMAX), np.int32)
    for b, p in enumerate(prompts):
        batch[b, PMAX - p.shape[0]:] = p
    gen = make_generate_fn(mc, cfg, max_len=max_len)
    ref = np.asarray(gen(params, batch, prompt_lens=np.asarray(lens)))

    adapter = TransformerAdapter(mc, cfg)
    # self-draft speculation drives verify_ragged (per-row chunk starts)
    # and must not move a token whatever the draft proposes
    spec = dict(draft_adapter=adapter, draft_params=host, spec_k=3) \
        if speculative else {}
    eng = ServingEngine(adapter, host, n_slots=4, horizon=64,
                        max_prompt=PMAX, block=8, round_tokens=4, **spec)
    rids = [eng.submit(p, max_new=NEW) for p in prompts]
    comps = {c.rid: c for c in eng.run(max_steps=500)}
    for b, rid in enumerate(rids):
        assert comps[rid].status == "ok", comps[rid].detail
        np.testing.assert_array_equal(
            comps[rid].tokens, ref[b, PMAX:],
            err_msg=f"row {b} diverged from the static ragged decode")


def test_engine_matches_static_generate_dp_tp():
    _engine_matches_static_generate(speculative=False)


def test_engine_self_draft_speculation_matches_static_generate_dp_tp():
    _engine_matches_static_generate(speculative=True)


def test_adapter_rejects_moe_and_seq():
    import dataclasses

    from chainermn_tpu.models import TransformerConfig

    cfg = _cfg()
    with pytest.raises(ValueError, match="MoE"):
        TransformerAdapter(
            MeshConfig(data=8),
            dataclasses.replace(cfg, moe=True, n_experts=2))
    with pytest.raises(ValueError, match="seq"):
        TransformerAdapter(MeshConfig(data=4, seq=2), cfg)
