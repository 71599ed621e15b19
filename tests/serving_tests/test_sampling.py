"""Keyed sampling in the decode round.

The determinism contract: greedy requests stay token-identical to the
engine-independent solo oracle even when sampled requests share their
rounds (greedy IS the exactness oracle), and sampled requests replay
bit-identically from (seed, params, prompt) under ANY scheduling —
different policies, different batch compositions, different slots."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.serving import SamplingParams, ServingEngine
from chainermn_tpu.serving.sampling import (
    filter_logits,
    fold_keys,
    sample_tokens,
)

_NEG_CUT = -1e29        # anything below = filtered


class TestFilters:
    def test_top_k(self):
        lg = jnp.asarray([[1.0, 4.0, 3.0, 2.0]])
        out = np.asarray(filter_logits(lg, jnp.asarray([2]),
                                       jnp.asarray([1.0])))[0]
        assert list(out > _NEG_CUT) == [False, True, True, False]

    def test_top_k_zero_disables(self):
        lg = jnp.asarray([[1.0, 4.0, 3.0, 2.0]])
        out = np.asarray(filter_logits(lg, jnp.asarray([0]),
                                       jnp.asarray([1.0])))[0]
        assert (out > _NEG_CUT).all()

    def test_top_p(self):
        # softmax of [ln8, ln4, ln2, ln1] = [8,4,2,1]/15
        lg = jnp.log(jnp.asarray([[8.0, 4.0, 2.0, 1.0]]))
        out = np.asarray(filter_logits(lg, jnp.asarray([0]),
                                       jnp.asarray([0.75])))[0]
        # cum-before: 0, 8/15(0.53), 12/15(0.8), 14/15 -> keep first 2
        assert list(out > _NEG_CUT) == [True, True, False, False]
        # at least one token always survives even for tiny p
        out = np.asarray(filter_logits(lg, jnp.asarray([0]),
                                       jnp.asarray([1e-6])))[0]
        assert (out > _NEG_CUT).sum() == 1

    def test_per_row_parameters(self):
        lg = jnp.asarray([[1.0, 4.0, 3.0, 2.0],
                          [1.0, 4.0, 3.0, 2.0]])
        out = np.asarray(filter_logits(lg, jnp.asarray([1, 3]),
                                       jnp.asarray([1.0, 1.0])))
        assert (out[0] > _NEG_CUT).sum() == 1
        assert (out[1] > _NEG_CUT).sum() == 3

    def test_greedy_rows_take_argmax(self):
        lg = jnp.asarray([[0.1, 0.9], [0.9, 0.1]])
        keys = jnp.zeros((2, 2), jnp.uint32)
        toks = sample_tokens(lg, keys, jnp.asarray([0.0, 0.0]),
                             jnp.asarray([0, 0]),
                             jnp.asarray([1.0, 1.0]))
        assert list(np.asarray(toks)) == [1, 0]

    def test_vmap_matches_solo(self):
        """The replay oracle's load-bearing property: batched sampling
        is bitwise the solo call."""
        rng = np.random.RandomState(0)
        lg = jnp.asarray(rng.randn(4, 32).astype(np.float32))
        keys = fold_keys(
            jnp.stack([jax.random.PRNGKey(i) for i in range(4)]),
            jnp.arange(4, dtype=jnp.int32))
        batched = sample_tokens(lg, keys, jnp.full((4,), 0.8),
                                jnp.full((4,), 8, jnp.int32),
                                jnp.full((4,), 0.9))
        for i in range(4):
            solo = sample_tokens(lg[i:i + 1], keys[i:i + 1],
                                 jnp.asarray([0.8]),
                                 jnp.asarray([8], jnp.int32),
                                 jnp.asarray([0.9]))
            assert int(solo[0]) == int(batched[i])

    def test_params_validation(self):
        with pytest.raises(ValueError, match="temperature"):
            SamplingParams(temperature=0.0)
        with pytest.raises(ValueError, match="top_k"):
            SamplingParams(top_k=-1)
        with pytest.raises(ValueError, match="top_p"):
            SamplingParams(top_p=0.0)


def _sampled_oracle(solo_decode, prompt, max_new, sp, eos=-1):
    """Engine-independent replay: solo decode with the same key
    stream and the same sampling functions the round program uses."""
    root = jnp.asarray(sp.key())[None]

    def pick(logits, t):
        # token index of the PRODUCED token: t + 1 - offset (= i+1
        # counting the prompt's last token as index p-1... the engine
        # folds by t + 1 - offset with offset = position of token 0)
        keys = fold_keys(root, jnp.asarray([t + 1], jnp.int32))
        return sample_tokens(logits, keys,
                             jnp.asarray([sp.temperature]),
                             jnp.asarray([sp.top_k], jnp.int32),
                             jnp.asarray([sp.top_p]))

    return solo_decode(prompt, max_new, pick, eos)


class TestEngineSampling:
    @pytest.fixture(scope="class")
    def engine(self, mini_adapter, mini_params):
        return ServingEngine(mini_adapter, mini_params, n_slots=8,
                             horizon=160, max_prompt=16, block=8,
                             round_tokens=4)

    def test_sampled_replay_across_scheduling(self, engine,
                                              ragged_trace):
        """Same requests, two different schedules (fcfs vs spf, and a
        different submission interleaving) — sampled tokens identical:
        the key stream depends on the request alone."""
        rng = np.random.RandomState(10)
        trace = ragged_trace(rng, 12)
        sps = [SamplingParams(temperature=0.9, top_k=12, top_p=0.95,
                              seed=100 + i) for i in range(len(trace))]
        runs = []
        for policy in ("fcfs", "spf"):
            engine.reset()
            engine.set_policy(policy)
            try:
                rids = [engine.submit(p, max_new=n, sampling=sp)
                        for (p, n), sp in zip(trace, sps)]
                comps = {c.rid: c for c in engine.run(max_steps=2000)}
                runs.append({r: comps[r].tokens for r in rids})
            finally:
                engine.set_policy("fcfs")
        for rid in runs[0]:
            np.testing.assert_array_equal(
                runs[0][rid], runs[1][rid],
                err_msg=f"{rid} sampled tokens changed with the "
                        "schedule")

    def test_sampled_matches_solo_replay_oracle(self, engine,
                                                solo_decode):
        engine.reset()
        rng = np.random.RandomState(11)
        cases = [(rng.randint(0, 64, rng.randint(2, 17)), 8,
                  SamplingParams(temperature=0.8, top_k=10,
                                 top_p=0.9, seed=7 + i))
                 for i in range(4)]
        rids = [engine.submit(p, max_new=n, sampling=sp)
                for p, n, sp in cases]
        comps = {c.rid: c for c in engine.run(max_steps=2000)}
        for rid, (p, n, sp) in zip(rids, cases):
            ref = _sampled_oracle(solo_decode, p, n, sp)
            np.testing.assert_array_equal(
                comps[rid].tokens, ref,
                err_msg=f"{rid} diverged from its (key, params) "
                        "replay")

    def test_greedy_rows_stay_exact_in_mixed_rounds(self, engine,
                                                    oracle,
                                                    ragged_trace):
        """Greedy requests sharing rounds with sampled ones keep the
        engine's original guarantee — token-identical to the solo
        oracle."""
        engine.reset()
        rng = np.random.RandomState(12)
        trace = ragged_trace(rng, 8)
        rids = []
        for i, (p, n) in enumerate(trace):
            sp = SamplingParams(temperature=1.2, seed=i) \
                if i % 2 else None
            rids.append((engine.submit(p, max_new=n, sampling=sp),
                         p, n, sp))
        comps = {c.rid: c for c in engine.run(max_steps=2000)}
        assert engine.stats()["rounds"] > 0
        for rid, p, n, sp in rids:
            if sp is None:
                np.testing.assert_array_equal(
                    comps[rid].tokens, oracle(p, n),
                    err_msg=f"greedy {rid} corrupted by sampled "
                            "round-mates")

    def test_all_greedy_uses_original_program(self, engine,
                                              ragged_trace):
        """No sampled rows live -> the engine dispatches the ORIGINAL
        greedy round program (the byte-identical path)."""
        engine.reset()
        trace = ragged_trace(np.random.RandomState(13), 4)
        for p, n in trace:
            engine.submit(p, max_new=n)
        engine.run(max_steps=500)
        assert engine._n_sampled_active == 0

    def test_sampled_with_eos_freezes(self, mini_adapter, mini_params,
                                      oracle, solo_decode,
                                      ragged_trace):
        """EOS semantics under sampling: a sampled row emitting eos
        freezes and pads; its replay oracle agrees."""
        rng = np.random.RandomState(14)
        trace = ragged_trace(rng, 4, min_new=8)
        eos = int(oracle(trace[0][0], trace[0][1])[2])
        eng = ServingEngine(mini_adapter, mini_params, n_slots=8,
                            horizon=160, max_prompt=16, block=8,
                            round_tokens=4, eos_id=eos, pad_id=0)
        cases = [(p, n, SamplingParams(temperature=1.0, seed=50 + i))
                 for i, (p, n) in enumerate(trace)]
        rids = [eng.submit(p, max_new=n, sampling=sp)
                for p, n, sp in cases]
        comps = {c.rid: c for c in eng.run(max_steps=2000)}
        for rid, (p, n, sp) in zip(rids, cases):
            ref = _sampled_oracle(solo_decode, p, n, sp, eos=eos)
            np.testing.assert_array_equal(comps[rid].tokens, ref)

    def test_submit_rejects_non_sampling_params(self, engine):
        engine.reset()
        with pytest.raises(ValueError, match="SamplingParams"):
            engine.submit(np.arange(4) % 64, max_new=4,
                          sampling={"temperature": 1.0})
