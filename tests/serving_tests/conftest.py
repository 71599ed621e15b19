"""Shared MiniLM fixtures for the serving-engine suite.

Session-scoped model/adapter (compiles are the cost here, not compute)
plus an independent greedy oracle: a plain python loop over the same
adapter's pure step/prefill functions — no shard_map, no engine code —
so engine-vs-oracle token identity actually pins the scheduler, not
two copies of one bug."""

import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.parallel import MeshConfig
from chainermn_tpu.serving import (
    MiniLMAdapter,
    MiniLMConfig,
    init_minilm,
)

VOCAB = 64


@pytest.fixture(scope="session", autouse=True)
def _engine_registry():
    """Track every engine the suite constructs (weakly — fixtures may
    outlive tests) so the leak guard below can audit them all."""
    from chainermn_tpu.serving import engine as engine_mod

    registry = weakref.WeakSet()
    orig_init = engine_mod.ServingEngine.__init__

    def tracked_init(self, *args, **kwargs):
        orig_init(self, *args, **kwargs)
        registry.add(self)

    engine_mod.ServingEngine.__init__ = tracked_init
    try:
        yield registry
    finally:
        engine_mod.ServingEngine.__init__ = orig_init


@pytest.fixture(autouse=True)
def pool_leak_guard(_engine_registry):
    """Suite-wide refcount-leak fixture: after EVERY serving test,
    every engine that is idle (nothing queued, active, or staged) must
    account for all its pool blocks — free, or trie-cached with
    exactly the trie's reference.  A fork/eviction path that drops or
    double-counts a reference fails the suite here even if its own
    test never looked."""
    yield
    for eng in list(_engine_registry):
        if eng.idle and not eng._staged:
            problems = eng._alloc.leak_report()
            assert not problems, (
                f"pool leak after test (engine {eng!r}): {problems}")


@pytest.fixture(scope="session")
def mini_cfg():
    return MiniLMConfig(vocab_size=VOCAB, d_model=32, n_heads=2,
                        d_head=16, d_ff=64, n_layers=2, max_pos=256)


@pytest.fixture(scope="session")
def mini_params(mini_cfg):
    return init_minilm(jax.random.PRNGKey(0), mini_cfg)


@pytest.fixture(scope="session")
def mini_adapter(mini_cfg):
    return MiniLMAdapter(MeshConfig(data=8), mini_cfg)


@pytest.fixture(scope="session")
def solo_decode(mini_adapter, mini_params):
    """``solo_decode(prompt, max_new, pick, eos=-1) -> (n,) tokens``:
    one request decoded alone, ``pick(logits, t)`` choosing the token
    produced at clock ``t`` -- the loop under the greedy oracle here and
    ``test_sampling.py``'s sampled one."""
    ad, params = mini_adapter, mini_params
    # the adapter's two pure functions, each ONE compiled program a
    # shape: called eagerly, every op of the prefill was a program of
    # its own again at every new prompt length (493 compiles, 14 of the
    # 21 s of the first parity test alone)
    prefill, step = jax.jit(ad.prefill), jax.jit(ad.step)

    def run(prompt, max_new, pick, eos=-1):
        prompt = np.asarray(prompt, np.int32)
        p = prompt.shape[0]
        # a cache of whole 64s, not of p + max_new: the loop below runs
        # op by op, and every new cache length compiled each of its ops
        # again (40 s for twenty requests, 5 s at one length; positions
        # past the clock are masked, the tokens are the same)
        caches = ad.make_cache(1, -(-(p + max_new) // 64) * 64)
        offs = jnp.zeros((1,), jnp.int32)
        if p > 1:
            caches = prefill(
                params, caches, jnp.asarray(prompt[None, :p - 1]), offs)
        tok = jnp.asarray(prompt[-1:], jnp.int32)
        out = []
        for t in range(p - 1, p - 1 + max_new):
            logits, caches = step(params, caches, tok, jnp.int32(t),
                                  offs)
            tok = pick(logits, t)
            out.append(int(tok[0]))
            if eos >= 0 and out[-1] == eos:
                break
        return np.asarray(out, np.int32)

    return run


@pytest.fixture(scope="session")
def oracle(solo_decode):
    """``oracle(prompt, max_new, eos=-1) -> (n,) generated tokens`` —
    the solo static greedy decode every engine request must match."""
    cache = {}

    def greedy(logits, t):
        return jnp.argmax(logits, -1).astype(jnp.int32)

    def run(prompt, max_new, eos=-1):
        key = (bytes(np.asarray(prompt, np.int32)), int(max_new),
               int(eos))
        if key not in cache:
            cache[key] = solo_decode(prompt, max_new, greedy, eos)
        return cache[key]

    return run


@pytest.fixture(scope="session")
def ragged_trace():
    """Factory: (prompt, max_new) pairs with ragged lengths/budgets."""

    def make(rng, n, vocab=VOCAB, max_prompt=16, min_new=4, max_new=24):
        return [(rng.randint(0, vocab, rng.randint(2, max_prompt + 1)),
                 int(rng.randint(min_new, max_new + 1)))
                for _ in range(n)]

    return make
