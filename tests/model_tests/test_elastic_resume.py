"""Elastic mesh resume: a training state snapshotted on one topology
must continue on a different one — different axis sizes, a different
pipe grouping (blocks regrouped), a different at-rest layout (fsdp) —
with the same loss trajectory.  Beyond the reference: ChainerMN's
checkpointer required restart at the identical world size."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chainermn_tpu.models import (
    TransformerConfig,
    init_transformer,
    make_train_step,
    regroup_blocks,
    reshard_train_state,
    shard_params,
)
from chainermn_tpu.parallel import MeshConfig


VOCAB, B, T = 64, 8, 16


def tiny_cfg(**kw):
    base = dict(
        vocab_size=VOCAB, d_model=32, n_heads=4, d_head=8, d_ff=64,
        n_layers=4, max_seq=T, attention="local", dtype="float32",
        remat=False,
    )
    base.update(kw)
    return TransformerConfig(**base)


def tokens(seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, VOCAB, (B, T + 1)),
        jnp.int32)


def _layer_tagged_blocks(n_layers, pipe, virtual=1):
    """Toy block stack whose single leaf's value IS its global layer
    index, grouped the way init_transformer groups real blocks."""
    layers = jnp.arange(n_layers, dtype=jnp.float32)[:, None]  # base (1,)
    if virtual > 1:
        lpc = n_layers // (pipe * virtual)
        return {"w": layers.reshape(virtual, pipe, lpc, 1).swapaxes(0, 1)}
    return {"w": layers.reshape(pipe, n_layers // pipe, 1)}


@pytest.mark.parametrize("src,dst", [
    ((1, 1), (2, 1)),
    ((2, 1), (4, 1)),
    ((1, 1), (2, 2)),
    ((2, 2), (1, 1)),
    ((2, 2), (4, 1)),
])
def test_regroup_blocks_preserves_layer_order(src, dst):
    L = 8
    a = _layer_tagged_blocks(L, *src)
    b = regroup_blocks(a, src[0], dst[0], src[1], dst[1])
    expect = _layer_tagged_blocks(L, *dst)
    np.testing.assert_array_equal(np.asarray(b["w"]),
                                  np.asarray(expect["w"]))
    # round trip back is the identity
    back = regroup_blocks(b, dst[0], src[0], dst[1], src[1])
    np.testing.assert_array_equal(np.asarray(back["w"]),
                                  np.asarray(a["w"]))


def test_regroup_blocks_shape_mismatch_raises():
    a = _layer_tagged_blocks(8, 2)
    with pytest.raises(ValueError, match="from_pipe"):
        regroup_blocks(a, 4, 2)
    with pytest.raises(ValueError, match="divisible"):
        regroup_blocks(a, 2, 3)


def _run_steps(step, params, opt_state, toks, n):
    x, y = toks[:, :T], toks[:, 1:]
    losses = []
    for _ in range(n):
        params, opt_state, loss = step(params, opt_state, x, y)
        losses.append(float(loss))
    return params, opt_state, losses


@functools.cache
def _snapshot_and_uninterrupted():
    """The data=4 run every target resumes from: two steps, the host
    snapshot, then the three uninterrupted steps whose losses a resumed
    run must match.  The same for all six targets, so made once."""
    toks = tokens(7)
    opt = optax.adam(1e-2)
    cfg_a = tiny_cfg()
    mc_a = MeshConfig(data=4, devices=jax.devices()[:4])
    params = shard_params(
        mc_a, cfg_a, init_transformer(jax.random.PRNGKey(0), cfg_a))
    opt_state = jax.jit(opt.init)(params)
    step_a = make_train_step(mc_a, cfg_a, opt)
    params, opt_state, _ = _run_steps(step_a, params, opt_state, toks, 2)

    # host snapshot, BEFORE the donated buffers are consumed further
    host_p = jax.tree.map(np.asarray, params)
    host_o = jax.tree.map(np.asarray, opt_state)

    # uninterrupted continuation on mesh A
    _, _, ref = _run_steps(step_a, params, opt_state, toks, 3)
    return host_p, host_o, ref


RESUME_TARGETS = [
    ("data8", dict(), dict(data=8)),
    ("pipe2_gpipe", dict(num_microbatches=2), dict(pipe=2, data=2)),
    ("pipe2_interleaved",
     dict(pipeline_schedule="interleaved", virtual_pipe=2,
          num_microbatches=2),
     dict(pipe=2, data=2)),
    ("fsdp", dict(fsdp=True), dict(data=8)),
    ("tp_seq", dict(attention="ring"), dict(model=2, seq=2, data=2)),
    # the embed re-lays from replicated to vocab-sharded on resume
    ("vocab_tp", dict(attention="ring", vocab_parallel=True),
     dict(model=2, seq=2, data=2)),
]


@pytest.mark.parametrize(
    "name,cfg_kw,axes", RESUME_TARGETS,
    ids=[t[0] for t in RESUME_TARGETS])
def test_elastic_resume_matches_uninterrupted(name, cfg_kw, axes):
    """Train on a data=4 mesh, snapshot mid-run, reshard to a different
    topology and continue: the loss trajectory must match the
    uninterrupted data=4 run (schedules/shardings are implementation
    details of the same math)."""
    toks = tokens(7)
    opt = optax.adam(1e-2)
    host_p, host_o, ref = _snapshot_and_uninterrupted()

    # resharded continuation on mesh B
    cfg_b = tiny_cfg(**cfg_kw)
    n_dev = int(np.prod(list(axes.values())))
    mc_b = MeshConfig(**axes, devices=jax.devices()[:n_dev])
    pipe_b = axes.get("pipe", 1)
    p_b, o_b = reshard_train_state(
        mc_b, cfg_b, opt, host_p, host_o, from_pipe=1)
    assert pipe_b == mc_b.mesh.shape.get("pipe", 1)
    step_b = make_train_step(mc_b, cfg_b, opt)
    _, _, got = _run_steps(step_b, p_b, o_b, toks, 3)

    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=1e-5,
                               err_msg=f"resume target {name}")
