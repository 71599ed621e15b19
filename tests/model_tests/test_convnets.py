"""Convnet zoo: shapes, finiteness, DP-train smoke for each arch."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from chainermn_tpu.models import (
    ConvNetConfig,
    convnet_apply,
    init_convnet,
    softmax_cross_entropy,
)
from chainermn_tpu.parallel import MeshConfig


B, HW, C = 8, 32, 8


@functools.cache
def seeds(cfg):
    """The parameters at their seeds, made by ONE compiled program: op
    by op a 22-layer net's init compiles every draw at every shape, most
    of the 41 s the GoogLeNet forward case took of a 1,319 s tier-1 run
    (PR 45).  Once a config: GoogLeNet's program alone compiles for
    13 s, and two cases seed the same one."""
    return jax.jit(lambda: init_convnet(jax.random.PRNGKey(0), cfg))()


@pytest.mark.parametrize("arch", ["alex", "nin", "vgg16", "googlenet"])
def test_forward_shape(arch):
    cfg = ConvNetConfig(arch=arch, num_classes=C, dtype="float32",
                        head="gap")
    params = seeds(cfg)
    x = jnp.asarray(np.random.RandomState(0).randn(B, HW, HW, 3),
                    jnp.float32)
    # one compiled program: op by op the 22-layer net's eager dispatch
    # took 49 s of a 1,434 s tier-1 run (PR 42)
    logits = jax.jit(lambda p, x: convnet_apply(cfg, p, x))(params, x)
    assert logits.shape == (B, C)
    assert logits.dtype == jnp.float32
    assert np.isfinite(np.asarray(logits)).all()


def test_unknown_arch_rejected():
    with pytest.raises(ValueError):
        ConvNetConfig(arch="resnext")


def test_dp_step_reduces_loss():
    import optax

    cfg = ConvNetConfig(arch="nin", num_classes=4, dtype="float32",
                        head="gap")
    params = seeds(cfg)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(16, HW, HW, 3), jnp.float32)
    y = jnp.asarray(rng.randint(0, 4, 16))
    mc = MeshConfig(data=8)
    opt = optax.adam(3e-3)
    opt_state = opt.init(params)

    grad_fn = jax.shard_map(
        lambda p, xx, yy: jax.value_and_grad(
            lambda q: jax.lax.pmean(
                softmax_cross_entropy(convnet_apply(cfg, q, xx), yy),
                "data"))(p),
        mesh=mc.mesh, in_specs=(P(), P("data"), P("data")),
        out_specs=(P(), P()))

    @jax.jit
    def step(p, s):
        loss, g = grad_fn(p, x, y)
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s, loss

    losses = []
    for _ in range(10):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize("arch,fin", [("alex", 9216), ("vgg16", 25088)])
def test_reference_flatten_head_parity(arch, fin):
    """head="flatten" at the native insize reproduces the reference FC
    fan-ins (alex 256*6*6=9216 @227, vgg16 512*7*7=25088 @224) and a
    consistent end-to-end shape (checked via eval_shape, no FLOPs)."""
    cfg = ConvNetConfig(arch=arch, num_classes=C, dtype="float32")
    params = jax.eval_shape(
        lambda: init_convnet(jax.random.PRNGKey(0), cfg))
    fc = [p for p in params if p and p["w"].ndim == 2][0]
    assert fc["w"].shape == (fin, 4096)
    out = jax.eval_shape(
        lambda p, x: convnet_apply(cfg, p, x), params,
        jax.ShapeDtypeStruct((2, cfg.insize, cfg.insize, 3), jnp.float32))
    assert out.shape == (2, C)


def test_googlenet_aux_heads():
    """Reference geometry at 224px: aux taps flatten 4·4·128=2048, all
    three logit sets have class shape (checked via eval_shape); with_aux
    on other archs raises."""
    cfg = ConvNetConfig(arch="googlenet", num_classes=C, dtype="float32")
    params = jax.eval_shape(
        lambda: init_convnet(jax.random.PRNGKey(0), cfg))
    assert params["aux_4a"]["fc1"]["w"].shape == (2048, 1024)
    assert params["fc"]["w"].shape == (1024, C)
    outs = jax.eval_shape(
        lambda p, x: convnet_apply(cfg, p, x, with_aux=True), params,
        jax.ShapeDtypeStruct((2, 224, 224, 3), jnp.float32))
    assert [o.shape for o in outs] == [(2, C)] * 3

    with pytest.raises(ValueError, match="with_aux"):
        convnet_apply(ConvNetConfig(arch="alex"), [], None, with_aux=True)


def test_googlenet_gap_aux_small_input():
    """Size-robust head: aux classifiers GAP (fc1 128->1024) and run at
    32px with finite values."""
    cfg = ConvNetConfig(arch="googlenet", num_classes=C, dtype="float32",
                        head="gap")
    params = seeds(cfg)
    assert params["aux_4a"]["fc1"]["w"].shape == (128, 1024)
    x = jnp.asarray(np.random.RandomState(0).randn(2, HW, HW, 3),
                    jnp.float32)
    logits, a1, a2 = jax.jit(lambda p, x: convnet_apply(
        cfg, p, x, with_aux=True))(params, x)
    for o in (logits, a1, a2):
        assert o.shape == (2, C)
        assert np.isfinite(np.asarray(o)).all()


def test_flatten_head_rejects_collapsing_size():
    with pytest.raises(ValueError, match="collapses"):
        init_convnet(jax.random.PRNGKey(0),
                     ConvNetConfig(arch="alex", num_classes=C,
                                   image_size=32))
    with pytest.raises(ValueError, match="224"):
        init_convnet(jax.random.PRNGKey(0),
                     ConvNetConfig(arch="googlenet", num_classes=C,
                                   image_size=112))
