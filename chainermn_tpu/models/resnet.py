"""ResNet-50/101/152 — the reference's headline benchmark model
(reference: ``examples/imagenet/models/resnet50.py``; unverified — mount
empty, see SURVEY.md).

TPU-first design decisions (vs a Chainer translation):

- **NHWC** layout (TPU conv native; the reference is NCHW for cuDNN);
- params fp32, compute bf16: convs/matmuls hit the MXU at full rate and
  XLA fuses the BN + ReLU chains into the conv epilogues;
- functional: ``(params, state)`` pytrees in, ``(logits, state)`` out —
  BN running stats are explicit state, not hidden mutation;
- cross-replica BN is the *same* code path as local BN: pass
  ``axis_name="data"`` inside ``shard_map`` and the batch statistics are
  ``pmean``'d over the mesh axis (the reference needed a separate
  ``MultiNodeBatchNormalization`` link; here it is one optional kwarg via
  :func:`chainermn_tpu.links.multi_node_batch_normalization`).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.links.batch_normalization import (
    BatchNormState,
    init_batch_norm,
    multi_node_batch_normalization,
)
from chainermn_tpu.utils.telemetry import device_scope

__all__ = ["ResNetConfig", "init_resnet", "resnet_apply"]

_STAGES = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


@dataclass(frozen=True)
class ResNetConfig:
    depth: int = 50
    num_classes: int = 1000
    width: int = 64            # stem channels; stage c = width * 2**i
    dtype: str = "bfloat16"    # compute dtype (params/stats stay fp32)

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def stage_sizes(self) -> Tuple[int, ...]:
        return _STAGES[self.depth]


# --------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------- #


def _conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    w = jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)
    return w * jnp.sqrt(2.0 / fan_in)


def _init_bottleneck(key, cin, cmid, cout, projection):
    ks = jax.random.split(key, 4)
    p = {
        "conv1": _conv_init(ks[0], 1, 1, cin, cmid),
        "conv2": _conv_init(ks[1], 3, 3, cmid, cmid),
        "conv3": _conv_init(ks[2], 1, 1, cmid, cout),
    }
    s = {}
    for name, c in (("bn1", cmid), ("bn2", cmid), ("bn3", cout)):
        p[name], s[name] = init_batch_norm(c)
    # zero-init the last BN gamma: residual branches start as identity
    # (standard large-batch ResNet recipe; Goyal et al. 2017)
    p["bn3"]["gamma"] = jnp.zeros_like(p["bn3"]["gamma"])
    if projection:
        p["proj"] = _conv_init(ks[3], 1, 1, cin, cout)
        p["bn_proj"], s["bn_proj"] = init_batch_norm(cout)
    return p, s


def init_resnet(key, cfg: ResNetConfig):
    """Returns ``(params, state)`` pytrees (all fp32)."""
    key, k_stem, k_fc = jax.random.split(key, 3)
    params = {"conv1": _conv_init(k_stem, 7, 7, 3, cfg.width)}
    state = {}
    params["bn1"], state["bn1"] = init_batch_norm(cfg.width)

    cin = cfg.width
    for i, n_blocks in enumerate(cfg.stage_sizes):
        cmid = cfg.width * (2 ** i)
        cout = cmid * 4
        for j in range(n_blocks):
            key, sub = jax.random.split(key)
            name = f"stage{i + 1}_block{j + 1}"
            params[name], state[name] = _init_bottleneck(
                sub, cin, cmid, cout, projection=(j == 0))
            cin = cout

    params["fc"] = {
        "w": jax.random.normal(k_fc, (cin, cfg.num_classes), jnp.float32)
        * jnp.sqrt(1.0 / cin),
        "b": jnp.zeros((cfg.num_classes,), jnp.float32),
    }
    return params, state


# --------------------------------------------------------------------- #
# apply
# --------------------------------------------------------------------- #


def _conv(x, w, stride=1):
    with device_scope("resnet/conv"):
        return lax.conv_general_dilated(
            x, w.astype(x.dtype),
            window_strides=(stride, stride),
            padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )


def _bn_relu(p, s, x, axis_name, train, relu=True):
    y, new_s = multi_node_batch_normalization(
        p, s, x, axis_name=axis_name, train=train)
    return (jax.nn.relu(y) if relu else y), new_s


def _bottleneck(p, s, x, stride, axis_name, train):
    ns = {}
    h, ns["bn1"] = _bn_relu(
        p["bn1"], s["bn1"], _conv(x, p["conv1"]), axis_name, train)
    h, ns["bn2"] = _bn_relu(
        p["bn2"], s["bn2"], _conv(h, p["conv2"], stride), axis_name, train)
    h, ns["bn3"] = _bn_relu(
        p["bn3"], s["bn3"], _conv(h, p["conv3"]), axis_name, train,
        relu=False)
    if "proj" in p:
        x, ns["bn_proj"] = _bn_relu(
            p["bn_proj"], s["bn_proj"], _conv(x, p["proj"], stride),
            axis_name, train, relu=False)
    return jax.nn.relu(h + x), ns


def resnet_apply(
    cfg: ResNetConfig,
    params,
    state,
    x,
    *,
    train: bool = True,
    axis_name: Optional[str] = None,
):
    """Forward pass.

    Args:
      x: ``(B, H, W, 3)`` images (any float dtype; cast to compute dtype).
      axis_name: mesh axis for cross-replica BN statistics (pass
        ``"data"`` inside shard_map for the MultiNodeBatchNormalization
        behaviour); ``None`` = local BN.

    Returns ``(logits_fp32, new_state)``.
    """
    x = x.astype(cfg.compute_dtype)
    new_state = {}
    h = _conv(x, params["conv1"], stride=2)
    h, new_state["bn1"] = _bn_relu(
        params["bn1"], state["bn1"], h, axis_name, train)
    h = lax.reduce_window(
        h, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")

    for i, n_blocks in enumerate(cfg.stage_sizes):
        for j in range(n_blocks):
            name = f"stage{i + 1}_block{j + 1}"
            stride = 2 if (j == 0 and i > 0) else 1
            h, new_state[name] = _bottleneck(
                params[name], state[name], h, stride, axis_name, train)

    h = jnp.mean(h, axis=(1, 2))                       # global average pool
    logits = (h.astype(jnp.float32) @ params["fc"]["w"]
              + params["fc"]["b"])
    return logits, new_state
