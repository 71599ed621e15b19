"""ResNet (He et al., arXiv:1512.03385, Table 1) forward, loss and SGD
steps in plain float32 ``jax.numpy``: the yardstick the ResNet cells'
first steps are held to.

Departures from the paper, all to match what this repo's model
computes (the reference has to describe the same function):
- the stride of a down-sampling bottleneck is on its 3x3 convolution
  (torchvision's "v1.5"), not on the first 1x1;
- batch-norm eps is 2e-5 (Chainer's default);
- "SAME" padding, so the 7x7 stem pads 2/3 and not 3/3.

Train-mode batch-norm uses the batch's own statistics, so the running
averages never enter the loss and are not followed.  Each bottleneck is
under ``jax.checkpoint``: the float32 activations of 256 images at
224 px do not fit a chip otherwise (layer by layer, as the builder's
contract allows); the arithmetic is unchanged.
"""

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .common import delta_norms, leaf_norms, rounder, to_floats

STAGES = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}
BN_EPS = 2e-5


def init(key, cfg):
    """Seeded weights in the layout the program's ``init_resnet`` uses
    (the driver checks shape for shape).  He-normal convolutions,
    batch-norm scale 1 and shift 0 except the last scale of each
    bottleneck, ``cfg["last_bn_scale"]``: at the program's own 0 every
    gradient inside a residual branch is exactly zero on the first step
    and the check would see two thirds of the leaves only from step 2."""
    width, classes = cfg["width"], cfg["num_classes"]
    keys = iter(jax.random.split(key, 256))

    def conv(k, cin, cout):
        return jax.random.normal(next(keys), (k, k, cin, cout),
                                 jnp.float32) * (2.0 / (k * k * cin)) ** .5

    def bn(c, scale=1.0):
        return {"gamma": jnp.full((c,), scale, jnp.float32),
                "beta": jnp.zeros((c,), jnp.float32)}

    params = {"conv1": conv(7, 3, width), "bn1": bn(width)}
    cin = width
    for i, n_blocks in enumerate(STAGES[cfg["depth"]]):
        cmid, cout = width * 2 ** i, width * 2 ** i * 4
        for j in range(n_blocks):
            p = {"conv1": conv(1, cin, cmid), "bn1": bn(cmid),
                 "conv2": conv(3, cmid, cmid), "bn2": bn(cmid),
                 "conv3": conv(1, cmid, cout),
                 "bn3": bn(cout, cfg["last_bn_scale"])}
            if j == 0:
                p["proj"] = conv(1, cin, cout)
                p["bn_proj"] = bn(cout)
            params[f"stage{i + 1}_block{j + 1}"] = p
            cin = cout
    params["fc"] = {
        "w": jax.random.normal(next(keys), (cin, classes), jnp.float32)
        * cin ** -.5,
        "b": jnp.zeros((classes,), jnp.float32)}
    return params


def _conv(rnd, x, w, stride=1):
    return lax.conv_general_dilated(
        rnd(x), rnd(w), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(p, x):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
    return (x - mean) * lax.rsqrt(var + BN_EPS) * p["gamma"] + p["beta"]


def _bottleneck(rnd, p, x, stride):
    h = jax.nn.relu(_bn(p["bn1"], _conv(rnd, x, p["conv1"])))
    h = jax.nn.relu(_bn(p["bn2"], _conv(rnd, h, p["conv2"], stride)))
    h = _bn(p["bn3"], _conv(rnd, h, p["conv3"]))
    if "proj" in p:
        x = _bn(p["bn_proj"], _conv(rnd, x, p["proj"], stride))
    return jax.nn.relu(h + x)


def loss_fn(cfg, precision, params, x, y):
    """Mean softmax cross-entropy of the whole batch, train mode."""
    rnd = rounder(precision)
    h = jax.nn.relu(_bn(params["bn1"], _conv(rnd, x, params["conv1"], 2)))
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1),
                          (1, 2, 2, 1), "SAME")
    for i, n_blocks in enumerate(STAGES[cfg["depth"]]):
        for j in range(n_blocks):
            stride = 2 if (j == 0 and i > 0) else 1
            block = jax.checkpoint(
                lambda p, h, s=stride: _bottleneck(rnd, p, h, s))
            h = block(params[f"stage{i + 1}_block{j + 1}"], h)
    h = jnp.mean(h, axis=(1, 2))
    logits = rnd(h) @ rnd(params["fc"]["w"]) + params["fc"]["b"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def follow(cfg, make_params, batches, precision="float32"):
    """Drive SGD with momentum from ``make_params()`` through ``batches``
    (one step each; the start is made again at the end rather than kept
    beside the optimizer's state) and return what the check compares:
    every step's loss, the norm of each leaf's first gradient, and the
    norm of each leaf's change after the last step."""
    if cfg["optimizer"]["name"] != "sgd":
        raise SystemExit("this plain reference writes out the rule of SGD "
                         "with momentum; another optimizer is another "
                         "reference file")
    opt = cfg["optimizer"]["args"]
    lr, momentum = opt["learning_rate"], opt["momentum"]

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, trace, x, y):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, precision, p, x, y))(params)
        trace = jax.tree.map(lambda t, g: momentum * t + g, trace, grads)
        params = jax.tree.map(lambda p, t: p - lr * t, params, trace)
        return params, trace, loss, leaf_norms(grads)

    with jax.default_matmul_precision("highest"):
        params = make_params()
        trace = jax.tree.map(jnp.zeros_like, params)
        losses, grad_norms = [], None
        for x, y in batches:
            params, trace, loss, norms = step(
                params, trace, jnp.asarray(x, jnp.float32), jnp.asarray(y))
            losses.append(float(loss))
            grad_norms = grad_norms or to_floats(norms)
        del trace
        delta = delta_norms(params, make_params())
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta}
