"""Plain reference for the ``resnet50`` configuration.

ResNet-50 (He et al. 2015, arXiv:1512.03385, Table 1, 50-layer column:
7x7/2 stem, 3x3/2 max-pool, bottleneck stages (3, 4, 6, 3) of widths
64..512 with 4x expansion, global average pool, 1000-way classifier),
training-mode forward pass and mean softmax cross-entropy in straight
``jax.numpy``: float32, ``jax.default_matmul_precision("highest")`` (set
by the caller), no flax module, no kernel.  It reads the system's own
parameter tree by its pinned names (``stem_conv``, ``stem_bn``,
``BottleneckBlock_{i}``, ``BatchNorm_{j}``, ``proj_conv``, ``Dense_0``).

Departures from the paper, all the program's and followed here so that
the two compute the same function: stride 2 sits on the 3x3 conv of a
stage's first block (the "v1.5" placement), the stem's relu comes after
the max-pool (it commutes with it), batch statistics use the biased
variance ``max(0, E[x^2] - E[x]^2)`` with epsilon 1e-5, and the input is
the batch AFTER the program's on-device crop/flip/normalize (the random
offsets are the program's own and are not re-derived here).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

STAGES = (3, 4, 6, 3)
EPS = 1e-5


def _conv(x, kernel, stride: int, padding):
    return lax.conv_general_dilated(
        x, kernel, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, p, residual=None, relu: bool = False):
    mean = x.mean((0, 1, 2))
    var = jnp.maximum(0.0, (x * x).mean((0, 1, 2)) - mean * mean)
    y = (x - mean) * (lax.rsqrt(var + EPS) * p["scale"]) + p["bias"]
    if residual is not None:
        y = y + residual
    return jnp.maximum(y, 0.0) if relu else y


def _block(x, p, stride: int):
    names = iter(f"BatchNorm_{i}" for i in range(4))
    residual = x
    if "proj_conv" in p:
        residual = _conv(x, p["proj_conv"]["Conv_0"]["kernel"], stride,
                         "SAME")
        residual = _bn(residual, p[next(names)])
    y = _conv(x, p["Conv_0"]["Conv_0"]["kernel"], 1, "SAME")
    y = _bn(y, p[next(names)], relu=True)
    y = _conv(y, p["Conv_1"]["Conv_0"]["kernel"], stride, "SAME")
    y = _bn(y, p[next(names)], relu=True)
    y = _conv(y, p["Conv_2"]["Conv_0"]["kernel"], 1, "SAME")
    return _bn(y, p[next(names)], residual=residual, relu=True)


def loss(params, x, labels):
    """Mean cross-entropy of the training-mode forward pass.  ``x`` is
    float32 (N, 224, 224, 3), already cropped and normalized."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = x.astype(jnp.float32)
    x = _conv(x, params["stem_conv"]["Conv_0"]["kernel"], 2,
              [(3, 3), (3, 3)])
    x = _bn(x, params["stem_bn"])
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    x = jnp.maximum(x, 0.0)
    index = 0
    for stage, n_blocks in enumerate(STAGES):
        for block in range(n_blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            x = _block(x, params[f"BottleneckBlock_{index}"], stride)
            index += 1
    x = x.mean((1, 2))
    head = params["Dense_0"]["Dense_0"]
    logits = x @ head["kernel"] + head["bias"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def inputs(model, batch, rng):
    """The reference's inputs from a raw store batch: the program's own
    on-device crop/flip/normalize, under the key ``TpuModel.loss_fn``
    derives for it from ``rng``."""
    x, labels = batch
    _, aug_rng = jax.random.split(rng)
    return (model.data.device_transform(jnp.asarray(x), aug_rng, train=True),
            jnp.asarray(labels))
