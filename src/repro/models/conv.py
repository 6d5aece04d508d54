"""Small convnet for the CIFAR-class image task (DESIGN.md §Tasks).

Built from the same ParamDef primitives as every other model in models/
(one definition serves init, abstract lowering and param counting):

    conv 3x3 (3 -> c1) -> ReLU -> 2x2 avg-pool
    conv 3x3 (c1 -> c2) -> ReLU -> 2x2 avg-pool
    flatten -> dense hidden -> ReLU -> dense num_classes

All parameters are float32, so under the fleet engine's ``flat=True``
fused aggregation (kernels.ops.ota_aggregate_pytree) the raveled gradient
matrix accumulates in f32 with no mixed-dtype casts — the "f32-safe"
contract the cifar_conv task relies on.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.mlp import top1_accuracy
from repro.models.param import ParamDef, param_count

INPUT_SHAPE = (32, 32, 3)
NUM_CLASSES = 10
L2_COEF = 1e-4

# NHWC activations x HWIO kernels -> NHWC
_DIMNUMS = ("NHWC", "HWIO", "NHWC")


def conv_defs(channels: tuple = (16, 32), hidden: int = 128,
              num_classes: int = NUM_CLASSES,
              input_shape: tuple = INPUT_SHAPE):
    """ParamDef tree for the convnet (all f32)."""
    h, w, c_in = input_shape
    c1, c2 = channels
    pooled = (h // 4) * (w // 4) * c2        # two 2x2 pools
    return {
        "conv1": ParamDef((3, 3, c_in, c1), init="scaled", spec=P(),
                          dtype=jnp.float32, fan_in=3 * 3 * c_in),
        "bc1": ParamDef((c1,), init="zeros", spec=P(), dtype=jnp.float32),
        "conv2": ParamDef((3, 3, c1, c2), init="scaled", spec=P(),
                          dtype=jnp.float32, fan_in=3 * 3 * c1),
        "bc2": ParamDef((c2,), init="zeros", spec=P(), dtype=jnp.float32),
        "w1": ParamDef((pooled, hidden), init="scaled",
                       spec=P("data", "model"), dtype=jnp.float32,
                       fan_in=pooled),
        "b1": ParamDef((hidden,), init="zeros", spec=P("model"),
                       dtype=jnp.float32),
        "w2": ParamDef((hidden, num_classes), init="scaled",
                       spec=P("model", None), dtype=jnp.float32,
                       fan_in=hidden),
        "b2": ParamDef((num_classes,), init="zeros", spec=P(None),
                       dtype=jnp.float32),
    }


def conv_dim(channels: tuple = (16, 32), hidden: int = 128,
             num_classes: int = NUM_CLASSES,
             input_shape: tuple = INPUT_SHAPE) -> int:
    return param_count(conv_defs(channels, hidden, num_classes, input_shape))


def _avg_pool2(x: jax.Array) -> jax.Array:
    """2x2/2 average pool on NHWC."""
    b, h, w, c = x.shape
    return jnp.mean(x.reshape(b, h // 2, 2, w // 2, 2, c), axis=(2, 4))


def conv_forward(params, x: jax.Array) -> jax.Array:
    """x: [B, 32, 32, 3] -> logits [B, num_classes]."""
    h = jax.lax.conv_general_dilated(x, params["conv1"], (1, 1), "SAME",
                                     dimension_numbers=_DIMNUMS)
    h = _avg_pool2(jax.nn.relu(h + params["bc1"]))
    h = jax.lax.conv_general_dilated(h, params["conv2"], (1, 1), "SAME",
                                     dimension_numbers=_DIMNUMS)
    h = _avg_pool2(jax.nn.relu(h + params["bc2"]))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ params["w1"] + params["b1"])
    return h @ params["w2"] + params["b2"]


def conv_loss(params, batch, l2: float = L2_COEF):
    """l2-regularized mean cross-entropy; batch = (x [B,32,32,3], y [B])."""
    x, y = batch
    logits = conv_forward(params, x)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    xent = jnp.mean(logz - gold)
    reg = sum(jnp.sum(p.astype(jnp.float32) ** 2)
              for p in jax.tree.leaves(params))
    return xent + 0.5 * l2 * reg


def accuracy(params, x, y):
    return top1_accuracy(conv_forward(params, x), y)
