"""The paper's experiment model (§IV): one-hidden-layer MLP for 10-class
28x28 image classification.

784 -> 1024 (ReLU) -> 10, with l2-regularized cross-entropy (coef 0.01).
Parameter count: 784*1024 + 1024 + 1024*10 + 10 = 814,090 = d  (paper's d).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.param import ParamDef

INPUT_DIM = 784
HIDDEN_DIM = 1024
NUM_CLASSES = 10
L2_COEF = 0.01
PARAM_DIM = INPUT_DIM * HIDDEN_DIM + HIDDEN_DIM + HIDDEN_DIM * NUM_CLASSES + NUM_CLASSES


def mlp_defs(hidden: int = HIDDEN_DIM, num_classes: int = NUM_CLASSES,
             input_dim: int = INPUT_DIM):
    return {
        "w1": ParamDef((input_dim, hidden), init="scaled",
                       spec=P("data", "model"), dtype=jnp.float32,
                       fan_in=input_dim),
        "b1": ParamDef((hidden,), init="zeros", spec=P("model"),
                       dtype=jnp.float32),
        "w2": ParamDef((hidden, num_classes), init="scaled",
                       spec=P("model", None), dtype=jnp.float32,
                       fan_in=hidden),
        "b2": ParamDef((num_classes,), init="zeros", spec=P(None),
                       dtype=jnp.float32),
    }


def mlp_forward(params, x):
    """x: [B, 784] -> logits [B, 10]."""
    h = jax.nn.relu(x @ params["w1"] + params["b1"])
    return h @ params["w2"] + params["b2"]


def mlp_loss(params, batch, l2: float = L2_COEF):
    """l2-regularized mean cross-entropy; batch = (x [B,784], y [B])."""
    x, y = batch
    logits = mlp_forward(params, x)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    xent = jnp.mean(logz - gold)
    reg = sum(jnp.sum(p.astype(jnp.float32) ** 2)
              for p in jax.tree.leaves(params))
    return xent + 0.5 * l2 * reg


def top1_accuracy(logits, y):
    """Share of rows of ``logits`` [B, C] whose label ``y`` [B] is the
    first maximal logit — exactly ``mean(argmax(logits, -1) == y)``, ties
    included, but written as max reductions and compares.

    On a TPU v5e (jax 0.9.0, libtpu 0.0.34) ``jnp.argmax`` fused into the
    program that computes the logits returned class 0 for every sample in
    six of the seven cells of the fleet's vmapped eval; the max-and-compare
    form gives the host's argmax there."""
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    classes = jnp.arange(logits.shape[-1])
    earlier = jnp.where(classes[None, :] < y[:, None], logits, -jnp.inf)
    first_max = (gold >= jnp.max(logits, -1)) & (gold > jnp.max(earlier, -1))
    return jnp.mean(first_max.astype(jnp.float32))


def accuracy(params, x, y):
    return top1_accuracy(mlp_forward(params, x), y)
