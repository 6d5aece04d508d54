"""Plain f32 reference of the paper's MLP, its inputs and its FLOP count.

    784 -> 1024 (ReLU) -> 10, l2-regularized mean cross-entropy.

Written from the paper's description; it imports nothing of the program.
Every function takes the configuration dict of ``paper_mlp.json``.

The functions a configuration module gives the benchmark:

    init_params(key, cfg)   initial weights, any tree of arrays
    make_data(key, cfg)     {"train_x", "train_y"} with leading [N, M]
                            axes (devices, samples per device), and
                            "test_x", "test_y", "global_x", "global_y";
                            floating inputs take the reference's dtype,
                            integer ones (labels, token ids) keep theirs
    loss(params, batch, cfg), logits(params, x)
    flops_per_sample(cfg)   forward plus backward of one sample
    samples_per_device(cfg, batch_size), optional: the samples a device
                            trains on per round; where it is absent
                            (as here), ``model.step_mfu`` takes the
                            shard of samples_per_class x num_classes over
                            the devices, cut to the minibatch
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def shapes(cfg) -> dict:
    m = cfg["model"]
    i, h, c = m["input_dim"], m["hidden"], m["num_classes"]
    return {"w1": (i, h), "b1": (h,), "w2": (h, c), "b2": (c,)}


def init_params(key, cfg) -> dict:
    """Weights N(0, 1/fan_in), biases zero, f32."""
    out = {}
    for k, (name, shape) in zip(jax.random.split(key, 4),
                                sorted(shapes(cfg).items())):
        if len(shape) == 1:
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         / jnp.sqrt(jnp.float32(shape[0])))
    return out


def logits(params, x):
    h = jnp.maximum(x @ params["w1"] + params["b1"], 0)
    return h @ params["w2"] + params["b2"]


def loss(params, batch, cfg):
    x, y = batch
    z = logits(params, x)
    zmax = jnp.max(z, axis=-1, keepdims=True)
    logz = jnp.log(jnp.sum(jnp.exp(z - zmax), axis=-1)) + zmax[:, 0]
    gold = jnp.take_along_axis(z, y[:, None], axis=-1)[:, 0]
    reg = sum(jnp.sum(p * p) for p in jax.tree.leaves(params))
    return jnp.mean(logz - gold) + 0.5 * cfg["model"]["l2"] * reg


def flops_per_sample(cfg) -> int:
    """Forward plus backward of one sample: 3 x (2 x in x out) per dense
    layer; biases, activations and the loss are left out."""
    m = cfg["model"]
    fwd = 2 * (m["input_dim"] * m["hidden"] + m["hidden"] * m["num_classes"])
    return 3 * fwd


def _samples(key, templates, labels, noise):
    x = templates[labels] + noise * jax.random.normal(
        key, labels.shape + templates.shape[1:], jnp.float32)
    return jnp.clip(x, 0.0, 1.0)


def make_data(key, cfg) -> dict:
    """Class-template images and the paper's ring label split: device m
    holds labels m and m+1 (mod classes), samples_per_class / 2 of each."""
    c, d = cfg["model"]["num_classes"], cfg["model"]["input_dim"]
    n, lpd = cfg["num_devices"], cfg["labels_per_device"]
    per_label = cfg["samples_per_class"] * c // (n * lpd)
    kt, ks, kx, kxt, kxg, kyg = jax.random.split(key, 6)
    templates = jnp.where(jax.random.uniform(kt, (c, d)) < 0.15,
                          jax.random.uniform(ks, (c, d), minval=0.5,
                                             maxval=1.0), 0.0)
    labels = (jnp.arange(n)[:, None] + jnp.arange(lpd)[None, :]) % c
    y = jnp.repeat(labels, per_label, axis=1).astype(jnp.int32)   # [N, M]
    yt = jnp.repeat(jnp.arange(c), cfg["test_per_class"]).astype(jnp.int32)
    yg = jax.random.randint(kyg, (cfg["global_eval"],), 0, c, jnp.int32)
    noise = cfg["data_noise"]
    return {"train_x": _samples(kx, templates, y, noise), "train_y": y,
            "test_x": _samples(kxt, templates, yt, noise), "test_y": yt,
            "global_x": _samples(kxg, templates, yg, noise), "global_y": yg}
