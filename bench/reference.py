"""Plain reference of an over-the-air FL sweep cell, in jax.numpy.

One cell trains the configuration's model from the benchmark's initial
weights for the traffic's rounds.  Each round, written from the paper's
description (eqs. (3)-(6)) and imports nothing of the program:

    key, sub = split(key);  k_fade, k_ota, k_batch = split(sub, 3)
    each device m: a minibatch drawn uniformly with replacement from its
        shard (k_batch), or its whole shard; g_m = grad of the loss,
        clipped to global norm G_max
    h = the channel draw, CN(0, Lambda) or Rician with factor K (k_fade)
    k_coeff, k_noise = split(k_ota)
    (s, noise_scale) = the scheme's coefficient rule on |h| (k_coeff)
    ghat = sum_m s_m g_m + noise_scale * z,  z ~ N(0, I) drawn per leaf
        from split(k_noise, leaves) in the leaves' sorted-name order
    params = params - eta * ghat

The evaluation after each eval round gives the global loss and the test
logits; the accuracy is the argmax on the host.  The design constants of
each scheme (pre-scalers, thresholds, masks, power limits) are inputs of
the cell, like the data.

``simulate`` runs a few cells side by side (vmapped), at ``highest``
matmul precision for the reference, or in a lower dtype for the control.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np


def eval_rounds(rounds: int, every: int) -> list:
    """Rounds after which the sweep evaluates: every ``every``-th round
    from round 0, and the last."""
    return sorted(set(range(0, rounds, every)) | {rounds - 1})


def _fading(key, gains, k_factor):
    kr, ki = jax.random.split(key)
    diffuse = gains / (k_factor + 1.0)
    scale = jnp.sqrt(diffuse / 2.0)
    re = jax.random.normal(kr, gains.shape) * scale
    im = jax.random.normal(ki, gains.shape) * scale
    los = jnp.sqrt(gains * k_factor / (k_factor + 1.0))
    return jax.lax.complex(los + re, im)


def _first_min(vals):
    """Index of the first minimum, by compare (not argmin)."""
    idx = jnp.arange(vals.shape[0])
    return jnp.min(jnp.where(vals == jnp.min(vals), idx, vals.shape[0]))


def _coeffs(c, habs, key, n: int, grid: int):
    """(s [N], noise_scale) of one round, by the scheme kind of ``c``:
    0 noiseless uniform average, 1 truncated channel inversion with
    per-device pre-scalers, 2 full inversion to the weakest channel,
    3 per-round MSE-optimal scale on a log grid, 4 scheduling a subset
    (or, at random, everyone)."""
    inv_n = jnp.full((n,), 1.0 / n, habs.dtype)

    def ideal(_):
        return inv_n, jnp.zeros((), habs.dtype)

    def truncated(_):
        chi = (habs >= c["thresholds"]).astype(habs.dtype)
        return chi * c["gamma"] / c["alpha"], c["noise_over_alpha"]

    def vanilla(_):
        return inv_n, jnp.sqrt(c["n0"]) / (n * c["bmax"] * jnp.min(habs))

    def opc(_):
        base = c["bmax"] * habs * n

        def mse(scale):
            b = jnp.minimum(scale / (n * habs), c["bmax"])
            return (jnp.sum((b * habs / scale - 1.0 / n) ** 2)
                    * c["gmax"] ** 2 + c["n0"] / scale ** 2)

        cands = jnp.exp(jnp.linspace(jnp.log(0.02 * jnp.min(base)),
                                     jnp.log(50.0 * jnp.max(base)), grid))
        best = cands[_first_min(jax.vmap(mse)(cands))]
        for _ in range(2):
            fine = best * jnp.exp(jnp.linspace(-0.15, 0.15, 33))
            best = fine[_first_min(jax.vmap(mse)(fine))]
        b = jnp.minimum(best / (n * habs), c["bmax"])
        return b * habs / best, jnp.sqrt(c["n0"]) / best

    def scheduled(mask):
        k = jnp.maximum(jnp.sum(mask), 1.0)
        weakest = jnp.min(jnp.where(mask > 0, habs, jnp.inf))
        return mask / k, jnp.sqrt(c["n0"]) / (k * c["bmax"] * weakest)

    def bbfl(_):
        s_in, ns_in = scheduled(c["mask"])
        s_all, ns_all = scheduled(jnp.ones_like(c["mask"]))
        everyone = jnp.logical_and(jax.random.bernoulli(key, 0.5),
                                   c["alternative"] > 0)
        return (jnp.where(everyone, s_all, s_in),
                jnp.where(everyone, ns_all, ns_in))

    s, ns = jax.lax.switch(c["kind"], (ideal, truncated, vanilla, opc, bbfl),
                           None)
    return s.astype(habs.dtype), jnp.asarray(ns, habs.dtype)


def _round(model, cfg, batch: int, gmax: float, grid: int, dtype,
           keep: float, params, key, data, c):
    x, y = data
    n, m = x.shape[0], x.shape[1]
    key, sub = jax.random.split(key)
    k_fade, k_ota, k_batch = jax.random.split(sub, 3)
    if 0 < batch < m:
        idx = jax.random.randint(k_batch, (n, batch), 0, m)
        x = jax.vmap(lambda xm, im: xm[im])(x, idx)
        y = jax.vmap(lambda ym, im: ym[im])(y, idx)
    if keep < 1.0:
        x, y = x[:, :int(keep * x.shape[1])], y[:, :int(keep * y.shape[1])]

    def device(xm, ym):
        g = jax.grad(model.loss)(params, (xm, ym), cfg)
        leaf = jnp.stack([jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
                          for l in jax.tree.leaves(g)])
        norm = jnp.sqrt(jnp.sum(leaf ** 2))
        scale = jnp.minimum(1.0, gmax / jnp.maximum(norm, 1e-12))
        return jax.tree.map(lambda l: (l * scale).astype(dtype), g), norm, leaf

    grads, norms, leaf_norms = jax.vmap(device)(x, y)
    h = _fading(k_fade, c["gains"], c["k_factor"])
    k_coeff, k_noise = jax.random.split(k_ota)
    s, ns = _coeffs(c, jnp.abs(h), k_coeff, n, grid)
    s, ns = s.astype(dtype), ns.astype(dtype)
    p_leaves, treedef = jax.tree.flatten(params)
    g_leaves = jax.tree.leaves(grads)
    keys = jax.random.split(k_noise, len(p_leaves))
    out = []
    for p, g, k in zip(p_leaves, g_leaves, keys):
        z = jax.random.normal(k, (p.size,), jnp.float32).reshape(p.shape)
        ghat = (jnp.sum(s.reshape((-1,) + (1,) * p.ndim) * g, axis=0)
                + ns * z.astype(dtype))
        out.append((p - c["eta"].astype(dtype) * ghat).astype(dtype))
    metrics = {"grad_norm_mean": jnp.mean(norms),
               "noise_scale": ns.astype(jnp.float32),
               "active_devices": jnp.sum((s > 0).astype(jnp.float32)),
               "leaf_grad": jnp.mean(leaf_norms, axis=0)}
    return jax.tree.unflatten(treedef, out), key, metrics


def cell_rows(coeffs: list, fading: list, etas: list, rows: list) -> dict:
    """Stack the per-cell inputs the reference reads: design constants,
    channel statistics and step size of each checked cell's row."""
    def col(get, dtype=np.float32):
        return jnp.asarray(np.stack([np.asarray(get(r), np.float64)
                                     for r in rows]).astype(dtype))

    out = {k: col(lambda r, k=k: coeffs[r][k])
           for k in ("gamma", "alpha", "thresholds", "noise_over_alpha",
                     "bmax", "n0", "gmax", "mask", "alternative")}
    out["kind"] = col(lambda r: coeffs[r]["kind"], np.int32)
    out["gains"] = col(lambda r: fading[r]["gains"])
    out["k_factor"] = col(lambda r: fading[r]["k_factor"])
    out["eta"] = col(lambda r: etas[r])
    return out


def simulate(model, cfg: dict, data: dict, params0: dict, cells: dict,
             seeds: list, *, rounds: int, every: int, batch: int,
             gmax: float, grid: int = 128, dtype=jnp.float32,
             precision="highest", keep: float = 1.0) -> dict:
    """Train the checked cells side by side; returns host arrays:

    params    [leaf name -> [C, ...]] after the last round
    evals     [(round, {"global_loss": [C], "acc": [C]})]
    traces    {"grad_norm_mean", "noise_scale", "active_devices": [C, T]}
    leaf_grad [C, L] mean per-device gradient norm of each leaf in round 0

    ``keep`` < 1 trains on that leading share of each device's batch only
    (a planted fault for the calibration, never the reference itself).
    """
    num = len(seeds)
    round_fn = functools.partial(_round, model, cfg, batch, gmax, grid, dtype,
                                 keep)
    xd = (jnp.asarray(data["train_x"], dtype), jnp.asarray(data["train_y"]))
    gx = jnp.asarray(data["global_x"], dtype)
    gy = jnp.asarray(data["global_y"])
    tx = jnp.asarray(data["test_x"], dtype)

    # the data are arguments, not closed over: a closed-over array would
    # be compiled into the program as a constant
    @functools.partial(jax.jit, static_argnames=("length",))
    def chunk(params, keys, cells, xd, length):
        def one(p, k, c):
            def step(carry, _):
                p, k = carry
                p, k, met = round_fn(p, k, xd, c)
                return (p, k), met
            (p, k), met = jax.lax.scan(step, (p, k), None, length=length)
            return p, k, met
        return jax.vmap(one)(params, keys, cells)

    @jax.jit
    def evaluate(params, gx, gy, tx):
        def one(p):
            return model.loss(p, (gx, gy), cfg), model.logits(p, tx)
        return jax.vmap(one)(params)

    ctx = jax.default_matmul_precision(precision) if precision \
        else contextlib.nullcontext()
    test_y = np.asarray(data["test_y"])
    params = jax.tree.map(
        lambda a: jnp.broadcast_to(jnp.asarray(a, dtype),
                                   (num,) + jnp.shape(a)), params0)
    keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
    evals, mets, t = [], [], -1
    with ctx:
        for point in eval_rounds(rounds, every):
            params, keys, met = chunk(params, keys, cells, xd,
                                      length=point - t)
            mets.append(jax.tree.map(np.asarray, met))
            t = point
            loss, logits = evaluate(params, gx, gy, tx)
            pred = np.argmax(np.asarray(logits, np.float32), axis=-1)
            evals.append((t, {"global_loss": np.asarray(loss, np.float64),
                              "acc": np.mean(pred == test_y[None], axis=-1)}))
    traces = {k: np.concatenate([m[k] for m in mets], axis=1)
              for k in ("grad_norm_mean", "noise_scale", "active_devices")}
    return {"params": {k: np.asarray(v, np.float32)
                       for k, v in params.items()},
            "evals": evals, "traces": traces,
            "leaf_grad": mets[0]["leaf_grad"][:, 0]}
