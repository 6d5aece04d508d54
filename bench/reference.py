"""Plain reference of an over-the-air FL sweep cell, in jax.numpy.

One cell trains the configuration's model from the benchmark's initial
weights for the traffic's rounds.  Each round, written from the paper's
description (eqs. (3)-(6)) and imports nothing of the program:

    key, sub = split(key);  k_fade, k_ota, k_batch = split(sub, 3)
    h = the channel draw, CN(0, Lambda) or Rician with factor K (k_fade)
    k_coeff, k_noise = split(k_ota)
    (s, noise_scale) = the scheme's coefficient rule on |h| (k_coeff)
    minibatch indices of every device in one draw: uniform with
        replacement from its shard (k_batch), or its whole shard
    each device m in turn: g_m = grad of the loss, clipped to global
        norm G_max; sum_m s_m g_m accumulated in f32
    ghat = sum_m s_m g_m + noise_scale * z,  z ~ N(0, I) drawn per leaf
        from split(k_noise, leaves) in the tree's flatten order (for a
        flat dict, its sorted names)
    params = params - eta * ghat

The devices are folded one at a time in a scan, and the checked cells
run one after another, so a check holds one cell's params, the
accumulator, one device's gradient and activations, whatever the number
of devices or cells.  The evaluation after each eval round gives the
global loss and the test logits; the accuracy is the argmax on the host.
The design constants of each scheme (pre-scalers, thresholds, masks,
power limits) are inputs of the cell, like the data.  Parameters may be
any tree; a leaf is named by its key path (``leaf_names``).

``simulate`` runs at ``highest`` matmul precision for the reference, or
in a lower dtype for the control.
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


def leaf_names(tree) -> list:
    """Each leaf's key path joined with ``/``, in the tree's flatten order
    (for a flat dict: its keys, sorted)."""
    return [jax.tree_util.keystr(path, simple=True, separator="/")
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
                      for l in jax.tree.leaves(tree)])


def _round(model, cfg, batch: int, gmax: float, grid: int, dtype,
           keep: float, params, key, data, c):
    x, y = data
    n, m = x.shape[0], x.shape[1]
    key, sub = jax.random.split(key)
    k_fade, k_ota, k_batch = jax.random.split(sub, 3)
    h = _fading(k_fade, c["gains"], c["k_factor"])
    k_coeff, k_noise = jax.random.split(k_ota)
    s, ns = _coeffs(c, jnp.abs(h), k_coeff, n, grid)
    s, ns = s.astype(dtype), ns.astype(dtype)
    idx, rows = None, m
    if 0 < batch < m:
        idx, rows = jax.random.randint(k_batch, (n, batch), 0, m), batch
    rows = int(keep * rows)

    def device(carry, per):
        acc, norm_sum, leaf_sum = carry
        xm, ym, sm, im = per
        if im is not None:
            xm, ym = xm[im], ym[im]
        g = jax.grad(model.loss)(params, (xm[:rows], ym[:rows]), cfg)
        leaf = _leaf_norms(g)
        norm = jnp.sqrt(jnp.sum(leaf ** 2))
        scale = jnp.minimum(1.0, gmax / jnp.maximum(norm, 1e-12))
        g = jax.tree.map(lambda l: (l * scale).astype(dtype), g)
        acc = jax.tree.map(lambda a, l: a + (sm * l).astype(jnp.float32),
                           acc, g)
        return (acc, norm_sum + norm, leaf_sum + leaf), None

    p_leaves, treedef = jax.tree.flatten(params)
    zero = (jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            jnp.zeros((), jnp.float32), jnp.zeros(len(p_leaves), jnp.float32))
    (acc, norm_sum, leaf_sum), _ = jax.lax.scan(device, zero, (x, y, s, idx))
    keys = jax.random.split(k_noise, len(p_leaves))
    out = []
    for p, a, k in zip(p_leaves, jax.tree.leaves(acc), keys):
        z = jax.random.normal(k, (p.size,), jnp.float32).reshape(p.shape)
        ghat = a.astype(dtype) + ns * z.astype(dtype)
        out.append((p - c["eta"].astype(dtype) * ghat).astype(dtype))
    metrics = {"grad_norm_mean": norm_sum / n,
               "noise_scale": ns.astype(jnp.float32),
               "active_devices": jnp.sum((s > 0).astype(jnp.float32)),
               "leaf_grad": leaf_sum / n}
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


def make_chunk(model, cfg: dict, *, batch: int, gmax: float, grid: int,
               dtype=jnp.float32, keep: float = 1.0):
    """The jitted ``chunk(params, key, cell, data, length)`` of one cell:
    ``length`` rounds in a scan, returning (params, key, the per-round
    metrics).  ``params`` is donated."""
    round_fn = functools.partial(_round, model, cfg, batch, gmax, grid, dtype,
                                 keep)

    # the data are arguments, not closed over: a closed-over array would
    # be compiled into the program as a constant
    @functools.partial(jax.jit, static_argnames=("length",),
                       donate_argnums=0)
    def chunk(params, key, cell, data, length):
        def step(carry, _):
            p, k = carry
            p, k, met = round_fn(p, k, data, cell)
            return (p, k), met
        (params, key), met = jax.lax.scan(step, (params, key), None,
                                          length=length)
        return params, key, met
    return chunk


def _as_input(a, dtype):
    """``a`` on the device, in ``dtype`` where it holds floats: token ids
    and labels keep their integer type."""
    a = jnp.asarray(a)
    return a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a


def simulate(model, cfg: dict, data: dict, params0, cells: dict,
             seeds: list, *, rounds: int, every: int, batch: int,
             gmax: float, grid: int = 128, dtype=jnp.float32,
             precision="highest", keep: float = 1.0) -> dict:
    """Train the checked cells one after another; returns host arrays:

    params    {leaf name: [C, ...]} after the last round, named and
              ordered by ``leaf_names``
    evals     [(round, {"global_loss": [C], "acc": [C]})]; ``acc`` is the
              share of right argmax answers over every test target
    traces    {"grad_norm_mean", "noise_scale", "active_devices": [C, T]}
    leaf_grad [C, L] mean per-device gradient norm of each leaf in round 0

    One compiled chunk per chunk length and one compiled eval serve every
    cell, so the device holds one cell's state at a time.  ``keep`` < 1
    trains on that leading share of each device's batch only (a planted
    fault for the calibration, never the reference itself).
    """
    chunk = make_chunk(model, cfg, batch=batch, gmax=gmax, grid=grid,
                       dtype=dtype, keep=keep)

    @jax.jit
    def evaluate(params, gx, gy, tx):
        return model.loss(params, (gx, gy), cfg), model.logits(params, tx)

    xd = (_as_input(data["train_x"], dtype), _as_input(data["train_y"], dtype))
    gx, gy, tx = (_as_input(data[k], dtype)
                  for k in ("global_x", "global_y", "test_x"))
    test_y = np.asarray(data["test_y"])
    names = leaf_names(params0)
    points = eval_rounds(rounds, every)
    ctx = jax.default_matmul_precision(precision) if precision \
        else contextlib.nullcontext()
    runs = []
    with ctx:
        for c, seed in enumerate(seeds):
            cell = jax.tree.map(lambda a: a[c], cells)
            # a copy, since the chunk donates it
            params = jax.tree.map(lambda a: jnp.array(a, dtype), params0)
            key = jax.random.PRNGKey(int(seed))
            mets, losses, accs, t = [], [], [], -1
            for point in points:
                params, key, met = chunk(params, key, cell, xd,
                                         length=point - t)
                mets.append(jax.device_get(met))
                t = point
                loss, logits = evaluate(params, gx, gy, tx)
                pred = np.argmax(np.asarray(logits, np.float32), axis=-1)
                losses.append(float(loss))
                accs.append(float(np.mean(pred == test_y)))
            runs.append({
                "params": [np.asarray(l, np.float32)
                           for l in jax.tree.leaves(params)],
                "mets": mets, "loss": losses, "acc": accs})
            params = None
    return {"params": {k: np.stack([r["params"][i] for r in runs])
                       for i, k in enumerate(names)},
            "evals": [(t, {"global_loss": np.asarray([r["loss"][i]
                                                      for r in runs]),
                           "acc": np.asarray([r["acc"][i] for r in runs])})
                      for i, t in enumerate(points)],
            "traces": {k: np.stack([np.concatenate([m[k] for m in r["mets"]])
                                    for r in runs])
                       for k in ("grad_norm_mean", "noise_scale",
                                 "active_devices")},
            "leaf_grad": np.stack([r["mets"][0]["leaf_grad"][0]
                                   for r in runs])}
