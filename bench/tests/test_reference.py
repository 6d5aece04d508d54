"""The reference folds the devices and runs one checked cell at a time.

Today's formulation is kept here as ``stacked_round``: every device's
gradient stacked by a vmap and summed over the device axis.  The folded
reference agrees with it to f32 rounding, on the paper's MLP and on a
nested-tree token model defined here; its compiled chunk's temporaries
do not grow with the number of devices, where the stacked one's grow by
a parameter copy a device; and a nested tree with integer inputs goes
through ``simulate``, ``check.gather`` and ``check.numbers``.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from bench import check, reference, sweep

RTOL = 1e-6


def stacked_round(model, cfg, batch, gmax, grid, dtype, keep, params, key,
                  data, c):
    """One round with the N device gradients stacked (the formulation the
    fold replaced): same keys, same minibatch indices, same noise."""
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
    h = reference._fading(k_fade, c["gains"], c["k_factor"])
    k_coeff, k_noise = jax.random.split(k_ota)
    s, ns = reference._coeffs(c, jnp.abs(h), k_coeff, n, grid)
    s, ns = s.astype(dtype), ns.astype(dtype)
    p_leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(k_noise, len(p_leaves))
    out = []
    for p, g, k in zip(p_leaves, jax.tree.leaves(grads), keys):
        z = jax.random.normal(k, (p.size,), jnp.float32).reshape(p.shape)
        ghat = (jnp.sum(s.reshape((-1,) + (1,) * p.ndim) * g, axis=0)
                + ns * z.astype(dtype))
        out.append((p - c["eta"].astype(dtype) * ghat).astype(dtype))
    metrics = {"grad_norm_mean": jnp.mean(norms),
               "noise_scale": ns.astype(jnp.float32),
               "active_devices": jnp.sum((s > 0).astype(jnp.float32)),
               "leaf_grad": jnp.mean(leaf_norms, axis=0)}
    return jax.tree.unflatten(treedef, out), key, metrics


class TokenModel:
    """Token embedding, one dense ReLU layer, a head tied to the
    embedding; mean next-token cross-entropy.  A vocabulary over 256
    holds ids that bfloat16 cannot represent."""
    VOCAB, WIDTH, LENGTH = 300, 8, 5

    @staticmethod
    def init_params(key):
        ke, kw = jax.random.split(key)
        w = TokenModel.WIDTH
        return {"embed": 0.3 * jax.random.normal(ke, (TokenModel.VOCAB, w)),
                "layers": [{"b": jnp.zeros((w,)),
                            "w": jax.random.normal(kw, (w, w)) / w ** 0.5}]}

    @staticmethod
    def logits(params, x):
        h = params["embed"][x]
        for layer in params["layers"]:
            h = jnp.maximum(h @ layer["w"] + layer["b"], 0.0)
        return h @ params["embed"].T

    @staticmethod
    def loss(params, batch, cfg):
        x, y = batch
        z = TokenModel.logits(params, x)
        gold = jnp.take_along_axis(z, y[..., None], axis=-1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(z, axis=-1) - gold)

    @staticmethod
    def make_data(key, devices, per_device=6):
        ks = jax.random.split(key, 6)
        shape = (TokenModel.LENGTH,)

        def ids(k, lead):
            return jax.random.randint(k, lead + shape, 0, TokenModel.VOCAB)
        return {"train_x": ids(ks[0], (devices, per_device)),
                "train_y": ids(ks[1], (devices, per_device)),
                "test_x": ids(ks[2], (7,)), "test_y": ids(ks[3], (7,)),
                "global_x": ids(ks[4], (9,)), "global_y": ids(ks[5], (9,))}


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    bench = tiny.make_root(str(tmp_path_factory.mktemp("reference")))
    cell = sweep.load_cell("tiny_mlp", bench)
    return cell, sweep.make_world(cell)


ROWS = [1, 3, 4, 6]            # opc, lcpc, vanilla, bbfl_alternative


def _simulate(world, model, cfg, data, params0, batch, **kw):
    cell, w = world
    return reference.simulate(
        model, cfg, data, params0,
        reference.cell_rows(w.coeffs, w.fading, w.etas, ROWS),
        [11, 12, 13, 2 ** 31 - 5], rounds=5, every=2,
        batch=batch, gmax=cell.config["gmax"], **kw)


def _mlp(world, seed=5):
    cell, _ = world
    data, params0 = sweep.make_inputs(cell, seed)
    return cell.model, cell.config, data, params0


def _tokens(devices=10, seed=5):
    kd, kp = jax.random.split(jax.random.PRNGKey(seed))
    return (TokenModel, {}, TokenModel.make_data(kd, devices),
            TokenModel.init_params(kp))


def _gap(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


@pytest.mark.parametrize("kind, batch", [("mlp", 8), ("mlp", 0),
                                         ("tokens", 3), ("tokens", 0)])
def test_folded_reference_matches_the_stacked_one(world, monkeypatch, kind,
                                                  batch):
    model, cfg, data, params0 = _mlp(world) if kind == "mlp" else _tokens()
    folded = _simulate(world, model, cfg, data, params0, batch)
    monkeypatch.setattr(reference, "_round", stacked_round)
    stacked = _simulate(world, model, cfg, data, params0, batch)
    assert list(folded["params"]) == list(stacked["params"])
    for k in folded["params"]:
        assert _gap(folded["params"][k], stacked["params"][k]) < RTOL, k
    for (t, f), (u, s) in zip(folded["evals"], stacked["evals"]):
        assert t == u
        assert _gap(f["global_loss"], s["global_loss"]) < RTOL
        np.testing.assert_array_equal(f["acc"], s["acc"])
    for k in ("grad_norm_mean", "noise_scale"):
        assert _gap(folded["traces"][k], stacked["traces"][k]) < RTOL, k
    np.testing.assert_array_equal(folded["traces"]["active_devices"],
                                  stacked["traces"]["active_devices"])
    assert _gap(folded["leaf_grad"], stacked["leaf_grad"]) < RTOL


def _temp_bytes(world, devices):
    """Temporary bytes of the reference's compiled chunk at ``devices``
    devices: an MLP of 784 -> 256 -> 10, minibatch 16 of 64 a device."""
    cell, w = world
    cfg = {**cell.config, "model": {**cell.config["model"], "hidden": 256}}
    chunk = reference.make_chunk(cell.model, cfg, batch=16, gmax=10.0,
                                 grid=16)
    coeff = {**w.coeffs[1], "gamma": np.ones(devices),
             "thresholds": np.zeros(devices), "mask": np.ones(devices)}
    fading = {"gains": np.ones(devices), "k_factor": np.zeros(devices)}
    one = jax.tree.map(lambda a: a[0], reference.cell_rows(
        [coeff], [fading], [0.05], [0]))
    params = jax.eval_shape(lambda: cell.model.init_params(
        jax.random.PRNGKey(0), cfg))
    data = (jax.ShapeDtypeStruct((devices, 64, 784), jnp.float32),
            jax.ShapeDtypeStruct((devices, 64), jnp.int32))
    compiled = chunk.lower(params, jax.random.PRNGKey(0), one, data,
                           length=2).compile()
    copy = sum(p.size * p.dtype.itemsize for p in jax.tree.leaves(params))
    return compiled.memory_analysis().temp_size_in_bytes, copy


def test_reference_footprint_does_not_grow_with_the_devices(world,
                                                            monkeypatch):
    small, copy = _temp_bytes(world, 2)
    large, _ = _temp_bytes(world, 8)
    assert large - small < copy, (small, large, copy)
    # the stacked formulation holds every device's gradient: the same
    # measurement sees it grow by more than a copy a device
    monkeypatch.setattr(reference, "_round", stacked_round)
    small, _ = _temp_bytes(world, 2)
    large, _ = _temp_bytes(world, 8)
    assert large - small > 6 * copy, (small, large, copy)


def _program_result(ref, params0, picks, rows, seeds):
    """An ``FLResult``-like object holding ``ref``'s cells at ``picks`` of
    a [rows, seeds] grid, in ``params0``'s tree, and zeros elsewhere."""
    def grid(stack):
        out = np.zeros((rows, seeds) + stack.shape[1:], stack.dtype)
        for c, (r, s) in enumerate(picks):
            out[r, s] = stack[c]
        return out

    params = jax.tree.unflatten(
        jax.tree.structure(params0),
        [jnp.asarray(grid(v)) for v in ref["params"].values()])
    return types.SimpleNamespace(
        params=params,
        evals=[(t, {k: grid(v) for k, v in ev.items()})
               for t, ev in ref["evals"]],
        traces={k: grid(v) for k, v in ref["traces"].items()})


def test_a_nested_tree_of_token_ids_goes_through_the_check(world):
    model, cfg, data, params0 = _tokens()
    ref = _simulate(world, model, cfg, data, params0, batch=3)
    assert list(ref["params"]) == ["embed", "layers/0/b", "layers/0/w"]
    assert ref["leaf_grad"].shape == (len(ROWS), 3)
    assert all(ev["acc"].shape == (len(ROWS),) for _, ev in ref["evals"])
    picks = [(0, 1), (1, 0), (2, 2), (3, 1)]
    fake = _program_result(ref, params0, picks, 4, 3)
    prog = check.gather(fake, picks)
    assert list(prog["params"]) == list(ref["params"])
    nums = check.numbers(prog, ref, jax.device_get(params0))
    assert nums == {k: 0.0 for k in nums}
    # a change of one nested leaf of one checked cell shows in delta
    fake.params["layers"][0]["w"] = fake.params["layers"][0]["w"].at[
        2, 2].multiply(1.5)
    nums = check.numbers(check.gather(fake, picks), ref,
                         jax.device_get(params0))
    assert nums["delta"] > 0.1


def test_only_floating_inputs_take_the_dtype():
    ids = jnp.asarray([257, 299], jnp.int32)
    assert reference._as_input(ids, jnp.bfloat16).dtype == jnp.int32
    np.testing.assert_array_equal(reference._as_input(ids, jnp.bfloat16),
                                  ids)
    assert reference._as_input(jnp.ones(2), jnp.bfloat16).dtype == \
        jnp.bfloat16


def test_the_control_runs_on_token_ids(world):
    model, cfg, data, params0 = _tokens()
    ref = _simulate(world, model, cfg, data, params0, batch=3)
    ctl = _simulate(world, model, cfg, data, params0, batch=3,
                    dtype=jnp.bfloat16, precision=None)
    nums = check.numbers(ctl, ref, jax.device_get(params0))
    assert all(np.isfinite(v) for v in nums.values())
    assert nums["delta"] > tiny.LIMITS["delta"]
