"""The comparison that decides ``correct``.

What the timed path produced for the checked cells of a sweep (final
params, the evals after every eval round, the per-round traces) is held
against the plain reference of the same cells.  Each number is a worst
case over the checked cells; each is compared with its limit in the
cell's ``limits/<cell>.json``:

    loss         relative gap of the global loss, over every eval
    acc          gap of the test accuracy (a share), over every eval
    grad_norm    relative gap of the mean per-device gradient norm in
                 rounds 0-2 (round 0 is the gradient pass alone)
    noise_scale  relative gap of the round's receiver-noise scale, every
                 round
    active       largest difference in the count of transmitting
                 devices, every round
    delta        gap between the norms of the parameters' change over
                 the sweep, worst leaf, against the reference's norm of
                 that leaf or of the median leaf, whichever is larger;
                 leaves whose round-0 reference gradient is under a
                 thousandth of the median leaf's are left out

A leaf is named by its key path (``reference.leaf_names``), so the
parameters may be any tree.
"""
from __future__ import annotations

import jax
import numpy as np

from bench.reference import leaf_names

GRAD_ROUNDS = 3
LEAF_RULE = 1e-3


def gather(result, picks: list) -> dict:
    """The checked cells of a program ``FLResult`` as host arrays, in the
    layout ``reference.simulate`` returns."""
    params = {name: np.stack([np.asarray(jax.device_get(leaf[r, s]),
                                         np.float32) for r, s in picks])
              for name, leaf in zip(leaf_names(result.params),
                                    jax.tree.leaves(result.params))}
    evals = [(int(t), {k: np.asarray([np.asarray(ev[k])[r, s]
                                      for r, s in picks], np.float64)
                       for k in ("global_loss", "acc")})
             for t, ev in result.evals]
    traces = {k: np.stack([np.asarray(result.traces[k])[r, s]
                           for r, s in picks])
              for k in ("grad_norm_mean", "noise_scale", "active_devices")}
    return {"params": params, "evals": evals, "traces": traces}


def _rel(a, b, floor=0.0):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    den = np.maximum(np.abs(b), floor)
    gap = np.abs(a - b)
    out = np.where(den > 0, gap / np.where(den > 0, den, 1.0),
                   np.where(gap > 0, np.inf, 0.0))
    return float(np.max(out)) if out.size else 0.0


def numbers(prog: dict, ref: dict, params0: dict) -> dict:
    """Every compared number of one check (see the module docstring)."""
    out = {}
    if [t for t, _ in prog["evals"]] != [t for t, _ in ref["evals"]]:
        raise ValueError("the program and the reference evaluated after "
                         "different rounds")
    out["loss"] = max(_rel(p["global_loss"], r["global_loss"])
                      for (_, p), (_, r) in zip(prog["evals"], ref["evals"]))
    out["acc"] = max(float(np.max(np.abs(p["acc"] - r["acc"])))
                     for (_, p), (_, r) in zip(prog["evals"], ref["evals"]))
    pt, rt = prog["traces"], ref["traces"]
    out["grad_norm"] = _rel(pt["grad_norm_mean"][:, :GRAD_ROUNDS],
                            rt["grad_norm_mean"][:, :GRAD_ROUNDS])
    out["noise_scale"] = _rel(pt["noise_scale"], rt["noise_scale"])
    out["active"] = float(np.max(np.abs(pt["active_devices"]
                                        - rt["active_devices"])))
    # leaf_grad's order: the tree's flatten order, as ref["params"] keeps
    names = list(ref["params"])
    p0 = {k: np.asarray(v, np.float64)
          for k, v in zip(leaf_names(params0), jax.tree.leaves(params0))}
    worst = 0.0
    for c in range(ref["leaf_grad"].shape[0]):
        grad = ref["leaf_grad"][c]
        keep = grad >= LEAF_RULE * np.median(grad)
        dp = np.array([np.linalg.norm(prog["params"][k][c] - p0[k])
                       for k in names])
        dr = np.array([np.linalg.norm(ref["params"][k][c] - p0[k])
                       for k in names])
        scale = np.maximum(dr, np.median(dr[keep]))
        worst = max(worst, float(np.max((np.abs(dp - dr) / scale)[keep])))
    out["delta"] = worst
    return out


def verdict(nums: dict, limits: dict) -> tuple:
    """(all within limits, {name: {"value", "limit"}}) over the limits'
    names; a number with no reading (a failed sweep) fails."""
    checks, ok = {}, True
    for name, limit in limits.items():
        value = nums.get(name)
        checks[name] = {"value": value, "limit": limit}
        if value is None or not np.isfinite(value) or value > limit:
            ok = False
    return ok, checks
