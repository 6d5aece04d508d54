"""SCA power-control benchmarks: solution quality, convergence, timing.

``solver_benchmark`` compares the host scipy SLSQP loop (``core.sca``)
against the compiled batched solver (``repro.solvers``) across device
counts and scenario-batch sizes, and persists the rows to
``experiments/sca/solver_benchmark.json`` — the BENCH trajectory for the
solver subsystem (acceptance: the 64-scenario batch solve is >= 10x faster
than the looped scipy baseline at matching objective quality).
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.core import channel, sca, theory
from repro.core.theory import OTAParams

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                            "sca")


def make_prm(n: int, seed: int, d: int = 814090) -> OTAParams:
    wcfg = channel.WirelessConfig(num_devices=n, seed=seed)
    dep = channel.deploy(wcfg)
    return OTAParams(d=d, gmax=10.0, es=wcfg.energy_per_sample,
                     n0=wcfg.noise_psd, gains=dep.gains,
                     sigma_sq=np.zeros(n), eta=0.05, lsmooth=1.0,
                     kappa_sq=4.0)


def batch_gap_vs_scipy(n: int = 6, batch: int = 4, placement=None):
    """Relative (P1) objective gap of a ``batch``-scenario JAX solve at
    ``n`` devices against the scipy SLSQP oracle: every row against its own
    SLSQP solve, the gap of largest magnitude returned.  ``placement`` as
    in ``solvers.solve_batch``.  Returns (gap, solvers.BatchResult)."""
    from repro import solvers

    prms = [make_prm(n, s) for s in range(batch)]
    br = solvers.solve_batch(prms, placement=placement)
    gaps = [float(br.objective[i] / sca.solve_sca(p).objective - 1.0)
            for i, p in enumerate(prms)]
    return max(gaps, key=abs), br


def run(num_seeds: int = 5, sizes=(10, 20, 50)) -> list:
    rows = []
    for n in sizes:
        gaps, iters, times, vs_zb = [], [], [], []
        for seed in range(num_seeds):
            prm = make_prm(n, seed)
            t0 = time.time()
            res = sca.solve_sca(prm)
            dt = time.time() - t0
            oracle = sca.solve_direct(prm, num_starts=6, seed=seed)
            zb = theory.p1_objective(theory.zero_bias_gamma(prm), prm)
            gaps.append(res.objective / max(oracle.objective, 1e-30) - 1.0)
            vs_zb.append(res.objective / zb)
            iters.append(res.iterations)
            times.append(dt)
        rows.append({
            "bench": f"sca_n{n}",
            "us_per_call": round(np.mean(times) * 1e6, 1),
            "iters_mean": round(float(np.mean(iters)), 1),
            "gap_vs_oracle_max": round(float(np.max(gaps)), 5),
            "objective_vs_zero_bias": round(float(np.mean(vs_zb)), 4),
        })
    return rows


def solver_benchmark(sizes=(10, 20, 50), batches=(1, 16, 64),
                     save: bool = True) -> dict:
    """scipy ``solve_sca`` loop vs compiled ``solvers.solve_batch``.

    Per device count: the objective gap on the reference scenario and
    per-batch wall clocks (compile excluded for the jax path — recorded
    separately — since the executable is reused across rounds/sweeps; the
    scipy baseline pays its full cost every call and is timed as such).
    Writes ``experiments/sca/solver_benchmark.json``.
    """
    from repro import solvers

    out = {"sizes": [], "config": dataclasses_asdict(solvers.DEFAULT_CONFIG)}
    for n in sizes:
        prms = [make_prm(n, seed) for seed in range(max(batches))]
        # objective quality on the reference scenario (seed 0)
        ref = sca.solve_sca(prms[0])
        res = solvers.solve(prms[0])
        row = {
            "num_devices": n,
            "scipy_objective": ref.objective,
            "jax_objective": res.objective,
            "objective_rel_gap": res.objective / ref.objective - 1.0,
            "batch": [],
        }
        for b in batches:
            sub = prms[:b]
            t0 = time.time()
            scipy_objs = [sca.solve_sca(p).objective for p in sub]
            t_scipy = time.time() - t0
            t0 = time.time()
            br = solvers.solve_batch(sub)
            t_compile = time.time() - t0       # includes compile on first use
            t0 = time.time()
            br = solvers.solve_batch(sub)
            t_jax = time.time() - t0
            gaps = [theory.p1_objective(br.gamma[i], sub[i])
                    / max(scipy_objs[i], 1e-30) - 1.0 for i in range(b)]
            row["batch"].append({
                "batch_size": b,
                "scipy_loop_s": round(t_scipy, 4),
                "jax_batch_s": round(t_jax, 4),
                "jax_first_call_s": round(t_compile, 4),
                "speedup": round(t_scipy / max(t_jax, 1e-9), 2),
                "objective_rel_gap_max": float(np.max(gaps)),
            })
        out["sizes"].append(row)
    if save:
        os.makedirs(ARTIFACT_DIR, exist_ok=True)
        path = os.path.join(ARTIFACT_DIR, "solver_benchmark.json")
        with open(path, "w") as f:
            json.dump(out, f, indent=2)
        print(f"# wrote {os.path.relpath(path)}")
    return out


def dataclasses_asdict(cfg) -> dict:
    import dataclasses
    return {k: (list(v) if isinstance(v, tuple) else v)
            for k, v in dataclasses.asdict(cfg).items()}


def solver_rows(result: dict) -> list:
    """Flatten solver_benchmark output into the repo's CSV row convention."""
    rows = []
    for size in result["sizes"]:
        n = size["num_devices"]
        for b in size["batch"]:
            rows.append({
                "bench": f"sca_solver_n{n}_b{b['batch_size']}",
                "us_per_call": round(b["jax_batch_s"] * 1e6
                                     / b["batch_size"], 1),
                "scipy_loop_s": b["scipy_loop_s"],
                "jax_batch_s": b["jax_batch_s"],
                "speedup": b["speedup"],
                "gap_max": f"{b['objective_rel_gap_max']:.2e}",
            })
    return rows


def tradeoff_sweep(n: int = 10, seed: int = 0, points: int = 9) -> list:
    """Bias-variance decomposition along gamma = f * gamma_max (paper §III-A
    discussion): noise falls and bias rises as f grows."""
    prm = make_prm(n, seed)
    gm = theory.gamma_max(prm)
    rows = []
    for f in np.linspace(0.2, 1.0, points):
        gamma = f * gm
        z = theory.zeta_terms(gamma, prm)
        _, _, p = theory.participation(gamma, prm)
        rows.append({
            "bench": f"tradeoff_f{f:.2f}",
            "noise_var": z["noise"],
            "tx_var": z["transmission"],
            "bias": theory.bias_term(p, prm),
            "objective": theory.p1_objective(gamma, prm),
        })
    return rows


def bound_decomposition(n: int = 10, seed: int = 0,
                        rounds=(50, 200, 1000)) -> list:
    """Theorem-1 bound components for the SCA and zero-bias designs."""
    prm = make_prm(n, seed)
    res = sca.solve_sca(prm)
    rows = []
    for name, gamma in [("sca", res.gamma),
                        ("zero_bias", theory.zero_bias_gamma(prm))]:
        for t in rounds:
            b = theory.theorem1_bound(gamma, prm, init_gap=5.0, num_rounds=t)
            rows.append({
                "bench": f"bound_{name}_T{t}",
                "optimization": round(b["optimization"], 4),
                "variance": round(b["variance"], 4),
                "bias": round(b["bias"], 6),
                "total": round(b["total"], 4),
            })
    return rows
