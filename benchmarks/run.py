"""Benchmark harness (deliverable d): one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fig2-rounds N] [--skip-fig2]
    PYTHONPATH=src python -m benchmarks.run --smoke

Emits ``name,us_per_call,derived`` CSV rows per the repo convention, plus a
human-readable summary.  Roofline rows appear when experiments/dryrun/
artifacts exist (produced by repro.launch.dryrun).

``--smoke`` is the CI engine-regression gate: it drives the scan/vmap
experiment engine end to end on CPU in a couple of minutes — the full
7-scheme fig2 fleet for a handful of minibatch rounds plus a short
scenario-sweep training fleet — and fails loudly if the compiled engine
stops producing finite, learning trajectories.
"""
from __future__ import annotations

import argparse
import time


def _csv(row: dict) -> str:
    name = row.pop("bench", None) or row.pop("scheme", None) \
        or f"{row.pop('arch', '?')}_{row.pop('shape', '')}"
    us = row.pop("us_per_call", "")
    derived = ";".join(f"{k}={v}" for k, v in row.items())
    return f"{name},{us},{derived}"


def smoke(seed: int = 0) -> None:
    """Minutes-scale engine smoke: compiled fig2 fleet + scenario fleet."""
    import numpy as np

    from benchmarks import fig2, scenario_sweep

    print("bench,us_per_call,derived")
    t0 = time.time()
    hist = fig2.run(num_rounds=8, eval_every=4, seed=seed, batch_size=64,
                    save=False)
    assert set(hist) == set(fig2.SCHEMES), sorted(hist)
    for name, rows in hist.items():
        accs = [r["acc"] for r in rows]
        assert np.all(np.isfinite(accs)), (name, accs)
        assert rows[-1]["active"] >= 1.0, (name, rows[-1])
        print(_csv({"bench": f"smoke_fig2_{name}",
                    "final_acc": round(accs[-1], 4)}), flush=True)
    print(f"# smoke fig2 fleet (7 schemes x 8 rounds): "
          f"{time.time() - t0:.1f}s", flush=True)

    t0 = time.time()
    rows = scenario_sweep.train_sweep(
        scenario_names=("disk_rayleigh", "disk_markov"), num_rounds=4,
        eval_every=2, seed=seed, batch_size=64)
    for r in rows:
        assert np.isfinite(r["final_acc"]), r
        print(_csv({"bench": f"smoke_{r['scenario']}_{r['scheme']}",
                    "final_acc": r["final_acc"]}), flush=True)
    print(f"# smoke scenario fleets: {time.time() - t0:.1f}s", flush=True)

    # --- batched SCA solver + AdaptiveSCA engine gate (DESIGN.md §Solvers):
    # a tiny batch solve must track the scipy oracle, and the adaptive
    # scheme must re-design inside a compiled Gauss-Markov fleet ---
    t0 = time.time()
    from benchmarks.sca_bench import batch_gap_vs_scipy
    gap, br = batch_gap_vs_scipy()
    assert abs(gap) < 1e-3, (gap, br.objective[0])
    assert np.all(np.isfinite(br.gamma)) and np.all(br.gamma > 0)
    print(_csv({"bench": "smoke_solver_batch4", "gap_vs_scipy": f"{gap:.2e}",
                "objective": round(float(br.objective[0]), 4)}), flush=True)

    import jax
    from repro.core import power_control as pcm, scenarios as scn
    from repro.data import partition, synthetic
    from repro.fl import engine as eng
    from repro.fl.server import FLRunConfig
    from repro.models import mlp
    from repro.models.param import init_params
    sc = scn.get_scenario("disk_markov")
    dep = scn.realize(sc)
    prm = scn.make_ota_params(dep, d=10000, gmax=10.0, eta=0.05, kappa_sq=4.0)
    fp = scn.make_fading_process(dep, sc.dynamics)
    x, y, xt, yt = synthetic.mnist_like(40, seed=seed)
    data = partition.stack_shards(partition.partition_by_label(x, y, 10,
                                                               seed=seed))
    params0 = init_params(mlp.mlp_defs(hidden=32), jax.random.PRNGKey(seed))
    run_cfg = FLRunConfig(eta=0.05, num_rounds=4, eval_every=2)
    pc = pcm.make_power_control("adaptive_sca", dep, prm)
    res = eng.run_fleet(mlp.mlp_loss, params0, [pc], dep.gains, data,
                        run_cfg, fading=fp, flat=False)
    assert res.designs is not None and len(res.designs) >= 2, res.designs
    g0, g1 = res.designs[0][1], res.designs[1][1]
    moved = float(np.max(np.abs(g1 - g0) / np.abs(g0)))
    assert moved > 1e-4, "adaptive re-design did not move the design"
    assert all(np.all(np.isfinite(np.asarray(v))) for v in
               jax.tree.leaves(res.params))
    print(_csv({"bench": "smoke_adaptive_sca",
                "design_moved_rel": round(moved, 4),
                "redesigns": len(res.designs) - 1}), flush=True)
    print(f"# smoke solver + adaptive engine: {time.time() - t0:.1f}s",
          flush=True)

    # --- task-registry gate (DESIGN.md §Tasks): grow a few-round
    # cifar_conv fleet through the fleet stack INCLUDING a kill-and-resume
    # step; on the forced >= 4-device mesh (the CI tasks-smoke job) the
    # grid shards over the debug mesh, otherwise it runs vmapped ---
    import os
    import tempfile

    from repro import tasks
    from repro.fl.driver import run_fleet_task

    t0 = time.time()
    task = tasks.get("cifar_conv", channels=(8, 16), hidden=32,
                     samples_per_class=24, test_per_class=10, alpha=1.0)
    dep_t, prm_t, td = fig2.build_world(task, seed=seed)
    pcs_t = fig2.make_schemes(task, dep_t, prm_t, ["ideal", "sca"])
    run_cfg = task.run_config(num_rounds=6, eval_every=2, batch_size=4,
                              seed=seed)
    placement, where = None, "vmap"
    if jax.device_count() >= 4:
        from repro.fl.placement import ShardedPlacement
        from repro.launch.mesh import make_debug_mesh
        placement = ShardedPlacement(make_debug_mesh(2, 2))
        where = f"sharded{placement.num_devices}"
    kw = dict(task_data=td, seeds=(0, 1), flat=True, placement=placement)
    with tempfile.TemporaryDirectory() as tmp:
        ck = os.path.join(tmp, "cifar_fleet")
        res_part = run_fleet_task(task, pcs_t, dep_t.gains, run_cfg, **kw,
                                  checkpoint_path=ck, max_chunks=1)  # kill
        rounds_part = res_part.traces["active_devices"].shape[-1]
        assert rounds_part < run_cfg.num_rounds, rounds_part
        res_res = run_fleet_task(task, pcs_t, dep_t.gains, run_cfg, **kw,
                                 checkpoint_path=ck, resume=True)
        res_full = run_fleet_task(task, pcs_t, dep_t.gains, run_cfg, **kw)
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(res_res.params),
                               jax.tree.leaves(res_full.params))), \
        "cifar_conv resume is not bitwise vs the uninterrupted fleet"
    final_acc = np.asarray(res_res.evals[-1][1]["acc"])
    assert final_acc.shape == (2, 2) and np.all(np.isfinite(final_acc))
    print(_csv({"bench": f"smoke_cifar_conv_{where}",
                "final_acc_ideal": round(float(final_acc[0].mean()), 4),
                "resumed_rounds_done": rounds_part,
                "resume_bitwise": 1}), flush=True)
    print(f"# smoke cifar_conv task fleet ({where}, kill+resume): "
          f"{time.time() - t0:.1f}s", flush=True)
    print("# smoke OK", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fig2-rounds", type=int, default=150)
    ap.add_argument("--fig2-every", type=int, default=15)
    ap.add_argument("--skip-fig2", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: short compiled-engine runs, asserts")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    from repro import compile_cache
    compile_cache.enable()

    if args.smoke:
        smoke(seed=args.seed)
        return

    print("bench,us_per_call,derived")

    # --- SCA solver quality/timing (paper §III-B) ---
    from benchmarks import sca_bench
    for row in sca_bench.run(num_seeds=3, sizes=(10, 20)):
        print(_csv(row), flush=True)

    # --- scipy-vs-batched-solver benchmark (DESIGN.md §Solvers); persists
    # experiments/sca/solver_benchmark.json ---
    for row in sca_bench.solver_rows(sca_bench.solver_benchmark()):
        print(_csv(row), flush=True)

    # --- bias-variance trade-off sweep (paper §III-A / Theorem 1) ---
    for row in sca_bench.tradeoff_sweep():
        print(_csv(row), flush=True)

    # --- Theorem-1 bound decomposition ---
    for row in sca_bench.bound_decomposition():
        print(_csv(row), flush=True)

    # --- scenario-family sweep (DESIGN.md §Scenarios) ---
    from benchmarks import scenario_sweep
    for row in scenario_sweep.sweep():
        row["bench"] = f"scenario_{row.pop('scenario')}_{row.pop('scheme')}"
        for k in ("bias", "variance", "var_transmission", "var_noise",
                  "objective", "p_spread", "mean_participation",
                  "gain_spread_db"):
            row[k] = f"{row[k]:.4g}"
        print(_csv(row), flush=True)

    # --- kernel micro-benches ---
    from benchmarks import kernel_bench
    for row in kernel_bench.run():
        print(_csv(row), flush=True)

    # --- Fig. 2 reproduction (the paper's main experiment): the whole
    # scheme grid through one compiled scan program (fl.engine) ---
    if not args.skip_fig2:
        from benchmarks import fig2
        t0 = time.time()
        hist = fig2.run(num_rounds=args.fig2_rounds,
                        eval_every=args.fig2_every, seed=args.seed)
        wall = time.time() - t0
        for row in fig2.summarize(hist):
            row["bench"] = "fig2_" + row.pop("scheme")
            print(_csv(row), flush=True)
        print(f"# fig2 wall time: {wall:.1f}s", flush=True)

    # --- roofline terms from dry-run artifacts (if present) ---
    from benchmarks import roofline
    rows = roofline.run()
    for row in rows:
        row["bench"] = f"roofline_{row.pop('arch')}_{row.pop('shape')}"
        for k in ("compute_s", "memory_s", "collective_s",
                  "model_flops_per_device"):
            row[k] = f"{row[k]:.4g}"
        row["useful_flops_ratio"] = f"{row['useful_flops_ratio']:.3f}"
        print(_csv(row), flush=True)
    if not rows:
        print("# no dryrun artifacts yet — run: "
              "PYTHONPATH=src python -m repro.launch.dryrun --all",
              flush=True)


if __name__ == "__main__":
    main()
