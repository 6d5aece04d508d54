"""Scenario-family sweep: bias/variance/objective per power-control scheme
across heterogeneous wireless deployments (DESIGN.md §Scenarios).

    PYTHONPATH=src python -m benchmarks.scenario_sweep [--train] [--sharded]
                                                       [--grid] [--rounds N]

``--grid`` (with ``--train``) is the scenario-grid payoff benchmark
(DESIGN.md §Grid): the same (scenario, scheme) sweep run twice — once as
today's SEQUENTIAL per-scenario fleets (one compile + execute per
scenario) and once as ONE compiled [C x K x S] grid through
``core.scenarios.ScenarioStack`` — with both walls, the C=1
grid-vs-fleet bitwise check, and the donate/no-donate peak-RSS probe
recorded in the ``scenario_grid`` section of the repo-root
BENCH_engine.json.

Multi-process bring-up (``--coordinator HOST:PORT --num-processes P
--process-id I [--local-devices N]``) joins a ``jax.distributed``
cluster before any backend touch and restricts this process to its
contiguous slice of the scenario axis (distributed.process_grid_slice);
artifacts are written by process 0 only.  See benchmarks/grid_smoke.py
for the 2-process forced-CPU proof.

For every scenario in the sweep grid (default: the four-family grid
``scenarios.SWEEP_FAMILIES`` — disk-Rayleigh baseline, Rician, shadowed,
two-cluster; ``--all`` sweeps the whole registry) and every statistical-CSI
scheme (sca / lcpc / zero_bias), this computes the Theorem-1 quantities with
the scenario's family-aware statistics:

    bias        2 N kappa^2 sum_m (p_m - 1/N)^2          (theory.bias_term)
    variance    zeta = transmission + minibatch + noise  (theory.zeta_terms)
    objective   2 eta L zeta + bias                      (the (P1) objective)

and emits one CSV row per (scenario, scheme).  With ``--train`` it also runs
an FL workload from the task registry (``--task``, default the paper's MLP;
DESIGN.md §Tasks) on each scenario's FadingProcess — the scheme axis as
one compiled scan fleet per scenario, through the task-first driver
(``fl.driver.run_fleet_task``; ``--sharded`` shards the cells over the
debug mesh) — and appends test accuracy.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

import numpy as np

from repro import compile_cache
from repro.configs.paper_mlp import CONFIG as PAPER
from repro.core import power_control as pcm
from repro.core import scenarios as scn
from repro.core import theory

SCHEMES = ("sca", "lcpc", "zero_bias")
ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                            "scenario_sweep")


def scheme_theory_row(name: str, dep, prm) -> dict:
    """Theorem-1 decomposition for a truncated-inversion scheme."""
    pc = pcm.make_power_control(name, dep, prm)
    z = theory.zeta_terms(pc.gamma, prm)
    bias = theory.bias_term(pc.p, prm)
    return {
        "scheme": name,
        "bias": bias,
        "variance": z["total"],
        "var_transmission": z["transmission"],
        "var_noise": z["noise"],
        "objective": 2.0 * prm.eta * prm.lsmooth * z["total"] + bias,
        "p_spread": float(np.max(pc.p) - np.min(pc.p)),
        "mean_participation": float(np.mean(
            theory.expected_participation_indicator(pc.gamma, prm))),
    }


def sweep(scenario_names=scn.SWEEP_FAMILIES, schemes=SCHEMES,
          d: int = 814090, gmax: float = 10.0, eta: float = 0.05,
          kappa_sq: float = 4.0, seed: int = 0) -> list:
    """One theory row per (scenario, scheme)."""
    rows = []
    for sc_name in scenario_names:
        sc = scn.get_scenario(sc_name)
        dep = scn.realize(sc, seed=seed)
        prm = scn.make_ota_params(dep, d=d, gmax=gmax, eta=eta,
                                  kappa_sq=kappa_sq)
        for scheme in schemes:
            row = scheme_theory_row(scheme, dep, prm)
            row.update(scenario=sc_name, fading=dep.fading_spec.family,
                       gain_spread_db=float(10 * np.log10(
                           dep.gains.max() / dep.gains.min())))
            rows.append(row)
    return rows


def train_sweep(scenario_names=scn.SWEEP_FAMILIES, schemes=SCHEMES,
                num_rounds: int = 100, eval_every: int = 20,
                seed: int = 0, log: bool = False,
                batch_size=None, placement=None,
                task="paper_mlp") -> list:
    """Short FL runs of a registered task per (scenario, scheme).

    The workload — data, params, loss, eval, per-scheme step sizes —
    comes from the task registry (``repro.tasks``, DESIGN.md §Tasks) and
    is built ONCE, shared across every scenario fleet.  Per scenario, the
    whole scheme axis runs as ONE compiled scan fleet through the
    task-first host driver (fl.driver.run_fleet_task, DESIGN.md
    §Placement) on the scenario's FadingProcess — the default
    sca/lcpc/zero_bias grid is a homogeneous TruncatedInversion stack, so
    a single cell program covers it; aggregation rides the flattened
    Pallas hot path (DESIGN.md §Engine).  ``placement`` maps each
    scenario's scheme grid onto hardware (None = single-device vmap;
    fl.placement.ShardedPlacement(mesh) shards the cells over the
    ("data", "model") mesh).
    """
    from repro import tasks as task_registry
    from repro.fl.driver import run_fleet_task

    if isinstance(task, str):
        task = task_registry.get(task, expect_runtime="fleet")
    elif task.runtime != "fleet":
        raise ValueError(f"task {task.name!r} is not a fleet workload")
    if batch_size is None:   # the task's preferred sweep mode (fig2 ditto)
        batch_size = int(task.defaults.get("batch_size", 0))
    td = task.build_data(seed)
    params0 = task.init_params(seed)
    evals = task.make_eval(td)

    rows = []
    for sc_name in scenario_names:
        sc = scn.get_scenario(sc_name)
        dep = scn.realize(sc, seed=seed)
        if len(dep.gains) != task.num_devices:
            raise ValueError(
                f"scenario {sc_name!r} deploys {len(dep.gains)} devices "
                f"but task {task.name!r} partitions {task.num_devices}")
        prm = scn.make_ota_params(dep, d=task.param_dim,
                                  gmax=float(task.defaults.get("gmax",
                                                               PAPER.gmax)),
                                  eta=0.05, kappa_sq=4.0)
        fading = scn.make_fading_process(dep, sc.dynamics)
        # global-CSI schemes pick up dropout-awareness from dep.p_dropout
        pcs = [pcm.make_power_control(s, dep, prm) for s in schemes]
        run_cfg = task.run_config(eta=0.05, num_rounds=num_rounds,
                                  eval_every=eval_every, seed=seed,
                                  batch_size=batch_size)
        # schemes are designed at prm.eta above, so train at that same
        # operating point (the task's per-scheme eta map is fig2's concern)
        res = run_fleet_task(task, pcs, dep.gains, run_cfg, task_data=td,
                             params=params0, eval_fn=evals,
                             etas=[run_cfg.eta] * len(schemes),
                             fading=fading, flat=True, log=log,
                             placement=placement)
        final = res.evals[-1][1]["acc"]
        for i, scheme in enumerate(schemes):
            rows.append({"scenario": sc_name, "scheme": scheme,
                         "final_acc": round(float(final[i, 0]), 4),
                         "rounds": num_rounds})
    return rows


# ---------------------------------------------------------------------------
# --grid: sequential-per-scenario fleets vs ONE compiled [C x K x S] grid
# (DESIGN.md §Grid) -> scenario_grid section of BENCH_engine.json.
# ---------------------------------------------------------------------------

def _walls(res) -> dict:
    return {"wall_s": round(res.wall, 2),
            "compile_s": round(res.wall_compile, 2),
            "exec_s": round(res.wall_exec, 2)}


def _task_gmax(task) -> float:
    return float(task.defaults.get("gmax", PAPER.gmax))


def _scenario_fleet_inputs(task, sc_name: str, schemes, seed: int):
    """(dep, fading, pcs, etas placeholder source) for one scenario."""
    sc = scn.get_scenario(sc_name)
    dep = scn.realize(sc, seed=seed)
    prm = scn.make_ota_params(dep, d=task.param_dim, gmax=_task_gmax(task),
                              eta=0.05, kappa_sq=4.0)
    pcs = [pcm.make_power_control(s, dep, prm) for s in schemes]
    return sc, dep, pcs


def _grid_fleet(task, scenario_names, schemes, run_cfg, seeds, *,
                task_data, params, eval_fn, placement=None):
    """ONE [C x K x S] fleet over the stacked scenario axis: the schemes
    are flattened scenario-major (the driver's layout) and the channel
    comes from the ScenarioStack, not a FadingProcess."""
    from repro.fl.driver import run_fleet_task

    stack = scn.stack_scenarios(scenario_names, seed=run_cfg.seed)
    pcs = []
    for sc_name in scenario_names:
        pcs.extend(_scenario_fleet_inputs(task, sc_name, schemes,
                                          run_cfg.seed)[2])
    return run_fleet_task(task, pcs, None, run_cfg, task_data=task_data,
                          params=params, eval_fn=eval_fn,
                          etas=[run_cfg.eta] * len(pcs), seeds=seeds,
                          flat=True, placement=placement, scenarios=stack)


def _results_bitwise(a, b) -> bool:
    import jax

    pa = [np.asarray(x) for x in jax.tree.leaves(a.params)]
    pb = [np.asarray(x) for x in jax.tree.leaves(b.params)]
    ok = len(pa) == len(pb) and all(np.array_equal(x, y)
                                    for x, y in zip(pa, pb))
    ok = ok and set(a.traces) == set(b.traces)
    return bool(ok and all(np.array_equal(a.traces[t], b.traces[t])
                           for t in a.traces))


def _rss_probe_child(task, scenario_names, schemes, num_rounds: int,
                     seed: int, num_seeds: int, donate: bool) -> None:
    """Child side of the peak-RSS probe: run the grid once with carry
    donation on/off and print the process high-water mark (satellite:
    donated scan-chunk carries should lower it)."""
    import resource

    from repro.fl.placement import VmapPlacement

    from repro import tasks as task_registry

    task = task_registry.get(task, expect_runtime="fleet")
    td = task.build_data(seed)
    run_cfg = task.run_config(eta=0.05, num_rounds=num_rounds,
                              eval_every=num_rounds, seed=seed,
                              batch_size=int(task.defaults.get(
                                  "batch_size", 0)))
    _grid_fleet(task, scenario_names, schemes, run_cfg,
                tuple(range(num_seeds)), task_data=td,
                params=task.init_params(seed), eval_fn=task.make_eval(td),
                placement=VmapPlacement(donate=donate))
    peak_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    print("RSS_PROBE " + json.dumps({"donate": donate,
                                     "peak_rss_mb": round(peak_mb, 1)}),
          flush=True)


def _run_rss_probe(task_name: str, scenario_names, num_rounds: int,
                   seed: int, num_seeds: int) -> dict:
    """Spawn one fresh process per donation mode (RSS high-water marks
    only mean something process-wide) and report the delta."""
    out = {}
    for mode in ("donate", "nodonate"):
        cmd = [sys.executable, "-m", "benchmarks.scenario_sweep",
               "--rss-probe", mode, "--task", task_name,
               "--rounds", str(num_rounds), "--seed", str(seed),
               "--grid-seeds", str(num_seeds),
               "--scenarios", ",".join(scenario_names)]
        # the probe is a host-RSS measurement, and the parent has touched
        # JAX: on an accelerator host it holds the chip the child would need
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              cwd=os.path.join(os.path.dirname(__file__),
                                               ".."),
                              env={**os.environ, "JAX_PLATFORMS": "cpu"})
        line = next((ln for ln in proc.stdout.splitlines()
                     if ln.startswith("RSS_PROBE ")), None)
        if proc.returncode != 0 or line is None:
            raise RuntimeError(f"rss probe ({mode}) failed:\n{proc.stderr}")
        out[mode] = json.loads(line[len("RSS_PROBE "):])["peak_rss_mb"]
    return {"donate_peak_rss_mb": out["donate"],
            "nodonate_peak_rss_mb": out["nodonate"],
            "delta_mb": round(out["nodonate"] - out["donate"], 1)}


def grid_sweep(scenario_names=scn.SWEEP_FAMILIES, schemes=SCHEMES,
               num_rounds: int = 40, eval_every: int = 20, seed: int = 0,
               num_seeds: int = 2, batch_size=None, placement=None,
               task="paper_mlp", log: bool = True, rss_probe: bool = True,
               write_bench: bool = True) -> dict:
    """Sequential-per-scenario fleets vs one compiled grid, measured.

    Runs the identical (scenario, scheme, seed) sweep both ways on the
    same task world, checks the C=1 grid slice is bitwise today's fleet,
    optionally probes carry-donation peak RSS in subprocesses, and
    merges a ``scenario_grid`` section into the task's
    engine_benchmark.json + the repo-root BENCH_engine.json."""
    import jax

    from repro import tasks as task_registry

    if isinstance(task, str):
        task = task_registry.get(task, expect_runtime="fleet")
    if batch_size is None:
        batch_size = int(task.defaults.get("batch_size", 0))
    td = task.build_data(seed)
    params0 = task.init_params(seed)
    evals = task.make_eval(td)
    run_cfg = task.run_config(eta=0.05, num_rounds=num_rounds,
                              eval_every=eval_every, seed=seed,
                              batch_size=batch_size)
    seeds = tuple(range(num_seeds))
    kw = dict(task_data=td, params=params0, eval_fn=evals,
              placement=placement)

    from repro.fl.driver import run_fleet_task

    per_scenario, seq_first = [], None
    for sc_name in scenario_names:
        sc, dep, pcs = _scenario_fleet_inputs(task, sc_name, schemes, seed)
        fading = scn.make_fading_process(dep, sc.dynamics)
        res = run_fleet_task(task, pcs, dep.gains, run_cfg,
                             etas=[run_cfg.eta] * len(pcs), fading=fading,
                             seeds=seeds, flat=True, **kw)
        seq_first = seq_first if seq_first is not None else res
        per_scenario.append({"scenario": sc_name, **_walls(res)})
        if log:
            print(f"sequential {sc_name}: {per_scenario[-1]['wall_s']}s "
                  f"(exec {per_scenario[-1]['exec_s']}s)", flush=True)

    gres = _grid_fleet(task, scenario_names, schemes, run_cfg, seeds, **kw)
    cells = len(scenario_names) * len(schemes) * num_seeds
    grid = {**_walls(gres)}
    if placement is not None and hasattr(placement, "_pad"):
        grid["padded_frac"] = round(placement._pad(cells)[1], 6)
    if log:
        print(f"grid [{len(scenario_names)}x{len(schemes)}x{num_seeds}]: "
              f"{grid['wall_s']}s (exec {grid['exec_s']}s)", flush=True)

    c1 = _grid_fleet(task, scenario_names[:1], schemes, run_cfg, seeds,
                     **kw)
    c1_bitwise = _results_bitwise(c1, seq_first)

    seq_total = round(sum(r["wall_s"] for r in per_scenario), 2)
    report = {
        "config": {"task": task.name, "scenarios": list(scenario_names),
                   "schemes": list(schemes), "num_seeds": num_seeds,
                   "num_rounds": num_rounds, "eval_every": eval_every,
                   "batch_size": batch_size, "seed": seed, "cells": cells,
                   "placement": (placement.describe(cells=cells)
                                 if placement is not None else "vmap"),
                   "device_count": jax.device_count(),
                   "backend": jax.default_backend()},
        "sequential": {"per_scenario": per_scenario, "total_s": seq_total},
        "grid": grid,
        "speedup": {
            "grid_vs_sequential": round(
                seq_total / max(grid["wall_s"], 1e-9), 2),
            "exec_grid_vs_sequential": round(
                sum(r["exec_s"] for r in per_scenario)
                / max(grid["exec_s"], 1e-9), 2)},
        "c1_slice_bitwise": c1_bitwise,
    }
    if rss_probe:
        report["carry_donation"] = _run_rss_probe(
            task.name, scenario_names, min(num_rounds, 10), seed,
            num_seeds)
    if log:
        print(f"sequential total {seq_total}s vs grid {grid['wall_s']}s "
              f"({report['speedup']['grid_vs_sequential']}x); "
              f"C=1 slice bitwise: {c1_bitwise}", flush=True)
    if not c1_bitwise:
        raise RuntimeError("C=1 grid slice is NOT bitwise the "
                           "per-scenario fleet — grid semantics broken")
    if write_bench:
        from benchmarks.fig2 import _merge_benchmark_json, \
            write_bench_summary
        _merge_benchmark_json(task, {"scenario_grid": report})
        write_bench_summary(task)
    return report


def _fmt(v):
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--all", action="store_true",
                    help="sweep every registered scenario")
    ap.add_argument("--train", action="store_true",
                    help="also run short FL training per (scenario, scheme)")
    ap.add_argument("--task", default="paper_mlp",
                    help="registered workload for --train "
                         "(paper_mlp | cifar_conv; DESIGN.md §Tasks)")
    ap.add_argument("--sharded", action="store_true",
                    help="shard each scenario's scheme grid over the "
                         "('data', 'model') debug mesh (needs >= 4 devices)")
    ap.add_argument("--grid", action="store_true",
                    help="with --train: benchmark sequential-per-scenario "
                         "fleets vs ONE compiled [C x K x S] grid and "
                         "record the scenario_grid BENCH section")
    ap.add_argument("--grid-seeds", type=int, default=2,
                    help="seed-axis width S of the --grid fleet")
    ap.add_argument("--scenarios", default=None,
                    help="comma-separated scenario names (overrides the "
                         "default sweep grid / --all)")
    ap.add_argument("--no-rss-probe", action="store_true",
                    help="skip the donate/no-donate peak-RSS subprocess "
                         "probe under --grid")
    ap.add_argument("--rss-probe", choices=("donate", "nodonate"),
                    default=None, help=argparse.SUPPRESS)  # probe child
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=None,
                    help="minibatch size for --train (0 = full batch; "
                         "default = the task's preferred size)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                    help="jax.distributed coordinator address; joins a "
                         "multi-process cluster and runs only this "
                         "process's slice of the scenario axis")
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--local-devices", type=int, default=None,
                    help="force N host-platform (CPU) devices per process "
                         "(multi-process CPU smoke)")
    args = ap.parse_args(argv)
    if args.sharded and not args.train:
        raise SystemExit("--sharded shards the training fleets; "
                         "pass --train with it")
    if args.grid and not args.train:
        raise SystemExit("--grid benchmarks the training fleets; "
                         "pass --train with it")

    names = scn.scenario_names() if args.all else scn.SWEEP_FAMILIES
    if args.scenarios:
        names = tuple(s.strip() for s in args.scenarios.split(","))

    if args.rss_probe:        # subprocess child of grid_sweep's RSS probe
        _rss_probe_child(args.task, names, SCHEMES, args.rounds, args.seed,
                         args.grid_seeds, donate=args.rss_probe == "donate")
        return
    compile_cache.enable()

    process_id = 0
    if args.coordinator:
        from repro import distributed as dist
        if args.num_processes is None or args.process_id is None:
            raise SystemExit("--coordinator needs --num-processes and "
                             "--process-id")
        nproc, ndev = dist.initialize_multiprocess(
            args.coordinator, args.num_processes, args.process_id,
            local_device_count=args.local_devices)
        process_id = args.process_id
        sl = dist.process_grid_slice(len(names))
        print(f"process {process_id}/{nproc} ({ndev} local devices): "
              f"scenarios {list(names[sl])}", flush=True)
        names = tuple(names[sl])

    rows = sweep(names, seed=args.seed)
    cols = ("scenario", "scheme", "bias", "variance", "objective",
            "p_spread", "mean_participation", "gain_spread_db")
    print(",".join(cols))
    for r in rows:
        print(",".join(_fmt(r[c]) for c in cols), flush=True)

    if args.train:
        placement = None
        if args.sharded:
            from benchmarks.fig2 import _sharded_placement
            placement = _sharded_placement()
        if args.grid:
            grid_sweep(names, num_rounds=min(args.rounds, 40),
                       seed=args.seed, num_seeds=args.grid_seeds,
                       batch_size=args.batch_size, placement=placement,
                       task=args.task, rss_probe=not args.no_rss_probe,
                       write_bench=process_id == 0)
        trows = train_sweep(names, num_rounds=args.rounds, seed=args.seed,
                            batch_size=args.batch_size,
                            placement=placement, task=args.task)
        print("scenario,scheme,final_acc,rounds")
        for r in trows:
            print(f"{r['scenario']},{r['scheme']},{r['final_acc']},"
                  f"{r['rounds']}", flush=True)
        rows = {"theory": rows, "train": trows}
    if process_id != 0:
        return           # multi-process: only process 0 owns the artifacts
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    with open(os.path.join(ARTIFACT_DIR,
                           f"sweep_seed{args.seed}.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
