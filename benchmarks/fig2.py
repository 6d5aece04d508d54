"""Fig.-2-style reproduction for any registered task: test accuracy (2a)
and global loss (2b) vs FL rounds for all seven schemes.

    PYTHONPATH=src python -m benchmarks.fig2 [--task paper_mlp|cifar_conv]
        [--bench] [--bench-placement] [--sharded] [--rounds N]
        [--checkpoint] [--resume]
        [--population P --cohort N [--cohort-rounds R] [--no-stream]]

The workload comes from the task registry (``repro.tasks``, DESIGN.md
§Tasks): ``paper_mlp`` (default) is the paper's §IV experiment and stays
bit-identical to the pre-task hand-wired path; ``cifar_conv`` is the
CIFAR-class Dirichlet-non-iid conv workload, writing its artifacts to
experiments/cifar/.  All seven schemes run as ONE compiled scan program
(``fl.driver.run_fleet_task``); ``--sharded`` shards the scheme grid over
the ("data", "model") debug mesh and ``--checkpoint`` / ``--resume`` turn
on chunk-boundary checkpointing with mid-grid resume.

``--population P`` switches the fleet to the streaming-cohort serving loop
(DESIGN.md §Population): each round runs on a ``--cohort``-sized draw from
a P-device parametric population (traffic-weighted Gumbel-top-k sampling),
redrawn every ``--cohort-rounds`` rounds, with the next cohort's draw /
gain materialization / SCA redesign double-buffered against the executing
chunk (``--no-stream`` serializes the same stages — identical numbers).

``--bench`` records the engine-vs-legacy wall-clock comparison into
<artifacts>/engine_benchmark.json.  ``--bench-placement`` (also implied by
``--bench``) adds the placement-vs-placement comparison — vmap vs sharded
at growing K*S — and refreshes the repo-root ``BENCH_engine.json`` summary
(headline walls + speedups, machine-readable across PRs; shape pinned by
``benchmarks/bench_schema.json`` via ``benchmarks.validate_bench``).
``--bench`` also runs :func:`population_benchmark` — sustained rounds/sec
of the 1M-population / 50-cohort streaming loop, stream vs serial — and
:func:`kernel_benchmark`, the fused-vs-unfused ``ota_round_step`` walls
per uplink dtype (f32/bf16/int8) with the f32 bitwise pin;
``--bench-kernel`` runs ONLY that section (seconds, not the multi-minute
legacy sweep).

Claims validated (paper §IV):
  * Ideal FedAvg best everywhere.
  * OPC (global CSI) fastest practical; the proposed SCA design (statistical
    CSI only) closely tracks it.
  * SCA beats Vanilla OTA-FL and LCPC.
  * BB-FL Alternative > BB-FL Interior (interior misses labels).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro import compile_cache, tasks
from repro.core import channel, power_control as pcm, scenarios as scn
from repro.core.theory import OTAParams
from repro.fl.driver import run_fleet_task
from repro.fl.server import run_fl_legacy
from repro.tasks.base import Task

SCHEMES = ["ideal", "opc", "sca", "lcpc", "vanilla", "bbfl_interior",
           "bbfl_alternative"]
# minibatch size of the engine's throughput mode (--bench; per-PR sweeps)
BENCH_BATCH = 128

ROOT = os.path.join(os.path.dirname(__file__), "..")
BENCH_SUMMARY = os.path.join(ROOT, "BENCH_engine.json")


def _task(task) -> Task:
    """Resolve a task name/instance and require the fleet runtime.  Raises
    KeyError/ValueError (catchable from library callers); main() translates
    to SystemExit for the CLI."""
    if isinstance(task, str):
        return tasks.get(task, expect_runtime="fleet")
    if task.runtime != "fleet":
        raise ValueError(f"task {task.name!r} is a {task.runtime!r}-runtime "
                         f"workload; this benchmark needs a fleet task")
    return task


def artifact_dir(task) -> str:
    task = _task(task)
    return os.path.join(ROOT, "experiments", task.artifact_tag or task.name)


def build_world(task="paper_mlp", seed: int = 0, num_devices=None):
    """Wireless deployment + OTA design constants + materialized task data.

    The deployment geometry is seeded independently of the data seed (the
    paper fixes one wireless world across data seeds), matching the
    committed pre-task fig2 world bit-for-bit on ``paper_mlp``.
    ``num_devices`` overrides the task's device count — population runs
    design their schemes for a cohort-sized world, not the shard count.
    """
    task = _task(task)
    wcfg = channel.WirelessConfig(
        num_devices=num_devices or task.num_devices, seed=0)
    dep = channel.deploy(wcfg)
    td = task.build_data(seed)
    prm = OTAParams(d=task.param_dim,
                    gmax=float(task.defaults.get("gmax", 10.0)),
                    es=wcfg.energy_per_sample, n0=wcfg.noise_psd,
                    gains=dep.gains, sigma_sq=np.zeros(wcfg.num_devices),
                    eta=0.05, lsmooth=1.0, kappa_sq=4.0)
    return dep, prm, td


def make_population(size: int, sampling: str = "traffic",
                    seed: int = 0) -> scn.Population:
    """Parametric serving population for --population runs: disk geometry
    with log-normal shadowing, i.i.d. Rayleigh fading (the engine's
    fading=None fast path) and heavy-tailed traffic-weighted cohort draws.
    Lazy — 1M devices cost nothing until a cohort materializes them."""
    spec = scn.PopulationSpec(size=size, shadowing=scn.ShadowingSpec(),
                              sampling=sampling, seed=seed)
    return scn.Population(spec=spec)


def make_schemes(task: Task, dep, prm, names=SCHEMES) -> list:
    """One PowerControl per scheme, each designed at the task's
    grid-searched step size (eta enters the (P1) objective)."""
    return [pcm.make_power_control(
        n, dep, prm.replace(eta=task.eta_for(n, float(prm.eta))))
        for n in names]


def _fleet_histories(res, wall_total: float):
    """FLResult (seed axis S=1) -> legacy-shaped {scheme: history list}."""
    histories = {}
    for i, name in enumerate(res.names):
        hist = []
        for t, ev in res.evals:
            hist.append({
                "acc": float(ev["acc"][i, 0]),
                "global_loss": float(ev["global_loss"][i, 0]),
                "round": t, "scheme": name,
                "active": float(res.traces["active_devices"][i, 0, t]),
                "wall": wall_total,
            })
        histories[name] = hist
    return histories


def run(num_rounds: int = 150, eval_every: int = 10, seed: int = 0,
        schemes=SCHEMES, log=False, engine: str = "fleet",
        batch_size=0, save: bool = True, placement=None,
        with_result: bool = False, task="paper_mlp",
        checkpoint_path=None, resume: bool = False,
        population: int = 0, cohort=None, cohort_rounds=None,
        stream: bool = True, max_chunks=None, telemetry=None):
    """Fig.-2-style histories for all schemes on the given task.

    engine="fleet": one compiled scan program for the whole scheme grid,
    through the task-first host driver (fl.driver.run_fleet_task);
    ``placement`` routes the grid onto hardware (None = single-device
    vmap, ShardedPlacement(mesh) to shard the scheme cells over a mesh),
    ``checkpoint_path``/``resume`` persist and fast-forward the fleet at
    chunk boundaries.
    engine="legacy": the pre-engine host loop, one scheme at a time (the
    wall-clock baseline; bit-reproduces the committed pre-engine curves
    on paper_mlp).
    batch_size=0 is full batch (the paper's §IV protocol — on paper_mlp
    the fleet matches the legacy loop to float rounding); None takes the
    task's preferred batch size; batch_size>0 switches to on-device
    minibatch sampling and the flattened Pallas aggregation.
    population>0 runs the fleet in streaming-cohort mode (``cohort``
    devices per round drawn from a ``make_population(population)`` world,
    schemes designed for the cohort-sized deployment; see module
    docstring); cohort defaults to the task's device count.
    with_result=True also returns the driver's FLResult (the honest
    wall_compile/wall_exec split for --bench).
    telemetry turns on the fleet telemetry subsystem (fleet engine only):
    True writes events.jsonl + bias--variance diagnostics into the task's
    artifact dir with the task's kappa^2 (render with
    ``python -m repro.telemetry.report <artifact_dir>``); a string or a
    ``repro.telemetry.Telemetry`` selects the run dir explicitly.
    """
    task = _task(task)
    if batch_size is None:
        batch_size = int(task.defaults.get("batch_size", 0))
    pop_kw = {}
    if population:
        if engine != "fleet":
            raise ValueError("population mode needs the fleet engine")
        cohort = int(cohort or task.num_devices)
        pop_kw = dict(population=make_population(int(population)),
                      cohort_size=cohort, cohort_rounds=cohort_rounds,
                      stream=stream)
    dep, prm, td = build_world(task, seed, num_devices=cohort)
    params0 = task.init_params(seed)
    evals = task.make_eval(td)

    telemetry = telemetry or None
    if telemetry is not None and engine != "fleet":
        raise ValueError("telemetry needs the fleet engine")
    if telemetry is True:
        from repro.telemetry import Telemetry
        telemetry = Telemetry(run_dir=artifact_dir(task),
                              kappa_sq=float(prm.kappa_sq))

    res = None
    if engine == "fleet":
        run_cfg = task.run_config(num_rounds=num_rounds,
                                  eval_every=eval_every, seed=seed,
                                  batch_size=batch_size)
        pcs = make_schemes(task, dep, prm, schemes)
        res = run_fleet_task(task, pcs, dep.gains, run_cfg, task_data=td,
                             params=params0, eval_fn=evals,
                             flat=batch_size > 0, log=log,
                             placement=placement,
                             checkpoint_path=checkpoint_path, resume=resume,
                             max_chunks=max_chunks, telemetry=telemetry,
                             **pop_kw)
        histories = _fleet_histories(res, res.wall)
    elif engine == "legacy":
        histories = {}
        ev_jit = jax.jit(evals)
        for name in schemes:
            eta = task.eta_for(name, 0.05)
            pc = pcm.make_power_control(name, dep, prm.replace(eta=eta))
            run_cfg = task.run_config(eta=eta, num_rounds=num_rounds,
                                      eval_every=eval_every, seed=seed,
                                      batch_size=batch_size)
            t0 = time.time()
            _, hist = run_fl_legacy(task.loss_fn, params0, pc, dep.gains,
                                    td.train, run_cfg, ev_jit, log=log)
            histories[name] = hist
            if log:
                print(f"  {name}: {time.time() - t0:.1f}s")
    else:
        raise ValueError(f"unknown engine {engine!r}")

    if save:
        out = artifact_dir(task)
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, f"histories_seed{seed}.json"),
                  "w") as f:
            json.dump(histories, f, indent=1)
    if with_result:
        return histories, res
    return histories


def rounds_to_accuracy(hist, target: float):
    for h in hist:
        if h["acc"] >= target:
            return h["round"]
    return None


def summarize(histories) -> list:
    rows = []
    for name, hist in histories.items():
        final = hist[-1]
        rows.append({
            "scheme": name,
            "final_acc": round(final["acc"], 4),
            "final_loss": round(final["global_loss"], 4),
            "rounds_to_80": rounds_to_accuracy(hist, 0.80),
            "csi": ("global" if name in ("opc", "vanilla", "bbfl_interior",
                                         "bbfl_alternative")
                    else ("none" if name == "ideal" else "statistical")),
        })
    return rows


def _history_deltas(a: dict, b: dict) -> dict:
    """Max |delta| between two scheme->history maps at each eval metric."""
    out = {}
    for metric in ("acc", "global_loss"):
        out[metric] = max(
            abs(ra[metric] - rb[metric])
            for name in a for ra, rb in zip(a[name], b[name]))
    return out


def benchmark(num_rounds: int = 150, eval_every: int = 15, seed: int = 0,
              batch_size: int = BENCH_BATCH, task="paper_mlp",
              log: bool = True) -> dict:
    """Engine-vs-legacy wall clock for the full scheme grid; writes
    <artifacts>/engine_benchmark.json.

    Three runs of the 7-scheme x num_rounds grid:
      legacy          pre-engine host loop, full batch (the old fig2 path)
      fleet_fullbatch one scan program, full batch — same arithmetic and
                      streams as legacy, history deltas recorded
      fleet_minibatch one scan program, on-device batch_size sampling +
                      Pallas flattened aggregation — the per-PR sweep mode

    All three top-line walls are measured with the SAME outer clock around
    the whole run() call (world build, data generation, eval jit included)
    so the speedup ratios compare like with like; the fleet rows
    additionally carry FLResult's compile/exec split of the engine portion
    — what amortizes over longer sweeps — while the legacy loop compiles
    per round and has no meaningful split.
    """
    task = _task(task)
    cfg = dict(num_rounds=num_rounds, eval_every=eval_every, seed=seed,
               save=False, task=task)
    t0 = time.time()
    legacy = run(engine="legacy", **cfg)
    wall_legacy = time.time() - t0
    if log:
        print(f"legacy loop (full batch): {wall_legacy:.1f}s")

    t0 = time.time()
    fleet_full, res_full = run(engine="fleet", with_result=True, **cfg)
    wall_full = time.time() - t0
    if log:
        print(f"scan fleet (full batch):  {wall_full:.1f}s "
              f"(compile {res_full.wall_compile:.1f}s"
              f" + exec {res_full.wall_exec:.1f}s)")

    t0 = time.time()
    fleet_mb, res_mb = run(engine="fleet", batch_size=batch_size,
                           with_result=True, **cfg)
    wall_mb = time.time() - t0
    if log:
        print(f"scan fleet (minibatch {batch_size}): {wall_mb:.1f}s "
              f"(compile {res_mb.wall_compile:.1f}s"
              f" + exec {res_mb.wall_exec:.1f}s)")

    deltas = _history_deltas(legacy, fleet_full)
    report = {
        "grid": {"task": task.name, "schemes": SCHEMES,
                 "num_rounds": num_rounds,
                 "eval_every": eval_every, "seed": seed,
                 "bench_batch_size": batch_size,
                 "device": jax.devices()[0].device_kind,
                 "backend": jax.default_backend()},
        "wall_s": {"legacy_loop_fullbatch": round(wall_legacy, 2),
                   "fleet_fullbatch": round(wall_full, 2),
                   "fleet_fullbatch_compile": round(res_full.wall_compile, 2),
                   "fleet_fullbatch_exec": round(res_full.wall_exec, 2),
                   "fleet_minibatch": round(wall_mb, 2),
                   "fleet_minibatch_compile": round(res_mb.wall_compile, 2),
                   "fleet_minibatch_exec": round(res_mb.wall_exec, 2)},
        "speedup": {
            # headline: the engine's sweep mode vs the pre-engine fig2 path
            "engine_vs_legacy": round(wall_legacy / wall_mb, 2),
            "fullbatch_engine_vs_legacy": round(wall_legacy / wall_full, 2),
            # compile excluded: what a longer sweep actually amortizes to
            "engine_exec_vs_legacy": round(
                wall_legacy / max(res_mb.wall_exec, 1e-9), 2),
        },
        "equivalence": {
            "note": "fleet_fullbatch vs legacy at identical seeds/streams",
            "max_abs_delta": {k: float(v) for k, v in deltas.items()},
        },
        "final_acc": {
            "legacy": {n: legacy[n][-1]["acc"] for n in legacy},
            "fleet_fullbatch": {n: fleet_full[n][-1]["acc"]
                                for n in fleet_full},
            "fleet_minibatch": {n: fleet_mb[n][-1]["acc"] for n in fleet_mb},
        },
    }
    _merge_benchmark_json(task, report)
    if log:
        print(json.dumps(report["speedup"], indent=1))
    return report


# ---------------------------------------------------------------------------
# Placement-vs-placement wall comparison (ROADMAP: vmap vs sharded at
# growing K*S) + the repo-root BENCH_engine.json summary.
# ---------------------------------------------------------------------------

def _wall_split(res) -> dict:
    return {"wall": round(res.wall, 2),
            "compile": round(res.wall_compile, 2),
            "exec": round(res.wall_exec, 2)}


def placement_benchmark(task="paper_mlp", num_rounds: int = 30,
                        eval_every: int = 15, seed: int = 0,
                        batch_size: int = BENCH_BATCH,
                        seeds_grid=(1, 2, 4), log: bool = True) -> dict:
    """vmap-vs-sharded wall clocks for the 7-scheme grid at growing K*S.

    Each grid point runs the same minibatch+flat fleet once per placement
    (sharded only when >= 4 devices are visible — on CPU force them with
    XLA_FLAGS=--xla_force_host_platform_device_count=8); walls come from
    FLResult's compile/exec split, and the exec-only speedup is the
    number that scales with sweep length.
    """
    task = _task(task)
    dep, prm, td = build_world(task, seed)
    params0 = task.init_params(seed)
    evals = task.make_eval(td)
    pcs = make_schemes(task, dep, prm)
    sharded = None
    if jax.device_count() >= 4:
        sharded = _sharded_placement()

    rows = []
    for s in seeds_grid:
        run_cfg = task.run_config(num_rounds=num_rounds,
                                  eval_every=eval_every, seed=seed,
                                  batch_size=batch_size)
        kw = dict(task_data=td, params=params0, eval_fn=evals,
                  seeds=tuple(range(s)), flat=True)
        res_v = run_fleet_task(task, pcs, dep.gains, run_cfg, **kw)
        row = {"k": len(SCHEMES), "s": s, "cells": len(SCHEMES) * s,
               "vmap": _wall_split(res_v)}
        if sharded is not None:
            res_s = run_fleet_task(task, pcs, dep.gains, run_cfg, **kw,
                                   placement=sharded)
            row["sharded"] = _wall_split(res_s)
            row["sharded_devices"] = sharded.num_devices
            row["exec_speedup_sharded_vs_vmap"] = round(
                res_v.wall_exec / max(res_s.wall_exec, 1e-9), 2)
        else:
            row["sharded"] = "skipped (needs >= 4 devices; set XLA_FLAGS="
            row["sharded"] += "--xla_force_host_platform_device_count=8)"
        if log:
            print(f"cells={row['cells']}: vmap exec "
                  f"{row['vmap']['exec']}s"
                  + (f", sharded exec {row['sharded']['exec']}s "
                     f"({row['exec_speedup_sharded_vs_vmap']}x)"
                     if sharded is not None else " (sharded skipped)"))
        rows.append(row)

    placement = {
        "config": {"task": task.name, "num_rounds": num_rounds,
                   "eval_every": eval_every, "seed": seed,
                   "batch_size": batch_size,
                   "device_count": jax.device_count(),
                   "backend": jax.default_backend()},
        "rows": rows,
    }
    _merge_benchmark_json(task, {"placement": placement})
    write_bench_summary(task)
    return placement


def population_benchmark(task="paper_mlp", size: int = 1_000_000,
                         cohort: int = 50, num_rounds: int = 48,
                         eval_every: int = 16, cohort_rounds: int = 1,
                         seed: int = 0, batch_size: int = BENCH_BATCH,
                         log: bool = True) -> dict:
    """Streaming-cohort serving throughput (DESIGN.md §Population).

    One ``adaptive_sca`` scheme over a ``size``-device traffic-weighted
    population at ``cohort`` devices/round, redrawn + SCA-redesigned on the
    incoming cohort's statistical CSI every ``cohort_rounds`` rounds (the
    default redraws EVERY round — the hardest streaming cadence).  The
    same fleet runs twice — stream=True (staging double-buffered against
    the executing chunk) and stream=False (identical stages, serialized) —
    so the exec-wall gap IS the hidden staging + redesign latency; results
    are checked bitwise-equal across the two modes.  Run with at least two
    visible devices (CI forces host devices via XLA_FLAGS) so the driver's
    staging lane keeps the redesign solve off the chunk's device — on one
    device the solve queues behind the chunk and overlap cannot win.
    Also re-verifies the full-participation contract: a cohort ==
    population run over the task's own deployment is bitwise the
    pre-population engine path.

    Records sustained rounds/sec (stream mode, compile excluded) into
    <artifacts>/engine_benchmark.json under "population" and refreshes
    BENCH_engine.json.
    """
    task = _task(task)
    pop = make_population(size)
    dep, prm, td = build_world(task, seed, num_devices=cohort)
    params0 = task.init_params(seed)
    evals = task.make_eval(td)
    pcs = make_schemes(task, dep, prm, ["adaptive_sca"])
    run_cfg = task.run_config(num_rounds=num_rounds, eval_every=eval_every,
                              seed=seed, batch_size=batch_size)
    kw = dict(task_data=td, params=params0, eval_fn=evals,
              flat=batch_size > 0, population=pop, cohort_size=cohort,
              cohort_rounds=cohort_rounds)
    res_st = run_fleet_task(task, pcs, dep.gains, run_cfg, **kw, stream=True)
    res_se = run_fleet_task(task, pcs, dep.gains, run_cfg, **kw,
                            stream=False)
    stream_eq = all(
        bool(np.array_equal(np.asarray(a), np.asarray(b)))
        for a, b in zip(jax.tree.leaves(res_st.params),
                        jax.tree.leaves(res_se.params)))
    if log:
        print(f"population {size} / cohort {cohort}: "
              f"stream exec {res_st.wall_exec:.1f}s "
              f"(staged {res_st.wall_stage:.1f}s overlapped), "
              f"serial exec {res_se.wall_exec:.1f}s")

    # full-participation identity: deployment-as-population, cohort == N
    dep0, prm0, _ = build_world(task, seed)
    pcs0 = make_schemes(task, dep0, prm0, ["sca"])
    run0 = task.run_config(num_rounds=6, eval_every=3, seed=seed,
                           batch_size=batch_size)
    kw0 = dict(task_data=td, params=params0, eval_fn=evals,
               flat=batch_size > 0)
    ref = run_fleet_task(task, pcs0, dep0.gains, run0, **kw0)
    full = run_fleet_task(task, pcs0, dep0.gains, run0, **kw0,
                          population=scn.Population.from_deployment(dep0),
                          cohort_size=task.num_devices, stream=False)
    full_bitwise = all(
        bool(np.array_equal(np.asarray(a), np.asarray(b)))
        for a, b in zip(jax.tree.leaves(ref.params),
                        jax.tree.leaves(full.params))) \
        and all(np.array_equal(ref.traces[k], full.traces[k])
                for k in ref.traces)

    report = {
        "config": {"task": task.name, "population": size, "cohort": cohort,
                   "num_rounds": num_rounds, "eval_every": eval_every,
                   "cohort_rounds": cohort_rounds, "seed": seed,
                   "batch_size": batch_size, "scheme": "adaptive_sca",
                   "sampling": "traffic", "backend": jax.default_backend()},
        "wall_s": {"stream_exec": round(res_st.wall_exec, 2),
                   "serial_exec": round(res_se.wall_exec, 2),
                   "stream_stage": round(res_st.wall_stage, 2),
                   "serial_stage": round(res_se.wall_stage, 2),
                   "stream_compile": round(res_st.wall_compile, 2)},
        # per-chunk staging walls (FLResult.stage_walls): where inside the
        # run the staging lane spent its time, stream vs serialized — the
        # chunk-resolved half of the wall_s aggregates above
        "stage_chunks_s": {
            "stream": [round(w, 4) for w in res_st.stage_walls],
            "serial": [round(w, 4) for w in res_se.stage_walls]},
        "rounds_per_sec": round(num_rounds / max(res_st.wall_exec, 1e-9), 3),
        "overlap_saving_s": round(res_se.wall_exec - res_st.wall_exec, 2),
        "stream_bitwise": bool(stream_eq),
        "full_cohort_bitwise": bool(full_bitwise),
    }
    _merge_benchmark_json(task, {"population": report})
    write_bench_summary(task)
    if log:
        print(json.dumps({k: report[k] for k in
                          ("rounds_per_sec", "overlap_saving_s",
                           "stream_bitwise", "full_cohort_bitwise")},
                         indent=1))
    return report


def kernel_benchmark(task="paper_mlp", num_rounds: int = 12,
                     eval_every: int = 6, seed: int = 0,
                     batch_size: int = BENCH_BATCH,
                     log: bool = True) -> dict:
    """Fused-vs-unfused round-step walls per uplink dtype (DESIGN.md
    §Kernels) — the measured side of the ``ota_round_step`` fusion.

    Two layers, both recorded under "round_step" in the task's
    engine_benchmark.json and surfaced into BENCH_engine.json:

    kernel  micro walls of the round tail alone at the paper's model
            scale (``kernel_bench.round_step_rows``): one fused launch vs
            the historical aggregate/ghat/step chain, plus uplink bytes
            per wire dtype — what the fusion and a low-precision uplink
            each save.
    fleet   the same comparison end-to-end through ``run_fleet_task`` on
            the 7-scheme grid: exec walls with ``fuse_round`` on/off at
            each ``uplink_dtype``, with the two trajectories checked
            bitwise-equal (f32's check is the acceptance pin — fusion
            must not move a single bit of the committed numbers).

    Also runs the interpret-mode Pallas-vs-oracle equivalence gate so the
    committed JSON records kernel agreement, not just jnp-path walls.
    """
    from benchmarks import kernel_bench

    task = _task(task)
    if log:
        print("round-step micro walls (paper scale, per uplink dtype):")
    micro = kernel_bench.round_step_rows()
    if log:
        for r in micro:
            print(f"  {r['uplink_dtype']}: fused {r['fused_us']}us vs "
                  f"unfused {r['unfused_us']}us ({r['speedup']}x), "
                  f"uplink {r['uplink_mb']}MB")
    interp_err = kernel_bench.round_step_equivalence()

    dep, prm, td = build_world(task, seed)
    params0 = task.init_params(seed)
    evals = task.make_eval(td)
    pcs = make_schemes(task, dep, prm)
    run_cfg = task.run_config(num_rounds=num_rounds, eval_every=eval_every,
                              seed=seed, batch_size=batch_size)
    kw = dict(task_data=td, params=params0, eval_fn=evals, seeds=(0,),
              flat=True)
    fleet = {}
    for ud in kernel_bench.UPLINKS:
        res_f = run_fleet_task(task, pcs, dep.gains, run_cfg, **kw,
                               uplink_dtype=ud, fuse_round=True)
        res_u = run_fleet_task(task, pcs, dep.gains, run_cfg, **kw,
                               uplink_dtype=ud, fuse_round=False)
        bitwise = all(
            bool(np.array_equal(np.asarray(a), np.asarray(b)))
            for a, b in zip(jax.tree.leaves(res_f.params),
                            jax.tree.leaves(res_u.params))) \
            and all(np.array_equal(res_f.traces[k], res_u.traces[k])
                    for k in res_f.traces)
        fleet[ud] = {"fused_exec_s": round(res_f.wall_exec, 2),
                     "unfused_exec_s": round(res_u.wall_exec, 2),
                     "bitwise_fused_vs_unfused": bool(bitwise)}
        if log:
            print(f"fleet grid ({ud}): fused exec "
                  f"{fleet[ud]['fused_exec_s']}s vs unfused "
                  f"{fleet[ud]['unfused_exec_s']}s, bitwise={bitwise}")

    report = {
        "config": {"task": task.name, "schemes": SCHEMES,
                   "num_rounds": num_rounds, "eval_every": eval_every,
                   "seed": seed, "batch_size": batch_size,
                   "backend": jax.default_backend()},
        "kernel": micro,
        "interpret_max_err": interp_err,
        "fleet": fleet,
        "f32_bitwise": fleet["f32"]["bitwise_fused_vs_unfused"],
    }
    _merge_benchmark_json(task, {"round_step": report})
    write_bench_summary(task)
    return report


def _benchmark_json_path(task) -> str:
    return os.path.join(artifact_dir(task), "engine_benchmark.json")


def _merge_benchmark_json(task, update: dict) -> dict:
    """Merge ``update`` into the task's engine_benchmark.json (so a
    placement-only rerun never clobbers the committed legacy-vs-engine
    walls, and vice versa)."""
    path = _benchmark_json_path(task)
    report = {}
    if os.path.exists(path):
        with open(path) as f:
            report = json.load(f)
    report.update(update)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
    return report


def write_bench_summary(task="paper_mlp") -> dict:
    """Repo-root BENCH_engine.json: the machine-readable perf trajectory.

    Condenses the task's engine_benchmark.json to headline walls and
    speedups (engine-vs-legacy, sharded-vs-vmap per K*S point) so a later
    PR — or a reviewer — can diff throughput without parsing the full
    benchmark artifact.
    """
    task = _task(task)
    path = _benchmark_json_path(task)
    report = {}
    if os.path.exists(path):
        with open(path) as f:
            report = json.load(f)
    summary = {"source": os.path.relpath(path, ROOT), "task": task.name}
    if "grid" in report:
        summary["grid"] = {k: report["grid"][k]
                           for k in ("num_rounds", "eval_every",
                                     "bench_batch_size", "backend", "device")
                           if k in report["grid"]}
    if "wall_s" in report:
        summary["wall_s"] = report["wall_s"]
    if "speedup" in report:
        summary["speedup"] = report["speedup"]
    if "placement" in report:
        pl = report["placement"]
        summary["placement"] = {
            "config": pl["config"],
            "rows": [{"cells": r["cells"],
                      "vmap_exec_s": r["vmap"]["exec"],
                      **({"sharded_exec_s": r["sharded"]["exec"],
                          "exec_speedup":
                              r["exec_speedup_sharded_vs_vmap"]}
                         if isinstance(r.get("sharded"), dict) else
                         {"sharded": "skipped"})}
                     for r in pl["rows"]],
        }
    if "population" in report:
        summary["population"] = report["population"]
    if "scenario_grid" in report:
        summary["scenario_grid"] = report["scenario_grid"]
    if "round_step" in report:
        summary["round_step"] = report["round_step"]
    with open(BENCH_SUMMARY, "w") as f:
        json.dump(summary, f, indent=1)
    from benchmarks.validate_bench import validate
    errors = validate(BENCH_SUMMARY)
    if errors:
        raise ValueError(f"BENCH_engine.json violates "
                         f"benchmarks/bench_schema.json: {errors}")
    return summary


def _sharded_placement():
    """Debug-mesh placement for --sharded (forced-8-CPU-device CI path or
    any real multi-device host)."""
    from repro.fl.placement import ShardedPlacement
    from repro.launch.mesh import make_debug_mesh

    if jax.device_count() < 4:
        raise SystemExit(
            "--sharded needs >= 4 devices; on CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    return ShardedPlacement(make_debug_mesh(2, 2))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--task", default="paper_mlp",
        help="registered fleet workload "
             f"({'|'.join(tasks.names(runtime='fleet'))})")
    ap.add_argument("--bench", action="store_true",
                    help="engine-vs-legacy wall-clock benchmark + JSON "
                         "(also runs the placement comparison)")
    ap.add_argument("--bench-placement", action="store_true",
                    help="vmap-vs-sharded wall comparison at growing K*S; "
                         "refreshes repo-root BENCH_engine.json")
    ap.add_argument("--bench-kernel", action="store_true",
                    help="fused-vs-unfused round-step walls per uplink "
                         "dtype only (skips the multi-minute legacy "
                         "sweep); refreshes BENCH_engine.json")
    ap.add_argument("--legacy", action="store_true",
                    help="run the pre-engine host loop instead of the fleet")
    ap.add_argument("--sharded", action="store_true",
                    help="shard the scheme grid over the ('data', 'model') "
                         "debug mesh (DESIGN.md §Placement)")
    ap.add_argument("--checkpoint", action="store_true",
                    help="persist the fleet at chunk boundaries under the "
                         "task's artifact dir")
    ap.add_argument("--resume", action="store_true",
                    help="fast-forward from the task's checkpoint if present"
                         " (implies --checkpoint)")
    ap.add_argument("--max-chunks", type=int, default=None,
                    help="stop after N compiled chunks (with --checkpoint: "
                         "a clean mid-run kill the next --resume completes)")
    ap.add_argument("--population", type=int, default=0,
                    help="streaming-cohort mode: population size (devices); "
                         "0 = full participation (DESIGN.md §Population)")
    ap.add_argument("--cohort", type=int, default=None,
                    help="active devices per round under --population "
                         "(default: the task's device count)")
    ap.add_argument("--cohort-rounds", type=int, default=None,
                    help="redraw the cohort every R rounds (default: once "
                         "per chunk, i.e. the eval cadence)")
    ap.add_argument("--telemetry", action="store_true",
                    help="write events.jsonl + bias-variance diagnostics "
                         "into the task's artifact dir; render with "
                         "python -m repro.telemetry.report <dir>")
    ap.add_argument("--no-stream", action="store_true",
                    help="serialize cohort staging instead of double-"
                         "buffering it against the executing chunk "
                         "(identical numbers, different walls)")
    ap.add_argument("--rounds", type=int, default=150)
    ap.add_argument("--every", type=int, default=None,
                    help="eval cadence (default: 10, or 15 under --bench)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch-size", type=int, default=None,
                    help="0 = full batch (paper); default = the task's "
                         f"preferred size; under --bench, the minibatch "
                         f"mode size (default {BENCH_BATCH})")
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                    help="join a jax.distributed cluster before any "
                         "backend touch (multi-process bring-up, "
                         "DESIGN.md §Grid)")
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--local-devices", type=int, default=None,
                    help="force N host-platform (CPU) devices per "
                         "process (multi-process CPU smoke)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.coordinator:
        if args.num_processes is None or args.process_id is None:
            raise SystemExit("--coordinator needs --num-processes and "
                             "--process-id")
        from repro.distributed import initialize_multiprocess
        nproc, ndev = initialize_multiprocess(
            args.coordinator, args.num_processes, args.process_id,
            local_device_count=args.local_devices)
        print(f"process {args.process_id}/{nproc}: {ndev} local devices "
              f"({jax.device_count()} global)", flush=True)
    try:
        task = _task(args.task)
    except (KeyError, ValueError) as e:
        raise SystemExit(str(e))
    if args.sharded and (args.legacy or args.bench or args.bench_kernel):
        raise SystemExit("--sharded applies to the fleet engine only; "
                         "drop --legacy/--bench/--bench-kernel")
    if (args.checkpoint or args.resume) \
            and (args.legacy or args.bench or args.bench_placement
                 or args.bench_kernel):
        raise SystemExit("--checkpoint/--resume apply to the fleet engine "
                         "only; drop --legacy/--bench/--bench-placement/"
                         "--bench-kernel")
    if args.population and (args.legacy or args.sharded):
        raise SystemExit("--population applies to the vmap fleet engine; "
                         "drop --legacy/--sharded")
    if args.telemetry and (args.legacy or args.bench or args.bench_placement
                           or args.bench_kernel):
        raise SystemExit("--telemetry applies to the fleet engine only; "
                         "drop --legacy/--bench/--bench-placement/"
                         "--bench-kernel")
    if args.bench_kernel and not args.bench:
        kernel_benchmark(task=task, num_rounds=min(args.rounds, 12),
                         eval_every=args.every or 6, seed=args.seed,
                         batch_size=args.batch_size or BENCH_BATCH)
        return
    if args.bench:
        benchmark(num_rounds=args.rounds, eval_every=args.every or 15,
                  seed=args.seed, task=task,
                  batch_size=args.batch_size or BENCH_BATCH)
        placement_benchmark(task=task, num_rounds=min(args.rounds, 30),
                            eval_every=args.every or 15, seed=args.seed,
                            batch_size=args.batch_size or BENCH_BATCH)
        population_benchmark(task=task,
                             size=args.population or 1_000_000,
                             cohort=args.cohort or 50, seed=args.seed,
                             batch_size=args.batch_size or BENCH_BATCH)
        kernel_benchmark(task=task, num_rounds=12,
                         eval_every=args.every or 6, seed=args.seed,
                         batch_size=args.batch_size or BENCH_BATCH)
        return
    if args.bench_placement:
        placement_benchmark(task=task, num_rounds=min(args.rounds, 30),
                            eval_every=args.every or 15, seed=args.seed,
                            batch_size=args.batch_size or BENCH_BATCH)
        return
    ckpt_path = None
    if args.checkpoint or args.resume:
        ckpt_path = os.path.join(artifact_dir(task),
                                 f"fleet_seed{args.seed}")
    hist = run(num_rounds=args.rounds, eval_every=args.every or 10,
               seed=args.seed, task=task,
               engine="legacy" if args.legacy else "fleet",
               batch_size=args.batch_size, log=True,
               placement=_sharded_placement() if args.sharded else None,
               checkpoint_path=ckpt_path, resume=args.resume,
               population=args.population, cohort=args.cohort,
               cohort_rounds=args.cohort_rounds,
               stream=not args.no_stream, max_chunks=args.max_chunks,
               telemetry=args.telemetry)
    for row in summarize(hist):
        print(row)


if __name__ == "__main__":
    main()
