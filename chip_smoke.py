#!/usr/bin/env python3
"""Chip smoke test: drive the fleet's main path once on a TPU and check it.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the sharded scenario grid, 4 chips

One chip: the paper's 7-scheme ``paper_mlp`` fleet (N=10 devices,
d=814,090) through ``benchmarks.fig2.run``, full batch (checked against
the same fleet on the host's CPU backend) and with a 128-sample minibatch
(the flat path, where the fused ``ota_round_step`` Pallas kernel runs the
round tail, checked against the tree-map path); the kernel against its jnp
oracle per uplink dtype; the README's population stream (1M devices,
50-device cohorts, so the kernel runs at N=50); and the batched SCA solve
against the scipy oracle, on the chip and on the host.  ``--four-chips``
runs only the [4 scenario x 3 scheme x 2 seed] grid of
``benchmarks.scenario_sweep`` sharded over a 2x2 mesh and compares it with
the same grid vmapped on one chip.

Everything runs in this one process: a TPU chip belongs to one process at a
time.  The script refuses to run anywhere but a TPU (exit code 1, no result
line).  Per phase it prints compile and execute seconds and the device's
peak bytes in use; those lines are smoke output, not benchmark metrics.
A watchdog ends the process (exit code 1, every thread's stack on stderr)
when the run passes its deadline (``DEADLINE_S``).  The last line of
stdout is a JSON object with ``ok`` and the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

D_PAPER = 814_090          # paper_mlp's parameter count
ROUNDS, EVAL_EVERY = 4, 2
GRID_ROUNDS = 2            # the four-chip grid at full matmul precision
MINIBATCH = 128
# flat (fused-kernel) vs tree-map oracle after ROUNDS minibatch rounds:
# max |param difference| relative to the largest |param|, both in f32
FLAT_VS_TREE_RTOL = 1e-4
# chip vs host-CPU full-batch histories: the chip's default f32 matmul
# precision moves the loss by ~1e-5 relative, and flips a few of the 1,000
# test predictions of a barely trained model
HISTORY_LOSS_RTOL = 1e-3
HISTORY_ACC_ATOL = 0.02
# fused kernel vs the jnp oracle on O(1) operands, per uplink dtype
KERNEL_ATOL = 1e-4
# sharded vs vmapped grid: different compiled programs of the same cells,
# both at full f32 matmul precision (at the chip's default precision a
# 1-ulp f32 difference flips the bf16 rounding of a matmul input: 7e-4 after
# 4 rounds on a v5e)
GRID_RTOL = 1e-4
# watchdog deadlines: the one-chip run, compilation included, ends inside
# 1200 s; the four-chip grid takes ~140 s on v5e chips, and a stall there
# costs four chips' time
DEADLINE_S = {"one_chip": 1100, "four_chips": 360}
# what marks a Pallas TPU kernel in compiled text
KERNEL_MARK = "tpu_custom_call"


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _report(phase: str, **fields) -> None:
    fields["peak_bytes_in_use"] = _peak_bytes()
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[smoke output, not a metric] {phase}: {body}", flush=True)


def _finite_histories(hist, schemes) -> None:
    import numpy as np

    assert set(hist) == set(schemes), sorted(hist)
    for name, rows in hist.items():
        vals = [r[m] for r in rows for m in ("acc", "global_loss")]
        assert rows and np.all(np.isfinite(vals)), (name, vals)


def _max_rel_diff(a_tree, b_tree) -> float:
    import jax
    import numpy as np

    worst = 0.0
    for a, b in zip(jax.tree.leaves(a_tree), jax.tree.leaves(b_tree)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.all(np.isfinite(a)) and np.all(np.isfinite(b))
        worst = max(worst, float(np.max(np.abs(a - b))
                                 / max(float(np.max(np.abs(b))), 1e-30)))
    return worst


def _fleet(task, batch_size: int, **kw):
    from benchmarks import fig2

    t0 = time.time()
    hist, res = fig2.run(num_rounds=ROUNDS, eval_every=EVAL_EVERY,
                         task=task, batch_size=batch_size, save=False,
                         with_result=True, **kw)
    return hist, res, time.time() - t0


def _history_gaps(hist, ref) -> tuple:
    """(max relative global-loss gap, max accuracy gap) over every scheme
    and eval round of two fig2 histories."""
    loss_gap = acc_gap = 0.0
    for name, rows in hist.items():
        for r, q in zip(rows, ref[name], strict=True):
            loss_gap = max(loss_gap, abs(r["global_loss"] - q["global_loss"])
                           / abs(q["global_loss"]))
            acc_gap = max(acc_gap, abs(r["acc"] - q["acc"]))
    return loss_gap, acc_gap


def phase_fullbatch(task) -> None:
    import jax

    from benchmarks import fig2

    hist, res, call_s = _fleet(task, 0)
    _finite_histories(hist, fig2.SCHEMES)
    _report("fleet_fullbatch", schemes=len(res.names), rounds=ROUNDS,
            compile_s=res.wall_compile, execute_s=res.wall_exec,
            call_s=call_s, final_acc={n: h[-1]["acc"]
                                      for n, h in hist.items()},
            final_loss={n: h[-1]["global_loss"] for n, h in hist.items()})

    # the same fleet on the host's CPU backend, in this process
    with jax.default_device(jax.devices("cpu")[0]):
        ref, _, ref_s = _fleet(task, 0)
    loss_gap, acc_gap = _history_gaps(hist, ref)
    print(f"chip vs host-CPU fleet histories: max rel loss gap "
          f"{loss_gap:.3e} (tolerance {HISTORY_LOSS_RTOL:.0e}), max acc gap "
          f"{acc_gap:.3f} (tolerance {HISTORY_ACC_ATOL})", flush=True)
    assert loss_gap <= HISTORY_LOSS_RTOL and acc_gap <= HISTORY_ACC_ATOL, \
        (hist, ref)
    _report("fleet_fullbatch_cpu_reference", call_s=ref_s)


def _recording_placement():
    """A ``VmapPlacement`` whose chunks run through their own ahead-of-time
    compiled program, and keep its text: what the fleet ran, on the fleet's
    operands."""
    import jax
    import jax.numpy as jnp

    from repro.fl.placement import VmapPlacement

    @dataclasses.dataclass(frozen=True)
    class Recording(VmapPlacement):
        texts: list = dataclasses.field(default_factory=list, compare=False)

        def build_chunk(self, round_body, adaptive, cohort=False,
                        scenario=False):
            jitted = super().build_chunk(round_body, adaptive, cohort,
                                         scenario)
            programs = {}

            def chunk(*args, length):
                leaves, tree = jax.tree.flatten(args)
                key = (length, tree, tuple((jnp.shape(a), jnp.result_type(a))
                                           for a in leaves))
                if key not in programs:
                    programs[key] = jitted.lower(*args,
                                                 length=length).compile()
                    self.texts.append(programs[key].as_text())
                return programs[key](*args)

            chunk._cache_size = lambda: len(programs)
            return chunk

    return Recording()


def phase_minibatch(task) -> None:
    from benchmarks import fig2
    from repro.fl.driver import run_fleet_task

    placement = _recording_placement()
    hist, res, call_s = _fleet(task, MINIBATCH, placement=placement)
    _finite_histories(hist, fig2.SCHEMES)
    _report("fleet_minibatch_flat_f32", schemes=len(res.names),
            rounds=ROUNDS, compile_s=res.wall_compile,
            execute_s=res.wall_exec, call_s=call_s,
            final_acc={n: h[-1]["acc"] for n, h in hist.items()})
    calls = [t.count(KERNEL_MARK) for t in placement.texts]
    print(f"flat chunk programs the fleet ran: {len(calls)}, "
          f"{KERNEL_MARK} in each: {calls}", flush=True)
    assert calls and min(calls) > 0, "a flat chunk holds no Pallas kernel"

    # the same minibatch fleet through the tree-map round tail
    task = fig2._task(task)
    dep, prm, td = fig2.build_world(task, 0)
    run_cfg = task.run_config(num_rounds=ROUNDS, eval_every=EVAL_EVERY,
                              seed=0, batch_size=MINIBATCH)
    t0 = time.time()
    tree = run_fleet_task(task, fig2.make_schemes(task, dep, prm),
                          dep.gains, run_cfg, task_data=td,
                          params=task.init_params(0),
                          eval_fn=task.make_eval(td), flat=False)
    rel = _max_rel_diff(res.params, tree.params)
    print(f"flat (kernel) vs flat=False (tree-map oracle) params: max rel "
          f"diff {rel:.3e} (tolerance {FLAT_VS_TREE_RTOL:.0e})", flush=True)
    assert rel <= FLAT_VS_TREE_RTOL, rel
    _report("tree_oracle_fleet", call_s=time.time() - t0)


def phase_kernel(d: int = D_PAPER, n: int = 10) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    kg, ks, kz, kp = jax.random.split(jax.random.PRNGKey(0), 4)
    g = jax.random.normal(kg, (n, d), jnp.float32)
    s = jax.random.uniform(ks, (n,), jnp.float32, 0.1, 1.0)
    z = jax.random.normal(kz, (d,), jnp.float32)
    p = jax.random.normal(kp, (d,), jnp.float32)
    ns, eta = jnp.float32(0.25), jnp.float32(0.05)
    for ud in ops.UPLINK_DTYPES:
        t0 = time.time()
        kern = ops.ota_round_step.lower(g, s, z, ns, p, eta,
                                        uplink_dtype=ud).compile()
        t1 = time.time()
        out = jax.block_until_ready(kern(g, s, z, ns, p, eta))
        t2 = time.time()
        wire, q_scale = ops.quantize_uplink(g, ud)
        exp = ref.ota_round_step_ref(wire, s, z, ns, p, eta, q_scale=q_scale)
        err = float(jnp.max(jnp.abs(out - exp)))
        _report(f"kernel_vs_ref_{ud}", n=n, d=d, max_abs_err=err,
                compile_s=t1 - t0, execute_s=t2 - t1)
        assert out.shape == (d,) and err <= KERNEL_ATOL, (ud, err)
        assert KERNEL_MARK in kern.as_text(), ud


def phase_population(task, cohort: int = 50) -> None:
    from benchmarks import fig2
    from benchmarks.sca_bench import batch_gap_vs_scipy
    from repro.fl.placement import VmapPlacement

    hist, res, call_s = _fleet(task, MINIBATCH, population=1_000_000,
                               cohort=cohort, cohort_rounds=2)
    _finite_histories(hist, fig2.SCHEMES)
    _report("population_stream", population=1_000_000, cohort=cohort,
            rounds=ROUNDS, compile_s=res.wall_compile,
            execute_s=res.wall_exec, stage_s=res.wall_stage, call_s=call_s)

    # the fleets design on the host (solvers.x64_scope); an explicit
    # placement runs the same batch solve on the chip, in emulated f64
    for where, placement in (("chip", VmapPlacement()), ("host", None)):
        t0 = time.time()
        gap, br = batch_gap_vs_scipy(placement=placement)
        print(f"batched SCA solve on the {where} vs scipy SLSQP: largest "
              f"gap over {len(br.objective)} rows {gap:.3e} (tolerance "
              "1e-3)", flush=True)
        assert abs(gap) < 1e-3, (where, gap, br.objective)
        _report(f"sca_batch_vs_scipy_{where}", gap=gap,
                call_s=time.time() - t0)


def phase_four_chip_grid(task) -> None:
    import jax
    import numpy as np

    from benchmarks import fig2, scenario_sweep
    from repro.core import scenarios as scn
    from repro.fl.placement import ShardedPlacement
    from repro.launch.mesh import make_debug_mesh

    task = fig2._task(task)
    td = task.build_data(0)
    run_cfg = task.run_config(eta=0.05, num_rounds=GRID_ROUNDS,
                              eval_every=EVAL_EVERY, seed=0,
                              batch_size=MINIBATCH)
    seeds = (0, 1)
    cells = len(scn.SWEEP_FAMILIES) * len(scenario_sweep.SCHEMES) * len(seeds)

    def grid(label, placement):
        print(f"{label} grid: {cells} cells, start", flush=True)
        t0 = time.time()
        res = scenario_sweep._grid_fleet(
            task, scn.SWEEP_FAMILIES, scenario_sweep.SCHEMES, run_cfg, seeds,
            task_data=td, params=task.init_params(0),
            eval_fn=task.make_eval(td), placement=placement)
        _report(f"{label}_grid", cells=cells, compile_s=res.wall_compile,
                execute_s=res.wall_exec, call_s=time.time() - t0)
        return res

    with jax.default_matmul_precision("highest"):
        res_s = grid("sharded", ShardedPlacement(make_debug_mesh(2, 2)))
        res_v = grid("vmap", None)
    spans = {len(leaf.sharding.device_set)
             for leaf in jax.tree.leaves(res_s.params)}
    print(f"sharded grid params span {sorted(spans)} devices", flush=True)
    assert spans == {4}, spans
    for name in ("active_devices", "noise_scale"):
        same = np.array_equal(res_s.traces[name], res_v.traces[name])
        print(f"key-stream trace {name}: sharded == vmap bitwise: {same}",
              flush=True)
        assert same, name
    rel = _max_rel_diff(res_s.params, res_v.params)
    print(f"sharded vs vmap params: max rel diff {rel:.3e} "
          f"(tolerance {GRID_RTOL:.0e})", flush=True)
    assert rel <= GRID_RTOL, rel


def run_phases(phases) -> bool:
    """Run every phase; report each failure with its traceback."""
    ok = True
    for name, fn in phases:
        print(f"== {name}", flush=True)
        try:
            fn()
        except Exception:            # a phase failed: report, keep going
            traceback.print_exc()
            print(f"== {name}: FAILED", flush=True)
            ok = False
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded [4 x 3 x 2] scenario grid "
                         "against the vmap grid (needs 4 chips)")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(
        DEADLINE_S["four_chips" if args.four_chips else "one_chip"],
        exit=True)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (device 0 is "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    from repro import compile_cache

    print(f"compile cache: {compile_cache.enable()}", flush=True)
    task = "paper_mlp"
    if args.four_chips:
        phases = [("four_chip_grid", lambda: phase_four_chip_grid(task))]
    else:
        phases = [("fleet_fullbatch", lambda: phase_fullbatch(task)),
                  ("fleet_minibatch", lambda: phase_minibatch(task)),
                  ("kernel_vs_ref", phase_kernel),
                  ("population", lambda: phase_population(task))]
    ok = run_phases(phases)
    faulthandler.cancel_dump_traceback_later()
    if not ok:
        return 1
    print(json.dumps({"ok": True,
                      "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
