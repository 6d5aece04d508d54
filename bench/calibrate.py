#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from.

    python3 bench/calibrate.py --workload <cell> --seeds <first> <count> \
        [--control 3] [--faults 3] [--out <file.json>]

On the chip, at the cell's own size, for each seed: the first sweep of a
run with that ``--seed`` (the window's first call, the one a run checks)
against the plain reference — the lower readings.  Then, on the first
``--control`` seeds, the control: the reference computed in bfloat16 put
in the program's place — the upper readings.  Then, on the first
``--faults`` seeds, planted faults: half of each device's batch left out
(in the reference put in the program's place), the test accuracy
altered where the eval produces it (every prediction class 0, the fault
a fused argmax once made on the chip), and on a sharded cell the
exchange between chips left out (every chip's cells given chip 0's
results).  A state left unchanged reads 1 on ``delta`` by definition
and needs no run.  The benchmark's own runs never run this.
"""
import time

_T0 = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@contextlib.contextmanager
def exchange_left_out():
    """Sharded chunks hand every chip's cells chip 0's results, as if the
    other chips' outputs never came back."""
    import jax
    import jax.numpy as jnp
    from repro.fl.placement import ShardedPlacement

    saved = ShardedPlacement._compile_scenario, ShardedPlacement._compile

    def broken(orig):
        def compile_fn(self, round_body, length, k, s):
            fn = orig(self, round_body, length, k, s)
            block = k * s // self.num_devices

            def run(*args):
                def chip0(a):
                    flat = jnp.reshape(a, (k * s,) + a.shape[2:])
                    flat = jnp.tile(flat[:block],
                                    (k * s // block,) + (1,) * (a.ndim - 2))
                    return jnp.reshape(flat, a.shape)
                return jax.tree.map(chip0, fn(*args))
            return run
        return compile_fn

    ShardedPlacement._compile_scenario = broken(saved[0])
    ShardedPlacement._compile = broken(saved[1])
    try:
        yield
    finally:
        ShardedPlacement._compile_scenario, ShardedPlacement._compile = saved


def class0_answers(prog: dict, test_y) -> dict:
    """The program's output with every eval's accuracy as if each test
    prediction were class 0."""
    import numpy as np

    share = float(np.mean(np.asarray(test_y) == 0))
    evals = [(t, {**ev, "acc": np.full_like(ev["acc"], share)})
             for t, ev in prog["evals"]]
    return {**prog, "evals": evals}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs=2, required=True,
                    metavar=("FIRST", "COUNT"))
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import check, harness, reference, sweep

    cell = sweep.load_cell(args.workload, BENCH_DIR)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"cell {cell.name} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 4
    devices = devices[:cell.chips]
    harness.configure_cache(cell.root)
    tr, cfg = cell.traffic, cell.config
    world = sweep.make_world(cell)
    n_rows, n_seeds = len(world.schemes), tr["seeds_per_sweep"]
    first, count = args.seeds
    out = {"cell": cell.name, "device": devices[0].device_kind,
           "chips": cell.chips, "program": {}, "control": {}, "faults": {}}

    def ref_of(seed, data, params0, picks, **kw):
        return reference.simulate(
            cell.model, cfg, data, params0,
            reference.cell_rows(world.coeffs, world.fading, world.etas,
                                [r for r, _ in picks]),
            [sweep.fleet_seeds(seed, 0, n_seeds)[s] for _, s in picks],
            rounds=tr["rounds"], every=tr["eval_every"],
            batch=tr["batch_size"], gmax=cfg["gmax"],
            grid=world.coeffs[0]["grid_size"], **kw)

    def program_run(seed, inputs, picks):
        sweeper = sweep.Sweeper(cell, world, inputs, devices)
        res = sweeper(sweep.fleet_seeds(seed, 0, n_seeds))
        return check.gather(res, picks)

    def note(kind, key, nums):
        out[kind].setdefault(key, []).append(nums)
        print(f"# {kind} {key} {json.dumps(nums)}", file=sys.stderr,
              flush=True)

    for i, seed in enumerate(range(first, first + count)):
        t = time.monotonic()
        inputs = sweep.make_inputs(cell, seed)
        data, params0 = inputs
        p0 = jax.device_get(params0)
        picks = sweep.check_cells(seed, n_rows, n_seeds,
                                   harness.CHECK_CELLS)
        prog = program_run(seed, inputs, picks)
        ref = ref_of(seed, data, params0, picks)
        note("program", str(seed), check.numbers(prog, ref, p0))
        if i < args.control:
            ctl = ref_of(seed, data, params0, picks, dtype=jnp.bfloat16,
                         precision=None)
            note("control", "bf16", check.numbers(ctl, ref, p0))
        if i < args.faults:
            half = ref_of(seed, data, params0, picks, keep=0.5)
            note("faults", "half_batch", check.numbers(half, ref, p0))
            note("faults", "answer_class0",
                 check.numbers(class0_answers(prog, data["test_y"]), ref,
                               p0))
            if tr["placement"] == "sharded":
                with exchange_left_out():
                    bad = program_run(seed, inputs, picks)
                note("faults", "exchange", check.numbers(bad, ref, p0))
        print(f"# seed {seed} done in {time.monotonic() - t:.1f} s",
              file=sys.stderr, flush=True)

    names = sorted(next(iter(out["program"].values()))[0])
    summary = {"lower": {n: max(v[0][n] for v in out["program"].values())
                         for n in names}}
    for kind in ("control", "faults"):
        for key, runs in out[kind].items():
            summary[f"{kind}:{key}"] = {n: min(r[n] for r in runs)
                                        for n in names}
    out["summary"] = summary
    out["seconds"] = time.monotonic() - _T0
    text = json.dumps(out, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
