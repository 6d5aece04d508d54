"""One run of one cell: set-up, the measured window, the trace, the check.

``run`` returns the result line's object; ``bench/run.py`` is the command
that prints it.  Set-up (``setup_s``) lasts from process start to the
window: inputs on the device from the seed, the wireless world and the
power-control designs on the host, and one short warm-up sweep whose
chunk programs are the window's.  The window calls the timed path back
to back, one whole sweep per call; a sweep that has started is finished,
and the window ends when the last sweep that started inside ``seconds``
ends.  As soon as the first sweep returns, its checked cells (params,
evals, traces) are copied to the host and the rest of its result is
dropped, so the device holds no earlier sweep while later ones run; the
copy's measured seconds are left out of the window, on the host clock
and in the traced window alike.  With ``trace`` the window is
``TRACE_SWEEPS`` sweeps under the JAX profiler and the program's
telemetry, and the run reports the per-layer metrics instead of the
end-to-end ones.  Then the checked cells are held against the plain
reference.
"""
from __future__ import annotations

import os
import shutil
import sys
import time
import traceback
import types

import numpy as np

from bench import check, peaks, reference, sweep, xtrace


# sweeps in a traced window: the second shows the steady state, since the
# first also pays what a sweep after set-up pays once
TRACE_SWEEPS = 2
# cells checked against the reference: one from each quarter of the
# flattened cell axis, so that each of four chips' blocks is checked
CHECK_CELLS = 4


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _chunk_set(rounds: int, every: int) -> set:
    pts = reference.eval_rounds(rounds, every)
    return set(np.diff([-1] + pts).tolist())


def configure_cache(root: str) -> str:
    """JAX's persistent compile cache at a fixed path in the checkout
    (or where JAX_COMPILATION_CACHE_DIR says), for every program however
    fast it compiles: a program left out would compile again in every
    run."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class _CompileCounter:
    """Counts backend compiles and persistent-cache fetches while on."""

    def __init__(self):
        import jax.monitoring as mon

        self.on, self.compiles, self.fetches = False, 0, 0
        mon.register_event_duration_secs_listener(self._heard)

    def _heard(self, event, duration, **_):
        if not self.on:
            return
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.fetches += 1


def _finite(tree_check, res) -> bool:
    if not bool(tree_check(res.params)):
        return False
    return all(np.all(np.isfinite(np.asarray(v)))
               for _, ev in res.evals for v in ev.values())


def run(workload: str, seed: int, seconds: float, trace: bool,
        bench_dir: str, t0: float, require_chip: bool = True) -> dict:
    import jax
    import jax.numpy as jnp

    cell = sweep.load_cell(workload, bench_dir)
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        raise NoChip(f"cell {workload} needs {cell.chips} TPU chip(s); JAX "
                     f"found {len(devices)} {devices[0].platform} device(s)")
    devices = devices[:cell.chips]
    configure_cache(cell.root)
    counter = _CompileCounter()
    tr, cfg = cell.traffic, cell.config
    if _chunk_set(tr["warmup_rounds"], tr["eval_every"]) != \
            _chunk_set(tr["rounds"], tr["eval_every"]):
        raise ValueError("the warm-up sweep would not compile the window's "
                         "chunk programs")

    # -- set-up ------------------------------------------------------------
    world = sweep.make_world(cell)
    inputs = sweep.make_inputs(cell, seed)
    sweeper = sweep.Sweeper(cell, world, inputs, devices)
    n_rows, n_seeds = len(world.schemes), tr["seeds_per_sweep"]
    all_finite = jax.jit(lambda t: jnp.all(jnp.stack(
        [jnp.all(jnp.isfinite(x)) for x in jax.tree.leaves(t)])))
    warm = sweeper(sweep.fleet_seeds(seed, 0, n_seeds, warmup=True),
                   rounds=tr["warmup_rounds"])
    _finite(all_finite, warm)
    del warm
    setup_s = time.monotonic() - t0
    _log(f"# set-up {setup_s:.3f} s: {n_rows} rows x {n_seeds} seeds, "
         f"{tr['rounds']} rounds per sweep")

    # -- the window ----------------------------------------------------------
    out_dir = os.path.join(cell.root, ".bench_out", cell.name)
    trace_dir = os.path.join(out_dir, "trace")
    tel_dirs, marks = [], []
    if trace:
        shutil.rmtree(out_dir, ignore_errors=True)
        os.makedirs(out_dir, exist_ok=True)
        from repro.telemetry import Telemetry
        jax.profiler.start_trace(trace_dir)
    attempted = failed = 0
    picks = sweep.check_cells(seed, n_rows, n_seeds, CHECK_CELLS)
    prog, copy_s = None, 0.0
    counter.on = True
    w0 = time.monotonic()
    while (trace and attempted < TRACE_SWEEPS) or \
            (not trace and time.monotonic() - w0 - copy_s < seconds):
        i = attempted
        attempted += 1
        telemetry = None
        if trace:
            tel_dirs.append(os.path.join(out_dir, f"telemetry{i}"))
            telemetry = Telemetry(run_dir=tel_dirs[-1], trace=True,
                                  diagnostics=False)
        try:
            mono = time.monotonic()
            with jax.profiler.TraceAnnotation("bench.sweep", sweep=i):
                res = sweeper(sweep.fleet_seeds(seed, i, n_seeds),
                              telemetry=telemetry)
                jax.block_until_ready(res.params)
            marks.append((mono, time.monotonic()))
            if not _finite(all_finite, res):
                failed += 1
                _log(f"# sweep {i}: non-finite params or evals")
        except Exception:                         # noqa: BLE001 - counted
            failed += 1
            res = None
            _log(f"# sweep {i} failed:\n{traceback.format_exc()}")
        if i == 0 and res is not None:
            c0 = time.monotonic()
            with jax.profiler.TraceAnnotation("bench.copy"):
                prog = check.gather(res, picks)
            copy_s = time.monotonic() - c0
        res = None
    w1 = time.monotonic()
    counter.on = False
    if trace:
        jax.profiler.stop_trace()
    # a cache hit reports a backend compile too: what is left compiled anew
    window = w1 - w0 - copy_s
    _log(f"# window {window:.3f} s (the copy of the checked cells, "
         f"{copy_s:.3f} s, left out), {attempted} sweeps, "
         f"{failed} failed, "
         f"{counter.fetches} programs fetched from the compile cache, "
         f"{counter.compiles - counter.fetches} compiled")

    cells = n_rows * n_seeds
    done = (attempted - failed) * cells * tr["rounds"]
    peak = None
    stats = [d.memory_stats() for d in devices]
    _log(f"# memory_stats {stats}")
    if all(stats):
        # the TPU runtime holds a program's scratch apart from its buffers
        # and reports its peak as ``peak_bytes_reserved``
        peak = max(int(s.get("peak_bytes_in_use", 0))
                   + int(s.get("peak_bytes_reserved", 0)) for s in stats)

    result = {"correct": False, "attempted": attempted, "failed": failed}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": cell.chips,
              "memory_peak_bytes": peak}
    metrics, breakdown = {}, None
    if trace:
        ctx = _trace_context(cell, trace_dir, tel_dirs, marks, devices,
                             cells, attempted - failed)
        device["busy_s"] = ctx.busy_s
        device["window_s"] = ctx.window_s
        for m in cell.per_layer:
            reader = sweep.load_module(
                os.path.join(bench_dir, "metrics", m["name"] + ".py"),
                "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = ctx.breakdown
    else:
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": m["unit"]}
            elif m["name"] == "cell_rounds_per_s":
                metrics[m["name"]] = {"value": done / window,
                                      "unit": m["unit"]}

    # -- the check, once the program's state is gone -------------------------
    nums = {}
    if prog is not None:
        t_ref = time.monotonic()
        data, params0 = inputs
        rows = [r for r, _ in picks]
        ref = reference.simulate(
            cell.model, cfg, data, params0,
            reference.cell_rows(world.coeffs, world.fading, world.etas,
                                rows),
            [sweep.fleet_seeds(seed, 0, n_seeds)[s] for _, s in picks],
            rounds=tr["rounds"], every=tr["eval_every"],
            batch=tr["batch_size"], gmax=cfg["gmax"],
            grid=world.coeffs[0]["grid_size"])
        nums = check.numbers(prog, ref, jax.device_get(params0))
        _log(f"# reference {time.monotonic() - t_ref:.3f} s over cells "
             f"{[(world.row_names[r], s) for r, s in picks]}")
    ok, checks = check.verdict(nums, cell.limits["limits"])
    result["correct"] = bool(ok and failed == 0 and prog is not None)
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        _log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    return result


def _trace_context(cell, trace_dir, tel_dirs, marks, devices, cells,
                   sweeps):
    """What the per-layer readers read: device ops per chip over the
    traced window, the program's telemetry spans on the profiler's clock,
    and the traced work.  The window runs from the first sweep's start to
    the last sweep's end, less the copy of the checked cells."""
    events = xtrace.load_xplane(trace_dir)
    first, held = {}, []
    for e in sorted(events, key=lambda e: e["start"]):
        if e["plane"].startswith(xtrace.DEVICE_PREFIX):
            continue
        if e["name"] == "bench.sweep":
            first.setdefault(e["stats"].get("sweep", len(first)), e)
        elif e["name"] == "bench.copy":
            held.append((e["start"], e["start"] + e["dur"]))
    ann = [first[k] for k in sorted(first)]
    planes = xtrace.device_planes(events)[:len(devices)]
    ctx = types.SimpleNamespace(
        cell=cell, events=events, planes=planes, sweeps=sweeps,
        cells=cells, chips=len(devices), rounds=cell.traffic["rounds"],
        peaks=None, spans=[], telemetry=[], ops={}, window_s=None,
        busy_s=None, breakdown=None, log=_log)
    try:
        ctx.peaks = peaks.chip_peaks(devices[0].device_kind)
    except ValueError as e:
        _log(f"# {e}")
    if len(ann) != len(marks) or not ann:
        _log(f"# trace: {len(ann)} sweep annotations for {len(marks)} "
             "sweeps; no device numbers")
        ctx.telemetry = xtrace.telemetry_spans(tel_dirs, 0.0)
        return ctx
    offset = float(np.median([a["start"] - m[0] * 1e9
                              for a, m in zip(ann, marks)]))
    pieces = xtrace.without(ann[0]["start"],
                            ann[-1]["start"] + ann[-1]["dur"], held)
    ctx.window_s = sum(b - a for a, b in pieces) / 1e9
    ctx.telemetry = xtrace.telemetry_spans(tel_dirs, offset)
    ctx.spans = ctx.telemetry + [{"kind": "bench.sweep", "start": a["start"],
                                  "end": a["start"] + a["dur"]} for a in ann]
    by_piece = {p: [xtrace.device_ops(events, p, a, b) for a, b in pieces]
                for p in planes}
    ctx.ops = {p: [e for ops in by_piece[p] for e in ops] for p in planes}
    if planes:
        busy = [xtrace.busy_ns(ctx.ops[p]) / 1e9 for p in planes]
        ctx.busy_s = float(np.mean(busy))
        gaps = []
        for p in planes:
            for (a, b), ops in zip(pieces, by_piece[p]):
                gaps += xtrace.idle_gaps(ops, ctx.spans, a, b)
            _log(f"# {p}: busy {busy[planes.index(p)]:.6f} s of "
                 f"{ctx.window_s:.6f} s")
        gaps.sort(key=lambda g: -g[1])
        _log(f"# idle by host span: {xtrace.idle_by_label(gaps)}")
        ctx.breakdown = {"device_ops": xtrace.top_ops(ctx.ops),
                         "idle_gaps": gaps[:10]}
    return ctx
