"""Scan-compiled batched FL experiment engine (DESIGN.md §Engine).

The paper's experiments are sweeps — schemes x seeds x scenarios — but a
host Python loop over rounds pays per-round dispatch, host->device batch
copies, and one compilation per scheme, and can never batch the grid.  This
module folds the FL round loop into XLA:

* ``make_round_body`` — one round as a pure function (gradients, fading,
  OTA aggregation, PS update), shared by every runtime below and by the
  legacy ``fl.server.make_round_fn`` wrapper.  Minibatches are sampled
  *on device* from the round key's ``k_batch`` lane.
* ``run_rounds`` — single (scheme, seed) run with the round loop compiled
  as chunked ``lax.scan`` (chunk boundaries = the eval cadence, so at most
  three chunk lengths ever compile).  Bit-identical to the legacy Python
  loop on the default path: the key stream, fading draws and update math
  are the same ops in the same order.
* ``run_fleet`` — a [K-scheme x S-seed] grid as one compiled program per
  chunk: schemes are stacked into a pytree (``power_control
  .stack_schemes``) and the scanned round body runs over (scheme, seed)
  cells.  Each cell reproduces the corresponding single run run-for-run.
  The grid machinery lives one layer up: ``fl.placement`` decides WHERE
  the cells run (vmap on one device — the default, bit-identical to the
  pre-placement engine — or shard_map over a ("data", "model") mesh) and
  ``fl.driver`` owns the chunk loop, adaptive re-design hook, and
  checkpointed resume; ``run_fleet`` here is the single-device alias that
  delegates to them (DESIGN.md §Placement).

Per-round metric traces (grad-norm mean, active devices, noise scale) come
back as stacked arrays straight from the scan — no per-round host sync.

Aggregation inside the round body is switchable: ``flat=False`` uses the
per-leaf tree-map oracle (bitwise-stable reference), ``flat=True`` ravels
the gradient pytree once and runs one fused flattened aggregation
(``kernels.ops.ota_aggregate_pytree`` — the Pallas ``ota_aggregate``
kernel on TPU, the flattened jnp oracle on CPU) with f32 accumulation and
a single fused noise draw whose per-leaf keying reproduces the tree path's
realizations, so the two paths agree to float rounding.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ota
from repro.core.power_control import PowerControl
from repro.optim.optimizers import clip_by_global_norm

PyTree = Any

# key folded into the run seed for FadingProcess state init (must match
# fl.server.run_fl_legacy so engine and legacy runs share state streams)
FADING_INIT_SALT = 0x5CE7A810


@dataclasses.dataclass
class FLResult:
    """What a compiled run returns.

    params        final parameters; leading [K, S] axes for fleet runs
    traces        per-round metric traces as arrays: {name: [T]} for single
                  runs, {name: [K, S, T]} for fleets
    evals         [(round, {name: scalar-or-[K, S] array})] at the eval
                  cadence (empty when no eval_fn was given)
    names         scheme names, length K (single runs: (scheme.name,))
    seeds         seeds swept, length S
    wall          total wall-clock seconds (= wall_compile + wall_exec)
    wall_compile  summed wall of the chunk calls that grew the chunk's
                  compile cache: each traces, lowers and compiles (or
                  fetches from the persistent cache) before it dispatches,
                  so its wall is compile; benchmark speedups quote it
                  separately so compile never inflates throughput.  The
                  fleet driver reuses a chunk across calls
                  (``fl.driver.run_fleet``), so a call whose chunk lengths
                  an earlier call already ran at the same shapes compiled
                  nothing and reads 0
    wall_exec     the rest: set-up, execution, eval and host work between
                  chunks
    fading_state  final FadingProcess state (None on the i.i.d. path)
    designs       adaptive-scheme design trace: [(round, gamma [K, S, N])]
                  with entry (t, g) meaning design g is in effect from
                  round t (None for non-adaptive runs)
    wall_stage    seconds spent staging cohorts (draw + gain
                  materialization + cohort redesign); under the streaming
                  driver this work overlaps chunk execution, so it shows
                  up here but mostly not in wall_exec
    cohorts       population-run cohort trace: [(round, idx [S, N])] with
                  entry (t, idx) meaning those device indices are active
                  from round t (None for full-participation runs)
    stage_walls   per-chunk staging seconds (``wall_stage`` is their sum)
                  for the chunks THIS invocation executed — the
                  streaming-lane profile benchmarks and telemetry consume
                  (None for full-participation runs)
    scenario_names scenario axis of a [C x K x S] grid run, length C
                  (None for single-scenario fleets); ``names`` is then the
                  flattened scenario-major cell axis, length C*K
    """
    params: PyTree
    traces: dict
    evals: list
    names: tuple
    seeds: tuple
    wall: float
    wall_compile: float = 0.0
    wall_exec: float = 0.0
    fading_state: Any = None
    designs: Optional[list] = None
    wall_stage: float = 0.0
    cohorts: Optional[list] = None
    stage_walls: Optional[list] = None
    scenario_names: Optional[tuple] = None


def make_round_body(loss_fn: Callable, gains: np.ndarray, run,
                    fading=None, flat: bool = False,
                    sample_on_device: bool = True,
                    cohort: bool = False,
                    scenario: bool = False,
                    metrics_hook: Optional[Callable] = None,
                    uplink_dtype: Optional[str] = None,
                    fuse_round: Optional[bool] = None) -> Callable:
    """One FL round as a pure function.

        body(scheme, eta, params, fading_state, key, data)
            -> (params, fading_state, metrics)

    ``scheme`` is a PowerControl pytree (so it may be a vmapped row of a
    stacked fleet), ``eta`` a scalar step size (vmappable per scheme),
    ``data`` the stacked per-device datasets (x [N, D, ...], y [N, D]).

    The round key is split exactly like the legacy loop —
    (k_fade, k_ota, k_batch) — with k_batch now actually consumed: when
    ``sample_on_device`` and 0 < run.batch_size < D, each device's
    minibatch is gathered on device (uniform with replacement, the same
    sampling law as the legacy host-numpy path).  The default full-batch
    path consumes keys and data identically to the legacy round function,
    so trajectories are bit-for-bit reproducible against it.

    With ``cohort=True`` the body takes one extra operand —
    ``co = {"gains": [N] active gains, "data_idx": [N] shard indices}`` —
    and the round runs on the gathered active set instead of the closed-
    over ``gains``/full ``data`` (DESIGN.md §Population).  Cohort arrays
    are fixed-size [N] operands, never constants, so the compiled chunk is
    reused across every cohort draw; the key stream is untouched, and a
    cohort equal to the full device set gathers identity — bitwise the
    non-cohort program's values.

    With ``scenario=True`` the body instead takes a per-cell
    ``core.scenarios.ScenarioStack`` row as its extra operand —
    ``body(..., data, sc)`` — and both the channel draw and its state
    update come from ``sc.step`` (gains live in the row, so ``gains`` may
    be None and ``fading`` must be: the row IS the fading process).  A
    [C x K x S] grid is then just a [C*K, S] fleet whose cells carry their
    scenario row alongside their scheme row (DESIGN.md §Grid); each cell's
    key split and update math are unchanged, so every cell is bitwise the
    single-scenario fleet's.

    ``metrics_hook`` (DESIGN.md §Telemetry) extends the per-round metrics
    dict: called as ``hook(s=..., noise_scale=..., h=..., params=...)``
    with the realized OTA coefficients right after they are fixed, it
    returns extra scalar traces (the in-graph bias-variance diagnostics).
    The default ``None`` leaves the round body — and therefore the
    compiled chunk — literally unchanged: the bitwise-off guarantee.

    ``uplink_dtype`` (default: ``run.uplink_dtype``, itself "f32") picks
    the wire precision devices transmit — f32, bf16 or int8 with a
    per-device symmetric scale (kernels.ops.quantize_uplink); the receiver
    always dequantizes and accumulates in f32.  Quantized uplinks require
    the flat path (there is no wire on the tree-map oracle).

    ``fuse_round`` controls whether the flat round tail runs as the ONE
    fused ``ota.fused_round_step`` launch (aggregate + noise + SGD step,
    kernels/round_step.py) or as the historical aggregate-then-update op
    chain.  Default ``None`` = fuse exactly when ``flat`` — with an f32
    uplink the fused launch is bitwise the unfused chain (pinned in
    tests/test_kernels.py), so flipping the default changes no numbers.
    ``fuse_round=False`` keeps the unfused reference for parity tests and
    the fused-vs-unfused benchmark.
    """
    gains_j = None if gains is None else jnp.asarray(gains)
    if uplink_dtype is None:
        uplink_dtype = getattr(run, "uplink_dtype", "f32") or "f32"
    if uplink_dtype not in ota.UPLINK_DTYPES:
        raise ValueError(f"uplink_dtype must be one of {ota.UPLINK_DTYPES}, "
                         f"got {uplink_dtype!r}")
    if uplink_dtype != "f32" and not flat:
        raise ValueError(f"uplink_dtype={uplink_dtype!r} requires the flat "
                         "aggregation path (flat=True)")
    fuse = bool(flat) if fuse_round is None else bool(fuse_round)
    if fuse and not flat:
        raise ValueError("fuse_round=True requires flat=True")
    if scenario and cohort:
        raise ValueError("scenario grids and cohort sampling are exclusive "
                         "(a cohort row would need per-scenario gathers)")
    if scenario and fading is not None:
        raise ValueError("scenario=True owns the channel process; "
                         "pass fading=None")

    # the round's layers as name scopes (metadata only: the compiled
    # program and its compile-cache key are unchanged), so a profile
    # attributes device time to them: fl.grad (minibatch draw, per-device
    # gradients, clip), fl.channel (fading, coefficients), fl.step (the
    # aggregation and SGD update; the flat path carves its layout work out
    # as fl.uplink, in kernels/ops.py)
    def grads_of(params, data, k_batch):
        with jax.named_scope("fl.grad"):
            batch = sample(data, k_batch)
            return jax.vmap(lambda b: device_grad(params, b))(batch)

    def device_grad(params, batch):
        g = jax.grad(loss_fn)(params, batch)
        if run.clip_to_gmax:
            g, norm = clip_by_global_norm(g, run.gmax)
        else:
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(l))
                                for l in jax.tree.leaves(g)))
        return g, norm

    def sample(data, k_batch):
        x_dev, y_dev = data
        d = x_dev.shape[1]
        if not sample_on_device or run.batch_size <= 0 \
                or run.batch_size >= d:
            return data
        idx = jax.random.randint(k_batch, (x_dev.shape[0], run.batch_size),
                                 0, d)
        xb = jnp.take_along_axis(
            x_dev, idx.reshape(idx.shape + (1,) * (x_dev.ndim - 2)), axis=1)
        yb = jnp.take_along_axis(y_dev, idx, axis=1)
        return xb, yb

    def finish(scheme, eta, params, fading_state, k_ota, h, grads, norms):
        # coefficients once, threaded into both the aggregation and the
        # metrics — they can never disagree (bbfl_alternative randomizes
        # round_coeffs, so recomputing from a different key split would).
        with jax.named_scope("fl.channel"):
            k_coeff, k_noise = ota.split_ota_key(k_ota)
            s, noise_scale = scheme.round_coeffs(h, k_coeff)
        with jax.named_scope("fl.step"):
            if fuse:
                params = ota.fused_round_step(grads, s, noise_scale, k_noise,
                                              params, eta,
                                              uplink_dtype=uplink_dtype)
            else:
                g_hat = ota.apply_round_coeffs(grads, s, noise_scale,
                                               k_noise, flat=flat,
                                               uplink_dtype=uplink_dtype)
                params = jax.tree.map(
                    lambda p, g: (p.astype(jnp.float32)
                                  - eta * g.astype(jnp.float32)
                                  ).astype(p.dtype),
                    params, g_hat)
        metrics = {
            "grad_norm_mean": jnp.mean(norms),
            "active_devices": jnp.sum((s > 0).astype(jnp.float32)),
            "noise_scale": jnp.asarray(noise_scale, jnp.float32),
        }
        if metrics_hook is not None:
            metrics.update(metrics_hook(s=s, noise_scale=noise_scale, h=h,
                                        params=params))
        return params, fading_state, metrics

    def body(scheme, eta, params, fading_state, key, data):
        k_fade, k_ota, k_batch = jax.random.split(key, 3)
        grads, norms = grads_of(params, data, k_batch)
        with jax.named_scope("fl.channel"):
            if fading is None:
                h = ota.draw_fading(k_fade, gains_j)
            else:
                fading_state, h = fading.step(fading_state, k_fade)
        return finish(scheme, eta, params, fading_state, k_ota, h, grads,
                      norms)

    def cohort_body(scheme, eta, params, fading_state, key, data, co):
        k_fade, k_ota, k_batch = jax.random.split(key, 3)
        with jax.named_scope("fl.grad"):
            active = jax.tree.map(
                lambda a: jnp.take(a, co["data_idx"], axis=0), data)
        grads, norms = grads_of(params, active, k_batch)
        with jax.named_scope("fl.channel"):
            if fading is None:
                h = ota.draw_fading(k_fade, co["gains"])
            else:
                fading_state, h = fading.step_cohort(fading_state, k_fade,
                                                     co["gains"])
        return finish(scheme, eta, params, fading_state, k_ota, h, grads,
                      norms)

    def scenario_body(scheme, eta, params, fading_state, key, data, sc):
        k_fade, k_ota, k_batch = jax.random.split(key, 3)
        grads, norms = grads_of(params, data, k_batch)
        with jax.named_scope("fl.channel"):
            fading_state, h = sc.step(fading_state, k_fade)
        return finish(scheme, eta, params, fading_state, k_ota, h, grads,
                      norms)

    if scenario:
        return scenario_body
    return cohort_body if cohort else body


def chunk_lengths(num_rounds: int, eval_every: int, with_eval: bool,
                  cohort_rounds: Optional[int] = None) -> list:
    """Scan chunk lengths whose boundaries hit the legacy eval cadence
    (t % eval_every == 0 or t == num_rounds - 1).  At most three distinct
    lengths occur — {1, eval_every, tail} — so a run compiles at most three
    scan programs, and a fleet call whose lengths an earlier call with the
    same cached chunk ran compiles none (``fl.driver.run_fleet``).

    ``cohort_rounds`` adds population-cohort boundaries: the active set
    changes BEFORE every round t with t % cohort_rounds == 0, so chunks
    also end at rounds c*cohort_rounds - 1 (a cohort never straddles a
    chunk).  The default schedule (None) leaves the chunk grid untouched —
    cohort runs then redraw per chunk, i.e. at the eval cadence."""
    if num_rounds <= 0:
        return []
    pts = set(range(0, num_rounds, eval_every)) if with_eval else set()
    if cohort_rounds:
        pts |= set(range(cohort_rounds - 1, num_rounds, cohort_rounds))
    if not pts:
        return [num_rounds]
    pts = sorted(pts | {num_rounds - 1})
    lengths, prev = [], -1
    for t in pts:
        lengths.append(t - prev)
        prev = t
    return lengths


def _scan_chunk(round_body, scheme, eta, params, fading_state, key, data,
                length: int, cohort=None, scenario=None):
    """``length`` rounds of ``round_body`` under lax.scan; returns stacked
    per-round metrics.  The main key is split once per round, exactly like
    the legacy host loop.  ``cohort`` (a cohort-body operand dict, see
    ``make_round_body``) and ``scenario`` (a ScenarioStack cell row) ride
    along as scan constants — operands of the compiled chunk, so changing
    cohorts or scenario parameters never recompiles."""
    def step(carry, _):
        params, fading_state, key = carry
        key, sub = jax.random.split(key)
        if cohort is not None:
            params, fading_state, metrics = round_body(
                scheme, eta, params, fading_state, sub, data, cohort)
        elif scenario is not None:
            params, fading_state, metrics = round_body(
                scheme, eta, params, fading_state, sub, data, scenario)
        else:
            params, fading_state, metrics = round_body(
                scheme, eta, params, fading_state, sub, data)
        return (params, fading_state, key), metrics

    (params, fading_state, key), metrics = jax.lax.scan(
        step, (params, fading_state, key), None, length=length)
    return params, fading_state, key, metrics


def _concat_traces(chunks: list) -> dict:
    if not chunks:
        return {}
    # intersect on the first chunk's keys: a resume that toggled the
    # telemetry diagnostics mid-run degrades to the common traces instead
    # of KeyError-ing (the diagnostic keys are additive, never load-bearing)
    keys = [k for k in chunks[0] if all(k in c for c in chunks)]
    return {k: np.concatenate([np.asarray(c[k]) for c in chunks], axis=-1)
            for k in keys}


def run_rounds(loss_fn: Callable, params: PyTree, scheme: PowerControl,
               gains: np.ndarray, data: tuple, run,
               eval_fn: Optional[Callable] = None, fading=None,
               flat: bool = False, log: bool = False) -> FLResult:
    """Single (scheme, seed) run with the round loop compiled as chunked
    lax.scan.  Bit-identical to ``fl.server.run_fl_legacy`` on the default
    full-batch path; with 0 < run.batch_size < D minibatches are sampled on
    device from the round key (the legacy host-numpy sampling stream is
    retired with the host loop)."""
    t0 = time.time()
    round_body = make_round_body(loss_fn, gains, run, fading=fading,
                                 flat=flat)
    # scheme and eta are *closed over*, not passed as operands: the legacy
    # per-round jit embeds them as constants, and constant-vs-operand flips
    # XLA constant folding enough to break bitwise equality with it.
    chunk = jax.jit(
        functools.partial(_scan_chunk, round_body, scheme, run.eta),
        static_argnames=("length",))
    data = tuple(jnp.asarray(a) for a in data)
    key = jax.random.PRNGKey(run.seed)
    fading_state = None
    if fading is not None:
        fading_state = fading.init(jax.random.fold_in(key, FADING_INIT_SALT))

    evals, metric_chunks, t = [], [], 0
    wall_compile = 0.0
    for length in chunk_lengths(run.num_rounds, run.eval_every,
                                eval_fn is not None):
        size0, t_call = chunk._cache_size(), time.time()
        params, fading_state, key, metrics = chunk(
            params, fading_state, key, data, length=length)
        if chunk._cache_size() > size0:
            wall_compile += time.time() - t_call
        metric_chunks.append(metrics)
        t += length
        if eval_fn is not None:
            ev = {k: float(v) for k, v in eval_fn(params).items()}
            evals.append((t - 1, ev))
            if log:
                print({"round": t - 1, "scheme": scheme.name,
                       **{k: round(v, 4) for k, v in ev.items()}})
    wall = time.time() - t0
    return FLResult(params=params, traces=_concat_traces(metric_chunks),
                    evals=evals, names=(scheme.name,), seeds=(run.seed,),
                    wall=wall, wall_compile=wall_compile,
                    wall_exec=wall - wall_compile,
                    fading_state=fading_state)


def run_fleet(loss_fn: Callable, params: PyTree, schemes, gains: np.ndarray,
              data: tuple, run, eval_fn: Optional[Callable] = None, *,
              etas=None, seeds: Optional[Sequence[int]] = None, fading=None,
              flat: bool = True, log: bool = False, **driver_kw) -> FLResult:
    """A [K-scheme x S-seed] experiment grid as ONE compiled scan program.

    The single-device alias of the layered executor: delegates to
    ``fl.driver.run_fleet`` on the default ``VmapPlacement`` (bit-identical
    to the pre-placement engine); extra keyword args — ``placement``,
    ``checkpoint_path``, ``resume``, ``max_chunks`` — pass through to the
    driver (DESIGN.md §Placement).

    ``schemes``: a list of PowerControl objects (stacked via
    ``stack_schemes`` — heterogeneous mixes dispatch through the
    SchemeBatch union) or an already-stacked fleet.  ``etas``: per-scheme
    step sizes [K] (default run.eta everywhere).  ``seeds``: the seed axis
    (default (run.seed,)); each (k, s) cell consumes the exact key/fading
    streams of a standalone run with that seed, so the fleet matches the
    per-scheme loop run-for-run.

    Every cell shares ``data`` (device-resident once) and the initial
    ``params``.  eval_fn is vmapped across the grid at each eval boundary;
    traces/evals come back with leading [K, S] axes (see FLResult).

    Adaptive schemes (``power_control.AdaptiveSCA``: a ``redesign_fn``
    attribute) re-design their power control BETWEEN scan chunks from the
    live fading state: their design leaves are tiled to the full [K, S]
    grid (each cell tracks its own channel trajectory), chunk boundaries
    follow the eval cadence even without an eval_fn (the re-design
    cadence), and the per-chunk designs come back as ``FLResult.designs``.
    Without a fading process (static CSI) the redesign hook is a no-op and
    the run is identical to the plain ``sca`` scheme's.
    """
    from repro.fl import driver  # deferred: driver imports this module
    return driver.run_fleet(loss_fn, params, schemes, gains, data, run,
                            eval_fn, etas=etas, seeds=seeds, fading=fading,
                            flat=flat, log=log, **driver_kw)


def run_fleet_task(task, schemes, gains: np.ndarray, run=None,
                   **kw) -> FLResult:
    """Task-first alias of ``run_fleet`` (DESIGN.md §Tasks): the workload's
    loss/params/data/eval come from a ``repro.tasks`` bundle; delegates to
    ``fl.driver.run_fleet_task`` (same keyword surface)."""
    from repro.fl import driver  # deferred: driver imports this module
    return driver.run_fleet_task(task, schemes, gains, run, **kw)
