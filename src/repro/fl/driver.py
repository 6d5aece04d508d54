"""Host-driver layer of the fleet executor (DESIGN.md §Placement).

Owns everything around the compiled grid chunks of a [K-scheme x S-seed]
fleet: the chunk loop over ``engine.chunk_lengths``, the adaptive
re-design hook between chunks, the eval cadence, the compile/exec wall
split, and the checkpointed-resume path.  WHERE the cells run is the
placement layer's business (``fl.placement``): the driver hands every
chunk a [K, S]-shaped carry and gets one back, whether the cells ran as
one vmapped program on a single device or sharded over a
``("data", "model")`` mesh.

Checkpointed resume: pass ``checkpoint_path`` and the driver persists the
full fleet carry — params_b, fading_state, keys_b, the stacked schemes'
design leaves, plus the metric traces / evals / ``FLResult.designs``
accumulated so far — through ``checkpoint/checkpoint.py`` at every chunk
boundary.  A preempted sweep rerun with ``resume=True`` fast-forwards to
the first incomplete chunk and finishes bit-identically to an
uninterrupted run (same carries, same key streams, same chunk schedule);
AdaptiveSCA design trajectories survive the restart.

Population mode (DESIGN.md §Population): pass a ``scenarios.Population``
and the driver becomes a streaming serving loop — each chunk runs on a
per-round-drawn cohort of ``cohort_size`` devices out of up to ~1M, with
the draw, gain materialization and ``adaptive_sca`` cohort redesign staged
on the host WHILE the previous chunk executes on device (double-buffered;
``stream=False`` serializes the same stages — identical math, different
walls).  Staging is pure in (population, run seed, tick), never in chunk
outputs, which is both why overlap cannot change results and why resume
needs no RNG cursor: a restart re-derives every draw from the chunk index.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import hashlib
import os
import threading
import time
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry as tlm
from repro.checkpoint import checkpoint as ckpt
from repro.core.power_control import _scheme_n, stack_schemes
from repro.fl.engine import (FADING_INIT_SALT, FLResult, _concat_traces,
                             chunk_lengths, make_round_body)
from repro.fl.placement import Placement, VmapPlacement

PyTree = Any


class _Staged(NamedTuple):
    """One staged cohort: everything chunk ``ci`` needs that can be
    computed before chunk ``ci - 1`` finishes (the double buffer)."""
    ci: int
    tick: int
    idx: np.ndarray      # [S, N] drawn device indices (per seed row)
    cohort: dict         # chunk operand: gains [S, N], data_idx [S, N]
    stacked: Any         # cohort-redesigned schemes (None if non-adaptive)
    wall: float


def _ckpt_file(path: str) -> str:
    return path if path.endswith(".npz") else path + ".npz"


def _carry_tree(stacked, params_b, fading_state, keys_b) -> dict:
    carry = {"carry": {"params": params_b, "keys": keys_b},
             "scheme": stacked}
    if fading_state is not None:
        carry["carry"]["fstate"] = fading_state
    return carry


def _array_digest(*arrays) -> str:
    h = hashlib.sha1()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def _fading_desc(fading) -> str:
    if fading is None:
        return "none"
    return (f"{type(fading).__name__}(family={getattr(fading, 'family', '?')}"
            f",rho={float(getattr(fading, 'rho', 0.0))}"
            f",p_dropout={float(getattr(fading, 'p_dropout', 0.0))})")


def _fleet_identity(names, seeds, run, etas, flat, placement, gains, data,
                    fading, population=None, cohort_size=None,
                    cohort_rounds=None, uplink_dtype="f32",
                    scenarios=None) -> dict:
    """Everything that must match for a resumed run to be bit-identical
    to the uninterrupted one: the grid, the full run config (dynamics:
    eta/batch_size/gmax/clipping), the per-scheme etas, the aggregation
    path, the placement (the bitwise contract holds per placement), and
    the physics/data — gains and dataset content hashes plus the fading
    process descriptor and the population/cohort schedule — so a resume
    against a different world is rejected, not silently mixed.  The
    ``stream`` flag is deliberately absent: overlap changes walls, never
    math, so resuming across stream modes is legal — as is ``fuse_round``
    (fused and unfused round tails agree bitwise for f32 and share the
    wire values for quantized uplinks).  ``uplink_dtype`` IS identity:
    quantization changes every trajectory.

    ``scenarios`` (a ``core.scenarios.ScenarioStack``) joins the identity
    twice: the scenario NAMES as a list (so telemetry/report can segment
    the cell axis) and the full stack digest — gains, families, dynamics
    parameters — via ``ScenarioStack.describe()``, so a thousand-cell grid
    resume against a different scenario axis is rejected, not silently
    mixed.  In scenario mode ``gains`` is None (the rows own their gains)
    and the gains digest covers the stacked [C, N] matrix instead."""
    return {"uplink_dtype": str(uplink_dtype),
            "names": list(names), "seeds": list(seeds),
            "num_rounds": run.num_rounds, "eval_every": run.eval_every,
            "eta": run.eta, "batch_size": run.batch_size, "gmax": run.gmax,
            "clip_to_gmax": bool(run.clip_to_gmax), "seed": run.seed,
            "etas": [float(e) for e in np.asarray(etas)],
            "flat": bool(flat), "placement": placement.describe(),
            "gains": _array_digest(gains if gains is not None
                                   else scenarios.gains),
            "data": _array_digest(*data),
            "fading": _fading_desc(fading),
            "population": ("none" if population is None
                           else population.describe()),
            "cohort_size": int(cohort_size or 0),
            "cohort_rounds": int(cohort_rounds or 0),
            "scenarios": ("none" if scenarios is None
                          else list(scenarios.names)),
            "scenario_world": ("none" if scenarios is None
                               else scenarios.describe())}


def _save_fleet_state(path: str, chunks_done: int, t: int, stacked,
                      params_b, fading_state, keys_b, metric_chunks,
                      evals, designs, identity: dict, pop_table=None,
                      cohorts=None) -> None:
    state = _carry_tree(jax.tree.map(np.asarray, stacked),
                        jax.tree.map(np.asarray, params_b),
                        None if fading_state is None
                        else np.asarray(fading_state),
                        np.asarray(keys_b))
    if metric_chunks:
        state["traces"] = _concat_traces(metric_chunks)
    if evals:
        state["evals_t"] = np.asarray([tt for tt, _ in evals], np.int64)
        state["evals"] = {kk: np.stack([np.asarray(ev[kk])
                                        for _, ev in evals])
                          for kk in evals[0][1]}
    if designs:
        state["designs_t"] = np.asarray([tt for tt, _ in designs], np.int64)
        state["designs_g"] = np.stack([np.asarray(g) for _, g in designs])
    if pop_table is not None:
        # the population cursor: which devices a resumed stream has seen,
        # and their Gauss-Markov states — cohort draws themselves need no
        # cursor (they re-derive from (population seed, run seed, tick))
        state["pop_last"] = pop_table["last"]
        state["pop_state"] = pop_table["state"]
    if cohorts:
        state["cohorts_t"] = np.asarray([tt for tt, _ in cohorts], np.int64)
        state["cohorts_idx"] = np.stack([np.asarray(i) for _, i in cohorts])
    ckpt.save(path, state, meta={
        "chunks_done": chunks_done, "rounds_done": t, **identity})


def _load_fleet_state(path: str, stacked, params_b, fading_state, keys_b,
                      identity: dict, adaptive: bool, pop_table=None):
    meta = ckpt.load_meta(path)
    got = {k: meta.get(k) for k in identity}
    mismatch = {k: (got[k], identity[k]) for k in identity
                if got[k] != identity[k]}
    if mismatch:
        raise ValueError(f"checkpoint {path!r} does not match this fleet "
                         f"(saved vs running): {mismatch}")
    flat = ckpt.load_flat(path)          # one read serves carry + extras
    state = ckpt.restore_flat(flat, _carry_tree(stacked, params_b,
                                                fading_state, keys_b))
    traces = {kk[len("traces/"):]: v for kk, v in flat.items()
              if kk.startswith("traces/")}
    metric_chunks = [traces] if traces else []
    evals = []
    if "evals_t" in flat:
        ev_names = [kk[len("evals/"):] for kk in flat
                    if kk.startswith("evals/")]
        evals = [(int(tt), {nm: flat[f"evals/{nm}"][i] for nm in ev_names})
                 for i, tt in enumerate(flat["evals_t"])]
    designs = None
    if adaptive:
        designs = [(int(tt), flat["designs_g"][i])
                   for i, tt in enumerate(flat["designs_t"])]
    if pop_table is not None and "pop_last" in flat:
        pop_table["last"][...] = flat["pop_last"]
        pop_table["state"][...] = flat["pop_state"]
    cohorts = None
    if "cohorts_t" in flat:
        cohorts = [(int(tt), np.asarray(flat["cohorts_idx"][i]))
                   for i, tt in enumerate(flat["cohorts_t"])]
    fstate = state["carry"].get("fstate") if fading_state is not None \
        else None
    return (int(meta["chunks_done"]), int(meta["rounds_done"]),
            state["scheme"], state["carry"]["params"], fstate,
            state["carry"]["keys"], metric_chunks, evals, designs, cohorts)


def grid_eval(eval_fn: Callable):
    """``eval_fn`` vmapped over the [K, S] cell grid and jitted, under the
    ``fl.eval`` name scope (metadata only: the program is the bare
    ``jax.jit(jax.vmap(jax.vmap(eval_fn)))``, under the same name)."""
    per_cell = jax.vmap(jax.vmap(eval_fn))

    @functools.wraps(per_cell)
    def scoped(params_b):
        with jax.named_scope("fl.eval"):
            return per_cell(params_b)

    return jax.jit(scoped)


# -- compiled fleet programs, reused across calls (DESIGN.md §Placement) ----
# JAX keys its trace cache on the function object, so a call that built
# new jitted chunks would trace, lower and fetch every chunk length again.
# These two small caches hand a call the jitted chunk and eval an earlier
# call built, under a key that holds exactly what the traced programs close
# over or branch on.  The bounds are constants: the oldest entry goes, and
# with it whatever its closures hold (an eval's test arrays).
_CHUNK_CACHE_SIZE = 8
_EVAL_CACHE_SIZE = 4
_chunk_cache: OrderedDict = OrderedDict()
_eval_cache: OrderedDict = OrderedDict()
_chunk_counts = {"hit": 0, "miss": 0}
_cache_lock = threading.Lock()


def _content_key(obj):
    """A hashable key that changes whenever ``obj``'s content does: scalars
    by type and value, arrays by digest, tuples and dataclass instances
    (every instance attribute) item by item.  TypeError for anything else,
    which has no safe content key."""
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return (type(obj), obj)
    if isinstance(obj, (np.ndarray, np.generic, jax.Array)):
        return ("array", _array_digest(obj))
    if isinstance(obj, tuple):
        return tuple(_content_key(v) for v in obj)
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return (type(obj),) + tuple((k, _content_key(v))
                                    for k, v in sorted(vars(obj).items()))
    raise TypeError(f"no content key for {type(obj).__name__}")


def _chunk_key(loss_fn, gains, run, *, uplink_dtype, flat, fuse_round,
               cohort, scenario, adaptive, kappa_sq, fading, placement):
    """The chunk cache's key: what ``make_round_body`` and
    ``placement.build_chunk`` close over or branch on.  Everything else a
    call varies (rounds, eval cadence, eta, seeds, design leaves, data,
    params) is an operand or drives only the host loop.  None where some
    part has no safe key: that call builds its own chunk."""
    try:
        key = (loss_fn,
               None if gains is None else _array_digest(gains),
               _content_key(run.batch_size), _content_key(run.clip_to_gmax),
               _content_key(run.gmax), str(uplink_dtype), bool(flat),
               bool(flat) if fuse_round is None else bool(fuse_round),
               bool(cohort), bool(scenario), bool(adaptive),
               _content_key(kappa_sq), _content_key(fading), placement)
    except TypeError:
        return None
    return key


def _cached(cache: OrderedDict, key, size: int, build: Callable):
    """(value, hit): ``cache[key]``, else ``build()`` stored under ``key``
    with the least recently used entry dropped past ``size``.  A None or
    unhashable key builds and stores nothing."""
    try:
        hash(key)
    except TypeError:
        key = None
    if key is None:
        return build(), False
    with _cache_lock:
        if key in cache:
            cache.move_to_end(key)
            return cache[key], True
    value = build()
    with _cache_lock:
        cache[key] = value
        while len(cache) > size:
            cache.popitem(last=False)
    return value, False


def chunk_cache_stats() -> dict:
    """Process-wide chunk-cache lookups (``hit``/``miss``, one per
    ``run_fleet`` call) and the entries held (``chunks``/``evals``)."""
    with _cache_lock:
        return {**_chunk_counts, "chunks": len(_chunk_cache),
                "evals": len(_eval_cache)}


def clear_chunk_cache() -> None:
    """Drop every cached chunk and eval program: the next call compiles as
    in a fresh process.  The hit/miss counts stay."""
    with _cache_lock:
        _chunk_cache.clear()
        _eval_cache.clear()


def run_fleet(loss_fn: Callable, params: PyTree, schemes, gains: np.ndarray,
              data: tuple, run, eval_fn: Optional[Callable] = None, *,
              etas=None, seeds: Optional[Sequence[int]] = None, fading=None,
              flat: bool = True, log: bool = False,
              placement: Optional[Placement] = None,
              checkpoint_path: Optional[str] = None, resume: bool = False,
              max_chunks: Optional[int] = None, population=None,
              cohort_size: Optional[int] = None,
              cohort_rounds: Optional[int] = None,
              stream: bool = True, telemetry=None,
              uplink_dtype: Optional[str] = None,
              fuse_round: Optional[bool] = None,
              scenarios=None) -> FLResult:
    """A [K-scheme x S-seed] experiment grid through a hardware placement.

    The grid/scheme/seed/eta semantics are ``engine.run_fleet``'s (which
    now delegates here): each (k, s) cell consumes the exact key/fading
    streams of a standalone run with that seed.  New driver-level knobs:

    placement        fl.placement.VmapPlacement() (default — one device,
                     bit-identical to the pre-refactor engine) or
                     ShardedPlacement(mesh) to shard the flattened cell
                     grid over a mesh.
    checkpoint_path  persist the fleet carry (params_b, fading_state,
                     keys_b, scheme design leaves, traces/evals/designs)
                     at every chunk boundary via checkpoint/checkpoint.py.
    resume           fast-forward from checkpoint_path if it exists: the
                     completed chunks are skipped and the final FLResult
                     is bit-identical to an uninterrupted run's.
    max_chunks       stop (with a checkpoint saved) after this many chunks
                     this invocation — the preemption hook sweeps and the
                     resume tests use.

    Population mode (DESIGN.md §Population):

    population       a ``scenarios.Population``: each chunk runs on a
                     drawn cohort instead of the full device set.  Data
                     shards are assigned by device index mod the shard
                     count; gains come from the population, lazily.  When
                     ``fading`` is None it defaults to the population's
                     own process (``Population.fading_process``).
    cohort_size      active devices per round, default = the schemes'
                     device count (which it must equal either way).
    cohort_rounds    redraw cadence in rounds; None = once per chunk
                     (i.e. the eval cadence).  Cohorts never straddle a
                     chunk: ``chunk_lengths`` inserts boundaries.
    stream           double-buffer staging (default True): the next
                     cohort's draw + gains + ``adaptive_sca`` cohort
                     redesign run on a host worker thread WHILE the
                     current chunk executes, so redesign latency hides
                     behind device time.  ``stream=False`` runs the same
                     stages serially — bitwise-identical results.
    telemetry        a ``telemetry.Telemetry`` (or a bare run-dir string)
                     turns on structured JSONL run tracing and the
                     in-graph bias–variance diagnostics riding
                     ``traces`` (DESIGN.md §Telemetry).  ``None``
                     (default) compiles and runs the exact pre-telemetry
                     program — bitwise, not just numerically.
    uplink_dtype     wire precision devices transmit — "f32" | "bf16" |
                     "int8" (per-device symmetric scale; DESIGN.md
                     §Kernels).  ``None`` (default) takes
                     ``run.uplink_dtype``.  Non-f32 requires ``flat``.
                     Part of the checkpoint identity: it changes the
                     numbers, so resuming across uplink dtypes is
                     rejected.
    fuse_round       force the flat round tail fused (one
                     ``ota_round_step`` launch) or unfused (the
                     historical aggregate-then-update chain); ``None`` =
                     fused exactly when ``flat``.  NOT part of the
                     checkpoint identity — with an f32 uplink the two are
                     bitwise-identical, and quantized uplinks share the
                     same wire values either way.
    scenarios        a ``core.scenarios.ScenarioStack`` of C deployments:
                     the fleet becomes the [C x K x S] grid of DESIGN.md
                     §Grid, laid out as [C*K, S] cells with the scenario
                     rows riding the cell axis.  ``schemes`` must then be
                     the scenario-major flattened list (scenario c's K
                     schemes at rows c*K..c*K+K-1 — every scenario gets
                     its own power-control designs, solved against ITS
                     gains), ``gains``/``fading`` must be None (each row
                     owns its channel world), and cell (c, k, s) is
                     bitwise the (k, s) cell of a plain fleet run on
                     scenario c alone.  ``FLResult.names`` come back as
                     "scenario/scheme"; the scenario axis joins the
                     checkpoint identity.  Exclusive with population mode
                     and adaptive (redesign_fn) schemes.

    Compiled programs outlive the call.  The jitted chunk and eval a call
    builds are kept in small process-wide caches (DESIGN.md §Placement) and
    handed to later calls whose traced programs would be the same: the
    same ``loss_fn`` and ``eval_fn`` objects, gains content, the run's
    ``batch_size``/``clip_to_gmax``/``gmax``, uplink dtype, ``flat``,
    ``fuse_round``, mode, diagnostics, fading content and placement; the
    ``fleet_config`` event says ``chunk_cache: "hit"``.  A hit traces
    nothing for the chunk lengths an earlier call ran at the same shapes
    (cells, seeds, devices, data, params): a repeat of the same sweep
    reports ``wall_compile`` 0 and writes no ``chunk_compile`` span.  A
    hit at other shapes or chunk lengths traces them on the reused chunk
    and counts them as compile, as a fresh call would.  A call with a
    part that has no safe key (an unhashable placement, a fading object
    that is not a dataclass) builds its own chunk, as every call did
    before.  ``chunk_cache_stats`` and ``clear_chunk_cache`` read and
    empty the caches.

    Adaptive schemes (``power_control.AdaptiveSCA``) re-design BETWEEN
    chunks from the live fading state, whatever the placement: the state
    gathers to host at the chunk boundary, the batched SCA solver re-solves
    per cell, and the new [K, S] design leaves ship with the next chunk.
    In population mode the redesign input is the INCOMING cohort's
    stationary statistical CSI instead (``redesign_cohort_fn`` — pure in
    the cohort gains, hence overlappable); Gauss-Markov state still
    threads through rounds via the population's re-entry table.
    """
    t0 = time.time()
    placement = placement if placement is not None else VmapPlacement()
    stacked = schemes if not isinstance(schemes, (list, tuple)) \
        else stack_schemes(schemes)
    names = tuple(getattr(stacked, "names", (stacked.name,)))
    k = len(names)
    seeds = tuple(int(s) for s in (seeds if seeds is not None
                                   else (run.seed,)))
    s_axis = len(seeds)
    if etas is None:
        etas = np.full(k, run.eta, np.float64)
    etas = np.asarray(etas, np.float64)
    if etas.shape != (k,):
        raise ValueError(f"etas shape {etas.shape} != ({k},)")
    # resolve here (not just in make_round_body): the checkpoint identity
    # must record the wire precision actually used
    if uplink_dtype is None:
        uplink_dtype = getattr(run, "uplink_dtype", "f32") or "f32"

    redesign = getattr(stacked, "redesign_fn", None)
    pop_mode = population is not None
    scen_mode = scenarios is not None
    scen_b = None
    if scen_mode:
        c = len(scenarios)
        if pop_mode:
            raise ValueError("scenario grids and population mode are "
                             "exclusive (a cohort would need per-scenario "
                             "device worlds)")
        if fading is not None:
            raise ValueError("scenario grids own the channel process; "
                             "pass fading=None")
        if gains is not None:
            raise ValueError("scenario grids own the gains; pass gains=None")
        if redesign is not None:
            raise ValueError("adaptive (redesign_fn) schemes are not "
                             "supported on scenario grids")
        if k % c:
            raise ValueError(f"{k} stacked schemes don't tile over {c} "
                             f"scenarios (need a multiple of {c})")
        if scenarios.num_devices != _scheme_n(stacked):
            raise ValueError(
                f"scenario stack is a {scenarios.num_devices}-device world "
                f"but the schemes are designed for {_scheme_n(stacked)}")
        k_schemes = k // c
        # cell axis is scenario-major: names scope to "scenario/scheme"
        names = tuple(f"{sn}/{nm}" for sn, nm
                      in zip(np.repeat(list(scenarios.names), k_schemes),
                             names))
        scen_b = scenarios.tile_over_schemes(k_schemes)   # [K, ...] rows
    n_cohort = cohort_cadence = None
    if pop_mode:
        n_cohort = int(cohort_size) if cohort_size else _scheme_n(stacked)
        if not 0 < n_cohort <= population.size:
            raise ValueError(f"cohort size {n_cohort} not in "
                             f"[1, {population.size}]")
        if _scheme_n(stacked) != n_cohort:
            raise ValueError(
                f"schemes are designed for {_scheme_n(stacked)} devices "
                f"but the cohort draws {n_cohort} — build the power "
                f"control for the cohort-sized world")
        cohort_cadence = int(cohort_rounds) if cohort_rounds else None
        if fading is None:
            fading = population.fading_process()
    adaptive = redesign is not None and fading is not None and not pop_mode
    redesign_cohort = getattr(stacked, "redesign_cohort_fn", None)
    pop_adaptive = pop_mode and redesign_cohort is not None
    stacked = placement.prepare_schemes(stacked, s_axis,
                                        adaptive or pop_adaptive)

    tel = tlm.Telemetry(run_dir=telemetry) if isinstance(telemetry, str) \
        else telemetry
    resuming = bool(checkpoint_path and resume
                    and os.path.exists(_ckpt_file(checkpoint_path)))
    # fresh=False keeps the existing event log: the resumed process reads
    # the run id back and ``tracer.resume`` prunes the superseded suffix
    tracer = tlm.Tracer(tel.run_dir, fresh=not resuming) \
        if tel is not None and tel.trace else None
    metrics_hook = tlm.make_metrics_hook(tel.kappa_sq) \
        if tel is not None and tel.diagnostics else None

    def _span(kind, **fields):
        return tracer.span(kind, **fields) if tracer is not None \
            else contextlib.nullcontext()

    def _ctx(**fields):
        return tracer.ctx(**fields) if tracer is not None \
            else contextlib.nullcontext()

    def _build_chunk():
        round_body = make_round_body(loss_fn, gains, run, fading=fading,
                                     flat=flat, cohort=pop_mode,
                                     scenario=scen_mode,
                                     metrics_hook=metrics_hook,
                                     uplink_dtype=uplink_dtype,
                                     fuse_round=fuse_round)
        return placement.build_chunk(round_body, adaptive or pop_adaptive,
                                     cohort=pop_mode, scenario=scen_mode)

    chunk_key = _chunk_key(
        loss_fn, gains, run, uplink_dtype=uplink_dtype, flat=flat,
        fuse_round=fuse_round, cohort=pop_mode, scenario=scen_mode,
        adaptive=adaptive or pop_adaptive,
        kappa_sq=None if metrics_hook is None else tel.kappa_sq,
        fading=fading, placement=placement)
    chunk, hit = _cached(_chunk_cache, chunk_key, _CHUNK_CACHE_SIZE,
                         _build_chunk)
    with _cache_lock:
        _chunk_counts["hit" if hit else "miss"] += 1

    data = tuple(jnp.asarray(a) for a in data)
    params_b = jax.tree.map(
        lambda a: jnp.tile(jnp.asarray(a)[None, None],
                           (k, s_axis) + (1,) * jnp.ndim(a)), params)
    keys0 = jnp.stack([jax.random.PRNGKey(s) for s in seeds])      # [S, 2]
    keys_b = jnp.tile(keys0[None], (k, 1, 1))                      # [K, S, 2]
    fading_state = None
    pop_table = None
    if scen_mode:
        # each scenario row inits its own channel state from the SAME
        # per-seed salted keys a standalone fleet on that scenario uses,
        # then repeats over its schemes — cell (c, k, s) starts bitwise
        # where scenario c's plain fleet does
        init_keys = jax.vmap(
            lambda kk: jax.random.fold_in(kk, FADING_INIT_SALT))(keys0)
        state_cs = scenarios.init_grid(init_keys)                # [C, S, N]
        fading_state = jnp.repeat(state_cs, k // len(scenarios), axis=0)
    elif fading is not None and not pop_mode:
        init_keys = jax.vmap(
            lambda kk: jax.random.fold_in(kk, FADING_INIT_SALT))(keys0)
        state_s = fading.init_batch(init_keys)                     # [S, N]
        fading_state = jnp.tile(state_s[None], (k,) + (1,) * state_s.ndim)
    elif pop_mode and fading is not None:
        # cohort states are staged per chunk from the re-entry table
        pop_table = population.init_table(s_axis)

    eval_b = None
    if eval_fn is not None:
        eval_b, _ = _cached(_eval_cache, eval_fn, _EVAL_CACHE_SIZE,
                            lambda: grid_eval(eval_fn))

    designs = None
    if adaptive:
        designs = [(0, np.asarray(stacked.gamma))]
    elif pop_adaptive:
        designs = []
    cohorts = [] if pop_mode else None
    evals, metric_chunks, t = [], [], 0
    lengths = chunk_lengths(run.num_rounds, run.eval_every,
                            eval_fn is not None or adaptive or pop_adaptive,
                            cohort_cadence)
    starts = np.concatenate([[0], np.cumsum(lengths)])[:-1].astype(int)

    def _tick_of(ci: int) -> int:
        return int(starts[ci]) // cohort_cadence if cohort_cadence else ci

    n_shards = int(jnp.shape(data[0])[0]) if pop_mode else 0

    # the staging lane: devices execute queued computations in FIFO order,
    # so a redesign solve dispatched to the device running the chunk waits
    # for the whole chunk instead of overlapping it.  With more than one
    # device visible the solve runs on the LAST one (the vmap fleet only
    # occupies the first); CPU executables are identical across host
    # devices, so the lane cannot change a single bit — only walls.  Off
    # the CPU backend the solve already runs on the host (x64_scope).
    stage_dev = None
    if pop_adaptive and len(jax.devices()) > 1:
        stage_dev = jax.devices()[-1]

    def _stage(ci: int, base) -> _Staged:
        # everything here is pure in (population, seeds, tick) and the
        # schemes' static problem constants — NEVER in chunk outputs — so
        # running it concurrently with the executing chunk (stream=True)
        # cannot change any number, only walls.  The tracer ctx tags the
        # worker thread's events (the cohort redesign's ``sca_solve``)
        # with this chunk index, which is what lets ``tracer.resume``
        # prune them correctly after a preemption.
        ts = time.time()
        with _ctx(chunk=ci):
            tick = _tick_of(ci)
            idx = np.stack([population.draw_cohort(n_cohort, tick, s)
                            for s in seeds])                      # [S, N]
            gains_sn = np.stack([population.gains_of(r) for r in idx])
            cohort_b = {"gains": jnp.asarray(gains_sn),
                        "data_idx": jnp.asarray((idx % n_shards)
                                                .astype(np.int32))}
            new_stacked = None
            fresh = ci == 0 or tick != _tick_of(ci - 1)
            if pop_adaptive and fresh:
                gains_ksn = np.broadcast_to(
                    gains_sn[None], (k,) + gains_sn.shape).copy()
                if stage_dev is not None:
                    with jax.default_device(stage_dev):
                        new_stacked = redesign_cohort(base, gains_ksn)
                else:
                    new_stacked = redesign_cohort(base, gains_ksn)
        staged = _Staged(ci=ci, tick=tick, idx=idx, cohort=cohort_b,
                         stacked=new_stacked, wall=time.time() - ts)
        if tracer is not None:
            tracer.event("stage", chunk=ci, tick=tick,
                         dur=round(staged.wall, 6),
                         redesigned=new_stacked is not None)
        return staged

    identity = None
    if checkpoint_path is not None:
        identity = _fleet_identity(names, seeds, run, etas, flat, placement,
                                   gains, data, fading, population,
                                   n_cohort, cohort_cadence, uplink_dtype,
                                   scenarios)
    start_chunk = 0
    if resuming:
        (start_chunk, t, stacked, params_b, fading_state, keys_b,
         metric_chunks, evals, designs, loaded_cohorts) = _load_fleet_state(
            checkpoint_path, stacked, params_b, fading_state, keys_b,
            identity, adaptive or pop_adaptive, pop_table)
        if loaded_cohorts is not None:
            cohorts = loaded_cohorts
        if log:
            print(f"# resumed fleet from {checkpoint_path} at chunk "
                  f"{start_chunk} (round {t})")
    if tracer is not None:
        if resuming:
            # drop events from chunks the preempted process started but
            # this one will re-run, so the log describes ONE consistent
            # execution (no duplicate chunk spans after a kill+resume)
            tracer.resume(start_chunk)
        tracer.event("fleet_config", names=list(names), seeds=list(seeds),
                     num_rounds=int(run.num_rounds),
                     eval_every=int(run.eval_every),
                     placement=placement.describe(cells=k * s_axis),
                     chunks=len(lengths),
                     population=(int(population.size) if pop_mode else None),
                     cohort_size=n_cohort, cohort_rounds=cohort_cadence,
                     scenarios=(list(scenarios.names) if scen_mode
                                else None),
                     stream=bool(stream), start_chunk=start_chunk,
                     chunk_cache="hit" if hit else "miss")
    last_tick = _tick_of(start_chunk - 1) \
        if pop_mode and start_chunk > 0 else None

    executor = ThreadPoolExecutor(max_workers=1) \
        if pop_mode and stream else None
    staged = next_fut = None
    wall_stage = 0.0
    stage_walls = [] if pop_mode else None
    wall_compile = 0.0
    prev_hook, prev_compile, hook_set = None, None, False
    if tracer is not None:
        from repro.solvers import sca_jax
        prev_hook = sca_jax.set_trace_hook(
            lambda rec: tracer.event("sca_solve", **rec))
        prev_compile = tlm.set_compile_tracer(tracer)
        hook_set = True
    try:
        for ci, length in enumerate(lengths):
            if ci < start_chunk:
                continue
            if pop_mode:
                if next_fut is not None:
                    tw = time.time()
                    staged, next_fut = next_fut.result(), None
                    if tracer is not None:
                        # visible staging latency: how long the driver sat
                        # waiting on the double buffer (0 when staging hid
                        # completely behind the previous chunk)
                        tracer.event("stage_wait", chunk=staged.ci,
                                     dur=round(time.time() - tw, 6))
                if staged is None or staged.ci != ci:
                    staged = _stage(ci, stacked)
                wall_stage += staged.wall
                stage_walls.append(staged.wall)
                t_start = int(starts[ci])
                if staged.tick != last_tick:
                    last_tick = staged.tick
                    cohorts.append((t_start, staged.idx))
                    if pop_adaptive:
                        stacked = staged.stacked
                        designs.append((t_start, np.asarray(stacked.gamma)))
                    if tracer is not None:
                        rec = {"chunk": ci, "t": t_start,
                               "tick": staged.tick,
                               "cohort_size": int(staged.idx.shape[1])}
                        if pop_table is not None:
                            # per-device staleness off the re-entry table
                            # BEFORE staging touches it: rounds since each
                            # drawn device last participated (-1 = never)
                            seen = np.stack(
                                [pop_table["last"][si, staged.idx[si]]
                                 for si in range(s_axis)])
                            rec["staleness"] = np.where(
                                seen < 0, -1,
                                np.maximum(t_start - 1 - seen, 0))
                            rec["never_seen"] = int(np.sum(seen < 0))
                        tracer.event("cohort", **rec)
                if fading is not None:
                    # re-entry staging reads the table committed by the
                    # PREVIOUS chunk, so it stays serialized (it is a [N]
                    # gather + aging arithmetic — cheap by construction)
                    state_sn = np.stack([
                        population.stage_states(pop_table, si,
                                                staged.idx[si], t_start,
                                                seed=seeds[si])
                        for si in range(s_axis)])                 # [S, N]
                    fading_state = jnp.asarray(np.broadcast_to(
                        state_sn[None], (k,) + state_sn.shape))
                will_stop = (max_chunks is not None
                             and ci + 1 - start_chunk >= max_chunks
                             and ci + 1 < len(lengths))
                if executor is not None and ci + 1 < len(lengths) \
                        and not will_stop:
                    # the double buffer: stage chunk ci+1 on the worker
                    # BEFORE dispatching chunk ci, then collect it after
                    # the chunk returns — the cohort draw and SCA redesign
                    # overlap device execution instead of serializing
                    next_fut = executor.submit(_stage, ci + 1, stacked)
            with _ctx(chunk=ci):
                size0 = tlm.chunk_cache_size(chunk)
                t_call, t_call_ns = time.monotonic(), time.time_ns()
                if pop_mode:
                    params_b, fading_state, keys_b, metrics = chunk(
                        stacked, etas, params_b, fading_state, keys_b, data,
                        staged.cohort, length=length)
                elif scen_mode:
                    params_b, fading_state, keys_b, metrics = chunk(
                        stacked, etas, params_b, fading_state, keys_b, data,
                        scen_b, length=length)
                else:
                    params_b, fading_state, keys_b, metrics = chunk(
                        stacked, etas, params_b, fading_state, keys_b, data,
                        length=length)
                t_ret, t_ret_ns = time.monotonic(), time.time_ns()
                size1 = tlm.chunk_cache_size(chunk)
                t_ex, t_ex_ns = t_call, t_call_ns
                if size0 is not None and size1 > size0:
                    # a call that grows the compile cache traces, lowers
                    # and compiles (or fetches) before it dispatches, and
                    # dispatch is async: its wall is the compile, and the
                    # chunk's execution starts where it returns
                    wall_compile += t_ret - t_call
                    t_ex, t_ex_ns = t_ret, t_ret_ns
                    if tracer is not None:
                        pad = getattr(chunk, "_pad_frac", None)
                        frac = pad() if pad is not None else None
                        extra = {} if frac is None \
                            else {"padded_frac": round(frac, 6)}
                        tracer.event("chunk_compile",
                                     dur=round(t_ret - t_call, 6),
                                     t0_ns=t_call_ns, t1_ns=t_ret_ns,
                                     length=int(length), cache_size=size1,
                                     **extra)
                if tracer is not None:
                    # the block makes dur the true device wall (dispatch is
                    # async); telemetry-off keeps the async pipeline as-is
                    jax.block_until_ready(params_b)
                    tracer.event("chunk_exec", chunk=ci, length=int(length),
                                 t_start=t, cache_size=size1,
                                 dur=round(time.monotonic() - t_ex, 6),
                                 t0_ns=t_ex_ns)
            metric_chunks.append(metrics)
            t += length
            if pop_mode and fading is not None:
                # scheme rows share keys, so states agree across K: commit
                # row 0 of the [K, S, N] state per seed
                fs = np.asarray(fading_state)
                for si in range(s_axis):
                    population.commit_states(pop_table, si, staged.idx[si],
                                             t - 1, fs[0, si])
            if adaptive and t < run.num_rounds:
                # gather the live state to host first: the re-design solve
                # must see one replicated array, not a mesh-sharded one, so
                # the new design is bitwise the same whatever placement ran
                # the chunk
                with _ctx(chunk=ci), _span("redesign", chunk=ci, t=t):
                    stacked = redesign(stacked, fading,
                                       np.asarray(fading_state))
                designs.append((t, np.asarray(stacked.gamma)))
            if eval_b is not None:
                with _ctx(chunk=ci), _span("eval", chunk=ci, t=t - 1):
                    ev = {kk: np.asarray(v)
                          for kk, v in eval_b(params_b).items()}
                evals.append((t - 1, ev))
                if log:
                    lead = next(iter(ev))
                    print({"round": t - 1,
                           **{n: round(float(ev[lead][i, 0]), 4)
                              for i, n in enumerate(names)}})
            if checkpoint_path is not None:
                with _span("ckpt_save", chunk=ci):
                    _save_fleet_state(checkpoint_path, ci + 1, t, stacked,
                                      params_b, fading_state, keys_b,
                                      metric_chunks, evals, designs, identity,
                                      pop_table, cohorts)
            if max_chunks is not None and ci + 1 - start_chunk >= max_chunks \
                    and ci + 1 < len(lengths):
                break        # preempted on purpose; resume=True continues
    finally:
        if hook_set:
            sca_jax.set_trace_hook(prev_hook)
            tlm.set_compile_tracer(prev_compile)
        if executor is not None:
            executor.shutdown(wait=True)

    wall = time.time() - t0
    if tracer is not None:
        tracer.event("run_end", rounds_done=int(t),
                     chunks_done=(ci + 1 if lengths else 0),
                     wall_s=round(wall, 3), wall_stage=round(wall_stage, 3))
    return FLResult(params=params_b, traces=_concat_traces(metric_chunks),
                    evals=evals, names=names, seeds=seeds, wall=wall,
                    wall_compile=wall_compile, wall_exec=wall - wall_compile,
                    fading_state=fading_state, designs=designs,
                    wall_stage=wall_stage, cohorts=cohorts,
                    stage_walls=stage_walls,
                    scenario_names=(scenarios.names if scen_mode else None))


def _scheme_names(schemes) -> list:
    if isinstance(schemes, (list, tuple)):
        return [pc.name for pc in schemes]
    return list(getattr(schemes, "names", (schemes.name,)))


def resolve_task_bundle(task, run, *, task_data=None, params=None,
                        eval_fn=None, seed=None, data_kw=None):
    """Default resolution shared by every task-first entry point
    (``run_fleet_task`` here, ``fl.server.run_fl_task``) so the
    load-bearing conventions live in ONE place: run = task.run_config()
    unless given, and seed = run.seed feeds BOTH build_data and the
    param-init PRNGKey — the historical wiring the paper_mlp bit-identity
    contract pins.  Returns (run, task_data, params, eval_fn)."""
    run = run if run is not None else task.run_config()
    seed = run.seed if seed is None else seed
    td = task_data if task_data is not None \
        else task.build_data(seed, **(data_kw or {}))
    if params is None:
        params = task.init_params(seed)
    if eval_fn is None:
        eval_fn = task.make_eval(td)
    return run, td, params, eval_fn


def run_fleet_task(task, schemes, gains: np.ndarray, run=None, *,
                   task_data=None, params: Optional[PyTree] = None,
                   eval_fn: Optional[Callable] = None, etas=None,
                   seed: Optional[int] = None, data_kw: Optional[dict] = None,
                   **driver_kw) -> FLResult:
    """Task-first fleet entry point (DESIGN.md §Tasks).

    ``task`` is any object honouring the ``repro.tasks.base.Task``
    contract (duck-typed — the fl layer never imports the registry): the
    workload's data / param-init / loss / eval and its preferred run
    config all come from the bundle, so callers only supply the wireless
    side (``schemes``, ``gains``) and placement/checkpoint knobs.

    Defaults resolve exactly like the pre-task hand-wired path, so
    ``paper_mlp`` through here is bit-identical to
    ``run_fleet(mlp.mlp_loss, init_params(...), ...)``:

    run        task.run_config() unless given.
    seed       run.seed unless given — feeds BOTH build_data and the
               param-init PRNGKey, the historical convention.
    task_data  a pre-built TaskData (skip build_data — e.g. to share one
               materialized dataset across placements or scheme grids).
    params     explicit initial params (skip task.init_params).
    eval_fn    explicit eval (else task.make_eval on the built data).
    etas       per-scheme step sizes [K]; defaults to the task's
               grid-searched ``scheme_etas`` with run.eta as fallback.
    data_kw    extra kwargs for build_data (e.g. steps= for LM tasks).

    Everything else (``seeds``, ``fading``, ``flat``, ``placement``,
    ``checkpoint_path``, ``resume``, ``max_chunks``, ``log``) passes
    through to :func:`run_fleet`.
    """
    run, td, params, eval_fn = resolve_task_bundle(
        task, run, task_data=task_data, params=params, eval_fn=eval_fn,
        seed=seed, data_kw=data_kw)
    if etas is None:
        etas = [task.eta_for(n, run.eta) for n in _scheme_names(schemes)]
    return run_fleet(task.loss_fn, params, schemes, gains, td.train, run,
                     eval_fn, etas=etas, **driver_kw)
