"""Placement layer of the fleet executor (DESIGN.md §Placement).

The fleet is three layers:

* **cell program** (``fl.engine``): the chunked-scan single-cell runtime —
  ``make_round_body`` + ``_scan_chunk`` — pure and placement-agnostic.
* **placement** (this module): maps the [K-scheme x S-seed] grid onto
  hardware.  ``VmapPlacement`` is the single-device path — the exact
  vmap-over-cells program the engine has always compiled, bit-identical.
  ``ShardedPlacement`` flattens the grid to a [K*S] cell axis and shards
  it over a ``("data", "model")`` mesh via ``distributed.shard_vmap``:
  cells are independent so the shard_map is psum-free, the grid is padded
  with copies of cell 0 when K*S doesn't divide the device count (padded
  outputs sliced off), and traces/evals/designs gather to host at chunk
  boundaries.
* **host driver** (``fl.driver``): the chunk loop, adaptive re-design
  hook, and checkpointed resume — consumes either placement through the
  same two-method interface.

A placement exposes:

    prepare_schemes(stacked, s_axis, adaptive) -> stacked'
        layout the stacked schemes' design leaves for this placement
        (vmap broadcasts non-adaptive designs over seeds; sharding tiles
        every leaf to the full [K, S] grid so it can flatten to cells).
    build_chunk(round_body, adaptive, cohort=False, scenario=False)
        -> chunk
        chunk(stacked, etas, params_b, fstate_b, keys_b, data, length)
        -> (params_b, fstate_b, keys_b, metrics), everything with leading
        [K, S] grid axes either way — the driver never knows where the
        cells ran.  With ``cohort=True`` the chunk takes one extra operand
        before ``length`` — the staged cohort dict with [S, N] leaves
        (per-seed active sets, shared across schemes) — and the cell
        program is the engine's cohort body (DESIGN.md §Population).
        With ``scenario=True`` the extra operand is instead a
        ``ScenarioStack`` tiled to the cell axis (leaves [K, ...], one row
        per cell) and the cell program is the engine's scenario body: the
        [C x K x S] grid is just a [C*K, S] fleet whose cells carry their
        channel world as an operand (DESIGN.md §Grid).
        Every chunk exposes ``_cache_size()`` — the number of compiled
        programs behind it (the jit trace cache here, the explicit
        per-(length, grid) dict on the sharded path) — which
        ``telemetry.assert_no_recompile`` audits and the driver reads to
        tell a call that compiled from one that only ran.  Chunks that
        pad the cell grid to the device count also expose
        ``_pad_frac()``, the fraction of compiled cells that are cell-0
        copies.

        The carry buffers (``params_b``/``fstate_b``/``keys_b``) are
        DONATED to the compiled chunk (``jax.jit(...,
        donate_argnums=(2, 3, 4))``): the chunk returns same-shaped
        replacements, so XLA aliases them in place and a big grid never
        holds two copies of every carry.  Callers must treat the passed-in
        carries as consumed — the driver's linear chunk chain already
        does.  ``donate=False`` on a placement restores the copying
        behaviour (the RSS A/B probe in benchmarks/scenario_sweep.py).
    map_batch(fn, batch_tree) -> out_tree
        generic per-row map over a leading [B] batch axis — how
        ``solvers.solve_batch`` shards thousand-scenario SCA design
        batches over the same mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro import distributed
from repro.core.power_control import tile_over_seeds
from repro.fl.engine import _scan_chunk
from repro.launch.mesh import grid_axes

PyTree = Any


class Placement:
    """Interface marker; see module docstring for the contract."""

    def prepare_schemes(self, stacked, s_axis: int, adaptive: bool):
        raise NotImplementedError

    def build_chunk(self, round_body, adaptive: bool, cohort: bool = False,
                    scenario: bool = False):
        raise NotImplementedError

    def compile_batch(self, fn):
        """Compiled per-row map over a leading [B] axis.  Callers that
        invoke the result repeatedly should hold on to it (or cache keyed
        on this placement — both placements hash stably), so the jit trace
        cache survives across calls."""
        raise NotImplementedError

    def map_batch(self, fn, batch_tree):
        return self.compile_batch(fn)(batch_tree)

    def describe(self, cells=None) -> str:
        """Stable identity string, recorded in fleet checkpoints so a
        resume on a different placement is rejected (the bitwise-resume
        contract holds per placement).  ``cells`` (the flattened grid
        size, when the caller knows it) lets padding placements report
        their cell-0 waste in the string; placements that never pad
        ignore it."""
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class VmapPlacement(Placement):
    """The single-device grid: vmap over (scheme, seed) cells.

    This is byte-for-byte the fleet program ``engine.run_fleet`` has
    always compiled — non-adaptive schemes broadcast over the seed axis
    (in_axes None), adaptive schemes tile per cell — so the refactor keeps
    the default path run-for-run identical.  ``donate=False`` disables
    carry-buffer donation (see module docstring).
    """
    donate: bool = True

    def _donate(self):
        return (2, 3, 4) if self.donate else ()

    def prepare_schemes(self, stacked, s_axis: int, adaptive: bool):
        # every (scheme, seed) cell owns its design: tile the design state
        # over the seed axis and vmap the scheme at both grid levels
        return tile_over_seeds(stacked, s_axis) if adaptive else stacked

    def build_chunk(self, round_body, adaptive: bool, cohort: bool = False,
                    scenario: bool = False):
        if cohort and scenario:
            raise ValueError("cohort and scenario chunks are exclusive")
        if scenario:
            # scenario rows ride the cell axis next to the scheme rows:
            # mapped per cell, broadcast over seeds (every seed of a cell
            # lives in the same channel world)
            def scenario_chunk(stacked, etas, params_b, fstate_b, keys_b,
                               data, scen_b, length):
                def cell(scheme, eta, params, fstate, key, sc):
                    return _scan_chunk(round_body, scheme, eta, params,
                                       fstate, key, data, length,
                                       scenario=sc)
                per_seed = jax.vmap(cell, in_axes=(0 if adaptive else None,
                                                   None, 0, 0, 0, None))
                per_cell = jax.vmap(per_seed, in_axes=(0, 0, 0, 0, 0, 0))
                return per_cell(stacked, etas, params_b, fstate_b, keys_b,
                                scen_b)

            chunk = jax.jit(scenario_chunk, static_argnames=("length",),
                            donate_argnums=self._donate())
            return chunk

        if not cohort:
            def fleet_chunk(stacked, etas, params_b, fstate_b, keys_b, data,
                            length):
                def cell(scheme, eta, params, fstate, key):
                    return _scan_chunk(round_body, scheme, eta, params,
                                       fstate, key, data, length)
                per_seed = jax.vmap(cell, in_axes=(0 if adaptive else None,
                                                   None, 0, 0, 0))
                per_cell = jax.vmap(per_seed, in_axes=(0, 0, 0, 0, 0))
                return per_cell(stacked, etas, params_b, fstate_b, keys_b)

            chunk = jax.jit(fleet_chunk, static_argnames=("length",),
                            donate_argnums=self._donate())
            return chunk

        # cohort leaves are [S, N]: per-seed active sets (each seed row
        # draws its own cohort), broadcast across the scheme axis
        def cohort_chunk(stacked, etas, params_b, fstate_b, keys_b, data,
                         cohort_b, length):
            def cell(scheme, eta, params, fstate, key, co):
                return _scan_chunk(round_body, scheme, eta, params, fstate,
                                   key, data, length, cohort=co)
            per_seed = jax.vmap(cell, in_axes=(0 if adaptive else None,
                                               None, 0, 0, 0, 0))
            per_cell = jax.vmap(per_seed, in_axes=(0, 0, 0, 0, 0, None))
            return per_cell(stacked, etas, params_b, fstate_b, keys_b,
                            cohort_b)

        chunk = jax.jit(cohort_chunk, static_argnames=("length",),
                        donate_argnums=self._donate())
        return chunk

    def compile_batch(self, fn):
        return jax.jit(jax.vmap(fn))

    def describe(self, cells=None) -> str:
        return "vmap"


@dataclasses.dataclass(frozen=True)
class ShardedPlacement(Placement):
    """Shard the flattened [K*S] cell axis over mesh axes.

    ``mesh`` is any jax Mesh (``launch.mesh.make_debug_mesh(2, 2)`` for
    the forced-8-CPU-device CI path, ``make_production_mesh()`` on real
    hardware); ``axes`` defaults to every mesh axis — fleet cells are
    independent single-device programs, so "data" and "model" both serve
    as cell slots.  Each device scans its local block of cells; results
    come back as global arrays with the grid axes restored, so the host
    driver (and its checkpoint format) is identical to the vmap path.
    ``donate=False`` disables carry-buffer donation (see module
    docstring).
    """
    mesh: Any
    axes: tuple = None  # default: every axis of ``mesh``
    donate: bool = True

    def __post_init__(self):
        if self.axes is None:
            object.__setattr__(self, "axes", grid_axes(self.mesh))

    @property
    def num_devices(self) -> int:
        return distributed.grid_devices(self.mesh, self.axes)

    def _donate(self):
        return (2, 3, 4) if self.donate else ()

    def _pad(self, cells: int):
        """(padded grid size, padded-cell fraction) for a flattened grid
        of ``cells`` rows — the cell-0 copies shard_vmap adds so the grid
        divides the device count."""
        n = self.num_devices
        gp = -(-cells // n) * n
        return gp, (gp - cells) / gp

    def prepare_schemes(self, stacked, s_axis: int, adaptive: bool):
        # sharding flattens the grid to cells, so every design leaf must
        # carry the full [K, S] axes — adaptive or not
        return tile_over_seeds(stacked, s_axis)

    def build_chunk(self, round_body, adaptive: bool, cohort: bool = False,
                    scenario: bool = False):
        if cohort and scenario:
            raise ValueError("cohort and scenario chunks are exclusive")
        compiled = {}
        pad_info = {"frac": None}

        def lookup(length, keys_b, compile_fn):
            k, s = int(keys_b.shape[0]), int(keys_b.shape[1])
            pad_info["frac"] = self._pad(k * s)[1]
            fn = compiled.get((length, k, s))
            if fn is None:
                fn = compiled[(length, k, s)] = compile_fn(
                    round_body, length, k, s)
            return fn

        if scenario:
            def scenario_chunk(stacked, etas, params_b, fstate_b, keys_b,
                               data, scen_b, length):
                fn = lookup(length, keys_b, self._compile_scenario)
                return fn(stacked, etas, params_b, fstate_b, keys_b, data,
                          scen_b)

            scenario_chunk._cache_size = lambda: len(compiled)
            scenario_chunk._pad_frac = lambda: pad_info["frac"]
            return scenario_chunk

        if not cohort:
            def chunk(stacked, etas, params_b, fstate_b, keys_b, data,
                      length):
                fn = lookup(length, keys_b, self._compile)
                return fn(stacked, etas, params_b, fstate_b, keys_b, data)

            chunk._cache_size = lambda: len(compiled)
            chunk._pad_frac = lambda: pad_info["frac"]
            return chunk

        def cohort_chunk(stacked, etas, params_b, fstate_b, keys_b, data,
                         cohort_b, length):
            fn = lookup(length, keys_b, self._compile_cohort)
            return fn(stacked, etas, params_b, fstate_b, keys_b, data,
                      cohort_b)

        cohort_chunk._cache_size = lambda: len(compiled)
        cohort_chunk._pad_frac = lambda: pad_info["frac"]
        return cohort_chunk

    def _compile(self, round_body, length: int, k: int, s: int):
        def cell(scheme, eta, params, fstate, key, data):
            return _scan_chunk(round_body, scheme, eta, params, fstate, key,
                               data, length)

        grid_call = distributed.shard_vmap(cell, self.mesh, self.axes,
                                           num_sharded=5)

        def run(stacked, etas, params_b, fstate_b, keys_b, data):
            def flat(tree):
                return jax.tree.map(
                    lambda a: jnp.reshape(a, (k * s,) + a.shape[2:]), tree)

            def unflat(tree):
                return jax.tree.map(
                    lambda a: jnp.reshape(a, (k, s) + a.shape[1:]), tree)

            etas_f = jnp.reshape(
                jnp.broadcast_to(jnp.asarray(etas)[:, None], (k, s)), (k * s,))
            out = grid_call(flat(stacked), etas_f, flat(params_b),
                            flat(fstate_b), flat(keys_b), data)
            return unflat(out)

        return jax.jit(run, donate_argnums=self._donate())

    def _compile_scenario(self, round_body, length: int, k: int, s: int):
        # scenario rows are per CELL ([K, ...] leaves, K = C*schemes): tile
        # over the seed axis and flatten to the same [K*S] cell axis as the
        # carry, so each cell ships its channel world through the mesh
        def cell(scheme, eta, params, fstate, key, sc, data):
            return _scan_chunk(round_body, scheme, eta, params, fstate, key,
                               data, length, scenario=sc)

        grid_call = distributed.shard_vmap(cell, self.mesh, self.axes,
                                           num_sharded=6)

        def run(stacked, etas, params_b, fstate_b, keys_b, data, scen_b):
            def flat(tree):
                return jax.tree.map(
                    lambda a: jnp.reshape(a, (k * s,) + a.shape[2:]), tree)

            def unflat(tree):
                return jax.tree.map(
                    lambda a: jnp.reshape(a, (k, s) + a.shape[1:]), tree)

            etas_f = jnp.reshape(
                jnp.broadcast_to(jnp.asarray(etas)[:, None], (k, s)), (k * s,))
            scen_f = jax.tree.map(
                lambda a: jnp.reshape(
                    jnp.broadcast_to(jnp.asarray(a)[:, None],
                                     (k, s) + jnp.shape(a)[1:]),
                    (k * s,) + jnp.shape(a)[1:]), scen_b)
            out = grid_call(flat(stacked), etas_f, flat(params_b),
                            flat(fstate_b), flat(keys_b), scen_f, data)
            return unflat(out)

        return jax.jit(run, donate_argnums=self._donate())

    def _compile_cohort(self, round_body, length: int, k: int, s: int):
        # the [S, N] cohort leaves tile across the scheme axis and flatten
        # to the same [K*S] cell axis as the carry, so each cell ships its
        # own active set through the mesh (padded with cell 0 like every
        # other sharded operand when K*S doesn't divide the device count)
        def cell(scheme, eta, params, fstate, key, co, data):
            return _scan_chunk(round_body, scheme, eta, params, fstate, key,
                               data, length, cohort=co)

        grid_call = distributed.shard_vmap(cell, self.mesh, self.axes,
                                           num_sharded=6)

        def run(stacked, etas, params_b, fstate_b, keys_b, data, cohort_b):
            def flat(tree):
                return jax.tree.map(
                    lambda a: jnp.reshape(a, (k * s,) + a.shape[2:]), tree)

            def unflat(tree):
                return jax.tree.map(
                    lambda a: jnp.reshape(a, (k, s) + a.shape[1:]), tree)

            etas_f = jnp.reshape(
                jnp.broadcast_to(jnp.asarray(etas)[:, None], (k, s)), (k * s,))
            cohort_f = jax.tree.map(
                lambda a: jnp.reshape(
                    jnp.broadcast_to(jnp.asarray(a)[None],
                                     (k,) + jnp.shape(a)),
                    (k * s,) + jnp.shape(a)[1:]), cohort_b)
            out = grid_call(flat(stacked), etas_f, flat(params_b),
                            flat(fstate_b), flat(keys_b), cohort_f, data)
            return unflat(out)

        return jax.jit(run, donate_argnums=self._donate())

    def compile_batch(self, fn):
        return jax.jit(distributed.shard_vmap(fn, self.mesh, self.axes))

    def describe(self, cells=None) -> str:
        shape = ",".join(f"{a}={self.mesh.shape[a]}" for a in self.axes)
        if cells is None:
            return f"sharded[{shape}]"
        gp, _ = self._pad(int(cells))
        return f"sharded[{shape},cells={int(cells)},pad={gp - int(cells)}/{gp}]"
