"""Scenario-axis grid fleet (DESIGN.md §Grid).

Contract under test:

  * ``ScenarioStack`` rows reproduce the standalone ``FadingProcess`` for
    the row's (family, dynamics) BITWISE — init and step, including the
    Gauss-Markov state and dropout masks — even in a family-heterogeneous
    stack where vmap turns the per-row ``lax.switch`` into a select over
    every branch.
  * A [C x K x S] grid run (``run_fleet(..., scenarios=stack)``) is
    bitwise identical, cell for cell, to C separate per-scenario fleet
    runs: params, traces, evals.  In particular the C=1 grid IS today's
    fleet.
  * ShardedPlacement on the debug mesh reproduces the vmap grid per cell:
    key-stream traces bitwise, float traces/evals to the usual reduction
    tolerance (the same parity contract test_placement pins for plain
    fleets).
  * Mid-grid kill-and-resume is bitwise, and a resume against a DIFFERENT
    scenario axis (same scenario names, different realized gains) is
    rejected via the checkpoint identity.
  * Carry donation (params_b/fstate_b/keys_b) emits no donation warnings
    on either placement, and the sharded chunk reports its padded-cell
    fraction in ``chunk_compile`` telemetry and ``describe(cells=...)``.

The sharded tests need >= 4 host devices
(XLA_FLAGS=--xla_force_host_platform_device_count=8; the CI ``grid-smoke``
job forces them) and skip otherwise.
"""
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core import power_control as pcm, scenarios as scn
from repro.data import partition, synthetic
from repro.fl import driver
from repro.fl.placement import ShardedPlacement, VmapPlacement
from repro.fl.server import FLRunConfig
from repro.launch.mesh import make_debug_mesh
from repro.models import mlp
from repro.models.param import init_params

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

# heterogeneous on purpose: an i.i.d. Rician row, a Gauss-Markov row and a
# dropout row exercise three different switch branches in ONE stack
SCENS = ("disk_rician", "disk_markov", "disk_dropout")
SCHEMES = ("sca", "zero_bias")
HIDDEN = 16


@pytest.fixture(scope="module")
def grid_world():
    x, y, xt, yt = synthetic.mnist_like(40, seed=0)
    data = partition.stack_shards(partition.partition_by_label(x, y, 10,
                                                               seed=0))
    params0 = init_params(mlp.mlp_defs(hidden=HIDDEN), jax.random.PRNGKey(0))
    xt_j, yt_j = jnp.asarray(xt), jnp.asarray(yt)
    ev = jax.jit(lambda p: {"acc": mlp.accuracy(p, xt_j, yt_j)})
    run = FLRunConfig(eta=0.05, num_rounds=7, eval_every=3, seed=0,
                      batch_size=0)
    return data, params0, ev, run


def _scenario_pcs(name, seed=0):
    sc = scn.get_scenario(name)
    dep = scn.realize(sc, seed=seed)
    prm = scn.make_ota_params(dep, d=10000, gmax=10.0, eta=0.05,
                              kappa_sq=4.0)
    return sc, dep, [pcm.make_power_control(s, dep, prm) for s in SCHEMES]


def _grid_inputs(scens=SCENS, seed=0):
    stack = scn.stack_scenarios(scens, seed=seed)
    flat_pcs = []
    for name in scens:
        flat_pcs += _scenario_pcs(name, seed=seed)[2]
    return stack, flat_pcs


def _run_grid(world, stack, flat_pcs, **kw):
    data, params0, ev, run = world
    kw.setdefault("etas", [run.eta] * len(flat_pcs))
    kw.setdefault("seeds", (0, 1))
    return driver.run_fleet(mlp.mlp_loss, params0, flat_pcs, None, data,
                            run, ev, flat=True, scenarios=stack, **kw)


def _leaves_equal(a, b):
    return all(bool(np.array_equal(np.asarray(x), np.asarray(y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# stack vs standalone FadingProcess (the lax.switch union)
# ---------------------------------------------------------------------------

def test_stack_rows_match_fading_processes_bitwise():
    names = ["disk_rayleigh", "disk_rician", "disk_markov", "disk_dropout",
             "disk_nakagami"]
    stack = scn.stack_scenarios(names, seed=0)
    key = jax.random.PRNGKey(7)
    init_keys = jnp.stack(
        [jax.random.fold_in(jax.random.PRNGKey(s), 0x5CE7A810)
         for s in (0, 1)])
    state = stack.init_grid(init_keys)                       # [C, S, N]
    step_grid = jax.jit(jax.vmap(
        lambda row, st: jax.vmap(row.step, in_axes=(0, None))(st, key)))
    st2, h2 = step_grid(stack, state)
    for c, name in enumerate(names):
        sc = scn.get_scenario(name)
        dep = scn.realize(sc, seed=0)
        fp = scn.make_fading_process(dep, sc.dynamics)
        st_ref = fp.init_batch(init_keys)
        assert bool(jnp.all(state[c] == st_ref)), f"{name}: init"
        str_, hr = jax.jit(jax.vmap(lambda st: fp.step(st, key)))(st_ref)
        assert bool(jnp.all(st2[c] == str_)), f"{name}: state"
        assert bool(jnp.all(h2[c] == hr)), f"{name}: h"


def test_stack_builder_validation():
    import dataclasses as dc
    with pytest.raises(ValueError, match="at least one"):
        scn.stack_deployments([])
    sc = scn.get_scenario("disk_nakagami")
    dep = scn.realize(sc, seed=0)
    with pytest.raises(ValueError, match="nakagami"):
        scn.stack_deployments([dep], [scn.DynamicsSpec(rho=0.9)])
    shrunk = dc.replace(dep, gains=dep.gains[:5])
    with pytest.raises(ValueError, match="device count"):
        scn.stack_deployments([dep, shrunk])


def test_row_and_tile_layout():
    stack = scn.stack_scenarios(SCENS, seed=0)
    tiled = stack.tile_over_schemes(2)
    assert np.asarray(tiled.gains).shape[0] == len(SCENS) * 2
    # scenario-major: rows 2c and 2c+1 are scenario c
    for c in range(len(SCENS)):
        for j in (0, 1):
            assert np.array_equal(np.asarray(tiled.gains)[2 * c + j],
                                  np.asarray(stack.gains)[c])
    one = stack.row(1)
    assert one.names == (SCENS[1],)
    assert np.array_equal(np.asarray(one.gains)[0],
                          np.asarray(stack.gains)[1])


# ---------------------------------------------------------------------------
# grid fleet vs per-scenario fleets (vmap)
# ---------------------------------------------------------------------------

def test_grid_matches_per_scenario_fleets_bitwise(grid_world):
    data, params0, ev, run = grid_world
    stack, flat_pcs = _grid_inputs()
    grid = _run_grid(grid_world, stack, flat_pcs)
    assert grid.scenario_names == SCENS
    assert grid.names == tuple(f"{s}/{k}" for s in SCENS for k in SCHEMES)
    k_schemes = len(SCHEMES)
    for c, name in enumerate(SCENS):
        sc, dep, pcs = _scenario_pcs(name)
        fp = scn.make_fading_process(dep, sc.dynamics)
        res = driver.run_fleet(mlp.mlp_loss, params0, pcs, dep.gains, data,
                               run, ev, etas=[run.eta] * k_schemes,
                               seeds=(0, 1), flat=True, fading=fp)
        for ki in range(k_schemes):
            row = c * k_schemes + ki
            for lg, lr in zip(jax.tree.leaves(grid.params),
                              jax.tree.leaves(res.params)):
                assert np.array_equal(np.asarray(lg)[row],
                                      np.asarray(lr)[ki]), (name, ki)
            for tr in grid.traces:
                assert np.array_equal(grid.traces[tr][row],
                                      res.traces[tr][ki]), (name, ki, tr)
            for (tg, eg), (tr_, er) in zip(grid.evals, res.evals):
                assert tg == tr_
                assert np.array_equal(np.asarray(eg["acc"])[row],
                                      np.asarray(er["acc"])[ki]), (name, ki)


def test_c1_grid_is_todays_fleet_bitwise(grid_world):
    """The single-scenario slice of the grid machinery IS the plain fleet:
    a C=1 grid and a scenarios=None run produce identical bits."""
    data, params0, ev, run = grid_world
    name = SCENS[1]                                   # the stateful one
    stack, flat_pcs = _grid_inputs(scens=(name,))
    grid = _run_grid(grid_world, stack, flat_pcs)
    sc, dep, pcs = _scenario_pcs(name)
    fp = scn.make_fading_process(dep, sc.dynamics)
    res = driver.run_fleet(mlp.mlp_loss, params0, pcs, dep.gains, data, run,
                           ev, etas=[run.eta] * len(pcs), seeds=(0, 1),
                           flat=True, fading=fp)
    assert _leaves_equal(grid.params, res.params)
    assert set(grid.traces) == set(res.traces)
    for tr in grid.traces:
        assert np.array_equal(grid.traces[tr], res.traces[tr]), tr
    for (tg, eg), (tr_, er) in zip(grid.evals, res.evals):
        assert tg == tr_ and np.array_equal(np.asarray(eg["acc"]),
                                            np.asarray(er["acc"]))


def test_grid_input_validation(grid_world):
    data, params0, ev, run = grid_world
    stack, flat_pcs = _grid_inputs()
    with pytest.raises(ValueError, match="tile over"):
        _run_grid(grid_world, stack, flat_pcs[:-1],
                  etas=[run.eta] * (len(flat_pcs) - 1))
    with pytest.raises(ValueError, match="own the gains"):
        driver.run_fleet(mlp.mlp_loss, params0, flat_pcs,
                         np.ones(10), data, run, ev,
                         etas=[run.eta] * len(flat_pcs), flat=True,
                         scenarios=stack)
    fp = scn.make_fading_process(scn.realize(scn.get_scenario(SCENS[0]),
                                             seed=0),
                                 scn.get_scenario(SCENS[0]).dynamics)
    with pytest.raises(ValueError, match="channel process"):
        _run_grid(grid_world, stack, flat_pcs, fading=fp)


# ---------------------------------------------------------------------------
# checkpointed resume on the grid
# ---------------------------------------------------------------------------

def test_grid_kill_and_resume_bitwise(grid_world, tmp_path):
    stack, flat_pcs = _grid_inputs()
    cp = os.path.join(tmp_path, "grid")
    full = _run_grid(grid_world, stack, flat_pcs,
                     checkpoint_path=os.path.join(tmp_path, "full"))
    _run_grid(grid_world, stack, flat_pcs, checkpoint_path=cp, max_chunks=1)
    res = _run_grid(grid_world, stack, flat_pcs, checkpoint_path=cp,
                    resume=True)
    assert _leaves_equal(full.params, res.params)
    for tr in full.traces:
        assert np.array_equal(full.traces[tr], res.traces[tr]), tr
    for (tf, ef), (tr_, er) in zip(full.evals, res.evals):
        assert tf == tr_ and np.array_equal(np.asarray(ef["acc"]),
                                            np.asarray(er["acc"]))


def test_grid_resume_rejects_scenario_axis_mismatch(grid_world, tmp_path):
    """Same scenario NAMES, different realized world (seed) — only the
    gains digest and ScenarioStack descriptor differ, and the identity
    check must still refuse to mix them."""
    cp = os.path.join(tmp_path, "grid")
    stack, flat_pcs = _grid_inputs(seed=0)
    _run_grid(grid_world, stack, flat_pcs, checkpoint_path=cp, max_chunks=1)
    stack2, flat_pcs2 = _grid_inputs(seed=1)
    with pytest.raises(ValueError, match="does not match"):
        _run_grid(grid_world, stack2, flat_pcs2, checkpoint_path=cp,
                  resume=True)


# ---------------------------------------------------------------------------
# carry donation + pad-waste reporting
# ---------------------------------------------------------------------------

def test_vmap_grid_donation_emits_no_warning(grid_world):
    stack, flat_pcs = _grid_inputs()
    with warnings.catch_warnings(record=True) as wlog:
        warnings.simplefilter("always")
        _run_grid(grid_world, stack, flat_pcs)
    donation = [w for w in wlog if "donat" in str(w.message).lower()]
    assert not donation, [str(w.message) for w in donation]


@needs_mesh
def test_sharded_grid_donation_emits_no_warning(grid_world):
    stack, flat_pcs = _grid_inputs()
    pl = ShardedPlacement(make_debug_mesh(2, 2))
    with warnings.catch_warnings(record=True) as wlog:
        warnings.simplefilter("always")
        _run_grid(grid_world, stack, flat_pcs, placement=pl)
    donation = [w for w in wlog if "donat" in str(w.message).lower()]
    assert not donation, [str(w.message) for w in donation]


def test_describe_reports_pad_waste():
    assert VmapPlacement().describe(cells=12) == "vmap"
    if jax.device_count() >= 4:
        pl = ShardedPlacement(make_debug_mesh(2, 2))
        assert pl.describe() == "sharded[data=2,model=2]"
        assert pl.describe(cells=12) == "sharded[data=2,model=2," \
                                        "cells=12,pad=0/12]"
        assert pl.describe(cells=10) == "sharded[data=2,model=2," \
                                        "cells=10,pad=2/12]"


@needs_mesh
def test_sharded_chunk_compile_event_carries_padded_frac(grid_world,
                                                         tmp_path,
                                                         cold_fleet_cache):
    """[C=3, K=2, S=3] = 18 cells on a 2x2 mesh pads to 20: the compile
    telemetry must say 10% of the compiled cells are masking waste."""
    stack, flat_pcs = _grid_inputs()
    pl = ShardedPlacement(make_debug_mesh(2, 2))
    tel = telemetry.Telemetry(run_dir=str(tmp_path / "run"))
    _run_grid(grid_world, stack, flat_pcs, placement=pl, seeds=(0, 1, 2),
              telemetry=tel)
    events = telemetry.read_events(tel.run_dir)
    compiles = [e for e in events if e.get("ev") == "chunk_compile"]
    assert compiles, "no chunk_compile events recorded"
    for e in compiles:
        assert e.get("padded_frac") == pytest.approx(2 / 20)


# ---------------------------------------------------------------------------
# sharded grid parity
# ---------------------------------------------------------------------------

@needs_mesh
def test_sharded_grid_matches_vmap(grid_world):
    """[C=3, K, S] family-heterogeneous grid: key-stream traces bitwise
    across placements, float traces/evals to the reduction tolerance
    (test_placement's plain-fleet parity contract, on the grid)."""
    stack, flat_pcs = _grid_inputs()
    vres = _run_grid(grid_world, stack, flat_pcs)
    sres = _run_grid(grid_world, stack, flat_pcs,
                     placement=ShardedPlacement(make_debug_mesh(2, 2)))
    assert set(vres.traces) == set(sres.traces)
    for tr in ("active_devices", "noise_scale"):
        assert np.array_equal(vres.traces[tr], sres.traces[tr]), tr
    # Norm-derived traces drift: the per-device block size changes the
    # reduction order inside each cell's global-norm (observed 2e-4 at
    # round 0 for this world's 12.7k-param reduction), and SGD compounds
    # it to a few 1e-3 over 7 rounds.
    for tr in vres.traces:
        np.testing.assert_allclose(vres.traces[tr], sres.traces[tr],
                                   rtol=2e-2, atol=1e-6, err_msg=tr)
    assert [t for t, _ in vres.evals] == [t for t, _ in sres.evals]
    for (_, ea), (_, eb) in zip(vres.evals, sres.evals):
        np.testing.assert_allclose(np.asarray(ea["acc"]),
                                   np.asarray(eb["acc"]), rtol=1e-5,
                                   atol=3e-3)


@needs_mesh
def test_sharded_grid_kill_and_resume_bitwise(grid_world, tmp_path):
    stack, flat_pcs = _grid_inputs()
    pl = ShardedPlacement(make_debug_mesh(2, 2))
    full = _run_grid(grid_world, stack, flat_pcs, placement=pl)
    cp = os.path.join(tmp_path, "sgrid")
    _run_grid(grid_world, stack, flat_pcs, placement=pl,
              checkpoint_path=cp, max_chunks=1)
    res = _run_grid(grid_world, stack, flat_pcs, placement=pl,
                    checkpoint_path=cp, resume=True)
    assert _leaves_equal(full.params, res.params)
    for tr in full.traces:
        assert np.array_equal(full.traces[tr], res.traces[tr]), tr


# ---------------------------------------------------------------------------
# engine-level guards
# ---------------------------------------------------------------------------

def test_round_body_scenario_exclusions():
    from repro.fl import engine as eng
    run = FLRunConfig(eta=0.05, num_rounds=2, eval_every=2)
    with pytest.raises(ValueError, match="exclusive"):
        eng.make_round_body(mlp.mlp_loss, None, run, scenario=True,
                            cohort=True)
    fp = scn.make_fading_process(
        scn.realize(scn.get_scenario("disk_rayleigh"), seed=0),
        scn.DynamicsSpec())
    with pytest.raises(ValueError, match="fading=None"):
        eng.make_round_body(mlp.mlp_loss, None, run, scenario=True,
                            fading=fp)


# ---------------------------------------------------------------------------
# report rendering: the bias-variance trajectory segments per scenario
# ---------------------------------------------------------------------------

def test_report_segments_bias_variance_per_scenario(grid_world, tmp_path,
                                                    capsys):
    """A telemetry-enabled grid run's checkpoint carries the scenario
    axis; the report tool must group the bv_* trajectory per scenario
    with the per-cell scheme labels stripped of their scope prefix."""
    stack, flat_pcs = _grid_inputs()
    cp = os.path.join(tmp_path, "grid")
    _run_grid(grid_world, stack, flat_pcs, checkpoint_path=cp,
              telemetry=telemetry.Telemetry(run_dir=str(tmp_path)))
    from repro.telemetry import report as rpt
    rpt.bias_variance(cp + ".npz", 3)
    out = capsys.readouterr().out
    for name in SCENS:
        assert f"scenario {name}" in out
    assert "scheme sca" in out and "scheme zero_bias" in out
    assert "disk_rician/sca" not in out       # prefix lives on the header
