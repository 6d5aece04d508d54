"""The control — the reference computed in bfloat16, put in the
program's place — fails the comparison that decides ``correct``, at a
size a test run holds; the f32 reference against itself passes."""

import jax
import jax.numpy as jnp
import pytest

import tiny
from bench import check, harness, reference, sweep


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    bench = tiny.make_root(str(tmp_path_factory.mktemp("control")))
    cell = sweep.load_cell("tiny_mlp", bench)
    world = sweep.make_world(cell)
    data, params0 = sweep.make_inputs(cell, 5)
    tr = cell.traffic
    picks = sweep.check_cells(5, len(world.schemes), tr["seeds_per_sweep"],
                              harness.CHECK_CELLS)

    def sim(**kw):
        return reference.simulate(
            cell.model, cell.config, data, params0,
            reference.cell_rows(world.coeffs, world.fading, world.etas,
                                [r for r, _ in picks]),
            [sweep.fleet_seeds(5, 0, tr["seeds_per_sweep"])[s]
             for _, s in picks],
            rounds=tr["rounds"], every=tr["eval_every"],
            batch=tr["batch_size"], gmax=cell.config["gmax"], **kw)

    return cell, sim, jax.device_get(params0)


def test_control_is_not_correct(setup):
    cell, sim, p0 = setup
    ref = sim()
    ctl = sim(dtype=jnp.bfloat16, precision=None)
    ok, checks = check.verdict(check.numbers(ctl, ref, p0),
                               cell.limits["limits"])
    assert not ok
    assert checks["delta"]["value"] > checks["delta"]["limit"]


def test_reference_against_itself_is_correct(setup):
    cell, sim, p0 = setup
    ref = sim()
    ok, checks = check.verdict(check.numbers(ref, ref, p0),
                               cell.limits["limits"])
    assert ok and all(c["value"] == 0 for c in checks.values())
