"""FLOP and byte counts, seeds, cell sampling and the readers' arithmetic."""
import json
import os
import types

import pytest

from bench import peaks, sweep

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _mod(name):
    return sweep.load_module(os.path.join(BENCH, "configs", name + ".py"),
                             "test_cfg_" + name)


def test_mlp_flops_are_three_dense_passes():
    cfg = _cfg("paper_mlp")
    assert _mod("paper_mlp").flops_per_sample(cfg) == \
        3 * 2 * (784 * 1024 + 1024 * 10)


@pytest.mark.parametrize("name", ["paper_mlp", "paper_mlp_grid4"])
def test_config_param_dim_matches_its_shapes(name):
    import numpy as np
    cfg = _cfg(name)
    dims = _mod(name).shapes(cfg).values()
    assert sum(int(np.prod(s)) for s in dims) == cfg["param_dim"]


def test_round_step_bytes_per_wire():
    assert peaks.round_step_bytes(10, 814090, "f32") == 13 * 814090 * 4
    assert peaks.round_step_bytes(10, 100, "int8") == 10 * 100 + 1200
    with pytest.raises(ValueError):
        peaks.chip_peaks("cpu")


def test_seeds_are_stable_for_large_and_distinct_runs():
    big = 2 ** 31 + 12345
    assert sweep.fleet_seeds(big, 0, 8) == sweep.fleet_seeds(big, 0, 8)
    assert sweep.fleet_seeds(big, 0, 8) != sweep.fleet_seeds(big, 1, 8)
    assert sweep.fleet_seeds(big, 0, 8) != \
        sweep.fleet_seeds(big, 0, 8, warmup=True)
    assert all(0 <= s < 2 ** 31 for s in sweep.fleet_seeds(big, 3, 16))
    assert 0 <= sweep.data_seed(big) < 2 ** 31


def test_check_cells_cover_every_chip_block():
    picks = sweep.check_cells(99, 12, 16, 4)
    blocks = sorted((r * 16 + s) // 48 for r, s in picks)
    assert blocks == [0, 1, 2, 3]


@pytest.mark.parametrize("seed, picks", [
    (1, [(0, 8), (2, 1), (3, 6), (5, 3)]),
    (99, [(1, 2), (3, 1), (3, 8), (6, 11)]),
    (2 ** 31 + 12345, [(1, 8), (2, 3), (4, 1), (5, 8)]),
    (3000000001, [(1, 4), (3, 1), (4, 9), (5, 3)]),
])
def test_check_cells_of_the_84_cell_grid_are_pinned(seed, picks):
    # the picks of the 7 x 12 grids of both cells, as every run since the
    # benchmark began has drawn them
    assert sweep.check_cells(seed, 7, 12, 4) == picks


@pytest.mark.parametrize("rows, seeds", [(1, 2), (2, 1), (1, 3)])
def test_check_cells_of_a_small_grid_are_every_cell(rows, seeds):
    picks = sweep.check_cells(5, rows, seeds, 4)
    assert sorted(picks) == [(r, s) for r in range(rows)
                             for s in range(seeds)]


def _reader(name):
    return sweep.load_module(os.path.join(BENCH, "metrics", name + ".py"),
                             "test_metric_" + name.replace(".", "_"))


def test_step_mfu_arithmetic():
    cfg = _cfg("paper_mlp")
    cell = types.SimpleNamespace(config=cfg, model=_mod("paper_mlp"),
                                 traffic={"batch_size": 128})
    ctx = types.SimpleNamespace(cell=cell, window_s=2.0, sweeps=1, cells=56,
                                rounds=150, chips=1,
                                peaks=peaks.chip_peaks("TPU v5 lite"))
    flops = 56 * 150 * 10 * 128 * 3 * 2 * (784 * 1024 + 1024 * 10)
    assert _reader("model.step_mfu").read(ctx) == \
        pytest.approx(100 * flops / (2.0 * 197e12))
    cell.traffic = {"batch_size": 0}           # full batch: 1000 a device
    assert _reader("model.step_mfu").read(ctx) == \
        pytest.approx(100 * flops / 128 * 1000 / (2.0 * 197e12))
    # a configuration module that counts its own samples per device
    counts = []

    def samples_per_device(cfg, batch):
        counts.append(batch)
        return 7
    cell.model = types.SimpleNamespace(
        flops_per_sample=cell.model.flops_per_sample,
        samples_per_device=samples_per_device)
    assert _reader("model.step_mfu").read(ctx) == \
        pytest.approx(100 * flops / 128 * 7 / (2.0 * 197e12))
    assert counts == [0]


def test_roofline_arithmetic():
    cfg = _cfg("paper_mlp")
    cell = types.SimpleNamespace(config=cfg, traffic={"uplink": "f32"})
    dev = "/device:TPU:0"
    ops = [{"plane": dev, "line": "XLA Ops", "name": "ota_round_step",
            "start": 0.0, "dur": 1e9, "stats": {}}]
    ctx = types.SimpleNamespace(cell=cell, planes=[dev], ops={dev: ops},
                                sweeps=2, cells=56, rounds=150,
                                peaks=peaks.chip_peaks("TPU v5 lite"))
    need = 2 * 56 * 150 * 13 * 814090 * 4
    assert _reader("kernel.ota_round_step_roofline").read(ctx) == \
        pytest.approx(100 * need / 819e9)
    ctx.ops = {dev: []}
    assert _reader("kernel.ota_round_step_roofline").read(ctx) is None
    assert _reader("kernel.ota_round_step_busy_share").read(ctx) is None
