"""The readers of device time by name scope."""
import os
import types

import pytest

from bench import scopes, sweep

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def ctx_of(sweeps=2, rounds=150, planes=(), chips=1, busy_s=None):
    logged = []
    ctx = types.SimpleNamespace(
        sweeps=sweeps, rounds=rounds, planes=list(planes), chips=chips,
        busy_s=busy_s,
        cell=types.SimpleNamespace(root="/nonexistent", name="cell"),
        log=logged.append)
    return ctx, logged


def reader(name):
    return sweep.load_module(os.path.join(BENCH, "metrics", name + ".py"),
                             "test_metric_" + name.replace(".", "_"))


@pytest.mark.parametrize("stack, scope", [
    ("jit(fleet_chunk)/vmap(vmap())/while/body/fl.grad/dot_general",
     "fl.grad"),
    ("jit(fleet_chunk)/while/body/fl.step/jit(ota_round_step)/fl.uplink/pad",
     "fl.uplink"),
    ("jit(fleet_chunk)/while/body/transpose(fl.grad)/mul", "fl.grad"),
    ("jit(evaluate)/fl.eval/vmap(vmap(evaluate))/dot_general", "fl.eval"),
    ("jit(fleet_chunk)/while/body/closed_call/add", None),
    ("jit(f)/self.grad/add", None),
    ("", None),
    (None, None),
])
def test_innermost_scope_of_a_name_stack(stack, scope):
    assert scopes.scope_of(stack) == scope


def test_datatable_rows():
    table = {"cols": [{"id": "hlo_op_name"}, {"id": "tf_op_name"},
                      {"id": "total_self_time"}],
             "rows": [{"c": [{"v": "fusion.1"}, {"v": "a/fl.grad/b"},
                             {"v": 2.5}]},
                      {"c": [{"v": "copy.2"}, None, {"v": 1.0}]}]}
    assert scopes.table_rows(table) == [
        {"hlo_op_name": "fusion.1", "tf_op_name": "a/fl.grad/b",
         "total_self_time": 2.5},
        {"hlo_op_name": "copy.2", "tf_op_name": None,
         "total_self_time": 1.0}]
    assert scopes.table_rows({}) == []


ROWS = [  # hlo_stats rows; self time in microseconds
    {"hlo_op_name": "fusion.18", "total_self_time": 3000.0,
     "tf_op_name": "jit(fleet_chunk)/while/body/fl.grad/dot_general"},
    {"hlo_op_name": "reshape.538", "total_self_time": 9000.0,
     "tf_op_name": "jit(fleet_chunk)/while/body/fl.step/"
                   "jit(ota_round_step)/fl.uplink/reshape"},
    {"hlo_op_name": "ota_round_step.7", "total_self_time": 1500.0,
     "tf_op_name": "jit(fleet_chunk)/while/body/fl.step/"
                   "jit(ota_round_step)/pallas_call"},
    {"hlo_op_name": "fusion.9", "total_self_time": 300.0,
     "tf_op_name": "jit(fleet_chunk)/while/body/fl.channel/mul"},
    {"hlo_op_name": "fusion.4", "total_self_time": 150.0,
     "tf_op_name": "jit(evaluate)/fl.eval/dot_general"},
    {"hlo_op_name": "copy.697", "total_self_time": 45.0, "tf_op_name": ""},
    {"hlo_op_name": "add.3", "total_self_time": 5.0,
     "tf_op_name": "jit(fleet_chunk)/while/body/add"},
]


def test_self_time_by_scope():
    by, total, unscoped = scopes.self_time_by_scope(ROWS)
    assert by == pytest.approx({"fl.grad": 3e6, "fl.uplink": 9e6,
                                "fl.step": 1.5e6, "fl.channel": 3e5,
                                "fl.eval": 1.5e5})
    assert total == pytest.approx(14e6)
    assert [u[0] for u in unscoped] == ["copy.697", "add.3"]


def test_scope_readers(monkeypatch):
    monkeypatch.setattr(scopes, "hlo_rows", lambda trace_dir: ROWS)
    ctx, logged = ctx_of(planes=["/device:TPU:0"], sweeps=2, rounds=150,
                         busy_s=0.014)
    per_round = 2 * 150
    assert reader("round.grad_ms_per_round").read(ctx) == \
        pytest.approx(3.0 / per_round)
    assert reader("round.uplink_ms_per_round").read(ctx) == \
        pytest.approx(9.0 / per_round)
    assert reader("device.scoped_share").read(ctx) == \
        pytest.approx(100.0 * (14e6 - 50e3) / 14e6)
    assert any("unscoped copy.697" in m for m in logged)
    # two chips: the converter sums them, the readers give a chip's mean
    ctx, _ = ctx_of(planes=["/device:TPU:0", "/device:TPU:1"], chips=2)
    assert reader("round.grad_ms_per_round").read(ctx) == \
        pytest.approx(1.5 / per_round)


def test_no_fl_scope_reads_none_not_zero(monkeypatch):
    # an executable from a compile-cache entry that a program without the
    # scopes wrote names none of them: every scope reader says so
    bare = [{**r, "tf_op_name": r["tf_op_name"].replace("fl.", "")}
            for r in ROWS]
    monkeypatch.setattr(scopes, "hlo_rows", lambda trace_dir: bare)
    ctx, logged = ctx_of(planes=["/device:TPU:0"])
    for name in ("round.grad_ms_per_round", "round.uplink_ms_per_round",
                 "device.scoped_share"):
        assert reader(name).read(ctx) is None, name
    assert sum("names an fl. scope" in m for m in logged) == 1


def test_a_missing_scope_reads_none(monkeypatch):
    # the tree path has no uplink layout work
    monkeypatch.setattr(scopes, "hlo_rows", lambda trace_dir: [
        r for r in ROWS if "fl.uplink" not in r["tf_op_name"]])
    ctx, logged = ctx_of(planes=["/device:TPU:0"])
    assert reader("round.uplink_ms_per_round").read(ctx) is None
    assert reader("round.grad_ms_per_round").read(ctx) > 0
    assert any("no device op under fl.uplink" in m for m in logged)


def test_no_device_trace_reads_none(monkeypatch):
    def never(trace_dir):
        raise AssertionError("read a profile without device planes")
    monkeypatch.setattr(scopes, "hlo_rows", never)
    ctx, _ = ctx_of(planes=[])
    assert reader("device.scoped_share").read(ctx) is None


def test_a_failing_converter_reads_none(monkeypatch):
    def broken(trace_dir):
        raise RuntimeError("converter")
    monkeypatch.setattr(scopes, "hlo_rows", broken)
    ctx, logged = ctx_of(planes=["/device:TPU:0"])
    assert reader("round.grad_ms_per_round").read(ctx) is None
    assert any("failed" in m for m in logged)


def test_hlo_rows_without_a_profile(tmp_path):
    assert scopes.hlo_rows(str(tmp_path)) == []
