"""The readers of compile-phase spans and of device time by name scope."""
import os
import types

import pytest

from bench import scopes, sweep

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def span(kind, t0, t1, **fields):
    """A telemetry span as ``xtrace.telemetry_spans`` gives it (seconds
    in, nanoseconds on the clock); ``fields`` holds the program's own
    wall-clock stamps."""
    return {"kind": kind, "start": t0 * 1e9, "end": t1 * 1e9,
            "dur": t1 - t0,
            "fields": {"ev": kind, "t0_ns": int(t0 * 1e9),
                       "t1_ns": int(t1 * 1e9), **fields}}


def ctx_of(spans=(), sweeps=2, rounds=150, planes=(), chips=1, busy_s=None):
    logged = []
    ctx = types.SimpleNamespace(
        telemetry=list(spans), sweeps=sweeps, rounds=rounds,
        planes=list(planes), chips=chips, busy_s=busy_s,
        cell=types.SimpleNamespace(root="/nonexistent", name="cell"),
        log=logged.append)
    return ctx, logged


def reader(name):
    return sweep.load_module(os.path.join(BENCH, "metrics", name + ".py"),
                             "test_metric_" + name.replace(".", "_"))


# one chunk call: the chunk's trace holds two nested jit traces, its
# lowering traces one more jit, then the backend step; the eval compiles
# after it, outside
SWEEP = [span("chunk_compile", 10.0, 15.0),
         span("compile.jaxpr_trace", 10.2, 10.3, fun="add"),
         span("compile.jaxpr_trace", 10.4, 10.45, fun="less"),
         span("compile.jaxpr_trace", 10.0, 11.0, fun="fleet_chunk"),
         span("compile.jaxpr_trace", 11.5, 11.6, fun="_threefry_split"),
         span("compile.lower", 11.0, 12.0, fun="jit(fleet_chunk)"),
         span("compile.backend", 12.0, 14.5, fun="jit(fleet_chunk)"),
         span("chunk_exec", 15.0, 16.0),
         span("eval", 16.0, 16.5),
         span("compile.jaxpr_trace", 16.0, 16.1, fun="evaluate"),
         span("compile.lower", 16.1, 16.15, fun="jit(evaluate)"),
         span("compile.backend", 16.15, 16.35, fun="jit(evaluate)")]


def test_nested_compile_spans_are_counted_once():
    kept = scopes.outermost(SWEEP)
    assert [(s["kind"], s["fields"]["fun"]) for s in kept] == [
        ("compile.jaxpr_trace", "fleet_chunk"),
        ("compile.lower", "jit(fleet_chunk)"),
        ("compile.backend", "jit(fleet_chunk)"),
        ("compile.jaxpr_trace", "evaluate"),
        ("compile.lower", "jit(evaluate)"),
        ("compile.backend", "jit(evaluate)")]


def test_phase_readers_per_sweep():
    ctx, _ = ctx_of(SWEEP, sweeps=1)
    got = {name: reader(name).read(ctx) for name in (
        "driver.jaxpr_trace_ms_per_sweep", "driver.lower_ms_per_sweep",
        "driver.backend_compile_ms_per_sweep")}
    assert got == pytest.approx({
        "driver.jaxpr_trace_ms_per_sweep": 1000.0 + 100.0,
        "driver.lower_ms_per_sweep": 1000.0 + 50.0,
        "driver.backend_compile_ms_per_sweep": 2500.0 + 200.0})
    ctx, _ = ctx_of(SWEEP, sweeps=2)
    assert reader("driver.lower_ms_per_sweep").read(ctx) == \
        pytest.approx(525.0)


def test_outermost_falls_back_to_the_harness_clock():
    bare = [{**s, "fields": {}} for s in SWEEP]
    assert [s["fields"] for s in scopes.outermost(bare)] == [{}] * 6


def test_no_compile_span_reads_none_not_zero():
    # a program that writes no compile-phase spans (the parent of the
    # change that added them): the reader has nothing to read
    ctx, logged = ctx_of([span("chunk_compile", 0.0, 4.0),
                          span("eval", 4.0, 4.2)])
    assert reader("driver.jaxpr_trace_ms_per_sweep").read(ctx) is None
    assert logged
    ctx, _ = ctx_of([], sweeps=0)
    assert scopes.phase_ms_per_sweep(ctx, "compile.lower") is None


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
