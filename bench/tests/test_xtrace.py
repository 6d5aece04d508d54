"""The reduction from trace events and spans to per-layer numbers."""
import json

import pytest

from bench import xtrace

DEV = "/device:TPU:0"


def op(name, start, dur, plane=DEV, **stats):
    return {"plane": plane, "line": xtrace.OPS_LINE, "name": name,
            "start": float(start), "dur": float(dur), "stats": stats}


def test_union_and_busy_merge_overlaps():
    ops = [op("a", 0, 10), op("b", 5, 10), op("c", 20, 5), op("d", 21, 1)]
    assert xtrace.union([(0, 10), (5, 15), (20, 25), (21, 22)]) == \
        [[0, 15], [20, 25]]
    assert xtrace.busy_ns(ops) == 20.0


@pytest.mark.parametrize("holes, pieces", [
    ([], [(0, 100)]),
    ([(40, 45)], [(0, 40), (45, 100)]),
    ([(40, 45), (44, 50), (-5, 3), (99, 120)], [(3, 40), (50, 99)]),
    ([(200, 300)], [(0, 100)]),
])
def test_a_window_less_its_holes(holes, pieces):
    assert xtrace.without(0, 100, holes) == pieces


def test_device_ops_clip_to_window_and_plane():
    events = [op("a", 0, 10), op("b", 8, 10), op("c", 30, 5),
              op("d", 0, 100, plane="/device:TPU:1"),
              {**op("e", 0, 100), "line": "XLA Modules"}]
    got = xtrace.device_ops(events, DEV, 5, 15)
    assert [(e["name"], e["start"], e["dur"]) for e in got] == \
        [("a", 5, 5), ("b", 8, 7)]


def test_kernel_time_by_name_or_stat():
    ops = [op("ota_round_step", 0, 4), op("fusion.1", 4, 3),
           op("custom-call.7", 7, 5, long_name="ota_round_step.1")]
    assert xtrace.kernel_ns(ops, "ota_round_step") == (9.0, 2)


def test_top_ops_average_over_chips():
    by_plane = {DEV: [op("a", 0, 4e9), op("b", 4e9, 1e9)],
                "/device:TPU:1": [op("a", 0, 2e9)]}
    assert xtrace.top_ops(by_plane) == [["a", 3.0], ["b", 0.5]]


def test_a_loop_op_is_not_counted_over_its_body():
    ops = [op("while", 0, 100), op("a", 0, 30), op("b", 29, 61),
           op("ota_round_step", 95, 5)]
    assert [e["name"] for e in xtrace.leaf_ops(ops)] == \
        ["a", "b", "ota_round_step"]
    assert xtrace.top_ops({DEV: ops}) == [["b", 6.1e-8], ["a", 3e-8],
                                          ["ota_round_step", 5e-9]]
    assert xtrace.kernel_ns(ops, "ota_round_step") == (5.0, 1)
    assert xtrace.busy_ns(ops) == 100.0


def test_idle_gaps_take_the_innermost_span():
    ops = [op("a", 0, 10), op("b", 30, 10), op("c", 90, 10)]
    spans = [{"kind": "bench.sweep", "start": 0, "end": 100},
             {"kind": "chunk_compile", "start": 12, "end": 28},
             {"kind": "eval", "start": 41, "end": 80}]
    gaps = xtrace.idle_gaps(ops, spans, 0, 110)
    assert gaps == [["eval", 50e-9], ["chunk_compile", 20e-9],
                    ["none", 10e-9]]
    assert xtrace.idle_by_label(gaps + [["eval", 1e-9]])["eval"] == \
        pytest.approx(51e-9)


def test_telemetry_spans_move_to_profiler_clock(tmp_path):
    recs = [{"ev": "run_start", "mono": 1.0},
            {"ev": "chunk_compile", "mono": 2.5, "dur": 0.5},
            {"ev": "eval", "mono": 3.0, "dur": 0.25}]
    (tmp_path / "events.jsonl").write_text(
        "\n".join(json.dumps(r) for r in recs) + "\n{broken")
    spans = xtrace.telemetry_spans([str(tmp_path)], offset_ns=-1e9)
    assert [(s["kind"], s["start"], s["end"]) for s in spans] == \
        [("chunk_compile", 1.0e9, 1.5e9), ("eval", 1.75e9, 2.0e9)]
    assert xtrace.span_seconds(spans, "eval") == 0.25


def test_recorded_chip_trace_and_events(tmp_path):
    """A trace and a telemetry events file recorded on a v5e chip in a
    traced ``mlp_mb128`` run: the device events of the first 200 ms of
    its window (set-up copies, no kernel yet) and the first sweep's
    spans."""
    import gzip
    import os
    import shutil
    import types

    data = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
    with gzip.open(os.path.join(data, "mlp_mb128_trace.json.gz"), "rt") as f:
        events = json.load(f)
    ann = [e for e in events if e["name"] == "bench.sweep"]
    assert [a["stats"]["sweep"] for a in ann] == [0, 1]
    planes = xtrace.device_planes(events)
    assert planes == [DEV]
    t0, t1 = ann[0]["start"], ann[0]["start"] + 2e8
    ops = xtrace.device_ops(events, DEV, t0, t1)
    assert len(ops) == 78 and len(xtrace.leaf_ops(ops)) == 78
    busy = xtrace.busy_ns(ops)
    assert busy == 418807.0
    gaps = xtrace.idle_gaps(ops, [], t0, t1)
    assert sum(g for _, g in gaps) * 1e9 + busy == pytest.approx(t1 - t0)
    assert xtrace.kernel_ns(ops, "ota_round_step") == (0, 0)
    assert xtrace.top_ops({DEV: ops}, 1)[0][0].startswith("%broadcast.1 ")

    from bench import sweep
    bench = os.path.dirname(os.path.dirname(data))
    ctx = types.SimpleNamespace(planes=planes, ops={DEV: ops},
                                window_s=(t1 - t0) / 1e9, log=lambda m: None,
                                peaks={"hbm_bw": 819e9}, sweeps=1)
    idle = sweep.load_module(
        os.path.join(bench, "metrics", "device.idle_share.py"), "t_idle")
    assert idle.read(ctx) == pytest.approx(100 * (1 - 418807.0 / 2e8))
    roof = sweep.load_module(
        os.path.join(bench, "metrics", "kernel.ota_round_step_roofline.py"),
        "t_roof")
    assert roof.read(ctx) is None           # no kernel event: no number

    shutil.copy(os.path.join(data, "mlp_mb128_events.jsonl"),
                tmp_path / "events.jsonl")
    spans = xtrace.telemetry_spans([str(tmp_path)], 0.0)
    assert xtrace.span_seconds(spans, "chunk_compile") == \
        pytest.approx(12.857636)
    assert xtrace.span_seconds(spans, "eval") == pytest.approx(0.415488)
