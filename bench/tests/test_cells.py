"""BENCHMARK.json against the contract, additions as new files only, and
the command's refusals."""
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import time

import pytest

import tiny
from bench import harness

ROOT, BENCH = tiny.ROOT, tiny.BENCH
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_command(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "bench/run.py"]
    assert spec["paths"] == ["bench"]
    assert 1 <= spec["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_every_cell_finds_its_files(spec):
    configs = {c["name"]: c for c in spec["configs"]}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and \
            os.path.exists(os.path.join(ROOT, c["file"]))
        assert os.path.exists(os.path.join(
            ROOT, os.path.splitext(c["file"])[0] + ".py"))
        assert any(w["config"] == c["name"] for w in spec["workloads"])
    four = 0
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        four += w["chips"] == 4
        for sub in (("traffic", w["traffic"]), ("limits", w["name"])):
            assert os.path.exists(os.path.join(BENCH, sub[0],
                                               sub[1] + ".json"))
    assert four <= len(spec["workloads"]) // 2


def test_every_metric_is_well_formed(spec):
    cells = {w["name"] for w in spec["workloads"]}
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
    for m in spec["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["moves"] in e2e and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))


def _digest(path):
    out = {}
    for d, _, files in os.walk(path):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.relpath(os.path.join(d, f), path)] = \
                    hashlib.sha1(fh.read()).hexdigest()
    return out


DUMMY = '''"""dummy.sweeps: traced sweeps (a test metric)."""


def read(ctx):
    return float(ctx.sweeps)
'''


def test_a_new_cell_and_metric_are_new_files_only(tmp_path):
    bench = tiny.make_root(str(tmp_path))
    before = _digest(BENCH)
    root = os.path.dirname(bench)
    with open(os.path.join(bench, "metrics", "dummy.sweeps.py"), "w") as f:
        f.write(DUMMY)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["per_layer"].append({"name": "dummy.sweeps", "unit": "sweeps",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "cell_rounds_per_s",
                              "workloads": ["tiny_mlp"]})
    with open(path, "w") as f:
        json.dump(spec, f)
    res = harness.run("tiny_mlp", 3, 1.0, True, bench, time.monotonic(),
                      require_chip=False)
    assert res["metrics"]["dummy.sweeps"] == {"value": 2.0, "unit": "sweeps"}
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"
    assert _digest(BENCH) == before


def _run_cli(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mlp_mb128", "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_command_refuses_without_a_chip():
    out = _run_cli(ROOT)
    assert out.returncode != 0 and out.stdout == ""
    assert "TPU" in out.stderr


def test_command_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_cli(str(tmp_path))
    assert out.returncode != 0 and out.stdout == ""
