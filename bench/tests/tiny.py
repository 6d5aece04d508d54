"""A copy of the benchmark at sizes a CPU test run can hold.

``make_root(tmp)`` copies ``bench/`` into ``tmp/bench`` and writes a
``tmp/BENCHMARK.json`` whose cells are the real cells' mixes cut to a few
rounds, cells and samples, with tiny widths.  Every tiny cell is added
the way a later change would add one: new files and BENCHMARK.json
entries, no edit of a file the benchmark has.
"""
from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

# on the CPU the program and the reference agree to f32 rounding (~2e-7
# relative, measured on the CPU): these limits hold them to that
LIMITS = {"loss": 1e-5, "acc": 1e-6, "grad_norm": 1e-5,
          "noise_scale": 1e-5, "active": 0.0, "delta": 1e-5}


def _load(path):
    with open(path) as f:
        return json.load(f)


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def tiny_mlp(base="paper_mlp"):
    cfg = _load(os.path.join(BENCH, "configs", base + ".json"))
    cfg["model"]["hidden"] = 16
    cfg["task_args"] = {"hidden": 16}
    m = cfg["model"]
    cfg["param_dim"] = (m["input_dim"] * 16 + 16 + 16 * m["num_classes"]
                        + m["num_classes"])
    cfg.update(samples_per_class=20, test_per_class=5, global_eval=40)
    return cfg


def tiny_traffic(name, seeds=2, batch=8, rounds=6, every=3):
    tr = _load(os.path.join(BENCH, "traffic", name + ".json"))
    tr.update(seeds_per_sweep=seeds, batch_size=batch, rounds=rounds,
              eval_every=every, warmup_rounds=rounds)
    return tr


def make_root(tmp: str, limits=None) -> str:
    """A checkout-like root under ``tmp`` with the tiny cells
    ``tiny_mlp``, ``tiny_full`` and ``tiny_grid``;
    returns its bench directory."""
    root = os.path.join(tmp, "root")
    bench = os.path.join(root, "bench")
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = _load(os.path.join(ROOT, "BENCHMARK.json"))
    lim = {"limits": dict(limits or LIMITS)}
    cells = {
        "tiny_mlp": ("tiny_paper_mlp", tiny_mlp(), "paper_mlp",
                     tiny_traffic("mb128")),
        "tiny_full": ("tiny_paper_mlp", tiny_mlp(), "paper_mlp",
                      tiny_traffic("fullbatch", batch=0)),
        "tiny_grid": ("tiny_paper_mlp_grid4", tiny_mlp("paper_mlp_grid4"),
                      "paper_mlp", tiny_traffic("grid_sharded")),
    }
    spec["configs"], spec["workloads"] = [], []
    for cell, (cname, cfg, module, traffic) in cells.items():
        if cname not in [c["name"] for c in spec["configs"]]:
            path = f"bench/configs/{cname}.json"
            _write(os.path.join(root, path), {**cfg, "name": cname})
            shutil.copy(os.path.join(BENCH, "configs", module + ".py"),
                        os.path.join(bench, "configs", cname + ".py"))
            spec["configs"].append({"name": cname, "source": "test",
                                    "file": path, "reduced": [],
                                    "why": "test"})
        _write(os.path.join(bench, "traffic", cell + ".json"), traffic)
        _write(os.path.join(bench, "limits", cell + ".json"), lim)
        spec["workloads"].append({"name": cell, "config": cname,
                                  "traffic": cell,
                                  "chips": 4 if cell == "tiny_grid" else 1,
                                  "why": "test"})
    for m in spec["per_layer"]:
        m.pop("workloads", None)
    _write(os.path.join(root, "BENCHMARK.json"), spec)
    return bench
