"""One cell's set-up and its timed path.

The timed path is the program's task-first fleet entry,
``repro.fl.driver.run_fleet_task``: one call runs one whole sweep of the
cell's [scenario x scheme x seed] grid for the traffic's rounds, eval
cadence and placement.  Set-up makes what every call shares: the inputs
(data and initial weights, on the device, from ``--seed``), the wireless
world and the power-control designs (on the host, by the program's own
design code, as its benchmarks build them).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any

import numpy as np

# the scheme classes whose per-round coefficient rule the reference knows,
# by the program's class name
COEFF_KIND = {"Ideal": 0, "TruncatedInversion": 1, "VanillaOTA": 2,
              "OPC": 3, "BBFL": 4}


@dataclasses.dataclass
class Cell:
    """Everything BENCHMARK.json and the cell's files say about one cell."""
    name: str
    chips: int
    config: dict
    model: Any              # the configuration's plain reference module
    traffic: dict
    limits: dict
    end_to_end: list        # metric entries of BENCHMARK.json for this cell
    per_layer: list
    bench_dir: str

    @property
    def root(self) -> str:
        return os.path.dirname(self.bench_dir)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import the Python file at ``path`` under the module name ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_dir: str) -> Cell:
    """The cell ``name`` of ``<bench_dir>/../BENCHMARK.json`` with its
    configuration, traffic and limits files."""
    spec = _json(os.path.join(os.path.dirname(bench_dir), "BENCHMARK.json"))
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise SystemExit(f"unknown workload {name!r} (known: {sorted(work)})")
    w = work[name]
    cfgs = {c["name"]: c for c in spec["configs"]}
    cfg_file = os.path.join(os.path.dirname(bench_dir),
                            cfgs[w["config"]]["file"])
    config = _json(cfg_file)
    model = load_module(os.path.splitext(cfg_file)[0] + ".py",
                        f"bench_config_{w['config']}")
    traffic = _json(os.path.join(bench_dir, "traffic", w["traffic"] + ".json"))
    limits = _json(os.path.join(bench_dir, "limits", name + ".json"))
    return Cell(name=name, chips=int(w["chips"]), config=config, model=model,
                traffic=traffic, limits=limits,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
                bench_dir=bench_dir)


# -- seeds -----------------------------------------------------------------

def _entropy(seed: int) -> list:
    """A non-negative seed of any size as SeedSequence entropy words."""
    seed = int(seed) % (1 << 128)
    return [(seed >> (32 * i)) & 0xFFFFFFFF for i in range(4)]


def data_seed(seed: int) -> int:
    """The 31-bit key of the inputs of run ``seed``."""
    ss = np.random.SeedSequence(_entropy(seed) + [0])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


def fleet_seeds(seed: int, sweep: int, count: int, warmup=False) -> tuple:
    """The seed axis of sweep ``sweep`` of run ``seed`` (or of its warm-up
    sweep): ``count`` 31-bit fleet seeds derived from (seed, sweep)."""
    ss = np.random.SeedSequence(_entropy(seed) + [3 if warmup else 1,
                                                  int(sweep)])
    return tuple(int(s & 0x7FFFFFFF) for s in ss.generate_state(count))


def check_cells(seed: int, rows: int, seeds: int, count: int) -> list:
    """``count`` (row, seed-column) cells of a [rows, seeds] grid drawn
    from ``seed``, or every cell of a smaller grid: one from each of as
    many equal strata of the flattened cell axis, so that on a sharded
    grid every chip's block of cells is checked."""
    rng = np.random.default_rng(_entropy(seed) + [2])
    total = rows * seeds
    count = min(count, total)
    edges = np.linspace(0, total, count + 1).astype(int)
    flat = [int(rng.integers(a, b)) for a, b in zip(edges[:-1], edges[1:])]
    return [(f // seeds, f % seeds) for f in flat]


# -- inputs ----------------------------------------------------------------

def make_inputs(cell: Cell, seed: int):
    """Data and initial weights of run ``seed``, made on the device in one
    jitted call: (data dict, params dict)."""
    import jax

    cfg = cell.config

    @jax.jit
    def build(key):
        kd, kp = jax.random.split(key)
        return cell.model.make_data(kd, cfg), cell.model.init_params(kp, cfg)

    return jax.block_until_ready(build(jax.random.PRNGKey(data_seed(seed))))


# -- wireless world and designs -------------------------------------------

@dataclasses.dataclass
class World:
    schemes: list           # one PowerControl per cell row (scenario-major)
    etas: list              # per row
    gains: Any              # [N] (None on a scenario grid)
    scenarios: Any          # a ScenarioStack, or None
    row_names: list
    coeffs: list            # per row: the design constants, for the reference
    fading: list            # per row: {"gains" [N], "k_factor" [N]}


def _coeff_row(pc, n: int) -> dict:
    kind = COEFF_KIND[type(pc).__name__]
    if getattr(pc, "dropout_aware", False):
        raise ValueError(f"{pc.name}: dropout-aware rules are not in the "
                         "reference")

    def arr(v, default):
        return np.asarray(default if v is None else v, np.float64)

    return {"kind": kind,
            "gamma": arr(getattr(pc, "gamma", None), np.zeros(n)),
            "alpha": arr(getattr(pc, "alpha", None), 1.0),
            "thresholds": arr(getattr(pc, "thresholds", None), np.zeros(n)),
            "noise_over_alpha": arr(getattr(pc, "noise_over_alpha", None),
                                    0.0),
            "bmax": arr(getattr(pc, "bmax", None), 1.0),
            "n0": arr(getattr(pc, "n0", None), 0.0),
            "gmax": arr(getattr(pc, "gmax", None), 1.0),
            "mask": arr(getattr(pc, "mask", None), np.ones(n)),
            "alternative": arr(float(getattr(pc, "alternative", False)), 0.0),
            "grid_size": int(getattr(pc, "grid_size", 128))}


def make_world(cell: Cell) -> World:
    """Deployment(s) and power-control designs, built on the host by the
    program's design code exactly as its Fig.-2 and scenario sweeps do."""
    from repro.core import channel, power_control as pcm
    from repro.core import scenarios as scn
    from repro.core.theory import OTAParams

    cfg = cell.config
    n, d = cfg["num_devices"], cfg["param_dim"]
    design = cfg["design"]
    if "scenarios" in cfg:
        names = list(cfg["scenarios"])
        stack = scn.stack_scenarios(names, seed=cfg["scenario_seed"])
        kinds = np.asarray(stack.kind)
        if not set(kinds.tolist()) <= {0, 1}:
            raise ValueError("the reference draws i.i.d. Rayleigh and Rician "
                             f"fading only; scenario kinds {kinds}")
        pcs, fading, rows = [], [], []
        for c, sc_name in enumerate(names):
            dep = scn.realize(scn.get_scenario(sc_name),
                              seed=cfg["scenario_seed"])
            prm = scn.make_ota_params(dep, d=d, gmax=cfg["gmax"], **design)
            for s in cfg["schemes"]:
                pcs.append(pcm.make_power_control(s, dep, prm))
                fading.append({"gains": np.asarray(stack.gains[c]),
                               "k_factor": np.asarray(stack.k_factor[c])})
                rows.append(f"{sc_name}/{s}")
        etas = [cfg["eta"][s] for _ in names for s in cfg["schemes"]]
        return World(schemes=pcs, etas=etas, gains=None, scenarios=stack,
                      row_names=rows, coeffs=[_coeff_row(p, n) for p in pcs],
                      fading=fading)
    w = cfg["wireless"]
    wcfg = channel.WirelessConfig(
        num_devices=n, r_max=w["r_max"], pl0_db=w["pl0_db"],
        pl_exponent=w["pl_exponent"], bandwidth_hz=w["bandwidth_hz"],
        ptx_dbm=w["ptx_dbm"], n0_dbm_hz=w["n0_dbm_hz"],
        seed=w["deploy_seed"])
    dep = channel.deploy(wcfg)
    prm = OTAParams(d=d, gmax=cfg["gmax"], es=wcfg.energy_per_sample,
                    n0=wcfg.noise_psd, gains=dep.gains,
                    sigma_sq=np.zeros(n), eta=0.05, **design)
    pcs = [pcm.make_power_control(s, dep, prm.replace(eta=cfg["eta"][s]))
           for s in cfg["schemes"]]
    flat = {"gains": np.asarray(dep.gains), "k_factor": np.zeros(n)}
    return World(schemes=pcs, etas=[cfg["eta"][s] for s in cfg["schemes"]],
                 gains=dep.gains, scenarios=None,
                 row_names=list(cfg["schemes"]),
                 coeffs=[_coeff_row(p, n) for p in pcs],
                 fading=[flat] * len(pcs))


# -- the timed path --------------------------------------------------------

class Sweeper:
    """Calls ``run_fleet_task`` with what set-up built: one call, one
    sweep.  ``rounds`` other than the traffic's serves the warm-up."""

    def __init__(self, cell: Cell, world: World, inputs, devices):
        from repro import tasks
        from repro.fl.placement import ShardedPlacement, VmapPlacement
        from repro.tasks.base import TaskData

        self.cell, self.world = cell, world
        data, self.params = inputs
        tr = cell.traffic
        cfg = cell.config
        # the program's task at the configuration's widths
        self.task = tasks.get(cfg["task"], expect_runtime="fleet",
                              **cfg.get("task_args", {}))
        if self.task.param_dim != cfg["param_dim"]:
            raise ValueError(
                f"configuration {cfg['name']!r} states d = "
                f"{cfg['param_dim']}, the program's task {cfg['task']!r} "
                f"built with {cfg.get('task_args', {})} has d = "
                f"{self.task.param_dim}")
        self.task_data = TaskData(
            train=(data["train_x"], data["train_y"]),
            test=(data["test_x"], data["test_y"]),
            extras={"global": (data["global_x"], data["global_y"])})
        self.eval_fn = self.task.make_eval(self.task_data)
        if tr["placement"] == "sharded":
            import jax
            from jax.sharding import AxisType

            shape = tuple(tr["mesh"])
            mesh = jax.make_mesh(shape, ("data", "model"),
                                 axis_types=(AxisType.Auto,) * len(shape),
                                 devices=devices[:int(np.prod(shape))])
            self.placement = ShardedPlacement(mesh)
        else:
            self.placement = VmapPlacement()

    def run_config(self, rounds: int):
        tr = self.cell.traffic
        return self.task.run_config(
            num_rounds=rounds, eval_every=tr["eval_every"],
            batch_size=tr["batch_size"], gmax=self.cell.config["gmax"],
            uplink_dtype=tr["uplink"])

    def __call__(self, seeds, rounds=None, telemetry=None):
        from repro.fl.driver import run_fleet_task

        tr = self.cell.traffic
        rounds = tr["rounds"] if rounds is None else rounds
        return run_fleet_task(
            self.task, self.world.schemes, self.world.gains,
            self.run_config(rounds), task_data=self.task_data,
            params=self.params, eval_fn=self.eval_fn, etas=self.world.etas,
            seeds=seeds, flat=tr["flat"], placement=self.placement,
            scenarios=self.world.scenarios, uplink_dtype=tr["uplink"],
            telemetry=telemetry)
