"""2-process ``jax.distributed`` bring-up smoke on forced-CPU devices.

    PYTHONPATH=src python -m benchmarks.grid_smoke --launch

The CI proof of the multi-process story (DESIGN.md §Grid): the parent
picks a free coordinator port and spawns 2 worker processes, each of
which

  1. joins the cluster via ``distributed.initialize_multiprocess``
     (forced to 4 local host-platform devices) and verifies the global
     view: 2 processes, 8 global devices;
  2. runs its ``distributed.process_grid_slice`` slice of the scenario
     axis as one compiled [C_slice x K x S] grid on a mesh of its LOCAL
     devices — on the CPU backend one XLA computation cannot span
     processes, so process-sliced execution IS the bring-up contract;
  3. runs a shared C=1 CANARY grid (same scenario, same config on every
     process) and exchanges result digests through the coordination
     service's key-value store (``kv_put``/``kv_get``): bitwise-equal
     canary digests prove the processes compute identical fleets, so
     their disjoint slices compose into one deterministic sweep.

Workers exit non-zero on any mismatch; the parent propagates failure.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import socket
import subprocess
import sys

import numpy as np

ROOT = os.path.join(os.path.dirname(__file__), "..")
SCENARIOS = ("disk_rayleigh", "disk_rician", "disk_markov", "disk_dropout")
SCHEMES = ("sca", "zero_bias")
SEEDS = (0, 1)
NUM_ROUNDS = 4
CANARY = SCENARIOS[0]


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------------------
# worker: everything below runs AFTER initialize_multiprocess
# ---------------------------------------------------------------------------

def _local_mesh():
    """2x2 ("data", "model") mesh of this process's LOCAL devices —
    jax.make_mesh would grab the global device list, which the CPU
    backend cannot run one computation across."""
    import jax
    from jax.sharding import Mesh

    local = jax.local_devices()
    if len(local) < 4:
        raise SystemExit(f"need 4 local devices, have {len(local)}")
    return Mesh(np.asarray(local[:4]).reshape(2, 2), ("data", "model"))


def _world(seed: int = 0):
    """Tiny 10-device MLP world (the test-suite grid world, shrunk for a
    CI smoke)."""
    import jax
    import jax.numpy as jnp

    from repro.data import partition, synthetic
    from repro.fl.server import FLRunConfig
    from repro.models import mlp
    from repro.models.param import init_params

    x, y, xt, yt = synthetic.mnist_like(40, seed=seed)
    data = partition.stack_shards(partition.partition_by_label(
        x, y, 10, seed=seed))
    params0 = init_params(mlp.mlp_defs(hidden=16), jax.random.PRNGKey(seed))
    xt_j, yt_j = jnp.asarray(xt), jnp.asarray(yt)
    ev = jax.jit(lambda p: {"acc": mlp.accuracy(p, xt_j, yt_j)})
    run = FLRunConfig(eta=0.05, num_rounds=NUM_ROUNDS, eval_every=2,
                      seed=seed, batch_size=0)
    return data, params0, ev, run


def _run_grid(world, names, placement=None):
    from repro.core import power_control as pcm
    from repro.core import scenarios as scn
    from repro.fl.driver import run_fleet
    from repro.models import mlp

    data, params0, ev, run = world
    stack = scn.stack_scenarios(names, seed=0)
    pcs = []
    for name in names:
        dep = scn.realize(scn.get_scenario(name), seed=0)
        prm = scn.make_ota_params(dep, d=10000, gmax=10.0, eta=run.eta,
                                  kappa_sq=4.0)
        pcs.extend(pcm.make_power_control(s, dep, prm) for s in SCHEMES)
    return run_fleet(mlp.mlp_loss, params0, pcs, None, data, run, ev,
                     etas=[run.eta] * len(pcs), seeds=SEEDS, flat=True,
                     scenarios=stack, placement=placement)


def _digest(res) -> str:
    import jax

    h = hashlib.sha1()
    for leaf in jax.tree.leaves(res.params):
        h.update(np.asarray(leaf).tobytes())
    for t in sorted(res.traces):
        h.update(np.asarray(res.traces[t]).tobytes())
    return h.hexdigest()


def worker(args) -> None:
    from repro import distributed as dist
    from repro.fl.placement import ShardedPlacement

    nproc, ndev = dist.initialize_multiprocess(
        args.coordinator, args.num_processes, args.process_id,
        local_device_count=args.local_devices)
    import jax

    me = args.process_id
    print(f"[p{me}] joined: {nproc} processes, {ndev} local / "
          f"{jax.device_count()} global devices", flush=True)
    if nproc != args.num_processes or ndev != args.local_devices:
        raise SystemExit(f"[p{me}] cluster view wrong: {nproc} processes, "
                         f"{ndev} local devices")

    world = _world()
    placement = ShardedPlacement(_local_mesh())

    sl = dist.process_grid_slice(len(SCENARIOS))
    mine = SCENARIOS[sl]
    res = _run_grid(world, mine, placement=placement)
    slice_digest = _digest(res)
    dist.kv_put(f"slice/{me}", json.dumps(
        {"scenarios": list(mine), "digest": slice_digest,
         "cells": len(mine) * len(SCHEMES) * len(SEEDS)}))
    print(f"[p{me}] slice {list(mine)}: {slice_digest[:12]}", flush=True)

    canary = _run_grid(world, (CANARY,), placement=placement)
    mine_d = _digest(canary)
    dist.kv_put(f"canary/{me}", mine_d)
    for j in range(nproc):
        theirs = dist.kv_get(f"canary/{j}", timeout_s=120.0)
        if theirs != mine_d:
            raise SystemExit(f"[p{me}] canary digest mismatch vs p{j}: "
                             f"{mine_d[:12]} != {theirs[:12]}")
    print(f"[p{me}] canary bitwise across {nproc} processes: "
          f"{mine_d[:12]}", flush=True)

    if me == 0:       # gather the slice record: the composed sweep proof
        slices = [json.loads(dist.kv_get(f"slice/{j}", timeout_s=120.0))
                  for j in range(nproc)]
        covered = [s for rec in slices for s in rec["scenarios"]]
        if covered != list(SCENARIOS):
            raise SystemExit(f"[p0] slices {covered} do not compose the "
                             f"scenario axis {list(SCENARIOS)}")
        print(f"[p0] {len(SCENARIOS)} scenarios covered by {nproc} "
              f"disjoint process slices; "
              f"{sum(r['cells'] for r in slices)} cells total", flush=True)


# ---------------------------------------------------------------------------
# parent
# ---------------------------------------------------------------------------

def launch(num_processes: int = 2, local_devices: int = 4,
           timeout_s: float = 900.0) -> None:
    port = _free_port()
    env = dict(os.environ)
    # each worker forces its OWN device count via --local-devices; a
    # parent-level forced count would leak into both
    env.pop("XLA_FLAGS", None)
    # the workers are a CPU bring-up proof: on an accelerator host both
    # would otherwise try to take the same chip
    env["JAX_PLATFORMS"] = "cpu"
    procs = []
    for i in range(num_processes):
        cmd = [sys.executable, "-m", "benchmarks.grid_smoke",
               "--coordinator", f"127.0.0.1:{port}",
               "--num-processes", str(num_processes),
               "--process-id", str(i),
               "--local-devices", str(local_devices)]
        procs.append(subprocess.Popen(cmd, cwd=ROOT, env=env,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT, text=True))
    rc = 0
    for i, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
            out += f"\n[p{i}] TIMEOUT after {timeout_s}s"
            rc = 1
        sys.stdout.write(out)
        rc = rc or p.returncode
    if rc:
        raise SystemExit(f"grid smoke FAILED (rc={rc})")
    print(f"grid smoke OK: {num_processes} processes x {local_devices} "
          "devices, process-sliced scenario grid + bitwise canary")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--launch", action="store_true",
                    help="spawn the workers and wait (the CI entry point)")
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT")
    ap.add_argument("--num-processes", type=int, default=2)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--local-devices", type=int, default=4)
    ap.add_argument("--timeout", type=float, default=900.0)
    args = ap.parse_args(argv)
    if args.launch:
        launch(args.num_processes, args.local_devices, args.timeout)
        return
    if args.coordinator is None or args.process_id is None:
        raise SystemExit("worker mode needs --coordinator and "
                         "--process-id (or pass --launch)")
    worker(args)


if __name__ == "__main__":
    main()
