"""Multi-pod dry-run: AOT-lower + compile every (arch x shape x mesh)
combination and extract roofline terms — no real TPU, no allocation.

MUST be run as a fresh process (jax locks device count on first init):

    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-8b \
        --shape train_4k [--multi-pod] [--all]
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

# ruff: noqa: E402
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro import distributed as dist
from repro.core import power_control as pcm
from repro.core.channel import WirelessConfig, deploy
from repro.core.theory import OTAParams
from repro.launch import mesh as mesh_lib
from repro.launch import steps as steps_lib
from repro.models.param import param_bytes, param_count
from repro.models.registry import build_bundle

from repro.launch.hlo import collective_bytes  # noqa: E402

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "experiments", "dryrun")


def _scheme_for(bundle, mesh, scheme_name: str, eta: float):
    """Build the OTA power-control scheme for the mesh's FL clients."""
    n = mesh_lib.num_clients(mesh)
    wcfg = WirelessConfig(num_devices=n, seed=0)
    dep = deploy(wcfg)
    prm = OTAParams(d=max(bundle.num_params, 1), gmax=10.0,
                    es=wcfg.energy_per_sample, n0=wcfg.noise_psd,
                    gains=dep.gains, sigma_sq=np.zeros(n), eta=eta,
                    lsmooth=1.0, kappa_sq=4.0)
    return pcm.make_power_control(scheme_name, dep, prm), dep


def build_step_and_args(arch: str, shape_name: str, mesh,
                        scheme_name: str = "sca", eta: float = 1e-2):
    """Returns (step_fn, args, in_shardings, donate) ready to jit."""
    shape = configs.get_shape(shape_name)
    cfg = (configs.long_context_config(arch) if shape_name == "long_500k"
           else configs.get_config(arch))
    tp = mesh.shape.get("model", 1)
    dp = mesh.shape.get("data", 1)
    bundle = build_bundle(cfg, tp=tp, dp=dp)
    pshard = steps_lib.param_shardings(bundle, mesh)
    abstract = bundle.abstract()

    (step_args, arg_shardings) = steps_lib.input_specs(bundle, shape, mesh)

    if shape.kind == "train":
        scheme, dep = _scheme_for(bundle, mesh, scheme_name, eta)
        step = steps_lib.make_train_step(bundle, scheme, dep.gains,
                                         steps_lib.TrainStepConfig(eta=eta))
        args = (abstract,) + tuple(step_args)
        shardings = (pshard,) + tuple(arg_shardings)
        donate = (0,)
    elif shape.kind == "prefill":
        step = steps_lib.make_prefill_step(bundle)
        tokens_or_inputs, caches = step_args
        args = (abstract, tokens_or_inputs, caches)
        shardings = (pshard, arg_shardings[0], arg_shardings[1])
        donate = (2,)
    else:  # decode
        step = steps_lib.make_serve_step(bundle)
        caches, token, pos = step_args
        args = (abstract, caches, token, pos)
        shardings = (pshard,) + tuple(arg_shardings)
        donate = (1,)
    return step, args, shardings, donate, bundle


def run_one(arch: str, shape_name: str, *, multi_pod: bool,
            scheme: str = "sca", save: bool = True,
            mesh=None, correct_costs: bool = True) -> dict:
    mesh = mesh if mesh is not None else mesh_lib.make_production_mesh(
        multi_pod=multi_pod)
    t0 = time.time()
    with dist.mesh_rules(mesh):
        step, args, shardings, donate, bundle = build_step_and_args(
            arch, shape_name, mesh, scheme)
        jitted = jax.jit(step, in_shardings=shardings,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "generated_code_bytes": int(
                getattr(mem, "generated_code_size_in_bytes", 0)),
        }
    except Exception as e:  # CPU backend may not implement it
        mem_info = {"error": str(e)}
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    n_dev = mesh.devices.size
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": dict(zip(mesh.axis_names, [int(mesh.shape[a])
                                           for a in mesh.axis_names])),
        "devices": int(n_dev),
        "device_kind": mesh_lib.TARGET_DEVICE_KIND,
        "scheme": scheme,
        "num_params": int(bundle.num_params),
        "param_bytes_total": int(param_bytes(bundle.defs)),
        "flops_per_device": float(cost.get("flops", -1.0)),
        "bytes_accessed_per_device": float(cost.get("bytes accessed", -1.0)),
        "collective_bytes_per_device": coll,
        "memory_analysis": mem_info,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
    }
    if correct_costs:
        from repro.launch.cost import corrected_costs
        shape = configs.get_shape(shape_name)
        cfg = (configs.long_context_config(arch) if shape_name == "long_500k"
               else configs.get_config(arch))
        try:
            with dist.mesh_rules(mesh):
                record.update(corrected_costs(record, cfg, shape, mesh))
        except Exception as e:
            record["cost_correction_error"] = repr(e)
    if save:
        os.makedirs(ARTIFACT_DIR, exist_ok=True)
        tag = f"{arch}_{shape_name}_{'multipod' if multi_pod else 'pod'}"
        with open(os.path.join(ARTIFACT_DIR, tag + ".json"), "w") as f:
            json.dump(record, f, indent=1)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=configs.ARCH_IDS)
    ap.add_argument("--shape", default=None,
                    choices=tuple(configs.SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--scheme", default="sca", choices=pcm.SCHEMES)
    ap.add_argument("--all", action="store_true",
                    help="run every supported (arch x shape)")
    ap.add_argument("--no-correct", action="store_true",
                    help="skip loop-corrected cost extraction (faster; used "
                         "for the multi-pod pass — roofline is single-pod)")
    args = ap.parse_args()

    pairs = []
    if args.all:
        for arch in configs.ARCH_IDS:
            for shp in configs.supported_shapes(arch):
                pairs.append((arch, shp))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        pairs = [(args.arch, args.shape)]

    failures = []
    for arch, shp in pairs:
        try:
            rec = run_one(arch, shp, multi_pod=args.multi_pod,
                          scheme=args.scheme,
                          correct_costs=not args.no_correct)
            fl = rec.get("flops_per_device_corrected",
                         rec["flops_per_device"])
            cl = rec.get("collective_bytes_corrected",
                         rec["collective_bytes_per_device"]["total"])
            print(f"OK   {arch:22s} {shp:12s} "
                  f"flops/dev={fl:.3e} coll/dev={cl:.3e}B "
                  f"compile={rec['compile_s']}s", flush=True)
        except Exception as e:
            failures.append((arch, shp, repr(e)))
            traceback.print_exc()
            print(f"FAIL {arch:22s} {shp:12s} {e!r}", flush=True)
    if failures:
        raise SystemExit(f"{len(failures)} dry-run failures: {failures}")


if __name__ == "__main__":
    main()
