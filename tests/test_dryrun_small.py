"""Dry-run machinery on a small forced-device-count mesh (subprocess).

The production 512-device dry-run is exercised by launch/dryrun.py itself
(EXPERIMENTS.md §Dry-run); here we prove the same code path — lower, compile,
memory/cost analysis, collective parsing — on an 8-device debug mesh with
reduced configs, inside pytest.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, jax, jax.numpy as jnp
    from repro import configs, distributed as dist
    from repro.launch import mesh as mesh_lib, steps as steps_lib
    from repro.launch.hlo import collective_bytes
    from repro.launch.dryrun import _scheme_for
    from repro.models.registry import build_bundle
    from repro.configs.shapes import InputShape

    results = {}
    mesh = mesh_lib.make_debug_mesh(2, 2, multi_pod=True)   # (2,2,2)
    for arch, kind in [("granite-8b", "train"), ("mamba2-1.3b", "decode"),
                       ("mixtral-8x22b", "train"),
                       ("seamless-m4t-medium", "prefill")]:
        cfg = configs.get_config(arch).smoke()
        bundle = build_bundle(cfg, tp=2, dp=2)
        shape = InputShape("t", 64, 16, kind)
        with dist.mesh_rules(mesh):
            pshard = steps_lib.param_shardings(bundle, mesh)
            args, shardings = steps_lib.input_specs(bundle, shape, mesh)
            if kind == "train":
                scheme, dep = _scheme_for(bundle, mesh, "sca", 0.01)
                step = steps_lib.make_train_step(
                    bundle, scheme, dep.gains, steps_lib.TrainStepConfig())
            elif kind == "prefill":
                step = steps_lib.make_prefill_step(bundle)
            else:
                step = steps_lib.make_serve_step(bundle)
            jitted = jax.jit(step, in_shardings=(pshard,) + tuple(shardings))
            compiled = jitted.lower(bundle.abstract(), *args).compile()
        cost = compiled.cost_analysis() or {}
        coll = collective_bytes(compiled.as_text())
        mem = compiled.memory_analysis()
        results[arch + ":" + kind] = {
            "flops": float(cost.get("flops", -1)),
            "coll_total": coll["total"],
            "arg_bytes": int(mem.argument_size_in_bytes),
        }
    print("RESULTS" + json.dumps(results))
""")


@pytest.mark.slow
def test_debug_mesh_dryrun_all_kinds():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-4000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULTS")][0]
    results = json.loads(line[len("RESULTS"):])
    assert len(results) == 4
    for k, v in results.items():
        assert v["flops"] > 0, (k, v)
        assert v["coll_total"] > 0, (k, v)   # sharded => collectives exist
        assert v["arg_bytes"] > 0, (k, v)


def test_collective_bytes_parser():
    from repro.launch.hlo import collective_bytes
    hlo = """
      %ar = bf16[1024,32]{1,0} all-reduce(bf16[1024,32] %x), replica_groups={}
      %ag.1 = f32[64]{0} all-gather(f32[16] %y), dimensions={0}
      %cp = (f32[8]{0}, f32[8]{0}) collective-permute-start(f32[8] %z)
      %cpd = f32[8]{0} collective-permute-done(%cp)
      %a2a = f32[128,4]{1,0} all-to-all(f32[128,4] %w), dimensions={1}
    """
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 1024 * 32 * 2
    assert out["all-gather"] == 64 * 4
    # start tuple (in+out buffers) counted once; -done skipped
    assert out["collective-permute"] == 8 * 4 * 2
    assert out["all-to-all"] == 128 * 4 * 4
    assert out["total"] == sum(v for k, v in out.items() if k != "total")
