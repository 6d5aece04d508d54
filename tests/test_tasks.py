"""Task subsystem (DESIGN.md §Tasks): registry contract, the paper_mlp
bit-identity regression against the pre-task hand-wired path, and the
cifar_conv workload end to end through the fleet executor (vmap resume
everywhere; sharded parity under the forced multi-device mesh).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tasks
from repro.core import channel, power_control as pcm
from repro.data import partition, synthetic
from repro.fl import driver, engine as eng, server
from repro.fl.server import FLRunConfig
from repro.models import mlp
from repro.models.param import init_params
from tests.helpers import make_prm

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

# cheap factory overrides per task so the whole registry smokes in seconds
SMOKE_KW = {
    "paper_mlp": dict(hidden=32, samples_per_class=20, test_per_class=10),
    "cifar_conv": dict(channels=(8, 16), hidden=32, samples_per_class=20,
                       test_per_class=10, alpha=1.0),
    "token_stream": dict(),       # factory defaults are already CPU-tiny
}


def _world(task, seed=0):
    dep = channel.deploy(channel.WirelessConfig(
        num_devices=task.num_devices, seed=0))
    prm = make_prm(dep.gains, d=min(task.param_dim, 10000))
    return dep, prm


# ---------------------------------------------------------------------------
# registry contract
# ---------------------------------------------------------------------------

def test_registry_lists_builtin_tasks():
    assert set(tasks.names()) >= {"paper_mlp", "cifar_conv", "token_stream"}


def test_registry_unknown_task_raises():
    with pytest.raises(KeyError, match="unknown task"):
        tasks.get("no_such_task")


def test_registry_expect_runtime_guards_before_factory():
    """A runtime mismatch is rejected from the registration record, BEFORE
    the factory sees (and TypeErrors on) runtime-specific overrides."""
    with pytest.raises(ValueError, match="'steps'-runtime"):
        tasks.get("token_stream", expect_runtime="fleet")
    with pytest.raises(ValueError, match="'fleet'-runtime"):
        # arch= would TypeError inside make_paper_mlp if the guard ran late
        tasks.get("paper_mlp", expect_runtime="steps", arch="qwen1.5-0.5b")
    assert tasks.names(runtime="fleet") == ("cifar_conv", "paper_mlp")
    assert tasks.names(runtime="steps") == ("token_stream",)


def test_registry_rejects_duplicate_and_misnamed():
    with pytest.raises(ValueError, match="already registered"):
        tasks.register("paper_mlp", tasks.make_paper_mlp)
    tasks.register("misnamed_tmp", tasks.make_paper_mlp)
    try:
        with pytest.raises(ValueError, match="built task"):
            tasks.get("misnamed_tmp")
    finally:
        tasks.registry._FACTORIES.pop("misnamed_tmp")


@pytest.mark.parametrize("name", sorted(SMOKE_KW))
def test_registry_task_inits_losses_evals_under_jit(name):
    """The ISSUE-5 registry gate: every registered task builds data, inits
    params, and runs loss_fn and eval_fn under jax.jit with finite
    outputs."""
    task = tasks.get(name, **SMOKE_KW[name])
    td = task.build_data(seed=0)
    params = task.init_params(seed=0)
    assert task.param_dim == sum(int(np.prod(np.shape(l)))
                                 for l in jax.tree.leaves(params))
    batch = task.sample_batch(td)
    loss = jax.jit(task.loss_fn)(params, batch)
    assert np.isfinite(float(loss))
    ev = jax.jit(task.make_eval(td))(params)
    assert ev and all(np.isfinite(float(v)) for v in ev.values()), ev
    run = task.run_config(num_rounds=7)
    assert isinstance(run, FLRunConfig) and run.num_rounds == 7
    # determinism: same seed -> same data and params, bitwise
    td2 = task.build_data(seed=0)
    assert all(np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(td.train), jax.tree.leaves(td2.train)))
    p2 = task.init_params(seed=0)
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
               zip(jax.tree.leaves(params), jax.tree.leaves(p2)))


def test_task_eta_map():
    task = tasks.get("paper_mlp")
    assert task.eta_for("ideal", 0.05) == pytest.approx(0.08)
    assert task.eta_for("unknown_scheme", 0.07) == pytest.approx(0.07)


# ---------------------------------------------------------------------------
# bit-identity regression: paper_mlp through run_fleet_task reproduces the
# pre-refactor run_fleet(mlp.mlp_loss, ...) wiring exactly
# ---------------------------------------------------------------------------

def _params_equal(a, b):
    return all(bool(np.array_equal(np.asarray(x), np.asarray(y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_paper_mlp_task_bit_identical_to_prerefactor_fleet():
    task = tasks.get("paper_mlp", hidden=32, samples_per_class=40)
    dep, prm = _world(task)
    schemes = [pcm.make_power_control(n, dep, prm)
               for n in ("ideal", "sca", "vanilla")]
    run = FLRunConfig(eta=0.05, num_rounds=6, eval_every=3, seed=0)

    res_t = driver.run_fleet_task(task, schemes, dep.gains, run, flat=False)

    # the pre-task hand-wiring, reproduced verbatim (this is what
    # benchmarks/fig2.py compiled before the refactor)
    x, y, xt, yt = synthetic.mnist_like(40, noise=0.75, seed=0)
    shards = partition.partition_by_label(x, y, 10, 2, 2, seed=0)
    data = partition.stack_shards(shards)
    params0 = init_params(mlp.mlp_defs(hidden=32), jax.random.PRNGKey(0))
    xt_j, yt_j = jnp.asarray(xt), jnp.asarray(yt)
    xg, yg = jnp.asarray(x[:4000]), jnp.asarray(y[:4000])
    ev = jax.jit(lambda p: {"acc": mlp.accuracy(p, xt_j, yt_j),
                            "global_loss": mlp.mlp_loss(p, (xg, yg))})
    etas = [task.eta_for(pc.name, run.eta) for pc in schemes]
    res_o = eng.run_fleet(mlp.mlp_loss, params0, schemes, dep.gains, data,
                          run, ev, etas=etas, flat=False)

    assert _params_equal(res_t.params, res_o.params)
    assert set(res_t.traces) == set(res_o.traces)
    for k in res_t.traces:
        assert np.array_equal(res_t.traces[k], res_o.traces[k]), k
    assert [t for t, _ in res_t.evals] == [t for t, _ in res_o.evals]
    for (_, ea), (_, eb) in zip(res_t.evals, res_o.evals):
        for k in ea:
            assert np.array_equal(np.asarray(ea[k]), np.asarray(eb[k])), k


def test_run_fl_task_matches_run_fl():
    """The single-run task entry (fl.server.run_fl_task) is the same
    program as run_fl on the hand-built bundle."""
    task = tasks.get("paper_mlp", hidden=32, samples_per_class=20)
    dep, prm = _world(task)
    pc = pcm.make_power_control("sca", dep, prm)
    run = FLRunConfig(eta=0.05, num_rounds=4, eval_every=2, seed=0)
    params_t, hist_t = server.run_fl_task(task, pc, dep.gains, run)
    td = task.build_data(0)
    params_o, hist_o = server.run_fl(task.loss_fn, task.init_params(0), pc,
                                     dep.gains, td.train, run,
                                     task.make_eval(td))
    assert _params_equal(params_t, params_o)
    assert len(hist_t) == len(hist_o)
    for ra, rb in zip(hist_t, hist_o):
        assert {k: v for k, v in ra.items() if k != "wall"} \
            == {k: v for k, v in rb.items() if k != "wall"}


# ---------------------------------------------------------------------------
# cifar_conv through the whole fleet stack
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cifar_world():
    task = tasks.get("cifar_conv", **SMOKE_KW["cifar_conv"])
    dep, prm = _world(task)
    schemes = [pcm.make_power_control(n, dep, prm)
               for n in ("ideal", "sca")]
    return task, dep, schemes


def test_cifar_conv_fleet_runs_flat_minibatch(cifar_world):
    """[2 schemes x 2 seeds] cifar fleet on the minibatch + flat hot path
    (the task's preferred sweep mode): finite learning trajectories with
    the grid axes in place."""
    task, dep, schemes = cifar_world
    run = task.run_config(num_rounds=6, eval_every=3, batch_size=4, seed=0)
    res = driver.run_fleet_task(task, schemes, dep.gains, run,
                                seeds=(0, 1), flat=True)
    assert res.traces["active_devices"].shape == (2, 2, 6)
    assert res.evals and set(res.evals[-1][1]) == {"acc", "global_loss"}
    assert all(np.all(np.isfinite(np.asarray(v)))
               for _, e in res.evals for v in e.values())
    assert all(np.all(np.isfinite(np.asarray(l)))
               for l in jax.tree.leaves(res.params))


def test_cifar_conv_resume_bitwise_vmap(cifar_world, tmp_path):
    """Kill the cifar fleet after chunk 1, resume from the checkpoint:
    params/traces/evals bitwise equal to the uninterrupted run."""
    task, dep, schemes = cifar_world
    run = task.run_config(num_rounds=9, eval_every=3, batch_size=4, seed=0)
    path = os.path.join(tmp_path, "cifar_fleet")
    kw = dict(seeds=(0, 2), flat=True)
    res_full = driver.run_fleet_task(task, schemes, dep.gains, run, **kw)
    res_part = driver.run_fleet_task(task, schemes, dep.gains, run, **kw,
                                     checkpoint_path=path, max_chunks=1)
    assert res_part.traces["active_devices"].shape[-1] < run.num_rounds
    res_res = driver.run_fleet_task(task, schemes, dep.gains, run, **kw,
                                    checkpoint_path=path, resume=True)
    assert _params_equal(res_full.params, res_res.params)
    for k in res_full.traces:
        assert np.array_equal(res_full.traces[k], res_res.traces[k]), k
    for (ta, ea), (tb, eb) in zip(res_full.evals, res_res.evals):
        assert ta == tb
        for k in ea:
            assert np.array_equal(np.asarray(ea[k]), np.asarray(eb[k])), k


def test_checkpoint_meta_rides_inside_npz(tmp_path):
    """The fleet-resume atomicity contract: meta (chunks_done etc.) lives
    INSIDE the npz archive, atomic with the arrays — a checkpoint is
    readable with no manifest at all, and load_flat never leaks the meta
    key into the restored state."""
    from repro.checkpoint import checkpoint as ckpt

    path = os.path.join(tmp_path, "fleet")
    tree = {"a": np.arange(4.0), "b": {"c": np.ones((2, 2))}}
    ckpt.save(path, tree, meta={"chunks_done": 3, "names": ["sca"]})
    os.remove(path + ".manifest.json")        # manifest is advisory only
    assert ckpt.load_meta(path) == {"chunks_done": 3, "names": ["sca"]}
    flat = ckpt.load_flat(path)
    assert "__meta__" not in flat and set(flat) == {"a", "b/c"}
    got = ckpt.restore_flat(flat, jax.tree.map(np.zeros_like, tree))
    assert np.array_equal(got["a"], tree["a"])
    assert np.array_equal(got["b"]["c"], tree["b"]["c"])


@needs_mesh
def test_cifar_conv_sharded_matches_vmap(cifar_world):
    """The cifar grid sharded over the debug mesh reproduces the
    single-device fleet: key-stream traces bitwise, norm-derived
    traces/evals/params to float rounding (the §Placement contract,
    now exercised by a conv workload)."""
    from repro.fl.placement import ShardedPlacement
    from repro.launch.mesh import make_debug_mesh

    task, dep, schemes = cifar_world
    run = task.run_config(num_rounds=6, eval_every=3, batch_size=4, seed=0)
    kw = dict(seeds=(0, 1), flat=True)
    res_v = driver.run_fleet_task(task, schemes, dep.gains, run, **kw)
    res_s = driver.run_fleet_task(task, schemes, dep.gains, run, **kw,
                                  placement=ShardedPlacement(
                                      make_debug_mesh(2, 2)))
    for k in ("active_devices", "noise_scale"):
        assert np.array_equal(res_v.traces[k], res_s.traces[k]), k
    np.testing.assert_allclose(res_v.traces["grad_norm_mean"],
                               res_s.traces["grad_norm_mean"],
                               rtol=1e-5, atol=1e-6)
    for (_, ea), (_, eb) in zip(res_v.evals, res_s.evals):
        for k in ea:
            np.testing.assert_allclose(np.asarray(ea[k]), np.asarray(eb[k]),
                                       rtol=1e-5, atol=3e-3, err_msg=k)
    diff = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
               for a, b in zip(jax.tree.leaves(res_v.params),
                               jax.tree.leaves(res_s.params)))
    assert diff < 1e-5, diff


@pytest.mark.parametrize("num_classes", [2, 10])
def test_top1_accuracy_is_argmax_accuracy(num_classes):
    """The image tasks' accuracy (max-and-compare) equals the argmax rule
    on every row, ties included: integer logits in a narrow range tie
    often, and argmax then takes the lowest class."""
    kl, ky = jax.random.split(jax.random.PRNGKey(3))
    logits = jax.random.randint(kl, (4, 500, num_classes), -2, 3
                                ).astype(jnp.float32)
    y = jax.random.randint(ky, (4, 500), 0, num_classes)
    got = jax.vmap(mlp.top1_accuracy)(logits, y)
    want = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32), -1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
