"""The fleet driver's cross-call program cache (DESIGN.md §Placement).

Contracts pinned here:
  * a ``run_fleet`` call whose traced programs would be an earlier call's
    reuses that call's jitted chunk and eval: it compiles nothing for the
    chunk lengths already run at the same shapes, counts a hit, reports
    ``wall_compile`` 0 and writes no ``chunk_compile`` span, and its
    params, traces and evals are bitwise those of the same call on an
    empty cache; a hit at new shapes counts their compile as a fresh
    call does;
  * every part of the key — gains content, the run fields the round body
    reads, uplink dtype, ``flat``, ``fuse_round``, ``loss_fn``, the
    diagnostics, fading, placement, cohort and scenario modes — makes a
    call that changes it a miss, and a part with no safe content key
    makes the call a miss that stores nothing;
  * a change to any ``FLRunConfig`` field gives bitwise the result of the
    same call on an empty cache;
  * both caches are bounded, and an evicted eval's closure is freed.
"""
import dataclasses
import gc
import sys
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.core import channel, power_control as pcm, scenarios as scn
from repro.data import partition, synthetic
from repro.fl import driver
from repro.fl.placement import VmapPlacement
from repro.fl.server import FLRunConfig
from repro.models import mlp
from repro.models.param import init_params
from tests.helpers import make_prm

HIDDEN = 8
# chunk lengths [1, 1]: one program
BASE_RUN = dict(eta=0.05, num_rounds=2, eval_every=2, batch_size=8)


@pytest.fixture(scope="module")
def world():
    dep = channel.deploy(channel.WirelessConfig(num_devices=10, seed=0))
    x, y, xt, yt = synthetic.mnist_like(40, seed=0)
    data = partition.stack_shards(partition.partition_by_label(x, y, 10,
                                                               seed=0))
    prm = make_prm(dep.gains, d=10000)
    params0 = init_params(mlp.mlp_defs(hidden=HIDDEN), jax.random.PRNGKey(0))
    xt_j, yt_j = jnp.asarray(xt), jnp.asarray(yt)
    ev = jax.jit(lambda p: {"acc": mlp.accuracy(p, xt_j, yt_j)})
    return dep, data, params0, ev, [pcm.make_power_control("vanilla", dep,
                                                           prm)]


@pytest.fixture(autouse=True)
def _empty_cache():
    driver.clear_chunk_cache()
    yield
    driver.clear_chunk_cache()


def _fleet(world, run=None, **kw):
    dep, data, params0, ev, pcs = world
    kw.setdefault("seeds", (0, 1))
    kw.setdefault("flat", True)
    loss_fn = kw.pop("loss_fn", mlp.mlp_loss)
    gains = kw.pop("gains", dep.gains)
    schemes = kw.pop("schemes", pcs)
    eval_fn = kw.pop("eval_fn", ev)
    return driver.run_fleet(loss_fn, params0, schemes, gains, data,
                            run or FLRunConfig(**BASE_RUN), eval_fn, **kw)


def _lookup(world, **kw):
    """A call that runs no round: it looks its chunk up and compiles
    nothing, which is all a test of the key needs."""
    run = kw.pop("run", FLRunConfig(**BASE_RUN))
    return _fleet(world, run=dataclasses.replace(run, num_rounds=0), **kw)


def _counted(fn):
    """(fn(), "hit" | "miss"): how the call's chunk lookup went."""
    before = driver.chunk_cache_stats()
    out = fn()
    after = driver.chunk_cache_stats()
    assert after["hit"] + after["miss"] == before["hit"] + before["miss"] + 1
    return out, ("hit" if after["hit"] > before["hit"] else "miss")


def _assert_same(a, b):
    for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert sorted(a.traces) == sorted(b.traces)
    for k in a.traces:
        np.testing.assert_array_equal(a.traces[k], b.traces[k], err_msg=k)
    assert [t for t, _ in a.evals] == [t for t, _ in b.evals]
    for (_, ea), (_, eb) in zip(a.evals, b.evals):
        for k in ea:
            np.testing.assert_array_equal(ea[k], eb[k], err_msg=k)


# ---------------------------------------------------------------------------
# reuse across calls, bitwise
# ---------------------------------------------------------------------------

def test_second_call_reuses_the_chunk_and_matches_a_fresh_cache(world):
    """Two calls that differ in seeds, etas and rounds (the same chunk
    lengths {1, 2}) share one compiled chunk; each is bitwise the same
    call made on an empty cache."""
    call_a = dict(run=FLRunConfig(**{**BASE_RUN, "num_rounds": 3}),
                  seeds=(0, 1), etas=[0.05])
    call_b = dict(run=FLRunConfig(**{**BASE_RUN, "num_rounds": 5}),
                  seeds=(2, 3), etas=[0.03])
    res_a, how = _counted(lambda: _fleet(world, **call_a))
    assert how == "miss" and res_a.wall_compile > 0
    (chunk,) = driver._chunk_cache.values()
    with telemetry.assert_no_recompile(chunk):
        res_b, how = _counted(lambda: _fleet(world, **call_b))
    assert how == "hit" and res_b.wall_compile == 0.0
    assert res_b.traces["noise_scale"].shape == (1, 2, 5)

    driver.clear_chunk_cache()
    fresh_b, how = _counted(lambda: _fleet(world, **call_b))
    assert how == "miss"
    _assert_same(res_b, fresh_b)
    hit_a, how = _counted(lambda: _fleet(world, **call_a))
    assert how == "hit"
    _assert_same(res_a, hit_a)


def test_hit_writes_no_chunk_compile_span(world, tmp_path):
    def traced(name):
        tel = telemetry.Telemetry(run_dir=str(tmp_path / name),
                                  diagnostics=False)
        res = _fleet(world, telemetry=tel)
        return res, telemetry.read_events(tel.run_dir)

    _, first = traced("first")
    res, second = traced("second")
    config = [e["chunk_cache"] for e in first + second
              if e["ev"] == "fleet_config"]
    assert config == ["miss", "hit"]
    assert sum(e["ev"] == "chunk_compile" for e in first) == 1
    assert not any(e["ev"] == "chunk_compile" for e in second)
    assert sum(e["ev"] == "chunk_exec" for e in second) == 2
    assert res.wall_compile == 0.0 and res.wall_exec == res.wall


def test_hit_at_new_shapes_counts_their_compile(world, tmp_path):
    """Another seed count is a hit on the key (shapes are not in it) but
    traces the reused chunk at the new shapes: the call reports that
    compile and writes its ``chunk_compile`` span, and its results are
    bitwise the same call's on an empty cache."""
    _fleet(world, seeds=(0, 1))
    tel = telemetry.Telemetry(run_dir=str(tmp_path / "wider"),
                              diagnostics=False)
    res, how = _counted(lambda: _fleet(world, seeds=(0, 1, 2),
                                       telemetry=tel))
    assert how == "hit" and res.wall_compile > 0
    events = telemetry.read_events(tel.run_dir)
    assert [e["chunk_cache"] for e in events
            if e["ev"] == "fleet_config"] == ["hit"]
    assert sum(e["ev"] == "chunk_compile" for e in events) == 1
    driver.clear_chunk_cache()
    _assert_same(res, _fleet(world, seeds=(0, 1, 2)))


# ---------------------------------------------------------------------------
# the key: each part misses, equal content hits, no safe key misses
# ---------------------------------------------------------------------------

def _population(dep):
    spec = scn.PopulationSpec(
        size=40, shadowing=scn.ShadowingSpec(sigma_db=6.0),
        fading=channel.FadingSpec(family="rician", rician_k=3.0),
        dynamics=scn.DynamicsSpec(rho=0.9), sampling="traffic",
        traffic_sigma=1.0, seed=7)
    return scn.Population(spec=spec)


def _scenario_call(world):
    stack = scn.stack_scenarios(["disk_rayleigh"], seed=0)
    dep = scn.realize(scn.get_scenario("disk_rayleigh"), seed=0)
    prm = scn.make_ota_params(dep, d=10000, gmax=10.0, eta=0.05,
                              kappa_sq=4.0)
    return dict(schemes=[pcm.make_power_control("vanilla", dep, prm)],
                gains=None, scenarios=stack)


def _run_with(**fields):
    return {"run": FLRunConfig(**{**BASE_RUN, **fields})}


MISSES = {
    "gains": lambda w, tmp: {"gains": np.asarray(w[0].gains) * 1.5},
    "batch_size": lambda w, tmp: _run_with(batch_size=16),
    "clip_to_gmax": lambda w, tmp: _run_with(clip_to_gmax=False),
    "gmax": lambda w, tmp: _run_with(gmax=5.0),
    "uplink_dtype": lambda w, tmp: {"uplink_dtype": "bf16"},
    "flat": lambda w, tmp: {"flat": False},
    "fuse_round": lambda w, tmp: {"fuse_round": False},
    "loss_fn": lambda w, tmp: {"loss_fn": lambda p, b: mlp.mlp_loss(p, b)},
    "diagnostics": lambda w, tmp: {"telemetry": telemetry.Telemetry(
        run_dir=str(tmp / "diag"), trace=False)},
    "fading": lambda w, tmp: {"fading": scn.FadingProcess(
        gains=jnp.asarray(w[0].gains), rho=0.5)},
    "placement": lambda w, tmp: {"placement": VmapPlacement(donate=False)},
    "cohort": lambda w, tmp: {"population": _population(w[0]),
                              "cohort_size": 10},
    "scenario": lambda w, tmp: _scenario_call(w),
}


@pytest.mark.parametrize("part", sorted(MISSES))
def test_each_key_part_misses(world, tmp_path, part):
    _lookup(world)
    _, how = _counted(lambda: _lookup(world, **MISSES[part](world, tmp_path)))
    assert how == "miss", part
    assert driver.chunk_cache_stats()["chunks"] == 2


def _fading(gains, rho):
    return scn.FadingProcess(gains=jnp.asarray(gains), rho=rho)


HITS = {
    "gains_copy": lambda w, tmp: {"gains": np.array(w[0].gains)},
    "run_copy": lambda w, tmp: _run_with(),
    "placement_copy": lambda w, tmp: {"placement": VmapPlacement()},
    "trace_only_telemetry": lambda w, tmp: {"telemetry": telemetry.Telemetry(
        run_dir=str(tmp / "trace"), diagnostics=False)},
}


@pytest.mark.parametrize("part", sorted(HITS))
def test_equal_content_hits(world, tmp_path, part):
    _lookup(world)
    _, how = _counted(lambda: _lookup(world, **HITS[part](world, tmp_path)))
    assert how == "hit", part


def test_fading_is_keyed_by_content(world):
    gains = world[0].gains
    _lookup(world, fading=_fading(gains, 0.5))
    _, how = _counted(lambda: _lookup(world, fading=_fading(gains, 0.5)))
    assert how == "hit"
    _, how = _counted(lambda: _lookup(world, fading=_fading(gains, 0.6)))
    assert how == "miss"


class _OpaqueFading:
    """A fading process with no content key: not a dataclass."""

    def __init__(self, inner):
        self._inner = inner

    def init_batch(self, keys):
        return self._inner.init_batch(keys)

    def step(self, state, key):
        return self._inner.step(state, key)


def test_part_without_content_key_misses_and_stores_nothing(world):
    fp = _OpaqueFading(_fading(world[0].gains, 0.5))
    res, how = _counted(lambda: _fleet(world, fading=fp))
    assert how == "miss"
    again, how = _counted(lambda: _fleet(world, fading=fp))
    assert how == "miss" and again.wall_compile > 0
    assert driver.chunk_cache_stats()["chunks"] == 0
    _assert_same(res, again)


# ---------------------------------------------------------------------------
# every FLRunConfig field: a miss, or bitwise the fresh-cache result
# ---------------------------------------------------------------------------

FIELD_CHANGES = {"eta": 0.02, "num_rounds": 4, "gmax": 5.0,
                 "batch_size": 16, "eval_every": 1, "seed": 3,
                 "clip_to_gmax": False, "uplink_dtype": "bf16"}


def test_field_changes_cover_the_run_config():
    assert set(FIELD_CHANGES) == {f.name for f in
                                  dataclasses.fields(FLRunConfig)}


@pytest.mark.parametrize("field", sorted(FIELD_CHANGES))
def test_run_field_change_matches_a_fresh_cache(world, field):
    changed = FLRunConfig(**{**BASE_RUN, field: FIELD_CHANGES[field]})
    _fleet(world, seeds=None)
    after_base = _fleet(world, run=changed, seeds=None)
    driver.clear_chunk_cache()
    fresh = _fleet(world, run=changed, seeds=None)
    _assert_same(after_base, fresh)


# ---------------------------------------------------------------------------
# bounds
# ---------------------------------------------------------------------------

def test_cache_is_least_recently_used_and_bounded():
    cache = driver.OrderedDict()
    for i in range(3):
        assert driver._cached(cache, i, 2, lambda i=i: f"v{i}") \
            == (f"v{i}", False)
    assert list(cache) == [1, 2]
    assert driver._cached(cache, 1, 2, lambda: "new") == ("v1", True)
    driver._cached(cache, 3, 2, lambda: "v3")
    assert list(cache) == [1, 3]
    assert driver._cached(cache, None, 2, lambda: "x") == ("x", False)
    assert driver._cached(cache, [1], 2, lambda: "y") == ("y", False)
    assert list(cache) == [1, 3]


def test_cache_holds_under_concurrent_lookups():
    """More threads than cores look up and fill one bounded cache with a
    short switch interval: every lookup returns its own key's value and
    the bound holds."""
    cache, errors = driver.OrderedDict(), []

    def worker(w):
        try:
            for i in range(300):
                key = (w + i) % 7
                value, _ = driver._cached(cache, key, 4, lambda k=key: k)
                assert value == key and len(cache) <= 4
        except AssertionError as e:
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors and len(cache) == 4


def test_eval_cache_is_bounded_and_frees_evicted_evals(world):
    def make_eval(i):
        xt = jnp.full((4, 784), float(i))
        return lambda p: {"out": jnp.sum(mlp.mlp_forward(p, xt))}

    first = make_eval(0)
    gone = weakref.ref(first)
    _lookup(world, eval_fn=first)
    (jitted,) = driver._eval_cache.values()
    _lookup(world, eval_fn=first)
    assert driver._eval_cache[first] is jitted
    del first, jitted
    for i in range(1, driver._EVAL_CACHE_SIZE + 2):
        _lookup(world, eval_fn=make_eval(i))
    assert driver.chunk_cache_stats()["evals"] == driver._EVAL_CACHE_SIZE
    gc.collect()
    assert gone() is None
