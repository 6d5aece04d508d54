"""Fleet telemetry subsystem (DESIGN.md §Telemetry).

Contracts pinned here:
  * the JSONL tracer appends whole lines, tolerates killed-mid-write
    partial lines, and ``resume(start_chunk)`` prunes a re-opened log to
    ONE consistent execution — run id preserved, completed chunks kept,
    superseded/untagged events dropped, one run_resume marker.
  * telemetry OFF is the default and the driver's results are bitwise
    identical with telemetry ON — the diagnostics ride extra ``bv_*``
    trace keys; every pre-existing key and the params are unchanged.
  * the bv_* diagnostics realize Theorem 1 per round: Ideal FedAvg has
    exactly zero noise variance and ~zero bias power; noisy schemes
    don't.
  * a telemetry-enabled kill-and-resume produces one event log: single
    run id, exactly one run_resume, no duplicated chunk_exec spans, and
    numerics bitwise vs the uninterrupted telemetry-on run.
  * the report tool renders a real run directory without error.
  * the round step's layers and the eval are name scopes in the compiled
    programs' op metadata; JAX's compile phases become ``compile.*``
    spans inside the chunk call or eval that paid them, every event
    carries wall-clock ``t0_ns``/``t1_ns``, a chunk's exec span starts
    where its compile ends, and ``FLResult.wall_compile`` is the summed
    wall of the calls that compiled.
"""
import io
import json
import os
import re
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax._src import monitoring as jax_monitoring

from repro import telemetry
from repro.telemetry import trace as tlm_trace
from repro.core import channel, power_control as pcm, scenarios as scn
from repro.data import partition, synthetic
from repro.fl import driver, engine as eng
from repro.fl.server import FLRunConfig
from repro.models import mlp
from repro.models.param import init_params
from repro.telemetry import report as tlm_report
from tests.helpers import make_prm


def _params_equal(a, b):
    return all(bool(np.array_equal(np.asarray(x), np.asarray(y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# tracer: append, read-back, kill-tolerance, resume pruning
# ---------------------------------------------------------------------------

def test_tracer_roundtrip_and_partial_lines(tmp_path):
    run_dir = str(tmp_path / "run")
    tr = telemetry.Tracer(run_dir)
    with tr.ctx(chunk=0):
        tr.event("stage", dur=0.5, tick=np.int64(3))      # numpy jsonifies
    with tr.span("eval", chunk=0):
        pass
    # a kill mid-write leaves a partial trailing line: reader skips it
    with open(tr.path, "a") as f:
        f.write('{"ev": "chunk_exec", "chunk": 1, "trunc')
    events = telemetry.read_events(run_dir)
    assert [e["ev"] for e in events] == ["run_start", "stage", "eval"]
    assert events[1]["chunk"] == 0 and events[1]["tick"] == 3
    assert events[2]["dur"] >= 0
    assert len({e["run"] for e in events}) == 1
    # monotonic clock is ordered even if wall steps
    assert events[0]["mono"] <= events[1]["mono"] <= events[2]["mono"]


def test_tracer_resume_prunes_to_completed_chunks(tmp_path):
    run_dir = str(tmp_path / "run")
    tr = telemetry.Tracer(run_dir)
    run_id = tr.run_id
    for ci in range(3):
        tr.event("chunk_exec", chunk=ci)
    tr.event("sca_solve", chunk=2)       # staging-thread event, re-run chunk
    tr.event("run_end")                  # untagged, superseded by the resume
    # killed here; a new process re-opens and fast-forwards to chunk 2
    tr2 = telemetry.Tracer(run_dir, fresh=False)
    assert tr2.run_id == run_id
    tr2.resume(start_chunk=2)
    tr2.event("chunk_exec", chunk=2)
    events = telemetry.read_events(run_dir)
    assert [e["ev"] for e in events] == [
        "run_start", "chunk_exec", "chunk_exec", "run_resume", "chunk_exec"]
    assert [e.get("chunk") for e in events if e["ev"] == "chunk_exec"] \
        == [0, 1, 2]
    assert {e["run"] for e in events} == {run_id}
    # fresh=True on the same dir starts over with a new id
    tr3 = telemetry.Tracer(run_dir)
    assert tr3.run_id != run_id
    assert [e["ev"] for e in telemetry.read_events(run_dir)] == ["run_start"]


def test_tracer_missing_log_degrades_to_fresh(tmp_path):
    tr = telemetry.Tracer(str(tmp_path / "nothing"), fresh=False)
    events = telemetry.read_events(tr.run_dir)
    assert [e["ev"] for e in events] == ["run_start"]


# ---------------------------------------------------------------------------
# driver integration: bitwise-off guarantee + diagnostics + resume log
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def pop_world():
    dep = channel.deploy(channel.WirelessConfig(num_devices=10, seed=0))
    x, y, xt, yt = synthetic.mnist_like(40, seed=0)
    data = partition.stack_shards(partition.partition_by_label(x, y, 10,
                                                               seed=0))
    prm = make_prm(dep.gains, d=10000)
    params0 = init_params(mlp.mlp_defs(hidden=32), jax.random.PRNGKey(0))
    xt_j, yt_j = jnp.asarray(xt), jnp.asarray(yt)
    ev = jax.jit(lambda p: {"acc": mlp.accuracy(p, xt_j, yt_j)})
    spec = scn.PopulationSpec(
        size=200, shadowing=scn.ShadowingSpec(sigma_db=6.0),
        fading=channel.FadingSpec(family="rician", rician_k=3.0),
        dynamics=scn.DynamicsSpec(rho=0.9), sampling="traffic",
        traffic_sigma=1.0, seed=7)
    pop = scn.Population(spec=spec)
    return dep, prm, data, params0, ev, pop


def test_telemetry_on_is_bitwise_off_plus_diagnostics(pop_world, tmp_path):
    """telemetry=None vs telemetry=Telemetry(...): identical params and
    pre-existing traces; ON adds the per-round bv_* Theorem-1 cells —
    Ideal FedAvg with exactly zero realized noise variance and ~zero bias
    power, the noisy SCA design with neither."""
    dep, prm, data, params0, ev, pop = pop_world
    schemes = [pcm.make_power_control(n, dep, prm) for n in ("sca", "ideal")]
    run = FLRunConfig(eta=0.05, num_rounds=6, eval_every=3)
    kw = dict(seeds=(0, 2), flat=False, population=pop, cohort_size=10,
              cohort_rounds=3)
    args = (mlp.mlp_loss, params0, schemes, dep.gains, data, run, ev)
    res_off = driver.run_fleet(*args, **kw)
    tel = telemetry.Telemetry(run_dir=str(tmp_path / "run"),
                              kappa_sq=float(prm.kappa_sq))
    res_on = driver.run_fleet(*args, **kw, telemetry=tel)

    assert _params_equal(res_off.params, res_on.params)
    for k in res_off.traces:
        assert np.array_equal(res_off.traces[k], res_on.traces[k]), k
    bv = sorted(k for k in res_on.traces if telemetry.is_diagnostic(k))
    assert bv == ["bv_bias_power", "bv_chan_power", "bv_noise_var",
                  "bv_weight_dev"]
    for k in bv:
        assert res_on.traces[k].shape == (2, 2, run.num_rounds)
        assert k not in res_off.traces
    # Theorem-1 sanity: ideal aggregation is the zero-bias zero-noise cell
    sca, ideal = 0, 1
    assert np.all(res_on.traces["bv_noise_var"][ideal] == 0.0)
    assert np.all(res_on.traces["bv_bias_power"][ideal] < 1e-10)
    assert np.all(res_on.traces["bv_noise_var"][sca] > 0.0)
    assert np.any(res_on.traces["bv_bias_power"][sca] > 0.0)
    # stage_walls: the per-chunk lane profile the bench breakdown reads
    lengths = eng.chunk_lengths(run.num_rounds, run.eval_every, True, 3)
    assert res_on.stage_walls is not None
    assert len(res_on.stage_walls) == len(lengths)
    assert all(w >= 0 for w in res_on.stage_walls)


def _listeners():
    return (jax_monitoring.get_event_time_span_listeners(),
            jax_monitoring.get_scalar_listeners())


class _CountingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation`` and counts entries."""
    entered = 0

    def __init__(self, name, **kw):
        self.name = name

    def __enter__(self):
        type(self).entered += 1
        return self

    def __exit__(self, *exc):
        return False


def test_telemetry_off_adds_no_traces_and_no_files(pop_world, tmp_path,
                                                   monkeypatch):
    dep, prm, data, params0, ev, pop = pop_world
    schemes = [pcm.make_power_control("ideal", dep, prm)]
    run = FLRunConfig(eta=0.05, num_rounds=2, eval_every=2)
    listeners = _listeners()
    monkeypatch.setattr(_CountingAnnotation, "entered", 0)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    seen = []
    monkeypatch.setattr(telemetry, "set_compile_tracer", seen.append)
    res = driver.run_fleet(mlp.mlp_loss, params0, schemes, dep.gains, data,
                           run, ev, flat=False, population=pop,
                           cohort_size=10)
    assert not any(telemetry.is_diagnostic(k) for k in res.traces)
    assert list(tmp_path.iterdir()) == []
    # no monitoring listener registered or left, no annotation entered
    assert seen == [] and tlm_trace._COMPILE_TRACER is None
    after = _listeners()
    assert after == listeners
    assert tlm_trace._on_compile_phase not in after[0]
    assert tlm_trace._on_compile_start not in after[1]
    assert _CountingAnnotation.entered == 0


def test_telemetry_kill_and_resume_single_log(pop_world, tmp_path):
    """adaptive_sca streaming run preempted after 2 chunks, resumed with
    the SAME run dir: numerics bitwise vs the uninterrupted telemetry-on
    run; the event log keeps one run id, gains exactly one run_resume,
    and no chunk_exec span is duplicated or lost."""
    dep, prm, data, params0, ev, pop = pop_world
    pc = pcm.make_power_control("adaptive_sca", dep, prm)
    run = FLRunConfig(eta=0.05, num_rounds=8, eval_every=4)
    kw = dict(seeds=(0,), flat=False, population=pop, cohort_size=10,
              cohort_rounds=2, stream=True)
    args = (mlp.mlp_loss, params0, [pc], dep.gains, data, run, ev)

    full_dir = str(tmp_path / "full")
    res_full = driver.run_fleet(
        *args, **kw, telemetry=telemetry.Telemetry(run_dir=full_dir))
    full_events = telemetry.read_events(full_dir)
    chunks_full = sorted(e["chunk"] for e in full_events
                         if e["ev"] == "chunk_exec")

    res_dir = str(tmp_path / "resumed")
    tel = telemetry.Telemetry(run_dir=res_dir)
    path = str(tmp_path / "fleet")
    driver.run_fleet(*args, **kw, checkpoint_path=path, max_chunks=2,
                     telemetry=tel)
    pre = telemetry.read_events(res_dir)
    res_res = driver.run_fleet(*args, **kw, checkpoint_path=path,
                               resume=True, telemetry=tel)

    assert _params_equal(res_full.params, res_res.params)
    for k in res_full.traces:
        assert np.array_equal(res_full.traces[k], res_res.traces[k]), k

    events = telemetry.read_events(res_dir)
    assert {e["run"] for e in events} == {pre[0]["run"]}   # id preserved
    assert sum(1 for e in events if e["ev"] == "run_start") == 1
    assert sum(1 for e in events if e["ev"] == "run_resume") == 1
    chunks = [e["chunk"] for e in events if e["ev"] == "chunk_exec"]
    assert len(chunks) == len(set(chunks)), "duplicated chunk span"
    assert sorted(chunks) == chunks_full, "lost chunk span"
    # sca_solve events from the staging worker are chunk-tagged, so the
    # pruned log attributes every solve to exactly one surviving chunk
    solves = [e for e in events if e["ev"] == "sca_solve"]
    assert solves and all(isinstance(e.get("chunk"), int) for e in solves)


def test_resume_telemetry_does_not_change_numbers_vs_off(pop_world,
                                                         tmp_path):
    """The same kill-and-resume WITHOUT telemetry: bitwise equal to the
    telemetry-on resumed run (the observability never leaks into math)."""
    dep, prm, data, params0, ev, pop = pop_world
    pc = pcm.make_power_control("adaptive_sca", dep, prm)
    run = FLRunConfig(eta=0.05, num_rounds=8, eval_every=4)
    kw = dict(seeds=(0,), flat=False, population=pop, cohort_size=10,
              cohort_rounds=2, stream=True)
    args = (mlp.mlp_loss, params0, [pc], dep.gains, data, run, ev)
    p_off = str(tmp_path / "off")
    driver.run_fleet(*args, **kw, checkpoint_path=p_off, max_chunks=2)
    res_off = driver.run_fleet(*args, **kw, checkpoint_path=p_off,
                               resume=True)
    p_on = str(tmp_path / "on")
    tel = telemetry.Telemetry(run_dir=str(tmp_path / "run"))
    driver.run_fleet(*args, **kw, checkpoint_path=p_on, max_chunks=2,
                     telemetry=tel)
    res_on = driver.run_fleet(*args, **kw, checkpoint_path=p_on,
                              resume=True, telemetry=tel)
    assert _params_equal(res_off.params, res_on.params)
    for k in res_off.traces:
        assert np.array_equal(res_off.traces[k], res_on.traces[k]), k


# ---------------------------------------------------------------------------
# report tool
# ---------------------------------------------------------------------------

def test_report_renders_run_dir(pop_world, tmp_path, cold_fleet_cache):
    dep, prm, data, params0, ev, pop = pop_world
    pc = pcm.make_power_control("adaptive_sca", dep, prm)
    run = FLRunConfig(eta=0.05, num_rounds=6, eval_every=3)

    def fleet(run_dir):
        tel = telemetry.Telemetry(run_dir=run_dir,
                                  kappa_sq=float(prm.kappa_sq))
        driver.run_fleet(mlp.mlp_loss, params0, [pc], dep.gains, data, run,
                         ev, seeds=(0,), flat=False, population=pop,
                         cohort_size=10, cohort_rounds=2,
                         checkpoint_path=os.path.join(run_dir, "fleet"),
                         telemetry=tel)
        out = io.StringIO()
        with redirect_stdout(out):
            tlm_report.main([run_dir])
        return out.getvalue()

    text = fleet(str(tmp_path / "run"))
    for section in ("staging-lane timeline", "SCA solver",
                    "bias--variance trajectory", "cohort staleness",
                    "recompilation audit"):
        assert section in text, section
    assert "bv_bias_power" in text and "bv_noise_var" in text
    assert "staging overlap" in text
    audit = text.split("recompilation audit")[1]
    assert "chunk_cache: miss" in audit and "no recompilation" in audit
    # the same fleet again: its chunk programs come from the driver's
    # cache, and the audit reads the missing compiles as that reuse
    audit = fleet(str(tmp_path / "hit")).split("recompilation audit")[1]
    assert "chunk_cache: hit" in audit
    assert "no compiles recorded; the chunk cache held every program" \
        in audit
    with pytest.raises(SystemExit, match="events.jsonl"):
        tlm_report.main([str(tmp_path / "empty")])


def test_run_dir_string_shorthand(pop_world, tmp_path):
    """run_fleet(telemetry=<str>) builds a default Telemetry — the CLI
    convenience path."""
    dep, prm, data, params0, ev, pop = pop_world
    schemes = [pcm.make_power_control("ideal", dep, prm)]
    run = FLRunConfig(eta=0.05, num_rounds=2, eval_every=2)
    run_dir = str(tmp_path / "run")
    res = driver.run_fleet(mlp.mlp_loss, params0, schemes, dep.gains, data,
                           run, ev, flat=False, population=pop,
                           cohort_size=10, telemetry=run_dir)
    assert any(telemetry.is_diagnostic(k) for k in res.traces)
    assert os.path.exists(os.path.join(run_dir, telemetry.EVENTS_FILE))


# ---------------------------------------------------------------------------
# name scopes and compile phases
# ---------------------------------------------------------------------------

SCOPES = ("fl.grad", "fl.channel", "fl.step", "fl.uplink")


def _op_scopes(hlo_text: str) -> set:
    """The ``fl.`` scopes named in the op_name metadata of an HLO text."""
    names = re.findall(r'op_name="([^"]*)"', hlo_text)
    return {m for n in names for m in re.findall(r"fl\.[a-z]+", n)}


@pytest.mark.parametrize("flat", [False, True])
def test_compiled_chunk_names_the_round_step_scopes(pop_world, flat):
    from repro.fl.placement import VmapPlacement

    dep, prm, data, params0, ev, _ = pop_world
    schemes = pcm.stack_schemes(
        [pcm.make_power_control(n, dep, prm) for n in ("sca", "vanilla")])
    run = FLRunConfig(eta=0.05, num_rounds=2, eval_every=2, batch_size=8)
    body = eng.make_round_body(mlp.mlp_loss, dep.gains, run, flat=flat)
    chunk = VmapPlacement(donate=False).build_chunk(body, adaptive=False)
    params_b = jax.tree.map(
        lambda a: jnp.tile(a[None, None], (2, 1) + (1,) * a.ndim), params0)
    keys_b = jnp.tile(jax.random.PRNGKey(0)[None, None], (2, 1, 1))
    text = chunk.lower(schemes, jnp.asarray([0.05, 0.05]), params_b, None,
                       keys_b, tuple(jnp.asarray(a) for a in data),
                       length=2).compile().as_text()
    want = set(SCOPES) if flat else set(SCOPES) - {"fl.uplink"}
    assert _op_scopes(text) == want
    # the eval's program carries its own scope
    text = driver.grid_eval(ev).lower(params_b).compile().as_text()
    assert _op_scopes(text) == {"fl.eval"}


def test_round_step_kernel_separates_uplink_from_step():
    from repro.kernels import ops

    grads = {"a": jnp.ones((10, 300)), "b": jnp.ones((10, 7, 5))}
    params = {"a": jnp.ones((300,)), "b": jnp.ones((7, 5))}

    def tail(g, p):
        with jax.named_scope("fl.step"):
            return ops.ota_round_step_pytree(
                g, jnp.ones(10), 0.1, jax.random.PRNGKey(0), p, 0.05,
                use_kernel=True, interpret=True)

    text = jax.jit(tail).lower(grads, params).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    inner = {re.findall(r"fl\.[a-z]+", n)[-1] for n in names
             if "fl." in n}
    assert inner == {"fl.step", "fl.uplink"}
    # the layout work is fl.uplink, the kernel itself stays fl.step
    assert any(n.endswith("/pad") and "fl.uplink" in n for n in names)


@pytest.fixture(scope="module")
def traced_fleet(pop_world, tmp_path_factory):
    """A plain telemetry-on fleet: three chunk lengths, an eval each."""
    dep, prm, data, params0, ev, _ = pop_world
    schemes = [pcm.make_power_control(n, dep, prm)
               for n in ("sca", "vanilla")]
    # chunk lengths [1, 3, 2]: three programs
    run = FLRunConfig(eta=0.05, num_rounds=6, eval_every=3, batch_size=8)
    run_dir = str(tmp_path_factory.mktemp("traced") / "run")
    listeners = _listeners()
    # compile as in a fresh process: an earlier test may have cached these
    # programs (the module scope rules out the cold_fleet_cache fixture)
    driver.clear_chunk_cache()
    res = driver.run_fleet(
        mlp.mlp_loss, params0, schemes, dep.gains, data, run, ev,
        seeds=(0, 1), flat=True,
        telemetry=telemetry.Telemetry(run_dir=run_dir, diagnostics=False))
    return res, telemetry.read_events(run_dir), listeners


def test_compile_phases_are_spans_inside_the_calls_that_paid_them(
        traced_fleet):
    res, events, listeners = traced_fleet
    phases = [e for e in events if e["ev"].startswith("compile.")]
    kinds = {e["ev"] for e in phases}
    assert kinds == {"compile.jaxpr_trace", "compile.lower",
                     "compile.backend"}
    for e in events:
        assert e["t0_ns"] <= e["t1_ns"] and "mono" in e, e
    holders = [e for e in events if e["ev"] in ("chunk_compile", "eval")]
    for e in phases:
        assert e["dur"] > 0 or e["ev"] == "compile.jaxpr_trace", e
        assert isinstance(e.get("fun"), str) and e.get("chunk") is not None
        assert any(h["t0_ns"] <= e["t0_ns"] and e["t1_ns"] <= h["t1_ns"]
                   for h in holders), e
    for kind in kinds:
        assert max(e["dur"] for e in phases if e["ev"] == kind) > 0, kind
    # the chunk's own trace, lowering and backend step, and the eval's
    funs = {(e["ev"], e["fun"]) for e in phases}
    assert ("compile.backend", "jit(fleet_chunk)") in funs
    evals = [e for e in events if e["ev"] == "eval"]
    assert any(e["ev"] == "compile.backend" and e["fun"] != "jit(fleet_chunk)"
               and evals[0]["t0_ns"] <= e["t0_ns"] <= evals[0]["t1_ns"]
               for e in phases)
    # the end ``mono`` and ``dur`` place a phase on the monotonic clock
    # where its wall-clock stamps place it
    for e in phases:
        assert e["dur"] == pytest.approx((e["t1_ns"] - e["t0_ns"]) / 1e9,
                                         abs=2e-6)
    # only a thread's outermost phases are written: none holds another
    for e in phases:
        assert not any(o is not e and o["t0_ns"] <= e["t0_ns"]
                       and e["t1_ns"] <= o["t1_ns"] for o in phases), e
    # the listeners are gone once the run ends
    assert _listeners() == listeners
    assert tlm_trace._COMPILE_TRACER is None


def test_chunk_exec_starts_where_its_compile_ends(traced_fleet):
    res, events, _ = traced_fleet
    compiles = {e["chunk"]: e for e in events if e["ev"] == "chunk_compile"}
    execs = {e["chunk"]: e for e in events if e["ev"] == "chunk_exec"}
    assert len(compiles) == 3 and set(compiles) <= set(execs)
    for ci, c in compiles.items():
        assert execs[ci]["t0_ns"] >= c["t1_ns"], ci
    # execution of a chunk that compiled is not the compile's length
    assert all(execs[ci]["dur"] < c["dur"] for ci, c in compiles.items())


def test_wall_compile_is_the_summed_compile_calls(traced_fleet, pop_world,
                                                  cold_fleet_cache):
    res, events, _ = traced_fleet
    spans = [e["dur"] for e in events if e["ev"] == "chunk_compile"]
    assert res.wall_compile == pytest.approx(sum(spans), abs=1e-5 * 3)
    assert res.wall_exec == pytest.approx(res.wall - res.wall_compile)
    assert 0 < res.wall_compile < res.wall
    # the same split with telemetry off: every chunk length compiles once
    dep, prm, data, params0, ev, _ = pop_world
    run = FLRunConfig(eta=0.05, num_rounds=3, eval_every=2, batch_size=8)
    pc = pcm.make_power_control("vanilla", dep, prm)
    off = driver.run_fleet(mlp.mlp_loss, params0, [pc], dep.gains, data,
                           run, ev, flat=False)
    assert 0 < off.wall_compile < off.wall
    single = eng.run_rounds(mlp.mlp_loss, params0, pc, dep.gains, data, run,
                            ev)
    assert 0 < single.wall_compile < single.wall
