"""JAX-native SCA solver subsystem (repro.solvers, DESIGN.md §Solvers).

Three contracts:
  * theory parity: the jnp port of the Theorem-1 quantities agrees with
    the float64 numpy/scipy reference (core/theory.py) to 1e-6 relative
    across all fading families and random OTAParams (hypothesis);
  * solver quality: ``solve``/``solve_batch`` match the scipy SLSQP
    oracle's (P1) objective (1e-3 required, ~1e-6 typical), with monotone
    descent history;
  * adaptive engine: ``AdaptiveSCA`` inside ``run_fleet`` re-designs from
    the drifting Gauss-Markov CSI (operating point moves) while static-CSI
    runs stay bit-identical to the plain ``sca`` scheme.
"""
import numpy as np
import pytest

try:        # only the property test needs hypothesis (CI installs it)
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

import jax
import jax.numpy as jnp

from repro import solvers
from repro.core import channel, sca, theory
from repro.core.channel import FadingSpec
from repro.solvers import theory_jax as tj
from tests.helpers import make_prm


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30))


def _random_prm(seed, n, family):
    rng = np.random.default_rng(seed)
    dists = rng.uniform(80.0, 1750.0, size=n)
    gains = channel.average_gain(dists)
    if family == "rayleigh":
        fading = None
    elif family == "rician":
        fading = FadingSpec(family="rician",
                            rician_k=rng.uniform(0.2, 12.0, size=n))
    else:
        fading = FadingSpec(family="nakagami",
                            nakagami_m=rng.uniform(0.6, 4.0, size=n))
    return make_prm(gains, d=814090, sigma=float(rng.uniform(0.0, 2.0)),
                    kappa_sq=float(rng.uniform(0.5, 16.0)), fading=fading)


# ---------------------------------------------------------------------------
# jnp-vs-numpy theory parity (satellite: 1e-6 across families)
# ---------------------------------------------------------------------------

def _check_theory_parity(seed, n, family):
    prm = _random_prm(seed, n, family)
    with jax.enable_x64(True):
        pj = tj.from_ota(prm)
        gm_np = theory.gamma_max(prm)
        gm_j = np.asarray(tj.gamma_max(pj))
        assert _rel(gm_j, gm_np) < 1e-6

        gamma = 0.7 * gm_np
        assert _rel(np.asarray(tj.log_alpha_of_gamma(jnp.asarray(gamma), pj)),
                    theory.log_alpha_of_gamma(gamma, prm)) < 1e-6
        z_np = theory.zeta_terms(gamma, prm)
        z_j = tj.zeta_terms(jnp.asarray(gamma), pj)
        for k in ("transmission", "minibatch", "noise", "total"):
            assert abs(float(z_j[k]) - z_np[k]) \
                <= 1e-6 * max(1e-30, abs(z_np["total"])), k
        assert _rel(float(tj.p1_objective(jnp.asarray(gamma), pj)),
                    theory.p1_objective(gamma, prm)) < 1e-6


@pytest.mark.parametrize("family", ["rayleigh", "rician", "nakagami"])
@pytest.mark.parametrize("seed,n", [(0, 5), (7, 10)])
def test_theory_parity_fixed(seed, n, family):
    _check_theory_parity(seed, n, family)


if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(st.integers(min_value=0, max_value=10_000),
           st.integers(min_value=3, max_value=12),
           st.sampled_from(["rayleigh", "rician", "nakagami"]))
    def test_theory_parity_property(seed, n, family):
        _check_theory_parity(seed, n, family)


@pytest.mark.parametrize("family", ["rayleigh", "rician", "nakagami"])
def test_theory_parity_with_dropout(family):
    prm = _random_prm(3, 8, family).replace(dropout=0.15)
    with jax.enable_x64(True):
        pj = tj.from_ota(prm)
        gm = theory.gamma_max(prm)
        assert _rel(np.asarray(tj.alpha_max(pj)), theory.alpha_max(prm)) < 1e-6
        gamma = 0.5 * gm
        assert _rel(np.asarray(tj.alpha_of_gamma(jnp.asarray(gamma), pj)),
                    theory.alpha_of_gamma(gamma, prm)) < 1e-6
        assert _rel(float(tj.p1_objective(jnp.asarray(gamma), pj)),
                    theory.p1_objective(gamma, prm)) < 1e-6


def test_marcum_q1_matches_scipy_rice():
    from scipy.stats import rice
    with jax.enable_x64(True):
        a = jnp.asarray([0.0, 0.3, 1.0, 3.0, 7.0], jnp.float64)[:, None]
        b = jnp.asarray([0.1, 0.5, 1.0, 2.0, 5.0], jnp.float64)[None, :]
        q = np.asarray(tj.marcum_q1(jnp.broadcast_to(a, (5, 5)),
                                    jnp.broadcast_to(b, (5, 5))))
    ref = rice.sf(np.broadcast_to(np.asarray(b), (5, 5)),
                  np.broadcast_to(np.asarray(a), (5, 5)))
    np.testing.assert_allclose(q, ref, rtol=1e-9, atol=1e-12)


def test_stack_params_rejects_mixed_families():
    p1 = _random_prm(0, 6, "rayleigh")
    p2 = _random_prm(0, 6, "rician")
    with pytest.raises(ValueError, match="mixed fading families"):
        tj.stack_params([p1, p2])


# ---------------------------------------------------------------------------
# solver quality vs the scipy oracle
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def prm10():
    dep = channel.deploy(channel.WirelessConfig(num_devices=10, seed=0))
    return make_prm(dep.gains, d=814090)


def test_solve_matches_scipy_reference(prm10):
    """Acceptance: <= 1e-3 relative on the 10-device Rayleigh reference."""
    ref = sca.solve_sca(prm10)
    res = solvers.solve(prm10)
    assert res.objective <= ref.objective * (1 + 1e-3)
    assert abs(res.objective / ref.objective - 1.0) < 1e-3


def test_solve_monotone_history(prm10):
    res = solvers.solve(prm10)
    assert np.all(np.diff(res.history) <= 1e-9), res.history[:5]
    assert res.converged


def test_solve_solution_feasible(prm10):
    res = solvers.solve(prm10)
    gm = theory.gamma_max(prm10)
    assert np.all(res.gamma > 0)
    assert np.all(res.gamma <= gm * (1 + 1e-9))
    assert abs(res.p.sum() - 1.0) < 1e-9
    am = theory.alpha_of_gamma(res.gamma, prm10)
    assert np.allclose(am, res.alpha * res.p, rtol=1e-9)


def test_solve_beats_zero_bias(prm10):
    res = solvers.solve(prm10)
    zb = theory.p1_objective(theory.zero_bias_gamma(prm10), prm10)
    assert res.objective < zb * 0.99


@pytest.mark.parametrize("family", ["rician", "nakagami"])
def test_solve_off_rayleigh_matches_scipy(family):
    prm = _random_prm(1, 8, family)
    ref = sca.solve_sca(prm)
    res = solvers.solve(prm)
    assert abs(res.objective / ref.objective - 1.0) < 1e-3


def test_solve_batch_matches_loop():
    prms = [_random_prm(s, 8, "rayleigh") for s in range(5)]
    br = solvers.solve_batch(prms)
    assert br.gamma.shape == (5, 8)
    for i, prm in enumerate(prms):
        single = solvers.solve(prm)
        assert abs(br.objective[i] / single.objective - 1.0) < 1e-9
        # true objective re-evaluated on the numpy side agrees
        assert abs(theory.p1_objective(br.gamma[i], prm)
                   / br.objective[i] - 1.0) < 1e-9


def test_make_sca_jax_vs_scipy_design(prm10):
    from repro.core import power_control as pcm
    dep = channel.deploy(channel.WirelessConfig(num_devices=10, seed=0))
    pc_j = pcm.make_power_control("sca", dep, prm10)
    pc_s = pcm.make_power_control("sca", dep, prm10, method="scipy")
    oj = theory.p1_objective(pc_j.gamma, prm10)
    os_ = theory.p1_objective(pc_s.gamma, prm10)
    assert abs(oj / os_ - 1.0) < 1e-3


# ---------------------------------------------------------------------------
# AdaptiveSCA in the engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fl_world():
    from repro.data import partition, synthetic
    from repro.models import mlp
    from repro.models.param import init_params
    x, y, xt, yt = synthetic.mnist_like(40, seed=0)
    shards = partition.partition_by_label(x, y, 10, seed=0)
    data = partition.stack_shards(shards)
    params0 = init_params(mlp.mlp_defs(hidden=32), jax.random.PRNGKey(0))
    xt_j, yt_j = jnp.asarray(xt), jnp.asarray(yt)
    ev = jax.jit(lambda p: {"acc": mlp.accuracy(p, xt_j, yt_j)})
    return mlp.mlp_loss, data, params0, ev


def test_adaptive_sca_static_bit_identical_to_sca(fl_world):
    """Acceptance: static-CSI AdaptiveSCA == plain sca, bitwise."""
    from repro.core import power_control as pcm
    from repro.fl import engine as eng
    from repro.fl.server import FLRunConfig
    loss, data, params0, ev = fl_world
    dep = channel.deploy(channel.WirelessConfig(num_devices=10, seed=0))
    prm = make_prm(dep.gains, d=10000)
    run = FLRunConfig(eta=0.05, num_rounds=7, eval_every=3)
    pc_sca = pcm.make_power_control("sca", dep, prm)
    pc_ad = pcm.make_power_control("adaptive_sca", dep, prm)
    assert np.array_equal(pc_sca.gamma, pc_ad.gamma)
    r1 = eng.run_fleet(loss, params0, [pc_sca], dep.gains, data, run, ev,
                       flat=False)
    r2 = eng.run_fleet(loss, params0, [pc_ad], dep.gains, data, run, ev,
                       flat=False)
    assert all(bool(jnp.all(r1.params[k] == r2.params[k]))
               for k in r1.params)
    assert r2.designs is None     # no fading process -> no redesign


def test_adaptive_sca_tracks_markov_drift(fl_world):
    """Acceptance: on a Gauss-Markov scenario the re-design moves the
    operating point per chunk and per seed, and changes the trajectory."""
    from repro.core import power_control as pcm, scenarios as scn
    from repro.fl import engine as eng
    from repro.fl.server import FLRunConfig
    loss, data, params0, ev = fl_world
    sc = scn.get_scenario("disk_markov")
    dep = scn.realize(sc)
    prm = scn.make_ota_params(dep, d=10000, gmax=10.0)
    fp = scn.make_fading_process(dep, sc.dynamics)
    run = FLRunConfig(eta=0.05, num_rounds=6, eval_every=3)
    pc_ad = pcm.make_power_control("adaptive_sca", dep, prm)
    pc_st = pcm.make_power_control("sca", dep, prm)
    res = eng.run_fleet(loss, params0, [pc_ad], dep.gains, data, run, ev,
                        fading=fp, flat=False, seeds=(0, 1))
    assert res.designs is not None and len(res.designs) >= 2
    t0, g0 = res.designs[0]
    t1, g1 = res.designs[1]
    assert t0 == 0 and t1 > 0
    assert g1.shape == (1, 2, dep.num_devices)
    # the operating point moved with the drifting CSI ...
    assert np.max(np.abs(g1 - g0) / np.abs(g0)) > 1e-3
    # ... independently per seed (each cell tracks its own channel)
    assert not np.array_equal(g1[0, 0], g1[0, 1])
    # ... and the trained params differ from the static design's
    res_st = eng.run_fleet(loss, params0, [pc_st], dep.gains, data, run, ev,
                           fading=fp, flat=False, seeds=(0, 1))
    assert any(not bool(jnp.all(res.params[k] == res_st.params[k]))
               for k in res.params)


def test_solve_batch_accepts_prestacked_f32_params():
    """stack_params outside an x64 scope yields f32 leaves; solve_batch
    must recast instead of crashing the scan carry dtype check."""
    prms = [_random_prm(s, 6, "rayleigh") for s in range(3)]
    stacked = tj.stack_params(prms)       # built OUTSIDE jax.enable_x64
    br = solvers.solve_batch(stacked)
    ref = solvers.solve_batch(prms)
    np.testing.assert_allclose(br.objective, ref.objective, rtol=1e-6)


def test_make_sca_accepts_legacy_solve_sca_kwargs():
    from repro.core import power_control as pcm
    dep = channel.deploy(channel.WirelessConfig(num_devices=8, seed=2))
    prm = make_prm(dep.gains, d=10000)
    pc = pcm.make_power_control("sca", dep, prm, max_iters=8, tol=1e-5)
    assert np.all(pc.gamma > 0)


def test_adaptive_sca_stack_k2():
    """Two same-class AdaptiveSCA schemes stack treedef-preserving (the
    first scheme's redesign hook serves both rows)."""
    from repro.core import power_control as pcm
    dep = channel.deploy(channel.WirelessConfig(num_devices=10, seed=0))
    prm = make_prm(dep.gains, d=10000)
    a1 = pcm.make_power_control("adaptive_sca", dep, prm)
    a2 = pcm.make_power_control("adaptive_sca", dep, prm)
    st_ = pcm.stack_schemes([a1, a2])
    assert type(st_) is pcm.AdaptiveSCA
    assert st_.gamma.shape == (2, dep.num_devices)
    assert st_.redesign_fn is a1.redesign_fn


def test_adaptive_sca_cannot_join_union():
    from repro.core import power_control as pcm
    dep = channel.deploy(channel.WirelessConfig(num_devices=10, seed=0))
    prm = make_prm(dep.gains, d=10000)
    ad = pcm.make_power_control("adaptive_sca", dep, prm)
    ideal = pcm.make_power_control("ideal", dep, prm)
    with pytest.raises(ValueError, match="AdaptiveSCA"):
        pcm.stack_schemes([ad, ideal])
