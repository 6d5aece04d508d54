"""OTA power-control schemes: the paper's SCA design + all Fig.-2 baselines.

Every scheme reduces, per FL round, to a pair of coefficients

    g_hat = sum_m s_m * g_m  +  noise_scale * z,     z ~ N(0, I_d)

where ``s_m`` absorbs the device pre-scaler, the (truncated) channel
inversion, the transmission indicator chi_{m,t}, and the PS post-scaler; and
``noise_scale`` is the effective receiver-noise amplitude per gradient
component.  ``round_coeffs`` is pure jnp so schemes embed directly in a
jit'd/pjit'd train step.

Schemes are scenario-agnostic (DESIGN.md §Scenarios): they consume a
Deployment's (gains, fading-spec) statistics at build time — the truncated
family via the family-aware theory module — and the per-round complex h at
run time, whatever scenario produced it.  Global-CSI schemes become
dropout-aware automatically when the Deployment's scenario dynamics include
device dropout (h = 0 rounds), so their channel-inversion minima bind on
the active devices only; the ``dropout_aware`` kwarg overrides.

Schemes (paper §IV):
  sca               proposed: per-device gamma_m from the SCA solver,
                    truncated channel inversion, statistical CSI at PS.
  lcpc              LCPC OTA-Comp [13]: truncated inversion with a COMMON
                    pre-scaler, grid-optimized with statistical CSI.
  vanilla           Vanilla OTA-FL [5]: full channel inversion, common scale
                    set by the weakest instantaneous channel (zero inst. bias,
                    needs global instantaneous CSI).
  opc               OPC OTA-Comp [13]: per-round MSE-optimal power control
                    (threshold structure), needs global instantaneous CSI.
  bbfl_interior     BB-FL [11]: schedule only devices within R_in.
  bbfl_alternative  BB-FL [11]: randomly alternate full/interior scheduling.
  ideal             noiseless FedAvg (upper reference, eq. (2)).
  zero_bias         structured zero-average-bias truncated inversion
                    (p_m = 1/N exactly; the 'weakest channel binds' regime).

Beyond the paper grid, ``adaptive_sca`` (class ``AdaptiveSCA``) re-solves
the SCA design between fl.engine scan chunks from the scenario's current
statistical CSI (DESIGN.md §Solvers) — the compiled batched solver in
``repro.solvers`` is what makes the in-training re-design affordable.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sca as sca_mod
from repro.core import theory
from repro.core.channel import Deployment
from repro.core.theory import OTAParams

# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PowerControl:
    """Base: time-invariant design state + per-round coefficient map.

    Every concrete scheme is registered as a JAX pytree (see
    ``_register_scheme_pytrees`` at the bottom of this module): its numeric
    design state (gamma, alpha, thresholds, ...) are the array leaves and
    its name/config flags are static aux data.  A scheme object can
    therefore cross jit boundaries as an argument, and same-structure
    schemes can be stacked along a leading [K] axis (``stack_schemes``) and
    run as one vmapped fleet — the substrate of the batched experiment
    engine (DESIGN.md §Engine).  ``round_coeffs`` is pure jnp on the leaf
    fields, so it traces with either concrete numpy state or batched
    tracers.
    """
    name: str = "base"
    requires_global_csi: bool = False
    # Time-invariant design (populated where applicable):
    gamma: Optional[np.ndarray] = None   # [N] device pre-scalers
    alpha: Optional[float] = None        # PS post-scaler
    p: Optional[np.ndarray] = None       # [N] avg participation levels

    def round_coeffs(self, h: jnp.ndarray, key: jax.Array):
        """(s[N], noise_scale) for one round given complex fading h[N]."""
        raise NotImplementedError


def _bmax(prm: OTAParams) -> float:
    """Max transmit amplitude per unit gradient: sqrt(d Es)/Gmax."""
    return float(np.sqrt(prm.d * prm.es) / prm.gmax)


# ---------------------------------------------------------------------------
# Truncated-channel-inversion family (time-invariant gamma): SCA / LCPC /
# zero-bias.  s_m = chi_m gamma_m / alpha,  noise = sqrt(N0)/alpha.
# ---------------------------------------------------------------------------

def _truncated_coeffs(habs, gamma, alpha, thresholds, noise_over_alpha):
    """chi-truncated inversion coefficients (shared by the class and the
    SchemeBatch union branch — one definition, bitwise-identical paths)."""
    dt = habs.dtype
    chi = (habs >= jnp.asarray(thresholds, dt)).astype(dt)
    s = chi * jnp.asarray(gamma, dt) / jnp.asarray(alpha, dt)
    return s, jnp.asarray(noise_over_alpha, dt)


@dataclasses.dataclass
class TruncatedInversion(PowerControl):
    thresholds: Optional[np.ndarray] = None   # [N] chi thresholds on |h|
    n0: float = 0.0
    # sqrt(n0)/alpha, precomputed in float64 at build time so round_coeffs
    # never does host math on (possibly traced) leaves.
    noise_over_alpha: Optional[float] = None

    def __post_init__(self):
        if self.noise_over_alpha is None and self.alpha is not None:
            self.noise_over_alpha = float(np.sqrt(self.n0) / self.alpha)

    def round_coeffs(self, h: jnp.ndarray, key: jax.Array):
        return _truncated_coeffs(jnp.abs(h), self.gamma, self.alpha,
                                 self.thresholds, self.noise_over_alpha)


def _make_truncated(name: str, gamma: np.ndarray, prm: OTAParams) -> TruncatedInversion:
    am, a, pm = theory.participation(gamma, prm)
    return TruncatedInversion(
        name=name, requires_global_csi=False,
        gamma=np.asarray(gamma, np.float64), alpha=a, p=pm,
        thresholds=theory.chi_threshold(gamma, prm), n0=prm.n0)


def make_sca(deployment: Deployment, prm: OTAParams, method: str = "jax",
             **kw) -> TruncatedInversion:
    """The paper's SCA design.  ``method="jax"`` (default) runs the compiled
    batched solver (repro.solvers, DESIGN.md §Solvers); ``method="scipy"``
    runs the host SLSQP reference oracle (core.sca.solve_sca).  Both descend
    the same (P1) objective from the same start and agree to ~1e-6 relative
    on the reference cases (benchmarks/sca_bench.py tracks the gap)."""
    if method == "scipy":
        res = sca_mod.solve_sca(prm, **kw)
    elif method == "jax":
        from repro import solvers  # deferred: keep core importable fast
        # translate the legacy solve_sca budget kwargs onto SolverConfig so
        # pre-existing make_power_control("sca", ..., max_iters=...) callers
        # keep working across the default-path switch
        legacy = {k: kw.pop(k) for k in ("max_iters", "tol", "backtracks")
                  if k in kw}
        cfg = kw.pop("cfg", solvers.DEFAULT_CONFIG)
        if legacy:
            cfg = dataclasses.replace(cfg, **legacy)
        res = solvers.solve(prm, cfg=cfg, **kw)
    else:
        raise ValueError(f"unknown sca method {method!r} (jax|scipy)")
    pc = _make_truncated("sca", res.gamma, prm)
    pc.sca_result = res  # attach for inspection
    return pc


def make_lcpc(deployment: Deployment, prm: OTAParams,
              grid_size: int = 512) -> TruncatedInversion:
    """Common pre-scaler, grid-optimized expected-MSE with statistical CSI."""
    gmax_arr = theory.gamma_max(prm)
    grid = np.geomspace(1e-3 * gmax_arr.min(), gmax_arr.max(), grid_size)
    best_g, best_v = None, np.inf
    n = prm.num_devices
    for g in grid:
        gamma = np.full(n, g)
        am = theory.alpha_of_gamma(gamma, prm)
        a = am.sum()
        if a <= 0:
            continue
        pm = am / a
        z = theory.zeta_terms(gamma, prm)
        # expected MSE proxy: variance + squared-bias (G^2-scaled; LCPC has no
        # access to the true dissimilarity kappa -> 'less controllable bias')
        v = z["total"] + prm.gmax**2 * n * np.sum((pm - 1.0 / n) ** 2)
        if v < best_v:
            best_g, best_v = g, v
    return _make_truncated("lcpc", np.full(n, best_g), prm)


def make_zero_bias(deployment: Deployment, prm: OTAParams,
                   slack: float = 1.0) -> TruncatedInversion:
    return _make_truncated("zero_bias", theory.zero_bias_gamma(prm, slack), prm)


# ---------------------------------------------------------------------------
# AdaptiveSCA: truncated inversion whose design re-solves DURING training
# (between fl.engine scan chunks) from the scenario's current statistical
# CSI.  DESIGN.md §Solvers.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class AdaptiveSCA(TruncatedInversion):
    """SCA design that tracks time-varying statistical CSI.

    Round coefficients are plain truncated inversion (inherited), so inside
    a scan chunk the scheme is indistinguishable from ``sca``.  Between
    chunks the engine calls ``redesign_fn(scheme, fading, state)`` — for a
    Gauss-Markov scenario this maps the current scattered state d_t to the
    one-step conditional channel law (Rician: mean rho d_t + LOS, diffuse
    variance (1-rho^2) Lambda_d), batch-solves (P1) under that conditional
    CSI with the compiled solver, and swaps in the new design.  On static
    CSI (``fading=None`` or rho=0) the redesign is a no-op, so static runs
    are bit-identical to the ``sca`` scheme built by the same solver.

    The design leaves carry whatever leading batch axes the engine's fleet
    grid has ([K, S] after the first redesign) — ``round_coeffs`` is
    per-cell under vmap either way.

    ``redesign_cohort_fn(pc, gains)`` is the population-mode sibling
    (DESIGN.md §Population): it re-solves (P1) on an incoming cohort's
    STATIONARY statistical CSI (``gains`` [..., N], any leading batch
    axes).  It is pure in ``gains`` — no dependence on the live fading
    state or current design — which is what lets the streaming driver run
    it for cohort c+1 while chunk c is still executing.
    """
    redesign_fn: Optional[object] = None   # static aux: (pc, fading, state)
    redesign_cohort_fn: Optional[object] = None   # static aux: (pc, gains)


# K-factors above this are effectively deterministic channels; the cap keeps
# the conditional-CSI solve inside the Marcum-series accuracy envelope
# (theory_jax._MARCUM_TERMS).
_ADAPTIVE_K_CAP = 50.0


def make_adaptive_sca(deployment: Deployment, prm: OTAParams,
                      **kw) -> AdaptiveSCA:
    """Build the adaptive scheme: initial design = the static solve on the
    deployment's stationary CSI (identical to ``make_sca(..., "jax")``).

    When K same-class AdaptiveSCA schemes are stacked into one fleet, the
    first scheme's redesign hook serves every row — the hook reads the
    per-row fading state for gains, but problem constants (d, Gmax, Es,
    N0, eta, L, kappa^2, sigma^2) come from ITS ``prm``, so rows of one
    adaptive fleet should share those constants."""
    from repro import solvers
    from repro.solvers import theory_jax as tjx

    cfg = kw.pop("cfg", solvers.DEFAULT_CONFIG)
    res = solvers.solve(prm, cfg=cfg, **kw)
    base = _make_truncated("adaptive_sca", res.gamma, prm)

    def redesign(pc: AdaptiveSCA, fading, state):
        rho = float(getattr(fading, "rho", 0.0))
        if state is None or rho == 0.0:
            return pc      # static CSI: nothing to track
        with solvers.x64_scope():
            n = prm.num_devices
            state64 = jnp.asarray(state)                     # [..., N] complex
            batch = state64.shape[:-1]
            diffuse = (1.0 - rho**2) * jnp.asarray(
                np.asarray(fading._diffuse_gains(), np.float64))
            los = jnp.asarray(np.asarray(fading._los(), np.float64))
            mean = los + rho * state64       # one-step conditional mean
            nu2 = jnp.abs(mean) ** 2
            gains_eff = (nu2 + diffuse).reshape((-1, n))     # [B, N]
            k_eff = jnp.minimum(nu2 / diffuse,
                                _ADAPTIVE_K_CAP).reshape((-1, n))
            b = gains_eff.shape[0]

            def row(v):
                return jnp.broadcast_to(jnp.asarray(v, jnp.float64), (b,))

            prm_b = tjx.SolverParams(
                d=row(prm.d), gmax=row(prm.gmax), es=row(prm.es),
                n0=row(prm.n0), gains=gains_eff,
                sigma_sq=jnp.broadcast_to(
                    jnp.asarray(prm.sigma_sq, jnp.float64), (b, n)),
                eta=row(prm.eta), lsmooth=row(prm.lsmooth),
                kappa_sq=row(prm.kappa_sq), dropout=row(prm.dropout),
                fading_param=k_eff, family="rician")
            out = solvers.solve_batch_device(prm_b, cfg)
            shape = batch + (n,)
            gamma = np.asarray(out["gamma"]).reshape(shape)
            p = np.asarray(out["p"]).reshape(shape)
            alpha = np.asarray(out["alpha"]).reshape(batch)
        return dataclasses.replace(
            pc, gamma=gamma, alpha=alpha, p=p,
            thresholds=np.asarray(theory.chi_threshold(gamma, prm)),
            noise_over_alpha=np.sqrt(prm.n0) / alpha)

    # population cohorts: same solver, but the CSI is the incoming
    # cohort's stationary gains (family from prm, scalar parameter) —
    # pure in `gains`, safe to run ahead of the executing chunk
    family = "rayleigh" if prm.is_rayleigh else prm.fading.family
    if family == "rician":
        fparam = float(np.asarray(prm.fading.rician_k))
    elif family == "nakagami":
        fparam = float(np.asarray(prm.fading.nakagami_m))
    else:
        fparam = 1.0

    def redesign_cohort(pc: AdaptiveSCA, gains):
        with solvers.x64_scope():
            n = prm.num_devices
            g = np.asarray(gains, np.float64)
            if g.shape[-1] != n:
                raise ValueError(f"cohort gains have {g.shape[-1]} devices "
                                 f"but the design was built for {n}")
            batch = g.shape[:-1]
            gb = jnp.asarray(g.reshape((-1, n)))
            b = gb.shape[0]

            def row(v):
                return jnp.broadcast_to(jnp.asarray(v, jnp.float64), (b,))

            prm_b = tjx.SolverParams(
                d=row(prm.d), gmax=row(prm.gmax), es=row(prm.es),
                n0=row(prm.n0), gains=gb,
                sigma_sq=jnp.broadcast_to(
                    jnp.asarray(prm.sigma_sq, jnp.float64), (b, n)),
                eta=row(prm.eta), lsmooth=row(prm.lsmooth),
                kappa_sq=row(prm.kappa_sq), dropout=row(prm.dropout),
                fading_param=jnp.full((b, n), fparam, jnp.float64),
                family=family)
            out = solvers.solve_batch_device(prm_b, cfg)
            shape = batch + (n,)
            gamma = np.asarray(out["gamma"]).reshape(shape)
            p = np.asarray(out["p"]).reshape(shape)
            alpha = np.asarray(out["alpha"]).reshape(batch)
        return dataclasses.replace(
            pc, gamma=gamma, alpha=alpha, p=p,
            thresholds=np.asarray(theory.chi_threshold(gamma, prm)),
            noise_over_alpha=np.sqrt(prm.n0) / alpha)

    return AdaptiveSCA(
        name="adaptive_sca", requires_global_csi=False, gamma=base.gamma,
        alpha=base.alpha, p=base.p, thresholds=base.thresholds, n0=prm.n0,
        noise_over_alpha=base.noise_over_alpha, redesign_fn=redesign,
        redesign_cohort_fn=redesign_cohort)


# ---------------------------------------------------------------------------
# Vanilla OTA-FL [5]: zero instantaneous bias; common scale c_t bound by the
# weakest instantaneous channel.  Needs global instantaneous CSI.
# ---------------------------------------------------------------------------

def _vanilla_coeffs(habs, n, bmax, n0, dropout_aware: bool):
    dt = habs.dtype
    if not dropout_aware:  # paper baseline: exact pre-scenario graph
        c_t = bmax * jnp.min(habs)
        s = jnp.full((n,), 1.0 / n, dtype=dt)
        noise_scale = jnp.sqrt(n0) / (n * c_t)
        return s, noise_scale.astype(dt)
    # Dropped devices (h = 0) are excluded from the inversion: the scale
    # binds on the weakest *active* channel and only active devices are
    # averaged (uniform over the k participants).
    active = (habs > 0).astype(dt)
    k = jnp.maximum(jnp.sum(active), 1.0)
    c_t = bmax * jnp.min(jnp.where(habs > 0, habs, jnp.inf))
    s = active / k
    noise_scale = jnp.sqrt(n0) / (k * c_t)
    return s, noise_scale.astype(dt)


@dataclasses.dataclass
class VanillaOTA(PowerControl):
    bmax: float = 0.0
    n0: float = 0.0
    num_devices: int = 0
    dropout_aware: bool = False   # scenarios with p_dropout > 0 observe h=0

    def round_coeffs(self, h: jnp.ndarray, key: jax.Array):
        return _vanilla_coeffs(jnp.abs(h), self.num_devices, self.bmax,
                               self.n0, self.dropout_aware)


def _dropout_aware(deployment: Deployment, override) -> bool:
    """Default the flag from the deployment's scenario dynamics so schemes
    built on a dropout scenario never hit the h=0 division-by-zero path."""
    if override is not None:
        return bool(override)
    return getattr(deployment, "p_dropout", 0.0) > 0


def make_vanilla(deployment: Deployment, prm: OTAParams,
                 dropout_aware: Optional[bool] = None) -> VanillaOTA:
    n = prm.num_devices
    return VanillaOTA(name="vanilla", requires_global_csi=True,
                      p=np.full(n, 1.0 / n), bmax=_bmax(prm), n0=prm.n0,
                      num_devices=n,
                      dropout_aware=_dropout_aware(deployment, dropout_aware))


# ---------------------------------------------------------------------------
# OPC OTA-Comp [13]: per-round MSE-optimal (threshold structure).  For a
# denoising scale c, the MSE-optimal amplitudes are b_m = min(c/(N|h_m|),
# bmax): strong channels invert to the common target, weak channels transmit
# at full power.  c is optimized on a fixed log grid (jit-friendly).
# ---------------------------------------------------------------------------

def _opc_coeffs(habs, n, bmax, n0, gmax, grid_size: int,
                dropout_aware: bool):
    dt = habs.dtype
    base = bmax * habs * n                  # c at which device m leaves inversion
    if dropout_aware:
        # dropped devices have base = 0: b_m = min(c/(n*0), bmax) = bmax
        # but s_m = b_m * 0 / c = 0, so they only matter for the grid
        # bounds — anchor those on the active channels.  An all-dropped
        # round would give (c_lo, c_hi) = (inf, 0) and a NaN grid, so it
        # falls back to a dummy finite bracket; s is identically 0 there
        # and the noise is zeroed below — a no-op round, like Vanilla.
        any_active = jnp.any(base > 0)
        c_lo = jnp.where(any_active,
                         0.02 * jnp.min(jnp.where(base > 0, base,
                                                  jnp.inf)), 1.0)
        c_hi = jnp.where(any_active, 50.0 * jnp.max(base), 2.0)
    else:
        c_lo = 0.02 * jnp.min(base)
        c_hi = 50.0 * jnp.max(base)
    grid = jnp.exp(jnp.linspace(jnp.log(c_lo), jnp.log(c_hi), grid_size))

    def mse(c):
        b = jnp.minimum(c / (n * habs), bmax)
        sig = jnp.sum((b * habs / c - 1.0 / n) ** 2) * gmax**2
        return sig + n0 / c**2

    vals = jax.vmap(mse)(grid)
    c_star = grid[jnp.argmin(vals)]
    # zoom refinement around the coarse optimum
    for _ in range(2):
        fine = c_star * jnp.exp(jnp.linspace(-0.15, 0.15, 33))
        c_star = fine[jnp.argmin(jax.vmap(mse)(fine))]
    b = jnp.minimum(c_star / (n * habs), bmax)
    s = (b * habs / c_star).astype(dt)
    noise_scale = (jnp.sqrt(n0) / c_star).astype(dt)
    if dropout_aware:
        noise_scale = jnp.where(any_active, noise_scale, 0.0)
    return s, noise_scale


@dataclasses.dataclass
class OPC(PowerControl):
    bmax: float = 0.0
    n0: float = 0.0
    gmax: float = 0.0
    num_devices: int = 0
    grid_size: int = 128
    dropout_aware: bool = False   # scenarios with p_dropout > 0 observe h=0

    def round_coeffs(self, h: jnp.ndarray, key: jax.Array):
        return _opc_coeffs(jnp.abs(h), self.num_devices, self.bmax, self.n0,
                           self.gmax, self.grid_size, self.dropout_aware)


def make_opc(deployment: Deployment, prm: OTAParams,
             dropout_aware: Optional[bool] = None) -> OPC:
    n = prm.num_devices
    return OPC(name="opc", requires_global_csi=True, p=np.full(n, 1.0 / n),
               bmax=_bmax(prm), n0=prm.n0, gmax=prm.gmax, num_devices=n,
               dropout_aware=_dropout_aware(deployment, dropout_aware))


# ---------------------------------------------------------------------------
# BB-FL [11]: interior scheduling within R_in (and the alternating variant).
# ---------------------------------------------------------------------------

def _bbfl_mask_coeffs(habs, mask, bmax, n0, dropout_aware: bool):
    if dropout_aware:
        # scheduled devices that dropped out (h = 0) cannot transmit
        mask = mask * (habs > 0).astype(habs.dtype)
    # make_bbfl guarantees >= 1 scheduled device, so the max() guard only
    # binds in the dropout case (all scheduled devices out this round)
    k = jnp.maximum(jnp.sum(mask), 1.0)
    c_t = bmax * jnp.min(jnp.where(mask > 0, habs, jnp.inf))
    s = mask / k
    noise_scale = jnp.sqrt(n0) / (k * c_t)
    return s.astype(habs.dtype), noise_scale.astype(habs.dtype)


def _bbfl_coeffs(habs, key, mask, alternative, bmax, n0,
                 dropout_aware: bool):
    """``alternative`` may be a python bool (class path, branch folded at
    trace time) or a traced scalar (SchemeBatch union path, folded into the
    select so interior/alternative rows share one graph)."""
    interior = jnp.asarray(mask, dtype=habs.dtype)
    if isinstance(alternative, bool) and not alternative:
        return _bbfl_mask_coeffs(habs, interior, bmax, n0, dropout_aware)
    full = jnp.ones_like(interior)
    use_full = jax.random.bernoulli(key, 0.5)
    if not isinstance(alternative, bool):
        use_full = jnp.logical_and(use_full, alternative > 0)
    s_i, ns_i = _bbfl_mask_coeffs(habs, interior, bmax, n0, dropout_aware)
    s_f, ns_f = _bbfl_mask_coeffs(habs, full, bmax, n0, dropout_aware)
    s = jnp.where(use_full, s_f, s_i)
    ns = jnp.where(use_full, ns_f, ns_i)
    return s, ns


@dataclasses.dataclass
class BBFL(PowerControl):
    mask: Optional[np.ndarray] = None    # [N] 1 if within R_in
    alternative: bool = False
    bmax: float = 0.0
    n0: float = 0.0
    num_devices: int = 0
    dropout_aware: bool = False   # scenarios with p_dropout > 0 observe h=0

    def round_coeffs(self, h: jnp.ndarray, key: jax.Array):
        return _bbfl_coeffs(jnp.abs(h), key, self.mask, self.alternative,
                            self.bmax, self.n0, self.dropout_aware)


def make_bbfl(deployment: Deployment, prm: OTAParams, alternative: bool,
              r_in_frac: float = 0.6,
              dropout_aware: Optional[bool] = None) -> BBFL:
    r_in = r_in_frac * deployment.cfg.r_max
    mask = (deployment.distances <= r_in).astype(np.float64)
    if mask.sum() == 0:  # degenerate deployment: keep the closest device
        mask[np.argmin(deployment.distances)] = 1.0
    n = prm.num_devices
    name = "bbfl_alternative" if alternative else "bbfl_interior"
    # average participation: interior always on; alternative: 0.5 full + 0.5 interior
    k = mask.sum()
    p = (mask / k) if not alternative else 0.5 * (mask / k) + 0.5 / n
    return BBFL(name=name, requires_global_csi=True, p=p, mask=mask,
                alternative=alternative, bmax=_bmax(prm), n0=prm.n0,
                num_devices=n,
                dropout_aware=_dropout_aware(deployment, dropout_aware))


# ---------------------------------------------------------------------------
# Ideal FedAvg: noiseless uniform aggregation (eq. (2)).
# ---------------------------------------------------------------------------

def _ideal_coeffs(habs, n):
    s = jnp.full((n,), 1.0 / n, dtype=habs.dtype)
    return s, jnp.zeros((), dtype=habs.dtype)


@dataclasses.dataclass
class Ideal(PowerControl):
    num_devices: int = 0

    def round_coeffs(self, h: jnp.ndarray, key: jax.Array):
        return _ideal_coeffs(jnp.abs(h), self.num_devices)


def make_ideal(deployment: Deployment, prm: OTAParams) -> Ideal:
    n = prm.num_devices
    return Ideal(name="ideal", p=np.full(n, 1.0 / n), num_devices=n)


# ---------------------------------------------------------------------------

SCHEMES = ("sca", "lcpc", "vanilla", "opc", "bbfl_interior",
           "bbfl_alternative", "ideal", "zero_bias")


def make_power_control(name: str, deployment: Deployment, prm: OTAParams,
                       **kw) -> PowerControl:
    if name == "sca":
        return make_sca(deployment, prm, **kw)
    if name == "lcpc":
        return make_lcpc(deployment, prm, **kw)
    if name == "vanilla":
        return make_vanilla(deployment, prm, **kw)
    if name == "opc":
        return make_opc(deployment, prm, **kw)
    if name == "bbfl_interior":
        return make_bbfl(deployment, prm, alternative=False, **kw)
    if name == "bbfl_alternative":
        return make_bbfl(deployment, prm, alternative=True, **kw)
    if name == "ideal":
        return make_ideal(deployment, prm)
    if name == "zero_bias":
        return make_zero_bias(deployment, prm, **kw)
    if name == "adaptive_sca":
        return make_adaptive_sca(deployment, prm, **kw)
    raise ValueError(f"unknown power-control scheme: {name!r}; "
                     f"available: {SCHEMES + ('adaptive_sca',)}")


# ---------------------------------------------------------------------------
# Pytree registration + scheme stacking (DESIGN.md §Engine).
#
# Every concrete scheme is a pytree: numeric design state = leaves, name and
# config flags = static aux.  ``stack_schemes`` turns a list of schemes into
# one object whose leaves carry a leading [K] axis, so a single vmapped
# program evaluates all K schemes' round coefficients — the [K-scheme x
# S-seed] fleet of fl.engine rides on this.
# ---------------------------------------------------------------------------

# leaf (array) fields per class; every other dataclass field is static aux.
_SCHEME_LEAVES = {
    TruncatedInversion: ("gamma", "alpha", "p", "thresholds", "n0",
                         "noise_over_alpha"),
    AdaptiveSCA: ("gamma", "alpha", "p", "thresholds", "n0",
                  "noise_over_alpha"),
    VanillaOTA: ("gamma", "alpha", "p", "bmax", "n0"),
    OPC: ("gamma", "alpha", "p", "bmax", "n0", "gmax"),
    BBFL: ("gamma", "alpha", "p", "mask", "bmax", "n0"),
    Ideal: ("gamma", "alpha", "p"),
}


def _scheme_statics(cls):
    leaves = _SCHEME_LEAVES[cls]
    return tuple(f.name for f in dataclasses.fields(cls)
                 if f.name not in leaves)


def _register_scheme_pytree(cls):
    leaf_fields = _SCHEME_LEAVES[cls]
    static_fields = _scheme_statics(cls)

    def flatten(obj):
        children = tuple(getattr(obj, f) for f in leaf_fields)
        aux = tuple(getattr(obj, f) for f in static_fields)
        return children, aux

    def unflatten(aux, children):
        kw = dict(zip(static_fields, aux))
        kw.update(zip(leaf_fields, children))
        return cls(**kw)

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)


for _cls in _SCHEME_LEAVES:
    _register_scheme_pytree(_cls)


_UNION_KIND_OF = {TruncatedInversion: 0, VanillaOTA: 1, OPC: 2, BBFL: 3,
                  Ideal: 4}


@dataclasses.dataclass
class SchemeBatch:
    """Union representation of K *heterogeneous* schemes, stacked [K].

    Each row carries the superset of all kinds' design state (unused fields
    hold benign fillers) plus a ``kind`` index; ``round_coeffs`` on one row
    dispatches through ``lax.switch``, which under vmap becomes a select
    over all kind branches — one compiled program runs an arbitrary mix of
    truncated-inversion / vanilla / OPC / BB-FL / ideal rows.  The branch
    bodies are the *same* module-level coefficient functions the scheme
    classes call, so a SchemeBatch row reproduces the standalone scheme
    run-for-run.
    """
    names: tuple = ()
    num_devices: int = 0
    grid_size: int = 128
    dropout_aware: bool = False
    kind: Optional[np.ndarray] = None            # [K] int32
    gamma: Optional[np.ndarray] = None           # [K, N]
    alpha: Optional[np.ndarray] = None           # [K]
    p: Optional[np.ndarray] = None               # [K, N]
    thresholds: Optional[np.ndarray] = None      # [K, N]
    noise_over_alpha: Optional[np.ndarray] = None  # [K]
    mask: Optional[np.ndarray] = None            # [K, N]
    alternative: Optional[np.ndarray] = None     # [K] (0/1)
    bmax: Optional[np.ndarray] = None            # [K]
    n0: Optional[np.ndarray] = None              # [K]
    gmax: Optional[np.ndarray] = None            # [K]

    def __len__(self):
        return len(self.names)

    @property
    def name(self):
        return "+".join(self.names)

    def round_coeffs(self, h: jnp.ndarray, key: jax.Array):
        """Per-row coefficients (use under vmap; rows are scalar/[N])."""
        habs = jnp.abs(h)
        n = self.num_devices
        branches = (
            lambda op: _truncated_coeffs(op[0], self.gamma, self.alpha,
                                         self.thresholds,
                                         self.noise_over_alpha),
            lambda op: _vanilla_coeffs(op[0], n, self.bmax, self.n0,
                                       self.dropout_aware),
            lambda op: _opc_coeffs(op[0], n, self.bmax, self.n0, self.gmax,
                                   self.grid_size, self.dropout_aware),
            lambda op: _bbfl_coeffs(op[0], op[1], self.mask,
                                    self.alternative, self.bmax, self.n0,
                                    self.dropout_aware),
            lambda op: _ideal_coeffs(op[0], n),
        )
        return jax.lax.switch(self.kind, branches, (habs, key))


jax.tree_util.register_pytree_node(
    SchemeBatch,
    lambda sb: (tuple(getattr(sb, f) for f in
                      ("kind", "gamma", "alpha", "p", "thresholds",
                       "noise_over_alpha", "mask", "alternative", "bmax",
                       "n0", "gmax")),
                (sb.names, sb.num_devices, sb.grid_size, sb.dropout_aware)),
    lambda aux, ch: SchemeBatch(*aux, *ch),
)


def _union_row(pc: PowerControl, n: int) -> dict:
    """One SchemeBatch row from a concrete scheme (fillers keep every dead
    branch finite so the vmapped select never sees NaN/Inf)."""
    def arr(v, default):
        return np.asarray(default if v is None else v, np.float64)
    return dict(
        kind=np.int32(_UNION_KIND_OF[type(pc)]),
        gamma=arr(pc.gamma, np.zeros(n)),
        alpha=arr(pc.alpha, 1.0),
        p=arr(pc.p, np.full(n, 1.0 / n)),
        thresholds=arr(getattr(pc, "thresholds", None), np.zeros(n)),
        noise_over_alpha=arr(getattr(pc, "noise_over_alpha", None), 0.0),
        mask=arr(getattr(pc, "mask", None), np.ones(n)),
        alternative=arr(float(getattr(pc, "alternative", False)), 0.0),
        bmax=arr(getattr(pc, "bmax", None), 1.0),
        n0=arr(getattr(pc, "n0", None), 0.0),
        gmax=arr(getattr(pc, "gmax", None), 1.0),
    )


def _scheme_n(pc: PowerControl) -> int:
    for f in ("p", "gamma", "mask", "thresholds"):
        v = getattr(pc, f, None)
        if v is not None:
            return int(np.asarray(v).shape[-1])
    n = getattr(pc, "num_devices", 0)
    if n:
        return int(n)
    raise ValueError(f"cannot infer device count for scheme {pc.name!r}")


def stack_schemes(schemes):
    """Stack K PowerControl schemes for a vmapped fleet (DESIGN.md §Engine).

    Same-class schemes with identical static config (name aside) stack
    directly: the result is one instance of that class whose array leaves
    have a leading [K] axis, ready for ``jax.vmap`` with in_axes=0 on the
    scheme argument.  Any mix of classes (or of static configs) falls back
    to the ``SchemeBatch`` union with per-row lax.switch dispatch.  Either
    way the result duck-types ``round_coeffs`` per row and exposes
    ``.names``.
    """
    schemes = list(schemes)
    if not schemes:
        raise ValueError("stack_schemes needs at least one scheme")
    names = tuple(pc.name for pc in schemes)
    n = _scheme_n(schemes[0])
    if any(_scheme_n(pc) != n for pc in schemes):
        raise ValueError("schemes disagree on device count")

    cls = type(schemes[0])
    homogeneous = (cls in _SCHEME_LEAVES
                   and all(type(pc) is cls for pc in schemes))
    if homogeneous:
        # redesign_fn closures are per-instance and never compare equal;
        # same-class adaptive schemes stack with the FIRST scheme's hook
        # (rows share the fleet's fading process and problem constants —
        # per-row state is what the redesign actually consumes).
        statics = [f for f in _scheme_statics(cls)
                   if f not in ("name", "redesign_fn", "redesign_cohort_fn")]
        s0 = {f: getattr(schemes[0], f) for f in statics}
        homogeneous = all(
            all(getattr(pc, f) == s0[f] for f in statics)
            for pc in schemes[1:])
    if homogeneous:
        kw = dict(s0, name="+".join(names))
        fields = tuple(f.name for f in dataclasses.fields(cls))
        for hook in ("redesign_fn", "redesign_cohort_fn"):
            if hook in fields:
                kw[hook] = getattr(schemes[0], hook)
        for f in _SCHEME_LEAVES[cls]:
            vals = [getattr(pc, f) for pc in schemes]
            if all(v is None for v in vals):
                kw[f] = None
            elif any(v is None for v in vals):
                raise ValueError(f"inconsistent leaf {f!r} across schemes")
            else:
                kw[f] = np.stack([np.asarray(v, np.float64) for v in vals])
        stacked = cls(**kw)
        stacked.names = names
        return stacked

    unsupported = sorted({type(pc).__name__ for pc in schemes
                          if type(pc) not in _UNION_KIND_OF})
    if unsupported:
        raise ValueError(
            f"schemes of type {unsupported} cannot join a heterogeneous "
            f"SchemeBatch union (AdaptiveSCA re-designs between chunks and "
            f"must be stacked with same-class schemes only)")
    # only schemes that have the flag vote: truncated-inversion/ideal rows
    # are dropout-agnostic (h=0 -> chi=0 / uniform average regardless)
    dropout = {bool(pc.dropout_aware) for pc in schemes
               if hasattr(pc, "dropout_aware")} or {False}
    if len(dropout) > 1:
        raise ValueError("cannot stack schemes with mixed dropout_aware")
    grid = {int(getattr(pc, "grid_size", 128)) for pc in schemes}
    if len(grid) > 1:
        raise ValueError("cannot stack OPC schemes with mixed grid_size")
    rows = [_union_row(pc, n) for pc in schemes]
    stacked = {f: np.stack([r[f] for r in rows]) for f in rows[0]}
    return SchemeBatch(names=names, num_devices=n, grid_size=grid.pop(),
                       dropout_aware=dropout.pop(), **stacked)


def tile_over_seeds(stacked, s_axis: int):
    """Tile a stacked fleet's design leaves over a seed axis: [K, ...] ->
    [K, S, ...].

    Gives every (scheme, seed) cell its own copy of the design state.
    Adaptive schemes need this so each cell can track its own channel
    trajectory (the re-design between scan chunks is per cell); sharded
    placements (fl.placement.ShardedPlacement) need it so EVERY scheme leaf
    carries the grid axes and can be flattened to the [K*S] cell axis that
    shards over the mesh.  Leaves come back as numpy (host-resident design
    state, like ``stack_schemes``); static aux (name, redesign_fn, ...) is
    preserved through the pytree treedef.
    """
    return jax.tree.map(
        lambda a: np.repeat(np.asarray(a)[:, None], s_axis, axis=1),
        stacked)


def round_coeffs_fleet(stacked, h: jnp.ndarray, keys: jax.Array):
    """Vmapped coefficients for a stacked fleet.

    h: [N] (shared channel draw) or [K, N] per-scheme; keys: [K, 2].
    Returns (s [K, N], noise_scale [K]).
    """
    in_h = 0 if jnp.ndim(h) == 2 else None
    return jax.vmap(lambda pc, hh, kk: pc.round_coeffs(hh, kk),
                    in_axes=(0, in_h, 0))(stacked, h, keys)
