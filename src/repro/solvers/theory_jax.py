"""jnp port of the Theorem-1 quantities (DESIGN.md §Solvers).

``core/theory.py`` is float64 numpy/scipy — exact, host-bound, one scenario
at a time.  This module re-expresses the same maps as pure ``jax.numpy`` on
a pytree parameter container (``SolverParams``) so they jit, vmap over
scenario batches, and differentiate — the substrate of the batched SCA
solver (``repro.solvers.sca_jax``) and of in-training power re-design
(``power_control.AdaptiveSCA``).

Numerical contract (tests/test_solvers.py): with x64 enabled, every function
here agrees with its ``core/theory.py`` counterpart to <= 1e-6 relative
across all three fading families and random ``OTAParams``.  The only
implementation divergence is the Rician magnitude survival function: scipy
evaluates Marcum Q_1 through the non-central chi-square CDF, while here it
is the canonical Poisson-mixture series

    Q_1(a, b) = sum_k e^{-a^2/2} (a^2/2)^k / k! * Q(k+1, b^2/2)

with Q the regularized upper incomplete gamma (jax.scipy.special.gammaincc)
and a fixed term count — exact to ~1e-12 for the K-factors the scenario
engine uses (the Poisson(a^2/2 = K) tail at ``_MARCUM_TERMS`` is
negligible for K <~ 40).

All functions follow input dtype; the public solver entry points run them
under ``jax.enable_x64(True)`` because the physical scales
(gains ~1e-9..1e-13, N0 ~1e-21) need f64 headroom even though the *scaled*
SCA variables are O(1).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy import special as jsp

from repro.core.theory import (GAMMA_MAX_GRID_COARSE, GAMMA_MAX_GRID_FINE,
                               OTAParams)

# Terms in the Marcum-Q_1 Poisson-mixture series (Rician SF).  The k-th
# weight is Poisson(K)(k), so 96 terms cover K-factors to ~40 at f64.
_MARCUM_TERMS = 96


# ---------------------------------------------------------------------------
# Parameter container: one pytree, vmappable over a leading scenario batch.
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SolverParams:
    """Array view of ``theory.OTAParams`` (+ fading family parameters).

    Every numeric field is a pytree leaf, so ``jax.vmap`` over a stacked
    instance (``stack_params``) batches whole scenarios; ``family`` is
    static aux data, so one compiled solve serves any batch of scenarios
    that share a fading family (the batch layout of DESIGN.md §Solvers).

    ``fading_param`` holds the per-device family parameter ([N]): the
    Rician K-factor or Nakagami m; ones (unused) for Rayleigh.
    """
    d: jnp.ndarray              # scalar (f64 under the solver's x64 scope)
    gmax: jnp.ndarray           # scalar
    es: jnp.ndarray             # scalar
    n0: jnp.ndarray             # scalar
    gains: jnp.ndarray          # [N]
    sigma_sq: jnp.ndarray       # [N]
    eta: jnp.ndarray            # scalar
    lsmooth: jnp.ndarray        # scalar
    kappa_sq: jnp.ndarray       # scalar
    dropout: jnp.ndarray        # scalar
    fading_param: jnp.ndarray   # [N]
    family: str = "rayleigh"

    _LEAVES = ("d", "gmax", "es", "n0", "gains", "sigma_sq", "eta",
               "lsmooth", "kappa_sq", "dropout", "fading_param")

    def tree_flatten(self):
        return tuple(getattr(self, f) for f in self._LEAVES), self.family

    @classmethod
    def tree_unflatten(cls, family, leaves):
        return cls(*leaves, family=family)

    @property
    def num_devices(self) -> int:
        return int(self.gains.shape[-1])

    @property
    def is_rayleigh(self) -> bool:
        return self.family == "rayleigh"


def from_ota(p: OTAParams) -> SolverParams:
    """Lift a (numpy) ``OTAParams`` into the jnp parameter pytree."""
    n = p.num_devices
    family = "rayleigh" if p.is_rayleigh else p.fading.family
    if family == "rician":
        fparam = np.broadcast_to(
            np.asarray(p.fading.rician_k, np.float64), (n,))
    elif family == "nakagami":
        fparam = np.broadcast_to(
            np.asarray(p.fading.nakagami_m, np.float64), (n,))
    else:
        fparam = np.ones(n)
    as_a = lambda v: jnp.asarray(v, jnp.float64)
    return SolverParams(
        d=as_a(p.d), gmax=as_a(p.gmax), es=as_a(p.es), n0=as_a(p.n0),
        gains=as_a(p.gains), sigma_sq=as_a(p.sigma_sq), eta=as_a(p.eta),
        lsmooth=as_a(p.lsmooth), kappa_sq=as_a(p.kappa_sq),
        dropout=as_a(p.dropout), fading_param=as_a(np.asarray(fparam)),
        family=family)


def stack_params(prms: Sequence[OTAParams]) -> SolverParams:
    """Stack scenarios into one SolverParams with a leading [B] batch axis.

    All scenarios must share the fading family and device count (the static
    parts of the pytree); everything else — gains, noise, dropout, Rician K,
    weights — varies per batch row.  ``solve_batch`` vmaps over the result.
    """
    ps = [from_ota(p) for p in prms]
    if not ps:
        raise ValueError("stack_params needs at least one OTAParams")
    fam = {p.family for p in ps}
    if len(fam) > 1:
        raise ValueError(f"cannot stack mixed fading families {sorted(fam)}")
    return jax.tree.map(lambda *ls: jnp.stack(ls), *ps)


# ---------------------------------------------------------------------------
# Fading-family survival functions
# ---------------------------------------------------------------------------

def marcum_q1(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Marcum Q_1(a, b) by the Poisson-mixture series (see module doc)."""
    a, b = jnp.broadcast_arrays(a, b)
    lam = 0.5 * a**2                       # Poisson mean
    x = 0.5 * b**2
    k = jnp.arange(_MARCUM_TERMS, dtype=a.dtype)
    shape = (1,) * a.ndim + (_MARCUM_TERMS,)
    k = k.reshape(shape)
    logw = k * jnp.log(jnp.maximum(lam[..., None], 1e-300)) \
        - lam[..., None] - jsp.gammaln(k + 1.0)
    # lam == 0 (K = 0, pure Rayleigh limit): only the k = 0 term survives.
    w = jnp.where(lam[..., None] > 0, jnp.exp(logw),
                  jnp.where(k == 0, 1.0, 0.0))
    tails = jsp.gammaincc(k + 1.0, x[..., None])
    return jnp.clip(jnp.sum(w * tails, axis=-1), 0.0, 1.0)


def _rician_nu_sigma(gains, k):
    nu = jnp.sqrt(gains * k / (k + 1.0))
    sigma = jnp.sqrt(gains / (2.0 * (k + 1.0)))
    return nu, sigma


def magnitude_sf(gains: jnp.ndarray, x: jnp.ndarray, p: SolverParams
                 ) -> jnp.ndarray:
    """P(|h_m| >= x): jnp mirror of ``channel.fading_magnitude_sf``."""
    if p.family == "rician":
        k = jnp.broadcast_to(p.fading_param, jnp.shape(gains)) \
            if jnp.ndim(gains) <= 1 else p.fading_param[:, None]
        nu, sigma = _rician_nu_sigma(gains, k)
        return marcum_q1(nu / sigma, x / sigma)
    if p.family == "nakagami":
        m = jnp.broadcast_to(p.fading_param, jnp.shape(gains)) \
            if jnp.ndim(gains) <= 1 else p.fading_param[:, None]
        return jsp.gammaincc(m, m * x**2 / gains)
    return jnp.exp(-x**2 / gains)


# ---------------------------------------------------------------------------
# alpha_m(gamma) and its extremes — mirrors core/theory.py one-for-one
# ---------------------------------------------------------------------------

def trunc_exponent(gamma, p: SolverParams):
    return gamma**2 * p.gmax**2 / (p.d * p.gains * p.es)


def chi_threshold(gamma, p: SolverParams):
    return p.gmax * gamma / jnp.sqrt(p.d * p.es)


def expected_participation_indicator(gamma, p: SolverParams):
    if p.is_rayleigh:
        sf = jnp.exp(-trunc_exponent(gamma, p))
    else:
        sf = magnitude_sf(p.gains, chi_threshold(gamma, p), p)
    return (1.0 - p.dropout) * sf


def alpha_of_gamma(gamma, p: SolverParams):
    return gamma * expected_participation_indicator(gamma, p)


def log_alpha_of_gamma(gamma, p: SolverParams):
    """ln alpha_m(gamma); Rayleigh keeps the cancellation-free closed form
    used by the SCA constraint (11c)."""
    if p.is_rayleigh:
        return jnp.log(gamma) - trunc_exponent(gamma, p) \
            + jnp.log1p(-p.dropout)
    return jnp.log(jnp.maximum(alpha_of_gamma(gamma, p), 1e-300))


def _rayleigh_gamma_max(p: SolverParams):
    return jnp.sqrt(p.d * p.gains * p.es / (2.0 * p.gmax**2))


def gamma_max(p: SolverParams):
    """Per-device maximizer of alpha_m; same two-stage log grid as the
    numpy path (shared ``GAMMA_MAX_GRID_*`` constants) off-Rayleigh."""
    g_ray = _rayleigh_gamma_max(p)
    if p.is_rayleigh:
        return g_ray

    def argmax_on(grid):          # [N, G]
        vals = grid * magnitude_sf(p.gains[:, None],
                                   chi_threshold(grid, p), p)
        return jnp.take_along_axis(
            grid, jnp.argmax(vals, axis=1)[:, None], axis=1)[:, 0]

    lo, hi, num = GAMMA_MAX_GRID_COARSE
    coarse = argmax_on(g_ray[:, None]
                       * jnp.asarray(np.geomspace(lo, hi, num))[None, :])
    lo, hi, num = GAMMA_MAX_GRID_FINE
    return argmax_on(coarse[:, None]
                     * jnp.asarray(np.geomspace(lo, hi, num))[None, :])


def alpha_max(p: SolverParams):
    if p.is_rayleigh:
        amax = jnp.sqrt(p.d * p.gains * p.es / (2.0 * np.e * p.gmax**2))
        return (1.0 - p.dropout) * amax
    return alpha_of_gamma(gamma_max(p), p)


# ---------------------------------------------------------------------------
# Participation, variance, objective
# ---------------------------------------------------------------------------

def participation(gamma, p: SolverParams):
    am = alpha_of_gamma(gamma, p)
    a = jnp.sum(am)
    return am, a, am / a


def zeta_terms(gamma, p: SolverParams):
    _, a, pm = participation(gamma, p)
    tx = p.gmax**2 * jnp.sum(pm * gamma / a - pm**2)
    mb = jnp.sum(pm**2 * p.sigma_sq)
    nz = p.d * p.n0 / a**2
    return {"transmission": tx, "minibatch": mb, "noise": nz,
            "total": tx + mb + nz}


def bias_term(pm, p: SolverParams):
    n = pm.shape[-1]
    return 2.0 * n * p.kappa_sq * jnp.sum((pm - 1.0 / n) ** 2)


def p1_objective(gamma, p: SolverParams):
    z = zeta_terms(gamma, p)["total"]
    _, _, pm = participation(gamma, p)
    return 2.0 * p.eta * p.lsmooth * z + bias_term(pm, p)
