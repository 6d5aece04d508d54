"""JAX-native batched SCA solver for the (P1) power-control design.

Same algorithm as ``core/sca.py`` (paper §III-B), re-expressed so the whole
solve — outer SCA loop, convex inner subproblems, monotone-descent
backtracking — is ONE jit-compiled program that ``vmap``s over a scenario
batch (``solve_batch``).  The scipy SLSQP path stays as the reference
oracle; this path is the default design engine (``power_control.make_sca``)
and the only one fast enough to re-design powers *during* training
(``AdaptiveSCA``).

Structure (DESIGN.md §Solvers):

* Scaled variables, identical to ``core/sca.py``: gamma_hat = gamma /
  gamma_max in (0, 1], p on the simplex, alpha_hat = alpha / sum(alpha_max)
  — every decision variable O(1) despite physical scales ~1e-9.
* Inner solver: each SCA iteration minimizes the convex surrogate (11a-11e)
  (epigraph variable eliminated via tight (11b), exactly like the scipy
  path) with a projected-gradient method: constraints (11c)/(11d) enter as
  smooth quadratic penalties on an escalating schedule, the simplex /
  box constraints by exact projection (sort-based simplex projection), and
  every step is Armijo-backtracked — a fixed iteration budget so the loop
  is a ``lax.scan``.
* Monotone descent is preserved *outside* the inner solver, as in scipy:
  after each subproblem the exact coupling (p, alpha from gamma) is
  restored and the candidate is backtracked toward the anchor on the TRUE
  objective; a step is only taken if it strictly improves.
* A final polish stage descends the true objective itself (smooth in
  gamma_hat over the box, with (p, alpha) restored by exact coupling): an
  adaptive best-iterate-tracked stage rides the ill-conditioned tail, an
  Armijo stage finishes.  Both return iterates no worse than their input,
  so monotonicity survives and ``solve_batch`` tracks the SLSQP oracle to
  ~1e-6 relative on the reference cases (asserted in tests and
  benchmarks/sca_bench.py).

Everything runs under ``jax.enable_x64(True)``: the *scaled*
variables are O(1) but intermediate quantities (alpha ~ 1e-8, alpha^2 in
the noise term) need f64 headroom.  The x64 scope is entered per public
call and never leaks into the (f32) training path.

Where the default backend is an accelerator, ``x64_scope`` also pins the
solve to the host's CPU backend: a TPU emulates f64, and each new solve
program there compiles and runs for ~100 s where the host takes seconds.
An explicit ``placement`` in ``solve_batch`` still maps the batch onto its
own devices.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sca import SCAResult
from repro.core.theory import OTAParams
from repro.solvers import theory_jax as tj
from repro.solvers.theory_jax import SolverParams

_EPS = 1e-12


@dataclasses.dataclass(frozen=True)
class SolverConfig:
    """Fixed iteration budgets (static jit config; hashable)."""
    max_iters: int = 16           # outer SCA iterations
    inner_iters: int = 100        # projected-gradient steps per penalty stage
    inner_lr: float = 0.03        # inner per-coordinate adaptive step size
    penalties: tuple = (1e2, 1e4, 1e6)   # (11c)/(11d) penalty schedule
    backtracks: int = 12          # true-objective backtracking halvings
    armijo_halvings: int = 20     # polish line-search halvings
    polish_adam_iters: int = 400  # adaptive polish steps (best-iterate kept)
    polish_adam_lr: float = 0.01
    polish_iters: int = 120       # Armijo polish steps (finisher)
    tol: float = 1e-6             # convergence tolerance (reported only)


DEFAULT_CONFIG = SolverConfig()


@dataclasses.dataclass
class BatchResult:
    """``solve_batch`` output: leading [B] axis on every field (numpy)."""
    gamma: np.ndarray        # [B, N] physical pre-scalers
    p: np.ndarray            # [B, N] participation levels
    alpha: np.ndarray        # [B] post-scalers
    objective: np.ndarray    # [B] true (P1) objectives
    history: np.ndarray      # [B, max_iters + 2]: start, outer iterates,
    #                          post-polish objective (monotone)
    converged: np.ndarray    # [B] bool: the outer SCA loop plateaued


# ---------------------------------------------------------------------------
# projections
# ---------------------------------------------------------------------------

def project_simplex(v: jnp.ndarray) -> jnp.ndarray:
    """Euclidean projection onto the probability simplex (sort-based)."""
    n = v.shape[-1]
    u = jnp.sort(v)[..., ::-1]
    css = jnp.cumsum(u, axis=-1) - 1.0
    idx = jnp.arange(1, n + 1, dtype=v.dtype)
    cond = u - css / idx > 0
    rho = jnp.sum(cond, axis=-1)
    theta = jnp.take_along_axis(css, rho[..., None] - 1, axis=-1)[..., 0] \
        / rho.astype(v.dtype)
    return jnp.maximum(v - theta[..., None], 0.0)


def _project(x, n):
    gh = jnp.clip(x[:n], 1e-6, 1.0)
    p = jnp.maximum(project_simplex(x[n:2 * n]), _EPS)
    ah = jnp.clip(x[2 * n:], 1e-6, 2.0)
    return jnp.concatenate([gh, p, ah])


# ---------------------------------------------------------------------------
# the convex surrogate (11) around an anchor, penalized form
# ---------------------------------------------------------------------------

def _surrogate_fn(prm: SolverParams, gmax_arr, amax_arr, a0,
                  anchor_gh, anchor_p, anchor_ah, mu):
    """Penalized surrogate phi(x) for x = [gh(N), p(N), ah(1)] (scaled)."""
    n = gmax_arr.shape[0]
    eta_l = prm.eta * prm.lsmooth
    g2 = prm.gmax**2
    g_bar = anchor_gh * gmax_arr
    a_bar = anchor_ah * a0
    p_bar = jnp.maximum(anchor_p, 1e-9)

    def phi(x):
        gh = jnp.maximum(x[:n], _EPS)
        p = jnp.maximum(x[n:2 * n], _EPS)
        ah = jnp.maximum(x[2 * n], _EPS)
        gamma = gh * gmax_arr
        alpha = ah * a0
        # z_m eliminated via tight (11b)
        logz = (jnp.log(g_bar * p_bar) + gamma / g_bar + p / p_bar - 2.0
                - jnp.log(alpha))
        z = jnp.exp(logz)
        lin_p2 = p_bar * (2.0 * p - p_bar)
        obj = eta_l * (g2 * jnp.sum(z) + prm.d * prm.n0 / alpha**2
                       + jnp.sum(p**2 * prm.sigma_sq)
                       - g2 * jnp.sum(lin_p2))
        obj += n * prm.kappa_sq * jnp.sum((p - 1.0 / n) ** 2)
        # (11c): ln alpha_m(gamma) >= linearized ln(alpha p_m)
        c11c = tj.log_alpha_of_gamma(gamma, prm) \
            - (jnp.log(a_bar * p_bar) + alpha / a_bar + p / p_bar - 2.0)
        # (11d): concave 1/alpha bound, alpha-scaled to O(1)
        c11d = a0 * ((2.0 * a_bar - alpha) / a_bar**2 - p / amax_arr)
        pen = jnp.sum(jnp.minimum(c11c, 0.0) ** 2) \
            + jnp.sum(jnp.minimum(c11d, 0.0) ** 2)
        return obj + mu * pen

    return phi


def _inner_pgd(phi, x0, n, num_iters: int, lr: float):
    """Projected per-coordinate-adaptive gradient descent on the penalized
    surrogate (Adam-style moments + exact simplex/box projection).

    The penalty valley is stiff — plain Armijo gradient steps stall at the
    anchor — so the inner solver uses adaptive per-coordinate scaling and a
    fixed budget instead of a line search.  It need not be monotone: SCA
    descent is enforced OUTSIDE, by the true-objective backtracking that
    only accepts improving candidates (exactly the scipy path's safeguard).
    """
    grad = jax.grad(phi)
    b1, b2 = 0.9, 0.999

    def step(carry, _):
        x, m, v, t = carry
        g = grad(x)
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        t = t + 1
        mh = m / (1.0 - b1**t)
        vh = v / (1.0 - b2**t)
        x = _project(x - lr * mh / (jnp.sqrt(vh) + 1e-12), n)
        return (x, m, v, t), None

    zero = jnp.zeros_like(x0)
    (x, _, _, _), _ = jax.lax.scan(
        step, (x0, zero, zero, jnp.asarray(0, jnp.int32)), None,
        length=num_iters)
    return x


# ---------------------------------------------------------------------------
# the solve: SCA outer loop + polish, all inside one jit
# ---------------------------------------------------------------------------

def _true_objective(gh, prm: SolverParams, gmax_arr):
    return tj.p1_objective(jnp.maximum(gh, 1e-6) * gmax_arr, prm)


def _solve_one(prm: SolverParams, gamma0: Optional[jnp.ndarray],
               cfg: SolverConfig):
    n = prm.gains.shape[0]
    gmax_arr = tj.gamma_max(prm)
    amax_arr = tj.alpha_max(prm)
    a0 = jnp.sum(amax_arr)

    gh0 = jnp.ones(n, gmax_arr.dtype) if gamma0 is None \
        else jnp.asarray(gamma0) / gmax_arr
    true_obj = lambda gh: _true_objective(gh, prm, gmax_arr)

    def coupled(gh):
        _, a, pm = tj.participation(gh * gmax_arr, prm)
        return pm, a / a0

    def outer(carry, _):
        gh, pm, ah, obj = carry
        x = jnp.concatenate([gh, pm, ah[None]])
        for mu in cfg.penalties:
            phi = _surrogate_fn(prm, gmax_arr, amax_arr, a0, gh, pm, ah,
                                jnp.asarray(mu, x.dtype))
            x = _inner_pgd(phi, x, n, cfg.inner_iters, cfg.inner_lr)
        cand = jnp.clip(x[:n], 1e-6, 1.0)
        # true-objective backtracking toward the anchor: accept the first
        # (largest) theta that strictly improves, else stay (scipy logic).
        thetas = 0.5 ** jnp.arange(cfg.backtracks, dtype=gh.dtype)
        trials = thetas[:, None] * cand[None, :] \
            + (1.0 - thetas[:, None]) * gh[None, :]
        objs = jax.vmap(true_obj)(trials)
        improves = objs < obj
        any_imp = jnp.any(improves)
        first = jnp.argmax(improves)          # first True = largest theta
        gh_next = jnp.where(any_imp, trials[first], gh)
        obj_next = jnp.where(any_imp, objs[first], obj)
        pm_next, ah_next = coupled(gh_next)
        return (gh_next, pm_next, ah_next, obj_next), obj_next

    pm0, ah0 = coupled(gh0)
    obj0 = true_obj(gh0)
    (gh, pm, ah, obj), hist = jax.lax.scan(
        outer, (gh0, pm0, ah0, obj0), None, length=cfg.max_iters)

    # polish on the true objective: a best-iterate-tracked adaptive stage
    # rides down the ill-conditioned tail, an Armijo stage finishes.  Both
    # only ever return iterates at least as good as their input, so the
    # overall descent stays monotone.
    if cfg.polish_adam_iters > 0:
        gh = _polish_adam(true_obj, gh, cfg.polish_adam_iters,
                          cfg.polish_adam_lr)
    if cfg.polish_iters > 0:
        gh = _polish(true_obj, gh, cfg.polish_iters, cfg.armijo_halvings)
    obj = true_obj(gh)
    pm, ah = coupled(gh)

    # history = [start, outer iterates..., post-polish objective]; converged
    # reports the OUTER loop's plateau (the polish may still refine the
    # returned objective — its result is history's last entry).
    history = jnp.concatenate([obj0[None], hist, obj[None]])
    converged = jnp.abs(hist[-1] - hist[-2]) \
        <= cfg.tol * jnp.maximum(1.0, jnp.abs(hist[-1]))
    gamma = gh * gmax_arr
    return dict(gamma=gamma, p=pm, alpha=ah * a0, objective=obj,
                history=history, converged=converged)


def _polish_adam(true_obj, gh0, num_iters: int, lr: float):
    """Box-projected adaptive descent on the true objective, returning the
    best iterate seen (never worse than gh0)."""
    grad = jax.grad(true_obj)
    b1, b2 = 0.9, 0.999

    def step(carry, _):
        x, m, v, t, best_x, best_f = carry
        g = grad(x)
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        t = t + 1
        x = jnp.clip(
            x - lr * (m / (1.0 - b1**t))
            / (jnp.sqrt(v / (1.0 - b2**t)) + 1e-12), 1e-6, 1.0)
        fx = true_obj(x)
        better = fx < best_f
        best_x = jnp.where(better, x, best_x)
        best_f = jnp.where(better, fx, best_f)
        return (x, m, v, t, best_x, best_f), None

    zero = jnp.zeros_like(gh0)
    (_, _, _, _, best_x, _), _ = jax.lax.scan(
        step, (gh0, zero, zero, jnp.asarray(0, jnp.int32), gh0,
               true_obj(gh0)), None, length=num_iters)
    return best_x


def _polish(true_obj, gh0, num_iters: int, halvings: int):
    """Box-projected Armijo gradient descent on the true objective."""
    grad = jax.grad(true_obj)

    def step(carry, _):
        gh, t = carry
        g = grad(gh)
        f0 = true_obj(gh)

        def try_step(tt):
            xn = jnp.clip(gh - tt * g, 1e-6, 1.0)
            return xn, true_obj(xn)

        def cond(state):
            tt, _, fn, k = state
            return jnp.logical_and(fn > f0 - 1e-4 * tt * jnp.sum(g * g),
                                   k < halvings)

        def body(state):
            tt, _, _, k = state
            tt = 0.5 * tt
            xn, fn = try_step(tt)
            return tt, xn, fn, k + 1

        x1, f1 = try_step(t)
        t_fin, x_fin, f_fin, _ = jax.lax.while_loop(
            cond, body, (t, x1, f1, 0))
        ok = f_fin < f0
        gh_next = jnp.where(ok, x_fin, gh)
        t_next = jnp.maximum(
            jnp.where(ok, jnp.minimum(t_fin * 2.0, 1.0), 0.25 * t), 1e-12)
        return (gh_next, t_next), None

    (gh, _), _ = jax.lax.scan(step, (gh0, jnp.asarray(0.1, gh0.dtype)),
                              None, length=num_iters)
    return gh


@functools.partial(jax.jit, static_argnames=("cfg", "with_gamma0"))
def _solve_single_jit(prm, gamma0, cfg, with_gamma0):
    return _solve_one(prm, gamma0 if with_gamma0 else None, cfg)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _solve_batch_jit(prm_b, cfg):
    return jax.vmap(lambda p: _solve_one(p, None, cfg))(prm_b)


@functools.lru_cache(maxsize=None)
def _placed_batch_solver(placement, cfg):
    """Compiled batch solve on a placement, cached per (placement, cfg) so
    repeated placed solves reuse the jit trace exactly like the default
    ``_solve_batch_jit`` path (a fresh closure per call would retrace —
    and recompile the whole SSCA scan — every time)."""
    return placement.compile_batch(lambda p: _solve_one(p, None, cfg))


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def x64_scope():
    """The scope every design solve runs in: x64 on, and the host's CPU
    backend as default device when the default backend is not the CPU."""
    with jax.enable_x64(True):
        if jax.default_backend() == "cpu":
            yield
        else:
            with jax.default_device(jax.devices("cpu")[0]):
                yield


def solve(prm: OTAParams, gamma0: Optional[np.ndarray] = None,
          cfg: SolverConfig = DEFAULT_CONFIG) -> SCAResult:
    """Single-scenario compiled SCA solve; drop-in for ``sca.solve_sca``.

    Returns the same ``SCAResult`` (numpy, physical units); ``iterations``
    reports the fixed outer budget (the loop is compiled, not early-exited).
    """
    with x64_scope():
        pj = tj.from_ota(prm)
        g0 = None if gamma0 is None else jnp.asarray(gamma0, jnp.float64)
        out = _solve_single_jit(pj, g0, cfg, gamma0 is not None)
        out = {k: np.asarray(v) for k, v in out.items()}
    return SCAResult(gamma=out["gamma"], p=out["p"],
                     alpha=float(out["alpha"]),
                     objective=float(out["objective"]),
                     history=[float(h) for h in out["history"]],
                     converged=bool(out["converged"]),
                     iterations=cfg.max_iters)


def _as_f64(pj: SolverParams) -> SolverParams:
    """Recast every leaf to f64 (must run inside an x64 scope).  Guards the
    pre-stacked path: ``stack_params`` called OUTSIDE an x64 scope silently
    builds f32 leaves, which would crash the scan carry dtype check."""
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float64), pj)


def solve_batch(prms, cfg: SolverConfig = DEFAULT_CONFIG,
                placement=None) -> BatchResult:
    """Design powers for a batch of scenarios in ONE compiled program.

    ``prms``: a sequence of ``OTAParams`` (stacked here), or an already
    stacked ``SolverParams`` with a leading [B] batch axis (e.g. from
    ``theory_jax.stack_params`` or built on device by ``AdaptiveSCA``).
    All rows share the fading family and device count; gains / noise /
    dropout / family parameters / objective weights vary per row.

    ``placement``: optional ``fl.placement`` object mapping the batch axis
    onto hardware — ``ShardedPlacement(mesh)`` shards a thousand-scenario
    design batch over the ``("data", "model")`` mesh exactly like the
    fleet grid shards (rows are independent; the shard_map is psum-free,
    with the same pad-with-row-0 rule when B doesn't divide the device
    count).  ``None`` (default) keeps the single-device vmap program, on
    the host (``x64_scope``).
    """
    with (x64_scope() if placement is None else jax.enable_x64(True)):
        pj = _as_f64(prms if isinstance(prms, SolverParams) else stack(prms))
        if placement is None:
            out = _solve_batch_jit(pj, cfg)
        else:
            out = _placed_batch_solver(placement, cfg)(pj)
        out = {k: np.asarray(v) for k, v in out.items()}
    return BatchResult(gamma=out["gamma"], p=out["p"], alpha=out["alpha"],
                       objective=out["objective"], history=out["history"],
                       converged=out["converged"])


def stack(prms: Sequence[OTAParams]) -> SolverParams:
    return tj.stack_params(prms)


# Per-solve telemetry hook (DESIGN.md §Telemetry).  The driver installs
# one around telemetry-enabled runs; unset (the default) the solve path
# is untouched — no timing calls, no host syncs.
_TRACE_HOOK: Optional[Callable] = None


def set_trace_hook(hook: Optional[Callable]) -> Optional[Callable]:
    """Install ``hook(record: dict)`` called once per device-resident
    batched SCA solve with {batch, iters, objective_mean, converged, dur}.
    Returns the previous hook so callers can restore it (try/finally).
    The hook host-syncs the solve outputs to report the objective, so it
    belongs in observability paths only."""
    global _TRACE_HOOK
    prev = _TRACE_HOOK
    _TRACE_HOOK = hook
    return prev


def solve_batch_device(prm_b: SolverParams,
                       cfg: SolverConfig = DEFAULT_CONFIG) -> dict:
    """Device-resident batch solve: jnp in, jnp out (no host round-trip).

    Used by the in-training re-design path (``AdaptiveSCA``), where the
    batch of scenarios is derived from the live fading state.  Caller is
    responsible for the x64 scope semantics: this enters ``x64_scope`` too,
    so the returned arrays are f64 (on the host, off the CPU backend).
    """
    with x64_scope():
        hook = _TRACE_HOOK
        t0 = time.monotonic() if hook is not None else 0.0
        out = _solve_batch_jit(_as_f64(prm_b), cfg)
        if hook is not None:
            obj = np.asarray(out["objective"])
            conv = np.asarray(out["converged"])
            hook({"batch": int(obj.shape[0]) if obj.ndim else 1,
                  "iters": int(cfg.max_iters),
                  "objective_mean": float(np.mean(obj)),
                  "converged": int(np.sum(conv)),
                  "dur": round(time.monotonic() - t0, 6)})
        return out
