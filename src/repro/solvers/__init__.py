"""JAX-native batched SCA solver subsystem (DESIGN.md §Solvers).

``theory_jax``  jnp port of the Theorem-1 statistical-CSI quantities
                (all fading families), jit/vmap/grad-ready.
``sca_jax``     the compiled SCA solver: ``solve`` (single scenario,
                drop-in for ``core.sca.solve_sca``) and ``solve_batch``
                (one compiled program over a stacked scenario batch).

``core/sca.py`` (scipy SLSQP) remains the reference oracle.
"""
from repro.solvers.sca_jax import (BatchResult, DEFAULT_CONFIG, SolverConfig,
                                   set_trace_hook, solve, solve_batch,
                                   solve_batch_device, x64_scope)
from repro.solvers.theory_jax import SolverParams, from_ota, stack_params

__all__ = [
    "BatchResult", "DEFAULT_CONFIG", "SolverConfig", "SolverParams",
    "from_ota", "set_trace_hook", "solve", "solve_batch",
    "solve_batch_device", "stack_params", "x64_scope",
]
