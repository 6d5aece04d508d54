"""driver.jaxpr_trace_ms_per_sweep: milliseconds per sweep in the
program's outermost ``compile.jaxpr_trace`` spans, JAX's Python trace of
each function it compiles (mostly the chunk's ``vmap(scan(round body))``,
paid in every ``run_fleet_task`` call)."""
from bench import scopes


def read(ctx):
    return scopes.phase_ms_per_sweep(ctx, "compile.jaxpr_trace")
