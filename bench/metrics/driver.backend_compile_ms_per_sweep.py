"""driver.backend_compile_ms_per_sweep: milliseconds per sweep in the
program's outermost ``compile.backend`` spans: on a warm persistent
compile cache, the cache-key hash, the fetch and the executable load;
on a cold one, the XLA compile."""
from bench import scopes


def read(ctx):
    return scopes.phase_ms_per_sweep(ctx, "compile.backend")
