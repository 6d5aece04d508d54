"""driver.lower_ms_per_sweep: milliseconds per sweep in the program's
outermost ``compile.lower`` spans, the lowering of each traced function
to an MLIR module."""
from bench import scopes


def read(ctx):
    return scopes.phase_ms_per_sweep(ctx, "compile.lower")
