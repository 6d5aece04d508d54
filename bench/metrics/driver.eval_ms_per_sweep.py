"""driver.eval_ms_per_sweep: milliseconds per sweep in the program's
``eval`` spans, the vmapped eval at every eval round, read back to the
host."""
from bench import xtrace


def read(ctx):
    if not ctx.sweeps or not ctx.telemetry:
        return None
    return 1e3 * xtrace.span_seconds(ctx.telemetry, "eval") / ctx.sweeps
