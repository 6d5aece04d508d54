"""driver.retrace_ms_per_sweep: milliseconds per sweep in the program's
``chunk_compile`` spans, the calls that grow a chunk's compile cache
(trace, lowering and compile or persistent-cache fetch).  The driver
reuses the warm-up sweep's chunks across ``run_fleet_task`` calls, so a
window sweep reads 0: the guard that a window sweep compiles nothing."""
from bench import xtrace


def read(ctx):
    if not ctx.sweeps or not ctx.telemetry:
        return None
    return 1e3 * xtrace.span_seconds(ctx.telemetry, "chunk_compile") \
        / ctx.sweeps
