"""driver.retrace_ms_per_sweep: milliseconds per sweep in the program's
``chunk_compile`` spans, the calls that grow a chunk's compile cache
(trace, lowering and compile or persistent-cache fetch) — paid again in
every ``run_fleet_task`` call, since each builds new jitted chunks."""
from bench import xtrace


def read(ctx):
    if not ctx.sweeps or not ctx.telemetry:
        return None
    return 1e3 * xtrace.span_seconds(ctx.telemetry, "chunk_compile") \
        / ctx.sweeps
