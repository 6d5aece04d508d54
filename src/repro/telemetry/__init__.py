"""Fleet telemetry subsystem (DESIGN.md §Telemetry).

Three layers, all opt-in:

``trace``        structured JSONL span/event writer (run id, monotonic
                 and wall clocks, line-atomic appends, kill-and-resume
                 pruning) and the ``compile.*`` spans of JAX's compile
                 phases (``set_compile_tracer``).
``diagnostics``  in-graph Theorem-1 collectors — realized OTA bias power
                 and effective noise variance per [K, S] cell, riding the
                 engine's ``hist.traces`` mechanism.
``report``       ``python -m repro.telemetry.report <run_dir>`` renders
                 the staging-overlap timeline, bias-variance trajectory,
                 staleness histograms and a recompilation audit.

The whole subsystem hangs off one knob: ``fl.driver.run_fleet(...,
telemetry=Telemetry(run_dir))``.  Left at the default ``None``, every
hook stays unset and the compiled programs, key streams and walls are
byte-identical to a build without this package (the bitwise-off
guarantee, pinned by tests/test_telemetry.py).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional

from repro.telemetry.diagnostics import (DIAG_PREFIX, is_diagnostic,
                                         make_metrics_hook)
from repro.telemetry.trace import (EVENTS_FILE, Tracer, read_events,
                                   set_compile_tracer)

__all__ = [
    "DIAG_PREFIX", "EVENTS_FILE", "Telemetry", "Tracer",
    "assert_no_recompile", "chunk_cache_size", "is_diagnostic",
    "make_metrics_hook", "read_events", "set_compile_tracer",
]


@dataclasses.dataclass(frozen=True)
class Telemetry:
    """Telemetry configuration handed to ``fl.driver.run_fleet``.

    run_dir      where ``events.jsonl`` lives; the report tool reads the
                 same directory (put the fleet checkpoint next to it to
                 get the bias-variance trajectory in the report too).
    trace        emit the structured event stream (spans for chunk exec,
                 cohort staging, redesign, checkpoint I/O, SCA solves,
                 and JAX's compile phases).
    diagnostics  add the in-graph ``bv_*`` Theorem-1 traces to every
                 round's metrics (recorded into FLResult.traces and any
                 fleet checkpoint; keep the setting consistent across a
                 kill-and-resume so trace keys line up).
    kappa_sq     the paper's kappa^2 gradient-dissimilarity constant, so
                 the traced bias power is in the SCA objective's units.

    Overhead contract: diagnostics are a handful of extra scalar
    reductions fused into the already-compiled chunk (no host syncs, no
    extra dispatches); tracing adds one ``block_until_ready`` per chunk
    for honest exec attribution plus O(events) tiny host writes and one
    JAX monitoring listener for the run — walls may shift, math never
    does (stream/serial and resume stay bitwise).
    """
    run_dir: str
    trace: bool = True
    diagnostics: bool = True
    kappa_sq: float = 1.0


def chunk_cache_size(chunk) -> Optional[int]:
    """Compiled-program cache size of a placement-built chunk: the jit
    trace cache for ``VmapPlacement`` chunks, the explicit per-(length,
    grid) compile dict for ``ShardedPlacement`` chunks.  None when the
    object exposes neither (nothing to audit)."""
    fn = getattr(chunk, "_cache_size", None)
    return int(fn()) if callable(fn) else None


@contextlib.contextmanager
def assert_no_recompile(*chunks, allowed: int = 0):
    """Assert the compile caches of ``chunks`` grow by at most ``allowed``
    entries across the scope — the reusable form of the inline
    ``chunk._cache_size()`` checks the population tests pinned: operands
    (cohort draws, design leaves) must swap through ONE compiled program.

    Warm the expected shapes before entering (the first call at a new
    chunk length legitimately compiles); then any growth inside the scope
    is a recompilation regression.
    """
    before = []
    for c in chunks:
        size = chunk_cache_size(c)
        if size is None:
            raise ValueError(f"{c!r} exposes no compile cache to audit")
        before.append(size)
    yield
    for c, b in zip(chunks, before):
        now = chunk_cache_size(c)
        if now - b > allowed:
            raise AssertionError(
                f"chunk recompiled: compile cache grew {b} -> {now} "
                f"(allowed growth {allowed}) for {c!r}")
