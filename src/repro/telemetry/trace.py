"""Structured JSONL event tracing (DESIGN.md §Telemetry).

One run directory holds one ``events.jsonl``: a flat, append-only stream
of events, one JSON object per line.  Every event carries the run id, a
wall clock (``wall``, epoch seconds — for humans and cross-process
ordering), a monotonic clock (``mono`` — for in-process durations; span
events additionally carry ``dur``, measured monotonically so NTP steps
can never produce negative spans, and ``mono`` is then the span's end)
and ``t0_ns``/``t1_ns``, the event's start and end on ``time.time_ns()``
— the JAX profiler's clock once its trace's ``profile_start_time`` is
subtracted, so a span lines up with the device ops of a profile with no
estimated offset.

While a tracer is installed with ``set_compile_tracer``, JAX's compile
phases become spans too: ``compile.jaxpr_trace`` (the Python trace to a
jaxpr), ``compile.lower`` (jaxpr to MLIR) and ``compile.backend`` (the
backend step: cache-key hash, persistent-cache fetch or XLA compile, and
executable load), each with ``fun`` (the function JAX names) and the
calling thread's ``ctx`` tags.  A ``jax.jit`` traced or lowered inside
another's phase is part of that phase and is not written on its own.

Appends are line-atomic by construction: each event is a single
``write()`` of one ``\\n``-terminated line to a file opened with
``O_APPEND``, behind a process-wide lock — the streaming driver's staging
worker and the main chunk loop interleave whole lines, never bytes.  A
kill can at worst truncate the final line; the resume path drops partial
trailing lines.

Kill-and-resume contract: ``Tracer(run_dir, fresh=False)`` re-opens an
existing log preserving its run id, and ``resume(start_chunk)`` prunes it
to exactly the events of completed chunks — every event tagged with
``chunk >= start_chunk`` is dropped (those chunks re-run and re-emit),
untagged non-lifecycle events are dropped too (they cannot be attributed,
so they may not be double-counted), and a ``run_resume`` marker is
appended.  A resumed run therefore produces ONE consistent log: no
duplicated chunk spans, no lost completed spans, a single run id.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
import uuid
from typing import List, Optional

import jax

EVENTS_FILE = "events.jsonl"

# JAX's compile-phase monitoring events and the span kind each becomes
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}

# lifecycle events survive resume pruning even though they carry no chunk
# tag: they record the history of the run, not per-chunk work
_LIFECYCLE = ("run_start", "run_resume")


def _jsonify(obj):
    """json.dumps default= hook: numpy scalars/arrays -> python."""
    if hasattr(obj, "tolist"):
        return obj.tolist()
    if hasattr(obj, "item"):
        return obj.item()
    return str(obj)


def read_events(path: str) -> List[dict]:
    """Parse an events.jsonl (or the run dir holding one) into a list,
    skipping partial (killed-mid-write) lines."""
    if os.path.isdir(path):
        path = os.path.join(path, EVENTS_FILE)
    out = []
    with open(path) as f:
        for line in f:
            if not line.endswith("\n"):
                continue                   # partial trailing line
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return out


class Tracer:
    """Low-overhead span/event writer for one run directory.

    fresh=True   truncate any existing log and start a new run id.
    fresh=False  re-open the existing log (kill-and-resume): the run id
                 is read back from its ``run_start`` line; call
                 ``resume(start_chunk)`` once the driver knows which
                 chunk it fast-forwarded to.  A missing log degrades to
                 a fresh start.
    """

    def __init__(self, run_dir: str, fresh: bool = True):
        os.makedirs(run_dir, exist_ok=True)
        self.run_dir = run_dir
        self.path = os.path.join(run_dir, EVENTS_FILE)
        self._lock = threading.Lock()
        self._local = threading.local()
        self.run_id: Optional[str] = None
        if not fresh or not os.path.exists(self.path):
            if os.path.exists(self.path):
                for ev in read_events(self.path):
                    if ev.get("ev") == "run_start":
                        self.run_id = ev.get("run")
                        break
        if self.run_id is None:
            self.run_id = uuid.uuid4().hex[:12]
            with open(self.path, "w"):
                pass                       # truncate: this is a new run
            self.event("run_start")

    # -- context tags -------------------------------------------------------

    @contextlib.contextmanager
    def ctx(self, **fields):
        """Thread-local default fields merged into every event emitted
        inside the scope — how the driver tags solver events fired deep
        inside a staging thread with the chunk they belong to."""
        old = getattr(self._local, "ctx", {})
        self._local.ctx = {**old, **fields}
        try:
            yield
        finally:
            self._local.ctx = old

    # -- emission -----------------------------------------------------------

    def event(self, kind: str, **fields) -> None:
        """Append one event.  ``t1_ns`` (default now) is its end on the
        wall clock and ``t0_ns`` its start (default ``t1_ns`` less
        ``dur``); ``mono`` is moved back with ``t1_ns``, so a span timed
        earlier keeps its end on the monotonic clock."""
        rec = {"ev": kind, "run": self.run_id,
               **getattr(self._local, "ctx", {}), **fields}
        now_ns, mono = time.time_ns(), time.monotonic()
        t1 = int(rec.setdefault("t1_ns", now_ns))
        dur = rec.get("dur")
        rec.setdefault("t0_ns", t1 - int(round(dur * 1e9))
                       if isinstance(dur, (int, float)) else t1)
        rec["wall"] = round(now_ns / 1e9, 6)
        rec["mono"] = round(mono - (now_ns - t1) / 1e9, 6)
        line = json.dumps(rec, default=_jsonify) + "\n"
        with self._lock:
            with open(self.path, "a") as f:
                f.write(line)

    @contextlib.contextmanager
    def span(self, kind: str, **fields):
        """Emit ``kind`` with a monotonic ``dur`` on scope exit; the scope
        is also a ``jax.profiler.TraceAnnotation`` named ``kind``, so a
        profile shows it beside the device ops."""
        t0, t0_ns = time.monotonic(), time.time_ns()
        try:
            with jax.profiler.TraceAnnotation(kind):
                yield
        finally:
            self.event(kind, dur=round(time.monotonic() - t0, 6),
                       t0_ns=t0_ns, **fields)

    # -- resume -------------------------------------------------------------

    def resume(self, start_chunk: int) -> None:
        """Prune the re-opened log to completed chunks (< ``start_chunk``)
        and mark the resume.  Atomic: the pruned log replaces the old one
        via ``os.replace``, so a kill during pruning loses nothing."""
        kept = [ev for ev in read_events(self.path)
                if ev.get("ev") in _LIFECYCLE
                or (isinstance(ev.get("chunk"), int)
                    and ev["chunk"] < start_chunk)]
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            for ev in kept:
                f.write(json.dumps(ev, default=_jsonify) + "\n")
        os.replace(tmp, self.path)
        self.event("run_resume", start_chunk=int(start_chunk))


# -- compile phases ------------------------------------------------------------

_COMPILE_TRACER: Optional[Tracer] = None
# open compile phases of each thread: JAX reports a phase's start as a
# scalar event and its span at the end, and a jit traced or lowered inside
# another's phase opens one of its own (hundreds per chunk program); only
# a thread's outermost phase is written, its inner ones are inside it
_OPEN = threading.local()


def _on_compile_start(event: str, value, **kwargs) -> None:
    if event in COMPILE_EVENTS:
        _OPEN.depth = getattr(_OPEN, "depth", 0) + 1


def _on_compile_phase(event: str, start_time: float, end_time: float,
                      **kwargs) -> None:
    kind = COMPILE_EVENTS.get(event)
    if kind is None:
        return
    _OPEN.depth = max(getattr(_OPEN, "depth", 1) - 1, 0)
    tracer = _COMPILE_TRACER
    if tracer is None or _OPEN.depth:
        return
    tracer.event(kind, fun=kwargs.get("fun_name"),
                 dur=round(end_time - start_time, 6),
                 t0_ns=int(start_time * 1e9), t1_ns=int(end_time * 1e9))


def set_compile_tracer(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Route JAX's compile-phase events to ``tracer`` (None: to nobody)
    and return the previous one, for the caller to restore in a
    ``finally``.  The two JAX listeners (phase start, phase span) are
    registered while some tracer is installed and removed when the slot
    is emptied again, so a process that never traces registers nothing."""
    global _COMPILE_TRACER
    prev, _COMPILE_TRACER = _COMPILE_TRACER, tracer
    if prev is None and tracer is not None:
        jax.monitoring.register_scalar_listener(_on_compile_start)
        jax.monitoring.register_event_time_span_listener(_on_compile_phase)
    elif prev is not None and tracer is None:
        jax.monitoring.unregister_scalar_listener(_on_compile_start)
        jax.monitoring.unregister_event_time_span_listener(_on_compile_phase)
    return prev
