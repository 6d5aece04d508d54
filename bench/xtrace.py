"""Reduction of a profiler trace and the program's spans to per-layer numbers.

A run with ``--trace 1`` records a JAX profiler trace (``.xplane.pb``)
of its traced sweeps and the program's telemetry events.  This module
turns them into plain event lists and reduces those: device busy time as
the union of op intervals, a kernel's summed device time, the device ops
that took most time, and idle gaps labelled with the host span they fell
in.  Every function below works on plain lists, so the tests can check it
on a small recorded trace without a chip.

Event dicts: ``{"plane", "line", "name", "start", "dur", "stats"}`` with
times in nanoseconds on the profiler's clock.
"""
from __future__ import annotations

import glob
import json
import os
import warnings

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def load_xplane(trace_dir: str) -> list:
    """Every timed event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        return []
    data = ProfileData.from_file(paths[-1])
    out = []
    with warnings.catch_warnings():
        # jaxlib's stat iterator warns once per event about its own type
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in data.planes:
            for line in plane.lines:
                for ev in line.events:
                    stats = {str(k): v for k, v in ev.stats
                             if isinstance(v, (str, int, float))}
                    out.append({"plane": plane.name, "line": line.name,
                                "name": ev.name, "start": float(ev.start_ns),
                                "dur": float(ev.duration_ns),
                                "stats": stats})
    return out


def device_planes(events: list) -> list:
    """Names of the TPU planes, sorted."""
    return sorted({e["plane"] for e in events
                   if e["plane"].startswith(DEVICE_PREFIX)})


def device_ops(events: list, plane: str, t0: float, t1: float) -> list:
    """Ops of one device plane that overlap [t0, t1], clipped to it."""
    out = []
    for e in events:
        if e["plane"] != plane or e["line"] != OPS_LINE:
            continue
        a, b = max(e["start"], t0), min(e["start"] + e["dur"], t1)
        if b > a:
            out.append({**e, "start": a, "dur": b - a})
    return out


def union(intervals: list) -> list:
    """Merged [start, end) intervals of ``intervals`` (pairs), sorted."""
    merged = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def without(t0: float, t1: float, holes: list) -> list:
    """[t0, t1] less the intervals ``holes`` (pairs): the pieces left, as
    sorted (start, end) pairs."""
    out, cursor = [], t0
    for a, b in union(holes):
        a, b = max(a, t0), min(b, t1)
        if b <= a:
            continue
        if a > cursor:
            out.append((cursor, a))
        cursor = max(cursor, b)
    if t1 > cursor:
        out.append((cursor, t1))
    return out


def busy_ns(ops: list) -> float:
    """Nanoseconds in which at least one of ``ops`` ran."""
    return sum(b - a for a, b in
               union([(e["start"], e["start"] + e["dur"]) for e in ops]))


def leaf_ops(ops: list) -> list:
    """The ops of one device that hold no other op.  A ``while`` (the
    chunk's scan over rounds) spans the ops of its body on the same
    line; counting it too would count its body twice."""
    ordered = sorted(ops, key=lambda e: (e["start"], -e["dur"]))
    return [e for e, nxt in zip(ordered, ordered[1:] + [None])
            if nxt is None
            or nxt["start"] + nxt["dur"] > e["start"] + e["dur"]]


def matches(event: dict, needle: str) -> bool:
    """Whether the event's name, or one of its string stats, names
    ``needle`` (a Pallas kernel shows its name in either, by version)."""
    if needle in event["name"]:
        return True
    return any(isinstance(v, str) and needle in v
               for v in event["stats"].values())


def kernel_ns(ops: list, needle: str) -> tuple:
    """(summed device time, number of events) of the ops naming
    ``needle``."""
    hits = [e for e in leaf_ops(ops) if matches(e, needle)]
    return sum(e["dur"] for e in hits), len(hits)


def top_ops(ops_by_plane: dict, top: int = 10) -> list:
    """[[op name, seconds per chip], ...]: the ``top`` op names that took
    most device time, summed over each chip's leaf ops and averaged over
    chips."""
    total = {}
    for ops in ops_by_plane.values():
        for e in leaf_ops(ops):
            total[e["name"]] = total.get(e["name"], 0.0) + e["dur"]
    chips = max(1, len(ops_by_plane))
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / chips / 1e9] for name, ns in ranked]


def idle_gaps(ops: list, spans: list, t0: float, t1: float) -> list:
    """Idle stretches of one device within [t0, t1], longest first, each
    as [label, seconds]: the label is the kind of the shortest host span
    that holds the gap's midpoint (``"none"`` where no span does).

    ``spans`` are ``{"kind", "start", "end"}`` on the profiler's clock."""
    busy = union([(e["start"], e["start"] + e["dur"]) for e in ops])
    gaps, cursor = [], t0
    for a, b in busy:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if t1 > cursor:
        gaps.append((cursor, t1))
    out = []
    for a, b in gaps:
        mid = 0.5 * (a + b)
        holding = [s for s in spans if s["start"] <= mid <= s["end"]]
        label = (min(holding, key=lambda s: s["end"] - s["start"])["kind"]
                 if holding else "none")
        out.append([label, (b - a) / 1e9])
    out.sort(key=lambda g: -g[1])
    return out


def idle_by_label(gaps: list) -> dict:
    """Idle seconds summed per label."""
    out = {}
    for label, sec in gaps:
        out[label] = out.get(label, 0.0) + sec
    return out


def telemetry_spans(run_dirs: list, offset_ns: float) -> list:
    """The program's telemetry spans from ``events.jsonl`` in each of
    ``run_dirs``, moved onto the profiler's clock by ``offset_ns``
    (profiler ns minus monotonic ns).  A span event is written when it
    ends: its ``mono`` is the end and ``dur`` the length, in seconds."""
    out = []
    for d in run_dirs:
        path = os.path.join(d, "events.jsonl")
        if not os.path.exists(path):
            continue
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if "dur" not in rec or "mono" not in rec:
                    continue
                end = float(rec["mono"]) * 1e9 + offset_ns
                out.append({"kind": rec.get("ev", "?"),
                            "start": end - float(rec["dur"]) * 1e9,
                            "end": end, "dur": float(rec["dur"]),
                            "fields": rec})
    return out


def span_seconds(spans: list, kind: str) -> float:
    """Summed seconds of the spans of one kind."""
    return sum(s["dur"] for s in spans if s["kind"] == kind)
