"""What the per-layer readers of name scopes share.

The program names the layers of its round step with
``jax.named_scope`` (``fl.grad``, ``fl.channel``, ``fl.uplink``,
``fl.step``, ``fl.eval``).  The harness writes the traced run's profile
to ``<root>/.bench_out/<cell>/trace``; the xprof converter's
``hlo_stats`` tool turns it into one row per (program, HLO op) with the
op's JAX name stack (``tf_op_name``) and its device self time.  An op
belongs to the innermost ``fl.`` scope its name stack names.  The
compile cache's key leaves name metadata out, so an executable fetched
from an entry that a program without the scopes wrote names none of
them: the readers then return None and log why, never 0.

Every function below but ``hlo_rows`` works on plain lists, so the tests
check it without a chip or a profile.
"""
from __future__ import annotations

import glob
import json
import os
import re

SCOPE = re.compile(r"(?<![\w.])fl\.[A-Za-z_]+")


def scope_of(name_stack) -> str | None:
    """The innermost ``fl.`` scope a JAX name stack names (a transform
    may wrap it, as in ``transpose(fl.grad)``), or None."""
    found = SCOPE.findall(name_stack or "")
    return found[-1] if found else None


def table_rows(table: dict) -> list:
    """The rows of a DataTable JSON object (``cols`` with ids, ``rows``
    of ``{"c": [{"v": value}, ...]}``) as dicts keyed by column id."""
    ids = [c["id"] for c in table.get("cols", [])]
    out = []
    for row in table.get("rows", []):
        cells = row.get("c", []) if isinstance(row, dict) else row
        out.append({k: (c.get("v") if isinstance(c, dict) else c)
                    for k, c in zip(ids, cells)})
    return out


def self_time_by_scope(rows: list) -> tuple:
    """({scope: self ns}, total self ns, [(op, name stack, ns), ...] of
    the unscoped ops, longest first) over ``hlo_stats`` rows, whose
    ``total_self_time`` is in microseconds."""
    by, total, unscoped = {}, 0.0, []
    for r in rows:
        ns = 1e3 * float(r.get("total_self_time") or 0.0)
        total += ns
        scope = scope_of(r.get("tf_op_name"))
        if scope is None:
            unscoped.append((r.get("hlo_op_name"), r.get("tf_op_name"), ns))
        else:
            by[scope] = by.get(scope, 0.0) + ns
    unscoped.sort(key=lambda u: -u[2])
    return by, total, unscoped


def hlo_rows(trace_dir: str) -> list:
    """The ``hlo_stats`` rows of the newest profile under ``trace_dir``
    (empty where there is none, or the converter gives nothing)."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        return []
    from xprof.convert import raw_to_tool_data

    data, _ = raw_to_tool_data.xspace_to_tool_data([paths[-1]], "hlo_stats",
                                                   {})
    if not data:
        return []
    return table_rows(json.loads(data))


def device_scopes(ctx):
    """{"by_scope": {scope: self ns per chip}, "total_ns": ...} of the
    traced run's device ops, read once per run; None (logged) where the
    run has no device trace or no op names an ``fl.`` scope."""
    if hasattr(ctx, "fl_scopes"):
        return ctx.fl_scopes
    ctx.fl_scopes = None
    if not ctx.planes:
        return None
    trace_dir = os.path.join(ctx.cell.root, ".bench_out", ctx.cell.name,
                             "trace")
    try:
        rows = hlo_rows(trace_dir)
    except Exception as e:                  # noqa: BLE001 - logged, None
        ctx.log(f"# scopes: hlo_stats of {trace_dir} failed: {e!r}")
        return None
    by, total, unscoped = self_time_by_scope(rows)
    if not by:
        ctx.log(f"# scopes: none of {len(rows)} device ops names an fl. "
                "scope: the program has none, or its executables came from "
                "a compile cache written by one that had none (the cache "
                "key leaves name metadata out)")
        return None
    chips = max(1, ctx.chips)
    ctx.log(f"# scopes: self time {total / chips / 1e9!r} s per chip "
            f"(device busy {ctx.busy_s!r} s); "
            + ", ".join(f"{k} {v / chips / 1e9!r} s"
                        for k, v in sorted(by.items())))
    for op, stack, ns in unscoped[:5]:
        ctx.log(f"# scopes: unscoped {op} {ns / chips / 1e9!r} s: {stack}")
    ctx.fl_scopes = {"by_scope": {k: v / chips for k, v in by.items()},
                     "total_ns": total / chips}
    return ctx.fl_scopes


def scope_ms_per_round(ctx, scope: str):
    """Device self milliseconds a round under ``scope``, per chip; None
    where the scopes cannot be read or none of the ops is under it."""
    got = device_scopes(ctx)
    if got is None or not ctx.sweeps or not ctx.rounds:
        return None
    ns = got["by_scope"].get(scope)
    if not ns:
        ctx.log(f"# scopes: no device op under {scope}")
        return None
    return ns / 1e6 / (ctx.sweeps * ctx.rounds)
