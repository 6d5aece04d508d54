"""HLO text utilities: collective-byte accounting for the roofline.

collective_bytes is NOT in cost_analysis(); we parse the compiled per-device
HLO module and sum result-shape bytes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute.  ``-start`` async variants
are counted, ``-done`` are not (no double counting).
"""
from __future__ import annotations

import re

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8,
                "c128": 16}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*([^=]*?)\s*"
    r"(all-gather-start|all-gather-done|all-gather|"
    r"all-reduce-start|all-reduce-done|all-reduce|"
    r"reduce-scatter|all-to-all|"
    r"collective-permute-start|collective-permute-done|collective-permute)"
    r"\(")


def shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> dict:
    out: dict = {}
    for m in _COLL_RE.finditer(hlo_text):
        type_str, op = m.group(1), m.group(2)
        if op.endswith("-done"):
            continue
        op = op.replace("-start", "")
        out[op] = out.get(op, 0) + shape_bytes(type_str)
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def count_op(hlo_text: str, opname: str) -> int:
    return len(re.findall(rf"\b{re.escape(opname)}\(", hlo_text))
