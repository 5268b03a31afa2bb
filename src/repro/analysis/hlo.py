"""Collective payloads parsed from compiled HLO text.

Pure text parsing with no import side effects, so the decomposition
path (``core.qr_dist`` under deep tracing) and the lowering benchmarks
can read collective bytes without touching the process's XLA flags.
"""
from __future__ import annotations

import re

__all__ = ["COLLECTIVE_RE", "shape_bytes", "collective_bytes"]

# HLO collective result-shape parser: handles tuples and all dtypes.
COLLECTIVE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+\[[^\]]*\]\S*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", re.M)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16}


def shape_bytes(type_str: str) -> int:
    """Bytes of every array shape named in an HLO type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes per collective kind over the whole module."""
    out: dict = {}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        ty, kind = m.group(1), m.group(2)
        # -start ops carry the real payload; -done would double count.
        if m.group(0).rstrip("(").endswith("-done"):
            continue
        out[kind] = out.get(kind, 0) + shape_bytes(ty)
    return out
