"""Shared benchmark utilities: robust timing and CSV/JSON emission.

Timing goes through the observability layer: ``time_fn`` brackets each
iteration with an obs span (``bench.iter``) read off the sanctioned
clock, so measured wall times land in the SAME trace stream as the
library's own spans when a tracer is active — and BENCH_scaling.json's
``wall_s`` column is obs-measured by construction.
"""
from __future__ import annotations

from typing import Callable

import json
import os

import jax

from repro.obs import trace as obs_trace
from repro.obs.clock import MONOTONIC


def append_json_rows(path: str, rows: list[dict]) -> None:
    """Append ``rows`` to the JSON list at ``path`` (created if absent) —
    the single implementation of the ``BENCH_scaling.json`` record
    contract shared by bench_scaling and bench_qr's fused sweep.
    benchmarks/run.py (and the CI bench job) delete the file up front so
    each harness run starts a fresh record."""
    existing = []
    if os.path.exists(path):
        with open(path) as f:
            existing = json.load(f)
    with open(path, "w") as f:
        json.dump(existing + rows, f, indent=1)


def time_fn(fn: Callable, *args, warmup: int = 2, iters: int = 5,
            label: str = "bench.iter") -> float:
    """Median wall seconds of ``fn(*args)`` (blocks on all outputs).

    Each timed iteration is an obs span named ``label`` whose attrs
    carry the measured seconds — under ``repro.obs.tracing`` the bench
    iterations appear in the exported trace; with no tracer the spans
    are shared no-ops and only the clock reads remain."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for i in range(iters):
        with obs_trace.span(label, iter=i) as sp:
            t0 = MONOTONIC()
            jax.block_until_ready(fn(*args))
            dt = MONOTONIC() - t0
            sp.set(seconds=dt)
        ts.append(dt)
    ts.sort()
    return ts[len(ts) // 2]


def emit(rows: list[dict], header: str = ""):
    """Print rows as aligned CSV (the bench harness contract)."""
    if not rows:
        return
    cols = list(rows[0].keys())
    if header:
        print(f"# {header}")
    print(",".join(cols))
    for r in rows:
        print(",".join(_fmt(r[c]) for c in cols))


def _fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:.6g}"
    return str(v)
