"""Streaming RID scaling: peak device residency vs input size + the
transfer/compute overlap of the two-stream pipeline (ISSUE 5).

The claim under test is the subsystem's reason to exist: the streamed
decomposition's device working set is ``O(l n + chunk_rows n)`` —
FLAT in ``m`` — while the input grows without bound.  The sweep feeds
host-resident matrices of growing ``m`` through ``rid_streamed``,
samples ``jax.live_arrays()`` at every chunk boundary (both transfer
buffers + the accumulator live: the streaming peak), and records

  bench = "stream_scaling": m, n, k, chunk_rows, input_bytes,
  peak_device_bytes, acc_bytes (the l x n accumulator),
  wall_pipelined_s, wall_serialized_s, overlap_efficiency
  (= serialized / pipelined; ~1.0 on CPU where host->device is a
  no-op copy, > 1 wherever a DMA engine overlaps the accumulate GEMM)

into ``BENCH_scaling.json`` (benchmarks/run.py contract).  Wall times
come from the pipeline's OWN obs spans (``repro.obs.tracing`` around
``rid_streamed``; the root span's duration is the measured wall) rather
than a stopwatch around the call, so the bench measures exactly what a
production trace would show.

The largest input additionally runs under DEEP tracing (per-phase
``block_until_ready`` bracketing — serializes the pipeline, honest
device time per phase) and emits ``bench = "stream_phases"`` rows: one
per pipeline phase (h2d / accumulate / qr_interp / gather) with the
obs-measured ``wall_s`` NEXT TO the v5e-roofline ``model_time_s`` for
that phase — the measured-vs-modeled pairs benchmarks/run.py turns into
``model_accuracy`` ratios.

The run asserts the acceptance shape: the largest input exceeds its own
streaming working set (a decomposition that could NOT have run with a
single resident buffer of the same budget), and the peak stays flat
across the sweep.
"""
from __future__ import annotations

import argparse
import os
import tempfile

import jax
import numpy as np

from jax import make_mesh
from jax.sharding import AxisType
from repro.core import rid_streamed
from repro.obs import MeteredSource, tracing
from repro.stream import ArraySource, FileSource

from .bench_scaling import HBM, PEAK
from .common import append_json_rows, emit


def _root_dur(tracer, name="rid_streamed") -> float:
    return next(s.dur for s in tracer.spans if s.name == name)


def _span_sum(tracer, name: str) -> float:
    return sum(s.dur or 0.0 for s in tracer.spans if s.name == name)


def _phase_rows(tr, *, m, n, k, l, chunk_rows) -> list[dict]:
    """Measured (deep-traced obs spans) next to modeled (v5e roofline
    terms) seconds, one row per streamed-RID phase."""
    fbytes = 4                                   # f32 sweep
    model = {
        # H2D ingest of the whole input, at HBM write bandwidth
        "h2d": m * n * fbytes / HBM,
        # accumulate GEMM: Omega^T A, flops vs one full read of A
        "accumulate": max(2.0 * m * n * l / PEAK, m * n * fbytes / HBM),
        # QRCP + interpolation solve on the l x n sketch
        "qr_interp": max(4.0 * l * n * k / PEAK, l * n * fbytes / HBM),
        # pass-2 host gather of the k pivot columns
        "gather": m * k * fbytes / HBM,
    }
    spans = {"h2d": "stream.h2d", "accumulate": "stream.accumulate",
             "qr_interp": "stream.qr_interp", "gather": "stream.gather"}
    return [{"bench": "stream_phases", "m": m, "n": n, "k": k,
             "chunk_rows": chunk_rows, "phase": ph,
             "wall_s": _span_sum(tr, spans[ph]),
             "model_time_s": model[ph]}
            for ph in model]


def stream_sweep(*, full=False, json_path=None):
    n, k, chunk_rows = 512, 48, 512
    ms = (8192, 16384, 32768, 131072) if full else (8192, 16384, 32768)
    l = 2 * k
    rows, phase_rows = [], []
    for m in ms:
        A = np.asarray(np.random.default_rng(3).standard_normal((m, n)),
                       np.float32)
        key = jax.random.key(1)
        src = MeteredSource(ArraySource(A, chunk_rows))
        # warm the per-shape jit caches, then measure off the root span
        jax.block_until_ready(rid_streamed(key, src, k).P)
        with tracing() as tr:
            jax.block_until_ready(rid_streamed(key, src, k).P)
        wall_pipe = _root_dur(tr)
        jax.block_until_ready(rid_streamed(key, src, k, overlap=False).P)
        with tracing() as tr_ser:
            jax.block_until_ready(
                rid_streamed(key, src, k, overlap=False).P)
        wall_serial = _root_dur(tr_ser)
        rows.append({
            "bench": "stream_scaling", "m": m, "n": n, "k": k,
            "chunk_rows": chunk_rows,
            "input_bytes": m * n * A.itemsize,
            "peak_device_bytes": src.peak_bytes,
            "acc_bytes": l * n * 4,          # f32 accumulator
            "wall_pipelined_s": wall_pipe,
            "wall_serialized_s": wall_serial,
            "overlap_efficiency": wall_serial / wall_pipe,
        })
        if m == ms[-1]:
            # Per-phase device timing needs the deep (serializing) mode;
            # run it once, on the largest input only.
            with tracing(deep=True) as tr_deep:
                jax.block_until_ready(rid_streamed(key, src, k).P)
            phase_rows = _phase_rows(tr_deep, m=m, n=n, k=k, l=l,
                                     chunk_rows=chunk_rows)
    emit(rows, header="streaming RID: peak device residency (flat in m) "
                      "vs input size; two-stream overlap")
    emit(phase_rows, header="streamed-RID phases: obs-measured wall vs "
                            "v5e roofline model (deep tracing, largest m)")
    if json_path:
        append_json_rows(json_path, rows + phase_rows)
    # Acceptance shape: the largest input exceeds the streaming working
    # set it was decomposed with, and the working set is flat in m.
    last = rows[-1]
    assert last["input_bytes"] > last["peak_device_bytes"], \
        (last["input_bytes"], last["peak_device_bytes"])
    peaks = [r["peak_device_bytes"] for r in rows]
    assert max(peaks) < 2 * min(peaks), f"peak residency grows with m: {peaks}"
    return rows + phase_rows


def stream_sharded_sweep(*, full=False, json_path=None):
    """Weak scaling of the sharded, FILE-BACKED pipeline (ISSUE 9): the
    on-disk matrix grows with the device count (``n = n0 * ndev`` —
    each device keeps the same column shard) while ``m`` streams from
    disk, so ideal weak scaling is flat wall time AND flat per-device
    residency.  Emits ``bench = "stream_sharded"`` rows:

      ndev, m, n, k, chunk_rows, on_disk_bytes, wall_s,
      peak_device_bytes (all devices), peak_per_device_bytes,
      acc_shard_bytes (the l x n/ndev accumulator shard — constant
      across the sweep by construction)

    into the ``BENCH_scaling.json`` record.  CI runs this step under
    ``--xla_force_host_platform_device_count=8``.
    """
    devices = jax.devices()
    n0, k, chunk_rows = 256, 48, 512
    m = 16384 if full else 8192
    l = 2 * k
    ndevs = [d for d in (1, 2, 4, 8) if d <= len(devices)]
    rows = []
    with tempfile.TemporaryDirectory() as tmp:
        for ndev in ndevs:
            n = n0 * ndev
            mesh = make_mesh((ndev,), ("data",), devices=devices[:ndev],
                             axis_types=(AxisType.Auto,))
            path = os.path.join(tmp, f"a_{ndev}.npy")
            np.save(path, np.asarray(
                np.random.default_rng(3).standard_normal((m, n)),
                np.float32))
            key = jax.random.key(1)
            with FileSource(path, chunk_rows) as fsrc:
                src = MeteredSource(fsrc)
                # warm the per-(mesh, shape) jit caches off the clock
                jax.block_until_ready(
                    rid_streamed(key, src, k, mesh=mesh).P)
                with tracing() as tr:
                    jax.block_until_ready(
                        rid_streamed(key, src, k, mesh=mesh).P)
            rows.append({
                "bench": "stream_sharded", "ndev": ndev, "m": m, "n": n,
                "k": k, "chunk_rows": chunk_rows,
                "on_disk_bytes": os.path.getsize(path),
                "wall_s": _root_dur(tr),
                "peak_device_bytes": src.peak_bytes,
                "peak_per_device_bytes": src.peak_bytes // ndev,
                "acc_shard_bytes": l * (n // ndev) * 4,
            })
    emit(rows, header="sharded file-backed streaming RID: weak scaling "
                      "(devices x on-disk bytes; flat per-device residency)")
    if json_path:
        append_json_rows(json_path, rows)
    # Acceptance shape: every input exceeds the device working set it was
    # decomposed with (the file never fit), and the PER-DEVICE residency
    # stays flat as devices x columns grow together.
    for r in rows:
        assert r["on_disk_bytes"] > r["peak_device_bytes"], r
    per_dev = [r["peak_per_device_bytes"] for r in rows]
    assert max(per_dev) < 2 * min(per_dev), \
        f"per-device residency grows with the mesh: {per_dev}"
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--sharded", action="store_true",
                    help="run the sharded file-backed weak-scaling sweep "
                         "(n grows with the local device count) instead "
                         "of the single-device m-sweep")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="append stream_scaling rows to this JSON record "
                         "(the BENCH_scaling.json contract)")
    args = ap.parse_args(argv)
    if args.sharded:
        stream_sharded_sweep(full=args.full, json_path=args.json)
    else:
        stream_sweep(full=args.full, json_path=args.json)


if __name__ == "__main__":
    main()
