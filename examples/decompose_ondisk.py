"""Sharded, file-backed, fault-tolerant RID — the 64 GB path end to end.

The matrix lives in a multi-GB ``.npy`` ON DISK and is never resident
anywhere: ``FileSource`` memory-maps it and read-ahead feeds 4096-row
chunks, ``rid_streamed(mesh=...)`` streams ``m`` from disk while
column-sharding ``n`` over the device mesh (each device keeps only its
``l x n/ndev`` accumulator shard — no replicated sketch), and a
checkpoint directory makes the whole run killable.

The script demonstrates, in order:

  1. flat residency — the SAME pipeline over a 1/8-size file and the
     full (>= 1 GB) file, with ``MeteredSource`` sampling live device
     bytes at every chunk: peak residency is flat in ``m`` while the
     input grows 8x.  The big run is WATCHED live (ISSUE 10): a
     ``ProgressReporter`` publishes atomic per-chunk status JSON, a
     ``TelemetryServer`` serves ``/metrics`` while the run is in flight
     (scraped mid-run below), and the JSONL trace is analyzed post-hoc
     by ``obs/timeline.py`` (critical path, throughput, psum overlap);
  2. kill + resume — a seeded ``FlakySource`` kills the small run
     mid-pass-1; resuming against the same file (same ``(path, size,
     mtime_ns)`` fingerprint) replays the remaining chunks onto the
     checkpointed accumulator and the result is BIT-identical to the
     uninterrupted run;
  3. fingerprint rejection — after touching the file, the same
     checkpoint directory refuses to resume ("written by a different
     job"): a mutated on-disk matrix can never silently mix into an old
     decomposition.

Size defaults to ~1 GB on disk; override with ``ONDISK_GB=4`` etc.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/decompose_ondisk.py
"""
import os
import tempfile

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax

jax.config.update("jax_enable_x64", False)      # f32 matrix: GB go further

import numpy as np
from jax import make_mesh
from jax.sharding import AxisType
from repro.core import rid_streamed
from repro.obs import MeteredSource
from repro.runtime import FaultPlan, FlakySource, ProcessKilled
from repro.stream import FileSource

GB = float(os.environ.get("ONDISK_GB", "1.0"))
N, K, CHUNK = 2048, 48, 4096
M = max(1, round(GB * 1e9 / (N * 4 * CHUNK))) * CHUNK   # chunk-aligned
ndev = len(jax.devices())
mesh = make_mesh((ndev,), ("data",), axis_types=(AxisType.Auto,))
workdir = tempfile.mkdtemp(prefix="repro_ondisk_")


def write_lowrank_npy(path, m):
    """Stream an approximately rank-K matrix to disk chunk by chunk —
    the writer never holds more than one chunk either."""
    rng = np.random.default_rng(11)
    W = rng.standard_normal((K, N)).astype(np.float32)
    out = np.lib.format.open_memmap(path, mode="w+", dtype=np.float32,
                                    shape=(m, N))
    for r0 in range(0, m, CHUNK):
        r1 = min(r0 + CHUNK, m)
        g = rng.standard_normal((r1 - r0, K)).astype(np.float32)
        noise = rng.standard_normal((r1 - r0, N)).astype(np.float32)
        out[r0:r1] = g @ W + 1e-4 * noise
    out.flush()
    del out
    return path


def run(path, *, resume_dir=None, wrap=None, progress=None):
    with FileSource(path, CHUNK) as fsrc:
        src = MeteredSource(wrap(fsrc) if wrap else fsrc)
        dec = rid_streamed(jax.random.key(8), src, K, mesh=mesh,
                           resume_dir=resume_dir, progress=progress)
        return dec, src.peak_bytes


print(f"mesh: {ndev} devices; target {GB:.1f} GB on disk "
      f"-> A is {M}x{N} f32 in {workdir}")

small = write_lowrank_npy(os.path.join(workdir, "small.npy"), M // 8)
big = write_lowrank_npy(os.path.join(workdir, "big.npy"), M)
small_gb = os.path.getsize(small) / 1e9
big_gb = os.path.getsize(big) / 1e9

# ---- 1. flat residency: 8x the file, same device working set -----------
# The big run is the WATCHED one (ISSUE 10): a ProgressReporter publishes
# atomic status JSON per chunk, a TelemetryServer serves /metrics +
# /progress while the decomposition is in flight (scraped from a
# progress callback mid-run), and the JSONL trace is analyzed post-hoc.
import json as _json
import urllib.request

from repro.obs import ProgressReporter, TelemetryServer, Timeline, tracing

dec_small, peak_small = run(small)

status_path = os.path.join(workdir, "progress.json")
trace_path = os.path.join(workdir, "trace.jsonl")
scrapes, dones = [], []


def watch(status):
    dones.append(status["done"])
    if len(dones) == 3:            # a few chunks in: the run is live
        with urllib.request.urlopen(server.url + "/metrics") as r:
            scrapes.append((r.status, r.read().decode()))
        with open(status_path) as f:      # atomic publish: never torn
            assert _json.load(f)["state"] == "running"


reporter = ProgressReporter(status_path, callbacks=[watch])
with tracing(jsonl=trace_path) as tr, \
        TelemetryServer(registry=tr.metrics, progress=reporter) as server:
    print(f"\nwatch the big run: curl {server.url}/metrics  "
          f"(or /progress, or cat {status_path})")
    dec_big, peak_big = run(big, progress=reporter)

code, body = scrapes[0]
assert code == 200 and "repro_stream_chunks_total" in body, body[:200]
final = _json.load(open(status_path))
assert final["state"] == "done" and final["done"] == final["total"]
assert len(set(dones)) > M // 8 // CHUNK    # status advanced per chunk
print(f"live /metrics scrape mid-run: HTTP {code}, "
      f"{len(body.splitlines())} metric lines; final status: "
      f"{final['done']}/{final['total']} {final['state']}, "
      f"{final['checkpoints']} checkpoints")

# Post-hoc trace analytics: where the wall-clock went, measured rates.
tl = Timeline.from_jsonl(trace_path)
thr = tl.throughput()
top = [f"{name} {sec:.2f}s" for name, sec in tl.critical_path()[:3]]
print(f"timeline: wall {tl.wall():.2f}s; critical path: {', '.join(top)}; "
      f"throughput {thr['rows_per_s']:.0f} rows/s, "
      f"{thr['bytes_per_s'] / 1e6:.0f} MB/s h2d, "
      f"psum overlap: {tl.psum_overlap()}")

print(f"\nresidency: {small_gb:.2f} GB file -> peak {peak_small / 1e6:.1f} "
      f"MB on device; {big_gb:.2f} GB file -> peak {peak_big / 1e6:.1f} MB")
assert peak_big < 1.5 * peak_small, (peak_big, peak_small)
print(f"flat in m: 8x the input, {peak_big / peak_small:.2f}x the peak "
      f"(accumulator shard per device: {2 * K * N // ndev * 4 / 1e6:.2f} MB)")

# ---- 2. kill mid-run, resume under the matching fingerprint ------------
ckpt = os.path.join(workdir, "ckpt")
try:
    run(small, resume_dir=ckpt,
        wrap=lambda s: FlakySource(s, FaultPlan(kill_at=(4,))))
except ProcessKilled as e:
    print(f"\ninjected mid-pass-1 kill: {e}")
dec_resumed, _ = run(small, resume_dir=ckpt)
same = all(np.array_equal(np.asarray(getattr(dec_resumed, f)),
                          np.asarray(getattr(dec_small, f)))
           for f in ("B", "P", "J", "Q", "R"))
print(f"resumed from {ckpt}: bit-identical to the uninterrupted run "
      f"-> {same}")
assert same

# ---- 3. a mutated file is a different job ------------------------------
os.utime(small, ns=(1, 1))
try:
    run(small, resume_dir=ckpt)
    raise SystemExit("resume against a touched file must be rejected")
except ValueError as e:
    print(f"\nfile touched -> resume rejected: {str(e).splitlines()[0][:76]}")

# ---- the decomposition itself: residual, streamed from disk ------------
# Power iteration on E = A - B P, one mmap pass per iteration; the exact
# sigma_{K+1} of the generated matrix is ~1e-4 * sqrt(M) by construction.
Bh, Ph = np.asarray(dec_big.B), np.asarray(dec_big.P)
mm = np.load(big, mmap_mode="r")
rng = np.random.default_rng(0)
v = rng.standard_normal(N).astype(np.float32)
v /= np.linalg.norm(v)
for _ in range(4):
    u = np.empty(M, np.float32)
    w = np.zeros(N, np.float32)
    pv = Ph @ v
    for r0 in range(0, M, CHUNK):
        r1 = min(r0 + CHUNK, M)
        ch = np.array(mm[r0:r1])
        u[r0:r1] = ch @ v - Bh[r0:r1] @ pv
        w += ch.T @ u[r0:r1]
    w -= Ph.T @ (Bh.T @ u)
    v = w / max(np.linalg.norm(w), 1e-30)
from repro.core import error_bound

err = float(np.linalg.norm(u))
# sigma_{K+1}(A) is the noise spectrum's edge: 1e-4 (sqrt(M) + sqrt(N))
bound = error_bound(M, N, K) * 1e-4 * (np.sqrt(M) + np.sqrt(N))
print(f"\n||A - BP||_2 ~= {err:.3e} on the {big_gb:.2f} GB matrix   "
      f"eq.(3) bound = {bound:.3e}   ok = {err <= bound}")
assert err <= bound
print(f"done; artifacts in {workdir}")
