"""Distributed + STREAMED RID — the paper's parallel experiment on a JAX
mesh, then a decomposition whose input never fits on a device at all.

Part 1 column-shards A over a data-parallel mesh (the XMT's "each
processor owns columns"), sketches with ZERO communication, factors the
sketch with the panel-parallel QRCP (qr_impl="panel_parallel": each
device keeps only its l x n/ndev shard — no replicated l x n sketch),
solves R1 T = R2 column-parallel, and validates the error against the
paper's Table 5 regime.

Part 2 grows m 16x past part 1 — a ~0.4 GB f64 matrix that is NEVER
materialized: a seeded known-spectrum generator (repro.stream.
SpectrumSource) feeds 2048-row chunks to rid_streamed, whose peak
device residency is O(l n + chunk) regardless of m — the paper's
64 GB-scale path on laptop hardware.  The streamed decomposition runs
under ``repro.obs.tracing``, exporting a Chrome trace-event file
(``TRACE_OUT``, default /tmp/decompose_large_trace.json) with the
per-chunk H2D / accumulate / gather spans and the job's eq.(3)
certificate event — open it at https://ui.perfetto.dev.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/decompose_large.py
"""
import os

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
from jax import make_mesh
from jax.sharding import AxisType

from repro.core import rid_distributed, shard_columns, spectral_norm_dense
from repro.core.errors import error_bound, expected_sigma_kp1

ndev = len(jax.devices())
mesh = make_mesh((ndev,), ("data",), axis_types=(AxisType.Auto,))
print(f"mesh: {ndev} devices, axis 'data' (column-parallel)")

key = jax.random.key(1)
m, n, k = 4096, 2048, 100          # paper row k=100 at 1/8 linear scale
kb, kp = jax.random.split(key)
A = jax.random.normal(kb, (m, k)) @ jax.random.normal(kp, (k, n))
A = shard_columns(A, mesh, "data")
print(f"A: {m}x{n} f64 rank {k}, column-sharded "
      f"{n // ndev} cols/device")

# panel=16 keeps the panel-greedy pivot quality within the eq.(3) bound at
# k=100 (panel-at-a-time pivoting trades a little pivot quality per panel
# width; see tests/test_qr_blocked.py)
dec = rid_distributed(jax.random.key(2), A, k, mesh=mesh, axis="data",
                      sketch_kind="gaussian", qr_impl="panel_parallel",
                      qr_panel=16)
err = float(spectral_norm_dense(jnp.asarray(A) - dec.B @ dec.P))
bound = error_bound(m, n, k) * expected_sigma_kp1(m, n)
print(f"||A - BP||_2 = {err:.2e}   eq.(3) bound = {bound:.2e}   "
      f"ok = {err <= bound}")
print(f"P stays column-sharded: {dec.P.sharding}")
print(f"R stays column-sharded too (panel-parallel QR): {dec.R.sharding}")

# ---- part 2: streamed, beyond a single buffer ---------------------------
from repro.core import error_bound as eq3_bound, rid_streamed
from repro.obs import tracing
from repro.stream import SpectrumSource

ms, ns, ks, chunk = 65536, 768, 48, 2048
src = SpectrumSource(jax.random.key(7), ms, ns, "fast_decay", ks,
                     chunk_rows=chunk, dtype=jnp.float64, floor=1e-10)
gb = ms * ns * 8 / 1e9
print(f"\nstreamed: {ms}x{ns} f64 (~{gb:.2f} GB input, generated "
      f"{chunk}-row chunks; resident sketch only {2 * ks}x{ns})")
trace_out = os.environ.get("TRACE_OUT", "/tmp/decompose_large_trace.json")
with tracing(chrome=trace_out) as tr:
    sdec = rid_streamed(jax.random.key(8), src, ks)
n_chunk_spans = sum(s.name == "stream.h2d" for s in tr.spans)
print(f"trace: {len(tr.spans)} spans ({n_chunk_spans} H2D chunks) -> "
      f"{trace_out} (open in ui.perfetto.dev)")

# Validation-only error estimate, HOST-side and chunk-streamed like the
# decomposition itself: power iteration on E^T E with E = A - B P, where
# every E v / E^T u product re-reads the source one chunk at a time —
# the device never holds A here either.
import numpy as np
from repro.stream import chunk_bounds, num_chunks

Bh, Ph = np.asarray(sdec.B), np.asarray(sdec.P)
rng = np.random.default_rng(0)
v = rng.standard_normal(ns)
v /= np.linalg.norm(v)
for _ in range(20):
    u = np.empty(ms)
    w = np.zeros(ns)
    pv = Ph @ v
    for c in range(num_chunks(src)):
        r0, r1 = chunk_bounds(src, c)
        ch = np.asarray(src.chunk(c))
        u[r0:r1] = ch @ v - Bh[r0:r1] @ pv                 # (E v) rows
        w += ch.T @ u[r0:r1]                               # accumulate A^T u
    w -= Ph.T @ (Bh.T @ u)                                 # E^T u
    v = w / max(np.linalg.norm(w), 1e-300)
err_s = float(np.linalg.norm(u))
bound_s = eq3_bound(ms, ns, ks) * float(src.sigmas[ks])
print(f"||A - BP||_2 ~= {err_s:.2e}   eq.(3) bound = {bound_s:.2e}   "
      f"ok = {err_s <= bound_s}")
