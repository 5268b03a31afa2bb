"""Out-of-core streaming RID: decompose matrices that never fit on device.

Every other entry point (``rid``, ``rid_distributed``) needs the full
``m x n`` matrix resident; this one needs a :class:`~repro.stream.chunks.
ChunkSource` and keeps peak device residency at ``O(l n + chunk_rows n)``
— independent of ``m``.  The sketch ``Y = Phi A`` is a one-pass row
reduction (Halko-Martinsson-Tropp), so the pipeline feeds row chunks
through the accumulating kernel while the NEXT chunk's host->device
transfer is in flight, then hands the finished ``l x n`` sketch to the
exact same QRCP + interpolation machinery the in-memory path uses.

Memory / IO cost by phase (the ``distributed.py`` accounting, rebuilt
for the host->device axis; ``C = ceil(m / chunk_rows)`` chunks):

  phase             device bytes resident           H2D traffic
  sketch (pass 1)   l n (accumulator)               m n   (each chunk
                    + 2 chunk_rows n (double buf)          sent once)
                    + l chunk_rows (operator slab)
  pivoted QR        l n + engine panel state        0
  interp solve      k n                             0
  gather (pass 2)   one chunk                       m n -> m k result
                                                    assembled on HOST

The two-stream pass-1 schedule: the accumulate GEMM of chunk ``c`` is
dispatched asynchronously, then the transfer of chunk ``c + 1`` is
enqueued — on hardware with a DMA engine the copy overlaps the GEMM
(``overlap=False`` serializes the two for benchmarking the gain;
``benchmarks/bench_stream.py`` records the measured overlap efficiency).

SHARDED N AXIS — ``mesh=`` composes the stream with the device mesh:
``m`` streams from host (chunks) while ``n`` shards over
``ndev = mesh.shape[axis]`` devices (columns), the two scaling axes of
the 64 GB path.  The table above, rebuilt PER DEVICE for the two-axis
(stream-m x shard-n) pipeline:

  phase             device bytes resident / DEVICE   communication
  sketch (pass 1)   l n/ndev (accumulator shard)     0 between devices —
                    + 2 chunk_rows n/ndev (buffers)  the operator acts on
                    + l chunk_rows (operator slab)   the ROW index only
  pivoted QR        l n/ndev + panel state           O(k/b (n + l b))
                    (``core.qr_dist`` in-place       psum bytes, the O(n)
                    panel-parallel engine)           term latency-hidden
  interp solve      k n/ndev                         one l x k + k x k
                                                     psum (pivot columns)
  gather (pass 2)   one chunk (host numpy ``B``)     0

No stage materializes an ``l x n`` array per device — the accumulator,
sketch, and ``R`` live column-sharded end to end, so sketch width
scales with the mesh while peak residency stays flat in ``m`` (the
``rid_streamed.sharded_step`` analysis entry pins a collective budget
of ``l*n - 1`` elements in CI).  Because ``kernels/sketch_accum``
computes every output column independently (fixed ACCUM_BLOCK row
association), the shard-local accumulator is BIT-equal to the same
columns of the single-device accumulator; pivots and all IDResult
fields then agree with the single-device ``panel_parallel`` engine's.

REPLAY GUARANTEE — ``rid_streamed`` is bit-for-bit identical to the
in-memory ``rid`` for the same PRNG key.  Three pieces make that true:

  1. the gaussian operator is seeded per canonical ``ACCUM_BLOCK``-row
     block (``core.sketch.gaussian_omega_cols``), so chunked generation
     reproduces exactly the in-memory operator values;
  2. the row reduction runs through ``kernels/sketch_accum``, whose
     fixed-block association makes the accumulated bits independent of
     how the rows were partitioned — PROVIDED ``chunk_rows`` is a
     multiple of ``ACCUM_BLOCK`` (validated below);
  3. the QR + interpolation stages run through the same jit boundary as
     ``rid_from_sketch`` (``core.rid._qr_interp``), and the pivot-column
     gather copies values untouched.

Only the ``gaussian`` sketch streams: srft/srht mix ALL ``m`` rows
through an FFT/FWHT, so a row chunk cannot be sketched independently.

OBSERVABILITY (``repro.obs``): under an ambient tracer the pipeline
records one ``rid_streamed`` root span with per-chunk children —
``stream.h2d`` / ``stream.accumulate`` for pass 1 and ``stream.gather``
for pass 2 — plus ``stream.h2d_bytes`` / ``stream.chunks`` counters, a
``device.live_bytes`` gauge sampled at every chunk boundary, and a
final ``eq3.certificate`` event carrying the paper's eq.(3) bound for
this (m, n, k), so one trace is simultaneously a perf profile and a
correctness record.  Every span additionally carries a ``job=`` attr
(the first 12 hex chars of the resume fingerprint) via the tracer's
attribute-binding stack, so ``obs/timeline.py`` can join spans to jobs
even when several jobs share a trace; a ``progress=`` reporter
(``obs/progress.py``) turns the same per-chunk cadence into live
done/total/ETA status while the run is in flight.  All spans open/close in THIS host loop, outside
the jit boundaries (the registered analysis entry's jaxpr is
instrumentation-free — ``jaxpr.host-transfer`` re-proves it in CI).
Under normal tracing the per-chunk spans time DISPATCH (no added syncs:
the double-buffered schedule is preserved, ``sync=False`` on the span);
deep tracing (``tracing(deep=True)``) blocks on each phase for true
per-chunk device timing at the cost of serializing the pipeline.

FAULT TOLERANCE — at 64 GB scale a streamed decomposition is thousands
of chunk reads; the failure modes and their recovery paths (all
exercised by the seeded harness in ``runtime/faults.py``):

  failure mode            raised as            recovery
  transient read error    TransientReadError   ``retry=RetryPolicy(...)``
                                               retries with exponential
                                               backoff + seeded jitter
                                               (``stream.retry`` counter)
  stalled read            ReadTimeout          the policy's elapsed-clock
                          (via ``timeout_s``)  timeout discards the slow
                                               read and retries
  retry budget exhausted  ChunkReadFailed      terminal for THIS run;
                                               ``stream.chunk_failures``
                                               counter; resume later from
                                               ``resume_dir``
  source permanently dead SourceDied           terminal; resume from
                                               ``resume_dir`` against a
                                               replacement source with
                                               the same fingerprint
  process kill            (nothing to catch)   ``resume_dir`` checkpoints
                                               survive: atomic-rename +
                                               fsync + per-leaf crc32
                                               (``checkpoint/store.py``)

CHECKPOINT / RESUME CONTRACT: with ``resume_dir`` set, the pipeline
persists ``(fingerprint, phase, chunks_done, acc)`` every
``checkpoint_every`` chunks of pass 1, and ``(fingerprint, phase,
chunks_done, P, J, Q, R, B)`` after QR + every ``checkpoint_every``
chunks of the pass-2 gather.  Because PR 5 pinned the reduction order
to fixed ``ACCUM_BLOCK`` blocks with per-block seeded omega, replaying
from a checkpoint re-accumulates the SAME blocks in the SAME order onto
the SAME saved accumulator bits — a resumed run is therefore
BIT-FOR-BIT identical to an uninterrupted one (and to the in-memory
``rid``), not merely close.  The fingerprint covers (m, n, k, l,
chunk_rows, dtype, key, qr arguments, and the source's own optional
``fingerprint()``); a checkpoint written for any other job is rejected
eagerly with both fingerprints named.
"""
from __future__ import annotations

import hashlib
import json
import os
from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..checkpoint.store import CheckpointManager, latest_step, restore_pytree
from ..core.qr import resolve_norm_recompute, resolve_panel
from ..core.qr_dist import panel_parallel_rid_interp_local
from ..core.rid import _cast_interp, _qr_interp
from ..core.sketch import finalize_gaussian_sketch, gaussian_omega_cols
from ..core.types import IDResult
from ..core.validate import (check_divides, check_l_ge_k, check_panel,
                             check_rank_bounds)
from ..kernels.sketch_accum import ACCUM_BLOCK, accum_dtype_for, sketch_accum
from ..obs import trace as obs_trace
from ..obs.metrics import live_device_bytes
from .chunks import ChunkSource, chunk_bounds, num_chunks

__all__ = ["rid_streamed", "source_fingerprint"]


# --------------------------------------------------- sharded n-axis plumbing
# Cached per (mesh, axis[, qr args]) so every chunk of a streamed job (and
# every job on the same mesh) reuses ONE traced/compiled program instead of
# re-tracing a fresh shard_map per call.

@lru_cache(maxsize=None)
def _sharded_accum_fn(mesh: Mesh, axis: str):
    """jit(shard_map) of one accumulate step over column shards:
    ``acc_loc += omega_c @ a_loc``.  The sketch operator acts on the ROW
    index only, so each device reduces its own columns with ZERO
    communication, and because ``kernels/sketch_accum`` computes every
    output column independently (fixed ACCUM_BLOCK row association,
    zero-padding only), the shard-local accumulator is BIT-equal to the
    same columns of the single-device accumulator."""
    spec = PartitionSpec(None, axis)

    def step(x, a_loc, acc_loc):
        return sketch_accum(x, a_loc, acc_loc)

    return jax.jit(jax.shard_map(step, mesh=mesh,
                                 in_specs=(PartitionSpec(), spec, spec),
                                 out_specs=spec, check_vma=False))


@lru_cache(maxsize=None)
def _sharded_qr_interp_fn(mesh: Mesh, axis: str, k: int, qr_panel: int,
                          qr_norm_recompute):
    """jit(shard_map) of the sharded steps 2-3: the panel-parallel QRCP +
    column-parallel interpolation body (``core.qr_dist.
    panel_parallel_rid_interp_local``) over the column-sharded sketch —
    no ``l x n`` array ever materializes per device (the
    ``jaxpr.replicated-collective`` contract registered below)."""
    ndev = mesh.shape[axis]
    fn = partial(panel_parallel_rid_interp_local, k=k, axis=axis, ndev=ndev,
                 panel=qr_panel, norm_recompute=qr_norm_recompute)
    spec = PartitionSpec(None, axis)
    mapped = jax.shard_map(fn, mesh=mesh, in_specs=(spec,),
                           out_specs=(spec, PartitionSpec(), PartitionSpec(),
                                      spec),
                           check_vma=False)
    return jax.jit(mapped)


def _checked_chunk(source: ChunkSource, c: int):
    """Fetch chunk ``c`` and validate its shape/dtype eagerly — a source
    that lies about its geometry fails HERE with the chunk named, not
    deep inside a jitted GEMM."""
    r0, r1 = chunk_bounds(source, c)
    ch = source.chunk(c)
    n = source.shape[1]
    if tuple(ch.shape) != (r1 - r0, n):
        raise ValueError(f"source.chunk({c}) returned shape "
                         f"{tuple(ch.shape)}, expected ({r1 - r0}, {n}) "
                         f"for rows [{r0}, {r1}) of {source.shape}")
    if jnp.dtype(ch.dtype) != jnp.dtype(source.dtype):
        raise ValueError(f"source.chunk({c}) dtype {jnp.dtype(ch.dtype)} "
                         f"disagrees with source.dtype "
                         f"{jnp.dtype(source.dtype)}")
    return ch


def source_fingerprint(key: jax.Array, source: ChunkSource, k: int, l: int,
                       qr_impl: str, qr_panel: int,
                       qr_norm_recompute) -> np.ndarray:
    """The resume identity: a sha256 digest (as a (32,) uint8 array, the
    checkpointable form) over everything that determines the output bits
    — geometry, dtype, chunking, the PRNG key, the QR arguments, and the
    source's own optional ``fingerprint()`` (e.g. a file path + mtime).
    A checkpoint whose digest disagrees belongs to a DIFFERENT job and
    resuming from it would silently mix two decompositions."""
    m, n = source.shape
    extra = getattr(source, "fingerprint", None)
    extra = extra() if callable(extra) else extra
    text = (f"m={m} n={n} k={k} l={l} chunk_rows={source.chunk_rows} "
            f"dtype={jnp.dtype(source.dtype)} "
            f"key={np.asarray(jax.random.key_data(key)).tobytes().hex()} "
            f"qr={qr_impl}/{qr_panel}/{qr_norm_recompute} src={extra!r}")
    digest = hashlib.sha256(text.encode()).digest()
    return np.frombuffer(digest, np.uint8).copy()


def _resume_like(resume_dir: str, step: int) -> Optional[dict]:
    """Build the ``restore_pytree`` ``like`` tree straight from the
    manifest of ``step`` (shapes/dtypes are self-describing; the
    fingerprint check below is what authenticates them)."""
    path = os.path.join(resume_dir, f"step_{step:06d}", "manifest.json")
    with open(path) as f:
        leaves = json.load(f)["leaves"]

    def sds(name):
        ent = leaves[f"['{name}']"]
        return jax.ShapeDtypeStruct(tuple(ent["shape"]),
                                    np.dtype(ent["dtype"]))

    names = ["fp", "phase", "chunks_done"]
    names += ["P", "J", "Q", "R", "B"] if "['B']" in leaves else ["acc"]
    return {name: sds(name) for name in names}


def _load_resume_state(resume_dir: str, fp: np.ndarray) -> Optional[dict]:
    """Latest checkpoint in ``resume_dir`` as host numpy state, or None
    for a fresh directory.  Rejects a fingerprint mismatch eagerly."""
    step = latest_step(resume_dir)
    if step is None:
        return None
    state = restore_pytree(resume_dir, step,
                           _resume_like(resume_dir, step), host=True)
    if not np.array_equal(state["fp"], fp):
        raise ValueError(
            f"checkpoint at {resume_dir} (step {step}) was written by a "
            f"different job: its fingerprint "
            f"{bytes(state['fp']).hex()[:16]}... != this job's "
            f"{bytes(fp).hex()[:16]}... — same source/key/k/l/chunking/qr "
            f"arguments are required for a bit-identical resume")
    return state


def rid_streamed(key: jax.Array, source: ChunkSource, k: int, *,
                 l: Optional[int] = None, sketch_kind: str = "gaussian",
                 qr_impl: str = "auto", qr_panel: int = 32,
                 qr_norm_recompute="auto", mesh: Optional[Mesh] = None,
                 axis: str = "data", overlap: bool = True,
                 retry=None, resume_dir: Optional[str] = None,
                 checkpoint_every: int = 1, progress=None) -> IDResult:
    """Rank-``k`` randomized ID of a chunk-fed matrix: ``A ~= B @ P``.

    Bit-for-bit identical to ``rid(key, A, k, sketch_kind="gaussian",
    ...)`` on the materialized matrix, for every ``chunk_rows`` that is a
    multiple of ``ACCUM_BLOCK`` (module docstring) — same pivots, same
    ``P``, same everything.  The guarantee survives interruption: a run
    resumed from ``resume_dir`` replays the remaining chunks onto the
    checkpointed accumulator and is bit-identical to an uninterrupted
    run (module docstring, CHECKPOINT / RESUME CONTRACT).

    Args:
      key: PRNG key driving the sketch operator (same semantics as
        ``rid``).
      source: a :class:`ChunkSource` feeding row chunks of ``A``; read
        twice (sketch pass + pivot-column gather pass).
      k: target rank (static).
      l: sketch rows; defaults to the paper's universal ``l = 2k``.
      sketch_kind: must be ``'gaussian'`` — the one backend whose
        operator applies row-by-row (srft/srht need all of ``m``).
      qr_impl / qr_panel / qr_norm_recompute: forwarded unchanged to the
        QRCP engine (see ``rid``).  ``qr_impl='auto'`` (default) resolves
        to ``'blocked'`` without a mesh and ``'panel_parallel'`` with one
        — the resolution happens BEFORE the resume fingerprint is
        computed, so existing single-device checkpoints stay valid.
      mesh / axis: optional device mesh.  With ``mesh`` set, the n axis
        is column-sharded over ``mesh.shape[axis]`` devices for the whole
        device-side pipeline (module docstring, SHARDED N AXIS): the
        accumulator lives as ``l x n/ndev`` shards, the QRCP +
        interpolation run through the in-place panel-parallel body
        (``core.qr_dist.panel_parallel_rid_interp_local``), and no
        ``l x n`` array is ever replicated on one device — m streams
        from host while n scales with the mesh.  ``n`` must divide
        ``ndev``; ``qr_impl`` must be ``'auto'``/``'panel_parallel'``.
      overlap: pipeline the next chunk's host->device transfer against
        the current chunk's accumulate GEMM (default); ``False``
        serializes them (benchmark baseline).
      retry: optional :class:`~repro.runtime.faults.RetryPolicy` — every
        chunk read goes through it (transient errors / timeouts retry
        with backoff through the policy's injected clock; exhaustion
        raises ``ChunkReadFailed``).  ``None`` = fail on first error.
      resume_dir: optional checkpoint directory.  A fresh directory
        enables checkpointing; a directory holding a matching-fingerprint
        checkpoint makes this call RESUME from it (both passes — pass 2
        resumes the host-side ``B`` gather).  A checkpoint from a
        different job (source/key/k/l/chunking/qr args) is rejected.
      checkpoint_every: checkpoint cadence in chunks (default 1 =
        chunk-granular; each pass-1 save materializes the accumulator,
        so raise it to trade re-read work on resume for pipeline slack).
      progress: optional :class:`~repro.obs.progress.ProgressReporter`.
        The job reports ``2 * C`` units of work (pass-1 chunks then
        pass-2 gather chunks), advancing one unit per chunk with phase
        transitions (``pass1`` / ``qr_interp`` / ``pass2``), checkpoint
        saves, read retries, and a terminal ``done``/``failed`` state —
        the reporter's status file / callbacks are the live view of a
        multi-hour run (obs/README.md, "watch a long job").

    Returns an ``IDResult`` whose ``B`` (m x k pivot columns) is
    assembled on the HOST (numpy) so device residency stays m-free;
    ``P``/``J``/``Q``/``R`` are small device arrays.
    """
    if not isinstance(source, ChunkSource):    # runtime_checkable: all four
        raise ValueError(f"source must implement the ChunkSource protocol "
                         f"(shape/dtype/chunk_rows/chunk), got "
                         f"{type(source).__name__}")
    m, n = source.shape
    chunk_rows = source.chunk_rows
    dtype = jnp.dtype(source.dtype)
    if sketch_kind != "gaussian":
        raise ValueError(f"sketch kind {sketch_kind!r} cannot stream row "
                         f"chunks (srft/srht mix ALL m rows through the "
                         f"FFT/FWHT); pick 'gaussian'")
    if chunk_rows < 1:
        raise ValueError(f"need chunk_rows >= 1, got chunk_rows={chunk_rows}")
    if chunk_rows < m and chunk_rows % ACCUM_BLOCK:
        raise ValueError(
            f"need chunk_rows a multiple of ACCUM_BLOCK={ACCUM_BLOCK} (the "
            f"canonical reduction block that keeps the streamed sketch "
            f"bit-for-bit identical to the in-memory one), got "
            f"chunk_rows={chunk_rows}")
    l = 2 * k if l is None else l
    check_l_ge_k(l, k)
    check_rank_bounds(k, l, n)
    if checkpoint_every < 1:
        raise ValueError(f"need checkpoint_every >= 1, got "
                         f"checkpoint_every={checkpoint_every}")
    # qr_impl resolves BEFORE the fingerprint below so a single-device
    # 'auto' job digests as 'blocked' (PR-8 checkpoints stay resumable)
    # and a sharded job digests as 'panel_parallel' (a different job —
    # its R comes back column-sharded, not replicated).
    if qr_impl == "auto":
        qr_impl = "blocked" if mesh is None else "panel_parallel"
    sharding = None
    if mesh is None:
        if qr_impl == "panel_parallel":
            raise ValueError(
                f"qr_impl={qr_impl!r} factors column SHARDS in place and "
                f"needs mesh=...; got mesh=None — pass a mesh or leave "
                f"qr_impl='auto'")
    else:
        if qr_impl != "panel_parallel":
            raise ValueError(
                f"sharded rid_streamed factors the column shards in place; "
                f"need qr_impl='panel_parallel' (or 'auto'), got "
                f"qr_impl={qr_impl!r}")
        if axis not in mesh.shape:
            raise ValueError(f"axis={axis!r} is not an axis of the mesh "
                             f"(axes: {tuple(mesh.shape)})")
        ndev = mesh.shape[axis]
        check_divides(n, ndev, axis, ctx="rid_streamed: ")
        qr_panel = resolve_panel(qr_panel, k, l)
        check_panel(qr_panel, name="qr_panel")
        resolve_norm_recompute(qr_norm_recompute)  # eager: reject pre-trace
        sharding = NamedSharding(mesh, PartitionSpec(None, axis))

    def put(x):
        # Chunk/accumulator placement: column-sharded over the mesh, or
        # the default device when unsharded.
        return jax.device_put(x) if sharding is None else \
            jax.device_put(x, sharding)

    def read_chunk(c):
        if retry is None:
            return _checked_chunk(source, c)
        return retry.call(lambda: _checked_chunk(source, c),
                          description=f"source.chunk({c})",
                          on_retry=None if progress is None
                          else progress.on_retry)

    C = num_chunks(source)
    # The job identity is computed unconditionally (one sha256 over the
    # argument text): it is the resume fingerprint AND the `job=` attr
    # every span carries, so the timeline analyzer can join spans to
    # jobs across traces.
    fp = source_fingerprint(key, source, k, l, qr_impl, qr_panel,
                            qr_norm_recompute)
    job = bytes(fp).hex()[:12]
    mgr = None
    phase, start1, start2 = 1, 0, 0
    acc = interp = B = None
    if resume_dir is not None:
        mgr = CheckpointManager(resume_dir)
        state = _load_resume_state(resume_dir, fp)
        if state is not None:
            phase = int(state["phase"])
            done = int(state["chunks_done"])
            if phase == 1:
                start1, acc = done, put(state["acc"])
            else:
                interp = tuple(jnp.asarray(state[name])
                               for name in ("P", "J", "Q", "R"))
                B, start2 = state["B"], done

    if mesh is None:
        accum_step = sketch_accum      # acc=None on the first chunk is fine
    else:
        accum_step = _sharded_accum_fn(mesh, axis)
        if phase == 1 and acc is None:
            # shard_map needs an explicit operand: sharded zeros in the
            # accumulator dtype (what sketch_accum would have created).
            acc = put(jnp.zeros((l, n), accum_dtype_for(dtype)))

    tracer = obs_trace.current_tracer()
    deep = obs_trace.deep_tracing()
    chunks_ctr = obs_trace.counter("stream.chunks")
    h2d_ctr = obs_trace.counter("stream.h2d_bytes")
    ckpt_ctr = obs_trace.counter("stream.checkpoints")
    live_gauge = obs_trace.gauge("device.live_bytes")

    def save(step, tree):
        # mgr.save snapshots to host synchronously (materializing the
        # accumulator) then writes on its background thread — the disk
        # IO rides the next chunks' GEMMs, not the pipeline.
        with obs_trace.span("stream.checkpoint", step=step):
            mgr.save(step, tree)
        ckpt_ctr.add(1)
        if progress is not None:
            progress.checkpoint_saved(step)

    if progress is not None:
        if not progress.job:
            progress.job = job
        progress.update(total=2 * C,
                        phase="pass1" if phase == 1 else "pass2",
                        done=start1 if phase == 1 else C + start2,
                        force=True)

    # Every span below (and in engines this call reaches) inherits the
    # job fingerprint; the timeline analyzer joins spans to jobs on it.
    with obs_trace.attributes(job=job), \
            obs_trace.span("rid_streamed", m=m, n=n, k=k, l=l,
                           chunk_rows=chunk_rows, overlap=overlap,
                           dtype=str(dtype),
                           ndev=1 if mesh is None else mesh.shape[axis]):
        if resume_dir is not None and (start1 or phase == 2):
            obs_trace.event("stream.resume", phase=phase,
                            chunks_done=start1 if phase == 1 else start2)
        try:
            # ---- pass 1: double-buffered sketch accumulation -----------
            if phase == 1:
                with obs_trace.span("stream.pass1", chunks=C,
                                    start=start1) as p1:
                    if start1 < C:
                        with obs_trace.span("stream.h2d", chunk=start1,
                                            sync=deep) as sp:
                            nxt = put(read_chunk(start1))
                            h2d_ctr.add(int(nxt.nbytes))
                            if deep:
                                sp.block_on(nxt)
                    for c in range(start1, C):
                        cur = nxt
                        if tracer is not None:
                            live_gauge.set(live_device_bytes())
                        r0, r1 = chunk_bounds(source, c)
                        with obs_trace.span("stream.accumulate", chunk=c,
                                            rows=r1 - r0,
                                            sync=deep or not overlap) as sp:
                            omega_c = gaussian_omega_cols(key, r0, r1, l,
                                                          dtype)
                            acc = accum_step(omega_c, cur, acc)  # async
                            if not overlap:
                                jax.block_until_ready(acc)
                            elif deep:           # deep tracing: true device
                                sp.block_on(acc)  # timing, serializes
                        if c + 1 < C:            # H2D of c+1 rides the GEMM
                            with obs_trace.span("stream.h2d", chunk=c + 1,
                                                sync=deep) as sp:
                                nxt = put(read_chunk(c + 1))
                                h2d_ctr.add(int(nxt.nbytes))
                                if deep:
                                    sp.block_on(nxt)
                        chunks_ctr.add(1)
                        if progress is not None:
                            progress.update(done=c + 1)
                        if mgr is not None and \
                                ((c + 1) % checkpoint_every == 0
                                 or c + 1 == C):
                            save(c + 1, {"fp": fp, "phase": np.int64(1),
                                         "chunks_done": np.int64(c + 1),
                                         "acc": acc})
                    Y = finalize_gaussian_sketch(acc, l, dtype)
                    p1.block_on(Y)
                    # Drop the pass-1 buffers before the QR: at row-8
                    # widths the accumulator alone is 2 GB on one chip.
                    acc = cur = nxt = None

            # ---- steps 2-3: identical jit boundary to the in-memory path
            if interp is None:
                if progress is not None:
                    progress.update(phase="qr_interp")
                with obs_trace.span("stream.qr_interp", qr_impl=qr_impl,
                                    qr_panel=qr_panel) as sp:
                    if mesh is None:
                        P, piv, Q, R = _qr_interp(Y, k, qr_impl, qr_panel,
                                                  qr_norm_recompute)
                    else:
                        P, piv, Q, R = _sharded_qr_interp_fn(
                            mesh, axis, k, qr_panel, qr_norm_recompute)(Y)
                    P = _cast_interp(P, dtype)
                    sp.block_on((P, piv, Q, R))
            else:
                P, piv, Q, R = interp

            # ---- pass 2: streamed pivot-column gather B = A[:, J] ------
            # Re-checked per chunk: a forward-only source that misbehaves
            # on the RE-read (chunks must be re-readable — two passes)
            # fails with the chunk named, not an opaque numpy broadcast
            # error.
            J = np.asarray(piv)
            if B is None:
                B = np.empty((m, k), dtype=dtype)

            def phase2_tree(done):
                # B is shared (not copied) with the async writer: the
                # gather only mutates rows ABOVE `done`, and only rows
                # up to `done` are meaningful in the snapshot.
                return {"fp": fp, "phase": np.int64(2),
                        "chunks_done": np.int64(done), "P": np.asarray(P),
                        "J": J, "Q": np.asarray(Q), "R": np.asarray(R),
                        "B": B}

            if mgr is not None and phase == 1:
                save(C + 1, phase2_tree(0))   # a pass-2 resume never
            if progress is not None:          # redoes pass 1 or the QR
                progress.update(phase="pass2")
            with obs_trace.span("stream.pass2", chunks=C, start=start2):
                for c in range(start2, C):
                    r0, r1 = chunk_bounds(source, c)
                    # Same device-bracketed discipline as pass 1: when a
                    # source hands back device arrays, deep tracing
                    # blocks on the chunk so the span holds true read
                    # time, not dispatch.
                    with obs_trace.span("stream.gather", chunk=c,
                                        rows=r1 - r0, sync=deep) as sp:
                        ch = read_chunk(c)
                        if deep:
                            sp.block_on(ch)
                        B[r0:r1] = np.asarray(ch)[:, J]
                    if progress is not None:
                        progress.update(done=C + c + 1)
                    if mgr is not None and \
                            ((c + 1) % checkpoint_every == 0 or c + 1 == C):
                        save(C + 1 + c + 1, phase2_tree(c + 1))
        except BaseException:
            if progress is not None:
                progress.on_failure()
                progress.finish("failed")
            if mgr is not None:       # a failed background write must not
                try:                  # mask the pipeline's own failure
                    mgr.wait()
                except Exception:
                    pass
            raise
        if mgr is not None:
            mgr.wait()                # final checkpoint durable on return

        # The trace doubles as a correctness record: the paper's eq.(3)
        # residual certificate for this job, as a span event.
        if tracer is not None:
            from ..core.errors import error_bound
            cert = {"m": m, "n": n, "k": k, "l": l,
                    "bound_constant": error_bound(m, n, k)}
            sigmas = getattr(source, "sigmas", None)
            if sigmas is not None:
                cert["sigma_kp1"] = float(sigmas[k])
                cert["bound"] = cert["bound_constant"] * cert["sigma_kp1"]
            obs_trace.event("eq3.certificate", **cert)
    if progress is not None:
        progress.finish("done")
    return IDResult(B=B, P=P, J=piv, Q=Q, R=R)


# ------------------------------------------------------------- analysis
# Registered contract: one pass-1 accumulate step fused with the shared
# steps-2-3 jit boundary — the device-side program of the streaming path
# (the host chunk loop itself is not traceable; its residency is metered
# by the shared sampler in repro.analysis.residency / bench_stream).

def _analysis_build_stream_step():
    l, n, k, rows = 48, 400, 21, 2 * ACCUM_BLOCK

    def step(x, a, acc):
        Y = finalize_gaussian_sketch(sketch_accum(x, a, acc), l, jnp.float32)
        return _qr_interp(Y, k, "blocked", 7, "auto")

    return step, (jax.ShapeDtypeStruct((l, rows), jnp.float32),
                  jax.ShapeDtypeStruct((rows, n), jnp.float32),
                  jax.ShapeDtypeStruct((l, n), jnp.float32))


def _analysis_build_stream_sharded_step():
    l, n, k, rows = 48, 400, 21, 2 * ACCUM_BLOCK
    mesh = Mesh(np.array(jax.devices()), ("data",))
    ndev = mesh.shape["data"]
    spec = PartitionSpec(None, "data")

    def local(x, a_loc, acc_loc):
        Y_loc = finalize_gaussian_sketch(sketch_accum(x, a_loc, acc_loc),
                                         l, jnp.float32)
        return panel_parallel_rid_interp_local(Y_loc, k, axis="data",
                                               ndev=ndev, panel=7)

    step = jax.shard_map(local, mesh=mesh,
                         in_specs=(PartitionSpec(), spec, spec),
                         out_specs=(spec, PartitionSpec(), PartitionSpec(),
                                    spec),
                         check_vma=False)
    return step, (jax.ShapeDtypeStruct((l, rows), jnp.float32),
                  jax.ShapeDtypeStruct((rows, n), jnp.float32),
                  jax.ShapeDtypeStruct((l, n), jnp.float32))


def _register_analysis_entries():
    from ..analysis.registry import register
    l, n = 48, 400
    register("rid_streamed.step", _analysis_build_stream_step)
    # The sharded-stream device program PROMISES no collective ever
    # materializes an l x n (replicated sketch-sized) array per device.
    register("rid_streamed.sharded_step", _analysis_build_stream_sharded_step,
             max_collective_elems=l * n - 1)


_register_analysis_entries()
