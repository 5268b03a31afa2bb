"""Chunk sources: the host-side feeding half of the streaming RID.

A :class:`ChunkSource` hands the pipeline one row chunk of ``A`` at a
time — the ONLY way the streaming decomposition ever sees the matrix.
Three implementations ship:

  * ``ArraySource``    — slices a host-resident (numpy) array; the
                         paper-shaped "matrix on the host, not in HBM"
                         case.  Chunks are zero-copy row views.
  * ``SpectrumSource`` — seeded generator over a KNOWN-spectrum matrix
                         (``data.synthetic.spectrum_factors``): rows are
                         evaluated in closed form per chunk, so the
                         eq.(3) error tests scale ``m`` out-of-core with
                         the exact ``sigma_{k+1}`` still in hand.
  * ``FileSource``     — memory-mapped ``.npy`` on disk, with an async
                         read-ahead thread (``data.prefetch.
                         PrefetchIterator``) so the NEXT chunk's disk
                         read overlaps the current chunk's host->device
                         transfer and accumulate GEMM — the out-of-core
                         leg of the paper's 64 GB path.

Sources must be re-readable: the decomposition makes TWO passes (sketch
accumulation, then the pivot-column gather ``B = A[:, J]``), so
``chunk(c)`` may be called more than once and must return the same rows
each time.

Out-of-range reads fail LOUDLY: ``chunk(c)`` and ``chunk_bounds`` with
``c`` outside ``[0, num_chunks)`` raise ``ValueError`` naming ``c`` and
the valid chunk count.  (Historically the slice ``A[r0:r1]`` past EOF
silently returned a ``(0, n)`` array, so an off-by-one in the pipeline
— or a resume against a stale manifest — corrupted the accumulator
instead of crashing.)

FINGERPRINTS: a source may expose ``fingerprint()`` returning a value
that identifies the MATRIX (not just its geometry); it is folded into
the streamed pipeline's resume identity (``rid_stream.
source_fingerprint``), so a checkpoint directory written against one
matrix is rejected for any other.  ``FileSource`` fingerprints
``(path, size, mtime_ns)``; ``SpectrumSource`` fingerprints
``(seed, spectrum, k, r, floor, dtype)``.  A source WITHOUT a
fingerprint (``ArraySource``) contributes only its geometry — callers
who resume against host arrays own the identity question themselves.

``FileSource`` failure modes (all exercised in tests/test_stream_file.py):

  failure                 surfaces as                       when
  missing file            FileNotFoundError naming path     construction
  not a 2-D .npy          ValueError (ndim named)           construction
  truncated file          ValueError from the mmap (the     construction
                          header promises more bytes than
                          the file holds)
  file replaced/appended  SourceDied naming path + both     next chunk
  mid-job (mtime/size     (size, mtime_ns) pairs            read (every
  drift)                                                    read re-stats)
  read after close()      ValueError naming the source      chunk(c)
  out-of-range chunk      ValueError naming c and the       chunk(c)
                          valid count

Mtime drift is PERMANENT (``runtime.faults.SourceDied``, never retried):
the mmap would hand back a mix of old and new bytes, and the right
recovery is a fresh job — a resume of the old checkpoint against the
mutated file is rejected by the ``(path, size, mtime)`` fingerprint.
"""
from __future__ import annotations

import os
from typing import Iterator, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from ..data.prefetch import PrefetchIterator
from ..data.synthetic import SpectrumFactors, spectrum_factors, spectrum_rows
from ..runtime.faults import SourceDied

__all__ = ["ChunkSource", "ArraySource", "SpectrumSource", "FileSource",
           "num_chunks", "chunk_bounds", "check_chunk_index"]


@runtime_checkable
class ChunkSource(Protocol):
    """Row-chunked read access to an ``m x n`` matrix."""

    shape: tuple[int, int]
    dtype: jnp.dtype
    chunk_rows: int

    def chunk(self, c: int):
        """Rows ``[c * chunk_rows, min((c + 1) * chunk_rows, m))`` as a
        host (numpy) or device array.  Must be deterministic per ``c``;
        ``c`` outside ``[0, num_chunks)`` raises ``ValueError``."""
        ...


def num_chunks(source: ChunkSource) -> int:
    m = source.shape[0]
    return -(-m // source.chunk_rows)


def check_chunk_index(source: ChunkSource, c: int) -> None:
    """Reject an out-of-range chunk index EAGERLY, naming ``c`` and the
    valid range — the silent alternative is an empty ``(0, n)`` slice
    past EOF that corrupts the accumulator instead of crashing."""
    C = num_chunks(source)
    if not 0 <= c < C:
        raise ValueError(f"chunk index c={c} out of range for "
                         f"{type(source).__name__} with {C} chunks "
                         f"(m={source.shape[0]}, "
                         f"chunk_rows={source.chunk_rows}); valid c are "
                         f"[0, {C})")


def chunk_bounds(source: ChunkSource, c: int) -> tuple[int, int]:
    check_chunk_index(source, c)
    m = source.shape[0]
    r0 = c * source.chunk_rows
    return r0, min(r0 + source.chunk_rows, m)


class ArraySource:
    """Host-array slicer: ``A`` stays host-resident; each chunk is a
    zero-copy row view that the pipeline transfers on demand."""

    def __init__(self, A, chunk_rows: int):
        A = np.asarray(A)
        if A.ndim != 2:
            raise ValueError(f"need a 2-D matrix, got shape {A.shape}")
        if chunk_rows < 1:
            raise ValueError(f"need chunk_rows >= 1, got "
                             f"chunk_rows={chunk_rows}")
        self._A = A
        self.shape = A.shape
        self.dtype = jnp.dtype(A.dtype)
        self.chunk_rows = int(chunk_rows)

    def chunk(self, c: int) -> np.ndarray:
        r0, r1 = chunk_bounds(self, c)
        return self._A[r0:r1]


class SpectrumSource:
    """Seeded generator source over a known-spectrum matrix.

    ``sigmas`` carries the EXACT singular values (``sigmas[k]`` is the
    eq.(3) reference ``sigma_{k+1}``), and ``factors`` the factorization
    itself (``data.synthetic.SpectrumFactors``), from which a reference
    can evaluate ``||A x||`` without ``A``; rows are generated per chunk and
    never held all at once, so ``m`` can exceed device (and host)
    memory.  Generation is closed-form per global row index — the same
    matrix regardless of ``chunk_rows``.
    """

    def __init__(self, key: jax.Array, m: int, n: int, spectrum: str,
                 k: int, *, chunk_rows: int, r: Optional[int] = None,
                 dtype=jnp.float64, floor: float = 1e-6):
        if chunk_rows < 1:
            raise ValueError(f"need chunk_rows >= 1, got "
                             f"chunk_rows={chunk_rows}")
        self.factors: SpectrumFactors = spectrum_factors(
            key, m, n, spectrum, k, r=r, dtype=dtype, floor=floor)
        self.sigmas = np.asarray(self.factors.sig)
        self.shape = (m, n)
        self.dtype = jnp.dtype(dtype)
        self.chunk_rows = int(chunk_rows)
        # The MATRIX identity, beyond geometry: two sources with the same
        # (m, n, chunk_rows, dtype) but different key/spectrum/k/r/floor
        # generate different matrices and must not share a resume dir.
        self._fp = (
            np.asarray(jax.random.key_data(key)).tobytes().hex(),
            str(spectrum), int(k), int(r) if r is not None else None,
            float(floor), str(jnp.dtype(dtype)))

    def fingerprint(self) -> tuple:
        """Everything the generated VALUES depend on (seed, spectrum, k,
        r, floor, dtype) — folded into the resume identity so a
        checkpoint from a different generated matrix is rejected even
        when the geometry matches."""
        return self._fp

    def chunk(self, c: int) -> jax.Array:
        r0, r1 = chunk_bounds(self, c)
        return spectrum_rows(self.factors, r0, r1)

    def materialize(self) -> np.ndarray:
        """Concatenate every chunk — small-``m`` tests only."""
        return np.concatenate([np.asarray(self.chunk(c))
                               for c in range(num_chunks(self))])


class FileSource:
    """Memory-mapped ``.npy`` chunk source with async read-ahead.

    The matrix lives on DISK; ``chunk(c)`` copies rows out of the mmap
    (forcing the page-in on the reader thread, not the pipeline), so
    peak HOST memory is ``O(readahead * chunk_rows * n)`` and the
    streamed decomposition's input size is bounded by the filesystem —
    the paper's 64 GB matrices on a machine with neither 64 GB of HBM
    nor 64 GB of RAM.

    READ-AHEAD: with ``readahead >= 1`` a background thread
    (``data.prefetch.PrefetchIterator``, the leak-free one) walks the
    chunks sequentially and keeps up to ``readahead`` of them decoded in
    a bounded queue, so in pass 1 the DISK read of chunk ``c + 1``
    overlaps the host->device transfer AND the accumulate GEMM of chunk
    ``c`` (three-deep pipeline: disk -> host -> device).  Both passes of
    ``rid_streamed`` are sequential scans, the prefetcher's fast path; a
    non-sequential read (a resume replaying from a checkpoint, a retry
    re-reading the same chunk) transparently restarts the read-ahead at
    the requested chunk.  ``readahead=0`` reads synchronously.

    RESUME IDENTITY: ``fingerprint()`` is ``(abspath, size, mtime_ns)``
    captured at construction, and every read re-stats the file — see the
    module docstring's failure-mode table for what drifts raise.

    ``close()`` stops the reader thread and drops the mmap; it is
    idempotent, and the source doubles as a context manager.
    """

    def __init__(self, path, chunk_rows: int, *, readahead: int = 2):
        if chunk_rows < 1:
            raise ValueError(f"need chunk_rows >= 1, got "
                             f"chunk_rows={chunk_rows}")
        if readahead < 0:
            raise ValueError(f"need readahead >= 0, got "
                             f"readahead={readahead}")
        path = os.fspath(path)
        if not os.path.isfile(path):
            raise FileNotFoundError(f"FileSource: no such file: {path!r}")
        # A truncated file fails HERE: the .npy header promises more
        # bytes than the file holds and the mmap constructor rejects it.
        self._mm = np.load(path, mmap_mode="r")
        if self._mm.ndim != 2:
            raise ValueError(f"FileSource needs a 2-D .npy, got ndim="
                             f"{self._mm.ndim} (shape {self._mm.shape}) "
                             f"in {path!r}")
        st = os.stat(path)
        self.path = os.path.abspath(path)
        self._size = int(st.st_size)
        self._mtime_ns = int(st.st_mtime_ns)
        self.shape = tuple(self._mm.shape)
        self.dtype = jnp.dtype(self._mm.dtype)
        self.chunk_rows = int(chunk_rows)
        self._readahead = int(readahead)
        self._pf: Optional[PrefetchIterator] = None
        self._pf_next = 0            # chunk the prefetcher yields next
        self._closed = False

    def fingerprint(self) -> tuple:
        """``(abspath, size, mtime_ns)`` at construction — the on-disk
        matrix identity the PR-8 resume contract authenticates against."""
        return (self.path, self._size, self._mtime_ns)

    def _read(self, c: int) -> np.ndarray:
        """The actual disk read (runs on the read-ahead thread): re-stat
        first — a file replaced or appended mid-job would hand back a
        mix of old and new bytes through the mmap."""
        st = os.stat(self.path)
        if (int(st.st_size), int(st.st_mtime_ns)) != (self._size,
                                                      self._mtime_ns):
            raise SourceDied(
                f"file {self.path!r} changed mid-job: (size, mtime_ns) now "
                f"({st.st_size}, {st.st_mtime_ns}), was ({self._size}, "
                f"{self._mtime_ns}) at open — the mmap would mix old and "
                f"new bytes; start a fresh job against the new file")
        r0, r1 = chunk_bounds(self, c)
        return np.array(self._mm[r0:r1])     # copy = force the page-in

    def _chunks_from(self, c0: int) -> Iterator[np.ndarray]:
        for c in range(c0, num_chunks(self)):
            yield self._read(c)

    def chunk(self, c: int) -> np.ndarray:
        check_chunk_index(self, c)
        if self._closed:
            raise ValueError(f"FileSource({self.path!r}) is closed; "
                             f"chunk({c}) after close() is a bug in the "
                             f"caller's lifetime management")
        if self._readahead == 0:
            return self._read(c)
        if self._pf is None or self._pf_next != c:
            # Non-sequential read (resume / retry): restart the
            # read-ahead at the requested chunk.
            if self._pf is not None:
                self._pf.close()
            self._pf = PrefetchIterator(self._chunks_from(c),
                                        depth=self._readahead)
            self._pf_next = c
        try:
            out = next(self._pf)
        except BaseException:
            # The reader thread died raising (e.g. mtime drift): drop the
            # iterator so a later read restarts cleanly instead of
            # blocking on the dead queue.
            self._pf.close()
            self._pf = None
            self._pf_next = 0
            raise
        self._pf_next = c + 1
        if self._pf_next >= num_chunks(self):
            self._pf.close()         # pass done; the next pass restarts
            self._pf = None
            self._pf_next = 0
        return out

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._pf is not None:
            self._pf.close()
            self._pf = None
        self._mm = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
