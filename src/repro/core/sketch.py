"""Randomized sketching operators: ``Y = Phi @ A`` with ``Phi`` l x m.

Three interchangeable backends (paper section 2 + DESIGN.md section 2):

* ``srft``     — the paper's faithful operator ``Y = S F D A`` (eq. 4-7):
                 random complex phases per row, column-wise DFT, and
                 ``l`` i.i.d. uniformly sampled rows.  For small ``l``
                 the ``l`` sampled rows of ``F D`` are applied as one
                 dense GEMM, O(lmn) on the MXU; for large ``l`` as the
                 full FFT, O(mn log m) (``srft_path``).
* ``srht``     — real-valued TPU-native analogue: random signs, a fast
                 Walsh-Hadamard transform (power-of-two butterflies that
                 block cleanly into VMEM — see ``repro.kernels.srht``),
                 and the same row sampling.
* ``gaussian`` — ``Y = Omega A`` as dense GEMM work.  On TPU the MXU
                 makes this the wall-clock winner for moderate ``m``
                 despite the worse O(l m n) flop count; the paper itself
                 invites replacing the randomization step with whatever
                 is fastest on the target machine.

All backends act on the ROW index of ``A`` only, so a column-sharded
``A`` sketches with ZERO communication (the property the paper's XMT
implementation exploits via column-parallel FFTs).

The gaussian backend is additionally ROW-STREAMABLE, and is defined so
that streaming is bit-for-bit exact:

  * ``Omega``'s columns are generated per canonical ``ACCUM_BLOCK``-row
    block from ``fold_in(key, block_index)`` (``gaussian_omega_cols``),
    so any row range at block granularity reproduces exactly the same
    operator values without materializing the rest;
  * the reduction ``Y = Omega A`` runs through the canonically-blocked
    ``kernels/sketch_accum`` op, which pins ONE floating-point
    association for the row sum regardless of how the rows arrive.

``repro.stream.rid_streamed`` replays both pieces chunk-at-a-time and
therefore reproduces this module's in-memory sketch exactly — the
replay guarantee ``rid``'s docstring promises, extended out-of-core.
(srft/srht mix ALL ``m`` rows through an FFT/FWHT, so they cannot
stream row chunks.)
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from ..kernels.sketch_accum import ACCUM_BLOCK, sketch_accum
from .types import SketchResult

__all__ = [
    "sketch",
    "srft_sketch",
    "srft_path",
    "srht_sketch",
    "gaussian_sketch",
    "gaussian_omega_cols",
    "finalize_gaussian_sketch",
    "fwht",
    "next_pow2",
]


def next_pow2(m: int) -> int:
    return 1 << max(0, (m - 1)).bit_length()


def fwht(x: jax.Array) -> jax.Array:
    """Orthonormal fast Walsh-Hadamard transform along axis 0.

    ``x.shape[0]`` must be a power of two.  Pure-jnp reference used both
    by the ``srht`` backend and as the oracle for the Pallas kernel.
    """
    m = x.shape[0]
    if m & (m - 1):
        raise ValueError(f"FWHT length must be a power of two, got {m}")
    tail = x.shape[1:]
    y = x
    h = 1
    while h < m:
        y = y.reshape((m // (2 * h), 2, h) + tail)
        y = jnp.stack([y[:, 0] + y[:, 1], y[:, 0] - y[:, 1]], axis=1)
        y = y.reshape((m,) + tail)
        h *= 2
    return y * jnp.asarray(1.0 / math.sqrt(m), dtype=x.dtype)


def _sample_rows(key: jax.Array, m: int, l: int) -> jax.Array:
    """Paper eq. (5): l i.i.d. uniform row indices (with replacement)."""
    return jax.random.randint(key, (l,), 0, m, dtype=jnp.int32)


# ``srft_sketch`` applies ``S F D`` as a dense GEMM while ``l`` times the
# number of real planes of ``A`` (2 if complex, 1 if real) is at most this,
# and as a full FFT above it: the GEMM's cost grows with ``l`` and the
# FFT's does not.  On a TPU v5e at m = n = 2^14 the two cross near
# l = 740 for complex64 and l = 1460 for float32 (PERF.md, section 6).
SRFT_DENSE_MAX_PLANE_ROWS = 1400


def srft_path(l: int, dtype) -> str:
    """How ``srft_sketch`` applies its operator for ``l`` sampled rows of an
    ``A`` of ``dtype``: ``"dense"`` (the ``l`` sampled DFT rows as one
    GEMM) or ``"fft"`` (the full transform, then the ``l`` rows)."""
    planes = 2 if jnp.issubdtype(dtype, jnp.complexfloating) else 1
    return "dense" if l * planes <= SRFT_DENSE_MAX_PLANE_ROWS else "fft"


def _mulmod(a: jax.Array, b: jax.Array, m: int) -> jax.Array:
    """``(a * b) mod m`` for int32 ``a``, ``b`` in ``[0, m)``, exact in 32-bit
    integers for every ``m <= 2^29``: Horner over ``s``-bit digits of ``b``,
    with ``m * 2^s <= 2^30`` so that no partial sum passes 2^31."""
    s = max(1, 30 - (m - 1).bit_length())
    digits = max(1, -(-(m - 1).bit_length() // s))
    acc = jnp.zeros(jnp.broadcast_shapes(a.shape, b.shape), jnp.int32)
    for t in reversed(range(digits)):
        digit = (b >> (s * t)) & ((1 << s) - 1)
        acc = ((acc << s) + a * digit) % m
    return acc


def _srft_operator(d: jax.Array, rows: jax.Array, l: int) -> jax.Array:
    """``S F D`` as an ``l x m`` matrix, scaled by ``1/sqrt(l)``:
    ``W[j, i] = d_i exp(-2 pi i (rows_j i mod m) / m) / sqrt(l)``.  The
    twiddle index is reduced mod ``m`` in integers before it becomes an
    angle, so it stays exact where ``rows_j * i`` passes 2^31."""
    m = d.shape[0]
    rdtype = jnp.finfo(d.dtype).dtype
    t = _mulmod(rows.astype(jnp.int32)[:, None],
                jnp.arange(m, dtype=jnp.int32)[None, :], m)
    angle = t.astype(rdtype) * jnp.asarray(-2 * math.pi / m, rdtype)
    twiddle = jax.lax.complex(jnp.cos(angle), jnp.sin(angle))
    return twiddle * (d * jnp.asarray(1 / math.sqrt(l), rdtype))[None, :]


def _srft_dense(d: jax.Array, rows: jax.Array, A: jax.Array,
                l: int) -> jax.Array:
    """``Y = W A`` with ``W = _srft_operator(d, rows, l)``, as real GEMMs at
    ``HIGHEST``: ``[Wr; Wi]`` (2l x m) against each real plane of ``A``."""
    W = _srft_operator(d, rows, l)
    rdtype = W.real.dtype
    Wc = jnp.concatenate([W.real, W.imag], axis=0)
    dot = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    if jnp.issubdtype(A.dtype, jnp.complexfloating):
        P = dot(Wc, A.real.astype(rdtype))
        Q = dot(Wc, A.imag.astype(rdtype))
        return jax.lax.complex(P[:l] - Q[l:], Q[:l] + P[l:])
    P = dot(Wc, A.astype(rdtype))
    return jax.lax.complex(P[:l], P[l:])


def _srft_fft(d: jax.Array, rows: jax.Array, A: jax.Array,
              l: int) -> jax.Array:
    """``Y = S F D A`` through the full column-wise FFT of ``D A``."""
    m = d.shape[0]
    FDA = jnp.fft.fft(d[:, None] * A.astype(d.dtype), axis=0)
    scale = jnp.asarray(1.0 / math.sqrt(l * m) * math.sqrt(m), dtype=d.dtype)  # = 1/sqrt(l)
    return FDA[rows] * scale


@partial(jax.jit, static_argnames=("l",))
def srft_sketch(key: jax.Array, A: jax.Array, l: int) -> jax.Array:
    """Paper eq. (4): ``Y = S F D A`` — the subsampled random Fourier transform.

    ``D`` multiplies each row by a random unit phase (eq. 7), ``F`` is the
    unnormalized DFT applied to every column (eq. 6), ``S`` keeps ``l``
    random rows (eq. 5); the result is scaled by ``1/sqrt(l)``.  Output is
    complex regardless of input dtype.  ``srft_path`` picks the form:
    the ``l`` sampled rows of ``F D`` as one dense GEMM, O(lmn) on the
    MXU, or the full FFT, O(mn log m), for large ``l``.
    """
    m = A.shape[0]
    kphase, krows = jax.random.split(key)
    cdtype = jnp.complex128 if A.dtype in (jnp.float64, jnp.complex128) else jnp.complex64
    rdtype = jnp.finfo(cdtype).dtype  # float64 for c128, float32 for c64
    phi = jax.random.uniform(kphase, (m,), dtype=rdtype)
    d = jnp.exp((2j * jnp.pi) * phi).astype(cdtype)
    rows = _sample_rows(krows, m, l)
    apply = _srft_dense if srft_path(l, A.dtype) == "dense" else _srft_fft
    return apply(d, rows, A, l)


@partial(jax.jit, static_argnames=("l",))
def srht_sketch(key: jax.Array, A: jax.Array, l: int) -> jax.Array:
    """Real subsampled randomized Hadamard transform (TPU-native SRFT).

    Rows are zero-padded to the next power of two; the padded rows carry
    no information about ``A`` so the row space is preserved exactly.
    """
    m, _ = A.shape
    mp = next_pow2(m)
    ksign, krows = jax.random.split(key)
    signs = jax.random.rademacher(ksign, (m,), dtype=A.dtype)
    DA = signs[:, None] * A
    if mp != m:
        DA = jnp.pad(DA, ((0, mp - m), (0, 0)))
    HDA = fwht(DA)
    rows = _sample_rows(krows, mp, l)
    scale = jnp.asarray(math.sqrt(mp / l), dtype=A.dtype)
    return HDA[rows] * scale


@partial(jax.jit, static_argnames=("nb", "l", "dtype"))
def _omega_blocks(key: jax.Array, b0, nb: int, l: int, dtype) -> jax.Array:
    """UNSCALED gaussian operator columns for canonical row blocks
    ``[b0, b0 + nb)``: an ``(l, nb * ACCUM_BLOCK)`` slab whose block ``b``
    is drawn entirely from ``fold_in(key, b)`` — so the values of any
    block depend only on ``(key, b)``, never on which other blocks the
    caller happens to generate alongside it.  ``b0`` is a TRACED operand
    (fold_in is integer hashing, value-exact either way): a streamed
    pass over thousands of chunks reuses one compile per chunk SHAPE
    instead of compiling per chunk INDEX."""
    blocks = jnp.asarray(b0, jnp.int32) + jnp.arange(nb, dtype=jnp.int32)
    keys = jax.vmap(lambda b: jax.random.fold_in(key, b))(blocks)
    if jnp.issubdtype(jnp.dtype(dtype), jnp.complexfloating):
        rdtype = jnp.float64 if dtype == jnp.complex128 else jnp.float32

        def one(kk):
            kr, ki = jax.random.split(kk)
            return (jax.random.normal(kr, (ACCUM_BLOCK, l), rdtype)
                    + 1j * jax.random.normal(ki, (ACCUM_BLOCK, l), rdtype))
    else:
        def one(kk):
            return jax.random.normal(kk, (ACCUM_BLOCK, l), dtype)
    omega_t = jax.vmap(one)(keys).reshape(nb * ACCUM_BLOCK, l)
    return omega_t.T.astype(dtype)


def gaussian_omega_cols(key: jax.Array, r0: int, r1: int, l: int,
                        dtype) -> jax.Array:
    """Columns ``[r0, r1)`` of the gaussian operator ``Omega`` (l x m),
    unscaled (``finalize_gaussian_sketch`` applies the 1/sqrt(l) at the
    end, where it is exact for every chunking).  ``r0`` must sit on a
    canonical block boundary — the granularity at which the operator is
    seeded (module docstring)."""
    if r0 % ACCUM_BLOCK:
        raise ValueError(f"need r0 a multiple of ACCUM_BLOCK={ACCUM_BLOCK}, "
                         f"got r0={r0}")
    b0, nb = r0 // ACCUM_BLOCK, -(-(r1 - r0) // ACCUM_BLOCK)
    return _omega_blocks(key, b0, nb, l, jnp.dtype(dtype))[:, :r1 - r0]


@partial(jax.jit, static_argnames=("l", "dtype"))
def finalize_gaussian_sketch(acc: jax.Array, l: int, dtype) -> jax.Array:
    """Scale the canonical accumulator into the sketch: ``1/sqrt(l)``
    (``1/sqrt(2l)`` for complex — each entry of ``Omega`` keeps variance
    ``1/l``) and cast to the input dtype."""
    cx = jnp.issubdtype(jnp.dtype(dtype), jnp.complexfloating)
    scale = 1.0 / math.sqrt(2 * l if cx else l)
    rdt = jnp.finfo(acc.dtype).dtype
    return (acc * jnp.asarray(scale, rdt)).astype(dtype)


def gaussian_sketch(key: jax.Array, A: jax.Array, l: int) -> jax.Array:
    """Dense Gaussian sketch ``Y = Omega A`` through the CANONICAL
    accumulation path (``kernels/sketch_accum``): block-seeded operator
    columns, fixed-block row reduction, one final scale.  Exactly the
    computation ``repro.stream.rid_streamed`` replays chunk-at-a-time,
    which is what makes streamed and in-memory sketches bit-for-bit
    identical.  Deliberately NOT jitted as a whole: ``sketch_accum``
    must stay its own jit boundary for that replay contract to hold."""
    m = A.shape[0]
    omega = gaussian_omega_cols(key, 0, m, l, A.dtype)
    return finalize_gaussian_sketch(sketch_accum(omega, A), l, A.dtype)


_BACKENDS = {
    "srft": srft_sketch,
    "srht": srht_sketch,
    "gaussian": gaussian_sketch,
}


def sketch(key: jax.Array, A: jax.Array, l: int, kind: str = "srft") -> SketchResult:
    """Dispatch to a sketch backend.  ``kind in {'srft','srht','gaussian'}``."""
    try:
        fn = _BACKENDS[kind]
    except KeyError:
        raise ValueError(f"unknown sketch kind {kind!r}; pick from {sorted(_BACKENDS)}")
    return SketchResult(Y=fn(key, A, l), kind=kind)
