"""Synthetic data: token streams for the LM stack and KNOWN-SPECTRUM test
matrices for the eq.(3) verification grid.

Tokens follow a noisy periodic Markov-ish pattern (token ~ affine hash of
position and a per-sequence phase, plus noise) so a real model TRAINS to
a loss well below uniform — the end-to-end example needs a demonstrable
learning curve, not white noise.  Generation is counter-based
(threefry on (seed, step, index)) — O(1) seekable, host-shardable.

``spectrum_sigmas`` / ``spectrum_matrix`` build matrices ``A = U S V^H``
with an exactly known singular spectrum, so eq.(3) — which bounds
``||A - BP||_2`` by a multiple of ``sigma_{k+1}`` — can be checked
against the TRUE ``sigma_{k+1}`` instead of the paper's noise-floor
estimate.  Three shapes cover the failure modes the blocked/fused QRCP
engines are known to have (tests/test_error_bounds.py):

  fast_decay — geometric decay down to ``floor``: the f32 residual-norm
               DOWNDATE drift case (cancellation noise drowns the tail
               panels' pivot statistics — core.qr_dist docstring);
  cliff      — flat at 1.0 through index k-1 then a hard drop: the
               pivot-QUALITY case (picking any k of the leading columns
               is right; missing one costs a factor 1/gap);
  noisy_tail — polynomial decay into a flat noise plateau: the
               near-tie case (panel-granularity pivoting must not do
               worse than the per-column oracle on ties).
"""
from __future__ import annotations

from functools import partial
from typing import Iterator, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

SPECTRA = ("fast_decay", "cliff", "noisy_tail")

# Smallest spectrum floor per dtype that keeps sigma_{k+1} well above the
# working precision's cancellation level — the single source the
# verification-grid tests (tests/strategies.py) and the calibration bench
# (benchmarks/bench_error.py --grid) both draw from, so the tested and
# the recorded grids stay the same grid.
DTYPE_FLOORS = {"float32": 1e-5, "complex64": 1e-5,
                "float64": 1e-12, "complex128": 1e-12}


def spectrum_sigmas(spectrum: str, r: int, k: int, *,
                    floor: float = 1e-6) -> np.ndarray:
    """The ``r`` singular values of a synthetic ``spectrum`` (see module
    docstring), scaled so ``sigma_0 = 1``; ``floor`` sets the smallest
    value (pick it well above the working dtype's cancellation level:
    ~1e-5 for f32, ~1e-12 for f64)."""
    if spectrum not in SPECTRA:
        raise ValueError(f"unknown spectrum {spectrum!r}; expected one of "
                         f"{SPECTRA}")
    if not (0 < k < r):
        raise ValueError(f"need 0 < k < r, got k={k}, r={r}")
    i = np.arange(r, dtype=np.float64)
    if spectrum == "fast_decay":
        return floor ** (i / (r - 1))
    if spectrum == "cliff":
        # sqrt(floor) keeps the post-cliff block itself well-conditioned
        # relative to the dtype while the k|k+1 gap stays hard.
        return np.where(i < k, 1.0, np.sqrt(floor))
    # noisy_tail: polynomial decay into a flat plateau at sqrt(floor)
    return np.maximum((i + 1.0) ** -1.5, np.sqrt(floor))


class SpectrumFactors(NamedTuple):
    """Row-generable factorization ``A = D U S V^H`` with EXACT singular
    values (``spectrum_rows`` evaluates any row range in closed form):

      ``U``  — ``r`` distinct orthonormal DCT-II (real) / DFT (complex)
               basis columns, picked by seeded frequencies: row ``i`` of
               column ``j`` is a cosine/phasor evaluated at ``(i, f_j)``,
               so a chunk of rows never needs the rest of the matrix;
      ``D``  — a seeded unit-modulus row diagonal (signs / phases,
               hashed per GLOBAL row index), which randomizes the row
               space without touching the spectrum;
      ``V``  — dense orthonormal ``n x r`` from QR of a seeded normal
               (``n`` is the sketch-resident dimension, fine to hold).

    This is the streaming analogue of ``spectrum_matrix``: the eq.(3)
    tests can scale ``m`` out-of-core while still knowing sigma_{k+1}
    exactly (``repro.stream.SpectrumSource`` wraps it as a ChunkSource).
    """

    freqs: np.ndarray       # (r,) int64 HOST array of distinct frequencies
    V: jax.Array            # (n, r) orthonormal right factor (f64/c128)
    sig: np.ndarray         # (r,) exact singular values, descending
    sign_key: jax.Array     # per-row unit-modulus diagonal seed
    m: int
    dtype: jnp.dtype


def _distinct_ints(key: jax.Array, r: int, lo: int, hi: int) -> jax.Array:
    """``r`` distinct seeded integers in ``[lo, hi)`` with O(r) memory —
    NOT ``random.choice(replace=False)``, whose internal permutation is
    O(hi) and would make the streaming-scale generator OOM at the very
    ``m`` it exists for.  Uniform f64 draws (exact integers below 2^53)
    deduplicated host-side; collisions at ``r << hi`` are rare, so a
    couple of rounds suffice."""
    if hi - lo < r:
        raise ValueError(f"need hi - lo >= r, got [{lo}, {hi}) for r={r}")
    vals = np.empty(0, np.int64)
    while vals.size < r:
        key, sub = jax.random.split(key)
        u = np.asarray(jax.random.uniform(sub, (2 * r,), jnp.float64))
        draw = lo + np.floor(u * (hi - lo)).astype(np.int64)
        vals = np.unique(np.concatenate([vals, draw]))
    # Host int64 (NOT a device int32 array): frequencies reach m, which
    # overflows int32 exactly at the out-of-core scales this exists for.
    return vals[:r]


def spectrum_factors(key: jax.Array, m: int, n: int, spectrum: str, k: int, *,
                     r: Optional[int] = None, dtype=jnp.float64,
                     floor: float = 1e-6) -> SpectrumFactors:
    """Build the row-generable known-spectrum factorization (see
    ``SpectrumFactors``).  Requires ``r <= m - 1`` distinct nonzero
    frequencies (real DCT basis) — trivially true at streaming scales."""
    # Default clamps to m - 1 (unlike spectrum_matrix's min(.., m, ..)):
    # the real DCT basis has only m - 1 nonzero frequencies to draw from.
    r = min(2 * k + 16, m - 1, n) if r is None else r
    if r > min(m - 1, n):
        raise ValueError(f"need r <= min(m - 1, n), got r={r}, m={m}, n={n}")
    sig = spectrum_sigmas(spectrum, r, k, floor=floor)
    dtype = jnp.dtype(dtype)
    cx = jnp.issubdtype(dtype, jnp.complexfloating)
    kf, kv, kv2, ks = jax.random.split(key, 4)
    if cx:
        freqs = _distinct_ints(kf, r, 0, m)
    else:
        freqs = _distinct_ints(kf, r, 1, m)
    V = jax.random.normal(kv, (n, r), jnp.float64)
    if cx:
        V = V + 1j * jax.random.normal(kv2, (n, r), jnp.float64)
    V = jnp.linalg.qr(V)[0]
    return SpectrumFactors(freqs=freqs, V=V, sig=sig,
                           sign_key=ks, m=m, dtype=dtype)


def _mulmod(a: jax.Array, b: jax.Array, mod: int) -> jax.Array:
    """``(a * b) % mod`` exactly in 32-bit integers, for integer arrays
    ``0 <= a, b < mod <= 2**30``: Horner over ``L``-bit limbs of ``b``,
    with ``L`` chosen so that no partial product reaches ``2**31``."""
    bits = max(1, (mod - 1).bit_length())
    limb = 31 - bits
    r = jnp.zeros(jnp.broadcast_shapes(a.shape, b.shape), a.dtype)
    for shift in reversed(range(0, bits, limb)):
        part = (b >> shift) & ((1 << limb) - 1)
        r = ((r << limb) % mod + (a * part) % mod) % mod
    return r


def spectrum_rows(f: SpectrumFactors, r0: int, r1: int) -> jax.Array:
    """Rows ``[r0, r1)`` of the factored matrix, in ``f.dtype``.  Each row
    depends only on its global index, so any chunking of ``[0, m)``
    concatenates to the same matrix."""
    i = jnp.arange(r0, r1)
    keys = jax.vmap(lambda ii: jax.random.fold_in(f.sign_key, ii))(i)
    # i * f reaches ~m^2: reduce it modulo the basis period in exact
    # integer arithmetic BEFORE the 2*pi scaling, so the trig arguments
    # stay small and full-precision at any streaming m, with or without
    # x64.
    ff = jnp.asarray(f.freqs)[None, :]
    # The generating GEMM runs at full precision: the spectrum is only
    # exact if the rows are (a TPU's default f32 matmul rounds its inputs
    # to bfloat16).
    gemm = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    if jnp.issubdtype(f.dtype, jnp.complexfloating):
        phase = jax.vmap(lambda kk: jax.random.uniform(kk, ()))(keys)
        d = jnp.exp((2j * jnp.pi) * phase.astype(jnp.float64))
        frac = _mulmod(i[:, None], ff, f.m).astype(jnp.float64) / f.m
        U = (d[:, None] * jnp.exp((2j * jnp.pi) * frac)) / np.sqrt(f.m)
    else:
        d = jax.vmap(lambda kk: jax.random.rademacher(kk, (), jnp.float64))(keys)
        # cos(pi (i + 1/2) f / m) has period 4m in (2i+1) f
        t = _mulmod((2 * i + 1)[:, None], ff, 4 * f.m).astype(jnp.float64)
        U = (d[:, None] * jnp.cos((jnp.pi / (2.0 * f.m)) * t)) * \
            np.sqrt(2.0 / f.m)
    rows = gemm(U * jnp.asarray(f.sig)[None, :], f.V.conj().T)
    return rows.astype(f.dtype)


def spectrum_matrix(key: jax.Array, m: int, n: int, spectrum: str, k: int, *,
                    r: Optional[int] = None, dtype=jnp.float64,
                    floor: float = 1e-6) -> tuple[jax.Array, np.ndarray]:
    """``(A, sigmas)``: an ``m x n`` matrix of rank ``r`` (default
    ``min(2 * k + 16, m, n)``) with EXACTLY the singular values
    ``spectrum_sigmas(spectrum, r, k, floor=floor)`` (up to the rounding
    of two orthonormal factors), in ``dtype`` (real or complex).  The
    true ``sigma_{k+1}`` is ``sigmas[k]`` — the eq.(3) reference."""
    r = min(2 * k + 16, m, n) if r is None else r
    sig = spectrum_sigmas(spectrum, r, k, floor=floor)
    ku, kv, ku2, kv2 = jax.random.split(key, 4)
    U = jax.random.normal(ku, (m, r), jnp.float64)
    V = jax.random.normal(kv, (n, r), jnp.float64)
    if jnp.issubdtype(dtype, jnp.complexfloating):
        U = U + 1j * jax.random.normal(ku2, (m, r), jnp.float64)
        V = V + 1j * jax.random.normal(kv2, (n, r), jnp.float64)
    U = jnp.linalg.qr(U)[0]
    V = jnp.linalg.qr(V)[0]
    A = (U * jnp.asarray(sig)[None, :]) @ V.conj().T
    return A.astype(dtype), sig


class SyntheticConfig(NamedTuple):
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0
    noise: float = 0.05          # fraction of tokens replaced with noise
    period: int = 17             # base period of the learnable pattern


def _pattern_tokens(key: jax.Array, cfg: SyntheticConfig, batch: int):
    """(batch, seq_len + 1) tokens: per-row phase + periodic ramp + noise."""
    kphase, knoise, kval = jax.random.split(key, 3)
    S = cfg.seq_len + 1
    phase = jax.random.randint(kphase, (batch, 1), 0, cfg.period)
    pos = jnp.arange(S, dtype=jnp.int32)[None, :]
    base = (phase * 31 + pos * 7) % (cfg.period * 13)
    toks = base % cfg.vocab_size
    noise_mask = jax.random.bernoulli(knoise, cfg.noise, (batch, S))
    noise_val = jax.random.randint(kval, (batch, S), 0, cfg.vocab_size)
    return jnp.where(noise_mask, noise_val, toks).astype(jnp.int32)


def batch_for_step(cfg: SyntheticConfig, step: int, *, host: int = 0,
                   n_hosts: int = 1) -> dict:
    """The batch (or this host's shard of it) for global step ``step``."""
    assert cfg.global_batch % n_hosts == 0
    local = cfg.global_batch // n_hosts
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.key(cfg.seed), step), host)
    toks = _pattern_tokens(key, cfg, local)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def make_batch_iterator(cfg: SyntheticConfig, *, start_step: int = 0,
                        host: int = 0, n_hosts: int = 1) -> Iterator[dict]:
    step = start_step
    while True:
        yield batch_for_step(cfg, step, host=host, n_hosts=n_hosts)
        step += 1
