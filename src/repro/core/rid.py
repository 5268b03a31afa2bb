"""Randomized interpolative decomposition (the paper's core algorithm).

Pipeline (paper section 2):                      cost (paper's accounting)
  1. sketch      Y = Phi A          (l x n)      O(mn log m)   [FFT backend]
  2. pivoted QR  Y Pi ~= Q [R1 R2]               O(l k n)      [the bottleneck]
  3. interp      R1 T = R2, P = [I T] Pi^-1      O(k(l+k)(n-k)) [column-parallel]
  4. subset      B = A[:, J]

``rid`` is jit-compatible (k, l static).  Every stage takes an explicit
PRNG key; the same key reproduces the same decomposition bit-for-bit,
which the fault-tolerance layer relies on for replay.  The replay
contract extends OUT-OF-CORE: ``repro.stream.rid_streamed`` reproduces
``rid``'s gaussian-sketch result exactly without ever holding ``A`` on
device, because the sketch reduction is canonically blocked
(``kernels/sketch_accum``) and steps 2-3 run through the shared
``_qr_interp`` jit boundary below.  (For that reason the default entry
points compose separately-jitted stages rather than one outer jit —
wrapping them in a caller's jit is still fine, but the wrapped result
is only bit-identical to itself.)

Step 2 has two engines, selected by ``qr_impl``:

  * ``"blocked"`` — the blocked-panel engine (``blocked_pivoted_qr``):
                    panel-at-a-time pivoting with one GEMM-pair trailing
                    update per panel (``qr_panel`` columns, default 32),
                    the MXU-bound production DEFAULT;
  * ``"cgs2"``    — the paper's per-column iterated Gram-Schmidt
                    (``cgs2_pivoted_qr``), kept as the parity oracle.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ..kernels.common import full_precision
from .qr import pivoted_qr
from .sketch import sketch
from .tsolve import interp_from_qr
from .types import IDResult
from .validate import check_l_ge_k

__all__ = ["rid", "rid_from_sketch"]


@partial(jax.jit, static_argnames=("k", "qr_impl", "qr_panel",
                                   "qr_norm_recompute"))
@full_precision
def _qr_interp(Y: jax.Array, k: int, qr_impl: str, qr_panel: int,
               qr_norm_recompute):
    """Steps 2-3 (pivoted QR of the sketch + interpolation solve) as ONE
    shared jit boundary: both ``rid_from_sketch`` and the streaming
    ``repro.stream.rid_streamed`` call exactly this computation, so the
    same sketch bits yield the same ``(P, piv, Q, R)`` bits on either
    path (the streamed replay guarantee)."""
    qr = pivoted_qr(Y, k, impl=qr_impl, panel=qr_panel,
                    norm_recompute=qr_norm_recompute)
    P = interp_from_qr(qr.R, qr.piv)
    return P, qr.piv, qr.Q, qr.R


def _cast_interp(P: jax.Array, a_dtype) -> jax.Array:
    """P is in sketch dtype (complex for SRFT); cast to ``A``'s dtype when
    ``A`` is real and the sketch was complex: the imaginary part is pure
    roundoff because A's row space is real."""
    if jnp.issubdtype(P.dtype, jnp.complexfloating) and not jnp.issubdtype(
            a_dtype, jnp.complexfloating):
        return P.real.astype(a_dtype)
    return P


def rid_from_sketch(A: jax.Array, Y: jax.Array, k: int, *,
                    qr_impl: str = "blocked", qr_panel: int = 32,
                    qr_norm_recompute="auto") -> IDResult:
    """Steps 2-4 given an existing sketch ``Y`` (l x n)."""
    P, piv, Q, R = _qr_interp(Y, k, qr_impl, qr_panel, qr_norm_recompute)
    B = jnp.take(A, piv, axis=1)
    return IDResult(B=B, P=_cast_interp(P, A.dtype), J=piv, Q=Q, R=R)


def rid(key: jax.Array, A: jax.Array, k: int, *, l: Optional[int] = None,
        sketch_kind: str = "srft", qr_impl: str = "blocked",
        qr_panel: int = 32, qr_norm_recompute="auto") -> IDResult:
    """Rank-``k`` randomized ID of ``A``: ``A ~= B @ P``.

    Args:
      key: PRNG key driving ``D``/``S`` (and ``Omega`` for gaussian).
      A: (m, n) matrix, real or complex.
      k: target rank (static).
      l: sketch rows; defaults to the paper's universal choice ``l = 2k``.
      sketch_kind: 'srft' (paper-faithful) | 'srht' | 'gaussian'.
      qr_impl: 'blocked' (panel GEMM engine, the production default) |
        'cgs2' (the paper-faithful parity oracle).
      qr_panel: panel width for the blocked engine (ignored by cgs2).
        An int, or 'auto' for the widest width the fitted eq.(3) drift
        model predicts safe at this (k, l) — 16 at the universal l = 2k
        oversampling; see ``core.qr.resolve_panel``.
      qr_norm_recompute: exact-norm recompute cadence of the fused panel
        loop ('auto' = every 8 panels, 1 = every panel — the
        paper-parity pin, 0 = never); ignored by cgs2.  See
        ``core.qr.resolve_norm_recompute``.
    """
    l = 2 * k if l is None else l
    check_l_ge_k(l, k)
    Y = sketch(key, A, l, kind=sketch_kind).Y
    return rid_from_sketch(A, Y, k, qr_impl=qr_impl, qr_panel=qr_panel,
                           qr_norm_recompute=qr_norm_recompute)


# ------------------------------------------------------------- analysis
# Registered contract: the end-to-end single-device RID (gaussian sketch
# so the trace is real-dtype'd; srft's complex FFT path has its own
# explicit casts).

def _analysis_build_rid():
    def fn(key, A):
        return rid(key, A, 21, sketch_kind="gaussian")
    return fn, (jax.random.key(0),
                jax.ShapeDtypeStruct((256, 400), jnp.float32))


def _register_analysis_entries():
    from ..analysis.registry import register
    register("rid", _analysis_build_rid)


_register_analysis_entries()
