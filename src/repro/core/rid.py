"""Randomized interpolative decomposition (the paper's core algorithm).

Pipeline (paper section 2):                      cost (paper's accounting)
  1. sketch      Y = Phi A          (l x n)      O(lmn)        [srft GEMM, small l]
                                                 O(mn log m)   [srft FFT, large l]
  2. pivoted QR  Y Pi ~= Q [R1 R2]               O(l k n)      [the bottleneck]
  3. interp      R1 T = R2, P = [I T] Pi^-1      O(k(l+k)(n-k)) [column-parallel]
  4. subset      B = A[:, J]                     O(mk)         [k column copies, k <= n/128]
                                                 O(mn)         [jnp.take, k > n/128]

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

OBSERVABILITY: under a ``repro.obs`` tracer, ``rid`` opens the span
``rid`` (attrs ``m``, ``n``, ``k``, ``l``, ``sketch_kind``) with the
children ``rid.sketch`` (for srft, the attr ``srft_path``: ``"dense"``
or ``"fft"``, as ``core.sketch.srft_path`` picks), ``rid.qr_interp`` and
``rid.gather`` (the attr ``gather_path``, ``"slices"`` or ``"take"``, as
``gather_path`` picks; the last two spans also from
``rid_from_sketch`` called directly).  They time the host's dispatch of
each stage and never block, so the schedule is the untraced one; inside
a caller's jit no span opens.
"""
from __future__ import annotations

import contextlib
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels.common import full_precision
from ..obs import trace as obs_trace
from .qr import pivoted_qr
from .sketch import sketch, srft_path
from .tsolve import interp_from_qr
from .types import IDResult
from .validate import check_l_ge_k

__all__ = ["gather_path", "rid", "rid_from_sketch"]


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


def _span(A, name: str, **attrs):
    """The ambient span ``name``, or none where ``A`` is a tracer: under a
    caller's jit it would time the trace, not the run."""
    if isinstance(A, jax.core.Tracer):
        return contextlib.nullcontext()
    return obs_trace.span(name, **attrs)


# ``_take`` copies the k pivot columns one at a time while k is at most
# this share of n, and gathers them with one ``jnp.take`` above it.  Each
# copy reads the column's whole 128-lane tile stripe of ``A`` (23 us per
# f32 plane at m = 2^14); the take relays all of ``A`` out once (3.2 ms
# for f32 at m = n = 2^14).  Both grow with m, so the share decides: on a
# TPU v5e at m = n = 2^14 they cross near k = 142 for float32 and 161 for
# complex64, whose 32-bit split of ``A`` both forms pay (PERF.md,
# section 6).
GATHER_SLICES_MAX_SHARE = 1 / 128


def gather_path(k: int, n: int) -> str:
    """How ``_take`` gathers ``k`` of ``n`` columns: ``"slices"`` (one
    column copy per pivot) or ``"take"`` (``jnp.take``)."""
    return "slices" if k <= GATHER_SLICES_MAX_SHARE * n else "take"


@jax.jit
def _take(A: jax.Array, piv: jax.Array) -> jax.Array:
    """Step 4, ``B = A[:, piv]``, by ``gather_path``.  The slices are one
    column of ``A`` per step of a loop, so the device moves k stripes of
    ``A``, not all of it (``jnp.take`` relays the whole matrix out to
    gather its minor axis).  The loop is not unrolled, so the program is
    the same size at every ``k``; ``piv`` stays on the device.  On a TPU a
    complex64 ``A`` is still split whole into its two 32-bit planes as the
    program reads it: XLA's 64-bit rewrite does that for any op on ``A``."""
    def column(i, B):
        j = lax.dynamic_index_in_dim(piv, i, keepdims=False)
        return lax.dynamic_update_slice_in_dim(
            B, lax.dynamic_slice_in_dim(A, j, 1, axis=1), i, axis=1)

    m, n = A.shape
    k = piv.shape[0]
    if gather_path(k, n) == "take":
        return jnp.take(A, piv, axis=1)
    return lax.fori_loop(0, k, column, jnp.zeros((m, k), A.dtype))


def rid_from_sketch(A: jax.Array, Y: jax.Array, k: int, *,
                    qr_impl: str = "blocked", qr_panel: int = 32,
                    qr_norm_recompute="auto") -> IDResult:
    """Steps 2-4 given an existing sketch ``Y`` (l x n)."""
    with _span(A, "rid.qr_interp"):
        P, piv, Q, R = _qr_interp(Y, k, qr_impl, qr_panel,
                                  qr_norm_recompute)
    with _span(A, "rid.gather",
               gather_path=gather_path(piv.shape[0], A.shape[1])):
        B = _take(A, piv)
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
    m, n = A.shape
    with _span(A, "rid", m=m, n=n, k=k, l=l, sketch_kind=sketch_kind):
        attrs = ({"srft_path": srft_path(l, A.dtype)}
                 if sketch_kind == "srft" else {})
        with _span(A, "rid.sketch", **attrs):
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
