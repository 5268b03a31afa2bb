"""Jit'd wrappers for the fused panel-step kernels."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..common import full_precision, interpret_default, pad_to, round_up
from .kernel import panel_apply_kernel, panel_coeff_kernel, panel_step_kernel
from .ref import (panel_apply_norms_ref, panel_apply_ref, panel_coeff_ref,
                  panel_step_ref)

__all__ = ["panel_step", "panel_coeff", "panel_apply"]


# Widest sketch height at which the fused step's 256-lane slabs fit a
# v5e's VMEM with its contractions at full precision (compile rehearsal:
# l = 1800 fits, l = 2000 needs 128 lanes); taller sketches take 128.
_WIDE_SLAB_MAX_L = 1024


def _is_complex(*xs) -> bool:
    return any(jnp.issubdtype(x.dtype, jnp.complexfloating) for x in xs)


@partial(jax.jit, static_argnames=("bn", "interpret", "emit_w"))
@full_precision
def panel_step(c: jax.Array, z: jax.Array, *, bn: int | None = None,
               interpret: bool | None = None, emit_w: bool = True
               ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused panel step: factor the candidate panel ``c`` (l x b) with
    CholeskyQR2 and sweep the residual ``z`` (l x n) ONCE, returning
    ``(Q_p, Z - Q_p W, W, colnorms^2(Z - Q_p W))`` — the orthonormal
    panel, the deflated trailing slab, the coefficient block, and the
    next panel's pivot statistics from one VMEM residency.  Callers
    that never read ``W`` (e.g. ``blocked_pivoted_qr``, which recomputes
    ``R = Q^H Y`` at the end) pass ``emit_w=False`` to elide its
    (b x n) HBM writeback; the ``W`` slot is then ``None``.  Real dtypes
    take the Pallas path; complex falls back to the oracle formula like
    the other kernels (the production path is real).  ``bn`` (slab
    lanes) defaults to 256, or 128 above ``_WIDE_SLAB_MAX_L`` rows."""
    interpret = interpret_default() if interpret is None else interpret
    if _is_complex(c, z):
        qp, o, w, r2 = panel_step_ref(c, z)
        return qp, o, (w if emit_w else None), r2
    l, n = z.shape
    if bn is None:
        bn = 256 if l <= _WIDE_SLAB_MAX_L else 128
    np_ = round_up(n, bn)
    qp, o, w, r2 = panel_step_kernel(c, pad_to(z, (l, np_)), bn=bn,
                                     interpret=interpret, emit_w=emit_w)
    return qp, o[:, :n], (w[:, :n] if emit_w else None), r2[0, :n]


@partial(jax.jit, static_argnames=("bn", "interpret"))
@full_precision
def panel_coeff(c: jax.Array, z: jax.Array, res2: jax.Array, *,
                bn: int = 256, interpret: bool | None = None
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Factor + coefficient half (distributed stage A): ``(Q_p, W,
    max(res2 - colnorms^2(W), 0))``.  The downdated norms make the next
    panel's pivot psum independent of the deflation (stage B), which is
    what lets the collective overlap the GEMM in ``core.qr_dist``."""
    interpret = interpret_default() if interpret is None else interpret
    if _is_complex(c, z):
        return panel_coeff_ref(c, z, res2)
    l, n = z.shape
    np_ = round_up(n, bn)
    qp, w, r2 = panel_coeff_kernel(c, pad_to(z, (l, np_)),
                                   pad_to(res2[None, :].astype(z.dtype),
                                          (1, np_)),
                                   bn=bn, interpret=interpret)
    return qp, w[:, :n], r2[0, :n]


@partial(jax.jit, static_argnames=("bn", "interpret", "emit_norms"))
@full_precision
def panel_apply(qp: jax.Array, w: jax.Array, z: jax.Array, *,
                bn: int = 256, interpret: bool | None = None,
                emit_norms: bool = False):
    """Deflation half (distributed stage B): ``z - qp @ w`` with ``w``
    from ``panel_coeff`` — the pass the norm psum runs concurrently
    with.  ``emit_norms=True`` returns ``(O, colnorms^2(O))`` from the
    same fused pass: the EXACT pivot statistics of the deflated slab,
    which a periodic ``norm_recompute`` panel substitutes for the
    drift-accumulating downdate (core.qr_dist)."""
    interpret = interpret_default() if interpret is None else interpret
    if _is_complex(qp, z):
        if emit_norms:
            return panel_apply_norms_ref(qp, w, z)
        return panel_apply_ref(qp, w, z)
    l, n = z.shape
    b = qp.shape[1]
    np_ = round_up(n, bn)
    out = panel_apply_kernel(qp, pad_to(w, (b, np_)), pad_to(z, (l, np_)),
                             bn=bn, interpret=interpret,
                             emit_norms=emit_norms)
    if emit_norms:
        o, r2 = out
        return o[:, :n], r2[0, :n]
    return out[:, :n]
