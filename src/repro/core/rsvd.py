"""Randomized SVD built on the interpolative decomposition (paper ref [3]).

Given ``A ~= B P`` with ``B = A[:, J]`` (m x k) and ``P`` (k x n):

  1. thin-QR the tall panel:   B = Q_b R_b      (CholeskyQR2 — MXU-native)
  2. small dense SVD:          R_b P = U' S Vh  (k x n, k tiny)
  3. lift:                     U = Q_b U'

Total extra cost over the ID is O(mk^2 + nk^2 + k^3) — the paper's point
that the ID "can serve as the basis for fast methods for the SVD".
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ..kernels.common import full_precision
from .qr import cholesky_qr2
from .rid import rid
from .types import IDResult, SVDResult

__all__ = ["rsvd", "rsvd_from_id"]


@jax.jit
@full_precision
def rsvd_from_id(dec: IDResult) -> SVDResult:
    Qb, Rb = cholesky_qr2(dec.B.astype(dec.P.dtype))
    M = Rb @ dec.P                                   # (k, n), small
    U_small, S, Vh = jnp.linalg.svd(M, full_matrices=False)
    return SVDResult(U=Qb @ U_small, S=S, Vh=Vh)


def rsvd(key: jax.Array, A: jax.Array, k: int, *, l: Optional[int] = None,
         sketch_kind: str = "gaussian", qr_impl: str = "blocked",
         qr_panel: int = 32, qr_norm_recompute="auto") -> SVDResult:
    """Rank-``k`` randomized SVD of ``A`` via the ID.  ``qr_impl`` /
    ``qr_panel`` / ``qr_norm_recompute`` select and tune the pivoted-QR
    engine of the underlying ID (see ``core.qr``)."""
    return rsvd_from_id(rid(key, A, k, l=l, sketch_kind=sketch_kind,
                            qr_impl=qr_impl, qr_panel=qr_panel,
                            qr_norm_recompute=qr_norm_recompute))
