"""Operations and bytes that the algorithm defines for each call of the
panel-parallel pivoted QR's two kernels (``kernels/panel_step``:
``panel_coeff``, stage A, and ``panel_apply``, stage B), from the call's
shapes, as ``kernel_work`` does for the other kernels.  Each call works
on one chip's column shard ``Z`` (l x n) of the sketch, with the
replicated candidate panel ``C`` (l x b).  Real float32 data (4 bytes an
element) is what the kernels take.
"""
from __future__ import annotations

from bench.metrics.kernel_work import F32


def panel_coeff(l: int, b: int, n: int) -> tuple[float, float]:
    """Stage A of one panel: factor ``C`` and take the coefficients and
    the downdated pivot statistics.

    * CholeskyQR2 of ``C``: two Gram products (``2 l b^2`` each) and two
      triangular solves for ``Q_p`` (``l b^2`` each): ``6 l b^2``;
    * coefficients ``W = Q_p^T Z``: ``2 l b n``;
    * downdated norms ``res2 - colnorms^2(W)``: ``2 b n + n``.

    Bytes: ``C`` read and ``Q_p`` written (``l b`` each), the shard read
    (``l n``), ``W`` written (``b n``), and the norms read and written
    (``n`` each).
    """
    ops = 6.0 * l * b * b + 2.0 * l * b * n + 2.0 * b * n + n
    nbytes = F32 * (2 * l * b + l * n + b * n + 2 * n)
    return ops, nbytes


def panel_apply(l: int, b: int, n: int,
                norms: bool = False) -> tuple[float, float]:
    """Stage B of one panel: the deflation ``Z - Q_p W``: ``2 l b n``
    operations.  Bytes: ``Q_p`` (``l b``) and ``W`` (``b n``) read, the
    shard read and written (``l n`` each).  A norm-recompute panel
    (``norms``) also takes the deflated shard's column norms: ``2 l n``
    operations more, and ``n`` bytes more written.
    """
    ops = 2.0 * l * b * n + (2.0 * l * n if norms else 0.0)
    nbytes = F32 * (l * b + b * n + 2 * l * n + (n if norms else 0))
    return ops, nbytes
