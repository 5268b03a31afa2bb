"""Operations and bytes that the algorithm defines for each kernel call,
from the call's shapes.  They count the work the decomposition needs,
not what an implementation happens to do, so that a share of the
roofline reads the same work whatever implements it.  Real float32
data (4 bytes an element) is what the kernels take.
"""
from __future__ import annotations

F32 = 4


def sketch_accum(l: int, rows: int, n: int) -> tuple[float, float]:
    """``acc + Omega_c @ A_c`` for ``Omega_c`` (l x rows), ``A_c`` (rows x
    n): ``2 l rows n`` operations; bytes of ``Omega_c`` and ``A_c``
    read, and of the (l x n) accumulator read and written."""
    ops = 2.0 * l * rows * n
    nbytes = F32 * (l * rows + rows * n + 2 * l * n)
    return ops, nbytes


def panel_step(l: int, b: int, n: int) -> tuple[float, float]:
    """One fused panel of the blocked pivoted QR on an (l x n) slab ``Z``
    with ``b`` candidate columns ``C`` (l x b):

    * CholeskyQR2 of ``C``: two Gram products (``2 l b^2`` each) and two
      triangular solves for ``Q_p`` (``l b^2`` each): ``6 l b^2``;
    * coefficients ``W = Q_p^T Z``: ``2 l b n``;
    * deflation ``Z - Q_p W``: ``2 l b n``;
    * the next panel's column norms of the deflated slab: ``2 l n``.

    Bytes: ``C`` read and ``Q_p`` written (``l b`` each), the slab read
    and written (``l n`` each), and the norms written (``n``).
    """
    ops = 4.0 * l * b * n + 2.0 * l * n + 6.0 * l * b * b
    nbytes = F32 * (2 * l * b + 2 * l * n + n)
    return ops, nbytes


def roofline_s(ops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations over
    the published bf16 peak and bytes over the HBM bandwidth."""
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
