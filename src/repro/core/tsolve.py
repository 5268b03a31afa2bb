"""The interpolation solve (paper eq. 10): ``R1 T = R2`` with ``R1`` upper
triangular.

The paper's key observation is that the solve is INDEPENDENT per column
of ``R2`` — each XMT processor owned a column; on TPU each grid step of
the Pallas kernel (``repro.kernels.tsolve``) owns a column TILE, and in
the distributed path each device owns its local column shard with zero
communication.

``solve_upper_triangular`` is the pure-jnp oracle (row-recurrence back
substitution, vectorized across columns).  ``solve_upper_triangular_xla``
wraps the XLA builtin for comparison.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels.common import full_precision

__all__ = ["solve_upper_triangular", "solve_upper_triangular_xla", "interp_from_qr"]


@jax.jit
@full_precision
def solve_upper_triangular(R1: jax.Array, R2: jax.Array) -> jax.Array:
    """Back substitution: return ``T`` with ``triu(R1) @ T = R2``.

    R1: (k, k) (only the upper triangle is read), R2: (k, n).
    Row recurrence, all columns in parallel — paper section 2's
    "solve L v = w for triangular L", vectorized.
    """
    k = R1.shape[0]
    R1u = jnp.triu(R1)
    rdtype = jnp.finfo(R1.dtype).dtype

    def body(i_, T):
        i = k - 1 - i_
        row = R1u[i]                                  # (k,) zeros at < i by triu
        acc = row @ T                                  # includes diag*T[i] (T[i] still 0)
        diag = row[i]
        safe = jnp.where(jnp.abs(diag) > 0, diag,
                         jnp.asarray(jnp.finfo(rdtype).tiny, R1.dtype))
        Ti = (R2[i] - acc) / safe
        return T.at[i].set(Ti)

    T0 = jnp.zeros_like(R2)
    return lax.fori_loop(0, k, body, T0)


@jax.jit
@full_precision
def solve_upper_triangular_xla(R1: jax.Array, R2: jax.Array) -> jax.Array:
    """XLA's native TriangularSolve — the production fast path."""
    return jax.scipy.linalg.solve_triangular(jnp.triu(R1), R2, lower=False)


def interp_from_qr(R: jax.Array, piv: jax.Array, *, use_xla: bool = True) -> jax.Array:
    """Build the interpolation matrix ``P`` (paper eq. 11) from ``R = Q^H Y``.

    Solving against ALL of ``R`` (not just the non-pivot block ``R2``)
    yields ``P = R1^-1 R`` whose pivot columns are identity columns
    automatically — this sidesteps any dynamic complement-index gather
    under jit.  We then scatter an exact ``I_k`` into the pivot columns.
    """
    k = R.shape[0]
    R1 = jnp.take(R, piv, axis=1)                     # (k, k), upper-tri in pivot order
    solve = solve_upper_triangular_xla if use_xla else solve_upper_triangular
    P = solve(R1, R)
    return P.at[:, piv].set(jnp.eye(k, dtype=P.dtype))
