"""The benchmark's matrices: the paper's rank-``k`` Gaussian products,
made from the seed on the device.

``A = B0 @ P0`` with ``B0`` (m x k) and ``P0`` (k x n) standard Gaussian
(complex Gaussian for a complex dtype): "almost no exploitable
structure, other than their rank" (arXiv:1205.3830, section 5).  The
product runs at full precision; at a TPU's default a complex product
leaves full-rank noise near 1e-3 and the matrix is no longer rank ``k``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int) -> jax.Array:
    """A threefry key holding all 64 bits of ``seed`` (``jax.random.key``
    keeps only the low 32 bits, so two large seeds could collide)."""
    seed %= 1 << 64
    data = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(data, impl="threefry2x32")


@partial(jax.jit, static_argnames=("m", "n", "k", "dtype"))
def factors(key, m: int, n: int, k: int, dtype: str):
    """``(B0, P0)`` in ``dtype``."""
    dtype = jnp.dtype(dtype)
    rdt = jnp.finfo(dtype).dtype
    kb, kp, kbi, kpi = jax.random.split(key, 4)
    B0 = jax.random.normal(kb, (m, k), rdt)
    P0 = jax.random.normal(kp, (k, n), rdt)
    if jnp.issubdtype(dtype, jnp.complexfloating):
        B0 = B0 + 1j * jax.random.normal(kbi, (m, k), rdt)
        P0 = P0 + 1j * jax.random.normal(kpi, (k, n), rdt)
    return B0.astype(dtype), P0.astype(dtype)


@jax.jit
def _product(B0, P0):
    return jnp.matmul(B0, P0, precision=HIGHEST)


@partial(jax.jit, static_argnames=("m", "n", "k", "dtype"))
def device_matrix(key, m: int, n: int, k: int, dtype: str) -> jax.Array:
    """``A`` on the device, in one jitted call."""
    return _product(*factors(key, m, n, k, dtype))


def host_matrix(key, m: int, n: int, k: int, dtype: str,
                rows: int) -> np.ndarray:
    """``A`` in host memory, made on the device ``rows`` rows at a time so
    that the device never holds more than one block of it."""
    B0, P0 = factors(key, m, n, k, dtype)
    A = np.empty((m, n), jnp.dtype(dtype))
    for r0 in range(0, m, rows):
        A[r0:r0 + rows] = np.asarray(_product(B0[r0:r0 + rows], P0))
    return A
