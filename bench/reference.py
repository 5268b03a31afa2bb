"""The plain reference that decides a run's ``correct``.

It imports nothing of the program under test and takes nothing it made:
the matrix comes from the benchmark's own generator (``matrices.py``),
and the factors ``B``, ``P``, ``J`` that a decomposition returned are
judged against that matrix alone.

An interpolative decomposition ``A ~= B P`` of rank ``k`` is right when

* ``J`` holds ``k`` distinct column indices of ``A``;
* ``B`` is exactly ``A[:, J]`` (the column gather copies values);
* ``P[:, J]`` is exactly the identity (paper eq. 11);
* ``||A - B P||_F / ||A||_F`` is at the level the configuration's
  precision gives, far below what a lower precision gives.

``reference_id`` is a plain randomized ID (Gaussian sketch, column-
pivoted Gram-Schmidt, triangular solve).  With ``low=True`` every
product rounds its operands to bfloat16 and accumulates in float32, as
a TPU does at its default matmul precision: that is the control, the
precision step below float32 that the check must refuse.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def matmul(a: jax.Array, b: jax.Array, low: bool = False) -> jax.Array:
    """``a @ b`` at full float32 precision, or with ``low`` both operands
    rounded to bfloat16 and the sum kept in float32 (complex operands
    as four real products)."""
    if not low:
        return jnp.matmul(a, b, precision=HIGHEST)
    if jnp.iscomplexobj(a) or jnp.iscomplexobj(b):
        ar, ai, br, bi = jnp.real(a), jnp.imag(a), jnp.real(b), jnp.imag(b)
        return (matmul(ar, br, True) - matmul(ai, bi, True)
                + 1j * (matmul(ar, bi, True) + matmul(ai, br, True)))
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _round_bf16(x: jax.Array) -> jax.Array:
    """``x`` with its (real and imaginary) parts rounded to bfloat16."""
    def r(v):
        return v.astype(jnp.bfloat16).astype(v.dtype)
    return r(jnp.real(x)) + 1j * r(jnp.imag(x)) if jnp.iscomplexobj(x) \
        else r(x)


# ------------------------------------------------------------------ check

@jax.jit
def _block_sums(a, b, p, j):
    """Sums over one block of rows: ``||a - b p||_F^2``, ``||a||_F^2``,
    and ``max |b - a[:, j]|`` (the gather)."""
    r = a - matmul(b, p)
    gather = jnp.max(jnp.abs(b - jnp.take(a, j, axis=1)))
    return jnp.sum(jnp.abs(r) ** 2), jnp.sum(jnp.abs(a) ** 2), gather


def _structure(P: np.ndarray, J: np.ndarray, n: int, k: int):
    """``(invalid pivots, max |P[:, J] - I|)`` on the host."""
    J = np.asarray(J)
    bad = int(J.shape != (k,)) or int(np.sum((J < 0) | (J >= n))
                                      + (k - np.unique(J).size))
    if bad:
        return bad, float("inf")
    return 0, float(np.max(np.abs(P[:, J] - np.eye(k, dtype=P.dtype))))


def check_factors(row_blocks, results, n: int, k: int) -> list[dict]:
    """Judge each ``(B, P, J)`` in ``results`` against ``A``.

    ``row_blocks`` yields ``(r0, r1, a)`` with ``a`` the rows
    ``[r0, r1)`` of ``A`` (on the device or on the host), covering all
    of ``A`` once; every result is checked in the same pass.  Returns
    one dict per result: ``pivots_invalid``, ``identity_err``,
    ``gather_err`` and ``rel_err``.
    """
    out, factors = [], []
    for B, P, J in results:
        P = np.asarray(P)
        bad, ident = _structure(P, J, n, k)
        out.append({"pivots_invalid": bad, "identity_err": ident,
                    "gather_err": 0.0, "err2": 0.0, "nrm2": 0.0})
        factors.append((B, jnp.asarray(P), jnp.asarray(np.asarray(J))))
    for r0, r1, a in row_blocks:
        a = jnp.asarray(a)
        for rec, (B, P, J) in zip(out, factors):
            if rec["pivots_invalid"]:
                continue
            e2, n2, g = _block_sums(a, jnp.asarray(B[r0:r1]), P, J)
            rec["err2"] += float(e2)
            rec["nrm2"] += float(n2)
            rec["gather_err"] = max(rec["gather_err"], float(g))
    for rec in out:
        e2, n2 = rec.pop("err2"), rec.pop("nrm2")
        rec["rel_err"] = (float("inf") if rec["pivots_invalid"]
                          else float(np.sqrt(e2) / np.sqrt(n2)))
    return out


# ----------------------------------------------------- reference ID (control)

@partial(jax.jit, static_argnames=("l", "low"))
def _sketch_block(key, r0, a, acc, l: int, low: bool):
    """``acc + Omega[:, r0:r0+rows] @ a`` with the Gaussian operator's
    columns drawn from ``fold_in(key, r0)``."""
    shape = (l, a.shape[0])
    kr, ki = jax.random.split(jax.random.fold_in(key, r0))
    om = jax.random.normal(kr, shape, jnp.float32)
    if jnp.iscomplexobj(a):
        om = om + 1j * jax.random.normal(ki, shape, jnp.float32)
    return acc + matmul(om.astype(a.dtype), a, low)


@partial(jax.jit, static_argnames=("k", "low"))
def _pivoted_qr_interp(Y, k: int, low: bool):
    """Column-pivoted Gram-Schmidt of ``Y`` (each step takes the column of
    largest residual norm, orthogonalises it twice against the basis,
    and deflates the rest), then ``P = R11^-1 R`` with ``P[:, J] = I``."""
    l, n = Y.shape
    rdt = jnp.finfo(Y.dtype).dtype

    def h(x):
        return jnp.conj(x).T

    def body(j, state):
        Z, Q, piv, picked = state
        norms = jnp.where(picked, -1.0, jnp.sum(jnp.abs(Z) ** 2, axis=0))
        p = jnp.argmax(norms).astype(jnp.int32)
        q = Z[:, p][:, None]
        for _ in range(2):
            q = q - matmul(Q, matmul(h(Q), q, low), low)
        q = q / jnp.linalg.norm(q).astype(rdt)
        Z = Z - matmul(q, matmul(h(q), Z, low), low)
        return (Z, Q.at[:, j].set(q[:, 0]), piv.at[j].set(p),
                picked.at[p].set(True))

    state = (Y, jnp.zeros((l, k), Y.dtype), jnp.zeros((k,), jnp.int32),
             jnp.zeros((n,), bool))
    _, Q, piv, _ = jax.lax.fori_loop(0, k, body, state)
    R = matmul(h(Q), Y, low)
    if low:
        R = _round_bf16(R)
    R11 = jnp.triu(jnp.take(R, piv, axis=1))
    with jax.default_matmul_precision("bfloat16" if low else "highest"):
        P = jax.scipy.linalg.solve_triangular(R11, R, lower=False)
    return P.at[:, piv].set(jnp.eye(k, dtype=P.dtype)), piv


def gather(row_blocks, J) -> np.ndarray:
    """``A[:, J]`` on the host, block by block."""
    J = np.asarray(J)
    return np.concatenate([np.asarray(a[:, J]) for _, _, a in row_blocks])


def reference_id(key, row_blocks, n: int, k: int, l: int, dtype,
                 low: bool = False):
    """Plain randomized ID of the matrix fed by ``row_blocks`` (``(r0,
    r1, a)`` triples, as in ``check_factors``): returns ``(P, J)``.
    ``B`` is the gather ``A[:, J]``, which the caller takes."""
    acc = jnp.zeros((l, n), dtype)
    for r0, _, a in row_blocks:
        acc = _sketch_block(key, r0, jnp.asarray(a), acc, l, low)
    P, J = _pivoted_qr_interp(acc, k, low)
    return P, J
