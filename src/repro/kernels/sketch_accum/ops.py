"""Jit'd public wrapper for the accumulating sketch GEMM.

``sketch_accum`` is the ONE jit boundary both sketch paths share: the
in-memory ``gaussian_sketch`` calls it once over all of ``m``, the
streaming pipeline (``repro.stream``) calls it once per row chunk, and
because every call reduces in the same canonical ``ACCUM_BLOCK`` blocks
(kernel.py), the two produce bit-for-bit identical accumulators whenever
``chunk_rows`` is a multiple of ``ACCUM_BLOCK``.  Callers must NOT nest
it inside a larger jit when they rely on that replay guarantee — fusion
context could re-associate the adds.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..common import (LANE, SUBLANE, full_precision, interpret_default,
                      pad_to, round_up)
from .kernel import ACCUM_BLOCK, accum_tile, sketch_accum_kernel
from .ref import accum_dtype_for, sketch_accum_ref

__all__ = ["sketch_accum", "ACCUM_BLOCK", "accum_dtype_for"]


@partial(jax.jit, static_argnames=("interpret",))
@full_precision
def sketch_accum(x: jax.Array, a: jax.Array, acc: jax.Array | None = None, *,
                 interpret: bool | None = None) -> jax.Array:
    """``acc + x @ a`` in the accumulator dtype (``accum_dtype_for``), with
    the reduction over ``a``'s rows pinned to canonical ``ACCUM_BLOCK``
    blocks.  ``x``: (l, m) sketch-operator columns; ``a``: (m, n) row
    chunk; ``acc``: (l, n) running accumulator (``None`` = zeros).

    Real dtypes take the Pallas kernel (each ``acc`` column tile stays
    in VMEM across all blocks); complex falls back to the
    canonically-blocked ref like the other kernels (TPU has no complex
    MXU path).
    """
    interpret = interpret_default() if interpret is None else interpret
    l, m = x.shape
    m2, n = a.shape
    if m != m2:
        raise ValueError(f"x columns ({m}) must match a rows ({m2})")
    adt = accum_dtype_for(jnp.promote_types(x.dtype, a.dtype))
    if acc is None:
        acc = jnp.zeros((l, n), adt)
    if acc.shape != (l, n):
        raise ValueError(f"acc shape {acc.shape} must be {(l, n)}")
    acc = acc.astype(adt)
    if jnp.issubdtype(adt, jnp.complexfloating):
        return sketch_accum_ref(x.astype(adt), a.astype(adt), acc)
    # Pad the TPU tile dims (l -> sublane, n -> lane multiples) and the
    # reduction dim to whole canonical blocks.  The pads are zeros on
    # every call, so interior values are exact and chunk-invariant.
    lp, np_ = round_up(l, SUBLANE), round_up(n, LANE)
    mp = round_up(m, ACCUM_BLOCK)
    out = sketch_accum_kernel(pad_to(x, (lp, mp)), pad_to(a, (mp, np_)),
                              pad_to(acc, (lp, np_)),
                              bn=accum_tile(lp, np_, jnp.dtype(adt).itemsize),
                              interpret=interpret)
    return out[:l, :n]
