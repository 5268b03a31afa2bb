"""Accumulating sketch GEMM: ``acc + X @ A`` with a CANONICAL reduction
order — the kernel the streaming RID's replay guarantee hangs on.

The streamed sketch is ``Y = sum_c Phi_c A_c`` over row chunks of ``A``.
Floating-point addition is not associative, so a naive per-chunk GEMM
would make the sketch bits depend on ``chunk_rows`` — and break the
bit-for-bit replay contract ``rid``'s docstring promises.  This kernel
pins ONE association for every caller: the reduction over ``A``'s rows
always proceeds in fixed ``ACCUM_BLOCK``-row blocks, sequentially, with
one ``(l, B) x (B, bn)`` MXU dot + one add per block.  Any partition of
the rows at ``ACCUM_BLOCK`` multiples therefore replays the identical
rounding sequence — streamed chunk-at-a-time or in one in-memory call.

Blocking:

  grid = (n/bn, m/B)  — axis 0 ("parallel") walks column tiles of
                        ``bn`` lanes; axis 1 ("arbitrary", innermost) is
                        the sequential block reduction.  Each ``l x bn``
                        accumulator tile stays resident in VMEM across
                        its whole reduction and is written back once.

Every output column is reduced on its own, in the same block order, so
the column tiling changes no bits: a column-sharded caller
(``rid_streamed(mesh=...)``) gets exactly the full-width columns.

VMEM per step (double-buffered): ``2 (l B + B bn + 2 l bn)`` accumulator
words.  ``accum_tile`` picks the widest ``bn`` (a multiple of 128 lanes
dividing the padded width, at most ``MAX_BN``) whose working set fits
``VMEM_BUDGET_BYTES`` — 1024 lanes at the paper's l = 200 and 128 at
l = 2000 — so the kernel compiles for a v5e at every paper width; the
m dimension never materializes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import LANE, VMEM_BUDGET_BYTES

# The canonical reduction block (rows of A per accumulate step).  This is
# a REPLAY CONSTANT, not a tuning knob: streamed and in-memory sketches
# are bit-for-bit identical only because both sides reduce in exactly
# these blocks, so changing it changes every gaussian-sketch result.
# 128 = the MXU lane width (full-throughput contraction on TPU).
ACCUM_BLOCK = 128

# Widest column tile; wider tiles only lengthen each step.
MAX_BN = 2048


def accum_tile(l: int, n: int, itemsize: int) -> int:
    """Column tile ``bn`` for a padded ``(l, n)`` accumulator: the widest
    multiple of ``LANE`` that divides ``n``, is at most ``MAX_BN``, and
    keeps the double-buffered step within ``VMEM_BUDGET_BYTES`` (never
    below one lane tile)."""
    assert n % LANE == 0, (n, LANE)
    best = LANE
    for bn in range(LANE, min(n, MAX_BN) + 1, LANE):
        step = 2 * itemsize * (l * ACCUM_BLOCK + ACCUM_BLOCK * bn + 2 * l * bn)
        if n % bn == 0 and step <= VMEM_BUDGET_BYTES:
            best = bn
    return best


def _accum_kernel(x_ref, a_ref, acc_ref, o_ref):
    @pl.when(pl.program_id(1) == 0)
    def _load():
        o_ref[...] = acc_ref[...]

    o_ref[...] += jnp.dot(x_ref[...], a_ref[...],
                          preferred_element_type=o_ref.dtype)


def sketch_accum_kernel(x: jax.Array, a: jax.Array, acc: jax.Array, *,
                        bn: int, interpret: bool = True) -> jax.Array:
    """Raw pallas_call.  Requires pre-padded shapes: ``ACCUM_BLOCK | m``,
    ``bn | n``; ``x`` (l, m), ``a`` (m, n), ``acc`` (l, n) in the
    accumulator dtype."""
    l, m = x.shape
    m2, n = a.shape
    assert m == m2 and acc.shape == (l, n), (x.shape, a.shape, acc.shape)
    assert m % ACCUM_BLOCK == 0 and n % bn == 0, (m, n, bn)
    return pl.pallas_call(
        _accum_kernel,
        grid=(n // bn, m // ACCUM_BLOCK),
        in_specs=[
            pl.BlockSpec((l, ACCUM_BLOCK), lambda i, j: (0, j)),
            pl.BlockSpec((ACCUM_BLOCK, bn), lambda i, j: (j, i)),
            pl.BlockSpec((l, bn), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((l, bn), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((l, n), acc.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, a, acc)
