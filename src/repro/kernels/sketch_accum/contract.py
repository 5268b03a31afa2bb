"""Static contract for the canonically-blocked sketch accumulator (see
``kernels.common.KernelContract`` for field semantics).

``ACCUM_BLOCK`` is pinned here: it is the replay constant the streamed /
in-memory bit-for-bit contract hangs on (``stream.rid_stream`` module
docstring) — a silent change would break every stored decomposition.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common import KernelContract

f32 = jnp.float32


def _example():
    # Paper grid row 1's sketch width (l = 200, n = 2^14) over one
    # 1024-row chunk: the VMEM check sees a real accumulator tile.
    from .ops import sketch_accum
    x = jax.ShapeDtypeStruct((200, 1024), f32)
    a = jax.ShapeDtypeStruct((1024, 2 ** 14), f32)
    return sketch_accum, (x, a), {}


def _bad_call():
    # x columns (64) disagree with a rows (128): ops.py must reject this
    # EAGERLY with both values named, before any pallas_call is built.
    from .ops import sketch_accum
    sketch_accum(jnp.ones((96, 64), f32), jnp.ones((128, 512), f32))


CONTRACT = KernelContract(
    name="sketch_accum",
    ops=("sketch_accum",),
    kernels=("sketch_accum_kernel",),
    refs=("sketch_accum_ref",),
    pairs=(("sketch_accum", "sketch_accum_ref"),),
    example=_example,
    constants={"ACCUM_BLOCK": 128},
    bad_call=_bad_call,
    measure_residency=True,
)
