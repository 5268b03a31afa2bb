"""Jit'd wrapper for the fused panel-Gram kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..common import full_precision, interpret_default, pad_to, round_up
from .kernel import panel_gram_kernel
from .ref import panel_gram_ref

__all__ = ["panel_gram"]


@partial(jax.jit, static_argnames=("bn", "interpret"))
@full_precision
def panel_gram(c: jax.Array, z: jax.Array, *, bn: int = 128,
               interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """``(c^H c, c^H z)`` with ``c`` (l x b) a candidate panel and ``z``
    (l x n) the local residual shard — both products from ONE VMEM pass
    over ``z`` (the panel stays resident across slabs).  Real dtypes take
    the Pallas path; complex falls back to the oracle formula like the
    ``cgs`` kernels (the distributed production path is real)."""
    interpret = interpret_default() if interpret is None else interpret
    if jnp.issubdtype(z.dtype, jnp.complexfloating) or \
            jnp.issubdtype(c.dtype, jnp.complexfloating):
        return panel_gram_ref(c, z)
    l, n = z.shape
    np_ = round_up(n, bn)
    g, v = panel_gram_kernel(c, pad_to(z, (l, np_)), bn=bn, interpret=interpret)
    return g, v[:, :n]
