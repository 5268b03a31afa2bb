"""Distributed randomized ID under ``jax.shard_map`` — the paper's
parallelization (section 3.2) mapped onto a TPU mesh.

Layout: ``A`` is sharded BY COLUMNS over one mesh axis (the paper's
"each processor owns columns"; on the XMT this was loop-level, here it is
mesh-level).  Phase costs by ``qr_impl``:

  sketch        : zero communication — every backend acts on the row index
                  only, so each device sketches its own column block.
                  (Scope note: the streamed/in-memory BIT-FOR-BIT replay
                  contract of rid/rid_streamed does NOT extend here —
                  shard-local sketch GEMMs have different shapes than the
                  full-width one, and the whole body runs inside one jit.
                  THIS path's replay guarantee is the per-program one:
                  same key, same mesh -> same result, and the replicated
                  outputs bitwise identical on every device.)
  pivoted QR    :
    'cgs2' /    one ``all_gather`` of the ``l x n_local`` sketches, then
    'blocked'   REPLICATED factorization on every device.  Per device:
                O(l n) gathered bytes and memory, O(l k n) redundant flops.
                Fine while the sketch fits one device; it caps matrix size
                at a single device's HBM.
    'panel_     NO replication (``core.qr_dist``): each device factors its
    parallel'   own ``l x n_local`` shard in place through the fused
                panel-step kernel (``kernels/panel_step``).  Per PANEL of
                ``b`` pivots:
                  - one ``l x b`` psum gathering the owners' candidate
                    columns (each global column lives on one shard);
                  - stage A (one kernel sweep of the shard): in-kernel
                    CholeskyQR2 of the replicated panel + coefficient
                    block ``W`` + DOWNDATED residual norms;
                  - one psum of the n downdated norms — panel p+1's
                    pivot statistics, issued BEFORE the deflation and
                    data-independent of it, so the all-reduce OVERLAPS
                    the trailing GEMM (double-buffered collectives)
                    instead of serializing behind it;
                  - stage B: shard-local deflation ``Z -= Q_p W``.
                Per device: O(l n/ndev + l b) memory, O(l k n/ndev)
                flops, O(k/b * (n + l b)) communicated bytes total with
                the O(n) term latency-hidden — sketch width scales with
                the mesh, not one device.
  interp solve  : zero communication — each device solves ``R1 T = R2`` for
                  its own column block (paper: "column-wise in parallel").

The pivot-column gather ``B = A[:, J]`` is the only cross-shard data
motion proportional to ``m`` and moves just ``m x k`` elements.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels.common import full_precision
from .qr import (_h as _conj_t, pivoted_qr, resolve_norm_recompute,
                 resolve_panel)
from .qr_dist import (identity_at_owned_pivots,
                      panel_parallel_rid_interp_local)
from .sketch import sketch as _sketch
from .tsolve import solve_upper_triangular_xla
from .types import IDResult
from .validate import (check_divides, check_l_ge_k, check_panel,
                       check_rank_bounds)

__all__ = ["rid_distributed", "shard_columns"]

QR_IMPLS = ("cgs2", "blocked", "panel_parallel")


def shard_columns(A: jax.Array, mesh: Mesh, axis: str) -> jax.Array:
    """Place ``A`` column-sharded over ``axis`` (helper for callers/tests)."""
    return jax.device_put(A, NamedSharding(mesh, P(None, axis)))


def _local_rid_fn(k: int, l: int, sketch_kind: str, axis: str,
                  qr_impl: str, qr_panel: int, norm_recompute):
    """Per-device body for the REPLICATED-QR path; identical randomness on
    every device via a replicated key, so the replicated QR is bitwise
    identical too."""

    @full_precision
    def fn(key, A_loc):
        Y_loc = _sketch(key, A_loc, l, kind=sketch_kind).Y          # (l, n_loc), no comm
        Y = lax.all_gather(Y_loc, axis, axis=1, tiled=True)          # (l, n) full gather
        qr = pivoted_qr(Y, k, impl=qr_impl, panel=qr_panel,          # replicated compute
                        norm_recompute=norm_recompute)
        R1 = jnp.take(qr.R, qr.piv, axis=1)
        P_loc = solve_upper_triangular_xla(R1, _conj_t(qr.Q) @ Y_loc)  # no comm
        P_loc = identity_at_owned_pivots(P_loc, qr.piv, axis)
        return P_loc, qr.piv, qr.Q, qr.R

    return fn


def _local_rid_panel_parallel_fn(k: int, l: int, sketch_kind: str, axis: str,
                                 ndev: int, qr_panel: int, norm_recompute):
    """Per-device body for the PANEL-PARALLEL path: the sketch shard is
    factored in place and interpolated column-parallel — the shared
    ``core.qr_dist.panel_parallel_rid_interp_local`` body, with the
    shard-local sketch in front (no ``l x n`` array per device)."""

    @full_precision
    def fn(key, A_loc):
        Y_loc = _sketch(key, A_loc, l, kind=sketch_kind).Y           # (l, n_loc)
        return panel_parallel_rid_interp_local(
            Y_loc, k, axis=axis, ndev=ndev, panel=qr_panel,
            norm_recompute=norm_recompute)

    return fn


def rid_distributed(key: jax.Array, A: jax.Array, k: int, *,
                    mesh: Mesh, axis: str = "data",
                    l: Optional[int] = None,
                    sketch_kind: str = "gaussian",
                    qr_impl: str = "blocked",
                    qr_panel: int = 32,
                    qr_norm_recompute="auto") -> IDResult:
    """Rank-``k`` randomized ID of a column-sharded ``A``.

    Returns an ``IDResult`` whose ``P`` stays column-sharded over ``axis``
    and whose ``B`` is the gathered ``m x k`` pivot-column panel.
    ``qr_impl`` selects the pivoted-QR engine:

      'cgs2' / 'blocked'  — gather-and-replicate (the parity oracles; both
                            run identically on every device from the
                            bitwise-identical gathered sketch — see
                            ``core.qr``);
      'panel_parallel'    — factor the column shards in place with panel
                            pivots from psum-reduced norms and panel-sized
                            gathers (``core.qr_dist``) — no ``l x n``
                            sketch per device, so sketch width scales
                            with the mesh.  ``R`` comes back column-
                            sharded over ``axis`` instead of replicated.

    ``qr_panel`` is the panel width for 'blocked' and 'panel_parallel'
    (ignored by 'cgs2'); an int, or 'auto' for the fitted eq.(3)-drift
    width model (``core.qr.resolve_panel``).  ``qr_norm_recompute`` is
    the fused panel loop's exact-norm cadence ('auto' = every 8 panels,
    1 = every panel, 0 = never — ``core.qr.resolve_norm_recompute``); on
    'panel_parallel' it is what bounds the f32 downdate drift of the
    overlapped pivot psum (``core.qr_dist``).
    """
    l = 2 * k if l is None else l
    n = A.shape[1]
    check_l_ge_k(l, k)
    check_rank_bounds(k, l, n)
    if qr_impl not in QR_IMPLS:
        raise ValueError(f"unknown qr impl {qr_impl!r}; expected one of "
                         f"{QR_IMPLS}")
    qr_panel = resolve_panel(qr_panel, k, l)
    check_panel(qr_panel, name="qr_panel")
    resolve_norm_recompute(qr_norm_recompute)  # eager: reject before tracing
    ndev = mesh.shape[axis]
    check_divides(n, ndev, axis)

    if qr_impl == "panel_parallel":
        fn = _local_rid_panel_parallel_fn(k, l, sketch_kind, axis, ndev,
                                          qr_panel, qr_norm_recompute)
        r_spec = P(None, axis)       # R stays column-sharded, never gathered
    else:
        fn = _local_rid_fn(k, l, sketch_kind, axis, qr_impl, qr_panel,
                           qr_norm_recompute)
        r_spec = P()                 # R is replicated by the redundant QR
    # check_vma=False: the replicated outputs (piv, Q, and R on the
    # gather-and-replicate path) are bitwise identical on every device —
    # either recomputed from identical gathered inputs or produced by
    # collectives — but the rep-checker cannot prove it through the loop
    # carries.
    mapped = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(), P(None, axis)),
        out_specs=(P(None, axis), P(), P(), r_spec),
        check_vma=False,
    )
    P_sh, piv, Q, R = jax.jit(mapped)(key, A)
    B = jnp.take(A, piv, axis=1)                     # m x k cross-shard gather
    if jnp.issubdtype(P_sh.dtype, jnp.complexfloating) and not jnp.issubdtype(
            A.dtype, jnp.complexfloating):
        P_sh = P_sh.real.astype(A.dtype)
    return IDResult(B=B, P=P_sh, J=piv, Q=Q, R=R)


# ------------------------------------------------------------- analysis
# Registered contracts: both distributed RID paths at the canonical
# analyzer shape (m=64, n=400, k=12, l=2k=24, panel=7).  The
# panel-parallel path PROMISES no collective ever materializes l x n per
# device (budget l*n - 1); the gather-and-replicate path documents its
# one l x n all_gather as the allowed maximum (budget exactly l*n —
# anything bigger is a regression there too).

def _analysis_build_rid_distributed(qr_impl: str):
    def build():
        import numpy as np
        mesh = Mesh(np.array(jax.devices()), ("data",))

        def fn(key, A):
            return rid_distributed(key, A, 12, mesh=mesh, axis="data",
                                   sketch_kind="gaussian", qr_impl=qr_impl,
                                   qr_panel=7)
        return fn, (jax.random.key(0),
                    jax.ShapeDtypeStruct((64, 400), jnp.float32))
    return build


def _register_analysis_entries():
    from ..analysis.registry import register
    l, n = 24, 400
    register("rid_distributed.panel_parallel",
             _analysis_build_rid_distributed("panel_parallel"),
             max_collective_elems=l * n - 1)
    register("rid_distributed.blocked",
             _analysis_build_rid_distributed("blocked"),
             max_collective_elems=l * n)


_register_analysis_entries()
