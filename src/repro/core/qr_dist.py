"""Panel-parallel distributed pivoted QR of a column-sharded sketch.

The replicated path in ``core.distributed`` all-gathers the full ``l x n``
sketch onto every device and factors it redundantly — O(l n) replicated
memory and compute that caps the decomposable matrix size at one device's
HBM.  This module factors the sketch IN PLACE of replicating it, in the
communication-avoiding shape of parallel rank-revealing factorizations
(Heavner et al., arXiv:2104.05782; Yang/Meng/Mahoney, arXiv:1502.03032):

  * each device keeps only its ``l x n_local`` shard of ``Y`` and deflates
    only that shard — no ``l x n`` array ever materializes per device;
  * panel pivots are selected from ``psum``-reduced residual column norms
    (one n-length f32/f64 all-reduce per panel) with global-index
    bookkeeping, so every device agrees on the same global pivots;
  * the owning devices contribute their candidate columns via a b-sized
    ``psum`` gather (``l x panel`` — each global column lives on exactly
    one shard, so the sum IS the gather);
  * the panel step runs through ``kernels/panel_step``
    (``panel_impl="fused"``, the default): stage A factors the
    replicated candidate panel with in-kernel CholeskyQR2 and emits the
    coefficient block ``W = Q_p^H Z_loc`` PLUS the downdated residual
    norms (``res2 - colnorms^2(W)``, exact for an orthonormal panel) in
    one sweep of the shard; stage B applies the deflation
    ``Z_loc -= Q_p W``.
  * DOUBLE-BUFFERED COLLECTIVES: because stage A already yields the next
    panel's pivot statistics, the n-length norm psum for panel p+1 is
    issued BEFORE stage B of panel p — the all-reduce has no data
    dependence on the deflation GEMM, so XLA's scheduler overlays the
    collective with the largest per-panel compute instead of serializing
    behind it (the latency-hiding shape of Heavner et al.'s parallel
    UTV).  tests/test_qr_dist.py asserts the independence structurally
    on the lowering.

``panel_impl="gram"`` keeps the PR-2 split path (``kernels/panel_gram``
+ b x b triangular solves + XLA deflation, norms recomputed from the
deflated shard) as the in-place parity oracle; its psum chain is fully
serialized, which is exactly what the fused path's overlap removes.

Downdate vs recompute: the fused path's pivot norms are DOWNDATED
(GEQP3-style, like the cgs2 oracle's per-column loop) rather than
recomputed from the deflated shard — that is what frees the psum from
the deflation.  The clamped downdate is exact for an orthonormal panel
up to rounding, but the rounding compounds over k/panel panels, so on
fast-decaying spectra in f32 the tail panels' statistics can drown in
accumulated cancellation noise and pivot quality degrades relative to
the recomputing 'gram' oracle (late junk pivots cannot be detected by
the Q_p orthogonality check).  ``norm_recompute`` (default ``"auto"`` =
every 8 panels) bounds that drift WITHOUT re-serializing every
collective: on a recompute panel, stage B runs the
``panel_apply(..., emit_norms=True)`` kernel mode — the deflated shard's
TRUE column norms from the same fused pass — and the pivot psum is
issued from those exact statistics (through the SAME
``_scatter_res2_psum``), so only that 1-in-R psum waits on the
deflation; every other panel keeps the overlap.  The drift therefore
accumulates over at most one R-panel window instead of all k/panel
panels.  Pin ``norm_recompute=1`` for paper-parity runs (every panel
exact, fully serialized psums — the 'gram' oracle's freshness with the
fused kernel's memory traffic), ``0`` to never recompute
(tests/test_error_bounds.py measures exactly how far that drifts).

Per-device storage is ``O(l * n/ndev + l * panel)`` and per-panel
communication is ``O(n + l * panel)`` bytes — versus the replicated
engine's one-shot ``O(l * n)`` all-gather — with the ``O(n)`` half of
that hidden behind the deflation on the fused path.  That makes sketch
width (and hence matrix size) scale with the mesh instead of with a
single device's memory — the paper's 64 GB / 128-processor regime.

``panel_parallel_qr_local`` is the per-device body (composable inside an
existing ``shard_map``, e.g. ``rid_distributed``);
``panel_parallel_pivoted_qr`` is the standalone sharded entry point.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..kernels.common import full_precision
from ..kernels.panel_gram import panel_gram
from ..kernels.panel_step import panel_apply, panel_coeff
from ..obs import trace as obs_trace
from .qr import _h, householder_qr, resolve_norm_recompute
from .tsolve import solve_upper_triangular_xla
from .types import QRResult
from .validate import check_divides, check_panel, check_rank_bounds

__all__ = ["panel_parallel_pivoted_qr", "panel_parallel_qr_local",
           "panel_parallel_rid_interp_local", "gather_columns_psum",
           "identity_at_owned_pivots"]


def gather_columns_psum(Z_loc: jax.Array, idx: jax.Array, axis: str
                        ) -> jax.Array:
    """Gather GLOBAL columns ``idx`` from a column-sharded array: every
    device contributes the columns it owns (zeros elsewhere) and one
    ``psum`` replicates the ``l x b`` panel.  Each global column lives on
    exactly one shard, so the sum is an exact gather — the panel-sized
    replacement for the full-sketch all-gather."""
    n_loc = Z_loc.shape[1]
    off = lax.axis_index(axis).astype(jnp.int32) * n_loc
    loc = idx - off
    owned = (loc >= 0) & (loc < n_loc)
    cols = jnp.take(Z_loc, jnp.clip(loc, 0, n_loc - 1), axis=1)
    contrib = jnp.where(owned[None, :], cols, jnp.zeros((), Z_loc.dtype))
    return lax.psum(contrib, axis)


def _scatter_res2_psum(res2_loc: jax.Array, n: int, axis: str) -> jax.Array:
    """Assemble the replicated length-``n`` pivot statistics from each
    device's length-``n_loc`` masked local norms: scatter into the
    device's slot of a zero vector, one ``psum``.  On the fused path
    this psum is issued from downdated norms BEFORE the deflation runs —
    the double-buffered collective the module docstring describes."""
    n_loc = res2_loc.shape[0]
    off = lax.axis_index(axis).astype(jnp.int32) * n_loc
    contrib = lax.dynamic_update_slice(jnp.zeros((n,), res2_loc.dtype),
                                       res2_loc, (off,))
    return lax.psum(contrib, axis)


def _masked_local_res2(Z_loc: jax.Array, picked: jax.Array) -> jax.Array:
    """Local residual norms^2 with picked columns at the -1 sentinel."""
    rdtype = jnp.finfo(Z_loc.dtype).dtype
    res2_loc = jnp.sum(jnp.abs(Z_loc) ** 2, axis=0).astype(rdtype)
    return jnp.where(picked, jnp.asarray(-1.0, rdtype), res2_loc)


def _global_res2(Z_loc: jax.Array, picked: jax.Array, n: int, axis: str
                 ) -> jax.Array:
    """Replicated length-``n`` residual norms^2, recomputed from the
    deflated shard (the 'gram' oracle path; picked columns carry the -1
    sentinel from their owner, everyone else contributes 0 there)."""
    return _scatter_res2_psum(_masked_local_res2(Z_loc, picked), n, axis)


def _panel_qp_w(C: jax.Array, Z_loc: jax.Array
                ) -> tuple[jax.Array, jax.Array]:
    """CholeskyQR2 of the replicated candidate panel ``C`` (l x b) through
    the fused Gram pass, returning ``(Q_p, W = Q_p^H Z_loc)``.

    Round 1 factors the kernel's Gram (``Q_1 = C L_1^{-H}``) and maps the
    kernel's coefficient block with the same solve
    (``Q_1^H Z = L_1^{-1} C^H Z``); round 2 re-orthonormalizes from the
    COMPUTED ``Q_1`` (the Yamamoto correction — the Gram of the materialized
    ``Q_1`` carries the round-1 rounding the second factorization removes).
    ``Z_loc`` is touched exactly once, inside the kernel."""
    G, V = panel_gram(C, Z_loc)                    # one VMEM pass over Z_loc
    L1 = jnp.linalg.cholesky(G)                    # lower: G = L1 L1^H
    solve = partial(jax.scipy.linalg.solve_triangular, lower=True)
    Q1 = _h(solve(L1, _h(C)))                      # C L1^{-H}
    L2 = jnp.linalg.cholesky(_h(Q1) @ Q1)
    Qp = _h(solve(L2, _h(Q1)))                     # Q1 L2^{-H}
    W = solve(L2, solve(L1, V))                    # L2^-1 L1^-1 C^H Z = Qp^H Z
    return Qp, W


@full_precision
def panel_parallel_qr_local(Y_loc: jax.Array, k: int, *, axis: str,
                            ndev: int, panel: int = 32,
                            panel_impl: str = "fused",
                            norm_recompute="auto"
                            ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-device body of the panel-parallel pivoted QR; call INSIDE a
    ``shard_map`` over ``axis`` with ``Y_loc`` the device's ``l x n/ndev``
    column shard of the sketch.

    ``panel_impl="fused"`` (default) runs the panel step through
    ``kernels/panel_step`` with double-buffered collectives: stage A
    (factor + coefficients + downdated norms) feeds panel p+1's pivot
    psum BEFORE stage B (the shard deflation) runs, so the all-reduce
    overlaps the GEMM.  Every ``norm_recompute`` panels (``"auto"`` = 8,
    ``1`` = every panel, ``0`` = never) stage B instead emits the
    deflated shard's EXACT column norms (``panel_apply`` recompute mode)
    and the psum is issued from those — bounding the f32 downdate drift
    while serializing only that panel's collective (module docstring).
    ``panel_impl="gram"`` keeps the PR-2 split path (``panel_gram`` +
    solves + XLA deflation, norms recomputed from the deflated shard) as
    the serialized parity oracle; it recomputes every panel by
    construction and ignores ``norm_recompute``.

    Returns ``(Q, piv, R_loc)``: ``Q`` (l x k) and the global pivot
    indices ``piv`` (k,) are bitwise identical on every device (all inputs
    to their computation arrive through collectives), ``R_loc = Q^H Y_loc``
    (k x n_loc) stays sharded.
    """
    l, n_loc = Y_loc.shape
    check_rank_bounds(k, l, n_loc * ndev, ctx="panel_parallel_qr_local: ")
    check_panel(panel, ctx="panel_parallel_qr_local: ")
    if panel_impl not in ("fused", "gram"):
        raise ValueError(f"panel_parallel_qr_local: unknown panel_impl "
                         f"{panel_impl!r}; expected 'fused' or 'gram'")
    recompute_every = resolve_norm_recompute(norm_recompute)
    n = n_loc * ndev
    dtype = Y_loc.dtype
    rdtype = jnp.finfo(dtype).dtype

    Q = jnp.zeros((l, k), dtype)
    piv = jnp.zeros((k,), jnp.int32)
    picked = jnp.zeros((n_loc,), bool)
    off = lax.axis_index(axis).astype(jnp.int32) * n_loc
    Z = Y_loc
    pos = 0
    if panel_impl == "fused":
        # Prologue psum: panel 0's statistics from the undeflated shard.
        res2_loc = _masked_local_res2(Z, picked)
        res2_g = _scatter_res2_psum(res2_loc, n, axis)
        p_i = 0                                # panel counter (recompute cadence)
        while pos < k:                         # static unroll: k/panel panels
            b = min(panel, k - pos)
            # 1. pivots from the psum issued LAST panel (double buffer).
            _, idx = lax.top_k(res2_g, b)
            idx = idx.astype(jnp.int32)
            # 2. candidate gather: l x b psum, owners contribute columns.
            C = gather_columns_psum(Z, idx, axis)
            if pos:
                C = C - Q[:, :pos] @ (_h(Q[:, :pos]) @ C)
            # 3. stage A: in-kernel CholeskyQR2 of the replicated panel +
            #    coefficient block + downdated norms, one shard sweep.
            #    (Replicated C in -> bitwise-identical Q_p on every device.)
            Qp, W, r2d = panel_coeff(C, Z, res2_loc)
            # Rank-deficient panels (noise-floor candidates) break the
            # in-kernel cholesky into junk factors; fall back to Householder
            # on the replicated panel, which completes junk directions
            # orthonormally.  Generic sketches never take this branch.
            err = jnp.max(jnp.abs(_h(Qp) @ Qp - jnp.eye(b, dtype=dtype)))
            ok = jnp.all(jnp.isfinite(Qp)) & \
                (err < jnp.sqrt(jnp.finfo(rdtype).eps))

            def _fallback(C=C, Z=Z, res2_loc=res2_loc):
                Qf = householder_qr(C)[0]
                Wf = _h(Qf) @ Z
                dd = jnp.sum(jnp.abs(Wf) ** 2, axis=0).astype(rdtype)
                return Qf, Wf, jnp.maximum(res2_loc - dd,
                                           jnp.zeros((), rdtype))

            Qp, W, r2d = lax.cond(
                ok, lambda Qp=Qp, W=W, r2d=r2d: (Qp, W, r2d), _fallback)
            # 4. bookkeeping for the pivot set everyone agreed on.
            loc = idx - off
            picked = picked.at[jnp.clip(loc, 0, n_loc - 1)].max(
                (loc >= 0) & (loc < n_loc))
            p_i += 1
            if recompute_every and p_i % recompute_every == 0 and pos + b < k:
                # RECOMPUTE panel: stage B emits the deflated shard's
                # exact column norms from the same fused pass, and the
                # pivot psum is issued from those — drift resets to zero
                # at the cost of serializing THIS panel's collective.
                Z, r2x = panel_apply(Qp, W, Z, emit_norms=True)
                res2_loc = jnp.where(picked, jnp.asarray(-1.0, rdtype),
                                     r2x.astype(rdtype))
                res2_g = _scatter_res2_psum(res2_loc, n, axis)
            else:
                # ISSUE panel p+1's pivot psum from the DOWNDATED norms —
                # its inputs are (W, picked), NOT the deflated shard, so
                # the collective is independent of stage B below and
                # overlaps it.
                res2_loc = jnp.where(picked, jnp.asarray(-1.0, rdtype), r2d)
                res2_g = _scatter_res2_psum(res2_loc, n, axis)
                # 5. stage B: deflate OWN shard — the GEMM the psum hides
                #    behind.
                Z = panel_apply(Qp, W, Z)
            Q = Q.at[:, pos:pos + b].set(Qp)
            piv = piv.at[pos:pos + b].set(idx)
            pos += b
        R_loc = _h(Q) @ Y_loc                  # exact recompute, oracle contract
        return Q, piv, R_loc
    while pos < k:                             # static unroll: k/panel panels
        b = min(panel, k - pos)
        # 1. global pivot selection from psum-reduced norms (n floats).
        res2 = _global_res2(Z, picked, n, axis)
        _, idx = lax.top_k(res2, b)
        idx = idx.astype(jnp.int32)
        # 2. candidate gather: l x b psum, owners contribute their columns.
        C = gather_columns_psum(Z, idx, axis)
        # 3. project off the prior basis (replicated l x k GEMMs) and
        #    orthonormalize with CholeskyQR2 via the fused Gram kernel.
        if pos:
            C = C - Q[:, :pos] @ (_h(Q[:, :pos]) @ C)
        Qp, W = _panel_qp_w(C, Z)
        # Rank-deficient panels (noise-floor candidates) break the Gram
        # cholesky; fall back to Householder on the replicated panel, which
        # completes junk directions orthonormally.  Generic sketches never
        # take this branch.
        err = jnp.max(jnp.abs(_h(Qp) @ Qp - jnp.eye(b, dtype=dtype)))
        ok = jnp.all(jnp.isfinite(Qp)) & (err < jnp.sqrt(jnp.finfo(rdtype).eps))

        def _fallback(C=C, Z=Z):
            Qf = householder_qr(C)[0]
            return Qf, _h(Qf) @ Z

        Qp, W = lax.cond(ok, lambda Qp=Qp, W=W: (Qp, W), _fallback)
        # 4. deflate OWN shard only; bookkeeping stays replicated.
        Z = Z - Qp @ W
        loc = idx - off
        picked = picked.at[jnp.clip(loc, 0, n_loc - 1)].max(
            (loc >= 0) & (loc < n_loc))
        Q = Q.at[:, pos:pos + b].set(Qp)
        piv = piv.at[pos:pos + b].set(idx)
        pos += b
    R_loc = _h(Q) @ Y_loc                      # exact recompute, oracle contract
    return Q, piv, R_loc


def identity_at_owned_pivots(P_loc: jax.Array, piv: jax.Array, axis: str
                             ) -> jax.Array:
    """Exact-identity scatter for pivot columns that live in this shard:
    the interpolation matrix at the pivot columns is the identity by
    construction, so write it exactly instead of through the solve's
    roundoff."""
    n_loc = P_loc.shape[1]
    off = lax.axis_index(axis) * n_loc
    cols = off + jnp.arange(n_loc, dtype=jnp.int32)
    match = cols[None, :] == piv[:, None]                    # (k, n_loc)
    return jnp.where(match.any(axis=0)[None, :], match.astype(P_loc.dtype),
                     P_loc)


@full_precision
def panel_parallel_rid_interp_local(Y_loc: jax.Array, k: int, *, axis: str,
                                    ndev: int, panel: int = 32,
                                    panel_impl: str = "fused",
                                    norm_recompute="auto"
                                    ) -> tuple[jax.Array, jax.Array,
                                               jax.Array, jax.Array]:
    """Per-device QRCP + interpolation body: the sharded twin of
    ``core.rid._qr_interp`` — call INSIDE a ``shard_map`` over ``axis``
    with ``Y_loc`` the device's ``l x n/ndev`` column shard of the
    sketch.  Composes :func:`panel_parallel_qr_local` with the
    column-parallel interpolation solve:

      * ``R1 = Q^H Y[:, piv]`` is exactly the pivot columns of the
        sharded ``R`` — a ``k x k`` psum gather, no extra GEMM;
      * each device solves ``R1 P_loc = R_loc`` for its OWN column block
        (zero communication — the paper's "column-wise in parallel");
      * pivot columns the shard owns are written as exact identity.

    Returns ``(P_loc, piv, Q, R_loc)``: ``piv``/``Q`` replicated
    (bitwise identical on every device), ``P_loc`` (k x n_loc) and
    ``R_loc`` (k x n_loc) column-sharded.  Both ``rid_distributed``'s
    panel-parallel path and the sharded ``stream.rid_streamed`` wrap
    exactly this body — the device-side program of the n-axis is ONE
    function regardless of where the m-axis lives (HBM or a chunk
    stream).
    """
    Q, piv, R_loc = panel_parallel_qr_local(
        Y_loc, k, axis=axis, ndev=ndev, panel=panel, panel_impl=panel_impl,
        norm_recompute=norm_recompute)
    R1 = gather_columns_psum(R_loc, piv, axis)
    P_loc = solve_upper_triangular_xla(R1, R_loc)            # no comm
    P_loc = identity_at_owned_pivots(P_loc, piv, axis)
    return P_loc, piv, Q, R_loc


def panel_parallel_pivoted_qr(Y: jax.Array, k: int, *, mesh: Mesh,
                              axis: str = "data", panel: int = 32,
                              panel_impl: str = "fused",
                              norm_recompute="auto") -> QRResult:
    """Standalone sharded entry point: pivoted thin QR of a column-sharded
    wide sketch ``Y`` (l x n) without ever materializing ``l x n`` on one
    device.  ``panel_impl`` picks the per-panel engine ('fused' — the
    double-buffered kernel default — or 'gram', the PR-2 split oracle)
    and ``norm_recompute`` the fused path's exact-norm cadence ('auto' =
    every 8 panels; see ``panel_parallel_qr_local``).  Returns
    ``QRResult(Q, R, piv)`` with ``Q``/``piv`` replicated and ``R``
    column-sharded over ``axis`` — the same contract as
    ``core.qr.pivoted_qr`` up to panel-granularity pivot order.

    OBSERVABILITY: the panel loop runs inside shard_map+jit, so no host
    timer can see individual panels without planting syncs in traced
    code.  Instead the whole call gets ONE device-bracketed span
    (``qr.panel_parallel``) carrying, as span events, the per-panel psum
    schedule the cadence determines statically on the host —
    ``psum="overlapped"`` (downdated norms, collective hides behind the
    deflation GEMM) vs ``"serialized"`` (exact-norm recompute panel, or
    every 'gram' panel) — plus a ``qr.recompute_panels`` counter.  Under
    ``obs.trace.deep_tracing()`` the call is also lowered/compiled first
    and the HLO's summed collective payload is recorded as
    ``qr.collective_bytes`` (compile-time analysis, not a wire capture).
    """
    l, n = Y.shape
    check_rank_bounds(k, l, n, ctx="panel_parallel_pivoted_qr: ")
    check_panel(panel, ctx="panel_parallel_pivoted_qr: ")
    if panel_impl not in ("fused", "gram"):
        raise ValueError(f"panel_parallel_pivoted_qr: unknown panel_impl "
                         f"{panel_impl!r}; expected 'fused' or 'gram'")
    recompute_every = resolve_norm_recompute(norm_recompute)  # eager reject
    ndev = mesh.shape[axis]
    check_divides(n, ndev, axis, ctx="panel_parallel_pivoted_qr: ")

    fn = partial(panel_parallel_qr_local, k=k, axis=axis, ndev=ndev,
                 panel=panel, panel_impl=panel_impl,
                 norm_recompute=norm_recompute)
    mapped = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(None, axis),),
        out_specs=(P(), P(), P(None, axis)),
        check_vma=False,
    )
    jitted = jax.jit(mapped)
    with obs_trace.span("qr.panel_parallel", l=l, n=n, k=k, panel=panel,
                        panel_impl=panel_impl, ndev=ndev) as sp:
        if obs_trace.current_tracer() is not None:
            recompute_ctr = obs_trace.counter("qr.recompute_panels")
            p_i = pos = 0
            while pos < k:                 # mirror of the loop inside jit
                b = min(panel, k - pos)
                p_i += 1
                serialized = panel_impl == "gram" or bool(
                    recompute_every and p_i % recompute_every == 0
                    and pos + b < k)
                sp.event("qr.panel_schedule", panel=p_i - 1, off=pos,
                         width=b,
                         psum="serialized" if serialized else "overlapped")
                if serialized and panel_impl == "fused":
                    recompute_ctr.add(1)
                pos += b
            if obs_trace.deep_tracing():
                from ..analysis.hlo import collective_bytes
                compiled = jitted.lower(Y).compile()
                obs_trace.counter("qr.collective_bytes").add(float(sum(
                    collective_bytes(compiled.as_text()).values())))
        Q, piv, R = jitted(Y)
        sp.block_on((Q, piv, R))
    return QRResult(Q=Q, R=R, piv=piv)


# ------------------------------------------------------------- analysis
# Registered contracts (repro.analysis): the fused path PROMISES the
# double-buffered-collectives schedule (module docstring) — the analyzer
# re-proves it on every CI run; the gram path is registered as the
# serialized positive control (expect_overlap=False: the analyzer must
# DETECT its serialization or fail its own control).  48x400, k=21,
# panel=7 => 3 panels; 400 divides both 1 (in-process) and 8 (CI) devs.

def _analysis_build(panel_impl: str):
    def build():
        import numpy as np
        l, n, k, b = 48, 400, 21, 7
        mesh = Mesh(np.array(jax.devices()), ("data",))
        ndev = mesh.shape["data"]
        fn = partial(panel_parallel_qr_local, k=k, axis="data", ndev=ndev,
                     panel=b, panel_impl=panel_impl)
        mapped = jax.shard_map(fn, mesh=mesh, in_specs=(P(None, "data"),),
                               out_specs=(P(), P(), P(None, "data")),
                               check_vma=False)
        return mapped, (jax.ShapeDtypeStruct((l, n), jnp.float32),)
    return build


def _register_analysis_entries():
    from ..analysis.registry import OverlapSpec, register
    l, n = 48, 400
    register("panel_parallel_qr_local.fused", _analysis_build("fused"),
             overlap=OverlapSpec(norm_shape=(n,), deflate="panel_apply"),
             max_collective_elems=l * n - 1)
    register("panel_parallel_qr_local.gram", _analysis_build("gram"),
             overlap=OverlapSpec(norm_shape=(n,), deflate="sub",
                                 deflate_shape=(l, -1),
                                 expect_overlap=False),
             max_collective_elems=l * n - 1, tags=("control",))


_register_analysis_entries()
