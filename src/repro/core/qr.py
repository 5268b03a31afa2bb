"""QR factorizations of the sketch (paper eq. 8-9).

The paper's stability choice is the *iterated classical Gram-Schmidt*
(CGS2) with greedy column pivoting [Bjorck '94, Lingen '00, Hoffman '89]:
classical (not modified) GS so every projection is a dense matvec that
parallelizes, iterated (a second orthogonalization pass) for stability.
That is ``cgs2_pivoted_qr`` below, expressed as a ``lax.fori_loop`` whose
body is three GEMV-shaped contractions — exactly the shape the XMT ran
thread-per-element and a TPU runs on the VPU/MXU.

Beyond-paper options (DESIGN.md section 2):

* ``householder_qr``  — the paper's own "would be ~2x faster" suggestion,
  for the tall-skinny panel ``Y[:, piv]``.
* ``cholesky_qr2``    — two rounds of ``Q = Y @ chol(Y^H Y)^-H``; turns
  orthonormalization into pure MXU matmuls (the TPU-native winner for
  well-conditioned panels, used by the RSVD path).
* ``blocked_pivoted_qr`` — the production pivoted factorization: pivots
  are selected a PANEL (default 32 columns) at a time by residual norm,
  each panel is orthonormalized with the tall-panel routines above, and
  the trailing residual is deflated with ONE GEMM pair per panel
  (``Z -= Q_p (Q_p^H Z)``) instead of one rank-1 update per column.
  Same O(lkn) flops as CGS2, but MXU/GEMM-bound instead of VPU/GEMV-
  bound, and k/b trailing updates instead of k.

The blocked engine's per-panel work runs, by default, as the single
fused Pallas kernel ``kernels/panel_step`` (``panel_impl="fused"``): one
VMEM residency of each residual slab produces the orthonormal panel
(in-kernel CholeskyQR2), the coefficient block, the deflated slab, AND
the next panel's residual norms — where the split path re-reads the
residual from HBM for the Gram, again for the deflation, and a third
time for the norm recompute at the next panel's top.  The split
``panel_impl`` spellings ('auto' | 'chol' | 'house') remain as parity
oracles and benchmark references.

Panel width vs eq.(3) quality: wider panels mean fewer (GEMM-bound)
trailing updates but rank the whole panel from ONE set of residual
norms, so pivot quality drifts from the per-column oracle as the ratio
``panel * k / l`` grows.  ``pivoted_qr(..., panel="auto")`` resolves the
width through a model FITTED to measured eq.(3) bound-constant drift
(``benchmarks/bench_error.py --grid`` sweeps k x l x panel and records
the per-width bound ratios into ``BENCH_scaling.json``): the widest
power-of-two panel with ``panel * k / l <= _WIDTH_TAU`` is safe, so at
the paper's universal oversampling ``l = 2k`` the model picks 16 —
including the measured k ~ 100 cliff where ``panel=32`` exceeds the
paper's bound by ~2x while 16 stays ~10x inside it — and relaxes to
32/64 only when the oversampling ratio ``l/k`` leaves slack.  See
``resolve_panel``.

Residual-norm freshness (``norm_recompute``): the fused kernel here
recomputes each panel's statistics exactly from the freshly deflated
slab, so this engine never accumulates downdate error.  The DISTRIBUTED
engine (core.qr_dist) is different: it downdates the norms GEQP3-style
so its pivot psum can overlap the deflation, which accumulates f32
cancellation noise on fast-decaying spectra.  ``norm_recompute``
(default ``"auto"`` = every 8 panels; ``1`` = every panel, the
paper-parity pin; ``0`` = never) sets the cadence at which that engine
inserts an exact recompute panel (the ``panel_apply(...,
emit_norms=True)`` kernel mode, serializing only that one panel's
psum); it is accepted on both engines for one API shape and validated
by ``resolve_norm_recompute``.  tests/test_error_bounds.py bounds the
drift on a verification grid of spectra x dtypes x impls.

Callers choose via ``pivoted_qr(Y, k, impl=...)`` with
``impl in {"cgs2", "blocked"}`` — ``cgs2`` is the paper-faithful parity
oracle, ``blocked`` the fast path.  ``rid``/``rsvd``/``rid_distributed``
expose the same switch as ``qr_impl``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels.common import full_precision
from ..kernels.panel_step import panel_step
from ..obs import trace as obs_trace
from .types import QRResult
from .validate import check_panel, check_rank_bounds

__all__ = ["cgs2_pivoted_qr", "blocked_pivoted_qr", "pivoted_qr",
           "householder_qr", "cholesky_qr2", "resolve_panel",
           "resolve_norm_recompute"]


def _h(x: jax.Array) -> jax.Array:
    """Conjugate transpose that is a plain transpose for real dtypes."""
    return x.conj().T if jnp.issubdtype(x.dtype, jnp.complexfloating) else x.T


def _masked_res2(Z: jax.Array, picked: jax.Array, rdtype) -> jax.Array:
    """Residual column norms^2 with picked columns at the -1 sentinel."""
    res2 = jnp.sum(jnp.abs(Z) ** 2, axis=0).astype(rdtype)
    return jnp.where(picked, jnp.asarray(-1.0, rdtype), res2)


def _downdate_res2(res2: jax.Array, w: jax.Array, p: jax.Array) -> jax.Array:
    """Downdate norms^2 after selecting pivot ``p`` with coefficients
    ``w = Z^H q``.  Picked columns carry a negative sentinel that the
    downdate must PRESERVE (clamping them to 0 would re-admit them — a
    duplicate pivot — once every live residual hits the noise floor)."""
    rdtype = res2.dtype
    res2 = jnp.where(res2 < 0, res2,
                     jnp.maximum(res2 - jnp.abs(w) ** 2,
                                 jnp.zeros((), rdtype)))
    return res2.at[p].set(jnp.asarray(-1.0, rdtype))


@partial(jax.jit, static_argnames=("k",))
@full_precision
def cgs2_pivoted_qr(Y: jax.Array, k: int) -> QRResult:
    """Greedy-pivoted CGS2 thin QR of the wide sketch ``Y`` (l x n).

    Selects ``k`` columns by largest residual norm (the permutation ``Pi``
    the paper folds into the randomization), orthonormalizes each against
    the running basis TWICE (the "iteration" of iterated CGS), and
    deflates the residual ``Z <- Z - q q^H Z`` so the next pivot reflects
    the remaining energy.

    Returns ``QRResult(Q, R, piv)`` with ``R = Q^H Y`` recomputed exactly
    at the end, so ``R[:, piv]`` is upper triangular up to orthogonalization
    error and ``Y[:, piv] ~= Q @ triu(R[:, piv])``.
    """
    l, n = Y.shape
    check_rank_bounds(k, l, n)
    dtype = Y.dtype
    rdtype = jnp.finfo(dtype).dtype

    def body(j, state):
        Q, piv, Z, res2 = state
        p = jnp.argmax(res2).astype(jnp.int32)
        v = lax.dynamic_slice_in_dim(Z, p, 1, axis=1)[:, 0]
        # Pass 1: Z is already orthogonal to Q[:, :j] (deflated below), so
        # normalizing the residual column IS the classical GS step.
        v = v / jnp.maximum(jnp.linalg.norm(v), jnp.finfo(rdtype).tiny).astype(dtype)
        # Pass 2 ("iterated"): re-orthogonalize against the basis built so
        # far; columns >= j of Q are still zero so the masked GEMV is safe.
        c = _h(Q) @ v
        v = v - Q @ c
        v = v / jnp.maximum(jnp.linalg.norm(v), jnp.finfo(rdtype).tiny).astype(dtype)
        Q = lax.dynamic_update_slice_in_dim(Q, v[:, None], j, axis=1)
        piv = piv.at[j].set(p)
        # Deflate: one rank-1 update across all columns (the column-parallel
        # work unit the XMT ran one-thread-per-column).
        w = _h(Z) @ v                      # (n,) coefficients Z^H q
        Z = Z - v[:, None] * w.conj()[None, :]
        res2 = _downdate_res2(res2, w, p)  # sentinel-preserving: never re-pick
        return Q, piv, Z, res2

    Q0 = jnp.zeros((l, k), dtype)
    piv0 = jnp.zeros((k,), jnp.int32)
    res2_0 = jnp.sum(jnp.abs(Y) ** 2, axis=0).astype(rdtype)
    Q, piv, _, _ = lax.fori_loop(0, k, body, (Q0, piv0, Y, res2_0))
    R = _h(Q) @ Y
    return QRResult(Q=Q, R=R, piv=piv)


@jax.jit
@full_precision
def householder_qr(Y: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Compact-WY-free Householder thin QR of a TALL panel (l x k, l >= k).

    The paper learned post-hoc that Householder reflections halve the GS
    runtime at equal stability; we provide it for the panel factorization
    benchmark (benchmarks/bench_qr.py).  Returns ``(Q, R)`` with ``Q``
    l x k orthonormal and ``R`` k x k upper triangular.
    """
    l, k = Y.shape
    dtype = Y.dtype
    rdtype = jnp.finfo(dtype).dtype

    def body(j, state):
        A, V = state
        col = A[:, j]
        idx = jnp.arange(l)
        tail = jnp.where(idx >= j, col, jnp.zeros((), dtype))
        sigma = jnp.linalg.norm(tail).astype(dtype)
        ajj = col[j]
        # phase(ajj): keep complex-safe sign choice for stability
        absa = jnp.abs(ajj)
        phase = jnp.where(absa > 0, ajj / jnp.maximum(absa, jnp.finfo(rdtype).tiny).astype(dtype),
                          jnp.ones((), dtype))
        alpha = -phase * sigma
        v = tail.at[j].add(-alpha)
        vnorm = jnp.maximum(jnp.linalg.norm(v), jnp.finfo(rdtype).tiny).astype(dtype)
        v = v / vnorm
        A = A - 2.0 * jnp.outer(v, v.conj() @ A)
        V = lax.dynamic_update_slice_in_dim(V, v[:, None], j, axis=1)
        return A, V

    A, V = lax.fori_loop(0, k, body, (Y, jnp.zeros((l, k), dtype)))
    R = jnp.triu(A[:k, :])
    # Re-materialize Q by applying the reflectors (in reverse) to I_{l x k}.
    def apply_back(j_, Q):
        j = k - 1 - j_
        v = V[:, j]
        return Q - 2.0 * jnp.outer(v, v.conj() @ Q)
    Q = lax.fori_loop(0, k, apply_back, jnp.eye(l, k, dtype=dtype))
    return Q, R


@jax.jit
@full_precision
def cholesky_qr2(Y: jax.Array) -> tuple[jax.Array, jax.Array]:
    """CholeskyQR2 of a TALL panel (l x k): pure-matmul orthonormalization.

    One round loses half the digits of kappa(Y); the second round recovers
    machine-precision orthogonality for kappa below ~1e7 [Yamamoto et al.].
    All flops are GEMM-shaped -> MXU-bound on TPU, which is why the RSVD
    path prefers it over Gram-Schmidt (DESIGN.md section 2).
    """
    def one_round(Q):
        G = _h(Q) @ Q
        C = jnp.linalg.cholesky(G)             # lower: G = C C^H
        Qn = _h(jnp.linalg.solve(C, _h(Q)))    # Q C^-H, solve on the small k x k
        return Qn, C
    Q1, C1 = one_round(Y)
    Q2, C2 = one_round(Q1)
    R = _h(C2) @ _h(C1)                        # upper triangular k x k
    return Q2, R


# --------------------------------------------------------------------------
# Blocked-panel pivoted QR (the MXU-bound replacement for the CGS2 loop)
# --------------------------------------------------------------------------

def _panel_select_cgs2(Z: jax.Array, Q_prev: jax.Array, picked: jax.Array,
                       b: int) -> tuple[jax.Array, jax.Array]:
    """Adaptive per-column pivot selection for ONE panel — the robust
    fallback when the one-shot top-``b`` candidates are (near-)collinear
    (duplicate columns, rank-deficient sketches).

    Runs ``b`` steps of the oracle's greedy loop, but with the trailing
    update DEFERRED: residual norms are downdated GEQP3-style
    (``res2 -= |q^H Z|^2``) instead of rewriting ``Z`` rank-1 per column,
    so the expensive ``Z`` mutation still happens once per panel in the
    caller's GEMM.  Each pivot is CGS2-orthogonalized against the prior
    basis AND the panel built so far, which keeps junk directions from
    zero-residual columns orthonormal exactly like the oracle does.
    """
    l, n = Z.shape
    dtype = Z.dtype
    rdtype = jnp.finfo(dtype).dtype
    tiny = jnp.finfo(rdtype).tiny
    res2 = _masked_res2(Z, picked, rdtype)

    def body(j, state):
        Qp, idx, res2 = state
        p = jnp.argmax(res2).astype(jnp.int32)
        v = lax.dynamic_slice_in_dim(Z, p, 1, axis=1)[:, 0]
        v = v / jnp.maximum(jnp.linalg.norm(v), tiny).astype(dtype)
        # Three projection passes, not CGS2's two: a noise-floor column can
        # be a bitwise COPY of an earlier junk pick, so pass 1 collapses it
        # entirely into the span and the renormalized remainder needs two
        # further passes to reach machine-precision orthogonality.
        for _ in range(3):
            v = v - Q_prev @ (_h(Q_prev) @ v)
            v = v - Qp @ (_h(Qp) @ v)          # cols >= j still zero: safe
            v = v / jnp.maximum(jnp.linalg.norm(v), tiny).astype(dtype)
        Qp = lax.dynamic_update_slice_in_dim(Qp, v[:, None], j, axis=1)
        idx = idx.at[j].set(p)
        w = _h(Z) @ v                          # norm downdate, no Z write
        res2 = _downdate_res2(res2, w, p)
        return Qp, idx, res2

    Qp, idx, _ = lax.fori_loop(
        0, b, body,
        (jnp.zeros((l, b), dtype), jnp.zeros((b,), jnp.int32), res2))
    return Qp, idx


def _panel_orthonormalize(Z: jax.Array, idx: jax.Array, Q_prev: jax.Array,
                          picked: jax.Array,
                          panel_impl: str) -> tuple[jax.Array, jax.Array]:
    """Orthonormal basis for the panel ``Z[:, idx]`` (l x b), orthogonal to
    ``Q_prev``; returns ``(Q_panel, idx)`` where ``idx`` may be REPLACED by
    an adaptive re-selection when the candidates are degenerate.

    The panel comes from the deflated residual, so it is already orthogonal
    to ``Q_prev`` up to one-pass CGS error; the block re-projection here is
    the "2" of CGS2 at panel granularity.  ``panel_impl``:

      "chol"  — CholeskyQR2, pure GEMM (fastest; needs kappa under ~1e7);
      "house" — Householder panel QR (benchmark reference);
      "auto"  — CholeskyQR2, with a ``lax.cond`` fallback to the adaptive
                per-column selection when the Gram cholesky degenerates
                (NaNs or lost orthogonality).  Generic sketches never take
                the fallback; duplicate-column inputs do.
    """
    C = jnp.take(Z, idx, axis=1)
    rdtype = jnp.finfo(C.dtype).dtype
    if Q_prev.shape[1]:
        C = C - Q_prev @ (_h(Q_prev) @ C)
    if panel_impl == "house":
        return householder_qr(C)[0], idx
    Qp, _ = cholesky_qr2(C)
    if panel_impl == "chol":
        return Qp, idx
    b = C.shape[1]
    err = jnp.max(jnp.abs(_h(Qp) @ Qp - jnp.eye(b, dtype=C.dtype)))
    ok = jnp.all(jnp.isfinite(Qp)) & (err < jnp.sqrt(jnp.finfo(rdtype).eps))
    return lax.cond(ok, lambda: (Qp, idx),
                    lambda: _panel_select_cgs2(Z, Q_prev, picked, b))


def _fused_panel_update(Z, res2, picked, Q, piv, off: int, b: int):
    """One panel of the fused blocked engine: select, orthonormalize
    (``panel_step``), deflate, and fall back to adaptive per-column
    selection on a degenerate panel.  ``off``/``b`` are static python
    ints (the caller's loop is statically unrolled).

    Shared verbatim by the jitted production loop in
    ``blocked_pivoted_qr`` and the per-panel deep-tracing driver
    (``_blocked_pivoted_qr_deep``) so the two paths run the SAME op
    sequence per panel — that is what makes the traced profile an
    honest account of the production engine.
    """
    dtype = Z.dtype
    rdtype = jnp.finfo(dtype).dtype
    _, idx = lax.top_k(res2, b)
    idx = idx.astype(jnp.int32)
    C = jnp.take(Z, idx, axis=1)
    if off:                                     # block re-projection ("2"
        C = C - Q[:, :off] @ (_h(Q[:, :off]) @ C)   # of CGS2)
    # one VMEM pass over Z; W elided (R is recomputed at the end)
    Qp, O, _, r2 = panel_step(C, Z, emit_w=False)
    err = jnp.max(jnp.abs(_h(Qp) @ Qp - jnp.eye(b, dtype=dtype)))
    ok = jnp.all(jnp.isfinite(Qp)) & \
        (err < jnp.sqrt(jnp.finfo(rdtype).eps))

    def _fallback(Z=Z, Qprev=Q[:, :off], picked=picked, b=b):
        Qf, idxf = _panel_select_cgs2(Z, Qprev, picked, b)
        Of = Z - Qf @ (_h(Qf) @ Z)
        r2f = jnp.sum(jnp.abs(Of) ** 2, axis=0).astype(rdtype)
        return Qf, idxf, Of, r2f

    Qp, idx, Z, r2 = lax.cond(
        ok, lambda Qp=Qp, idx=idx, O=O, r2=r2: (Qp, idx, O, r2),
        _fallback)
    picked = picked.at[idx].set(True)
    res2 = jnp.where(picked, jnp.asarray(-1.0, rdtype),
                     r2.astype(rdtype))
    Q = Q.at[:, off:off + b].set(Qp)
    piv = piv.at[off:off + b].set(idx)
    return Z, res2, picked, Q, piv


@partial(jax.jit, static_argnames=("k", "panel", "panel_impl",
                                   "norm_recompute"))
@full_precision
def blocked_pivoted_qr(Y: jax.Array, k: int, *, panel: int = 32,
                       panel_impl: str = "fused",
                       norm_recompute="auto") -> QRResult:
    """Blocked-panel greedy-pivoted thin QR of the wide sketch ``Y`` (l x n).

    Per panel of ``b = panel`` pivots:

      1. residual column norms of the deflated ``Z`` rank the candidates;
         the top-``b`` unpicked columns become this panel's pivots
         (``lax.top_k`` — the panel analogue of the paper's greedy argmax);
      2. the panel is orthonormalized against the prior basis and itself;
      3. the trailing residual deflates with ONE GEMM pair,
         ``Z -= Q_p (Q_p^H Z)``, replacing ``b`` rank-1 GEMV updates.

    ``panel_impl`` selects how steps 2-3 run:

      "fused" (default) — ONE Pallas kernel per panel
          (``kernels/panel_step``): in-kernel CholeskyQR2 of the
          candidates plus coefficient block, deflation, and the NEXT
          panel's residual norms, all in a single VMEM residency of each
          residual slab.  The norms are loop-carried, so the split
          paths' per-panel norm recompute (one extra full read of ``Z``)
          disappears.  Degenerate panels fall back to the adaptive
          per-column selection exactly like "auto".
      "auto" / "chol" / "house" — the split parity oracles: XLA-level
          CholeskyQR2 / Householder panels with a separate GEMM-pair
          deflation (see ``_panel_orthonormalize``).

    Pivot ORDER within a panel follows residual-norm rank at panel entry,
    so the pivot set may differ from ``cgs2_pivoted_qr``'s on near-ties —
    the ID quality is the same (see tests/test_qr_blocked.py).  Panel
    width trades throughput against eq.(3) pivot quality (module
    docstring); 32 is the production default, 16 the fitted "auto" choice
    at the paper's universal ``l = 2k`` oversampling.

    ``norm_recompute`` is accepted (and validated) for API symmetry with
    the distributed engine, where the cadence bounds the f32 downdate
    drift (core.qr_dist).  On THIS path it is a no-op by construction:
    the fused kernel re-derives every panel's statistics exactly from
    the freshly deflated slab (``panel_step`` emits ``colnorms^2(O)``,
    never a downdate), and the split oracles recompute from the residual
    each panel — both already satisfy the tightest cadence.

    Returns ``QRResult(Q, R, piv)`` with ``R = Q^H Y``; ``R[:, piv]`` is
    upper triangular up to orthogonalization error, exactly like the
    oracle's contract.
    """
    l, n = Y.shape
    check_rank_bounds(k, l, n)
    check_panel(panel)
    if panel_impl not in ("fused", "auto", "chol", "house"):
        raise ValueError(f"unknown panel_impl {panel_impl!r}")
    resolve_norm_recompute(norm_recompute)      # validated; no-op here (doc)
    dtype = Y.dtype
    rdtype = jnp.finfo(dtype).dtype

    Q = jnp.zeros((l, k), dtype)
    piv = jnp.zeros((k,), jnp.int32)
    picked = jnp.zeros((n,), bool)
    Z = Y
    off = 0
    if panel_impl == "fused":
        res2 = _masked_res2(Z, picked, rdtype)  # the ONLY full norm pass
        while off < k:                          # static unroll: k/b panels
            b = min(panel, k - off)
            Z, res2, picked, Q, piv = _fused_panel_update(
                Z, res2, picked, Q, piv, off, b)
            off += b
        R = _h(Q) @ Y
        return QRResult(Q=Q, R=R, piv=piv)
    while off < k:                              # static unroll: k/b panels
        b = min(panel, k - off)
        res2 = _masked_res2(Z, picked, rdtype)
        _, idx = lax.top_k(res2, b)
        idx = idx.astype(jnp.int32)
        Qp, idx = _panel_orthonormalize(Z, idx, Q[:, :off], picked, panel_impl)
        Z = Z - Qp @ (_h(Qp) @ Z)               # the ONE GEMM-pair deflation
        Q = Q.at[:, off:off + b].set(Qp)
        piv = piv.at[off:off + b].set(idx)
        picked = picked.at[idx].set(True)
        off += b
    R = _h(Q) @ Y
    return QRResult(Q=Q, R=R, piv=piv)


@partial(jax.jit, static_argnames=("off", "b"))
@full_precision
def _fused_panel_step_jit(Z, res2, picked, Q, piv, off: int, b: int):
    """Per-panel jit of the shared fused body, for the deep driver."""
    return _fused_panel_update(Z, res2, picked, Q, piv, off, b)


def _blocked_pivoted_qr_deep(Y: jax.Array, k: int, *, panel: int,
                             norm_recompute) -> QRResult:
    """Deep-tracing panel-at-a-time driver for the fused blocked engine.

    The production loop lives INSIDE one jit boundary, so host timers
    there would plant device syncs in traced code (banned by the
    ``jaxpr.host-transfer`` analysis rule).  Under
    ``obs.trace.deep_tracing()`` the dispatcher routes here instead: a
    HOST python loop over per-panel jitted steps of the SAME body
    (``_fused_panel_update``), each bracketed by a span that blocks on
    the panel's outputs — true per-panel device timing (``qr.panel``
    spans, ``qr.panels`` counter), at the cost of one dispatch + sync
    per panel.  A profiling mode, never the production path: same op
    sequence per panel means same pivots; Q/R agree with
    ``blocked_pivoted_qr`` to fusion-level rounding
    (tests/test_obs.py pins the parity).
    """
    l, n = Y.shape
    check_rank_bounds(k, l, n)
    check_panel(panel)
    resolve_norm_recompute(norm_recompute)      # validated; no-op (see doc)
    dtype = Y.dtype
    rdtype = jnp.finfo(dtype).dtype
    Q = jnp.zeros((l, k), dtype)
    piv = jnp.zeros((k,), jnp.int32)
    picked = jnp.zeros((n,), bool)
    Z = Y
    panels_ctr = obs_trace.counter("qr.panels")
    with obs_trace.span("qr.blocked_deep", l=l, n=n, k=k, panel=panel):
        res2 = _masked_res2(Z, picked, rdtype)
        off = pi = 0
        while off < k:
            b = min(panel, k - off)
            # panel= is the ordinal index (uniform span attribution:
            # timeline stragglers attribute by chunk=/panel=), off/width
            # locate it in the factorization.
            with obs_trace.span("qr.panel", engine="blocked-fused",
                                panel=pi, off=off, width=b) as sp:
                Z, res2, picked, Q, piv = _fused_panel_step_jit(
                    Z, res2, picked, Q, piv, off, b)
                sp.block_on((Z, res2, Q))
            panels_ctr.add(1)
            off += b
            pi += 1
        with obs_trace.span("qr.final_r") as sp:
            R = _h(Q) @ Y
            sp.block_on(R)
    return QRResult(Q=Q, R=R, piv=piv)


# --------------------------------------------------------------------------
# Fitted panel-width model + norm-recompute cadence
# --------------------------------------------------------------------------

# Calibrated against the measured eq.(3) bound-constant drift recorded by
# ``python -m benchmarks.bench_error --grid`` (rows bench="error_grid_width"
# in BENCH_scaling.json): the bound ratio stays flat while
# ``panel * k / l`` is below ~12 and inflates past the paper's constant
# beyond ~16 (the k ~ 100, l = 2k, panel = 32 cliff sits at 16).  The model
# picks the WIDEST power-of-two width whose predicted drift stays in the
# flat region — wider panels mean fewer trailing updates, so throughput
# wants the largest safe width, not the smallest.
_WIDTH_TAU = 12.0
_PANEL_WIDTHS = (64, 32, 16, 8)

# "auto" recompute cadence: one exact-norm panel every 8 downdated panels
# bounds the f32 drift to a single window's accumulation (~panel * 8
# rounding steps) while serializing only 1-in-8 pivot psums.
_NORM_RECOMPUTE_AUTO = 8


def resolve_panel(panel, k: int, l: int) -> int:
    """Resolve ``panel="auto"`` through the fitted width model: the widest
    width in ``_PANEL_WIDTHS`` with ``panel * k <= _WIDTH_TAU * l``
    (falling back to the narrowest).  At the paper's universal ``l = 2k``
    oversampling this yields 16 — the measured safe width at the k ~ 100
    bound cliff — and relaxes to 32/64 only when ``l/k`` leaves slack
    (heavy oversampling), where the one-shot panel ranking provably has
    room.  Integers pass through unchanged; any other string is rejected
    eagerly (not deep inside a jitted comparison)."""
    if isinstance(panel, str):
        if panel == "auto":
            for w in _PANEL_WIDTHS:
                if w * k <= _WIDTH_TAU * l:
                    return w
            return _PANEL_WIDTHS[-1]
        raise ValueError(f"unknown panel {panel!r}; expected an int or 'auto'")
    return panel


def resolve_norm_recompute(norm_recompute) -> int:
    """Resolve the ``norm_recompute`` cadence to an int: recompute exact
    residual norms every N fused panels (``0`` = never, ``1`` = every
    panel — the paper-parity pin, ``"auto"`` = every 8).  Rejected
    eagerly with the offending value so jitted callers fail fast."""
    if norm_recompute is None:
        return 0
    if isinstance(norm_recompute, str):
        if norm_recompute == "auto":
            return _NORM_RECOMPUTE_AUTO
        raise ValueError(f"unknown norm_recompute {norm_recompute!r}; "
                         f"expected an int >= 0 or 'auto'")
    if not isinstance(norm_recompute, int) or norm_recompute < 0:
        raise ValueError(f"need norm_recompute >= 0 (or 'auto'), "
                         f"got {norm_recompute!r}")
    return norm_recompute


def pivoted_qr(Y: jax.Array, k: int, *, impl: str = "blocked",
               panel=32, panel_impl: str = "fused",
               norm_recompute="auto") -> QRResult:
    """Dispatch the pivoted QR of the sketch.

    ``impl="cgs2"``    — the paper's per-column iterated Gram-Schmidt
                         (parity oracle, O(k) sequential GEMV steps).
    ``impl="blocked"`` — the blocked-panel engine above (O(k/panel)
                         sequential GEMM steps; the production default,
                         ~MXU-bound).  ``panel_impl`` picks its panel
                         step ('fused' — the one-kernel default — or the
                         split 'auto' | 'chol' | 'house' oracles; see
                         ``blocked_pivoted_qr``); ignored by cgs2.

    ``panel`` may be an int or ``"auto"`` (``resolve_panel``): the widest
    panel the fitted eq.(3) drift model predicts safe for this (k, l) —
    16 at the paper's ``l = 2k`` oversampling.  ``norm_recompute`` sets
    the exact-norm recompute cadence of the fused path (module
    docstring); ignored by cgs2.

    (The distributed-only 'panel_parallel' engine lives in
    ``core.qr_dist`` — it needs a mesh axis, not a replicated ``Y``.)

    OBSERVABILITY: when called EAGERLY (``Y`` not a jax tracer — i.e.
    not from inside a jitted caller like ``rid``'s fused path) the
    dispatch opens a ``qr.pivoted`` span around the engine call, and
    under ``obs.trace.deep_tracing()`` the fused blocked engine is
    served by the per-panel driver (``_blocked_pivoted_qr_deep``:
    ``qr.panel`` spans with device-bracketed timing).  Inside a jit
    trace no spans are opened — span timing there would be trace-time,
    not runtime, and blocking on tracers is impossible.
    """
    if impl not in ("cgs2", "blocked"):
        raise ValueError(
            f"unknown qr impl {impl!r}; expected 'cgs2' or 'blocked'")
    eager = not isinstance(Y, jax.core.Tracer)
    if impl == "cgs2":
        if eager:
            with obs_trace.span("qr.pivoted", impl="cgs2", k=k) as sp:
                out = cgs2_pivoted_qr(Y, k)
                sp.block_on(out)
            return out
        return cgs2_pivoted_qr(Y, k)
    p = resolve_panel(panel, k, Y.shape[0])
    if eager and panel_impl == "fused" and obs_trace.deep_tracing():
        return _blocked_pivoted_qr_deep(Y, k, panel=p,
                                        norm_recompute=norm_recompute)
    if eager:
        with obs_trace.span("qr.pivoted", impl="blocked", k=k, panel=p,
                            panel_impl=panel_impl) as sp:
            out = blocked_pivoted_qr(Y, k, panel=p, panel_impl=panel_impl,
                                     norm_recompute=norm_recompute)
            sp.block_on(out)
        return out
    return blocked_pivoted_qr(Y, k, panel=p, panel_impl=panel_impl,
                              norm_recompute=norm_recompute)


# ------------------------------------------------------------- analysis
# Registered contract: the production blocked engine at the analyzer's
# canonical sketch shape — single-device dataflow rules (dtype leaks,
# host transfers) re-proven on every CI run.

def _analysis_build_blocked():
    def fn(Y):
        return pivoted_qr(Y, 21, impl="blocked", panel=7)
    return fn, (jax.ShapeDtypeStruct((48, 400), jnp.float32),)


def _register_analysis_entries():
    from ..analysis.registry import register
    register("pivoted_qr.blocked", _analysis_build_blocked)


_register_analysis_entries()
