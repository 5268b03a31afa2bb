"""Paper Table 3: the Gram-Schmidt phase (dominated by k, the paper's
non-scaling bottleneck) — CGS2 vs the paper's own post-hoc suggestion
(Householder, 'similar stability with only half the runtime') vs the
TPU-native CholeskyQR2, plus the Pallas deflation kernels, plus the
blocked-panel pivoted QR (core.qr.blocked_pivoted_qr) swept over panel
sizes with its speedup over the per-column CGS2 loop, plus the fused
panel-step kernel (kernels/panel_step) vs the split
panel_gram+panel_deflate path it subsumes (--json records that sweep
into BENCH_scaling.json)."""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.paper_rid import PAPER_GRID, SMALL_GRID
from repro.core import (blocked_pivoted_qr, cgs2_pivoted_qr, cholesky_qr2,
                        householder_qr)
from repro.kernels import panel_deflate, panel_gram, project_out

from .common import append_json_rows, emit, time_fn

PANEL_SWEEP = (16, 32, 64)


def split_blocked_qr(Y: jax.Array, k: int, panel: int):
    """The SPLIT panel loop the fused kernel replaces: per panel, a full
    residual-norm recompute pass, the ``panel_gram`` kernel + b x b
    triangular solves for the panel factor, and the ``panel_deflate``
    kernel for the trailing update (which re-derives the coefficient
    block the solves already produced) — three reads of the residual
    slab per panel where ``panel_impl="fused"`` does one."""
    l, n = Y.shape
    dtype = Y.dtype
    rdtype = jnp.finfo(dtype).dtype
    solve = lambda L, B: jax.scipy.linalg.solve_triangular(L, B, lower=True)
    Q = jnp.zeros((l, k), dtype)
    piv = jnp.zeros((k,), jnp.int32)
    picked = jnp.zeros((n,), bool)
    Z = Y
    off = 0
    while off < k:
        b = min(panel, k - off)
        res2 = jnp.where(picked, jnp.asarray(-1.0, rdtype),
                         jnp.sum(Z * Z, axis=0).astype(rdtype))
        _, idx = lax.top_k(res2, b)
        idx = idx.astype(jnp.int32)
        C = jnp.take(Z, idx, axis=1)
        if off:
            C = C - Q[:, :off] @ (Q[:, :off].T @ C)
        G, _ = panel_gram(C, Z)
        L1 = jnp.linalg.cholesky(G)
        Q1 = solve(L1, C.T).T
        L2 = jnp.linalg.cholesky(Q1.T @ Q1)
        Qp = solve(L2, Q1.T).T
        Z, _ = panel_deflate(Qp, Z)
        Q = Q.at[:, off:off + b].set(Qp)
        piv = piv.at[off:off + b].set(idx)
        picked = picked.at[idx].set(True)
        off += b
    return Q, Q.T @ Y, piv


def fused_vs_split_sweep(panels, *, l=256, n=4096, k=128, json_path=None):
    """ISSUE-3 acceptance sweep: the whole panel loop at ``l=256,
    n=4096`` through the fused kernel vs the split
    panel_gram+panel_deflate path (target >= 1.5x)."""
    Y = jax.random.normal(jax.random.key(0), (l, n), jnp.float32)
    rows = []
    for b in panels:
        fused = jax.jit(lambda y, b=b: blocked_pivoted_qr(
            y, k, panel=b, panel_impl="fused"))
        split = jax.jit(lambda y, b=b: split_blocked_qr(y, k, b))
        t_fused = time_fn(fused, Y)
        t_split = time_fn(split, Y)
        cost = fused.lower(Y).compile().cost_analysis() or {}
        rows.append({"bench": "fused_panel_step", "l": l, "n": n, "k": k,
                     "panel": b, "split_s": t_split, "fused_s": t_fused,
                     "speedup": t_split / t_fused,
                     "flops": float(cost.get("flops", 0.0))})
    emit(rows, header="Fused panel-step kernel vs split panel_gram+"
                      "panel_deflate path, l=256 n=4096 f32 "
                      "(target >= 1.5x)")
    if json_path:
        append_json_rows(json_path, rows)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--panels", type=int, nargs="*", default=list(PANEL_SWEEP),
                    help="panel sizes for the blocked engine sweep")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="append the fused-vs-split sweep rows to a "
                         "machine-readable JSON file (the BENCH_scaling"
                         ".json contract of benchmarks/run.py)")
    args = ap.parse_args(argv)
    panels = args.panels or list(PANEL_SWEEP)     # bare --panels -> default sweep
    grid = PAPER_GRID if args.full else SMALL_GRID
    rdt = jnp.float64 if args.full else jnp.float32
    if args.full:
        jax.config.update("jax_enable_x64", True)
    rows = []
    for case in grid:
        key = jax.random.key(case.k)
        l, n, k = case.l, case.n, case.k
        Y = jax.random.normal(key, (l, n), rdt)
        t_cgs2 = time_fn(jax.jit(lambda y: cgs2_pivoted_qr(y, k)), Y)
        row = {"k": k, "l": l, "n": n, "cgs2_pivoted_s": t_cgs2}
        best = None
        for b in panels:
            t_blk = time_fn(
                jax.jit(lambda y, b=b: blocked_pivoted_qr(y, k, panel=b)), Y)
            row[f"blocked_b{b}_s"] = t_blk
            best = t_blk if best is None else min(best, t_blk)
        row["blocked_speedup"] = t_cgs2 / best
        panel = Y[:, :k]
        t_house = time_fn(jax.jit(householder_qr), panel)
        t_chol = time_fn(jax.jit(cholesky_qr2), panel)
        Q = jnp.linalg.qr(jax.random.normal(key, (l, k), rdt))[0]
        t_proj = time_fn(lambda q, z: project_out(q, z), Q, Y)
        bp = min(32, k)
        t_pdef = time_fn(lambda q, z: panel_deflate(q, z)[0], Q[:, :bp], Y)
        row.update({"householder_panel_s": t_house,
                    "choleskyqr2_panel_s": t_chol,
                    "pallas_deflate_s": t_proj,
                    "pallas_panel_deflate_s": t_pdef})
        rows.append(row)
    emit(rows, header="Table 3 analogue: QR phase "
                      "(paper: GS dominated by k; blocked panels are the "
                      "GEMM-bound replacement for the per-column loop)")

    # Acceptance shape (ISSUE 1): l=256, n=4096 float32 sketch on CPU —
    # the blocked engine must beat the per-column loop by >= 2x.
    l, n, k = 256, 4096, 128
    Y = jax.random.normal(jax.random.key(0), (l, n), jnp.float32)
    t_cgs2 = time_fn(jax.jit(lambda y: cgs2_pivoted_qr(y, k)), Y)
    acc_rows = []
    for b in panels:
        t_blk = time_fn(
            jax.jit(lambda y, b=b: blocked_pivoted_qr(y, k, panel=b)), Y)
        acc_rows.append({"k": k, "l": l, "n": n, "panel": b,
                         "cgs2_s": t_cgs2, "blocked_s": t_blk,
                         "speedup": t_cgs2 / t_blk})
    emit(acc_rows, header="Acceptance: blocked vs cgs2, l=256 n=4096 f32 "
                          "(target >= 2x)")

    # Acceptance shape (ISSUE 3): same sketch, fused panel-step kernel vs
    # the split panel_gram+panel_deflate path it subsumes.
    fused_vs_split_sweep(panels, l=l, n=n, k=k, json_path=args.json)


if __name__ == "__main__":
    main()
