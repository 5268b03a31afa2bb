"""Paper Table 5: ||A - BP||_2 across the grid + the eq. (3) bound.

Runs in complex128 like the paper (f64 enabled at startup); the default
SMALL_GRID reproduces the paper's REGIME (error ~ sqrt(min(m,n)) * 1e-16
x O(10..100), bound satisfied 'reasonably tightly'); ``--full`` runs the
paper's exact rows and should land in the 1e-10..1e-9 band of Table 5.

This is a PAPER-PARITY check, so the QR engine pins the paper's CGS2
oracle rather than following the production default: the blocked/panel
engines trade a little pivot quality per panel width (within 10x of the
oracle — tests/test_qr_blocked.py) which can exceed eq.(3)'s constant at
the largest SMALL_GRID ranks.  Probe them with ``--qr-impl blocked``,
which defaults ``--qr-panel`` to the dispatcher's "auto" width model.

``--grid`` runs the KNOWN-SPECTRUM verification grid instead of the
noise-floor Table-5 rows: matrices built with exact singular values
(``repro.data.synthetic.spectrum_matrix``) over spectra {fast_decay,
cliff, noisy_tail} x dtypes {f32, f64, c64} x impls {cgs2, blocked,
panel_parallel} x k, measuring the eq.(3) bound RATIO against the true
``sigma_{k+1}``, plus the panel-width calibration sweep the fitted
``core.qr.resolve_panel`` model derives from (bound ratio vs width on
the quality-critical cliff spectrum — the k ~ 96, l = 2k, panel = 32
point is the measured ~50-300x inflation cliff).  ``--json`` appends the
rows (bench = "error_grid" / "error_grid_width") and a worst-ratio-per-
impl/dtype summary (bench = "error_grid_summary") to the
BENCH_scaling.json record benchmarks/run.py tracks across PRs.
"""
from __future__ import annotations

import argparse

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp

from jax import make_mesh
from jax.sharding import AxisType
from repro.configs.paper_rid import (PAPER_GRID, PAPER_TABLE5_ERRORS,
                                     SMALL_GRID)
from repro.core import (error_bound, expected_sigma_kp1, rid,
                        rid_distributed, shard_columns, spectral_error,
                        spectral_norm_dense)
from repro.core.distributed import QR_IMPLS as GRID_IMPLS
from repro.data.synthetic import DTYPE_FLOORS, SPECTRA, spectrum_matrix

from .bench_total import lowrank_complex
from .common import append_json_rows, emit

GRID_DTYPES = {name: (getattr(jnp, name), DTYPE_FLOORS[name])
               for name in ("float32", "float64", "complex64")}
GRID_SHAPES = {10: (128, 120), 40: (256, 240), 96: (512, 480),
               100: (512, 480)}
WIDTH_SWEEP = (8, 16, 32, 64)


def _grid_err(key, A, k, impl, qr_panel="auto", norm_recompute="auto"):
    """f64 reconstruction error of the rank-k RID through ``impl``
    (panel_parallel on a mesh spanning the devices that divide n).
    Mirrors tests/test_error_bounds._grid_rid, which pins a 1-device
    mesh; it cannot be imported from there (this module flips x64 at
    import, which must not leak into the test process at collection)."""
    if impl == "panel_parallel":
        ndev = len(jax.devices())
        if A.shape[1] % ndev:
            ndev = 1
        mesh = make_mesh((ndev,), ("data",), axis_types=(AxisType.Auto,))
        dec = rid_distributed(key, shard_columns(A, mesh, "data"), k,
                              mesh=mesh, axis="data", sketch_kind="gaussian",
                              qr_impl="panel_parallel", qr_panel=qr_panel,
                              qr_norm_recompute=norm_recompute)
    else:
        dec = rid(key, A, k, sketch_kind="gaussian", qr_impl=impl,
                  qr_panel=qr_panel, qr_norm_recompute=norm_recompute)
    E = jnp.asarray(A, jnp.complex128) - \
        jnp.asarray(dec.B, jnp.complex128) @ jnp.asarray(dec.P, jnp.complex128)
    return float(spectral_norm_dense(E))


def grid_sweep(*, full=False, json_path=None):
    """The eq.(3) verification grid + the width-calibration sweep; the
    per-impl/dtype worst bound ratios are the quality trajectory
    benchmarks/run.py records next to the perf rows."""
    ks = (10, 40, 100) if full else (10, 40)
    rows = []
    for k in ks:
        m, n = GRID_SHAPES[k]
        for spectrum in SPECTRA:
            for dname, (dtype, floor) in GRID_DTYPES.items():
                A, sig = spectrum_matrix(jax.random.key(k), m, n, spectrum,
                                         k, dtype=dtype, floor=floor)
                bound = error_bound(m, n, k) * float(sig[k])
                for impl in GRID_IMPLS:
                    err = _grid_err(jax.random.key(k + 1), A, k, impl)
                    rows.append({"bench": "error_grid", "spectrum": spectrum,
                                 "dtype": dname, "impl": impl, "k": k,
                                 "m": m, "n": n, "err_2norm": err,
                                 "sigma_kp1": float(sig[k]),
                                 "eq3_bound": bound, "ratio": err / bound,
                                 "within_bound": err <= bound})
    emit(rows, header="eq.(3) verification grid: known-spectrum matrices, "
                      "bound ratio vs the TRUE sigma_k+1")

    # Width calibration (the data core.qr.resolve_panel's fitted model is
    # pinned to): bound ratio vs panel width on the cliff spectrum.
    wrows = []
    for k in ((40, 96) if not full else (40, 96, 100)):
        m, n = GRID_SHAPES[k]
        l = 2 * k
        A, sig = spectrum_matrix(jax.random.key(3), m, n, "cliff", k,
                                 dtype=jnp.float64, floor=1e-10)
        bound = error_bound(m, n, k) * float(sig[k])
        for panel in WIDTH_SWEEP:
            err = _grid_err(jax.random.key(5), A, k, "blocked",
                            qr_panel=panel)
            wrows.append({"bench": "error_grid_width", "k": k, "l": l,
                          "m": m, "n": n, "panel": panel,
                          "wk_over_l": panel * k / l,
                          "ratio": err / bound,
                          "within_bound": err <= bound})
    emit(wrows, header="Width calibration: bound ratio vs panel width "
                       "(cliff spectrum, l = 2k) — resolve_panel's fit")

    # Per-impl/dtype worst ratios: the one-line quality trajectory.
    summary = []
    for impl in GRID_IMPLS:
        for dname in GRID_DTYPES:
            worst = max(r["ratio"] for r in rows
                        if r["impl"] == impl and r["dtype"] == dname)
            summary.append({"bench": "error_grid_summary", "impl": impl,
                            "dtype": dname, "worst_ratio": worst,
                            "within_bound": worst <= 1.0})
    emit(summary, header="error-grid summary: worst eq.(3) bound ratio "
                         "per impl/dtype")
    # Record BEFORE gating: on a bound violation the CI artifact must
    # still carry the grid rows that diagnose which point regressed.
    if json_path:
        append_json_rows(json_path, rows + wrows + summary)
    # The width-sweep rows are calibration DATA (they deliberately probe
    # past the safe region); only the auto-width grid gates.
    assert all(r["within_bound"] for r in rows + summary), \
        "eq.(3) bound violated on the verification grid!"
    return rows + wrows + summary


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--sketch", default="srft",
                    choices=["srft", "srht", "gaussian"])
    ap.add_argument("--qr-impl", default="cgs2", choices=["cgs2", "blocked"],
                    help="pivoted-QR engine (default: the paper's CGS2 "
                         "oracle — this bench checks paper parity)")
    ap.add_argument("--qr-panel", default="auto",
                    help="blocked-engine panel width: an int, or 'auto' "
                         "for the fitted eq.(3)-drift width model "
                         "(core.qr.resolve_panel; ignored by cgs2)")
    ap.add_argument("--grid", action="store_true",
                    help="run the known-spectrum eq.(3) verification grid "
                         "+ panel-width calibration sweep instead of the "
                         "Table-5 rows")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="append the grid rows and worst-ratio summary to "
                         "this JSON record (the BENCH_scaling.json "
                         "contract of benchmarks/run.py)")
    args = ap.parse_args(argv)
    if args.grid:
        grid_sweep(full=args.full, json_path=args.json)
        return
    qr_panel = args.qr_panel if args.qr_panel == "auto" else int(args.qr_panel)
    grid = PAPER_GRID if args.full else SMALL_GRID
    rows = []
    for i, case in enumerate(grid):
        key = jax.random.key(case.k + 13)
        A = lowrank_complex(key, case.m, case.n, case.k, jnp.complex128)
        dec = rid(jax.random.fold_in(key, 3), A, case.k,
                  sketch_kind=args.sketch, qr_impl=args.qr_impl,
                  qr_panel=qr_panel)
        err = float(spectral_error(jax.random.fold_in(key, 4), A, dec.B,
                                   dec.P, iters=40))
        floor = expected_sigma_kp1(case.m, case.n)
        bound = error_bound(case.m, case.n, case.k) * floor
        row = {"k": case.k, "m": case.m, "n": case.n, "err_2norm": err,
               "sigma_floor": floor, "eq3_bound": bound,
               "within_bound": err <= bound}
        if args.full:
            row["paper_table5"] = PAPER_TABLE5_ERRORS[i]
        rows.append(row)
    emit(rows, header=f"Table 5 analogue: ||A-BP||_2 in complex128 "
                      f"(sketch={args.sketch}); eq.(3) bound check")
    assert all(r["within_bound"] for r in rows), "eq.(3) bound violated!"


if __name__ == "__main__":
    main()
