"""Property-based eq.(3) verification grid (ISSUE 4).

The paper's eq. (3) bounds ``||A - BP||_2 <= 50 sqrt(mn) (1/eps)^(1/k)
sigma_{k+1}``.  Every matrix here is built with an EXACTLY known spectrum
(``repro.data.synthetic.spectrum_matrix``), so the bound is checked
against the true ``sigma_{k+1}`` — not the noise-floor estimate the
paper-parity bench uses — across the grid

    spectra {fast_decay, cliff, noisy_tail}
  x dtypes  {float32, float64, complex64}
  x impls   {cgs2, blocked/fused, panel_parallel/fused}
  x k       {10, 40, 100}

plus the two failure modes the ROADMAP flags for the fused path:

  * f32 residual-norm DOWNDATE drift (core.qr_dist overlaps the pivot
    psum with the deflation by downdating instead of recomputing):
    ``_downdate_chain`` replays the distributed engine's exact
    stage-A/stage-B kernel sequence on one shard and compares the
    downdated statistics against the deflated residual's true norms —
    the ``norm_recompute`` cadence must reset the drift;
  * panel-width pivot-quality loss: pivot sets must agree between the
    replicated and distributed fused engines, and ``qr_panel="auto"``
    (the fitted width model) must not lose to the best fixed width.

Fast representatives run in the smoke lane; the full cartesian grid is
marked slow (main/nightly).  ``panel_parallel`` cases run on a 1-device
mesh — the downdate/recompute arithmetic is device-count independent;
the 8-fake-device parity lives in tests/test_qr_dist.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)

from jax import make_mesh
from jax.sharding import AxisType
from repro.core import (error_bound, pivoted_qr, rid, rid_distributed,
                        shard_columns, spectral_norm_dense)
from repro.core.qr import resolve_panel
from repro.core.sketch import sketch
from repro.data.synthetic import spectrum_matrix
from repro.kernels.panel_step import panel_apply, panel_coeff

from strategies import (DTYPE_FLOOR, GRID_DTYPES, GRID_IMPLS, GRID_KS,
                        SPECTRA, given, grid_cases, qr_cases, settings)


@pytest.fixture(autouse=True, scope="module")
def _x64_scope():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


DTYPES = {"float32": jnp.float32, "float64": jnp.float64,
          "complex64": jnp.complex64, "complex128": jnp.complex128}

# Shapes per k: small enough for dense-SVD error measurement, wide enough
# that the sketch (l = 2k) never degenerates.
SHAPES = {10: (128, 120), 16: (160, 144), 40: (256, 240), 100: (512, 420)}


def _one_dev_mesh():
    return make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))


def _grid_rid(A, k, impl, *, norm_recompute="auto", qr_panel="auto", seed=11):
    """Run the rank-k RID of ``A`` through ``impl`` (panel_parallel via a
    1-device mesh) and return the f64 reconstruction error."""
    key = jax.random.key(seed)
    if impl == "panel_parallel":
        mesh = _one_dev_mesh()
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


def _check_eq3(spectrum, dtype_name, impl, k, seed=0):
    """One grid point: eq.(3) with the paper's constant against the TRUE
    sigma_{k+1}.  Returns the bound ratio for callers that compare."""
    m, n = SHAPES[k]
    dtype = DTYPES[dtype_name]
    floor = DTYPE_FLOOR[dtype_name]
    A, sig = spectrum_matrix(jax.random.key(seed), m, n, spectrum, k,
                             dtype=dtype, floor=floor)
    err = _grid_rid(A, k, impl)
    bound = error_bound(m, n, k) * sig[k]          # the paper's constant
    assert err <= bound, (
        f"eq.(3) violated: {spectrum}/{dtype_name}/{impl}/k={k}: "
        f"err={err:.3e} > bound={bound:.3e} (sigma_k+1={sig[k]:.3e})")
    return err / bound


# ----------------------------------------------------------- eq.(3) grid

FAST_GRID = [
    ("fast_decay", "float32", "blocked", 10),
    ("fast_decay", "float64", "cgs2", 40),
    ("cliff", "complex64", "blocked", 40),
    ("cliff", "float64", "panel_parallel", 40),
    ("noisy_tail", "float32", "cgs2", 10),
    ("noisy_tail", "float64", "panel_parallel", 10),
]


@pytest.mark.parametrize("spectrum,dtype_name,impl,k", FAST_GRID)
def test_eq3_grid_fast(spectrum, dtype_name, impl, k):
    """Smoke-lane representatives: every spectrum, dtype, and impl at
    least once (full cartesian product below, marked slow)."""
    _check_eq3(spectrum, dtype_name, impl, k)


@pytest.mark.slow
@pytest.mark.parametrize("k", GRID_KS)
@pytest.mark.parametrize("impl", GRID_IMPLS)
@pytest.mark.parametrize("dtype_name", GRID_DTYPES)
@pytest.mark.parametrize("spectrum", SPECTRA)
def test_eq3_grid_full(spectrum, dtype_name, impl, k):
    """The full spectra x dtype x impl x k verification grid — the
    paper's "bounds still hold" claim, checked against true spectra."""
    _check_eq3(spectrum, dtype_name, impl, k)


@pytest.mark.slow
@settings(max_examples=5, deadline=None)
@given(grid_cases())
def test_property_eq3_grid(case):
    """Hypothesis-sampled off-grid points (skips cleanly without the
    dep, like test_core_rid)."""
    _check_eq3(case["spectrum"], case["dtype"], case["impl"], case["k"],
               seed=case["seed"])


# ---------------------------------------------------- streamed eq.(3) case

@pytest.mark.slow
def test_eq3_streamed_out_of_core_shape():
    """eq.(3) at the largest shape CI can hold, decomposed STREAMED: the
    matrix is never materialized on the decomposition side (a
    SpectrumSource generates 512-row chunks on demand), m is 16x the
    largest in-memory grid shape, and the bound is checked against the
    EXACT sigma_{k+1} the source knows.  The error is measured against a
    one-off materialization — fine on the test host, unlike a device
    residency."""
    from repro.core import rid_streamed
    from repro.stream import SpectrumSource

    m, n, k = 8192, 384, 40
    src = SpectrumSource(jax.random.key(21), m, n, "fast_decay", k,
                         chunk_rows=512, dtype=jnp.float64, floor=1e-10)
    dec = rid_streamed(jax.random.key(22), src, k)
    A = jnp.asarray(src.materialize())
    E = A - jnp.asarray(dec.B) @ dec.P
    err = float(spectral_norm_dense(E))
    bound = error_bound(m, n, k) * float(src.sigmas[k])
    assert err <= bound, (err, bound, src.sigmas[k])


# ------------------------------------------- downdate drift vs recompute

def _downdate_chain(Y, k, panel, recompute_every):
    """Replay the distributed fused engine's per-panel kernel sequence
    (stage A ``panel_coeff`` downdate -> stage B ``panel_apply``) on a
    single shard, recomputing exact norms every ``recompute_every``
    panels exactly like ``core.qr_dist.panel_parallel_qr_local`` — then
    return the max relative drift of the carried pivot statistics
    against the deflated residual's TRUE column norms."""
    Z = Y
    res2 = jnp.sum(jnp.abs(Z) ** 2, axis=0)
    picked = jnp.zeros((Y.shape[1],), bool)
    Q = jnp.zeros((Y.shape[0], 0), Y.dtype)
    p_i = pos = 0
    while pos < k:
        b = min(panel, k - pos)
        _, idx = jax.lax.top_k(jnp.where(picked, -1.0, res2), b)
        C = jnp.take(Z, idx, axis=1)
        if pos:
            C = C - Q @ (Q.conj().T @ C)
        Qp, W, r2d = panel_coeff(C, Z, res2)
        picked = picked.at[idx].set(True)
        p_i += 1
        # Same last-panel guard as the engine: the FINAL statistics are
        # downdated ones, so the drift measured below is the real
        # window-tail accumulation, not a freshly recomputed vector.
        if recompute_every and p_i % recompute_every == 0 and pos + b < k:
            Z, res2 = panel_apply(Qp, W, Z, emit_norms=True)
        else:
            res2 = r2d
            Z = panel_apply(Qp, W, Z)
        Q = jnp.concatenate([Q, Qp], axis=1)
        pos += b
    exact = jnp.sum(jnp.abs(Z) ** 2, axis=0)
    live = ~picked
    drift = jnp.abs(res2 - exact) / jnp.maximum(exact, jnp.finfo(
        exact.dtype).tiny)
    return float(jnp.max(jnp.where(live, drift, 0.0)))


def _fast_decay_f32(m=256, n=320, k=96):
    A64, sig = spectrum_matrix(jax.random.key(42), m, n, "fast_decay", k,
                               dtype=jnp.float64, floor=1e-9)
    return A64, A64.astype(jnp.float32), sig


def test_f32_downdate_drift_measurable_and_reset():
    """The drift half of the acceptance criterion: on a fast-decaying
    spectrum in f32, the no-recompute downdate chain's pivot statistics
    drift past 100% relative error, while the auto cadence (exact-norm
    panel every 8) keeps them faithful."""
    _, A32, _ = _fast_decay_f32()
    k, panel = 96, 4
    Y32 = sketch(jax.random.key(7), A32, 2 * k, kind="gaussian").Y
    drift_never = _downdate_chain(Y32, k, panel, 0)
    drift_auto = _downdate_chain(Y32, k, panel, 8)
    drift_pin = _downdate_chain(Y32, k, panel, 1)
    assert drift_never > 1.0, f"expected measurable drift, got {drift_never}"
    assert drift_auto < 0.1, (drift_auto, drift_never)
    assert drift_pin < 1e-3, drift_pin
    assert drift_auto < drift_never / 10


def test_f32_fused_with_recompute_within_2x_of_f64_oracle():
    """The bound-ratio half of the acceptance criterion: f32 fused
    panel-parallel QRCP with norm_recompute="auto" stays within 2x of
    the f64 CGS2 oracle's eq.(3) bound ratio on the same fast-decay
    matrix where the no-recompute statistics measurably drift (test
    above)."""
    A64, A32, sig = _fast_decay_f32()
    k = 96
    m, n = A64.shape
    bound = error_bound(m, n, k) * sig[k]
    err64 = _grid_rid(A64, k, "cgs2", seed=7)
    err32 = _grid_rid(A32, k, "panel_parallel", norm_recompute="auto",
                      qr_panel=4, seed=7)
    assert err64 <= bound and err32 <= bound, (err64, err32, bound)
    assert err32 <= 2 * err64, (
        f"f32 fused+recompute ratio {err32 / bound:.4f} vs f64 oracle "
        f"{err64 / bound:.4f} — more than 2x apart")


@pytest.mark.slow
@pytest.mark.parametrize("spectrum", SPECTRA)
def test_drift_grid_recompute_faithful(spectrum):
    """Across all spectra (f32): pinned recompute keeps the carried
    statistics faithful, and auto never does worse than never."""
    k, panel = 40, 4
    m, n = SHAPES[k]
    A, _ = spectrum_matrix(jax.random.key(5), m, n, spectrum, k,
                           dtype=jnp.float32, floor=1e-5)
    Y = sketch(jax.random.key(6), A, 2 * k, kind="gaussian").Y
    drift_never = _downdate_chain(Y, k, panel, 0)
    drift_auto = _downdate_chain(Y, k, panel, 8)
    drift_pin = _downdate_chain(Y, k, panel, 1)
    # The pinned cadence still carries ONE window of downdate rounding
    # (the last-panel guard in _downdate_chain), so its floor is a
    # single panel's f32 cancellation — on the cliff spectrum's 3-decade
    # norm drop that is a few 1e-3 relative (it sat just under 1e-3
    # before PR 5's block-seeded gaussian stream moved the draw).
    assert drift_pin < 5e-3, (spectrum, drift_pin)
    assert drift_pin <= drift_auto <= max(drift_never, 0.05), \
        (spectrum, drift_pin, drift_auto, drift_never)


# -------------------------------------------------- pivot-set agreement

@pytest.mark.parametrize("spectrum", ["fast_decay", "cliff"])
def test_pivot_set_agreement_blocked_vs_panel_parallel(spectrum):
    """With the recompute cadence pinned to 1, both fused engines rank
    panels from exact residual norms — the pivot SETS must agree (the
    noisy_tail plateau is excluded: its near-ties legitimately break
    differently between summation orders)."""
    from repro.core import panel_parallel_pivoted_qr

    k = 40
    m, n = SHAPES[k]
    A, _ = spectrum_matrix(jax.random.key(9), m, n, spectrum, k,
                           dtype=jnp.float64, floor=1e-12)
    Y = sketch(jax.random.key(10), A, 2 * k, kind="gaussian").Y
    blk = pivoted_qr(Y, k, impl="blocked", panel=8, norm_recompute=1)
    mesh = _one_dev_mesh()
    pp = panel_parallel_pivoted_qr(shard_columns(Y, mesh, "data"), k,
                                   mesh=mesh, axis="data", panel=8,
                                   norm_recompute=1)
    assert set(np.asarray(blk.piv).tolist()) == \
        set(np.asarray(pp.piv).tolist()), (spectrum, blk.piv, pp.piv)
    assert len(set(np.asarray(blk.piv).tolist())) == k


# ------------------------------------- dispatcher parity (property test)

ATOL = {"float32": 1e-3, "float64": 1e-11, "complex128": 1e-11}


def _check_dispatcher_parity(k, l_extra, n_extra, dtype, panel, seed):
    """blocked/fused vs the CGS2 oracle on a hypothesis-shaped case, and
    qr_panel="auto" (the fitted model) vs the best fixed width."""
    l = 2 * k + l_extra
    n = l + n_extra
    dt = DTYPES[dtype]
    rdt = jnp.float64 if dt in (jnp.float64, jnp.complex128) else jnp.float32
    key = jax.random.key(seed)
    kb, kp, kb2, kp2 = jax.random.split(key, 4)
    B = jax.random.normal(kb, (l, k), rdt)
    P = jax.random.normal(kp, (k, n), rdt)
    if jnp.issubdtype(dt, jnp.complexfloating):
        B = B + 1j * jax.random.normal(kb2, (l, k), rdt)
        P = P + 1j * jax.random.normal(kp2, (k, n), rdt)
    Y = (B @ P).astype(dt)
    scale = float(jnp.linalg.norm(Y))

    def recon_err(qr):
        R1 = jnp.triu(jnp.take(qr.R, qr.piv, axis=1))
        return float(jnp.linalg.norm(jnp.take(Y, qr.piv, axis=1) - qr.Q @ R1))

    orc = pivoted_qr(Y, k, impl="cgs2")
    blk = pivoted_qr(Y, k, impl="blocked", panel=panel)
    assert len(set(np.asarray(blk.piv).tolist())) == k
    assert recon_err(blk) <= 10 * recon_err(orc) + ATOL[dtype] * scale, \
        (k, l, n, dtype, panel)
    # the fitted auto width never loses to the best fixed width (up to a
    # roundoff-floor: every error here is at reconstruction noise level)
    err_auto = recon_err(pivoted_qr(Y, k, impl="blocked", panel="auto"))
    best = min(recon_err(pivoted_qr(Y, k, impl="blocked", panel=w))
               for w in (8, 16, 32))
    assert err_auto <= 5 * best + ATOL[dtype] * scale, \
        (k, l, n, dtype, resolve_panel("auto", k, l), err_auto, best)


@settings(max_examples=6, deadline=None)
@given(qr_cases())
def test_property_dispatcher_parity(case):
    _check_dispatcher_parity(**case)


@pytest.mark.parametrize("case", [
    dict(k=12, l_extra=0, n_extra=76, dtype="float64", panel=8, seed=3),
    dict(k=24, l_extra=16, n_extra=120, dtype="float32", panel="auto", seed=4),
    dict(k=7, l_extra=3, n_extra=33, dtype="complex128", panel=4, seed=5),
])
def test_dispatcher_parity_fixed(case):
    """Fixed representatives of the property test above — these run even
    when hypothesis is absent (it is a dev-only dependency)."""
    _check_dispatcher_parity(**case)
