"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the Pallas kernel bodies on CPU with the same
blocking semantics)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (fwht_pallas, panel_apply, panel_coeff,
                           panel_deflate, panel_gram, panel_step,
                           project_out, sketch_matmul, srht_pallas, tsolve)
from repro.kernels.cgs.ref import panel_deflate_ref, project_out_ref
from repro.kernels.panel_gram.ref import panel_gram_ref
from repro.kernels.panel_step.ref import (panel_apply_ref, panel_coeff_ref,
                                          panel_step_ref)
from repro.kernels.srht.ref import fwht_ref, srht_ref
from repro.kernels.sketch_matmul.ref import sketch_matmul_ref as matmul_ref
from repro.kernels.tsolve.ref import tsolve_ref


def key(i=0):
    return jax.random.key(i)


# --------------------------------------------------------------- sketch gemm

@pytest.mark.parametrize("l,m,n", [(8, 64, 32), (32, 300, 150), (100, 777, 129),
                                   (128, 512, 256), (17, 1024, 31)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sketch_matmul_sweep(l, m, n, dtype):
    om = jax.random.normal(key(1), (l, m), dtype=dtype)
    a = jax.random.normal(key(2), (m, n), dtype=dtype)
    got = sketch_matmul(om, a)
    want = matmul_ref(om, a)
    # accumulation-order differences scale with sqrt(m) for N(0,1) inputs
    atol = (1e-5 if dtype == jnp.float32 else 2e-2) * np.sqrt(m)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


def test_sketch_matmul_complex():
    om = (jax.random.normal(key(1), (16, 100)) +
          1j * jax.random.normal(key(2), (16, 100))).astype(jnp.complex64)
    a = (jax.random.normal(key(3), (100, 40)) +
         1j * jax.random.normal(key(4), (100, 40))).astype(jnp.complex64)
    np.testing.assert_allclose(np.asarray(sketch_matmul(om, a)),
                               np.asarray(om @ a), atol=1e-3)


# --------------------------------------------------------------------- fwht

@pytest.mark.parametrize("m", [2, 64, 256, 8192, 16384])   # incl. 4-step split
@pytest.mark.parametrize("n", [1, 5, 128, 200])
def test_fwht_sweep(m, n):
    if m * n > 1 << 22:
        pytest.skip("too large for CI sweep")
    x = jax.random.normal(key(3), (m, n), dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(fwht_pallas(x)),
                               np.asarray(fwht_ref(x)), atol=1e-4)


def test_srht_full():
    m, n, l = 700, 96, 32                       # non-pow2 m exercises padding
    a = jax.random.normal(key(4), (m, n), dtype=jnp.float32)
    signs = jax.random.rademacher(key(5), (m,), dtype=jnp.float32)
    mp = 1024
    rows = jax.random.randint(key(6), (l,), 0, mp)
    got = srht_pallas(signs, a, rows)
    # oracle on the padded matrix
    ap = jnp.pad(signs[:, None] * a, ((0, mp - m), (0, 0)))
    want = fwht_ref(ap)[rows] * jnp.sqrt(mp / l)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


# -------------------------------------------------------------- sketch accum

def _skip_without_x64(dtype):
    """This module runs under the ambient x64 setting (no fixture): wide
    dtypes silently truncate when x64 is off, so skip rather than test
    the wrong precision.  (f64 streaming parity runs in test_stream.py,
    which pins x64.)"""
    if dtype in (jnp.float64, jnp.complex128) and not jax.config.jax_enable_x64:
        pytest.skip("x64 disabled in this lane")


@pytest.mark.parametrize("l,m,n", [(8, 128, 32), (64, 1000, 150),
                                   (100, 777, 129), (17, 64, 200)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_sketch_accum_sweep(l, m, n, dtype):
    """The replay contract, EXACT: one call over all of m is bit-equal to
    every ACCUM_BLOCK-multiple chunking of the rows.  Against the
    canonically-blocked oracle and a plain dot the kernel agrees within
    tolerance: its padded tiles are other GEMM shapes than the oracle's
    unpadded dots, and nothing promises those round alike."""
    from repro.kernels.sketch_accum import ACCUM_BLOCK, sketch_accum
    from repro.kernels.sketch_accum.ref import (accum_dtype_for,
                                                sketch_accum_ref)
    _skip_without_x64(dtype)
    x = jax.random.normal(key(7), (l, m), dtype=dtype)
    a = jax.random.normal(key(8), (m, n), dtype=dtype)
    adt = accum_dtype_for(dtype)
    acc0 = jax.random.normal(key(9), (l, n), dtype=adt)
    got = sketch_accum(x, a, acc0)
    for chunk in (ACCUM_BLOCK, 2 * ACCUM_BLOCK):
        acc = acc0
        for r0 in range(0, m, chunk):
            acc = sketch_accum(x[:, r0:r0 + chunk], a[r0:r0 + chunk], acc)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(acc))
    atol = (1e-4 if dtype == jnp.float32 else 1e-10) * np.sqrt(m)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(sketch_accum_ref(
                                   x.astype(adt), a.astype(adt), acc0)),
                               atol=atol)
    want = acc0 + jnp.dot(x, a, preferred_element_type=adt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol)


@pytest.mark.parametrize("dtype", [jnp.complex64, jnp.complex128])
def test_sketch_accum_complex_ref_path(dtype):
    from repro.kernels.sketch_accum import sketch_accum
    _skip_without_x64(dtype)
    rdt = jnp.float64 if dtype == jnp.complex128 else jnp.float32
    x = (jax.random.normal(key(1), (16, 300), rdt)
         + 1j * jax.random.normal(key(2), (16, 300), rdt)).astype(dtype)
    a = (jax.random.normal(key(3), (300, 40), rdt)
         + 1j * jax.random.normal(key(4), (300, 40), rdt)).astype(dtype)
    got = sketch_accum(x, a)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ a),
                               atol=(1e-3 if dtype == jnp.complex64
                                     else 1e-10))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64, jnp.complex64])
def test_sketch_accum_chunk_invariance(dtype):
    """The replay pin: canonical-multiple chunkings reproduce the one-shot
    accumulation BIT FOR BIT (incl. an uneven final chunk)."""
    from repro.kernels.sketch_accum import ACCUM_BLOCK, sketch_accum
    _skip_without_x64(dtype)
    rdt = jnp.float64 if dtype == jnp.float64 else jnp.float32
    m, l, n = 1000, 48, 70
    x = jax.random.normal(key(5), (l, m), rdt)
    a = jax.random.normal(key(6), (m, n), rdt)
    if jnp.issubdtype(dtype, jnp.complexfloating):
        x = (x + 1j * jax.random.normal(key(7), (l, m), rdt)).astype(dtype)
        a = (a + 1j * jax.random.normal(key(8), (m, n), rdt)).astype(dtype)
    one = sketch_accum(x, a)
    for chunk in (ACCUM_BLOCK, 3 * ACCUM_BLOCK):
        acc = None
        for r0 in range(0, m, chunk):
            r1 = min(r0 + chunk, m)
            acc = sketch_accum(x[:, r0:r1], a[r0:r1], acc)
        np.testing.assert_array_equal(np.asarray(one), np.asarray(acc))


def test_sketch_accum_validation():
    from repro.kernels.sketch_accum import sketch_accum
    x = jnp.zeros((4, 8), jnp.float32)
    with pytest.raises(ValueError, match=r"x columns \(8\) must match a "
                                         r"rows \(16\)"):
        sketch_accum(x, jnp.zeros((16, 3), jnp.float32))
    with pytest.raises(ValueError, match=r"acc shape \(4, 5\) must be "
                                         r"\(4, 3\)"):
        sketch_accum(x, jnp.zeros((8, 3), jnp.float32),
                     jnp.zeros((4, 5), jnp.float32))


# ----------------------------------------------------------------- cgs block

@pytest.mark.parametrize("l,k,n", [(16, 4, 30), (64, 16, 200), (128, 32, 513),
                                   (256, 64, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_project_out_sweep(l, k, n, dtype):
    q = jnp.linalg.qr(jax.random.normal(key(7), (l, k)))[0].astype(dtype)
    z = jax.random.normal(key(8), (l, n), dtype=dtype)
    got = project_out(q, z)
    want = project_out_ref(q, z)
    atol = 1e-4 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)
    if dtype == jnp.float32:
        # the residual really is orthogonal to the basis
        assert float(jnp.max(jnp.abs(q.T @ got))) < 1e-3


@pytest.mark.parametrize("l,b,n", [(16, 4, 30), (64, 32, 200), (256, 32, 513)])
def test_panel_deflate_matches_ref(l, b, n):
    q = jnp.linalg.qr(jax.random.normal(key(11), (l, b)))[0]
    z = jax.random.normal(key(12), (l, n), dtype=jnp.float32)
    got_o, got_w = panel_deflate(q, z)
    want_o, want_w = panel_deflate_ref(q, z)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o), atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w), atol=1e-4)
    # deflated slab is orthogonal to the panel; W really is Q^T Z
    assert float(jnp.max(jnp.abs(q.T @ got_o))) < 1e-3
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(q.T @ z),
                               atol=1e-4)


# --------------------------------------------------------------- panel gram

@pytest.mark.parametrize("l,b,n", [(16, 4, 30), (64, 32, 200), (256, 32, 513),
                                   (48, 7, 129)])
def test_panel_gram_matches_ref(l, b, n):
    c = jax.random.normal(key(13), (l, b), dtype=jnp.float32)
    z = jax.random.normal(key(14), (l, n), dtype=jnp.float32)
    got_g, got_v = panel_gram(c, z)
    want_g, want_v = panel_gram_ref(c, z)
    np.testing.assert_allclose(np.asarray(got_g), np.asarray(want_g), atol=1e-3)
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v), atol=1e-3)
    # the fused outputs really are the Gram and the coefficient block
    np.testing.assert_allclose(np.asarray(got_g), np.asarray(c.T @ c), atol=1e-3)
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(c.T @ z), atol=1e-3)


def test_panel_gram_complex_fallback():
    c = (jax.random.normal(key(15), (32, 8)) +
         1j * jax.random.normal(key(16), (32, 8))).astype(jnp.complex64)
    z = (jax.random.normal(key(17), (32, 50)) +
         1j * jax.random.normal(key(18), (32, 50))).astype(jnp.complex64)
    got_g, got_v = panel_gram(c, z)
    np.testing.assert_allclose(np.asarray(got_g), np.asarray(c.conj().T @ c),
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(c.conj().T @ z),
                               atol=1e-3)


# --------------------------------------------------------------- panel step

# (l, b, n) incl. remainder panels (b=7, b=2) and non-bn-divisible n.
PANEL_STEP_SHAPES = [(16, 4, 30), (64, 32, 200), (256, 32, 513),
                     (48, 7, 129), (64, 2, 100)]
PS_ATOL = {jnp.float32: 1e-3, jnp.float64: 1e-10,
           jnp.complex64: 1e-3, jnp.complex128: 1e-10}


def _randn(k, shape, dtype):
    rdt = jnp.float64 if dtype in (jnp.float64, jnp.complex128) else jnp.float32
    x = jax.random.normal(key(k), shape, rdt)
    if jnp.issubdtype(dtype, jnp.complexfloating):
        x = x + 1j * jax.random.normal(key(k + 100), shape, rdt)
    return x.astype(dtype)


@pytest.mark.parametrize("l,b,n", PANEL_STEP_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64,
                                   jnp.complex64, jnp.complex128])
def test_panel_step_matches_ref(l, b, n, dtype):
    """Fused panel step vs the pure-jnp oracle: orthonormal panel,
    deflated slab, coefficient block, and residual norms all agree
    (complex dtypes exercise the oracle fallback path end to end)."""
    if dtype in (jnp.float64, jnp.complex128):
        jax.config.update("jax_enable_x64", True)
    try:
        c = _randn(20, (l, b), dtype)
        z = _randn(21, (l, n), dtype)
        qp, o, w, r2 = panel_step(c, z)
        qpr, orf, wr, r2r = panel_step_ref(c, z)
        atol = PS_ATOL[dtype]
        np.testing.assert_allclose(np.asarray(qp), np.asarray(qpr), atol=atol)
        np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                                   atol=10 * atol)
        np.testing.assert_allclose(np.asarray(w), np.asarray(wr),
                                   atol=10 * atol)
        np.testing.assert_allclose(np.asarray(r2), np.asarray(r2r),
                                   atol=100 * atol)
        # the factor really is orthonormal and the slab really deflated
        orth = float(jnp.max(jnp.abs(qp.conj().T @ qp
                                     - jnp.eye(b, dtype=dtype))))
        assert orth < atol, orth
        assert float(jnp.max(jnp.abs(qp.conj().T @ o))) < 100 * atol
        # emit_w=False (the blocked-engine spelling) elides W, same rest
        qp2, o2, w2, r22 = panel_step(c, z, emit_w=False)
        assert w2 is None
        np.testing.assert_allclose(np.asarray(o2), np.asarray(o), atol=0)
        np.testing.assert_allclose(np.asarray(r22), np.asarray(r2), atol=0)
    finally:
        if dtype in (jnp.float64, jnp.complex128):
            jax.config.update("jax_enable_x64", False)


@pytest.mark.parametrize("l,b,n", [(64, 32, 200), (48, 7, 129)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.complex64])
def test_panel_coeff_apply_compose(l, b, n, dtype):
    """The split pair (stage A coeff+downdate, stage B apply) composes to
    the same deflation as the fused kernel, and the downdated norms
    match the recomputed norms of the deflated slab (Pythagoras for an
    orthonormal panel)."""
    c = _randn(22, (l, b), dtype)
    z = _randn(23, (l, n), dtype)
    res2 = jnp.sum(jnp.abs(z) ** 2, axis=0)
    qp, w, r2d = panel_coeff(c, z, res2)
    o = panel_apply(qp, w, z)
    qpr, orf, wr, r2r = panel_step_ref(c, z)
    atol = 10 * PS_ATOL[dtype]
    np.testing.assert_allclose(np.asarray(o), np.asarray(orf), atol=atol)
    np.testing.assert_allclose(np.asarray(w), np.asarray(wr), atol=atol)
    # downdate == recompute up to cancellation-scaled roundoff
    np.testing.assert_allclose(np.asarray(r2d), np.asarray(r2r),
                               atol=float(jnp.max(res2)) * 1e-3)
    # ref oracles agree with the naive formulas
    qc, wc, rc = panel_coeff_ref(c, z, res2)
    np.testing.assert_allclose(np.asarray(panel_apply_ref(qc, wc, z)),
                               np.asarray(z - qc @ wc), atol=0)


def test_panel_step_rank_deficient_detectable():
    """Rank-deficient candidates (duplicated columns) must surface as a
    caller-detectable failure — junk/non-finite factor, large
    ||Q^H Q - I|| — so the engines' per-column/Householder fallbacks
    trigger; they must NOT silently return a plausible-looking panel."""
    c10 = jax.random.normal(key(24), (64, 4), jnp.float32)
    c = jnp.concatenate([c10, c10], axis=1)              # rank 4, b=8
    z = jax.random.normal(key(25), (64, 100), jnp.float32)
    qp, o, w, r2 = panel_step(c, z)
    bad = (not bool(jnp.all(jnp.isfinite(qp)))) or \
        float(jnp.max(jnp.abs(qp.T @ qp - jnp.eye(8)))) > 1e-3
    assert bad, "degenerate panel produced a seemingly orthonormal factor"


# ------------------------------------------------------------------- tsolve

@pytest.mark.parametrize("k,n", [(4, 16), (32, 100), (100, 257), (128, 128),
                                 (200, 64)])
def test_tsolve_sweep(k, n):
    r1 = jnp.triu(jax.random.normal(key(9), (k, k), dtype=jnp.float32)) \
        + 3.0 * jnp.eye(k)
    r2 = jax.random.normal(key(10), (k, n), dtype=jnp.float32)
    got = tsolve(r1, r2)
    want = tsolve_ref(r1, r2)
    # both are f32 solves with different accumulation order; agreement is
    # bounded by the recurrence depth — compare with depth-scaled tolerance
    # and check the RESIDUAL (the invariant that actually matters) tightly.
    sol_scale = float(jnp.max(jnp.abs(want))) + 1e-6
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4 * k * sol_scale)
    resid = np.asarray(jnp.triu(r1) @ got - r2)
    assert np.max(np.abs(resid)) < 2e-5 * k * sol_scale


# -------------------------------------------------------------- flash attn

@pytest.mark.parametrize("S,T,H,hd,causal,window", [
    (100, 100, 3, 16, True, None),
    (65, 129, 2, 8, True, None),      # rectangular + padding
    (64, 64, 2, 16, True, 24),        # sliding window
    (48, 80, 1, 32, False, None),     # non-causal (whisper encoder)
])
def test_flash_attention_kernel(S, T, H, hd, causal, window):
    from repro.kernels.flash.ops import flash_attention
    from repro.kernels.flash.ref import flash_ref
    B = 2
    kq, kk, kv = jax.random.split(key(11), 3)
    q = jax.random.normal(kq, (B, S, H, hd), dtype=jnp.float32)
    k = jax.random.normal(kk, (B, T, H, hd), dtype=jnp.float32)
    v = jax.random.normal(kv, (B, T, H, hd), dtype=jnp.float32)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          bq=32, bk=32)
    tohm = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, t.shape[1], hd)
    want = flash_ref(tohm(q) * hd ** -0.5, tohm(k), tohm(v),
                     causal=causal, window=window)
    want = want.reshape(B, H, S, hd).transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
