"""Core algorithm tests: the paper's RID pipeline + property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:     # property tests skip cleanly without the dep
    st = None

    def _skip_property_test(*_args, **_kwargs):
        def deco(_fn):
            @pytest.mark.skip(reason="hypothesis not installed "
                                     "(pip install -r requirements-dev.txt)")
            def stub():
                pass
            stub.__name__ = getattr(_fn, "__name__", "property_test")
            return stub
        return deco

    given = settings = _skip_property_test

    class _StrategyStub:
        def __getattr__(self, _name):
            return lambda *a, **k: None
    st = _StrategyStub()

jax.config.update("jax_enable_x64", True)


@pytest.fixture(autouse=True, scope="module")
def _x64_scope():
    """f64 for this module only — leaking x64 into later modules changes
    weak-type promotion and flips near-tie argmaxes in the LM tests."""
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


from repro.core import (cgs2_pivoted_qr, cholesky_qr2, error_bound,
                        expected_sigma_kp1, gaussian_sketch, householder_qr,
                        rid, rsvd, spectral_error, spectral_norm_dense,
                        srft_sketch, srht_sketch)
from repro.core.sketch import fwht
from repro.core.tsolve import (interp_from_qr, solve_upper_triangular,
                               solve_upper_triangular_xla)


def lowrank(key, m, n, k, dtype=jnp.float64, cplx=False):
    kb, kp, kb2, kp2 = jax.random.split(key, 4)
    B = jax.random.normal(kb, (m, k), dtype=dtype)
    P = jax.random.normal(kp, (k, n), dtype=dtype)
    if cplx:
        B = B + 1j * jax.random.normal(kb2, (m, k), dtype=dtype)
        P = P + 1j * jax.random.normal(kp2, (k, n), dtype=dtype)
    return B @ P


# ------------------------------------------------------------------ sketches

@pytest.mark.parametrize("kind,cplx", [("srft", True), ("srft", False),
                                       ("srht", False), ("gaussian", True),
                                       ("gaussian", False)])
def test_sketch_preserves_rank(kind, cplx):
    key = jax.random.key(0)
    m, n, k = 300, 200, 12
    A = lowrank(key, m, n, k, cplx=cplx)
    fn = {"srft": srft_sketch, "srht": srht_sketch,
          "gaussian": gaussian_sketch}[kind]
    Y = fn(jax.random.key(1), A, 2 * k)
    s = jnp.linalg.svd(Y, compute_uv=False)
    assert float(s[k - 1]) > 1e-8            # rank at least k survives
    assert float(s[k] / s[0]) < 1e-10        # and not more than k


def _srft_draws(key, m, l):
    """``srft_sketch``'s ``D`` and ``S`` from ``key``, drawn here as the
    paper's eq. (5) and (7) say, independently of the module's code."""
    kphase, krows = jax.random.split(key)
    phi = jax.random.uniform(kphase, (m,), dtype=jnp.float64)
    d = jnp.exp((2j * jnp.pi) * phi)
    rows = jax.random.randint(krows, (l,), 0, m, dtype=jnp.int32)
    return d, rows


@pytest.mark.parametrize("m", [256, 300])
@pytest.mark.parametrize("cplx", [True, False])
@pytest.mark.parametrize("path", ["dense", "fft"])
def test_srft_paths_match_explicit_fft(path, cplx, m):
    """Each way of applying ``S F D`` is the explicit transform:
    ``fft(d[:, None] * A, axis=0)[rows] / sqrt(l)`` from the same keys,
    for complex and real ``A`` and an ``m`` that is not a power of two;
    ``srft_sketch`` itself takes the path ``srft_path`` picks."""
    from repro.core.sketch import _srft_dense, _srft_fft, srft_path
    key, l = jax.random.key(21), 24
    A = lowrank(jax.random.key(22), m, 40, 6, cplx=cplx)
    d, rows = _srft_draws(key, m, l)
    ref = np.fft.fft(np.asarray(d)[:, None] * np.asarray(A),
                     axis=0)[np.asarray(rows)] / np.sqrt(l)
    apply = {"dense": _srft_dense, "fft": _srft_fft}[path]
    Y = apply(d, rows, A, l)
    assert Y.dtype == jnp.complex128
    scale = np.abs(ref).max()
    np.testing.assert_allclose(np.asarray(Y), ref, atol=1e-10 * scale)
    if srft_path(l, A.dtype) == path:
        np.testing.assert_allclose(np.asarray(srft_sketch(key, A, l)), ref,
                                   atol=1e-10 * scale)


@pytest.mark.parametrize("m", [2 ** 18, 46349])
def test_srft_operator_twiddle_index_past_int32(m):
    """The dense operator's twiddle index ``rows_j * i mod m`` is reduced in
    32-bit integers where ``rows_j * i`` passes 2^31 (m > 46340): rows of
    ``W`` at the grid's largest m and at an odd m, against int64 and
    float64 in numpy."""
    from repro.core.sketch import _mulmod, _srft_operator
    l = 3
    rows = jnp.array([m - 1, m // 2 + 1, 40503], jnp.int32)
    i = jnp.arange(m, dtype=jnp.int32)
    t = (np.asarray(rows, np.int64)[:, None] * np.arange(m)) % m
    assert t.max() == m - 1 and int(rows[0]) * (m - 1) >= 2 ** 31
    np.testing.assert_array_equal(
        np.asarray(_mulmod(rows[:, None], i[None, :], m)), t)
    d, _ = _srft_draws(jax.random.key(23), m, l)
    ref = (np.exp(-2j * np.pi * t / m) * np.asarray(d)[None, :]
           / np.sqrt(l))
    np.testing.assert_allclose(np.asarray(_srft_operator(d, rows, l)), ref,
                               atol=1e-12)


@pytest.mark.parametrize("l,dtype,path", [
    (200, jnp.complex64, "dense"), (800, jnp.complex64, "fft"),
    (2000, jnp.complex64, "fft"), (1200, jnp.float32, "dense"),
    (1600, jnp.float32, "fft")])
def test_srft_path_rule(l, dtype, path):
    """The shape rule at m = 2^14: the paper grid's l = 200 (rows 1-2)
    takes the GEMM; its l = 800 (row 3) and 2000 (rows 6 and 8) keep the
    FFT; a real ``A`` halves the GEMM and moves the crossover to twice the
    l (the measured table in PERF.md)."""
    from repro.core.sketch import srft_path
    assert srft_path(l, dtype) == path


def test_fwht_orthonormal():
    key = jax.random.key(2)
    x = jax.random.normal(key, (256, 33), dtype=jnp.float64)
    y = fwht(x)
    # orthonormal transform: norms preserved, self-inverse
    np.testing.assert_allclose(np.linalg.norm(y, axis=0),
                               np.linalg.norm(x, axis=0), rtol=1e-12)
    np.testing.assert_allclose(np.asarray(fwht(y)), np.asarray(x), atol=1e-12)


# ------------------------------------------------------------------------ QR

def test_cgs2_pivoted_qr_invariants():
    key = jax.random.key(3)
    Y = lowrank(key, 64, 200, 20, cplx=True)
    qr = cgs2_pivoted_qr(Y, 20)
    QhQ = np.asarray(qr.Q.conj().T @ qr.Q)
    np.testing.assert_allclose(QhQ, np.eye(20), atol=1e-12)   # orthonormal
    # R1 (pivot-ordered) is upper triangular up to roundoff
    R1 = np.asarray(jnp.take(qr.R, qr.piv, axis=1))
    assert np.max(np.abs(np.tril(R1, -1))) < 1e-10
    # pivots unique
    assert len(set(np.asarray(qr.piv).tolist())) == 20
    # Q R reconstructs the rank-k matrix
    np.testing.assert_allclose(np.asarray(qr.Q @ qr.R), np.asarray(Y),
                               atol=1e-9)


@pytest.mark.parametrize("fn", [householder_qr, cholesky_qr2])
def test_panel_qr(fn):
    key = jax.random.key(4)
    Y = jax.random.normal(key, (96, 24), dtype=jnp.float64)
    Q, R = fn(Y)
    np.testing.assert_allclose(np.asarray(Q.T @ Q), np.eye(24), atol=1e-12)
    np.testing.assert_allclose(np.asarray(Q @ R), np.asarray(Y), atol=1e-10)
    assert np.max(np.abs(np.tril(np.asarray(R), -1))) < 1e-12


# -------------------------------------------------------------------- tsolve

def test_tsolve_matches_xla():
    key = jax.random.key(5)
    k, n = 40, 130
    R1 = jnp.triu(jax.random.normal(key, (k, k), dtype=jnp.float64)) \
        + 4 * jnp.eye(k, dtype=jnp.float64)
    R2 = jax.random.normal(jax.random.key(6), (k, n), dtype=jnp.float64)
    T1 = solve_upper_triangular(R1, R2)
    T2 = solve_upper_triangular_xla(R1, R2)
    np.testing.assert_allclose(np.asarray(T1), np.asarray(T2), atol=1e-10)
    np.testing.assert_allclose(np.asarray(jnp.triu(R1) @ T1),
                               np.asarray(R2), atol=1e-10)


# ---------------------------------------------------------------------- RID

@pytest.mark.parametrize("kind,cplx,dtype", [
    ("srft", True, jnp.complex128), ("srft", False, jnp.float64),
    ("srht", False, jnp.float64), ("srht", False, jnp.float32),
    ("gaussian", True, jnp.complex128), ("gaussian", False, jnp.float32),
])
def test_rid_reconstructs(kind, cplx, dtype):
    key = jax.random.key(7)
    m, n, k = 400, 300, 15
    rdt = jnp.float64 if dtype in (jnp.float64, jnp.complex128) else jnp.float32
    A = lowrank(key, m, n, k, dtype=rdt, cplx=cplx)
    dec = rid(jax.random.key(8), A, k, sketch_kind=kind)
    err = float(spectral_norm_dense(A - dec.reconstruct()))
    scale = float(spectral_norm_dense(A))
    tol = 1e-9 if rdt == jnp.float64 else 1e-3
    assert err / scale < tol
    # P carries an exact identity at the pivot columns (paper eq. 11)
    Pp = np.asarray(jnp.take(dec.P, dec.J, axis=1))
    np.testing.assert_allclose(Pp, np.eye(k), atol=0)
    # B is an exact column subset
    np.testing.assert_allclose(np.asarray(dec.B),
                               np.asarray(A[:, np.asarray(dec.J)]), atol=0)


@pytest.mark.parametrize("outer_jit", [False, True])
@pytest.mark.parametrize("piv,n,path", [
    ("unsorted", 640, "slices"), ("unsorted", 48, "take"),
    ("k1", 640, "slices"), ("k1", 48, "take"), ("kn", 48, "take")])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.complex64, jnp.float64,
                                   jnp.complex128])
def test_take_is_the_column_gather(dtype, piv, n, path, outer_jit):
    """``_take(A, piv)`` is ``A[:, piv]`` bit for bit on both sides of
    ``gather_path``: pivots unsorted, the first and last columns among
    them, ``k = 1`` and ``k = n``, alone and inside a caller's jit."""
    from repro.core.rid import _take, gather_path
    piv = {"unsorted": [n - 1, 3, 0, 22, 5], "k1": [17],
           "kn": list(range(n))[::-1]}[piv]
    assert gather_path(len(piv), n) == path
    A = lowrank(jax.random.key(30), 64, n, 4, dtype=jnp.finfo(dtype).dtype,
                cplx=jnp.issubdtype(dtype, jnp.complexfloating))
    assert A.dtype == dtype
    J = jnp.asarray(piv, jnp.int32)
    take = jax.jit(lambda A, J: _take(A, J)) if outer_jit else _take
    B = take(A, J)
    assert B.dtype == A.dtype
    np.testing.assert_array_equal(np.asarray(B), np.asarray(A)[:, piv])


@pytest.mark.parametrize("n,path", [(512, "slices"), (48, "take")])
def test_take_touches_no_whole_matrix(n, path):
    """On the slices side the gather's program holds no ``gather`` and no
    equation with as many elements as ``A``: each step moves one column.
    Past the crossover it is the one ``gather`` of ``jnp.take``."""
    from repro.analysis.jaxpr import iter_eqns
    from repro.core.rid import _take, gather_path
    m, k = 64, 4
    assert gather_path(k, n) == path
    jaxpr = jax.make_jaxpr(_take)(jax.ShapeDtypeStruct((m, n), jnp.float32),
                                  jax.ShapeDtypeStruct((k,), jnp.int32))
    eqns = list(iter_eqns(jaxpr.jaxpr))
    prims = {e.primitive.name for e in eqns}
    if path == "slices":
        assert "dynamic_slice" in prims and "gather" not in prims
        assert all(np.prod(v.aval.shape) < m * n for e in eqns
                   for v in e.outvars)
    else:
        assert "gather" in prims and "while" not in prims


@pytest.mark.parametrize("k,n,path", [
    (100, 2 ** 14, "slices"), (400, 2 ** 14, "take"),
    (1000, 2 ** 16, "take"), (1000, 2 ** 18, "slices"),
    (128, 2 ** 14, "slices"), (129, 2 ** 14, "take")])
def test_gather_path_rule(k, n, path):
    """The shape rule: the paper grid's k = 100 of n = 2^14 (rows 1-2) and
    k = 1000 of 2^18 (row 8) copy columns; k = 400 of 2^14 (rows 3-4) and
    1000 of 2^16 (row 6) take; the crossover sits at k = n / 128 (the
    measured table in PERF.md)."""
    from repro.core.rid import gather_path
    assert gather_path(k, n) == path


def test_rsvd_matches_dense_svd():
    key = jax.random.key(9)
    A = lowrank(key, 300, 220, 10, cplx=True)
    out = rsvd(jax.random.key(10), A, 10)
    s_dense = np.linalg.svd(np.asarray(A), compute_uv=False)[:10]
    np.testing.assert_allclose(np.asarray(out.S), s_dense, rtol=1e-8)
    err = float(spectral_norm_dense(A - out.reconstruct()))
    assert err < 1e-8 * s_dense[0]


def test_spectral_error_estimator():
    key = jax.random.key(11)
    A = lowrank(key, 200, 150, 8)
    dec = rid(jax.random.key(12), A, 6)      # under-rank: non-trivial error
    est = float(spectral_error(jax.random.key(13), A, dec.B, dec.P, iters=60))
    exact = float(spectral_norm_dense(A - dec.B @ dec.P))
    assert abs(est - exact) / exact < 0.05


# --------------------------------------------------------------- properties

@settings(max_examples=15, deadline=None)
@given(st.integers(2, 24), st.integers(0, 4), st.integers(0, 4),
       st.booleans(), st.sampled_from(["srft", "srht", "gaussian"]))
def test_property_rid_error_bound(k, dm, dn, cplx, kind):
    """Paper eq. (3): ||A - BP||_2 <= 50 sqrt(mn) (1/eps)^(1/k) sigma_{k+1},
    checked on exactly-rank-k matrices where sigma_{k+1} is roundoff."""
    m, n = 80 + 37 * dm, 64 + 29 * dn
    key = jax.random.key(k * 1000 + dm * 100 + dn * 10 + cplx)
    A = lowrank(key, m, n, min(k, m, n), cplx=cplx)
    dec = rid(jax.random.fold_in(key, 1), A, k, sketch_kind=kind)
    err = float(spectral_norm_dense(A - dec.reconstruct()))
    sigma_floor = expected_sigma_kp1(m, n)   # paper's noise-floor estimate
    assert err <= error_bound(m, n, k, eps=1e-20) * sigma_floor * 10


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 12), st.integers(1, 3))
def test_property_rid_idempotent_on_exact_rank(k, seed):
    """Decomposing an exactly rank-k matrix at rank k is (near-)exact and
    reconstruction is a projection: rid(BP) == BP (numerically)."""
    key = jax.random.key(seed)
    A = lowrank(key, 150, 120, k)
    dec = rid(jax.random.fold_in(key, 2), A, k, sketch_kind="gaussian")
    A2 = dec.reconstruct()
    dec2 = rid(jax.random.fold_in(key, 3), A2, k, sketch_kind="gaussian")
    assert float(spectral_norm_dense(A2 - dec2.reconstruct())) < 1e-9 * \
        max(1.0, float(spectral_norm_dense(A2)))


# ------------------------------------------------------------- precision

def _stage(name):
    """``(fn, args)`` of one jit stage of the decomposition, f32."""
    from jax.sharding import Mesh

    from repro.core import rid_distributed
    from repro.core.rid import _qr_interp
    from repro.kernels import sketch_accum
    from repro.stream.rid_stream import _sharded_qr_interp_fn
    S = jax.ShapeDtypeStruct
    f32 = jnp.float32
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    return {
        "srft_sketch_c64": (lambda key, A: srft_sketch(key, A, 24),
                            (jax.random.key(0), S((256, 400), jnp.complex64))),
        "srft_sketch_f32": (lambda key, A: srft_sketch(key, A, 24),
                            (jax.random.key(0), S((256, 400), f32))),
        "sketch_accum": (sketch_accum, (S((48, 256), f32), S((256, 400), f32))),
        "qr_interp": (lambda Y: _qr_interp(Y, 21, "blocked", 7, "auto"),
                      (S((48, 400), f32),)),
        "sharded_qr_interp": (_sharded_qr_interp_fn(mesh, "data", 21, 7,
                                                    "auto"),
                              (S((48, 400), f32),)),
        "rid_distributed": (lambda key, A: rid_distributed(
            key, A, 21, mesh=mesh, sketch_kind="gaussian",
            qr_impl="panel_parallel", qr_panel=7).P,
            (jax.random.key(0), S((256, 400), f32))),
    }[name]


@pytest.mark.parametrize("name", ["srft_sketch_c64", "srft_sketch_f32",
                                  "sketch_accum", "qr_interp",
                                  "sharded_qr_interp", "rid_distributed"])
def test_main_path_matmuls_at_full_precision(name):
    """Every matmul a stage traces — XLA's and the Pallas kernels' —
    carries ``HIGHEST``.  At a TPU's default precision (one bfloat16
    pass) the ID was wrong at paper grid row 8 on a v5e (relative error
    above 1), while the CPU computes f32 dots in full either way, so no
    numeric test here would notice the pin going missing."""
    from repro.analysis.jaxpr import iter_eqns
    fn, args = _stage(name)
    dots = [e for e in iter_eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if e.primitive.name == "dot_general"]
    assert dots
    highest = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
    assert all(e.params["precision"] == highest for e in dots), \
        {e.params["precision"] for e in dots}


# ------------------------------------------------------------ tracing

@pytest.mark.parametrize("kind,cplx,dtype", [
    ("srft", True, jnp.complex64), ("gaussian", False, jnp.float32)])
def test_rid_bits_unchanged_by_tracing(kind, cplx, dtype):
    """The spans time the host's dispatch of each stage and change no
    program: ``B``, ``P`` and ``J`` are bit-identical under a tracer."""
    from repro.obs import tracing
    A = lowrank(jax.random.key(3), 96, 80, 6, dtype=jnp.float32,
                cplx=cplx).astype(dtype)
    base = rid(jax.random.key(4), A, 6, sketch_kind=kind)
    with tracing():
        traced = rid(jax.random.key(4), A, 6, sketch_kind=kind)
    for name in ("B", "P", "J"):
        np.testing.assert_array_equal(np.asarray(getattr(base, name)),
                                      np.asarray(getattr(traced, name)))


def test_rid_span_tree():
    """``rid`` > ``rid.sketch`` / ``rid.qr_interp`` / ``rid.gather``, the
    root carrying the shape, ``rid.sketch``, for srft, the path
    ``srft_path`` picks and ``rid.gather`` the path ``gather_path`` picks;
    ``rid_from_sketch`` alone opens its two."""
    from repro.core import rid_from_sketch
    from repro.core.sketch import SRFT_DENSE_MAX_PLANE_ROWS
    from repro.obs import tracing
    A = lowrank(jax.random.key(5), 64, 48, 4, dtype=jnp.float32)
    with tracing() as tr:
        rid(jax.random.key(6), A, 4, sketch_kind="gaussian")
    spans = [s for s in sorted(tr.spans, key=lambda s: s.index)
             if s.name != "jax.compile"]
    root, *children = spans
    assert (root.name, root.parent, root.depth) == ("rid", None, 0)
    assert root.attrs == {"m": 64, "n": 48, "k": 4, "l": 8,
                          "sketch_kind": "gaussian"}
    assert [(s.name, s.parent, s.depth) for s in children] == [
        ("rid.sketch", root.index, 1), ("rid.qr_interp", root.index, 1),
        ("rid.gather", root.index, 1)]
    assert all(root.t0 <= s.t0 and s.t1 <= root.t1 for s in children)
    assert children[0].attrs == {}
    assert children[2].attrs == {"gather_path": "take"}
    for n, path in ((512, "slices"), (48, "take")):
        B = lowrank(jax.random.key(5), 64, n, 4, dtype=jnp.float32)
        with tracing() as tr:
            rid(jax.random.key(6), B, 4, sketch_kind="gaussian")
        assert [s.attrs for s in tr.spans if s.name == "rid.gather"] == [
            {"gather_path": path}]
    for l, path in ((8, "dense"), (SRFT_DENSE_MAX_PLANE_ROWS + 1, "fft")):
        with tracing() as tr:
            rid(jax.random.key(6), A, 4, l=l, sketch_kind="srft")
        assert [s.attrs for s in tr.spans if s.name == "rid.sketch"] == [
            {"srft_path": path}]
    Y = gaussian_sketch(jax.random.key(6), A, 8)
    with tracing() as tr:
        rid_from_sketch(A, Y, 4)
    assert [(s.name, s.parent) for s in sorted(tr.spans,
                                               key=lambda s: s.index)
            if s.name != "jax.compile"] == [
        ("rid.qr_interp", None), ("rid.gather", None)]


def test_rid_under_caller_jit_records_no_span():
    """Inside a caller's jit ``A`` is a tracer: a span would time the
    trace, so none opens; the caller's compile is all the tracer sees."""
    from repro.obs import tracing
    A = lowrank(jax.random.key(9), 64, 48, 4, dtype=jnp.float32)

    @jax.jit
    def caller(A):
        return rid(jax.random.key(1), A, 4, sketch_kind="gaussian").J

    with tracing() as tr:
        J = caller(A)
    assert {s.name for s in tr.spans} == {"jax.compile"}
    np.testing.assert_array_equal(
        np.asarray(J),
        np.asarray(rid(jax.random.key(1), A, 4, sketch_kind="gaussian").J))
