"""Panel-parallel distributed QRCP (core.qr_dist) — multi-device parity
against the replicated engines, edge panels, and the no-replication
guarantee (multi-device cases run in subprocesses with 8 fake CPU devices,
per conftest; validation paths run in-process on a 1-device mesh)."""
import jax
import jax.numpy as jnp
import pytest

from jax import make_mesh
from jax.sharding import AxisType
from repro.core import rid_distributed


# A subprocess preamble shared by the 8-device tests: builds the mesh and a
# deterministic low-rank A, and defines the QR-quality metrics.
PRELUDE = """
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_enable_x64", True)
from jax import make_mesh
from jax.sharding import AxisType
from repro.core import (rid_distributed, shard_columns, spectral_norm_dense,
                        panel_parallel_pivoted_qr)
from repro.core.qr import cgs2_pivoted_qr, blocked_pivoted_qr

mesh = make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))

def lowrank(key, m, n, r, cplx=False):
    kb, kp, kb2, kp2 = jax.random.split(key, 4)
    B = jax.random.normal(kb, (m, r))
    P = jax.random.normal(kp, (r, n))
    if cplx:
        B = B + 1j * jax.random.normal(kb2, (m, r))
        P = P + 1j * jax.random.normal(kp2, (r, n))
    return B @ P

def recon_err(Y, qr):
    R1 = jnp.triu(jnp.take(qr.R, qr.piv, axis=1))
    return float(jnp.linalg.norm(jnp.take(Y, qr.piv, axis=1) - qr.Q @ R1))

def orth_err(qr):
    k = qr.Q.shape[1]
    return float(jnp.max(jnp.abs(qr.Q.conj().T @ qr.Q
                                 - jnp.eye(k, dtype=qr.Q.dtype))))
"""


def test_rid_panel_parallel_matches_oracles(subproc):
    """All three engines hit the oracle-grade ID error on the same sharded
    input; panel_parallel's pivot SET matches the replicated blocked
    engine's (same selection rule, psum-assembled statistics)."""
    r = subproc(PRELUDE + """
key = jax.random.key(0)
m, n, k = 512, 400, 12
A = shard_columns(lowrank(key, m, n, k), mesh, "data")
scale = float(spectral_norm_dense(jnp.asarray(A)))
errs, pivs = {}, {}
for impl in ("cgs2", "blocked", "panel_parallel"):
    dec = rid_distributed(jax.random.key(2), A, k, mesh=mesh, axis="data",
                          sketch_kind="gaussian", qr_impl=impl)
    errs[impl] = float(spectral_norm_dense(jnp.asarray(A) - dec.B @ dec.P)) / scale
    pivs[impl] = set(np.asarray(dec.J).tolist())
    assert len(pivs[impl]) == k, (impl, pivs[impl])
    Pp = np.asarray(jnp.take(dec.P, dec.J, axis=1))
    np.testing.assert_allclose(Pp, np.eye(k), atol=1e-12)
# acceptance bar: within 2x of the replicated oracle's relative error
# (plus an fp floor: on exact-rank inputs every engine sits at roundoff)
floor = 1e-13
assert errs["panel_parallel"] <= 2 * max(errs["cgs2"], floor), errs
assert errs["panel_parallel"] <= 2 * max(errs["blocked"], floor), errs
assert pivs["panel_parallel"] == pivs["blocked"], (pivs["panel_parallel"],
                                                  pivs["blocked"])
print("OK", errs)
""")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_qr_parity_remainder_and_k_equals_l(subproc):
    """Standalone sharded QR: remainder panels (k % panel != 0) and the
    square k == l case factor to oracle-grade residuals."""
    r = subproc(PRELUDE + """
key = jax.random.key(1)
# remainder panels: k=23, panel=7 -> panels 7,7,7,2
l, n, k = 48, 400, 23
Y = lowrank(key, l, n, k)
qr_pp = panel_parallel_pivoted_qr(shard_columns(Y, mesh, "data"), k,
                                  mesh=mesh, axis="data", panel=7)
qr_or = cgs2_pivoted_qr(Y, k)
scale = float(jnp.linalg.norm(Y))
assert orth_err(qr_pp) < 1e-12, orth_err(qr_pp)
assert recon_err(Y, qr_pp) <= 10 * recon_err(Y, qr_or) + 1e-11 * scale
assert len(set(np.asarray(qr_pp.piv).tolist())) == k
# k == l: Q square orthonormal
l2 = 24
Y2 = lowrank(jax.random.key(2), l2, 400, l2)
qr2 = panel_parallel_pivoted_qr(shard_columns(Y2, mesh, "data"), l2,
                                mesh=mesh, axis="data", panel=8)
assert orth_err(qr2) < 1e-12, orth_err(qr2)
scale2 = float(jnp.linalg.norm(Y2))
assert recon_err(Y2, qr2) <= 10 * recon_err(Y2, cgs2_pivoted_qr(Y2, l2)) \\
    + 1e-11 * scale2
print("OK")
""")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_rid_panel_parallel_complex(subproc):
    """Complex dtype flows through the whole distributed pipeline (the
    panel_gram kernel falls back to its oracle formula for complex)."""
    r = subproc(PRELUDE + """
key = jax.random.key(3)
m, n, k = 256, 320, 10
A = shard_columns(lowrank(key, m, n, k, cplx=True), mesh, "data")
dec = rid_distributed(jax.random.key(4), A, k, mesh=mesh, axis="data",
                      sketch_kind="gaussian", qr_impl="panel_parallel",
                      qr_panel=4)
err = float(spectral_norm_dense(jnp.asarray(A) - dec.B @ dec.P)) / \\
    float(spectral_norm_dense(jnp.asarray(A)))
assert err < 1e-11, err
assert len(set(np.asarray(dec.J).tolist())) == k
print("OK", err)
""")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_no_full_sketch_allgather_in_hlo(subproc):
    """The acceptance-criterion inspection: the panel-parallel lowering
    contains NO l x n (or larger) all-gather — per-device sketch storage
    stays O(l n/ndev + l panel) — while the replicated path's lowering
    does contain one (positive control for the regex)."""
    r = subproc(PRELUDE + """
import re
from jax.sharding import NamedSharding, PartitionSpec as P
m, n, k = 256, 320, 12
l = 2 * k
A = jax.ShapeDtypeStruct((m, n), jnp.float64,
                         sharding=NamedSharding(mesh, P(None, "data")))

def lower_text(impl):
    def run(key, A):
        dec = rid_distributed(key, A, k, mesh=mesh, axis="data",
                              sketch_kind="gaussian", qr_impl=impl)
        return dec.B, dec.P
    return jax.jit(run).lower(jax.random.key(5), A).compile().as_text()

AG = re.compile(r"f\\d+\\[(\\d+),(\\d+)\\][^\\n]*all-gather")
def ln_gathers(txt):
    return [(int(a), int(b)) for a, b in AG.findall(txt)
            if int(a) * int(b) >= l * n]

assert ln_gathers(lower_text("cgs2")), "control failed: replicated path " \\
    "should all-gather the l x n sketch"
big = ln_gathers(lower_text("panel_parallel"))
assert not big, f"panel_parallel materializes an l x n gather: {big}"
print("OK")
""")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_fused_matches_gram_oracle_8dev(subproc):
    """ISSUE-3 parity: the fused panel step (panel_impl='fused', the
    default) against the PR-2 split 'gram' oracle on the same sharded
    sketch, 8 fake devices, multi-panel (panel=4 on k=12), real AND
    complex: identical pivot sets, oracle-grade factors both."""
    r = subproc(PRELUDE + """
key = jax.random.key(7)
l, n, k = 48, 400, 12
for cplx in (False, True):
    Y = lowrank(key, l, n, k, cplx=cplx)
    Ysh = shard_columns(Y, mesh, "data")
    qr_f = panel_parallel_pivoted_qr(Ysh, k, mesh=mesh, axis="data",
                                     panel=4, panel_impl="fused")
    qr_g = panel_parallel_pivoted_qr(Ysh, k, mesh=mesh, axis="data",
                                     panel=4, panel_impl="gram")
    assert set(np.asarray(qr_f.piv).tolist()) == \\
        set(np.asarray(qr_g.piv).tolist()), (cplx, qr_f.piv, qr_g.piv)
    assert len(set(np.asarray(qr_f.piv).tolist())) == k
    scale = float(jnp.linalg.norm(Y))
    orc = cgs2_pivoted_qr(Y, k)
    for tag, qr in (("fused", qr_f), ("gram", qr_g)):
        assert orth_err(qr) < 1e-12, (cplx, tag, orth_err(qr))
        assert recon_err(Y, qr) <= 10 * recon_err(Y, orc) + 1e-11 * scale
print("OK")
""")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_fused_norm_recompute_parity_8dev(subproc):
    """ISSUE-4: the periodic norm-RECOMPUTE panel on 8 devices.  With the
    cadence pinned to 1 every psum carries the deflated shards' exact
    norms (the ``panel_apply`` recompute kernel mode + the same scatter
    psum), so the fused engine must match the always-recomputing 'gram'
    oracle pivot-for-pivot; cadence 2 mixes downdated and exact panels
    and must stay oracle-grade too."""
    r = subproc(PRELUDE + """
key = jax.random.key(8)
l, n, k = 48, 400, 24
Y = lowrank(key, l, n, k)
Ysh = shard_columns(Y, mesh, "data")
qr_g = panel_parallel_pivoted_qr(Ysh, k, mesh=mesh, axis="data", panel=4,
                                 panel_impl="gram")
orc = cgs2_pivoted_qr(Y, k)
scale = float(jnp.linalg.norm(Y))
for nr in (1, 2):
    qr_r = panel_parallel_pivoted_qr(Ysh, k, mesh=mesh, axis="data",
                                     panel=4, norm_recompute=nr)
    assert set(np.asarray(qr_r.piv).tolist()) == \\
        set(np.asarray(qr_g.piv).tolist()), (nr, qr_r.piv, qr_g.piv)
    assert len(set(np.asarray(qr_r.piv).tolist())) == k
    assert orth_err(qr_r) < 1e-12, (nr, orth_err(qr_r))
    assert recon_err(Y, qr_r) <= 10 * recon_err(Y, orc) + 1e-11 * scale
print("OK")
""")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_norm_psum_overlaps_deflation(subproc):
    """The double-buffered-collectives acceptance check, on the lowering
    (through the ``repro.analysis`` contracts — the same rule CI runs):

    (1) dependency structure — the norm psum that selects panel p+1's
        pivots must NOT consume the output of panel p's deflation kernel
        (stage B ``panel_apply``): the collective is issued from stage
        A's downdated norms, so the scheduler is free to overlap it with
        the deflation GEMM.  It MUST still depend on earlier panels'
        deflations (the rule's built-in positive control), and on the
        'gram' oracle path the same psum DOES consume the deflated shard
        (the serialization the fused path removes) — plus a probe that
        holds the gram schedule to the fused expectation and demands the
        alarm.
    (2) the compiled HLO still contains zero l x n (or larger)
        all-gathers — the overlap did not reintroduce replication."""
    r = subproc(PRELUDE + """
import re
from repro.analysis.jaxpr import analyze_entry
from repro.analysis.registry import (EntryPoint, OverlapSpec, get,
                                     load_entry_points)

load_entry_points()

# (1) via the registered contracts — exactly what the CI analyze job
# re-proves: the fused entry satisfies the overlap rule (including its
# built-in previous-panel cone control), and the gram entry passes its
# serialized positive control (the analyzer DETECTED the serialization).
for name in ("panel_parallel_qr_local.fused",
             "panel_parallel_qr_local.gram"):
    findings = analyze_entry(get(name))
    assert not findings, (name, [(f.rule, f.key, f.message)
                                 for f in findings])

# Regression probe: hold the serialized gram schedule to the FUSED
# expectation — the overlap rule must fire, proving the clean fused
# result above is a detection, not silence.
g = get("panel_parallel_qr_local.gram")
probe = EntryPoint(name="probe.gram-as-fused", build=g.build,
                   overlap=OverlapSpec(norm_shape=(400,), deflate="sub",
                                       deflate_shape=(48, -1),
                                       expect_overlap=True))
fs = analyze_entry(probe)
assert any(f.rule == "jaxpr.collective-overlap" for f in fs), \\
    [(f.rule, f.key) for f in fs]

# (2) compiled HLO of the full distributed RID keeps zero l x n gathers
from jax.sharding import NamedSharding, PartitionSpec as P
l, n, k, b = 48, 400, 21, 7
m = 256
A = jax.ShapeDtypeStruct((m, n), jnp.float64,
                         sharding=NamedSharding(mesh, P(None, "data")))
def run(key, A):
    dec = rid_distributed(key, A, k, mesh=mesh, axis="data",
                          sketch_kind="gaussian", qr_impl="panel_parallel",
                          qr_panel=b)
    return dec.B, dec.P
txt = jax.jit(run).lower(jax.random.key(5), A).compile().as_text()
AG = re.compile(r"f\\d+\\[(\\d+),(\\d+)\\][^\\n]*all-gather")
big = [(int(a), int(c)) for a, c in AG.findall(txt)
       if int(a) * int(c) >= (2 * k) * n]
assert not big, f"fused panel-parallel path materializes l x n: {big}"
print("OK")
""")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


# ------------------------------------------------- validation (in-process)

def _one_dev_mesh():
    return make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))


def test_rid_distributed_validates_l_ge_k():
    A = jnp.zeros((32, 16))
    with pytest.raises(ValueError, match="need l >= k"):
        rid_distributed(jax.random.key(0), A, 8, l=4, mesh=_one_dev_mesh())


def test_rid_distributed_validates_k_le_min_l_n():
    A = jnp.zeros((32, 6))
    with pytest.raises(ValueError, match="need 0 < k <= min"):
        rid_distributed(jax.random.key(0), A, 8, mesh=_one_dev_mesh())


def test_rid_distributed_validates_qr_impl():
    A = jnp.zeros((32, 16))
    with pytest.raises(ValueError, match="unknown qr impl"):
        rid_distributed(jax.random.key(0), A, 4, mesh=_one_dev_mesh(),
                        qr_impl="nope")


def test_rid_distributed_validates_qr_panel():
    A = jnp.zeros((32, 16))
    with pytest.raises(ValueError, match="need qr_panel >= 1"):
        rid_distributed(jax.random.key(0), A, 4, mesh=_one_dev_mesh(),
                        qr_impl="panel_parallel", qr_panel=0)


def test_rid_distributed_validates_norm_recompute():
    A = jnp.zeros((32, 16))
    with pytest.raises(ValueError, match="norm_recompute.*got -3"):
        rid_distributed(jax.random.key(0), A, 4, mesh=_one_dev_mesh(),
                        qr_impl="panel_parallel", qr_norm_recompute=-3)


def test_qr_local_validation_messages():
    """Every eager check in panel_parallel_qr_local names the offending
    argument AND the received value — uniformly, no bare asserts."""
    from repro.core.qr_dist import panel_parallel_qr_local

    Y_loc = jnp.zeros((16, 8))
    with pytest.raises(ValueError,
                       match=r"need 0 < k <= min\(l, n\); got k=40"):
        panel_parallel_qr_local(Y_loc, 40, axis="data", ndev=2)
    with pytest.raises(ValueError, match="need panel >= 1, got panel=0"):
        panel_parallel_qr_local(Y_loc, 4, axis="data", ndev=2, panel=0)
    with pytest.raises(ValueError,
                       match="unknown panel_impl 'split'; expected"):
        panel_parallel_qr_local(Y_loc, 4, axis="data", ndev=2,
                                panel_impl="split")
    with pytest.raises(ValueError, match="unknown norm_recompute 'always'"):
        panel_parallel_qr_local(Y_loc, 4, axis="data", ndev=2,
                                norm_recompute="always")
    with pytest.raises(ValueError,
                       match=r"need norm_recompute >= 0 \(or 'auto'\), "
                             r"got -1"):
        panel_parallel_qr_local(Y_loc, 4, axis="data", ndev=2,
                                norm_recompute=-1)


def test_panel_parallel_pivoted_qr_validation_messages():
    """The sharded entry point repeats the same uniform contract."""
    from repro.core import panel_parallel_pivoted_qr

    mesh = _one_dev_mesh()
    Y = jnp.zeros((16, 24))
    with pytest.raises(ValueError,
                       match=r"need 0 < k <= min\(l, n\); got k=0"):
        panel_parallel_pivoted_qr(Y, 0, mesh=mesh)
    with pytest.raises(ValueError, match="need panel >= 1, got panel=-2"):
        panel_parallel_pivoted_qr(Y, 4, mesh=mesh, panel=-2)
    with pytest.raises(ValueError, match="unknown panel_impl 'nope'"):
        panel_parallel_pivoted_qr(Y, 4, mesh=mesh, panel_impl="nope")
    with pytest.raises(ValueError, match="unknown norm_recompute 'n'"):
        panel_parallel_pivoted_qr(Y, 4, mesh=mesh, norm_recompute="n")


def test_uneven_shard_raises(subproc):
    """n not divisible by the mesh axis raises eagerly, before tracing."""
    r = subproc("""
import jax, jax.numpy as jnp
from jax import make_mesh
from jax.sharding import AxisType
from repro.core import rid_distributed
mesh = make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
A = jnp.zeros((64, 100))            # 100 % 8 != 0
try:
    rid_distributed(jax.random.key(0), A, 4, mesh=mesh,
                    qr_impl="panel_parallel")
except ValueError as e:
    assert "must divide" in str(e), e
    print("OK")
""")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
