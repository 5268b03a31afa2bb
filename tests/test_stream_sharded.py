"""Sharded streaming RID (ISSUE 9 tentpole): ``rid_streamed(mesh=...)``
composes the m-axis host stream with n-axis column sharding.

Multi-device cases run in subprocesses with 8 fake CPU devices (per
conftest); the acceptance bar is the ISSUE's: the sharded run matches
the single-device ``rid_streamed`` (same key, canonical chunking) on
EVERY IDResult field — pivots exactly, floats within dtype tolerance —
with zero ``l x n`` replicated collectives (the registered analysis
budget).  Validation paths run in-process on a 1-device mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import make_mesh
from jax.sharding import AxisType
from repro.stream import ArraySource, rid_streamed


# Shared subprocess preamble: the mesh, a well-separated low-rank matrix
# (distinct singular values -> a stable pivot order to compare exactly),
# and the single-device reference run.
PRELUDE = """
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_enable_x64", True)
from jax.sharding import Mesh
from jax import make_mesh
from jax.sharding import AxisType
from repro.stream import ArraySource, rid_streamed
from repro.kernels.sketch_accum import ACCUM_BLOCK

mesh = make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
assert len(jax.devices()) == 8

def matrix(m=1000, n=400, k=21, seed=0):
    rng = np.random.default_rng(seed)
    U = np.linalg.qr(rng.standard_normal((m, k)))[0]
    V = np.linalg.qr(rng.standard_normal((n, k)))[0]
    s = np.geomspace(1.0, 1e-3, k)
    return ((U * s) @ V.T + 1e-9 * rng.standard_normal((m, n))).astype(
        np.float64)

def fields(dec):
    return {f: np.asarray(getattr(dec, f)) for f in
            ("B", "P", "J", "Q", "R")}
"""


def test_sharded_stream_matches_single_device(subproc):
    """The acceptance parity: 8-device sharded vs single-device
    rid_streamed, same key, canonical chunking — pivots and the gathered
    B agree EXACTLY, P/Q/R within f64 tolerance."""
    r = subproc(PRELUDE + """
A = matrix()
k, key, chunk = 21, jax.random.key(7), 3 * ACCUM_BLOCK
sh = rid_streamed(key, ArraySource(A, chunk), k, mesh=mesh,
                  qr_norm_recompute=1)
ref = rid_streamed(key, ArraySource(A, chunk), k)   # auto -> blocked
a, b = fields(sh), fields(ref)
assert np.array_equal(a["J"], b["J"]), (a["J"], b["J"])
assert np.array_equal(a["B"], b["B"])          # same pivots, same gather
for f in ("P", "Q", "R"):
    np.testing.assert_allclose(a[f], b[f], rtol=1e-9, atol=1e-10,
                               err_msg=f)
# interpolation identity at the pivots survives the sharded solve
np.testing.assert_allclose(a["P"][:, a["J"]], np.eye(k), atol=1e-12)
print("OK")
""", x64=True)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_sharded_accumulator_bit_equal_to_full_sketch(subproc):
    """The sharding correctness pin: the column-sharded streamed
    accumulator, gathered, is BIT-equal to the in-memory full-width
    sketch — sharding n never touches the m-axis reduction order."""
    r = subproc(PRELUDE + """
from repro.core.sketch import (finalize_gaussian_sketch, gaussian_omega_cols,
                               gaussian_sketch)
from repro.stream import chunk_bounds, num_chunks
from repro.stream.rid_stream import _sharded_accum_fn
from repro.kernels.sketch_accum import accum_dtype_for
from jax.sharding import NamedSharding, PartitionSpec

A = matrix()
l, key = 48, jax.random.key(3)
src = ArraySource(A, 3 * ACCUM_BLOCK)
shard = NamedSharding(mesh, PartitionSpec(None, "data"))
acc = jax.device_put(jnp.zeros((l, A.shape[1]),
                               accum_dtype_for(jnp.float64)), shard)
step = _sharded_accum_fn(mesh, "data")
for c in range(num_chunks(src)):
    r0, r1 = chunk_bounds(src, c)
    omega = gaussian_omega_cols(key, r0, r1, l, jnp.float64)
    acc = step(omega, jax.device_put(src.chunk(c), shard), acc)
Y = finalize_gaussian_sketch(acc, l, jnp.float64)
full = gaussian_sketch(key, jnp.asarray(A), l)
assert np.array_equal(np.asarray(Y), np.asarray(full))
print("OK")
""", x64=True)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_sharded_stream_kill_resume_bit_identical(subproc):
    """The resume contract holds sharded: kill mid-pass-1 on 8 devices,
    resume onto the restored (re-sharded) accumulator, and every field
    equals the uninterrupted sharded run's bits."""
    r = subproc(PRELUDE + """
import tempfile, pytest
from repro.runtime import FaultPlan, FlakySource, ProcessKilled

A = matrix()
k, key, chunk = 21, jax.random.key(7), ACCUM_BLOCK
ref = rid_streamed(key, ArraySource(A, chunk), k, mesh=mesh)
with tempfile.TemporaryDirectory() as ckpt:
    flaky = FlakySource(ArraySource(A, chunk), FaultPlan(kill_at=(3,)))
    try:
        rid_streamed(key, flaky, k, mesh=mesh, resume_dir=ckpt)
        raise SystemExit("expected ProcessKilled")
    except ProcessKilled:
        pass
    out = rid_streamed(key, flaky, k, mesh=mesh, resume_dir=ckpt)
    a, b = fields(out), fields(ref)
    for f in a:
        assert np.array_equal(a[f], b[f]), f
    # and the single-device job is a DIFFERENT job to this directory:
    # qr_impl resolves into the fingerprint
    try:
        rid_streamed(key, ArraySource(A, chunk), k, resume_dir=ckpt)
        raise SystemExit("expected fingerprint rejection")
    except ValueError as e:
        assert "written by a different job" in str(e)
print("OK")
""", x64=True)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_sharded_step_has_no_replicated_collective(subproc):
    """The registered ``rid_streamed.sharded_step`` entry traces on 8
    devices with every collective under the ``l*n - 1`` element budget —
    no stage replicates a sketch-sized array."""
    r = subproc("""
import repro.analysis.registry as reg
from repro.analysis.jaxpr import analyze_entry
reg.load_entry_points()
entry = reg.get("rid_streamed.sharded_step")
assert entry.max_collective_elems == 48 * 400 - 1, entry.max_collective_elems
findings = [f for f in analyze_entry(entry)
            if f.rule == "jaxpr.replicated-collective"]
assert not findings, findings
print("OK")
""")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


# ------------------------------------------------- in-process validation

def _one_dev_mesh():
    return make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))


def _src(m=256, n=64, chunk=128):
    return ArraySource(np.zeros((m, n), np.float32), chunk)


def test_validation_panel_parallel_needs_mesh():
    with pytest.raises(ValueError, match=r"qr_impl='panel_parallel'.*"
                                         r"got mesh=None"):
        rid_streamed(jax.random.key(0), _src(), 8, qr_impl="panel_parallel")


def test_validation_mesh_needs_panel_parallel():
    with pytest.raises(ValueError, match=r"need qr_impl='panel_parallel' "
                                         r"\(or 'auto'\), got "
                                         r"qr_impl='blocked'"):
        rid_streamed(jax.random.key(0), _src(), 8, mesh=_one_dev_mesh(),
                     qr_impl="blocked")


def test_validation_axis_must_exist():
    with pytest.raises(ValueError, match=r"axis='model' is not an axis"):
        rid_streamed(jax.random.key(0), _src(), 8, mesh=_one_dev_mesh(),
                     axis="model")


def test_sharded_on_one_device_mesh_matches_panel_parallel():
    """mesh with ndev=1 is the degenerate sharding: it must agree with
    the meshless panel-parallel... which doesn't exist single-device, so
    the reference is the blocked engine via pivot equality on a
    well-separated matrix (the same bar the engines hold in
    test_qr_dist)."""
    rng = np.random.default_rng(1)
    U = np.linalg.qr(rng.standard_normal((512, 12)))[0]
    V = np.linalg.qr(rng.standard_normal((64, 12)))[0]
    A = ((U * np.geomspace(1, 1e-2, 12)) @ V.T).astype(np.float32)
    sh = rid_streamed(jax.random.key(2), ArraySource(A, 128), 12,
                      mesh=_one_dev_mesh(), qr_norm_recompute=1)
    ref = rid_streamed(jax.random.key(2), ArraySource(A, 128), 12)
    assert np.array_equal(np.asarray(sh.J), np.asarray(ref.J))
    np.testing.assert_allclose(np.asarray(sh.P), np.asarray(ref.P),
                               rtol=1e-4, atol=1e-5)
