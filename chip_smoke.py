#!/usr/bin/env python3
"""Run the randomized ID's main path once on a TPU and check what it returns.

    python3 chip_smoke.py [--seed N]              # one chip: phases A, B, C
    python3 chip_smoke.py --chips 4 [--seed N]    # the sharded stream only

One process owns every chip it uses.  Phase 0 prints the JAX version and
devices and fails unless the platform is ``tpu``: there is no CPU
fallback.  Then, at paper grid row 1 (``configs/paper_rid.PAPER_GRID[0]``:
k = 100, l = 200, m = n = 2^14), through the public entry points:

  A  ``rid`` with the paper's defaults (srft sketch) on a complex64
     rank-k Gaussian product built on the device from ``--seed`` (2 GiB);
  B  ``rid(sketch_kind="gaussian")`` on the same row in float32 — the
     path whose sketch accumulate and QR panels are Mosaic kernels; the
     compiled programs must hold ``tpu_custom_call``;
  C  ``rid_streamed`` over an ``ArraySource`` of phase B's matrix held in
     host memory, 2048-row chunks: ``J`` and ``P`` must be bit-identical
     to phase B's (the replay contract of ``rid`` / ``rid_streamed``).

``--chips 4`` runs only ``rid_streamed(mesh=<4 chips>)`` at the shape of
grid row 8 (k = 1000, l = 2000, m = 2^14, n = 2^18, float32) from a
``SpectrumSource`` and compares it with the same job on one chip: the
sketch must be bit-equal column by column, both errors must be under
the tolerance, and the agreement of the two ``J`` is printed.

Every phase checks ``||A - BP||_2 / ||A||_2`` against a plain reference
computed at ``highest`` matmul precision, and prints the eq.(3) ratio
beside it.  Times are informational (first call includes compilation;
both end in ``block_until_ready``).  Any failed phase makes the exit code
non-zero; on success the last line of stdout is one JSON object naming
the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# ||A - BP||_2 / ||A||_2 limits.  Row 1 is exactly rank k, so its error is
# rounding, f32 eps times cond(R11) of the pivot block: 6e-6 .. 2.4e-4 on
# a v5e for cond 88 .. 3509.  Matmuls at the TPU's default precision (one
# bfloat16 pass) gave 1.6e-2 and up there, and a wrong interpolation
# matrix or column gather gives O(1).  Row 8's cliff spectrum leaves
# sigma_{k+1} = sqrt(FLOOR_ROW8) = 1e-5 after k values, and the ID's
# error is a large multiple of it at k = 1000: 1.4e-2 (1403 sigma_{k+1})
# on a v5e with n cut to 2^16, growing like sqrt(n).
TOL_ROW1 = 1e-2
TOL_ROW8 = 0.1
FLOOR_ROW8 = 1e-10
CHUNK_ROWS = 2048          # phase C: host chunks (16 ACCUM_BLOCKs)
CHUNK_ROWS_ROW8 = 256      # row 8: 256 MiB chunks of 2^18 f32 columns
EPS32 = float(np.finfo(np.float32).eps)   # the paper's precision delta


def log(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


class PhaseFailed(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def phase0(want_chips: int) -> dict:
    devs = jax.devices()
    log("0", jax=jax.__version__, devices=devs)
    d = devs[0]
    if d.platform != "tpu":
        raise PhaseFailed(f"no TPU: JAX's first device is on platform "
                          f"{d.platform!r}")
    if len(devs) < want_chips:
        raise PhaseFailed(f"need {want_chips} chips, JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded stream on four chips "
                         "and its one-chip comparison")
    args = ap.parse_args(argv)

    try:
        device = phase0(args.chips)
    except PhaseFailed as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.compile_cache import enable_compile_cache
    log("0", compile_cache=enable_compile_cache())

    phases = ([("row8-sharded", sharded_phase)] if args.chips == 4 else
              [("A", phase_a), ("B", phase_b), ("C", phase_c)])
    state: dict = {"seed": args.seed}
    failed = []
    for name, fn in phases:
        if name == "C" and "B" in failed:
            log(name, status="skipped", reason="phase B failed")
            failed.append(name)
            continue
        t0 = time.perf_counter()
        try:
            fn(state)
            log(name, status="ok", phase_s=time.perf_counter() - t0)
        except Exception:
            traceback.print_exc()
            log(name, status="FAILED", phase_s=time.perf_counter() - t0)
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


# ------------------------------------------------------------------ helpers

def _arrays(out):
    return [x for x in jax.tree.leaves(out) if isinstance(x, jax.Array)]


def timed(fn, runs: int = 2):
    """``(last result, [seconds per run])``: ``fn`` run ``runs`` times,
    each timed up to ``block_until_ready`` of its device outputs; the
    first run includes compilation."""
    times, out = [], None
    for _ in range(runs):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(_arrays(out))
        times.append(time.perf_counter() - t0)
    return out, times


@partial(jax.jit, static_argnames=("m", "n", "k", "dtype"))
def lowrank(key, m: int, n: int, k: int, dtype: str):
    """``B0 @ P0`` with Gaussian (complex Gaussian for a complex
    ``dtype``) factors: exactly rank ``k``, the paper's test matrices.
    The product runs at full precision: at the TPU's default a complex
    matmul does not come out exactly rank ``k``."""
    dtype = jnp.dtype(dtype)
    rdt = jnp.finfo(dtype).dtype
    kb, kp, kbi, kpi = jax.random.split(key, 4)
    B0 = jax.random.normal(kb, (m, k), rdt)
    P0 = jax.random.normal(kp, (k, n), rdt)
    if jnp.issubdtype(dtype, jnp.complexfloating):
        B0 = B0 + 1j * jax.random.normal(kbi, (m, k), rdt)
        P0 = P0 + 1j * jax.random.normal(kpi, (k, n), rdt)
    return jnp.matmul(B0.astype(dtype), P0.astype(dtype),
                      precision=jax.lax.Precision.HIGHEST)


def eq3_ratio(err: float, m: int, n: int, k: int, sigma_kp1: float) -> float:
    from repro.core import error_bound
    return err / (error_bound(m, n, k) * sigma_kp1)


def check_row1_error(phase: str, key, A, dec) -> None:
    """Plain reference: ``||A - BP||_2 / ||A||_2`` by power iteration, at
    ``highest`` precision so the matmul precision under test does not
    blur the check; the eq.(3) ratio takes sigma_{k+1} as the paper's
    noise floor at f32 precision."""
    from repro.core import expected_sigma_kp1, spectral_error
    m, n = A.shape
    k = dec.P.shape[0]
    with jax.default_matmul_precision("highest"):
        err = float(spectral_error(key, A, dec.B, dec.P))
        nrm = float(spectral_error(key, A, jnp.zeros((m, k), A.dtype),
                                   jnp.zeros((k, n), A.dtype)))
    rel = err / nrm
    log(phase, rel_err=rel, abs_err=err, tol=TOL_ROW1,
        eq3_ratio=eq3_ratio(err, m, n, k, expected_sigma_kp1(m, n, EPS32)))
    check(rel <= TOL_ROW1, f"rel_err {rel} > {TOL_ROW1}")


def mosaic_calls(jitted, *args) -> int:
    """Mosaic kernel launches in the compiled program of one stage."""
    return jitted.lower(*args).compile().as_text().count("tpu_custom_call")


def kernel_report(phase: str, stages) -> dict:
    """Count compiled kernels per stage.  ``stages``: ``(name, jitted,
    args, has_kernel)``; a stage with a kernel for real dtypes that
    compiled none ran its jnp reference."""
    counts = {name: mosaic_calls(fn, *args) for name, fn, args, _ in stages}
    reference = [name for name, _, _, has in stages
                 if has and counts[name] == 0]
    log(phase, kernels=counts, total=sum(counts.values()),
        jnp_reference=reference or "none")
    return counts


def memory(phase: str) -> None:
    stats = jax.devices()[0].memory_stats() or {}
    log(phase, peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        bytes_in_use=stats.get("bytes_in_use"))


# ------------------------------------------------------------------ row 1

def _row1():
    from repro.configs.paper_rid import PAPER_GRID
    case = PAPER_GRID[0]
    return case.k, case.l, case.m, case.n


def phase_a(state) -> None:
    from repro.core import rid
    from repro.core.rid import _qr_interp
    from repro.core.sketch import srft_sketch
    k, l, m, n = _row1()
    key = jax.random.key(state["seed"])
    A = lowrank(jax.random.fold_in(key, 0), m, n, k, "complex64")
    kr = jax.random.fold_in(key, 1)
    dec, (first, warm) = timed(lambda: rid(kr, A, k))
    log("A", k=k, l=l, m=m, n=n, dtype=A.dtype, sketch="srft",
        first_call_s=first, warm_s=warm)
    check(dec.P.shape == (k, n) and dec.B.shape == (m, k), "shapes")
    check(bool(jnp.all(jnp.isfinite(dec.P))), "P has non-finite values")
    check_row1_error("A", jax.random.fold_in(key, 2), A, dec)
    S = jax.ShapeDtypeStruct
    kernel_report("A", [
        ("srft_sketch", srft_sketch, (kr, S((m, n), A.dtype), l), False),
        ("qr_interp", _qr_interp, (S((l, n), A.dtype), k, "blocked", 32,
                                   "auto"), True)])
    memory("A")


def phase_b(state) -> None:
    from repro.core import rid
    from repro.core.rid import _qr_interp
    from repro.kernels import sketch_accum
    k, l, m, n = _row1()
    key = jax.random.key(state["seed"])
    A = lowrank(jax.random.fold_in(key, 3), m, n, k, "float32")
    kr = jax.random.fold_in(key, 4)
    dec, (first, warm) = timed(lambda: rid(kr, A, k, sketch_kind="gaussian"))
    log("B", k=k, l=l, m=m, n=n, dtype=A.dtype, sketch="gaussian",
        first_call_s=first, warm_s=warm)
    check(bool(jnp.all(jnp.isfinite(dec.P))), "P has non-finite values")
    check_row1_error("B", jax.random.fold_in(key, 5), A, dec)
    S = jax.ShapeDtypeStruct
    counts = kernel_report("B", [
        ("sketch_accum", sketch_accum, (S((l, m), A.dtype),
                                        S((m, n), A.dtype)), True),
        ("qr_interp", _qr_interp, (S((l, n), A.dtype), k, "blocked", 32,
                                   "auto"), True)])
    check(all(counts.values()), f"a kernel stage compiled no tpu_custom_call:"
                                f" {counts}")
    memory("B")
    state.update(B_key=kr, B_A=A, B_dec=dec,
                 B_err_key=jax.random.fold_in(key, 5))


def phase_c(state) -> None:
    from repro.stream import ArraySource, rid_streamed
    k, l, m, n = _row1()
    A_host = np.asarray(state["B_A"])          # set-up: the host copy
    ref = state["B_dec"]
    src = ArraySource(A_host, CHUNK_ROWS)
    dec, (first, warm) = timed(lambda: rid_streamed(state["B_key"], src, k))
    log("C", chunk_rows=CHUNK_ROWS, chunks=-(-m // CHUNK_ROWS),
        first_call_s=first, warm_s=warm)
    same_j = np.array_equal(np.asarray(dec.J), np.asarray(ref.J))
    same_p = np.array_equal(np.asarray(dec.P), np.asarray(ref.P))
    log("C", J_bit_identical=same_j, P_bit_identical=same_p)
    check(same_j and same_p, "rid_streamed differs from rid (replay)")
    check_row1_error("C", state["B_err_key"], state["B_A"], dec)
    memory("C")


# ------------------------------------------------------- row 8, four chips

@partial(jax.jit, static_argnames=("iters",))
def factored_error(key, V, s, J, P, iters: int = 50):
    """``(||A - A[:, J] P||_2, ||A||_2)`` for ``A = D U diag(s) V^T`` with
    ``D U`` orthonormal (``data.synthetic.SpectrumFactors``): both equal
    the norms of ``diag(s) (V^T - V[J]^T P)`` and ``diag(s) V^T``, so
    power iteration runs on ``r x n`` factors instead of the 16 GB
    matrix."""
    VJ = V[J]                                        # (k, r)

    def err_mv(x):
        return s * (V.T @ x - VJ.T @ (P @ x))

    def err_rmv(y):
        sy = s * y
        return V @ sy - P.T @ (VJ @ sy)

    def a_mv(x):
        return s * (V.T @ x)

    def a_rmv(y):
        return V @ (s * y)

    def norm(mv, rmv):
        v = jax.random.normal(key, (V.shape[0],), V.dtype)
        v = v / jnp.linalg.norm(v)

        def body(_, v):
            w = rmv(mv(v))
            return w / jnp.maximum(jnp.linalg.norm(w),
                                   jnp.finfo(V.dtype).tiny)
        return jnp.linalg.norm(mv(jax.lax.fori_loop(0, iters, body, v)))

    return norm(err_mv, err_rmv), norm(a_mv, a_rmv)


def _stream_sketch(src, key, l, step, put):
    """Pass 1 of ``rid_streamed`` alone: the finalized sketch."""
    from repro.core.sketch import finalize_gaussian_sketch, gaussian_omega_cols
    from repro.stream import chunk_bounds, num_chunks
    acc = put(jnp.zeros((l, src.shape[1]), jnp.float32))
    for c in range(num_chunks(src)):
        r0, r1 = chunk_bounds(src, c)
        acc = step(gaussian_omega_cols(key, r0, r1, l, jnp.float32),
                   put(src.chunk(c)), acc)
    return finalize_gaussian_sketch(acc, l, jnp.float32)


def sharded_phase(state) -> None:
    from jax.sharding import AxisType, NamedSharding, PartitionSpec
    from repro.configs.paper_rid import PAPER_GRID
    from repro.kernels import sketch_accum
    from repro.stream import SpectrumSource, rid_streamed
    from repro.stream.rid_stream import _sharded_accum_fn
    case = PAPER_GRID[7]
    k, l, m, n = case.k, case.l, case.m, case.n
    key = jax.random.key(state["seed"])
    devs = jax.devices()[:4]
    mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,),
                         devices=devs)
    shard = NamedSharding(mesh, PartitionSpec(None, "data"))
    t0 = time.perf_counter()
    src = SpectrumSource(jax.random.fold_in(key, 0), m, n, "cliff", k,
                         chunk_rows=CHUNK_ROWS_ROW8, dtype=jnp.float32,
                         floor=FLOOR_ROW8)
    jax.block_until_ready(src.factors.V)
    sigma_kp1 = float(src.sigmas[k])
    log("row8", k=k, l=l, m=m, n=n, dtype="float32", spectrum="cliff",
        sigma_kp1=sigma_kp1, chunk_rows=CHUNK_ROWS_ROW8,
        source_setup_s=time.perf_counter() - t0)
    kr = jax.random.fold_in(key, 1)
    problems = []             # every comparison runs; the phase fails after

    def expect(cond, msg):
        if not cond:
            problems.append(msg)

    def on_four(arr, shape, what):
        placed = {s.device: s.data.shape for s in arr.addressable_shards}
        log("row8", **{f"{what}_shards": sorted(str(d) for d in placed)},
            shard_shape=set(placed.values()))
        expect(len(placed) == 4 and set(placed.values()) == {shape},
               f"{what} shards are not {shape} on four devices: {placed}")

    # The sketch, sharded over four chips and on one: bit-equal columns.
    Y4 = _stream_sketch(src, kr, l, _sharded_accum_fn(mesh, "data"),
                        lambda x: jax.device_put(x, shard))
    on_four(Y4, (l, n // 4), "sketch")
    Y1 = _stream_sketch(src, kr, l, sketch_accum, jax.device_put)
    cols_differ = int(jnp.sum(jnp.any(jax.device_put(Y4, devs[0]) != Y1,
                                      axis=0)))
    log("row8", sketch_columns_differing=cols_differ, of=n)
    expect(cols_differ == 0, f"{cols_differ} sketch columns differ between "
                             f"four chips and one")
    del Y4, Y1

    # The same job on one chip, then on four; each run once (the first
    # call includes compilation).
    results = {}
    for name, mesh_arg in (("one_chip", None), ("four_chips", mesh)):
        dec, (first,) = timed(lambda: rid_streamed(kr, src, k,
                                                   mesh=mesh_arg), runs=1)
        if mesh_arg is not None:
            on_four(dec.P, (k, n // 4), "P")
        J, P = np.asarray(dec.J), np.asarray(dec.P)   # off the chips
        del dec
        with jax.default_matmul_precision("highest"):
            err, nrm = factored_error(jax.random.fold_in(key, 2),
                                      src.factors.V,
                                      jnp.asarray(src.sigmas, jnp.float32),
                                      jnp.asarray(J), jnp.asarray(P))
        rel = float(err) / float(nrm)
        log("row8", job=name, first_call_s=first, rel_err=rel,
            abs_err=float(err), tol=TOL_ROW8,
            eq3_ratio=eq3_ratio(float(err), m, n, k, sigma_kp1))
        expect(rel <= TOL_ROW8, f"{name}: rel_err {rel} > {TOL_ROW8}")
        memory("row8")
        results[name] = J
    j1, j4 = results["one_chip"], results["four_chips"]
    log("row8", J_identical=bool(np.array_equal(j1, j4)),
        J_common=len(set(j1.tolist()) & set(j4.tolist())), of=k)
    check(not problems, "; ".join(problems))


if __name__ == "__main__":
    sys.exit(main())
