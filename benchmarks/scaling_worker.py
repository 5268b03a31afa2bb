"""Subprocess worker for bench_scaling: lowers the distributed RID on an
N-device mesh and reports per-device roofline terms as JSON.

Invoked as:
  python -m benchmarks.scaling_worker <k> <m> <n> <nproc> [qr_impl] [exec]

(the parent sets XLA_FLAGS for the fake device count).  ``qr_impl``
selects the distributed pivoted-QR engine ('cgs2' | 'blocked' |
'panel_parallel' — see repro.core.distributed); ``exec=1`` additionally
allocates a real operand and reports median wall seconds (only sane for
the CPU-feasible grid — paper-size shapes stay lowering-only).
"""
import json
import sys


def main():
    k, m, n, nproc = map(int, sys.argv[1:5])
    qr_impl = sys.argv[5] if len(sys.argv) > 5 else "blocked"
    do_exec = len(sys.argv) > 6 and sys.argv[6] == "1"
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType

    from repro.core.distributed import rid_distributed
    from repro.analysis.hlo import collective_bytes

    mesh = jax.make_mesh((nproc,), ("data",),
                     axis_types=(AxisType.Auto,))
    key = jax.random.key(0)
    A = jax.ShapeDtypeStruct((m, n), jnp.float32)

    def run(key, A):
        dec = rid_distributed(key, A, k, mesh=mesh, axis="data",
                              sketch_kind="gaussian", qr_impl=qr_impl)
        return dec.B, dec.P

    with mesh:
        lowered = jax.jit(run).lower(key, A)
        compiled = lowered.compile()
    cost = compiled.cost_analysis() or {}
    bytes_per_device = 0.0
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            bytes_per_device = float(
                getattr(ma, "temp_size_in_bytes", 0)
                + getattr(ma, "argument_size_in_bytes", 0)
                + getattr(ma, "output_size_in_bytes", 0))
    except Exception:
        pass
    wall_s = None
    if do_exec:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .common import time_fn
        Areal = jax.device_put(
            jax.random.normal(jax.random.key(1), (m, n), jnp.float32),
            NamedSharding(mesh, P(None, "data")))
        wall_s = time_fn(jax.jit(run), key, Areal, warmup=1, iters=3)
    out = {
        "nproc": nproc,
        "qr_impl": qr_impl,
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": float(sum(collective_bytes(
            compiled.as_text()).values())),
        "bytes_per_device": bytes_per_device,
        "wall_s": wall_s,
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
