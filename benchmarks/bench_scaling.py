"""Paper Figures 1-2: parallel speed-up of the (distributed) RID.

No multi-chip hardware exists in this container, so — per DESIGN.md — the
scaling curve is derived STRUCTURALLY: the column-sharded RID is lowered
on meshes of 4..128 fake devices and each width's per-device roofline
time is modeled from compiled cost analysis under the v5e constants,

    t(N) = max(flops/peak, bytes/hbm_bw, collective_bytes/link_bw).

Speedup(N) = t(4) * 4 / (t(N) * N) * N  (paper's baseline is 4 procs).
The paper's qualitative result — near-linear scaling of the column-
parallel phases with the replicated tiny-QR eventually flattening the
curve — reproduces directly.

``--qr-impl`` threads the distributed pivoted-QR engine through to
``rid_distributed`` ('cgs2' | 'blocked' gather-and-replicate, or
'panel_parallel' which factors the shards in place).  ``--weak`` grows
``n`` proportionally with the device count (constant columns per device):
under the replicated engines per-device bytes grow with the mesh, under
'panel_parallel' they stay flat — the dropped O(l n) replication.
``--json PATH`` additionally dumps the rows machine-readably (the
``BENCH_scaling.json`` contract of benchmarks/run.py).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from repro.configs.paper_rid import PAPER_GRID, SMALL_GRID
from repro.core.distributed import QR_IMPLS

from .common import emit

PEAK = 197e12
HBM = 819e9
LINK = 50e9


def worker(k, m, n, nproc, qr_impl="blocked", do_exec=False) -> dict:
    # The worker is a lowering-only model on fake CPU devices: pin it to
    # the CPU so it never competes with the parent for an accelerator.
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={nproc}"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
        env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.scaling_worker",
         str(k), str(m), str(n), str(nproc), qr_impl,
         "1" if do_exec else "0"],
        capture_output=True, text=True, env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-2000:])
    return json.loads(r.stdout.strip().splitlines()[-1])


def model_time(rec: dict) -> float:
    return max(rec["flops"] / PEAK, rec["bytes"] / HBM,
               rec["collective_bytes"] / LINK)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--procs", default="4,8,16,32,64,128")
    ap.add_argument("--rows", default="1,6",
                    help="grid row indices (default: a tall m-heavy "
                         "row and a wide n-heavy row — the paper's two "
                         "scaling regimes)")
    ap.add_argument("--paper", action="store_true",
                    help="use the paper's full-size rows (lowering-only: "
                         "the worker takes ShapeDtypeStructs, so no 64 GB "
                         "matrices are allocated)")
    ap.add_argument("--qr-impl", default="blocked", choices=QR_IMPLS,
                    help="distributed pivoted-QR engine threaded through "
                         "rid_distributed")
    ap.add_argument("--weak", action="store_true",
                    help="weak scaling: grow n with the device count "
                         "(constant columns per device) — shows the "
                         "per-device replication dropped by "
                         "qr_impl=panel_parallel")
    ap.add_argument("--exec", dest="do_exec", action="store_true",
                    help="also run the compiled program and record median "
                         "wall seconds (CPU-feasible rows only)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="append rows to a machine-readable JSON file")
    args = ap.parse_args(argv)
    procs = [int(p) for p in args.procs.split(",")]
    grid = PAPER_GRID if args.paper else SMALL_GRID
    mode = "weak" if args.weak else "strong"
    rows = []
    for case in [grid[int(i)] for i in args.rows.split(",")]:
        recs, ns = {}, {}
        for p in procs:
            n_eff = case.n * p // procs[0] if args.weak else case.n
            ns[p] = n_eff
            recs[p] = worker(case.k, case.m, n_eff, p, args.qr_impl,
                             args.do_exec and not args.paper)
        t4 = model_time(recs[procs[0]])
        for p in procs:
            t = model_time(recs[p])
            if args.weak:
                # constant work per device: perfect scaling keeps t flat
                speedup = t4 / t * p
            else:
                speedup = (t4 / t) * (procs[0])   # vs the 4-proc baseline
            rows.append({"k": case.k, "m": case.m, "n": ns[p], "procs": p,
                         "qr_impl": args.qr_impl, "mode": mode,
                         "flops_per_dev": recs[p]["flops"],
                         "coll_bytes_per_dev": recs[p]["collective_bytes"],
                         "bytes_per_dev": recs[p]["bytes_per_device"],
                         "wall_s": recs[p]["wall_s"],
                         "model_time_s": t,
                         "speedup_vs4": speedup,
                         "efficiency": speedup / p})
    emit(rows, header=f"Figures 1-2 analogue: structural parallel scaling "
                      f"of distributed RID (v5e roofline model, "
                      f"qr_impl={args.qr_impl}, {mode} scaling)")
    if args.json:
        from .common import append_json_rows
        append_json_rows(args.json, rows)
    return rows


if __name__ == "__main__":
    main()
