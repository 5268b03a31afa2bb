#!/usr/bin/env python3
"""The readings that each correctness limit is set from.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...] \
        [--decomps 20] [--controls 3]

Not part of a benchmark run.  For each seed, in one process on the
cell's chips, on the cell's own matrix at its own size:

* ``program``: ``--decomps`` decompositions through the timed path, with
  the keys the window gives them, each checked by the reference;
* ``control``: the reference ID put in the program's place, computed
  with every product at bfloat16 (``reference.matmul(low=True)``), the
  precision step below the configuration's float32; ``--controls`` of
  them, each checked the same way;
* ``reference``: the same reference ID at full precision, once.

A limit sits above every ``program`` reading and below every ``control``
reading (``PERF.md`` gives both).  One JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as harness  # noqa: E402


def control_decompose(work, config: dict, low: bool = True):
    """The reference ID in the program's place: ``(B, P, J)`` for a key,
    ``B`` gathered from the rows of ``A``."""
    import jax.numpy as jnp

    from bench import reference

    def decompose(kd):
        P, J = reference.reference_id(kd, work.row_blocks(), config["n"],
                                      config["k"], config["l"],
                                      jnp.dtype(config["dtype"]), low)
        return reference.gather(work.row_blocks(), J), P, J

    return decompose


def readings(cell: dict, seed: int, decomps: int, controls: int,
             devices) -> dict:
    import jax

    from bench import matrices, reference
    config, traffic = cell["config"], cell["traffic"]
    root_key = matrices.seed_key(seed)
    t0 = time.perf_counter()
    work = harness.build_workload(config, traffic, devices,
                                  jax.random.fold_in(root_key, 0))
    key = jax.random.fold_in(root_key, 1)
    out = {"seed": seed, "program": [], "control": [], "reference": []}

    def judge(result):
        return reference.check_factors(work.row_blocks(), [result],
                                       config["n"], config["k"])[0]

    for i in range(decomps):
        out["program"].append(judge(work.decompose(jax.random.fold_in(key,
                                                                      i))))
    low = control_decompose(work, config, low=True)
    for i in range(controls):
        out["control"].append(judge(low(jax.random.fold_in(key, i))))
    full = control_decompose(work, config, low=False)
    out["reference"].append(judge(full(jax.random.fold_in(key, 0))))
    out["seconds"] = time.perf_counter() - t0
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--decomps", type=int, default=20)
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    devices, _ = harness.devices_for(cell["chips"])
    harness.enable_cache()
    for seed in args.seeds:
        r = readings(cell, seed, args.decomps, args.controls, devices)
        summary = {side: [min(x["rel_err"] for x in r[side]),
                          max(x["rel_err"] for x in r[side])]
                   for side in ("program", "control", "reference") if r[side]}
        print(json.dumps({"workload": args.workload,
                          "rel_err_min_max": summary, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
