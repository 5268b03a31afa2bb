#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine; print one JSON line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/``:
the matrix), a traffic mix (``bench/traffic/``: which entry point is
called, and how) and a number of chips.  The run, in one process:

1. fails, with no result, unless JAX's devices are TPUs, as many as the
   cell asks for, of a kind that ``bench/peaks.json`` lists;
2. turns on JAX's persistent compilation cache in the checkout;
3. makes the cell's matrix from ``--seed`` on the device (a streamed cell
   copies it to host memory block by block);
4. runs one decomposition to warm up, which compiles the cell's shapes;
5. runs a closed loop with one caller for ``--seconds``: decomposition
   ``i`` takes the key ``fold_in(key, i)`` on the same matrix and ends
   when its ``B``, ``P`` and ``J`` are ready;
6. reads the device's peak memory, then checks a sample of the window's
   decompositions, drawn from the seed, against the plain reference
   (``bench/reference.py``);
7. prints the result: the cell's end-to-end metrics with ``--trace 0``;
   with ``--trace 1`` the same window runs under the profiler and the
   program's ``repro.obs`` spans, and the cell's per-layer metrics
   (``bench/metrics/<name>.py``) are read from them.

The numbers compared and their limits are the last lines on standard
error and the last key (``checks``) of the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

SAMPLES = 3                    # decompositions of a window that are checked
CHECK_BLOCK_BYTES = 256 << 20  # rows of A per block of the check


class SetupError(RuntimeError):
    """The run cannot start here: no TPU, too few chips, an unknown device
    kind or cell."""


# ------------------------------------------------------------------ the spec

def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its configuration
    and traffic mix read and its metrics listed."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SetupError(f"no workload {name!r} in BENCHMARK.json "
                         f"(there are {sorted(cells)})")
    cell = dict(cells[name])
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cell["config"] = json.loads((root / conf["file"]).read_text())
    cell["traffic"] = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    cell["end_to_end"] = [m for m in spec["end_to_end"]
                          if name in m.get("workloads", [name])]
    reported = {m["name"] for m in cell["end_to_end"]}
    cell["per_layer"] = [
        m for m in spec["per_layer"]
        if name in m.get("workloads",
                         [name] if m["moves"] in reported else [])]
    return cell


def devices_for(chips: int):
    """The first ``chips`` TPUs and their row of ``peaks.json``."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SetupError(f"no TPU: JAX's devices are on "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SetupError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    kind = devs[0].device_kind
    if kind not in peaks:
        raise SetupError(f"device kind {kind!r} is not in bench/peaks.json")
    return devs[:chips], peaks[kind]


def enable_cache() -> None:
    """JAX's persistent compilation cache in ``.jax_cache/`` of the
    checkout, a fixed path whatever ``JAX_COMPILATION_CACHE_DIR`` says,
    keeping every program so that a second run compiles nothing."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


# ------------------------------------------------------------ the workloads

@dataclass
class Workload:
    """A cell's matrix and the call into the program under test.

    ``decompose(key)`` returns ``(B, P, J)``; ``row_blocks()`` yields
    ``(r0, r1, rows r0:r1 of A)`` covering ``A`` once, for the
    reference."""
    decompose: Callable
    row_blocks: Callable
    n: int
    k: int


def build_workload(config: dict, traffic: dict, devices, key) -> Workload:
    """The entry point that ``traffic["entry"]`` names, on the matrix that
    ``config`` describes, made from ``key``."""
    import jax

    from bench import matrices
    m, n, k, l = config["m"], config["n"], config["k"], config["l"]
    dtype = config["dtype"]
    step = max(1, CHECK_BLOCK_BYTES // (n * np.dtype(dtype).itemsize))
    entry = traffic["entry"]
    if entry == "rid":
        from repro.core import rid
        A = matrices.device_matrix(key, m, n, k, dtype)
        kind = traffic["sketch_kind"]

        def decompose(kd):
            d = rid(kd, A, k, l=l, sketch_kind=kind)
            return d.B, d.P, d.J
    elif entry == "rid_streamed":
        from repro.stream import ArraySource, rid_streamed
        A = matrices.host_matrix(key, m, n, k, dtype, traffic["chunk_rows"])
        src = ArraySource(A, traffic["chunk_rows"])
        mesh = None
        if len(devices) > 1:
            from jax.sharding import AxisType
            mesh = jax.make_mesh((len(devices),), ("data",),
                                 axis_types=(AxisType.Auto,),
                                 devices=devices)

        def decompose(kd):
            d = rid_streamed(kd, src, k, l=l, mesh=mesh)
            return d.B, d.P, d.J
    else:
        raise SetupError(f"unknown entry {entry!r} in the traffic mix")

    def row_blocks():
        for r0 in range(0, m, step):
            yield r0, min(m, r0 + step), A[r0:r0 + step]

    return Workload(decompose, row_blocks, n, k)


def _arrays(out):
    import jax
    return [x for x in jax.tree.leaves(out) if isinstance(x, jax.Array)]


# --------------------------------------------------------------- the window

def run_window(work: Workload, key, seconds: float, seed: int,
               annotate=lambda name: contextlib.nullcontext()):
    """A closed loop with one caller for ``seconds``.  Returns the
    latencies, the window's seconds up to the end of its last
    decomposition, and a reservoir of ``SAMPLES`` results drawn from
    ``seed``."""
    import jax
    rng = np.random.default_rng(seed % (1 << 64))
    lat, sample = [], []
    t_start = time.perf_counter()
    while True:
        i = len(lat)
        kd = jax.random.fold_in(key, i)
        t0 = time.perf_counter()
        with annotate("bench.decompose"):
            out = work.decompose(kd)
        with annotate("bench.block_until_ready"):
            jax.block_until_ready(_arrays(out))
        t1 = time.perf_counter()
        lat.append(t1 - t0)
        if i < SAMPLES:
            sample.append(out)
        elif (j := rng.integers(0, i + 1)) < SAMPLES:
            sample[j] = out
        del out
        if t1 - t_start >= seconds:
            return lat, t1 - t_start, sample


def peak_bytes(devices) -> int:
    """The fullest device's peak: ``peak_bytes_in_use`` counts arrays
    only, and a compiled program's temporaries are reserved apart
    (``peak_bytes_reserved``), so the two are added.  The sum can exceed
    the true high-water mark where the two peaks did not coincide."""
    def peak(stats):
        return (int(stats.get("peak_bytes_in_use", 0))
                + int(stats.get("peak_bytes_reserved", 0)))
    return max(peak(d.memory_stats() or {}) for d in devices)


def check(work: Workload, sample, limits: dict) -> tuple[bool, dict]:
    """The reference's verdict on the sampled decompositions: each number
    compared, at its worst over the sample, beside its limit.  A number
    that is not a number (NaN) fails."""
    from bench import reference
    per = reference.check_factors(work.row_blocks(), sample, work.n, work.k)
    checks = {name: {"value": max(float(r[name]) for r in per),
                     "limit": limit}
              for name, limit in limits.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    return correct, checks


# ---------------------------------------------------------------- per layer

@dataclass
class TracedWindow:
    """What a per-layer metric reads: the reduced profiler trace
    (``metrics/xplane.Trace``), the program's span durations by name, the
    number of decompositions in the window, and the chip's peaks."""
    trace: object
    spans: dict
    decomps: int
    peaks: dict


def read_metric(name: str, window: TracedWindow):
    """``bench/metrics/<name>.py``'s ``read(window)``: a number, or None
    where the metric finds nothing to read."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(window)


def traced_window(work, key, seconds, seed, devices):
    """``run_window`` under the profiler and a (non-deep) ``repro.obs``
    tracer: returns its results, the reduced trace and the spans."""
    import jax

    from bench.metrics import xplane
    from repro.obs import trace as obs_trace
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        with obs_trace.tracing() as tracer, xplane.profile(tmp):
            with jax.profiler.TraceAnnotation(xplane.WINDOW):
                lat, window_s, sample = run_window(
                    work, key, seconds, seed, jax.profiler.TraceAnnotation)
        reduced = xplane.reduce(tmp, [d.id for d in devices])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    spans = {}
    for sp in tracer.spans:
        spans.setdefault(sp.name, []).append(sp.dur)
    return lat, window_s, sample, reduced, spans


# --------------------------------------------------------------------- main

def run(workload: str, seed: int, seconds: float, trace: bool,
        devices_fn=devices_for, root: Path = ROOT,
        workload_fn=build_workload) -> dict:
    """One run of one cell; returns the result line's object."""
    cell = load_cell(workload, root)
    devices, peaks = devices_fn(cell["chips"])
    enable_cache()
    import jax

    from bench import matrices
    config, traffic = cell["config"], cell["traffic"]
    root_key = matrices.seed_key(seed)
    work = workload_fn(config, traffic, devices,
                       jax.random.fold_in(root_key, 0))
    jax.block_until_ready(_arrays(
        work.decompose(jax.random.fold_in(root_key, 2))))     # warm-up
    key = jax.random.fold_in(root_key, 1)
    setup_s = time.perf_counter() - T_START

    if trace:
        lat, window_s, sample, reduced, spans = traced_window(
            work, key, seconds, seed, devices)
    else:
        lat, window_s, sample = run_window(work, key, seconds, seed)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes(devices)}
    correct, checks = check(work, sample, config["limits"])

    result = {"correct": correct, "attempted": len(lat), "failed": 0}
    metrics = {}
    if trace:
        window = TracedWindow(reduced, spans, len(lat), peaks)
        for m in cell["per_layer"]:
            value = read_metric(m["name"], window)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    else:
        values = {"decomp_s": window_s / len(lat),
                  "decomp_p95_s": float(np.percentile(lat, 95)),
                  "setup_s": setup_s}
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    result.update(metrics=metrics, device=device, latencies=lat,
                  checks=checks)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except SetupError as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    lat = result.pop("latencies")
    print(f"decompositions {len(lat)}: first {lat[0]!r} s, median "
          f"{float(np.median(lat))!r} s, max {max(lat)!r} s", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
