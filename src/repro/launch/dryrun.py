import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first init.

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input shape) cell and both production meshes
(16x16 single pod, 2x16x16 multi-pod), lower + compile the cell's step
function against ShapeDtypeStruct inputs (no allocation), then record:

  * memory_analysis()  — bytes per device (proves it fits a v5e's 16 GB)
  * cost_analysis()    — HLO FLOPs / bytes accessed (roofline inputs)
  * collective bytes   — parsed from the post-SPMD HLO text, per op kind

Artifacts land in experiments/dryrun/<arch>_<shape>_<mesh>.json; the
roofline table (benchmarks/roofline.py) and EXPERIMENTS.md section Dry-run
read from them.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh pod|multipod|both]
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.analysis.hlo import COLLECTIVE_RE, collective_bytes, shape_bytes
from repro.configs import ARCHS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, cell_applicable, input_specs
from repro.launch.steps import (TrainConfig, jit_decode_step, jit_prefill,
                                jit_train_step, train_state_shape)
from repro.optim import CompressorConfig

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "experiments", "dryrun")

def interpod_bytes(hlo_text: str, chips_per_pod: int = 256) -> float:
    """Bytes moved by collectives whose replica groups SPAN pods — the
    traffic that rides the slow inter-pod (DCN-class) links.  Groups are
    explicit id lists or iota forms like [2,256]<=[512] /
    [1,256]<=[2,16,16]T(1,0,2); a group crosses pods iff it mixes ids
    from different floor(id / chips_per_pod) buckets."""
    import numpy as _np
    total = 0.0
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m or m.group(0).rstrip("(").endswith("-done"):
            continue
        gm = re.search(
            r"replica_groups=(\{\{[\d,{} ]*\}\}|"
            r"\[[\d,]+\]<=\[[\d,]+\](?:T\([\d,]+\))?)", line)
        if not gm:
            continue
        spec = gm.group(1)
        crosses = False
        if spec.startswith("{{"):
            for grp in re.findall(r"\{([\d, ]+)\}", spec):
                ids = [int(x) for x in grp.replace(" ", "").split(",") if x]
                if len({i // chips_per_pod for i in ids}) > 1:
                    crosses = True
                    break
        else:
            im = re.match(r"\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?",
                          spec)
            if im:
                gshape = [int(x) for x in im.group(1).split(",")]
                ishape = [int(x) for x in im.group(2).split(",")]
                ids = _np.arange(int(_np.prod(ishape))).reshape(ishape)
                if im.group(3):
                    ids = ids.transpose([int(x) for x in im.group(3).split(",")])
                rows = ids.reshape(-1, gshape[-1])
                for row in rows:
                    if len({int(i) // chips_per_pod for i in row}) > 1:
                        crosses = True
                        break
        if crosses:
            total += shape_bytes(m.group(1))
    return total


def lower_cell(cfg, shape_name: str, mesh, *, compress: bool = False,
               mode: str = "tp"):
    """Build + lower + compile one cell.  Returns (lowered, compiled)."""
    case = SHAPES[shape_name]
    specs = input_specs(cfg, shape_name)
    with jax.default_device(jax.devices()[0]):
        if case.kind == "train":
            base, _, suffix = mode.partition("+")
            tcfg = TrainConfig(compress=CompressorConfig() if compress else None,
                               sharding_mode=base,
                               cast_params=(suffix == "cast"))
            step, state_shape, st_sh, b_sh = jit_train_step(
                cfg, tcfg, mesh, case.global_batch)
            lowered = step.lower(state_shape, specs)
        elif case.kind == "prefill":
            from repro.models.transformer import params_shape
            fn, pshard, in_b, _ = jit_prefill(cfg, mesh, case.global_batch,
                                              case.seq_len)
            lowered = fn.lower(params_shape(cfg), specs)
        else:
            from repro.models.transformer import params_shape
            fn, pshard, _ = jit_decode_step(cfg, mesh, case.global_batch,
                                            case.seq_len)
            lowered = fn.lower(params_shape(cfg), specs["tokens"],
                               specs["pos"], specs["caches"])
        compiled = lowered.compile()
    return lowered, compiled


def analyze(compiled) -> dict:
    cost = compiled.cost_analysis() or {}
    mem = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = {k: getattr(ma, k) for k in dir(ma)
                   if k.endswith("size_in_bytes") and not k.startswith("_")}
    except Exception:
        pass
    txt = compiled.as_text()
    coll = collective_bytes(txt)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "transcendentals": float(cost.get("transcendentals", 0.0)),
        "memory_analysis": mem,
        "collective_bytes": coll,
        "collective_total": float(sum(coll.values())),
        "interpod_bytes": interpod_bytes(txt),
    }


def _reduced_cfg(cfg, n_super: int):
    """Config with ``n_super`` superblocks, scans UNROLLED.

    XLA's HloCostAnalysis counts a while-loop body ONCE regardless of trip
    count, so the full lowering under-reports FLOPs/bytes/collectives by
    ~n_super x.  Costs are affine in depth, f(L) = a + b*L, so we lower
    unrolled L = p and L = 2p variants and extrapolate to the real depth
    (recorded as *_extrapolated; the full lowering still provides
    memory_analysis and the pass/fail signal).
    """
    from repro.models.transformer import pattern_period
    p = pattern_period(cfg)
    kw = {"n_layers": p * n_super, "unroll": True}
    if cfg.encdec:
        # scale encoder proportionally so cost stays affine in one variable
        kw["n_encoder_layers"] = max(1, cfg.n_encoder_layers * (p * n_super)
                                     // cfg.n_layers)
    return cfg.replace(**kw)


def extrapolated_costs(cfg, shape_name: str, mesh, *, compress: bool,
                       mode: str = "tp") -> dict:
    """Affine-in-depth extrapolation of per-device flops / bytes /
    collective bytes to the full layer count."""
    from repro.models.transformer import pattern_period
    p = pattern_period(cfg)
    nsb_full = cfg.n_layers // p
    points = {}
    for ns in (1, 2):
        rcfg = _reduced_cfg(cfg, ns)
        _, compiled = lower_cell(rcfg, shape_name, mesh, compress=compress,
                                 mode=mode)
        points[ns] = analyze(compiled)
    out = {}
    for key in ("flops", "bytes_accessed", "collective_total",
                "interpod_bytes"):
        f1, f2 = points[1][key], points[2][key]
        b = f2 - f1                      # cost of one superblock
        a = f1 - b                       # depth-independent cost
        out[key + "_extrapolated"] = a + b * nsb_full
        out[key + "_per_superblock"] = b
        out[key + "_fixed"] = a
    # collective mix extrapolated per kind
    mix = {}
    for kind in set(points[1]["collective_bytes"]) | set(points[2]["collective_bytes"]):
        f1 = points[1]["collective_bytes"].get(kind, 0)
        f2 = points[2]["collective_bytes"].get(kind, 0)
        mix[kind] = (f1 - (f2 - f1)) + (f2 - f1) * nsb_full
    out["collective_bytes_extrapolated"] = mix
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             compress: bool = False, save: bool = True,
             mode: str = "tp") -> dict:
    from repro.configs import ALIASES
    arch = ALIASES.get(arch, arch)        # normalize artifact naming
    cfg = get_config(arch)
    ok, reason = cell_applicable(cfg, shape_name)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "compress": compress, "mode": mode}
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    t0 = time.time()
    try:
        with mesh:
            lowered, compiled = lower_cell(cfg, shape_name, mesh,
                                           compress=compress, mode=mode)
            rec.update(status="ok", **analyze(compiled))
            rec.update(extrapolated_costs(cfg, shape_name, mesh,
                                          compress=compress, mode=mode))
        rec["seconds"] = round(time.time() - t0, 1)
    except Exception as e:
        rec.update(status="error", seconds=round(time.time() - t0, 1),
                   error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    if save:
        os.makedirs(ARTIFACT_DIR, exist_ok=True)
        suffix = ("_rcomp" if compress else "") + \
            (f"_{mode}" if mode != "tp" else "")
        fn = os.path.join(ARTIFACT_DIR,
                          f"{arch}_{shape_name}_{mesh_kind}{suffix}.json")
        with open(fn, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="both",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--compress", action="store_true",
                    help="lower the RandLR-compressed train step")
    args = ap.parse_args()

    archs = ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]

    n_bad = 0
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                rec = run_cell(arch, shape, mk, compress=args.compress)
                line = f"{arch:22s} {shape:12s} {mk:9s} {rec['status']:8s}"
                if rec["status"] == "ok":
                    ct = rec.get("collective_total_extrapolated",
                                 rec["collective_total"])
                    fl = rec.get("flops_extrapolated", rec["flops"])
                    line += (f" {rec['seconds']:7.1f}s  "
                             f"flops={fl:.3e}  "
                             f"coll={ct / 1e9:.2f} GB")
                elif rec["status"] == "error":
                    n_bad += 1
                    line += f"  {rec['error'][:110]}"
                else:
                    line += f"  ({rec['reason'][:70]})"
                print(line, flush=True)
    sys.exit(1 if n_bad else 0)


if __name__ == "__main__":
    main()
