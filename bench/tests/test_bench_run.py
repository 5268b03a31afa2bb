"""The harness end to end on the CPU at a tiny size: a sound run is
correct, the control and every fault planted in the timed path make
``correct`` false, and without a TPU the run fails with no result."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import control, run

ROOT = Path(run.__file__).resolve().parents[1]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
LIMITS = {"pivots_invalid": 0, "identity_err": 0.0, "gather_err": 0.0,
          "rel_err": 1e-4}
CELLS = {
    "tiny-srft": ("complex64", {"entry": "rid", "sketch_kind": "srft"}),
    "tiny-gaussian": ("float32", {"entry": "rid",
                                  "sketch_kind": "gaussian"}),
    "tiny-stream": ("float32", {"entry": "rid_streamed", "chunk_rows": 128}),
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout whose BENCHMARK.json holds tiny cells of each entry."""
    root = tmp_path_factory.mktemp("checkout")
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"], spec["workloads"] = [], []
    for name, (dtype, traffic) in CELLS.items():
        config = {"k": 8, "l": 16, "m": 512, "n": 256, "dtype": dtype,
                  "limits": LIMITS}
        (root / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(config))
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(traffic))
        spec["configs"].append({"name": name,
                                "file": f"bench/configs/{name}.json"})
        spec["workloads"].append({"name": name, "config": name,
                                  "traffic": name, "chips": 1})
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(autouse=True)
def no_cache(monkeypatch):
    monkeypatch.setattr(run, "enable_cache", lambda: None)


def _cpu(chips):
    return jax.devices()[:chips], PEAKS


def _run(root, cell, workload_fn=run.build_workload, seed=2 ** 33 + 1):
    return run.run(cell, seed, 0.2, False, devices_fn=_cpu, root=root,
                   workload_fn=workload_fn)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(root, cell):
    result = _run(root, cell)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == set(LIMITS)
    assert {"decomp_s", "setup_s"} <= set(result["metrics"])
    assert result["device"]["count"] == 1


def _faulty(fault):
    """``build_workload`` with the timed path's answer altered where it is
    produced."""
    def build(config, traffic, devices, key):
        work = run.build_workload(config, traffic, devices, key)
        sound = work.decompose

        def decompose(kd):
            B, P, J = sound(kd)
            B, P, J = np.array(B), np.array(P), np.array(J)
            if fault == "coefficient":
                free = np.setdiff1d(np.arange(work.n), J)[0]
                P[:, free] *= 2
            elif fault == "pivot":
                J[0] = np.setdiff1d(np.arange(work.n), J)[0]
            elif fault == "gather":
                B[:, 0] = B[:, 1]
            elif fault == "half":
                B[B.shape[0] // 2:] = 0
            return B, P, J

        work.decompose = decompose
        return work
    return build


@pytest.mark.parametrize("fault", ["coefficient", "pivot", "gather", "half"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_fault_in_timed_path_is_not_correct(root, cell, fault):
    result = _run(root, cell, _faulty(fault))
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_is_not_correct(root, cell):
    """The reference at bfloat16 in the program's place fails the limit
    that the reference at full precision meets."""
    def build(low):
        def fn(config, traffic, devices, key):
            work = run.build_workload(config, traffic, devices, key)
            work.decompose = control.control_decompose(work, config, low)
            return work
        return fn
    assert _run(root, cell, build(False))["correct"]
    assert not _run(root, cell, build(True))["correct"]


def test_no_tpu_fails_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "r1-c64-srft", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


class _FakeDevice:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


@pytest.mark.parametrize("kind,chips,message", [
    ("TPU v99", 1, "not in bench/peaks.json"),
    ("TPU v5 lite", 4, "needs 4 chips"),
])
def test_devices_for_refuses(monkeypatch, kind, chips, message):
    monkeypatch.setattr(jax, "devices", lambda: [_FakeDevice(kind)])
    with pytest.raises(run.SetupError, match=message):
        run.devices_for(chips)


def test_unknown_cell_is_refused():
    with pytest.raises(run.SetupError, match="no workload"):
        run.load_cell("no-such-cell")
