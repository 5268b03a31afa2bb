"""The harness's sharded path (``rid_streamed(mesh=...)``, a cell with
``chips: 4``) on four virtual CPU devices: a sound run is correct, and a
run whose exchange between chips is left out (every ``psum`` returns its
own shard's part) is not."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

SCRIPT = textwrap.dedent("""
    import json, sys
    import jax
    from bench import run
    from bench.tests.test_bench_run import CELLS, LIMITS, PEAKS
    root = __import__("pathlib").Path(sys.argv[1])
    root.joinpath("bench/configs").mkdir(parents=True)
    root.joinpath("bench/traffic").mkdir()
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    config = {"k": 8, "l": 16, "m": 512, "n": 256, "dtype": "float32",
              "limits": LIMITS}
    (root / "bench/configs/tiny.json").write_text(json.dumps(config))
    (root / "bench/traffic/tiny.json").write_text(
        json.dumps(CELLS["tiny-stream"][1]))
    spec["configs"] = [{"name": "tiny", "file": "bench/configs/tiny.json"}]
    spec["workloads"] = [{"name": "tiny", "config": "tiny",
                          "traffic": "tiny", "chips": 4}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    run.enable_cache = lambda: None
    devices = lambda chips: (jax.devices()[:chips], PEAKS)
    out = {"sound": run.run("tiny", 7, 0.2, False, devices_fn=devices,
                            root=root)}
    jax.lax.psum = lambda x, axis_name, **kw: x
    from repro.stream import rid_stream
    rid_stream._sharded_qr_interp_fn.cache_clear()
    out["no_exchange"] = run.run("tiny", 7, 0.2, False, devices_fn=devices,
                                 root=root)
    print(json.dumps({k: [v["correct"], v["device"]["count"],
                          v["checks"]["rel_err"]["value"]]
                      for k, v in out.items()}))
""")


def test_four_devices_sound_and_without_exchange(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    proc = subprocess.run([sys.executable, "-c", SCRIPT,
                           str(tmp_path / "checkout")],
                          capture_output=True, text=True, env=env,
                          timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sound"][:2] == [True, 4], out
    assert out["no_exchange"][:2] == [False, 4], out
