"""Multi-device behaviour (subprocess with 8 fake CPU devices — the main
test process stays single-device by design, see conftest)."""
import json

import pytest


def test_distributed_rid_matches_error(subproc):
    r = subproc("""
import jax, jax.numpy as jnp
from repro.core import rid_distributed, rid, spectral_norm_dense
key = jax.random.key(0)
m, n, k = 512, 400, 12
A = jax.random.normal(key, (m, k)) @ jax.random.normal(jax.random.fold_in(key,1), (k, n))
from jax import make_mesh
from jax.sharding import AxisType
mesh = make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
dec = rid_distributed(jax.random.key(2), A, k, mesh=mesh, axis="data", sketch_kind="gaussian")
err = float(spectral_norm_dense(A - dec.B @ dec.P)) / float(spectral_norm_dense(A))
assert err < 1e-4, err
import numpy as np
Pp = np.asarray(jnp.take(dec.P, dec.J, axis=1))
np.testing.assert_allclose(Pp, np.eye(k), atol=1e-5)
print("OK", err)
""")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_train_step_sharded_with_compression(subproc):
    r = subproc("""
import jax, jax.numpy as jnp
from jax import make_mesh
from jax.sharding import AxisType
from repro.configs import get_smoke_config
from repro.launch.steps import TrainConfig, jit_train_step, init_train_state
from repro.optim import CompressorConfig

mesh = make_mesh((2,2,2), ("pod","data","model"), axis_types=(AxisType.Auto,)*3)
cfg = get_smoke_config("granite_3_2b")
key = jax.random.key(7)
B, S = 8, 32
batch = {"tokens": jax.random.randint(key, (B,S), 0, cfg.vocab_size),
         "labels": jax.random.randint(key, (B,S), 0, cfg.vocab_size)}
losses = {}
for name, tcfg in [("dense", TrainConfig()),
                   ("rcomp", TrainConfig(compress=CompressorConfig(rank=8, min_dim=16, min_numel=64)))]:
    step, state_shape, st_sh, b_sh = jit_train_step(cfg, tcfg, mesh, B)
    with mesh:
        state = jax.device_put(init_train_state(key, cfg, tcfg, npods=2), st_sh)
        bd = jax.device_put(batch, b_sh)
        for i in range(4):
            state, m = step(state, bd)
    losses[name] = float(m["loss"])
    assert jnp.isfinite(m["loss"]) and float(m["grad_norm"]) > 0
# compression approximates the dense step closely at rank 8 on a tiny model
assert abs(losses["dense"] - losses["rcomp"]) / losses["dense"] < 0.05, losses
print("OK", losses)
""")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_elastic_reshard_restore(subproc):
    """Save on a 2x2x2 ('pod','data','model') mesh, restore onto 4x2 —
    the failure-recovery path (mesh-agnostic checkpoints)."""
    r = subproc("""
import tempfile, jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import make_mesh
from jax.sharding import AxisType
from repro.checkpoint import save_pytree, restore_pytree

devs = jax.devices()
mesh_a = make_mesh((2,2,2), ("pod","data","model"), axis_types=(AxisType.Auto,)*3)
mesh_b = make_mesh((4,2), ("data","model"), devices=devs, axis_types=(AxisType.Auto,)*2)
x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
xa = jax.device_put(x, NamedSharding(mesh_a, P(("pod","data"), "model")))
d = tempfile.mkdtemp()
save_pytree(d, 3, {"x": xa})
like = {"x": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
out = restore_pytree(d, 3, like, shardings={"x": NamedSharding(mesh_b, P("data", "model"))})
np.testing.assert_array_equal(np.asarray(out["x"]), np.asarray(x))
assert out["x"].sharding.mesh.shape == {"data": 4, "model": 2}
print("OK")
""")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


@pytest.mark.slow
def test_train_loop_failure_recovery(subproc):
    """End-to-end: train, inject a host failure, elastic re-plan, restore
    from checkpoint on the smaller mesh, losses replay deterministically."""
    r = subproc("""
import tempfile, jax
from jax import make_mesh
from jax.sharding import AxisType
from repro.configs import get_smoke_config
from repro.launch.steps import TrainConfig
from repro.launch.train import train_loop
from repro.runtime import HostFailure, plan_elastic_mesh

cfg = get_smoke_config("xlstm_125m")
tcfg = TrainConfig(peak_lr=1e-3, warmup_steps=2, total_steps=12)
ck = tempfile.mkdtemp()
mesh_a = make_mesh((2,2,2), ("pod","data","model"), axis_types=(AxisType.Auto,)*3)
# run 1: fails at step 9 (after the step-8 checkpoint)
try:
    train_loop(cfg, tcfg, mesh_a, global_batch=8, seq_len=32, steps=12,
               ckpt_dir=ck, ckpt_every=4, fail_at=9, log=lambda *a: None)
    raise SystemExit("expected HostFailure")
except HostFailure as e:
    alive = 8 - len(e.dead_hosts)
shape, axes = plan_elastic_mesh(alive_chips=6, model_axis=2, chips_per_pod=4)
assert shape == (2, 2) and axes == ("data", "model"), (shape, axes)
mesh_b = make_mesh(shape, axes, devices=jax.devices()[:4], axis_types=(AxisType.Auto,)*2)
import shutil, os
ck_copy = tempfile.mkdtemp(); shutil.rmtree(ck_copy); shutil.copytree(ck, ck_copy)
out_b = train_loop(cfg, tcfg, mesh_b, global_batch=8, seq_len=32, steps=12,
                   ckpt_dir=ck, ckpt_every=4, log=lambda *a: None)
got = out_b["losses"]
assert len(got) == 4       # resumed from the step-8 checkpoint
# 1) restore+replay on the same mesh is bitwise deterministic
out_b2 = train_loop(cfg, tcfg, mesh_b, global_batch=8, seq_len=32, steps=12,
                    ckpt_dir=ck_copy, ckpt_every=100, log=lambda *a: None)
assert out_b2["losses"] == got, (out_b2["losses"], got)
# 2) cross-mesh continuation stays close to an uninterrupted run
#    (bf16 reduction order differs between mesh shapes)
ck2 = tempfile.mkdtemp()
out_ref = train_loop(cfg, tcfg, mesh_b, global_batch=8, seq_len=32, steps=12,
                     ckpt_dir=ck2, ckpt_every=100, log=lambda *a: None)
tail = out_ref["losses"][8:]
for a, b in zip(got, tail):
    assert abs(a - b) < 0.15, (got, tail)
print("OK", got)
""", timeout=1200)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout
