"""Checkpoint store + deterministic data pipeline."""
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointManager, latest_step, restore_pytree,
                              save_pytree)
from repro.data import PrefetchIterator, SyntheticConfig, batch_for_step

KEY = jax.random.key(0)


def tree():
    return {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": (jnp.ones((2,), jnp.int32), {"c": jnp.asarray(2.5)})}


def test_roundtrip(tmp_path):
    t = tree()
    save_pytree(str(tmp_path), 7, t)
    assert latest_step(str(tmp_path)) == 7
    like = jax.eval_shape(lambda: t)
    out = restore_pytree(str(tmp_path), 7, like)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_atomicity_no_tmp_left(tmp_path):
    save_pytree(str(tmp_path), 1, tree())
    assert not [d for d in os.listdir(tmp_path) if d.startswith(".tmp")]


def test_manager_async_and_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, tree())
    mgr.wait()
    steps = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert steps == ["step_000003", "step_000004"]
    step, out = mgr.restore_latest(jax.eval_shape(tree))
    assert step == 4 and out is not None


def test_restore_shape_mismatch_raises(tmp_path):
    save_pytree(str(tmp_path), 1, {"a": jnp.ones((3,))})
    with pytest.raises(ValueError):
        restore_pytree(str(tmp_path), 1, {"a": jax.ShapeDtypeStruct((4,), jnp.float32)})


def test_restore_missing_leaf_raises(tmp_path):
    save_pytree(str(tmp_path), 1, {"a": jnp.ones((3,))})
    with pytest.raises(KeyError):
        restore_pytree(str(tmp_path), 1,
                       {"zz": jax.ShapeDtypeStruct((3,), jnp.float32)})


def test_restore_detects_corrupt_leaf(tmp_path):
    """Durability half of ISSUE 8: the manifest's per-leaf crc32 catches
    a torn/truncated leaf BEFORE np.load, with the leaf named."""
    import json
    save_pytree(str(tmp_path), 1, {"a": jnp.arange(6, dtype=jnp.float32)})
    step_dir = tmp_path / "step_000001"
    leaf = json.loads((step_dir / "manifest.json").read_text()
                      )["leaves"]["['a']"]["file"]
    blob = (step_dir / leaf).read_bytes()
    (step_dir / leaf).write_bytes(blob[:-2] + b"\x00\x00")   # torn write
    with pytest.raises(ValueError, match=r"\['a'\].*corrupt.*crc32"):
        restore_pytree(str(tmp_path), 1,
                       {"a": jax.ShapeDtypeStruct((6,), jnp.float32)})


def test_restore_accepts_pre_crc_checkpoints(tmp_path):
    """Backward compat: a manifest written before the crc32 field simply
    has nothing to verify against and restores as before."""
    import json
    t = {"a": jnp.arange(4, dtype=jnp.float32)}
    save_pytree(str(tmp_path), 1, t)
    mpath = tmp_path / "step_000001" / "manifest.json"
    manifest = json.loads(mpath.read_text())
    for ent in manifest["leaves"].values():
        del ent["crc32"]
    mpath.write_text(json.dumps(manifest))
    out = restore_pytree(str(tmp_path), 1, jax.eval_shape(lambda: t))
    np.testing.assert_array_equal(np.asarray(out["a"]), np.arange(4))


def test_restore_host_preserves_f64_without_x64(tmp_path):
    """``host=True`` returns plain numpy (no jnp canonicalization): f64
    state restores bit-exact even with x64 off — the contract the
    streamed-RID resume path depends on.  x64 is pinned OFF here because
    other modules flip it at import time during collection."""
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        vals = np.array([1.0 + 1e-12, np.pi], dtype=np.float64)
        save_pytree(str(tmp_path), 1, {"acc": vals})
        like = {"acc": jax.ShapeDtypeStruct((2,), np.float64)}
        out = restore_pytree(str(tmp_path), 1, like, host=True)
        assert isinstance(out["acc"], np.ndarray)
        assert out["acc"].dtype == np.float64
        np.testing.assert_array_equal(out["acc"], vals)
        # the default (device) path would canonicalize f64 -> f32 here
        assert np.asarray(restore_pytree(str(tmp_path), 1, like)
                          ["acc"]).dtype == np.float32
        mgr = CheckpointManager(str(tmp_path))
        step, host_out = mgr.restore_latest(like, host=True)
        assert step == 1 and host_out["acc"].dtype == np.float64
    finally:
        jax.config.update("jax_enable_x64", prev)


# ------------------------------------------------------------------- data

def test_data_deterministic_replay():
    cfg = SyntheticConfig(vocab_size=128, seq_len=32, global_batch=8, seed=3)
    b1 = batch_for_step(cfg, 17)
    b2 = batch_for_step(cfg, 17)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    b3 = batch_for_step(cfg, 18)
    assert not np.array_equal(np.asarray(b1["tokens"]), np.asarray(b3["tokens"]))


def test_data_host_sharding_partitions_global_batch():
    cfg = SyntheticConfig(vocab_size=128, seq_len=16, global_batch=8, seed=0)
    full = batch_for_step(cfg, 5)
    assert full["tokens"].shape == (8, 16)
    shards = [batch_for_step(cfg, 5, host=h, n_hosts=4) for h in range(4)]
    assert all(s["tokens"].shape == (2, 16) for s in shards)
    # labels are next-token shifted views of the same stream
    np.testing.assert_array_equal(np.asarray(full["tokens"][:, 1:]),
                                  np.asarray(full["labels"][:, :-1]))


def test_data_is_learnable_not_uniform():
    cfg = SyntheticConfig(vocab_size=256, seq_len=64, global_batch=64,
                          seed=1, noise=0.0)
    b = batch_for_step(cfg, 0)
    toks = np.asarray(b["tokens"])
    # noiseless rows collapse to at most `period` distinct sequences —
    # the structure a model can learn (noise is added on top of this)
    assert len(np.unique(toks, axis=0)) <= cfg.period


def test_prefetch_iterator():
    it = PrefetchIterator(iter(range(5)), depth=2)
    assert list(it) == [0, 1, 2, 3, 4]

    def boom():
        yield 1
        raise RuntimeError("io error")
    it = PrefetchIterator(boom())
    assert next(it) == 1
    with pytest.raises(RuntimeError):
        next(it)


@pytest.mark.parametrize("end", ["exhausted", "raised"])
def test_prefetch_end_reaches_consumer_past_a_full_queue(end):
    """The source ends, or raises, while the queue is full: the consumer
    still gets every item and then the end, instead of blocking in
    ``get`` for good."""
    ended = threading.Event()

    def source():
        yield 0
        yield 1                            # the queue (depth 2) is full
        ended.set()
        if end == "raised":
            raise RuntimeError("io error")

    it = PrefetchIterator(source(), depth=2)
    assert ended.wait(timeout=5.0)
    time.sleep(0.1)                        # the worker reaches its end
    got = []

    def consume():
        try:
            for x in it:
                got.append(x)
        except RuntimeError as e:
            got.append(str(e))

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    t.join(timeout=5.0)
    stuck = t.is_alive()
    it.close()
    assert not stuck
    assert got == ([0, 1, "io error"] if end == "raised" else [0, 1])


def test_prefetch_close_unblocks_worker():
    """ISSUE 8 satellite: an abandoned prefetcher whose worker is BLOCKED
    on a full queue joins promptly on close() instead of leaking the
    thread (and the source it pins) for the life of the process."""
    released = threading.Event()

    def infinite():
        try:
            i = 0
            while True:
                yield i
                i += 1
        finally:
            released.set()                 # generator actually collected

    it = PrefetchIterator(infinite(), depth=1)
    assert next(it) == 0                   # worker now re-blocked on put
    it.close()
    assert not it._t.is_alive()
    assert released.wait(timeout=2.0)
    with pytest.raises(StopIteration):     # closed iterator is exhausted
        next(it)
    it.close()                             # idempotent


def test_prefetch_context_manager_closes():
    with PrefetchIterator(iter(range(100)), depth=1) as it:
        assert next(it) == 0
    assert not it._t.is_alive()
    # closing after natural exhaustion is also fine
    with PrefetchIterator(iter(range(3)), depth=2) as it2:
        assert list(it2) == [0, 1, 2]
    assert not it2._t.is_alive()
