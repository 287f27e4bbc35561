"""Data pipeline, checkpointing, fault-tolerant loop, optimizer, and
gradient compression."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.store import CheckpointStore
from repro.data.pipeline import SyntheticLMData
from repro.optim.adamw import AdamW, cosine_schedule
from repro.optim.compression import compress_int8, decompress_int8
from repro.train.loop import LoopConfig, run_loop


# -- data ---------------------------------------------------------------------


def test_data_deterministic_across_restarts():
    d = SyntheticLMData(vocab_size=100, seq_len=16, global_batch=8, seed=3)
    b1 = d.batch(step=5)
    b2 = SyntheticLMData(vocab_size=100, seq_len=16, global_batch=8,
                         seed=3).batch(step=5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # different steps differ
    assert not np.array_equal(b1["tokens"], d.batch(step=6)["tokens"])


def test_data_sharding_consistency():
    """Concatenated per-shard batches == the global batch (multi-host
    correctness)."""
    d = SyntheticLMData(vocab_size=100, seq_len=16, global_batch=8, seed=1)
    full = d.batch(step=2)
    parts = [d.batch(step=2, shard=s, n_shards=4) for s in range(4)]
    np.testing.assert_array_equal(
        full["tokens"], np.concatenate([p["tokens"] for p in parts]))


def test_data_labels_are_shifted_tokens():
    d = SyntheticLMData(vocab_size=50, seq_len=12, global_batch=2, seed=0)
    b = d.batch(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


# -- optimizer ------------------------------------------------------------------


def test_adamw_descends_quadratic():
    opt = AdamW(learning_rate=0.1, weight_decay=0.0)
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = opt.init(params)
    for _ in range(100):
        grads = {"w": 2 * params["w"]}
        params, state, _ = opt.update(grads, state, params)
    assert float(jnp.max(jnp.abs(params["w"]))) < 0.5


def test_adamw_clipping_and_schedule():
    sched = cosine_schedule(1.0, warmup=10, total=100)
    assert float(sched(0)) == pytest.approx(0.0)
    assert float(sched(10)) == pytest.approx(1.0, rel=1e-2)
    assert float(sched(100)) == pytest.approx(0.1, rel=1e-2)
    opt = AdamW(learning_rate=1e-2, clip_norm=1.0)
    params = {"w": jnp.zeros(3)}
    state = opt.init(params)
    huge = {"w": jnp.full(3, 1e6)}
    new, state, m = opt.update(huge, state, params)
    assert float(m["grad_norm"]) > 1e5
    assert float(jnp.max(jnp.abs(new["w"]))) < 1.0  # clipped step


def test_int8_compression_roundtrip_error_bounded(rng):
    x = jnp.asarray(rng.standard_normal(1000), jnp.float32)
    q, s = compress_int8(x)
    assert q.dtype == jnp.int8
    y = decompress_int8(q, s)
    assert float(jnp.max(jnp.abs(x - y))) <= float(s) * 0.5 + 1e-7


# -- checkpointing ---------------------------------------------------------------


def tree_example():
    return {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.asarray([1, 2, 3], jnp.int32),
                  "d": (jnp.zeros(4), jnp.ones((2, 2)))}}


def test_checkpoint_roundtrip(tmp_path):
    store = CheckpointStore(str(tmp_path), keep=2)
    tree = tree_example()
    store.save(3, tree, blocking=True)
    out = store.restore(jax.tree.map(jnp.zeros_like, tree))
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_retention_and_latest(tmp_path):
    store = CheckpointStore(str(tmp_path), keep=2)
    tree = tree_example()
    for s in (1, 5, 9):
        store.save(s, tree, blocking=True)
    assert store.steps() == [5, 9]
    assert store.latest_step() == 9


def test_checkpoint_atomicity(tmp_path):
    """A stale .tmp dir (crashed writer) must not be visible as a
    checkpoint."""
    store = CheckpointStore(str(tmp_path), keep=3)
    os.makedirs(os.path.join(str(tmp_path), "step_000777.tmp"))
    assert store.latest_step() is None
    store.save(1, tree_example(), blocking=True)
    assert store.latest_step() == 1


def test_checkpoint_elastic_restore_new_sharding(tmp_path):
    """Restore with explicit (new-mesh) shardings — the elastic path."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_host_mesh
    store = CheckpointStore(str(tmp_path))
    tree = {"w": jnp.arange(8, dtype=jnp.float32)}
    store.save(0, tree, blocking=True)
    mesh = make_host_mesh(1, 1)
    sh = {"w": NamedSharding(mesh, P())}
    out = store.restore(tree, shardings=sh)
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(tree["w"]))
    assert out["w"].sharding == sh["w"]


# -- fault-tolerant loop ----------------------------------------------------------


class ToyData:
    def batch(self, step):
        return {"x": jnp.asarray([float(step)])}


def toy_step(state, batch):
    new = state + batch["x"][0]
    return new, {"loss": new}


def test_loop_checkpoint_restart(tmp_path):
    ckdir = str(tmp_path / "ck")
    cfg = LoopConfig(total_steps=10, checkpoint_every=3, checkpoint_dir=ckdir,
                     log_every=0)
    out = run_loop(toy_step, jnp.asarray(0.0), ToyData(), cfg,
                   log=lambda *_: None)
    assert out["final_step"] == 10
    # a fresh loop restores and does nothing more
    out2 = run_loop(toy_step, jnp.asarray(0.0), ToyData(),
                    LoopConfig(total_steps=10, checkpoint_dir=ckdir,
                               log_every=0), log=lambda *_: None)
    assert float(out2["state"]) == float(out["state"])


def test_loop_failure_recovery(tmp_path):
    """A simulated node failure mid-run: the loop restores the latest
    checkpoint and converges to the same final state."""
    ckdir = str(tmp_path / "ck")
    fail_at = {"armed": True}

    def failure_hook(step):
        if step == 7 and fail_at["armed"]:
            fail_at["armed"] = False
            raise RuntimeError("simulated device loss")

    cfg = LoopConfig(total_steps=10, checkpoint_every=2, checkpoint_dir=ckdir,
                     log_every=0)
    out = run_loop(toy_step, jnp.asarray(0.0), ToyData(), cfg,
                   failure_hook=failure_hook, log=lambda *_: None)
    assert out["recoveries"] == 1
    assert out["final_step"] == 10
    # deterministic data + restore-from-step => exact same sum 0..9
    assert float(out["state"]) == pytest.approx(sum(range(10)))


def test_loop_recovery_waits_for_the_save_in_flight(tmp_path, monkeypatch):
    """A failure right after a checkpoint step: the save of that step is
    still being written when the loop restores, and publishes while the
    restore reads.  The loop must resume from the step it restored."""
    import time as _t

    from repro.checkpoint import store as store_mod
    rename, load = os.rename, np.load

    def slow_rename(a, b):
        _t.sleep(0.05)
        rename(a, b)

    def slow_load(*a, **k):
        _t.sleep(0.2)
        return load(*a, **k)

    monkeypatch.setattr(store_mod.os, "rename", slow_rename)
    monkeypatch.setattr(store_mod.np, "load", slow_load)
    fail_at = {"armed": True}

    def failure_hook(step):
        if step == 7 and fail_at["armed"]:
            fail_at["armed"] = False
            raise RuntimeError("simulated device loss")

    cfg = LoopConfig(total_steps=10, checkpoint_every=2,
                     checkpoint_dir=str(tmp_path / "ck"), log_every=0)
    out = run_loop(toy_step, jnp.asarray(0.0), ToyData(), cfg,
                   failure_hook=failure_hook, log=lambda *_: None)
    assert out["recoveries"] == 1
    assert float(out["state"]) == pytest.approx(sum(range(10)))


def test_loop_straggler_watchdog():
    import time as _t

    class SlowData(ToyData):
        pass

    def slow_step(state, batch):
        if int(batch["x"][0]) == 8:
            _t.sleep(0.35)
        else:
            _t.sleep(0.01)
        return state + 1, {"loss": state}

    out = run_loop(slow_step, jnp.asarray(0.0), SlowData(),
                   LoopConfig(total_steps=10, log_every=0),
                   log=lambda *_: None)
    assert out["stragglers"] >= 1
