"""The solver throughput layer: unique-row dedup + LRU solve cache
(bit-equality end to end), hierarchical kernel refinement monotonicity,
benign pad rows, and sharded dispatch."""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import dvfs, online, scheduling, single_task, solver_cache, tasks
from repro.core.solver_cache import SolveCache, build_keys, solve_rows

SEED = 7


def _dup_task_set(n_base: int, n_total: int, seed: int):
    """A task set with a random duplication pattern over ``n_base`` unique
    tasks (recurring-jobs shape; ``subset`` keeps repeated indices)."""
    rng = np.random.default_rng(seed)
    base = tasks.generate_offline_n(n_base, seed=seed,
                                    library=tasks.app_library())
    return base.subset(rng.integers(0, len(base), size=n_total))


def _assert_configs_equal(a, b):
    for fa, fb in zip(a, b):
        if isinstance(fa, int):
            assert fa == fb
        else:
            assert np.array_equal(np.asarray(fa), np.asarray(fb))


# ---------------------------------------------------------------------------
# Bit-equality of the dedup path (the layer's core contract).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("use_kernel", [False, True])
def test_configure_tasks_dedup_bit_identical(use_kernel):
    ts = _dup_task_set(24, 300, SEED)
    allowed = ts.deadline - ts.arrival
    solver_cache.GLOBAL_CACHE.clear()
    c0 = single_task.configure_tasks(ts.params, allowed,
                                     use_kernel=use_kernel, dedup=False)
    c1 = single_task.configure_tasks(ts.params, allowed,
                                     use_kernel=use_kernel, dedup=True)
    _assert_configs_equal(c0, c1)


@pytest.mark.parametrize("alg", ["edl", "edf-wf", "edf-bf", "lpt-ff"])
def test_offline_scheduler_dedup_bit_identical(alg):
    """All four offline policies: e_total and every per-assignment field
    must be bit-identical with and without the dedup layer."""
    ts = _dup_task_set(20, 240, SEED + 1)
    r0 = scheduling.schedule_offline(ts, l=2, theta=0.9, algorithm=alg,
                                     dedup=False)
    r1 = scheduling.schedule_offline(ts, l=2, theta=0.9, algorithm=alg,
                                     dedup=True)
    assert r1.e_total == r0.e_total
    assert r1.e_idle == r0.e_idle
    assert (r1.n_pairs, r1.n_servers, r1.violations) == \
        (r0.n_pairs, r0.n_servers, r0.violations)
    assert r1.assignments == r0.assignments


@pytest.mark.parametrize("alg", ["edl", "bin"])
def test_online_scheduler_dedup_bit_identical(alg):
    ts = tasks.generate_online(offline_util=0.02, online_util=0.05,
                               seed=1, horizon=120)
    r0 = online.schedule_online(ts, l=2, theta=0.9, algorithm=alg,
                                dedup=False)
    r1 = online.schedule_online(ts, l=2, theta=0.9, algorithm=alg,
                                dedup=True)
    assert r1.e_total == r0.e_total
    assert r1.assignments == r0.assignments


def test_kernel_classes_dedup_bit_identical():
    """The stacked heterogeneous-class kernel dispatch through the dedup
    layer (per-row interval bounds are part of the cache key)."""
    ts = _dup_task_set(16, 200, SEED + 2)
    kw = dict(l=2, theta=0.9, algorithm="edl",
              classes=("gtx-1080ti", "tpu-v5e"), use_kernel=True)
    r0 = scheduling.schedule_offline(ts, dedup=False, **kw)
    r1 = scheduling.schedule_offline(ts, dedup=True, **kw)
    assert r1.e_total == r0.e_total
    assert r1.assignments == r0.assignments


def test_cache_serves_repeat_calls():
    """A second identical call is answered from the cache (zero misses)
    with bit-identical output."""
    ts = _dup_task_set(16, 100, SEED + 3)
    allowed = ts.deadline - ts.arrival
    solver_cache.GLOBAL_CACHE.clear()
    c0 = single_task.configure_tasks(ts.params, allowed, dedup=True)
    solver_cache.GLOBAL_CACHE.reset_stats()
    c1 = single_task.configure_tasks(ts.params, allowed, dedup=True)
    assert solver_cache.GLOBAL_CACHE.misses == 0
    assert solver_cache.GLOBAL_CACHE.hits > 0
    _assert_configs_equal(c0, c1)


def test_theoretical_bound_dedup_bit_identical():
    ts = _dup_task_set(16, 150, SEED + 4)
    b0 = scheduling.bounds.theoretical_bound(ts, dedup=False)
    b1 = scheduling.bounds.theoretical_bound(ts, dedup=True)
    assert b0 == b1


# ---------------------------------------------------------------------------
# The cache data structure itself.
# ---------------------------------------------------------------------------


def test_lru_eviction_and_refresh():
    c = SolveCache(maxsize=3)
    rows = [np.full(8, float(i), np.float32) for i in range(5)]
    keys = [bytes([i]) for i in range(5)]
    for i in range(3):
        c.put("t", keys[i], rows[i])
    assert len(c) == 3
    # touching key 0 refreshes it; inserting key 3 must evict key 1 (LRU)
    assert c.get("t", keys[0]) is not None
    c.put("t", keys[3], rows[3])
    assert len(c) == 3
    assert c.get("t", keys[1]) is None          # evicted
    assert c.get("t", keys[0]) is not None      # refreshed, survived
    assert c.get("t", keys[3]) is not None
    # over-filling keeps the size bounded
    c.put("t", keys[4], rows[4])
    assert len(c) == 3


def test_cache_tags_namespace_entries():
    c = SolveCache(maxsize=8)
    c.put("a", b"k", np.zeros(8, np.float32))
    assert c.get("b", b"k") is None
    assert c.get("a", b"k") is not None


def test_solve_rows_dedups_within_call(rng):
    """solver_fn sees each unique row exactly once, scatter restores order;
    cache=None still dedups but persists nothing."""
    base = rng.random((6, solver_cache.KEY_COLS)).astype(np.float32)
    keys = base[rng.integers(0, 6, size=64)]
    calls = []

    def fn(km):
        calls.append(km.shape[0])
        return km[:, :8] * 2.0

    out = solve_rows(keys, fn, tag="test", cache=None)
    assert np.array_equal(out, keys[:, :8] * 2.0)
    assert len(calls) == 1 and calls[0] == 8    # 6 unique, pow-2 padded


# ---------------------------------------------------------------------------
# Kernel refinement + pad rows + sharding.
# ---------------------------------------------------------------------------


def test_kernel_refinement_monotone():
    """A finer (G0, G1) grid never yields MORE energy than the coarse grid
    on the golden task set (the fine winner is guarded against the coarse
    winner inside the kernel)."""
    from repro.kernels import ops

    lib = tasks.generate_offline(0.08, seed=9)
    allowed = np.asarray(lib.deadline - lib.arrival)
    keys = build_keys(lib.params.astuple(), allowed, False,
                      np.asarray(dvfs.WIDE.bounds(), np.float32))
    coarse = ops.dvfs_solve_matrix(keys, grid=(64, 2))
    fine = ops.dvfs_solve_matrix(keys, grid=(64, 64))
    feas = coarse[:, 7] > 0.5
    assert np.all(fine[feas, 5] <= coarse[feas, 5] * (1 + 1e-6))


def test_kernel_pad_rows_are_benign():
    """Pad rows (batch not a block multiple) cannot poison the block: a
    task's solution is identical whether it shares a block with pad rows
    or with other real tasks, and pads never produce inf/nan."""
    from repro.kernels import ops

    lib = tasks.generate_offline_n(5, seed=4, library=tasks.app_library())
    allowed = np.asarray(lib.deadline - lib.arrival)
    keys5 = build_keys(lib.params.astuple(), allowed, False,
                       np.asarray(dvfs.WIDE.bounds(), np.float32))
    out5 = ops.dvfs_solve_matrix(keys5, shard=False)      # 123 pad rows
    big = np.broadcast_to(keys5[-1], (256 - 5, keys5.shape[1]))
    out256 = ops.dvfs_solve_matrix(np.concatenate([keys5, big]), shard=False)
    assert np.array_equal(out5, out256[:5])
    assert np.all(np.isfinite(out5))


def test_sharded_dispatch_matches_single_device():
    """dvfs_solve_matrix(shard=True) is bitwise identical to the
    single-device path, each part placed straight on its own device —
    proven on 2 forced host devices in a subprocess (device count is fixed
    at jax import time)."""
    code = """
import numpy as np
from repro.core import dvfs, tasks
from repro.core.solver_cache import build_keys
from repro.kernels import ops
import jax
assert len(jax.local_devices()) == 2, jax.local_devices()
ts = tasks.generate_offline_n(5000, seed=5, library=tasks.app_library())
keys = build_keys(ts.params.astuple(),
                  np.asarray(ts.deadline - ts.arrival), False,
                  np.asarray(dvfs.WIDE.bounds(), np.float32))
placed, kernel = [], ops.dvfs_solve_kernel
def spy(x, **kw):
    placed.append(x.devices())
    return kernel(x, **kw)
ops.dvfs_solve_kernel = spy
a = ops.dvfs_solve_matrix(keys, shard=True)
ops.dvfs_solve_kernel = kernel
assert placed == [{d} for d in jax.local_devices()], placed
b = ops.dvfs_solve_matrix(keys, shard=False)
assert a.shape == (5000, 8)
assert np.array_equal(a, b)
print("OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src")]
                   + sys.path))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr[-2000:]
