"""Pipelined online scheduling (async solve prefetch + incremental pools).

The contract under test is ``schedule_online(pipeline=True)`` — the
default — being *bit-identical* to the synchronous reference path while
overlapping device solves with host placement:

* bit-identity grid over {edl, bin} x {vector, scalar} x theta x class
  mixes, plus the kernel / dedup-off / injected-config variants;
* the same identity under a pinned fault trace (epoch invalidation is on
  the hot path there);
* unit tests for the persistent-pool delta rules (epoch invalidation,
  batched power-off compaction) and the :class:`AsyncSolve` handle
  (``unique=False`` probe-side dedup, memoized result, pad grid);
* the solve-cache counter semantics that back ``result.cache_stats`` and
  ``BENCH_sched.json`` (per-run reset vs lifetime totals, duplicated-trace
  hit pinning).
"""

import inspect
import types

import numpy as np
import pytest

from repro.core import online, placement, solver_cache, tasks
from repro.core.faults import FaultTrace
from repro.core.solver_cache import (KEY_COLS, SOL_COLS, SolveCache,
                                     _pad_rows, solve_rows_async)

MIX = ("gtx-1080ti", "tpu-v5e")


def trace(n=500, pattern="uniform", horizon=60, seed=0):
    return tasks.generate_trace(n, pattern=pattern, horizon=horizon,
                                seed=seed)


def assert_same_schedule(r0, r1, fault_stats=False):
    assert r1.e_total == r0.e_total
    assert r1.violations == r0.violations
    assert r1.assignments == r0.assignments
    if fault_stats:
        assert r1.fault_stats == r0.fault_stats


# ---------------------------------------------------------------------------
# Bit-identity with the synchronous path (the tentpole contract).
# ---------------------------------------------------------------------------


def test_pipeline_is_the_default():
    sig = inspect.signature(online.schedule_online)
    assert sig.parameters["pipeline"].default is True


@pytest.mark.parametrize("classes", [None, MIX])
@pytest.mark.parametrize("theta", [1.0, 0.7])
@pytest.mark.parametrize("mode", ["vector", "scalar"])
@pytest.mark.parametrize("alg", ["edl", "bin"])
def test_pipeline_bit_identical(alg, mode, theta, classes):
    ts = trace(seed=3)
    kw = dict(l=2, theta=theta, algorithm=alg, placement=mode,
              classes=classes, bound=False)
    r0 = online.schedule_online(ts, pipeline=False, **kw)
    r1 = online.schedule_online(ts, pipeline=True, **kw)
    assert_same_schedule(r0, r1)


def test_pipeline_bit_identical_small_chunks(monkeypatch):
    """Force many chunk boundaries (the prefetch double-buffer actually
    cycles) on a small trace; still bit-identical."""
    monkeypatch.setattr(online, "PIPELINE_CHUNK_TASKS", 64)
    ts = trace(seed=4, pattern="bursty")
    kw = dict(l=2, theta=0.9, algorithm="edl", bound=False)
    r0 = online.schedule_online(ts, pipeline=False, **kw)
    r1 = online.schedule_online(ts, pipeline=True, **kw)
    assert_same_schedule(r0, r1)


@pytest.mark.parametrize("mode", ["vector", "scalar"])
@pytest.mark.parametrize("alg", ["edl", "bin"])
def test_pipeline_bit_identical_under_faults(alg, mode):
    """Fault transitions bump the pool epoch mid-run; the pipelined path
    must invalidate its carried state and stay bit-identical."""
    ts = trace(seed=5, pattern="bursty")
    tr = FaultTrace.sample(16, 60.0, mtbf=25.0, mttr=5.0, seed=2)
    kw = dict(l=2, theta=0.9, algorithm=alg, placement=mode, faults=tr,
              bound=False)
    r0 = online.schedule_online(ts, pipeline=False, **kw)
    r1 = online.schedule_online(ts, pipeline=True, **kw)
    assert r1.fault_stats["failures"] > 0   # the trace actually engaged
    assert_same_schedule(r0, r1, fault_stats=True)


def test_pipeline_bit_identical_kernel_path():
    ts = trace(n=300, seed=7)
    kw = dict(l=2, theta=0.9, use_kernel=True, bound=False)
    r0 = online.schedule_online(ts, pipeline=False, **kw)
    r1 = online.schedule_online(ts, pipeline=True, **kw)
    assert_same_schedule(r0, r1)


def test_pipeline_bit_identical_dedup_off():
    ts = trace(n=300, seed=8)
    kw = dict(l=2, theta=0.9, dedup=False, bound=False)
    r0 = online.schedule_online(ts, pipeline=False, **kw)
    r1 = online.schedule_online(ts, pipeline=True, **kw)
    assert r1.cache_stats is None
    assert_same_schedule(r0, r1)


def test_pipeline_injected_cfgs_bit_identical():
    """With precomputed configs there is nothing to prefetch; the driver
    degenerates to chunked placement + readjustment prefetch only."""
    ts = trace(n=300, seed=9)
    mcs = online.machines.reference_classes()
    cfgs = online.online_configs(ts, mcs)
    kw = dict(l=2, theta=0.9, cfgs=cfgs, bound=False)
    r0 = online.schedule_online(ts, pipeline=False, **kw)
    r1 = online.schedule_online(ts, pipeline=True, **kw)
    assert_same_schedule(r0, r1)


# ---------------------------------------------------------------------------
# Persistent-pool delta rules (unit level).
# ---------------------------------------------------------------------------


class _StubEngine:
    """Just enough of ClusterEngine for _GroupPools' reconciliation path."""

    def __init__(self):
        self.pool_epoch = 0
        self.classes = [None]           # single class: no server_class calls
        self.drains = 0

    def drain_offs(self):
        self.drains += 1
        return []


def _stub_pools(grain=2):
    eng = _StubEngine()
    ctx = types.SimpleNamespace(eng=eng, grain=grain,
                                pre={"t_hat_l": None})
    gp = placement._GroupPools(ctx, 0.0, None, None, None, None)
    return eng, gp


def test_epoch_bump_invalidates_carried_pools():
    """A fault transition (pool_epoch bump) drops every carried pool —
    the next group rebuilds lazily from the live engine."""
    eng, gp = _stub_pools()
    gp.pools[0] = [np.arange(6, dtype=np.int64), np.zeros(6), 6]
    eng.pool_epoch += 1
    gp.begin_group(1.0, None, None, None, None)
    assert gp.epoch == eng.pool_epoch
    assert not gp.pools
    assert eng.drains == 1              # queued power-offs still consumed


def test_same_epoch_keeps_carried_pools():
    eng, gp = _stub_pools()
    ids = np.arange(6, dtype=np.int64)
    gp.pools[0] = [ids, np.zeros(6), 6]
    gp.begin_group(1.0, None, None, None, None)
    assert gp.pools[0][0] is ids        # untouched carry


def test_power_off_deletion_compacts_pool_and_stream():
    """Batched power-off: one keep-mask compaction per class that keeps
    the surviving pairs in id order with their own mu."""
    eng, gp = _stub_pools(grain=2)      # pair id = 2 * server + k
    ids = np.arange(8, dtype=np.int64)  # servers 0..3, fully pooled
    mus = np.arange(8, dtype=np.float64)
    gp.pools[0] = [ids.copy(), mus.copy(), 8]
    gp.apply_offs([1])                  # cuts pair ids 2 and 3
    ids2, mus2, n2 = gp.pools[0]
    assert n2 == 6
    assert list(ids2[:n2]) == [0, 1, 4, 5, 6, 7]
    assert list(mus2[:n2]) == [0.0, 1.0, 4.0, 5.0, 6.0, 7.0]


def test_power_off_of_unpooled_server_is_a_noop():
    eng, gp = _stub_pools(grain=2)
    ids = np.arange(4, dtype=np.int64)  # servers 0..1 only
    gp.pools[0] = [ids.copy(), np.zeros(4), 4]
    gp.apply_offs([3])                  # server 3 never entered the pool
    assert gp.pools[0][2] == 4
    assert list(gp.pools[0][0]) == [0, 1, 2, 3]


# ---------------------------------------------------------------------------
# AsyncSolve handle and the pad grid.
# ---------------------------------------------------------------------------


def _toy_solver(calls):
    def fn(km):
        calls.append(km.shape[0])
        out = np.zeros((km.shape[0], SOL_COLS), np.float32)
        out[:, 0] = km[:, 0] * 2.0
        out[:, 1] = km[:, 1] + 1.0
        return out
    return fn


def _dup_keys(n_unique, n_total, seed=0):
    rng = np.random.default_rng(seed)
    uniq = rng.random((n_unique, KEY_COLS)).astype(np.float32)
    return uniq[rng.integers(0, n_unique, size=n_total)]


def test_async_solve_unique_false_matches_unique_true():
    """The pipelined chunks skip the sort-based np.unique pass
    (``unique=False``) and lean on the cache probe; results are identical
    and only the dispatched row count differs."""
    keys = _dup_keys(40, 130, seed=1)
    c_t, c_f = SolveCache(), SolveCache()
    calls_t, calls_f = [], []
    h_t = solve_rows_async(keys, _toy_solver(calls_t), tag="t", cache=c_t,
                           unique=True)
    h_f = solve_rows_async(keys, _toy_solver(calls_f), tag="t", cache=c_f,
                           unique=False)
    assert h_t.in_flight and h_f.in_flight
    assert h_t.n_missing <= 40 < h_f.n_missing == 130
    r_t, r_f = h_t.result(), h_f.result()
    assert not h_t.in_flight and not h_f.in_flight
    assert r_t.shape == r_f.shape == (130, SOL_COLS)
    assert np.array_equal(r_t, r_f)
    assert h_t.result() is r_t          # memoized


def test_async_solve_feeds_the_cache():
    keys = _dup_keys(24, 90, seed=2)
    cache = SolveCache()
    calls = []
    first = solve_rows_async(keys, _toy_solver(calls), tag="t", cache=cache,
                             unique=False).result()
    again = solve_rows_async(keys, _toy_solver(calls), tag="t", cache=cache,
                             unique=False)
    assert again.n_missing == 0         # fully served from the cache
    assert np.array_equal(again.result(), first)
    assert len(calls) == 1              # the solver ran exactly once


@pytest.mark.parametrize("k,expect", [
    (1, 8), (5, 8), (8, 8), (9, 16), (600, 1024), (1024, 1024),
    (1025, 2048), (2049, 3072)])
def test_pad_rows_shape_grid(k, expect):
    """Powers of two (>= 8) up to 1024, 1024-multiples above — so jit
    compiles a bounded family of solver shapes."""
    m = np.arange(k * 2, dtype=np.float32).reshape(k, 2)
    p = _pad_rows(m)
    assert p.shape == (expect, 2)
    assert np.array_equal(p[:k], m)
    if expect > k:
        assert np.array_equal(
            p[k:], np.broadcast_to(m[-1], (expect - k, 2)))


@pytest.mark.parametrize("readjust,k,expect", [
    (1.0, 1, 512), (1.0, 9, 512), (1.0, 300, 512), (1.0, 513, 1024),
    (1.0, 1500, 2048), (0.0, 9, 16), (0.0, 300, 512)])
def test_readjust_batches_pad_to_one_shape(readjust, k, expect):
    """Readjustment batches (key column ``readjust`` set) pad to at least
    512 rows, so a day's few-to-hundreds readjusts share one solver shape;
    deadline batches keep the grid from 8."""
    keys = np.random.default_rng(k).random((k, KEY_COLS)).astype(np.float32)
    keys[:, solver_cache.layout.READJUST] = readjust
    calls = []
    got = solve_rows_async(keys, _toy_solver(calls), tag="t", cache=None,
                           unique=False).result()
    assert calls == [expect]
    assert np.array_equal(got[:, 0], keys[:, 0] * 2.0)


@pytest.mark.parametrize("sizes,expect", [
    ([3, 3, 2], [[3, 3, 2]]),
    ([4, 4, 4, 4], [[4, 4], [4, 4]]),
    ([5, 4, 2, 9, 1], [[5], [4, 2], [9], [1]]),
    ([8, 1], [[8], [1]])])
def test_chunks_hold_at_most_the_target(sizes, expect):
    """Whole groups, each run within the target (one that alone exceeds
    it runs by itself), so a full run's solve pads to the target's shape."""
    groups = [(s, np.arange(n)) for s, n in enumerate(sizes)]
    chunks = online._chunk_groups(groups, 8)
    assert [[g[1].size for g in c] for c in chunks] == expect
    assert [g for c in chunks for g in c] == groups


# ---------------------------------------------------------------------------
# Cache counters: per-run reset vs lifetime totals, hit pinning.
# ---------------------------------------------------------------------------


def test_reset_stats_preserves_lifetime_totals():
    c = SolveCache(maxsize=8)
    keys = _dup_keys(2, 2, seed=3)
    out = np.zeros((2, SOL_COLS), np.float32)
    miss, miss_keys = c.get_many("t", keys, out)
    assert (c.misses, c.misses_total) == (2, 2)
    c.put_keys(miss_keys, [np.zeros(SOL_COLS, np.float32)] * 2)
    c.get_many("t", keys, out)
    assert (c.hits, c.hits_total) == (2, 2)
    c.reset_stats()
    assert (c.hits, c.misses) == (0, 0)
    assert (c.hits_total, c.misses_total) == (2, 2)
    s = c.stats()
    assert s["hits"] == 0 and s["hits_total"] == 2


def test_eviction_counters_per_run_and_lifetime():
    c = SolveCache(maxsize=2)
    keys = _dup_keys(3, 3, seed=4)
    out = np.zeros((3, SOL_COLS), np.float32)
    _, miss_keys = c.get_many("t", keys, out)
    c.put_keys(miss_keys, [np.zeros(SOL_COLS, np.float32)] * 3)
    assert (c.evictions, c.evictions_total) == (1, 1)
    c.reset_stats()
    assert c.evictions == 0 and c.evictions_total == 1


def test_schedule_online_resets_per_run_counters():
    """Every dedup run reports its OWN counters in ``cache_stats`` — the
    cached rows persist, so a warm rerun is pure hits."""
    ts = trace(n=300, seed=10)
    solver_cache.GLOBAL_CACHE.clear()
    s1 = online.schedule_online(ts, l=2, theta=0.9,
                                bound=False).cache_stats
    s2 = online.schedule_online(ts, l=2, theta=0.9,
                                bound=False).cache_stats
    assert s1["misses"] > 0
    assert s2["misses"] == 0
    assert s2["hits"] == s1["hits"] + s1["misses"]
    assert s2["hits_total"] >= s2["hits"] + s1["hits"]


def test_duplicated_trace_cache_hits_pinned(monkeypatch):
    """A trace whose second epoch replays the first (same params, same
    DVFS windows, shifted arrivals) must be answered from the cache once
    chunk boundaries separate the epochs — and the counters are
    deterministic run to run."""
    monkeypatch.setattr(online, "PIPELINE_CHUNK_TASKS", 64)
    ts = trace(n=300, horizon=40, seed=11)
    shifted = tasks.TaskSet(ts.arrival + 40.0, ts.deadline + 40.0,
                            ts.params, ts.utilization)
    dup = ts.concat(shifted)
    kw = dict(l=2, theta=0.9, bound=False)
    solver_cache.GLOBAL_CACHE.clear()
    s1 = online.schedule_online(dup, **kw).cache_stats
    # Every second-epoch Algorithm-1 row re-probes a first-epoch key.
    assert s1["hits"] >= len(ts)
    # Cold-cache per-run counters are pinned: an identical rerun
    # reproduces them exactly (the *_total fields keep accumulating
    # across runs by design, so compare the per-run view only).
    solver_cache.GLOBAL_CACHE.clear()
    s2 = online.schedule_online(dup, **kw).cache_stats
    per_run = ("rows", "hits", "misses", "evictions", "hit_rate")
    assert {k: s2[k] for k in per_run} == {k: s1[k] for k in per_run}
