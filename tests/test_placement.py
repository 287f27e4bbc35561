"""The shared placement subsystem (``core/placement.py``) and the §5
theoretical bound (``core/bounds.py``).

``schedule_offline`` is now a thin driver over the same placement core the
online simulator uses.  These tests pin

* scalar/vector bit-identity for all four offline policies across
  {homogeneous, mixed-class} x theta in {1.0, 0.7};
* the PR-1 offline golden energies, unchanged to 1e-9 rel (exact values
  re-recorded from the pre-refactor implementation at commit 2b52443,
  which reproduced the seed goldens of ``tests/test_engine.py`` to 1e-6);
* online scalar/vector bit-identity on days whose arrival groups straddle
  the small-group crossover of ``place_group_vector``;
* the §5 wide-interval ~36% savings ceiling from ``theoretical_bound``
  and the e_bound reporting contract of both schedulers.
"""

import heapq

import numpy as np
import pytest

from repro.core import (bounds, cluster as cl, machines, online, placement,
                        scheduling, tasks)
from repro.core.faults import FaultTrace


@pytest.fixture(scope="module")
def library():
    return tasks.app_library()


MIXES = {"homogeneous": None, "mixed": ("gtx-1080ti", "tpu-v5e")}

# Exact e_total/e_idle of the pre-refactor schedule_offline (commit
# 2b52443) on generate_offline(0.1, seed=3), l=2, theta=0.9 — the same
# scenario whose seed goldens tests/test_engine.py pins at 1e-6.  The
# placement-subsystem driver must reproduce them to 1e-9 rel (it matches
# bit-for-bit).
OFFLINE_GOLDEN_EXACT = {
    "edl":    (3678787.8401555126, 6735.992463771603, 84, 42, 0),
    "edf-wf": (3669301.5104696816, 18451.408134148674, 91, 46, 0),
    "edf-bf": (3725938.3543846672, 75088.25204913388, 78, 39, 0),
    "lpt-ff": (3708240.1715263743, 57390.06919084124, 114, 57, 0),
}


def _fields(a):
    return (a.task, a.pair, a.start, a.finish, a.v, a.fc, a.fm, a.power,
            a.energy, a.readjusted, a.class_id)


# ---------------------------------------------------------------------------
# Scalar vs vectorized offline placement: bit-identical.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("theta", [1.0, 0.7])
@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("alg", ["edl", "edf-wf", "edf-bf", "lpt-ff"])
def test_offline_vector_bit_identical(alg, mix, theta, library):
    ts = tasks.generate_offline(0.08, seed=13, library=library)
    kw = dict(l=3, theta=theta, algorithm=alg, classes=MIXES[mix],
              bound=False)
    r_s = scheduling.schedule_offline(ts, placement="scalar", **kw)
    r_v = scheduling.schedule_offline(ts, placement="vector", **kw)
    assert r_v.e_total == r_s.e_total           # bit-for-bit
    assert r_v.e_idle == r_s.e_idle
    assert (r_v.n_pairs, r_v.n_servers, r_v.violations) == \
        (r_s.n_pairs, r_s.n_servers, r_s.violations)
    assert len(r_v.assignments) == len(r_s.assignments)
    for a, b in zip(r_s.assignments, r_v.assignments):
        assert _fields(a) == _fields(b)


def test_offline_vector_bit_identical_wide_batch(library):
    """A batch large enough (~2k tasks) to exercise the bulk fresh-open
    heap path of the vectorized offline EDL placement."""
    ts = tasks.generate_offline_n(2000, seed=1, library=library)
    kw = dict(l=4, theta=0.9, algorithm="edl", bound=False)
    r_s = scheduling.schedule_offline(ts, placement="scalar", **kw)
    r_v = scheduling.schedule_offline(ts, placement="vector", **kw)
    assert r_v.e_total == r_s.e_total
    for a, b in zip(r_s.assignments, r_v.assignments):
        assert _fields(a) == _fields(b)


def test_unknown_offline_placement_rejected(library):
    ts = tasks.generate_offline(0.02, seed=0, library=library)
    with pytest.raises(ValueError):
        scheduling.schedule_offline(ts, placement="warp")


# ---------------------------------------------------------------------------
# Online days around the small-group crossover: bit-identical.
# ---------------------------------------------------------------------------


def _group_sizes(kind):
    """Group sizes around the crossover: a pool's pairs move it, but the
    test days hold under a hundred, too few to move it by one task."""
    s = placement._SMALL_GROUP
    return {"below": [s - 1] * 8, "at": [s] * 8, "above": [s + 1] * 8,
            "mix": [s - 1, 2 * s, 1, s, 3, s + 1, s - 1, 2, s]}[kind]


def _sized_day(sizes, seed=5, gap=150.0):
    """Tasks drawn the §5.1.3 way, one arrival group of each size every
    ``gap`` slots (long enough that earlier tasks free pairs of servers
    still on, so a group meets a non-empty pool), each task keeping its
    drawn window."""
    ts = tasks.generate_offline_n(sum(sizes), seed=seed)
    arrival = np.repeat(gap * np.arange(1.0, len(sizes) + 1.0), sizes)
    return tasks.TaskSet(arrival, arrival + ts.deadline, ts.params,
                         ts.utilization)


class _CountingHeapq:
    """``heapq`` with its ``heapify`` calls counted: the heap finisher
    heapifies its seed once per group and the per-task rule never does."""

    def __init__(self):
        self.heapify_calls = 0
        self.heappush = heapq.heappush
        self.heapreplace = heapq.heapreplace

    def heapify(self, heap):
        self.heapify_calls += 1
        heapq.heapify(heap)


@pytest.mark.parametrize("faults", [False, True])
@pytest.mark.parametrize("pipeline", [True, False])
@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("sizes", ["below", "at", "above", "mix"])
def test_online_vector_bit_identical_around_small_groups(sizes, mix,
                                                         pipeline, faults,
                                                         monkeypatch):
    ts = _sized_day(_group_sizes(sizes))
    kw = dict(l=2, theta=0.9, algorithm="edl", classes=MIXES[mix],
              pipeline=pipeline, bound=False)
    if faults:
        kw["faults"] = FaultTrace.sample(
            tasks.peak_pair_estimate(ts) // 2, float(ts.arrival[-1]),
            mtbf=400.0, mttr=50.0, seed=1)
    r_s = online.schedule_online(ts, placement="scalar", **kw)
    heap = _CountingHeapq()
    monkeypatch.setattr(placement, "heapq", heap)
    r_v = online.schedule_online(ts, placement="vector", **kw)
    assert r_v.e_total == r_s.e_total           # bit-for-bit
    assert (r_v.e_idle, r_v.e_overhead, r_v.violations, r_v.n_pairs) == \
        (r_s.e_idle, r_s.e_overhead, r_s.violations, r_s.n_pairs)
    assert r_v.fault_stats == r_s.fault_stats
    if faults:
        assert r_v.fault_stats["failures"] > 0
    assert [_fields(a) for a in r_v.assignments] == \
        [_fields(a) for a in r_s.assignments]
    # every task by a per-task rule, re-placed orphans once more
    assert r_v.counters["placement.scalar"] == r_v.counters["tasks"] \
        + (r_v.fault_stats or {}).get("restarted", 0)
    if sizes == "below":
        # no pool of the day holds enough pairs to move the crossover
        assert (placement._SMALL_GROUP - 1) * (
            placement._SMALL_GROUP_POOL + r_v.n_pairs) \
            < placement._SMALL_GROUP * placement._SMALL_GROUP_POOL
    if sizes == "below" or mix == "mixed":
        assert heap.heapify_calls == 0          # every task by place_one
    elif sizes in ("at", "above"):
        assert heap.heapify_calls > 0           # the heap finisher ran


# ---------------------------------------------------------------------------
# PR-1 golden energies: unchanged through the refactor.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("alg", sorted(OFFLINE_GOLDEN_EXACT))
def test_offline_energies_unchanged_to_1e9(alg, library):
    ts = tasks.generate_offline(0.1, seed=3, library=library)
    r = scheduling.schedule_offline(ts, l=2, theta=0.9, algorithm=alg)
    e_total, e_idle, n_pairs, n_servers, violations = \
        OFFLINE_GOLDEN_EXACT[alg]
    assert r.e_total == pytest.approx(e_total, rel=1e-9)
    assert r.e_idle == pytest.approx(e_idle, rel=1e-9)
    assert (r.n_pairs, r.n_servers, r.violations) == \
        (n_pairs, n_servers, violations)
    # ... and the seed goldens of tests/test_engine.py still hold at their
    # original 1e-6 through this exact chain.
    from test_engine import OFFLINE_GOLDEN
    assert r.e_total == pytest.approx(OFFLINE_GOLDEN[alg][0], rel=1e-6)


# The old hasattr-based meta test ("online.py owns no placement internals")
# is retired: the layer-contract lint rule (tools/lint, backed by
# tools/lint/layer_dag.py) now enforces the import DAG for every module,
# not just this one edge — tests/test_lint.py covers it.


# ---------------------------------------------------------------------------
# The §5 theoretical bound.
# ---------------------------------------------------------------------------


def test_theoretical_bound_reproduces_wide_ceiling(library):
    """Paper §5: with the wide (analytic) scaling interval at most ~36% of
    energy can be saved; the generated library is calibrated to the 36.4%
    Fig. 4 anchor and the aggregate ceiling lands right there."""
    ts = tasks.generate_offline(0.3, seed=0, library=library)
    b = bounds.theoretical_bound(ts)
    assert b.savings_ceiling == pytest.approx(0.3646, abs=0.01)
    assert b.e_idle == 0.0 and b.e_overhead == 0.0   # exact-fit floor
    assert b.e_baseline == pytest.approx(cl.baseline_energy(ts))


def test_achieved_savings_stay_below_ceiling(library):
    """The schedulers' achieved savings (paper: 33-35%) must sit below the
    analytical ceiling, and every reported e_total above its e_bound."""
    ts = tasks.generate_offline(0.3, seed=0, library=library)
    base = cl.baseline_energy(ts)
    r = scheduling.schedule_offline(ts, l=1, algorithm="edl")
    assert r.e_bound > 0.0
    assert r.e_total >= r.e_bound
    achieved = 1.0 - r.e_total / base
    ceiling = bounds.theoretical_bound(ts).savings_ceiling
    assert 0.30 <= achieved <= ceiling


def test_bound_floor_per_task(library):
    """Per-task check: no assignment's energy beats its unconstrained
    optimum (the bound's run floor is truly per-task)."""
    ts = tasks.generate_offline(0.05, seed=21, library=library)
    from repro.core import dvfs, single_task
    mcs = machines.resolve_classes(None)
    params, _, _, _ = single_task.pad_pow2(ts.params, np.zeros(len(ts)))
    e_unc = bounds.unconstrained_energies(params, mcs, dvfs.WIDE, len(ts))
    r = scheduling.schedule_offline(ts, l=2, theta=0.9, algorithm="edl")
    for a in r.assignments:
        assert a.energy >= e_unc[0, a.task] - 1e-6 * abs(e_unc[0, a.task])


def test_online_bound_includes_drs_floors(library):
    """rho > 0 adds the exact online floors: one power-on of l pairs
    (Delta each) and rho idle slots per powered pair."""
    ts = tasks.generate_online(0.02, 0.05, seed=1, horizon=200,
                               library=library)
    b_off = bounds.theoretical_bound(ts)
    b_on = bounds.theoretical_bound(ts, l=4, rho=2)
    assert b_on.e_run == b_off.e_run
    assert b_on.e_idle == pytest.approx(cl.P_IDLE * 2 * 4)
    assert b_on.e_overhead == pytest.approx(cl.DELTA_ON * 4)
    r = online.schedule_online(ts, l=4, theta=1.0, algorithm="edl")
    assert r.e_bound == pytest.approx(b_on.e_bound)
    assert r.e_total >= r.e_bound


def test_bound_flag_and_summary(library):
    ts = tasks.generate_offline(0.02, seed=2, library=library)
    r0 = scheduling.schedule_offline(ts, bound=False)
    assert r0.e_bound == 0.0 and r0.bound_gap == 0.0
    r1 = scheduling.schedule_offline(ts)
    assert r1.e_bound > 0.0
    assert r1.summary()["e_bound"] == r1.e_bound
    assert r1.bound_gap == pytest.approx(r1.e_total / r1.e_bound - 1.0)


def test_bound_empty_task_set():
    empty = tasks.TaskSet(np.zeros(0), np.zeros(0),
                          tasks.app_library()[np.zeros(0, dtype=np.int64)],
                          np.zeros(0))
    b = bounds.theoretical_bound(empty)
    assert b.e_bound == 0.0 and b.savings_ceiling == 0.0


# ---------------------------------------------------------------------------
# Engine bulk accessors backing the subsystem.
# ---------------------------------------------------------------------------


def test_engine_open_pairs_matches_scalar_loop():
    from repro.core.engine import ClusterEngine
    a = ClusterEngine(l=2, servers=False,
                      classes=machines.get_classes(("gtx-1080ti",
                                                    "tpu-v5e")))
    b = ClusterEngine(l=2, servers=False,
                      classes=machines.get_classes(("gtx-1080ti",
                                                    "tpu-v5e")))
    cls = np.asarray([0, 1, 1, 0, 1], dtype=np.int64)
    base = a.open_pairs(cls)
    assert base == 0 and a.n_pairs == 5
    for c in cls:
        b.open_pair(class_id=int(c))
    np.testing.assert_array_equal(a.pair_class, b.pair_class)
    np.testing.assert_array_equal(a.mu, b.mu)


def test_engine_pool_ids_offline_and_online():
    from repro.core.engine import ClusterEngine
    mcs = machines.get_classes(("gtx-1080ti", "tpu-v5e"))
    off = ClusterEngine(l=2, servers=False, classes=mcs)
    off.open_pairs(np.asarray([0, 1, 0], dtype=np.int64))
    np.testing.assert_array_equal(off.pool_ids(0), [0, 2])
    np.testing.assert_array_equal(off.pool_ids(1), [1])
    on = ClusterEngine(l=2, servers=True, classes=mcs)
    on.acquire_pair(0.0, class_id=1)
    on.acquire_pair(0.0, class_id=0)
    on.drs_sweep(100.0)                    # both servers power off
    assert on.pool_ids(0).size == 0 and on.pool_ids(1).size == 0
    on.acquire_pair(100.0, class_id=1)     # re-wakes the class-1 server
    np.testing.assert_array_equal(on.pool_ids(1), [0, 1])
