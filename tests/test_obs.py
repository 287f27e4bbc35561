"""The scheduler's own spans and per-call counters (``repro.core.obs``):
the spans a call opens under ``jax.profiler``, read back from the trace;
the counters' exact values on pinned inputs; the call record's life (a
nested call, the bounded ring, a call that raises); and schedules that do
not change when a profiler session runs."""

import gc
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import obs, online, scheduling, solver_cache, tasks

PREFIXES = ("schedule.", "solve.", "placement.", "engine.", "host.")
KW = dict(l=4, theta=0.9, bound=False)


def offline_set():
    return tasks.generate_offline(0.3, seed=1)


def online_set():
    return tasks.generate_online(0.1, 0.3, horizon=60, seed=2)


def traced(fn, tmp_path):
    """Run ``fn`` under a profiler session; returns its result and the
    host spans whose names look like the program's, as ``(name, start,
    end, thread)``."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns, i)
                         for e in line.events if e.name.startswith(PREFIXES))
    return out, spans


def assert_nested(spans, call_names):
    """Spans on one thread nest (none partly overlaps another), and every
    span lies inside a call span of that thread."""
    by_thread = {}
    for sp in spans:
        by_thread.setdefault(sp[3], []).append(sp)
    for items in by_thread.values():
        items.sort(key=lambda x: (x[1], -x[2]))
        stack = []
        for name, s, e, _ in items:
            while stack and stack[-1][2] <= s:
                stack.pop()
            if stack:
                assert e <= stack[-1][2], (name, stack[-1][0])
            else:
                assert name in call_names, name
            stack.append((name, s, e))


@pytest.mark.parametrize("entry", ["offline", "online"])
def test_calls_open_the_expected_spans(entry, tmp_path):
    common = {"solve.keys", "solve.dedup", "solve.probe", "solve.dispatch",
              "solve.wait", "solve.fill", "solve.config",
              "placement.prepare", "placement.group",
              "schedule.records", "schedule.account", "engine.finalize"}
    solver_cache.GLOBAL_CACHE.clear()
    if entry == "offline":
        ts = offline_set()
        call = "schedule.offline"
        expected = common | {call, "placement.pin"}
        _, spans = traced(lambda: scheduling.schedule_offline(ts, **KW),
                          tmp_path)
    else:
        ts = online_set()
        call = "schedule.online"
        expected = (common - {"solve.dedup"}) | {call, "engine.settle"}
        _, spans = traced(lambda: online.schedule_online(ts, **KW), tmp_path)
    names = {sp[0] for sp in spans} - {"host.gc"}
    assert names <= obs.NAMES
    assert names == expected
    assert sum(sp[0] == call for sp in spans) == 1
    assert_nested(spans, {call})


def test_garbage_collections_open_spans_inside_a_call(tmp_path):
    class Result:
        counters = None

    @obs.call("schedule.offline")
    def collecting():
        gc.collect()
        return Result()

    r, spans = traced(collecting, tmp_path)
    assert r.counters["gc.collections"] >= 1
    assert r.counters["gc.full"] >= 1
    assert sum(sp[0] == "host.gc" for sp in spans) >= 1
    assert_nested(spans, {"schedule.offline"})
    assert not any(getattr(cb, "__module__", None) == obs.__name__
               for cb in gc.callbacks)


@pytest.mark.parametrize("generations,full", [((0,), 0), ((0, 1), 0),
                                              ((0, 1, 2), 1), ((2, 2), 2)])
def test_full_collections_are_counted_apart(generations, full):
    """``gc.full`` counts the collections of the oldest generation only;
    ``gc.collections`` counts every one."""
    class Result:
        counters = None

    @obs.call("schedule.online")
    def collecting():
        for g in generations:
            gc.collect(g)
        return Result()

    gc.collect()              # no automatic collection is due in the call
    r = collecting()
    assert r.counters["gc.full"] == full
    assert r.counters["gc.collections"] >= len(generations)


def pinned(counters):
    return {k: v for k, v in counters.items()
            if k not in ("gc.collections", "gc.full")}


def test_offline_counters_are_pinned():
    solver_cache.GLOBAL_CACHE.clear()
    ts = offline_set()
    r = scheduling.schedule_offline(ts, **KW)
    assert pinned(r.counters) == {
        "tasks": 602, "solve.rows": 1056, "solve.hits": 0,
        "solve.misses": 631, "solve.evictions": 0, "solve.sent": 1536,
        "solve.pad": 905, "placement.batched": 0, "placement.scalar": 512,
        "placement.pinned": 90, "placement.fallback": 0}
    # a warm rerun: every row a hit, nothing sent
    r2 = scheduling.schedule_offline(ts, **KW)
    assert pinned(r2.counters) == {**pinned(r.counters), "solve.hits": 631,
                                   "solve.misses": 0, "solve.sent": 0,
                                   "solve.pad": 0}


def test_online_counters_are_pinned():
    solver_cache.GLOBAL_CACHE.clear()
    ts = online_set()
    r = online.schedule_online(ts, **KW)
    assert pinned(r.counters) == {
        "tasks": 802, "solve.rows": 819, "solve.hits": 0,
        "solve.misses": 819, "solve.evictions": 0, "solve.sent": 1536,
        "solve.pad": 717, "placement.batched": 0, "placement.scalar": 802,
        "placement.pinned": 0, "placement.fallback": 0}


@pytest.mark.parametrize("algorithm", ["edl", "bin"])
@pytest.mark.parametrize("placement", ["vector", "scalar"])
@pytest.mark.parametrize("entry", ["offline", "online"])
def test_counters_add_up(entry, placement, algorithm):
    """Every task is placed once by exactly one path, and every row sent
    to the solver is a cache miss or padding."""
    if entry == "offline":
        alg = "edl" if algorithm == "edl" else "lpt-ff"
        r = scheduling.schedule_offline(offline_set(), placement=placement,
                                        algorithm=alg, **KW)
    else:
        r = online.schedule_online(online_set(), placement=placement,
                                   algorithm=algorithm, **KW)
    c = r.counters
    assert c["placement.batched"] + c["placement.scalar"] \
        + c["placement.pinned"] == c["tasks"] == len(r.assignments)
    if placement == "scalar" or algorithm == "bin":
        assert c["placement.batched"] == 0
    assert c["solve.pad"] == c["solve.sent"] - c["solve.misses"]
    assert c["solve.rows"] >= c["solve.hits"] + c["solve.misses"]
    assert r.cache_stats["hits"] == c["solve.hits"]
    assert r.cache_stats["misses"] == c["solve.misses"]


FLEET = ("tesla-t4", "tesla-p100", "v100-sxm2")


@pytest.mark.parametrize("placement", ["vector", "scalar"])
@pytest.mark.parametrize("entry,algorithm,fallback", [
    ("online", "edl", 396), ("online", "bin", 339),
    ("offline", "edl", 207), ("offline", "lpt-ff", 40)])
def test_fallback_counter_is_pinned(entry, algorithm, fallback, placement):
    """On three classes every placement rule counts the tasks it puts on
    a pair in use of a class other than their primary one, and the vector
    and scalar paths count alike."""
    if entry == "offline":
        r = scheduling.schedule_offline(offline_set(), classes=FLEET,
                                        placement=placement,
                                        algorithm=algorithm, **KW)
    else:
        r = online.schedule_online(online_set(), classes=FLEET,
                                   placement=placement, algorithm=algorithm,
                                   **KW)
    assert r.counters["placement.fallback"] == fallback


@pytest.mark.parametrize("classes", [None, ("v100-sxm2",)])
def test_fallback_counter_is_zero_on_one_class(classes):
    r = online.schedule_online(online_set(), classes=classes, **KW)
    assert r.counters["placement.fallback"] == 0


def test_cache_stats_come_from_the_call_not_the_cache():
    """The process-wide cache's own per-run counters are no longer reset
    by a call; ``cache_stats`` reads the call's counters."""
    ts = online_set()
    online.schedule_online(ts, **KW)
    before = solver_cache.GLOBAL_CACHE.hits
    r = online.schedule_online(ts, **KW)
    assert r.cache_stats["misses"] == 0
    assert r.cache_stats["hits"] == r.counters["solve.hits"] > 0
    assert solver_cache.GLOBAL_CACHE.hits == before + r.cache_stats["hits"]


class _Result:
    counters = None


def test_a_nested_call_records_once():
    @obs.call("schedule.online")
    def inner():
        obs.count("tasks", 3)
        return _Result()

    @obs.call("schedule.offline")
    def outer():
        obs.count("tasks", 2)
        r = inner()
        assert r.counters is None
        assert obs.counts()["tasks"] == 5
        return _Result()

    n0 = len(obs.recent(obs.RING_CALLS))
    r = outer()
    assert r.counters["tasks"] == 5
    assert obs.recent(1) == [r.counters]
    assert len(obs.recent(obs.RING_CALLS)) == min(n0 + 1, obs.RING_CALLS)


def test_a_raising_call_closes_its_record():
    @obs.call("schedule.offline")
    def failing():
        obs.count("tasks", 7)
        raise RuntimeError("boom")

    with pytest.raises(RuntimeError, match="boom"):
        failing()
    assert obs.recent(1)[0]["tasks"] == 7
    assert obs.counts() == {}
    obs.count("tasks", 1)             # outside a call: nothing to add to
    assert obs.counts() == {}
    assert not any(getattr(cb, "__module__", None) == obs.__name__
               for cb in gc.callbacks)


def test_the_ring_stays_bounded():
    @obs.call("schedule.offline")
    def one(i):
        obs.count("tasks", i)
        return _Result()

    assert obs.RING_CALLS >= 16384
    assert obs.RING_CALLS * len(obs.COUNTERS) * 8 <= 4 << 20
    for i in range(obs.RING_CALLS + 5):
        one(i)
    rows = obs.recent(obs.RING_CALLS + 100)
    assert len(rows) == obs.RING_CALLS
    assert [r["tasks"] for r in rows[-3:]] == [obs.RING_CALLS + 2,
                                               obs.RING_CALLS + 3,
                                               obs.RING_CALLS + 4]
    assert rows[0]["tasks"] == 5
    with pytest.raises(KeyError):
        obs.count("no.such.counter")


def _same(r0, r1):
    assert r0.e_total == r1.e_total
    assert r0.violations == r1.violations
    assert r0.n_pairs == r1.n_pairs
    assert r0.assignments == r1.assignments


@pytest.mark.parametrize("entry", ["offline", "online"])
def test_schedules_are_identical_with_a_profiler_session(entry, tmp_path):
    if entry == "offline":
        ts = offline_set()

        def run():
            return scheduling.schedule_offline(ts, **KW)
    else:
        ts = online_set()

        def run():
            return online.schedule_online(ts, **KW)
    solver_cache.GLOBAL_CACHE.clear()
    plain = run()
    solver_cache.GLOBAL_CACHE.clear()
    under, _ = traced(run, tmp_path)
    _same(plain, under)
    assert np.array_equal(
        [a.energy for a in plain.assignments],
        [a.energy for a in under.assignments])
