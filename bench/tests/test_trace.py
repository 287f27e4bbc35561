"""The reduction from a profiler trace to busy and idle time, spans and the
breakdown: on hand-made intervals, and on a small trace recorded on one
TPU v5e chip (``data/offline_batch.xplane.pb``: two ``paper.offline-batch``
calls under the harness's spans)."""

import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "offline_batch.xplane.pb")


def test_union_merges_overlaps_once():
    assert trace._union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3],
                                                               [5, 9]]


def test_outermost_drops_nested_spans_per_thread():
    spans = [("a", 0, 10, 0), ("b", 2, 4, 0), ("a", 12, 15, 0),
             ("b", 3, 5, 1)]
    assert sorted(trace.outermost(spans)) == [("a", 0, 10, 0),
                                              ("a", 12, 15, 0),
                                              ("b", 3, 5, 1)]


def test_gaps_take_the_innermost_open_span():
    spans = sorted([("call", 0, 100, 0), ("place", 10, 40, 0),
                    ("settle", 50, 60, 0)], key=lambda s: s[1])
    assert trace._names_at(spans, [5, 20, 55, 70, 150]) == [
        "call", "place", "settle", "call", trace.IDLE_UNNAMED]


@pytest.fixture(scope="module")
def recorded():
    return trace.reduce(RECORDED, {"place_group_vector", "pin_fresh",
                                   "settle", "bench.schedule_call"})


def test_recorded_window_busy_and_programs(recorded):
    assert recorded["window_ns"] == 69856114.0
    assert recorded["busy_ns"] == {"/device:TPU:0": 60813.0}
    assert [n for n, _ in trace.top(recorded["ops"])] == [
        "jit_solve_with_deadline", "jit_solve_on_boundary"]
    assert sum(recorded["ops"].values()) >= 60813.0


def test_recorded_gaps_are_named_by_host_spans(recorded):
    gaps = dict(trace.top(recorded["gaps"]))
    assert set(gaps) == {"bench.schedule_call", "place_group_vector"}
    assert sum(gaps.values()) * 1e9 == pytest.approx(69856114.0 - 60813.0)


def test_metric_readers_on_the_recorded_trace(recorded):
    from bench.harness import load_metric

    run = dict(trace=recorded, tasks=6560, rows=6560,
               device_kind="TPU v5 lite", notes={})
    idle = load_metric("device_idle").read(run)
    assert idle == pytest.approx(100 * (1 - 60813.0 / 69856114.0))
    roof = load_metric("solve_roofline").read(run)
    assert roof == pytest.approx(100 * 6560 * 84 / 819e9 / 60813e-9)
    assert run["notes"]["solve_roofline_bound"] == "bytes"
    place_ns = ((66430584 - 65799374) + (74226653 - 66648904)
                + (101464370 - 100865180) + (109316009 - 101685970))
    assert load_metric("placement_ms_per_ktask").read(run) == \
        pytest.approx(place_ns * 1e-6 / 6.56)


def test_readers_find_nothing_in_an_empty_trace():
    from bench.harness import load_metric

    run = dict(trace={"window_ns": 1e9, "busy_ns": {}, "ops": {},
                      "spans": [], "gaps": {}},
               tasks=0, rows=0, device_kind="TPU v5 lite", notes={})
    for name in ("device_idle", "solve_roofline", "placement_ms_per_ktask"):
        assert load_metric(name).read(run) is None
