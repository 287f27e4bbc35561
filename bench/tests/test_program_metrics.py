"""The readers of the program's own spans and counters: self time on
hand-made spans, two threads, empty traces, a hand-made ring of per-call
counters, a program that keeps none, a traced run on the CPU whose
reduced trace carries the program's span names, and two calls of each
cell recorded on one TPU v5e chip (``data/*_obs.xplane.pb`` with the
calls' counters in ``data/*_obs.counters.json``)."""

import json
import os
import sys

import pytest

from bench import harness, trace
from bench.metrics import _program

SPAN_READERS = ("solve_host_ms_per_ktask", "solve_wait_ms_per_ktask",
                "engine_ms_per_ktask", "schedule_self_ms_per_ktask")
COUNTER_READERS = ("placement_batched_share", "solve_pad_share")
CALL = harness.SCHEDULE_SPAN

#: One offline call on thread 0 (ns), under the harness's own spans, which
#: the readers leave out: every program span nests in the call.
CALL_SPANS = [
    (CALL, -5, 105, 0),
    ("schedule.offline", 0, 100, 0),
    ("solve.keys", 10, 20, 0),
    ("solve.dispatch", 20, 40, 0),
    ("solve.wait", 25, 35, 0),
    ("place_group_vector", 44, 81, 0),        # a harness wrapper
    ("placement.group", 45, 80, 0),
    ("placement.prepare", 70, 78, 0),
    ("host.gc", 72, 75, 0),                   # stays with placement.prepare
    ("schedule.records", 82, 95, 0),
    ("solve.fill", 88, 90, 0),
    ("host.gc", 91, 93, 0),                   # stays with schedule.records
    ("schedule.account", 95, 100, 0),
    ("engine.finalize", 96, 99, 0),
]
SELF = {"schedule.offline": 10 + 5 + 2, "solve.keys": 10,
        "solve.dispatch": 10, "solve.wait": 10, "placement.group": 27,
        "placement.prepare": 8, "schedule.records": 11, "solve.fill": 2,
        "schedule.account": 2, "engine.finalize": 3}


def run_of(spans, tasks=1000):
    return dict(trace={"window_ns": 1e9, "busy_ns": {}, "ops": {},
                       "spans": spans, "gaps": {}},
                tasks=tasks, rows=tasks, device_kind="TPU v5 lite",
                notes={})


def read(name, run):
    return harness.load_metric(name).read(run)


def test_self_time_goes_to_the_innermost_program_span():
    got = _program.self_ns(CALL_SPANS)
    assert got == SELF
    assert sum(got.values()) == 100          # the call, exactly once


def test_span_readers_partition_the_call():
    run = run_of(CALL_SPANS)
    ms = {n: read(n, run) for n in SPAN_READERS}
    # 1,000 tasks: ns / 1e6 ms / 1 ktask
    assert ms == {"solve_host_ms_per_ktask": pytest.approx(22e-6),
                  "solve_wait_ms_per_ktask": pytest.approx(10e-6),
                  "engine_ms_per_ktask": pytest.approx(3e-6),
                  "schedule_self_ms_per_ktask": pytest.approx(30e-6)}
    placement_ns = SELF["placement.group"] + SELF["placement.prepare"]
    assert sum(ms.values()) + placement_ns * 1e-6 == pytest.approx(100e-6)


def test_span_readers_take_each_thread_on_its_own():
    other = [("solve.wait", 30, 60, 1),       # overlaps thread 0's spans
             ("host.gc", 70, 90, 2)]          # no span around it: no one's
    run = run_of(CALL_SPANS + other, tasks=2000)
    assert read("solve_wait_ms_per_ktask", run) == \
        pytest.approx((10 + 30) * 1e-6 / 2)
    assert read("schedule_self_ms_per_ktask", run) == \
        pytest.approx(30e-6 / 2)


def test_a_span_that_outlasts_its_parent_is_cut():
    spans = [("schedule.online", 0, 50, 0), ("engine.settle", 40, 70, 0)]
    assert _program.self_ns(spans) == {"schedule.online": 40,
                                       "engine.settle": 10}


@pytest.mark.parametrize("name", SPAN_READERS + COUNTER_READERS)
def test_readers_find_nothing_in_an_empty_trace(name):
    run = run_of([], tasks=0)
    assert read(name, run) is None


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_readers_find_nothing_without_program_spans(name):
    run = run_of([(CALL, 0, 100, 0), ("settle", 10, 20, 0)])
    assert read(name, run) is None


def ring(monkeypatch, rows):
    from repro.core import obs

    def recent(n):
        return rows[-n:] if n else []

    monkeypatch.setattr(obs, "recent", recent)


def calls(n):
    return [(CALL, 100 * i, 100 * i + 90, 0) for i in range(n)]


def test_counter_readers_sum_the_window_calls(monkeypatch):
    ring(monkeypatch, [
        {"placement.batched": 99, "placement.scalar": 0, "solve.sent": 8,
         "solve.pad": 8},                        # before the window
        {"placement.batched": 30, "placement.scalar": 10, "solve.sent": 64,
         "solve.pad": 16},
        {"placement.batched": 0, "placement.scalar": 60, "solve.sent": 1024,
         "solve.pad": 0}])
    run = run_of(calls(2))
    assert read("placement_batched_share", run) == pytest.approx(30.0)
    assert read("solve_pad_share", run) == pytest.approx(
        100 * 16 / (64 + 1024))


@pytest.mark.parametrize("name", COUNTER_READERS)
def test_counter_readers_need_the_whole_window(monkeypatch, name):
    ring(monkeypatch, [{"placement.batched": 1, "placement.scalar": 1,
                        "solve.sent": 8, "solve.pad": 1}])
    run = run_of(calls(3))
    assert read(name, run) is None
    assert "recorded 1 calls, the window made 3" in run["notes"][name]


@pytest.mark.parametrize("name", COUNTER_READERS)
def test_counter_readers_need_a_denominator(monkeypatch, name):
    ring(monkeypatch, [{"placement.batched": 0, "placement.scalar": 0,
                        "solve.sent": 0, "solve.pad": 0}])
    run = run_of(calls(1))
    assert read(name, run) is None
    assert run["notes"][name].startswith("no ")


@pytest.mark.parametrize("name", COUNTER_READERS)
def test_counter_readers_on_a_program_without_counters(monkeypatch, name):
    monkeypatch.setitem(sys.modules, "repro.core.obs", None)
    run = run_of(calls(2))
    assert read(name, run) is None
    assert run["notes"][name] == "the program keeps no per-call counters"


def test_every_program_span_is_read():
    from repro.core import obs

    assert set(_program.ALL) == obs.NAMES
    listed = set()
    for name in SPAN_READERS:
        for names in getattr(harness.load_metric(name), "SPANS", {}).values():
            listed |= set(names)
    assert obs.NAMES <= listed


def test_the_spans_key_wraps_nothing():
    (target,) = _program.SPANS
    mod, attr = target.split(":")
    obj = getattr(__import__(mod, fromlist=[attr]), attr)
    before = dict(vars(obj))
    harness.install_spans(_program.SPANS)()
    assert dict(vars(obj)) == before
    assert not any(hasattr(obj, n) for n in _program.ALL)


def test_program_spans_reach_the_reduced_trace(monkeypatch):
    """A traced run on the CPU: the trace reduction keeps the program's
    spans, and all six readers report."""
    from bench.tests.test_reference import PAPER, SMALL_OFFLINE, small_cell

    spec = harness.load_json(harness.SPEC_FILE)
    cell = small_cell(PAPER, {**SMALL_OFFLINE, "requests": 40})
    cell["per_layer"] = [m for m in spec["per_layer"]
                         if m["name"] in SPAN_READERS + COUNTER_READERS]
    reduced = []
    real = trace.reduce

    def keep(*a, **k):
        reduced.append(real(*a, **k))
        return reduced[-1]

    monkeypatch.setattr(trace, "reduce", keep)
    out = harness.run("small", 11, 2.0, True, require_tpu=False,
                      cell_data=cell)
    names = {sp[0] for sp in reduced[0]["spans"]}
    assert {"schedule.offline", "solve.dispatch", "solve.wait",
            "placement.pin", "placement.group", "engine.finalize"} <= names
    got = out["line"]["metrics"]
    assert set(got) == set(SPAN_READERS + COUNTER_READERS)
    assert all(v["value"] is not None for v in got.values())
    assert got["placement_batched_share"]["value"] == 0.0


#: Two calls of each cell recorded on one TPU v5e chip under the harness's
#: spans and the program's own, with the calls' counters.
RECORDED = {
    "offline_batch": dict(
        call="schedule.offline", calls_ns=118779608.0,
        ms={"solve_host_ms_per_ktask": 5.297662455707903,
            "solve_wait_ms_per_ktask": 2.4654213526421196,
            "engine_ms_per_ktask": 0.03787536589123402,
            "schedule_self_ms_per_ktask": 0.8841007548913881},
        share={"placement_batched_share": 0.0,
               "solve_pad_share": 100 * (939 + 916) / (2 * 4352)}),
    "online_day": dict(
        call="schedule.online", calls_ns=650725524.0,
        ms={"solve_host_ms_per_ktask": 4.92354119804401,
            "solve_wait_ms_per_ktask": 0.30654645476772613,
            "engine_ms_per_ktask": 3.347612224938875,
            "schedule_self_ms_per_ktask": 2.842714792176039},
        share={"placement_batched_share":
               100 * (3076 + 3090) / (4093 + 4087),
               "solve_pad_share": 100 * (27 + 38) / (2 * 4160)}),
}


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_readers_on_recorded_chip_traces(cell, monkeypatch):
    data = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
    want = RECORDED[cell]
    names = {m for g in harness.OWN_SPANS.values() for m in g} | {CALL}
    for name in SPAN_READERS + ("placement_ms_per_ktask",):
        for g in getattr(harness.load_metric(name), "SPANS", {}).values():
            names |= set(g)
    tr = trace.reduce(os.path.join(data, cell + "_obs.xplane.pb"), names)
    with open(os.path.join(data, cell + "_obs.counters.json")) as f:
        rec = json.load(f)
    ring(monkeypatch, rec["calls"])
    run = dict(trace=tr, tasks=rec["tasks"], rows=rec["rows"],
               device_kind="TPU v5 lite", notes={})
    # the program's spans partition its calls exactly
    calls = [s for s in tr["spans"] if s[0] == want["call"]]
    assert len(calls) == 2
    assert sum(e - s for _, s, e, _ in calls) == want["calls_ns"]
    assert sum(_program.self_ns(tr["spans"]).values()) == want["calls_ns"]
    for name, v in {**want["ms"], **want["share"]}.items():
        assert read(name, run) == pytest.approx(v, rel=1e-12), name
    # every idle gap inside a call is named by a span inside the call: the
    # program's own or a harness wrapper of one of its methods
    assert CALL not in tr["gaps"]
    assert set(tr["gaps"]) <= names - {CALL}
