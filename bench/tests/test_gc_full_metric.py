"""The reader of full garbage collections per 100 scheduler calls,
``gc_full_per_100_calls``, on a hand-made ring of per-call counters: the
window's calls summed, a program without the ``gc.full`` counter, a ring
shorter than the window, and a program that keeps no counters."""

import sys

import pytest

from bench.tests.test_program_metrics import calls, read, ring, run_of

GC_FULL = "gc_full_per_100_calls"


def test_full_collections_per_100_window_calls(monkeypatch):
    ring(monkeypatch, [
        {"gc.collections": 40, "gc.full": 5},     # before the window
        {"gc.collections": 12, "gc.full": 1},
        {"gc.collections": 9, "gc.full": 0},
        {"gc.collections": 30, "gc.full": 2},
        {"gc.collections": 7, "gc.full": 0}])
    run = run_of(calls(4))
    assert read(GC_FULL, run) == pytest.approx(100 * 3 / 4)
    ring(monkeypatch, [{"gc.collections": 3, "gc.full": 0}])
    assert read(GC_FULL, run_of(calls(1))) == 0.0


def test_full_collections_need_the_counter(monkeypatch):
    ring(monkeypatch, [{"gc.collections": 3}])     # a program without it
    run = run_of(calls(1))
    assert read(GC_FULL, run) is None
    assert run["notes"][GC_FULL] == "the program counts no gc.full"
    ring(monkeypatch, [{"gc.collections": 3, "gc.full": 0}])
    run = run_of(calls(2))
    assert read(GC_FULL, run) is None
    assert "recorded 1 calls, the window made 2" in run["notes"][GC_FULL]
    assert read(GC_FULL, run_of([], tasks=0)) is None
    monkeypatch.setitem(sys.modules, "repro.core.obs", None)
    run = run_of(calls(2))
    assert read(GC_FULL, run) is None
    assert run["notes"][GC_FULL] == "the program keeps no per-call counters"
