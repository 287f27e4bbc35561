"""The PAI 2020 fleet (``configs/pai2020-t4-p100-v100.json``): its classes
are the program's registry classes, a small fleet day is judged
``correct`` and each planted fault is caught on it, and the reader of the
class-fallback counter."""

import dataclasses
import sys

import pytest

from bench import harness
from bench.tests.test_program_metrics import calls, ring, run_of
from bench.tests.test_reference import FAULTS, SMALL_ONLINE, small_cell

FLEET = harness.load_json(harness.os.path.join(
    harness.BENCH, "configs", "pai2020-t4-p100-v100.json"))
#: A diurnal fleet day at a size the CPU judges in seconds.
SMALL_DAY = {**SMALL_ONLINE, "pattern": "diurnal"}
METRIC = "placement_fallback_share"


def test_the_class_model_is_the_registry():
    from repro.core import machines

    assert FLEET["rho"] == 2
    assert [c["name"] for c in FLEET["class_model"]] == FLEET["classes"]
    for c in FLEET["class_model"]:
        mc = machines.REGISTRY[c["name"]]
        assert {k: v for k, v in c.items() if k != "interval"} == \
            {f.name: getattr(mc, f.name) for f in dataclasses.fields(mc)
             if f.name != "interval"}
        assert c["interval"] == dataclasses.asdict(mc.interval)
        assert int(c["delta_on"] // c["p_idle"]) == FLEET["rho"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fleet_day_is_judged(fault, monkeypatch):
    if FAULTS[fault] is not None:
        FAULTS[fault](monkeypatch)
    from repro.core import solver_cache

    solver_cache.GLOBAL_CACHE.clear()     # no rows solved by another case
    out = harness.run("small", 2**31 + 16, 0.3, False, require_tpu=False,
                      cell_data=small_cell(FLEET, SMALL_DAY))
    line = out["line"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["correct"] is (fault == "sound"), line["checks"]


def test_the_fallback_share_sums_the_window_calls(monkeypatch):
    ring(monkeypatch, [
        {"placement.batched": 9, "placement.scalar": 0,
         "placement.fallback": 9},                 # before the window
        {"placement.batched": 10, "placement.scalar": 30,
         "placement.fallback": 12},
        {"placement.batched": 0, "placement.scalar": 60,
         "placement.fallback": 3}])
    run = run_of(calls(2))
    assert harness.load_metric(METRIC).read(run) == \
        pytest.approx(100 * 15 / 100)


def test_the_fallback_share_needs_the_counter(monkeypatch):
    ring(monkeypatch, [{"placement.batched": 1, "placement.scalar": 1}])
    run = run_of(calls(1))
    assert harness.load_metric(METRIC).read(run) is None
    assert "placement.fallback" in run["notes"][METRIC]
    monkeypatch.setitem(sys.modules, "repro.core.obs", None)
    run = run_of(calls(1))
    assert harness.load_metric(METRIC).read(run) is None
