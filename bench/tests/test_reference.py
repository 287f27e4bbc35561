"""The reference that decides ``correct``: its solver against a dense
search, the bfloat16 control failing it, and whole runs driven on the CPU
with the timed path broken underneath, each of which must read
``correct: false``."""

import dataclasses

import numpy as np
import pytest

from bench import harness, reference, traffic
from bench.control import control_solver

PAPER = harness.load_json(harness.os.path.join(harness.BENCH, "configs",
                                               "paper-1080ti.json"))
#: A two-class deployment for the reference's class paths: the program's
#: registry classes ``gtx-1080ti`` and ``tpu-v5e`` with the constants the
#: registry gives them.  A test fixture, not a benchmarked deployment.
TWO_CLASS = {**PAPER, "name": "two-class",
             "classes": ["gtx-1080ti", "tpu-v5e"],
             "class_model": PAPER["class_model"] + [
                 {"name": "tpu-v5e", "speed": 1.35,
                  "power_scale": 200.0 / 190.0, "p0_frac": 0.3,
                  "gamma_frac": 0.15,
                  "interval": {"v_min": 0.7, "v_max": 1.1, "fc_min": 0.6,
                               "fm_min": 0.6, "fm_max": 1.05},
                  "p_idle": 37.0, "delta_on": 90.0}]}
SMALL_OFFLINE = {"generator": "offline", "entry": "offline", "util": 0.25,
                 "requests": 400, "checked": 2, "warmup_max": 3}
SMALL_ONLINE = {"generator": "trace", "entry": "online", "n_tasks": 1500,
                "pattern": "uniform", "horizon": 120, "requests": 100,
                "checked": 2, "warmup_max": 3}


def test_solve_matches_a_dense_search():
    rng = np.random.default_rng(0)
    d = traffic.draw({"generator": "trace", "n_tasks": 12,
                      "pattern": "uniform", "horizon": 1}, 3, 0, 0)
    n = d["arrival"].shape[0]
    p = {f: d[f] for f in reference.FIELDS}
    t_star = d["big_d"] + d["t0"]
    window = t_star * rng.uniform(0.8, 1.6, n)
    for box in (PAPER["interval"], TWO_CLASS["class_model"][1]["interval"]):
        v, fc, fm, t, pw, e, ok = reference.solve(p, window, box)
        fcs = np.linspace(box["fc_min"], reference.g1(box["v_max"]), 1201)
        fms = np.linspace(box["fm_min"], box["fm_max"], 1201)
        FC, FM = np.meshgrid(fcs, fms, indexing="ij")
        V = np.maximum(box["v_min"], reference.g1_inv(FC))
        for i in range(n):
            pi = {f: p[f][i] for f in reference.FIELDS}
            tt = reference.exec_time(pi, FC, FM)
            ee = np.where(tt <= window[i], reference.power(pi, V, FC, FM) * tt,
                          np.inf)
            brute = ee.min()
            if not np.isfinite(brute):
                assert not ok[i]
                continue
            assert ok[i] and t[i] <= window[i] * (1 + 1e-12)
            assert e[i] <= brute * (1 + 1e-12)
            assert e[i] >= brute * (1 - 1e-2)    # the grid misses the boundary


def small_cell(deploy, mix):
    return dict(workload={"name": "small", "config": deploy["name"],
                          "traffic": "small", "chips": 1},
                deploy=deploy, mix=mix,
                end_to_end=[{"name": "tasks_per_s", "unit": "tasks/s"},
                            {"name": "setup_s", "unit": "s"}],
                per_layer=[])


def break_state(monkeypatch):
    """Pairs keep their state: the engine forgets every assignment."""
    from repro.core.engine import ClusterEngine

    for name in ("assign", "sync_mu", "book_assignments"):
        monkeypatch.setattr(ClusterEngine, name, lambda *a, **k: None)


def break_half(monkeypatch):
    """Half of each batch is left out."""
    call = harness.Program.__call__

    def half(self, ts):
        return call(self, ts.subset(np.arange(len(ts) // 2)))

    monkeypatch.setattr(harness.Program, "__call__", half)


def break_answer(monkeypatch):
    """The solver's core frequency is altered where it is produced."""
    from repro.core import single_task

    for name in ("solve_with_deadline", "solve_on_boundary"):
        fn = getattr(single_task, name)

        def altered(*a, _fn=fn, **k):
            sol = _fn(*a, **k)
            return sol._replace(fc=sol.fc * 0.995)

        monkeypatch.setattr(single_task, name, altered)


def max_speed(monkeypatch):
    """The solver returns the fastest setting: valid, never late, and
    off the energy optimum."""
    import jax.numpy as jnp

    from repro.core import dvfs, single_task

    for name in ("solve_with_deadline", "solve_on_boundary"):
        fn = getattr(single_task, name)

        def fastest(params, allowed, interval=dvfs.WIDE, _fn=fn):
            sol = _fn(params, allowed, interval)
            p = dvfs.DvfsParams(*(jnp.asarray(f, jnp.float32)
                                  for f in params.astuple()))
            v = jnp.full_like(sol.v, interval.v_max)
            fc = jnp.full_like(sol.fc, interval.fc_max)
            fm = jnp.full_like(sol.fm, interval.fm_max)
            t = dvfs.exec_time(p, fc, fm)
            pw = dvfs.power(p, v, fc, fm)
            return sol._replace(v=v, fc=fc, fm=fm, time=t, power=pw,
                                energy=pw * t)

        monkeypatch.setattr(single_task, name, fastest)


def no_reuse(monkeypatch):
    """Every task on a fresh pair: valid, and not EDL's placement."""
    from repro.core.engine import ClusterEngine
    from repro.core.placement import PlacementContext

    def fresh_each(self, idx, order, t_now, prep=None):
        if order is None:
            order = np.argsort(self.deadline[idx], kind="stable")
        self.place_group_scalar(idx, order, t_now, "wf")

    monkeypatch.setattr(ClusterEngine, "worst_fit",
                        lambda self, class_id=None: -1)
    monkeypatch.setattr(PlacementContext, "place_group_vector", fresh_each)


def no_readjust(monkeypatch):
    """Theta-readjustment skipped: a task that does not fit at its optimal
    length goes to a fresh pair."""
    from repro.core.placement import PlacementContext

    init = PlacementContext.__init__

    def without(self, *a, **k):
        init(self, *a, **{**k, "readjust": False})

    monkeypatch.setattr(PlacementContext, "__init__", without)


FAULTS = {"sound": None, "state_unchanged": break_state,
          "half_left_out": break_half, "answer_altered": break_answer,
          "max_speed": max_speed, "no_reuse": no_reuse,
          "no_readjust": no_readjust}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("deploy,mix", [(PAPER, SMALL_OFFLINE),
                                        (PAPER, SMALL_ONLINE)],
                         ids=["offline", "online"])
def test_a_broken_run_is_not_correct(fault, deploy, mix, monkeypatch):
    if FAULTS[fault] is not None:
        FAULTS[fault](monkeypatch)
    from repro.core import solver_cache

    solver_cache.GLOBAL_CACHE.clear()     # no rows solved by another case
    out = harness.run("small", 2**31 + 11, 0.3, False, require_tpu=False,
                      cell_data=small_cell(deploy, mix))
    line = out["line"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert line["correct"] is (fault == "sound"), line["checks"]


@pytest.mark.parametrize("fault", ["sound", "no_reuse"])
def test_two_classes(fault, monkeypatch):
    """The reference's class order and per-class boxes, on the program's
    two registry classes."""
    if FAULTS[fault] is not None:
        FAULTS[fault](monkeypatch)
    from repro.core import solver_cache

    solver_cache.GLOBAL_CACHE.clear()
    out = harness.run("small", 2**31 + 12, 0.3, False, require_tpu=False,
                      cell_data=small_cell(TWO_CLASS, SMALL_ONLINE))
    assert out["line"]["correct"] is (fault == "sound"), out["line"]["checks"]


def test_the_bf16_control_fails(monkeypatch):
    from repro.core import single_task, solver_cache

    program = harness.Program(PAPER, SMALL_OFFLINE)
    d = traffic.draw(SMALL_OFFLINE, 5, 0, 0)

    def numbers():
        r = program(program.task_set(d))
        return reference.check(d, harness.records(r),
                               {"e_total": r.e_total,
                                "violations": r.violations}, PAPER, False,
                               np.random.default_rng(0))

    solver_cache.GLOBAL_CACHE.clear()
    sound = numbers()
    assert reference.passed(sound), sound
    monkeypatch.setattr(single_task, "solve_with_deadline",
                        control_solver(False))
    monkeypatch.setattr(single_task, "solve_on_boundary",
                        control_solver(True))
    solver_cache.GLOBAL_CACHE.clear()
    ctrl = numbers()
    assert not reference.passed(ctrl)
    assert ctrl["solve_gap"] > 3 * reference.LIMITS["solve_gap"]


def test_reservoir_keeps_k_and_the_largest():
    r = harness.Reservoir(2, np.random.default_rng(0))
    for i in range(50):
        r.offer(i, 100 if i == 17 else i % 7)
    s = r.sample()
    assert 17 in s and len(s) in (2, 3) and len(set(s)) == len(s)
