"""Heterogeneous machine classes: degenerate-case goldens, kernel parity
for the class-extended task matrix, and class-aware scheduling behavior."""

import numpy as np
import pytest

from repro.core import dvfs, machines, online, scheduling, tasks
from repro.core.engine import ClusterEngine
from repro.core.machines import MachineClass
from repro.kernels import ops, ref

from tests.test_engine import OFFLINE_GOLDEN, ONLINE_GOLDEN


@pytest.fixture(scope="module")
def library():
    return tasks.app_library()


# ---------------------------------------------------------------------------
# Degenerate case: one reference class == the homogeneous code path.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("alg", sorted(OFFLINE_GOLDEN))
def test_single_class_offline_matches_goldens(alg, library):
    """A one-reference-class heterogeneous run reproduces the seed goldens
    (1e-9 rel on e_total) and is bit-for-bit the homogeneous path."""
    ts = tasks.generate_offline(0.1, seed=3, library=library)
    r_homo = scheduling.schedule_offline(ts, l=2, theta=0.9, algorithm=alg)
    r_het = scheduling.schedule_offline(ts, l=2, theta=0.9, algorithm=alg,
                                        classes=("gtx-1080ti",))
    assert r_het.e_total == r_homo.e_total          # bit-for-bit
    assert r_het.e_idle == r_homo.e_idle
    assert r_het.n_pairs == r_homo.n_pairs
    assert r_het.n_servers == r_homo.n_servers
    assert r_het.violations == r_homo.violations
    e_total, e_idle, n_pairs, n_servers, violations = OFFLINE_GOLDEN[alg]
    assert r_het.e_total == pytest.approx(e_total, rel=1e-9)
    assert r_het.e_idle == pytest.approx(e_idle, rel=1e-6)
    assert (r_het.n_pairs, r_het.n_servers, r_het.violations) == \
        (n_pairs, n_servers, violations)


@pytest.mark.parametrize("alg,l,theta", sorted(ONLINE_GOLDEN))
def test_single_class_online_matches_goldens(alg, l, theta, library):
    ts = tasks.generate_online(offline_util=0.02, online_util=0.05, seed=1,
                               horizon=200, library=library)
    r_homo = online.schedule_online(ts, l=l, theta=theta, algorithm=alg)
    r_het = online.schedule_online(ts, l=l, theta=theta, algorithm=alg,
                                   classes=("gtx-1080ti",))
    assert r_het.e_total == r_homo.e_total          # bit-for-bit
    assert r_het.e_overhead == r_homo.e_overhead
    assert r_het.n_pairs == r_homo.n_pairs
    e_total, e_overhead, n_pairs, n_servers, violations = \
        ONLINE_GOLDEN[(alg, l, theta)]
    assert r_het.e_total == pytest.approx(e_total, rel=1e-9)
    assert r_het.e_overhead == pytest.approx(e_overhead, rel=1e-6)
    assert (r_het.n_pairs, r_het.n_servers, r_het.violations) == \
        (n_pairs, n_servers, violations)


# ---------------------------------------------------------------------------
# Class-extended kernel task matrix vs the oracle.
# ---------------------------------------------------------------------------


def _class_matrix(ts, mcs, interval=dvfs.WIDE, readjust=False):
    """Build the stacked [C*n, 16] matrix the widened kernel consumes."""
    n = len(ts)
    allowed = np.asarray(ts.deadline - ts.arrival, np.float32)
    blocks = []
    for mc in mcs:
        a = mc.adapt(ts.params)
        iv = mc.effective_interval(interval)
        cols = [np.asarray(f, np.float32) for f in a.astuple()]
        flag = np.full(n, 1.0 if readjust else 0.0, np.float32)
        m = np.stack(cols + [allowed, flag], axis=1)
        b = np.broadcast_to(np.asarray(iv.bounds(), np.float32), (n, 5))
        blocks.append(np.concatenate([m, b, np.zeros((n, 3), np.float32)],
                                     axis=1))
    return np.concatenate(blocks, axis=0)


def test_kernel_oracle_parity_class_matrix(library):
    """One widened pallas_call over a class-stacked matrix (three different
    scaling boxes) matches the per-interval production solver."""
    from repro.kernels.dvfs_opt import dvfs_solve_kernel
    import jax.numpy as jnp

    ts = tasks.generate_offline(0.05, seed=17, library=library)
    mcs = machines.get_classes(("gtx-1080ti", "tpu-v5e", "v100-sxm2"))
    mat = _class_matrix(ts, mcs)
    out = np.asarray(dvfs_solve_kernel(jnp.asarray(mat), interpret=True))
    exp = ref.dvfs_solve_ref(mat)
    rel = np.abs(out[:, 5] - exp[:, 5]) / np.maximum(exp[:, 5], 1e-9)
    assert float(np.max(rel)) < 1e-2
    assert float(np.mean((out[:, 6] > .5) == (exp[:, 6] > .5))) > 0.97
    # solutions stay inside each class's own box
    n = len(ts)
    for c, mc in enumerate(mcs):
        iv = mc.effective_interval(dvfs.WIDE)
        sl = slice(c * n, (c + 1) * n)
        assert np.all(out[sl, 2] >= iv.fm_min - 1e-5)
        assert np.all(out[sl, 2] <= iv.fm_max + 1e-5)
        assert np.all(out[sl, 1] <= iv.fc_max + 1e-4)


def test_legacy_8col_matrix_still_supported(library):
    """The homogeneous [n, 8] layout is widened from the static interval."""
    ts = tasks.generate_offline(0.05, seed=9, library=library)
    allowed = ts.deadline - ts.arrival
    sol8 = ops.dvfs_solve(ts.params, allowed, interval=dvfs.NARROW)
    rows = np.broadcast_to(np.asarray(dvfs.NARROW.bounds(), np.float64),
                           (len(ts), 5))
    sol16 = ops.dvfs_solve(ts.params, allowed, interval_rows=rows)
    np.testing.assert_allclose(sol8.energy, sol16.energy, rtol=1e-6)


def test_configure_classes_kernel_matches_jnp(library):
    ts = tasks.generate_offline(0.05, seed=23, library=library)
    mcs = machines.get_classes(("gtx-1080ti", "tpu-v5e"))
    allowed = ts.deadline - ts.arrival
    cfg_j = machines.configure_classes(ts.params, allowed, mcs, dvfs.WIDE)
    cfg_k = machines.configure_classes(ts.params, allowed, mcs, dvfs.WIDE,
                                       use_kernel=True)
    for j, k in zip(cfg_j, cfg_k):
        ok = np.asarray(j.feasible) & np.asarray(k.feasible)
        rel = np.abs(k.e_hat[ok] - j.e_hat[ok]) / np.maximum(j.e_hat[ok], 1e-9)
        assert float(np.max(rel)) < 1e-2
        assert float(np.mean(j.deadline_prior == k.deadline_prior)) > 0.97


# ---------------------------------------------------------------------------
# Class-aware scheduling behavior.
# ---------------------------------------------------------------------------


def test_scheduler_prefers_min_energy_class(library):
    """An identical-but-half-power class should host every task."""
    cheap = MachineClass("half-power", power_scale=0.5)
    ts = tasks.generate_offline(0.05, seed=3, library=library)
    r = scheduling.schedule_offline(ts, l=2, theta=0.9, algorithm="edl",
                                    classes=(machines.GTX_1080TI, cheap))
    assert r.violations == 0
    assert all(a.class_id == 1 for a in r.assignments)
    r_ref = scheduling.schedule_offline(ts, l=2, theta=0.9, algorithm="edl")
    assert r.e_total < r_ref.e_total


def test_heterogeneous_online_decomposition_and_overheads(library):
    """Per-class Δ accounting: total overhead is a nonneg combination of the
    class Δs, and the energy identity holds."""
    ts = tasks.generate_online(0.02, 0.05, seed=5, horizon=200,
                               library=library)
    mcs = machines.get_classes(("gtx-1080ti", "v100-sxm2"))
    r = online.schedule_online(ts, l=2, theta=0.9, algorithm="edl",
                               classes=mcs)
    assert r.violations == 0
    assert r.e_total == pytest.approx(r.e_run + r.e_idle + r.e_overhead)
    assert r.e_run == pytest.approx(sum(a.energy for a in r.assignments))
    # overhead decomposes into integer pair turn-ons per class Δ
    d0, d1 = mcs[0].delta_on, mcs[1].delta_on
    found = any(
        abs(r.e_overhead - (d0 * i + d1 * round((r.e_overhead - d0 * i) / d1)))
        < 1e-6 and round((r.e_overhead - d0 * i) / d1) >= 0
        for i in range(2000))
    assert found, r.e_overhead


def test_all_algorithms_run_heterogeneous(library):
    ts = tasks.generate_offline(0.04, seed=2, library=library)
    for alg in ("edl", "edf-wf", "edf-bf", "lpt-ff"):
        r = scheduling.schedule_offline(ts, l=2, theta=0.9, algorithm=alg,
                                        classes=("gtx-1080ti", "tpu-v5e"))
        assert r.violations == 0, alg
        assert len(r.assignments) == len(ts)
    ts2 = tasks.generate_online(0.02, 0.04, seed=3, horizon=120,
                                library=library)
    for alg in ("edl", "bin"):
        r = online.schedule_online(ts2, l=2, theta=0.9, algorithm=alg,
                                   classes=("gtx-1080ti", "tpu-v5e"))
        assert r.violations == 0, alg
        assert len(r.assignments) == len(ts2)


def test_adapt_identity_and_transforms(library):
    ref_cls = machines.GTX_1080TI
    assert ref_cls.is_reference
    a = ref_cls.adapt(library)
    for x, y in zip(a.astuple(), library.astuple()):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    fast = MachineClass("fast", speed=2.0)
    f = fast.adapt(library)
    np.testing.assert_allclose(np.asarray(f.big_d),
                               np.asarray(library.big_d) / 2.0)
    np.testing.assert_allclose(np.asarray(f.default_time()),
                               np.asarray(library.default_time()) / 2.0)
    tpu = machines.TPU_V5E
    t = tpu.adapt(library)
    p_star = np.asarray(library.default_power())  # f32 jnp eval
    np.testing.assert_allclose(np.asarray(t.p0),
                               p_star * tpu.power_scale * tpu.p0_frac,
                               rtol=1e-5)
    # power split sums back to the scaled envelope (f32 jnp eval)
    np.testing.assert_allclose(np.asarray(t.default_power()),
                               p_star * tpu.power_scale, rtol=1e-5)


def test_engine_class_selectors_and_acquire():
    mcs = machines.get_classes(("gtx-1080ti", "tpu-v5e"))
    eng = ClusterEngine(l=2, classes=mcs)
    eng.new_server(0.0, class_id=0)
    eng.new_server(0.0, class_id=1)
    assert eng.worst_fit(class_id=1) == 2     # first pair of the class-1 server
    eng.assign(2, 0.0, 5.0)
    assert eng.worst_fit(class_id=1) == 3
    assert eng.worst_fit(class_id=0) == 0
    # DRS powers both off; acquire wakes the server of the requested class
    eng.drs_sweep(10.0)
    assert eng.n_on_servers() == 0
    pid = eng.acquire_pair(10.0, class_id=1)
    assert pid == 2 and eng.n_servers == 2
    np.testing.assert_array_equal(eng.pair_class, [0, 0, 1, 1])


def test_engine_offline_finalize_groups_per_class():
    """Virtual servers never mix classes: idle energy is the per-class sum."""
    from repro.core import cluster as cl
    mcs = (MachineClass("a", p_idle=10.0), MachineClass("b", p_idle=100.0))
    eng = ClusterEngine(l=2, servers=False, classes=mcs)
    for mu, cid in ((5.0, 0), (3.0, 0), (8.0, 1)):
        pid = eng.open_pair(class_id=cid)
        eng.assign(pid, 0.0, mu)
    e_idle, e_over, n_srv = eng.finalize()
    exp_a, n_a = cl.offline_idle_energy(np.asarray([5.0, 3.0]), 2,
                                        p_idle=10.0)
    exp_b, n_b = cl.offline_idle_energy(np.asarray([8.0]), 2, p_idle=100.0)
    assert e_idle == pytest.approx(exp_a + exp_b)
    assert n_srv == n_a + n_b
    assert e_over == 0.0


def test_registry_lookup_and_errors():
    assert machines.get_classes(("gtx-1080ti",))[0] is machines.GTX_1080TI
    with pytest.raises(KeyError):
        machines.get_classes(("no-such-class",))
    with pytest.raises(ValueError):
        machines.get_classes(())
    with pytest.raises(ValueError):
        scheduling.schedule_offline(
            tasks.generate_offline(0.01, seed=0), algorithm="edl",
            cfg=scheduling.default_config(tasks.generate_offline(0.01, seed=0)),
            classes=("gtx-1080ti", "tpu-v5e"))


# ---------------------------------------------------------------------------
# Datacenter GPUs by the spec-sheet rule, and their fixed memory clock.
# ---------------------------------------------------------------------------

PAI_FLEET = ("tesla-t4", "tesla-p100", "v100-sxm2")


@pytest.mark.parametrize("name,tflops,watts", [("tesla-t4", 8.1, 70.0),
                                               ("tesla-p100", 9.3, 250.0),
                                               ("v100-sxm2", 15.7, 300.0)])
def test_spec_sheet_classes_follow_the_rule(name, tflops, watts):
    mc = machines.REGISTRY[name]
    assert mc.speed == tflops / 11.34
    assert mc.power_scale == watts / 250.0
    assert mc.p0_frac is None and mc.gamma_frac is None
    assert mc.p_idle == 24.0 + 13.0 * watts / 250.0
    assert mc.delta_on == pytest.approx(mc.p_idle * 90.0 / 37.0, rel=1e-15)
    assert int(mc.delta_on // mc.p_idle) == 2      # the run's rho
    assert mc.interval == machines.FIXED_MEMORY_CLOCK
    assert mc.interval.fm_min == mc.interval.fm_max == 1.0
    assert (mc.interval.v_min, mc.interval.v_max, mc.interval.fc_min) == \
        (dvfs.NARROW.v_min, dvfs.NARROW.v_max, dvfs.NARROW.fc_min)


def test_fixed_memory_clock_box_jnp_and_kernel(library):
    """On a box with ``fm_min == fm_max`` every row sits at that clock
    exactly, on the jnp solver (closed form on the deadline boundary) and
    on the Pallas kernel (its fm sweep collapses), with no NaN and the
    kernel within the oracle tolerance of the NARROW-interval test."""
    import jax.numpy as jnp

    from repro.core import single_task
    from repro.kernels.dvfs_opt import dvfs_solve_kernel

    box = machines.FIXED_MEMORY_CLOCK
    ts = tasks.generate_offline(0.06, seed=29, library=library)
    mcs = machines.get_classes(PAI_FLEET)
    allowed = np.asarray(ts.deadline - ts.arrival, np.float64)
    for mc in mcs:
        p = mc.adapt(ts.params)
        for solver in (single_task.solve_with_deadline,
                       single_task.solve_on_boundary):
            sol = solver(p, allowed, box)
            assert np.all(np.asarray(sol.fm) == 1.0)
            assert all(np.all(np.isfinite(np.asarray(f))) for f in sol[:6])
        # the search the closed form skips would land on the same bits
        p32 = dvfs.DvfsParams(*(jnp.asarray(f, jnp.float32)
                                for f in p.astuple()))
        one = jnp.ones_like(p32.big_d)
        fm = single_task._grid_then_golden(
            lambda f: single_task._deadline_energy_of_fm(
                p32, f, jnp.asarray(allowed, jnp.float32), box)[0], one, one)
        assert np.all(np.asarray(fm) == 1.0)
    for readjust in (False, True):
        mat = _class_matrix(ts, mcs, readjust=readjust)
        out = np.asarray(dvfs_solve_kernel(jnp.asarray(mat), interpret=True))
        exp = ref.dvfs_solve_ref(mat)
        assert np.all(np.isfinite(out)) and np.all(np.isfinite(exp))
        assert np.all(out[:, 2] == 1.0) and np.all(exp[:, 2] == 1.0)
        ok = (out[:, 7] > .5) & (exp[:, 7] > .5)
        rel = np.abs(out[ok, 5] - exp[ok, 5]) / exp[ok, 5]
        assert float(np.max(rel)) < 1e-5


@pytest.mark.parametrize("pipeline", [True, False])
def test_pai_fleet_vector_matches_scalar(pipeline, library):
    """Vector and scalar placement give the same records on the three
    fixed-memory-clock classes, with class fallback on the path."""
    from repro.core import solver_cache

    ts = tasks.generate_trace(2000, pattern="diurnal", horizon=120, seed=6,
                              library=library)
    runs = {}
    for mode in ("vector", "scalar"):
        solver_cache.GLOBAL_CACHE.clear()
        runs[mode] = online.schedule_online(
            ts, l=8, theta=0.9, algorithm="edl", interval=dvfs.NARROW,
            classes=PAI_FLEET, placement=mode, pipeline=pipeline,
            bound=False)
    v, s = runs["vector"], runs["scalar"]
    assert v.e_total == s.e_total and v.violations == s.violations
    assert v.assignments == s.assignments
    assert v.counters["placement.fallback"] == \
        s.counters["placement.fallback"] > 0
    assert {a.fm for a in v.assignments} == {1.0}
    assert {a.class_id for a in v.assignments} <= {0, 1, 2}
