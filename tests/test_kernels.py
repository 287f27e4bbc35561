"""Per-kernel shape/dtype sweeps, assert_allclose vs the ref.py oracles
(interpret mode executes the kernel bodies on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tasks as tasklib
from repro.kernels import ops, ref

KEY = jax.random.key(42)


@pytest.mark.parametrize("B,H,KV,S,dh", [
    (1, 2, 2, 128, 64),
    (2, 4, 2, 256, 64),
    (1, 8, 8, 384, 128),
    (2, 4, 1, 256, 80),     # MQA + non-128 head_dim (pad path)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(B, H, KV, S, dh, dtype):
    ks = jax.random.split(jax.random.fold_in(KEY, hash((B, H, S, dh)) %
                                             2**31), 3)
    q = jax.random.normal(ks[0], (B, H, S, dh), dtype)
    k = jax.random.normal(ks[1], (B, KV, S, dh), dtype)
    v = jax.random.normal(ks[2], (B, KV, S, dh), dtype)
    out = ops.flash_attention(q, k, v, causal=True)
    exp = ref.attention_ref(q, k, v, causal=True)
    tol = 2e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol)


@pytest.mark.parametrize("window", [64, 128])
def test_flash_attention_sliding_window(window):
    B, H, KV, S, dh = 1, 4, 2, 256, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, KV, S, dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, KV, S, dh), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, window=window)
    exp = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-3)


def test_flash_attention_noncausal():
    B, H, KV, S, dh = 2, 2, 2, 128, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, KV, S, dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, KV, S, dh), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=False)
    exp = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-3)


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 128, 2, 64, 128, 64),
    (2, 256, 4, 64, 128, 128),
    (1, 256, 2, 128, 64, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan(B, S, H, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.fold_in(KEY, S + P), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(dtype)
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    b = (jax.random.normal(ks[3], (B, S, N)) * 0.3).astype(dtype)
    c = (jax.random.normal(ks[4], (B, S, N)) * 0.3).astype(dtype)
    y = ops.ssd_scan(x, dt, a, b, c, chunk=chunk)
    exp = ref.ssd_ref(x, dt, a, b, c)
    scale = float(jnp.max(jnp.abs(exp.astype(jnp.float32)))) + 1e-6
    tol = 2e-3 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(y, np.float32) / scale,
                               np.asarray(exp, np.float32) / scale,
                               atol=tol)


def test_ssd_matches_model_chunked_path():
    """Kernel vs the model's production jnp chunked implementation."""
    from repro.models.ssm import ssd_chunked
    B, S, H, P, N = 2, 256, 4, 64, 64
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    b = jax.random.normal(ks[3], (B, S, N)) * 0.3
    c = jax.random.normal(ks[4], (B, S, N)) * 0.3
    y_kernel = ops.ssd_scan(x, dt, a, b, c, chunk=128)
    y_model, _ = ssd_chunked(x, dt, a, b, c, chunk=128)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_model),
                               atol=5e-3)


def test_dvfs_kernel_full_library():
    lib = tasklib.generate_offline(0.08, seed=9)
    allowed = lib.deadline - lib.arrival
    sol = ops.dvfs_solve(lib.params, allowed)
    tasks_mat = np.stack(
        [np.asarray(f, np.float32) for f in lib.params.astuple()]
        + [np.asarray(allowed, np.float32),
           np.zeros(len(lib), np.float32)], axis=1)
    expect = ref.dvfs_solve_ref(tasks_mat)
    rel = np.abs(sol.energy - expect[:, 5]) / expect[:, 5]
    # hierarchical (G0, G1) refinement: ~1e-7 typical, vs ~1e-5 flat-128
    assert float(np.max(rel)) < 1e-5
    assert float(np.mean(sol.deadline_prior == (expect[:, 6] > .5))) > 0.99
    # feasible solutions respect the deadline
    ok = sol.feasible
    assert np.all(sol.time[ok] <= np.asarray(allowed)[ok] * (1 + 1e-4))


def test_dvfs_kernel_narrow_interval():
    """Kernel/oracle parity on the realistic NARROW (GTX-1080Ti) interval."""
    from repro.core import dvfs

    lib = tasklib.generate_offline(0.06, seed=21)
    allowed = lib.deadline - lib.arrival
    sol = ops.dvfs_solve(lib.params, allowed, interval=dvfs.NARROW)
    tasks_mat = np.stack(
        [np.asarray(f, np.float32) for f in lib.params.astuple()]
        + [np.asarray(allowed, np.float32),
           np.zeros(len(lib), np.float32)], axis=1)
    expect = ref.dvfs_solve_ref(tasks_mat, interval=dvfs.NARROW)
    rel = np.abs(sol.energy - expect[:, 5]) / expect[:, 5]
    assert float(np.max(rel)) < 1e-5
    assert float(np.mean(sol.deadline_prior == (expect[:, 6] > .5))) > 0.99
    # solutions stay inside the NARROW box
    assert np.all(sol.fm >= dvfs.NARROW.fm_min - 1e-5)
    assert np.all(sol.fm <= dvfs.NARROW.fm_max + 1e-5)
    assert np.all(sol.fc <= dvfs.NARROW.fc_max + 1e-4)


def test_dvfs_kernel_readjust_path():
    """The kernel's theta-readjustment sweep (column-7 flag) matches the
    scalar ``single_task.readjust`` decisions within grid tolerance."""
    from repro.core.dvfs import DvfsParams

    from repro.core import dvfs

    lib = tasklib.app_library()
    rows = [lib[i] for i in range(8)]
    params = DvfsParams.stack(rows)
    tstar = np.asarray(params.default_time())
    tmin = np.asarray(dvfs.min_time(params, dvfs.WIDE))
    # feasible windows strictly below the default execution time (and hence
    # below the optimal DVFS time): the theta-readjustment regime
    windows = tmin + (tstar - tmin) * np.linspace(0.15, 0.9, 8)
    sol = ops.dvfs_solve(params, windows, readjust=True)
    for i in range(8):
        v, fc, fm, t, p, e = ref.dvfs_solve_ref(
            np.asarray([[*np.asarray(params[i].astuple(), np.float32),
                         np.float32(windows[i]), 1.0]], np.float32))[0][:6]
        assert abs(sol.energy[i] - e) / e < 1e-2
        # both respect the shrunken window
        assert sol.time[i] <= windows[i] * (1 + 1e-4)
        assert t <= windows[i] * (1 + 1e-4)
    # and the batched production path agrees with the scalar readjust
    from repro.core import single_task
    vb, fcb, fmb, tb, pb, eb = single_task.readjust_batch(
        params, windows, use_kernel=True)
    for i in range(8):
        vs, fcs, fms, ts_, ps, es = single_task.readjust(
            params[i], float(windows[i]))
        assert abs(eb[i] - es) / es < 1e-2
        assert tb[i] == pytest.approx(min(float(windows[i]), ts_), rel=1e-4)


def test_dvfs_kernel_through_scheduler():
    """configure_tasks(use_kernel=True) plugs the Pallas solver into
    Algorithm 1 and must produce a near-identical schedule."""
    from repro.core import scheduling
    ts = tasklib.generate_offline(0.05, seed=13)
    r_ref = scheduling.schedule_offline(ts, l=2, algorithm="edl",
                                        use_kernel=False)
    r_ker = scheduling.schedule_offline(ts, l=2, algorithm="edl",
                                        use_kernel=True)
    assert r_ker.violations == 0
    assert r_ker.e_total == pytest.approx(r_ref.e_total, rel=2e-3)


# ---------------------------------------------------------------------------
# Differential fuzz: the hierarchical kernel vs the kernels/ref.py oracle on
# random widened [n, 16] matrices — random params, random windows, random
# readjust flags, and MIXED per-row interval boxes including a degenerate
# (single-point) box.  The seeded sweep always runs; the same checker runs
# under hypothesis when installed (CI installs it).
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _fuzz_boxes(rng):
    """A few random scaling boxes plus one degenerate single-point box
    (v_min == v_max, fm_min == fm_max, fc pinned at g1(v_max))."""
    from repro.core import dvfs

    boxes = [dvfs.WIDE.bounds(), dvfs.NARROW.bounds()]
    for _ in range(2):
        v_min = float(rng.uniform(0.5, 0.9))
        v_max = float(rng.uniform(v_min + 0.05, 1.24))
        fm_min = float(rng.uniform(0.5, 0.9))
        boxes.append((v_min, v_max, float(rng.uniform(0.5, 0.8)),
                      fm_min, float(rng.uniform(fm_min + 0.05, 1.2))))
    v = float(rng.uniform(0.7, 1.2))
    fc = dvfs.g1_float(v)
    boxes.append((v, v, fc, 1.0, 1.0))        # degenerate: one point
    return boxes


def check_kernel_matches_oracle_fuzz(seed: int, n: int = 64):
    from repro.core import dvfs
    from repro.core.dvfs import DvfsParams

    rng = np.random.default_rng(seed)
    p_star = rng.uniform(120, 260, n)
    gamma = p_star * rng.uniform(0.05, 0.25, n)
    p0 = p_star * rng.uniform(0.1, 0.5, n)
    params = DvfsParams(p0=p0, gamma=gamma, c=p_star - gamma - p0,
                        big_d=rng.uniform(1.0, 50.0, n),
                        delta=rng.uniform(0.0, 1.0, n),
                        t0=rng.uniform(0.05, 5.0, n))
    boxes = _fuzz_boxes(rng)
    bounds = np.asarray([boxes[i] for i in rng.integers(0, len(boxes), n)],
                        np.float32)
    tstar = np.asarray(params.default_time())
    tmin = np.asarray([float(dvfs.min_time(params[i],
                                           dvfs.ScalingInterval(*bounds[i])))
                       for i in range(n)])
    readj = (rng.random(n) < 0.3).astype(np.float32)
    # windows span infeasible (below t_min) through slack (2 t*); readjust
    # rows stay >= t_min (the boundary solve's contract: a bookable window)
    lo = np.where(readj > 0.5, tmin, 0.5 * tmin)
    allowed = lo + (2.0 * tstar - lo) * rng.random(n)
    mat = np.stack([np.asarray(f, np.float32) for f in params.astuple()]
                   + [allowed.astype(np.float32), readj], axis=1)
    mat = np.concatenate([mat, bounds, np.zeros((n, 3), np.float32)], axis=1)
    assert mat.shape == (n, 16)

    got = ops.dvfs_solve_matrix(mat)
    expect = ref.dvfs_solve_ref(mat)

    e_got, e_exp = got[:, 5], expect[:, 5]
    rel = np.abs(e_got - e_exp) / np.maximum(e_exp, 1e-9)
    assert float(np.median(rel)) < 2e-3
    assert float(np.mean(rel)) < 1e-2
    assert float(np.mean((got[:, 6] > .5) == (expect[:, 6] > .5))) >= 0.9
    assert np.all(np.isfinite(got))
    # solutions stay inside their per-row box
    fc_top = np.asarray(dvfs.g1(mat[:, 9]))                # g1(v_max)
    empty = mat[:, 10] > fc_top + 1e-4       # box holds no core frequency
    box = ~empty
    assert np.all(got[:, 0] >= mat[:, 8] - 1e-4)           # v >= v_min
    assert np.all(got[box, 0] <= mat[box, 9] + 1e-4)       # v <= v_max
    assert np.all(got[:, 2] >= mat[:, 11] - 1e-4)          # fm in its box
    assert np.all(got[:, 2] <= mat[:, 12] + 1e-4)
    assert np.all(got[box, 1] >= mat[box, 10] - 1e-4)      # fc >= fc_min
    # a box with fc_min > g1(v_max) holds no core frequency: the solve
    # stays on the V-fc curve between g1(v_max) and fc_min
    fc_e = got[empty, 1]
    assert np.all(fc_e >= fc_top[empty] - 1e-4)
    assert np.all(fc_e <= mat[empty, 10] + 1e-4)
    assert np.allclose(got[empty, 0],
                       np.maximum(mat[empty, 8], dvfs.g1_inv(fc_e)),
                       atol=1e-4)
    # feasible deadline-prior rows respect their window (both sides)
    for out in (got, expect):
        ok = (out[:, 7] > .5) & (out[:, 6] > .5)
        assert np.all(out[ok, 3] <= allowed[ok] * (1 + 1e-3))
    return int(empty.sum())


@pytest.mark.parametrize("seed", range(5))
def test_dvfs_kernel_fuzz_vs_oracle(seed):
    check_kernel_matches_oracle_fuzz(seed)


def test_dvfs_kernel_fuzz_empty_box():
    """A draw whose random box has fc_min > g1(v_max): kernel and oracle
    both stay finite and on the V-fc curve."""
    assert check_kernel_matches_oracle_fuzz(1995334097, n=32) > 0


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1))
    def test_dvfs_kernel_fuzz_vs_oracle_hypothesis(seed):
        check_kernel_matches_oracle_fuzz(seed, n=32)
