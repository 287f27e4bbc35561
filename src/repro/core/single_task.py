"""Single-task DVFS optimization (paper S4.1, Algorithm 1).

Two sub-problems, both reduced to a 1-D minimization:

* **Unconstrained** ``argmin E(V, fc, fm)``: the paper's Theorem 1 shows
  ``dE/dV > 0`` everywhere, so the optimum has the *minimum voltage that
  sustains the chosen core frequency*, ``V = max(v_min, g1^{-1}(fc))``; and for
  fixed ``(V, fc)`` the optimal memory frequency has the closed form
  :func:`repro.core.dvfs.optimal_fm`.  That leaves a single decision variable
  ``fc in [fc_min, g1(v_max)]`` which we minimize with a coarse grid followed
  by golden-section refinement (the energy curve is unimodal on the analytic
  interval where P is strictly convex; the grid stage guards against the
  clamped-fm kinks).

* **Deadline-constrained** (deadline-prior tasks, ``t_hat > d - a``): the
  optimum sits on the time boundary ``t(fc, fm) = allowed``.  Parametrizing by
  ``fm``, the required core frequency is
  ``fc_req(fm) = D delta / (allowed - t0 - D (1 - delta) / fm)`` and
  ``V = max(v_min, g1^{-1}(fc))``; again a 1-D search over ``fm``, or, on
  a box with a single memory clock (``fm_min == fm_max``), that clock.

Everything is vectorized over a batch of tasks and jit-compatible; it is both
the production solver and the oracle for the ``dvfs_opt`` Pallas kernel.
Heterogeneous machine classes run this same solver once per class —
:func:`repro.core.machines.configure_classes` stacks the class blocks into
one widened kernel dispatch.  See docs/EQUATIONS.md for the
equation/algorithm -> code map.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dvfs, obs
from repro.core.dvfs import DvfsParams, ScalingInterval
from repro.kernels import layout
from repro.kernels.layout import DvfsSolution  # noqa: F401  (re-export)

INV_PHI = 0.6180339887498949  # 1/golden ratio
GRID_POINTS = 65
GOLDEN_ITERS = 40


# ---------------------------------------------------------------------------
# Unconstrained optimum.
# ---------------------------------------------------------------------------


def _energy_of_fc(params: DvfsParams, fc, interval: ScalingInterval):
    """Energy along the optimal-V / optimal-fm manifold, as a function of fc."""
    v = jnp.maximum(interval.v_min, dvfs.g1_inv(fc))
    fm = dvfs.optimal_fm(params, v, fc, interval)
    return dvfs.energy(params, v, fc, fm), (v, fm)


def _golden_minimize(fn, lo, hi, iters: int = GOLDEN_ITERS):
    """Vectorized golden-section minimization of ``fn`` over ``[lo, hi]``."""

    def body(state, _):
        lo, hi = state
        d = (hi - lo) * INV_PHI
        x1 = hi - d
        x2 = lo + d
        f1 = fn(x1)
        f2 = fn(x2)
        shrink_right = f1 < f2  # minimum is in [lo, x2]
        new_lo = jnp.where(shrink_right, lo, x1)
        new_hi = jnp.where(shrink_right, x2, hi)
        return (new_lo, new_hi), None

    (lo, hi), _ = jax.lax.scan(body, (lo, hi), None, length=iters)
    return 0.5 * (lo + hi)


def _grid_then_golden(fn, lo, hi, n_grid: int = GRID_POINTS):
    """Coarse grid scan to bracket the global minimum, then golden refine.

    ``lo``/``hi`` may be per-task arrays. Returns the argmin x (same shape).
    """
    ts = jnp.linspace(0.0, 1.0, n_grid)

    def eval_at(frac):
        return fn(lo + (hi - lo) * frac)

    vals = jax.vmap(eval_at)(ts)  # [n_grid, batch...]
    best = jnp.argmin(vals, axis=0)
    step = 1.0 / (n_grid - 1)
    frac_lo = jnp.clip(best * step - step, 0.0, 1.0)
    frac_hi = jnp.clip(best * step + step, 0.0, 1.0)
    x = _golden_minimize(lambda f: fn(lo + (hi - lo) * f), frac_lo, frac_hi)
    return lo + (hi - lo) * x


@partial(jax.jit, static_argnames=("interval",))
def solve_unconstrained(params: DvfsParams, interval: ScalingInterval = dvfs.WIDE) -> DvfsSolution:
    """argmin_{V, fc, fm} E for each task, ignoring deadlines (paper Eq. 9)."""
    params = DvfsParams(*(jnp.asarray(f, jnp.float32) for f in params.astuple()))

    def efc(fc):
        return _energy_of_fc(params, fc, interval)[0]

    lo = jnp.full_like(params.big_d, interval.fc_min)
    hi = jnp.full_like(params.big_d, interval.fc_max)
    fc = _grid_then_golden(efc, lo, hi)
    e, (v, fm) = _energy_of_fc(params, fc, interval)
    t = dvfs.exec_time(params, fc, fm)
    p = dvfs.power(params, v, fc, fm)
    true_ = jnp.ones_like(e, dtype=bool)
    return DvfsSolution(v, fc, fm, t, p, e, ~true_, true_)


# ---------------------------------------------------------------------------
# Deadline-constrained optimum.
# ---------------------------------------------------------------------------


def _deadline_energy_of_fm(params: DvfsParams, fm, allowed, interval: ScalingInterval):
    """Energy on the ``t = allowed`` boundary parametrized by fm.

    Infeasible fm (required fc above fc_max, or non-positive time budget for
    the core component) get +inf energy.
    """
    slack = allowed - params.t0 - params.big_d * (1.0 - params.delta) / fm
    fc_req = params.big_d * params.delta / jnp.maximum(slack, 1e-30)
    # delta == 0: any fc meets the deadline; run the core floor.
    fc_req = jnp.where(params.delta <= 0.0, interval.fc_min, fc_req)
    infeasible = (slack <= 0.0) & (params.delta > 0.0)
    fc = jnp.clip(fc_req, interval.fc_min, interval.fc_max)
    v = jnp.maximum(interval.v_min, dvfs.g1_inv(fc))
    t = dvfs.exec_time(params, fc, fm)
    e = dvfs.power(params, v, fc, fm) * t
    e = jnp.where(infeasible | (fc_req > interval.fc_max + 1e-6), jnp.inf, e)
    return e, (v, fc)


def _boundary_optimum(params: DvfsParams, allowed, interval: ScalingInterval):
    """The deadline-boundary optimum ``(v, fc, fm)``: 1-D search over fm on
    the ``t(fc, fm) = allowed`` manifold (params/allowed already f32)."""

    def efm(fm):
        return _deadline_energy_of_fm(params, fm, allowed, interval)[0]

    lo = jnp.full_like(params.big_d, interval.fm_min)
    if interval.fm_min == interval.fm_max:
        fm = lo                  # a fixed memory clock: one boundary point
    else:
        hi = jnp.full_like(params.big_d, interval.fm_max)
        fm = _grid_then_golden(efm, lo, hi)
    _, (v, fc) = _deadline_energy_of_fm(params, fm, allowed, interval)
    return v, fc, fm


@partial(jax.jit, static_argnames=("interval",))
def solve_with_deadline(params: DvfsParams, allowed,
                        interval: ScalingInterval = dvfs.WIDE) -> DvfsSolution:
    """Optimal setting subject to ``t <= allowed`` (Algorithm 1 body).

    Tasks whose unconstrained optimum already fits (``t_hat <= allowed``) keep
    it (energy-prior); the rest are re-solved on the deadline boundary
    (deadline-prior).  Tasks that cannot meet the deadline even at maximum
    frequencies are flagged infeasible and returned at max speed.
    """
    params = DvfsParams(*(jnp.asarray(f, jnp.float32) for f in params.astuple()))
    allowed = jnp.asarray(allowed, jnp.float32)
    unc = solve_unconstrained(params, interval)
    energy_prior = unc.time <= allowed + 1e-6

    v, fc, fm = _boundary_optimum(params, allowed, interval)

    # Infeasible deadline => max speed, still report honestly.
    tmin = dvfs.min_time(params, interval)
    feasible = allowed >= tmin - 1e-6
    vmax = jnp.full_like(v, interval.v_max)
    fcmax = jnp.full_like(fc, interval.fc_max)
    fmmax = jnp.full_like(fm, interval.fm_max)

    def pick(con_val, unc_val, max_val):
        x = jnp.where(energy_prior, unc_val, con_val)
        return jnp.where(feasible, x, max_val)

    v = pick(v, unc.v, vmax)
    fc = pick(fc, unc.fc, fcmax)
    fm = pick(fm, unc.fm, fmmax)
    t = dvfs.exec_time(params, fc, fm)
    p = dvfs.power(params, v, fc, fm)
    e = p * t
    return DvfsSolution(v, fc, fm, t, p, e, ~energy_prior, feasible)


@partial(jax.jit, static_argnames=("interval",))
def solve_on_boundary(params: DvfsParams, allowed,
                      interval: ScalingInterval = dvfs.WIDE) -> DvfsSolution:
    """The deadline-boundary solve used by theta-readjustment.

    A readjustment shrinks a task's window *below* its optimal execution
    time, so the constrained optimum sits on the ``t = allowed`` boundary by
    construction — no unconstrained solve or energy-prior comparison is
    needed.  Windows below ``t_min`` fall back to max speed (infeasible).
    """
    params = DvfsParams(*(jnp.asarray(f, jnp.float32) for f in params.astuple()))
    allowed = jnp.asarray(allowed, jnp.float32)
    v, fc, fm = _boundary_optimum(params, allowed, interval)

    tmin = dvfs.min_time(params, interval)
    feasible = allowed >= tmin - 1e-6
    v = jnp.where(feasible, v, interval.v_max)
    fc = jnp.where(feasible, fc, interval.fc_max)
    fm = jnp.where(feasible, fm, interval.fm_max)
    t = dvfs.exec_time(params, fc, fm)
    p = dvfs.power(params, v, fc, fm)
    dp = jnp.ones_like(feasible)
    return DvfsSolution(v, fc, fm, t, p, p * t, dp, feasible)


# ---------------------------------------------------------------------------
# Algorithm 1: voltage/frequency configuration for a task set.
# ---------------------------------------------------------------------------


class TaskConfig(NamedTuple):
    """Numpy view of Algorithm 1's output, consumed by the schedulers."""

    v: np.ndarray
    fc: np.ndarray
    fm: np.ndarray
    t_hat: np.ndarray          # optimized execution time (paper's t-hat / t-hat')
    p_hat: np.ndarray
    e_hat: np.ndarray
    t_min: np.ndarray          # fastest achievable time (theta floor)
    deadline_prior: np.ndarray
    feasible: np.ndarray
    n_deadline_prior: int


def pad_pow2(params: DvfsParams, allowed, extra_rows: np.ndarray = None):
    """Pad a batch to the next power of two (>= 8) by replicating the last
    task, so the jitted solvers compile O(log n) distinct shapes over a
    day-long online simulation instead of one per slot population.

    ``extra_rows`` (``[n, k]``, e.g. per-row interval bounds) is padded the
    same way; returns ``(params, allowed, extra_rows, n)``.
    """
    n = int(np.shape(np.asarray(params.p0))[0])
    n_pad = max(8, 1 << (n - 1).bit_length())
    if n_pad != n:
        pad = n_pad - n
        params = DvfsParams(*(np.concatenate(
            [np.asarray(f, np.float64), np.full(pad, np.asarray(f)[-1])])
            for f in params.astuple()))
        allowed = np.concatenate(
            [np.asarray(allowed, np.float64),
             np.full(pad, np.asarray(allowed)[-1])])
        if extra_rows is not None:
            extra_rows = np.concatenate(
                [extra_rows,
                 np.broadcast_to(extra_rows[-1], (pad, extra_rows.shape[1]))],
                axis=0)
    return params, allowed, extra_rows, n


def config_from_solution(sol: DvfsSolution, params: DvfsParams, allowed,
                         interval: ScalingInterval,
                         tmin: np.ndarray = None) -> TaskConfig:
    """TaskConfig assembly shared by :func:`configure_tasks` and the
    heterogeneous class path (``machines.configure_classes``): the t_min
    floor plus snapping the deadline-boundary f32 residual to ``allowed``
    so downstream deadline checks are exact.

    ``tmin`` short-circuits the :func:`repro.core.dvfs.min_time` call when
    the caller already holds it — the pipelined online path computes the
    whole horizon's floors once up front and passes per-chunk slices
    (``min_time`` is elementwise, so slices are bitwise equal)."""
    with obs.span("solve.config"):
        sol = DvfsSolution(*(np.asarray(f) for f in sol))
        if tmin is None:
            floors = dvfs.min_time(params, interval)
            with obs.span("solve.wait"):
                tmin = np.asarray(floors)
        allowed_arr = np.broadcast_to(np.asarray(allowed, np.float64),
                                      sol.time.shape)
        t_hat = np.where(sol.deadline_prior & sol.feasible,
                         np.minimum(sol.time, allowed_arr), sol.time)
        return TaskConfig(
            v=sol.v, fc=sol.fc, fm=sol.fm,
            t_hat=t_hat, p_hat=sol.power, e_hat=sol.power * t_hat,
            t_min=np.broadcast_to(tmin, sol.time.shape).copy(),
            deadline_prior=sol.deadline_prior, feasible=sol.feasible,
            n_deadline_prior=int(np.sum(sol.deadline_prior)),
        )


def no_dvfs_config(params: DvfsParams, allowed) -> TaskConfig:
    """The no-DVFS configuration: every task runs at ``(1, 1, 1)``.

    The ONE implementation behind both ``scheduling.default_config``
    (homogeneous) and ``machines.default_configs`` (per adapted class), so
    the ``(1, 1, 1)`` fallback cannot drift between the two paths.  With no
    scaling there is no shrink room: ``t_min == t_hat == t*``.
    """
    allowed = np.asarray(allowed, dtype=np.float64)
    t_star = np.asarray(params.default_time())
    p_star = np.asarray(params.default_power())
    ones = np.ones(t_star.shape[0])
    deadline_prior = t_star > allowed + 1e-9
    return TaskConfig(
        v=ones.copy(), fc=ones.copy(), fm=ones.copy(),
        t_hat=t_star.copy(), p_hat=p_star.copy(), e_hat=(p_star * t_star),
        t_min=t_star.copy(),
        deadline_prior=deadline_prior,
        feasible=~deadline_prior,
        n_deadline_prior=int(np.sum(deadline_prior)),
    )


def max_speed_setting(params: DvfsParams,
                      interval: ScalingInterval = dvfs.WIDE):
    """Every task at the interval's maximum speed: ``(v_max, fc_max,
    fm_max)``, with ``t`` equal to the class ``t_min`` bitwise (both are
    :func:`repro.core.dvfs.min_time` on the same params/interval).

    The graceful-degradation setting of the fault-recovery policy
    (:meth:`repro.core.placement.PlacementContext.place_orphans`): a task
    re-placed after a server failure that cannot meet its deadline on any
    pair runs flat out, and the remaining miss is counted as a violation.
    Returns numpy arrays ``(v, fc, fm, t, p)``.
    """
    t = np.asarray(dvfs.min_time(params, interval), np.float64)
    p = np.asarray(dvfs.power(params, interval.v_max, interval.fc_max,
                              interval.fm_max), np.float64)
    n = t.shape[0]
    return (np.full(n, interval.v_max), np.full(n, interval.fc_max),
            np.full(n, interval.fm_max), t, np.broadcast_to(p, (n,)))


def _dedup_solve(params: DvfsParams, allowed, interval: ScalingInterval,
                 boundary: bool) -> DvfsSolution:
    """Route a batched jnp solve through the unique-row dedup + process-wide
    LRU cache (:mod:`repro.core.solver_cache`).

    Bit-identical to the direct solve: the f32 key matrix IS the solver
    input (both solvers cast to f32 before computing) and every solver is
    row-independent, so deduped rows scatter back to exactly the values a
    full-batch solve would produce.
    """
    from repro.core import solver_cache

    with obs.span("solve.keys"):
        keys = solver_cache.build_keys(
            params.astuple(), allowed, boundary,
            np.asarray(interval.bounds(), np.float32))
    solver = solve_on_boundary if boundary else solve_with_deadline

    def solve(km: np.ndarray) -> np.ndarray:
        p = DvfsParams(*(km[:, i] for i in range(layout.N_PARAMS)))
        return solver_cache.solution_to_rows(
            solver(p, km[:, layout.ALLOWED], interval))

    rows = solver_cache.solve_rows(keys, solve,
                                   tag="jnp-bd" if boundary else "jnp-dl")
    return solver_cache.rows_to_solution(rows)


def solve_rows_async(params: DvfsParams, allowed,
                     interval: ScalingInterval, *, boundary: bool,
                     use_kernel: bool = False, dedup: bool = True):
    """Dispatch one solve batch without blocking — the pipelined online
    scheduler's per-chunk entry point.

    Builds the f32 key matrix, probes the cache, and dispatches only the
    misses; returns a :class:`repro.core.solver_cache.AsyncSolve` whose
    ``.result()`` is bit-identical to the synchronous
    :func:`configure_tasks` / :func:`readjust_batch` solves (same tags, so
    the cache composes across both paths).  The jnp path keeps the result
    on device by stacking the solution columns eagerly (dispatch, not
    compute); the kernel path defers via ``dvfs_solve_matrix(block=False)``.

    Chunks skip the sort-based intra-batch unique pass
    (``solve_rows_async(unique=False)``): online chunks are nearly
    duplicate-free, so the cache probe alone carries the dedup and
    cross-chunk repeats still hit.
    """
    from repro.core import solver_cache

    with obs.span("solve.keys"):
        keys = solver_cache.build_keys(
            params.astuple(), allowed, boundary,
            np.asarray(interval.bounds(), np.float32))
    cache = solver_cache.GLOBAL_CACHE if dedup else None
    if use_kernel:
        from repro.kernels import ops as kernel_ops
        from repro.kernels.dvfs_opt import DEFAULT_GRID

        tag = f"k{int(DEFAULT_GRID[0])}x{int(DEFAULT_GRID[1])}"

        def solve(km: np.ndarray):
            return kernel_ops.dvfs_solve_matrix(km, block=False)

    else:
        tag = "jnp-bd" if boundary else "jnp-dl"
        solver = solve_on_boundary if boundary else solve_with_deadline

        def solve(km: np.ndarray):
            p = DvfsParams(*(km[:, i] for i in range(layout.N_PARAMS)))
            sol = solver(p, km[:, layout.ALLOWED], interval)
            # Device-side stack: pure data movement (bitwise equal to the
            # host-side ``solution_to_rows``), so the host never waits here.
            return jnp.stack(
                [jnp.asarray(f, jnp.float32) for f in sol], axis=1)

    return solver_cache.solve_rows_async(keys, solve, tag=tag, cache=cache,
                                         unique=False)


def configure_tasks(params: DvfsParams, allowed, interval: ScalingInterval = dvfs.WIDE,
                    use_kernel: bool = False, dedup: bool = True) -> TaskConfig:
    """Algorithm 1: per-task optimal DVFS settings for a whole task set.

    ``allowed`` is ``d - a`` per task.  With ``use_kernel=True`` the batched
    Pallas kernel (interpret mode on CPU) computes the whole solve.
    ``dedup=True`` (default) solves only unique ``(params, allowed)`` rows
    and serves repeats — within this call or from any previous one — out of
    the process-wide solve cache, bit-identically.
    """
    with obs.span("solve.keys"):
        params, allowed, _, n = pad_pow2(params, allowed)
    if use_kernel:
        from repro.kernels import ops as kernel_ops

        sol = kernel_ops.dvfs_solve(params, np.asarray(allowed), interval,
                                    dedup=dedup)
    elif dedup:
        sol = _dedup_solve(params, allowed, interval, boundary=False)
    else:
        sol = solve_with_deadline(params, allowed, interval)
    if np.shape(np.asarray(params.p0))[0] != n:
        sol = DvfsSolution(*(np.asarray(f)[:n] for f in sol))
        params = params[:n]
        allowed = np.asarray(allowed)[:n]
    return config_from_solution(sol, params, allowed, interval)


def readjust_batch(params: DvfsParams, windows, interval: ScalingInterval = dvfs.WIDE,
                   use_kernel: bool = False, dedup: bool = True):
    """Batched theta-readjustment: re-solve ``n`` tasks with shrunken time
    budgets in ONE solver dispatch (Algorithm 2 lines 16-19 / Algorithm 5).

    A readjusted window sits below the task's optimal execution time by
    construction, so every row takes the deadline-boundary branch; with
    ``use_kernel=True`` the whole batch goes through the Pallas kernel's
    readjust sweep in a single ``pallas_call``.  Returns numpy arrays
    ``(v, fc, fm, t, p, e)`` with ``t`` snapped to the window where feasible
    (so scheduler mu updates land exactly on the deadline).
    """
    windows = np.asarray(windows, dtype=np.float64)
    with obs.span("solve.keys"):
        params, padded, _, n = pad_pow2(params, windows)
    if use_kernel:
        from repro.kernels import ops as kernel_ops

        sol = kernel_ops.dvfs_solve(params, np.asarray(padded), interval,
                                    readjust=True, dedup=dedup)
    elif dedup:
        sol = _dedup_solve(params, padded, interval, boundary=True)
    else:
        sol = solve_on_boundary(params, padded, interval)
    with obs.span("solve.config"):
        v, fc, fm, t, p = (np.asarray(f, np.float64)[:n] for f in (
            sol.v, sol.fc, sol.fm, sol.time, sol.power))
        feas = np.asarray(sol.feasible)[:n]
        t = np.where(feas, np.minimum(t, windows), t)  # snap f32 residual
        return v, fc, fm, t, p, p * t


def readjust(params: DvfsParams, new_allowed: float,
             interval: ScalingInterval = dvfs.WIDE):
    """theta-readjustment: re-solve one task with a shrunken time budget.

    Returns ``(v, fc, fm, t, p, e)`` as python floats.  Thin scalar wrapper
    over :func:`readjust_batch`: ``new_allowed`` must sit below the task's
    unconstrained optimal time (the readjustment regime) — the boundary
    solution is returned unconditionally, so a window wide enough for the
    interior optimum would come back pessimally stretched to fill it.
    """
    batched = DvfsParams(*(np.asarray([f], dtype=np.float64) for f in params.astuple()))
    out = readjust_batch(batched, np.asarray([float(new_allowed)]), interval)
    return tuple(float(np.asarray(f)[0]) for f in out)


def brute_force_optimum(params: DvfsParams, allowed: float | None = None,
                        interval: ScalingInterval = dvfs.WIDE, n: int = 160):
    """Dense-grid reference optimum (tests only; O(n^3) with feasibility mask)."""
    vs = np.linspace(interval.v_min, interval.v_max, n)
    fms = np.linspace(interval.fm_min, interval.fm_max, n)
    best = (np.inf, None)
    for v in vs:
        fc_hi = float(dvfs.g1(v))
        fcs = np.linspace(interval.fc_min, fc_hi, n)
        fcs = fcs[fcs <= fc_hi + 1e-9]
        for fc in fcs:
            t = np.asarray(dvfs.exec_time(params, fc, fms))
            p = np.asarray(dvfs.power(params, v, fc, fms))
            e = p * t
            if allowed is not None:
                e = np.where(t <= allowed + 1e-9, e, np.inf)
            i = int(np.argmin(e))
            if e[i] < best[0]:
                best = (float(e[i]), (float(v), float(fc), float(fms[i]), float(t[i])))
    return best
