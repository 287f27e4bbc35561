"""Pallas TPU kernel for the batched single-task DVFS optimum (paper §4.1).

This is the scheduler's own hot-spot Φ: at every online time slot the
cluster solves ``argmin E(V, fc, fm)`` for every newly-arrived task
(Algorithm 1/5) — thousands of independent 2-variable minimizations, and
with heterogeneous machine classes one such solve per task **per class**.
The kernel evaluates the energy surface for a block of tasks over a
hierarchically refined frequency grid entirely in VMEM and reduces the
argmin, fusing what would otherwise be a dozen HBM round-trips per task
into one.

Layout: tasks are a [n, NCOL=16] f32 matrix whose columns are declared
once in :mod:`repro.kernels.layout`
    (P0, GAMMA, C_COEF, BIG_D, DELTA, T0, ALLOWED, READJUST,
     V_MIN, V_MAX, FC_MIN, FM_MIN, FM_MAX, pad, pad, pad);
block = BT=128 tasks per VPU tile row.
The ``BOUNDS_SLICE`` columns carry the row's own :class:`ScalingInterval`
bounds, which is what lets one ``pallas_call`` solve a class-stacked
``[C*n, 16]`` matrix where every class block has a different DVFS box (see
``repro.core.machines.configure_classes``).  The legacy
``[n, LEGACY_NCOL=8]`` layout (homogeneous interval) is widened on entry
from the static ``interval`` argument.

Each of the two 1-D sweeps is **hierarchical** (``grid=(G0, G1)`` static
args, default ``(64, 64)``): a coarse pass over ``G0`` equispaced points
brackets the argmin, then a fine pass re-sweeps ``G1`` points inside the
``±1``-coarse-step bracket — ~``G0·G1/2`` effective resolution for
``G0+G1`` evaluations, i.e. the same evaluation budget as the old flat
128-point sweep but ~16x the resolution.  The fine winner is guarded
against the coarse winner (finer grids can never *increase* the energy),
mirroring the coarse-grid-then-golden-refinement structure of the
production jnp solver (``single_task._grid_then_golden``, the ``ref.py``
oracle).

The two sweeps match the paper's case split:

* unconstrained: fc-grid over [fc_min, g1(v_max)]; V = max(v_min, g1⁻¹(fc));
  fm = closed-form optimum clamped to the box (paper §4.1);
* deadline boundary: fm-grid; fc from t(fc, fm) = allowed (§4.1 deadline-
  prior case); +inf energy where infeasible.

The winner per task replicates exactly the decision rule of
``repro.core.single_task.solve_with_deadline`` (the pure-jnp oracle in
``ref.py``) up to grid resolution.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.dvfs import G1_A, G1_B, G1_C, ScalingInterval, WIDE
from repro.kernels.layout import (ALLOWED, BIG_D, C_COEF, DELTA, FC_MIN,
                                  FM_MAX, FM_MIN, GAMMA, KEY_COLS,
                                  LEGACY_NCOL, N_BOUNDS, NCOL, P0, READJUST,
                                  SOL_COLS, T0, V_MAX, V_MIN, col)

BT = 128   # tasks per block
DEFAULT_GRID = (64, 64)  # (coarse, fine) sweep points; ~16x the old flat-128
INF = 1e30

#: A benign, fully-feasible pad task: reference-ish constants on the WIDE
#: box with a huge deadline window, so pad rows always take the smooth
#: energy-prior branch.  (The old ``jnp.ones`` pad encoded the degenerate
#: box v_min=v_max=fc_min=fm_min=fm_max=1, which pushed every pad row
#: through the INF-masked deadline-boundary sweep.)
PAD_ROW = np.asarray(
    [[1.0, 1.0, 1.0, 1.0, 0.5, 0.1, 1e6, 0.0, *WIDE.bounds(), 0.0, 0.0, 0.0]],
    np.float32)
assert PAD_ROW.shape == (1, NCOL)


def _g1(v):
    return jnp.sqrt(jnp.maximum(v - G1_A, 0.0) / G1_B) + G1_C


def _g1_inv(fc):
    return G1_B * jnp.square(jnp.maximum(fc - G1_C, 0.0)) + G1_A


def _lanes(k: int):
    """``[BT, k]`` int32 lane index.  Mosaic's iota is integer-only."""
    return jax.lax.broadcasted_iota(jnp.int32, (BT, k), 1)


def _take(x, onehot):
    """Per-row ``x[r, i_r]`` as a masked lane sum over a one-hot mask.

    Mosaic has no in-kernel gather; exactly one lane survives the mask and
    the rest add ``0.0``, so the result is bitwise the gathered value."""
    return jnp.sum(jnp.where(onehot, x, 0.0), axis=1)


def _hier_argmin(efn, g0: int, g1: int):
    """Coarse-then-fine argmin of ``efn`` over the unit interval.

    ``efn`` maps a fraction array ``[BT, k]`` to energies ``[BT, k]``.
    Sweeps ``g0`` coarse points, brackets the winner one coarse step to
    each side, re-sweeps ``g1`` fine points inside the bracket, and
    returns the per-row winning fraction ``[BT]`` — guarded so the fine
    winner is never worse than the coarse one (refinement is monotone).
    """
    lane0 = _lanes(g0)
    f0 = lane0.astype(jnp.float32) / (g0 - 1)
    e0 = efn(f0)
    i0 = jnp.argmin(e0, axis=1)
    hot0 = lane0 == i0[:, None]
    e0_best = _take(e0, hot0)
    f0_best = _take(f0, hot0)
    step = 1.0 / (g0 - 1)
    f_lo = jnp.clip((i0.astype(jnp.float32) - 1.0) * step, 0.0, 1.0)
    f_hi = jnp.clip((i0.astype(jnp.float32) + 1.0) * step, 0.0, 1.0)
    lane1 = _lanes(g1)
    frac = lane1.astype(jnp.float32) / (g1 - 1)
    f1 = f_lo[:, None] + (f_hi - f_lo)[:, None] * frac
    e1 = efn(f1)
    i1 = jnp.argmin(e1, axis=1)
    hot1 = lane1 == i1[:, None]
    e1_best = _take(e1, hot1)
    f1_best = _take(f1, hot1)
    return jnp.where(e1_best <= e0_best, f1_best, f0_best)


def _sq(x):
    """``[BT, 1] -> [BT]`` squeeze (a shape op, not a schema column read)."""
    return jnp.squeeze(x, axis=1)


def _kernel(tasks_ref, out_ref, *, g0: int, g1: int):
    t = tasks_ref[...].astype(jnp.float32)               # [BT, NCOL]
    p0, gamma, cc = t[:, col(P0)], t[:, col(GAMMA)], t[:, col(C_COEF)]
    dd, delta, t0 = t[:, col(BIG_D)], t[:, col(DELTA)], t[:, col(T0)]
    allowed = t[:, col(ALLOWED)]
    readjust = t[:, READJUST] > 0.5  # theta-readjustment rows: boundary binds
    # Per-row scaling-interval bounds, shape [BT, 1].
    v_min, v_max = t[:, col(V_MIN)], t[:, col(V_MAX)]
    fc_min, fm_min, fm_max = (t[:, col(FC_MIN)], t[:, col(FM_MIN)],
                              t[:, col(FM_MAX)])

    def energy_at(v, fc, fm):
        pw = p0 + gamma * fm + cc * jnp.square(v) * fc
        tt = dd * (delta / fc + (1.0 - delta) / fm) + t0
        return pw * tt, pw, tt

    # ---- sweep 1: unconstrained, fc grid on [fc_min, g1(v_max)].
    fc_max = _g1(v_max)                                  # [BT, 1]

    def unc_at(frac):
        """frac [BT, k] -> (energy, (v, fc, fm, t)) on the optimal-V /
        closed-form-fm manifold (paper §4.1)."""
        fc = fc_min + (fc_max - fc_min) * frac           # [BT, k]
        v = jnp.maximum(v_min, _g1_inv(fc))
        # closed-form fm (paper §4.1), clamped; gamma == 0 -> fm_max.
        num = (p0 + cc * jnp.square(v) * fc) * dd * (1.0 - delta)
        den = gamma * (t0 + dd * delta / fc)
        fm = jnp.sqrt(num / jnp.maximum(den, 1e-30))
        fm = jnp.where(gamma <= 0.0, fm_max, fm)
        fm = jnp.clip(fm, fm_min, fm_max)
        e, _, tt = energy_at(v, fc, fm)
        return e, (v, fc, fm, tt)

    fu = _hier_argmin(lambda f: unc_at(f)[0], g0, g1)
    _, (v_1, fc_1, fm_1, t_1) = unc_at(fu[:, None])      # [BT, 1] at winner
    v_u, fc_u, fm_u, t_un = _sq(v_1), _sq(fc_1), _sq(fm_1), _sq(t_1)

    # ---- sweep 2: deadline boundary t(fc, fm) = allowed, fm grid.
    def bnd_at(frac):
        """frac [BT, k] -> (energy, (v, fc, fm)) on the t = allowed
        manifold; infeasible points get +INF."""
        fm2 = fm_min + (fm_max - fm_min) * frac
        slack = allowed - t0 - dd * (1.0 - delta) / fm2
        fc_req = dd * delta / jnp.maximum(slack, 1e-30)
        fc_req = jnp.where(delta <= 0.0, fc_min, fc_req)
        bad = (slack <= 0.0) & (delta > 0.0)
        fc2 = jnp.clip(fc_req, fc_min, fc_max)
        v2 = jnp.maximum(v_min, _g1_inv(fc2))
        e, _, _ = energy_at(v2, fc2, fm2)
        e = jnp.where(bad | (fc_req > fc_max + 1e-6), INF, e)
        return e, (v2, fc2, fm2)

    fb = _hier_argmin(lambda f: bnd_at(f)[0], g0, g1)
    _, (v_2, fc_2, fm_2) = bnd_at(fb[:, None])
    v_d, fc_d, fm_d = _sq(v_2), _sq(fc_2), _sq(fm_2)

    # ---- decision rule (== solve_with_deadline / solve_on_boundary):
    # energy-prior if the unconstrained optimum meets the deadline;
    # readjust rows shrank their window below the optimum, so the boundary
    # binds by construction; infeasible (deadline < t_min) -> max speed.
    allowed1 = _sq(allowed)
    energy_prior = (t_un <= allowed1 + 1e-6) & ~readjust
    t_min = _sq(dd * (delta / fc_max + (1.0 - delta) / fm_max) + t0)
    feasible = allowed1 >= t_min - 1e-6
    v_mx = _sq(v_max)
    fc_mx = _sq(fc_max)
    fm_mx = _sq(fm_max)

    def pick(unc, con, mx):
        x = jnp.where(energy_prior, unc, con)
        return jnp.where(feasible, x, mx)

    vf = pick(v_u, v_d, v_mx)
    fcf = pick(fc_u, fc_d, fc_mx)
    fmf = pick(fm_u, fm_d, fm_mx)
    pw = _sq(p0) + _sq(gamma) * fmf + _sq(cc) * jnp.square(vf) * fcf
    tt = _sq(dd) * (_sq(delta) / fcf + (1.0 - _sq(delta)) / fmf) + _sq(t0)
    tt = jnp.where(feasible & ~energy_prior, jnp.minimum(tt, allowed1), tt)

    # [BT, SOL_COLS] in layout.SOL_* column order.
    out = jnp.stack([vf, fcf, fmf, tt, pw, pw * tt,
                     (~energy_prior).astype(jnp.float32),
                     feasible.astype(jnp.float32)], axis=1)
    out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interval", "grid", "interpret"))
def dvfs_solve_kernel(tasks: jax.Array, *, interval: ScalingInterval = WIDE,
                      grid: tuple = DEFAULT_GRID,
                      interpret: bool = False) -> jax.Array:
    """tasks: [n, 8] or [n, 16] f32 (see module docstring) ->
    [n, 8] (v, fc, fm, t, p, e, deadline_prior, feasible).

    An 8-column matrix is widened with the static ``interval``'s bounds
    (the homogeneous legacy layout); a 16-column matrix carries per-row
    bounds and ignores ``interval``.  ``grid=(G0, G1)`` sets the coarse /
    fine sweep sizes of the hierarchical refinement (both >= 2); the
    effective resolution of each 1-D sweep is ~``G0*G1/2`` points for
    ``G0 + G1`` evaluations.
    """
    g0, g1 = int(grid[0]), int(grid[1])
    if g0 < 2 or g1 < 2:
        raise ValueError(f"grid sizes must be >= 2, got {grid}")
    n = tasks.shape[0]
    if tasks.shape[1] == LEGACY_NCOL:
        bounds = jnp.broadcast_to(
            jnp.asarray(interval.bounds(), tasks.dtype), (n, N_BOUNDS))
        pad = jnp.zeros((n, NCOL - KEY_COLS), tasks.dtype)
        tasks = jnp.concatenate([tasks, bounds, pad], axis=1)
    elif tasks.shape[1] != NCOL:
        raise ValueError(f"task matrix must have {LEGACY_NCOL} or {NCOL} "
                         f"columns, got {tasks.shape[1]}")
    n_pad = -(-n // BT) * BT
    if n_pad != n:
        pad = jnp.broadcast_to(jnp.asarray(PAD_ROW, tasks.dtype),
                               (n_pad - n, NCOL))
        tasks = jnp.concatenate([tasks, pad], axis=0)
    out = pl.pallas_call(
        functools.partial(_kernel, g0=g0, g1=g1),
        grid=(n_pad // BT,),
        in_specs=[pl.BlockSpec((BT, NCOL), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((BT, SOL_COLS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, SOL_COLS), jnp.float32),
        interpret=interpret,
    )(tasks.astype(jnp.float32))
    return out[:n]
