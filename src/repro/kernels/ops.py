"""Jit'd public wrappers for the Pallas kernels.

On the CPU container every kernel runs in ``interpret=True`` mode (the
kernel body executes as JAX ops — bit-identical control flow to the TPU
lowering); on a real TPU backend the same calls compile to Mosaic.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import obs, solver_cache
from repro.core.dvfs import DvfsParams, ScalingInterval, WIDE
from repro.kernels import layout
from repro.kernels.dvfs_opt import BT, DEFAULT_GRID, PAD_ROW, dvfs_solve_kernel
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.layout import DvfsSolution
from repro.kernels.ssd_scan import ssd_scan as _ssd

#: Below this row count a multi-device split costs more in transfer/dispatch
#: than it saves in compute.
SHARD_MIN_ROWS = 4096


def default_interpret() -> bool:
    """THE ``interpret=`` policy for every kernel call site: run the Pallas
    bodies as JAX ops unless a real TPU backend is attached, so CI, laptops,
    and TPU hosts all exercise the same code path without per-caller flags."""
    return jax.default_backend() != "tpu"


#: Fixed home of the persistent compilation cache when the environment
#: names none: the cache key includes the path, so it must not move.
COMPILE_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), *[os.pardir] * 3, ".jax_cache"))


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for an entry point.

    ``JAX_COMPILATION_CACHE_DIR``, when set, already points JAX at its
    directory and is left alone; otherwise the cache lives in the
    checkout's ``.jax_cache``.  The compile-time floor drops to zero so the
    many small per-shape solve compiles (``solver_cache._pad_rows``) are
    cached too.  Called by entry points, never at import."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def _pad_head_dim(x: jax.Array, to: int = 128) -> jax.Array:
    dh = x.shape[-1]
    if dh % to == 0:
        return x
    pad = -(-dh // to) * to - dh
    cfgpad = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, cfgpad)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    bq: int = 128, bk: int = 128) -> jax.Array:
    """MXU-padded flash attention.  q: [B, H, S, dh]; k/v: [B, KV, Sk, dh].

    Pads dh to a multiple of 128 (scores are unaffected because padded
    columns are zero in both q and k; v padding is sliced off)."""
    dh = q.shape[-1]
    qp, kp, vp = (_pad_head_dim(t) for t in (q, k, v))
    # scale uses the REAL dh: compensate the kernel's padded-dh scale.
    fix = (qp.shape[-1] / dh) ** 0.5
    out = _flash(qp * fix, kp, vp, causal=causal, window=window, bq=bq,
                 bk=bk, interpret=default_interpret())
    return out[..., :dh]


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, chunk: int = 128) -> jax.Array:
    """SSD chunked scan (no D-skip).  See kernels/ssd_scan.py."""
    return _ssd(x, dt, a, b, c, chunk=chunk, interpret=default_interpret())


def dvfs_solve_matrix(mat: np.ndarray, *, grid: tuple = DEFAULT_GRID,
                      interpret: Optional[bool] = None,
                      shard: bool = True, block: bool = True):
    """Dispatch a ``[m, 16]`` (or ``[m, 13]`` key-layout) task matrix to the
    Pallas solver, sharded across local devices when it pays off.

    The matrix is padded to a whole number of kernel blocks with benign
    rows, split into equal per-device chunks (all chunks one compiled
    shape), dispatched asynchronously to each device, and re-concatenated —
    per-row results are bitwise identical to the single-device path because
    the solver is row-independent.  Falls back to one local dispatch when
    there is a single device or the batch is under ``SHARD_MIN_ROWS``.
    Returns the ``[m, 8]`` solution matrix as numpy.

    ``block=False`` is the pipelined-scheduler entry point: the kernel is
    dispatched but the host does NOT wait for it — the return value is the
    in-flight device array (single device) or a zero-arg callable that
    gathers the per-device parts when invoked.  Either form is what
    ``solver_cache._materialize`` consumes at the pipeline's sync point.
    """
    if interpret is None:
        interpret = default_interpret()
    mat = np.asarray(mat, np.float32)
    if mat.shape[1] == layout.KEY_COLS:  # widen key layout -> NCOL
        mat = np.concatenate(
            [mat, np.zeros((mat.shape[0], layout.NCOL - layout.KEY_COLS),
                           np.float32)], axis=1)
    m = mat.shape[0]
    devs = jax.local_devices()
    nd = 1
    if shard and len(devs) > 1 and m >= SHARD_MIN_ROWS:
        nd = 1 << (len(devs).bit_length() - 1)   # pow-2 device count
        while nd > 1 and -(-m // nd) < BT:
            nd //= 2
    if nd == 1:
        fut = dvfs_solve_kernel(jnp.asarray(mat), grid=grid,
                                interpret=interpret)
        if not block:
            return fut
        with obs.span("solve.wait"):
            return np.asarray(fut)
    per_dev = -(-m // nd)
    chunk = -(-per_dev // BT) * BT  # whole kernel blocks per device
    if nd * chunk != m:
        pad = np.broadcast_to(PAD_ROW, (nd * chunk - m, layout.NCOL))
        mat = np.concatenate([mat, pad], axis=0)
    parts = [dvfs_solve_kernel(
                 jax.device_put(mat[i * chunk:(i + 1) * chunk], devs[i]),
                 grid=grid, interpret=interpret)
             for i in range(nd)]  # dispatches are async; concat blocks

    def gather() -> np.ndarray:
        return np.concatenate([np.asarray(p) for p in parts], axis=0)[:m]

    if not block:
        return gather
    with obs.span("solve.wait"):
        return gather()


def dvfs_solve(params: DvfsParams, allowed: np.ndarray,
               interval: ScalingInterval = WIDE,
               readjust: bool = False,
               interval_rows: Optional[np.ndarray] = None,
               dedup: bool = True,
               grid: tuple = DEFAULT_GRID,
               cache: Optional["solver_cache.SolveCache"] = None) -> DvfsSolution:
    """Batched single-task DVFS optimum via the Pallas kernel.

    Drop-in for ``single_task.solve_with_deadline`` (same DvfsSolution
    contract; used by ``configure_tasks(use_kernel=True)``).  With
    ``readjust=True`` every row is flagged as a theta-readjustment (column
    7 of the task matrix): the kernel then takes the deadline-boundary
    sweep unconditionally — the drop-in for ``single_task.solve_on_boundary``
    used by ``readjust_batch(use_kernel=True)``.

    ``interval_rows`` (``[n, 5]``: v_min, v_max, fc_min, fm_min, fm_max)
    gives every row its own scaling box — the heterogeneous-class path
    (``machines.configure_classes``) stacks one class block per interval
    and solves them all in this one dispatch.  When omitted, the static
    ``interval`` applies to every row.

    ``dedup=True`` routes the matrix through the unique-row dedup +
    process-wide LRU solve cache (:mod:`repro.core.solver_cache`) — bit
    identical output, only previously-unseen rows touch the kernel.
    ``grid`` sets the kernel's hierarchical (coarse, fine) sweep sizes;
    ``cache=None`` means the global cache when deduping.
    """
    cols = [np.asarray(f, np.float32) for f in params.astuple()]
    n = cols[0].shape[0]
    if interval_rows is not None:
        bounds = np.asarray(interval_rows, np.float32)
        if bounds.shape != (n, layout.N_BOUNDS):
            raise ValueError(f"interval_rows must be [n, {layout.N_BOUNDS}], "
                             f"got {bounds.shape}")
    else:
        bounds = np.asarray(interval.bounds(), np.float32)
    keys = solver_cache.build_keys(cols, allowed, readjust, bounds)

    def solve(km: np.ndarray) -> np.ndarray:
        return dvfs_solve_matrix(km, grid=grid)

    if dedup:
        tag = f"k{int(grid[0])}x{int(grid[1])}"
        out = solver_cache.solve_rows(
            keys, solve, tag=tag,
            cache=solver_cache.GLOBAL_CACHE if cache is None else cache)
    else:
        out = solve(keys)
    return solver_cache.rows_to_solution(out)
