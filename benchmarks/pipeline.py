"""Pipelined online scheduling: async solve prefetch + incremental pools.

Benchmarks ``online.schedule_online(pipeline=True)`` against the
synchronous reference path (``pipeline=False``) on day-long traces.  The
two are bit-identical by construction (pinned by ``tests/test_pipeline.py``
and re-asserted here); the pipelined path wins by doing structurally less
work per arrival group:

* chunked per-arrival-group solve batches skip the serial path's
  sort-based ``np.unique`` pre-pass (the solve cache's probe already
  carries the cross-chunk dedup);
* the chunk prologue (EDF orders, deadline gathers) is hoisted into one
  vectorized ``PlacementContext.prepare_chunk`` pass;
* persistent per-class pools replace the per-group pool rebuild with
  delta reconciliation (batched power-off deletion, fault-epoch
  invalidation).

Timing method: both modes are fully warmed (jit compiles), then timed
interleaved for ``--reps`` repeats with a cold solve cache and the GC
paused inside the window; the best (min) repeat per mode is compared —
single-core CI boxes jitter far more than the path difference.

``--smoke`` is the CI guard: the pipelined run must beat the synchronous
one by ``--min-speedup`` (default 1.5x) inside a ``--budget`` wall cap,
with bit-equal ``e_total`` and scalar-placement parity, and the cell
results land in ``BENCH_sched.json`` at the repo root.

    PYTHONPATH=src python -m benchmarks.pipeline --smoke
    PYTHONPATH=src python -m benchmarks.pipeline --tasks 1000000 \\
        --pattern diurnal --no-scalar
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import time
from typing import Dict, Optional

from benchmarks.common import record
from repro.core import online, solver_cache, tasks

BENCH_JSON = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_sched.json")


def _timed_run(ts, pipeline: bool, kw: dict) -> float:
    """One wall-clock sample: cold solve cache, warm jit, GC paused."""
    solver_cache.GLOBAL_CACHE.clear()
    gc.collect()
    gc.disable()
    try:
        t0 = time.perf_counter()
        online.schedule_online(ts, pipeline=pipeline, **kw)
        return time.perf_counter() - t0
    finally:
        gc.enable()


def run_cell(n_tasks: int, pattern: str, l: int = 4, theta: float = 0.9,
             use_kernel: bool = False, horizon: Optional[int] = None,
             seed: int = 0, reps: int = 3, scalar: bool = True,
             verbose: bool = True) -> Dict:
    """One trace: bit-identity checks + interleaved pipelined/sync timing."""
    horizon = horizon or tasks.DAY_SLOTS
    ts = tasks.generate_trace(n_tasks, pattern=pattern, horizon=horizon,
                              seed=seed)
    kw = dict(l=l, theta=theta, algorithm="edl", placement="vector",
              use_kernel=use_kernel, bound=False)

    # Warmup both modes (jit compiles for every padded chunk shape) — these
    # runs double as the bit-identity guard.
    r_pipe = online.schedule_online(ts, pipeline=True, **kw)
    r_sync = online.schedule_online(ts, pipeline=False, **kw)
    bit_identical = (
        r_pipe.e_total == r_sync.e_total
        and r_pipe.violations == r_sync.violations
        and len(r_pipe.assignments) == len(r_sync.assignments)
        and all(a == b for a, b in zip(r_pipe.assignments,
                                       r_sync.assignments)))
    assert bit_identical, (
        f"pipeline=True diverged from the synchronous path: "
        f"e_total {r_pipe.e_total!r} vs {r_sync.e_total!r}")

    scalar_parity = None
    if scalar:
        r_sca = online.schedule_online(ts, placement="scalar",
                                       **{k: v for k, v in kw.items()
                                          if k != "placement"})
        scalar_parity = r_pipe.e_total == r_sca.e_total
        assert scalar_parity, (
            f"vector/scalar e_total diverged: {r_pipe.e_total!r} vs "
            f"{r_sca.e_total!r}")

    t_pipe, t_sync = [], []
    for _ in range(reps):
        t_pipe.append(_timed_run(ts, True, kw))
        t_sync.append(_timed_run(ts, False, kw))
    best_pipe, best_sync = min(t_pipe), min(t_sync)
    speedup = best_sync / best_pipe

    out = {
        "workload": f"{pattern}-{len(ts)}",
        "n_tasks": len(ts), "pattern": pattern, "horizon": horizon,
        "path": "kernel" if use_kernel else "jnp",
        "pipelined_s": best_pipe, "sync_s": best_sync,
        "speedup": speedup,
        "tasks_per_s": len(ts) / best_pipe,
        "e_total": r_pipe.e_total, "violations": r_pipe.violations,
        "bit_identical": bit_identical, "scalar_parity": scalar_parity,
        "cache_stats": r_pipe.cache_stats,
    }
    if verbose:
        print(f"{pattern:8s} n={len(ts):7d} pipelined={best_pipe:6.2f}s "
              f"({len(ts) / best_pipe:9.0f} tasks/s) sync={best_sync:6.2f}s "
              f"speedup={speedup:4.2f}x bit_identical={bit_identical}"
              + (f" scalar_parity={scalar_parity}" if scalar else ""),
              flush=True)
    record(f"pipeline/{pattern}_{len(ts)}", best_pipe / len(ts) * 1e6,
           f"{len(ts) / best_pipe:.0f} tasks/s, {speedup:.2f}x vs sync")
    return out


def write_bench_json(cells, path: str = BENCH_JSON) -> None:
    """Mirror of ``BENCH_solver.json`` for the scheduling layer."""
    head = cells[0]
    payload = {
        "benchmark": "pipeline_scheduling",
        "cells": cells,
        "headline": {
            "pipeline_speedup": head["speedup"],
            "pipelined_tasks_per_s": head["tasks_per_s"],
            "e_total": head["e_total"],
            "bit_identical": all(c["bit_identical"] for c in cells),
        },
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {path}", flush=True)


def smoke(n_tasks: int, budget: float, min_speedup: float, use_kernel: bool,
          reps: int) -> Dict:
    """The CI tripwire: budgeted wall clock + pipeline speedup + bit-equal
    energy + scalar parity, recorded into ``BENCH_sched.json``."""
    out = run_cell(n_tasks, "uniform", use_kernel=use_kernel, reps=reps,
                   scalar=True)
    assert out["violations"] == 0, out
    if out["speedup"] < min_speedup:
        # Shared CI boxes jitter; one re-measure pools the samples before
        # declaring a regression (a real one fails both rounds).
        again = run_cell(n_tasks, "uniform", use_kernel=use_kernel,
                         reps=reps, scalar=False, verbose=False)
        out["pipelined_s"] = min(out["pipelined_s"], again["pipelined_s"])
        out["sync_s"] = min(out["sync_s"], again["sync_s"])
        out["speedup"] = out["sync_s"] / out["pipelined_s"]
        out["tasks_per_s"] = out["n_tasks"] / out["pipelined_s"]
    assert out["pipelined_s"] <= budget, (
        f"pipelined {n_tasks}-task simulation took {out['pipelined_s']:.1f}s "
        f"(> {budget:.0f}s budget)")
    assert out["speedup"] >= min_speedup, (
        f"pipelined path regressed: {out['speedup']:.2f}x < "
        f"{min_speedup:.1f}x over pipeline=False")
    write_bench_json([out])
    print(f"smoke OK: {out['pipelined_s']:.2f}s <= {budget:.0f}s, "
          f"{out['speedup']:.2f}x >= {min_speedup:.1f}x, bit-identical, "
          f"scalar parity", flush=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tasks", type=int, default=100000)
    ap.add_argument("--pattern", default="uniform",
                    choices=tasks.TRACE_PATTERNS)
    ap.add_argument("--horizon", type=int, default=None,
                    help="slots (default: the 1440-slot day)")
    ap.add_argument("--reps", type=int, default=5,
                    help="interleaved timing repeats per mode")
    ap.add_argument("--kernel", action="store_true",
                    help="route the DVFS solves through the Pallas kernel")
    ap.add_argument("--no-scalar", action="store_true",
                    help="skip the scalar-placement parity run")
    ap.add_argument("--smoke", action="store_true",
                    help="CI guard: speedup + budget + bit-equality gates, "
                         "writes BENCH_sched.json")
    ap.add_argument("--budget", type=float, default=60.0,
                    help="--smoke: wall-clock cap for the pipelined run")
    ap.add_argument("--min-speedup", type=float, default=1.5,
                    help="--smoke: required pipelined/sync speedup")
    args = ap.parse_args(argv)

    if args.smoke:
        smoke(args.tasks, args.budget, args.min_speedup, args.kernel,
              args.reps)
        return
    run_cell(args.tasks, args.pattern, use_kernel=args.kernel,
             horizon=args.horizon, seed=0, reps=args.reps,
             scalar=not args.no_scalar)


if __name__ == "__main__":
    main()
