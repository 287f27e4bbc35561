"""Offline schedulers at scale: 1k-100k-task batches through the shared
placement subsystem (``core/placement.py``).

``schedule_offline`` is a thin driver over the same placement core the
online simulator uses — the offline batch is the degenerate "one group at
t=0" case.  This harness

* generates batches with exactly ``--tasks`` tasks
  (``repro.core.tasks.generate_offline_n``);
* times the Algorithm-1 solve twice — the jitted jnp solver and the Pallas
  kernel path — separately from the packing, by precomputing configs with
  ``scheduling.configure_all`` and injecting them via
  ``schedule_offline(cfgs=...)``;
* compares the vectorized placement path (``placement="vector"``, the
  default: worst-fit heap finisher, pooled probes, bulk fresh-pair
  opens) against the per-task scalar reference loop
  (``placement="scalar"``) — bit-identical by construction, asserted to
  1e-9 rel (it actually matches exactly);
* reports the §5 theoretical bound (``core/bounds.py``) next to every
  achieved energy, so each row shows achieved-vs-bound;
* emits a JSON + markdown report under ``--out`` for the full sweep
  (n × algorithm × class mix).

``--smoke`` is the CI guard: one 10k-task EDL batch must beat the scalar
loop by ``--min-speedup`` (default 2x, conservative for shared CI
hardware; quiet machines measure ~3x at 100k) inside a ``--budget``
wall-clock cap, with bit-equal energy.

    PYTHONPATH=src python -m benchmarks.offline_scale --tasks 10000 --smoke
    PYTHONPATH=src python -m benchmarks.offline_scale --full \\
        --out results/offline_scale
"""

from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List, Optional, Tuple

from benchmarks.common import record
from repro.core import bounds, machines, scheduling, tasks

ALGOS = ("edl", "edf-wf", "edf-bf", "lpt-ff")

#: class-mix name -> spec accepted by ``schedule_offline(classes=...)``
MIXES: Dict[str, Optional[Tuple[str, ...]]] = {
    "reference": None,
    "het2": ("gtx-1080ti", "tpu-v5e"),
}


def _solves(ts, mcs, time_kernel: bool):
    """Time the Algorithm-1 solve (jnp path, and optionally the Pallas
    kernel path) once for a (batch, mix); the configs feed every
    algorithm's packing run via ``schedule_offline(cfgs=...)``.

    ``dedup=False`` keeps the timings honest: they measure the solver
    itself, not hits on the process-wide solve cache (which
    ``benchmarks/solver_throughput.py`` measures separately).
    """
    t0 = time.perf_counter()
    cfgs = scheduling.configure_all(ts, True, mcs, dedup=False)
    t_solve = time.perf_counter() - t0
    t_solve_kernel = None
    if time_kernel:
        scheduling.configure_all(ts, True, mcs, use_kernel=True,
                                 dedup=False)  # warm
        t0 = time.perf_counter()
        scheduling.configure_all(ts, True, mcs, use_kernel=True, dedup=False)
        t_solve_kernel = time.perf_counter() - t0
    return cfgs, t_solve, t_solve_kernel


def run_one(n_tasks: int, algorithm: str = "edl", mix: str = "reference",
            l: int = 4, theta: float = 0.9, seed: int = 0,
            scalar: bool = True, time_kernel: bool = True,
            verbose: bool = True, _shared=None) -> Dict:
    """One batch end to end; returns timings, energies, bound and speedup.

    ``_shared`` (from :func:`sweep`) injects ``(ts, cfgs, t_solve,
    t_solve_kernel, bound)`` so the solve and bound — which depend only on
    the batch and the mix, not the algorithm — are computed once per
    (n, mix) cell.
    """
    classes = MIXES[mix]
    mcs = machines.resolve_classes(classes)
    if _shared is None:
        ts = tasks.generate_offline_n(n_tasks, seed=seed,
                                      library=tasks.app_library())
        cfgs, t_solve, t_solve_kernel = _solves(ts, mcs, time_kernel)
        b = bounds.theoretical_bound(ts, classes=mcs)
    else:
        ts, cfgs, t_solve, t_solve_kernel, b = _shared

    # ``bound=False``: the bound is computed once above; the timed runs
    # measure the packing hot path only.
    kw = dict(l=l, theta=theta, algorithm=algorithm, cfgs=cfgs,
              classes=classes, bound=False)
    # Warm the deferred-readjustment solver compile out of the timings so
    # the vector/scalar ratio is compile-free.
    scheduling.schedule_offline(ts, placement="vector", **kw)
    t0 = time.perf_counter()
    r_vec = scheduling.schedule_offline(ts, placement="vector", **kw)
    t_vec = time.perf_counter() - t0

    out = {
        "n_tasks": len(ts), "algorithm": algorithm, "mix": mix,
        "solve_s": t_solve, "solve_kernel_s": t_solve_kernel,
        "vector_s": t_vec, "vector_tasks_per_s": len(ts) / t_vec,
        "e_total": r_vec.e_total, "e_idle": r_vec.e_idle,
        "e_bound": b.e_bound, "savings_ceiling": b.savings_ceiling,
        "bound_gap": r_vec.e_total / b.e_bound - 1.0,
        "violations": r_vec.violations, "n_pairs": r_vec.n_pairs,
    }
    if scalar:
        t0 = time.perf_counter()
        r_sca = scheduling.schedule_offline(ts, placement="scalar", **kw)
        t_sca = time.perf_counter() - t0
        rel = abs(r_vec.e_total - r_sca.e_total) / max(abs(r_sca.e_total),
                                                       1e-12)
        out.update({"scalar_s": t_sca, "speedup": t_sca / t_vec,
                    "e_total_rel_err": rel})
        assert rel <= 1e-9, (
            f"vector/scalar e_total diverged: {r_vec.e_total!r} vs "
            f"{r_sca.e_total!r}")
    if verbose:
        line = (f"{algorithm:6s} {mix:9s} n={len(ts):7d} "
                f"solve={t_solve:5.2f}s vector={t_vec:5.2f}s "
                f"gap_vs_bound={out['bound_gap'] * 100:5.1f}%")
        if scalar:
            line += (f" scalar={out['scalar_s']:5.2f}s "
                     f"speedup={out['speedup']:4.1f}x "
                     f"rel_err={out['e_total_rel_err']:.1e}")
        print(line, flush=True)
    record(f"offline_scale/{algorithm}_{mix}_{len(ts)}",
           t_vec / len(ts) * 1e6,
           f"{len(ts) / t_vec:.0f} tasks/s, gap {out['bound_gap']:.3f}"
           + (f", {out['speedup']:.1f}x vs scalar" if scalar else ""))
    return out


def smoke(n_tasks: int, budget: float, min_speedup: float) -> Dict:
    """The CI tripwire: budgeted wall clock + speedup + bit-equal energy."""
    out = run_one(n_tasks, "edl", scalar=True, time_kernel=False)
    assert out["violations"] == 0, out
    assert out["vector_s"] <= budget, (
        f"vectorized {n_tasks}-task offline EDL took {out['vector_s']:.1f}s "
        f"(> {budget:.0f}s budget)")
    assert out["speedup"] >= min_speedup, (
        f"vectorized offline placement regressed: {out['speedup']:.1f}x < "
        f"{min_speedup:.1f}x over the scalar loop")
    assert out["bound_gap"] >= 0.0, out["bound_gap"]
    print(f"smoke OK: {out['vector_s']:.2f}s <= {budget:.0f}s, "
          f"{out['speedup']:.1f}x >= {min_speedup:.1f}x, "
          f"rel_err={out['e_total_rel_err']:.1e}, "
          f"gap_vs_bound={out['bound_gap'] * 100:.1f}%", flush=True)
    return out


def _write_report(rows: List[Dict], out_prefix: str):
    os.makedirs(os.path.dirname(out_prefix) or ".", exist_ok=True)
    with open(out_prefix + ".json", "w") as f:
        json.dump(rows, f, indent=2)
    cols = ("n_tasks", "algorithm", "mix", "solve_s", "solve_kernel_s",
            "scalar_s", "vector_s", "speedup", "e_total", "e_bound",
            "bound_gap", "violations")
    lines = ["# Offline placement at scale",
             "",
             "`e_bound` is the §5 theoretical lower bound "
             "(`core/bounds.py`); `bound_gap` = e_total / e_bound - 1.",
             "",
             "| " + " | ".join(cols) + " |",
             "|" + "---|" * len(cols)]
    for r in rows:
        cells = []
        for c in cols:
            v = r.get(c)
            if v is None:
                cells.append("-")
            elif isinstance(v, float):
                cells.append(f"{v:.4g}")
            else:
                cells.append(str(v))
        lines.append("| " + " | ".join(cells) + " |")
    with open(out_prefix + ".md", "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"wrote {out_prefix}.json and {out_prefix}.md", flush=True)


def sweep(ns, algorithms=ALGOS, mixes=tuple(MIXES), scalar: bool = True,
          time_kernel: bool = True, seed: int = 0,
          out: Optional[str] = None, verbose: bool = True) -> List[Dict]:
    lib = tasks.app_library()
    rows = []
    for n in ns:
        ts = tasks.generate_offline_n(int(n), seed=seed, library=lib)
        for mix in mixes:
            mcs = machines.resolve_classes(MIXES[mix])
            cfgs, t_solve, t_kernel = _solves(ts, mcs, time_kernel)
            b = bounds.theoretical_bound(ts, classes=mcs)
            shared = (ts, cfgs, t_solve, t_kernel, b)
            for alg in algorithms:
                rows.append(run_one(int(n), alg, mix, scalar=scalar,
                                    verbose=verbose, _shared=shared))
    if out:
        _write_report(rows, out)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tasks", type=int, nargs="*", default=None,
                    help="batch sizes to sweep (default 1k 10k; --full adds "
                         "100k); with --smoke, the single smoke batch size "
                         "(default 10k)")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale axes: adds the 100k-task batch")
    ap.add_argument("--algorithms", nargs="*", default=list(ALGOS),
                    choices=ALGOS)
    ap.add_argument("--mixes", nargs="*", default=list(MIXES),
                    choices=sorted(MIXES))
    ap.add_argument("--no-scalar", action="store_true",
                    help="skip the scalar reference run")
    ap.add_argument("--out", default="results/offline_scale",
                    help="JSON/markdown report path prefix")
    ap.add_argument("--smoke", action="store_true",
                    help="CI guard: budgeted wall clock + min speedup")
    ap.add_argument("--budget", type=float, default=60.0,
                    help="--smoke wall-clock cap for the vectorized run (s)")
    ap.add_argument("--min-speedup", type=float, default=2.0,
                    help="--smoke minimum vector/scalar speedup")
    args = ap.parse_args(argv)

    if args.smoke:
        smoke(args.tasks[0] if args.tasks else 10000, args.budget,
              args.min_speedup)
        return

    ns = list(args.tasks) if args.tasks else [1000, 10000]
    if args.full and 100000 not in ns:
        ns.append(100000)
    sweep(ns, tuple(args.algorithms), tuple(args.mixes),
          scalar=not args.no_scalar, out=args.out)


if __name__ == "__main__":
    main()
