"""Paper §5.3.3 / Fig. 9 (offline) and §5.4.3 / Figs. 12-13 (online):
the θ-readjustment sweep.

θ < 1 trades runtime energy for idle energy; the paper's findings to
reproduce: (i) θ matters only for l > 1; (ii) larger l leans harder on the
readjustment; (iii) θ = 0.8 generally minimizes total energy (except l=1);
(iv) the online EDL conserves 30-33% total energy with a good θ.
"""

from __future__ import annotations

import argparse
from typing import Dict

import numpy as np

from benchmarks.common import record
from repro.core import cluster as cl, online, scheduling, solver_cache, tasks

THETAS = (0.8, 0.85, 0.9, 0.95, 1.0)


def _report_cache(side: str, base: Dict, verbose: bool) -> Dict:
    """Record the sweep's cross-cell solve reuse: every (l, θ) cell of one
    seed shares the same Algorithm-1 rows, so after the first cell the
    process-wide solve cache serves them all (θ only changes the deferred
    readjustment windows).  Counted as the lifetime-counter delta since
    ``base``: the sweep's own reuse, whatever ran before it."""
    now = solver_cache.GLOBAL_CACHE.stats()
    hits = now["hits_total"] - base["hits_total"]
    misses = now["misses_total"] - base["misses_total"]
    stats = {"hits": hits, "misses": misses,
             "hit_rate": hits / (hits + misses) if hits + misses else 0.0}
    record(f"theta/{side}_solve_cache", 0.0,
           f"hit_rate {stats['hit_rate']:.3f} ({stats['hits']} hits / "
           f"{stats['misses']} misses)")
    if verbose:
        print(f"{side} solve-cache cross-cell reuse: "
              f"{stats['hit_rate']:.1%} ({stats['hits']} hits, "
              f"{stats['misses']} misses)")
    return stats


def run_offline(groups=3, util=0.4, ls=(1, 4, 16), verbose=True) -> Dict:
    lib = tasks.app_library()
    cache_base = solver_cache.GLOBAL_CACHE.stats()
    out = {}
    for seed in range(groups):
        ts = tasks.generate_offline(util, seed=seed, library=lib)
        base = cl.baseline_energy(ts)
        for l in ls:
            for th in THETAS:
                # bound=False: e_bound is (task_set, classes)-invariant, so
                # re-solving it per swept (l, theta) point is pure overhead.
                r = scheduling.schedule_offline(ts, l=l, theta=th,
                                                algorithm="edl", bound=False)
                out.setdefault((l, th), []).append(1 - r.e_total / base)
    summary = {f"l{l}/theta{th}": float(np.mean(v))
               for (l, th), v in sorted(out.items())}
    if verbose:
        for k, v in summary.items():
            print(f"offline {k:18s} saving={v:+.4f}")
    for l in ls:
        best = max(THETAS, key=lambda th: summary[f"l{l}/theta{th}"])
        record(f"theta/offline_best_l{l}", 0.0, f"theta={best}")
    summary["solve_cache"] = _report_cache("offline", cache_base, verbose)
    return summary


def run_online(groups=2, u_off=0.1, u_on=0.4, horizon=400, ls=(1, 4, 16),
               verbose=True) -> Dict:
    lib = tasks.app_library()
    cache_base = solver_cache.GLOBAL_CACHE.stats()
    out = {}
    base_tot = {}
    for seed in range(groups):
        ts = tasks.generate_online(u_off, u_on, seed=seed, library=lib,
                                   horizon=horizon)
        for l in ls:
            rb = online.schedule_online(ts, l=l, theta=1.0, algorithm="edl",
                                        use_dvfs=False, bound=False)
            base_tot.setdefault(l, []).append(rb.e_total)
            for th in THETAS:
                r = online.schedule_online(ts, l=l, theta=th,
                                           algorithm="edl", use_dvfs=True,
                                           bound=False)
                out.setdefault((l, th), []).append(
                    (r.e_run, r.e_idle, r.e_overhead, r.e_total))
    summary = {}
    for (l, th), rows in sorted(out.items()):
        rows = np.asarray(rows)
        summary[f"l{l}/theta{th}"] = {
            "e_run": float(rows[:, 0].mean()),
            "e_idle": float(rows[:, 1].mean()),
            "e_overhead": float(rows[:, 2].mean()),
            "reduction_vs_baseline": float(
                1 - rows[:, 3].mean() / np.mean(base_tot[l])),
        }
        if verbose:
            s = summary[f"l{l}/theta{th}"]
            print(f"online l{l} theta{th}: run={s['e_run']:.3e} "
                  f"idle={s['e_idle']:.3e} total_reduction="
                  f"{s['reduction_vs_baseline']:+.4f}")
    for l in ls:
        reds = {th: summary[f"l{l}/theta{th}"]["reduction_vs_baseline"]
                for th in THETAS}
        best = max(reds, key=reds.get)
        record(f"theta/online_reduction_l{l}", 0.0,
               f"best_theta={best} reduction={reds[best]:.4f} "
               f"(paper 0.30-0.33)")
    summary["solve_cache"] = _report_cache("online", cache_base, verbose)
    return summary


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args(argv)
    if args.full:
        run_offline(groups=20, ls=(1, 2, 4, 8, 16))
        run_online(groups=5, u_off=0.4, u_on=1.6, horizon=1440,
                   ls=(1, 2, 4, 8, 16))
    else:
        run_offline()
        run_online()


if __name__ == "__main__":
    main()
