"""Scenario-sweep harness: the paper's §5 trends across every scenario axis.

Sweeps the grid

    U_J (task-set utilization) x rho (DRS idle threshold) x
    Delta (turn-on overhead scale) x scaling interval x machine-class mix

and emits a JSON + markdown report under ``--out``.  The two *interval
settings* bundle the paper's two calibrations (§5.2):

* ``wide``   — the analytic interval (:data:`repro.core.dvfs.WIDE`) with the
  published shrunk-static fit ranges: single-task saving anchor ~36.4%
  (Fig. 4);
* ``narrow`` — the realistic GTX-1080Ti interval
  (:data:`repro.core.dvfs.NARROW`) with the measured whole-system static
  share (``tasks.REALISTIC_P0``): anchor ~4.3%.

Each cell reports the offline EDL saving vs the no-DVFS baseline (Figs. 5-8
axis) and the online EDL total-energy reduction (Figs. 10-13 axis), per
class mix — the reference homogeneous mix plus heterogeneous mixes from the
:mod:`repro.core.machines` registry.  rho and Delta only act through the
online DRS, so they are swept on the online half of the grid only.

    PYTHONPATH=src python -m benchmarks.scenario_sweep [--full] [--kernel] \
        [--out results/scenario_sweep]

CI default is a minutes-sized grid (2 mixes x 2 intervals x 2 rho x 2
Delta); ``--full`` widens every axis toward the paper's scale.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
from typing import Dict

import numpy as np

from benchmarks.common import record
from repro.core import (cluster as cl, dvfs, machines, online, scheduling,
                        single_task, solver_cache, tasks)

#: interval setting -> (ScalingInterval, app-library static-share range,
#: paper anchor for the mean single-task saving)
INTERVAL_SETTINGS = {
    "wide": (dvfs.WIDE, (0.20, 0.41), 0.364),
    "narrow": (dvfs.NARROW, tasks.REALISTIC_P0, 0.043),
}

DEFAULT_MIXES = (
    ("gtx-1080ti",),
    ("gtx-1080ti", "tpu-v5e"),
)
FULL_MIXES = DEFAULT_MIXES + (("gtx-1080ti", "tpu-v5e", "v100-sxm2"),
                              ("tesla-t4", "tesla-p100", "v100-sxm2"))


def _scaled_mix(names, delta_scale: float):
    """The mix with every class's turn-on overhead scaled by ``delta_scale``
    (the Delta axis of the grid)."""
    mcs = machines.get_classes(names)
    if delta_scale == 1.0:
        return mcs
    return tuple(dataclasses.replace(mc, delta_on=mc.delta_on * delta_scale)
                 for mc in mcs)


def single_task_anchor(library, interval) -> float:
    """Mean unconstrained single-task saving on the reference class — the
    Fig. 4 number every scheduling trend hangs off."""
    sol = single_task.solve_unconstrained(library, interval)
    saving = 1.0 - np.asarray(sol.energy) / np.asarray(library.default_energy())
    return float(np.mean(saving))


def run(groups: int = 1, utils=(0.2, 0.4), rhos=(1, 2),
        delta_scales=(0.5, 1.0), intervals=("wide", "narrow"),
        mixes=DEFAULT_MIXES, theta: float = 0.9,
        u_off: float = 0.02, u_on: float = 0.05, horizon: int = 200,
        l: int = 2, use_kernel: bool = False, verbose: bool = True) -> Dict:
    report: Dict = {
        "meta": dict(groups=groups, utils=list(utils), rhos=list(rhos),
                     delta_scales=list(delta_scales),
                     intervals=list(intervals),
                     mixes=["+".join(m) for m in mixes], theta=theta,
                     u_off=u_off, u_on=u_on, horizon=horizon, l=l,
                     use_kernel=use_kernel),
        "anchors": {},
        "offline": [],
        "online": [],
    }
    # The rho x Delta (and seed-group) cells of one (interval, mix) re-solve
    # identical (params, allowed) rows; the process-wide solve cache serves
    # them after the first cell.  Snapshot the lifetime counters so the
    # hit-rate below is this sweep's own cross-cell reuse.
    cache_base = solver_cache.GLOBAL_CACHE.stats()

    for iv_name in intervals:
        interval, p0_frac, paper_anchor = INTERVAL_SETTINGS[iv_name]
        lib = tasks.app_library(p0_frac=p0_frac)
        anchor = single_task_anchor(lib, interval)
        report["anchors"][iv_name] = {
            "single_task_saving": anchor, "paper": paper_anchor}
        if verbose:
            print(f"[{iv_name}] single-task anchor saving: {anchor:.3f} "
                  f"(paper ~{paper_anchor})")

        for mix in mixes:
            mix_name = "+".join(mix)
            mcs = machines.get_classes(mix)

            # ---- offline half: U_J axis (rho/Delta do not act offline).
            for u in utils:
                savings, viols, pairs = [], 0, []
                for seed in range(groups):
                    ts = tasks.generate_offline(u, seed=seed, library=lib)
                    base = cl.baseline_energy(ts)
                    # bound=False across the grid: e_bound only depends on
                    # (task_set, classes, interval), not the swept knobs.
                    r = scheduling.schedule_offline(
                        ts, l=l, theta=theta, algorithm="edl",
                        interval=interval, classes=mcs,
                        use_kernel=use_kernel, bound=False)
                    savings.append(1 - r.e_total / base)
                    viols += r.violations
                    pairs.append(r.n_pairs)
                row = dict(interval=iv_name, mix=mix_name, u=u,
                           saving=float(np.mean(savings)), violations=viols,
                           pairs=float(np.mean(pairs)))
                report["offline"].append(row)
                if verbose:
                    print(f"  offline {mix_name:28s} U={u:<4} "
                          f"saving={row['saving']:+.3f} viol={viols}")

            # ---- online half: rho x Delta axes.
            for rho in rhos:
                for ds in delta_scales:
                    mcs_d = _scaled_mix(mix, ds)
                    reds, viols = [], 0
                    for seed in range(groups):
                        ts = tasks.generate_online(u_off, u_on, seed=seed,
                                                   library=lib,
                                                   horizon=horizon)
                        rb = online.schedule_online(
                            ts, l=l, theta=1.0, algorithm="edl",
                            use_dvfs=False, rho=rho, classes=mcs_d,
                            bound=False)
                        rd = online.schedule_online(
                            ts, l=l, theta=theta, algorithm="edl",
                            use_dvfs=True, interval=interval, rho=rho,
                            classes=mcs_d, use_kernel=use_kernel,
                            bound=False)
                        reds.append(1 - rd.e_total / rb.e_total)
                        viols += rd.violations
                    row = dict(interval=iv_name, mix=mix_name, rho=rho,
                               delta_scale=ds,
                               reduction=float(np.mean(reds)),
                               violations=viols)
                    report["online"].append(row)
                    if verbose:
                        print(f"  online  {mix_name:28s} rho={rho} "
                              f"Deltax{ds:<4} reduction="
                              f"{row['reduction']:+.3f} viol={viols}")

    for iv_name in intervals:
        a = report["anchors"][iv_name]
        record(f"scenario/{iv_name}_anchor", 0.0,
               f"{a['single_task_saving']:.4f} (paper ~{a['paper']})")
    now = solver_cache.GLOBAL_CACHE.stats()
    hits = now["hits_total"] - cache_base["hits_total"]
    misses = now["misses_total"] - cache_base["misses_total"]
    stats = {"hits": hits, "misses": misses,
             "hit_rate": hits / (hits + misses) if hits + misses else 0.0}
    report["meta"]["solve_cache"] = stats
    record("scenario/solve_cache", 0.0,
           f"hit_rate {stats['hit_rate']:.3f} ({stats['hits']} hits / "
           f"{stats['misses']} misses)")
    if verbose:
        print(f"solve-cache cross-cell reuse: {stats['hit_rate']:.1%} "
              f"({stats['hits']} hits, {stats['misses']} misses)")
    return report


def to_markdown(report: Dict) -> str:
    """Render the sweep report as a standalone markdown document."""
    m = report["meta"]
    lines = [
        "# Scenario sweep report",
        "",
        f"Grid: U_J={m['utils']} x rho={m['rhos']} x "
        f"Delta-scale={m['delta_scales']} x intervals={m['intervals']} x "
        f"mixes={m['mixes']} (theta={m['theta']}, l={m['l']}, "
        f"{m['groups']} seed group(s), kernel={m['use_kernel']})",
        "",
        "## Single-task anchors (paper Fig. 4 / §5.2)",
        "",
        "| interval | mean saving | paper |",
        "|---|---|---|",
    ]
    if "solve_cache" in m:
        s = m["solve_cache"]
        lines[4:4] = [f"Solve-cache cross-cell reuse: {s['hit_rate']:.1%} "
                      f"({s['hits']} hits / {s['misses']} misses).", ""]
    for iv, a in report["anchors"].items():
        lines.append(f"| {iv} | {a['single_task_saving']:.1%} "
                     f"| ~{a['paper']:.1%} |")
    lines += [
        "",
        "## Offline EDL saving vs no-DVFS baseline (Figs. 5-8 axis)",
        "",
        "| interval | class mix | U_J | saving | violations |",
        "|---|---|---|---|---|",
    ]
    for r in report["offline"]:
        lines.append(f"| {r['interval']} | {r['mix']} | {r['u']} "
                     f"| {r['saving']:+.1%} | {r['violations']} |")
    lines += [
        "",
        "## Online EDL total-energy reduction (Figs. 10-13 axis)",
        "",
        "| interval | class mix | rho | Delta scale | reduction "
        "| violations |",
        "|---|---|---|---|---|---|",
    ]
    for r in report["online"]:
        lines.append(f"| {r['interval']} | {r['mix']} | {r['rho']} "
                     f"| x{r['delta_scale']} | {r['reduction']:+.1%} "
                     f"| {r['violations']} |")
    lines.append("")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--full", action="store_true",
                    help="paper-scale axes (slow); default is CI-sized")
    ap.add_argument("--kernel", action="store_true",
                    help="route every DVFS solve through the Pallas kernel")
    ap.add_argument("--theta", type=float, default=0.9)
    ap.add_argument("--out", default="results/scenario_sweep",
                    help="directory for scenario_sweep.{json,md}")
    args = ap.parse_args(argv)

    if args.full:
        report = run(groups=5, utils=(0.2, 0.4, 0.8, 1.6),
                     rhos=(1, 2, 4), delta_scales=(0.5, 1.0, 2.0),
                     mixes=FULL_MIXES, theta=args.theta,
                     u_off=0.4, u_on=1.6, horizon=1440,
                     use_kernel=args.kernel)
    else:
        report = run(theta=args.theta, use_kernel=args.kernel)

    os.makedirs(args.out, exist_ok=True)
    jpath = os.path.join(args.out, "scenario_sweep.json")
    mpath = os.path.join(args.out, "scenario_sweep.md")
    with open(jpath, "w") as f:
        json.dump(report, f, indent=2)
    with open(mpath, "w") as f:
        f.write(to_markdown(report))
    print(f"report: {jpath} + {mpath}")


if __name__ == "__main__":
    main()
