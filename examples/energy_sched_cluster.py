"""Flagship example: the paper's technique as a first-class framework
feature — DVFS-aware, deadline-constrained scheduling of a DAY of LM
training/serving jobs on a TPU fleet.

The pipeline (DESIGN.md §2-3):

1. Each job is N steps of an (architecture x shape) cell; its DVFS model
   parameters are derived from the ROOFLINE ANALYSIS of the compiled
   dry-run (no profiling pass):
       delta := T_compute / (T_compute + T_memory)   (core-freq sensitivity)
       t0    >= collective share of the step          (freq-insensitive)
2. The resulting task set feeds the SAME online EDL θ-readjustment
   scheduler the paper evaluates on GPU benchmark traces.
3. Output: fleet energy saving vs the no-DVFS baseline, per-job settings.

Homogeneous fleet (the default)::

    PYTHONPATH=src python examples/energy_sched_cluster.py \
        [--dryrun-dir results/dryrun] [--jobs 400]

Heterogeneous fleet — schedule the same day across a machine-class mix
from the ``repro.core.machines`` registry; the scheduler solves each job's
DVFS optimum on every class and sends it to the min-energy feasible one
(per-class assignment counts are printed at the end)::

    PYTHONPATH=src python examples/energy_sched_cluster.py \
        --classes gtx-1080ti,tpu-v5e,v100-sxm2

or the three GPU generations of the Alibaba PAI 2020 fleet (T4, P100 and
V100 pairs, each with its single memory clock)::

    PYTHONPATH=src python examples/energy_sched_cluster.py \
        --classes tesla-t4,tesla-p100,v100-sxm2

Falls back to a representative synthetic roofline table if the dry-run
JSONs are absent.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core import online, tasks
from repro.core.jobs import RooflineTerms, jobs_to_task_set, synth_job_stream
from repro.kernels import ops

FALLBACK = {
    "qwen2-72b/train_4k": RooflineTerms("qwen2-72b", "train_4k",
                                        3.2, 1.1, 0.6),
    "qwen2-72b/decode_32k": RooflineTerms("qwen2-72b", "decode_32k",
                                          0.02, 0.35, 0.04),
    "mamba2-370m/train_4k": RooflineTerms("mamba2-370m", "train_4k",
                                          0.5, 0.4, 0.05),
    "qwen3-moe-30b-a3b/train_4k": RooflineTerms("qwen3-moe-30b-a3b",
                                                "train_4k", 0.9, 0.7, 0.5),
    "recurrentgemma-2b/long_500k": RooflineTerms("recurrentgemma-2b",
                                                 "long_500k", 0.01, 0.2,
                                                 0.01),
}


def load_roofline(dir_: str):
    try:
        from benchmarks.roofline import load
        rows = load(dir_, mesh="single")
    except Exception:
        rows = []
    if not rows:
        return FALLBACK
    return {f"{r['arch']}/{r['shape']}": RooflineTerms(
        r["arch"], r["shape"], r["compute_s"], r["memory_s"],
        r["collective_s"]) for r in rows}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun-dir", default="results/dryrun")
    ap.add_argument("--jobs", type=int, default=400)
    ap.add_argument("--l", type=int, default=4,
                    help="accelerator slices per power domain")
    ap.add_argument("--theta", type=float, default=0.9)
    ap.add_argument("--horizon", type=int, default=720)
    ap.add_argument("--classes", default=None,
                    help="comma-separated machine-class mix from the "
                         "repro.core.machines registry, e.g. "
                         "gtx-1080ti,tpu-v5e (default: homogeneous)")
    args = ap.parse_args()
    ops.enable_compile_cache()
    mix = args.classes.split(",") if args.classes else None

    terms = load_roofline(args.dryrun_dir)
    print(f"[fleet] roofline table: {len(terms)} cells "
          f"({'dry-run' if terms is not FALLBACK else 'fallback'})")
    jobs = synth_job_stream(terms, n_jobs=args.jobs, horizon=args.horizon,
                            seed=0)
    ts = jobs_to_task_set(jobs)
    deltas = np.asarray(ts.params.delta)
    print(f"[fleet] {len(ts)} jobs; delta range "
          f"[{deltas.min():.2f}, {deltas.max():.2f}] "
          f"(memory-bound decode ... compute-bound train)")

    if mix:
        print(f"[fleet] heterogeneous mix: {', '.join(mix)}")
    r_dvfs = online.schedule_online(ts, l=args.l, theta=args.theta,
                                    algorithm="edl", use_dvfs=True,
                                    classes=mix)
    r_base = online.schedule_online(ts, l=args.l, theta=1.0,
                                    algorithm="edl", use_dvfs=False,
                                    classes=mix)
    print(f"[fleet] no-DVFS  : E_run={r_base.e_run:.3e} "
          f"E_idle={r_base.e_idle:.3e} E_ovh={r_base.e_overhead:.3e} "
          f"(pairs={r_base.n_pairs})")
    print(f"[fleet] DVFS+EDL : E_run={r_dvfs.e_run:.3e} "
          f"E_idle={r_dvfs.e_idle:.3e} E_ovh={r_dvfs.e_overhead:.3e} "
          f"(pairs={r_dvfs.n_pairs}, violations={r_dvfs.violations})")
    print(f"[fleet] runtime-energy saving: "
          f"{1 - r_dvfs.e_run / r_base.e_run:.1%}")
    print(f"[fleet] total-energy saving:   "
          f"{1 - r_dvfs.e_total / r_base.e_total:.1%}")

    # per-kind settings summary: what the scheduler actually dialed in
    by_cell = {}
    for a in r_dvfs.assignments:
        j = jobs[a.task]
        by_cell.setdefault(f"{j.arch}/{j.shape}", []).append(
            (a.fc, a.fm, a.v))
    print("[fleet] mean chosen (fc, fm) per cell kind:")
    for cell, rows in sorted(by_cell.items()):
        rows = np.asarray(rows)
        print(f"    {cell:34s} fc={rows[:,0].mean():.2f} "
              f"fm={rows[:,1].mean():.2f} (n={len(rows)})")

    if mix:
        counts = np.bincount([a.class_id for a in r_dvfs.assignments],
                             minlength=len(mix))
        print("[fleet] jobs per machine class:")
        for name, cnt in zip(mix, counts):
            print(f"    {name:20s} {int(cnt)}")


if __name__ == "__main__":
    main()
