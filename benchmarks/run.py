"""Benchmark entry point: one section per paper table/figure plus the
roofline deliverable.  Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--full]
"""

import argparse


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale axes (hours); default is CI-sized")
    ap.add_argument("--skip-roofline", action="store_true")
    args = ap.parse_args(argv)

    from repro.kernels import ops
    ops.enable_compile_cache()
    print("name,us_per_call,derived")

    print("# --- Fig 4: single-task DVFS optimum (S5.2) ---", flush=True)
    from benchmarks import single_task_dvfs
    single_task_dvfs.run(verbose=False)

    print("# --- Figs 5-8: offline scheduling (S5.3) ---", flush=True)
    from benchmarks import offline_scheduling
    offline_scheduling.main(["--full"] if args.full else [])

    print("# --- Fig 9, 12-13: theta sweeps (S5.3.3, S5.4.3) ---", flush=True)
    from benchmarks import theta_sweep
    theta_sweep.main(["--full"] if args.full else [])

    print("# --- Figs 10-11: online scheduling (S5.4) ---", flush=True)
    from benchmarks import online_scheduling
    online_scheduling.main(["--full"] if args.full else [])

    print("# --- S5 scenario grid (intervals x class mixes) ---", flush=True)
    from benchmarks import scenario_sweep
    scenario_sweep.run(utils=(0.2,), rhos=(2,), delta_scales=(1.0,),
                       verbose=False)

    print("# --- Phi cost (S2.1 low-overhead claim) ---", flush=True)
    from benchmarks import scheduler_throughput
    scheduler_throughput.run(verbose=False)

    print("# --- Solver throughput layer (dedup/cache + refined kernel) ---",
          flush=True)
    from benchmarks import solver_throughput
    solver_throughput.run(50000 if args.full else 10000, verbose=False)

    print("# --- Online scale (event-driven engine) ---", flush=True)
    from benchmarks import online_scale
    online_scale.run_one(100000 if args.full else 20000, "uniform",
                         verbose=False)

    print("# --- Pipelined online scheduling (prefetch + incremental "
          "pools) ---", flush=True)
    from benchmarks import pipeline
    pipeline.run_cell(100000 if args.full else 20000, "uniform",
                      reps=3 if args.full else 1, scalar=False,
                      verbose=False)

    print("# --- Offline scale (shared placement subsystem) ---", flush=True)
    from benchmarks import offline_scale
    offline_scale.run_one(100000 if args.full else 20000, "edl",
                          time_kernel=False, verbose=False)

    print("# --- Fault tolerance (failure rate x trace shape) ---",
          flush=True)
    from benchmarks import fault_tolerance
    fault_tolerance.sweep(20000 if args.full else 3000, verbose=False)

    if not args.skip_roofline:
        print("# --- Roofline (deliverable g; from dry-run JSONs) ---",
              flush=True)
        from benchmarks import roofline
        try:
            roofline.run(verbose=False)
        except Exception as e:  # dry-run not executed yet
            print(f"roofline/skipped,0,{type(e).__name__}: {e}")


if __name__ == "__main__":
    main()
