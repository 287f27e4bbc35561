"""Readings that set the limits of ``bench/reference.py``.

    python3 bench/control.py --workload <name> --seeds 1 2 3 ... \
        [--control-seeds 1 2 3] [--requests k]

For each seed, the cell's first ``k`` window requests (default: as many as
a run checks) go through the program as a run sends them, and the
reference judges each schedule: the program's readings, the lower end of
each limit.  For each control seed the same requests go through the
program with its Algorithm-1 solver replaced by the reference's solver
computed in bfloat16, the precision below the float32 the solver states:
the control's readings, the upper end.  One JSON line per request and
side; nothing is timed.  Like a run, it needs a TPU unless ``--cpu``.

The control stands in for the jnp solver entry points
(``single_task.solve_with_deadline`` / ``solve_on_boundary``), which is
the solver the program runs by default.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_solver(boundary: bool):
    """The reference in bfloat16, speaking the program's solver interface."""
    import jax.numpy as jnp

    from bench import reference
    from repro.kernels.layout import DvfsSolution

    def solve(params, allowed, interval):
        p = dict(zip(reference.FIELDS, params.astuple()))
        box = {k: getattr(interval, k) for k in reference.BOX}
        *vals, feas = reference.solve(p, allowed, box, dtype=jnp.bfloat16,
                                      xp=jnp)
        v, fc, fm, t, pw, e = (x.astype(jnp.float32) for x in vals)
        if boundary:
            dp = jnp.ones_like(feas)
        else:
            dp = feas & (t >= jnp.asarray(allowed, jnp.float32)
                         * (1.0 - 2.0 ** -7))
        return DvfsSolution(v, fc, fm, t, pw, e, dp, feas)

    return solve


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--cpu", action="store_true",
                    help="allow a run without a TPU (tests)")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    from bench import harness, reference, traffic

    c = harness.cell(harness.load_json(harness.SPEC_FILE), args.workload)
    if not args.cpu:
        harness.check_devices(int(c["workload"]["chips"]))
    harness.enable_compile_cache()
    deploy, mix = c["deploy"], c["mix"]
    program = harness.Program(deploy, mix)
    k = args.requests or int(mix["checked"])
    lib = traffic.library()

    def readings(seed, side):
        for i in range(k):
            d = traffic.draw(mix, seed, traffic.WINDOW, i, lib)
            r = program(program.task_set(d))
            got = reference.check(
                d, harness.records(r),
                {"e_total": r.e_total, "violations": r.violations}, deploy,
                mix["entry"] == "online",
                traffic.rng_for(seed, traffic.CHECK, 1 + i))
            print(json.dumps({"workload": args.workload, "side": side,
                              "seed": seed, "request": i, **got,
                              "passed": reference.passed(got)}), flush=True)

    for seed in args.seeds:
        readings(seed, "program")
    if args.control_seeds:
        from repro.core import single_task, solver_cache

        # Rows the program solved above must not be served to the control.
        solver_cache.GLOBAL_CACHE.clear()
        saved = single_task.solve_with_deadline, single_task.solve_on_boundary
        single_task.solve_with_deadline = control_solver(False)
        single_task.solve_on_boundary = control_solver(True)
        try:
            for seed in args.control_seeds:
                readings(seed, "control")
        finally:
            single_task.solve_with_deadline, single_task.solve_on_boundary \
                = saved
    return 0


if __name__ == "__main__":
    sys.exit(main())
