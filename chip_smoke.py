"""Bring-up smoke of the scheduler's main path on a TPU.

Drives ``online.schedule_online`` and ``scheduling.schedule_offline`` on a
100k-task day, with the Algorithm-1 DVFS solves on the chip, and checks the
results: the Pallas kernel against the ``kernels/ref.py`` oracle run on the
host CPU, the pipelined driver against the synchronous one, the kernel
path against the jnp path, and the schedule invariants (no deadline
violations, Eq. 7 energy conservation, one live record per task).

    python3 chip_smoke.py              # one chip: kernel, online, offline
    python3 chip_smoke.py --chips 4    # four chips: the sharded solve only

Wall times printed here are smoke timings that include compilation (cold
when the persistent compile cache was empty at start, as printed); they are
not benchmark numbers.  Any failed check exits non-zero before the last
line, which is a JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

N_TASKS = 100_000
SEED = 0
MIX = ("gtx-1080ti", "tpu-v5e")
#: The online day as ``benchmarks/pipeline.py`` runs it.
DAY = dict(l=4, theta=0.9, algorithm="edl", placement="vector", bound=False)
#: Solve-path agreement of schedule-level ``e_total``: solve-level error is
#: ~2e-7, but tiny finish-time differences flip packing decisions.
PATH_REL_TOL = 1e-3
#: Kernel vs ``kernels/ref.py`` oracle, max relative energy error, per
#: class block.  The (64, 64) grid holds 1e-5 on the WIDE and NARROW boxes
#: (the kernel tests) and on the gtx-1080ti block, but on the tpu-v5e box
#: the optimum sits on the ``V = v_min`` kink, between two fine-grid
#: points: 1.895892455650028e-05 on the CPU in interpret mode, with the
#: gather-based kernel this one replaced and with this one alike, and
#: 1.899524977488909e-05 on one v5e chip.
ORACLE_REL_TOL = {"gtx-1080ti": 1e-5, "tpu-v5e": 2e-5}
#: ``e_total`` of the SEED kernel-path day on one v5e chip; the four-chip
#: run must reproduce it bit for bit.
ONE_CHIP_E_TOTAL = float.fromhex("0x1.cdbaa07d9edc4p+30")

_compiles = [0]


def _count_compiles(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compiles[0] += 1


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def device_check():
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)} jax={jax.__version__}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU present (JAX found "
                         f"{d.platform!r}); this smoke runs only on the chip")
    return d, len(devs)


def compile_cache_was_empty() -> bool:
    """Report whether the persistent compile cache held entries at start."""
    path = jax.config.jax_compilation_cache_dir
    n = len(os.listdir(path)) if path and os.path.isdir(path) else 0
    print(f"compile cache: {path} held {n} entries at start", flush=True)
    return n == 0


def phase(name: str, cold: bool, fn, *args):
    """Run one phase, then print its wall time and compile count."""
    c0, t0 = _compiles[0], time.perf_counter()
    info = fn(*args)
    wall = time.perf_counter() - t0
    label = "cold smoke wall" if cold else "smoke wall (compile cache warm)"
    print(f"[{name}] ok: {label} {wall!r} s (includes compilation, "
          f"not a benchmark), compiles {_compiles[0] - c0}, "
          f"{json.dumps(info)}", flush=True)
    return info


def day_keys(ts):
    """The class-stacked ``[C*n, 13]`` solve keys of the day on MIX."""
    from repro.core import machines

    return machines.stacked_keys(ts.params, ts.deadline - ts.arrival,
                                 machines.get_classes(MIX))


def kernel_phase(ts):
    from repro.kernels import layout, ops, ref
    from repro.kernels.dvfs_opt import dvfs_solve_kernel

    check(not ops.default_interpret(), "default_interpret() is True on TPU")
    keys = day_keys(ts)
    lowered = dvfs_solve_kernel.lower(
        jax.ShapeDtypeStruct((keys.shape[0], layout.NCOL), np.float32),
        interpret=False)
    check("tpu_custom_call" in lowered.as_text(),
          "the lowered kernel holds no tpu_custom_call")
    got = ops.dvfs_solve_matrix(keys)
    with jax.default_device(jax.devices("cpu")[0]):
        want = ref.dvfs_solve_ref(keys)
    check(got.shape == want.shape and np.all(np.isfinite(got)),
          f"solution shape {got.shape} or non-finite values")
    e_got, e_want = got[:, layout.SOL_E], want[:, layout.SOL_E]
    rel = np.abs(e_got - e_want) / e_want
    per_class = dict(zip(MIX, map(float, rel.reshape(len(MIX), -1)
                                  .max(axis=1))))
    for name, err in per_class.items():
        check(err < ORACLE_REL_TOL[name],
              f"{name}: max relative energy error {err!r} >= "
              f"{ORACLE_REL_TOL[name]}")
    return {"rows": int(keys.shape[0]), "max_rel_energy_err": per_class}


def check_schedule(r, n: int, what: str) -> None:
    """Eq. 7 conservation and one non-failed record per task."""
    e_sum = sum(a.energy for a in r.assignments) + r.e_idle + r.e_overhead
    check(r.e_total == e_sum, f"{what}: e_total {r.e_total!r} != "
          f"sum(energy) + e_idle + e_overhead {e_sum!r}")
    live = np.bincount([a.task for a in r.assignments if not a.failed],
                       minlength=n)
    check(live.shape == (n,) and bool(np.all(live == 1)),
          f"{what}: tasks without exactly one live record: "
          f"{int(np.sum(live != 1))}")


def run_day(ts, what: str, **kw):
    """One online day from a cold solve cache, checked for invariants."""
    from repro.core import online, solver_cache

    solver_cache.GLOBAL_CACHE.clear()
    r = online.schedule_online(ts, **DAY, **kw)
    check_schedule(r, len(ts), what)
    check(r.violations == 0, f"{what}: {r.violations} deadline violations")
    return r


def close(a: float, b: float) -> bool:
    return abs(a - b) <= PATH_REL_TOL * abs(b)


def online_phase(ts):
    pipe = run_day(ts, "kernel pipelined", use_kernel=True, pipeline=True)
    sync = run_day(ts, "kernel sync", use_kernel=True, pipeline=False)
    check(pipe.e_total == sync.e_total
          and pipe.violations == sync.violations
          and pipe.assignments == sync.assignments,
          f"pipeline=True diverged from pipeline=False: e_total "
          f"{pipe.e_total!r} vs {sync.e_total!r}")
    jnp_day = run_day(ts, "jnp", use_kernel=False)
    check(close(pipe.e_total, jnp_day.e_total),
          f"kernel vs jnp e_total {pipe.e_total!r} vs {jnp_day.e_total!r}")
    mix_k = run_day(ts, "mix kernel", use_kernel=True, classes=MIX)
    mix_j = run_day(ts, "mix jnp", use_kernel=False, classes=MIX)
    check(close(mix_k.e_total, mix_j.e_total),
          f"mix kernel vs jnp e_total {mix_k.e_total!r} vs "
          f"{mix_j.e_total!r}")
    return {"e_total_kernel": pipe.e_total,
            "e_total_kernel_hex": pipe.e_total.hex(),
            "e_total_jnp": jnp_day.e_total,
            "e_total_mix_kernel": mix_k.e_total,
            "e_total_mix_jnp": mix_j.e_total,
            "cache_stats_kernel": pipe.cache_stats}


def offline_phase():
    from repro.core import scheduling, solver_cache, tasks

    ts = tasks.generate_offline_n(N_TASKS, seed=SEED)
    solver_cache.GLOBAL_CACHE.clear()
    solver_cache.GLOBAL_CACHE.reset_stats()
    r = scheduling.schedule_offline(ts, l=4, theta=0.9, algorithm="edl",
                                    use_kernel=True)
    check_schedule(r, len(ts), "offline edl")
    check(r.e_bound > 0 and r.bound_gap >= 0,
          f"offline bound_gap {r.bound_gap!r} (e_bound {r.e_bound!r})")
    return {"e_total": r.e_total, "bound_gap": r.bound_gap,
            "violations": r.violations,
            "cache_stats": solver_cache.GLOBAL_CACHE.stats()}


def sharded_phase(ts):
    """The (b) matrix split across four chips, then the kernel-path day."""
    from repro.kernels import ops

    devs = jax.local_devices()
    check(len(devs) == 4, f"--chips 4 needs 4 local devices, found "
          f"{len(devs)}")
    keys = day_keys(ts)
    placed, kernel = [], ops.dvfs_solve_kernel

    def spy(x, **kw):
        out = kernel(x, **kw)
        placed.append(out.devices())
        return out

    ops.dvfs_solve_kernel = spy
    try:
        sharded = ops.dvfs_solve_matrix(keys, shard=True)
    finally:
        ops.dvfs_solve_kernel = kernel
    check(placed == [{d} for d in devs],
          f"sharded parts landed on {placed}, not one per device")
    single = ops.dvfs_solve_matrix(keys, shard=False)
    check(sharded.tobytes() == single.tobytes(),
          "sharded and single-device solves differ")
    r = run_day(ts, "kernel pipelined on 4 chips", use_kernel=True)
    check(r.e_total == ONE_CHIP_E_TOTAL,
          f"four-chip e_total {r.e_total!r} != one-chip "
          f"{ONE_CHIP_E_TOTAL!r}")
    return {"rows": int(keys.shape[0]), "e_total_kernel": r.e_total,
            "e_total_kernel_hex": r.e_total.hex(),
            "cache_stats_kernel": r.cache_stats}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-solve phase on four chips")
    args = ap.parse_args(argv)

    dev, count = device_check()
    check(count >= args.chips, f"--chips {args.chips} but {count} devices")

    from repro.core import tasks
    from repro.kernels import ops

    ops.enable_compile_cache()
    cold = compile_cache_was_empty()
    jax.monitoring.register_event_duration_secs_listener(_count_compiles)
    ts = tasks.generate_trace(N_TASKS, "uniform", seed=SEED)
    if args.chips == 4:
        phase("sharded", cold, sharded_phase, ts)
    else:
        phase("kernel", cold, kernel_phase, ts)
        phase("online", cold, online_phase, ts)
        phase("offline", cold, offline_phase)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
