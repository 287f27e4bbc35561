"""One run of one benchmark cell: set-up, a timed window, the check.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``bench/configs/<config>.json``, the deployment: machine classes, ``l``,
``theta``, algorithm, scaling interval) under a traffic mix
(``bench/traffic/<traffic>.json``, read by :mod:`bench.traffic`).  Each
per-layer metric is a reader in ``bench/metrics/<metric>.py``.  The
harness finds all three by name, so a later cell or metric is new files
and new entries, never an edit here.

A run is a closed loop with one caller.  Set-up starts JAX, draws every
request of the window from ``(seed, request index)``, and makes warm-up
calls of the program's public entry on requests of a separate stream
until the mix's ``warmup_quiet`` calls in a row make no executable.  The
window then calls the program back to back, each request once, until
``seconds`` have passed; the call in flight at that moment finishes and
counts.  A mix draws about three times the requests its window takes
today; a program fast enough to use them all ends its window there
(``requests_exhausted`` on the detail line) rather than repeat one.  With
``trace`` the window runs under ``jax.profiler`` with spans around the
program's layers, and the per-layer metrics are read from the trace
instead of the end-to-end ones.  After the window a sample of the
calls, drawn from the seed by reservoir sampling, is judged by
:mod:`bench.reference`.

The harness passes the program only the configuration's deployment
parameters, leaves the garbage collector on and never clears the
program's solve cache.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
import traceback

import numpy as np

from bench import reference, trace, traffic

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SPEC_FILE = os.path.join(ROOT, "BENCHMARK.json")
#: Spans the harness opens besides those the metrics ask for: they name
#: the device's idle gaps in the breakdown.
SCHEDULE_SPAN = "bench.schedule_call"
OWN_SPANS = {"repro.core.engine:ClusterEngine": ("settle",)}
FIELDS = reference.FIELDS
#: Warm-up ends after a mix's ``warmup_quiet`` calls in a row made no
#: executable, or at its ``warmup_max`` calls (defaults below).
WARMUP_QUIET = 3
WARMUP_MAX = 24


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell(spec: dict, name: str) -> dict:
    """The workload ``name`` with its configuration, mix and metrics."""
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise KeyError(f"unknown workload {name!r}; BENCHMARK.json has "
                       f"{sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return dict(
        workload=w,
        deploy=load_json(os.path.join(ROOT, conf["file"])),
        mix=load_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json")),
        end_to_end=[m for m in spec["end_to_end"] if mine(m)],
        per_layer=[m for m in spec["per_layer"] if mine(m)],
    )


def load_metric(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def check_devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX found {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX found "
                            f"{len(devs)}")
    return devs


def enable_compile_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` points), small programs
    included."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class Program:
    """The system under test, bound to one deployment: its public entry
    point and the deployment's parameters, nothing else."""

    def __init__(self, deploy: dict, mix: dict):
        from repro.core import dvfs, online, scheduling, tasks

        self._dvfs, self._tasks = dvfs, tasks
        self.fn = {"online": online.schedule_online,
                   "offline": scheduling.schedule_offline}[mix["entry"]]
        self.kw = dict(
            classes=(None if deploy["classes"] is None
                     else tuple(deploy["classes"])),
            l=deploy["l"], theta=deploy["theta"],
            algorithm=deploy["algorithm"],
            interval=dvfs.ScalingInterval(**deploy["interval"]),
            bound=deploy["bound"])

    def task_set(self, d: dict):
        return self._tasks.TaskSet(
            d["arrival"], d["deadline"],
            self._dvfs.DvfsParams(*(d[f] for f in FIELDS)),
            d["utilization"])

    def __call__(self, ts):
        return self.fn(ts, **self.kw)


def records(result) -> dict:
    """The schedule's records as arrays."""
    a = result.assignments
    n = len(a)
    out = {f: np.fromiter((getattr(x, f) for x in a), np.int64, n)
           for f in ("task", "pair", "class_id")}
    out.update({f: np.fromiter((getattr(x, f) for x in a), np.float64, n)
                for f in ("start", "finish", "v", "fc", "fm", "power",
                          "energy")})
    out["failed"] = np.fromiter((x.failed for x in a), bool, n)
    return out


def install_spans(groups: dict):
    """Wrap the named methods in ``jax.profiler.TraceAnnotation`` spans of
    the same name; returns a function that takes them off again."""
    import functools
    import importlib

    import jax

    undo = []
    for target, methods in groups.items():
        mod, cls_name = target.split(":")
        cls = getattr(importlib.import_module(mod), cls_name)
        for m in methods:
            if not hasattr(cls, m):
                continue
            orig = getattr(cls, m)

            def wrap(fn, name):
                @functools.wraps(fn)
                def spanned(*a, **k):
                    with jax.profiler.TraceAnnotation(name):
                        return fn(*a, **k)
                return spanned

            setattr(cls, m, wrap(orig, m))
            undo.append((cls, m, orig))

    def remove():
        for cls, m, orig in reversed(undo):
            setattr(cls, m, orig)
    return remove


class Reservoir:
    """A uniform sample of ``k`` of a stream, drawn from ``rng``, and the
    stream's largest item by ``size``."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.seen = k, rng, 0
        self.items, self.largest = [], None

    def offer(self, item, size: float):
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(self.seen))
            if j < self.k:
                self.items[j] = item
        if self.largest is None or size > self.largest[1]:
            self.largest = (item, size)

    def sample(self):
        out = list(self.items)
        if self.largest is not None and all(
                x is not self.largest[0] for x in out):
            out.append(self.largest[0])
        return out


def run(workload: str, seed: int, seconds: float, traced: bool,
        t_start: float | None = None, require_tpu: bool = True,
        cell_data: dict | None = None, log=sys.stderr) -> dict:
    """One run of one cell: ``{"detail": ..., "line": ...}``, the run's
    details and the result line's object.  ``t_start`` is when set-up
    began (default: now).  Tests pass ``require_tpu=False`` and a small
    ``cell_data`` (what :func:`cell` returns) to drive a run on the CPU."""
    t_start = time.perf_counter() if t_start is None else t_start
    c = cell_data or cell(load_json(SPEC_FILE), workload)
    w, deploy, mix = c["workload"], c["deploy"], c["mix"]

    import jax

    devs = check_devices(int(w["chips"])) if require_tpu else jax.devices()
    enable_compile_cache()
    # An executable made in this process (``made``): compiled afresh
    # (``misses``) or read from the persistent cache.
    made, misses = [0], [0]

    def count_made(event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            made[0] += 1

    def count_miss(event: str, **_):
        if event == "/jax/compilation_cache/cache_misses":
            misses[0] += 1

    jax.monitoring.register_event_duration_secs_listener(count_made)
    jax.monitoring.register_event_listener(count_miss)

    metrics = {m["name"]: load_metric(m["name"]) for m in c["per_layer"]} \
        if traced else {}
    span_groups = dict(OWN_SPANS)
    for mod in metrics.values():
        for target, methods in getattr(mod, "SPANS", {}).items():
            span_groups[target] = tuple(span_groups.get(target, ())) \
                + tuple(methods)
    remove_spans = install_spans(span_groups) if traced else (lambda: None)

    phases = {"start": time.perf_counter() - t_start}
    program = Program(deploy, mix)
    lib = traffic.library()
    n_req = int(mix["requests"])
    drawn = [traffic.draw(mix, seed, traffic.WINDOW, i, lib)
             for i in range(n_req)]
    window_sets = [program.task_set(d) for d in drawn]
    phases["draw"] = time.perf_counter() - t_start - sum(phases.values())

    # Warm-up through the public entry on requests the window never sends,
    # until calls make no executable: the padded solve shapes the window
    # will use are compiled (or loaded from the persistent cache) here.
    # The window counts what it still compiles or loads
    # (``compiles_in_window``, ``cache_loads_in_window``).
    warm_calls = 0
    quiet = 0
    for i in range(int(mix.get("warmup_max", WARMUP_MAX))):
        c0 = made[0]
        program(program.task_set(
            traffic.draw(mix, seed, traffic.WARMUP, i, lib)))
        warm_calls += 1
        quiet = quiet + 1 if made[0] == c0 else 0
        if quiet == int(mix.get("warmup_quiet", WARMUP_QUIET)):
            break
    gc.collect()
    setup_s = time.perf_counter() - t_start
    phases["warmup_calls"] = setup_s - sum(phases.values())

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        window_span = jax.profiler.TraceAnnotation(trace.WINDOW)
        window_span.__enter__()

    reservoir = Reservoir(int(mix["checked"]),
                          traffic.rng_for(seed, traffic.CHECK, 0))
    walls, n_tasks, failed, rows = [], 0, 0, 0
    n_classes = 1 if deploy["classes"] is None else len(deploy["classes"])
    e_totals, violations, pairs, hit_rates = [], 0, [], []
    made0, misses0 = made[0], misses[0]
    i = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds and i < n_req:
        ts = window_sets[i]
        a = time.perf_counter()
        try:
            if traced:
                with jax.profiler.TraceAnnotation(SCHEDULE_SPAN):
                    r = program(ts)
            else:
                r = program(ts)
        except Exception:                    # a failed call counts, and on
            traceback.print_exc(file=log)    # the run goes
            failed += 1
            r = None
        walls.append(time.perf_counter() - a)
        if r is not None:
            n_tasks += len(ts)
            rows += len(ts) * n_classes
            e_totals.append(r.e_total)
            violations += r.violations
            pairs.append(r.n_pairs)
            if r.cache_stats:
                hit_rates.append(r.cache_stats["hit_rate"])
            reservoir.offer((i, r), len(ts))
        i += 1
    window_s = time.perf_counter() - t0
    made_window, misses_window = made[0] - made0, misses[0] - misses0
    if traced:
        window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    remove_spans()

    mem = [d.memory_stats() or {} for d in devs[: int(w["chips"])]]
    peak = max((m.get("peak_bytes_in_use", 0) for m in mem), default=0)

    out_metrics, breakdown, dev_extra, notes = {}, None, {}, {}
    if traced:
        tr = trace.reduce(trace.find(trace_dir), {
            m for methods in span_groups.values() for m in methods}
            | {SCHEDULE_SPAN})
        shutil.rmtree(trace_dir, ignore_errors=True)
        run_info = dict(trace=tr, tasks=n_tasks, rows=rows,
                        device_kind=devs[0].device_kind, notes=notes)
        for m in c["per_layer"]:
            v = metrics[m["name"]].read(run_info)
            if v is not None:
                out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        busy = tr["busy_ns"]
        dev_extra = {"busy_s": (sum(busy.values()) / len(busy) * 1e-9
                                if busy else 0.0),
                     "window_s": tr["window_ns"] * 1e-9}
        breakdown = {"device_ops": trace.top(tr["ops"]),
                     "idle_gaps": trace.top(tr["gaps"])}
    else:
        e2e = {"tasks_per_s": n_tasks / window_s,
               "schedule_p95_ms": (float(np.percentile(walls, 95)) * 1e3
                                   if walls else math.nan),
               "setup_s": setup_s}
        for m in c["end_to_end"]:
            out_metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}

    # The check, once the window has closed and the peak is read.
    sample = reservoir.sample()
    del window_sets, reservoir
    gc.collect()
    numbers = {}
    for idx, r in sample:
        got = reference.check(
            drawn[idx], records(r),
            {"e_total": r.e_total, "violations": r.violations}, deploy,
            mix["entry"] == "online",
            traffic.rng_for(seed, traffic.CHECK, 1 + idx))
        for k, v in got.items():
            numbers[k] = max(numbers.get(k, -math.inf), v)
    correct = bool(sample) and failed == 0 and reference.passed(numbers)

    detail = {
        "workload": workload, "seed": seed, "trace": int(traced),
        "calls": len(walls), "tasks": n_tasks, "window_s": window_s,
        "requests_exhausted": i == n_req,
        "setup_s": setup_s, "setup_phases_s": phases,
        "warmup_calls": warm_calls,
        "compiles_in_window": misses_window,
        "cache_loads_in_window": made_window - misses_window,
        "p50_ms": float(np.median(walls)) * 1e3 if walls else None,
        "slowest_ms": sorted((w * 1e3 for w in walls), reverse=True)[:5],
        "checked_requests": sorted(i for i, _ in sample),
        "violations": violations,
        "e_total_first": e_totals[:3],
        "pairs_mean": float(np.mean(pairs)) if pairs else None,
        "solve_cache_hit_rate_mean": (float(np.mean(hit_rates))
                                      if hit_rates else None),
        "notes": notes,
    }
    checks = {k: {"value": numbers.get(k), "limit": lim}
              for k, lim in reference.LIMITS.items()}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak),
              **dev_extra}
    line = {"correct": correct, "attempted": len(walls), "failed": failed,
            "metrics": out_metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return {"detail": detail, "line": line}
