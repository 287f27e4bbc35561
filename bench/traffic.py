"""The benchmark's one traffic generator: task sets drawn the paper's way.

A traffic mix is a JSON file under ``bench/traffic/`` that names a
``generator`` and its parameters; :func:`draw` turns the mix and a
``(seed, stream, index)`` triple into one request, a task set held as plain
numpy arrays.  The harness builds the program's own task type from them.

The draws follow Mei et al., arXiv:2104.00486, Sec. 5.1.3, as the
program's ``core/tasks.py`` does: a 20-application library fitted inside
the paper's published ranges (fixed seed 11), each task an application
scaled by an integer in [10, 50], utilization ``u ~ U(0, 1)`` and deadline
``d = a + t*/u``.  This copy is the yardstick and stays put when the
program's generators change; ``bench/tests/test_traffic.py`` pins what it
draws.

Generators:

* ``offline``  - tasks until their utilizations sum to ``util * 1024``
  pairs, all arriving at ``T = 0``;
* ``online``   - an ``offline_util`` batch at ``T = 0`` plus
  ``online_util`` of tasks spread over ``horizon`` one-minute slots by a
  Poisson profile refined to carry them exactly;
* ``trace``    - exactly ``n_tasks`` tasks over ``horizon`` slots with a
  named arrival ``pattern`` (``uniform``, ``sparse``, ``bursty``,
  ``diurnal``).

A mix may name another generator: ``bench/traffic/<generator>.py``, whose
``draw(rng, lib, **params)`` returns the same arrays.  A new traffic shape
is then new files, with no edit here.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "traffic")

UTILIZATION_BASE = 1024
DAY_SLOTS = 1440
SCALE_LO, SCALE_HI = 10, 50
LIBRARY_SEED = 11
N_APPS = 20
PATTERNS = ("uniform", "sparse", "bursty", "diurnal")
FIELDS = ("p0", "gamma", "c", "big_d", "delta", "t0")


def library() -> dict:
    """The 20-application library inside the paper's fit ranges:
    P* in [175, 206] W, gamma/P* in [0.1, 0.2], P0/P* in [0.20, 0.41],
    delta spread over [0.07, 0.91], D in [1.66, 7.61] s, t0 in
    [0.1, 0.95] s."""
    rng = np.random.default_rng(LIBRARY_SEED)
    p_star = rng.uniform(175.0, 206.0, N_APPS)
    gamma = p_star * rng.uniform(0.10, 0.20, N_APPS)
    p0 = p_star * rng.uniform(0.20, 0.41, N_APPS)
    c = p_star - gamma - p0
    delta = np.linspace(0.07, 0.91, N_APPS)
    rng.shuffle(delta)
    big_d = rng.uniform(1.66, 7.61, N_APPS)
    t0 = rng.uniform(0.10, 0.95, N_APPS)
    return dict(p0=p0, gamma=gamma, c=c, big_d=big_d, delta=delta, t0=t0)


def _tasks(lib: dict, app: np.ndarray, k: np.ndarray) -> dict:
    """Per-task model constants: app ``app`` with its times scaled by ``k``."""
    return dict(p0=lib["p0"][app], gamma=lib["gamma"][app], c=lib["c"][app],
                big_d=lib["big_d"][app] * k, delta=lib["delta"][app],
                t0=lib["t0"][app] * k)


def _draw_n(rng, lib: dict, n: int):
    app = rng.integers(N_APPS, size=n)
    k = rng.integers(SCALE_LO, SCALE_HI + 1, size=n).astype(np.float64)
    u = np.clip(rng.uniform(0.0, 1.0, n), 1e-3, 1.0)
    return _tasks(lib, app, k), u


def _draw_util(rng, lib: dict, target_util: float):
    """Tasks until the utilizations reach ``target_util * 1024``; the task
    that would cross the target is trimmed to land on it exactly (dropped
    when the remainder is under 1e-3)."""
    target = target_util * UTILIZATION_BASE
    block = int(2.5 * target) + 64           # mean u is 1/2: ~2 target tasks
    apps, ks, us = [], [], []
    total = 0.0
    while True:
        app = rng.integers(N_APPS, size=block)
        k = rng.integers(SCALE_LO, SCALE_HI + 1, size=block)
        u = np.clip(rng.uniform(0.0, 1.0, block), 1e-3, 1.0)
        cs = total + np.cumsum(u)
        m = int(np.searchsorted(cs, target, side="left"))
        if m == block:                       # target not reached yet
            apps.append(app), ks.append(k), us.append(u)
            total = float(cs[-1])
            continue
        before = float(cs[m - 1]) if m else total
        keep = m + 1 if cs[m] == target else m
        apps.append(app[:keep]), ks.append(k[:keep]), us.append(u[:keep])
        if cs[m] > target and target - before >= 1e-3:
            apps.append(app[m:m + 1]), ks.append(k[m:m + 1])
            us.append(np.array([target - before]))
        break
    app = np.concatenate(apps)
    k = np.concatenate(ks).astype(np.float64)
    return _tasks(lib, app, k), np.concatenate(us)


def _finish(params: dict, u: np.ndarray, arrival: np.ndarray) -> dict:
    t_star = params["big_d"] + params["t0"]
    return dict(arrival=arrival, deadline=arrival + t_star / u,
                utilization=u, **params)


def offline(rng, lib: dict, util: float) -> dict:
    params, u = _draw_util(rng, lib, util)
    return _finish(params, u, np.zeros(u.shape[0]))


def online(rng, lib: dict, offline_util: float, online_util: float,
           horizon: int = DAY_SLOTS) -> dict:
    off = offline(np.random.default_rng(int(rng.integers(2**31))), lib,
                  offline_util)
    params, u = _draw_util(rng, lib, online_util)
    n_on = u.shape[0]
    counts = rng.poisson(n_on / horizon, horizon)
    diff = int(counts.sum()) - n_on
    while diff != 0:                         # refine to carry exactly n_on
        slot = int(rng.integers(horizon))
        if diff > 0 and counts[slot] > 0:
            counts[slot] -= 1
            diff -= 1
        elif diff < 0:
            counts[slot] += 1
            diff += 1
    arrival = np.repeat(np.arange(1, horizon + 1, dtype=np.float64), counts)
    on = _finish(params, u, arrival)
    return {f: np.concatenate([off[f], on[f]]) for f in off}


def trace(rng, lib: dict, n_tasks: int, pattern: str,
          horizon: int = DAY_SLOTS) -> dict:
    if pattern not in PATTERNS:
        raise ValueError(f"unknown arrival pattern {pattern!r}; "
                         f"choose from {PATTERNS}")
    params, u = _draw_n(rng, lib, n_tasks)
    slots = np.arange(1, horizon + 1, dtype=np.int64)
    if pattern == "uniform":
        p = np.ones(horizon)
    elif pattern == "sparse":
        p = (slots % 32 == 1).astype(np.float64)
    elif pattern == "bursty":
        n_bursts = max(1, min(horizon, n_tasks // 512 + 1))
        p = np.zeros(horizon)
        p[rng.choice(horizon, size=n_bursts, replace=False)] = 1.0
    else:
        p = 1.0 + np.sin(2.0 * np.pi * slots / horizon - 0.5 * np.pi) + 1e-3
    counts = rng.multinomial(n_tasks, p / p.sum())
    arrival = np.repeat(slots.astype(np.float64), counts)
    return _finish(params, u, arrival)


GENERATORS = {"offline": offline, "online": online, "trace": trace}
#: Keys of a mix file that are not generator parameters.
MIX_KEYS = ("generator", "entry", "requests", "checked", "warmup_max",
            "warmup_quiet")


def generator(name: str):
    """One of ``GENERATORS``, or the ``draw`` of ``TRAFFIC_DIR/<name>.py``."""
    if name in GENERATORS:
        return GENERATORS[name]
    spec = importlib.util.spec_from_file_location(
        "bench_traffic_" + name.replace(".", "_").replace("-", "_"),
        os.path.join(TRAFFIC_DIR, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.draw


#: Stream ids keep the window's requests, the warm-up's and the check's
#: sample apart for one seed.
WINDOW, WARMUP, CHECK = 0, 1, 2


def rng_for(seed: int, stream: int, index: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**64, stream, index])


def draw(mix: dict, seed: int, stream: int, index: int,
         lib: dict | None = None) -> dict:
    """One request of ``mix``: a dict of equal-length float64 arrays
    (``arrival``, ``deadline``, ``utilization`` and the six model
    constants)."""
    params = {k: v for k, v in mix.items() if k not in MIX_KEYS}
    gen = generator(mix["generator"])
    return gen(rng_for(seed, stream, index),
               library() if lib is None else lib, **params)
