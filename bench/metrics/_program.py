"""What the readers of the program's own spans and counters share.

Not a metric: the harness loads only the readers ``BENCHMARK.json`` names.

The program opens spans named ``layer.step`` (``repro.core.obs``).  Inside
a scheduler call every nanosecond of a thread belongs to the innermost
program span open over it (:func:`self_ns`), so the layers' shares of a
call add up to the call exactly: where one layer's span opens inside
another's, the inner layer owns that time and the outer one does not.
``host.gc`` is no layer: a collection's time stays with the span around
it.  Spans the harness opens itself are not the program's and are left
out.

Counters are the program's per-call records (``repro.core.obs.recent``):
after the window the harness calls nothing of the program, so the last
``N`` records, ``N`` the window's ``bench.schedule_call`` spans, are the
window's calls.
"""

from __future__ import annotations

import importlib
from collections import defaultdict

DRIVER = ("schedule.offline", "schedule.online", "schedule.records",
          "schedule.account")
SOLVE = ("solve.keys", "solve.dedup", "solve.probe", "solve.dispatch",
         "solve.wait", "solve.fill", "solve.config")
PLACEMENT = ("placement.prepare", "placement.pin", "placement.group")
ENGINE = ("engine.settle", "engine.finalize")
TRANSPARENT = ("host.gc",)
#: Every span name the program opens.
ALL = DRIVER + SOLVE + PLACEMENT + ENGINE + TRANSPARENT

#: The harness imports the module before the colon of a ``SPANS`` key and
#: wraps each listed name that is an attribute of the object after it.  No
#: dotted name is one, so nothing is wrapped and the names only join the
#: spans the trace reduction keeps.  The key names an object that every
#: version of the program has, so a traced run of a program that opens no
#: such spans runs all the same, and its readers find nothing.
SPANS = {"repro.core.cluster:ScheduleResult": ALL}

CALL_SPAN = "bench.schedule_call"


def self_ns(spans) -> dict:
    """Self time per program span name over ``(name, start, end, thread)``
    spans: on each thread, each nanosecond goes to the innermost program
    span open over it (a ``host.gc`` span passes it to the span around
    it).  A span that outlasts its parent is cut at the parent's end."""
    out = defaultdict(float)
    by_thread = defaultdict(list)
    for sp in spans:
        if sp[0] in ALL:
            by_thread[sp[3]].append(sp)
    for items in by_thread.values():
        items.sort(key=lambda x: (x[1], -x[2]))
        stack = []                               # [name, end]
        t = items[0][1]

        def advance(to):
            nonlocal t
            if to <= t:
                return
            for name, _ in reversed(stack):
                if name not in TRANSPARENT:
                    out[name] += to - t
                    break
            t = to

        for name, s, e, _ in items:
            while stack and stack[-1][1] <= s:
                advance(stack[-1][1])
                stack.pop()
            advance(s)
            stack.append([name, min(e, stack[-1][1]) if stack else e])
        while stack:
            advance(stack[-1][1])
            stack.pop()
    return dict(out)


def layer_ms_per_ktask(run: dict, names) -> float | None:
    """Self time of the spans ``names`` per 1,000 tasks of the window, in
    ms; None when the trace holds no program span or the window no task."""
    own = self_ns(run["trace"]["spans"])
    if not own or not run["tasks"]:
        return None
    return sum(own.get(n, 0.0) for n in names) * 1e-6 / (run["tasks"] / 1e3)


def window_counters(run: dict, metric: str) -> dict | None:
    """The program's counters summed over the window's calls; None, with
    the reason in ``run["notes"][metric]``, when they cannot be read."""
    n = sum(1 for sp in run["trace"]["spans"] if sp[0] == CALL_SPAN)
    if not n:
        run["notes"][metric] = "no scheduler call in the window"
        return None
    try:
        obs = importlib.import_module("repro.core.obs")
    except ImportError:
        run["notes"][metric] = "the program keeps no per-call counters"
        return None
    rows = obs.recent(n)
    if len(rows) < n:
        run["notes"][metric] = (f"the program recorded {len(rows)} calls, "
                                f"the window made {n}")
        return None
    return {k: sum(r[k] for r in rows) for k in rows[0]}


def share(run: dict, metric: str, part: str, whole) -> float | None:
    """``part`` as a % of the sum of the counters ``whole`` over the
    window's calls; None (with a note) when that sum is 0."""
    c = window_counters(run, metric)
    if c is None:
        return None
    total = sum(c.get(k, 0) for k in whole)
    if not total:
        run["notes"][metric] = f"no {' or '.join(whole)} in the window"
        return None
    return 100.0 * c.get(part, 0) / total
