"""Reduce a ``jax.profiler`` trace to what the per-layer metrics read.

A trace (``*.xplane.pb``) holds one plane per device and one for the
host.  On a device plane the ``XLA Ops`` line carries every operation the
chip ran, with a start and a duration in nanoseconds on the host's clock,
and the ``XLA Modules`` line every program (``jit_solve_with_deadline``
and so on) those operations belong to.
On the host plane each thread is a line, and the spans the harness opens
(``jax.profiler.TraceAnnotation``) are events on the thread that opened
them.

:func:`reduce` returns

* ``window_ns``: the traced window, the span named ``WINDOW``;
* ``busy_ns``: per device, the union of its operations' intervals inside
  the window (an operation overlapping another counts once);
* ``ops``: device time per program (module) name, summed over devices;
* ``spans``: the harness's spans, ``(name, start, end, thread)``;
* ``gaps``: idle time per device between busy intervals, each named by the
  innermost harness span open across the gap's midpoint.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

WINDOW = "bench.window"
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
IDLE_UNNAMED = "(no harness span)"


def find(trace_dir: str) -> str:
    """The one ``.xplane.pb`` file a ``jax.profiler`` trace wrote."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"{trace_dir}: expected one .xplane.pb, "
                                f"found {len(files)}")
    return files[0]


def _union(iv):
    """Merge ``(start, end)`` intervals; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def outermost(spans):
    """Spans not contained in another span of the list (same thread)."""
    out, by_line = [], defaultdict(list)
    for sp in spans:
        by_line[sp[3]].append(sp)
    for items in by_line.values():
        items.sort(key=lambda x: (x[1], -x[2]))
        end = -1.0
        for sp in items:
            if sp[1] >= end:
                out.append(sp)
                end = sp[2]
            elif sp[2] > end:
                end = sp[2]
    return out


def _names_at(spans, times):
    """The innermost span open at each time (spans nest on a thread, so a
    sweep with a stack finds it)."""
    ev = [(s, 0, i) for i, (_, s, _, _) in enumerate(spans)]
    ev += [(e, 2, i) for i, (_, _, e, _) in enumerate(spans)]
    ev += [(t, 1, j) for j, t in enumerate(times)]
    ev.sort()
    out, stack = [IDLE_UNNAMED] * len(times), []
    for _, kind, i in ev:
        if kind == 0:
            stack.append(i)
        elif kind == 2:
            if stack and stack[-1] == i:
                stack.pop()
            elif i in stack:
                stack.remove(i)
        elif stack:
            out[i] = spans[stack[-1]][0]
    return out


def reduce(path: str, span_names) -> dict:
    """Reduce the trace at ``path``; ``span_names`` are the harness spans
    to keep (the window span is always kept)."""
    from jax.profiler import ProfileData

    keep = set(span_names) | {WINDOW}
    pd = ProfileData.from_file(path)
    device_ops, modules = defaultdict(list), []
    spans = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name].extend(
                        (e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
                elif line.name == MODULES_LINE:
                    modules.extend((e.name.split("(")[0], e.start_ns,
                                    e.start_ns + e.duration_ns)
                                   for e in line.events)
        elif plane.name == HOST_PLANE:
            for i, line in enumerate(plane.lines):
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                              i) for e in line.events if e.name in keep)
    windows = [s for s in spans if s[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{path}: expected one {WINDOW!r} span, "
                         f"found {len(windows)}")
    _, lo, hi, _ = windows[0]
    spans = [s for s in spans if s[0] != WINDOW and s[2] > lo and s[1] < hi]
    spans.sort(key=lambda x: x[1])

    busy, ops, gaps = {}, defaultdict(float), defaultdict(float)
    for name, s, e in modules:
        if e > lo and s < hi:
            ops[name] += min(e, hi) - max(s, lo)
    for dev, evs in device_ops.items():
        iv = _union(_clip(evs, lo, hi))
        busy[dev] = float(sum(e - s for s, e in iv))
        edges = [lo] + [x for se in iv for x in se] + [hi]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        names = _names_at(spans, [0.5 * (a + b) for a, b in idle])
        for name, (a, b) in zip(names, idle):
            gaps[name] += b - a
    return {"window_ns": float(hi - lo), "busy_ns": busy, "ops": dict(ops),
            "spans": spans, "gaps": dict(gaps)}


def top(d: dict, k: int = 10):
    """The ``k`` largest entries as ``[name, seconds]``."""
    return [[n, v * 1e-9] for n, v in
            sorted(d.items(), key=lambda x: -x[1])[:k]]
