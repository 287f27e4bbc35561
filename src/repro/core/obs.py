"""The scheduler's own spans and per-call counters.

**Spans** are ``jax.profiler.TraceAnnotation`` events named ``layer.step``
(:data:`NAMES` lists every one the scheduler opens).  They land in the
profiler's own trace, on the same clock as the device's operations, so
each idle gap of the chip can be put down to what the host was doing.
Nothing is recorded in Python: without a profiler session a span costs
one check of whether tracing is on.

**Counters** are integers per scheduler call.  :func:`call` decorates a
public scheduler entry point: it opens the call's span and its counter
record, :func:`count` adds to the open record (and does nothing outside a
call), and on exit the record is put on the result's ``counters`` and
appended to a bounded ring of the last :data:`RING_CALLS` calls
(:func:`recent`), also when the call raised.  A call made inside another
one opens its span but no record: its work is counted by the outermost
call, and its result's ``counters`` stays ``None``.

While an outermost call is open, a ``gc.callbacks`` hook opens a
``host.gc`` span over each garbage collection and counts it.  The hook is
registered on call entry and removed on exit.

This module reads no clock and keeps no state but the open record and the
ring; it imports no ``repro`` module.
"""

from __future__ import annotations

import functools
import gc

import numpy as np
from jax.profiler import TraceAnnotation

#: Every span name the scheduler opens.
NAMES = frozenset({
    # driver: the call itself, record write-back, result accounting
    "schedule.offline", "schedule.online", "schedule.records",
    "schedule.account",
    # solve path, host side (``solve.wait``: blocked on device results)
    "solve.keys", "solve.dedup", "solve.probe", "solve.dispatch",
    "solve.wait", "solve.fill", "solve.config",
    # placement
    "placement.prepare", "placement.pin", "placement.group",
    # cluster engine
    "engine.settle", "engine.finalize",
    # the interpreter's garbage collector, inside a call
    "host.gc",
})

#: Every counter, in the ring's column order.
COUNTERS = (
    "tasks",              # tasks in the call
    "solve.rows",         # key rows asked of the dedup solve path
    "solve.hits",         # unique rows served by the solve cache
    "solve.misses",       # unique rows the cache did not hold
    "solve.evictions",    # cache rows evicted to make room
    "solve.sent",         # rows handed to the solver, padding included
    "solve.pad",          # padding rows among them
    "placement.batched",  # tasks placed by batched prefix rounds
    "placement.scalar",   # tasks placed by a per-task rule
    "placement.pinned",   # deadline-prior tasks pinned to fresh pairs
    "placement.fallback",  # tasks on a pair in use of a non-primary class
    "gc.collections",     # garbage collections during the call
)

#: Calls the ring keeps (one int64 row of :data:`COUNTERS` each).
RING_CALLS = 1 << 14

#: A span: ``with span("layer.step"): ...``.
span = TraceAnnotation


def spanned(name: str):
    """Decorate a function to run inside a span named ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with TraceAnnotation(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


_COL = {name: i for i, name in enumerate(COUNTERS)}
_GC = _COL["gc.collections"]
_open: list | None = None      # the open outermost call's counts
_gc_span: TraceAnnotation | None = None
_ring: np.ndarray | None = None
_calls = 0                     # outermost calls recorded so far


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of the open call (no-op outside a
    call; an unknown name raises ``KeyError``)."""
    i = _COL[name]
    if _open is not None:
        _open[i] += n


def counts() -> dict:
    """The open call's counters so far (empty outside a call)."""
    return {} if _open is None else dict(zip(COUNTERS, _open))


def recent(n: int) -> list:
    """The counters of the last ``n`` recorded calls, oldest first (fewer
    when the ring holds fewer)."""
    m = min(int(n), _calls, RING_CALLS)
    if m <= 0:
        return []
    rows = (_ring[(_calls - m + j) % RING_CALLS] for j in range(m))
    return [dict(zip(COUNTERS, r.tolist())) for r in rows]


def _on_gc(phase: str, info: dict) -> None:
    global _gc_span
    if phase == "start":
        if _open is not None:
            _open[_GC] += 1
        _gc_span = TraceAnnotation("host.gc")
        _gc_span.__enter__()
    elif _gc_span is not None:
        sp, _gc_span = _gc_span, None
        sp.__exit__(None, None, None)


def _record(row: list) -> None:
    global _ring, _calls
    if _ring is None:
        _ring = np.zeros((RING_CALLS, len(COUNTERS)), np.int64)
    _ring[_calls % RING_CALLS] = row
    _calls += 1


def call(name: str):
    """Decorate a scheduler entry point as one call named ``name``: its
    span, its counter record and, while it runs, the garbage-collection
    hook."""
    def wrap(fn):
        @functools.wraps(fn)
        def scheduled(*args, **kwargs):
            global _open
            if _open is not None:
                with TraceAnnotation(name):
                    return fn(*args, **kwargs)
            row = _open = [0] * len(COUNTERS)
            gc.callbacks.append(_on_gc)
            try:
                with TraceAnnotation(name):
                    result = fn(*args, **kwargs)
            finally:
                gc.callbacks.remove(_on_gc)
                _open = None
                _record(row)
            result.counters = dict(zip(COUNTERS, row))
            return result
        return scheduled
    return wrap
