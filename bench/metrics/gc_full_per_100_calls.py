"""Full garbage collections per 100 scheduler calls.

The program's per-call counter ``gc.full`` (collections of the oldest
generation while a call runs, which trace every tracked object of the
process) summed over the window's calls, times 100, over the window's
calls.  A collection of that generation fires once the objects promoted
since the last one pass a quarter of those it holds, so records kept as
one object each set one off every few calls.  Its time lands in the span
open over it, mostly placement's.  A program that keeps no such counter
gives nothing.
"""

from bench.metrics import _program

NAME = "gc_full_per_100_calls"
PART = "gc.full"


def read(run: dict):
    c = _program.window_counters(run, NAME)
    if c is None:
        return None
    if PART not in c:
        run["notes"][NAME] = f"the program counts no {PART}"
        return None
    calls = sum(1 for sp in run["trace"]["spans"]
                if sp[0] == _program.CALL_SPAN)
    return 100.0 * c[PART] / calls
