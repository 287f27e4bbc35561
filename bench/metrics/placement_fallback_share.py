"""Share of the placed tasks that fell back to another class.

The program's per-call counters summed over the window's calls:
``placement.fallback`` over ``placement.batched + placement.scalar``, in
%.  A fallback puts a task on a pair in use of a class other than its
primary one (the least-energy feasible class), ahead of a fresh pair of
the primary class; a run of one class never counts one.  A program that
keeps no such counter gives nothing.
"""

from bench.metrics import _program

NAME = "placement_fallback_share"
PART = "placement.fallback"


def read(run: dict):
    c = _program.window_counters(run, NAME)
    if c is None:
        return None
    if PART not in c:
        run["notes"][NAME] = f"the program counts no {PART}"
        return None
    return _program.share(run, NAME, PART,
                          ("placement.batched", "placement.scalar"))
