"""Share of the tasks placed by batched prefix rounds.

The program's per-call counters summed over the window's calls:
``placement.batched`` over ``placement.batched + placement.scalar``, in
%.  Deadline-prior tasks pinned to fresh pairs are in neither.  A task a
batch round places costs a share of one array pass; the others go
through a per-task rule in Python.
"""

from bench.metrics import _program


def read(run: dict):
    return _program.share(run, "placement_batched_share",
                          "placement.batched",
                          ("placement.batched", "placement.scalar"))
