"""Host time in placement per 1,000 tasks scheduled.

Outermost spans around the entry points of the placement subsystem
(``core/placement.py``), summed over the traced window and divided by the
thousands of tasks the window scheduled.  The harness opens the spans in
traced runs only.
"""

from bench import trace

#: Methods the harness wraps in a span of the same name, by class.
SPANS = {"repro.core.placement:PlacementContext": (
    "place_group_vector", "place_group_select", "prepare_chunk",
    "binpack_offline_util", "place_orphans", "pin_fresh")}


def read(run: dict):
    names = {m for methods in SPANS.values() for m in methods}
    spans = [s for s in run["trace"]["spans"] if s[0] in names]
    if not spans or not run["tasks"]:
        return None
    total_ns = sum(e - s for _, s, e, _ in trace.outermost(spans))
    return total_ns * 1e-6 / (run["tasks"] / 1000.0)
