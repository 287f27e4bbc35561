"""The Algorithm-1 solves' share of the chip's roofline.

Least time for the traced window's solves over the device's busy time.
The work is the rows the schedules needed, tasks x classes, at the FLOPs
and bytes per row that ``peaks.json`` fixes from the algorithm (not from
whichever solver ran); the least time is the larger of FLOPs over peak
FLOP/s and bytes over peak bytes/s.  The device runs nothing but the solve
path, so its busy time is the solves' time; theta-readjustment rows are
device time with no counted work.
"""

from bench import work


def read(run: dict):
    busy = run["trace"]["busy_ns"]
    if not busy or not run["rows"]:
        return None
    mean_busy_s = sum(busy.values()) / len(busy) * 1e-9
    if mean_busy_s <= 0:
        return None
    least_s, bound = work.least_seconds(run["rows"], run["device_kind"])
    run["notes"]["solve_roofline_bound"] = bound
    return 100.0 * least_s / mean_busy_s
