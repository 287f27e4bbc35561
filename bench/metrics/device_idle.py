"""Share of the traced window in which no operation ran on the chip.

1 - (union of the device's operation intervals / traced window), averaged
over the chips the cell uses.  The scheduler's device work is the
Algorithm-1 solves; everything else is host work, so this reads how far
the host holds the chip back.
"""


def read(run: dict):
    busy = run["trace"]["busy_ns"]
    if not busy:
        return None
    mean_busy = sum(busy.values()) / len(busy)
    return 100.0 * (1.0 - mean_busy / run["trace"]["window_ns"])
