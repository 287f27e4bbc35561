"""Host time blocked on solver results per 1,000 tasks scheduled.

Self time of the program's ``solve.wait`` spans, opened wherever the host
waits for a solve on the device and copies it back, summed over the
traced window and divided by the thousands of tasks it scheduled.
"""

from bench.metrics import _program

SPANS = _program.SPANS


def read(run: dict):
    return _program.layer_ms_per_ktask(run, ["solve.wait"])
