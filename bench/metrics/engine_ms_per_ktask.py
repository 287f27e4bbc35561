"""Host time in the cluster engine per 1,000 tasks scheduled.

Self time of the program's ``engine.*`` spans (``ClusterEngine.settle``,
the DRS power-off events, and ``finalize``, the Eq. 7 accounting), summed
over the traced window and divided by the thousands of tasks it scheduled.
"""

from bench.metrics import _program

SPANS = _program.SPANS


def read(run: dict):
    return _program.layer_ms_per_ktask(run, _program.ENGINE)
