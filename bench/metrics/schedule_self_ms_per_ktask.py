"""The scheduler driver's own host time per 1,000 tasks scheduled.

Self time of the program's ``schedule.*`` spans: the scheduler calls less
the time their ``solve.*``, ``placement.*`` and ``engine.*`` spans cover,
so the driver's own work, writing readjusted settings into the records
and accounting the result included.  Summed over the traced window and
divided by the thousands of tasks it scheduled.  Lists every span the
program opens, so the breakdown's idle gaps carry the program's names.
"""

from bench.metrics import _program

SPANS = _program.SPANS


def read(run: dict):
    return _program.layer_ms_per_ktask(run, _program.DRIVER)
