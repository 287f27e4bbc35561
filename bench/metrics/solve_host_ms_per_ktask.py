"""Host time on the solve path per 1,000 tasks scheduled.

Self time of the program's ``solve.*`` spans other than ``solve.wait``
(building keys, dedup, the cache probe, padding and dispatch, filling the
cache, assembling configurations), summed over the traced window and
divided by the thousands of tasks the window scheduled: the outermost
solve spans less the time the host spent blocked on device results in
them.
"""

from bench.metrics import _program

SPANS = _program.SPANS


def read(run: dict):
    return _program.layer_ms_per_ktask(
        run, [n for n in _program.SOLVE if n != "solve.wait"])
