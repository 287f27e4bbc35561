"""Share of the rows sent to the solver that are padding.

The program's per-call counters summed over the window's calls:
``solve.pad`` over ``solve.sent``, in %.  The solve path pads each batch
of cache misses to a fixed grid of shapes (powers of two, then multiples
of 1,024) so that the solver compiles a bounded set of programs; the
padding is device work and transfer that no task needed.
"""

from bench.metrics import _program


def read(run: dict):
    return _program.share(run, "solve_pad_share", "solve.pad",
                          ("solve.sent",))
