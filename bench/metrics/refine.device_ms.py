"""Device milliseconds per solve in operations under the program's
``refine`` scope (``core.refine_solve``: the base solve, the sweeps'
residuals and triangular solves): the union of their intervals on
device 0, so the ``while`` that spans its sweeps counts once."""
from harness import scopes


def read(run):
    return scopes.device_ms_per_solve(run, "refine")
