"""Device milliseconds per solve in operations under the program's
``factor`` scope (``core.cholesky_padded``): the union of their
intervals on device 0, so an operation inside another counts once."""
from harness import scopes


def read(run):
    return scopes.device_ms_per_solve(run, "factor")
