"""100 * (1 - seconds in which an operation ran on the device / traced
window), from the profiler's trace."""

from benchmark.layer_metrics import _shared


def read(result, cell, peaks):
    return _shared.idle_share(result)
