"""Mean `evaluate` span of the window's trials."""

from benchmark.layer_metrics import _shared


def read(result, cell, peaks):
    return _shared.span_mean_s(result, "evaluate")
