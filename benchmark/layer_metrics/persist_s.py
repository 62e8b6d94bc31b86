"""Mean `persist_params` span of the window's trials: the dump of the
parameters and the write through the parameter store."""

from benchmark.layer_metrics import _shared


def read(result, cell, peaks):
    return _shared.span_mean_s(result, "persist_params")
