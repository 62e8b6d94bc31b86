"""Mean `propose` span of the window's trials (advisor), in ms."""

from benchmark.layer_metrics import _shared


def read(result, cell, peaks):
    s = _shared.span_mean_s(result, "propose")
    return None if s is None else s * 1000.0
