"""Seconds the set-up spent tracing programs to jaxprs and lowering them to
modules (stages `trace` and `lower` of the compile record before the window
opened): what no persistent cache holds."""

from benchmark.layer_metrics import _setup


def read(result, cell, peaks):
    return _setup.stage_seconds(result, "trace", "lower")
