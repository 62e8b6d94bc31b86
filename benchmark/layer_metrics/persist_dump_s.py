"""Mean `persist.dump` span of the window's trials, one of the three steps
under `persist_params` (the template's `dump_parameters()`: where the device-to-host fetch happens when the template fetches)."""

from benchmark.layer_metrics import _shared


def read(result, cell, peaks):
    return _shared.span_mean_s(result, "persist.dump")
