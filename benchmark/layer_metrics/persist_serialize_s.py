"""Mean `persist.serialize` span of the window's trials, one of the three steps
under `persist_params` (`sdk/params.py dump_params`: host copies of what is still on the device, then msgpack)."""

from benchmark.layer_metrics import _shared


def read(result, cell, peaks):
    return _shared.span_mean_s(result, "persist.serialize")
