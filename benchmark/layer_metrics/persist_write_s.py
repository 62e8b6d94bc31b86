"""Mean `persist.write` span of the window's trials, one of the three steps
under `persist_params` (`sdk/artifact.py write_artifact`: crc32, the framed copy, the write and its two fsyncs)."""

from benchmark.layer_metrics import _shared


def read(result, cell, peaks):
    return _shared.span_mean_s(result, "persist.write")
