"""The share of the device's idle-gap seconds that lie inside a `gen.*` span
of the serve loop, from the annotations in the trace alone. `info` names the
seconds by span."""

from benchmark.layer_metrics import _spans


def read(result, cell, peaks):
    return _spans.idle_named_share(result, _spans.named(result, "gen."))
