"""Streams preempted for want of pool blocks during the window (the change
of `rafiki_gen_preemptions_total`)."""

from benchmark.layer_metrics import _shared


def read(result, cell, peaks):
    return _shared.counter_delta(result, "rafiki_gen_preemptions_total")
