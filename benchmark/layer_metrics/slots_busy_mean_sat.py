"""Mean of `rafiki_gen_slots_busy`, sampled ten times a second through
the window: sequences decoding, of the slots the worker has."""

from benchmark.layer_metrics import _shared


def read(result, cell, peaks):
    return _shared.gauge_mean(result, "rafiki_gen_slots_busy")
