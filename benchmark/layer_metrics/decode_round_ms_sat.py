"""Mean host time of one decode round in the traced window: the seconds of
`gen.decode.build`, `gen.decode.device` and `gen.decode.post` over the
number of `gen.decode.device` spans (`rounds` in `info`)."""

from benchmark.layer_metrics import _spans


def read(result, cell, peaks):
    spans = _spans.named(result, "gen.decode.")
    rounds = sum(name == "gen.decode.device" for name, _, _ in spans)
    if not rounds:
        return None
    _spans.info(result)["rounds"] = rounds
    return 1e3 * sum((e - s) / 1e9 for _, s, e in spans) / rounds
