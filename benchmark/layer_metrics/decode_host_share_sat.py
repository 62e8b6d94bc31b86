"""The share of the serve thread's traced time that is host work between
device calls: self time of `gen.admit`, `gen.bookkeep`, `gen.decode.build`
and `gen.decode.post` over the time from the first `gen.*` span to the last.
The most that overlapping host work with the device (ROADMAP S6) can win.
An idle loop's wait for a request lies inside `gen.admit`: the share says
this only where the slots stay busy (`slots_busy_mean.sat`)."""

from benchmark.layer_metrics import _spans


def read(result, cell, peaks):
    serve = _spans.serve_thread(result)
    if serve is None:
        return None
    took, extent = serve
    return 100.0 * sum(took.get(n, 0.0) for n in _spans.GEN_HOST) / extent
