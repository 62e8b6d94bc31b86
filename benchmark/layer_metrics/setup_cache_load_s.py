"""Seconds the set-up spent loading programs from the persistent compile
cache (stage `load` of the compile record before the window opened). The
count of programs loaded goes to `info` as `setup_loaded`."""

from benchmark.layer_metrics import _setup, _spans


def read(result, cell, peaks):
    before = _setup.setup_events(result)
    if before is None:
        return None
    loaded = [e["seconds"] for e in before if e["stage"] == "load"]
    _spans.info(result)["setup_loaded"] = len(loaded)
    return sum(loaded)
