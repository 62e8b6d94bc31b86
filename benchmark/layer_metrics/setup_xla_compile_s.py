"""Seconds the set-up spent in XLA compiles that no persistent-cache entry
served (stage `compile` of the compile record before the window opened).
The five longest of them go to `info` as `setup_compiled`, by program."""

from benchmark.layer_metrics import _setup, _spans


def read(result, cell, peaks):
    before = _setup.setup_events(result)
    if before is None:
        return None
    compiled = sorted((e for e in before if e["stage"] == "compile"),
                      key=lambda e: -e["seconds"])
    _spans.info(result)["setup_compiled"] = [
        [e["fun_name"], e["seconds"]] for e in compiled[:5]]
    return sum(e["seconds"] for e in compiled)
