"""What the set-up readers share. The program keeps a record of every
compile in its process (`rafiki_tpu/sdk/compile_cache.py` `events`: the
wall-clock end, the stage `trace`, `lower`, `compile` or `load`, the
seconds, the program's name and the span open on the compiling thread), and
its set-up spans land in `rafiki_worker_phase_seconds{phase="setup.*"}`.
Thread placement keeps the workers in the measuring process, so both are
read here after the run, on the clock of `result["t0"]` (`time.time()`).

The set-up is what ended before the window opened (`t0`); what ended after
it closed (`t1`) is the reference's (`check_compile_s` in `info`). A program
without the record (one older than the set-up spans), or a record with no
event before `t0`, gives None, and each reader then leaves its metric out of
the line and raises nothing.
"""

from __future__ import annotations

from benchmark.layer_metrics import _spans


def _record(result: dict) -> list | None:
    if "_compile_record" not in result:
        try:
            from rafiki_tpu.sdk import compile_cache
        except ImportError:
            compile_cache = None
        events = getattr(compile_cache, "events", None)
        result["_compile_record"] = events() if events else None
    return result["_compile_record"]


def phase_seconds() -> dict:
    """Seconds of each `setup.*` span in this process (the histogram's
    `_sum`), by name; {} where there are none."""
    from rafiki_tpu.utils.metrics import REGISTRY

    hist = REGISTRY.get("rafiki_worker_phase_seconds")
    if hist is None:
        return {}
    return {key[0]: child.snapshot()["sum"]
            for key, child in sorted(hist.children().items())
            if key[0].startswith("setup.")}


def setup_events(result: dict) -> list | None:
    """The record's events that ended before the window opened, or None
    where there is no record or nothing in it before `t0`. Puts what every
    set-up reader gives beside its number into `info`."""
    record = _record(result)
    if not record or "t0" not in result:
        return None
    before = [e for e in record if e["end"] < result["t0"]]
    if not before:
        return None
    info = _spans.info(result)
    if "setup_compile_by_phase" not in info:
        by_phase: dict = {}
        for e in before:
            by_phase[e["phase"]] = by_phase.get(e["phase"], 0.0) + e["seconds"]
        info["setup_compile_by_phase"] = dict(
            sorted(by_phase.items(), key=lambda kv: -kv[1]))
        info["setup_phase_s"] = phase_seconds()
        info["check_compile_s"] = sum(
            e["seconds"] for e in record if e["end"] >= result.get("t1", 0))
    return before


def stage_seconds(result: dict, *stages: str) -> float | None:
    """Seconds of the set-up's compile record in `stages`."""
    before = setup_events(result)
    if before is None:
        return None
    return sum(e["seconds"] for e in before if e["stage"] in stages)
