"""What the readers share. A reader is `read(result, cell, peaks)`: it takes
its number from the run's record (trial spans and logs, the program's
counters, the client's log, the reduced trace) and returns None where it
finds nothing to read; the harness then leaves the metric out of the line."""

from __future__ import annotations

from benchmark import trace_reduce


def reduced(result: dict) -> dict | None:
    """The run's trace, reduced once. Trial spans name the idle gaps."""
    trace = result.get("trace")
    if not trace or not trace.get("path"):
        return None
    if "_reduced" not in result:
        spans = [(s["name"], int((s["start"] - trace["t0"]) * 1e9),
                  int((s["end"] - trace["t0"]) * 1e9))
                 for t in result.get("trials", []) for s in t["spans"]]
        result["_reduced"] = trace_reduce.reduce(
            trace["path"], trace["window_s"], spans)
    return result["_reduced"]


def idle_share(result: dict) -> float | None:
    r = reduced(result)
    if not r or not r.get("n_devices") or r["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])


def module_mean_s(result: dict, contains: str) -> float | None:
    """Mean device seconds of one run of the compiled program whose name
    holds `contains`."""
    r = reduced(result)
    if not r or "module_s" not in r:
        return None
    seconds = sum(v for k, v in r["module_s"].items() if contains in k)
    runs = sum(v for k, v in r["module_runs"].items() if contains in k)
    return seconds / runs if runs else None


def span_mean_s(result: dict, name: str) -> float | None:
    """Mean seconds of the trial spans called `name` that ended inside the
    window."""
    took = [s["end"] - s["start"] for t in result.get("trials", [])
            for s in t["spans"] if s["name"] == name
            and result["t0"] <= s["end"] < result["t1"]]
    return sum(took) / len(took) if took else None


def window_epochs(result: dict) -> list:
    return [e for t in result.get("trials", []) for e in t["epochs"]
            if result["t0"] <= e["time"] - e["epoch_time"]
            and e["time"] < result["t1"]]


def gauge_mean(result: dict, name: str) -> float | None:
    samples = result.get("gauges", {}).get(name)
    return sum(samples) / len(samples) if samples else None


def counter_delta(result: dict, name: str) -> float | None:
    before, after = result.get("counts_before"), result.get("counts_after")
    if not before or not after:
        return None
    return after["counters"][name] - before["counters"][name]
