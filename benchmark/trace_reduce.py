"""From a profiler trace (`.xplane.pb`) to the numbers the readers use: the
seconds in which an operation ran on each device, the idle gaps between them
and what the host was doing in the longest, and the time of each device
operation and of each compiled program (XLA module).

Read with `jax.profiler.ProfileData` and nothing else. A device plane is one
named `/device:TPU:<n>`; its line `XLA Ops` holds one event for each operation
that ran, and `XLA Modules` one for each run of a compiled program. Times are
nanoseconds from the start of the profiling session.
"""

from __future__ import annotations

import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def _union(intervals):
    """Sorted, merged copy of (start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def module_name(event_name: str) -> str:
    """`jit_epoch_scan(7261937)` -> `jit_epoch_scan`."""
    return re.sub(r"\(\d+\)$", "", event_name.strip())


def read(path: str) -> dict:
    """Planes of the trace as plain lists: for each device its operation and
    module events, and the host's events of 20 us or more."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in lines[OPS_LINE].events] if OPS_LINE in lines \
                else []
            mods = [(module_name(e.name), e.start_ns,
                     e.start_ns + e.duration_ns)
                    for e in lines[MODULES_LINE].events] \
                if MODULES_LINE in lines else []
            devices[int(m.group(1))] = {"ops": ops, "modules": mods}
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns >= 20_000:
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    return {"devices": devices, "host": host,
            "planes": [(p.name, [ln.name for ln in p.lines])
                       for p in data.planes]}


def reduce(path: str, window_s: float, spans=(), top: int = 10) -> dict:
    """The trace reduced. `window_s` is the length of the traced window (the
    profiler was on for that long, by the host's clock). `spans` are host
    spans the harness knows of, as (name, start_ns, end_ns) on the trace's
    clock; they name an idle gap before the trace's own host events do."""
    planes = read(path)
    devices = planes["devices"]
    if not devices:
        return {"window_s": window_s, "busy_s": 0.0, "n_devices": 0,
                "planes": planes["planes"]}
    busy, op_s, mod_s, mod_n, gaps = [], {}, {}, {}, []
    for dev in devices.values():
        merged = _union((s, e) for _, s, e in dev["ops"])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        gaps += [(merged[i][1], merged[i + 1][0])
                 for i in range(len(merged) - 1)]
        for name, s, e in dev["ops"]:
            op_s[name] = op_s.get(name, 0.0) + (e - s) / 1e9
        for name, s, e in dev["modules"]:
            mod_s[name] = mod_s.get(name, 0.0) + (e - s) / 1e9
            mod_n[name] = mod_n.get(name, 0) + 1
    n = len(devices)
    # an operation that holds others (a while loop, a fusion's parent) spans
    # them: the per-operation list is for names, not for a sum
    gap_s = {}
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:200]
    for g0, g1 in longest:
        name = _what_host_did(g0, g1, spans) or _what_host_did(
            g0, g1, planes["host"]) or "unattributed"
        gap_s[name] = gap_s.get(name, 0.0) + (g1 - g0) / 1e9
    by_time = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n,
        "n_devices": n,
        "device_ops": [[k[:80], v / n] for k, v in by_time(op_s)],
        "idle_gaps": [[k[:80], v / n] for k, v in by_time(gap_s)],
        "module_s": {k: v / n for k, v in mod_s.items()},
        "module_runs": {k: v / n for k, v in mod_n.items()},
        "planes": planes["planes"],
    }


def _what_host_did(g0: int, g1: int, events) -> str | None:
    """The event that covers most of the gap [g0, g1), if any covers a
    tenth of it."""
    best, best_cover = None, (g1 - g0) * 0.1
    for name, s, e in events:
        cover = min(e, g1) - max(s, g0)
        if cover > best_cover:
            best, best_cover = name, cover
    return best
