"""The program's own spans on the profiler's clock. Since PR 24 every span of
`rafiki_tpu/utils/trace.py` also enters a `jax.profiler.TraceAnnotation` of
its name, so a traced run's `.xplane.pb` holds them beside the device's
operations. The serve loop's `gen.*` spans exist nowhere else; this reads
them once (`trace_reduce.read`, so host events under 20 us are not among
them) and keeps them on `result`. A trial's spans are saved with the trial
too, on the host's clock, and are placed on the trace's as
`_shared.reduced` places them: at `start - trace.t0` (PR 24 measured that
anchor 0.13 ms from the profiler's zero on the chip).

A program without the annotations (the parent of PR 24) leaves none in the
trace: every function here then returns None or nothing and raises nothing,
and the readers built on it leave their metric out of the line.
"""

from __future__ import annotations

from benchmark import trace_reduce

GEN_PHASES = ("gen.admit", "gen.prefill_chunk", "gen.bookkeep",
              "gen.decode.build", "gen.decode.device", "gen.decode.post")
GEN_HOST = ("gen.admit", "gen.bookkeep", "gen.decode.build",
            "gen.decode.post")


def planes(result: dict) -> dict | None:
    """The run's trace as `trace_reduce.read` gives it, read once."""
    trace = result.get("trace")
    if not trace or not trace.get("path"):
        return None
    if "_planes" not in result:
        result["_planes"] = trace_reduce.read(trace["path"])
    return result["_planes"]


def named(result: dict, prefix: str) -> list:
    """(name, start_ns, end_ns) of the serve loop's annotations in the trace
    whose name starts with `prefix`, by start; [] where there is no trace or
    it holds none."""
    p = planes(result)
    if p is None:
        return []
    if "_gen_spans" not in result:
        result["_gen_spans"] = sorted(
            (a for a in p["host"] if a[0].startswith("gen.")),
            key=lambda a: (a[1], -a[2]))
    return [a for a in result["_gen_spans"] if a[0].startswith(prefix)]


def info(result: dict) -> dict:
    """Where a reader leaves what it counted beside its number: the line's
    `info` takes `check_info` in after the readers have run."""
    return result.setdefault("check_info", {})


def self_pieces(spans) -> list:
    """Each span's interval less what its children cover, as disjoint
    (name, start, end) pieces. For the spans of ONE thread, which nest or
    lie apart; a child that outlasts its parent is cut at the parent's end."""
    out, stack = [], []  # stack items: [name, end, covered up to]

    def close(item):
        if item[1] > item[2]:
            out.append((item[0], item[2], item[1]))

    for name, start, end in sorted(spans, key=lambda a: (a[1], -a[2])):
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            top = stack[-1]
            if start > top[2]:
                out.append((top[0], top[2], start))
            end = min(end, top[1])
            top[2] = max(top[2], end)
        stack.append([name, end, start])
    while stack:
        close(stack.pop())
    return sorted(out, key=lambda piece: piece[1])


def self_seconds(spans) -> dict:
    took: dict = {}
    for name, start, end in self_pieces(spans):
        took[name] = took.get(name, 0.0) + (end - start) / 1e9
    return took


def serve_thread(result: dict) -> tuple | None:
    """(self seconds of each `gen.*` span, seconds from the first one's
    start to the last one's end): the serve loop's traced time and what
    tiled it. One generation worker's loop is one thread."""
    gen = named(result, "gen.")
    if not gen:
        return None
    extent = (max(e for _, _, e in gen) - min(s for _, s, _ in gen)) / 1e9
    if extent <= 0:
        return None
    took = self_seconds(gen)
    info(result)["gen_span_coverage"] = sum(
        took.get(name, 0.0) for name in GEN_PHASES) / extent
    return took, extent


def trial_spans(result: dict) -> list:
    """Every span of every trial as (name, start_ns, end_ns) on the trace's
    clock: `Tracer` stamped them with `time.time()`, the harness took
    `trace.t0` on the same clock as it opened the trace. They also hold what
    began before the trace opened, which no annotation does."""
    trace = result.get("trace")
    if not trace:
        return []
    return [(s["name"], (s["start"] - trace["t0"]) * 1e9,
             (s["end"] - trace["t0"]) * 1e9)
            for t in result.get("trials", []) for s in t["spans"]]


def idle_gaps(result: dict) -> list:
    """The device's idle gaps as (start_ns, end_ns): between the merged
    operations of each device, as `trace_reduce.reduce` takes them. What
    lies before the first operation and after the last is not among them:
    a span that straddles the trace's edge is not in the trace either."""
    p = planes(result)
    gaps = []
    for dev in (p or {}).get("devices", {}).values():
        merged = trace_reduce._union((s, e) for _, s, e in dev["ops"])
        gaps += [(merged[i][1], merged[i + 1][0])
                 for i in range(len(merged) - 1)]
    return sorted(gaps)


def idle_named_share(result: dict, spans) -> float | None:
    """100 * idle-gap seconds that lie inside one of `spans` (one thread's,
    on the trace's clock) / all idle-gap seconds. The seconds by the
    innermost span's name go to `info` as `idle_by_span_s`."""
    gaps = idle_gaps(result)
    total = sum(g1 - g0 for g0, g1 in gaps)
    if not total or not spans:
        return None
    pieces = self_pieces(spans)
    by_name, j = {}, 0
    for g0, g1 in gaps:
        while j < len(pieces) and pieces[j][2] <= g0:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][1] < g1:
            inside = min(pieces[k][2], g1) - max(pieces[k][1], g0)
            if inside > 0:
                by_name[pieces[k][0]] = by_name.get(pieces[k][0], 0) + inside
            k += 1
    info(result)["idle_by_span_s"] = {
        k: v / 1e9 for k, v in sorted(by_name.items(), key=lambda kv: -kv[1])}
    info(result)["idle_gap_s"] = total / 1e9
    return 100.0 * sum(by_name.values()) / total
