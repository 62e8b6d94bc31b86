"""Device time of a compiled program's operations by the `jax.named_scope`
they were traced under. The profiler's `.xplane.pb` keeps, for each device
operation, the name JAX gave it (`tf_op`: `jit(paged_decode_round)/.../ssm/
dot_general`), but `jax.profiler.ProfileData` does not hand out the
operations' metadata, so this reads the file's own wire format (protocol
buffers: XSpace > XPlane > XLine > XEvent, and XPlane's tables of event and
stat metadata) with nothing but the standard library.

An operation that holds others (a while loop) spans them; each instant goes
to the innermost operation (`_spans.self_pieces`). A program traced without
such scopes (the parent of the PR that brought them) gives no time under
them: `scope_seconds` then returns None.
"""

from __future__ import annotations

import re

from benchmark.layer_metrics import _spans

_DEVICE = re.compile(r"^/device:TPU:\d+$")


def _varint(buf, i):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(field number, wire type, value) of one message: ints for varints
    and fixed widths, memoryviews for length-delimited fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {wire}")
        yield number, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def device_ops(path: str) -> list:
    """For each device plane, its `XLA Ops` as (tf_op, start_ps, end_ps)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = []
    for number, _, plane in fields(space):
        if number != 1:
            continue
        name, lines, event_meta, stat_names = "", [], {}, {}
        for n, _, v in fields(plane):
            if n == 2:
                name = _text(v)
            elif n == 3:
                lines.append(v)
            elif n in (4, 5):  # map entries: key = 1, value = 2
                entry = {k: val for k, _, val in fields(v)}
                if 2 not in entry:
                    continue
                if n == 5:
                    stat_names[entry.get(1, 0)] = _text(next(
                        (val for k, _, val in fields(entry[2]) if k == 2),
                        b""))
                else:
                    event_meta[entry.get(1, 0)] = entry[2]
        if not _DEVICE.match(name):
            continue
        tf_op = next((k for k, v in stat_names.items() if v == "tf_op"), None)
        if tf_op is None:
            continue
        named = {}
        for key, meta in event_meta.items():
            for n, _, stat in fields(meta):
                if n != 5:
                    continue
                s = {k: val for k, _, val in fields(stat)}
                if s.get(1) != tf_op:
                    continue
                if 5 in s:
                    named[key] = _text(s[5])
                elif 7 in s:  # a reference into the stat names
                    named[key] = stat_names.get(s[7], "")
        ops = []
        for line in lines:
            parts = list(fields(line))
            if not any(n == 2 and _text(v) == "XLA Ops" for n, _, v in parts):
                continue
            for n, _, event in parts:
                if n != 4:
                    continue
                e = {k: val for k, w, val in fields(event) if w == 0}
                start = e.get(2, 0)
                ops.append((named.get(e.get(1), ""), start,
                            start + e.get(3, 0)))
        out.append(ops)
    return out


def scope_seconds(result: dict, program: str) -> dict | None:
    """Self seconds of `program`'s device operations by the innermost
    named scope among `moe`, `ssm`, `attn` (else `other`), summed over the
    devices; None where there is no trace or the program ran under none of
    those scopes."""
    trace = result.get("trace")
    if not trace or not trace.get("path"):
        return None
    key = f"_scopes_{program}"
    if key not in result:
        took: dict = {}
        for ops in device_ops(trace["path"]):
            mine = [(_scope(name), s, e) for name, s, e in ops
                    if name.startswith(f"jit({program})")]
            for name, s, e in _spans.self_pieces(mine):
                took[name] = took.get(name, 0.0) + (e - s) / 1e12
        result[key] = took if set(took) & {"moe", "ssm", "attn"} else None
    return result[key]


def _scope(tf_op: str) -> str:
    found = [p for p in tf_op.split(":")[0].split("/")
             if p in ("moe", "ssm", "attn")]
    return found[-1] if found else "other"


def scope_share(result: dict, program: str, scope: str) -> float | None:
    took = scope_seconds(result, program)
    total = sum(took.values()) if took else 0.0
    return 100.0 * took.get(scope, 0.0) / total if total else None
