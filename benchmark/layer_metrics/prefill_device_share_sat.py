"""The share of the device's busy time, in the traced seconds, that the
prefill chunk's compiled program took: the device seconds of the runs of
`paged_prefill_chunk` (`trace_reduce`'s time by compiled program, the
template's jitted function by its name, as `_shared.module_mean_s` finds the
decode round's) over the seconds in which any operation ran. What
`prefill_share.sat` cannot say on multi-chunk traffic: a chunk that is not a
prompt's last is dispatched and not waited for, so the serve thread's span
holds none of its device time. A trace without that program (another
traffic kind, a run with no trace) reads nothing."""

from benchmark.layer_metrics import _shared

PROGRAM = "paged_prefill_chunk"


def read(result, cell, peaks):
    r = _shared.reduced(result)
    if not r or not r.get("busy_s") or "module_s" not in r:
        return None
    took = sum(v for k, v in r["module_s"].items() if PROGRAM in k)
    return 100.0 * took / r["busy_s"] if took else None
