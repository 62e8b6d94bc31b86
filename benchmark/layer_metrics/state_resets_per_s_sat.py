"""Slots whose recurrent state started from zero, a second: the total of
`rafiki_gen_state_resets_total` (a prefill from position 0 of a model that
declares such state: every admission, and every resume of a preempted
stream) over the seconds from the window's opening to the last stream's
end. Over the life of the process (the harness snapshots `serving.COUNTERS`
alone as the window opens), so the two warm requests are in the count."""

from benchmark import serving


def read(result, cell, peaks):
    ended = [r["done"] for r in result.get("records", [])
             if r.get("done") is not None]
    resets = serving._registry_total("rafiki_gen_state_resets_total")
    if not resets or not ended or max(ended) <= result["t0"]:
        return None
    return resets / (max(ended) - result["t0"])
