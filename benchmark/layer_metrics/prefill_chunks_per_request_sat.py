"""Prefill chunks dispatched for each request admitted: the total of
`rafiki_gen_prefill_chunks_total` (every chunk the serve loop hands the
model, a prompt's last or not) over the admissions the allocator counted
(`rafiki_gen_prefix_hits_total` and `rafiki_gen_prefix_misses_total`: every
slot opened is one or the other, a resume of a preempted stream too). Over
the life of the process, not the window (the harness snapshots
`serving.COUNTERS` alone as the window opens), so the two warm requests of
two chunks each are in the mean, and the chunks of requests the window's
close cuts short are too. `info` takes the mean real tokens a chunk beside
it. A program without the counter (the parent's) reads nothing."""

from benchmark import serving
from benchmark.layer_metrics import _spans


def read(result, cell, peaks):
    if not result.get("records"):
        return None
    total = serving._registry_total  # 0.0 where the program has no such
    chunks = total("rafiki_gen_prefill_chunks_total")
    admitted = total("rafiki_gen_prefix_hits_total") \
        + total("rafiki_gen_prefix_misses_total")
    if not chunks or not admitted:
        return None
    _spans.info(result)["prefill_chunk_tokens_mean"] = total(
        "rafiki_gen_prefill_chunk_tokens_total") / chunks
    return chunks / admitted
