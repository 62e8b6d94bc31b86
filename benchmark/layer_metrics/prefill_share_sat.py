"""The share of the serve thread's traced time spent in `gen.prefill_chunk`:
what prefill takes from decode. The span holds a chunk's device time only
where the program fetches the chunk's token inside it, which is the final
chunk of a greedy stream: every prefill of `chat_saturated` (one chunk,
greedy). Any other chunk is an asynchronous dispatch whose device time lands
under the next `gen.decode.device`: on multi-chunk or sampled traffic this
under-reads and `decode_round_ms.sat` over-reads, and a cell with such
traffic needs prefill's share from the device's module time instead."""

from benchmark.layer_metrics import _spans


def read(result, cell, peaks):
    serve = _spans.serve_thread(result)
    if serve is None or "gen.prefill_chunk" not in serve[0]:
        return None
    took, extent = serve
    return 100.0 * took["gen.prefill_chunk"] / extent
