"""The least time the chip could take for one prefill chunk at the stated
widths and dtypes (the configuration's `ops.prefill_chunk` module: compute
or memory, whichever binds), over the mean device time of one run of the
prefill chunk's compiled program in the trace. The chunk is the traffic
file's (`RAFIKI_GEN_PREFILL_CHUNK`: the template pads a prompt's last chunk
to it, so the program computes that many rows every time); the context
behind it is the mean over the traced chunks of the chunk's index (the
`chunk` attribute the serve loop gives each `gen.prefill_chunk` span, which
the profiler keeps as a statistic of the event) times the chunk. A program
whose spans carry no such attribute (the parent's), a configuration without
that module, or a run with no trace reads nothing."""

from benchmark import harness
from benchmark.layer_metrics import _shared, _spans

PROGRAM = "paged_prefill_chunk"
SPAN = "gen.prefill_chunk"


def chunk_indices(path: str) -> list:
    """The `chunk` statistic of every `gen.prefill_chunk` event on the
    trace's host planes."""
    from jax.profiler import ProfileData

    found = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for event in line.events:
                if event.name != SPAN:
                    continue
                stats = dict(event.stats)
                if "chunk" in stats:
                    found.append(int(stats["chunk"]))
    return found


def read(result, cell, peaks):
    took = _shared.module_mean_s(result, PROGRAM)
    module = cell["config_data"].get("ops", {}).get("prefill_chunk")
    if not took or not module:
        return None
    env = cell["traffic_data"]["settings"].get("env", {})
    chunk = int(env.get("RAFIKI_GEN_PREFILL_CHUNK", 0))
    indices = chunk_indices(result["trace"]["path"])
    if not chunk or not indices:
        return None
    context = chunk * sum(indices) / len(indices)
    ops = harness.load_by_name("ops", module)
    least, bound = ops.least_seconds(cell["config_data"], chunk, context,
                                     peaks)
    _spans.info(result).update(prefill_chunk_context_mean=context,
                               prefill_chunk_bound=bound,
                               prefill_chunks_traced=len(indices))
    return 100.0 * least / took
