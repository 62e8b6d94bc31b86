"""The least time the chip could take for one decode round at the stated
widths and dtypes (the configuration's `ops.decode_round` module: weights
read once, the live keys and values read once, the new rows written), over
the device time of one run of the decode program in the trace. Sequences
and live tokens are those of the streams decoding during the traced
seconds, from the client's log (serving.decoding): what the slots hold, not
what the pool holds."""

from benchmark import harness, serving
from benchmark.layer_metrics import _shared


def read(result, cell, peaks):
    took = _shared.module_mean_s(result, "paged_decode_round")
    trace = result.get("trace")
    if not took or not trace:
        return None
    sequences, live_tokens = serving.decoding(
        result["records"], trace["t0"], trace["t0"] + trace["window_s"])
    if not sequences:
        return None
    cfg = cell["config_data"]
    ops = harness.load_by_name("ops", cfg["ops"]["decode_round"])
    least, _ = ops.least_seconds(cfg, sequences, live_tokens, peaks)
    return 100.0 * least / took
