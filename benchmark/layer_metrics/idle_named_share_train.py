"""The share of the device's idle-gap seconds that lie inside a span of the
train worker: the trial spans (which also hold what began before the trace
opened), placed on the trace's clock as `_shared.reduced` places them.
`info` names the seconds by the innermost span: `persist.dump`, `.serialize`
and `.write` where the program has them, `persist_params` where not."""

from benchmark.layer_metrics import _spans


def read(result, cell, peaks):
    return _spans.idle_named_share(result, _spans.trial_spans(result))
