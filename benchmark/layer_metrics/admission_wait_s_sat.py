"""Mean seconds a generate request waited at the streaming door from its
admission there until a worker's slot admitted it and the stream was handed
back (queueing behind busy slots, then the first prefill chunk): `_sum /
_count` of `rafiki_gen_door_ttft_seconds`, which `predictor/server.py`
observes around `Predictor.generate` (`REGISTRY`, read as
`serving._registry_total` reads). Over the life of the process, not the
window: the warm and check requests, which wait for no slot, are in the mean
(the harness snapshots `serving.COUNTERS` alone as the window opens)."""


def read(result, cell, peaks):
    from rafiki_tpu.utils.metrics import REGISTRY

    metric = REGISTRY.get("rafiki_gen_door_ttft_seconds")
    if metric is None or not result.get("records"):
        return None
    snaps = [c.snapshot() for c in metric.children().values()]
    count = sum(s["count"] for s in snaps)
    return sum(s["sum"] for s in snaps) / count if count else None
