"""Held experts that a decode round's tokens chose, mean a round a layer:
`rafiki_gen_experts_hit_total` over `rafiki_gen_expert_layer_rounds_total`,
which the worker adds up from what the decode program counts and returns
with its tokens. Over the life of the process, not the window (the harness
snapshots `serving.COUNTERS` alone as the window opens): the warm request's
few rounds, which hold one sequence, are in the mean. `info` takes the mean
number of (token, held expert) choices a round a layer beside it."""

from benchmark import serving
from benchmark.layer_metrics import _spans


def read(result, cell, peaks):
    if not result.get("records"):
        return None
    total = serving._registry_total  # 0.0 where the program has no such
    hit = total("rafiki_gen_experts_hit_total")
    rounds = total("rafiki_gen_expert_layer_rounds_total")
    if not hit or not rounds:
        return None
    _spans.info(result)["expert_tokens_mean"] = total(
        "rafiki_gen_expert_tokens_total") / rounds
    return hit / rounds
