"""The share of the decode program's device time spent in its expert layers:
the self time of the operations traced under `jax.named_scope("moe")`
(router, the loop over the experts hit, the shared expert, the layer's norm
and residual) over that of all the program's operations in the trace."""

from benchmark.layer_metrics import _scopes


def read(result, cell, peaks):
    return _scopes.scope_share(result, "paged_decode_round", "moe")
