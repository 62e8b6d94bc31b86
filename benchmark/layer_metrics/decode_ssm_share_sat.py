"""The share of the decode program's device time spent in its Mamba layers:
the self time of the operations traced under `jax.named_scope("ssm")` (the
two projections, the convolution, the state's read, step and write, the
gated norm) over that of all the program's operations in the trace."""

from benchmark.layer_metrics import _scopes


def read(result, cell, peaks):
    return _scopes.scope_share(result, "paged_decode_round", "ssm")
