"""The share of the decode program's device time spent in its latent
attention layers: the self time of the operations traced under
`jax.named_scope("latent")` (the low-rank projections with their norms, the
rotary turn, the gather of the slots' latent rows through the block tables,
the absorbed product over them, the write of the new rows, the layer's norm
and residual) over that of all the program's operations in the trace.

Found as `decode_delta_share_sat.py` finds `delta`: `_scopes.scope_seconds`
gives the operations of a scope it does not know to `other`, so this reader
finds its one scope itself, over the same device operations
(`_scopes.device_ops`) and the same self pieces (`_spans.self_pieces`). An
operation is the latent layers' where `latent` is on its path and `_scopes`
finds no scope of its own further in. A program without the scope (the
parent's, another model's) reads nothing."""

from benchmark.layer_metrics import _scopes, _spans

PROGRAM = "paged_decode_round"
SCOPE = "latent"


def _in_scope(tf_op: str) -> bool:
    parts = tf_op.split(":")[0].split("/")
    if SCOPE not in parts:
        return False
    inner = parts[len(parts) - parts[::-1].index(SCOPE):]
    return _scopes._scope("/".join(inner)) == "other"


def read(result, cell, peaks):
    trace = result.get("trace")
    if not trace or not trace.get("path"):
        return None
    mine = total = 0.0
    for ops in _scopes.device_ops(trace["path"]):
        program = [(_in_scope(name), s, e) for name, s, e in ops
                   if name.startswith(f"jit({PROGRAM})")]
        for inside, s, e in _spans.self_pieces(program):
            total += e - s
            mine += (e - s) * inside
    return 100.0 * mine / total if mine else None
