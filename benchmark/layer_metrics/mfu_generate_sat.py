"""The whole serving step's share of the chip's peak: the operations a token
costs (the configuration's `ops.decode_round` module says: 2 for each
parameter the token passes through), for every prompt token prefilled and
every answer token received in the window, over the window's seconds times
the bf16 peak."""

from benchmark import harness


def read(result, cell, peaks):
    cfg = cell["config_data"]
    tokens = result["tokens_in_window"] + result["prompt_tokens_in_window"]
    if not tokens:
        return None
    ops = harness.load_by_name("ops", cfg["ops"]["decode_round"])
    window = result["t1"] - result["t0"]
    return (100.0 * ops.flops_per_token(cfg) * tokens
            / (window * peaks["bf16_flops_per_s"]))
