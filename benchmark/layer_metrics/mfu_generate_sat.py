"""The whole serving step's share of the chip's peak: 2 operations for each
parameter a token passes through (position table left out), for every
prompt token prefilled and every answer token received in the window, over
the window's seconds times the bf16 peak."""

from benchmark.ops import lm_decode_round


def read(result, cell, peaks):
    cfg = cell["config_data"]
    tokens = result["tokens_in_window"] + result["prompt_tokens_in_window"]
    if not tokens:
        return None
    per_token = 2.0 * (lm_decode_round.parameters(cfg)
                       - cfg["n_positions"] * cfg["n_embd"])
    window = result["t1"] - result["t0"]
    return 100.0 * per_token * tokens / (window * peaks["bf16_flops_per_s"])
