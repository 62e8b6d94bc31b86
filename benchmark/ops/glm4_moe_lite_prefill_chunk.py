"""Operations and bytes one prefill chunk of a `glm4_moe_lite` stack NEEDS
at the stated widths and dtypes, for the chip's share the configuration
states: `tokens` new tokens of ONE sequence behind `context` tokens already
in the pool. Every token passes through the attention's matrices, the
feed-forward of its layer (of the routed experts those that fall on this
chip in expectation) and, the chunk's last token alone, the head. The
product over the latent rows is causal (`tokens * context` pairs of query
and row behind the chunk and `tokens * (tokens + 1) / 2` inside it) and is
counted in whichever form costs less at these shapes: absorbed, `2 * (2 *
kv_lora_rank + qk_rope_head_dim)` operations a head a pair; or expanded,
`2 * (qk_nope_head_dim + qk_rope_head_dim + v_head_dim)` a head a pair and
the keys and values of every row read, `2 * kv_lora_rank * (qk_nope_head_dim
+ v_head_dim)` a head a row. Bytes: the weights once (the held experts that
`tokens` tokens hit: all of them at 512), the rows behind the chunk read and
the new ones written. What the program does beyond that (a table as wide as
the served context whatever the prompt has reached, rows behind the mask
computed and thrown away, a last chunk padded to the bucket) is what the
roofline share is meant to show.
"""

from benchmark.ops import glm4_moe_lite_decode_round as decode

BF16, F32 = 2, 4


def attention_flops(cfg: dict, tokens: float, context: float) -> float:
    """The cheaper form's operations over the rows, all latent layers."""
    z = decode.sizes(cfg)
    pairs = tokens * context + tokens * (tokens + 1) / 2.0
    absorbed = 2.0 * (2 * z["kvr"] + z["rope"]) * pairs
    expanded = 2.0 * (z["nope"] + z["rope"] + z["v"]) * pairs \
        + 2.0 * z["kvr"] * (z["nope"] + z["v"]) * (context + tokens)
    return z["n"] * z["h"] * min(absorbed, expanded)


def flops(cfg: dict, tokens: float, context: float) -> float:
    z = decode.sizes(cfg)
    head = 2.0 * z["vocab"] * z["d"]
    return (decode.flops_per_token(cfg) - head) * tokens + head \
        + attention_flops(cfg, tokens, context)


def bytes_moved(cfg: dict, tokens: float, context: float) -> float:
    z = decode.sizes(cfg)
    latent = z["n"] * (BF16 * z["l_matrix"] + F32 * z["l_small"]
                       + BF16 * z["row"] * (context + tokens))
    dense = z["n_f"] * (BF16 * z["dense"] + F32 * z["d"])
    experts = z["n_e"] * (
        F32 * (z["router"] + z["d"]) + BF16 * z["shared"]
        + BF16 * z["expert"] * decode.experts_hit(cfg, tokens))
    head = BF16 * z["vocab"] * z["d"] + BF16 * z["d"] * tokens
    return latent + dense + experts + head


def least_seconds(cfg: dict, tokens: float, context: float,
                  peaks: dict) -> tuple:
    by_flops = flops(cfg, tokens, context) / peaks["bf16_flops_per_s"]
    by_bytes = bytes_moved(cfg, tokens, context) / peaks["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes \
        else (by_bytes, "memory")
