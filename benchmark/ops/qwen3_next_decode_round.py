"""Operations and bytes one decode round of a `qwen3_next` stack NEEDS at
the stated widths and dtypes, for the chip's share the configuration states
(`expert_share`, the sliced vocabulary): every mixer's weights read once;
every gated-delta layer's state (a key-by-value matrix a value head, f32)
and convolution window read and written for each resident sequence; the
live keys and values of the attention layers read once and the new rows
written; the shared experts, the routers and the head read once; and of the
routed experts THOSE A ROUND'S TOKENS HIT, expected over uniform routing,
not all that are held, three matrices each: a token chooses
`num_experts_per_tok` of `expert_share.of`, so a held expert is missed by
one token with probability 1 - k/of and by all of a round's with that to
the power of the sequences. What the program moves beyond that (the state
read once more than it is written, experts read for no token, a view wider
than the live context) is what the roofline share is meant to show.

A token passes through the k * count/of routed experts that fall on this
chip in expectation (2.5 of its 10), not through all 10: the others'
products are the other chips'.
"""

BF16, F32 = 2, 4


def _sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vh = cfg["linear_num_value_heads"]
    values = vh * cfg["linear_value_head_dim"]
    conv = 2 * keys + values
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    n = cfg["num_hidden_layers"]
    n_a = n // cfg["full_attention_interval"]
    share = cfg["expert_share"]
    return {
        "d": d, "n": n, "n_a": n_a, "n_d": n - n_a,
        "d_matrix": d * (conv + values) + d * 2 * vh + values * d,
        "d_small": cfg["linear_conv_kernel_dim"] * conv + 2 * vh
        + cfg["linear_value_head_dim"] + d,
        "state": vh * cfg["linear_key_head_dim"]
        * cfg["linear_value_head_dim"],
        "window": (cfg["linear_conv_kernel_dim"] - 1) * conv,
        "q": q, "kv": kv,
        "a_matrix": d * 2 * q + 2 * d * kv + q * d,
        "a_small": 2 * cfg["head_dim"] + d,
        "expert": 3 * d * cfg["moe_intermediate_size"],
        "shared": 3 * d * cfg["shared_expert_intermediate_size"] + d,
        "router": d * share["of"],
        "held": share["count"], "of": share["of"],
        "k": cfg["num_experts_per_tok"], "vocab": cfg["vocab_size"],
    }


def parameters(cfg: dict) -> int:
    """Parameters held on this chip."""
    z = _sizes(cfg)
    expert_block = z["held"] * z["expert"] + z["shared"] + z["router"] \
        + z["d"]
    return (z["n_d"] * (z["d_matrix"] + z["d_small"])
            + z["n_a"] * (z["a_matrix"] + z["a_small"])
            + z["n"] * expert_block + 2 * z["vocab"] * z["d"] + z["d"])


def experts_hit(cfg: dict, sequences: float) -> float:
    """Held experts that at least one of `sequences` tokens chooses, a
    layer, expected over uniform routing."""
    z = _sizes(cfg)
    return z["held"] * (1.0 - (1.0 - z["k"] / z["of"]) ** sequences)


def flops_per_token(cfg: dict) -> float:
    """2 operations for each parameter a token passes through here: the
    mixers, the router, its routed experts that fall on this chip in
    expectation, the shared expert, the head; and the delta rule's step
    (decay, `S^T k`, the rank-one update, `S^T q`: 7 a state element)."""
    z = _sizes(cfg)
    delta = 2.0 * z["d_matrix"] + 7.0 * z["state"]
    experts = 2.0 * (z["router"] + z["shared"]
                     + z["k"] * z["held"] / z["of"] * z["expert"])
    return (z["n_d"] * delta + z["n_a"] * 2.0 * z["a_matrix"]
            + z["n"] * experts + 2.0 * z["vocab"] * z["d"])


def flops(cfg: dict, sequences: float, live_tokens: float) -> float:
    """`live_tokens` is the sum over resident sequences of their lengths."""
    z = _sizes(cfg)
    attention = 4.0 * z["n_a"] * z["q"] * live_tokens
    return flops_per_token(cfg) * sequences + attention


def bytes_moved(cfg: dict, sequences: float, live_tokens: float) -> float:
    z = _sizes(cfg)
    delta = z["n_d"] * (BF16 * z["d_matrix"] + F32 * z["d_small"]
                        + 2 * F32 * (z["state"] + z["window"]) * sequences)
    attn = z["n_a"] * (BF16 * z["a_matrix"] + F32 * z["a_small"]
                       + 2 * BF16 * z["kv"] * (live_tokens + sequences))
    experts = z["n"] * (F32 * (z["router"] + z["d"]) + BF16 * z["shared"]
                        + BF16 * z["expert"] * experts_hit(cfg, sequences))
    head = BF16 * z["vocab"] * z["d"] + BF16 * z["d"] * sequences
    return delta + attn + experts + head


def least_seconds(cfg: dict, sequences: float, live_tokens: float,
                  peaks: dict) -> tuple:
    by_flops = flops(cfg, sequences, live_tokens) / peaks["bf16_flops_per_s"]
    by_bytes = bytes_moved(cfg, sequences, live_tokens) \
        / peaks["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes \
        else (by_bytes, "memory")
