"""Operations and bytes one decode round of a `nemotron_h` stack NEEDS at the
stated widths and dtypes, for the chip's share the configuration states
(`expert_share`, the sliced vocabulary): every mixer's weights read once;
every Mamba layer's state read and written for each resident sequence; the
live keys and values read once and the new rows written; the shared experts
and the head read once; and of the routed experts THOSE A ROUND'S TOKENS
HIT, expected over uniform routing, not all that are held: a token chooses
`num_experts_per_tok` of `expert_share.of`, so a held expert is missed by
one token with probability 1 - k/of and by all of a round's with that to
the power of the sequences. What the program moves beyond that (experts
read for no token, a view of every slot's whole context) is what the
roofline share is meant to show.

A token passes through the k * count/of routed experts that fall on this
chip in expectation (3 of its 6), not through all 6: the others' products
are the other chip's.
"""

BF16, F32 = 2, 4


def _sizes(cfg: dict) -> dict:
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    inner = heads * hd
    bc = 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    share = cfg["expert_share"]
    pattern = cfg["hybrid_override_pattern"]
    d = cfg["hidden_size"]
    return {
        "d": d, "n_m": pattern.count("M"), "n_e": pattern.count("E"),
        "n_a": pattern.count("*"),
        "m_matrix": d * (2 * inner + bc + heads) + inner * d,
        "m_small": (cfg["conv_kernel"] + 1) * (inner + bc) + 3 * heads
        + inner + d,
        "state": heads * hd * cfg["ssm_state_size"],
        "window": (cfg["conv_kernel"] - 1) * (inner + bc),
        "q": cfg["num_attention_heads"] * cfg["head_dim"],
        "kv": cfg["num_key_value_heads"] * cfg["head_dim"],
        "expert": 2 * d * cfg["moe_intermediate_size"],
        "shared": 2 * d * cfg["moe_shared_expert_intermediate_size"],
        "router": d * share["of"] + share["of"],
        "held": share["count"], "of": share["of"],
        "k": cfg["num_experts_per_tok"], "vocab": cfg["vocab_size"],
    }


def parameters(cfg: dict) -> int:
    """Parameters held on this chip."""
    z = _sizes(cfg)
    attn = 2 * z["d"] * z["q"] + 2 * z["d"] * z["kv"] + z["d"]
    expert_layer = (z["held"] * z["expert"] + z["shared"] + z["router"]
                    + z["d"])
    return (z["n_m"] * (z["m_matrix"] + z["m_small"]) + z["n_a"] * attn
            + z["n_e"] * expert_layer + 2 * z["vocab"] * z["d"] + z["d"])


def experts_hit(cfg: dict, sequences: float) -> float:
    """Held experts that at least one of `sequences` tokens chooses, a
    layer, expected over uniform routing."""
    z = _sizes(cfg)
    return z["held"] * (1.0 - (1.0 - z["k"] / z["of"]) ** sequences)


def flops_per_token(cfg: dict) -> float:
    """2 operations for each parameter a token passes through here: the
    mixers, the router, its routed experts that fall on this chip in
    expectation, the shared expert, the head; and the state-space step
    (decay, outer product, read-out: 6 a state element)."""
    z = _sizes(cfg)
    mamba = 2.0 * z["m_matrix"] + 6.0 * z["state"]
    attn = 2.0 * (2 * z["d"] * z["q"] + 2 * z["d"] * z["kv"])
    experts = 2.0 * (z["router"] + z["shared"]
                     + z["k"] * z["held"] / z["of"] * z["expert"])
    return (z["n_m"] * mamba + z["n_a"] * attn + z["n_e"] * experts
            + 2.0 * z["vocab"] * z["d"])


def flops(cfg: dict, sequences: float, live_tokens: float) -> float:
    """`live_tokens` is the sum over resident sequences of their lengths."""
    z = _sizes(cfg)
    attention = 4.0 * z["n_a"] * z["q"] * live_tokens
    return flops_per_token(cfg) * sequences + attention


def bytes_moved(cfg: dict, sequences: float, live_tokens: float) -> float:
    z = _sizes(cfg)
    mamba = z["n_m"] * (BF16 * z["m_matrix"] + F32 * z["m_small"]
                        + 2 * F32 * (z["state"] + z["window"]) * sequences)
    attn = z["n_a"] * (BF16 * (2 * z["d"] * z["q"] + 2 * z["d"] * z["kv"])
                       + 2 * BF16 * z["kv"] * (live_tokens + sequences))
    experts = z["n_e"] * (F32 * z["router"] + BF16 * z["shared"]
                          + BF16 * z["expert"] * experts_hit(cfg, sequences))
    head = BF16 * z["vocab"] * z["d"] + BF16 * z["d"] * sequences
    return mamba + attn + experts + head


def least_seconds(cfg: dict, sequences: float, live_tokens: float,
                  peaks: dict) -> tuple:
    by_flops = flops(cfg, sequences, live_tokens) / peaks["bf16_flops_per_s"]
    by_bytes = bytes_moved(cfg, sequences, live_tokens) \
        / peaks["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes \
        else (by_bytes, "memory")
