"""Operations and bytes one decode round of a `glm4_moe_lite` stack NEEDS at
the stated widths and dtypes, for the chip's share the configuration states
(`expert_share`, the sliced vocabulary): every latent-attention layer's
weights read once; the LIVE latent rows read once, `kv_lora_rank +
qk_rope_head_dim` numbers a token a layer in bfloat16 (1,152 bytes: keys and
values are never made), and the new rows written; the dense MLP, the shared
experts, the routers and the head read once; and of the routed experts THOSE
A ROUND'S TOKENS HIT, expected over uniform routing, not all that are held,
three matrices each: a token chooses `num_experts_per_tok` of
`expert_share.of`, so a held expert is missed by one token with probability
1 - k/of and by all of a round's with that to the power of the sequences.
The product over the rows is counted in the absorbed form, the cheaper for
one query a sequence: `2 * (2 * kv_lora_rank + qk_rope_head_dim)` operations
a head a live row. What the program moves beyond that (a gathered view of
the rows, written and read again; a view wider than the live context;
experts read for no token) is what the roofline share is meant to show.

A token passes through the k * count/of routed experts that fall on this
chip in expectation (2 of its 4), not through all 4: the others' products
are the other chip's.
"""

BF16, F32 = 2, 4


def sizes(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, v = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    rope = int(cfg["qk_rope_head_dim"] * cfg["partial_rotary_factor"])
    n = cfg["num_hidden_layers"]
    n_f = cfg["first_k_dense_replace"]
    share = cfg["expert_share"]
    return {
        "d": d, "h": h, "n": n, "n_f": n_f, "n_e": n - n_f,
        "kvr": kvr, "nope": nope, "rope": rope, "v": v, "row": kvr + rope,
        "l_matrix": d * qr + qr * h * (nope + rope) + d * (kvr + rope)
        + kvr * h * (nope + v) + h * v * d,
        "l_small": d + qr + kvr,
        "dense": 3 * d * cfg["intermediate_size"],
        "expert": 3 * d * cfg["moe_intermediate_size"],
        "shared": 3 * d * cfg["moe_intermediate_size"]
        * cfg["n_shared_experts"],
        "router": d * share["of"] + share["of"],
        "held": share["count"], "of": share["of"],
        "k": cfg["num_experts_per_tok"], "vocab": cfg["vocab_size"],
    }


def parameters(cfg: dict) -> int:
    """Parameters held on this chip."""
    z = sizes(cfg)
    expert_block = z["held"] * z["expert"] + z["shared"] + z["router"] \
        + z["d"]
    return (z["n"] * (z["l_matrix"] + z["l_small"])
            + z["n_f"] * (z["dense"] + z["d"]) + z["n_e"] * expert_block
            + 2 * z["vocab"] * z["d"] + z["d"])


def experts_hit(cfg: dict, sequences: float) -> float:
    """Held experts that at least one of `sequences` tokens chooses, a
    layer, expected over uniform routing."""
    z = sizes(cfg)
    return z["held"] * (1.0 - (1.0 - z["k"] / z["of"]) ** sequences)


def flops_per_token(cfg: dict) -> float:
    """2 operations for each parameter a token passes through here: the
    attention's five matrices (a head's share of `W_ukv` once, folded into
    the query and the output or applied to the token's own row), the dense
    MLP, the router, its routed experts that fall on this chip in
    expectation, the shared expert, the head."""
    z = sizes(cfg)
    experts = 2.0 * (z["router"] + z["shared"]
                     + z["k"] * z["held"] / z["of"] * z["expert"])
    return (z["n"] * 2.0 * z["l_matrix"] + z["n_f"] * 2.0 * z["dense"]
            + z["n_e"] * experts + 2.0 * z["vocab"] * z["d"])


def flops(cfg: dict, sequences: float, live_tokens: float) -> float:
    """`live_tokens` is the sum over resident sequences of their lengths."""
    z = sizes(cfg)
    attention = 2.0 * z["n"] * z["h"] * (2 * z["kvr"] + z["rope"]) \
        * live_tokens
    return flops_per_token(cfg) * sequences + attention


def bytes_moved(cfg: dict, sequences: float, live_tokens: float) -> float:
    z = sizes(cfg)
    latent = z["n"] * (BF16 * z["l_matrix"] + F32 * z["l_small"]
                       + BF16 * z["row"] * (live_tokens + sequences))
    dense = z["n_f"] * (BF16 * z["dense"] + F32 * z["d"])
    experts = z["n_e"] * (F32 * (z["router"] + z["d"]) + BF16 * z["shared"]
                          + BF16 * z["expert"] * experts_hit(cfg, sequences))
    head = BF16 * z["vocab"] * z["d"] + BF16 * z["d"] * sequences
    return latent + dense + experts + head


def least_seconds(cfg: dict, sequences: float, live_tokens: float,
                  peaks: dict) -> tuple:
    by_flops = flops(cfg, sequences, live_tokens) / peaks["bf16_flops_per_s"]
    by_bytes = bytes_moved(cfg, sequences, live_tokens) \
        / peaks["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes \
        else (by_bytes, "memory")
