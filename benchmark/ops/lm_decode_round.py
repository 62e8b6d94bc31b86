"""Operations and bytes one decode round NEEDS at the stated widths and
dtypes: every weight read once, the live keys and values of every resident
sequence read once, the new row written. What the program moves beyond that
(the gathered views of all slots at full context, copied and written back)
is what the roofline share is meant to show, so it is not counted here."""


def parameters(cfg: dict) -> int:
    dim, inner = cfg["n_embd"], 4 * cfg["n_embd"]
    block = (4 * dim * dim + dim + 2 * dim * inner + inner + dim + 4 * dim)
    return (cfg["n_layer"] * block + cfg["vocab_size"] * dim
            + cfg["n_positions"] * dim + 2 * dim)


def flops_per_token(cfg: dict) -> float:
    """2 operations for each parameter a token passes through: all of this
    dense model's but the position table, which is looked up."""
    return 2.0 * (parameters(cfg) - cfg["n_positions"] * cfg["n_embd"])


def flops(cfg: dict, sequences: int, live_tokens: float) -> float:
    """`live_tokens` is the sum over resident sequences of their lengths."""
    dim = cfg["n_embd"]
    matmul = flops_per_token(cfg) * sequences
    attention = 4.0 * cfg["n_layer"] * dim * live_tokens
    return matmul + attention


def bytes_moved(cfg: dict, sequences: int, live_tokens: float,
                weight_bytes: int = 4, kv_bytes: int = 4) -> float:
    dim = cfg["n_embd"]
    weights = weight_bytes * (parameters(cfg) - cfg["n_positions"] * dim)
    kv_read = 2.0 * cfg["n_layer"] * dim * kv_bytes * live_tokens
    kv_write = 2.0 * cfg["n_layer"] * dim * kv_bytes * sequences
    return weights + kv_read + kv_write


def least_seconds(cfg: dict, sequences: int, live_tokens: float,
                  peaks: dict) -> tuple:
    by_flops = flops(cfg, sequences, live_tokens) / peaks["bf16_flops_per_s"]
    by_bytes = bytes_moved(cfg, sequences, live_tokens) \
        / peaks["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes \
        else (by_bytes, "memory")
