"""Operations and bytes of one ViT training step (forward, backward, no
optimizer matmuls), from the configuration's sizes. Copied from
bench_models.vit_train_flops: a multiply-add is 2 operations, backward is
twice forward, matrix and convolution terms only. The step is bound by
compute at batch 32 (its bytes are the weights, their gradient and Adam's
state, read and written once: 16 bytes a parameter plus activations)."""


def flops(cfg: dict, batch: int) -> float:
    seq = (cfg["image_size"] // cfg["patch_size"]) ** 2
    dim, hidden = cfg["hidden_size"], cfg["intermediate_size"]
    per_block = (8 * seq * dim * dim          # q, k, v and output projections
                 + 4 * seq * seq * dim        # scores and weighted values
                 + 4 * seq * dim * hidden)    # MLP in and out
    patch = 2 * seq * dim * cfg["patch_size"] ** 2 * cfg["num_channels"]
    head = 2 * dim * cfg["assumed"]["num_labels"]
    forward = cfg["num_hidden_layers"] * per_block + patch + head
    return 3.0 * forward * batch


def parameters(cfg: dict) -> int:
    seq = (cfg["image_size"] // cfg["patch_size"]) ** 2
    dim, hidden = cfg["hidden_size"], cfg["intermediate_size"]
    block = (4 * dim * dim + dim               # attention, output bias
             + 2 * dim * hidden + hidden + dim  # MLP with biases
             + 4 * dim)                         # two LayerNorms
    return (cfg["num_hidden_layers"] * block
            + cfg["patch_size"] ** 2 * cfg["num_channels"] * dim + dim
            + seq * dim + 2 * dim
            + (dim + 1) * cfg["assumed"]["num_labels"])


def bytes_moved(cfg: dict, batch: int) -> float:
    """f32 weights read, gradient written and read, weights and both Adam
    moments read and written: 28 bytes a parameter; activations left out."""
    return 28.0 * parameters(cfg)


def least_seconds(cfg: dict, batch: int, peaks: dict) -> tuple:
    """(least time the chip could take, which peak bounds it)."""
    by_flops = flops(cfg, batch) / peaks["bf16_flops_per_s"]
    by_bytes = bytes_moved(cfg, batch) / peaks["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes \
        else (by_bytes, "memory")
