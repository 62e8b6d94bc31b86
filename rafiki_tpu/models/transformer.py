"""Shared pre-LN transformer stack, scan-over-layers, sharding-annotated.

The layer stack is a single pytree whose leaves carry a leading ``depth``
axis (models/core.py ``stack_layers``), consumed by ``lax.scan`` — one
compiled block body regardless of depth. Partition specs shard:

- attention heads and MLP hidden over the ``model`` (TP) axis,
- the scanned ``depth`` axis over the ``pipe`` axis when pipeline parallelism
  is on (parallel/pipeline.py),
- activations batch over ``data`` and sequence over ``seq`` (SP).

This stack is what ViT/BERT instantiate; the reference has no transformer
at all (its deepest model is a TF1 ProGAN, reference pg_gans.py), so this
subsystem is part of the BASELINE.json north-star configs (ViT-B/16,
BERT-base) rather than a port.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from rafiki_tpu.models import core
from rafiki_tpu.ops.attention import attention_init, multi_head_attention

Params = Dict[str, Any]


@dataclass(frozen=True)
class TransformerConfig:
    dim: int = 768
    depth: int = 12
    heads: int = 12
    mlp_ratio: int = 4
    dropout: float = 0.0
    causal: bool = False
    # None = auto: flash once the (S,S) score tensors would crowd HBM
    # (ops/attention.py FLASH_SCORES_BYTES); XLA's fused attention is
    # faster below that
    use_flash: Optional[bool] = None
    # >0 replaces the MLP with an expert-parallel MoE (top-1, drop-free)
    moe_experts: int = 0
    # "ring" routes attention through parallel/ring.py when the current mesh
    # has a seq axis > 1: exact attention with k/v shards rotating over ICI,
    # sequence length scaling linearly in chips. None = GSPMD seq-sharding
    # of activations only (all-gather on the attention matmuls).
    seq_parallel: Optional[str] = None
    # Rematerialization of the scanned block body (the memory knob that lets
    # large batches fit HBM — without it lax.scan saves every layer's
    # activations for backward, ~0.4 GB/layer for ViT-B at batch 128):
    #   None   — save everything (fastest when it fits),
    #   "dots" — jax.checkpoint_policies.dots_with_no_batch_dims_saveable:
    #            projection/MLP matmul outputs are saved, attention scores
    #            and elementwise ops recomputed (the PaLM recipe — near-zero
    #            extra MXU work, (S,S) score tensors never saved),
    #   "full" — save only each block's input; backward re-runs the whole
    #            block forward (~33% extra hardware FLOPs, minimal memory).
    remat: Optional[str] = None
    # "gpipe" runs the depth stack through parallel/pipeline.py microbatch
    # pipelining when the current mesh has a pipe axis > 1: each stage holds
    # depth/n_stages layers, activations hop stage-to-stage over ICI. None =
    # GSPMD weight-sharding of the scanned depth axis.
    pipeline: Optional[str] = None
    n_microbatches: int = 4


def block_init(rng: jax.Array, cfg: TransformerConfig) -> Params:
    from rafiki_tpu.parallel.moe import moe_init

    k_attn, k_mlp1, k_mlp2 = jax.random.split(rng, 3)
    hidden = cfg.dim * cfg.mlp_ratio
    params = {
        "ln1": core.layernorm_init(cfg.dim),
        "attn": attention_init(k_attn, cfg.dim, cfg.heads),
        "ln2": core.layernorm_init(cfg.dim),
    }
    if cfg.moe_experts > 0:
        params["moe"] = moe_init(k_mlp1, cfg.dim, hidden, cfg.moe_experts)
    else:
        params["mlp"] = {
            "w1": core.dense_init(k_mlp1, cfg.dim, hidden),
            "w2": core.dense_init(k_mlp2, hidden, cfg.dim),
        }
    return params


def block_apply(params: Params, x: jax.Array, cfg: TransformerConfig,
                rng: Optional[jax.Array] = None,
                deterministic: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Returns (x, aux_loss) — aux is the MoE load-balancing term (0 for
    dense blocks)."""
    from rafiki_tpu.parallel.moe import moe_apply
    from rafiki_tpu.parallel.sharding import (
        current_mesh,
        mesh_axis_size,
        shard_activations,
    )

    x = shard_activations(x, ("data", "seq", None))
    r1 = r2 = None
    if rng is not None:
        r1, r2 = jax.random.split(rng)
    attn_fn = None
    if cfg.seq_parallel == "ring" and mesh_axis_size("seq") > 1:
        from rafiki_tpu.parallel.ring import ring_attention

        mesh = current_mesh()
        attn_fn = lambda q, k, v, causal: ring_attention(  # noqa: E731
            q, k, v, mesh, causal=causal)
    h = multi_head_attention(params["attn"], core.layernorm(params["ln1"], x),
                             causal=cfg.causal, use_flash=cfg.use_flash,
                             attn_fn=attn_fn)
    x = x + core.dropout(r1, h, cfg.dropout, deterministic)
    h = core.layernorm(params["ln2"], x)
    aux = jnp.zeros((), jnp.float32)
    if cfg.moe_experts > 0:
        h, aux = moe_apply(params["moe"], h)
    else:
        h = core.dense(params["mlp"]["w1"], h)
        h = jax.nn.gelu(h)
        h = core.dense(params["mlp"]["w2"], h)
    x = x + core.dropout(r2, h, cfg.dropout, deterministic)
    return x, aux


def stack_init(rng: jax.Array, cfg: TransformerConfig) -> Params:
    keys = jax.random.split(rng, cfg.depth)
    return core.stack_layers([block_init(k, cfg) for k in keys])


def stack_apply(stacked: Params, x: jax.Array, cfg: TransformerConfig,
                rng: Optional[jax.Array] = None,
                deterministic: bool = True) -> Tuple[jax.Array, jax.Array]:
    """scan over the depth-stacked block params -> (x, summed aux loss).

    With ``cfg.pipeline == 'gpipe'`` and a pipe axis > 1 on the current
    mesh, the scan is replaced by microbatch pipelining over the stages
    (parallel/pipeline.py) — each stage holds depth/n_stages layers and
    activations hop over ICI. The gpipe path is deterministic (no dropout
    rng threading across stages) and returns aux = 0.
    """
    from rafiki_tpu.parallel.sharding import (
        activation_mesh,
        current_mesh,
        mesh_axis_size,
    )

    def remat_wrap(fn):
        if cfg.remat == "dots":
            return jax.checkpoint(
                fn,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        if cfg.remat == "full":
            return jax.checkpoint(fn)
        if cfg.remat is not None:
            raise ValueError(f"unknown remat policy {cfg.remat!r} "
                             "(expected None, 'dots' or 'full')")
        return fn

    if cfg.pipeline == "gpipe" and mesh_axis_size("pipe") > 1:
        from rafiki_tpu.parallel.pipeline import gpipe_apply

        if cfg.moe_experts > 0:
            raise ValueError(
                "pipeline='gpipe' does not support MoE blocks (the stage "
                "body drops the load-balancing aux loss); use GSPMD pipe "
                "weight-sharding (pipeline=None) for MoE models")
        if mesh_axis_size("model") > 1:
            raise ValueError(
                "pipeline='gpipe' cannot combine with a model (TP) axis "
                "> 1: the pipeline shard_map claims stage weights whole, "
                "which would silently all-gather TP-sharded kernels; use "
                "GSPMD pipe weight-sharding (pipeline=None) with TP")
        depth = jax.tree.leaves(stacked)[0].shape[0]
        n_stages = mesh_axis_size("pipe")
        if depth % n_stages != 0:
            raise ValueError(
                f"stack depth {depth} not divisible by {n_stages} pipeline "
                "stages")
        if cfg.dropout > 0 and not deterministic:
            raise ValueError(
                "pipeline='gpipe' is deterministic (no dropout-rng "
                "threading across stages); set dropout=0 or pipeline=None")
        if x.shape[0] % cfg.n_microbatches != 0:
            raise ValueError(
                f"batch {x.shape[0]} not divisible by "
                f"n_microbatches={cfg.n_microbatches}")

        mesh = current_mesh()

        @remat_wrap
        def block_fn(layer, h):
            # plain per-stage compute: no activation sharding constraints or
            # nested shard_maps inside the pipeline's shard_map body
            with activation_mesh(None):
                y, _ = block_apply(layer, h, cfg, None, True)
            return y

        y = gpipe_apply(block_fn, stacked, x, mesh,
                        n_microbatches=cfg.n_microbatches)
        return y, jnp.zeros((), jnp.float32)

    block = remat_wrap(lambda layer, h, sub: block_apply(
        layer, h, cfg, sub, deterministic))

    def body(carry, layer):
        x, key = carry
        sub = None
        if key is not None:
            key, sub = jax.random.split(key)
        y, aux = block(layer, x, sub)
        return (y, key), aux

    (x, _), auxs = jax.lax.scan(body, (x, rng), stacked)
    return x, jnp.sum(auxs)


def block_partition_specs(cfg: TransformerConfig, stacked: bool = True) -> Params:
    """PartitionSpecs for one block (or the depth-stacked pytree).

    TP sharding follows the megatron split: column-parallel qkv/w1, row-
    parallel wo/w2 — XLA inserts the psum on the row-parallel matmul's
    output over ICI.
    """
    from rafiki_tpu.parallel.moe import moe_partition_specs

    lead = ("pipe",) if stacked else ()

    def spec(*axes):
        return P(*(lead + axes))

    specs = {
        "ln1": {"scale": spec(None), "bias": spec(None)},
        "attn": {
            "wq": spec(None, "model", None),
            "wk": spec(None, "model", None),
            "wv": spec(None, "model", None),
            "wo": spec("model", None, None),
            "bo": spec(None),
        },
        "ln2": {"scale": spec(None), "bias": spec(None)},
    }
    if cfg.moe_experts > 0:
        specs["moe"] = jax.tree.map(
            lambda s: P(*(lead + tuple(s))), moe_partition_specs(),
            is_leaf=lambda x: isinstance(x, P))
    else:
        specs["mlp"] = {
            "w1": {"kernel": spec(None, "model"), "bias": spec("model")},
            "w2": {"kernel": spec("model", None), "bias": spec(None)},
        }
    return specs
