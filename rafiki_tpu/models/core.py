"""Shared neural-net building blocks over plain dict pytrees.

Design notes (TPU-first):
- Weights are kept in float32 "master" precision; ``cast_for_compute``
  downcasts activations/weights to bfloat16 inside the forward pass so
  matmuls hit the MXU at full rate while the optimizer still sees f32.
- All shapes are static; anything sequence-like is padded by the caller.
- Initializers mirror the usual fan-in scalings (He for conv/relu, Xavier
  for dense/attention) without pulling in a layers framework.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def xavier_uniform(rng: jax.Array, shape: Sequence[int], in_axis: int = -2,
                   out_axis: int = -1, dtype=jnp.float32,
                   fan_in: Optional[int] = None,
                   fan_out: Optional[int] = None) -> jax.Array:
    """Explicit fan_in/fan_out override the axis-derived fans — used when the
    logical matmul shape differs from the stored param shape (e.g. a
    (dim, heads, dh) projection whose logical fan_out is heads*dh)."""
    if fan_in is None:
        fan_in = shape[in_axis]
    if fan_out is None:
        fan_out = shape[out_axis]
    limit = math.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(rng, tuple(shape), dtype, -limit, limit)


def he_normal(rng: jax.Array, shape: Sequence[int], fan_in: Optional[int] = None,
              dtype=jnp.float32) -> jax.Array:
    if fan_in is None:
        fan_in = int(np.prod(shape[:-1]))
    std = math.sqrt(2.0 / fan_in)
    return jax.random.normal(rng, tuple(shape), dtype) * std


def normal_init(rng: jax.Array, shape: Sequence[int], std: float = 0.02,
                dtype=jnp.float32) -> jax.Array:
    return jax.random.normal(rng, tuple(shape), dtype) * std


# ---------------------------------------------------------------------------
# layers (init + apply pairs)
# ---------------------------------------------------------------------------

def dense_init(rng: jax.Array, in_dim: int, out_dim: int) -> Params:
    kr, _ = jax.random.split(rng)
    return {
        "kernel": xavier_uniform(kr, (in_dim, out_dim)),
        "bias": jnp.zeros((out_dim,), jnp.float32),
    }


def dense(params: Params, x: jax.Array) -> jax.Array:
    return jnp.dot(x, params["kernel"].astype(x.dtype)) + params["bias"].astype(x.dtype)


def layernorm_init(dim: int) -> Params:
    return {"scale": jnp.ones((dim,), jnp.float32),
            "bias": jnp.zeros((dim,), jnp.float32)}


def layernorm(params: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    # LN statistics in f32 for stability even when x is bf16
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"] + params["bias"]
    return y.astype(x.dtype)


def rmsnorm_init(dim: int) -> Params:
    return {"scale": jnp.ones((dim,), jnp.float32)}


def rmsnorm(params: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """Root-mean-square norm, no centring and no bias; statistics in f32.
    Returns f32: what follows it is a product's input, which casts."""
    xf = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return xf * rms * params["scale"]


def group_rmsnorm(scale: jax.Array, x: jax.Array, groups: int,
                  eps: float = 1e-5) -> jax.Array:
    """RMS norm over each of `groups` equal runs of the last axis (the
    gated norm of a Mamba-2 mixer), then the elementwise scale; f32."""
    xf = x.astype(jnp.float32)
    g = xf.reshape(x.shape[:-1] + (groups, x.shape[-1] // groups))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(x.shape) * scale


def carried_conv(window: jax.Array, x: jax.Array, kernel: jax.Array,
                 lengths: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Causal depthwise convolution of ``x`` (B, T, C) continued from the
    last ``K - 1`` inputs ``window`` (B, K-1, C), ``kernel`` (K, C), no
    bias. Returns (the convolution (B, T, C), the window after each
    sequence's ``lengths[b]`` real tokens: padding past them moves none)."""
    k, t = kernel.shape[0], x.shape[1]
    seen = jnp.concatenate([window, x], axis=1)            # (B, K-1+T, C)
    conv = sum(seen[:, j:j + t] * kernel[j] for j in range(k))
    after = jax.vmap(lambda w, n: jax.lax.dynamic_slice_in_dim(
        w, n, k - 1, axis=0))(seen, lengths)
    return conv, after


def relu2(x: jax.Array) -> jax.Array:
    """Squared ReLU."""
    return jnp.square(jax.nn.relu(x))


def embedding_init(rng: jax.Array, vocab: int, dim: int, std: float = 0.02) -> Params:
    return {"table": normal_init(rng, (vocab, dim), std)}


def embedding(params: Params, ids: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return params["table"].astype(dtype)[ids]


def conv2d_init(rng: jax.Array, kh: int, kw: int, cin: int, cout: int) -> Params:
    return {
        "kernel": he_normal(rng, (kh, kw, cin, cout), fan_in=kh * kw * cin),
        "bias": jnp.zeros((cout,), jnp.float32),
    }


def conv2d(params: Params, x: jax.Array, stride: int = 1,
           padding: str = "SAME") -> jax.Array:
    """NHWC conv — the layout XLA:TPU tiles best onto the MXU."""
    y = jax.lax.conv_general_dilated(
        x, params["kernel"].astype(x.dtype),
        window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return y + params["bias"].astype(y.dtype)


def dropout(rng: Optional[jax.Array], x: jax.Array, rate: float,
            deterministic: bool) -> jax.Array:
    if deterministic or rate <= 0.0:
        return x
    assert rng is not None
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


def cast_for_compute(x: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# pytree helpers
# ---------------------------------------------------------------------------

def split_keys(rng: jax.Array, n: int) -> Tuple[jax.Array, ...]:
    return tuple(jax.random.split(rng, n))


def stack_layers(layer_params: Sequence[Params]) -> Params:
    """Stack per-layer param pytrees along a new leading axis so the forward
    pass can ``lax.scan`` over layers — one compiled block body regardless of
    depth (compile time O(1) in depth, and the natural layout for pipeline
    parallelism: shard the leading axis over the ``pipe`` mesh axis)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layer_params)


def param_count(params: Any) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
