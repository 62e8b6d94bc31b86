"""Multi-head attention: XLA reference semantics + flash-kernel dispatch.

``mha_reference`` is the ground truth (used for gradients and for unit-test
comparison); ``multi_head_attention`` is the layer the model zoo calls —
projections + attention + output projection over a plain param dict, routing
the inner attention to the pallas flash kernel when profitable.
"""

from __future__ import annotations

import math
import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from rafiki_tpu.models import core

Params = Dict[str, Any]

# Auto-dispatch threshold: route to the flash kernel once the f32 (S, S)
# score tensor (4*B*H*S^2 bytes) would crowd HBM. Below it XLA's fused
# attention is FASTER on TPU (measured fwd+bwd at B4/H12: 14 vs 22 ms at
# seq 2048, 50 vs 65 ms at 4096) — flash's win is memory, not speed: at
# seq 8192 the same shape needs ~13 GB of scores and fails to compile,
# while flash runs it in 242 ms. 1 GB default leaves room for the scores
# XLA saves for backward alongside params/activations.
def _flash_threshold_bytes() -> int:
    raw = os.environ.get("RAFIKI_FLASH_THRESHOLD_BYTES", str(1 << 30))
    try:
        return int(raw)
    except ValueError:
        raise ValueError(
            f"RAFIKI_FLASH_THRESHOLD_BYTES={raw!r} must be a plain integer "
            "byte count (e.g. 1073741824)") from None


FLASH_SCORES_BYTES = _flash_threshold_bytes()


def mha_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = False,
                  sm_scale: Optional[float] = None) -> jax.Array:
    """Plain attention over (B, H, S, Dh); softmax statistics in f32."""
    dh = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(dh)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sq, skv = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, skv), bool), k=skv - sq)
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def attention_init(rng: jax.Array, dim: int, heads: int) -> Params:
    """QKV + output projection params. Head axis kept explicit so tensor
    parallelism can shard it (heads over the ``model`` mesh axis)."""
    dh = dim // heads
    kq, kk, kv, ko = jax.random.split(rng, 4)
    # fans of the *logical* dim -> heads*dh projection, not the per-head
    # slice — matches the standard init of the fused (dim, dim) matmul
    shape = (dim, heads, dh)
    return {
        "wq": core.xavier_uniform(kq, shape, fan_in=dim, fan_out=heads * dh),
        "wk": core.xavier_uniform(kk, shape, fan_in=dim, fan_out=heads * dh),
        "wv": core.xavier_uniform(kv, shape, fan_in=dim, fan_out=heads * dh),
        "wo": core.xavier_uniform(ko, (heads, dh, dim), fan_in=heads * dh,
                                  fan_out=dim),
        "bo": jnp.zeros((dim,), jnp.float32),
    }


def multi_head_attention(params: Params, x: jax.Array,
                         causal: bool = False,
                         use_flash: Optional[bool] = None,
                         attn_fn=None) -> jax.Array:
    """Self-attention over (B, S, D). ``use_flash=None`` auto-selects the
    pallas kernel once the (S, S) score tensors would crowd HBM (see
    FLASH_SCORES_BYTES — below that, XLA's fused attention is faster).
    ``attn_fn(q, k, v, causal)`` overrides the inner attention entirely
    (the seam ring attention plugs into — see models/transformer.py
    seq_parallel)."""
    from rafiki_tpu.ops.flash_attention import flash_attention

    b, s, d = x.shape
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bhsk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bhsk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bhsk", x, params["wv"].astype(dt))
    n_heads = params["wq"].shape[1]
    scores_bytes = 4 * b * n_heads * s * s
    if attn_fn is not None:
        o = attn_fn(q, k, v, causal)
    elif use_flash or (use_flash is None
                       and jax.default_backend() == "tpu"
                       and scores_bytes > FLASH_SCORES_BYTES):
        # compiled (Mosaic) or it raises: an explicit use_flash=True off
        # the TPU is a caller error, never a silent trip to the interpreter
        o = flash_attention(q, k, v, causal=causal)
    else:
        o = mha_reference(q, k, v, causal=causal)
    out = jnp.einsum("bhsk,hkd->bsd", o, params["wo"].astype(dt))
    return out + params["bo"].astype(dt)


def gqa_init(rng: jax.Array, dim: int, q_heads: int, kv_heads: int,
             head_dim: int, dtype=jnp.float32) -> Params:
    """Grouped-query projections with no bias, heads side by side in the
    minor axis; `q_heads * head_dim` need not be `dim`."""
    kq, kk, kv, ko = jax.random.split(rng, 4)
    q, kvd = q_heads * head_dim, kv_heads * head_dim
    into = dim ** -0.5  # by fan-in
    return {"wq": core.normal_init(kq, (dim, q), std=into, dtype=dtype),
            "wk": core.normal_init(kk, (dim, kvd), std=into, dtype=dtype),
            "wv": core.normal_init(kv, (dim, kvd), std=into, dtype=dtype),
            "wo": core.normal_init(ko, (q, dim), std=q ** -0.5,
                                   dtype=dtype)}


def gated_gqa_init(rng: jax.Array, dim: int, q_heads: int, kv_heads: int,
                   head_dim: int, dtype=jnp.float32) -> Params:
    """:func:`gqa_init` with what a gated rotary layer adds: the query
    projection twice as wide (a head's query, then its output gate) and an
    RMSNorm scale over a head for queries and for keys."""
    p = gqa_init(rng, dim, q_heads, kv_heads, head_dim, dtype)
    wide = core.normal_init(jax.random.fold_in(rng, 1),
                            (dim, 2 * q_heads * head_dim), std=dim ** -0.5,
                            dtype=dtype)
    return {**p, "wq": wide, "q_norm": core.rmsnorm_init(head_dim),
            "k_norm": core.rmsnorm_init(head_dim)}


def rotary(x: jax.Array, positions: jax.Array, rotary_dim: int,
           theta: float) -> jax.Array:
    """Rotary position embedding on the first ``rotary_dim`` of each head,
    the rest passed through: ``x`` (B, T, H, Dh) f32, ``positions`` (B, T).
    Dimension i < rotary_dim / 2 turns with i + rotary_dim / 2
    ("rotate-half") by ``positions * theta^(-2i / rotary_dim)``."""
    half = rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, :, None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b, rest = jnp.split(x, [half, rotary_dim], axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


def gqa_cached(q: jax.Array, lk: jax.Array, lv: jax.Array,
               positions: jax.Array) -> jax.Array:
    """Grouped-query attention of new tokens over a cache view, with no
    position encoding. ``q`` (B, T, Hq, Dh); ``lk``/``lv`` (B, L, Hkv, Dh)
    hold the new tokens' rows already; a query at ``positions[b, i]``
    attends rows up to its own. Query head ``h`` reads key/value head
    ``h // (Hq // Hkv)``. Returns (B, T, Hq * Dh) in q's dtype; softmax
    statistics in f32."""
    b, t, hq, dh = q.shape
    hkv = lk.shape[2]
    qg = q.reshape(b, t, hkv, hq // hkv, dh)
    s = jnp.einsum("btgrk,blgk->bgrtl", qg, lk.astype(q.dtype),
                   preferred_element_type=jnp.float32) / math.sqrt(dh)
    mask = jnp.arange(lk.shape[1])[None, None, :] <= positions[:, :, None]
    s = jnp.where(mask[:, None, None], s, -1e30)
    a = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bgrtl,blgk->btgrk", a, lv.astype(q.dtype))
    return o.reshape(b, t, hq * dh)
