"""Ring attention: exact attention over a sequence sharded across chips.

Long-context first-class support: each chip holds a sequence shard of q/k/v;
k/v shards rotate around the ``seq`` mesh axis via ``lax.ppermute`` (ICI
neighbour hops) while each chip accumulates its q-shard's attention with
online-softmax statistics — so the full (S, S) score matrix never exists on
any chip and sequence length scales linearly with the number of chips. The
communication pattern matches Ring Attention (blockwise transformers); the
compute per hop is the same online-softmax update as the flash kernel
(ops/flash_attention.py) applied to one (S_local, S_local) tile.

Gradients flow through ``lax.scan`` + ``ppermute`` natively, so this is
trainable without a custom VJP.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from rafiki_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS

NEG_INF = -1e30


def _ring_local(q: jax.Array, k: jax.Array, v: jax.Array, *, axis_name: str,
                causal: bool, sm_scale: Optional[float]) -> jax.Array:
    """Per-shard body (inside shard_map): q,k,v are (B, H, S_local, Dh)."""
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    s_local = q.shape[2]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    qf = q.astype(jnp.float32) * scale
    perm = [(r, (r + 1) % n) for r in range(n)]

    q_pos = my * s_local + jax.lax.broadcasted_iota(
        jnp.int32, (s_local, s_local), 0)

    def step(carry, i):
        o, m, l, k_cur, v_cur = carry
        src = (my - i) % n  # whose kv shard we hold at step i
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_cur.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        if causal:
            k_pos = src * s_local + jax.lax.broadcasted_iota(
                jnp.int32, (s_local, s_local), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        o_new = o * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (o_new, m_new, l_new, k_nxt, v_nxt), None

    b, h, _, dh = q.shape
    o0 = jnp.zeros((b, h, s_local, dh), jnp.float32)
    m0 = jnp.full((b, h, s_local, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_local, 1), jnp.float32)
    (o, _, l, _, _), _ = jax.lax.scan(
        step, (o0, m0, l0, k, v), jnp.arange(n))
    return (o / jnp.maximum(l, 1e-30)).astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                   causal: bool = False, sm_scale: Optional[float] = None,
                   seq_axis: str = SEQ_AXIS,
                   data_axis: str = DATA_AXIS) -> jax.Array:
    """Exact attention over (B, H, S, Dh) with S sharded over ``seq_axis``
    and B over ``data_axis`` of `mesh`. S must divide by the seq axis size."""
    spec = P(data_axis, None, seq_axis, None)
    fn = jax.shard_map(
        partial(_ring_local, axis_name=seq_axis, causal=causal,
                sm_scale=sm_scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
