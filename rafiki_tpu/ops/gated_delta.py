"""Gated delta-rule mixer (Yang, Kautz & Hatamizadeh 2024, "Gated Delta
Networks") over a carried state.

One function for ``T`` tokens from a given state: a prefill chunk continues
the slot's state through the chunked form, and ``T = 1`` is the decode step,
the recurrence itself. The state is what a slot keeps in place of keys and
values: a matrix a value head, ``S`` (value heads, key dim, value dim) in
f32, and the last ``conv_kernel - 1`` inputs of the depthwise convolution.

    [q | k | v | z] = u W_qkvz;  [b | a] = u W_ba
    [q | k | v] = silu(conv1d_causal([q | k | v]))
    beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
    q, k: L2-normalised a head, q scaled by key_dim^-1/2, a key head
          repeated for its value heads
    S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T
    o_t = S^T q_t
    out = (RMSNorm_head(o) * silu(z)) W_out

The update reads the state before it writes (``v - S^T k``), so a chunk is
not a plain scan: inside a chunk of ``chunk_size`` tokens the tokens' own
updates are solved for at once (the WY form: a unit lower-triangular system
over the chunk's keys), between chunks the state is carried as the
recurrence carries it. A chunk of one token is the recurrence.

The projections run as the rest of the model's products do (bf16 operands,
f32 accumulation); the rule, its gates and norms are f32 at ``highest``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from rafiki_tpu.models import core

Params = Dict[str, Any]
HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class GatedDeltaConfig:
    dim: int = 256
    key_heads: int = 2
    value_heads: int = 4
    key_dim: int = 16        # of a head
    value_dim: int = 16      # of a head
    conv_kernel: int = 4
    chunk_size: int = 64
    eps: float = 1e-6

    @property
    def keys(self) -> int:
        return self.key_heads * self.key_dim

    @property
    def values(self) -> int:
        return self.value_heads * self.value_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.keys + self.values

    @property
    def in_cols(self) -> int:
        return self.conv_dim + self.values


def gated_delta_init(rng: jax.Array, cfg: GatedDeltaConfig,
                     dtype=jnp.float32) -> Params:
    k_in, k_ba, k_conv, k_dt, k_a, k_out = jax.random.split(rng, 6)
    into = cfg.dim ** -0.5  # by fan-in
    return {
        "w_qkvz": core.normal_init(k_in, (cfg.dim, cfg.in_cols), std=into,
                                   dtype=dtype),
        "w_ba": core.normal_init(k_ba, (cfg.dim, 2 * cfg.value_heads),
                                 std=into, dtype=dtype),
        "conv_w": core.normal_init(k_conv, (cfg.conv_kernel, cfg.conv_dim),
                                   std=0.4),
        "dt_bias": -3.0 + core.normal_init(k_dt, (cfg.value_heads,), std=1.0),
        "A_log": core.normal_init(k_a, (cfg.value_heads,), std=0.7),
        "onorm": jnp.ones((cfg.value_dim,), jnp.float32),
        "w_out": core.normal_init(k_out, (cfg.values, cfg.dim),
                                  std=cfg.values ** -0.5, dtype=dtype),
    }


def gated_delta_state_init(cfg: GatedDeltaConfig,
                           slots: int) -> Dict[str, jax.Array]:
    """A zero state for ``slots`` sequences."""
    return {"conv": jnp.zeros((slots, cfg.conv_kernel - 1, cfg.conv_dim),
                              jnp.float32),
            "s": jnp.zeros((slots, cfg.value_heads, cfg.key_dim,
                            cfg.value_dim), jnp.float32)}


def _step(q, k, v, g, beta, s):
    """The recurrence for one token: q/k (B, H, K), v (B, H, V), g/beta
    (B, H), s (B, H, K, V). The state is read twice and written once: one
    pass gives both of its products (``S^T k``, ``S^T q``; the decay is a
    scalar a head and is applied to them, not to a copy of the state), one
    pass decays and updates it. The output follows from the products, since
    ``(S + k d^T)^T q = S^T q + d (k . q)``."""
    decay = jnp.exp(g)[..., None]
    kq = jnp.stack([k, q], axis=2)                           # (B, H, 2, K)
    sk, sq = jnp.moveaxis(
        jnp.sum(s[:, :, None] * kq[..., None], axis=-2), 2, 0)
    d = beta[..., None] * (v - decay * sk)
    o = decay * sq + d * jnp.sum(k * q, axis=-1, keepdims=True)
    return o, decay[..., None] * s + k[..., None] * d[..., None, :]


def _chunks(q, k, v, g, beta, s, size: int):
    """``T`` tokens from state ``s`` in chunks of ``size``: q/k (B, T, H, K),
    v (B, T, H, V), g/beta (B, T, H), s (B, H, K, V). Returns (o (B, T, H,
    V), s after the last token). A token with g = 0 and beta = 0 leaves the
    state as it was."""
    b, t, heads, _ = q.shape
    c = min(size, t)
    pad = -t % c
    if pad:  # whole chunks: the tail's g and beta are 0
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    nc = (t + pad) // c
    # (chunks, B, H, C, ...): a head's chunk is a matrix
    split = lambda a: jnp.moveaxis(
        a.reshape((b, nc, c) + a.shape[2:]), (1, 3), (0, 2))
    lower = jnp.tril(jnp.ones((c, c), bool))
    strict = jnp.tril(jnp.ones((c, c), bool), k=-1)
    eye = jnp.eye(c, dtype=jnp.float32)

    def one(s, chunk):
        q_c, k_c, v_c, g_c, beta_c = chunk          # (B, H, C, .), (B, H, C)
        cum = jnp.cumsum(g_c, axis=-1)
        # decay from token j (after its own step) to token i, i >= j
        decay = jnp.exp(jnp.where(lower, cum[..., :, None]
                                  - cum[..., None, :], -jnp.inf))
        kk = jnp.einsum("bhik,bhjk->bhij", k_c, k_c, precision=HIGHEST)
        a = jnp.where(strict, kk * decay * beta_c[..., None], 0.0)
        # every token's own update, given the state the chunk starts from:
        # (I + A) [u | w] = beta [v | k exp(cum)]
        rhs = jnp.concatenate(
            [v_c, k_c * jnp.exp(cum)[..., None]], axis=-1) \
            * beta_c[..., None]
        solved = jax.lax.linalg.triangular_solve(
            eye + a, rhs, left_side=True, lower=True, unit_diagonal=True)
        u, w = jnp.split(solved, [v_c.shape[-1]], axis=-1)
        d = u - jnp.einsum("bhik,bhkv->bhiv", w, s, precision=HIGHEST)
        qk = jnp.einsum("bhik,bhjk->bhij", q_c, k_c, precision=HIGHEST)
        o = jnp.einsum("bhik,bhkv->bhiv", q_c * jnp.exp(cum)[..., None], s,
                       precision=HIGHEST) \
            + jnp.einsum("bhij,bhjv->bhiv", jnp.where(lower, qk * decay, 0.0),
                         d, precision=HIGHEST)
        last = cum[..., -1:]
        s = s * jnp.exp(last)[..., None] + jnp.einsum(
            "bhik,bhiv->bhkv", k_c * jnp.exp(last - cum)[..., None], d,
            precision=HIGHEST)
        return s, o

    s, os = jax.lax.scan(one, s, tuple(split(a) for a in (q, k, v, g, beta)))
    o = jnp.moveaxis(os, (0, 2), (1, 3)).reshape(b, t + pad, heads, -1)
    return o[:, :t], s


def _l2norm(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def gated_delta_mixer(p: Params, u: jax.Array, state: Dict[str, jax.Array],
                      lengths: jax.Array, cfg: GatedDeltaConfig
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``u`` (B, T, D), already normalised; ``state`` the B sequences' own
    (``conv`` (B, K-1, C), ``s`` (B, Hv, Dk, Dv)); ``lengths`` (B,) how many
    of the T tokens are real (the rest is padding after them and moves no
    state). Returns (out (B, T, D) f32, the state after the last real
    token)."""
    b, t, _ = u.shape
    kh, vh, kd, vd = (cfg.key_heads, cfg.value_heads, cfg.key_dim,
                      cfg.value_dim)
    dt_w = p["w_qkvz"].dtype
    uw = u.astype(dt_w)
    qkvz = jnp.dot(uw, p["w_qkvz"], preferred_element_type=jnp.float32)
    ba = jnp.dot(uw, p["w_ba"], preferred_element_type=jnp.float32)
    qkv, z = jnp.split(qkvz, [cfg.conv_dim], axis=-1)
    conv, conv_state = core.carried_conv(state["conv"], qkv, p["conv_w"],
                                         lengths)
    q, k, v = jnp.split(jax.nn.silu(conv), [cfg.keys, 2 * cfg.keys], axis=-1)
    per = vh // kh  # value head h reads key head h // per
    q = jnp.repeat(_l2norm(q.reshape(b, t, kh, kd)) * kd ** -0.5, per, axis=2)
    k = jnp.repeat(_l2norm(k.reshape(b, t, kh, kd)), per, axis=2)
    v = v.reshape(b, t, vh, vd)
    beta_in, a_in = jnp.split(ba, 2, axis=-1)                # (B, T, Hv)
    real = (jnp.arange(t)[None, :] < lengths[:, None])[..., None]
    beta = jnp.where(real, jax.nn.sigmoid(beta_in), 0.0)
    g = jnp.where(real, -jnp.exp(p["A_log"])
                  * jax.nn.softplus(a_in + p["dt_bias"]), 0.0)
    if t == 1:
        o, s = _step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                     state["s"])
        o = o[:, None]
    else:
        o, s = _chunks(q, k, v, g, beta, state["s"], cfg.chunk_size)
    o = core.rmsnorm({"scale": p["onorm"]}, o, cfg.eps)       # a head each
    o = o.reshape(b, t, cfg.values) * jax.nn.silu(z)
    out = jnp.dot(o.astype(dt_w), p["w_out"],
                  preferred_element_type=jnp.float32)
    return out, {"conv": conv_state, "s": s}
