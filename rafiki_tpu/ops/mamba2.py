"""Mamba-2 mixer (state-space duality, Dao & Gu 2024) over a carried state.

One function for ``T`` tokens from a given state: a prefill chunk continues
the slot's state through the chunked scan (quadratic inside a chunk of
``chunk_size`` tokens, the recurrence between chunks), and ``T = 1`` is the
decode step, the recurrence itself. The state is what a slot keeps in place
of keys and values: the SSM state ``h`` (heads, head_dim, state) in f32 and
the last ``conv_kernel - 1`` inputs of the depthwise convolution.

    [z | xBC | dt] = u W_in
    xBC = silu(conv1d_causal(xBC) + b);  x, B, C = split(xBC)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    h_t = exp(dt A) h_{t-1} + dt x_t (x) B_t;  y_t = C_t . h_t + D x_t
    out = GroupRMSNorm(y * silu(z)) W_out

The two projections run as the rest of the model's products do (bf16
operands, f32 accumulation); everything of the scan is f32 at ``highest``,
which costs nothing beside them (about 0.2 GFLOP a 64-token chunk at
64 heads of 64 with a state of 128).
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
class Mamba2Config:
    dim: int = 256
    heads: int = 8
    head_dim: int = 16
    groups: int = 2
    state: int = 16
    conv_kernel: int = 4
    chunk_size: int = 128
    eps: float = 1e-5

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.groups * self.state

    @property
    def in_cols(self) -> int:
        return 2 * self.inner + 2 * self.groups * self.state + self.heads


def mamba2_init(rng: jax.Array, cfg: Mamba2Config,
                dtype=jnp.float32) -> Params:
    k_in, k_conv, k_dt, k_a, k_out = jax.random.split(rng, 5)
    return {
        "w_in": core.normal_init(k_in, (cfg.dim, cfg.in_cols),
                                 std=cfg.dim ** -0.5, dtype=dtype),
        "conv_w": core.normal_init(k_conv, (cfg.conv_kernel, cfg.conv_dim),
                                   std=0.4),
        "conv_b": jnp.zeros((cfg.conv_dim,), jnp.float32),
        "dt_bias": -3.0 + core.normal_init(k_dt, (cfg.heads,), std=1.0),
        "A_log": core.normal_init(k_a, (cfg.heads,), std=0.7),
        "D": jnp.ones((cfg.heads,), jnp.float32),
        "gnorm": jnp.ones((cfg.inner,), jnp.float32),
        "w_out": core.normal_init(k_out, (cfg.inner, cfg.dim),
                                  std=cfg.inner ** -0.5, dtype=dtype),
    }


def mamba2_state_init(cfg: Mamba2Config, slots: int) -> Dict[str, jax.Array]:
    """A zero state for ``slots`` sequences."""
    return {"conv": jnp.zeros((slots, cfg.conv_kernel - 1, cfg.conv_dim),
                              jnp.float32),
            "h": jnp.zeros((slots, cfg.heads, cfg.head_dim, cfg.state),
                           jnp.float32)}


def _chunk_scan(x, bm, cm, dt, a, h, cfg: Mamba2Config):
    """The scan over ``T`` tokens from state ``h``: x (B, T, H, P), bm/cm
    (B, T, G, N), dt/a (B, T, H) with ``a = dt * A``, h (B, H, P, N).
    Returns (y (B, T, H, P) without the D term, h after the last token). A
    token with dt = 0 leaves the state as it was."""
    b, t, heads, hd = x.shape
    g, n = cfg.groups, cfg.state
    r = heads // g
    q = min(cfg.chunk_size, t)
    pad = -t % q
    if pad:  # whole chunks: the tail's dt is 0
        x, bm, cm, dt, a = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, bm, cm, dt, a))
    nc = (t + pad) // q
    chunks = lambda v, tail: jnp.moveaxis(
        v.reshape((b, nc, q) + tail), 1, 0)
    xs = (chunks(x, (g, r, hd)), chunks(bm, (g, n)), chunks(cm, (g, n)),
          chunks(dt, (g, r)), chunks(a, (g, r)))
    lower = jnp.tril(jnp.ones((q, q), bool))

    def one(h, chunk):
        x_c, b_c, c_c, dt_c, a_c = chunk
        hg = h.reshape(b, g, r, hd, n)
        cum = jnp.cumsum(a_c, axis=1)                       # (B, Q, G, R)
        # decay from token k (after its own step) to token i, i >= k
        diff = cum[:, :, None] - cum[:, None, :]            # (B, Qi, Qk, G, R)
        decay = jnp.exp(jnp.where(lower[None, :, :, None, None], diff,
                                  -jnp.inf))
        cb = jnp.einsum("bign,bkgn->bikg", c_c, b_c, precision=HIGHEST)
        w = cb[..., None] * decay * dt_c[:, None]           # (B, Qi, Qk, G, R)
        y = jnp.einsum("bikgr,bkgrp->bigrp", w, x_c, precision=HIGHEST)
        y = y + jnp.einsum("bign,bgrpn->bigrp", c_c, hg,
                           precision=HIGHEST) * jnp.exp(cum)[..., None]
        last = cum[:, -1]                                    # (B, G, R)
        carry = jnp.exp(last[:, None] - cum) * dt_c          # (B, Q, G, R)
        hg = jnp.exp(last)[..., None, None] * hg + jnp.einsum(
            "bkgrp,bkgn->bgrpn", x_c * carry[..., None], b_c,
            precision=HIGHEST)
        return hg.reshape(b, heads, hd, n), y

    h, ys = jax.lax.scan(one, h, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(b, t + pad, heads, hd)
    return y[:, :t], h


def mamba2_mixer(p: Params, u: jax.Array, state: Dict[str, jax.Array],
                 lengths: jax.Array, cfg: Mamba2Config
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``u`` (B, T, D), already normalised; ``state`` the B sequences' own
    (``conv`` (B, K-1, C), ``h`` (B, H, P, N)); ``lengths`` (B,) how many of
    the T tokens are real (the rest is padding after them and moves no
    state). Returns (out (B, T, D) f32, the state after the last real
    token)."""
    b, t, _ = u.shape
    inner = cfg.inner
    dt_w = p["w_in"].dtype
    proj = jnp.dot(u.astype(dt_w), p["w_in"],
                   preferred_element_type=jnp.float32)
    gate, xbc, dt = jnp.split(proj, [inner, inner + cfg.conv_dim], axis=-1)
    conv, conv_state = core.carried_conv(state["conv"], xbc, p["conv_w"],
                                         lengths)
    xbc = jax.nn.silu(conv + p["conv_b"])
    x, bm, cm = jnp.split(xbc, [inner, inner + cfg.groups * cfg.state],
                          axis=-1)
    x = x.reshape(b, t, cfg.heads, cfg.head_dim)
    bm = bm.reshape(b, t, cfg.groups, cfg.state)
    cm = cm.reshape(b, t, cfg.groups, cfg.state)
    real = jnp.arange(t)[None, :] < lengths[:, None]
    dt = jnp.where(real[..., None], jax.nn.softplus(dt + p["dt_bias"]), 0.0)
    a = dt * -jnp.exp(p["A_log"])
    y, h = _chunk_scan(x, bm, cm, dt, a, state["h"], cfg)
    y = y + p["D"][:, None] * x
    y = y.reshape(b, t, inner) * jax.nn.silu(gate)
    y = core.group_rmsnorm(p["gnorm"], y, cfg.groups, cfg.eps)
    out = jnp.dot(y.astype(dt_w), p["w_out"],
                  preferred_element_type=jnp.float32)
    return out, {"conv": conv_state, "h": h}
