"""Multi-head latent attention (MLA; DeepSeek-V2, arXiv:2405.04434, section
2.1) over a cache of latent rows.

A token leaves ONE row a layer in the cache, not keys and values of full
heads: ``[c_kv | k_r]``, the key/value latent after its norm (``kv_rank``
numbers) and the one rotary key all heads share after its turn (``rope_dim``).
A head's keys and values are linear in the latent,
``[k_nope_h | v_h] = c_kv W_ukv,h``, so the product over the cache has two
forms with one result:

- **expanded**: make ``k_nope_h`` and ``v_h`` of every cached row, then
  attend as full heads do. Costs ``kv_rank * heads * (nope + v)`` products a
  cached row, once, whatever the number of queries.
- **absorbed**: fold ``W_uk,h`` into the query (``q~_h = q_nope_h W_uk,h^T``,
  ``kv_rank`` wide) and ``W_uv,h`` into the output, and attend over the
  latent rows themselves: no key or value of a head is ever made. Costs
  ``heads * (2 * kv_rank + rope)`` products a cached row a QUERY.

A decode round (one query a sequence) is absorbed, a prefill chunk of
hundreds of queries expanded: :func:`cheaper_form` counts both from the
shapes. On the TPU the chunk's attention over the expanded keys and values
is one Pallas kernel (:func:`attend_rows`) that keeps the scores in VMEM.
Scores are scaled by ``(nope + rope) ** -0.5``; rotary turns all of the
``rope_dim`` part (``ops/attention.rotary``'s pairs ``(i, i + rope/2)``).
Operands in the weights' dtype, accumulation, norms, rotary and softmax in
f32.

In the pool a row takes ``cache_row`` lanes: ``row`` rounded up to the TPU's
128 (576 numbers in 640). The chip's tiled layout pads the minor dimension
to that anyway, and with a minor dimension that is no multiple of it the
compiler re-lays the WHOLE pool out on every call (a copy of 4.03 GB in each
program at 12 layers x 16,384 blocks; compiled for a described v5e, PR 34).
The padding lanes hold zeros and meet zeros of the query.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rafiki_tpu.models import core
from rafiki_tpu.ops.attention import rotary

Params = Dict[str, Any]
LANES = 128  # of a TPU tile: the minor dimension a pool is laid out in


@dataclass(frozen=True)
class MLAConfig:
    dim: int = 64
    heads: int = 4
    q_rank: int = 32
    kv_rank: int = 16
    nope_dim: int = 8
    rope_dim: int = 4
    v_dim: int = 8
    rope_theta: float = 1e6
    eps: float = 1e-5

    @property
    def row(self) -> int:
        """Numbers a token leaves in the cache, a layer."""
        return self.kv_rank + self.rope_dim

    @property
    def cache_row(self) -> int:
        """Lanes the row takes in the pool: ``row`` up to a multiple of 128."""
        return -(-self.row // LANES) * LANES


def mla_init(rng: jax.Array, cfg: MLAConfig, dtype=jnp.bfloat16) -> Params:
    """``w_uq``'s columns are a head's ``[q_nope | q_rope]``, head after
    head; ``w_ukv``'s a head's ``[k_nope | v]``."""
    kq, kuq, kkv, kukv, ko = jax.random.split(rng, 5)
    h = cfg.heads
    normal = lambda k, shape: core.normal_init(
        k, shape, std=shape[0] ** -0.5, dtype=dtype)  # by fan-in
    return {
        "w_dq": normal(kq, (cfg.dim, cfg.q_rank)),
        "q_norm": core.rmsnorm_init(cfg.q_rank),
        "w_uq": normal(kuq, (cfg.q_rank, h * (cfg.nope_dim + cfg.rope_dim))),
        "w_dkv": normal(kkv, (cfg.dim, cfg.row)),
        "kv_norm": core.rmsnorm_init(cfg.kv_rank),
        "w_ukv": normal(kukv, (cfg.kv_rank, h * (cfg.nope_dim + cfg.v_dim))),
        "wo": normal(ko, (h * cfg.v_dim, cfg.dim)),
    }


def cheaper_form(queries: int, cfg: MLAConfig) -> str:
    """The form with fewer products a cached row, for ``queries`` queries a
    sequence (static: a shape of the compiled program)."""
    h = cfg.heads
    absorbed = queries * h * (2 * cfg.kv_rank + cfg.rope_dim)
    expanded = h * (cfg.nope_dim + cfg.v_dim) * (cfg.kv_rank + queries) \
        + queries * h * cfg.rope_dim
    return "absorbed" if absorbed <= expanded else "expanded"


def mla_project(p: Params, u: jax.Array, positions: jax.Array,
                cfg: MLAConfig, turn_rows: bool = True
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The new tokens' queries and cache rows: ``u`` (B, T, D) the layer's
    normed input, ``positions`` (B, T) -> (q_nope (B, T, H, nope), q_rope
    (B, T, H, rope), rows (B, T, row)), f32. ``turn_rows=False`` leaves the
    rows' rotary key unturned: a fault, for a test of what the comparison
    with the reference catches."""
    b, t, _ = u.shape
    dt = p["w_dq"].dtype
    dot = lambda x, w: jnp.dot(x.astype(dt), w,
                               preferred_element_type=jnp.float32)
    c_q = core.rmsnorm(p["q_norm"], dot(u, p["w_dq"]), cfg.eps)
    q = dot(c_q, p["w_uq"]).reshape(b, t, cfg.heads,
                                    cfg.nope_dim + cfg.rope_dim)
    q_nope, q_rope = jnp.split(q, [cfg.nope_dim], axis=-1)
    q_rope = rotary(q_rope, positions, cfg.rope_dim, cfg.rope_theta)
    c_kv, k_r = jnp.split(dot(u, p["w_dkv"]), [cfg.kv_rank], axis=-1)
    c_kv = core.rmsnorm(p["kv_norm"], c_kv, cfg.eps)
    if turn_rows:  # one rotary key for all heads
        k_r = rotary(k_r[:, :, None, :], positions, cfg.rope_dim,
                     cfg.rope_theta)[:, :, 0]
    return q_nope, q_rope, jnp.concatenate([c_kv, k_r], axis=-1)


BLOCK_ROWS = 512  # cached rows a step of the prefill kernel


def _rows_kernel(at_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                 *, scale: float, block: int):
    """One head's queries (T, d) against one block of its keys and values,
    both TRANSPOSED (d, block), the softmax's statistics carried in VMEM
    over the blocks (the kv axis is the grid's inner, sequential one).
    ``at_ref``: the first query's position and the last real one; query i
    sits at ``first + i`` and attends rows up to its own. Blocks past the
    last real position are not computed (nor fetched: the index map holds
    at the last live block)."""
    j = pl.program_id(1)
    first, last = at_ref[0], at_ref[1]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    def update(masked: bool):
        s = jnp.dot(q_ref[0], k_ref[0],
                    preferred_element_type=jnp.float32) * scale  # (T, block)
        if masked:
            row = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(col <= row, s, -1e30)
        m_new = jnp.maximum(m_ref[...], jnp.max(s, axis=-1, keepdims=True))
        # every query sees row 0, which is in the first block: m_new is a
        # real score from there on, and a masked column's weight is exp of
        # -1e30 less it, 0
        w = jnp.exp(s - m_new)
        alpha = jnp.exp(m_ref[...] - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(w, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            w.astype(v_ref.dtype), v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    # a block wholly before the first query is seen by all of them
    pl.when((j + 1) * block <= first)(lambda: update(False))
    pl.when(jnp.logical_and((j + 1) * block > first,
                            j * block <= last))(lambda: update(True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = acc_ref[...] / l_ref[...]


def attend_rows(q: jax.Array, kv: jax.Array, first: jax.Array,
                last: jax.Array, scale: float, interpret: bool = False
                ) -> jax.Array:
    """softmax(q k^T * scale, causal from ``first``) v as one Pallas kernel
    that never writes a score to memory: q (H, T, d), kv (H, 2d, L) a
    head's keys then its values, both transposed (the layout the expansion
    of :func:`mla_attend` comes out in, read in place: no copy of either),
    L a multiple of ``BLOCK_ROWS`` -> (H, T, d) f32. Query i is at position
    ``first + i``; rows past ``last`` (the last real query's position) are
    not read. XLA's own attention writes the (H, T, L) f32 scores four
    times over: at 20 heads, 512 queries and 8,192 rows that is most of a
    prefill chunk's time (PERF.md, PR 34)."""
    h, t, d = q.shape
    rows = kv.shape[2]
    at = jnp.stack([jnp.asarray(first, jnp.int32),
                    jnp.asarray(last, jnp.int32)])
    live = lambda half: lambda i, j, at: (
        i, half, jnp.minimum(j, at[1] // BLOCK_ROWS))
    return pl.pallas_call(
        functools.partial(_rows_kernel, scale=scale, block=BLOCK_ROWS),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(h, rows // BLOCK_ROWS),
            in_specs=[
                pl.BlockSpec((1, t, d), lambda i, j, at: (i, 0, 0)),
                pl.BlockSpec((1, d, BLOCK_ROWS), live(0)),
                pl.BlockSpec((1, d, BLOCK_ROWS), live(1))],
            out_specs=pl.BlockSpec((1, t, d), lambda i, j, at: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((t, d), jnp.float32),
                            pltpu.VMEM((t, 1), jnp.float32),
                            pltpu.VMEM((t, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((h, t, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(at, q, kv, kv)


def _expansion(w_ukv: jax.Array, cfg: MLAConfig, wide: int, d: int
               ) -> jax.Array:
    """(H, 2d, wide): for each head the matrix that takes a cache row
    ``[c_kv | k_r | zeros]`` to its key ``[k_nope_h | k_r]`` (the rotary
    key through an identity: one product makes the whole key) and, from
    row ``d`` on, to its value; transposed, so that the product with the
    view comes out as the kernel reads it."""
    w = jnp.transpose(w_ukv, (1, 2, 0))                       # (H, n + v, c)
    at_k = (slice(None), slice(0, cfg.nope_dim), slice(0, cfg.kv_rank))
    at_r = (slice(None), slice(cfg.nope_dim, cfg.nope_dim + cfg.rope_dim),
            slice(cfg.kv_rank, cfg.row))
    at_v = (slice(None), slice(d, d + cfg.v_dim), slice(0, cfg.kv_rank))
    return jnp.zeros((cfg.heads, 2 * d, wide), w.dtype) \
        .at[at_k].set(w[:, :cfg.nope_dim]) \
        .at[at_r].set(jnp.eye(cfg.rope_dim, dtype=w.dtype)) \
        .at[at_v].set(w[:, cfg.nope_dim:])


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernel_takes(b: int, t: int, rows: int) -> bool:
    """Whether the prefill kernel runs these shapes: one sequence, on the
    TPU, with dimensions its tiles hold (the tiny sizes of the tests and a
    decode round's one query go the plain way)."""
    return (_on_tpu() and b == 1 and t % LANES == 0
            and rows % BLOCK_ROWS == 0)


def mla_attend(p: Params, q_nope: jax.Array, q_rope: jax.Array,
               view: jax.Array, positions: jax.Array, cfg: MLAConfig,
               form: Optional[str] = None, last: Optional[jax.Array] = None,
               kernel: Optional[bool] = None) -> jax.Array:
    """Attention of the new tokens over a view of latent rows and the
    layer's output projection: ``view`` (B, L, row or wider: ``cache_row``,
    zeros past the row) holds the new tokens' rows already; a query at
    ``positions[b, i]`` attends rows up to its own. Returns (B, T, D) f32.
    ``form``: ``"absorbed"``, ``"expanded"`` or None for the cheaper. The
    expanded form of ONE sequence at contiguous positions goes through the
    Pallas kernel :func:`attend_rows` where :func:`kernel_takes` the shapes
    (``kernel``: force it, or the plain way); ``last`` is then the last
    real query's position (default: the last query's), past which no row
    is read."""
    b, t, h, _ = q_nope.shape
    form = form or cheaper_form(t, cfg)
    dt = p["w_ukv"].dtype
    view = view.astype(dt)
    w_ukv = p["w_ukv"].reshape(cfg.kv_rank, h, cfg.nope_dim + cfg.v_dim)
    mask = (jnp.arange(view.shape[1])[None, None, :]
            <= positions[:, :, None])[:, None]               # (B, 1, T, L)
    scale = 1.0 / math.sqrt(cfg.nope_dim + cfg.rope_dim)
    last = positions[0, -1] if last is None else last
    if form == "absorbed":
        # a head's query over the whole row, [q~ | q_rope]: one product
        # reads the view for the scores, one for the output (whose last
        # `rope_dim` columns, the rotary key's, are dropped)
        q_lat = jnp.einsum("bthn,chn->bthc", q_nope.astype(dt),
                           w_ukv[..., :cfg.nope_dim],
                           preferred_element_type=jnp.float32)
        q_row = jnp.concatenate([q_lat, q_rope, jnp.zeros(
            q_rope.shape[:-1] + (view.shape[-1] - cfg.row,))],
            axis=-1).astype(dt)
        s = jnp.einsum("bthr,blr->bhtl", q_row, view,
                       preferred_element_type=jnp.float32) * scale
        a = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1).astype(dt)
        o_lat = jnp.einsum("bhtl,blr->bhtr", a, view,
                           preferred_element_type=jnp.float32)
        o = jnp.einsum("bhtc,chv->bthv",
                       o_lat[..., :cfg.kv_rank].astype(dt),
                       w_ukv[..., cfg.nope_dim:],
                       preferred_element_type=jnp.float32)
    elif form == "expanded" and (kernel if kernel is not None else
                                 kernel_takes(b, t, view.shape[1])):
        # contiguous positions (a prefill chunk's): query i at first + i
        d = -(-max(cfg.nope_dim + cfg.rope_dim, cfg.v_dim) // LANES) * LANES
        kv = jnp.einsum("hxr,lr->hxl",
                        _expansion(w_ukv, cfg, view.shape[-1], d), view[0],
                        preferred_element_type=jnp.float32).astype(dt)
        q = jnp.concatenate([q_nope, q_rope, jnp.zeros(
            (b, t, h, d - cfg.nope_dim - cfg.rope_dim))], axis=-1)
        o = jnp.swapaxes(attend_rows(
            jnp.swapaxes(q[0], 0, 1).astype(dt), kv, positions[0, 0], last,
            scale, interpret=not _on_tpu()), 0, 1)[None, ..., :cfg.v_dim]
    elif form == "expanded":
        kv = jnp.einsum("blc,chx->blhx", view[..., :cfg.kv_rank], w_ukv,
                        preferred_element_type=jnp.float32).astype(dt)
        # the rotary part is a product of its own, 64 deep: a head's whole
        # key [k_nope_h | k_r] under ONE product of 256 ran ten times slower
        # at 8,192 rows, with or without a barrier (my chip run, PR 34)
        s = jnp.einsum("bthn,blhn->bhtl", q_nope.astype(dt),
                       kv[..., :cfg.nope_dim],
                       preferred_element_type=jnp.float32)
        s = s + jnp.einsum("bthr,blr->bhtl", q_rope.astype(dt),
                           view[..., cfg.kv_rank:cfg.row],
                           preferred_element_type=jnp.float32)
        a = jax.nn.softmax(jnp.where(mask, s * scale, -1e30),
                           axis=-1).astype(dt)
        o = jnp.swapaxes(jnp.einsum(
            "bhtl,blhv->bhtv", a, kv[..., cfg.nope_dim:],
            preferred_element_type=jnp.float32), 1, 2)
    else:
        raise ValueError(f"unknown form {form!r} of latent attention")
    return jnp.dot(o.reshape(b, t, h * cfg.v_dim).astype(dt), p["wo"],
                   preferred_element_type=jnp.float32)
