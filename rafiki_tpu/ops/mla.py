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
is one Pallas kernel (:func:`attend_rows`) that keeps the scores in VMEM,
and a decode round's product over the rows another (:func:`attend_pool`)
that reads each slot's live rows where they lie in the paged pool, once,
with no gathered view.
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


POOL_BLOCKS = 64  # pool blocks a step of the decode kernel fetches, at most
# off the TPU the decode kernel runs in the TPU interpreter, which raises on
# a read past an array and fills memory not yet written with NaN: a block
# fetched past a slot's table, or a buffer read before its fetch, shows
INTERPRET = pltpu.InterpretParams()


def _pool_kernel(layer_ref, tables_ref, lens_ref, q_ref, pool_ref, o_ref,
                 buf, sem, acc_ref, m_ref, l_ref, *, scale: float, per: int,
                 width: int):
    """One slot's absorbed queries (H, wide) against its live latent rows,
    fetched from the pool in HBM ``per`` blocks a step into one of two VMEM
    buffers, the next step's (the next live slot's first, after a slot's
    last) fetched while this one is computed; the softmax's statistics
    carried in VMEM over the steps. ``tables_ref`` holds the slots' tables
    one after another, ``width`` blocks each; ``lens_ref`` each slot's
    rows, 0 for a slot that is not live. No block past a slot's length is
    fetched, and its last block's rows past the length are masked out of
    the scores and zeroed in the values, as is what an earlier step left in
    the buffer: nothing there reaches the output."""
    s, slots = pl.program_id(0), pl.num_programs(0)
    bt = pool_ref.shape[2]
    rows = per * bt
    pool = pool_ref.at[layer_ref[0]]
    blocks = lambda slot: pl.cdiv(lens_ref[slot], bt)
    steps = lambda slot: pl.cdiv(blocks(slot), per)

    def copies(slot, step, into):
        """(the DMA of the step's k-th block, how many of its blocks are
        live)."""
        first = step * per
        copy = lambda k: pltpu.make_async_copy(
            pool.at[tables_ref[slot * width + first + k]],
            buf.at[into, pl.ds(pl.multiple_of(k * bt, bt), bt)],
            sem.at[into])
        return copy, jnp.minimum(per, blocks(slot) - first)

    def start(slot, step, into):
        copy, n = copies(slot, step, into)
        jax.lax.fori_loop(0, n, lambda k, c: (copy(k).start(), c)[1], 0)

    def wait(slot, step, into):
        copy, n = copies(slot, step, into)
        jax.lax.fori_loop(0, n, lambda k, c: (copy(k).wait(), c)[1], 0)

    n = steps(s)
    # the steps of the slots before this one say which buffer each step
    # takes; the first live slot fetches its own first step
    before = jax.lax.fori_loop(0, s, lambda i, c: c + steps(i), 0)
    after = jax.lax.fori_loop(
        s + 1, slots, lambda i, c: jnp.where(
            jnp.logical_and(c == slots, lens_ref[i] > 0), i, c), slots)

    @pl.when(jnp.logical_and(n > 0, before == 0))
    def _first():
        start(s, 0, 0)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, -1e30)
    l_ref[...] = jnp.zeros_like(l_ref)

    def step(i, carry):
        cur = (before + i) % 2

        @pl.when(i + 1 < n)
        def _next_step():
            start(s, i + 1, 1 - cur)

        @pl.when(jnp.logical_and(i + 1 == n, after < slots))
        def _next_slot():
            start(after, 0, 1 - cur)

        wait(s, i, cur)
        left = lens_ref[s] - i * rows  # of this step's rows, the live ones

        def update(masked: bool):
            kv = buf[cur]
            q = q_ref[0].astype(kv.dtype)
            if masked:
                ok = jax.lax.broadcasted_iota(jnp.int32, kv.shape, 0) < left
                kv = jnp.where(ok, kv, jnp.zeros_like(kv))
            sc = jax.lax.dot_general(
                q, kv, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (H, rows)
            if masked:
                col = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
                sc = jnp.where(col < left, sc, -1e30)
            # a step holds at least one live row: m_new is a real score
            m_new = jnp.maximum(m_ref[...], jnp.max(sc, axis=-1,
                                                    keepdims=True))
            w = jnp.exp(sc - m_new)
            alpha = jnp.exp(m_ref[...] - m_new)
            l_ref[...] = alpha * l_ref[...] + jnp.sum(w, axis=-1,
                                                      keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
                w.astype(kv.dtype), kv, preferred_element_type=jnp.float32)
            m_ref[...] = m_new

        pl.when(left >= rows)(lambda: update(False))
        pl.when(left < rows)(lambda: update(True))
        return carry

    jax.lax.fori_loop(0, n, step, 0)

    @pl.when(n > 0)
    def _finish():
        o_ref[0] = acc_ref[...] / l_ref[...]

    @pl.when(n == 0)
    def _idle():
        o_ref[0] = jnp.zeros_like(o_ref[0])


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def attend_pool(q: jax.Array, pool: jax.Array, layer: jax.Array,
                tables: jax.Array, lengths: jax.Array, scale: float,
                interpret: Any = False) -> jax.Array:
    """softmax(q rows^T * scale) rows over each slot's live rows where they
    lie in the paged pool, as one Pallas kernel: q (S, H, wide) a slot's
    queries over the whole row, pool (layers, blocks, tokens, wide) left in
    HBM, ``layer`` which of its layers, tables (S, width) each slot's
    blocks, lengths (S,) each slot's rows (0: not live, which reads nothing
    and gives zeros) -> (S, H, wide) f32. Every live row is read once and
    serves all heads; nothing is read past a slot's length. Jitted, so that
    the latent layers of one program share one traced and lowered kernel.
    ``interpret``: off the TPU, :data:`INTERPRET`."""
    slots, h, wide = q.shape
    width = tables.shape[1]
    bt = pool.shape[2]
    per = min(POOL_BLOCKS, width)
    return pl.pallas_call(
        functools.partial(_pool_kernel, scale=scale, per=per, width=width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(slots,),
            in_specs=[
                pl.BlockSpec((1, h, wide), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, h, wide), lambda s, *_: (s, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, per * bt, wide), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((h, wide), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32),
                            pltpu.VMEM((h, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((slots, h, wide), jnp.float32),
        # a slot's last step fetches the next live slot's first: the grid
        # runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      tables.astype(jnp.int32).reshape(-1), lengths.astype(jnp.int32),
      q, pool)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernel_takes(b: int, t: int, rows: int) -> bool:
    """Whether the prefill kernel runs these shapes: one sequence, on the
    TPU, with dimensions its tiles hold (the tiny sizes of the tests and a
    decode round's one query go the plain way)."""
    return (_on_tpu() and b == 1 and t % LANES == 0
            and rows % BLOCK_ROWS == 0)


def pool_kernel_takes(t: int, block_tokens: int) -> bool:
    """Whether a round's product over the rows reads the pool in place
    through :func:`attend_pool`: one query a sequence, on the TPU, blocks
    that fill whole tiles of bfloat16 rows."""
    return _on_tpu() and t == 1 and block_tokens % 16 == 0


def _absorbed_query(p: Params, q_nope: jax.Array, q_rope: jax.Array,
                    wide: int, cfg: MLAConfig) -> jax.Array:
    """A head's query over the whole row, ``[q~ | q_rope | zeros]`` up to
    ``wide``, in the weights' dtype: ``W_uk,h`` folded into the query."""
    dt = p["w_ukv"].dtype
    w_ukv = p["w_ukv"].reshape(cfg.kv_rank, cfg.heads,
                               cfg.nope_dim + cfg.v_dim)
    q_lat = jnp.einsum("bthn,chn->bthc", q_nope.astype(dt),
                       w_ukv[..., :cfg.nope_dim],
                       preferred_element_type=jnp.float32)
    return jnp.concatenate([q_lat, q_rope, jnp.zeros(
        q_rope.shape[:-1] + (wide - cfg.row,))], axis=-1).astype(dt)


def mla_attend_pool(p: Params, q_nope: jax.Array, q_rope: jax.Array,
                    pool: jax.Array, layer: int, tables: jax.Array,
                    lengths: jax.Array, cfg: MLAConfig) -> jax.Array:
    """The absorbed form of one query a sequence, its product over the rows
    read where they lie in the pool (:func:`attend_pool`), and the layer's
    output projection: q_nope/q_rope (S, 1, H, .), ``pool`` the latent
    group (layers, blocks, tokens, ``cache_row``) that holds the new rows
    already, tables (S, width), lengths (S,) each slot's rows (0: not
    live). Returns (S, 1, D) f32."""
    s, _, h, _ = q_nope.shape
    dt = p["w_ukv"].dtype
    w_ukv = p["w_ukv"].reshape(cfg.kv_rank, h, cfg.nope_dim + cfg.v_dim)
    q_row = _absorbed_query(p, q_nope, q_rope, pool.shape[-1], cfg)[:, 0]
    o_lat = attend_pool(q_row, pool, jnp.asarray(layer, jnp.int32), tables,
                        lengths, 1.0 / math.sqrt(cfg.nope_dim + cfg.rope_dim),
                        interpret=False if _on_tpu() else INTERPRET)
    o = jnp.einsum("shc,chv->shv", o_lat[..., :cfg.kv_rank].astype(dt),
                   w_ukv[..., cfg.nope_dim:],
                   preferred_element_type=jnp.float32)
    return jnp.dot(o.reshape(s, 1, h * cfg.v_dim).astype(dt), p["wo"],
                   preferred_element_type=jnp.float32)


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
        q_row = _absorbed_query(p, q_nope, q_rope, view.shape[-1], cfg)
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
