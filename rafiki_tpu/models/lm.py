"""Decoder-only transformer LM — the long-context / MoE vehicle.

Causal transformer over token ids with optional expert-parallel MoE FFNs
(TransformerConfig.moe_experts) and tied-embedding output head. Exercises
every mesh axis: data (batch), model (TP heads/MLP), seq (SP activations /
ring attention), expert (MoE), pipe (stacked depth). The reference system
has no language model at all; this backs the BASELINE.json BERT/ENAS config
and the long-context requirement.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from rafiki_tpu.models import core
from rafiki_tpu.models.transformer import (
    TransformerConfig,
    block_partition_specs,
    stack_apply,
    stack_init,
)

Params = Dict[str, Any]


@dataclass(frozen=True)
class LMConfig:
    vocab: int = 32000
    max_len: int = 2048
    encoder: TransformerConfig = field(
        default_factory=lambda: TransformerConfig(causal=True))


def tiny(vocab: int = 256, max_len: int = 128, dim: int = 64, depth: int = 2,
         heads: int = 4, moe_experts: int = 0, **encoder_kw) -> LMConfig:
    """``encoder_kw`` passes through to TransformerConfig (seq_parallel,
    pipeline, n_microbatches, ...)."""
    return LMConfig(vocab=vocab, max_len=max_len,
                    encoder=TransformerConfig(dim=dim, depth=depth,
                                              heads=heads, causal=True,
                                              moe_experts=moe_experts,
                                              **encoder_kw))


def init(rng: jax.Array, cfg: LMConfig) -> Params:
    k_emb, k_pos, k_blocks = jax.random.split(rng, 3)
    return {
        "embed": core.embedding_init(k_emb, cfg.vocab, cfg.encoder.dim),
        "pos": core.normal_init(k_pos, (1, cfg.max_len, cfg.encoder.dim)),
        "blocks": stack_init(k_blocks, cfg.encoder),
        "ln_f": core.layernorm_init(cfg.encoder.dim),
    }


def apply(params: Params, ids: jax.Array, cfg: LMConfig,
          rng: Optional[jax.Array] = None, deterministic: bool = True
          ) -> Tuple[jax.Array, jax.Array]:
    """ids: (B, S) int32 -> (logits (B, S, V) f32, moe aux loss)."""
    s = ids.shape[1]
    x = core.embedding(params["embed"], ids)
    x = x + params["pos"][:, :s, :].astype(x.dtype)
    x, aux = stack_apply(params["blocks"], x, cfg.encoder, rng, deterministic)
    x = core.layernorm(params["ln_f"], x)
    # tied output head: logits = x @ E^T
    logits = jnp.einsum("bsd,vd->bsv", x,
                        params["embed"]["table"].astype(x.dtype))
    return logits.astype(jnp.float32), aux


def loss_fn(params: Params, batch: Tuple[jax.Array, jax.Array],
            rng: jax.Array, cfg: LMConfig,
            aux_weight: float = 1e-2) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross-entropy; batch = (ids, mask)."""
    import optax

    ids, mask = batch
    logits, aux = apply(params, ids, cfg, rng, deterministic=False)
    targets = ids[:, 1:]
    lm_mask = mask[:, 1:].astype(jnp.float32)
    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], targets)
    loss = jnp.sum(ce * lm_mask) / jnp.maximum(jnp.sum(lm_mask), 1.0)
    total = loss + aux_weight * aux
    return total, {"ce": loss, "moe_aux": aux}


# -- autoregressive decode path (generative serving) -------------------------
#
# The serving subsystem (worker/generation.py) drives these three functions:
# ``init_kv_cache`` preallocates a fixed-shape per-layer K/V ring for a fixed
# number of sequence SLOTS, ``prefill`` ingests one slot's prompt (same math
# as ``apply`` — causal full-sequence attention — while also writing the
# prompt's K/V into the slot), and ``decode_step`` advances EVERY slot by one
# token against the cache. All shapes are fixed at cache-allocation time, so
# one jitted decode program serves the whole lifetime of the batch: sequences
# join (prefill) and leave (slot reuse) without recompiling, which is what
# makes token-level continuous batching cheap.
#
# Both forwards share one implementation (``_cached_forward``): prefill is
# the T=P case with positions 0..P-1, decode the T=1 case at each slot's
# current position. An expert block routes each token on its own and drops
# none (parallel/moe.py), so it decodes through the cache as a dense one does.

Cache = Dict[str, jax.Array]


def init_kv_cache(cfg: LMConfig, max_slots: int,
                  max_len: Optional[int] = None,
                  dtype=jnp.float32) -> Cache:
    """Preallocate the decode cache: per-layer K/V of shape
    ``(depth, max_slots, max_len, heads, head_dim)``. ``max_len`` defaults
    to ``cfg.max_len`` (prompt + generated tokens must fit)."""
    enc = cfg.encoder
    max_len = int(max_len or cfg.max_len)
    shape = (enc.depth, int(max_slots), max_len, enc.heads,
             enc.dim // enc.heads)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_max_len(cache: Cache) -> int:
    return int(cache["k"].shape[2])


def cache_max_slots(cache: Cache) -> int:
    return int(cache["k"].shape[1])


def _embed_tokens(params: Params, ids, positions, dtype) -> jax.Array:
    x = core.embedding(params["embed"], ids, dtype=dtype)
    pos_table = params["pos"][0].astype(dtype)          # (max_len, D)
    return x + jnp.take(pos_table, positions, axis=0)   # (B, T, D)


def _cached_block(p: Params, x: jax.Array, lk: jax.Array, lv: jax.Array,
                  positions: jax.Array, heads: int):
    """One block (dense or expert feed-forward) over cache views ``lk``/``lv`` (B, L, H, Dh), for the
    ring and the paged forward alike: sets the new tokens' K/V at
    ``positions``, attends up to each query's own position. Returns
    (x, lk, lv), the views updated."""
    b, length = lk.shape[:2]
    batch_ix = jnp.arange(b)[:, None]                   # (B, 1)
    # (B, T, L): query token at positions[b, i] attends cache slots <= it
    mask = jnp.arange(length)[None, None, :] <= positions[:, :, None]
    scale = 1.0 / jnp.sqrt(jnp.asarray(x.shape[-1] // heads, jnp.float32))
    h = core.layernorm(p["ln1"], x)
    q = jnp.einsum("btd,dhk->bthk", h, p["attn"]["wq"].astype(x.dtype))
    k = jnp.einsum("btd,dhk->bthk", h, p["attn"]["wk"].astype(x.dtype))
    v = jnp.einsum("btd,dhk->bthk", h, p["attn"]["wv"].astype(x.dtype))
    lk = lk.at[batch_ix, positions].set(k.astype(lk.dtype))
    lv = lv.at[batch_ix, positions].set(v.astype(lv.dtype))
    s = jnp.einsum("bthk,blhk->bthl", q, lk.astype(q.dtype),
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[:, :, None, :], s, -1e30)  # broadcast over H
    a = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bthl,blhk->bthk", a, lv.astype(q.dtype))
    attn_out = jnp.einsum(
        "bthk,hkd->btd", o, p["attn"]["wo"].astype(x.dtype))
    x = x + attn_out + p["attn"]["bo"].astype(x.dtype)
    h = core.layernorm(p["ln2"], x)
    if "moe" in p:
        from rafiki_tpu.parallel.moe import moe_apply

        h, _ = moe_apply(p["moe"], h)
    else:
        h = core.dense(p["mlp"]["w1"], h)
        h = jax.nn.gelu(h)
        h = core.dense(p["mlp"]["w2"], h)
    return x + h, lk, lv


def _lm_head(params: Params, x: jax.Array) -> jax.Array:
    x = core.layernorm(params["ln_f"], x)
    logits = jnp.einsum("btd,vd->btv", x,
                        params["embed"]["table"].astype(x.dtype))
    return logits.astype(jnp.float32)


def _dense_layers(blocks: Params, x: jax.Array, ck: jax.Array, cv: jax.Array,
                  layer) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The one walk over a dense stack's layers at serving, for the ring and
    the paged forward: a ``lax.scan`` over the stacked ``blocks`` in which
    ``layer(l, p, x, ck, cv) -> (x, ck, cv)`` runs layer ``l`` on its own
    leaves ``p`` and threads the K/V planes through as the carry.

    Each leaf passes a rounding to its OWN format on its way into the layer:
    the identity, bit for bit, on every backend, and what keeps the weights
    where they are. Without it the TPU compiler moves the rounding of every
    product's operand (f32 to bf16, the default precision) above the layer's
    slice and out of the loop, and each call reads the whole stack, writes a
    bf16 copy and reads that again (GPT-2 large: 6.5 of a decode round's 10.6
    ms and 1.4 GB of temporaries; PR 33). It does not look through this
    rounding, so a product's fusion takes the stacked f32 leaf itself and a
    weight byte crosses the chip's memory once a call;
    ``tests/test_chip_compile.py`` holds the compiler to that. What does
    NOT stop the hoist: an ``optimization_barrier``, a partial unroll, a
    ``fori_loop`` with a dynamic slice or a gather, a clamp, a pair of bit
    casts. The layers in line do, at 46-74 MB of code a program (12-18 s to
    compile, seconds to load from the compile cache, five programs a
    deploy). Training keeps its own scan (``transformer.stack_apply``)."""

    def in_place(a):
        fmt = jnp.finfo(a.dtype)
        return jax.lax.reduce_precision(a, fmt.nexp, fmt.nmant)

    def body(carry, at):
        p, l = at
        return layer(l, jax.tree_util.tree_map(in_place, p), *carry), None

    carry, _ = jax.lax.scan(body, (x, ck, cv),
                            (blocks, jnp.arange(ck.shape[0])))
    return carry


def _cached_forward(params: Params, ck: jax.Array, cv: jax.Array,
                    ids: jax.Array, positions: jax.Array, cfg: LMConfig
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Shared prefill/decode forward over per-slot caches.

    ``ids``/``positions``: (B, T) int32 — token ids and the cache indices
    they occupy. ``ck``/``cv``: (depth, B, L, H, Dh) — the cache rows of
    the B slots being advanced. New K/V are written at ``positions`` and
    attention reads the cache up to each query's own position (causal by
    construction). Returns (logits (B, T, V) f32, new_ck, new_cv).
    Same math as :func:`apply` for dense blocks (reference attention,
    f32 softmax statistics), so a prefilled-then-decoded sequence tracks
    the full-sequence forward."""
    x = _embed_tokens(params, ids, positions, ck.dtype)

    def layer(l, p, x, ck, cv):
        x, lk, lv = _cached_block(p, x, ck[l], cv[l], positions,
                                  cfg.encoder.heads)
        return x, ck.at[l].set(lk), cv.at[l].set(lv)

    x, ck, cv = _dense_layers(params["blocks"], x, ck, cv, layer)
    return _lm_head(params, x), ck, cv


def prefill(params: Params, cache: Cache, slot: jax.Array, ids: jax.Array,
            length: jax.Array, cfg: LMConfig) -> Tuple[jax.Array, Cache]:
    """Ingest one slot's prompt: write its K/V into ``cache[:, slot]`` and
    return the next-token logits at the prompt's last REAL position.

    ``ids``: (T,) int32, right-padded to any fixed bucket length so one
    compiled prefill serves every prompt of that bucket; ``length`` is the
    true prompt length (pad K/V beyond it are written but sit above the
    decode frontier, and each decode step overwrites the next index before
    attention can ever reach it). Returns (logits (V,), cache)."""
    ids = jnp.asarray(ids, jnp.int32)[None]                    # (1, T)
    positions = jnp.arange(ids.shape[1], dtype=jnp.int32)[None]
    ck = cache["k"][:, slot][:, None]                          # (D, 1, L, H, Dh)
    cv = cache["v"][:, slot][:, None]
    logits, ck, cv = _cached_forward(params, ck, cv, ids, positions, cfg)
    cache = {"k": cache["k"].at[:, slot].set(ck[:, 0]),
             "v": cache["v"].at[:, slot].set(cv[:, 0])}
    last = jnp.asarray(length, jnp.int32) - 1
    return logits[0, last], cache


def decode_step(params: Params, cache: Cache, ids: jax.Array,
                positions: jax.Array, cfg: LMConfig
                ) -> Tuple[jax.Array, Cache]:
    """Advance every slot one token: ``ids``/``positions`` are (S,) int32
    (the last emitted token per slot and the cache index it lands at).
    Returns (logits (S, V) f32, cache). Fixed shapes — one jitted program
    serves the batch for its whole lifetime; idle slots are advanced too
    (their outputs are ignored by the scheduler), which wastes flops but
    never recompiles."""
    ids = jnp.asarray(ids, jnp.int32)[:, None]                 # (S, 1)
    positions = jnp.asarray(positions, jnp.int32)[:, None]
    logits, ck, cv = _cached_forward(
        params, cache["k"], cache["v"], ids, positions, cfg)
    return logits[:, 0], {"k": ck, "v": cv}


# -- paged KV cache (block-granular decode memory) ---------------------------
#
# The contiguous ring above preallocates ``max_slots x max_len`` K/V rows
# whatever the actual sequence lengths are — HBM cost is worst-case, which
# caps co-resident streams. The paged layout (PagedAttention, vLLM) keeps
# one flat POOL of fixed-size blocks (``block_tokens`` K/V rows each) plus a
# per-slot BLOCK TABLE mapping logical positions to physical blocks, so a
# slot only holds blocks for tokens it has actually written — and blocks
# whose contents are a shared prompt prefix can appear in many tables at
# once (the worker-side allocator, worker/kv_paging.py, owns refcounts and
# copy-on-write; this layer is pure array math).
#
# A call's shapes are its table's: the view is as long as the table it is
# handed is wide, so a caller chooses how far each program reads (the worker
# hands a decode round the narrowest of a short ladder of widths that holds
# its longest live sequence; one compiled program a width). The pool is read
# and written in place (``_paged_forward``): inside the layer scan
# (``_dense_layers``, the ring's walk too, which also keeps the compiler
# from copying the stacked weights), layer l gathers its own blocks through
# the table into a ``(B, NB*block_tokens, H, Dh)`` view, runs the SAME
# ``_cached_block`` as the ring path on it (so paged outputs are
# bit-identical given the same logical contents), and writes ONLY the new
# rows into the pool; no view of all layers, no copy of the donated pool and
# no copy of the stacked weights is ever made. Sentinel table entries (>=
# pool size) gather clipped garbage that the causal mask keeps out of every
# real query, and their writes are dropped (`mode="drop"`), so idle slots
# and bucket padding never touch a live block.

def init_paged_kv_cache(cfg: LMConfig, pool_blocks: int, block_tokens: int,
                        dtype=jnp.float32) -> Cache:
    """Preallocate the paged decode pool: per-layer K/V of shape
    ``(depth, pool_blocks, block_tokens, heads * head_dim)``: a row's heads
    side by side, so that the minor dimension fills the device's lanes and
    a block is one contiguous run."""
    enc = cfg.encoder
    shape = (enc.depth, int(pool_blocks), int(block_tokens), enc.dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def paged_pool_blocks(cache: Cache) -> int:
    return int(cache["k"].shape[1])


def paged_block_tokens(cache: Cache) -> int:
    return int(cache["k"].shape[2])


def paged_pool_bytes(cache: Cache) -> int:
    """Persistent HBM the pool holds (both K and V planes)."""
    return int(cache["k"].nbytes + cache["v"].nbytes)


def _paged_forward(params: Params, cache: Cache, ids: jax.Array,
                   positions: jax.Array, block_tables: jax.Array,
                   cfg: LMConfig) -> Tuple[jax.Array, Cache]:
    """The paged prefill/decode/verify forward: ``ids``/``positions``
    (B, T) int32, ``block_tables`` (B, NB) int32 -> (logits (B, T, V) f32,
    cache). The pool planes ride the layer scan as its carry
    (:func:`_dense_layers`) and are touched one layer at a time, in place:
    layer ``l`` gathers its own blocks through the table into a
    (B, NB*BT, H, Dh) view (sentinel entries clip to the last pool block:
    finite garbage the mask excludes), runs :func:`_cached_block` on it as
    the ring path does, and writes only the B x T new rows into the pool
    at (l, block, offset).
    Rows that map through a sentinel entry or past the table are dropped,
    never clamped onto a live block."""
    pk, pv = cache["k"], cache["v"]
    nbpool, bt = pk.shape[1], pk.shape[2]
    block_tables = jnp.asarray(block_tables, jnp.int32)
    b, nb = block_tables.shape
    phys = jnp.take_along_axis(
        block_tables, jnp.clip(positions // bt, 0, nb - 1), axis=1)
    phys = jnp.where(positions < nb * bt, phys, nbpool)  # (B, T); drop pads
    off = positions % bt
    heads = cfg.encoder.heads
    view = (b, nb * bt, heads, pk.shape[3] // heads)
    rows = positions.shape + pk.shape[3:]
    x = _embed_tokens(params, ids, positions, pk.dtype)

    def layer(l, p, x, pk, pv):
        lk = pk.at[l, block_tables].get(mode="clip").reshape(view)
        lv = pv.at[l, block_tables].get(mode="clip").reshape(view)
        x, lk, lv = _cached_block(p, x, lk, lv, positions, heads)
        # the rows come back out of the view the block wrote them to, so
        # that the pool's write follows its read and needs no copy
        k = jnp.take_along_axis(lk, positions[:, :, None, None], axis=1)
        v = jnp.take_along_axis(lv, positions[:, :, None, None], axis=1)
        pk = pk.at[l, phys, off].set(k.reshape(rows), mode="drop")
        pv = pv.at[l, phys, off].set(v.reshape(rows), mode="drop")
        return x, pk, pv

    x, pk, pv = _dense_layers(params["blocks"], x, pk, pv, layer)
    return _lm_head(params, x), {"k": pk, "v": pv}


def paged_prefill(params: Params, cache: Cache, block_table: jax.Array,
                  ids: jax.Array, start: jax.Array, length: jax.Array,
                  cfg: LMConfig) -> Tuple[jax.Array, Cache]:
    """Ingest (a chunk of) one slot's prompt at logical positions
    ``start .. start+T-1``. ``block_table``: (NB,) int32 physical blocks
    covering the slot's logical space (sentinel entries for unallocated
    tails); ``ids``: (T,) suffix tokens right-padded to a bucket;
    ``length`` the true token count of this chunk. Returns
    (logits (V,) at the chunk's last REAL position, cache) — for
    intermediate chunks of a chunked prefill the caller ignores the
    logits; the final chunk's logits yield the first generated token."""
    ids = jnp.asarray(ids, jnp.int32)[None]                      # (1, T)
    t = ids.shape[1]
    start = jnp.asarray(start, jnp.int32)
    positions = (start + jnp.arange(t, dtype=jnp.int32))[None]   # (1, T)
    logits, cache = _paged_forward(params, cache, ids, positions,
                                   jnp.asarray(block_table)[None], cfg)
    last = jnp.asarray(length, jnp.int32) - 1
    return logits[0, last], cache


def paged_decode_step(params: Params, cache: Cache, ids: jax.Array,
                      positions: jax.Array, block_tables: jax.Array,
                      cfg: LMConfig) -> Tuple[jax.Array, Cache]:
    """Advance every slot one token against the pool: ``ids``/``positions``
    (S,) int32, ``block_tables`` (S, NB) int32. One jitted program for each
    NB it is called with: NB need only cover every live row's position (a
    write at or past ``NB * block_tokens`` is dropped), and a table cut to
    fewer columns returns the same bits, since what lies past a row's
    position is masked. Idle slots carry all-sentinel table rows so their
    writes are dropped and their (ignored) outputs read only clipped
    garbage."""
    ids = jnp.asarray(ids, jnp.int32)[:, None]                   # (S, 1)
    positions2 = jnp.asarray(positions, jnp.int32)[:, None]
    logits, cache = _paged_forward(params, cache, ids, positions2,
                                   block_tables, cfg)
    return logits[:, 0], cache


def copy_kv_blocks(cache: Cache, src: jax.Array, dst: jax.Array) -> Cache:
    """Copy whole pool blocks ``src[i] -> dst[i]`` (both (M,) int32) — the
    allocator's copy-on-write primitive. dst blocks are always private to
    one slot, so indices never collide."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    return {"k": cache["k"].at[:, dst].set(
                jnp.take(cache["k"], src, axis=1)),
            "v": cache["v"].at[:, dst].set(
                jnp.take(cache["v"], src, axis=1))}


def greedy_token(logits: jax.Array) -> jax.Array:
    """argmax over the vocab axis — the default (deterministic) sampler."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


# -- sampling + speculative verify (draft/verify decoding) -------------------
#
# Real sampling (temperature / top-k / top-p) with a COUNTER-BASED key
# discipline: every random draw for a stream is keyed by
# ``fold_in(fold_in(PRNGKey(seed), token_position), role)`` — a pure
# function of (stream seed, absolute position, draw kind), never of
# wall-clock state or round boundaries. That is what keeps sampled streams
# exactly resumable after preemption (worker/generation.py resumes a
# stream by re-prefilling its committed history; the keys for every future
# position are unchanged) and makes speculative rejection-sampling
# well-defined. temperature <= 0 collapses the modified distribution to a
# one-hot argmax, so the greedy path is reproduced bit-identically.
#
# Roles (the third fold_in operand): distinct draw kinds at the same
# position must not share a key, or the accept test would be correlated
# with the proposal it judges.

ROLE_TARGET = 0  # a draw from the target's (modified) distribution
ROLE_DRAFT = 1   # the draft model's proposal draw
ROLE_ACCEPT = 2  # the speculative accept/reject uniform


def _uniform_at(seeds: jax.Array, positions: jax.Array,
                role) -> jax.Array:
    """One uniform in [0, 1) per entry of ``positions``, keyed by the
    counter discipline above. ``seeds``: (S,) uint32 per-slot stream
    seeds; ``positions``: (S,) or (S, T) int32 absolute token positions."""
    seeds = jnp.asarray(seeds, jnp.uint32)
    positions = jnp.asarray(positions, jnp.int32)
    shape = positions.shape
    sb = jnp.broadcast_to(
        seeds.reshape((-1,) + (1,) * (len(shape) - 1)), shape)

    def one(seed, pos):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(seed), pos), role)
        return jax.random.uniform(key)

    return jax.vmap(one)(sb.reshape(-1), positions.reshape(-1)).reshape(shape)


def modified_dist(logits: jax.Array, temperature, top_k, top_p) -> jax.Array:
    """The temperature/top-k/top-p-modified sampling distribution.

    ``logits``: (..., V) f32; the three knobs broadcast against the
    leading shape (per-slot arrays on a batched step). top_k <= 0 and
    top_p >= 1 disable their filters. Rows with temperature <= 0 return
    the exact one-hot of ``argmax(logits)`` — sampling from that
    distribution reproduces :func:`greedy_token` bit-identically, which
    is the invariant speculative verify and preemption-resume rely on."""
    head = logits.shape[:-1]
    v = logits.shape[-1]
    t = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), head)
    tk = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), head)
    tp = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), head)
    greedy = t <= 0.0
    scaled = logits / jnp.where(greedy, 1.0, t)[..., None]
    # top-k: keep each row's k largest logits (ties keep all equal values)
    sorted_desc = jnp.sort(scaled, axis=-1)[..., ::-1]
    k = jnp.clip(jnp.where(tk <= 0, v, tk), 1, v)
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[..., None], axis=-1)
    scaled = jnp.where(scaled >= kth, scaled, -jnp.inf)
    probs = jax.nn.softmax(scaled, axis=-1)
    # top-p: smallest descending-sorted prefix covering mass top_p (the
    # first token is always kept, so the filter never empties a row)
    order = jnp.argsort(-probs, axis=-1)
    sp = jnp.take_along_axis(probs, order, axis=-1)
    keep_sorted = (jnp.cumsum(sp, axis=-1) - sp) < tp[..., None]
    inv = jnp.argsort(order, axis=-1)
    keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
    probs = probs * keep
    probs = probs / jnp.maximum(jnp.sum(probs, -1, keepdims=True), 1e-20)
    onehot = jax.nn.one_hot(jnp.argmax(logits, axis=-1), v,
                            dtype=jnp.float32)
    return jnp.where(greedy[..., None], onehot, probs)


def sample_from(probs: jax.Array, u: jax.Array) -> jax.Array:
    """Inverse-CDF draw: the smallest index whose cumulative mass exceeds
    ``u``. Exact on one-hot rows (returns the hot index for any u in
    [0, 1)), which is what makes temperature=0 sampling ≡ argmax."""
    c = jnp.cumsum(probs, axis=-1)
    idx = jnp.sum((c <= u[..., None]).astype(jnp.int32), axis=-1)
    return jnp.clip(idx, 0, probs.shape[-1] - 1).astype(jnp.int32)


def _draw(logits: jax.Array, token_positions: jax.Array,
          sampling: Dict[str, jax.Array]
          ) -> Tuple[jax.Array, jax.Array]:
    """(token ids, modified distribution) for a batched single-position
    draw. ``token_positions`` are the ABSOLUTE positions the sampled
    tokens will occupy (write position + 1) — the counter the keys fold."""
    v = logits.shape[-1]

    def _greedy(_):
        am = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return am, jax.nn.one_hot(am, v, dtype=jnp.float32)

    def _full(_):
        probs = modified_dist(logits, sampling["temperature"],
                              sampling["top_k"], sampling["top_p"])
        u = _uniform_at(sampling["seed"], token_positions,
                        sampling["role"])
        return sample_from(probs, u), probs

    # whole-batch greedy fast path: modified_dist at temperature<=0 IS
    # onehot(argmax) and sample_from(onehot, u) IS the argmax for any u,
    # so skipping the vocab sorts and counter-RNG draws cannot change a
    # single emitted token — it only makes the common greedy table cheap
    all_greedy = jnp.all(
        jnp.asarray(sampling["temperature"], jnp.float32) <= 0.0)
    return jax.lax.cond(all_greedy, _greedy, _full, None)


def decode_step_sampled(params: Params, cache: Cache, ids: jax.Array,
                        positions: jax.Array,
                        sampling: Dict[str, jax.Array], cfg: LMConfig
                        ) -> Tuple[jax.Array, jax.Array, Cache]:
    """:func:`decode_step` + an in-graph sampled draw. Returns
    (token ids (S,), modified distribution (S, V), cache) — the full
    distribution is returned because a draft model's proposal q is the
    denominator of the speculative accept test."""
    logits, cache = decode_step(params, cache, ids, positions, cfg)
    tok, probs = _draw(logits, jnp.asarray(positions, jnp.int32) + 1,
                       sampling)
    return tok, probs, cache


def decode_steps_sampled(params: Params, cache: Cache, ids: jax.Array,
                         positions: jax.Array, k: int,
                         sampling: Dict[str, jax.Array], cfg: LMConfig
                         ) -> Tuple[jax.Array, jax.Array, Cache]:
    """``k`` chained :func:`decode_step_sampled` calls fused into ONE
    program — the draft model's whole proposal burst per speculative
    round. The worker's fallback is k separate jitted calls, each paying
    dispatch plus a host sync to feed the sampled token back in; fusing
    keeps the token feedback in-graph, which is most of a small draft's
    per-round cost on dispatch-bound backends. ``k`` is static (the
    spec-k knob is fixed for a deployment), so the loop unrolls. Returns
    (tokens (S, k), modified distributions q (S, k, V), cache)."""
    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    toks, probs = [], []
    for j in range(k):
        ids, pj, cache = decode_step_sampled(params, cache, ids,
                                             positions + j, sampling, cfg)
        toks.append(ids)
        probs.append(pj)
    return jnp.stack(toks, axis=1), jnp.stack(probs, axis=1), cache


def paged_decode_step_sampled(params: Params, cache: Cache, ids: jax.Array,
                              positions: jax.Array, block_tables: jax.Array,
                              sampling: Dict[str, jax.Array], cfg: LMConfig
                              ) -> Tuple[jax.Array, jax.Array, Cache]:
    """:func:`paged_decode_step` + an in-graph sampled draw (see
    :func:`decode_step_sampled`)."""
    logits, cache = paged_decode_step(params, cache, ids, positions,
                                      block_tables, cfg)
    tok, probs = _draw(logits, jnp.asarray(positions, jnp.int32) + 1,
                       sampling)
    return tok, probs, cache


def paged_verify_step(params: Params, cache: Cache, ids: jax.Array,
                      positions: jax.Array, block_tables: jax.Array,
                      draft_probs: jax.Array,
                      sampling: Dict[str, jax.Array], cfg: LMConfig
                      ) -> Tuple[jax.Array, jax.Array, Cache]:
    """Verify k drafted tokens per slot in ONE fixed-shape forward.

    ``ids``: (S, k+1) int32 — column 0 is each slot's last committed
    token, columns 1..k the draft's proposals; ``positions``: (S, k+1)
    the write positions (frontier .. frontier+k); ``draft_probs``:
    (S, k, V) the draft's modified distributions q. Rejection sampling
    (Leviathan et al. / Chen et al.) runs in-graph per slot: draft token
    d_j is accepted iff u_j * q(d_j) < p(d_j) (u_j keyed ROLE_ACCEPT at
    d_j's position), the first rejection resamples from
    norm(max(p - q, 0)), and a fully-accepted row draws a bonus token
    from the k+1-th target distribution — so every round commits
    accept_len + 1 tokens. Per-slot accept lengths are data, not shape:
    mixed acceptance across resident streams never retraces.

    temperature <= 0 rows degrade exactly to greedy: p is one-hot, so a
    draft token is accepted iff it IS the argmax and every correction or
    bonus draw returns the argmax — bit-identical to the plain greedy
    decode loop.

    The K/V written for rejected suffixes need no device-side rollback:
    ``_cached_block`` writes every new row before attention and the
    causal mask bounds reads at the query's own position, so the next
    round's writes overwrite any stale row before it can be attended.
    Returns (accept_len (S,) int32, tokens (S, k+1) int32 — the committed
    tokens left-packed, entries past accept_len are padding — cache)."""
    ids = jnp.asarray(ids, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    logits, cache = _paged_forward(params, cache, ids, positions,
                                   block_tables, cfg)
    s, k1 = ids.shape
    k = k1 - 1
    d = ids[:, 1:]                                       # (S, k) proposals
    jj = jnp.arange(k1, dtype=jnp.int32)[None, :]
    d_pad = jnp.concatenate([d, jnp.zeros((s, 1), jnp.int32)], axis=1)

    def _greedy(_):
        # whole-batch greedy fast path: p is onehot(argmax), so the
        # accept test collapses to d_j == argmax_j and every correction
        # or bonus draw returns that position's argmax — provably the
        # same tokens as the rejection-sampling branch, minus its vocab
        # sorts and counter-RNG draws
        am = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (S, k+1)
        accept = d == am[:, :k]
        a = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=-1),
                    axis=-1)
        extra = jnp.take_along_axis(am, a[:, None], axis=1)
        toks = jnp.where(jj < a[:, None], d_pad,
                         jnp.where(jj == a[:, None], extra, 0))
        return a.astype(jnp.int32), toks.astype(jnp.int32)

    def _full(_):
        temp = jnp.asarray(sampling["temperature"], jnp.float32)[:, None]
        top_k = jnp.asarray(sampling["top_k"], jnp.int32)[:, None]
        top_p = jnp.asarray(sampling["top_p"], jnp.float32)[:, None]
        p = modified_dist(logits, temp, top_k, top_p)    # (S, k+1, V)
        q = jnp.asarray(draft_probs, jnp.float32)        # (S, k, V)
        p_head = p[:, :k, :]
        p_d = jnp.take_along_axis(p_head, d[:, :, None], axis=-1)[..., 0]
        q_d = jnp.take_along_axis(q, d[:, :, None], axis=-1)[..., 0]
        u_acc = _uniform_at(sampling["seed"], positions[:, 1:],
                            ROLE_ACCEPT)
        accept = u_acc * q_d < p_d                       # u < min(1, p/q)
        a = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=-1),
                    axis=-1)
        # the replacement draw at every possible rejection point j < k ...
        resid = jnp.maximum(p_head - q, 0.0)
        rs = jnp.sum(resid, axis=-1, keepdims=True)
        corr = jnp.where(rs > 1e-20, resid / jnp.maximum(rs, 1e-20),
                         p_head)
        # ... and the bonus distribution at j == k (all k accepted)
        dist_all = jnp.concatenate([corr, p[:, k:k + 1, :]], axis=1)
        xdist = jnp.take_along_axis(
            dist_all, a[:, None, None], axis=1)[:, 0, :]
        extra_pos = positions[:, 0] + a + 1
        u_x = _uniform_at(sampling["seed"], extra_pos, ROLE_TARGET)
        extra = sample_from(xdist, u_x)
        toks = jnp.where(jj < a[:, None], d_pad,
                         jnp.where(jj == a[:, None], extra[:, None], 0))
        return a.astype(jnp.int32), toks.astype(jnp.int32)

    all_greedy = jnp.all(
        jnp.asarray(sampling["temperature"], jnp.float32) <= 0.0)
    a, toks = jax.lax.cond(all_greedy, _greedy, _full, None)
    return a, toks, cache


def partition_specs(cfg: LMConfig) -> Params:
    return {
        "embed": {"table": P(None, "model")},
        "pos": P(None, None, None),
        "blocks": block_partition_specs(cfg.encoder, stacked=True),
        "ln_f": {"scale": P(None), "bias": P(None)},
    }


def batch_spec() -> Any:
    return (P("data", "seq"), P("data", "seq"))


# -- a stack of layer kinds: state-space, expert and attention layers ---------
#
# A hybrid language model's layer is ONE mixer: ``x <- x + mixer(RMSNorm(x))``
# with the mixer one of seven kinds, in the order ``pattern`` gives, each layer
# with its own leaves (a published layer of a mixer and a feed-forward is two
# entries of the pattern):
#   ``M``  a Mamba-2 scan (ops/mamba2.py);
#   ``D``  the gated delta rule (ops/gated_delta.py);
#   ``*``  grouped-query attention without positions (ops/attention.py);
#   ``G``  the same attention with RMSNorm over a head of queries and of keys,
#          rotary positions on a head's first ``rotary_dim`` and a sigmoid gate
#          on its output;
#   ``L``  latent attention (ops/mla.py): queries and a key/value latent
#          through low-rank projections with their own norms, one rotary key
#          for all heads;
#   ``E``  a sparse-expert feed-forward (parallel/moe.py), whose variant (the
#          router's score, correction bias and scale, the activation, gated
#          experts or not, the shared expert's own gate) the config states;
#   ``F``  a dense feed-forward alone, of the experts' form (their activation,
#          gated or not) and ``dense_ffn`` wide.
# The decode cache is a group a stateful kind. Two kinds of group are paged,
# ``(layers of the kind, blocks, tokens, row width)`` behind ONE block table a
# slot, the same tables as the dense model's pool: keys and values ``k``/``v``
# of ``kv_heads * head_dim`` for ``*`` and ``G`` together, and ``latent`` of
# ``kv_rank + rope_dim`` for ``L``, one array where full heads have two (the
# row is the latent after its norm and the rotary key after its turn, and
# zeros up to a multiple of the chip's 128 lanes: ops/mla.py says why). A
# fixed state a SLOT for ``M`` (``conv``: the convolution's last inputs,
# ``h``: the SSM state) and for ``D`` (``delta_conv``: the same window,
# ``delta_s``: a key-by-value matrix a head), all f32. Each group is there
# only where the pattern holds its kind; nothing for ``E`` and ``F``. So the
# forward is told which slot each sequence is where the pattern holds a
# stateful kind (without one it needs no slot and the cache no slot count):
# prefill continues the slot's state from a chunk's ``start`` and starts from
# zero at ``start == 0``; a decode row whose table is all sentinel (an idle
# or still-prefilling slot) moves no state and reads no expert.

@dataclass(frozen=True)
class HybridConfig:
    vocab: int = 256
    max_len: int = 128
    dim: int = 64
    pattern: str = "MEM*E"
    mamba: Any = None                 # ops.mamba2.Mamba2Config, for ``M``
    delta: Any = None                 # ops.gated_delta.GatedDeltaConfig, ``D``
    mla: Any = None                   # ops.mla.MLAConfig, for ``L``
    q_heads: int = 4
    kv_heads: int = 2
    head_dim: int = 16
    rotary_dim: int = 0               # of a head, for ``G``
    rope_theta: float = 1e4
    n_experts: int = 8
    top_k: int = 2
    ffn: int = 32
    shared_ffn: int = 64
    dense_ffn: int = 128              # of ``F``
    route_score: str = "sigmoid"      # or "softmax"
    route_bias: bool = True           # a correction bias moves the choice
    route_scale: float = 2.5
    expert_act: str = "relu2"         # or "silu"
    expert_gated: bool = False        # act(x W_gate) * (x W_up)
    shared_gate: bool = False         # sigmoid(x w_s) * shared(x)
    held: Tuple[int, int] = (0, 8)    # (first, count) of the experts held
    eps: float = 1e-5

    def count(self, kinds: str) -> int:
        return sum(self.pattern.count(kind) for kind in kinds)


HYBRID_KINDS = ("M", "D", "E", "*", "G", "L", "F")
ATTENTION_KINDS = "*G"                # they share the paged pool
PAGED_GROUPS = ("k", "v", "latent")   # rows a token a layer, behind the tables
# a stateful kind: (its named scope, its mixer's state name -> cache group)
_RECURRENT = {"M": ("ssm", {"conv": "conv", "h": "h"}),
              "D": ("delta", {"conv": "delta_conv", "s": "delta_s"})}
_EXPERT_ACTS = {"relu2": core.relu2, "silu": jax.nn.silu}


def _recurrent_mixer(kind: str, cfg: HybridConfig):
    """(mixer, its config, a zero state for n slots) of a stateful kind."""
    if kind == "M":
        from rafiki_tpu.ops.mamba2 import mamba2_mixer, mamba2_state_init

        return mamba2_mixer, cfg.mamba, mamba2_state_init
    from rafiki_tpu.ops.gated_delta import (gated_delta_mixer,
                                            gated_delta_state_init)

    return gated_delta_mixer, cfg.delta, gated_delta_state_init


def hybrid_layer_init(rng: jax.Array, kind: str, cfg: HybridConfig,
                      dtype=jnp.bfloat16) -> Params:
    from rafiki_tpu.ops.attention import gated_gqa_init, gqa_init
    from rafiki_tpu.ops.gated_delta import gated_delta_init
    from rafiki_tpu.ops.mamba2 import mamba2_init

    if kind not in HYBRID_KINDS:
        raise ValueError(f"unknown layer kind {kind!r} in {cfg.pattern!r}")
    norm = core.rmsnorm_init(cfg.dim)
    if kind == "M":
        return {"norm": norm, **mamba2_init(rng, cfg.mamba, dtype)}
    if kind == "D":
        return {"norm": norm, **gated_delta_init(rng, cfg.delta, dtype)}
    if kind in ATTENTION_KINDS:
        init = gqa_init if kind == "*" else gated_gqa_init
        return {"norm": norm, **init(rng, cfg.dim, cfg.q_heads, cfg.kv_heads,
                                     cfg.head_dim, dtype)}
    if kind == "L":
        from rafiki_tpu.ops.mla import mla_init

        return {"norm": norm, **mla_init(rng, cfg.mla, dtype)}
    kr, kb, ku, kd, su, sd = jax.random.split(rng, 6)
    count = cfg.held[1]
    into = cfg.dim ** -0.5  # by fan-in
    ups = 2 if cfg.expert_gated else 1  # [W_gate | W_up] side by side
    if kind == "F":
        return {"norm": norm,
                "w_up": core.normal_init(ku, (cfg.dim, ups * cfg.dense_ffn),
                                         std=into, dtype=dtype),
                "w_down": core.normal_init(kd, (cfg.dense_ffn, cfg.dim),
                                           std=cfg.dense_ffn ** -0.5,
                                           dtype=dtype)}
    p = {
        "norm": norm,
        "router": core.normal_init(kr, (cfg.dim, cfg.n_experts), std=into),
        "w_up": core.normal_init(ku, (count, cfg.dim, ups * cfg.ffn),
                                 std=into, dtype=dtype),
        "w_down": core.normal_init(kd, (count, cfg.ffn, cfg.dim),
                                   std=cfg.ffn ** -0.5, dtype=dtype),
        "s_up": core.normal_init(su, (cfg.dim, ups * cfg.shared_ffn),
                                 std=into, dtype=dtype),
        "s_down": core.normal_init(sd, (cfg.shared_ffn, cfg.dim),
                                   std=cfg.shared_ffn ** -0.5, dtype=dtype),
    }
    if cfg.route_bias:
        p["b_corr"] = core.normal_init(kb, (cfg.n_experts,))
    if cfg.shared_gate:
        p["s_gate"] = core.normal_init(jax.random.fold_in(rng, 6),
                                       (cfg.dim, 1), std=into, dtype=dtype)
    return p


def hybrid_layers(layers: list) -> Params:
    """Per-layer parameter trees, in the pattern's order, as the tree the
    forward reads: keyed ``"00"``, ``"01"``, ... (a layer's kind is the
    pattern's character at its index)."""
    return {f"{l:02d}": p for l, p in enumerate(layers)}


def hybrid_init(rng: jax.Array, cfg: HybridConfig,
                dtype=jnp.bfloat16) -> Params:
    keys = jax.random.split(rng, len(cfg.pattern) + 2)
    layers = [hybrid_layer_init(k, kind, cfg, dtype)
              for k, kind in zip(keys, cfg.pattern)]
    return {"embed": {"table": core.normal_init(
                keys[-2], (cfg.vocab, cfg.dim), dtype=dtype)},
            "head": core.normal_init(keys[-1], (cfg.vocab, cfg.dim),
                                     std=cfg.dim ** -0.5, dtype=dtype),
            "norm_f": core.rmsnorm_init(cfg.dim),
            "layers": hybrid_layers(layers)}


def init_hybrid_cache(cfg: HybridConfig, pool_blocks: int, block_tokens: int,
                      max_slots: int = 0, kv_dtype=jnp.bfloat16) -> Cache:
    """The cache's groups, each there where the pattern holds its kind:
    ``k``/``v`` the attention layers' paged pool and ``latent`` the latent
    layers' (a pattern with neither keeps an empty ``k``/``v``, which says
    the pool's shape), and for each stateful kind its layers' state for each
    of ``max_slots``: ``conv``/``h`` (``M``), ``delta_conv``/``delta_s``
    (``D``). A pattern without a stateful kind takes no ``max_slots``."""
    paged = lambda layers, width: jnp.zeros(
        (layers, int(pool_blocks), int(block_tokens), width), kv_dtype)
    cache = {}
    if cfg.count(ATTENTION_KINDS) or not cfg.count("L"):
        kv = (cfg.count(ATTENTION_KINDS), cfg.kv_heads * cfg.head_dim)
        cache.update(k=paged(*kv), v=paged(*kv))
    if cfg.count("L"):
        cache["latent"] = paged(cfg.count("L"), cfg.mla.cache_row)
    for kind, (_, groups) in _RECURRENT.items():
        if cfg.count(kind):
            if int(max_slots) < 1:
                raise ValueError(
                    f"pattern {cfg.pattern!r} holds the stateful kind "
                    f"{kind!r}: its cache needs the number of slots")
            _, mixer_cfg, state_init = _recurrent_mixer(kind, cfg)
            cache.update({groups[name]: jnp.zeros(
                (cfg.count(kind),) + a.shape, a.dtype) for name, a in
                state_init(mixer_cfg, int(max_slots)).items()})
    return cache


def _paged(cache: Cache) -> Dict[str, jax.Array]:
    """The cache's paged groups: what a block table maps and copy-on-write
    copies."""
    return {name: cache[name] for name in PAGED_GROUPS if name in cache}


def hybrid_pool_shape(cache: Cache) -> Tuple[int, int]:
    """(blocks, tokens a block) of the paged pool, whichever groups it has."""
    return next(iter(_paged(cache).values())).shape[1:3]


def hybrid_state_bytes(cache: Cache) -> int:
    """Bytes of the per-slot recurrent state (``conv``, ``h``,
    ``delta_conv``, ``delta_s``, as far as the pattern holds their kinds):
    no paged group is state, whatever rows it holds."""
    return int(sum(a.nbytes for name, a in cache.items()
                   if name not in PAGED_GROUPS))


def _hybrid_forward(params: Params, cache: Cache, ids: jax.Array,
                    positions: jax.Array, block_tables: jax.Array,
                    slots: Optional[jax.Array], lengths: jax.Array,
                    reset: jax.Array, cfg: HybridConfig,
                    turn_rows: bool = True
                    ) -> Tuple[jax.Array, Cache, Dict[str, jax.Array]]:
    """ids/positions (B, T), block_tables (B, NB), slots (B,) the state rows
    of the sequences (None: row i is slot i and every slot is a row, so a
    layer's state is read and written in place, with no gather and no
    scatter of it), lengths (B,) how many of the T tokens are real (0: an
    idle row, which moves no state), reset (B,) bool: start from a zero
    state; ``turn_rows=False`` writes the latent rows without their rotary
    turn (a test's fault). Returns (x (B, T, D) f32 before the last norm,
    cache, counts of the expert layers summed over them)."""
    from rafiki_tpu.ops.attention import gqa_cached, rotary
    from rafiki_tpu.parallel.moe import expert_layer, ffn

    b, t = ids.shape
    nbpool, bt = hybrid_pool_shape(cache)
    nb = block_tables.shape[1]
    phys = jnp.take_along_axis(
        block_tables, jnp.clip(positions // bt, 0, nb - 1), axis=1)
    phys = jnp.where(positions < nb * bt, phys, nbpool)  # drop the pads
    off = positions % bt
    batch_ix = jnp.arange(b)[:, None]
    live = (jnp.arange(t)[None, :] < lengths[:, None]).reshape(b * t)
    x = jnp.take(params["embed"]["table"], ids, axis=0).astype(jnp.float32)
    zero = jnp.zeros((), jnp.int32)

    def recurrent(kind, p, x, cache, l):
        scope, groups = _RECURRENT[kind]
        mixer, mixer_cfg, _ = _recurrent_mixer(kind, cfg)
        with jax.named_scope(scope):
            u = core.rmsnorm(p["norm"], x, cfg.eps)
            at = l if slots is None else (l, slots)
            state = {name: jnp.where(
                reset.reshape((b,) + (1,) * (cache[group].ndim - 2)), 0.0,
                cache[group][at]) for name, group in groups.items()}
            out, state = mixer(p, u, state, lengths, mixer_cfg)
            cache = {**cache, **{group: cache[group].at[at].set(state[name])
                                 for name, group in groups.items()}}
            x = x + out
        return x, cache

    def attention(kind, p, x, cache, l):
        with jax.named_scope("attn"):
            u = core.rmsnorm(p["norm"], x, cfg.eps).astype(p["wq"].dtype)
            proj = lambda w: jnp.dot(u, w,
                                     preferred_element_type=jnp.float32)
            kv_dt = cache["k"].dtype
            rows = (b, t, cfg.kv_heads, cfg.head_dim)
            q, k, v = proj(p["wq"]), proj(p["wk"]), proj(p["wv"])
            if kind == "G":  # a head's query then its gate; norms, rotary
                q, gate = jnp.split(q.reshape(
                    b, t, cfg.q_heads, 2 * cfg.head_dim), 2, axis=-1)
                q, k = (rotary(core.rmsnorm(p[norm], a, cfg.eps), positions,
                               cfg.rotary_dim, cfg.rope_theta)
                        for norm, a in (("q_norm", q),
                                        ("k_norm", k.reshape(rows))))
            q = q.astype(kv_dt).reshape(b, t, cfg.q_heads, cfg.head_dim)
            k, v = k.astype(kv_dt).reshape(rows), v.astype(kv_dt).reshape(rows)
            view = (b, nb * bt, cfg.kv_heads, cfg.head_dim)
            lk = cache["k"].at[l, block_tables].get(mode="clip").reshape(view)
            lv = cache["v"].at[l, block_tables].get(mode="clip").reshape(view)
            lk = lk.at[batch_ix, positions].set(k)
            lv = lv.at[batch_ix, positions].set(v)
            o = gqa_cached(q, lk, lv, positions)
            if kind == "G":
                o = (o * jax.nn.sigmoid(gate).reshape(o.shape)).astype(
                    p["wo"].dtype)
            out = jnp.dot(o, p["wo"], preferred_element_type=jnp.float32)
            # the rows come back out of the view, so that the pool's write
            # follows its read and needs no copy (as `_paged_forward`)
            k = jnp.take_along_axis(
                lk, positions[:, :, None, None], axis=1).reshape(b, t, -1)
            v = jnp.take_along_axis(
                lv, positions[:, :, None, None], axis=1).reshape(b, t, -1)
            cache = {**cache,
                     "k": cache["k"].at[l, phys, off].set(k, mode="drop"),
                     "v": cache["v"].at[l, phys, off].set(v, mode="drop")}
            x = x + out
        return x, cache

    def latent(p, x, cache, l):
        from rafiki_tpu.ops.mla import mla_attend, mla_project

        with jax.named_scope("latent"):
            u = core.rmsnorm(p["norm"], x, cfg.eps)
            pool = cache["latent"]
            wide = cfg.mla.cache_row  # the row, and zeros up to the lanes
            q_nope, q_rope, rows = mla_project(p, u, positions, cfg.mla,
                                               turn_rows)
            rows = jnp.pad(rows.astype(pool.dtype),
                           ((0, 0), (0, 0), (0, wide - cfg.mla.row)))

            # the last real token's position: no row past it is read
            reach = jnp.max(jnp.where(
                jnp.arange(t)[None, :] < lengths[:, None], positions, 0))

            def over(width):
                """The product over a view of the table's first `width`
                blocks, and the new rows as the view holds them."""
                def attend(_):
                    view = pool.at[l, block_tables[:, :width]].get(
                        mode="clip").reshape(b, width * bt, wide)
                    view = view.at[batch_ix, positions].set(rows,
                                                            mode="drop")
                    out = mla_attend(p, q_nope, q_rope, view, positions,
                                     cfg.mla, last=reach)
                    # the rows come back out of the view (as the attention
                    # kinds'), so that the pool's write follows its read and
                    # needs no copy; a padding row past the view is written
                    # as it was made
                    back = jnp.take_along_axis(
                        view, jnp.minimum(positions, width * bt - 1)[
                            :, :, None], axis=1)
                    return out, jnp.where(
                        (positions < width * bt)[:, :, None], back, rows)
                return attend

            # A chunk of many tokens reads no further than its last real
            # token reaches: of a short ladder of widths (the table's, halved
            # down to a sixteenth) the narrowest that holds it, chosen in the
            # program, so that a prompt's first chunks do not pay for the
            # served context. One query a sequence takes the table as it is
            # handed (the worker cuts a decode round's).
            widths = sorted({w for w in (nb // 16, nb // 8, nb // 4, nb // 2,
                                         nb) if w * bt >= t})
            if t == 1 or len(widths) == 1:
                out, back = over(nb)(None)
            else:
                out, back = jax.lax.switch(
                    sum((reach >= w * bt).astype(jnp.int32)
                        for w in widths[:-1]),
                    [over(w) for w in widths], None)
            cache = {**cache, "latent": pool.at[l, phys, off].set(
                back, mode="drop")}
            x = x + out
        return x, cache

    act = _EXPERT_ACTS[cfg.expert_act]

    def dense(p, x):
        with jax.named_scope("mlp"):
            u = core.rmsnorm(p["norm"], x, cfg.eps).reshape(b * t, cfg.dim)
            out = ffn(u, p["w_up"], p["w_down"], act, cfg.expert_gated)
            x = x + out.reshape(b, t, cfg.dim)
        return x

    def experts(p, x, counts):
        with jax.named_scope("moe"):
            u = core.rmsnorm(p["norm"], x, cfg.eps).reshape(b * t, cfg.dim)
            # the loop over the experts hit reads each one's two matrices
            # in place: only the experts a token chose are touched
            routed, c = expert_layer(
                p, u, cfg.top_k, held=cfg.held, score=cfg.route_score,
                scale=cfg.route_scale, act=act, gated=cfg.expert_gated,
                live=live, gather=True)
            shared = ffn(u, p["s_up"], p["s_down"], act, cfg.expert_gated)
            if cfg.shared_gate:
                shared = shared * jax.nn.sigmoid(jnp.dot(
                    u.astype(p["s_gate"].dtype), p["s_gate"],
                    preferred_element_type=jnp.float32))
            out = routed + shared
            counts = {name: counts[name] + c[name] for name in counts}
            x = x + out.reshape(b, t, cfg.dim)
        return x, counts

    # The layers run in line, each with its own leaves. Under a `lax.scan`
    # over the pattern's periods (leaves stacked by period) the TPU compiler
    # copied the whole stack of W_up into the loop on every call (3.96 GB at
    # 6 x 64 experts of 2688 x 1856; compiled for a described v5e, PR 27);
    # 14 layers in line compile in 6 s.
    counts = {"expert_tokens": zero, "experts_hit": zero}
    at = {}  # the next cache row of each group of layers
    for l, kind in enumerate(cfg.pattern):
        p = params["layers"][f"{l:02d}"]
        group = "*" if kind in ATTENTION_KINDS else kind  # one pool for both
        row = at.get(group, 0)
        if kind in _RECURRENT:
            x, cache = recurrent(kind, p, x, cache, row)
        elif kind in ATTENTION_KINDS:
            x, cache = attention(kind, p, x, cache, row)
        elif kind == "L":
            x, cache = latent(p, x, cache, row)
        elif kind == "F":
            x = dense(p, x)
        else:
            x, counts = experts(p, x, counts)
        at[group] = row + 1
    counts["expert_layers"] = jnp.asarray(cfg.count("E"), jnp.int32)
    return x, cache, counts


def _hybrid_head(params: Params, x: jax.Array, cfg: HybridConfig) -> jax.Array:
    x = core.rmsnorm(params["norm_f"], x, cfg.eps).astype(
        params["head"].dtype)
    return jnp.einsum("...d,vd->...v", x, params["head"],
                      preferred_element_type=jnp.float32)


def hybrid_paged_prefill(params: Params, cache: Cache, block_table: jax.Array,
                         ids: jax.Array, start: jax.Array, length: jax.Array,
                         slot: Optional[jax.Array], cfg: HybridConfig,
                         reset: Optional[jax.Array] = None,
                         turn_rows: bool = True) -> Tuple[jax.Array, Cache]:
    """:func:`paged_prefill` for a hybrid stack: the chunk continues
    ``slot``'s recurrent state, from zero where ``start == 0`` (``reset``
    overrides that, and ``turn_rows=False`` leaves the latent rows
    unturned, for tests of what a stale state and a wrong row do). A pattern
    without a stateful kind takes ``slot=None``. Returns (logits (V,) at the
    chunk's last real position, cache)."""
    if slot is None and cfg.count("".join(_RECURRENT)):
        raise ValueError(f"pattern {cfg.pattern!r} holds a stateful kind: "
                         "its prefill needs the slot")
    ids = jnp.asarray(ids, jnp.int32)[None]
    start = jnp.asarray(start, jnp.int32)
    length = jnp.asarray(length, jnp.int32)
    positions = (start + jnp.arange(ids.shape[1], dtype=jnp.int32))[None]
    reset = (start == 0) if reset is None else jnp.asarray(reset, bool)
    x, cache, _ = _hybrid_forward(
        params, cache, ids, positions,
        jnp.asarray(block_table, jnp.int32)[None],
        None if slot is None else jnp.asarray(slot, jnp.int32)[None],
        length[None], reset[None], cfg, turn_rows)
    return _hybrid_head(params, x[0, length - 1], cfg), cache


def hybrid_paged_decode_step(params: Params, cache: Cache, ids: jax.Array,
                             positions: jax.Array, block_tables: jax.Array,
                             cfg: HybridConfig
                             ) -> Tuple[jax.Array, Cache,
                                        Dict[str, jax.Array]]:
    """:func:`paged_decode_step` for a hybrid stack: row i is slot i. A row
    whose table is all sentinel keeps its state and chooses no expert.
    Returns (logits (S, V), cache, the expert layers' counts)."""
    block_tables = jnp.asarray(block_tables, jnp.int32)
    s = block_tables.shape[0]
    live = block_tables[:, 0] < hybrid_pool_shape(cache)[0]
    x, cache, counts = _hybrid_forward(
        params, cache, jnp.asarray(ids, jnp.int32)[:, None],
        jnp.asarray(positions, jnp.int32)[:, None], block_tables, None,
        live.astype(jnp.int32), jnp.zeros((s,), bool), cfg)
    return _hybrid_head(params, x[:, 0], cfg), cache, counts


def hybrid_apply(params: Params, ids: jax.Array, cfg: HybridConfig
                 ) -> jax.Array:
    """ids (B, S) -> logits (B, S, V): the whole sequences at once from a
    zero state, through a cache made for the call."""
    ids = jnp.asarray(ids, jnp.int32)
    b, s = ids.shape
    cache = init_hybrid_cache(cfg, b, s, b, kv_dtype=params["head"].dtype)
    x, _, _ = _hybrid_forward(
        params, cache, ids, jnp.broadcast_to(jnp.arange(s), (b, s)),
        jnp.arange(b, dtype=jnp.int32)[:, None], jnp.arange(b),
        jnp.full((b,), s, jnp.int32), jnp.ones((b,), bool), cfg)
    return _hybrid_head(params, x, cfg)


def copy_hybrid_kv_blocks(cache: Cache, src: jax.Array,
                          dst: jax.Array) -> Cache:
    """:func:`copy_kv_blocks` over every paged group (``k``/``v``,
    ``latent``); the state has no blocks."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    return {**cache, **{name: a.at[:, dst].set(jnp.take(a, src, axis=1))
                        for name, a in _paged(cache).items()}}
