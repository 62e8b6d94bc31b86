"""Paged KV allocator + shared prefix cache + chunked prefill
(worker/kv_paging.py, models/lm.py paged forwards, the generation
worker's paged scheduler). THE tier-1 invariant lives here: paged
``decode_step`` output is bit-identical to the contiguous-ring path for
the same prompts, including across a copy-on-write divergence point."""

import os
import sys
import threading
import time

import numpy as np
import pytest

from rafiki_tpu.worker.kv_paging import (
    KVPoolExhaustedError,
    PagedKVAllocator,
)

HERE = os.path.dirname(__file__)
GEN_FIXTURE = os.path.join(HERE, "fixtures", "gen_model.py")


# -- model layer: the tier-1 bit-identity invariant ---------------------------

def test_paged_forward_bit_identical_to_ring():
    """Prefill + decode through block tables must produce EXACTLY the
    ring path's logits — the gather view presents the same logical rows
    to the same `_cached_forward`, so even the float bits match."""
    import jax
    import jax.numpy as jnp

    from rafiki_tpu.models import lm

    cfg = lm.tiny(vocab=64, max_len=32, dim=16, depth=2, heads=2)
    params = lm.init(jax.random.PRNGKey(0), cfg)
    bt, nb = 8, 4
    prompt = jnp.array([5, 9, 2, 7, 3], jnp.int32)
    n = 5

    ring = lm.init_kv_cache(cfg, max_slots=2, max_len=32)
    lg_r, ring = lm.prefill(params, ring, 0, jnp.pad(prompt, (0, 3)), n,
                            cfg)
    pool = lm.init_paged_kv_cache(cfg, pool_blocks=8, block_tokens=bt)
    table = np.full(nb, 8, np.int32)
    table[0], table[1] = 3, 6  # non-contiguous physical pages on purpose
    lg_p, pool = lm.paged_prefill(params, pool, table,
                                  jnp.pad(prompt, (0, 3)), 0, n, cfg)
    assert np.array_equal(np.asarray(lg_r), np.asarray(lg_p))

    ids = np.array([int(lm.greedy_token(lg_r)), 0], np.int32)
    pos = np.array([n, 0], np.int32)
    tables = np.full((2, nb), 8, np.int32)
    tables[0] = table
    for _ in range(6):
        lg2_r, ring = lm.decode_step(params, ring, ids,
                                     jnp.asarray(pos), cfg)
        lg2_p, pool = lm.paged_decode_step(params, pool, ids, pos,
                                           tables, cfg)
        assert np.array_equal(np.asarray(lg2_r), np.asarray(lg2_p))
        t = int(lm.greedy_token(lg2_r)[0])
        ids[0] = t
        pos[0] += 1
        blk = pos[0] // bt
        if pos[0] % bt == 0 and blk < nb and tables[0][blk] == 8:
            tables[0][blk] = 1  # grow the table mid-decode


def test_paged_cow_divergence_no_corruption():
    """Two streams sharing a prefix page, diverging at the tail: the
    INCUMBENT stream's decode must stay BIT-identical to its ring
    reference through the sibling's divergence (its pages are never
    touched — the COW invariant), and the diverging stream must track its
    own ring reference at token level (its suffix is forwarded with a
    different shape than a full prefill, so bit-identity is per-shape:
    ulp-level rounding differs, the greedy stream must not)."""
    import jax
    import jax.numpy as jnp

    from rafiki_tpu.models import lm

    cfg = lm.tiny(vocab=64, max_len=32, dim=16, depth=1, heads=2)
    params = lm.init(jax.random.PRNGKey(1), cfg)
    bt, nb = 8, 4
    shared = [4, 8, 15, 16, 23, 42, 7, 1]          # exactly one block
    pa = shared + [11]
    pb = shared + [33]                              # diverges at pos 8

    pool = lm.init_paged_kv_cache(cfg, pool_blocks=8, block_tokens=bt)
    # stream A prefills the shared block (page 0) + its tail (page 1)
    ta = np.full(nb, 8, np.int32)
    ta[0], ta[1] = 0, 1
    lga, pool = lm.paged_prefill(params, pool, ta,
                                 np.asarray(pa, np.int32), 0, 9, cfg)
    # stream B shares page 0, gets its own tail page 2; it only forwards
    # its one-token suffix at position 8 — the shared page serves 0..7
    tb = np.full(nb, 8, np.int32)
    tb[0], tb[1] = 0, 2
    lgb, pool = lm.paged_prefill(params, pool, tb,
                                 np.asarray([33], np.int32), 8, 1, cfg)
    # reference: two independent ring caches
    ring = lm.init_kv_cache(cfg, max_slots=2, max_len=32)
    lga_r, ring = lm.prefill(params, ring, 0,
                             np.pad(np.asarray(pa, np.int32), (0, 7)), 9,
                             cfg)
    lgb_r, ring = lm.prefill(params, ring, 1,
                             np.pad(np.asarray(pb, np.int32), (0, 7)), 9,
                             cfg)
    # A forwarded the same shape as the ring prefill: bit-identical
    assert np.array_equal(np.asarray(lga), np.asarray(lga_r))
    # B skipped the shared span: token-identical, logits within ulps
    assert int(lm.greedy_token(lgb)) == int(lm.greedy_token(lgb_r))
    assert np.allclose(np.asarray(lgb), np.asarray(lgb_r), atol=1e-5)
    ids = np.array([int(lm.greedy_token(lga)),
                    int(lm.greedy_token(lgb))], np.int32)
    pos = np.array([9, 9], np.int32)
    tables = np.stack([ta, tb])
    for _ in range(5):
        lg_r, ring = lm.decode_step(params, ring, ids,
                                    jnp.asarray(pos), cfg)
        lg_p, pool = lm.paged_decode_step(params, pool, ids, pos,
                                          tables, cfg)
        # slot A: bit-identical through B's divergence — B never wrote
        # into the shared page
        assert np.array_equal(np.asarray(lg_r)[0], np.asarray(lg_p)[0])
        # slot B: the greedy stream tracks its ring reference exactly
        assert np.array_equal(np.asarray(lm.greedy_token(lg_r)),
                              np.asarray(lm.greedy_token(lg_p)))
        assert np.allclose(np.asarray(lg_r)[1], np.asarray(lg_p)[1],
                           atol=1e-5)
        ids = np.asarray(lm.greedy_token(lg_r))
        pos += 1
        for s in range(2):
            blk = pos[s] // bt
            if pos[s] % bt == 0 and tables[s][blk] == 8:
                tables[s][blk] = 3 + s


_BT, _NB, _POOL = 8, 4, 10          # block tokens, table width, pool blocks
_SHARED = [4, 8, 15, 16, 23, 42, 7, 1]   # exactly one block


def _ring_and_pool():
    """Two live streams in a ring and in a pool that hold the same rows:
    A (11 tokens) and B (14) share their first block, slot 2 is idle. Every
    pool block no table names holds noise, the clipped-to last one too."""
    import jax

    from rafiki_tpu.models import lm

    cfg = lm.tiny(vocab=64, max_len=_NB * _BT, dim=16, depth=2, heads=2)
    params = lm.init(jax.random.PRNGKey(3), cfg)
    prompts = [_SHARED + [11, 12, 13], _SHARED + [33, 2, 9, 40, 6, 21]]
    ring = lm.init_kv_cache(cfg, max_slots=3, max_len=_NB * _BT)
    toks = []
    for slot, prompt in enumerate(prompts):
        lg, ring = lm.prefill(params, ring, slot, np.pad(
            np.asarray(prompt, np.int32), (0, 16 - len(prompt))),
            len(prompt), cfg)
        toks.append(int(lm.greedy_token(lg)))
    rk, rv = np.asarray(ring["k"]), np.asarray(ring["v"])
    assert np.array_equal(rk[:, 0, :_BT], rk[:, 1, :_BT])  # one shared block
    tables = np.full((3, _NB), _POOL, np.int32)
    tables[0, :2], tables[1, :2] = (4, 7), (4, 2)
    rng = np.random.default_rng(5)
    pool = {}
    for name, plane in (("k", rk), ("v", rv)):
        pp = rng.normal(size=(2, _POOL, _BT, 16)).astype(np.float32)
        for slot in (0, 1):
            for blk in (0, 1):
                pp[:, tables[slot, blk]] = plane[
                    :, slot, blk * _BT:(blk + 1) * _BT].reshape(2, _BT, 16)
        pool[name] = pp
    frontiers = np.array([len(prompts[0]), len(prompts[1]), 0], np.int32)
    return cfg, params, ring, pool, tables, frontiers, toks


def _assert_pool_tracks_ring(pool, before, ring, tables, frontiers, shared=4):
    """Through its table each live slot reads the ring's rows, bit for bit,
    up to its frontier; no block outside the live tables, nor the shared
    one, has changed."""
    for name in ("k", "v"):
        pp, rr = np.asarray(pool[name]), np.asarray(ring[name])
        for slot, n in enumerate(frontiers):
            pos = np.arange(min(int(n), _NB * _BT))
            phys = tables[slot, pos // _BT]
            live = phys < _POOL          # rows past the table were dropped
            rows = pp[:, phys[live], pos[live] % _BT]
            assert np.array_equal(
                rows, rr[:, slot, pos[live]].reshape(rows.shape))
        named = {int(b) for b in tables.ravel() if b < _POOL}
        for blk in (set(range(_POOL)) - named) | {shared}:
            assert np.array_equal(pp[:, blk], before[name][:, blk]), blk


@pytest.mark.parametrize("entry", ["decode", "prefill", "verify"])
def test_paged_entry_points_bit_identical_to_ring(entry):
    """Decode (T = 1), prefill in chunks of two bucket sizes and verify
    (T = k+1) give the ring forward's bits, with an idle slot whose table
    row is all sentinel, rows that map to sentinel entries (in the view,
    never in the pool) and a block two tables share; and they write the new
    rows and nothing else."""
    import jax.numpy as jnp

    from rafiki_tpu.models import lm

    cfg, params, ring, pool, tables, front, toks = _ring_and_pool()
    before = {n: a.copy() for n, a in pool.items()}
    pool = {n: jnp.asarray(a) for n, a in pool.items()}
    if entry == "decode":
        ids = np.array(toks + [0], np.int32)
        pos = front.copy()
        for _ in range(6):            # B, then A, grow into a third block
            for slot in (0, 1):
                if tables[slot, pos[slot] // _BT] == _POOL:
                    tables[slot, pos[slot] // _BT] = 5 + slot
            lg_r, ring = lm.decode_step(params, ring, ids, pos, cfg)
            lg_p, pool = lm.paged_decode_step(params, pool, ids, pos,
                                              tables, cfg)
            assert np.array_equal(np.asarray(lg_r), np.asarray(lg_p))
            ids[:2] = np.asarray(lm.greedy_token(lg_r))[:2]
            pos[:2] += 1
        front = pos
    elif entry == "prefill":
        # a third stream of 13 tokens: a full chunk of 8, then 5 padded to
        # a bucket of 16 whose last 8 rows map to a sentinel entry
        prompt = np.asarray(_SHARED[::-1] + [3, 1, 4, 1, 5], np.int32)
        tables[2, :2] = (9, 0)
        for start, bucket, n in ((0, 8, 8), (8, 16, 5)):
            ids = np.zeros(bucket, np.int32)
            ids[:n] = prompt[start:start + n]
            positions = (start + np.arange(bucket, dtype=np.int32))[None]
            lg_r, ck, cv = lm._cached_forward(
                params, ring["k"][:, 2:3], ring["v"][:, 2:3], ids[None],
                positions, cfg)
            ring = {"k": ring["k"].at[:, 2:3].set(ck),
                    "v": ring["v"].at[:, 2:3].set(cv)}
            lg_p, pool = lm.paged_prefill(params, pool, tables[2], ids,
                                          start, n, cfg)
            assert np.array_equal(np.asarray(lg_r)[0, n - 1],
                                  np.asarray(lg_p))
        front = np.array([front[0], front[1], 16], np.int32)
    else:
        k = 3                         # B's rows 16, 17 map to a sentinel
        ids = np.zeros((3, k + 1), np.int32)
        ids[:2, 0] = toks
        positions = front[:, None] + np.arange(k + 1, dtype=np.int32)
        for j in range(k + 1):        # the greedy chain, a proposal a pass
            if j == k:
                ids[0, k] = (ids[0, k] + 1) % 64   # A's last one is wrong
            lg_r, ck, cv = lm._cached_forward(params, ring["k"], ring["v"],
                                              ids, positions, cfg)
            am = np.asarray(lm.greedy_token(lg_r))        # (3, k+1)
            if j < k:
                ids[:2, j + 1] = am[:2, j]
        ring = {"k": ck, "v": cv}
        sampling = {"seed": np.zeros(3, np.uint32),
                    "temperature": np.zeros(3, np.float32),
                    "top_k": np.zeros(3, np.int32),
                    "top_p": np.ones(3, np.float32),
                    "role": lm.ROLE_TARGET}
        acc, out, pool = lm.paged_verify_step(
            params, pool, ids, positions, tables,
            np.full((3, k, 64), 1.0 / 64, np.float32), sampling, cfg)
        for slot in (0, 1):
            hits = list(ids[slot, 1:] == am[slot, :k]) + [False]
            a = hits.index(False)
            assert int(np.asarray(acc)[slot]) == a
            assert list(np.asarray(out)[slot, :a + 1]) == \
                list(ids[slot, 1:a + 1]) + [am[slot, a]]
        assert list(np.asarray(acc)[:2]) == [k - 1, k]   # B earns the bonus
        front = np.array([front[0] + k + 1, front[1] + k + 1, 0], np.int32)
    _assert_pool_tracks_ring(pool, before, ring, tables, front)


def _scanned_ring_forward(params, cache, ids, positions, cfg):
    """The plain reference of the layer walk: `lm._cached_forward` as a
    `lax.scan` over the stacked leaves and the ring's planes."""
    import jax

    from rafiki_tpu.models import lm

    def body(x, layer):
        p, lk, lv = layer
        x, lk, lv = lm._cached_block(p, x, lk, lv, positions,
                                     cfg.encoder.heads)
        return x, (lk, lv)

    x = lm._embed_tokens(params, ids, positions, cache["k"].dtype)
    x, (ck, cv) = jax.lax.scan(
        body, x, (params["blocks"], cache["k"], cache["v"]))
    return lm._lm_head(params, x), {"k": ck, "v": cv}


def _scanned_paged_forward(params, cache, ids, positions, tables, cfg):
    """The same for `lm._paged_forward`: the pool rides the scan's carry,
    layer `l` gathers its blocks, runs the block, writes its new rows."""
    import jax
    import jax.numpy as jnp

    from rafiki_tpu.models import lm

    pk, pv = cache["k"], cache["v"]
    depth, nbpool, bt, dim = pk.shape
    b, nb = tables.shape
    heads = cfg.encoder.heads
    phys = jnp.take_along_axis(
        tables, jnp.clip(positions // bt, 0, nb - 1), axis=1)
    phys = jnp.where(positions < nb * bt, phys, nbpool)
    off = positions % bt

    def body(carry, layer):
        x, pk, pv = carry
        p, l = layer
        view = (b, nb * bt, heads, dim // heads)
        lk = pk.at[l, tables].get(mode="clip").reshape(view)
        lv = pv.at[l, tables].get(mode="clip").reshape(view)
        x, lk, lv = lm._cached_block(p, x, lk, lv, positions, heads)
        at = positions[:, :, None, None]
        k = jnp.take_along_axis(lk, at, axis=1).reshape(*positions.shape, dim)
        v = jnp.take_along_axis(lv, at, axis=1).reshape(*positions.shape, dim)
        return (x, pk.at[l, phys, off].set(k, mode="drop"),
                pv.at[l, phys, off].set(v, mode="drop")), None

    x = lm._embed_tokens(params, ids, positions, pk.dtype)
    (x, pk, pv), _ = jax.lax.scan(
        body, (x, pk, pv), (params["blocks"], jnp.arange(depth)))
    return lm._lm_head(params, x), {"k": pk, "v": pv}


@pytest.mark.parametrize("depth", [1, 2, 5])
@pytest.mark.parametrize("layout", ["ring", "paged"])
def test_the_layer_walk_returns_the_bits_of_a_plain_scan(layout, depth):
    """`lm._dense_layers` hands each layer its leaves through a rounding to
    their own format (what keeps the TPU compiler from copying the stack)
    and carries the ring's planes as it does the pool's; a plain `lax.scan`
    over the same `_cached_block` (the form before PR 33, kept here as the
    reference) gives the same logits and the same cache, bit for bit, for a
    chunk of a prompt (T = 8) and for decode rounds (T = 1) after it, an
    idle slot among the live."""
    import jax
    import jax.numpy as jnp

    from rafiki_tpu.models import lm

    cfg = lm.tiny(vocab=64, max_len=_NB * _BT, dim=16, depth=depth, heads=2)
    params = lm.init(jax.random.PRNGKey(7), cfg)
    rng = np.random.default_rng(depth)
    tables = np.full((3, _NB), _POOL, np.int32)
    tables[0, :2], tables[1, :2] = (4, 7), (1, 2)
    if layout == "ring":
        cache = jax.tree.map(
            lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype),
            lm.init_kv_cache(cfg, max_slots=3, max_len=_NB * _BT))

        def walked(c, ids, pos):
            lg, ck, cv = lm._cached_forward(params, c["k"], c["v"], ids, pos,
                                            cfg)
            return lg, {"k": ck, "v": cv}

        def scanned(c, ids, pos):
            return _scanned_ring_forward(params, c, ids, pos, cfg)
    else:
        cache = jax.tree.map(
            lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype),
            lm.init_paged_kv_cache(cfg, _POOL, _BT))

        def walked(c, ids, pos):
            return lm._paged_forward(params, c, ids, pos, tables, cfg)

        def scanned(c, ids, pos):
            return _scanned_paged_forward(params, c, ids, pos,
                                          jnp.asarray(tables), cfg)

    walked, scanned = jax.jit(walked), jax.jit(scanned)
    mine = theirs = cache
    ids = rng.integers(0, 64, size=(3, 8)).astype(np.int32)
    pos = np.tile(np.arange(8, dtype=np.int32), (3, 1))
    for _ in range(4):                # the chunk, then three decode rounds
        lg_i, mine = walked(mine, ids, pos)
        lg_s, theirs = scanned(theirs, ids, pos)
        assert np.array_equal(np.asarray(lg_i), np.asarray(lg_s))
        for name in ("k", "v"):
            assert np.array_equal(np.asarray(mine[name]),
                                  np.asarray(theirs[name])), name
        ids = np.asarray(lm.greedy_token(lg_i))[:, -1:]
        pos = pos[:, -1:] + 1


def _fixture(module):
    sys.path.insert(0, HERE)
    try:
        return __import__(f"fixtures.{module}", fromlist=[module])
    finally:
        sys.path.pop(0)


@pytest.mark.parametrize("width", [2, 4, 8, 16])
@pytest.mark.parametrize("entry", ["dense", "dense_sampled", "hybrid"])
def test_decode_over_a_cut_table_is_bit_identical(entry, width):
    """A decode round whose tables are cut to 1x, 2x and 4x the blocks its
    furthest write needs returns the full table's bits: logits (tokens and
    distributions, sampled), the pool's planes and a recurrent model's
    state. The fixtures' own models (context 64, here in blocks of 4), an
    idle row, a row that maps a block past its position, and a pool of
    noise: whatever is gathered beyond the cut is masked, and adds zeros."""
    import jax
    import jax.numpy as jnp

    from rafiki_tpu.models import lm

    bt, full, pool_blocks, slots = 4, 16, 24, 4
    positions = np.array([5, 7, 3, 0], np.int32)   # need: 2 blocks
    ids = np.array([9, 30, 2, 0], np.int32)
    tables = np.full((slots, full), pool_blocks, np.int32)
    tables[0, :3], tables[1, :2], tables[2, :1] = (4, 11, 6), (9, 2), (17,)
    noise = lambda shape, k: jax.random.normal(jax.random.key(k), shape)
    if entry == "hybrid":
        cfg = _fixture("hybrid_gen_model").CFG
        params = lm.hybrid_init(jax.random.key(0), cfg, dtype=jnp.float32)
        cache = lm.init_hybrid_cache(cfg, pool_blocks, bt, slots,
                                     kv_dtype=jnp.float32)
        step = lambda t: lm.hybrid_paged_decode_step(
            params, cache, ids, positions, t, cfg)
    else:
        model = _fixture("gen_model").TinyGenLM()
        model.train(None)
        cfg, params = model._cfg, model._params
        cache = lm.init_paged_kv_cache(cfg, pool_blocks, bt)
        sampling = {"seed": np.arange(slots, dtype=np.uint32) + 7,
                    "temperature": np.array([0.0, 0.9, 1.3, 0.0], np.float32),
                    "top_k": np.array([0, 8, 0, 0], np.int32),
                    "top_p": np.array([1.0, 1.0, 0.8, 1.0], np.float32),
                    "role": lm.ROLE_TARGET}
        step = (lambda t: lm.paged_decode_step_sampled(
            params, cache, ids, positions, t, sampling, cfg)) \
            if entry == "dense_sampled" else (lambda t: lm.paged_decode_step(
                params, cache, ids, positions, t, cfg))
    cache = {name: noise(a.shape, i).astype(a.dtype)
             for i, (name, a) in enumerate(sorted(cache.items()))}
    want, got = step(tables), step(tables[:, :width])
    same = jax.tree.map(lambda a, b: np.array_equal(np.asarray(a),
                                                    np.asarray(b)), want, got)
    assert all(jax.tree.leaves(same)), same
    # and the round wrote its rows: the pool is not the one it was handed
    new = want[-2] if entry == "hybrid" else want[-1]
    assert not np.array_equal(np.asarray(new["k"]), np.asarray(cache["k"]))


def test_decode_tables_take_the_next_rung_and_refuse_past_the_last():
    """The model drops a write at `width * block_tokens`, silently. The
    worker's tables for a row about to write there are a rung wider, and
    past the widest rung the round is refused, typed."""
    from rafiki_tpu.worker.generation import GenerationWorker, _Slot

    alloc = PagedKVAllocator(pool_blocks=80, block_tokens=16,
                             table_blocks=40)
    assert alloc.table_widths == (8, 16, 32, 40)
    assert PagedKVAllocator(8, 8, 8).table_widths == (8,)    # 64 tokens
    assert PagedKVAllocator(8, 256, 4).table_widths == (1, 2, 4)
    worker = GenerationWorker("widthjob", "trial1", db=None, broker=None)
    worker._alloc = alloc
    slot = _Slot(None, [1], 1, None, seq=1)
    slots = [None, slot]
    alloc.open_slot(1, [1])
    for width, wider in zip(alloc.table_widths,
                            alloc.table_widths[1:] + (None,)):
        slot.position = width * 16 - 1          # the rung's last position
        assert alloc.ensure_capacity(1, slot.position)
        tables = worker._decode_tables(slots, {1})
        assert tables.shape == (2, width)
        assert (tables[0] == alloc.sentinel).all()
        assert (tables[1] < alloc.sentinel).all()
        slot.position = width * 16              # one past it
        if wider is None:
            with pytest.raises(KVPoolExhaustedError, match="past the table"):
                worker._decode_tables(slots, {1})
        else:
            assert worker._decode_tables(slots, {1}).shape == (2, wider)


def test_paged_decode_program_keeps_no_whole_depth_view():
    """The decode program gathers one layer's blocks at a time and updates
    the donated pool in place: compiled, its temporaries stay under ONE
    view of all slots over all layers. A pool-sized copy, or the views of
    every layer gathered ahead of the scan, is at least two of them."""
    import jax
    import jax.numpy as jnp

    from rafiki_tpu.models import lm

    depth, slots, nb, bt, dim = 4, 4, 32, 16, 64
    cfg = lm.tiny(vocab=256, max_len=nb * bt, dim=dim, depth=depth, heads=4)
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), cfg))
    pool = jax.eval_shape(
        lambda: lm.init_paged_kv_cache(cfg, slots * nb, bt))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    compiled = jax.jit(
        lambda p, c, i, q, t: lm.paged_decode_step(p, c, i, q, t, cfg),
        donate_argnums=1).lower(
            params, pool, i32(slots), i32(slots), i32(slots, nb)).compile()
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries < depth * slots * nb * bt * dim * 4, temporaries


def test_paged_cache_takes_expert_blocks():
    """The paged pool of a model with expert blocks: same planes as a
    dense one's, and a prefill through it gives the ring path's logits."""
    import jax
    import jax.numpy as jnp

    from rafiki_tpu.models import lm

    cfg = lm.tiny(vocab=64, max_len=32, dim=16, depth=2, heads=2,
                  moe_experts=2)
    params = lm.init(jax.random.PRNGKey(0), cfg)
    pool = lm.init_paged_kv_cache(cfg, 4, 8)
    assert pool["k"].shape == (2, 4, 8, 16)
    ids = jnp.array([5, 9, 2, 7, 3, 0, 0, 0], jnp.int32)
    ring = lm.init_kv_cache(cfg, max_slots=1, max_len=32)
    lg_r, _ = lm.prefill(params, ring, 0, ids, 5, cfg)
    lg_p, _ = lm.paged_prefill(params, pool, np.arange(4, dtype=np.int32),
                               ids, 0, 5, cfg)
    assert np.array_equal(np.asarray(lg_r), np.asarray(lg_p))


# -- the allocator ------------------------------------------------------------

def test_allocator_alloc_free_refcounts():
    a = PagedKVAllocator(pool_blocks=8, block_tokens=4, table_blocks=4,
                         prefix_cache=False)
    plan = a.open_slot(0, [1, 2, 3, 4, 5])
    assert plan.cached_tokens == 0 and not plan.copies
    assert a.ensure_capacity(0, 5)          # 2 blocks for 6 positions
    assert a.used_blocks() == 2
    row = a.table_row(0)
    assert row.shape == (4,) and (row[2:] == a.sentinel).all()
    a.close_slot(0)
    assert a.used_blocks() == 0
    assert all(r == 0 for r in a.refcounts())
    with pytest.raises(KVPoolExhaustedError):
        a.ensure_capacity(0, 999)


def test_allocator_prefix_chain_hit_and_tail_cow():
    bt = 4
    a = PagedKVAllocator(pool_blocks=16, block_tokens=bt, table_blocks=8)
    prompt = list(range(10))                 # 2 full blocks + 2-token tail
    a.open_slot("A", prompt)
    assert a.ensure_capacity("A", 9)
    a.publish("A", prompt)
    # chain entries for blocks 0/1, tail entry for tokens (8, 9)
    assert a.stats()["cache_entries"] == 3
    # identical prompt: chain hit (8 tokens) + tail COPY of 1 usable token
    plan = a.open_slot("B", prompt)
    assert plan.cached_tokens == 9           # usable = n-1
    assert len(plan.copies) == 1             # the tail page was copied
    assert a.hits == 1 and a.hit_tokens == 9
    # the copy target is private to B: writing position 9 needs no COW
    assert a.ensure_writable("B", 9) == []
    # A, the publisher, must COW before writing into its published tail
    copies = a.ensure_writable("A", 10 // bt * bt + 2)
    assert copies and copies[0][0] != copies[0][1]
    # refcounts drain to cache-only on close, to zero on drop_cache
    a.close_slot("A")
    a.close_slot("B")
    assert a.evictable_blocks() == a.stats()["cache_entries"] == 3
    freed = a.drop_cache()
    assert freed == 3
    assert all(r == 0 for r in a.refcounts())
    assert a.free_blocks() == 16


def test_allocator_lru_eviction_under_pressure():
    bt = 4
    a = PagedKVAllocator(pool_blocks=4, block_tokens=bt, table_blocks=4)
    a.open_slot("A", list(range(5)))
    assert a.ensure_capacity("A", 4)
    a.publish("A", list(range(5)))     # chain block 0 + tail block cached
    a.close_slot("A")
    assert a.used_blocks() == 2              # cache holds two pages
    # a new slot needing the whole pool evicts the cache LRU-style
    a.open_slot("B", list(range(100, 113)))
    assert a.ensure_capacity("B", 12)        # 4 blocks
    assert a.used_blocks() == 4 and a.cache_evictions == 2
    a.close_slot("B")
    assert a.free_blocks() == 4


def test_allocator_tail_copy_survives_lru_pressure():
    """Review regression: open_slot's tail copy must pin the matched
    entry across the allocation — with the free list dry, _alloc_one's
    LRU eviction could otherwise evict (and free!) the very block it is
    about to copy from, crashing the admission (or copying a block onto
    itself)."""
    bt = 4
    a = PagedKVAllocator(pool_blocks=2, block_tokens=bt, table_blocks=4)
    prompt = list(range(6))                  # 1 chain block + 2-token tail
    a.open_slot("A", prompt)
    assert a.ensure_capacity("A", 5)
    a.publish("A", prompt)
    a.close_slot("A")
    assert a.free_blocks() == 0              # both pages cache-held
    # same prompt, free list dry: the chain page maps shared; the tail
    # copy cannot be satisfied (its own entry is the only LRU candidate
    # and must NOT be evicted out from under the copy) — admission
    # degrades to chain-only instead of crashing
    plan = a.open_slot("B", prompt)
    assert plan.cached_tokens == 4 and plan.copies == []
    # the tail entry survived intact
    assert a.stats()["cache_entries"] == 2


def test_stream_outgrowing_pool_fails_typed_not_forever(monkeypatch):
    """Review regression: a stream whose history grows past what the
    whole pool can hold must end with a TYPED kv_pool error — not cycle
    preempt -> resume forever while blocking all new admissions."""
    from rafiki_tpu.cache.queue import GenerationError, InProcessBroker

    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "2")
    monkeypatch.setenv("RAFIKI_GEN_KV_BLOCK_TOKENS", "8")
    monkeypatch.setenv("RAFIKI_GEN_KV_POOL_BLOCKS", "2")   # 16 tokens
    monkeypatch.setenv("RAFIKI_GEN_KV_PAGED", "1")
    monkeypatch.setenv("RAFIKI_GEN_PREFIX_CACHE", "0")
    monkeypatch.setenv("RAFIKI_GEN_PREFILL_CHUNK", "8")
    broker = InProcessBroker()
    worker, ctx, t = _start_worker(broker, _tiny_model(), job="growjob")
    q = list(broker.get_worker_queues("growjob").values())[0]
    try:
        # admission fits (ceil(11/8)=2 blocks) but position 16 needs a
        # third block the pool will never have
        s = _stream(q, [3] * 10, 20)
        with pytest.raises(GenerationError, match="outgrew the KV pool"):
            while True:
                d = s.next_delta(20)
                if d.finished:
                    break
        # the worker is healthy and admitting: a small request completes
        toks, _ = _drain(_stream(q, [5, 6], 3))
        assert len(toks) == 3
    finally:
        ctx.stopping = True
        t.join(timeout=10)


def test_readmitted_request_keeps_original_seq(monkeypatch):
    """Review regression: a stashed request resumed through _admit must
    keep its ORIGINAL admission seq — a fresh seq would make the oldest
    waiter the youngest resident and the first preemption victim."""
    from rafiki_tpu.cache.queue import InProcessBroker

    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "2")
    monkeypatch.setenv("RAFIKI_GEN_KV_BLOCK_TOKENS", "8")
    monkeypatch.setenv("RAFIKI_GEN_KV_PAGED", "1")
    broker = InProcessBroker()
    worker, ctx, t = _start_worker(broker, _tiny_model(), job="seqjob")
    q = list(broker.get_worker_queues("seqjob").values())[0]
    try:
        # drive one admission so the worker's scheduler state exists
        _drain(_stream(q, [2, 3], 2))
        seen = {}
        orig = worker._admit_paged

        def spy(model, spec, cache, slots, free, fut, prompt, max_tokens,
                deadline, service_id, seq=None, **kw):
            seen["seq"] = seq
            return orig(model, spec, cache, slots, free, fut, prompt,
                        max_tokens, deadline, service_id, seq=seq, **kw)

        worker._admit_paged = spy
        from rafiki_tpu.worker.generation import _Pending

        # simulate the re-admission path with a stashed (fut, query) that
        # carries its original seq
        class _Fut:
            def set_result(self, v):
                seen["resolved"] = v

            def set_error(self, e):
                seen["error"] = e

        worker._pending.append(_Pending(
            7, fut=_Fut(), query={"prompt_ids": [4, 5], "max_tokens": 2}))
        deadline = time.monotonic() + 10
        while "seq" not in seen and time.monotonic() < deadline:
            time.sleep(0.02)
        assert seen.get("seq") == 7, seen
    finally:
        ctx.stopping = True
        t.join(timeout=10)


def test_allocator_disabled_prefix_cache_never_shares():
    a = PagedKVAllocator(pool_blocks=8, block_tokens=4, table_blocks=4,
                         prefix_cache=False)
    prompt = list(range(9))
    a.open_slot("A", prompt)
    a.ensure_capacity("A", 8)
    a.publish("A", prompt)
    assert a.stats()["cache_entries"] == 0
    plan = a.open_slot("B", prompt)
    assert plan.cached_tokens == 0 and a.hits == 0


# -- the worker's paged scheduler ---------------------------------------------

class _Ctx:
    def __init__(self, service_id="w1"):
        self.service_id = service_id
        self.chips = None
        self.stopping = False
        self.is_ready = threading.Event()

    def ready(self):
        self.is_ready.set()


def _tiny_model():
    m = _fixture("gen_model").TinyGenLM()
    m.train(None)
    return m


def _start_worker(broker, model, job="pagedjob"):
    from rafiki_tpu.worker.generation import GenerationWorker

    worker = GenerationWorker(job, "trial1", db=None, broker=broker)
    worker._load_model = lambda sid: model
    ctx = _Ctx()
    t = threading.Thread(target=worker.start, args=(ctx,), daemon=True)
    t.start()
    deadline = time.monotonic() + 10
    while not broker.get_worker_queues(job) and time.monotonic() < deadline:
        time.sleep(0.01)
    assert broker.get_worker_queues(job), "worker never registered"
    return worker, ctx, t


def _stream(q, prompt, max_tokens, timeout_s=30.0):
    fut = q.submit_many([{"prompt_ids": list(prompt),
                          "max_tokens": max_tokens}],
                        deadline=time.monotonic() + timeout_s)[0]
    return fut.result(timeout_s)


def _drain(stream, timeout_s=30.0):
    toks, reason = [], None
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            d = stream.next_delta(1.0)
        except TimeoutError:
            continue
        except StopIteration:
            break
        toks.extend(d.tokens)
        if d.finished:
            reason = d.reason
            break
    return toks, reason


def _spy_paged_calls(model):
    """Wrap the model's paged prefill and decode so that each call is
    noted, in the worker's own order: ("prefill", start) | ("decode", the
    width of its tables in blocks, the furthest position it writes at)."""
    events = []
    op, od = model.paged_prefill, model.paged_decode_step

    def spy_p(cache, bt, ids, start):
        events.append(("prefill", int(start)))
        return op(cache, bt, ids, start)

    def spy_d(cache, ids, pos, bts):
        events.append(("decode", int(np.shape(bts)[1]), int(np.max(pos))))
        return od(cache, ids, pos, bts)

    model.paged_prefill, model.paged_decode_step = spy_p, spy_d
    return events


def test_worker_paged_matches_ring_e2e(monkeypatch):
    """The scheduler-level half of the invariant: the same prompts served
    under the paged allocator (prefix sharing + COW + chunked prefill
    active) and under the legacy ring produce identical token streams."""
    from rafiki_tpu.cache.queue import InProcessBroker

    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "2")
    monkeypatch.setenv("RAFIKI_GEN_KV_BLOCK_TOKENS", "8")
    monkeypatch.setenv("RAFIKI_GEN_PREFILL_CHUNK", "8")
    shared = list(range(1, 21))
    prompts = [shared + [30], shared + [30], shared + [40], [7, 7, 7]]

    def serve(paged: bool, job: str):
        monkeypatch.setenv("RAFIKI_GEN_KV_PAGED", "1" if paged else "0")
        broker = InProcessBroker()
        worker, ctx, t = _start_worker(broker, _tiny_model(), job=job)
        q = list(broker.get_worker_queues(job).values())[0]
        try:
            out = []
            for p in prompts:
                toks, _ = _drain(_stream(q, p, 6))
                out.append(toks)
            return out, worker
        finally:
            ctx.stopping = True
            t.join(timeout=10)

    paged_out, worker = serve(True, "pj1")
    assert worker._alloc is not None, "paged path must have engaged"
    st = worker._alloc.stats()
    assert st["prefix_hits"] >= 2, st       # identical + diverging prompt
    assert st["cow_copies"] >= 1, st
    ring_out, worker2 = serve(False, "rj1")
    assert worker2._alloc is None
    assert paged_out == ring_out
    assert paged_out[0] == paged_out[1]     # identical prompts, same stream


# -- the decode round's table follows the longest live sequence ---------------

_LONG_CONTEXT = 512     # at blocks of 16: rungs of 8, 16 and 32 blocks


def _long_model(monkeypatch):
    """TinyGenLM over a context of three rungs (the fixture's own is one)."""
    from rafiki_tpu.models import lm
    from rafiki_tpu.sdk import GenerationSpec

    mod = _fixture("gen_model")
    monkeypatch.setattr(mod, "_MAX_CONTEXT", _LONG_CONTEXT)
    monkeypatch.setattr(mod, "_PREFILL_BUCKETS",
                        (8, 16, 32, 64, 128, 256, _LONG_CONTEXT))

    class LongGenLM(mod.TinyGenLM):
        generation_spec = GenerationSpec(eos_token_id=None,
                                         max_context=_LONG_CONTEXT)

        def __init__(self, **knobs):
            super().__init__(**knobs)
            self._cfg = lm.tiny(vocab=64, max_len=_LONG_CONTEXT, dim=16,
                                depth=1, heads=2)

    model = LongGenLM()
    model.train(None)
    return model


def _width_env(monkeypatch):
    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "2")
    monkeypatch.setenv("RAFIKI_GEN_KV_BLOCK_TOKENS", "16")
    monkeypatch.setenv("RAFIKI_GEN_PREFILL_CHUNK", "64")
    monkeypatch.setenv("RAFIKI_GEN_MAX_TOKENS", "64")


def _table_rounds():
    from rafiki_tpu.utils.metrics import REGISTRY

    metric = REGISTRY.get("rafiki_gen_decode_table_blocks")
    return {} if metric is None else {
        int(key[0]): child.value() for key, child in metric.children().items()}


@pytest.fixture(scope="module")
def crossing():
    """One scenario, served once under the paged layout (calls, counter and
    span attributes noted) and once under the ring: a stream of 120 + 20
    tokens crosses the 128-token rung beside a short one, and a third short
    one follows when both have left."""
    from rafiki_tpu.cache.queue import InProcessBroker
    from rafiki_tpu.utils import trace

    rng = np.random.default_rng(11)
    prompts = [[int(x) for x in rng.integers(1, 60, size=n)]
               for n in (120, 5, 9)]
    out = {}
    with pytest.MonkeyPatch.context() as mp:
        _width_env(mp)
        annotated = []
        real = trace.annotation

        def spy_annotation(name, **attrs):
            if name == "gen.decode.device":
                annotated.append(attrs)
            return real(name, **attrs)

        mp.setattr(trace, "annotation", spy_annotation)
        for paged in (True, False):
            mp.setenv("RAFIKI_GEN_KV_PAGED", "1" if paged else "0")
            model = _long_model(mp)
            events = _spy_paged_calls(model) if paged else []
            job = "crossjob" if paged else "crossring"
            before = _table_rounds()
            broker = InProcessBroker()
            worker, ctx, t = _start_worker(broker, model, job=job)
            q = list(broker.get_worker_queues(job).values())[0]
            try:
                assert ctx.is_ready.wait(60)
                warmed = len(events)
                del annotated[:]
                futs = q.submit_many(
                    [{"prompt_ids": p, "max_tokens": n}
                     for p, n in zip(prompts[:2], (20, 12))],
                    deadline=time.monotonic() + 30)
                tokens = [_drain(f.result(30))[0] for f in futs]
                tokens.append(_drain(_stream(q, prompts[2], 6))[0])
            finally:
                ctx.stopping = True
                t.join(timeout=10)
            if paged:
                after = _table_rounds()
                out.update(
                    paged=tokens, alloc=worker._alloc,
                    rounds=[e for e in events[warmed:] if e[0] == "decode"],
                    counted={w: n - before.get(w, 0)
                             for w, n in after.items()
                             if n > before.get(w, 0)},
                    annotated=[a.get("table_blocks") for a in annotated])
            else:
                out["ring"] = tokens
    return out


def test_decode_table_width_follows_the_longest_live_sequence(crossing):
    """(a) Every round's tables are the narrowest rung that holds the
    furthest position the round writes at: up a rung as the long stream
    crosses 128 tokens, down again when it has left."""
    alloc, rounds = crossing["alloc"], crossing["rounds"]
    assert alloc.table_widths == (8, 16, 32)
    for _, width, furthest in rounds:
        assert width == alloc.table_width(furthest + 1), (width, furthest)
    widths = [w for _, w, _ in rounds]
    runs = [w for i, w in enumerate(widths) if i == 0 or widths[i - 1] != w]
    assert runs == [8, 16, 8], widths
    # 120 tokens prefilled, the first answer token with them: positions
    # 120..127 fit the first rung, the last 11 rounds do not
    assert widths.count(16) == 11


def test_streams_equal_the_rings_across_the_crossing(crossing):
    """(b) The tokens of every stream, the one that changed program
    mid-answer among them, equal the contiguous ring's."""
    assert [len(t) for t in crossing["paged"]] == [20, 12, 6]
    assert crossing["paged"] == crossing["ring"]


@pytest.mark.parametrize("deploy_timeout_s", [60.0, 0.0])
def test_every_rung_is_warm_before_the_first_request(monkeypatch,
                                                     deploy_timeout_s):
    """(c) Between deploy and `ready` the worker runs one all-idle round a
    rung, narrowest first, and the pool comes out as it went in; what the
    deploy's wait has no time for (here: none of it) runs after `ready`,
    still before the first request is admitted. Serving every rung
    afterwards compiles nothing more."""
    from rafiki_tpu import config
    from rafiki_tpu.cache.queue import InProcessBroker

    monkeypatch.setattr(config, "SERVICE_DEPLOY_TIMEOUT_S", deploy_timeout_s)
    _width_env(monkeypatch)
    monkeypatch.setenv("RAFIKI_GEN_KV_PAGED", "1")
    model = _long_model(monkeypatch)
    events = _spy_paged_calls(model)
    broker = InProcessBroker()
    worker, ctx, t = _start_worker(broker, model, job="warmjob")
    q = list(broker.get_worker_queues("warmjob").values())[0]
    try:
        assert ctx.is_ready.wait(60)
        idle_rounds = [("decode", w, 0) for w in (8, 16, 32)]
        if deploy_timeout_s:
            assert events == idle_rounds
        rng = np.random.default_rng(5)
        long_prompt = [int(x) for x in rng.integers(1, 60, size=250)]
        toks, _ = _drain(_stream(q, [3, 1, 4], 4))
        assert len(toks) == 4
        assert events[:4] == idle_rounds + [("prefill", 0)]
        programs = model._jit_paged_decode._cache_size()
        assert programs == 3
        toks, _ = _drain(_stream(q, long_prompt[:125], 8))
        assert len(toks) == 8
        toks, _ = _drain(_stream(q, long_prompt, 12))
        assert len(toks) == 12
        assert {e[1] for e in events[3:] if e[0] == "decode"} == {8, 16, 32}
        assert model._jit_paged_decode._cache_size() == programs
    finally:
        ctx.stopping = True
        t.join(timeout=10)
    # an idle round writes nothing: a pool of noise comes back bit for bit
    import jax

    from rafiki_tpu.models import lm

    pool = {name: jax.random.normal(jax.random.key(i), a.shape)
            for i, (name, a) in enumerate(
                lm.init_paged_kv_cache(model._cfg, 6, 16).items())}
    out = worker._idle_round(model, pool, 2, 8)
    assert all(np.array_equal(np.asarray(out[n]), np.asarray(pool[n]))
               for n in pool)


def test_counter_and_span_attribute_read_the_widths(crossing):
    """(d) `rafiki_gen_decode_table_blocks{blocks}` counts the rounds at
    each width and the `gen.decode.device` span carries it: both read what
    the model was handed."""
    from rafiki_tpu.utils.metrics import REGISTRY

    widths = [w for _, w, _ in crossing["rounds"]]
    assert crossing["annotated"] == widths
    assert crossing["counted"] == {8: widths.count(8), 16: widths.count(16)}
    exposition = REGISTRY.render()      # what the door's /metrics serves
    for rung in ("8", "16"):
        assert f'rafiki_gen_decode_table_blocks{{blocks="{rung}"}}' \
            in exposition


def test_worker_shared_prefix_pays_prefill_once(monkeypatch):
    """N streams sharing a system prompt: after the first, admissions hit
    the chain cache — the model's paged_prefill only ever forwards the
    unshared suffix (call lengths prove the prefill was paid once)."""
    from rafiki_tpu.cache.queue import InProcessBroker

    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "4")
    monkeypatch.setenv("RAFIKI_GEN_KV_BLOCK_TOKENS", "8")
    monkeypatch.setenv("RAFIKI_GEN_KV_PAGED", "1")
    monkeypatch.setenv("RAFIKI_GEN_PREFILL_CHUNK", "0")
    model = _tiny_model()
    calls = []
    orig = model.paged_prefill

    def spy(cache, block_table, prompt_ids, start):
        calls.append((int(start), len(prompt_ids)))
        return orig(cache, block_table, prompt_ids, start)

    model.paged_prefill = spy
    broker = InProcessBroker()
    worker, ctx, t = _start_worker(broker, model, job="sharejob")
    q = list(broker.get_worker_queues("sharejob").values())[0]
    try:
        system = list(range(1, 25))          # 24 tokens = 3 full blocks
        streams = [_stream(q, system + [30 + i], 4) for i in range(4)]
        outs = [_drain(s) for s in streams]
        assert all(len(toks) == 4 for toks, _ in outs)
        first = calls[0]
        assert first == (0, 25)              # full prefill, once
        # every later admission forwarded only the tail past the cache
        assert all(c[0] >= 16 and c[1] <= 9 for c in calls[1:]), calls
        assert worker._alloc.hits == 3 and worker._alloc.misses == 1
    finally:
        ctx.stopping = True
        t.join(timeout=10)


@pytest.mark.chaos
def test_pool_exhaustion_preempts_youngest_typed(monkeypatch):
    """The pool-exhaustion drill: a flood of long streams through a pool
    sized for ~1.5 of them. The youngest is preempted (typed counter,
    blocks freed, request re-queued) while older siblings advance; every
    stream still completes with the exact greedy continuation, and after
    the flood the refcounts drain back to zero."""
    from rafiki_tpu.cache.queue import InProcessBroker
    from rafiki_tpu.utils.metrics import REGISTRY

    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "3")
    monkeypatch.setenv("RAFIKI_GEN_KV_BLOCK_TOKENS", "8")
    monkeypatch.setenv("RAFIKI_GEN_KV_POOL_BLOCKS", "6")  # 48 tokens total
    monkeypatch.setenv("RAFIKI_GEN_KV_PAGED", "1")
    monkeypatch.setenv("RAFIKI_GEN_PREFIX_CACHE", "0")  # pure pool drill
    monkeypatch.setenv("RAFIKI_GEN_PREFILL_CHUNK", "8")
    broker = InProcessBroker()
    worker, ctx, t = _start_worker(broker, _tiny_model(), job="floodjob")
    q = list(broker.get_worker_queues("floodjob").values())[0]
    try:
        preempts0 = REGISTRY.get(
            "rafiki_gen_preemptions_total").value()
        # each stream wants 16 prompt + 16 decode = 32 tokens = 4 blocks;
        # three concurrent want 12 blocks against a 6-block pool
        prompts = [[10 + i] * 16 for i in range(3)]
        streams = [_stream(q, p, 16) for p in prompts]
        outs = [_drain(s, timeout_s=60) for s in streams]
        for i, (toks, reason) in enumerate(outs):
            assert len(toks) == 16, f"stream {i}: {reason} {toks}"
        preempts = REGISTRY.get(
            "rafiki_gen_preemptions_total").value() - preempts0
        assert preempts >= 1, "pool pressure must have preempted someone"
        # continuation is exact: a fresh uncontended run of the same
        # prompt yields the same tokens the preempted stream streamed
        solo, _ = _drain(_stream(q, prompts[2], 16), timeout_s=60)
        assert solo == outs[2][0]
        deadline = time.monotonic() + 10
        while worker._alloc.used_blocks() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert worker._alloc.used_blocks() == 0
        assert all(r == 0 for r in worker._alloc.refcounts())
    finally:
        ctx.stopping = True
        t.join(timeout=10)


def test_chunked_prefill_interleaves_with_decode(monkeypatch):
    """A max-context prompt joining must NOT stall resident streams: its
    prefill is ingested chunk-by-chunk with decode rounds in between, so
    the resident stream keeps emitting while the join is mid-prefill."""
    from rafiki_tpu.cache.queue import InProcessBroker

    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "2")
    monkeypatch.setenv("RAFIKI_GEN_KV_BLOCK_TOKENS", "8")
    monkeypatch.setenv("RAFIKI_GEN_KV_PAGED", "1")
    monkeypatch.setenv("RAFIKI_GEN_PREFIX_CACHE", "0")
    monkeypatch.setenv("RAFIKI_GEN_PREFILL_CHUNK", "8")
    model = _tiny_model()
    events = _spy_paged_calls(model)
    broker = InProcessBroker()
    worker, ctx, t = _start_worker(broker, model, job="joinjob")
    q = list(broker.get_worker_queues("joinjob").values())[0]
    try:
        resident = _stream(q, [5, 6, 7], 48)      # long-running resident
        # wait until the resident is decoding
        resident.next_delta(10)
        long_prompt = list(range(1, 57))          # 56 tokens = 7 chunks
        join = _stream(q, long_prompt, 4)
        toks_j, _ = _drain(join)
        assert len(toks_j) == 4
        resident.cancel()
        # the join's prefill chunks must have decode rounds between them
        starts = [i for i, e in enumerate(events) if e[0] == "prefill"
                  and e[1] > 0]
        assert len(starts) >= 3, "long prompt must have chunked"
        interleaved = sum(
            1 for a, b in zip(starts, starts[1:])
            if any(events[i][0] == "decode" for i in range(a + 1, b)))
        assert interleaved >= len(starts) - 2, (
            f"chunks must interleave with decode rounds: {events}")
    finally:
        ctx.stopping = True
        t.join(timeout=10)


def test_worker_stats_row_carries_block_picture(monkeypatch):
    from rafiki_tpu.cache.queue import InProcessBroker
    from rafiki_tpu.worker.inference import serving_stats

    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "2")
    monkeypatch.setenv("RAFIKI_GEN_KV_BLOCK_TOKENS", "8")
    monkeypatch.setenv("RAFIKI_GEN_KV_PAGED", "1")
    broker = InProcessBroker()
    worker, ctx, t = _start_worker(broker, _tiny_model(), job="statsjob")
    q = list(broker.get_worker_queues("statsjob").values())[0]
    try:
        toks, _ = _drain(_stream(q, [3, 1, 4], 3))
        assert len(toks) == 3
        row = serving_stats()[ctx.service_id]
        assert row["gen_kv_pool_blocks"] == worker._alloc.pool_blocks
        assert row["gen_kv_block_tokens"] == 8
        assert "gen_prefix_hits" in row and "gen_kv_blocks_used" in row
        assert row["gen_job"] == "statsjob"
    finally:
        ctx.stopping = True
        t.join(timeout=10)


def _timed_stream(q, prompt, max_tokens, gaps, on_first=None):
    """Drain one stream, appending the seconds between its token deltas
    to `gaps`; `on_first` runs when the first delta is in (the stream is
    decoding from then on)."""
    stream = _stream(q, prompt, max_tokens)
    last = time.monotonic()
    while True:
        try:
            d = stream.next_delta(30)
        except StopIteration:
            break
        now = time.monotonic()
        if d.tokens:
            gaps.append(now - last)
            if on_first is not None and len(gaps) == 1:
                on_first()
                now = time.monotonic()
        last = now
        if d.finished:
            break


def _p95_ms(gaps):
    xs = sorted(gaps)
    return xs[min(int(len(xs) * 0.95), len(xs) - 1)] * 1000.0


def test_long_prompt_join_intertoken_p95_within_budget(monkeypatch):
    """THE chunked-prefill acceptance drill: a max-context prompt joining
    mid-decode leaves the resident stream's inter-token p95 within the
    no-join budget (3x baseline + timer-noise floor) because the join is
    ingested chunk-by-chunk between decode rounds."""
    from rafiki_tpu.cache.queue import InProcessBroker

    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "2")
    monkeypatch.setenv("RAFIKI_GEN_KV_BLOCK_TOKENS", "16")
    monkeypatch.setenv("RAFIKI_GEN_PREFILL_CHUNK", "32")
    monkeypatch.setenv("RAFIKI_GEN_KV_PAGED", "1")
    model = _tiny_model()
    context = model.generation_spec.max_context
    events = _spy_paged_calls(model)
    broker = InProcessBroker()
    _, ctx, t = _start_worker(broker, model, job="drilljob")
    q = list(broker.get_worker_queues("drilljob").values())[0]
    rng = np.random.default_rng(3)

    def long_prompt():
        # a fresh one each time: a repeat would be served by the prefix
        # cache and prefill nothing
        return [int(x) for x in rng.integers(1, 60, size=context - 10)]

    # short enough that the join's two chunk rounds are over a twentieth
    # of the resident's gaps, or its p95 could not see them
    resident_prompt, resident_tokens = [5, 6, 7, 8], 32
    try:
        # warm-up: compile decode and every prefill bucket a chunk takes
        _drain(_stream(q, [3, 1, 4], 8))
        _drain(_stream(q, long_prompt(), 2))
        # baseline: one resident stream, no join
        base_gaps = []
        _timed_stream(q, resident_prompt, resident_tokens, base_gaps)

        def drill():
            """The resident decodes while a max-context prompt joins: its
            gaps, and whether the join's last chunk went in while the
            resident still had a round to run (by the worker's own order
            of calls, not by a clock)."""
            del events[:]
            gaps, joins = [], []
            prompt = long_prompt()

            def join():
                fut = q.submit_many(
                    [{"prompt_ids": prompt, "max_tokens": 4}],
                    deadline=time.monotonic() + 30)[0]
                jt = threading.Thread(
                    target=lambda: _drain(fut.result(30)), daemon=True)
                jt.start()
                joins.append(jt)

            _timed_stream(q, resident_prompt, resident_tokens, gaps,
                          on_first=join)
            joins[0].join(timeout=60)
            assert not joins[0].is_alive()
            assert len(gaps) == resident_tokens
            chunks = [i for i, e in enumerate(events)
                      if e[0] == "prefill" and e[1] > 0]
            assert chunks, "the max-context prompt must have chunked"
            # the resident's rounds are the first resident_tokens - 1
            rounds_before = sum(1 for e in events[:chunks[-1]]
                                if e[0] == "decode")
            return gaps, rounds_before < resident_tokens - 1

        # the tiny model's resident lives some 15 ms: a starved test
        # thread can miss it, and a drill in which the two never met
        # shows nothing
        for _ in range(3):
            join_gaps, met = drill()
            if met:
                break
        assert met, "the join never met the resident"
        # drop the first gap (it includes the resident's own prefill)
        base_p95, join_p95 = _p95_ms(base_gaps[1:]), _p95_ms(join_gaps[1:])
        # the join may cost residents at most 3x the no-join p95 (plus a
        # 20 ms absolute floor for timer noise): a one-shot prefill of a
        # max-context prompt blows through this
        budget_ms = max(base_p95 * 3.0, base_p95 + 20.0)
        drill_within_budget = join_p95 <= budget_ms
        assert drill_within_budget, (base_p95, join_p95, budget_ms)
    finally:
        ctx.stopping = True
        t.join(timeout=10)


# -- door admission cost + fleet health ---------------------------------------

def test_generate_admission_cost_in_block_units(monkeypatch):
    from rafiki_tpu.predictor.server import _generate_cost

    monkeypatch.setenv("RAFIKI_GEN_KV_PAGED", "1")
    monkeypatch.setenv("RAFIKI_GEN_KV_BLOCK_TOKENS", "16")
    # a long prompt charges even with a tiny decode budget
    assert _generate_cost(120, 8) == 8       # ceil(128/16)
    assert _generate_cost(0, 1) == 1
    monkeypatch.setenv("RAFIKI_GEN_KV_PAGED", "0")
    assert _generate_cost(120, 8) == 8       # ring: the decode budget
    assert _generate_cost(120, 256) == 256


def test_fleet_health_aggregates_generation_per_job():
    from rafiki_tpu.admin.admin import Admin
    from rafiki_tpu.db.database import Database
    from rafiki_tpu.placement.manager import (
        ChipAllocator,
        LocalPlacementManager,
    )

    admin = Admin(db=Database(":memory:"),
                  placement=LocalPlacementManager(
                      allocator=ChipAllocator([0])))
    try:
        admin.db.get_inference_job_worker = (
            lambda sid: {"service_id": sid, "inference_job_id": "jobG",
                         "trial_id": "t"})
        for sid, hits in (("svcA", 3), ("svcB", 5)):
            admin.handle_event("inference_worker_stats", {
                "service_id": sid, "batches": 1, "queries": 4,
                "gen_slots_busy": 1, "gen_slots_max": 2,
                "gen_tokens": 10, "gen_job": "jobG",
                "gen_kv_blocks_used": 6, "gen_kv_pool_blocks": 40,
                "gen_prefix_hits": hits, "gen_prefix_misses": 1,
                "gen_prefix_hit_tokens": hits * 16})
        gen = admin.get_fleet_health()["serving"]["generation"]
        assert gen["jobG"]["workers"] == 2
        assert gen["jobG"]["prefix_hits"] == 8
        assert gen["jobG"]["kv_pool_blocks"] == 80
        assert gen["jobG"]["prefix_hit_rate"] == 0.8
        # block occupancy (not slot occupancy) fed the autoscaler ring
        from rafiki_tpu.utils.metrics import REGISTRY

        series = REGISTRY.ring("slot_occupancy:job:jobG").series()
        assert series and abs(series[-1][1] - 6 / 40) < 1e-9
    finally:
        admin.shutdown()


# -- doctor -------------------------------------------------------------------

def test_doctor_paged_layout_warns(monkeypatch):
    from rafiki_tpu.doctor import check_generative_serving

    monkeypatch.setenv("RAFIKI_DB_PATH", "/nonexistent/nowhere.sqlite3")
    name, status, _ = check_generative_serving()
    assert name == "generative serving" and status == "PASS"
    # degenerate block size, both edges
    monkeypatch.setenv("RAFIKI_GEN_KV_BLOCK_TOKENS", "2")
    _, status, detail = check_generative_serving()
    assert status == "WARN" and "degenerate" in detail
    monkeypatch.setenv("RAFIKI_GEN_KV_BLOCK_TOKENS", "9999")
    _, status, detail = check_generative_serving()
    assert status == "WARN" and "degenerate" in detail
    monkeypatch.delenv("RAFIKI_GEN_KV_BLOCK_TOKENS")
    # pool capacity past the chip-memory heuristic
    monkeypatch.setenv("RAFIKI_GEN_KV_POOL_BLOCKS", "100000")
    _, status, detail = check_generative_serving()
    assert status == "WARN" and "memory heuristic" in detail
    monkeypatch.delenv("RAFIKI_GEN_KV_POOL_BLOCKS")


def test_doctor_warns_disabled_prefix_cache_under_shareable_traffic(
        monkeypatch):
    from rafiki_tpu.doctor import check_generative_serving
    from rafiki_tpu.utils.metrics import REGISTRY

    monkeypatch.setenv("RAFIKI_DB_PATH", "/nonexistent/nowhere.sqlite3")
    # cache ENABLED: shareable traffic is never a warning by itself
    _, status, _ = check_generative_serving()
    assert status == "PASS"
    monkeypatch.setenv("RAFIKI_GEN_PREFIX_CACHE", "0")
    REGISTRY.counter("rafiki_gen_prefix_shareable_total").inc(5)
    _, status, detail = check_generative_serving()
    assert status == "WARN" and "RAFIKI_GEN_PREFIX_CACHE" in detail


# -- PR 24: what the slots hold, and the serve loop's spans -------------------

def test_live_blocks_leave_out_what_only_the_cache_keeps():
    """`used_blocks` is pool less free and fills with blocks that only the
    prefix cache keeps; `live_blocks` is what the slots' tables hold."""
    a = PagedKVAllocator(pool_blocks=8, block_tokens=4, table_blocks=4)
    prompt = list(range(5))
    a.open_slot("A", prompt)
    assert a.ensure_capacity("A", 4)
    a.publish("A", prompt)
    st = a.stats()
    assert st["used_blocks"] == st["live_blocks"] == 2  # A holds both
    a.close_slot("A")
    st = a.stats()
    assert st["used_blocks"] == 2 and st["evictable_blocks"] == 2
    assert st["live_blocks"] == 0


def _phase_counts():
    from rafiki_tpu.utils import trace

    return {key[0]: child.snapshot()["count"]
            for key, child in trace.phase_histogram().children().items()}


def test_serve_loop_phases_are_counted(monkeypatch):
    """Streams served through GenerationWorker leave a count for each of
    the serve loop's six phases; a decode round is one build, one device
    call and one post, so the three counts rise together; the row and the
    gauge carry the blocks the slots hold."""
    from rafiki_tpu.cache.queue import InProcessBroker
    from rafiki_tpu.utils.metrics import REGISTRY
    from rafiki_tpu.worker.inference import serving_stats

    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "2")
    monkeypatch.setenv("RAFIKI_GEN_KV_BLOCK_TOKENS", "8")
    monkeypatch.setenv("RAFIKI_GEN_PREFILL_CHUNK", "8")
    monkeypatch.setenv("RAFIKI_GEN_KV_PAGED", "1")
    before = _phase_counts()
    broker = InProcessBroker()
    worker, ctx, t = _start_worker(broker, _tiny_model(), job="spanjob")
    q = list(broker.get_worker_queues("spanjob").values())[0]
    try:
        long_prompt = list(range(1, 21))  # three chunks of 8
        streams = [_stream(q, long_prompt, 6), _stream(q, [7, 7, 7], 4)]
        assert [len(_drain(s)[0]) for s in streams] == [6, 4]
        row = serving_stats()[ctx.service_id]
        assert row["gen_kv_blocks_live"] <= row["gen_kv_blocks_used"]
        live = REGISTRY.get("rafiki_gen_kv_blocks_live")
        assert live.value(ctx.service_id) == row["gen_kv_blocks_live"]
    finally:
        ctx.stopping = True
        t.join(timeout=10)
    rose = {k: v - before.get(k, 0) for k, v in _phase_counts().items()}
    for phase in ("gen.admit", "gen.prefill_chunk", "gen.bookkeep",
                  "gen.decode.build", "gen.decode.device",
                  "gen.decode.post"):
        assert rose.get(phase, 0) > 0, (phase, rose)
    assert rose["gen.decode.build"] == rose["gen.decode.device"] \
        == rose["gen.decode.post"] >= 5  # six tokens: a prefill's and five
    assert rose["gen.prefill_chunk"] == 4  # three chunks and one
    assert rose["gen.admit"] >= rose["gen.bookkeep"] > 0
