"""Paged KV allocator + shared prefix cache — host-side block bookkeeping.

The generation worker's decode memory used to be one contiguous K/V ring
per slot: HBM cost ``slots x max_context`` whatever the actual sequence
lengths, which caps co-resident streams at the worst case. This module
implements the block-granular alternative (PagedAttention, Kwon et al.
2023): a fixed pool of ``block_tokens``-sized pages plus a per-slot block
table, so a stream only holds pages for tokens it has actually written —
slot count is bound by *used* tokens.

On top of the pool sits a **shared prefix cache** (RadixAttention-style
prefix reuse): after a prompt's prefill, its full blocks are published
under a content hash of the token prefix they hold, refcounted, and mapped
read-only into later streams that share the prefix — N streams with one
system prompt pay its prefill once. The partial tail block is published
too; any write into a shared block goes through **copy-on-write**
(``ensure_writable``), so two streams diverging after a shared prefix can
never corrupt each other's tails.

Division of labour: this class is pure host-side bookkeeping — block ids,
refcounts, tables, hashes, and *copy instructions*. The model owns the
device arrays (models/lm.py ``paged_prefill``/``paged_decode_step``/
``copy_kv_blocks``); the worker (worker/generation.py) is the only caller
and drives both from its single serve thread, so no locking is needed
here. Pool exhaustion is the caller's signal to preempt the youngest
stream (blocks freed, request re-queued) rather than crash a round.

Correctness contract for partial tail reuse: a matched tail block may
carry rows beyond the matched length that belong to the *publisher's*
prompt. Those rows sit at logical positions the new stream's own suffix
prefill (or decode) writes BEFORE attention can read them — the same
write-then-attend ordering the ring path already relies on for bucket
padding — so stale rows are never attended.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np


#: The narrowest table a decode round is handed, in tokens: the gathered
#: view's token dimension then fills the 128 lanes of a TPU tile, and a
#: narrower view would be padded back up to them.
MIN_TABLE_TOKENS = 128


def table_ladder(block_tokens: int, table_blocks: int) -> Tuple[int, ...]:
    """The widths, in blocks, a decode round's table is cut to: the blocks
    of ``MIN_TABLE_TOKENS``, doubling, up to and always including
    ``table_blocks``. One compiled decode program a rung."""
    rungs = []
    width = -(-MIN_TABLE_TOKENS // block_tokens)
    while width < table_blocks:
        rungs.append(width)
        width *= 2
    return tuple(rungs) + (table_blocks,)


class KVPoolExhaustedError(RuntimeError):
    """The pool cannot hold even one stream's working set — a typed
    stream-level error (the caller fails THAT stream; siblings and the
    worker keep serving)."""


def _digest(tokens: Sequence[int]) -> str:
    return hashlib.sha1(
        np.asarray(list(tokens), np.int32).tobytes()).hexdigest()


def _chain_digests(tokens: Sequence[int], block_tokens: int):
    """``_digest(tokens[:block_tokens])``, ``_digest(tokens[:2 *
    block_tokens])``, ... for every full block, in one pass over the tokens:
    the hash of a prefix is the running hash of its blocks. A prompt of
    7,590 tokens has 474 of them; hashed one by one from the start they
    cost 70 ms of the serve thread a request, in one pass 1 ms."""
    arr = np.asarray(list(tokens), np.int32)
    running = hashlib.sha1()
    for end in range(block_tokens, len(arr) + 1, block_tokens):
        running.update(arr[end - block_tokens:end].tobytes())
        yield running.copy().hexdigest()


def _common_prefix_len(a: Sequence[int], b: Sequence[int]) -> int:
    n = min(len(a), len(b))
    i = 0
    while i < n and a[i] == b[i]:
        i += 1
    return i


class AdmitPlan:
    """What :meth:`PagedKVAllocator.open_slot` resolved for a prompt:
    ``cached_tokens`` logical positions 0..cached_tokens-1 are already in
    the pool (shared chain blocks + a copied tail), and ``copies`` are
    (src, dst) block pairs the caller must apply to the device cache
    (``copy_kv_blocks``) before running any forward for this slot."""

    __slots__ = ("cached_tokens", "copies")

    def __init__(self, cached_tokens: int,
                 copies: List[Tuple[int, int]]) -> None:
        self.cached_tokens = cached_tokens
        self.copies = copies


class PagedKVAllocator:
    """Block pool + per-slot tables + refcounted prefix cache.

    ``pool_blocks`` physical pages of ``block_tokens`` K/V rows each;
    ``table_blocks`` is the widest per-slot table (ceil(max_context /
    block_tokens)): what prefill and verify are handed. A decode round is
    handed the narrowest of ``table_widths`` (:func:`table_ladder`) that
    covers its longest live sequence, so the jitted decode program has one
    shape a rung and reads no further than the round's sequences reach.
    The sentinel id ``pool_blocks`` marks unallocated table entries —
    the model layer drops writes through it.
    """

    def __init__(self, pool_blocks: int, block_tokens: int,
                 table_blocks: int, prefix_cache: bool = True,
                 max_tails_per_chain: int = 4) -> None:
        if pool_blocks < 1 or block_tokens < 1 or table_blocks < 1:
            raise ValueError(
                f"degenerate paged-KV geometry: pool_blocks={pool_blocks} "
                f"block_tokens={block_tokens} table_blocks={table_blocks}")
        self.pool_blocks = int(pool_blocks)
        self.block_tokens = int(block_tokens)
        self.table_blocks = int(table_blocks)
        self.table_widths = table_ladder(self.block_tokens,
                                         self.table_blocks)
        self.sentinel = self.pool_blocks
        self.prefix_cache = bool(prefix_cache)
        self.max_tails_per_chain = int(max_tails_per_chain)
        self._free: List[int] = list(range(self.pool_blocks - 1, -1, -1))
        self._refs = [0] * self.pool_blocks
        self._tables: Dict[Any, List[int]] = {}
        self._shared: Dict[Any, set] = {}
        #: LRU-ordered cache entries: chain entries keyed by the prefix
        #: digest, tail entries by ("tail", chain_digest, tokens_tuple)
        self._entries: "OrderedDict[Any, Dict[str, Any]]" = OrderedDict()
        self._tails: Dict[str, List[tuple]] = {}
        # counters (mirrored into the PR-6 registry by the worker)
        self.hits = 0
        self.misses = 0
        self.hit_tokens = 0
        self.cow_copies = 0
        self.cache_evictions = 0

    # -- pool primitives -----------------------------------------------------

    def free_blocks(self) -> int:
        return len(self._free)

    def used_blocks(self) -> int:
        return self.pool_blocks - len(self._free)

    def evictable_blocks(self) -> int:
        """Cache-only blocks (refcount 1, held by no slot) LRU eviction
        could reclaim right now."""
        return sum(1 for e in self._entries.values()
                   if self._refs[e["block"]] == 1)

    def blocks_for(self, tokens: int) -> int:
        return -(-max(int(tokens), 0) // self.block_tokens)

    def _alloc_one(self) -> Optional[int]:
        """One private block (refcount 1), evicting LRU cache-only
        entries if the free list is dry. None = genuinely exhausted."""
        if not self._free and not self._evict_lru():
            return None
        b = self._free.pop()
        self._refs[b] = 1
        return b

    def _evict_lru(self) -> bool:
        for key, e in self._entries.items():
            if self._refs[e["block"]] == 1:
                self._drop_entry(key)
                self.cache_evictions += 1
                return True
        return False

    def _drop_entry(self, key: Any) -> None:
        e = self._entries.pop(key)
        b = e["block"]
        self._refs[b] -= 1
        if self._refs[b] == 0:
            self._free.append(b)
        if e["kind"] == "tail":
            toks = self._tails.get(e["chain"], [])
            if e["tokens"] in toks:
                toks.remove(e["tokens"])
                if not toks:
                    self._tails.pop(e["chain"], None)

    # -- slot lifecycle ------------------------------------------------------

    def open_slot(self, slot: Any, prompt: Sequence[int]) -> AdmitPlan:
        """Map the longest cached prefix of ``prompt`` into a new slot's
        table (shared chain blocks refcounted; a matching partial tail is
        COPIED into a private block — the 'copy' of copy-on-write). At
        most ``len(prompt) - 1`` tokens come from cache: the last prompt
        token is always forwarded so prefill has logits to return."""
        if slot in self._tables:
            raise ValueError(f"slot {slot!r} already open")
        prompt = list(prompt)
        usable = len(prompt) - 1
        bt = self.block_tokens
        table: List[int] = []
        shared: set = set()
        copies: List[Tuple[int, int]] = []
        cached = 0
        if self.prefix_cache and usable > 0:
            c = 0
            chain_d = _digest(())
            for d in _chain_digests(prompt[:usable], bt):
                e = self._entries.get(d)
                if e is None or c >= self.table_blocks:
                    break
                table.append(e["block"])
                self._refs[e["block"]] += 1
                shared.add(c)
                self._entries.move_to_end(d)
                c, chain_d = c + 1, d
            cached = c * bt
            best_key = None
            best_t = 0
            for toks in self._tails.get(chain_d, ()):
                key = ("tail", chain_d, toks)
                e = self._entries.get(key)
                if e is None:
                    continue
                t = _common_prefix_len(toks, prompt[cached:usable])
                if t > best_t:
                    best_t, best_key = t, key
            if best_key is not None and len(table) < self.table_blocks:
                # pin the source entry across the allocation: _alloc_one
                # may LRU-evict refcount-1 cache entries, and the matched
                # tail (not yet touched this admission) is a prime victim
                # — unpinned, its freed block could even be handed back
                # as the copy TARGET
                src_block = self._entries[best_key]["block"]
                self._refs[src_block] += 1
                dst = self._alloc_one()
                self._refs[src_block] -= 1
                if dst is not None:
                    copies.append((src_block, dst))
                    table.append(dst)
                    cached += best_t
                    self._entries.move_to_end(best_key)
                    self.cow_copies += 1
        if cached > 0:
            self.hits += 1
            self.hit_tokens += cached
        else:
            self.misses += 1
        self._tables[slot] = table
        self._shared[slot] = shared
        return AdmitPlan(cached, copies)

    def close_slot(self, slot: Any) -> None:
        """Release every block the slot maps: private refcounts drop to
        zero and return to the free list; shared blocks stay alive under
        the cache's own reference."""
        table = self._tables.pop(slot, None)
        self._shared.pop(slot, None)
        if table is None:
            return
        for b in table:
            self._refs[b] -= 1
            if self._refs[b] == 0:
                self._free.append(b)

    def truncate_to(self, slot: Any, tokens: int) -> int:
        """Shed blocks wholly past logical row ``tokens - 1`` — the
        speculative-decode rollback: a verify round allocates capacity
        for all k drafted positions up front, and when fewer are accepted
        the blocks that only ever held rejected-suffix K/V go back to the
        pool (no device-side work: the model layer's write-then-attend
        ordering guarantees stale rows are overwritten before any query
        can attend them). Shared blocks are dereferenced exactly like
        :meth:`close_slot` — a published prefix can never sit past the
        committed frontier anyway. Returns blocks freed to the pool."""
        table = self._tables[slot]
        keep = self.blocks_for(tokens)
        freed = 0
        while len(table) > keep:
            b = table.pop()
            self._shared[slot].discard(len(table))
            self._refs[b] -= 1
            if self._refs[b] == 0:
                self._free.append(b)
                freed += 1
        return freed

    def ensure_capacity(self, slot: Any, position: int) -> bool:
        """Grow the slot's table until it covers logical ``position``
        (the next write). False = pool exhausted even after cache
        eviction — the caller preempts the youngest stream and retries."""
        if position >= self.table_blocks * self.block_tokens:
            raise KVPoolExhaustedError(
                f"position {position} is past the table "
                f"({self.table_blocks} x {self.block_tokens} tokens)")
        table = self._tables[slot]
        need = position // self.block_tokens + 1
        while len(table) < need:
            b = self._alloc_one()
            if b is None:
                return False
            table.append(b)
        return True

    def ensure_writable(self, slot: Any, position: int
                        ) -> Optional[List[Tuple[int, int]]]:
        """Copy-on-write barrier: if the block holding ``position`` is
        shared (a published tail another stream — or the cache — still
        references), move this slot onto a private copy first. Returns
        the (src, dst) copy list to apply (usually empty), or None when
        the pool cannot supply the copy target (caller preempts)."""
        ix = position // self.block_tokens
        table = self._tables[slot]
        if ix >= len(table) or ix not in self._shared[slot]:
            return []
        dst = self._alloc_one()
        if dst is None:
            return None
        src = table[ix]
        table[ix] = dst
        self._shared[slot].discard(ix)
        self._refs[src] -= 1
        if self._refs[src] == 0:  # defensive: shared implies a cache ref
            self._free.append(src)
        self.cow_copies += 1
        return [(src, dst)]

    def publish(self, slot: Any, prompt: Sequence[int]) -> None:
        """Offer a freshly-prefilled prompt to the prefix cache: every
        full block under its chain digest, the partial tail block (if
        any) under its chain + token tuple. Published blocks gain a cache
        reference and become copy-on-write for the OWNER too — its next
        decode write into the tail block goes through a private copy,
        leaving the cached content immutable."""
        if not self.prefix_cache:
            return
        prompt = list(prompt)
        bt = self.block_tokens
        table = self._tables[slot]
        shared = self._shared[slot]
        fb = len(prompt) // bt
        chain_d = _digest(())
        for i, d in enumerate(_chain_digests(prompt, bt)):
            chain_d = d  # of the prompt's full blocks, after the last
            if i >= len(table):
                continue
            if d in self._entries:
                self._entries.move_to_end(d)
                continue
            b = table[i]
            self._entries[d] = {"kind": "chain", "block": b}
            self._refs[b] += 1
            shared.add(i)
        r = len(prompt) - fb * bt
        if r > 0 and fb < len(table):
            toks = tuple(prompt[fb * bt:])
            key = ("tail", chain_d, toks)
            tails = self._tails.setdefault(chain_d, [])
            if key not in self._entries \
                    and len(tails) < self.max_tails_per_chain:
                b = table[fb]
                self._entries[key] = {"kind": "tail", "block": b,
                                      "chain": chain_d, "tokens": toks}
                tails.append(toks)
                self._refs[b] += 1
                shared.add(fb)

    # -- views ---------------------------------------------------------------

    def table_width(self, tokens: int) -> int:
        """The narrowest rung of ``table_widths`` whose view holds logical
        positions ``0 .. tokens - 1``. The model layer DROPS a write at or
        past its table's last position, silently, so a caller takes its
        width from the furthest position it is about to write, plus one;
        what no rung holds is refused here."""
        need = self.blocks_for(tokens)
        for width in self.table_widths:
            if need <= width:
                return width
        raise KVPoolExhaustedError(
            f"position {tokens - 1} is past the table "
            f"({self.table_blocks} x {self.block_tokens} tokens)")

    def table_row(self, slot: Any, width: Optional[int] = None) -> np.ndarray:
        """The slot's table row, sentinel-padded — what the jitted paged
        forwards consume: ``table_blocks`` wide, or cut to ``width`` (a
        rung of ``table_widths``; blocks past it hold no position the
        call's causal mask lets through)."""
        width = self.table_blocks if width is None else width
        row = np.full(width, self.sentinel, np.int32)
        t = self._tables[slot][:width]
        row[:len(t)] = t
        return row

    def idle_row(self, width: Optional[int] = None) -> np.ndarray:
        return np.full(self.table_blocks if width is None else width,
                       self.sentinel, np.int32)

    def refcounts(self) -> List[int]:
        return list(self._refs)

    def drop_cache(self) -> int:
        """Evict every cache-only entry (deploy/rollback flush and the
        refcount drill); returns blocks freed. Entries still mapped by a
        live slot stay until that slot closes."""
        freed = 0
        for key in [k for k, e in self._entries.items()
                    if self._refs[e["block"]] == 1]:
            self._drop_entry(key)
            freed += 1
            self.cache_evictions += 1
        return freed

    def stats(self) -> Dict[str, int]:
        used, evictable = self.used_blocks(), self.evictable_blocks()
        return {
            "pool_blocks": self.pool_blocks,
            "block_tokens": self.block_tokens,
            "used_blocks": used,
            # what the slots' tables hold: `used` also counts blocks that
            # only the prefix cache keeps, and fills with them
            "live_blocks": used - evictable,
            "free_blocks": self.free_blocks(),
            "cache_entries": len(self._entries),
            "evictable_blocks": evictable,
            "prefix_hits": self.hits,
            "prefix_misses": self.misses,
            "prefix_hit_tokens": self.hit_tokens,
            "cow_copies": self.cow_copies,
            "cache_evictions": self.cache_evictions,
        }
