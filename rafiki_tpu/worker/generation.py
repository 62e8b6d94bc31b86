"""Generation worker: token-streaming decode with continuous batching.

The classification worker (worker/inference.py) is one-request/one-answer:
take a batch, run ``predict``, resolve futures. Generative serving cannot
work that way — a 512-token completion would hold its whole batch hostage
for 512 steps. This worker applies the Orca insight (iteration-level
scheduling: admit/evict at TOKEN granularity, not request granularity) on
top of the platform's existing data plane:

- a **fixed-width slot table** (``RAFIKI_GEN_MAX_SLOTS``): the model's KV
  cache is preallocated for that many co-resident sequences, so one jitted
  ``decode_step`` program serves the table for its whole lifetime (under
  the paged layout below, one program for each width of block table: a
  short ladder, every rung run once before the first request is admitted);
- per decode round the scheduler **pulls newly queued requests** from the
  same bounded ``WorkerQueue`` every serving hop already uses (deadline /
  expiry / depth-cap semantics preserved), prefills them into free slots,
  runs ONE step for every active slot, and pushes each sequence's token
  delta onto its :class:`~rafiki_tpu.cache.queue.TokenStream`;
- sequences **leave mid-decode** — EOS, ``max_tokens``, context edge,
  deadline, client cancel, injected fault — freeing their slot to the next
  queued request without stalling co-resident sequences.

Decode memory comes in two layouts. Templates that implement only the
base generation contract get the **contiguous ring**: one
``max_context``-long K/V ring per slot, simple but worst-case-sized.
Templates that also implement the paged methods (sdk/model.py
``GENERATION_PAGED_METHODS``) serve under the **paged KV allocator**
(worker/kv_paging.py, ``RAFIKI_GEN_KV_PAGED``): a fixed pool of
``RAFIKI_GEN_KV_BLOCK_TOKENS``-sized pages plus per-slot block tables, so
resident streams are bound by *used* tokens rather than
``slots x max_context``. A decode round's block tables are as wide as its
longest live sequence needs, not as the context (the narrowest rung of
``kv_paging.table_ladder``), so the round gathers and masks no further than
its sequences reach. The paged path adds three levers the ring cannot
offer:

- **shared prefix cache** (``RAFIKI_GEN_PREFIX_CACHE``): prompt-prefix
  blocks are content-hashed, refcounted, and mapped read-only into later
  streams — N streams sharing a system prompt pay prefill once, with
  copy-on-write protecting the partial tail block when streams diverge;
- **chunked prefill** (``RAFIKI_GEN_PREFILL_CHUNK``): a long-prompt join
  is ingested a chunk per scheduler round, interleaved with decode
  rounds, so resident streams' inter-token latency never stalls behind
  one giant prompt;
- **preempt-don't-crash**: pool exhaustion preempts the youngest stream
  (blocks freed, the stream transparently re-queued and later resumed
  from a fresh prefill of its tokens-so-far — greedy decode makes the
  continuation exact) instead of failing a round.

On top of the paged plane, **speculative decoding** (``RAFIKI_GEN_SPEC``;
a draft trial budgeted as ``GEN_DRAFT_TRIAL``) multiplies tokens per
round: a small draft LM proposes ``RAFIKI_GEN_SPEC_K`` tokens per
scheduler round and the target verifies all k+1 positions in ONE
fixed-shape ``paged_verify_step`` forward — per-slot accept lengths are
data, not shape, so mixed acceptance across resident streams never
retraces. **Real sampling** (temperature / top-k / top-p,
``RAFIKI_GEN_SAMPLING``) rides the same plane under a counter-based RNG
key — every draw is keyed by (stream seed, absolute token position, draw
role) — which keeps sampled streams exactly resumable through the
preemption path above and makes the speculative accept test
well-defined; temperature=0 reproduces the greedy path bit-identically.
A draft fault (crash, stall, vocab mismatch) degrades the worker to
plain decode TYPED: resident streams keep their tokens/s floor and
``gen_spec_degraded`` in the stats row names the reason.

Observability: time-to-first-token and inter-token-latency histograms,
a slot-occupancy gauge + per-job ring (the autoscaler's generative
backlog signal — BLOCK-pool occupancy under the paged layout, busy
slots under the ring), prefix hit/miss/evict + COW + preemption
counters, eviction counters by reason, and the shared SERVING_STATS row
every stats surface already reads.
"""

from __future__ import annotations

import hashlib
import logging
import threading
import time
import traceback
import uuid
from collections import OrderedDict
from typing import Dict, List, Optional

import numpy as np

from rafiki_tpu import config
from rafiki_tpu.cache.queue import TokenStream
from rafiki_tpu.constants import BudgetType
from rafiki_tpu.sdk.model import (
    GenerationSpec,
    ROLE_DRAFT,
    ROLE_TARGET,
    draft_capability,
    generation_capability,
    paged_generation_capability,
    sampling_capability,
    spec_verify_capability,
)
from rafiki_tpu.utils import chaos, trace
from rafiki_tpu.worker.inference import (
    InferenceWorker,
    SERVING_STATS,
    _record_queue,
    _stats_lock,
)
from rafiki_tpu.worker.kv_paging import PagedKVAllocator

logger = logging.getLogger(__name__)


class GenerationUnsupportedError(RuntimeError):
    """The deployed template does not advertise a fully-wired generation
    capability — a typed deploy-time error (the serving analogue of
    InvalidModelClassError), never a mid-stream AttributeError."""


class GenerationRequestError(ValueError):
    """A malformed generation request (bad prompt/max_tokens shape) —
    resolved onto the request's future so the door answers 400."""


def _metrics():
    """Lazily-created registry handles for the generation plane (same
    pattern as worker/inference.py — import stays cheap, increments all
    happen at one site per signal)."""
    global _M
    if _M is None:
        from rafiki_tpu.utils.metrics import REGISTRY

        _M = {
            "ttft": REGISTRY.histogram(
                "rafiki_gen_ttft_seconds",
                "admission-to-first-token latency of generation "
                "requests: the clock starts when a slot ADMITS the request "
                "(worker side), not when it arrived; the wait before "
                "admission is inside rafiki_gen_door_ttft_seconds"),
            "intertoken": REGISTRY.histogram(
                "rafiki_gen_intertoken_seconds",
                "latency between consecutive decode rounds of a live "
                "slot table"),
            "tokens": REGISTRY.counter(
                "rafiki_gen_tokens_total",
                "tokens emitted by generation workers in this process"),
            "slots": REGISTRY.gauge(
                "rafiki_gen_slots_busy",
                "generation slots currently decoding", ("service",)),
            "evictions": REGISTRY.counter(
                "rafiki_gen_evictions_total",
                "sequences leaving the slot table, by finish reason",
                ("reason",)),
            "kv_used": REGISTRY.gauge(
                "rafiki_gen_kv_blocks_used",
                "paged-KV pool blocks currently allocated (pool less free: "
                "counts blocks that only the prefix cache keeps)",
                ("service",)),
            "kv_live": REGISTRY.gauge(
                "rafiki_gen_kv_blocks_live",
                "paged-KV pool blocks held by a slot's block table (used "
                "less the blocks only the prefix cache keeps)",
                ("service",)),
            "kv_pool": REGISTRY.gauge(
                "rafiki_gen_kv_pool_blocks",
                "paged-KV pool size in blocks", ("service",)),
            "prefix_hits": REGISTRY.counter(
                "rafiki_gen_prefix_hits_total",
                "admissions that reused cached prompt-prefix blocks"),
            "prefix_misses": REGISTRY.counter(
                "rafiki_gen_prefix_misses_total",
                "admissions that found no cached prefix"),
            "prefix_tokens": REGISTRY.counter(
                "rafiki_gen_prefix_tokens_total",
                "prompt tokens served from the prefix cache instead of "
                "prefill compute"),
            "prefix_evictions": REGISTRY.counter(
                "rafiki_gen_prefix_evictions_total",
                "prefix-cache entries evicted (LRU, refcount back to "
                "zero)"),
            "prefix_shareable": REGISTRY.counter(
                "rafiki_gen_prefix_shareable_total",
                "admitted prompts whose leading tokens matched a "
                "recently-seen prompt (shared-prefix traffic signal — "
                "counted even while the prefix cache is disabled, so the "
                "doctor can flag a disabled cache under shareable load)"),
            "cow": REGISTRY.counter(
                "rafiki_gen_kv_cow_copies_total",
                "copy-on-write page copies (tail-block divergence)"),
            "preempts": REGISTRY.counter(
                "rafiki_gen_preemptions_total",
                "streams preempted by pool exhaustion (blocks freed, "
                "request re-queued and later resumed)"),
            "spec_proposed": REGISTRY.counter(
                "rafiki_gen_spec_proposed_total",
                "draft tokens proposed to the speculative verify step"),
            "spec_accepted": REGISTRY.counter(
                "rafiki_gen_spec_accepted_total",
                "draft tokens accepted by the target's verify step "
                "(acceptance rate = accepted / proposed)"),
            "spec_rounds": REGISTRY.counter(
                "rafiki_gen_spec_rounds_total",
                "speculative draft-propose/verify rounds run"),
            "spec_degraded": REGISTRY.counter(
                "rafiki_gen_spec_degraded_total",
                "speculation degradations to plain decode (draft fault, "
                "verify fault, capability mismatch)"),
            "table_blocks": REGISTRY.counter(
                "rafiki_gen_decode_table_blocks",
                "paged decode rounds by the width, in blocks, of the block "
                "tables they were handed: the narrowest rung of the "
                "worker's ladder that covers the round's longest live "
                "sequence", ("blocks",)),
            "state_resets": REGISTRY.counter(
                "rafiki_gen_state_resets_total",
                "prefills from position 0 of a model that declares "
                "recurrent state: a slot's state started from zero "
                "(admissions, and resumes of preempted streams)"),
            "state_bytes": REGISTRY.gauge(
                "rafiki_gen_state_bytes",
                "bytes of per-slot recurrent state the decode cache holds "
                "beside its paged rows (unset for a model without one)",
                ("service",)),
            "kv_row_bytes": REGISTRY.gauge(
                "rafiki_gen_kv_row_bytes",
                "bytes a token leaves in the paged pool over all layers: "
                "keys and values of full heads, or a latent row a layer",
                ("service",)),
            "prefill_chunks": REGISTRY.counter(
                "rafiki_gen_prefill_chunks_total",
                "prefill chunks dispatched to the model, final or not"),
            "prefill_chunk_tokens": REGISTRY.counter(
                "rafiki_gen_prefill_chunk_tokens_total",
                "real prompt tokens of the prefill chunks dispatched (the "
                "padding to a bucket is the template's and not counted)"),
            "expert_tokens": REGISTRY.counter(
                "rafiki_gen_expert_tokens_total",
                "(token, expert) choices that fell on an expert held here, "
                "over the expert layers of every decode round"),
            "experts_hit": REGISTRY.counter(
                "rafiki_gen_experts_hit_total",
                "held experts that a decode round's tokens chose, summed "
                "over expert layers and rounds (whose weights a round "
                "has to read)"),
            "expert_layer_rounds": REGISTRY.counter(
                "rafiki_gen_expert_layer_rounds_total",
                "expert layers run by decode rounds (experts_hit over "
                "this is the mean number hit a round a layer)"),
            "migrated": REGISTRY.counter(
                "rafiki_gen_streams_migrated_total",
                "unfinished streams handed back typed (MIGRATING) by a "
                "retiring generation replica for door-side resume on a "
                "sibling (docs/failure-model.md \"Stream continuity\")"),
        }
    return _M


_M = None

#: leading-token window hashed for the shared-prefix-traffic signal
_SHARE_PROBE_TOKENS = 16


class _Slot:
    """One resident sequence's scheduler state."""

    __slots__ = ("stream", "last_id", "position", "produced", "max_tokens",
                 "deadline", "muted", "last_step_t", "prompt", "tokens",
                 "pending_from", "seq", "t0", "temperature", "top_k",
                 "top_p", "rng_seed", "draft_ready")

    def __init__(self, stream: TokenStream, prompt: List[int],
                 max_tokens: int, deadline: Optional[float], seq: int,
                 produced: int = 0,
                 pending_from: Optional[int] = None,
                 sampling: Optional[tuple] = None) -> None:
        self.stream = stream
        self.prompt = prompt          # full token history being prefilled
        self.tokens: List[int] = []   # tokens produced SINCE (re)admission
        self.last_id = 0
        self.position = 0             # cache index the NEXT token lands at
        self.produced = produced      # client-visible tokens so far
        self.max_tokens = max_tokens
        self.deadline = deadline
        #: admission order — pool exhaustion preempts the YOUNGEST stream
        self.seq = seq
        #: next prompt index still to prefill (None = decoding)
        self.pending_from = pending_from
        #: admit time, for the TTFT observation (None after first token
        #: or for preemption resumes — a resume is not a first token)
        self.t0: Optional[float] = None
        #: chaos action=drop: the stalled-decode drill — the slot keeps
        #: its place but its deltas stop arriving; the DOOR's inter-token
        #: timeout must convert the silence into a typed error frame
        self.muted = False
        self.last_step_t = time.monotonic()
        #: sampling params (temperature=0 = greedy); rng_seed is the
        #: stream's counter-RNG seed, FIXED at first admission so a
        #: preemption resume replays the identical sampled sequence
        t, tk, tp, sd = sampling or (0.0, 0, 1.0, 0)
        self.temperature = float(t)
        self.top_k = int(tk)
        self.top_p = float(tp)
        self.rng_seed = int(sd)
        #: draft-model KV rows cover this slot's history (speculation).
        #: Any round a decoding slot sits out garbles its draft-ring row,
        #: so non-participants are invalidated and re-prefilled lazily.
        self.draft_ready = False


class _Pending:
    """A stream waiting for pool blocks: either a not-yet-admitted
    request (``fut``/``query`` set) or a preempted resident stream being
    resumed (``stream``/``prompt`` carry its full token history)."""

    __slots__ = ("fut", "query", "stream", "prompt", "produced",
                 "max_tokens", "deadline", "seq", "sampling")

    def __init__(self, seq: int, fut=None, query=None, stream=None,
                 prompt=None, produced=0, max_tokens=0, deadline=None,
                 sampling=None):
        self.seq = seq
        self.fut = fut
        self.query = query
        self.stream = stream
        self.prompt = prompt
        self.produced = produced
        self.max_tokens = max_tokens
        self.deadline = deadline
        self.sampling = sampling


class GenerationWorker(InferenceWorker):
    """Serves one trained trial's LM as a token stream. Reuses the
    classification worker's model loading / stats reporting / queue
    registration; only the serve loop differs."""

    def start(self, ctx) -> None:
        from rafiki_tpu.parallel.mesh import set_device_grant
        from rafiki_tpu.utils.metrics import REGISTRY

        set_device_grant(ctx.chips)
        t_start = time.monotonic()
        model = None
        queue = self._broker.register_worker(self._job_id, ctx.service_id)
        try:
            model = self._load_model(ctx.service_id)
            spec = generation_capability(type(model))
            if spec is None:
                raise GenerationUnsupportedError(
                    f"trial {self._trial_id}'s template does not advertise "
                    "a fully-wired GenerationSpec (init_kv_cache/prefill/"
                    "decode_step) — it cannot serve TEXT_GENERATION")
            max_slots = max(int(config.GEN_MAX_SLOTS), 1)
            self._alloc: Optional[PagedKVAllocator] = None
            self._chunk = 0
            # a fixed state a slot beside the keys and values (sdk/model.py
            # GenerationSpec): nothing of it can be shared, rewound or
            # rolled back
            self._recurrent = bool(spec.recurrent_state)
            if self._recurrent:
                self._refuse_unsound_for_state(type(model))
            paged_spec = paged_generation_capability(type(model))
            if bool(config.GEN_KV_PAGED) and paged_spec is not None:
                block_tokens = max(int(config.GEN_KV_BLOCK_TOKENS), 1)
                table_blocks = -(-int(spec.max_context) // block_tokens)
                pool_blocks = (int(config.GEN_KV_POOL_BLOCKS)
                               or max_slots * table_blocks)
                # a cached prefix holds keys and values and no state, so a
                # recurrent model is served no hit: every admission counts
                # as a miss and prefills from position 0
                self._alloc = PagedKVAllocator(
                    pool_blocks, block_tokens, table_blocks,
                    prefix_cache=(bool(config.GEN_PREFIX_CACHE)
                                  and not self._recurrent))
                self._chunk = max(int(config.GEN_PREFILL_CHUNK), 0)
                cache = (model.init_paged_kv_cache(pool_blocks, block_tokens,
                                                   max_slots)
                         if self._recurrent else
                         model.init_paged_kv_cache(pool_blocks, block_tokens))
                logger.info(
                    "generation worker %s: paged KV (%d blocks x %d "
                    "tokens, prefix cache %s, prefill chunk %d)",
                    ctx.service_id, pool_blocks, block_tokens,
                    "on" if self._alloc.prefix_cache else "off",
                    self._chunk)
            else:
                cache = model.init_kv_cache(max_slots)
            state_bytes = (int(model.recurrent_state_bytes(cache))
                           if self._recurrent else 0)
            if self._recurrent:
                _metrics()["state_bytes"].labels(ctx.service_id).set(
                    state_bytes)
            if self._alloc is not None:
                # what is not state is the pool: every paged group's rows
                import jax

                pool_bytes = sum(int(a.nbytes) for a in
                                 jax.tree_util.tree_leaves(cache)) \
                    - state_bytes
                _metrics()["kv_row_bytes"].labels(ctx.service_id).set(
                    pool_bytes // (pool_blocks * block_tokens))
            self._init_spec(model, spec, max_slots, ctx)
            # pre-warm per-bucket prefill + decode programs under the
            # persistent compile cache, before ctx.ready(): a still-
            # compiling generation replica stays DEPLOYING/unroutable
            from rafiki_tpu.worker.warmup import run_warmup

            # Every rung of the decode round's table ladder is run once,
            # all rows idle, so that no width is first met, and compiled,
            # under live streams. The deploy waits SERVICE_DEPLOY_TIMEOUT_S
            # for this replica and the model's load may have taken most of
            # it (40 of 60 s for 9 GB of weights on a v5e), so the rungs
            # that do not fit the first half of that wait (a cold compile
            # cache: seconds a rung; a warm one loads them all) are run
            # after ready(), still before the first request is admitted.
            unwarmed = (list(self._alloc.table_widths)
                        if self._alloc is not None else [])

            def warm_table_widths(until: Optional[float] = None):
                nonlocal cache
                while unwarmed and not ctx.stopping and (
                        until is None or time.monotonic() < until):
                    cache = self._idle_round(model, cache, max_slots,
                                             unwarmed[0])
                    del unwarmed[0]

            run_warmup(
                ctx.service_id, self._job_id,
                [("warm_up", model.warm_up)]
                + ([("decode_table_widths", lambda: warm_table_widths(
                    t_start + float(config.SERVICE_DEPLOY_TIMEOUT_S) / 2))]
                   if unwarmed else []))
            ctx.ready()
            warm_table_widths()
            if self._report_stats is not None:
                threading.Thread(
                    target=self._stats_reporter, args=(ctx,),
                    name="stats-reporter", daemon=True).start()
            slots: List[Optional[_Slot]] = [None] * max_slots
            occupancy_ring = REGISTRY.ring(
                f"slot_occupancy:job:{self._job_id}")
            m = _metrics()
            # lint: thread-confined(only the serve thread writes and reads this; the reporter thread reads the _stats_lock'd module dict copy)
            self._tokens_emitted = 0
            # lint: thread-confined(admission order counter — the serve thread is the only scheduler)
            self._seq = 0
            # lint: thread-confined(preempted/stashed continuations — only the serve thread admits, preempts, and resumes)
            self._pending = []
            self._recent_prefixes: "OrderedDict[str, bool]" = OrderedDict()
            self._last_alloc_stats: Dict[str, int] = {}
            # lint: thread-confined(set by the serve thread's chaos kill only)
            killed = False
            while not ctx.stopping:
                # replica-level chaos (RAFIKI_CHAOS site=worker, the same
                # target shape as the classification serve loop): the
                # deterministic SIGKILL-mid-stream drill. drop = abrupt
                # death — resident streams are ABANDONED without terminal
                # deltas (exactly what a real SIGKILL leaves behind; the
                # door detects the dead replica on its stall timeout and
                # resumes from the journal); error = clean kill — every
                # resident stream is handed back typed MIGRATING before
                # the replica exits; delay = slow replica.
                rule = chaos.hit(chaos.SITE_WORKER,
                                 f"{self._job_id}/{ctx.service_id}")
                if rule is not None:
                    if rule.action == chaos.ACTION_DELAY:
                        chaos.sleep_for(rule)
                    elif rule.action == chaos.ACTION_DROP:
                        logger.warning(
                            "chaos: killing generation replica %s "
                            "(streams abandoned, SIGKILL drill)",
                            ctx.service_id)
                        killed = True
                        break
                    else:  # ACTION_ERROR: clean kill with handoff
                        logger.warning(
                            "chaos: retiring generation replica %s "
                            "(streams handed back MIGRATING)",
                            ctx.service_id)
                        break
                n_active = sum(1 for s in slots if s is not None)
                free = [i for i, s in enumerate(slots) if s is None]
                # The iteration is tiled by spans on the profiler's clock
                # (utils/trace.py span; docs/observability.md "Spans"):
                # gen.admit, gen.prefill_chunk, gen.bookkeep, then the
                # round's gen.decode.build / .device / .post. A
                # speculative round has no span yet.
                # -- admit: resumes first, then queued requests -----------
                with trace.span("gen.admit"):
                    if free and self._pending:
                        cache = self._readmit(model, spec, cache, slots,
                                              free, ctx.service_id)
                    if free and (n_active == 0 or queue.depth() > 0) \
                            and self._room_for_new():
                        batch = queue.take_batch(
                            max_size=len(free), deadline_s=0.0,
                            wait_timeout_s=(0.25 if n_active == 0
                                            and not self._pending else 0.0))
                        if batch is None:
                            logger.info("query queue closed; generation "
                                        "worker %s exiting", ctx.service_id)
                            break
                        for fut, query in batch:
                            cache = self._admit(
                                model, spec, cache, slots, free, fut, query,
                                ctx.service_id)
                        _record_queue(ctx.service_id, queue)
                # -- chunked prefill: one chunk per prefilling slot -------
                if self._alloc is not None:
                    cache = self._prefill_round(model, spec, cache, slots,
                                                ctx)
                with trace.span("gen.bookkeep"):
                    n_active = sum(1 for s in slots if s is not None)
                    m["slots"].labels(ctx.service_id).set(
                        sum(1 for s in slots
                            if s is not None and s.pending_from is None))
                    self._mirror_alloc(ctx.service_id, m)
                    occupancy_ring.record(self._occupancy(slots, max_slots))
                    self._stats_row(ctx.service_id, slots, max_slots)
                if n_active == 0 and not self._pending:
                    continue
                # -- decode: one token for every resident sequence (or a
                # draft-propose/verify burst when speculation is live) ----
                if any(s is not None and s.pending_from is None
                       for s in slots):
                    if self._spec_on:
                        cache = self._spec_round(model, spec, cache,
                                                 slots, ctx)
                    else:
                        cache = self._decode_round(model, spec, cache,
                                                   slots, ctx)
                elif n_active == 0:
                    # only stashed streams remain and nothing can run —
                    # don't spin while the pool refills
                    time.sleep(0.005)
            # -- drain handoff (docs/failure-model.md "Stream
            # continuity"): a retiring replica (scale-down drain, rollout
            # retirement, queue closed, clean chaos kill) must never
            # abandon a resident stream silently — each one is handed
            # back typed MIGRATING so the door resumes it on a sibling.
            # A chaos SIGKILL (killed=True) skips this on purpose: the
            # whole point of that drill is recovering WITHOUT a handoff.
            if not killed:
                self._hand_back_all(slots, ctx.service_id)
        finally:
            self._broker.unregister_worker(self._job_id, ctx.service_id)
            if getattr(self, "_draft", None) is not None:
                self._draft.destroy()
            if model is not None:
                model.destroy()
            set_device_grant(None)

    @staticmethod
    def _refuse_unsound_for_state(clazz: type) -> None:
        """A template that declares recurrent state and also wires sampled
        decode or speculative verify is refused at deploy: a sampled
        stream's first round replays the prompt's last token (once more
        into the state), and a rejected draft cannot leave it."""
        wired = [what for what, cap in (
            ("sampled decode", sampling_capability),
            ("speculative verify", spec_verify_capability))
            if cap(clazz) is not None]
        if wired:
            raise GenerationUnsupportedError(
                f"{clazz.__name__} declares recurrent_state and wires "
                f"{' and '.join(wired)}: a fixed per-slot state cannot be "
                "rewound one token or rolled back past a rejected draft, "
                "so such a template serves greedy decode only")

    # -- sampling + speculation setup ----------------------------------------

    def _init_spec(self, model, spec: GenerationSpec, max_slots: int,
                   ctx) -> None:
        """Wire sampling + speculative decoding for this worker. Sampling
        needs only a capable template; speculation additionally needs the
        paged plane, the verify capability, and a draft trial budgeted
        as ``BudgetType.GEN_DRAFT_TRIAL`` on the inference job. Anything
        missing degrades TYPED — the worker serves plain decode and the
        reason lands in the stats row for the doctor to surface."""
        self._sampling_cap = sampling_capability(type(model))
        # lint: thread-confined(speculation state — only the serve thread schedules; the reporter thread reads the _stats_lock'd row copy)
        self._spec_on = False
        self._spec_degraded: Optional[str] = None
        self._spec_k = min(max(int(config.GEN_SPEC_K), 1), 16)
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_rounds = 0
        self._draft = None
        self._draft_spec: Optional[GenerationSpec] = None
        self._draft_cache = None
        if not bool(config.GEN_SPEC) or self._alloc is None:
            return  # speculation is opt-in and lives on the paged plane
        if spec_verify_capability(type(model)) is None:
            self._spec_degraded = (
                "template lacks the speculative verify capability "
                "(paged_verify_step + sampled decode)")
            return
        try:
            draft = self._load_draft_model(ctx.service_id)
        except Exception:
            logger.error("draft model failed to load in generation "
                         "worker %s:\n%s", ctx.service_id,
                         traceback.format_exc())
            self._spec_degraded = "draft model failed to load"
            return
        if draft is None:
            return  # job budgets no draft: plain decode, not a fault
        dspec = draft_capability(type(draft))
        if dspec is None:
            draft.destroy()
            self._spec_degraded = (
                "draft trial's template is not draft-capable (generation "
                "contract + decode_step_sampled)")
            return
        self._draft = draft
        self._draft_spec = dspec
        self._draft_cache = draft.init_kv_cache(max_slots)
        self._spec_on = True
        logger.info(
            "generation worker %s: speculative decoding on (k=%d, draft "
            "max_context=%d)", ctx.service_id, self._spec_k,
            dspec.max_context)

    def _load_draft_model(self, service_id: str):
        """The job's draft LM: ``BudgetType.GEN_DRAFT_TRIAL`` in the
        inference job's budget names a (small) generation-capable trial,
        loaded through the normal trial-artifact path. None = the job
        budgets no draft, so speculation simply stays off."""
        if getattr(self, "_db", None) is None:
            return None
        inf = self._db.get_inference_job(self._job_id)
        draft_tid = ((inf or {}).get("budget") or {}).get(
            BudgetType.GEN_DRAFT_TRIAL)
        if not draft_tid:
            return None
        return self._load_one(str(draft_tid), f"{service_id}-draft")

    def _degrade_spec(self, reason: str) -> None:
        """Speculation faulted (draft crash/stall, verify mismatch): fall
        back to plain paged decode TYPED. Resident streams keep decoding
        — losing the multiplier must never lose tokens."""
        if not self._spec_on:
            return
        self._spec_on = False
        self._spec_degraded = reason
        _metrics()["spec_degraded"].inc()
        logger.error("generation worker: speculative decoding degraded "
                     "to plain decode — %s", reason)

    def _sampling_arrays(self, slots, role, only=None) -> Dict[str, object]:
        """Per-slot sampling params as the fixed-shape arrays the sampled
        model methods take. Idle (and filtered) rows get temperature 0,
        whose modified distribution is the argmax one-hot — shape-stable
        and harmless for rows whose writes are dropped anyway."""
        n = len(slots)
        seed = np.zeros(n, np.uint32)
        temp = np.zeros(n, np.float32)
        tk = np.zeros(n, np.int32)
        tp = np.ones(n, np.float32)
        for i, s in enumerate(slots):
            if s is None or (only is not None and i not in only):
                continue
            seed[i] = np.uint32(s.rng_seed & 0xFFFFFFFF)
            temp[i] = np.float32(s.temperature)
            tk[i] = np.int32(s.top_k)
            tp[i] = np.float32(s.top_p)
        return {"seed": seed, "temperature": temp, "top_k": tk,
                "top_p": tp, "role": int(role)}

    # -- the decode round's block tables -------------------------------------

    def _idle_round(self, model, cache, max_slots: int, width: int):
        """One greedy decode round with every row idle, at table width
        ``width``: what warms that rung's program before the worker admits
        its first request. The rows are all sentinel, so the writes are
        dropped, a recurrent model keeps its state and chooses no expert,
        and the (donated) cache comes back as it went in. The sampled
        program is not warmed: it compiles at first use, as it always has,
        now once a rung."""
        zeros = np.zeros(max_slots, np.int32)
        tables = np.tile(self._alloc.idle_row(width), (max_slots, 1))
        _, cache, *_ = model.paged_decode_step(cache, zeros, zeros, tables)
        return cache

    def _decode_tables(self, slots, live) -> np.ndarray:
        """The round's (slots, width) block tables: ``width`` the narrowest
        rung that holds the furthest position a ``live`` row writes this
        round (a position past its table is dropped by the model, silently:
        ``table_width`` refuses what no rung holds); other rows idle."""
        alloc = self._alloc
        width = alloc.table_width(
            max(slots[i].position for i in live) + 1)
        return np.stack([
            alloc.table_row(i, width) if i in live else alloc.idle_row(width)
            for i in range(len(slots))])

    # -- admission -----------------------------------------------------------

    def _room_for_new(self) -> bool:
        """Gate NEW queue pulls under the paged allocator: stashed
        streams resume first, and an effectively-dry pool admits no one
        (churning admissions straight into preemption helps nobody)."""
        if self._alloc is None:
            return True
        if self._pending:
            return False
        return (self._alloc.free_blocks()
                + self._alloc.evictable_blocks()) >= 2

    def _admit(self, model, spec: GenerationSpec, cache,
               slots: List[Optional[_Slot]], free: List[int], fut, query,
               service_id: str, seq: Optional[int] = None):
        """Prefill one queued request into a free slot and hand its
        TokenStream back through the request's future. A malformed
        request fails ITS future (typed, -> 400 at the door) and costs no
        slot; a prefill crash likewise never kills co-resident slots.
        ``seq`` re-admits a stashed request under its ORIGINAL admission
        order — minting a fresh one would make the oldest waiter the
        youngest resident and the first preemption victim (starvation).

        A RESUME request (``resume_tokens`` carries a dead/retired
        sibling's committed history) admits through this same path: the
        full history is prefilled under the stream's pinned seed, the
        position-keyed RNG continues the sampled sequence
        token-identically, and the slot starts with ``produced`` already
        at the committed count so ``max_tokens`` stays the ORIGINAL
        budget — the KV charge is exactly history + remaining budget,
        and a resume never lands a TTFT observation."""
        try:
            prompt, max_tokens, max_duration_s, sampling = \
                self._parse_query(query)
            resume = self._parse_resume(query)
        except GenerationRequestError as e:
            fut.set_error(e)
            return cache
        if resume and len(resume) >= max_tokens:
            fut.set_error(GenerationRequestError(
                f"resume_tokens ({len(resume)}) already meets max_tokens "
                f"({max_tokens}) — nothing left to resume"))
            return cache
        if sampling[0] > 0.0 \
                and getattr(self, "_sampling_cap", None) is None:
            fut.set_error(GenerationRequestError(
                "sampled generation (temperature > 0) needs a "
                "sampling-capable template (decode_step_sampled; plus "
                "paged_decode_step_sampled under the paged layout)"))
            return cache
        if not free:
            # take_batch was sized to the free count, but a same-round
            # earlier admit may have failed and returned its slot unused;
            # being here with none left means a scheduler bug upstream —
            # fail the request rather than strand it silently
            fut.set_error(RuntimeError("no free generation slot"))
            return cache
        if len(prompt) + max_tokens > spec.max_context:
            fut.set_error(GenerationRequestError(
                f"prompt ({len(prompt)} tokens) + max_tokens "
                f"({max_tokens}) exceeds the template's max_context "
                f"({spec.max_context})"))
            return cache
        self._note_shareable(prompt)
        #: the prefill history — prompt + committed tokens for a resume
        history = prompt + resume
        produced = len(resume)
        deadline = (time.monotonic() + max_duration_s
                    if max_duration_s else None)
        if self._alloc is not None:
            if self._alloc.blocks_for(len(history) + 1) \
                    > self._alloc.pool_blocks:
                fut.set_error(GenerationRequestError(
                    f"prompt+history ({len(history)} tokens) cannot fit "
                    f"the KV pool ({self._alloc.pool_blocks} blocks x "
                    f"{self._alloc.block_tokens} tokens) — raise "
                    "RAFIKI_GEN_KV_POOL_BLOCKS"))
                return cache
            return self._admit_paged(model, spec, cache, slots, free, fut,
                                     history, max_tokens, deadline,
                                     service_id, seq=seq,
                                     sampling=sampling, produced=produced)
        # -- contiguous-ring path -------------------------------------------
        slot_ix = free.pop(0)
        t0 = time.monotonic()
        try:
            first_id, cache = model.prefill(cache, slot_ix, list(history))
            if self._recurrent:
                _metrics()["state_resets"].inc()
        except Exception as e:
            free.insert(0, slot_ix)
            logger.error("prefill failed in generation worker %s:\n%s",
                         service_id, traceback.format_exc())
            fut.set_error(RuntimeError(f"prefill failed: {e}"))
            return cache
        stream = TokenStream(seq_id=uuid.uuid4().hex[:12])
        slot = _Slot(stream, list(history), max_tokens, deadline,
                     self._next_seq() if seq is None else seq,
                     produced=produced, sampling=sampling)
        slots[slot_ix] = slot
        fut.set_result(stream)
        from rafiki_tpu.worker.inference import _record_batch

        _record_batch(service_id, 1)  # one admitted request
        m = _metrics()
        if slot.temperature > 0.0:
            # sampled stream: prefill's token is the GREEDY pick — do not
            # commit it. Rewind one row so the next decode round rewrites
            # the last prompt position (identical K/V) and SAMPLES the
            # first token under its position-keyed counter RNG; TTFT
            # lands on that first sampled commit. A resume rewinds the
            # same way — onto its last COMMITTED token — and suppresses
            # TTFT (a resumed token is never a first token).
            slot.last_id = history[-1]
            slot.position = len(history) - 1
            slot.t0 = None if produced else t0
            return cache
        first_id = int(first_id)
        slot.last_id = first_id
        slot.position = len(history)
        slot.produced += 1
        slot.tokens.append(first_id)
        if not produced:
            m["ttft"].observe(time.monotonic() - t0)
        m["tokens"].inc()
        finished, reason = self._finish_reason(slot, spec, first_id)
        stream.push([first_id], finished=finished, reason=reason)
        if finished:
            self._evict(slots, slot_ix, reason)
        return cache

    def _next_seq(self) -> int:
        self._seq += 1
        return self._seq

    def _note_shareable(self, prompt: List[int]) -> None:
        """Record shared-prefix traffic whether or not the cache is on —
        the doctor's disabled-cache-under-shareable-load signal."""
        probe = tuple(prompt[:_SHARE_PROBE_TOKENS])
        if len(probe) < 2:
            return
        d = hashlib.sha1(np.asarray(probe, np.int64).tobytes()).hexdigest()
        lru = self._recent_prefixes
        if d in lru:
            lru.move_to_end(d)
            _metrics()["prefix_shareable"].inc()
            return
        lru[d] = True
        while len(lru) > 512:
            lru.popitem(last=False)

    # -- paged admission / prefill -------------------------------------------

    def _admit_paged(self, model, spec, cache, slots, free, fut, prompt,
                     max_tokens, deadline, service_id, seq=None,
                     sampling=None, produced=0):
        """Open a block table for the prompt (mapping any cached prefix),
        run the FIRST prefill chunk synchronously, and resolve the
        request's future. Remaining chunks (long prompts) advance one per
        scheduler round so resident streams keep decoding in between. A
        pool too full for even the first chunk stashes the request — it
        is the youngest stream, so IT waits, not the residents.

        For a door-side RESUME, ``prompt`` is the full prompt+committed
        history and ``produced`` the committed count — the slot keeps the
        original ``max_tokens`` budget and never lands a TTFT sample."""
        slot_ix = free.pop(0)
        slot = _Slot(TokenStream(seq_id=uuid.uuid4().hex[:12]),
                     list(prompt), max_tokens, deadline,
                     self._next_seq() if seq is None else seq,
                     produced=produced, sampling=sampling)
        plan = self._alloc.open_slot(slot_ix, prompt)
        slot.pending_from = plan.cached_tokens
        slot.position = plan.cached_tokens
        slot.t0 = None if produced else time.monotonic()
        slots[slot_ix] = slot  # before _try_chunk: a same-call finish
        # (tiny prompt hitting EOS on its first token) evicts through the
        # normal path
        try:
            if plan.copies:
                cache = self._apply_copies(model, cache, plan.copies)
            n = len(prompt)
            end = n if self._chunk <= 0 else min(n, plan.cached_tokens
                                                 + self._chunk)
            ok, cache = self._try_chunk(model, spec, cache, slots, slot_ix,
                                        slot, end)
            if not ok:
                # pool dry: stash the request un-admitted (future intact)
                slots[slot_ix] = None
                self._alloc.close_slot(slot_ix)
                free.insert(0, slot_ix)
                cut = len(prompt) - produced
                query = {"prompt_ids": list(prompt[:cut]),
                         "max_tokens": max_tokens,
                         "max_duration_s": None,
                         # carry the DERIVED seed: the resumed parse
                         # must replay the identical sampled stream
                         "temperature": slot.temperature,
                         "top_k": slot.top_k, "top_p": slot.top_p,
                         "seed": slot.rng_seed}
                if produced:
                    query["resume_tokens"] = list(prompt[cut:])
                self._stash(_Pending(slot.seq, fut=fut, query=query,
                                     deadline=deadline))
                return cache
        except Exception as e:
            slots[slot_ix] = None
            self._alloc.close_slot(slot_ix)
            free.insert(0, slot_ix)
            logger.error("prefill failed in generation worker %s:\n%s",
                         service_id, traceback.format_exc())
            fut.set_error(RuntimeError(f"prefill failed: {e}"))
            return cache
        fut.set_result(slot.stream)
        from rafiki_tpu.worker.inference import _record_batch

        _record_batch(service_id, 1)
        return cache

    def _readmit(self, model, spec, cache, slots, free, service_id):
        """Resume stashed streams (oldest first): preempted residents
        re-prefill their full token history — greedy decode makes the
        continuation exact — and not-yet-admitted requests go through
        the normal paged admission."""
        while free and self._pending:
            entry = self._pending[0]
            if not self._room_for_resume(entry):
                break
            self._pending.pop(0)
            now = time.monotonic()
            if entry.deadline is not None and now >= entry.deadline:
                if entry.stream is not None:
                    entry.stream.push([], finished=True, reason="deadline")
                elif entry.fut is not None:
                    entry.fut.set_error(TimeoutError(
                        "generation request expired waiting for KV pool "
                        "blocks"))
                continue
            if entry.fut is not None:
                if entry.deadline is not None:
                    # re-derive the request's remaining duration so the
                    # resumed admission keeps the original absolute bound
                    entry.query["max_duration_s"] = max(
                        entry.deadline - now, 0.001)
                cache = self._admit(model, spec, cache, slots, free,
                                    entry.fut, entry.query, service_id,
                                    seq=entry.seq)
                continue
            if entry.stream.cancelled:
                continue
            slot_ix = free.pop(0)
            slot = _Slot(entry.stream, list(entry.prompt),
                         entry.max_tokens, entry.deadline, entry.seq,
                         produced=entry.produced,
                         sampling=entry.sampling)
            plan = self._alloc.open_slot(slot_ix, slot.prompt)
            slot.pending_from = plan.cached_tokens
            slot.position = plan.cached_tokens
            try:
                if plan.copies:
                    cache = self._apply_copies(model, cache, plan.copies)
            except Exception:
                logger.error("resume copy failed in generation worker "
                             "%s:\n%s", service_id,
                             traceback.format_exc())
                self._alloc.close_slot(slot_ix)
                free.insert(0, slot_ix)
                slot.stream.fail("preempted stream could not be resumed")
                continue
            slots[slot_ix] = slot  # chunks advance in _prefill_round
        return cache

    def _room_for_resume(self, entry: _Pending) -> bool:
        need = self._alloc.blocks_for(
            self._chunk if self._chunk > 0
            else len(entry.prompt or (entry.query or {}).get(
                "prompt_ids", [])) + 1)
        return (self._alloc.free_blocks()
                + self._alloc.evictable_blocks()) >= max(need, 1)

    def _stash(self, entry: _Pending) -> None:
        self._pending.append(entry)
        self._pending.sort(key=lambda e: e.seq)

    def _apply_copies(self, model, cache, copies):
        src = np.asarray([s for s, _ in copies], np.int32)
        dst = np.asarray([d for _, d in copies], np.int32)
        return model.kv_copy_blocks(cache, src, dst)

    def _try_chunk(self, model, spec, cache, slots, slot_ix, slot, end):
        """Prefill prompt positions [pending_from, end) for one slot.
        Returns (ok, cache); ok=False means the pool could not supply
        blocks even after preempting every younger stream — the CALLER
        stashes/fails this slot. Exceptions propagate (model crash)."""
        start = slot.pending_from
        n = len(slot.prompt)
        if not self._make_capacity(slots, slot_ix, end - 1):
            return False, cache
        for ix in range(start // self._alloc.block_tokens,
                        (end - 1) // self._alloc.block_tokens + 1):
            copies = self._alloc.ensure_writable(
                slot_ix, ix * self._alloc.block_tokens)
            if copies is None:
                if not self._preempt_youngest(slots, exclude=slot_ix):
                    return False, cache
                copies = self._alloc.ensure_writable(
                    slot_ix, ix * self._alloc.block_tokens)
                if copies is None:
                    return False, cache
            if copies:
                cache = self._apply_copies(model, cache, copies)
        chunk_tokens = slot.prompt[start:end]
        # The span holds the chunk's device time only where the token is
        # fetched: the final chunk of a greedy stream. Any other chunk (not
        # final, or a sampled stream's, whose token is dropped) is an
        # asynchronous dispatch, and its device time lands under the next
        # gen.decode.device.
        m = _metrics()
        m["prefill_chunks"].inc()
        m["prefill_chunk_tokens"].inc(len(chunk_tokens))
        with trace.span("gen.prefill_chunk",
                        chunk=(start // self._chunk if self._chunk > 0
                               else 0),
                        table_blocks=self._alloc.table_blocks):
            args = (cache, self._alloc.table_row(slot_ix),
                    list(chunk_tokens), int(start))
            if self._recurrent:
                # the chunk continues THIS slot's state; at 0 it starts anew
                tok, cache = model.paged_prefill(*args, slot_ix)
                if start == 0:
                    m["state_resets"].inc()
            else:
                tok, cache = model.paged_prefill(*args)
            if end == n and slot.temperature <= 0.0:
                tok = int(tok)
        slot.pending_from = end
        slot.position = end
        if end < n:
            # a sign of life: a long prompt takes many rounds to its first
            # token, and the door ends a stream it hears nothing from
            # (RAFIKI_GEN_STREAM_TIMEOUT_S); it forwards no empty delta
            slot.stream.push([], finished=False)
            return True, cache
        if slot.temperature > 0.0:
            # sampled stream: prefill's token is the greedy pick — do not
            # commit it. Rewind one row so the next decode rewrites the
            # last prompt position (identical K/V) and SAMPLES the first
            # token under its position-keyed counter RNG — which is also
            # exactly how a preempted sampled stream resumes mid-sequence.
            slot.pending_from = None
            slot.last_id = slot.prompt[-1]
            slot.position = n - 1
            self._alloc.publish(slot_ix, slot.prompt)
            return True, cache
        # final chunk: first generated token
        slot.pending_from = None
        slot.last_id = tok
        slot.produced += 1
        slot.tokens.append(tok)
        now = time.monotonic()
        if slot.t0 is not None:
            m["ttft"].observe(now - slot.t0)
            slot.t0 = None
        m["tokens"].inc()
        slot.last_step_t = now
        self._tokens_emitted += 1
        finished, reason = self._finish_reason(slot, spec, tok)
        if slot.deadline is not None and now >= slot.deadline:
            finished, reason = True, "deadline"
        slot.stream.push([tok], finished=finished, reason=reason)
        if finished:
            self._evict_slot(slots, slot_ix, reason)
        else:
            self._alloc.publish(slot_ix, slot.prompt)
        return True, cache

    def _prefill_round(self, model, spec, cache, slots, ctx):
        """Advance every PREFILLING slot by one chunk — interleaved with
        decode rounds so a max-context prompt joining never stalls
        resident streams' inter-token latency."""
        for i, slot in enumerate(slots):
            if slot is None or slot.pending_from is None:
                continue
            if slot.stream.cancelled:
                self._evict_slot(slots, i, "cancelled")
                continue
            n = len(slot.prompt)
            end = n if self._chunk <= 0 else min(n, slot.pending_from
                                                 + self._chunk)
            try:
                ok, cache = self._try_chunk(model, spec, cache, slots, i,
                                            slot, end)
            except Exception:
                logger.error(
                    "chunked prefill failed in generation worker %s:\n%s",
                    ctx.service_id, traceback.format_exc())
                slot.stream.fail("prefill failed on the serving worker")
                self._evict_slot(slots, i, "error")
                continue
            if not ok and slots[i] is slot:
                # pool dry even after preempting younger streams: this
                # slot yields its blocks and waits its turn
                self._preempt(slots, i)
        return cache

    # -- preemption ----------------------------------------------------------

    def _make_capacity(self, slots, slot_ix, position) -> bool:
        """ensure_capacity with the pool-exhaustion policy: preempt the
        youngest resident stream YOUNGER than the requester (typed:
        blocks freed, request re-queued) until the allocation lands or no
        such victim remains — an older stream is never displaced by a
        newer one, so the oldest stream always makes progress and the
        preemption chain terminates."""
        while not self._alloc.ensure_capacity(slot_ix, position):
            if not self._preempt_youngest(slots, exclude=slot_ix):
                return False
        return True

    def _preempt_youngest(self, slots, exclude: int) -> bool:
        """Preempt the youngest resident stream younger than ``exclude``
        (by admission order); False when there is nobody eligible."""
        mine = slots[exclude].seq if slots[exclude] is not None else -1
        cand = [(s.seq, i) for i, s in enumerate(slots)
                if s is not None and i != exclude and s.seq > mine]
        if not cand:
            return False
        _, victim = max(cand)
        self._preempt(slots, victim)
        return True

    def _preempt(self, slots, i) -> None:
        """Evict slot ``i`` for pool exhaustion: its blocks return to the
        pool and the stream is re-queued as a continuation (full token
        history re-prefilled on resume — the client just sees a pause,
        never an error or duplicate tokens). A stream whose grown history
        can NEVER fit the pool again is failed typed instead: re-queueing
        it would cycle preempt -> resume -> preempt forever while
        ``_room_for_new`` holds all new admissions behind it."""
        slot = slots[i]
        slots[i] = None
        self._alloc.close_slot(i)
        m = _metrics()
        if slot.stream.cancelled:
            m["evictions"].labels("cancelled").inc()
            return
        history = list(slot.prompt)
        if slot.pending_from is None:
            history += slot.tokens
        if self._alloc.blocks_for(len(history) + 1) \
                > self._alloc.pool_blocks:
            slot.stream.fail(
                f"stream outgrew the KV pool ({len(history)} tokens vs "
                f"{self._alloc.pool_blocks} blocks x "
                f"{self._alloc.block_tokens} tokens) — raise "
                "RAFIKI_GEN_KV_POOL_BLOCKS")
            m["evictions"].labels("kv_pool").inc()
            return
        m["evictions"].labels("preempted").inc()
        m["preempts"].inc()
        logger.warning(
            "generation worker: KV pool exhausted — preempting youngest "
            "stream %s (seq %d, %d tokens produced); re-queued",
            slot.stream.seq_id, slot.seq, slot.produced)
        self._stash(_Pending(
            slot.seq, stream=slot.stream, prompt=history,
            produced=slot.produced, max_tokens=slot.max_tokens,
            deadline=slot.deadline,
            sampling=(slot.temperature, slot.top_k, slot.top_p,
                      slot.rng_seed)))

    # -- drain handoff -------------------------------------------------------

    def _hand_back_all(self, slots: List[Optional[_Slot]],
                       service_id: str) -> None:
        """Typed MIGRATING handback of every unfinished resident (and
        preempted-stashed) stream — the retiring replica's half of the
        door-side resume contract. Streams that could finish inside the
        drain window already ran out through the normal serve loop; what
        is left here continues on a sibling from the door's journal.
        Pool-dry requests still waiting on their future get the same
        queue-closed error a close() would give them (the door's submit
        walk owns pre-stream retry)."""
        m = _metrics()
        handed = 0
        for i, s in enumerate(slots):
            if s is None:
                continue
            if s.stream.cancelled:
                self._evict_slot(slots, i, "cancelled")
                continue
            s.stream.hand_back(
                f"generation replica {service_id} is retiring; stream "
                "handed back for resume on a sibling")
            m["migrated"].inc()
            handed += 1
            self._evict_slot(slots, i, "migrating")
        for entry in self._pending:
            if entry.stream is not None:
                if not entry.stream.cancelled:
                    entry.stream.hand_back(
                        f"generation replica {service_id} is retiring; "
                        "stream handed back for resume on a sibling")
                    m["migrated"].inc()
                    handed += 1
            elif entry.fut is not None:
                entry.fut.set_error(RuntimeError("worker queue closed"))
        self._pending = []
        if handed:
            logger.info(
                "generation replica %s handed back %d unfinished "
                "stream(s) for door-side resume", service_id, handed)

    # -- the decode round ----------------------------------------------------

    def _decode_round(self, model, spec: GenerationSpec, cache,
                      slots: List[Optional[_Slot]], ctx, only=None):
        """Advance every resident DECODING sequence one token. Slot-level
        chaos is consulted per sequence, so a drill injures exactly one
        stream while siblings keep decoding. ``only`` restricts the round
        to a subset of slot indices — the speculative round uses it to
        advance the streams that sat out a verify burst (context edge,
        burst-capacity demotion) without re-stepping the participants."""
        n = len(slots)
        paged = self._alloc is not None
        with trace.span("gen.decode.build"):
            if paged:
                # growth + COW barriers for this round's writes
                for i, s in enumerate(slots):
                    if s is None or s.pending_from is not None:
                        continue
                    if only is not None and i not in only:
                        continue
                    if not self._make_capacity(slots, i, s.position):
                        if slots[i] is s:
                            self._preempt(slots, i)
                        continue
                    copies = self._alloc.ensure_writable(i, s.position)
                    if copies is None:
                        if not self._preempt_youngest(slots, exclude=i):
                            s.stream.fail(
                                "KV pool exhausted and no sibling stream "
                                "left to preempt — raise "
                                "RAFIKI_GEN_KV_POOL_BLOCKS")
                            self._evict_slot(slots, i, "kv_pool")
                            continue
                        copies = self._alloc.ensure_writable(i, s.position)
                        if copies is None:
                            s.stream.fail("KV pool exhausted")
                            self._evict_slot(slots, i, "kv_pool")
                            continue
                    if copies:
                        cache = self._apply_copies(model, cache, copies)
            active = [(i, s) for i, s in enumerate(slots)
                      if s is not None and s.pending_from is None
                      and (only is None or i in only)]
            if not active:
                return cache
            ids = np.zeros(n, np.int32)
            positions = np.zeros(n, np.int32)
            for i, s in active:
                ids[i] = s.last_id
                positions[i] = s.position
            # one sampled slot puts the whole batch through the sampled step
            # (greedy rows are bit-identical there: their modified dist is
            # the argmax one-hot) — the program count stays at one per shape
            sampled = (getattr(self, "_sampling_cap", None) is not None
                       and any(s.temperature > 0.0 for _, s in active))
            live = set(i for i, _ in active)
            try:
                tables = self._decode_tables(slots, live) if paged else None
            # lint: absorb(_fail_round logs it and fails the streams typed)
            except Exception:
                return self._fail_round(slots, ctx, cache)
            attrs = {"table_blocks": tables.shape[1]} if paged else {}
            if paged:
                _metrics()["table_blocks"].labels(tables.shape[1]).inc()
        try:
            # the model call through the fetch of its tokens: the one span
            # under which JAX's own host events nest
            with trace.span("gen.decode.device", **attrs):
                if paged and sampled:
                    next_ids, _probs, cache = \
                        model.paged_decode_step_sampled(
                            cache, ids, positions, tables,
                            self._sampling_arrays(slots, ROLE_TARGET,
                                                  only=live))
                elif paged:
                    # a third value is what the program counted (sdk/model.py
                    # paged_decode_step), fetched with the tokens
                    next_ids, cache, *counted = model.paged_decode_step(
                        cache, ids, positions, tables)
                    if counted:
                        import jax

                        next_ids, counted = jax.device_get(
                            (next_ids, counted[0]))
                        self._count_experts(counted)
                elif sampled:
                    next_ids, _probs, cache = model.decode_step_sampled(
                        cache, ids, positions,
                        self._sampling_arrays(slots, ROLE_TARGET,
                                              only=live))
                else:
                    next_ids, cache = model.decode_step(cache, ids,
                                                        positions)
                next_ids = np.asarray(next_ids)
        # lint: absorb(_fail_round logs it and fails the streams typed)
        except Exception:
            return self._fail_round(slots, ctx, cache)
        with trace.span("gen.decode.post"):
            now = time.monotonic()
            m = _metrics()
            for i, slot in enumerate(slots):
                if slot is None or slot.pending_from is not None:
                    continue
                if i not in live:
                    continue
                rule = chaos.hit(
                    chaos.SITE_GENERATE,
                    f"{self._job_id}/{ctx.service_id}/slot{i}/"
                    f"{slot.stream.seq_id}")
                if rule is not None:
                    if rule.action == chaos.ACTION_DELAY:
                        chaos.sleep_for(rule)
                    elif rule.action == chaos.ACTION_DROP:
                        # stalled decode: the slot stays resident but its
                        # deltas stop — the door's inter-token timeout owns
                        # recovery (typed error frame + cancel)
                        logger.warning(
                            "chaos: muting generation slot %d (%s)", i,
                            slot.stream.seq_id)
                        slot.muted = True
                    else:  # ACTION_ERROR: mid-stream fault on THIS stream
                        slot.stream.fail(
                            "chaos-injected mid-stream generation fault")
                        self._evict_slot(slots, i, "error")
                        continue
                if slot.stream.cancelled:
                    self._evict_slot(slots, i, "cancelled")
                    continue
                token = int(next_ids[i])
                slot.position += 1
                slot.last_id = token
                slot.produced += 1
                slot.tokens.append(token)
                m["intertoken"].observe(now - slot.last_step_t)
                slot.last_step_t = now
                m["tokens"].inc()
                self._tokens_emitted += 1
                if slot.t0 is not None:
                    # a sampled stream's first token commits HERE (admission
                    # rewound past prefill's greedy pick)
                    m["ttft"].observe(now - slot.t0)
                    slot.t0 = None
                finished, reason = self._finish_reason(slot, spec, token)
                if slot.deadline is not None and now >= slot.deadline:
                    finished, reason = True, "deadline"
                if not slot.muted:
                    slot.stream.push([token], finished=finished, reason=reason)
                if finished:
                    self._evict_slot(slots, i, reason)
        return cache

    @staticmethod
    def _count_experts(counted: Dict[str, object]) -> None:
        m = _metrics()
        for key, name in (("expert_tokens", "expert_tokens"),
                          ("experts_hit", "experts_hit"),
                          ("expert_layers", "expert_layer_rounds")):
            if key in counted:
                m[name].inc(int(counted[key]))

    def _fail_round(self, slots, ctx, cache):
        """A decode_step crash poisons the whole table (the cache may be
        half-written): fail every resident stream TYPED and clear the
        table — the worker keeps serving new requests. Called from the
        handler of the exception."""
        logger.error("decode_step failed in generation worker %s:\n%s",
                     ctx.service_id, traceback.format_exc())
        for i, s in enumerate(slots):
            if s is not None:
                s.stream.fail("decode step failed on the serving worker")
                self._evict_slot(slots, i, "error")
        return cache

    # -- the speculative round -----------------------------------------------

    def _spec_round(self, model, spec: GenerationSpec, cache,
                    slots: List[Optional[_Slot]], ctx):
        """One draft-propose/verify round: the draft LM proposes k tokens
        per eligible resident stream, the target verifies all k+1
        positions in ONE fixed-shape ``paged_verify_step`` forward, and
        every participant commits accept_len+1 tokens. Streams near a
        context edge (or demoted by a burst-capacity shortfall) take the
        plain one-token round instead THIS round; a draft or verify fault
        degrades speculation typed and the round finishes plain for
        everyone — the multiplier is lost, never the streams."""
        k = self._spec_k
        cand = []
        for i, s in enumerate(slots):
            if s is None or s.pending_from is not None:
                continue
            if (s.position + k >= spec.max_context
                    or s.position + k >= self._draft_spec.max_context):
                continue  # burst would cross a context edge
            cand.append(i)
        if not cand:
            return self._decode_round(model, spec, cache, slots, ctx)
        # draft-fault drill: a crashing/stalling DRAFT must cost the
        # multiplier, never the streams (docs/failure-model.md)
        rule = chaos.hit(chaos.SITE_GENERATE,
                         f"draft/{self._job_id}/{ctx.service_id}")
        if rule is not None:
            if rule.action == chaos.ACTION_DELAY:
                chaos.sleep_for(rule)  # slow draft: the round still lands
            elif rule.action == chaos.ACTION_DROP:
                # draft stalled THIS round: skip speculation, decode plain
                return self._decode_round(model, spec, cache, slots, ctx)
            else:
                self._degrade_spec("chaos-injected draft fault")
                return self._decode_round(model, spec, cache, slots, ctx)
        # growth + COW barriers for the whole k+1-row write burst
        bt = self._alloc.block_tokens
        part: List[int] = []
        for i in cand:
            s = slots[i]
            if s is None:
                continue  # preempted making room for an earlier burst
            ok = self._make_capacity(slots, i, s.position + k)
            if ok:
                for bx in range(s.position // bt,
                                (s.position + k) // bt + 1):
                    copies = self._alloc.ensure_writable(i, bx * bt)
                    if copies is None:
                        ok = False
                        break
                    if copies:
                        cache = self._apply_copies(model, cache, copies)
            if ok:
                part.append(i)
        part = [i for i in part if slots[i] is not None]
        rest = set(i for i, s in enumerate(slots)
                   if s is not None and s.pending_from is None
                   and i not in part)
        if not part:
            return self._decode_round(model, spec, cache, slots, ctx,
                                      only=rest)
        # the propose steps below write garbage into the draft-ring rows
        # of every slot sitting this round out — invalidate them so their
        # next participation re-prefills the draft cache
        for i in rest:
            slots[i].draft_ready = False
        n = len(slots)
        try:
            for i in part:
                s = slots[i]
                if s.draft_ready:
                    continue
                # lazy draft prefill of the slot's committed history
                # (positions 0..position; the first propose step rewrites
                # row `position` with identical K/V)
                _, self._draft_cache = self._draft.prefill(
                    self._draft_cache, i, list(s.prompt) + list(s.tokens))
                s.draft_ready = True
            cur = np.zeros(n, np.int32)
            cpos = np.zeros(n, np.int32)
            for i in part:
                cur[i] = slots[i].last_id
                cpos[i] = slots[i].position
            dsamp = self._sampling_arrays(slots, ROLE_DRAFT, only=part)
            fused = getattr(self._draft, "decode_steps_sampled", None)
            if callable(fused):
                # fused proposal: all k chained steps in ONE program —
                # the k-call loop below pays dispatch + a host sync per
                # step just to feed the sampled token back in
                d_j, q_j, self._draft_cache = fused(
                    self._draft_cache, cur, cpos, k, dsamp)
                d_ids = np.asarray(d_j, np.int32)        # (S, k)
                draft_probs = np.asarray(q_j, np.float32)
            else:
                d_ids = np.zeros((n, k), np.int32)
                q_list = []
                for j in range(k):
                    nxt, q, self._draft_cache = \
                        self._draft.decode_step_sampled(
                            self._draft_cache, cur.copy(), cpos.copy(),
                            dsamp)
                    nxt = np.asarray(nxt, np.int32)
                    d_ids[:, j] = nxt
                    q_list.append(np.asarray(q, np.float32))
                    cur = nxt
                    cpos = cpos + 1
                draft_probs = np.stack(q_list, axis=1)   # (S, k, V_draft)
        except Exception:
            logger.error("draft propose failed in generation worker "
                         "%s:\n%s", ctx.service_id, traceback.format_exc())
            self._degrade_spec("draft propose failed")
            return self._decode_round(model, spec, cache, slots, ctx)
        ids2 = np.zeros((n, k + 1), np.int32)
        pos2 = np.tile(np.arange(k + 1, dtype=np.int32), (n, 1))
        for i in part:
            s = slots[i]
            ids2[i, 0] = s.last_id
            ids2[i, 1:] = d_ids[i]
            pos2[i] = s.position + np.arange(k + 1, dtype=np.int32)
        tables = np.stack([
            self._alloc.table_row(i) if i in part
            else self._alloc.idle_row() for i in range(n)])
        vsamp = self._sampling_arrays(slots, ROLE_TARGET, only=part)
        try:
            acc, toks, cache = model.paged_verify_step(
                cache, ids2, pos2, tables, draft_probs, vsamp)
            acc = np.asarray(acc)
            toks = np.asarray(toks)
        except Exception:
            # the verify forward raised BEFORE returning a new cache, so
            # the resident table is intact — degrade typed (the classic
            # cause is a draft/target vocab mismatch) and finish the
            # round plain for everyone
            logger.error("speculative verify failed in generation worker "
                         "%s:\n%s", ctx.service_id, traceback.format_exc())
            self._degrade_spec(
                "verify step failed (draft/target mismatch?)")
            return self._decode_round(model, spec, cache, slots, ctx)
        now = time.monotonic()
        m = _metrics()
        # lint: unguarded(scheduler thread is the only writer; the stats snapshot reads cross-thread and tolerates a stale round count)
        self._spec_rounds += 1
        m["spec_rounds"].inc()
        for i in part:
            s = slots[i]
            if s is None:
                continue
            rule = chaos.hit(
                chaos.SITE_GENERATE,
                f"{self._job_id}/{ctx.service_id}/slot{i}/"
                f"{s.stream.seq_id}")
            if rule is not None:
                if rule.action == chaos.ACTION_DELAY:
                    chaos.sleep_for(rule)
                elif rule.action == chaos.ACTION_DROP:
                    logger.warning(
                        "chaos: muting generation slot %d (%s)", i,
                        s.stream.seq_id)
                    s.muted = True
                else:
                    s.stream.fail(
                        "chaos-injected mid-stream generation fault")
                    self._evict_slot(slots, i, "error")
                    continue
            if s.stream.cancelled:
                self._evict_slot(slots, i, "cancelled")
                continue
            a = int(acc[i])
            # lint: unguarded(scheduler-thread-only writer, stale reads ok)
            self._spec_proposed += k
            # lint: unguarded(scheduler-thread-only writer, stale reads ok)
            self._spec_accepted += a
            m["spec_proposed"].inc(k)
            m["spec_accepted"].inc(a)
            emit: List[int] = []
            finished, reason = False, None
            for t in toks[i, :a + 1]:
                token = int(t)
                s.position += 1
                s.last_id = token
                s.produced += 1
                s.tokens.append(token)
                emit.append(token)
                self._tokens_emitted += 1
                finished, reason = self._finish_reason(s, spec, token)
                if finished:
                    break
            if s.deadline is not None and now >= s.deadline:
                finished, reason = True, "deadline"
            m["intertoken"].observe(now - s.last_step_t)
            s.last_step_t = now
            m["tokens"].inc(len(emit))
            if s.t0 is not None:
                m["ttft"].observe(now - s.t0)
                s.t0 = None
            if not s.muted:
                s.stream.push(emit, finished=finished, reason=reason)
            if finished:
                self._evict_slot(slots, i, reason)
            else:
                # free any block now holding ONLY rejected-suffix rows;
                # stale rows inside the frontier block are overwritten
                # before attention by the next round's writes
                self._alloc.truncate_to(i, s.position)
        if rest:
            cache = self._decode_round(model, spec, cache, slots, ctx,
                                       only=rest)
        return cache

    @staticmethod
    def _finish_reason(slot: _Slot, spec: GenerationSpec, token: int):
        if spec.eos_token_id is not None and token == spec.eos_token_id:
            return True, "eos"
        if slot.produced >= slot.max_tokens:
            return True, "max_tokens"
        if slot.position + 1 >= spec.max_context:
            return True, "context"
        return False, None

    def _evict_slot(self, slots: List[Optional[_Slot]], i: int,
                    reason: str) -> None:
        slots[i] = None
        if self._alloc is not None:
            self._alloc.close_slot(i)
        _metrics()["evictions"].labels(reason or "unknown").inc()

    # kept for compatibility with the ring-path call sites/tests
    def _evict(self, slots: List[Optional[_Slot]], i: int,
               reason: str) -> None:
        self._evict_slot(slots, i, reason)

    @staticmethod
    def _parse_query(query):
        if not isinstance(query, dict):
            raise GenerationRequestError(
                "generation query must be an object with 'prompt_ids'")
        prompt = query.get("prompt_ids")
        if (not isinstance(prompt, (list, tuple)) or not prompt
                or not all(isinstance(t, int) and t >= 0 for t in prompt)):
            raise GenerationRequestError(
                "'prompt_ids' must be a non-empty list of non-negative "
                "token ids")
        cap = max(int(config.GEN_MAX_TOKENS), 1)
        raw = query.get("max_tokens", cap)
        try:
            max_tokens = int(raw)
        except (TypeError, ValueError):
            raise GenerationRequestError(
                f"max_tokens={raw!r} is not an integer") from None
        if max_tokens < 1:
            raise GenerationRequestError(
                f"max_tokens={max_tokens} must be >= 1")
        max_tokens = min(max_tokens, cap)
        max_duration_s = query.get("max_duration_s")
        if max_duration_s is not None:
            try:
                max_duration_s = float(max_duration_s)
            except (TypeError, ValueError):
                raise GenerationRequestError(
                    "max_duration_s must be a number") from None
        raw_t = query.get("temperature", 0.0)
        try:
            temperature = float(raw_t if raw_t is not None else 0.0)
        except (TypeError, ValueError):
            raise GenerationRequestError(
                f"temperature={raw_t!r} is not a number") from None
        if temperature < 0.0:
            raise GenerationRequestError(
                f"temperature={temperature} must be >= 0")
        raw_k = query.get("top_k", 0)
        try:
            top_k = int(raw_k if raw_k is not None else 0)
        except (TypeError, ValueError):
            raise GenerationRequestError(
                f"top_k={raw_k!r} is not an integer") from None
        if top_k < 0:
            raise GenerationRequestError(f"top_k={top_k} must be >= 0")
        raw_p = query.get("top_p", 1.0)
        try:
            top_p = float(raw_p if raw_p is not None else 1.0)
        except (TypeError, ValueError):
            raise GenerationRequestError(
                f"top_p={raw_p!r} is not a number") from None
        if not 0.0 < top_p <= 1.0:
            raise GenerationRequestError(
                f"top_p={top_p} must be in (0, 1]")
        raw_s = query.get("seed")
        if raw_s is not None:
            try:
                seed = int(raw_s)
            except (TypeError, ValueError):
                raise GenerationRequestError(
                    f"seed={raw_s!r} is not an integer") from None
            if seed < 0:
                raise GenerationRequestError(f"seed={seed} must be >= 0")
        elif temperature > 0.0:
            # derive one NOW and keep it for the stream's whole life —
            # a preemption resume must replay the identical sequence
            seed = uuid.uuid4().int & 0x7FFFFFFF
        else:
            seed = 0
        if temperature > 0.0 and not bool(config.GEN_SAMPLING):
            raise GenerationRequestError(
                "sampled generation is disabled on this deployment "
                "(RAFIKI_GEN_SAMPLING=0)")
        return (list(prompt), max_tokens, max_duration_s,
                (temperature, top_k, top_p, seed))

    @staticmethod
    def _parse_resume(query) -> List[int]:
        """The committed-token history of a door-side RESUME request
        ([] for a fresh stream). The worker prefills prompt+history
        under the stream's pinned seed; the position-keyed counter RNG
        (PR 18 invariant) then continues the sampled sequence
        token-identically from where the dead replica stopped."""
        raw = query.get("resume_tokens") if isinstance(query, dict) \
            else None
        if raw is None:
            return []
        if (not isinstance(raw, (list, tuple))
                or not all(isinstance(t, int) and t >= 0 for t in raw)):
            raise GenerationRequestError(
                "'resume_tokens' must be a list of non-negative token "
                "ids")
        return list(raw)

    # -- observability -------------------------------------------------------

    def _occupancy(self, slots, max_slots: int) -> float:
        """The autoscaler's saturation signal: under the paged layout the
        binding resource is POOL BLOCKS, not slots — a few long streams
        can exhaust the pool with the slot table half empty, and block
        occupancy is what predicts the next admission stalling."""
        if self._alloc is not None:
            return self._alloc.used_blocks() / self._alloc.pool_blocks
        busy = sum(1 for s in slots if s is not None)
        return busy / max_slots

    def _mirror_alloc(self, service_id: str, m) -> None:
        """Mirror the allocator's cumulative counters into the PR-6
        registry by delta (one site per loop — host-side bookkeeping has
        no natural increment hook) and refresh the pool gauges."""
        if self._alloc is None:
            return
        st = self._alloc.stats()
        last = self._last_alloc_stats
        for key, counter in (("prefix_hits", "prefix_hits"),
                             ("prefix_misses", "prefix_misses"),
                             ("prefix_hit_tokens", "prefix_tokens"),
                             ("cow_copies", "cow"),
                             ("cache_evictions", "prefix_evictions")):
            delta = st[key] - last.get(key, 0)
            if delta > 0:
                m[counter].inc(delta)
        self._last_alloc_stats = st
        m["kv_used"].labels(service_id).set(st["used_blocks"])
        m["kv_live"].labels(service_id).set(st["live_blocks"])
        m["kv_pool"].labels(service_id).set(st["pool_blocks"])

    def _stats_row(self, service_id: str, slots, max_slots: int) -> None:
        """Fold the slot picture into the shared SERVING_STATS row (the
        /healthz + fleet-health + stats-relay surface every PR already
        reads); the 'queries' counter stays the admitted-request count.
        ``gen_tokens`` advances every decode round, so the process-mode
        stats relay (report_stats dedupes on an unchanged row) keeps
        pushing — and the admin keeps re-recording the occupancy ring —
        for as long as the table is actually decoding, even when
        occupancy itself sits pinned at full. Under the paged layout the
        row also carries the block-pool picture (the admin relay then
        records BLOCK occupancy into the autoscaler ring) and the prefix
        hit counters fleet health aggregates per job."""
        busy = sum(1 for s in slots if s is not None)
        with _stats_lock:
            s = SERVING_STATS.setdefault(
                service_id, {"batches": 0, "queries": 0})
            s["gen_slots_busy"] = busy
            s["gen_slots_max"] = max_slots
            # resident + preempted-stashed: what a drain must wait out
            # (admin/services.py _drain_one) before destroying
            s["gen_resident_streams"] = busy + len(
                getattr(self, "_pending", ()))
            s["gen_tokens"] = getattr(self, "_tokens_emitted", 0)
            s["gen_job"] = self._job_id
            s["gen_spec_on"] = bool(getattr(self, "_spec_on", False))
            s["gen_spec_proposed"] = getattr(self, "_spec_proposed", 0)
            s["gen_spec_accepted"] = getattr(self, "_spec_accepted", 0)
            s["gen_spec_rounds"] = getattr(self, "_spec_rounds", 0)
            deg = getattr(self, "_spec_degraded", None)
            if deg:
                s["gen_spec_degraded"] = deg
            if self._alloc is not None:
                st = self._last_alloc_stats or self._alloc.stats()
                s["gen_kv_blocks_used"] = st["used_blocks"]
                s["gen_kv_blocks_live"] = st["live_blocks"]
                s["gen_kv_pool_blocks"] = st["pool_blocks"]
                s["gen_kv_block_tokens"] = st["block_tokens"]
                s["gen_prefix_hits"] = st["prefix_hits"]
                s["gen_prefix_misses"] = st["prefix_misses"]
                s["gen_prefix_hit_tokens"] = st["prefix_hit_tokens"]
