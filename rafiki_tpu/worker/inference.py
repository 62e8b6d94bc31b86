"""Inference worker: serves one trained trial's model with continuous
batching.

Parity with the reference's InferenceWorker (reference
rafiki/worker/inference.py:19-105): register in the job's worker set, load the
trial's model (class bytes from the store + persisted params), serve batches.

TPU-native difference: instead of popping <=32 queries from a Redis list every
0.25 s (reference inference.py:43-65, config.py:17-18), the worker blocks on a
condition-variable queue and wakes the instant a query lands, draining up to
``PREDICT_MAX_BATCH_SIZE`` of whatever has queued — batches fill under load
because queries accumulate during the previous dispatch, and a single query
at idle is served immediately (PREDICT_BATCH_DEADLINE_MS defaults to 0).
"""

from __future__ import annotations

import logging
import os
import threading
import time
import traceback
from typing import Any, Dict, List, Optional

import numpy as np

from rafiki_tpu import config
from rafiki_tpu.cache.queue import Broker
from rafiki_tpu.db.database import Database
from rafiki_tpu.utils import chaos, trace
from rafiki_tpu.parallel.mesh import set_device_grant
from rafiki_tpu.placement.manager import ServiceContext
from rafiki_tpu.sdk.model import load_model_class
from rafiki_tpu.sdk.params import load_params

logger = logging.getLogger(__name__)

# Per-service serving counters (batches served, queries served), updated by
# the worker loop so benchmarks and ops can compute *batch occupancy* —
# mean queries/batch, the signal that continuous batching actually
# coalesces under concurrent load instead of serving singletons. Overload
# control adds the queue picture: `queue_depth` (gauge), `expired`
# (queries dropped past their request deadline) and `shed` (queries the
# bounded queue refused) — surfaced through GET /fleet/health.
_stats_lock = threading.Lock()
SERVING_STATS: Dict[str, Dict[str, int]] = {}


def _metrics():
    """Registry mirrors of the serving counters (utils/metrics.py) —
    lazily created so import stays cheap; the JSON SERVING_STATS keeps
    its shape and the mirrors increment at the same sites."""
    global _M
    if _M is None:
        from rafiki_tpu.utils.metrics import REGISTRY
        from rafiki_tpu.utils.trace import phase_histogram

        _M = {
            "batches": REGISTRY.counter(
                "rafiki_serving_batches_total",
                "batches served by inference workers in this process"),
            "queries": REGISTRY.counter(
                "rafiki_serving_queries_total",
                "queries served by inference workers in this process"),
            "batch_size": REGISTRY.histogram(
                "rafiki_serving_batch_size",
                "queries per served batch (continuous-batching occupancy)",
                buckets=[1, 2, 4, 8, 16, 32, 64, 128, 256]),
            "depth": REGISTRY.gauge(
                "rafiki_queue_depth",
                "current worker-queue depth", ("service",)),
            "phase": phase_histogram(),
        }
    return _M


_M = None


def serving_stats() -> Dict[str, Dict[str, int]]:
    """Snapshot of {service_id: {batches, queries, ...}} for this process."""
    with _stats_lock:
        return {k: dict(v) for k, v in SERVING_STATS.items()}


def _record_batch(service_id: str, n_queries: int) -> None:
    with _stats_lock:
        s = SERVING_STATS.setdefault(service_id, {"batches": 0, "queries": 0})
        s["batches"] += 1
        s["queries"] += n_queries
    m = _metrics()
    m["batches"].inc()
    m["queries"].inc(n_queries)
    m["batch_size"].observe(n_queries)


def _record_queue(service_id: str, queue) -> None:
    """Fold the queue's overload counters into this service's stats row
    (queues without a stats() signal just contribute nothing). Only the
    keys a queue actually reports are written: condvar queues carry the
    depth/expired/rejected overload picture, shm queues carry the wire
    picture (undecodable frames, ring occupancy high-water)."""
    stats_fn = getattr(queue, "stats", None)
    if not callable(stats_fn):
        return
    try:
        q = stats_fn()
    # lint: absorb(queue stats are best-effort telemetry)
    except Exception:
        return
    with _stats_lock:
        s = SERVING_STATS.setdefault(service_id, {"batches": 0, "queries": 0})
        for src, dst in (("depth", "queue_depth"), ("expired", "expired"),
                         ("rejected", "shed"), ("wire_errors", "wire_errors"),
                         ("ring_used_bytes_hw", "ring_used_bytes_hw")):
            if src in q:
                s[dst] = int(q[src])
    if "depth" in q:
        m = _metrics()
        m["depth"].labels(service_id).set(int(q["depth"]))
        # autoscaler-grade ring series (~1 s resolution): the depth the
        # worker observed at this tick. One ring PER service — a shared
        # ring would interleave last-write-wins samples from every queue
        # in the process into one meaningless sawtooth.
        from rafiki_tpu.utils.metrics import REGISTRY

        REGISTRY.ring(f"queue_depth:{service_id}").record(int(q["depth"]))


def _resolve_batch(futures: List[Any], predictions: Any,
                   service_id: str) -> None:
    """Resolve one served batch, delivering every computed prediction
    and failing the rest with a TYPED error when a buggy model returns
    fewer predictions than queries. Every future MUST resolve here: the
    shm plane's per-frame response flushes only once a frame's futures
    have all resolved, so a silently-dropped future would strand its
    whole request — computed results included — until the SLO."""
    n = len(predictions)
    for fut, pred in zip(futures, predictions):
        fut.set_result(pred)
    if n < len(futures):
        logger.error(
            "model in worker %s returned %d predictions for %d queries",
            service_id, n, len(futures))
        err = RuntimeError(
            f"model returned {n} predictions for {len(futures)} queries")
        for fut in futures[n:]:
            fut.set_error(err)


class _BatchAssembler:
    """Single-copy batch assembly for ndarray queries.

    The old path handed the model a Python list, so every predict paid a
    per-query ``np.asarray`` shuffle over N separate objects. When a
    batch's queries are homogeneous ndarrays (the shape the binary wire
    delivers: zero-copy frombuffer rows), they are now copied ONCE into a
    contiguous batch — into a reused preallocated buffer when the queue
    declares ``reusable_batch_ok`` (shm queues: responses serialize
    inside the resolve loop, so the buffer is dead by the next take;
    in-process futures hand objects across threads, so those batches get
    a fresh ``np.stack`` instead of a buffer a pathological input-echoing
    model could alias). Heterogeneous/non-array batches pass through
    untouched."""

    def __init__(self) -> None:
        self._buf: Optional[np.ndarray] = None

    def assemble(self, queries: List[Any], reusable: bool):
        from rafiki_tpu.cache import wire

        if not wire.stackable(queries):  # the one shared predicate
            return queries
        first = queries[0]
        n = len(queries)
        if not reusable:
            return wire.stack_batch(queries)
        buf = self._buf
        if (buf is None or buf.shape[1:] != first.shape
                or buf.dtype != first.dtype or buf.shape[0] < n):
            cap = max(int(config.PREDICT_MAX_BATCH_SIZE), n)
            buf = self._buf = np.empty((cap,) + first.shape, first.dtype)
        for i, q in enumerate(queries):
            buf[i] = q
        return buf[:n]


class _FusedEnsembleModel:
    """The fused-ensemble serving unit (budget ``ENSEMBLE_FUSED``): every
    best trial's model co-resident in this worker, answering each batch as
    one unit. When the group shares a compiled predict
    (``BaseModel.ensemble_stack``), the whole ensemble is ONE vmapped
    device dispatch; otherwise the models answer sequentially in-process.
    Either way this worker resolves futures with the FINAL (cross-trial
    ensembled) predictions, so the predictor treats the group as a single
    replica set."""

    def __init__(self, models, task: str):
        from rafiki_tpu.predictor.ensemble import ensemble_predictions

        self._models = models
        self._task = task
        self._ensemble = ensemble_predictions
        # sandboxed serving children (sdk/sandbox.py SandboxedModelServer)
        # are separate processes — co-residency is impossible there, so the
        # hook may be absent entirely
        stack_fn = getattr(models[0], "ensemble_stack", None)
        self._stacked = None
        if callable(stack_fn):
            try:
                self._stacked = stack_fn(models)
            except Exception:
                # the hook is TEMPLATE code (ADVICE r5): a raising hook —
                # OOM stacking N param trees, a template bug — must
                # degrade to sequential serving, not fail worker startup
                # and roll back the whole inference job
                logger.exception(
                    "fused worker: ensemble_stack hook raised; falling "
                    "back to sequential in-process serving of %d models",
                    len(models))
        if self._stacked is None and len(models) > 1:
            logger.info(
                "fused worker: trials do not share a compiled predict; "
                "serving %d models sequentially in-process", len(models))

    @property
    def fused_dispatch(self) -> bool:
        return self._stacked is not None

    @property
    def dead(self) -> bool:
        # sandbox-mode members expose .dead when their child process died
        # and will never recover; the worker loop reads this to exit and
        # let placement's restart policy replace the whole replica
        return any(getattr(m, "dead", False) for m in self._models)

    def predict(self, queries):
        if self._stacked is not None:
            per_model = self._stacked.predict_all(queries)
        else:
            per_model = [m.predict(queries) for m in self._models]
        return [
            self._ensemble([pm[i] for pm in per_model], self._task)
            for i in range(len(queries))
        ]

    def warm_up(self):
        if self._stacked is not None and hasattr(self._stacked, "warm_up"):
            self._stacked.warm_up()
        else:
            for m in self._models:
                m.warm_up()

    def destroy(self):
        for m in self._models:
            try:
                m.destroy()
            except Exception:
                logger.exception("destroy failed for a fused-ensemble model")


class InferenceWorker:
    def __init__(
        self,
        inference_job_id: str,
        trial_id: str,
        db: Database,
        broker: Broker,
        report_stats=None,
        report_interval_s: float = 5.0,
        trial_ids: Optional[list] = None,
    ):
        """``report_stats({"service_id", "batches", "queries"})`` relays
        cumulative serving counters to a remote admin (process placement —
        the admin cannot see this process's SERVING_STATS). Pushed from a
        background thread every ``report_interval_s`` (and once at ready
        and at exit) so counters stay fresh even when traffic pauses;
        best-effort."""
        self._job_id = inference_job_id
        self._trial_id = trial_id
        #: fused-ensemble mode (budget ENSEMBLE_FUSED): ALL the job's best
        #: trials co-served by this one worker; ``trial_id`` is then the
        #: group's top trial (the bookkeeping row)
        self._trial_ids = list(trial_ids) if trial_ids else [trial_id]
        self._db = db
        self._broker = broker
        self._report_stats = report_stats
        self._report_interval_s = report_interval_s
        #: set-up span -> seconds, for the set-up line (warmup.log_setup);
        #: the worker's own thread writes and reads it in start()
        self._setup_s: Dict[str, float] = {}

    def _stats_reporter(self, ctx: ServiceContext) -> None:
        """Push cumulative counters on a fixed cadence, independent of
        traffic (a throttle piggybacked on the serve loop would leave the
        last batches before a pause unreported). First push immediately —
        benches/dashboards read stats right after the first predicts."""
        last = None

        def push():
            nonlocal last
            s = serving_stats().get(ctx.service_id,
                                    {"batches": 0, "queries": 0})
            # warm-state fields from this boot's warm-up report ride on
            # every row (static after boot — cheap) so fleet health can
            # show per-replica warm + last-boot compile seconds
            from rafiki_tpu.worker.warmup import stats_row_fields

            s = {**s, **stats_row_fields(ctx.service_id)}
            if s == last:
                return
            try:
                self._report_stats({"service_id": ctx.service_id, **s})
                # only remember a SUCCESSFUL push — a transient failure
                # must retry on the next tick even with unchanged counters
                last = s
            except Exception:
                logger.warning("stats report failed (continuing)",
                               exc_info=True)

        while True:
            push()
            if ctx.stop_event.wait(self._report_interval_s):
                push()  # final snapshot: batches since the last tick
                return

    def _load_model(self, service_id: str):
        """The served model, in a ``setup.load`` span (attribute ``bytes``:
        the parameters' files): the artifact read, ``load_params``, the
        template's import and ``load_parameters``. JAX places arrays
        asynchronously, so ``load_parameters`` may return before the bytes
        are on the device; that tail lands in the next span that waits."""
        paths = [(self._db.get_trial(tid) or {}).get("params_file_path")
                 for tid in self._trial_ids]
        size = sum(os.path.getsize(p) for p in paths
                   if p and os.path.isfile(p))
        with trace.span("setup.load", bytes=size) as span:
            model = self._load_models(service_id)
        self._setup_s["setup.load"] = span.seconds  # lint: unguarded(the worker's own thread, before the stats reporter starts)
        return model

    def _load_models(self, service_id: str):
        if len(self._trial_ids) > 1:
            models = [
                self._load_one(tid, f"{service_id}-m{i}")
                for i, tid in enumerate(self._trial_ids)
            ]
            inf = self._db.get_inference_job(self._job_id)
            assert inf is not None
            train_job = self._db.get_train_job(inf["train_job_id"])
            assert train_job is not None
            return _FusedEnsembleModel(models, train_job["task"])
        return self._load_one(self._trial_id, service_id)

    def _load_one(self, trial_id: str, service_id: str):
        trial = self._db.get_trial(trial_id)
        assert trial is not None, f"no trial {trial_id}"
        model_row = self._db.get_model(trial["model_id"])
        assert model_row is not None
        from rafiki_tpu.sdk.deps import activate_prefix, ensure_dependencies
        from rafiki_tpu.sdk.sandbox import sandbox_enabled

        prefix = ensure_dependencies(model_row.get("dependencies"))
        from rafiki_tpu.sdk.artifact import read_artifact

        # verified read: a truncated/bit-rotten params file raises the
        # typed ArtifactCorruptError here — the deploy path surfaces it as
        # a clean ServiceDeploymentError instead of a msgpack traceback
        params_bytes = read_artifact(trial["params_file_path"])
        if sandbox_enabled():
            # serving isolation parity with the trial path: the uploaded
            # template answers batches from a locked-down child; this
            # trusted worker keeps the store, the params file, and the
            # data plane (sdk/sandbox.py SandboxedModelServer — warm-up
            # happens child-side before the ready frame)
            from rafiki_tpu.sdk.sandbox import (
                SandboxedModelServer,
                make_jail,
            )

            return SandboxedModelServer(
                model_row["model_file_bytes"], model_row["model_class"],
                trial["knobs"], params_bytes,
                make_jail(config.WORKDIR, f"serve-{service_id}"),
                extra_pythonpath=prefix,
            )
        activate_prefix(prefix)
        clazz = load_model_class(
            model_row["model_file_bytes"], model_row["model_class"]
        )
        model = clazz(**trial["knobs"])
        model.load_parameters(load_params(params_bytes))
        return model

    def start(self, ctx: ServiceContext) -> None:
        set_device_grant(ctx.chips)
        since = time.time()
        model = None
        assembler = _BatchAssembler()
        queue = self._broker.register_worker(self._job_id, ctx.service_id)
        try:
            model = self._load_model(ctx.service_id)
            # compile every serving batch bucket before accepting
            # traffic — a mid-traffic XLA compile is a multi-second
            # p99 spike (the reference never compiled anything, but
            # paid 0.25 s polls instead). run_warmup enables the
            # persistent compile cache, times the compiles, and records
            # this boot's cold/warm verdict; it runs BEFORE ctx.ready()
            # so a still-compiling replica stays DEPLOYING/unroutable.
            from rafiki_tpu.worker.warmup import log_setup, run_warmup

            self._setup_s["setup.warmup"] = run_warmup(  # lint: unguarded(the worker's own thread, before the stats reporter starts)
                ctx.service_id, self._job_id,
                [("warm_up", model.warm_up)])["compile_s"]
            ctx.ready()  # model + params loaded: startup succeeded
            log_setup(ctx.service_id, self._job_id, self._setup_s, since)
            if self._report_stats is not None:
                threading.Thread(
                    target=self._stats_reporter, args=(ctx,),
                    name="stats-reporter", daemon=True).start()
            while not ctx.stopping:
                batch = queue.take_batch(
                    max_size=config.PREDICT_MAX_BATCH_SIZE,
                    deadline_s=config.PREDICT_BATCH_DEADLINE_MS / 1000.0,
                )
                if batch is None:
                    # the data plane was closed under us (broker teardown,
                    # owner gone): serving is over — exit instead of
                    # spinning on a queue that answers instantly
                    logger.info("query queue closed; worker %s exiting",
                                ctx.service_id)
                    break
                if not batch:
                    # still publish the queue gauge/counters on idle ticks
                    # and on takes that only dropped expired entries
                    _record_queue(ctx.service_id, queue)
                    continue
                _record_batch(ctx.service_id, len(batch))
                _record_queue(ctx.service_id, queue)
                futures = [f for f, _ in batch]
                # trace sinks for sampled requests in this batch — the
                # in-process future carries the door's RequestTrace, the
                # shm handle its frame responder; both accept
                # add_span(name, start, end). Deduplicated: a request's
                # entries share one sink.
                sinks = []
                for f in futures:
                    sink = getattr(f, "trace", None)
                    if sink is not None and all(s is not sink
                                                for s in sinks):
                        sinks.append(sink)
                t_asm = time.monotonic()
                queries = assembler.assemble(
                    [q for _, q in batch],
                    reusable=getattr(queue, "reusable_batch_ok", False))
                t_fwd = time.monotonic()
                for sink in sinks:
                    sink.add_span("batch_assembly", t_asm, t_fwd)
                rule = chaos.hit(chaos.SITE_WORKER,
                                 f"{self._job_id}/{ctx.service_id}")
                if rule is not None:
                    # deterministic overload drills (RAFIKI_CHAOS
                    # site=worker): slow replica / silent stall / failing
                    # replica, injected between take and predict so queue
                    # bounding and admission shed upstream are what a test
                    # observes
                    if rule.action == chaos.ACTION_DELAY:
                        chaos.sleep_for(rule)
                    elif rule.action == chaos.ACTION_DROP:
                        # swallow the batch: futures never resolve — the
                        # predictor's SLO/hedging machinery owns recovery
                        logger.warning(
                            "chaos: worker %s stalling a %d-query batch",
                            ctx.service_id, len(batch))
                        continue
                    else:  # ACTION_ERROR
                        err = RuntimeError("chaos-injected worker error")
                        for fut in futures:
                            fut.set_error(err)
                        continue
                try:
                    predictions = model.predict(queries)
                    t_done = time.monotonic()
                    m = _metrics()
                    m["phase"].labels("batch_assembly").observe(
                        t_fwd - t_asm)
                    m["phase"].labels("model_forward").observe(
                        t_done - t_fwd)
                    for sink in sinks:
                        sink.add_span("model_forward", t_fwd, t_done)
                    _resolve_batch(futures, predictions, ctx.service_id)
                except Exception as e:
                    logger.error(
                        "predict failed in worker %s:\n%s",
                        ctx.service_id,
                        traceback.format_exc(),
                    )
                    for fut in futures:
                        fut.set_error(e)
                    if getattr(model, "dead", False):
                        # a dead sandbox child never recovers — exit so
                        # placement's restart policy replaces this worker
                        # instead of serving errors forever
                        raise
        finally:
            self._broker.unregister_worker(self._job_id, ctx.service_id)
            if model is not None:
                model.destroy()
            set_device_grant(None)
