"""Cross-process serving broker over the native shared-memory queue.

`InProcessBroker` (cache/queue.py) hands queries between threads of one
process. This broker carries the same traffic between *processes* on one
host through rafiki_tpu.native.shm_queue — the native replacement for the
reference's Redis data plane (reference rafiki/cache/cache.py: every query
rpush'd over TCP to a Redis server and polled at 0.25 s). Queue names are
deterministic in (prefix, job, worker), so a worker process can attach with
`ShmWorkerQueue.attach(...)` knowing only its ids.

Wire format (cache/wire.py): one **binary frame per request** each way —
``{"ids": [...], "qarr": <stacked ndarray> | "queries": [...],
"deadline": ...}`` on the per-worker query queue, ``{"ids": [...],
"results": [...], "errors": {...}}`` on the per-job response queue —
ndarrays as raw bytes, decoded worker-side with zero-copy
``np.frombuffer`` views. The float→text→float tax of the old per-query
JSON messages was the serving path's dominant CPU cost, not the model.
Receivers *sniff* every popped message (binary magic vs JSON),
so legacy per-query JSON peers interoperate; responses echo the format
their query frame arrived in, and ``RAFIKI_WIRE_BINARY=0`` forces JSON
framing on the submit side for a version-mismatched fleet. A listener
thread on the predictor side resolves `QueryFuture`s by id.

Select with RAFIKI_BROKER=shm (Admin falls back to the in-process broker if
the native library can't be built).
"""

from __future__ import annotations

import hashlib
import json
import logging
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from rafiki_tpu.cache import wire
from rafiki_tpu.cache.queue import (
    Broker,
    FrameTooLargeError,
    QueryFuture,
    QueueFullError,
)
from rafiki_tpu.native.shm_queue import (
    ShmMessageQueue,
    ShmQueueClosed,
    available,
)
from rafiki_tpu.utils import chaos
from rafiki_tpu.utils.jsonutil import json_default

logger = logging.getLogger(__name__)


def _qname(prefix: str, *parts: str) -> str:
    digest = hashlib.sha256("|".join(parts).encode()).hexdigest()[:24]
    return f"/{prefix}-{digest}"


def _encode_query_frame(ids: List[str], queries: List[Any],
                        deadline: Optional[float],
                        trace_meta: Optional[Dict[str, Any]] = None) -> bytes:
    """One frame for a whole submit_many request (binary unless
    RAFIKI_WIRE_BINARY=0). Homogeneous ndarray queries stack into ONE
    contiguous array (single header entry, single memcpy) — the common
    shape for the binary HTTP door, whose ``list(arr)`` rows share dtype
    and shape by construction. ``trace_meta`` (a sampled request's wire
    context + submit timestamp) rides the v2 frame header; under JSON
    framing it rides the message's ``_trace`` key instead so the
    kill-switch path keeps its traces too."""
    msg: Dict[str, Any] = {"ids": ids}
    if deadline is not None:
        msg["deadline"] = deadline
    # qarr only when the frame is actually binary: under JSON framing
    # (RAFIKI_WIRE_BINARY=0) a stacked array would serialize as nested
    # lists, which the receiving decoder must not confuse with rows
    stacked = wire.stack_batch(queries) if wire.binary_enabled() else None
    if stacked is not None:
        msg["qarr"] = stacked
    else:
        msg["queries"] = queries
        if trace_meta is not None and not wire.binary_enabled():
            msg["_trace"] = trace_meta
    return wire.dumps(msg, trace=trace_meta)


def _decode_query_frame(raw: bytes) -> Tuple[
        List[Tuple[str, Any, Optional[float]]], bool,
        Optional[Dict[str, Any]]]:
    """One popped query message -> ([(qid, query, deadline), ...],
    arrived_binary, trace_meta_or_None). Accepts the batched binary
    frame (v1 or trace-carrying v2), the batched JSON frame
    (RAFIKI_WIRE_BINARY=0 submitter), and the legacy per-query
    ``{"id", "query"}`` message. Raises WireFormatError on garbage."""
    binary = wire.is_frame(raw)
    msg, meta = wire.decode_any_meta(raw)
    if not isinstance(msg, dict):
        raise wire.WireFormatError("query frame is not an object")
    trace_meta = meta.get("trace") or msg.get("_trace")
    if not isinstance(trace_meta, dict):
        trace_meta = None
    try:
        # the frame decoded, but every field is still untrusted input:
        # ids must be strings (dict keys downstream) and the deadline a
        # number (compared against time.monotonic()) — anything else is
        # a malformed frame, absorbed by the caller, never a crash in
        # the worker serve loop
        deadline = msg.get("deadline")
        if deadline is not None:
            deadline = float(deadline)
        if "id" in msg:  # legacy single-query message
            if not isinstance(msg["id"], str):
                raise wire.WireFormatError("query id is not a string")
            return [(msg["id"], msg["query"], deadline)], binary, trace_meta
        ids = msg["ids"]
        if (not isinstance(ids, list)
                or not all(isinstance(i, str) for i in ids)):
            raise wire.WireFormatError("ids must be a list of strings")
        if "qarr" in msg:
            qarr = msg["qarr"]
            if isinstance(qarr, np.ndarray) and qarr.ndim >= 1:
                queries: List[Any] = list(qarr)  # zero-copy row views
            elif isinstance(qarr, list):
                # a JSON-framed qarr (old sender under the kill-switch)
                # arrives as nested lists: rows stay rows
                queries = qarr
            else:
                raise wire.WireFormatError("qarr is not a batch")
        else:
            queries = msg["queries"]
        if not isinstance(queries, (list, np.ndarray)) \
                or len(queries) != len(ids):
            raise wire.WireFormatError("queries/ids length mismatch")
        return ([(qid, q, deadline) for qid, q in zip(ids, queries)],
                binary, trace_meta)
    except (KeyError, TypeError, ValueError) as e:
        if isinstance(e, wire.WireFormatError):
            raise
        raise wire.WireFormatError(f"malformed query frame: {e}") from e


class _FrameResponder:
    """Accumulates one popped query frame's responses and flushes them as
    ONE message in the same wire format the frame arrived in (binary
    frame -> batched binary response; legacy JSON -> legacy per-id JSON
    messages, so an old-version listener still resolves them).

    Flush fires when every id has resolved — the worker loop always
    resolves a batch completely (results, a shared error, or take-time
    expiry), so a response frame is written exactly once per request.
    Transport backpressure (full response ring, broker mid-close) must
    not crash the serving worker loop — the predictor's SLO timeout
    covers a dropped response frame.

    For a SAMPLED request (the query frame carried trace metadata) the
    responder also collects worker-side spans — queue_wait, codec_decode,
    batch_assembly, model_forward — as ``[name, offset_s, duration_s]``
    triples relative to the submitter's ``ts`` and ships them home in the
    response frame's metadata, where the broker listener grafts them onto
    the door's span tree. Legacy JSON responses drop the spans (old
    listeners can't read them) but still serve the request."""

    __slots__ = ("_rq", "_ids", "_binary", "_lock", "_out",
                 "trace_meta", "_spans")

    def __init__(self, rq: ShmMessageQueue, ids: List[str], binary: bool,
                 trace_meta: Optional[Dict[str, Any]] = None):
        self._rq = rq
        self._ids = ids
        self._binary = binary
        self._lock = threading.Lock()
        self._out: Dict[str, Tuple[str, Any]] = {}
        self.trace_meta = trace_meta if (
            isinstance(trace_meta, dict) and trace_meta.get("s")) else None
        self._spans: List[List[Any]] = []

    @property
    def anchor(self) -> Optional[float]:
        """The submitter's monotonic submit timestamp (same host, same
        CLOCK_MONOTONIC) — worker span offsets are measured against it."""
        if self.trace_meta is None:
            return None
        try:
            return float(self.trace_meta.get("ts"))
        except (TypeError, ValueError):
            return None

    def add_span(self, name: str, start: float, end: float) -> None:
        """Record one worker-side span (monotonic interval). No-op for
        unsampled frames so the hot path pays one None check."""
        anchor = self.anchor
        if anchor is None:
            return
        with self._lock:
            self._spans.append(
                [name, round(start - anchor, 6),
                 round(max(end - start, 0.0), 6)])

    def resolve(self, qid: str, kind: str, value: Any) -> None:
        with self._lock:
            if qid in self._out:
                return  # first resolution wins (double-set guard)
            self._out[qid] = (kind, value)
            if len(self._out) < len(self._ids):
                return
        self._flush()

    def _flush(self) -> None:
        try:
            if self._binary:
                results: List[Any] = []
                errors: Dict[str, str] = {}
                for i, qid in enumerate(self._ids):
                    kind, value = self._out[qid]
                    if kind == "error":
                        errors[str(i)] = value
                        results.append(None)
                    else:
                        results.append(value)
                msg: Dict[str, Any] = {"ids": self._ids, "results": results}
                if errors:
                    msg["errors"] = errors
                trace_out = None
                if self.trace_meta is not None:
                    with self._lock:
                        trace_out = {"id": self.trace_meta.get("id"),
                                     "spans": list(self._spans)}
                self._rq.push(wire.encode(msg, trace=trace_out))
            else:
                # legacy listener compatibility: per-id JSON messages
                for qid in self._ids:
                    kind, value = self._out[qid]
                    payload = ({"id": qid, "error": value}
                               if kind == "error"
                               else {"id": qid, "result": value})
                    self._rq.push(json.dumps(
                        payload, default=json_default).encode())
        except Exception:
            logger.exception("dropping response frame for %d queries",
                             len(self._ids))


class ShmWorkerQueue:
    """Worker-side view: drains query batches, pushes responses.

    Duck-types cache.queue.WorkerQueue's `take_batch` but yields
    (ResponseHandle, query) pairs — the handle writes into its frame's
    shared :class:`_FrameResponder` instead of resolving an in-process
    future.
    """

    #: batches from this queue serialize at resolve time (the responder
    #: encodes inside the worker's resolve loop, before the next take),
    #: so the worker may assemble them into a REUSED batch buffer
    #: (worker/inference.py) without aliasing hazards
    reusable_batch_ok = True

    class ResponseHandle:
        __slots__ = ("_responder", "_id")

        def __init__(self, responder: _FrameResponder, qid: str):
            self._responder = responder
            self._id = qid

        @property
        def trace(self):
            """Span sink for the worker loop (duck-typed with
            QueryFuture.trace): the frame's responder when this query's
            request is sampled, else None."""
            r = self._responder
            return r if r.trace_meta is not None else None

        def set_result(self, value: Any) -> None:
            self._responder.resolve(self._id, "result", value)

        def set_error(self, error: BaseException) -> None:
            self._responder.resolve(self._id, "error", str(error))

    def __init__(self, query_q: ShmMessageQueue, response_q: ShmMessageQueue):
        self._qq = query_q
        self._rq = response_q
        self._wire_errors = 0  # undecodable frames dropped (see stats())
        from rafiki_tpu.utils.metrics import REGISTRY

        self._m_wire_errors = REGISTRY.counter(
            "rafiki_wire_errors_total",
            "undecodable wire frames dropped (query + response sides)")
        self._m_expired = REGISTRY.counter(
            "rafiki_queue_expired_total",
            "queries dropped past their deadline in a worker queue")

    @classmethod
    def attach(cls, prefix: str, inference_job_id: str,
               worker_id: str) -> "ShmWorkerQueue":
        """Open the queues from another process by deterministic name."""
        qq = ShmMessageQueue(
            _qname(prefix, "q", inference_job_id, worker_id), create=False)
        rq = ShmMessageQueue(
            _qname(prefix, "r", inference_job_id), create=False)
        return cls(qq, rq)

    def stats(self) -> Dict[str, int]:
        """Wire + ring picture folded into SERVING_STATS: undecodable
        frames seen, and the ring occupancy high-water mark as seen from
        THIS handle's pushes (RAFIKI_SHM_RING_BYTES headroom). A worker
        process only pushes the RESPONSE ring, so that is the mark it
        can honestly report; the query ring's mark lives owner-side
        (_SubmitProxy.stats, surfaced via the predictor /healthz)."""
        qr, rr = self._qq.stats(), self._rq.stats()
        return {
            "wire_errors": self._wire_errors,
            "ring_used_bytes": qr["used_bytes"],
            "ring_used_bytes_hw": max(qr["used_bytes_hw"],
                                      rr["used_bytes_hw"]),
        }

    def _pop_decoded(self, timeout_s: float) -> Optional[Tuple[
            List[Tuple[str, Any, Optional[float]]], bool,
            Optional[Dict[str, Any]], float, float]]:
        """Pop + decode one query frame, absorbing corruption: a frame
        that fails to decode is counted and reported as an EMPTY frame
        (([], ...)) — the submitter's SLO timeout covers its queries; the
        worker loop must keep serving. None means ring timeout. The last
        two elements are the monotonic instant decoding started and its
        duration — the codec_decode span of a sampled frame, at its REAL
        interval (queue_wait ends where it begins)."""
        raw = self._qq.pop(timeout_s=timeout_s)
        if raw is None:
            return None
        rule = chaos.hit(chaos.SITE_WIRE, self._qq.name)
        if rule is not None and rule.action == chaos.ACTION_CORRUPT:
            raw = chaos.corrupt_bytes(raw, rule)
        t_pop = time.monotonic()
        try:
            entries, binary, trace_meta = _decode_query_frame(raw)
            return (entries, binary, trace_meta, t_pop,
                    time.monotonic() - t_pop)
        except wire.WireFormatError as e:
            self._wire_errors += 1
            self._m_wire_errors.inc()
            logger.error("dropping undecodable query frame on %s: %s",
                         self._qq.name, e)
            return [], False, None, t_pop, 0.0

    def take_batch(self, max_size: int, deadline_s: float,
                   wait_timeout_s: float = 0.5
                   ) -> Optional[List[Tuple["ShmWorkerQueue.ResponseHandle",
                                            Any]]]:
        """[] on timeout; None once the queue is closed-and-drained (same
        contract as cache.queue.WorkerQueue.take_batch — a closed ring
        answers instantly, and callers polling it as if it were a timeout
        would spin hot). One popped frame carries a whole request's
        queries; draining stops once ``max_size`` is reached (a single
        frame larger than ``max_size`` is still served whole — requests
        are admitted as units)."""
        try:
            first = self._pop_decoded(timeout_s=wait_timeout_s)
        except ShmQueueClosed:
            return None
        if first is None:
            return []
        groups = [first]
        n_entries = len(first[0])
        t0 = time.monotonic()
        while n_entries < max_size:
            # drain whatever is ALREADY in the ring without waiting — same
            # contract as WorkerQueue.take_batch (the deadline is only an
            # optional coalescing wait, and at the default 0 the already-
            # queued frames must still come out as one batch)
            try:
                nxt = self._pop_decoded(timeout_s=0)
                if nxt is None:
                    remaining = deadline_s - (time.monotonic() - t0)
                    if remaining <= 0:
                        break
                    nxt = self._pop_decoded(timeout_s=remaining)
            except ShmQueueClosed:
                break
            if nxt is None:
                break
            groups.append(nxt)
            n_entries += len(nxt[0])
        out: List[Tuple[ShmWorkerQueue.ResponseHandle, Any]] = []
        now = time.monotonic()
        for entries, binary, trace_meta, t_pop, decode_s in groups:
            if not entries:
                continue  # corrupt frame already absorbed
            responder = _FrameResponder(
                self._rq, [qid for qid, _, _ in entries], binary,
                trace_meta=trace_meta)
            anchor = responder.anchor
            if anchor is not None:
                # worker-side half of the sampled request's span tree:
                # queue_wait (submit ts -> this frame's pop, both on the
                # host's shared CLOCK_MONOTONIC) then the decode at its
                # actual interval — the phases tile, they don't overlap
                responder.add_span("queue_wait", anchor, t_pop)
                responder.add_span("codec_decode", t_pop, t_pop + decode_s)
            for qid, query, deadline in entries:
                handle = self.ResponseHandle(responder, qid)
                # overload control: a query whose request deadline passed
                # while it sat in the ring is dropped here, not served —
                # CLOCK_MONOTONIC is system-wide on one host, so the
                # submitter's absolute deadline is directly comparable in
                # this worker process
                if deadline is not None and now >= deadline:
                    self._m_expired.inc()
                    handle.set_error(TimeoutError(
                        "query expired in the shm queue before dispatch"))
                    continue
                out.append((handle, query))
        return out

    def close(self) -> None:
        self._qq.close()


class _SubmitProxy:
    """Predictor-side view of one worker's query queue.

    Overload control happens owner-side (this process): the broker counts
    each worker's *outstanding* queries (submitted, not yet answered), so
    ``depth()`` gives the hedge-suppression/admission load signal and
    ``submit_many`` enforces RAFIKI_PREDICT_QUEUE_DEPTH with the same
    QueueFullError contract as the in-process queue — the shm ring itself
    cannot be asked its message count from here."""

    def __init__(self, broker: "ShmBroker", job_id: str, worker_id: str,
                 query_q: ShmMessageQueue):
        self._broker = broker
        self._job_id = job_id
        self._worker_id = worker_id
        self._qq = query_q

    def depth(self) -> int:
        return self._broker._outstanding_count(self._job_id, self._worker_id)

    def stats(self) -> Dict[str, int]:
        """Submit-side queue picture: outstanding depth plus the query
        ring's occupancy high-water mark (is RAFIKI_SHM_RING_BYTES sized
        for the batched frames actually flowing?)."""
        ring = self._qq.stats()
        return {
            "depth": self.depth(),
            "ring_capacity": ring["capacity"],
            "ring_used_bytes": ring["used_bytes"],
            "ring_used_bytes_hw": ring["used_bytes_hw"],
        }

    def submit(self, query: Any,
               deadline: Optional[float] = None) -> QueryFuture:
        return self.submit_many([query], deadline=deadline)[0]

    def submit_many(self, queries: List[Any],
                    deadline: Optional[float] = None,
                    trace=None) -> List[QueryFuture]:
        """One wire frame per request (cache/wire.py): the whole request
        travels as a single binary message and lands as one worker batch
        by construction. The depth-cap check is all-or-nothing per
        request, like WorkerQueue.submit_many, and the reservation is
        atomic with it (released on response, push failure, or expiry).

        Push failures keep the shed contract typed: a full ring maps to
        the retryable :class:`QueueFullError`, an oversized frame to the
        permanent :class:`FrameTooLargeError` (413 at the doors — split
        the request or raise RAFIKI_SHM_RING_BYTES).

        A sampled request's ``trace`` context crosses the ring in the
        frame metadata; the worker's spans come home in the response
        frame and the broker listener grafts them onto ``trace``."""
        self._broker._reserve_capacity(
            self._job_id, self._worker_id, len(queries))
        ids = [uuid.uuid4().hex for _ in queries]
        futs = [QueryFuture() for _ in queries]
        trace_meta = None
        if trace is not None:
            trace.mark_submitted()
            trace_meta = {**trace.ctx.to_wire(), "ts": trace.t_submit}
        for qid, fut in zip(ids, futs):
            # absolute monotonic deadline; comparable worker-side because
            # both processes share the host's CLOCK_MONOTONIC
            self._broker._register_pending(
                self._job_id, self._worker_id, qid, fut, deadline,
                trace=trace)
        try:
            self._qq.push(_encode_query_frame(ids, queries, deadline,
                                              trace_meta=trace_meta))
        except BaseException as e:
            for qid in ids:
                self._broker._pop_pending(self._job_id, qid)
            if isinstance(e, TimeoutError):
                # ring full past the push timeout: transient backpressure,
                # same retryable shed signal as a full bounded queue
                raise QueueFullError(
                    f"shm ring to worker {self._worker_id} full "
                    f"(ring {self._qq.stats()['used_bytes']}B used)") from e
            if isinstance(e, ValueError):
                raise FrameTooLargeError(
                    f"request frame for {len(queries)} queries exceeds the "
                    f"shm ring capacity (RAFIKI_SHM_RING_BYTES) — split "
                    f"the request or raise the ring size: {e}") from e
            for fut in futs:
                fut.set_error(e)
        return futs


class ShmBroker(Broker):
    """Owner (predictor-process) side of the shm data plane."""

    def __init__(self, prefix: Optional[str] = None,
                 queue_capacity: Optional[int] = None):
        if not available():
            raise RuntimeError("native shmqueue unavailable")
        self.prefix = prefix or f"rafiki{uuid.uuid4().hex[:8]}"
        self._capacity = queue_capacity  # None -> RAFIKI_SHM_RING_BYTES
        self._lock = threading.Lock()
        self._query_qs: Dict[str, Dict[str, ShmMessageQueue]] = {}
        self._response_qs: Dict[str, ShmMessageQueue] = {}
        # qid -> (future, worker_id, expiry_ts): worker_id feeds the
        # per-worker outstanding counts (the depth signal), expiry_ts lets
        # a never-answered query (worker crashed mid-batch) be pruned
        # instead of counting against the depth cap forever
        self._pending: Dict[str, Dict[str, Tuple[QueryFuture, str, float]]] = {}
        self._outstanding: Dict[Tuple[str, str], int] = {}
        self._listeners: Dict[str, threading.Thread] = {}
        self._graveyard: List[ShmMessageQueue] = []
        self.wire_errors = 0  # undecodable response frames dropped
        self._closed = False
        # registry mirrors of the owner-side shed/expiry counters — the
        # shm twin of WorkerQueue's (utils/metrics.py)
        from rafiki_tpu.utils.metrics import REGISTRY

        self._m_rejected = REGISTRY.counter(
            "rafiki_queue_rejected_total",
            "queries refused by a bounded worker queue's depth cap")
        self._m_expired = REGISTRY.counter(
            "rafiki_queue_expired_total",
            "queries dropped past their deadline in a worker queue")

    # -- Broker interface --------------------------------------------------

    def register_worker(self, inference_job_id: str,
                        worker_id: str) -> ShmWorkerQueue:
        with self._lock:
            rq = self._ensure_response_queue(inference_job_id)
            qq = ShmMessageQueue(
                _qname(self.prefix, "q", inference_job_id, worker_id),
                capacity=self._capacity, create=True)
            self._query_qs.setdefault(inference_job_id, {})[worker_id] = qq
        # a same-process worker thread shares the owner's handles; a separate
        # worker process uses ShmWorkerQueue.attach() instead
        return ShmWorkerQueue(qq, rq)

    def unregister_worker(self, inference_job_id: str, worker_id: str) -> None:
        with self._lock:
            qq = self._query_qs.get(inference_job_id, {}).pop(worker_id, None)
            if qq is not None:
                # close only — a _SubmitProxy snapshot taken before this call
                # may still hold the handle, and destroy() munmaps under it
                # (closed pushes fail cleanly; unmapped ones segfault).
                # The segment is reclaimed at broker close().
                qq.close()
                self._graveyard.append(qq)

    def get_worker_queues(self, inference_job_id: str) -> Dict[str, Any]:
        with self._lock:
            return {
                wid: _SubmitProxy(self, inference_job_id, wid, qq)
                for wid, qq in self._query_qs.get(inference_job_id, {}).items()
            }

    # -- response plumbing -------------------------------------------------

    def _ensure_response_queue(  # guarded-by: _lock
            self, job_id: str) -> ShmMessageQueue:
        """Caller holds self._lock."""
        if job_id not in self._response_qs:
            rq = ShmMessageQueue(
                _qname(self.prefix, "r", job_id),
                capacity=self._capacity, create=True)
            self._response_qs[job_id] = rq
            self._pending[job_id] = {}
            t = threading.Thread(
                target=self._listen, args=(job_id, rq),
                name=f"shm-listener-{job_id[:8]}", daemon=True)
            self._listeners[job_id] = t
            t.start()
        return self._response_qs[job_id]

    def _register_pending(self, job_id: str, worker_id: str, qid: str,
                          fut: QueryFuture,
                          deadline: Optional[float], trace=None) -> None:
        """Record one reserved query's future (the outstanding count was
        already taken by _reserve_capacity — registering must NOT count
        again). Expiry gets a grace period past the request deadline (or
        the configured SLO): a query the worker never answers must stop
        counting against its depth eventually, or one crash would pin the
        replica "full" forever."""
        from rafiki_tpu import config

        expiry = (deadline if deadline is not None
                  else time.monotonic() + config.PREDICT_TIMEOUT_S) + 30.0
        with self._lock:
            self._pending.setdefault(job_id, {})[qid] = (
                fut, worker_id, expiry, trace)

    def _pop_pending(self, job_id: str, qid: str) -> Optional[QueryFuture]:
        fut, _ = self._pop_pending_traced(job_id, qid)
        return fut

    def _pop_pending_traced(self, job_id: str, qid: str):
        """(future, trace) for one pending id — (None, None) if unknown."""
        with self._lock:
            entry = self._pending.get(job_id, {}).pop(qid, None)
            if entry is None:
                return None, None
            fut, worker_id, _, trace = entry
            self._dec_outstanding_locked(job_id, worker_id)
            return fut, trace

    def _dec_outstanding_locked(self, job_id: str,  # guarded-by: _lock
                                worker_id: str) -> None:
        key = (job_id, worker_id)
        n = self._outstanding.get(key, 0) - 1
        if n <= 0:
            self._outstanding.pop(key, None)
        else:
            self._outstanding[key] = n

    def _prune_expired_locked(self, job_id: str, worker_id: str) -> None:
        """Drop never-answered entries past their expiry (worker crashed
        mid-batch). Must run on EVERY read of the count, not just on
        submits: the admission layer sheds on depth() *before* any submit
        happens, so a prune that only ran at submit time could never fire
        again once phantoms pushed the estimated wait over every
        deadline — a permanent-429 lockout."""
        now = time.monotonic()
        job_pending = self._pending.get(job_id, {})
        for qid, (_, wid, expiry, _trace) in list(job_pending.items()):
            if wid == worker_id and now >= expiry:
                job_pending.pop(qid)
                self._dec_outstanding_locked(job_id, wid)
                self._m_expired.inc()

    def _outstanding_count(self, job_id: str, worker_id: str) -> int:
        with self._lock:
            if self._outstanding.get((job_id, worker_id), 0) > 0:
                self._prune_expired_locked(job_id, worker_id)
            return self._outstanding.get((job_id, worker_id), 0)

    def _reserve_capacity(self, job_id: str, worker_id: str, n: int) -> None:
        """Atomically check RAFIKI_PREDICT_QUEUE_DEPTH and claim ``n``
        outstanding slots (one lock hold: a check-then-register split
        would let concurrent submitters jointly overshoot the cap). The
        claim is released by _pop_pending (response/push-failure) or by
        expiry pruning."""
        from rafiki_tpu import config

        cap = int(config.PREDICT_QUEUE_DEPTH)
        key = (job_id, worker_id)
        with self._lock:
            if self._outstanding.get(key, 0) > 0:
                self._prune_expired_locked(job_id, worker_id)
            queued = self._outstanding.get(key, 0)
            if cap > 0 and queued + n > cap:
                self._m_rejected.inc(n)
                raise QueueFullError(
                    f"shm worker {worker_id} full "
                    f"({queued}/{cap} outstanding)")
            self._outstanding[key] = queued + n

    def _resolve_response(self, job_id: str, msg: Any,
                          meta: Optional[Dict[str, Any]] = None) -> None:
        """Resolve futures for one decoded response message — batched
        frame ({"ids", "results", "errors"}) or legacy per-id JSON.
        ``meta`` may carry the worker's trace spans for a sampled
        request; they are grafted onto the request's RequestTrace before
        its futures resolve (the door reads the tree after gather)."""
        if not isinstance(msg, dict):
            raise wire.WireFormatError("response frame is not an object")
        trace_meta = (meta or {}).get("trace")
        wire_spans = (trace_meta.get("spans")
                      if isinstance(trace_meta, dict) else None)
        if "id" in msg:  # legacy single-response message
            if not isinstance(msg["id"], str):
                raise wire.WireFormatError("response id is not a string")
            fut = self._pop_pending(job_id, msg["id"])
            if fut is None:
                return
            if "error" in msg:
                fut.set_error(RuntimeError(msg["error"]))
            else:
                fut.set_result(msg.get("result"))
            return
        # validate EVERY field before touching pending state: a frame
        # that decodes but is malformed (results not a sequence,
        # non-string ids, errors not a dict) must raise the one typed
        # error _listen absorbs — the listener thread outlives any bad
        # message, or the whole job's futures strand forever
        try:
            ids = msg["ids"]
            results = msg["results"]
            errors = msg.get("errors") or {}
            if (not isinstance(ids, list)
                    or not all(isinstance(i, str) for i in ids)
                    or not isinstance(results, list)
                    or not isinstance(errors, dict)
                    or len(results) != len(ids)):
                raise wire.WireFormatError("malformed response frame")
        except (KeyError, TypeError) as e:
            raise wire.WireFormatError(
                f"malformed response frame: {e}") from e
        for i, qid in enumerate(ids):
            fut, trace = self._pop_pending_traced(job_id, qid)
            if fut is None:
                continue
            if wire_spans is not None and trace is not None:
                # one graft per response frame (a request's futures share
                # the trace; spans are offsets against ITS submit time)
                trace.add_wire_spans(wire_spans, anchor=trace.t_submit)
                wire_spans = None
            err = errors.get(str(i))
            if err is not None:
                fut.set_error(RuntimeError(err))
            else:
                fut.set_result(results[i])

    def _listen(self, job_id: str, rq: ShmMessageQueue) -> None:
        while not self._closed:
            try:
                raw = rq.pop(timeout_s=0.5)
            except ShmQueueClosed:
                break
            except Exception:
                logger.exception("response listener %s died", job_id)
                break
            if raw is None:
                continue
            rule = chaos.hit(chaos.SITE_WIRE, rq.name)
            if rule is not None and rule.action == chaos.ACTION_CORRUPT:
                raw = chaos.corrupt_bytes(raw, rule)
            try:
                body, meta = wire.decode_any_meta(raw)
                self._resolve_response(job_id, body, meta)
            except wire.WireFormatError as e:
                # a corrupt response frame is absorbed here: its pending
                # futures keep waiting and resolve with the request's own
                # (typed) TimeoutError at the SLO — the listener thread
                # must outlive any single bad message
                self._count_wire_error()
                logger.error("dropping undecodable response frame on %s: %s",
                             job_id, e)
                continue

    def _count_wire_error(self) -> None:
        """One undecodable frame. Under the lock: each job's listener is
        its own thread, and sibling listeners doing a bare ``+=`` on the
        shared counter lose updates against each other (found by the
        concurrency lint, CONC302)."""
        with self._lock:
            self.wire_errors += 1
        from rafiki_tpu.utils.metrics import REGISTRY

        REGISTRY.counter(
            "rafiki_wire_errors_total",
            "undecodable wire frames dropped (query + response "
            "sides)").inc()

    # -- lifecycle ---------------------------------------------------------

    def close(self) -> None:
        self._closed = True
        with self._lock:
            jobs = list(self._query_qs)
            for job_id in jobs:
                for qq in self._query_qs[job_id].values():
                    qq.close()
                    qq.destroy()
            self._query_qs.clear()
            for qq in self._graveyard:
                qq.destroy()
            self._graveyard.clear()
            for rq in self._response_qs.values():
                rq.close()
        for t in self._listeners.values():
            t.join(timeout=2.0)
        with self._lock:
            for rq in self._response_qs.values():
                rq.destroy()
            self._response_qs.clear()
            for pend in self._pending.values():
                for fut, _, _, _ in pend.values():
                    fut.set_error(RuntimeError("broker closed"))
            self._pending.clear()
            self._outstanding.clear()


class ShmBrokerClient:
    """Worker-process side of the shm data plane.

    The owner (`ShmBroker`, in the admin/predictor process) creates the
    segments when a serving service is placed; a worker process built by
    ProcessPlacementManager attaches to them by deterministic name —
    the analogue of the reference's workers connecting to the Redis address
    passed in their container env (reference rafiki/cache/cache.py:21,
    services_manager env plumbing). `register_worker` therefore *attaches*
    (with retry, the owner may still be creating) and `unregister_worker`
    detaches without closing: segment lifecycle belongs to the owner, so a
    crashed-and-restarted worker can re-attach and resume serving.
    """

    def __init__(self, prefix: str, attach_timeout_s: float = 10.0):
        self.prefix = prefix
        self._attach_timeout_s = attach_timeout_s
        self._queues: Dict[Tuple[str, str], ShmWorkerQueue] = {}

    def register_worker(self, inference_job_id: str,
                        worker_id: str) -> ShmWorkerQueue:
        deadline = time.monotonic() + self._attach_timeout_s
        while True:
            try:
                wq = ShmWorkerQueue.attach(
                    self.prefix, inference_job_id, worker_id)
                break
            except Exception:
                if time.monotonic() >= deadline:
                    raise
                time.sleep(0.05)
        self._queues[(inference_job_id, worker_id)] = wq
        return wq

    def unregister_worker(self, inference_job_id: str, worker_id: str) -> None:
        wq = self._queues.pop((inference_job_id, worker_id), None)
        if wq is not None:
            # detach only (munmap, no shm_unlink — we are not the owner);
            # do NOT close: the shared closed flag would kill the segment
            # for the owner and for any restarted worker
            wq._qq.destroy()
            wq._rq.destroy()

    def get_worker_queues(self, inference_job_id: str) -> Dict[str, Any]:
        raise NotImplementedError(
            "worker-side broker client cannot enumerate queues; the "
            "predictor runs in the owner process")


def make_broker() -> Broker:
    """RAFIKI_BROKER=shm -> native cross-process broker (with fallback);
    anything else -> in-process condition-variable broker."""
    import os

    from rafiki_tpu.cache.queue import InProcessBroker

    if os.environ.get("RAFIKI_BROKER") == "shm":
        try:
            return ShmBroker()
        except Exception:
            logger.warning("shm broker unavailable; using in-process broker")
    return InProcessBroker()
