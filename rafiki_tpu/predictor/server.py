"""Per-inference-job predictor HTTP listener.

The reference published each inference job's predictor on its own host
port (/root/reference/rafiki/admin/services_manager.py:379-384,
predictor/app.py:23-31), so serving traffic never shared a socket with
the control plane. Parity here: when ``RAFIKI_PREDICTOR_PORTS=1`` (or
``predictor_ports=True`` on the Admin), ServicesManager binds one of
these per deployed inference job; POST /predict traffic then bypasses
the admin server entirely. The admin /predict/<app> route keeps working
either way — this is an extra front door, not a move.

Auth parity with the admin route: the same stateless JWTs
(utils/auth.py) are accepted, so a client token works on both doors;
set ``auth=False`` for a trusted-network deployment (the reference's
predictor app had no auth at all).
"""

from __future__ import annotations

import json
import logging
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional

from rafiki_tpu.cache.queue import FrameTooLargeError, QueueFullError
from rafiki_tpu.predictor.admission import (
    AdmissionController,
    DeadlineUnmeetableError,
    ServerOverloadedError,
    retry_after_headers,
)
from rafiki_tpu.utils.auth import UnauthorizedError, decode_token
from rafiki_tpu.utils.reqfields import LowLatencyHandler

logger = logging.getLogger(__name__)


class _DoorHTTPServer(ThreadingHTTPServer):
    """The door's listener. ``socketserver``'s listen queue holds 5
    connections; a deployment's closed-loop callers reconnect together
    (64 of them on one door in the benchmark's fullest cell), and a
    connection past the queue is reset before its request is read
    (``ConnectionResetError`` at the client, never seen by admission
    control). The queue is as long as the callers a door is meant to
    carry; the kernel caps it at ``net.core.somaxconn``."""

    request_queue_size = 256


def _generate_cost(prompt_len: int, max_tokens: int) -> int:
    """Admission cost of one /generate request, in the units of the
    resource that actually gates the generation worker: KV-pool BLOCKS
    under the paged allocator (ceil((prompt + decode budget) / block
    tokens) — a long prompt holds pages even while producing few tokens,
    so prompt length must charge), or the decode budget itself under the
    legacy contiguous ring (every slot costs max_context there, so only
    residency TIME differentiates requests)."""
    from rafiki_tpu import config as _config

    if bool(_config.GEN_KV_PAGED):
        bt = max(int(_config.GEN_KV_BLOCK_TOKENS), 1)
        return max(-(-(prompt_len + max_tokens) // bt), 1)
    return max(max_tokens, 1)


class PredictorServer:
    """One jsonified POST /predict + GET /healthz listener over one
    Predictor (predictor/predictor.py).

    Overload control (docs/failure-model.md "Overload faults"): every
    predict passes the door's AdmissionController first — a bounded
    in-flight gate plus a deadline-aware estimated-wait check — and worker
    queues underneath are bounded, so excess traffic is shed instantly
    with ``429`` + ``Retry-After`` (backlog: retry later) or ``503`` (no
    capacity) instead of accumulating ThreadingHTTPServer handler threads
    until the host dies."""

    def __init__(self, predictor, app: str, host: str = "127.0.0.1",
                 port: int = 0, auth: bool = True):
        self.predictor = predictor
        self.app = app
        self.host = host
        self.port = port
        self.auth = auth
        # door label feeds the registry (admitted/shed counters + the
        # rafiki_request_seconds histogram the bench reads percentiles
        # from); the JSON stats() in /healthz stay per-door as before
        self.admission = AdmissionController(door=f"predictor:{app}")
        #: epoch seconds of the listener bind — a restarted admin rebinds
        #: an ADOPTED job's door on a fresh port (control-plane recovery),
        #: and a monitor that sees started_at jump knows the door moved
        #: (rather than silently aiming at the dead process's port)
        self.started_at: Optional[float] = None
        self._httpd: Optional[_DoorHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        self._stop_lock = threading.Lock()
        self._stopped = False
        self._draining = False

    def start(self) -> "PredictorServer":
        server = self

        class Handler(LowLatencyHandler):
            protocol_version = "HTTP/1.1"
            timeout = 300

            def do_GET(self):
                path = self.path.split("?", 1)[0].rstrip("/")
                if path == "/healthz":
                    server._healthz(self)
                elif path == "/metrics":
                    server._metrics(self)
                else:
                    server._respond(self, 404, {"error": "no such route"})

            def do_POST(self):
                path = self.path.split("?", 1)[0].rstrip("/")
                if path == "/generate":
                    server._generate(self)
                else:
                    server._predict(self)

        self._httpd = _DoorHTTPServer((self.host, self.port), Handler)
        self.port = self._httpd.server_address[1]
        self.started_at = time.time()
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name=f"predictor-{self.app}")
        self._thread.start()
        logger.info("predictor for %s listening on %s:%d",
                    self.app, self.host, self.port)
        return self

    def stop(self, drain_timeout_s: Optional[float] = None) -> None:
        """Graceful drain: stop accepting, let in-flight handlers finish
        (bounded by ``drain_timeout_s``, default RAFIKI_PREDICT_DRAIN_S),
        then close the socket and join the serve thread. Idempotent — the
        teardown paths (operator stop, all-replicas-dead refresh, deploy
        rollback) may race onto a double stop."""
        with self._stop_lock:
            if self._stopped:
                return
            self._stopped = True
            self._draining = True
        httpd, thread = self._httpd, self._thread
        if httpd is None:
            return
        from rafiki_tpu import config

        if drain_timeout_s is None:
            drain_timeout_s = float(config.PREDICT_DRAIN_S)
        httpd.shutdown()  # stop the accept loop; handler threads live on
        deadline = time.monotonic() + max(drain_timeout_s, 0.0)
        while (self.admission.inflight > 0
               and time.monotonic() < deadline):
            time.sleep(0.01)
        leftover = self.admission.inflight
        if leftover:
            logger.warning(
                "predictor %s closed with %d handler(s) still in flight "
                "after the %.1fs drain window", self.app, leftover,
                drain_timeout_s)
        httpd.server_close()
        if thread is not None:
            thread.join(timeout=5.0)
        self._draining = False

    # -- handling ----------------------------------------------------------

    def _healthz(self, handler: BaseHTTPRequestHandler) -> None:
        """Liveness + load: ``status`` is ``degraded`` when the serving
        plane is live-but-empty (zero worker queues registered — the door
        answers but no replica can), which the fleet-health monitor must
        be able to tell apart from healthy. Also carries the overload
        picture: queue depths, admission counters, hedge suppression."""
        depths: Dict[str, int] = {}
        depth_fn = getattr(self.predictor, "queue_depths", None)
        if callable(depth_fn):
            try:
                depths = depth_fn()
            except Exception:
                logger.exception("healthz queue-depth probe failed")
        overload_fn = getattr(self.predictor, "overload_stats", None)
        status = "ok"
        if self._draining:
            status = "draining"
        elif callable(depth_fn) and not depths:
            status = "degraded"
        payload: Dict[str, Any] = {
            "app": self.app,
            "status": status,
            "started_at": self.started_at,
            "workers": len(depths),
            "queue_depths": depths,
            "admission": self.admission.stats(),
        }
        # per-replica warm state (worker/warmup.py): cold/warm verdict +
        # last-boot compile seconds for every in-process replica in this
        # door's fan-out; replicas in other processes report the same
        # fields through their stats rows (GET /fleet/health workers)
        try:
            from rafiki_tpu.worker.warmup import warmup_stats

            reports = warmup_stats()
            replicas = {
                sid: {"warm": bool(r.get("warm")),
                      "compile_s": r.get("compile_s", 0.0),
                      "cache_hits": r.get("cache_hits", 0)}
                for sid, r in reports.items() if sid in depths}
            if replicas:
                payload["replicas"] = replicas
        # lint: absorb(/healthz must answer even when the warm-state probe crashes)
        except Exception:
            logger.exception("healthz warm-state probe failed")
        if callable(overload_fn):
            payload["overload"] = overload_fn()
        qstats_fn = getattr(self.predictor, "queue_stats", None)
        if callable(qstats_fn):
            try:
                qstats = qstats_fn()
            # lint: absorb(/healthz must answer even when a stats hook crashes)
            except Exception:
                qstats = {}
            if qstats:
                # submit-side ring picture (shm plane): this is where
                # ring_used_bytes_hw — the RAFIKI_SHM_RING_BYTES sizing
                # signal — is actually measured
                payload["queues"] = qstats
        self._respond(handler, 200, payload)

    def _predict(self, handler: BaseHTTPRequestHandler) -> None:
        from rafiki_tpu import config as _config
        from rafiki_tpu.utils.reqfields import read_bounded_body

        # body first: a refusal (404/401) that leaves it unread would
        # desync HTTP/1.1 keep-alive framing for the pooled connection
        raw, berr = read_bounded_body(
            handler, _config.PREDICT_MAX_BODY_MB)
        if berr:
            return self._respond(
                handler, berr[0],
                {"error": f"{berr[1]} (PREDICT_MAX_BODY_MB)"})
        if handler.path.split("?", 1)[0].rstrip("/") != "/predict":
            return self._respond(handler, 404, {"error": "no such route"})
        try:
            if self.auth:
                token = (handler.headers.get("Authorization")
                         or "").removeprefix("Bearer ")
                decode_token(token)  # any authenticated user may predict
            # media types are case-insensitive (RFC 9110); params follow ';'
            ctype = ((handler.headers.get("Content-Type") or "")
                     .split(";")[0].strip().lower())
            body: Dict[str, Any] = {}
            if ctype == "application/x-npy":
                # binary ndarray queries: first axis is the batch. JSON
                # costs ~20 bytes AND a float parse per element — for a
                # 3072-float image query that is the serving door's CPU,
                # not the model. Responses are negotiated separately via
                # Accept: application/x-npy (see below).
                # allow_pickle=False: this door is pre-auth'd but still
                # untrusted input.
                import io

                import numpy as _np

                try:
                    arr = _np.load(io.BytesIO(raw), allow_pickle=False)
                # lint: absorb(hostile npy bytes answer 400, never a 500)
                except Exception:
                    return self._respond(handler, 400, {
                        "error": "bad npy body (expected a valid, "
                                 "non-pickled .npy array)"})
                if arr.ndim < 1 or arr.shape[0] == 0:
                    return self._respond(handler, 400, {
                        "error": "npy body must have a leading batch axis"})
                queries = list(arr)
            else:
                body = json.loads(raw or b"{}")
                if not isinstance(body, dict):
                    return self._respond(handler, 400, {
                        "error": "body must be a JSON object like "
                                 '{"queries": [...]}'})
                queries = body.get("queries")
            if not isinstance(queries, list) or not queries:
                return self._respond(handler, 400, {
                    "error": "body must carry a non-empty 'queries' list"})
            cap = int(_config.PREDICT_QUEUE_DEPTH)
            if cap > 0 and len(queries) > cap:
                # bigger than any queue can EVER hold: a permanent
                # condition — 400, never the retryable 429 (a well-behaved
                # client would retry a 429 forever)
                return self._respond(handler, 400, {
                    "error": f"request carries {len(queries)} queries but "
                             f"the per-worker queue cap is {cap} "
                             "(RAFIKI_PREDICT_QUEUE_DEPTH) — split the "
                             "request"})
            from rafiki_tpu.utils.reqfields import parse_timeout_s

            # binary bodies have no JSON fields — the timeout rides a
            # header there (validated by the same rule either way)
            timeout_value = (handler.headers.get("X-Rafiki-Timeout-S")
                             if ctype == "application/x-npy"
                             else body.get("timeout_s"))
            timeout_s, terr = parse_timeout_s(
                timeout_value, default=_config.PREDICT_TIMEOUT_S,
                label=("X-Rafiki-Timeout-S header"
                       if ctype == "application/x-npy" else "timeout_s"))
            if terr:
                return self._respond(handler, 400, {"error": terr})
            # request tracing (utils/trace.py): honor an incoming
            # X-Rafiki-Trace header's sampling bit or draw against
            # RAFIKI_TRACE_SAMPLE; the unsampled path costs one header
            # read. The context rides queue entries / wire frames / the
            # fleet relay so one sampled request yields one span tree
            # door -> worker -> door.
            from rafiki_tpu.utils import trace as rtrace

            rt = rtrace.start_trace(
                handler.headers.get(rtrace.TRACE_HEADER))
            # admission: claim an in-flight slot AND prove the backlog
            # leaves room to answer inside this request's own deadline —
            # shed here costs microseconds; admitting a doomed request
            # costs model time
            backlog_fn = getattr(self.predictor, "backlog_depth", None)
            backlog = backlog_fn() if callable(backlog_fn) else None
            t_adm = time.monotonic()
            # tenant/cost feed the weighted-fair gate; on this per-job
            # door there is one tenant, so the gate is a no-op — the
            # accounting still shows in /healthz fair_shares. With the
            # prediction cache on, cost is the MISSES-ONLY estimate
            # (predictor/result_cache.py): cache-served queries shed no
            # load onto the worker fleet, so fairness must not charge
            # for them.
            cost_fn = getattr(self.predictor, "admission_cost", None)
            cost = (cost_fn(queries) if callable(cost_fn)
                    else len(queries))
            self.admission.admit(timeout_s, backlog_depth=backlog,
                                 tenant=self.app, cost=cost)
            t0 = time.monotonic()
            if rt is not None:
                rt.add_span("admission_wait", t_adm, t0)
            try:
                # trace kwarg only when sampled: unsampled traffic keeps
                # the pre-trace call shape (duck-typed predictor fakes)
                preds = self.predictor.predict_batch(
                    queries, timeout_s=timeout_s,
                    **({"trace": rt} if rt is not None else {}))
            finally:
                self.admission.release(tenant=self.app)
            e2e_s = time.monotonic() - t0
            self.admission.observe(e2e_s, len(queries))
            # Accept negotiation: a client that asked for
            # application/x-npy gets the predictions back as ONE binary
            # .npy body — the response-leg mirror of the binary request
            # door (JSON float text was the remaining serialization tax
            # on an end-to-end binary predict). Ragged/non-numeric
            # predictions fall back to JSON; the client sniffs the
            # response Content-Type either way.
            trace_headers = ({rtrace.TRACE_HEADER: rt.ctx.to_header()}
                             if rt is not None else None)
            if self._accepts_npy(handler):
                import io

                import numpy as _np

                arr = None
                try:
                    arr = _np.asarray(preds)
                # lint: absorb(un-arrayable predictions take the JSON response path)
                except Exception:
                    pass
                if arr is not None and arr.dtype != object:
                    buf = io.BytesIO()
                    _np.save(buf, arr, allow_pickle=False)
                    t_resp = time.monotonic()
                    self._respond_bytes(
                        handler, 200, buf.getvalue(), "application/x-npy",
                        headers=trace_headers)
                    self._finish_trace(rt, t0, t_resp)
                    return
            t_resp = time.monotonic()
            self._respond(handler, 200, {"data": {"predictions": preds}},
                          headers=trace_headers)
            self._finish_trace(rt, t0, t_resp)
        except UnauthorizedError as e:
            self._respond(handler, 401, {"error": str(e)})
        except json.JSONDecodeError as e:
            self._respond(handler, 400, {"error": f"bad JSON body: {e}"})
        except FrameTooLargeError as e:
            # the request's wire frame can never fit the shm ring: a
            # PERMANENT condition — 413, never the retryable 429
            self._respond(handler, 413, {"error": str(e)})
        except (QueueFullError, DeadlineUnmeetableError) as e:
            # backlog shed: retryable, and Retry-After says when (full
            # worker queues / estimated wait past the client's deadline)
            self._respond(handler, 429, {"error": str(e)},
                          headers=retry_after_headers(e))
        except ServerOverloadedError as e:
            # no capacity: the door's in-flight slots are gone
            self._respond(handler, 503, {"error": str(e)},
                          headers=retry_after_headers(e))
        except TimeoutError as e:
            self._respond(handler, 504, {"error": str(e)})
        except RuntimeError as e:
            # no workers / job being torn down
            self._respond(handler, 503, {"error": str(e)})
        except Exception:
            logger.exception("predict failed on dedicated port for %s",
                             self.app)
            self._respond(handler, 500, {"error": "internal server error"})

    # -- generative serving: the streaming door -----------------------------

    def _generate(self, handler: BaseHTTPRequestHandler) -> None:
        """POST /generate — the token-streaming door
        (docs/serving-generation.md). The request is one JSON object
        ``{"prompt_ids": [...], "max_tokens": N, "timeout_s": T}`` plus
        optional sampling knobs ``temperature`` / ``top_k`` / ``top_p`` /
        ``seed`` (temperature=0 = greedy; a fixed seed makes a sampled
        stream reproducible — worker/generation.py validates them typed);
        the response is chunked transfer, one delta per chunk: JSON
        lines by default, or length-prefixed v3 wire token-delta frames
        when the client sent ``Accept: application/x-rafiki-wire``
        (binary peers OPT IN — an old client never sees the new message
        kind). Admission charges the request its ESTIMATED DECODE COST,
        not 1 — see :func:`_generate_cost`: KV-pool BLOCKS under the
        paged allocator (prompt + budget, the resource that actually
        gates worker admission), ``max_tokens`` under the legacy ring.
        Either way a 256-token stream occupies decode memory ~256 times
        longer than a one-shot predict, and the fairness/backlog books
        must see that.

        Fault contract: every pre-stream refusal is an ordinary status
        code (400/401/429/503/504); once streaming begins the status is
        already 200, so mid-stream faults — an injured worker, a stalled
        decode step past RAFIKI_GEN_STREAM_TIMEOUT_S — end the response
        with a TYPED terminal error frame, never a silent hang."""
        from rafiki_tpu.utils.metrics import REGISTRY
        from rafiki_tpu.worker.generation import GenerationRequestError

        # release() must pair ONLY with a successful admit(): a request
        # refused before (or BY) admission never incremented the
        # in-flight book, and decrementing for it would leak capacity
        # another stream is holding — the cap would over-admit under a
        # shed burst
        held = [False]

        def release():
            if held[0]:
                held[0] = False
                self.admission.release(tenant=self.app)

        from rafiki_tpu import config as _config
        from rafiki_tpu.utils.reqfields import (
            parse_timeout_s,
            read_bounded_body,
        )

        # body first: a 401/413 with the body unread would desync the
        # keep-alive connection (see _predict)
        raw, berr = read_bounded_body(
            handler, _config.PREDICT_MAX_BODY_MB)
        if berr:
            return self._respond(
                handler, berr[0],
                {"error": f"{berr[1]} (PREDICT_MAX_BODY_MB)"})
        try:
            if self.auth:
                token = (handler.headers.get("Authorization")
                         or "").removeprefix("Bearer ")
                decode_token(token)
            body = json.loads(raw or b"{}")
            if not isinstance(body, dict):
                return self._respond(handler, 400, {
                    "error": "body must be a JSON object like "
                             '{"prompt_ids": [...]}'})
            timeout_s, terr = parse_timeout_s(
                body.get("timeout_s"), default=_config.PREDICT_TIMEOUT_S,
                label="timeout_s")
            if terr:
                return self._respond(handler, 400, {"error": terr})
            try:
                max_tokens = int(body.get(
                    "max_tokens", _config.GEN_MAX_TOKENS))
            except (TypeError, ValueError):
                return self._respond(handler, 400, {
                    "error": "max_tokens must be an integer"})
            query = {"prompt_ids": body.get("prompt_ids"),
                     "max_tokens": max_tokens}
            # sampling knobs ride the query to the worker, whose
            # _parse_query owns full validation (typed
            # GenerationRequestError -> 400 below); non-numeric junk is
            # refused HERE so it never costs an admission slot
            for key, cast in (("temperature", float), ("top_k", int),
                              ("top_p", float), ("seed", int)):
                if body.get(key) is not None:
                    try:
                        query[key] = cast(body[key])
                    except (TypeError, ValueError):
                        return self._respond(handler, 400, {
                            "error": f"{key} must be a number"})
            backlog_fn = getattr(self.predictor, "backlog_depth", None)
            backlog = backlog_fn() if callable(backlog_fn) else None
            # cost = the estimated decode footprint, not 1 (see docstring)
            prompt_ids = body.get("prompt_ids")
            prompt_len = (len(prompt_ids)
                          if isinstance(prompt_ids, (list, tuple)) else 0)
            self.admission.admit(timeout_s, backlog_depth=backlog,
                                 tenant=self.app,
                                 cost=_generate_cost(prompt_len,
                                                     max_tokens))
            held[0] = True
            t0 = time.monotonic()
            stream = self.predictor.generate(query, timeout_s=timeout_s)
            t_slot = time.monotonic()
            binary = self._accepts_wire(handler)
            REGISTRY.histogram(
                "rafiki_gen_door_ttft_seconds",
                "wait of a generation request at the streaming door, "
                "from the door's admission until a worker slot admitted "
                "it and handed the stream back: queueing behind busy "
                "slots, then the first prefill chunk (a one-chunk greedy "
                "prompt's first token). rafiki_gen_ttft_seconds starts "
                "at the slot's admission, inside this").observe(t_slot - t0)
            n_tokens = self._stream_deltas(handler, stream, binary)
            done = time.monotonic()
            # the wait model's unit is a token's SERVICE time: the wait
            # for a slot (and a cold replica's compilation) is not in it
            self.admission.observe(done - t0, max(n_tokens, 1),
                                   service_s=done - t_slot)
        except UnauthorizedError as e:
            self._respond(handler, 401, {"error": str(e)})
        except json.JSONDecodeError as e:
            self._respond(handler, 400, {"error": f"bad JSON body: {e}"})
        except GenerationRequestError as e:
            self._respond(handler, 400, {"error": str(e)})
        except (QueueFullError, DeadlineUnmeetableError) as e:
            if isinstance(e, QueueFullError):
                # whole-fleet-full: every replica's bounded queue
                # refused the new stream AFTER door admission — book the
                # shed so the admission metrics see it (the classifier
                # door's semantics, mirrored)
                self.admission.note_backend_shed()
            self._respond(handler, 429, {"error": str(e)},
                          headers=retry_after_headers(e))
        except ServerOverloadedError as e:
            self._respond(handler, 503, {"error": str(e)},
                          headers=retry_after_headers(e))
        except TimeoutError as e:
            # no slot admitted the request inside its own deadline
            self._respond(handler, 504, {"error": str(e)})
        except RuntimeError as e:
            self._respond(handler, 503, {"error": str(e)})
        except Exception:
            logger.exception("generate failed on dedicated port for %s",
                             self.app)
            self._respond(handler, 500, {"error": "internal server error"})
        finally:
            release()

    def _stream_deltas(self, handler, stream, binary: bool) -> int:
        """Pump one TokenStream into a chunked HTTP response; returns the
        token count served. Runs AFTER the 200 status line, so every
        failure mode in here must end the stream with a terminal frame
        (and cancel the worker-side slot), never an exception that slams
        the socket shut mid-chunk without a typed goodbye."""
        from rafiki_tpu import config as _config
        from rafiki_tpu.cache import wire
        from rafiki_tpu.cache.queue import GenerationError

        handler.send_response(200)
        handler.send_header(
            "Content-Type",
            wire.CONTENT_TYPE if binary else "application/x-ndjson")
        handler.send_header("Transfer-Encoding", "chunked")
        handler.send_header("Cache-Control", "no-store")
        # one stream per connection: clients drop the socket after the
        # terminal delta, so offering keep-alive only produces a noisy
        # reset in the server log when they do
        handler.send_header("Connection", "close")
        handler.close_connection = True
        handler.end_headers()

        def chunk(payload: bytes) -> bool:
            try:
                handler.wfile.write(
                    ("%x\r\n" % len(payload)).encode() + payload + b"\r\n")
                handler.wfile.flush()
                return True
            # lint: absorb(client gone mid-stream: status already sent; cancel frees the slot)
            except (BrokenPipeError, ConnectionResetError, OSError):
                stream.cancel()
                return False

        def emit(delta) -> bool:
            if binary:
                frame = wire.encode_token_delta(
                    stream.seq_id, delta.tokens, finished=delta.finished,
                    reason=delta.reason, error=delta.error)
                return chunk(len(frame).to_bytes(4, "little") + frame)
            return chunk(json.dumps(delta.to_json()).encode() + b"\n")

        stall_s = max(float(_config.GEN_STREAM_TIMEOUT_S), 0.1)
        served = 0
        from rafiki_tpu.cache.queue import TokenDelta

        # the pump waits one stall window per delta; the request's OVERALL
        # deadline is enforced worker-side (max_duration_s -> eviction
        # with reason "deadline"), so a live-but-slow stream is never cut
        # by the door while tokens keep arriving
        while True:
            try:
                delta = stream.next_delta(timeout=stall_s)
            except StopIteration:
                break
            # lint: absorb(mid-stream at 200: the typed terminal frame IS the error path)
            except TimeoutError:
                # the stalled-decode drill: the worker went mute on this
                # sequence — typed terminal frame, then tell the slot
                # scheduler to evict it
                emit(TokenDelta([], finished=True, reason="error",
                                error=f"decode stalled (no token within "
                                      f"{stall_s:.1f}s)"))
                stream.cancel()
                break
            # lint: absorb(mid-stream at 200: the typed terminal frame IS the error path)
            except GenerationError as e:
                emit(TokenDelta([], finished=True, reason="error",
                                error=str(e)))
                break
            if not delta.tokens and not delta.finished:
                # the worker's sign of life between the chunks of a long
                # prompt: it restarts the stall window and is not the
                # client's to see
                continue
            served += len(delta.tokens)
            if not emit(delta):
                return served
            if delta.finished:
                break
        try:
            handler.wfile.write(b"0\r\n\r\n")
            handler.wfile.flush()
        # lint: absorb(client gone at stream end: nothing left to answer)
        except (BrokenPipeError, ConnectionResetError, OSError):
            stream.cancel()
        return served

    @staticmethod
    def _accepts_wire(handler) -> bool:
        """Accept check for the binary token-delta stream (same lite rule
        as :meth:`_accepts_npy`): the client must NAME the wire media
        type — old clients never see the v3 message kind."""
        from rafiki_tpu.cache import wire

        accept = handler.headers.get("Accept") or ""
        return any(
            part.split(";")[0].strip().lower() == wire.CONTENT_TYPE
            for part in accept.split(","))

    def _metrics(self, handler: BaseHTTPRequestHandler) -> None:
        """GET /metrics: Prometheus text exposition of the process
        registry (?format=json for the JSON snapshot + ring series).
        Unauthenticated like /healthz — counters only, standard scraper
        contract."""
        from rafiki_tpu.utils.metrics import serve_http

        serve_http(handler, (handler.path.split("?", 1) + [""])[1])

    def _finish_trace(self, rt, t0: float, t_resp: float) -> None:
        """Close out a sampled request: the respond span, per-phase
        latency histograms, and — past RAFIKI_TRACE_SLOW_MS — a JSON-lines
        exemplar under LOGS_DIR. Never raises (telemetry must not fail a
        request that was already served)."""
        if rt is None:
            return
        try:
            from rafiki_tpu.utils import trace as rtrace
            from rafiki_tpu.utils.metrics import REGISTRY

            now = time.monotonic()
            rt.add_span("respond", t_resp, now)
            phase_h = REGISTRY.histogram(
                "rafiki_phase_seconds",
                "per-phase latency of sampled predict requests",
                ("phase",))
            for name, secs in rt.phase_durations().items():
                phase_h.labels(name).observe(secs)
            e2e_s = now - t0
            if e2e_s >= rtrace.slow_threshold_s():
                rtrace.record_exemplar(rt, e2e_s,
                                       door=f"predictor:{self.app}")
        except Exception:
            logger.debug("trace finish failed", exc_info=True)

    @staticmethod
    def _accepts_npy(handler) -> bool:
        """RFC 9110-lite Accept check: any listed media range equal to
        application/x-npy (params ignored, case-insensitive) opts the
        response into binary. No q-value algebra — this is a two-format
        door, not a content-negotiation engine."""
        accept = handler.headers.get("Accept") or ""
        return any(
            part.split(";")[0].strip().lower() == "application/x-npy"
            for part in accept.split(","))

    @staticmethod
    def _respond(handler, code: int, payload: Dict[str, Any],
                 headers: Optional[Dict[str, str]] = None) -> None:
        from rafiki_tpu.utils.jsonutil import json_default

        # json_default: predictions may carry stray numpy scalars/rows
        # when a binary-era worker answers a JSON client
        data = json.dumps(payload, default=json_default).encode()
        handler.send_response(code)
        handler.send_header("Content-Type", "application/json")
        handler.send_header("Content-Length", str(len(data)))
        for k, v in (headers or {}).items():
            handler.send_header(k, v)
        handler.end_headers()
        handler.wfile.write(data)

    @staticmethod
    def _respond_bytes(handler, code: int, data: bytes,
                       content_type: str,
                       headers: Optional[Dict[str, str]] = None) -> None:
        handler.send_response(code)
        handler.send_header("Content-Type", content_type)
        handler.send_header("Content-Length", str(len(data)))
        for k, v in (headers or {}).items():
            handler.send_header(k, v)
        handler.end_headers()
        handler.wfile.write(data)
