"""REST API over Admin (reference rafiki/admin/app.py:13-397).

Same resource model and JWT-style auth with per-route allowed user types
(reference utils/auth.py:28-45). Built on the stdlib threading HTTP server —
no Flask dependency — as a thin shell over the Admin library; every route
body is one Admin call.

Model upload: JSON with the template file base64-encoded (the reference used
multipart; base64-in-JSON keeps the stdlib server simple and the client SDK
hides the encoding either way).
"""

from __future__ import annotations

import base64
import json
import logging
import os
import re
import threading
import traceback
from http.server import BaseHTTPRequestHandler
from typing import Any, Callable, Dict, List, Optional, Tuple
from urllib.parse import parse_qs, urlparse

from rafiki_tpu import config
from rafiki_tpu.admin.admin import Admin, InvalidRequestError
from rafiki_tpu.admin.rollout import RolloutInFlightError
from rafiki_tpu.cache.queue import FrameTooLargeError, QueueFullError
from rafiki_tpu.constants import UserType
from rafiki_tpu.db.database import StaleEpochError
from rafiki_tpu.placement.hosts import StaleAdminEpochError
from rafiki_tpu.placement.manager import InsufficientChipsError
from rafiki_tpu.predictor.admission import (
    DeadlineUnmeetableError,
    ServerOverloadedError,
    retry_after_headers,
)
from rafiki_tpu.sdk.artifact import ArtifactCorruptError
from rafiki_tpu.sdk.model import InvalidModelClassError
from rafiki_tpu.utils.auth import UnauthorizedError, auth_check, decode_token
from rafiki_tpu.utils.reqfields import (
    LowLatencyHandler,
    SeveringHTTPServer,
    read_bounded_body,
)

logger = logging.getLogger(__name__)

_ANY = None  # any authenticated user
_ADMINS = [UserType.ADMIN, UserType.SUPERADMIN]
_MODEL_DEVS = [UserType.MODEL_DEVELOPER] + _ADMINS
_APP_DEVS = [UserType.APP_DEVELOPER] + _ADMINS

Route = Tuple[str, re.Pattern, Optional[List[str]], Callable]


def _field(body: Dict[str, Any], name: str) -> Any:
    """A required body field. Raised as InvalidRequestError (→ 400) at the
    route boundary so the dispatch loop never has to catch KeyError — a
    KeyError from inside Admin is then a genuine 500, not a masked 400."""
    try:
        return body[name]
    except (KeyError, TypeError):
        raise InvalidRequestError(f"missing body field '{name}'")


def _num_field(body: Dict[str, Any], name: str, cast, default=None):
    """A numeric body field coerced with ``cast`` (int/float); malformed
    values are client errors. ``default=None`` makes the field required."""
    if name not in body:
        if default is None:
            raise InvalidRequestError(f"missing body field '{name}'")
        return default
    try:
        return cast(body[name])
    except (ValueError, TypeError) as e:
        raise InvalidRequestError(
            f"field '{name}' must be {cast.__name__}: {e}")


def _b64_field(body: Dict[str, Any], name: str) -> bytes:
    """Decode a base64 body field; malformed input is a client error, not a
    server bug — keep broad except clauses out of the dispatch loop."""
    try:
        return base64.b64decode(_field(body, name))
    except (ValueError, TypeError) as e:
        raise InvalidRequestError(f"field '{name}' is not valid base64: {e}")


def _list_field(body: Dict[str, Any], name: str) -> list:
    """A required body field that must be a JSON array; anything else is
    a client error (the fuzz contract: malformed bodies answer 4xx, never
    a 500 from iterating an int)."""
    value = _field(body, name)
    if not isinstance(value, list):
        raise InvalidRequestError(
            f"field '{name}' must be a list, got {type(value).__name__}")
    return value


# Door cap on batched advisor proposals: each draw is a GP fit + EI
# optimization under the session lock, so an unbounded client-supplied k
# could pin the advisor (and starve every worker sharing it) for hours.
# Workers clamp far lower (RAFIKI_TRIAL_VMAP_K, PopulationSpec
# max_members); this bound is the trust boundary's backstop.
PROPOSE_BATCH_MAX = 64


def _knob_config_field(body: Dict[str, Any]):
    """Deserialize a client-supplied knob_config; any malformed shape or
    unknown knob type is a client error, validated here at the route
    boundary."""
    from rafiki_tpu.sdk.knob import deserialize_knob_config

    try:
        return deserialize_knob_config(_field(body, "knob_config"))
    except (ValueError, TypeError, KeyError, AttributeError) as e:
        raise InvalidRequestError(f"invalid knob_config: {e}")


def _int_param(query: Dict[str, str], name: str, default: int) -> int:
    try:
        return int(query.get(name, default))
    except (ValueError, TypeError) as e:
        raise InvalidRequestError(f"query param '{name}' must be an int: {e}")


class AdminServer:
    """HTTP façade; start() binds and serves on a daemon thread."""

    def __init__(self, admin: Admin, host: str = "127.0.0.1", port: int = 0):
        self.admin = admin
        self.host = host
        self.port = port
        self._httpd: Optional[SeveringHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        self.routes: List[Route] = self._build_routes()

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "AdminServer":
        server = self

        class Handler(LowLatencyHandler):
            # HTTP/1.1: keep-alive, so a client session reuses one
            # connection (and one server thread) across requests instead of
            # paying connect + thread-spawn per call. Safe because every
            # response path sends Content-Length. The idle timeout reaps
            # the thread of a client that died without closing (SIGKILL'd
            # worker) — otherwise dead-connection threads pile up forever.
            protocol_version = "HTTP/1.1"
            timeout = 300

            def do_GET(self):
                server._dispatch(self, "GET")

            def do_POST(self):
                server._dispatch(self, "POST")

            def do_DELETE(self):
                server._dispatch(self, "DELETE")

        self._httpd = SeveringHTTPServer((self.host, self.port), Handler)
        self.port = self._httpd.server_address[1]
        # worker *processes* coordinate HPO + events through this API;
        # tell the placement layer where it lives (placement/process.py)
        # getattr-safe: a hot standby (admin/standby.py) has no placement
        # layer until it promotes; its door serves hints + login only
        placement = getattr(self.admin, "placement", None)
        if placement is not None and hasattr(placement, "admin_addr"):
            placement.admin_addr = (self.host, self.port)
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._httpd:
            self._httpd.shutdown()
            self._httpd.server_close()
            # sever established keep-alive connections too: a stopped
            # door must go dark like a killed process, or HA drills keep
            # being served by the "dead" leader's handler threads
            self._httpd.sever()

    # -- routing -----------------------------------------------------------

    def _build_routes(self) -> List[Route]:
        A = self.admin

        def r(method: str, pattern: str, allowed, fn) -> Route:
            return (method, re.compile(f"^{pattern}$"), allowed, fn)

        return [
            # recovery STATE rides the public root so any client can wait
            # out a restarting admin without credentials (the full report
            # — ids, agent addresses, failure reasons — needs the
            # admin-rights /fleet/health)
            r("GET", "/", "public", lambda au, m, b, q: {
                "name": "rafiki_tpu admin", "status": "ok",
                "recovery": A.recovery_public(),
                # control-plane HA role + leader hint (public on purpose:
                # failover clients walk addresses pre-auth)
                "ha": getattr(A, "ha_public", lambda: {"role": "leader"})()}),
            r("POST", "/tokens", "public", lambda au, m, b, q: A.authenticate_user(
                _field(b, "email"), _field(b, "password"))),
            # users
            r("POST", "/users", _ADMINS, lambda au, m, b, q: A.create_user(
                _field(b, "email"), _field(b, "password"), _field(b, "user_type"))),
            r("GET", "/users", _ADMINS, lambda au, m, b, q: A.get_users()),
            r("DELETE", "/users", _ADMINS, lambda au, m, b, q: A.ban_user(
                _field(b, "email"))),
            # models
            r("POST", "/models", _MODEL_DEVS, lambda au, m, b, q: A.create_model(
                au["user_id"], _field(b, "name"), _field(b, "task"),
                _b64_field(b, "model_file_base64"), _field(b, "model_class"),
                b.get("dependencies"), b.get("access_right", "PRIVATE"))),
            # static-analysis dry run (analysis/template.py): the full
            # finding report, no model row created — the pre-upload loop
            # (Client.verify_model / python -m rafiki_tpu.analysis)
            r("POST", "/models/verify", _MODEL_DEVS,
                lambda au, m, b, q: A.verify_model(
                    _b64_field(b, "model_file_base64"),
                    _field(b, "model_class"), b.get("dependencies"))),
            r("GET", "/models", _ANY, lambda au, m, b, q: A.get_models(
                au["user_id"], q.get("task"))),
            r("GET", r"/models/(?P<name>[^/]+)", _ANY, lambda au, m, b, q:
                A.get_model(au["user_id"], m["name"], q.get("owner_id"))),
            r("GET", r"/models/(?P<name>[^/]+)/file", _ANY, lambda au, m, b, q:
                {"model_file_base64": base64.b64encode(A.get_model_file(
                    au["user_id"], m["name"], q.get("owner_id"))).decode()}),
            r("DELETE", r"/models/(?P<name>[^/]+)", _MODEL_DEVS,
                lambda au, m, b, q: A.delete_model(au["user_id"], m["name"]) or {}),
            # train jobs
            r("POST", "/train_jobs", _APP_DEVS, lambda au, m, b, q:
                A.create_train_job(
                    au["user_id"], _field(b, "app"), _field(b, "task"), _field(b, "train_dataset_uri"),
                    _field(b, "test_dataset_uri"), b.get("budget"), b.get("models"))),
            r("GET", "/train_jobs", _ANY, lambda au, m, b, q:
                A.get_train_jobs_of_user(au["user_id"])),
            r("GET", r"/train_jobs/(?P<app>[^/]+)", _ANY, lambda au, m, b, q:
                A.get_train_jobs_of_app(au["user_id"], m["app"])),
            r("GET", r"/train_jobs/(?P<app>[^/]+)/(?P<v>-?\d+)", _ANY,
                lambda au, m, b, q: A.get_train_job(
                    au["user_id"], m["app"], int(m["v"]))),
            r("POST", r"/train_jobs/(?P<app>[^/]+)/(?P<v>-?\d+)/stop", _APP_DEVS,
                lambda au, m, b, q: A.stop_train_job(
                    au["user_id"], m["app"], int(m["v"]))),
            r("GET", r"/train_jobs/(?P<app>[^/]+)/(?P<v>-?\d+)/trials", _ANY,
                lambda au, m, b, q: A.get_trials_of_train_job(
                    au["user_id"], m["app"], int(m["v"]))),
            r("GET", r"/train_jobs/(?P<app>[^/]+)/(?P<v>-?\d+)/best_trials",
                _ANY, lambda au, m, b, q: A.get_best_trials_of_train_job(
                    au["user_id"], m["app"], int(m["v"]),
                    _int_param(q, "max_count", 2))),
            # trials
            r("GET", r"/trials/(?P<tid>[^/]+)/logs", _ANY, lambda au, m, b, q:
                A.get_trial_logs(m["tid"])),
            r("GET", r"/trials/(?P<tid>[^/]+)/trace", _ANY, lambda au, m, b, q:
                A.get_trial_trace(m["tid"])),
            r("GET", r"/trials/(?P<tid>[^/]+)/parameters", _ANY,
                lambda au, m, b, q: {"params_base64": base64.b64encode(
                    A.get_trial_params(m["tid"])).decode()}),
            r("GET", r"/trials/(?P<tid>[^/]+)", _ANY, lambda au, m, b, q:
                A.get_trial(m["tid"])),
            # inference jobs
            r("POST", "/inference_jobs", _APP_DEVS, lambda au, m, b, q:
                A.create_inference_job(
                    au["user_id"], _field(b, "app"), b.get("app_version", -1),
                    budget=b.get("budget"))),
            r("GET", r"/inference_jobs/(?P<app>[^/]+)/(?P<v>-?\d+)", _ANY,
                lambda au, m, b, q: A.get_inference_job(
                    au["user_id"], m["app"], int(m["v"]))),
            r("GET", r"/inference_jobs/(?P<app>[^/]+)/(?P<v>-?\d+)/stats",
                _ANY, lambda au, m, b, q: A.get_inference_job_stats(
                    au["user_id"], m["app"], int(m["v"]))),
            r("POST", r"/inference_jobs/(?P<app>[^/]+)/(?P<v>-?\d+)/stop",
                _APP_DEVS, lambda au, m, b, q: A.stop_inference_job(
                    au["user_id"], m["app"], int(m["v"]))),
            # elastic serving: add / gracefully drain replicas at runtime
            # (admin/autoscaler.py drives the same primitive)
            r("POST", r"/inference_jobs/(?P<app>[^/]+)/(?P<v>-?\d+)/scale",
                _APP_DEVS, lambda au, m, b, q: A.scale_inference_job(
                    au["user_id"], m["app"], int(m["v"]),
                    delta=_num_field(b, "delta", int))),
            # safe live rollouts (admin/rollout.py): update the RUNNING
            # inference job to a new trial in place — canary, SLO judge,
            # rolling replace, automatic rollback. A second update while
            # one is in flight answers a typed 409.
            r("POST", r"/inference_jobs/(?P<app>[^/]+)/(?P<v>-?\d+)/update",
                _APP_DEVS, lambda au, m, b, q: A.update_inference_job(
                    au["user_id"], m["app"], int(m["v"]),
                    trial_id=_field(b, "trial_id"),
                    canary_fraction=(
                        _num_field(b, "canary_fraction", float, -1.0)
                        if "canary_fraction" in b else None),
                    batch=(_num_field(b, "batch", int, 1)
                           if "batch" in b else None))),
            r("GET", r"/inference_jobs/(?P<app>[^/]+)/(?P<v>-?\d+)/rollout",
                _ANY, lambda au, m, b, q: A.get_rollout_status(
                    au["user_id"], m["app"], int(m["v"]))),
            r("POST",
                r"/inference_jobs/(?P<app>[^/]+)/(?P<v>-?\d+)/rollout/abort",
                _APP_DEVS, lambda au, m, b, q: A.abort_rollout(
                    au["user_id"], m["app"], int(m["v"]))),
            r("POST",
                r"/inference_jobs/(?P<app>[^/]+)/(?P<v>-?\d+)/rollout/ack",
                _APP_DEVS, lambda au, m, b, q: A.ack_rollout(
                    au["user_id"], m["app"], int(m["v"]))),
            # drift closed loop (admin/drift.py): the job's loop state +
            # live signals; ack re-arms a parked loop / clears a flap
            r("GET", r"/inference_jobs/(?P<app>[^/]+)/(?P<v>-?\d+)/drift",
                _ANY, lambda au, m, b, q: A.get_drift_status(
                    au["user_id"], m["app"], int(m["v"]))),
            r("POST",
                r"/inference_jobs/(?P<app>[^/]+)/(?P<v>-?\d+)/drift/ack",
                _APP_DEVS, lambda au, m, b, q: A.ack_drift(
                    au["user_id"], m["app"], int(m["v"]))),
            # serving (the reference exposed this on a separate predictor app,
            # reference predictor/app.py:23-31)
            r("POST", r"/predict/(?P<app>[^/]+)", _ANY, lambda au, m, b, q:
                {"predictions": A.predict(
                    au["user_id"], m["app"], _field(b, "queries"),
                    b.get("app_version", -1))}),
            # advisor sessions (reference advisor/app.py:17-50)
            r("POST", "/advisors", _ANY, lambda au, m, b, q: {
                "advisor_id": A.advisor_store.create_advisor(
                    _knob_config_field(b),
                    advisor_id=b.get("advisor_id"))}),
            r("POST", r"/advisors/(?P<aid>[^/]+)/propose", _ANY,
                lambda au, m, b, q: {"knobs": A.advisor_store.propose(m["aid"])}),
            # batched proposals for vectorized trial execution: K knob
            # assignments in one call (the GP spreads them via its
            # pending-point fantasies); old clients keep using /propose
            r("POST", r"/advisors/(?P<aid>[^/]+)/propose_batch", _ANY,
                lambda au, m, b, q: {
                    "knobs_list": A.advisor_store.propose_batch(
                        m["aid"], max(1, min(_num_field(b, "k", int, 1),
                                             PROPOSE_BATCH_MAX)))}),
            r("POST", r"/advisors/(?P<aid>[^/]+)/feedback", _ANY,
                lambda au, m, b, q: {"knobs": A.advisor_store.feedback(
                    m["aid"], _field(b, "knobs"), _field(b, "score"))}),
            # the batch's return leg: K (knobs, score) observations,
            # applied member-by-member (each retires its own fantasy)
            r("POST", r"/advisors/(?P<aid>[^/]+)/feedback_batch", _ANY,
                lambda au, m, b, q: {
                    "count": A.advisor_store.feedback_batch(
                        m["aid"],
                        [(_field(i, "knobs"), _field(i, "score"))
                         for i in _list_field(b, "items")])}),
            # scoreless-failure signal (trial fault classification): the GP
            # steers away from the region; trial_id lets the session's
            # ASHA scheduler forget the dead trial's rung records
            r("POST", r"/advisors/(?P<aid>[^/]+)/infeasible", _ANY,
                lambda au, m, b, q: {
                    "infeasible": A.advisor_store.feedback_infeasible(
                        m["aid"], _field(b, "knobs"),
                        kind=b.get("kind", "USER"),
                        trial_id=b.get("trial_id"))}),
            r("POST", r"/advisors/(?P<aid>[^/]+)/replay", _ANY,
                lambda au, m, b, q: {"replayed": A.advisor_store.replay_feedback(
                    m["aid"],
                    [(_field(i, "knobs"), _field(i, "score"))
                     for i in _list_field(b, "items")],
                    infeasible=[
                        (_field(i, "knobs"), i.get("kind", "USER"))
                        for i in b.get("infeasible") or []])}),
            # ASHA rung report (early stopping; advisor/asha.py)
            r("POST", r"/advisors/(?P<aid>[^/]+)/report_rung", _ANY,
                lambda au, m, b, q: {"keep": A.advisor_store.report_rung(
                    m["aid"], _field(b, "trial_id"), _num_field(b, "resource", int),
                    _num_field(b, "value", float),
                    min_resource=_num_field(b, "min_resource", int, 1),
                    eta=_num_field(b, "eta", int, 3),
                    mode=b.get("mode", "min"))}),
            r("DELETE", r"/advisors/(?P<aid>[^/]+)", _ANY, lambda au, m, b, q:
                A.advisor_store.delete_advisor(m["aid"]) or {}),
            # admin actions (reference scripts/stop_all_jobs.py via client)
            r("POST", "/actions/stop_all_jobs", _ADMINS,
                lambda au, m, b, q: A.stop_all_jobs() or {}),
            # fleet health: per-agent heartbeat + circuit breaker state
            # (placement/hosts.py monitor; docs/failure-model.md)
            r("GET", "/fleet/health", _ADMINS,
                lambda au, m, b, q: A.get_fleet_health()),
            # internal events (reference admin/app.py:360). Workers
            # authenticate as superadmin (as the reference's did, reference
            # worker/train.py:261-263); plain users must not be able to stop
            # other tenants' services through this.
            r("POST", r"/event/(?P<name>[^/]+)", _ADMINS, lambda au, m, b, q:
                A.handle_event(m["name"], b) or {}),
        ]

    # -- static web admin --------------------------------------------------

    _WEB_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "web")

    def _serve_web(self, handler: BaseHTTPRequestHandler) -> None:
        """Serve the single-file dashboard SPA (the analogue of the
        reference's React/Express web admin, reference web/app.js:12-17 —
        here one static HTML file against the same-origin REST API)."""
        try:
            with open(os.path.join(self._WEB_DIR, "index.html"), "rb") as f:
                data = f.read()
        except OSError:
            self._respond(handler, 404, {"error": "web UI assets missing"})
            return
        handler.send_response(200)
        handler.send_header("Content-Type", "text/html; charset=utf-8")
        handler.send_header("Content-Length", str(len(data)))
        handler.end_headers()
        handler.wfile.write(data)

    def _dispatch(self, handler: BaseHTTPRequestHandler, method: str) -> None:
        try:
            parsed = urlparse(handler.path)
            path = parsed.path.rstrip("/") or "/"
            if method == "GET" and path == "/web":
                self._serve_web(handler)
                return
            if method == "GET" and path == "/metrics":
                # Prometheus text exposition (utils/metrics.py — one
                # rendering shared with the agent and predictor doors).
                # Public like the reference scraper contract, and exempt
                # from the recovery gate: a reconciling admin's metrics
                # are exactly what an operator wants to watch.
                from rafiki_tpu.utils.metrics import serve_http

                serve_http(handler, parsed.query)
                return
            # boot gate: while the control plane reconciles a crashed
            # predecessor's state (admin/recovery.py), every route that
            # could read or mutate half-reconciled state sheds with 503 +
            # Retry-After. Allowed through: the public root (carries the
            # recovery state), login, the fleet-health view, worker
            # events (agents keep forwarding statuses DURING recovery),
            # and the advisor routes — surviving train workers the
            # reconcile is adopting keep proposing/reporting mid-trial,
            # and the advisor store is fresh in-memory state, not part of
            # what is being reconciled.
            # the body is read BEFORE any gate can answer: an early 503
            # that leaves the body unread desyncs HTTP/1.1 keep-alive
            # framing — the next request on the pooled connection parses
            # the leftover bytes as its request line (a failover client
            # walking back to this door then sees a bogus 400)
            body: Dict[str, Any] = {}
            raw, berr = read_bounded_body(
                handler, config.ADMIN_MAX_BODY_MB, fallback_mb=256.0)
            if berr:
                # this door's error channel is InvalidRequestError (400)
                raise InvalidRequestError(f"{berr[1]} (ADMIN_MAX_BODY_MB)")
            # standby gate (control-plane HA, admin/standby.py): a hot
            # standby answers login, the public root and the fleet-health
            # snapshot read-only; everything else sheds with 503 + the
            # leader's address so clients fail over in one hop instead of
            # polling. Checked BEFORE the recovery gate — a standby has no
            # recovery state to consult until it promotes.
            role = getattr(self.admin, "ha_role", None)
            role = role() if callable(role) else "leader"
            if role == "standby" and not (
                    path == "/" or path == "/tokens"
                    or path == "/fleet/health"):
                self._respond(
                    handler, 503,
                    {"error": "admin is a hot standby; mutations go to "
                              "the leader",
                     "standby": True,
                     "leader": self.admin.leader_hint()},
                    headers={"Retry-After": "1"})
                return
            state = self.admin.recovery_status()
            if state.get("state") == "recovering" and not (
                    path == "/" or path == "/tokens"
                    or path == "/fleet/health"
                    or path.startswith("/event/")
                    or path.startswith("/advisors")):
                self._respond(
                    handler, 503,
                    {"error": "admin is recovering (boot reconciliation "
                              "in progress); retry shortly",
                     # state only: most gated routes are pre-auth, and
                     # the full report carries internal ids/addresses
                     "recovery": self.admin.recovery_public()},
                    headers={"Retry-After": "1"})
                return
            query = {k: v[0] for k, v in parse_qs(parsed.query).items()}
            try:
                if raw:
                    body = json.loads(raw or b"{}")
            except (ValueError, UnicodeDecodeError) as e:
                # malformed JSON or non-UTF-8 bytes (body fully read)
                raise InvalidRequestError(f"malformed request body: {e}")
            if raw and not isinstance(body, dict):
                raise InvalidRequestError("request body must be a JSON object")

            for m, pattern, allowed, fn in self.routes:
                if m != method:
                    continue
                match = pattern.match(path)
                if not match:
                    continue
                if allowed == "public":
                    auth: Dict[str, Any] = {}
                else:
                    token = (handler.headers.get("Authorization") or "").removeprefix(
                        "Bearer "
                    )
                    auth = decode_token(token)
                    if allowed is not _ANY:
                        auth_check(auth, allowed)
                result = fn(auth, match.groupdict(), body, query)
                self._respond(handler, 200, {"data": result})
                return
            self._respond(handler, 404, {"error": f"No route {method} {path}"})
        except UnauthorizedError as e:
            self._respond(handler, 401, {"error": str(e)})
        except (InvalidRequestError, InvalidModelClassError) as e:
            # field presence/coercion is validated at the route boundary
            # (_field/_num_field/_b64_field/_int_param), so ValueError &
            # friends from inside Admin stay genuine 500s instead of being
            # masked as client errors with internal text echoed back
            self._respond(handler, 400, {"error": f"{type(e).__name__}: {e}"})
        except RolloutInFlightError as e:
            # exactly one live rollout per job: the conflict is the
            # resource's current state, so 409 (retry after the rollout
            # ends, or abort it) — typed for Client.update_inference_job
            self._respond(handler, 409, {"error": f"{type(e).__name__}: {e}"})
        except ArtifactCorruptError as e:
            # a damaged on-disk artifact (params/checkpoint): the client
            # gets the typed error cleanly, never a deserialize traceback
            self._respond(handler, 500, {"error": f"{type(e).__name__}: {e}"})
        except FrameTooLargeError as e:
            # the request's wire frame exceeds the shm ring: permanent for
            # this payload — 413, never the retryable 429
            self._respond(handler, 413, {"error": f"{type(e).__name__}: {e}"})
        except (QueueFullError, DeadlineUnmeetableError) as e:
            # serving overload, retryable backlog (docs/failure-model.md
            # "Overload faults"): 429 + Retry-After, same contract as the
            # dedicated predictor port
            self._respond(handler, 429,
                          {"error": f"{type(e).__name__}: {e}"},
                          headers=retry_after_headers(e))
        except ServerOverloadedError as e:
            # serving door out of in-flight capacity
            self._respond(handler, 503,
                          {"error": f"{type(e).__name__}: {e}"},
                          headers=retry_after_headers(e))
        except TimeoutError as e:
            # predict missed its SLO: a 504 the client may retry, not an
            # internal error — same contract as the dedicated predictor
            # port, and no spurious server-side traceback per miss
            self._respond(handler, 504, {"error": f"{type(e).__name__}: {e}"})
        except InsufficientChipsError as e:
            self._respond(handler, 503, {"error": f"{type(e).__name__}: {e}"})
        except (StaleEpochError, StaleAdminEpochError) as e:
            # this admin lost leadership mid-request (epoch fence fired at
            # the DB chokepoint or an agent refused a stale epoch): answer
            # like a standby — 503 + leader hint — so the client's
            # multi-address failover walks to the new leader
            self._respond(
                handler, 503,
                {"error": f"{type(e).__name__}: admin lost leadership; "
                          "retry against the leader",
                 "standby": True,
                 "leader": getattr(self.admin, "leader_hint",
                                   lambda: None)()},
                headers={"Retry-After": "1"})
        except Exception:
            # log the traceback server-side; never leak it to callers
            logger.error("unhandled error on %s %s:\n%s", method,
                         handler.path, traceback.format_exc())
            self._respond(handler, 500, {"error": "internal server error"})

    @staticmethod
    def _respond(handler, code: int, payload: Dict[str, Any],
                 headers: Optional[Dict[str, str]] = None) -> None:
        data = json.dumps(payload).encode()
        handler.send_response(code)
        handler.send_header("Content-Type", "application/json")
        handler.send_header("Content-Length", str(len(data)))
        for k, v in (headers or {}).items():
            handler.send_header(k, v)
        handler.end_headers()
        handler.wfile.write(data)
