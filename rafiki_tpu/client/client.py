"""Python client SDK — full REST wrapper over the admin API
(reference rafiki/client/client.py:29-737).

Capability parity: login/JWT, user CRUD, model CRUD (file upload/download),
train job CRUD + trials + best trials + logs + raw params download,
`load_trial_model` (reconstruct a trained model locally, reference
client.py:487-506), inference job CRUD, predict, advisor endpoints,
`stop_all_jobs`.
"""

from __future__ import annotations

import base64
import json
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import requests

from rafiki_tpu.sdk.model import load_model_class
from rafiki_tpu.sdk.params import load_params


class RafikiError(Exception):
    """Admin API error. ``status`` carries the HTTP status code when the
    admin answered at all (None for transport/parse failures), so callers
    can tell a missing route (404 — an old admin without the endpoint)
    from a transient refusal (e.g. a 503 overload shed)."""

    def __init__(self, message: str, status: Optional[int] = None):
        super().__init__(message)
        self.status = status


class AdminRecoveringError(RafikiError):
    """The admin answered 503 because its boot reconciliation (control-
    plane crash recovery) is still running. Retryable: poll
    :meth:`Client.wait_until_admin_ready` or just retry after the
    ``Retry-After`` interval."""


class AdminUnavailableError(RafikiError):
    """No configured admin address answered: every one refused the
    connection or shed as a hot standby within the failover window
    (``RAFIKI_ADMIN_FAILOVER_TIMEOUT_S``). Typed and retryable — a
    failover is usually in flight; :meth:`Client.wait_until_admin_ready`
    absorbs it while walking the address list."""


class GenerationStreamError(RafikiError):
    """A generation stream ended with a typed terminal error frame
    (mid-stream worker fault, stalled decode past the door's inter-token
    timeout). Tokens yielded before the fault are valid — the stream
    failed, not the transport."""


class RolloutInFlightError(RafikiError):
    """The admin answered 409: a rollout is already in flight for this
    inference job (exactly one at a time). Wait it out with
    :meth:`Client.wait_until_rollout_done` or abort it with
    :meth:`Client.abort_rollout`, then retry."""


class RolloutRolledBackError(RafikiError):
    """The rollout ended without reaching DONE: ``phase`` is
    ``ROLLED_BACK`` (the SLO judge fired — ``reason`` carries its
    verdict and the rollout's event log holds the signal snapshot) or
    ``ABORTED`` (job stopped / admin restarted mid-flight). The job
    keeps serving the incumbent version."""

    def __init__(self, message: str, phase: str, reason: Optional[str]):
        super().__init__(message)
        self.phase = phase
        self.reason = reason


class Client:
    def __init__(self, admin_host: str = "127.0.0.1", admin_port: int = 3000,
                 admin_addrs: Optional[List[str]] = None):
        """``admin_addrs`` (or the ``RAFIKI_ADMIN_ADDRS`` env, a comma
        list of ``host:port``) enables control-plane HA failover: calls
        walk the list in order on connection-refused and standby-503
        answers, following the leader hint those 503s carry. Explicit
        ``admin_host``/``admin_port`` arguments mean the caller picked
        one admin on purpose, so the env list only applies to a
        default-constructed client."""
        from rafiki_tpu import config as _config

        explicit = (admin_host != "127.0.0.1" or admin_port != 3000)
        if admin_addrs:
            addrs = list(admin_addrs)
        elif not explicit and _config.ADMIN_ADDRS:
            addrs = [a.strip() for a in _config.ADMIN_ADDRS.split(",")
                     if a.strip()]
        else:
            addrs = []
        if not addrs:
            addrs = [f"{admin_host}:{admin_port}"]
        self._addrs: List[str] = addrs
        self._active = 0  # index of the last address that answered
        self._base = f"http://{addrs[0]}"
        self._token: Optional[str] = None
        self.user: Optional[Dict[str, Any]] = None
        # pooled keep-alive connections: a fresh TCP connect per call would
        # cost setup latency AND a new server-side handler thread each time
        # (the admin server speaks HTTP/1.1 — admin/http.py). One Session
        # PER THREAD: requests.Session is not documented thread-safe, and a
        # Client is shared across threads (e.g. the placement agent's
        # status forwarder reports from per-service threads).
        self._tls = threading.local()
        # predict_direct's resolved (app, version) -> (host, port); see
        # that method for the invalidation rule
        self._predictor_ports: Dict[Any, Any] = {}

    @property
    def _http(self) -> requests.Session:
        s = getattr(self._tls, "session", None)
        if s is None:
            s = self._tls.session = requests.Session()
        return s

    # -- plumbing ----------------------------------------------------------

    def _call(
        self,
        method: str,
        path: str,
        body: Optional[Dict[str, Any]] = None,
        params: Optional[Dict[str, Any]] = None,
    ) -> Any:
        """One admin API call, with multi-address failover.

        The walk is safe for NON-idempotent calls too, because it only
        moves on in two cases where the request provably did not execute:
        connection refused (no server accepted it) and a standby/fenced
        503 (the door shed before dispatch). A request the leader started
        processing never retries. Standby 503s carry the leader's address
        — that hint is tried first, so failover is one extra hop."""
        from rafiki_tpu import config as _config

        headers = {}
        if self._token:
            headers["Authorization"] = f"Bearer {self._token}"
        multi = len(self._addrs) > 1
        deadline = (time.monotonic()
                    + float(_config.ADMIN_FAILOVER_TIMEOUT_S))
        # the walk order: last-known-good first, then the rest in config
        # order; a leader hint from a standby 503 jumps the queue
        last_refusal: Optional[str] = None
        while True:
            order = [self._addrs[(self._active + i) % len(self._addrs)]
                     for i in range(len(self._addrs))]
            hint_first: List[str] = []
            for addr in order:
                if addr in hint_first:
                    continue
                hint_first.append(addr)
            resp = None
            for addr in hint_first:
                try:
                    resp = self._http.request(
                        method, f"http://{addr}" + path, json=body,
                        params=params, headers=headers)
                except requests.ConnectionError as e:
                    # the connection was refused/reset before the request
                    # went out — it never executed, walking on is safe
                    last_refusal = f"{addr}: {e}"
                    continue
                try:
                    payload = resp.json()
                except ValueError:
                    raise RafikiError(
                        f"Bad response ({resp.status_code}): {resp.text}")
                if (resp.status_code == 503 and isinstance(payload, dict)
                        and payload.get("standby")):
                    # a hot standby (or a just-fenced ex-leader) shed the
                    # call before dispatch; follow its leader hint
                    last_refusal = f"{addr}: {payload.get('error')}"
                    hint = payload.get("leader")
                    if hint and hint not in self._addrs:
                        self._addrs.append(hint)
                    if hint and hint in self._addrs:
                        self._active = self._addrs.index(hint)
                    continue
                if (multi and resp.status_code == 503
                        and isinstance(payload, dict)
                        and "recovery" in payload):
                    # a just-promoted leader still reconciling its store:
                    # the recovery gate shed the call BEFORE dispatch, so
                    # retrying within the failover window is safe. Only in
                    # multi-address mode — single-admin clients keep the
                    # typed AdminRecoveringError contract.
                    last_refusal = f"{addr}: {payload.get('error')}"
                    continue
                self._active = self._addrs.index(addr)
                self._base = f"http://{addr}"
                return self._finish_call(resp, payload)
            if not multi and len(self._addrs) == 1:
                # single-admin client: no list to walk — surface the
                # refusal immediately, but TYPED (satellite of the HA
                # work: wait_until_admin_ready retries it like any other
                # RafikiError instead of leaking a transport exception)
                raise AdminUnavailableError(
                    f"admin unreachable: {last_refusal}")
            if time.monotonic() >= deadline:
                raise AdminUnavailableError(
                    "no admin address answered within "
                    f"{_config.ADMIN_FAILOVER_TIMEOUT_S:.0f}s failover "
                    f"window (last: {last_refusal}); tried {self._addrs}")
            time.sleep(0.1)

    def _finish_call(self, resp, payload) -> Any:
        if resp.status_code != 200:
            if resp.status_code == 503 and isinstance(payload, dict) \
                    and "recovery" in payload:
                # the admin restarted and is still reconciling its store
                # (admin/recovery.py): typed, so callers can wait it out
                raise AdminRecoveringError(
                    payload.get("error", "admin is recovering"))
            if resp.status_code == 409:
                # one live rollout per job (admin/rollout.py): typed so
                # callers can wait the current one out or abort it
                raise RolloutInFlightError(
                    payload.get("error", "rollout already in flight"),
                    status=409)
            raise RafikiError(payload.get("error", f"HTTP {resp.status_code}"),
                              status=resp.status_code)
        return payload.get("data")

    # -- auth --------------------------------------------------------------

    def login(self, email: str, password: str) -> Dict[str, Any]:
        data = self._call("POST", "/tokens", {"email": email, "password": password})
        self._token = data["token"]
        self.user = {"user_id": data["user_id"], "user_type": data["user_type"]}
        return self.user

    def logout(self) -> None:
        self._token = None
        self.user = None

    # -- users -------------------------------------------------------------

    def create_user(self, email: str, password: str, user_type: str) -> Dict:
        return self._call(
            "POST",
            "/users",
            {"email": email, "password": password, "user_type": user_type},
        )

    def get_users(self) -> List[Dict]:
        return self._call("GET", "/users")

    def ban_user(self, email: str) -> Dict:
        return self._call("DELETE", "/users", {"email": email})

    # -- models ------------------------------------------------------------

    def create_model(
        self,
        name: str,
        task: str,
        model_file_path: str,
        model_class: str,
        dependencies: Optional[Dict[str, Optional[str]]] = None,
        access_right: str = "PRIVATE",
    ) -> Dict:
        with open(model_file_path, "rb") as f:
            file_b64 = base64.b64encode(f.read()).decode()
        return self._call(
            "POST",
            "/models",
            {
                "name": name,
                "task": task,
                "model_file_base64": file_b64,
                "model_class": model_class,
                "dependencies": dependencies,
                "access_right": access_right,
            },
        )

    def verify_model(
        self,
        model_file_path: str,
        model_class: str,
        dependencies: Optional[Dict[str, Optional[str]]] = None,
    ) -> Dict:
        """Dry-run the admin's template verifier (static analysis, no
        code execution server-side): returns {"mode", "ok", "findings",
        "capabilities", ...} and never creates a model row — iterate
        locally until ``ok`` before spending an upload (or run
        ``python -m rafiki_tpu.analysis file.py`` offline)."""
        with open(model_file_path, "rb") as f:
            file_b64 = base64.b64encode(f.read()).decode()
        return self._call(
            "POST",
            "/models/verify",
            {
                "model_file_base64": file_b64,
                "model_class": model_class,
                "dependencies": dependencies,
            },
        )

    def get_models(self, task: Optional[str] = None) -> List[Dict]:
        return self._call("GET", "/models", params={"task": task} if task else None)

    def get_model(self, name: str) -> Dict:
        return self._call("GET", f"/models/{name}")

    def download_model_file(self, name: str) -> bytes:
        data = self._call("GET", f"/models/{name}/file")
        return base64.b64decode(data["model_file_base64"])

    def delete_model(self, name: str) -> None:
        self._call("DELETE", f"/models/{name}")

    # -- train jobs ----------------------------------------------------------

    def create_train_job(
        self,
        app: str,
        task: str,
        train_dataset_uri: str,
        test_dataset_uri: str,
        budget: Optional[Dict[str, Any]] = None,
        models: Optional[List[str]] = None,
    ) -> Dict:
        return self._call(
            "POST",
            "/train_jobs",
            {
                "app": app,
                "task": task,
                "train_dataset_uri": train_dataset_uri,
                "test_dataset_uri": test_dataset_uri,
                "budget": budget,
                "models": models,
            },
        )

    def get_train_jobs(self) -> List[Dict]:
        """All of this user's train jobs, newest first (the dashboard's
        landing view)."""
        return self._call("GET", "/train_jobs")

    def get_train_jobs_of_app(self, app: str) -> List[Dict]:
        return self._call("GET", f"/train_jobs/{app}")

    def get_train_job(self, app: str, app_version: int = -1) -> Dict:
        return self._call("GET", f"/train_jobs/{app}/{app_version}")

    def stop_train_job(self, app: str, app_version: int = -1) -> Dict:
        return self._call("POST", f"/train_jobs/{app}/{app_version}/stop")

    def get_trials_of_train_job(self, app: str, app_version: int = -1) -> List[Dict]:
        return self._call("GET", f"/train_jobs/{app}/{app_version}/trials")

    def get_best_trials_of_train_job(
        self, app: str, app_version: int = -1, max_count: int = 2
    ) -> List[Dict]:
        return self._call(
            "GET",
            f"/train_jobs/{app}/{app_version}/best_trials",
            params={"max_count": max_count},
        )

    # -- trials ----------------------------------------------------------------

    def get_trial(self, trial_id: str) -> Dict:
        return self._call("GET", f"/trials/{trial_id}")

    def get_trial_logs(self, trial_id: str) -> Dict:
        return self._call("GET", f"/trials/{trial_id}/logs")

    def get_trial_trace(self, trial_id: str) -> List[Dict]:
        """Per-phase span breakdown of a trial (propose/train/evaluate/
        persist wall-clock) — no reference analogue (SURVEY.md §5.1)."""
        return self._call("GET", f"/trials/{trial_id}/trace")

    def download_trial_params(self, trial_id: str) -> bytes:
        data = self._call("GET", f"/trials/{trial_id}/parameters")
        return base64.b64decode(data["params_base64"])

    def load_trial_model(self, trial_id: str, model_name: str):
        """Reconstruct a trained model locally (reference client.py:487-506):
        download the template file + the trial's params, instantiate with the
        trial's knobs, restore parameters."""
        trial = self.get_trial(trial_id)
        model_bytes = self.download_model_file(model_name)
        model_info = self.get_model(model_name)
        clazz = load_model_class(model_bytes, model_info["model_class"])
        model = clazz(**trial["knobs"])
        model.load_parameters(load_params(self.download_trial_params(trial_id)))
        return model

    # -- inference jobs ----------------------------------------------------------

    def create_inference_job(self, app: str, app_version: int = -1,
                             budget: Optional[Dict] = None) -> Dict:
        """``budget={"CHIPS_PER_WORKER": n}`` serves each worker on an
        n-chip mesh (sharded predict) — see Admin.create_inference_job."""
        body = {"app": app, "app_version": app_version}
        if budget is not None:
            body["budget"] = budget
        return self._call("POST", "/inference_jobs", body)

    def get_inference_job(self, app: str, app_version: int = -1) -> Dict:
        return self._call("GET", f"/inference_jobs/{app}/{app_version}")

    def get_inference_job_stats(self, app: str, app_version: int = -1) -> Dict:
        """Serving counters: per-worker batches/queries and batch occupancy."""
        return self._call("GET", f"/inference_jobs/{app}/{app_version}/stats")

    def stop_inference_job(self, app: str, app_version: int = -1) -> Dict:
        return self._call("POST", f"/inference_jobs/{app}/{app_version}/stop")

    def scale_inference_job(self, app: str, delta: int,
                            app_version: int = -1) -> Dict:
        """Elastically add (``delta`` > 0) or gracefully drain
        (``delta`` < 0) serving replicas of the app's running inference
        job — no redeploy, in-flight requests complete or re-route. The
        answer carries the replicas added/removed, chips borrowed from /
        returned to the training plane, and the new live replica count.
        (The RAFIKI_AUTOSCALE control loop drives this same primitive
        automatically; see GET /fleet/health's "autoscaler" section.)"""
        return self._call(
            "POST", f"/inference_jobs/{app}/{app_version}/scale",
            {"delta": int(delta)})

    # -- safe live rollouts (docs/failure-model.md "Rollout faults") ---------

    def update_inference_job(
        self, app: str, trial_id: str, app_version: int = -1,
        canary_fraction: Optional[float] = None,
        batch: Optional[int] = None,
    ) -> Dict:
        """Update the app's RUNNING inference job to serve ``trial_id``
        in place: one canary replica takes ``canary_fraction`` of the
        traffic while an SLO judge compares it to the incumbents, then a
        rolling replace in ``batch``-sized steps — zero dropped requests,
        automatic rollback on a breach. Returns the rollout row (phase
        ``CANARY``) immediately; follow with
        :meth:`wait_until_rollout_done`. Raises the typed
        :class:`RolloutInFlightError` (HTTP 409) while another rollout
        of the same job is live."""
        body: Dict[str, Any] = {"trial_id": trial_id}
        if canary_fraction is not None:
            body["canary_fraction"] = float(canary_fraction)
        if batch is not None:
            body["batch"] = int(batch)
        return self._call(
            "POST", f"/inference_jobs/{app}/{app_version}/update", body)

    def get_rollout(self, app: str, app_version: int = -1) -> Dict:
        """The app's newest rollout (live phases carry the judge's
        per-lane signal snapshot under ``signals``)."""
        return self._call(
            "GET", f"/inference_jobs/{app}/{app_version}/rollout")

    def abort_rollout(self, app: str, app_version: int = -1) -> Dict:
        """Abort the in-flight rollout: the new version is drained and
        the incumbents restored (phase ``ROLLED_BACK``, reason
        "operator abort")."""
        return self._call(
            "POST", f"/inference_jobs/{app}/{app_version}/rollout/abort")

    def ack_rollout(self, app: str, app_version: int = -1) -> Dict:
        """Acknowledge the newest rolled-back rollout (clears the
        ``python -m rafiki_tpu.doctor`` WARN)."""
        return self._call(
            "POST", f"/inference_jobs/{app}/{app_version}/rollout/ack")

    def get_drift_status(self, app: str, app_version: int = -1) -> Dict:
        """The app's drift closed-loop state (admin/drift.py): phase,
        frozen-baseline flag, live divergence signals, event tail."""
        return self._call(
            "GET", f"/inference_jobs/{app}/{app_version}/drift")

    def ack_drift(self, app: str, app_version: int = -1) -> Dict:
        """Acknowledge the app's drift loop: re-arms a ``PARKED`` loop
        or clears a rollback-flap streak (clears the doctor WARNs)."""
        return self._call(
            "POST", f"/inference_jobs/{app}/{app_version}/drift/ack")

    def wait_until_rollout_done(
        self, app: str, app_version: int = -1, timeout_s: float = 300.0,
    ) -> Dict:
        """Poll until the app's rollout reaches a terminal phase.
        Returns the rollout row on ``DONE``; raises the typed
        :class:`RolloutRolledBackError` — carrying the judge's reason —
        on ``ROLLED_BACK``/``ABORTED``, and TimeoutError if it is still
        live after ``timeout_s``."""
        import time as _time

        deadline = _time.monotonic() + timeout_s
        while True:
            rollout = self.get_rollout(app, app_version)
            phase = rollout.get("phase")
            if phase == "DONE":
                return rollout
            if phase in ("ROLLED_BACK", "ABORTED"):
                raise RolloutRolledBackError(
                    f"rollout {rollout.get('id', '?')[:8]} ended "
                    f"{phase}: {rollout.get('reason')}",
                    phase=phase, reason=rollout.get("reason"))
            if _time.monotonic() > deadline:
                raise TimeoutError(
                    f"rollout still {phase} after {timeout_s:.0f}s")
            _time.sleep(0.1)

    def predict(
        self, app: str, queries: List[Any], app_version: int = -1
    ) -> List[Any]:
        data = self._call(
            "POST",
            f"/predict/{app}",
            {"queries": queries, "app_version": app_version},
        )
        return data["predictions"]

    def _dedicated_door(self, app: str, app_version: int):
        """Resolve (and TTL-cache) the app's dedicated predictor door as
        ``(host, port, expiry)`` — shared by :meth:`predict_direct` and
        :meth:`generate`; entries drop on any request failure so a moved
        door re-resolves within seconds."""
        import time as _time

        from rafiki_tpu import config as _config

        key = (app, app_version)
        cached = self._predictor_ports.get(key)
        now = _time.monotonic()
        if cached is None or cached[2] < now:
            inf = self.get_inference_job(app, app_version)
            host, port = inf.get("predictor_host"), inf.get("predictor_port")
            if not host or not port:
                raise RafikiError(
                    f"inference job for {app} has no dedicated predictor "
                    f"port (deployment runs without RAFIKI_PREDICTOR_PORTS)")
            cached = (host, port, now + _config.PREDICT_ROUTE_TTL_S)
            self._predictor_ports[key] = cached
        return cached

    def predict_direct(
        self, app: str, queries: Any, app_version: int = -1
    ) -> List[Any]:
        """Predict through the job's DEDICATED predictor port, bypassing
        the admin control-plane server (available when the deployment set
        RAFIKI_PREDICTOR_PORTS=1; reference parity: per-job published
        predictor ports, reference admin/services_manager.py:379-384).
        ``queries`` is a JSON list — or a numpy array (leading batch
        axis), which ships as one binary ``.npy`` body and skips JSON
        float formatting entirely (the serving-door CPU cost for dense
        queries).
        The same login token authorizes both doors. The resolved
        host:port is cached per (app, version) with the same short TTL
        the admin door uses for its predict route
        (``PREDICT_ROUTE_TTL_S``) — one control-plane GET per TTL
        window, not per predict — and dropped on any failure, so a
        redeploy (or an app_version=-1 'latest' that moved) re-resolves
        within seconds rather than serving a stale port forever."""
        key = (app, app_version)
        cached = self._dedicated_door(app, app_version)
        headers = {}
        if self._token:
            headers["Authorization"] = f"Bearer {self._token}"
        import numpy as _np

        body_kwargs: Dict[str, Any]
        if isinstance(queries, _np.ndarray):
            # binary door: ship the batch as one .npy body — no JSON
            # float formatting/parsing on either side (the serving CPU
            # cost for dense queries like images) — and ask for the
            # predictions back the same way (Accept negotiation; the
            # door falls back to JSON for ragged predictions, so the
            # response Content-Type is sniffed below). Encode OUTSIDE
            # the request try: a local encode error (object dtype etc.)
            # is the caller's bug, not a route failure
            import io

            buf = io.BytesIO()
            try:
                _np.save(buf, queries, allow_pickle=False)
            except ValueError as e:
                raise RafikiError(f"queries array not npy-encodable: {e}")
            headers["Content-Type"] = "application/x-npy"
            headers["Accept"] = "application/x-npy, application/json"
            body_kwargs = {"data": buf.getvalue()}
        else:
            body_kwargs = {"json": {"queries": queries}}
        try:
            resp = self._http.request(
                "POST", f"http://{cached[0]}:{cached[1]}/predict",
                headers=headers, **body_kwargs)
            rtype = (resp.headers.get("Content-Type") or "").split(";")[0]
            if resp.status_code == 200 and rtype == "application/x-npy":
                import io

                arr = _np.load(io.BytesIO(resp.content), allow_pickle=False)
                return list(arr)
            payload = resp.json()
        except (requests.RequestException, ValueError) as e:
            # connect failure OR an undecodable body (port reclaimed by
            # some other server): drop the route and surface the door's
            # error type, same contract as every _call path
            self._predictor_ports.pop(key, None)
            raise RafikiError(f"dedicated predictor unreachable: {e}")
        if resp.status_code != 200:
            self._predictor_ports.pop(key, None)
            raise RafikiError(payload.get("error",
                                          f"HTTP {resp.status_code}"))
        return payload["data"]["predictions"]

    def generate(self, app: str, prompt_ids: List[int],
                 max_tokens: Optional[int] = None, app_version: int = -1,
                 timeout_s: Optional[float] = None, binary: bool = False,
                 temperature: Optional[float] = None,
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 seed: Optional[int] = None):
        """Stream a ``TEXT_GENERATION`` completion token-by-token through
        the app's dedicated predictor door (POST /generate, chunked
        transfer). Yields one delta dict per emitted increment —
        ``{"tokens": [...], "finished": bool, "reason": ...}`` — the
        moment it arrives, so the first token lands long before a long
        completion ends.

        ``binary=True`` opts into length-prefixed v3 wire token-delta
        frames instead of JSON lines (the zero-parse path; old doors that
        ignore the Accept header still answer JSON — the frame sniff
        handles either). A typed terminal error frame (mid-stream worker
        fault, stalled decode) raises :class:`GenerationStreamError`
        after yielding every token received before the fault.

        ``temperature`` / ``top_k`` / ``top_p`` turn on real sampling
        (temperature=0 or unset = greedy); a fixed ``seed`` makes the
        sampled stream reproducible — and the platform keeps it stable
        across mid-stream preemption/resume, so the sequence is exactly
        the uncontended one either way.

        Stream continuity (docs/failure-model.md "Stream continuity"):
        the door journals the stream and transparently resumes it
        token-identically on a sibling replica if its worker dies or is
        drained/retired mid-stream — the client just keeps receiving
        deltas. Only when the bounded resume is exhausted (or refused:
        the stream's model version has no replica left) does the typed
        terminal error frame arrive."""
        key = (app, app_version)
        host, port, _ = self._dedicated_door(app, app_version)
        headers = {}
        if self._token:
            headers["Authorization"] = f"Bearer {self._token}"
        body: Dict[str, Any] = {"prompt_ids": list(prompt_ids)}
        if max_tokens is not None:
            body["max_tokens"] = int(max_tokens)
        if timeout_s is not None:
            body["timeout_s"] = float(timeout_s)
        if temperature is not None:
            body["temperature"] = float(temperature)
        if top_k is not None:
            body["top_k"] = int(top_k)
        if top_p is not None:
            body["top_p"] = float(top_p)
        if seed is not None:
            body["seed"] = int(seed)
        if binary:
            from rafiki_tpu.cache import wire

            headers["Accept"] = wire.CONTENT_TYPE
        try:
            resp = self._http.request(
                "POST", f"http://{host}:{port}/generate",
                headers=headers, json=body, stream=True)
        except requests.RequestException as e:
            self._predictor_ports.pop(key, None)
            raise RafikiError(f"dedicated predictor unreachable: {e}")
        with resp:
            if resp.status_code != 200:
                self._predictor_ports.pop(key, None)
                try:
                    payload = resp.json()
                except ValueError:
                    payload = {}
                raise RafikiError(
                    payload.get("error", f"HTTP {resp.status_code}"),
                    status=resp.status_code)
            ctype = (resp.headers.get("Content-Type") or "").split(";")[0]
            deltas = (self._iter_wire_deltas(resp)
                      if ctype == "application/x-rafiki-wire"
                      else self._iter_json_deltas(resp))
            try:
                yield from deltas
            except requests.RequestException as e:
                # the stream was cut by the TRANSPORT (door/worker host
                # died mid-chunk — no terminal delta arrived): typed like
                # every other route failure, and the cached door is
                # suspect, so drop it for the next call
                self._predictor_ports.pop(key, None)
                raise RafikiError(
                    f"generation stream cut mid-transfer: {e}")

    @staticmethod
    def _iter_json_deltas(resp):
        buf = b""
        for data in resp.iter_content(chunk_size=None):
            buf += data
            while b"\n" in buf:
                line, buf = buf.split(b"\n", 1)
                if not line.strip():
                    continue
                try:
                    delta = json.loads(line)
                except ValueError as e:
                    raise RafikiError(f"garbled stream delta: {e}")
                if delta.get("error"):
                    raise GenerationStreamError(delta["error"])
                yield delta
                if delta.get("finished"):
                    return

    @staticmethod
    def _iter_wire_deltas(resp):
        from rafiki_tpu.cache import wire

        buf = b""
        for data in resp.iter_content(chunk_size=None):
            buf += data
            while len(buf) >= 4:
                n = int.from_bytes(buf[:4], "little")
                if len(buf) < 4 + n:
                    break
                frame, buf = buf[4:4 + n], buf[4 + n:]
                try:
                    _, delta = wire.decode_token_delta(frame)
                except wire.WireFormatError as e:
                    raise RafikiError(f"garbled token-delta frame: {e}")
                if delta.error is not None:
                    raise GenerationStreamError(delta.error)
                yield delta.to_json()
                if delta.finished:
                    return

    # -- advisors (reference client.py:586-644) ----------------------------------

    def create_advisor(
        self, knob_config_json: Dict[str, Any], advisor_id: Optional[str] = None
    ) -> str:
        data = self._call(
            "POST",
            "/advisors",
            {"knob_config": knob_config_json, "advisor_id": advisor_id},
        )
        return data["advisor_id"]

    def propose_knobs(self, advisor_id: str) -> Dict[str, Any]:
        return self._call("POST", f"/advisors/{advisor_id}/propose")["knobs"]

    def propose_knobs_batch(self, advisor_id: str,
                            k: int) -> List[Dict[str, Any]]:
        """K concurrent knob proposals in one call (vectorized trial
        execution: the worker trains the batch as one vmapped program).
        Admins predating the batch route answer 404 — callers fall back
        to K :meth:`propose_knobs` calls (RemoteAdvisorStore does this
        automatically)."""
        return self._call(
            "POST", f"/advisors/{advisor_id}/propose_batch",
            {"k": int(k)})["knobs_list"]

    def feedback_knobs_batch(
        self, advisor_id: str,
        items: List[Tuple[Dict[str, Any], float]],
    ) -> int:
        """Record a batch of (knobs, score) observations; returns how
        many were applied."""
        return int(self._call(
            "POST", f"/advisors/{advisor_id}/feedback_batch",
            {"items": [{"knobs": kn, "score": float(s)}
                       for kn, s in items]})["count"])

    def replay_advisor_feedback(self, advisor_id: str, items,
                                infeasible=None) -> bool:
        """Seed a fresh advisor session with already-scored (knobs, score)
        pairs; no-op (False) if the session already has observations.
        ``infeasible`` — (knobs, fault_kind) pairs of scoreless failures
        — rides the same empty-only guard."""
        out = self._call(
            "POST",
            f"/advisors/{advisor_id}/replay",
            {"items": [{"knobs": k, "score": s} for k, s in items],
             "infeasible": [{"knobs": k, "kind": kind}
                            for k, kind in infeasible or []]},
        )
        return bool(out["replayed"])

    def feedback_infeasible_knobs(
        self, advisor_id: str, knobs: Dict[str, Any], kind: str = "USER",
        trial_id: Optional[str] = None,
    ) -> int:
        """Tell the advisor the trial at ``knobs`` failed without a
        usable score (fault classification kind USER/TIMEOUT/INVALID_SCORE);
        proposals steer away. Returns the session's infeasible count."""
        return int(self._call(
            "POST",
            f"/advisors/{advisor_id}/infeasible",
            {"knobs": knobs, "kind": kind, "trial_id": trial_id},
        )["infeasible"])

    def feedback_knobs(
        self, advisor_id: str, knobs: Dict[str, Any], score: float
    ) -> Dict[str, Any]:
        return self._call(
            "POST",
            f"/advisors/{advisor_id}/feedback",
            {"knobs": knobs, "score": score},
        )["knobs"]

    def report_rung(self, advisor_id: str, trial_id: str, resource: int,
                    value: float, min_resource: int = 1, eta: int = 3,
                    mode: str = "min") -> bool:
        """ASHA early-stop rung report; returns whether the trial should
        continue training."""
        return bool(self._call(
            "POST",
            f"/advisors/{advisor_id}/report_rung",
            {"trial_id": trial_id, "resource": int(resource),
             "value": float(value), "min_resource": int(min_resource),
             "eta": int(eta), "mode": mode},
        )["keep"])

    def delete_advisor(self, advisor_id: str) -> None:
        self._call("DELETE", f"/advisors/{advisor_id}")

    # -- misc --------------------------------------------------------------------

    def get_fleet_health(self) -> Dict[str, Any]:
        """Operator view: per-agent heartbeat/breaker state, the serving
        overload picture, and the boot-reconciliation report (admin-rights
        token required; GET /fleet/health)."""
        return self._call("GET", "/fleet/health")

    def wait_until_admin_ready(self, timeout_s: float = 60.0) -> Dict[str, Any]:
        """Block until a (re)starting admin finishes its boot
        reconciliation (recovery state `ready` on the public root) —
        no credentials needed, so deploy scripts can gate on it before
        logging in. Returns the public recovery state ({"state": ...});
        the full report lives behind :meth:`get_fleet_health`.

        With control-plane HA the underlying call walks the whole
        ``RAFIKI_ADMIN_ADDRS`` list (typed ``AdminUnavailableError``
        refusals are absorbed like any other transient), so this also
        waits out a leader failover, not just a restart."""
        import time as _time

        deadline = _time.monotonic() + timeout_s
        while True:
            try:
                data = self._call("GET", "/")
                rec = (data or {}).get("recovery") or {"state": "ready"}
                if rec.get("state") != "recovering":
                    return rec
            except (RafikiError, requests.RequestException):
                # not up yet (connection refused while the socket rebinds)
                # or transient — keep polling
                pass
            if _time.monotonic() > deadline:
                raise TimeoutError(
                    f"admin still recovering after {timeout_s:.0f}s")
            _time.sleep(0.1)

    def send_event(self, name: str, **payload: Any) -> None:
        self._call("POST", f"/event/{name}", payload)

    def stop_all_jobs(self) -> None:
        """Stop all running train and inference jobs (admin-only; reference
        client.py:647 / scripts/stop_all_jobs.py)."""
        self._call("POST", "/actions/stop_all_jobs")
