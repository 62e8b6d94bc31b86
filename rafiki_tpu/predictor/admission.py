"""Deadline-aware admission control for the serving doors.

Both serving doors — the per-job ``PredictorServer`` and the admin's
``/predict/<app>`` route — sit on ``ThreadingHTTPServer``, which happily
spawns one handler thread per connection forever. Under overload that is
the metastable failure of "The Tail at Scale": every queued request is
eventually served (long after its client gave up), each one slower than
the last. This module is the shared front gate:

- a **bounded in-flight semaphore** (``RAFIKI_PREDICT_MAX_INFLIGHT``):
  requests beyond the cap are shed instantly with ``503`` — capacity is
  the model fleet, not the thread scheduler;
- an **estimated-wait check**: if the backlog already implies a wait
  longer than the request's own deadline, admitting it only burns model
  time on a doomed request — shed with ``429`` + ``Retry-After`` so
  well-behaved clients back off;
- **counters** (admitted/shed/in-flight + an EWMA of per-query service
  time) surfaced through ``/healthz`` and ``GET /fleet/health``.

Shed-code contract (docs/failure-model.md "Overload faults"): ``429``
means *retryable later* — the queue is full or the wait exceeds your
deadline, and ``Retry-After`` says when to come back; ``503`` means *no
capacity right now* — in-flight slots are gone, retry is the client's
call. Neither code is ever sent after work started; a shed request costs
the server microseconds.
"""

from __future__ import annotations

import math
import threading
import time
from typing import Dict, Optional, Tuple

# parsed RAFIKI_AUTOSCALE_FAIR_WEIGHTS cache: (raw_value, {tenant: w})
_weights_cache: Tuple[Optional[str], Dict[str, float]] = (None, {})
_weights_lock = threading.Lock()


def _fair_weights() -> Dict[str, float]:
    """{tenant: weight} from RAFIKI_AUTOSCALE_FAIR_WEIGHTS
    ("appA=3,appB=1"); unlisted tenants weigh 1. Parsed once per distinct
    env value — this sits on the admission hot path."""
    from rafiki_tpu import config

    global _weights_cache
    raw = str(config.AUTOSCALE_FAIR_WEIGHTS)
    cached_raw, cached = _weights_cache
    if raw == cached_raw:
        return cached
    weights: Dict[str, float] = {}
    for part in raw.split(","):
        part = part.strip()
        if not part or "=" not in part:
            continue
        name, _, val = part.partition("=")
        try:
            w = float(val)
        except ValueError:
            continue
        if w > 0:
            weights[name.strip()] = w
    with _weights_lock:
        _weights_cache = (raw, weights)
    return weights


class ServerOverloadedError(RuntimeError):
    """The door's in-flight cap is exhausted (HTTP 503)."""

    def __init__(self, message: str, retry_after_s: float = 1.0) -> None:
        super().__init__(message)
        self.retry_after_s = max(float(retry_after_s), 0.0)


class DeadlineUnmeetableError(RuntimeError):
    """The estimated queue wait already exceeds the request's deadline
    (HTTP 429 + Retry-After): admitting it would spend model time on an
    answer nobody will read."""

    def __init__(self, message: str, retry_after_s: float = 1.0) -> None:
        super().__init__(message)
        self.retry_after_s = max(float(retry_after_s), 0.0)


class TenantOverShareError(DeadlineUnmeetableError):
    """The tenant is past its weighted fair share of admitted queries
    while the door is under pressure (HTTP 429 + Retry-After). Subclasses
    :class:`DeadlineUnmeetableError` so every door's shed mapping answers
    it retryable without new handler wiring — but the shed is PER-TENANT:
    the hot job backing off is exactly what keeps the cold jobs' latency
    (docs/failure-model.md "Overload adaptation")."""


def retry_after_headers(e: Exception) -> Dict[str, str]:
    """The Retry-After header (whole seconds, >= 1) from a shed error's
    estimate — THE one copy of the contract, used by every door."""
    return {"Retry-After": str(max(
        1, math.ceil(getattr(e, "retry_after_s", 1.0))))}


class AdmissionController:
    """One per serving door. Thread-safe; all operations are O(1) and
    lock-held for nanoseconds — this gate must stay cheap precisely when
    the server is busiest.

    ``door`` labels this controller's registry metrics (utils/metrics.py):
    per-door admitted/shed counters, an in-flight gauge, the EWMA-wait
    gauge, and the ``rafiki_request_seconds`` latency histogram fed by
    :meth:`observe` — the source of the bench's door-side p50/p95/p99.
    The JSON ``stats()`` shape is unchanged (per-controller ints,
    incremented at the same sites as the registry mirrors)."""

    def __init__(self, max_inflight: Optional[int] = None,
                 door: str = "predictor",
                 shared_tenants: bool = False) -> None:
        #: None defers to RAFIKI_PREDICT_MAX_INFLIGHT lazily per admit
        self._max_inflight = max_inflight
        #: True for doors several tenants enter (the admin /predict/<app>
        #: route); gates the per-tenant in-flight ceiling — a dedicated
        #: per-job door has ONE tenant by construction and may use every
        #: slot itself
        self._shared_tenants = shared_tenants
        self._lock = threading.Lock()
        self._inflight = 0
        self._admitted = 0
        self._shed_capacity = 0   # 503s
        self._shed_deadline = 0   # 429s
        # EWMA of per-query service seconds, admission's unit of wait
        # estimation; 0.0 until the first observation (estimate disabled —
        # never shed on a guess)
        self._ewma_query_s = 0.0
        self.door = door
        from rafiki_tpu.utils.metrics import REGISTRY

        self._m_admitted = REGISTRY.counter(
            "rafiki_admission_admitted_total",
            "requests admitted through a serving door", ("door",)
        ).labels(door)
        shed = REGISTRY.counter(
            "rafiki_admission_shed_total",
            "requests shed at a serving door (reason: capacity=503, "
            "deadline=429)", ("door", "reason"))
        self._m_shed_capacity = shed.labels(door, "capacity")
        self._m_shed_deadline = shed.labels(door, "deadline")
        self._m_shed_fairness = shed.labels(door, "fairness")
        # -- multi-tenant weighted fair admission (RAFIKI_AUTOSCALE_FAIR).
        # Deficit-style accounting on ADMITTED QUERIES: each tenant
        # carries a decaying charge of what it was actually granted; when
        # the door is under pressure, a tenant whose charge is past its
        # weighted fair share of the total is shed with 429 while tenants
        # under their share keep being admitted — degradation becomes
        # per-tenant, not global. {tenant: [charge, last_decay_monotonic]}
        self._fair: Dict[str, list] = {}
        # {tenant: slots currently held} for the in-flight ceiling —
        # release(tenant=) is the decrement
        self._fair_inflight: Dict[str, int] = {}
        self._shed_fairness = 0
        self._last_shed_mono = 0.0
        self._g_inflight = REGISTRY.gauge(
            "rafiki_admission_inflight",
            "requests currently in flight behind a serving door",
            ("door",)).labels(door)
        self._g_ewma = REGISTRY.gauge(
            "rafiki_admission_ewma_query_seconds",
            "EWMA of per-query service seconds (the wait-estimation "
            "unit)", ("door",)).labels(door)
        self._h_request = REGISTRY.histogram(
            "rafiki_request_seconds",
            "end-to-end served-request latency at a serving door",
            ("door",)).labels(door)
        # autoscaler-grade ring series (~1 s resolution, bounded window).
        # One ring per door: the admin door and every per-app predictor
        # door live in one process, and a shared ring would clobber their
        # samples into one interleaved series no control loop could read.
        self._ring_shed = REGISTRY.ring(f"shed_rate:{door}")
        self._ring_wait = REGISTRY.ring(f"ewma_wait_s:{door}")
        # EWMA cold start: a FRESH controller (rebound door after crash
        # recovery, a door for a just-scaled job) has no latency history,
        # so the estimated-wait check is disabled for its first requests —
        # under a flood at cold start that admits a pile of doomed work.
        # The process registry outlives any one controller: seed from the
        # door's running request-latency histogram when it has history.
        # Median REQUEST latency over-estimates per-QUERY time, which is
        # the conservative direction (shed slightly early, never admit
        # blind); the first real observe() blends it toward truth.
        seed = self._h_request.quantile(0.5)
        if seed is not None and seed > 0:
            self._ewma_query_s = float(seed)

    def _cap(self) -> int:
        if self._max_inflight is not None:
            return self._max_inflight
        from rafiki_tpu import config

        return int(config.PREDICT_MAX_INFLIGHT)

    # -- admission ---------------------------------------------------------

    def admit(self, timeout_s: float,
              backlog_depth: Optional[int] = None,
              tenant: Optional[str] = None, cost: int = 1) -> None:
        """Claim one in-flight slot or raise a shed error. The caller MUST
        pair a successful admit with :meth:`release` (try/finally).

        ``backlog_depth`` is the least-loaded replica path's queue depth
        (``Predictor.min_backlog_depth``); with a service-time EWMA it
        yields the estimated wait this request would face.

        ``tenant`` names the requesting job/app for the weighted-fair
        gate (``RAFIKI_AUTOSCALE_FAIR``); ``cost`` is the query count the
        tenant is charged on admission. ``None`` (every pre-existing call
        site) skips fairness entirely. ``cost=0`` is legal — a request
        the prediction cache will answer entirely still claims an
        in-flight slot (the handler thread is real) but charges nothing
        to the fairness book (it sheds no load onto the worker fleet)."""
        with self._lock:
            cap = self._cap()
            if tenant is not None:
                # in-flight ceiling BEFORE the capacity shed: the hot
                # tenant is turned away while slots remain, so the
                # capacity check below still has room for everyone else
                self._fair_ceiling_locked(tenant, cap)
            if cap > 0 and self._inflight >= cap:
                self._shed_capacity += 1
                self._m_shed_capacity.inc()
                self._ring_shed.add()
                self._last_shed_mono = time.monotonic()
                raise ServerOverloadedError(
                    f"serving door at capacity ({self._inflight}/{cap} "
                    f"in flight)",
                    retry_after_s=max(self._ewma_query_s, 1.0))
            if tenant is not None:
                self._fair_gate_locked(tenant, max(int(cost), 0), cap)
            est_wait = (backlog_depth * self._ewma_query_s
                        if backlog_depth and self._ewma_query_s > 0 else 0.0)
            if est_wait > timeout_s > 0:
                self._shed_deadline += 1
                self._m_shed_deadline.inc()
                self._ring_shed.add()
                self._last_shed_mono = time.monotonic()
                raise DeadlineUnmeetableError(
                    f"estimated queue wait {est_wait:.2f}s exceeds the "
                    f"request deadline {timeout_s:.2f}s",
                    retry_after_s=math.ceil(est_wait))
            self._inflight += 1
            self._admitted += 1
            if tenant is not None:
                self._fair_inflight[tenant] = (
                    self._fair_inflight.get(tenant, 0) + 1)
                # charge only what was actually ADMITTED — a request shed
                # at the capacity/deadline/fairness checks above must not
                # inflate the tenant's "admitted queries" book (cost 0:
                # a fully-cache-served request charges nothing)
                self._fair_charge_locked(tenant, max(int(cost), 0))
            self._m_admitted.inc()
            self._g_inflight.inc()

    # -- multi-tenant weighted fairness -------------------------------------

    def _fair_ceiling_locked(self, tenant: str,  # guarded-by: _lock
                             cap: int) -> None:
        """No single tenant may occupy EVERY in-flight slot of a shared
        door (caller holds ``self._lock``). The charge gate below can
        only defend a tenant it has admitted at least once — but a flood
        of SLOW requests from one hot job can hold all ``cap`` slots, so
        a cold tenant's first request would die at the capacity shed
        before any fairness accounting ever saw it. Under
        ``RAFIKI_AUTOSCALE_FAIR`` a tenant already holding ``cap - 1``
        slots is shed 429 instead: one slot always stays winnable by
        someone else."""
        from rafiki_tpu import config

        if cap < 2 or not self._shared_tenants or not config.AUTOSCALE_FAIR:
            return
        held = self._fair_inflight.get(tenant, 0)
        if held >= cap - 1:
            # fairness sheds deliberately do NOT refresh _last_shed_mono:
            # they are a CONSEQUENCE of pressure, and letting them renew
            # the pressure window would self-sustain shedding on a door
            # that has already gone quiet
            self._shed_fairness += 1
            self._m_shed_fairness.inc()
            self._ring_shed.add()
            raise TenantOverShareError(
                f"tenant {tenant!r} already holds {held} of the door's "
                f"{cap} in-flight slots",
                retry_after_s=max(self._ewma_query_s, 1.0))

    def _fair_gate_locked(self, tenant: str, cost: int,  # guarded-by: _lock
                          cap: int) -> None:
        """Deficit-style fair-share check (caller holds ``self._lock``).
        Check only — the charge lands in :meth:`_fair_charge_locked` once
        the request is actually admitted.

        Charges decay with a half-life of ``RAFIKI_AUTOSCALE_FAIR_WINDOW_S``
        so the accounting is a sliding picture of recent admissions, not
        all-time totals. The gate only sheds **under pressure** — the door
        near its in-flight cap, or sheds within the last few seconds;
        an uncontended door admits everyone (fairness is about dividing
        scarcity, not rationing plenty). A dedicated per-job door
        (``shared_tenants=False``) has ONE tenant by construction: its
        charges still accrue (``fair_shares`` observability) but it is
        never rationed against itself."""
        from rafiki_tpu import config

        if not self._shared_tenants or not config.AUTOSCALE_FAIR:
            return
        now = time.monotonic()
        half_life = max(float(config.AUTOSCALE_FAIR_WINDOW_S), 0.5)
        total = 0.0
        for state in self._fair.values():
            dt = now - state[1]
            if dt > 0:
                state[0] *= 0.5 ** (dt / half_life)
                state[1] = now
            total += state[0]
        charge = self._fair.get(tenant, (0.0, now))[0]
        # fairness needs someone to be fair TO: with no OTHER tenant
        # recently active, shedding the only customer serves nobody —
        # and for the sole tenant the share test degenerates to
        # cost > burst, rationing plenty
        others_active = any(
            t != tenant and s[0] > 0.5 for t, s in self._fair.items())
        pressure = ((cap > 0 and self._inflight >= max(cap // 2, 1))
                    or now - self._last_shed_mono < 2.0)
        if pressure and others_active:
            weights = _fair_weights()
            w = weights.get(tenant, 1.0)
            sum_w = sum(
                weights.get(t, 1.0) for t, s in self._fair.items()
                if s[0] > 0.5 or t == tenant)
            if tenant not in self._fair:
                sum_w += w
            fair_share = total * w / max(sum_w, w)
            burst = float(config.AUTOSCALE_FAIR_BURST)
            if charge + cost > fair_share + burst:
                # consequence of pressure, not evidence: see ceiling note
                self._shed_fairness += 1
                self._m_shed_fairness.inc()
                self._ring_shed.add()
                raise TenantOverShareError(
                    f"tenant {tenant!r} is past its weighted fair share "
                    f"({charge:.0f} recent queries vs share "
                    f"{fair_share:.0f} + burst {burst:.0f}) while the "
                    "door is contended",
                    retry_after_s=max(self._ewma_query_s * cost, 1.0))

    def _fair_charge_locked(self, tenant: str,  # guarded-by: _lock
                            cost: int) -> None:
        """Book ``cost`` admitted queries against ``tenant`` (caller holds
        ``self._lock``), decaying the tenant's prior charge to now first."""
        from rafiki_tpu import config

        if not config.AUTOSCALE_FAIR:
            return
        now = time.monotonic()
        state = self._fair.setdefault(tenant, [0.0, now])
        dt = now - state[1]
        if dt > 0:
            half_life = max(float(config.AUTOSCALE_FAIR_WINDOW_S), 0.5)
            state[0] *= 0.5 ** (dt / half_life)
        state[0] += cost
        state[1] = now

    def fair_shares(self) -> Dict[str, float]:
        """Snapshot of the decayed per-tenant admitted-query charges
        (operator view; /healthz + tests)."""
        with self._lock:
            return {t: round(s[0], 3) for t, s in self._fair.items()}

    def note_backend_shed(self) -> None:
        """Book a WHOLE-FLEET-FULL refusal: the door admitted the
        request, then every replica's bounded queue said no
        (``QueueFullError`` out of the predictor). The client saw the
        same 429 + Retry-After as a deadline shed, so it lands in the
        deadline-class books — without this the fleet-full path would be
        invisible to the shed counters, the shed-rate ring the
        autoscaler reads, and the fairness pressure window."""
        with self._lock:
            self._shed_deadline += 1
            self._m_shed_deadline.inc()
            self._ring_shed.add()
            self._last_shed_mono = time.monotonic()

    def release(self, tenant: Optional[str] = None) -> None:
        """Pair of :meth:`admit`. Callers that admitted with a ``tenant``
        must release with the same one (the in-flight ceiling's book)."""
        with self._lock:
            self._inflight = max(self._inflight - 1, 0)
            if tenant is not None:
                held = self._fair_inflight.get(tenant, 0) - 1
                if held > 0:
                    self._fair_inflight[tenant] = held
                else:
                    self._fair_inflight.pop(tenant, None)
            self._g_inflight.set(self._inflight)

    # -- feedback + observability ------------------------------------------

    def observe(self, latency_s: float, n_queries: int,
                service_s: Optional[float] = None) -> None:
        """Feed one served request's latency back into the wait model,
        the door's latency histogram, and the EWMA-wait ring series.
        ``service_s``: the part of the latency that was service, where the
        caller can tell it from the wait before it (a stream's seconds from
        its slot's admission on). The wait model learns from that alone:
        the wait for a slot is the queueing it estimates, and on a replica
        just deployed the first stream also waits for the programs'
        compilation (a minute on a cold cache: taken as service it put the
        estimate past every deadline, and the door shed a sixth of a
        saturated run's streams). The histogram keeps the whole latency.
        The one ``/generate`` door (``predictor/server.py``) always passes
        it; the predict doors, whose batch has no slot to wait for, feed
        the whole latency: the unit is service seconds in both."""
        if n_queries <= 0 or latency_s < 0:
            return
        per_query = (latency_s if service_s is None
                     else max(service_s, 0.0)) / n_queries
        with self._lock:
            if self._ewma_query_s <= 0.0:
                self._ewma_query_s = per_query
            else:
                self._ewma_query_s += 0.2 * (per_query - self._ewma_query_s)
            ewma = self._ewma_query_s
        self._h_request.observe(latency_s)
        self._g_ewma.set(ewma)
        self._ring_wait.record(ewma)

    @property
    def inflight(self) -> int:
        with self._lock:
            return self._inflight

    def stats(self) -> Dict[str, float]:
        with self._lock:
            return {
                "inflight": self._inflight,
                "max_inflight": self._cap(),
                "admitted": self._admitted,
                "shed_capacity": self._shed_capacity,
                "shed_deadline": self._shed_deadline,
                "shed_fairness": self._shed_fairness,
                "ewma_query_s": round(self._ewma_query_s, 6),
            }
