"""One wire protocol for talking to host agents (placement/agent.py).

Both the control plane (placement/hosts.py `_AgentHandle`) and the serving
data plane (cache/fleet.py `HttpWorkerQueue`) speak to agents; this is the
single copy of the request/auth/error-decode logic so the two cannot
drift. Callers map the two error types onto their own domains.

Fleet health hardening lives here too, shared by both planes:

- **Bounded retry** with exponential backoff + jitter for *idempotent*
  calls (GETs by default; callers assert idempotency for POSTs like
  ``/services/<id>/stop``). Non-idempotent calls never retry — the caller
  owns the ambiguous-create problem (placement/hosts.py).
- **Per-agent circuit breaker**: consecutive transport failures open the
  circuit; while open every call fails fast (<1 ms, vs the 10 s transport
  timeout) with :class:`AgentCircuitOpenError`; after a cooldown one
  half-open probe is let through — success closes the circuit, failure
  re-opens it. An HTTP-level error is a breaker *success* (the host
  answered); only transport failures count against it.
- **Fault injection**: the ``RAFIKI_CHAOS`` hook (utils/chaos.py) fires
  inside the attempt loop, so injected faults exercise the retry and
  breaker machinery exactly like real ones.
"""

from __future__ import annotations

import http.client
import json
import logging
import random
import threading
import time
import urllib.error
import urllib.request
from typing import Any, Dict, Optional

from rafiki_tpu import config
from rafiki_tpu.utils import chaos

logger = logging.getLogger(__name__)

AGENT_KEY_HEADER = "X-Rafiki-Agent-Key"
# control-plane HA (docs/failure-model.md "Control-plane HA"): the
# admin's leadership epoch rides every control call; agents remember the
# highest epoch seen and answer STALE_EPOCH_STATUS to any mutating call
# carrying a lower one — the agent-side half of epoch fencing.
ADMIN_EPOCH_HEADER = "X-Rafiki-Admin-Epoch"
STALE_EPOCH_STATUS = 412  # Precondition Failed: typed, never retried

# breaker states (surfaced by placement/hosts.py agent_health and doctor)
BREAKER_CLOSED = "CLOSED"
BREAKER_OPEN = "OPEN"
BREAKER_HALF_OPEN = "HALF_OPEN"


class AgentHTTPError(Exception):
    """The agent answered with an error status; ``code``/``message``
    carry the decoded payload."""

    def __init__(self, code: int, message: str):
        super().__init__(message)
        self.code = code
        self.message = message


class AgentTransportError(Exception):
    """The agent could not be reached (connect/timeout/socket error)."""


class AgentCircuitOpenError(AgentTransportError):
    """Fail-fast refusal: this agent's circuit breaker is open. Subclasses
    AgentTransportError so existing callers treat it as unreachable."""


class CircuitBreaker:
    """Per-agent breaker: CLOSED -> (threshold consecutive transport
    failures) -> OPEN -> (cooldown elapses) -> HALF_OPEN, where exactly one
    probe call is admitted; its outcome closes or re-opens the circuit."""

    def __init__(self, threshold: int, cooldown_s: float):
        self.threshold = max(int(threshold), 1)
        self.cooldown_s = float(cooldown_s)
        self._lock = threading.Lock()
        self._failures = 0
        self._state = BREAKER_CLOSED
        self._opened_at = 0.0
        self._probing = False

    @property
    def state(self) -> str:
        with self._lock:
            if (self._state == BREAKER_OPEN
                    and time.monotonic() - self._opened_at >= self.cooldown_s):
                return BREAKER_HALF_OPEN
            return self._state

    def allow(self) -> bool:
        """May a call proceed right now? In the half-open window only one
        in-flight probe is admitted; siblings keep failing fast until its
        verdict lands."""
        with self._lock:
            if self._state == BREAKER_CLOSED:
                return True
            if self._state == BREAKER_HALF_OPEN:
                if self._probing:
                    return False
                self._probing = True
                return True
            if time.monotonic() - self._opened_at >= self.cooldown_s:
                self._state = BREAKER_HALF_OPEN
                self._probing = True
                return True
            return False

    def record_success(self) -> None:
        with self._lock:
            self._failures = 0
            self._state = BREAKER_CLOSED
            self._probing = False

    def record_failure(self) -> None:
        with self._lock:
            self._failures += 1
            if (self._state != BREAKER_CLOSED
                    or self._failures >= self.threshold):
                self._state = BREAKER_OPEN
                self._opened_at = time.monotonic()
                self._probing = False


_breakers: Dict[str, CircuitBreaker] = {}
_breakers_lock = threading.Lock()


def get_breaker(addr: str) -> CircuitBreaker:
    with _breakers_lock:
        br = _breakers.get(addr)
        if br is None:
            br = _breakers[addr] = CircuitBreaker(
                config.AGENT_BREAKER_THRESHOLD,
                config.AGENT_BREAKER_COOLDOWN_S)
        return br


def reset_breaker(addr: Optional[str] = None) -> None:
    """Close one agent's breaker (heartbeat recovery) or, with no addr,
    drop the whole registry (test isolation)."""
    with _breakers_lock:
        if addr is None:
            _breakers.clear()
        elif addr in _breakers:
            _breakers[addr].record_success()


def breaker_states() -> Dict[str, str]:
    with _breakers_lock:
        return {addr: br.state for addr, br in _breakers.items()}


def _raw_call(
    addr: str,
    method: str,
    path: str,
    body: Optional[Dict[str, Any]],
    key: Optional[str],
    timeout_s: float,
    wire_frames: bool = False,
    epoch: Optional[int] = None,
) -> Dict[str, Any]:
    rule = chaos.hit(chaos.SITE_CALL_AGENT, f"{addr} {path}")
    if rule is not None:
        if rule.action == chaos.ACTION_DELAY:
            chaos.sleep_for(rule)
        elif rule.action == chaos.ACTION_DROP:
            raise AgentTransportError(f"{addr}: chaos-injected drop")
        elif rule.action == chaos.ACTION_ERROR:
            raise AgentHTTPError(rule.code, "chaos-injected error")
    url = f"http://{addr}{path}"
    # the serving data plane (cache/fleet.py) negotiates the binary wire
    # codec: ndarrays in `body` ride as raw bytes instead of JSON float
    # text. Control-plane calls stay plain JSON. Responses are sniffed
    # either way, so a binary-answering peer never needs a second flag.
    from rafiki_tpu.cache import wire as _wire

    data = None
    ctype = "application/json"
    if body is not None:
        if wire_frames:
            data = _wire.dumps(body)  # JSON framing if RAFIKI_WIRE_BINARY=0
            if _wire.is_frame(data):
                ctype = _wire.CONTENT_TYPE
        else:
            # jsonutil convention: ndarrays as float text — the shape
            # data-plane bodies take when the peer can't decode frames
            from rafiki_tpu.utils.jsonutil import json_default

            data = json.dumps(body, default=json_default).encode()
    req = urllib.request.Request(url, data=data, method=method)
    req.add_header("Content-Type", ctype)
    if key:
        req.add_header(AGENT_KEY_HEADER, key)
    if epoch is not None:
        req.add_header(ADMIN_EPOCH_HEADER, str(int(epoch)))
    try:
        with urllib.request.urlopen(req, timeout=timeout_s) as resp:
            raw = resp.read() or b"{}"
            if _wire.is_frame(raw):
                try:
                    return _wire.decode(raw)
                except _wire.WireFormatError as e:
                    raise AgentTransportError(
                        f"{addr}: garbled wire response: {e}") from e
            return json.loads(raw)
    except urllib.error.HTTPError as e:
        try:
            message = json.loads(e.read() or b"{}").get("error", str(e))
        except (ValueError, TypeError):
            message = str(e)
        raise AgentHTTPError(e.code, message) from e
    except (urllib.error.URLError, OSError, TimeoutError,
            http.client.HTTPException) as e:
        # HTTPException covers garbled/truncated responses (BadStatusLine,
        # IncompleteRead) that urllib does not wrap — a half-dead host
        raise AgentTransportError(f"{addr}: {e}") from e


def call_agent(
    addr: str,
    method: str,
    path: str,
    body: Optional[Dict[str, Any]] = None,
    key: Optional[str] = None,
    timeout_s: float = 10.0,
    idempotent: Optional[bool] = None,
    use_breaker: bool = True,
    wire_frames: bool = False,
    epoch: Optional[int] = None,
) -> Dict[str, Any]:
    """One request to a host agent, with retry + circuit breaking.

    ``idempotent`` (default: GETs only) enables bounded retry with
    exponential backoff + jitter on transport failures. ``use_breaker``
    is disabled only by the heartbeat monitor, whose probes must reach
    the wire regardless of breaker state — they ARE the recovery signal.
    ``wire_frames`` ships the body as one binary wire frame
    (cache/wire.py) — data-plane callers only, after negotiating support
    via the agent's /healthz ``wire_versions`` advertisement.
    ``epoch`` stamps the admin's leadership epoch on the request
    (control-plane HA): the agent refuses mutating calls from a lower
    epoch with STALE_EPOCH_STATUS — an AgentHTTPError here, which never
    retries (the host answered; the refusal is the answer).
    """
    if idempotent is None:
        idempotent = method.upper() == "GET"
    breaker = get_breaker(addr) if use_breaker else None
    if breaker is not None and not breaker.allow():
        raise AgentCircuitOpenError(
            f"{addr}: circuit open (agent recently unreachable; next probe "
            f"within {breaker.cooldown_s:.1f}s)")
    attempts = 1 + (config.AGENT_RETRY_MAX if idempotent else 0)
    backoff = config.AGENT_RETRY_BACKOFF_S
    last: Optional[AgentTransportError] = None
    for attempt in range(attempts):
        if attempt:
            # full jitter on an exponential base: decorrelates the retry
            # storms of many callers hitting one recovering agent
            time.sleep(backoff * (2 ** (attempt - 1)) * random.uniform(0.5, 1.5))
        try:
            out = _raw_call(addr, method, path, body, key, timeout_s,
                            wire_frames=wire_frames, epoch=epoch)
        except AgentHTTPError:
            # the host answered — alive, whatever the status code says
            if breaker is not None:
                breaker.record_success()
            raise
        except AgentTransportError as e:
            last = e
            from rafiki_tpu.utils.metrics import REGISTRY

            REGISTRY.counter(
                "rafiki_agent_transport_failures_total",
                "agent calls that failed at the transport layer").inc()
            if breaker is not None:
                breaker.record_failure()
                if attempt + 1 < attempts and not breaker.allow():
                    break  # retries must not slip through an open circuit
            if attempt + 1 < attempts:
                logger.info("agent %s transport failure (%s); retry %d/%d",
                            addr, e, attempt + 1, attempts - 1)
            continue
        except BaseException:
            # anything unexpected must still release a half-open probe
            # slot, or the breaker would fence this agent forever
            if breaker is not None:
                breaker.record_failure()
            raise
        if breaker is not None:
            breaker.record_success()
        return out
    assert last is not None
    raise last
