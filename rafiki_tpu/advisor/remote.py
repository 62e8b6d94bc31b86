"""Advisor sessions over HTTP — the out-of-process worker's view.

The reference's train workers talked to a separate advisor Flask service
over HTTP (reference rafiki/worker/train.py:207-215, advisor/app.py:17-50).
Here the advisor store lives inside the Admin process and is exposed on the
admin REST API (`/advisors/*`, admin/http.py); `RemoteAdvisorStore` adapts
that API to the in-process `AdvisorStore` interface the TrainWorker consumes
— so parallel worker *processes* of one sub-train-job still coordinate
through the single shared GP (the fix for reference train.py:213's
uncoordinated parallel HPO carries over to multi-process placement).

Control-plane crash tolerance: the admin may die and restart UNDER a
running worker (docs/failure-model.md "Control-plane faults" — the worker
is exactly what boot reconciliation adopts). Advisor calls therefore ride
out transport failures and the recovering-503 with bounded backoff
(``RAFIKI_ADVISOR_RETRY_S``, default 60 s; 0 disables) instead of
erroring the executor on the first connection-refused.
"""

from __future__ import annotations

import logging
import os
import random
import time
from typing import Any, Dict, List, Optional

import requests

from rafiki_tpu.client.client import AdminRecoveringError, Client, RafikiError
from rafiki_tpu.sdk.knob import serialize_knob_config

logger = logging.getLogger(__name__)


def _retry_window_s() -> float:
    return float(os.environ.get("RAFIKI_ADVISOR_RETRY_S", "60"))


def _ride_out(fn, what: str):
    """Run one advisor API call, riding out a dead/restarting admin:
    transport failures and the recovering 503 retry with jittered backoff
    until the window closes, then the last error propagates (the worker's
    own crash handling takes over).

    Retrying the mutating calls is a deliberate tradeoff: a request whose
    response was lost AFTER the admin applied it re-applies on retry. A
    duplicate GP observation is tolerable noise (worker/train.py makes
    the same call on its replay path), and ASHA rung reports are
    idempotent per (trial, rung) (advisor/asha.py records each rung
    once) — whereas NOT retrying kills the executor on the first
    connection blip, which is the failure this wrapper exists to stop."""
    deadline = time.monotonic() + _retry_window_s()
    delay = 0.2
    while True:
        try:
            return fn()
        except (requests.RequestException, AdminRecoveringError) as e:
            if time.monotonic() >= deadline:
                raise
            logger.warning(
                "advisor call %s failed (%s: %s); admin may be "
                "restarting — retrying for up to RAFIKI_ADVISOR_RETRY_S",
                what, type(e).__name__, e)
            time.sleep(delay * random.uniform(0.5, 1.5))
            delay = min(delay * 2, 5.0)


class _RemoteAdvisor:
    """Duck-types BaseAdvisor for the calls TrainWorker makes on it."""

    def __init__(self, client: Client, advisor_id: str):
        self._client = client
        self._id = advisor_id

    def feedback(self, knobs: Dict[str, Any], score: float) -> None:
        _ride_out(
            lambda: self._client.feedback_knobs(self._id, knobs,
                                                float(score)),
            "feedback")

    def feedback_infeasible(self, knobs: Dict[str, Any],
                            kind: str = "USER") -> None:
        _ride_out(
            lambda: self._client.feedback_infeasible_knobs(
                self._id, knobs, kind=kind),
            "feedback_infeasible")


class RemoteAdvisorStore:
    """AdvisorStore facade over the admin REST API (duck-typed; the
    TrainWorker never imports the concrete class)."""

    def __init__(self, client: Client):
        self._client = client
        # None = unknown, False = the admin answered an API error on a
        # batch route (pre-batch-API admin; probed once, then remembered)
        self._batch_api: Optional[bool] = None

    def create_advisor(self, knob_config: Dict[str, Any],
                       advisor_id: Optional[str] = None) -> str:
        return _ride_out(
            lambda: self._client.create_advisor(
                serialize_knob_config(knob_config), advisor_id=advisor_id),
            "create_advisor")

    def propose(self, advisor_id: str) -> Dict[str, Any]:
        return _ride_out(
            lambda: self._client.propose_knobs(advisor_id), "propose")

    def propose_batch(self, advisor_id: str, k: int) -> List[Dict[str, Any]]:
        """K proposals in one round trip. A mixed-version fleet (new
        worker, old admin without the /propose_batch route) degrades to
        K single proposals — the admin's shared GP still spreads them
        via its pending fantasies, the worker just pays K round trips."""
        k = max(int(k), 1)
        if self._batch_api is False:
            return [self.propose(advisor_id) for _ in range(k)]
        try:
            out = _ride_out(
                lambda: self._client.propose_knobs_batch(advisor_id, k),
                "propose_batch")
            self._batch_api = True
            return out
        except AdminRecoveringError:
            raise  # a recovering admin is not an OLD admin — let it retry
        except RafikiError as e:
            # latch the no-batch-API verdict ONLY on a missing route
            # (404): a transient refusal (503 overload shed, a flaky 500)
            # must not silently downgrade every later round to K serial
            # proposals — re-raise and let the caller handle this round
            if getattr(e, "status", None) != 404:
                raise
            self._batch_api = False
            logger.info(
                "admin has no batched advisor API (%s); falling back to "
                "single proposals for this session", e)
            return [self.propose(advisor_id) for _ in range(k)]

    def feedback_batch(self, advisor_id: str, items) -> int:
        if self._batch_api is False:
            for knobs, score in items:
                self.feedback(advisor_id, knobs, float(score))
            return len(items)
        try:
            out = int(_ride_out(
                lambda: self._client.feedback_knobs_batch(advisor_id, items),
                "feedback_batch"))
            self._batch_api = True
            return out
        except AdminRecoveringError:
            raise
        except RafikiError as e:
            if getattr(e, "status", None) != 404:
                raise  # transient refusal, not a pre-batch-API admin
            self._batch_api = False
            logger.info(
                "admin has no batched advisor API (%s); falling back to "
                "single feedback calls for this session", e)
            for knobs, score in items:
                self.feedback(advisor_id, knobs, float(score))
            return len(items)

    def feedback(self, advisor_id: str, knobs: Dict[str, Any],
                 score: float) -> Dict[str, Any]:
        return _ride_out(
            lambda: self._client.feedback_knobs(advisor_id, knobs,
                                                float(score)),
            "feedback")

    def get(self, advisor_id: str) -> _RemoteAdvisor:
        return _RemoteAdvisor(self._client, advisor_id)

    def feedback_infeasible(self, advisor_id: str, knobs: Dict[str, Any],
                            kind: str = "USER",
                            trial_id: Optional[str] = None) -> int:
        """Scoreless-failure signal (trial fault classification) over the
        admin API — same ride-out semantics as feedback: re-applying on
        a lost response adds one duplicate penalty point, which the GP
        tolerates."""
        return _ride_out(
            lambda: self._client.feedback_infeasible_knobs(
                advisor_id, knobs, kind=kind, trial_id=trial_id),
            "feedback_infeasible")

    def replay_feedback(self, advisor_id: str, items,
                        infeasible=None) -> bool:
        return _ride_out(
            lambda: self._client.replay_advisor_feedback(
                advisor_id, items, infeasible=infeasible),
            "replay_feedback")

    def report_rung(self, advisor_id: str, trial_id: str, resource: int,
                    value: float, min_resource: int = 1, eta: int = 3,
                    mode: str = "min") -> bool:
        return _ride_out(
            lambda: self._client.report_rung(
                advisor_id, trial_id, resource, value,
                min_resource=min_resource, eta=eta, mode=mode),
            "report_rung")

    def delete_advisor(self, advisor_id: str) -> None:
        # teardown is best-effort: never worth stalling a stop on
        self._client.delete_advisor(advisor_id)
