"""Advisor sessions: propose/feedback over knob configs.

Parity with the reference's advisor layer (reference
rafiki/advisor/advisor.py:8-62 and advisor/service.py:15-79): a ``BaseAdvisor``
contract, a GP-backed default, and a sessionized store keyed by advisor id.
The store is thread-safe (the reference instead forced its Flask advisor app
single-threaded, reference scripts/start_advisor.py:10).
"""

from __future__ import annotations

import logging
import threading
import uuid
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from rafiki_tpu.advisor.gp import BayesOpt
from rafiki_tpu.sdk.knob import (
    KnobConfig,
    knob_config_dims,
    knobs_from_unit,
    knobs_to_unit,
)


def _jsonify(value: Any) -> Any:
    """Simplify numpy scalars into JSON-native types (reference
    rafiki/advisor/advisor.py:44-62 did the same for BTB proposals)."""
    if isinstance(value, np.integer):
        return int(value)
    if isinstance(value, np.floating):
        return float(value)
    if isinstance(value, np.bool_):
        return bool(value)
    if isinstance(value, dict):
        return {k: _jsonify(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [_jsonify(v) for v in value]
    return value


class BaseAdvisor:
    """Contract: propose a knob assignment; feed back its achieved score.

    ``observation_count`` is part of the contract: the store's
    ``replay_feedback`` empty-only guard depends on every advisor type
    reporting how many observations it holds."""

    def __init__(self, knob_config: KnobConfig):
        self.knob_config = knob_config

    def propose(self) -> Dict[str, Any]:
        raise NotImplementedError

    def propose_batch(self, k: int) -> List[Dict[str, Any]]:
        """K knob assignments to evaluate CONCURRENTLY (the vectorized
        trial runner drains one batch per vmapped program). The base
        implementation loops ``propose`` — correct for any advisor type,
        since each advisor is responsible for making sequential proposals
        self-avoiding — so subclasses override only to batch more
        cleverly (the GP spreads the batch via its pending-point
        fantasies in one lock hold)."""
        return [self.propose() for _ in range(max(int(k), 1))]

    def feedback(self, knobs: Dict[str, Any], score: float) -> None:
        raise NotImplementedError

    def feedback_batch(
        self, items: List[Tuple[Dict[str, Any], float]]) -> int:
        """Record a batch of (knobs, score) observations — the return leg
        of ``propose_batch``. Applied member-by-member (each observation
        retires its own pending fantasy); returns how many were
        applied."""
        for knobs, score in items:
            self.feedback(knobs, float(score))
        return len(items)

    def feedback_infeasible(self, knobs: Dict[str, Any],
                            kind: str = "USER") -> None:
        """The trial at ``knobs`` failed WITHOUT a usable score (trial
        fault classification: USER crash, TIMEOUT, INVALID_SCORE). Optional
        signal — the base implementation ignores it, so advisor types
        that can't use it stay valid; advisors that can (the GP) steer
        their proposal distribution away from the region."""

    @property
    def observation_count(self) -> int:
        raise NotImplementedError

    @property
    def infeasible_count(self) -> int:
        return 0


class Advisor(BaseAdvisor):
    """GP Bayesian-optimization advisor (the default).

    Thread-safe: one instance is shared by all parallel workers of a
    sub-train-job, with in-flight proposals fantasized (constant liar) so
    concurrent trials explore different regions.
    """

    def __init__(self, knob_config: KnobConfig, seed: int = 0):
        super().__init__(knob_config)
        self._opt = BayesOpt(knob_config_dims(knob_config), seed=seed)
        self._lock = threading.Lock()

    def propose(self) -> Dict[str, Any]:
        with self._lock:
            return self._propose_locked()

    def _propose_locked(self) -> Dict[str, Any]:
        u = self._opt.suggest(register_pending=False)
        knobs = knobs_from_unit(self.knob_config, u)
        # register the *quantized* point (integer/categorical knobs round
        # to a grid) so feedback's re-encoding retires it by value
        self._opt.mark_pending(knobs_to_unit(self.knob_config, knobs))
        return _jsonify(knobs)

    def propose_batch(self, k: int) -> List[Dict[str, Any]]:
        """K proposals under ONE lock hold, spread by the constant-liar
        fantasy machinery: each draw registers its quantized point as
        pending, so the next draw's EI already sees it fantasized at the
        observed minimum and explores elsewhere (the same mechanism that
        spreads concurrent workers, and that PR 5 extended to infeasible
        points). One lock hold keeps a concurrent sibling worker from
        interleaving draws into the middle of this batch."""
        with self._lock:
            return [self._propose_locked() for _ in range(max(int(k), 1))]

    def feedback(self, knobs: Dict[str, Any], score: float) -> None:
        u = knobs_to_unit(self.knob_config, knobs)
        with self._lock:
            self._opt.observe(u, float(score))

    def feedback_infeasible(self, knobs: Dict[str, Any],
                            kind: str = "USER") -> None:
        u = knobs_to_unit(self.knob_config, knobs)
        with self._lock:
            self._opt.mark_infeasible(u)

    @property
    def history(self) -> List[Tuple[np.ndarray, float]]:
        return list(zip(self._opt.observed_X, self._opt.observed_y))

    @property
    def observation_count(self) -> int:
        return len(self._opt.observed_y)

    @property
    def infeasible_count(self) -> int:
        return len(self._opt.infeasible_X)


class RandomAdvisor(BaseAdvisor):
    """Uniform random search baseline."""

    def __init__(self, knob_config: KnobConfig, seed: int = 0):
        super().__init__(knob_config)
        self._rng = np.random.default_rng(seed)
        self._dims = knob_config_dims(knob_config)
        self._n_observed = 0

    def propose(self) -> Dict[str, Any]:
        return _jsonify(knobs_from_unit(self.knob_config, self._rng.random(self._dims)))

    def propose_batch(self, k: int) -> List[Dict[str, Any]]:
        # one rng draw for the whole batch (random search needs no
        # spreading machinery — uniform draws are already independent)
        u = self._rng.random((max(int(k), 1), self._dims))
        return [_jsonify(knobs_from_unit(self.knob_config, row))
                for row in u]

    def feedback(self, knobs: Dict[str, Any], score: float) -> None:
        self._n_observed += 1

    def feedback_infeasible(self, knobs: Dict[str, Any],
                            kind: str = "USER") -> None:
        # random search has no model to steer; count for observability
        self._n_infeasible = getattr(self, "_n_infeasible", 0) + 1

    @property
    def observation_count(self) -> int:
        return self._n_observed

    @property
    def infeasible_count(self) -> int:
        return getattr(self, "_n_infeasible", 0)


class AdvisorStore:
    """Sessionized advisor registry (reference rafiki/advisor/service.py kept
    an in-memory dict behind Flask; here it's an explicit thread-safe store
    usable in-process or behind the admin HTTP API)."""

    _TYPES = {"GP": Advisor, "RANDOM": RandomAdvisor}

    def __init__(self) -> None:
        self._advisors: Dict[str, BaseAdvisor] = {}
        self._schedulers: Dict[str, Any] = {}  # advisor_id -> AshaScheduler
        self._lock = threading.Lock()

    def create_advisor(
        self,
        knob_config: KnobConfig,
        advisor_id: Optional[str] = None,
        advisor_type: str = "GP",
    ) -> str:
        advisor_id = advisor_id or uuid.uuid4().hex
        with self._lock:
            if advisor_id not in self._advisors:
                self._advisors[advisor_id] = self._TYPES[advisor_type](knob_config)
        return advisor_id

    def get(self, advisor_id: str) -> BaseAdvisor:
        with self._lock:
            if advisor_id not in self._advisors:
                raise KeyError(f"No such advisor: {advisor_id}")
            return self._advisors[advisor_id]

    def propose(self, advisor_id: str) -> Dict[str, Any]:
        return self.get(advisor_id).propose()

    def propose_batch(self, advisor_id: str, k: int) -> List[Dict[str, Any]]:
        """K concurrent proposals (the vectorized trial runner's drain).
        Advisors predating the batch API fall back to K single proposals
        — old advisor types keep working behind a new store."""
        advisor = self.get(advisor_id)
        fn = getattr(advisor, "propose_batch", None)
        if fn is not None:
            return fn(k)
        return [advisor.propose() for _ in range(max(int(k), 1))]

    def feedback_batch(
        self,
        advisor_id: str,
        items: List[Tuple[Dict[str, Any], float]],
    ) -> int:
        """Record a batch of (knobs, score) pairs member-by-member;
        returns how many observations were applied. Same pre-batch-API
        fallback as ``propose_batch``."""
        advisor = self.get(advisor_id)
        fn = getattr(advisor, "feedback_batch", None)
        if fn is not None:
            return int(fn(items))
        for knobs, score in items:
            advisor.feedback(knobs, float(score))
        return len(items)

    def feedback(self, advisor_id: str, knobs: Dict[str, Any], score: float) -> Dict[str, Any]:
        """Record a score; returns the next proposal (matching the
        reference's feedback-returns-next-proposal API, reference
        advisor/service.py:62-70)."""
        advisor = self.get(advisor_id)
        advisor.feedback(knobs, score)
        return advisor.propose()

    def feedback_infeasible(
        self,
        advisor_id: str,
        knobs: Dict[str, Any],
        kind: str = "USER",
        trial_id: Optional[str] = None,
    ) -> int:
        """Record a scoreless failure at ``knobs`` (trial fault classification
        USER/TIMEOUT/INVALID_SCORE): the advisor steers its proposals
        away, and — when ``trial_id`` is given — the session's ASHA
        scheduler forgets the trial's rung records so a crashed trial's
        partial metrics can't set promotion bars for healthy ones.
        Returns the session's infeasible count (observability)."""
        advisor = self.get(advisor_id)
        advisor.feedback_infeasible(knobs, kind)
        if trial_id is not None:
            with self._lock:
                sched = self._schedulers.get(advisor_id)
            if sched is not None:
                sched.forget(trial_id)
        return advisor.infeasible_count

    def replay_feedback(
        self,
        advisor_id: str,
        items: List[Tuple[Dict[str, Any], float]],
        infeasible: Optional[List[Tuple[Dict[str, Any], str]]] = None,
    ) -> bool:
        """Seed a FRESH advisor session with already-scored (knobs, score)
        pairs — how a restarted worker rebuilds the GP from the completed
        trials already in the store. Atomic and empty-only: if the session
        has any observations (it survived, or a sibling already replayed),
        this is a no-op returning False, so concurrent restarts can't
        double-feed the optimizer. (Workers also feed back BEFORE marking a
        trial COMPLETED, so a trial visible as COMPLETED implies its score
        is already in a surviving session — the guard and that ordering
        together close the double-feed window.)

        ``infeasible`` — (knobs, fault_kind) pairs from USER/TIMEOUT/
        INVALID_SCORE-errored trials — rides the same guard: a fresh
        session relearns which regions crash, not just which scored."""
        with self._lock:
            advisor = self._advisors.get(advisor_id)
            if advisor is None:
                raise KeyError(f"No such advisor: {advisor_id}")
            # infeasible points count toward "not fresh" too: a session
            # that survived with ONLY infeasible history (every early
            # trial crashed) must not re-accumulate duplicates on each
            # worker restart of a crash-looping job
            if advisor.observation_count > 0 \
                    or getattr(advisor, "infeasible_count", 0) > 0:
                return False
            for knobs, score in items:
                advisor.feedback(knobs, float(score))
            for knobs, kind in infeasible or []:
                advisor.feedback_infeasible(knobs, str(kind))
            return True

    def report_rung(self, advisor_id: str, trial_id: str, resource: int,
                    value: float, min_resource: int = 1, eta: int = 3,
                    mode: str = "min") -> bool:
        """ASHA early-stop check: record an intermediate metric for a trial
        and return whether it should continue (advisor/asha.py). The
        scheduler shares the advisor session's lifecycle, so parallel
        workers of one sub-train-job compete within one rung population —
        like the shared GP."""
        from rafiki_tpu.advisor.asha import AshaScheduler

        with self._lock:
            if advisor_id not in self._advisors:
                raise KeyError(f"No such advisor: {advisor_id}")
            sched = self._schedulers.get(advisor_id)
            if sched is None:
                sched = self._schedulers[advisor_id] = AshaScheduler(
                    min_resource=min_resource, eta=eta, mode=mode)
            elif (sched.min_resource, sched.eta, sched.mode) != (
                    max(int(min_resource), 1), int(eta), mode):
                # the scheduler is shared per session and configured by
                # whoever reports first; a divergent caller (worker
                # restarted with a changed budget against a live admin)
                # competes under the existing ladder — say so, don't
                # silently ignore the requested parameters
                logging.getLogger(__name__).warning(
                    "ASHA params (%s,%s,%s) differ from session %s's "
                    "live scheduler (%s,%s,%s); using the existing one",
                    min_resource, eta, mode, advisor_id,
                    sched.min_resource, sched.eta, sched.mode)
        return sched.report(trial_id, resource, value)

    def delete_advisor(self, advisor_id: str) -> None:
        with self._lock:
            self._advisors.pop(advisor_id, None)
            self._schedulers.pop(advisor_id, None)
