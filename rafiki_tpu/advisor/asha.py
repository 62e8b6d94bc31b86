"""Asynchronous successive halving (ASHA) for early-stopping HPO trials.

A capability the reference lacks entirely: its trials always train to their
full epoch budget (reference worker/train.py:37-132 has no intermediate
signal at all). Here, models that report per-epoch metrics through their
``ModelLogger`` (which every SDK-trainer template does via ``fit(log=...)``)
get rung-based early stopping: at exponentially spaced resource levels
(``min_resource * eta^k`` epochs), a trial continues only while its metric
is competitive with what other trials of the same sub-train-job achieved at
the same rung. Poor knob draws stop after 1-2 epochs instead of burning
their whole budget, so the same trial-count budget explores several times
more of the search space per chip-hour.

This is the asynchronous variant (Li et al., "A System for Massively
Parallel Hyperparameter Tuning", MLSys 2020 — public algorithm): decisions
are made per-report against the rung's current population, with no
synchronized bracket barrier — workers never wait for each other, which is
the property that matters for parallel executors.

Promotion rule: at each rung the trial's value must sit in the top
``1/eta`` fraction of all values recorded at that rung so far. While a rung
has seen fewer than ``eta`` values there is not enough evidence to kill
anything, so reports pass (the permissive async variant — without it, the
second trial of a job dies merely for being worse than the first).
"""

from __future__ import annotations

import math
import threading
from typing import Dict, List


class AshaScheduler:
    """Shared per sub-train-job; thread-safe (parallel workers report
    concurrently, like the shared GP advisor)."""

    def __init__(self, min_resource: int = 1, eta: int = 3,
                 mode: str = "min"):
        if eta < 2:
            raise ValueError(f"eta must be >= 2, got {eta}")
        if mode not in ("min", "max"):
            raise ValueError(f"mode must be 'min' or 'max', got {mode!r}")
        self.min_resource = max(int(min_resource), 1)
        self.eta = int(eta)
        self.mode = mode
        self._lock = threading.Lock()
        # rung resource -> {trial_id: value}; keyed by trial so a trial
        # that later ERRORS can be forgotten (its partial metrics must
        # not set promotion bars for healthy trials — see forget())
        self._rungs: Dict[int, Dict[str, float]] = {}
        self._recorded: Dict[str, set] = {}        # trial -> rungs recorded

    def _rungs_reached(self, resource: int) -> List[int]:
        out, r = [], self.min_resource
        while r <= resource:
            out.append(r)
            r *= self.eta
        return out

    def report(self, trial_id: str, resource: int, value: float) -> bool:
        """Record `value` achieved by `trial_id` at `resource` (e.g. epochs
        completed). Returns True to continue training, False to stop.

        The value is recorded only at the HIGHEST rung this report newly
        reaches — a rung's population must hold values measured *at* that
        resource. Backfilling skipped lower rungs (a trial resumed from a
        late checkpoint after the scheduler restarted, or a template that
        reports every N > 1 epochs) with a later, better value would set an
        unbeatable bar that kills healthy fresh trials; those rungs are
        marked seen without a record instead."""
        value = float(value)
        if not math.isfinite(value):
            return False  # NaN/inf loss: this trial is going nowhere
        with self._lock:
            seen = self._recorded.setdefault(trial_id, set())
            new_rungs = [r for r in self._rungs_reached(int(resource))
                         if r not in seen]
            seen.update(new_rungs)
            if not new_rungs:
                return True  # between rungs: no decision point
            rung = new_rungs[-1]
            if int(resource) != rung:
                # the measurement was taken past the rung's resource (sparse
                # reporter, or a resume that overshot): recording it would
                # bias the rung with a later-epoch value, so skip — a rung
                # population holds only values measured AT its resource
                return True
            values = self._rungs.setdefault(rung, {})
            values[trial_id] = value
            if len(values) < self.eta:
                return True  # not enough evidence at this rung yet
            ranked = sorted(values.values(), reverse=(self.mode == "max"))
            top_k = max(int(math.ceil(len(ranked) / self.eta)), 1)
            threshold = ranked[top_k - 1]
            return (value <= threshold if self.mode == "min"
                    else value >= threshold)

    def forget(self, trial_id: str) -> None:
        """Erase a trial's rung records (trial fault classification: the trial
        ERRORED after reporting — a USER crash or invalid score). Its
        recorded values may be garbage from a template already failing,
        and a dead trial must not occupy top-1/eta slots that kill
        healthy fresh trials competing at the same rungs."""
        with self._lock:
            for values in self._rungs.values():
                values.pop(trial_id, None)
            self._recorded.pop(trial_id, None)
