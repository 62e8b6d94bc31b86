"""Trial fault classification for the training plane.

Before this module, every trial failure looked the same: the worker
caught ``Exception``, logged a traceback nobody could query, marked the
trial ERRORED (terminal, reasonless), burned the budget slot, and told
the advisor nothing — so the GP happily re-proposed the same crashing
knob region, and one flaky host could grind a whole search budget into
ERRORED rows. Vizier (Golovin et al., KDD 2017) treats the
transient-vs-infeasible distinction as first-class advisor signal; this
module gives rafiki_tpu the same spine.

Fault kinds and their contracts (docs/failure-model.md,
"Training-plane faults"):

``INFRA``
    The platform failed the trial, not the template: sandbox spawn
    failure, child killed by a signal, chaos injection, transient
    store/advisor errors. Retried under the SAME trial id with jittered
    backoff (``RAFIKI_TRIAL_RETRY_MAX``), resuming from the trial's
    checkpoint when the template keeps one — the retry does NOT consume
    an extra budget slot (the trial row is reused).
``MEM``
    The trial exceeded its memory envelope: in-process ``MemoryError``,
    RLIMIT_AS ``MemoryError`` inside the sandbox child, or a
    SIGKILLed child under an active ``RAFIKI_SANDBOX_MEM_MB`` cap.
    Retried like INFRA (a sibling trial's transient pressure may have
    tipped it), but the kind is recorded so a template that *always*
    OOMs is visible as such.
``USER``
    The template's own code raised (an ``err`` frame from
    ``sandbox_child``, or any unclassified exception in-process).
    Terminal: consumes the budget slot, feeds the advisor an
    *infeasible* observation so proposals steer away, and counts toward
    poison-knob quarantine and job fail-fast.
``TIMEOUT``
    The trial blew through ``TRIAL_TIMEOUT_S`` and could not be
    truncated at a metrics decision point (a mute runaway); the sandbox
    watchdog terminated it. Terminal + infeasible, like USER — the knob
    draw is too expensive for this budget.
``STALL``
    The sandbox child went mute before producing its FIRST frame for
    ``RAFIKI_TRIAL_STALL_S`` (wedged import, deadlocked setup, a backend
    that never comes up) and was killed by the no-frame watchdog. Retried like
    INFRA — stalls are overwhelmingly environmental.
``INVALID_SCORE``
    ``evaluate()`` returned NaN/inf/non-float. Terminal + infeasible:
    the trial "finished" but its result is unusable as advisor signal
    (previously only ASHA's rung check looked at finiteness).
"""

from __future__ import annotations

import math
import threading
import traceback
from typing import Any, Dict, Iterable, List, Optional, Tuple


class FaultKind:
    INFRA = "INFRA"
    MEM = "MEM"
    USER = "USER"
    TIMEOUT = "TIMEOUT"
    STALL = "STALL"
    INVALID_SCORE = "INVALID_SCORE"

    ALL = (INFRA, MEM, USER, TIMEOUT, STALL, INVALID_SCORE)


# kinds the worker re-runs under the same trial id (no budget consumed);
# everything else is terminal and burns the slot
RETRYABLE_KINDS = (FaultKind.INFRA, FaultKind.MEM, FaultKind.STALL)

# kinds that are the *template's* doing at these knobs: terminal AND fed
# to the advisor as an infeasible observation so the proposal
# distribution steers away (Vizier-style)
INFEASIBLE_KINDS = (FaultKind.USER, FaultKind.TIMEOUT,
                    FaultKind.INVALID_SCORE)


def is_infeasible_row(trial: Dict[str, Any]) -> bool:
    """Should this trial ROW feed the advisor as infeasible (replay,
    quarantine rebuild)? ERRORED user-class kinds, plus ERRORED MEM — a
    knob region that kept OOMing through its whole retry budget is
    knob-driven (batch/model size), and the optimizer must steer away
    from it too. The status check matters: COMPLETED/RUNNING rows carry
    the kind of an ABSORBED transient fault, which is not a verdict on
    their knobs."""
    if trial.get("status") != "ERRORED":
        return False
    kind = trial.get("fault_kind")
    return kind in INFEASIBLE_KINDS or kind == FaultKind.MEM

# how much traceback survives onto the trial row (fault_detail) — enough
# to diagnose without scraping worker logs, bounded so a pathological
# repr can't bloat the store
FAULT_DETAIL_MAX = 2000


class TrialFault(Exception):
    """Base for typed trial failures; carries its classification kind."""

    kind = FaultKind.INFRA

    def __init__(self, detail: str, kind: Optional[str] = None):
        super().__init__(detail)
        if kind is not None:
            self.kind = kind


class TrialChaosError(TrialFault):
    """RAFIKI_CHAOS site=trial action=error — the drillable stand-in for
    a transient platform fault at the trial-run chokepoint."""

    kind = FaultKind.INFRA


class InvalidScoreError(TrialFault):
    """evaluate() produced NaN/inf/non-castable — unusable as signal."""

    kind = FaultKind.INVALID_SCORE


def validate_score(raw: Any) -> float:
    """THE score gate: every path that turns an evaluate() result into a
    trial score goes through here, so NaN/inf/non-float is one typed
    fault instead of an arbitrary traceback (or, worse, a silently
    recorded NaN that poisons the GP's standardization)."""
    try:
        score = float(raw)
    except (TypeError, ValueError) as e:
        raise InvalidScoreError(
            f"evaluate() returned non-numeric {type(raw).__name__}: "
            f"{e}") from e
    if not math.isfinite(score):
        raise InvalidScoreError(f"evaluate() returned non-finite {score!r}")
    return score


def classify_failure(exc: BaseException) -> Tuple[str, str]:
    """Map a trial-execution exception to ``(fault_kind, detail)``.

    Typed faults (TrialFault and the sandbox's typed errors) carry their
    own kind; the remaining mapping is deliberately conservative —
    anything not provably the platform's fault is USER, because treating
    a template bug as INFRA would retry it forever at no budget cost."""
    detail = f"{type(exc).__name__}: {exc}"
    tb = traceback.format_exc()
    if tb and tb != "NoneType: None\n":
        detail = f"{detail}\n{tb}"
    detail = detail[-FAULT_DETAIL_MAX:]
    kind = getattr(exc, "kind", None)
    if kind in FaultKind.ALL:
        return kind, detail
    if isinstance(exc, MemoryError):
        return FaultKind.MEM, detail
    # transient control-plane trouble: store errors (chaos-injected OR
    # real — a locked sqlite file under concurrent workers, a brief
    # postgres outage surfacing through the trial-log sink), HTTP
    # transport failures to the admin (remote advisor), and the
    # recovering-503 — the trial itself may be fine, and classifying
    # these USER would feed bogus infeasible points and march the
    # fail-fast streak toward erroring a healthy job
    import sqlite3

    if isinstance(exc, sqlite3.OperationalError):
        return FaultKind.INFRA, detail
    try:
        import psycopg2

        if isinstance(exc, (psycopg2.OperationalError,
                            psycopg2.InterfaceError)):
            return FaultKind.INFRA, detail
    except ImportError:  # pragma: no cover - sqlite-only install
        pass
    try:
        from rafiki_tpu.db.database import MetadataStoreChaosError

        if isinstance(exc, MetadataStoreChaosError):
            return FaultKind.INFRA, detail
    except ImportError:  # pragma: no cover - partial install
        pass
    # NOT mapped: requests transport errors / the recovering-503. The
    # worker's own control-plane calls are already absorbed upstream
    # (advisor/remote.py _ride_out, _feedback_best_effort queueing), so
    # a RequestException reaching this classifier came from TEMPLATE
    # code running in-process (e.g. fetching a misconfigured dataset
    # URI) — classifying it INFRA would retry it for free, skip the
    # infeasible signal, and exempt a broken job from fail-fast.
    return FaultKind.USER, detail


# -- poison-knob signatures --------------------------------------------------

# quantization grid for "near-identical" knob vectors: each unit-cube
# coordinate rounds to 1/SIGNATURE_GRID — close draws (a GP circling a
# crashing basin) share a signature, distant ones never do
SIGNATURE_GRID = 8


def knob_signature(knob_config, knobs: Dict[str, Any]) -> str:
    """Stable signature of a knob assignment for quarantine matching.

    Encodes through the knobs' own unit-cube mapping (sdk/knob.py) and
    quantizes, so "near-identical" is measured in search space, not in
    raw values (1e-3 vs 1.1e-3 on an exp-scaled FloatKnob is the same
    cell; 1e-3 vs 1e-1 is not). Falls back to the sorted JSON of the
    raw knobs when no config is available (doctor-side grouping)."""
    if knob_config is not None:
        try:
            from rafiki_tpu.sdk.knob import knobs_to_unit

            u = knobs_to_unit(knob_config, knobs)
            cells = [int(round(float(x) * SIGNATURE_GRID)) for x in u]
            return "u:" + ",".join(str(c) for c in cells)
        # lint: absorb(unexpected knob shape falls through to the JSON signature)
        except Exception:  # unexpected knob shape: fall through to JSON
            pass
    import json

    return "j:" + json.dumps(knobs, sort_keys=True, default=str)


def poison_signature_counts(
    trials: Iterable[Dict[str, Any]],
    knob_config,
) -> Dict[str, int]:
    """Raw signature -> poison-fault count over ``trials`` (ERRORED
    rows with a user-class or MEM kind — is_infeasible_row). THE
    counting rule, shared by the worker's startup rebuild (which keeps
    the raw counts for incremental updates) and the doctor's store
    scan (which thresholds them via quarantined_signatures)."""
    counts: Dict[str, int] = {}
    for t in trials:
        if not is_infeasible_row(t):
            continue
        sig = knob_signature(knob_config, t.get("knobs") or {})
        counts[sig] = counts.get(sig, 0) + 1
    return counts


def quarantined_signatures(
    trials: Iterable[Dict[str, Any]],
    knob_config,
    threshold: int,
) -> Dict[str, int]:
    """Signatures with >= ``threshold`` poison faults among ``trials``."""
    counts = poison_signature_counts(trials, knob_config)
    return {s: n for s, n in counts.items() if n >= max(int(threshold), 1)}


# -- per-worker training-plane counters (fleet-health "training" section) ----

# sub_train_job_id -> counters; the training-plane twin of
# worker/inference.py's SERVING_STATS. In-process workers (thread
# placement / admin-embedded engines) update this dict directly and the
# admin's GET /fleet/health reads it; out-of-process workers' fault
# history is visible through the trial rows instead. BOUNDED: a
# long-lived admin runs jobs for weeks, and every sub-train-job ever
# seen must not leave a permanent entry — beyond the cap the
# least-recently-updated entries drop (their durable record stays in
# the trial rows).
TRAINING_STATS: Dict[str, Dict[str, Any]] = {}
_STATS_CAP = 256
_STATS_LOCK = threading.Lock()


def training_stats() -> Dict[str, Dict[str, Any]]:
    """Snapshot for the health endpoint (copy: callers may mutate)."""
    with _STATS_LOCK:
        return {
            k: {**v, "faults": dict(v.get("faults", {})),
                "quarantined": list(v.get("quarantined", []))}
            for k, v in TRAINING_STATS.items()
        }


def _stats_entry(sub_id: str) -> Dict[str, Any]:
    entry = TRAINING_STATS.pop(sub_id, None)
    if entry is None:
        entry = {
            "faults": {},            # fault kind -> count
            "retries": 0,            # infra-class re-runs (no budget burned)
            "quarantined": [],       # live poison-knob signatures
            "reproposals": 0,        # proposals rejected for quarantine
            "feedback_dropped": 0,   # pending-feedback overflow drops
            "consecutive_user_faults": 0,
        }
    # re-insert at the end: plain-dict insertion order IS the LRU order
    TRAINING_STATS[sub_id] = entry
    while len(TRAINING_STATS) > _STATS_CAP:
        TRAINING_STATS.pop(next(iter(TRAINING_STATS)))
    return entry


def record_fault(sub_id: str, kind: str, retried: bool = False) -> None:
    """Terminal faults land in the per-kind counters; absorbed
    (retried) transients count ONLY as retries — same split as the
    store-side fault summary, so the two /fleet/health views agree on
    what "faulted" means. The registry mirrors (utils/metrics.py) carry
    the same split process-wide, labeled by fault kind."""
    from rafiki_tpu.utils.metrics import REGISTRY

    with _STATS_LOCK:
        s = _stats_entry(sub_id)
        if retried:
            s["retries"] += 1
        else:
            s["faults"][kind] = s["faults"].get(kind, 0) + 1
    if retried:
        REGISTRY.counter(
            "rafiki_training_retries_total",
            "infra-class trial faults absorbed by same-id retry").inc()
    else:
        REGISTRY.counter(
            "rafiki_training_faults_total",
            "terminal trial faults by classification kind", ("kind",)
        ).labels(kind).inc()


def record_quarantine(sub_id: str, signatures: Iterable[str]) -> None:
    from rafiki_tpu.utils.metrics import REGISTRY

    with _STATS_LOCK:
        s = _stats_entry(sub_id)
        merged = set(s["quarantined"]) | set(signatures)
        s["quarantined"] = sorted(merged)
        total = sum(len(v.get("quarantined", ()))
                    for v in TRAINING_STATS.values())
    REGISTRY.gauge(
        "rafiki_training_quarantined_signatures",
        "poison-knob signatures currently quarantined in this process"
    ).set(total)


def record_counter(sub_id: str, counter: str, value: int = 1,
                   absolute: bool = False) -> None:
    from rafiki_tpu.utils.metrics import REGISTRY

    with _STATS_LOCK:
        s = _stats_entry(sub_id)
        s[counter] = value if absolute else s.get(counter, 0) + value
    if not absolute:
        # process-wide counter twin (reproposals, feedback_dropped, ...)
        REGISTRY.counter(
            "rafiki_training_counter_total",
            "training-plane worker counters", ("counter",)
        ).labels(counter).inc(value)


def reset_stats(sub_id: Optional[str] = None) -> None:
    with _STATS_LOCK:
        if sub_id is None:
            TRAINING_STATS.clear()
        else:
            TRAINING_STATS.pop(sub_id, None)
