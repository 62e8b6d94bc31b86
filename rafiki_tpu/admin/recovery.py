"""Control-plane crash recovery: admin restart reconciliation.

`ServicesManager` holds predictors, predict servers, and placement state
purely in memory, so an admin crash used to strand the store: jobs pinned
at RUNNING forever while workers on surviving host agents kept serving
and training unmanaged — the gap Rafiki inherited from its reference
(admin state outside the metadata DB) and the classic reconcile-on-restart
problem of Borg/Kubernetes-style controllers (PAPERS.md). A fresh
:class:`~rafiki_tpu.admin.admin.Admin` now boots idempotently:

1. **Scan** — one query (``Database.get_non_terminal_services``) snapshots
   every non-terminal service joined to its job linkage, plus the
   non-terminal job rows. The snapshot is taken synchronously in the
   Admin constructor, so state created *after* boot is never reconciled.
2. **Probe** — every registered host agent answers ``GET /inventory``
   with the services it is actually running (bounded by
   ``RAFIKI_RECOVER_PROBE_TIMEOUT_S``).
3. **Reconcile** (off-thread, behind a ``recovering -> ready`` admin
   state that 503s the HTTP doors):
   - **fence** orphans — services still running whose DB row or job went
     terminal while the admin was down (one service id, one executor);
   - **adopt** survivors — placement state rebuilt from the store
     (relay queues re-registered, `Predictor`/`PredictorServer`
     reconstructed, so ``predict()`` answers without a redeploy); local
     process-mode children are adopted by pid. ``RAFIKI_RECOVER_ADOPT=0``
     turns every would-be adoption into a fence;
   - **reschedule** train services whose hosts died, through the PR-1
     failover machinery (same service id -> stale-trial resume);
   - **error** the truly unrecoverable, with a recorded reason, through
     the admin's status callback so job-level refresh fires.
4. **Sweep** — every non-terminal job is refreshed; a job left with zero
   live services is terminal-ized (no DB row may survive recovery in a
   non-terminal status with nothing backing it).

Metadata-store hiccups during any step retry with bounded jittered
backoff (drillable via ``RAFIKI_CHAOS`` ``site=db``) instead of aborting
recovery. The final report is surfaced under ``recovery`` in
``GET /fleet/health`` and persisted to ``<logs>/recovery.json`` for the
doctor.
"""

from __future__ import annotations

import json
import logging
import os
import random
import re
import threading
import time
from typing import Any, Dict, List, Optional

from rafiki_tpu import config
from rafiki_tpu.constants import (
    InferenceJobStatus,
    ServiceStatus,
    ServiceType,
    TrainJobStatus,
    TrialStatus,
)

logger = logging.getLogger(__name__)

_TERMINAL = (ServiceStatus.STOPPED, ServiceStatus.ERRORED)
_JOB_TERMINAL = (TrainJobStatus.STOPPED, TrainJobStatus.ERRORED)
_MAX_REASONS = 64  # the report is an operator view, not a log archive

REPORT_FILENAME = "recovery.json"


class RecoveryAborted(Exception):
    """The admin is shutting down: reconciliation must stop placing
    things NOW — a service re-placed after teardown has nothing left to
    ever stop it."""


def report_path() -> str:
    return os.path.join(config.LOGS_DIR, REPORT_FILENAME)


def _job_status_of(row: Dict[str, Any]) -> Optional[str]:
    """The governing job status for a service row from the recovery scan
    (None = no job linkage at all)."""
    if row["service_type"] == ServiceType.TRAIN:
        return row.get("train_job_status")
    if row["service_type"] == ServiceType.INFERENCE:
        return row.get("inference_job_status")
    if row["service_type"] == ServiceType.PREDICT:
        return row.get("predictor_job_status")
    return None


def _extra_of(row: Dict[str, Any]) -> Dict[str, Any]:
    """Rebuild the placement payload from the store row — the declarative
    `extra` a placement engine needs to relaunch (or adopt) the worker."""
    if row["service_type"] == ServiceType.TRAIN:
        return {"sub_train_job_id": row.get("sub_train_job_id")}
    if row["service_type"] == ServiceType.INFERENCE:
        return {"inference_job_id": row.get("inference_job_id"),
                "trial_id": row.get("trial_id")}
    return {}


class ControlPlaneRecovery:
    """One boot-time reconciliation pass for an Admin."""

    def __init__(self, admin):
        self.admin = admin
        self.db = admin.db
        self.report: Dict[str, Any] = {
            "state": "recovering",
            "started_at": time.time(),
            "scanned": 0,
            "adopted": 0,
            "rescheduled": 0,
            "fenced": 0,
            "closed": 0,
            "errored": 0,
            "jobs_closed": 0,
            "agents_probed": 0,
            "agents_unreachable": 0,
            "db_retries": 0,
            "reasons": [],
        }
        self._restored_advisors: set = set()
        #: set by Admin.shutdown(): checked at every loop top and inside
        #: retry backoffs, so a reconcile can never re-place a service
        #: after teardown started
        self._abort = threading.Event()

    def abort(self) -> None:
        self._abort.set()

    def _check_abort(self) -> None:
        if self._abort.is_set():
            raise RecoveryAborted("admin is shutting down")

    # -- bounded-retry store access ---------------------------------------

    def _retry(self, fn, what: str):
        """Run a metadata-store step with bounded jittered backoff — a
        transient store failure (drill: RAFIKI_CHAOS site=db) must not
        abort recovery and leave the fleet unreconciled."""
        attempts = max(int(config.RECOVER_RETRY_MAX), 0) + 1
        for attempt in range(attempts):
            self._check_abort()
            try:
                return fn()
            except RecoveryAborted:
                raise
            except Exception as e:
                if attempt + 1 >= attempts:
                    raise
                self.report["db_retries"] += 1
                delay = (float(config.RECOVER_RETRY_BACKOFF_S)
                         * (2 ** attempt) * random.uniform(0.5, 1.5))
                logger.warning(
                    "recovery: %s failed (%s); retry %d/%d in %.2fs",
                    what, e, attempt + 1, attempts - 1, delay)
                if self._abort.wait(delay):
                    raise RecoveryAborted("admin is shutting down")

    def _reason(self, text: str) -> None:
        if len(self.report["reasons"]) < _MAX_REASONS:
            self.report["reasons"].append(text)

    # -- snapshot (synchronous, in the Admin constructor) ------------------

    def snapshot(self) -> Dict[str, Any]:
        from rafiki_tpu.constants import RolloutPhase

        services = self._retry(self.db.get_non_terminal_services,
                               "service scan")
        train_jobs = self._retry(
            lambda: self.db.get_train_jobs_by_statuses(
                [TrainJobStatus.STARTED, TrainJobStatus.RUNNING]),
            "train-job scan")
        inference_jobs = self._retry(
            lambda: self.db.get_inference_jobs_by_statuses(
                [InferenceJobStatus.STARTED, InferenceJobStatus.RUNNING]),
            "inference-job scan")
        # CANARY/ROLLING rollout rows force a reconcile even when every
        # job row happens to be terminal (e.g. the job was stopped while
        # the admin was down): a live rollout row must always be
        # resolved, never stranded
        rollouts = self._retry(
            lambda: self.db.get_rollouts_by_phases(
                list(RolloutPhase.LIVE)),
            "rollout scan")
        return {"services": services, "train_jobs": train_jobs,
                "inference_jobs": inference_jobs, "rollouts": rollouts}

    @staticmethod
    def needed(snapshot: Dict[str, Any]) -> bool:
        return any(snapshot.get(k) for k in
                   ("services", "train_jobs", "inference_jobs",
                    "rollouts"))

    def empty_report(self) -> Dict[str, Any]:
        return {**self.report, "state": "ready", "duration_s": 0.0}

    # -- reconciliation (off-thread) ---------------------------------------

    def run(self, snapshot: Dict[str, Any]) -> Dict[str, Any]:
        t0 = time.monotonic()
        try:
            self._reconcile(snapshot)
        except Exception as e:
            # an aborted reconcile must be VISIBLE — in memory AND in the
            # persisted report doctor reads — never dressed up as a clean
            # pass with partial counts. The doors still open (a failed
            # reconcile must not brick the admin); doctor flags the rest.
            self.report["failed"] = True
            self.report["error"] = f"{type(e).__name__}: {e}"
            self._reason(f"reconciliation ABORTED: {type(e).__name__}: {e}")
            logger.exception("control-plane reconciliation aborted")
        self.report["state"] = "ready"
        self.report["duration_s"] = round(time.monotonic() - t0, 3)
        self._persist_report()
        logger.info(
            "control-plane recovery done in %.2fs: %d scanned, %d adopted, "
            "%d rescheduled, %d fenced, %d errored%s",
            self.report["duration_s"], self.report["scanned"],
            self.report["adopted"], self.report["rescheduled"],
            self.report["fenced"], self.report["errored"],
            " (ABORTED)" if self.report.get("failed") else "")
        return dict(self.report)

    def _reconcile(self, snapshot: Dict[str, Any]) -> None:
        admin = self.admin
        placement = admin.placement
        services: List[Dict[str, Any]] = snapshot["services"]
        self.report["scanned"] = len(services)
        by_id = {s["id"]: s for s in services}
        adopt_enabled = bool(config.RECOVER_ADOPT)
        if not adopt_enabled:
            self._reason("RAFIKI_RECOVER_ADOPT=0: surviving workers are "
                         "fenced, not adopted")

        # -- rebuild advisor sessions FIRST: a surviving train worker may
        # hit POST /advisors/<sub_id>/propose at any moment (that route
        # rides through the recovering gate on purpose), so every
        # non-terminal train service's session must exist before the
        # slower probe/adopt passes run
        for row in services:
            if (row["service_type"] == ServiceType.TRAIN
                    and row.get("train_job_status") not in _JOB_TERMINAL
                    and row.get("sub_train_job_id")):
                self._restore_advisor(row["sub_train_job_id"])

        # -- probe agents for ground truth --------------------------------
        running_on: Dict[str, str] = {}  # service_id -> agent addr
        inventories: Dict[str, Optional[Dict[str, Any]]] = {}
        if hasattr(placement, "probe_inventories"):
            inventories = placement.probe_inventories()
            self.report["agents_probed"] = len(inventories)
            self.report["agents_unreachable"] = sum(
                1 for v in inventories.values() if v is None)
            for addr, inv in inventories.items():
                for entry in (inv or {}).get("services", []):
                    running_on[entry["service_id"]] = addr

        # -- fence: running orphans whose DB row/job went terminal, or
        # whose row lost its job linkage entirely -------------------------
        for addr, inv in inventories.items():
            for entry in (inv or {}).get("services", []):
                sid = entry["service_id"]
                row = by_id.get(sid)
                jstatus = _job_status_of(row) if row else None
                if row is not None and jstatus is not None \
                        and jstatus not in _JOB_TERMINAL:
                    continue  # a live, legitimately-owned service
                if row is None:
                    # not in the boot snapshot — either terminal/missing
                    # (an orphan) or created AFTER this admin booted by an
                    # in-process caller racing the off-thread reconcile.
                    # Re-read the LIVE row: a non-terminal row proves the
                    # service is this admin's own fresh placement, never
                    # an orphan to fence.
                    try:
                        fresh = self._retry(
                            lambda s=sid: self.db.get_service(s),
                            f"live re-check of {sid[:8]}")
                    except RecoveryAborted:
                        raise
                    # lint: absorb(cannot prove orphanhood after retries; leave the row alone)
                    except Exception:
                        continue  # cannot prove orphanhood: do nothing
                    if fresh is not None and fresh["status"] not in _TERMINAL:
                        # also off-limits for every later pass (the
                        # adoption-disabled fence sweep included): this
                        # is NOT a survivor of the dead admin
                        running_on.pop(sid, None)
                        continue
                why = ("no (or terminal) store row" if row is None
                       else "no job row references it"
                       if jstatus is None else f"its job is {jstatus}")
                fenced = (hasattr(placement, "fence_service")
                          and placement.fence_service(sid, addr))
                running_on.pop(sid, None)
                # either way this service must not be adopted/rescheduled
                # below; but its row is only CLOSED when the fence landed
                # — a row closed over a still-running executor would hide
                # the orphan from doctor and every future reconcile
                by_id.pop(sid, None)
                if fenced:
                    self.report["fenced"] += 1
                    self._reason(f"{sid[:8]}: fenced on {addr} ({why})")
                    if row is not None:
                        self._retry(
                            lambda s=sid: self.db.mark_service_as_stopped(s),
                            f"close fenced row {sid[:8]}")
                elif row is not None:
                    self._reason(
                        f"{sid[:8]}: could not fence on {addr} ({why}); "
                        "row left non-terminal for the next reconcile")

        if not adopt_enabled:
            # adoption disabled: every survivor is fenced; a fenced
            # service is then treated as host-dead below (reschedule/
            # error), while a FAILED fence leaves it untouched — acting
            # on a possibly-still-running executor could double-run it.
            # wait=True: a TRAIN service may be re-placed under the SAME
            # id right below, so the old executor must be provably gone
            for sid, addr in list(running_on.items()):
                if hasattr(placement, "fence_service") and \
                        placement.fence_service(sid, addr, wait=True):
                    self.report["fenced"] += 1
                else:
                    by_id.pop(sid, None)
                    self._reason(f"{sid[:8]}: could not fence on {addr} "
                                 "(adoption disabled); left untouched")
            running_on.clear()

        # -- adopt / reschedule / error every non-terminal service --------
        adopted_serving_jobs = set()
        unreachable = [a for a, inv in inventories.items() if inv is None]
        for row in services:
            self._check_abort()
            sid = row["id"]
            if sid not in by_id:
                continue  # already closed by the fence pass
            stype = row["service_type"]
            jstatus = _job_status_of(row)
            if jstatus in _JOB_TERMINAL:
                # the job finished/was stopped while the admin was down,
                # and nothing is running for it: close the stale row
                self._retry(
                    lambda s=sid: self.db.mark_service_as_stopped(s),
                    f"close stale row {sid[:8]}")
                self.report["closed"] += 1
                continue
            if stype == ServiceType.PREDICT:
                continue  # serving heads are rebuilt per-job below
            if jstatus is None:
                self._error_service(
                    sid, "no job row references this service "
                         "(orphaned linkage)")
                continue
            extra = _extra_of(row)
            n_chips = len(row.get("chips") or [])
            addr = running_on.get(sid)
            if addr is not None and hasattr(placement, "adopt_service"):
                if placement.adopt_service(
                        sid, addr, stype, n_chips=n_chips, extra=extra,
                        best_effort_chips=(stype == ServiceType.INFERENCE)):
                    self.report["adopted"] += 1
                    if stype == ServiceType.INFERENCE:
                        adopted_serving_jobs.add(extra["inference_job_id"])
                        self._readopt_chip_loan(row, extra)
                    continue
            if adopt_enabled and self._adopt_local_pid(row, extra):
                self.report["adopted"] += 1
                if stype == ServiceType.INFERENCE:
                    adopted_serving_jobs.add(extra["inference_job_id"])
                    self._readopt_chip_loan(row, extra)
                continue
            if not adopt_enabled:
                # surviving LOCAL children must be fenced before anything
                # is re-placed under their id (SIGTERM + bounded wait,
                # identity-pinned) — 'RAFIKI_RECOVER_ADOPT=0 fences all
                # survivors' holds on single-host placements too
                self._fence_local_survivor(row)
            # nothing is running this service anymore: its host (or the
            # whole single-host process tree) died
            if stype == ServiceType.TRAIN:
                if unreachable and hasattr(placement,
                                           "quarantine_on_rejoin"):
                    # BEFORE re-placing the id: the old executor MAY still
                    # run on an agent whose probe merely timed out — fence
                    # it there the moment that agent proves alive (or now,
                    # if it already rejoined)
                    placement.quarantine_on_rejoin(unreachable, sid)
                if self._restart_train(row, extra, n_chips,
                                       exclude=unreachable):
                    self.report["rescheduled"] += 1
                else:
                    self._error_service(
                        sid, "train executor lost (host died while the "
                             "control plane was down; no capacity to "
                             "reschedule)")
            else:
                if unreachable and hasattr(placement,
                                           "quarantine_on_rejoin"):
                    # same rule for an errored replica: if its host was
                    # only slow, the executor there must be fenced on
                    # rejoin — an ERRORED row with a live executor is the
                    # unmanaged-worker state recovery exists to eliminate
                    placement.quarantine_on_rejoin(unreachable, sid)
                self._error_service(
                    sid, "serving replica lost with its host while the "
                         "control plane was down")

        # -- rebuild serving heads for jobs with adopted replicas ----------
        for job_id in sorted(adopted_serving_jobs):
            self._check_abort()
            try:
                self._retry(
                    lambda j=job_id:
                        admin.services.adopt_inference_job(j),
                    f"serving adoption for job {job_id[:8]}")
            except RecoveryAborted:
                raise
            except Exception as e:
                logger.exception("serving adoption failed for %s", job_id)
                self._reason(f"job {job_id[:8]}: serving adoption failed "
                             f"({type(e).__name__}: {e})")

        # -- resolve half-finished rollouts (admin/rollout.py): the
        # adopted worker rows carry each replica's model_version, so a
        # rollout the dead admin left in CANARY/ROLLING is either
        # resumed-as-done (fleet already fully new-version) or rolled
        # back — never stranded mid-phase with a half-judged version
        # taking traffic
        rollouts = getattr(admin, "rollouts", None)
        if rollouts is not None:
            self._check_abort()
            try:
                rollouts.recover_on_boot()
            except RecoveryAborted:
                raise
            except Exception as e:
                logger.exception("boot-time rollout resolution failed")
                self._reason(f"rollout resolution failed "
                             f"({type(e).__name__}: {e})")

        # -- resume the drift closed loop (admin/drift.py): rows the
        # dead admin left RETRAINING/ROLLING_OUT re-attach by persisted
        # retrain id (the idempotency key); a write-ahead intent whose
        # launch fate is unknowable is adopted or parked — NEVER
        # relaunched, so a crash cannot double-spend the retrain budget
        drift = getattr(admin, "drift", None)
        if drift is not None:
            self._check_abort()
            try:
                drift.recover_on_boot()
            except RecoveryAborted:
                raise
            except Exception as e:
                logger.exception("boot-time drift resumption failed")
                self._reason(f"drift resumption failed "
                             f"({type(e).__name__}: {e})")

        # -- sweep: no job may stay non-terminal with nothing backing it ---
        self._sweep_jobs(snapshot)

    def _readopt_chip_loan(self, row: Dict[str, Any],
                           extra: Dict[str, Any]) -> None:
        """Rebuild the ChipBudgetArbiter's loan book for an adopted
        serving replica. A crashed admin's arbiter lived in memory; the
        ``borrowed_chips`` column on the worker row (written when the
        autoscaler's borrow committed) is the durable record, so an
        adopted replica that held borrowed trial chips is re-entered on
        the successor's loan book — the training plane can reclaim it
        and the fleet-health loan picture stays truthful instead of
        silently leaking the loan until the replica stops."""
        n = int(row.get("borrowed_chips") or 0)
        if n <= 0:
            return
        arbiter = getattr(self.admin, "chip_arbiter", None)
        if arbiter is None:
            return
        try:
            arbiter.note_borrow(row["id"], extra["inference_job_id"], n)
            # re-tag warm-standby loans (durable `standby` column): the
            # successor's reclaim ordering must keep draining standbys
            # FIRST, exactly like the admin that placed them would
            worker = self.db.get_inference_job_worker(row["id"])
            if worker is not None and int(worker.get("standby") or 0):
                arbiter.mark_standby(row["id"], True)
            logger.info("re-adopted a %d-chip serving loan on replica %s",
                        n, row["id"][:8])
        # lint: absorb(the loan book is advisory accounting: a rebuild failure must not fail the adoption itself)
        except Exception:
            logger.exception("chip-loan re-adoption failed for %s",
                             row["id"][:8])

    def _adopt_local_pid(self, row: Dict[str, Any],
                         extra: Dict[str, Any]) -> bool:
        """Single-host process placement: children outlive a crashed admin
        (start_new_session). Adopt a TRAIN child by its recorded pid; a
        surviving INFERENCE child is unreachable (the dead admin owned its
        shm data plane), so it is fenced instead — SIGTERM, then the
        normal lost-replica handling."""
        placement = self.admin.placement
        if hasattr(placement, "agents"):
            # hosts mode: a live pid on THIS machine may belong to a
            # co-located agent's engine (agents record child pids in the
            # same store) — adopting it here would double-manage one
            # worker from two placement engines. Agent-side services are
            # reconciled through the inventory probe instead.
            return False
        engine = placement if hasattr(placement, "adopt_pid") else None
        if engine is None:
            return False
        pid = row.get("pid")
        if not pid:
            return False
        if row["service_type"] == ServiceType.INFERENCE:
            self._fence_local_pid(row["id"], int(pid),
                                  why="its data plane died with the old "
                                      "admin")
            return False
        return bool(engine.adopt_pid(
            row["id"], row["service_type"], int(pid), extra=extra,
            chips=row.get("chips") or []))

    def _fence_local_survivor(self, row: Dict[str, Any]) -> None:
        """Adoption disabled: SIGTERM (and bounded-wait out) a surviving
        local child before its service id can be re-placed — otherwise
        the old and new executor would run concurrently under one id."""
        if hasattr(self.admin.placement, "agents"):
            return  # hosts mode: local pids may belong to agents' engines
        pid = row.get("pid")
        if not pid:
            return
        if self._fence_local_pid(row["id"], int(pid),
                                 why="RAFIKI_RECOVER_ADOPT=0",
                                 wait_s=10.0):
            self.report["fenced"] += 1
            self._reason(f"{row['id'][:8]}: fenced local child pid {pid} "
                         "(adoption disabled)")

    @staticmethod
    def _fence_local_pid(service_id: str, pid: int, why: str,
                         wait_s: float = 0.0) -> bool:
        from rafiki_tpu.placement.process import (
            _pid_is_worker,
            terminate_worker_pid,
        )

        # identity-pinned: a recycled pid belonging to some OTHER
        # service's worker must never be signalled
        if not _pid_is_worker(pid, service_id=service_id):
            return False
        logger.warning("fencing surviving child %s (pid %d): %s",
                       service_id[:8], pid, why)
        terminate_worker_pid(pid, service_id, grace_s=wait_s)
        return True

    def _restore_advisor(self, sub_train_job_id: Optional[str]) -> None:
        """An adopted train worker created its advisor session against
        the DEAD admin (advisor_id = its sub-train-job id). Rebuild the
        session in this admin's in-memory store — same id, seeded with
        the completed trials already persisted — before the worker's next
        proposal lands, or that proposal errors the very executor the
        reconcile just adopted."""
        if not sub_train_job_id:
            return
        if sub_train_job_id in self._restored_advisors:
            return
        self._restored_advisors.add(sub_train_job_id)
        try:
            sub = self.db.get_sub_train_job(sub_train_job_id)
            model = self.db.get_model(sub["model_id"]) if sub else None
            if model is None:
                return
            from rafiki_tpu.sdk.model import load_model_class

            clazz = load_model_class(model["model_file_bytes"],
                                     model["model_class"])
            store = self.admin.advisor_store
            store.create_advisor(clazz.get_knob_config(),
                                 advisor_id=sub_train_job_id)
            from rafiki_tpu.worker.faults import is_infeasible_row

            trials = self.db.get_trials_of_sub_train_job(sub_train_job_id)
            scored = [
                (t["knobs"], t["score"])
                for t in trials
                if t["status"] == TrialStatus.COMPLETED
                and t["score"] is not None
            ]
            # poison faults ride the replay too (trial fault classification):
            # the rebuilt GP must also remember which regions crash,
            # not just which scored
            infeasible = [
                (t["knobs"], t["fault_kind"])
                for t in trials
                if is_infeasible_row(t)
            ]
            if (scored or infeasible) and store.replay_feedback(
                    sub_train_job_id, scored, infeasible=infeasible):
                logger.info(
                    "advisor %s rebuilt with %d replayed + %d "
                    "infeasible trials", sub_train_job_id[:8],
                    len(scored), len(infeasible))
        except Exception as e:
            logger.exception("advisor restore failed for %s",
                             sub_train_job_id)
            self._reason(f"sub {sub_train_job_id[:8]}: advisor restore "
                         f"failed ({type(e).__name__}: {e})")

    def _restart_train(self, row: Dict[str, Any], extra: Dict[str, Any],
                       n_chips: int, exclude=()) -> bool:
        """Rehome a dead host's train executor: hosts placement replays it
        through the PR-1 failover machinery (never onto an ``exclude``d —
        probe-unreachable — agent, which may still be running the old
        executor); single-host placements relaunch the worker in-process.
        Same service id either way, so the stale-RUNNING-trial resume
        continues its work."""
        placement = self.admin.placement
        if hasattr(placement, "reschedule_service"):
            try:
                return bool(placement.reschedule_service(
                    row["id"], row["service_type"], n_chips=n_chips,
                    extra=extra, exclude=exclude))
            except Exception:
                logger.exception("reschedule of %s failed", row["id"][:8])
                return False
        return self.admin.services.restart_train_worker(
            row["id"], extra["sub_train_job_id"], n_chips=n_chips)

    def _error_service(self, service_id: str, reason: str) -> None:
        """Mark a service ERRORED *through the admin's status callback*,
        so the job-level refresh side effects (train-job completion,
        serving teardown, predict-route drops) fire exactly as they would
        for a live failure."""
        self.report["errored"] += 1
        self._reason(f"{service_id[:8]}: ERRORED — {reason}")
        logger.warning("recovery: service %s ERRORED (%s)",
                       service_id[:8], reason)
        try:
            self._retry(
                lambda: self.admin._on_service_status(service_id, "ERRORED"),
                f"error service {service_id[:8]}")
        except Exception:
            logger.exception("could not error service %s", service_id)

    def _sweep_jobs(self, snapshot: Dict[str, Any]) -> None:
        """Acceptance backstop: zero rows left in a non-terminal status
        with no live (or rescheduled) service backing them. Each job's
        whole sweep runs under the bounded-retry contract — every step is
        idempotent (guarded transitions / pure reads), so a transient
        store fault re-runs the body instead of silently skipping the
        job."""
        # one indexed query for the whole live-set — not a get_service
        # round trip per worker while the doors are still 503ing
        try:
            live = self._retry(
                lambda: {s["id"] for s in self.db.get_services(
                    statuses=[ServiceStatus.STARTED,
                              ServiceStatus.DEPLOYING,
                              ServiceStatus.RUNNING])},
                "live-set scan")
        except Exception:
            logger.exception("live-set scan failed; skipping the job sweep")
            return
        for job in snapshot["train_jobs"]:
            try:
                self._retry(lambda j=job: self._sweep_one_train(j, live),
                            f"sweep train job {job['id'][:8]}")
            except RecoveryAborted:
                raise
            except Exception:
                logger.exception("train-job sweep failed for %s", job["id"])
        for job in snapshot["inference_jobs"]:
            try:
                self._retry(
                    lambda j=job: self._sweep_one_inference(j, live),
                    f"sweep inference job {job['id'][:8]}")
            except RecoveryAborted:
                raise
            except Exception:
                logger.exception("inference-job sweep failed for %s",
                                 job["id"])

    def _sweep_one_train(self, job: Dict[str, Any], live: set) -> None:
        self.admin.services.refresh_train_job_status(job["id"])
        fresh = self.db.get_train_job(job["id"])
        if fresh is None or fresh["status"] in _JOB_TERMINAL:
            return
        workers = self.db.get_workers_of_train_job(job["id"])
        if any(w["service_id"] in live for w in workers):
            return
        self.db.mark_train_job_as_errored(job["id"])
        self.report["jobs_closed"] += 1
        self._reason(f"train job {job['id'][:8]}: ERRORED — "
                     "orphaned by a dead admin (no live services)")

    def _sweep_one_inference(self, job: Dict[str, Any], live: set) -> None:
        self.admin.services.refresh_inference_job_status(job["id"])
        fresh = self.db.get_inference_job(job["id"])
        if fresh is None or fresh["status"] in (
                InferenceJobStatus.STOPPED, InferenceJobStatus.ERRORED):
            return
        workers = self.db.get_workers_of_inference_job(job["id"])
        if any(w["service_id"] in live for w in workers):
            return
        self.admin.services._teardown_serving(job["id"], errored=True)
        self.report["jobs_closed"] += 1
        self._reason(f"inference job {job['id'][:8]}: ERRORED — "
                     "orphaned by a dead admin (no live replicas)")

    def _persist_report(self) -> None:
        """Best-effort: the doctor reads the last reconcile outcome from
        disk (it has no admin process to ask).

        With control-plane HA, two admins share one LOGS_DIR across a
        failover and would clobber each other's ``recovery.json`` — the
        promoted leader's adopt report overwriting the crashed leader's
        is the exact evidence an operator needs to diff. So the report is
        written twice: the unsuffixed latest (the stable doctor/test
        path) AND an epoch-suffixed ``recovery-e<N>.json``, pruned to the
        last ``RAFIKI_RECOVERY_REPORT_KEEP``."""
        try:
            from rafiki_tpu.sdk.artifact import atomic_write_bytes

            path = report_path()
            os.makedirs(os.path.dirname(path), exist_ok=True)
            payload = {**self.report, "finished_at": time.time()}
            epoch = None
            lease = getattr(self.admin, "lease", None)
            if lease is not None:
                epoch = lease.last_epoch()
                payload["epoch"] = epoch
            blob = json.dumps(payload, indent=2).encode()
            atomic_write_bytes(path, blob)
            if epoch is not None:
                atomic_write_bytes(
                    os.path.join(os.path.dirname(path),
                                 f"recovery-e{int(epoch)}.json"), blob)
                self._prune_epoch_reports(os.path.dirname(path))
        except Exception:
            logger.exception("could not persist the recovery report")

    @staticmethod
    def _prune_epoch_reports(logs_dir: str) -> None:
        """Keep the newest RAFIKI_RECOVERY_REPORT_KEEP epoch-suffixed
        reports (sorted by epoch, which is monotonic across failovers)."""
        keep = max(int(config.RECOVERY_REPORT_KEEP), 1)
        found = []
        for name in os.listdir(logs_dir):
            m = re.fullmatch(r"recovery-e(\d+)\.json", name)
            if m:
                found.append((int(m.group(1)), name))
        for _, name in sorted(found)[:-keep]:
            try:
                os.unlink(os.path.join(logs_dir, name))
            except OSError as e:  # lint: absorb(prune is housekeeping;
                # a leftover report costs bytes, not correctness)
                logger.warning("could not prune %s: %s", name, e)
