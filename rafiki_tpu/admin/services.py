"""Deployment engine: turns job rows into running executor services.

Parity with the reference's ServicesManager (reference
rafiki/admin/services_manager.py:28-403):

- train jobs: the chip budget is split evenly across sub-train-jobs (one per
  model), one executor per chip with a no-chip fallback executor when the
  budget is 0 (reference :190-202, :107-135 — there per GPU container, here
  per granted chip);
- inference jobs: for each of the best ``INFERENCE_MAX_BEST_TRIALS`` trials,
  ``INFERENCE_WORKER_REPLICAS_PER_TRIAL`` serving executors plus one predictor
  (reference :53-87);
- deployment waits until services report RUNNING and rolls back on failure
  (reference :279-290, :131-135);
- train-job status is derived from worker-service states (reference :160-184).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Dict, List, Optional

from rafiki_tpu import config
from rafiki_tpu.advisor.advisor import AdvisorStore
from rafiki_tpu.cache.queue import Broker
from rafiki_tpu.constants import (
    BudgetType,
    InferenceJobStatus,
    ServiceStatus,
    ServiceType,
    TaskType,
    TrainJobStatus,
    TrialStatus,
)
from rafiki_tpu.db.database import Database
from rafiki_tpu.placement.manager import (
    InsufficientChipsError,
    PlacementManager,
)
from rafiki_tpu.predictor.predictor import Predictor
from rafiki_tpu.utils import chaos
from rafiki_tpu.worker.inference import InferenceWorker
from rafiki_tpu.worker.train import TrainWorker

logger = logging.getLogger(__name__)


class ServiceDeploymentError(Exception):
    pass


def _chaos_deploy(inference_job_id: str, trial_id: str) -> None:
    """RAFIKI_CHAOS site=deploy: the place-new-replica chokepoint shared
    by the initial deploy, autoscaler scale-ups, and the rollout
    controller's canary/rolling placements. `error`/`drop` raise the
    typed deploy failure (the deterministic canary-failure rollback
    drill); `delay` models a slow deploy (against the rollout's deploy
    deadline, the deploy-timeout drill)."""
    rule = chaos.hit(chaos.SITE_DEPLOY, f"{inference_job_id}/{trial_id}")
    if rule is None:
        return
    if rule.action == chaos.ACTION_DELAY:
        chaos.sleep_for(rule)
        return
    raise ServiceDeploymentError(
        f"chaos-injected deploy failure placing a replica of trial "
        f"{trial_id} for job {inference_job_id}")


class ServicesManager:
    def __init__(
        self,
        db: Database,
        placement: PlacementManager,
        advisor_store: AdvisorStore,
        broker: Broker,
        send_event,
        params_dir: Optional[str] = None,
        arbiter=None,
    ):
        """``arbiter`` (placement/hosts.py ChipBudgetArbiter) mediates
        chip loans between the serving and training planes: autoscaler
        scale-ups may borrow idle trial chips through it, and a train
        executor that can't allocate reclaims them (the arbiter's reclaim
        callback is installed here — reclaim works whether or not the
        autoscaler loop itself is running)."""
        self._db = db
        self._placement = placement
        self._advisors = advisor_store
        self._broker = broker
        self._send_event = send_event
        self._params_dir = params_dir or config.PARAMS_DIR
        self._predictors: Dict[str, Predictor] = {}
        # inference_job_id -> PredictorServer (config.PREDICTOR_PORTS)
        self._predict_servers: Dict[str, object] = {}
        self._lock = threading.Lock()
        self._arbiter = arbiter
        if arbiter is not None:
            arbiter.set_reclaim_callback(self.reclaim_borrowed)
        # service_ids mid-graceful-drain (elastic scale-down): a second
        # scale-down landing during a drain must pick OTHER victims (or
        # no-op) — never double-drain, never double-count
        self._scale_lock = threading.Lock()
        self._scale_draining: set = set()

    # -- train -------------------------------------------------------------

    def create_train_services(self, train_job_id: str) -> None:
        job = self._db.get_train_job(train_job_id)
        assert job is not None
        sub_jobs = self._db.get_sub_train_jobs_of_train_job(train_job_id)
        budget = job["budget"]
        total_chips = int(
            budget.get(
                BudgetType.CHIP_COUNT, budget.get(BudgetType.GPU_COUNT, 0)
            )
        )
        avail = getattr(self._placement, "allocator", None)
        if avail is not None:
            # Clamp to the host's static capacity (asking for more chips than
            # exist downsizes the job, like the reference's even GPU split,
            # reference services_manager.py:190-202). Chips merely *busy* are
            # NOT clamped away: allocating them raises InsufficientChipsError
            # and the deploy rolls back — never silently share devices with
            # a running job.
            total_chips = min(total_chips, avail.total_chips)
        # Even split; chips left over go one each to the first sub-jobs
        # (the job's model order) rather than idling: on a one-chip host a
        # two-model job must not strand the only chip while both workers
        # run without one (a local chip cannot be shared across processes)
        even, spare = divmod(total_chips, max(len(sub_jobs), 1))
        # CHIPS_PER_TRIAL > 1 gives each trial executor its own multi-chip
        # mesh (the executor's device grant IS its mesh — see
        # worker/train.py set_device_grant -> parallel.get_default_mesh), so
        # a single trial trains data/tensor/sequence-parallel across chips.
        # The reference could never do this: 1 GPU per worker, hard-wired
        # (reference services_manager.py:117-126).
        chips_per_trial = max(int(budget.get(BudgetType.CHIPS_PER_TRIAL, 1)), 1)
        if avail is not None:
            # one executor's grant can never span hosts: clamp the per-trial
            # mesh to the largest single-host inventory (downsize, don't
            # fail — same policy as the CHIP_COUNT clamp above). Single-host
            # allocators report their whole inventory.
            max_per_service = getattr(
                avail, "max_chips_per_service", avail.total_chips)
            if chips_per_trial > max_per_service > 0:
                logger.info(
                    "CHIPS_PER_TRIAL=%d exceeds the largest host (%d chips); "
                    "downsizing the per-trial mesh", chips_per_trial,
                    max_per_service)
                chips_per_trial = max_per_service

        created: List[str] = []
        try:
            for i, sub in enumerate(sub_jobs):
                chips_per_sub = even + (1 if i < spare else 0)
                if chips_per_sub == 0:
                    # 0-chip fallback executor (shared devices)
                    workers = [0]
                elif chips_per_sub < chips_per_trial:
                    # downsized grant, like the chip-count clamp above —
                    # still one multi-chip executor rather than failing
                    workers = [chips_per_sub]
                else:
                    workers = [chips_per_trial] * (
                        chips_per_sub // chips_per_trial
                    )
                    stranded = chips_per_sub % chips_per_trial
                    if stranded:
                        # uniform grants on purpose: a smaller leftover
                        # executor would compile its own program instead of
                        # sharing the cached step — but say so
                        logger.info(
                            "sub_train_job %s: %d of %d chips idle "
                            "(CHIPS_PER_TRIAL=%d does not divide the "
                            "per-model share)", sub["id"], stranded,
                            chips_per_sub, chips_per_trial)
                for n_chips_each in workers:
                    sid = self._create_train_worker(sub["id"], n_chips_each)
                    created.append(sid)
            self._wait_until_services_running(created)
            self._db.mark_train_job_as_running(train_job_id)
        except Exception:
            # roll back partial deployments (reference :131-135)
            for sid in created:
                self._destroy_service(sid, wait=False)
            self._db.mark_train_job_as_errored(train_job_id)
            raise

    def _create_train_worker(self, sub_train_job_id: str, n_chips: int) -> str:
        service = self._db.create_service(ServiceType.TRAIN, replicas=1)
        self._db.create_train_job_worker(service["id"], sub_train_job_id)
        worker = TrainWorker(
            sub_train_job_id,
            self._db,
            self._advisors,
            send_event=self._send_event,
            params_dir=self._params_dir,
        )
        def place():
            return self._placement.create_service(
                service["id"], ServiceType.TRAIN, worker.start,
                n_chips=n_chips,
                # declarative payload so process/remote placements can
                # launch the worker without the closure
                extra={"sub_train_job_id": sub_train_job_id},
            )

        try:
            try:
                ctx = place()
            except InsufficientChipsError:
                # chip-budget arbitration: the chips this trial wants may
                # be ON LOAN to the serving plane (autoscaler borrow).
                # Training has priority over borrowed capacity — reclaim
                # (graceful scale-down of borrowed replicas) and retry
                # once before giving up.
                if (self._arbiter is None
                        or self._arbiter.reclaim_for_training(n_chips) <= 0):
                    raise
                logger.info(
                    "retrying train worker %s after reclaiming borrowed "
                    "serving chips", service["id"][:8])
                ctx = place()
        except Exception:
            # the DB rows exist but placement never started the service
            # (e.g. chips busy) — close the row so the rollback in
            # create_train_services (which only sees *returned* sids)
            # doesn't leave a phantom STARTED service behind
            self._db.mark_service_as_stopped(service["id"])
            raise
        try:
            # record the chip indices actually granted by the allocator
            self._db.update_service_chips(service["id"], ctx.chips)
        except Exception:
            # placement DID start the worker: tear it down, not just the row
            self._destroy_service(service["id"], wait=False)
            raise
        return service["id"]

    def stop_sub_train_job_services(self, sub_train_job_id: str) -> None:
        for w in self._db.get_workers_of_sub_train_job(sub_train_job_id):
            self._destroy_service(w["service_id"], wait=False)
        # the advisor session is keyed by sub_train_job_id; drop its GP
        # history now that no more trials will be proposed
        self._advisors.delete_advisor(sub_train_job_id)

    def stop_train_services(self, train_job_id: str) -> None:
        for w in self._db.get_workers_of_train_job(train_job_id):
            self._destroy_service(w["service_id"], wait=False)
        for sub in self._db.get_sub_train_jobs_of_train_job(train_job_id):
            self._advisors.delete_advisor(sub["id"])
        self.refresh_train_job_status(train_job_id)

    def refresh_train_job_status(self, train_job_id: str) -> None:
        """Derive job status from worker service states (reference :160-184)."""
        job = self._db.get_train_job(train_job_id)
        if job is None or job["status"] in (
            TrainJobStatus.STOPPED,
            TrainJobStatus.ERRORED,
        ):
            return
        workers = self._db.get_workers_of_train_job(train_job_id)
        statuses = []
        for w in workers:
            svc = self._db.get_service(w["service_id"])
            if svc:
                statuses.append(svc["status"])
        if not statuses:
            return
        if all(
            s in (ServiceStatus.STOPPED, ServiceStatus.ERRORED) for s in statuses
        ):
            if any(s == ServiceStatus.ERRORED for s in statuses):
                self._db.mark_train_job_as_errored(train_job_id)
            else:
                self._db.mark_train_job_as_stopped(train_job_id)

    def refresh_inference_job_status(
        self, inference_job_id: str
    ) -> Optional[str]:
        """Serving analogue of refresh_train_job_status (fleet health):
        when EVERY serving replica of an inference job is terminal — e.g.
        its hosts died and the heartbeat monitor errored their services —
        the job can never answer a query again, so it must reach a
        terminal status in the store without operator action. Returns the
        new job status when a transition happened, else None."""
        inf = self._db.get_inference_job(inference_job_id)
        if inf is None or inf["status"] in (
            InferenceJobStatus.STOPPED,
            InferenceJobStatus.ERRORED,
        ):
            return None
        statuses = []
        for w in self._db.get_workers_of_inference_job(inference_job_id):
            svc = self._db.get_service(w["service_id"])
            if svc:
                statuses.append(svc["status"])
        if not statuses or not all(
            s in (ServiceStatus.STOPPED, ServiceStatus.ERRORED)
            for s in statuses
        ):
            return None
        return self._teardown_serving(
            inference_job_id,
            errored=any(s == ServiceStatus.ERRORED for s in statuses))

    def _teardown_serving(self, inference_job_id: str,
                          errored: bool) -> str:
        """Shared serving-teardown tail: drop the predictor (and its
        dedicated port), close the predictor service row, and mark the
        job terminal. Used by the operator stop path and the all-replicas-
        dead refresh so the two cannot drift."""
        inf = self._db.get_inference_job(inference_job_id)
        with self._lock:
            self._predictors.pop(inference_job_id, None)
            psrv = self._predict_servers.pop(inference_job_id, None)
        if psrv is not None:
            psrv.stop()
        # the job's cached predictions die with its serving head: a
        # redeploy under the same app must never answer from the torn-
        # down fleet's cache (predictor/result_cache.py; the epoch bump
        # also drops in-flight fills that raced this teardown)
        from rafiki_tpu.predictor.result_cache import get_cache

        get_cache().flush_job(inference_job_id, reason="teardown")
        if inf and inf.get("predictor_service_id"):
            self._db.mark_service_as_stopped(inf["predictor_service_id"])
        if errored:
            self._db.mark_inference_job_as_errored(inference_job_id)
            return InferenceJobStatus.ERRORED
        self._db.mark_inference_job_as_stopped(inference_job_id)
        return InferenceJobStatus.STOPPED

    # -- inference -----------------------------------------------------------

    def create_inference_services(self, inference_job_id: str) -> Predictor:
        inf_job = self._db.get_inference_job(inference_job_id)
        assert inf_job is not None
        train_job = self._db.get_train_job(inf_job["train_job_id"])
        assert train_job is not None
        best_trials = self._db.get_best_trials_of_train_job(
            train_job["id"], max_count=config.INFERENCE_MAX_BEST_TRIALS
        )
        if not best_trials:
            self._db.mark_inference_job_as_errored(inference_job_id)
            raise ServiceDeploymentError(
                f"Train job {train_job['id']} has no completed trials"
            )
        # generative serving (docs/serving-generation.md): one BEST trial
        # serves the job — a token stream answers from exactly one model
        # (there is no cross-trial ensembling of incremental deltas), so
        # extra best trials would be dead weight; replicas still scale it
        generative = train_job["task"] == TaskType.TEXT_GENERATION
        if generative:
            best_trials = best_trials[:1]
        created: List[str] = []
        worker_trials: Dict[str, str] = {}
        # Capacity-aware replica count. Replicas buy capacity only when they
        # get their own chip, and redundancy only when they are separate
        # processes; same-chip replicas in one process just split batches —
        # halving batch occupancy and doubling per-query dispatches (the
        # reference's 2 replicas each got their own GPU,
        # reference services_manager.py:390-395 + config.py:10-11).
        n_replicas = config.INFERENCE_WORKER_REPLICAS_PER_TRIAL
        # CHIPS_PER_WORKER (inference budget): every serving executor gets
        # a multi-chip mesh — its worker sets the device grant
        # (worker/inference.py) and the model's pjit'd predict shards the
        # batch/params over those chips. The serving analogue of
        # CHIPS_PER_TRIAL; the reference pinned serving to 1 GPU/worker
        # (reference services_manager.py:390-395).
        budget = inf_job.get("budget") or {}
        chips_per_worker = max(
            int(budget.get(BudgetType.CHIPS_PER_WORKER, 1)), 1)
        alloc = getattr(self._placement, "allocator", None)
        if alloc is not None:
            # one worker's grant can never span hosts: clamp to the
            # largest single-host inventory, exactly like the
            # CHIPS_PER_TRIAL clamp above (fleet-total would let a
            # 6-chip ask through a 2x4-chip fleet and silently degrade
            # to the local fallback)
            max_per_service = getattr(
                alloc, "max_chips_per_service", alloc.total_chips)
            if chips_per_worker > max_per_service > 0:
                logger.warning(
                    "CHIPS_PER_WORKER=%d exceeds the largest host "
                    "(%d chips); downsizing the serving mesh",
                    chips_per_worker, max_per_service)
                chips_per_worker = max_per_service
            n_replicas = max(1, min(
                n_replicas,
                alloc.total_chips
                // max(len(best_trials) * chips_per_worker, 1)))
        # Fused ensemble (budget ENSEMBLE_FUSED): one worker per replica
        # slot holds ALL best trials co-resident and answers with the
        # final cross-trial ensemble — when the trials share a compiled
        # predict, the whole ensemble is a single vmapped device dispatch
        # (worker/inference.py _FusedEnsembleModel). Deployment shape
        # becomes n_replicas fused workers instead of a fleet per trial.
        fused = bool(budget.get(BudgetType.ENSEMBLE_FUSED, 0))
        if fused and generative:
            # fusing co-locates trials to answer one batch as one unit —
            # meaningless for a single-trial token stream; refuse typed
            # rather than deploy a worker shape the decode loop can't run
            self._db.mark_inference_job_as_errored(inference_job_id)
            raise ServiceDeploymentError(
                "budget ENSEMBLE_FUSED is unsupported for TEXT_GENERATION "
                "jobs: a token stream answers from one model, not a fused "
                "cross-trial ensemble — drop ENSEMBLE_FUSED")
        # Speculative decoding (budget GEN_DRAFT_TRIAL): the named draft
        # trial must exist, be COMPLETED, and be generation-capable — a
        # bad draft is a typed deploy error HERE, never a worker-boot
        # crash that takes the whole serving fleet down with it.
        draft_tid = budget.get(BudgetType.GEN_DRAFT_TRIAL)
        if draft_tid:
            if not generative:
                self._db.mark_inference_job_as_errored(inference_job_id)
                raise ServiceDeploymentError(
                    "budget GEN_DRAFT_TRIAL is only meaningful for "
                    "TEXT_GENERATION jobs — drop it, or deploy a "
                    "generative train job")
            draft_trial = self._db.get_trial(str(draft_tid))
            if draft_trial is None:
                self._db.mark_inference_job_as_errored(inference_job_id)
                raise ServiceDeploymentError(
                    f"budget GEN_DRAFT_TRIAL names unknown trial "
                    f"{draft_tid!r}")
            if draft_trial.get("status") != TrialStatus.COMPLETED:
                self._db.mark_inference_job_as_errored(inference_job_id)
                raise ServiceDeploymentError(
                    f"budget GEN_DRAFT_TRIAL trial {draft_tid!r} is "
                    f"{draft_trial.get('status')}, not COMPLETED — a "
                    "draft model needs trained params to propose tokens")
            draft_model = self._db.get_model(draft_trial["model_id"])
            from rafiki_tpu.admin.admin import Admin

            if draft_model is None \
                    or not Admin._model_generation_capable(draft_model):
                self._db.mark_inference_job_as_errored(inference_job_id)
                raise ServiceDeploymentError(
                    f"budget GEN_DRAFT_TRIAL trial {draft_tid!r} is not "
                    "generation-capable — the draft must implement the "
                    "generation contract (init_kv_cache/prefill/"
                    "decode_step) plus decode_step_sampled")
        if fused:
            from rafiki_tpu.sdk.sandbox import sandbox_enabled

            if sandbox_enabled():
                # ADVICE r5: fused serving would co-locate one JAX
                # sandbox CHILD PROCESS per trial on a single worker's
                # chip grant — N children contending for the same
                # devices is unsupported (and co-residency is the whole
                # point of fusing). Refuse with a typed deploy error
                # instead of failing at worker startup; the per-trial
                # fleet works fine under the sandbox.
                self._db.mark_inference_job_as_errored(inference_job_id)
                raise ServiceDeploymentError(
                    "budget ENSEMBLE_FUSED is unsupported with "
                    "RAFIKI_SANDBOX=1: fused serving co-locates every "
                    "best trial in one worker process, but sandboxed "
                    "models run as separate child processes that would "
                    "contend for the worker's chip grant — drop "
                    "ENSEMBLE_FUSED (per-trial fleet) or disable the "
                    "sandbox for this deployment")
            if alloc is not None:
                n_replicas = max(1, min(
                    config.INFERENCE_WORKER_REPLICAS_PER_TRIAL,
                    alloc.total_chips // max(chips_per_worker, 1)))
            # each deployment unit serves the whole group; the bookkeeping
            # row carries the group's top trial
            units = [{"trial_id": best_trials[0]["id"],
                      "group": f"fused:{inference_job_id}",
                      "trial_ids": [t["id"] for t in best_trials]}
                     for _ in range(n_replicas)]
        else:
            units = [{"trial_id": trial["id"], "group": trial["id"],
                      "trial_ids": None}
                     for trial in best_trials for _ in range(n_replicas)]
        try:
            for unit in units:
                _chaos_deploy(inference_job_id, unit["trial_id"])
                service = self._db.create_service(ServiceType.INFERENCE)
                self._db.create_inference_job_worker(
                    service["id"], inference_job_id, unit["trial_id"]
                )
                worker_trials[service["id"]] = unit["group"]
                worker_cls = InferenceWorker
                if generative:
                    from rafiki_tpu.worker.generation import GenerationWorker

                    worker_cls = GenerationWorker
                worker = worker_cls(
                    inference_job_id, unit["trial_id"], self._db,
                    self._broker, trial_ids=unit["trial_ids"],
                )
                # serving executors prefer an exclusive chip but fall
                # back to shared devices when training holds them all
                try:
                    ctx = self._placement.create_service(
                        service["id"],
                        ServiceType.INFERENCE,
                        worker.start,
                        n_chips=chips_per_worker,
                        best_effort_chips=True,
                        extra={"inference_job_id": inference_job_id,
                               "trial_id": unit["trial_id"],
                               **({"trial_ids": unit["trial_ids"]}
                                  if unit["trial_ids"] else {})},
                    )
                except Exception:
                    # close the row: it was never placed, and rollback
                    # only iterates sids in `created`
                    self._db.mark_service_as_stopped(service["id"])
                    raise
                # in `created` from the moment it is placed, so the
                # outer rollback tears it down even if the chip-index
                # bookkeeping below fails
                created.append(service["id"])
                self._db.update_service_chips(service["id"], ctx.chips)
                # STARTED -> DEPLOYING (guarded) while the deploy wait
                # runs: a row stuck here past SERVICE_DEPLOY_TIMEOUT_S
                # is a wedged deploy, and doctor flags it
                self._db.mark_service_as_deploying(service["id"])
            predictor_service = self._db.create_service(ServiceType.PREDICT)
            self._db.update_inference_job_predictor(
                inference_job_id, predictor_service["id"]
            )
            predictor = Predictor(
                inference_job_id, self._broker, train_job["task"],
                worker_trials=worker_trials,
            )
            with self._lock:
                self._predictors[inference_job_id] = predictor
            if config.PREDICTOR_PORTS:
                # dedicated serving door (reference parity: per-job
                # published ports, reference services_manager.py:379-384)
                from rafiki_tpu.predictor.server import PredictorServer

                psrv = PredictorServer(
                    predictor, train_job["app"],
                    host=config.PREDICTOR_HOST).start()
                with self._lock:
                    self._predict_servers[inference_job_id] = psrv
                self._db.update_service_host_port(
                    predictor_service["id"], psrv.host, psrv.port)
            self._wait_until_services_running(created)
            self._db.mark_service_as_running(predictor_service["id"])
            self._db.mark_inference_job_as_running(inference_job_id)
            return predictor
        except Exception:
            with self._lock:
                self._predictors.pop(inference_job_id, None)
                psrv = self._predict_servers.pop(inference_job_id, None)
            if psrv is not None:
                # failed deploy: nothing admitted is worth draining for —
                # close immediately rather than wait the drain window
                psrv.stop(drain_timeout_s=0.0)
            for sid in created:
                self._destroy_service(sid, wait=False)
            self._db.mark_inference_job_as_errored(inference_job_id)
            raise

    # -- control-plane crash recovery (admin/recovery.py) --------------------

    def adopt_inference_job(self, inference_job_id: str) -> Optional[Predictor]:
        """Rebuild the in-process serving head for an inference job whose
        replicas survived an admin restart: a fresh Predictor over the
        worker queues the recovery pass already re-registered with the
        broker, plus a rebound PredictorServer when the deployment uses
        per-job ports. predict() then answers WITHOUT a redeploy; the
        predict-route cache repopulates lazily on first use."""
        inf = self._db.get_inference_job(inference_job_id)
        if inf is None:
            return None
        train_job = self._db.get_train_job(inf["train_job_id"])
        if train_job is None:
            return None
        budget = inf.get("budget") or {}
        fused = bool(budget.get(BudgetType.ENSEMBLE_FUSED, 0))
        group = f"fused:{inference_job_id}" if fused else None
        workers = self._db.get_workers_of_inference_job(inference_job_id)
        # standbys adopt like any replica (their processes were re-owned
        # or fenced by the recovery pass) but stay OUT of the routable
        # set: promotion, not adoption, is what makes a standby serve
        worker_trials = {
            w["service_id"]: (group or w["trial_id"]) for w in workers
            if not int(w.get("standby") or 0)
        }
        # recovery adoption invalidates the job's prediction cache: the
        # adopted fleet may differ from what the dead admin last served
        # (a rollout resolved at boot, replicas lost), and a pre-crash
        # answer must never outlive the reconcile (in practice the cache
        # died with the old process — this guards the same-process
        # adoption paths tests and retries exercise). The rebuilt
        # Predictor carries the adopted fleet's real rollout generation
        # so cache keys stay version-true.
        from rafiki_tpu.predictor.result_cache import get_cache

        get_cache().flush_job(inference_job_id, reason="adoption")
        version = max((int(w.get("model_version") or 0) for w in workers),
                      default=0)
        predictor = Predictor(
            inference_job_id, self._broker, train_job["task"],
            worker_trials=worker_trials, serving_version=version,
        )
        with self._lock:
            self._predictors[inference_job_id] = predictor
            # idempotency: recovery retries this method on transient
            # store faults — a server bound by an earlier attempt must be
            # closed, not leaked as a stale listener
            stale_psrv = self._predict_servers.pop(inference_job_id, None)
        if stale_psrv is not None:
            stale_psrv.stop(drain_timeout_s=0.0)
        psid = inf.get("predictor_service_id")
        if config.PREDICTOR_PORTS:
            from rafiki_tpu.predictor.server import PredictorServer

            psrv = PredictorServer(
                predictor, train_job["app"],
                host=config.PREDICTOR_HOST).start()
            with self._lock:
                self._predict_servers[inference_job_id] = psrv
            if psid:
                # the dedicated door moved with the new admin process:
                # republish its host:port
                self._db.update_service_host_port(psid, psrv.host, psrv.port)
        if psid:
            # the predictor head lives again — in THIS process
            self._db.mark_service_as_running(psid)
        self._db.mark_inference_job_as_running(inference_job_id)
        return predictor

    def restart_train_worker(self, service_id: str, sub_train_job_id: str,
                             n_chips: int = 0) -> bool:
        """Relaunch a train executor under its EXISTING service id after
        a control-plane restart on a single-host placement (the executor
        threads died with the old admin process). The stale-RUNNING-trial
        resume in worker/train.py then re-runs exactly the trials the
        dead executor left behind. Best-effort chips: a busy grant must
        downgrade the executor, not error the job a second time."""
        worker = TrainWorker(
            sub_train_job_id,
            self._db,
            self._advisors,
            send_event=self._send_event,
            params_dir=self._params_dir,
        )
        try:
            ctx = self._placement.create_service(
                service_id, ServiceType.TRAIN, worker.start,
                n_chips=n_chips,
                best_effort_chips=True,
                extra={"sub_train_job_id": sub_train_job_id},
            )
        except Exception:
            logger.exception("restarting train worker %s failed",
                             service_id[:8])
            return False
        try:
            self._db.update_service_chips(service_id, ctx.chips)
        except Exception:
            logger.exception("chip bookkeeping failed for restarted %s",
                             service_id[:8])
        return True

    def get_predictor(self, inference_job_id: str) -> Optional[Predictor]:
        with self._lock:
            return self._predictors.get(inference_job_id)

    def predictors(self) -> Dict[str, Predictor]:
        """Snapshot of the live {inference_job_id: Predictor} map (fleet
        health reads every job's queue depths / overload counters)."""
        with self._lock:
            return dict(self._predictors)

    def stop_inference_services(self, inference_job_id: str) -> None:
        for w in self._db.get_workers_of_inference_job(inference_job_id):
            self._destroy_service(w["service_id"], wait=False)
        self._teardown_serving(inference_job_id, errored=False)

    # -- elastic serving (admin/autoscaler.py; docs/failure-model.md
    # "Overload adaptation") ------------------------------------------------

    def live_inference_workers(self, inference_job_id: str) -> List[Dict]:
        """The job's live serving replicas: worker rows whose service is
        non-terminal, annotated with the predictor's replica-group key
        (trial id, or the fused group). Drain-in-progress replicas are
        excluded — they no longer take traffic — and so are warm
        standbys, which never took any (admin/warm_pool.py)."""
        inf = self._db.get_inference_job(inference_job_id)
        fused = bool(((inf or {}).get("budget") or {}).get(
            BudgetType.ENSEMBLE_FUSED, 0))
        group_of = (lambda t: f"fused:{inference_job_id}") if fused \
            else (lambda t: t)
        with self._scale_lock:
            draining = set(self._scale_draining)
        # one status-filtered query (idx_service_status), not a
        # get_service round trip per worker row — this runs every
        # autoscaler tick for every job
        alive = {
            s["id"]: s
            for s in self._db.get_services(statuses=[
                ServiceStatus.STARTED, ServiceStatus.DEPLOYING,
                ServiceStatus.RUNNING])}
        out: List[Dict] = []
        for w in self._db.get_workers_of_inference_job(inference_job_id):
            if w["service_id"] in draining or int(w.get("standby") or 0):
                continue
            svc = alive.get(w["service_id"])
            if svc is not None:
                out.append({"service_id": w["service_id"],
                            "trial_id": w["trial_id"],
                            "group": group_of(w["trial_id"]),
                            # rollout generation this replica serves
                            # (admin/rollout.py; 0 = initial deploy)
                            "model_version": int(
                                w.get("model_version") or 0),
                            "chips": svc.get("chips") or []})
        return out

    def scale_inference_job(self, inference_job_id: str, delta: int,
                            borrow: bool = True,
                            drain_timeout_s: Optional[float] = None,
                            min_replicas: int = 1) -> Dict[str, Any]:
        """Add (``delta`` > 0) or gracefully drain (``delta`` < 0) serving
        replicas of a RUNNING inference job WITHOUT a redeploy — the live
        elasticity primitive under the autoscaler and the operator scale
        API. Returns {added, removed, borrowed_chips, returned_chips}.

        Scale-up places each new replica best-effort: with an exclusive
        chip grant when ``borrow`` is allowed by the chip arbiter (the
        loan is recorded for training to reclaim), on shared devices
        otherwise. Scale-down picks borrowed replicas first, never drops
        a trial's last replica while other trials keep several, and never
        goes below ``min_replicas`` live replicas job-wide."""
        inf = self._db.get_inference_job(inference_job_id)
        if inf is None or inf["status"] != InferenceJobStatus.RUNNING:
            raise ServiceDeploymentError(
                f"inference job {inference_job_id} is not RUNNING")
        predictor = self.get_predictor(inference_job_id)
        if predictor is None:
            raise ServiceDeploymentError(
                f"inference job {inference_job_id} has no live predictor")
        report: Dict[str, Any] = {"added": [], "removed": [], "promoted": [],
                                  "borrowed_chips": 0, "returned_chips": 0}
        if delta > 0:
            for _ in range(delta):
                # per-replica isolation mirroring the drain path: a later
                # failure must not erase the record of replicas (and chip
                # loans) that DID land
                try:
                    sid, borrowed, promoted = self._scale_up_one(
                        inference_job_id, inf, predictor, borrow)
                except Exception as e:
                    if not report["added"]:
                        raise
                    logger.exception(
                        "scale-up of job %s stopped after %d replica(s)",
                        inference_job_id[:8], len(report["added"]))
                    report["error"] = str(e)
                    break
                report["added"].append(sid)
                if promoted:
                    report["promoted"].append(sid)
                report["borrowed_chips"] += borrowed
        elif delta < 0:
            victims = self._pick_scale_down_victims(
                inference_job_id, -delta, min_replicas)
            freed, removed = self.drain_replicas(
                inference_job_id, victims, drain_timeout_s=drain_timeout_s)
            report["removed"] = removed
            report["returned_chips"] = freed
        return report

    def _scale_up_one(self, inference_job_id: str, inf: Dict,
                      predictor, borrow: bool):
        """Add ONE serving replica: promote a warm standby when the pool
        holds one (an ``add_worker`` route, ~ms — the replica is already
        loaded, warmed, and holding its chips), else place a fresh
        replica for the trial group that currently has the fewest live
        replicas. Returns (service_id, borrowed_chip_count,
        served_by_promotion)."""
        promoted = self.promote_standby(inference_job_id)
        if promoted is not None:
            return promoted, 0, True
        sid, borrowed, group, chips = self._place_replica(
            inference_job_id, inf, borrow=borrow, standby=False)
        # replica JOIN: route new requests to it (its queue is already
        # registered with the broker by the worker's startup)
        predictor.add_worker(sid, group)
        logger.info("scaled UP job %s: replica %s for group %s "
                    "(chips=%s)", inference_job_id[:8], sid[:8],
                    group[:16], chips)
        return sid, borrowed, False

    def _place_replica(self, inference_job_id: str, inf: Dict,
                       borrow: bool, standby: bool):
        """Deploy ONE extra serving replica for the trial group that
        currently has the fewest live replicas (the scale-up placement
        body, shared with the warm pool). ``standby`` marks the worker
        row: the replica loads and pre-warms exactly like a routable one
        but is NOT handed to the predictor — promotion does that later.
        Returns (service_id, borrowed_chip_count, group, chips)."""
        train_job = self._db.get_train_job(inf["train_job_id"])
        assert train_job is not None
        budget = inf.get("budget") or {}
        fused = bool(budget.get(BudgetType.ENSEMBLE_FUSED, 0))
        chips_per_worker = max(
            int(budget.get(BudgetType.CHIPS_PER_WORKER, 1)), 1)
        alloc = getattr(self._placement, "allocator", None)
        if alloc is not None:
            max_per_service = getattr(
                alloc, "max_chips_per_service", alloc.total_chips)
            if chips_per_worker > max_per_service > 0:
                chips_per_worker = max_per_service
        live = self.live_inference_workers(inference_job_id)
        if fused:
            best = self._db.get_best_trials_of_train_job(
                train_job["id"], max_count=config.INFERENCE_MAX_BEST_TRIALS)
            unit = {"trial_id": best[0]["id"] if best
                    else (live[0]["trial_id"] if live else None),
                    "group": f"fused:{inference_job_id}",
                    "trial_ids": [t["id"] for t in best] or None}
        else:
            by_group: Dict[str, int] = {}
            for w in live:
                by_group[w["group"]] = by_group.get(w["group"], 0) + 1
            if not by_group:
                raise ServiceDeploymentError(
                    f"inference job {inference_job_id} has no live "
                    "replicas to model the new one on")
            group = min(sorted(by_group), key=lambda g: by_group[g])
            unit = {"trial_id": group, "group": group, "trial_ids": None}
        if unit["trial_id"] is None:
            raise ServiceDeploymentError(
                f"no trial to serve for job {inference_job_id}")
        # a scaled-up replica inherits its group's rollout generation —
        # a post-rollout scale-up must not mint version-0 rows beside
        # version-N siblings (recovery reads the version to reconstruct
        # a mid-rollout fleet)
        version = max((w["model_version"] for w in live
                       if fused or w["group"] == unit["group"]), default=0)
        _chaos_deploy(inference_job_id, unit["trial_id"])
        # chip loan: exclusive grant only when the arbiter allows it (the
        # training floor stays intact); otherwise shared devices.
        # begin_borrow is an atomic check-AND-reserve so two concurrent
        # scale-ups can't both pass the floor check before either takes
        # its chips from the allocator
        want_chips = 0
        reservation = None
        if borrow and self._arbiter is not None:
            reservation = self._arbiter.begin_borrow(chips_per_worker)
            if reservation is not None:
                want_chips = chips_per_worker
        try:
            service = self._db.create_service(ServiceType.INFERENCE)
            self._db.create_inference_job_worker(
                service["id"], inference_job_id, unit["trial_id"],
                model_version=version, standby=standby)
            worker_cls = InferenceWorker
            if train_job["task"] == TaskType.TEXT_GENERATION:
                from rafiki_tpu.worker.generation import GenerationWorker

                worker_cls = GenerationWorker
            worker = worker_cls(
                inference_job_id, unit["trial_id"], self._db, self._broker,
                trial_ids=unit["trial_ids"],
            )
            try:
                ctx = self._placement.create_service(
                    service["id"], ServiceType.INFERENCE, worker.start,
                    n_chips=want_chips, best_effort_chips=True,
                    extra={"inference_job_id": inference_job_id,
                           "trial_id": unit["trial_id"],
                           **({"trial_ids": unit["trial_ids"]}
                              if unit["trial_ids"] else {})},
                )
            except Exception:
                self._db.mark_service_as_stopped(service["id"])
                raise
            try:
                self._db.update_service_chips(service["id"], ctx.chips)
                self._db.mark_service_as_deploying(service["id"])
                self._wait_until_services_running([service["id"]])
            except Exception:
                self._destroy_service(service["id"], wait=False)
                raise
        except Exception:
            if reservation is not None:
                self._arbiter.cancel_borrow(reservation)
            raise
        borrowed = 0
        if reservation is not None:
            if want_chips and ctx.chips:
                self._arbiter.commit_borrow(
                    reservation, service["id"], inference_job_id, ctx.chips)
                borrowed = len(ctx.chips)
                # durable twin of the in-memory loan book: a successor
                # admin rebuilds the arbiter from this column when it
                # adopts the replica (admin/recovery.py
                # _readopt_chip_loan) — without it, an admin restart
                # silently leaked the loan until the replica stopped
                try:
                    self._db.set_worker_borrowed_chips(
                        service["id"], borrowed)
                # lint: absorb(the marker is recovery accounting: failing to write it must not undo a committed scale-up)
                except Exception:
                    logger.exception(
                        "could not persist the %d-chip loan marker for "
                        "replica %s", borrowed, service["id"][:8])
            else:
                self._arbiter.cancel_borrow(reservation)
        return service["id"], borrowed, unit["group"], ctx.chips

    # -- warm standby pool (admin/warm_pool.py; docs/failure-model.md
    # "Cold-start faults") ---------------------------------------------------

    def standby_workers(self, inference_job_id: str) -> List[Dict]:
        """The job's warm standbys: standby-flagged worker rows whose
        service is RUNNING (loaded + pre-warmed, holding chips, NOT
        routed). DEPLOYING standbys are still warming and not yet
        promotable."""
        inf = self._db.get_inference_job(inference_job_id)
        fused = bool(((inf or {}).get("budget") or {}).get(
            BudgetType.ENSEMBLE_FUSED, 0))
        group_of = (lambda t: f"fused:{inference_job_id}") if fused \
            else (lambda t: t)
        alive = {
            s["id"]: s
            for s in self._db.get_services(statuses=[ServiceStatus.RUNNING])}
        out: List[Dict] = []
        for w in self._db.get_workers_of_inference_job(inference_job_id):
            if not int(w.get("standby") or 0):
                continue
            svc = alive.get(w["service_id"])
            if svc is not None:
                out.append({"service_id": w["service_id"],
                            "trial_id": w["trial_id"],
                            "group": group_of(w["trial_id"]),
                            "model_version": int(
                                w.get("model_version") or 0),
                            "chips": svc.get("chips") or []})
        return out

    def create_standby_replica(self, inference_job_id: str) -> str:
        """Place ONE warm standby for a RUNNING inference job: loaded,
        pre-warmed, chips held through the arbiter's borrow book
        (training's reclaim drains standbys FIRST), but never routed —
        promotion is what makes it serve. Returns the service id."""
        inf = self._db.get_inference_job(inference_job_id)
        if inf is None or inf["status"] != InferenceJobStatus.RUNNING:
            raise ServiceDeploymentError(
                f"inference job {inference_job_id} is not RUNNING")
        sid, borrowed, group, chips = self._place_replica(
            inference_job_id, inf, borrow=True, standby=True)
        if borrowed and self._arbiter is not None:
            # reclaim-priority tag: training wins these chips back FIRST
            self._arbiter.mark_standby(sid, True)
        logger.info(
            "warm pool: standby %s ready for job %s group %s (chips=%s,"
            " borrowed=%d)", sid[:8], inference_job_id[:8], group[:16],
            chips, borrowed)
        return sid

    def promote_standby(self, inference_job_id: str,
                        group: Optional[str] = None) -> Optional[str]:
        """Turn one warm standby into a routable replica: clear the
        durable standby flag, then ``predictor.add_worker`` — the ~ms
        scale-up/replacement path (no deploy, no compile; the worker's
        queue has been registered since its boot). Standbys older than
        what their group currently serves are skipped (rollouts retire
        those — a promotion must never resurrect a stale version).
        Returns the promoted service id, or None when the pool is empty
        for the (optional) group filter."""
        predictor = self.get_predictor(inference_job_id)
        if predictor is None:
            return None
        candidates = self.standby_workers(inference_job_id)
        if group is not None:
            candidates = [w for w in candidates if w["group"] == group]
        cur: Dict[str, int] = {}
        for w in self.live_inference_workers(inference_job_id):
            cur[w["group"]] = max(cur.get(w["group"], 0),
                                  w["model_version"])
        for w in candidates:
            if w["model_version"] < cur.get(w["group"], 0):
                continue
            sid = w["service_id"]
            try:
                # flag first: a crash between the two leaves a
                # promotable-but-unrouted replica (re-promoted or swept),
                # never a routed row recovery would treat as a standby
                self._db.set_worker_standby(sid, False)
                predictor.add_worker(sid, w["group"])
            # lint: absorb(a single unpromotable standby must not block trying its siblings; the pool loop replaces it)
            except Exception:
                logger.exception("promoting standby %s failed; trying "
                                 "siblings", sid[:8])
                continue
            if self._arbiter is not None:
                # now a load-bearing replica: reclaim treats its loan
                # like any other serving replica's
                self._arbiter.mark_standby(sid, False)
            from rafiki_tpu.utils.metrics import REGISTRY

            REGISTRY.counter(
                "rafiki_warm_pool_promotions_total",
                "warm standbys promoted into serving").inc()
            logger.info("warm pool: promoted standby %s into job %s "
                        "group %s", sid[:8], inference_job_id[:8],
                        w["group"][:16])
            return sid
        return None

    def drop_standby(self, service_id: str) -> None:
        """Destroy a standby outright (stale-version retirement, pool
        shrink): it serves no traffic, so there is nothing to drain —
        its chip loan comes home through the _destroy_service
        note_return chokepoint."""
        self._destroy_service(service_id, wait=False)

    # -- safe live rollouts (admin/rollout.py; docs/failure-model.md
    # "Rollout faults") ------------------------------------------------------

    def deploy_version_replica(self, inference_job_id: str, trial_id: str,
                               model_version: int) -> str:
        """Place ONE serving replica of ``trial_id`` carrying
        ``model_version`` on its worker row — the rollout controller's
        canary/rolling/restore placement primitive. Same placement shape
        as the initial deploy (prefers an exclusive chip, falls back to
        shared devices); no chip-arbiter loan — a rollout replaces
        capacity, it does not grow it. Raises ServiceDeploymentError on
        placement failure, deploy timeout, or a chaos ``site=deploy``
        injection; a failed replica is fully torn down before the raise
        so the caller's rollback never inherits half-placed state."""
        inf = self._db.get_inference_job(inference_job_id)
        if inf is None:
            raise ServiceDeploymentError(
                f"no inference job {inference_job_id}")
        train_job = self._db.get_train_job(inf["train_job_id"])
        assert train_job is not None
        budget = inf.get("budget") or {}
        chips_per_worker = max(
            int(budget.get(BudgetType.CHIPS_PER_WORKER, 1)), 1)
        alloc = getattr(self._placement, "allocator", None)
        if alloc is not None:
            max_per_service = getattr(
                alloc, "max_chips_per_service", alloc.total_chips)
            if chips_per_worker > max_per_service > 0:
                chips_per_worker = max_per_service
        _chaos_deploy(inference_job_id, trial_id)
        service = self._db.create_service(ServiceType.INFERENCE)
        self._db.create_inference_job_worker(
            service["id"], inference_job_id, trial_id,
            model_version=model_version)
        worker_cls = InferenceWorker
        if train_job["task"] == TaskType.TEXT_GENERATION:
            from rafiki_tpu.worker.generation import GenerationWorker

            worker_cls = GenerationWorker
        worker = worker_cls(
            inference_job_id, trial_id, self._db, self._broker)
        try:
            ctx = self._placement.create_service(
                service["id"], ServiceType.INFERENCE, worker.start,
                n_chips=chips_per_worker, best_effort_chips=True,
                extra={"inference_job_id": inference_job_id,
                       "trial_id": trial_id},
            )
        except Exception as e:
            self._db.mark_service_as_stopped(service["id"])
            raise ServiceDeploymentError(
                f"placing replica of trial {trial_id} failed: "
                f"{type(e).__name__}: {e}") from e
        try:
            self._db.update_service_chips(service["id"], ctx.chips)
            self._db.mark_service_as_deploying(service["id"])
            self._wait_until_services_running([service["id"]])
        except Exception as e:
            self._destroy_service(service["id"], wait=False)
            if isinstance(e, ServiceDeploymentError):
                raise
            raise ServiceDeploymentError(
                f"replica of trial {trial_id} never reached RUNNING: "
                f"{type(e).__name__}: {e}") from e
        logger.info("rollout: placed replica %s (trial %s, version %d) "
                    "for job %s", service["id"][:8], trial_id[:8],
                    model_version, inference_job_id[:8])
        return service["id"]

    def _pick_scale_down_victims(self, inference_job_id: str, n: int,
                                 min_replicas: int) -> List[str]:
        """Choose up to ``n`` replicas to drain: borrowed-chip replicas
        first (scale-down returns the loan), then the youngest rows; a
        trial's LAST replica is only eligible when every other trial is
        down to one as well (the ensemble must not silently lose a trial
        while siblings hold spares), and the job never drops below
        ``min_replicas`` live replicas."""
        live = self.live_inference_workers(inference_job_id)
        headroom = len(live) - max(min_replicas, 1)
        if headroom <= 0:
            return []
        n = min(n, headroom)
        by_group: Dict[str, int] = {}
        for w in live:
            by_group[w["group"]] = by_group.get(w["group"], 0) + 1
        borrowed = set()
        if self._arbiter is not None:
            borrowed = set(self._arbiter.borrowed())
        # youngest-last rows come back last from the store scan; prefer
        # draining the replicas added most recently
        ordered = sorted(
            reversed(live),
            key=lambda w: 0 if w["service_id"] in borrowed else 1)
        victims: List[str] = []
        for w in ordered:
            if len(victims) >= n:
                break
            spare_groups = any(
                c > 1 for g, c in by_group.items() if g != w["group"])
            if by_group[w["group"]] <= 1 and spare_groups:
                continue
            victims.append(w["service_id"])
            by_group[w["group"]] -= 1
        return victims

    def drain_replicas(
            self, inference_job_id: str, service_ids: List[str],
            drain_timeout_s: Optional[float] = None,
    ) -> "tuple[int, List[str]]":
        """Gracefully remove serving replicas: stop admitting (the
        predictor retires the replica from its fan-out), flush the worker
        queue (bounded by ``RAFIKI_AUTOSCALE_DRAIN_S``), then destroy —
        zero in-flight requests dropped on the happy path, and any
        straggler that races the final close is re-routed by the
        predictor's failover machinery. Idempotent: replicas already
        draining (a second concurrent scale-down) are skipped. Returns
        ``(borrowed chips returned to the pool, service_ids actually
        removed)`` — a victim whose drain failed is restored to the
        fan-out and does NOT count as removed."""
        if drain_timeout_s is None:
            drain_timeout_s = float(config.AUTOSCALE_DRAIN_S)
        with self._scale_lock:
            mine = [s for s in service_ids if s not in self._scale_draining]
            self._scale_draining.update(mine)
        predictor = self.get_predictor(inference_job_id)
        freed = 0
        removed: List[str] = []
        try:
            for sid in mine:
                if predictor is not None:
                    predictor.retire_worker(sid)
            for sid in mine:
                # per-victim isolation: one failed drain must not abandon
                # the OTHER victims retired-but-undestroyed (dead capacity
                # still counted live, loans never returned)
                loan = 0
                if self._arbiter is not None:
                    # read the loan size up front: _destroy_service (the
                    # teardown chokepoint inside _drain_one) performs the
                    # actual note_return
                    loan = self._arbiter.borrowed().get(sid, ("", 0))[1]
                try:
                    self._drain_one(inference_job_id, sid, predictor,
                                    drain_timeout_s)
                except Exception:
                    logger.exception(
                        "drain of replica %s failed; restoring it to the "
                        "fan-out", sid[:8])
                    if predictor is not None:
                        predictor.unretire_worker(sid)
                    continue
                removed.append(sid)
                freed += loan
        finally:
            with self._scale_lock:
                self._scale_draining.difference_update(mine)
        return freed, removed

    @staticmethod
    def _resident_streams(sid: str) -> int:
        """Generation streams still RESIDENT on a replica (busy slots +
        preempted-stashed) — what a drain must wait out beyond the queue
        depth: a generation replica with an empty inbox can still be
        minutes from finishing its admitted streams. 0 for
        classification replicas (no such stats row key)."""
        from rafiki_tpu.worker.inference import SERVING_STATS, _stats_lock

        with _stats_lock:
            row = SERVING_STATS.get(sid)
            return int(row.get("gen_resident_streams", 0)) if row else 0

    def _drain_one(self, inference_job_id: str, sid: str, predictor,
                   drain_timeout_s: float) -> None:
        queue = self._broker.get_worker_queues(inference_job_id).get(sid)
        depth_fn = getattr(queue, "depth", None)
        deadline = time.monotonic() + max(drain_timeout_s, 0.0)
        zero_reads = 0
        while callable(depth_fn) and time.monotonic() < deadline:
            try:
                depth = depth_fn()
            # lint: absorb(a dead queue handle simply ends the drain wait)
            except Exception:
                break
            if depth <= 0 and self._resident_streams(sid) <= 0:
                # consecutive-zero confirmation: a request that snapshotted
                # its routes before the retire may still land one submit —
                # give those stragglers a beat to either arrive or finish
                zero_reads += 1
                if zero_reads >= 3:
                    break
            else:
                zero_reads = 0
            time.sleep(0.03)
        else:
            if callable(depth_fn):
                try:
                    leftover = depth_fn()
                # lint: absorb(final depth read is diagnostic only)
                except Exception:
                    leftover = -1
                if leftover:
                    logger.warning(
                        "replica %s still has %d queued queries after the "
                        "%.1fs drain window; destroying anyway (stragglers "
                        "fail over to siblings)", sid[:8], leftover,
                        drain_timeout_s)
        # wait=True: the worker finishes its in-flight batch before the
        # queue closes, so everything taken is answered
        self._destroy_service(sid, wait=True)
        if predictor is not None:
            predictor.drop_worker(sid)
        logger.info("scaled DOWN job %s: replica %s drained and destroyed",
                    inference_job_id[:8], sid[:8])

    def reclaim_borrowed(self, n_chips: int) -> int:
        """Chip-arbiter reclaim callback: drain borrowed serving replicas
        until ``n_chips`` came home or the loan book is empty. Training
        demand outranks borrowed serving capacity by contract — but a
        reclaim is still a scale-down, so it honors the same guards as
        any other: never below the job's replica floor, never a trial's
        last replica while siblings hold spares (a borrowed replica may
        have BECOME load-bearing if its siblings died since the loan).

        Warm standbys drain FIRST: they serve no traffic, so their
        chips come home with an outright destroy (no drain window, no
        routing guards) before any routable replica is touched —
        the training floor outranks warm spare capacity by contract."""
        if self._arbiter is None:
            return 0
        freed = 0
        for sid, (job_id, n) in list(self._arbiter.borrowed().items()):
            if freed >= n_chips:
                break
            try:
                row = self._db.get_inference_job_worker(sid)
            # lint: absorb(an unreadable worker row just means this loan is reclaimed through the regular drain path below)
            except Exception:
                continue
            if row is not None and int(row.get("standby") or 0):
                self._destroy_service(sid, wait=False)
                freed += n
                from rafiki_tpu.utils.metrics import REGISTRY

                REGISTRY.counter(
                    "rafiki_warm_pool_reclaims_total",
                    "warm standbys destroyed to return chips to "
                    "training").inc()
                logger.info("reclaim: standby %s destroyed, %d chip(s) "
                            "home", sid[:8], n)
        if freed >= n_chips:
            return freed
        loans = self._arbiter.borrowed()
        by_job: Dict[str, List[str]] = {}
        for sid, (job_id, _) in loans.items():
            by_job.setdefault(job_id, []).append(sid)
        min_r = max(int(config.AUTOSCALE_MIN_REPLICAS), 1)
        for job_id, sids in by_job.items():
            if freed >= n_chips:
                break
            try:
                eligible = [
                    s for s in self._pick_scale_down_victims(
                        job_id, len(sids), min_r)
                    if s in loans]
            except Exception:
                logger.exception("reclaim victim pick for job %s failed",
                                 job_id[:8])
                continue
            for sid in eligible:
                if freed >= n_chips:
                    break
                try:
                    freed += self.drain_replicas(job_id, [sid])[0]
                except Exception:
                    logger.exception("reclaim drain of %s failed", sid[:8])
        return freed

    # -- shared --------------------------------------------------------------

    def _destroy_service(self, service_id: str, wait: bool = True) -> None:
        try:
            self._placement.destroy_service(service_id, wait=wait)
        except Exception:
            logger.exception("destroying service %s failed", service_id)
        self._db.mark_service_as_stopped(service_id)
        # every teardown path funnels here: a destroyed replica's chip
        # loan comes home no matter WHY it died (job stop, deploy
        # rollback, drain) — note_return is an idempotent pop. The
        # durable marker clears with it so a later admin restart cannot
        # resurrect a loan that already came home.
        if self._arbiter is not None:
            if self._arbiter.note_return(service_id) > 0:
                try:
                    self._db.set_worker_borrowed_chips(service_id, 0)
                # lint: absorb(the marker is recovery accounting: a failed clear leaves a stale row for a stopped replica, which adoption ignores)
                except Exception:
                    logger.exception(
                        "could not clear the loan marker for replica %s",
                        service_id[:8])

    def _wait_until_services_running(self, service_ids: List[str]) -> None:
        """Poll the store until all services are RUNNING (reference :279-290)."""
        deadline = time.time() + config.SERVICE_DEPLOY_TIMEOUT_S
        pending = set(service_ids)
        while pending:
            for sid in list(pending):
                svc = self._db.get_service(sid)
                if svc is None or svc["status"] == ServiceStatus.ERRORED:
                    raise ServiceDeploymentError(f"Service {sid} errored on deploy")
                if svc["status"] in (ServiceStatus.RUNNING, ServiceStatus.STOPPED):
                    # STOPPED is fine: a fast worker may have already finished
                    pending.discard(sid)
            if pending:
                if time.time() > deadline:
                    raise ServiceDeploymentError(
                        f"Services not running after "
                        f"{config.SERVICE_DEPLOY_TIMEOUT_S}s: {pending}"
                    )
                time.sleep(0.05)
