"""Admin: all orchestration business logic (reference rafiki/admin/admin.py:29-675).

Capability parity: user management with RBAC + seeded superadmin, model CRUD
(template file stored as bytes, validated at upload), train-job lifecycle with
app auto-versioning, trial introspection (status/logs/params), inference-job
lifecycle (requires train job STOPPED, one running inference job per train
job), worker events driving job status.

Architectural difference: Admin composes the in-process stack directly —
store, placement manager, advisor store, broker — instead of shelling out to
Docker through a socket. The HTTP layer (admin/http.py) is a thin shell over
this class, so library use (tests, notebooks, single-host deployments) and
REST use are the same code path.
"""

from __future__ import annotations

import collections
import json
import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional

from rafiki_tpu import config
from rafiki_tpu.advisor.advisor import AdvisorStore
from rafiki_tpu.admin.services import ServicesManager
from rafiki_tpu.cache.shm_broker import make_broker
from rafiki_tpu.constants import (
    InferenceJobStatus,
    ModelAccessRight,
    TrainJobStatus,
    UserType,
)
from rafiki_tpu.db.database import Database, StaleEpochError
from rafiki_tpu.placement.hosts import StaleAdminEpochError
from rafiki_tpu.placement.manager import ChipAllocator, LocalPlacementManager
from rafiki_tpu.sdk.knob import serialize_knob_config
from rafiki_tpu.sdk.log import parse_logs
from rafiki_tpu.sdk.model import (
    InvalidModelClassError,
    load_model_class,
    validate_model_dependencies,
)
from rafiki_tpu.utils.auth import (
    UnauthorizedError,
    generate_token,
    hash_password,
    verify_password,
)
from rafiki_tpu.worker.train import (EVENT_BUDGET_REACHED,
                                     EVENT_TRIAL_FAULT_LIMIT)

logger = logging.getLogger(__name__)


class InvalidRequestError(Exception):
    pass


class Admin:
    def __init__(
        self,
        db: Optional[Database] = None,
        placement: Optional[LocalPlacementManager] = None,
        params_dir: Optional[str] = None,
        recover: bool = True,
        lease=None,
        advertise_addr: Optional[str] = None,
    ):
        """``recover`` (default on) makes boot idempotent on an existing
        store: non-terminal jobs/services left by a crashed admin are
        reconciled against what is actually running — adopt / reschedule /
        fence / error (admin/recovery.py; docs/failure-model.md
        "Control-plane faults"). The snapshot is taken synchronously here
        (state created after this constructor is never touched); the
        reconciliation itself runs off-thread behind a ``recovering ->
        ready`` state the HTTP doors gate on.

        ``lease`` is a LeaseManager that ALREADY holds leadership — the
        hot-standby promotion path (admin/standby.py) passes the one it
        just acquired with. Without it, RAFIKI_ADMIN_HA=1 makes this
        constructor acquire its own lease (blocking up to
        RAFIKI_ADMIN_LEASE_ACQUIRE_TIMEOUT_S) before touching the store;
        HA off (the default) keeps the legacy single-admin behavior with
        zero fencing overhead. ``advertise_addr`` ("host:port") rides the
        lease row as the leader hint standby 503s and client failover
        follow."""
        self.db = db or Database()
        # -- control-plane HA: leadership lease + epoch fence --------------
        # (admin/lease.py; docs/failure-model.md "Control-plane HA").
        # Must be settled BEFORE the first store mutation below
        # (_seed_superadmin / recovery): a leader's writes carry its epoch
        # from the very first one.
        from rafiki_tpu.admin.lease import LeaseManager, LeaseNotAcquiredError

        self._lease: Optional[LeaseManager] = lease
        if self._lease is None and config.ADMIN_HA:
            self._lease = LeaseManager(self.db, addr=advertise_addr)
            if not self._lease.acquire(
                    block=True,
                    timeout_s=config.ADMIN_LEASE_ACQUIRE_TIMEOUT_S):
                raise LeaseNotAcquiredError(
                    "another admin holds a live leadership lease "
                    f"(row: {self._lease.leader_row()}); boot this one as "
                    "a hot standby (admin/standby.py) instead")
        if self._lease is not None:
            # the promoted-standby path hands over a lease bound to the
            # watcher's handle; arm the fence on THIS admin's handle too.
            # The renewal thread starts at the END of this constructor
            # (acquire() armed a full TTL of validity, plenty for boot);
            # starting it here would un-confine every attribute below.
            self._lease.bind(self.db)
        self.advisor_store = AdvisorStore()
        # predict hot path: (user, app, version) -> (ts, Predictor); the
        # epoch counter lets stop-time invalidation win over in-flight
        # resolutions (see predict/_drop_predict_routes)
        self._predict_route_cache: Dict[Any, Any] = {}
        self._predict_route_lock = threading.Lock()
        self._predict_route_epoch = 0
        # serving counters reported by out-of-process inference workers
        # over the event channel (see handle_event / get_inference_job_stats).
        # Bounded LRU: stop-time pruning alone can lose the race with a
        # worker's final drain-window push, so the cap — not the prune — is
        # what makes unbounded growth impossible in a long-lived admin.
        self._remote_serving_stats: "collections.OrderedDict[str, Dict[str, int]]" = (
            collections.OrderedDict())
        self._remote_serving_stats_cap = 512
        # overload control on the admin serving door (/predict/<app>):
        # same bounded in-flight + estimated-wait gate the dedicated
        # predictor port runs (predictor/admission.py); one controller for
        # the whole door — it protects this process, not one job
        from rafiki_tpu.predictor.admission import AdmissionController

        # door="admin": the /predict/<app> route's registry metrics
        # (admitted/shed counters + request-latency histogram) are
        # labeled apart from the per-job dedicated ports
        self._predict_admission = AdmissionController(
            door="admin", shared_tenants=True)
        # RAFIKI_BROKER=shm selects the native cross-process data
        # plane (cache/shm_broker.py); default is in-process.
        # RAFIKI_PLACEMENT=process *requires* it (worker processes attach to
        # the shm segments), so process mode forces the shm broker.
        placement_mode = os.environ.get("RAFIKI_PLACEMENT")
        process_mode = (
            placement is None and placement_mode in ("process", "hosts")
        )
        if process_mode:
            from rafiki_tpu.cache.shm_broker import ShmBroker

            self.broker = ShmBroker()
        else:
            self.broker = make_broker()
        # FleetBroker adds remote (agent-relayed) serving queues on top of
        # whatever local data plane was chosen; pass-through otherwise
        from rafiki_tpu.cache.fleet import FleetBroker

        self.broker = FleetBroker(self.broker)
        if placement is not None:
            self.placement = placement
        elif process_mode:
            from rafiki_tpu.placement.manager import ChipAllocator
            from rafiki_tpu.placement.process import (
                ProcessPlacementManager, host_chip_inventory)

            local = ProcessPlacementManager(
                db=self.db,
                broker=self.broker,
                # an explicit inventory: this process starts the workers
                # that hold the chips, so it must never open them itself
                allocator=ChipAllocator(host_chip_inventory()),
                on_status=self._on_service_status,
                # admin-embedded engine: TRAIN children outlive an admin
                # crash so boot reconciliation can adopt them by pid
                # (worker/bootstrap.py orphan watchdog; admin/recovery.py).
                # NOT in hosts mode: there this engine is only a fallback,
                # recovery adopts via agents and deliberately never by
                # local pid — a surviving child would just double-run its
                # rescheduled service id.
                orphan_survivable=(placement_mode != "hosts"),
            )
            if placement_mode == "hosts":
                # multi-host: train AND inference go to per-host agents
                # (RAFIKI_AGENTS=host:port,host:port); remote inference
                # workers are reached through the FleetBroker's agent
                # relay, with this host's engine as the serving fallback
                from rafiki_tpu.placement.hosts import HostAgentPlacementManager

                agents = [a.strip() for a in
                          os.environ.get("RAFIKI_AGENTS", "").split(",")
                          if a.strip()]
                self.placement = HostAgentPlacementManager(
                    agents,
                    local=local,
                    key=os.environ.get("RAFIKI_AGENT_KEY"),
                    on_status=self._on_service_status,
                    db=self.db,
                )
            else:
                self.placement = local
        else:
            self.placement = LocalPlacementManager(
                on_status=self._on_service_status
            )
        if self.placement.on_status is None:
            self.placement.on_status = self._on_service_status
        if hasattr(self.placement, "set_broker"):
            # multi-host placement registers remote serving queues with the
            # FleetBroker when it places inference workers on agents
            self.placement.set_broker(self.broker)
        if self._lease is not None and hasattr(self.placement,
                                               "set_epoch_provider"):
            # agent calls carry the leadership epoch (the agent-side half
            # of epoch fencing); last_epoch so a fenced ex-leader still
            # gets the *typed* stale-epoch refusal
            self.placement.set_epoch_provider(self._lease.last_epoch)
        # chip-budget arbitration between the serving and training planes
        # (placement/hosts.py ChipBudgetArbiter): autoscaler scale-ups may
        # borrow idle trial chips; a train executor that can't allocate
        # reclaims them, with RAFIKI_AUTOSCALE_TRAIN_FLOOR chips that the
        # serving plane may never borrow into
        from rafiki_tpu.placement.hosts import ChipBudgetArbiter

        self.chip_arbiter = ChipBudgetArbiter(
            getattr(self.placement, "allocator", None))
        self.services = ServicesManager(
            self.db,
            self.placement,
            self.advisor_store,
            self.broker,
            send_event=self.handle_event,
            params_dir=params_dir,
            arbiter=self.chip_arbiter,
        )
        # the elastic serving control loop (admin/autoscaler.py). The
        # instance always exists — /fleet/health carries its section and
        # the operator scale API goes through the same machinery — but
        # the loop thread only runs when RAFIKI_AUTOSCALE=1.
        from rafiki_tpu.admin.autoscaler import Autoscaler

        self.autoscaler = Autoscaler(self)
        if config.AUTOSCALE:
            self.autoscaler.start()
        # warm standby pool (admin/warm_pool.py): K pre-loaded,
        # pre-warmed standby replicas per hot job, so scale-up and
        # failed-replica replacement become an add_worker route instead
        # of a deploy. Always constructed (fleet health carries its
        # section); the maintenance thread only runs when
        # RAFIKI_AUTOSCALE_WARM_POOL > 0.
        from rafiki_tpu.admin.warm_pool import WarmPool

        self.warm_pool = WarmPool(self)
        if int(config.AUTOSCALE_WARM_POOL) > 0:
            self.warm_pool.start()
        # safe live rollouts (admin/rollout.py): canary -> rolling ->
        # done with automatic rollback, updating a RUNNING inference job
        # to a new trial in place. Constructed before recovery so the
        # boot pass can resolve a crashed admin's half-finished rollout.
        from rafiki_tpu.admin.rollout import RolloutController

        self.rollouts = RolloutController(self)
        # the drift closed loop (admin/drift.py): detection -> bounded
        # warm-started retrain -> SLO-guarded auto-rollout. Always
        # constructed (fleet health + drift status/ack go through it);
        # the monitor thread only runs with RAFIKI_DRIFT=1. Built after
        # the rollout controller (it drives rollouts) and before
        # recovery (whose boot pass resumes mid-loop drift rows).
        from rafiki_tpu.admin.drift import DriftController

        self.drift = DriftController(self)
        if config.DRIFT:
            self.drift.start()
        self._seed_superadmin()
        # -- control-plane crash recovery (admin/recovery.py) -------------
        self._recovery: Dict[str, Any] = {"state": "ready"}
        self._recovery_thread: Optional[threading.Thread] = None
        self._recovery_runner = None
        if recover:
            from rafiki_tpu.admin.recovery import ControlPlaneRecovery

            rec = ControlPlaneRecovery(self)
            # the scan runs HERE, synchronously: the to-reconcile set is
            # frozen before the constructor returns, so jobs created on
            # this fresh admin can never race the reconciler
            snapshot = rec.snapshot()
            if rec.needed(snapshot):
                self._recovery = {"state": "recovering",
                                  "started_at": time.time()}
                self._recovery_runner = rec
                self._recovery_thread = threading.Thread(
                    target=self._run_recovery, args=(rec, snapshot),
                    name="admin-recovery", daemon=True)
                self._recovery_thread.start()
            else:
                self._recovery = rec.empty_report()
        if self._lease is not None:
            # no-op for a promoted standby's already-running lease thread
            self._lease.start()

    def _run_recovery(self, rec, snapshot) -> None:
        try:
            # run() absorbs reconcile failures into the report (state
            # `ready`, failed=True, persisted for doctor) — the doors
            # must open either way
            self._recovery = rec.run(snapshot)
        except Exception:
            # belt for a bug in run() itself: never leave the doors 503ing
            logger.exception("control-plane recovery failed")
            self._recovery = {**rec.report, "state": "ready",
                              "failed": True}

    def recovery_status(self) -> Dict[str, Any]:
        """The boot-reconciliation state/report (``recovering`` while the
        off-thread pass runs; the HTTP doors 503 until ``ready``)."""
        return dict(self._recovery)

    def recovery_public(self) -> Dict[str, Any]:
        """The unauthenticated slice of the recovery state: just enough
        for a credential-less client to wait out a restarting admin. The
        full report (counts, per-service reasons, agent addresses) stays
        behind the admin-rights GET /fleet/health."""
        return {"state": self._recovery.get("state", "ready")}

    # -- control-plane HA (admin/lease.py, admin/standby.py) ---------------

    @property
    def lease(self):
        """This admin's LeaseManager (None when HA is off)."""
        return self._lease

    def ha_role(self) -> str:
        """``leader`` (HA off counts as leader — there is nobody else),
        or ``fenced`` once this admin's lease lapsed or was taken over."""
        if self._lease is None:
            return "leader"
        return self._lease.role()

    def ha_epoch(self) -> Optional[int]:
        return self._lease.last_epoch() if self._lease is not None else None

    def leader_hint(self) -> Optional[str]:
        """The current lease holder's advertised address — what standby /
        fenced 503s carry so clients fail over straight to the leader."""
        if self._lease is None:
            return None
        row = self._lease.leader_row()
        return row.get("addr") if row else None

    def ha_public(self) -> Dict[str, Any]:
        """Unauthenticated HA slice for the public root: role + leader
        hint (no holder ids, no lease internals)."""
        if self._lease is None:
            return {"role": "leader"}
        return {"role": self._lease.role(), "leader": self.leader_hint()}

    # -- users ---------------------------------------------------------------

    def _seed_superadmin(self) -> None:
        if self.db.get_user_by_email(config.SUPERADMIN_EMAIL) is None:
            self.db.create_user(
                config.SUPERADMIN_EMAIL,
                hash_password(config.SUPERADMIN_PASSWORD),
                UserType.SUPERADMIN,
            )

    def authenticate_user(self, email: str, password: str) -> Dict[str, Any]:
        user = self.db.get_user_by_email(email)
        if user is None or not verify_password(password, user["password_hash"]):
            raise UnauthorizedError("Invalid email or password")
        if user["banned"]:
            raise UnauthorizedError("User is banned")
        token = generate_token(
            {"user_id": user["id"], "user_type": user["user_type"]}
        )
        return {
            "user_id": user["id"],
            "user_type": user["user_type"],
            "token": token,
        }

    def create_user(self, email: str, password: str, user_type: str) -> Dict:
        if self.db.get_user_by_email(email) is not None:
            raise InvalidRequestError(f"User {email} already exists")
        user = self.db.create_user(email, hash_password(password), user_type)
        return self._user_view(user)

    def get_users(self) -> List[Dict]:
        return [self._user_view(u) for u in self.db.get_users()]

    def ban_user(self, email: str) -> Dict:
        user = self.db.get_user_by_email(email)
        if user is None:
            raise InvalidRequestError(f"No such user {email}")
        self.db.ban_user(user["id"])
        return self._user_view({**user, "banned": 1})

    @staticmethod
    def _user_view(user: Dict) -> Dict:
        return {
            "id": user["id"],
            "email": user["email"],
            "user_type": user["user_type"],
            "banned": bool(user["banned"]),
        }

    # -- models ----------------------------------------------------------------

    def create_model(
        self,
        user_id: str,
        name: str,
        task: str,
        model_file_bytes: bytes,
        model_class: str,
        dependencies: Optional[Dict[str, Optional[str]]] = None,
        access_right: str = ModelAccessRight.PRIVATE,
    ) -> Dict:
        # validate at upload, not at trial time: class loads, subclasses
        # BaseModel, declares a sane knob config, deps importable. With
        # RAFIKI_INSTALL_DEPS=1 missing deps are accepted here — workers
        # provision them per dependency-set at first use (sdk/deps.py,
        # the reference's install synthesis re-homed,
        # reference model/model.py:244-273)
        from rafiki_tpu.sdk.deps import install_enabled

        # static verification FIRST (analysis/template.py): AST passes
        # over the uploaded source — the platform catches a bad template
        # HERE, not after it has burned trial budget and chip-hours, and
        # at enforce a hostile template (sandbox-forbidden imports) is
        # rejected BEFORE load_model_class executes its module top level
        # in this process. enforce rejects on error findings (typed
        # ModelVerificationError -> 400 at the door); warn persists
        # findings on the row and logs; off skips (doctor WARNs while
        # jobs are live). With dependencies=None the verifier reads the
        # class's literal ``dependencies`` attribute statically.
        report = self._verify_template(
            model_file_bytes, model_class, dependencies, enforce=True)
        clazz = load_model_class(model_file_bytes, model_class)
        # task/capability consistency (docs/serving-generation.md): a
        # generative template under a classification task — or a
        # classification template under TEXT_GENERATION — is a typed 400
        # HERE, not a trial-time crash or a deploy-time surprise
        self._validate_task_capability(task, clazz, report)
        missing = validate_model_dependencies(clazz)
        if missing and not install_enabled():
            raise InvalidModelClassError(
                f"Dependencies not available in this environment: {missing} "
                f"(set RAFIKI_INSTALL_DEPS=1 to let workers provision them)"
            )
        serialize_knob_config(clazz.get_knob_config())
        effective_deps = dependencies or dict(
            getattr(clazz, "dependencies", {}) or {})
        if self.db.get_model_by_name(user_id, name) is not None:
            raise InvalidRequestError(f"Model {name} already exists for user")
        model = self.db.create_model(
            user_id,
            name,
            task,
            model_file_bytes,
            model_class,
            effective_deps,
            access_right,
            verification=json.dumps(report.to_dict()) if report else None,
        )
        return self._model_view(model)

    @staticmethod
    def _model_generation_capable(model_row: Dict) -> bool:
        """Generation capability of a STORED model row: the persisted
        verification report when one exists, else a fresh static pass
        over the stored bytes (never executes the template)."""
        verification = model_row.get("verification")
        if isinstance(verification, str):
            try:
                verification = json.loads(verification)
            except ValueError:
                verification = None
        caps = (verification or {}).get("capabilities") or {}
        if "generation" in caps:
            return bool(caps.get("generation"))
        from rafiki_tpu import analysis

        return analysis.static_generation_capability(
            model_row["model_file_bytes"],
            model_row.get("model_class")) is not None

    @staticmethod
    def _validate_task_capability(task: str, clazz: type, report) -> None:
        """Task-type plumbing for the generative subsystem: the uploaded
        template's statically-derived capability (or the runtime oracle
        when verification ran =off) must MATCH the declared task. Both
        mismatch directions raise the typed InvalidModelClassError the
        HTTP door already maps to 400."""
        from rafiki_tpu.constants import TaskType
        from rafiki_tpu.sdk.model import generation_capability

        if report is not None and "generation" in (
                getattr(report, "capabilities", None) or {}):
            capable = bool(report.capabilities.get("generation"))
        else:
            capable = generation_capability(clazz) is not None
        if task == TaskType.TEXT_GENERATION and not capable:
            raise InvalidModelClassError(
                f"task {task} requires a generation-capable template: "
                "declare a GenerationSpec class attribute and override "
                "init_kv_cache/prefill/decode_step (sdk/model.py; a "
                "half-wired spec does not count — see the GEN001 finding)")
        if capable and task != TaskType.TEXT_GENERATION:
            raise InvalidModelClassError(
                f"template advertises a GenerationSpec but was uploaded "
                f"under task {task}: generative templates must be "
                f"uploaded under task {TaskType.TEXT_GENERATION} (their "
                "serving path is the token-streaming decode loop, which "
                f"a {task} inference job would never deploy)")

    @staticmethod
    def _verify_template(model_file_bytes: bytes, model_class: str,
                         dependencies: Optional[Dict[str, Optional[str]]],
                         enforce: bool):
        """Run the template verifier under the RAFIKI_VERIFY_TEMPLATES
        mode; returns the report (None when mode=off). ``enforce=False``
        is the dry-run path (verify_model) — report only, never raise."""
        from rafiki_tpu import analysis

        mode = analysis.verify_mode()
        if mode == "off":
            return None
        report = analysis.verify_template_bytes(
            model_file_bytes, model_class, dependencies)
        if report.findings:
            logger.warning(
                "template %s static verification: %s", model_class,
                "; ".join(str(f) for f in report.findings[:10]))
        if enforce and mode == "enforce" and not report.ok:
            raise analysis.ModelVerificationError(report)
        return report

    def verify_model(
        self,
        model_file_bytes: bytes,
        model_class: str,
        dependencies: Optional[Dict[str, Optional[str]]] = None,
    ) -> Dict:
        """Dry-run the template verifier (POST /models/verify): the full
        report as JSON, no model row created, nothing rejected — the
        pre-upload loop clients iterate against. Runs even when
        RAFIKI_VERIFY_TEMPLATES=off (an explicit dry-run request is an
        explicit request)."""
        from rafiki_tpu import analysis

        report = analysis.verify_template_bytes(
            model_file_bytes, model_class, dependencies)
        return {"mode": analysis.verify_mode(), **report.to_dict()}

    def get_models(
        self, user_id: str, task: Optional[str] = None
    ) -> List[Dict]:
        """Models visible to `user_id`: their own + PUBLIC ones."""
        return [
            self._model_view(m)
            for m in self.db.get_models(task)
            if m["user_id"] == user_id
            or m["access_right"] == ModelAccessRight.PUBLIC
        ]

    def _resolve_model(
        self, user_id: str, name: str, owner_id: Optional[str]
    ) -> Dict:
        """Resolve a model by name: explicit owner if given, else the
        caller's own, else any PUBLIC model of that name (so listed public
        models are actually fetchable)."""
        model = self.db.get_model_by_name(owner_id or user_id, name)
        if model is None and owner_id is None:
            model = next(
                (
                    m
                    for m in self.db.get_models()
                    if m["name"] == name
                    and m["access_right"] == ModelAccessRight.PUBLIC
                ),
                None,
            )
        if model is None:
            raise InvalidRequestError(f"No such model {name}")
        self._check_model_access(model, user_id)
        return model

    def get_model(self, user_id: str, name: str, owner_id: Optional[str] = None) -> Dict:
        return self._model_view(self._resolve_model(user_id, name, owner_id))

    def get_model_file(
        self, user_id: str, name: str, owner_id: Optional[str] = None
    ) -> bytes:
        return self._resolve_model(user_id, name, owner_id)["model_file_bytes"]

    def delete_model(self, user_id: str, name: str) -> None:
        model = self.db.get_model_by_name(user_id, name)
        if model is None:
            raise InvalidRequestError(f"No such model {name}")
        self.db.delete_model(model["id"])

    @staticmethod
    def _check_model_access(model: Dict, user_id: str) -> None:
        if (
            model["user_id"] != user_id
            and model["access_right"] != ModelAccessRight.PUBLIC
        ):
            raise UnauthorizedError("Model is private")

    @staticmethod
    def _model_view(model: Dict) -> Dict:
        # verification rides the row as a JSON blob (db migration r9);
        # rows from before the verifier (or uploaded under =off) carry
        # None — doctor's "static analysis" check lists those
        verification = model.get("verification")
        if isinstance(verification, str):
            try:
                verification = json.loads(verification)
            except ValueError:
                verification = None
        return {
            "id": model["id"],
            "user_id": model["user_id"],
            "name": model["name"],
            "task": model["task"],
            "model_class": model["model_class"],
            "dependencies": model["dependencies"],
            "access_right": model["access_right"],
            "verification": verification,
        }

    # -- train jobs -------------------------------------------------------------

    def create_train_job(
        self,
        user_id: str,
        app: str,
        task: str,
        train_dataset_uri: str,
        test_dataset_uri: str,
        budget: Optional[Dict[str, Any]] = None,
        model_names: Optional[List[str]] = None,
        warm_start_from: Optional[str] = None,
    ) -> Dict:
        """``warm_start_from`` (a prior train job id) seeds each new
        sub-job's advisor with the source job's scored + infeasible
        trials for models the two jobs share — the drift loop's cheap
        warm-started retrain (admin/drift.py). Seeding happens BEFORE
        the train services launch, so the first proposal already
        benefits; the TrainWorker's own create_advisor/replay are
        idempotent no-ops against the seeded session."""
        budget = {} if budget is None else budget
        self._validate_budget(budget)
        # pick the models: named ones, or all visible models for the task
        # (reference admin.py:118-161)
        # public models first, then the caller's own — so a same-named PUBLIC
        # model from another user can never shadow the caller's own model
        all_models = self.db.get_models(task)
        visible = {
            m["name"]: m
            for m in all_models
            if m["access_right"] == ModelAccessRight.PUBLIC
            and m["user_id"] != user_id
        }
        visible.update(
            {m["name"]: m for m in all_models if m["user_id"] == user_id}
        )
        if model_names is not None:
            missing = [n for n in model_names if n not in visible]
            if missing:
                raise InvalidRequestError(
                    f"Models not found (or private): {missing}"
                )
            models = [visible[n] for n in model_names]
        else:
            models = list(visible.values())
        if not models:
            raise InvalidRequestError(f"No usable models for task {task}")
        # generative task plumbing: every chosen template must actually be
        # able to serve the task — rows uploaded before the capability
        # check existed (or under RAFIKI_VERIFY_TEMPLATES=off) are
        # re-checked statically (zero uploaded code executes), so the
        # mismatch is a typed 400 here instead of a trial-time crash
        from rafiki_tpu.constants import TaskType

        if task == TaskType.TEXT_GENERATION:
            incapable = [m["name"] for m in models
                         if not self._model_generation_capable(m)]
            if incapable:
                raise InvalidRequestError(
                    f"task {task} needs generation-capable templates, but "
                    f"{incapable} advertise no fully-wired GenerationSpec "
                    "(init_kv_cache/prefill/decode_step; sdk/model.py)")

        version = self.db.get_next_app_version(user_id, app)
        job = self.db.create_train_job(
            user_id,
            app,
            version,
            task,
            train_dataset_uri,
            test_dataset_uri,
            budget,
        )
        for m in models:
            self.db.create_sub_train_job(job["id"], m["id"])
        if warm_start_from:
            self._seed_advisors_from(job["id"], warm_start_from)
        self.services.create_train_services(job["id"])
        return self.get_train_job(user_id, app, version)

    def _seed_advisors_from(self, train_job_id: str,
                            source_job_id: str) -> None:
        """Warm-start the new job's advisors from a prior job's trial
        history (matched per model id): replay scored feedback AND
        infeasible observations, mirroring recovery's advisor rebuild.
        Best-effort — a failed seed degrades to a cold-started search,
        never a failed job creation."""
        from rafiki_tpu.constants import TrialStatus
        from rafiki_tpu.sdk.model import load_model_class
        from rafiki_tpu.worker.faults import is_infeasible_row

        source_subs = {
            s["model_id"]: s
            for s in self.db.get_sub_train_jobs_of_train_job(source_job_id)}
        for sub in self.db.get_sub_train_jobs_of_train_job(train_job_id):
            src = source_subs.get(sub["model_id"])
            if src is None:
                continue
            try:
                trials = self.db.get_trials_of_sub_train_job(src["id"])
                scored = [
                    (t["knobs"], t["score"]) for t in trials
                    if t["status"] == TrialStatus.COMPLETED
                    and t["score"] is not None]
                infeasible = [
                    (t["knobs"], t["fault_kind"]) for t in trials
                    if is_infeasible_row(t)]
                if not (scored or infeasible):
                    continue
                model = self.db.get_model(sub["model_id"])
                clazz = load_model_class(model["model_file_bytes"],
                                         model["model_class"])
                self.advisor_store.create_advisor(
                    clazz.get_knob_config(), advisor_id=sub["id"])
                if self.advisor_store.replay_feedback(
                        sub["id"], scored, infeasible=infeasible):
                    logger.info(
                        "advisor %s warm-started with %d scored + %d "
                        "infeasible trials from job %s", sub["id"][:8],
                        len(scored), len(infeasible), source_job_id[:8])
            # lint: absorb(warm start is best-effort: a failed seed cold-starts the search instead of failing job creation)
            except Exception:
                logger.exception("advisor warm start failed for sub %s",
                                 sub["id"][:8])

    @staticmethod
    def _validate_budget(budget: Dict[str, Any]) -> None:
        """Reject malformed budgets at job creation — a bad value silently
        degrading the job later (e.g. ASHA_ETA=1 disabling early stopping
        with a warning per epoch) is strictly worse than a 400 here."""
        from rafiki_tpu.constants import BudgetType

        if not isinstance(budget, dict):
            raise InvalidRequestError(
                f"budget must be a JSON object, got {type(budget).__name__}")

        def as_int(key, minimum):
            raw = budget.get(key)
            if raw is None:
                return
            try:
                v = int(raw)
            except (TypeError, ValueError):
                raise InvalidRequestError(f"budget {key}={raw!r} is not an "
                                          "integer")
            if v < minimum:
                raise InvalidRequestError(
                    f"budget {key}={v} must be >= {minimum}")

        def as_float(key, minimum, exclusive=False):
            raw = budget.get(key)
            if raw is None:
                return
            try:
                v = float(raw)
            except (TypeError, ValueError):
                raise InvalidRequestError(
                    f"budget {key}={raw!r} is not a number")
            import math

            # NaN would pass every comparison and silently disable the
            # limit the value exists to enforce
            if not math.isfinite(v):
                raise InvalidRequestError(f"budget {key}={v} is not finite")
            if v < minimum or (exclusive and v == minimum):
                op = ">" if exclusive else ">="
                raise InvalidRequestError(
                    f"budget {key}={v} must be {op} {minimum}")

        as_int(BudgetType.MODEL_TRIAL_COUNT, 1)
        as_int(BudgetType.CHIP_COUNT, 0)
        as_int(BudgetType.GPU_COUNT, 0)
        as_int(BudgetType.CHIPS_PER_TRIAL, 1)
        as_int(BudgetType.ASHA_MIN_EPOCHS, 1)
        as_int(BudgetType.ASHA_ETA, 2)
        # TIME_HOURS=0 is legal: the deadline is already spent, so the job
        # stops before running any trial (tested behavior)
        as_float(BudgetType.TIME_HOURS, 0)
        as_float(BudgetType.TRIAL_TIMEOUT_S, 0, exclusive=True)
        as_int(BudgetType.CHIPS_PER_WORKER, 1)
        as_int(BudgetType.ENSEMBLE_FUSED, 0)

    def get_train_job(
        self, user_id: str, app: str, app_version: int = -1
    ) -> Dict:
        job = self.db.get_train_job_by_app_version(user_id, app, app_version)
        if job is None:
            raise InvalidRequestError(f"No such train job {app} v{app_version}")
        workers = self.db.get_workers_of_train_job(job["id"])
        services = [self.db.get_service(w["service_id"]) for w in workers]
        return {
            "id": job["id"],
            "app": job["app"],
            "app_version": job["app_version"],
            "task": job["task"],
            "status": job["status"],
            # trial fault classification: why an ERRORED job errored (e.g.
            # fail-fast on a broken template) — None for healthy jobs
            "fault_kind": job.get("fault_kind"),
            "error_reason": job.get("error_reason"),
            "budget": job["budget"],
            "train_dataset_uri": job["train_dataset_uri"],
            "test_dataset_uri": job["test_dataset_uri"],
            "datetime_started": job["datetime_started"],
            "datetime_stopped": job["datetime_stopped"],
            "workers": [
                {
                    "service_id": s["id"],
                    "status": s["status"],
                    "chips": s["chips"],
                }
                for s in services
                if s
            ],
        }

    def get_train_jobs_of_user(self, user_id: str) -> List[Dict]:
        """Light listing for dashboards: one row per train job, no worker
        fan-out (the web UI's landing view)."""
        return [
            {
                "id": j["id"],
                "app": j["app"],
                "app_version": j["app_version"],
                "task": j["task"],
                "status": j["status"],
                "budget": j["budget"],
                "datetime_started": j["datetime_started"],
                "datetime_stopped": j["datetime_stopped"],
            }
            for j in self.db.get_train_jobs_of_user(user_id)
        ]

    def get_train_jobs_of_app(self, user_id: str, app: str) -> List[Dict]:
        return [
            self.get_train_job(user_id, app, j["app_version"])
            for j in self.db.get_train_jobs_of_app(user_id, app)
        ]

    def stop_train_job(self, user_id: str, app: str, app_version: int = -1) -> Dict:
        job = self.db.get_train_job_by_app_version(user_id, app, app_version)
        if job is None:
            raise InvalidRequestError(f"No such train job {app} v{app_version}")
        self.services.stop_train_services(job["id"])
        self.db.mark_train_job_as_stopped(job["id"])
        return self.get_train_job(user_id, app, job["app_version"])

    def wait_until_train_job_stopped(
        self, user_id: str, app: str, app_version: int = -1, timeout_s: float = 600
    ) -> Dict:
        """Convenience for tests/CLI: poll until the job leaves RUNNING."""
        import time as _time

        deadline = _time.time() + timeout_s
        while True:
            job = self.get_train_job(user_id, app, app_version)
            if job["status"] in (TrainJobStatus.STOPPED, TrainJobStatus.ERRORED):
                return job
            if _time.time() > deadline:
                raise TimeoutError(f"Train job still {job['status']}")
            _time.sleep(0.1)

    # -- trials -----------------------------------------------------------------

    def get_trials_of_train_job(
        self, user_id: str, app: str, app_version: int = -1
    ) -> List[Dict]:
        job = self.db.get_train_job_by_app_version(user_id, app, app_version)
        if job is None:
            raise InvalidRequestError(f"No such train job {app} v{app_version}")
        return [self._trial_view(t) for t in self.db.get_trials_of_train_job(job["id"])]

    def get_best_trials_of_train_job(
        self, user_id: str, app: str, app_version: int = -1, max_count: int = 2
    ) -> List[Dict]:
        job = self.db.get_train_job_by_app_version(user_id, app, app_version)
        if job is None:
            raise InvalidRequestError(f"No such train job {app} v{app_version}")
        return [
            self._trial_view(t)
            for t in self.db.get_best_trials_of_train_job(job["id"], max_count)
        ]

    def get_trial(self, trial_id: str) -> Dict:
        trial = self.db.get_trial(trial_id)
        if trial is None:
            raise InvalidRequestError(f"No such trial {trial_id}")
        return self._trial_view(trial)

    def get_trial_logs(self, trial_id: str) -> Dict:
        if self.db.get_trial(trial_id) is None:
            raise InvalidRequestError(f"No such trial {trial_id}")
        return parse_logs(self.db.get_trial_logs(trial_id))

    def get_trial_trace(self, trial_id: str) -> List[Dict]:
        """Per-phase span breakdown recorded by the train worker (the
        tracing subsystem the reference lacks, SURVEY.md §5.1)."""
        if self.db.get_trial(trial_id) is None:
            raise InvalidRequestError(f"No such trial {trial_id}")
        from rafiki_tpu.utils.trace import load_trace

        return load_trace(trial_id)

    def get_trial_params(self, trial_id: str) -> bytes:
        trial = self.db.get_trial(trial_id)
        if trial is None or not trial.get("params_file_path"):
            raise InvalidRequestError(f"No params for trial {trial_id}")
        from rafiki_tpu.sdk.artifact import read_artifact

        # verified read: a damaged params file surfaces as the typed
        # ArtifactCorruptError (a clean error at the door) — the raw
        # payload handed to clients stays plain msgpack either way
        return read_artifact(trial["params_file_path"])

    @staticmethod
    def _trial_view(trial: Dict) -> Dict:
        return {
            "id": trial["id"],
            "sub_train_job_id": trial["sub_train_job_id"],
            "model_id": trial["model_id"],
            "knobs": trial["knobs"],
            "score": trial["score"],
            "status": trial["status"],
            # fault classification (worker/faults.py): how many infra-class
            # re-runs the trial absorbed, plus the typed kind +
            # truncated traceback of its LAST fault (terminal for
            # ERRORED trials; the absorbed transient for COMPLETED ones
            # with attempt > 0) — diagnosing a failure never requires
            # scraping worker logs
            "attempt": trial.get("attempt", 0),
            "fault_kind": trial.get("fault_kind"),
            "fault_detail": trial.get("fault_detail"),
            "datetime_started": trial["datetime_started"],
            "datetime_stopped": trial["datetime_stopped"],
        }

    # -- inference jobs ----------------------------------------------------------

    def create_inference_job(
        self, user_id: str, app: str, app_version: int = -1,
        budget: Optional[Dict[str, Any]] = None,
    ) -> Dict:
        """``budget`` (serving-side, optional): ``CHIPS_PER_WORKER`` >= 1
        grants every inference worker a multi-chip mesh, so one model
        serves its pjit'd predict sharded across chips (the serving
        analogue of CHIPS_PER_TRIAL; the reference was hard-wired to one
        GPU per serving worker, reference services_manager.py:390-395).
        ``ENSEMBLE_FUSED`` truthy co-locates ALL best trials in each
        worker: one vmapped device dispatch serves the whole ensemble when
        the trials share a compiled predict (admin/services.py)."""
        # malformed input 400s regardless of job state (route-boundary
        # validation, same policy as create_train_job)
        self._validate_budget(budget or {})
        job = self.db.get_train_job_by_app_version(user_id, app, app_version)
        if job is None:
            raise InvalidRequestError(f"No such train job {app} v{app_version}")
        if job["status"] != TrainJobStatus.STOPPED:
            # train must have fully stopped first (reference admin.py:360-361)
            raise InvalidRequestError(
                f"Train job must be STOPPED, is {job['status']}"
            )
        if self.db.get_running_inference_job_of_train_job(job["id"]) is not None:
            # one running inference job per train job (reference :363-366)
            raise InvalidRequestError(
                "An inference job is already running for this train job"
            )
        inf = self.db.create_inference_job(user_id, job["id"], budget=budget)
        self.services.create_inference_services(inf["id"])
        return self.get_inference_job(user_id, app, job["app_version"])

    def get_inference_job_stats(
        self, user_id: str, app: str, app_version: int = -1
    ) -> Dict:
        """Serving observability: per-worker batch/query counters and the
        derived batch occupancy (mean queries/batch — the signal that
        continuous batching coalesces under load). In-process workers are
        read from worker/inference.py SERVING_STATS directly; process-mode
        workers relay theirs over the event channel (every ~5 s while
        counters change, so freshly-started remote workers may briefly
        read 0). Counters reset with the worker."""
        from rafiki_tpu.worker.inference import serving_stats

        inf = self.get_inference_job(user_id, app, app_version)
        local = serving_stats()
        workers = []
        total_b = total_q = 0
        for w in inf["workers"]:
            # in-process workers land in the local module counters;
            # process-mode workers report over the event channel
            with self._predict_route_lock:
                remote = self._remote_serving_stats.get(w["service_id"])
            s = local.get(w["service_id"]) or remote or {
                "batches": 0, "queries": 0}
            total_b += s["batches"]
            total_q += s["queries"]
            workers.append({**w, **s})
        return {
            "inference_job_id": inf["id"],
            "status": inf["status"],
            "workers": workers,
            "batches": total_b,
            "queries": total_q,
            "batch_occupancy": round(total_q / total_b, 2) if total_b else None,
        }

    def get_inference_job(
        self, user_id: str, app: str, app_version: int = -1
    ) -> Dict:
        job = self.db.get_train_job_by_app_version(user_id, app, app_version)
        if job is None:
            raise InvalidRequestError(f"No such train job {app} v{app_version}")
        infs = self.db.get_inference_jobs_of_train_job(job["id"])
        if not infs:
            raise InvalidRequestError("No inference job for this train job")
        inf = infs[0]
        workers = self.db.get_workers_of_inference_job(inf["id"])
        # dedicated serving endpoint, when config.PREDICTOR_PORTS bound one
        # (reference parity: the job info carried the predictor's published
        # host port, reference admin/services_manager.py:379-384)
        predictor_host = predictor_port = None
        if inf.get("predictor_service_id"):
            psvc = self.db.get_service(inf["predictor_service_id"])
            if psvc:
                predictor_host = psvc.get("host")
                predictor_port = psvc.get("port")
        def _chips(service_id: str) -> list:
            svc = self.db.get_service(service_id)
            return (svc or {}).get("chips") or []

        return {
            "id": inf["id"],
            "train_job_id": job["id"],
            "app": app,
            "app_version": job["app_version"],
            "predictor_host": predictor_host,
            "predictor_port": predictor_port,
            "status": inf["status"],
            "budget": inf.get("budget") or {},
            "datetime_started": inf["datetime_started"],
            "datetime_stopped": inf["datetime_stopped"],
            "workers": [
                {"service_id": w["service_id"], "trial_id": w["trial_id"],
                 "chips": _chips(w["service_id"])}
                for w in workers
            ],
        }

    def scale_inference_job(
        self, user_id: str, app: str, app_version: int = -1,
        delta: int = 1,
    ) -> Dict:
        """Operator-facing elastic scaling: add (``delta`` > 0) or
        gracefully drain (``delta`` < 0) serving replicas of the app's
        RUNNING inference job without a redeploy — the same primitive the
        autoscaler drives (admin/services.py scale_inference_job)."""
        if not delta:
            raise InvalidRequestError("delta must be a non-zero integer")
        # sanity bound: each added replica is a synchronous placement +
        # deploy wait on this HTTP worker — an unbounded delta would tie
        # the door up for hours mass-creating services
        limit = max(int(config.AUTOSCALE_MAX_REPLICAS), 8)
        if abs(int(delta)) > limit:
            raise InvalidRequestError(
                f"delta {delta} out of range (|delta| <= {limit}; raise "
                "RAFIKI_AUTOSCALE_MAX_REPLICAS to scale further)")
        job = self.db.get_train_job_by_app_version(user_id, app, app_version)
        if job is None:
            raise InvalidRequestError(f"No such train job {app} v{app_version}")
        inf = self.db.get_running_inference_job_of_train_job(job["id"])
        if inf is None:
            raise InvalidRequestError("No running inference job")
        from rafiki_tpu.admin.services import ServiceDeploymentError

        try:
            report = self.services.scale_inference_job(inf["id"], int(delta))
        except ServiceDeploymentError as e:
            raise InvalidRequestError(str(e))
        return {
            "inference_job_id": inf["id"],
            **report,
            "replicas": len(self.services.live_inference_workers(inf["id"])),
        }

    def stop_inference_job(
        self, user_id: str, app: str, app_version: int = -1
    ) -> Dict:
        job = self.db.get_train_job_by_app_version(user_id, app, app_version)
        if job is None:
            raise InvalidRequestError(f"No such train job {app} v{app_version}")
        inf = self.db.get_running_inference_job_of_train_job(job["id"])
        if inf is None:
            raise InvalidRequestError("No running inference job")
        # a rollout mid-flight must end (ABORTED, no rollback pass — the
        # stop below tears the whole fleet down) before the teardown, or
        # its thread would race the stop placing replicas
        self.rollouts.abort_for_job(inf["id"], "inference job stopped")
        self.services.stop_inference_services(inf["id"])
        self._drop_predict_routes(inf["id"])
        return self.get_inference_job(user_id, app, job["app_version"])

    # -- safe live rollouts (admin/rollout.py; docs/failure-model.md
    # "Rollout faults") ------------------------------------------------------

    def _running_inference_job(self, user_id: str, app: str,
                               app_version: int) -> Dict:
        # version -1 means "the serving version", NOT "the newest train
        # job": a drift auto-retrain (admin/drift.py) bumps the app's
        # version catalog without deploying, so the newest version may
        # have no inference job while an older one is still serving
        if app_version == -1:
            for job in self.db.get_train_jobs_of_app(user_id, app):
                inf = self.db.get_running_inference_job_of_train_job(
                    job["id"])
                if inf is not None:
                    return inf
            raise InvalidRequestError("No running inference job")
        job = self.db.get_train_job_by_app_version(user_id, app, app_version)
        if job is None:
            raise InvalidRequestError(f"No such train job {app} v{app_version}")
        inf = self.db.get_running_inference_job_of_train_job(job["id"])
        if inf is None:
            raise InvalidRequestError("No running inference job")
        return inf

    def update_inference_job(
        self, user_id: str, app: str, app_version: int = -1,
        trial_id: Optional[str] = None,
        canary_fraction: Optional[float] = None,
        batch: Optional[int] = None,
    ) -> Dict:
        """Update the app's RUNNING inference job to serve ``trial_id``
        in place — canary, SLO-judged, rolling replace, automatic
        rollback — without a redeploy outage. Answers immediately with
        the rollout row (phase CANARY); poll the status route (or
        ``Client.wait_until_rollout_done``) for the verdict. A second
        update while one is in flight raises the typed
        RolloutInFlightError (→ 409)."""
        if not trial_id:
            raise InvalidRequestError("missing rollout target trial_id")
        inf = self._running_inference_job(user_id, app, app_version)
        return self.rollouts.start(
            inf["id"], trial_id, canary_fraction=canary_fraction,
            batch=batch)

    def get_rollout_status(
        self, user_id: str, app: str, app_version: int = -1
    ) -> Dict:
        """The newest rollout of the app's current inference job (live
        phases carry the judge's per-lane signal snapshot)."""
        job = self.db.get_train_job_by_app_version(user_id, app, app_version)
        if job is None:
            raise InvalidRequestError(f"No such train job {app} v{app_version}")
        infs = self.db.get_inference_jobs_of_train_job(job["id"])
        for inf in infs:
            status = self.rollouts.status(inf["id"])
            if status is not None:
                return status
        raise InvalidRequestError(
            f"no rollout recorded for {app} v{job['app_version']}")

    def abort_rollout(
        self, user_id: str, app: str, app_version: int = -1
    ) -> Dict:
        inf = self._running_inference_job(user_id, app, app_version)
        return self.rollouts.abort(inf["id"])

    def ack_rollout(
        self, user_id: str, app: str, app_version: int = -1
    ) -> Dict:
        """Acknowledge the newest rolled-back rollout (clears the
        doctor WARN)."""
        job = self.db.get_train_job_by_app_version(user_id, app, app_version)
        if job is None:
            raise InvalidRequestError(f"No such train job {app} v{app_version}")
        infs = self.db.get_inference_jobs_of_train_job(job["id"])
        for inf in infs:
            try:
                return self.rollouts.ack(inf["id"])
            except InvalidRequestError:
                continue
        raise InvalidRequestError(
            f"no unacknowledged rollback for {app}")

    def get_drift_status(
        self, user_id: str, app: str, app_version: int = -1
    ) -> Dict:
        """The drift closed loop's state for the app's current inference
        job (admin/drift.py): phase, frozen-baseline flag, live signal
        snapshot, event tail."""
        if app_version == -1:
            # the drift row lives on the SERVING version's inference job;
            # a drift retrain's own (newer) train job never has one
            jobs = self.db.get_train_jobs_of_app(user_id, app)
            if not jobs:
                raise InvalidRequestError(f"No such app {app}")
        else:
            job = self.db.get_train_job_by_app_version(
                user_id, app, app_version)
            if job is None:
                raise InvalidRequestError(
                    f"No such train job {app} v{app_version}")
            jobs = [job]
        for job in jobs:
            for inf in self.db.get_inference_jobs_of_train_job(job["id"]):
                status = self.drift.status(inf["id"])
                if status is not None:
                    return status
        raise InvalidRequestError(
            f"no drift state recorded for {app}"
            + (f" v{app_version}" if app_version != -1 else ""))

    def ack_drift(
        self, user_id: str, app: str, app_version: int = -1
    ) -> Dict:
        """Acknowledge the app's drift loop: re-arms a PARKED loop or
        clears a rollback-flap streak (clears the doctor WARNs)."""
        inf = self._running_inference_job(user_id, app, app_version)
        return self.drift.ack(inf["id"])

    def _drop_predict_routes(self, inference_job_id: str) -> None:
        """Invalidate cached predict routes for a stopped inference job —
        within the TTL its workers may still be draining, so predict must
        go back to the control plane and correctly report the stop. Bumps
        the route epoch so an in-flight predict() that resolved before this
        stop cannot re-insert the dead route. Also prunes the job's relayed
        serving counters — a long-lived admin cycling many jobs must not
        accumulate entries for dead services forever."""
        with self._predict_route_lock:
            self._predict_route_epoch += 1
            for key, (_, predictor) in list(self._predict_route_cache.items()):
                if predictor._job_id == inference_job_id:
                    self._predict_route_cache.pop(key, None)
        workers = self.db.get_workers_of_inference_job(inference_job_id)
        with self._predict_route_lock:
            for w in workers:
                self._remote_serving_stats.pop(w["service_id"], None)

    def predict(
        self, user_id: str, app: str, queries: List[Any], app_version: int = -1
    ) -> List[Any]:
        """Serving entrypoint: route queries to the app's running predictor.

        The app->predictor resolution (two control-plane DB reads) is
        cached for a short TTL: the serving hot path must not convoy on the
        serialized metadata connection at high request rates, and a few
        seconds of staleness only delays visibility of a *newly swapped*
        inference job — a dead predictor raises and re-resolves
        immediately.

        Overload faults surface as typed exceptions the HTTP shell maps
        to shed codes (admin/http.py): QueueFullError /
        DeadlineUnmeetableError -> 429 + Retry-After,
        ServerOverloadedError -> 503."""
        from rafiki_tpu.cache.queue import QueueFullError
        from rafiki_tpu.predictor.admission import (
            DeadlineUnmeetableError,
            ServerOverloadedError,
        )

        key = (user_id, app, app_version)
        now = time.monotonic()
        with self._predict_route_lock:
            cached = self._predict_route_cache.get(key)
        if cached is not None and now - cached[0] < config.PREDICT_ROUTE_TTL_S:
            try:
                return self._admitted_predict(cached[1], queries, tenant=app)
            except (QueueFullError, ServerOverloadedError,
                    DeadlineUnmeetableError):
                # overload shed, not a dead route: re-resolving would only
                # add two control-plane reads to an already-loaded path
                raise
            except TimeoutError:
                # SLO missed. Drop the route (it MAY be stale) but do NOT
                # resubmit: under overload a timeout is the common outcome,
                # and a silent second full-length attempt doubles queue
                # pressure and pins the handler for 2x PREDICT_TIMEOUT_S —
                # retry policy belongs to the client, which just got a 504.
                with self._predict_route_lock:
                    self._predict_route_cache.pop(key, None)
                raise
            except RuntimeError:
                # workers gone (job stopped/replaced): fall through and
                # re-resolve against the control plane
                with self._predict_route_lock:
                    self._predict_route_cache.pop(key, None)
        with self._predict_route_lock:
            epoch = self._predict_route_epoch
        if app_version == -1:
            # serving resolution, not catalog resolution: skip versions
            # with no running inference job (e.g. a drift auto-retrain's
            # own train job, which bumps the version but never deploys)
            jobs = self.db.get_train_jobs_of_app(user_id, app)
            if not jobs:
                raise InvalidRequestError(f"No such app {app}")
            inf = next(
                (i for i in (
                    self.db.get_running_inference_job_of_train_job(j["id"])
                    for j in jobs) if i is not None), None)
        else:
            job = self.db.get_train_job_by_app_version(
                user_id, app, app_version)
            if job is None:
                raise InvalidRequestError(f"No such app {app}")
            inf = self.db.get_running_inference_job_of_train_job(job["id"])
        if inf is None:
            raise InvalidRequestError("No running inference job for this app")
        predictor = self.services.get_predictor(inf["id"])
        if predictor is None:
            raise InvalidRequestError("Predictor not available")
        with self._predict_route_lock:
            # only cache if no invalidation ran while we resolved — a
            # concurrent stop_inference_job must not have its route
            # resurrected by this thread's stale resolution
            if self._predict_route_epoch == epoch:
                self._predict_route_cache[key] = (now, predictor)
        return self._admitted_predict(predictor, queries, tenant=app)

    def _admitted_predict(self, predictor, queries: List[Any],
                          tenant: Optional[str] = None) -> List[Any]:
        """The admin door's admission wrapper: bounded in-flight +
        estimated-wait shed before the predictor sees the request, and
        latency feedback after (predictor/admission.py)."""
        cap = int(config.PREDICT_QUEUE_DEPTH)
        if cap > 0 and len(queries) > cap:
            # can never fit in any worker queue: permanent client error,
            # not the retryable 429
            raise InvalidRequestError(
                f"request carries {len(queries)} queries but the "
                f"per-worker queue cap is {cap} "
                "(RAFIKI_PREDICT_QUEUE_DEPTH) — split the request")
        backlog_fn = getattr(predictor, "backlog_depth", None)
        # tenant = the app: the admin door is SHARED across jobs, so this
        # is where one hot job saturating its weighted fair share gets
        # 429s while cold jobs keep their latency (RAFIKI_AUTOSCALE_FAIR).
        # With the prediction cache on, cost is the MISSES-ONLY estimate
        # (predictor/result_cache.py) — cache hits shed no load, so the
        # fairness book charges only what will reach a worker.
        cost_fn = getattr(predictor, "admission_cost", None)
        cost = cost_fn(queries) if callable(cost_fn) else len(queries)
        self._predict_admission.admit(
            config.PREDICT_TIMEOUT_S,
            backlog_depth=backlog_fn() if callable(backlog_fn) else None,
            tenant=tenant, cost=cost)
        t0 = time.monotonic()
        try:
            preds = predictor.predict_batch(queries)
        finally:
            self._predict_admission.release(tenant=tenant)
        self._predict_admission.observe(time.monotonic() - t0, len(queries))
        return preds

    def get_fleet_health(self) -> Dict[str, Any]:
        """Operator view of the fleet health subsystem: per-agent
        heartbeat state, circuit breaker state, and load
        (placement/hosts.py agent_health). Single-host placements report
        an empty agent map — the admin process itself answering IS the
        health signal there.

        The ``serving`` section is the overload picture (docs/
        failure-model.md "Overload faults"): per-job queue depths and
        hedge-suppression counters from each live Predictor — a job with
        zero registered worker queues reads ``degraded``, the admin-side
        twin of the per-job /healthz verdict — plus this door's admission
        stats and the local workers' queue counters (SERVING_STATS)."""
        from rafiki_tpu.utils import chaos as _chaos
        from rafiki_tpu.worker.inference import serving_stats

        agents = {}
        if hasattr(self.placement, "agent_health"):
            agents = self.placement.agent_health()
        down = [a for a, h in agents.items() if h["state"] == "DOWN"]
        jobs: Dict[str, Any] = {}
        predictors = self.services.predictors()
        for job_id, predictor in predictors.items():
            try:
                depths = predictor.queue_depths()
                jobs[job_id] = {
                    "status": "ok" if depths else "degraded",
                    "workers": len(depths),
                    "queue_depths": depths,
                    "overload": predictor.overload_stats(),
                }
            except Exception:
                logger.exception("fleet-health probe of job %s failed",
                                 job_id)
        # local workers update SERVING_STATS in-process; process/hosts
        # placement workers relay the same counters over the event channel
        # (handle_event inference_worker_stats) — merge both so the
        # overload picture covers every deployment mode
        workers = serving_stats()
        with self._predict_route_lock:
            for sid, s in self._remote_serving_stats.items():
                workers.setdefault(sid, {}).update(s)
        # per-replica warm state (worker/warmup.py): cold/warm verdict +
        # last-boot compile seconds. Local workers' reports are read
        # directly; process/hosts workers relay the same fields on their
        # stats rows (merged above).
        from rafiki_tpu.worker.warmup import stats_row_fields, warmup_stats

        for sid in list(warmup_stats()):
            workers.setdefault(sid, {}).update(stats_row_fields(sid))
        # generative serving picture, aggregated per job (the workers'
        # rows carry their job id): the paged-KV pool footprint and the
        # per-tenant prefix-cache hit rates the shared-prefix lever is
        # judged by (docs/serving-generation.md)
        generation: Dict[str, Any] = {}
        for s in workers.values():
            job = s.get("gen_job")
            if not job:
                continue
            g = generation.setdefault(job, {
                "workers": 0, "slots_busy": 0, "tokens": 0,
                "kv_blocks_used": 0, "kv_blocks_live": 0,
                "kv_pool_blocks": 0,
                "prefix_hits": 0, "prefix_misses": 0,
                "prefix_hit_tokens": 0,
                "spec_workers": 0, "spec_proposed": 0,
                "spec_accepted": 0, "spec_rounds": 0,
                "spec_degraded": [], "resident_streams": 0,
            })
            g["workers"] += 1
            g["slots_busy"] += int(s.get("gen_slots_busy", 0))
            g["resident_streams"] += int(s.get("gen_resident_streams", 0))
            g["tokens"] += int(s.get("gen_tokens", 0))
            g["kv_blocks_used"] += int(s.get("gen_kv_blocks_used", 0))
            g["kv_blocks_live"] += int(s.get("gen_kv_blocks_live", 0))
            g["kv_pool_blocks"] += int(s.get("gen_kv_pool_blocks", 0))
            g["prefix_hits"] += int(s.get("gen_prefix_hits", 0))
            g["prefix_misses"] += int(s.get("gen_prefix_misses", 0))
            g["prefix_hit_tokens"] += int(
                s.get("gen_prefix_hit_tokens", 0))
            # speculative decoding picture (worker/generation.py): the
            # acceptance rate is the lever's health signal — a low rate
            # means the draft earns its k forward passes back rarely
            g["spec_workers"] += 1 if s.get("gen_spec_on") else 0
            g["spec_proposed"] += int(s.get("gen_spec_proposed", 0))
            g["spec_accepted"] += int(s.get("gen_spec_accepted", 0))
            g["spec_rounds"] += int(s.get("gen_spec_rounds", 0))
            if s.get("gen_spec_degraded"):
                g["spec_degraded"].append(str(s["gen_spec_degraded"]))
        for g in generation.values():
            admitted = g["prefix_hits"] + g["prefix_misses"]
            g["prefix_hit_rate"] = (
                round(g["prefix_hits"] / admitted, 3) if admitted
                else None)
            g["spec_acceptance_rate"] = (
                round(g["spec_accepted"] / g["spec_proposed"], 3)
                if g["spec_proposed"] else None)
        # stream-continuity rollup (docs/failure-model.md "Stream
        # continuity"): the door-side journal/resume picture per gen job
        # — resumes by trigger, client-visible continuity losses, and
        # the journal's occupancy — merged from each job's Predictor
        for job_id, g in generation.items():
            predictor = predictors.get(job_id)
            cont_fn = getattr(predictor, "gen_continuity_stats", None)
            if callable(cont_fn):
                try:
                    g["continuity"] = cont_fn()
                except Exception:
                    logger.exception(
                        "continuity probe of job %s failed", job_id)
        # training-plane fault picture (docs/failure-model.md,
        # "Training-plane faults"): per-job fault-kind counters and
        # absorbed retries from the STORE (covers every placement mode),
        # plus in-process worker counters (quarantined signatures,
        # re-proposals, feedback drops) from worker/faults.py
        from rafiki_tpu.constants import TrainJobStatus as _TJS
        from rafiki_tpu.worker.faults import training_stats as _tstats

        train_jobs: Dict[str, Any] = {}
        try:
            summary = self.db.get_trial_fault_summary_of_live_jobs()
            for j in self.db.get_train_jobs_by_statuses(
                    [_TJS.STARTED, _TJS.RUNNING]):
                entry = summary.get(j["id"], {"faults": {}, "retries": 0})
                train_jobs[j["id"]] = {"status": j["status"], **entry}
        except Exception:
            logger.exception("fleet-health training scan failed")
        return {
            "placement": type(self.placement).__name__,
            "agents": agents,
            "agents_down": down,
            "chaos_active": _chaos.enabled(),
            # boot-reconciliation outcome (admin/recovery.py): state is
            # `recovering` while the off-thread pass runs — the HTTP
            # doors 503 until it reads `ready`
            "recovery": self.recovery_status(),
            # control-plane HA (admin/lease.py): leadership role, epoch,
            # lease validity — `enabled: False` when running solo
            "ha": ({"enabled": True, **self._lease.status()}
                   if self._lease is not None else {"enabled": False}),
            # closed-loop overload adaptation (admin/autoscaler.py):
            # loop state, chip-loan picture, recent scale decisions with
            # their reason + signal snapshot
            "autoscaler": self.autoscaler.report(),
            # warm standby pool (admin/warm_pool.py): per-job standby
            # counts, degraded pools, loan split, recent pool events
            "warm_pool": self.warm_pool.report(),
            # safe live rollouts (admin/rollout.py): in-flight rollouts
            # with the judge's live per-lane signals, plus recent events
            # (rollback reasons + the signal snapshots they fired on)
            "rollouts": self.rollouts.report(),
            # drift closed loop (admin/drift.py): per-job phase +
            # divergence signal snapshot, plus the recent event tail
            # (drift verdicts, retrain launches, rollout outcomes)
            "drift": self.drift.report(),
            "serving": {
                "jobs": jobs,
                "admission": self._predict_admission.stats(),
                # per-tenant decayed admitted-query charges at this door
                # (weighted fair admission, RAFIKI_AUTOSCALE_FAIR)
                "fair_shares": self._predict_admission.fair_shares(),
                "workers": workers,
                # per-job generative picture: paged-KV pool footprint +
                # prefix-cache hit rates (worker/kv_paging.py)
                "generation": generation,
                # prediction result cache + single-flight picture
                # (predictor/result_cache.py): bounds, occupancy, and
                # per-tenant hit rates
                "prediction_cache": self._prediction_cache_stats(),
            },
            "training": {
                "jobs": train_jobs,
                "workers": _tstats(),
            },
        }

    @staticmethod
    def _prediction_cache_stats() -> Dict[str, Any]:
        from rafiki_tpu.predictor.result_cache import get_cache

        try:
            return get_cache().stats()
        # lint: absorb(fleet health must answer even when the cache probe faults)
        except Exception:
            logger.exception("prediction-cache stats probe failed")
            return {}

    def stop_all_jobs(self) -> None:
        """Stop every running train/inference job (reference client
        stop_all_jobs, rafiki/client/client.py:647), marking the job rows —
        not just their services — so job state stays consistent."""
        for inf in self.db.get_inference_jobs_by_statuses(
            [InferenceJobStatus.STARTED, InferenceJobStatus.RUNNING]
        ):
            self.rollouts.abort_for_job(inf["id"], "stop_all_jobs")
            self.services.stop_inference_services(inf["id"])
            self._drop_predict_routes(inf["id"])
        for job in self.db.get_train_jobs_by_statuses(
            [TrainJobStatus.STARTED, TrainJobStatus.RUNNING]
        ):
            self.services.stop_train_services(job["id"])
            self.db.mark_train_job_as_stopped(job["id"])
        # sweep any stragglers (e.g. services of already-errored jobs) —
        # the status filter runs in SQL against idx_service_status, not
        # as a full-table python sweep
        for svc in self.db.get_services(
                statuses=["STARTED", "DEPLOYING", "RUNNING"]):
            self.services._destroy_service(svc["id"], wait=False)

    # -- events ------------------------------------------------------------------

    def handle_event(self, name: str, payload: Dict[str, Any]) -> None:
        """Worker events drive job status (reference admin.py:595-616)."""
        try:
            if name == EVENT_BUDGET_REACHED:
                # Graceful drain: each worker exits on its own once the shared
                # budget is consumed (the reference instead destroyed the
                # sub-job's containers, terminating peers mid-trial and
                # discarding their work, reference admin.py:607). Nothing to
                # kill — just fold the exit into job status.
                self.services.refresh_train_job_status(payload["train_job_id"])
            elif name == EVENT_TRIAL_FAULT_LIMIT:
                # Job fail-fast (trial fault classification): a worker hit
                # RAFIKI_TRIAL_FAULT_LIMIT consecutive user-class trial
                # faults — the template is broken, so the job errors NOW
                # with the typed reason instead of grinding its budget.
                # The worker already marked the row (works headless);
                # the guarded transition makes this a no-op then. Tear
                # down sibling workers — they are failing the same way.
                self.db.mark_train_job_as_errored(
                    payload["train_job_id"],
                    payload.get("fault_kind"),
                    payload.get("reason"))
                self.services.stop_train_services(payload["train_job_id"])
            elif name in ("train_job_worker_started", "train_job_worker_stopped"):
                self.services.refresh_train_job_status(payload["train_job_id"])
            elif name == "service_status":
                # forwarded by per-host placement agents (placement/agent.py)
                # so job-level refresh fires even for remotely-placed workers
                self._on_service_status(payload["service_id"], payload["status"])
            elif name == "inference_worker_stats":
                # serving counters from OUT-OF-PROCESS inference workers
                # (process placement) — in-process workers update the local
                # SERVING_STATS module dict directly
                sid = payload["service_id"]
                # compound insert+move+evict must be atomic vs the API
                # threads reading/pruning this dict (GIL atomicity only
                # covers single C-level dict ops)
                with self._predict_route_lock:
                    self._remote_serving_stats[sid] = {
                        "batches": int(payload.get("batches", 0)),
                        "queries": int(payload.get("queries", 0)),
                        # overload counters ride the same event when the
                        # worker's queue exposes them (queue_depth gauge,
                        # expired/shed totals); paged-KV generation
                        # workers add the block-pool + prefix-cache
                        # picture fleet health aggregates per job
                        **{k: int(payload[k])
                           for k in ("queue_depth", "expired", "shed",
                                     "gen_slots_busy", "gen_slots_max",
                                     "gen_kv_blocks_used",
                                     "gen_kv_blocks_live",
                                     "gen_kv_pool_blocks",
                                     "gen_kv_block_tokens",
                                     "gen_prefix_hits",
                                     "gen_prefix_misses",
                                     "gen_prefix_hit_tokens",
                                     "gen_spec_proposed",
                                     "gen_spec_accepted",
                                     "gen_spec_rounds")
                           if k in payload},
                        **{k: payload[k]
                           for k in ("gen_spec_on",)
                           if k in payload},
                        **({"gen_job": str(payload["gen_job"])}
                           if "gen_job" in payload else {}),
                        **({"gen_spec_degraded":
                            str(payload["gen_spec_degraded"])}
                           if "gen_spec_degraded" in payload else {}),
                    }
                    self._remote_serving_stats.move_to_end(sid)
                    while (len(self._remote_serving_stats)
                           > self._remote_serving_stats_cap):
                        self._remote_serving_stats.popitem(last=False)
                if "gen_slots_busy" in payload:
                    # the autoscaler's generative load signal lives in
                    # THIS process's registry; a process-placed
                    # generation worker's occupancy reaches it through
                    # this relay (in-process workers record the ring
                    # directly — same name, so the reader can't tell).
                    # Under the paged layout the binding resource is the
                    # BLOCK POOL, so its fraction is the signal; ring
                    # workers keep reporting busy slots.
                    worker_row = self.db.get_inference_job_worker(sid)
                    if "gen_kv_pool_blocks" in payload:
                        pool = max(int(payload["gen_kv_pool_blocks"]), 1)
                        occupancy = int(
                            payload.get("gen_kv_blocks_used", 0)) / pool
                    else:
                        slots_max = max(
                            int(payload.get("gen_slots_max", 1)), 1)
                        occupancy = int(
                            payload["gen_slots_busy"]) / slots_max
                    if worker_row is not None:
                        from rafiki_tpu.utils.metrics import REGISTRY

                        REGISTRY.ring(
                            "slot_occupancy:job:"
                            f"{worker_row['inference_job_id']}").record(
                            occupancy)
        except Exception:
            logger.exception("event %s failed", name)

    def _on_service_status(self, service_id: str, status: str) -> None:
        if status == "RUNNING":
            self.db.mark_service_as_running(service_id)
        elif status == "STOPPED":
            self.db.mark_service_as_stopped(service_id)
        elif status == "ERRORED":
            self.db.mark_service_as_errored(service_id)
        if status in ("STOPPED", "ERRORED"):
            # a dying replica's chip loan comes home however it died —
            # heartbeat-detected host death never reaches the
            # ServicesManager teardown chokepoint (idempotent pop;
            # getattr: status events can predate arbiter wiring at boot)
            arbiter = getattr(self, "chip_arbiter", None)
            if arbiter is not None:
                arbiter.note_return(service_id)
        # a train worker stopping may complete its train job
        worker = self.db.get_train_job_worker(service_id)
        if worker is not None and status in ("STOPPED", "ERRORED"):
            sub = self.db.get_sub_train_job(worker["sub_train_job_id"])
            if sub is not None:
                self.services.refresh_train_job_status(sub["train_job_id"])
        # the last serving replica dying must terminate its inference job
        # (fleet health: dead-host workers are errored by the heartbeat
        # monitor, placement/hosts.py) — and its cached predict routes
        if worker is None and status in ("STOPPED", "ERRORED"):
            iworker = self.db.get_inference_job_worker(service_id)
            if iworker is not None:
                if status == "ERRORED":
                    # zero-deploy replacement: a dead ROUTABLE replica is
                    # replaced from the warm standby pool immediately (an
                    # add_worker route); the pool's next tick replenishes
                    pool = getattr(self, "warm_pool", None)
                    if pool is not None:
                        try:
                            pool.on_replica_errored(
                                service_id, iworker["inference_job_id"])
                        # lint: absorb(replacement is a fast-path optimization: the job-status refresh below still runs either way)
                        except Exception:
                            logger.exception(
                                "warm-pool replacement for %s failed",
                                service_id[:8])
                final = self.services.refresh_inference_job_status(
                    iworker["inference_job_id"])
                if final is not None:
                    self._drop_predict_routes(iworker["inference_job_id"])

    def shutdown(self) -> None:
        # the autoscaler must stop deciding before services are torn down
        # — a tick racing the teardown would re-place replicas
        if getattr(self, "autoscaler", None) is not None:
            self.autoscaler.stop()
        # the warm pool likewise: a top-up racing the teardown would
        # place standbys nothing will ever stop
        if getattr(self, "warm_pool", None) is not None:
            self.warm_pool.stop()
        # the drift loop must stop deciding before the rollout
        # controller it drives — a tick racing the teardown could start
        # a rollout nothing will ever judge
        if getattr(self, "drift", None) is not None:
            self.drift.stop()
        # rollout runs likewise: a mid-flight placement racing the
        # teardown would resurrect a replica nothing will ever stop
        if getattr(self, "rollouts", None) is not None:
            self.rollouts.stop()
        # a reconcile racing a shutdown would resurrect services the stop
        # below is about to tear down: signal it to ABORT (it checks at
        # every loop top and inside retry backoffs), then join it out
        if self._recovery_runner is not None:
            self._recovery_runner.abort()
        if self._recovery_thread is not None:
            self._recovery_thread.join(timeout=30)
        try:
            self.stop_all_jobs()
        except (StaleEpochError, StaleAdminEpochError) as e:
            # a fenced ex-leader has nothing left to tear down — the new
            # leader adopted the fleet; forcing the teardown through would
            # be exactly the double-teardown the fence exists to stop
            logger.warning("shutdown teardown skipped (fenced): %s", e)
        if hasattr(self.placement, "stop_all"):
            self.placement.stop_all()
        # the shm broker holds listener threads + /dev/shm segments; the
        # in-process broker has no close()
        close = getattr(self.broker, "close", None)
        if close is not None:
            close()
        # last: releasing the lease clears the fences, so every mutation
        # above still ran under epoch protection
        if self._lease is not None:
            self._lease.stop(release=True)
