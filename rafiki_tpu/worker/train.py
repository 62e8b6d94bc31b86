"""Train worker: the AutoML trial loop.

Parity with the reference's TrainWorker (reference rafiki/worker/train.py:37-132):
read job info -> budget check -> propose knobs -> instantiate model -> train ->
evaluate -> persist params -> record trial -> feed back the score -> repeat,
with crash handling (trial marked ERRORED, loop continues — the reference
instead exited the container and let swarm restart it) and termination
handling (in-flight trial marked TERMINATED on stop, reference train.py:134-148).

TPU-native differences:
- the worker is an *executor thread* with a chip grant; the model's mesh is
  built from exactly the granted devices (set_device_grant), so parallel
  trials occupy disjoint chips of the host slice;
- the advisor is shared per sub-train-job through AdvisorStore (keyed by
  sub_train_job_id, not worker service id), so parallel workers coordinate —
  fixing reference train.py:213;
- no per-boot pip install: dependencies are validated at model registration,
  and with RAFIKI_INSTALL_DEPS=1 provisioned ONCE per dependency-set into a
  cached prefix (sdk/deps.py) instead of the reference's per-container-boot
  install (reference scripts/start_worker.py:6-9).
"""

from __future__ import annotations

import logging
import os
import random
import threading
import time
import traceback
from typing import Any, Callable, Dict, Optional

from rafiki_tpu import config
from rafiki_tpu.advisor.advisor import AdvisorStore
from rafiki_tpu.constants import BudgetType, TrialStatus
from rafiki_tpu.db.database import Database
from rafiki_tpu.parallel.mesh import set_device_grant
from rafiki_tpu.placement.manager import ServiceContext
from rafiki_tpu.sdk import compile_cache
from rafiki_tpu.sdk.artifact import write_artifact
from rafiki_tpu.sdk.log import ModelLogger, StopTrialEarly
from rafiki_tpu.sdk.model import load_model_class, population_capability
from rafiki_tpu.sdk.params import stream_params
from rafiki_tpu.worker.vmap_partition import partition_for_vmap
from rafiki_tpu.utils import chaos
from rafiki_tpu.utils.metrics import REGISTRY
from rafiki_tpu.utils.trace import Tracer, jax_profile
from rafiki_tpu.worker import faults, warmup
from rafiki_tpu.worker.faults import FaultKind, TrialChaosError, validate_score

logger = logging.getLogger(__name__)

# Event name the worker sends when its sub-train-job exhausts its budget
# (reference train.py:198-205).
EVENT_BUDGET_REACHED = "sub_train_job_budget_reached"

# Sent when the job fail-fast tripped: RAFIKI_TRIAL_FAULT_LIMIT
# consecutive user-class trial faults — the template is broken, and
# grinding the remaining budget through it would only produce more
# ERRORED rows. Payload: train_job_id, sub_train_job_id, fault_kind,
# reason. The admin marks the job ERRORED (with the reason on the row)
# and tears down its services.
EVENT_TRIAL_FAULT_LIMIT = "sub_train_job_fault_limit"

EventFn = Callable[[str, Dict[str, Any]], None]


def persist_trusted_params(tracer: Tracer, params_path: str,
                           dump: Callable[[], Any]) -> None:
    """Persist what ``dump`` returns (a trial's ``dump_parameters``, one
    member's ``dump_member_parameters``) to ``params_path``: the one
    sequence of the trusted paths, under the caller's ``persist_params``
    span, on the trial's own thread. Its three depth-1 spans:
    ``persist.dump`` around the template's dump (its fetch from the
    device); ``persist.serialize`` around the host fetch of any leaf still
    on the device and the building of the msgpack stream (``sdk/params.py``:
    headers between views of the leaves' own memory; only a leaf that is
    not C-contiguous is copied); ``persist.write`` around crc32, the write
    and the two fsyncs (``sdk/artifact.py``: atomic and checksummed, so a
    crash mid-write or later bit rot is a typed ArtifactCorruptError at
    download or deploy, never a deserialize traceback), with the attributes
    ``bytes`` and ``copied_bytes``. An ``OSError`` from the write is
    trusted-side I/O (full disk, yanked volume), the platform's fault and
    never the template's knobs: the INFRA TrialFault. Whatever ``dump``
    raises is the template's."""
    with tracer.span("persist.dump"):
        params = dump()
    with tracer.span("persist.serialize"):
        buffers, copied = stream_params(params)
    try:
        with tracer.span("persist.write", copied_bytes=copied) as span:
            span.attrs["bytes"] = written = write_artifact(
                params_path, buffers)
    except OSError as e:
        raise faults.TrialFault(f"params persist failed: {e}",
                                kind=FaultKind.INFRA) from e
    REGISTRY.counter(
        "rafiki_params_persist_bytes_total",
        "parameter bytes the train worker's trusted persist wrote").inc(
            written)
    REGISTRY.counter(
        "rafiki_params_persist_copied_bytes_total",
        "of those, array bytes that were copied on the host first (leaves "
        "not C-contiguous or outside the tree's dicts)").inc(copied)


class TrainWorker:
    """One trial executor for a sub-train-job."""

    def __init__(
        self,
        sub_train_job_id: str,
        db: Database,
        advisor_store: AdvisorStore,
        send_event: Optional[EventFn] = None,
        params_dir: Optional[str] = None,
    ):
        self._sub_id = sub_train_job_id
        self._db = db
        self._advisors = advisor_store
        self._send_event = send_event or (lambda name, payload: None)
        self._params_dir = params_dir or config.PARAMS_DIR
        # observations whose advisor feedback failed, awaiting retry —
        # BOUNDED (RAFIKI_PENDING_FEEDBACK_MAX, drop-oldest): an advisor
        # unreachable for hours must not grow this without limit
        self._pending_feedback: list = []
        self._feedback_drop_warned = False
        # trial fault tolerance (worker/faults.py): poison-knob
        # quarantine and the consecutive user-fault streak. The
        # signature counts are rebuilt from trial rows at startup, so
        # quarantine survives worker restarts; the streak is in-memory
        # on purpose — a restart is fresh evidence-gathering.
        self._knob_config = None
        self._quarantine: set = set()
        self._user_fault_sigs: Dict[str, int] = {}
        self._fault_streak = 0
        # vectorized trial execution (set per job in _loop): the
        # template's PopulationSpec when every gate passed, else None
        self._pop_spec = None
        self._vmap_k = 1

    def start(self, ctx: ServiceContext) -> None:
        """The trial loop; returns when budget is reached or stop is set."""
        set_device_grant(ctx.chips)
        # on-disk XLA executable reuse across trials AND worker processes —
        # the TPU-native answer to the reference's per-trial container boot
        # cost (reference scripts/start_worker.py:6-9)
        compile_cache.enable()
        try:
            self._loop(ctx)
        finally:
            set_device_grant(None)

    # -- internals ---------------------------------------------------------

    def _loop(self, ctx: ServiceContext) -> None:
        sub = self._db.get_sub_train_job(self._sub_id)
        assert sub is not None, f"no sub_train_job {self._sub_id}"
        job = self._db.get_train_job(sub["train_job_id"])
        model = self._db.get_model(sub["model_id"])
        assert job is not None and model is not None

        budget = job["budget"]
        max_trials = int(
            budget.get(BudgetType.MODEL_TRIAL_COUNT, config.DEFAULT_TRIAL_COUNT)
        )
        # optional wall-clock budget, measured from job start (a capability
        # the reference lacked: its only budgets were trials and GPUs)
        time_budget_h = budget.get(BudgetType.TIME_HOURS)
        deadline = (
            job["datetime_started"] + float(time_budget_h) * 3600
            if time_budget_h is not None
            else None
        )
        # ASHA early stopping (budget-opt-in): rung-check each trial's
        # per-epoch "loss" report against the sub-job's shared scheduler
        self._early_stop = bool(budget.get(BudgetType.EARLY_STOP, False))
        self._asha_min = int(budget.get(BudgetType.ASHA_MIN_EPOCHS, 1))
        self._asha_eta = int(budget.get(BudgetType.ASHA_ETA, 3))
        # deadlines enforced MID-trial through the same stop-check channel:
        # the job's TIME_HOURS deadline, and an optional per-trial wall cap
        # (TRIAL_TIMEOUT_S). Without these a runaway trial (bad knob draw
        # compiling into an enormous model) holds its executor forever —
        # the between-trials deadline check alone cannot interrupt it.
        self._job_deadline = deadline
        tt = budget.get(BudgetType.TRIAL_TIMEOUT_S)
        self._trial_timeout_s = float(tt) if tt is not None else None
        # provision declared dependencies before touching the template
        # (RAFIKI_INSTALL_DEPS=1 installs per dependency-set; default
        # validates and fails the executor fast — sdk/deps.py)
        from rafiki_tpu.sdk.deps import activate_prefix, ensure_dependencies

        self._deps_prefix = ensure_dependencies(model.get("dependencies"))
        activate_prefix(self._deps_prefix)
        clazz = load_model_class(model["model_file_bytes"], model["model_class"])
        # kept for the sandbox path: the child re-imports from bytes in its
        # own restricted process (sdk/sandbox.py)
        self._model_bytes = model["model_file_bytes"]
        self._model_class = model["model_class"]
        knob_config = clazz.get_knob_config()
        # Vectorized trial execution (vmap-over-knobs): when the template
        # advertises a PopulationSpec, drain K proposals per round and
        # train each shape-compatible bucket as ONE PopulationTrainer
        # program on this executor's chip grant — K trials for roughly
        # one trial's dispatch/overhead cost on underutilized chips.
        # Every gate below degrades to the unchanged scalar path.
        self._pop_spec = population_capability(clazz)
        vk = budget.get(BudgetType.TRIAL_VMAP_K)
        self._vmap_k = int(vk) if vk is not None else int(config.TRIAL_VMAP_K)
        if self._pop_spec is not None:
            from rafiki_tpu.sdk.sandbox import sandbox_enabled

            if not config.TRIAL_VMAP:
                self._pop_spec = None  # operator kill switch
            elif self._vmap_k < 2:
                self._pop_spec = None  # a population of one is a trial
            elif sandbox_enabled():
                # the sandbox runs one restricted child per trial; a
                # population shares one process by construction — scalar
                # until a population-aware sandbox child exists
                logger.info("RAFIKI_SANDBOX=1: vectorized trial execution "
                            "disabled; trials run scalar in children")
                self._pop_spec = None
            elif not set(self._pop_spec.dynamic_knobs) <= set(knob_config):
                logger.warning(
                    "population_spec dynamic knobs %s are not all in the "
                    "knob config %s; trials run scalar",
                    self._pop_spec.dynamic_knobs, sorted(knob_config))
                self._pop_spec = None
        advisor_id = self._advisors.create_advisor(
            knob_config, advisor_id=self._sub_id
        )
        self._db.update_sub_train_job_advisor(self._sub_id, advisor_id)
        ctx.ready()  # job info read + model class loaded: startup succeeded

        all_trials = self._db.get_trials_of_sub_train_job(self._sub_id)

        # Fault-tolerance state rebuild: poison-knob signatures with
        # enough recorded user-class faults are quarantined from the
        # first proposal of this incarnation — a restart must not spend
        # fresh budget re-learning which region crashes.
        self._knob_config = knob_config
        self._user_fault_sigs = faults.poison_signature_counts(
            all_trials, knob_config)
        k = max(int(config.TRIAL_QUARANTINE_K), 1)
        self._quarantine = {s for s, n in self._user_fault_sigs.items()
                            if n >= k}
        if self._quarantine:
            faults.record_quarantine(self._sub_id, self._quarantine)
            logger.warning("%d poison-knob signature(s) quarantined from "
                           "recorded trial faults", len(self._quarantine))

        # Crash recovery, part 1: if the advisor session is fresh (its
        # process died too — in-process store, or an admin restart), rebuild
        # the GP from the completed trials already in the store; otherwise
        # the remaining budget would be proposed from the prior as if no
        # trial had ever run. Atomic + empty-only on the store side, so
        # concurrently restarted siblings can't double-feed. Infeasible
        # observations (USER/TIMEOUT/INVALID_SCORE-errored trials) ride
        # the same replay so the GP also relearns which regions crash.
        scored = [(t["knobs"], t["score"]) for t in all_trials
                  if t["status"] == TrialStatus.COMPLETED
                  and t["score"] is not None]
        infeasible = [(t["knobs"], t["fault_kind"]) for t in all_trials
                      if faults.is_infeasible_row(t)]
        if scored or infeasible:
            try:
                if self._advisors.replay_feedback(advisor_id, scored,
                                                  infeasible=infeasible):
                    logger.info("replayed %d completed + %d infeasible "
                                "trials into advisor %s", len(scored),
                                len(infeasible), advisor_id)
            except TypeError:
                # an advisor store predating the infeasible signal
                try:
                    self._advisors.replay_feedback(advisor_id, scored)
                except Exception:
                    logger.warning("advisor replay failed; proposals start "
                                   "from the prior", exc_info=True)
            except Exception:
                logger.warning("advisor replay failed; proposals start from "
                               "the prior", exc_info=True)

        # Crash recovery, part 2: trials left RUNNING by a killed
        # predecessor of this service (a restarted worker keeps its service
        # id) are re-run under the SAME trial id and knobs — a template that
        # feeds ``checkpoint_path`` to fit() resumes from its last epoch
        # rather than from scratch (the reference discarded all progress,
        # reference worker/train.py:122-132).
        for stale in all_trials:
            if ctx.stopping:
                return
            if (stale["status"] != TrialStatus.RUNNING
                    or stale["worker_id"] != ctx.service_id):
                continue
            if deadline is not None and time.time() >= deadline:
                # the time budget expired while this trial was down: it
                # will never run — release its budget slot (the main loop
                # reports budget-reached right after)
                logger.info("time budget spent; terminating stale trial %s",
                            stale["id"])
                self._db.mark_trial_as_terminated(stale["id"])
                self._cleanup_ckpt(stale["id"])
                continue
            logger.info("resuming stale trial %s after worker restart",
                        stale["id"])
            if not self._execute_trial(ctx, clazz, job, advisor_id,
                                       stale["id"], stale["knobs"],
                                       start_attempt=int(
                                           stale.get("attempt") or 0)):
                return

        while not ctx.stopping:
            # shared budget accounting through the DB (reference
            # train.py:227-232) — but the reserve is ATOMIC (count + insert
            # in one transaction, db.reserve_trial): the reference's
            # check-then-create let N parallel workers overshoot the trial
            # budget by up to N-1
            over_time = deadline is not None and time.time() >= deadline
            if self._pop_spec is not None and not over_time:
                verdict = self._population_round(
                    ctx, clazz, job, model, advisor_id, max_trials)
                if verdict == "stop":
                    return
                if verdict == "budget":
                    self._send_event(EVENT_BUDGET_REACHED, {
                        "sub_train_job_id": self._sub_id,
                        "train_job_id": job["id"],
                    })
                    return
                continue
            trial = None
            tracer = Tracer("pending")
            if not over_time:
                with tracer.span("propose"):
                    try:
                        self._retry_pending_feedback(advisor_id)
                    except Exception:
                        logger.warning("pending feedback retry failed; "
                                       "proposing without it", exc_info=True)
                    knobs = self._propose_clear_of_quarantine(advisor_id)
                trial = self._db.reserve_trial(
                    self._sub_id, model["id"], knobs,
                    worker_id=ctx.service_id, max_trials=max_trials,
                )
            if trial is None:
                self._send_event(
                    EVENT_BUDGET_REACHED,
                    {
                        "sub_train_job_id": self._sub_id,
                        "train_job_id": job["id"],
                    },
                )
                return
            tracer.trace_id = trial["id"]
            if not self._execute_trial(ctx, clazz, job, advisor_id,
                                       trial["id"], knobs, tracer=tracer):
                return

    def _execute_trial(self, ctx, clazz, job, advisor_id: str,
                       trial_id: str, knobs, tracer=None,
                       start_attempt: int = 0) -> bool:
        """Run one trial end to end: per-trial logger + stop-check wiring,
        train/evaluate/persist, and terminal bookkeeping. Shared by the
        stale-resume path and the main loop. Returns False when the worker
        is exiting its loop — stopping (trial TERMINATED) or job
        fail-fast (RAFIKI_TRIAL_FAULT_LIMIT tripped).

        Failures run through the fault classification (worker/faults.py):
        infra-class kinds (INFRA/MEM/STALL) re-run under the SAME trial
        id with jittered backoff up to RAFIKI_TRIAL_RETRY_MAX — no extra
        budget slot is consumed (the row is reused), and a template that
        keeps a checkpoint resumes mid-trial. User-class kinds
        (USER/TIMEOUT/INVALID_SCORE) are terminal: the trial is ERRORED
        with its kind + truncated traceback on the row, the budget slot
        is consumed (as before), and the advisor receives an infeasible
        observation so the proposal distribution steers away (the
        reference instead exited the worker, reference train.py:122-132,
        and this repo previously told the advisor nothing)."""
        trial_logger = ModelLogger()
        trial_logger.set_sink(
            lambda line, _tid=trial_id: self._db.add_trial_log(_tid, line))
        tracer = tracer or Tracer(trial_id)
        retry_max = max(int(config.TRIAL_RETRY_MAX), 0)
        attempt = max(int(start_attempt), 0)
        while True:
            # fresh stop-check per attempt: the TRIAL_TIMEOUT_S clock
            # measures THIS run of the template, not the sum of retries
            self._install_stop_check(trial_logger, advisor_id, trial_id)
            try:
                self._chaos_trial(trial_id)
                t_trial = time.time()
                with self._trial_profile(trial_id):
                    score, params_path = self._run_trial(
                        clazz, knobs, job, trial_id, trial_logger, tracer)
                # the boot's FIRST completed trial carries the cold-start
                # verdict: cache hits mean its jit programs loaded from
                # the persistent cache instead of compiling (the r5
                # cold-compile collapse, measured per boot); its compile
                # record ends the worker's set-up
                if warmup.note_first_program(
                        ctx.service_id, self._sub_id, "first_trial",
                        compile_cache.events(
                            since=t_trial,
                            thread=threading.current_thread().name)):
                    warmup.log_setup(ctx.service_id, self._sub_id,
                                     tracer.summary(), t_trial)
                # feedback BEFORE mark-complete: a sibling restarting in
                # between sees COMPLETED only once the observation is in
                # the GP, so its empty-only replay can't double-feed (the
                # reverse window re-runs the trial at worst — a duplicate
                # noisy observation, which the GP tolerates). A feedback
                # failure must not cost the finished trial its result —
                # _feedback_best_effort queues it. A stop signal that
                # lands after the work finished does NOT discard the
                # result: the score and params exist, persisting them is
                # free, and only the loop exits early.
                self._feedback_best_effort(advisor_id, knobs, score)
                self._db.mark_trial_as_complete(trial_id, score, params_path)
                self._fault_streak = 0
                faults.record_counter(self._sub_id,
                                      "consecutive_user_faults", 0,
                                      absolute=True)
                return not ctx.stopping
            except Exception as e:
                if ctx.stopping:
                    self._db.mark_trial_as_terminated(trial_id)
                    self._cleanup_ckpt(trial_id)
                    return False
                kind, detail = faults.classify_failure(e)
                logger.error("trial %s fault %s (attempt %d):\n%s",
                             trial_id, kind, attempt, detail)
                if kind in faults.RETRYABLE_KINDS and attempt < retry_max:
                    # same trial id, same knobs, same budget slot; the
                    # attempt counter lives on the ROW, so the bound
                    # holds across worker restarts too
                    attempt = self._db.record_trial_fault(
                        trial_id, kind, detail)
                    faults.record_fault(self._sub_id, kind, retried=True)
                    trial_logger.set_stop_check(None)
                    self._retry_backoff(ctx, attempt)
                    if ctx.stopping:
                        self._db.mark_trial_as_terminated(trial_id)
                        self._cleanup_ckpt(trial_id)
                        return False
                    logger.info("retrying trial %s (attempt %d/%d) after "
                                "%s fault", trial_id, attempt, retry_max,
                                kind)
                    continue
                self._db.mark_trial_as_errored(trial_id, kind, detail)
                self._cleanup_ckpt(trial_id)
                faults.record_fault(self._sub_id, kind)
                if kind in faults.INFEASIBLE_KINDS or \
                        kind == FaultKind.MEM:
                    # terminal MEM (retries exhausted) is knob-driven
                    # too — steer the advisor away and count toward
                    # quarantine; only user-class kinds march the job
                    # fail-fast streak (repeated MEM on distinct knobs
                    # reads as host pressure, not a broken template)
                    self._feedback_infeasible_best_effort(
                        advisor_id, knobs, kind, trial_id=trial_id)
                    if not self._note_user_fault(
                            job, trial_id, knobs, kind,
                            streak=kind in faults.INFEASIBLE_KINDS):
                        return False  # job fail-fast: exit the loop
                return True

    @staticmethod
    def _trial_profile(trial_id: str):
        """RAFIKI_PROFILE: one `jax.profiler` session around the WHOLE
        trial (train, evaluate, persist — the idle that evaluate and
        persist cause is in it), under LOGS_DIR/profiles/<trial id>, with
        the trial's spans on it (utils/trace.py annotation). The one way
        to a device trace from a worker in a child process; a no-op
        without the variable."""
        return jax_profile(
            os.path.join(config.LOGS_DIR, "profiles", trial_id))

    def _chaos_trial(self, trial_id: str) -> None:
        """RAFIKI_CHAOS site=trial: the drillable fault chokepoint —
        every retry/classification path is exercisable in CPU tier-1
        tests without a real flaky host (docs/failure-model.md)."""
        rule = chaos.hit(chaos.SITE_TRIAL, f"{self._sub_id} {trial_id}")
        if rule is None:
            return
        if rule.action == chaos.ACTION_DELAY:
            chaos.sleep_for(rule)
            return
        if rule.action == chaos.ACTION_OOM:
            raise MemoryError("chaos-injected trial OOM (site=trial)")
        raise TrialChaosError(
            "chaos-injected transient trial fault (site=trial)")

    def _retry_backoff(self, ctx, attempt: int) -> None:
        """Exponential backoff with full jitter before an infra-retry
        (uniform in [0, min(base * 2^(n-1), 30 s)] — the cap bounds the
        realized sleep, not just the pre-jitter value), responsive to
        the stop signal (waits on the stop event, never a blind
        sleep)."""
        base = max(float(config.TRIAL_RETRY_BACKOFF_S), 0.0)
        ceiling = min(base * (2 ** max(attempt - 1, 0)), 30.0)
        if ceiling > 0:
            ctx.stop_event.wait(random.uniform(0, ceiling))

    def _note_user_fault(self, job, trial_id: str, knobs,
                         kind: str, streak: bool = True) -> bool:
        """Poison-knob quarantine + job fail-fast bookkeeping after a
        terminal poison fault. ``streak=False`` (terminal MEM) counts
        toward quarantine only, never the fail-fast streak. Returns
        False when the consecutive-fault limit tripped and the job was
        errored (the caller exits)."""
        sig = faults.knob_signature(self._knob_config, knobs)
        self._user_fault_sigs[sig] = self._user_fault_sigs.get(sig, 0) + 1
        k = max(int(config.TRIAL_QUARANTINE_K), 1)
        if (self._user_fault_sigs[sig] >= k
                and sig not in self._quarantine):
            self._quarantine.add(sig)
            faults.record_quarantine(self._sub_id, [sig])
            logger.warning(
                "knob signature %s quarantined after %d poison faults "
                "(RAFIKI_TRIAL_QUARANTINE_K=%d); matching proposals "
                "will be re-proposed", sig,
                self._user_fault_sigs[sig], k)
        if not streak:
            return True
        self._fault_streak += 1
        faults.record_counter(self._sub_id, "consecutive_user_faults",
                              self._fault_streak, absolute=True)
        limit = int(config.TRIAL_FAULT_LIMIT)
        if limit <= 0 or self._fault_streak < limit:
            return True
        reason = (
            f"{self._fault_streak} consecutive user-class trial faults "
            f"(RAFIKI_TRIAL_FAULT_LIMIT={limit}); last: {kind} on trial "
            f"{trial_id} — template broken at every proposed knob "
            f"combination, failing the job early instead of burning the "
            f"remaining budget")
        logger.error("train job %s fail-fast: %s", job["id"], reason)
        # record the typed reason directly (works headless), then tell
        # the admin so it tears down sibling workers; the guarded
        # transition makes the double-mark harmless
        self._db.mark_train_job_as_errored(job["id"], FaultKind.USER,
                                           reason)
        self._send_event(EVENT_TRIAL_FAULT_LIMIT, {
            "train_job_id": job["id"],
            "sub_train_job_id": self._sub_id,
            "fault_kind": FaultKind.USER,
            "reason": reason,
        })
        return False

    def _propose_clear_of_quarantine(self, advisor_id: str, knobs=None):
        """Propose knobs, re-proposing (bounded) while the draw matches
        a quarantined poison signature. Each rejection ALSO feeds the
        advisor an infeasible observation at the rejected point, so the
        GP's penalty mass grows until the region stops being proposed —
        the loop converges instead of fighting the optimizer forever.
        After RAFIKI_TRIAL_REPROPOSE_MAX rejections the last draw is
        accepted (with a warning): a mostly-quarantined search space
        must degrade to slow progress, never to a spinning worker.
        ``knobs`` seeds the loop with an already-made draw (the batch
        path filters each of its K draws through the same rule)."""
        if knobs is None:
            knobs = self._advisors.propose(advisor_id)
        if not self._quarantine:
            return knobs
        limit = max(int(config.TRIAL_REPROPOSE_MAX), 0)
        for rejections in range(limit + 1):
            sig = faults.knob_signature(self._knob_config, knobs)
            if sig not in self._quarantine:
                return knobs
            if rejections == limit:
                break  # this draw IS quarantined and the budget is out
            faults.record_counter(self._sub_id, "reproposals")
            logger.info("proposal matches quarantined signature %s; "
                        "re-proposing", sig)
            self._feedback_infeasible_best_effort(advisor_id, knobs,
                                                  FaultKind.USER)
            knobs = self._advisors.propose(advisor_id)
        logger.warning(
            "proposal still quarantined after %d re-proposals "
            "(RAFIKI_TRIAL_REPROPOSE_MAX); accepting it — most of the "
            "search space may be poisoned", limit)
        return knobs

    # -- vectorized trial execution (vmap-over-knobs) ----------------------

    def _propose_batch_clear_of_quarantine(self, advisor_id: str, k: int):
        """Drain K proposals in one advisor call (the GP spreads them via
        constant-liar fantasies), then run each draw through the same
        quarantine filter the scalar path uses. Advisor stores predating
        propose_batch fall back to K single proposals."""
        draws = None
        fn = getattr(self._advisors, "propose_batch", None)
        if fn is not None:
            try:
                draws = fn(advisor_id, k)
            except Exception:
                logger.warning("propose_batch failed; falling back to "
                               "single proposals", exc_info=True)
        if draws is None:
            draws = [self._advisors.propose(advisor_id) for _ in range(k)]
        if not self._quarantine:
            return draws
        return [self._propose_clear_of_quarantine(advisor_id, knobs=d)
                for d in draws]

    def _population_round(self, ctx, clazz, job, model,
                          advisor_id: str, max_trials: int) -> str:
        """One vectorized round: drain up to K proposals, bucket them by
        program shape (worker/vmap_partition.py), atomically reserve a
        trial ROW per member (the PR-5 budget contract is untouched —
        reserve_trial's count+insert transaction is still the only
        authority, so MODEL_TRIAL_COUNT=N yields exactly N rows no
        matter how K divides N), and train each bucket as one
        PopulationTrainer program. Singleton buckets run the scalar
        path. Returns "stop" (worker exiting), "budget" (caller sends
        the budget-reached event), or "ok" (next round)."""
        try:
            self._retry_pending_feedback(advisor_id)
        except Exception:
            logger.warning("pending feedback retry failed; proposing "
                           "without it", exc_info=True)
        # clamp the drain by the remaining budget (best-effort count; the
        # per-member reserve below stays authoritative) so a nearly-spent
        # job doesn't strand K-1 never-scored constant-liar fantasies in
        # the shared GP
        live = sum(1 for t in self._db.get_trials_of_sub_train_job(
            self._sub_id) if t["status"] != TrialStatus.TERMINATED)
        remaining = max_trials - live
        if remaining <= 0:
            return "budget"
        k = min(self._vmap_k, remaining,
                max(int(self._pop_spec.max_members), 1))
        draws = self._propose_batch_clear_of_quarantine(
            advisor_id, max(k, 1))
        buckets = partition_for_vmap(draws, self._pop_spec.dynamic_knobs,
                                     self._pop_spec.max_members)
        budget_out = False
        for bucket in buckets:
            if ctx.stopping:
                return "stop"
            members = []
            for knobs in bucket:
                trial = self._db.reserve_trial(
                    self._sub_id, model["id"], knobs,
                    worker_id=ctx.service_id, max_trials=max_trials)
                if trial is None:
                    budget_out = True
                    break
                members.append((trial["id"], knobs))
            if members:
                if len(members) == 1:
                    ok = self._execute_trial(ctx, clazz, job, advisor_id,
                                             members[0][0], members[0][1])
                else:
                    ok = self._execute_population_trial(
                        ctx, clazz, job, advisor_id, members)
                if not ok:
                    return "stop"
            if budget_out:
                return "budget"
        return "ok"

    def _execute_population_trial(self, ctx, clazz, job, advisor_id: str,
                                  members) -> bool:
        """Run one vmapped batch end to end: train all members as one
        program, evaluate all members, then settle each member's trial
        row INDIVIDUALLY — per-member scores feed the advisor one by
        one, a member whose score fails validation becomes a typed
        INVALID_SCORE fault + infeasible observation for that member
        only (never a batch abort), and ASHA rungs are reported per
        member. A batch-LEVEL failure (template crash, OOM, chaos)
        falls back to scalar execution of every member, so the full
        fault classification — same-id infra retries included — applies
        exactly as if the batch had never been tried. Returns False
        when the worker is exiting its loop."""
        lead_id = members[0][0]
        trial_logger = ModelLogger()
        # the shared training log lands on the LEAD member's row; sibling
        # rows still carry their own knobs/score/params/fault columns
        trial_logger.set_sink(
            lambda line, _tid=lead_id: self._db.add_trial_log(_tid, line))
        tracer = Tracer(lead_id)
        self._install_population_stop_check(trial_logger, advisor_id,
                                            [tid for tid, _ in members])
        try:
            self._chaos_trial(lead_id)
            with self._trial_profile(lead_id):
                results = self._run_population_trial(
                    clazz, members, job, trial_logger, tracer)
        except Exception:
            if ctx.stopping:
                for tid, _ in members:
                    self._db.mark_trial_as_terminated(tid)
                    self._cleanup_ckpt(tid)
                return False
            logger.warning(
                "population batch %s failed; re-running its %d members "
                "as scalar trials (same ids, full fault classification):\n%s",
                lead_id, len(members), traceback.format_exc())
            self._cleanup_ckpt(lead_id)
            for idx, (tid, knobs) in enumerate(members):
                if ctx.stopping:
                    # never-started siblings must not stay RUNNING
                    self._terminate_members(members[idx:])
                    return False
                if not self._execute_trial(ctx, clazz, job, advisor_id,
                                           tid, knobs):
                    self._terminate_members(members[idx + 1:])
                    return False
            return not ctx.stopping
        # settle COMPLETED members first (pure DB writes): a blocking
        # scalar re-run or a fail-fast verdict below must never discard a
        # sibling's already-finished, already-persisted work
        for tid, knobs, score, params_path, err in results:
            if err is None:
                # same ordering contract as the scalar path: feedback
                # BEFORE mark-complete, so a restarting sibling's
                # empty-only replay can't double-feed
                self._feedback_best_effort(advisor_id, knobs, score)
                self._db.mark_trial_as_complete(tid, score, params_path)
                self._fault_streak = 0
                faults.record_counter(self._sub_id,
                                      "consecutive_user_faults", 0,
                                      absolute=True)
        faulted = [r for r in results if r[4] is not None]
        for idx, (tid, knobs, _, _, err) in enumerate(faulted):
            kind, detail = faults.classify_failure(err)
            if kind in faults.RETRYABLE_KINDS:
                # a platform fault on one member (params persist I/O)
                # is not a verdict on its knobs OR its siblings:
                # re-run just this member scalar under the same trial
                # id — the full classification applies (same-id infra
                # retries, no budget burn)
                logger.warning(
                    "population member %s hit retryable %s fault; "
                    "re-running it as a scalar trial:\n%s",
                    tid, kind, detail)
                if not self._execute_trial(ctx, clazz, job,
                                           advisor_id, tid, knobs):
                    self._terminate_members(
                        [(t, k) for t, k, _, _, _ in faulted[idx + 1:]])
                    return False
                continue
            logger.error("population member %s fault %s:\n%s",
                         tid, kind, detail)
            self._db.mark_trial_as_errored(tid, kind, detail)
            faults.record_fault(self._sub_id, kind)
            self._feedback_infeasible_best_effort(
                advisor_id, knobs, kind, trial_id=tid)
            if not self._note_user_fault(job, tid, knobs, kind):
                self._terminate_members(
                    [(t, k) for t, k, _, _, _ in faulted[idx + 1:]])
                return False  # job fail-fast tripped
        return not ctx.stopping

    def _terminate_members(self, members) -> None:
        """Mark a batch's not-yet-settled members TERMINATED when the
        worker exits mid-settle (stop signal or job fail-fast): a
        reserved row must never outlive its batch as a forever-RUNNING
        orphan."""
        for tid, _ in members:
            try:
                self._db.mark_trial_as_terminated(tid)
                self._cleanup_ckpt(tid)
            except Exception:
                logger.warning("failed to terminate batch member %s",
                               tid, exc_info=True)

    def _run_population_trial(self, clazz, members, job,
                              trial_logger: ModelLogger,
                              tracer: Optional[Tracer] = None) -> list:
        """The vmapped analogue of _run_trial: one model instance
        (constructed with the lead member's knobs — all members share
        the program-shaping knobs by bucketing), one train_population
        call, one evaluate_population call, then per-member score
        validation and params persistence. Returns
        ``[(trial_id, knobs, score, params_path, error)]`` with exactly
        one entry per member; ``error`` is the member's typed fault (an
        InvalidScoreError) and the other fields None when set. The
        stacked checkpoint rides the lead member's .ckpt slot through
        the PR-4 artifact frame, so a restarted batch resumes mid-trial
        like a scalar trial would (a resume with a different K is typed
        artifact corruption -> fresh start)."""
        lead_id = members[0][0]
        tracer = tracer or Tracer(lead_id)
        member_knobs = [dict(knobs) for _, knobs in members]
        model = clazz(**member_knobs[0])
        model.logger = trial_logger
        os.makedirs(self._params_dir, exist_ok=True)
        model.checkpoint_path = os.path.join(
            self._params_dir, f"{lead_id}.ckpt")
        try:
            try:
                with tracer.span("train"):
                    model.train_population(job["train_dataset_uri"],
                                           member_knobs)
            except StopTrialEarly:
                trial_logger.log(
                    "population batch stopped early by scheduler")
            trial_logger.set_stop_check(None)
            with tracer.span("evaluate"):
                raw_scores = model.evaluate_population(
                    job["test_dataset_uri"])
            if raw_scores is None or len(raw_scores) != len(members):
                # a template answering the wrong number of scores broke the
                # population contract: fail the BATCH (caller falls back
                # to scalar, where the classification judges each member alone)
                raise faults.TrialFault(
                    f"evaluate_population returned "
                    f"{0 if raw_scores is None else len(raw_scores)} "
                    f"score(s) for {len(members)} members",
                    kind=FaultKind.USER)
            results = []
            with tracer.span("persist_params"):
                for i, (tid, knobs) in enumerate(members):
                    try:
                        score = validate_score(raw_scores[i])
                    except faults.TrialFault as e:
                        # per-member fault isolation: THIS member is
                        # infeasible; its siblings' scores stand
                        results.append((tid, knobs, None, None, e))
                        continue
                    params_path = os.path.join(
                        self._params_dir, f"{tid}.params")
                    try:
                        # dump + write both per-member: a template whose
                        # dump_member_parameters raises for ONE member
                        # (user code), or a disk blip on one artifact
                        # (platform), fails that member alone — siblings
                        # keep their completed, persisted work. The
                        # caller classifies: retryable kinds re-run the
                        # member scalar (same id, no budget burn),
                        # user-class kinds error it with infeasible
                        # feedback.
                        persist_trusted_params(
                            tracer, params_path,
                            lambda: model.dump_member_parameters(i))
                    except OSError as e:
                        results.append((tid, knobs, None, None,
                                        faults.TrialFault(
                                            f"params persist failed: {e}",
                                            kind=FaultKind.INFRA)))
                        continue
                    # lint: absorb(the exception is carried in results for per-member fault classification)
                    except Exception as e:
                        results.append((tid, knobs, None, None, e))
                        continue
                    results.append((tid, knobs, score, params_path, None))
            self._cleanup_ckpt(lead_id)
            return results
        finally:
            try:
                model.destroy()
            finally:
                try:
                    tracer.save()
                    trial_logger.log(
                        "population batch phase breakdown",
                        members=float(len(members)), **{
                            f"trace_{k}_s": round(v, 4)
                            for k, v in tracer.summary().items()
                        })
                except Exception:
                    logger.exception("failed to persist batch trace")

    def _install_population_stop_check(self, trial_logger: ModelLogger,
                                       advisor_id: str,
                                       member_ids: list) -> None:
        """The batch variant of _install_stop_check. Wall-clock caps
        (TRIAL_TIMEOUT_S, the job TIME_HOURS deadline) act on the whole
        batch — one program, one clock. ASHA rung accounting stays PER
        MEMBER: each member's ``member{k}_loss`` (PopulationTrainer.fit
        logs one per epoch) is reported under that member's own trial
        id, and the batch stops early only when EVERY member's verdict
        says stop — a population is competitive while any member is.
        Templates that log only the population-mean ``loss`` degrade to
        reporting that mean under each member's id (rung rows stay per
        trial, the signal is just shared)."""
        early_stop = getattr(self, "_early_stop", False)
        report = getattr(self._advisors, "report_rung", None)
        if early_stop and report is None:
            logger.warning("EARLY_STOP budget set but the advisor store "
                           "has no report_rung; rung checks disabled")
        job_deadline = getattr(self, "_job_deadline", None)
        trial_timeout = getattr(self, "_trial_timeout_s", None)
        if not ((early_stop and report is not None)
                or job_deadline is not None or trial_timeout is not None):
            return
        batch_start = time.time()

        def check(metrics: Dict[str, Any]) -> bool:
            now = time.time()
            if trial_timeout is not None \
                    and now - batch_start > trial_timeout:
                logger.info("population batch %s hit TRIAL_TIMEOUT_S=%.0f; "
                            "stopping", member_ids[0], trial_timeout)
                return True
            if job_deadline is not None and now >= job_deadline:
                logger.info("population batch %s crossed the job "
                            "TIME_HOURS deadline; stopping", member_ids[0])
                return True
            if not (early_stop and report is not None
                    and "epoch" in metrics):
                return False
            rung = int(metrics["epoch"]) + 1
            keep_any, reported = False, False
            for i, tid in enumerate(member_ids):
                value = metrics.get(f"member{i}_loss",
                                    metrics.get("loss"))
                if value is None:
                    continue
                reported = True
                try:
                    if report(advisor_id, tid, rung, value,
                              min_resource=self._asha_min,
                              eta=self._asha_eta):
                        keep_any = True
                except Exception:
                    logger.warning("ASHA rung report failed for member "
                                   "%s; keeping it", tid, exc_info=True)
                    keep_any = True
            return reported and not keep_any

        trial_logger.set_stop_check(check)

    def _feedback_best_effort(self, advisor_id: str, knobs, score) -> None:
        """Feed a trial score to the advisor, never letting an advisor
        failure destroy the trial result: the caller marks the trial
        COMPLETED right after. A failed observation is queued and retried
        before each later proposal (_retry_pending_feedback) — it cannot be
        recovered by replay_feedback, which only seeds *empty* sessions.
        The queue is bounded (RAFIKI_PENDING_FEEDBACK_MAX, drop-oldest):
        an advisor unreachable for a whole shift must cost observations,
        not memory."""
        try:
            self._retry_pending_feedback(advisor_id)
            self._advisors.get(advisor_id).feedback(knobs, score)
        except Exception:
            self._pending_feedback.append((knobs, score))
            logger.warning(
                "advisor feedback failed for %s (queued for retry):\n%s",
                advisor_id, traceback.format_exc())
            cap = max(int(config.PENDING_FEEDBACK_MAX), 1)
            if len(self._pending_feedback) > cap:
                dropped = len(self._pending_feedback) - cap
                del self._pending_feedback[:dropped]
                faults.record_counter(self._sub_id, "feedback_dropped",
                                      dropped)
                if not self._feedback_drop_warned:
                    self._feedback_drop_warned = True
                    logger.warning(
                        "pending advisor feedback exceeded "
                        "RAFIKI_PENDING_FEEDBACK_MAX=%d; dropping oldest "
                        "observations (warning once; drops counted in "
                        "training stats)", cap)

    def _feedback_infeasible_best_effort(self, advisor_id: str, knobs,
                                         kind: str,
                                         trial_id: Optional[str] = None
                                         ) -> None:
        """Best-effort infeasible signal: penalty points are advisory —
        a failure to deliver one is logged and DROPPED (never queued:
        unlike scores, losing one costs a little steering, not an
        observation). Tolerates advisor stores predating the signal."""
        fi = getattr(self._advisors, "feedback_infeasible", None)
        if fi is None:
            return
        try:
            fi(advisor_id, knobs, kind=kind, trial_id=trial_id)
        except Exception:
            logger.warning("infeasible feedback for %s dropped",
                           advisor_id, exc_info=True)

    def _install_stop_check(self, trial_logger: ModelLogger,
                            advisor_id: str, trial_id: str) -> None:
        """Wire a trial's logger to its in-flight stop conditions. Every
        METRICS report is a decision point; a verdict makes the next log()
        raise StopTrialEarly, which fit()/the trial runner treat as a
        normal (truncated) completion. Conditions, cheapest first:

        - per-trial wall cap (budget TRIAL_TIMEOUT_S),
        - the job's TIME_HOURS deadline (otherwise only enforced between
          trials — an in-flight runaway would sail past it),
        - ASHA rung checks on per-epoch "loss" (budget EARLY_STOP; advisor
          stores without report_rung silently disable this — never fail a
          trial over it)."""
        early_stop = getattr(self, "_early_stop", False)
        report = getattr(self._advisors, "report_rung", None)
        if early_stop and report is None:
            logger.warning("EARLY_STOP budget set but the advisor store "
                           "has no report_rung; rung checks disabled")
        job_deadline = getattr(self, "_job_deadline", None)
        trial_timeout = getattr(self, "_trial_timeout_s", None)
        if not ((early_stop and report is not None)
                or job_deadline is not None or trial_timeout is not None):
            return
        trial_start = time.time()

        def check(metrics: Dict[str, Any]) -> bool:
            now = time.time()
            if trial_timeout is not None and now - trial_start > trial_timeout:
                logger.info("trial %s hit TRIAL_TIMEOUT_S=%.0f; stopping",
                            trial_id, trial_timeout)
                return True
            if job_deadline is not None and now >= job_deadline:
                logger.info("trial %s crossed the job TIME_HOURS deadline; "
                            "stopping", trial_id)
                return True
            if (early_stop and report is not None
                    and "loss" in metrics and "epoch" in metrics):
                try:
                    return not report(
                        advisor_id, trial_id, int(metrics["epoch"]) + 1,
                        metrics["loss"], min_resource=self._asha_min,
                        eta=self._asha_eta)
                except Exception:
                    logger.warning("ASHA rung report failed; continuing "
                                   "trial", exc_info=True)
            return False

        trial_logger.set_stop_check(check)

    def _retry_pending_feedback(self, advisor_id: str) -> None:
        """Flush observations whose original feedback failed (advisor
        briefly unreachable). Called before proposing and before new
        feedback so the GP sees every completed trial, in order."""
        while self._pending_feedback:
            knobs, score = self._pending_feedback[0]
            self._advisors.get(advisor_id).feedback(knobs, score)
            self._pending_feedback.pop(0)

    def _run_trial_sandboxed(
        self,
        knobs: Dict[str, Any],
        job: Dict[str, Any],
        trial_id: str,
        trial_logger: ModelLogger,
        tracer: Optional[Tracer] = None,
    ) -> tuple:
        """Sandbox path (RAFIKI_SANDBOX=1): the untrusted slice — model
        import, train, evaluate, dump — runs in a restricted child
        (sdk/sandbox.py: env scrub, cwd jail, rlimits, uid drop under
        root); this trusted side forwards its log stream to the trial
        sink, applies the same mid-trial stop checks on METRICS records,
        and persists the returned params bytes itself. The child never
        sees the store, other trials' params, or admin credentials."""
        from rafiki_tpu import config as rconfig
        from rafiki_tpu.sdk.sandbox import make_jail, run_trial_sandboxed

        tracer = tracer or Tracer(trial_id)
        os.makedirs(self._params_dir, exist_ok=True)
        os.chmod(self._params_dir, 0o700)  # owner-only: jailed uids locked out
        jail = make_jail(rconfig.WORKDIR, trial_id)
        # the logger sink writes lines to the store; stop checks ride the
        # same METRICS records as the in-process path
        stop_check = getattr(trial_logger, "_stop_check", None)
        sink = (lambda line: trial_logger._sink(line)) if \
            trial_logger._sink else (lambda line: None)
        try:
            with tracer.span("train"):
                score, params_bytes = run_trial_sandboxed(
                    self._model_bytes, self._model_class, knobs,
                    job["train_dataset_uri"], job["test_dataset_uri"],
                    jail, on_log_line=sink, stop_check=stop_check,
                    timeout_s=getattr(self, "_trial_timeout_s", None),
                    extra_pythonpath=getattr(self, "_deps_prefix", None),
                )
            # NaN/inf survives the child's float() cast and the JSON
            # pipe — gate it here so it becomes a typed INVALID_SCORE
            # fault, never a poisoned GP observation
            score = validate_score(score)
            with tracer.span("persist_params"):
                params_path = os.path.join(
                    self._params_dir, f"{trial_id}.params")
                # atomic + checksummed (sdk/artifact.py): a crash mid-write
                # or later bit rot surfaces as a typed ArtifactCorruptError
                # at download/deploy, never a deserialize traceback
                try:
                    # the child dumped and serialized: only the write is here
                    with tracer.span("persist.write"):
                        write_artifact(params_path, params_bytes,
                                       mode=0o600)
                except OSError as e:
                    # trusted-side I/O (full disk, yanked volume) — the
                    # platform's fault, never the template's knobs
                    raise faults.TrialFault(
                        f"params persist failed: {e}",
                        kind=FaultKind.INFRA) from e
            import shutil

            shutil.rmtree(jail, ignore_errors=True)
            return score, params_path
        finally:
            try:
                tracer.save()
                trial_logger.set_stop_check(None)
                trial_logger.log("trial phase breakdown", **{
                    f"trace_{k}_s": round(v, 4)
                    for k, v in tracer.summary().items()
                })
            except Exception:
                logger.exception("failed to persist trial trace")

    def _cleanup_ckpt(self, trial_id: str) -> None:
        """Drop a trial's mid-trial checkpoint once the trial reached a
        terminal state it will never resume from (ERRORED/TERMINATED —
        only RUNNING trials are ever re-run). Success-path cleanup lives in
        _run_trial."""
        for suffix in (".ckpt", ".ckpt.tmp"):
            try:
                os.remove(os.path.join(self._params_dir,
                                       f"{trial_id}{suffix}"))
            except OSError:
                pass
        # sandbox-mode trials keep their checkpoint inside the jail
        from rafiki_tpu import config as rconfig
        from rafiki_tpu.sdk.sandbox import jail_path

        jail = jail_path(rconfig.WORKDIR, trial_id)
        if os.path.isdir(jail):
            import shutil

            shutil.rmtree(jail, ignore_errors=True)

    def _run_trial(
        self,
        clazz: type,
        knobs: Dict[str, Any],
        job: Dict[str, Any],
        trial_id: str,
        trial_logger: ModelLogger,
        tracer: Optional[Tracer] = None,
    ) -> tuple:
        from rafiki_tpu.sdk.sandbox import sandbox_enabled

        if sandbox_enabled():
            return self._run_trial_sandboxed(knobs, job, trial_id,
                                             trial_logger, tracer)
        tracer = tracer or Tracer(trial_id)
        model = clazz(**knobs)
        model.logger = trial_logger
        # per-trial checkpoint slot: templates that pass it to fit() get
        # resume-from-last-epoch when a crashed worker re-runs this trial
        os.makedirs(self._params_dir, exist_ok=True)
        model.checkpoint_path = os.path.join(
            self._params_dir, f"{trial_id}.ckpt")
        try:
            try:
                with tracer.span("train"):
                    model.train(job["train_dataset_uri"])
            except StopTrialEarly:
                # templates with hand-rolled train loops surface the ASHA
                # verdict here (SDK-trainer templates never do — fit()
                # absorbs it); the truncated model still gets evaluated
                trial_logger.log("trial stopped early by scheduler")
            # the verdict is delivered; trace/trace-metric logs after this
            # must not re-raise
            trial_logger.set_stop_check(None)
            with tracer.span("evaluate"):
                # typed INVALID_SCORE fault for NaN/inf/non-numeric —
                # previously only ASHA's rung check looked at finiteness
                score = validate_score(model.evaluate(job["test_dataset_uri"]))
            with tracer.span("persist_params"):
                params_path = os.path.join(
                    self._params_dir, f"{trial_id}.params")
                persist_trusted_params(tracer, params_path,
                                       model.dump_parameters)
            # the trial is complete: its mid-trial checkpoint is dead weight
            self._cleanup_ckpt(trial_id)
            return score, params_path
        finally:
            try:
                model.destroy()
            finally:
                # diagnostics only: a trace-persistence failure must never
                # turn a successful trial into ERRORED (or mask the real
                # exception of a failed one)
                try:
                    tracer.save()
                    # the phase breakdown also lands in the trial's metric
                    # stream so the existing log/plot plumbing surfaces it
                    trial_logger.log("trial phase breakdown", **{
                        f"trace_{k}_s": round(v, 4)
                        for k, v in tracer.summary().items()
                    })
                except Exception:
                    logger.exception("failed to persist trial trace")
