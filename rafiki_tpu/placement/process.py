"""Process-level service placement: workers as child processes.

The reference deployed every dynamic worker as a Docker Swarm *container*
with env-var plumbing and a restart-on-failure policy (reference
rafiki/container/docker_swarm.py:122-148, scripts/start_worker.py:15-25).
`ProcessPlacementManager` is the TPU-host analogue: each service is a child
**process** launched on `python -m rafiki_tpu.worker.bootstrap` with

- its chip grant as libtpu's visible-chips variables (the analogue of
  ``CUDA_VISIBLE_DEVICES``, reference docker_swarm.py:122-126): a chip
  belongs to one process, so the child opens ONLY the chips it was granted
  and numbers them 0..n-1; ``RAFIKI_CHIP_GRANT`` carries the host's
  numbering for the record. The parent never initialises a JAX backend —
  it would hold the chips its children need (:func:`host_chip_inventory`),
- its payload ids (`sub_train_job_id` / `inference_job_id`+`trial_id`) in
  env, the way the reference forwarded ``RAFIKI_SERVICE_ID`` etc.
  (reference services_manager.py:307-318),
- the metadata store reached by every process through the same SQLite/WAL
  file, and the serving data plane through the native shm queues
  (cache/shm_broker.py) — created owner-side here at placement time, so the
  child only ever attaches,
- HPO coordination through the admin REST API (advisor/remote.py), keeping
  the shared-GP semantics across *processes*.

Restart-on-failure parity: a child exiting non-zero while not being stopped
is relaunched up to ``max_restarts`` times (reference
container_manager.py:23-25); chips are released only when the child is
actually gone.

Status protocol: the child itself marks its service RUNNING (on ready) /
STOPPED / ERRORED in the store, like the reference's in-container bootstrap
(reference utils/service.py:10-46, 94-105). The monitor thread here is the
backstop for children that die without writing (SIGKILL, interpreter
crash).
"""

from __future__ import annotations

import logging
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from rafiki_tpu import config
from rafiki_tpu.constants import ServiceStatus, ServiceType
from rafiki_tpu.placement.manager import (
    ChipAllocator,
    InsufficientChipsError,
    PlacementManager,
    ServiceContext,
    StatusFn,
)

logger = logging.getLogger(__name__)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# libtpu's per-process chip bounds for a grant of n chips of one host
_TPU_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}


def host_chip_inventory(
        setting: str = "RAFIKI_VISIBLE_DEVICES") -> List[int]:
    """This host's chip indices, for a parent that must stay off JAX: the
    comma list in ``setting`` where set, else a count read by a
    short-lived child (utils/backend_probe.py) that has exited — and
    freed the chip — before any worker starts."""
    spec = os.environ.get(setting, "").strip()
    if spec:
        return [int(s) for s in spec.split(",") if s.strip()]
    from rafiki_tpu.utils.backend_probe import probe_device_count

    n, err = probe_device_count()
    if not n:
        raise RuntimeError(
            f"could not count this host's chips ({err}); set "
            f"{setting} to the chip indices to use")
    return list(range(n))


def grant_env(chips: List[int]) -> Dict[str, str]:
    """Environment that pins a worker child to its chip grant: libtpu
    shows the child only those chips (which it then sees as devices
    0..n-1). A child with no grant is kept off the accelerator — it
    would otherwise open every chip of the host."""
    if not chips:
        return {"JAX_PLATFORMS": "cpu"}
    env = {"TPU_VISIBLE_CHIPS": ",".join(str(c) for c in chips)}
    bounds = _TPU_CHIP_BOUNDS.get(len(chips))
    if bounds:
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = bounds
        env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    return env


class _ProcRunner:
    def __init__(self, manager: "ProcessPlacementManager", ctx: ServiceContext,
                 env: Dict[str, str], log_path: str):
        self.manager = manager
        self.ctx = ctx
        self.env = env
        self.log_path = log_path
        self.proc: Optional[subprocess.Popen] = None
        self._proc_lock = threading.Lock()
        self.thread = threading.Thread(
            target=self._run, name=f"proc-svc-{ctx.service_id[:8]}",
            daemon=True)

    def _spawn(self) -> subprocess.Popen:
        os.makedirs(os.path.dirname(self.log_path), exist_ok=True)
        logf = open(self.log_path, "ab")
        try:
            proc = subprocess.Popen(
                [sys.executable, "-m", "rafiki_tpu.worker.bootstrap"],
                env=self.env, cwd=_REPO_ROOT,
                stdout=logf, stderr=subprocess.STDOUT,
                start_new_session=True,
            )
        finally:
            logf.close()  # the child holds its own fd now
        return proc

    def _run(self) -> None:
        mgr = self.manager
        try:
            restarts = 0
            rc: Optional[int] = None
            while not self.ctx.stop_event.is_set():
                with self._proc_lock:
                    self.proc = self._spawn()
                # record the child's pid so a restarted control plane can
                # adopt (or fence) it; refreshed on every restart
                if mgr.db is not None:
                    try:
                        mgr.db.update_service_pid(
                            self.ctx.service_id, self.proc.pid)
                    except Exception:
                        logger.exception("pid record failed for %s",
                                         self.ctx.service_id)
                rc = self._wait_current()
                if self.ctx.stop_event.is_set() or rc == 0:
                    break
                logger.error(
                    "service %s process exited rc=%s (log: %s)",
                    self.ctx.service_id, rc, self.log_path)
                restarts += 1
                if restarts > mgr.max_restarts:
                    self._report_final(ServiceStatus.ERRORED)
                    return
            self._report_final(
                ServiceStatus.STOPPED if (rc == 0 or rc is None)
                else ServiceStatus.ERRORED)
        finally:
            self.manager._on_runner_exit(self.ctx)

    def _wait_current(self) -> Optional[int]:
        with self._proc_lock:
            proc = self.proc
        if proc is None:
            return None
        while True:
            try:
                return proc.wait(timeout=0.5)
            except subprocess.TimeoutExpired:
                if self.ctx.stop_event.is_set():
                    return self._terminate(proc)

    def _terminate(self, proc: subprocess.Popen) -> Optional[int]:
        """SIGTERM -> child marks its own status and exits; SIGKILL after
        the grace period."""
        try:
            proc.terminate()
        except ProcessLookupError:
            return proc.poll()
        try:
            return proc.wait(timeout=self.manager.stop_grace_s)
        except subprocess.TimeoutExpired:
            logger.warning("service %s ignored SIGTERM; killing",
                           self.ctx.service_id)
            try:
                proc.kill()
            except ProcessLookupError:
                pass
            return proc.wait(timeout=5)

    def _report_final(self, status_from_rc: str) -> None:
        """Report the service's terminal status through on_status — ALWAYS,
        even when the child already wrote its own row: the orchestration
        side-effects (refresh_train_job_status etc.) live behind the
        callback, and in process mode nobody else fires them after the last
        worker exits. The child's self-written status wins over the
        rc-derived one (it knows stop-vs-crash better than the exit code)."""
        mgr = self.manager
        final = status_from_rc
        try:
            if mgr.db is not None:
                svc = mgr.db.get_service(self.ctx.service_id)
                if svc is not None and svc["status"] in (
                        ServiceStatus.STOPPED, ServiceStatus.ERRORED):
                    final = svc["status"]
            if mgr.on_status:
                mgr.on_status(self.ctx.service_id, final)
        except Exception:
            logger.exception("final status report failed for %s",
                             self.ctx.service_id)


def _pid_is_worker(pid: Optional[int],
                   service_id: Optional[str] = None) -> bool:
    """Is ``pid`` an alive rafiki worker bootstrap — and, when
    ``service_id`` is given, THE bootstrap of that exact service? Guards
    against pid reuse two ways: the cmdline must be a worker bootstrap,
    and the child's environment must carry the matching
    ``RAFIKI_SERVICE_ID`` (a recycled pid belonging to a *different*
    service's worker must never be adopted or signalled)."""
    if not pid:
        return False
    try:
        os.kill(pid, 0)
    except (OSError, ProcessLookupError):
        return False
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            if b"rafiki_tpu.worker.bootstrap" not in f.read():
                return False
        if service_id is not None:
            with open(f"/proc/{pid}/environ", "rb") as f:
                env_blob = f.read()
            return (b"RAFIKI_SERVICE_ID=" + service_id.encode()
                    ) in env_blob.split(b"\0")
        return True
    except OSError:
        # no /proc (or unreadable): cannot verify — treat as not ours
        return False


def terminate_worker_pid(pid: int, service_id: str,
                         grace_s: float) -> None:
    """Identity-pinned kill escalation for a non-child worker process:
    SIGTERM, bounded wait for exit, then SIGKILL — re-verifying
    `_pid_is_worker(pid, service_id)` before EVERY signal so a recycled
    pid is never touched. ``grace_s <= 0`` means fire-and-forget SIGTERM
    (no SIGKILL escalation: the child deserves its clean store write).
    The single copy of this escalation; the adopted-child watcher and
    the recovery fence both use it."""
    if not _pid_is_worker(pid, service_id=service_id):
        return
    try:
        os.kill(pid, signal.SIGTERM)
    except (OSError, ProcessLookupError):
        return
    if grace_s <= 0:
        return
    deadline = time.monotonic() + grace_s
    while time.monotonic() < deadline:
        if not _pid_is_worker(pid, service_id=service_id):
            return
        time.sleep(0.1)
    if _pid_is_worker(pid, service_id=service_id):
        logger.warning("worker %s (pid %d) ignored SIGTERM; killing",
                       service_id[:8], pid)
        try:
            os.kill(pid, signal.SIGKILL)
        except (OSError, ProcessLookupError):
            pass


class _AdoptedRunner:
    """Watcher over a child that SURVIVED a control-plane restart (the
    bootstrap's start_new_session keeps workers alive when the admin
    dies). Mirrors _ProcRunner's contract — stop_event -> SIGTERM ->
    SIGKILL, terminal status reported through on_status (the child's
    self-written DB row wins) — without owning a Popen handle."""

    def __init__(self, manager: "ProcessPlacementManager",
                 ctx: ServiceContext, pid: int):
        self.manager = manager
        self.ctx = ctx
        self.pid = pid
        self.proc = None  # list_services reads .proc on spawned runners
        self.thread = threading.Thread(
            target=self._run, name=f"adopted-svc-{ctx.service_id[:8]}",
            daemon=True)

    def _alive(self) -> bool:
        # identity-verified, not just kill(pid, 0): this runner cannot
        # reap its non-child, so the pid CAN be recycled under it — a
        # recycled pid (different process) must read as "our worker is
        # gone", and must never be signalled
        return _pid_is_worker(self.pid, service_id=self.ctx.service_id)

    def _run(self) -> None:
        mgr = self.manager
        try:
            while self._alive():
                if self.ctx.stop_event.wait(0.5):
                    self._terminate()
                    break
            # the child writes its own terminal row; rc is unknowable
            # here, so default to STOPPED and let the row override
            self._report_final()
        finally:
            mgr._on_runner_exit(self.ctx)

    def _terminate(self) -> None:
        terminate_worker_pid(self.pid, self.ctx.service_id,
                             self.manager.stop_grace_s)

    def _report_final(self) -> None:
        mgr = self.manager
        final = ServiceStatus.STOPPED
        try:
            if mgr.db is not None:
                svc = mgr.db.get_service(self.ctx.service_id)
                if svc is not None and svc["status"] in (
                        ServiceStatus.STOPPED, ServiceStatus.ERRORED):
                    final = svc["status"]
                elif not self.ctx.stop_event.is_set():
                    # died on its own without writing (SIGKILL): backstop
                    final = ServiceStatus.ERRORED
            if mgr.on_status:
                mgr.on_status(self.ctx.service_id, final)
        except Exception:
            logger.exception("final status report failed for adopted %s",
                             self.ctx.service_id)


class ProcessPlacementManager(PlacementManager):
    """Places services as child processes on this host.

    Requirements: a file-backed store (``db.path`` != ':memory:') shared via
    SQLite WAL, and for serving, a `ShmBroker` whose segments the children
    attach to. ``admin_addr`` (host, port) of a running AdminServer enables
    cross-process HPO coordination; without it train workers fall back to a
    process-local advisor (the reference's uncoordinated-parallel-HPO
    behavior) with a warning.
    """

    def __init__(
        self,
        db=None,
        broker=None,
        admin_addr: Optional[tuple] = None,
        allocator: Optional[ChipAllocator] = None,
        on_status: Optional[StatusFn] = None,
        max_restarts: int = 3,
        stop_grace_s: float = 15.0,
        orphan_survivable: bool = False,
    ):
        """``orphan_survivable``: set by an ADMIN-embedded engine (single-
        host process placement) so its TRAIN children outlive a control-
        plane crash and can be adopted by pid on restart (the orphan
        watchdog then exits on a terminal store row instead of on
        reparenting — worker/bootstrap.py). Agent-embedded engines keep
        the default: an agent's death is a HOST failure, and its children
        must die fast so the PR-1 reschedule never double-runs a service
        id."""
        self.db = db
        self.broker = broker
        self.admin_addr = admin_addr
        if allocator is None:
            # never ChipAllocator(None) here: that asks jax.devices() in
            # THIS process, which then holds the chips every child needs
            raise TypeError(
                "ProcessPlacementManager needs an explicit inventory: "
                "allocator=ChipAllocator(host_chip_inventory())")
        self.allocator = allocator
        self.on_status = on_status
        self.max_restarts = max_restarts
        self.stop_grace_s = stop_grace_s
        self.orphan_survivable = orphan_survivable
        self._lock = threading.Lock()
        self._runners: Dict[str, _ProcRunner] = {}
        # runners detached by destroy_service(wait=False) whose children
        # may still be in the SIGTERM->SIGKILL grace window; stop_all()
        # must wait these out — otherwise an exiting admin kills its own
        # daemon monitor threads mid-escalation and orphans a child that
        # ignored SIGTERM (e.g. one stuck inside a long XLA dispatch)
        self._dying: List[_ProcRunner] = []

    # -- PlacementManager --------------------------------------------------

    def create_service(
        self,
        service_id: str,
        service_type: str,
        run_fn=None,  # declarative launch: the payload travels in `extra`
        n_chips: int = 0,
        extra: Optional[Dict[str, Any]] = None,
        best_effort_chips: bool = False,
    ) -> ServiceContext:
        if self.db is None or self.db.path == ":memory:":
            raise RuntimeError(
                "ProcessPlacementManager needs a file-backed Database "
                "(children open the same SQLite/WAL file)")
        extra = dict(extra or {})
        try:
            chips = self.allocator.allocate(n_chips) if n_chips > 0 else []
        except InsufficientChipsError:
            if not best_effort_chips:
                raise
            chips = []
        ctx = ServiceContext(
            service_id=service_id,
            service_type=service_type,
            chips=chips,
            stop_event=threading.Event(),
            extra=extra,
        )
        try:
            env = self._child_env(ctx)
        except Exception:
            self.allocator.release(chips)
            raise
        if service_type == ServiceType.INFERENCE and self.broker is not None:
            # owner-side data-plane provisioning: create the query segment
            # now so the child (and the predictor fan-out) can attach
            self.broker.register_worker(extra["inference_job_id"], service_id)
        log_path = os.path.join(
            config.LOGS_DIR, f"service-{service_id}.log")
        runner = _ProcRunner(self, ctx, env, log_path)
        with self._lock:
            self._runners[service_id] = runner
        runner.thread.start()
        return ctx

    def adopt_pid(self, service_id: str, service_type: str, pid: int,
                  extra: Optional[Dict[str, Any]] = None,
                  chips: Optional[List[int]] = None) -> bool:
        """Adopt a worker child that survived a control-plane restart
        (its service row carries the pid): verify it is alive AND one of
        ours, reclaim its chip grant, and watch it exactly like a spawned
        child — destroy_service/stop_all SIGTERM it, its exit fires
        on_status with the row it wrote itself. Returns False when the
        pid is gone or unverifiable (caller respawns or errors)."""
        if not _pid_is_worker(pid, service_id=service_id):
            return False
        chips = list(chips or [])
        self.allocator.claim(chips)
        ctx = ServiceContext(
            service_id=service_id,
            service_type=service_type,
            chips=chips,
            stop_event=threading.Event(),
            extra=dict(extra or {}),
        )
        runner = _AdoptedRunner(self, ctx, pid)
        with self._lock:
            self._runners[service_id] = runner
        runner.thread.start()
        logger.info("adopted surviving worker %s (pid %d)",
                    service_id[:8], pid)
        return True

    def list_services(self) -> List[Dict[str, Any]]:
        """This host's LIVE executors, for the restart-reconciliation
        inventory (placement/agent.py GET /inventory). Finished runners
        already wrote their terminal rows and are not running-set."""
        with self._lock:
            runners = dict(self._runners)
        out = []
        for sid, r in runners.items():
            if not r.thread.is_alive():
                continue
            proc = getattr(r, "proc", None)
            out.append({
                "service_id": sid,
                "service_type": r.ctx.service_type,
                "status": "RUNNING",
                "chips": list(r.ctx.chips),
                "pid": (proc.pid if proc is not None
                        else getattr(r, "pid", None)),
            })
        return out

    def destroy_service(self, service_id: str, wait: bool = True) -> None:
        with self._lock:
            runner = self._runners.pop(service_id, None)
            # only track runners whose monitor thread still runs: appending
            # an already-finished runner would leak it (its _on_runner_exit
            # has already fired and won't prune it again)
            if runner is not None and runner.thread.is_alive():
                self._dying.append(runner)
        if runner is None:
            return  # tolerate concurrent deletion
        runner.ctx.stop_event.set()
        if wait:
            runner.thread.join(timeout=self.stop_grace_s + 10)
        if (self.broker is not None
                and runner.ctx.service_type == ServiceType.INFERENCE):
            job_id = runner.ctx.extra.get("inference_job_id")
            if job_id:
                try:
                    self.broker.unregister_worker(job_id, service_id)
                except Exception:
                    logger.exception("broker unregister failed for %s",
                                     service_id)

    def stop_all(self) -> None:
        with self._lock:
            ids = list(self._runners)
        for sid in ids:
            self.destroy_service(sid)
        # reap runners detached earlier with wait=False: their monitor
        # threads may still be escalating SIGTERM->SIGKILL, and the caller
        # (admin shutdown) exits right after this returns
        with self._lock:
            dying = list(self._dying)
        for runner in dying:
            runner.thread.join(timeout=self.stop_grace_s + 10)
        with self._lock:
            # sweep entries whose exit raced the is_alive() append guard
            self._dying = [r for r in self._dying if r.thread.is_alive()]

    # -- internals ---------------------------------------------------------

    def _on_runner_exit(self, ctx: ServiceContext) -> None:
        self.allocator.release(ctx.chips)
        with self._lock:
            self._dying = [r for r in self._dying
                           if r.ctx.service_id != ctx.service_id]

    def _child_env(self, ctx: ServiceContext) -> Dict[str, str]:
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (_REPO_ROOT, env.get("PYTHONPATH")) if p)
        env["RAFIKI_SERVICE_ID"] = ctx.service_id
        env["RAFIKI_SERVICE_TYPE"] = ctx.service_type
        # the store may be a postgresql:// URL (multi-host control plane);
        # only filesystem paths get absolutized
        db_ref = self.db.path
        env["RAFIKI_DB_PATH"] = (
            db_ref if "://" in db_ref else os.path.abspath(db_ref))
        env["RAFIKI_WORKDIR"] = config.WORKDIR
        env["RAFIKI_CHIP_GRANT"] = ",".join(str(c) for c in ctx.chips)
        env.update(grant_env(ctx.chips))
        # the process-wide fallback must not fight the explicit grant
        env.pop("RAFIKI_VISIBLE_DEVICES", None)
        if self.admin_addr is not None:
            env["RAFIKI_ADMIN_ADDR"] = f"{self.admin_addr[0]}:{self.admin_addr[1]}"
        if ctx.service_type == ServiceType.TRAIN:
            env["RAFIKI_SUB_TRAIN_JOB_ID"] = ctx.extra["sub_train_job_id"]
            if self.orphan_survivable:
                # control-plane crash recovery: this TRAIN child should
                # outlive its admin parent and be adopted by pid on
                # restart (INFERENCE children never survive — their shm
                # data plane dies with the parent)
                env["RAFIKI_ORPHAN_SURVIVE"] = "1"
        elif ctx.service_type == ServiceType.INFERENCE:
            env["RAFIKI_INFERENCE_JOB_ID"] = ctx.extra["inference_job_id"]
            env["RAFIKI_TRIAL_ID"] = ctx.extra["trial_id"]
            if ctx.extra.get("trial_ids"):
                # fused ensemble group (budget ENSEMBLE_FUSED)
                env["RAFIKI_TRIAL_IDS"] = ",".join(ctx.extra["trial_ids"])
            # a broker without an shm namespace reports prefix=None
            # (e.g. FleetBroker over the in-process broker) — treat it
            # the same as no broker at all, with an explicit error
            prefix = getattr(self.broker, "prefix", None)
            if prefix is None:
                raise RuntimeError(
                    "process-mode inference needs the shm broker "
                    "(RAFIKI_BROKER=shm) so worker processes can attach "
                    "to the serving data plane")
            env["RAFIKI_BROKER_PREFIX"] = prefix
        else:
            raise ValueError(
                f"unsupported process service type {ctx.service_type!r}")
        return env
