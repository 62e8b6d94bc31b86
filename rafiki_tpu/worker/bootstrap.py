"""Worker-process bootstrap: `python -m rafiki_tpu.worker.bootstrap`.

The analogue of the reference's in-container entrypoint (reference
scripts/start_worker.py:15-25 dispatching on RAFIKI_SERVICE_TYPE, and
rafiki/utils/service.py:10-46 installing signal handlers and marking the
service RUNNING/ERRORED in the store). Launched by ProcessPlacementManager
with everything it needs in env:

    RAFIKI_SERVICE_ID / RAFIKI_SERVICE_TYPE   identity + dispatch
    RAFIKI_CHIP_GRANT                         the grant in the HOST's chip
                                              numbering (this process sees
                                              only those chips, as 0..n-1)
    RAFIKI_DB_PATH                            shared SQLite/WAL file
    RAFIKI_SUB_TRAIN_JOB_ID                   (TRAIN)
    RAFIKI_INFERENCE_JOB_ID, RAFIKI_TRIAL_ID  (INFERENCE)
    RAFIKI_ADMIN_ADDR                         host:port for advisor/events
    RAFIKI_BROKER_PREFIX                      shm data-plane namespace

Status protocol: RUNNING is written on ctx.ready() (startup really
succeeded), STOPPED on clean exit/SIGTERM, ERRORED on crash — rc mirrors it
so the parent's monitor can backstop a silent death.
"""

from __future__ import annotations

import logging
import os
import signal
import sys
import threading
import traceback

logger = logging.getLogger(__name__)


def _require(name: str) -> str:
    v = os.environ.get(name)
    if not v:
        raise RuntimeError(f"bootstrap: missing env {name}")
    return v


def main() -> int:
    from rafiki_tpu import config
    from rafiki_tpu.constants import ServiceType
    from rafiki_tpu.db.database import Database
    from rafiki_tpu.placement.manager import ServiceContext

    service_id = _require("RAFIKI_SERVICE_ID")
    service_type = _require("RAFIKI_SERVICE_TYPE")

    logging.basicConfig(
        level=logging.INFO,
        format=f"%(levelname)s:%(asctime)s:{service_id[:8]}:%(name)s: "
               "%(message)s",
    )

    # the parent pinned this process to its grant (placement/process.py
    # grant_env): the n granted chips are this process's devices 0..n-1,
    # whatever their numbers on the host
    grant = [c for c in os.environ.get("RAFIKI_CHIP_GRANT", "").split(",")
             if c.strip()]
    chips = list(range(len(grant)))
    db = Database(_require("RAFIKI_DB_PATH"))

    stop_event = threading.Event()

    def on_signal(signum, frame):
        logger.info("signal %s: stopping", signum)
        stop_event.set()

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)

    # Orphan watchdog: if the parent placement manager dies without managing
    # to SIGTERM us (hard kill mid-teardown, agent crash), this process must
    # not linger — an orphaned serving worker with a torn-down data plane
    # spins forever and, on a small host, starves everything else. Detected
    # by reparenting (PPID becomes init).
    #
    # Control-plane crash recovery (RAFIKI_ORPHAN_SURVIVE=1, set by an
    # ADMIN-embedded engine for TRAIN children only): the parent dying is
    # an admin crash, and THIS worker is the thing recovery adopts by pid
    # — so instead of stopping on reparent, keep working and watch the
    # shared store: exit only when the service row goes terminal (a
    # restarted admin fenced or stopped us, or we finished on our own).
    # Agent-spawned children never get the flag: an agent's death is a
    # host failure and the PR-1 reschedule must never find the old
    # executor still running.
    parent0 = os.getppid()
    survivable = (os.environ.get("RAFIKI_ORPHAN_SURVIVE") == "1"
                  and service_type == ServiceType.TRAIN)

    def watch_parent():
        orphaned = False
        while not stop_event.wait(2.0):
            if not orphaned and os.getppid() != parent0:
                if not survivable:
                    logger.warning("parent %d died; stopping", parent0)
                    stop_event.set()
                    return
                orphaned = True
                logger.warning(
                    "parent %d died; surviving for control-plane recovery "
                    "(will stop when the store says so)", parent0)
            if orphaned:
                try:
                    svc = db.get_service(service_id)
                # lint: absorb(store hiccup while orphaned: keep serving, retry next beat)
                except Exception:
                    continue  # store hiccup: keep working
                if svc is None or svc["status"] in ("STOPPED", "ERRORED"):
                    logger.warning("service row is terminal while "
                                   "orphaned; stopping")
                    stop_event.set()
                    return

    threading.Thread(target=watch_parent, name="orphan-watchdog",
                     daemon=True).start()

    def on_ready():
        devs = ctx.devices()
        logger.info(
            "ready: grant %s -> %d device(s), platform=%s kind=%s",
            ",".join(grant) or "none", len(devs), devs[0].platform,
            devs[0].device_kind)
        db.mark_service_as_running(service_id)

    ctx = ServiceContext(
        service_id=service_id,
        service_type=service_type,
        chips=chips,
        stop_event=stop_event,
        on_ready=on_ready,
    )

    admin_client = None
    addr = os.environ.get("RAFIKI_ADMIN_ADDR")
    if addr:
        from rafiki_tpu.client.client import Client

        host, port = addr.rsplit(":", 1)
        admin_client = Client(admin_host=host, admin_port=int(port))
        admin_client.login(config.SUPERADMIN_EMAIL, config.SUPERADMIN_PASSWORD)

    try:
        if service_type == ServiceType.TRAIN:
            _run_train(ctx, db, admin_client)
        elif service_type == ServiceType.INFERENCE:
            _run_inference(ctx, db, admin_client)
        else:
            raise RuntimeError(f"bootstrap: unsupported type {service_type}")
    except Exception:
        logger.error("service crashed:\n%s", traceback.format_exc())
        try:
            db.mark_service_as_errored(service_id)
        except Exception:
            logger.exception("could not mark errored")
        return 1
    db.mark_service_as_stopped(service_id)
    return 0


def _run_train(ctx, db, admin_client) -> None:
    from rafiki_tpu.worker.train import TrainWorker

    if admin_client is not None:
        from rafiki_tpu.advisor.remote import RemoteAdvisorStore

        advisors = RemoteAdvisorStore(admin_client)

        def send_event(name, payload):
            # best-effort: events are advisory (job refresh also rides
            # the service-status rows) — an admin that happens to be
            # down/restarting at this moment must not error a worker
            # that just finished its work
            try:
                admin_client.send_event(name, **payload)
            except Exception as e:
                logger.warning("event %s could not reach the admin "
                               "(%s); continuing", name, e)
    else:
        # no admin API reachable: process-local advisor (the reference's
        # uncoordinated-parallel-HPO behavior, reference train.py:213)
        from rafiki_tpu.advisor.advisor import AdvisorStore

        logger.warning("no RAFIKI_ADMIN_ADDR; HPO is process-local")
        advisors = AdvisorStore()
        send_event = lambda name, payload: None  # noqa: E731

    worker = TrainWorker(
        _require("RAFIKI_SUB_TRAIN_JOB_ID"),
        db,
        advisors,
        send_event=send_event,
    )
    worker.start(ctx)


def _run_inference(ctx, db, admin_client) -> None:
    from rafiki_tpu.cache.shm_broker import ShmBrokerClient
    from rafiki_tpu.worker.inference import InferenceWorker

    broker = ShmBrokerClient(_require("RAFIKI_BROKER_PREFIX"))
    report = None
    if admin_client is not None:
        # relay serving counters to the admin (its in-process SERVING_STATS
        # cannot see this process) for /inference_jobs/<app>/<v>/stats
        report = lambda payload: admin_client.send_event(  # noqa: E731
            "inference_worker_stats", **payload)
    trial_ids = os.environ.get("RAFIKI_TRIAL_IDS")
    worker = InferenceWorker(
        _require("RAFIKI_INFERENCE_JOB_ID"),
        _require("RAFIKI_TRIAL_ID"),
        db,
        broker,
        report_stats=report,
        # fused ensemble group (budget ENSEMBLE_FUSED)
        trial_ids=trial_ids.split(",") if trial_ids else None,
    )
    worker.start(ctx)


if __name__ == "__main__":
    sys.exit(main())
