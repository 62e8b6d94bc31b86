"""Deployment health check: ``python -m rafiki_tpu.doctor``.

One bounded pass over everything a rafiki_tpu deployment depends on,
printing a PASS/WARN/FAIL line per check and exiting non-zero on FAIL.
The accelerator check counts devices in a child under a timeout
(utils/backend_probe.py): this command never opens the chip itself. The
child takes the chip for its lifetime, so run the doctor BEFORE the
stack starts — while a worker of this host holds the chip the check
reports the backend's own "already in use" error as a WARN.

The reference's closest analogue was docker/compose healthchecks plus
reading container logs; a process-native stack gets a first-class
doctor instead.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
from typing import Any, Callable, Dict, List, Optional, Tuple

PASS, WARN, FAIL = "PASS", "WARN", "FAIL"

Check = Tuple[str, str, str]  # (name, status, detail)


def check_backend(timeout_s: float = 60.0) -> Check:
    from rafiki_tpu.utils.backend_probe import probe_device_count

    n, err = probe_device_count(timeout_s=timeout_s)
    if n >= 1:
        return ("accelerator", PASS, f"{n} device(s) visible")
    return ("accelerator", WARN,
            f"no accelerator could be opened ({err}) — nothing falls back "
            "to the CPU: workers granted a chip will fail to start (a "
            "worker of this host already holding the chip also reads so)")


def check_workdir() -> Check:
    from rafiki_tpu import config

    wd = config.WORKDIR
    try:
        os.makedirs(wd, exist_ok=True)
        probe = tempfile.NamedTemporaryFile(dir=wd, delete=True)
        probe.close()
    except OSError as e:
        return ("workdir", FAIL, f"{wd} not writable: {e}")
    return ("workdir", PASS, wd)


def check_store() -> Check:
    from rafiki_tpu import config
    from rafiki_tpu.db.database import Database

    target = str(config.DB_PATH)
    try:
        if target.startswith(("postgresql://", "postgres://")):
            db = Database(target)  # connects (or raises) against the server
            label = target
        elif os.path.exists(target):
            # exercise the REAL store the server will open (same WAL
            # sidecar behavior the server has) — a corrupt or
            # wrong-owner file must fail here, not at boot
            db = Database(target)
            label = target
        else:
            db = Database(":memory:")  # engine sanity; store not created yet
            label = f"{target} (not created yet; embedded engine ok)"
        db.get_users()
        db.close()
    # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
    except Exception as e:
        return ("metadata store", FAIL, f"{target}: {type(e).__name__}: {e}")
    return ("metadata store", PASS, label)


def check_shm_broker() -> Check:
    """Native build, configured ring size, and which wire format shm
    traffic will actually ride — an operator who set RAFIKI_BROKER=shm
    for the binary data plane must SEE it when framing silently fell
    back to JSON (kill-switch, or a mixed-version fleet)."""
    shm_selected = os.environ.get("RAFIKI_BROKER") == "shm"
    try:
        from rafiki_tpu.cache import wire
        from rafiki_tpu.native.shm_queue import available, default_capacity

        if not available():
            if shm_selected:
                return ("shm data plane", WARN,
                        "RAFIKI_BROKER=shm but the native shmqueue did "
                        "not build — falling back to the in-process "
                        "broker (process placement/serving agents need "
                        "the native library)")
            return ("shm data plane", WARN,
                    "native shmqueue unavailable — in-process broker only "
                    "(process placement/serving agents need it)")
        from rafiki_tpu import config

        ring = default_capacity()
        if not wire.binary_enabled():
            return ("shm data plane", WARN,
                    f"binary wire framing DISABLED (RAFIKI_WIRE_BINARY=0): "
                    f"shm/relay traffic rides JSON float text — ~an order "
                    f"of magnitude more serialization CPU per dense query; "
                    f"re-enable once every peer speaks wire v{wire.VERSION} "
                    f"(ring {ring} B)")
        if ring < 4 * (1 << 20) and int(config.PREDICT_QUEUE_DEPTH) > 0:
            detail = (f"native queue library loads; ring {ring} B "
                      f"(RAFIKI_SHM_RING_BYTES), binary wire v{wire.VERSION}"
                      " — batched binary frames are larger than per-query "
                      "JSON; watch ring_used_bytes_hw in serving stats")
        else:
            detail = (f"native queue library loads; ring {ring} B "
                      f"(RAFIKI_SHM_RING_BYTES), binary wire "
                      f"v{wire.VERSION}")
    # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
    except Exception as e:
        return ("shm data plane", WARN, f"{type(e).__name__}: {e}")
    return ("shm data plane", PASS, detail)


def check_sandbox() -> Check:
    from rafiki_tpu.sdk.sandbox import (_uid_range, sandbox_enabled,
                                        sandbox_gid, uid_for_jail)

    if not sandbox_enabled():
        return ("model sandbox", WARN,
                "RAFIKI_SANDBOX unset — uploaded model code runs with "
                "worker privileges")
    if uid_for_jail("doctor-probe") is None:
        return ("model sandbox", WARN,
                "enabled, but worker is not root: uid-drop layer inactive "
                "(env scrub + jail + rlimits still apply)")
    gid = sandbox_gid()
    note = " (gid 0 RETAINED — RAFIKI_SANDBOX_KEEP_GID0)" if gid == 0 else ""
    if _uid_range()[1] <= 0:
        return ("model sandbox", WARN,
                "enabled, but RAFIKI_SANDBOX_UID_RANGE=0: ONE shared "
                "sandbox uid — concurrent trials are not isolated from "
                f"each other, gid {gid}{note}")
    return ("model sandbox", PASS,
            f"enabled, per-trial uid drop, gid {gid}{note}")


def check_chaos() -> Check:
    from rafiki_tpu.utils import chaos

    if not os.environ.get(chaos.ENV_VAR):
        return ("fault injection", PASS, "off (RAFIKI_CHAOS unset)")
    if chaos.enabled():
        # loud on purpose: chaos left on after a failover drill makes a
        # healthy fleet look like it's dying
        return ("fault injection", WARN,
                f"RAFIKI_CHAOS is ACTIVE: "
                f"{os.environ[chaos.ENV_VAR]!r} — requests are being "
                "dropped/delayed/errored on schedule")
    return ("fault injection", WARN,
            f"RAFIKI_CHAOS set but unparseable (ignored): "
            f"{os.environ[chaos.ENV_VAR]!r}")


def check_overload_knobs() -> Check:
    """Serving-plane overload control (docs/failure-model.md "Overload
    faults"): the knobs must describe a coherent pipeline — a queue cap
    below the batch size silently caps batch occupancy, and an uncapped
    queue plus an uncapped door disables shedding entirely."""
    from rafiki_tpu import config

    depth = int(config.PREDICT_QUEUE_DEPTH)
    inflight = int(config.PREDICT_MAX_INFLIGHT)
    hedge = int(config.PREDICT_HEDGE_SUPPRESS_DEPTH)
    batch = int(config.PREDICT_MAX_BATCH_SIZE)
    if 0 < depth < batch:
        # serving still works (take_batch dispatches whatever is queued);
        # batches just can't reach max occupancy, and single requests
        # above the cap are refused outright
        return ("overload control", WARN,
                f"RAFIKI_PREDICT_QUEUE_DEPTH={depth} is below "
                f"PREDICT_MAX_BATCH_SIZE={batch}: batches cap at {depth} "
                f"queries and requests above {depth} queries are refused "
                "— intended?")
    if depth <= 0 and inflight <= 0:
        return ("overload control", WARN,
                "queue depth AND in-flight caps disabled "
                "(RAFIKI_PREDICT_QUEUE_DEPTH=0, "
                "RAFIKI_PREDICT_MAX_INFLIGHT=0): overload will queue "
                "unboundedly instead of shedding 429/503")
    detail = (f"queue depth {depth or 'uncapped'}, in-flight "
              f"{inflight or 'uncapped'}, hedge suppression at "
              f"{hedge or 'off'}")
    return ("overload control", PASS, detail)


def check_recovery() -> Check:
    """Control-plane crash recovery (docs/failure-model.md): flag
    non-terminal jobs with zero live services — the signature of a dead
    admin that has not been restarted to reconcile them — report the last
    reconcile outcome/duration, and WARN when the RAFIKI_RECOVER_* knobs
    disable adoption (restarts will fence surviving workers instead)."""
    from rafiki_tpu import config

    notes = []
    if not config.RECOVER_ADOPT:
        notes.append("RAFIKI_RECOVER_ADOPT=0: restarts FENCE surviving "
                     "workers instead of adopting them")
    # last reconcile outcome, persisted by admin/recovery.py
    last = None
    try:
        from rafiki_tpu.admin.recovery import report_path

        with open(report_path()) as f:
            last = json.load(f)
    except (OSError, ValueError):
        pass
    failed = bool(last and last.get("failed"))
    if last is not None:
        notes.append(
            f"last reconcile{' ABORTED' if failed else ''}: "
            f"{last.get('duration_s', '?')}s — "
            f"{last.get('adopted', 0)} adopted, "
            f"{last.get('rescheduled', 0)} rescheduled, "
            f"{last.get('fenced', 0)} fenced, "
            f"{last.get('errored', 0)} errored"
            + (f" ({last.get('error')})" if failed else ""))
    target = str(config.DB_PATH)
    orphaned = 0
    is_url = target.startswith(("postgresql://", "postgres://"))
    if is_url or os.path.exists(target):
        try:
            from rafiki_tpu.db.database import Database

            import time as _time

            # only jobs older than a deploy takes: a LIVE admin mid-deploy
            # legitimately has a STARTED job whose worker rows don't exist
            # yet, and that must not read as "restart your healthy admin"
            min_age_s = 120.0
            now = _time.time()
            db = Database(target)
            try:
                jobs = db.get_train_jobs_by_statuses(
                    ["STARTED", "RUNNING"])
                inf_jobs = db.get_inference_jobs_by_statuses(
                    ["STARTED", "RUNNING"])
                live_services = {
                    s["id"] for s in db.get_services(
                        statuses=["STARTED", "DEPLOYING", "RUNNING"])}
                for j in jobs + inf_jobs:
                    if now - (j.get("datetime_started") or now) < min_age_s:
                        continue
                    get_workers = (
                        db.get_workers_of_train_job
                        if "app" in j else db.get_workers_of_inference_job)
                    sids = {w["service_id"] for w in get_workers(j["id"])}
                    if not (sids & live_services):
                        orphaned += 1
            finally:
                db.close()
        # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
        except Exception as e:
            return ("crash recovery", WARN,
                    f"could not scan {target}: {type(e).__name__}: {e}")
    if orphaned:
        notes.insert(0, f"{orphaned} non-terminal job(s) with ZERO live "
                        "services — orphaned by a dead admin; restarting "
                        "the admin reconciles them (adopt/reschedule/"
                        "fence)")
        return ("crash recovery", WARN, "; ".join(notes))
    if failed or not config.RECOVER_ADOPT:
        return ("crash recovery", WARN, "; ".join(notes))
    return ("crash recovery", PASS,
            "; ".join(notes) if notes else
            "no orphaned jobs; adoption enabled")


def check_rollouts() -> Check:
    """Safe live rollouts (docs/failure-model.md "Rollout faults"): WARN
    on service rows stuck in DEPLOYING longer than
    SERVICE_DEPLOY_TIMEOUT_S — a wedged placement nothing is waiting on
    (the deploy path marks rows DEPLOYING while it waits; a live admin's
    wait either resolves them or tears them down inside the timeout) —
    and on rolled-back rollouts no operator has acknowledged (a rollback
    is the platform saying a version was bad; somebody should look
    before the next update ships the same regression)."""
    from rafiki_tpu import config
    from rafiki_tpu.constants import RolloutPhase

    notes = []
    warn = False
    live_rollouts = 0
    target = str(config.DB_PATH)
    is_url = target.startswith(("postgresql://", "postgres://"))
    if is_url or os.path.exists(target):
        try:
            import time as _time

            from rafiki_tpu.db.database import Database

            timeout_s = float(config.SERVICE_DEPLOY_TIMEOUT_S)
            now = _time.time()
            db = Database(target)
            try:
                wedged = [
                    s for s in db.get_services(status="DEPLOYING")
                    if now - (s.get("datetime_started") or now) > timeout_s]
                if wedged:
                    warn = True
                    notes.append(
                        f"{len(wedged)} service row(s) stuck in DEPLOYING "
                        f"longer than SERVICE_DEPLOY_TIMEOUT_S="
                        f"{timeout_s:g}s: "
                        + ", ".join(s["id"][:8] for s in wedged[:5])
                        + (" …" if len(wedged) > 5 else "")
                        + " — a wedged deploy; restarting the admin "
                        "reconciles them")
                unacked = [
                    r for r in db.get_rollouts_by_phases(
                        [RolloutPhase.ROLLED_BACK])
                    if not r["operator_ack"]]
                if unacked:
                    warn = True
                    notes.append(
                        f"{len(unacked)} rolled-back rollout(s) with no "
                        "operator ack: "
                        + "; ".join(
                            f"job {r['inference_job_id'][:8]} "
                            f"({(r.get('reason') or 'no reason')[:60]})"
                            for r in unacked[:3])
                        + (" …" if len(unacked) > 3 else "")
                        + " — review, then POST .../rollout/ack "
                        "(Client.ack_rollout)")
                live_rollouts = len(db.get_rollouts_by_phases(
                    list(RolloutPhase.LIVE)))
            finally:
                db.close()
        # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
        except Exception as e:
            return ("rollouts", WARN,
                    f"could not scan {target}: {type(e).__name__}: {e}")
    if warn:
        return ("rollouts", WARN, "; ".join(notes))
    detail = (f"no wedged deploys, no unacked rollbacks; "
              f"{live_rollouts} rollout(s) in flight, canary fraction "
              f"{float(config.ROLLOUT_CANARY_FRACTION):g}, judge window "
              f"{float(config.ROLLOUT_JUDGE_WINDOW_S):g}s")
    return ("rollouts", PASS, detail)


def check_drift() -> Check:
    """The drift closed loop (docs/failure-model.md "Model drift
    faults"): WARN when the loop is enabled but can't work — no digest
    stream will flow (metrics disabled hides the loop entirely; a
    WATCHING row that never froze a baseline means no samples reach the
    monitor), retrain budget 0 (monitor-only: verdicts fire, nothing is
    ever retrained), or a baseline window shorter than the monitor
    window (the reference population is a subset of every comparison
    window, so novelty can never clear the threshold) — and on loop
    rows that need an operator: a PARKED loop waiting for an ack, or
    ≥2 consecutive auto-retrained candidates rolled back (the loop is
    flapping — raise RAFIKI_DRIFT_COOLDOWN_S or fix the training
    signal)."""
    from rafiki_tpu import config
    from rafiki_tpu.constants import DriftPhase
    from rafiki_tpu.utils import metrics as _metrics

    enabled = bool(config.DRIFT)
    notes = []
    warn = False
    if enabled:
        if not _metrics.metrics_enabled():
            warn = True
            notes.append(
                "RAFIKI_DRIFT=1 with RAFIKI_METRICS=0: the loop runs "
                "but every rafiki_drift_* signal is a no-op — its "
                "verdicts and retrains are invisible to operators")
        if int(config.DRIFT_RETRAIN_BUDGET) <= 0:
            warn = True
            notes.append(
                "RAFIKI_DRIFT_RETRAIN_BUDGET<=0: monitor-only mode — "
                "drift events fire but nothing is ever retrained; set "
                "a positive trial budget to close the loop")
        if float(config.DRIFT_BASELINE_WINDOW_S) \
                < float(config.DRIFT_WINDOW_S):
            warn = True
            notes.append(
                f"RAFIKI_DRIFT_BASELINE_WINDOW_S="
                f"{float(config.DRIFT_BASELINE_WINDOW_S):g} < "
                f"RAFIKI_DRIFT_WINDOW_S={float(config.DRIFT_WINDOW_S):g}"
                ": the frozen baseline samples a shorter horizon than "
                "every window it judges — novelty verdicts will be "
                "noise; make the baseline window at least the monitor "
                "window")
    target = str(config.DB_PATH)
    is_url = target.startswith(("postgresql://", "postgres://"))
    stale_watch = 0
    if is_url or os.path.exists(target):
        try:
            import time as _time

            from rafiki_tpu.db.database import Database

            now = _time.time()
            db = Database(target)
            try:
                rows = db.get_drift_states()
                parked = [r for r in rows
                          if r["phase"] == DriftPhase.PARKED
                          and not r["operator_ack"]]
                if parked:
                    warn = True
                    notes.append(
                        f"{len(parked)} drift loop(s) PARKED with no "
                        "operator ack: "
                        + "; ".join(
                            f"job {r['inference_job_id'][:8]} "
                            f"({(r.get('reason') or 'no reason')[:60]})"
                            for r in parked[:3])
                        + (" …" if len(parked) > 3 else "")
                        + " — review, then POST .../drift/ack "
                        "(Client.ack_drift)")
                flapping = [r for r in rows
                            if int(r.get("consecutive_rollbacks") or 0)
                            >= 2]
                if flapping:
                    warn = True
                    notes.append(
                        f"{len(flapping)} drift loop(s) with >=2 "
                        "consecutive auto-retrained candidates rolled "
                        "back: "
                        + ", ".join(f"job {r['inference_job_id'][:8]} "
                                    f"(x{r['consecutive_rollbacks']})"
                                    for r in flapping[:3])
                        + " — the loop is flapping; raise "
                        "RAFIKI_DRIFT_COOLDOWN_S (backoff already "
                        "doubles per rollback) or fix the training "
                        "signal, then .../drift/ack to clear")
                if enabled:
                    # a WATCHING row much older than the baseline window
                    # that never froze a baseline: the monitor sees no
                    # digest stream from that job's serving plane
                    horizon = max(
                        float(config.DRIFT_BASELINE_WINDOW_S),
                        float(config.DRIFT_INTERVAL_S)) * 10
                    stale_watch = sum(
                        1 for r in rows
                        if r["phase"] == DriftPhase.WATCHING
                        and r.get("baseline") is None
                        and now - float(r.get("datetime_updated") or now)
                        > horizon)
                    if stale_watch:
                        warn = True
                        notes.append(
                            f"{stale_watch} WATCHING loop(s) never froze "
                            "a baseline: no digest stream is flowing "
                            "from the serving plane (job idle, or the "
                            "admin restarted without RAFIKI_DRIFT=1)")
            finally:
                db.close()
        # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
        except Exception as e:
            return ("drift loop", WARN,
                    f"could not scan {target}: {type(e).__name__}: {e}")
    if warn:
        return ("drift loop", WARN, "; ".join(notes))
    if not enabled:
        return ("drift loop", PASS,
                "disabled (RAFIKI_DRIFT=0); no parked or flapping loop "
                "rows")
    return ("drift loop", PASS,
            f"enabled: window {float(config.DRIFT_WINDOW_S):g}s, budget "
            f"{int(config.DRIFT_RETRAIN_BUDGET)} trial(s), cooldown "
            f"{float(config.DRIFT_COOLDOWN_S):g}s")


def check_trial_faults() -> Check:
    """Training-plane fault tolerance (docs/failure-model.md,
    "Training-plane faults"): WARN when infra-retry is disabled
    (RAFIKI_TRIAL_RETRY_MAX=0 — every transient fault burns a budget
    slot), when a live job's recent trials are mostly ERRORED (the
    signature of a broken template or a sick host), and list poison-knob
    signatures with enough recorded user-class faults to be quarantined
    (grouped by exact knob JSON here — the store scan has no knob
    config, so this is the conservative subset of the worker's
    unit-cube quarantine)."""
    from rafiki_tpu import config

    notes = []
    retry_disabled = int(config.TRIAL_RETRY_MAX) <= 0
    if retry_disabled:
        notes.append("RAFIKI_TRIAL_RETRY_MAX=0: transient INFRA/MEM/"
                     "STALL faults will NOT be retried — each burns a "
                     "budget slot")
    target = str(config.DB_PATH)
    is_url = target.startswith(("postgresql://", "postgres://"))
    hot_jobs = 0
    quarantined = []
    if is_url or os.path.exists(target):
        try:
            import time as _time

            from rafiki_tpu.db.database import Database
            from rafiki_tpu.worker.faults import quarantined_signatures

            recent_s = 3600.0
            now = _time.time()
            db = Database(target)
            try:
                for j in db.get_train_jobs_by_statuses(
                        ["STARTED", "RUNNING"]):
                    trials = db.get_trials_of_train_job(j["id"])
                    recent = [t for t in trials
                              if now - (t.get("datetime_started") or now)
                              < recent_s]
                    errored = [t for t in recent
                               if t["status"] == "ERRORED"]
                    if len(recent) >= 3 and \
                            len(errored) / len(recent) > 0.5:
                        hot_jobs += 1
                        kinds = db.get_trial_fault_counts_of_train_job(
                            j["id"])
                        notes.append(
                            f"job {j['id'][:8]}: {len(errored)}/"
                            f"{len(recent)} recent trials ERRORED "
                            f"(fault kinds: {kinds or 'unrecorded'})")
                    q = quarantined_signatures(
                        trials, None,
                        int(config.TRIAL_QUARANTINE_K))
                    quarantined.extend(
                        f"job {j['id'][:8]}: {sig} x{n}"
                        for sig, n in q.items())
            finally:
                db.close()
        # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
        except Exception as e:
            return ("trial faults", WARN,
                    f"could not scan {target}: {type(e).__name__}: {e}")
    if quarantined:
        notes.append("quarantined knob signatures: "
                     + "; ".join(quarantined[:5])
                     + (" …" if len(quarantined) > 5 else ""))
    if hot_jobs or retry_disabled:
        return ("trial faults", WARN, "; ".join(notes))
    detail = (f"retry up to {int(config.TRIAL_RETRY_MAX)} per trial, "
              f"quarantine at {int(config.TRIAL_QUARANTINE_K)} faults, "
              f"job fail-fast at {int(config.TRIAL_FAULT_LIMIT) or 'off'}")
    if quarantined:
        return ("trial faults", PASS, detail + "; " + notes[-1])
    return ("trial faults", PASS, detail)


def check_vectorized_trials() -> Check:
    """Vectorized trial execution (docs/performance.md, "Vectorized
    trial execution"): WARN when the operator explicitly enabled
    population mode (RAFIKI_TRIAL_VMAP=1) but a live train job's
    template advertises no population capability — the worker silently
    falls back to scalar trials, and "enabled but not engaging" is
    exactly the state an operator cannot see from throughput alone. Also
    WARN when K exceeds the per-chip memory heuristic (stacked params +
    optimizer state scale linearly with K) or is too small to ever
    vectorize. The capability probe is the static analyzer's verdict on
    the uploaded template bytes (analysis/template.py — AST passes, no
    untrusted code runs inside doctor; this replaced the r8 regex-grade
    ``b"population_spec" in bytes`` source sniff)."""
    from rafiki_tpu import config

    notes = []
    warn = False
    enabled = bool(config.TRIAL_VMAP)
    k = int(config.TRIAL_VMAP_K)
    explicit = os.environ.get("RAFIKI_TRIAL_VMAP") == "1"
    k_warn = int(os.environ.get("RAFIKI_TRIAL_VMAP_K_WARN", "16"))
    if enabled and k < 2:
        warn = True
        notes.append(
            f"RAFIKI_TRIAL_VMAP_K={k} < 2: the vectorized path can never "
            "engage — every 'batch' is one trial")
    if enabled and k > k_warn:
        warn = True
        notes.append(
            f"RAFIKI_TRIAL_VMAP_K={k} exceeds the per-chip memory "
            f"heuristic ({k_warn}): K stacked (params + opt state) copies "
            "must fit HBM next to the replicated dataset — expect OOM-"
            "classed faults (templates additionally cap via "
            "PopulationSpec.max_members)")
    if explicit:
        target = str(config.DB_PATH)
        is_url = target.startswith(("postgresql://", "postgres://"))
        if is_url or os.path.exists(target):
            try:
                from rafiki_tpu.db.database import Database

                db = Database(target)
                try:
                    from rafiki_tpu.analysis import (
                        static_population_capability)

                    incapable = []
                    for j in db.get_train_jobs_by_statuses(
                            ["STARTED", "RUNNING"]):
                        for sub in db.get_sub_train_jobs_of_train_job(
                                j["id"]):
                            m = db.get_model(sub["model_id"])
                            if m and static_population_capability(
                                    m.get("model_file_bytes") or b"",
                                    m.get("model_class")) is None:
                                incapable.append(
                                    f"job {j['id'][:8]}/"
                                    f"{m.get('name', '?')}")
                    if incapable:
                        warn = True
                        notes.append(
                            "RAFIKI_TRIAL_VMAP=1 but these live jobs' "
                            "templates advertise no population capability "
                            "(silent scalar fallback): "
                            + "; ".join(incapable[:5])
                            + (" …" if len(incapable) > 5 else ""))
                finally:
                    db.close()
            # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
            except Exception as e:
                notes.append(f"could not scan {target}: "
                             f"{type(e).__name__}: {e}")
    detail = (f"{'on' if enabled else 'OFF (kill switch)'}, K={k} "
              "(population-capable templates train K proposals as one "
              "vmapped program)"
              + ("; " + "; ".join(notes) if notes else ""))
    return ("vectorized trials", WARN if warn else PASS, detail)


def check_static_analysis() -> Check:
    """Upload-time template verification (docs/static-analysis.md): WARN
    when RAFIKI_VERIFY_TEMPLATES=off while train/inference jobs are live
    — the platform is accepting templates nothing has looked at — and
    list models whose rows carry no verification report (uploaded before
    the verifier, or under =off): those are exactly the templates a
    fault at trial time would "discover" the expensive way. Also WARNs
    on models whose persisted report carries error findings (an upload
    that went through under =warn)."""
    from rafiki_tpu import config
    from rafiki_tpu.analysis import verify_mode

    mode = verify_mode()
    notes = []
    warn = False
    live_jobs = 0
    unverified = []
    flagged = []
    target = str(config.DB_PATH)
    is_url = target.startswith(("postgresql://", "postgres://"))
    if is_url or os.path.exists(target):
        try:
            from rafiki_tpu.db.database import Database

            db = Database(target)
            try:
                live_jobs = len(db.get_train_jobs_by_statuses(
                    ["STARTED", "RUNNING"]))
                for m in db.get_models():
                    blob = m.get("verification")
                    if not blob:
                        unverified.append(m.get("name", m["id"][:8]))
                        continue
                    try:
                        report = json.loads(blob)
                    # lint: absorb(an unreadable report reads as unverified)
                    except ValueError:
                        unverified.append(m.get("name", m["id"][:8]))
                        continue
                    if not report.get("ok", True):
                        flagged.append(m.get("name", m["id"][:8]))
            finally:
                db.close()
        # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
        except Exception as e:
            return ("static analysis", WARN,
                    f"could not scan {target}: {type(e).__name__}: {e}")
    if mode == "off" and live_jobs:
        warn = True
        notes.append(
            f"RAFIKI_VERIFY_TEMPLATES=off with {live_jobs} live train "
            "job(s): uploads are going straight to trial time unchecked")
    if unverified:
        warn = warn or mode != "off"
        notes.append(
            f"{len(unverified)} model(s) have no verification report "
            "(pre-verifier uploads or =off): "
            + ", ".join(unverified[:5])
            + (" …" if len(unverified) > 5 else "")
            + " — re-upload or dry-run via Client.verify_model")
    if flagged:
        warn = True
        notes.append(
            f"{len(flagged)} model(s) carry ERROR findings (uploaded "
            "under =warn): " + ", ".join(flagged[:5])
            + (" …" if len(flagged) > 5 else ""))
    detail = (f"mode={mode} (AST template verifier at upload; "
              "framework self-lint rides tier-1)"
              + ("; " + "; ".join(notes) if notes else ""))
    return ("static analysis", WARN if warn else PASS, detail)


def check_concurrency_lint() -> Check:
    """The whole-package concurrency analyzer (docs/static-analysis.md,
    CONC1xx/2xx/3xx): tier-1 pins the shipped tree at zero findings, but
    an operator running a locally-edited tree never sees CI — WARN when
    the INSTALLED package lints dirty, so a race or lock-order inversion
    introduced by a local patch is caught at doctor time, not in
    production."""
    try:
        from rafiki_tpu.analysis.concurrency import analyze_package

        findings = analyze_package()
    # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
    except Exception as e:
        return ("concurrency lint", WARN,
                f"analyzer failed on the installed tree: "
                f"{type(e).__name__}: {e}")
    if not findings:
        return ("concurrency lint", PASS,
                "installed tree lints clean (lockset inference, "
                "lock-order cycles, atomicity — zero unannotated "
                "findings)")
    by_code: Dict[str, int] = {}
    for f in findings:
        by_code[f.code] = by_code.get(f.code, 0) + 1
    head = "; ".join(str(f) for f in findings[:3])
    return ("concurrency lint", WARN,
            f"{len(findings)} finding(s) in the installed tree "
            f"({', '.join(f'{c}x{n}' for c, n in sorted(by_code.items()))})"
            f" — local edits regressed the race gate: {head}"
            + (" …" if len(findings) > 3 else "")
            + " (fix the race or annotate the true negative; "
            "python -m rafiki_tpu.analysis --self-lint lists all)")


def check_int8_serving() -> Check:
    """int8 weight-only serving (docs/performance.md): retired from the
    default record after measuring a 0.805x SLOWDOWN on the bench matmul
    shapes (VERDICT r5) — the weight-bandwidth win it targets did not
    materialize there, and the in-graph dequantize costs real time.
    WARN whenever an operator forces it on, so nobody serves slower
    without noticing."""
    if os.environ.get("RAFIKI_SERVE_INT8") != "1":
        return ("int8 serving", PASS,
                "off (default; measured 0.805x SLOWDOWN on the bench "
                "matmul shapes, VERDICT r5 — no cell measures it on the "
                "chip (ROADMAP D3))")
    return ("int8 serving", WARN,
            "RAFIKI_SERVE_INT8=1: this path measured a 0.805x SLOWDOWN "
            "on the bench matmul shapes (VERDICT r5) — it also "
            "quantizes trial-time evaluate. No cell measures it on the "
            "chip (ROADMAP D3): unset it unless your own measurement "
            "shows a win; "
            "docs/performance.md explains when int8 can still win")


#: paged-KV pool capacity (block_tokens x pool_blocks, in tokens) past
#: which the doctor reads "this pool will not fit beside the model in
#: chip HBM" — the paged twin of the ~64-slot ring heuristic (64 slots of
#: a 4k context).
PAGED_POOL_TOKEN_HEURISTIC = 64 * 4096


def check_generative_serving() -> Check:
    """Generative serving (docs/serving-generation.md): WARN when the
    slot table is misconfigured against the chip-memory heuristic (every
    slot preallocates a max_context-long KV ring — slots x context is the
    cache's token capacity, and past ~64 slots a worker is trading HBM
    for queueing the door could do better), when the PAGED layout is
    degenerate (block size < 8 amplifies table/gather overhead, past the
    2048-token ceiling a "page" is bigger than most contexts and paging
    buys nothing) or its pool capacity exceeds the chip-memory heuristic,
    when the prefix cache is disabled while the shareable-prefix counter
    shows shared-prompt traffic, when the stall detector is disabled, and
    when live TEXT_GENERATION jobs have no reachable streaming door (the
    chunked /generate route only exists on the dedicated per-job
    predictor port)."""
    from rafiki_tpu import config

    notes = []
    warn = False
    slots = int(config.GEN_MAX_SLOTS)
    if slots < 1:
        warn = True
        notes.append(f"RAFIKI_GEN_MAX_SLOTS={slots}: generation workers "
                     "clamp to 1 slot — continuous batching is OFF")
    elif slots > 64:
        warn = True
        notes.append(
            f"RAFIKI_GEN_MAX_SLOTS={slots} is past the memory heuristic "
            "(~64): each slot preallocates a full max_context KV ring in "
            "HBM and decode advances EVERY slot each step — prefer more "
            "replicas over a wider table")
    block_tokens = int(config.GEN_KV_BLOCK_TOKENS)
    pool_blocks = int(config.GEN_KV_POOL_BLOCKS)
    if bool(config.GEN_KV_PAGED):
        if block_tokens < 8 or block_tokens > 2048:
            warn = True
            notes.append(
                f"RAFIKI_GEN_KV_BLOCK_TOKENS={block_tokens} is degenerate "
                "(sane range 8..2048): tiny pages spend the pool on block-"
                "table overhead, giant pages degrade to one-ring-per-slot")
        if pool_blocks and block_tokens * pool_blocks \
                > PAGED_POOL_TOKEN_HEURISTIC:
            warn = True
            notes.append(
                f"RAFIKI_GEN_KV_BLOCK_TOKENS={block_tokens} x "
                f"RAFIKI_GEN_KV_POOL_BLOCKS={pool_blocks} = "
                f"{block_tokens * pool_blocks} tokens of K/V exceeds the "
                f"chip-memory heuristic ({PAGED_POOL_TOKEN_HEURISTIC}): "
                "the pool competes with the model for HBM — prefer more "
                "replicas over a deeper pool")
        if not bool(config.GEN_PREFIX_CACHE):
            try:
                from rafiki_tpu.utils.metrics import REGISTRY

                shareable = REGISTRY.get(
                    "rafiki_gen_prefix_shareable_total")
                shared_n = shareable.value() if shareable else 0
            # lint: absorb(telemetry probe is best-effort inside a doctor check)
            except Exception:
                shared_n = 0
            if shared_n > 0:
                warn = True
                notes.append(
                    f"RAFIKI_GEN_PREFIX_CACHE=0 while "
                    f"{int(shared_n)} admissions shared a prompt prefix "
                    "(rafiki_gen_prefix_shareable_total): these streams "
                    "are re-paying prefill the cache would serve free")
    if float(config.GEN_STREAM_TIMEOUT_S) <= 0:
        warn = True
        notes.append("RAFIKI_GEN_STREAM_TIMEOUT_S<=0: the door clamps "
                     "the stall detector to 0.1s — streams may be cut "
                     "before slow decodes deliver")
    gen_jobs = 0
    doors = []
    target = str(config.DB_PATH)
    is_url = target.startswith(("postgresql://", "postgres://"))
    if is_url or os.path.exists(target):
        try:
            from rafiki_tpu.db.database import Database

            db = Database(target)
            try:
                for inf in db.get_inference_jobs_by_statuses(["RUNNING"]):
                    tj = db.get_train_job(inf["train_job_id"])
                    if not tj or tj["task"] != "TEXT_GENERATION":
                        continue
                    gen_jobs += 1
                    psvc = (db.get_service(inf["predictor_service_id"])
                            if inf.get("predictor_service_id") else None)
                    host = (psvc or {}).get("host")
                    port = (psvc or {}).get("port")
                    if not host or not port:
                        warn = True
                        notes.append(
                            f"gen job {inf['id'][:8]}: no dedicated "
                            "predictor door published — streaming "
                            "/generate needs RAFIKI_PREDICTOR_PORTS=1")
                        continue
                    try:
                        import urllib.request

                        with urllib.request.urlopen(
                                f"http://{host}:{port}/healthz",
                                timeout=2.0) as resp:
                            ok = resp.status == 200
                    # lint: absorb(an unreachable door is the WARN itself, not a crash)
                    except Exception:
                        ok = False
                    if ok:
                        doors.append(f"{host}:{port}")
                    else:
                        warn = True
                        notes.append(
                            f"gen job {inf['id'][:8]}: streaming door "
                            f"{host}:{port} UNREACHABLE")
            finally:
                db.close()
        # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
        except Exception as e:
            return ("generative serving", WARN,
                    f"could not scan {target}: {type(e).__name__}: {e}")
    if warn:
        return ("generative serving", WARN, "; ".join(notes))
    detail = (f"{slots} slots/worker, max {int(config.GEN_MAX_TOKENS)} "
              f"tokens/request, stall cutoff "
              f"{float(config.GEN_STREAM_TIMEOUT_S):g}s")
    if bool(config.GEN_KV_PAGED):
        detail += (f"; paged KV: {block_tokens}-token blocks, pool "
                   + (f"{pool_blocks} blocks" if pool_blocks
                      else "auto-sized (ring parity)")
                   + (", prefix cache on"
                      if bool(config.GEN_PREFIX_CACHE)
                      else ", prefix cache OFF"))
    else:
        detail += "; paged KV OFF (legacy contiguous ring)"
    if gen_jobs:
        detail += (f"; {gen_jobs} live generation job(s), doors: "
                   + (", ".join(doors) or "none"))
    return ("generative serving", PASS, detail)


#: speculative lookahead past which the draft's k proposals rarely all
#: land — each extra position costs draft compute AND verify width, and
#: acceptance decays geometrically with depth
GEN_SPEC_K_HEURISTIC = 8


def check_speculative_decoding() -> Check:
    """Speculative decoding (docs/serving-generation.md "Speculative
    decoding & sampling"): WARN when RAFIKI_GEN_SPEC is on without the
    paged plane it lives on, when RAFIKI_GEN_SPEC_K is outside the sane
    1..8 window, when a RUNNING generation job budgets a GEN_DRAFT_TRIAL
    whose template is not generation-capable or whose max_context trails
    the target's (long streams silently drop out of speculation), when a
    worker reports speculation DEGRADED (gen_spec_degraded in its stats
    row names the fault), and when the measured acceptance rate sits
    under RAFIKI_GEN_SPEC_MIN_RATE — a draft that rarely earns its k
    proposals back is pure overhead."""
    from rafiki_tpu import config

    notes = []
    warn = False
    spec_on = bool(config.GEN_SPEC)
    k = int(config.GEN_SPEC_K)
    if spec_on and not bool(config.GEN_KV_PAGED):
        warn = True
        notes.append(
            "RAFIKI_GEN_SPEC=1 with RAFIKI_GEN_KV_PAGED=0: speculation "
            "verifies through paged_verify_step on the paged plane — "
            "workers will serve plain ring decode")
    if spec_on and not (1 <= k <= GEN_SPEC_K_HEURISTIC):
        warn = True
        notes.append(
            f"RAFIKI_GEN_SPEC_K={k} is outside 1..{GEN_SPEC_K_HEURISTIC}:"
            " acceptance decays geometrically with lookahead depth, so "
            "deep drafts burn proposal compute the verify step rejects")
    drafted = 0
    target = str(config.DB_PATH)
    is_url = target.startswith(("postgresql://", "postgres://"))
    if spec_on and (is_url or os.path.exists(target)):
        try:
            from rafiki_tpu import analysis
            from rafiki_tpu.constants import BudgetType
            from rafiki_tpu.db.database import Database

            db = Database(target)
            try:
                for inf in db.get_inference_jobs_by_statuses(["RUNNING"]):
                    tj = db.get_train_job(inf["train_job_id"])
                    if not tj or tj["task"] != "TEXT_GENERATION":
                        continue
                    draft_tid = (inf.get("budget") or {}).get(
                        BudgetType.GEN_DRAFT_TRIAL)
                    if not draft_tid:
                        continue
                    drafted += 1
                    trial = db.get_trial(str(draft_tid))
                    model = (db.get_model(trial["model_id"])
                             if trial else None)
                    if model is None:
                        warn = True
                        notes.append(
                            f"gen job {inf['id'][:8]}: GEN_DRAFT_TRIAL "
                            f"{str(draft_tid)[:8]} has no stored model")
                        continue
                    dspec = analysis.static_generation_capability(
                        model["model_file_bytes"],
                        model.get("model_class"))
                    if dspec is None:
                        warn = True
                        notes.append(
                            f"gen job {inf['id'][:8]}: draft trial "
                            f"{str(draft_tid)[:8]}'s template is not "
                            "generation-capable — its workers degrade "
                            "to plain decode at boot")
                        continue
                    # the TARGET's context: the job's best trial's model
                    best = db.get_best_trials_of_train_job(
                        tj["id"], max_count=1)
                    tmodel = (db.get_model(best[0]["model_id"])
                              if best else None)
                    tspec = (analysis.static_generation_capability(
                        tmodel["model_file_bytes"],
                        tmodel.get("model_class"))
                        if tmodel is not None else None)
                    if tspec and int(dspec.get("max_context", 0)) \
                            < int(tspec.get("max_context", 0)):
                        warn = True
                        notes.append(
                            f"gen job {inf['id'][:8]}: draft max_context "
                            f"{dspec.get('max_context')} < target "
                            f"{tspec.get('max_context')} — streams past "
                            "the draft's horizon drop out of speculation "
                            "and decode plain")
            finally:
                db.close()
        # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
        except Exception as e:
            return ("speculative decoding", WARN,
                    f"could not scan {target}: {type(e).__name__}: {e}")
    # live worker verdicts: degradations + the measured acceptance rate
    try:
        from rafiki_tpu.utils.metrics import REGISTRY
        from rafiki_tpu.worker.inference import SERVING_STATS, _stats_lock

        with _stats_lock:
            degraded = sorted({
                str(row["gen_spec_degraded"])
                for row in SERVING_STATS.values()
                if row.get("gen_spec_degraded")})
        if degraded:
            warn = True
            notes.append("speculation DEGRADED on live worker(s): "
                         + "; ".join(degraded))
        prop = REGISTRY.get("rafiki_gen_spec_proposed_total")
        acc = REGISTRY.get("rafiki_gen_spec_accepted_total")
        proposed = prop.value() if prop else 0
        accepted = acc.value() if acc else 0
        min_rate = float(config.GEN_SPEC_MIN_RATE)
        if proposed >= 200 and accepted / proposed < min_rate:
            warn = True
            notes.append(
                f"acceptance rate {accepted / proposed:.2f} < "
                f"RAFIKI_GEN_SPEC_MIN_RATE={min_rate:g} over "
                f"{int(proposed)} proposals: the draft disagrees with "
                "the target too often to pay for itself — use a draft "
                "distilled from the target, or lower RAFIKI_GEN_SPEC_K")
    # lint: absorb(telemetry probe is best-effort inside a doctor check)
    except Exception:
        pass
    if warn:
        return ("speculative decoding", WARN, "; ".join(notes))
    if not spec_on:
        return ("speculative decoding", PASS,
                "RAFIKI_GEN_SPEC=0 (plain decode)")
    detail = f"on, k={k}"
    if drafted:
        detail += f"; {drafted} live job(s) budget a draft trial"
    return ("speculative decoding", PASS, detail)


def check_stream_continuity() -> Check:
    """Stream continuity (docs/failure-model.md "Stream continuity"):
    WARN when the door-side resume journal's byte cap cannot hold a
    max-length stream (~8 B per journaled token id, so a cap under
    GEN_MAX_TOKENS*8 means long streams overflow and silently lose
    resume eligibility before they finish), when resume is disabled
    (RAFIKI_GEN_RESUME_MAX=0) while the autoscaler is ON (every
    scale-down's MIGRATING handoff then surfaces as a client error
    instead of a sibling resume), when the journal TTL is shorter than
    the serving deadline (a stream can outlive its own resume
    eligibility), and when a rollout's drain window is zero (every
    rolling step force-migrates every resident stream instead of
    letting finishable ones run out)."""
    from rafiki_tpu import config

    notes = []
    warn = False
    cap_bytes = int(config.GEN_JOURNAL_MAX_KB) * 1024
    need = int(config.GEN_MAX_TOKENS) * 8
    if 0 < cap_bytes < need:
        warn = True
        notes.append(
            f"RAFIKI_GEN_JOURNAL_MAX_KB={int(config.GEN_JOURNAL_MAX_KB)} "
            f"({cap_bytes} B) < GEN_MAX_TOKENS*8 ({need} B): max-length "
            "streams overflow the journal and lose resume eligibility "
            "mid-stream")
    resume_max = int(config.GEN_RESUME_MAX)
    if resume_max <= 0 and bool(config.AUTOSCALE):
        warn = True
        notes.append(
            "RAFIKI_GEN_RESUME_MAX=0 with RAFIKI_AUTOSCALE=1: scale-down "
            "drain handoffs of generation streams cannot be resumed — "
            "every forced migration becomes a client-visible error")
    ttl = float(config.GEN_JOURNAL_TTL_S)
    if 0 < ttl < float(config.PREDICT_TIMEOUT_S):
        warn = True
        notes.append(
            f"RAFIKI_GEN_JOURNAL_TTL_S={ttl:g} < "
            f"PREDICT_TIMEOUT_S={float(config.PREDICT_TIMEOUT_S):g}: a "
            "stream can outlive its journal entry and die unresumable "
            "inside its own deadline")
    if resume_max > 0 and float(config.AUTOSCALE_DRAIN_S) <= 0:
        warn = True
        notes.append(
            f"RAFIKI_AUTOSCALE_DRAIN_S="
            f"{float(config.AUTOSCALE_DRAIN_S):g}: gen rollouts/scale-"
            "downs skip the run-out window and force-migrate EVERY "
            "resident stream — resumes work but burn sibling prefills "
            "for streams that could have finished in place")
    if warn:
        return ("stream continuity", WARN, "; ".join(notes))
    if resume_max <= 0:
        return ("stream continuity", PASS,
                "resume disabled (RAFIKI_GEN_RESUME_MAX=0)")
    return ("stream continuity", PASS,
            f"resume on: {resume_max} attempt(s), journal cap "
            f"{int(config.GEN_JOURNAL_MAX_KB)} KB, TTL {ttl:g}s")


#: prediction-cache byte cap past which the doctor reads "this cache
#: will contend with the models for host memory" — results live in the
#: admin process's RAM beside every Predictor, door, and broker ring
PREDICT_CACHE_BYTES_HEURISTIC = 1 << 30


def check_prediction_cache() -> Check:
    """Prediction result cache + single-flight (docs/performance.md
    "Prediction caching & single-flight"): WARN when the cache is ON
    with a zero TTL (every fill is dropped — pure digest overhead), when
    the byte cap is past the host-memory heuristic, when it is enabled
    alongside live TEXT_GENERATION jobs (generative serving is excluded
    by design, so the operator's knob is doing less than they think),
    and when it is OFF while the sampled duplicate-query counter shows
    sustained identical-query traffic being forwarded redundantly (the
    `shareable`-style signal, applied to classification)."""
    from rafiki_tpu import config

    enabled = bool(config.PREDICT_CACHE)
    notes = []
    warn = False
    if enabled:
        ttl = float(config.PREDICT_CACHE_TTL_S)
        if ttl <= 0:
            warn = True
            notes.append(
                f"RAFIKI_PREDICT_CACHE_TTL_S={ttl:g} with the cache ON: "
                "every fill is dropped, so requests pay the digest cost "
                "and never hit — set a positive TTL or disable the cache")
        cap = int(config.PREDICT_CACHE_MAX_BYTES)
        if cap > PREDICT_CACHE_BYTES_HEURISTIC:
            warn = True
            notes.append(
                f"RAFIKI_PREDICT_CACHE_MAX_BYTES={cap} is past the "
                f"host-memory heuristic ({PREDICT_CACHE_BYTES_HEURISTIC}): "
                "the cache shares the admin process's RAM with every "
                "serving head and broker ring — prefer a shorter TTL "
                "over a deeper cache")
        target = str(config.DB_PATH)
        is_url = target.startswith(("postgresql://", "postgres://"))
        if is_url or os.path.exists(target):
            try:
                from rafiki_tpu.db.database import Database

                db = Database(target)
                try:
                    gen_jobs = [
                        inf["id"][:8]
                        for inf in db.get_inference_jobs_by_statuses(
                            ["RUNNING"])
                        if (db.get_train_job(inf["train_job_id"]) or {}
                            ).get("task") == "TEXT_GENERATION"]
                finally:
                    db.close()
                if gen_jobs:
                    warn = True
                    notes.append(
                        "RAFIKI_PREDICT_CACHE=1 beside live "
                        f"TEXT_GENERATION job(s) {gen_jobs}: generative "
                        "serving is EXCLUDED from the prediction cache "
                        "by design (token streams answer from decode "
                        "state, not a one-shot forward) — the knob does "
                        "nothing for those jobs; the shared-prefix KV "
                        "cache (RAFIKI_GEN_PREFIX_CACHE) is their "
                        "equivalent lever")
            # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
            except Exception as e:
                notes.append(f"could not scan {target} for generative "
                             f"jobs: {type(e).__name__}: {e}")
    else:
        try:
            from rafiki_tpu.utils.metrics import REGISTRY

            shareable = REGISTRY.get("rafiki_cache_shareable_total")
            # per-job labeled family: the signal is the fleet-wide sum
            shared_n = (sum(ch.value()
                            for ch in shareable.children().values())
                        if shareable else 0)
        # lint: absorb(telemetry probe is best-effort inside a doctor check)
        except Exception:
            shared_n = 0
        if shared_n > 0:
            warn = True
            notes.append(
                f"RAFIKI_PREDICT_CACHE=0 while the sampled duplicate-"
                f"query probe counted {int(shared_n)} repeat(s) "
                "(rafiki_cache_shareable_total, 1-in-16 sampling): "
                "identical queries are re-paying model forwards the "
                "cache would serve free — consider "
                "RAFIKI_PREDICT_CACHE=1 (results must be deterministic "
                "per model version; flushed automatically on deploy/"
                "rollback/adoption)")
    if warn:
        return ("prediction cache", WARN, "; ".join(notes))
    if not enabled:
        return ("prediction cache", PASS,
                "off (default; no duplicate-query traffic observed — "
                "RAFIKI_PREDICT_CACHE=1 adds a versioned result cache "
                "with single-flight coalescing)")
    detail = (f"on: TTL {float(config.PREDICT_CACHE_TTL_S):g}s, cap "
              f"{int(config.PREDICT_CACHE_MAX_BYTES)} bytes, "
              "single-flight "
              + ("on" if bool(config.PREDICT_SINGLEFLIGHT) else "OFF"))
    if notes:
        detail += "; " + "; ".join(notes)
    return ("prediction cache", PASS, detail)


def check_autoscaler(total_chips: int = None) -> Check:
    """Elastic serving autoscaler (docs/failure-model.md "Overload
    adaptation"): WARN when the serving plane is visibly shedding while
    the control loop that could fix it is disabled, when the replica
    bounds are inverted (the loop would be wedged between them), and when
    the chip-borrow training floor exceeds the fleet's capacity (no
    borrow could ever be granted — probably a typo'd knob).

    ``total_chips`` injects the fleet capacity when the caller knows it;
    otherwise it is summed from RAFIKI_AGENTS inventories when set."""
    from rafiki_tpu import config
    from rafiki_tpu.utils.metrics import REGISTRY, ring_window_s

    notes = []
    warn = False
    enabled = bool(config.AUTOSCALE)
    min_r = int(config.AUTOSCALE_MIN_REPLICAS)
    max_r = int(config.AUTOSCALE_MAX_REPLICAS)
    if min_r > max_r:
        warn = True
        notes.append(
            f"replica bounds INVERTED: RAFIKI_AUTOSCALE_MIN_REPLICAS="
            f"{min_r} > RAFIKI_AUTOSCALE_MAX_REPLICAS={max_r} — the loop "
            "can neither grow nor shrink any job")
    low, high = float(config.AUTOSCALE_DEPTH_LOW), float(
        config.AUTOSCALE_DEPTH_HIGH)
    if low >= high:
        warn = True
        notes.append(
            f"no hysteresis: RAFIKI_AUTOSCALE_DEPTH_LOW={low:g} >= "
            f"DEPTH_HIGH={high:g} — the loop will flap between up and "
            "down on the same signal")
    # sustained shed with the loop off: scan the shed-rate ring series
    # (in-process registry — embedded use — plus the admin door's JSON
    # snapshot when an admin is reachable)
    ring_snapshot = {
        name: series
        for name, series in REGISTRY.snapshot()["rings"].items()
        if name.startswith("shed_rate:")}
    try:
        import json as _json
        import urllib.request

        with urllib.request.urlopen(
                f"http://{config.ADMIN_HOST}:{config.ADMIN_PORT}"
                "/metrics?format=json", timeout=2) as resp:
            remote = _json.load(resp).get("rings", {})
        for name, series in remote.items():
            if name.startswith("shed_rate:"):
                ring_snapshot.setdefault(name, series)
    # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
    except Exception:
        pass  # no admin on this host — in-process rings only
    shed_doors = sorted(
        name.split(":", 1)[1]
        for name, series in ring_snapshot.items()
        if sum(v for _, v in series) > 0)
    if shed_doors and not enabled:
        warn = True
        notes.append(
            f"sustained shed observed at {shed_doors} within the last "
            f"{ring_window_s()}s but RAFIKI_AUTOSCALE is OFF — the fleet "
            "is turning traffic away that a scale-up could absorb")
    # chip-borrow floor vs fleet capacity
    floor = int(config.AUTOSCALE_TRAIN_FLOOR)
    if total_chips is None:
        agents = [a.strip() for a in os.environ.get(
            "RAFIKI_AGENTS", "").split(",") if a.strip()]
        if agents:
            from rafiki_tpu.utils.agent_http import call_agent

            total_chips = 0
            for addr in agents:
                try:
                    inv = call_agent(
                        addr, "GET", "/inventory",
                        key=os.environ.get("RAFIKI_AGENT_KEY"),
                        timeout_s=5, use_breaker=False)
                    total_chips += int(inv.get("total_chips", 0))
                # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
                except Exception:
                    total_chips = None
                    break
    if total_chips is not None and floor > total_chips > 0:
        warn = True
        notes.append(
            f"RAFIKI_AUTOSCALE_TRAIN_FLOOR={floor} exceeds the fleet's "
            f"{total_chips} chip(s): no serving borrow can ever be "
            "granted — probably a typo")
    state = "loop ON" if enabled else "loop off"
    fair = "fair admission ON" if config.AUTOSCALE_FAIR else \
        "fair admission off"
    detail = (f"{state}, {fair}, replicas [{min_r}, {max_r}] step "
              f"{int(config.AUTOSCALE_STEP)}, train floor {floor} chip(s)"
              + ("; " + "; ".join(notes) if notes else ""))
    return ("autoscaler", WARN if warn else PASS, detail)


def check_compile_cache(total_chips: Optional[int] = None) -> Check:
    """Cold-start resilience (docs/failure-model.md "Cold-start
    faults"): WARN when the persistent compile cache cannot actually
    serve worker boots — the dir missing/unwritable, the cache disabled
    while the autoscaler or warm pool is ON (their replacement replicas
    would recompile from scratch, defeating the point), recent boots
    compiling without a
    single cache hit (a silently-misconfigured key or dir), or a
    warm-pool floor no fleet capacity could ever hold."""
    from rafiki_tpu import config
    from rafiki_tpu.sdk import compile_cache
    from rafiki_tpu.utils.metrics import REGISTRY

    notes = []
    warn = False
    enabled = bool(config.COMPILE_CACHE)
    root = compile_cache.cache_dir()
    scaler_on = bool(config.AUTOSCALE) or int(config.AUTOSCALE_WARM_POOL) > 0
    if not enabled and scaler_on:
        warn = True
        notes.append(
            "RAFIKI_COMPILE_CACHE=0 while the autoscaler/warm pool is ON "
            "— every replacement replica pays a full cold compile, which "
            "is exactly the latency those loops exist to remove")
    if enabled:
        try:
            os.makedirs(root, exist_ok=True)
            probe = os.path.join(root, ".rafiki_doctor_probe")
            with open(probe, "w", encoding="utf-8") as f:
                f.write("ok")
            os.unlink(probe)
        # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
        except Exception as e:
            warn = True
            notes.append(
                f"cache dir {root} is missing/unwritable "
                f"({type(e).__name__}: {e}) — workers degrade to fresh "
                "compiles every boot")
    # recent boots compiling without a single hit: the
    # silently-misconfigured-key case (this process's registry plus the
    # admin door's JSON snapshot when an admin is reachable)
    local = REGISTRY.snapshot().get("metrics", {})
    remote = {}
    try:
        import json as _json
        import urllib.request

        with urllib.request.urlopen(
                f"http://{config.ADMIN_HOST}:{config.ADMIN_PORT}"
                "/metrics?format=json", timeout=2) as resp:
            remote = _json.load(resp).get("metrics", {})
    # lint: absorb(doctor checks must never crash; no admin on this host means in-process counters only)
    except Exception:
        pass
    hits = (_sum_counter(local, "rafiki_compile_cache_hits_total")
            + _sum_counter(remote, "rafiki_compile_cache_hits_total"))
    misses = (_sum_counter(local, "rafiki_compile_cache_misses_total")
              + _sum_counter(remote, "rafiki_compile_cache_misses_total"))
    if enabled and hits == 0 and misses >= 2:
        warn = True
        notes.append(
            f"{misses} program(s) compiled fresh with ZERO persistent-"
            "cache hits — a cache directory that moves between boots "
            "(JAX_COMPILATION_CACHE_DIR / RAFIKI_COMPILE_CACHE_DIR) never "
            "matches (every boot is cold)")
    # warm-pool floor vs fleet capacity
    pool = int(config.AUTOSCALE_WARM_POOL)
    if total_chips is None:
        agents = [a.strip() for a in os.environ.get(
            "RAFIKI_AGENTS", "").split(",") if a.strip()]
        if agents:
            from rafiki_tpu.utils.agent_http import call_agent

            total_chips = 0
            for addr in agents:
                try:
                    inv = call_agent(
                        addr, "GET", "/inventory",
                        key=os.environ.get("RAFIKI_AGENT_KEY"),
                        timeout_s=5, use_breaker=False)
                    total_chips += int(inv.get("total_chips", 0))
                # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
                except Exception:
                    total_chips = None
                    break
    if total_chips is not None and pool > total_chips > 0:
        warn = True
        notes.append(
            f"RAFIKI_AUTOSCALE_WARM_POOL={pool} standbys/job exceeds the "
            f"fleet's {total_chips} chip(s) — the pool can never reach "
            "its floor, probably a typo")
    state = "cache ON" if enabled else "cache off"
    pool_s = f"warm pool {pool}/job" if pool > 0 else "warm pool off"
    detail = (f"{state} at {root}, {pool_s}, hits {hits} misses {misses}"
              + ("; " + "; ".join(notes) if notes else ""))
    return ("compile cache", WARN if warn else PASS, detail)


def _sum_counter(metrics: Dict[str, Any], name: str) -> int:
    """Sum a counter family out of a registry JSON snapshot's flat
    {``name{labels}``: value} metric map (all label sets folded)."""
    total = 0.0
    for key, val in metrics.items():
        if (key == name or key.startswith(name + "{")) \
                and isinstance(val, (int, float)):
            total += val
    return int(total)


def check_observability() -> Check:
    """Telemetry plane (docs/observability.md): the registry must render
    parseable exposition, RAFIKI_TRACE_SAMPLE must be a sane rate, and
    the slow-request exemplar log must not be growing past its rotation
    cap. When RAFIKI_AGENTS is set, each agent's GET /metrics is probed —
    the scrape endpoint an autoscaler/dashboard will sit on."""
    from rafiki_tpu import config
    from rafiki_tpu.utils import trace as rtrace
    from rafiki_tpu.utils.metrics import (
        REGISTRY, metrics_enabled, parse_prometheus)

    notes = []
    warn = False
    if not metrics_enabled():
        warn = True
        notes.append("RAFIKI_METRICS=0: registry writes are no-ops — "
                     "/metrics will expose zeros")
    raw_rate = os.environ.get("RAFIKI_TRACE_SAMPLE", "")
    if raw_rate:
        try:
            r = float(raw_rate)
            if not 0.0 <= r <= 1.0:
                warn = True
                notes.append(f"RAFIKI_TRACE_SAMPLE={raw_rate} outside "
                             "[0, 1] — clamped, probably a typo")
            elif r >= 0.5 and rtrace.slow_threshold_s() <= 0:
                warn = True
                notes.append(
                    f"RAFIKI_TRACE_SAMPLE={r:g} with RAFIKI_TRACE_SLOW_MS "
                    "unset dumps an exemplar for (nearly) EVERY request — "
                    "set a slow threshold for production traffic")
        except ValueError:
            warn = True
            notes.append(f"RAFIKI_TRACE_SAMPLE={raw_rate!r} unparseable — "
                         "tracing is OFF")
    try:
        parse_prometheus(REGISTRY.render())
        n_metrics = len(REGISTRY.names())
    # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
    except Exception as e:
        return ("observability", FAIL,
                f"registry exposition does not parse: {e}")
    try:
        path = rtrace.exemplar_path()
        if os.path.exists(path):
            mb = os.path.getsize(path) / (1 << 20)
            cap = rtrace.exemplar_max_mb()
            if mb > cap * 1.5:
                warn = True
                notes.append(
                    f"exemplar log {path} at {mb:.0f} MB, past its "
                    f"{cap:g} MB rotation cap — rotation is not keeping "
                    "up (RAFIKI_TRACE_EXEMPLAR_MAX_MB)")
            else:
                notes.append(f"exemplar log {mb:.1f} MB / {cap:g} MB cap")
    except OSError:
        pass
    agents = [a.strip() for a in os.environ.get(
        "RAFIKI_AGENTS", "").split(",") if a.strip()]
    unreachable = []
    for addr in agents:
        try:
            import urllib.request

            with urllib.request.urlopen(
                    f"http://{addr}/metrics", timeout=5) as resp:
                parse_prometheus(resp.read().decode())
        # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
        except Exception:
            unreachable.append(addr)
    if unreachable:
        warn = True
        notes.append(f"agent /metrics unreachable: {unreachable}")
    rate = rtrace.sample_rate()
    detail = (f"{n_metrics} metric families registered, trace sampling "
              f"{rate:g}" + ("; " + "; ".join(notes) if notes else ""))
    return ("observability", WARN if warn else PASS, detail)


def check_agents() -> Check:
    from rafiki_tpu.utils.agent_http import AgentHTTPError, call_agent

    agents = [a.strip() for a in os.environ.get("RAFIKI_AGENTS", "").split(",")
              if a.strip()]
    if not agents:
        return ("host agents", PASS, "single-host (RAFIKI_AGENTS unset)")
    key = os.environ.get("RAFIKI_AGENT_KEY")
    down, rejected, locked = [], [], []
    total = 0
    for addr in agents:
        try:
            # /healthz first (unauthenticated): separates "host process
            # dead" from "alive but misconfigured" — the same liveness
            # probe the admin's heartbeat monitor uses, so doctor and the
            # /fleet/health API agree on what DOWN means
            call_agent(addr, "GET", "/healthz", timeout_s=5,
                       use_breaker=False)
        except AgentHTTPError:
            pass  # the host ANSWERED: alive (any config problem shows below)
        # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
        except Exception:
            down.append(addr)
            continue
        try:
            inv = call_agent(addr, "GET", "/inventory", key=key, timeout_s=5,
                             use_breaker=False)
            total += int(inv.get("total_chips", 0))
        except AgentHTTPError as e:
            # a live agent refusing the request is a CONFIG problem, not
            # an outage — agents are keyed by default since r5. 401 =
            # key mismatch (fix on the admin side); 403 = the AGENT has
            # no key and no insecure opt-in (fix on the agent side)
            if e.code == 401:
                rejected.append(addr)
            elif e.code == 403:
                locked.append(addr)
            else:
                down.append(addr)
        # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
        except Exception:
            down.append(addr)
    if locked:
        return ("host agents", FAIL,
                f"locked (keyless, no RAFIKI_AGENT_INSECURE): {locked} — "
                "configure RAFIKI_AGENT_KEY on those agents")
    if rejected:
        why = ("RAFIKI_AGENT_KEY unset on this admin" if not key
               else "this admin's RAFIKI_AGENT_KEY does not match")
        return ("host agents", FAIL,
                f"key rejected by: {rejected} ({why}; copy the agents' "
                "agent.key here)")
    if down:
        return ("host agents", FAIL if len(down) == len(agents) else WARN,
                f"DOWN (no /healthz answer): {down} (fleet chips visible: "
                f"{total}) — a hosts-mode admin evicts their serving "
                "queues and fails their train executors over; see "
                "GET /fleet/health and docs/failure-model.md")
    if not key:
        return ("host agents", WARN,
                f"{len(agents)} agent(s), {total} fleet chips — keyless "
                "admin talking to RAFIKI_AGENT_INSECURE agents; set a "
                "fleet key")
    return ("host agents", PASS,
            f"{len(agents)} agent(s), {total} fleet chips")


def check_control_plane_ha() -> Check:
    """Control-plane HA (docs/failure-model.md "Control-plane HA"): lease
    timing sanity, standby reachability, leader-epoch agreement between
    the store and the agent fleet, and the HA-off-but-controllers-on
    single-point-of-failure shape."""
    from rafiki_tpu import config

    notes = []
    warn = False
    ha_on = bool(config.ADMIN_HA)
    ttl = float(config.ADMIN_LEASE_TTL_S)
    renew = float(config.ADMIN_LEASE_RENEW_S) or ttl / 3.0
    if ha_on and ttl <= 2.0 * renew:
        warn = True
        notes.append(
            f"lease TTL {ttl:g}s <= 2x renewal period {renew:g}s: one "
            "missed renewal forfeits leadership (set "
            "RAFIKI_ADMIN_LEASE_TTL_S >= 3x RAFIKI_ADMIN_LEASE_RENEW_S)")
    if not ha_on and (config.AUTOSCALE or config.DRIFT):
        warn = True
        notes.append(
            "closed-loop controllers on (RAFIKI_AUTOSCALE/RAFIKI_DRIFT) "
            "with RAFIKI_ADMIN_HA=0: the deciding admin is a single "
            "point of failure — run a hot standby")
    addrs = [a.strip() for a in str(config.ADMIN_ADDRS).split(",")
             if a.strip()]
    if len(addrs) > 1:
        import urllib.request as _ur

        dead = []
        for addr in addrs:
            try:
                with _ur.urlopen(f"http://{addr}/", timeout=3):
                    pass
            # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
            except Exception:
                dead.append(addr)
        if dead:
            warn = True
            notes.append(
                f"RAFIKI_ADMIN_ADDRS lists unreachable admin(s): {dead} "
                "— clients will burn the failover window walking them")
    # leader-epoch agreement: the lease row is the truth; an agent
    # remembering a HIGHER epoch than the store means a stale/forked
    # store (or an admin writing to a different one)
    lease_epoch = None
    target = str(config.DB_PATH)
    is_url = target.startswith(("postgresql://", "postgres://"))
    if ha_on and (is_url or os.path.exists(target)):
        try:
            from rafiki_tpu.db.database import Database

            db = Database(target)
            row = db.read_lease()
            if row is not None:
                lease_epoch = int(row["epoch"])
                import time as _time

                live = row["expires_at"] > _time.time()
                notes.append(
                    f"lease: epoch {lease_epoch} held by "
                    f"{row.get('holder')}"
                    + ("" if live else " (EXPIRED — no leader)"))
                if not live:
                    warn = True
        # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
        except Exception as e:
            notes.append(f"lease row unreadable: {type(e).__name__}")
    agents = [a.strip() for a in os.environ.get("RAFIKI_AGENTS", "").split(",")
              if a.strip()]
    if lease_epoch is not None and agents:
        from rafiki_tpu.utils.agent_http import call_agent

        skewed = []
        for addr in agents:
            try:
                hz = call_agent(addr, "GET", "/healthz", timeout_s=5,
                                use_breaker=False)
                seen = int(hz.get("admin_epoch", 0))
            # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
            except Exception:
                continue  # reachability is check_agents' job, not ours
            if seen > lease_epoch:
                skewed.append(f"{addr}=e{seen}")
        if skewed:
            warn = True
            notes.append(
                f"agents remember a HIGHER epoch than the lease row "
                f"({skewed} vs store e{lease_epoch}): this admin is "
                "reading a stale or forked store")
    if not ha_on and not notes:
        return ("control-plane HA", PASS,
                "off (RAFIKI_ADMIN_HA=0, no controllers demanding it)")
    detail = "; ".join(notes) if notes else (
        f"on: TTL {ttl:g}s, renew {renew:g}s, "
        f"{len(addrs) or 1} admin addr(s)")
    return ("control-plane HA", WARN if warn else PASS, detail)


CHECKS: List[Callable[[], Check]] = [
    check_workdir, check_store, check_shm_broker, check_sandbox,
    check_chaos, check_overload_knobs, check_autoscaler,
    check_compile_cache, check_recovery,
    check_rollouts, check_drift, check_trial_faults,
    check_vectorized_trials,
    check_static_analysis, check_concurrency_lint,
    check_int8_serving, check_generative_serving,
    check_speculative_decoding, check_stream_continuity,
    check_prediction_cache,
    check_observability, check_agents, check_control_plane_ha,
    check_backend,
]


def run(json_out: bool = False) -> int:
    results = []
    for check in CHECKS:
        try:
            results.append(check())
        # lint: absorb(doctor checks must never crash; the failure becomes the check detail)
        except Exception as e:  # a doctor must never crash mid-diagnosis
            results.append((check.__name__, FAIL,
                            f"check crashed: {type(e).__name__}: {e}"))
    worst = PASS
    for name, status, detail in results:
        if not json_out:
            print(f"[{status}] {name}: {detail}")
        if status == FAIL or (status == WARN and worst == PASS):
            worst = status
    if json_out:
        print(json.dumps([
            {"check": n, "status": s, "detail": d} for n, s, d in results]))
    return 1 if worst == FAIL else 0


if __name__ == "__main__":
    sys.exit(run(json_out="--json" in sys.argv))
