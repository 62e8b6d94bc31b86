"""Training-plane trial fault tolerance (worker/faults.py +
docs/failure-model.md "Training-plane faults"): the classification drills.

The acceptance contract, exercised here on CPU in tier-1:

- a chaos-injected transient fault retries the trial under the SAME id
  and the job still completes exactly its MODEL_TRIAL_COUNT scored
  trials (no budget slot burned);
- an OOMing sandbox child classifies MEM, a mute child is killed within
  RAFIKI_TRIAL_STALL_S and classifies STALL;
- a template that always raises errors its job early with a typed
  reason recorded on the job row (fault_kind=USER);
- the GP steers away from regions fed as infeasible, and the infeasible
  signal round-trips the remote-advisor HTTP API.
"""

import os
import textwrap
import threading
import time

import pytest

from rafiki_tpu import config
from rafiki_tpu.advisor.advisor import Advisor, AdvisorStore
from rafiki_tpu.advisor.asha import AshaScheduler
from rafiki_tpu.advisor.gp import BayesOpt
from rafiki_tpu.constants import (ServiceType, TrainJobStatus, TrialStatus,
                                  UserType)
from rafiki_tpu.db.database import Database
from rafiki_tpu.placement.manager import ServiceContext
from rafiki_tpu.sdk.knob import FixedKnob, FloatKnob
from rafiki_tpu.utils import chaos
from rafiki_tpu.worker import faults
from rafiki_tpu.worker.faults import FaultKind
from rafiki_tpu.worker.train import (EVENT_TRIAL_FAULT_LIMIT, TrainWorker)

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "fake_model.py")

pytestmark = pytest.mark.chaos


# a template that always raises in train(): the poison-template drill
ALWAYS_RAISES = textwrap.dedent("""
    from rafiki_tpu.sdk import BaseModel, FloatKnob

    class Broken(BaseModel):
        @staticmethod
        def get_knob_config():
            return {"lr": FloatKnob(1e-4, 1e-1)}

        def __init__(self, **knobs):
            super().__init__(**knobs)

        def train(self, uri):
            raise RuntimeError("poison template: always crashes")

        def evaluate(self, uri):
            return 0.0

        def predict(self, queries):
            return queries

        def dump_parameters(self):
            return {}

        def load_parameters(self, p):
            pass
    """).encode()

# evaluate() returns NaN: the INVALID_SCORE drill
NAN_SCORE = textwrap.dedent("""
    from rafiki_tpu.sdk import BaseModel, FloatKnob

    class NanModel(BaseModel):
        @staticmethod
        def get_knob_config():
            return {"lr": FloatKnob(1e-4, 1e-1)}

        def __init__(self, **knobs):
            super().__init__(**knobs)

        def train(self, uri):
            pass

        def evaluate(self, uri):
            return float("nan")

        def predict(self, queries):
            return queries

        def dump_parameters(self):
            return {}

        def load_parameters(self, p):
            pass
    """).encode()


def _seed_job(db, model_bytes=None, model_class="FakeModel", budget=None):
    user = db.create_user("u@x", "h", UserType.APP_DEVELOPER)
    if model_bytes is None:
        with open(FIXTURE, "rb") as f:
            model_bytes = f.read()
    model = db.create_model(user["id"], "m", "IMAGE_CLASSIFICATION",
                            model_bytes, model_class, {"numpy": None},
                            "PUBLIC")
    job = db.create_train_job(
        user["id"], "app", 1, "IMAGE_CLASSIFICATION", "uri://t", "uri://e",
        budget or {"MODEL_TRIAL_COUNT": 3})
    sub = db.create_sub_train_job(job["id"], model["id"])
    return job, sub, model


def _run_worker(db, sub_id, tmp_path, events=None, service_id="svc-1"):
    worker = TrainWorker(
        sub_id, db, AdvisorStore(),
        send_event=(lambda name, payload: events.append((name, payload)))
        if events is not None else None,
        params_dir=str(tmp_path / "params"))
    ctx = ServiceContext(service_id=service_id,
                         service_type=ServiceType.TRAIN,
                         chips=[], stop_event=threading.Event())
    worker.start(ctx)
    return worker


@pytest.fixture(autouse=True)
def _clean_state(monkeypatch):
    faults.reset_stats()
    chaos.clear()
    yield
    faults.reset_stats()
    chaos.clear()


# -- the budget contract: infra faults retry without burning slots ----------

def test_infra_chaos_retry_preserves_budget(tmp_path, monkeypatch):
    """One transient fault at the trial chokepoint: the trial re-runs
    under the same id and the job STILL completes exactly N scored
    trials — the acceptance drill for the budget contract."""
    monkeypatch.setenv("RAFIKI_CHAOS", "site=trial;action=error;times=1")
    monkeypatch.setenv("RAFIKI_TRIAL_RETRY_BACKOFF_S", "0.01")
    db = Database(":memory:")
    job, sub, _ = _seed_job(db, budget={"MODEL_TRIAL_COUNT": 3})
    _run_worker(db, sub["id"], tmp_path)

    trials = db.get_trials_of_sub_train_job(sub["id"])
    assert len(trials) == 3  # the faulted trial did NOT burn an extra slot
    assert all(t["status"] == TrialStatus.COMPLETED for t in trials)
    assert all(t["score"] is not None for t in trials)
    # the first trial absorbed the injected fault: retried in place
    retried = [t for t in trials if t["attempt"] > 0]
    assert len(retried) == 1
    assert retried[0]["fault_kind"] == FaultKind.INFRA
    db.close()


def test_chaos_oom_classified_mem_and_errors_when_retry_disabled(
        tmp_path, monkeypatch):
    monkeypatch.setenv("RAFIKI_CHAOS", "site=trial;action=oom;times=1")
    monkeypatch.setenv("RAFIKI_TRIAL_RETRY_MAX", "0")
    db = Database(":memory:")
    job, sub, _ = _seed_job(db, budget={"MODEL_TRIAL_COUNT": 2})
    _run_worker(db, sub["id"], tmp_path)

    trials = db.get_trials_of_sub_train_job(sub["id"])
    errored = [t for t in trials if t["status"] == TrialStatus.ERRORED]
    assert len(errored) == 1
    assert errored[0]["fault_kind"] == FaultKind.MEM
    assert "MemoryError" in errored[0]["fault_detail"]
    # with retry disabled the fault consumed a budget slot (as before)
    assert len(trials) == 2
    db.close()


def test_retry_bound_exhausts_then_errors(tmp_path, monkeypatch):
    """Every attempt faults: after RAFIKI_TRIAL_RETRY_MAX re-runs the
    trial errors with the transient kind recorded (no infinite loop)."""
    monkeypatch.setenv("RAFIKI_CHAOS", "site=trial;action=error")
    monkeypatch.setenv("RAFIKI_TRIAL_RETRY_MAX", "2")
    monkeypatch.setenv("RAFIKI_TRIAL_RETRY_BACKOFF_S", "0.01")
    db = Database(":memory:")
    job, sub, _ = _seed_job(db, budget={"MODEL_TRIAL_COUNT": 1})
    _run_worker(db, sub["id"], tmp_path)

    trials = db.get_trials_of_sub_train_job(sub["id"])
    assert len(trials) == 1
    t = trials[0]
    assert t["status"] == TrialStatus.ERRORED
    assert t["fault_kind"] == FaultKind.INFRA
    assert t["attempt"] == 2  # both re-runs recorded on the row
    db.close()


# -- poison template: fail-fast + recorded reason ---------------------------

def test_poison_template_fails_job_fast_with_reason(tmp_path, monkeypatch):
    monkeypatch.setenv("RAFIKI_TRIAL_FAULT_LIMIT", "4")
    db = Database(":memory:")
    job, sub, _ = _seed_job(db, model_bytes=ALWAYS_RAISES,
                            model_class="Broken",
                            budget={"MODEL_TRIAL_COUNT": 50})
    events = []
    _run_worker(db, sub["id"], tmp_path, events=events)

    trials = db.get_trials_of_sub_train_job(sub["id"])
    # failed early: nowhere near the 50-trial budget
    assert len(trials) == 4
    assert all(t["status"] == TrialStatus.ERRORED for t in trials)
    assert all(t["fault_kind"] == FaultKind.USER for t in trials)
    # the truncated traceback is on the row — no log scraping needed
    assert "poison template: always crashes" in trials[0]["fault_detail"]
    refreshed = db.get_train_job(job["id"])
    assert refreshed["status"] == TrainJobStatus.ERRORED
    assert refreshed["fault_kind"] == FaultKind.USER
    assert "RAFIKI_TRIAL_FAULT_LIMIT" in refreshed["error_reason"]
    # and the admin was told, so it can tear down sibling workers
    names = [n for n, _ in events]
    assert EVENT_TRIAL_FAULT_LIMIT in names
    payload = dict(events)[EVENT_TRIAL_FAULT_LIMIT]
    assert payload["fault_kind"] == FaultKind.USER
    db.close()


def test_nan_score_classified_invalid_and_fed_infeasible(
        tmp_path, monkeypatch):
    monkeypatch.setenv("RAFIKI_TRIAL_FAULT_LIMIT", "0")  # no fail-fast
    db = Database(":memory:")
    job, sub, _ = _seed_job(db, model_bytes=NAN_SCORE,
                            model_class="NanModel",
                            budget={"MODEL_TRIAL_COUNT": 2})
    store = AdvisorStore()
    worker = TrainWorker(sub["id"], db, store,
                         params_dir=str(tmp_path / "params"))
    ctx = ServiceContext(service_id="svc-nan",
                         service_type=ServiceType.TRAIN,
                         chips=[], stop_event=threading.Event())
    worker.start(ctx)

    trials = db.get_trials_of_sub_train_job(sub["id"])
    assert len(trials) == 2
    assert all(t["status"] == TrialStatus.ERRORED for t in trials)
    assert all(t["fault_kind"] == FaultKind.INVALID_SCORE for t in trials)
    # the invalid scores became infeasible observations in the GP (>=1:
    # two draws landing in one dedup grid cell collapse to one row)
    assert store.get(sub["id"]).infeasible_count >= 1
    db.close()


# -- sandbox drills: MEM, STALL, exit classification ------------------------

MEM_TEMPLATE = textwrap.dedent("""
    from rafiki_tpu.sdk import BaseModel, FixedKnob

    class Oom(BaseModel):
        @staticmethod
        def get_knob_config():
            return {"k": FixedKnob(1)}

        def __init__(self, **knobs):
            super().__init__(**knobs)

        def train(self, uri):
            raise MemoryError("simulated RLIMIT_AS breach")

        def evaluate(self, uri):
            return 0.0

        def predict(self, queries):
            return queries

        def dump_parameters(self):
            return {}

        def load_parameters(self, p):
            pass
    """).encode()

MUTE_TEMPLATE = textwrap.dedent("""
    import time
    from rafiki_tpu.sdk import BaseModel, FixedKnob

    class Mute(BaseModel):
        @staticmethod
        def get_knob_config():
            return {"k": FixedKnob(1)}

        def __init__(self, **knobs):
            super().__init__(**knobs)

        def train(self, uri):
            time.sleep(300)  # never logs, never returns in test time

        def evaluate(self, uri):
            return 0.0

        def predict(self, queries):
            return queries

        def dump_parameters(self):
            return {}

        def load_parameters(self, p):
            pass
    """).encode()


def test_oom_child_classified_mem(tmp_path, monkeypatch):
    from rafiki_tpu.sdk.sandbox import SandboxMemError, make_jail, \
        run_trial_sandboxed

    jail = make_jail(str(tmp_path), "trial-mem")
    with pytest.raises(SandboxMemError) as ei:
        run_trial_sandboxed(MEM_TEMPLATE, "Oom", {"k": 1}, "uri://t",
                            "uri://e", jail, on_log_line=lambda l: None)
    assert ei.value.kind == FaultKind.MEM
    assert "MemoryError" in str(ei.value)


def test_mute_child_killed_within_stall_deadline(tmp_path, monkeypatch):
    from rafiki_tpu.sdk.sandbox import SandboxStallError, make_jail, \
        run_trial_sandboxed

    monkeypatch.setenv("RAFIKI_TRIAL_STALL_S", "8")
    jail = make_jail(str(tmp_path), "trial-mute")
    t0 = time.monotonic()
    with pytest.raises(SandboxStallError) as ei:
        run_trial_sandboxed(MUTE_TEMPLATE, "Mute", {"k": 1}, "uri://t",
                            "uri://e", jail, on_log_line=lambda l: None)
    elapsed = time.monotonic() - t0
    # killed by the no-frame watchdog, not train()'s 300 s sleep
    assert elapsed < 60
    assert ei.value.kind == FaultKind.STALL
    assert "RAFIKI_TRIAL_STALL_S" in str(ei.value)


def test_sandboxed_user_fault_reaches_trial_row(tmp_path, monkeypatch):
    """Full worker + sandbox: a crashing template's fault lands on the
    trial row as USER with the CHILD-side traceback."""
    monkeypatch.setenv("RAFIKI_SANDBOX", "1")
    monkeypatch.setenv("RAFIKI_WORKDIR", str(tmp_path))
    monkeypatch.setenv("RAFIKI_TRIAL_FAULT_LIMIT", "0")
    db = Database(":memory:")
    job, sub, _ = _seed_job(db, model_bytes=ALWAYS_RAISES,
                            model_class="Broken",
                            budget={"MODEL_TRIAL_COUNT": 1})
    _run_worker(db, sub["id"], tmp_path)
    trials = db.get_trials_of_sub_train_job(sub["id"])
    assert len(trials) == 1
    assert trials[0]["status"] == TrialStatus.ERRORED
    assert trials[0]["fault_kind"] == FaultKind.USER
    assert "poison template: always crashes" in trials[0]["fault_detail"]
    db.close()


# -- the GP steers away from infeasible regions -----------------------------

def test_gp_penalizes_infeasible_region():
    opt = BayesOpt(dims=1, seed=7)
    import numpy as np

    for x, y in [(0.1, 0.2), (0.2, 0.4), (0.3, 0.6), (0.4, 0.7),
                 (0.5, 0.8)]:
        opt.observe(np.array([x]), y)
    for _ in range(3):
        opt.mark_infeasible(np.array([0.9]))
    for _ in range(10):
        x = opt.suggest(register_pending=False)
        assert abs(float(x[0]) - 0.9) > 0.05


def test_warmup_draw_avoids_infeasible():
    import numpy as np

    opt = BayesOpt(dims=1, seed=3)
    for _ in range(3):
        opt.mark_infeasible(np.array([0.5]))
    for _ in range(10):
        x = opt.suggest(register_pending=False)
        assert abs(float(x[0]) - 0.5) > 0.2


def test_advisor_infeasible_counts_and_asha_forget():
    cfg = {"lr": FloatKnob(1e-4, 1e-1)}
    adv = Advisor(cfg)
    adv.feedback_infeasible({"lr": 1e-2}, FaultKind.USER)
    assert adv.infeasible_count == 1
    assert adv.observation_count == 0  # infeasible is not an observation

    s = AshaScheduler(min_resource=1, eta=3)
    assert s.report("dead", 1, 0.001)  # would set an unbeatable bar
    s.forget("dead")
    # fresh trials now compete among themselves: the rung bar is 0.5 (a
    # real fresh-trial loss), NOT the dead trial's 0.001 — so the best
    # fresh trial promotes, which the 0.001 bar would have prevented
    assert s.report("a", 1, 0.5)
    assert s.report("b", 1, 0.6)
    assert not s.report("c", 1, 0.55)  # only top-1/3 (0.5) promotes
    assert 0.001 not in list(s._rungs[1].values())


def test_store_replay_carries_infeasible():
    cfg = {"lr": FloatKnob(1e-4, 1e-1)}
    store = AdvisorStore()
    aid = store.create_advisor(cfg, advisor_id="replay-test")
    assert store.replay_feedback(
        aid, [({"lr": 1e-2}, 0.5)],
        infeasible=[({"lr": 5e-2}, FaultKind.TIMEOUT)])
    adv = store.get(aid)
    assert adv.observation_count == 1
    assert adv.infeasible_count == 1
    # non-empty session: the guard refuses a second replay
    assert not store.replay_feedback(
        aid, [({"lr": 1e-3}, 0.9)],
        infeasible=[({"lr": 2e-2}, FaultKind.USER)])
    assert adv.infeasible_count == 1


# -- quarantine: bounded re-proposal + stats --------------------------------

def test_quarantine_reproposes_and_survives_restart(tmp_path, monkeypatch):
    """Pre-recorded USER faults on one signature quarantine it at
    worker startup; with a FixedKnob-only space every proposal matches,
    so the bounded re-proposal loop runs out and accepts — counted in
    TRAINING_STATS, never a spinning worker."""
    monkeypatch.setenv("RAFIKI_TRIAL_QUARANTINE_K", "2")
    monkeypatch.setenv("RAFIKI_TRIAL_REPROPOSE_MAX", "3")
    monkeypatch.setenv("RAFIKI_TRIAL_FAULT_LIMIT", "0")
    fixed_only = textwrap.dedent("""
        from rafiki_tpu.sdk import BaseModel, FixedKnob

        class Fixed(BaseModel):
            @staticmethod
            def get_knob_config():
                return {"k": FixedKnob(1)}

            def __init__(self, **knobs):
                super().__init__(**knobs)

            def train(self, uri):
                pass

            def evaluate(self, uri):
                return 0.5

            def predict(self, queries):
                return queries

            def dump_parameters(self):
                return {}

            def load_parameters(self, p):
                pass
        """).encode()
    db = Database(":memory:")
    job, sub, model = _seed_job(db, model_bytes=fixed_only,
                                model_class="Fixed",
                                budget={"MODEL_TRIAL_COUNT": 3})
    # two recorded user faults on the (single) signature -> quarantined
    for _ in range(2):
        t = db.create_trial(sub["id"], model["id"], {"k": 1},
                            worker_id="dead-worker")
        db.mark_trial_as_errored(t["id"], FaultKind.USER, "boom")
    _run_worker(db, sub["id"], tmp_path)

    stats = faults.training_stats()[sub["id"]]
    assert stats["quarantined"]  # rebuilt from the store at startup
    assert stats["reproposals"] >= 1  # the bounded loop fired
    # the worker still made progress: budget filled despite quarantine
    trials = db.get_trials_of_sub_train_job(sub["id"])
    assert sum(1 for t in trials
               if t["status"] == TrialStatus.COMPLETED) == 1
    db.close()


# -- remote-advisor round-trip ----------------------------------------------

def test_remote_infeasible_roundtrip(tmp_path):
    from rafiki_tpu.admin.admin import Admin
    from rafiki_tpu.admin.http import AdminServer
    from rafiki_tpu.client.client import Client
    from rafiki_tpu.placement.manager import (ChipAllocator,
                                              LocalPlacementManager)
    from rafiki_tpu.sdk.knob import serialize_knob_config

    admin = Admin(
        db=Database(":memory:"),
        placement=LocalPlacementManager(allocator=ChipAllocator([0])),
        params_dir=str(tmp_path / "params"),
    )
    srv = AdminServer(admin, port=0).start()
    try:
        client = Client("127.0.0.1", srv.port)
        client.login(config.SUPERADMIN_EMAIL, config.SUPERADMIN_PASSWORD)
        cfg = {"lr": FloatKnob(1e-4, 1e-1)}
        aid = client.create_advisor(serialize_knob_config(cfg),
                                    advisor_id="remote-infeasible")
        n = client.feedback_infeasible_knobs(aid, {"lr": 1e-2},
                                             kind=FaultKind.USER,
                                             trial_id="t-1")
        assert n == 1
        assert admin.advisor_store.get(aid).infeasible_count == 1
        # replay with infeasible over HTTP seeds a fresh session
        aid2 = client.create_advisor(serialize_knob_config(cfg),
                                     advisor_id="remote-replay")
        assert client.replay_advisor_feedback(
            aid2, [({"lr": 1e-3}, 0.7)],
            infeasible=[({"lr": 9e-2}, FaultKind.TIMEOUT)])
        adv2 = admin.advisor_store.get(aid2)
        assert adv2.observation_count == 1
        assert adv2.infeasible_count == 1
    finally:
        srv.stop()
        admin.shutdown()


# -- satellites: pending-feedback bound, chaos spec, doctor -----------------

class _DeadAdvisorStore:
    """Every call fails — an unreachable admin, forever."""

    def get(self, advisor_id):
        raise ConnectionError("advisor unreachable")


def test_pending_feedback_bounded_drop_oldest(tmp_path, monkeypatch):
    monkeypatch.setenv("RAFIKI_PENDING_FEEDBACK_MAX", "5")
    worker = TrainWorker("sub-x", Database(":memory:"),
                         _DeadAdvisorStore(),
                         params_dir=str(tmp_path / "params"))
    for i in range(12):
        worker._feedback_best_effort("aid", {"lr": i}, float(i))
    assert len(worker._pending_feedback) == 5
    # drop-OLDEST: the newest observations survive
    assert [k["lr"] for k, _ in worker._pending_feedback] == [
        7, 8, 9, 10, 11]
    assert faults.training_stats()["sub-x"]["feedback_dropped"] == 7


def test_chaos_trial_spec_validation():
    rules = chaos.parse_rules("site=trial;action=oom;times=1")
    assert rules[0].site == chaos.SITE_TRIAL
    with pytest.raises(chaos.ChaosSpecError):
        chaos.parse_rules("site=db;action=oom")
    with pytest.raises(chaos.ChaosSpecError):
        chaos.parse_rules("site=trial;action=corrupt")


def test_doctor_warns_on_disabled_retry(monkeypatch, tmp_path):
    from rafiki_tpu.doctor import WARN, check_trial_faults

    monkeypatch.setenv("RAFIKI_TRIAL_RETRY_MAX", "0")
    monkeypatch.setenv("RAFIKI_WORKDIR", str(tmp_path))  # empty store
    name, status, detail = check_trial_faults()
    assert status == WARN
    assert "RAFIKI_TRIAL_RETRY_MAX=0" in detail


def test_doctor_flags_hot_job_and_quarantine(monkeypatch, tmp_path):
    from rafiki_tpu.doctor import WARN, check_trial_faults

    monkeypatch.setenv("RAFIKI_WORKDIR", str(tmp_path))
    monkeypatch.setenv("RAFIKI_DB_PATH", str(tmp_path / "doc.sqlite3"))
    monkeypatch.setenv("RAFIKI_TRIAL_QUARANTINE_K", "3")
    db = Database(str(tmp_path / "doc.sqlite3"))
    job, sub, model = _seed_job(db)
    db.mark_train_job_as_running(job["id"])
    for _ in range(4):
        t = db.create_trial(sub["id"], model["id"], {"lr": 0.01},
                            worker_id="w")
        db.mark_trial_as_errored(t["id"], FaultKind.USER, "boom")
    db.close()
    name, status, detail = check_trial_faults()
    assert status == WARN
    assert "ERRORED" in detail
    assert "quarantined knob signatures" in detail


def test_admin_handles_fault_limit_event(tmp_path):
    from rafiki_tpu.admin.admin import Admin
    from rafiki_tpu.placement.manager import (ChipAllocator,
                                              LocalPlacementManager)

    admin = Admin(
        db=Database(":memory:"),
        placement=LocalPlacementManager(allocator=ChipAllocator([0])),
        params_dir=str(tmp_path / "params"),
    )
    try:
        job, sub, _ = _seed_job(admin.db)
        admin.db.mark_train_job_as_running(job["id"])
        admin.handle_event(EVENT_TRIAL_FAULT_LIMIT, {
            "train_job_id": job["id"],
            "sub_train_job_id": sub["id"],
            "fault_kind": FaultKind.USER,
            "reason": "drill: broken template",
        })
        refreshed = admin.db.get_train_job(job["id"])
        assert refreshed["status"] == TrainJobStatus.ERRORED
        assert refreshed["fault_kind"] == FaultKind.USER
        assert refreshed["error_reason"] == "drill: broken template"
        # fleet health exposes nothing for the now-terminal job, and the
        # trial-fault counters endpoint stays well-formed
        health = admin.get_fleet_health()
        assert "training" in health
        assert job["id"] not in health["training"]["jobs"]
    finally:
        admin.shutdown()


def test_store_errors_classify_infra_not_user():
    import sqlite3

    kind, detail = faults.classify_failure(
        sqlite3.OperationalError("database is locked"))
    assert kind == FaultKind.INFRA
    from rafiki_tpu.db.database import MetadataStoreChaosError
    kind, _ = faults.classify_failure(MetadataStoreChaosError("chaos"))
    assert kind == FaultKind.INFRA
    # a plain template exception stays USER
    kind, _ = faults.classify_failure(ValueError("bad shape"))
    assert kind == FaultKind.USER


def test_replay_guard_blocks_infeasible_only_sessions():
    cfg = {"lr": FloatKnob(1e-4, 1e-1)}
    store = AdvisorStore()
    aid = store.create_advisor(cfg, advisor_id="inf-only")
    store.feedback_infeasible(aid, {"lr": 1e-2}, FaultKind.USER)
    # the session is NOT fresh: a crash-looping worker's restarts must
    # not stack duplicate penalty points
    assert not store.replay_feedback(
        aid, [], infeasible=[({"lr": 1e-2}, FaultKind.USER)])
    assert store.get(aid).infeasible_count == 1


def test_template_network_errors_stay_user_class():
    import requests

    kind, _ = faults.classify_failure(
        requests.ConnectionError("dataset host unreachable"))
    assert kind == FaultKind.USER  # template/config bug: no free retries


def test_terminal_mem_feeds_infeasible_without_streak(tmp_path, monkeypatch):
    """A knob region that OOMs through its whole retry budget steers
    the advisor away and counts toward quarantine — but repeated MEM on
    distinct knobs must NOT fail-fast the job (host pressure, not a
    broken template)."""
    monkeypatch.setenv("RAFIKI_CHAOS", "site=trial;action=oom")
    monkeypatch.setenv("RAFIKI_TRIAL_RETRY_MAX", "0")
    monkeypatch.setenv("RAFIKI_TRIAL_FAULT_LIMIT", "2")
    db = Database(":memory:")
    job, sub, _ = _seed_job(db, budget={"MODEL_TRIAL_COUNT": 3})
    store = AdvisorStore()
    worker = TrainWorker(sub["id"], db, store,
                         params_dir=str(tmp_path / "params"))
    ctx = ServiceContext(service_id="svc-mem",
                         service_type=ServiceType.TRAIN,
                         chips=[], stop_event=threading.Event())
    worker.start(ctx)

    trials = db.get_trials_of_sub_train_job(sub["id"])
    # every trial OOMed terminally, but the job ran its full budget
    # (no USER fail-fast) and stayed un-errored at the job level
    assert len(trials) == 3
    assert all(t["fault_kind"] == FaultKind.MEM for t in trials)
    assert db.get_train_job(job["id"])["status"] != TrainJobStatus.ERRORED
    assert store.get(sub["id"]).infeasible_count >= 1
    db.close()


def test_infeasible_dedup_and_health_split():
    import numpy as np

    opt = BayesOpt(dims=1, seed=0)
    for _ in range(10):
        opt.mark_infeasible(np.array([0.5004]))  # same grid cell
    assert len(opt.infeasible_X) == 1
    opt.mark_infeasible(np.array([0.9]))
    assert len(opt.infeasible_X) == 2

    # a completed trial that absorbed a transient retry is NOT a fault
    # in the store-side health summary — it aggregates as a retry
    db = Database(":memory:")
    job, sub, model = _seed_job(db)
    db.mark_train_job_as_running(job["id"])
    t = db.create_trial(sub["id"], model["id"], {"lr": 0.01}, worker_id="w")
    db.record_trial_fault(t["id"], FaultKind.INFRA, "absorbed")
    db.mark_trial_as_complete(t["id"], 0.9, None)
    t2 = db.create_trial(sub["id"], model["id"], {"lr": 0.02}, worker_id="w")
    db.mark_trial_as_errored(t2["id"], FaultKind.USER, "boom")
    summary = db.get_trial_fault_summary_of_live_jobs()[job["id"]]
    assert summary["faults"] == {FaultKind.USER: 1}
    assert summary["retries"] == 1
    assert db.get_trial_fault_counts_of_train_job(job["id"]) == {
        FaultKind.USER: 1}
    db.close()
