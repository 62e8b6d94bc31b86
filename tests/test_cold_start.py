"""Cold-start resilience (ISSUE 17; docs/failure-model.md "Cold-start
faults"): the persistent compile cache makes a replica's SECOND boot
warm (cache hits, compile seconds ~ 0) across process death and
reschedule; warm-up runs before a replica becomes routable and its
warm state is observable; the warm standby pool turns failed-replica
replacement into an ~ms promotion with zero client-visible errors
under load; and training's reclaim drains standby chip loans FIRST.

Tier-1, CPU-only: the cache drills opt the CPU backend in
(RAFIKI_COMPILE_CACHE_CPU=1) with the min-compile-time floor at 0 so
every jit program round-trips the on-disk cache deterministically."""

import os
import threading
import time

import pytest

from rafiki_tpu import config
from rafiki_tpu.admin.admin import Admin
from rafiki_tpu.constants import ServiceType, TrainJobStatus
from rafiki_tpu.placement.hosts import ChipBudgetArbiter
from rafiki_tpu.sdk import compile_cache
from rafiki_tpu.utils import chaos
from rafiki_tpu.worker import warmup
from rafiki_tpu.worker.warmup import WarmupError, run_warmup

pytestmark = pytest.mark.chaos

FIXTURE = __file__.rsplit("/", 1)[0] + "/fixtures/fake_model.py"


def _reset_cache_state():
    import jax

    chaos.clear()
    compile_cache.reset_for_tests()
    warmup.reset_for_tests()
    # jax's own config keeps the LAST dir a test enabled; a later test
    # that expects "cache off" must not silently hit it
    jax.config.update("jax_compilation_cache_dir", None)
    jax.clear_caches()


@pytest.fixture(autouse=True)
def _clean_state():
    _reset_cache_state()
    yield
    _reset_cache_state()


@pytest.fixture
def cpu_cache(tmp_path, monkeypatch):
    """Deterministic persistent-cache setup for this CPU-only suite."""
    monkeypatch.setenv("RAFIKI_COMPILE_CACHE", "1")
    monkeypatch.setenv("RAFIKI_COMPILE_CACHE_CPU", "1")
    monkeypatch.setenv("RAFIKI_COMPILE_CACHE_MIN_COMPILE_S", "0")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("RAFIKI_COMPILE_CACHE_DIR", str(tmp_path / "xc"))
    return str(tmp_path / "xc")


def _boot(service_id, scope="job"):
    """One worker boot's warm-up: a fresh jit wrapper per boot (same
    HLO -> same cache key), exactly what a restarted process sees."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(128, 128)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(16, 128)).astype(np.float32))

    @jax.jit
    def prog(v):
        h = v
        for _ in range(16):
            h = jnp.tanh(h @ w) + jnp.cos(h)
        return h.sum()

    return run_warmup(service_id, scope, [
        ("prog", lambda: prog(x).block_until_ready())])


def _new_interpreter():
    """What a SIGKILL'd-and-replaced worker process starts with: no
    in-memory executables, no process-local cache state — only the
    shared on-disk cache."""
    import jax

    jax.clear_caches()
    compile_cache.reset_for_tests()
    warmup.reset_for_tests()


# -- THE second-boot drill (acceptance criterion) ---------------------------


def test_second_boot_is_warm_from_persistent_cache(cpu_cache, monkeypatch):
    """A rescheduled/SIGKILL'd-and-replaced worker's second boot reports
    warm=True with demonstrated cache hits and compile seconds a
    fraction of the cold boot's — the compile survived the process."""
    # a tight threshold so "warm" can only come from real cache hits
    monkeypatch.setenv("RAFIKI_COMPILE_WARM_THRESHOLD_S", "0.001")
    cold = _boot("svc-cold")
    assert cold["cache_misses"] >= 1 and cold["cache_hits"] == 0
    assert cold["warm"] is False
    assert compile_cache.active_dir().startswith(cpu_cache)

    _new_interpreter()
    warm = _boot("svc-warm")
    assert warm["warm"] is True
    assert warm["cache_hits"] >= 1 and warm["cache_misses"] == 0
    assert warm["compile_s"] <= 0.5 * cold["compile_s"]
    # the stats-row fields every worker relays to fleet health
    row = warmup.stats_row_fields("svc-warm")
    assert row["warm"] == 1 and row["compile_cache_hits"] >= 1
    assert warmup.stats_row_fields("svc-nobody") == {}


def test_cache_dir_is_used_as_it_is(cpu_cache):
    """No topology sub-directory: JAX's own key covers device kind and
    versions, so entries land directly in the configured directory."""
    import os

    assert compile_cache.enable() == cpu_cache
    assert compile_cache.stats()["dir"] == cpu_cache
    _boot("svc-flat")
    entries = [n for n in os.listdir(cpu_cache)]
    assert entries and all(
        os.path.isfile(os.path.join(cpu_cache, n)) for n in entries)


def test_jax_compilation_cache_dir_wins_and_is_not_set_in_code(
        tmp_path, monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set the cache is that directory,
    as it is, and no code sets another: JAX reads the variable itself.
    Checked in a child interpreter, where JAX really does."""
    import json
    import subprocess
    import sys

    placed = tmp_path / "placed"
    other = tmp_path / "rafiki-root"
    code = (
        "import json, jax\n"
        "calls = []\n"
        "real = jax.config.update\n"
        "def spy(name, value):\n"
        "    calls.append(name)\n"
        "    return real(name, value)\n"
        "jax.config.update = spy\n"
        "from rafiki_tpu.sdk import compile_cache\n"
        "d = compile_cache.enable()\n"
        "jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones((64, 64)))"
        ".block_until_ready()\n"
        "print(json.dumps({'dir': d, 'stats': compile_cache.stats()['dir'],"
        " 'jax': jax.config.jax_compilation_cache_dir, 'calls': calls}))\n"
    )
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(placed),
               RAFIKI_COMPILE_CACHE_DIR=str(other),
               RAFIKI_COMPILE_CACHE="1", RAFIKI_COMPILE_CACHE_CPU="1",
               RAFIKI_COMPILE_CACHE_MIN_COMPILE_S="0",
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    out = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, timeout=240)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["dir"] == rec["stats"] == rec["jax"] == str(placed)
    assert "jax_compilation_cache_dir" not in rec["calls"]
    assert os.listdir(placed), "the placed directory received the entries"
    assert not other.exists()


def test_default_cache_dir_is_fixed_in_the_checkout(monkeypatch, tmp_path):
    """Unset, the cache is <checkout>/xla_cache: nothing in the path
    depends on the pid, the time, the cwd or RAFIKI_WORKDIR."""
    import os

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("RAFIKI_COMPILE_CACHE_DIR", raising=False)
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(checkout, "xla_cache")
    assert compile_cache.cache_dir() == want
    monkeypatch.setenv("RAFIKI_WORKDIR", str(tmp_path))
    monkeypatch.chdir(tmp_path)
    assert compile_cache.cache_dir() == want
    assert str(os.getpid()) not in want
    monkeypatch.setenv("RAFIKI_COMPILE_CACHE_DIR", str(tmp_path / "r"))
    assert compile_cache.cache_dir() == str(tmp_path / "r")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "j"))
    assert compile_cache.cache_dir() == str(tmp_path / "j")


# -- typed degrade paths ----------------------------------------------------


def test_cpu_backend_opted_out_by_default(tmp_path, monkeypatch):
    monkeypatch.setenv("RAFIKI_COMPILE_CACHE", "1")
    monkeypatch.delenv("RAFIKI_COMPILE_CACHE_CPU", raising=False)
    monkeypatch.setenv("RAFIKI_COMPILE_CACHE_DIR", str(tmp_path / "xc"))
    assert compile_cache.enable() is None
    assert "cpu backend" in compile_cache.stats()["reason"]
    # the worker still boots and serves — it just compiles fresh
    report = _boot("svc-nocache")
    assert report["cache_hits"] == 0 and report["compile_s"] > 0


def test_unusable_cache_dir_degrades_typed_not_crash(tmp_path, monkeypatch):
    blocker = tmp_path / "not-a-dir"
    blocker.write_text("file where the cache root should be")
    monkeypatch.setenv("RAFIKI_COMPILE_CACHE", "1")
    monkeypatch.setenv("RAFIKI_COMPILE_CACHE_CPU", "1")
    monkeypatch.setenv("RAFIKI_COMPILE_CACHE_DIR", str(blocker))
    assert compile_cache.enable() is None
    assert "unusable dir" in compile_cache.stats()["reason"]
    report = _boot("svc-baddir")  # fresh compile, no crash
    assert report["cache_hits"] == 0


def test_disabled_cache_reports_reason(monkeypatch):
    monkeypatch.setenv("RAFIKI_COMPILE_CACHE", "0")
    assert compile_cache.enable() is None
    assert "RAFIKI_COMPILE_CACHE=0" in compile_cache.stats()["reason"]


# -- chaos site=compile drills ----------------------------------------------


def test_chaos_corrupt_cache_recompiles_fresh_and_self_heals(
        cpu_cache, monkeypatch):
    """Bit-rot drill: every on-disk entry garbled between boots — the
    second boot absorbs the damage (JAX's reader warns), recompiles
    fresh, SERVES, and evicts the unreadable entries (jax never
    overwrites them in place, so without the eviction every later boot
    would stay cold forever). The following boot rewrites the cache and
    the one after that is warm again."""
    monkeypatch.setenv("RAFIKI_COMPILE_WARM_THRESHOLD_S", "0.001")
    _boot("svc-seed")
    _new_interpreter()
    chaos.install([chaos.ChaosRule(
        site=chaos.SITE_COMPILE, action=chaos.ACTION_CORRUPT,
        match="job/svc-rot")])
    report = _boot("svc-rot")
    assert report["cache_hits"] == 0 and report["cache_misses"] >= 1
    assert report["warnings"] == []  # degrade, not a program failure
    assert report["evicted"] >= 1  # the self-heal
    chaos.clear()
    # next boot: a CLEAN miss (no unreadable entry left) that rewrites
    _new_interpreter()
    rewrite = _boot("svc-rewrite")
    assert rewrite["evicted"] == 0 and rewrite["cache_misses"] >= 1
    # ...and the boot after that is warm again
    _new_interpreter()
    assert _boot("svc-after-rot")["warm"] is True


def test_chaos_compile_error_fails_boot_typed(cpu_cache):
    chaos.install([chaos.ChaosRule(
        site=chaos.SITE_COMPILE, action=chaos.ACTION_ERROR,
        match="job/svc-err")])
    with pytest.raises(WarmupError):
        _boot("svc-err")
    # unmatched services are untouched
    assert _boot("svc-ok")["compile_s"] >= 0


def test_chaos_compile_delay_stretches_warmup(cpu_cache):
    """Slow-compile drill: the injected delay lands INSIDE the warm-up
    window (before ctx.ready() in a real worker), so a still-warming
    replica is simply not routable yet."""
    chaos.install([chaos.ChaosRule(
        site=chaos.SITE_COMPILE, action=chaos.ACTION_DELAY,
        match="job/svc-slow", delay_s=0.3)])
    t0 = time.monotonic()
    report = _boot("svc-slow")
    assert time.monotonic() - t0 >= 0.3
    assert report["compile_s"] >= 0.3


def test_chaos_corrupt_rejected_outside_wire_and_compile():
    with pytest.raises(chaos.ChaosSpecError):
        chaos.ChaosRule(site=chaos.SITE_TRIAL, action=chaos.ACTION_CORRUPT)
    chaos.ChaosRule(site=chaos.SITE_COMPILE, action=chaos.ACTION_CORRUPT)


def test_warmup_absorbs_program_failure_warn_only(cpu_cache):
    def broken():
        raise RuntimeError("optional warm-up path broke")

    report = run_warmup("svc-warnonly", "job", [("broken", broken)])
    assert len(report["warnings"]) == 1
    assert "optional warm-up path broke" in report["warnings"][0]


def test_note_first_program_is_one_shot(monkeypatch):
    monkeypatch.setenv("RAFIKI_COMPILE_WARM_THRESHOLD_S", "1.0")
    warmup.note_first_program("svc-t", "sub", "first_trial", 0.2, 0)
    r = warmup.warmup_stats("svc-t")
    assert r["warm"] is True and r["cache_misses"] == 1
    # later programs never overwrite the boot's cold-start verdict
    warmup.note_first_program("svc-t", "sub", "later", 99.0, 0)
    assert warmup.warmup_stats("svc-t")["compile_s"] == 0.2


# -- durable standby flag + arbiter tagging ---------------------------------


def test_standby_column_roundtrip_and_migration(tmp_path):
    from rafiki_tpu.db.database import Database

    db = Database(str(tmp_path / "meta.sqlite3"))
    try:
        uid = db.create_user("a@b", "x", "ADMIN")["id"]
        tj = db.create_train_job(uid, "app", 1, "T", "uri://t", "uri://e",
                                 {})
        model = db.create_model(uid, "m", "T", b"", "M", {}, "PRIVATE")
        sub = db.create_sub_train_job(tj["id"], model["id"])
        trial = db.create_trial(sub["id"], model["id"], {})
        inf = db.create_inference_job(uid, tj["id"])
        svc = db.create_service(ServiceType.INFERENCE)
        w = db.create_inference_job_worker(
            svc["id"], inf["id"], trial["id"], standby=True)
        assert int(w["standby"]) == 1
        assert int(db.get_inference_job_worker(svc["id"])["standby"]) == 1
        db.set_worker_standby(svc["id"], False)
        assert int(db.get_inference_job_worker(svc["id"])["standby"]) == 0
    finally:
        db.close()


class _FakeAllocator:
    def __init__(self, total, free):
        self.total_chips = total
        self.free_chips = free


def test_arbiter_standby_tagging_and_loan_split():
    arb = ChipBudgetArbiter(_FakeAllocator(total=8, free=8))
    arb.note_borrow("svc-serve", "job-1", [0])
    arb.note_borrow("svc-stby", "job-1", [1, 2])
    arb.mark_standby("svc-stby", True)
    arb.mark_standby("svc-ghost", True)  # not a loan: ignored
    assert set(arb.standby_loans()) == {"svc-stby"}
    assert arb.loan_split() == {"serving": 1, "standby": 2}
    # a returned loan drops its tag with it
    arb.note_return("svc-stby")
    assert arb.standby_loans() == {}
    assert arb.loan_split() == {"serving": 1, "standby": 0}


# -- warm standby pool: place / promote / replace / reclaim -----------------


def _add_app(admin, app):
    auth = admin.authenticate_user(
        config.SUPERADMIN_EMAIL, config.SUPERADMIN_PASSWORD)
    uid = auth["user_id"]
    if admin.db.get_model_by_name(uid, "fake") is None:
        with open(FIXTURE, "rb") as f:
            admin.create_model(uid, "fake", "IMAGE_CLASSIFICATION",
                               f.read(), "FakeModel")
    admin.create_train_job(
        uid, app, "IMAGE_CLASSIFICATION", "uri://t", "uri://e",
        budget={"MODEL_TRIAL_COUNT": 1, "CHIP_COUNT": 0})
    job = admin.wait_until_train_job_stopped(uid, app, timeout_s=60)
    assert job["status"] == TrainJobStatus.STOPPED, job
    admin.create_inference_job(uid, app)
    return uid


def _job_id(admin, uid, app):
    tj = admin.db.get_train_job_by_app_version(uid, app, -1)
    return admin.db.get_running_inference_job_of_train_job(tj["id"])["id"]


def test_standby_is_placed_warm_but_never_routed(tmp_workdir, monkeypatch):
    admin = Admin(params_dir=str(tmp_workdir / "params"))
    try:
        uid = _add_app(admin, "wp")
        job_id = _job_id(admin, uid, "wp")
        live0 = admin.services.live_inference_workers(job_id)
        sid = admin.services.create_standby_replica(job_id)
        # loaded + RUNNING, out of the routable set, adoptable shape
        standbys = admin.services.standby_workers(job_id)
        assert [w["service_id"] for w in standbys] == [sid]
        assert len(admin.services.live_inference_workers(job_id)) == \
            len(live0)
        # the in-process worker ran its warm-up BEFORE ctx.ready()
        assert warmup.warmup_stats(sid) != {}
        # fleet health surfaces the pool and per-replica warm state
        fh = admin.get_fleet_health()
        assert fh["warm_pool"]["enabled"] is False
        assert "warm" in fh["serving"]["workers"].get(sid, {})
    finally:
        admin.shutdown()


def test_killed_replica_replaced_from_standby_zero_errors_under_load(
        tmp_workdir, monkeypatch):
    """THE warm-pool drill: a routable replica dies under concurrent
    load; the pool promotes a standby immediately (an add_worker route)
    and no client sees an error — the job never leaves RUNNING."""
    admin = Admin(params_dir=str(tmp_workdir / "params"))
    try:
        uid = _add_app(admin, "kill")
        job_id = _job_id(admin, uid, "kill")
        assert admin.predict(uid, "kill", [[0.0]])  # predictor live
        stby = admin.services.create_standby_replica(job_id)
        victim = admin.services.live_inference_workers(
            job_id)[0]["service_id"]

        errors, lock = [], threading.Lock()
        stop = threading.Event()

        def client():
            while not stop.is_set():
                try:
                    admin.predict(uid, "kill", [[0.0]])
                except Exception as e:
                    with lock:
                        errors.append(repr(e))

        threads = [threading.Thread(target=client) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.3)
        admin._on_service_status(victim, "ERRORED")  # the SIGKILL verdict
        time.sleep(0.5)
        stop.set()
        for t in threads:
            t.join(timeout=10)

        assert errors == []
        live = [w["service_id"]
                for w in admin.services.live_inference_workers(job_id)]
        assert stby in live and victim not in live
        assert admin.services.standby_workers(job_id) == []
        assert admin.db.get_inference_job(job_id)["status"] == "RUNNING"
        events = [e["action"] for e in admin.warm_pool.events]
        assert "replace" in events
    finally:
        admin.shutdown()


def test_scale_up_prefers_promotion_over_deploy(tmp_workdir, monkeypatch):
    admin = Admin(params_dir=str(tmp_workdir / "params"))
    try:
        uid = _add_app(admin, "promo")
        job_id = _job_id(admin, uid, "promo")
        assert admin.predict(uid, "promo", [[0.0]])
        stby = admin.services.create_standby_replica(job_id)
        n_live = len(admin.services.live_inference_workers(job_id))
        t0 = time.monotonic()
        report = admin.services.scale_inference_job(job_id, 1)
        promote_s = time.monotonic() - t0
        assert report["added"] == [stby]
        assert report["borrowed_chips"] == 0  # the standby held its own
        assert len(admin.services.live_inference_workers(job_id)) == \
            n_live + 1
        # no deploy happened: promotion is a flag flip + route
        assert promote_s < 5.0
    finally:
        admin.shutdown()


def test_warm_pool_tick_tops_up_shrinks_and_retires_stale(
        tmp_workdir, monkeypatch):
    admin = Admin(params_dir=str(tmp_workdir / "params"))
    try:
        uid = _add_app(admin, "pool")
        job_id = _job_id(admin, uid, "pool")
        monkeypatch.setenv("RAFIKI_AUTOSCALE_WARM_POOL", "2")
        admin.warm_pool.tick()
        standbys = admin.services.standby_workers(job_id)
        assert len(standbys) == 2
        # K lowered -> the pool shrinks and frees the chips
        monkeypatch.setenv("RAFIKI_AUTOSCALE_WARM_POOL", "1")
        admin.warm_pool.tick()
        standbys = admin.services.standby_workers(job_id)
        assert len(standbys) == 1
        # a rollout advances the group past the standby: retired, and
        # (same tick) replaced by a fresh-version one
        trial = standbys[0]["trial_id"]
        svc = admin.db.create_service(ServiceType.INFERENCE)
        admin.db.create_inference_job_worker(
            svc["id"], job_id, trial, model_version=3)
        admin.db.mark_service_as_running(svc["id"])
        stale_sid = standbys[0]["service_id"]
        actions = admin.warm_pool.tick()
        assert "retire_stale" in [a["action"] for a in actions]
        now = admin.services.standby_workers(job_id)
        assert stale_sid not in [w["service_id"] for w in now]
        assert all(w["model_version"] >= 3 for w in now)
        rep = admin.warm_pool.report()
        assert rep["target_per_job"] == 1
    finally:
        admin.shutdown()


def test_warm_pool_bounded_retries_then_degraded_then_recovers(
        tmp_workdir, monkeypatch):
    admin = Admin(params_dir=str(tmp_workdir / "params"))
    try:
        uid = _add_app(admin, "deg")
        job_id = _job_id(admin, uid, "deg")
        monkeypatch.setenv("RAFIKI_AUTOSCALE_WARM_POOL", "1")
        monkeypatch.setenv("RAFIKI_AUTOSCALE_WARM_RETRY_MAX", "2")
        monkeypatch.setenv("RAFIKI_AUTOSCALE_WARM_RETRY_COOLDOWN_S", "0.2")

        real = admin.services.create_standby_replica

        def broken(_job_id):
            raise RuntimeError("no capacity for standbys")

        monkeypatch.setattr(admin.services, "create_standby_replica",
                            broken)
        admin.warm_pool.tick()  # failure 1
        admin.warm_pool.tick()  # failure 2 -> DEGRADED, cooldown starts
        rep = admin.warm_pool.report()
        assert rep["jobs"][job_id]["degraded"] is True
        assert "no capacity" in str(rep["jobs"][job_id]["last_error"])
        assert "degraded" in [e["action"] for e in admin.warm_pool.events]
        # during the cooldown the loop does NOT hammer placement
        admin.warm_pool.tick()
        assert admin.services.standby_workers(job_id) == []
        # cooldown expires, capacity is back: the pool heals itself
        monkeypatch.setattr(admin.services, "create_standby_replica", real)
        time.sleep(0.25)
        admin.warm_pool.tick()
        assert len(admin.services.standby_workers(job_id)) == 1
    finally:
        admin.shutdown()


def test_training_reclaim_drains_standbys_first(tmp_workdir, monkeypatch):
    """Chip arbitration order: when training calls its loans, standby
    loans are destroyed FIRST (they serve no traffic); routable borrowed
    replicas only drain if standbys did not satisfy the demand."""
    monkeypatch.setenv("RAFIKI_AUTOSCALE_TRAIN_FLOOR", "1")
    admin = Admin(params_dir=str(tmp_workdir / "params"))
    try:
        uid = _add_app(admin, "rec")
        job_id = _job_id(admin, uid, "rec")
        # a borrowed ROUTABLE replica, then a borrowed STANDBY
        r = admin.services.scale_inference_job(job_id, 1)
        assert r["borrowed_chips"] == 1
        routable_sid = r["added"][0]
        stby = admin.services.create_standby_replica(job_id)
        assert stby in admin.chip_arbiter.standby_loans()
        assert admin.chip_arbiter.loan_split() == {
            "serving": 1, "standby": 1}

        freed = admin.chip_arbiter.reclaim_for_training(1)
        assert freed == 1
        # the standby died for the cause; the serving replica lives
        assert admin.services.standby_workers(job_id) == []
        assert routable_sid in [
            w["service_id"]
            for w in admin.services.live_inference_workers(job_id)]
        assert admin.chip_arbiter.loan_split() == {
            "serving": 1, "standby": 0}
        assert admin.predict(uid, "rec", [[0.0]])
    finally:
        admin.shutdown()


def test_recovery_readopts_standby_flag_and_loan_tag(tmp_workdir,
                                                     monkeypatch):
    """Admin restart: the durable standby column re-enters the arbiter's
    loan book standby-tagged, and the adopted standby stays OUT of the
    routable set — reclaim-priority survives the control plane dying."""
    from rafiki_tpu.db.database import Database

    monkeypatch.setenv("RAFIKI_AUTOSCALE_TRAIN_FLOOR", "1")
    db = Database(str(tmp_workdir / "meta.sqlite3"))
    admin = Admin(db=db, params_dir=str(tmp_workdir / "params"))
    try:
        uid = _add_app(admin, "radopt")
        job_id = _job_id(admin, uid, "radopt")
        stby = admin.services.create_standby_replica(job_id)
        assert stby in admin.chip_arbiter.standby_loans()
        row = db.get_inference_job_worker(stby)
        assert int(row["standby"]) == 1
        # the durable half of the loan book: a fresh arbiter re-reads it
        loans = {sid: j for sid, (j, _c) in
                 admin.chip_arbiter.borrowed().items()}
        assert loans.get(stby) == job_id
    finally:
        admin.shutdown()
        db.close()


# -- observability surfaces -------------------------------------------------


def test_predictor_healthz_reports_replica_warm_state():
    from rafiki_tpu.cache.queue import InProcessBroker
    from rafiki_tpu.predictor.predictor import Predictor
    from rafiki_tpu.predictor.server import PredictorServer
    import json
    import urllib.request

    broker = InProcessBroker()
    server = None
    try:
        broker.register_worker("job-hz", "svc-hz")
        warmup.note_first_program("svc-hz", "job-hz", "warm_up", 0.01, 1)
        predictor = Predictor("job-hz", broker, "IMAGE_CLASSIFICATION",
                              worker_trials={"svc-hz": "t1"})
        server = PredictorServer(predictor, "job-hz", auth=False).start()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/healthz", timeout=5) as r:
            payload = json.load(r)
        rep = payload["replicas"]["svc-hz"]
        assert rep["warm"] is True and rep["cache_hits"] == 1
    finally:
        if server is not None:
            server.stop(drain_timeout_s=0.0)
        close = getattr(broker, "close", None)
        if close is not None:
            close()


def test_doctor_compile_cache_check(tmp_workdir, monkeypatch):
    from rafiki_tpu import doctor

    # healthy defaults: PASS (fleet size passed in: no agent probing)
    monkeypatch.setenv("RAFIKI_COMPILE_CACHE", "1")
    name, status, detail = doctor.check_compile_cache(total_chips=8)
    assert name == "compile cache" and status == doctor.PASS, detail
    # cache off while the warm pool is on: the pool's whole point is gone
    monkeypatch.setenv("RAFIKI_COMPILE_CACHE", "0")
    monkeypatch.setenv("RAFIKI_AUTOSCALE_WARM_POOL", "1")
    _, status, detail = doctor.check_compile_cache(total_chips=8)
    assert status == doctor.WARN and "RAFIKI_COMPILE_CACHE=0" in detail
    # a warm-pool floor no fleet could hold
    monkeypatch.setenv("RAFIKI_COMPILE_CACHE", "1")
    monkeypatch.setenv("RAFIKI_AUTOSCALE_WARM_POOL", "64")
    _, status, detail = doctor.check_compile_cache(total_chips=2)
    assert status == doctor.WARN and "exceeds" in detail
