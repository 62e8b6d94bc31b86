"""Vectorized trial execution (vmap-over-knobs): the shape-bucketing
partitioner, the batched-proposal advisor API on every layer (advisor /
store / HTTP / client / remote-store fallback), and the end-to-end
contract — a real CPU train job in vmapped mode proving that
MODEL_TRIAL_COUNT=N yields exactly N scored trials, that K distinct knob
vectors train in ONE PopulationTrainer.fit call, that per-member scores
feed the advisor individually, and that one member's invalid score
faults that member only (never the batch)."""

import os

import numpy as np
import pytest

from rafiki_tpu import config as rconfig
from rafiki_tpu.admin.admin import Admin
from rafiki_tpu.advisor.advisor import Advisor, AdvisorStore, RandomAdvisor
from rafiki_tpu.constants import TrialStatus
from rafiki_tpu.db.database import Database
from rafiki_tpu.placement.manager import ChipAllocator, LocalPlacementManager
from rafiki_tpu.sdk.knob import (
    CategoricalKnob,
    FixedKnob,
    FloatKnob,
    IntegerKnob,
    serialize_knob_config,
)
from rafiki_tpu.sdk import population as population_mod
from rafiki_tpu.worker.train import TrainWorker
from rafiki_tpu.worker.vmap_partition import (
    partition_for_vmap,
    static_signature,
)

POP_FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                           "pop_model.py")
FAKE_FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                            "fake_model.py")


# -- shape-bucketing partitioner (pure) --------------------------------------

def test_partition_architecture_knobs_split():
    # same dynamic knob (lr) but two widths: two buckets, order preserved
    knobs = [
        {"width": 16, "lr": 0.1},
        {"width": 32, "lr": 0.2},
        {"width": 16, "lr": 0.3},
        {"width": 32, "lr": 0.4},
    ]
    buckets = partition_for_vmap(knobs, ("lr",))
    assert buckets == [
        [{"width": 16, "lr": 0.1}, {"width": 16, "lr": 0.3}],
        [{"width": 32, "lr": 0.2}, {"width": 32, "lr": 0.4}],
    ]


def test_partition_pure_hp_knobs_stack_and_cap():
    # only dynamic knobs differ: ONE bucket; max_members chunks it
    knobs = [{"width": 8, "lr": 0.01 * (i + 1)} for i in range(5)]
    assert partition_for_vmap(knobs, ("lr",)) == [knobs]
    capped = partition_for_vmap(knobs, ("lr",), max_members=2)
    assert [len(b) for b in capped] == [2, 2, 1]
    assert [m for b in capped for m in b] == knobs  # order preserved


def test_partition_single_knob_degenerate_bucket():
    assert partition_for_vmap([], ("lr",)) == []
    one = [{"lr": 0.5}]
    assert partition_for_vmap(one, ("lr",)) == [one]
    # every knob dynamic -> one bucket regardless of values
    many = [{"lr": 0.1}, {"lr": 0.9}]
    assert partition_for_vmap(many, ("lr",)) == [many]


def test_static_signature_ignores_dynamic_and_orders_keys():
    a = static_signature({"b": 2, "a": 1, "lr": 0.5}, ("lr",))
    b = static_signature({"a": 1, "lr": 0.7, "b": 2}, ("lr",))
    assert a == b
    assert static_signature({"a": 2, "lr": 0.5}, ("lr",)) != a


# -- batched-proposal advisor API --------------------------------------------

def _knob_config():
    return {
        "lr": FloatKnob(1e-4, 1e-1, is_exp=True),
        "depth": IntegerKnob(1, 4),
        "act": CategoricalKnob(["relu", "gelu"]),
        "pin": FixedKnob("x"),
    }


def test_gp_propose_batch_spreads_via_fantasies():
    adv = Advisor(_knob_config(), seed=0)
    # past warmup so the GP (not the warmup sampler) makes the batch
    for i in range(3):
        adv.feedback(adv.propose(), 0.1 * i)
    assert len(adv._opt.pending_X) == 0  # feedback retired each fantasy
    batch = adv.propose_batch(4)
    assert len(batch) == 4
    # each draw registered a pending fantasy (the constant-liar spread)
    assert len(adv._opt.pending_X) == 4
    # distinct points (continuous lr dimension): no two draws identical
    assert len({str(sorted(k.items())) for k in batch}) == 4
    # the batch return leg retires them member-by-member
    n = adv.feedback_batch([(k, 0.5) for k in batch])
    assert n == 4
    assert len(adv._opt.pending_X) == 0
    assert adv.observation_count == 7


def test_random_advisor_propose_batch():
    adv = RandomAdvisor(_knob_config(), seed=1)
    batch = adv.propose_batch(3)
    assert len(batch) == 3
    for k in batch:
        assert set(k) == {"lr", "depth", "act", "pin"}


def test_store_falls_back_for_legacy_advisor_without_batch():
    class LegacyAdvisor:
        """Duck-typed pre-batch-API advisor: propose/feedback only."""

        def __init__(self):
            self.proposals = 0
            self.scores = []

        def propose(self):
            self.proposals += 1
            return {"lr": 0.01 * self.proposals}

        def feedback(self, knobs, score):
            self.scores.append((knobs, score))

    store = AdvisorStore()
    legacy = LegacyAdvisor()
    store._advisors["old"] = legacy
    batch = store.propose_batch("old", 3)
    assert len(batch) == 3 and legacy.proposals == 3
    assert store.feedback_batch("old", [(k, 1.0) for k in batch]) == 3
    assert len(legacy.scores) == 3


def test_worker_batch_drain_falls_back_for_legacy_store():
    class LegacyStore:
        """Duck-typed pre-batch-API advisor STORE (no propose_batch)."""

        def __init__(self):
            self.proposals = 0

        def propose(self, advisor_id):
            self.proposals += 1
            return {"lr": 0.01 * self.proposals}

    stub = LegacyStore()
    worker = TrainWorker("sub", db=None, advisor_store=stub)
    draws = worker._propose_batch_clear_of_quarantine("aid", 3)
    assert len(draws) == 3 and stub.proposals == 3


def test_http_batch_routes(tmp_path):
    from rafiki_tpu.admin.http import AdminServer
    from rafiki_tpu.client.client import Client

    admin = Admin(
        db=Database(":memory:"),
        placement=LocalPlacementManager(allocator=ChipAllocator([0])),
        params_dir=str(tmp_path / "params"),
    )
    srv = AdminServer(admin, port=0).start()
    try:
        c = Client("127.0.0.1", srv.port)
        c.login(rconfig.SUPERADMIN_EMAIL, rconfig.SUPERADMIN_PASSWORD)
        aid = c.create_advisor(serialize_knob_config(_knob_config()))
        batch = c.propose_knobs_batch(aid, 3)
        assert len(batch) == 3
        for k in batch:
            assert set(k) == {"lr", "depth", "act", "pin"}
        assert c.feedback_knobs_batch(
            aid, [(k, float(i)) for i, k in enumerate(batch)]) == 3
        assert admin.advisor_store.get(aid).observation_count == 3
    finally:
        srv.stop()
        admin.shutdown()


def test_remote_store_falls_back_on_old_admin():
    from rafiki_tpu.advisor.remote import RemoteAdvisorStore
    from rafiki_tpu.client.client import RafikiError

    class OldAdminClient:
        def __init__(self):
            self.batch_calls = 0
            self.single_proposes = 0
            self.single_feedbacks = 0

        def propose_knobs_batch(self, aid, k):
            self.batch_calls += 1
            raise RafikiError("No route POST /advisors/x/propose_batch",
                              status=404)

        def feedback_knobs_batch(self, aid, items):
            self.batch_calls += 1
            raise RafikiError("No route POST /advisors/x/feedback_batch",
                              status=404)

        def propose_knobs(self, aid):
            self.single_proposes += 1
            return {"lr": 0.01 * self.single_proposes}

        def feedback_knobs(self, aid, knobs, score):
            self.single_feedbacks += 1
            return {"lr": 0.5}

    client = OldAdminClient()
    store = RemoteAdvisorStore(client)
    draws = store.propose_batch("a", 3)
    assert len(draws) == 3
    assert client.batch_calls == 1 and client.single_proposes == 3
    # the no-batch-API verdict is cached: no second probe
    store.propose_batch("a", 2)
    assert client.batch_calls == 1 and client.single_proposes == 5
    assert store.feedback_batch("a", [({"lr": 0.1}, 1.0)]) == 1
    assert client.batch_calls == 1 and client.single_feedbacks == 1


def test_remote_store_does_not_latch_on_transient_error():
    """A transient refusal (503 shed, flaky 500) must NOT permanently
    downgrade the session to serial proposals — only a 404 (missing
    route: a pre-batch-API admin) latches the fallback."""
    from rafiki_tpu.advisor.remote import RemoteAdvisorStore
    from rafiki_tpu.client.client import RafikiError

    class FlakyAdminClient:
        def __init__(self):
            self.batch_calls = 0

        def propose_knobs_batch(self, aid, k):
            self.batch_calls += 1
            if self.batch_calls == 1:
                raise RafikiError("server overloaded", status=503)
            return [{"lr": 0.01}] * k

    client = FlakyAdminClient()
    store = RemoteAdvisorStore(client)
    with pytest.raises(RafikiError):
        store.propose_batch("a", 2)
    # the verdict was NOT latched: the next round retries the batch route
    assert store.propose_batch("a", 2) == [{"lr": 0.01}] * 2
    assert client.batch_calls == 2


# -- end-to-end: a real vmapped train job on CPU -----------------------------

@pytest.fixture()
def pop_admin(tmp_path):
    a = Admin(
        db=Database(":memory:"),
        placement=LocalPlacementManager(allocator=ChipAllocator([0])),
        params_dir=str(tmp_path / "params"),
    )
    yield a
    a.shutdown()


def _write_datasets(tmp_path):
    from rafiki_tpu.sdk.dataset import write_numpy_dataset

    rng = np.random.default_rng(0)
    y = rng.integers(0, 3, size=96).astype(np.int32)
    x = (0.5 * rng.normal(size=(96, 8)) + y[:, None]).astype(np.float32)
    train = write_numpy_dataset(x, y, str(tmp_path / "train.npz"))
    test = write_numpy_dataset(x[:32], y[:32], str(tmp_path / "test.npz"))
    return train, test


def _register_pop_model(admin, name="popfix"):
    from rafiki_tpu import config

    auth = admin.authenticate_user(
        config.SUPERADMIN_EMAIL, config.SUPERADMIN_PASSWORD)
    with open(POP_FIXTURE, "rb") as f:
        admin.create_model(auth["user_id"], name, "IMAGE_CLASSIFICATION",
                           f.read(), "PopFixtureModel")
    return auth["user_id"]


def test_vmapped_train_job_budget_and_fit_batching(pop_admin, tmp_path,
                                                   monkeypatch):
    """The tier-1 acceptance drill: MODEL_TRIAL_COUNT=5 at K=2 yields
    EXACTLY 5 scored trials, trained as fit batches [2, 2, 1] — two
    vmapped programs of 2 distinct knob vectors plus the scalar
    remainder — with every member's score fed back individually."""
    monkeypatch.delenv("RAFIKI_TRIAL_VMAP", raising=False)  # default on
    train_uri, test_uri = _write_datasets(tmp_path)
    uid = _register_pop_model(pop_admin)
    population_mod.reset_fit_stats()
    pop_admin.create_train_job(
        uid, "vmapapp", "IMAGE_CLASSIFICATION", train_uri, test_uri,
        budget={"MODEL_TRIAL_COUNT": 5, "CHIP_COUNT": 1,
                "TRIAL_VMAP_K": 2},
    )
    job = pop_admin.wait_until_train_job_stopped(uid, "vmapapp",
                                                 timeout_s=120)
    assert job["status"] == "STOPPED"
    trials = pop_admin.get_trials_of_train_job(uid, "vmapapp")
    completed = [t for t in trials if t["status"] == TrialStatus.COMPLETED]
    # exactly the budget — K=2 not dividing N=5 changed nothing
    assert len(trials) == 5 and len(completed) == 5
    for t in completed:
        assert t["score"] is not None and np.isfinite(t["score"])
    # K distinct knob vectors per vmapped program: 2 two-member fits,
    # then the remainder as a population of one (fixture's scalar path)
    assert population_mod.FIT_STATS["fit_calls"] == 3
    assert population_mod.FIT_STATS["member_counts"] == [2, 2, 1]
    # five distinct proposals, each fed back individually
    lrs = {round(float(t["knobs"]["lr"]), 12) for t in completed}
    assert len(lrs) == 5
    subs = pop_admin.db.get_sub_train_jobs_of_train_job(
        pop_admin.db.get_train_job_by_app_version(uid, "vmapapp", -1)["id"])
    advisor = pop_admin.advisor_store.get(subs[0]["id"])
    assert advisor.observation_count == 5
    # every member's params are a loadable artifact (winner-ready)
    for t in completed:
        blob = pop_admin.get_trial_params(t["id"])
        assert isinstance(blob, bytes) and len(blob) > 0


def test_one_member_fault_is_isolated(pop_admin, tmp_path, monkeypatch):
    """Chaos drill: one member of a vmapped batch reports NaN — that
    member alone becomes a typed INVALID_SCORE fault + an infeasible
    observation; its batch siblings complete, and the N-row budget
    contract holds."""
    monkeypatch.delenv("RAFIKI_TRIAL_VMAP", raising=False)
    sentinel = tmp_path / "nan_once"
    sentinel.write_text("poison member 0 of the first batch")
    monkeypatch.setenv("RAFIKI_POPFIX_NAN_FILE", str(sentinel))
    train_uri, test_uri = _write_datasets(tmp_path)
    uid = _register_pop_model(pop_admin)
    population_mod.reset_fit_stats()
    pop_admin.create_train_job(
        uid, "nanapp", "IMAGE_CLASSIFICATION", train_uri, test_uri,
        budget={"MODEL_TRIAL_COUNT": 4, "CHIP_COUNT": 1,
                "TRIAL_VMAP_K": 2},
    )
    pop_admin.wait_until_train_job_stopped(uid, "nanapp", timeout_s=120)
    assert not sentinel.exists()  # the drill fired
    trials = pop_admin.get_trials_of_train_job(uid, "nanapp")
    completed = [t for t in trials if t["status"] == TrialStatus.COMPLETED]
    errored = [t for t in trials if t["status"] == TrialStatus.ERRORED]
    # budget contract: 4 rows total; the faulted member burned its slot
    # (INVALID_SCORE is terminal, exactly like the scalar classification)
    assert len(trials) == 4
    assert len(errored) == 1 and len(completed) == 3
    assert errored[0]["fault_kind"] == "INVALID_SCORE"
    # both vmapped batches ran as 2-member programs: the fault did not
    # abort its batch (the sibling of the NaN member completed)
    assert population_mod.FIT_STATS["member_counts"] == [2, 2]
    subs = pop_admin.db.get_sub_train_jobs_of_train_job(
        pop_admin.db.get_train_job_by_app_version(uid, "nanapp", -1)["id"])
    advisor = pop_admin.advisor_store.get(subs[0]["id"])
    assert advisor.observation_count == 3
    assert advisor.infeasible_count == 1


def test_scalar_model_unchanged_with_vmap_enabled(pop_admin, tmp_path,
                                                  monkeypatch):
    """A template with no population capability runs exactly as before
    even with population mode on — automatic scalar fallback."""
    monkeypatch.delenv("RAFIKI_TRIAL_VMAP", raising=False)
    from rafiki_tpu import config

    auth = pop_admin.authenticate_user(
        config.SUPERADMIN_EMAIL, config.SUPERADMIN_PASSWORD)
    uid = auth["user_id"]
    with open(FAKE_FIXTURE, "rb") as f:
        pop_admin.create_model(uid, "fake", "IMAGE_CLASSIFICATION",
                               f.read(), "FakeModel")
    population_mod.reset_fit_stats()
    pop_admin.create_train_job(
        uid, "scalarapp", "IMAGE_CLASSIFICATION", "uri://t", "uri://e",
        budget={"MODEL_TRIAL_COUNT": 3, "CHIP_COUNT": 1},
    )
    pop_admin.wait_until_train_job_stopped(uid, "scalarapp", timeout_s=60)
    trials = pop_admin.get_trials_of_train_job(uid, "scalarapp")
    assert sum(1 for t in trials
               if t["status"] == TrialStatus.COMPLETED) == 3
    assert population_mod.FIT_STATS["fit_calls"] == 0  # never vectorized


def test_vmap_kill_switch_forces_scalar(pop_admin, tmp_path, monkeypatch):
    monkeypatch.setenv("RAFIKI_TRIAL_VMAP", "0")
    train_uri, test_uri = _write_datasets(tmp_path)
    uid = _register_pop_model(pop_admin)
    population_mod.reset_fit_stats()
    pop_admin.create_train_job(
        uid, "killapp", "IMAGE_CLASSIFICATION", train_uri, test_uri,
        budget={"MODEL_TRIAL_COUNT": 2, "CHIP_COUNT": 1,
                "TRIAL_VMAP_K": 2},
    )
    pop_admin.wait_until_train_job_stopped(uid, "killapp", timeout_s=120)
    trials = pop_admin.get_trials_of_train_job(uid, "killapp")
    assert sum(1 for t in trials
               if t["status"] == TrialStatus.COMPLETED) == 2
    # the fixture's scalar path still fits populations of ONE
    assert population_mod.FIT_STATS["member_counts"] == [1, 1]


# -- per-member ASHA rung accounting ------------------------------------------

def test_population_stop_check_reports_per_member_and_stops_on_all():
    class RungStore:
        def __init__(self, keep):
            self.keep = keep
            self.calls = []

        def report_rung(self, advisor_id, trial_id, resource, value,
                        min_resource=1, eta=3, mode="min"):
            self.calls.append((trial_id, resource, value))
            return trial_id in self.keep

    from rafiki_tpu.sdk.log import ModelLogger

    def build(keep):
        store = RungStore(keep)
        w = TrainWorker("sub", db=None, advisor_store=store)
        w._early_stop = True
        w._asha_min, w._asha_eta = 1, 3
        w._job_deadline = w._trial_timeout_s = None
        tl = ModelLogger()
        w._install_population_stop_check(tl, "aid", ["m0", "m1"])
        return store, tl._stop_check

    metrics = {"epoch": 0.0, "loss": 1.5,
               "member0_loss": 1.0, "member1_loss": 2.0}
    # one member still competitive -> the batch continues
    store, check = build(keep={"m1"})
    assert check(metrics) is False
    assert [(c[0], c[1], c[2]) for c in store.calls] == [
        ("m0", 1, 1.0), ("m1", 1, 2.0)]  # per-member ids, member losses
    # every member told to stop -> the batch stops
    store, check = build(keep=set())
    assert check(metrics) is True
    # mean-only logs degrade to the shared loss under each member's id
    store, check = build(keep={"m0"})
    assert check({"epoch": 1.0, "loss": 0.7}) is False
    assert store.calls == [("m0", 2, 0.7), ("m1", 2, 0.7)]


# -- checkpoint member-count mismatch drill ----------------------------------

def _tiny_pop_trainer(lrs):
    import jax
    import jax.numpy as jnp
    import optax

    from rafiki_tpu.sdk import (
        PopulationTrainer,
        softmax_classifier_loss,
        tunable_optimizer,
    )

    def apply(params, xb):
        return xb @ params["w"] + params["b"]

    def init(key):
        return {"w": 0.01 * jax.random.normal(key, (8, 3)),
                "b": jnp.zeros((3,))}

    t = PopulationTrainer(
        loss_fn=softmax_classifier_loss(apply),
        optimizer=tunable_optimizer(optax.sgd, learning_rate=0.01),
        predict_fn=lambda p, x: apply(p, x))
    params, opt = t.init(init, {"learning_rate": lrs}, seed=3)
    return t, params, opt


def test_population_checkpoint_member_mismatch_is_typed_corruption(
        tmp_path, caplog):
    from rafiki_tpu.sdk.artifact import ArtifactCorruptError

    rng = np.random.RandomState(0)
    x = rng.randn(64, 8).astype(np.float32)
    y = rng.randint(0, 3, size=64).astype(np.int32)
    ckpt = str(tmp_path / "pop.ckpt")
    t3, p3, o3 = _tiny_pop_trainer([0.01, 0.02, 0.03])
    t3.fit(p3, o3, (x, y), epochs=1, batch_size=32, seed=1,
           checkpoint_path=ckpt)
    assert os.path.exists(ckpt)
    # direct restore with a different K: typed artifact corruption,
    # never a cryptic reshape deep inside the epoch scan
    t2, p2, o2 = _tiny_pop_trainer([0.01, 0.02])
    with pytest.raises(ArtifactCorruptError, match="3 member"):
        t2._restore_checkpoint(ckpt, p2, o2)
    # through fit(): the standard corrupt-checkpoint contract — warn and
    # train from scratch, returning the NEW population size
    import logging

    with caplog.at_level(logging.WARNING,
                         logger="rafiki_tpu.sdk.population"):
        params, _ = t2.fit(p2, o2, (x, y), epochs=1, batch_size=32,
                           seed=1, checkpoint_path=ckpt)
    assert t2.n_members(params) == 2
    assert any("corrupt" in r.message for r in caplog.records)


# -- doctor ------------------------------------------------------------------

def test_doctor_vectorized_trials_check(tmp_path, monkeypatch):
    from rafiki_tpu.doctor import check_vectorized_trials

    monkeypatch.setenv("RAFIKI_WORKDIR", str(tmp_path))  # no store to scan
    monkeypatch.delenv("RAFIKI_TRIAL_VMAP", raising=False)
    monkeypatch.delenv("RAFIKI_TRIAL_VMAP_K", raising=False)
    name, status, detail = check_vectorized_trials()
    assert (name, status) == ("vectorized trials", "PASS")
    assert "K=4" in detail
    # K past the per-chip memory heuristic
    monkeypatch.setenv("RAFIKI_TRIAL_VMAP_K", "64")
    _, status, detail = check_vectorized_trials()
    assert status == "WARN" and "memory" in detail
    # population mode on but K can never engage
    monkeypatch.setenv("RAFIKI_TRIAL_VMAP", "1")
    monkeypatch.setenv("RAFIKI_TRIAL_VMAP_K", "1")
    _, status, detail = check_vectorized_trials()
    assert status == "WARN" and "never engage" in detail


def test_doctor_int8_check_warns_when_forced_on(monkeypatch):
    from rafiki_tpu.doctor import check_int8_serving

    monkeypatch.delenv("RAFIKI_SERVE_INT8", raising=False)
    name, status, detail = check_int8_serving()
    assert (name, status) == ("int8 serving", "PASS")
    assert "0.805" in detail
    monkeypatch.setenv("RAFIKI_SERVE_INT8", "1")
    _, status, detail = check_int8_serving()
    assert status == "WARN" and "SLOWDOWN" in detail
