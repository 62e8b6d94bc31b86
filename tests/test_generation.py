"""Generative serving subsystem (docs/serving-generation.md): KV-cached
decode in models/lm.py, the continuous-batching slot scheduler
(worker/generation.py), the streaming door + client, task-type
validation, chaos drills, and the tier-1 end-to-end acceptance drill —
two concurrent ``Client.generate`` streams with different lengths
through ONE worker, slot reuse mid-decode, and a mid-stream fault that
injures exactly one stream."""

import os
import threading
import time

import numpy as np
import pytest

from rafiki_tpu.cache.queue import (
    GenerationError,
    InProcessBroker,
    TokenStream,
)
from rafiki_tpu.sdk.model import (
    BaseModel,
    GenerationSpec,
    generation_capability,
)
from rafiki_tpu.utils import chaos
from rafiki_tpu.worker.generation import (
    GenerationRequestError,
    GenerationUnsupportedError,
    GenerationWorker,
)

HERE = os.path.dirname(__file__)
GEN_FIXTURE = os.path.join(HERE, "fixtures", "gen_model.py")


# -- model layer: KV-cached decode (models/lm.py) ---------------------------

def test_lm_prefill_decode_consistency():
    """Decoding token-by-token from a prefilled cache must match a fresh
    prefill over the longer sequence — one shared cached-forward serves
    both shapes, so this is the cache-correctness invariant."""
    import jax
    import jax.numpy as jnp

    from rafiki_tpu.models import lm

    cfg = lm.tiny(vocab=64, max_len=32, dim=16, depth=2, heads=2)
    params = lm.init(jax.random.PRNGKey(0), cfg)
    cache = lm.init_kv_cache(cfg, max_slots=2, max_len=32)
    prompt = jnp.array([5, 9, 2, 7], jnp.int32)
    logits, cache = lm.prefill(
        params, cache, 0, jnp.pad(prompt, (0, 4)), 4, cfg)
    toks = [int(lm.greedy_token(logits))]
    ids = jnp.array([toks[0], 0], jnp.int32)
    pos = jnp.array([4, 0], jnp.int32)
    step = jax.jit(lambda c, i, p: lm.decode_step(params, c, i, p, cfg))
    for _ in range(5):
        lg, cache = step(cache, ids, pos)
        t = int(lm.greedy_token(lg)[0])
        toks.append(t)
        ids = ids.at[0].set(t)
        pos = pos.at[0].set(pos[0] + 1)
    # fresh prefill over prompt + all-but-last generated token predicts
    # exactly the last generated token
    longer = jnp.concatenate(
        [prompt, jnp.array(toks[:-1], jnp.int32)])
    cache2 = lm.init_kv_cache(cfg, max_slots=1, max_len=32)
    lg2, _ = lm.prefill(
        params, cache2, 0,
        jnp.pad(longer, (0, 16 - longer.shape[0])), int(longer.shape[0]),
        cfg)
    assert int(lm.greedy_token(lg2)) == toks[-1]


def test_lm_kv_cache_takes_expert_blocks():
    """The refusal went with the capacity: a drop-free top-1 expert block
    routes each token on its own, so the ring cache serves it and a decode
    step tracks the full forward."""
    import jax
    import jax.numpy as jnp

    from rafiki_tpu.models import lm

    cfg = lm.tiny(vocab=64, max_len=16, dim=16, depth=1, heads=2,
                  moe_experts=2)
    params = lm.init(jax.random.PRNGKey(0), cfg)
    ids = jnp.arange(6, dtype=jnp.int32) * 5 % 64
    cache = lm.init_kv_cache(cfg, max_slots=2)
    assert cache["k"].shape[:2] == (1, 2)
    _, cache = lm.prefill(params, cache, 1, ids[:5], 5, cfg)
    logits, _ = lm.decode_step(params, cache, jnp.array([0, ids[5]]),
                               jnp.array([0, 5]), cfg)
    full, _ = lm.apply(params, ids[None], cfg)
    assert np.abs(np.asarray(logits[1]) - np.asarray(full[0, 5])).max() < 0.05


# -- data plane: TokenStream ------------------------------------------------

def test_token_stream_semantics():
    s = TokenStream("seq1")
    s.push([1, 2])
    s.push([3], finished=True, reason="eos")
    d1 = s.next_delta(0.1)
    assert d1.tokens == [1, 2] and not d1.finished
    d2 = s.next_delta(0.1)
    assert d2.tokens == [3] and d2.finished and d2.reason == "eos"
    with pytest.raises(StopIteration):
        s.next_delta(0.1)
    # pushes after the terminal delta are dropped
    s.push([9])
    with pytest.raises(StopIteration):
        s.next_delta(0.1)


def test_token_stream_fail_and_timeout():
    s = TokenStream("seq2")
    with pytest.raises(TimeoutError):
        s.next_delta(0.05)
    s.fail("worker exploded")
    with pytest.raises(GenerationError, match="worker exploded"):
        s.next_delta(0.1)
    s2 = TokenStream("seq3")
    s2.cancel()
    assert s2.cancelled


# -- SDK capability oracle --------------------------------------------------

class _HalfWired(BaseModel):
    generation_spec = GenerationSpec(eos_token_id=0)

    @staticmethod
    def get_knob_config():
        return {}

    def train(self, dataset_uri):
        pass

    def evaluate(self, dataset_uri):
        return 0.0

    def predict(self, queries):
        return list(queries)

    def dump_parameters(self):
        return {}

    def load_parameters(self, params):
        pass


class _Scripted(_HalfWired):
    """Deterministic jax-free decode: next token = last + 1; EOS at 99.
    max_context generous so tests control finish via max_tokens/EOS."""

    generation_spec = GenerationSpec(eos_token_id=99, max_context=100000)

    def init_kv_cache(self, max_slots):
        return {"slots": max_slots}

    def prefill(self, cache, slot, prompt_ids):
        return prompt_ids[-1] + 1, cache

    def decode_step(self, cache, ids, positions):
        return np.asarray(ids) + 1, cache


def test_generation_capability_oracle():
    assert generation_capability(_HalfWired) is None
    spec = generation_capability(_Scripted)
    assert spec is not None and spec.eos_token_id == 99
    assert generation_capability(type("NoSpec", (BaseModel,), {})) is None


# -- the slot scheduler -----------------------------------------------------

class _Ctx:
    def __init__(self, service_id="w1"):
        self.service_id = service_id
        self.chips = None
        self.stopping = False

    def ready(self):
        pass


def _start_worker(broker, model, job="genjob"):
    worker = GenerationWorker(job, "trial1", db=None, broker=broker)
    worker._load_model = lambda sid: model
    ctx = _Ctx()
    t = threading.Thread(target=worker.start, args=(ctx,), daemon=True)
    t.start()
    deadline = time.monotonic() + 5
    while not broker.get_worker_queues(job) and time.monotonic() < deadline:
        time.sleep(0.01)
    assert broker.get_worker_queues(job), "worker never registered"
    return ctx, t


def _submit(broker, job, query, timeout_s=5.0):
    q = list(broker.get_worker_queues(job).values())[0]
    fut = q.submit_many([query],
                        deadline=time.monotonic() + timeout_s)[0]
    return fut.result(timeout_s)


def _drain(stream, timeout_s=5.0):
    toks, reason = [], None
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            d = stream.next_delta(0.5)
        except StopIteration:
            break
        toks.extend(d.tokens)
        if d.finished:
            reason = d.reason
            break
    return toks, reason


def test_scheduler_eos_and_max_tokens(monkeypatch):
    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "2")
    broker = InProcessBroker()
    ctx, t = _start_worker(broker, _Scripted())
    try:
        # EOS: prompt ends at 97 -> tokens 98, 99(=EOS)
        toks, reason = _drain(_submit(
            broker, "genjob", {"prompt_ids": [97], "max_tokens": 50}))
        assert toks == [98, 99] and reason == "eos"
        # max_tokens: clamped stream of exactly 3
        toks, reason = _drain(_submit(
            broker, "genjob", {"prompt_ids": [5], "max_tokens": 3}))
        assert toks == [6, 7, 8] and reason == "max_tokens"
    finally:
        ctx.stopping = True
        t.join(timeout=5)


def test_scheduler_continuous_batching_mid_decode_join(monkeypatch):
    """The Orca property: a short sequence finishing frees its slot to a
    QUEUED request while the long co-resident sequence keeps decoding —
    admission happens mid-decode, not at batch boundaries."""
    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "2")

    class _Slow(_Scripted):
        def decode_step(self, cache, ids, positions):
            time.sleep(0.01)  # ~10ms/token so ordering is observable
            return np.asarray(ids) + 1, cache

    broker = InProcessBroker()
    ctx, t = _start_worker(broker, _Slow())
    try:
        q = list(broker.get_worker_queues("genjob").values())[0]
        deadline = time.monotonic() + 30
        fa = q.submit_many([{"prompt_ids": [1], "max_tokens": 200}],
                           deadline=deadline)[0]
        fb = q.submit_many([{"prompt_ids": [1], "max_tokens": 3}],
                           deadline=deadline)[0]
        sa, sb = fa.result(5), fb.result(5)
        # both slots busy; C queues behind them
        fc = q.submit_many([{"prompt_ids": [1], "max_tokens": 3}],
                           deadline=deadline)[0]
        toks_b, reason_b = _drain(sb)
        assert reason_b == "max_tokens"
        sc = fc.result(5.0)  # admitted the moment B's slot freed
        c_first = sc.next_delta(2.0)
        assert c_first.tokens  # C streams...
        probe = sa.next_delta(2.0)
        assert not probe.finished  # ...while A is still mid-decode
        sa.cancel()
        _drain(sc)
    finally:
        ctx.stopping = True
        t.join(timeout=5)


def test_scheduler_malformed_request_typed(monkeypatch):
    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "1")
    broker = InProcessBroker()
    ctx, t = _start_worker(broker, _Scripted())
    try:
        q = list(broker.get_worker_queues("genjob").values())[0]
        fut = q.submit_many([{"prompt_ids": []}],
                            deadline=time.monotonic() + 5)[0]
        with pytest.raises(GenerationRequestError):
            fut.result(5)
        # the bad request cost no slot: a good one still serves
        toks, _ = _drain(_submit(
            broker, "genjob", {"prompt_ids": [10], "max_tokens": 2}))
        assert toks == [11, 12]
    finally:
        ctx.stopping = True
        t.join(timeout=5)


def test_scheduler_context_edge_finishes(monkeypatch):
    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "1")

    class _Tiny(_Scripted):
        generation_spec = GenerationSpec(eos_token_id=9999, max_context=8)

    broker = InProcessBroker()
    ctx, t = _start_worker(broker, _Tiny())
    try:
        # prompt 4 + budget 4 fits max_context 8 exactly; the ring edge
        # finishes the stream with reason "context" before overflow
        toks, reason = _drain(_submit(
            broker, "genjob",
            {"prompt_ids": [1, 2, 3, 4], "max_tokens": 4}))
        assert reason in ("context", "max_tokens") and len(toks) >= 3
        # prompt + budget past the ring is refused typed, costs no slot
        q = list(broker.get_worker_queues("genjob").values())[0]
        fut = q.submit_many(
            [{"prompt_ids": [1, 2, 3, 4, 5, 6], "max_tokens": 50}],
            deadline=time.monotonic() + 5)[0]
        with pytest.raises(GenerationRequestError, match="max_context"):
            fut.result(5)
    finally:
        ctx.stopping = True
        t.join(timeout=5)


def test_worker_without_capability_is_typed_deploy_error():
    broker = InProcessBroker()
    worker = GenerationWorker("j2", "t", db=None, broker=broker)
    worker._load_model = lambda sid: _HalfWired()
    with pytest.raises(GenerationUnsupportedError):
        worker.start(_Ctx())
    assert not broker.get_worker_queues("j2")  # unregistered on the way out


@pytest.mark.chaos
def test_chaos_error_injures_exactly_one_stream(monkeypatch):
    """Mid-stream fault drill: slot0's stream ends with the typed error,
    the co-resident slot1 stream completes untouched."""
    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "2")

    class _Slow(_Scripted):
        def decode_step(self, cache, ids, positions):
            time.sleep(0.005)
            return np.asarray(ids) + 1, cache

    chaos.install(chaos.parse_rules(
        "site=generate;action=error;match=/slot0/;after=2"))
    broker = InProcessBroker()
    ctx, t = _start_worker(broker, _Slow())
    try:
        q = list(broker.get_worker_queues("genjob").values())[0]
        deadline = time.monotonic() + 30
        fa = q.submit_many([{"prompt_ids": [1], "max_tokens": 30}],
                           deadline=deadline)[0]
        sa = fa.result(5)  # admitted first -> slot0
        fb = q.submit_many([{"prompt_ids": [1], "max_tokens": 30}],
                           deadline=deadline)[0]
        sb = fb.result(5)
        got = []
        with pytest.raises(GenerationError, match="chaos-injected"):
            while True:
                d = sa.next_delta(5.0)
                got.extend(d.tokens)
                if d.finished:
                    break
        assert got  # tokens arrived BEFORE the mid-stream fault
        toks_b, reason_b = _drain(sb, timeout_s=10)
        assert reason_b == "max_tokens" and len(toks_b) == 30
    finally:
        chaos.clear()
        ctx.stopping = True
        t.join(timeout=5)


# -- the streaming door (chunked HTTP + stall drill) ------------------------

@pytest.mark.chaos
def test_door_streams_and_stall_yields_typed_error(monkeypatch):
    """The dedicated door streams deltas incrementally, and a stalled
    decode step (chaos drop) ends the response with a typed terminal
    error frame — never a silent hang (satellite drill)."""
    import requests

    from rafiki_tpu.predictor.predictor import Predictor
    from rafiki_tpu.predictor.server import PredictorServer

    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "2")
    monkeypatch.setenv("RAFIKI_GEN_STREAM_TIMEOUT_S", "0.5")

    class _Slow(_Scripted):
        def decode_step(self, cache, ids, positions):
            time.sleep(0.005)
            return np.asarray(ids) + 1, cache

    broker = InProcessBroker()
    ctx, t = _start_worker(broker, _Slow(), job="doorjob")
    predictor = Predictor("doorjob", broker, task=None)
    server = PredictorServer(predictor, "doorapp", auth=False).start()
    try:
        # healthy stream, token-by-token
        lines = []
        with requests.post(
                f"http://127.0.0.1:{server.port}/generate",
                json={"prompt_ids": [5], "max_tokens": 4},
                stream=True, timeout=30) as resp:
            assert resp.status_code == 200
            assert resp.headers["Content-Type"].startswith(
                "application/x-ndjson")
            for raw in resp.iter_lines():
                if raw:
                    lines.append(__import__("json").loads(raw))
        toks = [t for d in lines for t in d["tokens"]]
        assert toks == [6, 7, 8, 9]
        assert lines[-1]["finished"] and lines[-1]["reason"] == "max_tokens"
        # stalled decode: mute the slot after 2 deltas -> typed error
        chaos.install(chaos.parse_rules(
            "site=generate;action=drop;match=doorjob;after=2;times=1"))
        lines = []
        with requests.post(
                f"http://127.0.0.1:{server.port}/generate",
                json={"prompt_ids": [5], "max_tokens": 50},
                stream=True, timeout=30) as resp:
            for raw in resp.iter_lines():
                if raw:
                    lines.append(__import__("json").loads(raw))
        assert lines, "stalled stream must still terminate"
        last = lines[-1]
        assert last["finished"] and "stalled" in (last.get("error") or "")
    finally:
        chaos.clear()
        server.stop(drain_timeout_s=0.0)
        ctx.stopping = True
        t.join(timeout=5)


def test_door_binary_wire_stream(monkeypatch):
    """Accept: application/x-rafiki-wire streams length-prefixed v3
    token-delta frames end to end."""
    import requests

    from rafiki_tpu.cache import wire
    from rafiki_tpu.predictor.predictor import Predictor
    from rafiki_tpu.predictor.server import PredictorServer

    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "1")
    broker = InProcessBroker()
    ctx, t = _start_worker(broker, _Scripted(), job="binjob")
    predictor = Predictor("binjob", broker, task=None)
    server = PredictorServer(predictor, "binapp", auth=False).start()
    try:
        buf = b""
        with requests.post(
                f"http://127.0.0.1:{server.port}/generate",
                json={"prompt_ids": [20], "max_tokens": 3},
                headers={"Accept": wire.CONTENT_TYPE},
                stream=True, timeout=30) as resp:
            assert resp.headers["Content-Type"].startswith(
                wire.CONTENT_TYPE)
            for data in resp.iter_content(chunk_size=None):
                buf += data
        toks, finished = [], False
        while len(buf) >= 4:
            n = int.from_bytes(buf[:4], "little")
            frame, buf = buf[4:4 + n], buf[4 + n:]
            sid, delta = wire.decode_token_delta(frame)
            toks.extend(delta.tokens)
            finished = finished or delta.finished
        assert toks == [21, 22, 23] and finished
    finally:
        server.stop(drain_timeout_s=0.0)
        ctx.stopping = True
        t.join(timeout=5)


# -- task-type validation (typed 400s) --------------------------------------

@pytest.fixture()
def admin(tmp_path):
    from rafiki_tpu.admin.admin import Admin
    from rafiki_tpu.db.database import Database
    from rafiki_tpu.placement.manager import (
        ChipAllocator,
        LocalPlacementManager,
    )

    # ONE chip: the capacity-aware replica count then deploys exactly ONE
    # serving worker, so concurrent streams provably share one slot table
    a = Admin(
        db=Database(":memory:"),
        placement=LocalPlacementManager(allocator=ChipAllocator([0])),
        params_dir=str(tmp_path / "params"),
    )
    yield a
    a.shutdown()


def _login(admin):
    from rafiki_tpu import config

    return admin.authenticate_user(
        config.SUPERADMIN_EMAIL, config.SUPERADMIN_PASSWORD)


def _read(path):
    with open(path, "rb") as f:
        return f.read()


def test_task_capability_validation_at_upload(admin):
    from rafiki_tpu.sdk.model import InvalidModelClassError

    uid = _login(admin)["user_id"]
    gen_bytes = _read(GEN_FIXTURE)
    fake_bytes = _read(os.path.join(HERE, "fixtures", "fake_model.py"))
    # classification template under TEXT_GENERATION: typed 400
    with pytest.raises(InvalidModelClassError, match="generation-capable"):
        admin.create_model(uid, "nogen", "TEXT_GENERATION", fake_bytes,
                           "FakeModel")
    # generative template under a classification task: typed 400
    with pytest.raises(InvalidModelClassError, match="TEXT_GENERATION"):
        admin.create_model(uid, "misfiled", "IMAGE_CLASSIFICATION",
                           gen_bytes, "TinyGenLM")
    # the matched pairing uploads clean
    m = admin.create_model(uid, "genlm", "TEXT_GENERATION", gen_bytes,
                           "TinyGenLM")
    assert m["task"] == "TEXT_GENERATION"
    assert m["verification"]["capabilities"]["generation"] is True


def test_task_validation_at_train_job_create(admin):
    """Defense in depth: a row that slipped past upload validation
    (pre-PR rows, verification off) is re-checked STATICALLY at train-job
    creation — typed 400, zero uploaded code executed."""
    from rafiki_tpu.admin.admin import InvalidRequestError

    uid = _login(admin)["user_id"]
    fake_bytes = _read(os.path.join(HERE, "fixtures", "fake_model.py"))
    # plant a mismatched row directly (bypasses upload validation)
    admin.db.create_model(uid, "sneaky", "TEXT_GENERATION", fake_bytes,
                          "FakeModel", {}, "PRIVATE")
    with pytest.raises(InvalidRequestError, match="generation-capable"):
        admin.create_train_job(
            uid, "genapp", "TEXT_GENERATION", "uri://train", "uri://test",
            budget={"MODEL_TRIAL_COUNT": 1})


# -- doctor -----------------------------------------------------------------

def test_doctor_generative_serving_check(monkeypatch):
    from rafiki_tpu.doctor import check_generative_serving

    monkeypatch.setenv("RAFIKI_DB_PATH", "/nonexistent/nowhere.sqlite3")
    name, status, detail = check_generative_serving()
    assert name == "generative serving" and status == "PASS"
    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "128")
    _, status, detail = check_generative_serving()
    assert status == "WARN" and "memory heuristic" in detail
    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "8")
    monkeypatch.setenv("RAFIKI_GEN_STREAM_TIMEOUT_S", "0")
    _, status, detail = check_generative_serving()
    assert status == "WARN" and "stall" in detail


# -- the tier-1 end-to-end acceptance drill ---------------------------------

def _stream_collector(client, app, prompt, max_tokens, record):
    """Run one Client.generate stream, recording (first_token_ts,
    finish_ts, tokens, error)."""
    toks = []
    first = None
    err = reason = None
    try:
        for delta in client.generate(app, prompt, max_tokens=max_tokens,
                                     timeout_s=60.0):
            if delta.get("tokens") and first is None:
                first = time.monotonic()
            toks.extend(delta.get("tokens") or [])
            reason = delta.get("reason") or reason
    except Exception as e:  # GenerationStreamError in the chaos phase
        err = e
    record.update(first=first, finish=time.monotonic(), tokens=toks,
                  error=err, reason=reason)


@pytest.mark.chaos
def test_e2e_streaming_generation_drill(admin, monkeypatch):
    """The acceptance drill: deploy the tiny LM as a TEXT_GENERATION
    inference job on CPU, stream concurrent ``Client.generate`` requests
    with different lengths through ONE worker, and assert (a) tokens
    arrive incrementally, (b) an early-finishing sequence frees its slot
    to a queued request mid-decode (slot-occupancy observable), and (c) a
    chaos mid-stream fault injures exactly one stream while the sibling
    completes."""
    from rafiki_tpu.admin.http import AdminServer
    from rafiki_tpu.client.client import Client, GenerationStreamError
    from rafiki_tpu.utils.metrics import REGISTRY

    monkeypatch.setenv("RAFIKI_PREDICTOR_PORTS", "1")
    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "2")
    uid = _login(admin)["user_id"]
    admin.create_model(uid, "genlm", "TEXT_GENERATION", _read(GEN_FIXTURE),
                       "TinyGenLM")
    admin.create_train_job(
        uid, "genapp", "TEXT_GENERATION", "uri://train", "uri://test",
        budget={"MODEL_TRIAL_COUNT": 1, "CHIP_COUNT": 1})
    job = admin.wait_until_train_job_stopped(uid, "genapp", timeout_s=120)
    assert job["status"] == "STOPPED"
    inf = admin.create_inference_job(uid, "genapp")
    assert inf["status"] == "RUNNING"
    assert len(inf["workers"]) == 1  # ONE worker serves both streams
    assert inf["predictor_port"], "streaming door must be published"

    server = AdminServer(admin).start()
    try:
        from rafiki_tpu import config

        client = Client(admin_port=server.port)
        client.login(config.SUPERADMIN_EMAIL, config.SUPERADMIN_PASSWORD)

        # ---- (a) + (b): concurrent different-length streams ------------
        # sampler: poll the slot-occupancy gauge while the streams run —
        # the continuous-batching witness (the table must hit 2/2 busy)
        max_busy = [0.0]
        sampling = threading.Event()

        def sample():
            g = None
            while not sampling.is_set():
                g = g or REGISTRY.get("rafiki_gen_slots_busy")
                if g is not None:
                    busy = sum(c.value() for c in g.children().values())
                    max_busy[0] = max(max_busy[0], busy)
                time.sleep(0.003)

        sampler = threading.Thread(target=sample, daemon=True)
        sampler.start()
        a_rec, b_rec, c_rec = {}, {}, {}
        ta = threading.Thread(
            target=_stream_collector,
            args=(client, "genapp", [2, 3, 4], 40, a_rec), daemon=True)
        ta.start()
        # B starts after A so slot order is deterministic; C queues
        # behind the full table and must be admitted MID-decode of A
        tb = threading.Thread(
            target=_stream_collector,
            args=(client, "genapp", [9, 8], 3, b_rec), daemon=True)
        tb.start()
        time.sleep(0.1)
        tc = threading.Thread(
            target=_stream_collector,
            args=(client, "genapp", [5], 3, c_rec), daemon=True)
        tc.start()
        for t in (ta, tb, tc):
            t.join(timeout=90)
        sampling.set()
        sampler.join(timeout=5)
        assert a_rec.get("error") is None and b_rec.get("error") is None \
            and c_rec.get("error") is None
        assert len(a_rec["tokens"]) == 40
        assert 1 <= len(b_rec["tokens"]) <= 3
        assert 1 <= len(c_rec["tokens"]) <= 3
        # (a) incremental: short streams' FIRST tokens landed before the
        # long stream finished
        assert b_rec["first"] < a_rec["finish"]
        assert c_rec["first"] < a_rec["finish"]
        # (b) continuous batching: the 2-slot table filled (both slots
        # busy at once), yet the THIRD stream was served before the long
        # one finished — only a slot freed mid-decode can explain C
        assert max_busy[0] >= 2, f"slot table never filled ({max_busy})"
        evictions = REGISTRY.get("rafiki_gen_evictions_total")
        assert evictions is not None

        # ---- (c) chaos: mid-stream fault on exactly one stream ---------
        # the table is empty again, so the next admission takes slot0
        chaos.install(chaos.parse_rules(
            "site=generate;action=error;match=/slot0/;after=3;times=1"))
        d_rec, e_rec = {}, {}
        td = threading.Thread(
            target=_stream_collector,
            args=(client, "genapp", [7, 7], 30, d_rec), daemon=True)
        td.start()
        # wait until D holds slot0 (first delta arrived), then start E
        deadline = time.monotonic() + 30
        while d_rec.get("first") is None and not d_rec.get("finish") \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        te = threading.Thread(
            target=_stream_collector,
            args=(client, "genapp", [3, 1, 2], 12, e_rec), daemon=True)
        te.start()
        td.join(timeout=60)
        te.join(timeout=60)
        assert isinstance(d_rec.get("error"), GenerationStreamError), (
            f"injured stream must fail typed, got {d_rec.get('error')!r}")
        assert d_rec["tokens"], "tokens arrived before the mid-stream fault"
        assert e_rec.get("error") is None
        assert len(e_rec["tokens"]) == 12, "sibling stream must complete"
    finally:
        chaos.clear()
        server.stop()


@pytest.mark.slow
def test_multi_client_streaming_stress(admin, monkeypatch):
    """8 concurrent streaming clients through a 4-slot worker: every
    stream completes, tokens are the deterministic greedy continuation,
    and nothing deadlocks under sustained slot churn."""
    from rafiki_tpu.admin.http import AdminServer
    from rafiki_tpu.client.client import Client

    monkeypatch.setenv("RAFIKI_PREDICTOR_PORTS", "1")
    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "4")
    uid = _login(admin)["user_id"]
    admin.create_model(uid, "genlm", "TEXT_GENERATION", _read(GEN_FIXTURE),
                       "TinyGenLM")
    admin.create_train_job(
        uid, "genapp", "TEXT_GENERATION", "uri://train", "uri://test",
        budget={"MODEL_TRIAL_COUNT": 1, "CHIP_COUNT": 1})
    admin.wait_until_train_job_stopped(uid, "genapp", timeout_s=120)
    admin.create_inference_job(uid, "genapp")
    server = AdminServer(admin).start()
    try:
        from rafiki_tpu import config

        client = Client(admin_port=server.port)
        client.login(config.SUPERADMIN_EMAIL, config.SUPERADMIN_PASSWORD)
        records = [{} for _ in range(8)]
        threads = [
            threading.Thread(
                target=_stream_collector,
                args=(client, "genapp", [2 + i], 8 + (i % 3) * 4,
                      records[i]),
                daemon=True)
            for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        for i, rec in enumerate(records):
            assert rec.get("error") is None, f"client {i}: {rec}"
            # greedy decode may legitimately hit the template's EOS
            # before the budget; anything else must run to max_tokens
            if rec.get("reason") == "eos":
                assert 1 <= len(rec["tokens"]) <= 8 + (i % 3) * 4
            else:
                assert len(rec["tokens"]) == 8 + (i % 3) * 4, f"client {i}"
    finally:
        server.stop()


def test_door_refused_generate_does_not_leak_admission_slot(monkeypatch):
    """Review regression: a /generate refused BEFORE (or by) admission
    must not decrement the in-flight book — release() pairs only with a
    successful admit, else shed bursts corrupt the capacity gate."""
    import requests

    from rafiki_tpu.predictor.predictor import Predictor
    from rafiki_tpu.predictor.server import PredictorServer

    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "1")

    class _Slow(_Scripted):
        def decode_step(self, cache, ids, positions):
            time.sleep(0.01)
            return np.asarray(ids) + 1, cache

    broker = InProcessBroker()
    ctx, t = _start_worker(broker, _Slow(), job="leakjob")
    predictor = Predictor("leakjob", broker, task=None)
    server = PredictorServer(predictor, "leakapp", auth=False).start()
    try:
        done = threading.Event()

        def long_stream():
            with requests.post(
                    f"http://127.0.0.1:{server.port}/generate",
                    json={"prompt_ids": [1], "max_tokens": 300},
                    stream=True, timeout=30) as resp:
                for _ in resp.iter_lines():
                    if done.is_set():
                        return

        ts = threading.Thread(target=long_stream, daemon=True)
        ts.start()
        deadline = time.monotonic() + 10
        while server.admission.inflight < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert server.admission.inflight == 1
        # refusals at every pre-admission stage: bad JSON, bad
        # max_tokens, malformed prompt (post-admission 400) — the
        # admitted stream's slot must survive each
        r = requests.post(f"http://127.0.0.1:{server.port}/generate",
                          data=b"{not json", timeout=10)
        assert r.status_code == 400
        r = requests.post(f"http://127.0.0.1:{server.port}/generate",
                          json={"prompt_ids": [1], "max_tokens": "zap"},
                          timeout=10)
        assert r.status_code == 400
        assert server.admission.inflight == 1, \
            "refused requests leaked an admission slot"
    finally:
        done.set()
        server.stop(drain_timeout_s=0.0)
        ctx.stopping = True
        t.join(timeout=5)


def test_door_ttft_one_sample_a_stream_and_holds_the_wait_for_a_slot(
        monkeypatch):
    """`rafiki_gen_door_ttft_seconds` takes one sample for each stream the
    door hands back and none for a refused request; with the one slot
    taken, the next request's sample holds its wait for the slot (which
    `rafiki_gen_ttft_seconds`, started at the slot's admission, leaves
    out)."""
    import requests

    from rafiki_tpu.predictor.predictor import Predictor
    from rafiki_tpu.predictor.server import PredictorServer
    from rafiki_tpu.utils.metrics import REGISTRY

    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "1")

    class _Slow(_Scripted):
        def decode_step(self, cache, ids, positions):
            time.sleep(0.02)
            return np.asarray(ids) + 1, cache

    def snap(name):
        metric = REGISTRY.get(name)
        return (metric.labels().snapshot() if metric is not None
                else {"count": 0, "sum": 0.0})

    def post(max_tokens):
        with requests.post(
                f"http://127.0.0.1:{server.port}/generate",
                json={"prompt_ids": [1], "max_tokens": max_tokens},
                stream=True, timeout=30) as resp:
            assert resp.status_code == 200
            return sum(1 for raw in resp.iter_lines() if raw)

    broker = InProcessBroker()
    ctx, t = _start_worker(broker, _Slow(), job="waitjob")
    predictor = Predictor("waitjob", broker, task=None)
    server = PredictorServer(predictor, "waitapp", auth=False).start()
    try:
        door0 = snap("rafiki_gen_door_ttft_seconds")
        worker0 = snap("rafiki_gen_ttft_seconds")
        holder = threading.Thread(target=post, args=(30,), daemon=True)
        holder.start()  # holds the one slot for 30 rounds of 20 ms
        deadline = time.monotonic() + 10
        while snap("rafiki_gen_door_ttft_seconds")["count"] \
                == door0["count"] and time.monotonic() < deadline:
            time.sleep(0.005)
        assert post(2) >= 1  # waits for the holder's last token
        holder.join(timeout=10)
        r = requests.post(f"http://127.0.0.1:{server.port}/generate",
                          json={"prompt_ids": [1], "max_tokens": "zap"},
                          timeout=10)
        assert r.status_code == 400
        door1 = snap("rafiki_gen_door_ttft_seconds")
        worker1 = snap("rafiki_gen_ttft_seconds")
        assert door1["count"] - door0["count"] == 2
        assert worker1["count"] - worker0["count"] == 2
        assert door1["sum"] - door0["sum"] >= 0.3
        assert worker1["sum"] - worker0["sum"] < 0.1
    finally:
        server.stop(drain_timeout_s=0.0)
        ctx.stopping = True
        t.join(timeout=5)


def test_decode_crash_fails_resident_streams_typed_and_keeps_serving(
        monkeypatch):
    """A decode_step that raises fails every resident stream with a typed
    error and clears the table; the worker serves the next request."""
    monkeypatch.setenv("RAFIKI_GEN_MAX_SLOTS", "2")

    class _CrashOnce(_Scripted):
        crashed = False

        def decode_step(self, cache, ids, positions):
            time.sleep(0.005)
            if not self.crashed and np.count_nonzero(ids) == 2:
                self.crashed = True  # with both streams resident
                raise RuntimeError("device fell over")
            return np.asarray(ids) + 1, cache

    broker = InProcessBroker()
    ctx, t = _start_worker(broker, _CrashOnce(), job="crashjob")
    try:
        a = _submit(broker, "crashjob", {"prompt_ids": [9],
                                         "max_tokens": 80})
        b = _submit(broker, "crashjob", {"prompt_ids": [3],
                                         "max_tokens": 80})
        for stream in (a, b):
            with pytest.raises(GenerationError, match="decode step failed"):
                for _ in range(100):
                    stream.next_delta(2.0)
        c = _submit(broker, "crashjob", {"prompt_ids": [40],
                                         "max_tokens": 3})
        assert _drain(c) == ([41, 42, 43], "max_tokens")
    finally:
        ctx.stopping = True
        t.join(timeout=5)


def test_remote_worker_stats_relay_feeds_occupancy_ring(admin):
    """Review regression: a PROCESS-placed generation worker's slot
    occupancy reaches the admin-side autoscaler through the
    inference_worker_stats event relay (the worker's own registry ring
    lives in the child process, invisible to the control loop)."""
    from rafiki_tpu.utils.metrics import REGISTRY

    job_id = "relayjob-" + str(id(admin))
    admin.db.get_inference_job_worker = (  # the relay's one lookup
        lambda sid: {"service_id": sid, "inference_job_id": job_id,
                     "trial_id": "t"})
    admin.handle_event("inference_worker_stats", {
        "service_id": "svc1", "batches": 1, "queries": 1,
        "gen_slots_busy": 3, "gen_slots_max": 4, "gen_tokens": 120})
    series = REGISTRY.ring(f"slot_occupancy:job:{job_id}").series()
    assert series and abs(series[-1][1] - 0.75) < 1e-9
    # and the relayed row is readable where the stats route looks
    with admin._predict_route_lock:
        row = admin._remote_serving_stats["svc1"]
    assert row["gen_slots_busy"] == 3 and row["gen_slots_max"] == 4
