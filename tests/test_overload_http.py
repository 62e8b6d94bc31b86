"""Serving doors under overload (ISSUE 2): chaos-stalled replicas drive
the full HTTP path — shed 429 + Retry-After while the backlog is full,
504 + expired-counter for queries whose deadline lapses in the queue,
degraded /healthz, graceful drain, and the admin door's identical shed
contract. Tier-1 tests are deterministic (chaos schedules, no real
load); the genuinely concurrent stress drill is marked slow."""

import json
import socket
import threading
import time
import urllib.error
import urllib.request

import pytest

from rafiki_tpu import config
from rafiki_tpu.admin.admin import Admin
from rafiki_tpu.admin.http import AdminServer
from rafiki_tpu.cache.queue import InProcessBroker
from rafiki_tpu.constants import TrainJobStatus
from rafiki_tpu.predictor.predictor import Predictor
from rafiki_tpu.predictor.server import PredictorServer
from rafiki_tpu.utils import chaos

pytestmark = pytest.mark.chaos

FIXTURE = __file__.rsplit("/", 1)[0] + "/fixtures/fake_model.py"


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos.clear()
    yield
    chaos.clear()


def _post(host, port, path, body, token=None, timeout=30):
    req = urllib.request.Request(
        f"http://{host}:{port}{path}", data=json.dumps(body).encode(),
        method="POST")
    req.add_header("Content-Type", "application/json")
    if token:
        req.add_header("Authorization", f"Bearer {token}")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


def _get(host, port, path, timeout=10):
    with urllib.request.urlopen(
            f"http://{host}:{port}{path}", timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _deploy(tmp_workdir, monkeypatch, app, env=None):
    monkeypatch.setenv("RAFIKI_PREDICTOR_PORTS", "1")
    for k, val in (env or {}).items():
        monkeypatch.setenv(k, val)
    admin = Admin(params_dir=str(tmp_workdir / "params"))
    auth = admin.authenticate_user(
        config.SUPERADMIN_EMAIL, config.SUPERADMIN_PASSWORD)
    uid = auth["user_id"]
    with open(FIXTURE, "rb") as f:
        admin.create_model(uid, "fake", "IMAGE_CLASSIFICATION",
                           f.read(), "FakeModel")
    admin.create_train_job(
        uid, app, "IMAGE_CLASSIFICATION", "uri://t", "uri://e",
        budget={"MODEL_TRIAL_COUNT": 1, "CHIP_COUNT": 0})
    job = admin.wait_until_train_job_stopped(uid, app, timeout_s=60)
    assert job["status"] == TrainJobStatus.STOPPED, job
    admin.create_inference_job(uid, app)
    inf = admin.get_inference_job(uid, app)
    return admin, uid, auth["token"], inf["predictor_host"], inf[
        "predictor_port"]


def _stall_workers(delay_s):
    """Every serving batch in this process stalls `delay_s` before the
    model runs — the deterministic slow-fleet drill."""
    chaos.install([chaos.ChaosRule(
        site=chaos.SITE_WORKER, action=chaos.ACTION_DELAY,
        delay_s=delay_s)])


def test_stalled_fleet_sheds_429_fast_and_admitted_still_answer(
        tmp_workdir, monkeypatch):
    """THE acceptance drill: with every replica chaos-stalled and the
    queue depth capped at 1, over-capacity requests shed with 429 +
    Retry-After in well under PREDICT_TIMEOUT_S — while every admitted
    request is still answered. The admin door sheds with the identical
    contract."""
    admin, uid, token, host, port = _deploy(
        tmp_workdir, monkeypatch, "ovl",
        env={"RAFIKI_PREDICT_QUEUE_DEPTH": "1"})
    try:
        _stall_workers(1.5)
        results = []
        lock = threading.Lock()

        def fire():
            status, payload, _ = _post(
                host, port, "/predict", {"queries": [[0.0]]}, token=token)
            with lock:
                results.append((status, payload))

        # 2 replicas x (1 in service + 1 queued) = 4 occupied slots
        threads = [threading.Thread(target=fire) for _ in range(4)]
        for t in threads:
            t.start()
            time.sleep(0.15)
        # the 5th request: every queue full -> shed instantly
        t0 = time.monotonic()
        status, payload, headers = _post(
            host, port, "/predict", {"queries": [[0.0]]}, token=token)
        shed_ms = (time.monotonic() - t0) * 1000
        assert status == 429, (status, payload)
        assert "Retry-After" in headers
        assert int(headers["Retry-After"]) >= 1
        assert shed_ms < 100, f"shed took {shed_ms:.0f}ms (not admission!)"

        # the admin control-plane door sheds with the same contract
        server = AdminServer(admin).start()
        try:
            astatus, apayload, aheaders = _post(
                "127.0.0.1", server.port, "/predict/ovl", {
                    "queries": [[0.0]]}, token=token)
            assert astatus == 429, (astatus, apayload)
            assert "Retry-After" in aheaders
        finally:
            server.stop()

        for t in threads:
            t.join(timeout=30)
        assert [s for s, _ in results] == [200] * 4, results
        # the shed is visible to operators
        health = admin.get_fleet_health()
        jobs = health["serving"]["jobs"]
        assert jobs and all(j["status"] == "ok" for j in jobs.values())
        shed_total = sum(
            j["overload"]["requests_shed"] for j in jobs.values())
        assert shed_total >= 1
    finally:
        chaos.clear()
        admin.shutdown()


def test_expired_queries_never_reach_the_model(tmp_workdir, monkeypatch):
    """A request whose deadline lapses while queued behind a stalled
    replica is dropped at take_batch — 504 to the client inside its own
    timeout (not the worker's stall), and the expired counter increments
    in SERVING_STATS."""
    admin, uid, token, host, port = _deploy(
        tmp_workdir, monkeypatch, "exp",
        env={"RAFIKI_PREDICT_QUEUE_DEPTH": "8"})
    try:
        _stall_workers(1.5)
        threads = []
        for _ in range(2):  # occupy both replicas
            t = threading.Thread(target=_post, args=(
                host, port, "/predict", {"queries": [[0.0]]}, token))
            t.start()
            threads.append(t)
            time.sleep(0.15)
        t0 = time.monotonic()
        status, payload, _ = _post(
            host, port, "/predict",
            {"queries": [[0.0]], "timeout_s": 0.4}, token=token)
        waited = time.monotonic() - t0
        assert status == 504, (status, payload)
        assert waited < 1.2, f"504 after {waited:.2f}s — waited out the stall"
        for t in threads:
            t.join(timeout=30)
        # the doomed queries were dropped un-served: expired counter ticks
        # once the workers take (and discard) them
        deadline = time.monotonic() + 10
        expired = 0
        while time.monotonic() < deadline:
            workers = admin.get_fleet_health()["serving"]["workers"]
            expired = sum(w.get("expired", 0) for w in workers.values())
            if expired >= 1:
                break
            time.sleep(0.2)
        assert expired >= 1, workers
    finally:
        chaos.clear()
        admin.shutdown()


def test_healthz_reports_load_and_degrades_without_workers():
    # live-but-empty serving plane: zero registered worker queues
    empty = Predictor("nojob", InProcessBroker(), None)
    srv = PredictorServer(empty, "emptyapp", auth=False).start()
    try:
        status, payload = _get(srv.host, srv.port, "/healthz")
        assert status == 200  # alive — degraded is a STATE, not an outage
        assert payload["status"] == "degraded"
        assert payload["workers"] == 0
        assert "admission" in payload and "overload" in payload
    finally:
        srv.stop()

    broker = InProcessBroker()
    broker.register_worker("job", "w1")
    live = Predictor("job", broker, None, worker_trials={"w1": "t"})
    srv = PredictorServer(live, "liveapp", auth=False).start()
    try:
        status, payload = _get(srv.host, srv.port, "/healthz")
        assert payload["status"] == "ok"
        assert payload["queue_depths"] == {"w1": 0}
    finally:
        srv.stop()


def test_fleet_health_marks_queueless_job_degraded(tmp_workdir, monkeypatch):
    """Admin-side twin of the /healthz verdict: a job whose predictor has
    zero registered worker queues reads degraded in GET /fleet/health."""
    admin = Admin(params_dir=str(tmp_workdir / "params"))
    try:
        admin.services._predictors["ghost-job"] = Predictor(
            "ghost-job", InProcessBroker(), None)
        serving = admin.get_fleet_health()["serving"]
        assert serving["jobs"]["ghost-job"]["status"] == "degraded"
        assert serving["jobs"]["ghost-job"]["workers"] == 0
        assert "admission" in serving
    finally:
        admin.services._predictors.pop("ghost-job", None)
        admin.shutdown()


class _SlowPredictor:
    """Predictor-shaped stub whose predict blocks — drain-test fodder."""

    def __init__(self, latency_s):
        self.latency_s = latency_s

    def predict_batch(self, queries, timeout_s=None):
        time.sleep(self.latency_s)
        return [[1.0] for _ in queries]

    def queue_depths(self):
        return {"w": 0}


def test_stop_drains_inflight_then_closes_and_is_idempotent():
    srv = PredictorServer(_SlowPredictor(0.6), "drainapp",
                          auth=False).start()
    host, port = srv.host, srv.port
    results = []

    def fire():
        results.append(_post(host, port, "/predict",
                             {"queries": [[0.0]]}, timeout=10)[0])

    t = threading.Thread(target=fire)
    t.start()
    time.sleep(0.2)  # request is mid-predict
    t0 = time.monotonic()
    srv.stop(drain_timeout_s=5.0)
    drained_in = time.monotonic() - t0
    t.join(timeout=10)
    # stop waited for the in-flight handler (≥ the remaining predict time)
    # and the client got a real answer, not a slammed connection
    assert results == [200]
    assert 0.2 < drained_in < 5.0
    # door is actually closed now
    with pytest.raises((urllib.error.URLError, ConnectionError, OSError)):
        _post(host, port, "/predict", {"queries": [[0.0]]}, timeout=2)
    srv.stop()  # double-stop: no-op, no raise


def test_stop_drain_window_is_bounded():
    srv = PredictorServer(_SlowPredictor(3.0), "slowdrain",
                          auth=False).start()
    threading.Thread(target=_post, args=(
        srv.host, srv.port, "/predict", {"queries": [[0.0]]}, None, 10),
        daemon=True).start()
    time.sleep(0.2)
    t0 = time.monotonic()
    srv.stop(drain_timeout_s=0.3)  # handler needs ~3s: the bound must win
    assert time.monotonic() - t0 < 2.0


@pytest.mark.slow
def test_stress_concurrent_clients_shed_cleanly(tmp_workdir, monkeypatch):
    """Real concurrent clients through the HTTP door with a tiny
    in-flight cap: every response is a clean 200/429/503 (shed, not
    socket errors or 500s), at least one succeeds, and the door still
    serves afterwards."""
    admin, uid, token, host, port = _deploy(
        tmp_workdir, monkeypatch, "stress",
        env={"RAFIKI_PREDICT_MAX_INFLIGHT": "2",
             "RAFIKI_PREDICT_QUEUE_DEPTH": "4"})
    try:
        _stall_workers(0.05)  # mild slowness so requests actually overlap
        codes = []
        lock = threading.Lock()

        def client():
            for _ in range(3):
                status, _, _ = _post(host, port, "/predict",
                                     {"queries": [[0.0]]}, token=token)
                with lock:
                    codes.append(status)

        threads = [threading.Thread(target=client) for _ in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert len(codes) == 36
        assert set(codes) <= {200, 429, 503}, sorted(set(codes))
        assert codes.count(200) >= 1
        chaos.clear()
        status, payload, _ = _post(host, port, "/predict",
                                   {"queries": [[0.0]]}, token=token)
        assert status == 200, (status, payload)  # door healthy after the storm
    finally:
        chaos.clear()
        admin.shutdown()


def test_the_doors_listener_accepts_64_connections_opened_at_once():
    """The per-job door's listen queue holds a burst of callers: 64
    connections opened before the accept loop takes one are all accepted
    and answered (Python's default of 5 resets the rest under load)."""
    class _Predictor:
        def queue_depths(self):
            return {"w": 0}

    server = PredictorServer(_Predictor(), "burst", auth=False).start()
    try:
        assert server._httpd.request_queue_size >= 64
        # hold the accept loop: the burst has to wait in the listen queue
        held = threading.Event()
        real = server._httpd.get_request

        def slow():
            held.wait(5)
            return real()

        server._httpd.get_request = slow
        socks = []
        for _ in range(64):
            s = socket.socket()
            s.settimeout(10)
            s.connect(("127.0.0.1", server.port))
            socks.append(s)
        held.set()
        for s in socks:
            s.sendall(b"GET /healthz HTTP/1.1\r\nHost: x\r\n"
                      b"Connection: close\r\n\r\n")
        answers = [s.recv(64) for s in socks]
        for s in socks:
            s.close()
        assert all(a.startswith(b"HTTP/1.1 200") for a in answers)
    finally:
        server.stop(drain_timeout_s=1.0)


def test_a_streams_wait_for_its_slot_stays_out_of_the_doors_wait_model():
    """The first stream of a cold replica waits for the programs to
    compile before a slot takes it. The door's estimate of a queue's wait
    (backlog x seconds a token) learns from the seconds a token took once
    the stream had its slot; the latency histogram keeps the whole."""
    import http.client

    from rafiki_tpu.cache.queue import TokenStream
    class _Predictor:
        def queue_depths(self):
            return {"w": 0}

        def backlog_depth(self):
            return 32

        def generate(self, query, timeout_s):
            time.sleep(0.6)  # no slot yet: a compile, a queue
            stream = TokenStream("s1")
            stream.push([1, 2, 3, 4], finished=True, reason="length")
            return stream

    server = PredictorServer(_Predictor(), "coldstart", auth=False).start()
    try:
        def ask():
            conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                              timeout=10)
            conn.request("POST", "/generate", json.dumps(
                {"prompt_ids": [1, 2], "max_tokens": 4, "timeout_s": 5.0}))
            resp = conn.getresponse()
            return resp.status, resp.read()

        status, body = ask()
        assert status == 200 and b'"tokens": [1, 2, 3, 4]' in body
        # 0.6 s over 4 tokens and a backlog of 32 would be 4.8 s of 5
        # the door observes after the body's last byte: wait for it
        until = time.monotonic() + 5.0
        while (server.admission._h_request.quantile(0.5) is None
               and time.monotonic() < until):
            time.sleep(0.01)
        ewma = server.admission.stats()["ewma_query_s"]
        assert 0.0 < ewma < 0.05
        assert server.admission._h_request.quantile(0.5) >= 0.5
        assert ask()[0] == 200  # and the next stream is not shed
    finally:
        server.stop(drain_timeout_s=1.0)
