"""Tracing subsystem: span recording, persistence, summary, nesting, and
the per-trial wiring through the full stack (SURVEY.md §5.1 names tracing
as the first-class upgrade over the reference, which has none)."""

import json
import os
import time

import numpy as np
import pytest

from rafiki_tpu.utils import trace
from rafiki_tpu.utils.trace import (
    Tracer,
    jax_profile,
    load_trace,
    trace_path,
)


def test_span_timing_and_nesting(tmp_workdir):
    t = Tracer("t1")
    with t.span("outer"):
        time.sleep(0.01)
        with t.span("inner", detail="x"):
            time.sleep(0.01)
    names = {s.name: s for s in t.spans}
    assert names["outer"].depth == 0 and names["inner"].depth == 1
    assert names["inner"].attrs == {"detail": "x"}
    assert names["outer"].duration_s >= names["inner"].duration_s > 0.0
    # inner closes first (appended first) but save orders by start time
    path = t.save()
    rows = [json.loads(line) for line in open(path)]
    assert rows[0]["name"] == "outer"


def test_trace_roundtrip(tmp_workdir):
    t = Tracer("trial-xyz")
    with t.span("train"):
        pass
    t.save()
    assert os.path.exists(trace_path("trial-xyz"))
    rows = load_trace("trial-xyz")
    assert len(rows) == 1 and rows[0]["name"] == "train"
    assert load_trace("nonexistent") == []


def test_summary_sums_by_name(tmp_workdir):
    t = Tracer("t2")
    for _ in range(3):
        with t.span("step"):
            time.sleep(0.005)
    s = t.summary()
    assert set(s) == {"step"} and s["step"] >= 0.015


def test_jax_profile_noop_without_env(tmp_workdir, monkeypatch):
    monkeypatch.delenv("RAFIKI_PROFILE", raising=False)
    with jax_profile() as out:
        assert out is None


def test_trial_trace_through_stack(tmp_workdir):
    """A train job records a trace per trial, served over REST."""
    from rafiki_tpu.admin.admin import Admin
    from rafiki_tpu.admin.http import AdminServer
    from rafiki_tpu.client.client import Client
    from rafiki_tpu.config import SUPERADMIN_EMAIL, SUPERADMIN_PASSWORD
    from rafiki_tpu.db.database import Database
    from rafiki_tpu.sdk.dataset import write_numpy_dataset

    admin = Admin(db=Database(str(tmp_workdir / "db.sqlite")))
    server = AdminServer(admin).start()
    try:
        client = Client(admin_host="127.0.0.1", admin_port=server.port)
        client.login(SUPERADMIN_EMAIL, SUPERADMIN_PASSWORD)
        rng = np.random.default_rng(0)
        y = rng.integers(0, 3, size=120).astype(np.int32)
        x = (rng.normal(size=(120, 8, 8, 1)) + y[:, None, None, None]
             ).astype(np.float32)
        train = write_numpy_dataset(x, y, str(tmp_workdir / "train.npz"))
        test = write_numpy_dataset(x, y, str(tmp_workdir / "test.npz"))
        client.create_model(
            name="NpDt", task="IMAGE_CLASSIFICATION",
            model_file_path=os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "examples", "models", "image_classification",
                "NpDecisionTree.py"),
            model_class="NpDecisionTree")
        client.create_train_job(
            app="trace_app", task="IMAGE_CLASSIFICATION",
            train_dataset_uri=train, test_dataset_uri=test,
            budget={"MODEL_TRIAL_COUNT": 1})
        deadline = time.time() + 120
        while time.time() < deadline:
            job = client.get_train_job(app="trace_app")
            if job["status"] in ("STOPPED", "ERRORED"):
                break
            time.sleep(0.5)
        assert job["status"] == "STOPPED"
        trials = client.get_trials_of_train_job(app="trace_app")
        trace = client.get_trial_trace(trials[0]["id"])
        names = {s["name"] for s in trace}
        assert {"propose", "train", "evaluate", "persist_params"} <= names
        # the phase breakdown also lands in the metric stream
        logs = client.get_trial_logs(trials[0]["id"])
        assert any("trace_train_s" in m for m in logs.get("metrics", []))
    finally:
        server.stop()
        admin.shutdown()


# -- spans on the profiler's clock (PR 24) ------------------------------------

def _host_events(trace_dir):
    """{name: [(start_ns, end_ns)]} of the host planes of the one trace
    under `trace_dir`."""
    from jax.profiler import ProfileData

    paths = [os.path.join(base, n) for base, _, names in os.walk(trace_dir)
             for n in names if n.endswith(".xplane.pb")]
    assert len(paths) == 1, paths
    events = {}
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    return events


def _phase_count(name):
    child = trace.phase_histogram().children().get((name,))
    return child.snapshot()["count"] if child else 0


def test_spans_land_in_an_open_profiler_session(tmp_path):
    """Both kinds of span, entered while a `jax.profiler` session is open,
    are in the trace's host plane by name, the child inside its parent."""
    import jax

    t = Tracer("t-prof")
    before = _phase_count("test.loop_phase")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with t.span("test.trial_phase"):
            time.sleep(0.002)
            with t.span("test.trial_step"):
                time.sleep(0.002)
        with trace.span("test.loop_phase"):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    for name in ("test.trial_phase", "test.trial_step", "test.loop_phase"):
        assert len(events.get(name, [])) == 1, (name, sorted(events))
    (p0, p1), (c0, c1) = (events["test.trial_phase"][0],
                          events["test.trial_step"][0])
    assert p0 <= c0 and c1 <= p1 and c1 - c0 >= 2e6
    # the annotation is the Tracer's own span, on another clock
    span = {s.name: s for s in t.spans}["test.trial_phase"]
    assert abs(span.duration_s - (p1 - p0) / 1e9) < 1e-3
    assert _phase_count("test.loop_phase") == before + 1


def test_spans_cost_nothing_visible_without_a_session(tmp_path, monkeypatch):
    """No session open: nothing is written and nothing raised. The same
    with `jax.profiler` not importable at all: the span primitive works
    on, its histogram and the Tracer's record with it."""
    import sys

    with Tracer("t-quiet").span("quiet"), trace.span("test.quiet_phase"):
        pass
    assert not os.listdir(tmp_path)
    monkeypatch.setattr(trace, "_annotation_cls", None)
    monkeypatch.setitem(sys.modules, "jax.profiler", None)  # ImportError
    before = _phase_count("test.quiet_phase")
    t = Tracer("t-nojax")
    with t.span("quiet"), trace.span("test.quiet_phase"):
        pass
    assert trace._annotation_cls is False
    assert trace.annotation("x") is trace.annotation("y")  # the no-op
    assert [s.name for s in t.spans] == ["quiet"]
    assert _phase_count("test.quiet_phase") == before + 1


def test_trace_module_imports_no_jax():
    """The control plane imports utils/trace.py; it must not pay a jax
    import for it, at import or at the first span."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from rafiki_tpu.utils import trace\n"
        "with trace.Tracer('a').span('x'), trace.span('y'):\n"
        "    pass\n"
        "assert trace.annotation('z') is trace._NO_ANNOTATION\n"
        "assert not [m for m in sys.modules if m.split('.')[0] == 'jax']\n")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]


class _SlowDump:
    """A template whose three persist steps each take long enough to time."""

    def __init__(self, **knobs):
        pass

    def train(self, uri):
        pass

    def evaluate(self, uri):
        return 0.5

    def dump_parameters(self):
        time.sleep(0.03)
        return {"w": np.zeros(200_000, np.float32)}

    def destroy(self):
        pass


def _train_worker(tmp_workdir):
    from rafiki_tpu.advisor.advisor import AdvisorStore
    from rafiki_tpu.db.database import Database
    from rafiki_tpu.worker.train import TrainWorker

    return TrainWorker("sub-t", Database(":memory:"), AdvisorStore(),
                       params_dir=str(tmp_workdir / "params"))


def test_persist_steps_nest_under_persist_params(tmp_workdir):
    """`persist.dump`, `.serialize` and `.write` are depth-1 spans inside
    `persist_params` and account for it."""
    from rafiki_tpu.sdk.log import ModelLogger

    tracer = Tracer("trial-p")
    score, path = _train_worker(tmp_workdir)._run_trial(
        _SlowDump, {}, {"train_dataset_uri": "", "test_dataset_uri": ""},
        "trial-p", ModelLogger(), tracer)
    assert score == 0.5 and os.path.getsize(path) > 800_000
    rows = {r["name"]: r for r in load_trace("trial-p")}
    whole = rows["persist_params"]
    steps = [rows[f"persist.{k}"] for k in ("dump", "serialize", "write")]
    assert whole["depth"] == 0 and all(s["depth"] == 1 for s in steps)
    assert all(whole["start"] <= s["start"] and s["end"] <= whole["end"]
               for s in steps)
    assert steps[0]["duration_s"] >= 0.03
    assert sum(s["duration_s"] for s in steps) == pytest.approx(
        whole["duration_s"], rel=0.05)


def test_rafiki_profile_wraps_the_whole_trial(tmp_workdir, monkeypatch):
    """RAFIKI_PROFILE: one session around the whole trial, under
    LOGS_DIR/profiles/<trial id>, with evaluate and persist on it; a
    session that is already open is left alone, with no traceback."""
    import jax

    from rafiki_tpu import config
    from rafiki_tpu.sdk.log import ModelLogger

    monkeypatch.setenv("RAFIKI_PROFILE", "1")
    worker = _train_worker(tmp_workdir)
    job = {"train_dataset_uri": "", "test_dataset_uri": ""}
    with worker._trial_profile("trial-q") as out:
        assert out == os.path.join(config.LOGS_DIR, "profiles", "trial-q")
        with worker._trial_profile("trial-r") as second:
            assert second is None  # one session a process
        worker._run_trial(_SlowDump, {}, job, "trial-q", ModelLogger(),
                          Tracer("trial-q"))
    events = _host_events(out)
    assert {"train", "evaluate", "persist_params", "persist.dump",
            "persist.serialize", "persist.write"} <= set(events)
    assert not os.path.exists(
        os.path.join(config.LOGS_DIR, "profiles", "trial-r"))
    jax.profiler.start_trace(str(tmp_workdir / "other"))
    try:
        with jax_profile() as out:
            assert out is None
    finally:
        jax.profiler.stop_trace()
