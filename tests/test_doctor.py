"""Deployment doctor (rafiki_tpu/doctor.py): bounded health checks that
never hang on an accelerator that does not come up."""

import json
import os
import sys
import threading

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from rafiki_tpu import doctor


def test_all_checks_run_and_report(tmp_path, monkeypatch, capsys):
    monkeypatch.setenv("RAFIKI_WORKDIR", str(tmp_path))
    monkeypatch.delenv("RAFIKI_AGENTS", raising=False)
    # keep the accelerator probe instant in tests: the env mesh is healthy
    rc = doctor.run()
    out = capsys.readouterr().out
    assert rc == 0
    for name in ("workdir", "metadata store", "shm data plane",
                 "model sandbox", "host agents", "accelerator"):
        assert name in out


def test_json_output_parses(tmp_path, monkeypatch, capsys):
    monkeypatch.setenv("RAFIKI_WORKDIR", str(tmp_path))
    rc = doctor.run(json_out=True)
    records = json.loads(capsys.readouterr().out)
    assert {r["check"] for r in records} >= {"workdir", "metadata store"}
    assert all(r["status"] in ("PASS", "WARN", "FAIL") for r in records)


def test_unwritable_workdir_fails(tmp_path, monkeypatch):
    blocked = tmp_path / "blocked"
    blocked.mkdir(mode=0o500)
    if os.geteuid() == 0:
        pytest.skip("root writes anywhere; perm-based check not testable")
    monkeypatch.setenv("RAFIKI_WORKDIR", str(blocked))
    assert doctor.run() == 1


def test_down_agents_reported(tmp_path, monkeypatch, capsys):
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        dead = f"127.0.0.1:{s.getsockname()[1]}"
    monkeypatch.setenv("RAFIKI_WORKDIR", str(tmp_path))
    monkeypatch.setenv("RAFIKI_AGENTS", dead)
    rc = doctor.run()
    out = capsys.readouterr().out
    assert rc == 1
    # dead host (no /healthz answer) reads as DOWN, distinct from the
    # locked/key-rejected config failures
    assert "DOWN (no /healthz answer)" in out


def test_recovery_check_flags_orphaned_jobs(tmp_path, monkeypatch, capsys):
    """A non-terminal job whose services are all terminal is the
    signature of a dead, never-restarted admin — doctor must say so."""
    from rafiki_tpu.constants import ServiceType, UserType
    from rafiki_tpu.db.database import Database

    monkeypatch.setenv("RAFIKI_WORKDIR", str(tmp_path))
    db = Database(str(tmp_path / "rafiki.sqlite3"))
    user = db.create_user("u@x", "h", UserType.APP_DEVELOPER)
    model = db.create_model(user["id"], "m", "T", b"", "M", {}, "PRIVATE")
    tj = db.create_train_job(user["id"], "app", 1, "T", "u://t", "u://e", {})
    db.mark_train_job_as_running(tj["id"])
    sub = db.create_sub_train_job(tj["id"], model["id"])
    svc = db.create_service(ServiceType.TRAIN)
    db.create_train_job_worker(svc["id"], sub["id"])
    db.mark_service_as_errored(svc["id"])  # worker died; admin never saw
    # backdate past the deploy-in-progress grace: a FRESH job with no
    # workers yet is a live admin mid-deploy, not an orphan
    import time

    db._exec("UPDATE train_job SET datetime_started=? WHERE id=?",
             (time.time() - 600, tj["id"]))
    db.close()
    name, status, detail = doctor.check_recovery()
    assert status == doctor.WARN
    assert "orphaned by a dead admin" in detail


def test_recovery_check_warns_when_adoption_disabled(tmp_path, monkeypatch):
    monkeypatch.setenv("RAFIKI_WORKDIR", str(tmp_path))
    monkeypatch.setenv("RAFIKI_RECOVER_ADOPT", "0")
    name, status, detail = doctor.check_recovery()
    assert status == doctor.WARN
    assert "FENCE" in detail


def test_recovery_check_reports_last_reconcile(tmp_path, monkeypatch):
    monkeypatch.setenv("RAFIKI_WORKDIR", str(tmp_path))
    from rafiki_tpu.admin import recovery as rec

    os.makedirs(os.path.dirname(rec.report_path()), exist_ok=True)
    with open(rec.report_path(), "w") as f:
        json.dump({"state": "ready", "duration_s": 1.25, "adopted": 3,
                   "rescheduled": 1, "fenced": 0, "errored": 0}, f)
    name, status, detail = doctor.check_recovery()
    assert status == doctor.PASS
    assert "3 adopted" in detail and "1.25" in detail


def test_autoscaler_check_warns_on_inverted_bounds(tmp_path, monkeypatch):
    monkeypatch.setenv("RAFIKI_WORKDIR", str(tmp_path))
    monkeypatch.setenv("RAFIKI_AUTOSCALE_MIN_REPLICAS", "5")
    monkeypatch.setenv("RAFIKI_AUTOSCALE_MAX_REPLICAS", "2")
    name, status, detail = doctor.check_autoscaler(total_chips=8)
    assert status == doctor.WARN
    assert "INVERTED" in detail


def test_autoscaler_check_warns_when_floor_exceeds_fleet(tmp_path,
                                                         monkeypatch):
    monkeypatch.setenv("RAFIKI_WORKDIR", str(tmp_path))
    monkeypatch.setenv("RAFIKI_AUTOSCALE_TRAIN_FLOOR", "64")
    name, status, detail = doctor.check_autoscaler(total_chips=8)
    assert status == doctor.WARN
    assert "exceeds" in detail
    # a sane floor against the same fleet: that clause stays quiet
    monkeypatch.setenv("RAFIKI_AUTOSCALE_TRAIN_FLOOR", "2")
    name, status, detail = doctor.check_autoscaler(total_chips=8)
    assert "exceeds the fleet" not in detail


def test_autoscaler_check_warns_on_shed_with_loop_off(tmp_path,
                                                      monkeypatch):
    """Sustained shed observed while autoscaling is disabled: the fleet
    is turning traffic away that a scale-up could absorb — WARN."""
    from rafiki_tpu.utils.metrics import REGISTRY

    monkeypatch.setenv("RAFIKI_WORKDIR", str(tmp_path))
    monkeypatch.delenv("RAFIKI_AUTOSCALE", raising=False)
    REGISTRY.ring("shed_rate:doctor-drill-door").add(5)
    name, status, detail = doctor.check_autoscaler(total_chips=8)
    assert status == doctor.WARN
    assert "RAFIKI_AUTOSCALE is OFF" in detail
    assert "doctor-drill-door" in detail


def test_autoscaler_check_warns_without_hysteresis(tmp_path, monkeypatch):
    monkeypatch.setenv("RAFIKI_WORKDIR", str(tmp_path))
    monkeypatch.setenv("RAFIKI_AUTOSCALE_DEPTH_LOW", "8")
    monkeypatch.setenv("RAFIKI_AUTOSCALE_DEPTH_HIGH", "8")
    name, status, detail = doctor.check_autoscaler(total_chips=8)
    assert status == doctor.WARN
    assert "hysteresis" in detail


def test_crashing_check_is_contained(monkeypatch, tmp_path, capsys):
    monkeypatch.setenv("RAFIKI_WORKDIR", str(tmp_path))

    def boom():
        raise RuntimeError("diagnostic bug")

    monkeypatch.setattr(doctor, "CHECKS", [boom, doctor.check_workdir])
    rc = doctor.run()
    out = capsys.readouterr().out
    assert rc == 1
    assert "check crashed" in out
    assert "workdir" in out  # later checks still ran


def test_doctor_never_blocks_event_loop(tmp_path, monkeypatch):
    """The whole point: even with every probe path exercised, the doctor
    finishes quickly (bounded probes; no live-backend init in-process)."""
    monkeypatch.setenv("RAFIKI_WORKDIR", str(tmp_path))
    done = threading.Event()

    def run():
        doctor.run()
        done.set()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert done.wait(timeout=120), "doctor hung"
