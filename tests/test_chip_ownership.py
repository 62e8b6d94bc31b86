"""One process for each chip, and no fallback that hides the device.

A chip belongs to the process that opened it. So: a parent of worker
processes never initialises a JAX backend, each child is pinned to its
grant and numbers its devices from 0, and the driver-facing entry points
fail without a device instead of re-running themselves on the CPU.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from rafiki_tpu.placement import process as proc_mod
from rafiki_tpu.placement.manager import ChipAllocator, ServiceContext
from rafiki_tpu.utils import backend_probe


def _child(code: str, env: dict, timeout: float = 240):
    full = dict(os.environ, PYTHONPATH=REPO, **env)
    return subprocess.run([sys.executable, "-c", code], env=full, text=True,
                          capture_output=True, timeout=timeout)


# -- the probe --------------------------------------------------------------

def test_probe_counts_devices_in_a_child():
    # the test env is a virtual 8-device CPU mesh (conftest.py)
    n, err = backend_probe.probe_device_count(timeout_s=120)
    assert err is None and n == 8


def test_probe_dead_backend_reports_the_backends_error(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "nosuchplatform")
    n, err = backend_probe.probe_device_count(timeout_s=120)
    assert n == 0 and "rc=" in err


def test_probe_kills_its_child_at_the_timeout(monkeypatch):
    """The child takes the chip for its lifetime: one that outlives its
    timeout is killed, never left behind holding it."""
    monkeypatch.setattr(
        backend_probe, "_PROBE_CODE",
        "import os, time; open(os.environ['PID_FILE'], 'w')"
        ".write(str(os.getpid())); time.sleep(600)")
    pid_file = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                            f"probe-child-{os.getpid()}.pid")
    monkeypatch.setenv("PID_FILE", pid_file)
    try:
        n, err = backend_probe.probe_device_count(timeout_s=3.0)
        assert n == 0 and "killed" in err
        pid = int(open(pid_file).read())
        with pytest.raises(ProcessLookupError):
            os.kill(pid, 0)
    finally:
        if os.path.exists(pid_file):
            os.unlink(pid_file)


def test_probe_module_is_only_the_count():
    """The probe lock, orphan ledger, deferred signals and hook stripping
    belonged to a backend that is gone."""
    import types

    functions = {n for n, v in vars(backend_probe).items()
                 if isinstance(v, types.FunctionType)}
    assert functions == {"probe_device_count"}


# -- the parent stays off JAX -----------------------------------------------

def test_host_chip_inventory_from_setting_needs_no_child(monkeypatch):
    monkeypatch.setenv("RAFIKI_VISIBLE_DEVICES", "2,3")
    monkeypatch.setattr(
        backend_probe, "probe_device_count",
        lambda *a, **k: pytest.fail("a setting needs no probe"))
    assert proc_mod.host_chip_inventory() == [2, 3]


def test_host_chip_inventory_from_probe(monkeypatch):
    monkeypatch.delenv("RAFIKI_VISIBLE_DEVICES", raising=False)
    monkeypatch.setattr(backend_probe, "probe_device_count",
                        lambda *a, **k: (4, None))
    assert proc_mod.host_chip_inventory() == [0, 1, 2, 3]
    monkeypatch.setattr(backend_probe, "probe_device_count",
                        lambda *a, **k: (0, "rc=1: no backend"))
    with pytest.raises(RuntimeError, match="RAFIKI_VISIBLE_DEVICES"):
        proc_mod.host_chip_inventory()


@pytest.mark.parametrize("mode", ["process", "hosts"])
def test_admin_with_worker_processes_never_initialises_jax(tmp_path, mode):
    """Admin in process/hosts mode, booted in a fresh interpreter: the
    chip inventory is there, and jax has no initialised backend."""
    code = (
        "import json, os\n"
        "from rafiki_tpu.admin.admin import Admin\n"
        "from rafiki_tpu.admin.http import AdminServer\n"
        "from rafiki_tpu.db.database import Database\n"
        "admin = Admin(db=Database(os.environ['DBP']),\n"
        "              params_dir=os.environ['PARAMS'])\n"
        "server = AdminServer(admin, port=0).start()\n"
        "import jax._src.xla_bridge as xb\n"
        "local = getattr(admin.placement, 'local', admin.placement)\n"
        "print(json.dumps({'initialised': xb.backends_are_initialized(),\n"
        "                  'chips': local.allocator.total_chips}))\n"
        "server.stop(); admin.shutdown()\n"
    )
    out = _child(code, {
        "RAFIKI_PLACEMENT": mode, "RAFIKI_WORKDIR": str(tmp_path),
        "DBP": str(tmp_path / "rafiki.sqlite3"),
        "PARAMS": str(tmp_path / "params"),
        # an agent nobody answers at: placement is built, never reached
        "RAFIKI_AGENTS": "127.0.0.1:9", "RAFIKI_VISIBLE_DEVICES": "0,1"})
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec == {"initialised": False, "chips": 2}


# -- the child sees only its grant ------------------------------------------

@pytest.mark.parametrize("chips,bounds", [
    ([0], "1,1,1"), ([3], "1,1,1"), ([2, 3], "1,2,1"),
    ([0, 1, 2, 3], "2,2,1")])
def test_grant_env_pins_the_child_to_its_chips(chips, bounds):
    env = proc_mod.grant_env(chips)
    assert env["TPU_VISIBLE_CHIPS"] == ",".join(map(str, chips))
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == bounds
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert "JAX_PLATFORMS" not in env


def test_child_without_a_grant_is_kept_off_the_accelerator():
    assert proc_mod.grant_env([]) == {"JAX_PLATFORMS": "cpu"}


def test_child_env_carries_the_grant(tmp_path, monkeypatch):
    from rafiki_tpu.db.database import Database

    monkeypatch.setenv("RAFIKI_VISIBLE_DEVICES", "0,1,2,3")
    mgr = proc_mod.ProcessPlacementManager(
        db=Database(str(tmp_path / "db.sqlite3")),
        allocator=ChipAllocator([0, 1, 2, 3]))
    import threading

    ctx = ServiceContext("svc-1", "TRAIN", [2], threading.Event(),
                         extra={"sub_train_job_id": "sub"})
    env = mgr._child_env(ctx)
    assert env["RAFIKI_CHIP_GRANT"] == "2"
    assert env["TPU_VISIBLE_CHIPS"] == "2"
    # the process-wide fallback must not fight the explicit grant
    assert "RAFIKI_VISIBLE_DEVICES" not in env


def test_process_manager_needs_an_explicit_inventory():
    """No default: ChipAllocator(None) would open the chips in the parent."""
    with pytest.raises(TypeError, match="explicit inventory"):
        proc_mod.ProcessPlacementManager()


def test_agent_inventory_reads_its_own_setting(monkeypatch):
    monkeypatch.setenv("RAFIKI_AGENT_CHIPS", "4,5")
    assert proc_mod.host_chip_inventory("RAFIKI_AGENT_CHIPS") == [4, 5]


def test_worker_child_reindexes_its_grant(tmp_path):
    """The parent granted host chip 5; the child has that chip as ITS
    device 0 — bootstrap must index what the child actually has."""
    from rafiki_tpu.db.database import Database

    db_path = str(tmp_path / "db.sqlite3")
    db = Database(db_path)
    svc = db.create_service("TRAIN", replicas=1)
    db.close()
    code = (
        "import json, sys\n"
        "import rafiki_tpu.worker.bootstrap as b\n"
        "def fake_train(ctx, db, admin_client):\n"
        "    ctx.ready()\n"
        "    print('CTX=' + json.dumps({'chips': ctx.chips, 'devices':\n"
        "          [d.id for d in ctx.devices()]}), flush=True)\n"
        "b._run_train = fake_train\n"
        "sys.exit(b.main())\n"
    )
    out = _child(code, {
        "RAFIKI_SERVICE_ID": svc["id"], "RAFIKI_SERVICE_TYPE": "TRAIN",
        "RAFIKI_DB_PATH": db_path, "RAFIKI_CHIP_GRANT": "5",
        "RAFIKI_WORKDIR": str(tmp_path), "RAFIKI_ADMIN_ADDR": "",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("CTX=")][0]
    assert json.loads(line[4:]) == {"chips": [0], "devices": [0]}
    # platform, kind and count of the granted devices, logged at ready()
    assert "ready: grant 5 -> 1 device(s), platform=cpu" in out.stderr
    assert Database(db_path).get_service(svc["id"])["status"] == "STOPPED"


def test_sandbox_child_is_cpu_only(tmp_path, monkeypatch):
    """RAFIKI_SANDBOX=1 starts a child per trial from a worker that already
    holds the chip: the jailed child must not reach for it."""
    from rafiki_tpu.sdk import sandbox

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0")
    env = sandbox._child_env(str(tmp_path))
    assert env["JAX_PLATFORMS"] == "cpu"


# -- spare chips go to the first sub-jobs -----------------------------------

def test_one_chip_two_models_gives_the_chip_to_the_first(tmp_path):
    """A two-model job on a one-chip host must not strand the only chip
    while both workers run without one."""
    from rafiki_tpu.admin.admin import Admin
    from rafiki_tpu.db.database import Database
    from rafiki_tpu.placement.manager import LocalPlacementManager

    fixture = os.path.join(REPO, "tests", "fixtures", "fake_model.py")
    admin = Admin(db=Database(":memory:"),
                  placement=LocalPlacementManager(
                      allocator=ChipAllocator([0])),
                  params_dir=str(tmp_path / "params"))
    grants = []
    real = admin.placement.create_service

    def spy(service_id, service_type, run_fn, n_chips=0, **kw):
        grants.append(n_chips)
        return real(service_id, service_type, run_fn, n_chips=n_chips, **kw)

    admin.placement.create_service = spy
    try:
        from rafiki_tpu import config

        uid = admin.authenticate_user(
            config.SUPERADMIN_EMAIL, config.SUPERADMIN_PASSWORD)["user_id"]
        with open(fixture, "rb") as f:
            src = f.read()
        admin.create_model(uid, "m1", "IMAGE_CLASSIFICATION", src,
                           "FakeModel")
        admin.create_model(uid, "m2", "IMAGE_CLASSIFICATION", src,
                           "FakeModel")
        admin.create_train_job(
            uid, "app", "IMAGE_CLASSIFICATION", "u://t", "u://e",
            budget={"MODEL_TRIAL_COUNT": 1, "CHIP_COUNT": 1})
        admin.wait_until_train_job_stopped(uid, "app", timeout_s=60)
        assert sorted(grants) == [0, 1]
    finally:
        admin.shutdown()


# -- no re-run on the CPU ---------------------------------------------------

def test_dryrun_multichip_fails_on_too_few_devices():
    import jax

    import __graft_entry__ as ge

    with pytest.raises(RuntimeError, match="needs 64 devices"):
        ge.dryrun_multichip(64)
    assert len(jax.devices()) == 8  # nothing re-provisioned anything


def test_graft_entry_has_no_child_or_probe_path():
    import __graft_entry__ as ge

    for gone in ("_run_dryrun_child", "_dryrun_impl", "subprocess"):
        assert not hasattr(ge, gone)


def test_benchmark_finds_no_chip_on_the_cpu():
    """No CPU branch: in this CPU process the harness's look for a chip
    fails, naming what it found; it measures nothing on the CPU."""
    from benchmark import harness

    with pytest.raises(harness.BenchmarkError, match="no accelerator"):
        harness.find_chip(1)


def test_benchmark_run_off_the_chip_exits_2_and_prints_no_result(capsys):
    """A failure ends in a non-zero exit and no metrics line: off the chip
    the harness reports no number at all (find_chip raises before anything
    is compiled)."""
    from benchmark import run

    rc = run.main(["--workload", "vit_b16.hpo_search", "--seed", "1",
                   "--seconds", "1"])
    captured = capsys.readouterr()
    assert rc == 2
    assert captured.out == ""
    assert "no accelerator" in captured.err


# -- the peak is a table keyed by device kind -------------------------------

def test_peak_table_is_keyed_by_device_kind():
    from benchmark import harness

    peaks = harness.peaks_for("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchmarkError, match="not in"):
        harness.peaks_for("TPU v99 imaginary")


# -- the kernel does not choose the interpreter by itself -------------------

def test_flash_attention_does_not_choose_the_interpreter():
    """Off the TPU a plain flash_attention call must RAISE (Mosaic cannot
    compile for the CPU); only interpret=True reaches the interpreter."""
    import jax
    import jax.numpy as jnp

    from rafiki_tpu.ops import flash_attention, mha_reference

    q = jax.random.normal(jax.random.key(0), (1, 1, 32, 16), jnp.float32)
    with pytest.raises(Exception):
        jax.block_until_ready(flash_attention(q, q, q, False, None, 16, 16))
    out = flash_attention(q, q, q, False, None, 16, 16, True)
    ref = mha_reference(q, q, q)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5
    mod = sys.modules["rafiki_tpu.ops.flash_attention"]
    assert not hasattr(mod, "_use_interpret")


def test_no_version_shims_remain():
    from rafiki_tpu.parallel import sharding

    assert not hasattr(sharding, "shard_map")
    assert not hasattr(sharding, "axis_size")
