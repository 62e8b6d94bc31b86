"""Durable artifacts (sdk/artifact.py): atomic + checksummed trial params
and mid-trial checkpoints. The corruption drills: a truncated checkpoint
-> the trial completes from scratch (warn, never crash); a truncated
params file -> typed ArtifactCorruptError at download/deploy, never a
deserialize traceback or a worker crash (ISSUE 4 satellites)."""

import glob
import os
import threading

import numpy as np
import pytest

from rafiki_tpu import config
from rafiki_tpu.sdk import artifact
from rafiki_tpu.sdk.artifact import ArtifactCorruptError


# ---------------------------------------------------------------------------
# framing + atomic write
# ---------------------------------------------------------------------------


def test_wrap_unwrap_roundtrip_and_legacy_passthrough():
    payload = b"\x00\x01binary payload\xff" * 100
    framed = artifact.wrap(payload)
    assert framed.startswith(artifact.MAGIC)
    assert artifact.unwrap(framed) == payload
    # legacy (un-framed) data passes through untouched — old params and
    # checkpoints written before the frame existed must keep loading
    legacy = b"\x81\xa6params\xc4\x03abc"  # msgpack-ish: never magic
    assert artifact.unwrap(legacy) == legacy
    assert artifact.unwrap(b"") == b""
    assert artifact.unwrap(b"\x81") == b"\x81"  # short legacy passes too


@pytest.mark.parametrize("damage", [
    lambda d: d[: len(d) // 2],                      # truncated payload
    lambda d: d[: artifact.HEADER_SIZE - 3],         # truncated header
    lambda d: d[:3],                                 # truncated inside magic
    lambda d: d[:-4] + bytes(4),                     # garbled tail
    lambda d: d[: artifact.HEADER_SIZE] + b"X" + d[artifact.HEADER_SIZE + 1:],
])
def test_damaged_frames_raise_typed_error(damage):
    framed = artifact.wrap(b"precious parameters" * 50)
    with pytest.raises(ArtifactCorruptError):
        artifact.unwrap(damage(framed), path="x.params")


def test_atomic_write_leaves_no_tmp_and_applies_mode(tmp_path):
    path = tmp_path / "a.params"
    artifact.write_artifact(str(path), b"payload", mode=0o600)
    assert artifact.read_artifact(str(path)) == b"payload"
    assert (os.stat(path).st_mode & 0o777) == 0o600
    assert glob.glob(str(tmp_path / "*.tmp")) == []
    # overwrite is atomic too: the old content is never torn
    artifact.write_artifact(str(path), b"payload2")
    assert artifact.read_artifact(str(path)) == b"payload2"
    assert glob.glob(str(tmp_path / "*.tmp")) == []


def _some_buffers():
    """A stream as `sdk/params.py stream_params` hands one over: small bytes
    between views of arrays' memory (bfloat16 has no buffer format of its
    own: the view is of bytes)."""
    from rafiki_tpu.sdk.params import stream_params

    import ml_dtypes

    rng = np.random.default_rng(3)
    tree = {"w": rng.standard_normal((300, 70)).astype(np.float32),
            "h": rng.standard_normal((64, 9)).astype(ml_dtypes.bfloat16),
            "e": np.zeros((0, 4), np.float32), "step": 7}
    return stream_params(tree)[0]


def test_streamed_artifact_is_the_same_file_bit_for_bit(tmp_path):
    """`write_artifact(path, buffers)` == `write_artifact(path, joined)`:
    same frame, same bytes; the reader verifies it; damage is typed."""
    buffers = _some_buffers()
    joined = b"".join(buffers)
    a, b = str(tmp_path / "a.params"), str(tmp_path / "b.params")
    assert artifact.write_artifact(a, buffers, mode=0o600) == len(joined)
    assert artifact.write_artifact(b, joined, mode=0o600) == len(joined)
    with open(a, "rb") as fa, open(b, "rb") as fb:
        on_disk = fa.read()
        assert on_disk == fb.read() == artifact.wrap(joined)
    assert artifact.read_artifact(a) == joined
    assert (os.stat(a).st_mode & 0o777) == 0o600
    assert glob.glob(str(tmp_path / "*.tmp")) == []
    # a generator streams the same way (nothing asks for a length)
    artifact.write_artifact(a, (buf for buf in buffers))
    assert artifact.read_artifact(a) == joined
    # the un-framed writer takes buffers too
    artifact.atomic_write_bytes(b, buffers)
    with open(b, "rb") as fb:
        assert fb.read() == joined
    flipped = bytearray(on_disk)
    flipped[len(flipped) // 2] ^= 0x01
    with open(a, "wb") as fa:
        fa.write(flipped)
    with pytest.raises(ArtifactCorruptError, match="checksum"):
        artifact.read_artifact(a)


def test_stream_that_raises_halfway_leaves_the_old_file_whole(tmp_path):
    path = str(tmp_path / "t.params")
    artifact.write_artifact(path, b"the old parameters")

    def failing():
        yield b"x" * 100_000
        raise OSError(28, "No space left on device")

    with pytest.raises(OSError, match="No space left"):
        artifact.write_artifact(path, failing())
    assert artifact.read_artifact(path) == b"the old parameters"
    assert glob.glob(str(tmp_path / "*.tmp")) == []


def test_streamed_persist_holds_no_copy_of_the_payload(tmp_path):
    """The mechanism itself: a 64 MB tree goes from the leaves' memory to
    the framed file under 8 MB of traced allocations; through one `bytes`
    of the whole tree (flax's packer, the path until PR 28) it takes more
    than twice the payload."""
    import tracemalloc

    from flax import serialization

    from rafiki_tpu.sdk.params import load_params, stream_params

    tree = {f"layer{i}": {"w": np.full((2048, 1024), i, np.float32)}
            for i in range(8)}  # 8 x 8 MB
    path = str(tmp_path / "big.params")

    def peak_of(persist):
        tracemalloc.start()
        try:
            persist()
            return tracemalloc.get_traced_memory()[1]
        finally:
            tracemalloc.stop()

    streamed = peak_of(lambda: artifact.write_artifact(
        path, stream_params(tree)[0]))
    assert os.path.getsize(path) > 64 * 2 ** 20
    back = load_params(artifact.read_artifact(path))
    np.testing.assert_array_equal(back["layer7"]["w"], tree["layer7"]["w"])
    del back
    joined = peak_of(lambda: artifact.write_artifact(
        path, serialization.msgpack_serialize(tree)))
    assert streamed < 8 * 2 ** 20, streamed
    assert joined > 128 * 2 ** 20, joined


# ---------------------------------------------------------------------------
# corrupt checkpoint -> fresh start (warn, don't crash the trial)
# ---------------------------------------------------------------------------


def _tiny_trainer():
    import jax.numpy as jnp
    import optax

    from rafiki_tpu.sdk.jax_backend import DataParallelTrainer

    def loss_fn(params, batch, rng):
        x, y = batch
        pred = x @ params["w"]
        return jnp.mean((pred - y) ** 2), None

    trainer = DataParallelTrainer(loss_fn, optax.sgd(0.1))
    params, opt_state = trainer.init(
        lambda rng: {"w": jnp.zeros((4, 1), jnp.float32)})
    return trainer, params, opt_state


def test_corrupt_checkpoint_falls_back_to_fresh_start(tmp_path):
    x = np.random.default_rng(0).normal(size=(64, 4)).astype(np.float32)
    y = (x @ np.ones((4, 1), np.float32))
    trainer, params, opt_state = _tiny_trainer()
    ckpt = str(tmp_path / "trial.ckpt")
    # a healthy run writes a verifiable checkpoint
    trainer.fit(params, opt_state, (x, y),
                epochs=2, batch_size=32, checkpoint_path=ckpt)
    assert os.path.exists(ckpt)
    assert artifact.read_artifact(ckpt)  # frame verifies
    # now the checkpoint rots on disk: fit() must warn and train from
    # scratch, not crash the trial
    with open(ckpt, "wb") as f:
        f.write(artifact.wrap(b"not a checkpoint")[:-3])
    trainer2, params2, opt_state2 = _tiny_trainer()
    out2 = trainer2.fit(params2, opt_state2, (x, y),
                        epochs=2, batch_size=32, checkpoint_path=ckpt)
    w = np.asarray(out2[0]["w"])
    assert np.isfinite(w).all()
    # and the rewritten checkpoint is whole again
    assert artifact.read_artifact(ckpt)


# ---------------------------------------------------------------------------
# corrupt params -> typed error at download AND deploy
# ---------------------------------------------------------------------------


def _stack_with_completed_trial(tmp_workdir):
    from rafiki_tpu.admin.admin import Admin
    from rafiki_tpu.db.database import Database

    admin = Admin(db=Database(":memory:"),
                  params_dir=str(tmp_workdir / "params"))
    uid = admin.authenticate_user(
        config.SUPERADMIN_EMAIL, config.SUPERADMIN_PASSWORD)["user_id"]
    fixture = os.path.join(os.path.dirname(__file__), "fixtures",
                           "fake_model.py")
    with open(fixture, "rb") as f:
        admin.create_model(uid, "fake", "IMAGE_CLASSIFICATION", f.read(),
                           "FakeModel")
    admin.create_train_job(
        uid, "corruptapp", "IMAGE_CLASSIFICATION", "uri://t", "uri://e",
        budget={"MODEL_TRIAL_COUNT": 1})
    admin.wait_until_train_job_stopped(uid, "corruptapp", timeout_s=60)
    trial = admin.get_best_trials_of_train_job(uid, "corruptapp")[0]
    return admin, uid, trial


def test_corrupt_params_is_typed_at_download_and_deploy(tmp_workdir):
    from rafiki_tpu.admin.services import ServiceDeploymentError
    from rafiki_tpu.client.client import Client, RafikiError
    from rafiki_tpu.admin.http import AdminServer

    admin, uid, trial = _stack_with_completed_trial(tmp_workdir)
    server = AdminServer(admin).start()
    try:
        # healthy download first: framed on disk, plain msgpack over the
        # wire (the client-side load path is unchanged)
        raw = admin.get_trial_params(trial["id"])
        from rafiki_tpu.sdk.params import load_params

        assert load_params(raw)["weight"] == [1.0, 2.0]

        path = admin.db.get_trial(trial["id"])["params_file_path"]
        with open(path, "rb") as f:
            framed = f.read()
        with open(path, "wb") as f:
            f.write(framed[: len(framed) // 2])  # torn write / bit rot

        # download: typed, clean — library and HTTP door agree
        with pytest.raises(ArtifactCorruptError):
            admin.get_trial_params(trial["id"])
        client = Client(admin_port=server.port)
        client.login(config.SUPERADMIN_EMAIL, config.SUPERADMIN_PASSWORD)
        with pytest.raises(RafikiError, match="ArtifactCorruptError"):
            client.download_trial_params(trial["id"])

        # deploy: the serving worker refuses the corrupt file with the
        # typed error; the deploy rolls back cleanly (job ERRORED), the
        # worker never crashes the process
        with pytest.raises(ServiceDeploymentError):
            admin.create_inference_job(uid, "corruptapp")
        inf = admin.db.get_inference_jobs_by_statuses(["ERRORED"])
        assert len(inf) == 1
    finally:
        server.stop()
        admin.shutdown()


def test_resumed_trial_rewrites_params_with_frame(tmp_path):
    """End-to-end through TrainWorker: params written by the trial loop
    carry the checksummed frame and verify on read."""
    from rafiki_tpu.advisor.advisor import AdvisorStore
    from rafiki_tpu.constants import ServiceType, UserType
    from rafiki_tpu.db.database import Database
    from rafiki_tpu.placement.manager import ServiceContext
    from rafiki_tpu.worker.train import TrainWorker

    fixture = os.path.join(os.path.dirname(__file__), "fixtures",
                           "fake_model.py")
    db = Database(":memory:")
    user = db.create_user("u@x", "h", UserType.APP_DEVELOPER)
    with open(fixture, "rb") as f:
        model = db.create_model(
            user["id"], "fake", "IMAGE_CLASSIFICATION", f.read(),
            "FakeModel", {"numpy": None}, "PUBLIC")
    job = db.create_train_job(
        user["id"], "app", 1, "IMAGE_CLASSIFICATION", "uri://t", "uri://e",
        {"MODEL_TRIAL_COUNT": 1})
    sub = db.create_sub_train_job(job["id"], model["id"])
    worker = TrainWorker(sub["id"], db, AdvisorStore(),
                         params_dir=str(tmp_path / "params"))
    ctx = ServiceContext(service_id="svc", service_type=ServiceType.TRAIN,
                         chips=[], stop_event=threading.Event())
    worker.start(ctx)
    trial = db.get_trials_of_sub_train_job(sub["id"])[0]
    with open(trial["params_file_path"], "rb") as f:
        assert f.read().startswith(artifact.MAGIC)
    from rafiki_tpu.sdk.params import load_params

    assert "weight" in load_params(
        artifact.read_artifact(trial["params_file_path"]))
    db.close()
