import os
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from rafiki_tpu.parallel.mesh import (
    DATA_AXIS,
    MeshSpec,
    MODEL_AXIS,
    get_default_mesh,
    make_mesh,
)
from rafiki_tpu.sdk.jax_backend import (
    DataParallelTrainer,
    classification_accuracy,
    softmax_classifier_loss,
)


def test_virtual_mesh_has_8_devices():
    assert len(jax.devices()) == 8


def test_mesh_spec_resolution():
    assert MeshSpec({DATA_AXIS: -1}).resolve(8) == {DATA_AXIS: 8}
    assert MeshSpec({DATA_AXIS: -1, MODEL_AXIS: 2}).resolve(8) == {
        DATA_AXIS: 4,
        MODEL_AXIS: 2,
    }
    with pytest.raises(ValueError):
        MeshSpec({DATA_AXIS: 3}).resolve(8)


def test_visible_devices_grant(monkeypatch):
    from rafiki_tpu.parallel.mesh import visible_devices

    monkeypatch.setenv("RAFIKI_VISIBLE_DEVICES", "0,2,4,6")
    devs = visible_devices()
    assert len(devs) == 4
    mesh = make_mesh(devices=devs)
    assert mesh.shape[DATA_AXIS] == 4
    monkeypatch.delenv("RAFIKI_VISIBLE_DEVICES")
    assert len(visible_devices()) == 8


def _linear_data(n=512, d=8, classes=3, seed=0):
    rng = np.random.default_rng(seed)
    w = rng.standard_normal((d, classes))
    x = rng.standard_normal((n, d)).astype(np.float32)
    y = np.argmax(x @ w + 0.1 * rng.standard_normal((n, classes)), -1).astype(
        np.int32
    )
    return x, y


def test_data_parallel_trainer_learns_linear():
    x, y = _linear_data()

    def apply_fn(params, xb):
        return xb @ params["w"] + params["b"]

    def init_fn(key):
        return {
            "w": 0.01 * jax.random.normal(key, (8, 3)),
            "b": jnp.zeros((3,)),
        }

    trainer = DataParallelTrainer(
        loss_fn=softmax_classifier_loss(apply_fn),
        optimizer=optax.adam(1e-2),
        predict_fn=apply_fn,
        mesh=get_default_mesh(),
    )
    assert trainer.n_data == 8
    params, opt_state = trainer.init(init_fn)
    logs = []
    params, _ = trainer.fit(
        params,
        opt_state,
        (x, y),
        epochs=10,
        batch_size=64,
        log=lambda **kw: logs.append(kw),
    )
    assert len(logs) == 10
    assert logs[-1]["loss"] < logs[0]["loss"]
    acc = classification_accuracy(trainer, params, x, y)
    assert acc > 0.9


def test_predict_batched_handles_padding():
    def apply_fn(params, xb):
        return xb * params["s"]

    trainer = DataParallelTrainer(
        loss_fn=lambda p, b, r: (jnp.zeros(()), {}),
        optimizer=optax.sgd(0.1),
        predict_fn=apply_fn,
    )
    x = np.arange(13, dtype=np.float32).reshape(13, 1)
    out = trainer.predict_batched({"s": jnp.float32(2.0)}, x, batch_size=8)
    np.testing.assert_allclose(out, x * 2)


def test_predict_batched_uses_pow2_buckets():
    # serving batch sizes vary per tick; the compiled-shape set must stay on
    # the fixed pow-2 ladder regardless of the sizes that arrive
    seen_shapes = []

    def apply_fn(params, xb):
        seen_shapes.append(xb.shape[0])
        return xb * params["s"]

    trainer = DataParallelTrainer(
        loss_fn=lambda p, b, r: (jnp.zeros(()), {}),
        optimizer=optax.sgd(0.1),
        predict_fn=apply_fn,
    )
    params = {"s": jnp.float32(3.0)}
    buckets = set(trainer.predict_buckets(trainer.round_batch(64)))
    for n in (1, 3, 5, 9, 17, 33, 64, 100):
        x = np.arange(n, dtype=np.float32).reshape(n, 1)
        out = trainer.predict_batched(params, x, batch_size=64)
        np.testing.assert_allclose(out, x * 3)
    # every traced shape is on the ladder (tracing happens once per shape)
    assert set(seen_shapes) <= buckets


def test_warm_predict_compiles_every_bucket():
    traced = []

    def apply_fn(params, xb):
        traced.append(xb.shape[0])
        return xb * params["s"]

    trainer = DataParallelTrainer(
        loss_fn=lambda p, b, r: (jnp.zeros(()), {}),
        optimizer=optax.sgd(0.1),
        predict_fn=apply_fn,
    )
    params = {"s": jnp.float32(1.0)}
    n = trainer.warm_predict(params, np.zeros((1,), np.float32), batch_size=64)
    assert n == len(trainer.predict_buckets(trainer.round_batch(64)))
    assert sorted(traced) == trainer.predict_buckets(trainer.round_batch(64))
    # serving after warm-up must not trace any new shape
    traced.clear()
    trainer.predict_batched(params, np.zeros((13, 1), np.float32), batch_size=64)
    assert traced == []


def test_round_batch():
    trainer = DataParallelTrainer(
        loss_fn=lambda p, b, r: (jnp.zeros(()), {}),
        optimizer=optax.sgd(0.1),
    )
    assert trainer.round_batch(1) == trainer.n_data
    assert trainer.round_batch(17) % trainer.n_data == 0


def test_fit_trains_on_tiny_and_odd_datasets():
    # regression: fit() must take >=1 step/epoch even when n < n_devices or
    # n is not a multiple of the data-axis size
    import optax as _optax

    def apply_fn(params, xb):
        return xb @ params["w"]

    for n in (5, 13):
        x = np.ones((n, 2), np.float32)
        y = np.zeros((n,), np.int32)
        steps = []

        def loss_fn(params, batch, rng):
            xb, yb = batch
            logits = apply_fn(params, xb)
            return _optax.softmax_cross_entropy_with_integer_labels(
                logits, yb
            ).mean(), {}

        trainer = DataParallelTrainer(loss_fn=loss_fn, optimizer=_optax.sgd(0.1))
        params, opt_state = trainer.init(
            lambda k: {"w": jnp.zeros((2, 3))}
        )
        logs = []
        params, _ = trainer.fit(
            params, opt_state, (x, y), epochs=2, batch_size=64,
            log=lambda **kw: logs.append(kw),
        )
        assert len(logs) == 2  # a loss was logged => steps ran
        assert not np.allclose(np.asarray(params["w"]), 0.0)  # params moved


def test_thread_device_grant_precedence_and_isolation(monkeypatch):
    import threading

    from rafiki_tpu.parallel.mesh import (
        get_default_mesh,
        get_device_grant,
        set_device_grant,
        visible_devices,
    )

    # thread grant takes precedence over the env var
    monkeypatch.setenv("RAFIKI_VISIBLE_DEVICES", "0,1")
    set_device_grant([4, 5, 6])
    try:
        assert len(visible_devices()) == 3
        assert get_default_mesh().devices.size == 3
        assert get_device_grant() == (4, 5, 6)

        # another thread sees no grant (falls back to env) and its default
        # mesh cache doesn't leak into ours
        result = {}

        def child():
            result["n"] = len(visible_devices())
            result["mesh_n"] = get_default_mesh().devices.size
            set_device_grant(get_device_grant() or [7])  # propagation idiom
            result["propagated"] = len(visible_devices())

        t = threading.Thread(target=child)
        t.start()
        t.join()
        assert result["n"] == 2  # env fallback
        assert result["mesh_n"] == 2
        assert result["propagated"] == 1  # [7]
        assert get_default_mesh().devices.size == 3  # ours unchanged
    finally:
        set_device_grant(None)


def test_fit_checkpoint_resume_matches_uninterrupted(tmp_path):
    # a fit interrupted after 2 of 4 epochs and resumed from its checkpoint
    # must land on EXACTLY the params of an uninterrupted 4-epoch run (the
    # rng schedule is a pure function of (seed, epoch))
    x, y = _linear_data(n=256)

    def apply_fn(params, xb):
        return xb @ params["w"] + params["b"]

    def init_fn(key):
        return {"w": 0.01 * jax.random.normal(key, (8, 3)),
                "b": jnp.zeros((3,))}

    def make():
        t = DataParallelTrainer(
            loss_fn=softmax_classifier_loss(apply_fn),
            optimizer=optax.adam(1e-2), predict_fn=apply_fn)
        return t, *t.init(init_fn, seed=3)

    ckpt = str(tmp_path / "trial.ckpt")
    # straight 4-epoch run, no checkpointing
    t0, p0, s0 = make()
    ref, _ = t0.fit(p0, s0, (x, y), epochs=4, batch_size=64, seed=7)
    # 2 epochs with checkpoint (simulated crash: fresh trainer + state after)
    t1, p1, s1 = make()
    t1.fit(p1, s1, (x, y), epochs=2, batch_size=64, seed=7,
           checkpoint_path=ckpt)
    assert os.path.exists(ckpt)
    t2, p2, s2 = make()  # "restart": fresh params, resumes from the file
    resumed, _ = t2.fit(p2, s2, (x, y), epochs=4, batch_size=64, seed=7,
                        checkpoint_path=ckpt)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(resumed)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)


def test_fit_checkpoint_interrupted_epoch_boundary(tmp_path):
    # resume respects checkpoint_every_epochs: only epochs 0..k-1 replay
    x, y = _linear_data(n=128)

    def apply_fn(params, xb):
        return xb @ params["w"]

    trainer = DataParallelTrainer(
        loss_fn=softmax_classifier_loss(apply_fn),
        optimizer=optax.sgd(1e-2))
    params, opt = trainer.init(lambda k: {"w": jnp.zeros((8, 3))})
    ckpt = str(tmp_path / "c.ckpt")
    trainer.fit(params, opt, (x, y), epochs=3, batch_size=64,
                checkpoint_path=ckpt, checkpoint_every_epochs=2)
    from flax import serialization

    from rafiki_tpu.sdk.artifact import read_artifact

    # checkpoints are framed on disk now (atomic + checksummed,
    # sdk/artifact.py); the payload inside is the same msgpack state dict
    blob = serialization.msgpack_restore(read_artifact(ckpt))
    assert blob["epoch"] == 3  # final epoch always checkpointed


def test_stateful_trainer_threads_batchnorm_like_state(tmp_path):
    # stateful=True: non-trained state (here a running mean, batchnorm-
    # style) is threaded through the step, used by predict, checkpointed,
    # and NEVER touched by the optimizer (weight decay would corrupt it)
    def loss_fn(params, state, batch, rng):
        x, y = batch
        mean = x.mean()
        new_state = {"running": 0.9 * state["running"] + 0.1 * mean}
        logits = (x - state["running"]) @ params["w"]
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        return loss, ({}, new_state)

    def predict_fn(params, state, x):
        return (x - state["running"]) @ params["w"]

    trainer = DataParallelTrainer(
        loss_fn, optax.adamw(1e-2, weight_decay=0.5),
        predict_fn=predict_fn, stateful=True)
    x, y = _linear_data(n=256)
    x = x + 5.0  # offset the running stat must learn
    params, opt_state, state = trainer.init(
        lambda k: ({"w": 0.01 * jax.random.normal(k, (8, 3))},
                   {"running": jnp.float32(0.0)}))
    ckpt = str(tmp_path / "s.ckpt")
    params, opt_state, state = trainer.fit(
        params, opt_state, (x, y), epochs=3, batch_size=64,
        checkpoint_path=ckpt, state=state)
    # the running stat converged toward the data mean — and was NOT decayed
    # to zero by adamw's weight decay
    assert 3.0 < float(state["running"]) < 7.0
    out = trainer.predict_batched(params, x[:8], state=state)
    assert out.shape == (8, 3)
    # resume path restores the state too
    p2, o2, s2 = trainer.init(
        lambda k: ({"w": 0.01 * jax.random.normal(k, (8, 3))},
                   {"running": jnp.float32(0.0)}))
    p2, o2, s2 = trainer.fit(p2, o2, (x, y), epochs=3, batch_size=64,
                             checkpoint_path=ckpt, state=s2)
    np.testing.assert_allclose(float(s2["running"]), float(state["running"]),
                               rtol=1e-6)


def test_restore_pre_state_key_checkpoint(tmp_path):
    # checkpoints written before the stateful-trainer change have no "state"
    # entry; a worker upgraded mid-trial must still resume them, not ERROR
    from flax import serialization

    from rafiki_tpu.sdk.params import _to_host

    x, y = _linear_data(n=128)

    def apply_fn(params, xb):
        return xb @ params["w"]

    trainer = DataParallelTrainer(
        loss_fn=softmax_classifier_loss(apply_fn),
        optimizer=optax.sgd(1e-2))
    params, opt = trainer.init(lambda k: {"w": jnp.zeros((8, 3))})
    ckpt = str(tmp_path / "legacy.ckpt")
    # write the pre-upgrade format: no "state" key
    with open(ckpt, "wb") as f:
        f.write(serialization.to_bytes({
            "params": _to_host(params),
            "opt_state": _to_host(opt),
            "epoch": 2,
        }))
    p, o, s, epoch = trainer._restore_checkpoint(ckpt, params, opt)
    assert epoch == 2
    assert jax.tree.structure(p) == jax.tree.structure(params)
    # and fit() resumes from it end-to-end (epochs 0-1 skipped)
    out, _ = trainer.fit(p, o, (x, y), epochs=3, batch_size=64,
                         checkpoint_path=ckpt)
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree.leaves(out))


def test_scan_epoch_matches_per_step_loop(tmp_path):
    # the device-resident epoch scan must land on the params the per-step
    # loop produces (same op order, same rng schedule)
    x, y = _linear_data(n=96)

    def apply_fn(params, xb):
        return xb @ params["w"] + params["b"]

    def init_fn(key):
        return {"w": 0.01 * jax.random.normal(key, (8, 3)),
                "b": jnp.zeros((3,))}

    def make():
        t = DataParallelTrainer(
            loss_fn=softmax_classifier_loss(apply_fn),
            optimizer=optax.adam(1e-2), predict_fn=apply_fn)
        return t, *t.init(init_fn, seed=5)

    t0, p0, s0 = make()
    ref, _ = t0.fit(p0, s0, (x, y), epochs=3, batch_size=32, seed=11,
                    scan_epoch=False)
    t1, p1, s1 = make()
    scanned, _ = t1.fit(p1, s1, (x, y), epochs=3, batch_size=32, seed=11,
                        scan_epoch=True)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(scanned)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)


def test_scan_epoch_matches_loop_stateful(tmp_path):
    # same equivalence for the stateful trainer: the model state (here a
    # running-mean, batchnorm-style) must thread through the scan carry
    # exactly as it does through the per-step loop
    x, y = _linear_data(n=96)

    def loss_fn(params, state, batch, rng):
        xb, yb = batch
        logits = xb @ params["w"]
        new_state = {"running": 0.9 * state["running"] + 0.1 * jnp.mean(xb)}
        import optax as _optax

        loss = _optax.softmax_cross_entropy_with_integer_labels(
            logits, yb).mean()
        return loss, ({}, new_state)

    def make():
        t = DataParallelTrainer(loss_fn=loss_fn,
                                optimizer=optax.adam(1e-2), stateful=True)
        p, o, s = t.init(
            lambda k: ({"w": 0.01 * jax.random.normal(k, (8, 3))},
                       {"running": jnp.zeros(())}), seed=5)
        return t, p, o, s

    t0, p0, o0, s0 = make()
    rp, ro, rs = t0.fit(p0, o0, (x, y), epochs=3, batch_size=32, seed=11,
                        scan_epoch=False, state=s0)
    t1, p1, o1, s1 = make()
    sp, so, ss = t1.fit(p1, o1, (x, y), epochs=3, batch_size=32, seed=11,
                        scan_epoch=True, state=s1)
    np.testing.assert_allclose(float(rs["running"]), float(ss["running"]),
                               rtol=1e-6)
    for a, b in zip(jax.tree.leaves(rp), jax.tree.leaves(sp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)


def test_scan_epoch_checkpoint_resume(tmp_path):
    # resume composes with the scan path: interrupted scan-epoch fit lands
    # on the uninterrupted result
    x, y = _linear_data(n=64)

    def apply_fn(params, xb):
        return xb @ params["w"]

    def make():
        t = DataParallelTrainer(
            loss_fn=softmax_classifier_loss(apply_fn),
            optimizer=optax.sgd(1e-2))
        return t, *t.init(lambda k: {"w": jnp.zeros((8, 3))})

    ckpt = str(tmp_path / "scan.ckpt")
    t0, p0, s0 = make()
    ref, _ = t0.fit(p0, s0, (x, y), epochs=4, batch_size=32, seed=2,
                    scan_epoch=True)
    t1, p1, s1 = make()
    t1.fit(p1, s1, (x, y), epochs=2, batch_size=32, seed=2,
           checkpoint_path=ckpt, scan_epoch=True)
    t2, p2, s2 = make()
    resumed, _ = t2.fit(p2, s2, (x, y), epochs=4, batch_size=32, seed=2,
                        checkpoint_path=ckpt, scan_epoch=True)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(resumed)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)


def test_fit_reuses_device_dataset_across_calls(monkeypatch):
    # HPO trials call fit() with the same host arrays; the device upload
    # must happen once, not once per trial (pure per-trial overhead)
    x, y = _linear_data(n=64)

    def apply_fn(params, xb):
        return xb @ params["w"]

    trainer = DataParallelTrainer(
        loss_fn=softmax_classifier_loss(apply_fn),
        optimizer=optax.sgd(1e-2))
    import rafiki_tpu.sdk.jax_backend as jb
    puts = []
    real_put = jax.device_put
    monkeypatch.setattr(jb.jax, "device_put",
                        lambda v, s=None: (puts.append(np.shape(v)),
                                           real_put(v, s))[1])
    for trial in range(3):
        p, o = trainer.init(lambda k: {"w": jnp.zeros((8, 3))})
        trainer.fit(p, o, (x, y), epochs=1, batch_size=32,
                    scan_epoch=True)
    dataset_puts = [s for s in puts if s == np.shape(x)]
    assert len(dataset_puts) == 1  # uploaded once, reused twice


def test_dataset_array_cache_returns_identical_objects(tmp_path):
    from rafiki_tpu.sdk.dataset import DatasetUtils, write_numpy_dataset

    du = DatasetUtils()
    x = np.zeros((16, 4, 4, 1), np.float32)
    y = np.zeros((16,), np.int32)
    uri = write_numpy_dataset(x, y, str(tmp_path / "d.npz"))
    a1 = du.load_image_arrays(uri)
    a2 = du.load_image_arrays(uri)
    assert a1[0] is a2[0] and a1[1] is a2[1]
    # rewriting the file invalidates the entry
    write_numpy_dataset(x + 1, y, str(tmp_path / "d.npz"))
    a3 = du.load_image_arrays(uri)
    assert a3[0] is not a1[0]
