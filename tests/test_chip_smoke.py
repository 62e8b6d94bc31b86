"""chip_smoke.py: its contract without a chip, and a CPU rehearsal of its
phases at tiny size (Pallas in interpret mode, virtual CPU devices).

The script itself runs on a TPU only; the rehearsal calls its ``run()``
with tiny sizes, which is the one place the contract allows a CPU run —
nothing here prints the script's ``"ok": true`` line.
"""

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke

TINY = dataclasses.replace(
    chip_smoke.FULL,
    vit_cfg=("vit.tiny(num_classes=10, image_size=32, patch_size=4, dim=32, "
             "depth=2, heads=2)"),
    image=32, n_train=64, n_test=16, batch=16, gen_tokens=8,
    kernel_shape=(1, 2, 256, 64), kernel_long_shape=(1, 2, 512, 64),
    interpret=True,
    mesh_vit_cfg=("vit.tiny(num_classes=10, image_size=32, patch_size=4, "
                  "dim=32, depth=1, heads=2)"),
    mesh_n_train=128, mesh_batch=32)


class FakeTpu:
    platform, device_kind = "tpu", "TPU v5 lite"


def _records(capsys):
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]


@pytest.fixture()
def smoke_env(tmp_path, monkeypatch):
    """run() points RAFIKI_WORKDIR at a fresh directory of its own and turns
    the per-job doors on: register both with monkeypatch so the next test
    file in this worker gets its environment back."""
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    monkeypatch.setenv("RAFIKI_WORKDIR", str(tmp_path))
    monkeypatch.setenv("RAFIKI_PREDICTOR_PORTS", "1")
    return tmp_path


def test_exits_nonzero_and_prints_no_result_without_a_tpu(tmp_path):
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=str(tmp_path),
        text=True, capture_output=True, timeout=240)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no accelerator" in out.stderr
    assert not os.listdir(tmp_path), "nothing written before the refusal"


def test_alone_in_a_directory_it_fails(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the repo."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], env=env, cwd=str(tmp_path),
        text=True, capture_output=True, timeout=240)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_a_phase_that_raises_prints_no_ok(monkeypatch, capsys):
    """No phase's exception is caught and reported as a field."""
    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTpu()])

    def boom(*a, **k):
        raise RuntimeError("phase failed")

    monkeypatch.setattr(chip_smoke, "run", boom)
    with pytest.raises(RuntimeError, match="phase failed"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_four_chips_needs_four_devices(monkeypatch, capsys):
    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTpu()])
    monkeypatch.setattr(chip_smoke, "run",
                        lambda *a, **k: pytest.fail("must not run"))
    assert chip_smoke.main(["--chips", "4"]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_last_line_is_exactly_the_contracts(monkeypatch, capsys):
    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTpu()])
    monkeypatch.setattr(chip_smoke, "run", lambda *a, **k: None)
    assert chip_smoke.main(["--seed", "3"]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_the_vit_template_passes_the_verifier_at_enforce():
    """Knobs fixed as literals: the upload needs no lowered verifier."""
    from rafiki_tpu.analysis.template import verify_template_source

    src = chip_smoke._VIT_TEMPLATE.format(
        cfg=chip_smoke.FULL.vit_cfg, batch=32, epochs=1, seed=0)
    report = verify_template_source(
        src, "SmokeViT", {"jax": None, "optax": None})
    assert report.ok, [str(f) for f in report.errors]


def test_full_size_is_vit_b16_at_full_width():
    from rafiki_tpu.models import vit

    cfg = eval(chip_smoke.FULL.vit_cfg, {"vit": vit})
    assert (cfg.encoder.dim, cfg.encoder.depth, cfg.encoder.heads,
            cfg.image_size, cfg.patch_size) == (768, 12, 12, 224, 16)
    assert chip_smoke.FULL.interpret is False
    assert tuple(chip_smoke.FULL.kernel_shape) == (4, 12, 2048, 64)
    assert tuple(chip_smoke.FULL.kernel_long_shape) == (4, 12, 8192, 64)


def test_rehearsal_default_phases(smoke_env, capsys):
    """Template upload -> two trials -> deploy -> both doors; /generate
    with plain, sampled and speculative decode; the kernel comparison —
    every phase of the default run, tiny, on the CPU."""
    chip_smoke.run(0, 1, TINY, jax.devices()[:1])
    recs = {r["phase"]: r for r in _records(capsys)}
    assert list(recs) == ["search_and_serve", "generate_tiny_lm", "kernel"]
    search = recs["search_and_serve"]
    assert len(search["trials"]) == 2
    assert search["train_step_programs"] == 1
    assert set(search["predict"]) == {"admin", "binary"}
    assert search["data_plane"]
    gen = recs["generate_tiny_lm"]
    assert gen["greedy_rerun_identical"] and gen["spec_rounds"] >= 1
    kern = recs["kernel"]
    assert kern["compiled"] is False  # interpret mode: a rehearsal
    assert kern["fwd_rel_l2"] < 1e-4 and kern["grad_rel_l2"] < 1e-4
    for r in recs.values():
        assert {"wall_s", "compile_s", "compile_cache_dir",
                "compile_cache_hits"} <= set(r)
    # a fresh work directory of its own, under the fixture's tmp
    assert os.environ["RAFIKI_WORKDIR"].startswith(str(smoke_env))


def test_rehearsal_four_chip_paths(smoke_env, capsys):
    """--chips 4 on four virtual devices: parallel one-chip trials, a mesh
    trial and a sharded predict against one chip, the collectives dry run."""
    chip_smoke.run(0, 4, TINY, jax.devices()[:4])
    recs = {r["phase"]: r for r in _records(capsys)}
    assert list(recs) == ["parallel_trials", "mesh_trial", "sharded_predict",
                          "dryrun_multichip"]
    assert recs["parallel_trials"]["trials"] == 4
    assert recs["mesh_trial"]["relative_diff"] <= 1e-3
    assert recs["sharded_predict"]["chips_per_worker"] == 4
