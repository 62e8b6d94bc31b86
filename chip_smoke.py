#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that rafiki_tpu still starts on the chip.

Drives the product's main path once on ONE TPU chip, in ONE process (thread
placement: the only arrangement in which one process owns the chip), through
the entry points a user calls:

- search_and_serve: Admin + AdminServer in-process, driven by Client over
  HTTP — upload a ViT-B/16 template (full width: 768 x 12 layers x 12 heads,
  224 px, patch 16), two trials through worker/train.py, deploy, predict
  through the admin door and the per-job binary door, stop.
- generate_tiny_lm: tests/fixtures/gen_model.py under TEXT_GENERATION —
  paged prefill / decode / sampled decode / speculative verify programs
  compile and answer on the chip (the LM has only its tiny configuration:
  this proves programs, not a width).
- kernel: the Pallas flash-attention kernels compiled (never interpreted)
  against the plain XLA reference, plus what a dispatch costs here.

Every phase prints one JSON line; the LAST line of stdout is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any phase that fails raises: the exit code is non-zero and no "ok" line is
printed. Without a TPU it fails at once.

``--chips 4`` (run by hand on a four-chip host; the driver never passes it)
runs ONLY the cross-chip paths and what they are compared with: four
concurrent one-chip trials, a CHIPS_PER_TRIAL=4 trial vs one chip, a
CHIPS_PER_WORKER=4 predict vs one chip, and the ring/GPipe/expert-parallel
dry run.

All data is made from ``--seed``. Everything is written under a fresh work
directory (RAFIKI_WORKDIR), never the checkout's rafiki.sqlite3 or logs/.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import os
import sys
import tempfile
import threading
import time
from dataclasses import dataclass

_HERE = os.path.dirname(os.path.abspath(__file__))
if _HERE not in sys.path:
    sys.path.insert(0, _HERE)

# A few lines of template, knobs fixed as literals so the template verifier
# passes it at its default `enforce`. {cfg} is the only thing the CPU
# rehearsal (tests/test_chip_smoke.py) swaps.
_VIT_TEMPLATE = '''
import jax
import numpy as np
import optax

from rafiki_tpu.models import vit
from rafiki_tpu.sdk import (BaseModel, DataParallelTrainer, FixedKnob,
                            FloatKnob, cached_trainer,
                            classification_accuracy, dataset_utils,
                            softmax_classifier_loss, tunable_optimizer)

CFG = {cfg}


def _apply(params, x):
    return vit.apply(params, x, CFG)


class SmokeViT(BaseModel):
    dependencies = {{"jax": None, "optax": None}}

    @staticmethod
    def get_knob_config():
        return {{
            "learning_rate": FloatKnob(1e-4, 1e-3, is_exp=True),
            "batch_size": FixedKnob({batch}),
            "epochs": FixedKnob({epochs}),
        }}

    def __init__(self, **knobs):
        super().__init__(**knobs)
        self._knobs = knobs
        self._params = None

    def _trainer(self):
        return cached_trainer(("SmokeViT", CFG), lambda: DataParallelTrainer(
            softmax_classifier_loss(_apply),
            tunable_optimizer(optax.adamw, learning_rate=1e-3),
            predict_fn=lambda p, x: jax.nn.softmax(_apply(p, x), axis=-1)))

    def train(self, dataset_uri):
        x, y = dataset_utils.load_image_arrays(dataset_uri)
        trainer = self._trainer()
        params, opt_state = trainer.init(
            jax.jit(lambda rng: vit.init(rng, CFG)), seed={seed},
            hyperparams={{"learning_rate": self._knobs["learning_rate"]}})
        self._params, _ = trainer.fit(
            params, opt_state, (x, y), epochs=self._knobs["epochs"],
            batch_size=self._knobs["batch_size"], seed={seed},
            log=self.logger.log, checkpoint_path=self.checkpoint_path)

    def evaluate(self, dataset_uri):
        x, y = dataset_utils.load_image_arrays(dataset_uri)
        return classification_accuracy(self._trainer(), self._params, x, y)

    def predict(self, queries):
        probs = self._trainer().predict_batched(
            self._params, np.asarray(queries, np.float32))
        return [p.tolist() for p in probs]

    def dump_parameters(self):
        return {{"params": jax.tree.map(np.asarray, self._params)}}

    def load_parameters(self, blob):
        self._params = self._trainer().device_put_params(blob["params"])
'''


@dataclass(frozen=True)
class Sizes:
    """What a run is sized by. FULL is what the chip runs; the CPU
    rehearsal in tests/ passes a tiny one and interpret=True."""

    vit_cfg: str = "vit.vit_b16(num_classes=10, image_size=224)"
    image: int = 224
    n_train: int = 256
    n_test: int = 64
    batch: int = 32          # 8.1 GB by memory_analysis(); 64 needs 14.4
    epochs: int = 1          # 256 / 32 = 8 steps a trial
    gen_tokens: int = 32
    kernel_shape: tuple = (4, 12, 2048, 64)       # B, H, S, Dh
    kernel_long_shape: tuple = (4, 12, 8192, 64)  # ops/attention.py quotes it
    interpret: bool = False  # Pallas interpreter: CPU rehearsal only
    # --chips 4: a small template (the cross-chip paths are what is proven)
    mesh_vit_cfg: str = ("vit.tiny(num_classes=10, image_size=32, "
                         "patch_size=4, dim=128, depth=2, heads=4)")
    mesh_image: int = 32
    mesh_n_train: int = 512
    mesh_batch: int = 64


FULL = Sizes()
WATCHDOG_S = 1100


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


class CompileMeter:
    """Seconds the process spent in XLA backend compiles (cache retrievals
    included) and persistent-cache hits, from JAX's monitoring events —
    phases read deltas."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.programs = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _on_event(self, event: str, **kw) -> None:
        if event.endswith("/compilation_cache/cache_hits"):
            self.hits += 1

    def snapshot(self):
        return self.seconds, self.hits, self.programs

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_dur)
        jax.monitoring.unregister_event_listener(self._on_event)


def _phase_record(name: str, meter: CompileMeter, t0: float, snap, **extra):
    from rafiki_tpu.sdk import compile_cache

    s0, h0, n0 = snap
    s1, h1, n1 = meter.snapshot()
    emit({"phase": name, "wall_s": round(time.monotonic() - t0, 3),
          "compile_s": round(s1 - s0, 3), "programs_compiled": n1 - n0,
          "compile_cache_dir": compile_cache.stats()["dir"],
          "compile_cache_hits": h1 - h0, **extra})


def _data_plane(admin) -> dict:
    """Which serving data plane this run used, and whether the native one
    could have been built here (native/build.py degrades without g++)."""
    from rafiki_tpu.native import shm_queue

    broker = getattr(admin.broker, "_base", admin.broker)
    return {"data_plane": type(broker).__name__,
            "native_shmqueue_available": bool(shm_queue.available())}


def _make_images(seed: int, n: int, image: int, classes: int = 10):
    """Seeded images with a learnable per-class offset."""
    import numpy as np

    rng = np.random.default_rng(seed)
    y = rng.integers(0, classes, size=n).astype(np.int32)
    x = rng.normal(size=(n, image, image, 3)).astype(np.float32)
    x += (y[:, None, None, None].astype(np.float32) - classes / 2) * 0.25
    return x, y


def _write_dataset(workdir: str, name: str, x, y) -> str:
    import numpy as np

    path = os.path.join(workdir, "data", name)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    np.savez(path, x=x, y=y)  # uncompressed: random floats do not deflate
    return path


def _boot(workdir: str, n_chips: int):
    """Admin + AdminServer in this process (thread placement over the
    devices this process owns), and a logged-in Client over real HTTP."""
    from rafiki_tpu import config
    from rafiki_tpu.admin.admin import Admin
    from rafiki_tpu.admin.http import AdminServer
    from rafiki_tpu.client.client import Client
    from rafiki_tpu.db.database import Database
    from rafiki_tpu.placement.manager import (ChipAllocator,
                                              LocalPlacementManager)

    admin = Admin(
        db=Database(":memory:"),
        placement=LocalPlacementManager(
            allocator=ChipAllocator(list(range(n_chips)))),
        params_dir=os.path.join(workdir, "params"),
    )
    server = AdminServer(admin, port=0).start()

    def login() -> Client:
        # a Client caches each app's per-job door for a TTL: take a fresh
        # one after a redeploy rather than wait the stale route out
        client = Client("127.0.0.1", server.port)
        client.login(config.SUPERADMIN_EMAIL, config.SUPERADMIN_PASSWORD)
        return client

    return admin, server, login


def _upload_vit(client, workdir: str, name: str, cfg: str, batch: int,
                epochs: int, seed: int) -> None:
    path = os.path.join(workdir, f"{name}.py")
    with open(path, "w", encoding="utf-8") as f:
        f.write(_VIT_TEMPLATE.format(cfg=cfg, batch=batch, epochs=epochs,
                                     seed=seed))
    # default RAFIKI_VERIFY_TEMPLATES=enforce: a rejected template raises
    client.create_model(name, "IMAGE_CLASSIFICATION", path, "SmokeViT")


def _wait_stopped(client, app: str, timeout_s: float) -> dict:
    deadline = time.monotonic() + timeout_s
    while True:
        job = client.get_train_job(app)
        if job["status"] in ("STOPPED", "ERRORED"):
            return job
        if time.monotonic() > deadline:
            raise TimeoutError(f"train job {app} still {job['status']} "
                               f"after {timeout_s:.0f}s")
        time.sleep(0.25)


def _completed_trials(client, app: str, want: int,
                      logs_loss: bool = True) -> list:
    """The app's trials: exactly `want`, each COMPLETED with a finite score
    (and, for templates that log one, a finite loss)."""
    import math

    trials = client.get_trials_of_train_job(app)
    if len(trials) != want or any(
            t["status"] != "COMPLETED" for t in trials):
        raise RuntimeError(
            f"{app}: wanted {want} COMPLETED trials, have "
            f"{[(t['id'][:8], t['status']) for t in trials]}")
    out = []
    for t in trials:
        if t["score"] is None or not math.isfinite(t["score"]):
            raise RuntimeError(f"trial {t['id']}: score {t['score']}")
        row = {"id": t["id"], "score": t["score"]}
        if logs_loss:
            losses = [m["loss"]
                      for m in client.get_trial_logs(t["id"])["metrics"]
                      if "loss" in m]
            if not losses or not all(math.isfinite(v) for v in losses):
                raise RuntimeError(f"trial {t['id']}: losses {losses}")
            row["final_loss"] = losses[-1]
        out.append(row)
    return out


# ---------------------------------------------------------------------------
# default run, phase 1: search and serve at full width
# ---------------------------------------------------------------------------

def phase_search_and_serve(client, admin, workdir: str, seed: int,
                           sizes: Sizes, meter: CompileMeter) -> None:
    import numpy as np

    from rafiki_tpu.sdk import jax_backend

    t0, snap = time.monotonic(), meter.snapshot()
    x, y = _make_images(seed, sizes.n_train + sizes.n_test, sizes.image)
    train_uri = _write_dataset(workdir, "smoke_train.npz",
                               x[:sizes.n_train], y[:sizes.n_train])
    test_uri = _write_dataset(workdir, "smoke_test.npz",
                              x[sizes.n_train:], y[sizes.n_train:])
    _upload_vit(client, workdir, "smoke_vit", sizes.vit_cfg, sizes.batch,
                sizes.epochs, seed)

    def smoke_trainers():
        return [t for (key, _), t in jax_backend._trainer_cache.items()
                if key[0] == "SmokeViT"]

    known = {id(t) for t in smoke_trainers()}
    t_train = time.monotonic()
    client.create_train_job(
        "smokeapp", "IMAGE_CLASSIFICATION", train_uri, test_uri,
        budget={"MODEL_TRIAL_COUNT": 2, "CHIP_COUNT": 1},
        models=["smoke_vit"])
    job = _wait_stopped(client, "smokeapp", timeout_s=900)
    if job["status"] != "STOPPED":
        raise RuntimeError(f"train job ended {job['status']}: {job}")
    train_wall = time.monotonic() - t_train
    trials = _completed_trials(client, "smokeapp", want=2)

    # the second trial compiled no train step anew: one cached trainer,
    # one executable behind its epoch program (sdk/jax_backend.py
    # cached_trainer; the two trials differ only in the dynamic lr)
    trainers = [t for t in smoke_trainers() if id(t) not in known]
    if len(trainers) != 1:
        raise RuntimeError(f"expected ONE cached SmokeViT trainer, have "
                           f"{len(trainers)}")
    step_programs = (trainers[0]._epoch_scan._cache_size()
                     + trainers[0]._train_step._cache_size())
    if step_programs != 1:
        raise RuntimeError(f"two trials compiled {step_programs} train-step "
                           "programs; the second must reuse the first's")

    t_deploy = time.monotonic()
    inf = client.create_inference_job("smokeapp")
    if inf["status"] != "RUNNING" or not inf.get("predictor_port"):
        raise RuntimeError(f"inference job not serving: {inf}")
    deploy_wall = time.monotonic() - t_deploy
    queries = x[sizes.n_train:sizes.n_train + 3]
    doors = {}
    for door, ask in (
            ("admin", lambda: client.predict("smokeapp", queries.tolist())),
            ("binary", lambda: client.predict_direct("smokeapp", queries))):
        t_first = time.monotonic()
        first = np.asarray(ask(), np.float32)
        first_s = time.monotonic() - t_first
        lat = []
        for _ in range(4):
            t1 = time.monotonic()
            again = np.asarray(ask(), np.float32)
            lat.append(time.monotonic() - t1)
            if not np.array_equal(first, again):
                raise RuntimeError(f"{door} door: same queries, different "
                                   "answers")
        if first.shape != (len(queries), 10) or not np.all(
                np.isfinite(first)):
            raise RuntimeError(f"{door} door: bad predictions "
                               f"{first.shape}")
        if not np.allclose(first.sum(axis=-1), 1.0, atol=1e-2):
            raise RuntimeError(f"{door} door: rows are not probabilities")
        doors[door] = {"first_request_s": round(first_s, 3),
                       "warm_request_s_median": float(np.median(lat)),
                       "answer": first}
    if not np.allclose(doors["admin"]["answer"], doors["binary"]["answer"],
                       atol=1e-5):
        raise RuntimeError("the two doors disagree on the same queries")
    client.stop_inference_job("smokeapp")
    _phase_record(
        "search_and_serve", meter, t0, snap,
        model=sizes.vit_cfg, train_images=sizes.n_train, batch=sizes.batch,
        steps_per_trial=sizes.epochs * (sizes.n_train // sizes.batch),
        trials=[{k: t[k] for k in ("final_loss", "score")} for t in trials],
        train_step_programs=step_programs,
        train_job_wall_s=round(train_wall, 3),
        deploy_wall_s=round(deploy_wall, 3),
        workers=len(inf["workers"]),
        predict={d: {k: v for k, v in r.items() if k != "answer"}
                 for d, r in doors.items()},
        **_data_plane(admin))


# ---------------------------------------------------------------------------
# default run, phase 2: /generate on the tiny LM
# ---------------------------------------------------------------------------

def _stream(client, app: str, prompt, max_tokens: int, **sampling) -> list:
    toks = []
    for delta in client.generate(app, prompt, max_tokens=max_tokens,
                                 timeout_s=300.0, **sampling):
        toks.extend(delta.get("tokens") or [])
    return toks


def _concurrent_streams(client, app: str, prompts, max_tokens: int) -> list:
    out = [None] * len(prompts)
    errs = []

    def run(i):
        try:
            out[i] = _stream(client, app, prompts[i], max_tokens)
        except Exception as e:  # re-raised below, never swallowed
            errs.append(e)

    threads = [threading.Thread(target=run, args=(i,), daemon=True)
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    if errs:
        raise errs[0]
    if any(o is None for o in out):
        raise TimeoutError("a token stream did not finish")
    return out


def phase_generate(login, admin, seed: int, sizes: Sizes,
                   meter: CompileMeter) -> None:
    import numpy as np

    t0, snap = time.monotonic(), meter.snapshot()
    client = login()
    n = sizes.gen_tokens
    fixture = os.path.join(_HERE, "tests", "fixtures", "gen_model.py")
    client.create_model("smoke_lm", "TEXT_GENERATION", fixture, "TinyGenLM")
    client.create_train_job(
        "smokegen", "TEXT_GENERATION", "uri://none", "uri://none",
        budget={"MODEL_TRIAL_COUNT": 1, "CHIP_COUNT": 1},
        models=["smoke_lm"])
    job = _wait_stopped(client, "smokegen", timeout_s=600)
    if job["status"] != "STOPPED":
        raise RuntimeError(f"gen train job ended {job['status']}")
    trial = _completed_trials(client, "smokegen", want=1,
                              logs_loss=False)[0]["id"]
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, 60, size=3).tolist(),
               rng.integers(1, 60, size=5).tolist()]

    # plain paged decode: prefill, greedy decode, sampled decode
    inf = client.create_inference_job("smokegen")
    if inf["status"] != "RUNNING":
        raise RuntimeError(f"gen inference job not serving: {inf}")
    t_first = time.monotonic()
    greedy = _concurrent_streams(client, "smokegen", prompts, n)
    first_s = time.monotonic() - t_first
    t_again = time.monotonic()
    again = _concurrent_streams(client, "smokegen", prompts, n)
    again_s = time.monotonic() - t_again
    for a, b in zip(greedy, again):
        if len(a) != n or a != b:
            raise RuntimeError(f"greedy re-run differs or is short: "
                               f"{a} vs {b}")
    sampled = [_stream(client, "smokegen", prompts[0], n, temperature=0.8,
                       top_k=20, seed=seed) for _ in range(2)]
    if len(sampled[0]) != n or sampled[0] != sampled[1]:
        raise RuntimeError("seeded sampled stream is not reproducible")
    client.stop_inference_job("smokegen")

    # speculative decoding with the trial as its own draft: the k+1-wide
    # verify program compiles and greedy stays token-identical
    client = login()
    rounds_before = _counter_total("rafiki_gen_spec_rounds_total")
    client.create_inference_job(
        "smokegen", budget={"GEN_DRAFT_TRIAL": trial})
    spec = _concurrent_streams(client, "smokegen", prompts, n)
    if spec != greedy:
        raise RuntimeError(f"speculative greedy differs from plain greedy: "
                           f"{spec} vs {greedy}")
    spec_rounds = _counter_total(
        "rafiki_gen_spec_rounds_total") - rounds_before
    if spec_rounds < 1:
        raise RuntimeError("no speculative round ran: the verify program "
                           "was never exercised")
    client.stop_inference_job("smokegen")
    _phase_record(
        "generate_tiny_lm", meter, t0, snap,
        note="programs only (models/lm.py has only its tiny config): "
             "paged prefill, decode, sampled decode, speculative verify",
        streams=2, tokens_per_stream=n,
        first_pair_wall_s=round(first_s, 3),
        warm_pair_wall_s=round(again_s, 3),
        greedy_rerun_identical=True, sampled_seed_reproducible=True,
        spec_rounds=spec_rounds, spec_greedy_identical=True,
        **_data_plane(admin))


def _counter_total(name: str) -> float:
    from rafiki_tpu.utils.metrics import REGISTRY

    metric = REGISTRY.get(name)
    if metric is None:
        return 0.0
    return float(sum(c.value() for c in metric.children().values()))


# ---------------------------------------------------------------------------
# default run, phase 3: the kernel, compiled
# ---------------------------------------------------------------------------

# The kernels multiply f32 tiles with preferred_element_type=f32 at the
# DEFAULT matmul precision, which on the MXU rounds operands to bf16
# (2^-8 ~ 3.9e-3 relative per operand). The reference runs at HIGHEST
# (full f32). 2e-2 of the reference's L2 norm fits that; the interpreter's
# 2e-5 (tests/test_ops.py) does not apply to the chip.
KERNEL_PRECISION = "default (bf16 MXU passes, f32 accumulate)"
KERNEL_REL_L2_TOL = 2e-2


def _rel_l2(a, b) -> float:
    import jax.numpy as jnp

    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.maximum(
        jnp.linalg.norm(b), 1e-30))


def phase_kernel(seed: int, sizes: Sizes, meter: CompileMeter) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from rafiki_tpu.ops import flash_attention, mha_reference

    t0, snap = time.monotonic(), meter.snapshot()
    interpret = sizes.interpret

    def qkvw(shape, key):
        ks = jax.random.split(jax.random.key(key), 4)
        return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)

    def flash_loss(q, k, v, w, causal):
        return jnp.sum(flash_attention(q, k, v, causal, None, 128, 128,
                                       interpret) * w)

    def ref_loss(q, k, v, w, causal):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(mha_reference(q, k, v, causal=causal) * w)

    flash_fb = jax.jit(jax.value_and_grad(flash_loss, argnums=(0, 1, 2)),
                       static_argnums=4)
    ref_fb = jax.jit(jax.value_and_grad(ref_loss, argnums=(0, 1, 2)),
                     static_argnums=4)
    flash_fwd = jax.jit(
        lambda q, k, v, causal: flash_attention(
            q, k, v, causal, None, 128, 128, interpret), static_argnums=3)

    def ref_fwd(q, k, v, causal):
        with jax.default_matmul_precision("highest"):
            return mha_reference(q, k, v, causal=causal)

    ref_fwd = jax.jit(ref_fwd, static_argnums=3)

    worst = {"fwd_rel_l2": 0.0, "fwd_max_abs": 0.0, "grad_rel_l2": 0.0,
             "grad_max_abs": 0.0}

    def compare(out, ref, kind):
        rel = _rel_l2(out, ref)
        worst[f"{kind}_rel_l2"] = max(worst[f"{kind}_rel_l2"], rel)
        worst[f"{kind}_max_abs"] = max(
            worst[f"{kind}_max_abs"], float(jnp.max(jnp.abs(out - ref))))
        if not bool(jnp.all(jnp.isfinite(out))):
            raise RuntimeError(f"flash {kind}: non-finite values")
        if not rel <= KERNEL_REL_L2_TOL:
            raise RuntimeError(
                f"flash {kind} disagrees with mha_reference: relative L2 "
                f"{rel:.3e} > {KERNEL_REL_L2_TOL}")

    # full comparison at the mid shape
    times = {}
    for causal in (False, True):
        q, k, v, w = qkvw(sizes.kernel_shape, seed + int(causal))
        compare(flash_fwd(q, k, v, causal), ref_fwd(q, k, v, causal), "fwd")
        (_, grads), (_, ref_grads) = (flash_fb(q, k, v, w, causal),
                                      ref_fb(q, k, v, w, causal))
        for g, rg in zip(grads, ref_grads):
            compare(g, rg, "grad")
        t1 = time.monotonic()
        jax.block_until_ready(flash_fb(q, k, v, w, causal))
        times[f"fwdbwd_s_causal{int(causal)}"] = time.monotonic() - t1

    # the long shape once each way: the whole thing runs; one (batch, head)
    # is checked against the reference (heads are independent, and the full
    # (S, S) scores of every head do not fit the device — the kernel's point)
    for causal in (False, True):
        q, k, v, w = qkvw(sizes.kernel_long_shape, seed + 7 + int(causal))
        t1 = time.monotonic()
        _, grads = jax.block_until_ready(flash_fb(q, k, v, w, causal))
        times[f"long_fwdbwd_first_call_s_causal{int(causal)}"] = (
            time.monotonic() - t1)
        for g in grads:
            if g.shape != tuple(sizes.kernel_long_shape) or not bool(
                    jnp.all(jnp.isfinite(g))):
                raise RuntimeError("flash long-shape gradient is not finite")
        one = tuple(a[:1, :1] for a in (q, k, v, w))
        compare(flash_fwd(q, k, v, causal)[:1, :1],
                ref_fwd(*one[:3], causal), "fwd")
        _, ref_grads = ref_fb(*one, causal)
        for g, rg in zip(grads, ref_grads):
            compare(g[:1, :1], rg, "grad")

    # what a dispatch and a block_until_ready cost here (ROADMAP S4 hangs on
    # it), and whether block_until_ready fences: a chained matmul whose
    # wall time, if fenced, cannot imply more than the chip's peak
    tiny = jax.jit(lambda a: a + 1.0)
    a = jnp.zeros((8, 128), jnp.float32)
    jax.block_until_ready(tiny(a))
    enq, blk = [], []
    for _ in range(200):
        t1 = time.perf_counter()
        r = tiny(a)
        t2 = time.perf_counter()
        r.block_until_ready()
        t3 = time.perf_counter()
        enq.append(t2 - t1)
        blk.append(t3 - t1)
    mm_n = 512 if interpret else 8192
    m = jnp.ones((mm_n, mm_n), jnp.bfloat16)

    @jax.jit
    def chain(x):
        for _ in range(8):
            x = (x @ m) * (1.0 / mm_n)
        return x

    jax.block_until_ready(chain(m))
    t1 = time.perf_counter()
    out = chain(m)
    out.block_until_ready()
    fenced = time.perf_counter() - t1
    t1 = time.perf_counter()
    float(chain(m)[0, 0])
    fetched = time.perf_counter() - t1
    _phase_record(
        "kernel", meter, t0, snap,
        kernel="flash_attention fwd+bwd",
        compiled=not interpret, shape=list(sizes.kernel_shape),
        long_shape=list(sizes.kernel_long_shape),
        precision=KERNEL_PRECISION, reference_precision="highest",
        rel_l2_tolerance=KERNEL_REL_L2_TOL,
        **{k: float(f"{v:.4g}") for k, v in worst.items()},
        **{k: round(v, 4) for k, v in times.items()},
        dispatch_enqueue_us_median=float(np.median(enq) * 1e6),
        dispatch_and_block_us_median=float(np.median(blk) * 1e6),
        matmul_chain_block_until_ready_s=fenced,
        matmul_chain_fetch_scalar_s=fetched,
        matmul_chain_tflops_if_fenced=(
            8 * 2 * mm_n ** 3 / fenced / 1e12))


# ---------------------------------------------------------------------------
# --chips 4: only what exists across chips, and what it is compared with
# ---------------------------------------------------------------------------

def _train_one(client, app: str, train_uri: str, test_uri: str, budget: dict,
               model: str) -> dict:
    client.create_train_job(app, "IMAGE_CLASSIFICATION", train_uri, test_uri,
                            budget=budget, models=[model])
    job = _wait_stopped(client, app, timeout_s=900)
    if job["status"] != "STOPPED":
        raise RuntimeError(f"train job {app} ended {job['status']}")
    return _completed_trials(client, app, want=budget["MODEL_TRIAL_COUNT"])


def _peak_bytes(devices) -> list:
    out = []
    for d in devices:
        stats = d.memory_stats()
        out.append(None if stats is None else int(
            stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0))))
    return out


def phase_four_chips(login, admin, workdir: str, seed: int, sizes: Sizes,
                     meter: CompileMeter, devices) -> None:
    import numpy as np

    import __graft_entry__ as graft

    client = login()
    n = len(devices)
    x, y = _make_images(seed, sizes.mesh_n_train + 64, sizes.mesh_image)
    train_uri = _write_dataset(workdir, "mesh_train.npz",
                               x[:sizes.mesh_n_train], y[:sizes.mesh_n_train])
    test_uri = _write_dataset(workdir, "mesh_test.npz",
                              x[sizes.mesh_n_train:], y[sizes.mesh_n_train:])
    # the learning rate is a FixedKnob here: the mesh trial and the
    # one-chip trial must take the same knobs
    template = _VIT_TEMPLATE.replace(
        "FloatKnob(1e-4, 1e-3, is_exp=True)", "FixedKnob(3e-4)")
    path = os.path.join(workdir, "mesh_vit.py")
    with open(path, "w", encoding="utf-8") as f:
        f.write(template.format(cfg=sizes.mesh_vit_cfg,
                                batch=sizes.mesh_batch, epochs=2, seed=seed))
    client.create_model("mesh_vit", "IMAGE_CLASSIFICATION", path, "SmokeViT")

    # (a) n concurrent one-chip trials: every chip must hold memory
    t0, snap = time.monotonic(), meter.snapshot()
    before = _peak_bytes(devices)
    trials = _train_one(client, "par", train_uri, test_uri,
                        {"MODEL_TRIAL_COUNT": n, "CHIP_COUNT": n}, "mesh_vit")
    after = _peak_bytes(devices)
    rose = [None if a is None else a > b for a, b in zip(after, before)]
    if any(r is False for r in rose):
        raise RuntimeError(f"a chip held no memory during {n} parallel "
                           f"trials: peak before {before}, after {after}")
    _phase_record("parallel_trials", meter, t0, snap, chips=n,
                  trials=len(trials), peak_bytes_before=before,
                  peak_bytes_after=after, every_chip_rose=rose)

    # (b) one trial on an n-chip mesh vs the same knobs and seed on one chip
    t0, snap = time.monotonic(), meter.snapshot()
    one = _train_one(client, "one", train_uri, test_uri,
                     {"MODEL_TRIAL_COUNT": 1, "CHIP_COUNT": 1}, "mesh_vit")[0]
    mesh = _train_one(client, "mesh", train_uri, test_uri,
                      {"MODEL_TRIAL_COUNT": 1, "CHIP_COUNT": n,
                       "CHIPS_PER_TRIAL": n}, "mesh_vit")[0]
    rel = abs(mesh["final_loss"] - one["final_loss"]) / abs(one["final_loss"])
    if not rel <= 1e-3:
        raise RuntimeError(
            f"CHIPS_PER_TRIAL={n} final loss {mesh['final_loss']} vs one "
            f"chip {one['final_loss']}: relative {rel:.3e} > 1e-3")
    _phase_record("mesh_trial", meter, t0, snap, chips_per_trial=n,
                  one_chip_final_loss=one["final_loss"],
                  mesh_final_loss=mesh["final_loss"], relative_diff=rel)

    # (c) sharded predict vs one chip, same trained trial
    t0, snap = time.monotonic(), meter.snapshot()
    queries = x[sizes.mesh_n_train:sizes.mesh_n_train + 8]
    answers = {}
    for chips in (1, n):
        client = login()
        inf = client.create_inference_job(
            "one", budget={"CHIPS_PER_WORKER": chips})
        if inf["status"] != "RUNNING":
            raise RuntimeError(f"CHIPS_PER_WORKER={chips}: {inf}")
        answers[chips] = np.asarray(
            client.predict_direct("one", queries), np.float32)
        client.stop_inference_job("one")
    diff = float(np.max(np.abs(answers[n] - answers[1])))
    if not np.allclose(answers[n], answers[1], rtol=1e-3, atol=1e-3):
        raise RuntimeError(f"CHIPS_PER_WORKER={n} predict differs from one "
                           f"chip by {diff}")
    _phase_record("sharded_predict", meter, t0, snap, chips_per_worker=n,
                  max_abs_diff=diff, **_data_plane(admin))

    # (d) ring attention, GPipe and expert-parallel collectives
    t0, snap = time.monotonic(), meter.snapshot()
    graft.dryrun_multichip(n)
    _phase_record("dryrun_multichip", meter, t0, snap, devices=n)


# ---------------------------------------------------------------------------

def run(seed: int, chips: int, sizes: Sizes, devices) -> None:
    """All phases of one mode on `devices`, in a fresh work directory.
    Raises on the first failure."""
    from rafiki_tpu.sdk import compile_cache

    workdir = tempfile.mkdtemp(prefix="rafiki_smoke_")
    os.environ["RAFIKI_WORKDIR"] = workdir
    os.environ["RAFIKI_PREDICTOR_PORTS"] = "1"
    meter = CompileMeter()
    compile_cache.enable()
    admin, server, login = _boot(workdir, len(devices))
    try:
        if chips == 1:
            phase_search_and_serve(login(), admin, workdir, seed, sizes,
                                   meter)
            phase_generate(login, admin, seed, sizes, meter)
            phase_kernel(seed, sizes, meter)
        else:
            phase_four_chips(login, admin, workdir, seed, sizes, meter,
                             devices)
    finally:
        admin.stop_all_jobs()
        server.stop()
        admin.shutdown()
        meter.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)
    t0 = time.monotonic()
    # the contract: out within 1200 s. A phase that hangs dumps every
    # thread's stack and ends the process, instead of being cut in silence
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True,
                                      file=sys.__stderr__)
    try:
        return _main(args, t0)
    finally:
        faulthandler.cancel_dump_traceback_later()


def _main(args, t0: float) -> int:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no accelerator (jax.devices()[0].platform = "
              f"{devices[0].platform!r}); this script runs on a TPU only",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"this process has {len(devices)}", file=sys.stderr)
        return 2
    devices = devices[:args.chips]
    emit({"phase": "start", "seed": args.seed, "chips": args.chips,
          "jax": jax.__version__,
          "JAX_COMPILATION_CACHE_DIR": os.environ.get(
              "JAX_COMPILATION_CACHE_DIR")})
    run(args.seed, args.chips, FULL, devices)
    emit({"phase": "total", "wall_s": round(time.monotonic() - t0, 3)})
    emit({"ok": True, "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(jax.devices())}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
