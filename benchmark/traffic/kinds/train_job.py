"""Traffic kind `train_job`: one search job through the client's own calls.
`Client.create_train_job` over HTTP -> admin -> advisor -> thread placement
-> worker/train.py -> the template -> DataParallelTrainer's epoch scan ->
evaluate -> persist, trial after trial, with a trial count the window cannot
exhaust. Set-up runs trials until one has completed with no program
compiled; the window opens as the next trial is proposed and lasts
`--seconds`; `stop_train_job` closes it.
"""

from __future__ import annotations

import gc
import os
import time

import numpy as np

from benchmark import harness, trafficgen

APP, MODEL = "bench_search", "bench_vit"


def template_values(cfg: dict, traffic: dict, seed: int) -> dict:
    """The configuration's own values (among them `IMAGE`, `CHANNELS` and
    `CLASSES`, which the data set is made to), the seed, and the traffic
    file's trial."""
    return {**harness.template_values(cfg), "SEED": seed % harness.SEED_MOD,
            "BATCH": traffic["batch_size"], "EPOCHS": traffic["epochs"],
            "LR_MIN": traffic["lr_min"], "LR_MAX": traffic["lr_max"],
            "FAULT": traffic.get("fault", "")}


def run(cell: dict, ctx) -> dict:
    cfg, traffic = cell["config_data"], cell["traffic_data"]
    n_train, n_test = traffic["n_train"], traffic["n_test"]
    values = template_values(cfg, traffic, ctx.seed)
    x, y = trafficgen.images(ctx.seed, n_train + n_test, values["IMAGE"],
                             values["CHANNELS"], values["CLASSES"])
    platform = harness.Platform(len(ctx.devices), traffic["settings"])
    try:
        client = platform.login()
        data_dir = os.path.join(platform.workdir, "data")
        os.makedirs(data_dir)
        uris = {}
        for name, rows in (("train", slice(0, n_train)),
                           ("test", slice(n_train, None))):
            uris[name] = os.path.join(data_dir, f"{name}.npz")
            np.savez(uris[name], x=x[rows], y=y[rows])  # floats do not deflate
        path = harness.render_template(cell["config"], values,
                                       platform.workdir)
        client.create_model(MODEL, cfg["template"]["task"], path,
                            cfg["template"]["class"])
        budget = {"MODEL_TRIAL_COUNT": traffic["trial_count"],
                  **traffic["settings"].get("budget", {})}
        client.create_train_job(APP, cfg["template"]["task"], uris["train"],
                                uris["test"], budget=budget, models=[MODEL])
        _warm(client, ctx, traffic["warm_trials_max"])
        t0 = time.time()
        snap0 = ctx.meter.snapshot()
        trace = None
        if ctx.trace:
            trace = harness.TraceWindow(
                ctx.out_dir, t0 + 0.5,
                min(traffic["trace_seconds"], max(ctx.seconds - 1.0, 0.5)))
        time.sleep(max(t0 + ctx.seconds - time.time(), 0.0))
        t1 = t0 + ctx.seconds
        snap1 = ctx.meter.snapshot()
        client.stop_train_job(APP)
        trials = harness.wait_for(
            lambda: _settled(client), 180, "the last trial to end")
        rows = []
        for t in sorted(trials, key=lambda t: t["datetime_started"]):
            if t["datetime_stopped"] and t["datetime_stopped"] < t0:
                continue
            rows.append({
                "id": t["id"], "status": t["status"], "knobs": t["knobs"],
                "score": t["score"], "started": t["datetime_started"],
                "stopped": t["datetime_stopped"],
                "epochs": [m for m in client.get_trial_logs(
                    t["id"])["metrics"] if "epoch_time" in m],
                "spans": client.get_trial_trace(t["id"])})
        # the trial the comparison follows: the first the window proposed
        checked = next((r for r in rows if r["started"] >= t0 - 0.5
                        and r["status"] == "COMPLETED"), None)
        params = (client.download_trial_params(checked["id"])
                  if checked else None)
        trace_path = trace.finish() if trace else None
    finally:
        platform.close()
    _free_program_state()
    in_window = [r for r in rows if t0 - 0.5 <= r["started"] < t1]
    ended = [r for r in in_window if r["stopped"] and r["stopped"] <= t1]
    # a search job's unit of work is a scored, persisted trial: each
    # completed trial's samples count by the share of its whole life
    # (proposal to persisted parameters) that lies inside the window, so
    # turnover, evaluation and persisting count against the rate. The trial
    # the window's close cuts is let run to its end to learn how long it was.
    per_trial = (traffic["epochs"] * (n_train // traffic["batch_size"])
                 * traffic["batch_size"])
    samples = sum(_overlap(r["started"], r["stopped"], t0, t1) * per_trial
                  for r in rows if r["status"] == "COMPLETED")
    return {
        "setup_s": t0 - ctx.t_start,
        "attempted": len(in_window),
        "failed": sum(r["status"] != "COMPLETED" for r in ended),
        "end_to_end": {"train_samples_per_s": samples / ctx.seconds},
        "t0": t0, "t1": t1, "trials": rows,
        "compile": {k: snap1[k] - snap0[k] for k in snap0},
        "trace": ({"path": trace_path, "t0": trace.t0,
                   "window_s": trace.t1 - trace.t0} if trace else None),
        "steps_per_epoch": n_train // traffic["batch_size"],
        "check": {"trial": checked, "params": params, "x": x[:n_train],
                  "y": y[:n_train]},
    }


def _overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    """The share of [a0, a1] that lies inside [b0, b1]."""
    return max(min(a1, b1) - max(a0, b0), 0.0) / (a1 - a0)


def _warm(client, ctx, most: int) -> None:
    """Returns as a trial completes during which no program was compiled
    (the next one is being proposed)."""
    first_seen, done = {}, set()
    deadline = time.monotonic() + 1000
    while time.monotonic() < deadline:
        for t in client.get_trials_of_train_job(APP):
            first_seen.setdefault(t["id"], ctx.meter.snapshot()["programs"])
            if t["status"] == "ERRORED":
                raise harness.BenchmarkError(f"a warm-up trial errored: {t}")
            if t["status"] == "COMPLETED" and t["id"] not in done:
                done.add(t["id"])
                if ctx.meter.snapshot()["programs"] == first_seen[t["id"]]:
                    return
                if len(done) >= most:
                    raise harness.BenchmarkError(
                        f"{most} trials and every one compiled a program")
        time.sleep(0.05)
    raise harness.BenchmarkError("no warm trial within 1000 s")


def _settled(client):
    trials = client.get_trials_of_train_job(APP)
    return trials if all(t["status"] != "RUNNING" for t in trials) else None


def _free_program_state() -> None:
    """The trainer's programs, the data set on the device and every buffer
    the trials held go, so that the reference has the chip to itself."""
    import jax

    from rafiki_tpu.sdk import jax_backend

    jax_backend.trainer_cache_clear()
    gc.collect()
    jax.clear_caches()
