"""What the generate kinds share: bring a deployment of the cell's model
to steady state through the client's own calls (upload the template, one
trial that makes the weights, deploy), start the load generator in a child
process, open the window, and bring back what the child recorded with what
the program counted meanwhile.
"""

from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from benchmark import harness, trafficgen

APP, MODEL = "bench_chat", "bench_lm"
GAUGES = ("rafiki_gen_slots_busy",)
COUNTERS = ("rafiki_gen_tokens_total", "rafiki_gen_preemptions_total",
            "rafiki_gen_prefix_hits_total")


def template_values(cfg: dict, traffic: dict, seed: int) -> dict:
    """The configuration's own values (among them `VOCAB` and `MAX_CONTEXT`,
    which the requests are drawn within), the seed, and a test's fault."""
    return {**harness.template_values(cfg), "SEED": seed % harness.SEED_MOD,
            "FAULT": traffic.get("fault", "")}


def _registry_total(name: str) -> float:
    from rafiki_tpu.utils.metrics import REGISTRY

    metric = REGISTRY.get(name)
    if metric is None:
        return 0.0
    return float(sum(c.value() for c in metric.children().values()))


def _counts() -> dict:
    return {"counters": {n: _registry_total(n) for n in COUNTERS}}


class _Sampler(threading.Thread):
    """Reads the program's gauges ten times a second through the window, and
    its counters as the window opens and as it closes."""

    def __init__(self, t0: float, t1: float):
        super().__init__(daemon=True, name="bench-sampler")
        self.t0, self.t1 = t0, t1
        self.samples = {name: [] for name in GAUGES}
        self.before = self.after = None

    def run(self) -> None:
        time.sleep(max(self.t0 - time.time(), 0.0))
        self.before = _counts()
        while time.time() < self.t1:
            for name in GAUGES:
                self.samples[name].append(_registry_total(name))
            time.sleep(0.1)
        self.after = _counts()


def run(cell: dict, ctx) -> dict:
    cfg, traffic = cell["config_data"], cell["traffic_data"]
    platform = harness.Platform(len(ctx.devices), traffic["settings"])
    child = None
    try:
        client = platform.login()
        values = template_values(cfg, traffic, ctx.seed)
        path = harness.render_template(cell["config"], values,
                                       platform.workdir)
        task = cfg["template"]["task"]
        client.create_model(MODEL, task, path, cfg["template"]["class"])
        client.create_train_job(
            APP, task, "uri://none", "uri://none",
            budget={"MODEL_TRIAL_COUNT": 1, "CHIP_COUNT": 1}, models=[MODEL])
        harness.wait_for(
            lambda: client.get_train_job(APP)["status"]
            in ("STOPPED", "ERRORED"), 900, "the trial that makes the weights")
        trials = client.get_trials_of_train_job(APP)
        if [t["status"] for t in trials] != ["COMPLETED"]:
            raise harness.BenchmarkError(f"the weights' trial: {trials}")
        gc.collect()
        before_deploy = harness.bytes_in_use(ctx.devices)
        inf = client.create_inference_job(
            APP, budget=traffic["settings"].get("budget") or None)
        if inf["status"] != "RUNNING" or not inf.get("predictor_port"):
            raise harness.BenchmarkError(f"inference job not serving: {inf}")

        spec = _spec(traffic, ctx, platform, values)
        # the first request through the door compiles (or loads) the prefill
        # chunk and the decode round; the child's own warm request follows
        _one_stream(client, spec["warm"])
        spec_path = os.path.join(platform.workdir, "loadgen.json")
        with open(spec_path, "w", encoding="utf-8") as f:
            json.dump(spec, f)
        child = subprocess.Popen(
            [sys.executable, "-m", "benchmark.loadgen", spec_path],
            cwd=harness.ROOT, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"})
        ready = child.stdout.readline().strip()
        if ready != "READY":
            raise harness.BenchmarkError(
                f"the load generator said {ready!r}, not READY")
        t0 = time.time() + 0.5
        t1 = t0 + ctx.seconds
        sampler = _Sampler(t0, t1)
        sampler.start()
        snap0 = ctx.meter.snapshot()
        child.stdin.write(f"{t0!r}\n")
        child.stdin.flush()
        trace = None
        if ctx.trace:
            trace = harness.TraceWindow(
                ctx.out_dir, t0 + min(2.0, ctx.seconds / 4),
                min(traffic["trace_seconds"], max(ctx.seconds / 2, 0.5)))
        time.sleep(max(t1 - time.time(), 0.0))
        snap1 = ctx.meter.snapshot()
        try:
            child.wait(timeout=150)
        except subprocess.TimeoutExpired:
            raise harness.BenchmarkError("the load generator did not end")
        if child.returncode != 0:
            raise harness.BenchmarkError(
                f"the load generator exited with {child.returncode}")
        sampler.join(timeout=10)
        with open(spec["out"], encoding="utf-8") as f:
            records = json.load(f)["records"]
        trace_path = trace.finish() if trace else None
        client.stop_inference_job(APP)
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        platform.close()
    _wait_freed(ctx.devices)
    for r in records:
        r["prompt_ids"] = spec["requests"][r["i"]]["prompt_ids"]
    return {
        "setup_s": t0 - ctx.t_start, "t0": t0, "t1": t1,
        "records": records, "gauges": sampler.samples,
        "counts_before": sampler.before, "counts_after": sampler.after,
        "compile": {k: snap1[k] - snap0[k] for k in snap0},
        "bytes_in_use_before_deploy": before_deploy,
        "trace": ({"path": trace_path, "t0": trace.t0,
                   "window_s": trace.t1 - trace.t0} if trace else None),
        **reduce_records(records, t0, t1, ctx.seconds),
    }


def _spec(traffic: dict, ctx, platform, values: dict) -> dict:
    vocab = values["VOCAB"]
    count = int(ctx.seconds * traffic["most_requests_per_s"]
                + traffic["callers"])
    requests = trafficgen.request_stream(traffic, ctx.seed, vocab, count)
    # a chunk and a half, ending inside a block: the prefill chunk, the
    # decode round and the copy-on-write of the published last block
    chunk = int(os.environ.get("RAFIKI_GEN_PREFILL_CHUNK", "64"))
    warm_prompt = min(chunk + chunk // 2 + 5, values["MAX_CONTEXT"] - 8)
    warm = {"prompt_ids": np.random.default_rng([ctx.seed, 3]).integers(
        0, vocab, size=warm_prompt).tolist(), "max_tokens": 4}
    return {**platform.credentials(), "app": APP,
            "callers": traffic["callers"], "seconds": ctx.seconds,
            "requests": requests, "warm": warm,
            "out": os.path.join(platform.workdir, "loadgen_out.json")}


def _one_stream(client, request: dict) -> list:
    tokens = []
    for delta in client.generate(APP, request["prompt_ids"],
                                 max_tokens=request["max_tokens"],
                                 timeout_s=600.0):
        tokens.extend(delta.get("tokens") or [])
    if len(tokens) != request["max_tokens"]:
        raise harness.BenchmarkError(
            f"the warm request came back with {len(tokens)} tokens")
    return tokens


def _wait_freed(devices) -> None:
    """The serving worker's weights and pool have to be off the device
    before the reference runs; its thread ends a moment after the stop."""
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        gc.collect()
        if harness.bytes_in_use(devices) < (1 << 30):
            return
        time.sleep(0.25)


def reduce_records(records: list, t0: float, t1: float,
                   seconds: float) -> dict:
    """From the child's records to the cell's numbers. The rate is all the
    tokens the window saw over all its seconds; a stream that failed,
    stalled or came back short counts as failed."""
    tokens = sum(n for r in records for t, n in r["deltas"] if t0 <= t < t1)
    failed = [r for r in records if r["error"] or r["done"] is None
              or len(r["tokens"]) != r["max_tokens"]]
    prefilled = sum(r["prompt_tokens"] for r in records
                    if r["deltas"] and t0 <= r["deltas"][0][0] < t1)
    why = [r["error"] or f"{len(r['tokens'])} of {r['max_tokens']} tokens, "
           f"reason {r['reason']}, ended {r['done'] is not None}"
           for r in failed[:3]]
    return {"attempted": len(records), "failed": len(failed),
            "info": {"failures": why},
            "tokens_in_window": tokens, "prompt_tokens_in_window": prefilled,
            "end_to_end": {"tokens_per_s": tokens / seconds}}


def decoding(records: list, a: float, b: float) -> tuple:
    """What the decode rounds of [a, b) held, from the client's log: the
    mean number of sequences decoding and the mean sum of their lengths. A
    stream decodes from its first delta (the prefill's token) to its last,
    and between two deltas it is as long as its prompt and the tokens it
    has had. Blocks that the prefix cache keeps and no slot holds are not
    in it, which the program's `kv_blocks_used` gauge cannot say."""
    if b <= a:
        return 0.0, 0.0
    sequences = live = 0.0
    for r in records:
        length = r["prompt_tokens"]
        for (ta, n), (tb, _) in zip(r["deltas"], r["deltas"][1:]):
            length += n
            inside = max(min(tb, b) - max(ta, a), 0.0)
            sequences += inside
            live += inside * length
    return sequences / (b - a), live / (b - a)
