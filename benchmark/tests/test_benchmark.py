"""Tests of the benchmark's own files, run with
`python -m pytest benchmark/tests` (not part of tier-1). The rehearsals drive
the same functions as a run on the chip, at a tiny size on the CPU, through
`run.run_cell`, which is everything after the harness's look for a chip."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmark import harness, run, serving, trace_reduce, trafficgen
from benchmark.tests import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def bench() -> dict:
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def check_line(line: dict, cell: dict, trace: bool) -> None:
    assert LINE_KEYS <= set(line)
    assert list(line)[-1] == "checks"
    json.dumps(line)
    declared = {m["name"]: m for m in
                cell["per_layer" if trace else "end_to_end"]}
    for name, metric in line["metrics"].items():
        assert NAME.match(name) and UNIT.match(metric["unit"])
        assert metric["unit"] == declared[name]["unit"]
    if not trace:
        assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    for c in line["checks"].values():
        assert set(c) == {"value", "limit", "ok"}


# -- BENCHMARK.json ------------------------------------------------------------

def test_names_units_and_files():
    b = bench()
    e2e = {m["name"]: m for m in b["end_to_end"]}
    cells = {w["name"]: w for w in b["workloads"]}
    for group in (b["configs"], b["workloads"], b["end_to_end"],
                  b["per_layer"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in b["workloads"]:
        assert len(w["why"]) <= 200 and NAME.match(w["traffic"])
        cell = harness.load_cell(w["name"])
        harness.load_by_name("traffic.kinds", cell["traffic_data"]["kind"])
        harness.load_by_name("correct", cell["config_data"]["family"])
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
    for m in b["per_layer"]:
        assert hasattr(harness.load_by_name("layer_metrics", m["name"]),
                       "read")
        for w in m["workloads"]:  # each of its cells reports what it moves
            moved = e2e[m["moves"]]
            assert w in cells and w in moved.get("workloads", cells)
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert "setup_s" in e2e and all(0 < m["bound"] <= 0.1
                                    for m in b["end_to_end"])


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.BenchmarkError):
        harness.peaks_for("TPU v9 imaginary")
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_no_chip_no_result(capsys):
    assert run.main(["--workload", "vit_b16.hpo_search", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) != 0
    assert capsys.readouterr().out == ""


# -- traffic generation ---------------------------------------------------------

def test_generators_are_seeded_and_seed_free_in_their_sizes():
    traffic = harness.load_json("traffic", "chat_saturated.json")
    n = traffic["shapes"]
    a = trafficgen.request_stream(traffic, 7, 1000, 3 * n)
    b = trafficgen.request_stream(traffic, 7, 1000, 3 * n)
    c = trafficgen.request_stream(traffic, 2**31 + 8, 1000, 3 * n)
    assert a == b and a != c
    shape = lambda rs: [(len(r["prompt_ids"]), r["max_tokens"]) for r in rs]
    assert shape(a) != shape(c)  # the seed draws the order and the ids,
    for at in range(0, 3 * n, n):  # and every block is the same work
        assert sorted(shape(a)[at:at + n]) == sorted(shape(c)[at:at + n]) \
            == sorted(trafficgen.shapes(traffic))
    for key in ("prompt_tokens", "answer_tokens"):
        spec = traffic[key]
        lengths = trafficgen.lognormal_quantiles(spec, n)
        assert all(spec["min"] <= v <= spec["max"] for v in lengths)
        assert abs(sum(lengths) / n - spec["mean"]) < 0.02 * spec["mean"]
    x1, y1 = trafficgen.images(3, 4, 8)
    x2, _ = trafficgen.images(3, 4, 8)
    assert np.array_equal(x1, x2) and y1.dtype == np.int32


def test_the_client_log_gives_the_rate_and_what_the_rounds_held():
    rec = lambda sent, first: {
        "i": 0, "sent": sent, "done": first + 0.2, "error": None,
        "deltas": [[first, 1], [first + 0.1, 2]], "tokens": [1, 2, 3],
        "prompt_tokens": 5, "max_tokens": 3, "reason": "length"}
    out = serving.reduce_records([rec(10.4, 10.5)], 10.0, 20.0, 10.0)
    assert out["end_to_end"]["tokens_per_s"] == pytest.approx(0.3)
    assert out["prompt_tokens_in_window"] == 5 and out["failed"] == 0
    short = rec(10.0, 10.5)
    short["tokens"] = [1]
    assert serving.reduce_records([short], 10.0, 20.0, 10.0)["failed"] == 1
    # decoding from 10.5 to 10.6 at length 5 + 1, a tenth of [10, 11)
    sequences, live = serving.decoding([rec(10.4, 10.5)], 10.0, 11.0)
    assert sequences == pytest.approx(0.1) and live == pytest.approx(0.6)
    assert serving.decoding([rec(10.4, 10.5)], 12.0, 13.0) == (0.0, 0.0)


# -- the references against the program ----------------------------------------

def _template(config: str, values: dict, tmp_path):
    import importlib.util

    path = harness.render_template(config, values, str(tmp_path))
    spec = importlib.util.spec_from_file_location(f"tmpl_{config}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_vit_reference_against_models_vit(tmp_path):
    import jax
    from rafiki_tpu.models import vit

    from benchmark.correct import vit_train
    from benchmark.reference import vit as reference
    from benchmark.traffic.kinds import train_job

    cell = tiny.cell("vit_b16.hpo_search")
    cfg = vit_train.reference_cfg(cell["config_data"])
    tmpl = _template("vit_b16", train_job.template_values(
        cell["config_data"], cell["traffic_data"], 9), tmp_path)
    params = jax.jit(tmpl.make_params)(jax.random.key(9))  # as train() does
    weights = reference.make_weights(9, cfg)
    for name, path in vit_train.LEAVES.items():
        leaf = params
        for key in path:
            leaf = leaf[key]
        assert np.array_equal(np.asarray(leaf), np.asarray(weights[name]))
    x, _ = trafficgen.images(1, 4, cfg["image_size"])
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(reference.forward(weights, x, cfg))
    got = np.asarray(vit.apply(params, x, tmpl.CFG))  # bf16 compute
    assert np.abs(got - ref).max() < 0.05 * np.abs(ref).max() + 0.02


def test_gpt2_reference_against_models_lm(tmp_path):
    """Full forward, and prefill in chunks then paged decode, against the
    reference's full forward pass."""
    import jax
    from rafiki_tpu.models import lm

    from benchmark.reference import gpt2 as reference

    cell = tiny.cell("gpt2_large.chat_saturated")
    cfg = cell["config_data"]
    tmpl = _template("gpt2_large", serving.template_values(
        cfg, cell["traffic_data"], 4), tmp_path)
    params = jax.jit(tmpl.make_params)(jax.random.key(4))  # as train() does
    weights = reference.make_weights(4, cfg)
    assert np.array_equal(np.asarray(params["blocks"]["attn"]["wo"]),
                          np.asarray(weights["wo"]))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg["vocab_size"], size=40).astype(np.int32)
    full, _ = lm.apply(params, ids[None], tmpl.CFG)
    positions = np.arange(40, dtype=np.int32)[None]
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(reference.logits_at(
            weights, ids[None], positions, cfg))[0]
    assert np.abs(np.asarray(full[0]) - ref).max() < 0.05
    # the served path: 16-token chunks into a paged pool, then decode
    cache = lm.init_paged_kv_cache(tmpl.CFG, 16, 8)
    table = np.arange(16, dtype=np.int32)
    for start in (0, 16):
        logits, cache = lm.paged_prefill(
            params, cache, table, ids[start:start + 16], start, 16, tmpl.CFG)
    assert np.abs(np.asarray(logits) - ref[31]).max() < 1e-3
    tables = np.stack([table, np.full(16, 16, np.int32)])
    logits, cache = lm.paged_decode_step(
        params, cache, np.array([ids[32], 0]), np.array([32, 0]), tables,
        tmpl.CFG)
    assert np.abs(np.asarray(logits[0]) - ref[32]).max() < 1e-3
    gaps = reference.token_gaps([ref[31:33]],
                                [[int(ref[31].argmax()), 0]])
    assert gaps[0] == 0.0 and gaps[1] >= 0.0


# -- the trace reduction -------------------------------------------------------

def test_trace_reduce_on_a_recorded_trace():
    """`data/small.xplane.pb`: three runs of a small jitted matmul on one
    TPU v5e, recorded by PR 23's first chip call."""
    path = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")
    r = trace_reduce.reduce(path, window_s=1.0)
    assert r["n_devices"] == 1 and 0 < r["busy_s"] < 1.0
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])
    assert sum(r["module_runs"].values()) == 3
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert trace_reduce.module_name("jit_epoch_scan(123)") == "jit_epoch_scan"
    assert trace_reduce._union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]


# -- rehearsals: a whole run, tiny, on the CPU ----------------------------------

@pytest.mark.parametrize("workload,trace", [
    ("vit_b16.hpo_search", False),
    ("vit_b16.hpo_search", True),
    ("gpt2_large.chat_saturated", False),
    ("gpt2_large.chat_saturated", True),
])
def test_rehearsal(workload, trace, tmp_path):
    cell = tiny.cell(workload)
    line = run.run_cell(cell, tiny.context(str(tmp_path), seed=2**31 + 11,
                                           seconds=3.0, trace=trace))
    check_line(line, cell, trace)
    assert line["correct"] and line["attempted"] > 0 and line["failed"] == 0
    assert line["info"]["compiles_in_window"] == 0


@pytest.mark.parametrize("workload,fault", [
    ("vit_b16.hpo_search", "frozen"),
    ("vit_b16.hpo_search", "half_batch"),
    ("gpt2_large.chat_saturated", "wrong_token"),
])
def test_a_broken_timed_path_is_not_correct(workload, fault, tmp_path):
    """The rest of a run with the timed path broken underneath: a step that
    returns its state unchanged, half of each batch left out with the mean
    taken over the rest, a token altered where it is produced."""
    cell = tiny.cell(workload, fault=fault)
    line = run.run_cell(cell, tiny.context(str(tmp_path), seed=21,
                                           seconds=2.0))
    assert line["correct"] is False
    assert not all(c["ok"] for c in line["checks"].values())


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_control_precision_is_not_correct_vit(seed):
    """The control at a size a test can hold, through the family's own
    `judge()` and the CELL'S limits: the reference computed in float8 and
    put in the program's place is not correct; the reference itself is."""
    from benchmark.correct import vit_train
    from benchmark.reference import vit as reference

    cell = tiny.cell("vit_b16.hpo_search")
    cfg = vit_train.reference_cfg(cell["config_data"])
    cfg["limits"] = harness.load_cell(
        "vit_b16.hpo_search")["config_data"]["limits"]
    x, y = trafficgen.images(seed, 32, cfg["image_size"])
    ref = reference.train(seed, cfg, x, y, 3e-4, 8, 2)
    control = reference.train(seed, cfg, x, y, 3e-4, 8, 2, quant="fp8")
    as_program = lambda r: {"epoch_losses": r["epoch_losses"],
                            "change_norm": r["change_norm"]}
    sound = vit_train.judge(cfg, vit_train.compare(as_program(ref), ref))
    assert harness.within_limits(sound)
    assert all(c["value"] == 0.0 for c in sound.values())
    checks = vit_train.judge(cfg, vit_train.compare(as_program(control),
                                                    ref))
    assert harness.within_limits(checks) is False, checks


@pytest.mark.parametrize("seed", [4, 6])
def test_control_precision_is_not_correct_lm(seed):
    """The served model's control through the family's own `judge()`: at
    every position of the same prompts and tokens, the token that int8
    weights put first. At a size a test can hold (2 layers of 128, 8,192
    words) it reads 1.4e-5 and 1.7e-5 on these seeds, under the cell's
    limit of 1e-4, which two layers cannot reach; so the limit here is the
    tiny size's own, and benchmark/controls.py holds the control to the
    cell's limit at the cell's size on the chip."""
    from benchmark.correct import lm_serve
    from benchmark.reference import gpt2 as reference

    cfg = tiny.cell("gpt2_large.chat_saturated")["config_data"]
    cfg.update(vocab_size=8192, n_embd=128, n_layer=2,
               limits={"served_gap_mean": 2e-6})
    weights = reference.make_weights(seed, cfg)
    rng = np.random.default_rng(seed)
    draw = lambda n: rng.integers(0, cfg["vocab_size"], size=n).tolist()
    requests = [(draw(20), draw(100)) for _ in range(8)]
    ref = reference.served_logits(weights, cfg, requests)
    first = lambda logits: [np.argmax(a, axis=-1) for a in logits]
    sound = lm_serve.judge(cfg, reference.token_gaps(ref, first(ref)))
    assert harness.within_limits(sound)
    held = reference.at_precision(weights, "int8w")
    control = first(reference.served_logits(held, cfg, requests))
    checks = lm_serve.judge(cfg, reference.token_gaps(ref, control))
    assert harness.within_limits(checks) is False, checks
    assert harness.within_limits(lm_serve.judge(cfg, None)) is False


# -- driven by data: a later PR adds files and entries only ----------------------

def test_a_cell_a_configuration_and_a_metric_are_added_by_files(tmp_path):
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(harness.ROOT, "benchmark"),
                    root / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    b = bench()
    cfgs = root / "benchmark" / "configs"
    new_cfg = json.loads((cfgs / "vit_b16.json").read_text())
    new_cfg.update(tiny.TINY_VIT, name="vit_new")
    new_cfg["limits"] = {"loss_first_epoch_rel": 0.003,
                         "change_worst_leaf_rel": 0.008,
                         "change_median_leaf_rel": 0.002}
    (cfgs / "vit_new.json").write_text(json.dumps(new_cfg))
    shutil.copy(cfgs / "vit_b16_template.py", cfgs / "vit_new_template.py")
    traffic = harness.load_json("traffic", "hpo_search.json")
    traffic.update(n_train=32, n_test=16, batch_size=8, epochs=2)
    (root / "benchmark" / "traffic" / "short_search.json").write_text(
        json.dumps(traffic))
    (root / "benchmark" / "layer_metrics" / "trials_scored.py").write_text(
        "def read(result, cell, peaks):\n"
        "    return float(sum(t['status'] == 'COMPLETED'\n"
        "                     for t in result['trials']))\n")
    b["configs"].append({"name": "vit_new", "source": "test",
                         "file": "benchmark/configs/vit_new.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "vit_new.short_search",
                           "config": "vit_new", "traffic": "short_search",
                           "chips": 1, "why": "test"})
    for m in b["end_to_end"]:
        if m["name"] == "train_samples_per_s":
            m["workloads"].append("vit_new.short_search")
    b["per_layer"].append({
        "name": "trials_scored", "unit": "trials", "better": "higher",
        "source": "program_counter", "layer": "train worker",
        "moves": "train_samples_per_s",
        "workloads": ["vit_new.short_search"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    driver = (
        "import json, sys\n"
        "from benchmark import harness, run\n"
        "from benchmark.tests import tiny\n"
        "cell = harness.load_cell('vit_new.short_search')\n"
        "line = run.run_cell(cell, tiny.context(sys.argv[1], seconds=2.0,"
        " trace=True))\n"
        "print(json.dumps(line))\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": f"{root}{os.pathsep}{harness.ROOT}"}
    done = subprocess.run([sys.executable, "-c", driver, str(tmp_path)],
                          cwd=root, env=env, capture_output=True, text=True,
                          timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().split("\n")[-1])
    assert line["correct"] is True
    assert line["metrics"]["trials_scored"]["value"] >= 1


def test_a_reader_that_finds_nothing_returns_nothing():
    """Every reader under layer_metrics/, those of cells kept for later too,
    on a record with nothing to read: no number, never a 0."""
    import glob

    cell = harness.load_cell("gpt2_large.chat_saturated")
    empty = {"t0": 0.0, "t1": 1.0, "trials": [], "trace": None,
             "tokens_in_window": 0, "prompt_tokens_in_window": 0,
             "steps_per_epoch": 8}
    here = os.path.join(harness.HERE, "layer_metrics")
    for path in sorted(glob.glob(os.path.join(here, "[a-z]*.py"))):
        name = os.path.basename(path)[:-3]
        if name == "compiles_in_window_train":
            continue  # a count: 0 is what it reads
        reader = harness.load_by_name("layer_metrics", name)
        assert reader.read(dict(empty), cell, tiny.CPU_PEAKS) is None, name
