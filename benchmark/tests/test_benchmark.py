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


CELLS = tiny.cases()  # every cell of BENCHMARK.json, with its faults


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
    b = harness.load_benchmark()
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
    cut = serving.reduce_records([short], 10.0, 20.0, 10.0)
    assert cut["failed"] == 1
    assert cut["info"]["failures"] == [
        "1 of 3 tokens, reason length, ended True"]
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
    (name, trace) for name, _ in CELLS for trace in (False, True)])
def test_rehearsal(workload, trace, tmp_path):
    cell = tiny.cell(workload)
    line = run.run_cell(cell, tiny.context(str(tmp_path), seed=2**31 + 11,
                                           seconds=3.0, trace=trace))
    check_line(line, cell, trace)
    assert line["correct"] and line["attempted"] > 0 and line["failed"] == 0
    assert line["info"]["compiles_in_window"] == 0


@pytest.mark.parametrize("workload,fault", [
    (name, fault) for name, faults in CELLS for fault in faults])
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

def test_the_committed_templates_render_as_the_fixed_keys_rendered_them(
        tmp_path):
    """Both templates at the committed sizes: the `# @cell` lines that the
    parent's two TEMPLATE_KEYS tables and kinds gave (kept here as text),
    and every other line as it is on disk."""
    was = {
        "vit_b16.hpo_search": [
            "SEED = 7", "IMAGE = 224", "PATCH = 16", "CHANNELS = 3",
            "DIM = 768", "DEPTH = 12", "HEADS = 12", "CLASSES = 10",
            "BATCH = 32", "EPOCHS = 8", "LR_MIN = 0.0001", "LR_MAX = 0.001",
            "FAULT = ''"],
        "gpt2_large.chat_saturated": [
            "SEED = 7", "VOCAB = 50257", "MAX_CONTEXT = 1024", "DIM = 1280",
            "DEPTH = 36", "HEADS = 20", "FAULT = ''"]}
    from benchmark.traffic.kinds import train_job

    values_of = {"vit_b16.hpo_search": train_job.template_values,
                 "gpt2_large.chat_saturated": serving.template_values}
    for workload, lines in was.items():  # the parent's two, not every cell
        cell = harness.load_cell(workload)
        values = values_of[workload](
            cell["config_data"], cell["traffic_data"], 7 + harness.SEED_MOD)
        with open(harness.render_template(cell["config"], values,
                                          str(tmp_path))) as f:
            rendered = f.read().split("\n")
        with open(os.path.join(harness.HERE, "configs",
                               f"{cell['config']}_template.py")) as f:
            on_disk = f.read().split("\n")
        set_lines = lambda text: [t for t in text if t.endswith("# @cell")]
        assert set_lines(rendered) == [line + "  # @cell" for line in lines]
        assert len(rendered) == len(on_disk) and all(
            r == d or d in set_lines(on_disk)
            for r, d in zip(rendered, on_disk))


OTHER_KEYS = {"n_embd": "hidden_size", "n_layer": "num_hidden_layers",
              "n_head": "num_attention_heads",
              "n_positions": "max_position_embeddings"}


def _reads_other_keys(package: str, module: str, with_cfg: dict) -> str:
    """A reference or an operation count of the new family's own: a module
    that reads the new file's keys (and hands them to its relative under
    the names that one reads). `with_cfg`: function -> where `cfg` stands
    among its arguments."""
    return "\n".join([
        f"from benchmark.{package} import {module} as _base",
        f"KEYS = {OTHER_KEYS!r}",
        "def _with_cfg(name, at):",
        "    def call(*args):",
        "        cfg = args[at]",
        "        cfg = {**cfg, **{old: cfg[new] for old, new in KEYS.items()}}",
        "        return getattr(_base, name)(*args[:at], cfg, *args[at + 1:])",
        "    return call",
        *[f"{name} = _with_cfg({name!r}, {at})"
          for name, at in with_cfg.items()]]) + "\n"


def _add_a_vit_under_the_same_keys(root, b: dict) -> None:
    cfgs = root / "benchmark" / "configs"
    new_cfg = json.loads((cfgs / "vit_b16.json").read_text())
    new_cfg["name"] = "vit_new"
    (cfgs / "vit_new.json").write_text(json.dumps(new_cfg))
    shutil.copy(cfgs / "vit_b16_template.py", cfgs / "vit_new_template.py")
    shutil.copy(root / "benchmark" / "traffic" / "hpo_search.json",
                root / "benchmark" / "traffic" / "short_search.json")
    (root / "benchmark" / "layer_metrics" / "trials_scored.py").write_text(
        "def read(result, cell, peaks):\n"
        "    done = [t for t in result['trials']\n"
        "            if t['status'] == 'COMPLETED']\n"
        "    return float(len(done)) if done else None\n")
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


def _add_a_language_model_under_other_keys(root, b: dict) -> None:
    """`lm_new`: GPT-2 large's file keyed as most published configurations
    are, a template with one more `# @cell` line (the MLP's ratio, 2 on disk
    and 4 in the file: weights of another shape where it is not set), a
    reference and an operation count that read those keys, `tiny` in the
    file; one cell on it, reporting what the committed generate cell does."""
    at = root / "benchmark"
    old = json.loads((at / "configs" / "gpt2_large.json").read_text())
    rename = lambda d: {OTHER_KEYS.get(k, k): v for k, v in d.items()
                        if k != "n_ctx"}
    new_cfg = rename(old)
    new_cfg.update(name="lm_new", mlp_ratio=4, reference="lm_new",
                   ops={"decode_round": "lm_new_round"})
    new_cfg["template"]["values"] = {
        "VOCAB": "vocab_size", "MAX_CONTEXT": "max_position_embeddings",
        "DIM": "hidden_size", "DEPTH": "num_hidden_layers",
        "HEADS": "num_attention_heads", "MLP_RATIO": "mlp_ratio"}
    new_cfg["tiny"]["sizes"] = rename(old["tiny"]["sizes"])
    (at / "configs" / "lm_new.json").write_text(json.dumps(new_cfg))
    template = (at / "configs" / "gpt2_large_template.py").read_text()
    for was, now in (("HEADS = 4  # @cell\n",
                      "HEADS = 4  # @cell\nMLP_RATIO = 2  # @cell\n"),
                     ("heads=HEADS,", "heads=HEADS, mlp_ratio=MLP_RATIO,"),
                     ("DEPTH, 4 * DIM\n", "DEPTH, MLP_RATIO * DIM\n")):
        assert template.count(was) == 1
        template = template.replace(was, now)
    (at / "configs" / "lm_new_template.py").write_text(template)
    (at / "reference" / "lm_new.py").write_text(
        _reads_other_keys("reference", "gpt2",
                          {"make_weights": 1, "served_logits": 1})
        + "token_gaps, at_precision = _base.token_gaps, _base.at_precision\n")
    (at / "ops" / "lm_new_round.py").write_text(_reads_other_keys(
        "ops", "lm_decode_round", {"flops_per_token": 0, "least_seconds": 0}))
    shutil.copy(at / "traffic" / "chat_saturated.json",
                at / "traffic" / "short_chat.json")
    b["configs"].append({"name": "lm_new", "source": "test",
                         "file": "benchmark/configs/lm_new.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "lm_new.short_chat", "config": "lm_new",
                           "traffic": "short_chat", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "gpt2_large.chat_saturated" in m.get("workloads", []):
            m["workloads"].append("lm_new.short_chat")


def _drive_a_copy_with(add, tmp_path, script: str, suite=False) -> list:
    """What a later PR does: files and entries added by `add` to a copy of
    the benchmark, none that was there touched. `script` then runs in the
    copy, with `rehearse(workload, trace, fault)` at hand; the JSON lines it
    printed. With `suite`, the copy's own tests then run over its cells:
    a test that closes the set of cells fails here, not in the later PR."""
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(harness.ROOT, "benchmark"),
                    root / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    b = harness.load_benchmark()
    add(root, b)
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    assert all(p.read_bytes() == was for p, was in before.items())
    driver = (
        "import json, sys\n"
        "from benchmark import harness, run\n"
        "from benchmark.tests import tiny\n"
        "def rehearse(workload, trace, fault=''):\n"
        "    cell = tiny.cell(workload, fault=fault)\n"
        "    line = run.run_cell(cell, tiny.context(\n"
        "        sys.argv[1], seconds=2.0, trace=trace))\n"
        "    print(json.dumps(line))\n") + script
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": f"{root}{os.pathsep}{harness.ROOT}"}
    done = subprocess.run([sys.executable, "-c", driver, str(tmp_path)],
                          cwd=root, env=env, capture_output=True, text=True,
                          timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    if suite:
        tests = subprocess.run(
            [sys.executable, "-m", "pytest", "benchmark/tests", "-q", "-x",
             "-p", "no:cacheprovider", "-k", "not added_by_files"],
            cwd=root, env=env, capture_output=True, text=True, timeout=1800)
        assert tests.returncode == 0, tests.stdout[-3000:]
    return [json.loads(line) for line in done.stdout.strip().split("\n")
            if line.startswith(("{", "["))]


def test_a_cell_a_configuration_and_a_metric_are_added_by_files(tmp_path):
    line, = _drive_a_copy_with(
        _add_a_vit_under_the_same_keys, tmp_path,
        "rehearse('vit_new.short_search', True)\n")
    assert line["correct"] is True
    assert line["metrics"]["trials_scored"]["value"] >= 1


def test_a_language_model_under_other_keys_is_added_by_files(tmp_path):
    def both(root, b):
        _add_a_vit_under_the_same_keys(root, b)
        _add_a_language_model_under_other_keys(root, b)

    lm, broken, unset, rooflines = _drive_a_copy_with(
        both, tmp_path, suite=True, script=
        "rehearse('lm_new.short_chat', True)\n"
        "rehearse('lm_new.short_chat', False, 'wrong_token')\n"
        # a `# @cell` line that the file forgets would run at its tiny size
        "from benchmark import serving\n"
        "cell = tiny.cell('lm_new.short_chat')\n"
        "del cell['config_data']['template']['values']['MLP_RATIO']\n"
        "values = serving.template_values(\n"
        "    cell['config_data'], cell['traffic_data'], 1)\n"
        "try:\n"
        "    harness.render_template('lm_new', values, sys.argv[1])\n"
        "except harness.BenchmarkError as e:\n"
        "    print(json.dumps({'refused': str(e)}))\n"
        # the CPU's trace has no device plane: the roofline's reader on a
        # reduced trace made by hand, over the new cell and the committed
        "record = {'prompt_tokens': 5, 'deltas': [[0.1, 1], [0.6, 2]]}\n"
        "result = {'records': [record], 'trace': {'path': 'by hand',\n"
        "          't0': 0.0, 'window_s': 1.0}, '_reduced': {\n"
        "          'module_s': {'jit_paged_decode_round': 0.5},\n"
        "          'module_runs': {'jit_paged_decode_round': 10}}}\n"
        "reader = harness.load_by_name('layer_metrics',\n"
        "                              'decode_step_roofline')\n"
        "print(json.dumps([reader.read(result, tiny.cell(w), tiny.CPU_PEAKS)\n"
        "    for w in ('lm_new.short_chat', 'gpt2_large.chat_saturated')]))\n")
    assert lm["correct"] is True and lm["failed"] == 0
    assert lm["metrics"]["mfu.generate.sat"]["value"] > 0
    assert broken["correct"] is False
    assert "no value for ['MLP_RATIO']" in unset["refused"]
    assert rooflines[0] == rooflines[1] and rooflines[0] > 0


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
