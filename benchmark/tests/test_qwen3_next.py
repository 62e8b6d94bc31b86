"""What PR 32 added to the yardstick, on the CPU: the operation counts of
the `qwen3_next` decode round against the issue's arithmetic and against a
count of the tiny tree's leaves, the template as the configuration renders
it, its weights against the reference's, the configuration against the
catalog's row, and the new reader on a run that has nothing for it. The
cell's rehearsal and its two faults run with every other cell's
(test_benchmark.py takes its cells from BENCHMARK.json)."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from benchmark import harness, serving
from benchmark.ops import qwen3_next_decode_round as ops
from benchmark.tests import tiny
from benchmark.tests.test_benchmark import _template

CELL = "qwen3_next_80b_ep4.chat_saturated_s32"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_decode_round_counts_what_the_issue_counts():
    cfg = harness.load_cell(CELL)["config_data"]
    assert round(ops.parameters(cfg) / 1e6) == 3667
    assert 59.5 < ops.experts_hit(cfg, 32) < 60.3      # of 128, uniform
    assert ops.experts_hit(cfg, 1) == pytest.approx(128 * 10 / 512)
    moved = ops.bytes_moved(cfg, 32, 32 * 80)
    assert 4.5e9 < moved < 4.75e9
    state = 6 * 32 * 2 * 4 * (32 * 128 * 128 + 3 * 8192)
    assert 0.83e9 < state < 0.85e9                     # read and written
    assert ops.bytes_moved(cfg, 32, 0) - ops.bytes_moved(cfg, 0, 0) \
        > state                                        # and the experts hit
    every = ops.bytes_moved(cfg, 1e9, 0) - 2 * 4 * 1e9 * (
        32 * 128 * 128 + 3 * 8192) * 6 - 2 * 1e9 * (2 * 512 * 2 + 2048)
    # with every expert hit, a round reads all that is held but the
    # embedding, of which it looks up a row a sequence
    held = ops.parameters(cfg) - cfg["vocab_size"] * cfg["hidden_size"]
    assert every == pytest.approx(2 * held, rel=0.003)
    least, bound = ops.least_seconds(cfg, 32, 32 * 80, V5E)
    assert bound == "memory" and 5.4e-3 < least < 5.9e-3
    # a token passes through 2.5 of its 10 experts here, in expectation
    one_expert = 2 * 3 * 2048 * 512
    cfg4 = {**cfg, "expert_share": {"first": 0, "count": 512, "of": 512}}
    assert ops.flops_per_token(cfg4) - ops.flops_per_token(cfg) \
        == pytest.approx(8 * 7.5 * one_expert)


def test_the_counts_are_the_tiny_trees_leaves_and_the_references(tmp_path):
    """`parameters()` against a count of the leaves the template makes at
    the tiny size, and every leaf against the reference's recipe: a norm
    holds `1 + w`, an expert's `W_gate` and `W_up` lie side by side."""
    import jax

    from benchmark.reference import qwen3_next as reference

    cell = tiny.cell(CELL)
    cfg = cell["config_data"]
    tmpl = _template(cell["config"], serving.template_values(
        cfg, cell["traffic_data"], 9), tmp_path)
    params = tmpl.make_params(jax.random.key(9))
    leaves = jax.tree.leaves(params)
    assert sum(a.size for a in leaves) == ops.parameters(cfg)
    assert tmpl.CFG.pattern == "DEDEDEGE" and tmpl.CFG.rotary_dim == 4
    w = reference.make_weights(9, cfg)
    same = lambda a, b: np.array_equal(np.asarray(a), np.asarray(b))
    first, experts, attn = (params["layers"][k] for k in ("00", "01", "06"))
    ref0, ref3 = w["layers"][0], w["layers"][3]
    assert same(first["w_qkvz"], ref0["w_qkvz"])
    assert same(first["norm"]["scale"], 1.0 + ref0["norm1"])
    assert same(first["onorm"], ref0["onorm"])
    assert same(experts["w_up"][..., :32], ref0["w_gate"])
    assert same(experts["w_up"][..., 32:], ref0["w_up"])
    assert same(experts["s_up"][..., 32:], ref0["s_up"])
    assert same(experts["s_gate"], ref0["s_w"]) and "b_corr" not in experts
    assert same(attn["wq"], ref3["wq"])
    assert same(attn["q_norm"]["scale"], 1.0 + ref3["q_norm"])
    assert same(params["head"], w["top"]["head"])
    assert same(params["norm_f"]["scale"], 1.0 + w["top"]["norm_f"])


def test_the_template_renders_at_the_published_widths(tmp_path):
    cell = harness.load_cell(CELL)
    cfg = cell["config_data"]
    values = serving.template_values(cfg, cell["traffic_data"], 7)
    with open(harness.render_template(cell["config"], values,
                                      str(tmp_path))) as f:
        lines = [ln for ln in f.read().split("\n") if ln.endswith("# @cell")]
    assert lines == [f"{k} = {v!r}  # @cell" for k, v in [
        ("SEED", 7), ("VOCAB", 37984), ("MAX_CONTEXT", 4096), ("DIM", 2048),
        ("LAYERS", 8), ("ATTN_EVERY", 4), ("EPS", 1e-06), ("K_HEADS", 16),
        ("K_DIM", 128), ("V_HEADS", 32), ("V_DIM", 128), ("CONV", 4),
        ("CHUNK", 64), ("Q_HEADS", 16), ("KV_HEADS", 2), ("HEAD_DIM", 256),
        ("ROTARY_FACTOR", 0.25), ("THETA", 10000000), ("EXPERTS", 512),
        ("HELD_FIRST", 0), ("HELD", 128), ("TOP_K", 10), ("FFN", 512),
        ("SHARED_FFN", 512), ("FAULT", "")]]
    assert set(cfg["reduced"]) == set(cfg["published"]) \
        == set(cfg["why_reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size",
        "max_position_embeddings"}
    assert cfg["expert_share"] == {"first": 0, "count": cfg["num_experts"],
                                   "of": cfg["published"]["num_experts"]}
    assert cfg["num_hidden_layers"] % cfg["full_attention_interval"] == 0
    entry = next(c for c in harness.load_benchmark()["configs"]
                 if c["name"] == cell["config"])
    assert entry["reduced"] == cfg["reduced"]


def test_every_number_of_the_catalogs_row_is_in_the_file():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows
               if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    cfg = harness.load_cell(CELL)["config_data"]
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] < value
        else:
            assert cfg[key] == value, key


def test_the_new_reader_finds_nothing_on_a_run_without_its_scope():
    """A program without the `delta` scope (the parent's, another model's:
    the recorded trace ran under no scope at all), or a run with no trace:
    the reader returns None and raises nothing."""
    reader = harness.load_by_name("layer_metrics", "decode_delta_share.sat")
    assert reader.read({"trace": None}, {}, V5E) is None
    path = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")
    assert reader.read({"trace": {"path": path}}, {}, V5E) is None
    assert reader._in_scope("jit(f)/while/body/delta/while/body/mul")
    assert not reader._in_scope("jit(f)/moe/delta_rule/mul:")
    # an operation goes to its innermost scope, as `_scopes` has it
    assert not reader._in_scope("jit(f)/delta/moe/mul")
    assert reader._in_scope("jit(f)/moe/delta/mul")


def test_the_new_reader_gives_each_instant_to_the_innermost_operation(
        monkeypatch):
    from benchmark.layer_metrics import _scopes

    reader = harness.load_by_name("layer_metrics", "decode_delta_share.sat")
    jit = "jit(paged_decode_round)"
    ops = [(f"{jit}/while", 0, 100),              # holds the three below
           (f"{jit}/while/body/delta/dot_general:", 10, 40),
           (f"{jit}/while/body/moe/dot_general:", 50, 90),
           (f"{jit}/while/body/delta/while", 90, 100),
           (f"{jit}/while/body/delta/while/body/mul", 92, 97),
           ("jit(paged_prefill_chunk)/delta/mul", 100, 500),
           ("", 500, 600)]
    monkeypatch.setattr(_scopes, "device_ops", lambda path: [ops, ops])
    assert reader.read({"trace": {"path": "x"}}, {}, V5E) == 40.0


def test_the_cell_reports_the_generate_cells_readers_and_its_own():
    """Containment, not a closed set: a later PR adds a reader for this cell
    as files and entries of its own and edits nothing here."""
    bench = harness.load_benchmark()
    mine = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    shared = {m["name"] for m in bench["per_layer"]
              if "gpt2_large.chat_saturated" in m["workloads"]
              and m["name"].endswith(".sat")}
    assert shared and shared <= mine
    assert "decode_delta_share.sat" in mine


def test_routed_choices_of_the_reference_and_its_controls():
    from benchmark.reference import qwen3_next as reference

    cfg = tiny.cell(CELL)["config_data"]
    w = reference.make_weights(3, cfg)
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"],
                                            size=(2, 64)).astype(np.int32)
    plain = reference.routed_choices(w, ids, cfg)
    assert len(plain) == cfg["num_hidden_layers"]
    assert plain[0].shape == (2, 64, cfg["num_experts_per_tok"])
    moved = {}
    for name in ("bf16", "int8w"):
        other = reference.routed_choices(reference.at_precision(w, name),
                                         ids, cfg)
        moved[name] = np.mean([np.mean(np.sort(a) != np.sort(b))
                               for a, b in zip(plain, other)])
    assert 0.0 <= moved["bf16"] <= moved["int8w"] < 0.5
    assert moved["int8w"] > 0.0
