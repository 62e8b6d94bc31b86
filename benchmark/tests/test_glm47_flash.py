"""What PR 34 added to the yardstick, on the CPU: the operation counts of
the `glm4_moe_lite` decode round and prefill chunk against the issue's
arithmetic and against a count of the tiny tree's leaves, the template as
the configuration renders it, its weights against the reference's, the
configuration against the catalog's row, and the new readers on runs that
have nothing for them and on reckoned records. The cell's rehearsal and its
two faults run with every other cell's (test_benchmark.py takes its cells
from BENCHMARK.json)."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from benchmark import harness, serving
from benchmark.ops import glm4_moe_lite_decode_round as ops
from benchmark.ops import glm4_moe_lite_prefill_chunk as chunk_ops
from benchmark.tests import tiny
from benchmark.tests.test_benchmark import _template

CELL = "glm47_flash_30b_ep2.long_prompt_saturated_s16"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("prefill_device_share.sat", "prefill_chunk_roofline",
       "decode_latent_share.sat", "prefill_chunks_per_request.sat")


def test_the_decode_round_counts_what_the_issue_counts():
    cfg = harness.load_cell(CELL)["config_data"]
    z = ops.sizes(cfg)
    assert round(z["l_matrix"] / 1e4) == 2176          # 21.76 M a layer
    assert round(ops.parameters(cfg) / 1e6) == 4068
    assert 20.2 < ops.experts_hit(cfg, 16) < 20.7      # of 32, uniform
    assert ops.experts_hit(cfg, 1) == pytest.approx(32 * 4 / 64)
    # a layer at 16 sequences of 8k tokens: the experts hit against the
    # latent attention's weights and rows
    experts = 2 * z["expert"] * ops.experts_hit(cfg, 16)
    rows = 2 * 576 * 16 * 8192
    assert 380e6 < experts < 392e6 and 150e6 < rows < 152e6
    assert 2 * z["l_matrix"] == pytest.approx(43.5e6, rel=0.01)
    live = 16 * 8192
    moved = ops.bytes_moved(cfg, 16, live)
    assert ops.bytes_moved(cfg, 16, live) - ops.bytes_moved(cfg, 16, 0) \
        == 12 * 1152 * live                            # 1,152 B a row a layer
    least, bound = ops.least_seconds(cfg, 16, live, V5E)
    assert bound == "memory" and least == pytest.approx(moved / 819e9)
    # with every expert hit, a round reads all that is held but the
    # embedding, of which it looks up a row a sequence
    every = ops.bytes_moved(cfg, 1e9, 0) - 1e9 * (12 * 1152 + 2 * 2048)
    held = ops.parameters(cfg) - cfg["vocab_size"] * cfg["hidden_size"]
    assert every == pytest.approx(2 * held, rel=0.003)
    # a token passes through 2 of its 4 experts here, in expectation
    whole = {**cfg, "expert_share": {"first": 0, "count": 64, "of": 64}}
    assert ops.flops_per_token(whole) - ops.flops_per_token(cfg) \
        == pytest.approx(11 * 2 * 2 * z["expert"])
    # the product over the rows, absorbed: 2 x (2 x 512 + 64) a head a row
    assert ops.flops(cfg, 16, live) - ops.flops(cfg, 16, 0) \
        == 12 * 20 * 2 * 1088 * live


def test_the_prefill_chunk_takes_the_cheaper_form_and_reads_every_expert():
    cfg = harness.load_cell(CELL)["config_data"]
    z = ops.sizes(cfg)
    pairs = lambda t, c: t * c + t * (t + 1) / 2
    # 512 queries: expanded (the rows' keys and values made once)
    want = 12 * 20 * (2 * 512 * pairs(512, 7168)
                      + 2 * 512 * 448 * (7168 + 512))
    assert chunk_ops.attention_flops(cfg, 512, 7168) == pytest.approx(want)
    # one query: absorbed
    assert chunk_ops.attention_flops(cfg, 1, 7168) == pytest.approx(
        12 * 20 * 2 * 1088 * pairs(1, 7168))
    assert ops.experts_hit(cfg, 512) > 31.99
    # an empty pool: memory binds (the weights once); a long one: compute
    assert chunk_ops.least_seconds(cfg, 512, 0, V5E)[1] == "memory"
    assert chunk_ops.least_seconds(cfg, 512, 15872 - 512, V5E)[1] \
        == "compute"
    # the head is the chunk's last token's alone
    per_token = ops.flops_per_token(cfg) - 2 * z["vocab"] * z["d"]
    assert chunk_ops.flops(cfg, 512, 0) == pytest.approx(
        512 * per_token + 2 * z["vocab"] * z["d"]
        + chunk_ops.attention_flops(cfg, 512, 0))
    # a prompt of 7,590 tokens is 15 chunks: its operations from the counts
    whole = sum(chunk_ops.flops(cfg, 512, 512 * i) for i in range(15))
    assert 20e12 < whole < 30e12


def test_the_counts_are_the_tiny_trees_leaves_and_the_references(tmp_path):
    """`parameters()` against a count of the leaves the template makes at
    the tiny size, and every leaf against the reference's recipe: an MLP's
    and an expert's `W_gate` and `W_up` lie side by side."""
    import jax

    from benchmark.reference import glm4_moe_lite as reference

    cell = tiny.cell(CELL)
    cfg = cell["config_data"]
    tmpl = _template(cell["config"], serving.template_values(
        cfg, cell["traffic_data"], 9), tmp_path)
    params = tmpl.make_params(jax.random.key(9))
    leaves = jax.tree.leaves(params)
    assert sum(a.size for a in leaves) == ops.parameters(cfg)
    assert tmpl.CFG.pattern == "LFLELE" and tmpl.CFG.mla.row == 24
    assert tmpl.PREFILL_BUCKETS == (128,)
    w = reference.make_weights(9, cfg)
    same = lambda a, b: np.array_equal(np.asarray(a), np.asarray(b))
    attn, dense, experts = (params["layers"][k] for k in ("00", "01", "03"))
    ref0, ref1 = w["layers"][0], w["layers"][1]
    for name in ("w_dq", "w_uq", "w_dkv", "w_ukv", "wo"):
        assert same(attn[name], ref0[name]), name
    assert same(attn["norm"]["scale"], ref0["norm1"])
    assert same(attn["kv_norm"]["scale"], ref0["kv_norm"])
    assert same(dense["w_up"][..., :96], ref0["w_gate"])
    assert same(dense["w_up"][..., 96:], ref0["w_up"])
    assert same(dense["norm"]["scale"], ref0["norm2"])
    assert same(experts["w_up"][..., :32], ref1["w_gate"])
    assert same(experts["s_up"][..., 32:], ref1["s_up"])
    assert same(experts["b_corr"], ref1["b_corr"]) \
        and "s_gate" not in experts
    assert same(params["head"], w["top"]["head"])
    assert same(params["norm_f"]["scale"], w["top"]["norm_f"])


def test_the_template_renders_at_the_published_widths(tmp_path):
    cell = harness.load_cell(CELL)
    cfg = cell["config_data"]
    values = serving.template_values(cfg, cell["traffic_data"], 7)
    with open(harness.render_template(cell["config"], values,
                                      str(tmp_path))) as f:
        lines = [ln for ln in f.read().split("\n") if ln.endswith("# @cell")]
    assert lines == [f"{k} = {v!r}  # @cell" for k, v in [
        ("SEED", 7), ("VOCAB", 77440), ("MAX_CONTEXT", 16384), ("DIM", 2048),
        ("LAYERS", 12), ("DENSE_LAYERS", 1), ("EPS", 1e-05), ("HEADS", 20),
        ("Q_RANK", 768), ("KV_RANK", 512), ("NOPE_DIM", 192),
        ("ROPE_DIM", 64), ("ROTARY_FACTOR", 1), ("V_DIM", 256),
        ("THETA", 1000000), ("DENSE_FFN", 10240), ("EXPERTS", 64),
        ("HELD_FIRST", 0), ("HELD", 32), ("TOP_K", 4), ("FFN", 1536),
        ("SHARED_EXPERTS", 1), ("ROUTE_SCALE", 1.8), ("FAULT", "")]]
    assert set(cfg["reduced"]) == set(cfg["published"]) \
        == set(cfg["why_reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings", "num_nextn_predict_layers"}
    assert cfg["expert_share"] == {
        "first": 0, "count": cfg["n_routed_experts"],
        "of": cfg["published"]["n_routed_experts"]}
    assert "four pipeline stages of two chips" in cfg["deployment"]
    entry = next(c for c in harness.load_benchmark()["configs"]
                 if c["name"] == cell["config"])
    assert entry["reduced"] == cfg["reduced"]
    # the traffic's longest prompt and answer are the served context
    traffic = cell["traffic_data"]
    assert traffic["prompt_tokens"]["max"] + traffic["answer_tokens"]["max"] \
        == cfg["max_position_embeddings"]
    env = traffic["settings"]["env"]
    assert int(env["RAFIKI_GEN_KV_POOL_BLOCKS"]) * 16 \
        == int(env["RAFIKI_GEN_MAX_SLOTS"]) * cfg["max_position_embeddings"]


def test_every_number_of_the_catalogs_row_is_in_the_file():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG, encoding="utf-8") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["name"] == "GLM-4.7-Flash")
    cfg = harness.load_cell(CELL)["config_data"]
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] < value
        else:
            assert cfg[key] == value, key


def test_the_new_readers_find_nothing_on_a_run_without_their_sources():
    """A program without the scope, the counters and the span's attributes
    (the parent's, another model's: the recorded trace ran under no scope
    at all), or a run with no trace: each reader returns None and raises
    nothing."""
    from rafiki_tpu.utils.metrics import REGISTRY

    cell = harness.load_cell(CELL)
    path = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")
    for name in NEW:
        reader = harness.load_by_name("layer_metrics", name)
        if name.startswith("prefill_chunks_per_request") \
                and REGISTRY.get("rafiki_gen_prefill_chunks_total"):
            continue  # a worker has run in this process
        for result in ({"trace": None, "records": [], "t0": 0.0, "t1": 1.0},
                       {"trace": {"path": path, "t0": 0.0, "window_s": 1.0},
                        "records": [{"i": 0}], "t0": 0.0, "t1": 1.0}):
            assert reader.read(result, cell, V5E) is None, name
    latent = harness.load_by_name("layer_metrics", "decode_latent_share.sat")
    assert latent._in_scope("jit(f)/while/body/latent/dot_general:")
    assert not latent._in_scope("jit(f)/moe/latent_rows/mul:")
    # an operation goes to its innermost scope, as `_scopes` has it
    assert not latent._in_scope("jit(f)/latent/moe/mul")
    assert latent._in_scope("jit(f)/moe/latent/mul")


def test_the_new_readers_on_reckoned_records(monkeypatch):
    from benchmark.layer_metrics import _scopes

    cell = harness.load_cell(CELL)
    jit = "jit(paged_decode_round)"
    device = [(f"{jit}/latent/dot_general:", 0, 30),
              (f"{jit}/moe/dot_general:", 30, 90),
              (f"{jit}/mlp/dot_general:", 90, 100),
              ("jit(paged_prefill_chunk)/latent/mul", 100, 500)]
    monkeypatch.setattr(_scopes, "device_ops", lambda path: [device])
    latent = harness.load_by_name("layer_metrics", "decode_latent_share.sat")
    assert latent.read({"trace": {"path": "x"}}, cell, V5E) == 30.0

    reduced = {"busy_s": 4.0, "n_devices": 1, "window_s": 5.0,
               "module_s": {"jit_paged_prefill_chunk": 2.4,
                            "jit_paged_decode_round": 1.5},
               "module_runs": {"jit_paged_prefill_chunk": 60,
                               "jit_paged_decode_round": 50}}
    result = {"trace": {"path": "x"}, "_reduced": reduced}
    share = harness.load_by_name("layer_metrics", "prefill_device_share.sat")
    assert share.read(result, cell, V5E) == pytest.approx(60.0)
    roofline = harness.load_by_name("layer_metrics", "prefill_chunk_roofline")
    monkeypatch.setattr(roofline, "chunk_indices", lambda path: [0, 7, 14])
    least, bound = chunk_ops.least_seconds(cell["config_data"], 512,
                                           512 * 7, V5E)
    got = roofline.read(result, cell, V5E)
    assert got == pytest.approx(100 * least / 0.04) and 0 < got < 100
    assert result["check_info"]["prefill_chunk_context_mean"] == 3584
    assert result["check_info"]["prefill_chunk_bound"] == bound
    monkeypatch.setattr(roofline, "chunk_indices", lambda path: [])
    assert roofline.read(result, cell, V5E) is None   # the parent's spans

    per_request = harness.load_by_name("layer_metrics",
                                       "prefill_chunks_per_request.sat")
    totals = {"rafiki_gen_prefill_chunks_total": 300.0,
              "rafiki_gen_prefix_hits_total": 0.0,
              "rafiki_gen_prefix_misses_total": 20.0,
              "rafiki_gen_prefill_chunk_tokens_total": 150000.0}
    monkeypatch.setattr(serving, "_registry_total",
                        lambda name: totals.get(name, 0.0))
    result = {"records": [{"i": 0}]}
    assert per_request.read(result, cell, V5E) == 15.0
    assert result["check_info"]["prefill_chunk_tokens_mean"] == 500.0


def test_the_cell_reports_the_generate_cells_readers_and_its_own():
    """Containment, not a closed set: a later PR adds a reader for this cell
    as files and entries of its own and edits nothing here. What misreads
    multi-chunk traffic, and what another cell's test pins, is not
    reported."""
    bench = harness.load_benchmark()
    mine = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert {"slots_busy_mean.sat", "preemptions.sat", "mfu.generate.sat",
            "decode_step_roofline", "device_idle_share.sat",
            "decode_host_share.sat", "admission_wait_s.sat",
            "idle_named_share.sat"} | set(NEW) <= mine
    assert not mine & {"decode_round_ms.sat", "prefill_share.sat",
                       "decode_moe_share.sat", "decode_ssm_share.sat",
                       "experts_hit_mean.sat", "state_resets_per_s.sat"}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["tokens_per_s"]["workloads"]
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"


def test_routed_choices_of_the_reference_and_its_controls():
    from benchmark.reference import glm4_moe_lite as reference

    cfg = tiny.cell(CELL)["config_data"]
    w = reference.make_weights(3, cfg)
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"],
                                            size=(2, 64)).astype(np.int32)
    plain = reference.routed_choices(w, ids, cfg)
    assert len(plain) == cfg["num_hidden_layers"] \
        - cfg["first_k_dense_replace"]
    assert plain[0].shape == (2, 64, cfg["num_experts_per_tok"])
    moved = {}
    for name in ("bf16", "int8w"):
        other = reference.routed_choices(reference.at_precision(w, name),
                                         ids, cfg)
        moved[name] = np.mean([np.mean(np.sort(a) != np.sort(b))
                               for a, b in zip(plain, other)])
    assert 0.0 <= moved["bf16"] <= moved["int8w"] < 0.5
    assert moved["int8w"] > 0.0
    with pytest.raises(ValueError):
        reference.at_precision(w, "fp4")
