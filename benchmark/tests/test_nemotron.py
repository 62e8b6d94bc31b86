"""What PR 27 added to the yardstick, on the CPU: the operation counts of
the hybrid decode round against the issue's arithmetic, the reader of named
scopes on the recorded trace, the new readers on a run that has nothing for
them, and the template as the configuration renders it."""

from __future__ import annotations

import os

import pytest

from benchmark import harness, serving
from benchmark.layer_metrics import _scopes
from benchmark.ops import nemotron_h_decode_round as ops

CELL = "nemotron3_nano_30b_ep2.chat_saturated_s16"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_decode_round_counts_what_the_issue_counts():
    cfg = harness.load_cell(CELL)["config_data"]
    assert round(ops.parameters(cfg) / 1e6) == 4585
    assert 34.0 < ops.experts_hit(cfg, 16) < 34.6      # of 64, uniform
    assert ops.experts_hit(cfg, 1) == pytest.approx(64 * 6 / 128)
    moved = ops.bytes_moved(cfg, 16, 16 * 80)
    assert 5.5e9 < moved < 5.8e9
    every = ops.bytes_moved(cfg, 1e9, 0) - 2 * 4 * 1e9 * (
        64 * 64 * 128 + 3 * 6144) * 6 - 2 * 1e9 * (2 * 256 * 2 + 2688)
    # with every expert hit, a round reads all that is held but the
    # embedding, of which it looks up a row a sequence
    held = ops.parameters(cfg) - cfg["vocab_size"] * cfg["hidden_size"]
    assert every == pytest.approx(2 * held, rel=0.002)
    least, bound = ops.least_seconds(cfg, 16, 16 * 80, V5E)
    assert bound == "memory" and 6.5e-3 < least < 7.2e-3
    # a token passes through 3 of its 6 experts here, in expectation
    one_expert = 2 * 2 * 2688 * 1856
    cfg6 = {**cfg, "expert_share": {"first": 0, "count": 128, "of": 128}}
    assert ops.flops_per_token(cfg6) - ops.flops_per_token(cfg) \
        == pytest.approx(6 * 3 * one_expert)


def test_named_scopes_are_read_from_the_trace_file():
    path = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")
    (ops_line,) = _scopes.device_ops(path)
    named = [op for op in ops_line if op[0]]
    assert len(ops_line) == 9 and len(named) == 3
    assert all(name == "jit(<lambda>)/dot_general:" and end > start
               for name, start, end in named)
    assert _scopes._scope("jit(f)/while/body/moe/while/body/dot_general") \
        == "moe"
    assert _scopes._scope("jit(f)/ssm/mul:") == "ssm"
    assert _scopes._scope("jit(f)/attention/mul") == "other"
    # the recorded program ran under none of the scopes: nothing to report
    result = {"trace": {"path": path}}
    assert _scopes.scope_seconds(result, "<lambda>") is None
    assert _scopes.scope_share(result, "<lambda>", "moe") is None


def test_the_new_readers_find_nothing_on_a_run_without_their_sources():
    """A program without the scopes and counters (the parent), or a run
    with no trace: each reader returns None and raises nothing."""
    from rafiki_tpu.utils.metrics import REGISTRY

    bench = harness.load_benchmark()
    new = [m for m in bench["per_layer"] if m["workloads"] == [CELL]]
    assert {m["name"] for m in new} == {
        "decode_moe_share.sat", "decode_ssm_share.sat",
        "experts_hit_mean.sat", "state_resets_per_s.sat"}
    if REGISTRY.get("rafiki_gen_experts_hit_total") is not None:
        pytest.skip("a worker has run in this process")
    result = {"trace": None, "records": [], "t0": 0.0, "t1": 1.0}
    for m in new:
        reader = harness.load_by_name("layer_metrics", m["name"])
        assert reader.read(result, {}, V5E) is None


def test_the_template_renders_at_the_published_widths(tmp_path):
    cell = harness.load_cell(CELL)
    cfg = cell["config_data"]
    values = serving.template_values(cfg, cell["traffic_data"], 7)
    with open(harness.render_template(cell["config"], values,
                                      str(tmp_path))) as f:
        lines = [ln for ln in f.read().split("\n") if ln.endswith("# @cell")]
    assert lines == [f"{k} = {v!r}  # @cell" for k, v in [
        ("SEED", 7), ("VOCAB", 65536), ("MAX_CONTEXT", 4096), ("DIM", 2688),
        ("PATTERN", "MEMEM*EMEMEM*E"), ("M_HEADS", 64), ("M_HEAD_DIM", 64),
        ("GROUPS", 8), ("STATE", 128), ("CONV", 4), ("CHUNK", 128),
        ("Q_HEADS", 32), ("KV_HEADS", 2), ("HEAD_DIM", 128),
        ("EXPERTS", 128), ("HELD_FIRST", 0), ("HELD", 64), ("TOP_K", 6),
        ("FFN", 1856), ("SHARED_FFN", 3712), ("SCALE", 2.5), ("FAULT", "")]]
    # every number of the catalog's row is in the file under its key, and
    # what differs is listed
    assert set(cfg["reduced"]) == set(cfg["published"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "max_position_embeddings"}
    assert cfg["published"]["hybrid_override_pattern"].startswith(
        cfg["hybrid_override_pattern"])
    assert len(cfg["hybrid_override_pattern"]) == cfg["num_hidden_layers"]
    assert cfg["expert_share"]["count"] == cfg["n_routed_experts"]


def test_routed_choices_of_the_reference_and_its_controls():
    """What `benchmark/routed_choices.py` reads on the chip, at a tiny size:
    top-k expert ids a token an expert layer; a control that rounds more
    moves more of them."""
    import numpy as np

    from benchmark.reference import nemotron_h as reference
    from benchmark.tests import tiny

    cfg = tiny.cell(CELL)["config_data"]
    w = reference.make_weights(3, cfg)
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"],
                                            size=(2, 64)).astype(np.int32)
    plain = reference.routed_choices(w, ids, cfg)
    assert len(plain) == cfg["hybrid_override_pattern"].count("E")
    assert plain[0].shape == (2, 64, cfg["num_experts_per_tok"])
    moved = {}
    for name in ("bf16", "int8w"):
        other = reference.routed_choices(reference.at_precision(w, name),
                                         ids, cfg)
        moved[name] = np.mean([np.mean(np.sort(a) != np.sort(b))
                               for a, b in zip(plain, other)])
    assert 0.0 <= moved["bf16"] <= moved["int8w"] < 0.5
    assert moved["int8w"] > 0.0
