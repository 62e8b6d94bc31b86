"""The readers of the set-up (the program's compile record and its `setup.*`
spans), run with `python -m pytest benchmark/tests`: each on a record
reckoned by hand, and on a program without the record (the parent's), where
each returns nothing and raises nothing."""

from __future__ import annotations

import pytest

from benchmark import harness
from benchmark.tests import tiny

NEW = ("setup_load_s", "setup_trace_lower_s", "setup_xla_compile_s",
       "setup_cache_load_s")
T0, T1 = 1000.0, 1051.0
SERVING = {"gpt2_large.chat_saturated",
           "nemotron3_nano_30b_ep2.chat_saturated_s16",
           "qwen3_next_80b_ep4.chat_saturated_s32",
           "glm47_flash_30b_ep2.long_prompt_saturated_s16"}


def read(name: str, result: dict):
    reader = harness.load_by_name("layer_metrics", name)
    return reader.read(result, harness.load_cell(
        "glm47_flash_30b_ep2.long_prompt_saturated_s16"), tiny.CPU_PEAKS)


def event(end, stage, seconds, fun_name="jit(f)", phase="setup.warmup"):
    return {"end": end, "stage": stage, "seconds": seconds,
            "fun_name": fun_name, "phase": phase, "thread": "svc-1"}


RECORD = [
    event(10.0, "trace", 1.0), event(11.0, "lower", 2.0),
    event(20.0, "compile", 0.5, "jit(small)"),
    event(30.0, "load", 4.0, "jit(paged_decode_round)", "setup.rung"),
    event(40.0, "trace", 3.0, "paged_prefill_chunk", "gen.prefill_chunk"),
    event(52.0, "lower", 8.0, "jit(paged_prefill_chunk)",
          "gen.prefill_chunk"),
    event(60.0, "compile", 30.0, "jit(paged_prefill_chunk)",
          "gen.prefill_chunk"),
    # the window: nothing of it is set-up
    event(T0 + 5, "compile", 100.0), event(T0 + 6, "load", 100.0),
    event(T0 + 7, "trace", 100.0),
    # after the window: the reference's programs
    event(T1 + 3, "trace", 0.25, "<lambda>", "none"),
    event(T1 + 9, "compile", 6.0, "jit(<lambda>)", "none"),
]


@pytest.fixture
def program(monkeypatch):
    """The program's record and histogram, as a run would leave them."""
    from rafiki_tpu.sdk import compile_cache
    from rafiki_tpu.utils import metrics

    registry = metrics.Registry()
    hist = registry.histogram("rafiki_worker_phase_seconds", "", ("phase",))
    for phase, seconds in (("setup.load", 33.5), ("setup.pool", 1.25),
                           ("setup.rung", 4.5), ("gen.admit", 9.0)):
        hist.labels(phase).observe(seconds)
    monkeypatch.setattr(metrics, "REGISTRY", registry)
    monkeypatch.setattr(compile_cache, "events", lambda: list(RECORD),
                        raising=False)
    return compile_cache


def test_readers_on_a_reckoned_record(program):
    result = {"t0": T0, "t1": T1, "setup_s": 120.0}
    assert read("setup_load_s", result) == 33.5
    assert read("setup_trace_lower_s", result) == 1.0 + 2.0 + 3.0 + 8.0
    assert read("setup_xla_compile_s", result) == 0.5 + 30.0
    assert read("setup_cache_load_s", result) == 4.0
    info = result["check_info"]
    assert info["setup_compiled"] == [["jit(paged_prefill_chunk)", 30.0],
                                      ["jit(small)", 0.5]]
    assert info["setup_loaded"] == 1
    assert info["setup_compile_by_phase"] == {
        "gen.prefill_chunk": 41.0, "setup.warmup": 3.5, "setup.rung": 4.0}
    assert list(info["setup_compile_by_phase"])[0] == "gen.prefill_chunk"
    assert info["setup_phase_s"] == {"setup.load": 33.5, "setup.pool": 1.25,
                                     "setup.rung": 4.5}
    assert info["check_compile_s"] == 6.25


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_record_reads_nothing(name, monkeypatch):
    from rafiki_tpu.sdk import compile_cache

    monkeypatch.delattr(compile_cache, "events", raising=False)
    result = {"t0": T0, "t1": T1, "setup_s": 120.0}
    assert read(name, result) is None
    assert "check_info" not in result


@pytest.mark.parametrize("name", NEW)
def test_a_record_with_nothing_before_the_window_reads_nothing(
        name, program):
    assert read(name, {"t0": 5.0, "t1": 56.0, "setup_s": 3.0}) is None


def test_every_new_metric_lists_cells_that_report_setup_s():
    bench = harness.load_benchmark()
    cells = {w["name"] for w in bench["workloads"]}
    setup = {m["name"]: m for m in bench["end_to_end"]}["setup_s"]
    reports = set(setup.get("workloads", cells))
    new = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert set(new) == set(NEW)
    for m in new.values():
        assert m["moves"] == "setup_s" and m["unit"] == "s"
        assert m["better"] == "lower"
        # several cells each, so no closed set of one cell's metrics moves
        assert len(m["workloads"]) > 1
        assert set(m["workloads"]) <= reports
    # containment, not a closed set: a later cell may list itself too
    assert SERVING <= set(new["setup_load_s"]["workloads"])
    for name in NEW[1:]:
        assert SERVING | {"vit_b16.hpo_search"} <= set(new[name]["workloads"])
