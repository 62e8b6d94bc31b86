"""The readers of the program's own spans (PR 24), run with
`python -m pytest benchmark/tests`: each on a record reckoned by hand, on a
trace made here with the program's span primitive, and on records without
the spans (the parent's), where each returns nothing and raises nothing."""

from __future__ import annotations

import os
import threading
import time

import pytest

from benchmark import harness
from benchmark.layer_metrics import _spans
from benchmark.tests import tiny

MS = 1_000_000
TRAIN, SAT = "vit_b16.hpo_search", "gpt2_large.chat_saturated"
SMALL = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def read(name: str, result: dict, workload: str):
    reader = harness.load_by_name("layer_metrics", name)
    return reader.read(result, harness.load_cell(workload), tiny.CPU_PEAKS)


def new_metrics(workload: str) -> list:
    """The per-layer metrics of `workload` that PR 24 appended."""
    names = [m["name"] for m in harness.load_cell(workload)["per_layer"]]
    return names[names.index({TRAIN: "persist_dump_s",
                              SAT: "decode_round_ms.sat"}[workload]):]


def ms(*spans):
    return [(name, a * MS, b * MS) for name, a, b in spans]


def sat_result() -> dict:
    """Two iterations of the serve loop, the second after 1 ms that no span
    names; the device busy inside the prefill and the two decode calls."""
    host = ms(("gen.admit", 0, 10), ("gen.prefill_chunk", 2, 8),
              ("gen.bookkeep", 10, 11), ("gen.decode.build", 11, 12),
              ("gen.decode.device", 12, 92), ("gen.decode.post", 92, 94),
              ("gen.admit", 95, 96), ("gen.bookkeep", 96, 97),
              ("gen.decode.build", 97, 98), ("gen.decode.device", 98, 178),
              ("gen.decode.post", 178, 180),
              ("PjitFunction(paged_decode_round)", 12, 13),
              ("some.other.thread", 1, 150))
    ops = ms(("fusion.1", 3, 7), ("while.5", 13, 90), ("while.5", 98, 175))
    return {"t0": 0.0, "t1": 51.0, "records": [{"i": 0}],
            "trace": {"path": "synthetic", "t0": 0.0, "window_s": 0.2},
            "_planes": {"host": host, "planes": [],
                        "devices": {0: {"ops": ops, "modules": []}}}}


def test_serve_loop_readers_on_a_reckoned_record():
    r = sat_result()
    # self times: admit 10 - 6 + 1, prefill 6, bookkeep 2, build 2, device
    # 160, post 4, over the 180 ms from the first span to the last
    assert read("decode_round_ms.sat", r, SAT) == pytest.approx(166 / 2)
    assert r["check_info"]["rounds"] == 2
    assert read("decode_host_share.sat", r, SAT) == pytest.approx(
        100 * (5 + 2 + 2 + 4) / 180)
    assert read("prefill_share.sat", r, SAT) == pytest.approx(100 * 6 / 180)
    assert r["check_info"]["gen_span_coverage"] == pytest.approx(179 / 180)
    # idle gaps [7, 13) and [90, 98): all but the millisecond no span names
    assert read("idle_named_share.sat", r, SAT) == pytest.approx(
        100 * 13 / 14)
    by_span = r["check_info"]["idle_by_span_s"]
    assert by_span["gen.decode.device"] == pytest.approx(0.003)
    assert by_span["gen.admit"] == pytest.approx(0.003)
    assert "some.other.thread" not in by_span  # only gen.* is the loop's
    assert sum(by_span.values()) == pytest.approx(0.013)


def train_result() -> dict:
    """One whole trial and the next one's start, around a trace that the
    harness anchored at 101.0 s on the host's clock: the first trial's
    `train` began before it."""
    span = lambda name, a, b, depth=0: {"name": name, "start": a, "end": b,
                                        "depth": depth}
    first = [span("propose", 99.99, 99.995), span("train", 100.1, 103.0),
             span("evaluate", 103.0, 103.1),
             span("persist_params", 103.1, 105.9),
             span("persist.dump", 103.1, 104.0, 1),
             span("persist.serialize", 104.0, 105.0, 1),
             span("persist.write", 105.0, 105.9, 1)]
    second = [span("train", 106.1, 109.0), span("evaluate", 109.0, 109.1)]
    ops = [("while.1", 0.1e9, 1.7e9), ("fusion.2", 1.76e9, 1.84e9),
           ("while.1", 5.05e9, 5.2e9)]
    trial = lambda spans, **kw: {"status": "COMPLETED", "spans": spans,
                                 "epochs": [], **kw}
    return {"t0": 99.0, "t1": 110.0,
            "trials": [trial(first, started=100.0, stopped=106.0),
                       trial(second, started=106.0, stopped=None,
                             status="RUNNING")],
            "trace": {"path": "synthetic", "t0": 101.0, "window_s": 7.0},
            "_planes": {"host": [("ThunkExecutor", 5e8, 6e8)], "planes": [],
                        "devices": {0: {"ops": ops, "modules": []}}}}


def test_train_worker_readers_on_a_reckoned_record():
    r = train_result()
    assert read("persist_dump_s", r, TRAIN) == pytest.approx(0.9)
    assert read("persist_serialize_s", r, TRAIN) == pytest.approx(1.0)
    assert read("persist_write_s", r, TRAIN) == pytest.approx(0.9)
    # a life of 6 s; train 2.9, evaluate 0.1, persist_params 2.8 name 5.8
    assert read("trial_unspanned_share", r, TRAIN) == pytest.approx(
        100 * 0.2 / 6.0)
    # gaps [1.7, 1.76) and [1.84, 5.05) on the trace's clock: train (to
    # 2.0), evaluate and the three persist steps (to 4.9) name all but
    # [4.9, 5.05); the next trial's train begins at 5.1
    assert read("idle_named_share.train", r, TRAIN) == pytest.approx(
        100 * (0.06 + 3.06) / (0.06 + 3.21))
    by_span = r["check_info"]["idle_by_span_s"]
    assert by_span["persist.serialize"] == pytest.approx(1.0)
    assert by_span["train"] == pytest.approx(0.06 + 0.16)
    assert "persist_params" not in by_span  # its steps cover it


def test_self_pieces_cut_children_out():
    pieces = _spans.self_pieces(ms(("a", 0, 10), ("b", 2, 4), ("c", 3, 4),
                                   ("b", 6, 12), ("d", 20, 21)))
    assert pieces == ms(("a", 0, 2), ("b", 2, 3), ("c", 3, 4), ("a", 4, 6),
                        ("b", 6, 10), ("d", 20, 21))
    assert _spans.self_seconds(ms(("a", 0, 10), ("b", 2, 4))) == {
        "a": pytest.approx(0.008), "b": pytest.approx(0.002)}


def test_readers_on_a_trace_made_with_the_programs_spans(tmp_path):
    """Two decode rounds through `rafiki_tpu.utils.trace.span` inside a
    profiler session on the CPU: the readers find them in the `.xplane.pb`
    by name. No device plane here, so nothing is said of its idle time."""
    import jax

    from rafiki_tpu.utils import trace

    def rounds():
        for _ in range(2):
            with trace.span("gen.admit"):
                time.sleep(0.001)
            with trace.span("gen.decode.build"):
                time.sleep(0.001)
            with trace.span("gen.decode.device"):
                time.sleep(0.006)
            with trace.span("gen.decode.post"):
                time.sleep(0.001)

    rounds()  # first use makes the histogram's children: not in the trace
    t0 = time.time()
    jax.profiler.start_trace(str(tmp_path))
    try:
        thread = threading.Thread(target=rounds)
        thread.start()
        thread.join(timeout=30)
    finally:
        jax.profiler.stop_trace()
    path = [os.path.join(base, n) for base, _, names in os.walk(tmp_path)
            for n in names if n.endswith(".xplane.pb")][0]
    r = {"t0": t0, "t1": t0 + 1, "records": [],
         "trace": {"path": path, "t0": t0, "window_s": 0.1}}
    assert 8.0 <= read("decode_round_ms.sat", r, SAT) < 20.0
    assert r["check_info"]["rounds"] == 2
    assert 15.0 < read("decode_host_share.sat", r, SAT) < 50.0
    assert r["check_info"]["gen_span_coverage"] > 0.9
    assert read("prefill_share.sat", r, SAT) is None  # no such span ran
    assert read("idle_named_share.sat", r, SAT) is None


@pytest.mark.parametrize("workload", [TRAIN, SAT])
def test_a_program_without_the_spans_reads_nothing(workload):
    """A trace with device operations and no annotation, trials that saved
    no span, no request through the door. Nothing, and no error."""
    r = {"t0": 0.0, "t1": 51.0, "records": [],
         "trials": [{"status": "COMPLETED", "started": 1.0, "stopped": 7.0,
                     "epochs": [], "spans": []},
                    {"status": "ERRORED", "started": 7.0, "stopped": 8.0,
                     "epochs": [], "spans": []}],
         "trace": {"path": SMALL, "t0": 0.5, "window_s": 1.0}}
    for name in new_metrics(workload):
        assert read(name, r, workload) is None, name
    assert _spans.named(r, "gen.") == [] and _spans.idle_gaps(r)
    assert _spans.serve_thread(r) is None


def test_the_parents_trial_spans_name_its_idle_too():
    """A program whose spans stop at `persist_params` (the parent of PR 24):
    the steps' readers find nothing, the idle is named by the whole span."""
    r = train_result()
    for t in r["trials"]:
        t["spans"] = [s for s in t["spans"] if s["depth"] == 0]
    for name in ("persist_dump_s", "persist_serialize_s", "persist_write_s"):
        assert read(name, r, TRAIN) is None
    assert read("idle_named_share.train", r, TRAIN) == pytest.approx(
        100 * 3.12 / 3.27)
    assert r["check_info"]["idle_by_span_s"]["persist_params"] \
        == pytest.approx(2.8)


def test_admission_wait_reads_the_doors_histogram():
    from rafiki_tpu.utils.metrics import REGISTRY

    hist = REGISTRY.histogram("rafiki_gen_door_ttft_seconds", "")
    before = hist.labels().snapshot()
    hist.observe(2.0)
    hist.observe(4.0)
    r = {"t0": 0.0, "t1": 51.0, "records": [{"i": 0}], "trace": None}
    assert read("admission_wait_s.sat", r, SAT) == pytest.approx(
        (before["sum"] + 6.0) / (before["count"] + 2))
    assert read("admission_wait_s.sat", {**r, "records": []}, SAT) is None
