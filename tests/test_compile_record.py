"""The span stack (`utils/trace.py`) and the compile record
(`sdk/compile_cache.py`): every compile in the process filed by stage under
the span open on its thread, the compile counters taken from it, and the
warm-up report's hits and misses read from it. CPU-only; the cache drills
opt the CPU backend in (RAFIKI_COMPILE_CACHE_CPU=1) with the min-compile-time
floor at 0 so every jit program round-trips the on-disk cache."""

import threading
import time

import numpy as np
import pytest

from rafiki_tpu.sdk import compile_cache
from rafiki_tpu.utils import trace
from rafiki_tpu.utils.metrics import REGISTRY
from rafiki_tpu.worker import warmup


def _reset():
    import jax

    compile_cache.reset_for_tests()
    warmup.reset_for_tests()
    jax.config.update("jax_compilation_cache_dir", None)
    jax.clear_caches()


@pytest.fixture(autouse=True)
def _clean_state():
    _reset()
    compile_cache.enable()  # the listeners, with or without the cache
    yield
    _reset()


@pytest.fixture
def cpu_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("RAFIKI_COMPILE_CACHE", "1")
    monkeypatch.setenv("RAFIKI_COMPILE_CACHE_CPU", "1")
    monkeypatch.setenv("RAFIKI_COMPILE_CACHE_MIN_COMPILE_S", "0")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("RAFIKI_COMPILE_CACHE_DIR", str(tmp_path / "xc"))
    _reset()
    assert compile_cache.enable() == str(tmp_path / "xc")
    return str(tmp_path / "xc")


def _program(scale: float):
    """A fresh jit wrapper (a new trace every time) of one fixed HLO."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def prog(v):
        return jnp.tanh(v @ v.T) * scale + 1.0

    return prog


X = np.linspace(0.0, 1.0, 64 * 64, dtype=np.float32).reshape(64, 64)


def _mine(since):
    return compile_cache.events(since=since,
                                thread=threading.current_thread().name)


def _misses() -> float:
    metric = REGISTRY.get("rafiki_compile_cache_misses_total")
    return metric.value() if metric is not None else 0.0


def test_a_fresh_jit_is_traced_lowered_and_compiled_under_its_span():
    since = time.time()
    with trace.span("setup.warmup"):
        _program(2.0)(X).block_until_ready()
    mine = [e for e in _mine(since) if "prog" in e["fun_name"]]
    assert sorted(e["stage"] for e in mine) == ["compile", "lower", "trace"]
    for e in mine:
        assert e["phase"] == "setup.warmup" and e["seconds"] >= 0
        assert since <= e["end"] <= time.time()
        assert e["thread"] == threading.current_thread().name


def test_the_same_program_after_clear_caches_is_a_load(cpu_cache):
    import jax

    _program(3.0)(X).block_until_ready()
    jax.clear_caches()
    since = time.time()
    _program(3.0)(X).block_until_ready()
    stages = [e["stage"] for e in _mine(since) if "prog" in e["fun_name"]]
    assert "load" in stages and "compile" not in stages
    assert compile_cache.counts(_mine(since))["cache_hits"] >= 1


def test_a_jit_inside_a_jit_counts_its_trace_seconds_once():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner(v):
        time.sleep(0.05)  # at trace time: the inner trace takes 50 ms
        return jnp.sin(v)

    @jax.jit
    def outer(v):
        return inner(v) + 1.0

    since = time.time()
    t0 = time.perf_counter()
    outer(X).block_until_ready()
    wall = time.perf_counter() - t0
    traced = [e for e in _mine(since) if e["stage"] == "trace"]
    assert [e["fun_name"] for e in traced] == ["outer"]
    assert 0.05 <= traced[0]["seconds"] <= wall
    by_stage = compile_cache.counts(_mine(since))["stage_s"]
    assert by_stage["trace"] <= wall


def test_a_noop_warm_up_counts_no_miss():
    before = _misses()
    report = warmup.run_warmup("svc-noop", "job", [("warm_up", lambda: None)])
    assert report["cache_misses"] == 0 and report["cache_hits"] == 0
    assert _misses() == before


def test_a_first_request_compile_is_filed_under_its_phase_and_counted():
    before = _misses()
    since = time.time()
    with trace.span("gen.prefill_chunk"):
        _program(5.0)(X).block_until_ready()
    compiled = [e for e in _mine(since) if e["stage"] == "compile"]
    assert [e["fun_name"] for e in compiled] == ["jit(prog)"]
    assert compiled[0]["phase"] == "gen.prefill_chunk"
    assert _misses() == before + 1
    stage = REGISTRY.get("rafiki_compile_stage_seconds_total")
    assert stage.value("compile") >= compiled[0]["seconds"]


def test_two_threads_keep_separate_stacks():
    seen, both_in, done = {}, threading.Barrier(2), threading.Event()

    def work(name):
        with trace.span(name):
            tracer = trace.Tracer(name)
            with tracer.span(name + ".inner"):
                both_in.wait(timeout=10)
                seen[name] = trace.current_phase()
            seen[name + ".after"] = trace.current_phase()
        done.wait(timeout=10)
        seen[name + ".out"] = trace.current_phase()

    threads = [threading.Thread(target=work, args=(n,)) for n in "ab"]
    for t in threads:
        t.start()
    time.sleep(0.05)
    assert trace.current_phase() == "none"
    done.set()
    for t in threads:
        t.join(timeout=10)
    assert seen == {"a": "a.inner", "b": "b.inner", "a.after": "a",
                    "b.after": "b", "a.out": "none", "b.out": "none"}


def test_a_tracer_span_reaches_the_phase_histogram():
    hist = trace.phase_histogram()
    before = hist.labels("persist_params").snapshot()["count"]
    with trace.Tracer("t").span("persist_params"):
        pass
    assert hist.labels("persist_params").snapshot()["count"] == before + 1


def test_the_first_trial_verdict_takes_the_records_seconds_not_the_trials():
    since = time.time()
    time.sleep(0.2)  # the trial's work, not a compile
    _program(7.0)(X).block_until_ready()
    record = _mine(since)
    assert warmup.note_first_program("svc-trial", "sub", "first_trial",
                                     record)
    report = warmup.warmup_stats("svc-trial")
    assert report["compile_s"] == round(
        sum(e["seconds"] for e in record), 4)
    assert report["cache_misses"] == 1 and report["cache_hits"] == 0
    assert not warmup.note_first_program("svc-trial", "sub", "later", [])


def test_threads_compiling_at_once_keep_their_own_events_and_counts():
    """More threads than cores, each compiling a program of its own inside
    a span of its own, with the interpreter switching threads as often as it
    can: every compile is in the record once, under its thread and span,
    and the miss counter rose by as many."""
    import os
    import sys

    n = 2 * (os.cpu_count() or 4)
    programs = [_program(10.0 + i) for i in range(n)]
    before, since = _misses(), time.time()
    barrier = threading.Barrier(n)

    def work(i):
        with trace.span(f"stress.{i}"):
            barrier.wait(timeout=30)
            programs[i](X).block_until_ready()

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,), name=f"st-{i}")
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads)
    compiled = [e for e in compile_cache.events(since=since)
                if e["stage"] == "compile"]
    assert sorted((e["thread"], e["phase"]) for e in compiled) == sorted(
        (f"st-{i}", f"stress.{i}") for i in range(n))
    assert _misses() == before + n
