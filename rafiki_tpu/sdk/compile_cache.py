"""Persistent XLA executable cache, managed (docs/failure-model.md
"Cold-start faults").

Every process that compiles — trial workers, inference/generation
workers, the bench — calls :func:`enable` at startup, so compiled
programs survive process death, control-plane recovery, reschedules, and
autoscaler scale-up: a replacement replica's jit programs become a disk
read instead of an XLA compile.

Contract (the artifact-frame contract applied to XLA executables):

- **Placed from outside.** Where ``JAX_COMPILATION_CACHE_DIR`` is set
  the cache lives in exactly that directory: JAX reads the variable
  itself, and nothing here sets another. Otherwise the directory is
  ``RAFIKI_COMPILE_CACHE_DIR`` if given, else ``<checkout>/xla_cache`` —
  a fixed path (no pid, no time, not the workdir), because a cache that
  moves never hits. JAX's own key covers the program, the device kind
  and topology, and the jax/jaxlib versions, so one directory serves
  every process and backend.
- **Keyed by the program, not by where its source lies.** JAX strips
  debug info from the module it hashes, but not from inside a Pallas
  kernel's ``tpu_custom_call``: its Mosaic payload carries MLIR
  locations, Python frames and their file paths included. An uploaded
  template is imported from a temp file of a random name in every
  process (``sdk/model.py`` ``load_model_class``), so with frames in its
  locations a program that holds a kernel would key anew in every
  process and compile from scratch. :func:`enable` sets ``jax_traceback_in_locations_limit`` to 0: no
  frame goes into a location, so no path (the template's, the
  checkout's, a kernel's written in a template) goes into a key.
  Programs without a kernel keep their keys; profile names come from
  name stacks, which stay.
- **Typed degrade, never a crash.** An unusable cache dir (missing,
  unwritable, probe failure) disables the cache for this process and
  records *why* (``stats()["reason"]``, surfaced by the doctor); the
  worker compiles fresh. Corrupt entries are absorbed by JAX's reader
  and recompiled — a damaged cache can cost time, not correctness — and
  the warm-up chokepoint evicts unreadable entries (:func:`evict_entries`)
  because jax never overwrites them in place.
- **Observable: the compile record.** One JAX listener (installed by
  :func:`enable`) takes JAX's duration events, wherever in the process
  they happen (a worker's warm-up, a trial, a first request), and keeps
  each in a bounded record (:func:`events`): wall-clock end
  (``time.time()``), stage, seconds, the program's name, the innermost
  span open on the compiling thread (``utils/trace.py``
  ``current_phase``) and that thread's name. The stages:
  ``trace`` (``jaxpr_trace_duration``), ``lower``
  (``jaxpr_to_mlir_module_duration``), and for
  ``backend_compile_duration`` ``load`` where this thread saw a
  persistent-cache hit inside it, else ``compile`` (a program under
  ``RAFIKI_COMPILE_CACHE_MIN_COMPILE_S`` is never written, so it is a
  ``compile`` every time). Nested events of one stage on one thread (a
  jit traced inside a jit) count once, the outermost: JAX marks each
  start with a scalar of the event's name, so the listener knows how
  many of a stage are open on the thread. From the same
  events: ``rafiki_compile_cache_hits_total``,
  ``rafiki_compile_cache_misses_total`` and ``rafiki_compile_seconds``
  (every backend compile that was not a hit) and
  ``rafiki_compile_stage_seconds_total{stage}``.

The CPU backend stays opted out by default (RAFIKI_COMPILE_CACHE_CPU=1
to force): CPU AOT entries are tied to exact machine-feature sets and
can fail to load — or SIGILL — when the features differ between compile
and load. The cache pays off on TPU, where compiles are slow.
"""

from __future__ import annotations

import collections
import logging
import os
import threading
import time
from typing import Any, Dict, List, Optional

import jax

logger = logging.getLogger(__name__)

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_lock = threading.Lock()
#: process-wide cache state (guarded-by _lock): the active dir, or the
#: typed reason it is off
_state: Dict[str, Any] = {"enabled": False, "dir": None, "reason": None}
_listeners_installed = False
#: persistent-cache hits in this process (the doctor/health view)
_hit_count = 0

_STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
           "/jax/core/compile/backend_compile_duration": "compile"}
_HIT = "/jax/compilation_cache/cache_hits"
#: the compile record (guarded-by _record_lock); bounded: a process that
#: compiles forever keeps its latest programs
_record: "collections.deque[Dict[str, Any]]" = collections.deque(
    maxlen=1 << 15)
_record_lock = threading.Lock()


class _ThreadCompiles(threading.local):
    def __init__(self) -> None:
        #: wall time of this thread's latest cache hit
        self.hit_at = 0.0
        #: stage -> events of it open on this thread (JAX marks a start
        #: with a scalar of the same name)
        self.depth: Dict[str, int] = {}


_thread = _ThreadCompiles()


def cache_dir() -> str:
    """The one place every compiling process of the stack caches in
    (and the doctor inspects): ``JAX_COMPILATION_CACHE_DIR`` as it is,
    else ``RAFIKI_COMPILE_CACHE_DIR``, else ``<checkout>/xla_cache``."""
    from rafiki_tpu import config

    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or config.COMPILE_CACHE_DIR
            or os.path.join(_CHECKOUT, "xla_cache"))


def _on_event(event: str, **kw: Any) -> None:
    if event == _HIT:
        global _hit_count
        _hit_count += 1
        _thread.hit_at = time.time()
        from rafiki_tpu.utils.metrics import REGISTRY

        REGISTRY.counter(
            "rafiki_compile_cache_hits_total",
            "persistent compile-cache hits in this process",
        ).inc()


def _on_start(event: str, value: float, **kw: Any) -> None:
    stage = _STAGES.get(event)
    if stage is not None:
        _thread.depth[stage] = _thread.depth.get(stage, 0) + 1


def _on_duration(event: str, duration: float, **kw: Any) -> None:
    stage = _STAGES.get(event)
    if stage is None:
        return
    open_ = _thread.depth.get(stage, 0)
    _thread.depth[stage] = max(open_ - 1, 0)
    if open_ > 1:
        return  # inside an event of its stage on this thread: counted there
    from rafiki_tpu.utils import trace
    from rafiki_tpu.utils.metrics import REGISTRY

    end = time.time()
    if stage == "compile" and _thread.hit_at >= end - duration:
        stage = "load"
    ev = {"end": end, "stage": stage, "seconds": duration,
          "fun_name": str(kw.get("fun_name", "")),
          "phase": trace.current_phase(),
          "thread": threading.current_thread().name}
    with _record_lock:
        _record.append(ev)
    REGISTRY.counter(
        "rafiki_compile_stage_seconds_total",
        "seconds of the compile record by stage (trace, lower, compile, "
        "load), nested events of a stage counted once", ("stage",),
    ).labels(stage).inc(duration)
    if stage == "compile":
        REGISTRY.counter(
            "rafiki_compile_cache_misses_total",
            "backend compiles that were not a persistent-cache hit, "
            "anywhere in this process",
        ).inc()
        REGISTRY.histogram(
            "rafiki_compile_seconds",
            "seconds of each backend compile that was not a "
            "persistent-cache hit",
            buckets=[0.05, 0.25, 1, 5, 15, 60, 300],
        ).observe(duration)


def _install_listeners() -> None:
    """The one listener on JAX's monitoring events: hits, the start of a
    stage (a scalar JAX records as it enters one), its duration."""
    global _listeners_installed
    with _lock:
        if _listeners_installed:
            return
        _listeners_installed = True
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_scalar_listener(_on_start)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def events(since: float = 0.0,
           thread: Optional[str] = None) -> List[Dict[str, Any]]:
    """The compile record, oldest first: one dict an event, ``end``
    (``time.time()``), ``stage``, ``seconds``, ``fun_name``, ``phase``,
    ``thread``; those that ended at or after ``since``, on ``thread``
    where one is named."""
    with _record_lock:
        return [dict(e) for e in _record if e["end"] >= since
                and (thread is None or e["thread"] == thread)]


def counts(record: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Of some events of the record: seconds by stage, and the programs
    loaded from the cache (hits) and compiled (misses)."""
    stage_s: Dict[str, float] = {}
    for e in record:
        stage_s[e["stage"]] = stage_s.get(e["stage"], 0.0) + e["seconds"]
    return {"stage_s": stage_s,
            "cache_hits": sum(e["stage"] == "load" for e in record),
            "cache_misses": sum(e["stage"] == "compile" for e in record)}


def enable() -> Optional[str]:
    """Turn on JAX's persistent compilation cache in :func:`cache_dir`.
    Idempotent; returns the active dir, or None with a typed reason in
    ``stats()`` when the cache is off (disabled, CPU without the opt-in,
    or an unusable directory — the degrade path: the process compiles
    fresh, it never crashes). The compile record is kept either way."""
    from rafiki_tpu import config

    _install_listeners()
    with _lock:
        if _state["enabled"]:
            return _state["dir"]
        if not config.COMPILE_CACHE:
            _state["reason"] = "disabled (RAFIKI_COMPILE_CACHE=0)"
            return None
        if jax.default_backend() == "cpu" and not config.COMPILE_CACHE_CPU:
            _state["reason"] = ("cpu backend (entries are machine-feature-"
                                "tied; set RAFIKI_COMPILE_CACHE_CPU=1 to "
                                "opt in)")
            return None
        path = cache_dir()
        try:
            os.makedirs(path, exist_ok=True)
            # a write probe up front: an unwritable dir must degrade HERE,
            # typed, not as N absorbed warnings inside XLA later
            probe = os.path.join(path, ".rafiki_probe")
            with open(probe, "w", encoding="utf-8") as f:
                f.write("ok")
            os.unlink(probe)
        except OSError as e:
            logger.warning(
                "persistent compile cache unavailable at %s (%s: %s); "
                "compiling fresh", path, type(e).__name__, e)
            _state["reason"] = f"unusable dir {path}: {type(e).__name__}: {e}"
            return None
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            # placed from outside -> JAX read the variable itself and
            # no directory is set in code
            jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(config.COMPILE_CACHE_MIN_COMPILE_S))
        # no source frames in MLIR locations, so none in a kernel's
        # payload and none in its key (module docstring)
        jax.config.update("jax_traceback_in_locations_limit", 0)
        _state.update(enabled=True, dir=path, reason=None)
    logger.info("persistent compile cache at %s", path)
    return path


def stats() -> Dict[str, Any]:
    """{enabled, dir, reason, cache_hits} — the doctor/health view."""
    with _lock:
        return {"enabled": _state["enabled"], "dir": _state["dir"],
                "reason": _state["reason"], "cache_hits": _hit_count}


def active_dir() -> Optional[str]:
    with _lock:
        return _state["dir"] if _state["enabled"] else None


def corrupt_entries() -> int:
    """Garble every cache entry in the active dir (RAFIKI_CHAOS
    site=compile action=corrupt — the deterministic bit-rot drill).
    Returns the number of files damaged; JAX's reader absorbs the
    damage and recompiles fresh."""
    path = active_dir()
    if path is None:
        return 0
    damaged = 0
    for name in os.listdir(path):
        full = os.path.join(path, name)
        if not os.path.isfile(full):
            continue
        try:
            with open(full, "r+b") as f:
                head = bytearray(f.read(64))
                if not head:
                    continue
                f.seek(0)
                f.write(bytes(b ^ 0xFF for b in head))
            damaged += 1
        # lint: absorb(a file the drill cannot damage — racing eviction — just stays intact)
        except OSError:
            continue
    return damaged


def evict_entries(program: str) -> int:
    """Delete one program's on-disk entries (bit-rot self-healing: jax
    warns and recompiles on an unreadable entry but never overwrites
    it, so without eviction a damaged entry would stay cold on EVERY
    later boot). Returns the number of files removed."""
    path = active_dir()
    if path is None:
        return 0
    removed = 0
    for name in os.listdir(path):
        if not name.startswith(program + "-"):
            continue
        try:
            os.unlink(os.path.join(path, name))
            removed += 1
        # lint: absorb(an entry racing eviction just survives until the next read error)
        except OSError:
            continue
    return removed


def reset_for_tests() -> None:
    """Drop the process-level enablement so a test can re-point the
    cache dir. Also resets jax's cache SINGLETON: jax initializes its
    cache object lazily from the configured dir and then keeps it — a
    config update alone would keep serving the previous directory. The
    compile record is emptied; the listeners stay."""
    global _hit_count
    from jax.experimental.compilation_cache import compilation_cache as _cc

    with _lock:
        _state.update(enabled=False, dir=None, reason=None)
        _hit_count = 0
    with _record_lock:
        _record.clear()
    _cc.reset_cache()
