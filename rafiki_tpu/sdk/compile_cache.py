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
- **Typed degrade, never a crash.** An unusable cache dir (missing,
  unwritable, probe failure) disables the cache for this process and
  records *why* (``stats()["reason"]``, surfaced by the doctor); the
  worker compiles fresh. Corrupt entries are absorbed by JAX's reader
  and recompiled — a damaged cache can cost time, not correctness — and
  the warm-up chokepoint evicts unreadable entries (:func:`evict_entries`)
  because jax never overwrites them in place.
- **Observable.** Cache hits are counted via JAX's monitoring events
  into ``rafiki_compile_cache_hits_total``; the warm-up chokepoint
  (worker/warmup.py) accounts misses and per-program compile seconds.

The CPU backend stays opted out by default (RAFIKI_COMPILE_CACHE_CPU=1
to force): CPU AOT entries are tied to exact machine-feature sets and
can fail to load — or SIGILL — when the features differ between compile
and load. The cache pays off on TPU, where compiles are slow.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Any, Dict, Optional

import jax

logger = logging.getLogger(__name__)

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_lock = threading.Lock()
#: process-wide cache state (guarded-by _lock): the active dir, or the
#: typed reason it is off
_state: Dict[str, Any] = {"enabled": False, "dir": None, "reason": None}
_listeners_installed = False
#: monotonically-increasing persistent-cache hit count for this process,
#: fed by the JAX monitoring listener (lock-free read: int writes are
#: atomic under the GIL and readers only diff snapshots)
_hit_count = 0


def cache_dir() -> str:
    """The one place every compiling process of the stack caches in
    (and the doctor inspects): ``JAX_COMPILATION_CACHE_DIR`` as it is,
    else ``RAFIKI_COMPILE_CACHE_DIR``, else ``<checkout>/xla_cache``."""
    from rafiki_tpu import config

    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or config.COMPILE_CACHE_DIR
            or os.path.join(_CHECKOUT, "xla_cache"))


def _install_listeners() -> None:
    """Count persistent-cache hits via JAX's monitoring events."""
    global _listeners_installed
    if _listeners_installed:
        return
    _listeners_installed = True

    def _on_event(event: str, **kw: Any) -> None:
        if event.endswith("/compilation_cache/cache_hits"):
            global _hit_count
            _hit_count += 1
            from rafiki_tpu.utils.metrics import REGISTRY

            REGISTRY.counter(
                "rafiki_compile_cache_hits_total",
                "persistent compile-cache hits in this process",
            ).inc()

    jax.monitoring.register_event_listener(_on_event)


def hit_count() -> int:
    """Persistent-cache hits recorded in this process so far."""
    return _hit_count


def record_misses(n: int, seconds: float = 0.0) -> None:
    """Account ``n`` compiled-fresh programs (the warm-up chokepoint's
    bookkeeping — JAX's miss event is write-path-conditional, so misses
    are counted where the compile time is actually measured)."""
    if n <= 0:
        return
    from rafiki_tpu.utils.metrics import REGISTRY

    REGISTRY.counter(
        "rafiki_compile_cache_misses_total",
        "programs compiled fresh (persistent-cache misses) in this process",
    ).inc(n)
    if seconds > 0:
        REGISTRY.histogram(
            "rafiki_compile_seconds",
            "wall-clock seconds spent compiling (cache misses) per program",
            buckets=[0.05, 0.25, 1, 5, 15, 60, 300],
        ).observe(seconds)


def enable() -> Optional[str]:
    """Turn on JAX's persistent compilation cache in :func:`cache_dir`.
    Idempotent; returns the active dir, or None with a typed reason in
    ``stats()`` when the cache is off (disabled, CPU without the opt-in,
    or an unusable directory — the degrade path: the process compiles
    fresh, it never crashes)."""
    from rafiki_tpu import config

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
        _state.update(enabled=True, dir=path, reason=None)
    _install_listeners()
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
    config update alone would keep serving the previous directory."""
    global _hit_count
    from jax.experimental.compilation_cache import compilation_cache as _cc

    with _lock:
        _state.update(enabled=False, dir=None, reason=None)
        _hit_count = 0
    _cc.reset_cache()
