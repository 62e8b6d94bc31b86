"""Worker program pre-warming (docs/failure-model.md "Cold-start faults").

The single chokepoint every worker runs BEFORE registering as RUNNING:
enable the persistent compile cache (sdk/compile_cache.py), then compile
each enumerated program shape while the replica is still DEPLOYING. The
predictor's route-after-add_worker rule therefore never parks a request
behind a compiling replica — a still-warming worker is simply not
routable yet.

Each boot produces a warm-state report (stored in :data:`WARMUP_STATS`,
merged into the worker's stats row and `/healthz`):

- ``warm`` — True when the boot was served by the persistent cache
  (observed cache hits, or total compile time under
  ``RAFIKI_COMPILE_WARM_THRESHOLD_S`` when hit events are unavailable).
- ``compile_s`` / ``programs`` — total and per-program seconds.
- ``cache_hits`` / ``cache_misses`` — programs this thread loaded from
  the persistent cache / compiled, from the compile record
  (sdk/compile_cache.py): an entry that compiles nothing counts nothing.

Chaos (RAFIKI_CHAOS site=compile, target
``"{scope}/{service_id}/{program}"``): ``delay`` stretches the warm-up
(the slow-compile drill), ``corrupt`` garbles the on-disk cache entries
before the program compiles (the bit-rot drill — JAX absorbs the damage
and recompiles, and warm-up EVICTS the unreadable entries so the boot
after next re-warms), ``error`` raises the typed :class:`WarmupError` that
fails the worker's startup (the bounded standby-retry drill). A
program's own exception is absorbed warn-only: a model whose optional
warm-up fails still serves, it just serves cold.
"""

from __future__ import annotations

import logging
import re
import threading
import time
import warnings as _pywarnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from rafiki_tpu import config
from rafiki_tpu.sdk import compile_cache
from rafiki_tpu.utils import chaos, trace

logger = logging.getLogger(__name__)

#: per-service warm-up reports for this process, keyed by service_id
#: (guarded-by _warm_lock) — the /healthz and stats-row source
WARMUP_STATS: Dict[str, Dict[str, Any]] = {}
_warm_lock = threading.Lock()

#: a program is an (informative name, zero-arg callable that triggers
#: its compile) pair
Program = Tuple[str, Callable[[], Any]]


#: jax's warning when a persistent-cache entry exists but cannot be read
#: (bit rot / truncation): it recompiles fresh but never overwrites the
#: damaged entry, so warm-up evicts it or every later boot stays cold
_CACHE_READ_ERR = re.compile(
    r"Error reading persistent compilation cache entry for '([^']+)'")


class WarmupError(RuntimeError):
    """A warm-up program failed hard (today: only injected chaos — real
    program failures are absorbed warn-only). Propagates out of worker
    startup so the service lands ERRORED instead of half-warm RUNNING."""


def _this_thread_since(since: float) -> List[Dict[str, Any]]:
    return compile_cache.events(since=since,
                                thread=threading.current_thread().name)


def _report(service_id: str, scope: str, seconds: float,
            programs: Dict[str, float], record: Sequence[Dict[str, Any]],
            **extra: Any) -> Dict[str, Any]:
    """A boot's warm-state report, its hits and misses from ``record``
    (compile events). Warm <=> the cache demonstrably served the boot, or
    it compiled fast enough that a request parked behind it would not
    have noticed."""
    n = compile_cache.counts(record)
    return {"service_id": service_id, "scope": scope,
            "warm": bool(n["cache_hits"] > 0 or
                         seconds <= config.COMPILE_WARM_THRESHOLD_S),
            "compile_s": round(seconds, 4), "programs": programs,
            "cache_hits": n["cache_hits"],
            "cache_misses": n["cache_misses"], "warnings": [],
            "ts": time.time(), "cache": compile_cache.stats(), **extra}


def run_warmup(service_id: str, scope: str,
               programs: Sequence[Program]) -> Dict[str, Any]:
    """Compile ``programs`` under the persistent cache, timing each, in a
    ``setup.warmup`` span, and record + return the boot's warm-state
    report. Call BEFORE ``ctx.ready()``: the whole point is that warm-up
    time is spent while the replica is still DEPLOYING and unroutable."""
    compile_cache.enable()
    since = time.time()
    took: Dict[str, float] = {}
    warned, evicted = [], 0
    with trace.span("setup.warmup") as span:
        for name, fn in programs:
            rule = chaos.hit(chaos.SITE_COMPILE,
                             f"{scope}/{service_id}/{name}")
            if rule is not None:
                if rule.action == chaos.ACTION_DELAY:
                    chaos.sleep_for(rule)
                elif rule.action == chaos.ACTION_CORRUPT:
                    damaged = compile_cache.corrupt_entries()
                    logger.warning("chaos corrupted %d compile-cache "
                                   "entries before %s", damaged, name)
                else:  # error / drop: fail the boot, typed
                    raise WarmupError(
                        f"injected warm-up failure at {scope}/{service_id}/"
                        f"{name} (chaos site=compile)")
            t0 = time.monotonic()
            # record python warnings across the compile: jax reports an
            # unreadable (bit-rotted) cache entry that way, and warm-up is
            # the boot-time chokepoint where self-healing can happen
            with _pywarnings.catch_warnings(record=True) as caught:
                _pywarnings.simplefilter("always")
                try:
                    fn()
                # lint: absorb(an optional warm-up program failing must not block serving — the replica just boots cold; recorded in the report)
                except Exception as e:
                    warned.append(f"{name}: {type(e).__name__}: {e}")
                    logger.warning(
                        "warm-up program %s failed (serving anyway): %s",
                        name, warned[-1], exc_info=True)
            took[name] = round(time.monotonic() - t0, 4)
            for rec in caught:
                m = _CACHE_READ_ERR.search(str(rec.message))
                if m is None:
                    # not ours: hand it back to the normal warning machinery
                    _pywarnings.warn_explicit(rec.message, rec.category,
                                              rec.filename, rec.lineno)
                    continue
                evicted += compile_cache.evict_entries(m.group(1))
                logger.warning(
                    "evicted unreadable compile-cache entries for %s "
                    "(bit-rot self-heal: the next boot recompiles and "
                    "rewrites them)", m.group(1))
    report = _report(service_id, scope, span.seconds, took,
                     _this_thread_since(since), evicted=evicted,
                     warnings=warned)
    with _warm_lock:
        WARMUP_STATS[service_id] = report
    return report


def note_first_program(service_id: str, scope: str, name: str,
                       record: Sequence[Dict[str, Any]]) -> bool:
    """One-shot warm verdict for workers whose compiled programs only
    materialize mid-run (the trial worker: jit programs depend on the
    advisor's knob draw, so there is nothing to enumerate at boot):
    ``record`` is the compile record of the boot's first program (its
    first trial), whose seconds are the program's, every stage. Only the
    FIRST call for a service records (True); later ones do nothing."""
    seconds = sum(e["seconds"] for e in record)
    report = _report(service_id, scope, seconds, {name: round(seconds, 4)},
                     record)
    with _warm_lock:
        return WARMUP_STATS.setdefault(service_id, report) is report


def log_setup(service_id: str, scope: str, spans: Dict[str, float],
              since: float) -> None:
    """A worker's one line at the end of its set-up: the seconds of its
    set-up spans (name -> seconds) and this thread's compile record since
    ``since`` by stage, with hits and misses."""
    n = compile_cache.counts(_this_thread_since(since))
    logger.info("set-up %s (%s): %s; compile record %s; hits=%d misses=%d",
                service_id, scope,
                " ".join(f"{k}={v:.3f}s" for k, v in spans.items()),
                " ".join(f"{k}={v:.3f}s" for k, v in
                         sorted(n["stage_s"].items())),
                n["cache_hits"], n["cache_misses"])


def warmup_stats(service_id: Optional[str] = None) -> Dict[str, Any]:
    """This process's warm-up reports (one service's, or all of them) —
    consumed by worker stats rows and the predictor's /healthz."""
    with _warm_lock:
        if service_id is not None:
            return dict(WARMUP_STATS.get(service_id, {}))
        return {sid: dict(r) for sid, r in WARMUP_STATS.items()}


def stats_row_fields(service_id: str) -> Dict[str, Any]:
    """The compact warm-state fields a worker merges into its periodic
    stats row (relayed to admin -> GET /fleet/health workers)."""
    with _warm_lock:
        r = WARMUP_STATS.get(service_id)
    if not r:
        return {}
    return {"warm": 1 if r["warm"] else 0,
            "compile_ms": int(r["compile_s"] * 1000),
            "compile_cache_hits": r["cache_hits"],
            "compile_cache_misses": r["cache_misses"]}


def reset_for_tests() -> None:
    with _warm_lock:
        WARMUP_STATS.clear()
