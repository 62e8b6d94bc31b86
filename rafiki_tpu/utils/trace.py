"""Tracing and profiling — spans for the control plane, XLA profiles for
the compute plane.

The reference has no tracing subsystem at all (SURVEY.md §5.1: no timers,
spans, or profiler hooks anywhere); its nearest artifact is the per-trial
metric stream. This module is the first-class upgrade:

- **Spans**: lightweight wall-clock spans with nesting (thread-local
  stack), collected per trial/service by a `Tracer` and persisted as JSON
  lines under LOGS_DIR. The train worker wraps each trial phase (propose /
  train / evaluate / persist) so every trial ships a breakdown of where its
  time went; the REST layer serves it back (`GET /trials/<id>/trace`).
- **One clock with the device**: every span also enters a
  `jax.profiler.TraceAnnotation` of its name (:func:`annotation`), so
  while a profiler session is open the program's spans lie in the same
  `.xplane.pb` as the device's operations, on the profiler's clock; with
  no session open an annotation is a flag test. Code with no per-unit
  `Tracer` (the generation worker's serve loop, the workers' set-up
  `setup.*`) uses the module-level :func:`span`: the annotation plus one
  observation of `rafiki_worker_phase_seconds{phase=name}`, which a
  `Tracer` span observes too.
- **One span stack a thread**: both kinds of span push their name onto
  the thread's own list and pop it on exit; :func:`current_phase` is the
  innermost name. The compile record (sdk/compile_cache.py) files each
  compile under it, so a program compiled at a first request is seen
  where it happened.
- **XLA profiles**: `jax_profile(dir)` opens such a session
  (a TensorBoard-loadable xplane trace of the device with the spans
  above on it) — opt-in via the RAFIKI_PROFILE env var because capture
  is not free. The train worker opens one around each whole trial
  (train, evaluate, persist): the operator's way to a device trace from
  a worker in a child process. This is the TPU-side story the reference
  could never have (its compute was opaque inside user TF1 graphs).
- **Request traces** (the serving-plane half): a :class:`TraceContext`
  (trace id + sampling bit, rate ``RAFIKI_TRACE_SAMPLE``) enters at the
  predictor door as the ``X-Rafiki-Trace`` header, rides queue entries,
  the binary wire frame metadata (cache/wire.py, v2), and the fleet
  relay into the inference worker and back — so one sampled predict
  yields ONE span tree covering admission wait → queue wait → codec
  decode → batch assembly → model forward → codec encode → response.
  :class:`RequestTrace` extends :class:`Tracer` with direct span
  recording (monotonic clock; workers on the same host share it) and
  wire import/export; sampled requests slower than
  ``RAFIKI_TRACE_SLOW_MS`` are appended as JSON-lines exemplars to a
  size-rotated file under LOGS_DIR (:func:`record_exemplar`).
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional

from rafiki_tpu import config

logger = logging.getLogger(__name__)

# ---------------------------------------------------------------------------
# The profiler's clock

_NO_ANNOTATION = contextlib.nullcontext()
#: jax.profiler.TraceAnnotation once looked up; False where this jax has none
_annotation_cls: Any = None


def annotation(name: str, **attrs: Any):
    """A context manager that puts ``name`` into the open `jax.profiler`
    session's trace for its duration, ``attrs`` as the event's own
    statistics beside it (the event keeps the bare name). The class is
    looked up on first use
    in a process that has imported jax, never at import of this module
    and never by importing jax from here: a process without jax (the
    admin in process placement) has no session to write into and must not
    pay the import. With no session open it costs a flag test."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        if "jax" not in sys.modules:
            return _NO_ANNOTATION
        try:
            from jax.profiler import TraceAnnotation as cls
        except ImportError:
            cls = False
        _annotation_cls = cls
    return cls(name, **attrs) if cls else _NO_ANNOTATION


_phase_hist = None


def phase_histogram():
    """`rafiki_worker_phase_seconds{phase}`: the one histogram of worker
    phases. Its `_count` is the count at the phase's boundary, its `_sum`
    the time busy in it."""
    global _phase_hist
    if _phase_hist is None:
        from rafiki_tpu.utils.metrics import REGISTRY

        _phase_hist = REGISTRY.histogram(
            "rafiki_worker_phase_seconds",
            "worker-side phase latency: per served batch (batch_assembly, "
            "model_forward), per phase of the generation worker's serve "
            "loop (gen.*), of a worker's set-up (setup.*) and of a trial "
            "(propose, train, evaluate, persist*)", ("phase",))
    return _phase_hist


class _Stack(threading.local):
    def __init__(self) -> None:
        self.names: List[str] = []


_stack = _Stack()


def current_phase() -> str:
    """The innermost span open on this thread, or ``"none"``."""
    names = _stack.names
    return names[-1] if names else "none"


class span:
    """``with trace.span("gen.decode.device"):`` — a span for code that
    has no per-unit :class:`Tracer`: an :func:`annotation` of the name,
    the name on this thread's span stack while it is open, and one
    observation of `rafiki_worker_phase_seconds{phase=name}`; its
    ``seconds`` after exit. Keyword arguments go to the annotation alone."""

    __slots__ = ("name", "attrs", "seconds", "_ann", "_t0")

    def __init__(self, name: str, **attrs: Any) -> None:
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "span":
        self._ann = annotation(self.name, **self.attrs)
        self._ann.__enter__()
        _stack.names.append(self.name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        _stack.names.pop()
        self._ann.__exit__(*exc)
        self.seconds = dt
        phase_histogram().labels(self.name).observe(dt)


@dataclass
class Span:
    name: str
    start: float
    end: float = 0.0
    depth: int = 0
    attrs: Dict[str, Any] = field(default_factory=dict)

    @property
    def duration_s(self) -> float:
        return max(self.end - self.start, 0.0)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "start": self.start,
            "end": self.end,
            "duration_s": round(self.duration_s, 6),
            "depth": self.depth,
            **({"attrs": self.attrs} if self.attrs else {}),
        }


class Tracer:
    """Collects spans for one unit of work (a trial, a predict call...).

    Thread-safe for concurrent span entry from worker threads; nesting depth
    is tracked per thread.
    """

    def __init__(self, trace_id: str):
        self.trace_id = trace_id
        self.spans: List[Span] = []
        self._lock = threading.Lock()
        # depth per (tracer, thread) — a module-global thread-local would
        # interleave depths of two tracers active on one thread (e.g. a
        # predict-call tracer inside a trial tracer)
        self._depth: Dict[int, int] = {}

    @contextlib.contextmanager
    def span(self, name: str, **attrs: Any) -> Iterator[Span]:
        tid = threading.get_ident()
        with self._lock:
            depth = self._depth.get(tid, 0)
            self._depth[tid] = depth + 1
        ann = annotation(name)
        s = Span(name=name, start=time.time(), depth=depth, attrs=attrs)
        ann.__enter__()
        names = _stack.names
        names.append(name)
        try:
            yield s
        finally:
            names.pop()
            ann.__exit__(None, None, None)
            s.end = time.time()
            phase_histogram().labels(name).observe(s.duration_s)
            with self._lock:
                if depth == 0:
                    self._depth.pop(tid, None)
                else:
                    self._depth[tid] = depth
                self.spans.append(s)

    def summary(self) -> Dict[str, float]:
        """name -> total seconds (top-level occurrences summed)."""
        out: Dict[str, float] = {}
        with self._lock:
            for s in self.spans:
                out[s.name] = out.get(s.name, 0.0) + s.duration_s
        return out

    def save(self, path: Optional[str] = None) -> str:
        path = path or trace_path(self.trace_id)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with self._lock:
            ordered = sorted(self.spans, key=lambda s: s.start)
            with open(path, "w") as f:
                for s in ordered:
                    f.write(json.dumps(s.to_dict()) + "\n")
        return path


def trace_path(trace_id: str) -> str:
    return os.path.join(config.LOGS_DIR, f"trace-{trace_id}.jsonl")


def load_trace(trace_id: str) -> List[Dict[str, Any]]:
    """Read back a saved trace; [] if none was recorded."""
    path = trace_path(trace_id)
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# ---------------------------------------------------------------------------
# Request tracing (serving plane)

#: HTTP header carrying the trace context across doors/hops:
#: ``<hex trace id>;s=<0|1>`` (s is the sampling bit — a front door that
#: already decided to sample forces every hop behind it to record)
TRACE_HEADER = "X-Rafiki-Trace"


def sample_rate() -> float:
    """RAFIKI_TRACE_SAMPLE in [0, 1]; 0 (default) disables door-side
    sampling entirely. Malformed values read as 0 — doctor WARNs."""
    raw = os.environ.get("RAFIKI_TRACE_SAMPLE", "")
    if not raw:
        return 0.0
    try:
        return min(max(float(raw), 0.0), 1.0)
    except ValueError:
        return 0.0


def slow_threshold_s() -> float:
    """RAFIKI_TRACE_SLOW_MS: sampled requests at least this slow are
    dumped as JSON-lines exemplars (0 = every sampled request)."""
    try:
        return max(
            float(os.environ.get("RAFIKI_TRACE_SLOW_MS", "0")), 0.0) / 1000.0
    except ValueError:
        return 0.0


def exemplar_max_mb() -> float:
    try:
        return max(
            float(os.environ.get("RAFIKI_TRACE_EXEMPLAR_MAX_MB", "64")), 1.0)
    except ValueError:
        return 64.0


def exemplar_path() -> str:
    return os.path.join(config.LOGS_DIR, "predict_exemplars.jsonl")


class TraceContext:
    """The propagated part of a trace: id + sampling decision. Small and
    serializable — this is what crosses HTTP headers, queue entries, and
    wire frame metadata; the span collection stays in :class:`RequestTrace`
    at whichever hop records."""

    __slots__ = ("trace_id", "sampled")

    def __init__(self, trace_id: str, sampled: bool = True) -> None:
        self.trace_id = trace_id
        self.sampled = bool(sampled)

    def to_header(self) -> str:
        return f"{self.trace_id};s={1 if self.sampled else 0}"

    @classmethod
    def from_header(cls, value: Optional[str]) -> Optional["TraceContext"]:
        """Parse the X-Rafiki-Trace header; None for absent/garbled input
        (a malformed header from an untrusted client must never 500 a
        predict)."""
        if not value:
            return None
        parts = value.strip().split(";")
        tid = parts[0].strip()
        if not tid or len(tid) > 64 or not tid.isalnum():
            return None
        sampled = True
        for p in parts[1:]:
            k, _, v = p.strip().partition("=")
            if k == "s":
                sampled = v.strip() == "1"
        return cls(tid, sampled)

    def to_wire(self) -> Dict[str, Any]:
        return {"id": self.trace_id, "s": 1 if self.sampled else 0}

    @classmethod
    def from_wire(cls, meta: Any) -> Optional["TraceContext"]:
        if not isinstance(meta, dict) or not isinstance(meta.get("id"), str):
            return None
        return cls(meta["id"], bool(meta.get("s", 1)))


class RequestTrace(Tracer):
    """Span collector for ONE predict request, rooted at the serving
    door. Extends :class:`Tracer` (same Span/save machinery the trial
    path uses) with direct interval recording on the MONOTONIC clock —
    worker processes on the same host share CLOCK_MONOTONIC, so spans
    recorded worker-side line up with the door's without clock math —
    and with wire import/export for spans that crossed a hop as
    ``[name, offset_s, duration_s]`` triples."""

    def __init__(self, ctx: TraceContext) -> None:
        super().__init__(ctx.trace_id)
        self.ctx = ctx
        self.t0 = time.monotonic()
        #: set by the queue layer at submit time; the anchor worker-side
        #: queue_wait spans and returned wire spans are measured against
        self.t_submit: Optional[float] = None
        self._dequeued = False

    def add_span(self, name: str, start: float, end: float,
                 depth: int = 0, **attrs: Any) -> None:
        s = Span(name=name, start=start, end=max(end, start), depth=depth,
                 attrs=attrs)
        with self._lock:
            self.spans.append(s)

    def mark_submitted(self) -> None:
        if self.t_submit is None:
            self.t_submit = time.monotonic()

    def mark_dequeued(self, now: Optional[float] = None) -> None:
        """Record the queue_wait span once (a request's entries share one
        trace; the first dequeued entry closes the wait)."""
        with self._lock:
            if self._dequeued:
                return
            self._dequeued = True
        start = self.t_submit if self.t_submit is not None else self.t0
        self.add_span("queue_wait", start, now or time.monotonic(), depth=1)

    def add_wire_spans(self, spans: Any,
                       anchor: Optional[float] = None) -> None:
        """Import spans that crossed a hop as [name, offset_s, duration_s]
        triples, re-anchored at this trace's submit time. Garbled input is
        dropped silently — trace metadata is best-effort decoration, never
        worth failing a served request over."""
        if anchor is None:
            anchor = self.t_submit if self.t_submit is not None else self.t0
        if not isinstance(spans, list):
            return
        for entry in spans:
            try:
                name, off, dur = entry
                self.add_span(str(name)[:64], anchor + float(off),
                              anchor + float(off) + float(dur), depth=1)
            except (TypeError, ValueError):
                continue

    def wire_spans(self, anchor: float) -> List[List[Any]]:
        """Export spans as [name, offset_s, duration_s] relative to
        ``anchor`` — the hop-crossing format of :meth:`add_wire_spans`."""
        with self._lock:
            return [[s.name, round(s.start - anchor, 6),
                     round(s.duration_s, 6)] for s in self.spans]

    def phase_durations(self) -> Dict[str, float]:
        """name -> seconds for the latency histograms. Per name this is
        the MAX single span, not the sum: a multi-trial ensemble records
        one same-named span set per trial and the trials run in
        PARALLEL — summing would report a 3-trial 10 ms forward as one
        30 ms sample, exceeding the request's own wall time. Max is the
        per-phase critical path; for single-trial requests max == sum.
        The exemplar keeps every span, so per-trial detail is not lost."""
        out: Dict[str, float] = {}
        with self._lock:
            for s in self.spans:
                out[s.name] = max(out.get(s.name, 0.0), s.duration_s)
        return out


def start_trace(header_value: Optional[str] = None
                ) -> Optional[RequestTrace]:
    """Door-side entry point: honor an incoming header's sampling bit, or
    make the sampling decision locally at RAFIKI_TRACE_SAMPLE. Returns a
    RequestTrace only when this request is sampled — the unsampled path
    costs one header read and (without a header) one random draw."""
    ctx = TraceContext.from_header(header_value)
    if ctx is None:
        rate = sample_rate()
        if rate <= 0.0:
            return None
        import random
        import uuid

        if random.random() >= rate:
            return None
        ctx = TraceContext(uuid.uuid4().hex, True)
    if not ctx.sampled:
        return None
    return RequestTrace(ctx)


_exemplar_lock = threading.Lock()


def record_exemplar(trace: RequestTrace, e2e_s: float, door: str) -> None:
    """Append one request's span tree as a JSON line to the exemplar
    file, size-rotating at RAFIKI_TRACE_EXEMPLAR_MAX_MB (one ``.1``
    generation — bounded growth, doctor checks it). Best-effort: disk
    trouble must never fail a served request."""
    try:
        path = exemplar_path()
        os.makedirs(os.path.dirname(path), exist_ok=True)
        anchor = trace.t0
        line = json.dumps({
            "trace_id": trace.trace_id,
            "ts": round(time.time(), 3),
            "door": door,
            "e2e_s": round(e2e_s, 6),
            "spans": [
                {"name": s.name, "offset_s": round(s.start - anchor, 6),
                 "duration_s": round(s.duration_s, 6),
                 **({"attrs": s.attrs} if s.attrs else {})}
                for s in sorted(trace.spans, key=lambda s: s.start)
            ],
        })
        cap_bytes = int(exemplar_max_mb() * (1 << 20))
        with _exemplar_lock:
            try:
                if os.path.getsize(path) >= cap_bytes:
                    os.replace(path, path + ".1")
            except OSError:
                pass
            with open(path, "a") as f:
                f.write(line + "\n")
    except Exception:
        logger.debug("exemplar write failed", exc_info=True)


# ---------------------------------------------------------------------------
# XLA / device profiling

def profiling_enabled() -> bool:
    return os.environ.get("RAFIKI_PROFILE", "") not in ("", "0", "false")


@contextlib.contextmanager
def jax_profile(out_dir: Optional[str] = None,
                force: bool = False) -> Iterator[Optional[str]]:
    """Open a `jax.profiler` session around the body: an xplane trace
    (TensorBoard-loadable) of the device with the program's spans on it
    (:func:`annotation`). No-op unless RAFIKI_PROFILE is set (or
    force=True) — capture adds overhead and output is large. One session
    may be open in a process: where another already is (a benchmark's
    trace window, a sibling trial on another chip of a thread-placed
    worker) this logs a line and yields None, and the spans land in the
    session that is open."""
    if not (force or profiling_enabled()):
        yield None
        return
    out_dir = out_dir or os.path.join(config.LOGS_DIR, "profiles")
    import jax

    try:
        jax.profiler.start_trace(out_dir)
    except Exception as e:  # a session is open, or no profiler support
        logger.info("no profile under %s: %s", out_dir, e)
        yield None
        return
    os.makedirs(out_dir, exist_ok=True)
    try:
        yield out_dir
    finally:
        try:
            jax.profiler.stop_trace()
        except Exception:
            logger.exception("jax profiler failed to stop")
