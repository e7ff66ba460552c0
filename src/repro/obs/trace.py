"""Span tracer — contextvar-propagated, monotonic-clock, ring-bounded.

One :class:`Tracer` per session records :class:`SpanRecord` rows into a
bounded deque.  ``span()`` returns a context manager; nesting is tracked
through a module-level :class:`~contextvars.ContextVar` holding the current
span id, so a stage deep inside the pipeline (e.g. the fused delta pack in
``core/delta.py``) lands under the right parent without threading a handle
through every call signature.  Contextvars do *not* propagate into worker
threads — spans opened from the async-writer drain or the publish worker
simply become roots (parent ``None``), which is the honest picture: those
stages genuinely run off the commit's critical path.

Disabled cost is one attribute check plus returning a shared no-op context
manager — no allocation, no clock read — so the tracer can stay wired into
every hot path unconditionally.

While a tracer is enabled, each span also enters a
``jax.profiler.TraceAnnotation`` named ``kishu.<name>`` with its
``span_id`` and args as metadata.  When a profiler session is running the
spans then land on the host plane of its ``.xplane.pb``, on the clock of the
device trace; without one the annotation is never built.  jax is imported
lazily, so this module stays importable without it.

Work handed to another thread (the async writer's drain, the publish
worker) carries ``op``: :func:`current_op` on the thread that queued it —
the id of the outermost span open there, the operation that caused it.

Export is Chrome trace-event JSON (``ph: "X"`` complete events, µs
timestamps), loadable in Perfetto / ``chrome://tracing`` with no deps.
"""
from __future__ import annotations

import contextvars
import functools
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional

# current span id for the *calling* context; shared across tracers — span ids
# are globally unique per process so a stale id from another tracer can never
# be mistaken for a parent in this one (records are matched by id).
_current_span: contextvars.ContextVar[Optional[int]] = contextvars.ContextVar(
    "kishu_obs_current_span", default=None)
# id of the outermost open span of the calling context: the operation
_current_op: contextvars.ContextVar[Optional[int]] = contextvars.ContextVar(
    "kishu_obs_current_op", default=None)

ANNOTATION_PREFIX = "kishu."

_ids = iter(range(1, 1 << 62)).__next__
_ids_lock = threading.Lock()


def _next_id() -> int:
    with _ids_lock:
        return _ids()


@dataclass
class SpanRecord:
    """One completed span: ``t0_s`` is seconds since the tracer's epoch
    (``time.monotonic`` at construction), ``dur_s`` the wall duration."""
    span_id: int
    parent_id: Optional[int]
    name: str
    t0_s: float
    dur_s: float
    thread: int = 0
    args: Dict[str, Any] = field(default_factory=dict)


def current_op() -> Optional[int]:
    """Span id of the outermost span open in the calling context (None when
    tracing is off or no span is open)."""
    return _current_op.get()


@functools.lru_cache(maxsize=None)
def _annotation() -> Optional[type]:
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return None
    return TraceAnnotation


def _metadata(args: Dict[str, Any]) -> Dict[str, Any]:
    """Span args as profiler metadata: numbers and strings as they are,
    anything else by ``str``."""
    return {k: v if isinstance(v, (int, float, str)) else str(v)
            for k, v in args.items()}


class _NullSpan:
    """Shared no-op context manager returned when tracing is disabled."""
    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args: Any) -> None:
        pass


NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("_tracer", "name", "args", "span_id", "parent_id",
                 "_t0", "_token", "_op_token", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.args = args
        self.span_id = _next_id()
        self.parent_id: Optional[int] = None
        self._t0 = 0.0
        self._token = None
        self._op_token = None
        self._ann = None

    def __enter__(self) -> "_Span":
        self.parent_id = _current_span.get()
        self._token = _current_span.set(self.span_id)
        if _current_op.get() is None:
            self._op_token = _current_op.set(self.span_id)
        ann = _annotation()
        if ann is not None and ann.is_enabled():
            self._ann = ann(ANNOTATION_PREFIX + self.name,
                            span_id=self.span_id, **_metadata(self.args))
            self._ann.__enter__()
        self._t0 = time.monotonic()
        return self

    def set(self, **args: Any) -> None:
        """Add args known only once the span's work is done (bytes read)."""
        self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**_metadata(args))

    def __exit__(self, *exc) -> bool:
        t1 = time.monotonic()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        if self._op_token is not None:
            _current_op.reset(self._op_token)
        if self._token is not None:
            _current_span.reset(self._token)
        self._tracer._record(SpanRecord(
            span_id=self.span_id, parent_id=self.parent_id, name=self.name,
            t0_s=self._t0 - self._tracer.epoch, dur_s=t1 - self._t0,
            thread=threading.get_ident(), args=self.args))
        return False


class Tracer:
    """Ring-bounded span recorder.  ``enabled`` may be flipped at runtime;
    ``span()`` reads it per call, so benches can turn tracing on after the
    session is built."""

    def __init__(self, enabled: bool = False, max_spans: int = 16384,
                 on_drop: Optional[Callable[[], None]] = None):
        self.enabled = bool(enabled)
        self.epoch = time.monotonic()
        self.spans: deque = deque(maxlen=int(max_spans))
        self._lock = threading.Lock()
        # called once per span the full ring evicts
        self._on_drop = on_drop

    def span(self, name: str, **args: Any):
        """A span named ``name``; args that are None are left out."""
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, name,
                     {k: v for k, v in args.items() if v is not None})

    def _record(self, rec: SpanRecord) -> None:
        with self._lock:
            full = len(self.spans) == self.spans.maxlen
            self.spans.append(rec)
        if full and self._on_drop is not None:
            self._on_drop()

    def clear(self) -> None:
        with self._lock:
            self.spans.clear()

    # ---- aggregation / export ----

    def stage_totals(self) -> Dict[str, float]:
        """Total seconds per span name (for bench stage vectors)."""
        out: Dict[str, float] = {}
        with self._lock:
            for rec in self.spans:
                out[rec.name] = out.get(rec.name, 0.0) + rec.dur_s
        return out

    def to_doc(self) -> List[dict]:
        """JSON-serializable span dump (persisted under ``obs/trace/``)."""
        with self._lock:
            return [{"id": r.span_id, "parent": r.parent_id, "name": r.name,
                     "t0": r.t0_s, "dur": r.dur_s, "tid": r.thread,
                     "args": r.args} for r in self.spans]


def spans_from_doc(doc: Iterable[dict]) -> List[SpanRecord]:
    return [SpanRecord(span_id=int(d["id"]),
                       parent_id=(None if d.get("parent") is None
                                  else int(d["parent"])),
                       name=str(d["name"]), t0_s=float(d["t0"]),
                       dur_s=float(d["dur"]), thread=int(d.get("tid", 0)),
                       args=dict(d.get("args") or {}))
            for d in doc]


def chrome_trace(spans: Iterable[SpanRecord], *, pid: int = 1) -> dict:
    """Chrome trace-event JSON (Perfetto-loadable).  Complete ``"X"`` events
    with µs timestamps; span/parent ids ride in ``args`` so nesting survives
    round-trips even when viewers re-sort by timestamp."""
    spans = list(spans)
    # compact per-process thread ids: viewers lay tracks out per tid, and raw
    # thread idents are unreadable 15-digit numbers
    tids: Dict[int, int] = {}
    for r in spans:
        tids.setdefault(r.thread, len(tids) + 1)
    events = []
    for r in spans:
        args = {"span_id": r.span_id, "parent_id": r.parent_id}
        args.update(r.args)
        events.append({
            "name": r.name, "ph": "X", "cat": "kishu",
            "ts": round(r.t0_s * 1e6, 3),
            "dur": max(round(r.dur_s * 1e6, 3), 0.001),
            "pid": pid, "tid": tids[r.thread], "args": args,
        })
    events.sort(key=lambda e: e["ts"])
    return {"traceEvents": events, "displayTimeUnit": "ms"}
