"""Tracing + query-event pipeline.

Reference roles:
  - spi/tracing/Tracer.java + TracerProvider (SURVEY.md §5.1): named
    spans with wall-time points, queryable per query. SimpleTracer's
    add-point/get-points surface, W3C-style nesting flattened to
    (name, start, end, attributes) records.
  - spi/eventlistener (QueryCreatedEvent / QueryCompletedEvent /
    SplitCompletedEvent -> eventlistener/EventListenerManager.java +
    event/QueryMonitor.java, SURVEY.md §5.5): registered listeners get
    lifecycle events with timing/stats payloads.
  - TelemetryTracingImpl's context propagation: the coordinator stamps
    every worker RPC with an `X-Presto-Trace: <trace_id>;<span_id>`
    header; workers open their spans under the propagated trace id and
    the coordinator stitches worker span dumps (GET /v1/trace/{id})
    back into one cross-node timeline.

Engines call `tracer.span(...)` around phases (plan/lower/execute) and
`emit_query_event(...)` at lifecycle edges; listeners are plain
callables (the plugin surface collapsed to its functional core).

One span API, two sinks, one clock. Every span is also held open as a
`jax.profiler.TraceAnnotation("presto:<name>", **attributes)`: under a
running profiler it lies in the host plane of the device trace, on the
thread that did the work and on the device trace's clock; with no
profiler running the annotation is an inactive TraceMe (half a
microsecond). The in-memory span is what GET /v1/trace/{id},
`render_trace` and EXPLAIN ANALYZE serve. Spans stamp `now()`, a
monotonic clock. The span open on a thread is the parent of the next
one opened there, so a layer's self time can be computed; counts
(bytes, pages, rows) travel as attributes of the span around the work."""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
import uuid
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation as _Annotation

log = logging.getLogger("presto_tpu.tracing")

#: what the profiler sees of a span named <name>
ANNOTATION_PREFIX = "presto:"

#: wall time at perf_counter() == 0, read once: spans stamp the
#: monotonic clock, and the wire format keeps epoch seconds so span
#: dumps of several processes still lie on one axis
_EPOCH = time.time() - time.perf_counter()


def now() -> float:
    """The span clock: monotonic, in epoch seconds."""
    return _EPOCH + time.perf_counter()


#: wire header carrying "<trace_id>;<parent_span_id>" on every
#: coordinator -> worker RPC (PrestoHeaders-style custom header)
TRACE_HEADER = "X-Presto-Trace"


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: Optional[float] = None
    attributes: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #: process-unique id — remote-span stitching dedupes on it
    span_id: str = ""
    #: parent span id (propagated cross-node via X-Presto-Trace)
    parent_id: str = ""

    def __post_init__(self):
        if not self.span_id:
            self.span_id = uuid.uuid4().hex[:16]

    @property
    def duration_s(self) -> Optional[float]:
        return None if self.end is None else self.end - self.start

    def to_json(self) -> dict:
        return {"name": self.name, "start": self.start, "end": self.end,
                "spanId": self.span_id, "parentId": self.parent_id,
                "attributes": dict(self.attributes)}

    @staticmethod
    def from_json(doc: dict) -> "Span":
        return Span(name=doc.get("name", "?"),
                    start=float(doc.get("start", 0.0)),
                    end=(None if doc.get("end") is None
                         else float(doc["end"])),
                    attributes=dict(doc.get("attributes") or {}),
                    span_id=str(doc.get("spanId") or ""),
                    parent_id=str(doc.get("parentId") or ""))


# --------------------------------------------------------------------------
# Trace-context propagation. The ACTIVE context is thread-local: the
# scheduler thread sets it for one query, and `transport.HttpClient`
# stamps every outgoing RPC on that thread with the header. (Watcher /
# puller helper threads deliberately do not inherit it — control-plane
# polls are not part of the query timeline.)
@dataclasses.dataclass(frozen=True)
class TraceContext:
    trace_id: str
    parent_span_id: str = ""

    def header_value(self) -> str:
        return f"{self.trace_id};{self.parent_span_id}"


_ACTIVE = threading.local()

# tid -> trace id mirror of the thread-local context. `threading.local`
# cannot be read from another thread, but the sampling profiler
# (obs/profiler.py) attributes stacks to the query each thread is
# working on — so trace_scope maintains this parallel map too. Guarded
# by its own lock; entries live exactly as long as the scope.
_THREAD_TRACES: Dict[int, str] = {}
_THREAD_TRACES_LOCK = threading.Lock()


def current_trace() -> Optional[TraceContext]:
    return getattr(_ACTIVE, "ctx", None)


def thread_traces() -> Dict[int, str]:
    """Snapshot of thread-id -> active trace id (profiler attribution)."""
    with _THREAD_TRACES_LOCK:
        return dict(_THREAD_TRACES)


@contextmanager
def trace_scope(trace_id: str, parent_span_id: str = ""):
    """Install a TraceContext for the current thread; outgoing RPCs via
    transport.HttpClient carry it as X-Presto-Trace until exit."""
    prev = getattr(_ACTIVE, "ctx", None)
    _ACTIVE.ctx = TraceContext(trace_id, parent_span_id)
    tid = threading.get_ident()
    with _THREAD_TRACES_LOCK:
        prev_tid = _THREAD_TRACES.get(tid)
        _THREAD_TRACES[tid] = trace_id
    try:
        yield _ACTIVE.ctx
    finally:
        _ACTIVE.ctx = prev
        with _THREAD_TRACES_LOCK:
            if prev_tid is None:
                _THREAD_TRACES.pop(tid, None)
            else:
                _THREAD_TRACES[tid] = prev_tid


@contextmanager
def root_scope(trace_id: str, sampled: bool):
    """The outermost entry of a statement on this thread decides whether
    it is traced and under which id: the first caller installs
    `trace_id` if its draw (`ObsConfig.sampled`, the server layer's
    policy) came out `sampled`; a caller inside an open scope (the
    cluster under the statement server, a subquery under its query)
    keeps what it finds, whatever its own draw. Yields the active
    TraceContext, or None for an unsampled statement."""
    ctx = current_trace()
    if ctx is not None or getattr(_ACTIVE, "unsampled", False):
        yield ctx
        return
    if sampled:
        with trace_scope(trace_id) as ctx:
            yield ctx
        return
    _ACTIVE.unsampled = True
    try:
        yield None
    finally:
        _ACTIVE.unsampled = False


def parse_trace_header(value: Optional[str]) -> Optional[TraceContext]:
    """'<trace_id>;<parent_span_id>' -> TraceContext (None on absent or
    malformed input — tracing is never a reason to fail an RPC)."""
    if not value:
        return None
    parts = value.split(";", 1)
    trace_id = parts[0].strip()
    if not trace_id:
        return None
    parent = parts[1].strip() if len(parts) > 1 else ""
    return TraceContext(trace_id, parent)


class Tracer:
    """Per-process tracer: spans grouped by trace id (query id). Bounded
    two ways: only the most recent `max_traces` query traces are
    retained (the reference's QueryTracker similarly caps
    finished-query history), and within one trace at most
    `max_spans_per_trace` spans are recorded — beyond that spans still
    time their bodies but are counted as dropped instead of growing the
    list without bound (a long-running query with per-chunk spans must
    not eat the heap)."""

    def __init__(self, max_traces: int = 256,
                 max_spans_per_trace: int = 2048):
        self._lock = threading.Lock()
        self.max_traces = max_traces
        self.max_spans_per_trace = max_spans_per_trace
        self.spans: Dict[str, List[Span]] = {}
        #: trace id -> spans dropped by the per-trace cap
        self.dropped: Dict[str, int] = {}

    def _store(self, trace_id: str, s: Span) -> bool:
        """Append under the caps; False when the span was dropped."""
        with self._lock:
            lst = self.spans.setdefault(trace_id, [])
            if len(lst) >= self.max_spans_per_trace:
                self.dropped[trace_id] = \
                    self.dropped.get(trace_id, 0) + 1
                kept = False
            else:
                lst.append(s)
                kept = True
            while len(self.spans) > self.max_traces:
                evicted = next(iter(self.spans))   # oldest insert
                self.spans.pop(evicted)
                self.dropped.pop(evicted, None)
        if not kept:
            from presto_tpu.obs.metrics import counter
            counter("presto_tpu_tracer_dropped_spans_total",
                    "Spans dropped by the per-trace span cap").inc()
        return kept

    @contextmanager
    def span(self, trace_id: Optional[str], name: str,
             parent_id: Optional[str] = None, **attributes):
        """Time the body as span `name`, in the profiler's host plane
        and, where the thread has a trace, in memory. `trace_id` None
        takes the thread's `current_trace()` (the execution layer knows
        no query id); on a thread with none the span still annotates
        and is stored nowhere. The parent is `parent_id` if given (a
        helper thread working for a span of another thread), else the
        span open on this thread, else the propagated context's.
        Attributes set on the yielded span inside the body reach both
        sinks when it ends."""
        ctx = current_trace()
        if trace_id is None and ctx is not None:
            trace_id = ctx.trace_id
        outer = getattr(_ACTIVE, "span", None)
        if parent_id is None:
            if outer is not None and outer[0] == trace_id:
                parent_id = outer[1].span_id
            elif ctx is not None and ctx.trace_id == trace_id:
                parent_id = ctx.parent_span_id
            else:
                parent_id = ""
        s = Span(name, 0.0, attributes=dict(attributes),
                 parent_id=parent_id)
        _ACTIVE.span = (trace_id, s)
        with _Annotation(ANNOTATION_PREFIX + name, **attributes) as mark:
            s.start = now()
            if trace_id is not None:
                self._store(trace_id, s)
            try:
                yield s
            finally:
                s.end = now()
                _ACTIVE.span = outer
                late = {k: v for k, v in s.attributes.items()
                        if k not in attributes or attributes[k] != v}
                if late:
                    mark.set_metadata(**late)

    def record(self, trace_id: Optional[str], name: str, start: float,
               end: Optional[float] = None, parent_id: str = "",
               mark: bool = False, **attributes) -> Optional[Span]:
        """Record a span that was timed elsewhere, on the span clock
        (`now()`): the worker's per-island `op:` spans, a wait no
        thread sits in, a GET that turned out to be a pull. `mark` also
        leaves a marker in the profiler's trace at the moment of the
        call, carrying `waited_ms`, from which a reader back-dates the
        interval."""
        if mark:
            waited_ms = 1e3 * ((now() if end is None else end) - start)
            with _Annotation(ANNOTATION_PREFIX + name,
                             waited_ms=waited_ms, **attributes):
                pass
            attributes["waited_ms"] = waited_ms
        if trace_id is None:
            ctx = current_trace()
            if ctx is None:
                return None
            trace_id = ctx.trace_id
        s = Span(name, start, end=end, attributes=dict(attributes),
                 parent_id=parent_id)
        self._store(trace_id, s)
        return s

    def add(self, name: str, **counts) -> None:
        """Add counts to the span `name` open on this thread, if one is:
        the code that knows a count reports it, the span around the work
        carries it."""
        outer = getattr(_ACTIVE, "span", None)
        if outer is not None and outer[1].name == name:
            at = outer[1].attributes
            for k, v in counts.items():
                at[k] = at.get(k, 0) + v

    def here(self) -> Optional[TraceContext]:
        """This thread's trace with the span open on it as the parent:
        what a helper thread needs to record spans for this one."""
        ctx = current_trace()
        outer = getattr(_ACTIVE, "span", None)
        if ctx is None:
            return None
        if outer is not None and outer[0] == ctx.trace_id:
            return TraceContext(ctx.trace_id, outer[1].span_id)
        return ctx

    def get(self, trace_id: str) -> List[Span]:
        with self._lock:
            return list(self.spans.get(trace_id, []))

    def dropped_spans(self, trace_id: str) -> int:
        with self._lock:
            return self.dropped.get(trace_id, 0)

    # ---- cross-node stitching -------------------------------------------
    def to_json(self, trace_id: str) -> dict:
        """Wire dump for GET /v1/trace/{trace_id}."""
        return {"traceId": trace_id,
                "spans": [s.to_json() for s in self.get(trace_id)],
                "droppedSpans": self.dropped_spans(trace_id)}

    def merge_remote(self, trace_id: str, doc: dict) -> int:
        """Stitch a worker's span dump into this tracer's trace.
        Dedupes by span_id, so re-scrapes — and the in-process cluster,
        where workers share this very tracer — never duplicate spans.
        Returns the number of spans added."""
        have = {s.span_id for s in self.get(trace_id)}
        added = 0
        for sdoc in doc.get("spans", []):
            s = Span.from_json(sdoc)
            if s.span_id in have:
                continue
            if not self._store(trace_id, s):
                break
            have.add(s.span_id)
            added += 1
        return added

    def render(self, trace_id: str) -> str:
        """One cross-node timeline: spans sorted by start, offsets
        relative to the earliest span, worker column from the `worker`
        attribute of the span or of its nearest ancestor that has one
        (coordinator spans carry none)."""
        spans = sorted(self.get(trace_id), key=lambda s: s.start)
        if not spans:
            return ""
        t0 = spans[0].start
        by_id = {s.span_id: s for s in spans}

        def node_of(s: Span) -> str:
            # the nearest span up the chain that names its node
            for _ in range(len(spans)):
                if "worker" in s.attributes or s.parent_id not in by_id:
                    break
                s = by_id[s.parent_id]
            return str(s.attributes.get("worker", "coordinator"))

        out = []
        for s in spans:
            d = f"{s.duration_s * 1000:.1f}ms" if s.end else "…"
            attrs = dict(s.attributes)
            worker = node_of(s)
            attrs.pop("worker", None)
            rest = " ".join(f"{k}={v}" for k, v in attrs.items())
            out.append(f"+{(s.start - t0) * 1000:8.1f}ms "
                       f"{worker:<16} {s.name:<24} {d:>10} {rest}")
        ndrop = self.dropped_spans(trace_id)
        if ndrop:
            out.append(f"… {ndrop} span(s) dropped by the per-trace cap")
        return "\n".join(out)


@dataclasses.dataclass(frozen=True)
class QueryEvent:
    """QueryCreated/QueryCompleted payload subset (reference:
    spi/eventlistener/QueryCompletedEvent.java)."""
    kind: str   # "created" | "completed" | "failed" | "wide" | "alert"
    query_id: str
    sql: str
    wall_s: Optional[float] = None
    rows: Optional[int] = None
    error: Optional[str] = None
    #: structured payload for "wide" events (obs/wide_events.py): the
    #: full per-query stat surface as one JSON-compatible dict
    detail: Optional[dict] = None


class EventListenerManager:
    def __init__(self):
        self._listeners: List[Callable[[QueryEvent], None]] = []
        self._lock = threading.Lock()
        self._logged_failures: set = set()

    def register(self, listener: Callable[[QueryEvent], None]):
        with self._lock:
            self._listeners.append(listener)

    def unregister(self, listener: Callable[[QueryEvent], None]):
        with self._lock:
            if listener in self._listeners:
                self._listeners.remove(listener)

    def emit(self, event: QueryEvent):
        with self._lock:
            listeners = list(self._listeners)
        for cb in listeners:
            try:
                cb(event)
            except Exception:   # noqa: BLE001 — listeners must not kill queries
                # ...but they must not fail INVISIBLY either: count every
                # swallow in the registry and log each failing listener
                # once (not once per event — a broken listener on a busy
                # cluster would flood the log)
                from presto_tpu.obs.metrics import counter
                counter("presto_tpu_event_listener_errors_total",
                        "Exceptions swallowed from event listeners"
                        ).inc()
                key = id(cb)
                if key not in self._logged_failures:
                    self._logged_failures.add(key)
                    log.exception(
                        "event listener %r raised on %s event "
                        "(logged once; further failures only counted)",
                        getattr(cb, "__name__", cb), event.kind)


# process-wide defaults (the Guice-singleton analog)
TRACER = Tracer()
EVENTS = EventListenerManager()


@contextmanager
def query_lifecycle(qid: str, sql: str):
    """Shared created/failed/completed emission around one query's
    execution (used by LocalEngine and TpuCluster). Yields a one-slot
    list the body fills with the result rows so `completed` can report
    the row count."""
    t0 = time.perf_counter()
    EVENTS.emit(QueryEvent("created", qid, sql))
    box: List[Any] = [None]
    try:
        yield box
    except Exception as e:
        EVENTS.emit(QueryEvent("failed", qid, sql,
                               wall_s=time.perf_counter() - t0,
                               error=str(e)))
        raise
    rows = box[0]
    EVENTS.emit(QueryEvent(
        "completed", qid, sql, wall_s=time.perf_counter() - t0,
        rows=len(rows) if rows is not None else None))
