"""Tracing: Tracer/Span facade, context propagation, OTLP export.

Parity target: the reference's tracing package (tracing/tracing.go:27-76
Tracer/Span interfaces + GlobalTracer; opentracing/jaeger adapter
tracing/opentracing/opentracing.go:36).  Spans wrap executor ops and API
methods; the HTTP layer extracts/injects W3C ``traceparent`` headers the
way the reference's middleware does (http/handler.go:321), so a trace
follows a query across the scatter-gather fan-out to remote nodes.

Span parentage is implicit via a per-thread active-span stack (the
moral equivalent of context.Context threading in Go): ``start_span``
parents to the innermost active span unless an explicit parent is
given; cross-thread and cross-process boundaries re-attach via
``current_span()`` capture and ``inject_headers``/``extract_headers``.

Export: ``MemTracer`` records in-process (tests, /debug); ``OtlpExporter``
ships finished spans as OTLP/HTTP JSON to a collector endpoint from a
background thread.
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
import uuid

_active = threading.local()  # .stack: list of active spans (innermost last)


def new_trace_id() -> str:
    """A fresh 32-hex W3C trace id — for work that originates inside
    the cluster (anti-entropy rounds, hint replay, rebalance plans)
    rather than behind an instrumented client."""
    return uuid.uuid4().hex


def normalize_trace_id(tid) -> str:
    """Canonical 32-hex lowercase form.  Flight records may carry a
    short self-generated id (observe.QueryRecord's 20-hex fallback)
    while traceparent headers zero-pad to 32 — every cross-node match
    on trace id must compare normalized forms."""
    return f"{tid:0>32}".lower()


def current_span() -> "Span | None":
    stack = getattr(_active, "stack", None)
    return stack[-1] if stack else None


def _push(span) -> None:
    if not hasattr(_active, "stack"):
        _active.stack = []
    _active.stack.append(span)


def _pop(span) -> None:
    stack = getattr(_active, "stack", None)
    if stack and stack[-1] is span:
        stack.pop()


class Span:
    """No-op span; also the base for recorded spans.  Entering a span
    makes it the thread's active span (the default parent)."""

    trace_id: str | None = None
    span_id: str | None = None

    def set_tag(self, key: str, value) -> None:
        pass

    def finish(self) -> None:
        pass

    def __enter__(self):
        _push(self)
        return self

    def __exit__(self, *exc):
        _pop(self)
        self.finish()
        return False


class RemoteParent(Span):
    """A span handle reconstructed from a traceparent header — parent
    for server-side spans of a propagated trace."""

    def __init__(self, trace_id: str, span_id: str):
        self.trace_id = trace_id
        self.span_id = span_id
        self.name = "remote"


class ContextSpan(Span):
    """Trace identity WITHOUT recording: the nop tracer's propagation
    vehicle.  Before this class the default ``Tracer`` returned a bare
    ``Span()`` for every start_span call, which silently DROPPED an
    inbound RemoteParent — a remote node under the nop tracer
    self-generated a fresh record id and cross-node trace assembly had
    nothing to join on.  A ContextSpan inherits the ids (so
    ``inject_headers``/``active_trace_id`` keep working downstream)
    and records nothing; when no trace is in scope the nop tracer
    still returns the zero-cost bare ``Span()``."""

    def __init__(self, trace_id: str, span_id: str | None = None):
        self.trace_id = trace_id
        self._span_id = span_id

    @property
    def span_id(self) -> str:
        # drawn when an outgoing request first asks for it: a read that
        # sends none (every single-node query) draws none
        if self._span_id is None:
            self._span_id = uuid.uuid4().hex[:16]
        return self._span_id


def inject_headers(span: Span | None = None) -> dict[str, str]:
    """W3C trace-context header for an outgoing request (reference
    middleware inject, http/handler.go:321).  Empty when no recorded
    span is active (nop tracer: nothing to propagate)."""
    span = span or current_span()
    if span is None or not span.trace_id:
        return {}
    return {"traceparent":
            f"00-{span.trace_id:0>32}-{span.span_id:0>16}-01"}


def extract_headers(headers) -> RemoteParent | None:
    """Parse a traceparent header (mapping or http.client-style
    getter) into a RemoteParent, or None."""
    get = headers.get if hasattr(headers, "get") else None
    raw = get("traceparent") if get else None
    if not raw:
        return None
    parts = raw.strip().split("-")
    if len(parts) != 4 or len(parts[1]) != 32 or len(parts[2]) != 16:
        return None
    trace_id, span_id = parts[1], parts[2]
    hexdigits = set("0123456789abcdef")
    if (len(parts[0]) != 2 or not set(parts[0]) <= hexdigits
            or parts[0] == "ff"):
        return None  # W3C: malformed or explicitly-invalid version
    if not (set(trace_id) <= hexdigits and set(span_id) <= hexdigits):
        return None  # W3C: non-hex ids are invalid
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None  # W3C: all-zero ids mean "absent"
    return RemoteParent(trace_id, span_id)


class Tracer:
    def start_span(self, name: str, parent: "Span | None" = None) -> Span:
        if parent is None:
            parent = current_span()
        if parent is not None and parent.trace_id:
            # keep a propagated trace alive through the nop tracer:
            # server-side spans of a traced query must carry the ids
            # forward (records, downstream RPC headers) even when
            # nothing is being recorded locally
            return ContextSpan(parent.trace_id)
        return Span()


class RecordedSpan(Span):
    def __init__(self, tracer: "MemTracer", name: str,
                 parent: "Span | None"):
        self.tracer = tracer
        self.name = name
        self.trace_id = (parent.trace_id if parent is not None
                         and parent.trace_id else uuid.uuid4().hex)
        self.span_id = uuid.uuid4().hex[:16]
        self.parent_span_id = (parent.span_id if parent is not None
                               else None)
        self.parent_name = getattr(parent, "name", None)
        self.tags: dict = {}
        self.start_unix_ns = time.time_ns()
        self.start_ns = time.perf_counter_ns()
        self.duration_ns: int | None = None

    def set_tag(self, key, value):
        self.tags[key] = value

    def finish(self):
        if self.duration_ns is None:
            self.duration_ns = time.perf_counter_ns() - self.start_ns
            self.tracer._record(self)


class MemTracer(Tracer):
    """In-memory recording tracer — the test/debug backend; exporters
    subclass and ship finished spans instead."""

    def __init__(self, max_spans: int = 10000):
        self.max_spans = max_spans
        self._lock = threading.Lock()
        self.spans: list[RecordedSpan] = []

    def start_span(self, name, parent=None):
        if parent is None:
            parent = current_span()
        return RecordedSpan(self, name, parent)

    def _record(self, span: RecordedSpan) -> None:
        with self._lock:
            if len(self.spans) < self.max_spans:
                self.spans.append(span)

    def finished(self, name: str | None = None) -> list[RecordedSpan]:
        with self._lock:
            return [s for s in self.spans if name is None or s.name == name]


def _otlp_json(spans, service: str) -> bytes:
    def attrs(d):
        return [{"key": str(k), "value": {"stringValue": str(v)}}
                for k, v in d.items()]

    out = []
    for s in spans:
        rec = {
            "traceId": f"{s.trace_id:0>32}",
            "spanId": f"{s.span_id:0>16}",
            "name": s.name,
            "kind": 1,
            "startTimeUnixNano": str(s.start_unix_ns),
            "endTimeUnixNano": str(s.start_unix_ns + (s.duration_ns or 0)),
            "attributes": attrs(s.tags),
        }
        if s.parent_span_id:
            rec["parentSpanId"] = f"{s.parent_span_id:0>16}"
        out.append(rec)
    return json.dumps({"resourceSpans": [{
        "resource": {"attributes": attrs({"service.name": service})},
        "scopeSpans": [{"scope": {"name": "pilosa_tpu"}, "spans": out}],
    }]}).encode()


class OtlpExporter(MemTracer):
    """Ships finished spans to an OTLP/HTTP collector (`/v1/traces`)
    in batches from a daemon thread — the jaeger-adapter slot of the
    reference (tracing/opentracing/opentracing.go:36), speaking the
    open standard instead."""

    def __init__(self, endpoint: str, service: str = "pilosa-tpu",
                 flush_interval: float = 2.0, max_batch: int = 512):
        super().__init__(max_spans=1 << 30)
        self.endpoint = endpoint.rstrip("/")
        self.service = service
        self.flush_interval = flush_interval
        self.max_batch = max_batch
        self._buf: list[RecordedSpan] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="otlp-exporter")
        self._thread.start()

    MAX_BUFFER = 16384  # spans; beyond this the oldest drop (outage cap)

    def _record(self, span: RecordedSpan) -> None:
        with self._lock:
            self._buf.append(span)
            if len(self._buf) > self.MAX_BUFFER:
                del self._buf[: len(self._buf) - self.MAX_BUFFER]

    def _loop(self) -> None:
        while not self._stop.wait(self.flush_interval):
            self.flush()
        self.flush()

    def flush(self) -> None:
        while True:
            with self._lock:
                batch = self._buf[:self.max_batch]
                self._buf = self._buf[self.max_batch:]
            if not batch:
                return
            import urllib.request

            body = _otlp_json(batch, self.service)
            req = urllib.request.Request(
                self.endpoint + "/v1/traces", data=body,
                headers={"Content-Type": "application/json"})
            try:
                urllib.request.urlopen(req, timeout=5).read()
            except Exception:
                # collector outage never affects serving — but a
                # transient error must not LOSE the popped batch: put
                # it back for the next tick (MAX_BUFFER still caps
                # memory during a long outage)
                with self._lock:
                    self._buf[:0] = batch
                    if len(self._buf) > self.MAX_BUFFER:
                        del self._buf[: len(self._buf) - self.MAX_BUFFER]
                return

    def close(self) -> None:
        """Stop the exporter thread and ship the final span batch.
        Idempotent; wired into the server's shutdown closers
        (cmd.run_server) — without the explicit final flush the batch
        recorded since the last 2 s tick would die with the daemon
        thread.  The post-join flush also covers a thread that died or
        missed the join window, and the global tracer is reset so
        spans finished after shutdown stop buffering into a dead
        exporter."""
        self._stop.set()
        self._thread.join(timeout=10)
        self.flush()
        if global_tracer() is self:
            set_global_tracer(Tracer())


_global = Tracer()
_global_lock = threading.Lock()


def global_tracer() -> Tracer:
    return _global


def set_global_tracer(t: Tracer) -> None:
    global _global
    with _global_lock:
        _global = t


def start_span(name: str, parent: Span | None = None) -> Span:
    """(reference tracing.StartSpanFromContext, tracing/tracing.go:60)"""
    return _global.start_span(name, parent)


@contextlib.contextmanager
def propagate(trace_id):
    """Make ``trace_id`` this thread's active trace for the scope —
    the cross-thread/cross-subsystem re-attach primitive.  Worker
    threads (hedge IO, hint replay, AE rounds, rebalance transfers,
    debug fan-in) run outside the request thread's span stack; wrapping
    their work in ``propagate(tid)`` makes every RPC they issue carry
    ``traceparent`` and every record they produce link the trace.

    No-ops (zero allocation) for a falsy id, and defers to an already-
    active traced span — an explicit propagate never clobbers real
    span parentage established by a recording tracer."""
    cs = push_context(trace_id)
    try:
        # an active traced span keeps its parentage: it is what the
        # scope runs under
        yield cs if cs is not None or not trace_id else current_span()
    finally:
        if cs is not None:
            _pop(cs)


def push_context(trace_id) -> "ContextSpan | None":
    """:func:`propagate`'s entry, for a scope that is an object of the
    caller's own (``Executor.execute``'s): make ``trace_id`` this
    thread's active trace and return the span to hand to
    :func:`pop_context`, or None where nothing was pushed (a falsy id,
    or a traced span is active already and keeps its parentage)."""
    if not trace_id:
        return None
    span = current_span()
    if span is not None and span.trace_id:
        return None
    cs = ContextSpan(normalize_trace_id(trace_id))
    _push(cs)
    return cs


def pop_context(cs: "ContextSpan | None") -> None:
    if cs is not None:
        _pop(cs)


def active_trace_id() -> str | None:
    """Trace id of this thread's innermost active span, or None under
    the nop tracer.  The query flight recorder (pilosa_tpu.observe)
    stamps it on each QueryRecord so a /debug/queries entry, a slow-
    query log line, and a histogram exemplar all share the id of the
    exported span tree — the span -> record linkage."""
    span = current_span()
    return span.trace_id if span is not None else None
