"""Query flight recorder: per-query telemetry for the dispatch-bound
serving path.

The round-5 verdict's last big unknown is the dispatch window — the
committed chip number understates the engine ~5.6x — yet process-wide
stats (count/sum/min/max) cannot attribute latency to a QUERY.  This
module holds one ``QueryRecord`` per in-flight query: a SPAN TREE of
the request's host phases from the handler's dispatch to the response
(``observe.span``: one recording primitive, both times on one clock,
parent ids, the same spans on the profiler's timeline while a capture
runs), per-shard and per-node map timings, the device-launch count from
the ``ops/bitmap.py`` dispatch hook, coalescer batch occupancy, the
fused-vs-fallback expression path, and result sizes — the per-stage
timing discipline DrJAX (arxiv 2403.07128) and Ragged Paged Attention
(arxiv 2604.15464) use to diagnose TPU dispatch overhead, applied to
the reference's map-reduce executor (executor.go:2455).

Exposure (server/handler.py):

- ``GET /debug/queries`` — active-query table + ring buffer of recent
  records (``?sort=``/``?min_ms=``).
- ``?profile=1`` on ``POST /index/{index}/query`` — the breakdown
  inline in the response.
- slow-query log — ``[observe] long_query_time`` (config.py), logging
  PQL + trace id + breakdown (the reference's ``LongQueryTime``,
  api.go:1157, with a breakdown attached).

Lock discipline: the record is assembled THREAD-LOCALLY (``attach``
installs it on worker threads for the duration of one shard's
evaluation; list appends and ``next()`` on the span-id counter are
GIL-atomic) — no lock on the per-span / per-launch hot path.  The
recorder's own lock is touched once at begin and once at publish
(keeping the active table and ring buffer safely iterable from
/debug/queries), plus the stats registry's on the latency-histogram
observation.  What that costs: the whole span spine read
``read_p50_ms`` 6.74 -> 6.82 on ``seg-dense`` (the driver's PR 24
line) and ``?profile=1`` adds 0.25 to 0.45 ms (both PERF.md section
6); the clock reads, spans and lock takes of one coalesced Count, and
a disabled recorder beginning no record, are counts
``tests/test_observer_cost.py`` holds.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import Counter, deque

from pilosa_tpu import lockcheck as _lockcheck
from pilosa_tpu import tracing as _tracing

# .rec: active QueryRecord; .req: the handler's Request; .open: id of
# the innermost open span on this thread; .last: last published record
_tls = threading.local()

#: The span clock: ``time.perf_counter_ns`` (CLOCK_MONOTONIC on Linux —
#: what ``t0_ns``, the harness's ``time.monotonic()`` and the two
#: clocks ``/debug/profiler/start`` returns all read).
clock_ns = time.perf_counter_ns
_thread_id = threading.get_ident

#: True while a device-profiler capture runs (``perfobs.profiler_start``
#: / ``stop`` flip it through :func:`set_capturing`): every span then
#: also enters a ``jax.profiler.TraceAnnotation`` carrying the record's
#: trace id, so the capture's host plane shows the same spans on the
#: device plane's timeline.  With no capture the annotation class is
#: never touched.
capturing = False
_annotation = None  # jax.profiler.TraceAnnotation while capturing


def set_capturing(on: bool) -> None:
    global capturing, _annotation
    if on and _annotation is None:
        import jax

        _annotation = jax.profiler.TraceAnnotation
    capturing = bool(on)


#: PQL longer than this is truncated in records (a query string is
#: operator-facing debug data, not an archive).
MAX_PQL = 2048

#: Detail-list caps: the ring buffer pins `recent` finished records,
#: so a 10k-shard per-shard-path query must not make each record
#: hundreds of KB.  Per-shard timings keep the first MAX_SHARD_TIMINGS
#: entries (shards_n still reports the true fan-out); launch names cap
#: at MAX_LAUNCHES — far above any real query, so deviceLaunches stays
#: exact everywhere the regression tests pin it, while a pathological
#: loop cannot grow a record without bound.
MAX_SHARD_TIMINGS = 4096
MAX_LAUNCHES = 65536
#: Spans per request (a per-shard map over thousands of shards opens
#: a few per shard); past it spans still time and parent, unrecorded.
MAX_SPANS = 4096


def current() -> "QueryRecord | None":
    """The query record being assembled on THIS thread, or None.  The
    executor's map wrappers re-``attach`` it on pool workers, so shard
    evaluations tick the right record."""
    return getattr(_tls, "rec", None)


class attach:
    """Install a record (or None) as this thread's active record for a
    scope, with the span its children hang under: ``parent`` when given
    (a pool worker passes the :func:`open_span` it captured on the
    submitting thread), else the record's ``exec`` span.  Re-entrant:
    restores whatever was active before, so a remote re-execution
    beginning its OWN record inside an IO thread shadows rather than
    clobbers."""

    __slots__ = ("rec", "parent", "_prev", "_prev_open")

    def __init__(self, rec: "QueryRecord | None",
                 parent: int | None = None):
        self.rec = rec
        self.parent = parent

    def __enter__(self):
        self._prev = getattr(_tls, "rec", None)
        self._prev_open = getattr(_tls, "open", 0)
        rec = _tls.rec = self.rec
        if self.parent is not None:
            _tls.open = self.parent
        elif rec is not None:
            _tls.open = rec.exec_id
        return rec

    def __exit__(self, *exc):
        _tls.rec = self._prev
        _tls.open = self._prev_open
        return False


def open_span() -> int:
    """Id of the innermost span open on this thread (0: none) — what a
    caller captures before handing work to another thread."""
    return getattr(_tls, "open", 0)


class Request:
    """The handler's side of one request: the root ``http.request``
    span, and what happens before ``Executor.execute`` opens the flight
    record (admission wait, body read, parse) or after it publishes
    (serialisation).  Installed as a thread-local for the request's
    scope; ``FlightRecorder.begin`` ADOPTS it — the record takes over
    this object's span list, id counter and admission stamp, so both
    write one tree and no second record is opened.  ``arrived_ns`` is
    the clock when the request line had been read: the root starts
    there, and what the HTTP server did until the handler's dispatch
    (header parsing) is the ``http.parse`` span."""

    __slots__ = ("spans", "ids", "admission", "start_ns", "end_ns",
                 "_prev", "_prev_open", "_ann")

    def __init__(self, arrived_ns: int = 0):
        self.spans: list[tuple] = []
        self.ids = itertools.count(3)  # 1 is the root, 2 http.parse
        self.admission: dict | None = None
        self.start_ns = arrived_ns
        self.end_ns = 0  # 0 while the request is open

    def __enter__(self):
        self._prev = getattr(_tls, "req", None)
        self._prev_open = getattr(_tls, "open", 0)
        _tls.req = self
        _tls.open = 1
        self._ann = None
        if capturing:
            self._ann = _annotation("http.request")
            self._ann.__enter__()
        now = clock_ns()
        if self.start_ns:
            self.spans.append((2, 1, "http.parse", self.start_ns, now,
                               _thread_id(), None))
        else:
            self.start_ns = now
        return self

    def __exit__(self, *exc):
        self.end_ns = clock_ns()
        self.spans.append((1, 0, "http.request", self.start_ns,
                           self.end_ns, _thread_id(), None))
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _tls.req = self._prev
        _tls.open = self._prev_open
        return False


class _NoSpan:
    """What :func:`span` returns when nothing records: one shared
    object, no clock read."""

    __slots__ = ()
    id = start_ns = end_ns = 0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **counts) -> None:
        pass

    def note_engine(self) -> None:
        pass

    def before(self, name: str) -> None:
        pass


NOSPAN = _NoSpan()


class _Span:
    __slots__ = ("sink", "name", "counts", "timer", "export", "id",
                 "parent", "start_ns", "end_ns", "_x", "_ann")

    def __init__(self, sink, name, start_ns, timer, export, counts):
        self.sink = sink
        self.name = name
        self.start_ns = start_ns
        self.timer = timer
        self.export = export
        self.counts = counts
        self.id = self.parent = self.end_ns = 0

    def note(self, **counts) -> None:
        """Counts known only once the work is done (``putBytes``,
        ``engine``, ``batch``)."""
        if self.counts is None:
            self.counts = counts
        else:
            self.counts.update(counts)

    def note_engine(self) -> None:
        """Stamp a ``launch`` span with the engine its record was just
        attributed to (``perfobs.sample``: last launch wins)."""
        engine = getattr(self.sink, "engine", None)
        if engine is not None:
            self.note(engine=engine)

    def before(self, name: str) -> None:
        """Name what this thread did between its last phase under this
        span's parent (:func:`since`) and this span's start, as a
        sibling written from those two clock reads: ``route`` before
        ``stage``.  Called inside the ``with``; nothing is written
        where no earlier stamp is known."""
        sink = self.sink
        if sink is None:
            return
        t0 = _since(sink, self.parent)
        if 0 < t0 <= self.start_ns and len(sink.spans) < MAX_SPANS:
            sink.spans.append((next(sink.ids), self.parent, name, t0,
                               self.start_ns, _thread_id(), None))

    def __enter__(self):
        sink = self.sink
        if sink is not None:
            self.id = next(sink.ids)
            self.parent = getattr(_tls, "open", 0)
            _tls.open = self.id
        self._x = self._ann = None
        if self.export is not None:
            self._x = _tracing.start_span(self.export)
            for k, v in (self.counts or {}).items():
                self._x.set_tag(k, v)
            self._x.__enter__()
        if capturing:
            tid = getattr(sink, "trace_id", None)
            self._ann = (_annotation(self.name, rid=tid) if tid
                         else _annotation(self.name))
            self._ann.__enter__()
        if not self.start_ns:
            self.start_ns = clock_ns()
        return self

    def __exit__(self, *exc):
        self.end_ns = end = clock_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self._x is not None:
            self._x.__exit__(*exc)
        sink = self.sink
        if sink is not None:
            _tls.open = self.parent
            if len(sink.spans) < MAX_SPANS:
                sink.spans.append((self.id, self.parent, self.name,
                                   self.start_ns, end, _thread_id(),
                                   self.counts))
        if self.timer is not None:
            self.timer[0].timing(self.timer[1], end - self.start_ns)
        return False


def span(name: str, start_ns: int = 0, timer: tuple | None = None,
         export: str | None = None, **counts):
    """Time one host phase of the request this thread serves.

    On exit ONE tuple ``(id, parent id, name, start_ns, end_ns, thread,
    counts)`` is appended to the thread's current flight record (or,
    before/after it, to the handler's :class:`Request`); the parent is
    the innermost span open on this thread.  No lock; list appends and
    ``next()`` on the id counter are GIL-atomic.  While a profiler
    capture runs the span is also a ``TraceAnnotation``.

    ``timer=(stats, name)`` feeds the same duration to a stats timing;
    ``export`` opens the ``tracing.py`` span of that name when a
    recording tracer is installed (OTLP export), tagged with the
    counts; ``start_ns`` backdates the start to a clock read the caller
    already took (:func:`since` has the end of the phase before).  With
    no record, no timer and no recording tracer this returns the shared
    :data:`NOSPAN` and reads no clock."""
    sink = getattr(_tls, "rec", None) or getattr(_tls, "req", None)
    if export is not None and not isinstance(
            _tracing.global_tracer(), _tracing.MemTracer):
        export = None
    if sink is None and timer is None and export is None:
        return NOSPAN
    return _Span(sink, name, start_ns, timer, export, counts or None)


def _since(sink, parent: int) -> int:
    me = _thread_id()
    for s in reversed(sink.spans):
        if s[1] == parent and s[5] == me:
            return s[4]
    return sink.t0_ns if parent == getattr(sink, "exec_id", None) else 0


def since() -> int:
    """The clock read that ended this thread's last phase under the
    span now open: the end of the newest span THIS thread wrote with
    that parent, else the record's ``t0_ns`` when the open span is its
    ``exec``, else 0 (unknown: a span given that reads its own clock).
    A ``start_ns`` for a span that names the work since then at no
    clock read (``api.open``); never earlier than the parent's start,
    never another thread's stamp."""
    sink = getattr(_tls, "rec", None) or getattr(_tls, "req", None)
    return 0 if sink is None else _since(sink, getattr(_tls, "open", 0))


def take_last() -> "QueryRecord | None":
    """Pop the record most recently PUBLISHED on this thread (the
    ``?profile=1`` handoff: the handler thread that ran the query reads
    its own record back).  Clears on read so a bypassed execution (the
    SPMD collective path publishes its own record; a parse error
    publishes none) can never serve a stale profile."""
    rec = getattr(_tls, "last", None)
    _tls.last = None
    return rec


class AccessStats:
    """Per-cache-entry access statistics for the predictive
    prefetcher (runtime/prefetch.py): every tiered stack access —
    HBM hit, host-tier promotion, or cold build — ticks a decayed
    score per entry id, so 'which demoted entries is traffic about to
    want' is answerable by rank.  Scores decay by half every
    ``HALF_LIFE_S`` so yesterday's hot rows don't pin today's
    prefetch bandwidth; the table is LRU-capped (a per-row cache key
    churn must not grow it without bound).

    Lock discipline: one short lock per note — the note sits on the
    stack-accessor path (~µs against a rebuild measured in ms), not
    on the per-dispatch hot path."""

    HALF_LIFE_S = 30.0
    MAX_ENTRIES = 4096

    def __init__(self):
        self._lock = threading.Lock()
        # eid -> [score, last_monotonic]; insertion order = LRU
        self._scores: dict = {}

    def note(self, *eids) -> None:
        now = time.monotonic()
        scores = self._scores
        with self._lock:
            for eid in eids:
                rec = scores.pop(eid, None)
                if rec is None:
                    rec = [0.0, now]
                    if len(scores) >= self.MAX_ENTRIES:
                        scores.pop(next(iter(scores)))
                score, last = rec
                score *= 0.5 ** ((now - last) / self.HALF_LIFE_S)
                scores[eid] = [score + 1.0, now]

    def score(self, eid) -> float:
        now = time.monotonic()
        with self._lock:
            rec = self._scores.get(eid)
            if rec is None:
                return 0.0
            return rec[0] * 0.5 ** ((now - rec[1]) / self.HALF_LIFE_S)

_access = AccessStats()


def access_stats() -> AccessStats:
    """The process-wide access-statistics table (process-wide like the
    residency budget the prefetcher feeds)."""
    return _access


def note_access(eid) -> None:
    _access.note(eid)


def note_accesses(eids: list) -> None:
    """One read's stack accesses, under one take of the table's lock."""
    _access.note(*eids)


def result_size(res) -> int:
    """Cheap size proxy for one query result: list length, populated
    shard-segment count for Row-shaped results (duck-typed on
    ``.segments`` — materializing columns just to count them would cost
    more than the query), 1 for scalars.  Never raises."""
    if isinstance(res, list):
        return len(res)
    segments = getattr(res, "segments", None)
    if segments is not None:
        try:
            return len(segments)
        except TypeError:
            return 1
    return 1


class QueryRecord:
    """One query's telemetry, assembled lock-free on the threads that
    execute it.  ``launches`` is a list (not an int) because list
    appends are GIL-atomic while ``+= 1`` is a read-modify-write race
    across map workers — and the launch NAMES are the breakdown."""

    __slots__ = (
        "qid", "trace_id", "index", "pql", "start_unix", "t0_ns",
        "elapsed_ns", "shards_n", "spans", "ids", "exec_id",
        "exec_parent", "req", "shard_ns", "node_ns",
        "launches", "path", "coalesce", "result_sizes", "error", "slow",
        "admission", "outcome", "compiles", "cached", "cache_key",
        "delta_notes", "compacted", "hedged", "hedge_wins",
        "hedge_losers", "missing_shards", "tier_notes", "tenant",
        "engine", "remote",
    )

    def __init__(self, qid: int, index: str, pql: str,
                 trace_id: str | None = None):
        self.qid = qid
        self.index = index
        self.pql = pql[:MAX_PQL]
        now_ns = time.time_ns()
        self.trace_id = trace_id or f"{now_ns:016x}{qid & 0xFFFF:04x}"
        self.start_unix = now_ns / 1e9
        self.t0_ns = clock_ns()
        self.elapsed_ns: int | None = None  # None while in flight
        self.shards_n = 0
        # the span tree: (id, parent, name, start_ns, end_ns, thread,
        # counts) tuples appended as spans CLOSE (observe.span).  A
        # record begun under a handler Request adopts its list, id
        # counter and root (FlightRecorder.begin); begun anywhere
        # else, its own ``exec`` span is the root
        self.spans: list[tuple] = []
        self.ids = itertools.count(1)
        self.exec_id = 1
        self.exec_parent = 0
        self.req: Request | None = None
        self.shard_ns: list[tuple[int, int]] = []     # (shard, ns)
        self.node_ns: list[tuple[str, int, int]] = [] # (node, ns, n_shards)
        self.launches: list[str] = []
        self.path: str | None = None  # fused|per-shard|coalesced|collective
        # the ONE canonical engine enum (pilosa_tpu.perfobs.ENGINES:
        # dense|gather|tape|vm|mesh|host|collective) — unifies the
        # scattered path string + tape/vm booleans; ``path`` stays
        # populated for compat.  Stamped by perfobs.sample per launch
        # (last launch wins — the engine that produced the result);
        # plain attribute store, race-free under the GIL
        self.engine: str | None = None
        self.coalesce: dict | None = None
        self.result_sizes: list[int] = []
        self.error: str | None = None
        self.slow = False
        # admission stamp ({"class", "queue_wait_ns"}) and outcome
        # (ok | error | shed | expired; None resolves at to_dict time)
        self.admission: dict | None = None
        self.outcome: str | None = None
        # XLA compiles this query triggered: (kernel, ns) pairs stamped
        # by pilosa_tpu.devobs — list appends are GIL-atomic, matching
        # the launches discipline
        self.compiles: list[tuple[str, int]] = []
        # result-cache outcome (runtime/resultcache): ``cached`` is
        # set when a cache hit served (part of) the query; the rendered
        # flag (to_dict) additionally requires zero device launches so
        # it keeps the documented "answered without device work on this
        # node" meaning.  ``cache_key`` (a stable digest) is stamped
        # whenever a canonical key was computed — hit or miss, so
        # /debug/queries correlates repeated shapes either way
        self.cached = False
        self.cache_key: str | None = None
        # streaming-ingest annotations (pilosa_tpu.ingest): rendered
        # ``deltaDepth`` counts the fused leaves this query evaluated
        # WITH a pending delta overlay (``dfuse`` nodes staged — how
        # much un-compacted write traffic the read absorbed); a list
        # because leaves stage on concurrent map workers and appends
        # are GIL-atomic (the launches discipline).  ``compacted``
        # marks that a merge of a pending delta ran inside this query
        # (a ?nodelta=1 escape, a whole-matrix path, or an export) —
        # "slow because it compacted", symmetric with ``compiled``;
        # a single idempotent True store, race-free
        self.delta_notes: list[int] = []
        self.compacted = False
        # failure-handling annotations (the chaos round): ``hedged``
        # counts remote flights this query re-issued to a replica
        # past the peer's latency threshold, ``hedge_wins`` how many
        # of those races the hedge side won; ``missing_shards`` are
        # the shards a ?partial=1 request accounted as unavailable
        # (or, on a ShardsUnavailableError, the shards that failed
        # it).  All touched only by the origin map thread.
        self.hedged = 0
        self.hedge_wins = 0
        # the LOSING side of each settled hedge race: (node, ns the
        # abandoned flight had been in the air when the race committed)
        # — cross-node trace assembly shows the loser's spans too, so
        # "we paid for two flights" is visible on the origin record.
        # List appends from the map loop thread only.
        self.hedge_losers: list[tuple[str, int]] = []
        self.missing_shards: list[int] = []
        # True for remote sub-executions (ExecOptions.remote): the
        # trace assembler tells origin records from per-node remote
        # map records by this flag when both share a trace id
        self.remote = False
        # the request's tenant id ([tenants] isolation; None for
        # anonymous/default-tier traffic) — stamped by the executor
        # from ExecOptions.tenant, rendered on /debug/queries and the
        # slow-query log so abusive-tenant triage reads straight off
        # the flight recorder
        self.tenant: str | None = None
        # tiered-residency attribution (runtime/residency.py):
        # (outcome, ns) per tiered stack access — outcome one of
        # ``hbm`` (resident hit), ``promoted`` (waited for an async
        # host->HBM promotion), ``fallback`` (served host-compute
        # past the promotion wait), ``cold`` (assembled from fragment
        # state).  List appends, GIL-atomic across map workers (the
        # launches discipline); rendered as the ``tier`` dict — the
        # stall-vs-hit split ?profile=1 and /debug/queries carry.
        self.tier_notes: list[tuple[str, int]] = []

    # ------------------------------------------------------------ notes

    def add_span(self, name: str, start_ns: int, end_ns: int,
                 parent: int | None = None, **counts) -> int:
        """Append a span whose two times were read elsewhere (a
        follower's view of its batch leader's launch) -> its id."""
        sid = next(self.ids)
        if len(self.spans) < MAX_SPANS:
            self.spans.append((
                sid, getattr(_tls, "open", 0) if parent is None
                else parent, name, start_ns, end_ns, _thread_id(),
                counts or None))
        return sid

    def note_launch(self, name: str) -> None:
        """One kernel launch (called from ops/bitmap.note_dispatch).
        List append is GIL-atomic; the len guard may overshoot the cap
        by a few concurrent appends, which only bounds memory, never
        undercounts below the cap."""
        if len(self.launches) < MAX_LAUNCHES:
            self.launches.append(name)

    def note_compile(self, kernel: str, ns: int) -> None:
        """One XLA compile paid by this query (devobs.instrument) —
        the "slow because it compiled" attribution."""
        if len(self.compiles) < 256:
            self.compiles.append((kernel, ns))

    def note_delta(self, n: int = 1) -> None:
        """``n`` fused leaves staged with a pending delta overlay
        (Executor._stage_leaves) — list append, GIL-atomic."""
        if len(self.delta_notes) < MAX_SHARD_TIMINGS:
            self.delta_notes.append(n)

    def note_shard(self, shard: int, ns: int) -> None:
        if len(self.shard_ns) < MAX_SHARD_TIMINGS:
            self.shard_ns.append((shard, ns))

    def note_node(self, node: str, ns: int, n_shards: int) -> None:
        self.node_ns.append((node, ns, n_shards))

    def note_shards(self, n: int) -> None:
        if n > self.shards_n:
            self.shards_n = n

    def note_path(self, path: str) -> None:
        self.path = path

    def note_engine(self, engine: str) -> None:
        """The canonical engine that executed (a perfobs.ENGINES
        value) — last launch wins, so a fallback ladder ends up
        attributed to the engine that actually produced the result."""
        self.engine = engine

    def note_tier(self, outcome: str, ns: int = 0,
                  times: int = 1) -> None:
        """One tiered stack access (``times`` alike): ``hbm`` |
        ``promoted`` | ``fallback`` | ``cold``, with the wall time the
        access cost this query (the promotion wait / rebuild — the
        stall side of stall-vs-hit).  List append, GIL-atomic."""
        room = MAX_SHARD_TIMINGS - len(self.tier_notes)
        if room > 0:
            self.tier_notes.extend([(outcome, ns)] * min(times, room))

    def note_missing(self, shard: int) -> None:
        """One shard accounted unavailable (partial degradation or a
        structured exhaustion error)."""
        if len(self.missing_shards) < MAX_SHARD_TIMINGS:
            self.missing_shards.append(shard)

    # ----------------------------------------------------------- export

    def elapsed_live_ns(self) -> int:
        """Elapsed so far (in-flight) or final elapsed (published)."""
        if self.elapsed_ns is not None:
            return self.elapsed_ns
        return time.perf_counter_ns() - self.t0_ns

    #: span -> the ``stages`` entry it renders as (``call.<Name>``
    #: renders as ``execute.<Name>``); spans not named here are detail
    #: the flat stage list never had
    _STAGES = ("translate", "translateResults", "map", "map.fused")

    def stages(self) -> list[tuple[str, int]]:
        """The flat (name, ns) stage list, in order of completion,
        rendered from the spans."""
        out = []
        for sp in sorted(self.spans, key=lambda sp: sp[4]):
            name = sp[2]
            if name.startswith("call."):
                name = "execute." + name[5:]
            elif name not in self._STAGES:
                continue
            out.append((name, sp[4] - sp[3]))
        return out

    def spans_dict(self) -> tuple[int, list[dict]]:
        """(root start on the span clock, the spans with times relative
        to it, by start).  A handler root that is still open — the
        inline ``?profile=1`` rendering happens inside it — is shown to
        this instant and marked ``open``."""
        spans = list(self.spans)
        req = self.req
        if req is not None and not req.end_ns:
            spans.append((1, 0, "http.request", req.start_ns, clock_ns(),
                          0, {"open": True}))
        root = min((sp[3] for sp in spans if not sp[1]),
                   default=self.t0_ns)
        out = []
        for sid, parent, name, start, end, thread, counts in sorted(
                spans, key=lambda sp: (sp[3], sp[0])):
            d = {"id": sid, "parent": parent, "name": name,
                 "startNs": start - root, "endNs": end - root,
                 "thread": thread}
            if counts:
                d.update(counts)
            out.append(d)
        return root, out

    def to_dict(self) -> dict:
        ms = 1e6
        root_ns, spans = self.spans_dict()
        d = {
            "id": self.qid,
            "traceID": self.trace_id,
            "index": self.index,
            "pql": self.pql,
            "startTime": self.start_unix,
            "elapsedMs": round(self.elapsed_live_ns() / ms, 3),
            "active": self.elapsed_ns is None,
            "shards": self.shards_n,
            "stages": [{"name": n, "ms": round(v / ms, 3)}
                       for n, v in self.stages()],
            # the span tree; rootStartNs is the root's start on the
            # clock /debug/profiler/start returns as perfCounterNs
            "rootStartNs": root_ns,
            "spans": spans,
            "shardTimings": [{"shard": s, "ms": round(v / ms, 3)}
                             for s, v in self.shard_ns],
            "nodeTimings": [{"node": n, "ms": round(v / ms, 3),
                             "shards": k}
                            for n, v, k in self.node_ns],
            "deviceLaunches": len(self.launches),
            "launchKinds": dict(Counter(self.launches)),
            "compiled": bool(self.compiles),
            "compileMs": round(sum(ns for _, ns in self.compiles) / ms,
                               3),
            "resultSizes": list(self.result_sizes),
            "outcome": self.outcome or ("error" if self.error else "ok"),
            # rendered ``cached`` keeps the documented meaning — served
            # without device work on this node.  A PARTIAL hit (e.g.
            # filtered TopN whose unfiltered full-counts pass hit while
            # the filtered scan dispatched) marks the flag internally
            # but still launched, so it must not read as fully
            # cache-served; the "cached" path note records the partial
            # hit either way
            "cached": self.cached and not self.launches,
        }
        if self.cache_key is not None:
            # the probe left the key itself; its digest is drawn when
            # somebody reads the record
            d["cacheKey"] = self.cache_key.digest()
        if self.tenant is not None:
            d["tenant"] = self.tenant
        # streaming-ingest annotations: present only when the query
        # actually met a delta (the common no-ingest record stays small)
        if self.delta_notes:
            d["deltaDepth"] = sum(self.delta_notes)
        if self.compacted:
            d["compacted"] = True
        # chaos-round annotations: present only when the query hedged
        # or degraded (the common healthy record stays small)
        if self.hedged:
            d["hedged"] = self.hedged
            d["hedgeWins"] = self.hedge_wins
        if self.hedge_losers:
            d["hedgeLosers"] = [{"node": n, "ms": round(ns / ms, 3)}
                                for n, ns in self.hedge_losers]
        if self.remote:
            d["remote"] = True
        if self.missing_shards:
            d["missingShards"] = sorted(self.missing_shards)
        # tiered-residency attribution: present only when the query
        # crossed the tier machinery (the common fully-resident record
        # stays small).  ``stallMs`` is the time THIS query spent
        # waiting on promotions / host fallbacks / cold assembly —
        # the "slow because the working set exceeded HBM" answer.
        if self.tier_notes:
            by = Counter(o for o, _ in self.tier_notes)
            d["tier"] = {
                "hbm": by.get("hbm", 0),
                "promoted": by.get("promoted", 0),
                "fallback": by.get("fallback", 0),
                "cold": by.get("cold", 0),
                "stallMs": round(
                    sum(ns for o, ns in self.tier_notes
                        if o != "hbm") / ms, 3),
            }
        if self.admission is not None:
            d["admission"] = {
                "class": self.admission.get("class"),
                "queueWaitMs": round(
                    self.admission.get("queue_wait_ns", 0) / ms, 3),
            }
        if self.compiles:
            d["compileKernels"] = dict(
                Counter(k for k, _ in self.compiles))
        if len(self.shard_ns) >= MAX_SHARD_TIMINGS:
            d["shardTimingsTruncated"] = True
        if self.path is not None:
            d["path"] = self.path
        if self.engine is not None:
            d["engine"] = self.engine
        if self.coalesce is not None:
            c = self.coalesce
            d["coalescer"] = {
                "batch": c["batch"],
                # ragged-megabatch evidence (parallel/coalescer.py +
                # ops/tape.py): how many DISTINCT tree shapes shared
                # this query's flushed batch, and whether the
                # tape-interpreter engine ran the launch (false =
                # same-shape fast path / single-query passthrough)
                "shapes": c.get("shapes", 1),
                "tape": c.get("tape", False),
                "queueWaitMs": round(c["queue_wait_ns"] / ms, 3),
                "launchMs": round(c["launch_ns"] / ms, 3),
                "leader": c.get("leader", True),
            }
            if c.get("why"):
                # what ended the leader's wait: idle|busy|full|cap
                d["coalescer"]["why"] = c["why"]
            if c.get("launch_trace"):
                # a follower names the batch leader's trace — the
                # span that owns the shared device launch
                d["coalescer"]["launchTrace"] = c["launch_trace"]
        if self.error is not None:
            d["error"] = self.error
        if self.slow:
            d["slow"] = True
        return d


class FlightRecorder:
    """Active-query table + ring buffer of recent records.

    One per executor (the server wires config + logger + stats in).
    Record ASSEMBLY (the note_* calls on the hot path) is lock-free;
    the recorder's own lock is touched once per query transition
    (begin/publish) to keep the active table and ring buffer safely
    iterable from /debug/queries while queries publish."""

    def __init__(self, recent: int = 256, long_query_time: float = 0.0,
                 enabled: bool = True, logger=None, stats=None):
        self.enabled = enabled
        self.long_query_time = long_query_time  # seconds; 0 = log off
        self.logger = logger
        self.stats = stats
        self._seq = itertools.count(1)  # next() is atomic
        self._lock = threading.Lock()
        self._active: dict[int, QueryRecord] = {}
        self._recent: deque[QueryRecord] = deque(maxlen=recent)
        # shed-log throttle: overload sheds thousands/sec; one line
        # per second (with a suppressed count) keeps the log honest
        # without letting the log itself become the overload
        self._shed_log_t = 0.0
        self._shed_suppressed = 0

    # ----------------------------------------------------------- record

    def begin(self, index: str, pql: str,
              trace_id: str | None = None) -> QueryRecord:
        rec = QueryRecord(next(self._seq), index, pql, trace_id)
        # the handler's Request (root span, admission wait, body read,
        # parse) precedes the record: adopt its span list, id counter
        # and admission stamp, so handler and executor write ONE tree.
        # A record begun while another is attached on this thread (an
        # in-process remote re-execution) keeps a tree of its own.
        req = getattr(_tls, "req", None)
        if req is not None and getattr(_tls, "rec", None) is None:
            rec.req = req
            rec.spans = req.spans
            rec.ids = req.ids
            rec.admission = req.admission
            rec.exec_parent = getattr(_tls, "open", 0)
        rec.exec_id = next(rec.ids)
        with self._lock:
            self._active[rec.qid] = rec
        return rec

    def record_shed(self, index: str, pql: str, klass: str,
                    outcome: str, reason: str,
                    wait_ns: int = 0,
                    tenant: str | None = None,
                    trace_id: str | None = None) -> None:
        """A request refused at the admission gate never executes, so
        no record is begun for it — synthesize one straight into the
        ring buffer (outcome ``shed``/``expired``) so /debug/queries
        and the slow-query log tell the overload story, and skip the
        latency histogram (a refusal's sub-millisecond turnaround
        would drag the admitted-query percentiles down).  ``trace_id``
        (extracted from the refused request's traceparent — the shed
        happens before any span opens) links the refusal to the
        client's trace: a logged shed is one /debug/trace/{id} away."""
        if not self.enabled:
            return
        rec = QueryRecord(next(self._seq), index, pql,
                          trace_id=trace_id)
        rec.admission = {"class": klass, "queue_wait_ns": wait_ns}
        rec.tenant = tenant
        rec.outcome = outcome
        rec.error = reason
        rec.elapsed_ns = wait_ns
        suppressed = 0
        with self._lock:
            self._recent.append(rec)
            if self.logger is not None:
                now = time.monotonic()
                if now - self._shed_log_t < 1.0:
                    self._shed_suppressed += 1
                    return
                suppressed = self._shed_suppressed
                self._shed_suppressed = 0
                self._shed_log_t = now
        if self.logger is not None:
            # shed events ride the slow-query log: overload must be
            # diagnosable from the same place slow queries are
            self.logger.printf(
                "%s query (class=%s, waited %.1fms, trace=%s) on %s: %s"
                "%s",
                outcome, klass, wait_ns / 1e6, rec.trace_id,
                index or "-", reason,
                f" (+{suppressed} more shed in the last second)"
                if suppressed else "")

    def discard(self, rec: QueryRecord) -> None:
        """Drop an active record without publishing (a path that turned
        out not to execute, e.g. the collective upgrade declining)."""
        with self._lock:
            self._active.pop(rec.qid, None)

    def publish(self, rec: QueryRecord, error: str | None = None) -> None:
        end_ns = clock_ns()
        rec.elapsed_ns = end_ns - rec.t0_ns
        # the ``exec`` span is the record's own two clock reads
        rec.spans.append((rec.exec_id, rec.exec_parent, "exec",
                          rec.t0_ns, end_ns, _thread_id(), None))
        if error is not None:
            rec.error = error
        elapsed_s = rec.elapsed_ns / 1e9
        if self.long_query_time > 0 and elapsed_s > self.long_query_time:
            rec.slow = True
        with self._lock:
            self._active.pop(rec.qid, None)
            self._recent.append(rec)
        _tls.last = rec
        if self.stats is not None:
            # the /metrics + /debug/vars surface: a native Prometheus
            # histogram with this query's trace id as the bucket
            # exemplar (stats._Registry)
            self.stats.histogram("pilosa_query_latency", elapsed_s,
                                 exemplar=rec.trace_id)
        if rec.slow and self.logger is not None:
            compile_ms = sum(ns for _, ns in rec.compiles) / 1e6
            self.logger.printf(
                "slow query (%.3fs) trace=%s on %s: %s | stages=%s "
                "shards=%d launches=%d path=%s engine=%s compiled=%s%s%s",
                elapsed_s, rec.trace_id, rec.index, rec.pql,
                ",".join(f"{n}:{v / 1e6:.1f}ms" for n, v in rec.stages()),
                rec.shards_n, len(rec.launches), rec.path or "-",
                rec.engine or "-",
                "true" if rec.compiles else "false",
                f" compile_ms={compile_ms:.1f}" if rec.compiles else "",
                f" tenant={rec.tenant}" if rec.tenant else "")

    # ------------------------------------------------------------- views

    def active_records(self) -> list[QueryRecord]:
        with self._lock:
            return list(self._active.values())

    def recent_records(self) -> list[QueryRecord]:
        with self._lock:
            return list(self._recent)

    def records_for_trace(self, trace_id: str) -> list[QueryRecord]:
        """Every record (in-flight AND recent) linked to ``trace_id``
        — the per-node section of cross-node trace assembly.  Active
        records matter: the hedge LOSER's remote execution may still
        be running on its node when the origin assembles the tree.
        Matching is on normalized ids (records may carry the 20-hex
        self-generated fallback; headers zero-pad to 32)."""
        want = _tracing.normalize_trace_id(trace_id)
        with self._lock:
            recs = list(self._active.values()) + list(self._recent)
        return [r for r in recs
                if _tracing.normalize_trace_id(r.trace_id) == want]


# --------------------------------------------------------------------
# cluster event journal
# --------------------------------------------------------------------


class EventJournal:
    """Process-wide ring of structured events at the state transitions
    that previously only ticked counters — breaker open/close, hedge
    fired/won, rebalance shard transitions, AE round lifecycle,
    compaction runs, OOM evict-and-retry, residency demote/promote,
    failpoint arm/disarm, config baseline changes.  Each event is
    stamped with a monotonically increasing ``seq``, wall + monotonic
    time, the node id, and the active trace id when one is in scope —
    so a trace view can answer "p99 spiked because node2's breaker
    opened mid-backfill".

    Exposure: ``GET /debug/events`` per node (``?since=``/``?kind=``)
    plus the fanned-in ``GET /debug/cluster/events`` merged timeline.

    Lock discipline: one short lock per emit (append + counter tick);
    NEVER emit while holding another subsystem's lock — every
    emission site releases its own lock first (the breaker/faultinject
    discipline).  Disarmed cost (``journal_on`` false) is one module
    bool read at each site, the faultinject gate shape."""

    def __init__(self, size: int = 2048, node_id: str = "",
                 kinds: frozenset | None = None):
        self._lock = _lockcheck.lock("eventjournal")
        self._ring: deque[dict] = deque(maxlen=max(1, int(size)))
        self._seq = 0
        self._by_kind: Counter = Counter()
        self._dropped = 0
        self.node_id = node_id
        # empty/None = every kind; a non-empty set filters at emit
        # (the dropped counter keeps the suppression visible)
        self.kinds = frozenset(kinds) if kinds else frozenset()

    def emit(self, kind: str, trace_id: str | None = None,
             **fields) -> None:
        ev = {"t": time.time(), "mono": time.perf_counter_ns(),
              "kind": kind}
        if trace_id:
            ev["traceId"] = _tracing.normalize_trace_id(trace_id)
        if fields:
            ev.update(fields)
        with self._lock:
            # prefix allowlist, same contract as the events() filter:
            # kinds={"breaker"} keeps breaker.open AND breaker.close
            if self.kinds and not any(kind.startswith(k)
                                      for k in self.kinds):
                self._dropped += 1
                return
            self._seq += 1
            ev["seq"] = self._seq
            ev["node"] = self.node_id
            self._ring.append(ev)
            self._by_kind[kind] += 1

    def events(self, since: int = 0, kind: str | None = None,
               trace_id: str | None = None,
               limit: int = 512) -> list[dict]:
        """Ring contents, oldest first.  ``since`` keeps events with
        seq strictly greater (the incremental-poll cursor); ``kind``
        is a prefix match (``kind=breaker`` covers breaker.open /
        breaker.close); ``trace_id`` keeps events stamped with that
        trace; ``limit`` keeps the NEWEST matches."""
        want = (_tracing.normalize_trace_id(trace_id)
                if trace_id else None)
        with self._lock:
            evs = list(self._ring)
        out = [e for e in evs
               if e["seq"] > since
               and (kind is None or e["kind"].startswith(kind))
               and (want is None or e.get("traceId") == want)]
        return out[-max(0, int(limit)):]

    def counters(self) -> dict:
        with self._lock:
            return {"total": self._seq, "dropped": self._dropped,
                    "depth": len(self._ring),
                    "kinds": dict(self._by_kind)}


#: The one-word fast gate every emission site reads FIRST:
#: ``if observe.journal_on: observe.emit(kind, ...)`` — the
#: faultinject ``armed`` discipline, so the disarmed journal costs
#: one module-bool read on the hot path.
journal_on = True
_journal = EventJournal()
_cfg_lock = threading.Lock()
_baseline: tuple | None = None
_refs = 0


def journal() -> EventJournal:
    return _journal


def emit(kind: str, trace_id: str | None = None, **fields) -> None:
    """Emit one journal event.  ``trace_id=None`` auto-captures the
    thread's active trace id (``tracing.active_trace_id``) so events
    emitted inside a traced request link the trace for free."""
    if not journal_on:
        return
    if trace_id is None:
        trace_id = _tracing.active_trace_id()
    _journal.emit(kind, trace_id=trace_id, **fields)


def configure(node_id: str | None = None, size: int | None = None,
              kinds: str | None = None,
              enabled: bool | None = None) -> EventJournal:
    """Apply explicit journal settings in place (None leaves a knob
    alone).  ``kinds`` is a comma-separated prefix list ("" = every
    kind).  Emits a ``config.applied`` event — config baseline
    changes are themselves journal-worthy state transitions."""
    global journal_on, _journal
    with _cfg_lock:
        j = _journal
        if size is not None and int(size) != j._ring.maxlen:
            nj = EventJournal(size=int(size), node_id=j.node_id,
                              kinds=j.kinds)
            with j._lock:
                nj._seq = j._seq
                nj._by_kind = j._by_kind
                nj._dropped = j._dropped
                for ev in j._ring:
                    nj._ring.append(ev)
            _journal = j = nj
        if node_id is not None:
            j.node_id = node_id
        if kinds is not None:
            j.kinds = frozenset(
                k.strip() for k in kinds.split(",") if k.strip())
        if enabled is not None:
            journal_on = bool(enabled)
    if journal_on:
        emit("config.applied", section="observe.journal",
             node=node_id or _journal.node_id)
    return _journal


def retain() -> None:
    """First retain captures the pre-server journal baseline (the
    hints/perfobs P5 refcount idiom)."""
    global _refs, _baseline
    with _cfg_lock:
        if _refs == 0 and _baseline is None:
            _baseline = (journal_on, _journal.node_id, _journal.kinds)
        _refs += 1


def release() -> None:
    """Last release restores the baseline for library users."""
    global _refs, _baseline, journal_on
    restored = False
    with _cfg_lock:
        if _refs > 0:
            _refs -= 1
        if _refs == 0 and _baseline is not None:
            on, node_id, kinds = _baseline
            journal_on = on
            _journal.node_id = node_id
            _journal.kinds = kinds
            _baseline = None
            restored = True
    if restored and journal_on:
        emit("config.restored", section="observe.journal")


def reset_journal() -> EventJournal:
    """Test hook: a fresh default journal, no baseline, zero refs."""
    global _journal, _baseline, _refs, journal_on
    with _cfg_lock:
        _journal = EventJournal()
        _baseline = None
        _refs = 0
        journal_on = True
    return _journal


# trace-assembly counters (pilosa_tpu.traceasm ticks these): rendered
# as the trace_* gauge family next to the journal's event_* family
_trace_lock = _lockcheck.lock("trace-counters")
_trace_counters = {
    "trace.assemblies": 0,   # /debug/trace/{id} trees assembled
    "trace.fanins": 0,       # peer record fetches issued
    "trace.errors": 0,       # peers that failed/timed out in a fan-in
    "trace.orphans": 0,      # assemblies that found no origin record
}


def bump_trace(name: str, value: int = 1) -> None:
    with _trace_lock:
        _trace_counters[name] += value


def trace_counters() -> dict:
    with _trace_lock:
        return dict(_trace_counters)


def publish_journal_gauges(stats) -> None:
    """event.* + trace.* gauge families for /metrics and /debug/vars —
    published unconditionally (zeros on a clean server) so both
    families are scrape-visible before the first event or assembly."""
    c = _journal.counters()
    stats.gauge("event.total", c["total"])
    stats.gauge("event.dropped", c["dropped"])
    stats.gauge("event.depth", c["depth"])
    stats.gauge("event.kinds", len(c["kinds"]))
    stats.gauge("trace.assemblies",
                trace_counters()["trace.assemblies"])
    stats.gauge("trace.fanins", trace_counters()["trace.fanins"])
    stats.gauge("trace.errors", trace_counters()["trace.errors"])
    stats.gauge("trace.orphans", trace_counters()["trace.orphans"])
