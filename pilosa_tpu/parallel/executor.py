"""Query executor: the full PQL op table over per-shard device kernels.

Parity target: the reference's distributed executor (executor.go).  The
shape is the same — validate, dispatch per call, map over shards, reduce —
but shard-level evaluation is TPU-native: bitmap expressions evaluate as
chains of XLA bitwise kernels over HBM-resident fragment tensors
(pilosa_tpu.ops) instead of per-container roaring loops, and TopN/GroupBy
use batched whole-matrix popcount scans instead of heap walks.

Single-node map-reduce runs shards on a thread pool (the analog of the
reference's NumCPU worker pool, executor.go:80-104).  The cluster layer
(pilosa_tpu.parallel.cluster) plugs into ``shards_for_node`` to restrict
execution to locally-owned shards, and the mesh path
(pilosa_tpu.parallel.mesh) fuses whole shard batches into single sharded
XLA programs.
"""

from __future__ import annotations

import bisect
import datetime as _dt
import heapq
import threading
import time as _time
from operator import itemgetter as _itemgetter
from concurrent.futures import (
    FIRST_COMPLETED,
    Future,
    ThreadPoolExecutor,
    wait as futures_wait,
)
from dataclasses import dataclass, replace

import numpy as np

from pilosa_tpu.models.field import FieldType
from pilosa_tpu.models.row import Row
from pilosa_tpu.parallel.cluster import (
    UNOWNED_MARKER,
    ShedByPeerError,
    TransportError,
    converge_owner_deliveries,
    refusal_is_unowned,
)
from pilosa_tpu.models.timequantum import parse_time
from pilosa_tpu.models.view import VIEW_STANDARD
from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.ops import containers as _containers
from pilosa_tpu.ops import expr
from pilosa_tpu.parallel import meshexec
from pilosa_tpu.parallel import prepared as _prep
from pilosa_tpu.parallel.results import (
    FieldRow,
    GroupCount,
    Pair,
    ValCount,
    sort_pairs,
)
from pilosa_tpu.pql import Call, Query, parse
from pilosa_tpu.runtime import residency as _residency
from pilosa_tpu.runtime import resultcache
from pilosa_tpu.serve import deadline as _deadline
from pilosa_tpu.serve import tenant as _tenantmod
from pilosa_tpu.serve.deadline import DeadlineExceededError
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu import faultinject as _fi
from pilosa_tpu import observe as _observe
from pilosa_tpu import perfobs as _perfobs
from pilosa_tpu import stagecheck as _stagecheck
from pilosa_tpu import stats as _stats
from pilosa_tpu import tracing


def _next_pow2(n: int) -> int:
    """Smallest power of two >= n (shape padding so batched kernels
    compile O(log) distinct programs, not one per group count)."""
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def _with_slots(shape: tuple, slots: list) -> tuple:
    """``shape`` with prepared leaf slot i replaced by ``slots[i]``: a
    staged leaf's own slot, or the ``dfuse`` node of a row that has a
    pending delta (its set and clear stacks take the two slots after
    its base, so every later leaf moves up by two)."""
    if shape[0] == "leaf":
        return slots[shape[1]]
    return tuple([_with_slots(c, slots) if isinstance(c, tuple) else c
                  for c in shape])


@dataclass
class ExecOptions:
    """Per-request execution options (reference execOptions,
    executor.go:60)."""

    remote: bool = False
    exclude_row_attrs: bool = False
    exclude_columns: bool = False
    column_attrs: bool = False
    shards: list[int] | None = None
    # per-request opt-out of cross-query micro-batching (the HTTP
    # layer's ?nocoalesce=true — debugging / latency-sensitive callers)
    coalesce: bool = True
    # per-request opt-out of the generation-stamped result cache (the
    # HTTP layer's ?nocache=1 — symmetric with ?nocoalesce)
    cache: bool = True
    # per-request opt-out of streaming-ingest delta fusion (the HTTP
    # layer's ?nodelta=1 — symmetric with ?nocoalesce/?nocache): the
    # touched fragments' pending deltas are compacted up front and the
    # query runs against pure base state (a debugging escape; results
    # are bit-exact either way)
    delta: bool = True
    # per-request opt-out of the compressed container-directory
    # engine (the HTTP layer's ?nocontainers=1 — symmetric with
    # ?nocoalesce/?nocache/?nodelta): fused reads route the exact
    # dense pre-container path; results are bit-identical either way
    containers: bool = True
    # per-request opt-out of mesh-native SPMD execution (the HTTP
    # layer's ?nomesh=1 — symmetric with the other escapes): fused
    # dispatches run the exact pre-mesh single-device programs
    # (parallel/meshexec.py stays out of the launch); results are
    # byte-identical either way
    mesh: bool = True
    # per-request opt-out of the Pallas bitmap VM (the HTTP layer's
    # ?novm=1 — symmetric with ?nocontainers): coalesced sparse Count
    # batches route the pre-VM ragged/fused engines instead of the
    # one-kernel compressed megabatch (ops/tape.execute_vm); results
    # are byte-identical either way
    vm: bool = True
    # per-request opt-out of tiered residency (the HTTP layer's
    # ?notiers=1 — symmetric with the other escapes): host-tier
    # lookups miss, evictions drop instead of demoting, and misses
    # rebuild inline (runtime/residency.py pre-tier behavior); results
    # are byte-identical either way
    tiers: bool = True
    # end-to-end deadline (serve/deadline.Deadline), propagated from
    # the X-Pilosa-Deadline header; checked at translate, before each
    # per-shard map, and before reduce so expired work never reaches
    # device dispatch
    deadline: object | None = None
    # degraded-read mode (the HTTP layer's ?partial=1 / the
    # X-Pilosa-Partial header, forwarded on sub-queries like
    # ?nocache): shards whose replicas are ALL unavailable are
    # ACCOUNTED in ``missing`` instead of failing the whole query —
    # the caller surfaces missingShards/missingFraction.  The default
    # (partial=False, missing=None) keeps today's all-or-error
    # semantics on exactly the same code path.
    partial: bool = False
    missing: set | None = None
    # widest shard fan-out this request targeted (stamped by
    # _target_shards) — the denominator of missingFraction
    targeted: int = 0
    # the request's tenant id (the HTTP layer's X-Pilosa-Tenant header
    # / ?tenant= param, forwarded on node-to-node sub-queries like
    # ?nocache): installed as the thread-local tenant scope for the
    # execution, so admission quotas, result-cache soft budgets and
    # residency tier quotas all charge the right tenant.  None rides
    # the default tier; with [tenants] off it is inert.
    tenant: str | None = None


class ExecutionError(ValueError):
    pass


class ShardsUnavailableError(ExecutionError):
    """Read fan-out exhausted every replica of one or more shards.

    Structured (chaos round): ``shards`` is the sorted unavailable
    shard list and ``causes`` maps shard -> {node_id: cause} with
    cause one of ``transport`` / ``timeout`` / ``shed`` / ``breaker``
    — surfaced in the HTTP error body (503 with
    ``unavailableShards``/``causes``) and on the flight record,
    replacing the old flat "all replicas exhausted" string."""

    def __init__(self, shards, causes: dict | None = None):
        self.shards = sorted(shards)
        causes = causes or {}
        self.causes = {s: dict(causes.get(s, {})) for s in self.shards}
        head = self.shards[:8]
        detail = "; ".join(
            f"shard {s}: " + (", ".join(
                f"{n}={c}" for n, c in sorted(self.causes[s].items()))
                or "no live replica")
            for s in head)
        more = ("" if len(self.shards) <= 8
                else f" (+{len(self.shards) - 8} more)")
        super().__init__(
            f"shards {self.shards} unavailable: all replicas "
            f"exhausted{more}: {detail}")


def _failure_cause(e: BaseException) -> str:
    """Classify one replica failure for ShardsUnavailableError /
    /debug surfaces: shed (peer alive but refusing), timeout (the
    transport gave up waiting), transport (unreachable/mid-request
    death)."""
    if isinstance(e, ShedByPeerError):
        return "shed"
    s = str(e).lower()
    if "timed out" in s or "timeout" in s:
        return "timeout"
    return "transport"


class _Flight:
    """One in-flight remote shard map (original or hedge)."""

    __slots__ = ("node_id", "shards", "t0", "race", "is_hedge",
                 "hedge_attempted")

    def __init__(self, node_id: str, shards: list[int], t0: int,
                 race: "_HedgeRace | None" = None,
                 is_hedge: bool = False):
        self.node_id = node_id
        self.shards = shards
        self.t0 = t0
        self.race = race
        self.is_hedge = is_hedge
        self.hedge_attempted = False


class _HedgeRace:
    """One original flight racing its hedge re-issues.  Remote results
    are not separable per shard (a Count sub-query returns one total
    over its shard group), so the race commits a whole SIDE: the
    original, or the full set of hedge flights covering the same
    shards — first side to completely succeed wins, the loser is
    abandoned (ignored, never awaited).  Touched only by the one
    thread running the owning map loop — no lock."""

    __slots__ = ("node_id", "shards", "orig_failed", "orig_error",
                 "hedge_pending", "hedge_failed", "hedge_results",
                 "committed")

    def __init__(self, node_id: str, shards: list[int]):
        self.node_id = node_id
        self.shards = shards
        self.orig_failed = False
        self.orig_error: BaseException | None = None
        self.hedge_pending = 0
        self.hedge_failed = False
        self.hedge_results: list = []
        self.committed: str | None = None


class UnownedShardError(ExecutionError):
    """A replica write delivery targeted a shard this node does not
    own per its CURRENT membership view (reference api.go
    ErrClusterDoesNotOwnShard) — the origin's view is stale; it must
    re-resolve the owner set and retry.  In-process origins match the
    structured ``unowned`` flag; over HTTP the refusal degrades to the
    distinctive UNOWNED_MARKER token in the error string."""

    unowned = True

    def __init__(self, shard: int):
        super().__init__(
            f"{UNOWNED_MARKER}: node does not own shard {shard}")


# Sentinel call names substituted during key translation when a read-path
# key does not exist: _Empty evaluates as an empty bitmap, _Noop as a
# changed=False write (reference: missing keys yield empty rows /
# unchanged writes, executor.go:2610 translateCalls).
_EMPTY_CALL = "_Empty"
_NOOP_CALL = "_Noop"
_EMPTY_ROWS_CALL = "_EmptyRows"


_tls = threading.local()  # .scope: the _Scope open on this thread


class _Scope:
    """What ``Executor.execute`` holds open around one query, as ONE
    object: the flight record attached to the thread
    (``observe.attach``), the ?notiers escape (``residency.no_tiers``),
    the tenant (``tenant.scope``), the exported ``executor.Execute``
    span and the trace the query's RPCs carry
    (``tracing.propagate``).  ``__enter__`` does only what the options
    make necessary: with tiers on, no tenant, the nop tracer and no
    inbound trace id (a read as a server's defaults send it) that is
    the record's attach and the record's own id pushed as the active
    trace, so downstream RPCs (shard map, hedges) still carry a
    joinable traceparent and /debug/trace/{id} can assemble the
    cross-node tree.  Every effect of the five scopes it replaces is
    there when an option is not a default, and is restored on exit in
    the reverse order.

    It also keeps the query's books (``stats.Batch``): the counters
    and timings of this read are collected there, by the coalescer
    too, and written once by :meth:`settle`."""

    __slots__ = ("ex", "rec", "opt", "index", "books",
                 "_prev_scope", "_attach", "_notiers", "_tenant",
                 "_span", "_ctx", "_walks0")

    def __init__(self, ex: "Executor", rec, opt: ExecOptions,
                 index: str):
        self.ex = ex
        self.rec = rec
        self.opt = opt
        self.index = index
        self.books = _stats.Batch()

    def __enter__(self):
        rec, opt = self.rec, self.opt
        self._walks0 = _prep.walks()
        self._prev_scope = getattr(_tls, "scope", None)
        _tls.scope = self
        self._attach = _observe.attach(rec)
        self._attach.__enter__()
        # a scope that would write the value that is there writes none
        self._notiers = self._tenant = self._span = self._ctx = None
        if _residency.tiers_off_scope() != (not opt.tiers):
            self._notiers = _residency.no_tiers(not opt.tiers)
            self._notiers.__enter__()
        if _tenantmod.current() != opt.tenant:
            self._tenant = _tenantmod.scope(opt.tenant)
            self._tenant.__enter__()
        parent = tracing.current_span()
        trace_id = None
        if (type(tracing.global_tracer()) is not tracing.Tracer
                or (parent is not None and parent.trace_id)):
            # a recording tracer, or an inbound trace to carry on
            self._span = span = tracing.start_span("executor.Execute")
            span.__enter__()
            span.set_tag("index", self.index)
            trace_id = span.trace_id
        if rec is not None:
            rec.tenant = opt.tenant
            rec.remote = bool(opt.remote)
            if trace_id:
                # span -> record linkage: the record carries the
                # exported trace id, the span the record id
                rec.trace_id = trace_id
            else:
                # the propagate fallback: under the nop tracer with no
                # inbound traceparent the record's self-generated id
                # becomes the active trace
                self._ctx = tracing.push_context(rec.trace_id)
            if self._span is not None:
                self._span.set_tag("query.record", rec.qid)
        return self

    def __exit__(self, *exc):
        tracing.pop_context(self._ctx)
        if self._span is not None:
            self._span.__exit__(*exc)
        if self._tenant is not None:
            self._tenant.__exit__(*exc)
        if self._notiers is not None:
            self._notiers.__exit__(*exc)
        self._attach.__exit__(*exc)
        _tls.scope = self._prev_scope
        return False

    def settle(self) -> None:
        """The one place this query's counters, histograms and timings
        are written (one take of the registry's lock), with the tree
        walks its thread made (``plan.walks``)."""
        books = self.books
        walked = _prep.walks() - self._walks0
        if walked:
            books.count(self.ex.stats, "plan.walks", walked)
        books.settle()


class Executor:
    def __init__(self, holder, worker_pool_size: int | None = None, cluster=None):
        self.holder = holder
        self.cluster = cluster  # optional cluster layer
        self.node = None  # back-ref set by ClusterNode (shard broadcasts)
        self.stats = _stats.NOP  # injected by the server assembly
        self.logger = None
        self.long_query_time = 0.0  # seconds; 0 disables slow-query log
        self.fuse_shards = True  # master switch for fused all-shard paths
        # optional cross-query micro-batcher (parallel/coalescer.py),
        # injected by the server assembly; None = no coalescing
        self.coalescer = None
        # query flight recorder (pilosa_tpu.observe); the server
        # assembly replaces this with one carrying config/logger/stats
        self.recorder = _observe.FlightRecorder()
        # pool size defaults to CPU count (reference worker pool =
        # NumCPU, executor.go:80-104)
        import os as _os

        self.pool = ThreadPoolExecutor(
            max_workers=worker_pool_size or _os.cpu_count() or 8)
        # hedged replica reads ([cluster] hedge-* config; the server
        # assembly overwrites these): a remote shard map still in
        # flight past the peer's EWMA + k*dev latency threshold is
        # re-issued to the next replicas and the first full result
        # wins.  The fraction bound is global across queries, so the
        # counters live here under their own lock.
        self.hedge_min_samples = 8
        self.hedge_deviations = 4.0
        self.hedge_min_s = 0.02
        self.hedge_max_fraction = 0.1  # of RPC volume; <=0 disables
        self._hedge_lock = threading.Lock()
        self._hedge_rpcs = 0
        self._hedge_issued = 0
        self._hedge_wins = 0
        # partial-result accounting (?partial=1 requests / requests
        # that actually degraded) — the partial.* gauge family
        self._partial_requests = 0
        self._partial_degraded = 0

    # ------------------------------------------------------------- public

    def execute(self, index_name: str, query, shards=None,
                opt: ExecOptions | None = None, text: str | None = None):
        """Execute a PQL query string or Query -> list of results
        (reference executor.Execute, executor.go:113).  ``text`` is
        the PQL a parsed ``query`` came from, where the caller has it:
        the flight record then holds it instead of re-serialising the
        tree."""
        opt = opt or ExecOptions()
        raw_query = query
        if isinstance(query, str):
            # sentinel call spellings (_Empty/_Noop/_EmptyRows) only
            # parse with remote semantics: they are the translation
            # layer's wire detail, not public surface
            with _observe.span("pql.parse"):
                query = parse(query, allow_internal=opt.remote)
        if not isinstance(query, Query):
            raise TypeError("query must be a PQL string or Query")
        idx = self.holder.index(index_name)
        if idx is None:
            raise ExecutionError(f"index not found: {index_name}")
        if opt.remote and shards:
            # receiver-side ownership gate for remote sub-queries
            # (reads AND replica writes): after an online rebalance
            # cuts a shard over, an ex-owner still holds the data for
            # a cleanup-grace window but must refuse to answer for it
            # — silently serving would hand the origin a soon-stale
            # copy the anti-entropy/dual-write machinery no longer
            # maintains here.  The structured marker lets the origin
            # fail over to the current owners.
            self._check_remote_shards_owned(idx, shards)
        if opt.partial:
            if opt.missing is None:
                # a partial request always carries its accounting set
                opt.missing = set()
            with self._hedge_lock:
                self._partial_requests += 1
        if not opt.mesh:
            # ONE fallback tick per executed ?nomesh=1 request — the
            # fused paths consult _query_mesh at several call sites
            # (staging + per-group batch fns), which must not each
            # count
            meshexec.note_fallback()
        rec = None
        if self.recorder is not None and self.recorder.enabled:
            # str() on a parsed Query re-serializes the AST — only pay
            # it when a record is actually being assembled, and the
            # caller did not hand the text over
            pql_text = (raw_query if isinstance(raw_query, str)
                        else text or str(raw_query))
            rec = self.recorder.begin(index_name, pql_text,
                                      trace_id=tracing.active_trace_id())
        t0 = _time.perf_counter() if rec is None else 0.0
        scope = _Scope(self, rec, opt, index_name)
        books = scope.books
        try:
            with scope:
                # Key translation happens once at the originating node,
                # never on remote re-execution (reference
                # executor.Execute, executor.go:146).
                _deadline.check(opt.deadline, "translate")
                calls = query.calls
                if not opt.remote:
                    with _observe.span("translate") as sp:
                        # the record's own opening, from
                        # ``recorder.begin`` through the scope above
                        sp.before("exec.open")
                        # a Count holds no key of its own, and its
                        # tree's keys are translated in the tree's one
                        # walk (_execute_count, parallel/prepared.py)
                        calls = [c if c.name == "Count"
                                 else self._translate_call(idx, c)
                                 for c in calls]
                results = []
                timer = books.timer(self.stats)
                for call in calls:
                    books.count(self.stats, "query", 1,
                                (f"index:{index_name}",
                                 f"call:{call.name}"))
                    # ONE span per call: the record's ``call.<Name>``
                    # span (rendered as the ``execute.<Name>`` stage),
                    # the per-op stats timing (exception-safe: failed
                    # calls record too) and, under a recording tracer,
                    # the exported span.  That one parents implicitly
                    # on purpose: under the nop tracer the active span
                    # here is the scope's ContextSpan, not a bare
                    # Execute span — an explicit traceless parent would
                    # bury the trace for the whole call (map fan-out
                    # RPCs, replica writes, hint stamps)
                    with _observe.span(
                            "call." + call.name,
                            timer=(timer, "execute." + call.name),
                            export="executor.execute" + call.name):
                        results.append(
                            self._execute_call(idx, call, shards, opt))
                # a number holds no key: a read of Counts (or a write's
                # booleans) has nothing to translate back, and opens no
                # span over an identity
                if not opt.remote and any(type(res) not in (int, bool)
                                          for res in results):
                    with _observe.span("translateResults"):
                        results = [
                            self._translate_result(idx, call, res)
                            for call, res in zip(calls, results)
                        ]
        except BaseException as e:
            if rec is not None:
                if isinstance(e, DeadlineExceededError):
                    rec.outcome = "expired"
                if isinstance(e, ShardsUnavailableError):
                    # the structured unavailability surfaces on the
                    # flight record too, not just the HTTP body
                    for s in e.shards:
                        rec.note_missing(s)
            scope.settle()
            if rec is not None:
                self.recorder.publish(rec,
                                      error=f"{type(e).__name__}: {e}")
            raise
        if opt.missing:
            with self._hedge_lock:
                self._partial_degraded += 1
        # the read's books are settled inside its ``exec`` span, where
        # the single writes were (the latency histogram is the
        # recorder's own, written once the span has its end)
        scope.settle()
        if rec is not None:
            rec.result_sizes = [_observe.result_size(r) for r in results]
            self.recorder.publish(rec)
        # the record's exec span is the query's clock; an executor with
        # the flight recorder off reads its own
        elapsed = (rec.elapsed_ns / 1e9 if rec is not None
                   else _time.perf_counter() - t0)
        if (self.long_query_time > 0 and elapsed > self.long_query_time
                and self.logger is not None):
            # slow-query log (reference cluster.long-query-time,
            # api.go:1157); the trace id makes a logged outlier one
            # /debug/trace/{id} away
            self.logger.printf("slow query (%.3fs) trace=%s on %s: %s",
                               elapsed,
                               rec.trace_id if rec is not None else "-",
                               index_name, query)
        return results

    # ----------------------------------------------------------- dispatch

    def _execute_call(self, idx, call: Call, shards, opt: ExecOptions):
        name = call.name
        if name == _EMPTY_CALL:
            return Row()
        if name == _NOOP_CALL:
            return False
        if name == _EMPTY_ROWS_CALL:
            return []
        if name == "Set":
            return self._execute_set(idx, call, opt)
        if name == "Clear":
            return self._execute_clear(idx, call, opt)
        if name == "ClearRow":
            return self._execute_clear_row(idx, call, shards, opt)
        if name == "Store":
            return self._execute_store(idx, call, shards, opt)
        if name == "SetRowAttrs":
            return self._execute_set_row_attrs(idx, call, opt)
        if name == "SetColumnAttrs":
            return self._execute_set_column_attrs(idx, call, opt)
        if name == "Count":
            return self._execute_count(idx, call, shards, opt)
        if name == "TopN":
            return self._execute_topn(idx, call, shards, opt)
        if name == "Rows":
            return self._execute_rows(idx, call, shards, opt)
        if name == "GroupBy":
            return self._execute_group_by(idx, call, shards, opt)
        if name in ("Sum", "Min", "Max"):
            return self._execute_aggregate(idx, call, shards, opt)
        if name in ("MinRow", "MaxRow"):
            return self._execute_extreme_row(idx, call, shards, opt)
        if name == "Options":
            return self._execute_options(idx, call, shards, opt)
        # bitmap calls: Row/Union/Intersect/Difference/Xor/Not/Shift/Range
        return self._execute_bitmap_call(idx, call, shards, opt)

    # ------------------------------------------------------------ helpers

    def _target_shards(self, idx, shards, opt: ExecOptions) -> list[int]:
        if opt.shards is not None:
            out = sorted(opt.shards)
        elif shards is not None:
            out = sorted(shards)
        else:
            out = sorted(idx.available_shards())
        rec = _observe.current()
        if rec is not None:
            # the chokepoint every op's shard resolution passes through:
            # record the query's fan-out (max across calls)
            rec.note_shards(len(out))
        if opt is not None and len(out) > opt.targeted:
            # missingFraction's denominator for partial results
            opt.targeted = len(out)
        return out

    def _cluster_active(self, opt: ExecOptions | None) -> bool:
        return (
            self.cluster is not None
            and self.cluster.transport is not None
            and (opt is None or not opt.remote)
            and len(self.cluster.sorted_nodes()) > 1
        )

    @staticmethod
    def _submit_io(fn, *args):
        """Run a remote sub-query on its own thread and return a Future.
        The reference bounds only local shard work by NumCPU; per-node
        mapper goroutines are unbounded (executor.go:2517), so remote
        fan-out must never queue behind the compute pool or behind other
        nodes' sub-queries — distributed latency is max(per-node)."""
        fut = Future()
        # carry the caller's active span AND deadline into the IO
        # thread so the outbound RPC injects the right trace context
        # and re-serializes the remaining budget on the wire
        parent_span = tracing.current_span()
        dl = _deadline.current()

        def run():
            if not fut.set_running_or_notify_cancel():
                return
            try:
                with _deadline.scope(dl):
                    if parent_span is not None:
                        with tracing.start_span("executor.remoteExec",
                                                parent=parent_span):
                            fut.set_result(fn(*args))
                    else:
                        fut.set_result(fn(*args))
            except BaseException as e:  # delivered via fut.result()
                fut.set_exception(e)

        threading.Thread(target=run, daemon=True).start()
        return fut

    def _local_map(self, fn, shards, deadline=None):
        rec = _observe.current()
        notiers = _residency.tiers_off_scope()
        tenant = _tenantmod.current()
        if rec is not None or deadline is not None or _fi.armed \
                or notiers or tenant is not None:
            # re-attach the flight record on the pool workers so their
            # kernel launches tick it, time each shard's evaluation,
            # and bail before a shard whose deadline already expired —
            # expired work must never reach device dispatch.  The
            # ?notiers scope and the tenant identity re-install the
            # same way the record does: worker threads must honor the
            # caller's escape and charge the caller's tenant.
            inner = fn
            # the span the workers' spans hang under: whatever is open
            # on THIS thread now (the map span)
            parent = _observe.open_span()

            def fn(shard, _inner=inner, _rec=rec, _dl=deadline,
                   _nt=notiers, _ten=tenant, _par=parent):
                if _fi.armed:
                    # failpoint: the production per-shard map
                    _fi.hit("executor.map_shard")
                if _dl is not None and _dl.expired():
                    raise DeadlineExceededError(
                        f"deadline expired before map of shard {shard}")
                with _residency.no_tiers(_nt), _tenantmod.scope(_ten):
                    if _rec is None:
                        return _inner(shard)
                    t0 = _time.perf_counter_ns()
                    with _observe.attach(_rec, _par):
                        out = _inner(shard)
                    _rec.note_shard(shard, _time.perf_counter_ns() - t0)
                    return out

        if len(shards) <= 1:
            return [fn(s) for s in shards]
        return list(self.pool.map(fn, shards))

    def _map_shards(self, fn, shards, idx=None, call=None, opt=None, adapt=None,
                    remote_call=None, local_batch_fn=None):
        """Map over shards and return the flat list of per-shard/per-node
        partials.  Single-node: worker-pool map (reference mapperLocal,
        executor.go:2561).  Clustered (and not already a remote
        re-execution): group shards by owner node, run local shards on
        the pool, forward each remote group as one PQL sub-query, and on
        node failure re-map its shards onto replicas until owners are
        exhausted (reference mapReduce, executor.go:2455-2514).  `adapt`
        converts one remote result into a list of local-partial-shaped
        values.  `local_batch_fn(shards) -> partials` replaces the
        per-shard pool for the locally-owned group when the call has a
        fused all-shard evaluation (remote nodes fuse on their own side,
        since remote re-execution is non-clustered)."""
        rec = _observe.current()
        dl = opt.deadline if opt is not None else None
        _deadline.check(dl, "map")
        # the map stage boundary (reference mapReduce,
        # executor.go:2455); the enclosing call.<Name> span minus this
        # is the reduce side
        with _observe.span("map"):
            partials = self._map_shards_inner(
                fn, shards, idx, call, opt, adapt, remote_call,
                local_batch_fn, rec)
            # the reduce boundary: partials whose deadline died in
            # flight are dropped here, never folded
            _deadline.check(dl, "reduce")
            return partials

    def _map_shards_inner(self, fn, shards, idx, call, opt, adapt,
                          remote_call, local_batch_fn, rec):
        dl = opt.deadline if opt is not None else None
        if not (self._cluster_active(opt) and idx is not None and call is not None
                and adapt is not None):
            return self._local_map(fn, shards, deadline=dl)
        cluster = self.cluster
        pql = str(call if remote_call is None else remote_call)
        partials = []
        tried: dict[int, set] = {s: set() for s in shards}
        causes: dict[int, dict] = {}  # shard -> {node_id: cause}
        pending = cluster.shards_by_node(idx.name, shards)
        inflight: dict = {}  # future -> _Flight

        def submit(node_id, node_shards, race=None, is_hedge=False):
            extra = {}
            if opt is not None and not opt.cache:
                # forward the origin's ?nocache=1: peers must do a
                # real execution too, not answer from their
                # per-shard result caches
                extra["nocache"] = True
            if opt is not None and not opt.delta:
                # forward ?nodelta=1: peers compact their own
                # pending deltas and run against pure base too
                extra["nodelta"] = True
            if opt is not None and not opt.containers:
                # forward ?nocontainers=1: peers route their own
                # fused reads through the dense pre-container path
                extra["nocontainers"] = True
            if opt is not None and not opt.mesh:
                # forward ?nomesh=1: peers run their own fused
                # dispatches on the pre-mesh single-device programs
                extra["nomesh"] = True
            if opt is not None and not opt.vm:
                # forward ?novm=1: peers route their own coalesced
                # sparse reads through the pre-VM engines too
                extra["novm"] = True
            if opt is not None and not opt.tiers:
                # forward ?notiers=1: peers bypass their own tiered
                # residency too (inline rebuilds, drop-not-demote)
                extra["notiers"] = True
            if opt is not None and opt.partial:
                # forward ?partial=1: degraded-read semantics ride
                # sub-queries like the other per-request escapes
                extra["partial"] = True
            if opt is not None and opt.tenant:
                # forward the tenant id: the peer's admission gate,
                # result cache and residency tiers must charge the
                # SAME tenant the origin did (exactly like ?nocache)
                extra["tenant"] = opt.tenant
            if extra:
                fut = self._submit_io(
                    lambda n, i, p, s, _e=extra:
                    cluster.transport.query_node(n, i, p, s, **_e),
                    cluster.node(node_id), idx.name, pql,
                    node_shards,
                )
            else:
                fut = self._submit_io(
                    cluster.transport.query_node,
                    cluster.node(node_id), idx.name, pql, node_shards,
                )
            fl = _Flight(node_id, node_shards,
                         _time.perf_counter_ns(),
                         race=race, is_hedge=is_hedge)
            inflight[fut] = fl

            def _settle(f, _fl=fl):
                # Runs on the flight's IO thread the moment it
                # resolves — whether the map loop processes it, a
                # settled race purged it, or an exhaustion error
                # unwound with it still in the air — so breakers and
                # the latency EWMA ALWAYS learn the outcome.  Without
                # this, a hedged-over HALF_OPEN trial would never
                # resolve its probe and the breaker would wedge
                # refusing until a heartbeat probe happened by.
                try:
                    f.result()
                except ShedByPeerError:
                    # a shed is proof of life: never a breaker failure
                    cluster.note_peer_success(_fl.node_id)
                except TransportError:
                    cluster.note_peer_failure(_fl.node_id)
                except BaseException:  # noqa: BLE001 — deadline &c.:
                    pass  # says nothing about the PEER either way
                else:
                    cluster.note_peer_success(
                        _fl.node_id,
                        (_time.perf_counter_ns() - _fl.t0) / 1e9)

            fut.add_done_callback(_settle)
            with self._hedge_lock:
                self._hedge_rpcs += 1

        def fail_shards(node_shards, node_id, err, cause):
            """Fail ``node_shards`` over from ``node_id`` onto their
            next replicas; shards with no replica left are ACCOUNTED
            (?partial=1) or raised as a structured
            ShardsUnavailableError carrying the shard list and the
            per-replica causes collected along the way."""
            exhausted = []
            for s in node_shards:
                tried[s].add(node_id)
                causes.setdefault(s, {})[node_id] = cause
                nxt = cluster.next_replica(idx.name, s, tried[s])
                if nxt is None:
                    exhausted.append(s)
                else:
                    pending.setdefault(nxt.id, []).append(s)
            if not exhausted:
                return
            if (opt is not None and opt.partial
                    and opt.missing is not None):
                for s in exhausted:
                    opt.missing.add(s)
                    if rec is not None:
                        rec.note_missing(s)
                return
            if isinstance(err, ShedByPeerError):
                # every replica SHED (admission gates saturated
                # cluster-wide): transient overload, not missing data
                # — let it surface as 503 + Retry-After, never the 400
                # an ExecutionError maps to
                raise err
            raise ShardsUnavailableError(exhausted, causes)

        def purge_race(race):
            """Abandon (cancel-or-ignore) every still-inflight flight
            of a settled race: the loser's IO thread finishes on its
            own; its result is dropped.  Never await a loser — waiting
            out a slow peer is exactly what hedging exists to avoid."""
            for f2 in [f2 for f2, fl2 in inflight.items()
                       if fl2.race is race]:
                inflight.pop(f2)

        def try_hedge(fl):
            """Race ``fl``'s shards on their next replicas.  A remote
            result is one value for the whole shard group, so the
            hedge must cover EVERY shard of the flight (each on a live
            next replica) or not issue at all; the global fraction
            bound keeps hedges from ever exceeding hedge-max-fraction
            of RPC volume."""
            fl.hedge_attempted = True
            with self._hedge_lock:
                if (self._hedge_issued + 1
                        > self.hedge_max_fraction * self._hedge_rpcs):
                    return
            groups: dict[str, list[int]] = {}
            for s in fl.shards:
                nxt = cluster.next_replica(idx.name, s,
                                           tried[s] | {fl.node_id})
                if nxt is None or cluster.breaker_open(nxt.id):
                    return
                groups.setdefault(nxt.id, []).append(s)
            race = _HedgeRace(fl.node_id, fl.shards)
            race.hedge_pending = len(groups)
            fl.race = race
            for hnode_id, hshards in groups.items():
                submit(hnode_id, hshards, race=race, is_hedge=True)
            with self._hedge_lock:
                self._hedge_issued += 1
            if rec is not None:
                rec.hedged += 1
            if _observe.journal_on:
                _observe.emit("hedge.fired", node=fl.node_id,
                              shards=len(fl.shards),
                              replicas=sorted(groups))

        while pending or inflight:
            # fan out every remote group concurrently, then run local
            # shards inline while the remotes are in flight — distributed
            # latency is max(per-node), not sum (executor.go:2517 mapper
            # goroutines)
            for node_id in [k for k in list(pending) if k != cluster.local_id]:
                node_shards = pending.pop(node_id)
                if not cluster.peer_allows(node_id):
                    # breaker open: fast-fail onto the next replica
                    # without paying the transport timeout
                    fail_shards(node_shards, node_id,
                                TransportError(
                                    f"circuit breaker open for peer "
                                    f"{node_id}"),
                                "breaker")
                    continue
                submit(node_id, node_shards)
            if cluster.local_id in pending:
                local_shards = pending.pop(cluster.local_id)
                t_loc = _time.perf_counter_ns()
                _deadline.check(dl, "local map")
                if local_batch_fn is not None and len(local_shards) > 1:
                    partials.extend(local_batch_fn(local_shards))
                else:
                    partials.extend(self._local_map(fn, local_shards,
                                                    deadline=dl))
                if rec is not None:
                    rec.note_node("local",
                                  _time.perf_counter_ns() - t_loc,
                                  len(local_shards))
            if not inflight:
                continue
            # hedge pass: an original flight past its per-peer latency
            # threshold (EWMA + k*dev, floored) races its shards on
            # the next replicas; flights below threshold bound the
            # wait so the check re-runs when the soonest one crosses
            timeout = None
            if self.hedge_max_fraction > 0:
                now = _time.perf_counter_ns()
                soonest = None
                for fl in list(inflight.values()):
                    if (fl.race is not None or fl.is_hedge
                            or fl.hedge_attempted):
                        continue
                    thr = self._hedge_threshold_s(fl.node_id)
                    if thr is None:
                        continue
                    due = fl.t0 + int(thr * 1e9)
                    if now >= due:
                        try_hedge(fl)
                    elif soonest is None or due < soonest:
                        soonest = due
                if soonest is not None:
                    timeout = max(0.001, (soonest - now) / 1e9)
            done, _ = futures_wait(list(inflight), timeout=timeout,
                                   return_when=FIRST_COMPLETED)
            for fut in done:
                fl = inflight.pop(fut, None)
                if fl is None:
                    continue  # purged loser of a settled race
                try:
                    res = fut.result()
                except Exception as te:
                    if isinstance(te, TransportError):
                        # breaker/EWMA feedback already ran in the
                        # flight's _settle callback
                        cause = _failure_cause(te)
                    elif refusal_is_unowned(te):
                        # the peer answered (alive) but refused the
                        # sub-query as non-owner: an online rebalance
                        # cut the shards over and its view is fresher
                        # than ours — fail over onto the current
                        # owners without feeding the peer's breaker
                        te = TransportError(str(te))
                        cause = "unowned"
                    else:
                        raise
                    race = fl.race
                    if race is None:
                        fail_shards(fl.shards, fl.node_id, te, cause)
                        continue
                    if fl.is_hedge:
                        race.hedge_pending -= 1
                        race.hedge_failed = True
                        for s in fl.shards:
                            tried[s].add(fl.node_id)
                            causes.setdefault(s, {})[fl.node_id] = cause
                        if (race.committed is None and race.orig_failed
                                and race.hedge_pending == 0):
                            # both sides dead: normal failover for the
                            # original shard set
                            race.committed = "failed"
                            fail_shards(race.shards, race.node_id,
                                        race.orig_error,
                                        _failure_cause(race.orig_error))
                    else:
                        race.orig_failed = True
                        race.orig_error = te
                        if (race.committed is None and race.hedge_failed
                                and race.hedge_pending == 0):
                            race.committed = "failed"
                            fail_shards(fl.shards, fl.node_id, te,
                                        cause)
                        # hedge side still pending: wait for it
                    continue
                lat_ns = _time.perf_counter_ns() - fl.t0
                race = fl.race
                if race is None:
                    if rec is not None:
                        rec.note_node(fl.node_id, lat_ns,
                                      len(fl.shards))
                    partials.extend(adapt(res[0]))
                    continue
                if fl.is_hedge:
                    race.hedge_pending -= 1
                    race.hedge_results.append((fl, res))
                    if (race.committed is None and not race.hedge_failed
                            and race.hedge_pending == 0):
                        # the hedge side produced the full shard set
                        # first: commit it, abandon the original
                        race.committed = "hedge"
                        for hfl, hres in race.hedge_results:
                            if rec is not None:
                                rec.note_node(
                                    hfl.node_id,
                                    _time.perf_counter_ns() - hfl.t0,
                                    len(hfl.shards))
                            partials.extend(adapt(hres[0]))
                        with self._hedge_lock:
                            self._hedge_wins += 1
                        if rec is not None:
                            rec.hedge_wins += 1
                            # the abandoned original is the hedge
                            # loser: note who and how long its side
                            # had been in flight when the race settled
                            # — the /debug/trace/{id} tree shows the
                            # loser's side from this
                            now_ns = _time.perf_counter_ns()
                            for fl2 in inflight.values():
                                if fl2.race is race:
                                    rec.hedge_losers.append(
                                        (fl2.node_id,
                                         now_ns - fl2.t0))
                        if _observe.journal_on:
                            _observe.emit(
                                "hedge.won", side="hedge",
                                winner=sorted({hfl.node_id for hfl, _
                                               in race.hedge_results}),
                                losers=[race.node_id])
                        purge_race(race)
                else:
                    if race.committed is None:
                        race.committed = "orig"
                        now_ns = _time.perf_counter_ns()
                        losers = sorted({fl2.node_id
                                         for fl2 in inflight.values()
                                         if fl2.race is race})
                        if rec is not None:
                            rec.note_node(fl.node_id, lat_ns,
                                          len(fl.shards))
                            for fl2 in inflight.values():
                                if fl2.race is race:
                                    rec.hedge_losers.append(
                                        (fl2.node_id,
                                         now_ns - fl2.t0))
                        partials.extend(adapt(res[0]))
                        if _observe.journal_on:
                            _observe.emit("hedge.won", side="orig",
                                          winner=fl.node_id,
                                          losers=losers)
                        purge_race(race)
        return partials

    def _hedge_threshold_s(self, node_id: str) -> float | None:
        """The elapsed time past which a flight to ``node_id`` should
        hedge, or None while the peer has too few latency samples for
        the EWMA to mean anything."""
        ewma, dev, n = self.cluster.peer_latency(node_id)
        if n < self.hedge_min_samples:
            return None
        return max(self.hedge_min_s,
                   ewma + self.hedge_deviations * dev)

    @staticmethod
    def _rc_fill_ok(opt: ExecOptions | None) -> bool:
        """Partial results never enter the result cache: once this
        request has accounted a missing shard, every fill it would
        perform is suppressed (probes/hits stay — serving a COMPLETE
        cached value to a degraded request is strictly better than
        recomputing a partial one)."""
        return opt is None or not opt.missing

    def publish_chaos_gauges(self, stats) -> None:
        """hedge.* / partial.* gauge families for /metrics and
        /debug/vars — published unconditionally (zeros on a clean
        server) so the families are scrape-visible before any fault."""
        with self._hedge_lock:
            stats.gauge("hedge.rpcs", self._hedge_rpcs)
            stats.gauge("hedge.issued", self._hedge_issued)
            stats.gauge("hedge.wins", self._hedge_wins)
            stats.gauge("partial.requests", self._partial_requests)
            stats.gauge("partial.degraded", self._partial_degraded)

    def _field(self, idx, name: str):
        f = idx.field(name)
        if f is None:
            raise ExecutionError(f"field not found: {name}")
        return f

    @staticmethod
    def _np_words(words):
        return None if words is None else np.asarray(words)

    # ----------------------------------------------------- bitmap queries

    def _validate_call_fields(self, idx, call: Call) -> None:
        """Eagerly check referenced fields exist, even when the shard set
        is empty (the reference surfaces ErrFieldNotFound from the shard
        fn; with zero shards we must check up front)."""
        if call.name in ("Row", "Range"):
            cond = call.condition_arg()
            if cond is not None:
                self._field(idx, cond[0])
            else:
                self._field(idx, call.field_arg())
        for child in call.children:
            self._validate_call_fields(idx, child)

    # ------------------------------------------------ fused all-shard path

    def _prepare(self, idx, tree: Call | None,
                 translate: bool = False) -> _prep.Prepared | None:
        """The tree's ONE walk (parallel/prepared.py): translation where
        asked, whether it fuses, its shape and leaves, its cache key.
        Every later stage reads the result; None for no tree."""
        if tree is None:
            return None
        return _prep.prepare(self, idx, tree, translate)

    def _fuse_eligible(self, shards,
                       tree: _prep.Prepared | None = None,
                       extra: bool = True) -> bool:
        """The shared precondition of every fused all-shard dispatch:
        fusion enabled, a real multi-shard batch, any op-specific
        `extra` condition, and (when the op carries a bitmap tree) the
        tree being stack-evaluable: plain standard-view Row leaves,
        time-range Rows and BSI condition rows, combined with
        Union/Intersect/Difference/Xor/Not/Shift."""
        return (self.fuse_shards and len(shards) > 1 and extra
                and (tree is None or tree.fused))

    def _time_range_views(self, f, call: Call) -> list[str] | None:
        """The time views covering a Row(from=, to=) query — the same
        cover and clamping as the per-shard path (f.row_time /
        _clamp_to_views); None when the range is malformed.  Runs once
        for the support check and once per evaluation; the expensive
        part (the view-name scan) is memoized on the field."""
        from pilosa_tpu.models.timequantum import views_by_time_range

        from_arg = call.args.get("from")
        to_arg = call.args.get("to")
        try:
            start = (parse_time(from_arg) if from_arg is not None
                     else _dt.datetime(1, 1, 1))
            end = (parse_time(to_arg) if to_arg is not None
                   else _dt.datetime(9999, 1, 1))
        except (ValueError, TypeError, OverflowError, OSError):
            # int timestamps can overflow fromtimestamp (platform time_t)
            return None
        start, end = self._clamp_to_views(f, start, end)
        return ([] if start >= end
                else list(views_by_time_range(VIEW_STANDARD, start, end,
                                              f.time_quantum)))

    def _fused_expr(self, idx, tree: _prep.Prepared,
                    shards: tuple[int, ...], use_delta: bool = True,
                    before: str | None = None):
        """Stage a supported tree for ONE-launch evaluation: returns
        ``(shape, leaves)`` where ``shape`` is the canonical structure
        key (row ids and values erased into leaf slots — distinct rows
        share a compiled program) and ``leaves`` the operand stacks, for
        ops.expr.  Leaf staging is the cached stack builders
        (Field.stage_rows & friends); no compute dispatches here beyond
        what BSI range leaves inherently cost.  The tree is not walked
        again: ``tree.leaves`` lists what to stage and ``tree.shape``
        is the answer unless a row has a pending delta.

        ``use_delta=False`` is the ?nodelta=1 escape: pending delta
        planes on the touched fragments are compacted up front and
        every leaf stays a plain base leaf.

        ``before`` names, as a span of its own, what the caller's
        thread did between its last phase and this staging (the
        coalescer's ``route``)."""
        with _observe.span("stage") as sp:
            if before is not None:
                sp.before(before)
            fast0 = _stagecheck.fast_leaves()
            shape, leaves = self._stage_leaves(tree, shards, use_delta)
            # fast: leaves whose cached stacks were validated against
            # the view's write token alone, with no walk over the
            # shards (stagecheck.py)
            sp.note(leaves=len(leaves),
                    fast=_stagecheck.fast_leaves() - fast0)
        return shape, tuple(leaves)

    def _stage_leaves(self, tree: _prep.Prepared,
                      shards: tuple[int, ...], use_delta: bool):
        """The stacks of ``tree.leaves`` in slot order.  The plain
        rows of one field are staged together (``Field.stage_rows``:
        the view's write token read once, before every lookup, and one
        take of each owner's lock for the rows it proves good).  A
        standard-view row is delta-aware: its base stack is resident
        under its base token (delta writes don't evict it); when a
        pending delta touches the row in any fragment, the overlay
        stacks join as ``dfuse`` operands, staged BEFORE the base
        stack, so a compaction racing the two reads can only
        double-apply the (idempotent) overlay, never drop it."""
        descs = tree.leaves
        by_field: dict = {}
        for i, d in enumerate(descs):
            if d[0] == "row":
                by_field.setdefault(d[1], []).append(i)
        staged: list = [None] * len(descs)
        for f, slots in by_field.items():
            got = f.stage_rows([descs[i][2] for i in slots], shards,
                               use_delta)
            if len(slots) == len(descs) and not any(
                    [ds for _, ds in got]):
                # the common read, done: every leaf a row of one field
                # and no overlay (the tail below gives the same)
                return tree.shape, [base for base, _ in got]
            for i, pair in zip(slots, got):
                staged[i] = pair
        leaves: list = []
        slots_of: list = []
        deltas = 0
        for i, d in enumerate(descs):
            kind = d[0]
            if kind == "row":
                base, ds = staged[i]
                leaves.append(base)
                at = ("leaf", len(leaves) - 1)
                if ds is not None:
                    leaves.append(ds[0])
                    leaves.append(ds[1])
                    n = len(leaves)
                    at = ("dfuse", at, ("leaf", n - 2), ("leaf", n - 1))
                    deltas += 1
                slots_of.append(at)
                continue
            mark = _stagecheck.mark()
            if kind == "time":
                # time-range Row: ONE cached stack holding the
                # host-side union over the covering views (f.row_time's
                # union, batched across shards).  Delta overlays apply
                # inside the builder (effective reads; token carries
                # the delta seq) — no dfuse leaves needed.
                leaves.append(d[1].device_time_row_stack(d[2], shards,
                                                         d[3]))
            else:
                # the range compare dispatches while it stages: its
                # launch hangs under the stage span
                _, f, op, value = d
                leaves.append(_perfobs.launch(
                    self._raw_engine(self._query_mesh(None)),
                    lambda: f.device_range_stack(op, value, shards)))
            _stagecheck.leaf_done(mark)
            slots_of.append(("leaf", len(leaves) - 1))
        if not deltas:
            # no overlay: slot i holds leaf i, the shape as prepared
            return tree.shape, leaves
        rec = _observe.current()
        if rec is not None:
            rec.note_delta(deltas)
        return _with_slots(tree.shape, slots_of), leaves

    def _fused_eval(self, idx, tree: _prep.Prepared,
                    shards: tuple[int, ...],
                    use_delta: bool = True, mesh=None):
        """Evaluate a supported tree -> uint32 [n_shards, words] device
        stack, as ONE compiled program over the leaf stacks (ops.expr) —
        tree depth no longer multiplies the launch count, the dominant
        win when device dispatch has real latency (TPU behind an RPC
        boundary; the 20 us dispatch floor of VERDICT round 5).

        ``mesh`` (``_query_mesh``) routes the shard_map program so the
        one launch spans every mesh device; None is the pre-mesh
        single-device program (?nomesh=1 / [mesh] disabled)."""
        shape, leaves = self._fused_expr(idx, tree, shards, use_delta)
        with _observe.span("launch") as sp:
            out = expr.evaluate(shape, leaves, mesh=mesh)
            sp.note_engine()
        return out

    @staticmethod
    def _query_mesh(opt: ExecOptions | None):
        """The device mesh this request's fused dispatches run under:
        the active [mesh] layout, or None for ?nomesh=1 (counted as a
        mesh fallback) and whenever the mesh cannot activate."""
        return meshexec.query_mesh(opt is None or opt.mesh)

    @staticmethod
    def _raw_engine(mesh) -> str:
        """The engine enum of a raw ``bm``/``bsi`` kernel dispatch (the
        TopN scan, GroupBy levels, BSI planes, range compares), which
        passes no perfobs sample site: ``host`` on the numpy + native
        engine, else where the operand stacks are placed (``mesh`` is
        the request's ``_query_mesh``)."""
        if bm.host_mode():
            return "host"
        return "mesh" if mesh is not None else "dense"

    def _note_route(self, fused_ok: bool) -> None:
        """Stamp ``path`` on the flight record; the raw per-shard ops
        never pass an engine sample site, so that path reads ``host``
        until a launch says otherwise (note_engine: last launch wins)."""
        rec = _observe.current()
        if rec is not None:
            rec.note_path("fused" if fused_ok else "per-shard")
            if not fused_ok:
                rec.note_engine("host")

    # ------------------------------------------- result cache (read paths)

    @staticmethod
    def _rc_view_stamp(f, view_name: str, shards: tuple[int, ...]):
        """The invalidation stamp of one (field, view) pair over the
        shard set: the aggregate ``(count, sum_gen, sum_seq,
        sum_uid, max_uid)`` of the participating fragments' generation
        tokens — ``(base_gen, delta_seq)`` per fragment, the streaming-
        ingest extension (pilosa_tpu.ingest).

        The aggregate is change-DETECTING, not just change-likely,
        because of monotonicity invariants: a surviving fragment's
        ``_gen`` only ever increases (every base mutation and every
        compaction bumps it), ``_delta_seq`` only ever increases (every
        delta-landing write bumps it; compaction leaves it alone — so
        an entry filled against base ⊕ delta stays valid until *its*
        fragment's delta actually changes, and a compaction costs one
        conservative miss, not an eviction storm), and ``_uid`` comes
        from a process-global increasing counter, so a newly created
        fragment's uid exceeds every uid that ever existed.  Case
        analysis between fill and probe: any fragment CREATION (incl.
        a resize/restore replacement) raises ``max_uid`` past the old
        all-time high; any DELETION without a creation changes
        ``count``; any MUTATION of a surviving fragment raises
        ``sum_gen`` or ``sum_seq`` (which nothing can lower — resets
        only occur via replacement, caught by ``max_uid``).  So every
        state change flips at least one component, while an unchanged
        view reproduces the stamp exactly.

        One per (field name, view) of the one index a probe reads
        (``Prepared.views``): ``Intersect(Row(f=a), Row(f=b))``
        touches the same view twice but needs one stamp.  The single
        pass keeps a probe that misses to one walk over the shards: the
        common fully-populated case batches all dict lookups into one
        C-level ``itemgetter`` call, falling back to the filtering loop
        only when some shard has no fragment.

        And the walk is made only when the view's write token
        (stagecheck.py) has moved since the last one over this shard
        set: the token is read FIRST, and it changes after every event
        that could change any fragment's (uid, gen, delta_seq) or the
        view's map of fragments, so an aggregate remembered under the
        token that still stands is the one a walk would give now."""
        view = None if f is None else f.view(view_name)
        if view is None:
            return 0
        token = view.write_token
        memo = view.rc_stamps
        hit = memo.get(shards)
        if hit is not None and hit[0] == token:
            return hit[1]
        frags = view.fragments
        fs = None
        if len(shards) > 1:
            try:
                fs = _itemgetter(*shards)(frags)
            except KeyError:
                fs = None
        if fs is None:
            g = frags.get
            fs = [fr for s in shards if (fr := g(s)) is not None]
        sg = sq = su = mu = 0
        for fr in fs:
            u = fr._uid
            sg += fr._gen
            sq += fr._delta_seq
            su += u
            if u > mu:
                mu = u
        stamp = (len(fs), sg, sq, su, mu)
        if len(memo) >= 16:
            memo.clear()  # shard sets are few: a server has one or two
        memo[shards] = (token, stamp)
        return stamp

    def _rc_probe(self, idx, kind: str, shards: tuple[int, ...],
                  opt: ExecOptions | None,
                  tree: _prep.Prepared | None = None,
                  extra=None, gen_fields=()):
        """(cache, key, gens) for one fused read, or None when caching
        is off (process config or the request's ?nocache=1).  The
        tree's part of the key is ``tree.sig``, the canonical identity
        its one walk left: the expression shape with leaf identities
        (field, view, row / op+value) substituted at the slots —
        distinct queries over the same shape get distinct keys, unlike
        the coalescer's value-erased bucket key — and canonical in
        operand order: ``Intersect(a, b)`` and ``Intersect(b, a)`` are
        one key and one entry.  ``extra`` joins the key (e.g. the TopN
        field and truncation args); ``gen_fields`` is (field,
        view_name) pairs whose fragments participate beyond the tree
        leaves (e.g. the scanned TopN matrix).  Puts the key on the
        active flight record, whose ``cacheKey`` is its digest, hit or
        miss.

        The stamp is captured HERE, before any fragment data is read
        (resultcache stamp-before-read discipline — the reverse order
        could stamp fresh generations onto stale data): one aggregate
        a (field, view), in the order of their names and NOT in the
        order the leaves were met: two written orders of one tree
        share a key, and a stamp that followed the traversal would
        read the other order's fill as invalidated.

        ``?nodelta=1`` bypasses the probe too: its contract is an
        up-front compaction and a REAL pure-base read — a cached value
        (bit-identical, but filled through the delta path) would
        short-circuit the escape into a no-op whenever the stamp
        hasn't moved."""
        rc = resultcache.cache()
        if not rc.enabled or (opt is not None
                              and not (opt.cache and opt.delta)):
            return None
        if tree is not None and not tree.fused:
            return None  # no canonical identity: never one key for two
        views = () if tree is None else tree.views
        if gen_fields:
            merged = {(fn, vn): f for fn, vn, f in views}
            for f, vn in gen_fields:
                # gen_fields means a whole-matrix read (TopN refresh,
                # GroupBy Rows scan), and those merge pending deltas
                # during the read — merge BEFORE stamping instead, or
                # the fill carries pre-merge generations our own flush
                # just invalidated (dead on arrival: the next identical
                # query would re-execute instead of hitting)
                f.flush_deltas(shards)
                merged[(f.name, vn)] = f
            views = [(k[0], k[1], merged[k]) for k in sorted(merged)]
        stamp = self._rc_view_stamp
        gens = tuple([stamp(f, vn, shards) for _, vn, f in views])
        # the active placement flavor joins the key (PR 12 follow-up):
        # a [mesh] toggle or axis resize must not serve fills staged
        # under the previous device layout — and when the operator
        # toggles BACK, the old flavor's still-generation-valid
        # entries become warm again instead of having been overwritten
        placement = meshexec.placement_token(
            opt is None or opt.mesh)
        key = resultcache.Key(
            (self.holder.uid, idx.name, kind,
             None if tree is None else tree.sig, extra, shards,
             placement))
        rec = _observe.current()
        if rec is not None:
            rec.cache_key = key
        return rc, key, gens

    @staticmethod
    def _rc_mark_hit() -> None:
        rec = _observe.current()
        if rec is not None:
            rec.cached = True
            rec.note_path("cached")

    def _rc_get(self, idx, kind: str, shards: tuple[int, ...], opt,
                tree: _prep.Prepared | None = None, **probe_kw):
        """The ``cache.probe`` span: key + generation stamp, then the
        lookup (single-flight wait on another reader's fill included)
        -> ``(hit, value, probe)``; ``probe`` is None with caching off
        and otherwise what :meth:`_rc_put` fills."""
        with _observe.span("cache.probe") as sp:
            probe = self._rc_probe(idx, kind, shards, opt, tree,
                                   **probe_kw)
            if probe is None:
                return False, None, None
            rc, key, gens = probe
            moved = tree is not None and tree.moved
            if moved:
                # a tree whose operands the key reordered counts in
                # ``cache.reordered`` (under the lookup's own lock)
                sp.note(reordered=1)
            hit, val = rc.get(key, gens, self._rc_wait(opt),
                              reordered=moved)
            sp.note(hit=bool(hit))
            if hit:
                self._rc_mark_hit()
            return hit, val, probe

    def _rc_put(self, probe, opt, value, nbytes: int) -> None:
        """The ``cache.fill`` span: store one computed result under
        the key and stamp its probe captured before the read."""
        if probe is not None and self._rc_fill_ok(opt):
            with _observe.span("cache.fill"):
                rc, key, gens = probe
                rc.put(key, gens, value, nbytes)

    @staticmethod
    def _rc_wait(opt) -> float:
        """Single-flight wait budget for a cache probe: never park a
        query on another reader's in-progress fill beyond its own
        deadline (the deadline checks run after the probe returns, so
        an uncapped wait could hold an admission slot 10x past a
        short budget just to report expiry)."""
        dl = None if opt is None else getattr(opt, "deadline", None)
        if dl is None:
            return resultcache.FLIGHT_WAIT_S
        return max(0.0, min(resultcache.FLIGHT_WAIT_S, dl.remaining()))

    def _execute_bitmap_call(self, idx, call: Call, shards, opt: ExecOptions) -> Row:
        _prep.note_walk()
        self._validate_call_fields(idx, call)
        shards = self._target_shards(idx, shards, opt)
        row = Row()

        with _observe.span("plan"):
            tree = self._prepare(idx, call)
            fused_ok = self._fuse_eligible(shards, tree)

        def batch_fn(group):
            # probe the result cache FIRST (stamp captured before any
            # fragment read); a hit skips the device entirely
            g = tuple(group)
            hit, val, probe = self._rc_get(idx, "row", g, opt, tree=tree)
            if hit:
                # copies both ways (fill and hit): cached words
                # must never alias a Row a caller may mutate
                return [(s, w.copy()) for s, w in val]
            # sparse trees route the compressed container engine
            # (ops/containers.py): one launch over the pooled
            # directory-matched containers, scattered back to dense
            # per-shard words here
            with _observe.span("plan"):
                m = self._query_mesh(opt)
                cplan = _containers.plan_fused(tree, g, opt,
                                               counts=False)

            def _dispatch():
                # the fused Row launch (dense or container-gather),
                # under the shared RESOURCE_EXHAUSTED evict-and-retry
                if cplan is not None:
                    return cplan.row_words(mesh=m)
                stack = self._fused_eval(idx, tree, g,
                                         use_delta=opt.delta, mesh=m)
                with _observe.span("reduce"):
                    # copies: a view would pin the whole stack in
                    # memory for as long as one sparse segment lives
                    stack = np.asarray(stack)
                    return [(s, stack[i].copy())
                            for i, s in enumerate(group)
                            if stack[i].any()]

            partials = _residency.run_with_oom_retry(_dispatch)
            if probe is not None and self._rc_fill_ok(opt):
                value = [(s, w.copy()) for s, w in partials]
                self._rc_put(probe, opt, value,
                             sum(w.nbytes for _, w in value)
                             + 32 * len(value))
            return partials

        self._note_route(fused_ok)
        if fused_ok and not self._cluster_active(opt):
            _deadline.check(opt.deadline, "map")
            with _observe.span("map.fused"):
                partials = batch_fn(shards)
        else:
            def map_fn(shard):
                return shard, self._bitmap_words_shard(idx, call, shard,
                                                        opt.delta)

            _prep.note_walk(len(shards))  # the tree, shard by shard
            partials = self._map_shards(
                map_fn, shards, idx=idx, call=call, opt=opt,
                adapt=lambda r: list(r.segments.items()),
                local_batch_fn=batch_fn if fused_ok else None,
            )
        for shard, words in partials:
            w = self._np_words(words)
            if w is not None and w.any():
                row.segments[shard] = w

        # Attach row attributes for plain Row() queries (reference
        # executor.go:206 attachment; skipped when excluded).
        if call.name == "Row" and not opt.exclude_row_attrs and not call.has_condition_arg():
            try:
                fname = call.field_arg()
                rowid = call.args.get(fname)
                f = idx.field(fname)
                if f is not None and isinstance(rowid, int):
                    row.attrs = f.row_attrs.attrs(rowid)
            except (ValueError, ExecutionError):
                pass
        return row

    def _bitmap_words_shard(self, idx, call: Call, shard: int,
                            use_delta: bool = True):
        """Evaluate a bitmap call tree for one shard.  Returns packed words
        (device or numpy) or None for empty (reference
        executeBitmapCallShard, executor.go:651).

        ``use_delta`` threads the ?nodelta=1 escape down the per-shard
        recursion (the remote map path and sub-fusion-width shard
        sets): True reads base ⊕ delta through the host overlay, False
        compacts up front and reads pure base."""
        name = call.name
        if name == _EMPTY_CALL:
            return None
        if name == "Row" or name == "Range":
            return self._row_words_shard(idx, call, shard, use_delta)
        if name == "Union":
            out = None
            for child in call.children:
                w = self._bitmap_words_shard(idx, child, shard, use_delta)
                if w is None:
                    continue
                out = w if out is None else bm.b_or(out, w)
            return out
        if name == "Intersect":
            if not call.children:
                raise ExecutionError("Intersect() requires at least one row query")
            out = self._bitmap_words_shard(idx, call.children[0], shard,
                                           use_delta)
            for child in call.children[1:]:
                if out is None:
                    return None
                w = self._bitmap_words_shard(idx, child, shard, use_delta)
                if w is None:
                    return None
                out = bm.b_and(out, w)
            return out
        if name == "Difference":
            if not call.children:
                raise ExecutionError("Difference() requires at least one row query")
            out = self._bitmap_words_shard(idx, call.children[0], shard,
                                           use_delta)
            for child in call.children[1:]:
                if out is None:
                    return None
                w = self._bitmap_words_shard(idx, child, shard, use_delta)
                if w is not None:
                    out = bm.b_andnot(out, w)
            return out
        if name == "Xor":
            out = None
            for child in call.children:
                w = self._bitmap_words_shard(idx, child, shard, use_delta)
                if w is None:
                    continue
                out = w if out is None else bm.b_xor(out, w)
            return out
        if name == "Not":
            if len(call.children) != 1:
                raise ExecutionError("Not() requires a single row query")
            ef = idx.existence_field()
            if ef is None:
                raise ExecutionError(
                    "Not() queries require the index to have 'trackExistence' enabled"
                )
            exist = self._field_row_words(ef, 0, shard, use_delta)
            if exist is None:
                return None
            child = self._bitmap_words_shard(idx, call.children[0], shard,
                                             use_delta)
            if child is None:
                return exist
            return bm.b_not(child, exist)
        if name == "Shift":
            if len(call.children) != 1:
                raise ExecutionError("Shift() requires a single row query")
            n = call.int_arg("n")
            n = 1 if n is None else n
            child = self._bitmap_words_shard(idx, call.children[0], shard,
                                             use_delta)
            if child is None:
                return None
            return bm.b_shift(child, n)
        if name == "Distinct":
            raise ExecutionError("Distinct() is not supported")
        raise ExecutionError(f"unknown call: {name}")

    def _field_row_words(self, f, row_id: int, shard: int,
                         use_delta: bool = True):
        view = f.view(VIEW_STANDARD)
        if view is None:
            return None
        frag = view.fragment(shard)
        if frag is None:
            return None
        # pilosa-lint: allow(lock-discipline) -- unlocked ref-read gate keeps the no-delta fast path lock-free; a detached plane is immutable, so the post-lock row_touched reads a consistent (worst case: stale flight-record note) snapshot
        d = frag._delta
        if d is not None and not d.empty() and use_delta:
            # pending streaming delta: answer from the effective host
            # words rather than device_row, whose matrix restack would
            # MERGE the plane — per-shard reads must not compact, or
            # sustained ingest turns every read into a generation bump
            # (exactly the churn the delta plane exists to absorb).
            # The resident base matrix stays untouched either way.
            with frag._lock:
                arr, owned = frag._row_words_effective_locked(row_id)
                if arr is None:
                    return None
                words = arr if owned else arr.copy()
            if d.row_touched(row_id):
                rec = _observe.current()
                if rec is not None:
                    rec.note_delta(1)
            return words
        # no pending delta — or ?nodelta=1, where device_row's stack
        # merge IS the requested up-front compaction (pure base read)
        return frag.device_row(row_id)

    def _row_words_shard(self, idx, call: Call, shard: int,
                         use_delta: bool = True):
        """Row() in its three forms: standard, time-range, BSI condition
        (reference executeRowShard, executor.go:1441)."""
        cond = call.condition_arg()
        if cond is not None:
            fname, condition = cond
            f = self._field(idx, fname)
            if condition.op == "><":
                lo, hi = condition.int_slice_value()
                return f.range_between(lo, hi, shard)
            if condition.value is None:
                if condition.op == "!=":  # != null -> not null
                    return f.not_null(shard)
                raise ExecutionError("Row(): EQ null condition is not supported")
            if not isinstance(condition.value, int) or isinstance(condition.value, bool):
                raise ExecutionError("Row(): conditions only support integer values")
            return f.range_op(condition.op, condition.value, shard)

        fname = call.field_arg()
        f = self._field(idx, fname)
        row_id = self._bool_row_id(f, call, fname)
        if row_id is None:
            raise ExecutionError(f"Row(): field {fname!r} requires an integer row")

        from_arg = call.args.get("from")
        to_arg = call.args.get("to")
        if from_arg is None and to_arg is None:
            return self._field_row_words(f, row_id, shard, use_delta)

        if not f.time_quantum:
            raise ExecutionError(f"field {fname!r} does not support time-range queries")
        start = parse_time(from_arg) if from_arg is not None else _dt.datetime(1, 1, 1)
        end = parse_time(to_arg) if to_arg is not None else _dt.datetime(9999, 1, 1)
        start, end = self._clamp_to_views(f, start, end)
        if start >= end:
            return None
        return f.row_time(row_id, shard, start, end)

    @staticmethod
    def _clamp_to_views(f, start, end):
        """Clamp an open-ended time range to the span actually covered by
        existing time views (mirrors minMaxViews clamping in
        executeRowsShard, executor.go); the view-name scan is memoized
        by Field.time_view_times."""
        times = f.time_view_times()
        if not times:
            return start, start  # no time views -> empty
        lo = min(times)
        hi = max(times) + _dt.timedelta(days=366)
        return max(start, lo), min(end, hi)

    # ------------------------------------------------------------- counts

    def _execute_count(self, idx, call: Call, shards, opt: ExecOptions) -> int:
        if len(call.children) != 1:
            raise ExecutionError("Count() requires a single bitmap query")
        shards = self._target_shards(idx, shards, opt)
        with _observe.span("plan"):
            # the tree's ONE walk.  Key translation happens in it, once,
            # at the originating node (a tree that came translated, as
            # under Options, holds no string key any more)
            tree = self._prepare(idx, call.children[0],
                                 translate=not opt.remote)
            fused_ok = self._fuse_eligible(shards, tree)
        child = tree.call
        if child is not call.children[0]:
            call = Call("Count", call.args, [child])
        scope = getattr(_tls, "scope", None)
        if scope is not None:
            tree.books = scope.books
            scope.books.count(self.stats, "plan.prepared", 1)

        def compute_counts_once(group):
            # the whole tree INCLUDING the popcount root as one compiled
            # program (ops.expr) — a single dispatch for the group, with
            # XLA fusing AND+popcount so no intersection stack
            # materializes (the host engine keeps the native pairwise
            # kernel for the same reason); per-shard int32 counts summed
            # in Python ints — a single int32 reduce over the stack
            # could wrap past 2^31 set bits.  Sparse trees route the
            # compressed container engine first (ops/containers.py):
            # same single launch, but only the directory-matched
            # container blocks are ever read
            with _observe.span("plan"):
                m = self._query_mesh(opt)
                cplan = _containers.plan_fused(tree, tuple(group), opt)
            if cplan is not None:
                return cplan.counts(mesh=m)
            shape, leaves = self._fused_expr(idx, tree, tuple(group),
                                             use_delta=opt.delta)
            with _observe.span("launch") as sp:
                counts = expr.evaluate(shape, leaves, counts=True, mesh=m)
                sp.note_engine()
            with _observe.span("reduce"):
                return [int(c) for c in
                        expr.counts_to_host(counts)[:len(group)]]

        def compute_counts(group):
            # device-dispatch resilience: a backend RESOURCE_EXHAUSTED
            # evicts every residency-tracked device cache entry
            # (demoting — host twins survive), shrinks the HBM budget
            # so the tier demotes harder, and retries ONCE — the
            # shared run_with_oom_retry wrapper, applied to every
            # fused dispatch site (Count/Row/TopN/coalescer/mesh)
            return _residency.run_with_oom_retry(
                lambda: compute_counts_once(group))

        def batch_fn(group):
            # the clustered local-group path: per-shard counts for the
            # shards THIS node owns, cached under their own key so
            # every owner (replicas included) warms independently —
            # the remote map path caches on the remote side through
            # the single-node branch below when the sub-query arrives
            g = tuple(group)
            hit, val, probe = self._rc_get(idx, "count_shards", g, opt,
                                           tree=tree)
            if hit:
                return list(val)
            vals = compute_counts(group)
            self._rc_put(probe, opt, tuple(vals), 16 * len(vals))
            return vals

        self._note_route(fused_ok)
        if fused_ok and not self._cluster_active(opt):
            _deadline.check(opt.deadline, "map")
            # result-cache probe BEFORE the coalescer: a hit answers
            # pre-window and never occupies a batch slot
            hit, val, probe = self._rc_get(idx, "count", tuple(shards),
                                           opt, tree=tree)
            if hit:
                return val
            if (self.coalescer is not None
                    and self.coalescer.eligible(opt)):
                # the coalescer stamps the record itself (path,
                # batch occupancy, queue-wait vs launch split), drops
                # this entry from the batch if its deadline dies in
                # the window, and fills the cache for every flushed
                # batch member
                return self.coalescer.count(self, idx, tree,
                                            tuple(shards),
                                            deadline=opt.deadline,
                                            cache_fill=probe,
                                            use_delta=opt.delta,
                                            mesh=self._query_mesh(opt),
                                            tenant=opt.tenant,
                                            # ?nocontainers disables
                                            # the VM too: it executes
                                            # over compressed pools
                                            use_vm=(opt.vm
                                                    and opt.containers))
            with _observe.span("map.fused"):
                total = sum(compute_counts(shards))
            self._rc_put(probe, opt, total, 32)
            return total

        def map_fn(shard):
            words = self._bitmap_words_shard(idx, child, shard,
                                             opt.delta)
            if words is None:
                return 0
            return int(bm.popcount(words))

        _prep.note_walk(len(shards))  # the tree, shard by shard
        return sum(
            self._map_shards(
                map_fn, shards, idx=idx, call=call, opt=opt,
                adapt=lambda v: [v],
                local_batch_fn=batch_fn if fused_ok else None,
            )
        )

    # --------------------------------------------------------------- TopN

    def _execute_topn(self, idx, call: Call, shards, opt: ExecOptions) -> list[Pair]:
        """Exact TopN via batched device row scans (replaces the
        reference's approximate rank-cache two-phase protocol,
        executor.go:860-1038 — same results on non-tied data, exact
        counts always)."""
        fname = call.string_arg("_field") or call.args.get("_field")
        if not fname:
            raise ExecutionError("TopN() requires a field argument")
        f = self._field(idx, fname)
        n = call.uint_arg("n") or 0
        ids_arg = call.uint_slice_arg("ids")
        threshold = call.uint_arg("threshold") or 0
        attr_name = call.string_arg("attrName")
        attr_values = call.args.get("attrValues")
        tanimoto = call.uint_arg("tanimotoThreshold") or 0
        if tanimoto > 100:
            raise ExecutionError("Tanimoto Threshold is from 1 to 100 only")
        shards = self._target_shards(idx, shards, opt)
        filter_call = call.children[0] if call.children else None

        # A truncated per-shard cache is only exact when there is nothing
        # to merge with: multi-shard aggregation of per-shard top lists
        # loses rows that rank below the truncation point in one shard
        # but high globally.  Post-count filters likewise require the
        # complete row set.  cache_n=0 demands a complete cache.
        single_shard = len(shards) == 1
        cache_n = n if single_shard and not (ids_arg or attr_name or threshold) else 0
        engine = self._raw_engine(self._query_mesh(opt))

        def map_fn(shard):
            view = f.view(VIEW_STANDARD)
            frag = view.fragment(shard) if view is not None else None
            if frag is None:
                return {}
            if filter_call is None:
                cached = frag.cached_row_counts(cache_n)
                if cached is not None:
                    return cached
            gen, row_ids, matrix = frag.device_matrix_with_gen()
            if len(row_ids) == 0:
                return {}
            if filter_call is not None:
                fw = self._bitmap_words_shard(idx, filter_call, shard,
                                              opt.delta)
                if fw is None:
                    return {}
                # Pallas single-pass kernel on TPU for large matrices,
                # fused jnp otherwise (identical counts)
                from pilosa_tpu.ops import pallas_kernels as pk

                counts = _perfobs.launch(
                    engine, lambda: pk.row_counts_masked(matrix, fw))
            else:
                counts = _perfobs.launch(
                    engine, lambda: bm.row_counts(matrix))
            counts = np.asarray(counts)
            out = {int(r): int(c) for r, c in zip(row_ids, counts) if c > 0}
            if filter_call is None:
                frag.cache_row_counts(out, gen=gen)
            return out

        # Remote sub-queries must return complete per-node counts: n and
        # threshold truncate on *summed* counts, which only the
        # originating reduce can compute (the reference's two-phase
        # candidate protocol, executor.go:860-928, exists for the same
        # reason).
        remote_call = call.clone()
        remote_call.args.pop("n", None)
        remote_call.args.pop("threshold", None)
        remote_call.args.pop("tanimotoThreshold", None)

        with _observe.span("plan"):
            filt = self._prepare(idx, filter_call)
            fused_ok = self._fuse_eligible(shards, filt)
        self._note_route(fused_ok)

        def batch_fn(group):
            # same hook shape as the Count/Row fused paths: one stacked
            # dispatch for the whole locally-owned group
            return [self._fused_topn_counts(idx, f, filt,
                                            tuple(group), opt=opt)]

        if fused_ok and not self._cluster_active(opt):
            _deadline.check(opt.deadline, "map")
            with _observe.span("map.fused"):
                parts = batch_fn(shards)
        else:
            parts = self._map_shards(
                map_fn, shards, idx=idx, call=call, opt=opt,
                adapt=lambda pairs: [{p.id: p.count for p in pairs}],
                remote_call=remote_call,
                local_batch_fn=batch_fn if fused_ok else None,
            )
        totals = {}
        for part in parts:
            for r, c in part.items():
                totals[r] = totals.get(r, 0) + c

        if ids_arg:
            allowed = set(ids_arg)
            totals = {r: c for r, c in totals.items() if r in allowed}
        if attr_name:
            if not isinstance(attr_values, list):
                raise ExecutionError("TopN() attrValues must be a list")
            allowed_vals = set(attr_values)
            row_attrs = f.row_attrs.attrs_bulk(totals)
            totals = {
                r: c
                for r, c in totals.items()
                if row_attrs.get(r, {}).get(attr_name) in allowed_vals
            }
        if tanimoto and filter_call is not None:
            # Tanimoto similarity (reference fragment.top): the count
            # pre-window — full row count strictly inside
            # (|src|*T/100, |src|*100/T), fragment.go:1588-1617 — then
            # the exact coefficient ceil(100*|A∩src| /
            # (|A|+|src|-|A∩src|)) > T, fragment.go:1649-1652.  The
            # reference applies both per shard with per-shard counts;
            # here counts are global — consistent with this executor's
            # exact (non-rank-cache) TopN.
            import math

            src_count = self._execute_count(
                idx, Call("Count", children=[filter_call]), shards, opt)
            if fused_ok and not self._cluster_active(opt):
                # reuse the stacked scan directly — the filtered totals
                # above already warmed the matrix stack, so the
                # unfiltered pass is one more dispatch (and fragment
                # caches make repeats free); no Pair-sort detour
                full_counts = self._fused_topn_counts(idx, f, None,
                                                      tuple(shards),
                                                      opt=opt)
            else:
                full = self._execute_topn(
                    idx, Call("TopN", {"_field": fname}), shards, opt)
                full_counts = {p.id: p.count for p in full}
            lo = src_count * tanimoto / 100.0
            hi = src_count * 100.0 / tanimoto
            kept = {}
            for r, inter in totals.items():
                cnt = full_counts.get(r, 0)
                if not (lo < cnt < hi) or inter == 0:
                    continue
                coeff = math.ceil(inter * 100.0
                                  / (cnt + src_count - inter))
                if coeff > tanimoto:
                    kept[r] = inter
            totals = kept
        elif threshold:
            totals = {r: c for r, c in totals.items() if c >= threshold}

        pairs = sort_pairs([Pair(id=r, count=c) for r, c in totals.items()])
        if n:
            pairs = pairs[:n]
        return pairs

    def _fused_topn_counts(self, idx, f, filt,
                           shards: tuple[int, ...],
                           opt: ExecOptions | None = None
                           ) -> dict[int, int]:
        """All shards' TopN row counts, answered from the result cache
        when the scan (field matrix + filter leaves) is still at the
        stamped generations, else in ONE device dispatch — the per-
        fragment TopNCache generalized to the whole cross-shard scan.
        ``filt`` is the filter tree as prepared (None: no filter)."""
        hit, val, probe = self._rc_get(
            idx, "topn", shards, opt, tree=filt, extra=f.name,
            gen_fields=((f, VIEW_STANDARD),))
        if hit:
            return dict(val)
        totals = self._fused_topn_counts_uncached(idx, f, filt,
                                                  shards, opt=opt)
        if probe is not None and self._rc_fill_ok(opt):
            self._rc_put(probe, opt, dict(totals),
                         resultcache.result_nbytes(totals))
        return totals

    def _fused_topn_counts_uncached(self, idx, f, filt,
                                    shards: tuple[int, ...],
                                    opt: ExecOptions | None = None
                                    ) -> dict[int, int]:
        """All shards' TopN row counts in ONE device dispatch over the
        field's concatenated matrix stack (vs one scan per fragment).
        Unfiltered results also warm every fragment's TopN cache, so
        repeat queries skip the device entirely."""
        view = f.view(VIEW_STANDARD)
        totals: dict[int, int] = {}
        if view is None:
            return totals
        if filt is None:
            # whole-scan short-circuit: every fragment's cache complete
            cached_parts = []
            for s in shards:
                frag = view.fragment(s)
                if frag is None:
                    continue
                c = frag.cached_row_counts(0)
                if c is None:
                    cached_parts = None
                    break
                cached_parts.append(c)
            if cached_parts is not None:
                for part in cached_parts:
                    for r, c in part.items():
                        totals[r] = totals.get(r, 0) + c
                return totals

        def _scan():
            # the fused TopN matrix scan, under the shared
            # RESOURCE_EXHAUSTED evict-and-retry.  The matrix stack is
            # fetched INSIDE the retry scope: on an OOM, evict_all()
            # drops its cache entry, so the retry restages the query's
            # own largest operand post-eviction instead of
            # re-dispatching against the pinned pre-OOM buffers.
            with _observe.span("stage"):
                stack = f.device_matrix_stack(shards)
            mat_dev, pos_dev = stack[4], stack[3]
            if mat_dev is None:
                return stack, None
            engine = self._raw_engine(self._query_mesh(opt))
            if filt is not None:
                words = self._fused_eval(
                    idx, filt, shards,
                    use_delta=opt is None or opt.delta,
                    mesh=self._query_mesh(opt))
                return stack, _perfobs.launch(
                    engine, lambda: bm.row_counts_gathered(
                        mat_dev, words, pos_dev))
            return stack, _perfobs.launch(
                engine, lambda: bm.row_counts(mat_dev))

        (gens, row_ids, shard_pos, _pos_dev, _mat_dev), counts = \
            _residency.run_with_oom_retry(_scan)
        if counts is None:
            return totals
        with _observe.span("reduce"):
            return self._topn_totals(view, shards, filt, gens,
                                     row_ids, shard_pos, counts)

    @staticmethod
    def _topn_totals(view, shards, filt, gens, row_ids,
                     shard_pos, counts) -> dict[int, int]:
        """The host tail of the fused TopN scan: counts to the host,
        summed per row, and every fragment's TopN cache warmed."""
        totals: dict[int, int] = {}
        n_rows = len(row_ids)
        counts = np.asarray(counts, dtype=np.int64)[:n_rows]
        if filt is not None:
            for rid, c in zip(row_ids, counts):
                if c > 0:
                    rid = int(rid)
                    totals[rid] = totals.get(rid, 0) + int(c)
            return totals

        per_shard: dict[int, dict[int, int]] = {}
        for rid, pos, c in zip(row_ids, shard_pos, counts):
            if c > 0:
                rid, c = int(rid), int(c)
                totals[rid] = totals.get(rid, 0) + c
                per_shard.setdefault(int(pos), {})[rid] = c
        # warm every fragment's cache — including ones whose rows all
        # counted zero, whose complete answer is "no rows".  gens slots
        # are (uid, gen) tokens (field._frag_gen): stamp the cache with
        # the bare gen, and only when the token's uid still matches the
        # live object — a fragment replaced mid-query (resize re-fetch)
        # must not have a fresh object's cache validated by a stale scan
        for pos, s in enumerate(shards):
            frag = view.fragment(s)
            tok = gens[pos]
            if (frag is not None and isinstance(tok, tuple)
                    and tok[0] == frag._uid):
                frag.cache_row_counts(per_shard.get(pos, {}), gen=tok[1])
        return totals

    # --------------------------------------------------------------- Rows

    def _execute_rows(self, idx, call: Call, shards, opt: ExecOptions) -> list[int]:
        # "field=" is the reference's backwards-compat spelling of the
        # positional field (executor.go:1090-1093)
        fname = call.args.get("_field") or call.args.get("field")
        if not fname:
            raise ExecutionError("Rows() requires a field argument")
        f = self._field(idx, fname)
        limit = call.uint_arg("limit")
        previous = call.uint_arg("previous")
        column = call.uint_arg("column")
        shards = self._target_shards(idx, shards, opt)

        # Time fields with from=/to= (or no standard view) scan the
        # covering time views instead of standard — the reference's
        # executeRowsShard view selection with open ends clamped to
        # the existing views' min/max (executor.go:1319-1400); a
        # non-time field ignores from/to exactly as the reference does
        views = [VIEW_STANDARD]
        if f.time_quantum and ("from" in call.args
                                    or "to" in call.args
                                    or f.options.no_standard_view):
            cover = self._time_range_views(f, call)
            if cover is None:
                raise ExecutionError("Rows(): malformed from/to time")
            views = cover
            if not views:
                return []

        def push_down(ids: list[int]) -> list[int]:
            # previous/limit apply inside the shard scan (reference
            # executeRowsShard pushes the filter into the row iterator,
            # executor.go:1040-1071): a shard never ships more than
            # ``limit`` ids past ``previous``, so the host-side merge is
            # bounded by shards*limit, not total row cardinality
            if previous is not None:
                ids = ids[bisect.bisect_right(ids, previous):]
            if limit is not None:
                ids = ids[:limit]
            return ids

        def map_fn(shard):
            if column is not None and shard != column // SHARD_WIDTH:
                return []
            frags = []
            for vname in views:
                view = f.view(vname)
                frag = view.fragment(shard) if view is not None else None
                if frag is not None:
                    frags.append(frag)
            if not frags:
                return []
            if column is not None:
                # one vectorized read of the column's word down the row
                # matrix per view (reference rowFilter ColumnFilter,
                # fragment.go:2618) — a row qualifies when the bit is
                # set in ANY covering view (merged-row semantics)
                off = column % SHARD_WIDTH
                w, b = off // bm.WORD_BITS, off % bm.WORD_BITS
                hit: set[int] = set()
                for frag in frags:
                    ids_arr, matrix = frag._stacked()
                    if len(ids_arr) == 0:
                        continue
                    mask = (matrix[:, w] >> np.uint32(b)) & np.uint32(1)
                    hit.update(int(r) for r in ids_arr[mask.astype(bool)])
                return push_down(sorted(hit))
            if len(frags) == 1:
                return push_down(frags[0].row_ids())
            merged: set[int] = set()
            for frag in frags:
                merged.update(frag.row_ids())
            return push_down(sorted(merged))

        parts = self._map_shards(
            map_fn, shards, idx=idx, call=call, opt=opt, adapt=lambda ids: [ids]
        )
        # bounded k-way merge of the per-shard sorted lists (reference
        # mergeRowIDs, executor.go:1062-1071): dedup on the fly and stop
        # at ``limit`` — never a full union across shards
        out: list[int] = []
        for r in heapq.merge(*parts):
            if out and r == out[-1]:
                continue
            out.append(r)
            if limit is not None and len(out) >= limit:
                break
        return out

    # ------------------------------------------------------------ GroupBy

    def _execute_group_by(self, idx, call: Call, shards, opt: ExecOptions) -> list[GroupCount]:
        """Cartesian intersection counts over child Rows queries
        (reference groupByIterator, executor.go:3058), batched on device:
        each level ANDs the running group bitmap against the whole child
        row matrix and prunes empty groups."""
        if not call.children:
            raise ExecutionError("GroupBy() requires at least one Rows query")
        for child in call.children:
            if child.name != "Rows":
                raise ExecutionError("GroupBy() children must be Rows queries")
        limit = call.uint_arg("limit")
        filter_call = call.call_arg("filter")
        filt = self._prepare(idx, filter_call)
        shards = self._target_shards(idx, shards, opt)
        # result cache: a GroupBy's value depends on EVERY row of its
        # child fields, so the stamp covers the whole standard view of
        # each child (plus the filter leaves); eligibility is
        # conservative — plain standard-view children only, filter
        # absent or fused-supported — and the truncation args ride the
        # key, so the post-limit result caches directly
        probe = None
        if not self._cluster_active(opt):
            key_args = self._groupby_cache_args(idx, call, filt)
            if key_args is not None:
                hit, val, probe = self._rc_get(idx, "groupby",
                                               tuple(shards), opt,
                                               **key_args)
                if hit:
                    # deep copy: result translation writes row_key
                    # onto the returned objects and must not mutate
                    # the cached value
                    return self._copy_group_counts(val)
        child_fields = []
        child_allowed: list[set | None] = []
        for child in call.children:
            fname = child.args.get("_field") or child.args.get("field")
            if not fname:
                raise ExecutionError("Rows() requires a field argument")
            child_fields.append(self._field(idx, fname))
            # Rows children with limit/column/previous constraints
            # pre-execute CLUSTER-WIDE once at the originating node and
            # restrict the walk (reference executeGroupBy,
            # executor.go:1084-1117 — except the reference lets each
            # remote node recompute its own LOCAL truncation, which can
            # disagree with the global one; here remotes run the
            # unconstrained walk and the origin filters at reduce, so
            # the restriction is globally consistent)
            if (child.uint_arg("limit") is not None
                    or child.uint_arg("column") is not None
                    or child.uint_arg("previous") is not None):
                allowed = self._execute_rows(idx, child, shards, opt)
                if not allowed:
                    return []
                child_allowed.append(set(allowed))
            else:
                child_allowed.append(None)

        # Fused-supported filters evaluate ONCE as a stacked device
        # computation over the shards THIS node will scan (all of them
        # single-node; the locally-owned group when clustered — the
        # same local-group fusion Count/TopN get via local_batch_fn);
        # map_fn slices its shard's row out of the stack instead of
        # re-evaluating the filter tree per shard.
        filt_stack = None
        shard_pos: dict[int, int] = {}
        # the cartesian walk is a per-shard map whatever the filter does
        self._note_route(False)
        engine = self._raw_engine(self._query_mesh(opt))
        if filt is not None and self._fuse_eligible(shards, filt):
            if self._cluster_active(opt):
                group = sorted(self.cluster.local_shards(idx.name, shards))
            else:
                group = list(shards)
            if len(group) > 1:
                shard_pos = {s: i for i, s in enumerate(group)}
                filt_stack = self._fused_eval(idx, filt,
                                              tuple(group),
                                              use_delta=opt.delta,
                                              mesh=self._query_mesh(opt))

        def map_fn(shard):
            import jax.numpy as jnp

            mats = []
            for f, allowed in zip(child_fields, child_allowed):
                view = f.view(VIEW_STANDARD)
                frag = view.fragment(shard) if view is not None else None
                if frag is None:
                    return {}
                row_ids, matrix = frag.device_matrix()
                if allowed is not None and len(row_ids):
                    keep = np.flatnonzero(np.isin(
                        row_ids, np.fromiter(allowed, dtype=np.int64)))
                    row_ids = row_ids[keep]
                    matrix = matrix[keep] if len(keep) else matrix[:0]
                if len(row_ids) == 0:
                    return {}
                mats.append((f.name, row_ids, matrix))
            # Batched cartesian walk: at each level ONE dispatch counts
            # every (group, child-row) pair and one more builds the
            # surviving groups' masks — vs the reference's per-group
            # iterator (groupByIterator, executor.go:3058).  Pair counts
            # are padded to powers of two so XLA compiles O(log) shapes,
            # not one program per group-count.
            prefixes: list[tuple] = [()]
            # masks stays PADDED (power-of-two rows) across levels; the
            # live-group count is len(prefixes).  Padded garbage rows are
            # never read — counts are host-sliced to the live range.
            masks = None  # device [G_padded, words]; None = unconstrained
            host = isinstance(mats[0][2], np.ndarray) if mats else False
            if filt_stack is not None and shard in shard_pos:
                masks = filt_stack[shard_pos[shard]][None, :]
            elif filter_call is not None:
                base = self._bitmap_words_shard(idx, filter_call, shard,
                                                opt.delta)
                if base is None:
                    return {}
                # keep the filter on the same engine as the child
                # matrices: numpy in host mode (so masked_matrix_counts
                # / and_pairs dispatch to the native kernels), jax on
                # device
                masks = (np.asarray(base)[None, :] if host
                         else jnp.asarray(base)[None, :])
            for level, (fname, row_ids, matrix) in enumerate(mats):
                last = level == len(mats) - 1
                if masks is None:
                    cnts = np.asarray(_perfobs.launch(
                        engine, lambda: bm.row_counts(matrix)))[None, :]
                else:
                    # Pallas single-pass kernel on TPU for large
                    # products, bm dispatch (native host / jit)
                    # otherwise — identical counts
                    from pilosa_tpu.ops import pallas_kernels as pk

                    cnts = np.asarray(_perfobs.launch(
                        engine, lambda: pk.masked_matrix_counts(
                            matrix, masks)))[:len(prefixes)]
                nz_g, nz_r = np.nonzero(cnts)
                if len(nz_g) == 0:
                    return {}
                if last:
                    return {
                        prefixes[g] + ((fname, int(row_ids[r])),):
                            int(cnts[g, r])
                        for g, r in zip(nz_g, nz_r)
                    }
                new_prefixes = [
                    prefixes[g] + ((fname, int(row_ids[r])),)
                    for g, r in zip(nz_g, nz_r)
                ]
                p = len(nz_g)
                pp = _next_pow2(p)
                slots = np.zeros(pp, dtype=np.int32)
                slots[:p] = nz_r
                if masks is None:
                    new_masks = (np.take(matrix, slots, axis=0) if host
                                 else jnp.take(matrix, jnp.asarray(slots),
                                               axis=0))
                else:
                    gsel = np.zeros(pp, dtype=np.int32)
                    gsel[:p] = nz_g
                    new_masks = bm.and_pairs(matrix, masks, slots, gsel)
                prefixes, masks = new_prefixes, new_masks
            return {}

        def gc_adapt(gcs):
            return [
                {
                    tuple((fr.field, fr.row_id) for fr in gc.group): gc.count
                    for gc in gcs
                }
            ]

        # Remote nodes run the UNCONSTRAINED walk: child limit/column/
        # previous are stripped (the origin's cluster-wide allowed sets
        # are the single source of truth; group keys outside them drop
        # at reduce), and so are the top-level limit/offset — a remote
        # truncating its OWN sorted groups would lose partial counts
        # for group keys that span nodes.  Counts are unaffected by the
        # stripping: a group's count never depends on which other rows
        # were walked.
        remote_call = call.clone()
        remote_call.args.pop("limit", None)
        remote_call.args.pop("offset", None)
        for child in remote_call.children:
            child.args.pop("limit", None)
            child.args.pop("column", None)
            child.args.pop("previous", None)

        totals: dict[tuple, int] = {}
        parts = self._map_shards(
            map_fn, shards, idx=idx, call=call, opt=opt, adapt=gc_adapt,
            remote_call=remote_call,
        )
        for part in parts:
            for key, c in part.items():
                if any(
                    allowed is not None and key[i][1] not in allowed
                    for i, allowed in enumerate(child_allowed)
                ):
                    continue
                totals[key] = totals.get(key, 0) + c

        out = [
            GroupCount(group=[FieldRow(field=f, row_id=r) for f, r in key], count=c)
            for key, c in sorted(totals.items())
        ]
        # offset before limit (reference executeGroupBy,
        # executor.go:1135-1149)
        offset = call.uint_arg("offset")
        if offset is not None:
            out = out[offset:] if offset < len(out) else out
        if limit is not None:
            out = out[:limit]
        if probe is not None and self._rc_fill_ok(opt):
            self._rc_put(probe, opt, self._copy_group_counts(out),
                         resultcache.result_nbytes(out) * 2)
        return out

    def _groupby_cache_args(self, idx, call: Call, filt):
        """What keys and stamps a GroupBy in the result cache (the
        ``_rc_probe`` arguments), or None when ineligible: every
        child must be a plain standard-view Rows (time-view covers and
        no-standard-view fields change shape under writes in ways the
        per-view stamp would have to chase), the filter absent or a
        fused-supported tree (anything else has no canonical leaf
        signature to stamp)."""
        sig_children = []
        gen_fields = []
        for child in call.children:
            if child.name != "Rows":
                return None
            fname = child.args.get("_field") or child.args.get("field")
            if not fname:
                return None
            f = idx.field(fname)
            if (f is None or f.time_quantum
                    or f.options.no_standard_view
                    or "from" in child.args or "to" in child.args):
                return None
            sig_children.append((fname, child.uint_arg("limit"),
                                 child.uint_arg("column"),
                                 child.uint_arg("previous")))
            gen_fields.append((f, VIEW_STANDARD))
        if filt is not None and not filt.fused:
            return None
        extra = (tuple(sig_children), call.uint_arg("limit"),
                 call.uint_arg("offset"))
        return {"tree": filt, "extra": extra,
                "gen_fields": gen_fields}

    @staticmethod
    def _copy_group_counts(res: list) -> list:
        return [replace(gc, group=[replace(fr) for fr in gc.group])
                for gc in res]

    # --------------------------------------------------- BSI aggregates

    def _local_filter_row(self, idx, call: Call, shards, opt: ExecOptions):
        """Evaluate an aggregate's filter child for the shards this node
        will scan itself.  In a cluster the remote nodes re-evaluate the
        filter for their own shards when the forwarded aggregate arrives,
        so computing it cluster-wide at the origin would be wasted work
        (and a redundant distributed round-trip)."""
        if not call.children:
            return None
        if self._cluster_active(opt):
            local = sorted(self.cluster.local_shards(idx.name, shards))
            return self._execute_bitmap_call(
                idx, call.children[0], local, replace(opt, remote=True, shards=local)
            )
        return self._execute_bitmap_call(idx, call.children[0], shards, opt)

    def _execute_aggregate(self, idx, call: Call, shards, opt: ExecOptions) -> ValCount:
        fname = call.string_arg("field") or call.args.get("field")
        if not fname:
            raise ExecutionError(f"{call.name}() requires a field argument")
        f = self._field(idx, fname)
        shards = self._target_shards(idx, shards, opt)

        filt = self._prepare(idx,
                             call.children[0] if call.children else None)
        fused_ok = self._fuse_eligible(
            shards, filt, extra=f.options.type == FieldType.INT)
        if call.name == "Sum":
            def batch_fn(group):
                return [self._fused_sum(idx, f, filt, tuple(group),
                                        use_delta=opt.delta,
                                        mesh=self._query_mesh(opt))]
        else:
            def batch_fn(group):
                return [self._fused_extreme(idx, f, filt,
                                            call.name == "Min",
                                            tuple(group),
                                            use_delta=opt.delta,
                                            mesh=self._query_mesh(opt))]

        self._note_route(fused_ok)
        if fused_ok and not self._cluster_active(opt):
            _deadline.check(opt.deadline, "map")
            with _observe.span("map.fused"):
                return batch_fn(shards)[0]

        filter_row = self._local_filter_row(idx, call, shards, opt)
        local_batch_fn = batch_fn if fused_ok else None

        if call.name == "Sum":
            def map_fn(shard):
                s, c = f.sum(filter_row, shard)
                return ValCount(s, c)

            out = ValCount()
            for vc in self._map_shards(
                map_fn, shards, idx=idx, call=call, opt=opt,
                adapt=lambda v: [v], local_batch_fn=local_batch_fn,
            ):
                out = out.add(vc)
            return out

        reducer = "smaller" if call.name == "Min" else "larger"

        def map_fn(shard):
            r = f.min(None if filter_row is None else filter_row, shard) if call.name == "Min" else f.max(
                None if filter_row is None else filter_row, shard
            )
            if r is None:
                return ValCount()
            return ValCount(r[0], r[1])

        out = ValCount()
        for vc in self._map_shards(
            map_fn, shards, idx=idx, call=call, opt=opt,
            adapt=lambda v: [v], local_batch_fn=local_batch_fn,
        ):
            out = getattr(out, reducer)(vc)
        return out

    def _fused_sum(self, idx, f, tree, shards: tuple[int, ...],
                   use_delta: bool = True, mesh=None) -> ValCount:
        """Sum over all shards in one stacked dispatch: plane counts from
        the [S, planes, W] BSI stack, exact assembly in Python ints
        (reference fragment.sum per shard, fragment.go:1111; here the
        shard loop is the stack's leading axis)."""
        from pilosa_tpu.ops import bsi as bsi_ops

        with _observe.span("stage"):
            P = f.device_plane_stack(shards)
        filt = None
        if tree is not None:
            filt = self._fused_eval(idx, tree, shards,
                                    use_delta=use_delta, mesh=mesh)

        def scan():
            consider = P[:, bsi_ops.EXISTS_PLANE]
            if filt is not None:
                # the filter stack is padded to the same device multiple
                consider = consider & filt
            return bsi_ops.plane_counts_stacked(P, consider)

        pos, neg, count = _perfobs.launch(self._raw_engine(mesh), scan)
        with _observe.span("reduce"):
            pos = np.asarray(pos, dtype=np.int64).sum(axis=0)
            neg = np.asarray(neg, dtype=np.int64).sum(axis=0)
            total_count = int(np.asarray(count, dtype=np.int64).sum())
            total = sum((1 << i) * (int(p) - int(n))
                        for i, (p, n) in enumerate(zip(pos, neg)))
        return ValCount(total + total_count * f.options.base, total_count)

    def _fused_extreme(self, idx, f, tree, is_min: bool,
                       shards: tuple[int, ...],
                       use_delta: bool = True, mesh=None) -> ValCount:
        """Min/Max over all shards from one stacked dispatch: the
        vmapped extreme scans produce every per-shard candidate; the
        host applies the sign-branching of fragment.min/max
        (fragment.go:1147/1191) and folds with smaller/larger."""
        from pilosa_tpu.ops import bsi as bsi_ops

        with _observe.span("stage"):
            P = f.device_plane_stack(shards)
        filt = None
        if tree is not None:
            filt = self._fused_eval(idx, tree, shards,
                                    use_delta=use_delta, mesh=mesh)
        want = "min" if is_min else "max"

        def scan():
            consider = P[:, bsi_ops.EXISTS_PLANE]
            if filt is not None:
                consider = consider & filt
            return bsi_ops.extremes_stacked(P, consider, want)

        (signed_cnt, all_cnt, primary_taken, fallback_taken,
         primary_n, fallback_n) = [
            np.asarray(x)
            for x in _perfobs.launch(self._raw_engine(mesh), scan)]

        reducer = "smaller" if is_min else "larger"
        out = ValCount()
        for s in range(len(shards)):
            if all_cnt[s] == 0:
                continue
            if signed_cnt[s] > 0:
                # Min: a negative exists -> largest negative magnitude;
                # Max: a positive exists -> largest positive magnitude
                v = bsi_ops.assemble_value(primary_taken[s])
                if is_min:
                    v = -v
                c = int(primary_n[s])
            else:
                # fallback: smallest magnitude among what remains
                v = bsi_ops.assemble_value(fallback_taken[s])
                if not is_min:
                    v = -v  # Max of all-negative = closest to zero
                c = int(fallback_n[s])
            out = getattr(out, reducer)(
                ValCount(v + f.options.base, c))
        return out

    def _execute_extreme_row(self, idx, call: Call, shards, opt: ExecOptions) -> Pair:
        """MinRow/MaxRow (reference executeMinRow/executeMaxRow,
        executor.go:3029)."""
        fname = call.string_arg("field") or call.args.get("field")
        if not fname:
            raise ExecutionError(f"{call.name}() requires a field argument")
        f = self._field(idx, fname)
        shards = self._target_shards(idx, shards, opt)
        is_min = call.name == "MinRow"
        filter_call = call.children[0] if call.children else None
        filt = self._prepare(idx, filter_call)
        fused_ok = self._fuse_eligible(shards, filt)

        def batch_fn(group):
            # ONE stacked dispatch for the whole group (the TopN scan),
            # then a host argmin/argmax over the row totals — replaces
            # the per-row device round-trips of the old walk
            totals = self._fused_topn_counts(idx, f, filt,
                                             tuple(group), opt=opt)
            live = [r for r, c in totals.items() if c > 0]
            if not live:
                return [Pair()]
            rid = min(live) if is_min else max(live)
            return [Pair(id=rid, count=totals[rid])]

        if fused_ok and not self._cluster_active(opt):
            parts = batch_fn(shards)
        else:
            # when fused_ok the local group goes through batch_fn, which
            # evaluates the filter itself — map_fn only runs on this
            # node when fusion is off, so the eager evaluation (which
            # must happen OUTSIDE the worker pool: it fans out itself)
            # is skipped entirely in the fused case
            filter_row = (None if fused_ok
                          else self._local_filter_row(idx, call, shards, opt))

            def map_fn(shard):
                view = f.view(VIEW_STANDARD)
                frag = view.fragment(shard) if view is not None else None
                if frag is None:
                    return Pair()
                ids = frag.row_ids()
                if not is_min:
                    ids = list(reversed(ids))
                fw = (None if filter_row is None
                      else filter_row.shard_segment(shard))
                if filter_row is not None and fw is None:
                    return Pair()
                for rid in ids:
                    words = frag.row(rid)
                    if fw is not None:
                        words = words & fw
                    c = int(np.bitwise_count(words).sum())
                    if c > 0:
                        return Pair(id=rid, count=c)
                return Pair()

            parts = self._map_shards(
                map_fn, shards, idx=idx, call=call, opt=opt,
                adapt=lambda p: [p],
                local_batch_fn=batch_fn if fused_ok else None,
            )

        # Reduce: smallest/largest row id wins; counts for the winning row
        # are summed across shards.  (The reference's reduce keeps one
        # arbitrary shard's count on id ties, executor.go MinRow reduceFn —
        # summing is deterministic and reflects the whole row.)
        out = Pair()
        for p in parts:
            if p.count == 0:
                continue
            if out.count == 0:
                out = Pair(id=p.id, count=p.count)
            elif p.id == out.id:
                out.count += p.count
            elif (p.id < out.id) if is_min else (p.id > out.id):
                out = Pair(id=p.id, count=p.count)
        return out

    # -------------------------------------------------------------- writes

    @staticmethod
    def _bool_row_id(f, call: Call, fname: str):
        """Rewrite true/false row literals to row ids 0/1 on bool fields
        (reference callArgTranslation, executor.go:2678)."""
        v = call.args.get(fname)
        if f.options.type == FieldType.BOOL and isinstance(v, bool):
            return int(v)
        if isinstance(v, bool) or not isinstance(v, int) or v < 0:
            return None
        return v

    def _replicate_to_shard_owners(self, idx, call: Call, shard: int, local_fn) -> bool:
        """Run a single-shard write on every owner replica synchronously
        (reference executeSetBitField, executor.go:2137-2168).

        Under the default ``[replication] write-policy = "all"`` a
        replica that cannot be reached fails the write — the reference
        offers the same all-owners guarantee, with anti-entropy as the
        backstop (this path is byte-identical to the pre-hint behavior,
        regression-pinned).  Under ``write-policy = "available"`` the
        write commits on the reachable owners and each missed delivery
        (breaker-open peer skipped without an RPC, transport error,
        shed-exhausted peer) lands in the per-peer hint queue
        (parallel/hints.py) for replay when the peer heals — at least
        one owner must still apply, or the write fails (no durable
        copy would exist anywhere).

        An owner REFUSING as non-owner means a resize just re-homed the
        shard and its view is fresher than ours: wait for the status
        broadcast, re-resolve the owner set, and retry the refused
        deliveries within the PILOSA_TPU_WRITE_RETRY_S budget."""
        from pilosa_tpu.parallel import hints as _hints

        available = (_hints.config().write_policy
                     == _hints.WRITE_POLICY_AVAILABLE)
        applied: set[str] = set()
        hinted: set[str] = set()
        changed = False

        def hint_for(n) -> None:
            # marked now, FLUSHED to the store only once the write has
            # committed on some owner — a write that fails outright
            # must not leave hints that would later replay it
            hinted.add(n.id)

        def delivery_pass() -> bool:
            nonlocal changed
            refused = False
            # mid-rebalance a shard has PENDING owners (backfill
            # targets, or demoted ex-owners after cutover) on top of
            # the serving set: they receive every write too
            # (dual-write), and under the default "hint" policy a
            # missed pending delivery is always hinted — the migration
            # must never make writes stricter than steady state.  With
            # no route override installed, pending is empty and this
            # loop is byte-identical to the legacy replica fan-out.
            route = self.cluster.shard_route(idx.name, shard)
            pending_ids = set(route[1]) if route is not None else set()
            dual_hint = True
            if pending_ids:
                from pilosa_tpu.parallel import rebalance as _rebalance
                dual_hint = (_rebalance.config().dual_write_policy
                             == _rebalance.DUAL_WRITE_HINT)
            for n in self.cluster.write_nodes(idx.name, shard):
                if n.id in applied or n.id in hinted:
                    continue
                pending = n.id in pending_ids
                lenient = available or (pending and dual_hint)
                if n.id == self.cluster.local_id:
                    changed |= local_fn()
                    applied.add(n.id)
                    if pending:
                        from pilosa_tpu.parallel import (
                            rebalance as _rebalance)
                        _rebalance.bump("rebalance.dual_writes")
                    continue
                if lenient and self.cluster.breaker_open(n.id):
                    # known-dead peer: hint without paying the RPC
                    # timeout (the breaker's half-open trial re-admits
                    # it; the replay worker drains the backlog)
                    hint_for(n)
                    continue
                try:
                    if _fi.armed:
                        # failpoint: the production replica write
                        # delivery (errors here fail the write like a
                        # dead owner — or hint it, under "available")
                        _fi.hit("replica.write")
                    res = self.cluster.transport.query_node(
                        n, idx.name, str(call), [shard]
                    )
                except Exception as e:  # noqa: BLE001 — the refusal
                    # contract is a STRING over HTTP (ClientError, not
                    # TransportError), a typed error in-process
                    if refusal_is_unowned(e):
                        refused = True
                        continue
                    if lenient and isinstance(e, ShedByPeerError):
                        # shed-exhausted: proof of life (never feeds
                        # the breaker), but the delivery did not land
                        self.cluster.note_peer_success(n.id)
                        hint_for(n)
                        continue
                    if isinstance(e, TransportError):
                        if lenient:
                            self.cluster.note_peer_failure(n.id)
                            hint_for(n)
                            continue
                        raise ExecutionError(
                            f"write replication to node {n.id} "
                            f"failed: {e}")
                    if pending and dual_hint:
                        # the joiner answers 4xx until it applies the
                        # begin broadcast's schema ("index not found")
                        # — a missed PENDING delivery hints, it never
                        # fails the write (the peer is alive: no
                        # breaker feedback)
                        hint_for(n)
                        continue
                    raise
                if available or pending:
                    self.cluster.note_peer_success(n.id)
                changed |= bool(res[0])
                applied.add(n.id)
                if pending:
                    from pilosa_tpu.parallel import (
                        rebalance as _rebalance)
                    _rebalance.bump("rebalance.dual_writes")
            return refused

        def on_timeout() -> None:
            raise ExecutionError(
                f"shard {shard} owners refused the write as "
                "non-owners and the membership view did not "
                "converge; retry")

        converge_owner_deliveries(delivery_pass, on_timeout)
        if available and not applied:
            raise ExecutionError(
                f"no owner of shard {shard} was reachable; the write "
                "has no durable copy (write-policy=available still "
                "requires one live owner)")
        if hinted:
            store = (getattr(self.node, "hints", None)
                     if self.node is not None else None)
            pql = str(call)
            for nid in sorted(hinted):
                if store is not None:
                    store.append(nid, idx.name, pql, shard)
                else:
                    _hints.bump("hint.dropped")
        return changed

    def _check_remote_shards_owned(self, idx, shards) -> None:
        """Receiver-side ownership gate for WHOLE remote sub-queries
        (reads included): refuse any shard this node does not own per
        its current view with the structured ErrClusterDoesNotOwnShard
        marker, so a stale-view origin fails over instead of reading
        an unmaintained ex-owner copy (satellite of the online
        rebalance: nothing refused stale read sub-queries before)."""
        if (self.cluster is None or self.cluster.transport is None
                or len(self.cluster.sorted_nodes()) < 2):
            return
        for s in shards:
            if not self.cluster.owns_shard(self.cluster.local_id,
                                           idx.name, int(s)):
                raise UnownedShardError(int(s))

    def _check_remote_write_owned(self, idx, shard: int,
                                  opt: ExecOptions | None) -> None:
        """Receiver-side ownership gate for replica write deliveries
        (Set/Clear with remote semantics): refuse a shard this node
        does not own per its CURRENT view instead of silently
        absorbing a stale-view origin's write onto an ex-owner
        (reference api.go ErrClusterDoesNotOwnShard; the import
        message types carry the same gate in node.receive_message)."""
        if opt is None or not opt.remote:
            return
        if (self.cluster is None or self.cluster.transport is None
                or len(self.cluster.sorted_nodes()) < 2):
            return
        if not self.cluster.owns_shard(self.cluster.local_id,
                                       idx.name, shard):
            raise UnownedShardError(shard)

    def _note_new_shard(self, idx, f, shard: int) -> None:
        """Record shard existence locally and broadcast it (reference
        CreateShardMessage, view.go:263-305)."""
        if shard in f.available_shards():
            return
        f._note_shard(shard)
        if self.node is not None:
            self.node.note_shard_created(idx.name, f.name, shard)

    def _parse_set(self, idx, call: Call):
        """Fully validate a Set before any state is touched, so a
        rejected Set leaves no phantom column or shard behind — locally
        or broadcast."""
        col = call.uint_arg("_col")
        if col is None:
            raise ExecutionError("Set() column argument required")
        fname = call.field_arg()
        f = self._field(idx, fname)
        if f.options.type == FieldType.INT:
            value = call.int_arg(fname)
            if value is None:
                raise ExecutionError("Set() row argument required")
            timestamp = None
        else:
            value = self._bool_row_id(f, call, fname)
            if value is None:
                raise ExecutionError("Set() row argument required")
            ts = call.args.get("_timestamp")
            timestamp = parse_time(ts) if ts is not None else None
            if timestamp is not None and f.options.type != FieldType.TIME:
                raise ExecutionError(f"field {fname!r} does not accept timestamps")
        return f, col, value, timestamp

    def _apply_set(self, idx, f, col: int, value, timestamp) -> bool:
        ef = idx.existence_field()
        if ef is not None:
            ef.set_bit(0, col)
        if f.options.type == FieldType.INT:
            return f.set_value(col, value)
        return f.set_bit(value, col, timestamp=timestamp)

    def _execute_set(self, idx, call: Call, opt: ExecOptions) -> bool:
        f, col, value, timestamp = self._parse_set(idx, call)
        if self._cluster_active(opt):
            shard = col // SHARD_WIDTH
            self._note_new_shard(idx, f, shard)
            ef = idx.existence_field()
            if ef is not None:
                self._note_new_shard(idx, ef, shard)
            return self._replicate_to_shard_owners(
                idx, call, shard,
                lambda: self._apply_set(idx, f, col, value, timestamp),
            )
        self._check_remote_write_owned(idx, col // SHARD_WIDTH, opt)
        return self._apply_set(idx, f, col, value, timestamp)

    def _execute_set_local(self, idx, call: Call) -> bool:
        f, col, value, timestamp = self._parse_set(idx, call)
        return self._apply_set(idx, f, col, value, timestamp)

    def _execute_clear(self, idx, call: Call, opt: ExecOptions) -> bool:
        col = call.uint_arg("_col")
        if col is None:
            raise ExecutionError("Clear() column argument required")
        if self._cluster_active(opt):
            return self._replicate_to_shard_owners(
                idx, call, col // SHARD_WIDTH,
                lambda: self._execute_clear_local(idx, call),
            )
        self._check_remote_write_owned(idx, col // SHARD_WIDTH, opt)
        return self._execute_clear_local(idx, call)

    def _execute_clear_local(self, idx, call: Call) -> bool:
        col = call.uint_arg("_col")
        fname = call.field_arg()
        f = self._field(idx, fname)
        if f.options.type == FieldType.INT:
            return f.clear_value(col)
        row_id = self._bool_row_id(f, call, fname)
        if row_id is None:
            raise ExecutionError("Clear() row argument required")
        return f.clear_bit(row_id, col)

    def _forward_to_all_nodes(self, idx, call: Call, changed: bool, shards=None) -> bool:
        """Forward a whole-index write to every other node (each applies
        it to its local fragments/stores); used by ClearRow/Store/attrs.
        `shards` carries the caller's shard restriction (None = all)."""
        for n in self.cluster.sorted_nodes():
            if n.id == self.cluster.local_id:
                continue
            try:
                res = self.cluster.transport.query_node(n, idx.name, str(call), shards)
            except TransportError as e:
                raise ExecutionError(f"write forwarding to node {n.id} failed: {e}")
            r = res[0]
            changed |= bool(r) if isinstance(r, bool) else False
        return changed

    def _execute_clear_row(self, idx, call: Call, shards, opt: ExecOptions) -> bool:
        fname = call.field_arg()
        f = self._field(idx, fname)
        if f.options.type not in (FieldType.SET, FieldType.TIME, FieldType.MUTEX, FieldType.BOOL):
            raise ExecutionError(f"ClearRow() is not supported on {f.options.type} fields")
        row_id = call.uint_arg(fname)
        if row_id is None:
            raise ExecutionError("ClearRow() row argument required")
        changed = False
        for view in list(f.views.values()):
            for frag in list(view.fragments.values()):
                changed |= frag.clear_row(row_id)
        # every node clears its own fragments (replicas included)
        if self._cluster_active(opt):
            changed = self._forward_to_all_nodes(idx, call, changed)
        return changed

    def _execute_store(self, idx, call: Call, shards, opt: ExecOptions) -> bool:
        if len(call.children) != 1:
            raise ExecutionError("Store() requires a single row query")
        fname = call.field_arg()
        f = self._field(idx, fname)
        row_id = call.uint_arg(fname)
        if row_id is None:
            raise ExecutionError("Store() row argument required")
        if self._cluster_active(opt):
            # each node stores the row segments for the shards it owns;
            # the child re-evaluates per node restricted to those shards.
            # The caller's shard restriction travels with the forward.
            target = self._target_shards(idx, shards, opt)
            changed = self._store_local(idx, call, f, row_id, target, opt)
            return self._forward_to_all_nodes(idx, call, changed, shards=target)
        return self._store_local(idx, call, f, row_id, shards, opt)

    def _store_local(self, idx, call: Call, f, row_id: int, shards, opt: ExecOptions) -> bool:
        target = self._target_shards(idx, shards, opt)
        if self.cluster is not None and self.cluster.transport is not None:
            # restrict to locally-owned shards; peers handle their own
            local = sorted(self.cluster.local_shards(idx.name, target))
            src = self._execute_bitmap_call(
                idx, call.children[0], local, replace(opt, remote=True, shards=local)
            )
        else:
            src = self._execute_bitmap_call(idx, call.children[0], target, opt)
        changed = False
        view = f.create_view_if_not_exists(VIEW_STANDARD)
        # Shards to touch: those with source bits, plus those where the
        # target row already has bits to clear.  Shards with neither are
        # skipped — no empty fragments or no-op WAL records.
        target_shards = set(src.segments)
        for shard, frag in view.fragments.items():
            if frag.row_count(row_id) > 0:
                target_shards.add(shard)
        for shard in sorted(target_shards):
            words = src.shard_segment(shard)
            if words is None:
                words = np.zeros(bm.n_words(SHARD_WIDTH), dtype=np.uint32)
            frag = view.create_fragment_if_not_exists(shard)
            if frag.set_row(row_id, words):
                changed = True
                if words.any():
                    f._note_shard(shard)
        return changed

    def _execute_set_row_attrs(self, idx, call: Call, opt: ExecOptions):
        fname = call.args.get("_field")
        if not fname:
            raise ExecutionError("SetRowAttrs() requires a field argument")
        f = self._field(idx, fname)
        row_id = call.uint_arg("_row")
        if row_id is None:
            raise ExecutionError("SetRowAttrs() row argument required")
        attrs = {k: v for k, v in call.args.items() if not k.startswith("_")}
        f.row_attrs.set_attrs(row_id, attrs)
        # attrs replicate to every node (reference stores them on all
        # nodes and reconciles with anti-entropy block diffs, attr.go:90)
        if self._cluster_active(opt):
            self._forward_to_all_nodes(idx, call, False)
        return None

    def _execute_set_column_attrs(self, idx, call: Call, opt: ExecOptions):
        col = call.uint_arg("_col")
        if col is None:
            raise ExecutionError("SetColumnAttrs() column argument required")
        attrs = {k: v for k, v in call.args.items() if not k.startswith("_")}
        idx.column_attrs.set_attrs(col, attrs)
        if self._cluster_active(opt):
            self._forward_to_all_nodes(idx, call, False)
        return None

    # ------------------------------------------------------------ options

    def _execute_options(self, idx, call: Call, shards, opt: ExecOptions):
        """Options(call, ...) wrapper (reference executeOptionsCall,
        executor.go:343)."""
        if len(call.children) != 1:
            raise ExecutionError("Options() requires a single child query")
        new_opt = replace(opt)
        for key, value in call.args.items():
            if key == "columnAttrs":
                new_opt.column_attrs = bool(value)
            elif key == "excludeRowAttrs":
                new_opt.exclude_row_attrs = bool(value)
            elif key == "excludeColumns":
                new_opt.exclude_columns = bool(value)
            elif key == "shards":
                if not isinstance(value, list):
                    raise ExecutionError("Options() shards must be a list")
                new_opt.shards = [int(v) for v in value]
            else:
                raise ExecutionError(f"unknown Options() argument: {key!r}")
        res = self._execute_call(idx, call.children[0], shards, new_opt)
        if isinstance(res, Row):
            # serialization directives ride the result so the wire layer
            # honors per-call Options() the same as URL params
            res.exclude_columns = new_opt.exclude_columns
            res.wants_column_attrs = new_opt.column_attrs
        return res

    # ----------------------------------------------------- key translation

    def _translate_call(self, idx, call: Call) -> Call:
        """Rewrite string keys to uint64 ids on a clone of the call tree
        (reference translateCalls, executor.go:2610).  Read-path misses
        become _Empty/_Noop sentinels; write paths create keys."""
        _prep.note_walk()
        call = call.clone()
        return self._translate_call_rec(idx, call)

    def _translate_col_key(self, idx, call: Call, create: bool) -> bool:
        """Translate a string _col argument in place.  Returns False when
        the key doesn't exist and wasn't created."""
        v = call.args.get("_col")
        if not isinstance(v, str):
            return True
        if not idx.options.keys:
            raise ExecutionError(
                f"index {idx.name!r} does not use string keys (option keys=true)"
            )
        id = self._translate_one(idx, None, v, create)
        if id is None:
            return False
        call.args["_col"] = id
        return True

    def _translate_one(self, idx, field: str | None, key: str, create: bool):
        """Key -> id; creation is single-writer via the coordinator when
        clustered (reference holder.go:690).  All routing decisions live
        in node.translate_keys_cluster — the local path here only covers
        a bare Executor with no cluster node (unit tests)."""
        node = getattr(self, "node", None)
        if node is not None:
            return node.translate_keys_cluster(idx.name, field, [key],
                                               create=create)[0]
        store = (idx.translate_store if field is None
                 else idx.field(field).translate_store)
        return store.translate_key(key, create=create)

    def _ids_to_keys(self, idx, field: str | None, ids) -> list[str | None]:
        """Id -> key for result translation; read-through via the
        cluster node when present (stale replicas tail the primary)."""
        node = getattr(self, "node", None)
        if node is not None:
            return node.translate_ids_cluster(idx.name, field, ids)
        store = (idx.translate_store if field is None
                 else idx.field(field).translate_store)
        return store.translate_ids(list(ids))

    def _translate_row_key(self, idx, call: Call, arg_key: str, create: bool) -> bool:
        """Translate a string row value held under args[arg_key], where
        arg_key names the field.  Returns False on a read-path miss."""
        v = call.args.get(arg_key)
        if not isinstance(v, str):
            return True
        id = self._translate_row_id(idx, arg_key, v, create)
        if id is None:
            return False
        call.args[arg_key] = id
        return True

    def _translate_row_id(self, idx, fname: str, key: str,
                          create: bool = False):
        """The id of a string row key on field ``fname``, or None when
        nobody wrote that key and a read asks (the caller's
        ``_Empty``)."""
        f = idx.field(fname)
        if f is None:
            raise ExecutionError(f"field not found: {fname}")
        if not f.options.keys:
            raise ExecutionError(
                f"field {fname!r} does not use string keys (option keys=true)"
            )
        return self._translate_one(idx, fname, key, create)

    def _translate_call_rec(self, idx, call: Call) -> Call:
        name = call.name
        if name == "Set":
            self._translate_col_key(idx, call, create=True)
            self._translate_row_key(idx, call, call.field_arg(), create=True)
            return call
        if name == "Clear":
            if not self._translate_col_key(idx, call, create=False):
                return Call(_NOOP_CALL)
            if not self._translate_row_key(idx, call, call.field_arg(), create=False):
                return Call(_NOOP_CALL)
            return call
        if name == "SetColumnAttrs":
            self._translate_col_key(idx, call, create=True)
            return call
        if name == "SetRowAttrs":
            fname = call.args.get("_field")
            v = call.args.get("_row")
            if isinstance(v, str) and fname:
                f = idx.field(fname)
                if f is None:
                    raise ExecutionError(f"field not found: {fname}")
                if not f.options.keys:
                    raise ExecutionError(f"field {fname!r} does not use string keys")
                call.args["_row"] = self._translate_one(
                    idx, fname, v, create=True)
            return call
        if name in ("Store", "ClearRow"):
            created = name == "Store"
            if not self._translate_row_key(idx, call, call.field_arg(), create=created):
                return Call(_NOOP_CALL)
            call.children = [self._translate_call_rec(idx, c) for c in call.children]
            return call
        if name == "Row" or name == "Range":
            if call.has_condition_arg():
                return call
            fname = next(
                (
                    k
                    for k in call.args
                    if not k.startswith("_") and k not in ("from", "to")
                ),
                None,
            )
            if fname is None:
                return call
            if not self._translate_row_key(idx, call, fname, create=False):
                return Call(_EMPTY_CALL)
            return call
        if name == "Rows":
            fname = call.args.get("_field") or call.args.get("field")
            prev = call.args.get("previous")
            if isinstance(prev, str) and fname:
                f = idx.field(fname)
                if f is None:
                    raise ExecutionError(f"field not found: {fname}")
                if not f.options.keys:
                    raise ExecutionError(f"field {fname!r} does not use string keys")
                id = self._translate_one(idx, fname, prev, create=False)
                if id is None:
                    raise ExecutionError(f"previous key not found: {prev!r}")
                call.args["previous"] = id
            col = call.args.get("column")
            if isinstance(col, str):
                if not idx.options.keys:
                    raise ExecutionError(
                        f"index {idx.name!r} does not use string keys"
                    )
                id = self._translate_one(idx, None, col, create=False)
                if id is None:
                    return Call(_EMPTY_ROWS_CALL)  # unknown column: no rows
                call.args["column"] = id
            return call
        # Pure structural calls: recurse into children and the GroupBy
        # filter argument.
        call.children = [self._translate_call_rec(idx, c) for c in call.children]
        filt = call.args.get("filter")
        if isinstance(filt, Call):
            call.args["filter"] = self._translate_call_rec(idx, filt)
        return call

    def _translate_result(self, idx, call: Call, res):
        """Translate ids back to keys in results (reference
        translateResults, executor.go:2781)."""
        if isinstance(res, Row):
            if idx.options.keys:
                keys = self._ids_to_keys(idx, None, res.columns())
                res.keys = [k or "" for k in keys]
            return res
        if isinstance(res, Pair) or (
            isinstance(res, list) and res and isinstance(res[0], Pair)
        ):
            fname = call.args.get("_field") or call.args.get("field")
            f = idx.field(fname) if fname else None
            if f is not None and f.options.keys:
                pairs = [res] if isinstance(res, Pair) else res
                keys = self._ids_to_keys(idx, f.name,
                                         [p.id for p in pairs])
                for p, k in zip(pairs, keys):
                    p.key = k or ""
            return res
        if call.name == "Rows" and isinstance(res, list):
            fname = call.args.get("_field") or call.args.get("field")
            f = idx.field(fname) if fname else None
            if f is not None and f.options.keys:
                return [k or ""
                        for k in self._ids_to_keys(idx, f.name, res)]
            return res
        if call.name == "GroupBy" and isinstance(res, list):
            # batch per field: one translation call (possibly one
            # read-through RPC) per keyed field, not one per group row
            by_field: dict[str, set[int]] = {}
            for gc in res:
                for fr in gc.group:
                    f = idx.field(fr.field)
                    if f is not None and f.options.keys:
                        by_field.setdefault(f.name, set()).add(fr.row_id)
            keymaps = {
                fname: dict(zip(sorted(ids),
                                self._ids_to_keys(idx, fname, sorted(ids))))
                for fname, ids in by_field.items()
            }
            for gc in res:
                for fr in gc.group:
                    if fr.field in keymaps:
                        fr.row_key = keymaps[fr.field].get(fr.row_id) or ""
            return res
        return res
