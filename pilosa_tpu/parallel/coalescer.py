"""Cross-query micro-batched dispatch: concurrent count-style queries
share one device launch.

The Count/Intersect hot path is bound by the host's fixed cost a launch,
not by the kernel (PERF.md section 5, PR 29: a lone dense read's kernel
is 0.09 ms of a 3.6 ms read on `seg-dense`), so queries that arrive
while a launch is in flight share the next one: the serving-side
batching lever TPU inference stacks pull (Ragged Paged Attention, arxiv
2604.15464) applied to our map-reduce-over-shards execution model
(DrJAX, arxiv 2403.07128; reference executor.go:2455 scatter-gather).

Mechanics
---------
Fused-eligible `Count(tree)` queries stage their operands on the calling
thread (`Executor._fused_expr`: canonical tree SHAPE + leaf stacks),
then meet in a bucket.  The first arrival becomes the bucket's LEADER.
A batch is worth a wait only while the device (or the host thread that
feeds it) is busy: queries that arrive during a launch could not start
anyway, so collecting them is free.  The coalescer therefore counts its
own launches in flight (``Coalescer.in_flight``), and the leader's wait
depends on that count, not on a clock:

- no launch in flight: the leader seals its bucket and flushes at once
  (``why = idle``) — a read that batches with nobody pays no window;
- one or more in flight: it waits until the first of: the bucket fills
  (``max_batch``, ``why = full``); the in-flight count reaches zero
  (``why = busy``: every waiting leader wakes and flushes, buckets of
  different keys concurrently); ``window_s`` runs out (``why = cap``).

``window_s`` is a cap on the wait behind a launch, never a floor.  The
leader runs ONE launch for the sealed bucket and scatters the per-query
count rows back to every waiter's future.  Launches that bypass the
coalescer (TopN, GroupBy, BSI, range) do not count as in flight.

Bucketing is two-tier:

- **Ragged (default)**: the query's tree compiles to an op-tape
  (ops/tape.py) and the bucket keys on the tape's SIZE CLASS (pow2
  tape length x pow2 leaf slots) plus the leaf stack shape — so
  STRUCTURALLY DIFFERENT trees share a window and a launch, the fix
  for mixed dashboard traffic that mostly missed the same-shape
  window and paid per-query dispatch.  At flush, a bucket whose live
  members all share one exact shape takes the same-shape fast path
  below (the specialized fused program, zero interpreter overhead);
  a heterogeneous bucket executes as one tape-interpreter launch.
- **Per-shape fallback**: with ``[ragged]`` disabled — or for a query
  whose tape exceeds the configured caps (``max-tape``/``max-leaves``)
  or carries a structurally ineligible node (Shift) — the bucket keys
  on ``(index, shape, shards)`` exactly as before, merging only
  identical-shape queries through the fused program.  The ragged
  engine can therefore be disabled in production with no behavior
  change (regression-pinned in tests/test_tape.py).

Same ops, same integer arithmetic on both paths — results are
bit-exact against the unbatched path; a batch of one takes the
identical single-query program (passthrough).

Enablement: OFF in host mode (single CPU device — dispatch is a Python
call there and batching buys nothing); ON by default when an
accelerator is attached.  The server knobs live under ``[coalescer]``
and ``[ragged]`` (docs/configuration.md).
"""

from __future__ import annotations

import contextlib
import threading
from concurrent.futures import Future

import numpy as np

from pilosa_tpu import observe as _observe
from pilosa_tpu import perfobs as _perfobs
from pilosa_tpu import stagecheck as _stagecheck
from pilosa_tpu import stats as _stats
from pilosa_tpu import tracing
from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.ops import containers as _containers
from pilosa_tpu.ops import expr
from pilosa_tpu.ops import tape as _tape
from pilosa_tpu.runtime import residency as _residency
from pilosa_tpu.serve.deadline import DeadlineExceededError


def resolve_enabled(mode) -> bool:
    """``auto`` (accelerator-only) | true | false — TOML booleans and
    env strings both accepted.  Anything else is a configuration error
    and raises: a typo like ``enabled = "ture"`` silently falling back
    to auto would invert the operator's explicit intent."""
    if isinstance(mode, bool):
        return mode
    s = str(mode).strip().lower()
    if s in ("1", "true", "yes", "on"):
        return True
    if s in ("0", "false", "no", "off"):
        return False
    if s != "auto":
        raise ValueError(
            f"coalescer.enabled must be auto/true/false, got {mode!r}")
    return not bm.host_mode()


#: what ended a leader's wait: ``idle`` (no launch in flight, flushed
#: at once), ``busy`` (waited out the launches in flight), ``full``
#: (``max_batch``), ``cap`` (``window_s`` ran out behind a launch)
FLUSH_WHY = ("idle", "busy", "full", "cap")


class _Bucket:
    __slots__ = ("items", "sealed", "why",
                 "n_final", "shapes_final", "tape_final", "vm_final",
                 "flush_t0", "launch_ns", "engine",
                 "flush_trace", "launch_span")

    def __init__(self):
        # _Entry per enqueued query
        self.items: list[_Entry] = []
        self.sealed = False
        # what ended the leader's wait: one of FLUSH_WHY, written under
        # the coalescer's lock where the bucket is sealed
        self.why = ""
        # flight-recorder breakdown, written by the leader BEFORE the
        # futures resolve (so every waiter may read them after
        # fut.result() without a lock): final batch occupancy, distinct
        # shape count, whether the tape interpreter ran, whether the
        # bitmap VM ran, flush start (perf_counter_ns), and
        # device-launch duration
        self.n_final = 0
        self.shapes_final = 0
        self.tape_final = False
        self.vm_final = False
        self.flush_t0 = 0
        self.launch_ns = 0
        # the canonical perfobs engine the flush ran — followers stamp
        # it onto their own flight records (the ops-layer sample only
        # sees the leader's thread)
        self.engine: str | None = None
        # the LEADER's trace id at flush: batchmates inherit the
        # batch's launch span — a follower's /debug/trace tree can
        # point at the trace that actually owns the shared launch
        self.flush_trace: str | None = None
        # [the leader record's traceID, the id of its ``launch``
        # span]: the ``link`` a follower's own launch span carries
        self.launch_span: list | None = None


class _Entry:
    """One staged query waiting in a bucket.  ``tape`` is None on the
    per-shape fallback path (ragged off / oversize / Shift); ``mesh``
    is the device mesh this query's launch must run under (None = the
    pre-mesh single-device programs — ?nomesh=1 / [mesh] off).  The
    bucket key carries the mesh identity, so queries on different
    placement flavors never share a launch.  ``vm`` is the query's
    compressed VM staging (ops/containers.VMStage) when the bitmap VM
    routes it — VM entries carry no dense leaf stacks at all."""

    __slots__ = ("shape", "leaves", "tape", "fut", "deadline", "mesh",
                 "vm")

    def __init__(self, shape, leaves, tape, fut, deadline, mesh=None,
                 vm=None):
        self.shape = shape
        self.leaves = leaves
        self.tape = tape
        self.fut = fut
        self.deadline = deadline
        self.mesh = mesh
        self.vm = vm


class Coalescer:
    """One per executor.  Thread-safe; queries block at most
    ``window_s`` beyond their own execution time, and only behind a
    launch in flight: ``window_s`` is a cap, never a floor."""

    def __init__(self, window_s: float = 0.002, max_batch: int = 32,
                 enabled="auto", stats=None, ragged: bool = True,
                 max_tape: int = _tape.DEFAULT_MAX_TAPE,
                 max_leaves: int = _tape.DEFAULT_MAX_LEAVES,
                 vm: bool = True,
                 vm_min_domain: int = _containers.VM_MIN_DOMAIN,
                 vm_max_prefetch: int = _containers.VM_MAX_PREFETCH):
        self.window_s = window_s
        self.max_batch = max_batch
        self.enabled = resolve_enabled(enabled)
        self.ragged = bool(ragged)
        self.max_tape = max_tape
        self.max_leaves = max_leaves
        # the Pallas bitmap VM ([vm] config): heterogeneous ragged
        # buckets whose every leaf stages compressed execute as ONE
        # scalar-prefetch kernel over the pooled containers — rides
        # the ragged engine, so [ragged] off disables it too
        self.vm = bool(vm)
        self.vm_min_domain = int(vm_min_domain)
        self.vm_max_prefetch = int(vm_max_prefetch)
        self.stats = stats if stats is not None else _stats.NOP
        from pilosa_tpu import lockcheck

        self._lock = lockcheck.lock("coalescer")
        self._pending: dict[tuple, _Bucket] = {}
        # launches in flight (in_flight scopes open) and the condition
        # a waiting leader sleeps on: notified when the count reaches
        # zero and when a follower fills a bucket.  Both, and the
        # per-``why`` flush totals (/debug/ragged), live under _lock.
        self.inflight = 0
        self._drains = 0  # times the in-flight count fell to zero
        self._wake = threading.Condition(self._lock)
        self.flushes = dict.fromkeys(FLUSH_WHY, 0)
        # (shape, n_leaves) -> (Tape|None, fallback-counter-name|None):
        # shapes are canonical/hashable and few, so compile each once
        # instead of re-walking the tree (and re-raising TapeError for
        # Shift shapes) on every staged query of the serving hot path.
        # Unlocked by design: a racing duplicate compile is wasted
        # work, never a wrong entry; cleared wholesale on overflow.
        self._tape_memo: dict[tuple, tuple] = {}

    # ------------------------------------------------------------- entry

    def eligible(self, opt) -> bool:
        """Gate consulted by the executor's fused Count path — the
        caller has already established fusion eligibility and
        single-node execution.  A query whose remaining deadline is
        within two batching windows bypasses the coalescer entirely:
        never hold a query past its budget just to share a launch."""
        if not (self.enabled and (opt is None or opt.coalesce)):
            return False
        dl = None if opt is None else getattr(opt, "deadline", None)
        return dl is None or dl.remaining() > 2 * self.window_s

    def _tape_for(self, shape, n_leaves):
        """Memoized compile: Tape within the caps, or None (with the
        per-QUERY fallback counter bumped — the memo dedupes the tree
        walk, never the accounting)."""
        mkey = (shape, n_leaves)
        hit = self._tape_memo.get(mkey)
        if hit is None:
            try:
                tp = _tape.compile_shape(shape, n_leaves,
                                         self.max_tape)
                reason = None
                if n_leaves > self.max_leaves:
                    tp, reason = None, "tape.oversize_fallbacks"
            except _tape.TapeError as e:
                tp = None
                reason = ("tape.oversize_fallbacks"
                          if "exceeds cap" in str(e)
                          else "tape.unsupported")
            if len(self._tape_memo) >= 4096:
                self._tape_memo.clear()
            self._tape_memo[mkey] = hit = (tp, reason)
        tp, reason = hit
        if reason is not None:
            _tape.bump(reason)
        return tp

    def _bucket_key(self, idx, shape, shards, leaves, mesh=None):
        """(key, tape) for one staged query.  Ragged: tape compiles
        within the caps -> key on the size class + leaf stack shape,
        so heterogeneous trees of similar size meet in one bucket
        (distinct indexes included — the launch is index-agnostic;
        each waiter folds its own result).  Fallback: the exact
        per-shape key, the pre-ragged behavior.  The mesh identity
        joins both keys: a ?nomesh=1 query must not share a launch
        with mesh-routed batchmates (different compiled programs)."""
        if self.ragged:
            tp = self._tape_for(shape, len(leaves))
            if tp is not None:
                tb, lb = _tape.size_class(len(tp.instrs), len(leaves))
                return (("ragged", tuple(leaves[0].shape), tb, lb,
                         mesh), tp)
        return (idx.name, shape, shards, mesh), None

    def count(self, executor, idx, tree, shards: tuple[int, ...],
              deadline=None, cache_fill=None,
              use_delta: bool = True, mesh=None,
              tenant: str | None = None,
              use_vm: bool = True) -> int:
        """One Count(tree) query through the batching window -> total.
        ``tree`` is the read's Prepared (parallel/prepared.py): nothing
        here walks the call tree again, and the read's counters and
        timings go to its books (``tree.books``, settled once by
        ``Executor.execute``; a caller that brought none is settled
        here).  Staging runs on the CALLER's thread (fragment locks,
        and a staging error belongs to this query alone).

        ``cache_fill`` is the executor's result-cache probe triple
        ``(cache, key, gens)`` for THIS query — the executor already
        probed (a hit never reaches the window), so a flushed batch
        fills the cache for every member: each waiter stores its own
        total under its own key, stamped with the generations captured
        before its leaves were staged.  Entries dropped from the batch
        (deadline death, flush failure) raise out of ``fut.result()``
        and never fill.

        ``tenant`` is the query's tenant id ([tenants] isolation):
        tenants SHARE launches by design — batching across tenants is
        the whole point of the window — but each member's cache fill
        below charges its own tenant's soft budget.

        ``use_delta=False`` is the ?nodelta=1 escape, forwarded to
        staging.  Bucket keys stay delta-aware for free: a pending
        ingest delta puts ``dfuse`` nodes in the canonical SHAPE —
        which the tape compiler lowers to two extra instructions, so a
        delta-carrying query lands in the size class its overlay
        actually costs — and a ?nodelta=1 query (which compacts up
        front and stages plain leaves) batches with a delta-reading
        one only when the programs are identical anyway."""
        books = tree.books
        own_books = books is None
        if own_books:
            books = _stats.Batch()
        vmstage = None
        offer = self.vm and self.ragged and use_vm
        if (offer and mesh is None
                and _containers.kept_dense(tree, shards)):
            # a leaf row is known to be kept dense (the verdict its
            # last staging left under the view's write token): the VM
            # offer is certain to be declined, so it is declined here,
            # before anything is staged for it, and counted as
            # stage_vm would have.  The leaves are staged once, below.
            _containers.bump("container.fallbacks")
            _tape.bump("vm.fallbacks.ineligible_leaf", also="vm.fallbacks")
        elif offer and mesh is None:
            # the bitmap VM: stage compressed (directories + local
            # gather rows, NO dense stacks) and key on the tape size
            # class alone — domain widths re-pad to the bucket max at
            # flush, so 16 structurally distinct sparse queries still
            # meet in ONE bucket and ONE kernel.  mesh is None only:
            # the VM is a single-device kernel; mesh-routed queries
            # keep the shard_map interpreter.  Any decline (dense/hot
            # leaf, ineligible tree, oversize) falls through to the
            # existing ragged/fused staging below, all-or-nothing.
            with _observe.span("stage", vm=True) as sp:
                fast0 = _stagecheck.fast_leaves()
                leaves0 = _stagecheck.leaves()
                vmstage = _containers.stage_vm(
                    tree, shards, use_delta=use_delta,
                    max_tape=self.max_tape, max_leaves=self.max_leaves,
                    min_domain=self.vm_min_domain,
                    max_prefetch=self.vm_max_prefetch)
                # the row leaves it got through (a decline stops at
                # the first dense one) and how many needed no walk
                sp.note(leaves=_stagecheck.leaves() - leaves0,
                        fast=_stagecheck.fast_leaves() - fast0)
            if vmstage is None:
                _tape.bump("vm.fallbacks")
        elif offer:
            # mesh-routed query: informational reason cell ONLY — the
            # shard_map interpreter is a route, not a degradation, so
            # the central vm.fallbacks total stays untouched
            _tape.bump("vm.fallbacks.mesh_active")
        if vmstage is not None:
            tb, lb = _tape.size_class(len(vmstage.tape.instrs),
                                      len(vmstage.leaves))
            key = ("vm", tb, lb)
            entry = _Entry(vmstage.shape, (), vmstage.tape, Future(),
                           deadline, mesh=None, vm=vmstage)
        else:
            # ``route``: what this thread did since the cache's probe:
            # eligible, which mesh, the VM's offer and its decline
            shape, leaves = executor._fused_expr(idx, tree, shards,
                                                 use_delta=use_delta,
                                                 before="route")
            key, tp = self._bucket_key(idx, shape, shards, leaves,
                                       mesh=mesh)
            entry = _Entry(shape, leaves, tp, Future(), deadline,
                           mesh=mesh)
        t0 = _observe.clock_ns()
        with self._lock:
            bucket = self._pending.get(key)
            leader = bucket is None
            if leader:
                bucket = _Bucket()
                self._pending[key] = bucket
            bucket.items.append(entry)
            if len(bucket.items) >= self.max_batch:
                self._seal_locked(key, bucket, "full")
                self._wake.notify_all()
        if leader:
            # the wait, as the leader sits through it: none unless a
            # launch is in flight, then until the first of a full
            # bucket, no launch left in flight, the window's cap.  The
            # span is written even when it is zero long; a follower's
            # coalesce.wait is written below from the bucket's times.
            with _observe.span("coalesce.wait", start_ns=t0) as wait:
                with self._lock:
                    if not bucket.sealed:
                        why = "idle"
                        if self.inflight:
                            # a launch that starts between the drain
                            # and this thread's wake-up must not send
                            # it back to sleep: wait for the drain
                            # itself, not for a count of zero
                            drains = self._drains
                            self._wake.wait_for(
                                lambda: (bucket.sealed
                                         or self._drains != drains),
                                self.window_s)
                            why = ("busy" if self._drains != drains
                                   else "cap")
                        if not bucket.sealed:
                            self._seal_locked(key, bucket, why)
                wait.note(why=bucket.why)
            self._flush(bucket, books)
        counts = entry.fut.result()
        launch_end = bucket.flush_t0 + bucket.launch_ns
        rec = _observe.current()
        if rec is not None and not leader:
            # a follower never dispatched: its launch span is the
            # LEADER's, by its times, linked to the span that owns it
            rec.add_span("coalesce.wait", t0, bucket.flush_t0,
                         why=bucket.why)
            rec.add_span("launch", bucket.flush_t0, launch_end,
                         batch=bucket.n_final,
                         shapes=bucket.shapes_final,
                         engine=bucket.engine,
                         link=bucket.launch_span)
        if rec is not None:
            # bucket fields are final once fut resolved (leader writes
            # them before scattering results).  The batch's shared
            # launch ticks the LEADER's deviceLaunches only (the hook
            # is thread-local and honest — a follower never dispatched
            # anything); followers carry the launch evidence here, in
            # the batch context, with ``leader`` saying which record
            # owns the tick.
            rec.note_path("coalesced")
            if bucket.engine is not None:
                rec.note_engine(bucket.engine)
            rec.coalesce = {
                "batch": bucket.n_final,
                "shapes": bucket.shapes_final,
                "tape": bucket.tape_final,
                "vm": bucket.vm_final,
                "queue_wait_ns": max(0, bucket.flush_t0 - t0),
                "launch_ns": bucket.launch_ns,
                "leader": leader,
                "why": bucket.why,
            }
            if bucket.flush_trace and not leader:
                # a follower's record names the batch leader's trace —
                # the span that owns the shared device launch
                rec.coalesce["launch_trace"] = bucket.flush_trace
        # the dense engine's counts came home inside the launch's one
        # wait (expr.evaluate): host values, and this a sum of them.  A
        # tape or VM batch hands each member its own device row
        arr = (np.asarray(counts, dtype=np.int64) if bucket.tape_final
               else expr.counts_to_host(counts))
        if entry.vm is not None:
            # VM results are per-domain-slot counts over the bucket's
            # padded domain — pad slots gather the megapool zero row
            # and contribute 0, and the domain already concatenated
            # the per-shard walks, so the total sums ALL slots (there
            # is no shard-row alignment to trim)
            total = int(arr.sum())
        else:
            # leaf stacks are padded to the device multiple — sum only
            # the live shard rows, in Python ints (int32 could wrap)
            total = int(arr[:len(shards)].sum())
        end = _observe.clock_ns()
        books.timing(self.stats, "coalescer.query_ns", end - t0)
        if rec is not None:
            # the host tail, from the end of the shared launch: the
            # leader's scatter to the batch, this member's wake-up and
            # its own sum over shards
            rec.add_span("reduce", launch_end, end)
        if cache_fill is not None:
            with _observe.span("cache.fill"):
                rc, key, gens = cache_fill
                rc.put(key, gens, total, 32, tenant=tenant)
        if own_books:
            books.settle()
        return total

    # ------------------------------------------------------------- flush

    def _seal_locked(self, key, bucket: _Bucket, why: str) -> None:
        """Close the bucket to appends (caller holds ``_lock``)."""
        bucket.sealed = True
        bucket.why = why
        del self._pending[key]
        self.flushes[why] += 1

    @contextlib.contextmanager
    def in_flight(self):
        """One launch in flight for as long as the scope is open
        (``_flush`` holds it around the batch's ``launch`` span).  A
        leader that arrives meanwhile collects followers instead of
        launching; when the last scope closes every waiting leader
        wakes and flushes."""
        with self._lock:
            self.inflight += 1
        try:
            yield
        finally:
            with self._lock:
                self.inflight -= 1
                if not self.inflight:
                    self._drains += 1
                    self._wake.notify_all()

    def _flush(self, bucket: _Bucket, books) -> None:
        """Leader-side: ONE launch for the sealed bucket, results
        scattered to every waiter; the flush's counters go to the
        leader's ``books``.  Appends are impossible once sealed
        (sealing happens under the same lock that guards appends).
        EVERYTHING here runs inside the try: any failure — including
        stats/tracing backends — must resolve every waiter's future,
        or followers would block forever."""
        # deadline-aware launch: entries whose budget died while the
        # window was open are dropped from the batch BEFORE launch —
        # their futures resolve to DeadlineExceededError, and their
        # batchmates' results are unaffected (the stack simply omits
        # the expired rows)
        live: list[_Entry] = []
        expired: list[_Entry] = []
        for it in bucket.items:
            dl = it.deadline
            (expired if dl is not None and dl.expired()
             else live).append(it)
        for it in expired:
            it.fut.set_exception(DeadlineExceededError(
                "deadline expired in the coalescer window"))
        n = len(live)
        bucket.n_final = n
        shape_groups: dict = {}
        for it in live:
            shape_groups[it.shape] = shape_groups.get(it.shape, 0) + 1
        bucket.shapes_final = len(shape_groups)
        bucket.flush_t0 = _observe.clock_ns()
        if expired:
            try:
                books.count(self.stats, "coalescer.deadline_dropped",
                            len(expired))
            except Exception:  # noqa: BLE001 — telemetry must never
                pass  # strand the live waiters below
        if n == 0:
            return
        try:
            # heterogeneity accounting (the before/after evidence for
            # the ragged engine): a query whose flushed batch held no
            # same-shape partner is a shape MISS — with ragged off it
            # flushed alone; with ragged on it still shared the launch,
            # and the counter measures how much structural diversity
            # the traffic carries either way
            misses = sum(1 for c in shape_groups.values() if c == 1)
            if misses:
                # cumulative module counter, exposed as a gauge at
                # scrape time (tape.publish_gauges) — never ALSO
                # pushed as a count, which would double-count (the
                # ingest.*/cache.* family rule)
                _tape.bump("coalescer.shape_misses", misses)
            if bucket.shapes_final > 1:
                _tape.bump("coalescer.shape_flushes")
            stats = self.stats
            books.count(stats, "coalescer.dispatches", 1)
            books.count(stats, "coalescer.flush_" + bucket.why, 1)
            books.histogram(stats, "coalescer.batch_occupancy", n)
            books.histogram(stats, "coalescer.shape_distinct",
                            bucket.shapes_final)
            # the batch's ONE launch span, on the leader's record (and,
            # under a recording tracer, the exported coalescer.flush
            # span); it starts where the wait ended.  The launch is in
            # flight until its results are on the host, and no longer
            # by the time the futures resolve.
            with self.in_flight(), _observe.span(
                    "launch", start_ns=bucket.flush_t0,
                    timer=(books.timer(stats), "coalescer.launch_ns"),
                    export="coalescer.flush", batch=n,
                    shapes=bucket.shapes_final) as span:
                bucket.flush_trace = tracing.active_trace_id()
                lead = _observe.current()
                if lead is not None:
                    bucket.launch_span = [lead.trace_id, span.id]
                # the batch's workload signature for the engine
                # observatory: dense-equivalent uint32 words (the
                # size-class key every engine's cost-table cell shares)
                # and bytes-touched / dense-equivalent sparsity — the
                # perfobs.context scope threads both to the ops-layer
                # launch sample
                # (an entry's leaf stacks share one shape)
                sig_work = sum(
                    len(it.leaves) * int(it.leaves[0].size)
                    for it in live if it.leaves)
                sig_sparsity = 1.0
                if live[0].vm is not None:
                    # bitmap-VM bucket (every entry staged compressed
                    # — the key's "vm" leader guarantees it): the
                    # distinct leaves concatenate into ONE megapool,
                    # each entry's local gather rows globalize against
                    # it (re-padded to the bucket-wide domain width
                    # with the canonical zero row), and the whole
                    # heterogeneous batch executes as ONE
                    # scalar-prefetch kernel that never materializes a
                    # dense register file (ops/tape.execute_vm ->
                    # ops/pallas_kernels.vm_counts)
                    bucket.tape_final = True
                    bucket.vm_final = True
                    tb, lb = _tape.size_class(
                        max(len(it.tape.instrs) for it in live),
                        max(len(it.vm.leaves) for it in live))
                    D = max(it.vm.pad for it in live)
                    pool, bases, zero = _containers.megapool(
                        [lf for it in live for lf in it.vm.leaves])
                    vbatch = []
                    for it in live:
                        rows = []
                        for lf, ix in zip(it.vm.leaves, it.vm.idxs):
                            g = np.full(D, zero, dtype=np.int32)
                            if isinstance(ix, tuple):
                                # kind-split staging: combine the
                                # per-kind rows into the bundle's
                                # virtual dense row space ([0, Rb)
                                # bitmap, then arrays, then runs —
                                # containers.MegaPools); kv 0/1 both
                                # route through the bitmap base (an
                                # absent lane's ib is the leaf's zero
                                # row)
                                kv, ib, ia, ir = ix
                                bb, ab, rb = bases[lf.uid]
                                g[:len(ib)] = np.where(
                                    kv == 2, ab + ia,
                                    np.where(kv == 3, rb + ir,
                                             bb + ib)).astype(np.int32)
                            else:
                                base = bases[lf.uid]
                                if isinstance(base, tuple):
                                    base = base[0]  # legacy leaf in a
                                    # kinds megapool: bitmap rows only
                                g[:len(ix)] = base + ix
                            rows.append(g)
                        vbatch.append((it.tape, rows))
                    # domain slots holding a real container vs the
                    # padded directory capacity: the data sparsity the
                    # compressed engine exploits
                    cap = sum(len(it.vm.leaves) for it in live) * D
                    real = sum(len(ix[1] if isinstance(ix, tuple)
                                   else ix)
                               for it in live for ix in it.vm.idxs)
                    sig_work = cap * int(pool.shape[-1])
                    sig_sparsity = real / cap if cap else 1.0
                    bucket.engine = "vm"
                    with _perfobs.context(sparsity=sig_sparsity,
                                          work=sig_work):
                        results = _residency.run_with_oom_retry(
                            lambda: _tape.execute_vm(
                                vbatch, pool, zero, tape_len=tb,
                                slots=lb,
                                max_prefetch=self.vm_max_prefetch))
                elif n == 1:
                    # single-query passthrough: the identical program
                    # the un-coalesced path would run
                    bucket.engine = ("mesh" if live[0].mesh is not None
                                     else "dense")
                    with _perfobs.context(work=sig_work):
                        results = _residency.run_with_oom_retry(
                            lambda: [expr.evaluate(live[0].shape,
                                                   live[0].leaves,
                                                   counts=True,
                                                   mesh=live[0].mesh)])
                elif bucket.shapes_final == 1:
                    # same-shape fast path: the specialized fused
                    # program over stacked operands, exactly the
                    # pre-ragged engine (and what a ragged bucket that
                    # happened to fill homogeneously should run — the
                    # interpreter buys nothing over a specialized
                    # program)
                    shape = live[0].shape
                    # device batches pad to the next power of two: the
                    # jitted program re-lowers per INPUT shape, so
                    # free-running occupancies (2, 3, 5, ...) each pay
                    # a fresh XLA compile in the serving path — under
                    # sustained ingest the misses arrive at arbitrary
                    # batch sizes and the compiles convoy every other
                    # query in the process.  Bucketing holds the
                    # variant count at log2(max_batch); the zero pad
                    # rows count to zero and are never scattered back.
                    # Host stacks skip it (the host engine never jits).
                    pad = _pow2(n) - n
                    with _observe.span("launch.stack", batch=n,
                                       padded=n + pad):
                        stacked = tuple(
                            _stack([it.leaves[j] for it in live])
                            for j in range(len(live[0].leaves)))
                        if pad and not isinstance(stacked[0],
                                                  np.ndarray):
                            stacked = tuple(_pad_batch(s, pad)
                                            for s in stacked)
                    bucket.engine = ("mesh" if live[0].mesh is not None
                                     else "dense")
                    with _perfobs.context(work=sig_work):
                        counts = expr.counts_to_host(
                            _residency.run_with_oom_retry(
                                lambda: expr.evaluate(
                                    shape, stacked, counts=True,
                                    mesh=live[0].mesh,
                                    # live occupancy, not the pow2-
                                    # padded batch rows, feeds the
                                    # mesh.queries counter
                                    mesh_queries=n)))
                    results = [counts[b] for b in range(n)]
                else:
                    # heterogeneous bucket: the whole ragged batch as
                    # ONE tape-interpreter launch (ops/tape.py); the
                    # bucket key guarantees every member's tape fits
                    # the (tape_len, slots) size class and every leaf
                    # stack shares one shape
                    bucket.tape_final = True
                    tb, lb = _tape.size_class(
                        max(len(it.tape.instrs) for it in live),
                        max(it.tape.n_leaves for it in live))
                    bucket.engine = ("mesh" if live[0].mesh is not None
                                     else "tape")
                    with _perfobs.context(work=sig_work):
                        results = _residency.run_with_oom_retry(
                            lambda: _tape.execute(
                                [(it.tape, it.leaves) for it in live],
                                counts=True, tape_len=tb, slots=lb,
                                mesh=live[0].mesh))
                span.note(engine=bucket.engine)
            bucket.launch_ns = span.end_ns - bucket.flush_t0
        except BaseException as e:  # noqa: BLE001 — every waiter fails
            for it in live:
                it.fut.set_exception(e)
            return
        for it, row in zip(live, results):
            it.fut.set_result(row)


def _stack(arrs: list):
    """Stack one leaf slot across the batch -> [B, S, W].  numpy for
    host stacks; jnp on device (one gather launch per leaf slot,
    amortized over the B queries it serves)."""
    if all(isinstance(a, np.ndarray) for a in arrs):
        return np.stack(arrs)
    import jax.numpy as jnp

    return jnp.stack(arrs)


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return b


def _pad_batch(stack, pad: int):
    """Append ``pad`` zero rows along the batch dim (device stacks)."""
    import jax.numpy as jnp

    return jnp.concatenate(
        [stack, jnp.zeros((pad,) + stack.shape[1:], stack.dtype)])
