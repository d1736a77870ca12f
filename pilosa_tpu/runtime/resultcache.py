"""Generation-stamped query result cache: repeated reads skip the
device entirely.

The Count/Intersect hot path is bound by the host's fixed cost a
launch, not by HBM (PERF.md section 5, PR 29: on `seg-dense` a cached
read's root is 0.70 ms against 2.49 ms for a lone dense launch whose
kernel is 0.09 ms) — so for read-heavy traffic the biggest remaining
win is to not launch at all.  The reference ships
only the per-fragment rank cache (cache.go:136, ported as
models/cache.py with exact generation-stamped counts); this module
generalizes the same idiom to whole PQL subtrees, the classic
recomputation-vs-retained-state trade of the Roaring line of work
(Chambi et al.; Lemire et al., "Roaring Bitmaps: Implementation of an
Optimized Software Library").

One process-wide, memory-budgeted LRU cache maps a canonical query key
— (holder identity, index, root kind, fused expression shape with leaf
identities ``(field, view, row)`` substituted at the slots, shard set)
— to its result, stamped with the participating fragments' generation
state: per (field, view) an aggregate ``(count, sum_gen, sum_uid,
max_uid)`` over the shard set (change-detecting under the monotone
uid/gen discipline — see ``Executor._rc_view_stamp``).
**Invalidation is free**: every mutation path bumps the fragment
generation (import, import-value, import-roaring, Set/Clear, Store,
ClearRow, BSI set/clear-value — audited by tests/test_resultcache.py),
so a stale entry simply misses, exactly like ``TopNCache.get(gen)``
today.  The uid components make a fragment replaced by resize/restore
(a NEW object whose ``_gen`` can collide) unhittable.

Stamp-before-read discipline (the correctness core): callers capture
the generation tuple BEFORE reading any fragment data, and fill with
that same stamp.  A mutation that lands between capture and read
leaves the entry stamped with the OLD generations while the live
fragments carry new ones — the entry can never be served, only
refilled.  The reverse order (stamp after read) would serve stale data
and is therefore forbidden.

Results live on host: Count totals and per-shard count tuples are a
few machine words, TopN/GroupBy results small dicts, Row results numpy
word-array copies accounted against this cache's own byte budget
(separate from the device ResidencyManager budget — an evicted result
recomputes from the still-resident device stacks, so eviction here
costs one dispatch, not a transfer).

Single-flight fills (the streaming-ingest round): under sustained
ingest every delta write invalidates its key, and all concurrently
arriving readers miss TOGETHER — without coordination each one
re-executes the identical query, multiplying device work by the
convoy depth exactly when the system is busiest (the classic cache
stampede).  ``get`` therefore registers the FIRST misser of a
``(key, stamp)`` as the flight leader; same-stamp missers arriving
while the flight is open wait (bounded by ``FLIGHT_WAIT_S`` and the
flight's age) for the leader's ``put`` and then serve the fill as a
hit.  A leader that dies never wedges followers: the wait is bounded,
an expired flight (``FLIGHT_TTL_S``) is replaced by the next misser,
and a waiter whose wait runs out simply computes — the fallback is
the uncoordinated behavior, never an error.  A stamp moved by a newer
write never joins an older flight (and vice versa): mismatched stamps
compute independently, so single-flight can not serve stale data.

Per-tenant soft budgets (the [tenants] round, serve/tenant.py): with
isolation enabled every entry is charged to the tenant that filled it
(the executor's thread-local tenant scope), each tenant's soft budget
is its ``cache_share`` of the global budget, and the eviction loop
prefers the oldest entry OF AN OVER-BUDGET TENANT before touching the
global LRU order — so one tenant churning distinct keys evicts its own
entries, never the fleet's warm head.  Budgets are soft (a tenant may
transiently exceed its share when the cache has global headroom); the
global budget stays strict.  With [tenants] off the tenant structures
are never consulted — byte-identical behavior, regression-pinned.

Surface: ``[cache]`` config (budget bytes, max entry bytes, ttl,
enabled), ``?nocache=1`` on the query route (symmetric with
``?nocoalesce``), ``cached``/``cacheKey`` on every flight record,
``cache.{hits,reordered,misses,fills,evictions,invalidations,bytes,
flight_joins,flight_served}`` gauge families on /metrics, per-tenant
bytes/hit-rates on ``GET /debug/tenants``, and
``GET /debug/resultcache``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import threading
import time
from typing import Any

from pilosa_tpu.serve import tenant as _tenant


#: Defaults; the server assembly reconfigures from [cache] config.
DEFAULT_BUDGET_BYTES = 128 << 20
DEFAULT_MAX_ENTRY_BYTES = 8 << 20

#: Accounting floor per entry: key tuple + stamp tuple + dict slot.
#: Prevents a flood of "free" scalar entries from reading as zero
#: bytes while really holding megabytes of Python structure.
ENTRY_OVERHEAD_BYTES = 256

#: How long a same-stamp misser waits for an open flight's fill before
#: giving up and computing itself.  Fills normally land in
#: milliseconds; the cap only matters when the leader is wedged.
FLIGHT_WAIT_S = 1.0

#: A flight older than this is presumed dead (leader errored without
#: filling) and is replaced by the next misser.
FLIGHT_TTL_S = 5.0


class Key:
    """Hash-once wrapper for cache keys.  A key is a deep nested tuple
    whose tail is the full shard tuple (256+ ints at production shard
    counts), and tuples do not cache their hash — the probe's
    get / pop / insert sequence would rehash it three times.  Wrapping
    computes it once; equality (only reached when hashes already
    match) delegates to the C tuple compare.

    Executor keys fold in the mesh placement token
    (``meshexec.placement_token``) so a count computed under one
    device placement never answers a probe made under another — a
    mesh reshape (or mesh on/off flip) naturally misses instead of
    serving a stale single-device result."""

    __slots__ = ("k", "h")

    def __init__(self, k: Any) -> None:
        self.k = k
        self.h = hash(k)

    def __hash__(self) -> int:
        return self.h

    def __eq__(self, other: object) -> bool:
        if self is other:
            return True
        if isinstance(other, Key):
            return self.k == other.k
        return NotImplemented

    def __repr__(self) -> str:  # key_digest / debug stability
        return repr(self.k)

    def digest(self) -> str:
        """``key_digest`` of this key, for the flight record that
        carries it: drawn when a record is first rendered, not when
        the cache is probed."""
        return key_digest(self)


class _Entry:
    __slots__ = ("gens", "value", "nbytes", "t", "hits", "tenant")

    def __init__(self, gens: Any, value: object, nbytes: int,
                 tenant: str | None = None) -> None:
        self.gens = gens
        self.value = value
        self.nbytes = nbytes
        self.t = time.monotonic()
        self.hits = 0
        self.tenant = tenant


class _Flight:
    """One in-progress fill: the leader computes, same-stamp missers
    wait on the event.  ``put`` (any outcome, including an oversize
    refusal) resolves it.  ``tid`` identifies the leader — a thread
    never waits on its own flight (a leader re-probing before its
    fill, e.g. a retried miss, must compute, not self-deadlock)."""

    __slots__ = ("gens", "t0", "event", "tid")

    def __init__(self, gens: Any) -> None:
        self.gens = gens
        self.t0 = time.monotonic()
        self.event = threading.Event()
        self.tid = threading.get_ident()


class ResultCache:
    """Memory-budgeted LRU of generation-stamped query results.

    ``get(key, gens)`` hits only when the stored stamp equals the
    caller's freshly-computed generation tuple; a mismatched entry is
    dropped on the spot (counted as an invalidation) so mutated keys
    free their bytes immediately instead of waiting for LRU churn.
    ``put`` enforces the byte budget strictly — the cache NEVER holds
    more than ``budget`` bytes, even transiently after the insert
    (acceptance: the churn test mirrors test_residency's tiny-budget
    pattern)."""

    def __init__(self, budget_bytes: int = DEFAULT_BUDGET_BYTES,
                 max_entry_bytes: int = DEFAULT_MAX_ENTRY_BYTES,
                 ttl_s: float = 0.0, enabled: bool = True) -> None:
        self.budget = int(budget_bytes)
        self.max_entry_bytes = int(max_entry_bytes)
        self.ttl_s = float(ttl_s)
        self.enabled = bool(enabled)
        from pilosa_tpu import lockcheck

        self._lock = lockcheck.lock("resultcache")
        # insertion order == LRU order (move-to-end on hit)
        self._entries: dict[Any, _Entry] = {}
        #: key -> _Flight: fills in progress (single-flight gate)
        self._flights: dict[Any, _Flight] = {}
        #: keys whose last fill was refused as oversize — such a key
        #: can never serve a flight's waiters, so followers must not
        #: queue behind a leader whose put is doomed (bounded FIFO;
        #: a later successful fill readmits the key)
        self._noflight: dict[Any, None] = {}
        self.bytes = 0
        self.hits = 0
        self.misses = 0
        self.fills = 0
        self.evictions = 0
        self.invalidations = 0
        self.skipped_oversize = 0
        self.flight_joins = 0
        self.flight_served = 0
        #: probes whose tree was written in another operand order than
        #: its key's (parallel/prepared.py)
        self.reordered = 0
        # ---------------- per-tenant accounting ([tenants]) --------
        # tenant -> live bytes; tenant -> ordered key set (per-tenant
        # LRU, mirroring the global order's move-to-end); tenant ->
        # [hits, misses, fills, evictions].  Touched only while a
        # tenant id is attributable (isolation on) — the anonymous
        # path never pays the dict ops.
        self._tenant_bytes: dict[str, int] = {}
        self._tenant_lru: dict[str, dict] = {}
        self._tenant_counters: dict[str, list] = {}
        self.tenant_pref_evictions = 0  # over-budget-tenant victims

    # ------------------------------------------------- tenant helpers

    @staticmethod
    def _caller_tenant(tenant: str | None) -> str | None:
        """The tenant this access charges against: an explicit id, or
        the executor's thread-local scope — None (no accounting at
        all) while [tenants] isolation is off."""
        if not _tenant.enabled():
            # no accounting at all while isolation is off — returning
            # an explicit label here would mint per-label dict keys
            # from unauthenticated traffic on the DEFAULT config
            return None
        # explicit ids (coalescer fills) pass the individuation bound
        # too, so rotated labels collapse consistently
        return _tenant.resolve(tenant if tenant is not None
                               else _tenant.current())

    def _tc_locked(self, t: str) -> list:
        c = self._tenant_counters.get(t)
        if c is None:
            c = self._tenant_counters[t] = [0, 0, 0, 0]
        return c

    def _tenant_track_locked(self, key: Any, e: _Entry) -> None:
        if e.tenant is None:
            return
        self._tenant_bytes[e.tenant] = \
            self._tenant_bytes.get(e.tenant, 0) + e.nbytes
        self._tenant_lru.setdefault(e.tenant, {})[key] = None

    def _tenant_untrack_locked(self, key: Any, e: _Entry) -> None:
        if e.tenant is None:
            return
        self._tenant_bytes[e.tenant] = \
            self._tenant_bytes.get(e.tenant, 0) - e.nbytes
        lru = self._tenant_lru.get(e.tenant)
        if lru is not None:
            lru.pop(key, None)

    def _tenant_touch_locked(self, key: Any, e: _Entry) -> None:
        if e.tenant is None:
            return
        lru = self._tenant_lru.get(e.tenant)
        if lru is not None and key in lru:
            lru[key] = lru.pop(key)

    def _victim_key_locked(self, protect: Any) -> Any:
        """The next eviction victim: the oldest entry of any tenant
        over its soft budget (its churn evicts ITS OWN entries first —
        the isolation contract), else the global LRU head.  Never the
        entry being inserted (``protect``)."""
        pol = _tenant.policy()
        if pol is not None and self._tenant_bytes:
            for t, b in self._tenant_bytes.items():
                if b <= int(self.budget * pol.quota_for(t).cache_share):
                    continue
                for k in self._tenant_lru.get(t, ()):
                    if k != protect:
                        self.tenant_pref_evictions += 1
                        return k
        for k in self._entries:
            if k != protect:
                return k
        return None

    # -------------------------------------------------------- access

    def get(self, key: Any, gens: Any,
            wait_s: float = FLIGHT_WAIT_S,
            tenant: str | None = None,
            reordered: bool = False) -> tuple[bool, object]:
        """(hit, value).  ``gens`` is the CURRENT generation tuple the
        caller just computed from the live fragments; a stored stamp
        that differs means some participating fragment mutated (or was
        replaced) since the fill — the entry is dropped and the call
        counts as a miss.

        Single-flight: a miss with no open same-stamp flight registers
        one (the caller is the leader and is expected to ``put``); a
        miss while a same-stamp fill is already in progress waits up
        to ``wait_s`` for it and serves the fill as a hit.  Pass
        ``wait_s=0`` to never wait (pure probe)."""
        if not self.enabled:
            return False, None
        t = self._caller_tenant(tenant)
        budget = wait_s
        while True:
            with self._lock:
                if reordered:
                    # the key put this tree's operands in another order
                    # than the query wrote them (``cache.reordered``)
                    self.reordered += 1
                    reordered = False
                e = self._entries.get(key)
                if e is not None:
                    if e.gens == gens and not (
                            self.ttl_s > 0
                            and time.monotonic() - e.t > self.ttl_s):
                        self._entries[key] = self._entries.pop(key)
                        self._tenant_touch_locked(key, e)
                        e.hits += 1
                        self.hits += 1
                        if t is not None:
                            self._tc_locked(t)[0] += 1
                        return True, e.value
                    del self._entries[key]
                    self.bytes -= e.nbytes
                    self._tenant_untrack_locked(key, e)
                    self.invalidations += 1
                if key in self._noflight:
                    # last fill for this key was refused (oversize):
                    # waiting could never turn into a hit
                    self.misses += 1
                    if t is not None:
                        self._tc_locked(t)[1] += 1
                    return False, None
                fl = self._flights.get(key)
                now = time.monotonic()
                if (fl is None or fl.gens != gens
                        or fl.tid == threading.get_ident()
                        or now - fl.t0 > FLIGHT_TTL_S):
                    # no joinable fill: this caller computes.  A
                    # mismatched-stamp flight is left to its own
                    # waiters (its fill will simply never match ours);
                    # an expired one is presumed dead and replaced;
                    # our own open flight means WE are the leader.
                    if fl is None or now - fl.t0 > FLIGHT_TTL_S:
                        # leaders that die before put() (query error,
                        # deadline expiry) leave orphans only a
                        # same-key miss would replace — sweep expired
                        # flights here so diverse errored keys cannot
                        # grow the registry without bound
                        if len(self._flights) >= 64:
                            for k in [k for k, f in self._flights.items()
                                      if now - f.t0 > FLIGHT_TTL_S]:
                                self._flights.pop(k).event.set()
                        self._flights[key] = _Flight(gens)
                    self.misses += 1
                    if t is not None:
                        self._tc_locked(t)[1] += 1
                    return False, None
                if budget <= 0:
                    # joinable fill but the caller can't wait
                    self.misses += 1
                    if t is not None:
                        self._tc_locked(t)[1] += 1
                    return False, None
                self.flight_joins += 1
                remaining = min(budget, FLIGHT_TTL_S - (now - fl.t0))
            t0 = time.monotonic()
            filled = fl.event.wait(remaining)
            budget -= time.monotonic() - t0
            if filled:
                # loop re-probes: the normal outcome is a hit on the
                # leader's fill (counted below as flight_served); a
                # refused fill (oversize) falls through to computing
                with self._lock:
                    e = self._entries.get(key)
                    if e is not None and e.gens == gens:
                        self._entries[key] = self._entries.pop(key)
                        self._tenant_touch_locked(key, e)
                        e.hits += 1
                        self.hits += 1
                        if t is not None:
                            self._tc_locked(t)[0] += 1
                        self.flight_served += 1
                        return True, e.value
                    budget = 0  # resolved without a usable fill
            # timed out (or unusable fill): compute ourselves on the
            # next pass — budget is spent, so the re-entry can't wait

    def put(self, key: Any, gens: Any, value: object,
            nbytes: int, tenant: str | None = None) -> bool:
        """Insert one result stamped with the generations captured
        BEFORE its inputs were read.  Returns False when the entry was
        refused (disabled / oversize / bigger than the whole budget).
        Every outcome resolves an open flight for the key — waiters
        must never outlive their leader's attempt.  With [tenants]
        isolation on, the fill is charged to ``tenant`` (or the
        thread-local tenant scope) and eviction prefers over-budget
        tenants' own entries."""
        if not self.enabled:
            return False
        from pilosa_tpu import faultinject as _fi

        if _fi.armed:
            # failpoint: the production cache-fill path (an injected
            # error here surfaces to the filling query; waiters'
            # bounded flight wait covers the unresolved flight)
            _fi.hit("resultcache.fill")
        t = self._caller_tenant(tenant)
        nbytes = int(nbytes) + ENTRY_OVERHEAD_BYTES
        if nbytes > self.max_entry_bytes or nbytes > self.budget:
            with self._lock:
                self.skipped_oversize += 1
                self._resolve_flight_locked(key)
                self._noflight[key] = None
                while len(self._noflight) > 256:
                    self._noflight.pop(next(iter(self._noflight)))
            return False
        with self._lock:
            self._noflight.pop(key, None)
            old = self._entries.pop(key, None)
            if old is not None:
                self.bytes -= old.nbytes
                self._tenant_untrack_locked(key, old)
            e = _Entry(gens, value, nbytes, tenant=t)
            self._entries[key] = e
            self.bytes += nbytes
            self._tenant_track_locked(key, e)
            self.fills += 1
            if t is not None:
                self._tc_locked(t)[2] += 1
            self._resolve_flight_locked(key)
            # strict budget: evict until under — over-budget tenants'
            # oldest entries first (their churn displaces themselves),
            # then global LRU.  The entry just inserted is never a
            # victim, and since it fits the budget on its own (checked
            # above) the loop terminates with it retained.
            while self.bytes > self.budget and len(self._entries) > 1:
                vk = self._victim_key_locked(key)
                if vk is None:
                    break
                ve = self._entries.pop(vk)
                self.bytes -= ve.nbytes
                self._tenant_untrack_locked(vk, ve)
                self.evictions += 1
                if ve.tenant is not None:
                    self._tc_locked(ve.tenant)[3] += 1
            return True

    def _resolve_flight_locked(self, key: Any) -> None:
        fl = self._flights.pop(key, None)
        if fl is not None:
            fl.event.set()

    def invalidate_shard(self, index: str, shard: int) -> int:
        """Drop every entry whose key covers ``shard`` of ``index`` —
        the rebalance cutover hook.  Generation stamps alone do NOT
        cover an ownership change: the local fragments never mutated,
        so a node that just lost (or gained) a shard would keep
        serving its remote-map entries verbatim.  Executor keys are
        ``(holder_uid, index, kind, sig, extra, shards, placement)``
        (see Executor._rc_probe); foreign key shapes are left alone.
        Dropped keys resolve their open flights so waiters recompute
        instead of waiting on a fill for an evicted key."""
        shard = int(shard)
        with self._lock:
            victims = []
            for key, e in self._entries.items():
                k = getattr(key, "k", key)
                if (isinstance(k, tuple) and len(k) >= 6
                        and k[1] == index
                        and isinstance(k[5], tuple) and shard in k[5]):
                    victims.append((key, e))
            for key, e in victims:
                del self._entries[key]
                self.bytes -= e.nbytes
                self._tenant_untrack_locked(key, e)
                self._resolve_flight_locked(key)
            self.invalidations += len(victims)
            return len(victims)

    def stats_dict(self) -> dict[str, Any]:
        with self._lock:
            return {
                "enabled": self.enabled,
                "budget": self.budget,
                "maxEntryBytes": self.max_entry_bytes,
                "ttlS": self.ttl_s,
                "bytes": self.bytes,
                "entries": len(self._entries),
                "hits": self.hits,
                "misses": self.misses,
                "fills": self.fills,
                "evictions": self.evictions,
                "invalidations": self.invalidations,
                "skippedOversize": self.skipped_oversize,
                "flightJoins": self.flight_joins,
                "flightServed": self.flight_served,
                "flightsOpen": len(self._flights),
                "reordered": self.reordered,
                "tenantPrefEvictions": self.tenant_pref_evictions,
            }

    def tenant_stats(self) -> dict[str, dict]:
        """Per-tenant cache accounting — the result-cache half of
        GET /debug/tenants: live bytes, soft budget, and the
        hit/miss/fill/eviction counters an abusive-tenant triage
        reads.  Empty until a tenant-attributed access happens."""
        pol = _tenant.policy()
        out: dict[str, dict] = {}
        with self._lock:
            names = set(self._tenant_bytes) | set(self._tenant_counters)
            for t in sorted(names):
                c = self._tenant_counters.get(t, [0, 0, 0, 0])
                d = {
                    "bytes": self._tenant_bytes.get(t, 0),
                    "entries": len(self._tenant_lru.get(t, ())),
                    "hits": c[0],
                    "misses": c[1],
                    "fills": c[2],
                    "evictions": c[3],
                }
                if pol is not None:
                    d["softBudget"] = int(
                        self.budget * pol.quota_for(t).cache_share)
                out[t] = d
        return out

    def debug(self, top_n: int = 32) -> dict[str, Any]:
        """The /debug/resultcache document: totals plus the largest
        entries (key digest + human-readable key, bytes, age, hits)."""
        out = self.stats_dict()
        tstats = self.tenant_stats()
        if tstats:
            out["tenants"] = tstats
        now = time.monotonic()
        with self._lock:
            entries = sorted(self._entries.items(),
                             key=lambda kv: -kv[1].nbytes)[:top_n]
            out["top"] = [{
                "key": key_digest(k),
                "repr": repr(k)[:200],
                "bytes": e.nbytes,
                "ageS": round(now - e.t, 3),
                "hits": e.hits,
            } for k, e in entries]
        return out

    def publish_gauges(self, stats: Any) -> None:
        """Push the cache.* families into a stats registry at scrape
        time (/metrics, /debug/vars).  Cumulative totals render as
        gauges, not counters — re-publishing a cumulative value
        through a counter would double-count (same rule as
        devobs.publish_gauges)."""
        s = self.stats_dict()
        stats.gauge("cache.hits", s["hits"])
        stats.gauge("cache.reordered", s["reordered"])
        stats.gauge("cache.misses", s["misses"])
        stats.gauge("cache.fills", s["fills"])
        stats.gauge("cache.evictions", s["evictions"])
        stats.gauge("cache.invalidations", s["invalidations"])
        stats.gauge("cache.bytes", s["bytes"])
        stats.gauge("cache.entries", s["entries"])
        stats.gauge("cache.budget_bytes", s["budget"])
        stats.gauge("cache.flight_joins", s["flightJoins"])
        stats.gauge("cache.flight_served", s["flightServed"])


def key_digest(key: Any) -> str:
    """Stable short digest of a cache key for flight records and the
    debug surface (the full tuple is structured but verbose)."""
    return hashlib.blake2b(repr(key).encode(),
                           digest_size=8).hexdigest()


def result_nbytes(value: Any) -> int:
    """Byte estimate for one cached result: numpy buffers by .nbytes,
    containers and result dataclasses (GroupCount rows of FieldRow,
    Pair, ValCount...) recursively, scalars a machine word.  An
    estimate — the budget bounds order-of-magnitude memory, not
    malloc'd bytes.  Charging a GroupCount as a bare scalar would let
    a GroupBy-heavy workload exceed the budget by an order of
    magnitude in real memory, so dataclasses recurse into their
    fields."""
    nb = getattr(value, "nbytes", None)
    if nb is not None:
        return int(nb)
    if isinstance(value, dict):
        return 64 + sum(result_nbytes(k) + result_nbytes(v)
                        for k, v in value.items())
    if isinstance(value, (list, tuple)):
        return 64 + sum(result_nbytes(v) for v in value)
    if isinstance(value, (bytes, str)):
        return 48 + len(value)
    if dataclasses.is_dataclass(value) and not isinstance(value, type):
        return 64 + sum(
            result_nbytes(getattr(value, f.name))
            for f in dataclasses.fields(value))
    return 32


# ----------------------------------------------------------- process-wide


_global: ResultCache | None = None
_global_lock = threading.Lock()


def cache() -> ResultCache:
    """The process-wide cache (one budget per process, like the
    residency manager and the jit caches the results shortcut).
    Lock-free on the hot path — every query probe calls this; the
    lock only guards first construction."""
    global _global
    c = _global
    if c is not None:
        return c
    with _global_lock:
        if _global is None:
            _global = ResultCache()
        return _global


def configure(budget_bytes: int | None = None,
              max_entry_bytes: int | None = None,
              ttl_s: float | None = None,
              enabled: bool | None = None) -> ResultCache:
    """Apply [cache] config to the process-wide cache in place
    (counters and live entries survive — a second in-process server
    must not wipe the first's warm cache)."""
    c = cache()
    with c._lock:
        if budget_bytes is not None:
            c.budget = int(budget_bytes)
        if max_entry_bytes is not None:
            c.max_entry_bytes = int(max_entry_bytes)
        if ttl_s is not None:
            c.ttl_s = float(ttl_s)
        if enabled is not None:
            c.enabled = bool(enabled)
    return c


def reset(budget_bytes: int = DEFAULT_BUDGET_BYTES,
          max_entry_bytes: int = DEFAULT_MAX_ENTRY_BYTES,
          ttl_s: float = 0.0, enabled: bool = True) -> ResultCache:
    """Replace the process-wide cache (tests)."""
    global _global
    with _global_lock:
        _global = ResultCache(budget_bytes, max_entry_bytes, ttl_s,
                              enabled)
        return _global
