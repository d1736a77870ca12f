"""Admission control: priority-classed gating and load shedding for
the serving path.

The ROADMAP north star is heavy traffic from millions of users, yet a
stdlib ThreadingHTTPServer admits one unbounded thread per connection:
overload means unbounded queueing and latency collapse, with
anti-entropy and resize traffic competing head-to-head with user
queries.  This module is the process-wide gate between accept and
dispatch — the admission/batching discipline TPU serving stacks are
built around (Ragged Paged Attention, arxiv 2604.15464, exists because
TPU serving is admission-bound; DrJAX, arxiv 2403.07128, is the
map-reduce fan-out the deadline checks protect from expired
stragglers).

Three priority classes, strictly ordered:

- ``query``    — user PQL (highest; must never starve)
- ``ingest``   — import / import-value / import-roaring
- ``internal`` — syncer anti-entropy, resize fragment transfer,
  translate replication, cluster control messages (lowest)

Each class owns its own concurrency cap and bounded FIFO wait queue,
so classes are *isolated*: saturating ``internal`` cannot consume a
single ``query`` slot.  Load shedding is honest and lowest-class/
newest-first:

- a request arriving to a full class queue is refused (429 — the
  NEWEST request sheds; queued older requests keep their place);
- a request whose predicted queue wait exceeds its remaining deadline
  is refused up front (503) instead of timing out after burning a
  slot;
- ``internal`` arrivals yield (503) while the ``query`` queue is under
  pressure — the lowest class sheds first under saturation;
- a queued request whose deadline expires sheds with an ``expired``
  outcome (503) and never reaches dispatch.

Every refusal carries ``Retry-After`` derived from the class's EWMA
service time, so clients back off proportionally to actual load.

Per-tenant scheduling (the [tenants] table, serve/tenant.py): with
isolation enabled, every class additionally runs WEIGHTED FAIRNESS
across tenants *inside* its cap — each tenant holds at most its
``share`` of concurrent slots, queues in its own bounded FIFO
(arrivals past ``queue`` shed 429 ``tenant-queue-full`` — the "I am
over quota" signal, distinct from the class-wide ``queue-full``
"server is drowning" one), and freed slots dequeue by deficit round
robin weighted by ``share``, so a tenant flooding its queue drains at
exactly its configured proportion of class capacity while everyone
else's queue wait stays flat.  A per-tenant queue-wait EWMA feeds the
same deadline-unmeetable 503 machinery.  With [tenants] disabled
(the default) the tenant structures are never touched and behavior is
byte-identical to the class-only gate.

The ``admission.acquire`` failpoint (pilosa_tpu.faultinject) sits at
the top of :meth:`AdmissionController.acquire` — ``error(shed)``
injects a deterministic refusal, ``delay(ms)`` a queue-delay stall —
zero-cost disarmed like every other site.

Stats surface (per class, tag ``class:<name>``):
``admission.admitted``, ``admission.shed`` (tag ``reason:<why>``),
``admission.expired`` counters and the ``admission.queue_wait``
histogram (nanoseconds).  Per-tenant totals publish as the
``tenant.*`` gauge family at scrape time (serve/tenant.py).
"""

from __future__ import annotations

import functools
import math
import threading
import time
from collections import deque

from pilosa_tpu import faultinject as _fi
from pilosa_tpu import stats as _stats
from pilosa_tpu.serve import tenant as _tenant
from pilosa_tpu.serve.deadline import Deadline, tls_scope

#: Priority order: lower number = higher priority = sheds last.
PRIORITY = {"query": 0, "ingest": 1, "internal": 2}
CLASSES = tuple(sorted(PRIORITY, key=PRIORITY.get))

#: Hard ceiling on time spent queued without a deadline — a wedged
#: slot holder must not strand waiters forever.
MAX_QUEUE_WAIT_S = 60.0

#: Retry-After bounds (seconds).  The floor keeps the integer header
#: non-zero; the ceiling stops a long EWMA from telling clients to
#: disappear for minutes.
RETRY_AFTER_MIN_S = 1
RETRY_AFTER_MAX_S = 30


class ShedError(Exception):
    """A request refused (or expired) at the admission gate.  Carries
    the HTTP status the handler should answer with and the suggested
    Retry-After (seconds)."""

    def __init__(self, klass: str, reason: str, status: int,
                 retry_after: int, wait_ns: int = 0,
                 tenant: str | None = None):
        detail = f" (tenant {tenant})" if tenant else ""
        super().__init__(
            f"{klass} request {reason}{detail} "
            f"(admission control; retry after {retry_after}s)")
        self.klass = klass
        self.reason = reason  # queue-full | tenant-queue-full |
        #                       deadline-unmeetable | yield-to-query |
        #                       queue-timeout | expired
        self.status = status  # 429 (back off) or 503 (overloaded)
        self.retry_after = retry_after
        # time spent queued before the refusal (expired-in-queue) —
        # the shed flight record's queue-wait evidence
        self.wait_ns = wait_ns
        # the shedding tenant (isolation enabled): rides the
        # structured 429/503 body so a client can tell "I am over
        # quota" (tenant-queue-full) from "the server is drowning"
        self.tenant = tenant

    @property
    def outcome(self) -> str:
        """Flight-record outcome: ``expired`` for a spent deadline,
        ``shed`` for every capacity refusal."""
        return "expired" if self.reason == "expired" else "shed"


# --------------------------------------------------------------------
# outbound RPC class tagging
# --------------------------------------------------------------------

_tls_rpc = threading.local()  # .klass: class stamped on outbound RPC


class rpc_class(tls_scope):
    """Tag every outbound RPC issued inside the with-block with an
    admission class (the ``X-Pilosa-Class`` header, read by
    server/client.py).  Internal callers — syncer, resize, translate
    replication, broadcasts — wrap their send loops with
    ``rpc_class("internal")`` so their traffic lands in the receiving
    node's lowest class and can never starve user queries; the import
    fan-out tags its replica deliveries ``ingest``.  Re-entrant."""

    __slots__ = ()

    def __init__(self, klass: str):
        if klass not in PRIORITY:
            raise ValueError(f"unknown admission class: {klass!r}")
        super().__init__(_tls_rpc, "klass", klass)


def current_rpc_class() -> str | None:
    return getattr(_tls_rpc, "klass", None)


def tagged(klass: str):
    """Decorator form of :class:`rpc_class`: every RPC the function
    issues carries ``klass``.  The one-line spelling for internal call
    sites (syncer sweeps, resize jobs, translate tailing)."""
    if klass not in PRIORITY:
        raise ValueError(f"unknown admission class: {klass!r}")

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with rpc_class(klass):
                return fn(*args, **kwargs)

        return wrapper

    return deco


# --------------------------------------------------------------------
# controller
# --------------------------------------------------------------------

class _Waiter:
    __slots__ = ("event", "dl", "state", "tenant")

    def __init__(self, dl: Deadline | None, tenant: str | None = None):
        self.event = threading.Event()
        self.dl = dl
        self.state = "waiting"  # -> admitted | expired | abandoned
        self.tenant = tenant


class _TenantState:
    """One tenant's slot + queue accounting inside ONE class (guarded
    by the controller's lock).  ``deficit`` is the deficit-round-robin
    credit: each ring visit adds the tenant's share, each dequeued
    waiter spends 1 — a flooding tenant drains at its weight's
    proportion of freed slots, never faster."""

    __slots__ = ("in_flight", "waiters", "deficit", "admitted",
                 "shed", "expired", "wait_ewma_s")

    def __init__(self):
        self.in_flight = 0
        self.waiters: deque[_Waiter] = deque()
        self.deficit = 0.0
        self.admitted = 0
        self.shed = 0
        self.expired = 0
        self.wait_ewma_s = 0.0  # EWMA of observed queue waits


class _Gate:
    """One class's slot + queue accounting (guarded by the
    controller's lock).  ``tenants``/``rr``/``waiting_total`` are the
    per-tenant layer — untouched (and empty) while [tenants] is off."""

    __slots__ = ("cap", "depth", "in_flight", "waiters",
                 "ewma_service_s", "admitted", "shed", "expired",
                 "tenants", "rr", "waiting_total")

    def __init__(self, cap: int, depth: int):
        self.cap = max(1, int(cap))
        self.depth = max(0, int(depth))
        self.in_flight = 0
        self.waiters: deque[_Waiter] = deque()
        self.ewma_service_s = 0.0
        # local mirrors of the stats counters so /debug/admission works
        # even on a NOP stats backend
        self.admitted = 0
        self.shed = 0
        self.expired = 0
        # tenant name -> _TenantState; rr is the DRR ring of tenants
        # with queued waiters; waiting_total sums their queue lengths
        self.tenants: dict[str, _TenantState] = {}
        self.rr: deque[str] = deque()
        self.waiting_total = 0


class Ticket:
    """One admitted request's slot.  ``release()`` is idempotent and
    MUST run (the handler's finally) or the slot leaks."""

    __slots__ = ("_ctrl", "klass", "queue_wait_ns", "_t_admit",
                 "_released", "tenant")

    def __init__(self, ctrl: "AdmissionController | None", klass: str,
                 queue_wait_ns: int, tenant: str | None = None):
        self._ctrl = ctrl
        self.klass = klass
        self.queue_wait_ns = queue_wait_ns
        self._t_admit = time.monotonic()
        self._released = False
        self.tenant = tenant

    def release(self) -> None:
        if self._released:
            return
        self._released = True
        if self._ctrl is not None:
            self._ctrl._release(self.klass, self._t_admit,
                                tenant=self.tenant)

    def info(self) -> dict:
        """The flight-record stamp (rides ``observe.Request``)."""
        d = {"class": self.klass, "queue_wait_ns": self.queue_wait_ns}
        if self.tenant is not None:
            d["tenant"] = self.tenant
        return d


class AdmissionController:
    """Process-wide admission gate: per-class token/slot accounting
    over bounded FIFO wait queues.  One per server; thread-safe."""

    def __init__(self, query_cap: int = 32, query_queue: int = 128,
                 ingest_cap: int = 16, ingest_queue: int = 64,
                 internal_cap: int = 16, internal_queue: int = 64,
                 default_deadline: float = 0.0, enabled: bool = True,
                 stats=None):
        self.enabled = enabled
        self.default_deadline = default_deadline  # s; 0 = none implied
        self.stats = stats if stats is not None else _stats.NOP
        self._lock = threading.Lock()
        self._gates = {
            "query": _Gate(query_cap, query_queue),
            "ingest": _Gate(ingest_cap, ingest_queue),
            "internal": _Gate(internal_cap, internal_queue),
        }

    # ------------------------------------------------------------ sizing

    def total_capacity(self) -> int:
        """Sum of class caps + queue depths — the bound on requests
        the gate will ever hold concurrently, and the basis for the
        accept-side handler-thread cap (server/handler.py)."""
        return sum(g.cap + g.depth for g in self._gates.values())

    # ----------------------------------------------------------- acquire

    def acquire(self, klass: str, dl: Deadline | None = None,
                tenant: str | None = None) -> Ticket:
        """Admit (possibly after a bounded FIFO wait) or raise
        ShedError.  Runs on the request's handler thread; the wait is
        event-based, never a spin.  ``tenant`` is the request's tenant
        id — consulted only while [tenants] isolation is enabled, in
        which case the request also clears its tenant's per-class
        quota (anonymous requests ride the default tier)."""
        g = self._gates.get(klass)
        if g is None:
            raise ValueError(f"unknown admission class: {klass!r}")
        if _fi.armed:
            # failpoint: deterministic overload/queue-delay chaos at
            # the gate itself — error(shed) refuses, delay(ms) stalls
            _fi.hit("admission.acquire")
        if not self.enabled:
            return Ticket(None, klass, 0)
        pol = _tenant.policy()
        tname = _tenant.resolve(tenant) if pol is not None else None
        t0 = time.perf_counter_ns()
        with self._lock:
            ts = None
            if pol is not None:
                ts = g.tenants.get(tname)
                if ts is None:
                    ts = g.tenants[tname] = _TenantState()
                quota = pol.quota_for(tname)
                share = max(1, quota.share)
            if dl is not None and dl.expired():
                g.expired += 1
                if ts is not None:
                    ts.expired += 1
                err = ShedError(klass, "expired", 503,
                                self._retry_after(g), tenant=tname)
            elif klass == "internal" and self._query_pressure_locked():
                # lowest class sheds first: anti-entropy/resize yield
                # while user queries are stacking up
                g.shed += 1
                if ts is not None:
                    ts.shed += 1
                err = ShedError(klass, "yield-to-query", 503,
                                self._retry_after(self._gates["query"]),
                                tenant=tname)
            elif ts is None and g.in_flight < g.cap and not g.waiters:
                g.in_flight += 1
                g.admitted += 1
                err = None
                w = None
            elif (ts is not None and g.in_flight < g.cap
                  and ts.in_flight < share and not ts.waiters):
                # a tenant under BOTH caps with no queued peers admits
                # straight through; other tenants' waiters are waiting
                # on their own quota or on slots the wake loop already
                # found occupied
                g.in_flight += 1
                g.admitted += 1
                ts.in_flight += 1
                ts.admitted += 1
                # a zero-wait admit decays the queue-wait EWMA (sample
                # 0) — without it a past congestion episode pins the
                # deadline-unmeetable floor high forever, since sheds
                # never sample and queued admits only happen when the
                # floor already let the request queue
                ts.wait_ewma_s *= 0.8
                err = None
                w = None
            elif ts is not None and len(ts.waiters) >= max(0, quota.queue):
                # the TENANT's queue is full: this client is over its
                # own quota — distinct reason (and tenant on the body)
                # so it can tell quota pressure from server overload
                g.shed += 1
                ts.shed += 1
                err = ShedError(klass, "tenant-queue-full", 429,
                                self._retry_after(g), tenant=tname)
            elif (len(g.waiters) if ts is None
                  else g.waiting_total) >= g.depth:
                # newest-first shedding: the ARRIVING request refuses;
                # queued older requests keep their place
                g.shed += 1
                if ts is not None:
                    ts.shed += 1
                err = ShedError(klass, "queue-full", 429,
                                self._retry_after(g), tenant=tname)
            elif (dl is not None
                  and (self._predicted_wait_s(g) if ts is None
                       else self._predicted_tenant_wait_s(g, ts, share))
                  > dl.remaining()):
                g.shed += 1
                if ts is not None:
                    ts.shed += 1
                err = ShedError(klass, "deadline-unmeetable", 503,
                                self._retry_after(g), tenant=tname)
            elif ts is None:
                err = None
                w = _Waiter(dl)
                g.waiters.append(w)
            else:
                err = None
                w = _Waiter(dl, tenant=tname)
                ts.waiters.append(w)
                g.waiting_total += 1
                if tname not in g.rr:
                    g.rr.append(tname)
        # stats emit OUTSIDE the lock (a slow/raising backend must not
        # serialize admission) and exception-proof (a raising backend
        # must never leak a slot or mask the shed signal)
        if err is not None:
            self._emit_shed(klass, err.reason)
            raise err
        if w is None:
            self._emit_admitted(klass, 0)
            return Ticket(self, klass, 0, tenant=tname)
        timeout = MAX_QUEUE_WAIT_S
        if dl is not None:
            timeout = min(timeout, max(0.0, dl.remaining()))
        w.event.wait(timeout)
        # classify at WAKE time: only a deadline that actually passed
        # is an expiry; timing out on the MAX_QUEUE_WAIT_S backstop
        # (no deadline, or a budget longer than the backstop) is a
        # capacity incident (wedged slot holder) and reports as a
        # shed — or operators chase client deadlines instead of the
        # stuck slot
        reason = ("expired" if dl is not None and dl.expired()
                  else "queue-timeout")
        wait_ns = time.perf_counter_ns() - t0
        with self._lock:
            admitted = w.state == "admitted"
            if admitted:
                g.admitted += 1
                if ts is not None:
                    ts.admitted += 1
                    wait_s = wait_ns / 1e9
                    ts.wait_ewma_s = (wait_s if ts.wait_ewma_s == 0.0
                                      else 0.8 * ts.wait_ewma_s
                                      + 0.2 * wait_s)
            else:
                # deadline (or the safety cap) expired while queued —
                # either noticed here or marked by a promoter
                if w.state == "waiting":
                    w.state = "abandoned"
                    try:
                        if ts is None:
                            g.waiters.remove(w)
                        else:
                            ts.waiters.remove(w)
                            g.waiting_total -= 1
                    except ValueError:
                        pass
                if reason == "expired":
                    g.expired += 1
                    if ts is not None:
                        ts.expired += 1
                else:
                    g.shed += 1
                    if ts is not None:
                        ts.shed += 1
        if admitted:
            self._emit_admitted(klass, wait_ns)
            return Ticket(self, klass, wait_ns, tenant=tname)
        self._emit_shed(klass, reason)
        raise ShedError(klass, reason, 503, self._retry_after(g),
                        wait_ns=wait_ns, tenant=tname)

    def try_acquire(self, klass: str) -> Ticket:
        """Non-blocking admit: a free slot (with no queued waiters
        ahead) or an immediate ShedError — never a queue wait.  The
        gate for opportunistic background work (tiered-residency
        promotions, prefetch): under saturation such work must SHED,
        not line up behind user traffic it exists to serve."""
        g = self._gates.get(klass)
        if g is None:
            raise ValueError(f"unknown admission class: {klass!r}")
        if not self.enabled:
            return Ticket(None, klass, 0)
        with self._lock:
            if (klass == "internal" and self._query_pressure_locked()) \
                    or g.in_flight >= g.cap or g.waiters \
                    or g.waiting_total:
                g.shed += 1
                err = ShedError(klass, "yield-to-query", 503,
                                self._retry_after(g))
            else:
                g.in_flight += 1
                g.admitted += 1
                err = None
        if err is not None:
            self._emit_shed(klass, err.reason)
            raise err
        self._emit_admitted(klass, 0)
        return Ticket(self, klass, 0)

    def _release(self, klass: str, t_admit: float,
                 tenant: str | None = None) -> None:
        with self._lock:
            g = self._gates[klass]
            g.in_flight -= 1
            if tenant is not None:
                ts = g.tenants.get(tenant)
                if ts is not None and ts.in_flight > 0:
                    ts.in_flight -= 1
            held = time.monotonic() - t_admit
            g.ewma_service_s = (held if g.ewma_service_s == 0.0
                                else 0.8 * g.ewma_service_s + 0.2 * held)
            while g.in_flight < g.cap and g.waiters:
                w = g.waiters.popleft()
                if w.state != "waiting":  # abandoned by its own thread
                    continue
                if w.dl is not None and w.dl.expired():
                    # expired in queue: wake it to shed; its own thread
                    # counts the expiry (exactly once, in acquire)
                    w.state = "expired"
                    w.event.set()
                    continue
                w.state = "admitted"
                g.in_flight += 1
                w.event.set()
                break
            if g.rr:
                self._wake_tenants_locked(g)

    def _wake_tenants_locked(self, g: _Gate) -> None:
        """Deficit-round-robin dequeue across the tenants with queued
        waiters: each ring visit credits a tenant its ``share``, each
        admitted waiter spends one credit, and a tenant never exceeds
        its per-class concurrency share — so freed capacity divides in
        weight proportion no matter how deep any one queue is.  Caller
        holds the controller lock."""
        pol = _tenant.policy()
        while g.in_flight < g.cap and g.rr:
            advanced = False
            for _ in range(len(g.rr)):
                if g.in_flight >= g.cap:
                    break
                tname = g.rr[0]
                ts = g.tenants.get(tname)
                if ts is None or not ts.waiters:
                    g.rr.popleft()
                    if ts is not None:
                        ts.deficit = 0.0
                    advanced = True
                    continue
                # [tenants] turned off with waiters still queued: fall
                # back to unweighted drain so nobody strands
                quota = pol.quota_for(tname) if pol is not None else None
                share = max(1, quota.share) if quota is not None else g.cap
                if ts.deficit < 1.0:
                    ts.deficit += share
                while (ts.deficit >= 1.0 and ts.waiters
                       and g.in_flight < g.cap
                       and ts.in_flight < share):
                    w = ts.waiters.popleft()
                    g.waiting_total -= 1
                    if w.state != "waiting":
                        # abandoned by its own thread: costs no credit
                        advanced = True
                        continue
                    if w.dl is not None and w.dl.expired():
                        w.state = "expired"
                        w.event.set()
                        advanced = True
                        continue
                    w.state = "admitted"
                    g.in_flight += 1
                    ts.in_flight += 1
                    ts.deficit -= 1.0
                    w.event.set()
                    advanced = True
                if (ts.waiters and ts.deficit >= 1.0
                        and ts.in_flight < share):
                    # unspent credit with queued waiters and tenant
                    # capacity: the class is full — stay at the ring
                    # front so the NEXT freed slot continues this
                    # tenant's turn (rotating here would flatten the
                    # weights to plain round robin whenever slots free
                    # one at a time, i.e. always)
                    break
                g.rr.rotate(-1)
            if not advanced:
                # every queued tenant is at its concurrency share (or
                # the class is full): nothing more can wake now
                break

    # ---------------------------------------------------------- policies

    def _query_pressure_locked(self) -> bool:
        """True while the query class is saturated AND its queue is at
        least half full — the signal for lower classes to yield.
        Tenant-queued waiters (waiting_total) count: with isolation on
        the class queue lives in the per-tenant deques."""
        q = self._gates["query"]
        return (q.depth > 0 and q.in_flight >= q.cap
                and 2 * (len(q.waiters) + q.waiting_total) >= q.depth)

    def _predicted_wait_s(self, g: _Gate) -> float:
        """Queue-position estimate: (waiters ahead + 1) drain at
        cap-parallel EWMA service time.  Zero until the first release
        seeds the EWMA — never shed on a guess with no evidence."""
        return (len(g.waiters) + 1) * g.ewma_service_s / g.cap

    def _predicted_tenant_wait_s(self, g: _Gate, ts: _TenantState,
                                 share: int) -> float:
        """Per-tenant queue-position estimate: the tenant's waiters
        drain at ITS share of class parallelism (never the full cap —
        an over-quota tenant's queue moves at its weight), floored by
        the tenant's observed queue-wait EWMA so a tenant whose waits
        have been long sheds honestly even while its queue is short."""
        eff = max(1, min(share, g.cap))
        return max((len(ts.waiters) + 1) * g.ewma_service_s / eff,
                   ts.wait_ewma_s)

    def _retry_after(self, g: _Gate) -> int:
        return int(min(RETRY_AFTER_MAX_S,
                       max(RETRY_AFTER_MIN_S,
                           math.ceil(self._predicted_wait_s(g)))))

    # ---------------------------------------------------------- counting

    def _emit_admitted(self, klass: str, wait_ns: int) -> None:
        try:
            self.stats.count_with_tags("admission.admitted", 1, 1.0,
                                       [f"class:{klass}"])
            if wait_ns:
                self.stats.with_tags(f"class:{klass}").timing(
                    "admission.queue_wait", wait_ns)
        except Exception:  # noqa: BLE001 — telemetry never leaks slots
            pass

    def _emit_shed(self, klass: str, reason: str) -> None:
        try:
            if reason == "expired":
                self.stats.count_with_tags("admission.expired", 1, 1.0,
                                           [f"class:{klass}"])
            else:
                self.stats.count_with_tags(
                    "admission.shed", 1, 1.0,
                    [f"class:{klass}", f"reason:{reason}"])
        except Exception:  # noqa: BLE001 — telemetry never masks sheds
            pass

    def count_expired(self, klass: str) -> None:
        """An admitted request that expired DURING execution (the
        executor's deadline checks fired) — same counter, so
        ``admission.expired`` is the complete expiry picture."""
        g = self._gates.get(klass)
        if g is None:
            return
        with self._lock:
            g.expired += 1
        self._emit_shed(klass, "expired")

    # ------------------------------------------------------------- views

    def debug(self) -> dict:
        """The /debug/admission document.  With [tenants] isolation
        enabled each class carries its per-tenant queue/quota
        breakdown — the triage surface for "which tenant is eating
        the class"."""
        pol = _tenant.policy()
        with self._lock:
            out = {
                "enabled": self.enabled,
                "defaultDeadline": self.default_deadline,
                "classes": {
                    k: {
                        "cap": g.cap,
                        "queueDepth": g.depth,
                        "inFlight": g.in_flight,
                        "waiting": (len(g.waiters) + g.waiting_total),
                        "ewmaServiceMs": round(g.ewma_service_s * 1e3, 3),
                        "admitted": g.admitted,
                        "shed": g.shed,
                        "expired": g.expired,
                    }
                    for k, g in self._gates.items()
                },
            }
            if pol is not None:
                for k, g in self._gates.items():
                    out["classes"][k]["tenants"] = {
                        name: self._tenant_dict_locked(ts,
                                                       pol.quota_for(name))
                        for name, ts in g.tenants.items()
                    }
        if pol is not None:
            out["tenantsEnabled"] = True
        return out

    @staticmethod
    def _tenant_dict_locked(ts: _TenantState, quota) -> dict:
        return {
            "share": quota.share,
            "queueDepth": quota.queue,
            "inFlight": ts.in_flight,
            "waiting": len(ts.waiters),
            "deficit": round(ts.deficit, 3),
            "admitted": ts.admitted,
            "shed": ts.shed,
            "expired": ts.expired,
            "queueWaitEwmaMs": round(ts.wait_ewma_s * 1e3, 3),
        }

    def tenants_debug(self) -> dict:
        """Per-tenant totals aggregated across classes — the admission
        half of GET /debug/tenants (empty with isolation off AND no
        tenant state accrued)."""
        out: dict[str, dict] = {}
        with self._lock:
            for g in self._gates.values():
                for name, ts in g.tenants.items():
                    d = out.setdefault(name, {
                        "inFlight": 0, "waiting": 0, "admitted": 0,
                        "shed": 0, "expired": 0, "queueWaitEwmaMs": 0.0,
                    })
                    d["inFlight"] += ts.in_flight
                    d["waiting"] += len(ts.waiters)
                    d["admitted"] += ts.admitted
                    d["shed"] += ts.shed
                    d["expired"] += ts.expired
                    d["queueWaitEwmaMs"] = round(
                        max(d["queueWaitEwmaMs"],
                            ts.wait_ewma_s * 1e3), 3)
        return out
