"""Device-runtime telemetry: compile tracking, transfer metering, and
memory/residency sampling.

The flight recorder (pilosa_tpu.observe) explains where a query spent
its time; this module explains WHY the device made it slow — the three
failure classes that dominate TPU serving stacks and are invisible to
request-path timings alone (the per-kernel/per-shape compile and memory
telemetry Ragged Paged Attention, arxiv 2604.15464, motivates; DrJAX,
arxiv 2403.07128, makes the per-node runtime-visibility case for the
map-reduce fan-out):

- **XLA recompiles** — a query hitting a fresh canonical shape pays a
  trace+lower+compile (tens of ms to seconds) that looks like an
  inexplicable latency spike.  Every ``_jit_*`` kernel in ``ops/`` is
  wrapped by :func:`instrument`, which detects a jit cache miss (via
  the jitted callable's ``_cache_size``, falling back to first-seen
  shape keys on jax versions without it) and times the first-lowering
  call, keyed per (kernel, canonical operand shape).  Each detected
  compile is also an EVENT on the span clock (``compile.events``, the
  newest 256): which program and shape, when, on which thread, paid by
  which read, how long in JAX's own phases (trace, lower, backend) and
  what the persistent compile cache said (``hit``, ``miss`` or
  ``off``), from ``jax.monitoring`` listeners that fire only when JAX
  compiles.  The paying read gets a ``compile`` span, the event journal
  a ``compile`` event.
- **Host→device transfer bursts** — ``ops/bitmap.device_put``
  (the one staging funnel for fragment matrices, BSI planes, and field
  row stacks) reports bytes/chunks per labeled owner through
  :func:`note_transfer`.
- **Residency churn / HBM pressure** — the process-wide residency
  manager's usage/budget/evictions/high-water plus each device's
  ``memory_stats()`` (bytes_in_use vs bytes_limit, where the backend
  reports them) are sampled on demand and by the optional background
  sampler.

Exposure: ``GET /debug/devices`` (snapshot()), ``device.*`` /
``compile.*`` / ``residency.*`` gauges+histograms in the stats
registry (publish_gauges(), called at /metrics and /debug/vars scrape
time and by the ``[observe] device-sample-interval`` sampler), and
compile attribution stamped onto the active QueryRecord so a slow
query answers "slow because it compiled" in one request.

Lock discipline mirrors observe.py: the per-dispatch fast path is one
attribute read + two C calls (``_cache_size``), no locks; the
observer's lock is touched only on the rare compile/transfer events
and on snapshot: a warm fused Count takes it zero times and records
nothing (``tests/test_observer_cost.py``).
"""

from __future__ import annotations

import threading
import time
from collections import deque

from pilosa_tpu import observe as _observe

#: compile events kept for ``/debug/devices`` (the newest)
MAX_EVENTS = 256

# what JAX said while it compiled: (thread, span clock, slot, value)
# appended by the ``jax.monitoring`` listeners below on the compiling
# thread.  Appends are GIL-atomic; nothing reads it but a detected
# compile, which picks its own thread's marks inside its own interval
_marks: deque[tuple] = deque(maxlen=1024)
_PHASES = {"/jax/core/compile/jaxpr_trace_duration": "traceMs",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerMs",
           "/jax/core/compile/backend_compile_duration": "backendMs"}
_CACHE = {"/jax/compilation_cache/cache_hits": "hit",
          "/jax/compilation_cache/cache_misses": "miss"}
_listening = False


def _on_duration(event: str, duration_secs: float, **_kw) -> None:
    slot = _PHASES.get(event)
    if slot is not None:
        _marks.append((threading.get_ident(), time.perf_counter_ns(),
                       slot, duration_secs * 1e3))


def _on_event(event: str, **_kw) -> None:
    said = _CACHE.get(event)
    if said is not None:
        _marks.append((threading.get_ident(), time.perf_counter_ns(),
                       "persistent", said))


def _listen() -> None:
    """Register the two listeners, once a process (JAX keeps no way to
    take one back, and they cost nothing until it compiles)."""
    global _listening
    with _global_lock:
        if _listening:
            return
        _listening = True
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def _said(t0: int, t1: int) -> dict:
    """JAX's own account of what this thread compiled in [t0, t1]: ms
    by phase and what the persistent cache said.  Inner functions are
    traced inside the outer one's trace, so ``traceMs`` is the longest
    and not the sum; a cache written to says ``miss`` whatever else
    hit; ``off``: it was not asked, or keeps no entry this small."""
    out = {"traceMs": 0.0, "lowerMs": 0.0, "backendMs": 0.0,
           "persistent": "off"}
    me = threading.get_ident()
    for thread, at, slot, value in list(_marks):
        if thread != me or not t0 <= at <= t1:
            continue
        if slot == "persistent":
            if out[slot] != "miss":
                out[slot] = value
        elif slot == "traceMs":
            out[slot] = max(out[slot], value)
        else:
            out[slot] += value
    return {k: round(v, 3) if isinstance(v, float) else v
            for k, v in out.items()}


class _CompileStat:
    """Per-(kernel, canonical shape) compile accounting."""

    __slots__ = ("count", "total_ns", "last_ns", "first_unix")

    def __init__(self):
        self.count = 0
        self.total_ns = 0
        self.last_ns = 0
        self.first_unix = time.time()


class DeviceObserver:
    """Process-wide device-runtime registry (one per process, like the
    residency manager — compiles and transfers are process-wide by
    nature: the jit caches and the staging funnel are shared)."""

    def __init__(self):
        self.enabled = True
        # optional stats client (server assembly wires it in) so
        # compile events publish a compile.ms histogram live
        self.stats = None
        self._lock = threading.Lock()
        # kernel -> shape key -> _CompileStat
        self._compiles: dict[str, dict[str, _CompileStat]] = {}
        self.compile_count = 0
        self.compile_ns = 0
        # compiles the persistent cache had no entry for
        self.compile_cold = 0
        # the newest compiles as events on the span clock
        self._events: deque[dict] = deque(maxlen=MAX_EVENTS)
        # transfer metering: label -> [bytes, chunks, puts]
        self._transfers: dict[str, list[int]] = {}
        self.transfer_bytes = 0
        self.transfer_chunks = 0
        self.transfer_puts = 0
        # device-OOM recoveries: RESOURCE_EXHAUSTED launches that
        # evicted residency and retried (executor fused Count path)
        self.oom_retries = 0

    # -------------------------------------------------------------- events

    def note_compile(self, kernel: str, shape_key: str, ns: int,
                     end_ns: int | None = None) -> None:
        """One detected compile (cache-miss first lowering) of
        ``kernel`` at ``shape_key``, costing ``ns`` wall time up to
        ``end_ns`` on the span clock (now, when not given).  Also
        stamps the query record active on this thread, so the query
        that PAID the compile carries it: ``compiled``, and a
        ``compile`` span under whatever span is open."""
        if end_ns is None:
            end_ns = time.perf_counter_ns()
        start_ns = end_ns - ns
        rec = _observe.current()
        event = {"kernel": kernel, "shape": shape_key,
                 "startNs": start_ns, "endNs": end_ns,
                 "ms": round(ns / 1e6, 3),
                 "thread": threading.get_ident(),
                 "rid": rec.trace_id if rec is not None else None,
                 **_said(start_ns, end_ns)}
        with self._lock:
            per_shape = self._compiles.setdefault(kernel, {})
            st = per_shape.get(shape_key)
            if st is None:
                # bound the per-kernel shape table: a pathological
                # shape churn must not grow the registry without limit
                if len(per_shape) >= 256:
                    shape_key = "<overflow>"
                    st = per_shape.get(shape_key)
                if st is None:
                    st = per_shape[shape_key] = _CompileStat()
            st.count += 1
            st.total_ns += ns
            st.last_ns = ns
            self.compile_count += 1
            self.compile_ns += ns
            self.compile_cold += event["persistent"] == "miss"
            self._events.append(event)
        if rec is not None:
            rec.note_compile(kernel, ns)
            rec.add_span("compile", start_ns, end_ns, kernel=kernel,
                         persistent=event["persistent"])
        if _observe.journal_on:
            # after the lock: the journal takes its own
            _observe.emit("compile", event["rid"], kernel=kernel,
                          shape=shape_key, ms=event["ms"],
                          persistent=event["persistent"])
        stats = self.stats
        if stats is not None:
            try:
                stats.with_tags(f"kernel:{kernel}").histogram(
                    "compile.ms", ns / 1e6)
            except Exception:  # noqa: BLE001 — telemetry never raises
                pass

    def note_oom_retry(self) -> None:
        """One RESOURCE_EXHAUSTED launch recovered by evict-and-retry
        (device.oom_retries)."""
        with self._lock:
            self.oom_retries += 1

    def note_transfer(self, nbytes: int, chunks: int,
                      label: str = "other") -> None:
        """One host→device staging put of ``nbytes`` in ``chunks``
        pieces, attributed to ``label`` (the owning cache)."""
        if not self.enabled:
            return
        with self._lock:
            t = self._transfers.setdefault(label, [0, 0, 0])
            t[0] += nbytes
            t[1] += chunks
            t[2] += 1
            self.transfer_bytes += nbytes
            self.transfer_chunks += chunks
            self.transfer_puts += 1

    # ------------------------------------------------------------- exports

    @staticmethod
    def device_memory() -> list[dict]:
        """Per-device identity plus memory stats where the backend
        reports them (TPU does; CPU returns none — the entry still
        lists the device so the operator sees the topology).  A backend
        that fails to enumerate raises: a device page that quietly
        lists nothing would hide a dead accelerator."""
        import jax

        out = []
        for d in jax.devices():
            entry: dict = {"id": d.id, "platform": d.platform,
                           "kind": d.device_kind}
            ms = d.memory_stats()
            if ms:
                entry["bytesInUse"] = ms.get("bytes_in_use")
                entry["bytesLimit"] = ms.get("bytes_limit")
                entry["peakBytesInUse"] = ms.get("peak_bytes_in_use")
            out.append(entry)
        return out

    def snapshot(self) -> dict:
        """The /debug/devices document: which backend this process is
        on (and whether it computes on the host instead), which native
        libraries loaded, per-kernel/per-shape compiles, per-label
        transfers, residency accounting, device memory."""
        from pilosa_tpu import native_loader
        from pilosa_tpu.runtime import residency
        from pilosa_tpu.runtime.startup import backend_info

        with self._lock:
            kernels = {}
            for kernel, per_shape in self._compiles.items():
                shapes = {
                    key: {"compiles": st.count,
                          "totalMs": round(st.total_ns / 1e6, 3),
                          "lastMs": round(st.last_ns / 1e6, 3)}
                    for key, st in per_shape.items()
                }
                kernels[kernel] = {
                    "compiles": sum(s.count for s in per_shape.values()),
                    "totalMs": round(sum(s.total_ns
                                         for s in per_shape.values())
                                     / 1e6, 3),
                    "shapes": shapes,
                }
            transfers = {
                label: {"bytes": b, "chunks": c, "puts": p}
                for label, (b, c, p) in self._transfers.items()
            }
            out = {
                "enabled": self.enabled,
                "compile": {
                    "total": self.compile_count,
                    "totalMs": round(self.compile_ns / 1e6, 3),
                    "programEvictions": _program_evictions(),
                    "kernels": kernels,
                    # the newest compiles, oldest first; startNs and
                    # endNs on the clock of a record's rootStartNs
                    "events": list(self._events),
                },
                "transfer": {
                    "bytes": self.transfer_bytes,
                    "chunks": self.transfer_chunks,
                    "puts": self.transfer_puts,
                    "byLabel": transfers,
                },
                "oomRetries": self.oom_retries,
            }
        out["backend"] = backend_info()
        out["native"] = native_loader.status()
        out["residency"] = residency.manager().stats()
        # tiered residency: the promotion pool's live state joins the
        # manager's tier split (/debug/devices answers "is the working
        # set over HBM, and is promotion keeping up" in one read)
        out["residency"]["promoter"] = residency.promoter().stats()
        out["devices"] = self.device_memory()
        return out

    def publish_gauges(self, stats) -> None:
        """Push the device.*/compile.*/residency.* gauge families into
        a stats registry — called at /metrics and /debug/vars scrape
        time (so the surface is never stale) and by the background
        sampler (so statsd-only deployments see them too).  Totals are
        gauges, not counters: they are already cumulative here, and
        re-publishing a cumulative value through a counter would
        double-count."""
        from pilosa_tpu.runtime import residency

        with self._lock:
            stats.gauge("compile.count", self.compile_count)
            stats.gauge("compile.cold", self.compile_cold)
            stats.gauge("compile.total_ms",
                        round(self.compile_ns / 1e6, 3))
            # fused-program cache pressure (ops/expr._compiled): a
            # nonzero value means live tree shapes outnumber retained
            # programs and evicted shapes silently re-trace on reuse
            stats.gauge("compile.program_evictions",
                        _program_evictions())
            stats.gauge("device.transfer_bytes", self.transfer_bytes)
            stats.gauge("device.transfer_chunks", self.transfer_chunks)
            stats.gauge("device.transfer_puts", self.transfer_puts)
            stats.gauge("device.oom_retries", self.oom_retries)
        r = residency.manager().stats()
        stats.gauge("residency.usage_bytes", r["total"])
        stats.gauge("residency.budget_bytes", r["budget"])
        stats.gauge("residency.entries", r["entries"])
        stats.gauge("residency.evictions", r["evictions"])
        stats.gauge("residency.admits", r.get("admits", 0))
        stats.gauge("residency.high_water_bytes",
                    r.get("high_water", r["total"]))
        kinds = r.get("kinds") or {}
        # the compressed-vs-dense residency split (roaring-on-TPU
        # container pools vs dense plane tensors, ops/containers.py)
        stats.gauge("residency.dense_bytes", kinds.get("dense", 0))
        stats.gauge("residency.compressed_bytes",
                    kinds.get("compressed", 0))
        # tiered residency (runtime/residency.py): the host/disk tier
        # occupancy, demotion/promotion flow, and degradation counters
        # — residency.tier.* + prefetch.* families, published
        # unconditionally (zeros pre-pressure) so the surfaces are
        # scrape-visible before the first over-HBM working set
        t = r.get("tiers") or {}
        host = t.get("host") or {}
        disk = t.get("disk") or {}
        stats.gauge("residency.tier.host_bytes", host.get("bytes", 0))
        stats.gauge("residency.tier.host_budget_bytes",
                    host.get("budget", 0))
        stats.gauge("residency.tier.host_entries",
                    host.get("entries", 0))
        stats.gauge("residency.tier.disk_bytes", disk.get("bytes", 0))
        stats.gauge("residency.tier.disk_entries",
                    disk.get("entries", 0))
        stats.gauge("residency.tier.demotions", t.get("demotions", 0))
        stats.gauge("residency.tier.hits", t.get("hits", 0))
        stats.gauge("residency.tier.misses", t.get("misses", 0))
        stats.gauge("residency.tier.spills", t.get("spills", 0))
        stats.gauge("residency.tier.disk_hits", t.get("diskHits", 0))
        stats.gauge("residency.tier.fallbacks", t.get("fallbacks", 0))
        stats.gauge("residency.tier.oom_budget_shrinks",
                    t.get("oomBudgetShrinks", 0))
        p = residency.promoter().stats()
        stats.gauge("residency.tier.promotions", p.get("promotions", 0))
        stats.gauge("residency.tier.promotion_failures",
                    p.get("failures", 0))
        stats.gauge("residency.tier.promotion_sheds", p.get("sheds", 0))
        stats.gauge("residency.tier.promote_queue", p.get("queue", 0))
        stats.gauge("prefetch.issued", p.get("prefetchIssued", 0))
        stats.gauge("prefetch.completed",
                    p.get("prefetchCompleted", 0))
        stats.gauge("prefetch.shed", p.get("prefetchShed", 0))
        stats.gauge("prefetch.useful", t.get("prefetchUseful", 0))
        stats.gauge("prefetch.enabled",
                    1 if residency.config().prefetch else 0)
        for d in self.device_memory():
            if d.get("bytesInUse") is None:
                continue
            tagged = stats.with_tags(f"device:{d['id']}",
                                     f"platform:{d['platform']}")
            tagged.gauge("device.bytes_in_use", d["bytesInUse"])
            if d.get("bytesLimit") is not None:
                tagged.gauge("device.bytes_limit", d["bytesLimit"])


def _program_evictions() -> int:
    """Evictions from the fused-program lru cache — imported lazily so
    reading device telemetry never forces the ops stack in."""
    import sys

    expr = sys.modules.get("pilosa_tpu.ops.expr")
    if expr is None:
        return 0
    return expr.program_evictions()


_global = DeviceObserver()
_global_lock = threading.Lock()


def observer() -> DeviceObserver:
    """The process-wide observer (compiles/transfers are process-wide,
    like the residency budget)."""
    return _global


def reset() -> DeviceObserver:
    """Replace the global observer (tests)."""
    global _global
    with _global_lock:
        _global = DeviceObserver()
        return _global


def note_transfer(nbytes: int, chunks: int, label: str = "other") -> None:
    _global.note_transfer(nbytes, chunks, label)


# --------------------------------------------------------------- instrument


def _shape_key(args, kwargs) -> str:
    """Canonical-shape key for one call: dtype[dims] per array operand,
    repr for static scalars — the per-kernel axis compile telemetry is
    bucketed on."""
    parts = []
    for a in args:
        shp = getattr(a, "shape", None)
        if shp is not None:
            parts.append(f"{getattr(a, 'dtype', '?')}"
                         f"[{','.join(str(s) for s in shp)}]")
        else:
            parts.append(repr(a))
    for k in sorted(kwargs):
        parts.append(f"{k}={kwargs[k]!r}")
    return "(" + ", ".join(parts) + ")"


class _InstrumentedJit:
    """Wraps one jitted callable with compile-event detection.

    Fast path (cache hit, observer disabled): one attribute read and at
    most two ``_cache_size`` C calls on top of the dispatch, no lock.

    Detection is the jit cache-size delta around the call: jit only
    grows its cache on a genuine trace+lower+compile, so canonical-form
    aliasing (weak types, distinct-but-equal shapes) can never
    double-count the way a homegrown shape table would.  On jax builds
    without ``_cache_size`` the wrapper falls back to first-seen shape
    keys — approximate: the per-wrapper ``_seen`` set outlives
    ``jax.clear_caches``, so a recompile of an already-seen shape goes
    undetected there (the primary cache-size path has no such blind
    spot).  Concurrent first calls may attribute one compile to two
    threads — compile events are rare and the count stays within ±1 of
    truth, which the telemetry (not billing) use tolerates."""

    __slots__ = ("fn", "name", "_seen", "_has_cache_size")

    def __init__(self, name: str, fn):
        self.fn = fn
        self.name = name
        self._seen: set[str] = set()
        self._has_cache_size = hasattr(fn, "_cache_size")

    def __call__(self, *args, **kwargs):
        obs = _global
        if not obs.enabled:
            return self.fn(*args, **kwargs)
        if self._has_cache_size:
            try:
                s0 = self.fn._cache_size()
            except Exception:  # noqa: BLE001
                s0 = -1
            t0 = time.perf_counter_ns()
            out = self.fn(*args, **kwargs)
            if s0 >= 0:
                try:
                    grew = self.fn._cache_size() > s0
                except Exception:  # noqa: BLE001
                    grew = False
                if grew:
                    t1 = time.perf_counter_ns()
                    obs.note_compile(self.name, _shape_key(args, kwargs),
                                     t1 - t0, t1)
            return out
        key = _shape_key(args, kwargs)
        if key in self._seen:
            return self.fn(*args, **kwargs)
        t0 = time.perf_counter_ns()
        out = self.fn(*args, **kwargs)
        self._seen.add(key)
        t1 = time.perf_counter_ns()
        obs.note_compile(self.name, key, t1 - t0, t1)
        return out

    def __getattr__(self, item):
        # lower(), clear_cache(), _cache_size etc. reach the jit object
        return getattr(self.fn, item)


def instrument(name: str, fn):
    """Wrap a jitted callable so cache-miss compiles are detected,
    timed, and recorded under ``name`` — the one hook every ``_jit_*``
    kernel (ops/bitmap.py, ops/bsi.py, the fused expression programs,
    the Pallas entry points) routes through."""
    if not _listening:
        _listen()
    return _InstrumentedJit(name, fn)


def jit(name: str, fn, **jit_kwargs):
    """``jax.jit`` for a program built in a closure: named for its
    engine first — the compiled program is ``jit_<name>`` with dots as
    underscores (no shape in it, so the names stay few) instead of the
    closure's ``jit_run``, and its operations sit under
    ``jax.named_scope(name)`` — then :func:`instrument`-ed under the
    same name.  The device trace, ``/debug/devices`` and the flight
    record then call one program one thing."""
    import jax

    def named(*args):
        with jax.named_scope(name):
            return fn(*args)

    named.__name__ = named.__qualname__ = name.replace(".", "_")
    return instrument(name, jax.jit(named, **jit_kwargs))


# ------------------------------------------------------------------ sampler


class DeviceSampler:
    """Background gauge loop for the device families ([observe]
    device-sample-interval) — the statsd-shipping analog of scrape-time
    publishing (a pull scraper gets fresh gauges at /metrics anyway;
    push backends need the loop)."""

    def __init__(self, stats, interval: float):
        self.stats = stats
        self.interval = interval
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def start(self) -> None:
        if self.interval <= 0 or self.stats is None:
            return
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="device-sampler")
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                observer().publish_gauges(self.stats)
            except Exception:  # noqa: BLE001 — never take the loop down
                pass

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2)
