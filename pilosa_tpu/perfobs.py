"""Engine observatory: per-launch wall time + bytes-touched accounting,
achieved bandwidth per engine, the cost table, and on-demand device
profiler capture.

The flight recorder (pilosa_tpu.observe) explains where a QUERY spent
its time and devobs explains compile/transfer/memory events — but
neither measures what ROADMAP items 1 and 4 need: steady-state
per-LAUNCH device time and the bytes each engine actually touched, per
engine and per workload shape.  Roaring itself picks container
representations by measured cost (PAPERS.md 1709.07821) and TPU kernel
tuning of exactly our shape — ragged gathers over pooled blocks — is
driven by achieved-bandwidth accounting (PAPERS.md 2604.15464).  This
module is that measurement substrate:

- **Per-launch samples** — every engine dispatch site (dense fused
  ``ops/expr``, container-gather ``expr.evaluate_gathered``, ragged
  tape ``tape.execute``, Pallas VM ``tape.execute_vm``, the mesh
  shard_map variants, and the per-shard host path) brackets its launch
  with :func:`t0` / :func:`sample`.  ``sample`` blocks on the result
  (``jax.block_until_ready`` — compile time is already split out by
  devobs, so steady-state walls are clean after the first call) and
  pairs the wall time with an ANALYTIC bytes-touched estimate from the
  operand shapes: stack words for the dense engines, pooled container
  words gathered plus directory scalars for the compressed ones,
  register files for the interpreters.  bytes/wall yields achieved
  GB/s; against the configured roof (``[observe] device-peak-gbps``,
  defaulted per device kind) that is the ``bw_util`` ``/debug/cost``
  reports.
- **Cost table** — samples feed a process-wide EWMA + deviation table
  keyed (engine, work size-class, sparsity bucket), rendered at
  ``GET /debug/cost`` and summarized per engine in the tagged
  ``engine.*`` gauges.  Nothing routes on it (ROADMAP D4).
- **Profiler capture** — ``POST /debug/profiler/start|stop`` wraps
  ``jax.profiler.start_trace``/``stop_trace`` into a dated artifact
  dir, try-lock 409 on concurrent capture (the /debug/pprof/profile
  discipline) and auto-stop after ``[observe] profiler-max-seconds``.

Lock discipline: the disarmed fast path is ONE module-bool read
(:func:`t0` returns 0 and every sample call gates on it); blocking
(``block_until_ready``) always happens OUTSIDE the module lock, which
only covers the table/counter writes.  With the observatory off a
fused Count takes neither lock and records no sample
(``tests/test_observer_cost.py``).
"""

from __future__ import annotations

import math
import os
import threading
import time
from typing import Any

from pilosa_tpu import observe as _observe

#: The canonical engine taxonomy — the one ``engine`` enum the flight
#: record and /debug/cost share.
ENGINES = ("dense", "gather", "tape", "vm", "mesh", "host",
           "collective", "gather_aa", "gather_ab", "gather_kinds",
           "vm_kinds")

#: EWMA smoothing for wall/bytes/bandwidth per cell.
ALPHA = 0.2

#: Injectable monotonic clock (tests drive the cost-table math under a
#: fake clock by monkeypatching this).
_clock = time.perf_counter_ns

#: HBM roof (GB/s) per jax ``device_kind`` substring, checked in
#: order — datasheet figures (an operator with exact numbers sets
#: ``[observe] device-peak-gbps``).  A kind that is not listed — a CPU
#: host included — has NO roof here: utilization is then reported as
#: absent (``None``), never against an assumed peak.
KIND_PEAKS: tuple[tuple[str, float], ...] = (
    ("v5e", 819.0), ("v5 lite", 819.0), ("v5p", 2765.0),
    ("v6", 1640.0), ("v5", 2765.0), ("v4", 1228.0), ("v3", 900.0),
    ("v2", 700.0),
)


# ---------------------------------------------------------------- runtime cfg


class PerfobsRuntimeConfig:
    """Process-wide observatory knobs (``[observe]``)."""

    __slots__ = ("enabled", "peak_gbps", "profiler_max_seconds")

    def __init__(self, enabled: bool = True, peak_gbps: float = 0.0,
                 profiler_max_seconds: float = 30.0):
        self.enabled = enabled
        self.peak_gbps = peak_gbps  # 0 = default per device kind
        self.profiler_max_seconds = profiler_max_seconds


_cfg = PerfobsRuntimeConfig()
_cfg_lock = threading.Lock()
_baseline: PerfobsRuntimeConfig | None = None
_refs = 0
#: Module-bool fast gate mirroring ``config().enabled`` — the per-call
#: cost of a disabled observatory is one attribute read (the
#: faultinject.armed discipline).
enabled = True


def config() -> PerfobsRuntimeConfig:
    with _cfg_lock:
        return _cfg


def configure(enabled_: bool | None = None,
              peak_gbps: float | None = None,
              profiler_max_seconds: float | None = None) -> None:
    """Apply explicit values only (the containers.configure rule: an
    absent kwarg leaves the knob untouched)."""
    global enabled, _peak_cached
    with _cfg_lock:
        if enabled_ is not None:
            _cfg.enabled = enabled_
        if peak_gbps is not None:
            _cfg.peak_gbps = peak_gbps
        if profiler_max_seconds is not None:
            _cfg.profiler_max_seconds = profiler_max_seconds
        enabled = _cfg.enabled
        _peak_cached = None


def retain() -> None:
    """First retain snapshots the baseline config (server open)."""
    global _refs, _baseline
    with _cfg_lock:
        if _refs == 0:
            _baseline = PerfobsRuntimeConfig(
                _cfg.enabled, _cfg.peak_gbps, _cfg.profiler_max_seconds)
        _refs += 1


def release() -> None:
    """Last release restores the baseline (server close) — paired with
    :func:`retain`."""
    global _refs, _baseline, enabled, _peak_cached
    with _cfg_lock:
        if _refs == 0:
            return
        _refs -= 1
        if _refs == 0 and _baseline is not None:
            _cfg.enabled = _baseline.enabled
            _cfg.peak_gbps = _baseline.peak_gbps
            _cfg.profiler_max_seconds = _baseline.profiler_max_seconds
            _baseline = None
            enabled = _cfg.enabled
            _peak_cached = None


def reset() -> None:
    """Restore defaults and drop all samples/counters (tests)."""
    global _cfg, _baseline, _refs, enabled, _peak_cached
    with _cfg_lock:
        _cfg = PerfobsRuntimeConfig()
        _baseline = None
        _refs = 0
        enabled = True
        _peak_cached = None
    with _lock:
        _table.clear()
        for k in _counters:
            _counters[k] = 0


#: 0.0 caches "this device kind has no known roof"
_peak_cached: float | None = None


def device_peak_gbps() -> float | None:
    """The configured bandwidth roof, else the ``KIND_PEAKS`` entry for
    this process's ``device_kind``, else ``None`` (no roof known: no
    utilization figure) — cached until the next configure/reset (jax
    device lookup is not free and this is read per sample)."""
    global _peak_cached
    p = _peak_cached
    if p is not None:
        return p or None
    with _cfg_lock:
        explicit = _cfg.peak_gbps
    if explicit > 0:
        _peak_cached = explicit
        return explicit
    import jax

    kind = (jax.devices()[0].device_kind or "").lower()
    peak = 0.0
    for sub, gbps in KIND_PEAKS:
        if sub in kind:
            peak = gbps
            break
    _peak_cached = peak
    return peak or None


def _bw_util(gbps: float, peak: float | None) -> float | None:
    return round(gbps / peak, 4) if peak else None


# ------------------------------------------------------------------- counters

_lock = threading.Lock()
_counters = {
    "engine.launches": 0,       # sampled steady-state launches
    "engine.bytes": 0,          # analytic bytes across sampled launches
    "cost.samples": 0,          # cost-table sample insertions
    "cost.profiles": 0,         # completed profiler captures
    # sampled launches whose ONE wait was the fetch of their counts
    # (sample's ``fetch``), and launches of expr.evaluate(counts=True)
    # whose counts were asked of the device AGAIN after the wait
    # (expr.counts_to_host): a second round trip a read
    "launch.fetched": 0,
    "launch.refetched": 0,
}


def bump(name: str, value: int = 1) -> None:
    with _lock:
        _counters[name] += value


def counters() -> dict[str, int]:
    with _lock:
        return dict(_counters)


def publish_gauges(stats: Any) -> None:
    """Push the engine.*/cost.* families into a stats registry at
    scrape time — cumulative totals as GAUGES (the tape/devobs rule:
    re-publishing a cumulative value through a counter double-counts).
    Per-engine achieved bandwidth rides engine tags."""
    with _lock:
        snap = dict(_counters)
        cells = len(_table)
    for name, value in snap.items():
        stats.gauge(name, value)
    stats.gauge("cost.cells", cells)
    # 0 = no roof known for this device kind (then no bw_util either)
    stats.gauge("engine.peak_gbps", device_peak_gbps() or 0.0)
    for eng, s in engine_summary().items():
        tagged = stats.with_tags(f"engine:{eng}")
        tagged.gauge("engine.wall_us", s["wallUs"])
        tagged.gauge("engine.gbps", s["gbps"])
        if s["bwUtil"] is not None:
            tagged.gauge("engine.bw_util", s["bwUtil"])


# ----------------------------------------------------------------- cost table


class _Cell:
    """One (engine, size-class, sparsity-bucket) cost cell: EWMA wall
    time with an EWMA absolute deviation (the hedging estimator's
    shape, parallel/executor.py), plus bytes and achieved GB/s."""

    __slots__ = ("count", "ewma_us", "dev_us", "ewma_bytes",
                 "ewma_gbps", "last_us")

    def __init__(self):
        self.count = 0
        self.ewma_us = 0.0
        self.dev_us = 0.0
        self.ewma_bytes = 0.0
        self.ewma_gbps = 0.0
        self.last_us = 0.0

    def add(self, wall_us: float, nbytes: int, gbps: float) -> None:
        if self.count == 0:
            self.ewma_us = wall_us
            self.ewma_bytes = float(nbytes)
            self.ewma_gbps = gbps
        else:
            self.dev_us += ALPHA * (abs(wall_us - self.ewma_us)
                                    - self.dev_us)
            self.ewma_us += ALPHA * (wall_us - self.ewma_us)
            self.ewma_bytes += ALPHA * (nbytes - self.ewma_bytes)
            self.ewma_gbps += ALPHA * (gbps - self.ewma_gbps)
        self.count += 1
        self.last_us = wall_us


_table: dict[tuple[str, str, str], _Cell] = {}


def size_class(work: int) -> str:
    """Pow2 size-class label for a launch's work (uint32 words read by
    a dense-equivalent evaluation) — "2^14" etc., so similar workloads
    share a cell instead of every exact shape owning one."""
    if work <= 1:
        return "2^0"
    return f"2^{int(math.ceil(math.log2(work)))}"


def sparsity_bucket(sparsity: float) -> str:
    """Coarse bucket of bytes-touched / dense-equivalent-bytes: the
    compressed engines win exactly as this falls, so it is the second
    cost-table axis."""
    if sparsity <= 0.0:
        return "0"
    if sparsity < 0.01:
        return "<1%"
    if sparsity < 0.1:
        return "<10%"
    if sparsity < 0.5:
        return "<50%"
    return ">=50%"


# ------------------------------------------------------- launch-scope context


_tls = threading.local()


class context:
    """Attribute launches sampled on this thread: the orchestration
    layer (executor per-shard map, coalescer flush) knows the engine
    taxonomy slot, the data sparsity, and the dense-equivalent work;
    the ops layer only knows its own operands.  Scopes nest (inner
    shadows)."""

    __slots__ = ("engine", "sparsity", "work", "_prev")

    def __init__(self, engine: str | None = None,
                 sparsity: float | None = None,
                 work: int | None = None):
        self.engine = engine
        self.sparsity = sparsity
        self.work = work

    def __enter__(self) -> "context":
        self._prev = getattr(_tls, "ctx", None)
        _tls.ctx = self
        return self

    def __exit__(self, *exc) -> bool:
        _tls.ctx = self._prev
        return False


def _ctx() -> "context | None":
    return getattr(_tls, "ctx", None)


# ------------------------------------------------------------------- sampling


def t0() -> Any:
    """Launch-bracket start, handed to :func:`sample`.  With a flight
    record on this thread it is the record's ``launch.dispatch`` span,
    entered — the bracket and the span share their clock reads; with
    none it is the clock when the observatory is on and 0 when off, so
    a disabled observatory costs one module-bool read per launch."""
    sp = _observe.span("launch.dispatch")
    if sp is _observe.NOSPAN:
        return _clock() if enabled else 0
    return sp.__enter__()


def sample(engine: str, out: Any, t0_ns: Any, nbytes: int,
           work: int = 0, sparsity: float = 1.0,
           fetch: Any = None) -> Any:
    """Complete one launch sample: block on ``out`` (OUTSIDE any lock
    — the P3 rule), then fold wall/bytes/bandwidth into the cost table
    and stamp the engine onto the active flight record.  When
    :func:`t0` opened a ``launch.dispatch`` span, the jitted call has
    returned: close it and wait inside ``launch.ready``.

    ``nbytes`` — analytic bytes the launch touched (operand reads +
    result writes); ``work`` — dense-equivalent uint32 words for the
    size-class key (defaults to nbytes/4); ``sparsity`` — bytes
    touched / dense-equivalent bytes (1.0 for the dense engines).  A
    thread-local :class:`context` overrides engine/sparsity when the
    orchestration layer knows better than the ops layer.

    ``fetch`` — the caller's own wait, for a launch whose result it
    wants on the host: ``fetch(out)`` is called exactly once IN PLACE
    OF the block and its value returned, so the launch synchronises
    with the device once and the wait this sample times is the fetch
    itself (``launch.ready`` notes ``fetched=1``).  It is the
    caller's: with nothing observing it is still called, here, and
    what it raises is the query's error, not telemetry's."""
    if not t0_ns:
        return None if fetch is None else fetch(out)
    dispatch = None if isinstance(t0_ns, int) else t0_ns
    if dispatch is not None:
        dispatch.__exit__(None, None, None)
        if not enabled:
            return None if fetch is None else fetch(out)
    ctx = _ctx()
    if ctx is not None:
        if ctx.engine is not None:
            engine = ctx.engine
        if ctx.sparsity is not None:
            sparsity = ctx.sparsity
        if ctx.work is not None:
            work = ctx.work
    wait = _block if fetch is None else fetch
    if dispatch is None:
        got = wait(out)
        wall_ns = _clock() - t0_ns
    else:
        noted = {} if fetch is None else {"fetched": 1}
        with _observe.span("launch.ready", start_ns=dispatch.end_ns,
                           **noted) as ready:
            got = wait(out)
        wall_ns = ready.end_ns - dispatch.start_ns
    record_sample(engine, wall_ns, nbytes, work, sparsity,
                  fetched=fetch is not None)
    rec = _observe.current()
    if rec is not None:
        rec.note_engine(engine)
    return got


def launch(engine: str, fn: Any) -> Any:
    """Bracket one raw-kernel dispatch that passes no engine sample
    site (the TopN matrix scan, a GroupBy level, the BSI plane ops, a
    range compare): the flight record's ``launch`` span with its
    ``launch.dispatch`` / ``launch.ready`` children, and the engine
    stamp.  No cost-table sample: the table holds the Count engines
    only.  With no record it is just ``fn()``."""
    rec = _observe.current()
    if rec is None:
        return fn()
    with _observe.span("launch", engine=engine):
        with _observe.span("launch.dispatch") as dispatch:
            out = fn()
        with _observe.span("launch.ready", start_ns=dispatch.end_ns):
            _block(out)
    rec.note_engine(engine)
    return out


def _block(out: Any) -> None:
    try:
        import jax

        jax.block_until_ready(out)
    except Exception:  # noqa: BLE001 — telemetry never fails a query
        pass


def record_sample(engine: str, wall_ns: int, nbytes: int,
                  work: int = 0, sparsity: float = 1.0,
                  fetched: bool = False) -> None:
    """Fold one measured launch into the cost table (the pure math
    under :func:`sample` — tests drive it directly with a fake
    clock).  ``fetched``: the wait was the fetch of the result."""
    wall_us = wall_ns / 1e3
    gbps = ((nbytes / (wall_ns / 1e9)) / 1e9) if wall_ns > 0 else 0.0
    key = (engine, size_class(work if work > 0 else max(1, nbytes // 4)),
           sparsity_bucket(sparsity))
    with _lock:
        cell = _table.get(key)
        if cell is None:
            cell = _table[key] = _Cell()
        cell.add(wall_us, nbytes, gbps)
        _counters["engine.launches"] += 1
        _counters["engine.bytes"] += nbytes
        _counters["cost.samples"] += 1
        if fetched:
            _counters["launch.fetched"] += 1


# ------------------------------------------------------------------- exports


def engine_summary() -> dict[str, dict]:
    """Per-engine rollup of the cost table (sample-count-weighted):
    ``/debug/cost`` ``engines`` and the tagged engine.* gauges."""
    peak = device_peak_gbps()
    out: dict[str, dict] = {}
    with _lock:
        for (eng, _s, _sp), cell in _table.items():
            agg = out.setdefault(eng, {"launches": 0, "_us": 0.0,
                                       "_bytes": 0.0, "_gbps": 0.0})
            agg["launches"] += cell.count
            agg["_us"] += cell.ewma_us * cell.count
            agg["_bytes"] += cell.ewma_bytes * cell.count
            agg["_gbps"] += cell.ewma_gbps * cell.count
    for eng, agg in out.items():
        n = max(1, agg["launches"])
        gbps = agg.pop("_gbps") / n
        agg["wallUs"] = round(agg.pop("_us") / n, 3)
        agg["bytes"] = int(agg.pop("_bytes") / n)
        agg["gbps"] = round(gbps, 3)
        agg["bwUtil"] = _bw_util(gbps, peak)
    return out


def cost_debug() -> dict:
    """The GET /debug/cost document: config, counters, the per-cell
    cost table, and the per-engine rollup."""
    peak = device_peak_gbps()
    cfg = config()
    with _lock:
        rows = [
            {"engine": eng, "size": size, "sparsity": sp,
             "samples": c.count, "wallUs": round(c.ewma_us, 3),
             "devUs": round(c.dev_us, 3),
             "bytes": int(c.ewma_bytes), "gbps": round(c.ewma_gbps, 3),
             "bwUtil": _bw_util(c.ewma_gbps, peak),
             "lastUs": round(c.last_us, 3)}
            for (eng, size, sp), c in sorted(_table.items())
        ]
        snap = dict(_counters)
    return {
        "enabled": cfg.enabled,
        "peakGbps": peak,
        "counters": snap,
        "engines": engine_summary(),
        "table": rows,
        "profiler": profiler_status(),
    }


def debug() -> dict:
    """Alias kept symmetric with the other observability modules."""
    return cost_debug()


# ----------------------------------------------------------- profiler capture


class ProfilerBusy(RuntimeError):
    """A device-profiler capture is already active (handler -> 409)."""


class ProfilerIdle(RuntimeError):
    """Stop requested with no active capture (handler -> 409)."""


#: Held (non-blocking acquire) for the whole start..stop window — the
#: /debug/pprof/profile discipline: a concurrent start is a 409, never
#: a queued second capture.  A plain Lock deliberately: stop may run on
#: a different HTTP thread (or the auto-stop timer) than start.
_prof_lock = threading.Lock()
#: Tiny mutex over the capture bookkeeping (dir/since/timer) so status
#: reads and the manual-stop/auto-stop race stay consistent.
_prof_state_lock = threading.Lock()
_prof: dict[str, Any] = {"active": False, "dir": None, "since": 0.0,
                         "timer": None, "auto_stopped": False}


def profiler_start(base_dir: str,
                   max_seconds: float | None = None) -> dict:
    """Begin a device trace into a dated artifact dir under
    ``base_dir`` (``profiles/trace_<UTCSTAMP>``).  Raises
    :class:`ProfilerBusy` when a capture is already active; arms an
    auto-stop timer after ``max_seconds`` (default ``[observe]
    profiler-max-seconds``; 0 disables) so a forgotten capture cannot
    trace forever."""
    if not _prof_lock.acquire(blocking=False):
        raise ProfilerBusy("a profiler capture is already active")
    try:
        stamp = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
        out_dir = os.path.join(base_dir, "profiles", f"trace_{stamp}")
        os.makedirs(out_dir, exist_ok=True)
        import jax

        # the host tracer records the spans' TraceAnnotations; the
        # Python tracer would record every call of every thread and
        # slow the traced server a hundredfold (PERF.md, PR 23: p90
        # 2.4 s inside the capture against 19 ms outside it)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(out_dir, profiler_options=options)
        clocks = _two_clocks()
        _observe.set_capturing(True)
    except BaseException:
        _prof_lock.release()
        raise
    limit = (max_seconds if max_seconds is not None
             else config().profiler_max_seconds)
    timer = None
    if limit and limit > 0:
        timer = threading.Timer(limit, _profiler_auto_stop)
        timer.daemon = True
    with _prof_state_lock:
        _prof["active"] = True
        _prof["dir"] = out_dir
        _prof["since"] = time.time()
        _prof["timer"] = timer
        _prof["auto_stopped"] = False
    if timer is not None:
        timer.start()
    return {"dir": out_dir, "maxSeconds": limit, **clocks}


def profiler_stop() -> dict:
    """End the active capture: stop the jax trace, cancel the
    auto-stop timer, release the capture lock, and return the artifact
    dir + duration.  Raises :class:`ProfilerIdle` when nothing is
    active (the manual-stop/auto-stop race resolves here: whoever
    flips ``active`` first wins, the loser is told idle)."""
    with _prof_state_lock:
        if not _prof["active"]:
            raise ProfilerIdle("no active profiler capture")
        _prof["active"] = False
        out_dir = _prof["dir"]
        since = _prof["since"]
        timer = _prof["timer"]
        _prof["timer"] = None
    if timer is not None:
        timer.cancel()
    _observe.set_capturing(False)
    clocks = _two_clocks()
    try:
        import jax

        jax.profiler.stop_trace()
    except Exception:  # noqa: BLE001 — the lock must release regardless
        pass
    finally:
        _prof_lock.release()
    bump("cost.profiles")
    return {"dir": out_dir,
            "seconds": round(time.time() - since, 3), **clocks}


def _two_clocks() -> dict:
    """The span clock and the wall clock, read together next to the
    ``start_trace``/``stop_trace`` call: ``perfCounterNs`` places a
    flight record's spans (``rootStartNs`` + ``startNs``) on the
    capture without opening the trace, ``unixNs`` places the capture
    in the world."""
    return {"perfCounterNs": time.perf_counter_ns(),
            "unixNs": time.time_ns()}


def _profiler_auto_stop() -> None:
    """Timer body: stop an over-deadline capture; losing the race to a
    manual stop is fine (ProfilerIdle swallowed)."""
    try:
        profiler_stop()
        with _prof_state_lock:
            _prof["auto_stopped"] = True
    except ProfilerIdle:
        pass
    except Exception:  # noqa: BLE001 — a timer thread must not die loud
        pass


def profiler_status() -> dict:
    """Live capture state for /debug/cost and the profiler routes."""
    with _prof_state_lock:
        if not _prof["active"]:
            return {"active": False,
                    "autoStopped": _prof["auto_stopped"],
                    "lastDir": _prof["dir"]}
        return {"active": True, "dir": _prof["dir"],
                "seconds": round(time.time() - _prof["since"], 3)}
