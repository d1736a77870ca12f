"""Stats: counters/gauges/histograms with tag scoping and pluggable
backends.

Parity target: the reference's stats package (stats/stats.go:31
StatsClient interface; :84 expvar impl; :164 multi fan-out) and the
prometheus adapter (prometheus/prometheus.go:40) — collapsed here into
one in-process registry that can render both the /debug/vars JSON
snapshot and the /metrics Prometheus text exposition
(http/handler.go:280-282).

Timings and histograms record into FIXED-BUCKET latency histograms
(a 1/2.5/5-per-decade ladder wide enough for both nanosecond timings
and small occupancy counts), rendered as the native Prometheus
``histogram`` type — cumulative ``_bucket`` lines with optional
OpenMetrics-style trace-id exemplars — and summarized with
p50/p95/p99 estimates in the /debug/vars snapshot.  The strict
exposition checker (tools/check_metrics.py) validates the rendering
in CI."""

from __future__ import annotations

import bisect
import threading
import time
from collections import defaultdict


class StatsClient:
    """Interface (stats/stats.go:31).  Tag scoping via with_tags returns
    a child client that stamps every metric."""

    def count(self, name: str, value: int = 1, rate: float = 1.0) -> None:
        pass

    def count_with_tags(self, name: str, value: int, rate: float,
                        tags: list[str]) -> None:
        pass

    def gauge(self, name: str, value: float, rate: float = 1.0) -> None:
        pass

    def histogram(self, name: str, value: float, rate: float = 1.0,
                  exemplar: str | None = None) -> None:
        pass

    def set(self, name: str, value: str, rate: float = 1.0) -> None:
        pass

    def timing(self, name: str, value_ns: float, rate: float = 1.0,
               exemplar: str | None = None) -> None:
        pass

    def with_tags(self, *tags: str) -> "StatsClient":
        return self

    def tags(self) -> list[str]:
        return []

    def settle(self, ops: list) -> None:
        """Write one read's collected metrics (:class:`Batch`): ``ops``
        holds ``(client, kind, name, tags, value, exemplar)`` with kind
        ``c`` (count), ``h`` (histogram) or ``t`` (timing, ns); the
        client is the batch's own business.  The same values the
        single calls would have written; a registry takes its lock
        once for all of them."""
        for _, kind, name, tags, value, exemplar in ops:
            if kind == "c":
                if tags:
                    self.count_with_tags(name, value, 1.0, tags)
                else:
                    self.count(name, value)
            elif kind == "h":
                self.histogram(name, value, exemplar=exemplar)
            else:
                self.timing(name, value, exemplar=exemplar)


#: Shared no-op (reference NopStatsClient)
NOP = StatsClient()


class Batch:
    """One read's counters, histograms and timings, collected where
    they arise (``Executor.execute``, the coalescer's flush and wait)
    and written in ONE place at the end of the read's ``exec`` span
    (``Executor.execute``): a registry's lock is taken once for them,
    not once a metric.  Each entry names its client, since an
    executor, its coalescer and its recorder may each have been given
    their own; on a server they are one.  Owned by one thread."""

    __slots__ = ("ops",)

    def __init__(self) -> None:
        self.ops: list[tuple] = []

    def count(self, client: StatsClient, name: str, value: int = 1,
              tags: tuple = ()) -> None:
        self.ops.append((client, "c", name, tags, value, None))

    def histogram(self, client: StatsClient, name: str, value: float,
                  exemplar: str | None = None) -> None:
        self.ops.append((client, "h", name, (), value, exemplar))

    def timing(self, client: StatsClient, name: str, value_ns: float,
               exemplar: str | None = None) -> None:
        self.ops.append((client, "t", name, (), value_ns, exemplar))

    def timer(self, client: StatsClient) -> "_BatchTimer":
        """What ``observe.span(timer=(this, name))`` feeds a span's
        duration to, in place of the client itself."""
        return _BatchTimer(self, client)

    def settle(self) -> None:
        """Write everything collected, in order, and start over."""
        ops, self.ops = self.ops, []
        while ops:
            client = ops[0][0]
            rest = [op for op in ops if op[0] is not client]
            if rest:  # more than one client: a test's, not a server's
                ops = [op for op in ops if op[0] is client]
            if client is not NOP:
                client.settle(ops)
            ops = rest


class _BatchTimer:
    __slots__ = ("batch", "client")

    def __init__(self, batch: Batch, client: StatsClient) -> None:
        self.batch = batch
        self.client = client

    def timing(self, name: str, value_ns: float) -> None:
        self.batch.timing(self.client, name, value_ns)


class MemStatsClient(StatsClient):
    """In-memory registry backend — the expvar + prometheus roles in one
    (stats/stats.go:84, prometheus/prometheus.go:40)."""

    def __init__(self, registry: "_Registry | None" = None,
                 _tags: tuple[str, ...] = ()):
        self._registry = registry or _Registry()
        self._tags = tuple(sorted(_tags))

    # ------------------------------------------------------------ metrics

    def count(self, name, value=1, rate=1.0):
        self._registry.add_counter(name, self._tags, value)

    def count_with_tags(self, name, value, rate, tags):
        all_tags = tuple(sorted({*self._tags, *tags}))
        self._registry.add_counter(name, all_tags, value)

    def gauge(self, name, value, rate=1.0):
        self._registry.set_gauge(name, self._tags, value)

    def histogram(self, name, value, rate=1.0, exemplar=None):
        self._registry.observe(name, self._tags, value, exemplar)

    def set(self, name, value, rate=1.0):
        self._registry.set_gauge(f"{name}.{value}", self._tags, 1)

    def timing(self, name, value_ns, rate=1.0, exemplar=None):
        self._registry.observe(name, self._tags, value_ns, exemplar)

    def with_tags(self, *tags):
        return MemStatsClient(self._registry, (*self._tags, *tags))

    def settle(self, ops):
        self._registry.settle(ops, self._tags)

    def tags(self):
        return list(self._tags)

    # ----------------------------------------------------------- exports

    def snapshot(self) -> dict:
        return self._registry.snapshot()

    def prometheus_text(self, exemplars: bool = False) -> str:
        return self._registry.prometheus_text(exemplars)


class MultiStatsClient(StatsClient):
    """Fan-out to several backends (stats/stats.go:164)."""

    def __init__(self, clients: list[StatsClient]):
        self.clients = list(clients)

    def count(self, name, value=1, rate=1.0):
        for c in self.clients:
            c.count(name, value, rate)

    def count_with_tags(self, name, value, rate, tags):
        for c in self.clients:
            c.count_with_tags(name, value, rate, tags)

    def gauge(self, name, value, rate=1.0):
        for c in self.clients:
            c.gauge(name, value, rate)

    def histogram(self, name, value, rate=1.0, exemplar=None):
        for c in self.clients:
            c.histogram(name, value, rate, exemplar=exemplar)

    def set(self, name, value, rate=1.0):
        for c in self.clients:
            c.set(name, value, rate)

    def timing(self, name, value_ns, rate=1.0, exemplar=None):
        for c in self.clients:
            c.timing(name, value_ns, rate, exemplar=exemplar)

    def with_tags(self, *tags):
        return MultiStatsClient([c.with_tags(*tags) for c in self.clients])

    def settle(self, ops):
        for c in self.clients:
            c.settle(ops)

    def snapshot(self) -> dict:
        """Merged view across EVERY snapshot-capable backend, so a
        fan-out with two registries surfaces both key spaces (the old
        behavior returned only the first capable backend).  Like
        prometheus_text(), this assumes disjoint metric names per
        registry; on a collision the first backend's value wins."""
        out: dict = {}
        for c in self.clients:
            if hasattr(c, "snapshot"):
                for k, v in c.snapshot().items():
                    out.setdefault(k, v)
        return out

    def prometheus_text(self, exemplars: bool = False) -> str:
        """Concatenated exposition across every capable backend, with
        repeated ``# TYPE`` lines dropped so two registries sharing a
        metric name cannot produce the duplicate-TYPE exposition strict
        scrapers reject.  (Samples themselves are not merged: fan-out
        deployments keep disjoint metric names per registry; the server
        assembly wires exactly one MemStatsClient.)"""
        lines: list[str] = []
        seen_types: set[str] = set()
        for c in self.clients:
            if not hasattr(c, "prometheus_text"):
                continue
            for line in c.prometheus_text(exemplars).splitlines():
                if line.startswith("# TYPE "):
                    if line in seen_types:
                        continue
                    seen_types.add(line)
                lines.append(line)
        return "\n".join(lines) + ("\n" if lines else "")


#: Histogram bucket upper bounds: 1 / 2.5 / 5 per decade from 1e-6 to
#: 5e9 — one fixed ladder wide enough for second-scale latencies
#: (pilosa_query_latency), nanosecond timings (observe.span feeds ns), and
#: small value histograms (coalescer batch occupancy 1..32).  Fixed
#: buckets keep observe() O(log B) with no per-metric configuration.
BUCKETS: tuple[float, ...] = tuple(
    m * (10.0 ** e) for e in range(-6, 10) for m in (1.0, 2.5, 5.0))

#: Quantiles reported in the /debug/vars snapshot.
_QUANTILES = (("p50", 0.50), ("p95", 0.95), ("p99", 0.99))


class _Hist:
    """One (name, tagset) histogram: count/sum/min/max plus per-bucket
    counts and the last exemplar seen per bucket (trace id, value,
    unix seconds) — the hot-bucket -> trace linkage."""

    __slots__ = ("n", "total", "mn", "mx", "counts", "exemplars")

    def __init__(self):
        self.n = 0
        self.total = 0.0
        self.mn = float("inf")
        self.mx = float("-inf")
        # one slot per bound + the +Inf overflow slot
        self.counts = [0] * (len(BUCKETS) + 1)
        self.exemplars: dict[int, tuple[str, float, float]] = {}

    def observe(self, value: float, exemplar: str | None) -> None:
        self.n += 1
        self.total += value
        self.mn = min(self.mn, value)
        self.mx = max(self.mx, value)
        i = bisect.bisect_left(BUCKETS, value)
        self.counts[i] += 1
        if exemplar is not None:
            self.exemplars[i] = (exemplar, value, time.time())

    def quantile(self, q: float) -> float:
        """Estimate by linear interpolation inside the bucket holding
        rank q*n, clamped to the observed [min, max] — the pinned math
        of tests/test_observe.py."""
        if self.n == 0:
            return 0.0
        target = q * self.n
        cum = 0
        for i, c in enumerate(self.counts):
            if c == 0:
                continue
            if cum + c >= target:
                lo = BUCKETS[i - 1] if i > 0 else 0.0
                hi = BUCKETS[i] if i < len(BUCKETS) else self.mx
                v = lo + (hi - lo) * ((target - cum) / c)
                return min(max(v, self.mn), self.mx)
            cum += c
        return self.mx


class _Registry:
    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[tuple, float] = defaultdict(float)
        self._gauges: dict[tuple, float] = {}
        self._hists: dict[tuple, _Hist] = {}

    def add_counter(self, name, tags, value):
        with self._lock:
            self._counters[(name, tags)] += value

    def set_gauge(self, name, tags, value):
        with self._lock:
            self._gauges[(name, tags)] = value

    def observe(self, name, tags, value, exemplar=None):
        with self._lock:
            h = self._hists.get((name, tags))
            if h is None:
                h = self._hists[(name, tags)] = _Hist()
            h.observe(value, exemplar)

    def settle(self, ops, base: tuple = ()) -> None:
        """One read's metrics (``StatsClient.settle``'s ``ops``, each
        under the client's ``base`` tags and its own) under one take
        of the lock."""
        counters, hists = self._counters, self._hists
        with self._lock:
            for _, kind, name, tags, value, exemplar in ops:
                key = (name, tuple(sorted({*base, *tags})) if tags
                       else base)
                if kind == "c":
                    counters[key] += value
                    continue
                h = hists.get(key)
                if h is None:
                    h = hists[key] = _Hist()
                h.observe(value, exemplar)

    def snapshot(self) -> dict:
        with self._lock:
            out = {}
            for (name, tags), v in self._counters.items():
                out[_flat(name, tags)] = v
            for (name, tags), v in self._gauges.items():
                out[_flat(name, tags)] = v
            for (name, tags), h in self._hists.items():
                entry = {"count": h.n, "sum": h.total,
                         "min": h.mn, "max": h.mx}
                for label, q in _QUANTILES:
                    entry[label] = h.quantile(q)
                out[_flat(name, tags)] = entry
            return out

    def prometheus_text(self, exemplars: bool = False) -> str:
        """Prometheus 0.0.4 text exposition; tag "k:v" -> label k="v"
        (the reference's tag translation, prometheus/prometheus.go:120).
        Each ``# TYPE`` is emitted ONCE per metric name (a second
        tagset must not repeat it — strict scrapers reject duplicate
        TYPE lines).  Histograms render natively: sparse cumulative
        ``_bucket`` lines (buckets a value landed in, plus ``+Inf``),
        ``_sum``/``_count``.

        ``exemplars=True`` appends OpenMetrics-style trace-id
        exemplars to the buckets that have one.  OFF by default: the
        legacy 0.0.4 parser (a stock Prometheus scrape) rejects the
        trailing ``# {...}``, so the handler only enables it when the
        client negotiates OpenMetrics (or asks with ``?exemplars=1``)."""
        lines = []
        with self._lock:
            last = None
            for (name, tags), v in sorted(self._counters.items()):
                m = _prom_name(name)
                if m != last:
                    lines.append(f"# TYPE {m} counter")
                    last = m
                lines.append(f"{m}{_prom_labels(tags)} {v}")
            last = None
            for (name, tags), v in sorted(self._gauges.items()):
                m = _prom_name(name)
                if m != last:
                    lines.append(f"# TYPE {m} gauge")
                    last = m
                lines.append(f"{m}{_prom_labels(tags)} {v}")
            last = None
            for (name, tags), h in sorted(self._hists.items()):
                m = _prom_name(name)
                if m != last:
                    lines.append(f"# TYPE {m} histogram")
                    last = m
                cum = 0
                for i, c in enumerate(h.counts):
                    inf = i == len(BUCKETS)
                    if c == 0 and not inf:
                        continue  # sparse: unchanged cumulative buckets
                    cum += c
                    le = "+Inf" if inf else f"{BUCKETS[i]:g}"
                    line = (f"{m}_bucket"
                            f"{_prom_labels(tags, ('le', le))} {cum}")
                    ex = h.exemplars.get(i) if exemplars else None
                    if ex is not None:
                        tid, val, ts = ex
                        line += (f' # {{trace_id="{tid}"}} '
                                 f"{val:g} {ts:.3f}")
                    lines.append(line)
                lines.append(f"{m}_sum{_prom_labels(tags)} {h.total}")
                lines.append(f"{m}_count{_prom_labels(tags)} {h.n}")
        return "\n".join(lines) + ("\n" if lines else "")


def _flat(name: str, tags: tuple) -> str:
    return name if not tags else f"{name}[{','.join(tags)}]"


def _prom_name(name: str) -> str:
    out = "".join(ch if ch.isalnum() or ch == "_" else "_" for ch in name)
    return out if not out[:1].isdigit() else "_" + out


def _prom_labels(tags: tuple, extra: tuple[str, str] | None = None) -> str:
    if not tags and extra is None:
        return ""
    pairs = []
    for t in tags:
        k, _, v = t.partition(":")
        v = v.replace("\\", "\\\\").replace('"', '\\"')
        pairs.append(f'{_prom_name(k)}="{v}"')
    if extra is not None:
        pairs.append(f'{extra[0]}="{extra[1]}"')
    return "{" + ",".join(pairs) + "}"
