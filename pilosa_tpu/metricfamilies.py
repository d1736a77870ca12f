"""Declarative metric-family registry: every family this codebase
emits, declared exactly once.

Before this module the family lists lived embedded in
``tools/check_metrics.py`` (the live-server checker) and were
re-derived by hand in docs and review — adding a metric family meant
touching the checker, the docs, and remembering both.  Now a family is
declared here and consumed by:

- ``tools/check_metrics.check_families`` — the live-exposition gate
  (``--families`` CLI mode and tests/test_http.py) requires at least
  one sampled metric under every family whose ``live_prefixes`` is
  non-empty;
- ``tools/analyze`` pass P6 (metric-family drift) — statically
  harvests every metric-name string literal fed to the stats registry
  across ``pilosa_tpu/`` and fails when a name's family is not
  declared here, or a family declared ``static=True`` has no
  harvested emitter left (a refactor silently dropped it);
- docs cross-checks — a family naming a ``doc`` file must be
  mentioned there (rendered prefix), so operator documentation cannot
  silently rot.

Registry dot-names (``cache.hits``) render on /metrics with ``_``
(``cache_hits``); ``rendered`` is the family's Prometheus prefix.
"""

from __future__ import annotations

from dataclasses import dataclass, field


@dataclass(frozen=True)
class Family:
    """One metric family.

    ``name`` — the dot-name prefix as fed to the stats registry
    (first segment of ``cache.hits`` is ``cache``).
    ``rendered`` — the Prometheus-rendered prefix (``cache_``).
    ``live_prefixes`` — rendered prefixes a live server MUST sample
    under (empty tuple: not required on every assembly, e.g. families
    only emitted once traffic of that kind arrives).
    ``static`` — the P6 drift pass requires at least one statically
    harvested emitter for this family in ``pilosa_tpu/``.
    ``group`` — back-compat grouping for the per-subsystem constants
    ``tools/check_metrics.py`` has always exported.
    ``doc`` — docs file (under ``docs/``) that must mention the
    rendered prefix, or None.
    """

    name: str
    rendered: str
    description: str
    live_prefixes: tuple = ()
    static: bool = True
    group: str | None = None
    doc: str | None = None
    owners: tuple = field(default_factory=tuple)


#: Every metric family the package emits.  Add new families HERE —
#: check_metrics, the P6 static pass, and the docs check all consume
#: this one list.
FAMILIES: tuple[Family, ...] = (
    Family("device", "device_",
           "device memory, transfer metering (pilosa_tpu.devobs)",
           live_prefixes=("device_",), group="device",
           doc="administration.md"),
    Family("compile", "compile_",
           "jit first-lowering tracking and fused-program cache "
           "evictions (pilosa_tpu.devobs, ops/expr.py)",
           live_prefixes=("compile_",), group="device",
           doc="administration.md"),
    Family("residency", "residency_",
           "device-cache budget/evict/admit accounting plus the "
           "residency.tier.* host/disk-tier, demotion, promotion and "
           "fallback counters (runtime/residency.py)",
           live_prefixes=("residency_", "residency_tier_"),
           group="device", doc="administration.md"),
    Family("prefetch", "prefetch_",
           "predictive host-tier->HBM prefetcher "
           "(runtime/prefetch.py)",
           live_prefixes=("prefetch_",), group="tier",
           doc="administration.md"),
    Family("cache", "cache_",
           "generation-stamped result cache (runtime/resultcache.py)",
           live_prefixes=("cache_",), group="cache",
           doc="administration.md"),
    Family("ingest", "ingest_",
           "streaming-ingest delta planes and background compaction "
           "(pilosa_tpu.ingest)",
           live_prefixes=("ingest_",), group="ingest",
           doc="administration.md"),
    Family("tape", "tape_",
           "ragged op-tape interpreter (ops/tape.py)",
           live_prefixes=("tape_",), group="tape",
           doc="architecture.md"),
    Family("vm", "vm_",
           "Pallas bitmap VM: one scalar-prefetch kernel for ragged "
           "tapes over compressed containers (ops/pallas_kernels.py "
           "+ ops/tape.py); vm_fallbacks_* is the per-reason "
           "breakdown of dense-path fallbacks",
           live_prefixes=("vm_",), group="tape",
           doc="architecture.md"),
    Family("container", "container_",
           "compressed container-directory execution engine with "
           "per-kind bitmap/array/run pools (ops/containers.py); "
           "container_*_gathered breaks gathers out per kind",
           live_prefixes=("container_",), group="container",
           doc="architecture.md"),
    Family("stage", "stage_",
           "staged leaves validated against the view's write token "
           "alone (stage_fast) or by a walk over the shards "
           "(stage_walk) (stagecheck.py, models/field.py)",
           live_prefixes=("stage_",), group="container",
           doc="architecture.md"),
    Family("mesh", "mesh_",
           "mesh-native SPMD execution of the fused serving path "
           "(parallel/meshexec.py)",
           live_prefixes=("mesh_",), group="mesh",
           doc="architecture.md"),
    Family("coalescer", "coalescer_",
           "cross-query batching window (parallel/coalescer.py); the "
           "shape_* heterogeneity counters are pinned on live "
           "servers, the window timings appear once traffic flows",
           live_prefixes=("coalescer_shape_",), group="tape",
           doc="architecture.md"),
    Family("admission", "admission_",
           "priority-class admission control (serve/admission.py)",
           doc="administration.md"),
    Family("breaker", "breaker_",
           "per-peer circuit breakers on the cluster fan-out "
           "(parallel/cluster.py)",
           live_prefixes=("breaker_",), group="chaos",
           doc="administration.md"),
    Family("hedge", "hedge_",
           "hedged replica reads on the remote shard map "
           "(parallel/executor.py)",
           live_prefixes=("hedge_",), group="chaos",
           doc="administration.md"),
    Family("failpoint", "failpoint_",
           "failpoint registry arming/trigger accounting "
           "(pilosa_tpu.faultinject)",
           live_prefixes=("failpoint_",), group="chaos",
           doc="administration.md"),
    Family("partial", "partial_",
           "degraded-read (?partial=1) request accounting "
           "(parallel/executor.py)",
           live_prefixes=("partial_",), group="chaos",
           doc="administration.md"),
    Family("ae", "ae_",
           "anti-entropy rounds: fragments walked, dirty/reconciled/"
           "pushed blocks, classified peer failures, digest-cache "
           "hits (parallel/syncer.py)",
           live_prefixes=("ae_",), group="repl",
           doc="administration.md"),
    Family("hint", "hint_",
           "hinted handoff for degraded writes: queued/replayed/"
           "dropped hints plus live per-node queue depth "
           "(parallel/hints.py)",
           live_prefixes=("hint_",), group="repl",
           doc="administration.md"),
    Family("rebalance", "rebalance_",
           "online shard migration: plans/cutovers/aborts/resumes, "
           "dual-write deliveries, streamed backfill bytes, breaker "
           "backoffs, live per-state shard gauges "
           "(parallel/rebalance.py)",
           live_prefixes=("rebalance_",), group="rebalance",
           doc="administration.md"),
    Family("wal", "wal_",
           "fragment WAL replay health — torn/corrupt tail records "
           "ignored at reload (models/fragment.py)",
           live_prefixes=("wal_",), group="repl",
           doc="administration.md"),
    Family("engine", "engine_",
           "engine observatory per-launch accounting: sampled launch/"
           "byte totals plus per-engine tagged wall/bandwidth/bw_util "
           "gauges (pilosa_tpu.perfobs)",
           live_prefixes=("engine_",), group="engine",
           doc="administration.md"),
    Family("cost", "cost_",
           "cost table: samples and cells, completed profiler "
           "captures (pilosa_tpu.perfobs)",
           live_prefixes=("cost_",), group="engine",
           doc="administration.md"),
    Family("launch", "launch_",
           "device round trips of a sampled launch: launch_fetched "
           "counts launches whose one wait was the fetch of their "
           "counts, launch_refetched those of the dense engine whose "
           "counts were asked of the device again after the wait "
           "(pilosa_tpu.perfobs, ops/expr.py)",
           live_prefixes=("launch_",), group="engine",
           doc="administration.md"),
    Family("tenant", "tenant_",
           "per-tenant isolation totals: admission admitted/shed/"
           "waiting, result-cache bytes, residency HBM/host bytes "
           "(serve/tenant.py; zeros while [tenants] is off)",
           live_prefixes=("tenant_",), group="tenant",
           doc="administration.md"),
    Family("event", "event_",
           "cluster event journal: structured state-transition events "
           "(breaker/hedge/rebalance/AE/compaction/residency/"
           "failpoint), ring depth and drop accounting "
           "(pilosa_tpu.observe.EventJournal)",
           live_prefixes=("event_",), group="trace",
           doc="administration.md"),
    Family("trace", "trace_",
           "cross-node trace assembly: /debug/trace/{id} trees "
           "assembled, per-node record fan-ins, fan-in errors, "
           "origin-less assemblies (pilosa_tpu.traceasm + "
           "server/handler.py)",
           live_prefixes=("trace_",), group="trace",
           doc="administration.md"),
    Family("http", "http_",
           "per-route request counters (server/handler.py)"),
    Family("gc", "gc_",
           "python garbage-collector sampling (diagnostics.py)"),
    Family("memory", "memory_",
           "process RSS sampling (diagnostics.py)"),
)

#: Metric names without a family prefix (no dot): the runtime sampler
#: gauges and the native-histogram latency family.  The P6 harvest
#: only considers dotted names, so these are documented rather than
#: checked; they are listed so the registry is the complete inventory.
BARE_METRICS: tuple[str, ...] = (
    "open_files",
    "threads",
    "pilosa_query_latency",
)


def by_name() -> dict[str, Family]:
    return {f.name: f for f in FAMILIES}


def live_prefixes(group: str | None = None) -> tuple[str, ...]:
    """Rendered prefixes a live server must sample under — all of
    them, or one back-compat subsystem group's."""
    out: list[str] = []
    for f in FAMILIES:
        if group is not None and f.group != group:
            continue
        out.extend(f.live_prefixes)
    return tuple(out)


def static_families() -> tuple[Family, ...]:
    """Families the P6 drift pass requires a static emitter for."""
    return tuple(f for f in FAMILIES if f.static)
