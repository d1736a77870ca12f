"""Server: wires holder + cluster + executor + API + HTTP into one node
process.

Parity target: the reference's pilosa.NewServer / Server.Open
(server.go:297,417) and the server/ Command lifecycle
(server/server.go:60-220): build everything from options, open the
holder, join the cluster, start background loops, serve HTTP.
"""

from __future__ import annotations

import os
import threading
import uuid

from pilosa_tpu.api import API
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.parallel.cluster import (
    Cluster,
    NODE_READY,
    STATE_NORMAL,
    TransportError,
)
from pilosa_tpu.parallel.node import ClusterNode
from pilosa_tpu.server.client import HTTPTransport, InternalClient
from pilosa_tpu.server.handler import Handler


class Server:
    """One node: storage + cluster + HTTP (server.go:46)."""

    def __init__(
        self,
        data_dir: str,
        host: str = "127.0.0.1",
        port: int = 0,
        name: str | None = None,
        seeds: list[str] | None = None,
        replica_n: int = 1,
        partition_n: int = 256,
        coordinator: bool = False,
        anti_entropy_interval: float = 0.0,
        heartbeat_interval: float = 0.0,
        metric_poll_interval: float = 0.0,
        long_query_time: float = 0.0,
        max_writes_per_request: int = 0,
        tls_cert: str | None = None,
        tls_key: str | None = None,
        tls_skip_verify: bool = False,
        logger=None,
        stats=None,
        tracer=None,
        heap_profile: bool = False,
        heap_profile_frames: int = 4,
        coalescer_enabled="auto",
        coalescer_window_ms: float = 2.0,
        coalescer_max_batch: int = 32,
        ragged_enabled: bool = True,
        ragged_max_tape: int = 32,
        ragged_max_leaves: int = 16,
        ragged_prewarm: bool = True,
        vm_enabled: bool = True,
        vm_min_domain: int = 8,
        vm_max_prefetch: int = 65536,
        observe_enabled: bool = True,
        observe_recent: int = 256,
        observe_long_query_time: float = 0.0,
        observe_device_sample_interval: float = 0.0,
        observe_fanin_timeout: float = 2.0,
        observe_device_peak_gbps: float = 0.0,
        observe_profiler_max_seconds: float = 30.0,
        observe_journal: bool = True,
        observe_journal_size: int = 2048,
        observe_journal_kinds: str = "",
        admission_enabled: bool = True,
        admission_query_cap: int = 32,
        admission_query_queue: int = 128,
        admission_ingest_cap: int = 16,
        admission_ingest_queue: int = 64,
        admission_internal_cap: int = 16,
        admission_internal_queue: int = 64,
        admission_default_deadline: float = 0.0,
        cache_enabled: bool = True,
        cache_budget_bytes: int | None = None,
        cache_max_entry_bytes: int | None = None,
        cache_ttl: float | None = None,
        ingest_delta_enabled: bool = True,
        ingest_delta_budget_bytes: int | None = None,
        ingest_compact_threshold_bits: int | None = None,
        ingest_compact_interval: float | None = None,
        containers_enabled: bool | None = None,
        containers_threshold: float | None = None,
        containers_kinds: bool | None = None,
        containers_array_max: int | None = None,
        containers_run_cap: int | None = None,
        mesh_enabled=None,
        mesh_axis_size: int | None = None,
        residency_host_budget_bytes: int | None = None,
        residency_disk_path: str | None = None,
        residency_disk_budget_bytes: int | None = None,
        residency_promote_workers: int | None = None,
        residency_promote_queue: int | None = None,
        residency_promote_wait_ms: float | None = None,
        residency_prefetch: bool | None = None,
        residency_prefetch_interval: float | None = None,
        breaker_threshold: int = 5,
        breaker_cooldown: float = 5.0,
        hedge_min_samples: int = 8,
        hedge_deviations: float = 4.0,
        hedge_min_ms: float = 20.0,
        hedge_max_fraction: float = 0.1,
        faultinject_armed: str = "",
        write_policy: str = "all",
        hint_max_bytes: int | None = None,
        hint_max_age: float | None = None,
        hint_replay_interval: float | None = None,
        anti_entropy_jitter: float = 0.1,
        anti_entropy_round_budget: float = 0.0,
        anti_entropy_peer_timeout: float = 2.0,
        rebalance_transfer_budget: int | None = None,
        rebalance_dual_write_policy: str | None = None,
        rebalance_cursor_path: str | None = None,
        rebalance_backoff_base: float | None = None,
        rebalance_backoff_cap: float | None = None,
        rebalance_peer_timeout: float | None = None,
        tenants_enabled: bool = False,
        tenants_default_share: int | None = None,
        tenants_default_queue: int | None = None,
        tenants_default_cache_share: float | None = None,
        tenants_default_residency_share: float | None = None,
        tenants_quotas: dict | None = None,
    ):
        from pilosa_tpu import logger as _logger
        from pilosa_tpu import stats as _stats

        os.makedirs(data_dir, exist_ok=True)
        self.data_dir = data_dir
        self.logger = logger or _logger.NOP
        self.stats = stats if stats is not None else _stats.MemStatsClient()
        self.tracer = tracer
        if tracer is not None:
            # an injected tracer IS the process tracer: the middleware,
            # executor, and outbound RPC all consult the global (the
            # reference wires its jaeger tracer globally the same way,
            # tracing/tracing.go:27 GlobalTracer)
            from pilosa_tpu import tracing as _tracing

            _tracing.set_global_tracer(tracer)
        if heap_profile:
            # start tracemalloc before the holder opens so startup
            # allocations (fragment loads, stack builds) are captured —
            # the [profile] heap config (reference server/config.go:151)
            import tracemalloc

            if not tracemalloc.is_tracing():
                tracemalloc.start(heap_profile_frames)
        self.seeds = seeds or []
        self.anti_entropy_interval = anti_entropy_interval
        self.anti_entropy_jitter = anti_entropy_jitter
        self.anti_entropy_round_budget = anti_entropy_round_budget
        self.anti_entropy_peer_timeout = anti_entropy_peer_timeout
        self.heartbeat_interval = heartbeat_interval

        self.holder = Holder(data_dir)
        node_id = name or self.holder.node_id or uuid.uuid4().hex[:12]

        self._client = InternalClient(tls_skip_verify=tls_skip_verify)
        self.cluster = Cluster(
            local_id=node_id,
            replica_n=replica_n,
            partition_n=partition_n,
            transport=HTTPTransport(self._client),
            topology_path=os.path.join(data_dir, ".topology"),
            breaker_threshold=breaker_threshold,
            breaker_cooldown_s=breaker_cooldown,
        )
        self.node = ClusterNode(self.holder, self.cluster)
        # self-healing replication ([replication] config): process-wide
        # like [mesh] — the first server's retain() captures the
        # pre-server baseline, the LAST release() (in close) restores
        # it; the hint REPLAYER is per-node and starts in open()
        from pilosa_tpu.parallel import hints as _hints
        from pilosa_tpu.parallel.hints import HintReplayer

        _hints.retain()
        self._hints_retained = True
        # kept for the reopen path: close() releases the baseline, so
        # a reopened server must RE-APPLY its configured policy, not
        # silently revert to the restored default
        self._replication_cfg = dict(
            write_policy=write_policy,
            hint_max_bytes=hint_max_bytes,
            hint_max_age=hint_max_age,
            replay_interval=hint_replay_interval)
        _hints.configure(**self._replication_cfg)
        self.hint_replayer = HintReplayer(self.node)
        # [rebalance] — online shard migration; process-wide config is
        # refcounted like [replication], the coordinator DRIVER is
        # per-node (attached here so /cluster/resize can reach it)
        from pilosa_tpu.parallel import rebalance as _rebalance

        _rebalance.retain()
        self._rebalance_retained = True
        self._rebalance_cfg = {
            k: v for k, v in dict(
                transfer_budget=rebalance_transfer_budget,
                dual_write_policy=rebalance_dual_write_policy,
                cursor_path=rebalance_cursor_path,
                backoff_base=rebalance_backoff_base,
                backoff_cap=rebalance_backoff_cap,
                peer_timeout=rebalance_peer_timeout,
            ).items() if v is not None}
        if self._rebalance_cfg:
            _rebalance.configure(**self._rebalance_cfg)
        self.node.rebalance = _rebalance.RebalanceCoordinator(
            self.node, cursor_path=rebalance_cursor_path)
        self.node.executor.stats = self.stats
        self.node.executor.logger = self.logger
        self.node.executor.long_query_time = long_query_time
        # hedged replica reads ([cluster] hedge-* config)
        self.node.executor.hedge_min_samples = hedge_min_samples
        self.node.executor.hedge_deviations = hedge_deviations
        self.node.executor.hedge_min_s = hedge_min_ms / 1e3
        self.node.executor.hedge_max_fraction = hedge_max_fraction
        # failpoint registry ([faultinject] armed): armed at
        # construction, disarmed (process-wide) by close() — the
        # registry is process-global like the result cache, so only a
        # server that armed something clears it
        from pilosa_tpu import faultinject as _faultinject

        self._faultinject_armed = bool(faultinject_armed)
        if faultinject_armed:
            _faultinject.arm(faultinject_armed)
        # cross-query micro-batched dispatch ([coalescer] config);
        # "auto" resolves to on-accelerator-only
        from pilosa_tpu.parallel.coalescer import Coalescer

        self.node.executor.coalescer = Coalescer(
            window_s=coalescer_window_ms / 1e3,
            max_batch=coalescer_max_batch,
            enabled=coalescer_enabled,
            stats=self.stats,
            ragged=ragged_enabled,
            max_tape=ragged_max_tape,
            max_leaves=ragged_max_leaves,
            vm=vm_enabled,
            vm_min_domain=vm_min_domain,
            vm_max_prefetch=vm_max_prefetch,
        )
        self._ragged_prewarm = ragged_prewarm
        # query flight recorder ([observe] config): /debug/queries,
        # ?profile=1, slow-query log, pilosa_query_latency histogram
        from pilosa_tpu import observe as _observe

        self.node.executor.recorder = _observe.FlightRecorder(
            recent=observe_recent,
            long_query_time=observe_long_query_time,
            enabled=observe_enabled,
            logger=self.logger,
            stats=self.stats,
        )
        # cluster event journal ([observe] journal keys): process-wide
        # like [mesh] — the first server's retain() captures the
        # pre-server baseline, the LAST release() (in close) restores
        # it for library users sharing the process
        _observe.retain()
        self._journal_retained = True
        self._journal_cfg = dict(
            node_id=node_id,
            size=observe_journal_size,
            kinds=observe_journal_kinds,
            enabled=observe_journal,
        )
        _observe.configure(**self._journal_cfg)
        # generation-stamped query result cache ([cache] config):
        # process-wide like the residency manager — configure in place
        # so a second in-process server cannot wipe the first's warm
        # entries
        from pilosa_tpu.runtime import resultcache as _resultcache

        _resultcache.configure(
            budget_bytes=cache_budget_bytes,
            max_entry_bytes=cache_max_entry_bytes,
            ttl_s=cache_ttl,
            enabled=cache_enabled,
        )
        # streaming ingest ([ingest] config): delta planes + background
        # compaction are process-wide like the result cache — configure
        # in place; the compactor thread starts in open() and stops in
        # close().  Remember whether the package default (disabled, so
        # bare library embedders keep pre-delta semantics) was already
        # overridden: close() only restores what THIS server flipped.
        from pilosa_tpu import ingest as _ingest

        # the FIRST in-process server snapshots the pre-server config;
        # the LAST one to close restores it (ingest.restore_baseline —
        # per-server snapshots compose wrongly when servers close in
        # creation order, re-installing an earlier sibling's override)
        _ingest.capture_baseline()
        _ingest.configure(
            delta_enabled=ingest_delta_enabled,
            delta_budget_bytes=ingest_delta_budget_bytes,
            compact_threshold_bits=ingest_compact_threshold_bits,
            compact_interval=ingest_compact_interval,
        )
        self._ingest_enabled = bool(ingest_delta_enabled)
        self._ingest_retained = False
        self._closed = False
        # compressed container-directory engine ([containers] config):
        # process-wide like [ingest] — the first server's retain()
        # captures the pre-server baseline, the LAST release() (in
        # close) restores it for library users sharing the process
        from pilosa_tpu.ops import containers as _containers

        _containers.retain()
        self._containers_retained = True
        _containers.configure(enabled=containers_enabled,
                              threshold=containers_threshold,
                              kinds=containers_kinds,
                              array_max=containers_array_max,
                              run_cap=containers_run_cap)
        # mesh-native SPMD execution ([mesh] config): process-wide
        # like [containers] — the first server's retain() captures the
        # pre-server baseline, the LAST release() (in close) restores
        # it for library users sharing the process
        from pilosa_tpu.parallel import meshexec as _meshexec

        _meshexec.retain()
        self._mesh_retained = True
        _meshexec.configure(enabled=mesh_enabled,
                            axis_size=mesh_axis_size)
        # engine observatory ([observe] device-peak-gbps /
        # profiler-max-seconds): process-wide like
        # [mesh] — the first server's retain() captures the pre-server
        # baseline, the LAST release() (in close) restores it
        from pilosa_tpu import perfobs as _perfobs

        _perfobs.retain()
        self._perfobs_retained = True
        self._perfobs_cfg = dict(
            enabled_=observe_enabled,
            peak_gbps=observe_device_peak_gbps,
            profiler_max_seconds=observe_profiler_max_seconds)
        _perfobs.configure(**self._perfobs_cfg)
        # per-tenant isolation ([tenants] config): process-wide like
        # [mesh] — the first server's retain() captures the pre-server
        # baseline, the LAST release() (in close) restores it.  The
        # admission gate, result cache and residency manager all
        # consult serve.tenant.policy() live, so this configure is the
        # single switch.
        from pilosa_tpu.serve import tenant as _tenantcfg

        _tenantcfg.retain()
        self._tenants_retained = True
        self._tenants_cfg = dict(
            enabled=tenants_enabled,
            default_share=tenants_default_share,
            default_queue=tenants_default_queue,
            default_cache_share=tenants_default_cache_share,
            default_residency_share=tenants_default_residency_share,
            quotas=tenants_quotas)
        _tenantcfg.configure(**self._tenants_cfg)
        # tiered residency ([residency] config): process-wide like
        # [mesh] — the first server's retain() captures the pre-server
        # baseline, the LAST release() (in close) restores it and
        # stops the shared promotion workers
        from pilosa_tpu.runtime import residency as _residency

        _residency.retain()
        self._residency_retained = True
        _residency.configure(
            host_budget_bytes=residency_host_budget_bytes,
            disk_path=residency_disk_path,
            disk_budget_bytes=residency_disk_budget_bytes,
            promote_workers=residency_promote_workers,
            promote_queue=residency_promote_queue,
            promote_wait_ms=residency_promote_wait_ms,
            prefetch=residency_prefetch,
            prefetch_interval=residency_prefetch_interval)
        if self._ingest_enabled:
            # reference taken at CONSTRUCTION, where the configure
            # above landed — not at open() — so a sibling's close
            # cannot restore the baseline out from under a
            # constructed-but-not-yet-opened server (the scan thread
            # idling over an empty registry until open is harmless)
            from pilosa_tpu.ingest import compactor as _compactor

            _compactor.retain()
            self._ingest_retained = True
        # device-runtime telemetry (pilosa_tpu.devobs): wire the stats
        # backend in (compile.ms histograms publish live) and start the
        # optional background gauge sampler
        from pilosa_tpu import devobs as _devobs

        _devobs.observer().stats = self.stats
        self.device_sampler = _devobs.DeviceSampler(
            self.stats, observe_device_sample_interval)
        if coordinator:
            # statically designated coordinator (reference
            # cluster.coordinator config, server/config.go:104)
            self.cluster.coordinator_id = self.cluster.local_id
            self.cluster.local_node.is_coordinator = True
        self.api = API(self.node)
        self.api.max_writes_per_request = max_writes_per_request
        # admission control ([admission] config): priority-classed
        # gating + load shedding between accept and device dispatch
        from pilosa_tpu.serve.admission import AdmissionController

        self.admission = AdmissionController(
            query_cap=admission_query_cap,
            query_queue=admission_query_queue,
            ingest_cap=admission_ingest_cap,
            ingest_queue=admission_ingest_queue,
            internal_cap=admission_internal_cap,
            internal_queue=admission_internal_queue,
            default_deadline=admission_default_deadline,
            enabled=admission_enabled,
            stats=self.stats,
        )
        # background delta compactor (pilosa_tpu.ingest.compactor):
        # process-wide; runs each scan under admission's internal class
        # so compaction yields to query pressure like anti-entropy does
        from pilosa_tpu.ingest import compactor as _compactor

        _c = _compactor.compactor()
        _c.admission = self.admission
        # tiered-residency promotion pool: each promotion admits under
        # the internal class, so query saturation sheds promotions
        # (the waiting query takes the host-compute fallback) exactly
        # like it pauses compaction
        _residency.promoter().admission = self.admission
        from pilosa_tpu.runtime.prefetch import Prefetcher

        self.prefetcher = Prefetcher()
        self.handler = Handler(self.api, host=host, port=port,
                               stats=self.stats, tracer=tracer,
                               tls_cert=tls_cert, tls_key=tls_key,
                               heap_frames=heap_profile_frames,
                               admission=self.admission,
                               peer_client=self._client,
                               fanin_timeout=observe_fanin_timeout)
        self.cluster.local_node.uri = self.handler.uri
        from pilosa_tpu.diagnostics import RuntimeMonitor

        self.runtime_monitor = RuntimeMonitor(self.stats,
                                              metric_poll_interval)
        self._closers: list = []
        self._stop = threading.Event()

    @property
    def uri(self) -> str:
        return self.handler.uri

    # ---------------------------------------------------------- lifecycle

    def open(self) -> None:
        """Serve, then join via seeds or become a standalone NORMAL
        cluster (server.go:417 Open; gossip join with retry,
        gossip/gossip.go:65-123)."""
        self._closed = False  # an instance reopened after close()
        # reopened after close(): the holder closed its indexes and
        # released the directory flock — reload persisted state (no-op
        # on first open, which holds the flock from construction)
        self.holder.reopen()
        if not self._containers_retained:
            # reopened after close(): take the [containers] reference
            # back (the first open holds the construction-time one)
            from pilosa_tpu.ops import containers as _containers

            _containers.retain()
            self._containers_retained = True
        if not self._mesh_retained:
            # reopened after close(): take the [mesh] reference back
            from pilosa_tpu.parallel import meshexec as _meshexec

            _meshexec.retain()
            self._mesh_retained = True
        if not self._perfobs_retained:
            # reopened after close(): take the observatory reference
            # back and RE-APPLY this server's knobs (close() restored
            # the process baseline)
            from pilosa_tpu import perfobs as _perfobs

            _perfobs.retain()
            self._perfobs_retained = True
            _perfobs.configure(**self._perfobs_cfg)
        if not self._tenants_retained:
            # reopened after close(): take the [tenants] reference
            # back and RE-APPLY this server's configured quotas
            # (close() restored the process baseline — without the
            # re-apply a reopened server would serve with isolation
            # silently off, the [replication] reopen bug class)
            from pilosa_tpu.serve import tenant as _tenantcfg

            _tenantcfg.retain()
            self._tenants_retained = True
            _tenantcfg.configure(**self._tenants_cfg)
        if not self._residency_retained:
            # reopened after close(): take the [residency] reference
            # back and re-wire the promotion pool's admission gate
            from pilosa_tpu.runtime import residency as _residency

            _residency.retain()
            self._residency_retained = True
            _residency.promoter().admission = self.admission
        if self._ingest_enabled and not self._ingest_retained:
            # reopened after close(): take the reference back (the
            # normal first open already holds the construction-time
            # one)
            from pilosa_tpu.ingest import compactor as _compactor

            _compactor.retain()
            self._ingest_retained = True
        if not self._hints_retained:
            # reopened after close(): take the [replication] reference
            # back, RE-APPLY this server's configured policy (close()
            # restored the process baseline), and rebuild the hint
            # store (close() released its append handles; queued hints
            # reload from disk)
            import os as _os

            from pilosa_tpu.parallel import hints as _hints
            from pilosa_tpu.parallel.hints import HintStore

            _hints.retain()
            self._hints_retained = True
            _hints.configure(**self._replication_cfg)
            self.node.hints = HintStore(
                _os.path.join(self.holder.path, "hints")
                if getattr(self.holder, "path", None) else None)
        if not self._rebalance_retained:
            from pilosa_tpu.parallel import rebalance as _rebalance1

            _rebalance1.retain()
            self._rebalance_retained = True
            if self._rebalance_cfg:
                _rebalance1.configure(**self._rebalance_cfg)
        if not self._journal_retained:
            # reopened after close(): take the event-journal reference
            # back and RE-APPLY this server's node id / ring sizing
            # (close() restored the process baseline)
            from pilosa_tpu import observe as _observe1

            _observe1.retain()
            self._journal_retained = True
            _observe1.configure(**self._journal_cfg)
        self.handler.serve_background()
        self.cluster.save_topology()
        if self.seeds:
            self._join_via_seeds()
            # announce restored shards (peers' status came back in the
            # join response's nodeStatus)
            self.node.broadcast_node_status()
        else:
            # single/static bootstrap: coordinator of own cluster
            self.cluster.coordinator_id = self.cluster.local_id
            self.cluster.local_node.is_coordinator = True
            self.cluster.set_state(STATE_NORMAL)
        try:
            # crash mid-rebalance leaves a persisted cursor: pick the
            # migration back up from the last completed shard (no-op
            # when no cursor file exists or we are not the coordinator)
            if self.cluster.is_coordinator:
                self.node.rebalance.resume()
        except Exception as e:  # noqa: BLE001 — resume must not block
            # serving; the cluster keeps the old topology either way
            self.logger.printf("rebalance resume skipped: %r", e)
        if self.anti_entropy_interval > 0:
            t = threading.Thread(target=self._anti_entropy_loop, daemon=True)
            t.start()
        if self.heartbeat_interval > 0:
            t = threading.Thread(target=self._heartbeat_loop, daemon=True)
            t.start()
        self.runtime_monitor.start()
        self.device_sampler.start()
        self.prefetcher.start()
        # hinted-handoff replay worker: drains per-peer hint queues
        # with backoff once a peer's breaker closes / heartbeat returns
        self.hint_replayer.start()
        if self._ragged_prewarm:
            # lower the ragged bucket interpreter programs off the
            # serving path ([ragged] prewarm): background, a no-op in
            # host mode or with the coalescer/ragged off
            t = threading.Thread(target=self._prewarm_ragged,
                                 daemon=True, name="ragged-prewarm")
            t.start()

    def _prewarm_ragged(self) -> None:
        from pilosa_tpu.ops import bitmap as bm
        from pilosa_tpu.ops import tape as _tape
        from pilosa_tpu.shardwidth import SHARD_WIDTH

        co = self.node.executor.coalescer
        if co is None or not (co.enabled and co.ragged) or bm.host_mode():
            _tape.note_prewarm("done")
            return
        _tape.note_prewarm("running")
        try:
            import jax

            from pilosa_tpu.models.field import _padded_rows
            from pilosa_tpu.parallel import meshexec
            from pilosa_tpu.runtime import residency as _residency

            # the leaf stack shape every fused read stages: the widest
            # index's shard fan-out, padded exactly as serving stacks
            # pad (_padded_rows keys on the [mesh] axis in force — the
            # actual device layout), SHARD_WIDTH words.  The mesh is
            # threaded through so the programs LOWERED are the ones
            # serving traffic will run: shard_map variants on an
            # active mesh, single-device ones otherwise — a 1-device
            # process never lowers mesh-shaped programs and an
            # N-device mesh never wastes the warm-up on single-device
            # ones.  An empty holder warms a nominal 1-shard stack.
            n_shards = max(
                [len(idx.available_shards())
                 for idx in self.holder.indexes.values()] or [1])
            stack = (_padded_rows(max(1, n_shards)),
                     bm.n_words(SHARD_WIDTH))
            # warm-up jobs RUN on zero stacks: bound them by the device
            # memory the residency budget leaves free (CPU backends,
            # which report no limit, never warm — _prewarm_worthwhile)
            devs = jax.local_devices()
            ms = devs[0].memory_stats() or {}
            free = None
            if "bytes_limit" in ms:
                free = max(0, ms["bytes_limit"] * len(devs)
                           - _residency.manager().budget)
            _tape.prewarm(stack, co.max_batch, co.max_tape,
                          co.max_leaves,
                          mesh=meshexec.active_mesh(),
                          budget_bytes=free)
            _tape.note_prewarm("done")
        except Exception as e:  # noqa: BLE001 — a warm-up failure must
            # not stop the server (the first ragged window pays the
            # compile, or fails the same way where it can be seen), but
            # it is never a quiet "skipped": /debug/ragged carries it
            _tape.note_prewarm("failed", f"{type(e).__name__}: {e}")
            self.logger.printf("ragged prewarm FAILED: %r", e)

    def _join_via_seeds(self) -> None:
        client = self._client
        me = self.cluster.local_node.to_dict()
        last_err: Exception | None = None
        for attempt in range(60):  # 60 retries (gossip/gossip.go:102)
            for seed in self.seeds:
                try:
                    resp = client.send_message(
                        seed, {"type": "node-join", "node": me})
                    if resp.get("status") and self.cluster.apply_status(
                            resp["status"]):
                        # the join response carried a stale self-DOWN
                        # (predates this restart): heal stale peer
                        # views too, or with SWIM disabled they route
                        # reads away from us forever
                        self.node.broadcast({
                            "type": "node-state",
                            "node": self.cluster.local_id,
                            "state": NODE_READY})
                    # catch up on shards created while this node was
                    # away (the coordinator's NodeStatus)
                    if resp.get("nodeStatus"):
                        self.node.apply_node_status(resp["nodeStatus"])
                    return
                except (TransportError, Exception) as e:
                    last_err = e
            self._stop.wait(0.5)
            if self._stop.is_set():
                return
        raise RuntimeError(f"could not join cluster via seeds: {last_err}")

    def _anti_entropy_loop(self) -> None:
        import random

        from pilosa_tpu.parallel.syncer import HolderSyncer

        syncer = HolderSyncer(
            self.node, peer_timeout=self.anti_entropy_peer_timeout)
        budget = self.anti_entropy_round_budget
        while True:
            wait = self.anti_entropy_interval
            if self.anti_entropy_jitter > 0:
                # jittered cadence: a fleet restarted together must
                # not run every AE sweep (and its RPC fan-out) in
                # lockstep
                wait *= 1.0 + random.uniform(-self.anti_entropy_jitter,
                                             self.anti_entropy_jitter)
            if self._stop.wait(max(0.01, wait)):
                return
            try:
                syncer.sync_holder(
                    budget_s=budget if budget and budget > 0 else None)
            except Exception:
                pass

    def _heartbeat_loop(self) -> None:
        from pilosa_tpu.parallel.membership import heartbeat_round

        while not self._stop.wait(self.heartbeat_interval):
            try:
                heartbeat_round(self.node)
            except Exception:
                pass

    def close(self) -> None:
        # idempotent: a double-close (belt-and-braces test teardown)
        # must not release the shared compactor reference twice and
        # tear it down under a still-open sibling server
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        self.runtime_monitor.stop()
        self.device_sampler.stop()
        self.prefetcher.stop()
        self.hint_replayer.stop()
        # halt (not abort) any in-flight rebalance: the persisted
        # cursor survives so a restarted coordinator resumes the
        # migration instead of stranding the cluster mid-plan
        try:
            self.node.rebalance.stop()
        except Exception:  # noqa: BLE001 — close() must stay idempotent
            pass
        from pilosa_tpu.parallel import hints as _hints0, \
            rebalance as _rebalance0

        if self._rebalance_retained:
            self._rebalance_retained = False
            _rebalance0.release()
        if self._hints_retained:
            self._hints_retained = False
            _hints0.release()
        self.node.hints.close()
        # the scan thread and [ingest] config are shared across every
        # in-process server: drop our reference, and only when we were
        # the LAST ingest-enabled server stop the thread and restore
        # the pre-server baseline config (a closed server group must
        # not leave streaming semantics — or an aggressive budget/
        # threshold/interval — in force for unrelated library users,
        # nor yank them out from under a still-open sibling).  Pending
        # bits are WAL-durable — fragment close drops the planes,
        # reopen replays them.
        from pilosa_tpu import ingest as _ingest
        from pilosa_tpu.ingest import compactor as _compactor

        if self._ingest_retained:
            self._ingest_retained = False
            last = _compactor.release()
        else:
            # ingest-disabled server: only restore when no
            # ingest-enabled sibling still holds a reference
            last = _compactor.refs() == 0
        if last:
            _ingest.restore_baseline()
        from pilosa_tpu.ops import containers as _containers

        if self._containers_retained:
            self._containers_retained = False
            _containers.release()
        from pilosa_tpu.parallel import meshexec as _meshexec

        if self._mesh_retained:
            self._mesh_retained = False
            _meshexec.release()
        from pilosa_tpu import perfobs as _perfobs0

        if self._perfobs_retained:
            self._perfobs_retained = False
            _perfobs0.release()
        from pilosa_tpu.runtime import residency as _residency2

        if self._residency_retained:
            self._residency_retained = False
            _residency2.release()
        from pilosa_tpu.serve import tenant as _tenantcfg2

        if self._tenants_retained:
            self._tenants_retained = False
            _tenantcfg2.release()
        from pilosa_tpu import observe as _observe2

        if self._journal_retained:
            self._journal_retained = False
            _observe2.release()
        if self._faultinject_armed:
            # config-armed failpoints are process-wide: the arming
            # server disarms everything on close so library users
            # sharing the process never inherit injected faults
            from pilosa_tpu import faultinject as _faultinject

            _faultinject.disarm()
            self._faultinject_armed = False
        self.handler.close()
        self._client.close()  # drop pooled keep-alive sockets
        self.holder.close()
        for closer in self._closers:
            try:
                closer()
            except Exception:
                pass
