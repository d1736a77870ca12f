"""CLI: the operational command surface.

Parity target: the reference's cobra command tree (cmd/root.go:28) and
ctl/ implementations — ``server`` (ctl/server.go), ``import``
(ctl/import.go:34-350: CSV buffering, shard grouping, key-aware),
``export`` (ctl/export.go), ``check`` (ctl/check.go: offline file
integrity), ``inspect`` (ctl/inspect.go: fragment dump),
``generate-config``/``config`` (ctl/generate_config.go, ctl/config.go).

Run as ``python -m pilosa_tpu <command>``."""

from __future__ import annotations

import argparse
import csv
import datetime as dt
import signal
import sys
import threading

from pilosa_tpu.config import Config


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="pilosa-tpu",
        description="TPU-native distributed bitmap index")
    sub = p.add_subparsers(dest="command", required=True)

    ps = sub.add_parser("server", help="run a node")
    ps.add_argument("-c", "--config", help="TOML config file")
    ps.add_argument("-d", "--data-dir")
    ps.add_argument("-b", "--bind")
    ps.add_argument("--name")
    ps.add_argument("--seeds", help="comma-separated seed URIs")
    ps.add_argument("--replicas", type=int)
    ps.add_argument("--anti-entropy-interval", type=float)
    ps.add_argument("--heartbeat-interval", type=float)
    ps.add_argument("--long-query-time", type=float,
                    help="seconds; log queries slower than this with "
                         "their profile breakdown ([observe] "
                         "long-query-time; 0 disables)")
    ps.add_argument("--no-admission", action="store_true",
                    help="disable the admission gate ([admission] "
                         "enabled=false): no per-class caps, no load "
                         "shedding, no accept-side thread cap")
    ps.add_argument("--admission-default-deadline", type=float,
                    help="seconds applied to requests without an "
                         "X-Pilosa-Deadline header ([admission] "
                         "default-deadline; 0 = none)")
    for _cls in ("query", "ingest", "internal"):
        ps.add_argument(f"--admission-{_cls}-cap", type=int,
                        help=f"concurrent {_cls}-class requests "
                             f"([admission] {_cls}-cap)")
        ps.add_argument(f"--admission-{_cls}-queue", type=int,
                        help=f"queued {_cls}-class requests beyond the "
                             f"cap; overflow sheds 429 "
                             f"([admission] {_cls}-queue)")
    ps.add_argument("--no-result-cache", action="store_true",
                    help="disable the generation-stamped query result "
                         "cache ([cache] enabled=false): every read "
                         "re-executes on the device")
    ps.add_argument("--cache-budget-bytes", type=int,
                    help="host-memory budget for cached query results "
                         "([cache] budget-bytes)")
    ps.add_argument("--cache-max-entry-bytes", type=int,
                    help="largest single cacheable result "
                         "([cache] max-entry-bytes)")
    ps.add_argument("--cache-ttl", type=float,
                    help="seconds before a cached result ages out even "
                         "unmutated ([cache] ttl; 0 = generations only)")
    ps.add_argument("--no-ragged", action="store_true",
                    help="disable ragged megabatch execution "
                         "([ragged] enabled=false): the coalescer "
                         "merges only identical-shape queries through "
                         "the fused path (pre-ragged behavior)")
    ps.add_argument("--ragged-max-tape", type=int,
                    help="longest op-tape a query may compile to "
                         "before falling back to the per-shape fused "
                         "path ([ragged] max-tape)")
    ps.add_argument("--ragged-max-leaves", type=int,
                    help="most leaf operand stacks a query may stage "
                         "into a ragged bucket ([ragged] max-leaves)")
    ps.add_argument("--no-containers", action="store_true",
                    help="disable the compressed container-directory "
                         "device layout ([containers] enabled=false): "
                         "every fused read routes the dense "
                         "pre-container path")
    ps.add_argument("--containers-threshold", type=float,
                    help="per-fragment fill-ratio ceiling for "
                         "compressed execution ([containers] "
                         "threshold); rows denser than this stay on "
                         "the dense path")
    ps.add_argument("--no-container-kinds", action="store_true",
                    help="disable per-container kind specialization "
                         "([containers] kinds=false): every container "
                         "stays a dense 2048-word bitmap block")
    ps.add_argument("--containers-array-max", type=int,
                    help="cardinality ceiling for the array container "
                         "kind ([containers] array-max, canonical "
                         "4096); lower values only narrow the device "
                         "pick")
    ps.add_argument("--containers-run-cap", type=int,
                    help="most intervals a run container may carry on "
                         "device ([containers] run-cap); noisier "
                         "containers demote to array/bitmap")
    ps.add_argument("--no-mesh", action="store_true",
                    help="disable mesh-native SPMD execution ([mesh] "
                         "enabled=false): fused dispatches run the "
                         "pre-mesh single-device programs and operand "
                         "stacks place on one device")
    ps.add_argument("--mesh-axis-size", type=int,
                    help="local devices joined to the mesh shard axis "
                         "([mesh] axis-size); 0 = all local devices")
    ps.add_argument("--residency-host-budget-bytes", type=int,
                    help="host-RAM tier budget behind HBM ([residency] "
                         "host-budget-bytes); 0 disables tiering "
                         "(misses rebuild inline, evictions drop)")
    ps.add_argument("--residency-disk-path",
                    help="directory for the optional disk spill tier "
                         "behind host RAM ([residency] disk-path); "
                         "empty disables it")
    ps.add_argument("--residency-promote-workers", type=int,
                    help="async promotion worker threads ([residency] "
                         "promote-workers)")
    ps.add_argument("--residency-promote-wait-ms", type=float,
                    help="bound on a demand miss's promotion wait "
                         "before the host-compute fallback "
                         "([residency] promote-wait-ms)")
    ps.add_argument("--no-prefetch", action="store_true",
                    help="disable the predictive host-tier prefetcher "
                         "([residency] prefetch=false)")
    ps.add_argument("--no-ingest-delta", action="store_true",
                    help="disable streaming-ingest delta planes "
                         "([ingest] delta-enabled=false): every write "
                         "mutates base state and bumps the generation "
                         "(pre-delta semantics)")
    ps.add_argument("--ingest-delta-budget-bytes", type=int,
                    help="process-wide bound on pending delta bytes; "
                         "past it writers flush their own fragment "
                         "inline ([ingest] delta-budget-bytes)")
    ps.add_argument("--ingest-compact-threshold-bits", type=int,
                    help="pending bit positions that trigger a "
                         "fragment's compaction on the next scan "
                         "([ingest] compact-threshold-bits)")
    ps.add_argument("--ingest-compact-interval", type=float,
                    help="compactor scan period in seconds, and the "
                         "age bound for small deltas ([ingest] "
                         "compact-interval)")
    ps.add_argument("--breaker-threshold", type=int,
                    help="consecutive transport failures that open a "
                         "peer's circuit breaker ([cluster] "
                         "breaker-threshold)")
    ps.add_argument("--breaker-cooldown", type=float,
                    help="seconds a breaker stays open before the "
                         "half-open trial ([cluster] breaker-cooldown)")
    ps.add_argument("--hedge-max-fraction", type=float,
                    help="bound on hedged replica reads as a fraction "
                         "of RPC volume ([cluster] hedge-max-fraction; "
                         "0 disables hedging)")
    ps.add_argument("--faultinject-armed",
                    help="failpoint spec armed at open ([faultinject] "
                         "armed; e.g. "
                         "'client.request.send=error(transport)*3')")
    ps.add_argument("--write-policy", choices=("all", "available"),
                    help="replica write policy ([replication] "
                         "write-policy): 'all' fails the write when "
                         "any owner is unreachable (default); "
                         "'available' commits on the reachable owners "
                         "and hints the rest for replay")
    ps.add_argument("--hint-max-bytes", type=int,
                    help="total bytes of queued hinted-handoff writes "
                         "([replication] hint-max-bytes; 0 disables "
                         "the hint queue)")
    ps.add_argument("--rebalance-transfer-budget", type=int,
                    help="concurrent shard backfills during an online "
                         "rebalance ([rebalance] transfer-budget)")
    ps.add_argument("--rebalance-dual-write-policy",
                    choices=("hint", "strict"),
                    help="delivery contract for pending shard owners "
                         "during a migration ([rebalance] "
                         "dual-write-policy): 'hint' never fails the "
                         "write over a missed pending copy (queues a "
                         "hint); 'strict' holds pending owners to the "
                         "[replication] write-policy")
    ps.add_argument("--anti-entropy-round-budget", type=float,
                    help="seconds per anti-entropy slice before the "
                         "walk parks its cursor ([anti-entropy] "
                         "round-budget; 0 = whole holder per round)")
    ps.add_argument("--tenants-enabled", action="store_true",
                    help="enable per-tenant isolation ([tenants] "
                         "enabled): weighted-fair admission, "
                         "result-cache soft budgets and residency "
                         "tier quotas per X-Pilosa-Tenant")
    ps.add_argument("--tenant-default-share", type=int,
                    help="concurrency share (per admission class) of "
                         "tenants without their own quota ([tenants] "
                         "default-share)")
    ps.add_argument("--tenant-default-queue", type=int,
                    help="per-class queue depth of tenants without "
                         "their own quota ([tenants] default-queue)")
    ps.add_argument("--tenant-quota", action="append", default=None,
                    metavar="NAME:SHARE[:QUEUE[:CACHE[:RES]]]",
                    help="per-tenant quota entry ([tenants] quotas); "
                         "repeatable — e.g. --tenant-quota "
                         "gold:16:64:0.5 --tenant-quota free:2:8")
    ps.add_argument("--verbose", action="store_true")

    pi = sub.add_parser("import", help="bulk-import CSV bits")
    pi.add_argument("--host", default="http://127.0.0.1:10101")
    pi.add_argument("-i", "--index", required=True)
    pi.add_argument("-f", "--field", required=True)
    pi.add_argument("--create", action="store_true",
                    help="create index/field if missing")
    pi.add_argument("--clear", action="store_true")
    pi.add_argument("--field-type", default="set",
                    choices=["set", "int", "time", "mutex", "bool"])
    pi.add_argument("--min", type=int, default=0)
    pi.add_argument("--max", type=int, default=2**31 - 1)
    pi.add_argument("--time-quantum", default="")
    pi.add_argument("--batch-size", type=int, default=1_000_000,
                    help="bits buffered per request (reference buffers 10M)")
    pi.add_argument("files", nargs="+")

    pe = sub.add_parser("export", help="export a field as CSV")
    pe.add_argument("--host", default="http://127.0.0.1:10101")
    pe.add_argument("-i", "--index", required=True)
    pe.add_argument("-f", "--field", required=True)
    pe.add_argument("-o", "--output", default="-")

    pc = sub.add_parser("check", help="offline integrity check of a data dir")
    pc.add_argument("data_dir")

    pn = sub.add_parser("inspect", help="dump fragment stats from a data dir")
    pn.add_argument("data_dir")
    pn.add_argument("-i", "--index")
    pn.add_argument("-f", "--field")

    sub.add_parser("generate-config", help="print default TOML config")

    pcfg = sub.add_parser("config", help="print effective config")
    pcfg.add_argument("-c", "--config", help="TOML config file")

    args = p.parse_args(argv)
    if args.command in ("server", "import", "check", "inspect"):
        # These touch jax (directly or via bitmap/host_mode device
        # enumeration): place the persistent compile cache BEFORE any
        # backend exists.  After parsing, so --help/config/export (pure
        # HTTP) never import jax.
        from pilosa_tpu.runtime.startup import configure_compile_cache

        configure_compile_cache()
    return {
        "server": cmd_server,
        "import": cmd_import,
        "export": cmd_export,
        "check": cmd_check,
        "inspect": cmd_inspect,
        "generate-config": cmd_generate_config,
        "config": cmd_config,
    }[args.command](args)


# ---------------------------------------------------------------- server

def cmd_server(args) -> int:
    overrides = {}
    for key in ("data_dir", "bind", "name", "heartbeat_interval"):
        v = getattr(args, key, None)
        if v is not None:  # explicit 0 must override the config file
            overrides[key] = v
    if args.verbose:
        overrides["verbose"] = True
    cfg = Config.load(args.config, overrides=overrides)
    if args.seeds:
        cfg.cluster.seeds = [s for s in args.seeds.split(",") if s]
    if args.replicas is not None:
        cfg.cluster.replicas = args.replicas
    if args.anti_entropy_interval is not None:
        cfg.anti_entropy.interval = args.anti_entropy_interval
    if args.long_query_time is not None:
        cfg.observe.long_query_time = args.long_query_time
    if args.no_admission:
        cfg.admission.enabled = False
    if args.admission_default_deadline is not None:
        cfg.admission.default_deadline = args.admission_default_deadline
    for _cls in ("query", "ingest", "internal"):
        for _kind in ("cap", "queue"):
            v = getattr(args, f"admission_{_cls}_{_kind}", None)
            if v is not None:
                setattr(cfg.admission, f"{_cls}_{_kind}", v)
    if args.no_result_cache:
        cfg.cache.enabled = False
    for key in ("budget_bytes", "max_entry_bytes", "ttl"):
        v = getattr(args, f"cache_{key}", None)
        if v is not None:
            setattr(cfg.cache, key, v)
    if args.no_ragged:
        cfg.ragged.enabled = False
    for key in ("max_tape", "max_leaves"):
        v = getattr(args, f"ragged_{key}", None)
        if v is not None:
            setattr(cfg.ragged, key, v)
    if args.no_containers:
        cfg.containers.enabled = False
    if args.containers_threshold is not None:
        cfg.containers.threshold = args.containers_threshold
    if args.no_container_kinds:
        cfg.containers.kinds = False
    if args.containers_array_max is not None:
        cfg.containers.array_max = args.containers_array_max
    if args.containers_run_cap is not None:
        cfg.containers.run_cap = args.containers_run_cap
    if args.no_mesh:
        cfg.mesh.enabled = "false"
    if args.mesh_axis_size is not None:
        cfg.mesh.axis_size = args.mesh_axis_size
    if args.residency_host_budget_bytes is not None:
        cfg.residency.host_budget_bytes = \
            args.residency_host_budget_bytes
    if args.residency_disk_path is not None:
        cfg.residency.disk_path = args.residency_disk_path
    if args.residency_promote_workers is not None:
        cfg.residency.promote_workers = args.residency_promote_workers
    if args.residency_promote_wait_ms is not None:
        cfg.residency.promote_wait_ms = args.residency_promote_wait_ms
    if args.no_prefetch:
        cfg.residency.prefetch = False
    for key in ("breaker_threshold", "breaker_cooldown",
                "hedge_max_fraction"):
        v = getattr(args, key, None)
        if v is not None:
            setattr(cfg.cluster, key, v)
    if args.faultinject_armed is not None:
        cfg.faultinject.armed = args.faultinject_armed
    if args.write_policy is not None:
        cfg.replication.write_policy = args.write_policy
    if args.hint_max_bytes is not None:
        cfg.replication.hint_max_bytes = args.hint_max_bytes
    if args.anti_entropy_round_budget is not None:
        cfg.anti_entropy.round_budget = args.anti_entropy_round_budget
    if args.rebalance_transfer_budget is not None:
        cfg.rebalance.transfer_budget = args.rebalance_transfer_budget
    if args.rebalance_dual_write_policy is not None:
        cfg.rebalance.dual_write_policy = args.rebalance_dual_write_policy
    if args.no_ingest_delta:
        cfg.ingest.delta_enabled = False
    for key in ("delta_budget_bytes", "compact_threshold_bits",
                "compact_interval"):
        v = getattr(args, f"ingest_{key}", None)
        if v is not None:
            setattr(cfg.ingest, key, v)
    if args.tenants_enabled:
        cfg.tenants.enabled = True
    if args.tenant_default_share is not None:
        cfg.tenants.default_share = args.tenant_default_share
    if args.tenant_default_queue is not None:
        cfg.tenants.default_queue = args.tenant_default_queue
    if args.tenant_quota:
        from pilosa_tpu.serve.tenant import parse_quota_spec

        quotas = dict(cfg.tenants.quotas)
        for spec in args.tenant_quota:
            quotas.update(parse_quota_spec(spec))
        cfg.tenants.quotas = quotas
    return run_server(cfg)


def run_server(cfg: Config, ready_event: threading.Event | None = None,
               stop_event: threading.Event | None = None) -> int:
    """Build and run a node until SIGTERM/SIGINT (reference
    server.Command.Start, server/server.go:137-220)."""
    # Multi-host data plane joins FIRST: jax.distributed must see a
    # fresh runtime, before any import triggers backend init (no-op
    # unless JAX_NUM_PROCESSES/JAX_COORDINATOR_ADDRESS are set).
    from pilosa_tpu.parallel import multihost

    multihost.initialize()

    from pilosa_tpu import stats as _stats
    from pilosa_tpu import tracing as _tracing
    from pilosa_tpu.logger import StandardLogger, VerboseLogger
    from pilosa_tpu.server.server import Server

    log_stream = open(cfg.log_path, "a") if cfg.log_path else None
    log = (VerboseLogger(log_stream) if cfg.verbose
           else StandardLogger(log_stream))
    statsd = None
    if cfg.metric.service == "nop":
        stats = _stats.NOP
    elif cfg.metric.service == "statsd":
        from pilosa_tpu.statsd import StatsdClient

        sd_host, _, sd_port = cfg.metric.host.partition(":")
        statsd = StatsdClient(sd_host or "127.0.0.1",
                              int(sd_port or 8125))
        # fan out so /metrics and /debug/vars keep working too
        stats = _stats.MultiStatsClient([_stats.MemStatsClient(), statsd])
    else:
        stats = _stats.MemStatsClient()
    exporter = None
    if cfg.tracing.endpoint:
        exporter = _tracing.OtlpExporter(cfg.tracing.endpoint,
                                         service=cfg.name or "pilosa-tpu")
        _tracing.set_global_tracer(exporter)
    elif cfg.tracing.enabled:
        _tracing.set_global_tracer(_tracing.MemTracer())
    from pilosa_tpu.runtime import filebudget

    filebudget.set_cap(cfg.max_wal_files)
    srv = Server(
        cfg.expanded_data_dir(),
        host=cfg.host,
        port=cfg.port,
        name=cfg.name or None,
        seeds=cfg.cluster.seeds,
        replica_n=cfg.cluster.replicas,
        partition_n=cfg.cluster.partitions,
        coordinator=cfg.cluster.coordinator,
        anti_entropy_interval=cfg.anti_entropy.interval,
        heartbeat_interval=cfg.heartbeat_interval,
        metric_poll_interval=cfg.metric.poll_interval,
        long_query_time=cfg.cluster.long_query_time,
        max_writes_per_request=cfg.max_writes_per_request,
        tls_cert=cfg.tls.certificate_path or None,
        tls_key=cfg.tls.key_path or None,
        tls_skip_verify=cfg.tls.skip_verify,
        heap_profile=cfg.profile.heap,
        heap_profile_frames=cfg.profile.heap_frames,
        coalescer_enabled=cfg.coalescer.enabled,
        coalescer_window_ms=cfg.coalescer.window_ms,
        coalescer_max_batch=cfg.coalescer.max_batch,
        ragged_enabled=cfg.ragged.enabled,
        ragged_max_tape=cfg.ragged.max_tape,
        ragged_max_leaves=cfg.ragged.max_leaves,
        ragged_prewarm=cfg.ragged.prewarm,
        vm_enabled=cfg.vm.enabled,
        vm_min_domain=cfg.vm.min_domain,
        vm_max_prefetch=cfg.vm.max_prefetch,
        observe_enabled=cfg.observe.enabled,
        observe_recent=cfg.observe.recent,
        observe_long_query_time=cfg.observe.long_query_time,
        observe_device_sample_interval=cfg.observe.device_sample_interval,
        observe_fanin_timeout=cfg.observe.fanin_timeout,
        observe_device_peak_gbps=cfg.observe.device_peak_gbps,
        observe_profiler_max_seconds=cfg.observe.profiler_max_seconds,
        observe_journal=cfg.observe.journal,
        observe_journal_size=cfg.observe.journal_size,
        observe_journal_kinds=cfg.observe.journal_kinds,
        admission_enabled=cfg.admission.enabled,
        admission_query_cap=cfg.admission.query_cap,
        admission_query_queue=cfg.admission.query_queue,
        admission_ingest_cap=cfg.admission.ingest_cap,
        admission_ingest_queue=cfg.admission.ingest_queue,
        admission_internal_cap=cfg.admission.internal_cap,
        admission_internal_queue=cfg.admission.internal_queue,
        admission_default_deadline=cfg.admission.default_deadline,
        cache_enabled=cfg.cache.enabled,
        cache_budget_bytes=cfg.cache.budget_bytes,
        cache_max_entry_bytes=cfg.cache.max_entry_bytes,
        cache_ttl=cfg.cache.ttl,
        ingest_delta_enabled=cfg.ingest.delta_enabled,
        containers_enabled=cfg.containers.enabled,
        containers_threshold=cfg.containers.threshold,
        containers_kinds=cfg.containers.kinds,
        containers_array_max=cfg.containers.array_max,
        containers_run_cap=cfg.containers.run_cap,
        mesh_enabled=cfg.mesh.enabled,
        mesh_axis_size=cfg.mesh.axis_size,
        residency_host_budget_bytes=cfg.residency.host_budget_bytes,
        residency_disk_path=cfg.residency.disk_path,
        residency_disk_budget_bytes=cfg.residency.disk_budget_bytes,
        residency_promote_workers=cfg.residency.promote_workers,
        residency_promote_queue=cfg.residency.promote_queue,
        residency_promote_wait_ms=cfg.residency.promote_wait_ms,
        residency_prefetch=cfg.residency.prefetch,
        residency_prefetch_interval=cfg.residency.prefetch_interval,
        ingest_delta_budget_bytes=cfg.ingest.delta_budget_bytes,
        ingest_compact_threshold_bits=cfg.ingest.compact_threshold_bits,
        ingest_compact_interval=cfg.ingest.compact_interval,
        breaker_threshold=cfg.cluster.breaker_threshold,
        breaker_cooldown=cfg.cluster.breaker_cooldown,
        hedge_min_samples=cfg.cluster.hedge_min_samples,
        hedge_deviations=cfg.cluster.hedge_deviations,
        hedge_min_ms=cfg.cluster.hedge_min_ms,
        hedge_max_fraction=cfg.cluster.hedge_max_fraction,
        faultinject_armed=cfg.faultinject.armed,
        write_policy=cfg.replication.write_policy,
        hint_max_bytes=cfg.replication.hint_max_bytes,
        hint_max_age=cfg.replication.hint_max_age,
        hint_replay_interval=cfg.replication.replay_interval,
        anti_entropy_jitter=cfg.anti_entropy.jitter,
        anti_entropy_round_budget=cfg.anti_entropy.round_budget,
        anti_entropy_peer_timeout=cfg.anti_entropy.peer_timeout,
        rebalance_transfer_budget=cfg.rebalance.transfer_budget,
        rebalance_dual_write_policy=cfg.rebalance.dual_write_policy,
        rebalance_cursor_path=cfg.rebalance.cursor_path or None,
        rebalance_backoff_base=cfg.rebalance.backoff_base,
        rebalance_backoff_cap=cfg.rebalance.backoff_cap,
        rebalance_peer_timeout=cfg.rebalance.peer_timeout,
        tenants_enabled=cfg.tenants.enabled,
        tenants_default_share=cfg.tenants.default_share,
        tenants_default_queue=cfg.tenants.default_queue,
        tenants_default_cache_share=cfg.tenants.default_cache_share,
        tenants_default_residency_share=(
            cfg.tenants.default_residency_share),
        tenants_quotas=cfg.tenants.quotas or None,
        logger=log,
        stats=stats,
    )
    if statsd is not None:
        srv._closers.append(statsd.close)
    if exporter is not None:
        # final flush + thread join on shutdown (trailing spans ship)
        srv._closers.append(exporter.close)
    stop = stop_event or threading.Event()

    def _sig(signum, frame):
        stop.set()

    try:
        signal.signal(signal.SIGTERM, _sig)
        signal.signal(signal.SIGINT, _sig)
    except ValueError:
        pass  # not the main thread (tests)
    srv.open()
    from pilosa_tpu.runtime.startup import backend_info

    be = backend_info()
    log.printf("backend: platform=%s device_kind=%s devices=%d engine=%s "
               "compile_cache=%s", be["platform"], be["deviceKind"],
               be["deviceCount"], be["engine"], be["compileCacheDir"])
    log.printf("listening on %s (node %s)", srv.uri, srv.cluster.local_id)
    if ready_event is not None:
        ready_event.set()
    stop.wait()
    srv.close()
    return 0


# ---------------------------------------------------------------- import

# native-path read granularity; tests shrink it to exercise boundaries
_IMPORT_CHUNK_BYTES = 32 << 20


def cmd_import(args) -> int:
    """CSV rows are `row,col[,timestamp]` (set/time/mutex/bool) or
    `col,value` (int) — the reference's two formats (ctl/import.go:278).
    Bits are buffered, then sent via the bulk import API which routes by
    shard server-side."""
    from pilosa_tpu.server.client import InternalClient

    client = InternalClient()
    host = args.host.rstrip("/")
    if args.create:
        opts = {"type": args.field_type}
        if args.field_type == "int":
            opts.update(min=args.min, max=args.max)
        if args.field_type == "time":
            opts.update(timeQuantum=args.time_quantum or "YMDH")
        try:
            client.create_index(host, args.index, {})
        except Exception:
            pass
        try:
            client.create_field(host, args.index, args.field,
                                {"type": args.field_type, **opts})
        except Exception:
            pass

    is_value = args.field_type == "int"
    rows, cols, values, timestamps = [], [], [], []
    n_sent = 0

    def flush():
        nonlocal n_sent, rows, cols, values, timestamps
        if is_value and cols:
            client.import_values(host, args.index, args.field, cols, values)
        elif cols:
            client.import_bits(
                host, args.index, args.field, rows, cols,
                timestamps=[t for t in timestamps] if any(
                    t is not None for t in timestamps) else None,
                clear=args.clear)
        n_sent += len(cols)
        rows, cols, values, timestamps = [], [], [], []

    import contextlib
    from pilosa_tpu import csvload

    def consume_python(stream, path, line_base=0):
        """General path: full CSV semantics incl. timestamps/quoting
        (reference bufferBits, ctl/import.go:173)."""
        reader = csv.reader(stream)
        while True:
            try:
                rec = next(reader)
            except StopIteration:
                return True
            except csv.Error as e:
                print(f"{path}:{line_base + reader.line_num}: "
                      f"bad record: {e}", file=sys.stderr)
                return False
            line_no = line_base + reader.line_num
            if not rec or (len(rec) == 1 and not rec[0].strip()):
                continue
            try:
                if is_value:
                    cols.append(int(rec[0]))
                    values.append(int(rec[1]))
                else:
                    rows.append(int(rec[0]))
                    cols.append(int(rec[1]))
                    timestamps.append(
                        _csv_ts(rec[2]) if len(rec) > 2 and rec[2]
                        else None)
            except (ValueError, IndexError) as e:
                print(f"{path}:{line_no}: bad record {rec!r}: {e}",
                      file=sys.stderr)
                return False
            if len(cols) >= args.batch_size:
                flush()

    def consume_native(stream, path) -> bool:
        """Fast path: the C++ loader parses all-integer two-column
        chunks straight into int64 buffers.  The FIRST chunk it cannot
        own outright — quotes anywhere (a quoted record may span chunk
        boundaries), a chunk with no newline (pathological line
        lengths, lone-CR files), or any record the parser declines —
        permanently hands the rest of the stream to the streaming
        Python path, which alone decides what is an error.  A file
        therefore parses identically with or without the native
        library."""
        raw = csvload.raw_stream(stream)
        line_base = 0
        tail = b""
        while True:
            chunk = csvload.read_chunk(raw, _IMPORT_CHUNK_BYTES)
            buf = tail + chunk
            if not buf:
                return True
            if chunk:
                cut = buf.rfind(b"\n")
                if b'"' in buf or cut < 0:
                    return consume_python(csvload.chain_text(buf, raw),
                                          path, line_base)
                complete, tail = buf[:cut + 1], buf[cut + 1:]
            else:
                complete, tail = buf, b""  # final partial record
            try:
                a, b = csvload.parse_pairs(complete)
            except csvload.NeedsFallback:
                # (complete, tail) is a split of buf — hand back the
                # original buffer, no re-concatenation
                return consume_python(csvload.chain_text(buf, raw),
                                      path, line_base)
            # top up to the batch size exactly — one POST must never
            # exceed it, even with records already buffered
            i = 0
            while i < len(a):
                take = max(1, args.batch_size - len(cols))
                sa = a[i:i + take].tolist()
                sb = b[i:i + take].tolist()
                if is_value:
                    cols.extend(sa)
                    values.extend(sb)
                else:
                    rows.extend(sa)
                    cols.extend(sb)
                    timestamps.extend([None] * len(sa))
                i += take
                if len(cols) >= args.batch_size:
                    flush()
            line_base += complete.count(b"\n")
            if not chunk:
                return True

    for path in args.files:
        stream = sys.stdin if path == "-" else open(path)
        # never close stdin — callers (and later "-" args) still need it
        ctx = contextlib.nullcontext(stream) if path == "-" else stream
        with ctx:
            ok = (consume_native(stream, path) if csvload.available()
                  else consume_python(stream, path))
            if not ok:
                return 1
    flush()
    print(f"imported {n_sent} records into "
          f"{args.index}/{args.field}", file=sys.stderr)
    return 0


def _csv_ts(raw: str) -> str:
    # reference import format uses RFC3339 (ctl/import.go:300)
    return dt.datetime.fromisoformat(raw.replace("Z", "")).isoformat()


# ---------------------------------------------------------------- export

def cmd_export(args) -> int:
    import urllib.request

    host = args.host.rstrip("/")
    out = sys.stdout if args.output == "-" else open(args.output, "w")
    with urllib.request.urlopen(f"{host}/internal/shards/max",
                                timeout=30) as resp:
        import json

        max_shards = json.loads(resp.read())["standard"]
    max_shard = max_shards.get(args.index, 0)
    try:
        for shard in range(max_shard + 1):
            with urllib.request.urlopen(
                    f"{host}/export?index={args.index}&field={args.field}"
                    f"&shard={shard}", timeout=120) as resp:
                out.write(resp.read().decode())
    finally:
        if out is not sys.stdout:
            out.close()
    return 0


# ----------------------------------------------------------------- check

def cmd_check(args) -> int:
    """Open every fragment offline and verify snapshot+WAL load, matrix
    consistency, and roaring round-trip (reference ctl/check.go:30)."""
    from pilosa_tpu.storage.roaring import decode as decode_roaring

    bad = 0
    holder = _open_holder_or_report(args.data_dir)
    if holder is None:
        return 1
    try:
        for d in holder.schema():
            idx = holder.index(d["name"])
            for f in idx.all_fields():
                for vname, view in f.views.items():
                    for shard, frag in sorted(view.fragments.items()):
                        label = f"{d['name']}/{f.name}/{vname}/{shard}"
                        try:
                            frag.check()  # structural invariants
                            blob = frag.to_roaring()
                            decode_roaring(blob)
                            for r in frag.row_ids():
                                frag.row_count(r)
                            print(f"ok   {label}")
                        except Exception as e:
                            bad += 1
                            print(f"FAIL {label}: {e}")
    finally:
        holder.close()
    print(f"{'FAILED' if bad else 'passed'}: {bad} corrupt fragment(s)")
    return 1 if bad else 0


# --------------------------------------------------------------- inspect

def _open_holder_or_report(data_dir: str):
    """Open a data dir for the offline tools, reporting (instead of
    tracebacking) when it is corrupt or locked by a live server."""
    from pilosa_tpu.models.holder import Holder

    try:
        return Holder(data_dir)
    except Exception as e:
        print(f"FAIL open {data_dir}: {e}")
        print("FAILED: holder did not open")
        return None


def cmd_inspect(args) -> int:
    holder = _open_holder_or_report(args.data_dir)
    if holder is None:
        return 1
    bad = 0
    try:
        for d in holder.schema():
            if args.index and d["name"] != args.index:
                continue
            idx = holder.index(d["name"])
            for f in idx.all_fields():
                if args.field and f.name != args.field:
                    continue
                for vname, view in sorted(f.views.items()):
                    for shard, frag in sorted(view.fragments.items()):
                        label = f"{d['name']}/{f.name}/{vname}/shard={shard}"
                        try:
                            ids = frag.row_ids()
                            bits = sum(frag.row_count(r) for r in ids)
                            print(f"{label}: rows={len(ids)} bits={bits} "
                                  f"opN={frag._op_n}")
                        except Exception as e:
                            bad += 1
                            print(f"{label}: FAIL {e}")
    finally:
        holder.close()
    return 1 if bad else 0


# ---------------------------------------------------------------- config

def cmd_generate_config(args) -> int:
    print(Config().to_toml(), end="")
    return 0


def cmd_config(args) -> int:
    print(Config.load(getattr(args, "config", None)).to_toml(), end="")
    return 0


if __name__ == "__main__":
    sys.exit(main())
