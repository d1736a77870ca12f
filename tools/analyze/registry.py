"""The declarative project model pilosa-lint checks against.

Everything here is an INVARIANT REGISTRY, not analyzer configuration:
each entry names a concurrency/caching/device contract the codebase
relies on, in one place, machine-checked by the passes.  Growing the
system means growing this file — a new lock-guarded structure, metric
family, or process-wide config knob is declared here and the analyzer
holds every touch to the declared discipline from then on.

Paths are repo-relative suffixes (``models/fragment.py``) so the suite
works from any checkout root and on synthetic fixture paths in tests.
"""

from __future__ import annotations

from dataclasses import dataclass, field

# --------------------------------------------------------- P1: lock model

#: Attribute names that hold locks; ``with <recv>.<one of these>:``
#: marks a held-lock region for receiver ``<recv>``, and a bare
#: ``with <name>:`` where <name> ends in ``_lock`` marks a module-level
#: region.
LOCK_ATTR_NAMES = ("_lock", "_global_lock", "_cfg_lock", "_graph_lock",
                   "_plan_lock", "_route_lock")


@dataclass(frozen=True)
class ClassLockRule:
    """One class whose listed attributes are guarded by ``self.<lock>``.

    ``helpers`` are methods with a documented caller-holds-the-lock
    contract (the ``*_locked`` suffix is honored automatically, as is
    ``__init__`` — construction is single-threaded).  Listing a method
    here IS the declaration of that contract; the reason strings keep
    the registry reviewable.
    """

    lock: str
    attrs: frozenset
    helpers: dict = field(default_factory=dict)  # name -> contract note


CLASS_LOCKS: dict[tuple, ClassLockRule] = {
    ("models/fragment.py", "Fragment"): ClassLockRule(
        lock="_lock",
        attrs=frozenset({
            "_rows", "_gen", "_delta_seq", "_delta", "_op_n", "_wal",
            "_stack_cache", "_device_cache", "_container_cache",
            "_blocks_cache", "_snapshotting", "_closed",
        }),
        helpers={
            "_load": "construction-time replay, single-threaded",
            "_replay_wal": "construction-time replay, single-threaded",
            "_replay_wal_file": "construction-time replay",
            "_wal_append": "every caller is a mutator holding _lock",
            "_apply_set": "mutation primitive; callers hold _lock",
            "_apply_clear": "mutation primitive; callers hold _lock",
            "_apply_bulk": "mutation primitive; callers hold _lock",
            "_merge_roaring": "callers hold _lock (or _load replay)",
            "_merge_positions": "callers hold _lock (or _load replay)",
            "_row_array": "mutation primitive; callers hold _lock",
            "_maybe_snapshot": "called at the tail of locked mutators",
            "_delta_or_new": "delta write path; callers hold _lock",
            "_delta_set_bit": "delta write path; callers hold _lock",
            "_delta_row_seq": "token read under the caller's _lock",
            "_bump_gen": "the one place _gen moves; every caller is a "
                         "mutator holding _lock (or _load replay)",
            "_bump_delta_seq": "the one place _delta_seq moves; delta "
                               "write path, callers hold _lock",
        },
    ),
    ("ingest/compactor.py", "Compactor"): ClassLockRule(
        lock="_lock",
        attrs=frozenset({
            "_frags", "_pending_bytes", "_paused", "_thread",
            "compactions", "compacted_bits", "inline_flushes",
            "compact_skipped", "delta_writes",
        }),
    ),
    ("runtime/resultcache.py", "ResultCache"): ClassLockRule(
        lock="_lock",
        attrs=frozenset({
            "_entries", "_flights", "_noflight", "bytes", "hits",
            "misses", "fills", "evictions", "invalidations",
            "skipped_oversize", "flight_joins", "flight_served",
            "reordered", "_tenant_bytes", "_tenant_lru", "_tenant_counters",
            "tenant_pref_evictions",
        }),
        helpers={
            "_tc_locked": "callers hold self._lock",
            "_tenant_track_locked": "callers hold self._lock",
            "_tenant_untrack_locked": "callers hold self._lock",
            "_tenant_touch_locked": "callers hold self._lock",
            "_victim_key_locked": "callers hold self._lock",
        },
    ),
    ("parallel/coalescer.py", "Coalescer"): ClassLockRule(
        lock="_lock",
        attrs=frozenset({"_pending", "inflight", "_drains", "flushes"}),
        # _tape_memo is deliberately UNREGISTERED: racy-by-design
        # (a duplicate compile is wasted work, never a wrong entry —
        # see the inline comment at its definition)
    ),
    ("runtime/residency.py", "ResidencyManager"): ClassLockRule(
        lock="_lock",
        attrs=frozenset({
            "_entries", "total", "_per_device", "_by_kind",
            "evictions", "admits", "high_water",
            "_host", "_host_bytes", "_disk", "_disk_bytes",
            "_spill_seq", "demotions", "tier_hits", "tier_misses",
            "tier_spills", "tier_spill_drops", "disk_hits",
            "fallbacks", "oom_budget_shrinks", "_prefetched",
            "prefetch_useful", "_tenant_bytes", "_tenant_host_bytes",
            "_tenant_pressure",
        }),
        helpers={
            "_tenant_charge_locked": "callers hold self._lock",
            "_tenant_host_charge_locked": "callers hold self._lock",
        },
        # ``budget`` is deliberately UNREGISTERED: written only under
        # the lock (note_oom_feedback), read lock-free by the entry
        # caps and stats — the monotone-ish operator-knob discipline
        # (a stale read admits one borderline entry, never corrupts)
    ),
    ("runtime/residency.py", "Promoter"): ClassLockRule(
        lock="_lock",
        attrs=frozenset({
            "_queue", "_flights", "_workers", "_epoch", "promotions",
            "failures", "sheds", "prefetch_issued",
            "prefetch_completed", "prefetch_shed",
        }),
    ),
    ("parallel/hints.py", "HintStore"): ClassLockRule(
        lock="_lock",
        attrs=frozenset({"_queues", "_total_bytes"}),
        helpers={
            "_parse_file_locked": "called from _load under self._lock",
            "_queue_locked": "callers hold self._lock",
            "_rewrite_locked": "callers hold self._lock",
        },
    ),
    ("parallel/rebalance.py", "RebalanceCoordinator"): ClassLockRule(
        lock="_plan_lock",
        attrs=frozenset({"_plan", "_last"}),
        # _abort_requested/_thread/_halt are deliberately
        # UNREGISTERED: a bool flag checked once after the worker
        # join, a thread handle, and an Event — single-writer
        # signals, not shared mutable state
    ),
    ("parallel/cluster.py", "Cluster"): ClassLockRule(
        lock="_route_lock",
        attrs=frozenset({"_shard_routes"}),
    ),
    ("serve/admission.py", "AdmissionController"): ClassLockRule(
        lock="_lock",
        # ``_gates`` itself is immutable after construction (the dict
        # is only ever READ to find a gate; all mutable state lives in
        # gate/tenant fields touched under the lock), so it is
        # deliberately unregistered — the *_locked helper contracts
        # below are the checked surface
        attrs=frozenset(),
        helpers={
            "_wake_tenants_locked": "called from _release under "
                                    "self._lock",
            "_query_pressure_locked": "callers hold self._lock",
            "_tenant_dict_locked": "callers hold self._lock",
        },
    ),
    ("observe.py", "EventJournal"): ClassLockRule(
        lock="_lock",
        attrs=frozenset({"_ring", "_seq", "_by_kind", "_dropped"}),
        # node_id / kinds are deliberately UNREGISTERED: operator
        # knobs rebound under the module _cfg_lock and read at emit
        # time (a momentarily stale read stamps one event with the
        # old node id / filter, never corrupts the ring)
    ),
    ("parallel/cluster.py", "CircuitBreaker"): ClassLockRule(
        lock="_lock",
        attrs=frozenset({"_state", "_failures", "_opened_t",
                         "_probing", "_probe_t"}),
        # the cumulative transition counters (opened/closed/
        # half_opens/fast_fails) are deliberately UNREGISTERED:
        # monotone ints read lock-free by the gauge publisher (the
        # _gen discipline — a stale read is a stale gauge, never a
        # wrong transition)
    ),
}

#: Guarded attributes checked on NON-self receivers anywhere in the
#: sweep: ``frag._rows`` needs an active ``with frag._lock`` region.
#: mode "rw" checks loads and stores; "w" checks stores only — the
#: monotone token ints (_gen/_delta_seq) are read lock-free by design
#: (GIL-atomic int loads; the stamp-before-read discipline tolerates
#: any interleaving, see runtime/resultcache.py's module docstring).
CROSS_OBJECT_ATTRS: dict[str, str] = {
    "_rows": "rw",
    "_delta": "rw",
    "_frags": "rw",
    "_flights": "rw",
    "_noflight": "rw",
    "_gen": "w",
    "_delta_seq": "w",
}


@dataclass(frozen=True)
class ModuleGlobalRule:
    """One module-level global guarded by a module-level lock.  mode
    as above; ``attrs=True`` additionally guards attribute WRITES
    through the name (``_cfg.delta_enabled = ...``)."""

    name: str
    lock: str
    mode: str = "rw"
    attrs: bool = False


MODULE_LOCKS: dict[str, tuple] = {
    "ops/tape.py": (
        ModuleGlobalRule("_counters", "_lock", "rw"),
        ModuleGlobalRule("_lowered", "_lock", "rw"),
        ModuleGlobalRule("_vm_lowered", "_lock", "rw"),
    ),
    "ops/containers.py": (
        ModuleGlobalRule("_counters", "_lock", "rw"),
        ModuleGlobalRule("_cfg", "_cfg_lock", "w", attrs=True),
        ModuleGlobalRule("_baseline", "_cfg_lock", "rw"),
        ModuleGlobalRule("_refs", "_cfg_lock", "rw"),
        ModuleGlobalRule("_stage_memo", "_stage_lock", "w"),
        ModuleGlobalRule("_megapool_memo", "_mega_lock", "w"),
    ),
    "runtime/resultcache.py": (
        # reads are the lock-free fast path (documented); rebinds only
        # under the construction lock
        ModuleGlobalRule("_global", "_global_lock", "w"),
    ),
    "ingest/compactor.py": (
        ModuleGlobalRule("_global", "_global_lock", "w"),
        ModuleGlobalRule("_refs", "_global_lock", "w"),
    ),
    "ingest/__init__.py": (
        ModuleGlobalRule("_cfg", "_cfg_lock", "w", attrs=True),
        ModuleGlobalRule("_baseline", "_cfg_lock", "rw"),
    ),
    "serve/tenant.py": (
        # reads (policy()/enabled()/quota_for) are the lock-free hot
        # path by design — a momentarily stale policy admits one
        # borderline request, never corrupts; rebinds/attr-writes only
        # under the config lock
        ModuleGlobalRule("_cfg", "_cfg_lock", "w", attrs=True),
        ModuleGlobalRule("_baseline", "_cfg_lock", "rw"),
        ModuleGlobalRule("_refs", "_cfg_lock", "rw"),
    ),
    "parallel/meshexec.py": (
        ModuleGlobalRule("_counters", "_lock", "rw"),
        ModuleGlobalRule("_cfg", "_cfg_lock", "w", attrs=True),
        ModuleGlobalRule("_baseline", "_cfg_lock", "rw"),
        ModuleGlobalRule("_refs", "_cfg_lock", "rw"),
        ModuleGlobalRule("_mesh_cache", "_cfg_lock", "w"),
    ),
    "runtime/residency.py": (
        ModuleGlobalRule("_cfg", "_cfg_lock", "w", attrs=True),
        ModuleGlobalRule("_baseline", "_cfg_lock", "rw"),
        ModuleGlobalRule("_refs", "_cfg_lock", "rw"),
        ModuleGlobalRule("_global", "_global_lock", "w"),
    ),
    "parallel/hints.py": (
        ModuleGlobalRule("_counters", "_lock", "rw"),
        ModuleGlobalRule("_cfg", "_cfg_lock", "w", attrs=True),
        ModuleGlobalRule("_baseline", "_cfg_lock", "rw"),
        ModuleGlobalRule("_refs", "_cfg_lock", "rw"),
    ),
    "parallel/rebalance.py": (
        ModuleGlobalRule("_counters", "_lock", "rw"),
        ModuleGlobalRule("_cfg", "_cfg_lock", "w", attrs=True),
        ModuleGlobalRule("_baseline", "_cfg_lock", "rw"),
        ModuleGlobalRule("_refs", "_cfg_lock", "rw"),
    ),
    "parallel/syncer.py": (
        ModuleGlobalRule("_counters", "_lock", "rw"),
    ),
    "perfobs.py": (
        ModuleGlobalRule("_counters", "_lock", "rw"),
        ModuleGlobalRule("_table", "_lock", "rw"),
        ModuleGlobalRule("_cfg", "_cfg_lock", "w", attrs=True),
        ModuleGlobalRule("_baseline", "_cfg_lock", "rw"),
        ModuleGlobalRule("_refs", "_cfg_lock", "rw"),
        # the module-bool fast gate and the peak cache: rebinds under
        # the config lock; sites read them lock-free by design (a
        # stale read drops or takes one sample, never corrupts)
        ModuleGlobalRule("enabled", "_cfg_lock", "w"),
        # the profiler capture bookkeeping dict (the _prof_lock is the
        # start..stop exclusivity latch, not a data guard)
        ModuleGlobalRule("_prof", "_prof_state_lock", "rw", attrs=True),
    ),
    "models/fragment.py": (
        # the wal.* replay-health counters (module-level; every
        # fragment's construction-time replay can note a torn tail)
        ModuleGlobalRule("_counters", "_wal_counter_lock", "rw"),
    ),
    "observe.py": (
        # the event-journal fast gate and the journal handle itself:
        # rebinds only under the config lock; emission sites read both
        # lock-free by design (the faultinject `armed` discipline — a
        # stale read drops or keeps one event, never corrupts)
        ModuleGlobalRule("journal_on", "_cfg_lock", "w"),
        ModuleGlobalRule("_journal", "_cfg_lock", "w"),
        ModuleGlobalRule("_baseline", "_cfg_lock", "rw"),
        ModuleGlobalRule("_refs", "_cfg_lock", "rw"),
        # trace-assembly counters behind bump_trace/trace_counters
        ModuleGlobalRule("_trace_counters", "_trace_lock", "rw"),
    ),
    "faultinject.py": (
        # the failpoint registry: every read AND write of the armed
        # point table goes through the module lock (hit() is only
        # reached when something is armed, so the lock is off the
        # disarmed hot path by construction — the `armed` bool gate)
        ModuleGlobalRule("_points", "_lock", "rw"),
        # the fast gate itself: rebinds only under the lock; sites
        # read it lock-free by design (a stale read skips or probes
        # one injection window, never corrupts the registry)
        ModuleGlobalRule("armed", "_lock", "w"),
    ),
}

# ------------------------------------------------------ P2: mutation model


@dataclass(frozen=True)
class GenAuditRule:
    """Generation-audit model for one class: methods that (directly or
    via same-class helper calls) hit a mutation primitive or write a
    mutation target must also (transitively) bump a generation
    attribute.  ``primitives`` are the leaf write helpers themselves —
    their CALLERS own the bump.  ``exempt`` maps method -> reason."""

    bump_attrs: frozenset
    primitives: frozenset
    targets: frozenset          # attrs whose writes count as mutation
    delta_mutators: frozenset   # method calls that write a delta plane
    exempt: dict = field(default_factory=dict)


GEN_AUDIT: dict[tuple, GenAuditRule] = {
    ("models/fragment.py", "Fragment"): GenAuditRule(
        bump_attrs=frozenset({"_gen", "_delta_seq"}),
        primitives=frozenset({
            "_apply_set", "_apply_clear", "_apply_bulk",
            "_merge_roaring", "_merge_positions", "_row_array",
        }),
        targets=frozenset({"_rows"}),
        delta_mutators=frozenset({"add_bit", "add_positions"}),
        exempt={
            "_replay_wal_file": "WAL replay applies records one file "
                                "at a time; _replay_wal bumps _gen "
                                "once after both files",
        },
    ),
    ("models/field.py", "Field"): GenAuditRule(
        bump_attrs=frozenset({"_gen", "_delta_seq"}),
        primitives=frozenset(),
        targets=frozenset({"_rows"}),
        delta_mutators=frozenset({"add_bit", "add_positions"}),
    ),
}

# ------------------------------------------------------ P3: blocking model

#: (dotted-call suffixes, attr-call names) treated as blocking or
#: device-dispatching.  ``.join``/``.result``/``.wait`` match by attr;
#: string-constant receivers are excluded for ``join`` (str.join) and
#: receivers named in CONDITION_ATTRS for ``wait`` (Condition.wait
#: releases the lock while waiting — the one legitimate wait-under-
#: lock).
BLOCKING_CALL_SUFFIXES = (
    "time.sleep",
    "urllib.request.urlopen",
    "socket.create_connection",
    "jax.block_until_ready",
)
BLOCKING_ATTRS = ("join", "result", "wait", "block_until_ready",
                  "urlopen")
DEVICE_DISPATCH_NAMES = ("device_put",)
CONDITION_ATTRS = ("_snap_done",)

# ----------------------------------------------------- P4: recompile model

#: Call suffixes that reach a jitted program whose lowering
#: specializes on input shape.
JIT_ENTRY_SUFFIXES = ("expr.evaluate", "tape.execute", "_tape.execute",
                      "tape.execute_vm", "_tape.execute_vm",
                      "expr.evaluate_gathered",
                      "expr.evaluate_gathered_kinds",
                      "gathered_count_array_array",
                      "gathered_count_array_bitmap")
#: Batch-stack builders whose output shape tracks their (variable)
#: input length.
STACK_BUILDER_SUFFIXES = ("jnp.stack", "jnp.concatenate", "np.stack",
                          "numpy.stack")
#: Referencing any of these names in the same function is the evidence
#: the batch axis was routed through a pow2/size-class discipline.
SHAPE_HELPER_NAMES = frozenset({
    "_pow2", "pow2", "size_class", "_pad_batch", "_padded_rows",
    "MIN_BUCKET", "prewarm",
})
#: jax attribute roots whose module-import-time CALLS are flagged
#: (device init / tracing at import).  jax.jit/vmap wrapping is lazy
#: and allowed.
IMPORT_TIME_JAX_ROOTS = ("jnp", "jax")
IMPORT_TIME_ALLOWED = ("jax.jit", "jax.vmap", "functools.partial",
                       "jax.tree_util")

# -------------------------------------------------------- P5: config model


@dataclass(frozen=True)
class ConfigGuardRule:
    """One process-wide config surface: calling a mutator in a module
    requires that module to also reference every name in ``pair`` —
    the capture/restore (or retain/release) protocol that makes the
    mutation reversible.  ``owner`` modules (the definition site) and
    accessor-alias writes (``cfg = <x>.config(); cfg.attr = ...``)
    are handled by the pass."""

    mutator_suffixes: tuple
    pair: tuple
    owner_suffixes: tuple
    what: str


CONFIG_GUARDS = (
    ConfigGuardRule(
        mutator_suffixes=("ingest.configure", "_ingest.configure"),
        pair=("capture_baseline", "restore_baseline"),
        owner_suffixes=("ingest/__init__.py",),
        what="the process-wide [ingest] runtime config",
    ),
    ConfigGuardRule(
        mutator_suffixes=("compactor.retain", "_compactor.retain"),
        pair=("release",),
        owner_suffixes=("ingest/compactor.py",),
        what="the refcounted shared compactor scan thread",
    ),
    ConfigGuardRule(
        mutator_suffixes=("containers.configure",
                          "_containers.configure"),
        pair=("retain", "release"),
        owner_suffixes=("ops/containers.py",),
        what="the process-wide [containers] runtime config",
    ),
    ConfigGuardRule(
        mutator_suffixes=("containers.retain", "_containers.retain"),
        pair=("release",),
        owner_suffixes=("ops/containers.py",),
        what="the refcounted [containers] baseline",
    ),
    ConfigGuardRule(
        mutator_suffixes=("faultinject.arm", "_faultinject.arm"),
        pair=("disarm",),
        owner_suffixes=("faultinject.py",),
        what="the process-wide failpoint registry",
    ),
    ConfigGuardRule(
        mutator_suffixes=("residency.configure",
                          "_residency.configure"),
        pair=("retain", "release"),
        owner_suffixes=("runtime/residency.py",),
        what="the process-wide [residency] runtime config",
    ),
    ConfigGuardRule(
        mutator_suffixes=("residency.retain", "_residency.retain"),
        pair=("release",),
        owner_suffixes=("runtime/residency.py",),
        what="the refcounted [residency] baseline",
    ),
    ConfigGuardRule(
        mutator_suffixes=("hints.configure", "_hints.configure"),
        pair=("retain", "release"),
        owner_suffixes=("parallel/hints.py",),
        what="the process-wide [replication] runtime config",
    ),
    ConfigGuardRule(
        mutator_suffixes=("hints.retain", "_hints.retain"),
        pair=("release",),
        owner_suffixes=("parallel/hints.py",),
        what="the refcounted [replication] baseline",
    ),
    ConfigGuardRule(
        mutator_suffixes=("rebalance.configure", "_rebalance.configure",
                          "_rebalance1.configure"),
        pair=("retain", "release"),
        owner_suffixes=("parallel/rebalance.py",),
        what="the process-wide [rebalance] runtime config",
    ),
    ConfigGuardRule(
        mutator_suffixes=("rebalance.retain", "_rebalance.retain",
                          "_rebalance1.retain"),
        pair=("release",),
        owner_suffixes=("parallel/rebalance.py",),
        what="the refcounted [rebalance] baseline",
    ),
    ConfigGuardRule(
        mutator_suffixes=("tenant.configure", "_tenant.configure",
                          "_tenantcfg.configure"),
        pair=("retain", "release"),
        owner_suffixes=("serve/tenant.py",),
        what="the process-wide [tenants] runtime config",
    ),
    ConfigGuardRule(
        mutator_suffixes=("tenant.retain", "_tenant.retain",
                          "_tenantcfg.retain"),
        pair=("release",),
        owner_suffixes=("serve/tenant.py",),
        what="the refcounted [tenants] baseline",
    ),
    ConfigGuardRule(
        mutator_suffixes=("meshexec.configure", "_meshexec.configure"),
        pair=("retain", "release"),
        owner_suffixes=("parallel/meshexec.py",),
        what="the process-wide [mesh] runtime config",
    ),
    ConfigGuardRule(
        mutator_suffixes=("meshexec.retain", "_meshexec.retain"),
        pair=("release",),
        owner_suffixes=("parallel/meshexec.py",),
        what="the refcounted [mesh] baseline",
    ),
    ConfigGuardRule(
        mutator_suffixes=("observe.configure", "_observe.configure",
                          "_observe1.configure"),
        pair=("retain", "release"),
        owner_suffixes=("observe.py",),
        what="the process-wide [observe] event-journal config",
    ),
    ConfigGuardRule(
        mutator_suffixes=("observe.retain", "_observe.retain",
                          "_observe1.retain"),
        pair=("release",),
        owner_suffixes=("observe.py",),
        what="the refcounted [observe] journal baseline",
    ),
    ConfigGuardRule(
        mutator_suffixes=("perfobs.configure", "_perfobs.configure"),
        pair=("retain", "release"),
        owner_suffixes=("perfobs.py",),
        what="the process-wide engine-observatory runtime config",
    ),
    ConfigGuardRule(
        mutator_suffixes=("perfobs.retain", "_perfobs.retain"),
        pair=("release",),
        owner_suffixes=("perfobs.py",),
        what="the refcounted engine-observatory baseline",
    ),
)

#: ``<x>.config()`` accessors whose result's attribute WRITES count as
#: mutating the guarded config (same pairing requirement).
CONFIG_ACCESSOR_SUFFIXES = ("ingest.config", "_ingest.config")

# ------------------------------------------------------- P6: metric model

#: Stats-registry method names whose first string-literal argument is
#: a metric name.
STATS_CALL_ATTRS = ("count", "count_with_tags", "gauge", "histogram",
                    "timing")
#: Free functions that feed the module counter registries (published
#: as gauges at scrape time).
STATS_CALL_FUNCS = ("bump",)
#: Module-level dict literals whose string keys are metric names
#: (ops/tape.py's counter registry).
STATS_DICT_NAMES = ("_counters",)
