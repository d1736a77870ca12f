"""Mesh-native fused execution: the serving path's device-mesh SPMD
layer.

Before this module the modern engines — fused expression programs
(ops/expr.py), ragged op-tape batches (ops/tape.py), compressed
container gathers (ops/containers.py) — each ran as ONE launch, but
that launch landed on a single device (or leaned on XLA's implicit
GSPMD propagation when stacks happened to be sharded).  The
reference's only scale-out is host map-reduce over shards
(executor.go:2455), and our port mirrored it above the device.  This
module replaces that with the DrJAX shape (PAPERS.md 2403.07128):
map-reduce expressed as sharded one-launch JAX programs —

- **Layout** — the shard axis of every fused operand (dense row
  stacks, delta planes, tape register batches, container gather
  domains) lays out across a named 1-D ``jax.sharding.Mesh`` via
  ``NamedSharding``; container word pools replicate (gather indices
  cross shard boundaries by construction).  Placement is the shard
  plan: shard-axis row *i* lives on device ``i // (rows/axis)``, and
  ``models/field.py`` pads the axis to a multiple of the mesh size so
  blocks split evenly.
- **Execution** — the three fused dispatch paths compile
  ``shard_map`` variants of their programs: per-device blocks run the
  identical tree/tape/gather body, and per-shard popcounts return
  through a tiled ``lax.all_gather`` on the shard axis (the
  mesh-native analog of the host-side per-shard result gather;
  ``parallel/mesh.py`` keeps the scalar ``psum`` reductions the
  collective/spmd plane uses).  One launch therefore evaluates a
  query — or a whole coalesced megabatch — across every local chip.
- **Fallbacks** — ``[mesh] enabled=false`` and the per-request
  ``?nomesh=1`` escape route placement to a single device and
  execution through the exact pre-mesh jit programs (byte-identical,
  regression-pinned); host mode (one CPU device) and multi-process
  deployments (``parallel/spmd.py`` owns the cross-process mesh) are
  never mesh-active.

Process-wide configuration mirrors ``[containers]``: ``configure``
applies explicit values in place, the FIRST server to ``retain()``
captures the pre-server baseline and the LAST ``release()`` restores
it (pilosa-lint P5).
"""

from __future__ import annotations

import threading
from typing import Any

import jax
import numpy as np

from pilosa_tpu import devobs
from pilosa_tpu.ops import bitmap as bm

#: The one data axis of a bitmap index (SURVEY.md §2.5: sharding is
#: the reference's entire parallelism strategy) — shared with
#: parallel/mesh.py's collective programs.
SHARD_AXIS = "shards"


# ------------------------------------------------------------ runtime config


class MeshRuntimeConfig:
    """The process-wide [mesh] knobs (one per process, like the
    [containers] runtime config).  ``enabled`` is tri-state like the
    coalescer's: ``"auto"`` activates the mesh exactly when it can
    help — more than one local device, one process (multi-process
    fan-out belongs to parallel/spmd.py), not host mode.
    ``axis_size`` bounds how many local devices join the shard axis
    (0 = all of them)."""

    __slots__ = ("enabled", "axis_size")

    def __init__(self) -> None:
        self.enabled: Any = "auto"
        self.axis_size = 0


_cfg = MeshRuntimeConfig()
_cfg_lock = threading.Lock()
_baseline: tuple | None = None
_refs = 0
#: (axis_size, device ids) -> Mesh — meshes are cached singletons so
#: program caches keyed on the Mesh object stay warm across queries.
_mesh_cache: dict = {}
#: What a read used to ask JAX for ten times over (PERF.md section 6,
#: PR 37), kept until the event that can change it.  ``_eligible``'s
#: answer holds while the backend lives (``backend_reset`` forgets it);
#: ``axis_size``'s holds until the [mesh] config next changes
#: (``configure`` / ``release`` / ``reset`` forget it under
#: ``_cfg_lock``, where it is also computed, so a racing reader cannot
#: put back a value from before the change).  None = not asked yet.
_eligible_cached: bool | None = None
_axis_cached: int | None = None


def config() -> MeshRuntimeConfig:
    return _cfg


def configure(enabled=None, axis_size: int | None = None) -> MeshRuntimeConfig:
    """Apply [mesh] config in place — only explicit values land, so a
    second in-process server cannot wipe the first's settings with
    defaults (same contract as containers.configure)."""
    if enabled is not None and not isinstance(enabled, bool):
        # validate at the CONFIGURATION site, where a raise reaches
        # the operator (server construction / CLI startup): stored
        # unchecked, a typo like "ture" would only surface as
        # axis_size() quietly returning 1 — a silently-disabled mesh
        # indistinguishable from enabled=false
        s = str(enabled).strip().lower()
        if s not in ("1", "true", "yes", "on",
                     "0", "false", "no", "off", "auto"):
            raise ValueError(
                f"mesh.enabled must be auto/true/false, got {enabled!r}")
    global _axis_cached
    with _cfg_lock:
        if enabled is not None:
            _cfg.enabled = enabled
        if axis_size is not None:
            _cfg.axis_size = int(axis_size)
        _axis_cached = None
    return _cfg


def retain() -> None:
    """Take a server reference; the FIRST holder snapshots the
    pre-server baseline config (restore composes correctly under any
    close order — the PR-6 [ingest] lesson, pilosa-lint P5)."""
    global _refs, _baseline
    with _cfg_lock:
        if _refs == 0 and _baseline is None:
            _baseline = (_cfg.enabled, _cfg.axis_size)
        _refs += 1


def release() -> None:
    """Drop a server reference; the LAST holder restores the captured
    baseline for every other user of the process."""
    global _refs, _baseline, _axis_cached
    with _cfg_lock:
        if _refs > 0:
            _refs -= 1
        if _refs == 0 and _baseline is not None:
            _cfg.enabled, _cfg.axis_size = _baseline
            _baseline = None
            _axis_cached = None


def reset() -> MeshRuntimeConfig:
    """Restore defaults, drop any held baseline and cached meshes
    (tests)."""
    global _cfg, _baseline, _refs, _axis_cached
    with _cfg_lock:
        _cfg = MeshRuntimeConfig()
        _baseline = None
        _refs = 0
        _mesh_cache.clear()
        _axis_cached = None
    return _cfg


def backend_reset() -> None:
    """The JAX backend was torn down and will come back with other
    devices (``jax.extend.backend.clear_backends``, which
    ``__graft_entry__`` does for its virtual mesh): forget what was
    learned from the old one, here and in ``bm.host_mode``."""
    global _eligible_cached, _axis_cached
    with _cfg_lock:
        bm.forget_backend()
        _eligible_cached = None
        _axis_cached = None
        _mesh_cache.clear()


def resolve_enabled(mode) -> bool:
    """``auto`` | true | false — TOML booleans and env strings both
    accepted; a typo raises instead of silently meaning auto (the
    coalescer.resolve_enabled contract)."""
    if isinstance(mode, bool):
        return mode
    s = str(mode).strip().lower()
    if s in ("1", "true", "yes", "on"):
        return True
    if s in ("0", "false", "no", "off"):
        return False
    if s != "auto":
        raise ValueError(
            f"mesh.enabled must be auto/true/false, got {mode!r}")
    return _eligible()


def _eligible() -> bool:
    """Can a mesh help in this process at all?  More than one LOCAL
    device, single process (the multi-process global mesh belongs to
    parallel/spmd.py's collective plans), and not host mode (one CPU
    device runs the numpy/native engine — there is nothing to
    shard).  Asked of JAX once a backend: none of the three can change
    while it lives."""
    global _eligible_cached
    ok = _eligible_cached
    if ok is None:
        ok = _eligible_cached = (not bm.host_mode()
                                 and jax.process_count() == 1
                                 and len(jax.local_devices()) > 1)
    return ok


def axis_size() -> int:
    """The shard-axis size in force: ``[mesh] axis-size`` clamped to
    the local device count (0 = all local devices).  1 when the mesh
    cannot activate.  Computed once a [mesh] config (every staged leaf
    asks, through ``placement_token``)."""
    global _axis_cached
    n = _axis_cached
    if n is None:
        with _cfg_lock:
            n = _axis_cached
            if n is None:
                n = _axis_cached = _axis_size_locked()
    return n


def _axis_size_locked() -> int:
    if not _eligible():
        return 1
    try:
        if not resolve_enabled(_cfg.enabled):
            return 1
    except ValueError:
        return 1
    n = len(jax.local_devices())
    want = _cfg.axis_size
    if want and want > 0:
        n = min(n, want)
    return max(1, n)


def active() -> bool:
    """True when fused dispatches route the shard_map mesh programs."""
    return axis_size() > 1


def active_mesh():
    """The active 1-D device mesh, or None when mesh execution is off
    (disabled, single device, host mode, or multi-process).  Cached
    per (axis size, device ids) so the Mesh object — which keys the
    compiled mesh-program caches — is a stable singleton."""
    n = axis_size()
    if n <= 1:
        return None
    from jax.sharding import Mesh

    devs = tuple(jax.local_devices()[:n])
    key = (n, tuple(d.id for d in devs))
    with _cfg_lock:
        m = _mesh_cache.get(key)
        if m is None:
            m = Mesh(np.array(devs), (SHARD_AXIS,))
            _mesh_cache[key] = m
    return m


def query_mesh(want: bool = True):
    """The mesh one query's fused dispatches should run under: the
    active mesh, or None for the ``?nomesh=1`` escape.  NOT counted
    here — a single request consults this at several fused call sites
    (staging, per-shard-group batch fns), so the executor counts one
    ``mesh.fallbacks`` per executed request instead
    (``note_fallback``)."""
    if not want:
        return None
    return active_mesh()


def note_fallback() -> None:
    """One ?nomesh=1 request executed while the mesh was active — the
    fallback evidence operators read off /debug/mesh.  Called once
    per request (Executor.execute), never per fused call site."""
    if active():
        bump("mesh.fallbacks")


def placement_token(use_mesh: bool = True):
    """The placement flavor joined into stack-cache invalidation
    tuples: a [mesh] toggle or axis resize must MISS and re-place, not
    serve a stack laid out for the previous config."""
    if not use_mesh:
        return "dev"
    n = axis_size()
    return ("mesh", n) if n > 1 else "dev"


def pad_axis(use_mesh: bool = True) -> int:
    """The multiple the shard axis pads to under the given flavor —
    the mesh size (blocks must split evenly across devices), or 1 on
    the single-device path (no padding; the exact pre-mesh shapes)."""
    return axis_size() if use_mesh else 1


def pad_domain(n: int) -> int:
    """Container gather-domain padding: the next power of two (the
    O(log) lowered-shape discipline, pilosa-lint P4 — the shared
    ``containers._pow2`` helper, not a fourth copy) rounded up to a
    mesh-axis multiple so the domain shards evenly.  Axis sizes are
    nearly always powers of two, in which case this IS the pow2."""
    from pilosa_tpu.ops.containers import _pow2

    p = _pow2(max(1, n))
    a = axis_size()
    if a > 1 and p % a:
        p = ((p + a - 1) // a) * a
    return p


# --------------------------------------------------------------- placement


def shard_spec(ndim: int, shard_dim: int):
    """PartitionSpec placing ``shard_dim`` on the mesh axis."""
    from jax.sharding import PartitionSpec as P

    dims: list = [None] * ndim
    dims[shard_dim] = SHARD_AXIS
    return P(*dims)


def place_stack(stack: np.ndarray, label: str = "field.stack",
                mesh_label: str = "field.shard_stack"):
    """Place a host [shards, ...] array sharded over the active mesh
    (axis 0 = the shard axis), or as a plain uncommitted single-device
    put when the mesh is off (the pre-mesh placement — uncommitted so
    it composes with any committed operand in downstream jit calls).
    The caller pads axis 0 to a mesh-size multiple (``pad_axis``);
    transfer metering rides devobs under ``mesh_label``/``label`` for
    the sharded/single-device flavors like every other placement."""
    from jax.sharding import NamedSharding

    m = active_mesh()
    if m is None:
        return bm.device_put(stack, label=label)
    devobs.note_transfer(stack.nbytes, m.size, mesh_label)
    bump("mesh.placements")
    bump("mesh.placed_bytes", stack.nbytes)
    return jax.device_put(stack, NamedSharding(m, shard_spec(stack.ndim, 0)))


def place_replicated(arr, mesh=None, label: str = "field.containers"):
    """Place an array replicated on every mesh device (container word
    pools: gather indices address arbitrary pool rows, so the pool
    must be whole everywhere — the domain axis shards instead)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    m = mesh if mesh is not None else active_mesh()
    if m is None:
        return bm.device_put(arr, label=label)
    devobs.note_transfer(arr.nbytes * m.size, m.size, label)
    bump("mesh.placements")
    bump("mesh.placed_bytes", arr.nbytes * m.size)
    return jax.device_put(arr, NamedSharding(m, P()))


def ensure_placed(arr, mesh, shard_dim: int):
    """Commit one operand to the mesh sharding a shard_map program
    requires.  jit does NOT reshard committed inputs across device
    sets (it raises), so the mesh route re-places every operand; when
    the sharding already matches this is a ~15 ns no-op, and when a
    leaf arrived single-device (a cold cache filled under ?nomesh, a
    test's monkeypatched placement) it is one explicit transfer
    instead of an error."""
    from jax.sharding import NamedSharding

    return jax.device_put(
        arr, NamedSharding(mesh, shard_spec(arr.ndim, shard_dim)))


def ensure_replicated(arr, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(arr, NamedSharding(mesh, P()))


def shardable(mesh, n_rows: int) -> bool:
    """True when a shard-axis length splits evenly over the mesh —
    the precondition of every shard_map route; staging pads to make
    it so, and anything else (a stale memo from an axis resize) falls
    back to the single-device program rather than erroring."""
    return mesh is not None and n_rows % mesh.size == 0


def shard_plan(n_shards: int) -> list[dict]:
    """The per-device shard plan for an ``n_shards``-wide query: which
    padded shard-axis rows (and so which shards) each mesh device
    owns.  NamedSharding partitions axis 0 into equal contiguous
    blocks, so the plan is exactly row ``i`` -> device ``i // block``
    (the /debug/mesh surface; residency follows the same split)."""
    m = active_mesh()
    if m is None:
        return []
    a = m.size
    padded = ((n_shards + a - 1) // a) * a
    block = padded // a
    out = []
    for i, dev in enumerate(m.devices.flat):
        lo, hi = i * block, (i + 1) * block
        out.append({
            "device": dev.id,
            "platform": dev.platform,
            "rows": [lo, hi],
            "shards": [lo, min(hi, n_shards)] if lo < n_shards else [],
        })
    return out


# ------------------------------------------------------------ launch order

#: Serializes mesh-program dispatches process-wide.  A multi-device
#: (collective-carrying) computation enqueues work on EVERY mesh
#: device; two such computations dispatched concurrently from
#: different host threads can interleave their per-device enqueues in
#: different orders and deadlock the backend waiting on each other's
#: collectives — the standard multi-threaded-collectives hazard
#: (observed as a hard wedge on the multi-CPU-device test platform:
#: three reader threads inside the same gather program, none
#: progressing).  Holding this lock across the DISPATCH keeps the
#: per-device enqueue order globally consistent; execution itself
#: still pipelines (the dispatch returns async arrays), and
#: single-device programs never take it.
_launch_lock = threading.Lock()


def launch_lock() -> threading.Lock:
    """The process-wide mesh dispatch lock — every shard_map program
    dispatch (ops/expr, ops/tape mesh routes) runs under it."""
    return _launch_lock


# ---------------------------------------------------------------- counters

_lock = threading.Lock()
_counters = {
    "mesh.launches": 0,     # shard_map program dispatches (expr/tape/
                            # container routes combined)
    "mesh.queries": 0,      # queries those launches served (a coalesced
                            # megabatch counts each member)
    "mesh.fallbacks": 0,    # ?nomesh=1 requests while the mesh was active
    "mesh.placements": 0,   # operand placements onto the mesh
    "mesh.placed_bytes": 0,  # bytes those placements moved (replicated
                             # pools count once per device)
}


def bump(name: str, value: int = 1) -> None:
    with _lock:
        _counters[name] += value


def counters() -> dict[str, int]:
    with _lock:
        return dict(_counters)


def reset_counters() -> None:
    with _lock:
        for k in _counters:
            _counters[k] = 0


def note_launch(queries: int = 1) -> None:
    """One shard_map dispatch serving ``queries`` queries."""
    with _lock:
        _counters["mesh.launches"] += 1
        _counters["mesh.queries"] += queries


def publish_gauges(stats: Any) -> None:
    """Push the mesh.* family into a stats registry at scrape time —
    cumulative counters as gauges (the tape/container family rule),
    plus the axis layout in force."""
    for name, value in counters().items():
        stats.gauge(name, value)
    stats.gauge("mesh.devices", axis_size())
    stats.gauge("mesh.active", 1 if active() else 0)


def debug(n_shards: int | None = None) -> dict[str, Any]:
    """The GET /debug/mesh document: config in force, the resolved
    axis layout (devices joined to the shard axis), the per-device
    shard plan for an ``n_shards``-wide query (the widest index, when
    the handler knows it), and the mesh.* counters."""
    m = active_mesh()
    try:
        n_local = len(jax.local_devices())
    except Exception:
        n_local = 0
    out: dict[str, Any] = {
        "enabled": _cfg.enabled,
        "axisSize": _cfg.axis_size,
        "active": m is not None,
        "axis": SHARD_AXIS,
        "localDevices": n_local,
        "devices": ([] if m is None else
                    [{"id": d.id, "platform": d.platform,
                      "kind": getattr(d, "device_kind", "")}
                     for d in m.devices.flat]),
        "counters": counters(),
    }
    if n_shards:
        out["plan"] = shard_plan(n_shards)
    return out
