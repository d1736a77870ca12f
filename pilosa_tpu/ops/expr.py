"""Fused expression programs: a whole bitmap call tree as ONE dispatch.

The executor's fused all-shard path (`Executor._fused_eval`) used to emit
one jitted dispatch per AST node — `b_and`, then `row_counts_and`, … —
which is exactly wrong when device dispatch has real latency (VERDICT
round 5: a 20 us trivial-dispatch floor under a 0.555 ms/query capture;
the Count/Intersect hot path is dispatch-bound, not HBM-bound).  This
module compiles the SHAPE of a supported call tree into a single jitted
program over its leaf operand stacks, so the whole tree costs one launch
regardless of depth, and XLA fuses the chain (no materialized
intermediates for AND+popcount roots).

Shape grammar — hashable nested tuples; leaves are slot indices into the
operand tuple, so distinct row ids share one compiled program:

    ("leaf", i)                       operand slot i
    ("and"|"or"|"xor"|"andnot", c, ...)   left-fold over children
    ("not", ("leaf", i_exist), child)     exist & ~child
    ("shift", n, child)                   static shift by n words/bits
    ("dfuse", child, set_c, clear_c)      (child & ~clear) | set

``dfuse`` is the streaming-ingest delta fusion (pilosa_tpu.ingest): the
child is a base row stack resident since its last compaction, the
set/clear leaves are the fragment delta planes — the whole overlay
evaluates inside the same single launch, so sustained writes never
force the base stack off the device.

``evaluate(shape, leaves)`` returns the uint32 bitmap stack;
``evaluate(shape, leaves, counts=True)`` returns int32 per-row popcounts
(the Count root, reduced over the last axis inside the same program).

Every op is elementwise over the last axis (shift pads it, counts reduce
it), so ONE compiled program serves both the unbatched [S, W] stack and
the coalescer's cross-query [B, S, W] batch — jit re-specializes per
rank, the cached Python closure is shared.

Host stacks (single-CPU-device mode, where bm ops route to numpy + the
native popcount kernels) evaluate eagerly — dispatch is free there, so
the whole tree still ticks ONE `note_dispatch` to keep the launch-count
accounting meaningful across engines.
"""

from __future__ import annotations

import threading
from collections import namedtuple
from collections.abc import Callable
from typing import Any

import numpy as np

from pilosa_tpu import perfobs as _perfobs
from pilosa_tpu.ops import bitmap as bm

_FOLD_NAMES = ("and", "or", "xor", "andnot")


def _touched_bytes(*arrs) -> int:
    """Analytic bytes one launch touches: operand reads + result
    writes (perfobs bandwidth accounting) — ``.nbytes`` on every numpy
    / jax operand, 0 for anything shapeless."""
    return sum(getattr(a, "nbytes", 0) for a in arrs)


def _validate(shape: tuple, n_leaves: int) -> None:
    kind = shape[0]
    if kind == "leaf":
        if not 0 <= shape[1] < n_leaves:
            raise ValueError(f"leaf slot {shape[1]} out of range")
        return
    if kind in _FOLD_NAMES:
        if len(shape) < 2:
            raise ValueError(f"{kind} needs at least one child")
        for c in shape[1:]:
            _validate(c, n_leaves)
        return
    if kind == "not":
        _validate(shape[1], n_leaves)
        _validate(shape[2], n_leaves)
        return
    if kind == "dfuse":
        if len(shape) != 4:
            raise ValueError("dfuse needs (child, set, clear)")
        for c in shape[1:]:
            _validate(c, n_leaves)
        return
    if kind == "shift":
        if shape[1] < 0:
            raise ValueError("shift distance must be non-negative")
        _validate(shape[2], n_leaves)
        return
    raise ValueError(f"unknown expression node: {kind!r}")


# ------------------------------------------------------------ jit engine


def _build_jnp(shape: tuple) -> Callable[[tuple], Any]:
    """shape -> closure(leaves_tuple) -> jnp array, traced under jit."""
    import jax.numpy as jnp

    kind = shape[0]
    if kind == "leaf":
        i = shape[1]
        return lambda leaves: leaves[i]
    if kind in _FOLD_NAMES:
        kids = [_build_jnp(c) for c in shape[1:]]
        fold = {
            "and": jnp.bitwise_and,
            "or": jnp.bitwise_or,
            "xor": jnp.bitwise_xor,
            "andnot": lambda a, b: jnp.bitwise_and(a, jnp.bitwise_not(b)),
        }[kind]

        def ev(leaves: tuple) -> Any:
            out = kids[0](leaves)
            for k in kids[1:]:
                out = fold(out, k(leaves))
            return out

        return ev
    if kind == "not":
        exist = _build_jnp(shape[1])
        kid = _build_jnp(shape[2])
        return lambda leaves: jnp.bitwise_and(
            exist(leaves), jnp.bitwise_not(kid(leaves)))
    if kind == "dfuse":
        kid = _build_jnp(shape[1])
        dset = _build_jnp(shape[2])
        dclear = _build_jnp(shape[3])
        return lambda leaves: jnp.bitwise_or(
            jnp.bitwise_and(kid(leaves),
                            jnp.bitwise_not(dclear(leaves))),
            dset(leaves))
    # shift: the ONE shared body (bm.shift_words), traced into the
    # fused program with static n — cannot drift from the unfused path
    n = shape[1]
    kid = _build_jnp(shape[2])
    return lambda leaves: bm.shift_words(jnp, kid(leaves), n)


#: Compiled-program cache capacity.  Tests shrink it via
#: ``set_program_cache_size``; eviction past it means live tree shapes
#: outnumber retained programs and EVERY evicted shape re-traces +
#: re-lowers on its next query — tens of ms of invisible recompile per
#: hit, which is why evictions surface through devobs
#: (``compile.program_evictions``) instead of staying silent.
DEFAULT_PROGRAM_CACHE_SIZE = 512


_CacheInfo = namedtuple("_CacheInfo",
                        ("hits", "misses", "maxsize", "currsize"))


def _build_program(shape: tuple, counts: bool) -> Callable[..., Any]:
    """One jitted program per (canonical shape, root kind).  The
    cache is what makes tree fusion pay: distinct row ids (distinct
    leaf VALUES) reuse the program; only a new tree SHAPE traces."""
    import jax.numpy as jnp
    from jax import lax

    ev = _build_jnp(shape)
    if counts:
        def run(*leaves: Any) -> Any:
            return jnp.sum(lax.population_count(ev(leaves)),
                           axis=-1, dtype=jnp.int32)
    else:
        def run(*leaves: Any) -> Any:
            return ev(leaves)
    # compile telemetry (pilosa_tpu.devobs): fused-program first
    # lowerings are the ones a fresh tree SHAPE pays — exactly the
    # per-canonical-shape compile events the /debug/devices surface
    # exists to attribute
    from pilosa_tpu import devobs as _devobs

    name = "expr.fused_counts" if counts else "expr.fused"
    return _devobs.jit(name, run)


def _build_gather_program(shape: tuple, counts: bool) -> Callable[..., Any]:
    """The container-engine variant of ``_build_program``: leaves are
    (pool, gather-index) pairs and each leaf materializes as
    ``take(pool, idx, axis=0)`` INSIDE the jitted program, so the
    directory-driven gather, the fused tree body, and the optional
    popcount Count root all cost one launch (ops/containers.py stages
    the pools and pow2-padded indices; see its module docstring for
    the layout).  Argument convention: ``run(*pools, *idxs)``."""
    import jax.numpy as jnp
    from jax import lax

    ev = _build_jnp(shape)

    def run(*args: Any) -> Any:
        n = len(args) // 2
        pools, idxs = args[:n], args[n:]
        leaves = tuple(jnp.take(p, ix, axis=0, mode="clip")
                       for p, ix in zip(pools, idxs))
        out = ev(leaves)
        if counts:
            return jnp.sum(lax.population_count(out),
                           axis=-1, dtype=jnp.int32)
        return out

    from pilosa_tpu import devobs as _devobs

    name = "expr.fused_gather_counts" if counts else "expr.fused_gather"
    return _devobs.jit(name, run)


def _build_gather_kinds_program(key: tuple,
                                counts: bool) -> Callable[..., Any]:
    """The kind-dispatched variant of ``_build_gather_program``
    (roaring array/run parity, ops/kindpools.py): each leaf gathers
    compact rows from its per-kind pools and DECODES them to dense
    2048-word blocks inside the same launch — a lane's three gathers
    hit its own kind's row and the other kinds' canonical zero rows,
    so an OR reconstructs the block exactly and resident/transferred
    bytes stay compact.  ``key`` is ``(shape, spec)`` where ``spec``
    tags each leaf ``"b"`` (plain bitmap pool + index) or ``"k"``
    (bpool, apool, acard, rpool, ib, ia, ir); arguments flatten in
    leaf order."""
    shape, spec = key
    import jax.numpy as jnp
    from jax import lax

    from pilosa_tpu.ops import kindpools as kp

    ev = _build_jnp(shape)

    def run(*args: Any) -> Any:
        leaves = []
        i = 0
        for tag in spec:
            if tag == "b":
                pool, ib = args[i:i + 2]
                i += 2
                leaves.append(jnp.take(pool, ib, axis=0, mode="clip"))
                continue
            bpool, apool, acard, rpool, ib, ia, ir = args[i:i + 7]
            i += 7
            dense = jnp.take(bpool, ib, axis=0, mode="clip")
            av = jnp.take(apool, ia, axis=0, mode="clip")
            ac = jnp.take(acard, ia, axis=0, mode="clip")
            rv = jnp.take(rpool, ir, axis=0, mode="clip")
            leaves.append(dense | kp.decode_array_jnp(av, ac)
                          | kp.decode_runs_jnp(rv))
        out = ev(tuple(leaves))
        if counts:
            return jnp.sum(lax.population_count(out),
                           axis=-1, dtype=jnp.int32)
        return out

    from pilosa_tpu import devobs as _devobs

    name = ("expr.fused_gather_kinds_counts" if counts
            else "expr.fused_gather_kinds")
    return _devobs.jit(name, run)


def _build_mesh_program(meshkey: tuple, counts: bool) -> Callable[..., Any]:
    """The mesh-native variant of ``_build_program``: the same tree
    body runs per-device on shard-axis blocks under ``shard_map``
    (parallel/meshexec.py), so ONE launch evaluates the query across
    every mesh device.  A Count root popcounts its local shards and
    returns the full per-shard vector through a tiled
    ``lax.all_gather`` on the shard axis — the collective replacement
    for the host-side per-shard gather, keeping the output
    bit-identical to the single-device program (int32 per-shard
    counts; callers still sum in Python ints).  A bitmap root stays
    sharded in place (out_specs on the shard axis) — set algebra is
    embarrassingly shard-parallel and the host assembles segments
    from the sharded result.  ``meshkey`` is ``(shape, n_leaves,
    ndim, mesh)``: the in_specs tuple length and the shard-axis
    position are static per program."""
    shape, n_leaves, ndim, mesh = meshkey
    import jax.numpy as jnp
    from jax import lax

    from pilosa_tpu.parallel import meshexec
    from pilosa_tpu.parallel.mesh import shard_map

    ev = _build_jnp(shape)
    leaf_spec = meshexec.shard_spec(ndim, ndim - 2)
    if counts:
        from jax.sharding import PartitionSpec as P

        out_spec = P()  # replicated full per-shard counts (all_gather)
    else:
        out_spec = leaf_spec

    def body(*blks: Any) -> Any:
        out = ev(blks)
        if counts:
            local = jnp.sum(lax.population_count(out),
                            axis=-1, dtype=jnp.int32)
            return lax.all_gather(local, meshexec.SHARD_AXIS,
                                  axis=ndim - 2, tiled=True)
        return out

    sm = shard_map(body, mesh=mesh, in_specs=(leaf_spec,) * n_leaves,
                   out_specs=out_spec, check_vma=False)

    def run(*leaves: Any) -> Any:
        return sm(*leaves)

    from pilosa_tpu import devobs as _devobs

    name = "expr.mesh_counts" if counts else "expr.mesh"
    return _devobs.jit(name, run)


def _build_mesh_gather_program(meshkey: tuple,
                               counts: bool) -> Callable[..., Any]:
    """Mesh variant of ``_build_gather_program``: container word POOLS
    replicate across the mesh (gather indices address arbitrary pool
    rows — ops/containers.py's domain algebra crosses shard
    boundaries by construction) while the gather DOMAIN axis shards,
    so each device gathers and evaluates its block of the query's
    container domain.  Count roots all_gather the per-container
    popcounts back (replicated, same int32 vector as the
    single-device program); bitmap roots stay domain-sharded.
    Argument convention matches ``_build_gather_program``:
    ``run(*pools, *idxs)``."""
    shape, n_leaves, mesh = meshkey
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from pilosa_tpu.parallel import meshexec
    from pilosa_tpu.parallel.mesh import shard_map

    ev = _build_jnp(shape)
    pool_spec = P(None, None)
    idx_spec = P(meshexec.SHARD_AXIS)
    out_spec = P() if counts else P(meshexec.SHARD_AXIS, None)

    def body(*args: Any) -> Any:
        n = len(args) // 2
        pools, idxs = args[:n], args[n:]
        leaves = tuple(jnp.take(p, ix, axis=0, mode="clip")
                       for p, ix in zip(pools, idxs))
        out = ev(leaves)
        if counts:
            local = jnp.sum(lax.population_count(out),
                            axis=-1, dtype=jnp.int32)
            return lax.all_gather(local, meshexec.SHARD_AXIS,
                                  axis=0, tiled=True)
        return out

    sm = shard_map(body, mesh=mesh,
                   in_specs=(pool_spec,) * n_leaves
                   + (idx_spec,) * n_leaves,
                   out_specs=out_spec, check_vma=False)

    def run(*args: Any) -> Any:
        return sm(*args)

    from pilosa_tpu import devobs as _devobs

    name = ("expr.mesh_gather_counts" if counts
            else "expr.mesh_gather")
    return _devobs.jit(name, run)


def _make_compiled(maxsize: int,
                   build: Callable[[tuple, bool],
                                   Callable[..., Any]] | None = None) -> Any:
    """An explicit LRU over compiled programs with an EXACT eviction
    count.  ``functools.lru_cache`` was abandoned here because its
    counters can't express evictions: ``misses - currsize`` over-counts
    whenever two threads race the same fresh shape (both count a miss,
    one entry lands) or a build raises — which made the one-line
    overflow warning and the ``compile.program_evictions`` gauge fire
    spuriously.  Here an eviction increments exactly when a resident
    program is popped for capacity, nothing else."""
    lock = threading.Lock()
    builder = build if build is not None else _build_program
    # insertion order == LRU order (move-to-end on hit)
    cache: dict[tuple, Callable[..., Any]] = {}
    counters = {"hits": 0, "misses": 0, "evictions": 0}

    def _compiled(shape: tuple, counts: bool) -> Callable[..., Any]:
        key = (shape, counts)
        with lock:
            prog = cache.get(key)
            if prog is not None:
                cache[key] = cache.pop(key)
                counters["hits"] += 1
                return prog
            counters["misses"] += 1
        # trace/lower outside the lock — tens of ms for a fresh shape;
        # a concurrent duplicate build is wasted work, never a wrong
        # count: only the first insert lands and no eviction is charged
        prog = builder(shape, counts)
        evicted = False
        with lock:
            if key in cache:
                return cache[key]
            cache[key] = prog
            while len(cache) > maxsize:
                cache.pop(next(iter(cache)))
                counters["evictions"] += 1
                evicted = True
        if evicted:
            _note_program_eviction(maxsize)
        return prog

    def cache_info() -> _CacheInfo:
        with lock:
            return _CacheInfo(counters["hits"], counters["misses"],
                              maxsize, len(cache))

    def cache_clear() -> None:
        with lock:
            cache.clear()
            counters["hits"] = counters["misses"] = 0
            counters["evictions"] = 0

    def cache_evictions() -> int:
        with lock:
            return counters["evictions"]

    _compiled.cache_info = cache_info
    _compiled.cache_clear = cache_clear
    _compiled.cache_evictions = cache_evictions
    return _compiled


_compiled = _make_compiled(DEFAULT_PROGRAM_CACHE_SIZE)
#: gather-program cache (the container engine's fused programs): its
#: keys are the same canonical tree shapes, so the dense and gathered
#: variants of one shape are two entries — sized accordingly
_compiled_gather = _make_compiled(DEFAULT_PROGRAM_CACHE_SIZE,
                                  build=_build_gather_program)
#: kind-dispatched gather programs (array/run container parity): keyed
#: on (shape, per-leaf kind spec) composites
_compiled_gather_kinds = _make_compiled(DEFAULT_PROGRAM_CACHE_SIZE,
                                        build=_build_gather_kinds_program)
#: mesh-program caches (parallel/meshexec.py): keyed on the composite
#: (shape, n_leaves, ndim, mesh) — the Mesh is a cached singleton, so
#: one config's programs stay warm across queries and an axis resize
#: simply addresses fresh entries
_compiled_mesh = _make_compiled(DEFAULT_PROGRAM_CACHE_SIZE,
                                build=_build_mesh_program)
_compiled_mesh_gather = _make_compiled(DEFAULT_PROGRAM_CACHE_SIZE,
                                       build=_build_mesh_gather_program)
_eviction_warned: bool = False


def program_evictions() -> int:
    """Capacity evictions from the compiled-program caches so far —
    counted exactly at the point a resident program is popped (see
    ``_make_compiled``), so concurrent same-shape builds and failed
    builds never inflate it."""
    return (_compiled.cache_evictions()
            + _compiled_gather.cache_evictions()
            + _compiled_gather_kinds.cache_evictions()
            + _compiled_mesh.cache_evictions()
            + _compiled_mesh_gather.cache_evictions())


def set_program_cache_size(maxsize: int) -> None:
    """Swap in a fresh program cache of the given capacity (tests —
    forcing 512 distinct shapes to exercise eviction would dominate a
    test run with tracing)."""
    global _compiled, _compiled_gather, _eviction_warned
    global _compiled_mesh, _compiled_mesh_gather
    global _compiled_gather_kinds
    _compiled = _make_compiled(maxsize)
    _compiled_gather = _make_compiled(maxsize,
                                      build=_build_gather_program)
    _compiled_gather_kinds = _make_compiled(
        maxsize, build=_build_gather_kinds_program)
    _compiled_mesh = _make_compiled(maxsize,
                                    build=_build_mesh_program)
    _compiled_mesh_gather = _make_compiled(
        maxsize, build=_build_mesh_gather_program)
    _eviction_warned = False


def _note_program_eviction(maxsize: int) -> None:
    """One-line warning the FIRST time a compiled program is evicted:
    shape thrash otherwise shows up only as inexplicable recompile
    latency (the devobs gauge carries the running count).  Called
    where the eviction happens (``_make_compiled``), so a launch that
    evicts nothing asks no cache for its count."""
    global _eviction_warned
    if _eviction_warned:
        return
    _eviction_warned = True
    import logging

    logging.getLogger("pilosa_tpu.ops.expr").warning(
        "fused-program cache overflowed (maxsize=%d): tree shapes "
        "now evict each other and re-trace on reuse; see "
        "compile.program_evictions on /metrics", maxsize)


# ----------------------------------------------------------- host engine


def _host_tree(shape: tuple, leaves: tuple) -> np.ndarray:
    kind = shape[0]
    if kind == "leaf":
        return leaves[shape[1]]
    if kind in _FOLD_NAMES:
        fold = {
            "and": np.bitwise_and,
            "or": np.bitwise_or,
            "xor": np.bitwise_xor,
            "andnot": lambda a, b: np.bitwise_and(a, np.bitwise_not(b)),
        }[kind]
        out = _host_tree(shape[1], leaves)
        for c in shape[2:]:
            out = fold(out, _host_tree(c, leaves))
        return out
    if kind == "not":
        return np.bitwise_and(_host_tree(shape[1], leaves),
                              np.bitwise_not(_host_tree(shape[2], leaves)))
    if kind == "dfuse":
        return np.bitwise_or(
            np.bitwise_and(_host_tree(shape[1], leaves),
                           np.bitwise_not(_host_tree(shape[3], leaves))),
            _host_tree(shape[2], leaves))
    # shift — the shared body, numpy namespace
    return bm.shift_words(np, _host_tree(shape[2], leaves), shape[1])


def _host_counts(shape: tuple, leaves: tuple) -> np.ndarray:
    from pilosa_tpu.ops import hostkernels as hk

    if (shape[0] == "and" and len(shape) == 3
            and shape[1][0] == "leaf" and shape[2][0] == "leaf"):
        # pairwise fast path: native |a & b| per row without
        # materializing the intersection (at 10B columns that
        # intermediate alone is ~1.25 GB per query)
        a, b = leaves[shape[1][1]], leaves[shape[2][1]]
        lead = a.shape[:-1]
        flat = (a.reshape(-1, a.shape[-1]), b.reshape(-1, b.shape[-1]))
        return hk.row_counts_and(*flat).reshape(lead)
    return hk.row_counts(_host_tree(shape, leaves))


# -------------------------------------------------------------- frontend


def evaluate(shape: tuple, leaves: tuple, counts: bool = False,
             mesh: Any = None, mesh_queries: int | None = None) -> Any:
    """Evaluate one compiled tree over its leaf stacks in ONE launch.

    ``leaves`` — tuple of uint32 stacks, all the same shape ([S, W], or
    [B, S, W] for a coalesced cross-query batch).  Returns the result
    bitmap stack, left where it was computed, or int32 per-row counts
    with ``counts=True``: host values from the numpy engine and from
    the single-device program (fetched in the launch's one wait), the
    sharded device array from the mesh program (``counts_to_host``
    takes either).

    ``mesh`` — an active device mesh (meshexec.query_mesh) routes the
    shard_map program: the same tree body per device over shard-axis
    blocks, one launch across every mesh chip, results bit-identical.
    None (the default, and the ?nomesh=1 escape) runs the exact
    single-device program.  ``mesh_queries`` — how many LIVE queries
    this launch serves for the mesh.queries counter (the coalescer
    passes its live occupancy; a [B, S, W] batch otherwise counts its
    batch rows, which include pow2 padding).
    """
    _validate(shape, len(leaves))
    if shape[0] == "leaf" and not counts:
        return leaves[shape[1]]  # passthrough: no launch at all
    bm.note_dispatch("fused_expr")
    t0 = _perfobs.t0()
    if bm._host(*leaves):
        out = (_host_counts(shape, leaves) if counts
               else _host_tree(shape, leaves))
        # host fused is still the DENSE engine (same operands, numpy
        # body); the executor's per-shard map re-attributes via
        # perfobs.context(engine="host")
        _perfobs.sample("dense", out, t0,
                        nbytes=_touched_bytes(*leaves, out))
        return out
    ndim = leaves[0].ndim
    if mesh is not None:
        from pilosa_tpu.parallel import meshexec

        if meshexec.shardable(mesh, leaves[0].shape[ndim - 2]):
            # jit refuses committed inputs on foreign device sets, so
            # every leaf commits to the program's sharding here — a
            # no-op when placement already matches (the warm path)
            placed = tuple(meshexec.ensure_placed(lv, mesh, ndim - 2)
                           for lv in leaves)
            fn = _compiled_mesh((shape, len(leaves), ndim, mesh),
                                counts)
            meshexec.note_launch(
                mesh_queries if mesh_queries is not None
                else (leaves[0].shape[0] if ndim == 3 else 1))
            # dispatch under the process-wide mesh launch lock:
            # concurrent collective dispatches from different threads
            # can interleave per-device enqueues and deadlock the
            # backend (meshexec.launch_lock); execution pipelines —
            # the lock covers the enqueue, not the compute (and the
            # perfobs block_until_ready waits OUTSIDE the lock)
            with meshexec.launch_lock():
                out = fn(*placed)
            _perfobs.sample("mesh", out, t0,
                            nbytes=_touched_bytes(*placed, out))
            return out
    fn = _compiled(shape, counts)
    out = fn(*leaves)
    # the leaves of one launch share a shape: one stack's size, times
    # how many there are
    nbytes = len(leaves) * leaves[0].nbytes + out.nbytes
    if not counts:
        _perfobs.sample("dense", out, t0, nbytes=nbytes)
        return out
    # ONE round trip a launch: every caller wants the counts on the
    # host, so the copy is asked for here, still inside
    # launch.dispatch and before anybody waits: the transfer queues
    # behind the kernel.  The launch's one wait is then the fetch
    # itself (np.asarray finds the copy on its way), not a block with
    # a fetch after it (two wake-ups of this thread for one kernel),
    # whether or not anything observes it.
    out.copy_to_host_async()
    return _perfobs.sample("dense", out, t0, nbytes=nbytes,
                           fetch=np.asarray)


def counts_to_host(counts: Any) -> np.ndarray:
    """What every caller of ``evaluate(counts=True)`` does first with
    what it returned: int64 on the host.  Host values already (the
    single-device route's fetch, the numpy engine) convert in place.
    A device array (the mesh route, which blocks and leaves its
    sharded output where it is) is asked for AGAIN here, a second
    round trip after the wait, and counted: ``launch.refetched``."""
    if _perfobs.enabled and not isinstance(counts, np.ndarray):
        _perfobs.bump("launch.refetched")
    return np.asarray(counts, dtype=np.int64)


def evaluate_gathered(shape: tuple, pools: tuple, idxs: tuple,
                      counts: bool = False, mesh: Any = None) -> Any:
    """Evaluate one compiled tree over POOLED container operands in
    ONE launch (the compressed-fragment read path, ops/containers.py).

    ``pools[i]`` — leaf i's uint32[P_i, CWORDS] container block pool
    (host numpy or device array), rows past the directory's count all
    zeros; ``idxs[i]`` — int32[D] gather indices mapping the query's
    container domain into that pool (absent containers point at a zero
    row).  The caller pads D and each P_i to powers of two
    (``containers._pow2``) so the jit re-specializations stay O(log).
    Returns the uint32[D, CWORDS] result blocks, or int32[D]
    per-container popcounts with ``counts=True``.

    ``mesh`` (meshexec.query_mesh) shards the DOMAIN axis across the
    mesh and replicates the pools — one launch gathers and evaluates
    every device's domain block; None keeps the single-device gather
    program."""
    _validate(shape, len(pools))
    bm.note_dispatch("fused_gather")
    t0 = _perfobs.t0()
    if bm._host(*pools):
        leaves = tuple(p[np.asarray(ix)] for p, ix in zip(pools, idxs))
        out = (_host_counts(shape, leaves) if counts
               else _host_tree(shape, leaves))
        _perfobs.sample("gather", out, t0,
                        nbytes=_touched_bytes(*leaves, *idxs, out))
        return out
    import jax.numpy as jnp

    if mesh is not None:
        from pilosa_tpu.parallel import meshexec

        if meshexec.shardable(mesh, len(idxs[0])):
            placed_pools = tuple(meshexec.ensure_replicated(p, mesh)
                                 for p in pools)
            placed_idxs = tuple(meshexec.ensure_placed(
                jnp.asarray(ix), mesh, 0) for ix in idxs)
            fn = _compiled_mesh_gather((shape, len(pools), mesh),
                                       counts)
            meshexec.note_launch()
            with meshexec.launch_lock():  # see evaluate's mesh route
                out = fn(*placed_pools, *placed_idxs)
            _perfobs.sample(
                "mesh", out, t0,
                nbytes=_touched_bytes(*placed_pools, *placed_idxs,
                                      out))
            return out
    fn = _compiled_gather(shape, counts)
    out = fn(*pools, *(jnp.asarray(ix) for ix in idxs))
    # the gathered pool rows are what the launch actually reads — the
    # whole point of the compressed engine is touching D gathered
    # container blocks instead of the dense stacks
    gathered = sum(len(ix) for ix in idxs) * (
        pools[0].shape[-1] * 4 if pools else 0)
    _perfobs.sample("gather", out, t0,
                    nbytes=gathered + _touched_bytes(*idxs, out))
    return out


def evaluate_gathered_kinds(shape: tuple, leafops: tuple,
                            counts: bool = False) -> Any:
    """Evaluate one compiled tree over KIND-SPLIT container operands in
    ONE launch (roaring array/run parity; ops/kindpools.py holds the
    layouts, ops/containers.py stages the indices).

    ``leafops[i]`` is either ``("b", pool, ib)`` — a legacy all-bitmap
    leaf, gathered exactly like ``evaluate_gathered`` — or ``("k",
    bpool, apool, acard, rpool, ib, ia, ir)`` — a kind-split leaf whose
    three index vectors each point at the lane's own row in its kind's
    pool and at the OTHER pools' canonical zero rows, so gather +
    decode + OR reconstructs the lane's dense block inside the launch.
    Mesh execution never reaches here (ops/containers.py builds legacy
    leaves while a mesh is active)."""
    _validate(shape, len(leafops))
    bm.note_dispatch("fused_gather")
    t0 = _perfobs.t0()
    from pilosa_tpu.ops import kindpools as kp

    if bm._host(*(op[1] for op in leafops)):
        leaves = []
        for op in leafops:
            if op[0] == "b":
                _, pool, ib = op
                leaves.append(pool[np.asarray(ib)])
                continue
            _, bpool, apool, acard, rpool, ib, ia, ir = op
            ib, ia, ir = (np.asarray(v) for v in (ib, ia, ir))
            leaves.append(bpool[ib]
                          | kp.decode_array_np(apool[ia], acard[ia])
                          | kp.decode_runs_np(rpool[ir]))
        leaves = tuple(leaves)
        out = (_host_counts(shape, leaves) if counts
               else _host_tree(shape, leaves))
        _perfobs.sample("gather_kinds", out, t0,
                        nbytes=_touched_bytes(*leaves, out))
        return out
    import jax.numpy as jnp

    spec = tuple(op[0] for op in leafops)
    args: list[Any] = []
    gathered = 0
    for op in leafops:
        if op[0] == "b":
            _, pool, ib = op
            args.extend((pool, jnp.asarray(ib)))
            gathered += len(ib) * pool.shape[-1] * 4
            continue
        _, bpool, apool, acard, rpool, ib, ia, ir = op
        args.extend((bpool, apool, acard, rpool,
                     jnp.asarray(ib), jnp.asarray(ia), jnp.asarray(ir)))
        # the launch reads one compact row per lane per pool — the
        # whole point of the kind split is that those rows are small
        gathered += len(ib) * (bpool.shape[-1] * 4
                               + apool.shape[-1] * 2 + 4
                               + rpool.shape[-1] * 2)
    fn = _compiled_gather_kinds((shape, spec), counts)
    out = fn(*args)
    _perfobs.sample("gather_kinds", out, t0,
                    nbytes=gathered + _touched_bytes(out))
    return out
