"""Roaring-on-TPU: compressed container-directory execution engine.

Fragments have lived on device as fully dense bit planes, so a sparse
row spends ~all of its HBM traffic reading zero words and HBM capacity
caps the column count per chip.  The
reference's entire performance story is container specialization
(Chambi et al., "Better bitmap performance with Roaring bitmaps";
Lemire et al., "Consistently faster and smaller compressed bitmaps
with Roaring"): a row decomposes into 2^16-bit containers and only the
non-empty ones exist.  This module ports that idea to the device:

- **Layout** — per fragment row, the non-empty 1024x64-bit (= 2048
  uint32-word) containers are materialized into a contiguous device
  WORD POOL, driven by a small host-side DIRECTORY (per row: container
  keys, pool offsets, kind).  ``storage/roaring.py`` already decodes
  official roaring into exactly this ``(keys, 1024-word blocks)``
  shape, so the host side is a re-plumb, not a rewrite
  (``Fragment.row_containers`` builds it straight off the row words;
  ``Field.device_container_leaf`` pools a row's containers across the
  query's shard set and uploads once, cached under the same base
  generation tokens as the dense row stacks).
- **Execution** — a fused-supported expression tree evaluates over
  compressed leaves by (1) walking the leaf directories on host and
  computing the ROOT's container-key domain per shard with roaring's
  set rules (Intersect intersects key sets, Union/Xor unions,
  Difference keeps the left side, Not keeps the existence row's keys
  — containers absent from the domain are never touched, and two
  disjoint sparse rows intersect in ZERO device work), then (2)
  launching ONE jitted gather-program over the pooled operands
  (``ops/expr.evaluate_gathered``: per-leaf ``take`` from its pool +
  the same fused tree body + the optional popcount Count root, all
  inside one launch).  Domains and pools pad to powers of two so the
  lowered-program count stays O(log), never one per query shape (the
  PR-6 recompile-convoy lesson, enforced by pilosa-lint P4).
- **Fallback** — hot/full rows stay dense: a fragment row whose fill
  ratio (set bits / shard width) exceeds the ``[containers]``
  threshold marks its query dense, and the query routes through the
  exact pre-existing dense fused path (also the ``?nocontainers=1``
  escape, the ``[containers] enabled=false`` switch, pending ingest
  deltas on a queried row, and trees with non-row leaves — BSI
  ranges, time ranges, Shift).  The fallback is query-level by design
  so a fused read always costs exactly ONE launch either way (the
  dispatch-count pins across the suite stay valid).

Process-wide configuration mirrors ``pilosa_tpu.ingest``: ``configure``
applies explicit values in place, the FIRST server to retain() captures
the pre-server baseline and the LAST to release() restores it.
"""

from __future__ import annotations

import threading
from typing import Any

import numpy as np

from pilosa_tpu import observe as _observe
from pilosa_tpu import perfobs as _perfobs
from pilosa_tpu import stagecheck as _stagecheck
from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.ops import expr
from pilosa_tpu.ops import tape as _tp
from pilosa_tpu.shardwidth import SHARD_WIDTH

#: Container geometry: 2^16 bits = 1024 uint64 = 2048 uint32 words —
#: the reference's container size and storage/roaring.py's block shape.
CONTAINER_BITS = 1 << 16
CWORDS = CONTAINER_BITS // 32

DEFAULT_THRESHOLD = 0.25

#: Kind-selection defaults ([containers] kinds / array-max / run-cap):
#: the device pick mirrors the serializer's cost rule
#: (storage/roaring.pick_kind); ``array_max`` narrows the array-kind
#: cardinality ceiling below the canonical 4096 and ``run_cap`` bounds
#: the run pool's interval size class (a container with more maximal
#: runs re-picks array/bitmap).
DEFAULT_KINDS = True
DEFAULT_ARRAY_MAX = 4096
DEFAULT_RUN_CAP = 256


def _pow2(n: int) -> int:
    """Smallest power of two >= n (domain/pool padding so the gather
    programs lower O(log) distinct shapes, not one per query)."""
    b = 1
    while b < n:
        b <<= 1
    return b


# ------------------------------------------------------------ runtime config


class ContainersRuntimeConfig:
    """The process-wide [containers] knobs (one per process, like the
    residency budget and the [ingest] runtime config)."""

    __slots__ = ("enabled", "threshold", "kinds", "array_max",
                 "run_cap")

    def __init__(self) -> None:
        self.enabled = True
        self.threshold = DEFAULT_THRESHOLD
        self.kinds = DEFAULT_KINDS
        self.array_max = DEFAULT_ARRAY_MAX
        self.run_cap = DEFAULT_RUN_CAP


_cfg = ContainersRuntimeConfig()
_cfg_lock = threading.Lock()
_baseline: tuple | None = None
_refs = 0


def config() -> ContainersRuntimeConfig:
    return _cfg


def configure(enabled: bool | None = None,
              threshold: float | None = None,
              kinds: bool | None = None,
              array_max: int | None = None,
              run_cap: int | None = None) -> ContainersRuntimeConfig:
    """Apply [containers] config in place — only explicit values land,
    so a second in-process server cannot wipe the first's settings
    with defaults (same contract as ingest.configure)."""
    with _cfg_lock:
        if enabled is not None:
            _cfg.enabled = bool(enabled)
        if threshold is not None:
            _cfg.threshold = float(threshold)
        if kinds is not None:
            _cfg.kinds = bool(kinds)
        if array_max is not None:
            _cfg.array_max = int(array_max)
        if run_cap is not None:
            _cfg.run_cap = int(run_cap)
    return _cfg


def retain() -> None:
    """Take a server reference; the FIRST holder snapshots the
    pre-server baseline config (restore composes correctly under any
    close order — the PR-6 [ingest] lesson, pilosa-lint P5)."""
    global _refs, _baseline
    with _cfg_lock:
        if _refs == 0 and _baseline is None:
            _baseline = (_cfg.enabled, _cfg.threshold, _cfg.kinds,
                         _cfg.array_max, _cfg.run_cap)
        _refs += 1


def release() -> None:
    """Drop a server reference; the LAST holder restores the captured
    baseline for every other user of the process."""
    global _refs, _baseline
    with _cfg_lock:
        if _refs > 0:
            _refs -= 1
        if _refs == 0 and _baseline is not None:
            (_cfg.enabled, _cfg.threshold, _cfg.kinds,
             _cfg.array_max, _cfg.run_cap) = _baseline
            _baseline = None


def reset() -> ContainersRuntimeConfig:
    """Restore defaults and drop any held baseline (tests)."""
    global _cfg, _baseline, _refs
    with _cfg_lock:
        _cfg = ContainersRuntimeConfig()
        _baseline = None
        _refs = 0
    return _cfg


# ---------------------------------------------------------------- counters

_lock = threading.Lock()
_counters = {
    "container.queries": 0,             # fused reads served compressed
    "container.fallbacks": 0,           # eligible trees routed dense
                                        # (hot rows / pending deltas)
    "container.containers_gathered": 0,  # domain containers launched
    "container.containers_skipped": 0,   # dense-layout containers the
                                         # directory walk never touched
    "container.empty_domains": 0,       # whole-query zero-work answers
    # per-kind breakout of containers_gathered (kind-specialized
    # algebra: which layouts the domain walks actually touch)
    "container.bitmap_gathered": 0,
    "container.array_gathered": 0,
    "container.run_gathered": 0,
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


def publish_gauges(stats: Any) -> None:
    """Push the container.* family into a stats registry at scrape
    time — cumulative values as gauges, same rule as tape/devobs
    publish_gauges (re-publishing a cumulative total through a counter
    would double-count)."""
    for name, value in counters().items():
        stats.gauge(name, value)


def debug() -> dict[str, Any]:
    """The container section of the debug surface: config in force,
    counters, and the residency split (compressed vs dense bytes are
    on /debug/devices via residency.kinds)."""
    return {
        "enabled": _cfg.enabled,
        "threshold": _cfg.threshold,
        "kinds": _cfg.kinds,
        "arrayMax": _cfg.array_max,
        "runCap": _cfg.run_cap,
        "counters": counters(),
    }


# -------------------------------------------------------------- leaf pooling


import itertools as _itertools

_LEAF_UID = _itertools.count(1)


class ContainerLeaf:
    """One expression leaf (a standard-view row across the query's
    shard set) in pooled compressed form.

    ``entries[i]`` describes shard ``shards[i]``: ``None`` for a
    hot/ineligible fragment row (dense fallback evidence), else a
    sorted int64 key array of the row's non-empty container slots
    (possibly empty).  ``starts[i]`` is the shard's base offset into
    the pool; ``pool`` is the uint32[P, CWORDS] block pool (host numpy
    in host mode, device array otherwise) whose rows [n:] are zeros —
    gather index ``n`` is the canonical absent-container row.  ``kinds``
    mirrors the directory's per-container kind byte (1 = dense bitmap
    block, 2 = sorted-uint16 array, 3 = interval-list run).

    A KINDS leaf (``slots`` non-None) splits its containers across
    three pools: ``pool`` holds only the kind-1 dense blocks (``n`` is
    the bitmap count, row ``n`` still the canonical zero), ``apool`` /
    ``acard`` the array kind (uint16[Pa, acap] + int32[Pa], row ``an``
    the canonical empty array), ``rpool`` the run kind (uint16[Pr,
    2*rcap] interleaved (start, last), row ``rn`` all invalid pairs).
    ``slots[i]`` gives each directory container its kind-LOCAL pool
    row.  A legacy all-bitmap leaf keeps ``slots`` None and the other
    pools empty — every pre-kinds code path sees exactly the old
    layout.
    """

    __slots__ = ("shards", "entries", "starts", "kinds", "pool", "n",
                 "nbytes", "uid", "slots", "apool", "acard", "rpool",
                 "an", "rn", "_dense")

    def __init__(self, shards: tuple, entries: list, starts: list,
                 kinds: list, pool: Any, n: int, nbytes: int,
                 slots: list | None = None, apool: Any = None,
                 acard: Any = None, rpool: Any = None,
                 an: int = 0, rn: int = 0) -> None:
        self.shards = shards
        self.entries = entries
        self.starts = starts
        self.kinds = kinds
        self.pool = pool
        self.n = n
        self.nbytes = nbytes
        self.slots = slots
        self.apool = apool
        self.acard = acard
        self.rpool = rpool
        self.an = an
        self.rn = rn
        # identity for the staging memo: a rebuilt leaf (any base
        # mutation) is a NEW object with a fresh uid, so stale staged
        # gathers can never be addressed
        self.uid = next(_LEAF_UID)
        # found once: every read of a dense row asks (stage_vm's and
        # plan_fused's decline), and a leaf's directory never changes
        self._dense = [i for i, e in enumerate(entries) if e is None]

    def dense_slots(self) -> list[int]:
        """Shard positions whose fragment row is too hot to compress."""
        return self._dense

    @property
    def has_kinds(self) -> bool:
        """True when this leaf carries array/run containers (the
        kind-dispatched execution protocol applies)."""
        return self.slots is not None


# ------------------------------------------------------------ domain algebra


def _domain(shape: tuple, keysets: list) -> np.ndarray:
    """The ROOT's container-key domain for one shard: the minimal set
    of container keys that can hold a set bit of the result, from the
    leaves' key sets by roaring's per-op rules.  Containers outside
    the domain are skipped entirely — for Intersect that is exactly
    the reference's co-present-container walk
    (roaring.Intersect, roaring/roaring.go:595)."""
    kind = shape[0]
    if kind == "leaf":
        return keysets[shape[1]]
    if kind == "and":
        out = _domain(shape[1], keysets)
        for c in shape[2:]:
            out = np.intersect1d(out, _domain(c, keysets),
                                 assume_unique=True)
        return out
    if kind in ("or", "xor"):
        out = _domain(shape[1], keysets)
        for c in shape[2:]:
            out = np.union1d(out, _domain(c, keysets))
        return out
    if kind == "andnot":
        # a \ b can only be non-empty where a is
        return _domain(shape[1], keysets)
    if kind == "not":
        # exist & ~child lives inside the existence row's containers
        return _domain(shape[1], keysets)
    if kind == "dfuse":
        # (child & ~clear) | set — a result bit can only live in the
        # child's or the set-overlay's containers (clear only removes)
        return np.union1d(_domain(shape[1], keysets),
                          _domain(shape[2], keysets))
    raise ValueError(f"container-ineligible node: {kind!r}")


def _leaf_indices(leaf: ContainerLeaf, domains: list[np.ndarray],
                  pad_to: int) -> np.ndarray:
    """Gather indices into ``leaf.pool`` for the concatenated per-shard
    domains; absent containers (and the pow2 tail padding) point at the
    pool's canonical zero row."""
    zero = leaf.n
    parts: list[np.ndarray] = []
    for i, dom in enumerate(domains):
        if len(dom) == 0:
            continue
        keys = leaf.entries[i]
        if keys is None or len(keys) == 0:
            parts.append(np.full(len(dom), zero, dtype=np.int32))
            continue
        pos = np.searchsorted(keys, dom)
        pos_c = np.minimum(pos, len(keys) - 1)
        hit = keys[pos_c] == dom
        idx = np.where(hit, leaf.starts[i] + pos_c, zero)
        parts.append(idx.astype(np.int32))
    total = sum(len(p) for p in parts)
    out = np.full(pad_to, zero, dtype=np.int32)
    if parts:
        np.concatenate(parts, out=out[:total])
    return out


def _leaf_kind_indices(leaf: ContainerLeaf, domains: list[np.ndarray],
                       pad_to: int) -> tuple:
    """Kind-dispatched gather rows for the concatenated per-shard
    domains: ``(kv, ib, ia, ir)`` — per-lane kind byte (0 = absent /
    pad) plus per-kind-pool row indices.  Lanes whose kind differs
    from a pool point at that pool's canonical zero row (bitmap row
    ``n``, empty-array row ``an``, invalid-pairs row ``rn``), so a
    gather-then-OR across the three decoded pools reconstructs each
    lane's dense block exactly.  A legacy all-bitmap leaf yields kv in
    {0, 1} with ``ib`` identical to ``_leaf_indices``."""
    kv = np.zeros(pad_to, dtype=np.uint8)
    ib = np.full(pad_to, leaf.n, dtype=np.int32)
    ia = np.full(pad_to, leaf.an, dtype=np.int32)
    ir = np.full(pad_to, leaf.rn, dtype=np.int32)
    off = 0
    for i, dom in enumerate(domains):
        if len(dom) == 0:
            continue
        keys = leaf.entries[i]
        if keys is None or len(keys) == 0:
            off += len(dom)
            continue
        pos = np.searchsorted(keys, dom)
        pos_c = np.minimum(pos, len(keys) - 1)
        hit = keys[pos_c] == dom
        if leaf.slots is None:
            k = np.where(hit, 1, 0).astype(np.uint8)
            loc = (leaf.starts[i] + pos_c).astype(np.int32)
        else:
            k = np.where(hit, leaf.kinds[i][pos_c], 0).astype(np.uint8)
            loc = leaf.slots[i][pos_c].astype(np.int32)
        seg = slice(off, off + len(dom))
        kv[seg] = k
        ib[seg] = np.where(k == 1, loc, leaf.n)
        ia[seg] = np.where(k == 2, loc, leaf.an)
        ir[seg] = np.where(k == 3, loc, leaf.rn)
        off += len(dom)
    return kv, ib, ia, ir


# Staged-gather memo: (shape, leaf uids) -> (domains, bounds, total,
# idxs).  The domain algebra and searchsorted index builds are pure
# functions of the leaf directories, which are themselves cached per
# base generation — recomputing them per query would put ~0.5 ms of
# host numpy on a hot path whose whole launch costs less.  Leaf uids
# change on every rebuild, so stale entries simply stop being
# addressed; the LRU cap bounds memory.
_stage_lock = threading.Lock()
_stage_memo: dict = {}
_STAGE_MEMO_CAP = 256


def _apool_row_bytes(leaf: ContainerLeaf) -> int:
    """Gathered bytes per array-pool lane (values + cardinality)."""
    return int(leaf.apool.shape[-1]) * 2 + 4


def _bump_kind_gathers(idxs: list, total: int) -> None:
    """Per-kind breakout of containers_gathered from the staged gather
    rows (the live lanes only — the pow2 tail is kind 0)."""
    bm = ar = rn = 0
    for ix in idxs:
        if isinstance(ix, tuple):
            kv = ix[0][:total]
            bm += int((kv == 1).sum())
            ar += int((kv == 2).sum())
            rn += int((kv == 3).sum())
        else:
            # legacy all-bitmap staging: every present lane is kind 1
            bm += total
    if bm:
        bump("container.bitmap_gathered", bm)
    if ar:
        bump("container.array_gathered", ar)
    if rn:
        bump("container.run_gathered", rn)


# ------------------------------------------------------------------ planning


class Plan:
    """A fused read staged for compressed execution over ALL its
    shards.  ``counts()`` / ``row_words()`` perform the one-launch
    evaluation; both tick exactly one dispatch, like the dense fused
    path, so launch-count pins hold on either route."""

    def __init__(self, shape: tuple, leaves: list[ContainerLeaf],
                 shards: tuple, cpr: int, n_words: int) -> None:
        self.shape = shape
        self.leaves = leaves
        self.shards = shards
        self.cpr = cpr
        self.n_words = n_words
        self._staged: tuple | None = None

    # ------------------------------------------------------------- staging

    def _stage(self) -> tuple:
        """(domains, bounds, total, idxs) — the per-shard root domains,
        their concatenation boundaries, and the per-leaf gather
        indices.  Memoized across queries on (shape, leaf uids): the
        whole stage is a pure function of the cached directories."""
        if self._staged is not None:
            return self._staged
        mkey = (self.shape, tuple(leaf.uid for leaf in self.leaves))
        with _stage_lock:
            hit = _stage_memo.get(mkey)
            if hit is not None:
                _stage_memo[mkey] = _stage_memo.pop(mkey)  # LRU touch
        if hit is None:
            domains: list[np.ndarray] = []
            for i in range(len(self.shards)):
                keysets = [leaf.entries[i] for leaf in self.leaves]
                domains.append(_domain(self.shape, keysets))
            bounds = np.cumsum([0] + [len(d) for d in domains])
            total = int(bounds[-1])
            # pow2 padding rounded to a mesh-axis multiple so the
            # domain shards evenly under the mesh gather program
            # (parallel/meshexec.py; identical pow2 when no mesh)
            from pilosa_tpu.parallel import meshexec

            pad = meshexec.pad_domain(total) if total else 0
            # any array/run leaf switches the WHOLE query to the
            # kind-dispatched gather protocol (uniform per-lane
            # (kv, ib, ia, ir) tuples); all-bitmap queries keep the
            # exact legacy index arrays
            if any(leaf.has_kinds for leaf in self.leaves):
                idxs = [_leaf_kind_indices(leaf, domains, pad)
                        for leaf in self.leaves]
            else:
                idxs = [_leaf_indices(leaf, domains, pad)
                        for leaf in self.leaves]
            hit = (domains, bounds, total, idxs)
            with _stage_lock:
                _stage_memo[mkey] = hit
                while len(_stage_memo) > _STAGE_MEMO_CAP:
                    _stage_memo.pop(next(iter(_stage_memo)))
        domains, bounds, total, idxs = hit
        n_leaves = len(self.leaves)
        bump("container.containers_gathered", total * n_leaves)
        _bump_kind_gathers(idxs, total)
        # what the dense layout would have streamed vs what the
        # directory walk actually touches — the bandwidth story
        bump("container.containers_skipped",
             n_leaves * (len(self.shards) * self.cpr - total))
        self._staged = hit
        return self._staged

    def _gathered(self, counts: bool, mesh=None) -> Any:
        """ONE launch over the pooled operands; None when the root
        domain is empty everywhere (zero device work).  ``mesh``
        routes the shard_map gather program (domain axis sharded,
        pools replicated — parallel/meshexec.py)."""
        with _observe.span("stage", leaves=len(self.leaves)):
            _domains, _bounds, total, idxs = self._stage()
        if total == 0:
            bump("container.empty_domains")
            # the dense path would still have launched once; tick the
            # dispatch hook so launch accounting is route-invariant
            bm.note_dispatch("fused_gather")
            return None
        with _observe.span("launch") as sp:
            out = self._launch(counts, idxs, total, mesh)
            sp.note_engine()
        return out

    def _launch(self, counts: bool, idxs: list, total: int,
                mesh: Any) -> Any:
        """The one launch of ``_gathered``, by the cheapest arm."""
        from pilosa_tpu.ops import pallas_kernels as pk

        pools = [leaf.pool for leaf in self.leaves]
        # engine-observatory coordinates for this launch: the dense
        # stacks the gather replaced (size-class key) and the fraction
        # of possible containers the directory walk actually touches
        # (the sparsity the compressed engine exploits)
        dense_work = len(self.leaves) * len(self.shards) * self.n_words
        sparsity = total / max(1, len(self.shards) * self.cpr)
        if any(isinstance(ix, tuple) for ix in idxs):
            # kind-dispatched protocol: pair-matrix arms for the
            # homogeneous AND pair, else the generic decode-at-gather
            # program.  Always single-device — plan_fused builds
            # legacy all-bitmap leaves while a mesh is active, so a
            # non-None mesh here can only be a toggle race; the
            # single-device program stays bit-exact regardless.
            return self._gathered_kinds(counts, idxs, total,
                                        dense_work, sparsity)
        if (counts and mesh is None
                and self.shape == ("and", ("leaf", 0), ("leaf", 1))
                and pk.on_tpu() and not isinstance(pools[0], np.ndarray)):
            # the north-star pair: the Pallas directory-walk kernel
            # intersects+counts co-present containers in one pass
            # (single-device; the mesh route splits the domain walk
            # across chips through the shard_map gather instead)
            t0 = _perfobs.t0()
            out = pk.gathered_count_and(pools[0], idxs[0],
                                        pools[1], idxs[1])
            _perfobs.sample("gather", out, t0,
                            nbytes=(len(idxs[0]) + len(idxs[1]))
                            * CWORDS * 4,
                            work=dense_work, sparsity=sparsity)
            return out
        with _perfobs.context(sparsity=sparsity, work=dense_work):
            return expr.evaluate_gathered(self.shape, tuple(pools),
                                          tuple(idxs), counts=counts,
                                          mesh=mesh)

    def _gathered_kinds(self, counts: bool, idxs: list, total: int,
                        dense_work: int, sparsity: float) -> Any:
        """The kind-dispatched launch: host directory algebra has
        already resolved every lane's (kind, pool-row) pair, so this
        picks the cheapest ARM for the query — the Roaring pair
        matrix's array∩array (galloping membership) and array∩bitmap
        (gather-test) specializations for the homogeneous counts-root
        AND pair, else the generic decode-at-gather program (gather
        compact rows, decode to dense blocks, fold the tree — still
        ONE launch).  Bit-exact with the dense route by construction:
        every arm computes the same container algebra."""
        from pilosa_tpu.ops import pallas_kernels as pk

        if counts and self.shape == ("and", ("leaf", 0), ("leaf", 1)):
            # an AND domain is the keyset intersection, so every live
            # lane is present in BOTH leaves: the lane kinds alone
            # decide the arm
            kv0 = idxs[0][0][:total]
            kv1 = idxs[1][0][:total]
            l0, l1 = self.leaves[0], self.leaves[1]
            if (kv0 == 2).all() and (kv1 == 2).all():
                bm.note_dispatch("fused_gather")
                t0 = _perfobs.t0()
                out = pk.gathered_count_array_array(
                    l0.apool, l0.acard, idxs[0][2],
                    l1.apool, l1.acard, idxs[1][2])
                _perfobs.sample(
                    "gather_aa", out, t0,
                    nbytes=(len(idxs[0][2]) * _apool_row_bytes(l0)
                            + len(idxs[1][2]) * _apool_row_bytes(l1)),
                    work=dense_work, sparsity=sparsity)
                return out
            pair = None
            if (kv0 == 2).all() and (kv1 == 1).all():
                pair = (l0, idxs[0], l1, idxs[1])
            elif (kv0 == 1).all() and (kv1 == 2).all():
                pair = (l1, idxs[1], l0, idxs[0])
            if pair is not None:
                al, aix, bl, bix = pair
                bm.note_dispatch("fused_gather")
                t0 = _perfobs.t0()
                out = pk.gathered_count_array_bitmap(
                    al.apool, al.acard, aix[2], bl.pool, bix[1])
                _perfobs.sample(
                    "gather_ab", out, t0,
                    nbytes=(len(aix[2]) * _apool_row_bytes(al)
                            + len(bix[1]) * CWORDS * 4),
                    work=dense_work, sparsity=sparsity)
                return out
        leafops = []
        for leaf, ix in zip(self.leaves, idxs):
            _kv, ib, ia, ir = ix
            if leaf.has_kinds:
                leafops.append(("k", leaf.pool, leaf.apool, leaf.acard,
                                leaf.rpool, ib, ia, ir))
            else:
                # legacy all-bitmap leaf inside a kinds query: plain
                # gather (kv is {0, 1} and ib already routes absents
                # at the zero row)
                leafops.append(("b", leaf.pool, ib))
        with _perfobs.context(sparsity=sparsity, work=dense_work):
            return expr.evaluate_gathered_kinds(self.shape,
                                                tuple(leafops),
                                                counts=counts)

    # ----------------------------------------------------------- execution

    def counts(self, mesh=None) -> list[int]:
        """Per-shard popcounts of the tree, aligned with ``shards`` —
        the Count root folded into the same launch."""
        bump("container.queries")
        out = self._gathered(counts=True, mesh=mesh)
        _domains, bounds, total, _idxs = self._staged  # set by _gathered
        if out is None:
            return [0] * len(self.shards)
        with _observe.span("reduce"):
            cts = np.asarray(out, dtype=np.int64)[:total]
            return [int(cts[bounds[i]:bounds[i + 1]].sum())
                    for i in range(len(self.shards))]

    def row_words(self, mesh=None) -> list[tuple[int, np.ndarray]]:
        """Non-empty per-shard result words, scattered back to the
        dense row layout the Row reduce consumes."""
        bump("container.queries")
        out = self._gathered(counts=False, mesh=mesh)
        if out is None:
            return []
        domains, bounds, total, _idxs = self._staged
        partials: list[tuple[int, np.ndarray]] = []
        with _observe.span("reduce"):
            res = np.asarray(out)[:total]
            for i, s in enumerate(self.shards):
                dom = domains[i]
                if len(dom) == 0:
                    continue
                blocks = res[int(bounds[i]):int(bounds[i + 1])]
                if not blocks.any():
                    continue
                words = np.zeros(self.n_words, dtype=np.uint32)
                words.reshape(self.cpr, CWORDS)[dom] = blocks
                partials.append((s, words))
        return partials


#: Default ``[vm]`` knobs: the minimum padded domain width a staged VM
#: query rounds up to (keeps the lowered-variant count down for tiny
#: domains and gives empty-domain queries a real — all-zero-row — batch
#: slot, so the ONE-launch accounting never special-cases them), and
#: the per-launch scalar-prefetch budget in int32 directory entries
#: (slots x batch x domain live in SMEM on chip; oversized batches
#: split, oversized single queries decline to the dense engines).
VM_MIN_DOMAIN = 8
VM_MAX_PREFETCH = 1 << 16


class VMStage:
    """One fused Count read staged for the Pallas bitmap VM: the
    (possibly delta-substituted) shape, its compiled op-tape, the
    container leaves in slot order, the per-leaf LOCAL gather rows for
    the concatenated per-shard root domains (each int32[pad], absent
    containers and the pow2 tail pointing at the leaf's own zero row),
    and the live domain total.  parallel/coalescer.py globalizes the
    rows against the bucket megapool at flush."""

    __slots__ = ("shape", "tape", "leaves", "idxs", "total", "pad")

    def __init__(self, shape: tuple, tape: Any, leaves: list,
                 idxs: list, total: int, pad: int) -> None:
        self.shape = shape
        self.tape = tape
        self.leaves = leaves
        self.idxs = idxs
        self.total = total
        self.pad = pad


def stage_vm(tree: Any, shards: tuple,
             use_delta: bool = True, max_tape: int | None = None,
             max_leaves: int | None = None,
             min_domain: int = VM_MIN_DOMAIN,
             max_prefetch: int | None = VM_MAX_PREFETCH) -> VMStage | None:
    """Stage one fused Count read for the bitmap VM, or None to route
    the pre-existing engines (dense fused / plain ragged) — the
    all-or-nothing per-query contract of ``plan_fused``, with one
    deliberate difference: a pending ingest delta does NOT decline.
    The overlay stages as two extra compressed leaves under a
    ``dfuse`` node ((base & ~clear) | set, two tape instructions), so
    ingest-warm rows stay on the compressed path instead of falling
    back dense — the delta leaves stage BEFORE the base leaf, which
    makes a concurrent compaction safe (idempotent re-apply, the
    device_delta_stacks discipline)."""
    if not _cfg.enabled or not shards:
        _tp.bump("vm.fallbacks.disabled")
        return None
    if not tree.plain:
        _tp.bump("vm.fallbacks.ineligible_leaf")
        return None
    shape, leaf_descs = tree.shape, tree.rows()
    nodemap: dict = {}
    leaves: list[ContainerLeaf] = []
    for i, (f, row_id) in enumerate(leaf_descs):
        pair = None
        mark = _stagecheck.mark()
        if not use_delta:
            # the ?nodelta=1 contract: compact up front, then a real
            # pure-base read — which the VM is
            f.flush_deltas(shards)
        else:
            pair = f.device_delta_container_leaves(row_id, shards)
        base = f.device_container_leaf(row_id, shards)
        _stagecheck.leaf_done(mark)
        if base.dense_slots():
            bump("container.fallbacks")
            _tp.bump("vm.fallbacks.ineligible_leaf")
            return None
        if base.has_kinds and any(
                k is not None and len(k) and int(k.max()) > 3
                for k in base.kinds):
            # a kind byte this VM has no decode arm for (forward
            # compatibility: directories may carry future kinds)
            _tp.bump("vm.fallbacks.kind_unsupported")
            return None
        bi = len(leaves)
        leaves.append(base)
        if pair is None:
            nodemap[i] = ("leaf", bi)
        else:
            si = len(leaves)
            leaves.append(pair[0])
            ci = len(leaves)
            leaves.append(pair[1])
            nodemap[i] = ("dfuse", ("leaf", bi), ("leaf", si),
                          ("leaf", ci))
            # same flight-record note as the dense delta fuse
            # (executor._stage_leaves): this read met a pending delta
            rec = _observe.current()
            if rec is not None:
                rec.note_delta(1)

    def subst(node: tuple) -> tuple:
        if node[0] == "leaf":
            return nodemap[node[1]]
        return (node[0],) + tuple(subst(c) for c in node[1:])

    vshape = subst(shape)
    if max_leaves is not None and len(leaves) > max_leaves:
        _tp.bump("tape.oversize_fallbacks")
        _tp.bump("vm.fallbacks.oversize")
        return None
    tp = _tp.try_compile(vshape, len(leaves), max_tape)
    if tp is None:
        _tp.bump("vm.fallbacks.oversize")
        return None
    mkey = ("vm", vshape, tuple(leaf.uid for leaf in leaves),
            int(min_domain))
    with _stage_lock:
        hit = _stage_memo.get(mkey)
        if hit is not None:
            _stage_memo[mkey] = _stage_memo.pop(mkey)  # LRU touch
    if hit is None:
        domains: list[np.ndarray] = []
        for i in range(len(shards)):
            keysets = [leaf.entries[i] for leaf in leaves]
            domains.append(_domain(vshape, keysets))
        total = int(sum(len(d) for d in domains))
        pad = max(int(min_domain), _pow2(max(1, total)))
        if any(leaf.has_kinds for leaf in leaves):
            idxs = [_leaf_kind_indices(leaf, domains, pad)
                    for leaf in leaves]
        else:
            idxs = [_leaf_indices(leaf, domains, pad)
                    for leaf in leaves]
        hit = (total, pad, idxs)
        with _stage_lock:
            _stage_memo[mkey] = hit
            while len(_stage_memo) > _STAGE_MEMO_CAP:
                _stage_memo.pop(next(iter(_stage_memo)))
    total, pad, idxs = hit
    if max_prefetch is not None and len(leaves) * pad > max_prefetch:
        # a single query's directory would blow the per-launch scalar
        # budget even unbatched — the dense engines take it.  When the
        # plain pow2 pad would have fit, the configured min-domain
        # floor itself blew the budget — its own reason cell
        if len(leaves) * _pow2(max(1, total)) <= max_prefetch:
            _tp.bump("vm.fallbacks.min_domain")
        else:
            _tp.bump("vm.fallbacks.max_prefetch")
        return None
    cpr = SHARD_WIDTH // CONTAINER_BITS
    n_leaves = len(leaves)
    bump("container.containers_gathered", total * n_leaves)
    _bump_kind_gathers(idxs, total)
    bump("container.containers_skipped",
         n_leaves * (len(shards) * cpr - total))
    if total == 0:
        # the query still rides the batch (all-zero-row directory,
        # count 0) — ONE launch either way, so the empty-domain case
        # never forks the dispatch accounting like Plan._gathered must
        bump("container.empty_domains")
    return VMStage(vshape, tp, leaves, idxs, total, pad)


# Megapool memo: a VM bucket's distinct leaves concatenate into ONE
# device word pool the kernel gathers from; steady traffic re-flushes
# the same leaf sets, and re-concatenating device pools per flush would
# put an HBM copy on the hot path.  Keyed on the leaf uid tuple — uids
# change on every rebuild, so stale megapools stop being addressed and
# age out of the small LRU.
_mega_lock = threading.Lock()
_megapool_memo: dict = {}
_MEGAPOOL_MEMO_CAP = 8


class MegaPools:
    """A VM bucket's per-kind megapools: the bitmap rows plus the
    compact array/run pools whose DECODED dense rows conceptually
    append after them — one virtual dense pool of ``shape[0]`` rows
    the combined gather index addresses (``[0, Rb)`` bitmap, ``[Rb,
    Rb + Ra)`` array, ``[Rb + Ra, Rb + Ra + Rr)`` run).  The decode
    happens INSIDE the one jitted VM launch
    (ops/pallas_kernels.vm_counts), so resident and transferred bytes
    stay compact.  ``shape``/``ndim`` quack like the plain dense pool
    for the tape's size accounting; ``nbytes`` is the real compact
    total."""

    __slots__ = ("bpool", "apool", "acard", "rpool")

    def __init__(self, bpool: Any, apool: Any, acard: Any,
                 rpool: Any) -> None:
        self.bpool = bpool
        self.apool = apool
        self.acard = acard
        self.rpool = rpool

    @property
    def ndim(self) -> int:
        return 2

    @property
    def shape(self) -> tuple:
        rows = (int(self.bpool.shape[0]) + int(self.apool.shape[0])
                + int(self.rpool.shape[0]))
        return (rows, CWORDS)

    @property
    def nbytes(self) -> int:
        return (int(self.bpool.nbytes) + int(self.apool.nbytes)
                + int(self.acard.nbytes) + int(self.rpool.nbytes))


def megapool(leaves: list) -> tuple:
    """(pool, bases, zero_index) for a set of container leaves: the
    concatenated word pool a VM bucket gathers from, each leaf's row
    offset keyed by uid, and a canonical all-zero row (the first
    leaf's own zero tail).  Device megapools pad their row count to
    pow2 with zero rows so the gather programs keep lowering O(log)
    distinct shapes (the P4 rule); host pools stay tight.

    When any leaf carries array/run containers the pool is a
    ``MegaPools`` bundle and ``bases[uid]`` is the per-kind offset
    triple ``(bb, ab, rb)`` into the bundle's virtual dense row space;
    otherwise the legacy scalar-base dense pool is returned
    byte-identically."""
    order = sorted({leaf.uid: leaf for leaf in leaves}.values(),
                   key=lambda leaf: leaf.uid)
    key = tuple(leaf.uid for leaf in order)
    with _mega_lock:
        hit = _megapool_memo.get(key)
        if hit is not None:
            _megapool_memo[key] = _megapool_memo.pop(key)  # LRU touch
            return hit
    if any(leaf.has_kinds for leaf in order):
        hit = _megapool_kinds(order)
    else:
        hit = _megapool_plain(order)
    with _mega_lock:
        _megapool_memo[key] = hit
        while len(_megapool_memo) > _MEGAPOOL_MEMO_CAP:
            _megapool_memo.pop(next(iter(_megapool_memo)))
    return hit


def _megapool_plain(order: list) -> tuple:
    bases: dict = {}
    off = 0
    for leaf in order:
        bases[leaf.uid] = off
        off += int(leaf.pool.shape[0])
    zero_index = bases[order[0].uid] + order[0].n
    host = all(isinstance(leaf.pool, np.ndarray) for leaf in order)
    if len(order) == 1:
        pool = order[0].pool
    elif host:
        pool = np.concatenate([leaf.pool for leaf in order], axis=0)
    else:
        import jax.numpy as jnp

        parts = [jnp.asarray(leaf.pool) for leaf in order]
        rows = _pow2(off)
        if rows > off:
            parts.append(jnp.zeros((rows - off, CWORDS),
                                   dtype=jnp.uint32))
        pool = jnp.concatenate(parts, axis=0)
    return (pool, bases, zero_index)


def _megapool_kinds(order: list) -> tuple:
    """Concatenate per-kind pools across leaves into one MegaPools
    bundle.  Column widths re-pad to the cross-leaf pow2 maximum and
    device row counts pad to pow2 per kind pool (array tails with the
    sorted-safe 0xFFFF pad, run tails with the invalid (1, 0) pair —
    both decode to nothing); a leaf without a kind contributes zero
    rows to that pool."""
    from pilosa_tpu.ops import kindpools as kp

    host = all(isinstance(leaf.pool, np.ndarray) for leaf in order)
    acap = max([int(leaf.apool.shape[-1]) for leaf in order
                if leaf.apool is not None] or [1])
    rcap = max([int(leaf.rpool.shape[-1]) for leaf in order
                if leaf.rpool is not None] or [2])
    boffs: dict = {}
    aoffs: dict = {}
    roffs: dict = {}
    boff = aoff = roff = 0
    bparts: list = []
    aparts: list = []
    cparts: list = []
    rparts: list = []
    for leaf in order:
        boffs[leaf.uid] = boff
        aoffs[leaf.uid] = aoff
        roffs[leaf.uid] = roff
        boff += int(leaf.pool.shape[0])
        bparts.append(leaf.pool)
        if leaf.apool is not None and int(leaf.apool.shape[0]):
            rows = int(leaf.apool.shape[0])
            aparts.append((leaf.apool, rows, int(leaf.apool.shape[-1])))
            cparts.append(leaf.acard)
            aoff += rows
        if leaf.rpool is not None and int(leaf.rpool.shape[0]):
            rows = int(leaf.rpool.shape[0])
            rparts.append((leaf.rpool, rows, int(leaf.rpool.shape[-1])))
            roff += rows

    def _apad(rows: int, cols: int) -> np.ndarray:
        return np.full((rows, cols), kp.ARRAY_PAD, dtype=np.uint16)

    def _rpad(rows: int, cols: int) -> np.ndarray:
        out = np.zeros((rows, cols), dtype=np.uint16)
        out[:, 0::2] = 1  # (1, 0): the canonical invalid pair
        return out

    if host:
        xp = np
    else:
        import jax.numpy as jnp

        xp = jnp
    # row counts: pow2 per kind pool on device (the P4 O(log)-shapes
    # rule for the decode program); tight on host
    rb = boff if host else _pow2(max(1, boff))
    ra = max(1, aoff) if host else _pow2(max(1, aoff))
    rr = max(1, roff) if host else _pow2(max(1, roff))
    bits = [xp.asarray(p) for p in bparts]
    if rb > boff:
        bits.append(xp.zeros((rb - boff, CWORDS), dtype=xp.uint32))
    bpool = bits[0] if len(bits) == 1 else xp.concatenate(bits, axis=0)
    avs: list = []
    for p, rows, cols in aparts:
        p = xp.asarray(p)
        if cols < acap:
            p = xp.concatenate([p, xp.asarray(_apad(rows, acap - cols))],
                               axis=1)
        avs.append(p)
    if ra > aoff:
        avs.append(xp.asarray(_apad(ra - aoff, acap)))
    apool = avs[0] if len(avs) == 1 else xp.concatenate(avs, axis=0)
    cvs = [xp.asarray(c) for c in cparts]
    if ra > aoff:
        cvs.append(xp.zeros(ra - aoff, dtype=xp.int32))
    acard = cvs[0] if len(cvs) == 1 else xp.concatenate(cvs, axis=0)
    rvs: list = []
    for p, rows, cols in rparts:
        p = xp.asarray(p)
        if cols < rcap:
            p = xp.concatenate([p, xp.asarray(_rpad(rows, rcap - cols))],
                               axis=1)
        rvs.append(p)
    if rr > roff:
        rvs.append(xp.asarray(_rpad(rr - roff, rcap)))
    rpool = rvs[0] if len(rvs) == 1 else xp.concatenate(rvs, axis=0)
    # bases address the VIRTUAL dense row space: bitmap rows first,
    # then the decoded array rows, then the decoded run rows
    bases = {leaf.uid: (boffs[leaf.uid], rb + aoffs[leaf.uid],
                        rb + ra + roffs[leaf.uid])
             for leaf in order}
    zero_index = boffs[order[0].uid] + order[0].n
    return (MegaPools(bpool, apool, acard, rpool), bases, zero_index)


def kept_dense(tree: Any, shards: tuple) -> bool:
    """Whether the tree (a ``parallel/prepared.py`` Prepared) is KNOWN
    to hold a leaf row that is kept dense in some shard
    (``Field.row_kept_dense``: a verdict a staged leaf left under the
    view's write token).  ``stage_vm`` and ``plan_fused`` are
    all-or-nothing, so such a tree is certain to be declined and
    nothing need be staged to learn it: a dense read stages its leaves
    once, for the engine that will run it.  Stops at the first such
    leaf.  False = not known, or the engine is off: the caller offers
    the tree and the offer accounts for itself."""
    if not _cfg.enabled or not shards:
        return False
    return any(f.row_kept_dense(row_id, shards)
               for f, row_id in tree.probe_rows())


def plan_fused(tree: Any, shards: tuple, opt: Any,
               counts: bool = True) -> Plan | None:
    """Stage a fused read for compressed execution, or None to route
    the exact pre-existing dense path.  All-or-nothing per query: every
    leaf row must be compression-eligible (under the fill-ratio
    threshold, no pending delta overlay) in EVERY shard — so the read
    costs one launch on either route and partial results never mix.
    ``tree`` is the read's Prepared (parallel/prepared.py): the
    container-eligible grammar is its ``plain`` (every leaf a plain
    standard-view row; no BSI condition row, time range or Shift, whose
    bits cross container boundaries), its shape and rows what is staged.

    ``counts`` is the root kind: a bare-leaf Row tree is declined when
    ``counts=False`` because the dense path answers it as a ZERO-launch
    passthrough of the resident stack (expr.evaluate's leaf case) —
    gathering would both tick a launch the dense route doesn't (the
    route-invariant accounting would break) and redo work the stack
    cache already holds."""
    if not _cfg.enabled or not shards:
        return None
    if opt is not None and not getattr(opt, "containers", True):
        return None
    if not tree.plain:
        return None
    shape, leaf_descs = tree.shape, tree.rows()
    if not counts and shape[0] == "leaf":
        return None
    if any(f.row_kept_dense(row_id, shards) for f, row_id in leaf_descs):
        # known from the last read of that row: declined as below,
        # with nothing staged and no overlay looked for
        bump("container.fallbacks")
        return None
    use_delta = opt is None or opt.delta
    for f, row_id in leaf_descs:
        if not use_delta:
            # the ?nodelta=1 contract: compact up front, then a real
            # pure-base read — which the compressed path is
            f.flush_deltas(shards)
        elif f.delta_pending(row_id, shards):
            # pending overlay on a queried row: the dense path
            # fuses it (expr "dfuse"); compressed pools hold base
            # content only
            bump("container.fallbacks")
            return None
    leaves = []
    for f, row_id in leaf_descs:
        leaf = f.device_container_leaf(row_id, shards)
        if leaf.dense_slots():
            bump("container.fallbacks")
            return None
        leaves.append(leaf)
    return Plan(shape, leaves, shards, SHARD_WIDTH // CONTAINER_BITS,
                bm.n_words(SHARD_WIDTH))
