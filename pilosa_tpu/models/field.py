"""Field: a typed sub-matrix of an index.

Parity with the reference's Field (field.go:112-204): five types —
``set`` (plain rows), ``int`` (BSI bit-sliced integers), ``time``
(quantum-expanded views), ``mutex`` (one row per column), ``bool``
(rows 0/1, mutex semantics) — plus per-field shard tracking
(field.go:263-360) and BSI base/bit-depth management
(field.go:1540-1651).
"""

from __future__ import annotations

import datetime as _dt
import json
import os
import re
import threading
import time as _time
from dataclasses import dataclass

import jax
import numpy as np

from pilosa_tpu import observe as _observe
from pilosa_tpu import stagecheck as _stagecheck
from pilosa_tpu.models.timequantum import TimeQuantum, views_by_time, views_by_time_range
from pilosa_tpu.models.view import VIEW_BSI_PREFIX, VIEW_STANDARD, View
from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.ops import containers as ct
from pilosa_tpu.runtime import residency
from pilosa_tpu.shardwidth import SHARD_WIDTH


class FieldType:
    SET = "set"
    INT = "int"
    TIME = "time"
    MUTEX = "mutex"
    BOOL = "bool"


CACHE_TYPE_RANKED = "ranked"
CACHE_TYPE_LRU = "lru"
CACHE_TYPE_NONE = "none"

DEFAULT_CACHE_TYPE = CACHE_TYPE_RANKED
DEFAULT_CACHE_SIZE = 50000

# Row ids used by bool fields (reference fragment.go:87-88).
FALSE_ROW_ID = 0
TRUE_ROW_ID = 1


def _frag_gen(fr):
    """Cache-invalidation token for one fragment slot: (uid, gen,
    delta_seq), or 0 for an absent fragment.  The uid half guards
    against object replacement — a fragment deleted by resize cleanup
    and re-fetched later is a new object whose _gen can collide with a
    cached tuple, which a bare-gen comparison would treat as a (stale)
    hit.  The delta_seq half covers the streaming-ingest path
    (pilosa_tpu.ingest): delta-landing writes bump the monotone
    ``_delta_seq`` instead of ``_gen``, so any token consumer whose
    content reflects base ⊕ delta invalidates on either."""
    return 0 if fr is None else (fr._uid, fr._gen, fr._delta_seq)


def _frag_base_gen(fr):
    """Token for caches holding BASE-ONLY content (the fused row
    stacks, whose pending delta the executor fuses on top as separate
    ``dfuse`` leaves): deliberately blind to ``_delta_seq``, so
    streaming writes leave the big resident base stacks warm — the
    entire point of the delta plane."""
    return 0 if fr is None else (fr._uid, fr._gen)


def _padded_rows(n: int) -> int:
    """Pad the shard axis so stacks shard evenly over the mesh in
    force; padding rows are zero (no bits).  Single-process placement
    follows the [mesh] config (parallel/meshexec.py: the axis size,
    which is every local device by default and 1 — no padding — when
    the mesh is disabled); multi-process placement pads to the
    node-local device count for parallel/spmd.py's per-node stacks."""
    if jax.process_count() > 1:
        n_dev = len(jax.local_devices())
        if n_dev <= 1:
            return n
        return ((n + n_dev - 1) // n_dev) * n_dev
    a = _meshexec().pad_axis()
    if a <= 1:
        return n
    return ((n + a - 1) // a) * a

def _live(dev) -> bool:
    return residency.live(dev)


def _leaf_live(leaf) -> bool:
    """Every pool of a container leaf still device-resident (a kinds
    leaf carries three; a deleted buffer in ANY of them invalidates)."""
    if not _live(leaf.pool):
        return False
    return all(_live(p) for p in (leaf.apool, leaf.acard, leaf.rpool)
               if p is not None)


def _pair_live(pair) -> bool:
    """Both stacks of a (set, clear) delta pair."""
    return _live(pair[0]) and _live(pair[1])


def _leaf_pair_live(pair) -> bool:
    """Both pools of a (set, clear) pair of delta container leaves."""
    return _live(pair[0].pool) and _live(pair[1].pool)


_mx = None


def _meshexec():
    """``parallel/meshexec.py``, bound on first use.  It cannot be
    imported at the top: importing the ``pilosa_tpu.parallel`` package
    runs ``executor``, which imports this module.  An import statement
    a call is what every staged leaf paid three times over."""
    global _mx
    if _mx is None:
        from pilosa_tpu.parallel import meshexec

        _mx = meshexec
    return _mx


def _placement_token():
    """The [mesh] placement flavor in force (parallel/meshexec.py),
    joined into every device-stack cache's invalidation tuple: a mesh
    toggle or axis resize must MISS and re-place — a stack laid out
    for the previous shard plan would otherwise keep serving under
    fresh config."""
    return _meshexec().placement_token()


def _placement_devices() -> int:
    """How many devices the active placement spreads a stack over —
    the residency manager's per-device accounting (devobs/residency
    follow the shard plan)."""
    return _meshexec().axis_size()


_NAME_RE = re.compile(r"^[a-z][a-z0-9_-]{0,63}$")
# Internal names (the hidden existence field) carry a leading underscore and
# bypass user-name validation, as in the reference (holder.go:46).
_INTERNAL_NAME_RE = re.compile(r"^_[a-z0-9_-]{0,63}$")


def validate_name(name: str) -> None:
    if not (_NAME_RE.match(name) or _INTERNAL_NAME_RE.match(name)):
        raise ValueError(f"invalid name: {name!r}")


def bsi_base(lo: int, hi: int) -> int:
    """Default base for an int field's range (reference bsiBase,
    field.go:1551-1559)."""
    if lo > 0:
        return lo
    if hi < 0:
        return hi
    return 0


def bit_depth(uvalue: int) -> int:
    """Bits needed for a magnitude, minimum 1."""
    return max(int(uvalue).bit_length(), 1)


@dataclass
class FieldOptions:
    type: str = FieldType.SET
    cache_type: str = DEFAULT_CACHE_TYPE
    cache_size: int = DEFAULT_CACHE_SIZE
    min: int = 0
    max: int = 0
    base: int = 0
    bit_depth: int = 1
    time_quantum: str = ""
    no_standard_view: bool = False
    keys: bool = False

    def to_dict(self) -> dict:
        return {
            "type": self.type,
            "cacheType": self.cache_type,
            "cacheSize": self.cache_size,
            "min": self.min,
            "max": self.max,
            "base": self.base,
            "bitDepth": self.bit_depth,
            "timeQuantum": self.time_quantum,
            "noStandardView": self.no_standard_view,
            "keys": self.keys,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "FieldOptions":
        return cls(
            type=d.get("type", FieldType.SET),
            cache_type=d.get("cacheType", DEFAULT_CACHE_TYPE),
            cache_size=d.get("cacheSize", DEFAULT_CACHE_SIZE),
            min=d.get("min", 0),
            max=d.get("max", 0),
            base=d.get("base", 0),
            bit_depth=d.get("bitDepth", 1),
            time_quantum=d.get("timeQuantum", ""),
            no_standard_view=d.get("noStandardView", False),
            keys=d.get("keys", False),
        )

    # ---- constructors matching the reference's functional options ----

    @classmethod
    def set_field(cls, cache_type=DEFAULT_CACHE_TYPE, cache_size=DEFAULT_CACHE_SIZE, keys=False):
        return cls(type=FieldType.SET, cache_type=cache_type, cache_size=cache_size, keys=keys)

    @classmethod
    def int_field(cls, lo: int, hi: int):
        if lo > hi:
            raise ValueError("int field min cannot be greater than max")
        if lo < -(1 << 63) or hi >= (1 << 63):
            raise ValueError("int field range must fit in int64")
        base = bsi_base(lo, hi)
        depth = bit_depth(max(abs(lo - base), abs(hi - base)))
        if depth > 63:
            raise ValueError("int field range spans more than 63 bits from base")
        return cls(type=FieldType.INT, min=lo, max=hi, base=base, bit_depth=depth)

    @classmethod
    def time_field(cls, quantum: str, no_standard_view: bool = False):
        return cls(
            type=FieldType.TIME,
            time_quantum=str(TimeQuantum(quantum)),
            no_standard_view=no_standard_view,
        )

    @classmethod
    def mutex_field(cls, cache_type=DEFAULT_CACHE_TYPE, cache_size=DEFAULT_CACHE_SIZE):
        return cls(type=FieldType.MUTEX, cache_type=cache_type, cache_size=cache_size)

    @classmethod
    def bool_field(cls):
        return cls(type=FieldType.BOOL, cache_type=CACHE_TYPE_NONE, cache_size=0)


class Field:
    #: device-memory budget for cross-shard row-stack caching (bytes)
    ROW_STACK_CACHE_BYTES = 512 << 20

    def __init__(self, path: str | None, index: str, name: str, options: FieldOptions):
        validate_name(name)
        self.path = path
        self.index = index
        self.name = name
        self.options = options
        self.views: dict[str, View] = {}
        self._shards: set[int] = set()
        # (row, shards) -> (gens, dev, [stamp]): the per-fragment
        # tokens the entry was built from, the device value, and in a
        # one-slot list the view write token it was last proved good
        # under (_stamped_hit / _walked_hit)
        self._row_stack_cache: dict = {}
        # ("delta" | "dcont", row, shards) -> stamp: "no fragment has
        # an overlay for this row", as of that view write token
        self._no_delta: dict = {}
        # ("cont", row, shards) -> stamp: "this row is kept dense in
        # some shard of the set" (its container leaf has dense slots),
        # as of that view write token and those [containers] settings
        self._kept_dense: dict = {}
        # shards-tuple -> (gens, row_ids, shard_pos, pos_dev, mat_dev):
        # concatenated cross-shard row matrices for the fused TopN scan
        self._matrix_stack_cache: dict = {}
        self._view_times_memo = None  # (view names, parsed times)
        self._index_ref = None  # weakref to owning Index (set by Index._adopt)
        self._lock = threading.RLock()
        if path is not None:
            os.makedirs(path, exist_ok=True)
            self._load_meta()
            self._open_views()
        self._load_shards()
        from pilosa_tpu.models.attrs import AttrStore

        self.row_attrs = AttrStore(
            None if path is None else os.path.join(path, ".row_attrs.db")
        )
        self._translate_store = None

    @property
    def translate_store(self):
        """Row-key translate store, opened lazily (reference field-level
        TranslateStore, field.go keys option)."""
        if self._translate_store is None:
            from pilosa_tpu.storage.translate import open_translate_store

            path = None if self.path is None else os.path.join(self.path, ".keys.db")
            self._translate_store = open_translate_store(path)
        return self._translate_store

    # ------------------------------------------------------------ metadata

    @property
    def _meta_path(self) -> str:
        return os.path.join(self.path, ".meta")

    @property
    def _shards_path(self) -> str:
        return os.path.join(self.path, ".shards")

    def _load_meta(self) -> None:
        if os.path.exists(self._meta_path):
            with open(self._meta_path) as f:
                self.options = FieldOptions.from_dict(json.load(f))
        else:
            self.save_meta()

    def save_meta(self) -> None:
        if self.path is None:
            return
        from pilosa_tpu.ioutil import atomic_write_json

        atomic_write_json(self._meta_path, self.options.to_dict())

    def _load_shards(self) -> None:
        if self.path is not None and os.path.exists(self._shards_path):
            with open(self._shards_path) as f:
                self._shards = set(json.load(f))
        # union in shards discovered from opened fragments
        for view in self.views.values():
            self._shards |= view.available_shards()

    def _save_shards(self) -> None:
        # caller holds self._lock (serializing writers per field)
        if self.path is None:
            return
        from pilosa_tpu.ioutil import atomic_write_json

        atomic_write_json(self._shards_path, sorted(self._shards))

    def _open_views(self) -> None:
        views_dir = os.path.join(self.path, "views")
        if not os.path.isdir(views_dir):
            return
        for name in sorted(os.listdir(views_dir)):
            self.views[name] = View(
                os.path.join(views_dir, name), self.index, self.name, name,
                mutex=self._is_mutex_like,
                cache_type=self.options.cache_type,
                cache_size=self.options.cache_size,
            )

    # ------------------------------------------------------------- views

    @property
    def _is_mutex_like(self) -> bool:
        return self.options.type in (FieldType.MUTEX, FieldType.BOOL)

    @property
    def time_quantum(self) -> TimeQuantum:
        return TimeQuantum(self.options.time_quantum)

    def view(self, name: str) -> View | None:
        return self.views.get(name)

    def create_view_if_not_exists(self, name: str) -> View:
        with self._lock:
            v = self.views.get(name)
            if v is None:
                path = (
                    None if self.path is None
                    else os.path.join(self.path, "views", name)
                )
                v = View(
                    path, self.index, self.name, name,
                    mutex=self._is_mutex_like,
                    cache_type=self.options.cache_type,
                    cache_size=self.options.cache_size,
                )
                self.views[name] = v
            return v

    @property
    def bsi_view_name(self) -> str:
        return VIEW_BSI_PREFIX + self.name

    # ------------------------------------------------------------- shards

    def available_shards(self) -> set[int]:
        return set(self._shards)

    def add_remote_available_shards(self, shards: set[int]) -> None:
        """Merge shards owned by other nodes (reference
        AddRemoteAvailableShards, field.go:263-360)."""
        with self._lock:
            self._shards |= shards
            self._save_shards()

    def _note_shard(self, shard: int) -> None:
        shard = int(shard)  # numpy ints would poison the JSON .shards file
        with self._lock:
            if shard not in self._shards:
                self._shards.add(shard)
                self._save_shards()

    def _note_shards(self, shards) -> None:
        """Record many shards with ONE .shards write (bulk-import path;
        the per-shard variant would rewrite the file per fragment)."""
        shards = {int(s) for s in shards}
        with self._lock:
            new = shards - self._shards
            if new:
                self._shards |= new
                self._save_shards()

    # ------------------------------------------------------------ bit ops

    def set_bit(self, row: int, col: int, timestamp: _dt.datetime | None = None) -> bool:
        """Set a bit in the standard view and any time views
        (reference Field.SetBit, field.go:927)."""
        if self.options.type == FieldType.INT:
            raise ValueError(f"field {self.name} is an int field; use set_value")
        if self.options.type == FieldType.BOOL and row not in (FALSE_ROW_ID, TRUE_ROW_ID):
            raise ValueError("bool field rows must be 0 or 1")
        if timestamp is not None and self.options.type != FieldType.TIME:
            # validate before any write so a rejected call mutates nothing
            raise ValueError(f"field {self.name} has no time quantum")
        changed = False
        if not (self.options.type == FieldType.TIME and self.options.no_standard_view):
            changed |= self.create_view_if_not_exists(VIEW_STANDARD).set_bit(row, col)
        if timestamp is not None:
            for name in views_by_time(VIEW_STANDARD, timestamp, self.time_quantum):
                changed |= self.create_view_if_not_exists(name).set_bit(row, col)
        self._note_shard(col // SHARD_WIDTH)
        return changed

    def clear_bit(self, row: int, col: int) -> bool:
        """Clear a bit from the standard view and all time views
        (reference Field.ClearBit, field.go:967)."""
        changed = False
        for name, view in self.views.items():
            if name == VIEW_STANDARD or name.startswith(VIEW_STANDARD + "_"):
                changed |= view.clear_bit(row, col)
        return changed

    def row(self, row_id: int, shard: int) -> np.ndarray | None:
        view = self.view(VIEW_STANDARD)
        return None if view is None else view.row(row_id, shard)

    def device_row_stack(self, row_id: int, shards: tuple[int, ...]):
        """One standard-view row across many shards as a
        device-resident uint32 [n_shards, words] stack — the unit of
        the executor's fused all-shards-in-one-dispatch path (SURVEY.md
        §7 step 4: whole shard batches as single XLA programs; time
        ranges use device_time_row_stack).  Missing fragments
        contribute zero rows (semantically identical to the per-shard
        None propagation).  Cached per (row, shards) and invalidated by
        the per-fragment mutation generations, which are compared only
        when the view's write token has moved since the entry was last
        proved good (_stamped_hit, then _walked_hit)."""
        view = self.view(VIEW_STANDARD)
        key = (row_id, shards)
        stamp = (_stagecheck.view_token(view), _placement_token())
        self._note_access(self._row_stack_cache, key)
        dev = self._stamped_hit(key, stamp)
        if dev is not None:
            return dev
        # bind each fragment once: a concurrent delete_fragment between
        # two lookups must read as "empty", not crash.  BASE token: a
        # pending delta must NOT invalidate this stack — the executor
        # fuses it on top (device_delta_stacks + expr "dfuse")
        frags = [None if view is None else view.fragment(s) for s in shards]
        gens = (stamp[1],) + tuple(_frag_base_gen(fr) for fr in frags)
        dev = self._walked_hit(key, gens, stamp)
        if dev is not None:
            return dev
        # demoted-but-warm: the host tier holds the assembled stack —
        # promote asynchronously (bounded wait) or serve host bytes
        tiered = self._tier_consult(
            self._row_stack_cache, key, gens,
            lambda h: h[0] == gens and _live(h[1]))
        if tiered is not None:
            return tiered[1][1] if tiered[0] == "dev" else tiered[1]
        t_build = _time.perf_counter_ns()
        n_words = bm.n_words(SHARD_WIDTH)
        # np.empty, zeroing only rows no fragment fills: at north-star
        # scale the stack is ~1.25 GB and a full memset is a whole
        # extra memory pass before the copies even start
        stack = np.empty((_padded_rows(len(shards)), n_words),
                         dtype=np.uint32)
        for i, frag in enumerate(frags):
            copied = False
            if frag is not None:
                with frag._lock:  # consistent snapshot of a live row
                    arr = frag._rows.get(row_id)
                    if arr is not None:
                        stack[i] = arr
                        copied = True
            if not copied:
                stack[i] = 0
        stack[len(shards):] = 0  # device-count padding rows
        return self._place_and_cache_stack(key, gens, stack, stamp,
                                           t0_ns=t_build)

    def stage_rows(self, row_ids: list, shards: tuple[int, ...],
                   use_delta: bool = True) -> list[tuple]:
        """The standard-view rows one read stages on this field, in
        order -> ``[(base stack, delta pair or None), ...]``: what
        ``device_delta_stacks`` then ``device_row_stack`` give row by
        row, with the bookkeeping of the rows the view's write token
        proves good done ONCE for all of them.  The token is read
        first, before every lookup (stagecheck.py).  A row whose "no
        overlay pending" is remembered under that token AND whose base
        entry is stamped with it is good as of that one read of the
        token: one take of this field's lock looks all such rows up,
        and inside it one take of the residency manager's advances the
        LRU, row by row in the order given; one take of the access
        table's notes the accesses, one of the tally's the leaves.
        Every other row (first read, a write to its view since, an
        overlay pending, a dropped buffer) goes the builders' own way,
        alone: the overlay first and the token read again before the
        base, so a compaction racing the two can only re-apply the
        overlay, never drop it.

        ``use_delta=False`` (?nodelta=1): the touched fragments'
        pending deltas are compacted up front and no overlay is looked
        for."""
        if not use_delta:
            self.flush_deltas(shards)
        stamp = (_stagecheck.view_token(self.view(VIEW_STANDARD)),
                 _placement_token())
        cache = self._row_stack_cache
        no_delta = self._no_delta
        out: list = [None] * len(row_ids)
        ask = [(i, (row_id, shards)) for i, row_id in enumerate(row_ids)
               if not use_delta
               or no_delta.get(("delta", row_id, shards)) == stamp]
        good: list = []
        if ask:
            with self._lock:
                for i, key in ask:
                    hit = cache.get(key)
                    if (hit is not None and hit[2][0] == stamp
                            and _live(hit[1])):
                        out[i] = (hit[1], None)
                        good.append(key)
                if good:
                    residency.manager().touch_many(cache, good)
        if good:
            cid = id(cache)
            _observe.note_accesses([(cid, key) for key in good])
            _stagecheck.leaves_fast(len(good))
            self._note_tier("hbm", times=len(good))
        if len(good) < len(row_ids):
            for i, row_id in enumerate(row_ids):
                if out[i] is None:
                    mark = _stagecheck.mark()
                    ds = (self.device_delta_stacks(row_id, shards)
                          if use_delta else None)
                    out[i] = (self.device_row_stack(row_id, shards), ds)
                    _stagecheck.leaf_done(mark)
        return out

    def _stamped_hit(self, key, stamp, live=_live, tier: bool = True):
        """Step one of validating a cached stack (stagecheck.py):
        ``stamp`` holds the write token of every view the entry was
        built from, READ BEFORE THIS LOOKUP, with the placement token
        and whatever settings the entry froze.  An entry last proved
        good under the same stamp, buffers live, is good: nothing in
        those views was written since, and its value is returned.  No
        fragment is touched.  None sends the caller on to the
        per-fragment tokens (``_walked_hit``); this check only ever
        spares that walk, it never rebuilds, copies or evicts.
        ``tier`` stamps the access on the flight record as an HBM hit
        (the delta overlays are not tiered)."""
        with self._lock:
            hit = self._row_stack_cache.get(key)
            if (hit is None or hit[2][0] != stamp
                    or not live(hit[1])):
                return None
            self._touch(self._row_stack_cache, key)
        if tier:
            self._note_tier("hbm")
        return hit[1]

    def _walked_hit(self, key, gens, stamp, live=_live,
                    tier: bool = True):
        """Step two: the caller has walked the shards and rebuilt the
        per-fragment tokens ``gens``; the comparison is the one that
        was there before write tokens.  A match re-stamps the entry
        with the token read in step one (a write to ANOTHER row moved
        the view's token and left this entry's own tokens alone: one
        walk, then O(1) again) and returns its value; None means
        rebuild, as ever."""
        _stagecheck.note_walk()
        with self._lock:
            hit = self._row_stack_cache.get(key)
            if hit is None or hit[0] != gens or not live(hit[1]):
                return None
            hit[2][0] = stamp
            self._touch(self._row_stack_cache, key)
        if tier:
            self._note_tier("hbm")
        return hit[1]

    @staticmethod
    def _touch(cache: dict, key) -> None:
        residency.manager().touch(cache, key)

    @staticmethod
    def _note_tier(outcome: str, ns: int = 0, times: int = 1) -> None:
        """Stamp one tiered stack access (hbm | promoted | fallback |
        cold; ``times`` of them) onto the active flight record — the
        stall-vs-hit split ?profile=1 and /debug/queries carry.  Silent
        under ?notiers (the escape's profile must look pre-tier too)."""
        if not residency.tiers_enabled():
            return
        rec = _observe.current()
        if rec is not None:
            rec.note_tier(outcome, ns, times)

    @staticmethod
    def _note_access(cache: dict, key) -> None:
        """Tick the prefetcher's access-statistics table
        (observe.access_stats) for one stack entry."""
        _observe.note_access((id(cache), key))

    def _tier_consult(self, cache: dict, key, gens, valid):
        """Host-tier consult after an owner-cache miss: enqueue the
        async promotion (single-flight per key), wait a bounded slice
        of the request's deadline, and return ``("dev", entry)`` when
        the promoted owner-cache entry landed in time (``valid``
        re-checks it) — else ``("host", value)``, the host-compute
        fallback (bit-exact; the promotion keeps running for the next
        query).  None on a true cold miss: the caller assembles from
        fragment state, exactly the pre-tier path."""
        from pilosa_tpu.serve import deadline as _deadline

        mgr = residency.manager()
        ent = mgr.host_lookup(cache, key, gens)
        if ent is None:
            return None
        t0 = _time.perf_counter_ns()
        fl = residency.promoter().submit(ent)
        if fl is not None:
            fl.event.wait(
                residency.promote_wait_s(_deadline.current()))
        with self._lock:
            hit = cache.get(key)
            if hit is not None and valid(hit):
                self._touch(cache, key)
                self._note_tier("promoted",
                                _time.perf_counter_ns() - t0)
                return ("dev", hit)
        mgr.note_fallback()
        self._note_tier("fallback", _time.perf_counter_ns() - t0)
        return ("host", ent.host_value())

    @staticmethod
    def _place_on_devices(stack: np.ndarray):
        """Place a host array on device — sharded along axis 0 over
        the [mesh] shard plan (parallel/meshexec.py) when the mesh is
        active, so device assignment follows the same contiguous-block
        split the shard_map programs execute; a plain (uncommitted)
        single-device put when the mesh is disabled or only one chip
        is visible — the exact pre-mesh placement.  On a single CPU
        device the stack stays a host numpy array: every bm op
        dispatches host arrays to numpy + the native popcount kernels
        (ops/hostkernels.py), which beat XLA:CPU codegen ~8x at query
        shapes."""
        if bm.host_mode():
            return np.ascontiguousarray(stack)
        if jax.process_count() > 1:
            # multi-process: this stack holds NODE-LOCAL fragments, so
            # it must live on node-local devices — the global mesh is
            # spmd.py's (collective plans feed each process's blocks
            # from its own fragments); a device_put here against
            # jax.devices() would trip the same-value-on-every-process
            # rule and imply collectives no peer is entering
            from pilosa_tpu.parallel import mesh as pmesh

            local = jax.local_devices()
            if len(local) > 1:
                from pilosa_tpu import devobs

                devobs.note_transfer(stack.nbytes, len(local),
                                     "field.shard_stack")
                return pmesh.shard_stack(pmesh.local_device_mesh(), stack)
            return bm.device_put(stack, local[0],
                                         label="field.stack")
        return _meshexec().place_stack(stack, label="field.stack")

    def device_time_row_stack(self, row_id: int, shards: tuple[int, ...],
                              view_names: tuple[str, ...]):
        """One row UNIONED across a set of time views, as a device
        [n_shards, words] stack — the fused time-range Row operand
        (f.row_time's per-shard union, batched).  The union happens
        host-side (numpy OR over the fragments' host rows), so a wide
        cover costs ONE cache entry and one device transfer, not one
        per view.  Cached per (row, shards, views); every contributing
        fragment's generation invalidates, compared only when one of
        the covering views' write tokens has moved (_stamped_hit)."""
        key = ("time", row_id, shards, view_names)
        views = [self.view(vn) for vn in view_names]
        stamp = (tuple(_stagecheck.view_token(v) for v in views),
                 _placement_token())
        self._note_access(self._row_stack_cache, key)
        dev = self._stamped_hit(key, stamp)
        if dev is not None:
            return dev
        frag_grid = []
        gens = [stamp[1]]
        for s in shards:
            frags = [None if v is None else v.fragment(s) for v in views]
            frag_grid.append(frags)
            gens.append(tuple(_frag_gen(fr) for fr in frags))
        gens = tuple(gens)
        dev = self._walked_hit(key, gens, stamp)
        if dev is not None:
            return dev
        tiered = self._tier_consult(
            self._row_stack_cache, key, gens,
            lambda h: h[0] == gens and _live(h[1]))
        if tiered is not None:
            return tiered[1][1] if tiered[0] == "dev" else tiered[1]
        t_build = _time.perf_counter_ns()
        n_words = bm.n_words(SHARD_WIDTH)
        # np.empty + first-contributor copy: no whole-stack memset (see
        # device_row_stack); later contributors OR-accumulate
        stack = np.empty((_padded_rows(len(shards)), n_words),
                         dtype=np.uint32)
        for i, frags in enumerate(frag_grid):
            wrote = False
            for fr in frags:
                if fr is None:
                    continue
                with fr._lock:
                    # EFFECTIVE words (base ⊕ pending delta): the time
                    # union happens host-side, so the overlay applies
                    # here rather than as device leaves — the cache key
                    # (_frag_gen, delta_seq included) invalidates on
                    # every delta write to a covering fragment
                    arr, _ = fr._row_words_effective_locked(row_id)
                    if arr is not None:
                        if wrote:
                            np.bitwise_or(stack[i], arr, out=stack[i])
                        else:
                            stack[i] = arr
                            wrote = True
            if not wrote:
                stack[i] = 0
        stack[len(shards):] = 0
        return self._place_and_cache_stack(key, gens, stack, stamp,
                                           t0_ns=t_build)

    @staticmethod
    def _entry_cap(fixed_cap: int) -> int:
        """Per-entry cacheability cap: the fixed default, or a quarter
        of the residency budget when the OPERATOR sized the budget for
        a bigger working set (a 10B-column row stack is ~1.25 GB — it
        must be cacheable on a machine provisioned for it).  A probed
        default budget never relaxes the cap: on a big device a giant
        one-off stack must stay uncacheable rather than evict the
        whole warm cache."""
        mgr = residency.manager()
        if not mgr.operator_sized:
            return fixed_cap
        return max(fixed_cap, mgr.budget // 4)

    def _place_and_cache_stack(self, key, gens, stack: np.ndarray,
                               stamp, t0_ns: int | None = None):
        dev = self._place_on_devices(stack)
        if t0_ns is not None:
            # cold-build attribution: this query paid the fragment
            # re-assembly + placement (nothing in HBM or the host tier)
            self._note_tier("cold", _time.perf_counter_ns() - t0_ns)
        entry_bytes = stack.nbytes
        if entry_bytes > self._entry_cap(self.ROW_STACK_CACHE_BYTES):
            return dev  # uncacheable; never evict the warm cache for it
        place = self._place_on_devices

        def _promote(arr, _g=gens):
            # async re-promotion: re-place the demoted host stack under
            # whatever [mesh] layout is then in force; a placement-
            # token drift simply misses at the consumer and rebuilds
            # (unstamped: its first read walks the shards once)
            return (_g, place(arr), [None])

        self._evict_and_insert(
            self._row_stack_cache, key, (gens, dev, [stamp]), entry_bytes,
            max_entries=64, devices=_placement_devices(),
            token=gens, host=stack, promote=_promote)
        return dev

    def device_delta_stacks(self, row_id: int, shards: tuple[int, ...]):
        """The fused read side of streaming ingest: pending delta
        overlays for one standard-view row across the shard set, as a
        pair of device uint32 [n_shards, words] stacks ``(set_stack,
        clear_stack)`` — the operands of ops.expr's ``dfuse`` node
        ``(base & ~clear) | set``.  Returns None when NO fragment has a
        pending overlay for this row (the common post-compaction case:
        the tree shape stays the plain leaf and nothing recompiles).

        Cached per (row, shards) keyed on the per-fragment ``(uid,
        row_seq)`` tokens — a delta write to a DIFFERENT row leaves a
        cached pair valid, so only the written row's stacks rebuild.
        Those tokens are rebuilt only when the view's write token has
        moved since the answer was last proved good (_stamped_hit),
        and the answer so remembered includes None (_no_delta).
        Safe under a concurrent compaction because delta application
        is idempotent: the executor stages these BEFORE the base stack,
        and re-applying an already-merged overlay reproduces the same
        effective words ((b&~c|s)&~c|s == b&~c|s)."""
        view = self.view(VIEW_STANDARD)
        key = ("delta", row_id, shards)
        stamp = (_stagecheck.view_token(view), _placement_token())
        if self._no_delta.get(key) == stamp:
            return None
        pair = self._stamped_hit(key, stamp, _pair_live, tier=False)
        if pair is not None:
            return pair
        frags, toks = self._delta_tokens(view, key, stamp)
        if toks is None:
            return None
        pair = self._walked_hit(key, toks, stamp, _pair_live,
                                tier=False)
        if pair is not None:
            return pair
        n_words = bm.n_words(SHARD_WIDTH)
        rows = _padded_rows(len(shards))
        set_stack = np.zeros((rows, n_words), dtype=np.uint32)
        clear_stack = np.zeros((rows, n_words), dtype=np.uint32)
        for i, fr in enumerate(frags):
            if fr is None:
                continue
            with fr._lock:
                d = fr._delta
                if d is None or not d.row_touched(row_id):
                    continue
                s = d.sets.get(row_id)
                if s is not None:
                    set_stack[i] = s
                c = d.clears.get(row_id)
                if c is not None:
                    clear_stack[i] = c
        pair = (self._place_on_devices(set_stack),
                self._place_on_devices(clear_stack))
        entry_bytes = set_stack.nbytes + clear_stack.nbytes
        if entry_bytes <= self._entry_cap(self.ROW_STACK_CACHE_BYTES):
            self._evict_and_insert(self._row_stack_cache, key,
                                   (toks, pair, [stamp]), entry_bytes,
                                   max_entries=64,
                                   devices=_placement_devices())
        return pair

    def delta_pending(self, row_id: int, shards: tuple[int, ...]) -> bool:
        """Whether any fragment of the shard set has a pending overlay
        for this standard-view row: what ``device_delta_stacks``
        answers None to, for a caller that wants no stacks built
        (containers.plan_fused).  "No" is remembered like theirs."""
        view = self.view(VIEW_STANDARD)
        key = ("delta", row_id, shards)
        stamp = (_stagecheck.view_token(view), _placement_token())
        if self._no_delta.get(key) == stamp:
            return False
        return self._delta_tokens(view, key, stamp)[1] is not None

    #: entries of _no_delta kept before the table starts over (a stamp
    #: holds no buffer; forgetting one costs the next read one walk)
    _NO_DELTA_CAP = 4096

    def _delta_tokens(self, view, key, stamp):
        """The walk behind both delta builders: ``(frags, toks)`` with
        one ``(uid, row_seq)`` token per shard for the row of ``key``,
        or ``(frags, None)`` when no fragment has a pending overlay
        for it.  That answer is remembered under ``stamp``, the view
        write token read before the walk, so the next read of an
        unwritten view does not walk again."""
        row_id, shards = key[1], key[2]
        frags = [None if view is None else view.fragment(s)
                 for s in shards]
        toks = (stamp[1],) + tuple(
            0 if fr is None
            else (fr._uid, fr._delta_row_seq(row_id))
            for fr in frags)
        if any(t and t[1] for t in toks[1:]):
            self._no_delta.pop(key, None)
            return frags, toks
        _stagecheck.note_walk()
        if len(self._no_delta) >= self._NO_DELTA_CAP:
            self._no_delta.clear()
        self._no_delta[key] = stamp
        return frags, None

    def device_delta_container_leaves(self, row_id: int,
                                      shards: tuple[int, ...]):
        """Pending delta overlays for one standard-view row in POOLED
        compressed form: a pair of ContainerLeaf ``(set_leaf,
        clear_leaf)`` — the operands of the bitmap VM's ``dfuse`` node
        ``(base & ~clear) | set`` (ops/containers.stage_vm), or None
        when NO fragment has a pending overlay for this row (the
        common post-compaction case, same gate as
        device_delta_stacks).  A delta plane per shard is at most
        SHARD_WIDTH/2^16 containers, and only the non-empty ones pool.

        Cached per (row, shards) keyed on the per-fragment ``(uid,
        row_seq)`` tokens, like device_delta_stacks (None included,
        and walked only when the view's write token has moved) — and
        safe under a concurrent compaction for the same reason: the VM
        stages these BEFORE the base leaf, and re-applying an
        already-merged overlay is idempotent ((b&~c|s)&~c|s ==
        b&~c|s)."""
        view = self.view(VIEW_STANDARD)
        key = ("dcont", row_id, shards)
        stamp = (_stagecheck.view_token(view), _placement_token())
        if self._no_delta.get(key) == stamp:
            return None
        pair = self._stamped_hit(key, stamp, _leaf_pair_live,
                                 tier=False)
        if pair is not None:
            return pair
        frags, toks = self._delta_tokens(view, key, stamp)
        if toks is None:
            return None
        pair = self._walked_hit(key, toks, stamp, _leaf_pair_live,
                                tier=False)
        if pair is not None:
            return pair
        cpr = SHARD_WIDTH // ct.CONTAINER_BITS
        planes: list[list] = [[], []]  # per kind: (set, clear) words
        for fr in frags:
            s = c = None
            if fr is not None:
                with fr._lock:
                    d = fr._delta
                    if d is not None and d.row_touched(row_id):
                        # copy under the fragment lock: later delta
                        # writes mutate these word arrays in place
                        s = d.sets.get(row_id)
                        s = None if s is None else s.copy()
                        c = d.clears.get(row_id)
                        c = None if c is None else c.copy()
            planes[0].append(s)
            planes[1].append(c)
        pair = []
        for words_per_shard in planes:
            entries: list = []
            starts: list[int] = []
            kinds: list = []
            blocks_list: list[np.ndarray] = []
            n = 0
            for words in words_per_shard:
                starts.append(n)
                if words is None:
                    entries.append(np.empty(0, dtype=np.int64))
                    kinds.append(np.empty(0, dtype=np.uint8))
                    continue
                blocks = words.reshape(cpr, ct.CWORDS)
                keys = np.flatnonzero(blocks.any(axis=1)).astype(np.int64)
                entries.append(keys)
                kinds.append(np.ones(len(keys), dtype=np.uint8))
                if len(keys):
                    blocks_list.append(blocks[keys])
                    n += len(keys)
            rows = n + 1 if bm.host_mode() else ct._pow2(n + 1)
            pool = np.zeros((rows, ct.CWORDS), dtype=np.uint32)
            if blocks_list:
                pool[:n] = np.concatenate(blocks_list, axis=0)
            pair.append(ct.ContainerLeaf(shards, entries, starts, kinds,
                                         self._place_pool(pool), n,
                                         pool.nbytes))
        pair = (pair[0], pair[1])
        entry_bytes = pair[0].nbytes + pair[1].nbytes
        if entry_bytes <= self._entry_cap(self.ROW_STACK_CACHE_BYTES):
            self._evict_and_insert(self._row_stack_cache, key,
                                   (toks, pair, [stamp]), entry_bytes,
                                   max_entries=64, kind="compressed",
                                   devices=_placement_devices())
        return pair

    def device_container_leaf(self, row_id: int, shards: tuple[int, ...]):
        """One standard-view row across the shard set in POOLED
        compressed form (ops/containers.ContainerLeaf): each shard's
        non-empty 2^16-bit containers (Fragment.row_containers)
        concatenate into one device word pool, driven by the host-side
        per-shard directory — the compressed analog of
        device_row_stack, cached alongside it under the same BASE
        generation tokens (delta writes leave it warm; the engine
        routes delta-touched rows dense).  The residency manager
        accounts the REAL compressed bytes under kind="compressed", so
        a sparse row costs HBM proportional to its containers, not to
        shards x shard-width — the capacity multiplier of the roaring
        layout.  The per-fragment tokens are compared only when the
        view's write token, the placement or a setting below has
        moved since the leaf was last proved good (_stamped_hit).
        A leaf with dense slots leaves its verdict behind under the
        same stamp (row_kept_dense), so the next read of the row can
        decline the compressed engines without staging anything."""
        view = self.view(VIEW_STANDARD)
        key = ("cont", row_id, shards)
        settings = self._container_settings()
        stamp = (_stagecheck.view_token(view),) + settings
        leaf = self._container_leaf(view, key, settings, stamp)
        if leaf.dense_slots():
            if len(self._kept_dense) >= self._NO_DELTA_CAP:
                self._kept_dense.clear()
            self._kept_dense[key] = stamp
        return leaf

    @staticmethod
    def _container_settings() -> tuple:
        """What a container leaf froze besides fragment state, joined
        into its tokens.  The fill-ratio threshold: a cached leaf froze
        each fragment's sparse-vs-hot verdict, so a runtime
        [containers] threshold change must miss and re-evaluate — not
        wait for the next base mutation.  The effective kind-selection
        knobs (they decide the pool layout), and kinds switch off
        entirely while a mesh is active: the kind-dispatched programs
        are single-device, so mesh-routed queries keep the exact
        legacy all-bitmap leaves.  settings[1] is that effective
        ``kinds``."""
        cfg = ct.config()
        eff_kinds = bool(cfg.kinds) and not _meshexec().active()
        return (cfg.threshold, eff_kinds, cfg.array_max, cfg.run_cap,
                _placement_token())

    def row_kept_dense(self, row_id: int, shards: tuple[int, ...]) -> bool:
        """Whether this standard-view row is KNOWN to be kept dense in
        some shard of the set: ``device_container_leaf`` found dense
        slots, and neither the view's write token nor a setting the
        verdict froze has moved since (the stamp is read before the
        lookup, as ever).  Then the compressed engines' all-or-nothing
        decline is certain and needs no leaf staged
        (containers.kept_dense).  False = not known: never asked, or
        something moved; the caller stages and finds out."""
        stamp = ((_stagecheck.view_token(self.view(VIEW_STANDARD)),)
                 + self._container_settings())
        return self._kept_dense.get(("cont", row_id, shards)) == stamp

    def _container_leaf(self, view, key, settings, stamp):
        row_id, shards = key[1], key[2]
        eff_kinds = settings[1]
        self._note_access(self._row_stack_cache, key)
        leaf = self._stamped_hit(key, stamp, _leaf_live)
        if leaf is not None:
            return leaf
        frags = [None if view is None else view.fragment(s)
                 for s in shards]
        gens = (*settings, *(_frag_base_gen(fr) for fr in frags))
        leaf = self._walked_hit(key, gens, stamp, _leaf_live)
        if leaf is not None:
            return leaf
        tiered = self._tier_consult(
            self._row_stack_cache, key, gens,
            lambda h: h[0] == gens and _leaf_live(h[1]))
        if tiered is not None:
            return tiered[1][1] if tiered[0] == "dev" else tiered[1]
        t_build = _time.perf_counter_ns()
        entries: list = []
        starts: list[int] = []
        kinds: list = []
        blocks_list: list[np.ndarray] = []
        kinds_list: list[np.ndarray] = []
        n_dir = 0
        for fr in frags:
            starts.append(n_dir)
            if fr is None:
                entries.append(np.empty(0, dtype=np.int64))
                kinds.append(np.empty(0, dtype=np.uint8))
                continue
            rc = (fr.row_container_kinds(row_id) if eff_kinds
                  else fr.row_containers(row_id))
            if rc is None:
                # hot row in this fragment: dense-fallback evidence
                entries.append(None)
                kinds.append(None)
                continue
            if eff_kinds:
                keys, blocks, _bits, ks = rc
            else:
                keys, blocks, _bits = rc
                # kind 1 = dense bitmap block
                ks = np.ones(len(keys), dtype=np.uint8)
            entries.append(keys)
            kinds.append(ks)
            if len(keys):
                blocks_list.append(blocks)
                kinds_list.append(ks)
                n_dir += len(keys)
        flat_kinds = (np.concatenate(kinds_list) if kinds_list
                      else np.empty(0, dtype=np.uint8))
        if eff_kinds and bool((flat_kinds != 1).any()):
            leaf, host_payload = self._build_kinds_leaf(
                shards, entries, starts, kinds, blocks_list,
                flat_kinds)
        else:
            # all-bitmap directory (or kinds disabled): the exact
            # legacy layout, byte-identical pools and indices.
            # >= 1 zero tail row: gather index n is the canonical
            # absent-container block.  On device the row count pads to
            # pow2 so the gather programs lower O(log) distinct
            # shapes; in host mode there is no jit specialization to
            # bound, and the tight pool keeps resident bytes equal to
            # real data
            n = n_dir
            rows = n + 1 if bm.host_mode() else ct._pow2(n + 1)
            pool = np.zeros((rows, ct.CWORDS), dtype=np.uint32)
            if blocks_list:
                pool[:n] = np.concatenate(blocks_list, axis=0)
            # a kinds-eligible all-bitmap row rebuilds plain uint8 ones
            # so stale array/run kind bytes can never leak through
            if eff_kinds:
                kinds = [None if k is None
                         else np.ones(len(k), dtype=np.uint8)
                         for k in kinds]
            leaf = ct.ContainerLeaf(shards, entries, starts, kinds,
                                    self._place_pool(pool), n,
                                    pool.nbytes)
            host_payload = pool
        self._note_tier("cold", _time.perf_counter_ns() - t_build)
        if leaf.nbytes <= self._entry_cap(self.ROW_STACK_CACHE_BYTES):
            place_pool = self._place_pool
            kd = None
            if leaf.has_kinds:
                kd = {"array": int(leaf.apool.nbytes)
                      + int(leaf.acard.nbytes),
                      "run": int(leaf.rpool.nbytes)}

            def _promote_leaf(p, _g=gens, _leaf=leaf, _sh=shards):
                if isinstance(p, tuple):
                    pool_h, apool_h, acard_h, rpool_h = p
                    return (_g, ct.ContainerLeaf(
                        _sh, _leaf.entries, _leaf.starts, _leaf.kinds,
                        place_pool(pool_h), _leaf.n, _leaf.nbytes,
                        slots=_leaf.slots,
                        apool=place_pool(apool_h),
                        acard=place_pool(acard_h),
                        rpool=place_pool(rpool_h),
                        an=_leaf.an, rn=_leaf.rn), [None])
                return (_g, ct.ContainerLeaf(
                    _sh, _leaf.entries, _leaf.starts, _leaf.kinds,
                    place_pool(p), _leaf.n, p.nbytes), [None])

            def _leaf_host(p, _leaf=leaf, _sh=shards):
                if isinstance(p, tuple):
                    pool_h, apool_h, acard_h, rpool_h = p
                    return ct.ContainerLeaf(
                        _sh, _leaf.entries, _leaf.starts, _leaf.kinds,
                        np.ascontiguousarray(pool_h), _leaf.n,
                        _leaf.nbytes, slots=_leaf.slots,
                        apool=np.ascontiguousarray(apool_h),
                        acard=np.ascontiguousarray(acard_h),
                        rpool=np.ascontiguousarray(rpool_h),
                        an=_leaf.an, rn=_leaf.rn)
                return ct.ContainerLeaf(
                    _sh, _leaf.entries, _leaf.starts, _leaf.kinds,
                    np.ascontiguousarray(p), _leaf.n, p.nbytes)

            self._evict_and_insert(self._row_stack_cache, key,
                                   (gens, leaf, [stamp]), leaf.nbytes,
                                   max_entries=64, kind="compressed",
                                   token=gens, host=host_payload,
                                   promote=_promote_leaf,
                                   fallback=_leaf_host,
                                   kind_detail=kd)
        return leaf

    def _build_kinds_leaf(self, shards, entries, starts, kinds,
                          blocks_list, flat_kinds):
        """Split a mixed-kind container directory into the per-kind
        compact pools (ops/kindpools.split_pools) and assemble the
        kinds ContainerLeaf.  Every pool keeps >= 1 canonical zero
        tail row (empty bitmap block / card-0 array / all-invalid run
        pairs) — the absent-container gather targets — and device row
        counts pad to pow2 per pool (host pools stay tight)."""
        from pilosa_tpu.ops import kindpools as kp

        flat_blocks = (np.concatenate(blocks_list, axis=0)
                       if blocks_list
                       else np.empty((0, ct.CWORDS), dtype=np.uint32))
        slots_flat, bblocks, apool_t, acard_t, rpool_t = \
            kp.split_pools(flat_blocks, flat_kinds)
        # re-slice the flat kind-local slots back per shard (starts[]
        # indexes the flat directory order)
        slots = []
        off = 0
        for ks in kinds:
            if ks is None:
                slots.append(None)
                continue
            slots.append(slots_flat[off:off + len(ks)])
            off += len(ks)
        host = bm.host_mode()
        bn = int(bblocks.shape[0])
        an = int(apool_t.shape[0])
        rn = int(rpool_t.shape[0])
        brows = bn + 1 if host else ct._pow2(bn + 1)
        pool = np.zeros((brows, ct.CWORDS), dtype=np.uint32)
        pool[:bn] = bblocks
        arows = an + 1 if host else ct._pow2(an + 1)
        apool = np.full((arows, apool_t.shape[1]), kp.ARRAY_PAD,
                        dtype=np.uint16)
        apool[:an] = apool_t
        acard = np.zeros(arows, dtype=np.int32)
        acard[:an] = acard_t
        rrows = rn + 1 if host else ct._pow2(rn + 1)
        rpool = np.zeros((rrows, rpool_t.shape[1]), dtype=np.uint16)
        rpool[:, 0::2] = 1  # (1, 0): the canonical invalid pair
        rpool[:rn] = rpool_t
        nbytes = (pool.nbytes + apool.nbytes + acard.nbytes
                  + rpool.nbytes)
        leaf = ct.ContainerLeaf(
            shards, entries, starts, kinds, self._place_pool(pool),
            bn, nbytes, slots=slots, apool=self._place_pool(apool),
            acard=self._place_pool(acard),
            rpool=self._place_pool(rpool), an=an, rn=rn)
        return leaf, (pool, apool, acard, rpool)

    @staticmethod
    def _place_pool(pool: np.ndarray):
        """Place a container word pool: host numpy in host mode, one
        local-device upload otherwise.  Deliberately NOT sharded on
        the pool's row axis — pools are gather operands whose rows are
        addressed by indices that cross shard boundaries, so under an
        active mesh the pool REPLICATES onto every mesh device and the
        gather DOMAIN axis shards instead (ops/expr
        _build_mesh_gather_program)."""
        if bm.host_mode():
            return np.ascontiguousarray(pool)
        if jax.process_count() > 1:
            return bm.device_put(pool, jax.local_devices()[0],
                                         label="field.containers")
        if _meshexec().active():
            return _meshexec().place_replicated(
                pool, label="field.containers")
        return bm.device_put(pool, label="field.containers")

    def flush_deltas(self, shards=None) -> int:
        """Merge every pending delta of this field's fragments into
        base state (the ``?nodelta=1`` escape and test barrier).
        Returns the number of bit positions merged."""
        merged = 0
        for view in list(self.views.values()):
            frags = (list(view.fragments.values()) if shards is None
                     else [view.fragment(s) for s in shards])
            for frag in frags:
                if frag is not None:
                    merged += frag.flush_delta()
        return merged

    def _evict_and_insert(self, cache: dict, key, entry, entry_bytes: int,
                          max_entries: int, kind: str = "dense",
                          devices: int = 1, token=None, host=None,
                          promote=None, fallback=None,
                          kind_detail=None) -> None:
        """Insert under the entry cap; BYTE budgeting is global — the
        process-wide residency manager sees every owner's device caches
        and LRU-evicts across all of them, so the true device total is
        bounded even when several caches hold views of the same field
        (runtime/residency.py).  The manager may concurrently pop
        entries from this dict under its own lock, so every removal
        here tolerates a vanished key, and admit happens inside
        self._lock so the inserted entry can't be popped before it is
        tracked.  ``token``+``host``+``promote`` opt the entry into the
        host tier (eviction demotes instead of dropping); cap
        evictions DEMOTE too — the FIFO-displaced entry is still valid,
        merely cold."""
        mgr = residency.manager()
        with self._lock:
            if cache.pop(key, None) is not None:
                mgr.forget(cache, key)
            while len(cache) >= max_entries:
                try:
                    k = next(iter(cache))
                except StopIteration:
                    break
                cache.pop(k, None)
                mgr.demote(cache, k)
            cache[key] = entry
            mgr.admit(cache, key, entry_bytes, kind=kind,
                      devices=devices, token=token, host=host,
                      promote=promote, fallback=fallback,
                      kind_detail=kind_detail)

    def drop_shard_stacks(self, shard: int) -> int:
        """Drop every field-level stack-cache entry whose shard set
        covers ``shard`` and release its residency accounting (device
        placements AND tenant byte-attribution) — the rebalance
        cutover hook for a node losing the shard.  Generation stamps
        do not cover an ownership change (nothing local mutated), and
        close()'s whole-field sweep is too blunt: the node usually
        keeps serving this field's OTHER shards.  Every stack-cache
        key embeds the shard tuple (``(row, shards)``, ``("time", row,
        shards, views)``, the matrix cache's bare ``shards``...), so
        membership in any int-tuple component identifies coverage."""

        shard = int(shard)

        def covers(key) -> bool:
            if not isinstance(key, tuple):
                return False
            if key and all(isinstance(x, int) for x in key):
                return shard in key  # matrix cache: the key IS shards
            return any(isinstance(x, tuple) and x
                       and all(isinstance(y, int) for y in x)
                       and shard in x
                       for x in key)

        mgr = residency.manager()
        n = 0
        with self._lock:
            for cache in (self._row_stack_cache,
                          self._matrix_stack_cache):
                for k in [k for k in cache if covers(k)]:
                    cache.pop(k, None)
                    mgr.forget(cache, k)
                    n += 1
        return n

    #: device-memory budget for concatenated matrix stacks (bytes)
    MATRIX_STACK_CACHE_BYTES = 512 << 20

    def device_matrix_stack(self, shards: tuple[int, ...]):
        """Standard-view row matrices of many shards concatenated along
        the row axis: (gens, row_ids int64[N], shard_pos int32
        host[Np], shard_pos device[Np], matrix uint32 device[Np,
        words]), where Np >= N is padded to a device-count multiple —
        consumers must truncate against row_ids (pad entries read as
        position 0 over all-zero matrix rows).  ``shard_pos[i]`` is the
        POSITION of row i's shard within ``shards`` — it indexes the
        executor's fused filter stacks, which use the same order.  This
        is the fused TopN operand: the whole index scans in one
        dispatch instead of one per fragment (fragment.top,
        fragment.go:1570, batched across executor.go:2561's shard
        loop).  Returns (gens, [], None, None, None) when every
        fragment is empty — empty results are NOT cached (recomputing
        them is a few dict lookups, and a 0-byte entry could FIFO-evict
        a warm multi-MB stack via the entry cap).  Cached per shards
        tuple; per-fragment mutation generations invalidate."""
        view = self.view(VIEW_STANDARD)
        frags = [None if view is None else view.fragment(s) for s in shards]
        key = shards
        gens = []
        parts = []  # (pos, row_ids, host matrix) per non-empty fragment
        for i, frag in enumerate(frags):
            if frag is None:
                gens.append(0)
                continue
            with frag._lock:
                # _stacked merges any pending delta (bumping _gen), so
                # the token must be read AFTER it or the cache entry is
                # stamped with a pre-merge token that can never hit
                ids, mat = frag._stacked()
                gens.append(_frag_gen(frag))
            if len(ids):
                parts.append((i, ids, mat))
        # placement token APPENDED (not prepended): consumers index
        # gens positionally by shard slot (_fused_topn_counts_uncached
        # reads gens[pos] to validate per-fragment cache warms)
        gens.append(_placement_token())
        gens = tuple(gens)
        self._note_access(self._matrix_stack_cache, key)
        with self._lock:
            hit = self._matrix_stack_cache.get(key)
            if (hit is not None and hit[0] == gens
                    and (hit[4] is None or _live(hit[4]))):
                self._touch(self._matrix_stack_cache, key)
                self._note_tier("hbm")
                return hit
        tiered = self._tier_consult(
            self._matrix_stack_cache, key, gens,
            lambda h: h[0] == gens and (h[4] is None or _live(h[4])))
        if tiered is not None:
            return tiered[1]
        if not parts:
            return (gens, np.empty(0, dtype=np.int64), None, None, None)
        t_build = _time.perf_counter_ns()
        row_ids = np.concatenate([ids for _, ids, _ in parts])
        shard_pos = np.concatenate(
            [np.full(len(ids), pos, dtype=np.int32) for pos, ids, _ in parts])
        big = np.concatenate([m for _, _, m in parts], axis=0)
        pad = _padded_rows(len(row_ids)) - len(row_ids)
        if pad:
            big = np.pad(big, ((0, pad), (0, 0)))
            shard_pos = np.pad(shard_pos, (0, pad))
        mat_dev = self._place_on_devices(big)
        pos_dev = self._place_on_devices(shard_pos)
        self._note_tier("cold", _time.perf_counter_ns() - t_build)
        entry = (gens, row_ids, shard_pos, pos_dev, mat_dev)
        entry_bytes = big.nbytes
        if entry_bytes > self._entry_cap(self.MATRIX_STACK_CACHE_BYTES):
            return entry  # uncacheable; don't evict the warm cache for it
        place = self._place_on_devices

        def _promote_matrix(payload, _g=gens):
            ids_, pos_, big_ = payload
            return (_g, ids_, pos_, place(pos_), place(big_))

        def _matrix_host(payload, _g=gens):
            # host-compute fallback: the numpy halves stand in for the
            # device ones (bm dispatches numpy operands to the host
            # kernels; on a device backend they transfer implicitly —
            # still bounded by this query, never by a promotion queue)
            ids_, pos_, big_ = payload
            return (_g, ids_, pos_, pos_, big_)

        self._evict_and_insert(
            self._matrix_stack_cache, key, entry, entry_bytes,
            max_entries=8, devices=_placement_devices(),
            token=gens, host=(row_ids, shard_pos, big),
            promote=_promote_matrix, fallback=_matrix_host)
        return entry

    def time_view_times(self) -> list:
        """The timestamps encoded in this field's time-view names,
        memoized per view-name set (the executor's range clamping scans
        these on every time-range query; reference minMaxViews)."""
        with self._lock:
            names = tuple(self.views)
            cached = self._view_times_memo
            if cached is not None and cached[0] == names:
                return cached[1]
            times = []
            for name in names:
                part = name.rsplit("_", 1)[-1]
                if part.isdigit():
                    fmt = {4: "%Y", 6: "%Y%m", 8: "%Y%m%d",
                           10: "%Y%m%d%H"}.get(len(part))
                    if fmt:
                        times.append(_dt.datetime.strptime(part, fmt))
            self._view_times_memo = (names, times)
            return times

    def row_time(self, row_id: int, shard: int, start, end) -> np.ndarray | None:
        """Union of time views covering [start, end) for one shard
        (reference Field.RowTime / executor time-range Row)."""
        if not self.time_quantum:
            raise ValueError(f"field {self.name} has no time quantum")
        out = None
        for name in views_by_time_range(VIEW_STANDARD, start, end, self.time_quantum):
            view = self.view(name)
            if view is None:
                continue
            words = view.row(row_id, shard)
            if words is None:
                continue
            out = words if out is None else (out | words)
        return out

    def device_plane_stack(self, shards: tuple[int, ...]):
        """BSI plane stacks across shards as one device-resident uint32
        [n_shards, planes, words] tensor (planes = exists, sign, then
        bit_depth value planes) — the fused Sum path's operand.  Cached
        and generation-invalidated like device_row_stack (the BSI
        view's write token first, the per-fragment tokens when it has
        moved); shard axis is padded and mesh-sharded the same way."""
        from pilosa_tpu.ops import bsi as bsi_ops

        self._require_int()
        depth = self.options.bit_depth
        view = self.view(self.bsi_view_name)
        key = ("planes", shards, depth)
        stamp = (_stagecheck.view_token(view), _placement_token())
        self._note_access(self._row_stack_cache, key)
        dev = self._stamped_hit(key, stamp)
        if dev is not None:
            return dev
        frags = [None if view is None else view.fragment(s) for s in shards]
        gens = (stamp[1],) + tuple(_frag_gen(fr) for fr in frags)
        dev = self._walked_hit(key, gens, stamp)
        if dev is not None:
            return dev
        tiered = self._tier_consult(
            self._row_stack_cache, key, gens,
            lambda h: h[0] == gens and _live(h[1]))
        if tiered is not None:
            return tiered[1][1] if tiered[0] == "dev" else tiered[1]
        t_build = _time.perf_counter_ns()
        n_words = bm.n_words(SHARD_WIDTH)
        n_planes = bsi_ops.OFFSET_PLANE + depth
        # np.empty + per-plane copy-or-zero: no whole-stack memset (see
        # device_row_stack) — the plane stack is the largest builder
        stack = np.empty((_padded_rows(len(shards)), n_planes, n_words),
                         dtype=np.uint32)
        for i, frag in enumerate(frags):
            if frag is None:
                stack[i] = 0
                continue
            with frag._lock:
                for p in range(n_planes):
                    arr = frag._rows.get(p)
                    if arr is not None:
                        stack[i, p] = arr
                    else:
                        stack[i, p] = 0
        stack[len(shards):] = 0
        return self._place_and_cache_stack(key, gens, stack, stamp,
                                           t0_ns=t_build)

    # ------------------------------------------------------------ BSI ops

    def _require_int(self) -> None:
        if self.options.type != FieldType.INT:
            raise ValueError(f"field {self.name} is not an int field")

    def set_value(self, col: int, value: int) -> bool:
        """(reference Field.SetValue, field.go:1075)"""
        self._require_int()
        o = self.options
        if value < o.min:
            raise ValueError(f"value {value} below field minimum {o.min}")
        if value > o.max:
            raise ValueError(f"value {value} above field maximum {o.max}")
        base_value = value - o.base
        required = bit_depth(abs(base_value))
        if required > 63:
            raise ValueError("value is more than 63 bits from the field base")
        if required > o.bit_depth:
            with self._lock:
                o.bit_depth = required
                self.save_meta()
        view = self.create_view_if_not_exists(self.bsi_view_name)
        changed = view.set_value(col, o.bit_depth, base_value)
        self._note_shard(col // SHARD_WIDTH)
        return changed

    def value(self, col: int) -> tuple[int, bool]:
        """(reference Field.Value, field.go:1053)"""
        self._require_int()
        view = self.view(self.bsi_view_name)
        if view is None:
            return 0, False
        v, ok = view.value(col, self.options.bit_depth)
        if not ok:
            return 0, False
        return v + self.options.base, True

    def clear_value(self, col: int) -> bool:
        self._require_int()
        view = self.view(self.bsi_view_name)
        if view is None:
            return False
        frag = view.fragment(col // SHARD_WIDTH)
        return False if frag is None else frag.clear_value(col, self.options.bit_depth)

    def sum(self, filter_row, shard: int) -> tuple[int, int]:
        """Per-shard (sum, count) with base adjustment
        (reference Field.Sum, field.go:1121: sum + count*base)."""
        self._require_int()
        frag = self._bsi_fragment(shard)
        if frag is None:
            return 0, 0
        fw = None if filter_row is None else filter_row.shard_segment(shard)
        if filter_row is not None and fw is None:
            return 0, 0
        s, c = frag.sum(fw, self.options.bit_depth)
        return s + c * self.options.base, c

    def min(self, filter_row, shard: int):
        self._require_int()
        frag = self._bsi_fragment(shard)
        if frag is None:
            return None
        fw = None if filter_row is None else filter_row.shard_segment(shard)
        if filter_row is not None and fw is None:
            return None
        v, c = frag.min(fw, self.options.bit_depth)
        if c == 0:
            return None
        return v + self.options.base, c

    def max(self, filter_row, shard: int):
        self._require_int()
        frag = self._bsi_fragment(shard)
        if frag is None:
            return None
        fw = None if filter_row is None else filter_row.shard_segment(shard)
        if filter_row is not None and fw is None:
            return None
        v, c = frag.max(fw, self.options.bit_depth)
        if c == 0:
            return None
        return v + self.options.base, c

    def _bsi_fragment(self, shard: int):
        view = self.view(self.bsi_view_name)
        return None if view is None else view.fragment(shard)

    @property
    def bit_depth_min(self) -> int:
        """(reference bitDepthMin, field.go:1636)"""
        return self.options.base - (1 << self.options.bit_depth) + 1

    @property
    def bit_depth_max(self) -> int:
        """(reference bitDepthMax, field.go:1641)"""
        return self.options.base + (1 << self.options.bit_depth) - 1

    def base_value(self, op: str, value: int) -> tuple[int, bool]:
        """Translate an absolute predicate into a base-relative one, with
        out-of-range detection (reference bsiGroup.baseValue,
        field.go:1583-1612).  Unlike the reference, a GT predicate exactly
        at the representable minimum keeps its true base value rather than
        clamping to 0 (the reference's `value > min` guard silently turns
        `> min` into `> base`, dropping every negative; untested upstream).
        Predicates beyond the representable range are resolved by the
        not-null fallbacks in range_op, so this only flags genuinely
        unsatisfiable cases."""
        lo, hi = self.bit_depth_min, self.bit_depth_max
        base = self.options.base
        if op in (">", ">="):
            if value > hi:
                return 0, True  # nothing can exceed the representable max
            return max(value, lo) - base, False
        if op in ("<", "<="):
            if value < lo:
                return 0, True  # nothing can undercut the representable min
            return min(value, hi) - base, False
        if op in ("==", "!="):
            if value < lo or value > hi:
                return 0, True
            return value - base, False
        raise ValueError(f"invalid range operator: {op}")

    def base_value_between(self, lo_v: int, hi_v: int) -> tuple[int, int, bool]:
        """(reference baseValueBetween, field.go:1614-1628)"""
        lo, hi = self.bit_depth_min, self.bit_depth_max
        if hi_v < lo or lo_v > hi:
            return 0, 0, True
        lo_v = max(lo_v, lo)
        hi_v = min(hi_v, hi)
        return lo_v - self.options.base, hi_v - self.options.base, False

    def _classify_range(self, op: str, value):
        """Shard-independent predicate preprocessing shared by the
        per-shard and fused range paths (executor.go:1616-1661
        executeRowBSIGroupShard): base-value translation with
        out-of-range detection, the whole-range LT/GT shortcuts against
        the declared min/max, and the out-of-range NEQ -> not-null rule.

        Returns one of: ("empty",), ("not_null",),
        ("op", op, base_pred), ("between", blo, bhi)."""
        o = self.options
        if op == "><":
            lo_v, hi_v = value
            blo, bhi, out_of_range = self.base_value_between(lo_v, hi_v)
            if out_of_range:
                return ("empty",)
            if lo_v <= o.min and hi_v >= o.max:
                return ("not_null",)
            return ("between", blo, bhi)
        if value is None:
            if op == "!=":
                return ("not_null",)
            raise ValueError("EQ null condition is not supported")
        predicate = value
        base_pred, out_of_range = self.base_value(op, predicate)
        if out_of_range and op != "!=":
            return ("empty",)
        if (
            (op == "<" and predicate > o.max)
            or (op == "<=" and predicate >= o.max)
            or (op == ">" and predicate < o.min)
            or (op == ">=" and predicate <= o.min)
        ):
            return ("not_null",)
        if out_of_range:  # op is "!="
            return ("not_null",)
        return ("op", op, base_pred)

    def range_op(self, op: str, predicate: int, shard: int) -> np.ndarray | None:
        """Per-shard BSI comparison in absolute value space."""
        self._require_int()
        frag = self._bsi_fragment(shard)
        if frag is None:
            return None
        plan = self._classify_range(op, predicate)
        if plan[0] == "empty":
            return None
        if plan[0] == "not_null":
            return frag.not_null(self.options.bit_depth)
        return frag.range_op(plan[1], self.options.bit_depth, plan[2])

    def range_between(self, lo_v: int, hi_v: int, shard: int) -> np.ndarray | None:
        self._require_int()
        frag = self._bsi_fragment(shard)
        if frag is None:
            return None
        plan = self._classify_range("><", [lo_v, hi_v])
        if plan[0] == "empty":
            return None
        if plan[0] == "not_null":
            return frag.not_null(self.options.bit_depth)
        return frag.range_between(self.options.bit_depth, plan[1], plan[2])

    def not_null(self, shard: int) -> np.ndarray | None:
        self._require_int()
        frag = self._bsi_fragment(shard)
        return None if frag is None else frag.not_null(self.options.bit_depth)

    def device_range_stack(self, op: str, value, shards: tuple[int, ...]):
        """Stacked analog of range_op/range_between: one vmapped device
        dispatch over all shards; preprocessing shared with the
        per-shard path via _classify_range.  op '><' takes [lo, hi];
        op '!=' with value None means not-null.  Returns uint32
        [n_shards, words]."""
        import jax.numpy as jnp

        from pilosa_tpu.ops import bsi as bsi_ops

        self._require_int()
        P = self.device_plane_stack(shards)
        plan = self._classify_range(op, value)
        if plan[0] == "empty":
            if isinstance(P, np.ndarray):
                return np.zeros(P.shape[::2], dtype=np.uint32)
            return jnp.zeros(P.shape[::2], dtype=jnp.uint32)
        if plan[0] == "not_null":
            return P[:, bsi_ops.EXISTS_PLANE]
        if isinstance(P, np.ndarray):
            # host engine: the per-shard loop stays in numpy + native
            # kernels — a vmap here would ship the whole plane stack
            # into XLA on every query
            fn = ((lambda Ps: bsi_ops.between_words(Ps, plan[1], plan[2]))
                  if plan[0] == "between" else
                  (lambda Ps: bsi_ops.range_words(Ps, plan[1], plan[2])))
            return np.stack([fn(P[i]) for i in range(P.shape[0])])
        if plan[0] == "between":
            return jax.vmap(
                lambda Ps: bsi_ops.between_words(Ps, plan[1], plan[2]))(P)
        return jax.vmap(
            lambda Ps: bsi_ops.range_words(Ps, plan[1], plan[2]))(P)

    # --------------------------------------------------------- bulk import

    def import_bits(self, rows, cols, timestamps=None, clear: bool = False) -> None:
        """Bulk import of (row, col[, timestamp]) bits: group positions by
        (view, shard) with time-quantum expansion, then one
        ``import_positions`` per fragment (reference Field.Import,
        field.go:1204-1282).  Mutex/bool fields fall back to per-bit
        writes so single-row-per-column semantics hold (reference
        bulkImportMutex, fragment.go:2094)."""
        # ndarrays flow straight to the vectorized grouping below; a
        # list() round-trip would cost ~0.5 s per million bits
        if not isinstance(rows, np.ndarray):
            rows = list(rows)
        if not isinstance(cols, np.ndarray):
            cols = list(cols)
        if len(rows) != len(cols):
            raise ValueError("rows and columns length mismatch")
        if timestamps is not None and len(timestamps) != len(rows):
            raise ValueError("timestamps length mismatch")
        if self.options.type == FieldType.INT:
            raise ValueError(f"field {self.name} is an int field; use import_values")
        # exact overflow bounds shared by EVERY path below (incl. the
        # mutex per-bit loop): pos = r*SHARD_WIDTH + offset with
        # offset <= SHARD_WIDTH-1 must fit int64, so
        # r <= (2^63 - SHARD_WIDTH) // SHARD_WIDTH, and column ids
        # themselves must fit int64
        max_row = ((1 << 63) - SHARD_WIDTH) // SHARD_WIDTH
        max_col = (1 << 63) - 1

        def _check_pair(r: int, c: int) -> None:
            if r < 0 or c < 0:
                raise ValueError("negative row or column id in import")
            if r > max_row:
                raise ValueError("row id too large for position space")
            if c > max_col:
                raise ValueError("column id too large for position space")

        def _as_i64(a, what: str) -> np.ndarray:
            # uint64 ndarrays >= 2^63 would wrap NEGATIVE on the int64
            # cast and surface as a misleading "negative id" error;
            # out-of-int64 Python ints raise OverflowError — map both
            # to the same ValueError contract the per-bit paths use,
            # classifying by sign so negatives never read "too large"
            if isinstance(a, np.ndarray) and a.dtype.kind == "u" \
                    and len(a) and int(a.max()) > max_col:
                raise ValueError(f"{what} id too large for position space")
            try:
                return np.asarray(a, dtype=np.int64)
            except OverflowError:
                if any(int(v) < 0 for v in a):
                    raise ValueError(
                        "negative row or column id in import") from None
                raise ValueError(
                    f"{what} id too large for position space") from None

        if self._is_mutex_like and not clear:
            for i, (r, c) in enumerate(zip(rows, cols)):
                r, c = int(r), int(c)  # int(): ndarray-safe
                _check_pair(r, c)
                ts = timestamps[i] if timestamps is not None else None
                self.set_bit(r, c, ts)
            return
        # (view, shard) -> positions
        by_frag: dict[tuple[str, int], "list[int] | np.ndarray"] = {}
        has_std = not (self.options.type == FieldType.TIME and self.options.no_standard_view)
        if timestamps is None and has_std:
            # the common bulk path (no time expansion) groups in numpy:
            # a per-bit setdefault/append loop costs ~1.5 s at 2M bits
            # where one argsort + split costs ~0.1 s
            cols_np = _as_i64(cols, "column")
            rows_np = _as_i64(rows, "row")
            if len(rows_np) and (rows_np.min() < 0 or cols_np.min() < 0):
                # the pre-vectorization path rejected negatives at the
                # uint64 conversion (OverflowError); int64 arithmetic
                # would silently wrap them into phantom rows instead
                raise ValueError("negative row or column id in import")
            if len(rows_np) and rows_np.max() > max_row:
                # same wrap hazard at the top: row*SHARD_WIDTH must fit
                # int64 or the position silently lands in a wrong row
                raise ValueError("row id too large for position space")
            from pilosa_tpu.ops.bitmap import group_indices

            shard_np = cols_np // SHARD_WIDTH
            pos_np = rows_np * SHARD_WIDTH + (cols_np % SHARD_WIDTH)
            for s, sel in group_indices(shard_np).items():
                by_frag[(VIEW_STANDARD, s)] = pos_np[sel]
        else:
            for i, (r, c) in enumerate(zip(rows, cols)):
                # int(): ndarray elements are fixed-width and would
                # wrap silently at r*SHARD_WIDTH; Python ints fail loud
                r, c = int(r), int(c)
                _check_pair(r, c)
                shard = c // SHARD_WIDTH
                pos = r * SHARD_WIDTH + (c % SHARD_WIDTH)
                if has_std:
                    by_frag.setdefault((VIEW_STANDARD, shard), []).append(pos)
                ts = timestamps[i] if timestamps is not None else None
                if ts is not None:
                    for name in views_by_time(VIEW_STANDARD, ts, self.time_quantum):
                        by_frag.setdefault((name, shard), []).append(pos)
        # one .shards write for the whole batch — per-fragment saves
        # rewrite a growing JSON file O(n^2) times on wide imports.
        # finally: a mid-batch failure must still register the shards
        # already written, or their data goes invisible to queries
        done: set[int] = set()
        try:
            for (vname, shard), positions in by_frag.items():
                view = self.create_view_if_not_exists(vname)
                frag = view.create_fragment_if_not_exists(shard)
                if clear:
                    frag.import_positions((), positions)
                else:
                    frag.import_positions(positions)
                done.add(shard)
        finally:
            self._note_shards(done)
        if not clear:
            # warm the fused-path stacks for the imported rows in the
            # background, hottest first — the first query after a bulk
            # import must not pay the whole stack assembly (prewarm.py)
            from pilosa_tpu.runtime import prewarm

            if isinstance(rows, np.ndarray):
                # np.unique beats a Python-level Counter over millions
                # of np scalars by ~10x
                uniq, cnt = np.unique(rows, return_counts=True)
                hot = [int(r) for r in
                       uniq[np.argsort(-cnt, kind="stable")]
                       [:prewarm.ROW_CAP]]
            else:
                from collections import Counter

                hot = [r for r, _ in
                       Counter(rows).most_common(prewarm.ROW_CAP)]
            self._prewarm(hot)

    def import_values(self, cols, values) -> None:
        """Bulk import of BSI values (reference Field.importValue,
        field.go:1284-1345)."""
        self._require_int()
        from pilosa_tpu.ops import bsi as bsi_ops

        if not isinstance(cols, np.ndarray):
            cols = list(cols)
        if not isinstance(values, np.ndarray):
            values = list(values)
        if len(cols) != len(values):
            raise ValueError("columns and values length mismatch")
        if len(cols) == 0:
            return
        o = self.options
        cols_np = np.asarray(cols, dtype=np.int64)
        if cols_np.min() < 0:
            raise ValueError("negative column id in import")
        # Coerce values preserving the pre-vectorization error
        # contract: floats raised TypeError (shift op), out-of-range
        # ints raised ValueError — np.asarray(..., int64) would
        # silently truncate the former and turn the latter into
        # OverflowError (a 500 instead of a 400 at the handler).
        raw = values if isinstance(values, np.ndarray) \
            else np.asarray(values)
        if np.issubdtype(raw.dtype, np.floating):
            raise TypeError("BSI values must be integers")
        if raw.dtype == object:
            # mixed/bigint input: range-check in Python first (values
            # that pass fit int64 — FieldOptions caps ranges below
            # 63 bits from base)
            for v in raw.tolist():
                if not isinstance(v, int):
                    raise TypeError("BSI values must be integers")
                if v < o.min or v > o.max:
                    raise ValueError(f"value {v} outside field range "
                                     f"[{o.min}, {o.max}]")
            vals_np = np.asarray(raw.tolist(), dtype=np.int64)
        else:
            vals_np = raw.astype(np.int64, copy=False)
        bad = vals_np[(vals_np < o.min) | (vals_np > o.max)]
        if len(bad):
            raise ValueError(f"value {int(bad[0])} outside field range "
                             f"[{o.min}, {o.max}]")
        bv = vals_np - o.base
        uv = np.abs(bv)
        required = bit_depth(int(uv.max()))
        if required > o.bit_depth:
            with self._lock:
                o.bit_depth = required
                self.save_meta()
        depth = o.bit_depth
        view = self.create_view_if_not_exists(self.bsi_view_name)
        # One set/clear position batch per shard, built in numpy: each
        # value contributes its magnitude bit per plane, an exists bit,
        # and a sign bit (reference fragment.importValue,
        # fragment.go:2186 — there per-bit, here [n, depth] at once).
        from pilosa_tpu.ops.bitmap import group_indices

        off = cols_np % SHARD_WIDTH
        planes = np.arange(depth, dtype=np.int64)
        done: set[int] = set()
        try:
            for shard, sel in group_indices(cols_np // SHARD_WIDTH).items():
                offs = off[sel]
                bits = (uv[sel][:, None] >> planes[None, :]) & 1
                pos = ((bsi_ops.OFFSET_PLANE + planes)[None, :]
                       * SHARD_WIDTH + offs[:, None])
                neg = bv[sel] < 0
                sets = np.concatenate([
                    pos[bits == 1],
                    bsi_ops.EXISTS_PLANE * SHARD_WIDTH + offs,
                    bsi_ops.SIGN_PLANE * SHARD_WIDTH + offs[neg],
                ])
                clears = np.concatenate([
                    pos[bits == 0],
                    bsi_ops.SIGN_PLANE * SHARD_WIDTH + offs[~neg],
                ])
                frag = view.create_fragment_if_not_exists(int(shard))
                frag.import_positions(sets, clears)
                done.add(int(shard))
        finally:
            self._note_shards(done)
        self._prewarm(())  # int field: warms the BSI plane stack

    def _prewarm(self, rows) -> None:
        """Enqueue a background stack prewarm for this field (no-op
        without an owning index or with PILOSA_TPU_PREWARM=0)."""
        idx = self._index_ref() if self._index_ref is not None else None
        if idx is not None:
            from pilosa_tpu.runtime import prewarm

            prewarm.enqueue(idx, self, rows)

    # ---------------------------------------------------------- lifecycle

    def close(self) -> None:

        for view in self.views.values():
            view.close()
        self.row_attrs.close()
        if self._translate_store is not None:
            self._translate_store.close()
        # release device residency accounting for the field-level stack
        # caches (the manager holds strong refs to these dicts; without
        # this a deleted field's tensors stay budgeted until pressure
        # happens to evict them), mirroring Fragment.close
        mgr = residency.manager()
        with self._lock:
            for cache in (self._row_stack_cache, self._matrix_stack_cache):
                for k in list(cache):
                    mgr.forget(cache, k)
                cache.clear()

    def snapshot(self) -> None:
        for view in self.views.values():
            view.snapshot()
