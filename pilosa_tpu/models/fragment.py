"""Fragment: the storage workhorse — one (field, view, shard) bit matrix.

Parity target: the reference's fragment (fragment.go:100), redesigned for
TPU residency.  The reference keeps a mmap'd roaring file updated in place
with an embedded op log; here the design inverts the layout:

- **Host truth**: a dict of rowID -> dense uint32-packed words (numpy).
  Mutations apply here first, appended to a sidecar WAL for durability
  (same recovery semantics as the reference's in-file op log,
  fragment.go:454, roaring/roaring.go:1612).
- **Device residency**: dense [rows, words] uint32 tensors cached in HBM,
  invalidated by a generation counter and re-uploaded lazily — queries
  then slice HBM directly, so steady-state reads do zero host<->device
  transfers.  This mirrors the reference's own batching of mutations
  (opN -> snapshot, fragment.go:84): we batch mutations onto the device.
- **Snapshot**: when the WAL exceeds max_op_n (default 10000, matching
  defaultFragmentMaxOpN fragment.go:84) the matrix is rewritten as one
  atomic snapshot file and the WAL truncated (fragment.go:2296-2345).

BSI fields store bit planes as rows 0..depth+1 of the same matrix
(fragment.go:91-93) and aggregate/compare through pilosa_tpu.ops.bsi.
"""

from __future__ import annotations

import itertools
import os
import struct
import threading
import time

import numpy as np

from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.ops import bsi as bsi_ops
from pilosa_tpu.runtime import filebudget
from pilosa_tpu.shardwidth import SHARD_WIDTH

DEFAULT_MAX_OP_N = 10000
HASH_BLOCK_SIZE = 100  # rows per anti-entropy block (fragment.go:80)

# ---------------------------------------------------------------- wal.*
# Module-level WAL health counters (published as gauges at scrape
# time).  A torn/corrupt WAL tail is EXPECTED after a crash window —
# replay stops at the tear by design — but it must be visible:
# operators deciding whether a crash lost acknowledged records need
# the count and the log line, not a silent `break`.

from pilosa_tpu import lockcheck as _lockcheck  # noqa: E402

_wal_counter_lock = _lockcheck.lock("wal-counters")
_counters = {
    "wal.torn_records": 0,  # torn/corrupt tails ignored at replay
}


def _note_torn_wal(path: str, offset: int, trailing: int) -> None:
    import logging

    with _wal_counter_lock:
        _counters["wal.torn_records"] += 1
    logging.getLogger("pilosa_tpu.fragment").warning(
        "torn WAL tail in %s at byte %d (%d trailing bytes ignored; "
        "a crash window may have lost acknowledged tail records)",
        path, offset, trailing)


def wal_counters() -> dict:
    with _wal_counter_lock:
        return dict(_counters)


def publish_wal_gauges(stats) -> None:
    """wal.* gauge family for /metrics and /debug/vars — published
    unconditionally (zeros on a healthy server)."""
    for name, v in wal_counters().items():
        stats.gauge(name, v)

_SNAP_MAGIC = b"PTSF"
_SNAP_VERSION = 1
_SNAP_HEADER = struct.Struct("<4sIIQ")  # magic, version, width_exp, n_rows
_WAL_SET = 1
_WAL_CLEAR = 2
_WAL_BULK = 3
_WAL_ROARING = 4
_WAL_REC = struct.Struct("<BQQ")  # op, row, col-offset
_WAL_BULK_HDR = struct.Struct("<BQQ")  # op, n_set, n_clear
_WAL_ROARING_HDR = struct.Struct("<BQQ")  # op, blob_len, clear-flag


def _plane_promote(gen: int):
    """Tier-promotion closure for one generation of a fragment's BSI
    plane stack: host planes -> placed owner-cache entry (the
    runtime/residency host-tier contract)."""

    def promote(P: np.ndarray):
        dev = (P if bm.host_mode()
               else bm.device_put(P, label="fragment.planes"))
        return (gen, dev)

    return promote


class Fragment:
    """One shard of one view of one field."""

    _UID = itertools.count(1)

    def __init__(
        self,
        path: str | None,
        index: str,
        field: str,
        view: str,
        shard: int,
        mutex: bool = False,
        max_op_n: int = DEFAULT_MAX_OP_N,
        cache_type: str = "ranked",
        cache_size: int = 50000,
    ):
        self.path = path
        self.index = index
        self.field = field
        self.view = view
        self.shard = shard
        self.mutex = mutex
        self.max_op_n = max_op_n

        self.width = SHARD_WIDTH
        self.n_words = bm.n_words(SHARD_WIDTH)

        self._rows: dict[int, np.ndarray] = {}
        self._gen = 0
        # streaming-ingest delta plane (pilosa_tpu.ingest): pending
        # set/clear overlays that batched imports and set/clear_bit
        # land in WITHOUT bumping _gen, so device residency of the
        # base stays warm under sustained writes.  _delta_seq is the
        # monotone delta sequence the result-cache stamps carry: it
        # bumps on every delta-landing write and is NEVER reset —
        # compaction (flush_delta) merges the plane into _rows and
        # bumps _gen instead.
        self._delta = None  # ingest.deltaplane.DeltaPlane | None
        self._delta_seq = 0
        # the View whose map holds this fragment (bound by the map,
        # models/view.py): every change to _gen, _delta_seq or _delta
        # also changes that view's write token (_written)
        self._owner = None
        # process-unique identity for cache keys: a fragment deleted
        # (resize cleanup) and later re-fetched is a NEW object whose
        # _gen can collide with a stale cached tuple — uid makes a
        # false cache hit impossible (found by the resize soak leg)
        self._uid = next(Fragment._UID)
        self._closed = False
        self._snapshotting = False
        self._stack_cache: tuple[int, np.ndarray, np.ndarray] | None = None
        self._device_cache: dict = {}
        # compressed container directories (ops/containers.py): row ->
        # (gen, keys, blocks, bits); gen-stamped like _stack_cache, so
        # every mutation path invalidates by bumping _gen — no new
        # invalidation machinery, and delta-landing writes (which bump
        # _delta_seq only) leave the BASE directory warm by design
        self._container_cache: dict = {}
        # anti-entropy digest cache (parallel/syncer.py): (gen, blocks)
        # — gen-stamped like the caches above, so an unchanged fragment
        # costs ZERO checksum work per AE round and any mutation
        # invalidates by bumping _gen
        self._blocks_cache: tuple[int, list] | None = None
        from pilosa_tpu import lockcheck

        self._lock = lockcheck.rlock("fragment")
        self._snap_done = threading.Condition(self._lock)

        from pilosa_tpu.models.cache import TopNCache

        self.topn_cache = TopNCache(cache_type, cache_size)

        self._wal = None
        self._op_n = 0
        if path is not None:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            self._load()
            # budgeted: the process-wide fd cap may transparently close
            # and reopen this between appends (reference syswrap
            # OpenFile cap, syswrap/os.go:41) — ~9.5k open fragments at
            # the 10B scale must not blow ulimit -n
            self._wal = filebudget.open_append(self._wal_path)
            # A persisted .cache is exact only for a WAL-clean reopen
            # (fragment.go:2403 .cache files).
            if self._op_n == 0:
                self.topn_cache.load(self._cache_path, self._gen)

    # ------------------------------------------------------------------ io

    @property
    def _snap_path(self) -> str:
        return self.path + ".snap"

    @property
    def _wal_path(self) -> str:
        return self.path + ".wal"

    @property
    def _cache_path(self) -> str:
        return self.path + ".cache"

    @property
    def _wal_new_path(self) -> str:
        """Overflow WAL segment: writes land here while a background
        snapshot's file I/O runs outside the fragment lock; the segment
        is renamed over the truncated WAL when the snapshot commits."""
        return self.path + ".wal.new"

    def _load(self) -> None:
        if os.path.exists(self._snap_path):
            with open(self._snap_path, "rb") as f:
                magic, version, width_exp, n_rows = _SNAP_HEADER.unpack(
                    f.read(_SNAP_HEADER.size)
                )
                if magic != _SNAP_MAGIC or version != _SNAP_VERSION:
                    raise ValueError(f"bad fragment snapshot {self._snap_path}")
                if (1 << width_exp) != self.width:
                    raise ValueError(
                        f"fragment {self._snap_path} written with shard width "
                        f"2^{width_exp}, current width is {self.width}"
                    )
                row_ids = np.frombuffer(f.read(8 * n_rows), dtype=np.int64)
                need = (_SNAP_HEADER.size + 8 * n_rows
                        + 4 * self.n_words * n_rows)
                if os.path.getsize(self._snap_path) < need:
                    raise ValueError(
                        f"truncated fragment snapshot {self._snap_path}")
                # Eager read, deliberately NOT a lazy memmap: measured
                # at the 10B shape (9,537 fragments, 2.5 GB), CoW maps
                # saved only ~0.6 s of open (decode is cheap) while
                # adding a ~2.5 s first-pass fault tail and a pathological
                # open-vs-prewarm interleaving on one core.  The restart
                # tail is owned by prewarm (runtime/prewarm.py), not the
                # loader.
                data = np.frombuffer(
                    f.read(4 * self.n_words * n_rows), dtype=np.uint32
                ).reshape(n_rows, self.n_words)
                for rid, words in zip(row_ids, data):
                    self._rows[int(rid)] = words.copy()
        self._replay_wal()
        # Heal a crash mid-snapshot: fold the overflow segment into the
        # main WAL so the single-file invariant holds again.  Replaying
        # the old WAL against a snapshot that already incorporates it is
        # safe — set/clear replay is last-writer-wins per position.
        if os.path.exists(self._wal_new_path):
            with open(self._wal_path, "ab") as w, \
                    open(self._wal_new_path, "rb") as nf:
                w.write(nf.read())
            os.remove(self._wal_new_path)

    def _replay_wal(self) -> None:
        for path in (self._wal_path, self._wal_new_path):
            if os.path.exists(path):
                self._replay_wal_file(path)
        self._bump_gen()

    def _replay_wal_file(self, path: str) -> None:
        with open(path, "rb") as f:
            buf = f.read()
        off, n = 0, len(buf)
        torn_at = None  # byte offset of the first torn/corrupt record
        while off + _WAL_REC.size <= n:
            rec_start = off
            op, a, b = _WAL_REC.unpack_from(buf, off)
            off += _WAL_REC.size
            if op == _WAL_SET:
                self._apply_set(a, b)
                self._op_n += 1
            elif op == _WAL_CLEAR:
                self._apply_clear(a, b)
                self._op_n += 1
            elif op == _WAL_BULK:
                n_set, n_clear = a, b
                need = 8 * (n_set + n_clear)
                if off + need > n:
                    # torn bulk record: crash mid-append; ignore tail
                    torn_at = rec_start
                    break
                sets = np.frombuffer(buf, dtype=np.uint64, count=n_set, offset=off)
                off += 8 * n_set
                clears = np.frombuffer(buf, dtype=np.uint64, count=n_clear, offset=off)
                off += 8 * n_clear
                self._apply_bulk(sets.astype(np.int64), clears.astype(np.int64))
                self._op_n += n_set + n_clear
            elif op == _WAL_ROARING:
                blob_len, clear_flag = a, b
                if off + blob_len > n:
                    # torn roaring record: crash mid-append
                    torn_at = rec_start
                    break
                blob = bytes(buf[off:off + blob_len])
                off += blob_len
                try:
                    # re-merge is idempotent and replays IN ORDER, so
                    # re-applying a record the snapshot already holds
                    # reaches the same end state (last-writer-wins per
                    # position, like set/clear replay)
                    self._op_n += self._merge_roaring(
                        blob, clear=bool(clear_flag))
                except Exception:  # noqa: BLE001 — corrupt blob: stop
                    torn_at = rec_start  # like any torn/corrupt tail
                    break
            else:
                # corrupt/torn record; ignore tail (same as op-log
                # replay stop)
                torn_at = rec_start
                break
        if torn_at is None and off != n:
            torn_at = off  # partial header at the tail
        if torn_at is not None:
            _note_torn_wal(path, torn_at, n - torn_at)

    def _wal_append(self, data: bytes) -> None:
        if self._wal is not None:
            self._wal.write(data)  # BudgetedAppendFile flushes per write

    def snapshot(self) -> None:
        """Atomically persist the full matrix and truncate the WAL
        (reference protectedSnapshot, fragment.go:2325).

        Two-phase so writers only block for the in-memory matrix copy,
        never the file I/O + fsync: phase 1 (under the lock) copies the
        matrix and redirects the WAL handle to an overflow segment;
        phase 2 (lock released) writes + fsyncs the snapshot; phase 3
        (under the lock) renames the overflow segment over the old WAL
        — the open handle follows the inode, so concurrent appends are
        seamless.  Every crash window replays losslessly: the old WAL
        is incorporated into the snapshot (re-replaying it is
        last-writer-wins idempotent) and `_load` folds a leftover
        overflow segment back into the WAL."""
        with self._lock:
            if self.path is None or self._closed or self._snapshotting:
                return
            self._snapshotting = True
            old_wal = self._wal
            try:
                row_ids, matrix = self._stacked()
                matrix = np.ascontiguousarray(matrix)
                gen = self._gen
                ops_at_swap = self._op_n
                if old_wal is not None:
                    old_wal.close()
                self._wal = filebudget.open_append(self._wal_new_path,
                                                   truncate=True)
            except BaseException:
                # phase-1 failure (ENOSPC/EMFILE/MemoryError) must not
                # wedge the fragment: restore an appendable WAL handle
                # and clear the in-progress flag
                try:
                    if self._wal is not None and self._wal is not old_wal:
                        # the new-path handle was already swapped in
                        # (e.g. a signal landed after the assignment):
                        # close it, or its FileBudget registration
                        # strands an fd for the process lifetime
                        self._wal.close()
                    if old_wal is not None:
                        # idempotent; without it an early raise (e.g.
                        # MemoryError in _stacked) would strand the old
                        # handle registered in the fd budget forever
                        old_wal.close()
                    self._wal = filebudget.open_append(self._wal_path)
                except OSError:
                    # reopen failed too — keep the CLOSED old handle so
                    # the next write fails LOUDLY (ValueError) instead
                    # of being acknowledged without a WAL record
                    self._wal = old_wal
                self._snapshotting = False
                self._snap_done.notify_all()
                raise
        ok = False
        try:
            tmp = self._snap_path + ".tmp"
            width_exp = self.width.bit_length() - 1
            with open(tmp, "wb") as f:
                f.write(_SNAP_HEADER.pack(
                    _SNAP_MAGIC, _SNAP_VERSION, width_exp, len(row_ids)))
                f.write(row_ids.astype(np.int64).tobytes())
                f.write(matrix.tobytes())
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self._snap_path)
            ok = True
        finally:
            with self._lock:
                if ok:
                    # commit the overflow segment as the new WAL (the
                    # snapshot incorporated everything before it).
                    # rename_to keeps the budgeted handle's reopen path
                    # in lockstep with the rename — an eviction/reopen
                    # straddling a bare os.replace would resurrect the
                    # old path and strand acked records there
                    if self._wal is not None:
                        self._wal.rename_to(self._wal_path)
                    else:
                        # close() ran during phase 2: only the rename
                        # remains (no live handle to retarget)
                        os.replace(self._wal_new_path, self._wal_path)
                    self._op_n -= ops_at_swap
                    if not self._closed:
                        self.topn_cache.save(self._cache_path, gen)
                else:
                    # snapshot failed: the old WAL is still the only
                    # durable copy of its ops — fold the overflow
                    # segment back into it and resume appending there
                    if self._wal is not None:
                        self._wal.close()
                    with open(self._wal_path, "ab") as w, \
                            open(self._wal_new_path, "rb") as nf:
                        w.write(nf.read())
                    os.remove(self._wal_new_path)
                    if not self._closed:
                        self._wal = filebudget.open_append(self._wal_path)
                self._snapshotting = False
                self._snap_done.notify_all()

    def close(self) -> None:
        from pilosa_tpu.runtime import residency

        with self._lock:
            # Wait out an in-flight snapshot (bounded): its phase 3
            # renames .wal.new over the WAL, and proceeding past it
            # lets holder.close release the dir flock while that rename
            # is pending — a reopening process could heal/remove the
            # overflow segment under the worker's feet.  The bound
            # keeps a hung disk from wedging close; past it we accept
            # the (recoverable — WAL replay is idempotent) race rather
            # than never closing.
            deadline = time.monotonic() + 60.0
            while self._snapshotting and time.monotonic() < deadline:
                self._snap_done.wait(timeout=deadline - time.monotonic())
            self._closed = True  # a queued background snapshot becomes a no-op
            if self._wal is not None:
                self._wal.close()
                self._wal = None
            # pending delta bits are WAL-durable (replayed into base on
            # reopen); just drop the compactor registration
            if self._delta is not None:
                from pilosa_tpu.ingest import compactor

                compactor.compactor().forget(self)
                self._delta = None
                self._written()
            # release device residency accounting (drops the cache refs;
            # the jax buffers free once no computation holds them)
            mgr = residency.manager()
            for k in list(self._device_cache):
                mgr.forget(self._device_cache, k)
            self._device_cache.clear()

    def check(self) -> None:
        """Invariant validator (reference roaring.Bitmap.Check,
        roaring/roaring.go:1664): raises ValueError on the first
        violated structural invariant.  Run by ``pilosa-tpu check``,
        the paranoia gate after mutations, and tests."""
        with self._lock:
            for rid, arr in self._rows.items():
                if not isinstance(rid, int) or rid < 0:
                    raise ValueError(f"invalid row id {rid!r}")
                if not isinstance(arr, np.ndarray):
                    raise ValueError(f"row {rid}: not an ndarray")
                if arr.dtype != np.uint32:
                    raise ValueError(f"row {rid}: dtype {arr.dtype}")
                if arr.shape != (self.n_words,):
                    raise ValueError(
                        f"row {rid}: shape {arr.shape} != ({self.n_words},)")
            if self._delta is not None:
                self._delta.check()
            if self._stack_cache is not None:
                gen, ids, matrix = self._stack_cache
                if gen == self._gen:
                    # BASE row ids (not row_ids(), which overlays the
                    # pending delta): the stack cache is stamped with
                    # the base generation and holds base content; rows
                    # cleared to all-zero stay in _rows but are
                    # excluded from the stack, by design
                    base_ids = [r for r, a in self._rows.items()
                                if a.any()]
                    if len(ids) != len(base_ids):
                        raise ValueError(
                            "stack cache row count diverged from rows")
                    if not np.all(ids[:-1] < ids[1:]):
                        raise ValueError("stack cache ids not sorted")
            if self._op_n < 0:
                raise ValueError(f"negative op count {self._op_n}")
            if self.path is not None and not self._closed:
                if self._wal is None and not self._snapshotting:
                    raise ValueError("open durable fragment without a WAL")

    #: process-wide paranoia gate (reference build-tag paranoia checks,
    #: roaring/roaring_paranoia.go): when PILOSA_TPU_PARANOIA=1, every
    #: mutation re-validates invariants before returning
    PARANOIA = os.environ.get("PILOSA_TPU_PARANOIA", "") == "1"

    def _paranoia_check(self) -> None:
        if Fragment.PARANOIA:
            self.check()

    def _maybe_snapshot(self) -> None:
        """Past the opN threshold, queue a background compaction — the
        writing thread never stalls on it (reference holder.go:163
        snapshot queue; was inline here until round 2).  Durability is
        WAL-carried either way."""
        if self.path is not None and self._op_n > self.max_op_n:
            from pilosa_tpu.runtime import snapqueue

            snapqueue.enqueue(self)

    # -------------------------------------------------------- write tokens

    def _written(self) -> None:
        """Change the owning view's write token (stagecheck.py).
        Every event that changes what a per-fragment cache token reads
        (``_gen``, ``_delta_seq``, a row's ``row_seq`` in the delta
        plane, ``_delta`` itself) ends here, AFTER that state is final
        and before the write returns to its caller: a cached stack
        stamped with the old token is then never taken for current
        without the per-fragment comparison."""
        owner = self._owner
        if owner is not None:
            owner.note_write()

    def _bump_gen(self) -> None:
        """Base content changed (or a compaction merged the delta in):
        the one place ``_gen`` moves."""
        self._gen += 1
        self._written()

    def _bump_delta_seq(self) -> None:
        """A write is landing in the delta plane: the one place
        ``_delta_seq`` moves.  The plane's own ``row_seq`` moves after
        this, so ``_delta_after_write_locked`` changes the view's
        token once more when the plane holds the write."""
        self._delta_seq += 1
        self._written()

    # ------------------------------------------------ streaming delta plane

    #: BSI views (bsig_<field>) never take the delta path: their reads
    #: go through plane stacks and per-plane arithmetic that would all
    #: need fusing.  Literal mirrors models.view.VIEW_BSI_PREFIX
    #: (importing view here would cycle).
    _BSI_VIEW_PREFIX = "bsig_"

    def _delta_eligible(self) -> bool:
        """Whether writes land in the delta plane: [ingest] deltas on,
        and this fragment's semantics are overlay-safe — mutex/bool
        fragments mutate OTHER rows on set (cross-row read-modify-
        write) and BSI views read whole plane stacks, so both stay on
        the base path."""
        from pilosa_tpu import ingest

        return (ingest.config().delta_enabled and not self.mutex
                and not self.view.startswith(self._BSI_VIEW_PREFIX))

    def _delta_or_new(self):
        d = self._delta
        if d is None:
            from pilosa_tpu.ingest.deltaplane import DeltaPlane

            d = self._delta = DeltaPlane(self.n_words, self.width)
        return d

    def _delta_after_write_locked(self) -> None:
        """Post-delta-write bookkeeping (caller holds the lock):
        register with the compactor; past the process-wide pending
        budget the WRITER merges its own fragment inline (bounded
        memory — backpressure lands on the writer, never on readers)."""
        from pilosa_tpu.ingest import compactor

        self._written()  # row_seq moved after _bump_delta_seq
        if compactor.compactor().note_delta(self):
            self._flush_delta_locked(inline=True)

    def _delta_row_seq(self, row: int) -> int:
        """Per-row delta token (0 = no pending overlay): the executor's
        delta-stack caches key on (uid, row_seq) so writes to OTHER
        rows never invalidate a cached delta stack."""
        d = self._delta
        return 0 if d is None else d.row_seq.get(row, 0)

    def delta_stats(self) -> dict | None:
        with self._lock:
            d = self._delta
            if d is None or d.empty():
                return None
            return d.stats()

    def flush_delta(self) -> int:
        """Merge the pending delta plane into the base rows (the
        compaction step): bumps ``_gen`` (device residency of this
        fragment refreshes once), leaves ``_delta_seq`` alone (the
        effective content did not change, so result-cache entries
        stamped (gen, seq) miss exactly once and refill from the
        freshly-merged base).  No WAL append — the delta's records
        were written at delta-write time and replay idempotently.
        Returns the number of pending bit positions merged."""
        with self._lock:
            return self._flush_delta_locked()

    def _flush_delta_locked(self, inline: bool = False) -> int:
        d = self._delta
        if d is None or d.empty():
            if d is not None:
                self._delta = None
                self._written()
            return 0
        # sets first, clears second — matching _apply_bulk's order so a
        # position present in both planes (impossible by the disjoint
        # invariant, but belt-and-braces) resolves the same way
        for row, words in d.sets.items():
            arr = self._row_array(row, create=True)
            np.bitwise_or(arr, words, out=arr)
        for row, words in d.clears.items():
            arr = self._rows.get(row)
            if arr is not None:
                np.bitwise_and(arr, ~words, out=arr)
        bits = d.bits
        self._delta = None
        self._bump_gen()
        from pilosa_tpu.ingest import compactor

        compactor.compactor().note_flushed(self, bits, inline=inline)
        # flight-record annotation: a read-triggered merge inside a
        # query shows up as compacted=true on its record
        from pilosa_tpu import observe

        rec = observe.current()
        if rec is not None:
            rec.compacted = True
        return bits

    def _bit_off_locked(self, row: int, off: int) -> bool:
        """Effective bit (base ⊕ delta) at one offset; caller holds
        the lock (or accepts the same torn-read semantics bit() always
        had)."""
        d = self._delta
        if d is not None:
            ov = d.override(row, off)
            if ov is not None:
                return ov
        arr = self._rows.get(row)
        if arr is None:
            return False
        return bool(arr[off // bm.WORD_BITS]
                    & (np.uint32(1) << np.uint32(off % bm.WORD_BITS)))

    def _delta_set_bit(self, row: int, off: int, clear: bool) -> bool:
        """Single-bit write landing in the delta plane (caller holds
        the lock).  WAL record + op count identical to the base path;
        only the in-memory landing zone differs."""
        cur = self._bit_off_locked(row, off)
        if cur == (not clear):
            return False  # no-op write: no WAL, no seq bump, caches warm
        self._wal_append(_WAL_REC.pack(
            _WAL_CLEAR if clear else _WAL_SET, row, off))
        self._op_n += 1
        self._bump_delta_seq()
        self._delta_or_new().add_bit(row, off, clear, self._delta_seq)
        self._delta_after_write_locked()
        self._maybe_snapshot()
        self._paranoia_check()
        return True

    # ------------------------------------------------------- host mutation

    def _row_array(self, row: int, create: bool = False) -> np.ndarray | None:
        arr = self._rows.get(row)
        if arr is None and create:
            arr = np.zeros(self.n_words, dtype=np.uint32)
            self._rows[row] = arr
        return arr

    def _apply_set(self, row: int, off: int) -> bool:
        arr = self._row_array(row, create=True)
        w, b = off // bm.WORD_BITS, np.uint32(1) << np.uint32(off % bm.WORD_BITS)
        changed = not (arr[w] & b)
        arr[w] |= b
        return changed

    def _apply_clear(self, row: int, off: int) -> bool:
        arr = self._rows.get(row)
        if arr is None:
            return False
        w, b = off // bm.WORD_BITS, np.uint32(1) << np.uint32(off % bm.WORD_BITS)
        changed = bool(arr[w] & b)
        arr[w] &= ~b
        return changed

    def _apply_bulk(self, set_pos: np.ndarray, clear_pos: np.ndarray) -> None:
        """Apply absolute fragment positions (pos = row*width + off) in
        O(set bits): the same position-space merge import-roaring uses
        (native pt_merge_positions when available).  Replaces a per-row
        dense pack that allocated two [n_words] buffers per touched
        row — the top cost in the keyed-ingest profile at many rows
        per batch (round 5)."""
        if len(set_pos):
            self._merge_positions(set_pos, False)
        if len(clear_pos):
            self._merge_positions(clear_pos, True)

    def _offset(self, col: int) -> int:
        off = col - self.shard * self.width
        if not (0 <= off < self.width):
            raise ValueError(f"column {col} out of shard {self.shard} bounds")
        return off

    def set_bit(self, row: int, col: int) -> bool:
        """Set one bit; enforces mutex semantics when the owning field is a
        mutex/bool field (reference handleMutex, fragment.go:670,3096)."""
        with self._lock:
            off = self._offset(col)
            if self._delta_eligible():
                return self._delta_set_bit(row, off, clear=False)
            # base path: pending delta merges FIRST so in-memory apply
            # order matches WAL order (a delta write followed by a base
            # write must not resurrect later)
            self._flush_delta_locked()
            changed = False
            if self.mutex:
                for other_id, arr in self._rows.items():
                    if other_id == row:
                        continue
                    w, b = off // bm.WORD_BITS, np.uint32(1) << np.uint32(off % bm.WORD_BITS)
                    if arr[w] & b:
                        arr[w] &= ~b
                        self._wal_append(_WAL_REC.pack(_WAL_CLEAR, other_id, off))
                        self._op_n += 1
                        changed = True
            if self._apply_set(row, off):
                changed = True
                self._wal_append(_WAL_REC.pack(_WAL_SET, row, off))
                self._op_n += 1
            if changed:
                self._bump_gen()
            self._maybe_snapshot()
            self._paranoia_check()
            return changed

    def clear_bit(self, row: int, col: int) -> bool:
        with self._lock:
            off = self._offset(col)
            if self._delta_eligible():
                return self._delta_set_bit(row, off, clear=True)
            self._flush_delta_locked()
            if self._apply_clear(row, off):
                self._wal_append(_WAL_REC.pack(_WAL_CLEAR, row, off))
                self._op_n += 1
                self._bump_gen()
                self._maybe_snapshot()
                self._paranoia_check()
                return True
            return False

    def clear_row(self, row: int) -> bool:
        """Remove all bits in a row (ClearRow support, fragment clearRow)."""
        with self._lock:
            # whole-row base mutation: merge any pending delta first so
            # the pop sees (and the WAL order preserves) the effective
            # row — an unflushed delta would resurrect its bits later
            self._flush_delta_locked()
            arr = self._rows.pop(row, None)
            if arr is None or not arr.any():
                return False
            offs = bm.unpack_positions(arr)
            pos = (row * self.width + offs).astype(np.uint64)
            self._wal_append(
                _WAL_BULK_HDR.pack(_WAL_BULK, 0, len(pos)) + pos.tobytes()
            )
            self._op_n += len(pos)
            self._bump_gen()
            self._maybe_snapshot()
            self._paranoia_check()
            return True

    def set_row(self, row: int, words: np.ndarray) -> bool:
        """Replace a row wholesale (Store() support, fragment setRow)."""
        with self._lock:
            self._flush_delta_locked()  # base ordering (see clear_row)
            old = self._rows.get(row)
            new = np.asarray(words, dtype=np.uint32).copy()
            if old is None and not new.any():
                return False  # absent -> empty is a no-op
            if old is not None and np.array_equal(old, new):
                return False
            self._rows[row] = new
            sets = (row * self.width + bm.unpack_positions(new)).astype(np.uint64)
            clears = np.empty(0, dtype=np.uint64)
            if old is not None:
                gone = old & ~new
                clears = (row * self.width + bm.unpack_positions(gone)).astype(np.uint64)
            self._wal_append(
                _WAL_BULK_HDR.pack(_WAL_BULK, len(sets), len(clears))
                + sets.tobytes() + clears.tobytes()
            )
            self._op_n += len(sets) + len(clears)
            self._bump_gen()
            self._maybe_snapshot()
            self._paranoia_check()
            return True

    def import_positions(self, set_pos, clear_pos=()) -> None:
        """Bulk import of absolute fragment positions (pos = row*width+off);
        the fast ingest path (reference importPositions, fragment.go:2053)."""
        with self._lock:
            # np.unique = sort + dedup in one pass, ~10x Python
            # sorted() at bulk sizes, and accepts the ndarray chunks
            # field.import_bits now passes; dedup keeps the WAL bulk
            # record and _op_n proportional to unique bits on
            # duplicate-heavy ingest feeds
            sets = np.unique(np.asarray(set_pos, dtype=np.uint64))
            clears = np.unique(np.asarray(clear_pos, dtype=np.uint64))
            if len(sets) == 0 and len(clears) == 0:
                # empty import: a strict no-op — no WAL record, no
                # _gen/_delta_seq bump, no cache eviction (regression-
                # pinned in tests/test_ingest.py)
                return
            if self._delta_eligible():
                # streaming path: same WAL record, same op count; bits
                # land in the delta plane so _gen (and the device-
                # resident base) stays put until compaction
                self._wal_append(
                    _WAL_BULK_HDR.pack(_WAL_BULK, len(sets), len(clears))
                    + sets.tobytes() + clears.tobytes()
                )
                self._op_n += len(sets) + len(clears)
                self._bump_delta_seq()
                d = self._delta_or_new()
                d.add_positions(sets, False, self._delta_seq)
                d.add_positions(clears, True, self._delta_seq)
                self._delta_after_write_locked()
                self._maybe_snapshot()
                self._paranoia_check()
                return
            self._flush_delta_locked()
            self._apply_bulk(sets.astype(np.int64), clears.astype(np.int64))
            self._wal_append(
                _WAL_BULK_HDR.pack(_WAL_BULK, len(sets), len(clears))
                + sets.tobytes() + clears.tobytes()
            )
            self._op_n += len(sets) + len(clears)
            self._bump_gen()
            self._maybe_snapshot()
            self._paranoia_check()

    # ------------------------------------------------- roaring interchange

    def import_roaring(self, data: bytes, clear: bool = False) -> None:
        """Bulk-merge a serialized roaring bitmap in fragment position
        space (pos = row*width + off) — the fastest ingest path
        (reference fragment.importRoaring, fragment.go:2255, via
        roaring.ImportRoaringBits).  Durability: the WHOLE payload
        appends to the WAL as one roaring record (replay re-merges it;
        idempotent and in-order, so recovery is exact) — logging the
        blob instead of extracted per-bit deltas keeps the hot path
        free of bit-position expansion AND writes ~15x less WAL than
        8-byte-per-bit delta records at typical densities."""
        with self._lock:
            if not data:
                # empty payload: a strict no-op, not a decode error —
                # bulk loaders ship empty view shells routinely
                return
            if self._delta_eligible():
                pos = self._delta_roaring_positions(data)
                if pos is not None:
                    if len(pos) == 0:
                        return  # empty-but-valid payload: no-op
                    self._wal_append(
                        _WAL_ROARING_HDR.pack(_WAL_ROARING, len(data),
                                              1 if clear else 0) + data)
                    self._op_n += len(pos)
                    self._bump_delta_seq()
                    self._delta_or_new().add_positions(
                        pos, clear, self._delta_seq)
                    self._delta_after_write_locked()
                    self._maybe_snapshot()
                    self._paranoia_check()
                    return
            self._flush_delta_locked()
            changed = self._merge_roaring(data, clear)
            if changed:
                self._wal_append(
                    _WAL_ROARING_HDR.pack(_WAL_ROARING, len(data),
                                          1 if clear else 0) + data)
                self._op_n += changed
                self._bump_gen()
                self._maybe_snapshot()
            self._paranoia_check()

    #: positions path iff avg set bits/container is below this — the
    #: dense merge costs ~1024 word-ops (~3 passes over 8 KB) per
    #: container regardless of cardinality, the positions merge ~1
    #: word-op per bit, so the true crossover is near 1024; 512 leaves
    #: margin for the positions path's extra decode copy
    _SPARSE_BITS_PER_CONTAINER = 512
    #: absolute positions-path ceiling (u64 positions materialized)
    _SPARSE_MAX_BITS = 1 << 25

    #: roaring payloads above this many bits skip the delta plane and
    #: merge dense into the base directly (a delta that large would be
    #: flushed immediately anyway — routing it through the overlay
    #: would just double the work)
    _DELTA_MAX_ROARING_BITS = 1 << 22

    def _delta_roaring_positions(self, data: bytes):
        """Decode a roaring payload to absolute bit positions for the
        delta plane, or None when the payload is too dense/large (or
        malformed in a way the dense path owns reporting for)."""
        from pilosa_tpu.storage import roaring as rcodec

        stats = rcodec.payload_stats(data)
        if stats is None:
            return None
        _n_cont, n_bits = stats
        if n_bits > self._DELTA_MAX_ROARING_BITS:
            return None
        try:
            return rcodec.decode_positions(
                data, max_positions=2 * self._DELTA_MAX_ROARING_BITS)
        except rcodec.RoaringError:
            return None

    def _merge_roaring(self, data: bytes, clear: bool) -> int:
        """In-memory merge of a roaring payload; returns the number of
        bits actually flipped.  Caller holds the lock (or is _load
        replay, which is single-threaded).

        Two regimes, chosen from the payload's descriptive headers
        alone (cost ∝ container count, no expansion):

        - **sparse** (avg bits/container below _SPARSE_BITS_PER_
          CONTAINER): decode straight to bit positions and merge in
          position space — O(set bits), never touching the ~8 KB dense
          block per container.  This is the analog of the reference's
          streamed ImportRoaringBits (roaring/roaring.go:1511), whose
          cost also tracks bits, not container footprint.
        - **dense**: containers arrive sorted by key, so each row is
          one contiguous run — every container's current words gather
          into ONE matrix, the diff is one op, and the changed-bit
          count is a popcount reduce; no per-container Python loop.
          Chunked so a dense whole-fragment archive never materializes
          more than ~3x 64 MB of temporaries."""
        from pilosa_tpu.storage import roaring as rcodec

        stats = rcodec.payload_stats(data)
        if stats is not None:
            n_cont, n_bits = stats
            if (n_cont > 0 and n_bits <= self._SPARSE_MAX_BITS
                    and n_bits <= n_cont * self._SPARSE_BITS_PER_CONTAINER):
                try:
                    pos = rcodec.decode_positions(
                        data, max_positions=2 * self._SPARSE_MAX_BITS)
                except rcodec.RoaringError:
                    # descriptor cardinalities are untrusted: a payload
                    # whose runs expand past the cap (or any decode
                    # fault) falls through to the dense path, which is
                    # chunk-bounded and owns the error reporting
                    pass
                else:
                    return self._merge_positions(pos, clear)

        keys, cwords, _flags = rcodec.decode(data)
        cpr = self.width // rcodec.CONTAINER_BITS  # containers per row
        wpc = rcodec.WORDS_PER_CONTAINER
        # drop empty containers up front (the set path must not
        # materialize rows for them; decode may emit them)
        if len(keys):
            keep = cwords.any(axis=1)
            if not keep.all():
                keys, cwords = keys[keep], cwords[keep]
        changed = 0
        keys_i = keys.astype(np.int64)
        # the batched merge requires sorted, UNIQUE keys (rows must be
        # contiguous runs and the per-row fancy-index write-back is
        # last-writer-wins on duplicate slots).  The format says keys
        # are sorted, but decode accepts unsorted/duplicated wire
        # payloads — normalize or such a blob silently corrupts rows
        if len(keys_i) > 1:
            if not np.all(keys_i[1:] > keys_i[:-1]):
                order = np.argsort(keys_i, kind="stable")
                keys_i = keys_i[order]
                cwords = cwords[order]
                dup = keys_i[1:] == keys_i[:-1]
                if dup.any():
                    uk, inv = np.unique(keys_i, return_inverse=True)
                    merged = np.zeros((len(uk), cwords.shape[1]),
                                      dtype=np.uint64)
                    np.bitwise_or.at(merged, inv, cwords)
                    keys_i, cwords = uk, merged
        chunk = 8192  # containers per batch
        for c0 in range(0, len(keys_i), chunk):
            c1 = min(c0 + chunk, len(keys_i))
            ck = keys_i[c0:c1]
            cw = cwords[c0:c1]
            rows_of = ck // cpr
            slots_of = ck % cpr
            urows, starts = np.unique(rows_of, return_index=True)
            bounds = np.append(starts, len(ck))
            cur = np.zeros((len(ck), wpc), dtype=np.uint64)
            row_blocks = []
            for ri in range(len(urows)):
                row = int(urows[ri])
                sel = slice(int(bounds[ri]), int(bounds[ri + 1]))
                if clear:
                    arr = self._rows.get(row)
                    if arr is None:
                        continue
                else:
                    arr = self._row_array(row, create=True)
                w64 = arr.view(np.uint64).reshape(cpr, wpc)
                cur[sel] = w64[slots_of[sel]]
                row_blocks.append((w64, sel))
            delta = (cur & cw) if clear else (cw & ~cur)
            n_flip = int(np.bitwise_count(delta).sum())
            if not n_flip:
                continue
            changed += n_flip
            for w64, sel in row_blocks:
                if clear:
                    w64[slots_of[sel]] = cur[sel] & ~cw[sel]
                else:
                    w64[slots_of[sel]] = cur[sel] | cw[sel]
        return changed

    def _merge_positions(self, pos: np.ndarray, clear: bool) -> int:
        """Position-space merge: O(set bits).  ``pos`` is absolute
        fragment positions (row*width + off); sorted input is the wire
        contract, but a hostile unsorted payload is just re-sorted
        (duplicates are harmless — OR/ANDN are idempotent and the
        changed-bit count works on per-word aggregates)."""
        if len(pos) == 0:
            return 0
        pos = np.ascontiguousarray(pos, dtype=np.uint64)
        if len(pos) > 1 and not np.all(pos[1:] >= pos[:-1]):
            pos = np.sort(pos)
        # width is a power of two, so row/word boundaries align and
        # shift/mask replace div/mod; rows are contiguous runs in the
        # sorted positions — one diff-flag pass finds the segments
        width_shift = self.width.bit_length() - 1
        row_of = (pos >> np.uint64(width_shift)).astype(np.int64)
        rflag = np.empty(len(pos), dtype=bool)
        rflag[0] = True
        np.not_equal(row_of[1:], row_of[:-1], out=rflag[1:])
        rstarts = np.flatnonzero(rflag)
        rbounds = np.append(rstarts, len(pos))
        # materialize target rows (clear skips absent ones) — then the
        # whole payload merges in one native call when available
        row_arrays, seg = [], []
        for ri in range(len(rstarts)):
            row = int(row_of[rstarts[ri]])
            if clear:
                arr = self._rows.get(row)
                if arr is None:
                    continue
            else:
                arr = self._row_array(row, create=True)
            row_arrays.append(arr)
            seg.append(ri)
        if not row_arrays:
            return 0
        seg = np.asarray(seg, dtype=np.int64)
        seg_start, seg_end = rbounds[seg], rbounds[seg + 1]
        from pilosa_tpu.ops import hostkernels

        native = hostkernels.merge_positions(
            row_arrays, seg_start, seg_end, pos,
            self.width - 1, clear)
        if native is not None:
            return native
        # numpy fallback: per-word OR aggregates via diff-flag
        # segmentation + reduceat (sorted positions: each word is one
        # contiguous run), then gather/compare/scatter per row
        masks = np.uint64(1) << (pos & np.uint64(63))
        gw = (pos >> np.uint64(6)).astype(np.int64)
        changed = 0
        for k, arr in enumerate(row_arrays):
            s0, s1 = int(seg_start[k]), int(seg_end[k])
            gws = gw[s0:s1]
            flag = np.empty(s1 - s0, dtype=bool)
            flag[0] = True
            np.not_equal(gws[1:], gws[:-1], out=flag[1:])
            ws = np.flatnonzero(flag)
            wpr_shift = (self.width >> 6).bit_length() - 1
            uw = gws[ws] & ((1 << wpr_shift) - 1)
            a = np.bitwise_or.reduceat(masks[s0:s1], ws)
            w64 = arr.view(np.uint64)
            cur = w64[uw]
            if clear:
                delta = cur & a
                new = cur & ~a
            else:
                delta = a & ~cur
                new = cur | a
            n_flip = int(np.bitwise_count(delta).sum())
            if n_flip:
                changed += n_flip
                w64[uw] = new
        return changed

    def to_roaring(self) -> bytes:
        """Serialize the whole fragment as one roaring bitmap in fragment
        position space (reference fragment WriteTo archive payload,
        fragment.go:2436)."""
        from pilosa_tpu.storage import roaring as rcodec

        cpr = self.width // rcodec.CONTAINER_BITS
        keys = []
        blocks = []
        with self._lock:
            self._flush_delta_locked()  # export effective content
            for row in self.row_ids():
                w64 = self._rows[row].view(np.uint64)
                for b in range(cpr):
                    blk = w64[b * rcodec.WORDS_PER_CONTAINER : (b + 1) * rcodec.WORDS_PER_CONTAINER]
                    if blk.any():
                        keys.append(row * cpr + b)
                        blocks.append(blk)
            # copy while still holding the lock: blocks are views into live
            # row arrays, and a concurrent mutation must not tear the export
            stacked = (
                np.stack(blocks)
                if blocks
                else np.empty((0, rcodec.WORDS_PER_CONTAINER), np.uint64)
            )
        return rcodec.encode(np.array(keys, dtype=np.uint64), stacked)

    # -------------------------------------------------------- host queries

    def bit(self, row: int, col: int) -> bool:
        return self._bit_off_locked(row, self._offset(col))

    def row(self, row: int) -> np.ndarray:
        """Packed EFFECTIVE words for one row (base ⊕ delta; copy).

        Takes the lock (RLock — internal under-lock callers recurse
        fine): the background compactor can move a delta-only row from
        the plane into ``_rows`` at any moment, and an unlocked
        base-then-delta read would see neither half — a transient
        all-zeros answer for WAL-acknowledged bits."""
        with self._lock:
            arr, owned = self._row_words_effective_locked(row)
            if arr is None:
                return np.zeros(self.n_words, dtype=np.uint32)
            return arr if owned else arr.copy()

    def _row_words_effective_locked(self, row: int):
        """(words-or-None, owned) for one effective row — caller holds
        the lock.  ``owned`` says the array is a private overlay copy
        (safe to keep); otherwise it aliases the live base row and must
        be copied before the lock releases."""
        arr = self._rows.get(row)
        d = self._delta
        if d is not None and d.row_touched(row):
            out = (arr.copy() if arr is not None
                   else np.zeros(self.n_words, dtype=np.uint32))
            d.apply_row(row, out)
            return out, True
        return arr, False

    def row_ids(self) -> list[int]:
        # Locked like row(): the background compactor mutates _rows /
        # _delta, and unlocked iteration over _rows can raise
        # "dictionary changed size during iteration" mid-flush.
        with self._lock:
            d = self._delta
            if d is None or d.empty():
                return sorted(r for r, a in self._rows.items() if a.any())
            touched = set(d.touched_rows())
            out = [r for r, a in self._rows.items()
                   if r not in touched and a.any()]
            out.extend(r for r in touched
                       if d.row_any(r, self._rows.get(r)))
            return sorted(out)

    def row_count(self, row: int) -> int:
        with self._lock:
            arr, _ = self._row_words_effective_locked(row)
            return 0 if arr is None else int(np.bitwise_count(arr).sum())

    # ----------------------------------------------- anti-entropy blocks

    def blocks(self) -> list[dict]:
        """Per-block checksums for replica reconciliation: rows are
        grouped into blocks of HASH_BLOCK_SIZE=100, each hashed over its
        (rowID, packed words) content (reference FragmentBlocks,
        fragment.go:80 HashBlockSize, :1762 Checksum/Blocks).  The hash is
        blake2b-64 rather than the reference's xxhash — only cross-node
        consistency matters, not format compatibility."""
        return self.blocks_with_flag()[0]

    def blocks_with_flag(self) -> tuple[list[dict], bool]:
        """``(blocks, cache_hit)`` — the generation-keyed digest cache
        behind :meth:`blocks`: an unchanged fragment (same ``_gen``, no
        pending delta) serves the cached checksum list with zero hash
        work, so a quiescent anti-entropy round re-checksums nothing.
        Callers treat the returned list as READ-ONLY (it may be the
        cached object)."""
        import hashlib

        with self._lock:
            # replica reconciliation hashes base rows: merge the
            # pending overlay so checksums reflect effective content
            # (an empty overlay leaves _gen alone, keeping the cache)
            self._flush_delta_locked()
            cached = self._blocks_cache
            if cached is not None and cached[0] == self._gen:
                return cached[1], True
            out: list[dict] = []
            by_block: dict[int, list[int]] = {}
            for r in self.row_ids():
                by_block.setdefault(r // HASH_BLOCK_SIZE, []).append(r)
            for block in sorted(by_block):
                h = hashlib.blake2b(digest_size=8)
                for r in by_block[block]:
                    h.update(r.to_bytes(8, "little"))
                    h.update(self._rows[r].tobytes())
                out.append({"id": block, "checksum": h.hexdigest()})
            self._blocks_cache = (self._gen, out)
        return out, False

    def block_data(self, block: int) -> tuple[list[int], list[int]]:
        """(rowIDs, column offsets) parallel arrays for one block
        (reference fragment.blockData, fragment.go:1829)."""
        rows_out: list[int] = []
        cols_out: list[int] = []
        with self._lock:
            self._flush_delta_locked()  # same contract as blocks()
            lo, hi = block * HASH_BLOCK_SIZE, (block + 1) * HASH_BLOCK_SIZE
            for r in self.row_ids():
                if r < lo or r >= hi:
                    continue
                offs = np.nonzero(
                    np.unpackbits(self._rows[r].view(np.uint8), bitorder="little")
                )[0]
                rows_out.extend([r] * len(offs))
                cols_out.extend(int(o) for o in offs)
        return rows_out, cols_out

    def cached_row_counts(self, n: int = 0) -> dict[int, int] | None:
        """Exact {row: count} from the TopN cache when valid for the
        current generation and sufficient to answer TopN(n) exactly
        (n=0 demands a complete cache); else None."""
        with self._lock:
            if self._delta is not None and not self._delta.empty():
                # cached counts describe base content; the pending
                # overlay makes them stale — the caller's scan path
                # (device_matrix/_stacked) merges and recounts
                return None
            counts = self.topn_cache.get(self._gen)
            if counts is None or not self.topn_cache.exact_for(n):
                return None
            return counts

    def recalculate_cache(self) -> None:
        """Recompute exact row counts into the TopN cache (reference
        fragment.RecalculateCache via holder.RecalculateCaches,
        api.go:1139 /recalculate-caches)."""
        from pilosa_tpu.models.cache import CACHE_TYPE_NONE

        if self.topn_cache.cache_type == CACHE_TYPE_NONE:
            return  # put() would discard the counts unread
        with self._lock:
            self._flush_delta_locked()  # counts must cover the overlay
            counts = {}
            for r, arr in self._rows.items():
                c = int(np.bitwise_count(arr).sum(dtype=np.uint64))
                if c:
                    counts[int(r)] = c
            self.topn_cache.put(self._gen, counts)

    def cache_row_counts(self, counts: dict[int, int], gen: int | None = None) -> None:
        """Store counts computed at generation ``gen`` (defaults to the
        current one).  If a write advanced the generation since the caller
        read the matrix, the entry simply never hits — it must NOT be
        stamped with the newer generation."""
        with self._lock:
            self.topn_cache.put(self._gen if gen is None else gen, counts)

    def device_matrix_with_gen(self):
        """(gen, row_ids, device matrix) — gen captured atomically with
        the matrix read, for correctly-stamped downstream caching."""
        with self._lock:
            ids, dev = self.device_matrix()
            return self._gen, ids, dev

    def min_row_id(self):
        ids = self.row_ids()
        return ids[0] if ids else None

    def max_row_id(self):
        ids = self.row_ids()
        return ids[-1] if ids else None

    # ------------------------------------------------------ device tensors

    def _stacked(self) -> tuple[np.ndarray, np.ndarray]:
        """(row_ids int64[R], matrix uint32[R, words]) — cached per
        generation.  Merges any pending delta first: every whole-matrix
        consumer (snapshot, device_matrix, TopN scans, Rows column
        filters, resize export) then sees effective content at a single
        coherent generation — the delta plane only stays pending on the
        fused row paths that know how to fuse it."""
        with self._lock:
            self._flush_delta_locked()
            if self._stack_cache is not None and self._stack_cache[0] == self._gen:
                return self._stack_cache[1], self._stack_cache[2]
            ids = np.array(self.row_ids(), dtype=np.int64)
            if len(ids) == 0:
                matrix = np.zeros((0, self.n_words), dtype=np.uint32)
            else:
                matrix = np.stack([self._rows[int(r)] for r in ids]).copy()
            self._stack_cache = (self._gen, ids, matrix)
            return ids, matrix

    def device_matrix(self):
        """(row_ids, jax uint32[R, words]) resident in device memory;
        accounted by the process-wide residency manager."""
        import jax

        from pilosa_tpu.runtime import residency

        with self._lock:
            key = "matrix"
            hit = self._device_cache.get(key)
            if (hit is not None and hit[0] == self._gen
                    and residency.live(hit[2])):
                residency.manager().touch(self._device_cache, key)
                return hit[1], hit[2]
            ids, matrix = self._stacked()
            from pilosa_tpu.ops import bitmap as bm

            dev = (np.ascontiguousarray(matrix) if bm.host_mode()
                   # pilosa-lint: allow(blocking-under-lock) -- upload under the fragment lock is the residency design: it serializes per-fragment uploads so one generation uploads once; nothing re-enters
                   else bm.device_put(matrix,
                                              label="fragment.matrix"))
            self._device_cache[key] = (self._gen, ids, dev)
            residency.manager().admit(self._device_cache, key,
                                      matrix.nbytes)
            return ids, dev

    def device_row(self, row: int):
        """One row as a device array, sliced from the resident matrix."""
        ids, dev = self.device_matrix()
        slot = np.searchsorted(ids, row)
        if slot >= len(ids) or ids[slot] != row:
            if isinstance(dev, np.ndarray):
                return np.zeros(self.n_words, dtype=np.uint32)
            import jax.numpy as jnp

            return jnp.zeros(self.n_words, dtype=jnp.uint32)
        return dev[int(slot)]

    def row_containers(self, row: int):
        """One BASE row in compressed container-directory form:
        ``(keys int64[n], blocks uint32[n, 2048], bits)`` holding only
        the row's non-empty 2^16-bit containers — the host half of the
        roaring-on-TPU layout (ops/containers.py), the exact
        ``(keys, 1024x64-bit blocks)`` shape storage/roaring.py decodes
        — or ``None`` when the row is too dense to benefit (fill ratio
        ``bits/width`` above the [containers] threshold: the dense
        fused path stays the right engine for hot rows).  Cached per
        base generation; a pending delta plane does NOT invalidate
        (the engine routes delta-touched rows dense instead)."""
        from pilosa_tpu.ops import containers as ct

        with self._lock:
            hit = self._container_cache.get(row)
            if hit is not None and hit[0] == self._gen:
                _g, keys, blocks, bits = hit
            else:
                while len(self._container_cache) >= 1024:
                    self._container_cache.pop(
                        next(iter(self._container_cache)))
                arr = self._rows.get(row)
                bits = (0 if arr is None
                        else int(np.bitwise_count(arr)
                                 .sum(dtype=np.uint64)))
                # hot rows cache ONLY the bit count (keys=None): the
                # block build would copy the whole dense row per
                # queried row, for a path that falls back anyway
                keys = blocks = None
                self._container_cache[row] = (self._gen, keys, blocks,
                                              bits)
            if bits > ct.config().threshold * self.width:
                return None
            if keys is None:
                # sparse (under the CURRENT threshold) but not yet
                # built — materialize the directory now
                arr = self._rows.get(row)
                if arr is None or bits == 0:
                    keys = np.empty(0, dtype=np.int64)
                    blocks = np.empty((0, ct.CWORDS), dtype=np.uint32)
                else:
                    grid = arr.reshape(-1, ct.CWORDS)
                    keys = np.flatnonzero(grid.any(axis=1))
                    blocks = grid[keys].copy()
                self._container_cache[row] = (self._gen, keys, blocks,
                                              bits)
            return keys, blocks, bits

    def row_container_kinds(self, row: int):
        """``(keys, blocks, bits, kinds uint8[n])`` for one BASE row:
        ``row_containers`` plus the cheapest storage kind per container
        (ops/kindpools.pick_kinds — the serializer's own cost rule
        under the configured [containers] array-max / run-cap), picked
        at directory-build time.  Compaction bumps the base generation,
        which rebuilds the directory and re-picks — ingest churn
        promotes/demotes kinds for free.  ``None`` exactly when
        ``row_containers`` is ``None`` (hot rows stay dense)."""
        trio = self.row_containers(row)
        if trio is None:
            return None
        keys, blocks, bits = trio
        from pilosa_tpu.ops import containers as ct
        from pilosa_tpu.ops import kindpools as kp

        cfg = ct.config()
        kinds = kp.pick_kinds(blocks, array_max=cfg.array_max,
                              run_cap=cfg.run_cap)
        return keys, blocks, bits, kinds

    def device_planes(self, depth: int):
        """BSI plane stack uint32[2 + depth, words] resident on device;
        accounted by the process-wide residency manager.  Tiered: the
        assembled host planes register as the entry's host twin, so an
        HBM eviction demotes and a re-miss pays ONE placement instead
        of the per-plane re-assembly — inline rather than async (this
        runs under the fragment lock; the field-level stacks own the
        async promotion path, and ``device_matrix``'s host half is the
        existing generation-stamped ``_stack_cache``)."""
        import jax

        from pilosa_tpu import observe as _observe
        from pilosa_tpu.runtime import residency

        with self._lock:
            key = ("planes", depth)
            # tick the prefetcher's access table: plane-stack entries
            # are demote-eligible, so without a score a hot one would
            # be the permanent demote_coldest victim
            _observe.note_access((id(self._device_cache), key))
            hit = self._device_cache.get(key)
            if (hit is not None and hit[0] == self._gen
                    and residency.live(hit[1])):
                residency.manager().touch(self._device_cache, key)
                return hit[1]
            mgr = residency.manager()
            ent = mgr.host_lookup(self._device_cache, key, self._gen)
            if ent is not None:
                # demoted-but-warm: one placement (ent.promote — the
                # same upload-under-the-fragment-lock design as the
                # cold path below), no plane re-assembly
                value = ent.promote(ent.payload)
                self._device_cache[key] = value
                mgr.admit(self._device_cache, key, ent.nbytes,
                          token=self._gen, host=ent.payload,
                          promote=ent.promote)
                return value[1]
            P = np.zeros((bsi_ops.OFFSET_PLANE + depth, self.n_words), dtype=np.uint32)
            for i in range(P.shape[0]):
                arr = self._rows.get(i)
                if arr is not None:
                    P[i] = arr
            from pilosa_tpu.ops import bitmap as bm

            dev = (P if bm.host_mode()
                   # pilosa-lint: allow(blocking-under-lock) -- same residency design as device_matrix: per-fragment upload serialization under the owning lock
                   else bm.device_put(P, label="fragment.planes"))
            self._device_cache[key] = (self._gen, dev)
            residency.manager().admit(
                self._device_cache, key, P.nbytes, token=self._gen,
                host=P, promote=_plane_promote(self._gen))
            return dev

    # ------------------------------------------------------------ BSI ops

    def _bsi_base_rows(self, depth: int, filter_words=None):
        """(P, exists, sign, consider) device values shared by BSI ops."""
        import jax
        import jax.numpy as jnp

        P = self.device_planes(depth)
        exists = P[bsi_ops.EXISTS_PLANE]
        sign = P[bsi_ops.SIGN_PLANE]
        consider = exists
        if filter_words is not None:
            consider = consider & jax.device_put(np.asarray(filter_words, dtype=np.uint32))
        return P, exists, sign, consider

    def set_value(self, col: int, depth: int, value: int) -> bool:
        """Write a base-relative signed value as bit planes
        (reference setValueBase, fragment.go:977)."""
        uvalue = -value if value < 0 else value
        changed = False
        off = self._offset(col)
        with self._lock:
            for i in range(depth):
                plane = bsi_ops.OFFSET_PLANE + i
                if (uvalue >> i) & 1:
                    changed |= self._apply_set(plane, off)
                    self._wal_append(_WAL_REC.pack(_WAL_SET, plane, off))
                else:
                    changed |= self._apply_clear(plane, off)
                    self._wal_append(_WAL_REC.pack(_WAL_CLEAR, plane, off))
                self._op_n += 1
            changed |= self._apply_set(bsi_ops.EXISTS_PLANE, off)
            self._wal_append(_WAL_REC.pack(_WAL_SET, bsi_ops.EXISTS_PLANE, off))
            if value < 0:
                changed |= self._apply_set(bsi_ops.SIGN_PLANE, off)
                self._wal_append(_WAL_REC.pack(_WAL_SET, bsi_ops.SIGN_PLANE, off))
            else:
                changed |= self._apply_clear(bsi_ops.SIGN_PLANE, off)
                self._wal_append(_WAL_REC.pack(_WAL_CLEAR, bsi_ops.SIGN_PLANE, off))
            self._op_n += 2
            self._bump_gen()
            self._maybe_snapshot()
            self._paranoia_check()
        return changed

    def clear_value(self, col: int, depth: int) -> bool:
        off = self._offset(col)
        with self._lock:
            changed = self._apply_clear(bsi_ops.EXISTS_PLANE, off)
            if changed:
                self._wal_append(_WAL_REC.pack(_WAL_CLEAR, bsi_ops.EXISTS_PLANE, off))
                self._op_n += 1
                self._bump_gen()
        return changed

    def value(self, col: int, depth: int) -> tuple[int, bool]:
        """Read one column's base-relative value (reference fragment.value,
        fragment.go:896)."""
        if not self.bit(bsi_ops.EXISTS_PLANE, col):
            return 0, False
        v = 0
        for i in range(depth):
            if self.bit(bsi_ops.OFFSET_PLANE + i, col):
                v |= 1 << i
        if self.bit(bsi_ops.SIGN_PLANE, col):
            v = -v
        return v, True

    def sum(self, filter_words, depth: int) -> tuple[int, int]:
        """(base-relative sum, count) — device plane counts, exact host
        accumulation (reference fragment.sum, fragment.go:1111)."""
        from pilosa_tpu.ops.bitmap import popcount

        P, _, _, consider = self._bsi_base_rows(depth, filter_words)
        pos, neg = bsi_ops.plane_counts(P, consider)
        pos, neg = np.asarray(pos), np.asarray(neg)
        total = sum((int(p) - int(n)) << i for i, (p, n) in enumerate(zip(pos, neg)))
        count = int(popcount(consider))
        return total, count

    def min(self, filter_words, depth: int) -> tuple[int, int]:
        """(base-relative min, count) (reference fragment.min, fragment.go:1147)."""
        from pilosa_tpu.ops.bitmap import popcount

        P, _, sign, consider = self._bsi_base_rows(depth, filter_words)
        if int(popcount(consider)) == 0:
            return 0, 0
        negs = consider & sign
        if int(popcount(negs)) > 0:
            taken, count = bsi_ops.extreme_max(P, negs)
            return -bsi_ops.assemble_value(taken), int(count)
        taken, count = bsi_ops.extreme_min(P, consider)
        return bsi_ops.assemble_value(taken), int(count)

    def max(self, filter_words, depth: int) -> tuple[int, int]:
        """(base-relative max, count) (reference fragment.max, fragment.go:1191)."""
        from pilosa_tpu.ops.bitmap import popcount

        P, _, sign, consider = self._bsi_base_rows(depth, filter_words)
        if int(popcount(consider)) == 0:
            return 0, 0
        pos = consider & ~sign
        if int(popcount(pos)) == 0:
            taken, count = bsi_ops.extreme_min(P, consider)
            return -bsi_ops.assemble_value(taken), int(count)
        taken, count = bsi_ops.extreme_max(P, pos)
        return bsi_ops.assemble_value(taken), int(count)

    def not_null(self, depth: int) -> np.ndarray:
        """Existence row (reference notNull, fragment.go:1460)."""
        return self.row(bsi_ops.EXISTS_PLANE)

    def range_op(self, op: str, depth: int, predicate: int) -> np.ndarray:
        """BSI comparison -> packed words for this shard.  op in
        {'==','!=','<','<=','>','>='} (reference rangeOp, fragment.go:1273).
        The math lives in bsi_ops.range_words — one implementation shared
        with the executor's fused stacked path."""
        P = self.device_planes(depth)
        return np.asarray(bsi_ops.range_words(P, op, predicate))

    def range_between(self, depth: int, pred_min: int, pred_max: int) -> np.ndarray:
        """BSI between [min, max] inclusive (reference rangeBetween,
        fragment.go:1465); math shared with the fused path via
        bsi_ops.between_words."""
        P = self.device_planes(depth)
        return np.asarray(bsi_ops.between_words(P, pred_min, pred_max))
