"""View: one physical layout of a field, owning fragments by shard.

Parity with the reference's view (view.go:44): a field has a "standard"
view, time-quantum views named standard_YYYYMMDDHH etc., and BSI views
named bsig_<field> (view.go:37-41).  The view routes bits to the fragment
owning the column's shard and creates fragments on first write
(view.go:263 CreateFragmentIfNotExists).
"""

from __future__ import annotations

import os

import numpy as np

from pilosa_tpu import stagecheck
from pilosa_tpu.models.fragment import Fragment
from pilosa_tpu.shardwidth import SHARD_WIDTH

VIEW_STANDARD = "standard"
VIEW_BSI_PREFIX = "bsig_"


class _FragmentMap(dict):
    """shard -> Fragment.  The map cannot change without the view's
    write token changing (stagecheck.py): create, replace and
    delete all pass here, and a fragment put in is bound to the view,
    so that its own writes change the token from then on."""

    __slots__ = ("_view",)

    def __init__(self, view: "View"):
        super().__init__()
        self._view = view

    def __setitem__(self, shard: int, frag: Fragment) -> None:
        frag._owner = self._view
        super().__setitem__(shard, frag)
        self._view.note_write()

    def __delitem__(self, shard: int) -> None:
        super().__delitem__(shard)
        self._view.note_write()

    def pop(self, shard: int, *default):
        try:
            return super().pop(shard, *default)
        finally:
            self._view.note_write()

    def _unsupported(self, *args, **kwargs):
        raise TypeError("View.fragments changes one shard at a time")

    clear = update = setdefault = popitem = __ior__ = _unsupported


class View:
    def __init__(
        self,
        path: str | None,
        index: str,
        field: str,
        name: str,
        mutex: bool = False,
        cache_type: str = "ranked",
        cache_size: int = 50000,
    ):
        from pilosa_tpu import lockcheck as _lockcheck

        self.path = path
        self.index = index
        self.field = field
        self.name = name
        self.mutex = mutex
        self.cache_type = cache_type
        self.cache_size = cache_size
        #: changes after every write to any fragment of this view and
        #: every change of the map below; cached stacks are stamped
        #: with it (stagecheck.py)
        self.write_token = stagecheck.next_token()
        #: shard set -> (write token, the result cache's aggregate
        #: stamp of this view over that set), as last walked
        #: (Executor._rc_view_stamp): good while the token stands
        self.rc_stamps: dict[tuple, tuple] = {}
        self.fragments: dict[int, Fragment] = _FragmentMap(self)
        # guards fragment CREATION/DELETION only; reads stay lock-free
        # (GIL-atomic dict gets, the double-checked pattern)
        self._lock = _lockcheck.lock("view")
        if path is not None:
            os.makedirs(self._frag_dir, exist_ok=True)
            self._open_fragments()

    @property
    def _frag_dir(self) -> str:
        return os.path.join(self.path, "fragments")

    def _frag_path(self, shard: int) -> str:
        return os.path.join(self._frag_dir, str(shard))

    def _open_fragments(self) -> None:
        seen = set()
        for fn in os.listdir(self._frag_dir):
            base = fn.rsplit(".", 1)[0]
            if base.isdigit():
                seen.add(int(base))
        for shard in sorted(seen):
            self.fragments[shard] = Fragment(
                self._frag_path(shard), self.index, self.field, self.name,
                shard, mutex=self.mutex,
                cache_type=self.cache_type, cache_size=self.cache_size,
            )

    def note_write(self) -> None:
        """Something a per-fragment cache token could read has changed
        in this view.  Called by the writer, after the fragment's own
        counters and before the write returns to its caller."""
        self.write_token = stagecheck.next_token()

    def fragment(self, shard: int) -> Fragment | None:
        return self.fragments.get(shard)

    def create_fragment_if_not_exists(self, shard: int) -> Fragment:
        """Create-on-first-write, double-checked under the view lock:
        two concurrent first-writers to a fresh shard must get the
        SAME Fragment object — the unlocked check-then-act let each
        construct its own, one won the dict, and the loser's
        acknowledged write landed in an orphaned object (found by the
        self-healing convergence soak: one bit silently missing on a
        replica after concurrent degraded-write ingest; with a path,
        both objects also held append handles to the same WAL file)."""
        frag = self.fragments.get(shard)
        if frag is not None:
            return frag
        with self._lock:
            frag = self.fragments.get(shard)
            if frag is None:
                path = (None if self.path is None
                        else self._frag_path(shard))
                frag = Fragment(
                    path, self.index, self.field, self.name, shard,
                    mutex=self.mutex,
                    cache_type=self.cache_type,
                    cache_size=self.cache_size,
                )
                self.fragments[shard] = frag
        return frag

    def delete_fragment(self, shard: int) -> bool:
        """Close and delete one shard's fragment and its files — the
        post-resize cleaner path (reference holderCleaner,
        holder.go:1126 cleanHolder; view.deleteFragment)."""
        with self._lock:
            frag = self.fragments.pop(shard, None)
        if frag is None:
            return False
        frag.close()
        if self.path is not None:
            base = self._frag_path(shard)
            for suffix in (".snap", ".wal", ".cache"):
                try:
                    os.remove(base + suffix)
                except FileNotFoundError:
                    pass
        return True

    def available_shards(self) -> set[int]:
        return set(self.fragments)

    # -- bit ops ------------------------------------------------------------

    def set_bit(self, row: int, col: int) -> bool:
        return self.create_fragment_if_not_exists(col // SHARD_WIDTH).set_bit(row, col)

    def clear_bit(self, row: int, col: int) -> bool:
        frag = self.fragment(col // SHARD_WIDTH)
        return False if frag is None else frag.clear_bit(row, col)

    def row(self, row_id: int, shard: int) -> np.ndarray | None:
        frag = self.fragment(shard)
        return None if frag is None else frag.row(row_id)

    # -- BSI ops ------------------------------------------------------------

    def set_value(self, col: int, depth: int, value: int) -> bool:
        return self.create_fragment_if_not_exists(col // SHARD_WIDTH).set_value(
            col, depth, value
        )

    def value(self, col: int, depth: int) -> tuple[int, bool]:
        frag = self.fragment(col // SHARD_WIDTH)
        return (0, False) if frag is None else frag.value(col, depth)

    # -- streaming ingest (pilosa_tpu.ingest) -------------------------------

    def flush_deltas(self) -> int:
        """Merge every fragment's pending delta plane into base state;
        returns bit positions merged (0 when nothing pended)."""
        return sum(frag.flush_delta()
                   for frag in list(self.fragments.values()))

    def delta_stats(self) -> dict:
        """Pending-delta audit for this view: per-shard delta stats
        (the /debug/ingest per-fragment section aggregates these)."""
        out = {}
        for shard, frag in list(self.fragments.items()):
            s = frag.delta_stats()
            if s is not None:
                out[shard] = s
        return out

    def close(self) -> None:
        for frag in self.fragments.values():
            frag.close()

    def snapshot(self) -> None:
        for frag in self.fragments.values():
            frag.snapshot()
