"""What every schema's generator shares: the per-shard random streams,
the thread pool, and the ``Dataset`` a generator returns.

A dataset is generated shard by shard.  Shard ``s`` of configuration
``c`` under ``--seed n`` draws from ``default_rng([n, crc32(c), s])``
and from nothing else, so the data is a pure function of (config, seed)
whatever the number of threads.  numpy releases the GIL in its bulk
operations, so a thread pool generates shards in parallel in the one
process."""

from __future__ import annotations

import os
import threading
import zlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from perfbench.bits import (SHARD_WIDTH, WORDS_PER_SHARD, pack_bool,
                            pack_positions)


def shard_rng(seed: int, name: str, shard: int) -> np.random.Generator:
    return np.random.default_rng([seed, zlib.crc32(name.encode()), shard])


def gen_threads() -> int:
    return max(2, min(12, (os.cpu_count() or 4) - 1))


def field_rows(cfg: dict, field: str) -> int:
    """Rows of a field: a number, or the name of the configuration's
    top-level key that holds it (the keys ``reduced`` can name)."""
    rows = cfg["fields"][field]["rows"]
    return cfg[rows] if isinstance(rows, str) else rows


class Cancelled(Exception):
    """The run failed elsewhere while the data was being made."""


def map_shards(fn, n_shards: int, cancel: threading.Event | None = None
               ) -> list:
    """fn(shard) for every shard on the pool; ``cancel`` (set by a run
    that has already failed) stops the shards not yet started."""
    def one(shard: int):
        if cancel is not None and cancel.is_set():
            raise Cancelled()
        return fn(shard)

    with ThreadPoolExecutor(gen_threads()) as pool:
        return list(pool.map(one, range(n_shards)))


class Dataset:
    """The index as the generator made it: import bodies for the
    loader, and the same bits as numpy arrays for the oracle.

    fields      [{"name", "options"}] as POSTed to the server
    payloads    [(field, shard, roaring bytes)]
    values      {int field: (cols int64 ascending, vals int64)}
    dense       {field: {row: packed uint64 words over all shards}}
    sparse      {field: [per shard: sorted positions row<<20|offset]}
    codes       {field: per-column row id (a field with exactly one
                 row a column)}
    row_bytes   {field: int64[rows]: bytes a read of that row needs,
                 its roaring form over all shards}
    int_bytes   {int field: bytes of its bit planes and its exists
                 plane, each in the smaller of its roaring and its
                 dense form}
    """

    def __init__(self, n_shards: int):
        self.n_shards = n_shards
        self.n_words = n_shards * WORDS_PER_SHARD
        self.n_cols = n_shards * SHARD_WIDTH
        self.fields: list[dict] = []
        self.payloads: list[tuple[str, int, bytes]] = []
        self.values: dict[str, tuple[np.ndarray, np.ndarray]] = {}
        self.dense: dict[str, dict[int, np.ndarray]] = {}
        self.sparse: dict[str, list[np.ndarray]] = {}
        self.codes: dict[str, np.ndarray] = {}
        self.row_bytes: dict[str, np.ndarray] = {}
        self.n_rows: dict[str, int] = {}
        self.int_bytes: dict[str, int] = {}
        self._packed: dict[tuple[str, int], np.ndarray] = {}

    def meta(self) -> dict:
        """The index as ``perfbench/bytes_needed.py`` wants it."""
        return {"row_bytes": self.row_bytes, "int_bytes": self.int_bytes}

    def row(self, field: str, row: int) -> np.ndarray:
        """Packed words of one row (oracle side)."""
        d = self.dense.get(field)
        if d is not None and row in d:
            return d[row]
        key = (field, row)
        w = self._packed.get(key)
        if w is not None:
            return w
        if field in self.codes:
            w = pack_bool(self.codes[field] == row)
        else:
            lo, hi = np.uint64(row << 20), np.uint64((row + 1) << 20)
            parts = []
            for s, pos in enumerate(self.sparse[field]):
                a, b = np.searchsorted(pos, (lo, hi))
                parts.append((pos[a:b] - lo) + np.uint64(s * SHARD_WIDTH))
            w = pack_positions(np.concatenate(parts), self.n_words)
        if len(self._packed) >= 64:  # bound the oracle's memory
            self._packed.pop(next(iter(self._packed)))
        self._packed[key] = w
        return w
