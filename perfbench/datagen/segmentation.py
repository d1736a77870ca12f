"""Audience segmentation (Pilosa docs, introduction and "Getting
Started"): people as columns, attributes as rows of two set fields.

``demo``   dense demographic rows, each an independent Bernoulli(fill)
           draw per column; fills from the configuration.
``trait``  (where the configuration has the field) sparse behavioural
           rows in three styles, the style a fixed
           function of the row id (so rank r of the Zipf draw is the
           same kind of row under every seed): scattered ~0.1% (array
           containers), two contiguous spans a shard (run containers),
           8% of two containers a shard (bitmap containers) -- the
           styles of ``chip_smoke.py``, which the program's own
           serializer rule sorts into the three kinds.
"""

from __future__ import annotations

import numpy as np

from perfbench import wire
from perfbench.bits import (CONTAINERS_PER_SHARD, SHARD_WIDTH,
                            WORDS_PER_SHARD)
from perfbench.datagen.common import (Dataset, field_rows, map_shards,
                                      shard_rng)

STYLES = ("array", "run", "bitmap")
HOT_CONTAINERS = 4  # span and cluster rows live in containers 0..3
SCATTER_DRAWS = 1050
CLUSTER_FILL = 5243  # of 65536: over the array kind's 4096


def _band(lo: float, hi: float, n: int) -> np.ndarray:
    """n fills inside [lo, hi], highest first: the band in n equal
    slices and a row at the middle of each, so that no row sits on the
    band's edge."""
    return hi - (hi - lo) * (np.arange(n) + 0.5) / n


def demo_fills(cfg: dict) -> np.ndarray:
    """Fill of each demo row, hottest first: the ``hot_rows`` share of
    them over the band ``hot_fill``, the rest over ``cold_fill``."""
    d = cfg["fields"]["demo"]
    rows = field_rows(cfg, "demo")
    n_hot = round(d["hot_rows"] * rows)
    return np.concatenate([_band(*d["hot_fill"], n_hot),
                           _band(*d["cold_fill"], rows - n_hot)])


def trait_style(cfg: dict, row: int) -> str:
    """Style of a trait row: the mix's pattern repeats every 20 rows."""
    mix = cfg["fields"]["trait"]["style_mix"]  # shares of 20
    r = row % 20
    if r < mix["array"]:
        return "array"
    return "run" if r < mix["array"] + mix["run"] else "bitmap"


def generate(cfg: dict, seed: int, cancel=None) -> Dataset:
    n_shards = cfg["shards"]
    ds = Dataset(n_shards)
    fills = demo_fills(cfg)
    has_trait = "trait" in cfg["fields"]
    n_demo = len(fills)
    n_trait = field_rows(cfg, "trait") if has_trait else 0
    thresholds = np.round(fills * 65536).astype(np.uint32)
    styles = np.array([STYLES.index(trait_style(cfg, r))
                       for r in range(n_trait)])
    rows_of = [np.flatnonzero(styles == k) for k in range(3)]
    demo_keys = (np.repeat(np.arange(n_demo, dtype=np.uint64)
                           * CONTAINERS_PER_SHARD, CONTAINERS_PER_SHARD)
                 + np.tile(np.arange(CONTAINERS_PER_SHARD, dtype=np.uint64),
                           n_demo))
    demo_words = np.empty((n_demo, n_shards, WORDS_PER_SHARD),
                          dtype=np.uint64)

    def one(shard: int):
        rng = shard_rng(seed, cfg["name"], shard)
        for r in range(n_demo):
            u = rng.integers(0, 65536, size=SHARD_WIDTH, dtype=np.uint16)
            demo_words[r, shard] = np.packbits(
                u < thresholds[r], bitorder="little").view(np.uint64)
        demo_blob = wire.encode_bitmaps(demo_keys, demo_words[:, shard])
        if not has_trait:
            return demo_blob, None, None
        parts = []
        # scattered
        ra = rows_of[0].astype(np.uint64)
        p = rng.integers(0, SHARD_WIDTH, size=(len(ra), SCATTER_DRAWS),
                         dtype=np.uint64) + (ra << np.uint64(20))[:, None]
        parts.append(p.ravel())
        # two spans a shard, in two of the hot containers
        for rows, spans in ((rows_of[1], True), (rows_of[2], False)):
            n = len(rows)
            first = rng.integers(0, HOT_CONTAINERS, size=n)
            second = (first + rng.integers(1, HOT_CONTAINERS, size=n)) \
                % HOT_CONTAINERS
            conts = np.stack([first, second], axis=1).astype(np.uint64)
            base = ((rows.astype(np.uint64) << np.uint64(20))[:, None]
                    + (conts << np.uint64(16)))  # [n, 2]
            if spans:
                length = rng.integers(3000, 6000, size=(n, 2))
                start = (rng.random((n, 2))
                         * (65536 - length)).astype(np.uint64)
                flat_len = length.ravel()
                offs = (np.arange(int(flat_len.sum()), dtype=np.uint64)
                        - np.repeat(np.cumsum(flat_len) - flat_len,
                                    flat_len).astype(np.uint64))
                parts.append(np.repeat((base + start).ravel(), flat_len)
                             + offs)
            else:
                hit = rng.integers(0, 65536, size=(n, 2, 65536),
                                   dtype=np.uint16) < CLUSTER_FILL
                i, j, k = np.nonzero(hit)
                parts.append(base[i, j] + k.astype(np.uint64))
        pos = np.unique(np.concatenate(parts))
        return demo_blob, wire.encode_positions(pos), pos

    out = map_shards(one, n_shards, cancel)
    ds.fields = [{"name": "demo", "options": {"type": "set"}}]
    ds.payloads = [("demo", s, o[0]) for s, o in enumerate(out)]
    ds.dense["demo"] = {r: demo_words[r].reshape(-1)
                        for r in range(n_demo)}
    ds.n_rows = {"demo": n_demo}
    # bytes a read of a row needs, in the smaller of its roaring and
    # its dense form: a demo row is array containers (2 B a bit) under
    # 4096 bits a container and bitmap containers (8 KiB) over it
    per_container = np.minimum(2 * fills * 65536, 8192)
    ds.row_bytes = {"demo": (per_container * CONTAINERS_PER_SHARD
                             * n_shards).astype(np.int64)}
    if has_trait:
        ds.fields.append({"name": "trait", "options": {"type": "set"}})
        ds.payloads += [("trait", s, o[1]) for s, o in enumerate(out)]
        ds.sparse["trait"] = [o[2] for o in out]
        ds.n_rows["trait"] = n_trait
        # by style (array 2 B a bit; run two spans = 2 x 4 B + 2 a
        # container; bitmap 8 KiB a container), per shard
        per_shard = {"array": 2 * SCATTER_DRAWS, "run": 2 * (2 + 4),
                     "bitmap": 2 * 8192}
        ds.row_bytes["trait"] = np.array(
            [n_shards * per_shard[trait_style(cfg, r)]
             for r in range(n_trait)], dtype=np.int64)
    return ds
