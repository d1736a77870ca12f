"""NYC taxi rides (Pilosa docs/examples.md "Transportation"): rides as
columns; every categorical attribute is a field with exactly one row a
ride, the two numeric attributes are BSI int fields.

The categorical fields are made as one code per ride (a draw from the
field's distribution through a 65536-entry lookup table) and sent as
``set`` fields over ``/import-roaring`` (the program's roaring route
takes set and time fields only; the original example predates the
mutex type and used set fields too).  The int fields go over
``/import-value``, on the configuration's ``value_share`` of rides: the
columns ``c`` with ``c % (1/value_share) == 0``."""

from __future__ import annotations

import numpy as np

from perfbench import wire
from perfbench.bits import SHARD_WIDTH
from perfbench.datagen.common import (Dataset, field_rows, map_shards,
                                      shard_rng)


def distribution(spec: dict) -> np.ndarray:
    """Row probabilities of a categorical field from its spec."""
    n = spec["rows"]
    kind = spec["dist"]
    if kind == "uniform":
        p = np.ones(n)
    elif kind == "table":
        p = np.array(spec["p"], dtype=np.float64)
    elif kind == "zipf":
        p = 1.0 / np.arange(1, n + 1) ** spec["s"]
    elif kind == "exponential":  # bucket k holds [k, k+1) of Exp(mean)
        edges = np.arange(n + 1) / spec["mean"]
        p = np.exp(-edges[:-1]) - np.exp(-edges[1:])
        p[-1] += np.exp(-edges[-1])
    elif kind == "diurnal":  # two rush-hour humps over the day
        t = (np.arange(n) + 0.5) / n * 24
        p = (0.25 + np.exp(-((t - 8.5) / 2.0) ** 2)
             + 1.3 * np.exp(-((t - 19.0) / 3.0) ** 2))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if len(p) != n:
        raise ValueError(f"{spec}: {len(p)} probabilities for {n} rows")
    return p / p.sum()


def lookup_table(p: np.ndarray) -> np.ndarray:
    """uint16 draw -> row id, every row with at least one entry."""
    cum = np.cumsum(p)
    lut = np.searchsorted(cum * 65536, np.arange(65536), side="right")
    return np.minimum(lut, len(p) - 1).astype(
        np.uint8 if len(p) <= 256 else np.uint16)


def generate(cfg: dict, seed: int, cancel=None) -> Dataset:
    n_shards = cfg["shards"]
    ds = Dataset(n_shards)
    cats = {name: {**spec, "rows": field_rows(cfg, name)}
            for name, spec in cfg["fields"].items() if spec["type"] == "set"}
    ints = {name: spec for name, spec in cfg["fields"].items()
            if spec["type"] == "int"}
    luts = {name: lookup_table(distribution(spec))
            for name, spec in cats.items()}
    stride = round(1 / cfg["value_share"])
    valued = np.arange(0, SHARD_WIDTH, stride)
    codes = {name: np.empty(ds.n_cols, dtype=luts[name].dtype)
             for name in cats}
    vals = {name: np.empty(n_shards * len(valued), dtype=np.int64)
            for name in ints}

    def one(shard: int):
        rng = shard_rng(seed, cfg["name"], shard)
        lo = shard * SHARD_WIDTH
        blobs = []
        for name in cats:
            c = luts[name][rng.integers(0, 65536, size=SHARD_WIDTH,
                                        dtype=np.uint16)]
            codes[name][lo:lo + SHARD_WIDTH] = c
            order = np.argsort(c, kind="stable")
            blobs.append((name, shard, wire.encode_positions(
                (c[order].astype(np.uint64) << np.uint64(20))
                | order.astype(np.uint64))))
        # fare and duration follow the trip's distance bucket
        miles = codes["dist_miles"][lo:lo + SHARD_WIDTH][valued] \
            .astype(np.float64) + rng.random(len(valued))
        for name, spec in ints.items():
            v = (spec["base"] + spec["per_mile"] * miles
                 * rng.gamma(spec["shape"], 1.0 / spec["shape"],
                             size=len(valued)))
            vals[name][shard * len(valued):(shard + 1) * len(valued)] = \
                np.clip(np.round(v), spec["min"], spec["max"])
        return blobs

    for blobs in map_shards(one, n_shards, cancel):
        ds.payloads.extend(blobs)
    cols = (np.arange(n_shards, dtype=np.int64)[:, None] * SHARD_WIDTH
            + valued[None, :]).ravel()
    ds.fields = [{"name": n, "options": {"type": "set"}} for n in cats]
    ds.fields += [{"name": n, "options": {"type": "int", "min": s["min"],
                                          "max": s["max"]}}
                  for n, s in ints.items()]
    ds.codes = codes
    ds.values = {n: (cols, vals[n]) for n in ints}
    ds.n_rows = {n: s["rows"] for n, s in cats.items()}
    # a plane's set bits lie evenly over the valued columns: array
    # containers (2 B a bit) while that is under a dense plane's bytes
    dense = ds.n_cols // 8
    for n, s in ints.items():
        v = vals[n] - s["min"]
        depth = int(s["max"] - s["min"]).bit_length()
        bits = [len(v)] + [int(((v >> b) & 1).sum()) for b in range(depth)]
        ds.int_bytes[n] = sum(min(2 * k, dense) for k in bits)
    for name, spec in cats.items():
        # expected bits a container of each row: array under 4096
        per_cont = distribution(spec) * 65536
        ds.row_bytes[name] = (np.where(per_cont <= 4096, 2 * per_cont, 8192)
                              * (ds.n_cols >> 16)).astype(np.int64)
    return ds
