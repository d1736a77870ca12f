"""The trees of ``tests/test_prepared_read.py`` and the index they read.

Shared by the test and by the one-off recorder that wrote
``tests/prepared_golden.json`` from the parent commit's five walks
(``_translate_call_rec``, ``_fused_supported``, ``_fused_shape``,
``containers._walk``, ``_rc_sig``) before ISSUE 44 deleted them: the
14 shapes of ``perfbench/traffic/seg-dense.json`` and one tree each
with a keyed row, a compressed leaf, a time range, ``Not``, ``Shift``
and a pending delta.  Everything is drawn from fixed seeds, so the
golden values hold for any later run."""

from __future__ import annotations

import datetime as dt
import json
import os

import numpy as np

from pilosa_tpu import ingest
from pilosa_tpu.models.field import FieldOptions
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.parallel.executor import Executor
from pilosa_tpu.shardwidth import SHARD_WIDTH

N_SHARDS = 3
SHARDS = tuple(range(N_SHARDS))
#: the rows that fill slots 0..4 of a shape: out of order on purpose,
#: so the cache key has operands to move (11 sorts before 2 by repr)
SLOT_ROWS = (7, 2, 11, 4, 1)
DELTA_ROW = 9
SPARSE_ROW = 20
_NAMES = {"and": "Intersect", "or": "Union", "andnot": "Difference",
          "xor": "Xor"}
_TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench", "traffic", "seg-dense.json")


def _pql(shape) -> str:
    kids = [_pql(x) if isinstance(x, list) else f"Row(demo={SLOT_ROWS[x]})"
            for x in shape[1:]]
    return f"{_NAMES[shape[0]]}({', '.join(kids)})"


def cases() -> dict[str, str]:
    """name -> the Count's child, as PQL."""
    with open(_TRAFFIC) as fh:
        shapes = json.load(fh)["params"]["shapes"]
    out = {f"shape{i:02d}": _pql(s) for i, s in enumerate(shapes)}
    assert len(out) == 14
    out["keyed"] = 'Intersect(Row(seg="gold"), Row(demo=2))'
    out["keyed_missing"] = 'Union(Row(seg="nobody"), Row(demo=2))'
    out["compressed"] = f"Intersect(Row(demo={SPARSE_ROW}), Row(demo=2))"
    out["time"] = ("Union(Row(t=1, from='2019-01-02T00:00', "
                   "to='2019-01-05T00:00'), Row(demo=4))")
    out["not"] = "Intersect(Not(Row(demo=7)), Row(demo=1))"
    out["shift"] = "Union(Shift(Row(demo=2), n=3), Row(demo=11))"
    out["delta"] = f"Intersect(Row(demo={DELTA_ROW}), Row(demo=4))"
    return out


def build(path: str):
    """-> (executor, idx, bits): ``bits[(field, row)]`` is the set of
    columns the naive oracle reads; ``bits["exists"]`` the existence
    row; ``bits[("t", 1)]`` maps a day to its columns."""
    ingest.configure(delta_enabled=False)
    holder = Holder(path)
    idx = holder.create_index("i")
    rng = np.random.default_rng(44)
    n = N_SHARDS * SHARD_WIDTH
    bits: dict = {}
    demo = idx.create_field("demo")
    for row in range(16):
        cols = np.flatnonzero(rng.random(n) < 0.30 + 0.02 * row)
        bits[("demo", row)] = set(cols.tolist())
        demo.import_bits([row] * len(cols), cols.tolist())
    cols = np.flatnonzero(rng.random(n) < 0.03)
    bits[("demo", SPARSE_ROW)] = set(cols.tolist())
    demo.import_bits([SPARSE_ROW] * len(cols), cols.tolist())
    seg = idx.create_field("seg", FieldOptions.set_field(keys=True))
    gold = seg.translate_store.translate_key("gold", create=True)
    cols = np.flatnonzero(rng.random(n) < 0.35)
    bits[("seg", "gold")] = set(cols.tolist())
    seg.import_bits([gold] * len(cols), cols.tolist())
    t = idx.create_field("t", FieldOptions.time_field("YMD"))
    days: dict = {}
    for day in range(1, 8):
        cols = rng.integers(0, n, 40).tolist()
        days[day] = set(cols)
        for c in cols:
            t.set_bit(1, c, dt.datetime(2019, 1, day, 12))
    bits[("t", 1)] = days
    exists = set().union(*(v for k, v in bits.items() if k != ("t", 1)),
                         *days.values())
    idx.import_existence(sorted(exists))
    bits["exists"] = exists
    # the pending delta: one acknowledged write that no compaction has
    # merged, on a row no other case reads
    ingest.configure(delta_enabled=True)
    ex = Executor(holder)
    col = next(c for c in range(n) if c not in bits[("demo", DELTA_ROW)])
    assert ex.execute("i", f"Set({col}, demo={DELTA_ROW})") == [True]
    assert demo.delta_pending(DELTA_ROW, SHARDS)
    bits[("demo", DELTA_ROW)].add(col)
    exists.add(col)
    bits["exists"] = exists
    return ex, idx, bits
