"""The result cache's key is canonical in operand order (ISSUE 42).

The tree's one walk (``parallel/prepared.py``) sorts the operands of Union / Intersect / Xor, and
those of Difference after its first, before they join the key, so the
two written orders of one tree are one entry and one launch.  Every
case goes through ``Executor.execute`` on a small holder and reads the
flight record: a hit is ``cached`` with ``deviceLaunches`` 0.

What the cell ``seg-dense`` cannot see is here: two fields and two
views under one tree (the stamp has to be canonical with the key, or
the two orders evict each other for ever), a write between the two
orders, and siblings that tuple comparison cannot order."""

from __future__ import annotations

import itertools
import json
import os
import random

import numpy as np
import pytest

from perfbench import oracle
from perfbench.bits import pack_bool
from perfbench.querygen.set_trees import _fill, _slots
from pilosa_tpu.models import FieldOptions
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.parallel.executor import ExecOptions, Executor
from pilosa_tpu.runtime import resultcache
from pilosa_tpu.shardwidth import SHARD_WIDTH
from tests.naive import NaiveBitmap
from tests.test_cache_reckoning import by_rule

N_SHARDS = 4
N_COLS = N_SHARDS * SHARD_WIDTH
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def ex(tmp_path):
    """Two set fields (rows 0-3, each 300 draws from the first 600
    columns of the four shards, so that rows meet), an int field, a
    time field and a keyed field, with existence tracked."""
    holder = Holder(str(tmp_path / "h"))
    idx = holder.create_index("i")
    rng = random.Random(42)
    for name in ("f", "g"):
        rows, cols = [], []
        for row in range(4):
            for _ in range(300):
                rows.append(row)
                cols.append(rng.randrange(N_SHARDS) * SHARD_WIDTH
                            + rng.randrange(600))
        idx.create_field(name).import_bits(rows, cols)
        idx.import_existence(cols)
    idx.create_field("v", FieldOptions.int_field(0, 1000))
    idx.create_field("t", FieldOptions.time_field("YMD"))
    idx.create_field("k", FieldOptions(keys=True))
    ex = Executor(holder)
    for col in (s * SHARD_WIDTH + c for s in range(N_SHARDS)
                for c in range(0, 600, 5)):
        ex.execute("i", f"Set({col}, v={col % 1000})")
        ex.execute("i", f"Set({col}, t=1, 2018-0{1 + col % 3}-01T00:00)")
        ex.execute("i", f"Set({col}, k='x{col % 2}')")
    yield ex
    holder.close()


def _read(ex, q):
    """(answer, the read's flight record)."""
    got = ex.execute("i", q)[0]
    return got, ex.recorder.recent_records()[-1].to_dict()


def _fresh(ex, q):
    return ex.execute("i", q, opt=ExecOptions(cache=False))[0]


def _assert_one_entry(ex, first, second):
    """``second`` is served from ``first``'s entry, launches nothing
    and answers what a forced execution of either answers."""
    a, ra = _read(ex, first)
    b, rb = _read(ex, second)
    assert ra["cached"] is False and ra["deviceLaunches"] > 0
    assert rb["cached"] is True and rb["deviceLaunches"] == 0, rb
    assert rb["cacheKey"] == ra["cacheKey"]
    assert a == b == _fresh(ex, first) == _fresh(ex, second)
    assert resultcache.cache().stats_dict()["invalidations"] == 0
    return a


# ------------------------------------------------------- (1) the rule


COMMUTED = {
    "Intersect": ("Count(Intersect(Row(f=1), Row(g=2)))",
                  "Count(Intersect(Row(g=2), Row(f=1)))"),
    "Union": ("Count(Union(Row(f=1), Row(g=2), Row(f=3)))",
              "Count(Union(Row(f=3), Row(f=1), Row(g=2)))"),
    "Xor": ("Count(Xor(Row(f=0), Row(f=2)))",
            "Count(Xor(Row(f=2), Row(f=0)))"),
    "Difference-tail": (
        "Count(Difference(Row(f=1), Row(g=2), Row(g=0), Row(f=3)))",
        "Count(Difference(Row(f=1), Row(f=3), Row(g=2), Row(g=0)))"),
    "nested": (
        "Count(Union(Intersect(Row(f=1), Row(f=2)), Row(g=3)))",
        "Count(Union(Row(g=3), Intersect(Row(f=2), Row(f=1))))"),
    "under-Not-and-Shift": (
        "Count(Not(Union(Row(f=1), Shift(Xor(Row(g=1), Row(g=2)), n=1))))",
        "Count(Not(Union(Shift(Xor(Row(g=2), Row(g=1)), n=1), Row(f=1))))"),
}


@pytest.mark.parametrize("case", list(COMMUTED))
def test_other_operand_order_is_a_hit(ex, case):
    first, second = COMMUTED[case]
    assert _assert_one_entry(ex, first, second) > 0
    s = resultcache.cache().stats_dict()
    assert (s["entries"], s["hits"], s["fills"]) == (1, 1, 1)
    # at least one of the two written orders is not the key's own
    assert s["reordered"] in (1, 2)


def test_difference_keeps_its_first_operand(ex):
    """Difference(a, b) and Difference(b, a) are two answers and stay
    two keys; so do a tree and the same rows under another operator."""
    ab, rab = _read(ex, "Count(Difference(Row(f=1), Row(f=2)))")
    ba, rba = _read(ex, "Count(Difference(Row(f=2), Row(f=1)))")
    assert rba["cached"] is False and rba["deviceLaunches"] > 0
    assert rba["cacheKey"] != rab["cacheKey"]
    assert ab != ba
    assert ab == _fresh(ex, "Count(Difference(Row(f=1), Row(f=2)))")
    assert ba == _fresh(ex, "Count(Difference(Row(f=2), Row(f=1)))")
    x, rx = _read(ex, "Count(Xor(Row(f=2), Row(f=1)))")
    assert rx["cached"] is False and x not in (ab, ba)
    assert resultcache.cache().stats_dict()["reordered"] <= 1


def test_no_rewriting_beyond_operand_order(ex):
    """Nested same operators are not flattened: one rule only."""
    flat, _ = _read(ex, "Count(Intersect(Row(f=1), Row(f=2), Row(g=1)))")
    nested, rec = _read(
        ex, "Count(Intersect(Intersect(Row(f=1), Row(f=2)), Row(g=1)))")
    assert rec["cached"] is False
    assert flat == nested


def test_reordered_is_noted_on_the_probe_span_and_published(ex):
    first, second = COMMUTED["Intersect"]
    probes = []
    for q in (first, second):
        _, rec = _read(ex, q)
        probes += [s for s in rec["spans"] if s["name"] == "cache.probe"]
    assert len(probes) == 2
    assert sorted(bool(s.get("reordered")) for s in probes) == [False, True]
    assert resultcache.cache().debug()["reordered"] == 1
    from pilosa_tpu.stats import MemStatsClient

    stats = MemStatsClient()
    resultcache.cache().publish_gauges(stats)
    assert stats.snapshot()["cache.reordered"] == 1


# ------------------------------------ (2) the stamp follows the key


TWO_VIEWS = {
    "Intersect": ["Row(f=1)", "Row(v > 100)", "Row(g=2)"],
    "Union": ["Row(v < 500)", "Row(f=0)",
              "Row(t=1, from='2018-01-01T00:00', to='2018-03-01T00:00')"],
    "Xor": ["Row(g=1)", "Difference(Row(f=1), Row(v >= 10), Row(g=0))",
            "Row(f=2)"],
    "Difference": ["Row(f=1)", "Row(v >= 10)", "Row(g=0)", "Row(g=3)"],
}


def _orders(op: str) -> list[str]:
    """Every order of the operands that the rule calls the same tree
    (Difference: the first stays)."""
    keep = op == "Difference"
    parts = TWO_VIEWS[op]
    return [f"{op}({', '.join(parts[:keep] + list(p))})"
            for p in itertools.permutations(parts[keep:])]


@pytest.mark.parametrize("kind", ["count", "row"])
@pytest.mark.parametrize("op", list(TWO_VIEWS))
def test_two_fields_two_views_never_invalidate_each_other(ex, op, kind):
    """Fields f and g and the BSI / time views under one tree, every
    order, three rounds: one fill, every later read a hit, no entry
    dropped.  A stamp in traversal order would read (f, g) against
    (g, f) as a write and refill on every change of order."""
    orders = _orders(op)
    assert len(orders) == 6
    form = "Count({})" if kind == "count" else "{}"
    seen = []
    for _ in range(3):
        for o in orders:
            got, rec = _read(ex, form.format(o))
            seen.append((rec["cached"], rec["deviceLaunches"] == 0))
            if kind == "row":
                got = list(got.columns())
            want = _fresh(ex, form.format(orders[0]))
            assert got == (list(want.columns()) if kind == "row" else want)
    assert seen[0] == (False, False)
    assert all(s == (True, True) for s in seen[1:])
    s = resultcache.cache().stats_dict()
    assert (s["invalidations"], s["fills"], s["entries"]) == (0, 1, 1)
    assert s["hits"] == 3 * len(orders) - 1


# --------------------------------- (3) stamp-before-read still holds


@pytest.mark.parametrize("leaf", ["f", "g", "v"])
def test_write_between_the_two_orders_recomputes(ex, leaf):
    first = "Count(Intersect(Row(f=1), Row(g=2), Row(v >= 0)))"
    second = "Count(Intersect(Row(v >= 0), Row(g=2), Row(f=1)))"
    col = 3 * SHARD_WIDTH + 601
    before, _ = _read(ex, first)
    # the column enters the intersection with the last of three writes
    writes = {"f": f"Set({col}, f=1)", "g": f"Set({col}, g=2)",
              "v": f"Set({col}, v=7)"}
    for name, w in writes.items():
        if name != leaf:
            ex.execute("i", w)
    mid, rec = _read(ex, second)
    assert rec["cached"] is False and mid == before
    ex.execute("i", writes[leaf])
    after, rec = _read(ex, first if leaf == "g" else second)
    assert rec["cached"] is False and rec["deviceLaunches"] > 0
    assert after == before + 1 == _fresh(ex, first)
    again, rec = _read(ex, second if leaf == "g" else first)
    assert rec["cached"] is True and again == after


# ------------------------- (4) siblings tuple comparison cannot order


MIXED = [
    "Row(f=1)",
    "Row(v > 100)",
    "Row(v >< [10, 900])",
    "Row(v != null)",
    "Row(v != 5)",
    "Row(t=1, from='2018-01-01T00:00', to='2018-03-01T00:00')",
    "Not(Row(g=2))",
    "Row(k='x1')",
    "Union(Row(g=1), Row(f=0))",
    "Shift(Row(g=3), n=2)",
]


@pytest.mark.parametrize("seed", range(4))
def test_mixed_siblings_sort_without_raising(ex, seed):
    """A plain row, range leaves whose values are an int, a tuple and
    None, a time-range leaf, a Not, a string-keyed row, a nested
    operator and a Shift under one Intersect: ``sorted`` over their
    signatures as tuples raises TypeError (``!= null`` beside ``!= 5``
    compares None with an int), which the probe would swallow as "no
    signature" and never cache; the key's order does not raise, and
    every order is one key."""
    other = MIXED[:]
    random.Random(seed).shuffle(other)
    first = f"Count(Intersect({', '.join(MIXED)}))"
    second = f"Count(Intersect({', '.join(other)}))"
    _assert_one_entry(ex, first, second)


def test_int_and_string_row_ids_sort_without_raising(ex):
    """What a tree's ``sig`` can hold at one level and Python cannot
    compare: a range value None beside an int (a string row id never
    reaches a key: a key is translated or the tree does not fuse).  The order is total and the same for every written
    order."""
    from pilosa_tpu.pql import parse

    idx = ex.holder.index("i")
    kids = ["Row(f=1)", "Row(v != 5)", "Row(v > 5)", "Row(v != null)",
            "Row(v >< [1, 9])", "Not(Row(g=1))"]
    sigs = set()
    for perm in itertools.permutations(kids):
        call = parse(f"Intersect({', '.join(perm)})").calls[0]
        tree = ex._prepare(idx, call)
        assert tree.fused
        sigs.add(tree.sig)
    assert len(sigs) == 1
    with pytest.raises(TypeError):
        sorted(next(iter(sigs))[1:])


# ------------------------------------------------- (5) the row kind


@pytest.mark.parametrize("op", ["Intersect", "Union", "Xor", "Difference"])
def test_cached_row_of_the_other_order_equals_the_oracle(ex, op):
    rows = {}
    for field, row in (("f", 1), ("g", 2), ("f", 3)):
        cols = ex.execute("i", f"Row({field}={row})",
                          opt=ExecOptions(cache=False))[0].columns()
        rows[field, row] = NaiveBitmap(cols, nbits=N_COLS)
    a, b, c = rows["f", 1], rows["g", 2], rows["f", 3]
    want = {"Intersect": a.intersect(b).intersect(c),
            "Union": a.union(b).union(c),
            "Xor": a.xor(b).xor(c),
            "Difference": a.difference(b).difference(c)}[op]
    first = f"{op}(Row(f=1), Row(g=2), Row(f=3))"
    second = f"{op}(Row(f=1), Row(f=3), Row(g=2))"
    got1, rec1 = _read(ex, first)
    got2, rec2 = _read(ex, second)
    assert rec1["cached"] is False
    assert rec2["cached"] is True and rec2["deviceLaunches"] == 0
    assert [int(x) for x in got1.columns()] == want.positions()
    assert [int(x) for x in got2.columns()] == want.positions()
    # a served bitmap is a copy: a caller's edit never reaches the entry
    got2.segments.clear()
    got3, rec3 = _read(ex, first)
    assert rec3["cached"] is True
    assert [int(x) for x in got3.columns()] == want.positions()


# ------------------------ (6) the cell's shapes, operands shuffled


with open(os.path.join(ROOT, "perfbench", "traffic", "seg-dense.json")) as _f:
    SHAPES = json.load(_f)["params"]["shapes"]
DEMO_ROWS, DEMO_SHARDS = 16, 2


class _Rows:
    """What ``perfbench/oracle.py`` reads of a dataset for a Count."""

    def __init__(self, masks):
        self.words = [pack_bool(m) for m in masks]
        self.n_words = len(self.words[0])

    def row(self, field, r):
        return self.words[r]


@pytest.fixture(scope="module")
def demo(tmp_path_factory):
    rng = np.random.default_rng(42)
    masks = rng.random((DEMO_ROWS, DEMO_SHARDS * SHARD_WIDTH)) < 0.3
    holder = Holder(str(tmp_path_factory.mktemp("demo") / "h"))
    f = holder.create_index("i").create_field("demo")
    rows, cols = np.nonzero(masks)
    f.import_bits(rows.tolist(), cols.tolist())
    yield Executor(holder), _Rows(masks)
    holder.close()


def _shuffled(b, rng):
    if b[0] == "row":
        return b
    kids = [_shuffled(x, rng) for x in b[1:]]
    keep = 1 if b[0] == "andnot" else 0
    tail = kids[keep:]
    rng.shuffle(tail)
    return [b[0]] + kids[:keep] + tail


@pytest.mark.parametrize("shape", SHAPES, ids=json.dumps)
def test_cell_shapes_equal_keys_imply_equal_answers(demo, shape):
    """``seg-dense``'s 14 shapes over rows drawn like the cell's, each
    tree in three shuffles of its operands: the program's key is the
    same exactly when the rule's canonical form is, every answer is the
    numpy oracle's count, and a shuffle of a tree already read is a
    hit."""
    ex, ds = demo
    rng = random.Random(json.dumps(shape))
    by_key: dict[str, tuple] = {}
    while len(by_key) < 4:
        tree = _fill(shape, "demo",
                     rng.sample(range(DEMO_ROWS), _slots(shape)))
        if any(by_rule(tree) == c for c, _ in by_key.values()):
            continue
        want = oracle.answer(ds, ["count", tree])
        for i in range(3):
            q = ["count", _shuffled(tree, rng) if i else tree]
            got, rec = _read(ex, oracle.pql(q))
            assert got == want, oracle.pql(q)
            assert rec["cached"] is (i > 0), oracle.pql(q)
            seen = by_key.setdefault(rec["cacheKey"],
                                     (by_rule(tree), want))
            assert seen == (by_rule(q[1]), want)
    assert len(by_key) == len({c for c, _ in by_key.values()}) == 4
    assert resultcache.cache().stats_dict()["invalidations"] == 0
