"""The view write token (pilosa_tpu/stagecheck.py): a cached stack is
validated in O(1) while nothing in its view was written, every
mutation entry point sends the next read back to the per-fragment
tokens, and no read is ever older than the last acknowledged write.

The benchmark's cell has no write in its window, so its ``correct``
cannot see a missed invalidation: these tests hold the change to
read-your-writes.  Answers are compared with tests/naive.py.
"""

from __future__ import annotations

import sys
import threading

import numpy as np
import pytest

from pilosa_tpu import ingest, stagecheck
from pilosa_tpu.ingest import compactor
from pilosa_tpu.models.fragment import Fragment
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.models.field import FieldOptions
from pilosa_tpu.models.view import View
from pilosa_tpu.ops import containers as ct
from pilosa_tpu.parallel import meshexec
from pilosa_tpu.parallel.coalescer import Coalescer
from pilosa_tpu.parallel.executor import ExecOptions, Executor
from pilosa_tpu.pql import parse
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.storage import roaring
from tests.naive import NaiveBitmap

W = SHARD_WIDTH
N_SHARDS = 8
SHARDS = tuple(range(N_SHARDS))
ONE = 3  # the ONE shard every mutation lands in
ROWS = (1, 2, 3)
SET_TREE = "Count(Intersect(Row(f=1), Union(Row(f=2), Row(f=3))))"
RANGE_TREE = "Count(Intersect(Row(f=1), Row(v > 5)))"

#: the three ways a Count reaches the cached builders; between them
#: they read all four (and the plane stack behind a range leaf)
READERS = {
    # _fused_expr: device_delta_stacks + device_row_stack
    "dense": ExecOptions(cache=False, containers=False),
    # containers.plan_fused: delta_pending + device_container_leaf
    "plan": ExecOptions(cache=False),
    # Coalescer.count -> containers.stage_vm:
    # device_delta_container_leaves + device_container_leaf
    "vm": ExecOptions(cache=False, mesh=False),
}


class World:
    """One index with the oracle beside it: ``bits[row]`` is the set
    of columns of field ``f``, ``vals[col]`` the value of field ``v``."""

    def __init__(self):
        self.holder = Holder(None)
        self.idx = self.holder.create_index("i")
        self.f = self.idx.create_field("f")
        self.v = self.idx.create_field("v", FieldOptions.int_field(0, 100))
        rng = np.random.default_rng(29)
        # 512 columns a shard, so that rows overlap and every mutation
        # below moves the answer
        universe = np.array([s * W + k for s in SHARDS for k in range(512)])
        self.bits = {}
        for row in ROWS:
            cols = rng.choice(universe, size=1200, replace=False)
            self.bits[row] = {int(c) for c in cols}
            self.f.import_bits([row] * len(cols), cols)
        vcols = rng.choice(universe, size=2000, replace=False)
        self.vals = {int(c): int(x) for c, x in
                     zip(vcols, rng.integers(0, 12, size=len(vcols)))}
        self.v.import_values(list(self.vals), list(self.vals.values()))
        self.ex = Executor(self.holder)

    def reader(self, name: str):
        self.ex.coalescer = (Coalescer(window_s=0.0, enabled=True)
                             if name == "vm" else None)
        return READERS[name]

    def expect(self, tree: str) -> int:
        n = N_SHARDS * W
        row = {r: NaiveBitmap(c, n) for r, c in self.bits.items()}
        if tree == SET_TREE:
            return row[1].intersect(row[2].union(row[3])).count()
        over = NaiveBitmap((c for c, x in self.vals.items() if x > 5), n)
        return row[1].intersect(over).count()

    def count(self, tree: str, opt) -> tuple[int, int]:
        """(answer, builder calls that walked the shards)."""
        mark = stagecheck.mark()
        got = self.ex.execute("i", tree, opt=opt)[0]
        return got, stagecheck.mark() - mark

    def stage_span(self) -> dict:
        """The ``_fused_expr`` stage span of the last read."""
        spans = self.ex.recorder.recent_records()[-1].to_dict()["spans"]
        return [s for s in spans
                if s["name"] == "stage" and "fast" in s
                and not s.get("vm")][-1]

    def in_one(self, want: set, avoid: set = frozenset()) -> list[int]:
        """The columns of shard ONE in ``want`` and not in ``avoid``."""
        cols = sorted(c for c in want - avoid if c // W == ONE)
        assert cols
        return cols

    def drop_shard_one(self) -> None:
        for r in self.bits:
            self.bits[r] = {c for c in self.bits[r] if c // W != ONE}

    def close(self):
        self.holder.close()


@pytest.fixture
def world():
    w = World()
    yield w
    w.close()


# --------------------------------------------------------------------
# the mutation matrix: (set-up before the warm reads, the mutation)
# --------------------------------------------------------------------


def _delta_on(w):
    compactor.reset()
    ingest.configure(delta_enabled=True)


def m_set_bit(w):
    c = w.in_one(w.bits[2] | w.bits[3], avoid=w.bits[1])[0]
    assert w.f.set_bit(1, c)
    w.bits[1].add(c)


def m_clear_bit(w):
    c = w.in_one(w.bits[1] & w.bits[2], avoid=w.bits[3])[0]
    assert w.f.clear_bit(2, c)
    w.bits[2].discard(c)


def m_import_bits(w):
    cols = w.in_one(w.bits[1], avoid=w.bits[2] | w.bits[3])[:20]
    w.f.import_bits([3] * len(cols), cols)
    w.bits[3].update(cols)


def m_import_roaring(w):
    # as API.import_roaring applies it: one shard's fragment, the
    # payload in fragment position space (row * width + offset)
    cols = w.in_one(w.bits[2] | w.bits[3], avoid=w.bits[1])[:40]
    offs = np.array(cols, dtype=np.int64) - ONE * W
    keys, words = roaring.positions_to_containers(1 * W + offs)
    frag = w.f.view("standard").create_fragment_if_not_exists(ONE)
    frag.import_roaring(roaring.encode(keys, words))
    w.bits[1].update(cols)


def m_delta_write(w):
    # [ingest] on: the write lands in the delta plane, _gen stays
    _delta_on(w)
    gen = w.f.view("standard").fragment(ONE)._gen
    m_set_bit(w)
    fr = w.f.view("standard").fragment(ONE)
    assert fr._gen == gen and fr._delta_row_seq(1)


def s_pending_delta(w):
    _delta_on(w)
    m_set_bit(w)


def m_flush_deltas(w):
    assert w.f.flush_deltas() > 0
    assert w.f.view("standard").fragment(ONE)._delta is None


def m_delete_fragment(w):
    assert w.f.view("standard").delete_fragment(ONE)
    w.drop_shard_one()


def m_replace_fragment(w):
    # what a resize re-fetch or a restore can produce: a NEW object in
    # the slot, same generation, other content
    view = w.f.view("standard")
    old = view.fragments[ONE]
    new = Fragment(None, "i", "f", "standard", ONE)
    cols = [ONE * W + k for k in range(7, 77)]
    for c in cols:
        new.set_bit(1, c)
        new.set_bit(2, c)
    new._gen = old._gen
    view.fragments[ONE] = new
    w.drop_shard_one()
    w.bits[1].update(cols)
    w.bits[2].update(cols)


def m_threshold(w):
    # a [containers] setting the compressed leaves froze
    ct.configure(threshold=ct.config().threshold / 2)


def m_placement(w):
    # the mesh goes away: every stack is laid out for the old plan
    assert meshexec.placement_token() != "dev"
    meshexec.configure(enabled=False)


def _low(w) -> set:
    """Columns of row 1 whose value is absent or at most 5."""
    return {c for c in w.bits[1] if w.vals.get(c, 0) <= 5}


def m_set_value(w):
    c = w.in_one(_low(w))[0]
    w.v.set_value(c, 9)
    w.vals[c] = 9


def m_import_values(w):
    cols = w.in_one(_low(w))[:10]
    w.v.import_values(cols, [11] * len(cols))
    w.vals.update((c, 11) for c in cols)


ALL = ("dense", "plan", "vm")
#: name -> (tree, set-up, mutation, readers whose first read after the
#: mutation must NOT be served by the fast check)
MUTATIONS = {
    "set_bit": (SET_TREE, None, m_set_bit, ALL),
    "clear_bit": (SET_TREE, None, m_clear_bit, ALL),
    "import_bits": (SET_TREE, None, m_import_bits, ALL),
    "import_roaring": (SET_TREE, None, m_import_roaring, ALL),
    "delta_write": (SET_TREE, None, m_delta_write, ALL),
    "flush_deltas": (SET_TREE, s_pending_delta, m_flush_deltas, ALL),
    "delete_fragment": (SET_TREE, None, m_delete_fragment, ALL),
    "replace_fragment": (SET_TREE, None, m_replace_fragment, ALL),
    "containers_threshold": (SET_TREE, None, m_threshold, ("plan", "vm")),
    "placement_token": (SET_TREE, None, m_placement, ALL),
    "set_value": (RANGE_TREE, None, m_set_value, ("dense",)),
    "import_values": (RANGE_TREE, None, m_import_values, ("dense",)),
}
CASES = [(m, r) for m, spec in MUTATIONS.items()
         for r in (ALL if spec[0] == SET_TREE else ("dense",))]


@pytest.mark.parametrize("mutation,reader", CASES,
                         ids=[f"{m}-{r}" for m, r in CASES])
def test_mutation_is_read_back_and_walks_once(world, mutation, reader):
    tree, setup, mutate, must_walk = MUTATIONS[mutation]
    w = world
    threshold, mesh_on = ct.config().threshold, meshexec.config().enabled
    try:
        if setup is not None:
            setup(w)
        # warm the four builders over the 8 shards, by hand and then
        # through the reader under test
        for row in ROWS:
            w.f.device_delta_stacks(row, SHARDS)
            w.f.device_row_stack(row, SHARDS)
            w.f.device_delta_container_leaves(row, SHARDS)
            w.f.device_container_leaf(row, SHARDS)
        opt = w.reader(reader)
        w.count(tree, opt)
        got, walks = w.count(tree, opt)
        assert got == w.expect(tree)
        assert walks == 0, "a warm read walked the shards"

        before = w.expect(tree)
        mutate(w)  # ... to ONE shard; the oracle follows
        if mutate not in (m_threshold, m_placement, m_flush_deltas):
            assert w.expect(tree) != before, "the mutation moves nothing"

        got, walks = w.count(tree, opt)
        assert got == w.expect(tree), "an acknowledged write was not read"
        if reader in must_walk:
            assert walks > 0, "served by the fast check after a write"
            if reader == "dense":
                assert w.stage_span()["fast"] < len(ROWS)
        got, walks = w.count(tree, opt)
        assert got == w.expect(tree)
        assert walks == 0, "the second read walked again"
        if reader == "dense":
            # every row leaf of the tree (a range leaf counts as one)
            span = w.stage_span()
            assert span["fast"] == (3 if tree == SET_TREE else 2)
    finally:
        ct.configure(threshold=threshold)
        meshexec.configure(enabled=mesh_on)


@pytest.mark.parametrize("delta", [False, True], ids=["base", "delta"])
def test_reader_never_behind_an_acknowledged_write(world, delta):
    """Two writers and one reader, a fixed number of iterations each:
    every count lies between the writes acknowledged before the read
    began and the writes begun before it ended."""
    w = world
    if delta:
        _delta_on(w)
    n = 120
    base = len(w.bits[1])
    begun = [0, 0]
    acked = [0, 0]
    free = [[c for c in range(s * W, s * W + W) if c not in w.bits[1]][:n]
            for s in (1, 6)]
    # the reader hands each writer one write per read and does not wait
    # for it: the writes race with the read that released them
    go = [threading.Semaphore(0), threading.Semaphore(0)]
    errors = []

    def writer(k):
        try:
            for c in free[k]:
                go[k].acquire()
                begun[k] += 1
                assert w.f.set_bit(1, c)
                acked[k] += 1
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    opts = [READERS["dense"], READERS["plan"]]
    for opt in opts:  # warm: there is an entry to go stale
        assert w.ex.execute("i", "Count(Row(f=1))", opt=opt)[0] == base
    threads = [threading.Thread(target=writer, args=(k,)) for k in (0, 1)]
    for t in threads:
        t.start()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # switch threads inside the builders
    try:
        for i in range(n):
            for sem in go:
                sem.release()
            lo = base + sum(acked)
            got = w.ex.execute("i", "Count(Row(f=1))", opt=opts[i % 2])[0]
            hi = base + sum(begun)
            assert lo <= got <= hi, (i, lo, got, hi)
    finally:
        sys.setswitchinterval(interval)
        for sem in go:  # a failed read must not leave a writer waiting
            sem.release(n)
        for t in threads:
            t.join(60)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert sum(acked) == 2 * n
    for opt in opts:
        got = w.ex.execute("i", "Count(Row(f=1))", opt=opt)[0]
        assert got == base + 2 * n


def test_warm_four_leaf_count_touches_no_fragment(monkeypatch):
    """No clock: with warm caches a 4-leaf Count over 128 one-bit
    shards looks no fragment up while it stages, its stage span says
    fast=4, and a delta write to ANOTHER row leaves the base stacks
    the same device buffers."""
    n = 128
    shards = tuple(range(n))
    holder = Holder(None)
    idx = holder.create_index("i")
    f = idx.create_field("f")
    for row in (1, 2, 3, 4):
        f.import_bits([row] * n, [s * W + row for s in range(n)])
    ex = Executor(holder)
    q = "Count(Union(Intersect(Row(f=1), Row(f=2)), Row(f=3), Row(f=4)))"
    opt = READERS["dense"]
    assert ex.execute("i", q, opt=opt)[0] == 2 * n

    calls = []
    fragment = View.fragment
    monkeypatch.setattr(
        View, "fragment",
        lambda self, shard: calls.append(shard) or fragment(self, shard))
    child = ex._prepare(idx, parse(q).calls[0].children[0])
    shape, leaves = ex._fused_expr(idx, child, shards)
    assert len(leaves) == 4 and calls == []
    assert ex.execute("i", q, opt=opt)[0] == 2 * n
    spans = ex.recorder.recent_records()[-1].to_dict()["spans"]
    [stage] = [s for s in spans if s["name"] == "stage"]
    assert stage["leaves"] == 4 and stage["fast"] == 4
    assert calls == []
    monkeypatch.undo()

    compactor.reset()
    ingest.configure(delta_enabled=True)
    assert f.set_bit(9, 5 * W + 1)  # lands in shard 5's delta plane
    after = ex._fused_expr(idx, child, shards)[1]
    assert all(a is b for a, b in zip(after, leaves)), \
        "a delta write to another row rebuilt a resident base stack"
    # each entry paid today's walk once, and is O(1) again
    mark = stagecheck.mark()
    again = ex._fused_expr(idx, child, shards)[1]
    assert stagecheck.mark() == mark
    assert all(a is b for a, b in zip(again, leaves))
    assert ex.execute("i", q, opt=opt)[0] == 2 * n
    holder.close()
