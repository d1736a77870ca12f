"""Process-wide device-memory residency: one byte budget across every
owner's device caches (fragment matrices/planes, field row/matrix
stacks), LRU eviction that only drops cache warmth, never correctness.
Reference analog: the global syswrap mmap/file caps (syswrap/os.go:41)."""

from __future__ import annotations

import random
import threading

import numpy as np
import pytest

from pilosa_tpu.models.holder import Holder
from pilosa_tpu.parallel.executor import Executor
from pilosa_tpu.runtime import residency
from pilosa_tpu.shardwidth import SHARD_WIDTH


# (per-test residency reset now lives in conftest.py's
# _hermetic_residency_accounting, applied suite-wide)


class TestManagerUnit:
    def test_admit_within_budget_keeps_all(self):
        m = residency.ResidencyManager(1000)
        c: dict = {}
        for i in range(5):
            c[i] = f"v{i}"
            m.admit(c, i, 100)
        assert len(c) == 5 and m.total == 500

    def test_lru_eviction_across_owners(self):
        m = residency.ResidencyManager(250)
        a: dict = {"x": 1}
        b: dict = {"y": 2}
        m.admit(a, "x", 100)
        m.admit(b, "y", 100)
        # touching a's entry makes b's the LRU victim
        m.touch(a, "x")
        c: dict = {"z": 3}
        m.admit(c, "z", 100)
        assert "x" in a and "y" not in b and "z" in c
        assert m.total == 200 and m.evictions == 1

    def test_replacement_does_not_double_count(self):
        m = residency.ResidencyManager(300)
        c: dict = {"k": 1}
        m.admit(c, "k", 200)
        c["k"] = 2
        m.admit(c, "k", 200)  # replacement, not addition
        assert m.total == 200 and m.evictions == 0

    def test_oversized_entry_bounds_total(self):
        """An entry larger than the whole budget reclaims everything
        else: total is bounded by max(budget, largest entry), never by
        the sum of giants (each giant evicts its predecessor)."""
        m = residency.ResidencyManager(100)
        a: dict = {"small": 1}
        m.admit(a, "small", 50)
        big: dict = {"huge": 2}
        m.admit(big, "huge", 500)
        assert "small" not in a and "huge" in big
        assert m.total == 500
        big2: dict = {"huge2": 3}
        m.admit(big2, "huge2", 600)
        assert "huge" not in big and "huge2" in big2
        assert m.total == 600

    def test_forget(self):
        m = residency.ResidencyManager(100)
        c: dict = {"k": 1}
        m.admit(c, "k", 60)
        del c["k"]
        m.forget(c, "k")
        assert m.total == 0

    def test_never_evicts_entry_being_admitted(self):
        m = residency.ResidencyManager(100)
        c: dict = {}
        c["a"] = 1
        m.admit(c, "a", 80)
        c["b"] = 2
        m.admit(c, "b", 90)  # over budget even after evicting "a"
        assert "b" in c and "a" not in c

    def test_thread_safety_smoke(self):
        m = residency.ResidencyManager(10_000)
        caches = [dict() for _ in range(4)]

        def worker(c, seed):
            rng = random.Random(seed)
            for i in range(300):
                k = rng.randrange(20)
                c[k] = i
                m.admit(c, k, rng.randrange(1, 200))
                if rng.random() < 0.3:
                    m.touch(c, k)
        ts = [threading.Thread(target=worker, args=(c, i))
              for i, c in enumerate(caches)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        s = m.stats()
        assert s["total"] <= 10_000 or s["entries"] == 1
        # accounting agrees with the dicts the manager still tracks
        assert s["entries"] <= sum(len(c) for c in caches)


class TestProductIntegration:
    def _build(self, tmp_path, name="i"):
        holder = Holder(str(tmp_path / name))
        idx = holder.create_index(name)
        f = idx.create_field("f")
        rng = random.Random(1)
        rows, cols = [], []
        for r in range(6):
            for _ in range(300):
                rows.append(r)
                cols.append(rng.randrange(4 * SHARD_WIDTH))
        f.import_bits(rows, cols)
        return holder, Executor(holder)

    def test_queries_exact_under_tiny_budget(self, tmp_path):
        """A budget far below the working set forces constant eviction;
        every query must still be exact (eviction = cold cache only)."""
        # small enough to force churn, big enough that single-fragment
        # matrices (~48 KB at the test shard width) fit and compete
        residency.reset(100 << 10)
        holder, ex = self._build(tmp_path)
        want_count = ex.execute("i", "Count(Row(f=1))")[0]
        for _ in range(3):
            assert ex.execute("i", "Count(Row(f=1))")[0] == want_count
            topn = ex.execute("i", "TopN(f)")[0]
            assert sum(p.count for p in topn) > 0
            gb = ex.execute("i", "GroupBy(Rows(f))")[0]
            assert {(gc.group[0].row_id): gc.count for gc in gb} == \
                {p.id: p.count for p in topn}
        assert residency.manager().evictions > 0
        holder.close()

    def test_churn_bit_exact_vs_host_with_high_water(self, tmp_path):
        """Eviction+rebuild cycles under a tiny budget: usage stays
        within the budget bound, evictions and the high-water mark are
        counted, and every device-path result stays bit-exact against
        a host (numpy) recomputation from the fragments' own rows —
        eviction may only ever cost warmth."""
        # this test exercises the device-residency rebuild cycle; the
        # result cache would answer the repeated passes without ever
        # touching the stacks being churned
        from pilosa_tpu.runtime import resultcache

        resultcache.cache().enabled = False
        residency.reset(100 << 10)
        holder, ex = self._build(tmp_path)
        f = holder.index("i").field("f")
        view = f.view("standard")

        def host_row_positions(row: int) -> set[int]:
            out = set()
            for shard, frag in view.fragments.items():
                arr = frag._rows.get(row)
                if arr is not None:
                    from pilosa_tpu.ops import bitmap as bm

                    out.update(int(p) + shard * SHARD_WIDTH
                               for p in bm.unpack_positions(arr))
            return out

        want = {r: host_row_positions(r) for r in range(6)}
        mgr = residency.manager()
        ev0 = mgr.evictions
        # round-robin distinct rows: the working set exceeds the
        # budget, so every pass rebuilds entries the last pass evicted
        for _ in range(3):
            for r in range(6):
                row = ex.execute("i", f"Row(f={r})")[0]
                assert {int(c) for c in row.columns()} == want[r]
                got = int(ex.execute("i", f"Count(Row(f={r}))")[0])
                assert got == len(want[r])
                s = mgr.stats()
                # bounded by the budget (modulo one oversized entry,
                # which this working set does not produce)
                assert s["total"] <= s["budget"]
                assert s["high_water"] >= s["total"]
        s = mgr.stats()
        assert mgr.evictions > ev0  # churn actually happened
        assert s["admits"] > 6  # rebuild cycles re-admitted entries
        assert s["high_water"] <= s["budget"]
        holder.close()

    def test_budget_bounds_total_across_fields(self, tmp_path):
        residency.reset(1 << 20)
        holder, ex = self._build(tmp_path)
        # churn several distinct query shapes to fill caches
        for q in ["Row(f=0)", "Row(f=1)", "TopN(f)", "Count(Row(f=2))",
                  "GroupBy(Rows(f))"]:
            ex.execute("i", q)
        s = residency.manager().stats()
        assert s["total"] <= max(s["budget"], 4 * SHARD_WIDTH // 8 * 8)
        holder.close()

    def test_close_releases_accounting(self, tmp_path):
        residency.reset(64 << 20)
        holder, ex = self._build(tmp_path)
        ex.execute("i", "TopN(f)")
        ex.execute("i", "Row(f=1)")
        before = residency.manager().stats()["total"]
        assert before > 0
        holder.close()
        # closing releases BOTH fragment and field-level device caches
        f = holder.index("i").field("f")
        view = f.view("standard")
        for frag in view.fragments.values():
            assert not frag._device_cache
        assert not f._row_stack_cache and not f._matrix_stack_cache
        assert residency.manager().stats()["total"] == 0
