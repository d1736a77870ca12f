"""Fused all-shards execution path: one stacked device computation must
produce results identical to the per-shard map (and actually engage for
eligible queries)."""

from __future__ import annotations

import random

import pytest

from pilosa_tpu.models.field import FieldOptions
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.models.row import Row
from pilosa_tpu.parallel.executor import Executor
from pilosa_tpu.shardwidth import SHARD_WIDTH

from tests.test_fuzz_stress import gen_query


@pytest.fixture
def ex(tmp_path):
    holder = Holder(str(tmp_path / "h"))
    idx = holder.create_index("i")
    rng = random.Random(42)
    for fi in range(3):
        f = idx.create_field(f"f{fi}")
        rows, cols = [], []
        for row in range(5):
            for _ in range(200):
                rows.append(row)
                cols.append(rng.randrange(6 * SHARD_WIDTH))
        f.import_bits(rows, cols)
        idx.import_existence(cols)
    yield Executor(holder)
    holder.close()


def _general(ex, q):
    """Force the per-shard path via the executor's master fuse switch."""
    ex.fuse_shards = False
    try:
        return ex.execute("i", q)
    finally:
        ex.fuse_shards = True


class TestFusedEquivalence:
    @pytest.mark.parametrize("q", [
        "Row(f0=1)",
        "Count(Row(f0=1))",
        "Count(Intersect(Row(f0=1), Row(f1=2)))",
        "Union(Row(f0=0), Row(f1=1), Row(f2=2))",
        "Count(Difference(Row(f0=1), Row(f1=1), Row(f2=1)))",
        "Count(Xor(Row(f0=3), Row(f2=4)))",
        "Count(Not(Row(f0=1)))",
        "Count(Union(Not(Row(f1=0)), Intersect(Row(f0=2), Row(f2=3))))",
    ])
    def test_matches_per_shard_path(self, ex, q):
        fused = ex.execute("i", q)[0]
        general = _general(ex, q)[0]
        assert fused == general  # Row.__eq__ compares segments exactly

    def test_randomized_equivalence(self, ex):
        rng = random.Random(3)
        for _ in range(40):
            q = gen_query(rng)
            fused = ex.execute("i", q)[0]
            general = _general(ex, q)[0]
            if isinstance(fused, Row):
                assert list(fused.columns()) == list(general.columns()), q
            else:
                assert fused == general, q

    def test_fused_path_engages(self, ex):
        # _fused_expr is the dense staging point of every fused path
        # (Count stages directly; Row/TopN/GroupBy go via _fused_eval);
        # sparse trees may stage through the compressed container
        # engine instead (ops/containers.plan_fused) — either one is
        # the fused path, and exactly one launch results either way
        from pilosa_tpu.ops import bitmap as bm

        calls = {"n": 0}
        orig = ex._fused_expr

        def spy(idx, call, shards, *a, **k):
            calls["n"] += 1
            return orig(idx, call, shards, *a, **k)

        ex._fused_expr = spy
        with bm.dispatch_counter() as dc:
            ex.execute("i", "Count(Intersect(Row(f0=1), Row(f1=2)))")
        engaged_dense = calls["n"] > 0
        engaged_compressed = "fused_gather" in dc.launches
        assert engaged_dense or engaged_compressed
        assert dc.n == 1, dc.launches

    def test_fused_support_surface(self, ex):
        # BSI conditions, time ranges, and Shift all fuse now
        idx = ex.holder.index("i")
        idx.create_field("v", FieldOptions.int_field(0, 100))
        idx.create_field("t", FieldOptions.time_field("YMD"))
        parse = __import__("pilosa_tpu.pql", fromlist=["parse"]).parse
        assert ex._prepare(
            idx, parse("Shift(Row(f0=1), n=1)").calls[0]).fused
        assert ex._prepare(idx, parse(
            "Row(t=1, from='2020-01-01T00:00', to='2021-01-01T00:00')"
        ).calls[0]).fused
        assert ex._prepare(idx, parse("Row(v > 3)").calls[0]).fused
        assert ex._prepare(idx, parse("Row(v >< [1, 5])").calls[0]).fused

    def test_fused_shift_matches_per_shard(self, ex):
        for q in ["Shift(Row(f0=1), n=1)",
                  "Shift(Row(f0=2), n=40)",
                  "Count(Shift(Union(Row(f0=1), Row(f1=2)), n=3))",
                  "Count(Intersect(Shift(Row(f0=1)), Row(f1=1)))"]:
            fused = ex.execute("i", q)[0]
            general = _general(ex, q)[0]
            if isinstance(fused, Row):
                assert list(fused.columns()) == list(general.columns()), q
            else:
                assert fused == general, q

    def test_fused_bsi_conditions_match_per_shard(self, ex):
        rng = random.Random(17)
        idx = ex.holder.index("i")
        idx.create_field("bv", FieldOptions.int_field(-300, 300))
        f = idx.field("bv")
        vals = {}
        for _ in range(250):
            vals[rng.randrange(6 * SHARD_WIDTH)] = rng.randrange(-300, 300)
        for c, v in vals.items():
            f.set_value(c, v)
        queries = [
            ("Row(bv > 50)", {c for c, v in vals.items() if v > 50}),
            ("Row(bv >= -10)", {c for c, v in vals.items() if v >= -10}),
            ("Row(bv < -50)", {c for c, v in vals.items() if v < -50}),
            ("Row(bv <= 0)", {c for c, v in vals.items() if v <= 0}),
            ("Row(bv == 7)", {c for c, v in vals.items() if v == 7}),
            ("Row(bv != 7)", {c for c, v in vals.items() if v != 7}),
            ("Row(bv >< [-40, 90])",
             {c for c, v in vals.items() if -40 <= v <= 90}),
            ("Row(bv > 400)", set()),         # out of declared range
            ("Row(bv < 400)", set(vals)),     # whole range -> not-null
            ("Row(bv != null)", set(vals)),
            ("Count(Intersect(Row(bv > 0), Row(f0=1)))", None),
        ]
        for q, want in queries:
            fused = ex.execute("i", q)[0]
            general = _general(ex, q)[0]
            if isinstance(fused, Row):
                got = set(int(c) for c in fused.columns())
                if want is not None:
                    assert got == want, q
                assert list(fused.columns()) == list(general.columns()), q
            else:
                assert fused == general, q

    def test_stack_sharded_over_device_mesh(self, ex):
        """Under the virtual 8-device mesh, fused stacks shard across
        devices (the multi-chip data-parallel path)."""
        import jax

        if len(jax.devices()) < 2:
            pytest.skip("single device")
        idx = ex.holder.index("i")
        f = idx.field("f0")
        stack = f.device_row_stack(1, tuple(range(6)))
        # padded to a device multiple and actually distributed
        assert stack.shape[0] % len(jax.devices()) == 0
        assert len(stack.sharding.device_set) == len(jax.devices())
        # count through the fused path is still exact vs per-shard
        fused = ex.execute("i", "Count(Row(f0=1))")[0]
        general = _general(ex, "Count(Row(f0=1))")[0]
        assert fused == general

    def test_fused_sum_matches_per_shard(self, ex):
        rng = random.Random(5)
        idx = ex.holder.index("i")
        ex.holder.index("i").create_field(
            "val", FieldOptions.int_field(-500, 1000))
        f = idx.field("val")
        oracle = {}
        for _ in range(400):
            oracle[rng.randrange(6 * SHARD_WIDTH)] = rng.randrange(-500, 1000)
        for c, v in oracle.items():
            f.set_value(c, v)

        fused = ex.execute("i", "Sum(field=val)")[0]
        assert (fused.val, fused.count) == (sum(oracle.values()),
                                            len(oracle))
        general = _general(ex, "Sum(field=val)")[0]
        assert (fused.val, fused.count) == (general.val, general.count)

        # filtered by a fused-supported bitmap
        filt_cols = set(list(oracle)[::2])
        f0 = idx.field("f0")
        f0.import_bits([9] * len(filt_cols), sorted(filt_cols))
        fused = ex.execute("i", "Sum(Row(f0=9), field=val)")[0]
        want = sum(v for c, v in oracle.items() if c in filt_cols)
        assert (fused.val, fused.count) == (want, len(filt_cols))
        general = _general(ex, "Sum(Row(f0=9), field=val)")[0]
        assert (general.val, general.count) == (want, len(filt_cols))

    def test_fused_min_max_matches_per_shard(self, ex):
        rng = random.Random(13)
        idx = ex.holder.index("i")
        idx.create_field("m", FieldOptions.int_field(-900, 900))
        f = idx.field("m")
        oracle = {}
        for _ in range(300):
            c = rng.randrange(6 * SHARD_WIDTH)
            oracle[c] = rng.randrange(-900, 900)
        for c, v in oracle.items():
            f.set_value(c, v)
        for q, want in [("Min(field=m)", min(oracle.values())),
                        ("Max(field=m)", max(oracle.values()))]:
            fused = ex.execute("i", q)[0]
            general = _general(ex, q)[0]
            assert fused.val == want, (q, fused.val, want)
            assert (fused.val, fused.count) == (general.val, general.count)
        # filtered variants
        filt_cols = set(list(oracle)[::3])
        f0 = idx.field("f0")
        f0.import_bits([8] * len(filt_cols), sorted(filt_cols))
        sub = [v for c, v in oracle.items() if c in filt_cols]
        for q, want in [("Min(Row(f0=8), field=m)", min(sub)),
                        ("Max(Row(f0=8), field=m)", max(sub))]:
            fused = ex.execute("i", q)[0]
            general = _general(ex, q)[0]
            assert fused.val == want, (q, fused.val, want)
            assert (fused.val, fused.count) == (general.val, general.count)

    def test_fused_min_max_all_negative_and_empty(self, ex):
        idx = ex.holder.index("i")
        idx.create_field("neg", FieldOptions.int_field(-100, 100))
        f = idx.field("neg")
        f.set_value(1, -5)
        f.set_value(SHARD_WIDTH + 2, -70)
        assert ex.execute("i", "Min(field=neg)")[0].val == -70
        assert ex.execute("i", "Max(field=neg)")[0].val == -5
        idx.create_field("empty", FieldOptions.int_field(0, 10))
        # ensure multiple shards exist in the index so the fused gate opens
        out = ex.execute("i", "Min(field=empty)")[0]
        assert (out.val, out.count) == (0, 0)

    def test_fused_sum_engages(self, ex):
        idx = ex.holder.index("i")
        idx.create_field("v2", FieldOptions.int_field(0, 100))
        idx.field("v2").set_value(1, 7)
        idx.field("v2").set_value(SHARD_WIDTH + 1, 9)
        hits = {"n": 0}
        orig = ex._fused_sum

        def spy(*a, **k):
            hits["n"] += 1
            return orig(*a, **k)

        ex._fused_sum = spy
        out = ex.execute("i", "Sum(field=v2)")[0]
        assert (out.val, out.count) == (16, 2)
        assert hits["n"] == 1

    def test_clustered_local_group_fuses(self, tmp_path):
        """In a cluster, the originating node's local shard group
        evaluates fused (remote nodes fuse on their own side).  The
        compressed container engine is disabled so the spied
        ``_fused_expr`` staging point is the one that must engage —
        the clustered batch_fn wiring under test is engine-agnostic."""
        from pilosa_tpu.api import API
        from pilosa_tpu.ops import containers as ct
        from tests.test_cluster import make_cluster

        was = ct.config().enabled
        ct.configure(enabled=False)
        try:
            self._clustered_local_group_fuses(tmp_path, API,
                                              make_cluster)
        finally:
            ct.configure(enabled=was)

    def _clustered_local_group_fuses(self, tmp_path, API, make_cluster):

        _, nodes = make_cluster(tmp_path, n=3, replica_n=1)
        nodes[0].create_index("i")
        nodes[0].create_field("i", "f")
        api = API(nodes[0])
        cols = [s * SHARD_WIDTH + s for s in range(9)]
        api.import_bits("i", "f", [1] * len(cols), cols)
        hits = {n.cluster.local_id: 0 for n in nodes}
        for nd in nodes:
            orig = nd.executor._fused_expr

            def spy(idx, call, shards, *a, _o=orig,
                    _id=nd.cluster.local_id, **k):
                hits[_id] += 1
                return _o(idx, call, shards, *a, **k)

            nd.executor._fused_expr = spy
        got = nodes[0].executor.execute("i", "Count(Row(f=1))")[0]
        assert got == len(cols)
        # the ORIGINATOR's local group must fuse (placement is
        # deterministic: node0 owns several of the 9 shards), not just
        # the remote nodes (which fuse via the non-clustered path)
        n0_local = len(nodes[0].cluster.local_shards("i", range(9)))
        assert n0_local > 1, "placement changed; pick more shards"
        assert hits["node0"] > 0, hits
        # aggregates use the same clustered local-group fusion
        from pilosa_tpu.models.field import FieldOptions

        nodes[0].create_field("i", "v", FieldOptions.int_field(0, 100))
        api.import_values("i", "v", cols, [5] * len(cols))
        sum_hits = {"n": 0}
        orig_sum = nodes[0].executor._fused_sum
        nodes[0].executor._fused_sum = (
            lambda *a, **k: (sum_hits.__setitem__("n", sum_hits["n"] + 1),
                             orig_sum(*a, **k))[1])
        out = nodes[0].executor.execute("i", "Sum(field=v)")[0]
        assert (out.val, out.count) == (5 * len(cols), len(cols))
        assert sum_hits["n"] > 0

    def test_cache_invalidation_on_write(self, ex):
        q = "Count(Row(f0=1))"
        before = ex.execute("i", q)[0]
        ex.execute("i", f"Set({3 * SHARD_WIDTH + 7}, f0=1)")
        after = ex.execute("i", q)[0]
        assert after == before + 1
        # and the new bit is visible in the fused Row too
        row = ex.execute("i", "Row(f0=1)")[0]
        assert 3 * SHARD_WIDTH + 7 in set(int(c) for c in row.columns())


class TestFusedTopNGroupBy:
    """The cross-shard fused TopN scan and the batched GroupBy walk must
    match the per-shard path bit for bit."""

    def test_fused_topn_matches_per_shard(self, ex):
        for q in [
            "TopN(f0)",
            "TopN(f0, n=3)",
            "TopN(f0, n=2, threshold=100)",
            "TopN(f0, ids=[1, 3])",
            "TopN(f0, Row(f1=2), n=4)",
            "TopN(f1, Intersect(Row(f0=1), Row(f2=3)))",
        ]:
            # per-shard oracle FIRST, then invalidate the TopN caches it
            # warmed: either order of warm caches would let one path
            # answer from the other's output — the comparison must pit
            # two INDEPENDENT computations against each other
            general = _general(ex, q)[0]
            for f in ex.holder.index("i").fields.values():
                view = f.view("standard")
                for frag in (view.fragments.values() if view else ()):
                    frag.topn_cache.invalidate()
            fused = ex.execute("i", q)[0]
            assert [(p.id, p.count) for p in fused] == \
                [(p.id, p.count) for p in general], q

    def test_fused_topn_engages_and_warms_caches(self, ex):
        calls = {"n": 0}
        orig = ex._fused_topn_counts

        def spy(*a, **k):
            calls["n"] += 1
            return orig(*a, **k)

        ex._fused_topn_counts = spy
        first = ex.execute("i", "TopN(f0)")[0]
        assert calls["n"] == 1
        # second run answers from the fragment caches: the fused counter
        # still runs but must not touch the device matrix stack
        stack_calls = {"n": 0}
        f = ex.holder.index("i").field("f0")
        orig_stack = f.device_matrix_stack

        def stack_spy(shards):
            stack_calls["n"] += 1
            return orig_stack(shards)

        f.device_matrix_stack = stack_spy
        second = ex.execute("i", "TopN(f0)")[0]
        assert stack_calls["n"] == 0
        assert [(p.id, p.count) for p in first] == \
            [(p.id, p.count) for p in second]

    def test_fused_topn_bsi_filter(self, ex):
        idx = ex.holder.index("i")
        idx.create_field("fv", FieldOptions.int_field(0, 1000))
        fv = idx.field("fv")
        rng = random.Random(5)
        for c in range(0, 6 * SHARD_WIDTH, 997):
            fv.set_value(c, rng.randrange(1000))
        q = "TopN(f0, Row(fv > 500))"
        fused = ex.execute("i", q)[0]
        general = _general(ex, q)[0]
        assert [(p.id, p.count) for p in fused] == \
            [(p.id, p.count) for p in general]

    def test_fused_topn_after_write_invalidation(self, ex):
        q = "TopN(f0, n=5)"
        before = ex.execute("i", q)[0]
        ex.execute("i", f"Set({4 * SHARD_WIDTH + 11}, f0=0)")
        after = {p.id: p.count for p in ex.execute("i", q)[0]}
        want = {p.id: p.count for p in _general(ex, q)[0]}
        assert after == want
        assert after != {p.id: p.count for p in before} or \
            0 not in {p.id for p in before}

    def test_groupby_batched_matches_oracle(self, ex):
        for q in [
            "GroupBy(Rows(f0))",
            "GroupBy(Rows(f0), Rows(f1))",
            "GroupBy(Rows(f0), Rows(f1), Rows(f2))",
            "GroupBy(Rows(f0), Rows(f1), limit=4)",
            "GroupBy(Rows(f0), Rows(f1), filter=Row(f2=2))",
        ]:
            fused = ex.execute("i", q)[0]
            general = _general(ex, q)[0]
            assert [([(fr.field, fr.row_id) for fr in gc.group], gc.count)
                    for gc in fused] == \
                [([(fr.field, fr.row_id) for fr in gc.group], gc.count)
                 for gc in general], q

    def test_groupby_python_set_oracle(self, ex, tmp_path):
        """Independent oracle: recompute one GroupBy from raw sets."""
        from pilosa_tpu.models.holder import Holder

        holder = Holder(str(tmp_path / "g"))
        idx = holder.create_index("g")
        rng = random.Random(9)
        sets = {"a": {}, "b": {}}
        for fname in sets:
            f = idx.create_field(fname)
            rows, cols = [], []
            for row in range(4):
                members = {rng.randrange(3 * SHARD_WIDTH)
                           for _ in range(150)}
                sets[fname][row] = members
                for c in members:
                    rows.append(row)
                    cols.append(c)
            f.import_bits(rows, cols)
        ex2 = Executor(holder)
        got = {
            tuple((fr.field, fr.row_id) for fr in gc.group): gc.count
            for gc in ex2.execute("g", "GroupBy(Rows(a), Rows(b))")[0]
        }
        want = {}
        for ra, sa in sets["a"].items():
            for rb, sb in sets["b"].items():
                c = len(sa & sb)
                if c:
                    want[(("a", ra), ("b", rb))] = c
        assert got == want
        holder.close()

    def test_clustered_topn_local_group_fuses(self, tmp_path):
        """Clustered TopN: the originator's local shard group goes
        through the fused stacked scan, and the distributed result is
        exact."""
        from pilosa_tpu.api import API
        from tests.test_cluster import make_cluster

        _, nodes = make_cluster(tmp_path, n=3, replica_n=1)
        nodes[0].create_index("i")
        nodes[0].create_field("i", "f")
        api = API(nodes[0])
        rng = random.Random(13)
        counts = {}
        rows, cols = [], []
        for row in range(5):
            want = rng.randrange(20, 80)
            members = set()
            while len(members) < want:
                members.add(rng.randrange(9 * SHARD_WIDTH))
            counts[row] = len(members)
            rows.extend([row] * len(members))
            cols.extend(members)
        api.import_bits("i", "f", rows, cols)
        n0_local = len(nodes[0].cluster.local_shards("i", range(9)))
        assert n0_local > 1, "placement changed; pick more shards"
        hits = {"n": 0}
        orig = nodes[0].executor._fused_topn_counts
        nodes[0].executor._fused_topn_counts = (
            lambda *a, **k: (hits.__setitem__("n", hits["n"] + 1),
                             orig(*a, **k))[1])
        got = nodes[0].executor.execute("i", "TopN(f)")[0]
        assert hits["n"] > 0, "local group did not use the fused TopN scan"
        want = sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))
        assert [(p.id, p.count) for p in got] == want

    def test_fused_time_range_matches_per_shard(self, ex):
        """Time-range Rows now fuse: per-view stacks OR on device; the
        result must match the per-shard row_time union bit for bit."""
        import datetime as dt
        import random as _random

        idx = ex.holder.index("i")
        idx.create_field("tt", FieldOptions.time_field("YMDH"))
        tt = idx.field("tt")
        rng = _random.Random(23)
        rows, cols, stamps = [], [], []
        oracle = {}
        for _ in range(600):
            c = rng.randrange(6 * SHARD_WIDTH)
            ts = dt.datetime(2019, rng.randrange(1, 13),
                             rng.randrange(1, 28), rng.randrange(24))
            rows.append(1)
            cols.append(c)
            stamps.append(ts)
            oracle.setdefault(c, []).append(ts)
        tt.import_bits(rows, cols, timestamps=stamps)
        queries = [
            ("2019-03-01T00:00", "2019-07-15T12:00"),
            ("2019-01-01T00:00", "2020-01-01T00:00"),
            ("2019-06-02T03:00", "2019-06-02T04:00"),
            (None, "2019-05-01T00:00"),
            ("2019-10-01T00:00", None),
        ]
        for frm, to in queries:
            args = ["tt=1"]
            if frm:
                args.append(f"from='{frm}'")
            if to:
                args.append(f"to='{to}'")
            q = f"Row({', '.join(args)})"
            fused = ex.execute("i", q)[0]
            general = _general(ex, q)[0]
            assert list(fused.columns()) == list(general.columns()), q
            # independent set oracle
            lo = dt.datetime.fromisoformat(frm) if frm else dt.datetime(1, 1, 1)
            hi = dt.datetime.fromisoformat(to) if to else dt.datetime(9999, 1, 1)
            want = sorted(c for c, tss in oracle.items()
                          if any(lo <= t < hi for t in tss))
            got = [int(c) for c in fused.columns()]
            assert got == want, (q, len(got), len(want))

    def test_fused_time_range_in_algebra(self, ex):
        import datetime as dt
        import random as _random

        idx = ex.holder.index("i")
        idx.create_field("tt", FieldOptions.time_field("YMD"))
        tt = idx.field("tt")
        rng = _random.Random(8)
        cols = [rng.randrange(6 * SHARD_WIDTH) for _ in range(300)]
        tt.import_bits([1] * len(cols), cols,
                       timestamps=[dt.datetime(2019, 1 + i % 12, 5)
                                   for i in range(len(cols))])
        q = ("Count(Intersect(Row(tt=1, from='2019-01-01T00:00', "
             "to='2019-07-01T00:00'), Row(f0=1)))")
        got = ex.execute("i", q)[0]
        assert got == _general(ex, q)[0]


class TestFusedExtremeRowAndRows:
    def test_fused_minrow_maxrow_matches_per_shard(self, ex):
        for q in ("MinRow(field=f0)", "MaxRow(field=f0)",
                  "MinRow(Row(f1=1), field=f0)",
                  "MaxRow(Row(f1=1), field=f0)"):
            assert ex.execute("i", q)[0] == _general(ex, q)[0], q

    def test_fused_minrow_engages(self, ex, monkeypatch):
        calls = []
        orig = Executor._fused_topn_counts

        def spy(self, idx, f, filter_call, shards, opt=None):
            calls.append(shards)
            return orig(self, idx, f, filter_call, shards, opt=opt)

        monkeypatch.setattr(Executor, "_fused_topn_counts", spy)
        ex.execute("i", "MinRow(field=f0)")
        assert calls and len(calls[0]) > 1  # one batch over all shards

    def test_rows_column_vectorized_matches_probe(self, ex):
        # find a column that actually has bits in several rows
        holder = ex.holder
        f = holder.index("i").field("f0")
        view = f.view("standard")
        col = None
        for s, frag in view.fragments.items():
            ids, matrix = frag._stacked()
            if len(ids) == 0:
                continue
            import numpy as np

            hit = np.flatnonzero(matrix.any(axis=0))
            if len(hit):
                w = int(hit[0])
                # pick the first set bit in that word from any row
                word_or = 0
                for r in range(len(ids)):
                    word_or |= int(matrix[r, w])
                b = (word_or & -word_or).bit_length() - 1
                col = s * SHARD_WIDTH + w * 32 + b
                break
        assert col is not None
        got = ex.execute("i", f"Rows(f0, column={col})")[0]
        # oracle: per-row bit probe
        want = [r for r in frag.row_ids() if frag.bit(r, col)]
        assert got == want

    def test_tanimoto_fused_matches_general(self, ex):
        q = "TopN(f0, Row(f1=1), tanimotoThreshold=10)"
        assert ex.execute("i", q)[0] == _general(ex, q)[0]
