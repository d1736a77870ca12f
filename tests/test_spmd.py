"""Collective (SPMD) query execution tests — the multi-host data plane
(VERDICT round-2 missing #2; reference scatter-gather analog
executor.go:2455, here replaced by global-mesh collectives).

Two tiers: a single-process tier on the 8-virtual-device CPU mesh
(parity of the collective evaluator against the product executor and a
Python-set oracle), and a REAL multi-process jax.distributed tier (2
and 3 processes) where full pilosa_tpu servers form an HTTP cluster,
fragments land by jump hash, and collective queries run with stacks
genuinely spanning every process's devices."""

from __future__ import annotations

import random

import pytest

from pilosa_tpu.models.field import FieldOptions
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.parallel import spmd
from pilosa_tpu.parallel.cluster import Cluster, Node
from pilosa_tpu.parallel.executor import Executor
from pilosa_tpu.parallel.results import Pair
from pilosa_tpu.shardwidth import SHARD_WIDTH


def _build(holder, n_shards=5, seed=11, cols_per_row=(300, 301),
           n_vals=400, val_range=(-500, 1 << 18)):
    idx = holder.create_index("i")
    f = idx.create_field("f")
    rng = random.Random(seed)
    bits: dict[int, set[int]] = {}
    rows_l, cols_l = [], []
    for row in range(4):
        cols = {rng.randrange(n_shards * SHARD_WIDTH)
                for _ in range(rng.randrange(*cols_per_row))}
        bits[row] = cols
        rows_l += [row] * len(cols)
        cols_l += list(cols)
    f.import_bits(rows_l, cols_l)
    v = idx.create_field("v", FieldOptions.int_field(*val_range))
    vcols = sorted({rng.randrange(n_shards * SHARD_WIDTH)
                    for _ in range(n_vals)})
    vals = {c: rng.randrange(*val_range) for c in vcols}
    v.import_values(vcols, [vals[c] for c in vcols])
    return idx, bits, vals


@pytest.fixture
def single(tmp_path):
    h = Holder(str(tmp_path / "h"))
    idx, bits, vals = _build(h)
    cluster = Cluster(local_id="n0")
    cluster.add_node(Node(id="n0", uri="local"))
    ce = spmd.CollectiveExecutor(h, cluster, "i")
    yield h, ce, Executor(h), bits, vals
    h.close()


class TestSingleProcessCollective:
    def test_count_tree_parity(self, single):
        h, ce, ex, bits, vals = single
        for pql, want in [
            ("Count(Row(f=0))", len(bits[0])),
            ("Count(Intersect(Row(f=0), Row(f=1)))",
             len(bits[0] & bits[1])),
            ("Count(Union(Row(f=0), Row(f=1), Row(f=2)))",
             len(bits[0] | bits[1] | bits[2])),
            ("Count(Difference(Row(f=0), Row(f=3)))",
             len(bits[0] - bits[3])),
            ("Count(Xor(Row(f=1), Row(f=2)))",
             len(bits[1] ^ bits[2])),
        ]:
            got = ce.execute(pql)
            assert got == want, (pql, got, want)
            assert got == ex.execute("i", pql)[0], pql

    def test_range_count_parity(self, single):
        h, ce, ex, bits, vals = single
        for pql, pred in [
            ("Count(Row(v > 100000))", lambda x: x > 100000),
            ("Count(Row(v <= 0))", lambda x: x <= 0),
            ("Count(Row(v == -5))", lambda x: x == -5),
            ("Count(Row(v >< [-100, 50000]))",
             lambda x: -100 <= x <= 50000),
            ("Count(Row(v != null))", lambda x: True),
        ]:
            want = sum(1 for x in vals.values() if pred(x))
            got = ce.execute(pql)
            assert got == want, (pql, got, want)
            assert got == ex.execute("i", pql)[0], pql

    def test_sum_parity(self, single):
        h, ce, ex, bits, vals = single
        got = ce.execute("Sum(field=v)")
        assert got.val == sum(vals.values())
        assert got.count == len(vals)
        assert got == ex.execute("i", "Sum(field=v)")[0]
        got = ce.execute("Sum(Row(f=1), field=v)")
        want = [v for c, v in vals.items() if c in bits[1]]
        assert got.val == sum(want) and got.count == len(want)
        assert got == ex.execute("i", "Sum(Row(f=1), field=v)")[0]

    def test_min_max_parity(self, single):
        h, ce, ex, bits, vals = single
        for pql in ("Min(field=v)", "Max(field=v)",
                    "Min(Row(f=1), field=v)", "Max(Row(f=1), field=v)"):
            got = ce.execute(pql)
            assert got == ex.execute("i", pql)[0], pql
        lo = min(vals.values())
        got = ce.execute("Min(field=v)")
        assert got.val == lo
        assert got.count == sum(1 for x in vals.values() if x == lo)
        hi = max(vals.values())
        got = ce.execute("Max(field=v)")
        assert got.val == hi
        assert got.count == sum(1 for x in vals.values() if x == hi)

    def test_topn_parity(self, single):
        h, ce, ex, bits, vals = single
        want = sorted(
            (Pair(id=r, count=len(c)) for r, c in bits.items() if c),
            key=lambda p: (-p.count, p.id))
        assert ce.execute("TopN(f)") == want
        assert ce.execute("TopN(f, n=2)") == want[:2]
        filt = ce.execute("TopN(f, Row(f=0), n=3)")
        wantf = sorted(
            ((r, len(c & bits[0])) for r, c in bits.items()),
            key=lambda rc: (-rc[1], rc[0]))
        wantf = [Pair(id=r, count=c) for r, c in wantf if c > 0][:3]
        assert filt == wantf
        assert filt == ex.execute("i", "TopN(f, Row(f=0), n=3)")[0]

    def test_not_shift_time_parity(self, single):
        h, ce, ex, bits, vals = single
        idx = h.index("i")
        # existence bits via the executor's write path (maintains _exists)
        for c in sorted(bits[0])[:50]:
            ex.execute("i", f"Set({c}, f=7)")
        for pql in ("Count(Not(Row(f=0)))",
                    "Count(Union(Row(f=1), Not(Row(f=2))))",
                    "Count(Shift(Row(f=0), n=3))",
                    "Count(Shift(Row(f=1)))"):
            got = ce.execute(pql)
            assert got == ex.execute("i", pql)[0], pql

        from pilosa_tpu.models.field import FieldOptions
        from pilosa_tpu.models.timequantum import parse_time

        t = idx.create_field("t", FieldOptions.time_field("YMD"))
        rng = random.Random(2)
        trows, tcols, times = [], [], []
        for _ in range(200):
            trows.append(4)
            tcols.append(rng.randrange(3 * SHARD_WIDTH))
            times.append(parse_time(
                f"2019-0{1 + rng.randrange(9)}-{1 + rng.randrange(27):02d}T00:00"))
        t.import_bits(trows, tcols, timestamps=times)
        for pql in (
            "Count(Row(t=4, from='2019-02-01T00:00', to='2019-05-01T00:00'))",
            "Count(Row(t=4, from='2019-01-01T00:00', to='2020-01-01T00:00'))",
            "Count(Intersect(Row(f=0), Row(t=4, from='2019-01-01T00:00', "
            "to='2019-07-01T00:00')))",
        ):
            got = ce.execute(pql)
            assert got == ex.execute("i", pql)[0], pql
        # open-ended ranges need the local clamp: scatter path only
        with pytest.raises(spmd.CollectiveError):
            ce.execute("Count(Row(t=4, from='2019-01-01T00:00'))")

    def test_open_time_range_resolution(self, single):
        """Coordinator-side rewrite of open-ended time bounds to the
        GLOBAL view clamp (the collective analog of the scatter path's
        per-node _clamp_to_views): detection, peer-bounds merge, text
        round-trip, and the no-views-anywhere empty rewrite."""
        h, ce, ex, bits, vals = single
        idx = h.index("i")

        from pilosa_tpu.models.timequantum import parse_time
        from pilosa_tpu.pql import parse

        t = idx.create_field("t", FieldOptions.time_field("YMD"))
        rng = random.Random(5)
        trows, tcols, times = [], [], []
        for _ in range(120):
            trows.append(1)
            tcols.append(rng.randrange(3 * SHARD_WIDTH))
            times.append(parse_time(
                f"2019-0{1 + rng.randrange(9)}-"
                f"{1 + rng.randrange(27):02d}T00:00"))
        t.import_bits(trows, tcols, timestamps=times)

        call = parse("Count(Row(t=1, from='2019-03-01T00:00'))").calls[0]
        assert spmd._open_time_fields(idx, call) == {"t"}
        # bounded, non-time, and condition rows never trigger a round
        for pql in ("Count(Row(t=1, from='2019-01-01T00:00', "
                    "to='2019-02-01T00:00'))",
                    "Count(Row(f=0))", "Count(Row(v > 10))"):
            assert spmd._open_time_fields(idx, parse(pql).calls[0]) == set()

        class _N:
            def __init__(self, id):
                self.id = id

        sent = []

        class _Transport:
            def send_message(self, n, msg):
                sent.append((n.id, msg))
                return {"ok": True, "bounds":
                        {"t": ["2018-06-01T00:00", "2020-02-01T00:00"]}}

        class _Cluster:
            local_id = "n0"
            transport = _Transport()

            def sorted_nodes(self):
                return [_N("n0"), _N("n1")]

        class _Node:
            cluster = _Cluster()

        out = spmd._resolve_open_time_ranges(_Node(), idx, "i", call)
        row = out.children[0]
        assert row.args["from"] == "2019-03-01T00:00"  # given: untouched
        # peer's later bound wins the merge; +366d widening like
        # executor._clamp_to_views
        assert row.args["to"] == "2021-02-01T00:00"
        assert sent and sent[0][1]["type"] == "collective-time-bounds"
        # the rewritten call round-trips through PQL text (what ships)
        assert str(parse(str(out)).calls[0]) == str(out)
        # ... and the bounded rewrite is now collectively evaluable,
        # matching the executor's open-ended evaluation exactly
        want = ex.execute("i", "Count(Row(t=1, from='2019-03-01T00:00'))")[0]
        assert ce.execute(f"Count({row})") == want

        # no views anywhere: rewrite to a concrete empty range
        class _TransportNone:
            def send_message(self, n, msg):
                return {"ok": True, "bounds": {"u": None}}

        idx.create_field("u", FieldOptions.time_field("YMD"))
        _Node.cluster.transport = _TransportNone()
        call2 = parse("Count(Row(u=1, to='2019-01-01T00:00'))").calls[0]
        out2 = spmd._resolve_open_time_ranges(_Node(), idx, "i", call2)
        r2 = out2.children[0]
        assert r2.args["from"] == r2.args["to"] == spmd._EMPTY_RANGE_TS
        assert ce.execute(f"Count({r2})") == 0

        # a peer that cannot answer aborts resolution (scatter fallback)
        class _TransportErr:
            def send_message(self, n, msg):
                return {"ok": False, "error": "nope"}

        _Node.cluster.transport = _TransportErr()
        with pytest.raises(spmd.CollectiveError):
            spmd._resolve_open_time_ranges(
                _Node(), idx, "i",
                parse("Count(Row(t=1, from='2019-03-01T00:00'))").calls[0])

    def test_time_bounds_bus_message(self, tmp_path):
        """Peer side of the resolution round: the collective-time-bounds
        bus message reports the local view span per field."""
        from pilosa_tpu.models.timequantum import parse_time
        from pilosa_tpu.parallel.node import ClusterNode

        h = Holder(str(tmp_path / "hb"))
        idx = h.create_index("i")
        t = idx.create_field("t", FieldOptions.time_field("YM"))
        t.import_bits([0, 0], [5, 9],
                      timestamps=[parse_time("2020-03-15T00:00"),
                                  parse_time("2020-11-02T00:00")])
        idx.create_field("empty_t", FieldOptions.time_field("YMD"))
        cluster = Cluster(local_id="n0")
        cluster.add_node(Node(id="n0", uri="local"))
        node = ClusterNode(h, cluster)
        r = node.receive_message(
            {"type": "collective-time-bounds", "index": "i",
             "fields": ["t", "empty_t", "missing"]})
        assert r["ok"]
        # YM quantum: the year view floors the min to the year start;
        # the latest month view sets the max
        assert r["bounds"]["t"] == ["2020-01-01T00:00", "2020-11-01T00:00"]
        assert r["bounds"]["empty_t"] is None
        assert r["bounds"]["missing"] is None
        r = node.receive_message(
            {"type": "collective-time-bounds", "index": "nope",
             "fields": ["t"]})
        assert not r["ok"]
        h.close()

    def test_group_by_parity(self, single):
        h, ce, ex, bits, vals = single
        # second field so the 2-child walk crosses field boundaries
        g = h.index("i").create_field("g")
        rows_l, cols_l = [], []
        for row in range(3):
            for c in sorted(bits[row])[: 120]:
                rows_l.append(row)
                cols_l.append(c)
        g.import_bits(rows_l, cols_l)
        for pql in ("GroupBy(Rows(f))",
                    "GroupBy(Rows(f), Rows(g))",
                    "GroupBy(Rows(f), Rows(g), filter=Row(f=0))",
                    "GroupBy(Rows(f), Rows(g), limit=3)",
                    "GroupBy(Rows(f), Rows(g), offset=2, limit=4)",
                    # 3-level nests: lockstep outer loop (round 3)
                    "GroupBy(Rows(f), Rows(g), Rows(f))",
                    "GroupBy(Rows(f), Rows(g), Rows(g), "
                    "filter=Row(f=1))",
                    "GroupBy(Rows(g), Rows(f), Rows(g), offset=3, "
                    "limit=5)",
                    "GroupBy(Rows(f, limit=2), Rows(g), "
                    "Rows(f, previous=0))"):
            got = ce.execute(pql)
            want = ex.execute("i", pql)[0]
            assert got == want, (pql, got, want)

    def test_unsupported_calls_refused(self, single):
        h, ce, ex, bits, vals = single
        for pql in ("Set(5, f=1)",  # writes never run collectively
                    "GroupBy(Rows(f), previous=1)",
                    "Count(Row(f=0, from='2019-01-01T00:00'))",
                    # bare open-ended time Row: needs the coordinator's
                    # bounds resolution, declined at the evaluator
                    "Row(f=0, from='2019-01-01T00:00')",
                    # attrName without a list attrValues is the scatter
                    # path's user error; malformed tanimoto likewise
                    'TopN(f, attrName="x")',
                    "TopN(f, Row(f=0), tanimotoThreshold=101)"):
            with pytest.raises(spmd.CollectiveError):
                ce.execute(pql)

    def test_bare_bitmap_parity(self, single):
        """Bare bitmap trees — the single most ordinary PQL read —
        return a global Row assembled from the replicated gather,
        exactly matching the scatter executor (round-4 VERDICT #3;
        reference executeBitmapCall, executor.go:651)."""
        h, ce, ex, bits, vals = single
        for pql in ("Row(f=0)",
                    "Union(Row(f=0), Row(f=1), Row(f=2))",
                    "Intersect(Row(f=0), Row(f=1))",
                    "Difference(Row(f=2), Row(f=3))",
                    "Xor(Row(f=1), Row(f=2))",
                    "Shift(Row(f=0), n=5)",
                    "Row(v > 100000)",
                    "Row(v >< [-100, 50000])"):
            got = ce.execute(pql)
            want = ex.execute("i", pql)[0]
            assert got == want, (pql, len(got.columns()),
                                 len(want.columns()))
        # oracle spot-checks (not just plane agreement)
        got = ce.execute("Union(Row(f=0), Row(f=1))")
        assert sorted(int(c) for c in got.columns()) == \
            sorted(bits[0] | bits[1])
        got = ce.execute("Row(v > 100000)")
        assert sorted(int(c) for c in got.columns()) == \
            sorted(c for c, x in vals.items() if x > 100000)

    def test_bare_bitmap_windowed_gather(self, single, monkeypatch):
        """Past MAX_ROW_GATHER_BYTES the bare-bitmap result replicates
        in shard-range windows instead of one all-gather — same exact
        Row, bounded per-process transient (round-5 VERDICT #8).
        Shrinking the bound to ~2 shards per window forces the 5-shard
        index through the windowed path, including the clamped
        overlapping last window."""
        h, ce, ex, bits, vals = single
        words = spmd.bm.n_words(SHARD_WIDTH)
        for max_shards in (1, 2, 3):
            monkeypatch.setattr(spmd, "MAX_ROW_GATHER_BYTES",
                                max_shards * words * 4)
            for pql in ("Row(f=0)",
                        "Union(Row(f=0), Row(f=1), Row(f=2))",
                        "Row(v > 100000)"):
                got = ce.execute(pql)
                want = ex.execute("i", pql)[0]
                assert got == want, (max_shards, pql)
        got = ce.execute("Union(Row(f=0), Row(f=1))")
        assert sorted(int(c) for c in got.columns()) == \
            sorted(bits[0] | bits[1])

    def test_wide_group_by_parity(self, single):
        """4+-child GroupBy runs collectively via the outer cartesian
        lockstep loop (round-4 VERDICT #3)."""
        h, ce, ex, bits, vals = single
        g = h.index("i").field("g")
        if g is None:
            g = h.index("i").create_field("g")
            rows_l, cols_l = [], []
            for row in range(3):
                for c in sorted(bits[row])[:150]:
                    rows_l.append(row)
                    cols_l.append(c)
            g.import_bits(rows_l, cols_l)
        for pql in ("GroupBy(Rows(f), Rows(g), Rows(f), Rows(g))",
                    "GroupBy(Rows(f), Rows(g), Rows(f), Rows(g), "
                    "filter=Row(f=0))",
                    "GroupBy(Rows(f, limit=2), Rows(g), Rows(f), "
                    "Rows(g), limit=30, offset=4)",
                    "GroupBy(Rows(g), Rows(g), Rows(f), Rows(g), "
                    "Rows(f))"):
            got = ce.execute(pql)
            want = ex.execute("i", pql)[0]
            assert got == want, (pql, got[:4], want[:4])

    def test_group_by_constrained_children_parity(self, single):
        """Rows-child limit/column/previous constraints match the
        executor: column resolves via one collective bit gather, then
        previous/limit apply to the agreed list (the executor's
        _execute_rows order)."""
        h, ce, ex, bits, vals = single
        # a column present in row 1 and row 3 (deterministic probe)
        col13 = next(iter(bits[1] & bits[3]
                          or bits[1]))  # overlap or fall back to row 1
        for pql in ("GroupBy(Rows(f, limit=2))",
                    "GroupBy(Rows(f, previous=0))",
                    "GroupBy(Rows(f, previous=1, limit=1))",
                    f"GroupBy(Rows(f, column={col13}))",
                    f"GroupBy(Rows(f, column={col13}, limit=1))",
                    f"GroupBy(Rows(f, column={col13}), Rows(f))",
                    "GroupBy(Rows(f, limit=3), Rows(f, previous=0), "
                    "filter=Row(f=2))",
                    "GroupBy(Rows(f, column=999999999))"):  # absent col
            got = ce.execute(pql)
            want = ex.execute("i", pql)[0]
            assert got == want, (pql, got, want)

    def test_group_by_time_children_parity(self, single):
        """Time-constrained GroupBy Rows children match the scatter
        path's reference-faithful semantics (executor.go:1104-1117 +
        newGroupByIterator executor.go:3102): from/to bites only
        through the constrained-child row pre-selection; counts always
        come from the standard view; a no-standard-view child empties
        the whole GroupBy."""
        import datetime as dt

        from pilosa_tpu.models.field import FieldOptions as FO

        h, ce, ex, bits, vals = single
        idx = h.index("i")
        t = idx.create_field("t", FO.time_field("YMD"))
        ns = idx.create_field("ns", FO.time_field(
            "YMD", no_standard_view=True))
        rng = random.Random(55)
        for fld in (t, ns):
            rows_l, cols_l, ts_l = [], [], []
            for row in range(3):
                for c in sorted(bits[row])[:80]:
                    rows_l.append(row)
                    cols_l.append(c)
                    ts_l.append(dt.datetime(2020, rng.randrange(1, 13),
                                            rng.randrange(1, 28)))
            fld.import_bits(rows_l, cols_l, ts_l)
        for pql in (
                # unconstrained: from/to ignored (reference semantics)
                "GroupBy(Rows(t, from='2020-03-01T00:00', "
                "to='2020-06-01T00:00'))",
                # constrained: selection honors the time cover
                "GroupBy(Rows(t, from='2020-03-01T00:00', "
                "to='2020-06-01T00:00', limit=2))",
                "GroupBy(Rows(t, from='2020-02-01T00:00', "
                "to='2020-11-01T00:00', previous=0), Rows(f))",
                "GroupBy(Rows(f), Rows(t, from='2020-01-01T00:00', "
                "to='2021-01-01T00:00', limit=2), Rows(f))",
                # no-standard-view children: constant empty
                "GroupBy(Rows(ns))",
                "GroupBy(Rows(ns, limit=3))",
                "GroupBy(Rows(ns), Rows(f))"):
            got = ce.execute(pql)
            want = ex.execute("i", pql)[0]
            assert got == want, (pql, got, want)

    def test_options_parity(self, single):
        """Options() runs collectively: shards restrict the plan (and
        the agreed row lists), serialization flags ride the result —
        matching the scatter executor (reference executeOptionsCall)."""
        h, ce, ex, bits, vals = single
        for pql in ("Options(Count(Row(f=0)), shards=[0, 2])",
                    "Options(Count(Union(Row(f=0), Row(f=1))), "
                    "shards=[1])",
                    "Options(Row(f=1), excludeColumns=true)",
                    "Options(Sum(Row(f=0), field=v), shards=[0, 1, 3])",
                    "Options(TopN(f), shards=[2])",
                    "Options(Rows(f), shards=[0])",
                    "Options(Count(Row(f=2)), shards=[])"):
            got = ce.execute(pql)
            want = ex.execute("i", pql)[0]
            assert got == want, (pql, got, want)
        # flags ride the Row result like the scatter plane's
        r = ce.execute("Options(Row(f=0), excludeColumns=true)")
        assert r.exclude_columns is True
        r = ce.execute("Options(Row(f=0), columnAttrs=true)")
        assert r.wants_column_attrs is True
        # nested Options: inner levels override (scatter recurses too)
        got = ce.execute("Options(Options(Count(Row(f=0)), shards=[0]), "
                         "shards=[0, 1, 2, 3, 4])")
        want = ex.execute(
            "i", "Options(Options(Count(Row(f=0)), shards=[0]), "
            "shards=[0, 1, 2, 3, 4])")[0]
        assert got == want
        # unknown options stay the scatter path's user error; writes
        # under Options never run collectively
        with pytest.raises(spmd.CollectiveError):
            ce.execute("Options(Count(Row(f=0)), bogus=true)")
        with pytest.raises(spmd.CollectiveError):
            ce.execute("Options(Set(9999, f=0), shards=[0])")

    def test_rows_and_extreme_row_parity(self, single):
        """Standalone Rows (incl. constraints and time covers) and
        MinRow/MaxRow run collectively, matching the scatter executor
        (round 4: the ordinary-read surface rounds out)."""
        import datetime as dt

        from pilosa_tpu.models.field import FieldOptions as FO

        h, ce, ex, bits, vals = single
        idx = h.index("i")
        t = idx.create_field("t2", FO.time_field("YMD"))
        rng = random.Random(77)
        rows_l, cols_l, ts_l = [], [], []
        for row in range(4):
            for c in sorted(bits[row])[:60]:
                rows_l.append(row)
                cols_l.append(c)
                ts_l.append(dt.datetime(2021, rng.randrange(1, 13), 5))
        t.import_bits(rows_l, cols_l, ts_l)
        col0 = min(bits[0])
        for pql in ("Rows(f)",
                    "Rows(f, limit=2)",
                    "Rows(f, previous=1)",
                    f"Rows(f, column={col0})",
                    # time field: from/to select the covering views
                    "Rows(t2)",
                    "Rows(t2, from='2021-01-01T00:00', "
                    "to='2021-06-01T00:00')",
                    "Rows(t2, from='2021-01-01T00:00', "
                    "to='2022-01-01T00:00', limit=2)",
                    "MinRow(field=f)",
                    "MaxRow(field=f)",
                    "MinRow(Row(f=1), field=f)",
                    "MaxRow(Row(f=0), field=f)"):
            got = ce.execute(pql)
            want = ex.execute("i", pql)[0]
            assert got == want, (pql, got, want)

    def test_topn_arg_parity(self, single):
        """threshold/ids/tanimoto TopN args match the executor exactly
        (post-count filters on the complete global counts)."""
        h, ce, ex, bits, vals = single
        for pql in ("TopN(f, n=2, threshold=100)",
                    "TopN(f, threshold=301)",
                    "TopN(f, ids=[0,2])",
                    "TopN(f, ids=[1], n=1)",
                    "TopN(f, Row(f=1), ids=[0,1,3])",
                    "TopN(f, Row(f=0), threshold=10)",
                    "TopN(f, Row(f=1), tanimotoThreshold=30)",
                    "TopN(f, Row(f=0), tanimotoThreshold=95)",
                    "TopN(f, tanimotoThreshold=50)"):  # no filter: inert
            got = ce.execute(pql)
            want = ex.execute("i", pql)[0]
            assert [(p.id, p.count) for p in got] == \
                   [(p.id, p.count) for p in want], pql

    def test_topn_attr_filter_parity(self, single):
        """attrName/attrValues filter host-side on the complete global
        counts, matching the executor (the device programs are
        unchanged, so SPMD lockstep holds)."""
        h, ce, ex, bits, vals = single
        f = h.index("i").field("f")
        f.row_attrs.set_attrs(0, {"color": "red", "size": 3})
        f.row_attrs.set_attrs(1, {"color": "blue"})
        f.row_attrs.set_attrs(2, {"color": "red"})
        for pql in ('TopN(f, attrName="color", attrValues=["red"])',
                    'TopN(f, attrName="color", attrValues=["blue"], n=1)',
                    'TopN(f, attrName="size", attrValues=[3])',
                    'TopN(f, attrName="color", attrValues=["green"])',
                    'TopN(f, Row(f=1), attrName="color", '
                    'attrValues=["red","blue"])',
                    'TopN(f, attrName="color", attrValues=["red"], '
                    'threshold=100)'):
            got = ce.execute(pql)
            want = ex.execute("i", pql)[0]
            assert [(p.id, p.count) for p in got] == \
                   [(p.id, p.count) for p in want], pql

    def test_fuzz_sentinel_folding(self, tmp_path, monkeypatch):
        """Randomized keyed trees mixing real and MISSING keys through
        the coordinator: whenever try_collective answers, it must match
        the executor (which handles sentinels natively) and a Python
        oracle — and the fold must actually engage on a healthy
        fraction of ghost-bearing trees."""
        from pilosa_tpu.parallel.node import ClusterNode

        h = Holder(str(tmp_path / "h"))
        cluster = Cluster(local_id="n0")
        cluster.add_node(Node(id="n0", uri="local"))
        cluster.coordinator_id = "n0"
        cluster.set_state("NORMAL")
        node = ClusterNode(h, cluster)
        idx = h.create_index("i")
        idx.create_field("kf", FieldOptions.set_field(keys=True))
        rng = random.Random(2718)
        real = {}
        for key in ("a", "b", "c", "d"):
            cols = {rng.randrange(3000) for _ in range(200)}
            real[key] = cols
        # bulk-load via the executor write path (keys allocate ids)
        for key, cols in real.items():
            for c in sorted(cols):
                node.executor.execute("i", f'Set({c}, kf="{key}")')
        ghosts = ["g1", "g2"]

        def gen(depth):
            if depth == 0 or rng.random() < 0.4:
                key = rng.choice(list(real) + ghosts)
                return f'Row(kf="{key}")', real.get(key, set())
            op = rng.choice(["Union", "Intersect", "Difference", "Xor"])
            n = rng.randrange(2, 4)
            parts = [gen(depth - 1) for _ in range(n)]
            texts = [p[0] for p in parts]
            sets = [p[1] for p in parts]
            if op == "Union":
                acc = set().union(*sets)
            elif op == "Intersect":
                acc = sets[0]
                for s in sets[1:]:
                    acc = acc & s
            elif op == "Difference":
                acc = sets[0]
                for s in sets[1:]:
                    acc = acc - s
            else:
                acc = sets[0]
                for s in sets[1:]:
                    acc = acc ^ s
            return f"{op}({', '.join(texts)})", acc

        monkeypatch.setattr(spmd, "collective_available", lambda: True)
        answered_with_ghost = 0
        try:
            for _ in range(100):
                text, oracle = gen(depth=2)
                q = f"Count({text})"
                want = node.executor.execute("i", q)[0]
                assert want == len(oracle), (q, want, len(oracle))
                res = spmd.try_collective(node, "i", q)
                if res is not None:
                    assert res == [want], (q, res, want)
                    if '"g' in text:
                        answered_with_ghost += 1
            # the fold must be doing real work, not refusing everything
            assert answered_with_ghost >= 10, answered_with_ghost
        finally:
            h.close()

    def test_untranslated_key_args_refused(self, single):
        """The evaluator is id-space only: STRING row args (keys that
        never went through the coordinator's translation) are refused —
        the translated forms are covered by the keyed-query test."""
        h, ce, ex, bits, vals = single
        h.index("i").create_field(
            "kf", FieldOptions.set_field(keys=True))
        for pql in ('Count(Row(kf="alice"))',
                    'Count(Intersect(Row(f=0), Row(kf="x")))',
                    'Count(Row(f="stringy"))'):
            with pytest.raises(spmd.CollectiveError):
                ce.execute(pql)

    def test_fuzz_collective_vs_scatter_vs_oracle(self, tmp_path):
        """Randomized differential sweep: every collective-supported
        query shape must agree with BOTH the product executor and a
        Python-set oracle — the three-way check that caught the resize
        cache bug, applied to the whole collective surface."""
        import contextlib

        with contextlib.closing(Holder(str(tmp_path / "h"))) as h:
            self._run_fuzz(h)

    def _run_fuzz(self, h):
        from pilosa_tpu.pql import parse_python
        from tests.test_fuzz_stress import eval_set_algebra, gen_query

        idx = h.create_index("i")
        rng = random.Random(777)
        n_shards = 4
        row_sets: dict[tuple[str, int], set] = {}
        universe: set[int] = set()
        for fi in range(3):
            f = idx.create_field(f"f{fi}")
            rows_l, cols_l = [], []
            for row in range(5):
                cols = {rng.randrange(n_shards * SHARD_WIDTH)
                        for _ in range(rng.randrange(50, 250))}
                row_sets[(f"f{fi}", row)] = cols
                rows_l += [row] * len(cols)
                cols_l += list(cols)
                universe |= cols
            f.import_bits(rows_l, cols_l)
        # existence rows for Not: both planes complement against _exists
        ex = Executor(h)
        idx.existence_field().import_bits([0] * len(universe),
                                          sorted(universe))

        cluster = Cluster(local_id="n0")
        cluster.add_node(Node(id="n0", uri="local"))
        ce = spmd.CollectiveExecutor(h, cluster, "i")
        checked = 0
        for _ in range(120):
            q = f"Count({gen_query(rng, depth=1)})"
            calls = parse_python(q).calls
            if not ce.supported(calls[0]):
                continue
            want = len(eval_set_algebra(calls[0].children[0],
                                        row_sets, universe))
            got_c = ce.execute(q)
            got_x = ex.execute("i", q)[0]
            assert got_c == want == got_x, (q, got_c, got_x, want)
            checked += 1
        assert checked >= 60, f"only {checked} shapes exercised"

    def test_fuzz_aggregates_and_conditions(self, tmp_path):
        """Randomized aggregate surface: Sum/Min/Max with random filter
        trees, BSI-condition counts with random ops/predicates, TopN
        and GroupBy with random filters — collective vs executor vs
        dict/set oracles."""
        import contextlib

        with contextlib.closing(Holder(str(tmp_path / "h"))) as h:
            self._run_agg_fuzz(h)

    def _run_agg_fuzz(self, h):
        rng = random.Random(4040)
        idx, bits, vals = _build(h, n_shards=3, seed=4040,
                                 cols_per_row=(80, 300), n_vals=500,
                                 val_range=(-3000, 90000))
        # densify the row/value overlap: uniform draws over the column
        # space make filtered aggregates almost always empty (the fuzz
        # would rubber-stamp (0,0)==(0,0)); giving ~60% of each row's
        # columns a BSI value makes every filter branch non-trivial
        overlap = sorted({c for cols in bits.values()
                          for c in rng.sample(sorted(cols),
                                              int(len(cols) * 0.6))})
        v = idx.field("v")
        new_vals = {c: rng.randrange(-3000, 90000) for c in overlap}
        v.import_values(list(new_vals), list(new_vals.values()))
        vals.update(new_vals)
        assert any(vals.keys() & cols for cols in bits.values())
        cluster = Cluster(local_id="n0")
        cluster.add_node(Node(id="n0", uri="local"))
        ce = spmd.CollectiveExecutor(h, cluster, "i")
        ex = Executor(h)
        import operator as op

        cmps = {"<": op.lt, "<=": op.le, ">": op.gt, ">=": op.ge,
                "==": op.eq, "!=": op.ne}
        for i in range(80):
            kind = rng.randrange(5)
            if kind == 0:  # BSI condition count
                o = rng.choice(list(cmps))
                p = rng.randrange(-4000, 95000)
                q = f"Count(Row(v {o} {p}))"
                want = sum(1 for x in vals.values() if cmps[o](x, p))
                assert ce.execute(q) == want == ex.execute("i", q)[0], q
            elif kind == 1:  # between
                a = rng.randrange(-4000, 95000)
                b = a + rng.randrange(0, 50000)
                q = f"Count(Row(v >< [{a}, {b}]))"
                want = sum(1 for x in vals.values() if a <= x <= b)
                assert ce.execute(q) == want == ex.execute("i", q)[0], q
            elif kind == 2:  # Sum with random row filter
                r = rng.randrange(4)
                q = f"Sum(Row(f={r}), field=v)"
                sel = [x for c, x in vals.items() if c in bits[r]]
                got = ce.execute(q)
                assert (got.val, got.count) == (sum(sel), len(sel)), q
                assert got == ex.execute("i", q)[0], q
            elif kind == 3:  # Min/Max with random filter
                name = rng.choice(["Min", "Max"])
                r = rng.randrange(4)
                q = f"{name}(Row(f={r}), field=v)"
                got = ce.execute(q)
                assert got == ex.execute("i", q)[0], q
                sel = [x for c, x in vals.items() if c in bits[r]]
                if sel:
                    best = min(sel) if name == "Min" else max(sel)
                    assert (got.val, got.count) == \
                        (best, sel.count(best)), q
            else:  # TopN / GroupBy with random filter
                r = rng.randrange(4)
                roll = rng.random()
                if roll < 0.35:
                    q = f"TopN(f, Row(f={r}), n=3)"
                    got = ce.execute(q)
                    want = sorted(((rid, len(c & bits[r]))
                                   for rid, c in bits.items()),
                                  key=lambda rc: (-rc[1], rc[0]))
                    want = [(rid, c) for rid, c in want if c > 0][:3]
                    assert [(p.id, p.count) for p in got] == want, q
                elif roll < 0.6:
                    # random post-count arg mix: executor is the oracle
                    arg = rng.choice([
                        f"threshold={rng.randrange(1, 250)}",
                        f"ids=[{r}, {(r + 1) % 4}]",
                        f"tanimotoThreshold={rng.randrange(5, 99)}"])
                    q = f"TopN(f, Row(f={r}), n=3, {arg})"
                    got = ce.execute(q)
                else:
                    q = f"GroupBy(Rows(f), filter=Row(f={r}))"
                    got = ce.execute(q)
                    want = {rid: len(c & bits[r])
                            for rid, c in bits.items()
                            if len(c & bits[r])}
                    assert {g.group[0].row_id: g.count
                            for g in got} == want, q
                assert got == ex.execute("i", q)[0], q

    def test_keyed_queries_translate_then_run_collectively(
            self, tmp_path, monkeypatch):
        """try_collective translates string keys to ids ONCE at the
        origin (executor.go:146 semantics), ships id-only text, and
        re-keys the result; missing keys produce sentinel trees that
        fall back to the scatter path."""
        from pilosa_tpu.parallel.node import ClusterNode

        h = Holder(str(tmp_path / "h"))
        cluster = Cluster(local_id="n0")
        cluster.add_node(Node(id="n0", uri="local"))
        cluster.coordinator_id = "n0"
        cluster.set_state("NORMAL")
        node = ClusterNode(h, cluster)
        idx = h.create_index("i")
        idx.create_field("kf", FieldOptions.set_field(keys=True))
        for col, key in [(1, "alice"), (2, "alice"), (3, "bob"),
                         (2, "bob"), (9, "carol")]:
            node.executor.execute("i", f'Set({col}, kf="{key}")')

        monkeypatch.setattr(spmd, "collective_available", lambda: True)
        try:
            res = spmd.try_collective(node, "i",
                                      'Count(Row(kf="alice"))')
            assert res == [2], res
            assert spmd.try_collective(node, "i", 'TopN(kf)') is not None
            pairs = spmd.try_collective(node, "i", "TopN(kf)")[0]
            assert [(p.key, p.count) for p in pairs] == \
                [("alice", 2), ("bob", 2), ("carol", 1)]
            # missing key -> sentinel tree -> scatter path (None)
            assert spmd.try_collective(
                node, "i", 'Count(Row(kf="ghost"))') is None
            # and the scatter path answers it with the proper semantics
            assert node.executor.execute(
                "i", 'Count(Row(kf="ghost"))')[0] == 0
        finally:
            h.close()

    def test_sentinel_folding(self, tmp_path, monkeypatch):
        """Missing read keys fold out of the tree by set algebra at the
        coordinator (Union drops the empty child, Difference keeps its
        head, ...) so mixed trees still run collectively; only
        unfoldable shapes — whole-tree empty, Not(empty) — fall back
        to the scatter path (reference: missing keys are empty rows,
        executor.go:2610)."""
        from pilosa_tpu.parallel.node import ClusterNode
        from pilosa_tpu.pql import Call

        h = Holder(str(tmp_path / "h"))
        cluster = Cluster(local_id="n0")
        cluster.add_node(Node(id="n0", uri="local"))
        cluster.coordinator_id = "n0"
        cluster.set_state("NORMAL")
        node = ClusterNode(h, cluster)
        idx = h.create_index("i")
        idx.create_field("kf", FieldOptions.set_field(keys=True))
        for col, key in [(1, "alice"), (2, "alice"), (3, "bob"),
                         (2, "bob"), (9, "carol")]:
            node.executor.execute("i", f'Set({col}, kf="{key}")')

        monkeypatch.setattr(spmd, "collective_available", lambda: True)
        try:
            # Union: the empty child drops; answered collectively
            q = 'Count(Union(Row(kf="alice"), Row(kf="ghost")))'
            assert spmd.try_collective(node, "i", q) == [2]
            assert node.executor.execute("i", q)[0] == 2
            # Difference head survives
            q = 'Count(Difference(Row(kf="alice"), Row(kf="ghost")))'
            assert spmd.try_collective(node, "i", q) == [2]
            # Xor: empty is the identity
            q = 'Count(Xor(Row(kf="ghost"), Row(kf="bob")))'
            assert spmd.try_collective(node, "i", q) == [2]
            # Intersect with an empty leg folds to whole-tree empty:
            # scatter path answers (collective declines)
            q = 'Count(Intersect(Row(kf="alice"), Row(kf="ghost")))'
            assert spmd.try_collective(node, "i", q) is None
            assert node.executor.execute("i", q)[0] == 0
            # TopN filter tree folds too
            q = 'TopN(kf, Union(Row(kf="alice"), Row(kf="ghost")))'
            pairs = spmd.try_collective(node, "i", q)[0]
            assert [(p.key, p.count) for p in pairs] == \
                [("alice", 2), ("bob", 1)]
        finally:
            h.close()

        # algebra unit cases on raw trees
        E = Call("_Empty")
        row = Call("Row", {"f": 1})
        assert spmd._fold_bitmap_tree(Call("Not", children=[E])) is None
        assert spmd._fold_bitmap_tree(
            Call("Shift", {"n": 2}, [E])) is spmd._EMPTY_TREE
        assert spmd._fold_bitmap_tree(
            Call("Difference", children=[E, row])) is spmd._EMPTY_TREE
        u = spmd._fold_bitmap_tree(Call("Union", children=[E, row, E]))
        assert u is row
        x = spmd._fold_bitmap_tree(
            Call("Xor", children=[E, row, Call("Row", {"f": 2})]))
        assert x.name == "Xor" and len(x.children) == 2

    def test_row_attr_attachment_matches_scatter_plane(
            self, tmp_path, monkeypatch):
        """Row attrs attach for a LITERAL user Row() only — a tree that
        sentinel-folds down to a Row must serialize identically on both
        planes (the reference attaches only for Row calls,
        executor.go:206)."""
        from pilosa_tpu.parallel.node import ClusterNode

        h = Holder(str(tmp_path / "h"))
        cluster = Cluster(local_id="n0")
        cluster.add_node(Node(id="n0", uri="local"))
        cluster.coordinator_id = "n0"
        cluster.set_state("NORMAL")
        node = ClusterNode(h, cluster)
        idx = h.create_index("i")
        idx.create_field("kf", FieldOptions.set_field(keys=True))
        for col, key in [(1, "alice"), (2, "alice"), (3, "bob")]:
            node.executor.execute("i", f'Set({col}, kf="{key}")')
        node.executor.execute(
            "i", 'SetRowAttrs(kf, "alice", color="red")')
        monkeypatch.setattr(spmd, "collective_available", lambda: True)
        try:
            q = 'Row(kf="alice")'
            r_coll = spmd.try_collective(node, "i", q)[0]
            r_scat = node.executor.execute("i", q)[0]
            assert r_coll.attrs == r_scat.attrs == {"color": "red"}
            assert r_coll == r_scat
            # folded Union(Row, ghost) -> Row: neither plane attaches
            q = 'Union(Row(kf="alice"), Row(kf="ghost"))'
            u_coll = spmd.try_collective(node, "i", q)[0]
            u_scat = node.executor.execute("i", q)[0]
            assert u_coll == u_scat
            assert u_coll.attrs == u_scat.attrs == {}
        finally:
            h.close()

    def test_rank_convention_checker(self, single):
        h, ce, ex, bits, vals = single
        # single process: rank 0 must be the sorted position of "n0"
        spmd.verify_rank_convention(ce.cluster)
        bad = Cluster(local_id="zz")
        bad.add_node(Node(id="aa", uri="x"))
        bad.add_node(Node(id="zz", uri="y"))
        with pytest.raises(spmd.CollectiveError):
            spmd.verify_rank_convention(bad)  # "zz" sorts to rank 1


WORKER = '''
import json, os, random, sys, time, urllib.request
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)

from pilosa_tpu.parallel import multihost, spmd
from pilosa_tpu.server.server import Server
from pilosa_tpu.server.client import InternalClient
from pilosa_tpu.shardwidth import SHARD_WIDTH

multihost.initialize()
pid = jax.process_index()
NPROC = int(os.environ["JAX_NUM_PROCESSES"])
ports = [int(os.environ[f"T_PORT{i}"]) for i in range(NPROC)]
data = os.environ["T_DATA"]

# node ids in sorted order == process ids (the documented convention)
if pid == 0:
    srv = Server(data + "/n0", port=ports[0], name="n0", coordinator=True)
else:
    srv = Server(data + f"/n{pid}", port=ports[pid], name=f"n{pid}",
                 seeds=[f"http://127.0.0.1:{ports[0]}"])
srv.open()
c = InternalClient(timeout=30)

# barrier: both servers joined the HTTP cluster
deadline = time.monotonic() + 60
while len(srv.cluster.sorted_nodes()) < NPROC:
    if time.monotonic() > deadline:
        raise SystemExit("join timeout")
    time.sleep(0.05)
spmd.verify_rank_convention(srv.cluster)

# deterministic dataset, generated identically in both workers for the
# oracle; written once through node 0's HTTP API so fragments land by
# jump hash
N_SHARDS = 6
rng = random.Random(4242)
bits = {}
rows_l, cols_l = [], []
for row in range(3):
    cols = {rng.randrange(N_SHARDS * SHARD_WIDTH) for _ in range(250)}
    bits[row] = cols
    rows_l += [row] * len(cols); cols_l += sorted(cols)
vcols = sorted({rng.randrange(N_SHARDS * SHARD_WIDTH) for _ in range(300)})
vals = {c: rng.randrange(-1000, 100000) for c in vcols}
# time-field data: one month per column, deterministic for the oracle
tcols = sorted({rng.randrange(N_SHARDS * SHARD_WIDTH) for _ in range(200)})
tmonth = {cc: 1 + (i % 9) for i, cc in enumerate(tcols)}
t_oracle = sum(1 for m in tmonth.values() if m >= 3)

if pid == 0:
    post = lambda p, o: c.post_json(srv.uri + p, o)
    post("/index/i", {})
    post("/index/i/field/f", {})
    post("/index/i/field/v",
         {"options": {"type": "int", "min": -1000, "max": 100000}})
    post("/index/i/field/t",
         {"options": {"type": "time", "timeQuantum": "YMD"}})
    post("/index/i/field/f/import", {"rowIDs": rows_l, "columnIDs": cols_l})
    post("/index/i/field/v/import-value",
         {"columnIDs": vcols, "values": [vals[c] for c in vcols]})
    post("/index/i/field/t/import",
         {"rowIDs": [1] * len(tcols), "columnIDs": tcols,
          "timestamps": [f"2019-{tmonth[cc]:02d}-01T00:00"
                         for cc in tcols]})

# barrier: every process waits until the scatter-gather plane sees all
# data, then signals readiness over the CONTROL plane (a file), never a
# jax collective — a global sync enqueued while a peer still drives
# local device work through HTTP deadlocks (the collective parks on
# this process's devices, the peer's HTTP poll needs those devices,
# the peer never reaches the sync: learned the hard way)
want0 = len(bits[0])
deadline = time.monotonic() + 60
while True:
    try:
        got = c.post_json(srv.uri + "/index/i/query",
                          {"query": "Count(Row(f=0))"})["results"][0]
        if got == want0:
            break
    except Exception:
        pass
    if time.monotonic() > deadline:
        raise SystemExit("data visibility timeout")
    time.sleep(0.1)

open(f"{data}/ready.{pid}", "w").write("1")
deadline = time.monotonic() + 120
while not all(os.path.exists(f"{data}/ready.{p}") for p in range(NPROC)):
    if time.monotonic() > deadline:
        raise SystemExit("ready barrier timeout")
    time.sleep(0.05)

# sanity: this process owns only PART of the shard space (stacks must
# genuinely span processes)
plan = spmd.make_plan(
    sorted(srv.holder.index("i").available_shards()),
    spmd.owner_rank_fn(srv.cluster, "i"))
owned = [s for i, s in enumerate(plan.order) if s >= 0 and i in plan.local]
total = [s for s in plan.order if s >= 0]
# every process owns strictly less than the whole space (jump hash may
# legitimately assign SOME process zero shards at small shard counts)
assert len(owned) < len(total), (owned, total)

ce = spmd.CollectiveExecutor(srv.holder, srv.cluster, "i")
out = []
queries = [
    "Count(Row(f=0))",
    "Count(Intersect(Row(f=0), Row(f=1)))",
    "Count(Union(Row(f=0), Row(f=1), Row(f=2)))",
    "Count(Row(v > 50000))",
    "Count(Row(v >< [-500, 0]))",
    "Sum(field=v)",
    "Sum(Row(f=1), field=v)",
    "Min(field=v)",
    "Max(field=v)",
    "TopN(f)",
    "TopN(f, Row(f=0), n=2)",
]
oracle = {
    queries[0]: len(bits[0]),
    queries[1]: len(bits[0] & bits[1]),
    queries[2]: len(bits[0] | bits[1] | bits[2]),
    queries[3]: sum(1 for x in vals.values() if x > 50000),
    queries[4]: sum(1 for x in vals.values() if -500 <= x <= 0),
}
for q in queries:
    got = ce.execute(q)
    if q in oracle:
        assert got == oracle[q], (q, got, oracle[q])
    out.append((q, repr(got)))

# Sum/TopN oracles
sv = ce.execute("Sum(field=v)")
assert sv.val == sum(vals.values()) and sv.count == len(vals)
sf = ce.execute("Sum(Row(f=1), field=v)")
wantf = [v for cc, v in vals.items() if cc in bits[1]]
assert sf.val == sum(wantf) and sf.count == len(wantf)
tn = ce.execute("TopN(f)")
want_tn = sorted(((r, len(cc)) for r, cc in bits.items()),
                 key=lambda rc: (-rc[1], rc[0]))
assert [(p.id, p.count) for p in tn] == want_tn, (tn, want_tn)
mn = ce.execute("Min(field=v)")
lo = min(vals.values())
assert mn.val == lo and mn.count == sum(
    1 for x in vals.values() if x == lo), mn
mx = ce.execute("Max(field=v)")
hi = max(vals.values())
assert mx.val == hi and mx.count == sum(
    1 for x in vals.values() if x == hi), mx
gb = ce.execute("GroupBy(Rows(f))")
want_gb = sorted((r, len(cc)) for r, cc in bits.items() if cc)
assert [(g.group[0].row_id, g.count) for g in gb] == want_gb, gb
# constrained children: limit is a pure cut of the agreed list; column
# resolves via the collective bit gather on the owning shard's process
gbl = ce.execute("GroupBy(Rows(f, limit=2))")
want_gbl = [(r, len(bits[r])) for r in sorted(bits)[:2] if bits[r]]
assert [(g.group[0].row_id, g.count) for g in gbl] == want_gbl, gbl
cc1 = min(bits[1])
gbc = ce.execute(f"GroupBy(Rows(f, column={cc1}))")
want_gbc = [(r, len(bits[r])) for r in sorted(bits) if cc1 in bits[r]]
assert [(g.group[0].row_id, g.count) for g in gbc] == want_gbc, gbc
# TopN post-count args, same lockstep
tnt = ce.execute("TopN(f, Row(f=0), n=2, threshold=1)")
want_tnt = sorted(((r, len(cc & bits[0])) for r, cc in bits.items()),
                  key=lambda rc: (-rc[1], rc[0]))
want_tnt = [(r, cnt) for r, cnt in want_tnt if cnt >= 1][:2]
assert [(p.id, p.count) for p in tnt] == want_tnt, tnt
# bare bitmap results: the global Row gathers replicated; segments
# must match the oracle's columns exactly on EVERY process
br = ce.execute("Row(f=2)")
assert sorted(int(x) for x in br.columns()) == sorted(bits[2]), "bareRow"
br = ce.execute("Union(Row(f=0), Row(f=1))")
assert sorted(int(x) for x in br.columns()) == \
    sorted(bits[0] | bits[1]), "bareUnion"
br = ce.execute("Difference(Row(f=0), Row(f=1), Row(f=2))")
assert sorted(int(x) for x in br.columns()) == \
    sorted(bits[0] - bits[1] - bits[2]), "bareDiff"
# windowed gather (round 5): shrink the per-window bound so the
# 6-shard result replicates in 2-shard sub-plan windows — the window
# sequence must stay in LOCKSTEP across processes (divergence here
# deadlocks the fleet rather than just mismatching)
_saved_gather_bytes = spmd.MAX_ROW_GATHER_BYTES
spmd.MAX_ROW_GATHER_BYTES = 2 * spmd.bm.n_words(SHARD_WIDTH) * 4
try:
    br = ce.execute("Union(Row(f=0), Row(f=1))")
    assert sorted(int(x) for x in br.columns()) == \
        sorted(bits[0] | bits[1]), "windowedUnion"
    br = ce.execute("Row(f=2)")
    assert sorted(int(x) for x in br.columns()) == \
        sorted(bits[2]), "windowedRow"
finally:
    spmd.MAX_ROW_GATHER_BYTES = _saved_gather_bytes
# 4-child GroupBy: outer cartesian lockstep loop across processes
import itertools as _it
gb4 = ce.execute("GroupBy(Rows(f), Rows(f), Rows(f), Rows(f))")
want_gb4 = sorted(
    ((a, b, cc_, d), len(bits[a] & bits[b] & bits[cc_] & bits[d]))
    for a, b, cc_, d in _it.product(sorted(bits), repeat=4)
    if bits[a] & bits[b] & bits[cc_] & bits[d])
assert [tuple(fr.row_id for fr in g.group) for g in gb4] == \
    [k for k, _ in want_gb4], "gb4 keys"
assert [g.count for g in gb4] == [n for _, n in want_gb4], "gb4 counts"

# cross-check the collective data plane against the HTTP control plane.
# Two phases with a control-plane barrier between: an HTTP scatter-
# gather needs the PEER's devices, so it must never run while the peer
# sits in a collective (same deadlock as the ready barrier)
http_res = [c.post_json(srv.uri + "/index/i/query",
                        {"query": q})["results"][0] for q in queries[:5]]
open(f"{data}/xcheck.{pid}", "w").write("1")
deadline = time.monotonic() + 120
while not all(os.path.exists(f"{data}/xcheck.{p}") for p in range(NPROC)):
    if time.monotonic() > deadline:
        raise SystemExit("xcheck barrier timeout")
    time.sleep(0.05)
for q, http in zip(queries[:5], http_res):
    coll = ce.execute(q)
    assert http == coll, (q, http, coll)

# PRODUCT path: a plain HTTP query on the coordinator transparently
# upgrades to a collective — the peer joins via the broadcast bus while
# idling in a pure file-poll loop (no device work, no deadlock)
joined_before = spmd.counters()["collective_joined"]  # pre-barrier snapshot
open(f"{data}/product.{pid}", "w").write("1")
deadline = time.monotonic() + 120
while not all(os.path.exists(f"{data}/product.{p}") for p in range(NPROC)):
    if time.monotonic() > deadline:
        raise SystemExit("product barrier timeout")
    time.sleep(0.05)
if pid == 0:
    # a loaded box can time out one prepare round (legal fallback, the
    # result is exact either way) — require that SOME attempt runs
    # collectively, every attempt stays exact
    before = spmd.counters()["collective_initiated"]
    for attempt in range(5):
        got = c.post_json(srv.uri + "/index/i/query",
                          {"query": queries[1]})["results"][0]
        assert got == oracle[queries[1]], got
        if spmd.counters()["collective_initiated"] > before:
            break
    assert spmd.counters()["collective_initiated"] > before, \
        "no HTTP query ran collectively in 5 attempts"
    # open-ended time range: the coordinator resolves the global view
    # clamp over the control plane (collective-time-bounds round),
    # rewrites the text, and the bounded program runs collectively
    t_pql = "Count(Row(t=1, from='2019-03-01T00:00'))"
    before_t = spmd.counters()["collective_initiated"]
    for attempt in range(5):
        got = c.post_json(srv.uri + "/index/i/query",
                          {"query": t_pql})["results"][0]
        assert got == t_oracle, (got, t_oracle)
        if spmd.counters()["collective_initiated"] > before_t:
            break
    assert spmd.counters()["collective_initiated"] > before_t, \
        "open-ended time query never ran collectively in 5 attempts"
    # bare Row over HTTP: the most ordinary PQL query upgrades to the
    # collective plane end-to-end (translate -> gather -> serialize)
    r_pql = "Union(Row(f=0), Row(f=1))"
    before_r = spmd.counters()["collective_initiated"]
    for attempt in range(5):
        got = c.post_json(srv.uri + "/index/i/query",
                          {"query": r_pql})["results"][0]
        assert sorted(got["columns"]) == sorted(bits[0] | bits[1]), \
            "bare row HTTP result"
        if spmd.counters()["collective_initiated"] > before_r:
            break
    assert spmd.counters()["collective_initiated"] > before_r, \
        "bare row query never ran collectively in 5 attempts"
    assert spmd.counters()["collective_joined"] == 0  # only peers join
    open(f"{data}/product_done.ok", "w").write("1")
else:
    # wait on the coordinator's explicit signal, NOT the joined
    # counter: the xcheck phase's coordinator HTTP queries already ran
    # bus collectives, so the counter is non-zero before this phase —
    # waiting on it let peers race ahead into the refusal drill and
    # poison the coordinator's product attempts (learned from a flake)
    deadline = time.monotonic() + 240
    while not os.path.exists(f"{data}/product_done.ok"):
        if time.monotonic() > deadline:
            raise SystemExit("coordinator product phase timeout")
        time.sleep(0.05)
    # strictly-greater vs the pre-phase snapshot: this phase's
    # collective must have joined THIS peer (poll: the peer's bump can
    # lag the coordinator's return by a bus response)
    deadline = time.monotonic() + 60
    while spmd.counters()["collective_joined"] <= joined_before:
        if time.monotonic() > deadline:
            raise SystemExit("peer never joined the product collective")
        time.sleep(0.05)

# refusal drill: a peer that declines the collective plane (prepare
# returns not-ok) must degrade the coordinator to the scatter-gather
# plane with exact results — the all-or-hang property is handled BEFORE
# anyone enters a device collective
# dynamic phase: interleaved writes and collective reads — every write
# replicates synchronously over the control plane, and the next
# collective must see it (operands build fresh from fragments; no
# cross-query caching to go stale).  Peers serve the bus passively.
open(f"{data}/dynamic.{pid}", "w").write("1")
deadline = time.monotonic() + 120
while not all(os.path.exists(f"{data}/dynamic.{p}") for p in range(NPROC)):
    if time.monotonic() > deadline:
        raise SystemExit("dynamic barrier timeout")
    time.sleep(0.05)
if pid == 0:
    drng = random.Random(7171)
    for it in range(12):
        row = drng.randrange(3)
        col = drng.randrange(N_SHARDS * SHARD_WIDTH)
        if drng.random() < 0.75:
            c.post_json(srv.uri + "/index/i/query",
                        {"query": f"Set({col}, f={row})"})
            bits[row].add(col)
        else:
            c.post_json(srv.uri + "/index/i/query",
                        {"query": f"Clear({col}, f={row})"})
            bits[row].discard(col)
        got = c.post_json(srv.uri + "/index/i/query",
                          {"query": f"Count(Row(f={row}))"})["results"][0]
        assert got == len(bits[row]), (it, got, len(bits[row]))
    open(f"{data}/dynamic_done.ok", "w").write("1")
else:
    deadline = time.monotonic() + 240
    while not os.path.exists(f"{data}/dynamic_done.ok"):
        if time.monotonic() > deadline:
            raise SystemExit("dynamic phase timeout")
        time.sleep(0.05)

orig_avail = spmd.collective_available
if pid == 1:
    spmd.collective_available = lambda: False  # this peer refuses
# patch BEFORE signaling: the coordinator queries the moment the
# barrier opens, and an unpatched peer would let the collective win
open(f"{data}/refuse.{pid}", "w").write("1")
deadline = time.monotonic() + 120
while not all(os.path.exists(f"{data}/refuse.{p}") for p in range(NPROC)):
    if time.monotonic() > deadline:
        raise SystemExit("refuse barrier timeout")
    time.sleep(0.05)
if pid == 0:
    fb0 = spmd.counters()["collective_fallbacks"]
    got = c.post_json(srv.uri + "/index/i/query",
                      {"query": queries[1]})["results"][0]
    assert got == oracle[queries[1]], got
    assert spmd.counters()["collective_fallbacks"] == fb0 + 1, \
        "refusal did not route through the fallback path"
    open(f"{data}/refused.ok", "w").write("1")
else:
    deadline = time.monotonic() + 120
    while not os.path.exists(f"{data}/refused.ok"):
        if time.monotonic() > deadline:
            raise SystemExit("refusal drill timeout")
        time.sleep(0.05)
spmd.collective_available = orig_avail

# exit barrier on the control plane too: a process must not close its
# server while the peer's last collective still needs both sides
open(f"{data}/done.{pid}", "w").write("1")
deadline = time.monotonic() + 120
while not all(os.path.exists(f"{data}/done.{p}") for p in range(NPROC)):
    if time.monotonic() > deadline:
        raise SystemExit("done barrier timeout")
    time.sleep(0.05)
c.close(); srv.close()
print("RESULT " + json.dumps(out))
'''


@pytest.mark.parametrize("n_proc", [2, 3])
def test_multi_process_collective_executor(tmp_path, n_proc):
    """N OS processes, each a full pilosa_tpu server in one HTTP
    cluster; fragments placed by jump hash; Count/Range/Sum/Min/Max/
    TopN/GroupBy run collectively with global stacks spanning every
    process's devices, bit-identical to the Python oracle AND to the
    HTTP scatter-gather plane (the reconciled two-plane story,
    parallel/spmd.py).  The 3-process leg exercises uneven jump-hash
    groups and per-process block padding."""
    import os
    import socket
    import subprocess
    import sys

    socks = [socket.socket() for _ in range(1 + n_proc)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        coord_port, *node_ports = (s.getsockname()[1] for s in socks)
    finally:
        for s in socks:
            s.close()

    worker = tmp_path / "worker.py"
    worker.write_text(WORKER)
    env = dict(os.environ)
    env.update(
        JAX_COORDINATOR_ADDRESS=f"127.0.0.1:{coord_port}",
        JAX_NUM_PROCESSES=str(n_proc),
        T_DATA=str(tmp_path),
        PYTHONPATH=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))) + os.pathsep
        + env.get("PYTHONPATH", ""),
        **{f"T_PORT{i}": str(p) for i, p in enumerate(node_ports)},
    )
    procs = []
    for pid in range(n_proc):
        e = dict(env, JAX_PROCESS_ID=str(pid))
        procs.append(subprocess.Popen(
            [sys.executable, str(worker)], env=e,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = [p.communicate(timeout=540)[0] for p in procs]
    for p, out in zip(procs, outs):
        if "Multiprocess computations aren't implemented" in out:
            # this jaxlib's CPU backend has no cross-process
            # collectives at all — an environment limitation, not a
            # product regression
            pytest.skip("jax CPU backend lacks multiprocess collectives")
        assert p.returncode == 0, out[-3000:]
    results = {ln for out in outs for ln in out.splitlines()
               if ln.startswith("RESULT ")}
    # every process computed identical (replicated) results
    assert len(results) == 1, results
