"""Cross-query micro-batched dispatch (parallel/coalescer.py) and the
fused expression compiler's launch accounting (ops/expr.py + the
ops/bitmap.py dispatch hook).

The contract under test is the north-star regression bar: the fused
tree executes in <= 2 device dispatches (down from one per AST node),
and the coalescer merges >= 8 concurrent identical-shape queries into
ONE launch with bit-exact per-query results."""

from __future__ import annotations

import json
import random
import threading
import time
import urllib.request

import pytest

from pilosa_tpu import stats as _stats
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.ops import expr
from pilosa_tpu.parallel.coalescer import Coalescer, resolve_enabled
from pilosa_tpu.parallel.executor import Executor
from pilosa_tpu.shardwidth import SHARD_WIDTH
from tests.coalesce_batch import (map_behind_launch, queued,
                                  run_behind_launch, wait_until)

N_SHARDS = 6


@pytest.fixture
def ex(tmp_path):
    holder = Holder(str(tmp_path / "h"))
    idx = holder.create_index("i")
    rng = random.Random(99)
    for fi in range(3):
        f = idx.create_field(f"f{fi}")
        rows, cols = [], []
        for row in range(6):
            for _ in range(250):
                rows.append(row)
                cols.append(rng.randrange(N_SHARDS * SHARD_WIDTH))
        f.import_bits(rows, cols)
        idx.import_existence(cols)
    yield Executor(holder)
    holder.close()


def _unbatched(ex, q):
    """Ground truth: the per-shard path (fusion off, no coalescer)."""
    ex.fuse_shards = False
    try:
        return ex.execute("i", q)[0]
    finally:
        ex.fuse_shards = True


# ---------------------------------------------------------------------------
# Fused tree compiler: launch accounting
# ---------------------------------------------------------------------------


class TestFusedDispatchCount:
    def test_count_intersect_two_dispatches_max(self, ex):
        """The north-star query over a fused shard group must cost at
        most 2 launches (it costs exactly 1: the whole tree INCLUDING
        the popcount root is one compiled program)."""
        ex.execute("i", "Count(Row(f0=0))")  # warm row-stack caches
        with bm.dispatch_counter() as dc:
            got = ex.execute(
                "i", "Count(Intersect(Row(f0=1), Row(f1=2)))")[0]
        assert got == _unbatched(
            ex, "Count(Intersect(Row(f0=1), Row(f1=2)))")
        assert dc.n <= 2, dc.launches

    def test_deep_tree_single_launch(self, ex):
        """Tree depth must NOT multiply the launch count — the old
        per-AST-node evaluation cost one dispatch per operator."""
        q = ("Count(Union(Intersect(Row(f0=1), Row(f1=2)),"
             " Difference(Row(f2=3), Row(f0=4)),"
             " Xor(Row(f1=5), Row(f2=0))))")
        ex.execute("i", q)  # warm caches + jit
        with bm.dispatch_counter() as dc:
            got = ex.execute("i", q)[0]
        assert got == _unbatched(ex, q)
        assert dc.n <= 2, dc.launches

    def test_row_tree_single_launch(self, ex):
        """Bitmap-result trees (Row root) fuse the same way."""
        q = "Union(Intersect(Row(f0=1), Row(f1=1)), Row(f2=2))"
        ex.execute("i", q)
        with bm.dispatch_counter() as dc:
            got = ex.execute("i", q)[0]
        assert list(got.columns()) == list(_unbatched(ex, q).columns())
        assert dc.n <= 2, dc.launches

    def test_compiled_shape_cache_shared_across_row_ids(self, ex):
        """Distinct row ids share one compiled program (the shape key
        erases leaf values) — no per-query retrace."""
        expr._compiled.cache_clear()
        expr._compiled_gather.cache_clear()
        expr._compiled_mesh.cache_clear()
        expr._compiled_mesh_gather.cache_clear()
        for a in range(3):
            ex.execute("i", f"Count(Intersect(Row(f0={a}), Row(f1={a})))")
        # the query routes ONE of the two fused engines (dense program
        # or the compressed-container gather program) — whichever ran,
        # the three row-id variants must share a single compiled shape
        dense = expr._compiled.cache_info()
        gather = expr._compiled_gather.cache_info()
        mesh = expr._compiled_mesh.cache_info()
        mgather = expr._compiled_mesh_gather.cache_info()
        assert (dense.misses + gather.misses
                + mesh.misses + mgather.misses) == 1, (
            dense, gather, mesh, mgather)

    def test_expr_matches_bm_ops(self):
        """Direct engine check: compiled program == op-by-op chain."""
        rng = random.Random(5)
        import numpy as np

        leaves = tuple(
            np.array([[rng.getrandbits(32) for _ in range(8)]
                      for _ in range(4)], dtype=np.uint32)
            for _ in range(3))
        shape = ("or", ("and", ("leaf", 0), ("leaf", 1)),
                 ("shift", 3, ("leaf", 2)))
        got = expr.evaluate(shape, leaves)
        want = bm.b_or(bm.b_and(leaves[0], leaves[1]),
                       bm.b_shift(leaves[2], 3))
        assert (np.asarray(got) == np.asarray(want)).all()
        counts = expr.evaluate(shape, leaves, counts=True)
        assert (np.asarray(counts)
                == np.asarray(bm.row_counts(want))).all()


# ---------------------------------------------------------------------------
# Coalescer: wait semantics + bit-exactness
# ---------------------------------------------------------------------------


def _attach(ex, window_s=0.5, max_batch=8):
    stats = _stats.MemStatsClient()
    ex.coalescer = Coalescer(window_s=window_s, max_batch=max_batch,
                             enabled=True, stats=stats)
    return stats


def _run_concurrent(ex, queries):
    """The queries, one thread each, batched behind a held launch."""
    return map_behind_launch(
        ex.coalescer, lambda i: ex.execute("i", queries[i])[0],
        len(queries))


class TestCoalescer:
    def test_merges_eight_queries_into_one_launch(self, ex):
        """>= 8 concurrent identical-shape queries -> ONE launch,
        bit-exact per-query results (the acceptance bar)."""
        stats = _attach(ex, window_s=2.0, max_batch=8)
        qs = [f"Count(Intersect(Row(f0={a}), Row(f1={b})))"
              for a in range(4) for b in range(2)]
        expected = [_unbatched(ex, q) for q in qs]
        launches = []
        orig = expr.evaluate

        def spy(shape, leaves, **kw):
            launches.append(shape)
            return orig(shape, leaves, **kw)

        expr_evaluate = expr.evaluate
        expr.evaluate = spy
        try:
            got = _run_concurrent(ex, qs)
        finally:
            expr.evaluate = expr_evaluate
        assert got == expected
        assert len(launches) == 1, launches
        snap = stats.snapshot()
        assert snap["coalescer.dispatches"] == 1
        assert snap["coalescer.batch_occupancy"]["max"] == 8
        assert snap["coalescer.flush_full"] == 1  # 8 is max_batch

    def test_flush_on_max_batch_before_window(self, ex):
        """A full bucket seals immediately, launch in flight or not —
        the window is an upper bound, not a floor."""
        stats = _attach(ex, window_s=30.0, max_batch=4)
        qs = [f"Count(Intersect(Row(f0={a}), Row(f1=0)))"
              for a in range(4)]
        expected = [_unbatched(ex, q) for q in qs]
        t0 = time.monotonic()
        got = _run_concurrent(ex, qs)
        assert got == expected
        assert time.monotonic() - t0 < 15.0  # nowhere near the window
        snap = stats.snapshot()
        assert snap["coalescer.flush_full"] == 1
        assert snap["coalescer.batch_occupancy"]["max"] == 4

    def test_flush_on_deadline_with_partial_batch(self, ex):
        """Fewer queries than max_batch still flush when the window
        runs out behind a launch that outlasts it: the cap."""
        stats = _attach(ex, window_s=0.05, max_batch=32)
        qs = ["Count(Intersect(Row(f0=1), Row(f1=1)))",
              "Count(Intersect(Row(f0=2), Row(f1=2)))"]
        expected = [_unbatched(ex, q) for q in qs]
        got = [None, None]

        def run(i):
            got[i] = ex.execute("i", qs[i])[0]

        ts = [threading.Thread(target=run, args=(i,)) for i in range(2)]
        with ex.coalescer.in_flight():
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=30)  # answered while the launch holds
            assert got == expected
        snap = stats.snapshot()
        assert snap["coalescer.dispatches"] >= 1
        assert snap["coalescer.flush_cap"] == snap["coalescer.dispatches"]
        assert ex.coalescer.flushes["cap"] == snap["coalescer.flush_cap"]

    def test_single_query_passthrough(self, ex):
        """A lone query runs the identical single-query program —
        same result, occupancy 1."""
        stats = _attach(ex, window_s=0.01, max_batch=32)
        q = "Count(Intersect(Row(f0=3), Row(f2=4)))"
        assert ex.execute("i", q)[0] == _unbatched(ex, q)
        snap = stats.snapshot()
        assert snap["coalescer.batch_occupancy"]["max"] == 1

    def test_batch_pads_to_power_of_two(self, ex):
        """Free-running batch occupancies would each compile their own
        XLA variant (the jitted program re-lowers per [B, S, W] input
        shape), so under sustained ingest the serving path would pay a
        fresh multi-hundred-ms compile at every new batch size — the
        flush pads device batches to the next power of two instead.
        3 concurrent queries -> one launch whose stacks carry 4 batch
        rows; the 3 real results stay bit-exact."""
        _attach(ex, window_s=2.0, max_batch=8)
        qs = [f"Count(Intersect(Row(f0={a}), Row(f1=0)))"
              for a in range(3)]
        expected = [_unbatched(ex, q) for q in qs]
        seen = []
        orig = expr.evaluate

        def spy(shape, leaves, **kw):
            seen.append(tuple(getattr(lv, "shape", ()) for lv in leaves))
            return orig(shape, leaves, **kw)

        expr.evaluate = spy
        try:
            got = _run_concurrent(ex, qs)
        finally:
            expr.evaluate = orig
        assert got == expected
        batched = [s for s in seen if s and len(s[0]) == 3]
        assert batched, seen
        assert all(s[0][0] == 4 for s in batched), seen

    def test_different_shapes_do_not_merge(self, ex):
        """Structurally different trees dispatch separately but still
        answer correctly."""
        _attach(ex, window_s=0.05, max_batch=32)
        qs = ["Count(Intersect(Row(f0=1), Row(f1=2)))",
              "Count(Union(Row(f0=1), Row(f1=2), Row(f2=3)))",
              "Count(Row(f2=5))",
              "Count(Difference(Row(f0=0), Row(f1=0)))"]
        expected = [_unbatched(ex, q) for q in qs]
        assert _run_concurrent(ex, qs) == expected

    def test_nocoalesce_opt_bypasses(self, ex):
        """opt.coalesce=False (the HTTP ?nocoalesce=true) skips the
        window entirely."""
        from pilosa_tpu.parallel.executor import ExecOptions

        stats = _attach(ex, window_s=5.0, max_batch=32)
        q = "Count(Intersect(Row(f0=1), Row(f1=1)))"
        t0 = time.monotonic()
        got = ex.execute("i", q, opt=ExecOptions(coalesce=False))[0]
        assert time.monotonic() - t0 < 4.0
        assert got == _unbatched(ex, q)
        assert "coalescer.dispatches" not in stats.snapshot()

    def test_randomized_bit_exactness(self, ex):
        """Randomized fused-eligible Count corpus: coalesced batches
        must be bit-exact against the per-shard path."""
        rng = random.Random(31)
        _attach(ex, window_s=1.0, max_batch=8)

        def gen_tree(depth):
            if depth == 0 or rng.random() < 0.4:
                return f"Row(f{rng.randrange(3)}={rng.randrange(6)})"
            op = rng.choice(["Union", "Intersect", "Difference", "Xor"])
            kids = [gen_tree(depth - 1)
                    for _ in range(rng.randrange(2, 4))]
            return f"{op}({', '.join(kids)})"

        for _ in range(4):
            qs = [f"Count({gen_tree(2)})" for _ in range(8)]
            expected = [_unbatched(ex, q) for q in qs]
            assert _run_concurrent(ex, qs) == expected

    def test_error_propagates_to_every_waiter(self, ex):
        """A flush failure must fail every coalesced query loudly, not
        hang the waiters."""
        _attach(ex, window_s=1.0, max_batch=2)
        orig = expr.evaluate

        def boom(shape, leaves, **kw):
            raise RuntimeError("flush exploded")

        expr.evaluate = boom
        try:
            errs = []

            def run(i):
                try:
                    ex.execute(
                        "i", f"Count(Intersect(Row(f0={i}), Row(f1=0)))")
                except RuntimeError as e:
                    errs.append(str(e))

            run_behind_launch(
                ex.coalescer, [threading.Thread(target=run, args=(i,))
                               for i in range(2)], timeout=30)
        finally:
            expr.evaluate = orig
        assert errs == ["flush exploded", "flush exploded"]

    def test_resolve_enabled_modes(self):
        assert resolve_enabled(True) is True
        assert resolve_enabled(False) is False
        assert resolve_enabled("true") is True
        assert resolve_enabled("off") is False
        with pytest.raises(ValueError):
            resolve_enabled("ture")  # typo must not silently mean auto
        # "auto" on the 8-virtual-CPU-device test platform: not host
        # mode (multiple devices), so batching is on
        assert resolve_enabled("auto") == (not bm.host_mode())


class TestInFlightWait:
    """The leader's wait depends on whether a launch is in flight, not
    on a clock."""

    def test_lone_query_with_nothing_in_flight_does_not_wait(self, ex):
        stats = _attach(ex, window_s=30.0, max_batch=32)
        q = "Count(Intersect(Row(f0=3), Row(f2=4)))"
        want = _unbatched(ex, q)
        t0 = time.monotonic()
        assert ex.execute("i", q)[0] == want
        assert time.monotonic() - t0 < 15.0  # nowhere near the window
        snap = stats.snapshot()
        assert snap["coalescer.flush_idle"] == 1
        assert snap["coalescer.batch_occupancy"]["max"] == 1
        rec = ex.recorder.recent_records()[-1]
        assert rec.coalesce["why"] == "idle" and rec.coalesce["leader"]
        # the span is written though it is (next to) zero long
        [wait] = [sp for sp in rec.spans if sp[2] == "coalesce.wait"]
        assert wait[6] == {"why": "idle"}
        assert wait[4] - wait[3] < 1e9
        assert ex.coalescer.flushes == {"idle": 1, "busy": 0,
                                        "full": 0, "cap": 0}

    def test_arrivals_during_a_launch_share_the_next_one(self, ex):
        stats = _attach(ex, window_s=30.0, max_batch=32)
        qs = [f"Count(Intersect(Row(f0={a}), Row(f1=1)))"
              for a in range(5)]
        expected = [_unbatched(ex, q) for q in qs]
        n0 = len(ex.recorder.recent_records())
        assert _run_concurrent(ex, qs) == expected
        snap = stats.snapshot()
        assert snap["coalescer.dispatches"] == 1
        assert snap["coalescer.flush_busy"] == 1
        assert snap["coalescer.batch_occupancy"]["max"] == 5
        recs = ex.recorder.recent_records()[n0:]
        assert [r.coalesce["why"] for r in recs] == ["busy"] * 5
        assert sum(r.coalesce["leader"] for r in recs) == 1
        for r in recs:  # leader's and followers' spans both say why
            [wait] = [sp for sp in r.spans if sp[2] == "coalesce.wait"]
            assert wait[6] == {"why": "busy"}
        assert ex.coalescer.inflight == 0 and queued(ex.coalescer) == 0

    def test_window_zero_flushes_at_once_behind_a_launch(self, ex):
        stats = _attach(ex, window_s=0.0, max_batch=32)
        q = "Count(Intersect(Row(f0=1), Row(f1=1)))"
        want = _unbatched(ex, q)
        with ex.coalescer.in_flight():
            assert ex.execute("i", q)[0] == want
        assert stats.snapshot()["coalescer.flush_cap"] == 1

    def test_every_waiting_leader_flushes_when_the_count_drains(self, ex):
        """Buckets of different keys wake together and launch
        concurrently: the first one's launch must not send the second
        leader back to sleep until the cap."""
        _attach(ex, window_s=30.0, max_batch=32)
        ex.coalescer.ragged = False  # per-shape keys: two buckets
        qs = ["Count(Intersect(Row(f0=1), Row(f1=2)))",
              "Count(Union(Row(f0=1), Row(f1=2), Row(f2=3)))"]
        expected = [_unbatched(ex, q) for q in qs]
        both = threading.Barrier(2)
        orig = expr.evaluate

        def meet(shape, leaves, **kw):
            both.wait(timeout=20)  # broken if the other never launches
            return orig(shape, leaves, **kw)

        expr.evaluate = meet
        try:
            assert _run_concurrent(ex, qs) == expected
        finally:
            expr.evaluate = orig
        assert ex.coalescer.flushes["busy"] == 2

    def test_failed_flush_leaves_no_launch_in_flight(self, ex):
        """A flush that raises still ends its launch: the count returns
        to zero and the leader waiting behind it wakes and answers."""
        stats = _attach(ex, window_s=30.0, max_batch=32)
        co = ex.coalescer
        q1 = "Count(Intersect(Row(f0=1), Row(f1=1)))"
        q2 = "Count(Intersect(Row(f0=2), Row(f1=2)))"
        want2 = _unbatched(ex, q2)
        orig = expr.evaluate
        calls = []

        def first_explodes(shape, leaves, **kw):
            calls.append(shape)
            if len(calls) > 1:
                return orig(shape, leaves, **kw)
            # hold this launch until the second query waits behind it
            wait_until(lambda: queued(co))
            raise RuntimeError("flush exploded")

        out = {}

        def run(name, q):
            try:
                out[name] = ex.execute("i", q)[0]
            except RuntimeError as e:
                out[name] = str(e)

        expr.evaluate = first_explodes
        try:
            t1 = threading.Thread(target=run, args=("a", q1))
            t1.start()
            wait_until(lambda: co.inflight)
            t2 = threading.Thread(target=run, args=("b", q2))
            t2.start()
            t1.join(timeout=30)
            t2.join(timeout=30)
        finally:
            expr.evaluate = orig
        assert out == {"a": "flush exploded", "b": want2}
        assert co.inflight == 0 and queued(co) == 0
        snap = stats.snapshot()
        assert snap["coalescer.flush_idle"] == 1
        assert snap["coalescer.flush_busy"] == 1


# ---------------------------------------------------------------------------
# HTTP: parallel clients through the query route
# ---------------------------------------------------------------------------


class TestHTTPConcurrency:
    def test_parallel_clients_coalesce_and_answer(self, tmp_path):
        from pilosa_tpu.server.server import Server

        srv = Server(str(tmp_path / "srv"), port=0,
                     coalescer_enabled=True,
                     coalescer_window_ms=50.0,
                     coalescer_max_batch=8)
        srv.open()
        try:
            srv.api.create_index("i")
            srv.api.create_field("i", "f0")
            srv.api.create_field("i", "f1")
            rng = random.Random(12)
            for fi, fname in enumerate(["f0", "f1"]):
                rows, cols = [], []
                for row in range(4):
                    for _ in range(200):
                        rows.append(row)
                        cols.append(rng.randrange(4 * SHARD_WIDTH))
                srv.api.import_bits("i", fname, rows, cols)

            qs = [f"Count(Intersect(Row(f0={a}), Row(f1={b})))"
                  for a in range(4) for b in range(4)]
            # ground truth must not warm the result cache, or the
            # concurrent wave would answer from it and never reach the
            # coalescer this test exists to exercise
            expected = [srv.api.query("i", q, coalesce=False,
                                      cache=False)[0]
                        for q in qs]

            def post(q):
                req = urllib.request.Request(
                    f"{srv.uri}/index/i/query", data=q.encode(),
                    method="POST")
                with urllib.request.urlopen(req, timeout=30) as resp:
                    return json.loads(resp.read())["results"][0]

            out = map_behind_launch(
                srv.node.executor.coalescer, lambda i: post(qs[i]),
                len(qs))
            assert out == expected
            snap = srv.stats.snapshot()
            # batching engaged: strictly fewer launches than queries
            assert snap["coalescer.dispatches"] < len(qs)
            with urllib.request.urlopen(f"{srv.uri}/debug/ragged",
                                        timeout=30) as resp:
                co = json.loads(resp.read())["coalescer"]
            assert co["inFlight"] == 0 and co["windowMs"] == 50.0
            assert set(co["flushes"]) == {"idle", "busy", "full", "cap"}
            assert (sum(co["flushes"].values())
                    == snap["coalescer.dispatches"])
            assert co["flushes"]["busy"] + co["flushes"]["full"] >= 1
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# Satellite gates
# ---------------------------------------------------------------------------


class TestSentinelGate:
    def test_public_query_rejects_sentinels(self, ex):
        from pilosa_tpu.parallel.executor import ExecutionError
        from pilosa_tpu.pql import ParseError

        for q in ("_Empty()", "Count(_Empty())", "_Noop()",
                  "_EmptyRows()", "Union(_Empty(), Row(f0=1))",
                  # sentinels smuggled as arg values (the grammar
                  # admits Call under any key) must be caught too
                  "Row(f0=_Empty())",
                  "GroupBy(Rows(f0), filter=_Empty())"):
            with pytest.raises((ParseError, ExecutionError, ValueError)):
                ex.execute("i", q)

    def test_remote_semantics_still_parse_sentinels(self, ex):
        from pilosa_tpu.models.row import Row
        from pilosa_tpu.parallel.executor import ExecOptions

        out = ex.execute("i", "Count(_Empty())",
                         opt=ExecOptions(remote=True))
        assert out == [0]
        row = ex.execute("i", "_Empty()",
                         opt=ExecOptions(remote=True))[0]
        assert isinstance(row, Row) and not list(row.columns())


class TestImportShardGate:
    def test_multi_shard_delivery_refused(self, tmp_path):
        from tests.test_cluster import make_cluster

        _, nodes = make_cluster(tmp_path, n=2, replica_n=1)
        nodes[0].create_index("i")
        nodes[0].create_field("i", "f")
        resp = nodes[0].receive_message({
            "type": "import", "index": "i", "field": "f",
            "rows": [1, 1],
            "cols": [1, SHARD_WIDTH + 1],  # spans two shards
        })
        assert resp.get("ok") is False
        assert "spans" in resp.get("error", "")
        resp = nodes[0].receive_message({
            "type": "import-value", "index": "i", "field": "f",
            "cols": [1, SHARD_WIDTH + 1], "values": [1, 2],
        })
        assert resp.get("ok") is False
