"""Ragged megabatch execution: the op-tape interpreter (ops/tape.py)
and the size-class coalescer buckets (parallel/coalescer.py).

The contract under test is the ragged acceptance bar: 16 concurrent
queries with 16 DISTINCT fused-expression shapes execute in <= 2
device launches (vs 16 pre-ragged), bit-exact against per-query host
evaluation, with ingest deltas both off and on — plus the regression
pins that the [ragged] disable flag and the per-query oversize-tape
fallback route through the existing per-shape fused path unchanged."""

from __future__ import annotations

import json
import random
import urllib.request

import numpy as np
import pytest

from pilosa_tpu import ingest
from pilosa_tpu import stats as _stats
from pilosa_tpu.ingest import compactor
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.ops import expr
from pilosa_tpu.ops import tape
from pilosa_tpu.parallel.coalescer import Coalescer
from pilosa_tpu.parallel.executor import Executor
from pilosa_tpu.runtime import resultcache
from pilosa_tpu.shardwidth import SHARD_WIDTH
from tests.coalesce_batch import map_behind_launch

N_SHARDS = 4


@pytest.fixture
def ex(tmp_path):
    holder = Holder(str(tmp_path / "h"))
    idx = holder.create_index("i")
    rng = random.Random(424)
    for fi in range(3):
        f = idx.create_field(f"f{fi}")
        rows, cols = [], []
        for row in range(6):
            for _ in range(200):
                rows.append(row)
                cols.append(rng.randrange(N_SHARDS * SHARD_WIDTH))
        f.import_bits(rows, cols)
        idx.import_existence(cols)
    yield Executor(holder)
    holder.close()


@pytest.fixture
def nocache():
    """The concurrent waves must reach the coalescer, not the result
    cache (distinct ground-truth runs would otherwise pre-fill it)."""
    rc = resultcache.cache()
    was = rc.enabled
    rc.enabled = False
    yield
    rc.enabled = was


def _unbatched(ex, q):
    """Ground truth: the per-shard path (fusion off, no coalescer),
    delta-aware through the effective host words."""
    ex.fuse_shards = False
    try:
        return ex.execute("i", q)[0]
    finally:
        ex.fuse_shards = True


def _attach(ex, window_s=2.0, max_batch=16, **kw):
    stats = _stats.MemStatsClient()
    ex.coalescer = Coalescer(window_s=window_s, max_batch=max_batch,
                             enabled=True, stats=stats, **kw)
    return stats


#: 16 structurally DISTINCT fused-eligible trees over <= 3 leaves
#: (2-leaf binaries, 3-leaf folds, 3-leaf nested pairs) — sized so the
#: whole mix lands in at most two tape size classes with ingest deltas
#: both off and on.
SHAPES_16 = (
    ["{0}(Row(f0=1), Row(f1=2))".format(op)
     for op in ("Intersect", "Union", "Difference", "Xor")]
    + ["{0}(Row(f0=3), Row(f1=4), Row(f2=5))".format(op)
       for op in ("Intersect", "Union", "Difference", "Xor")]
    + ["{0}({1}(Row(f0=0), Row(f2=1)), Row(f1=3))".format(o1, o2)
       for o1, o2 in (("Intersect", "Union"), ("Intersect", "Xor"),
                      ("Union", "Intersect"), ("Union", "Difference"),
                      ("Difference", "Union"), ("Difference", "Xor"),
                      ("Xor", "Intersect"), ("Xor", "Union"))]
)


def _run_concurrent_counting(ex, queries):
    """Fire the queries concurrently behind a held launch (so they
    meet in their buckets), each worker under its own thread-local
    dispatch counter; returns (results, total_launches).
    The batch's shared launch ticks the leader's counter only, so the
    SUM across workers is the true device-launch count of the wave."""
    def run(i):
        with bm.dispatch_counter() as dc:
            got = ex.execute("i", queries[i])[0]
        return got, dc.n

    res = map_behind_launch(ex.coalescer, run, len(queries),
                            timeout=120)
    return [r[0] for r in res], sum(r[1] for r in res)


# ---------------------------------------------------------------------------
# Tape compiler
# ---------------------------------------------------------------------------


class TestTapeCompile:
    def test_binary_and(self):
        tp = tape.compile_shape(("and", ("leaf", 0), ("leaf", 1)), 2)
        assert tp.instrs == ((tape.OP_AND, 0, 1),)

    def test_fold_decomposes_left(self):
        tp = tape.compile_shape(
            ("or", ("leaf", 0), ("leaf", 1), ("leaf", 2)), 3)
        assert tp.instrs == ((tape.OP_OR, 0, 1), (tape.OP_OR, ~0, 2))

    def test_not_is_andnot_of_exist(self):
        tp = tape.compile_shape(("not", ("leaf", 0), ("leaf", 1)), 2)
        assert tp.instrs == ((tape.OP_ANDNOT, 0, 1),)

    def test_dfuse_two_instructions(self):
        tp = tape.compile_shape(
            ("dfuse", ("leaf", 0), ("leaf", 1), ("leaf", 2)), 3)
        assert tp.instrs == ((tape.OP_ANDNOT, 0, 2),
                             (tape.OP_OR, ~0, 1))

    def test_pure_leaf_materializes_copy(self):
        tp = tape.compile_shape(("leaf", 0), 1)
        assert tp.instrs == ((tape.OP_COPY, 0, 0),)

    def test_shift_is_not_tape_eligible(self):
        with pytest.raises(tape.TapeError):
            tape.compile_shape(("shift", 2, ("leaf", 0)), 1)
        assert tape.try_compile(("shift", 2, ("leaf", 0)), 1) is None

    def test_length_cap(self):
        shape = ("or", *(("leaf", i % 2) for i in range(9)))
        with pytest.raises(tape.TapeError):
            tape.compile_shape(shape, 2, max_len=4)
        assert tape.try_compile(shape, 2, max_len=4) is None
        assert tape.try_compile(shape, 2, max_len=8) is not None

    def test_bad_leaf_slot(self):
        with pytest.raises(tape.TapeError):
            tape.compile_shape(("leaf", 3), 2)

    def test_size_class_pow2_with_floor(self):
        assert tape.size_class(1, 1) == (4, 4)
        assert tape.size_class(4, 4) == (4, 4)
        assert tape.size_class(5, 9) == (8, 16)


# ---------------------------------------------------------------------------
# Interpreter engines: randomized bit-exactness vs the host twins
# ---------------------------------------------------------------------------


def _rand_shape(rng, n_leaves, depth):
    if depth == 0 or rng.random() < 0.35:
        return ("leaf", rng.randrange(n_leaves))
    kind = rng.choice(["and", "or", "xor", "andnot", "not", "dfuse"])
    if kind == "not":
        return ("not", ("leaf", rng.randrange(n_leaves)),
                _rand_shape(rng, n_leaves, depth - 1))
    if kind == "dfuse":
        return ("dfuse", _rand_shape(rng, n_leaves, depth - 1),
                ("leaf", rng.randrange(n_leaves)),
                ("leaf", rng.randrange(n_leaves)))
    kids = [_rand_shape(rng, n_leaves, depth - 1)
            for _ in range(rng.randrange(2, 4))]
    return (kind, *kids)


def _rand_batch(rng, n_queries):
    batch, wants_stack, wants_counts = [], [], []
    for _ in range(n_queries):
        n_leaves = rng.randrange(1, 5)
        leaves = tuple(
            np.array([[rng.getrandbits(32) for _ in range(6)]
                      for _ in range(4)], dtype=np.uint32)
            for _ in range(n_leaves))
        shape = _rand_shape(rng, n_leaves, 3)
        batch.append((tape.compile_shape(shape, n_leaves), leaves))
        wants_stack.append(expr._host_tree(shape, leaves))
        wants_counts.append(expr._host_counts(shape, leaves))
    return batch, wants_stack, wants_counts


class TestInterpreter:
    def test_host_engine_bit_exact_randomized(self):
        rng = random.Random(11)
        for _ in range(4):
            batch, ws, wc = _rand_batch(rng, 6)
            for got, want in zip(tape.execute(batch), ws):
                np.testing.assert_array_equal(np.asarray(got), want)
            for got, want in zip(tape.execute(batch, counts=True), wc):
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(want))

    def test_device_engine_bit_exact_randomized(self):
        """The jitted scan/switch interpreter over jnp leaf stacks —
        the path a real accelerator (and the multi-CPU-device test
        platform) runs — against the same host twins."""
        import jax.numpy as jnp

        rng = random.Random(12)
        batch, ws, wc = _rand_batch(rng, 6)
        jbatch = [(tp, tuple(jnp.asarray(lv) for lv in ls))
                  for tp, ls in batch]
        for got, want in zip(tape.execute(jbatch), ws):
            np.testing.assert_array_equal(np.asarray(got), want)
        for got, want in zip(tape.execute(jbatch, counts=True), wc):
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))

    def test_one_note_dispatch_per_batch(self):
        rng = random.Random(13)
        batch, _, _ = _rand_batch(rng, 5)
        with bm.dispatch_counter() as dc:
            tape.execute(batch, counts=True)
        assert dc.launches == ["tape"]

    def test_bucket_overflow_refused(self):
        rng = random.Random(14)
        batch, _, _ = _rand_batch(rng, 2)
        with pytest.raises(tape.TapeError):
            tape.execute(batch, counts=True, tape_len=1, slots=1)


# ---------------------------------------------------------------------------
# Ragged coalescer: the acceptance pins
# ---------------------------------------------------------------------------


class TestRaggedCoalescer:
    @pytest.mark.parametrize("deltas", [False, True])
    def test_16_distinct_shapes_two_launches(self, ex, nocache,
                                             deltas):
        """THE acceptance bar: 16 concurrent queries over 16 distinct
        fused-expression shapes -> <= 2 device launches, every result
        bit-exact against per-query host evaluation — deltas off and
        on (pending ingest overlays put dfuse nodes in the shapes; the
        tape engine batches those too)."""
        if deltas:
            compactor.reset()
            ingest.configure(delta_enabled=True)
            rng = random.Random(99)
            for fi in range(3):
                f = ex.holder.index("i").field(f"f{fi}")
                rows = [rng.randrange(6) for _ in range(64)]
                cols = [rng.randrange(N_SHARDS * SHARD_WIDTH)
                        for _ in range(64)]
                f.import_bits(rows, cols)  # lands in the delta planes
        qs = [f"Count({t})" for t in SHAPES_16]
        assert len(set(SHAPES_16)) == 16
        expected = [_unbatched(ex, q) for q in qs]
        for q in qs:  # warm row/delta stacks so staging is cache hits
            ex.execute("i", q)
        stats = _attach(ex, window_s=2.0, max_batch=16)
        got, launches = _run_concurrent_counting(ex, qs)
        assert got == expected
        assert launches <= 2, launches
        snap = stats.snapshot()
        assert snap["coalescer.dispatches"] <= 2
        recs = [r for r in ex.recorder.recent_records()
                if r.coalesce is not None]
        assert recs, "no coalesced flight records"
        assert any(r.coalesce.get("tape") for r in recs)
        assert max(r.coalesce.get("shapes", 1) for r in recs) > 1

    def test_ragged_disabled_routes_fused_path_unchanged(self, ex,
                                                         nocache):
        """[ragged] enabled=false: buckets key on exact shape and every
        flush runs the fused program — the tape engine is NEVER
        entered (the production off-switch regression pin)."""
        _attach(ex, window_s=0.05, max_batch=16, ragged=False)
        qs = [f"Count({t})" for t in SHAPES_16[:6]]
        expected = [_unbatched(ex, q) for q in qs]
        tape_calls = []
        orig = tape.execute

        def spy(batch, **kw):
            tape_calls.append(len(batch))
            return orig(batch, **kw)

        tape.execute = spy
        try:
            got, _ = _run_concurrent_counting(ex, qs)
        finally:
            tape.execute = orig
        assert got == expected
        assert tape_calls == []

    def test_oversize_tape_falls_back_per_query(self, ex, nocache):
        """A query whose tape exceeds [ragged] max-tape falls back to
        the per-shape fused path FOR THAT QUERY (identical behavior),
        while its batchmates keep merging — and the fallback is
        counted."""
        before = tape.counters()["tape.oversize_fallbacks"]
        _attach(ex, window_s=0.5, max_batch=16, max_tape=1)
        # tape length 2 > cap 1 -> every one of these falls back
        qs = [f"Count(Union(Row(f0={a}), Row(f1={a}), Row(f2={a})))"
              for a in range(4)]
        expected = [_unbatched(ex, q) for q in qs]
        tape_calls = []
        orig = tape.execute

        def spy(batch, **kw):
            tape_calls.append(len(batch))
            return orig(batch, **kw)

        tape.execute = spy
        try:
            got, _ = _run_concurrent_counting(ex, qs)
        finally:
            tape.execute = orig
        assert got == expected
        assert tape_calls == []  # identical shapes merged via expr
        assert tape.counters()["tape.oversize_fallbacks"] > before

    def test_same_shape_bucket_takes_fast_path(self, ex, nocache):
        """A ragged bucket that fills homogeneously runs the
        specialized fused program, not the interpreter — the
        same-shape fast path is preserved under ragged keying."""
        _attach(ex, window_s=2.0, max_batch=4)
        qs = [f"Count(Intersect(Row(f0={a}), Row(f1=0)))"
              for a in range(4)]
        expected = [_unbatched(ex, q) for q in qs]
        tape_calls, expr_calls = [], []
        orig_t, orig_e = tape.execute, expr.evaluate

        def spy_t(batch, **kw):
            tape_calls.append(len(batch))
            return orig_t(batch, **kw)

        def spy_e(shape, leaves, **kw):
            expr_calls.append(shape)
            return orig_e(shape, leaves, **kw)

        tape.execute, expr.evaluate = spy_t, spy_e
        try:
            got, _ = _run_concurrent_counting(ex, qs)
        finally:
            tape.execute, expr.evaluate = orig_t, orig_e
        assert got == expected
        assert tape_calls == []
        assert len(expr_calls) == 1

    def test_shape_miss_accounting(self, ex, nocache):
        """The heterogeneity evidence: queries flushed with no
        same-shape partner count as coalescer.shape_misses, the flush
        records its distinct-shape count, and the module counters
        (scrape-time gauges) advance."""
        before = tape.counters()["coalescer.shape_misses"]
        stats = _attach(ex, window_s=2.0, max_batch=4)
        qs = ["Count(Intersect(Row(f0=1), Row(f1=2)))",
              "Count(Union(Row(f0=1), Row(f1=2)))",
              "Count(Xor(Row(f0=1), Row(f1=2)))",
              "Count(Difference(Row(f0=1), Row(f1=2)))"]
        got, _ = _run_concurrent_counting(ex, qs)
        assert got == [_unbatched(ex, q) for q in qs]
        snap = stats.snapshot()
        assert snap["coalescer.shape_distinct"]["max"] == 4
        assert tape.counters()["coalescer.shape_misses"] == before + 4
        # the scrape-time surface: module counters render as gauges
        gauges = _stats.MemStatsClient()
        tape.publish_gauges(gauges)
        assert gauges.snapshot()["coalescer.shape_misses"] >= 4

    def test_mixed_indexes_cannot_corrupt_each_other(self, ex,
                                                     nocache):
        """Ragged buckets are index-agnostic by design (the launch is
        pure set algebra over staged stacks) — queries from two
        indexes merging into one bucket stay bit-exact."""
        idx2 = ex.holder.create_index("j")
        rng = random.Random(5)
        f = idx2.create_field("g")
        rows = [rng.randrange(4) for _ in range(300)]
        cols = [rng.randrange(N_SHARDS * SHARD_WIDTH)
                for _ in range(300)]
        f.import_bits(rows, cols)
        _attach(ex, window_s=2.0, max_batch=4)
        q_i = "Count(Intersect(Row(f0=1), Row(f1=2)))"
        q_j = "Count(Union(Row(g=0), Row(g=1)))"
        wave = [("i", q_i), ("j", q_j)]
        got = map_behind_launch(
            ex.coalescer, lambda k: ex.execute(*wave[k])[0], 2,
            timeout=120)
        out = {"i": got[0], "j": got[1]}
        assert out["i"] == _unbatched(ex, q_i)
        ex.fuse_shards = False
        try:
            want_j = ex.execute("j", q_j)[0]
        finally:
            ex.fuse_shards = True
        assert out["j"] == want_j


# ---------------------------------------------------------------------------
# HTTP surface
# ---------------------------------------------------------------------------


class TestHTTP:
    def test_debug_ragged_document(self, tmp_path):
        from pilosa_tpu.server.server import Server

        srv = Server(str(tmp_path / "srv"), port=0,
                     coalescer_enabled=True, ragged_max_tape=24,
                     ragged_prewarm=False)
        srv.open()
        try:
            with urllib.request.urlopen(f"{srv.uri}/debug/ragged",
                                        timeout=10) as resp:
                d = json.loads(resp.read())
            assert d["coalescer"]["ragged"] is True
            assert d["coalescer"]["maxTape"] == 24
            assert "tape.executions" in d["counters"]
            assert isinstance(d["programs"], list)
        finally:
            srv.close()

    def test_parallel_distinct_shape_clients_share_launches(
            self, tmp_path):
        """End-to-end through the query route: 12 concurrent clients
        with 12 distinct shapes answer correctly in strictly fewer
        launches than queries."""
        from pilosa_tpu.server.server import Server

        srv = Server(str(tmp_path / "srv"), port=0,
                     coalescer_enabled=True,
                     coalescer_window_ms=150.0,
                     coalescer_max_batch=12,
                     ragged_prewarm=False)
        srv.open()
        try:
            srv.api.create_index("i")
            for fi in range(3):
                srv.api.create_field("i", f"f{fi}")
                rng = random.Random(20 + fi)
                rows, cols = [], []
                for row in range(6):
                    for _ in range(150):
                        rows.append(row)
                        cols.append(rng.randrange(2 * SHARD_WIDTH))
                srv.api.import_bits("i", f"f{fi}", rows, cols)
            qs = [f"Count({t})" for t in SHAPES_16[:12]]
            expected = [srv.api.query("i", q, coalesce=False,
                                      cache=False)[0] for q in qs]

            def post(q):
                req = urllib.request.Request(
                    f"{srv.uri}/index/i/query?nocache=1",
                    data=q.encode(), method="POST")
                with urllib.request.urlopen(req, timeout=60) as resp:
                    return json.loads(resp.read())["results"][0]

            out = map_behind_launch(
                srv.node.executor.coalescer, lambda i: post(qs[i]),
                len(qs), timeout=120)
            assert out == expected
            snap = srv.stats.snapshot()
            assert snap["coalescer.dispatches"] < len(qs)
        finally:
            srv.close()
