"""Generation-stamped query result cache (runtime/resultcache.py).

The contract under test is the acceptance bar of the perf_opt round:
two identical Count queries cost exactly ONE device dispatch; any
interleaved mutation makes the second query recompute (bit-exact, no
stale read ever); ``?nocache=1`` forces re-execution; the cache never
exceeds its byte budget under churn; a 3-node cluster serves hits from
per-node entries with correct invalidation after a broadcasted import;
and EVERY fragment mutation path bumps the generation token the cache
stamps entries with (a missed bump is a silent stale-read bug)."""

from __future__ import annotations

import json
import random
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from pilosa_tpu.models.field import FieldOptions, _frag_gen
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.parallel.executor import ExecOptions, Executor
from pilosa_tpu.runtime import resultcache
from pilosa_tpu.shardwidth import SHARD_WIDTH

N_SHARDS = 4


@pytest.fixture
def ex(tmp_path):
    holder = Holder(str(tmp_path / "rc"))
    idx = holder.create_index("i")
    rng = random.Random(7)
    for fi in range(2):
        f = idx.create_field(f"f{fi}")
        rows, cols = [], []
        for row in range(4):
            for _ in range(200):
                rows.append(row)
                cols.append(rng.randrange(N_SHARDS * SHARD_WIDTH))
        f.import_bits(rows, cols)
        idx.import_existence(cols)
    yield Executor(holder)
    holder.close()


def _fresh(ex, q):
    """Ground truth: a forced re-execution (cache bypassed)."""
    return ex.execute("i", q, opt=ExecOptions(cache=False))[0]


# ---------------------------------------------------------------------------
# The pinned acceptance regression
# ---------------------------------------------------------------------------


class TestPinnedRegression:
    def test_repeat_count_one_dispatch(self, ex):
        """Two identical Count queries -> exactly 1 device dispatch;
        the repeat is a dictionary lookup."""
        q = "Count(Intersect(Row(f0=1), Row(f1=2)))"
        with bm.dispatch_counter() as dc:
            r1 = ex.execute("i", q)[0]
            r2 = ex.execute("i", q)[0]
        assert r1 == r2 == _fresh(ex, q)
        assert dc.n == 1, dc.launches

    def test_interleaved_import_recomputes(self, ex):
        """A mutation between two identical queries bumps the
        generation: 2 dispatches, bit-exact results."""
        q = "Count(Row(f0=1))"
        with bm.dispatch_counter() as dc:
            before = ex.execute("i", q)[0]
            ex.execute("i", f"Set({2 * SHARD_WIDTH + 4999}, f0=1)")
            after = ex.execute("i", q)[0]
        assert dc.n == 2, dc.launches
        assert after == before + 1
        assert after == _fresh(ex, q)

    def test_nocache_forces_two_dispatches(self, ex):
        q = "Count(Row(f1=3))"
        opt = ExecOptions(cache=False)
        with bm.dispatch_counter() as dc:
            a = ex.execute("i", q, opt=opt)[0]
            b = ex.execute("i", q, opt=opt)[0]
        assert a == b
        assert dc.n == 2, dc.launches

    def test_row_topn_groupby_hits_are_bit_exact(self, ex):
        """Every cached root kind answers identically to a forced
        recomputation — hit or miss is invisible to the caller."""
        for q in ("Row(f0=1)",
                  "Union(Intersect(Row(f0=1), Row(f1=1)), Row(f0=2))",
                  "TopN(f0)", "TopN(f0, Row(f1=1), n=3)",
                  "GroupBy(Rows(f0), Rows(f1), limit=6)",
                  "MinRow(field=f0)", "MaxRow(field=f0)"):
            first = ex.execute("i", q)[0]
            second = ex.execute("i", q)[0]  # cached
            fresh = _fresh(ex, q)
            for got in (first, second):
                if hasattr(got, "columns"):
                    assert list(got.columns()) == list(fresh.columns()), q
                elif isinstance(got, list) and got \
                        and hasattr(got[0], "group"):
                    key = lambda gcs: [  # noqa: E731
                        ([(fr.field, fr.row_id) for fr in gc.group],
                         gc.count) for gc in gcs]
                    assert key(got) == key(fresh), q
                elif isinstance(got, list):
                    assert [(p.id, p.count) for p in got] == \
                        [(p.id, p.count) for p in fresh], q
                else:
                    assert got == fresh, q

    def test_mutation_invalidates_every_kind(self, ex):
        """Row/TopN/GroupBy entries all miss after a write touching
        their fragments — no stale read on any cached path."""
        queries = ("Row(f0=1)", "TopN(f0)", "GroupBy(Rows(f0))")
        for q in queries:
            ex.execute("i", q)  # fill
        ex.execute("i", f"Set({SHARD_WIDTH + 777}, f0=1)")
        for q in queries:
            got = ex.execute("i", q)[0]
            fresh = _fresh(ex, q)
            if hasattr(got, "columns"):
                assert SHARD_WIDTH + 777 in got.columns()
                assert list(got.columns()) == list(fresh.columns())
            elif got and hasattr(got[0], "group"):
                assert [(tuple((fr.field, fr.row_id)
                               for fr in gc.group), gc.count)
                        for gc in got] == \
                    [(tuple((fr.field, fr.row_id) for fr in gc.group),
                      gc.count) for gc in fresh]
            else:
                assert [(p.id, p.count) for p in got] == \
                    [(p.id, p.count) for p in fresh]

    def test_flight_record_carries_cached_and_key(self, ex):
        q = "Count(Row(f0=2))"
        ex.execute("i", q)
        miss = ex.recorder.recent_records()[-1].to_dict()
        ex.execute("i", q)
        hit = ex.recorder.recent_records()[-1].to_dict()
        assert miss["cached"] is False
        assert hit["cached"] is True
        assert hit["path"] == "cached"
        assert hit["deviceLaunches"] == 0
        # the key digest correlates repeated shapes hit or miss
        assert miss["cacheKey"] == hit["cacheKey"]

    def test_partial_hit_never_renders_cached(self):
        """A query where a cache hit served only PART of the work
        (e.g. filtered TopN whose unfiltered full-counts pass hit
        while the filtered scan dispatched) must not read as fully
        cache-served: the documented meaning of ``cached: true`` is
        "answered with zero device launches on this node"."""
        from pilosa_tpu import observe

        rec = observe.QueryRecord(1, "i", "TopN(f)")
        rec.cached = True
        rec.note_launch("expr.fused_counts")
        d = rec.to_dict()
        assert d["cached"] is False
        assert d["deviceLaunches"] == 1
        rec2 = observe.QueryRecord(2, "i", "Count(Row(f=1))")
        rec2.cached = True
        assert rec2.to_dict()["cached"] is True


# ---------------------------------------------------------------------------
# ResultCache unit semantics
# ---------------------------------------------------------------------------


class TestResultCacheUnit:
    def test_gen_mismatch_is_invalidation(self):
        rc = resultcache.ResultCache()
        rc.put("k", (1, 2), "v", 100)
        hit, v = rc.get("k", (1, 2))
        assert hit and v == "v"
        hit, v = rc.get("k", (1, 3))  # a fragment mutated
        assert not hit
        s = rc.stats_dict()
        assert s["invalidations"] == 1 and s["entries"] == 0
        # the stale entry's bytes were released immediately
        assert s["bytes"] == 0

    def test_ttl_expiry(self, monkeypatch):
        rc = resultcache.ResultCache(ttl_s=10.0)
        t = [1000.0]
        monkeypatch.setattr(resultcache.time, "monotonic",
                            lambda: t[0])
        rc.put("k", (1,), "v", 10)
        assert rc.get("k", (1,))[0]
        t[0] += 11.0
        assert not rc.get("k", (1,))[0]

    def test_strict_budget_never_exceeded_under_churn(self):
        """Mirrors test_residency's tiny-budget pattern: hammer a
        too-small cache with distinct entries; the byte total must
        never exceed the budget (not even transiently observable) and
        evictions must be counted."""
        budget = 4096
        rc = resultcache.ResultCache(budget_bytes=budget,
                                     max_entry_bytes=1024)
        for i in range(200):
            rc.put(("k", i), (i,), bytes(400), 400)
            assert rc.bytes <= budget
        s = rc.stats_dict()
        assert s["evictions"] > 0
        assert s["bytes"] <= budget
        # LRU: the newest entries survived
        assert rc.get(("k", 199), (199,))[0]
        assert not rc.get(("k", 0), (0,))[0]

    def test_oversize_entry_refused(self):
        rc = resultcache.ResultCache(budget_bytes=1 << 20,
                                     max_entry_bytes=1000)
        assert not rc.put("big", (1,), "v", 2000)
        assert rc.stats_dict()["skippedOversize"] == 1
        assert rc.stats_dict()["entries"] == 0

    def test_disabled_cache_is_inert(self):
        rc = resultcache.ResultCache(enabled=False)
        assert not rc.put("k", (1,), "v", 10)
        assert rc.get("k", (1,)) == (False, None)
        assert rc.stats_dict()["misses"] == 0

    def test_executor_budget_churn_bit_exact(self, ex):
        """Product-path churn: a tiny budget evicts constantly while
        every answer stays bit-exact against forced recomputation."""
        resultcache.reset(budget_bytes=2048, max_entry_bytes=1024)
        qs = [f"Count(Intersect(Row(f0={a}), Row(f1={b})))"
              for a in range(4) for b in range(4)]
        for _ in range(3):
            for q in qs:
                assert ex.execute("i", q)[0] == _fresh(ex, q)
                assert resultcache.cache().bytes <= 2048
        assert resultcache.cache().stats_dict()["evictions"] > 0

    def test_result_nbytes_recurses_dataclass_results(self):
        """GroupBy results are dataclasses (GroupCount holding
        FieldRow lists) — charging them as 32-byte scalars would let a
        GroupBy-heavy workload exceed the budget by ~10x in real
        memory, so the estimator must recurse into their fields."""
        from pilosa_tpu.parallel.results import FieldRow, GroupCount

        g = GroupCount(group=[FieldRow(field="x" * 40, row_id=7),
                              FieldRow(field="y" * 40, row_key="k" * 30)],
                       count=3)
        nb = resultcache.result_nbytes(g)
        # at minimum the two long strings plus container overheads
        assert nb > 2 * 40 + 30
        assert nb == (64            # GroupCount
                      + 64          # group list
                      + 2 * 64     # two FieldRows
                      + (48 + 40) + 32 + (48 + 0) + 32   # FieldRow 1
                      + (48 + 40) + 32 + (48 + 30) + 32  # FieldRow 2
                      + 32)         # count


class TestSingleFlight:
    """Stampede control: concurrent same-stamp missers wait for the
    first misser's fill instead of re-executing (the streaming-ingest
    round — every delta write invalidates its key, so the convoy of
    readers behind each invalidation used to multiply device work by
    its own depth)."""

    def test_follower_serves_leader_fill(self):
        rc = resultcache.ResultCache()
        hit, _ = rc.get("k", (1,))   # this thread is now the leader
        assert not hit
        got = []

        def follower():
            got.append(rc.get("k", (1,), wait_s=5.0))

        t = threading.Thread(target=follower)
        t.start()
        # wait until the follower has actually joined the flight, then
        # land the leader's fill
        for _ in range(500):
            if rc.stats_dict()["flightJoins"] == 1:
                break
            time.sleep(0.002)
        rc.put("k", (1,), "v", 10)
        t.join(timeout=5)
        assert got == [(True, "v")]
        s = rc.stats_dict()
        assert s["flightJoins"] == 1 and s["flightServed"] == 1
        assert s["flightsOpen"] == 0

    def test_leader_reprobe_never_waits_on_itself(self):
        rc = resultcache.ResultCache()
        assert not rc.get("k", (1,))[0]
        t0 = time.monotonic()
        assert not rc.get("k", (1,))[0]  # same thread: no self-wait
        assert time.monotonic() - t0 < 0.5

    def test_zero_wait_probe_never_blocks(self):
        rc = resultcache.ResultCache()
        assert not rc.get("k", (1,))[0]

        def probe():
            t0 = time.monotonic()
            hit, _ = rc.get("k", (1,), wait_s=0)
            return (hit, time.monotonic() - t0)

        with ThreadPoolExecutor(max_workers=1) as pool:
            hit, took = pool.submit(probe).result(timeout=5)
        assert not hit and took < 0.5

    def test_mismatched_stamp_never_joins(self):
        """A reader whose stamp moved past the open flight's must
        compute, not wait — the flight's fill could never match."""
        rc = resultcache.ResultCache()
        assert not rc.get("k", (1,))[0]  # open flight stamped (1,)

        def probe_newer():
            t0 = time.monotonic()
            hit, _ = rc.get("k", (2,), wait_s=5.0)
            return (hit, time.monotonic() - t0)

        with ThreadPoolExecutor(max_workers=1) as pool:
            hit, took = pool.submit(probe_newer).result(timeout=5)
        assert not hit and took < 0.5
        assert rc.stats_dict()["flightJoins"] == 0

    def test_refused_fill_releases_waiters(self):
        """An oversize put must still resolve the flight: the waiter
        wakes, misses, and computes itself rather than hanging."""
        rc = resultcache.ResultCache(max_entry_bytes=1000)
        assert not rc.get("k", (1,))[0]
        got = []

        def follower():
            got.append(rc.get("k", (1,), wait_s=5.0)[0])

        t = threading.Thread(target=follower)
        t.start()
        for _ in range(500):
            if rc.stats_dict()["flightJoins"] == 1:
                break
            time.sleep(0.002)
        assert not rc.put("k", (1,), "v", 10_000)  # oversize: refused
        t.join(timeout=5)
        assert got == [False]
        # the refusal marks the key no-flight: an uncacheable key can
        # never serve waiters, so later missers compute immediately —
        # no new flight opens and nobody queues behind a doomed fill
        assert rc.stats_dict()["flightsOpen"] == 0
        t0 = time.monotonic()
        assert not rc.get("k", (1,))[0]
        assert time.monotonic() - t0 < 0.5
        assert rc.stats_dict()["flightsOpen"] == 0
        # a fill that actually fits readmits the key
        rc.put("k", (1,), "small", 10)
        assert rc.get("k", (1,)) == (True, "small")


# ---------------------------------------------------------------------------
# Generation-bump audit: every mutation path must invalidate
# ---------------------------------------------------------------------------


MUTATIONS = [
    ("set_bit", lambda fr: fr.set_bit(1, 77)),
    ("clear_bit", lambda fr: (fr.set_bit(1, 78), fr.clear_bit(1, 78))),
    ("clear_row", lambda fr: (fr.set_bit(2, 79), fr.clear_row(2))),
    ("set_row_store", lambda fr: fr.set_row(
        3, np.arange(fr.n_words, dtype=np.uint32) % 2)),
    ("import_positions", lambda fr: fr.import_positions(
        np.array([5 * fr.width // 8, 5 * fr.width // 8 + 1],
                 dtype=np.uint64))),
    ("import_positions_clear", lambda fr: (
        fr.import_positions(np.array([13], dtype=np.uint64)),
        fr.import_positions((), np.array([13], dtype=np.uint64)))),
    ("bsi_set_value", lambda fr: fr.set_value(40, 8, 123)),
    ("bsi_clear_value", lambda fr: (fr.set_value(41, 8, 5),
                                    fr.clear_value(41, 8))),
]


class TestGenerationAudit:
    @pytest.mark.parametrize("name,mutate",
                             MUTATIONS, ids=[m[0] for m in MUTATIONS])
    def test_mutation_bumps_generation(self, name, mutate):
        from pilosa_tpu.models.fragment import Fragment

        fr = Fragment(None, "i", "f", "standard", 0)
        tok0 = _frag_gen(fr)
        mutate(fr)
        assert _frag_gen(fr) != tok0, \
            f"{name} did not bump the generation (silent stale reads)"

    def test_import_roaring_bumps_generation(self):
        from pilosa_tpu.models.fragment import Fragment

        src = Fragment(None, "i", "f", "standard", 0)
        src.set_bit(0, 10)
        src.set_bit(1, 20)
        blob = src.to_roaring()
        fr = Fragment(None, "i", "f", "standard", 0)
        tok0 = _frag_gen(fr)
        fr.import_roaring(blob)
        assert _frag_gen(fr) != tok0
        # clear-mode too (the delete half of replica reconciliation)
        tok1 = _frag_gen(fr)
        fr.import_roaring(blob, clear=True)
        assert _frag_gen(fr) != tok1

    def test_field_import_paths_bump_fragment_generations(self, tmp_path):
        holder = Holder(str(tmp_path / "gen"))
        idx = holder.create_index("i")
        f = idx.create_field("f")
        f.import_bits([1, 1], [3, SHARD_WIDTH + 3])
        view = f.view("standard")
        toks = {s: _frag_gen(view.fragment(s)) for s in (0, 1)}
        f.import_bits([1, 1], [4, SHARD_WIDTH + 4])
        for s in (0, 1):
            assert _frag_gen(view.fragment(s)) != toks[s]
        fv = idx.create_field("v", FieldOptions.int_field(0, 1000))
        fv.import_values([7], [55])
        vview = fv.view(fv.bsi_view_name)
        tok = _frag_gen(vview.fragment(0))
        fv.import_values([7], [56])
        assert _frag_gen(vview.fragment(0)) != tok
        holder.close()

    def test_restore_reopen_changes_token(self, tmp_path):
        """A fragment reloaded from disk (restore / resize re-fetch)
        is a NEW object: even at a colliding _gen the (uid, gen) token
        differs, so a stale cached stamp can never validate."""
        from pilosa_tpu.models.fragment import Fragment

        path = str(tmp_path / "frag")
        fr = Fragment(path, "i", "f", "standard", 0)
        fr.set_bit(1, 5)
        tok0 = _frag_gen(fr)
        fr.close()
        re = Fragment(path, "i", "f", "standard", 0)
        assert _frag_gen(re) != tok0
        re.close()

    def test_time_view_creation_invalidates_time_range(self, tmp_path):
        """A timestamped Set into a FRESH time quantum creates a new
        view: the covering-view set (part of the key) changes and the
        repeat query recomputes — never serves the pre-write cover."""
        holder = Holder(str(tmp_path / "tq"))
        idx = holder.create_index("i")
        idx.create_field("t", FieldOptions.time_field("YMD"))
        ex = Executor(holder)
        for s in range(2):
            ex.execute(
                "i", f"Set({s * SHARD_WIDTH + 1}, t=1, "
                     f"2019-01-02T00:00)")
        # Count root: a bare single-leaf Row is a passthrough with no
        # launch at all, so the dispatch pin needs the fused count
        q = "Count(Row(t=1, from=2019-01-01T00:00, to=2019-03-01T00:00))"
        with bm.dispatch_counter() as dc:
            before = ex.execute("i", q)[0]
            again = ex.execute("i", q)[0]
        assert before == again == 2
        assert dc.n == 1, dc.launches  # repeat was a cache hit
        # first write into a new day -> new views -> fresh cover
        ex.execute("i", f"Set({SHARD_WIDTH + 9}, t=1, 2019-02-05T00:00)")
        after = ex.execute("i", q)[0]
        assert after == 3 == _fresh(ex, q)
        holder.close()


# ---------------------------------------------------------------------------
# Concurrency: imports racing cached reads
# ---------------------------------------------------------------------------


class TestRaceImportsVsCachedReads:
    def test_no_stale_result_under_concurrent_imports(self, tmp_path):
        """A writer monotonically ADDS bits while readers interleave
        cached and forced-fresh executions.  Monotonicity gives a
        serializability bound: every cached read must land between the
        fresh counts read immediately before and after it — a stale
        serve would undershoot the lower bound.  Final state must be
        bit-exact vs fresh recomputation."""
        holder = Holder(str(tmp_path / "race"))
        idx = holder.create_index("i")
        f = idx.create_field("f")
        # pre-seed every shard so the shard set (part of the key) is
        # stable for the whole race
        f.import_bits([1] * N_SHARDS,
                      [s * SHARD_WIDTH for s in range(N_SHARDS)])
        idx.import_existence([s * SHARD_WIDTH for s in range(N_SHARDS)])
        ex = Executor(holder)
        q = "Count(Row(f=1))"
        stop = threading.Event()
        errs: list = []

        def writer():
            try:
                off = 1
                while not stop.is_set() and off < 4000:
                    f.import_bits([1], [(off % N_SHARDS) * SHARD_WIDTH
                                        + off])
                    off += 1
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        def reader():
            try:
                for _ in range(150):
                    lo = _fresh(ex, q)
                    cached = ex.execute("i", q)[0]
                    hi = _fresh(ex, q)
                    assert lo <= cached <= hi, (lo, cached, hi)
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        w = threading.Thread(target=writer)
        rs = [threading.Thread(target=reader) for _ in range(3)]
        w.start()
        for r in rs:
            r.start()
        for r in rs:
            r.join(timeout=120)
        stop.set()
        w.join(timeout=30)
        assert not errs, errs[0]
        assert ex.execute("i", q)[0] == _fresh(ex, q)
        holder.close()


# ---------------------------------------------------------------------------
# Cluster: per-node caches + broadcasted-import invalidation
# ---------------------------------------------------------------------------


class TestCluster:
    def test_three_node_hits_and_broadcast_invalidation(self, tmp_path):
        from pilosa_tpu.api import API
        from tests.test_cluster import make_cluster

        _, nodes = make_cluster(tmp_path, n=3, replica_n=1)
        nodes[0].create_index("i")
        nodes[0].create_field("i", "f")
        api = API(nodes[0])
        rng = random.Random(3)
        cols = [rng.randrange(9 * SHARD_WIDTH) for _ in range(600)]
        api.import_bits("i", "f", [1] * len(cols), cols)
        q = "Count(Row(f=1))"
        expect = len(set(cols))
        rc = resultcache.cache()
        assert api.query("i", q)[0] == expect  # fill everywhere
        s0 = rc.stats_dict()
        assert api.query("i", q)[0] == expect  # hits everywhere
        s1 = rc.stats_dict()
        # the origin's local group AND each remote node answered from
        # their own (holder-keyed) entries — at least origin + remotes
        assert s1["hits"] - s0["hits"] >= 3
        assert s1["fills"] == s0["fills"]
        # per-node separation: the three holders have distinct uids,
        # so their entries can never collide in the shared test-process
        # cache (production nodes are separate processes anyway)
        assert len({n.holder.uid for n in nodes}) == 3
        # a broadcasted import re-homes one shard's bits: every node
        # that owns touched fragments must recompute
        newcols = [3 * SHARD_WIDTH + 123456 % SHARD_WIDTH,
                   7 * SHARD_WIDTH + 42]
        api.import_bits("i", "f", [1] * len(newcols), newcols)
        expect2 = len(set(cols) | set(newcols))
        assert api.query("i", q)[0] == expect2
        # and a repeat of THAT is served from cache again, still exact
        s2 = rc.stats_dict()
        assert api.query("i", q)[0] == expect2
        assert rc.stats_dict()["hits"] > s2["hits"]
        for n in nodes:
            n.holder.close()

    def test_nocache_forwarded_to_remote_nodes(self, tmp_path):
        """?nocache=1 must force a real execution on EVERY node: the
        origin forwards the flag on its node-to-node sub-queries, so
        peers may not answer from their per-shard entries (and, with
        the probe skipped entirely, may not refill them either)."""
        from pilosa_tpu.api import API
        from tests.test_cluster import make_cluster

        _, nodes = make_cluster(tmp_path, n=3, replica_n=1)
        nodes[0].create_index("i")
        nodes[0].create_field("i", "f")
        api = API(nodes[0])
        rng = random.Random(11)
        cols = [rng.randrange(9 * SHARD_WIDTH) for _ in range(400)]
        api.import_bits("i", "f", [1] * len(cols), cols)
        q = "Count(Row(f=1))"
        expect = len(set(cols))
        rc = resultcache.cache()
        assert api.query("i", q)[0] == expect  # fill everywhere
        s0 = rc.stats_dict()
        got = nodes[0].executor.execute(
            "i", q, opt=ExecOptions(cache=False))[0]
        assert got == expect
        s1 = rc.stats_dict()
        assert s1["hits"] == s0["hits"], \
            "a node served a ?nocache=1 sub-query from its cache"
        assert s1["fills"] == s0["fills"]
        for n in nodes:
            n.holder.close()


# ---------------------------------------------------------------------------
# HTTP surface: ?nocache=1, /debug/resultcache, cache.* families
# ---------------------------------------------------------------------------


def _post(uri, path, body):
    data = (json.dumps(body) if isinstance(body, dict)
            else body).encode()
    req = urllib.request.Request(
        uri + path, data=data, method="POST",
        headers={"Content-Type": "application/json"}
        if isinstance(body, dict) else {})
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())


def _get(uri, path):
    with urllib.request.urlopen(uri + path, timeout=30) as resp:
        return json.loads(resp.read())


class TestHTTPSurface:
    @pytest.fixture
    def srv(self, tmp_path):
        from pilosa_tpu.server.server import Server

        s = Server(str(tmp_path / "srv"), port=0)
        s.open()
        _post(s.uri, "/index/i", {})
        _post(s.uri, "/index/i/field/f", {})
        for sh in range(3):
            for k in range(4):
                _post(s.uri, "/index/i/query",
                      {"query": f"Set({sh * SHARD_WIDTH + k}, f=1)"})
        yield s
        s.close()

    def test_nocache_param_and_profile_cached_flag(self, srv):
        q = {"query": "Count(Row(f=1))"}
        r1 = _post(srv.uri, "/index/i/query?profile=1", q)
        assert r1["profile"]["cached"] is False
        r2 = _post(srv.uri, "/index/i/query?profile=1", q)
        assert r2["results"] == r1["results"] == [12]
        assert r2["profile"]["cached"] is True
        assert r2["profile"]["deviceLaunches"] == 0
        r3 = _post(srv.uri, "/index/i/query?profile=1&nocache=1", q)
        assert r3["results"] == [12]
        assert r3["profile"]["cached"] is False
        assert r3["profile"]["deviceLaunches"] > 0

    def test_debug_resultcache_document(self, srv):
        q = {"query": "Count(Row(f=1))"}
        _post(srv.uri, "/index/i/query", q)
        _post(srv.uri, "/index/i/query", q)
        d = _get(srv.uri, "/debug/resultcache")
        assert d["enabled"] is True
        assert d["hits"] >= 1 and d["fills"] >= 1
        assert d["bytes"] <= d["budget"]
        assert d["top"] and {"key", "bytes", "ageS", "hits"} <= \
            set(d["top"][0])

    def test_metrics_carries_cache_families(self, srv):
        from tools import check_metrics

        _post(srv.uri, "/index/i/query", {"query": "Count(Row(f=1))"})
        with urllib.request.urlopen(srv.uri + "/metrics") as resp:
            text = resp.read().decode()
        fams = check_metrics.check_families(
            text, check_metrics.ALL_FAMILIES)
        assert set(fams) == set(check_metrics.ALL_FAMILIES)
        assert "cache_hits" in text and "cache_bytes" in text
        snap = _get(srv.uri, "/debug/vars")
        assert "cache.fills" in snap


# ---------------------------------------------------------------------------
# Satellite: fused-program cache eviction telemetry (ops/expr)
# ---------------------------------------------------------------------------


class TestProgramEvictionTelemetry:
    def test_eviction_counted_and_warned_once(self, caplog):
        import logging

        from pilosa_tpu.ops import expr

        expr.set_program_cache_size(2)
        try:
            shapes = [("and", ("leaf", 0), ("leaf", 1)),
                      ("or", ("leaf", 0), ("leaf", 1)),
                      ("xor", ("leaf", 0), ("leaf", 1)),
                      ("andnot", ("leaf", 0), ("leaf", 1))]
            with caplog.at_level(logging.WARNING,
                                 logger="pilosa_tpu.ops.expr"):
                for shape in shapes:
                    expr._compiled(shape, False)
            # EXACT count: 4 shapes through a 2-slot cache = 2 popped
            # residents.  (misses - currsize inference would also say 2
            # here, but over-counts under racing same-shape builds or a
            # failed build — the explicit counter cannot.)
            assert expr.program_evictions() == 2
            warnings = [r for r in caplog.records
                        if "fused-program cache overflowed"
                        in r.getMessage()]
            assert len(warnings) == 1  # one line, not one per miss
            # devobs surfaces the running count as a gauge and on
            # /debug/devices
            from pilosa_tpu import devobs
            from pilosa_tpu import stats as _stats

            st = _stats.MemStatsClient()
            devobs.observer().publish_gauges(st)
            assert st.snapshot()["compile.program_evictions"] >= 1
            assert devobs.observer().snapshot()["compile"][
                "programEvictions"] >= 1
            # a repeat of a RESIDENT shape is a pure hit — no count
            # drift (this is where misses-based inference went wrong)
            before = expr.program_evictions()
            expr._compiled(shapes[-1], False)
            assert expr.program_evictions() == before
            # a failed build (unknown shape kind raises during
            # tracing) never charges an eviction either
            with pytest.raises(Exception):
                expr._compiled(("bogus",), False)
            assert expr.program_evictions() == before
        finally:
            expr.set_program_cache_size(
                expr.DEFAULT_PROGRAM_CACHE_SIZE)
