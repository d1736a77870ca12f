"""Query flight recorder: record shape, ring buffer, slow-query log,
latency histograms with exemplars, ?profile=1, /debug/queries, and the
distributed profile whose device-launch count must match the
ops/bitmap.py dispatch hook exactly."""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from pilosa_tpu import observe, stats as _stats
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.parallel.executor import Executor
from pilosa_tpu.server.server import Server
from pilosa_tpu.shardwidth import SHARD_WIDTH
from tests.coalesce_batch import (map_behind_launch, run_behind_launch,
                                   wait_until)


def _post(uri, path, obj=None):
    body = json.dumps(obj or {}).encode()
    req = urllib.request.Request(uri + path, data=body, method="POST")
    req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req, timeout=10) as resp:
        return json.loads(resp.read() or b"null")


def _get(uri, path):
    with urllib.request.urlopen(uri + path, timeout=35) as resp:
        return json.loads(resp.read())


class _CapturingLogger:
    def __init__(self):
        self.lines: list[str] = []

    def printf(self, fmt, *args):
        self.lines.append(fmt % args if args else fmt)


@pytest.fixture
def ex(tmp_path):
    holder = Holder(str(tmp_path / "obs"))
    idx = holder.create_index("i")
    idx.create_field("f")
    e = Executor(holder)
    for s in range(3):
        for k in range(4):
            e.execute("i", f"Set({s * SHARD_WIDTH + k}, f=7)")
    yield e
    holder.close()


class TestRecorder:
    def test_record_shape(self, ex):
        ex.execute("i", "Count(Row(f=7))")
        rec = ex.recorder.recent_records()[-1]
        d = rec.to_dict()
        assert d["pql"] == "Count(Row(f=7))"
        assert d["index"] == "i"
        assert d["shards"] == 3
        assert d["active"] is False
        assert d["elapsedMs"] > 0
        assert d["traceID"]
        assert d["resultSizes"] == [1]
        assert d["deviceLaunches"] >= 1
        assert sum(d["launchKinds"].values()) == d["deviceLaunches"]
        names = [s["name"] for s in d["stages"]]
        assert "translate" in names
        assert "execute.Count" in names
        # a Count holds no key: nothing to translate back, no stage
        # (PR 43); a Row could, so its read has one
        assert "translateResults" not in names
        ex.execute("i", "Row(f=7)")
        assert "translateResults" in [
            s["name"] for s in
            ex.recorder.recent_records()[-1].to_dict()["stages"]]
        # single-node host mode: the fused all-shard path
        assert d["path"] == "fused"
        assert any(s["name"] == "map.fused" for s in d["stages"])

    def test_record_holds_the_text_its_caller_had(self, ex, monkeypatch):
        """``API.query`` parses (the write limit) and hands the
        executor the tree AND the text it came from: the record holds
        that text and the tree is not serialised again for it (PR 43:
        the gap table's ``pql.parse -> exec`` held that)."""
        from pilosa_tpu.pql import Query, parse

        q = parse("Count(Row(f=7))")
        ex.execute("i", q)
        assert ex.recorder.recent_records()[-1].pql == "Count(Row(f=7))"
        monkeypatch.setattr(Query, "__str__", lambda self: 1 / 0)
        ex.execute("i", q, text="Count( Row(f=7) )")
        assert ex.recorder.recent_records()[-1].pql == "Count( Row(f=7) )"

    def test_per_shard_timings_on_unfused_path(self, ex):
        ex.fuse_shards = False
        ex.execute("i", "Count(Row(f=7))")
        d = ex.recorder.recent_records()[-1].to_dict()
        assert d["path"] == "per-shard"
        assert {t["shard"] for t in d["shardTimings"]} == {0, 1, 2}
        assert any(s["name"] == "map" for s in d["stages"])

    def test_error_recorded(self, ex):
        with pytest.raises(Exception):
            ex.execute("i", "Count(Row(nope=1))")
        d = ex.recorder.recent_records()[-1].to_dict()
        assert "error" in d and "nope" in d["error"]

    def test_ring_buffer_eviction(self, ex):
        ex.recorder = observe.FlightRecorder(recent=4)
        for k in range(6):
            ex.execute("i", f"Count(Row(f={k}))")
        recs = ex.recorder.recent_records()
        assert len(recs) == 4
        # oldest two evicted
        assert [r.pql for r in recs] == [
            f"Count(Row(f={k}))" for k in range(2, 6)]
        assert ex.recorder.active_records() == []

    def test_disabled_recorder_records_nothing(self, ex):
        ex.recorder = observe.FlightRecorder(enabled=False)
        ex.execute("i", "Count(Row(f=7))")
        assert ex.recorder.recent_records() == []
        assert ex.recorder.active_records() == []

    def test_slow_query_log_fires_and_not(self, ex):
        log = _CapturingLogger()
        ex.recorder = observe.FlightRecorder(
            long_query_time=1e-9, logger=log)
        ex.execute("i", "Count(Row(f=7))")
        assert len(log.lines) == 1
        line = log.lines[0]
        rec = ex.recorder.recent_records()[-1]
        assert "Count(Row(f=7))" in line
        assert rec.trace_id in line
        assert "execute.Count" in line  # the breakdown rides along
        assert rec.slow and rec.to_dict()["slow"] is True
        # above-threshold only: a generous threshold must not fire
        ex.recorder = observe.FlightRecorder(
            long_query_time=60.0, logger=log)
        ex.execute("i", "Count(Row(f=7))")
        assert len(log.lines) == 1
        assert ex.recorder.recent_records()[-1].slow is False

    def test_latency_histogram_and_exemplar_published(self, ex):
        stats = _stats.MemStatsClient()
        ex.recorder = observe.FlightRecorder(stats=stats)
        ex.execute("i", "Count(Row(f=7))")
        snap = stats.snapshot()
        assert snap["pilosa_query_latency"]["count"] == 1
        text = stats.prometheus_text(exemplars=True)
        assert "# TYPE pilosa_query_latency histogram" in text
        tid = ex.recorder.recent_records()[-1].trace_id
        assert f'# {{trace_id="{tid}"}}' in text
        # the scrape default stays clean 0.0.4 (no exemplar syntax)
        assert "trace_id" not in stats.prometheus_text()

    def test_span_record_linkage(self, ex):
        from pilosa_tpu import tracing

        tracer = tracing.MemTracer()
        old = tracing.global_tracer()
        tracing.set_global_tracer(tracer)
        try:
            ex.execute("i", "Count(Row(f=7))")
        finally:
            tracing.set_global_tracer(old)
        rec = ex.recorder.recent_records()[-1]
        spans = tracer.finished("executor.Execute")
        assert spans, "no executor span recorded"
        assert rec.trace_id == spans[-1].trace_id
        assert spans[-1].tags["query.record"] == rec.qid


class TestHistogramMath:
    def test_pinned_bucket_counts(self):
        reg = _stats.MemStatsClient()
        # bounds ladder contains ... 0.25, 0.5, 1, 2.5, 5 ...
        for v in (0.2, 0.5, 0.6, 4.0, 4.0):
            reg.histogram("lat", v)
        h = reg._registry._hists[("lat", ())]
        import bisect

        def bucket(v):
            return bisect.bisect_left(_stats.BUCKETS, v)

        assert h.counts[bucket(0.25)] == 1   # 0.2 -> le=0.25
        assert h.counts[bucket(0.5)] == 1    # 0.5 -> le=0.5 (le inclusive)
        assert h.counts[bucket(1.0)] == 1    # 0.6 -> le=1
        assert h.counts[bucket(5.0)] == 2    # both 4.0 -> le=5
        assert sum(h.counts) == 5

    def test_pinned_quantiles(self):
        reg = _stats.MemStatsClient()
        for v in (0.2, 0.5, 0.6, 4.0, 4.0):
            reg.histogram("lat", v)
        snap = reg.snapshot()["lat"]
        assert snap["count"] == 5 and snap["min"] == 0.2
        # p50: rank 2.5 falls in the le=1 bucket (cum before: 2, c=1)
        # -> 0.5 + (1 - 0.5) * 0.5 = 0.75
        assert snap["p50"] == pytest.approx(0.75)
        # p95: rank 4.75 in the le=5 bucket (cum before: 3, c=2)
        # -> 2.5 + (5 - 2.5) * (1.75/2) = 4.6875, clamped <= max 4.0
        assert snap["p95"] == pytest.approx(4.0)
        assert snap["p99"] == pytest.approx(4.0)

    def test_cumulative_bucket_rendering(self):
        reg = _stats.MemStatsClient()
        for v in (0.2, 0.5, 0.6, 4.0, 4.0):
            reg.histogram("lat", v)
        text = reg.prometheus_text()
        assert 'lat_bucket{le="0.25"} 1' in text
        assert 'lat_bucket{le="0.5"} 2' in text
        assert 'lat_bucket{le="1"} 3' in text
        assert 'lat_bucket{le="5"} 5' in text
        assert 'lat_bucket{le="+Inf"} 5' in text
        assert "lat_sum" in text and "lat_count 5" in text

    def test_exemplar_on_hot_bucket(self):
        reg = _stats.MemStatsClient()
        reg.histogram("lat", 0.4, exemplar="trace-a")
        reg.histogram("lat", 0.45, exemplar="trace-b")  # same bucket: last wins
        text = reg.prometheus_text(exemplars=True)
        assert 'lat_bucket{le="0.5"} 2 # {trace_id="trace-b"} 0.45' in text
        assert "trace-a" not in text


class TestSatelliteStats:
    def test_type_emitted_once_per_metric_name(self):
        s = _stats.MemStatsClient()
        s.count_with_tags("reqs", 1, 1.0, ["index:a"])
        s.count_with_tags("reqs", 2, 1.0, ["index:b"])
        s.timing("lat", 5.0)
        s.with_tags("index:a").timing("lat", 7.0)
        text = s.prometheus_text()
        assert text.count("# TYPE reqs counter") == 1
        assert text.count("# TYPE lat histogram") == 1
        assert 'reqs{index="a"} 1' in text
        assert 'reqs{index="b"} 2' in text

    def test_multi_stats_merges_backends(self):
        a, b = _stats.MemStatsClient(), _stats.MemStatsClient()
        multi = _stats.MultiStatsClient([a, b])
        a.count("only_a", 1)
        b.count("only_b", 2)
        snap = multi.snapshot()
        assert snap["only_a"] == 1 and snap["only_b"] == 2
        text = multi.prometheus_text()
        assert "only_a 1" in text and "only_b 2" in text

    def test_multi_stats_dedupes_type_lines(self):
        a, b = _stats.MemStatsClient(), _stats.MemStatsClient()
        multi = _stats.MultiStatsClient([a, b])
        multi.count("shared", 1)  # fans out: same name in both
        text = multi.prometheus_text()
        assert text.count("# TYPE shared counter") == 1


@pytest.fixture
def srv(tmp_path):
    s = Server(str(tmp_path / "node0"))
    s.open()
    _post(s.uri, "/index/i")
    _post(s.uri, "/index/i/field/f")
    for k in range(3):
        _post(s.uri, "/index/i/query",
              {"query": f"Set({k * SHARD_WIDTH + k}, f=9)"})
    yield s
    s.close()


class TestHTTPSurface:
    def test_profile_param_returns_breakdown(self, srv):
        r = _post(srv.uri, "/index/i/query?profile=1",
                  {"query": "Count(Row(f=9))"})
        assert r["results"] == [3]
        prof = r["profile"]
        assert prof["pql"] == "Count(Row(f=9))"
        assert prof["shards"] == 3
        assert prof["deviceLaunches"] >= 1
        assert {s["name"] for s in prof["stages"]} >= {
            "translate", "execute.Count"}
        # no profile key without the param
        r = _post(srv.uri, "/index/i/query", {"query": "Count(Row(f=9))"})
        assert "profile" not in r

    @pytest.mark.parametrize("where", ["ring", "inline"])
    def test_http_send_is_the_roots_last_child(self, srv, where):
        """Open question (e), PR 43: ``Handler._respond`` times its
        write.  The record in the recorder's ring holds ``http.send``
        as the root's last child and its root ends after the send (it
        did before PR 43 too: ``observe.Request.__exit__`` runs after
        the route's handler has written; only the child is new).  An
        inline ``?profile=1`` is rendered before its own send: its
        root stays ``open`` and holds no ``http.send``; the ring's
        copy of the same read does."""
        r = _post(srv.uri, "/index/i/query?profile=1",
                  {"query": "Count(Row(f=9))"})
        if where == "inline":
            spans = r["profile"]["spans"]
            [root] = [s for s in spans if not s["parent"]]
            assert root["open"] is True
            assert "http.send" not in {s["name"] for s in spans}
            return
        recorder = srv.node.executor.recorder
        # the handler closes the root after the client has its answer
        assert wait_until(lambda: any(
            s[2] == "http.request"
            for s in recorder.recent_records()[-1].spans), timeout=10)
        ring = _get(srv.uri, "/debug/queries")["recent"]
        [d] = [d for d in ring if d["traceID"] == r["profile"]["traceID"]]
        [root] = [s for s in d["spans"] if not s["parent"]]
        assert root["name"] == "http.request" and "open" not in root
        last = max((s for s in d["spans"] if s["parent"] == root["id"]),
                   key=lambda s: s["endNs"])
        assert last["name"] == "http.send"
        assert last["startNs"] < last["endNs"] <= root["endNs"]
        by_name = {s["name"]: s for s in d["spans"]}
        assert by_name["serialize"]["endNs"] <= last["startNs"]

    def test_debug_queries_roundtrip(self, srv):
        for _ in range(2):
            _post(srv.uri, "/index/i/query", {"query": "Count(Row(f=9))"})
        d = _get(srv.uri, "/debug/queries")
        assert d["active"] == []
        assert len(d["recent"]) >= 2
        last = d["recent"][0]  # newest-first by default
        assert last["pql"] == "Count(Row(f=9))"
        assert last["traceID"] and last["elapsedMs"] > 0
        # min_ms filters everything at an absurd threshold
        d = _get(srv.uri, "/debug/queries?min_ms=60000")
        assert d["recent"] == [] and d["active"] == []
        # sort=elapsed orders slowest-first
        d = _get(srv.uri, "/debug/queries?sort=elapsed")
        el = [r["elapsedMs"] for r in d["recent"]]
        assert el == sorted(el, reverse=True)

    def test_debug_vars_reports_quantiles(self, srv):
        _post(srv.uri, "/index/i/query", {"query": "Count(Row(f=9))"})
        snap = _get(srv.uri, "/debug/vars")
        lat = snap["pilosa_query_latency"]
        for k in ("count", "sum", "p50", "p95", "p99"):
            assert k in lat
        assert lat["count"] >= 1

    def test_metrics_exposes_native_histogram(self, srv):
        _post(srv.uri, "/index/i/query", {"query": "Count(Row(f=9))"})
        with urllib.request.urlopen(srv.uri + "/metrics") as resp:
            text = resp.read().decode()
        assert "# TYPE pilosa_query_latency histogram" in text
        assert 'pilosa_query_latency_bucket{le="+Inf"}' in text
        assert "pilosa_query_latency_count" in text
        # the scrape default is clean 0.0.4 — no exemplar syntax a
        # stock Prometheus would reject
        assert "trace_id" not in text
        from tools import check_metrics

        check_metrics.check_text(text)  # strict parser accepts it
        # exemplars render on explicit request, still parser-valid
        with urllib.request.urlopen(
                srv.uri + "/metrics?exemplars=1") as resp:
            annotated = resp.read().decode()
        assert 'trace_id="' in annotated
        check_metrics.check_text(annotated)

    def test_pprof_profile_serialized(self, srv):
        results: dict = {}

        def long_profile():
            try:
                with urllib.request.urlopen(
                        srv.uri + "/debug/pprof/profile?seconds=2",
                        timeout=35) as resp:
                    results["first"] = resp.status
            except urllib.error.HTTPError as e:
                results["first"] = e.code

        t = threading.Thread(target=long_profile)
        t.start()
        time.sleep(0.4)  # first sampler is mid-window
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(
                srv.uri + "/debug/pprof/profile?seconds=1", timeout=10)
        assert e.value.code == 409
        t.join()
        assert results["first"] == 200
        # the lock released: a later profile succeeds
        with urllib.request.urlopen(
                srv.uri + "/debug/pprof/profile?seconds=0.1",
                timeout=10) as resp:
            assert resp.status == 200


class TestDistributedProfile:
    def _make_cluster(self, tmp_path, n=3):
        from pilosa_tpu.parallel.cluster import (
            Cluster, LocalTransport, Node)
        from pilosa_tpu.parallel.node import ClusterNode

        transport = LocalTransport()
        node_ids = [f"node{i}" for i in range(n)]
        nodes = []
        for nid in node_ids:
            holder = Holder(str(tmp_path / nid))
            cluster = Cluster(
                nid, nodes=[Node(id=x) for x in node_ids],
                replica_n=1, transport=transport.bind(nid))
            cluster.set_state("NORMAL")
            nodes.append(ClusterNode(holder, cluster))
        return nodes

    def test_launch_count_matches_dispatch_hook_exactly(self, tmp_path):
        """Acceptance pin: a distributed Count profile's deviceLaunches
        equals the ops/bitmap.py dispatch-hook count for the same
        execution.  Shard set: exactly ONE locally-owned shard (so the
        local map runs inline on the calling thread, where both the
        dispatch_counter and the flight record observe every launch)
        plus one remote shard (whose launches belong to the remote
        node's own record, and tick neither local mechanism)."""
        nodes = self._make_cluster(tmp_path)
        origin = nodes[0]
        origin.create_index("i")
        origin.create_field("i", "f")
        n_shards = 6
        for s in range(n_shards):
            for k in range(3):
                origin.executor.execute(
                    "i", f"Set({s * SHARD_WIDTH + k}, f=1)")
        by_node = origin.cluster.shards_by_node("i", list(range(n_shards)))
        local = by_node.get(origin.cluster.local_id)
        remote = [ss for nid, ss in by_node.items()
                  if nid != origin.cluster.local_id]
        assert local and remote, "placement left a side empty"
        shards = [local[0], remote[0][0]]

        with bm.dispatch_counter() as dc:
            got = origin.executor.execute("i", "Count(Row(f=1))",
                                          shards=shards)[0]
        assert got == 6  # 3 bits in each of the two shards
        rec = origin.executor.recorder.recent_records()[-1]
        d = rec.to_dict()
        assert d["deviceLaunches"] == dc.n > 0
        assert d["launchKinds"] == dict(
            __import__("collections").Counter(dc.launches))
        # per-node: the local group and one remote node
        node_names = {t["node"] for t in d["nodeTimings"]}
        assert "local" in node_names and len(node_names) == 2
        # per-shard: the locally-executed shard
        assert [t["shard"] for t in d["shardTimings"]] == [shards[0]]
        # per-stage: map/reduce boundaries present
        names = [s["name"] for s in d["stages"]]
        assert "map" in names and "execute.Count" in names
        assert d["shards"] == 2
        for h in (n.holder for n in nodes):
            h.close()

    def test_profile_param_on_http_cluster(self, tmp_path):
        """?profile=1 through a real multi-node HTTP cluster returns
        per-node, per-shard, and per-stage timings plus the launch
        count."""
        s0 = Server(str(tmp_path / "n0"), name="node0")
        s0.open()
        s1 = Server(str(tmp_path / "n1"), name="node1", seeds=[s0.uri])
        s1.open()
        s2 = Server(str(tmp_path / "n2"), name="node2", seeds=[s0.uri])
        s2.open()
        try:
            _post(s0.uri, "/index/i")
            _post(s0.uri, "/index/i/field/f")
            n_shards = 6
            for s in range(n_shards):
                _post(s0.uri, "/index/i/query",
                      {"query": f"Set({s * SHARD_WIDTH + 2}, f=1)"})
            # per-shard map (the fused local batch is ONE launch with
            # no per-shard boundary, by design)
            s0.node.executor.fuse_shards = False
            r = _post(s0.uri, "/index/i/query?profile=1",
                      {"query": "Count(Row(f=1))"})
            assert r["results"] == [n_shards]
            prof = r["profile"]
            assert prof is not None
            assert prof["shards"] == n_shards
            assert prof["deviceLaunches"] > 0
            names = [st["name"] for st in prof["stages"]]
            assert "map" in names and "execute.Count" in names
            nodes_seen = {t["node"] for t in prof["nodeTimings"]}
            assert "local" in nodes_seen and len(nodes_seen) >= 2
            # origin-local shards carry per-shard timings when >0 local
            local_shards = s0.cluster.local_shards(
                "i", list(range(n_shards)))
            if local_shards:
                assert {t["shard"] for t in prof["shardTimings"]} == set(
                    local_shards)
        finally:
            for s in (s2, s1, s0):
                s.close()


class TestCoalescerObservability:
    def test_coalesced_record_carries_batch_context(self, tmp_path):
        from pilosa_tpu.parallel.coalescer import Coalescer

        holder = Holder(str(tmp_path / "co"))
        idx = holder.create_index("i")
        idx.create_field("f")
        e = Executor(holder)
        e.coalescer = Coalescer(window_s=0.01, max_batch=4, enabled=True)
        for s in range(2):
            for k in range(3):
                e.execute("i", f"Set({s * SHARD_WIDTH + k}, f=1)")
                e.execute("i", f"Set({s * SHARD_WIDTH + k + 8}, f=2)")
        n_threads = 4

        # DISTINCT same-shape queries: identical concurrent queries
        # now single-flight at the result cache (only the leader
        # reaches the coalescer; followers record as cache hits), so
        # observing per-member batch context needs distinct keys —
        # same canonical tree shape, different row ids, one batch.
        def worker(i):
            a, b = 1 + 2 * i, 2 + 2 * i
            return e.execute(
                "i", f"Count(Intersect(Row(f={a}), Row(f={b})))")[0]

        assert map_behind_launch(e.coalescer, worker,
                                 n_threads) == [0] * n_threads
        recs = [r for r in e.recorder.recent_records()
                if r.path == "coalesced"]
        assert len(recs) == n_threads
        batches = [r.coalesce["batch"] for r in recs]
        assert all(b >= 1 for b in batches)
        # leaders own the shared launch tick and carry no trace link;
        # followers name the leader's trace instead.  recent_records()
        # orders by completion, and which thread finishes last is a
        # race — so check each record against its own role rather than
        # assuming recs[-1] is a follower
        base = {"batch", "shapes", "tape", "queueWaitMs", "launchMs",
                "leader", "why"}
        for r in recs:
            d = r.to_dict()
            want = (base if d["coalescer"]["leader"]
                    else base | {"launchTrace"})
            assert set(d["coalescer"]) == want, d["coalescer"]
            assert d["coalescer"]["queueWaitMs"] >= 0
            assert d["coalescer"]["why"] in ("busy", "full")
        # exactly one record per flush owns the shared launch
        assert sum(1 for r in recs if r.coalesce["leader"]) >= 1
        holder.close()


class TestCheckMetricsParser:
    def test_rejects_duplicate_type(self):
        from tools.check_metrics import MetricsFormatError, check_text

        bad = "# TYPE a counter\na 1\n# TYPE a counter\n"
        with pytest.raises(MetricsFormatError, match="duplicate TYPE"):
            check_text(bad)

    def test_rejects_type_split_by_tagset(self):
        """The exact satellite bug: TYPE re-emitted per tagset."""
        from tools.check_metrics import MetricsFormatError, check_text

        bad = ('# TYPE a counter\na{x="1"} 1\n'
               '# TYPE a counter\na{x="2"} 2\n')
        with pytest.raises(MetricsFormatError):
            check_text(bad)

    def test_rejects_non_cumulative_buckets(self):
        from tools.check_metrics import MetricsFormatError, check_text

        bad = ("# TYPE h histogram\n"
               'h_bucket{le="1"} 5\nh_bucket{le="2"} 3\n'
               'h_bucket{le="+Inf"} 5\nh_sum 1\nh_count 5\n')
        with pytest.raises(MetricsFormatError,
                           match="not cumulative"):
            check_text(bad)

    def test_rejects_missing_inf_bucket(self):
        from tools.check_metrics import MetricsFormatError, check_text

        bad = ("# TYPE h histogram\n"
               'h_bucket{le="1"} 5\nh_sum 1\nh_count 5\n')
        with pytest.raises(MetricsFormatError, match=r"\+Inf"):
            check_text(bad)

    def test_rejects_bad_label_and_duplicate_series(self):
        from tools.check_metrics import MetricsFormatError, check_text

        with pytest.raises(MetricsFormatError):
            check_text("# TYPE a counter\na{x=unquoted} 1\n")
        with pytest.raises(MetricsFormatError, match="duplicate series"):
            check_text('# TYPE a counter\na{x="1"} 1\na{x="1"} 2\n')

    def test_rejects_exemplar_outside_bucket(self):
        from tools.check_metrics import MetricsFormatError, check_text

        bad = '# TYPE a counter\na 1 # {trace_id="t"} 1\n'
        with pytest.raises(MetricsFormatError, match="exemplar"):
            check_text(bad)

    def test_accepts_valid_histogram_with_exemplar(self):
        from tools.check_metrics import check_text

        good = ("# TYPE h histogram\n"
                'h_bucket{le="1"} 2 # {trace_id="t"} 0.5 123.0\n'
                'h_bucket{le="+Inf"} 3\n'
                "h_sum 4.5\nh_count 3\n")
        out = check_text(good)
        assert out["samples"] == 4


# ---------------------------------------------------------------------------
# The span tree (observe.span): one recording primitive per request
# ---------------------------------------------------------------------------


def _tree(rec):
    """{id: span dict} of a published record."""
    return {s["id"]: s for s in rec.to_dict()["spans"]}


def _names(rec):
    return [s["name"] for s in rec.to_dict()["spans"]]


@pytest.fixture
def cex(ex):
    """The executor with a coalescer attached (the conftest platform is
    an 8-device mesh, so the coalesced launch is the mesh engine)."""
    from pilosa_tpu.parallel.coalescer import Coalescer

    ex.coalescer = Coalescer(window_s=0.01, max_batch=8, enabled=True,
                             stats=_stats.MemStatsClient())
    return ex


class TestSpans:
    def test_nothing_recorded_without_a_record(self, monkeypatch):
        """No record, no Request, no timer, no recording tracer: the
        shared no-op, no clock read, no annotation built."""
        reads = []
        monkeypatch.setattr(observe, "clock_ns",
                            lambda: reads.append(1) or 0)
        built = []
        monkeypatch.setattr(observe, "_annotation",
                            lambda *a, **k: built.append(a))
        assert observe.span("stage", leaves=3) is observe.NOSPAN
        with observe.span("launch") as sp:
            sp.note(engine="dense")
            sp.note_engine()
        assert sp.id == 0 and not reads and not built
        assert observe.open_span() == 0

    @pytest.mark.parametrize("sink", ["request", "record", "none"])
    def test_before_names_the_glue_at_no_clock_read(self, monkeypatch,
                                                    sink):
        """``sp.before(name)`` inside a span: what this thread did
        since its last phase under the same parent (``observe.since``;
        the first child of ``exec``: since the record opened) is a
        span of its own, a sibling up to this span's start, written
        from clock reads already taken."""
        real, reads = observe.clock_ns, []
        monkeypatch.setattr(observe, "clock_ns",
                            lambda: reads.append(1) or real())
        if sink == "none":
            sp = observe.span("serialize")
            assert sp is observe.NOSPAN and observe.since() == 0
            with sp:
                sp.before("api.close")
            assert not reads
            return
        with observe.Request() as rq:
            with observe.span("http.read") as rd:
                pass
            n = len(reads)
            if sink == "request":
                assert observe.since() == rd.end_ns
                with observe.span("serialize") as ps:
                    ps.before("api.close")
                assert len(reads) - n == 2  # the span's own two
                spans = {s[2]: s for s in rq.spans}
                want = (rd.end_ns, ps.start_ns)
                glue, nxt = spans["api.close"], spans["serialize"]
            else:
                rec = observe.FlightRecorder().begin("i", "q")
                with observe.attach(rec):
                    n = len(reads)
                    assert observe.since() == rec.t0_ns
                    with observe.span("translate") as tr:
                        tr.before("exec.open")
                    assert len(reads) - n == 2
                assert rec.spans is rq.spans  # adopted: one tree
                spans = {s[2]: s for s in rec.spans}
                # the last span written ended before ``exec`` opened:
                # it is no sibling, and the gap starts with ``exec``
                assert rd.end_ns < rec.t0_ns
                want = (rec.t0_ns, tr.start_ns)
                glue, nxt = spans["exec.open"], spans["translate"]
        assert (glue[3], glue[4]) == want and glue[3] <= glue[4]
        assert glue[1] == nxt[1] and glue[0] != nxt[0]  # siblings
        assert glue[6] is None

    @pytest.mark.parametrize("case", ["no_sibling", "other_thread",
                                      "deeper", "sibling"])
    def test_a_gap_span_never_starts_before_its_parent(self, case):
        """``before`` takes its start from this thread's last span
        under the SAME parent: a span written deeper, under another
        parent or by another thread is not it, and with no such stamp
        nothing is written."""
        rec = observe.FlightRecorder().begin("i", "q")
        with observe.attach(rec):
            with observe.span("cache.probe"):
                pass  # under ``exec``: no sibling of what follows
            with observe.span("call.Count") as parent:
                if case == "other_thread":
                    t = threading.Thread(target=lambda: rec.add_span(
                        "launch", rec.t0_ns, rec.t0_ns + 1,
                        parent=parent.id))
                    t.start()
                    t.join()
                elif case == "deeper":
                    with observe.span("plan"):
                        with observe.span("plan.inner"):
                            pass
                    with observe.span("map.fused"):
                        # ``plan.inner`` was written last but lies
                        # under ``plan``; ``map.fused`` has no child yet
                        with observe.span("stage") as st:
                            st.before("route")
                elif case == "sibling":
                    with observe.span("plan") as plan:
                        pass
                if case != "deeper":
                    with observe.span("stage") as st:
                        st.before("route")
        spans = {s[2]: s for s in rec.spans}
        if case == "sibling":
            assert spans["route"][3:5] == (plan.end_ns, st.start_ns)
            assert spans["route"][1] == parent.id
            assert spans["route"][3] >= parent.start_ns
        else:
            assert "route" not in spans

    def test_ids_parents_and_one_trace(self, ex):
        ex.execute("i", "Count(Row(f=7))")
        rec = ex.recorder.recent_records()[-1]
        tree = _tree(rec)
        assert len(tree) == len(rec.spans)  # ids are unique
        [root] = [s for s in tree.values() if not s["parent"]]
        assert root["name"] == "exec" and root["startNs"] == 0
        # elapsedMs keeps its meaning: the exec span
        assert rec.elapsed_ns == root["endNs"] - root["startNs"]
        for s in tree.values():
            if s is root:
                continue
            p = tree[s["parent"]]  # every parent is a span of the record
            assert p["startNs"] <= s["startNs"] <= s["endNs"] <= p["endNs"]
        by_name = {s["name"]: s for s in tree.values()}
        assert by_name["call.Count"]["parent"] == root["id"]
        for child in ("cache.probe", "map.fused"):
            assert by_name[child]["parent"] == by_name["call.Count"]["id"]
        # the choice of engine: eligibility under the call, the
        # container plan under the fused group
        assert [tree[s["parent"]]["name"] for s in tree.values()
                if s["name"] == "plan"] == ["call.Count", "map.fused"]
        for child in ("stage", "launch", "reduce"):
            assert by_name[child]["parent"] == by_name["map.fused"]["id"]
        for child in ("launch.dispatch", "launch.ready"):
            assert by_name[child]["parent"] == by_name["launch"]["id"]
        assert by_name["stage"]["leaves"] == 1
        assert by_name["launch"]["engine"] == rec.engine

    def test_self_times_of_a_level_sum_to_the_parent(self, ex):
        ex.execute("i", "Count(Row(f=7))")
        tree = _tree(ex.recorder.recent_records()[-1])
        for p in tree.values():
            kids = [s for s in tree.values() if s["parent"] == p["id"]]
            if not kids:
                continue
            covered = sum(s["endNs"] - s["startNs"] for s in kids)
            self_ns = (p["endNs"] - p["startNs"]) - covered
            # one thread, children in sequence: self time is what is
            # left, and never negative
            assert self_ns >= 0, (p["name"], self_ns)
            assert self_ns + covered == p["endNs"] - p["startNs"]

    def test_stages_render_from_the_spans(self, ex):
        ex.execute("i", "Count(Row(f=7))")
        rec = ex.recorder.recent_records()[-1]
        d = rec.to_dict()
        by_name = {s["name"]: s for s in d["spans"]}
        stages = {s["name"]: s["ms"] for s in d["stages"]}
        assert list(stages) == ["translate", "map.fused",
                                "execute.Count"]
        # a result that can hold a key has the fourth stage
        ex.execute("i", "Row(f=7)")
        assert [s["name"] for s in ex.recorder.recent_records()[-1]
                .to_dict()["stages"]][-1] == "translateResults"
        call = by_name["call.Count"]
        assert stages["execute.Count"] == round(
            (call["endNs"] - call["startNs"]) / 1e6, 3)
        assert not hasattr(rec, "note_stage")

    def test_pool_workers_attach_under_the_map_span(self, ex):
        ex.fuse_shards = False
        ex.execute("i", "TopN(f, n=2)")
        rec = ex.recorder.recent_records()[-1]
        tree = _tree(rec)
        [mp] = [s for s in tree.values() if s["name"] == "map"]
        launches = [s for s in tree.values() if s["name"] == "launch"]
        assert len(launches) == 3  # one matrix scan a shard
        assert {s["parent"] for s in launches} == {mp["id"]}
        # the workers are other threads than the one that waits
        assert len({s["thread"] for s in launches} | {mp["thread"]}) > 1
        for s in launches:
            assert mp["startNs"] <= s["startNs"] <= s["endNs"] <= mp["endNs"]

    def test_request_scope_is_adopted_not_doubled(self, ex):
        """What the handler records before Executor.execute and after
        it lands on the ONE record, under the root http.request."""
        n0 = len(ex.recorder.recent_records())
        with observe.Request() as rq:
            rq.admission = {"class": "query", "queue_wait_ns": 5}
            with observe.span("admission.wait"):
                pass
            ex.execute("i", "Count(Row(f=7))")
            rec = ex.recorder.recent_records()[-1]
            # inline rendering: the root is still open
            [root] = [s for s in rec.to_dict()["spans"]
                      if not s["parent"]]
            assert root["name"] == "http.request" and root["open"] is True
            with observe.span("serialize"):
                pass
        assert len(ex.recorder.recent_records()) == n0 + 1
        d = rec.to_dict()
        tree = {s["id"]: s for s in d["spans"]}
        [root] = [s for s in tree.values() if not s["parent"]]
        assert root["id"] == 1 and "open" not in root
        assert d["rootStartNs"] == rq.start_ns
        assert d["admission"]["class"] == "query"
        names = [s["name"] for s in d["spans"]]
        assert names[:2] == ["http.request", "admission.wait"]
        assert "pql.parse" in names and names[-1] == "serialize"
        by_name = {s["name"]: s for s in tree.values()}
        for child in ("admission.wait", "pql.parse", "exec", "serialize"):
            assert by_name[child]["parent"] == 1
        assert root["endNs"] >= by_name["serialize"]["endNs"]

    def test_the_cap(self, ex, monkeypatch):
        monkeypatch.setattr(observe, "MAX_SPANS", 6)
        ex.execute("i", "Count(Row(f=7))")
        rec = ex.recorder.recent_records()[-1]
        assert len(rec.spans) == 7  # six, and the exec span itself
        assert "exec" in _names(rec)

    def test_follower_links_to_its_leader(self, cex):
        qs = ["Count(Row(f=7))", "Count(Union(Row(f=7), Row(f=8)))",
              "Count(Intersect(Row(f=7), Row(f=8)))"]
        run_behind_launch(
            cex.coalescer, [threading.Thread(target=cex.execute,
                                             args=("i", q),
                                             kwargs={"opt": None})
                            for q in qs])
        recs = cex.recorder.recent_records()[-3:]
        leaders = [r for r in recs if r.coalesce and r.coalesce["leader"]
                   and r.coalesce["batch"] > 1]
        followers = [r for r in recs
                     if r.coalesce and not r.coalesce["leader"]]
        assert leaders and followers
        for fo in followers:
            by = {s["name"]: s for s in fo.to_dict()["spans"]}
            trace, sid = by["launch"]["link"]
            [ld] = [r for r in leaders if r.trace_id == trace]
            lspan = _tree(ld)[sid]
            assert lspan["name"] == "launch"
            assert lspan["batch"] == by["launch"]["batch"] > 1
            # the leader's times, on the shared clock
            root_f = fo.to_dict()["rootStartNs"]
            root_l = ld.to_dict()["rootStartNs"]
            assert root_f + by["launch"]["endNs"] == \
                root_l + lspan["endNs"]
            # a follower dispatched nothing itself
            assert "launch.dispatch" not in by
            assert by["coalesce.wait"]["endNs"] == by["launch"]["startNs"]
            assert by["coalesce.wait"]["why"] == "busy"
            assert by["reduce"]["startNs"] == by["launch"]["endNs"]

    def test_clock_reads_per_coalesced_count_are_pinned(self, cex,
                                                        monkeypatch):
        """The budget of ISSUE 24: what one coalesced Count (a leader
        that flushes alone, cache probe and fill included) reads of the
        span clock from Executor.execute in, under a handler Request.
        A new span on this path moves the number: say why in the PR."""
        q = "Count(Union(Row(f=7), Row(f=8)))"
        cex.execute("i", q)  # compile, import, stage
        from pilosa_tpu.runtime import resultcache

        resultcache.reset()
        real = observe.clock_ns
        reads = []

        def counting():
            reads.append(1)
            return real()

        monkeypatch.setattr(observe, "clock_ns", counting)
        with observe.Request():
            cex.execute("i", q)
        monkeypatch.setattr(observe, "clock_ns", real)
        rec = cex.recorder.recent_records()[-1]
        assert rec.path == "coalesced" and not rec.cached
        assert len(reads) == CLOCK_READS_PER_COALESCED_COUNT, _names(rec)


#: Request 2, pql.parse 2, the record's begin 1 and publish 1 (the exec
#: span), translate 2, call.Count 2, plan 2, cache.probe 2, stage 2, the
#: submit 1 (coalesce.wait starts there) and its end 1, the flush 1
#: (launch starts there) and its end 1, launch.dispatch 2, launch.ready
#: 1 (it starts where dispatch ended), the end of reduce 1 (it starts
#: where launch ended), cache.fill 2; translateResults 2 until PR 43
#: (a Count holds no key, so none is opened: 28 then).  ``exec.open``
#: and ``route`` (PR 43) read no clock.  Behind the handler:
#: admission.wait, http.read and serialize, 2 each, api.open 1 and
#: http.send 2; the commit before PR 24 read the clock 19 times here.
CLOCK_READS_PER_COALESCED_COUNT = 26


@pytest.mark.parametrize("pql,kind", [
    ("TopN(f, n=2)", "topn"),
    ("GroupBy(Rows(f))", "groupby"),
    ("Sum(field=v)", "sum"),
    ("Max(field=v)", "max"),
    ("Row(v > 1)", "range"),
])
def test_engine_and_path_on_every_call_kind(tmp_path, pql, kind):
    """TopN, GroupBy, Sum/Min/Max and range records read engine=None
    path=None before ISSUE 24; their dispatch sites open the same
    launch span as a Count and stamp the engine."""
    from pilosa_tpu.models.field import FieldOptions, FieldType

    holder = Holder(str(tmp_path / kind))
    idx = holder.create_index("i")
    idx.create_field("f")
    idx.create_field("v", FieldOptions(type=FieldType.INT, min=0, max=100))
    e = Executor(holder)
    try:
        for s in range(3):
            for k in range(4):
                e.execute("i", f"Set({s * SHARD_WIDTH + k}, f={k % 2})")
                e.execute("i", f"Set({s * SHARD_WIDTH + k}, v={k + 1})")
        e.execute("i", pql)
        rec = e.recorder.recent_records()[-1]
        d = rec.to_dict()
        assert d["path"] in ("fused", "per-shard"), d.get("path")
        from pilosa_tpu import perfobs

        assert d["engine"] in perfobs.ENGINES, d.get("engine")
        launches = [s for s in d["spans"] if s["name"] == "launch"]
        assert launches and all(s.get("engine") for s in launches)
        ids = {s["id"] for s in launches}
        assert {"launch.dispatch", "launch.ready"} <= {
            s["name"] for s in d["spans"] if s["parent"] in ids}
    finally:
        holder.close()
