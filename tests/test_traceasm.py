"""Distributed query autopsy (the observability round): the cluster
event journal (observe.EventJournal + the refcounted config baseline),
cross-node trace assembly (pilosa_tpu.traceasm) both as pure functions
over fixture sections and over the real ``/debug/trace/{id}`` fan-in,
the traceparent-propagation audit across every internal RPC class
(shard map, hedge re-issues, hint replay, AE exchanges, rebalance
transfers), and the 3-node acceptance pin: a hedged query under an
armed ``client.request.send`` failpoint yields ONE causal span tree
with the hedge loser's side, per-span walls summing to the observed
latency, and the breaker-open event in the merged cluster timeline —
with byte-identical query results when the journal is disabled."""

from __future__ import annotations

import json
import time
import urllib.error
import urllib.request

import pytest

from pilosa_tpu import faultinject, observe, traceasm, tracing
from pilosa_tpu.api import API
from pilosa_tpu.observe import EventJournal
from pilosa_tpu.parallel import hints as hintsmod
from pilosa_tpu.parallel.hints import HintReplayer
from pilosa_tpu.parallel.syncer import HolderSyncer
from pilosa_tpu.server.client import InternalClient
from pilosa_tpu.server.server import Server
from pilosa_tpu.shardwidth import SHARD_WIDTH

from tests.test_cluster import make_cluster
from tests.test_http import _get, _post


@pytest.fixture(autouse=True)
def _fresh_journal():
    """The journal, its config baseline and the failpoint registry are
    process-wide — every test starts (and leaves) them pristine."""
    faultinject.disarm()
    observe.reset_journal()
    yield
    faultinject.disarm()
    observe.reset_journal()


# ================================================= event journal unit


class TestEventJournal:
    def test_emit_filters_and_limit(self):
        j = EventJournal(node_id="n0")
        j.emit("breaker.open", peer="node1")
        j.emit("breaker.close", peer="node1")
        j.emit("hedge.fired", trace_id="ab" * 10)
        evs = j.events()
        assert [e["kind"] for e in evs] == [
            "breaker.open", "breaker.close", "hedge.fired"]
        assert all(e["node"] == "n0" for e in evs)
        assert [e["seq"] for e in evs] == [1, 2, 3]  # monotonic
        # kind is a PREFIX match: "breaker" covers open AND close
        assert len(j.events(kind="breaker")) == 2
        # since is an exclusive cursor over seq
        assert [e["kind"] for e in j.events(since=2)] == ["hedge.fired"]
        # limit keeps the NEWEST matches
        assert [e["kind"] for e in j.events(limit=1)] == ["hedge.fired"]
        # trace filter matches on normalized ids (20-hex vs 32-hex)
        got = j.events(trace_id="ab" * 10)
        assert len(got) == 1
        assert got[0]["traceId"] == tracing.normalize_trace_id("ab" * 10)

    def test_ring_overflow_keeps_counting(self):
        j = EventJournal(size=4)
        for k in range(10):
            j.emit(f"kind.{k}")
        c = j.counters()
        assert c["total"] == 10          # seq keeps counting past evictions
        assert c["depth"] == 4           # ring capped
        assert c["dropped"] == 0
        assert [e["kind"] for e in j.events()] == [
            "kind.6", "kind.7", "kind.8", "kind.9"]

    def test_kinds_allowlist_counts_drops(self):
        j = EventJournal(kinds={"breaker.open"})
        j.emit("breaker.open")
        j.emit("hedge.fired")
        j.emit("ae.round.start")
        c = j.counters()
        assert c["total"] == 1 and c["dropped"] == 2
        assert [e["kind"] for e in j.events()] == ["breaker.open"]

    def test_module_emit_gates_on_journal_on(self):
        observe.configure(enabled=False)
        assert observe.journal_on is False  # the one-bool fast gate
        c0 = observe.journal().counters()
        observe.emit("breaker.open")
        assert observe.journal().counters() == c0  # nothing emitted
        observe.configure(enabled=True)
        observe.emit("breaker.open")
        c1 = observe.journal().counters()
        assert c1["kinds"].get("breaker.open") == 1

    def test_emit_autocaptures_active_trace(self):
        tid = tracing.new_trace_id()
        with tracing.propagate(tid):
            observe.emit("hedge.fired", node="node1")
        [ev] = observe.journal().events(kind="hedge")
        assert ev["traceId"] == tracing.normalize_trace_id(tid)

    def test_configure_resize_preserves_history(self):
        observe.emit("a.one")
        observe.emit("a.two")
        seq_before = observe.journal().counters()["total"]
        observe.configure(size=64)
        j = observe.journal()
        assert j._ring.maxlen == 64
        kinds = [e["kind"] for e in j.events()]
        # old contents survive the resize; the resize itself journals
        assert kinds[:2] == ["a.one", "a.two"]
        assert kinds[-1] == "config.applied"
        assert j.counters()["total"] == seq_before + 1  # seq continues

    def test_retain_release_restores_baseline(self):
        observe.retain()
        observe.configure(node_id="srv0", kinds="breaker",
                          enabled=False)
        j = observe.journal()
        assert j.node_id == "srv0" and observe.journal_on is False
        # a nested retain/release pair keeps the server config applied
        observe.retain()
        observe.release()
        assert observe.journal().node_id == "srv0"
        # the LAST release restores the pre-server baseline
        observe.release()
        j = observe.journal()
        assert j.node_id == "" and j.kinds == frozenset()
        assert observe.journal_on is True
        assert [e["kind"] for e in j.events()][-1] == "config.restored"

    def test_shed_record_carries_trace_id(self):
        """Satellite pin: a refused request's record links the
        client's trace — a logged shed is one /debug/trace/{id}
        away."""
        rec = observe.FlightRecorder()
        tid = tracing.new_trace_id()
        rec.record_shed("i", "Count(Row(f=1))", "query", "shed",
                        "queue full", wait_ns=5_000_000, trace_id=tid)
        [r] = rec.recent_records()
        assert r.trace_id == tid
        assert r.to_dict()["traceID"] == tid


# ============================================== pure trace assembly


def _sp(sid, parent, name, start_ms, end_ms, thread=7, **counts):
    """One rendered span (QueryRecord.to_dict's shape), times in ms."""
    return {"id": sid, "parent": parent, "name": name,
            "startNs": int(start_ms * 1e6), "endNs": int(end_ms * 1e6),
            "thread": thread, **counts}


def _origin_rec(**over) -> dict:
    rec = {
        "traceID": "a" * 32, "index": "i", "pql": "Count(Row(f=1))",
        "elapsedMs": 8.9,
        "admission": {"class": "query", "queueWaitMs": 1.0},
        "spans": [
            _sp(1, 0, "http.request", 0.0, 10.0),
            _sp(2, 1, "admission.wait", 0.0, 1.0),
            _sp(3, 1, "exec", 1.0, 9.9),
            _sp(4, 3, "translate", 1.0, 1.5),
            _sp(5, 3, "call.Count", 1.5, 9.5),
            _sp(6, 5, "map", 1.5, 7.5),
            _sp(7, 5, "reduce", 7.5, 9.5),
            _sp(8, 3, "translateResults", 9.5, 9.7),
        ],
        "engine": "fused", "deviceLaunches": 3,
        "nodeTimings": [{"node": "node1", "ms": 4.0, "shards": 2},
                        {"node": "local", "ms": 2.0, "shards": 1}],
    }
    rec.update(over)
    return rec


def _remote_rec(**over) -> dict:
    rec = {
        "traceID": "a" * 32, "index": "i", "pql": "Count(Row(f=1))",
        "elapsedMs": 3.0, "remote": True, "engine": "fused",
        "spans": [_sp(1, 0, "exec", 0.0, 3.0),
                  _sp(2, 1, "call.Count", 0.1, 2.9),
                  _sp(3, 2, "map", 0.2, 2.7)],
    }
    rec.update(over)
    return rec


def _walk(span, out=None):
    if out is None:
        out = []
    if span is None:
        return out
    out.append(span)
    for c in span["children"]:
        _walk(c, out)
    for a in span.get("abandoned", []):
        _walk(a, out)
    return out


def _find(span, name):
    return [s for s in _walk(span) if s["name"] == name]


class TestTraceAssembly:
    def test_accounting_identity_and_stage_nesting(self):
        sections = {
            "node0": {"records": [_origin_rec()]},
            "node1": {"records": [_remote_rec()]},
        }
        out = traceasm.assemble_trace(sections, {}, "a" * 32)
        assert out["origin"] == "node0"
        root = out["root"]
        assert root["name"] == "query/i" and root["ms"] == 10.0
        # the map span nests UNDER its call span, by its parent id
        # (rendering both at one level would double-count the map wall)
        [ex] = [c for c in _find(root, "call.Count")
                if c["node"] == "node0"]
        assert ex["engine"] == "fused" and ex["launches"] == 3
        [mp] = [c for c in ex["children"] if c["name"] == "map"]
        assert mp["ms"] == 6.0
        assert {c["name"] for c in mp["children"]} - {
            "(unattributed)"} == {"node/node1", "node/local"}
        [rd] = [c for c in ex["children"] if c["name"] == "reduce"]
        assert rd["ms"] == 2.0
        # never a sibling of its call
        assert "map" not in {c["name"] for c in root["children"]}
        # node1's own flight record hangs under the per-node map child
        [rsub] = _find(root, "remote/i")
        assert rsub["node"] == "node1" and rsub["ms"] == 3.0
        # admission wait + the root-level unattributed filler
        [adm] = _find(root, "admission.wait")
        assert adm["ms"] == 1.0
        acc = out["accounting"]
        # the invariant: per-span walls sum EXACTLY to the observed
        # latency (every level carries its explicit filler child)
        assert acc["observedMs"] == 10.0
        assert acc["accountedMs"] == 10.0
        assert acc["unaccountedMs"] == 0.0
        assert out["traceId"] == "a" * 32

    def test_hedge_loser_off_critical_path(self):
        origin = _origin_rec(
            hedgeLosers=[{"node": "node2", "ms": 5.0}])
        sections = {
            "node0": {"records": [origin]},
            "node1": {"records": [_remote_rec()]},
            "node2": {"records": [_remote_rec(elapsedMs=2.0)]},
        }
        out = traceasm.assemble_trace(sections, {}, "a" * 32)
        [ex] = [c for c in _find(out["root"], "call.Count")
                if "abandoned" in c]
        [lost] = ex["abandoned"]
        assert lost["name"] == "node/node2 (hedge loser)"
        assert lost["offCriticalPath"] is True and lost["ms"] == 5.0
        # the loser node's own record attaches under the abandoned span
        assert any(s["name"] == "remote/i" and s["node"] == "node2"
                   for s in _walk(lost))
        # abandoned work is reported but EXCLUDED from the accounting:
        # the identity still holds without the loser's 5 ms
        acc = out["accounting"]
        assert acc["observedMs"] == acc["accountedMs"] == 10.0

    def test_orphan_trace_has_no_root(self):
        sections = {"node1": {"records": [_remote_rec()]}}
        out = traceasm.assemble_trace(sections, {}, "a" * 32)
        assert out["root"] is None and out["origin"] is None
        assert out["accounting"] == {"observedMs": 0.0,
                                     "accountedMs": 0.0,
                                     "unaccountedMs": 0.0}
        assert len(out["records"]) == 1  # raw records still listed

    def test_dead_peer_errors_degrade(self):
        sections = {"node0": {"records": [_origin_rec()]},
                    "node2": None}
        errors = {"node1": "TransportError: node unreachable"}
        out = traceasm.assemble_trace(sections, errors, "a" * 32)
        assert out["errors"] == errors
        assert out["root"] is not None  # partial assembly still lands

    def test_map_without_a_call_parent_kept(self):
        """A map span whose call never closed (the parent id names no
        span of the record) hangs on the root, not silently dropped."""
        origin = _origin_rec(spans=[_sp(1, 0, "exec", 0.0, 8.0),
                                    _sp(2, 1, "translate", 0.0, 0.5),
                                    _sp(4, 3, "map", 0.5, 6.5)],
                             nodeTimings=[])
        out = traceasm.assemble_trace(
            {"node0": {"records": [origin]}}, {}, "a" * 32)
        assert [c["name"] for c in out["root"]["children"]] == [
            "translate", "map", "(unattributed)"]
        acc = out["accounting"]
        assert acc["observedMs"] == acc["accountedMs"] == 8.0

    def test_worker_spans_are_concurrent_and_follower_links(self):
        """Pool workers' spans under ``map`` run beside the thread that
        waits for them: shown, marked, and left out of the wall sum.  A
        follower's launch keeps its link to the leader's span."""
        origin = _origin_rec(nodeTimings=[], spans=[
            _sp(1, 0, "exec", 0.0, 10.0),
            _sp(2, 1, "call.Count", 0.0, 10.0),
            _sp(3, 2, "map", 1.0, 5.0),
            _sp(4, 3, "launch", 1.0, 4.0, thread=8),
            _sp(5, 3, "launch", 1.0, 4.5, thread=9),
            _sp(6, 2, "launch", 5.0, 9.0, link=["b" * 32, 12]),
        ])
        out = traceasm.assemble_trace(
            {"node0": {"records": [origin]}}, {}, "a" * 32)
        [mp] = _find(out["root"], "map")
        assert [c.get("concurrent") for c in mp["children"]] == [
            True, True]
        [linked] = [s for s in _find(out["root"], "launch")
                    if "link" in s]
        assert linked["link"] == ["b" * 32, 12]
        acc = out["accounting"]
        assert acc["observedMs"] == acc["accountedMs"] == 10.0

    def test_record_without_spans_is_one_leaf(self):
        """A peer that predates spans (or a shed record): its elapsedMs
        is the whole tree."""
        origin = {"traceID": "a" * 32, "index": "i", "pql": "",
                  "elapsedMs": 4.0}
        out = traceasm.assemble_trace(
            {"node0": {"records": [origin]}}, {}, "a" * 32)
        assert out["root"]["ms"] == 4.0 and not out["root"]["children"]
        assert out["accounting"]["accountedMs"] == 4.0

    def test_short_trace_id_normalizes(self):
        out = traceasm.assemble_trace({}, {}, "abc123")
        assert out["traceId"] == "0" * 26 + "abc123"
        assert len(out["traceId"]) == 32

    def test_merge_events_orders_and_keeps_counters(self):
        sections = {
            "node1": {"events": [
                {"t": 2.0, "seq": 1, "kind": "breaker.open",
                 "node": "node1"},
                {"t": 4.0, "seq": 2, "kind": "breaker.close",
                 "node": "node1"},
            ], "counters": {"total": 2}},
            "node0": {"events": [
                {"t": 3.0, "seq": 9, "kind": "hedge.fired",
                 "node": "node0"},
            ], "counters": {"total": 9}},
            "node2": None,
        }
        errors = {"node3": "timeout after 2s"}
        out = traceasm.merge_events(sections, errors, since=0,
                                    kind=None)
        # wall-clock ordered across nodes (seq is per-node only)
        assert [e["kind"] for e in out["events"]] == [
            "breaker.open", "hedge.fired", "breaker.close"]
        assert out["counters"] == {"node1": {"total": 2},
                                   "node0": {"total": 9}}
        assert out["errors"] == errors


# ============================================ HTTP routes, one node


class TestTraceRoutesHTTP:
    def test_debug_events_and_trace_routes(self, tmp_path):
        s = Server(str(tmp_path / "n0"), name="node0")
        s.open()
        try:
            _post(s.uri, "/index/i")
            _post(s.uri, "/index/i/field/f")
            _post(s.uri, "/index/i/query", {"query": "Set(1, f=7)"})
            _post(s.uri, "/index/i/query",
                  {"query": "Count(Row(f=7))"})

            d = _get(s.uri, "/debug/events")
            assert d["node"] == "node0"
            assert d["counters"]["total"] >= 1
            kinds = {e["kind"] for e in d["events"]}
            assert "config.applied" in kinds  # the server's own config
            # kind prefix filter + the since cursor
            cfg = _get(s.uri, "/debug/events?kind=config")["events"]
            assert cfg and all(e["kind"].startswith("config")
                               for e in cfg)
            top = max(e["seq"] for e in d["events"])
            assert _get(s.uri,
                        f"/debug/events?since={top}")["events"] == []
            assert len(_get(s.uri,
                            "/debug/events?limit=1")["events"]) == 1

            # the query's record keys the autopsy route
            recent = _get(s.uri, "/debug/queries")["recent"]
            rec = next(r for r in recent
                       if r["pql"] == "Count(Row(f=7))")
            tid = rec["traceID"]
            out = _get(s.uri, f"/debug/trace/{tid}")
            assert out["root"] is not None
            assert out["origin"] == s.cluster.local_id
            acc = out["accounting"]
            # the walls-sum-to-observed invariant over a REAL record
            # (rounding of the per-stage walls is the only slack)
            assert abs(acc["observedMs"] - acc["accountedMs"]) <= 0.1
            # the record id is the 20-hex fallback (no inbound
            # traceparent) — the route joins it via normalization
            assert out["traceId"] == tracing.normalize_trace_id(tid)
            # ?local=1 is the fan-in target: bare records + events
            loc = _get(s.uri, f"/debug/trace/{tid}?local=1")
            assert set(loc) == {"records", "events"}
            assert any(r["traceID"] == tid for r in loc["records"])

            # merged cluster timeline (single node: just this section)
            m = _get(s.uri, "/debug/cluster/events")
            assert {e["kind"] for e in m["events"]} >= {"config.applied"}
            assert "node0" in m["counters"]
        finally:
            s.close()

    def test_debug_trace_malformed_id_is_400(self, tmp_path):
        s = Server(str(tmp_path / "n0"))
        s.open()
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(s.uri, "/debug/trace/not-hex!")
            assert e.value.code == 400
        finally:
            s.close()

    def test_event_and_trace_gauge_families_render(self, tmp_path):
        """event_*/trace_* land on a clean server's /metrics (zeros)
        and survive the strict parser — covered generically by the
        families test in test_http, pinned here by name so a publisher
        regression is explicit."""
        s = Server(str(tmp_path / "n0"))
        s.open()
        try:
            text = _get(s.uri, "/metrics", expect_json=False).decode()
            for name in ("event_total", "event_dropped", "event_depth",
                         "event_kinds", "trace_assemblies",
                         "trace_fanins", "trace_errors",
                         "trace_orphans"):
                assert f"\n{name}" in text or text.startswith(name), name
            from tools import check_metrics

            # strict-parses AND raises if either family went missing
            counts = check_metrics.check_families(
                text, check_metrics.TRACE_FAMILIES)
            assert all(n >= 1 for n in counts.values())
        finally:
            s.close()

    def test_traceparent_survives_the_wire(self, tmp_path):
        """HTTP-side propagation: a propagated trace id injected by
        InternalClient crosses the wire, is extracted by the handler
        middleware, and lands on the remote node's flight record —
        the join cross-node assembly depends on."""
        s = Server(str(tmp_path / "n0"))
        s.open()
        try:
            c = InternalClient()
            c.create_index(s.uri, "i", {})
            c.create_field(s.uri, "i", "f", {})
            c.import_bits(s.uri, "i", "f", [1], [10])
            tid = tracing.new_trace_id()
            with tracing.propagate(tid):
                assert c.query_node(s.uri, "i", "Count(Row(f=1))",
                                    remote=False) == [1]
            recs = s.node.executor.recorder.records_for_trace(tid)
            assert recs, "traceparent did not reach the server record"
            assert (tracing.normalize_trace_id(recs[-1].trace_id)
                    == tracing.normalize_trace_id(tid))
            c.close()
        finally:
            s.close()

    def test_journal_config_plumbed_from_server_kwargs(self, tmp_path):
        s = Server(str(tmp_path / "n0"), name="nodeX",
                   observe_journal_size=99,
                   observe_journal_kinds="breaker,config")
        s.open()
        try:
            j = observe.journal()
            assert j.node_id == "nodeX"
            assert j._ring.maxlen == 99
            assert j.kinds == {"breaker", "config"}
        finally:
            s.close()
        # close() released the server's retain: baseline restored
        assert observe.journal().kinds == frozenset()


# ========================== traceparent audit over every RPC class


def _spy_transport(transport):
    """Wrap the shared LocalTransport's PUBLIC methods (the
    BoundTransport contract blesses exactly this) recording the
    active trace id at the moment each internal RPC leaves a node."""
    calls: list[tuple[str, str | None, str | None]] = []
    orig_q, orig_s = transport.query_node, transport.send_message

    def q(node, index, pql, shards, **kw):
        calls.append(("query_node", None, tracing.active_trace_id()))
        return orig_q(node, index, pql, shards, **kw)

    def s(node, message):
        calls.append(("send_message", message.get("type"),
                      tracing.active_trace_id()))
        return orig_s(node, message)

    transport.query_node = q
    transport.send_message = s
    return calls


class TestTraceparentPropagationAudit:
    """Every internal RPC class must carry a joinable trace at the
    transport boundary — the property /debug/trace/{id} assembly
    rests on.  The spy records ``tracing.active_trace_id()`` exactly
    where the HTTP transport injects ``traceparent``."""

    def test_shard_map_and_hedge_reissue(self, tmp_path):
        transport, nodes = make_cluster(tmp_path, n=3, replica_n=2)
        nodes[0].create_index("i")
        nodes[0].create_field("i", "f")
        cols, rows = [], []
        for sh in range(6):
            cols.append(sh * SHARD_WIDTH + 1)
            rows.append(1)
        API(nodes[0]).import_bits("i", "f", rows, cols)
        ex = nodes[0].executor
        ex.hedge_min_samples = 2
        ex.hedge_min_s = 0.02
        ex.hedge_max_fraction = 1.0
        for _ in range(4):  # latency EWMA samples for both peers
            assert ex.execute("i", "Count(Row(f=1))")[0] == 6

        calls = _spy_transport(transport)
        assert ex.execute("i", "Count(Row(f=1))")[0] == 6
        rec = ex.recorder.recent_records()[-1]
        want = tracing.normalize_trace_id(rec.trace_id)
        fanout = [c for c in calls if c[0] == "query_node"]
        assert fanout, "no remote shard map issued"
        # every map RPC carried the query's trace (executor.propagate
        # bridges the nop tracer via the record's self-generated id)
        assert all(t and tracing.normalize_trace_id(t) == want
                   for _, _, t in fanout), fanout

        # hedge re-issues ride the SAME trace from the hedge IO thread
        calls.clear()
        transport.set_slow("node1", 1.0)
        transport.set_slow("node2", 0.0)
        try:
            assert ex.execute("i", "Count(Row(f=1))")[0] == 6
        finally:
            transport.set_slow("node1", 0.0)
        assert ex._hedge_issued >= 1, "hedge did not engage"
        rec = ex.recorder.recent_records()[-1]
        want = tracing.normalize_trace_id(rec.trace_id)
        hedged = [c for c in calls if c[0] == "query_node"]
        assert len(hedged) >= 2  # original flight(s) + the hedge
        assert all(t and tracing.normalize_trace_id(t) == want
                   for _, _, t in hedged), hedged
        assert rec.hedge_losers  # the settled race recorded its loser
        # the hedge race journaled under the query's trace too
        fired = observe.journal().events(kind="hedge.fired")
        assert fired and fired[-1]["traceId"] == want

    def test_hint_replay_joins_the_original_write_trace(self, tmp_path):
        transport, nodes = make_cluster(tmp_path, n=3, replica_n=2)
        nodes[0].create_index("i")
        nodes[0].create_field("i", "f")
        hintsmod.configure(write_policy="available")
        from tests.test_selfheal import _owners

        a, b = _owners(nodes, "i", 0)
        bid = b.cluster.local_id
        transport.set_down(bid)
        a.executor.execute("i", "Set(11, f=1)")
        assert a.hints.depth(bid) == 1
        write_trace = tracing.normalize_trace_id(
            a.executor.recorder.recent_records()[-1].trace_id)
        transport.set_down(bid, False)

        calls = _spy_transport(transport)
        out = HintReplayer(a).run_once(force=True)
        assert out["replayed"] == 1
        deliveries = [c for c in calls if c[0] == "query_node"]
        assert deliveries
        # the replay RPC re-attached the QUEUED write's trace — the
        # delivery joins the original write's span tree
        assert all(t and tracing.normalize_trace_id(t) == write_trace
                   for _, _, t in deliveries), deliveries

    def test_ae_round_mints_one_trace_for_its_exchanges(self, tmp_path):
        transport, nodes = make_cluster(tmp_path, n=3, replica_n=2)
        nodes[0].create_index("i")
        nodes[0].create_field("i", "f")
        for sh in range(3):
            nodes[0].executor.execute(
                "i", f"Set({sh * SHARD_WIDTH + 2}, f=1)")
        calls = _spy_transport(transport)
        HolderSyncer(nodes[0]).sync_holder()
        ae = [c for c in calls if c[0] == "send_message"
              and c[1] in ("fragment-blocks", "fragment-block-data",
                           "fragment-import")]
        assert ae, "AE round issued no block exchanges"
        tids = {t for _, _, t in ae}
        # one minted round trace rides EVERY exchange of the slice
        assert None not in tids and len(tids) == 1, ae
        # and the round's lifecycle landed in the journal
        kinds = {e["kind"]
                 for e in observe.journal().events(kind="ae.round")}
        assert "ae.round.start" in kinds

    def test_rebalance_transfers_carry_the_plan_trace(self, tmp_path):
        from pilosa_tpu.parallel import rebalance as _rebalance
        from tests.test_rebalance import (
            _attach_drivers,
            _boot_joiner,
            _seed,
        )

        _rebalance.reset()
        try:
            transport, nodes = make_cluster(tmp_path, n=2, replica_n=1)
            driver = _attach_drivers(nodes)
            _seed(nodes[0], n_shards=4)
            joiner = _boot_joiner(tmp_path, transport, "node2")
            calls = _spy_transport(transport)
            out = driver.start(add=joiner.cluster.local_node,
                               background=False)
            assert out["started"] is True
            moves = [c for c in calls if c[0] == "send_message"
                     and c[1] in ("rebalance-begin",
                                  "rebalance-transfer",
                                  "rebalance-cutover")]
            assert any(c[1] == "rebalance-transfer" for c in moves)
            assert any(c[1] == "rebalance-cutover" for c in moves)
            tids = {t for _, _, t in moves}
            # begin broadcast, backfill transfers and cutovers all
            # carry the ONE plan trace
            assert None not in tids and len(tids) == 1, moves
            plan_ev = observe.journal().events(kind="rebalance.plan")
            assert plan_ev and plan_ev[-1]["traceId"] in tids
        finally:
            _rebalance.reset()


# ======================================== 3-node acceptance pin


def _raw_query(uri, pql):
    req = urllib.request.Request(
        uri + "/index/i/query",
        data=json.dumps({"query": pql}).encode(), method="POST")
    req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req, timeout=30) as resp:
        return resp.read()


class TestDistributedAutopsyAcceptance:
    def test_hedged_query_autopsy_and_cluster_timeline(self, tmp_path):
        """The PR's pin: on a real 3-node HTTP cluster, a hedged query
        under an armed ``client.request.send`` failpoint yields a
        ``/debug/trace/{id}`` tree with spans from every participating
        node INCLUDING the hedge loser's side, per-span walls summing
        to the observed latency; the breaker-open event lands in the
        merged ``/debug/events`` timeline inside the query's window;
        and query results are byte-identical with the journal off."""
        kw = dict(replica_n=2, breaker_threshold=1,
                  breaker_cooldown=0.2, hedge_min_samples=2,
                  hedge_deviations=0.5, hedge_min_ms=10.0,
                  hedge_max_fraction=1.0)
        s0 = Server(str(tmp_path / "n0"), name="node0", **kw)
        s0.open()
        s1 = Server(str(tmp_path / "n1"), name="node1",
                    seeds=[s0.uri], **kw)
        s1.open()
        s2 = Server(str(tmp_path / "n2"), name="node2",
                    seeds=[s0.uri], **kw)
        s2.open()
        try:
            _post(s0.uri, "/index/i")
            _post(s0.uri, "/index/i/field/f")
            cols = [sh * SHARD_WIDTH + sh + 1 for sh in range(6)]
            for c in cols:
                _post(s0.uri, "/index/i/query",
                      {"query": f"Set({c}, f=7)"})
            pql = "Count(Row(f=7))"
            for _ in range(4):  # prime the per-peer latency EWMAs
                r = _post(s0.uri, "/index/i/query?nocache=1",
                          {"query": pql})
                assert r["results"] == [len(cols)]

            # -- hedged flight: every outbound RPC send stalls well
            # past the primed thresholds, so the origin re-issues to
            # replicas; the race's loser is recorded on the origin
            faultinject.arm("client.request.send=delay(150)")
            try:
                r = _post(s0.uri, "/index/i/query?nocache=1",
                          {"query": pql})
            finally:
                faultinject.disarm()
            assert r["results"] == [len(cols)]  # correct under chaos
            recent = _get(s0.uri, "/debug/queries")["recent"]
            rec = next(d for d in recent if d.get("hedged"))
            assert rec["hedgeLosers"], "race settled without a loser"
            loser_nodes = {l["node"] for l in rec["hedgeLosers"]}
            tid = rec["traceID"]

            out = _get(s0.uri, f"/debug/trace/{tid}")
            root = out["root"]
            assert root is not None and out["origin"] == "node0"
            # flight records fanned in from more than one node (the
            # remote sides joined via the propagated traceparent)
            rec_nodes = {d["node"] for d in out["records"]}
            assert len(rec_nodes) >= 2, rec_nodes
            assert any(d.get("remote") for d in out["records"])
            spans = _walk(root)
            span_nodes = {s.get("node") for s in spans} - {None, ""}
            assert len(span_nodes) >= 2, span_nodes
            # ...INCLUDING the hedge loser's side, reported off the
            # critical path
            lost = [s for s in spans if s.get("offCriticalPath")]
            assert lost, "hedge loser missing from the span tree"
            assert any(ln in s["name"] for s in lost
                       for ln in loser_nodes)
            # per-span walls sum to the observed latency (rounding of
            # the many leaf walls is the only slack)
            acc = out["accounting"]
            assert acc["observedMs"] > 0
            assert (abs(acc["observedMs"] - acc["accountedMs"])
                    <= max(0.25, 0.02 * acc["observedMs"])), acc

            # -- breaker-open lands in the merged cluster timeline
            # inside the armed query's window
            opened = []
            for _ in range(3):  # a heartbeat may eat the one-shot
                t_arm = time.time()
                faultinject.arm(
                    "client.request.send=error(transport)*1")
                try:
                    r = _post(s0.uri, "/index/i/query?nocache=1",
                              {"query": pql})
                finally:
                    faultinject.disarm()
                assert r["results"] == [len(cols)]  # failed over
                merged = _get(s0.uri,
                              "/debug/cluster/events?kind=breaker")
                opened = [e for e in merged["events"]
                          if e["kind"] == "breaker.open"
                          and e["t"] >= t_arm - 0.1]
                if opened:
                    break
            assert opened, "breaker.open missing from the timeline"
            assert merged["counters"]  # per-node journal counters rode in

            # -- journal-off regression pin: byte-identical results,
            # zero events emitted, on the one-bool disarmed path
            b_on = _raw_query(s0.uri, pql)
            observe.configure(enabled=False)
            try:
                c0 = observe.journal().counters()
                b_off = _raw_query(s0.uri, pql)
                c1 = observe.journal().counters()
            finally:
                observe.configure(enabled=True)
            assert b_off == b_on
            assert (c1["total"], c1["dropped"]) == \
                (c0["total"], c0["dropped"])
        finally:
            for s in (s2, s1, s0):
                s.close()
