"""Device-runtime telemetry (pilosa_tpu.devobs): compile tracking per
kernel/canonical shape, the pinned compile-attribution semantics on the
query flight record, transfer metering through the staging funnel,
/debug/devices, the device.*/compile.*/residency.* metric families, and
the cluster-wide /debug/cluster/* fan-in over a 3-node in-process
cluster."""

from __future__ import annotations

import json
import time
import urllib.request

import jax
import numpy as np
import pytest

from pilosa_tpu import devobs, observe, stats as _stats
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.ops import expr
from pilosa_tpu.parallel.executor import ExecOptions, Executor
from pilosa_tpu.server.server import Server
from pilosa_tpu.shardwidth import SHARD_WIDTH


def _post(uri, path, obj=None):
    body = json.dumps(obj or {}).encode()
    req = urllib.request.Request(uri + path, data=body, method="POST")
    req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req, timeout=10) as resp:
        return json.loads(resp.read() or b"null")


def _get(uri, path, expect_json=True):
    with urllib.request.urlopen(uri + path, timeout=15) as resp:
        raw = resp.read()
    return json.loads(raw) if expect_json else raw


def _fresh_compile_state():
    """Guarantee the next device dispatch pays a real compile: drop the
    fused-program closure cache AND jax's own jit caches, and start a
    clean observer."""
    expr._compiled.cache_clear()
    jax.clear_caches()
    return devobs.reset()


# --------------------------------------------------------------- instrument


class TestInstrument:
    def test_cache_miss_detected_once_per_shape(self):
        obs = devobs.reset()
        import jax.numpy as jnp

        fn = devobs.instrument("t.k", jax.jit(lambda a: a + 1))
        a = jnp.arange(8, dtype=jnp.int32)
        fn(a)
        fn(a)
        snap = obs.snapshot()
        k = snap["compile"]["kernels"]["t.k"]
        assert k["compiles"] == 1
        assert k["totalMs"] > 0
        (shape_key,) = k["shapes"]
        assert shape_key == "(int32[8])"
        # a new canonical shape compiles again, under its own key
        fn(jnp.arange(16, dtype=jnp.int32))
        k = obs.snapshot()["compile"]["kernels"]["t.k"]
        assert k["compiles"] == 2
        assert len(k["shapes"]) == 2

    def test_fallback_without_cache_size(self):
        obs = devobs.reset()

        def raw(a):  # no _cache_size attribute -> first-seen-key path
            return a

        fn = devobs.instrument("t.fallback", raw)
        fn(np.zeros(4, dtype=np.uint32))
        fn(np.zeros(4, dtype=np.uint32))
        fn(np.zeros(8, dtype=np.uint32))
        k = obs.snapshot()["compile"]["kernels"]["t.fallback"]
        assert k["compiles"] == 2  # one per distinct shape

    def test_disabled_observer_records_nothing(self):
        obs = devobs.reset()
        obs.enabled = False
        import jax.numpy as jnp

        fn = devobs.instrument("t.off", jax.jit(lambda a: a * 2))
        fn(jnp.arange(4, dtype=jnp.int32))
        assert obs.snapshot()["compile"]["total"] == 0
        obs.enabled = True

    def test_compile_stamps_active_query_record(self):
        devobs.reset()
        import jax.numpy as jnp

        fn = devobs.instrument("t.rec", jax.jit(lambda a: a - 1))
        rec = observe.QueryRecord(1, "i", "Count(Row(f=1))")
        with observe.attach(rec):
            fn(jnp.arange(5, dtype=jnp.int32))
        d = rec.to_dict()
        assert d["compiled"] is True
        assert d["compileMs"] > 0
        assert d["compileKernels"] == {"t.rec": 1}
        # outside the scope nothing is stamped
        rec2 = observe.QueryRecord(2, "i", "q")
        fn(jnp.arange(5, dtype=jnp.int32))
        assert rec2.to_dict()["compiled"] is False

    def test_wrapper_delegates_jit_attrs(self):
        fn = devobs.instrument("t.attrs", jax.jit(lambda a: a))
        assert callable(fn.clear_cache)  # reaches through to the jit

    def test_compile_histogram_published_to_stats(self):
        obs = devobs.reset()
        obs.stats = _stats.MemStatsClient()
        import jax.numpy as jnp

        fn = devobs.instrument("t.hist", jax.jit(lambda a: a ^ 1))
        fn(jnp.arange(4, dtype=jnp.int32))
        snap = obs.stats.snapshot()
        key = [k for k in snap if k.startswith("compile.ms")]
        assert key and snap[key[0]]["count"] == 1


# ----------------------------------------------------------- compile events


def _compile_one(name="t.ev", n=6):
    """One real compile through an instrumented jit -> its event."""
    import jax.numpy as jnp

    fn = devobs.instrument(name, jax.jit(lambda a: (a * 3).sum()))
    fn(jnp.arange(n, dtype=jnp.int32))
    return devobs.observer().snapshot()["compile"]["events"][-1]


def _event_on_the_span_clock():
    devobs.reset()
    before = observe.clock_ns()
    ev = _compile_one()
    after = observe.clock_ns()
    assert before <= ev["startNs"] <= ev["endNs"] <= after
    assert ev["ms"] == round((ev["endNs"] - ev["startNs"]) / 1e6, 3)
    assert (ev["kernel"], ev["shape"]) == ("t.ev", "(int32[6])")
    import threading

    assert ev["thread"] == threading.get_ident() and ev["rid"] is None


def _event_phases_and_cache_off():
    """JAX's own account of the compile, from the listeners: each phase
    ran, none longer than the whole, and with no cache directory (the
    test process's) the persistent cache said nothing."""
    devobs.reset()
    ev = _compile_one()
    assert ev["persistent"] == "off"
    for phase in ("traceMs", "lowerMs", "backendMs"):
        assert 0 < ev[phase] <= ev["ms"], (phase, ev)
    # a warm call fires no listener and adds no event (the marks are a
    # bounded deque: compare its newest entries, not its length)
    import jax.numpy as jnp

    def newest():
        return list(devobs._marks)[-8:]

    before = newest()
    fn = devobs.instrument("t.warm", jax.jit(lambda a: a + 2))
    a = jnp.arange(4, dtype=jnp.int32)
    fn(a)
    grown = newest()
    assert grown != before
    fn(a)
    assert newest() == grown
    assert len(devobs.observer().snapshot()["compile"]["events"]) == 2


def _event_list_is_capped():
    obs = devobs.reset()
    for i in range(devobs.MAX_EVENTS + 44):
        obs.note_compile("t.cap", f"(int32[{i}])", 1000)
    snap = obs.snapshot()["compile"]
    assert snap["total"] == devobs.MAX_EVENTS + 44
    assert len(snap["events"]) == devobs.MAX_EVENTS == 256
    assert snap["events"][-1]["shape"] == f"(int32[{299}])"
    assert snap["events"][0]["shape"] == "(int32[44])"


def _event_names_the_paying_record():
    """The read that paid: ``rid`` is its trace id, and its record
    carries a ``compile`` span under the span that was open, inside
    it on the clock."""
    devobs.reset()
    rec = observe.FlightRecorder().begin("i", "Count(Row(f=1))")
    with observe.attach(rec):
        with observe.span("launch.dispatch") as outer:
            ev = _compile_one("t.paid", 9)
    assert ev["rid"] == rec.trace_id
    spans = {s[2]: s for s in rec.spans}
    sid, parent, _, start, end, _, counts = spans["compile"]
    assert parent == outer.id and sid != outer.id
    assert (start, end) == (ev["startNs"], ev["endNs"])
    assert outer.start_ns <= start <= end <= outer.end_ns
    assert counts == {"kernel": "t.paid", "persistent": "off"}
    assert [d["name"] for d in rec.to_dict()["spans"]
            if d["parent"] == outer.id] == ["compile"]


def _event_reaches_the_journal():
    devobs.reset()
    journal = observe.reset_journal()
    rec = observe.QueryRecord(8, "i", "q")
    with observe.attach(rec):
        ev = _compile_one("t.journal", 10)
    (said,) = journal.events(kind="compile")
    assert (said["kernel"], said["shape"], said["ms"],
            said["persistent"]) == ("t.journal", "(int32[10])",
                                    ev["ms"], "off")
    assert journal.events(trace_id=rec.trace_id) == [said]
    # off: the emission site stops at the module bool
    observe.journal_on = False
    try:
        _compile_one("t.journal", 11)
    finally:
        observe.journal_on = True
    assert len(journal.events(kind="compile")) == 1


def _event_says_hit_or_miss(tmp_path):
    """With a persistent cache directory the first compile of a program
    writes its entry (``miss``, counted ``compile.cold``) and the same
    program compiled again after the jit caches are dropped reads it
    back (``hit``)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    obs = devobs.reset()
    keep = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()
        said = []
        for _ in range(2):
            jax.clear_caches()
            said.append(_compile_one("t.cache", 12)["persistent"])
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert said == ["miss", "hit"]
    assert obs.compile_cold == 1
    stats = _stats.MemStatsClient()
    obs.publish_gauges(stats)
    snap = stats.snapshot()
    assert (snap["compile.cold"], snap["compile.count"]) == (1, 2)


COMPILE_EVENT = {
    "on the span clock": _event_on_the_span_clock,
    "phases, and persistent off": _event_phases_and_cache_off,
    "capped at 256": _event_list_is_capped,
    "rid and the compile span": _event_names_the_paying_record,
    "journal": _event_reaches_the_journal,
    "persistent hit or miss": _event_says_hit_or_miss,
}


@pytest.mark.parametrize("case", list(COMPILE_EVENT))
def test_compile_event(case, tmp_path):
    """A detected compile is an event on ``/debug/devices``
    ``compile.events``: which program and shape, when on the span
    clock, paid by which read, how long by JAX's phases, and what the
    persistent cache said."""
    fn = COMPILE_EVENT[case]
    fn(tmp_path) if case.startswith("persistent") else fn()


# ------------------------------------------------------ compile attribution


class TestCompileAttribution:
    @pytest.fixture
    def ex(self, tmp_path):
        holder = Holder(str(tmp_path / "ca"))
        idx = holder.create_index("i")
        idx.create_field("f")
        e = Executor(holder)
        for s in range(2):
            for k in range(5):
                e.execute("i", f"Set({s * SHARD_WIDTH + k}, f=3)")
        yield e
        holder.close()

    def test_first_query_on_fresh_shape_compiles_followup_does_not(
            self, ex):
        """The acceptance pin: a query that triggers an XLA compile
        carries compiled=true with nonzero compile_ms; an identical
        follow-up (same canonical shape, warm jit cache) carries
        compiled=false."""
        # warm stacks + translation WITHOUT filling the result cache —
        # the measured run must really execute (and compile)
        ex.execute("i", "Count(Row(f=3))", opt=ExecOptions(cache=False))
        _fresh_compile_state()
        assert int(ex.execute("i", "Count(Row(f=3))")[0]) == 10
        first = ex.recorder.recent_records()[-1].to_dict()
        assert first["compiled"] is True
        assert first["compileMs"] > 0
        assert first["compileKernels"]
        assert int(ex.execute("i", "Count(Row(f=3))")[0]) == 10
        second = ex.recorder.recent_records()[-1].to_dict()
        assert second["compiled"] is False
        assert second["compileMs"] == 0

    def test_slow_query_log_carries_compile_attribution(self, ex):
        class _Log:
            lines: list[str] = []

            def printf(self, fmt, *args):
                self.lines.append(fmt % args if args else fmt)

        # warm without filling the result cache (see the test above)
        ex.execute("i", "Count(Row(f=3))", opt=ExecOptions(cache=False))
        _fresh_compile_state()
        log = _Log()
        ex.recorder.logger = log
        ex.recorder.long_query_time = 1e-9  # everything is "slow"
        ex.execute("i", "Count(Row(f=3))")
        assert any("compiled=true" in ln and "compile_ms=" in ln
                   for ln in log.lines), log.lines
        log.lines.clear()
        ex.execute("i", "Count(Row(f=3))")
        assert any("compiled=false" in ln for ln in log.lines)


# --------------------------------------------------------- transfer metering


class TestTransferMetering:
    def test_put_reports_bytes_under_its_label(self):
        obs = devobs.reset()
        stack = np.random.randint(
            0, 2**32, size=(64, 256), dtype=np.uint64).astype(np.uint32)
        dev = bm.device_put(stack, label="test.stack")
        assert np.array_equal(np.asarray(dev), stack)
        snap = obs.snapshot()["transfer"]
        assert snap["bytes"] == stack.nbytes
        assert snap["byLabel"]["test.stack"]["puts"] == 1

    def test_unlabelled_put_counts_one_chunk(self):
        obs = devobs.reset()
        stack = np.zeros((4, 8), dtype=np.uint32)
        bm.device_put(stack)
        snap = obs.snapshot()["transfer"]
        assert snap["chunks"] == 1
        assert "other" in snap["byLabel"]

    def test_query_path_attributes_field_staging(self, tmp_path):
        obs = devobs.reset()
        holder = Holder(str(tmp_path / "tm"))
        idx = holder.create_index("i")
        idx.create_field("f")
        ex = Executor(holder)
        ex.execute("i", "Set(1, f=2)")
        ex.execute("i", "Count(Row(f=2))")
        ex.execute("i", "TopN(f)")
        labels = obs.snapshot()["transfer"]["byLabel"]
        # every staged tensor is attributed to a known owner
        assert labels and all(
            lbl.partition(".")[0] in ("field", "fragment")
            for lbl in labels), labels
        holder.close()


# ------------------------------------------------------------ debug surfaces


@pytest.fixture
def srv(tmp_path):
    s = Server(str(tmp_path / "devsrv"))
    s.open()
    yield s
    s.close()


class TestDebugDevices:
    def _prime(self, uri):
        _post(uri, "/index/dv")
        _post(uri, "/index/dv/field/f")
        _post(uri, "/index/dv/query", {"query": "Set(1, f=9)"})
        # ?nodelta=1: the Set lands in the streaming delta plane, and a
        # plain single-shard read would answer from the host overlay
        # without ever touching the device — this test needs the
        # up-front compaction + device-matrix read so a transfer is
        # actually metered
        _post(uri, "/index/dv/query?nodelta=1",
              {"query": "Count(Row(f=9))"})

    def test_debug_devices_document(self, srv):
        devobs.reset()
        srv.handler  # observer stats rewired below via publish path
        self._prime(srv.uri)
        d = _get(srv.uri, "/debug/devices")
        assert d["enabled"] is True
        assert set(d["compile"]) == {"total", "totalMs", "kernels",
                                     "programEvictions", "events"}
        assert len(d["compile"]["events"]) == d["compile"]["total"]
        for k in d["compile"]["kernels"].values():
            assert k["compiles"] >= 1 and "shapes" in k
        assert d["transfer"]["bytes"] > 0
        assert d["transfer"]["puts"] == sum(
            v["puts"] for v in d["transfer"]["byLabel"].values())
        res = d["residency"]
        assert {"budget", "total", "entries", "evictions", "admits",
                "high_water"} <= set(res)
        assert res["total"] <= res["budget"]
        assert res["high_water"] >= res["total"]
        # topology listed even where the backend reports no memory
        # stats (CPU); TPU adds bytesInUse/bytesLimit
        assert d["devices"] and all(
            "platform" in e and "id" in e for e in d["devices"])

    def test_metrics_and_vars_carry_device_families(self, srv):
        from tools import check_metrics

        self._prime(srv.uri)
        text = _get(srv.uri, "/metrics", expect_json=False).decode()
        fams = check_metrics.check_families(text)
        assert all(n >= 1 for n in fams.values())
        snap = _get(srv.uri, "/debug/vars")
        for key in ("residency.usage_bytes", "residency.budget_bytes",
                    "residency.evictions", "compile.count",
                    "device.transfer_bytes"):
            assert key in snap, key

    def test_check_families_flags_missing_family(self):
        from tools import check_metrics

        text = ("# TYPE residency_usage_bytes gauge\n"
                "residency_usage_bytes 0\n")
        with pytest.raises(ValueError, match="compile_"):
            check_metrics.check_families(
                text, ("residency_", "compile_"))

    def test_sampler_publishes_gauges(self):
        stats = _stats.MemStatsClient()
        sampler = devobs.DeviceSampler(stats, 0.01)
        sampler.start()
        try:
            deadline = time.monotonic() + 2.0
            while time.monotonic() < deadline:
                if "residency.usage_bytes" in stats.snapshot():
                    break
                time.sleep(0.01)
            assert "residency.usage_bytes" in stats.snapshot()
        finally:
            sampler.stop()


# --------------------------------------------------------- cluster fan-in


@pytest.fixture
def cluster3(tmp_path):
    s0 = Server(str(tmp_path / "n0"), name="node0")
    s0.open()
    s1 = Server(str(tmp_path / "n1"), name="node1", seeds=[s0.uri])
    s1.open()
    s2 = Server(str(tmp_path / "n2"), name="node2", seeds=[s0.uri])
    s2.open()
    yield s0, s1, s2
    for s in (s2, s1, s0):
        s.close()


class TestClusterFanIn:
    def test_cluster_queries_merges_every_node(self, cluster3):
        """Acceptance pin: /debug/cluster/queries merges records from
        every node of a 3-node in-process cluster."""
        s0, s1, s2 = cluster3
        _post(s0.uri, "/index/ci")
        _post(s0.uri, "/index/ci/field/f")
        for s in range(6):
            _post(s0.uri, "/index/ci/query",
                  {"query": f"Set({s * SHARD_WIDTH + 1}, f=1)"})
        # every node originates at least one query of its own, so every
        # node's recorder holds a record the merge must surface
        for node in cluster3:
            _post(node.uri, "/index/ci/query",
                  {"query": "Count(Row(f=1))"})
        d = _get(s0.uri, "/debug/cluster/queries")
        assert set(d["nodes"]) == {"node0", "node1", "node2"}
        assert d["errors"] == {}
        by_node = {rec["node"] for rec in d["recent"]}
        assert by_node == {"node0", "node1", "node2"}
        # merged list is newest-first and each record keeps its shape
        starts = [rec["startTime"] for rec in d["recent"]]
        assert starts == sorted(starts, reverse=True)
        assert all("elapsedMs" in rec and "pql" in rec
                   for rec in d["recent"])
        # min_ms passthrough reaches the peers too
        d2 = _get(s0.uri, "/debug/cluster/queries?min_ms=100000")
        assert all(not sec["recent"] and not sec["active"]
                   for sec in d2["nodes"].values())

    def test_cluster_devices_merges_and_totals(self, cluster3):
        s0, s1, s2 = cluster3
        _fresh_compile_state()  # the queries below must pay a compile
        _post(s0.uri, "/index/cd")
        _post(s0.uri, "/index/cd/field/f")
        for s in range(6):
            _post(s0.uri, "/index/cd/query",
                  {"query": f"Set({s * SHARD_WIDTH + 1}, f=1)"})
        _post(s0.uri, "/index/cd/query", {"query": "Count(Row(f=1))"})
        d = _get(s1.uri, "/debug/cluster/devices")
        assert set(d["nodes"]) == {"node0", "node1", "node2"}
        for sec in d["nodes"].values():
            assert {"compile", "transfer", "residency",
                    "devices"} <= set(sec)
        t = d["totals"]
        assert t["compiles"] >= 1  # in-process: one shared observer x3
        assert t["transferBytes"] > 0
        assert t["residencyBytes"] >= 0

    def test_dead_peer_degrades_to_error_entry(self, cluster3):
        s0, s1, s2 = cluster3
        s0.handler.fanin_timeout = 1.0
        s2.handler.close()  # node2 stops accepting HTTP
        # drop s0's pooled keep-alive sockets to node2 — the closed
        # accept loop leaves already-open connections alive, and a
        # pooled socket would still answer
        s0._client.close()
        d = _get(s0.uri, "/debug/cluster/queries")
        assert "node2" in d["errors"]
        assert {"node0", "node1"} <= set(d["nodes"])

    def test_single_node_cluster_routes_work(self, srv):
        d = _get(srv.uri, "/debug/cluster/queries")
        assert list(d["nodes"]) == [srv.cluster.local_id]
        assert d["errors"] == {}
        d = _get(srv.uri, "/debug/cluster/devices")
        assert list(d["nodes"]) == [srv.cluster.local_id]
