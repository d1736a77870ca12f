"""Admission control + deadline propagation (pilosa_tpu/serve/):
per-class gating and FIFO queueing, newest-first load shedding with
honest 429/503 + Retry-After, end-to-end deadlines that keep expired
work off the device dispatch path, the deadline-aware coalescer
flush, client-side Retry-After handling, the accept-side thread cap,
and an open-loop 2x-capacity overload run (tools/loadgen.py)."""

from __future__ import annotations

import json
import random
import socket
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, HTTPServer

import pytest

from pilosa_tpu import stats as _stats
from pilosa_tpu.config import Config
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.parallel.coalescer import Coalescer
from pilosa_tpu.parallel.executor import ExecOptions, Executor
from pilosa_tpu.serve import deadline as deadline_mod
from pilosa_tpu.serve.admission import (
    AdmissionController,
    ShedError,
    current_rpc_class,
    rpc_class,
)
from pilosa_tpu.serve.deadline import Deadline, DeadlineExceededError
from pilosa_tpu.server.client import InternalClient
from pilosa_tpu.server.server import Server
from pilosa_tpu.shardwidth import SHARD_WIDTH
from tests.coalesce_batch import (map_behind_launch, queued,
                                  run_behind_launch, wait_until)

N_SHARDS = 3


# ---------------------------------------------------------------------------
# deadline primitives
# ---------------------------------------------------------------------------


class TestDeadline:
    def test_parse_and_remaining(self):
        dl = deadline_mod.parse_header("1.5")
        assert 1.0 < dl.remaining() <= 1.5
        assert not dl.expired()

    def test_zero_and_negative_are_expired(self):
        assert deadline_mod.parse_header("0").expired()
        assert deadline_mod.parse_header("-3").expired()

    @pytest.mark.parametrize("raw", ["junk", "", "nan", "inf"])
    def test_malformed_rejected(self, raw):
        with pytest.raises(ValueError):
            deadline_mod.parse_header(raw)

    def test_clamped_to_max(self):
        dl = deadline_mod.parse_header("9999999")
        assert dl.remaining() <= deadline_mod.MAX_BUDGET_S

    def test_scope_nesting_restores(self):
        a, b = Deadline(10), Deadline(20)
        assert deadline_mod.current() is None
        with deadline_mod.scope(a):
            assert deadline_mod.current() is a
            with deadline_mod.scope(b):
                assert deadline_mod.current() is b
            assert deadline_mod.current() is a
        assert deadline_mod.current() is None

    def test_check_raises_only_when_expired(self):
        deadline_mod.check(None, "x")
        deadline_mod.check(Deadline(5), "x")
        with pytest.raises(DeadlineExceededError):
            deadline_mod.check(Deadline(-1), "x")


class TestRpcClass:
    def test_scope_and_restore(self):
        assert current_rpc_class() is None
        with rpc_class("internal"):
            assert current_rpc_class() == "internal"
            with rpc_class("ingest"):
                assert current_rpc_class() == "ingest"
            assert current_rpc_class() == "internal"
        assert current_rpc_class() is None

    def test_unknown_class_rejected(self):
        with pytest.raises(ValueError):
            rpc_class("bogus")


# ---------------------------------------------------------------------------
# controller unit behavior
# ---------------------------------------------------------------------------


def _controller(**kw):
    kw.setdefault("stats", _stats.MemStatsClient())
    return AdmissionController(**kw)


class TestController:
    def test_uncontended_admit_release(self):
        ctrl = _controller(query_cap=2)
        t1 = ctrl.acquire("query")
        t2 = ctrl.acquire("query")
        assert t1.queue_wait_ns == 0 and t2.queue_wait_ns == 0
        t1.release()
        t2.release()
        t2.release()  # idempotent
        dbg = ctrl.debug()["classes"]["query"]
        assert dbg["inFlight"] == 0 and dbg["admitted"] == 2

    def test_fifo_promotion_order(self):
        ctrl = _controller(query_cap=1, query_queue=4)
        holder = ctrl.acquire("query")
        order: list[int] = []
        ready = threading.Barrier(3)

        def waiter(i):
            ready.wait()
            if i == 1:
                time.sleep(0.05)  # enforce enqueue order 0 then 1
            t = ctrl.acquire("query")
            order.append(i)
            t.release()

        ts = [threading.Thread(target=waiter, args=(i,)) for i in (0, 1)]
        for t in ts:
            t.start()
        ready.wait()
        time.sleep(0.2)  # both queued behind the held slot
        holder.release()
        for t in ts:
            t.join(5)
        assert order == [0, 1]

    def test_queue_full_sheds_newest_with_429(self):
        ctrl = _controller(query_cap=1, query_queue=1)
        holder = ctrl.acquire("query")
        queued_err = []

        def queued():
            try:
                ctrl.acquire("query").release()
            except ShedError as e:  # pragma: no cover - must not shed
                queued_err.append(e)

        t = threading.Thread(target=queued)
        t.start()
        time.sleep(0.1)  # the older request occupies the queue slot
        with pytest.raises(ShedError) as e:
            ctrl.acquire("query")
        assert e.value.status == 429
        assert e.value.reason == "queue-full"
        assert e.value.retry_after >= 1
        assert e.value.outcome == "shed"
        holder.release()
        t.join(5)
        assert not queued_err  # the queued (older) request was admitted

    def test_expired_in_queue_sheds_503(self):
        ctrl = _controller(query_cap=1, query_queue=4)
        holder = ctrl.acquire("query")
        t0 = time.monotonic()
        with pytest.raises(ShedError) as e:
            ctrl.acquire("query", Deadline(0.1))
        assert e.value.status == 503
        assert e.value.reason == "expired"
        assert e.value.outcome == "expired"
        # the refusal carries the queue wait it burned — the shed
        # flight record's queueWaitMs evidence
        assert e.value.wait_ns >= 0.1 * 1e9
        assert time.monotonic() - t0 < 5.0  # waited ~the deadline only
        holder.release()
        assert ctrl.debug()["classes"]["query"]["expired"] == 1

    def test_predicted_wait_exceeding_deadline_sheds_upfront(self):
        ctrl = _controller(query_cap=1, query_queue=8)
        ctrl._gates["query"].ewma_service_s = 0.5  # seeded history
        holder = ctrl.acquire("query")
        t0 = time.monotonic()
        with pytest.raises(ShedError) as e:
            # predicted wait = (0 waiters + 1) * 0.5s > 10ms remaining
            ctrl.acquire("query", Deadline(0.01))
        assert e.value.reason == "deadline-unmeetable"
        assert e.value.status == 503
        assert time.monotonic() - t0 < 0.01 + 0.5  # shed up front, no wait
        holder.release()

    def test_internal_yields_under_query_pressure(self):
        ctrl = _controller(query_cap=1, query_queue=2,
                           internal_cap=4, internal_queue=4)
        holder = ctrl.acquire("query")
        waiter = threading.Thread(
            target=lambda: ctrl.acquire("query").release())
        waiter.start()
        time.sleep(0.1)  # 1 waiter -> 2*1 >= depth 2: pressure
        with pytest.raises(ShedError) as e:
            ctrl.acquire("internal")
        assert e.value.reason == "yield-to-query"
        # ingest does NOT yield: isolation, not a global brake
        ctrl.acquire("ingest").release()
        holder.release()
        waiter.join(5)
        # pressure gone: internal admits again
        ctrl.acquire("internal").release()

    def test_class_isolation_internal_cannot_take_query_slots(self):
        ctrl = _controller(query_cap=2, internal_cap=1,
                           internal_queue=0)
        ih = ctrl.acquire("internal")
        with pytest.raises(ShedError):  # internal is full
            ctrl.acquire("internal")
        # query slots untouched by internal saturation
        q1, q2 = ctrl.acquire("query"), ctrl.acquire("query")
        for t in (ih, q1, q2):
            t.release()

    def test_disabled_controller_admits_everything(self):
        ctrl = _controller(enabled=False, query_cap=1, query_queue=0)
        tickets = [ctrl.acquire("query") for _ in range(10)]
        for t in tickets:
            t.release()

    def test_stats_counters(self):
        stats = _stats.MemStatsClient()
        ctrl = _controller(query_cap=1, query_queue=0, stats=stats)
        h = ctrl.acquire("query")
        with pytest.raises(ShedError):
            ctrl.acquire("query")
        h.release()
        snap = stats.snapshot()
        admitted = [k for k in snap if k.startswith("admission.admitted")]
        shed = [k for k in snap if k.startswith("admission.shed")]
        assert admitted and shed
        assert any("class:query" in k for k in admitted)
        assert any("reason:queue-full" in k for k in shed)

    def test_total_capacity(self):
        ctrl = _controller(query_cap=2, query_queue=3, ingest_cap=1,
                           ingest_queue=1, internal_cap=1,
                           internal_queue=0)
        assert ctrl.total_capacity() == 8

    def test_uncontended_overhead_small(self):
        """The gate must be invisible on the uncontended path; what
        it takes is counted in tests/test_observer_cost.py (two lock
        takes and one counter an admitted request, none with the gate
        off) — this is the coarse CI regression net against a lock
        disaster."""
        ctrl = _controller()
        ctrl.acquire("query").release()
        n = 2000
        t0 = time.perf_counter()
        for _ in range(n):
            ctrl.acquire("query").release()
        per_us = (time.perf_counter() - t0) / n * 1e6
        assert per_us < 100.0, per_us


# ---------------------------------------------------------------------------
# executor deadline semantics
# ---------------------------------------------------------------------------


@pytest.fixture
def ex(tmp_path):
    holder = Holder(str(tmp_path / "h"))
    idx = holder.create_index("i")
    rng = random.Random(7)
    for fi in range(2):
        f = idx.create_field(f"f{fi}")
        rows, cols = [], []
        for row in range(4):
            for _ in range(120):
                rows.append(row)
                cols.append(rng.randrange(N_SHARDS * SHARD_WIDTH))
        f.import_bits(rows, cols)
        idx.import_existence(cols)
    yield Executor(holder)
    holder.close()


QUERY = "Count(Intersect(Row(f0=1), Row(f1=2)))"


class TestExecutorDeadline:
    def test_expired_before_translate_never_dispatches(self, ex):
        """The acceptance pin: an expired query costs ZERO device
        launches (ops/bitmap.py dispatch-count hook)."""
        ex.execute("i", QUERY)  # warm stacks + jit
        with bm.dispatch_counter() as dc:
            with pytest.raises(DeadlineExceededError):
                ex.execute("i", QUERY,
                           opt=ExecOptions(deadline=Deadline(-1.0)))
        assert dc.n == 0, dc.launches

    def test_expired_never_dispatches_per_shard_path(self, ex):
        ex.fuse_shards = False
        try:
            ex.execute("i", QUERY)
            with bm.dispatch_counter() as dc:
                with pytest.raises(DeadlineExceededError):
                    ex.execute("i", QUERY,
                               opt=ExecOptions(deadline=Deadline(-1.0)))
            assert dc.n == 0, dc.launches
        finally:
            ex.fuse_shards = True

    def test_local_map_checks_before_each_shard(self, ex):
        ran: list[int] = []
        with pytest.raises(DeadlineExceededError):
            ex._local_map(lambda s: ran.append(s), [0, 1, 2],
                          deadline=Deadline(-1.0))
        assert ran == []

    def test_live_deadline_executes_normally(self, ex):
        want = ex.execute("i", QUERY)[0]
        got = ex.execute("i", QUERY,
                         opt=ExecOptions(deadline=Deadline(30.0)))[0]
        assert got == want

    def test_expired_record_outcome(self, ex):
        with pytest.raises(DeadlineExceededError):
            ex.execute("i", QUERY,
                       opt=ExecOptions(deadline=Deadline(-1.0)))
        rec = ex.recorder.recent_records()[-1]
        assert rec.outcome == "expired"
        assert len(rec.launches) == 0
        assert rec.to_dict()["outcome"] == "expired"


class TestCoalescerDeadline:
    def test_expired_entry_dropped_without_poisoning_batch(self, ex):
        """An entry whose deadline dies in the window resolves to
        DeadlineExceededError; its batchmate's count is unaffected."""
        from pilosa_tpu.pql import parse

        expected = ex.execute("i", QUERY)[0]
        stats = _stats.MemStatsClient()
        co = Coalescer(window_s=0.3, max_batch=8, enabled=True,
                       stats=stats)
        idx = ex.holder.index("i")
        child = ex._prepare(idx, parse(QUERY).calls[0].children[0])
        shards = tuple(sorted(idx.available_shards()))
        results: dict = {}
        errs: dict = {}

        def leader():
            try:
                results["a"] = co.count(ex, idx, child, shards)
            except BaseException as e:  # noqa: BLE001
                errs["a"] = e

        def follower():
            wait_until(lambda: queued(co))  # join the leader's bucket
            try:
                results["b"] = co.count(ex, idx, child, shards,
                                        deadline=Deadline(-1.0))
            except BaseException as e:  # noqa: BLE001
                errs["b"] = e

        run_behind_launch(co, [threading.Thread(target=leader),
                               threading.Thread(target=follower)])
        assert "a" not in errs, errs
        assert results["a"] == expected
        assert isinstance(errs.get("b"), DeadlineExceededError)
        assert stats.snapshot().get("coalescer.deadline_dropped") == 1
        assert stats.snapshot().get("coalescer.dispatches") == 1

    def test_tight_deadline_bypasses_window(self, ex):
        """remaining < 2*window: the query must not be held for
        batching — it runs the solo fused path instead."""
        ex.coalescer = Coalescer(window_s=0.2, max_batch=8,
                                 enabled=True,
                                 stats=_stats.MemStatsClient())
        expected = ex.execute("i", QUERY,
                              opt=ExecOptions(coalesce=False))[0]
        t0 = time.perf_counter()
        got = ex.execute("i", QUERY,
                         opt=ExecOptions(deadline=Deadline(0.15)))[0]
        assert got == expected
        assert time.perf_counter() - t0 < 0.15  # never waited the window

    def test_no_deadline_still_coalesces(self, ex):
        stats = _stats.MemStatsClient()
        ex.coalescer = Coalescer(window_s=0.25, max_batch=4,
                                 enabled=True, stats=stats)
        out = map_behind_launch(
            ex.coalescer, lambda i: ex.execute("i", QUERY)[0], 4)
        assert len(set(out)) == 1
        occ = stats.snapshot().get("coalescer.batch_occupancy", {})
        assert occ.get("count", 0) >= 1


# ---------------------------------------------------------------------------
# HTTP surface: gating, shedding, outcomes, thread cap
# ---------------------------------------------------------------------------


def _post(uri, path, obj=None, headers=None, timeout=10):
    body = json.dumps(obj or {}).encode()
    req = urllib.request.Request(uri + path, data=body, method="POST")
    req.add_header("Content-Type", "application/json")
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read() or b"null")


def _get(uri, path, timeout=10):
    with urllib.request.urlopen(uri + path, timeout=timeout) as resp:
        return json.loads(resp.read())


@pytest.fixture
def srv(tmp_path):
    s = Server(str(tmp_path / "adm"),
               admission_query_cap=2, admission_query_queue=4,
               admission_ingest_cap=2, admission_ingest_queue=2,
               admission_internal_cap=2, admission_internal_queue=2)
    s.open()
    _post(s.uri, "/index/i")
    _post(s.uri, "/index/i/field/f")
    _post(s.uri, "/index/i/query", {"query": "Set(1, f=1)"})
    yield s
    s.close()


def _slow_executor(s, delay_s):
    orig = s.node.executor.execute

    def slow(*a, **kw):
        time.sleep(delay_s)
        return orig(*a, **kw)

    s.node.executor.execute = slow


class TestHTTPAdmission:
    def test_normal_query_unaffected(self, srv):
        r = _post(srv.uri, "/index/i/query",
                  {"query": "Count(Row(f=1))"})
        assert r["results"] == [1]
        dbg = _get(srv.uri, "/debug/admission")
        assert dbg["classes"]["query"]["admitted"] >= 1
        assert dbg["classes"]["query"]["cap"] == 2
        from pilosa_tpu.server.handler import Handler

        assert dbg["acceptThreads"]["max"] == \
            srv.admission.total_capacity() + Handler.ACCEPT_HEADROOM

    def test_overload_sheds_with_retry_after(self, srv):
        _slow_executor(srv, 0.15)
        n = 12
        bar = threading.Barrier(n)
        ok, shed, retry_after = [], [], []

        def fire():
            bar.wait()
            try:
                _post(srv.uri, "/index/i/query",
                      {"query": "Count(Row(f=1))"})
                ok.append(1)
            except urllib.error.HTTPError as e:
                assert e.code in (429, 503), e.code
                shed.append(e.code)
                retry_after.append(e.headers.get("Retry-After"))

        ts = [threading.Thread(target=fire) for _ in range(n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(15)
        # cap 2 + queue 4 admit 6; the rest shed newest-first
        assert len(ok) >= 6
        assert len(shed) >= 1
        assert all(ra is not None and int(ra) >= 1
                   for ra in retry_after)
        dbg = _get(srv.uri, "/debug/admission")
        assert dbg["classes"]["query"]["shed"] >= 1
        # shed outcomes are visible in the flight recorder
        recs = _get(srv.uri, "/debug/queries")["recent"]
        assert any(r.get("outcome") == "shed" for r in recs)

    def test_expired_deadline_sheds_503_with_outcome(self, srv):
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(srv.uri, "/index/i/query",
                  {"query": "Count(Row(f=1))"},
                  headers={"X-Pilosa-Deadline": "0"})
        assert e.value.code == 503
        assert b"expired" in e.value.read()
        recs = _get(srv.uri, "/debug/queries")["recent"]
        assert any(r.get("outcome") == "expired" for r in recs)

    def test_malformed_deadline_400(self, srv):
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(srv.uri, "/index/i/query",
                  {"query": "Count(Row(f=1))"},
                  headers={"X-Pilosa-Deadline": "soon"})
        assert e.value.code == 400

    def test_deadline_expiring_mid_execution_503_no_dispatch(self, srv):
        _slow_executor(srv, 0.2)  # sleeps before the translate check
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(srv.uri, "/index/i/query",
                  {"query": "Count(Row(f=1))"},
                  headers={"X-Pilosa-Deadline": "0.05"})
        assert e.value.code == 503
        recs = _get(srv.uri, "/debug/queries")["recent"]
        expired = [r for r in recs if r.get("outcome") == "expired"
                   and r.get("pql")]
        assert expired
        assert all(r["deviceLaunches"] == 0 for r in expired)
        dbg = _get(srv.uri, "/debug/admission")
        assert dbg["classes"]["query"]["expired"] >= 1

    def test_default_deadline_applies_without_header(self, srv):
        srv.admission.default_deadline = 0.05
        _slow_executor(srv, 0.2)
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(srv.uri, "/index/i/query",
                      {"query": "Count(Row(f=1))"})
            assert e.value.code == 503
        finally:
            srv.admission.default_deadline = 0.0

    def test_internal_saturation_leaves_query_throughput_intact(self, srv):
        """Satellite regression: flood the internal class; user
        queries must keep flowing at full speed (class isolation)."""
        orig = srv.node.receive_message

        def slow_receive(msg):
            time.sleep(0.05)
            return orig(msg)

        srv.node.receive_message = slow_receive
        stop = threading.Event()

        def flood():
            while not stop.is_set():
                try:
                    _post(srv.uri, "/internal/cluster/message",
                          {"type": "attr-blocks", "index": "i",
                           "field": None}, timeout=5)
                except Exception:  # noqa: BLE001 — shed responses
                    pass

        flooders = [threading.Thread(target=flood, daemon=True)
                    for _ in range(8)]
        for t in flooders:
            t.start()
        try:
            time.sleep(0.3)  # saturation established
            lat = []
            for _ in range(20):
                t0 = time.perf_counter()
                r = _post(srv.uri, "/index/i/query",
                          {"query": "Count(Row(f=1))"})
                lat.append(time.perf_counter() - t0)
                assert r["results"] == [1]
        finally:
            stop.set()
            for t in flooders:
                t.join(5)
            srv.node.receive_message = orig
        assert max(lat) < 1.0, lat  # queries never queued behind internal
        dbg = _get(srv.uri, "/debug/admission")
        assert (dbg["classes"]["internal"]["shed"]
                + dbg["classes"]["internal"]["expired"]) > 0
        assert dbg["classes"]["query"]["shed"] == 0

    def test_accept_thread_cap_fast_503(self, srv):
        """Satellite: a connection flood degrades to fast 503s instead
        of unbounded handler threads."""
        base = srv.handler._threads_active
        old_max = srv.handler.max_threads
        srv.handler.max_threads = base + 3
        socks = []
        try:
            # saturate the cap DETERMINISTICALLY: a handler thread
            # lingering from an earlier request can be counted in
            # ``base`` and exit before the probe, leaving spare
            # capacity — keep opening idle connections (each holds a
            # thread; refused extras cost nothing) until the active
            # count actually reaches the cap, instead of assuming
            # exactly 3 + a fixed sleep suffices (flaked under
            # full-suite load)
            deadline = time.time() + 5.0
            while (srv.handler._threads_active < srv.handler.max_threads
                   and time.time() < deadline and len(socks) < 12):
                socks.append(socket.create_connection(
                    (srv.handler.host, srv.handler.port), timeout=5))
                time.sleep(0.1)
            assert (srv.handler._threads_active
                    >= srv.handler.max_threads), "cap never saturated"
            t0 = time.perf_counter()
            with pytest.raises(urllib.error.HTTPError) as e:
                _get(srv.uri, "/status", timeout=5)
            assert e.value.code == 503
            assert e.value.headers.get("Retry-After") == "1"
            assert time.perf_counter() - t0 < 2.0  # fast, not hanging
        finally:
            for s in socks:
                s.close()
            srv.handler.max_threads = old_max
        time.sleep(0.3)  # flood threads drain
        assert _get(srv.uri, "/status")["state"] == "NORMAL"

    def test_remote_shed_maps_to_503_with_retry_after(self, srv):
        """A sub-request shed by a peer's gate (ShedByPeerError after
        client retry exhaustion) surfaces as 503 + Retry-After, not a
        masked 500."""
        from pilosa_tpu.parallel.cluster import ShedByPeerError

        orig = srv.node.executor.execute

        def shed(*a, **kw):
            raise ShedByPeerError("shed by peer: http://peer: busy",
                                  503)

        srv.node.executor.execute = shed
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(srv.uri, "/index/i/query",
                      {"query": "Count(Row(f=1))"})
            assert e.value.code == 503
            assert e.value.headers.get("Retry-After") is not None
        finally:
            srv.node.executor.execute = orig

    def test_ingest_route_counts_against_ingest_class(self, srv):
        _post(srv.uri, "/index/i/field/f/import",
              {"rowIDs": [2], "columnIDs": [5]})
        dbg = _get(srv.uri, "/debug/admission")
        assert dbg["classes"]["ingest"]["admitted"] >= 1

    def test_admission_disabled_server(self, tmp_path):
        s = Server(str(tmp_path / "noadm"), admission_enabled=False)
        s.open()
        try:
            _post(s.uri, "/index/i")
            _post(s.uri, "/index/i/field/f")
            r = _post(s.uri, "/index/i/query", {"query": "Set(1, f=1)"})
            assert r["results"] == [True]
            assert s.handler.max_threads is None
            dbg = _get(s.uri, "/debug/admission")
            assert dbg["enabled"] is False
        finally:
            s.close()


# ---------------------------------------------------------------------------
# open-loop overload (tools/loadgen.py) — the acceptance run
# ---------------------------------------------------------------------------


class TestOverloadAcceptance:
    def test_2x_capacity_sheds_and_p99_bounded(self, tmp_path):
        """Open-loop load at ~2x capacity: overflow sheds with 429/503
        + Retry-After, goodput holds, p99 of ADMITTED queries stays
        within the queue-depth bound, and zero deadline-expired
        queries reach device dispatch."""
        from tools import loadgen

        s = Server(str(tmp_path / "ov"),
                   admission_query_cap=2, admission_query_queue=6,
                   observe_recent=1024)
        s.open()
        try:
            _post(s.uri, "/index/i")
            _post(s.uri, "/index/i/field/f")
            _post(s.uri, "/index/i/query", {"query": "Set(1, f=1)"})
            _slow_executor(s, 0.02)  # capacity ~= cap/0.02 = 100 qps
            # ~2x capacity, scaled to what a shared CI host can
            # schedule without the client-side thread churn itself
            # distorting latency.  A loaded host can fail to sustain
            # the open-loop schedule (late arrivals close the loop and
            # void the measurement) — retry, then gate the latency
            # pins on the generator having kept pace.
            for _ in range(3):
                report = loadgen.run_load(
                    s.uri, "i", qps=160, seconds=1.25,
                    query="Count(Row(f=1))",
                    deadline_s=(1.0, 2.0))
                paced = report["late"] <= report["sent"] * 0.2
                if paced:
                    break
            assert report["errors"] == 0, report
            # goodput holds under overload (floor sized for a loaded
            # CI host at ~1/4 of nominal capacity)
            assert report["ok"] >= 20, report
            if paced:
                assert report["shed"] >= 15, report
                assert report["retry_after_seen"] >= 1, report
                # queue bound: depth 6 drain at 2-wide 20ms service
                # is ~60ms wait + service; 1s absorbs host noise
                # while still catching unbounded-queueing latency
                # collapse (seconds)
                assert report["p99_ms"] < 1000.0, report
            # expired work never dispatches: every record that expired
            # BEFORE reaching execution (shed at the gate, or killed
            # by the translate check) shows zero device launches (the
            # dispatch-count hook feeds deviceLaunches).  A query that
            # legitimately started and expired mid-flight may carry
            # pre-expiry launches; the boundary checks stop it at the
            # next stage — pinned deterministically by
            # TestExecutorDeadline.
            dbg = _get(s.uri, "/debug/queries?sort=start")
            records = dbg["recent"] + dbg["active"]
            assert any(r["outcome"] == "shed" for r in records)
            for r in records:
                if r["outcome"] == "expired" and not any(
                        s_["name"].startswith(("execute.", "map"))
                        for s_ in r["stages"]):
                    assert r["deviceLaunches"] == 0, r
        finally:
            s.close()


# ---------------------------------------------------------------------------
# client retry path
# ---------------------------------------------------------------------------


class _ScriptedHTTP:
    """One-shot HTTP server answering POSTs from a script of
    (status, headers, body) tuples; records request headers."""

    def __init__(self):
        self.script: list[tuple[int, dict, bytes]] = []
        self.seen: list[dict] = []
        outer = self

        class H(BaseHTTPRequestHandler):
            def do_POST(self):
                length = int(self.headers.get("Content-Length") or 0)
                self.rfile.read(length)
                outer.seen.append({k: v for k, v in self.headers.items()})
                status, headers, body = (outer.script.pop(0)
                                         if outer.script
                                         else (200, {}, b"{}"))
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in headers.items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, fmt, *args):
                pass

        self.httpd = HTTPServer(("127.0.0.1", 0), H)
        self.uri = f"http://127.0.0.1:{self.httpd.server_address[1]}"
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


@pytest.fixture
def scripted():
    s = _ScriptedHTTP()
    yield s
    s.close()


class TestClientRetry:
    def test_deadline_and_class_headers_sent(self, scripted):
        client = InternalClient(timeout=7.0)
        with rpc_class("internal"):
            client.send_message(scripted.uri, {"type": "x"})
        hdrs = scripted.seen[0]
        assert hdrs.get("X-Pilosa-Class") == "internal"
        assert 0 < float(hdrs["X-Pilosa-Deadline"]) <= 7.0
        client.close()

    def test_retry_after_honored_with_cap_and_jitter(self, scripted):
        scripted.script = [
            (429, {"Retry-After": "5"}, b'{"error":"shed"}'),
            (200, {}, b'{"ok": true}'),
        ]
        client = InternalClient(timeout=30.0)
        sleeps: list[float] = []
        client._sleep = sleeps.append
        resp = client.send_message(scripted.uri, {"type": "x"})
        assert resp == {"ok": True}
        assert len(sleeps) == 1
        # Retry-After 5 capped at 2.0s, jittered up to +25%
        assert 2.0 <= sleeps[0] <= 2.5 + 1e-9, sleeps
        client.close()

    def test_no_retry_without_retry_after(self, scripted):
        from pilosa_tpu.server.client import ClientError

        scripted.script = [(503, {}, b'{"error":"down"}')]
        client = InternalClient()
        client._sleep = lambda s: pytest.fail("must not sleep")
        with pytest.raises(ClientError) as e:
            client.send_message(scripted.uri, {"type": "x"})
        assert e.value.status == 503
        assert len(scripted.seen) == 1  # single attempt
        client.close()

    def test_retry_stops_when_caller_deadline_spent(self, scripted):
        from pilosa_tpu.parallel.cluster import ShedByPeerError

        scripted.script = [(429, {"Retry-After": "1"},
                            b'{"error":"shed"}')] * 5
        client = InternalClient()
        sleeps: list[float] = []
        client._sleep = sleeps.append
        with deadline_mod.scope(Deadline(0.5)):
            with pytest.raises(ShedByPeerError) as e:
                client.send_message(scripted.uri, {"type": "x"})
        assert e.value.status == 429
        assert sleeps == []  # 1s delay > 0.5s budget: no blind sleep
        client.close()

    def test_expired_caller_deadline_never_sends(self, scripted):
        client = InternalClient()
        with deadline_mod.scope(Deadline(-1.0)):
            with pytest.raises(DeadlineExceededError):
                client.send_message(scripted.uri, {"type": "x"})
        assert scripted.seen == []
        client.close()

    def test_bounded_retry_attempts(self, scripted):
        """Exhausted shed retries surface as ShedByPeerError — a
        TransportError subclass, so best-effort fan-outs (broadcast,
        anti-entropy, replica failover) skip the overloaded peer
        instead of aborting, while membership reads it as proof of
        life."""
        from pilosa_tpu.parallel.cluster import (
            ShedByPeerError,
            TransportError,
        )

        scripted.script = [(429, {"Retry-After": "0.01"},
                            b'{"error":"shed"}')] * 10
        client = InternalClient(timeout=30.0)
        sleeps: list[float] = []
        client._sleep = sleeps.append
        with pytest.raises(ShedByPeerError) as e:
            client.send_message(scripted.uri, {"type": "x"})
        assert isinstance(e.value, TransportError)
        assert e.value.status == 429
        assert len(sleeps) == client.MAX_SHED_RETRIES
        assert len(scripted.seen) == 1 + client.MAX_SHED_RETRIES
        client.close()


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------


class TestAdmissionConfig:
    def test_defaults(self):
        cfg = Config()
        assert cfg.admission.enabled is True
        assert cfg.admission.query_cap == 32
        assert cfg.admission.default_deadline == 0.0

    def test_toml_env_precedence(self, tmp_path):
        p = tmp_path / "c.toml"
        p.write_text("[admission]\nquery-cap = 5\n"
                     "default-deadline = 1.5\ninternal-queue = 9\n")
        cfg = Config.load(str(p), env={})
        assert cfg.admission.query_cap == 5
        assert cfg.admission.default_deadline == 1.5
        assert cfg.admission.internal_queue == 9
        cfg2 = Config.load(str(p), env={
            "PILOSA_TPU_ADMISSION_QUERY_CAP": "7",
            "PILOSA_TPU_ADMISSION_ENABLED": "false",
        })
        assert cfg2.admission.query_cap == 7
        assert cfg2.admission.enabled is False

    def test_to_toml_roundtrip(self, tmp_path):
        cfg = Config()
        cfg.admission.ingest_cap = 3
        text = cfg.to_toml()
        assert "[admission]" in text
        p = tmp_path / "rt.toml"
        p.write_text(text)
        back = Config.load(str(p), env={})
        assert back.admission.ingest_cap == 3
        assert back.admission == cfg.admission

    def test_server_flags_wire_admission(self, tmp_path):
        """The cmd.py server flags land on cfg.admission."""
        import pilosa_tpu.cmd as cmd

        captured = {}

        def fake_run(cfg, **kw):
            captured["cfg"] = cfg
            return 0

        orig = cmd.run_server
        cmd.run_server = fake_run
        try:
            cmd.main(["server", "-d", str(tmp_path / "d"),
                      "--admission-query-cap", "9",
                      "--admission-internal-queue", "17",
                      "--admission-default-deadline", "2.5"])
        finally:
            cmd.run_server = orig
        adm = captured["cfg"].admission
        assert adm.query_cap == 9
        assert adm.internal_queue == 17
        assert adm.default_deadline == 2.5
