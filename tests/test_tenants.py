"""Per-tenant isolation (the [tenants] round, serve/tenant.py):
weighted-fair admission inside each priority class, result-cache soft
budgets, residency tier quotas, end-to-end identity threading, the
``admission.acquire`` failpoint, quota-accounting balance under chaos,
and THE abusive-tenant acceptance run — one tenant flooding at 10× its
quota while a victim's p99 and cache hit rate hold near its solo
baseline, every result bit-exact.  Plus the default-config inert pin:
with no [tenants] table, behavior is byte-identical to pre-tenant
code."""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from pilosa_tpu import faultinject, stats as _stats
from pilosa_tpu.serve import tenant as _tenant
from pilosa_tpu.serve.admission import AdmissionController, ShedError
from pilosa_tpu.shardwidth import SHARD_WIDTH


def _enable(quotas=None, **kw):
    kw.setdefault("enabled", True)
    return _tenant.configure(quotas=quotas, **kw)


# --------------------------------------------------------------------
# policy / identity unit semantics
# --------------------------------------------------------------------


class TestTenantPolicy:
    def test_disabled_by_default(self):
        assert _tenant.policy() is None
        assert not _tenant.enabled()

    def test_quota_for_default_tier(self):
        _enable(default_share=2, default_queue=5,
                quotas={"gold": {"share": 9, "queue": 44}})
        cfg = _tenant.config()
        assert cfg.quota_for("gold").share == 9
        assert cfg.quota_for("gold").queue == 44
        # unknown tenants ride the default tier
        assert cfg.quota_for("nobody").share == 2
        assert cfg.quota_for("nobody").queue == 5

    def test_parse_quota_spec(self):
        q = _tenant.parse_quota_spec("gold:16:64:0.5:0.7,free:2")
        assert q["gold"].share == 16 and q["gold"].queue == 64
        assert q["gold"].cache_share == 0.5
        assert q["gold"].residency_share == 0.7
        assert q["free"].share == 2  # the rest default
        with pytest.raises(ValueError):
            _tenant.parse_quota_spec("noshare")
        with pytest.raises(ValueError):
            _tenant.configure(quotas={"x": {"bogus": 1}})

    def test_clean_and_resolve(self):
        assert _tenant.clean(None) is None
        assert _tenant.clean("  ") is None
        assert _tenant.clean(" bob ") == "bob"
        assert len(_tenant.clean("x" * 500)) == _tenant.MAX_TENANT_LEN
        assert _tenant.resolve(None) == _tenant.DEFAULT_TENANT
        assert _tenant.resolve("a") == "a"

    def test_retain_release_baseline(self):
        _tenant.retain()
        _enable(quotas={"t": {"share": 3}})
        assert _tenant.enabled()
        _tenant.release()  # last release restores the pre-retain state
        assert not _tenant.enabled()
        assert _tenant.config().quotas == {}

    def test_individuation_bound(self, monkeypatch):
        """Rotating arbitrary unconfigured labels cannot mint
        unbounded default-tier quotas: past MAX_TRACKED_TENANTS, new
        labels collapse into the shared default tier (configured and
        already-individuated labels never collapse) — bounding both
        the rotation attack and per-tenant state growth."""
        monkeypatch.setattr(_tenant, "MAX_TRACKED_TENANTS", 3)
        _enable(quotas={"gold": {"share": 4}})
        assert _tenant.resolve("a1") == "a1"
        assert _tenant.resolve("a2") == "a2"
        assert _tenant.resolve("a3") == "a3"
        # bound hit: a NEW label shares the default tier...
        assert _tenant.resolve("a4") == _tenant.DEFAULT_TENANT
        # ...individuated and configured labels keep their identity
        assert _tenant.resolve("a2") == "a2"
        assert _tenant.resolve("gold") == "gold"
        assert len(_tenant.config().seen) == 3
        # disabled: no individuation at all (the pre-tenant path)
        _tenant.configure(enabled=False)
        assert _tenant.resolve("a9") == "a9"

    def test_scope_is_reentrant(self):
        assert _tenant.current() is None
        with _tenant.scope("a"):
            assert _tenant.current() == "a"
            with _tenant.scope("b"):
                assert _tenant.current() == "b"
            assert _tenant.current() == "a"
        assert _tenant.current() is None


# --------------------------------------------------------------------
# admission: quotas, DRR, shed reasons
# --------------------------------------------------------------------


class TestAdmissionTenants:
    def test_tenant_concurrency_capped_inside_class(self):
        _enable(quotas={"t": {"share": 2, "queue": 0}})
        c = AdmissionController(query_cap=8, query_queue=32,
                                stats=_stats.MemStatsClient())
        t1 = c.acquire("query", tenant="t")
        t2 = c.acquire("query", tenant="t")
        # the class has 6 free slots, but the TENANT is at its share
        # and its queue depth is 0 -> tenant-queue-full, 429, tenant id
        with pytest.raises(ShedError) as e:
            c.acquire("query", tenant="t")
        assert e.value.reason == "tenant-queue-full"
        assert e.value.status == 429
        assert e.value.tenant == "t"
        # another tenant admits straight through
        t3 = c.acquire("query", tenant="other")
        for t in (t1, t2, t3):
            t.release()
        # released clean: per-tenant in-flight balances to zero
        for d in c.tenants_debug().values():
            assert d["inFlight"] == 0

    def test_unknown_tenant_rides_default_tier(self):
        _enable(default_share=1, default_queue=0,
                quotas={"gold": {"share": 4, "queue": 8}})
        c = AdmissionController(query_cap=8, query_queue=32,
                                stats=_stats.MemStatsClient())
        t1 = c.acquire("query", tenant="anon1")
        # anon1 is at the default tier's share=1; a second concurrent
        # request from the SAME unknown tenant sheds...
        with pytest.raises(ShedError) as e:
            c.acquire("query", tenant="anon1")
        assert e.value.reason == "tenant-queue-full"
        # ...while a DIFFERENT unknown tenant has its own default tier
        t2 = c.acquire("query", tenant="anon2")
        # and an anonymous request (no id) is the "default" tenant
        t3 = c.acquire("query")
        assert t3.tenant == _tenant.DEFAULT_TENANT
        for t in (t1, t2, t3):
            t.release()

    def test_wait_ewma_decays_on_fast_path_admits(self):
        """A congestion episode must not pin the deadline-unmeetable
        floor forever: zero-wait admits decay the per-tenant
        queue-wait EWMA (sheds never sample it, so without the decay
        one bad burst would 503 every later deadline-carrying request
        whenever the class is momentarily at cap)."""
        _enable(quotas={"t": {"share": 2, "queue": 8}})
        c = AdmissionController(query_cap=4, query_queue=32,
                                stats=_stats.MemStatsClient())
        c.acquire("query", tenant="t").release()
        ts = c._gates["query"].tenants["t"]
        ts.wait_ewma_s = 3.0  # a past burst left the floor high
        for _ in range(30):
            c.acquire("query", tenant="t").release()
        assert ts.wait_ewma_s < 0.01

    def test_class_queue_full_distinct_from_tenant_queue_full(self):
        _enable(quotas={"t": {"share": 1, "queue": 100}})
        c = AdmissionController(query_cap=1, query_queue=2,
                                stats=_stats.MemStatsClient())
        hold = c.acquire("query", tenant="t")
        waiters = []
        for _ in range(2):
            th = threading.Thread(
                target=lambda: waiters.append(
                    c.acquire("query", tenant="t")))
            th.start()
        for _ in range(100):
            if c.debug()["classes"]["query"]["waiting"] == 2:
                break
            time.sleep(0.01)
        # tenant queue has room (100) but the CLASS depth (2) is full:
        # the arriving request sheds with the class-wide reason — "the
        # server is drowning", not "you are over quota"
        with pytest.raises(ShedError) as e:
            c.acquire("query", tenant="someone-else")
        assert e.value.reason == "queue-full"
        hold.release()
        for _ in range(200):
            if len(waiters) == 2:
                break
            time.sleep(0.01)
        for t in waiters:
            t.release()

    def test_deficit_round_robin_honors_weights(self):
        """One slot frees at a time (the production pattern) and two
        tenants flood equally: admissions must divide ~3:1 by share,
        not alternate — the deficit carry is what separates DRR from
        plain round robin."""
        _enable(quotas={"a": {"share": 1, "queue": 100},
                        "b": {"share": 3, "queue": 100}})
        c = AdmissionController(query_cap=1, query_queue=256,
                                stats=_stats.MemStatsClient())
        hold = c.acquire("query", tenant="a")
        order: list[str] = []
        lock = threading.Lock()
        done = []

        def waiter(name):
            t = c.acquire("query", tenant=name)
            with lock:
                order.append(name)
            # release AFTER recording: each release frees exactly one
            # slot, driving the wake loop one admission at a time
            t.release()
            done.append(1)

        threads = []
        for i in range(16):
            for name in ("a", "b"):
                th = threading.Thread(target=waiter, args=(name,))
                th.start()
                threads.append(th)
        # wait until all 32 are queued, then open the floodgate
        for _ in range(500):
            if c.debug()["classes"]["query"]["waiting"] == 32:
                break
            time.sleep(0.01)
        assert c.debug()["classes"]["query"]["waiting"] == 32
        hold.release()
        for th in threads:
            th.join(timeout=30)
        assert len(order) == 32
        # share 3 vs 1: within any early window b should admit ~3x a
        head = order[:16]
        assert 10 <= head.count("b") <= 14, head
        # nothing leaked
        d = c.debug()["classes"]["query"]
        assert d["inFlight"] == 0 and d["waiting"] == 0
        for td in c.tenants_debug().values():
            assert td["inFlight"] == 0 and td["waiting"] == 0

    def test_tenant_stats_and_debug_shapes(self):
        _enable(quotas={"t": {"share": 2, "queue": 4}})
        c = AdmissionController(stats=_stats.MemStatsClient())
        c.acquire("query", tenant="t").release()
        d = c.debug()
        assert d["tenantsEnabled"] is True
        td = d["classes"]["query"]["tenants"]["t"]
        assert td["share"] == 2 and td["admitted"] == 1
        agg = c.tenants_debug()["t"]
        assert agg["admitted"] == 1 and agg["shed"] == 0
        # the tenant.* gauge family publishes (zeros included)
        mem = _stats.MemStatsClient()
        _tenant.publish_gauges(mem, c)
        snap = mem.snapshot()
        assert snap["tenant.enabled"] == 1
        assert snap["tenant.admitted"] == 1

    def test_disabled_config_keeps_gate_byte_identical(self):
        """The default-config pin: with [tenants] off, the tenant
        structures are never touched — same admit/shed decisions, no
        tenant state, no tenants key on /debug/admission."""
        c = AdmissionController(query_cap=1, query_queue=0,
                                stats=_stats.MemStatsClient())
        t1 = c.acquire("query", tenant="whoever")
        assert t1.tenant is None  # not even resolved
        with pytest.raises(ShedError) as e:
            c.acquire("query", tenant="whoever")
        assert e.value.reason == "queue-full"  # the class-only reason
        assert e.value.tenant is None
        t1.release()
        d = c.debug()
        assert "tenantsEnabled" not in d
        assert "tenants" not in d["classes"]["query"]
        assert c.tenants_debug() == {}
        for g in c._gates.values():
            assert not g.tenants and not g.rr and g.waiting_total == 0


# --------------------------------------------------------------------
# admission.acquire failpoint
# --------------------------------------------------------------------


class TestAdmissionFailpoint:
    def teardown_method(self):
        faultinject.disarm()

    def test_injected_shed(self):
        from pilosa_tpu.parallel.cluster import ShedByPeerError

        c = AdmissionController(stats=_stats.MemStatsClient())
        faultinject.arm("admission.acquire=error(shed)*2")
        with pytest.raises(ShedByPeerError):
            c.acquire("query")
        with pytest.raises(ShedByPeerError):
            c.acquire("query")
        # *2 exhausted: the gate serves normally again, nothing leaked
        c.acquire("query").release()
        assert c.debug()["classes"]["query"]["inFlight"] == 0

    def test_injected_delay(self):
        c = AdmissionController(stats=_stats.MemStatsClient())
        faultinject.arm("admission.acquire=delay(40)")
        t0 = time.perf_counter()
        c.acquire("query").release()
        assert time.perf_counter() - t0 >= 0.04
        faultinject.disarm()
        t0 = time.perf_counter()
        c.acquire("query").release()
        assert time.perf_counter() - t0 < 0.04  # zero-cost disarmed


# --------------------------------------------------------------------
# result cache: per-tenant soft budgets
# --------------------------------------------------------------------


class TestResultCacheTenants:
    def test_over_budget_tenant_evicts_its_own_entries(self):
        from pilosa_tpu.runtime import resultcache

        _enable(quotas={"victim": {"share": 4, "cache_share": 0.5},
                        "abuser": {"share": 4, "cache_share": 0.25}})
        rc = resultcache.reset(budget_bytes=8000, max_entry_bytes=4000)
        # victim warms 4 entries (~1KB each incl. overhead)
        for i in range(4):
            assert rc.put(("v", i), 1, b"x" * 700, 700,
                          tenant="victim")
        # abuser churns distinct keys well past its 2000-byte soft
        # budget: ITS oldest entries must evict; the victim's warm
        # head survives even though it is older in global LRU order
        for i in range(20):
            rc.put(("a", i), 1, b"y" * 700, 700, tenant="abuser")
        for i in range(4):
            hit, val = rc.get(("v", i), 1, tenant="victim")
            assert hit, f"victim entry {i} was evicted by abuser churn"
        ts = rc.tenant_stats()
        assert ts["abuser"]["evictions"] >= 15
        assert ts["victim"]["evictions"] == 0
        # soft semantics: the abuser may hold global HEADROOM beyond
        # its soft budget, but never a byte of the victim's share
        assert ts["victim"]["bytes"] == 4 * (700 + 256)
        assert ts["abuser"]["bytes"] + ts["victim"]["bytes"] \
            <= rc.budget
        assert rc.stats_dict()["tenantPrefEvictions"] >= 15

    def test_tenant_hit_miss_counters(self):
        from pilosa_tpu.runtime import resultcache

        _enable()
        rc = resultcache.reset()
        rc.get("k", 1, tenant="t")          # miss
        rc.put("k", 1, 42, 32, tenant="t")  # fill
        hit, v = rc.get("k", 1, tenant="t")
        assert hit and v == 42
        ts = rc.tenant_stats()["t"]
        assert ts["hits"] == 1 and ts["misses"] == 1 and ts["fills"] == 1

    def test_thread_scope_attribution(self):
        """Fills attribute through the executor's thread-local scope
        when no explicit tenant rides the call — the mechanism every
        fill site (Count/Row/TopN/GroupBy/coalescer) relies on."""
        from pilosa_tpu.runtime import resultcache

        _enable()
        rc = resultcache.reset()
        with _tenant.scope("scoped"):
            rc.put("k", 1, 42, 32)
        assert rc.tenant_stats()["scoped"]["bytes"] > 0

    def test_disabled_tenants_keep_cache_untouched(self):
        from pilosa_tpu.runtime import resultcache

        rc = resultcache.reset()
        rc.put("k", 1, 42, 32)
        hit, _ = rc.get("k", 1)
        assert hit
        assert rc.tenant_stats() == {}
        assert rc._tenant_bytes == {} and rc._tenant_lru == {}

    def test_disabled_explicit_tenant_not_accounted(self):
        """With [tenants] OFF (the default config), an explicit
        tenant= on put/get (the coalescer's fill path) must not mint
        per-label accounting state — otherwise unauthenticated
        traffic rotating X-Pilosa-Tenant labels grows the per-tenant
        dicts without bound, and the individuation bound only applies
        while isolation is enabled."""
        from pilosa_tpu.runtime import resultcache

        assert _tenant.policy() is None
        rc = resultcache.reset(budget_bytes=64 << 10)
        for i in range(50):
            rc.put(("k", i), 1, b"z" * 64, 64, tenant=f"rot{i}")
            rc.get(("k", i), 1, tenant=f"rot{i}")
        assert rc.tenant_stats() == {}
        with rc._lock:
            assert rc._tenant_bytes == {}
            assert rc._tenant_counters == {}

    def test_accounting_balances(self):
        from pilosa_tpu.runtime import resultcache

        _enable()
        rc = resultcache.reset(budget_bytes=64 << 10)
        for i in range(50):
            rc.put(("k", i), 1, b"z" * 256, 256,
                   tenant=f"t{i % 3}")
        for i in range(0, 50, 7):
            rc.get(("k", i), 2, tenant="t0")  # stamp moved: invalidate
        with rc._lock:
            per_tenant = dict(rc._tenant_bytes)
            real = {}
            for k, e in rc._entries.items():
                real[e.tenant] = real.get(e.tenant, 0) + e.nbytes
        assert {t: b for t, b in per_tenant.items() if b} == real
        assert sum(real.values()) == rc.bytes


# --------------------------------------------------------------------
# residency: per-tenant tier quotas
# --------------------------------------------------------------------


class TestResidencyTenants:
    def test_over_quota_tenant_demotes_its_own_stacks(self):
        from pilosa_tpu.runtime import residency

        _enable(quotas={"victim": {"share": 4, "residency_share": 0.6},
                        "abuser": {"share": 4,
                                   "residency_share": 0.25}})
        mgr = residency.reset(budget_bytes=10_000)
        vcache, acache = {}, {}
        with _tenant.scope("victim"):
            for i in range(3):
                vcache[i] = object()
                mgr.admit(vcache, i, 1500)
        with _tenant.scope("abuser"):
            # abuser's working set wants 6000 bytes against a
            # 2500-byte quota: its OWN oldest entries evict; the
            # victim's 4500 warm bytes stay resident
            for i in range(8):
                acache[i] = object()
                mgr.admit(acache, i, 750)
        assert len(vcache) == 3, "victim stacks were demoted"
        ts = mgr.tenant_stats()
        assert ts["abuser"]["hbmBytes"] <= ts["abuser"]["hbmQuota"]
        assert ts["abuser"]["pressure"] >= 4
        assert ts["victim"]["pressure"] == 0
        # accounting balances: per-tenant bytes sum to the total
        assert sum(d["hbmBytes"] for d in ts.values()) == mgr.total

    def test_anonymous_admit_inherits_owner(self):
        """A promotion worker (no tenant scope) re-admitting an entry
        keeps the original owner's attribution."""
        from pilosa_tpu.runtime import residency

        _enable()
        mgr = residency.reset(budget_bytes=10_000)
        cache = {}
        with _tenant.scope("owner"):
            cache["k"] = object()
            mgr.admit(cache, "k", 100)
        cache["k"] = object()
        mgr.admit(cache, "k", 100)  # anonymous re-admit
        assert mgr.tenant_stats()["owner"]["hbmBytes"] == 100

    def test_disabled_tenants_keep_residency_untouched(self):
        from pilosa_tpu.runtime import residency

        mgr = residency.reset(budget_bytes=10_000)
        cache = {}
        with _tenant.scope("t"):  # scope set but [tenants] OFF
            cache["k"] = object()
            mgr.admit(cache, "k", 100)
        assert mgr.tenant_stats() == {}
        assert "tenants" in mgr.stats()
        assert mgr.stats()["tenants"] == {}

    def test_host_tier_bytes_charged(self):
        from pilosa_tpu.runtime import residency

        _enable()
        residency.configure(host_budget_bytes=1 << 20)
        mgr = residency.reset(budget_bytes=10_000)
        cache = {}
        arr = np.arange(64, dtype=np.uint32)
        with _tenant.scope("t"):
            cache["k"] = object()
            mgr.admit(cache, "k", 100, token=1, host=arr,
                      promote=lambda: None)
        assert mgr.tenant_stats()["t"]["hostBytes"] == arr.nbytes


# --------------------------------------------------------------------
# identity threading: ExecOptions -> record, sub-query forwarding
# --------------------------------------------------------------------


class TestTenantThreading:
    def _seed(self, tmp_path, n=3):
        from pilosa_tpu.api import API
        from tests.test_cluster import make_cluster

        transport, nodes = make_cluster(tmp_path, n=n, replica_n=1)
        nodes[0].create_index("i")
        nodes[0].create_field("i", "f")
        api = API(nodes[0])
        cols = [s * SHARD_WIDTH + 5 for s in range(3 * n)]
        api.import_bits("i", "f", [1] * len(cols), cols)
        return transport, nodes, api, len(set(cols))

    def test_tenant_on_flight_record(self, tmp_path):
        transport, nodes, api, expect = self._seed(tmp_path, n=1)
        assert api.query("i", "Count(Row(f=1))",
                         tenant="alice")[0] == expect
        rec = nodes[0].executor.recorder.recent_records()[-1]
        assert rec.tenant == "alice"
        assert rec.to_dict()["tenant"] == "alice"
        # anonymous queries carry no tenant key (record stays small)
        api.query("i", "Count(Row(f=1))", cache=False)
        rec = nodes[0].executor.recorder.recent_records()[-1]
        assert rec.tenant is None and "tenant" not in rec.to_dict()
        for n_ in nodes:
            n_.holder.close()

    def test_tenant_forwarded_on_subqueries(self, tmp_path):
        """The origin's tenant id must ride every node-to-node
        sub-query (like ?nocache): the peers' ExecOptions — and
        therefore their admission/cache/residency accounting — charge
        the SAME tenant."""
        transport, nodes, api, expect = self._seed(tmp_path)
        seen: list[str | None] = []
        orig = type(transport).query_node

        def spy(self, node, index, pql, shards, **kw):
            seen.append(kw.get("tenant"))
            return orig(self, node, index, pql, shards, **kw)

        type(transport).query_node = spy
        try:
            assert api.query("i", "Count(Row(f=1))", cache=False,
                             tenant="alice")[0] == expect
        finally:
            type(transport).query_node = orig
        assert seen and all(t == "alice" for t in seen)
        # remote executions stamped their own records with the tenant
        remote_recs = [r for n_ in nodes[1:]
                       for r in n_.executor.recorder.recent_records()]
        assert any(r.tenant == "alice" for r in remote_recs)
        # and the default path forwards NO tenant (inert pin)
        seen.clear()
        type(transport).query_node = spy
        try:
            api.query("i", "Count(Row(f=1))", cache=False)
        finally:
            type(transport).query_node = orig
        assert seen and all(t is None for t in seen)
        for n_ in nodes:
            n_.holder.close()


# --------------------------------------------------------------------
# quota accounting balances to zero under chaos
# --------------------------------------------------------------------


class TestQuotaBalanceUnderChaos:
    def teardown_method(self):
        faultinject.disarm()

    def test_no_leaked_permits_or_phantom_bytes(self):
        """Concurrency/chaos leg: a mixed-tenant run with the
        admission.acquire and residency.promote failpoints armed must
        leave ZERO in-flight permits and per-tenant byte accounting
        that sums exactly to the managers' totals — injected sheds,
        delays and promotion failures may cost latency, never
        accounting."""
        from pilosa_tpu.parallel.cluster import ShedByPeerError
        from pilosa_tpu.runtime import residency, resultcache

        _enable(default_share=2, default_queue=8,
                quotas={"a": {"share": 2, "queue": 8,
                              "residency_share": 0.3},
                        "b": {"share": 3, "queue": 8,
                              "residency_share": 0.3}})
        ctrl = AdmissionController(query_cap=4, query_queue=64,
                                   stats=_stats.MemStatsClient())
        mgr = residency.reset(budget_bytes=50_000)
        rc = resultcache.reset(budget_bytes=64 << 10)
        caches: dict[str, dict] = {"a": {}, "b": {}, "c": {}}
        faultinject.arm("admission.acquire=delay(2)@5;"
                        "residency.promote=error@3")
        errors: list = []

        def client(name: str, n: int):
            for i in range(n):
                try:
                    tk = ctrl.acquire("query", tenant=name)
                except (ShedError, ShedByPeerError):
                    continue
                try:
                    with _tenant.scope(name):
                        caches[name][i % 20] = object()
                        mgr.admit(caches[name], i % 20,
                                  500 + 37 * (i % 7))
                        rc.put((name, i % 30), 1, i, 128)
                        rc.get((name, (i + 1) % 30), 1)
                finally:
                    tk.release()

        threads = [threading.Thread(target=client, args=(nm, 120))
                   for nm in ("a", "b", "c") for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        faultinject.disarm()
        # 1. no leaked admission permits, per tenant or per class
        d = ctrl.debug()
        for k, cd in d["classes"].items():
            assert cd["inFlight"] == 0, (k, cd)
            assert cd["waiting"] == 0, (k, cd)
            for name, td in cd.get("tenants", {}).items():
                assert td["inFlight"] == 0, (k, name, td)
        # 2. residency: per-tenant bytes sum exactly to the total
        with mgr._lock:
            per = dict(mgr._tenant_bytes)
            real: dict = {}
            for (_cid, _key), e in mgr._entries.items():
                real[e[5]] = real.get(e[5], 0) + e[2]
        assert {t: b for t, b in per.items() if b} == real
        assert sum(real.values()) == mgr.total
        # 3. result cache: per-tenant bytes sum exactly to the bytes
        with rc._lock:
            per = dict(rc._tenant_bytes)
            real = {}
            for k, e in rc._entries.items():
                real[e.tenant] = real.get(e.tenant, 0) + e.nbytes
        assert {t: b for t, b in per.items() if b} == real
        assert sum(real.values()) == rc.bytes


# --------------------------------------------------------------------
# HTTP surfaces + THE acceptance run
# --------------------------------------------------------------------


def _post_query(uri, index, pql, tenant=None, params="", timeout=10):
    req = urllib.request.Request(
        f"{uri}/index/{index}/query{params}",
        data=pql.encode(), method="POST")
    req.add_header("Content-Type", "text/plain")
    if tenant is not None:
        req.add_header("X-Pilosa-Tenant", tenant)
    t0 = time.perf_counter()
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        out = json.loads(resp.read())
    return out, time.perf_counter() - t0


def _get(uri, path):
    with urllib.request.urlopen(uri + path, timeout=10) as resp:
        return json.loads(resp.read())


class TestHTTPTenants:
    @pytest.fixture
    def srv(self, tmp_path):
        from pilosa_tpu.server.server import Server

        s = Server(str(tmp_path / "n0"),
                   tenants_enabled=True,
                   tenants_default_share=2,
                   tenants_default_queue=4,
                   tenants_quotas={
                       "gold": {"share": 8, "queue": 32,
                                "cache_share": 0.5},
                       "abuser": {"share": 1, "queue": 2,
                                  "cache_share": 0.1,
                                  "residency_share": 0.2},
                   })
        s.open()
        try:
            yield s
        finally:
            s.close()

    def _seed(self, srv):
        from pilosa_tpu.server.client import InternalClient

        c = InternalClient()
        c.create_index(srv.uri, "i")
        c.create_field(srv.uri, "i", "f")
        cols = list(range(0, 4 * SHARD_WIDTH, SHARD_WIDTH // 8))
        c.import_bits(srv.uri, "i", "f", [1] * len(cols), cols)
        c.close()
        return len(set(cols))

    def test_header_param_debug_and_metrics(self, srv):
        expect = self._seed(srv)
        out, _ = _post_query(srv.uri, "i", "Count(Row(f=1))",
                             tenant="gold")
        assert out["results"][0] == expect
        out, _ = _post_query(srv.uri, "i", "Count(Row(f=1))",
                             params="?tenant=toolbelt")
        assert out["results"][0] == expect
        # /debug/tenants: policy + per-tenant sections
        d = _get(srv.uri, "/debug/tenants")
        assert d["enabled"] is True
        assert d["quotas"]["gold"]["share"] == 8
        assert d["tenants"]["gold"]["admission"]["admitted"] >= 1
        assert "toolbelt" in d["tenants"]
        # /debug/admission: per-tenant breakdown inside the class
        a = _get(srv.uri, "/debug/admission")
        assert "gold" in a["classes"]["query"]["tenants"]
        # the query record carries the tenant
        q = _get(srv.uri, "/debug/queries")
        assert any(r.get("tenant") == "gold" for r in q["recent"])
        # tenant_* family renders on a live exposition
        import sys
        from os.path import dirname, join

        sys.path.insert(0, join(dirname(dirname(__file__)), "tools"))
        from tools.check_metrics import TENANT_FAMILIES, check_families

        with urllib.request.urlopen(srv.uri + "/metrics",
                                    timeout=10) as resp:
            text = resp.read().decode()
        counts = check_families(text, TENANT_FAMILIES)
        assert counts["tenant_"] >= 5

    def test_shed_body_carries_tenant_and_reason(self, srv):
        self._seed(srv)
        # hold the abuser's single slot (a slow cache fill keeps the
        # admission ticket held through execution), fill its queue(2),
        # then overflow it: the later requests shed tenant-queue-full
        # with the tenant id in the structured body
        faultinject.arm("resultcache.fill=delay(500)")
        try:
            results: list = []
            lock = threading.Lock()

            def bg(i):
                try:
                    out = _post_query(
                        srv.uri, "i", f"Count(Row(f={i}))",
                        tenant="abuser")[0]
                    with lock:
                        results.append(out)
                except urllib.error.HTTPError as e:
                    body = {}
                    try:
                        body = json.loads(e.read() or b"{}")
                    except (OSError, ValueError):
                        pass
                    with lock:
                        results.append((e.code, body))

            threads = [threading.Thread(target=bg, args=(i,))
                       for i in range(6)]
            for t in threads:
                t.start()
                time.sleep(0.03)
            for t in threads:
                t.join(timeout=30)
        finally:
            faultinject.disarm()
        sheds = [r for r in results
                 if isinstance(r, tuple) and r[0] == 429]
        assert sheds, [type(r).__name__ for r in results]
        body = sheds[0][1]
        assert body["reason"] == "tenant-queue-full"
        assert body["tenant"] == "abuser"
        assert body["class"] == "query"

    def test_acceptance_abusive_tenant_isolation(self, srv):
        """THE pinned isolation run: the abuser floods at ~10x its
        quota while the victim runs its dashboard mix.  Held to what
        the tenant governor counts: the abuser is shed, no victim
        request is refused, shed or expired, the victim's result-cache
        hit rate stays >= 0.8x solo, and every victim result is
        bit-exact.  The victim's p99 against its solo baseline is
        printed, not asserted: a wall-clock ratio under a ten-thread
        flood on a shared CPU says what the machine was doing.
        (Victim = 'gold', share 8; abuser share 1, queue 2.)"""
        expect = self._seed(srv)
        vq = "Count(Row(f=1))"

        def victim_burst(n=60):
            lats, hits, vals = [], 0, []
            for _ in range(n):
                out, dt = _post_query(srv.uri, "i", vq, tenant="gold")
                lats.append(dt)
                vals.append(out["results"][0])
            return sorted(lats), vals

        # solo baseline (warm cache: the first query fills)
        _post_query(srv.uri, "i", vq, tenant="gold")
        base_cache = _get(srv.uri, "/debug/tenants")["tenants"].get(
            "gold", {}).get("cache") or {"hits": 0, "misses": 0}
        solo_lats, solo_vals = victim_burst()
        assert all(v == expect for v in solo_vals)
        mid_cache = _get(srv.uri, "/debug/tenants")["tenants"][
            "gold"]["cache"]
        solo_hits = mid_cache["hits"] - base_cache["hits"]
        solo_misses = mid_cache["misses"] - base_cache["misses"]
        solo_hit_rate = solo_hits / max(1, solo_hits + solo_misses)
        solo_p99 = solo_lats[int(0.99 * (len(solo_lats) - 1))]

        # abuser floods from 10 threads (10x its share of 1), each
        # churning DISTINCT uncacheable-by-reuse queries
        stop = threading.Event()
        abuser_sheds = [0]

        def abuser():
            i = 0
            while not stop.is_set():
                i += 1
                try:
                    _post_query(srv.uri, "i",
                                f"Count(Row(f={i % 40}))",
                                tenant="abuser", timeout=10)
                except urllib.error.HTTPError:
                    abuser_sheds[0] += 1
                except OSError:
                    pass

        flood = [threading.Thread(target=abuser) for _ in range(10)]
        for t in flood:
            t.start()
        try:
            time.sleep(0.3)  # let the flood establish
            abused_lats, abused_vals = victim_burst()
        finally:
            stop.set()
            for t in flood:
                t.join(timeout=30)
        # bit-exact under abuse
        assert all(v == expect for v in abused_vals)
        end_cache = _get(srv.uri, "/debug/tenants")["tenants"][
            "gold"]["cache"]
        ab_hits = end_cache["hits"] - mid_cache["hits"]
        ab_misses = end_cache["misses"] - mid_cache["misses"]
        ab_hit_rate = ab_hits / max(1, ab_hits + ab_misses)
        ab_p99 = abused_lats[int(0.99 * (len(abused_lats) - 1))]
        print(f"victim p99 under abuse / solo: {ab_p99 * 1e3:.2f} ms / "
              f"{solo_p99 * 1e3:.2f} ms = {ab_p99 / solo_p99:.2f}x "
              f"(host clock, not asserted)")
        assert ab_hit_rate >= 0.8 * solo_hit_rate, \
            (ab_hit_rate, solo_hit_rate)
        tenants = _get(srv.uri, "/debug/tenants")["tenants"]
        # the abuser actually got throttled (the flood was real), by
        # the governor's own count and as its clients saw it
        assert tenants["abuser"]["admission"]["shed"] > 0
        assert abuser_sheds[0] > 0
        # every victim request was answered (victim_burst raises on a
        # refusal) and the governor refused, shed or expired none
        gold = tenants["gold"]["admission"]
        assert (gold["shed"], gold["expired"]) == (0, 0), gold
        assert gold["admitted"] >= 2 * 60 + 1

    def test_loadgen_tenant_mix_report(self, srv):
        """tools/loadgen --tenant-mix against a live server: every
        tenant in the mix gets a goodput/p50/p99/shed section, the
        stamped X-Pilosa-Tenant identities show up server-side, and
        the abuser's flood lands in ITS shed column."""
        self._seed(srv)
        from tools import loadgen

        mix = loadgen.parse_tenant_mix("gold:3:query,abuser:9:query")
        # a slow first fill holds every same-key admission ticket
        # through the single-flight wait (the shed-body test's
        # technique): the abuser's 9/12 arrival share piles onto its
        # share-1/queue-2 quota while gold's share 8 absorbs its 3/12
        faultinject.arm("resultcache.fill=delay(200)*1")
        try:
            report = loadgen.run_load(
                srv.uri, index="i", query="Count(Row(f=1))",
                qps=200, seconds=1.5, pool=16, tenant_mix=mix)
        finally:
            faultinject.disarm()
        tn = report["tenants"]
        assert set(tn) == {"gold", "abuser"}
        for t in tn.values():
            for k in ("ok", "shed", "goodput_qps", "p50_ms", "p99_ms"):
                assert k in t
        assert tn["gold"]["ok"] > 0
        # both identities reached the server's per-tenant accounting
        d = _get(srv.uri, "/debug/tenants")["tenants"]
        assert d["gold"]["admission"]["admitted"] >= tn["gold"]["ok"]
        assert "abuser" in d
        # the 9:1 flood exceeds the abuser's share-1/queue-2 quota at
        # 200 qps: its own shed column shows it, gold's stays clean
        assert tn["abuser"]["shed"] > 0
        assert tn["gold"]["shed"] == 0

    def test_reopen_reapplies_tenant_config(self, tmp_path):
        """close() restores the process baseline (isolation off); a
        reopened server must RE-APPLY its configured quotas or it
        silently serves with isolation off — the [replication]
        reopen bug class.  Also pins that reopen actually SERVES:
        the handler rebuilds its closed listening socket on the same
        port and the holder reloads persisted indexes (previously a
        reopened server refused every connection, and would have
        answered from an empty holder)."""
        from pilosa_tpu.server.client import InternalClient
        from pilosa_tpu.server.server import Server

        s = Server(str(tmp_path / "n0"), tenants_enabled=True,
                   tenants_quotas={"gold": {"share": 7}})
        s.open()
        try:
            c = InternalClient()
            c.create_index(s.uri, "i")
            c.create_field(s.uri, "i", "f")
            c.import_bits(s.uri, "i", "f", [1], [5])
            c.close()
            assert _tenant.policy() is not None
            uri0 = s.uri
            s.close()
            assert _tenant.policy() is None  # baseline restored
            s.open()
            assert s.uri == uri0
            out, _ = _post_query(s.uri, "i", "Count(Row(f=1))",
                                 tenant="gold")
            assert out["results"][0] == 1  # data survived the cycle
            assert _tenant.policy() is not None
            assert _tenant.config().quota_for("gold").share == 7
            d = _get(s.uri, "/debug/tenants")
            assert d["enabled"] is True
            assert d["quotas"]["gold"]["share"] == 7
        finally:
            s.close()

    def test_default_config_has_no_tenant_surface(self, tmp_path):
        """Default config (no [tenants] table): the gate, cache and
        residency run their exact pre-tenant paths — nothing tenant-
        shaped accrues even when clients SEND the header."""
        from pilosa_tpu.server.server import Server

        s = Server(str(tmp_path / "plain"))
        s.open()
        try:
            from pilosa_tpu.server.client import InternalClient

            c = InternalClient()
            c.create_index(s.uri, "i")
            c.create_field(s.uri, "i", "f")
            c.import_bits(s.uri, "i", "f", [1], [5])
            c.close()
            out, _ = _post_query(s.uri, "i", "Count(Row(f=1))",
                                 tenant="ghost")
            assert out["results"][0] == 1
            d = _get(s.uri, "/debug/tenants")
            assert d["enabled"] is False
            a = _get(s.uri, "/debug/admission")
            assert "tenants" not in a["classes"]["query"]
            for g in s.admission._gates.values():
                assert not g.tenants
            from pilosa_tpu.runtime import residency, resultcache

            assert resultcache.cache().tenant_stats() == {}
            assert residency.manager().tenant_stats() == {}
            # the record still notes the tenant id (observability is
            # free); only ENFORCEMENT is off
            q = _get(s.uri, "/debug/queries")
            assert any(r.get("tenant") == "ghost"
                       for r in q["recent"])
        finally:
            s.close()
