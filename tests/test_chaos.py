"""Chaos-ready serving (the failure-handling round): failpoint
registry semantics, per-peer circuit breakers (state machine +
fast-fail latency pin + heartbeat healing), hedged replica reads,
partial-result degradation (?partial=1) with exact missing-shard
accounting, the structured replica-exhaustion error, the device-OOM
evict-and-retry, and a 3-node chaos soak asserting every response is
a correct result, an explicit error, or a correctly-accounted
partial — never silently wrong data."""

from __future__ import annotations

import threading
import time

import pytest

from pilosa_tpu import faultinject as fi
from pilosa_tpu.api import API
from pilosa_tpu.parallel.cluster import (
    BREAKER_CLOSED,
    BREAKER_HALF_OPEN,
    BREAKER_OPEN,
    CircuitBreaker,
    TransportError,
)
from pilosa_tpu.parallel.executor import (
    ExecOptions,
    ExecutionError,
    ShardsUnavailableError,
)
from pilosa_tpu.parallel.membership import heartbeat_round
from pilosa_tpu.shardwidth import SHARD_WIDTH

from tests.test_cluster import make_cluster


@pytest.fixture(autouse=True)
def _disarm():
    fi.disarm()
    yield
    fi.disarm()


# ------------------------------------------------------------ failpoints


class TestFailpoints:
    def test_spec_parses_and_validates(self):
        fi.arm("client.request.send=error(transport)*3;"
               "executor.map_shard=delay(5)@2")
        snap = fi.snapshot()
        assert snap["armed"]
        assert set(snap["points"]) == {"client.request.send",
                                       "executor.map_shard"}
        fi.disarm("client.request.send")
        assert set(fi.snapshot()["points"]) == {"executor.map_shard"}
        fi.disarm()
        assert not fi.snapshot()["armed"]
        assert fi.armed is False

    def test_unknown_name_and_bad_action_rejected(self):
        with pytest.raises(ValueError, match="unknown failpoint"):
            fi.arm("no.such.site=error")
        with pytest.raises(ValueError, match="unparsable action"):
            fi.arm("device.dispatch=explode")
        with pytest.raises(ValueError, match="unknown error class"):
            fi.arm("device.dispatch=error(nuke)")
        # all-or-nothing: nothing armed by the failures above
        assert not fi.snapshot()["armed"]

    def test_error_count_and_nth_triggers(self):
        fi.arm("device.dispatch=error*2")
        with pytest.raises(fi.FailpointError):
            fi.hit("device.dispatch")
        with pytest.raises(fi.FailpointError):
            fi.hit("device.dispatch")
        fi.hit("device.dispatch")  # *2 exhausted: passes through
        p = fi.snapshot()["points"]["device.dispatch"]
        assert p["calls"] == 3 and p["triggers"] == 2 and p["exhausted"]

        fi.arm("device.dispatch=error@2")  # 1st, 3rd, 5th... calls
        with pytest.raises(fi.FailpointError):
            fi.hit("device.dispatch")
        fi.hit("device.dispatch")
        with pytest.raises(fi.FailpointError):
            fi.hit("device.dispatch")

    def test_error_classes(self):
        fi.arm("device.dispatch=error(transport)")
        with pytest.raises(TransportError):
            fi.hit("device.dispatch")
        fi.arm("device.dispatch=error(oom)")
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
            fi.hit("device.dispatch")

    def test_delay_action(self):
        fi.arm("device.dispatch=delay(30)")
        t0 = time.perf_counter()
        fi.hit("device.dispatch")
        assert time.perf_counter() - t0 >= 0.025

    def test_disarmed_gate_is_module_bool(self):
        """The zero-overhead contract: sites gate on ``fi.armed``
        before calling hit(), so the disarmed hot path pays one
        attribute read (tests/test_observer_cost.py counts hit() and
        the registry lock over a fused Count: zero)."""
        assert fi.armed is False
        fi.arm("device.dispatch=error")
        assert fi.armed is True
        fi.disarm()
        assert fi.armed is False


# ------------------------------------------------------- circuit breaker


class TestCircuitBreaker:
    def test_state_machine(self):
        now = [0.0]
        b = CircuitBreaker(threshold=3, cooldown_s=5.0,
                           clock=lambda: now[0])
        assert b.state == BREAKER_CLOSED and b.allow()
        b.note_failure()
        b.note_failure()
        assert b.state == BREAKER_CLOSED  # below threshold
        b.note_failure()
        assert b.state == BREAKER_OPEN
        assert not b.allow() and not b.allow()
        assert b.snapshot()["fastFails"] == 2
        # cooldown elapses: exactly ONE half-open trial admitted
        now[0] = 5.0
        assert b.allow()
        assert b.state == BREAKER_HALF_OPEN
        assert not b.allow()  # concurrent call during the trial
        b.note_success()
        assert b.state == BREAKER_CLOSED
        assert b.snapshot()["opened"] == 1
        assert b.snapshot()["closed"] == 1

    def test_half_open_failure_reopens(self):
        now = [0.0]
        b = CircuitBreaker(threshold=1, cooldown_s=2.0,
                           clock=lambda: now[0])
        b.note_failure()
        assert b.state == BREAKER_OPEN
        now[0] = 2.0
        assert b.allow()  # the trial
        b.note_failure()
        assert b.state == BREAKER_OPEN
        assert not b.allow()  # cooling down again from t=2
        now[0] = 4.0
        assert b.allow()
        b.note_success()
        assert b.state == BREAKER_CLOSED

    def test_lost_half_open_trial_does_not_wedge(self):
        """A HALF_OPEN trial whose outcome never arrives (abandoned
        flight, crashed caller) must not blacklist the peer forever:
        after one more cooldown the breaker admits a fresh trial."""
        now = [0.0]
        b = CircuitBreaker(threshold=1, cooldown_s=1.0,
                           clock=lambda: now[0])
        b.note_failure()
        now[0] = 1.0
        assert b.allow()          # the trial — and it is never noted
        assert not b.allow()      # still outstanding
        now[0] = 2.0
        assert b.allow()          # timeout escape: a fresh trial
        b.note_success()
        assert b.state == BREAKER_CLOSED

    def test_success_resets_consecutive_count(self):
        b = CircuitBreaker(threshold=3)
        b.note_failure()
        b.note_failure()
        b.note_success()
        b.note_failure()
        b.note_failure()
        assert b.state == BREAKER_CLOSED  # never 3 consecutive

    def test_shed_never_opens_breaker(self, tmp_path):
        """A shed (429/503 from a live peer) is proof of life: the
        executor feeds it to note_peer_success, never note_failure."""
        transport, nodes = make_cluster(tmp_path, n=2, replica_n=1)
        c = nodes[0].cluster
        c.breaker_threshold = 1
        from pilosa_tpu.parallel.cluster import ShedByPeerError  # noqa: F401

        c.note_peer_success("node1")  # what the executor does on shed
        assert c.breaker("node1").state == BREAKER_CLOSED

    def test_heartbeat_probe_closes_open_breaker(self, tmp_path):
        """Half-open trials ride the membership heartbeat: a
        successful SWIM probe heals the breaker without query
        traffic."""
        transport, nodes = make_cluster(tmp_path, n=3, replica_n=2)
        c = nodes[0].cluster
        for _ in range(c.breaker_threshold):
            c.note_peer_failure("node2")
        b = c.breaker("node2")
        assert b.state == BREAKER_OPEN
        heartbeat_round(nodes[0])  # node2 is reachable: probe succeeds
        assert b.state == BREAKER_CLOSED


def _seed_rows(nodes, n_shards=6, row=1):
    """row bits spread over n_shards through node0; returns per-shard
    truth {shard: count}."""
    nodes[0].create_index("i")
    nodes[0].create_field("i", "f")
    truth = {}
    cols = []
    rows = []
    for s in range(n_shards):
        k = 2 + (s % 3)
        truth[s] = k
        for j in range(k):
            cols.append(s * SHARD_WIDTH + j)
            rows.append(row)
    API(nodes[0]).import_bits("i", "f", rows, cols)
    return truth


class TestBreakerFastFail:
    def test_breaker_open_queries_fast_fail_under_10pct_of_timeout(
            self, tmp_path):
        """The acceptance pin: a dead peer that costs a full RPC
        timeout per dial stalls the FIRST query; once its breaker is
        open, subsequent queries mapping to it fast-fail onto the next
        replica in < 10% of the configured timeout."""
        rpc_timeout = 0.5
        transport, nodes = make_cluster(tmp_path, n=3, replica_n=2)
        truth = _seed_rows(nodes)
        total = sum(truth.values())
        ex = nodes[0].executor
        assert ex.execute("i", "Count(Row(f=1))")[0] == total  # warm
        c = nodes[0].cluster
        # the victim must actually be a routing target of the query
        victim = next(k for k in c.shards_by_node("i", list(truth))
                      if k != c.local_id)
        # the warm query already created the breaker at the default
        # threshold; tighten the live instance so one failure opens it
        c.breaker(victim).threshold = 1
        real = transport.query_node

        def dead_slow(node, index, pql, shards, **kw):
            if node.id == victim:
                time.sleep(rpc_timeout)  # a sunk dial that times out
                raise TransportError(
                    f"node unreachable: {victim}: timed out")
            return real(node, index, pql, shards, **kw)

        transport.query_node = dead_slow
        try:
            # first query pays the timeout once, fails over, opens the
            # breaker (threshold 1) — and stays correct
            assert ex.execute("i", "Count(Row(f=1))")[0] == total
            assert c.breaker(victim).state == BREAKER_OPEN
            t0 = time.perf_counter()
            assert ex.execute("i", "Count(Row(f=1))")[0] == total
            elapsed = time.perf_counter() - t0
            assert elapsed < rpc_timeout * 0.1, (
                f"breaker-open query took {elapsed:.3f}s, "
                f"expected < {rpc_timeout * 0.1:.3f}s")
        finally:
            transport.query_node = real


# ---------------------------------------------------------- hedged reads


class TestHedgedReads:
    def _prime(self, tmp_path):
        transport, nodes = make_cluster(tmp_path, n=3, replica_n=2)
        truth = _seed_rows(nodes)
        ex = nodes[0].executor
        ex.hedge_min_samples = 2
        ex.hedge_min_s = 0.02
        ex.hedge_max_fraction = 1.0
        total = sum(truth.values())
        for _ in range(4):  # latency EWMA samples for both peers
            assert ex.execute("i", "Count(Row(f=1))")[0] == total
        return transport, nodes, ex, total

    def test_hedge_beats_slow_peer_and_stays_correct(self, tmp_path):
        transport, nodes, ex, total = self._prime(tmp_path)
        slow = 1.0
        transport.set_slow("node1", slow)
        transport.set_slow("node2", 0.0)
        t0 = time.perf_counter()
        got = ex.execute("i", "Count(Row(f=1))")[0]
        elapsed = time.perf_counter() - t0
        assert got == total
        # the hedge answered from the replica while the slow peer was
        # still sleeping — nowhere near the full delay
        assert elapsed < slow * 0.5, f"hedge did not engage: {elapsed:.3f}s"
        assert ex._hedge_issued >= 1
        assert ex._hedge_wins >= 1
        # the flight record carries the hedge evidence
        rec = ex.recorder.recent_records()[-1]
        assert rec.hedged >= 1 and rec.hedge_wins >= 1
        assert rec.to_dict()["hedged"] >= 1

    def test_hedge_bound_disables_hedging(self, tmp_path):
        transport, nodes, ex, total = self._prime(tmp_path)
        ex.hedge_max_fraction = 0.0  # hard off
        transport.set_slow("node1", 0.15)
        t0 = time.perf_counter()
        got = ex.execute("i", "Count(Row(f=1))")[0]
        elapsed = time.perf_counter() - t0
        assert got == total
        assert ex._hedge_issued == 0
        assert elapsed >= 0.14  # paid the slow peer in full

    def test_hedge_fraction_bound_holds(self, tmp_path):
        """hedges never exceed the configured fraction of RPC volume:
        with a tiny fraction and few RPCs, no hedge may issue."""
        transport, nodes, ex, total = self._prime(tmp_path)
        ex.hedge_max_fraction = 0.01  # needs 100+ RPCs per hedge
        rpcs_before = ex._hedge_rpcs
        transport.set_slow("node1", 0.1)
        assert ex.execute("i", "Count(Row(f=1))")[0] == total
        assert ex._hedge_issued <= ex.hedge_max_fraction * ex._hedge_rpcs
        assert ex._hedge_rpcs > rpcs_before


# ------------------------------------------------------- partial results


class TestPartialResults:
    def _outage(self, tmp_path):
        transport, nodes = make_cluster(tmp_path, n=3, replica_n=1)
        truth = _seed_rows(nodes)
        ex = nodes[0].executor
        total = sum(truth.values())
        assert ex.execute("i", "Count(Row(f=1))")[0] == total
        victim = "node2"
        victim_shards = sorted(
            s for s in truth
            if nodes[0].cluster.shard_nodes("i", s)[0].id == victim)
        assert victim_shards, "placement gave node2 no shards"
        transport.set_down(victim)
        return transport, nodes, ex, truth, total, victim, victim_shards

    def test_default_raises_structured_error(self, tmp_path):
        (transport, nodes, ex, truth, total, victim,
         victim_shards) = self._outage(tmp_path)
        with pytest.raises(ShardsUnavailableError,
                           match="replicas exhausted") as ei:
            ex.execute("i", "Count(Row(f=1))")
        e = ei.value
        assert e.shards == victim_shards
        assert all(e.causes[s] == {victim: "transport"}
                   for s in e.shards)
        assert isinstance(e, ExecutionError)  # back-compat hierarchy

    def test_partial_counts_and_missing_match_outage_exactly(
            self, tmp_path):
        (transport, nodes, ex, truth, total, victim,
         victim_shards) = self._outage(tmp_path)
        opt = ExecOptions(partial=True, missing=set())
        got = ex.execute("i", "Count(Row(f=1))", opt=opt)[0]
        assert sorted(opt.missing) == victim_shards
        assert got == total - sum(truth[s] for s in victim_shards)
        # Row() accounts the same way: reachable columns only
        opt2 = ExecOptions(partial=True, missing=set())
        row = ex.execute("i", "Row(f=1)", opt=opt2)[0]
        want = {s * SHARD_WIDTH + j for s in truth
                if s not in victim_shards for j in range(truth[s])}
        assert {int(c) for c in row.columns()} == want
        assert sorted(opt2.missing) == victim_shards

    def test_partial_results_never_enter_the_cache(self, tmp_path):
        """After a degraded partial read, healing the outage and
        re-running the same query (default semantics) must return the
        FULL truth — a partial value cached under the query's key
        would serve a hole forever."""
        from pilosa_tpu.runtime import resultcache

        resultcache.configure(enabled=True)
        (transport, nodes, ex, truth, total, victim,
         victim_shards) = self._outage(tmp_path)
        opt = ExecOptions(partial=True, missing=set())
        got = ex.execute("i", "Count(Row(f=1))", opt=opt)[0]
        assert got < total
        transport.set_down(victim, False)
        assert ex.execute("i", "Count(Row(f=1))")[0] == total
        # the gate itself: a request that accounted a missing shard
        # suppresses every fill it would perform
        assert ex._rc_fill_ok(opt) is False
        assert ex._rc_fill_ok(ExecOptions(partial=True,
                                          missing=set())) is True

    def test_default_path_unchanged_without_flag(self, tmp_path):
        """No-flag requests keep all-or-error semantics: partial
        machinery is inert (missing=None) and healthy results are
        identical."""
        transport, nodes = make_cluster(tmp_path, n=3, replica_n=1)
        truth = _seed_rows(nodes)
        ex = nodes[0].executor
        opt = ExecOptions()
        assert opt.partial is False and opt.missing is None
        assert ex.execute("i", "Count(Row(f=1))",
                          opt=opt)[0] == sum(truth.values())
        assert opt.missing is None  # never materialized


# ------------------------------------------------------- device OOM retry


class TestDeviceOomRetry:
    def test_fused_count_retries_once_after_evict(self, tmp_path):
        from pilosa_tpu import devobs
        from pilosa_tpu.runtime import residency

        transport, nodes = make_cluster(tmp_path, n=1)
        truth = _seed_rows(nodes, n_shards=4)
        ex = nodes[0].executor
        total = sum(truth.values())
        assert ex.execute("i", "Count(Row(f=1))")[0] == total  # warm
        obs = devobs.reset()
        ev0 = residency.manager().evictions
        fi.arm("device.dispatch=error(oom)*1")
        got = ex.execute("i", "Count(Row(f=1))", opt=ExecOptions(
            cache=False))[0]
        assert got == total
        assert obs.oom_retries == 1
        assert obs.snapshot()["oomRetries"] == 1
        assert residency.manager().evictions >= ev0

    def test_persistent_oom_still_errors(self, tmp_path):
        transport, nodes = make_cluster(tmp_path, n=1)
        _seed_rows(nodes, n_shards=4)
        ex = nodes[0].executor
        fi.arm("device.dispatch=error(oom)")  # every call
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
            ex.execute("i", "Count(Row(f=1))",
                       opt=ExecOptions(cache=False))


# ------------------------------------------------------------ chaos soak


class TestChaosSoak:
    def test_three_node_soak_no_silent_wrong_data(self, tmp_path):
        """One of three nodes flaps, another carries injected latency,
        concurrent reads (default + partial) and writes flow — every
        read is a correct result, an explicit error, or a correctly-
        accounted partial, and read goodput stays >= 80%."""
        transport, nodes = make_cluster(tmp_path, n=3, replica_n=2)
        truth = _seed_rows(nodes)  # static row 1: the read target
        total = sum(truth.values())
        ex0 = nodes[0].executor
        assert ex0.execute("i", "Count(Row(f=1))")[0] == total
        transport.set_slow("node1", 0.05)  # 50 ms gray failure throughout

        stop = threading.Event()
        wrong: list[str] = []
        counts = {"ok": 0, "partial_ok": 0, "error": 0}
        lock = threading.Lock()

        def flapper():
            down = False
            while not stop.is_set():
                down = not down
                transport.set_down("node2", down)
                try:
                    heartbeat_round(nodes[0])
                except Exception:
                    pass
                stop.wait(0.15)
            transport.set_down("node2", False)

        def reader(use_partial: bool):
            node = nodes[0]
            while not stop.is_set():
                opt = ExecOptions(partial=use_partial,
                                  missing=set() if use_partial else None)
                try:
                    got = node.executor.execute(
                        "i", "Count(Row(f=1))", opt=opt)[0]
                except Exception:
                    with lock:
                        counts["error"] += 1
                    continue
                missing = sorted(opt.missing or ())
                want = total - sum(truth.get(s, 0) for s in missing)
                if got != want:
                    with lock:
                        wrong.append(
                            f"got {got}, want {want} "
                            f"(missing={missing})")
                else:
                    with lock:
                        counts["partial_ok" if missing else "ok"] += 1

        def writer():
            i = 0
            while not stop.is_set():
                i += 1
                col = (i % 6) * SHARD_WIDTH + 5000 + i
                try:
                    nodes[0].executor.execute("i", f"Set({col}, f=2)")
                except Exception:
                    pass  # writes may fail while an owner is down
                stop.wait(0.01)

        threads = ([threading.Thread(target=flapper, daemon=True),
                    threading.Thread(target=writer, daemon=True)]
                   + [threading.Thread(target=reader, args=(p,),
                                       daemon=True)
                      for p in (False, False, True, True)])
        for t in threads:
            t.start()
        time.sleep(2.5)
        stop.set()
        for t in threads:
            t.join(timeout=10)

        assert not wrong, f"silently wrong responses: {wrong[:5]}"
        done = counts["ok"] + counts["partial_ok"] + counts["error"]
        assert done > 20, f"soak produced too little traffic: {counts}"
        goodput = (counts["ok"] + counts["partial_ok"]) / done
        # replica_n=2 keeps every shard reachable through the flap, so
        # reads fail over (or degrade partially) instead of erroring
        assert goodput >= 0.8, f"goodput {goodput:.2f}: {counts}"


# ------------------------------------------------- failpoint integrations


class TestFailpointIntegrations:
    def test_map_shard_failpoint_ticks_on_per_shard_path(self, tmp_path):
        """The executor.map_shard site lives on the per-shard map (the
        fused all-shard paths batch around it): a single-shard
        restriction routes it, and an injected delay passes through
        without changing the result."""
        transport, nodes = make_cluster(tmp_path, n=1)
        truth = _seed_rows(nodes, n_shards=4)
        ex = nodes[0].executor
        fi.arm("executor.map_shard=delay(5)")
        got = ex.execute("i", "Count(Row(f=1))", shards=[0])[0]
        assert got == truth[0]
        assert fi.snapshot()["points"]["executor.map_shard"]["calls"] > 0

    def test_resultcache_fill_failpoint_counts(self):
        from pilosa_tpu.runtime.resultcache import Key, ResultCache

        rc = ResultCache()
        fi.arm("resultcache.fill=error*1")
        with pytest.raises(fi.FailpointError):
            rc.put(Key(("k",)), (1,), "v", 64)
        assert rc.put(Key(("k",)), (1,), "v", 64)  # *1 exhausted
        assert fi.snapshot()["points"]["resultcache.fill"]["triggers"] == 1
