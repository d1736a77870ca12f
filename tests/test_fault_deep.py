"""Deep fault-injection tier (round-2): node death mid-resize with
abort + recovery, anti-entropy convergence from bidirectional replica
divergence under concurrent writes, and a server restart over a torn
WAL.  Parity: internal/clustertests/cluster_test.go:69-80 (pumba
container pauses), cluster.go:1250 (resize abort), AE §3.5."""

from __future__ import annotations

import threading

import pytest

from pilosa_tpu.api import API
from pilosa_tpu.parallel.cluster import Node, TransportError
from pilosa_tpu.parallel.membership import heartbeat_round
from pilosa_tpu.parallel.resize import ResizeError, Resizer
from pilosa_tpu.parallel.syncer import HolderSyncer
from pilosa_tpu.shardwidth import SHARD_WIDTH

from tests.test_cluster import make_cluster


def _seed(node, n_shards=6, row=1):
    cols = [s * SHARD_WIDTH + 11 * s for s in range(n_shards)]
    node.create_index("i")
    node.create_field("i", "f")
    API(node).import_bits("i", "f", [row] * len(cols), cols)
    return cols


class TestNodeDiesMidResize:
    def test_source_dies_mid_resize_aborts_then_recovers(self, tmp_path):
        from pilosa_tpu.models.holder import Holder
        from pilosa_tpu.parallel.cluster import Cluster
        from pilosa_tpu.parallel.node import ClusterNode

        transport, nodes = make_cluster(tmp_path, n=2, replica_n=2)
        cols = _seed(nodes[0])
        want = len(cols)

        joiner_holder = Holder(str(tmp_path / "node2"))
        joiner = ClusterNode(
            joiner_holder,
            Cluster("node2", nodes=[Node(id="node2")], replica_n=1,
                    transport=transport))

        # kill node1 the moment the first resize instruction is
        # dispatched: fragment fetches from it fail mid-job
        real_send = transport.send_message
        state = {"instructions": 0}

        def chaotic_send(node, message):
            if message.get("type") == "resize-instruction":
                state["instructions"] += 1
                transport.set_down("node1")
            return real_send(node, message)

        transport.send_message = chaotic_send
        try:
            with pytest.raises((ResizeError, TransportError)):
                Resizer(nodes[0]).run(add=Node(id="node2"))
        finally:
            transport.send_message = real_send

        # abort path: coordinator back to NORMAL, membership unchanged,
        # reads exact from the surviving replica set
        assert nodes[0].cluster.state == "NORMAL"
        assert len(nodes[0].cluster.sorted_nodes()) == 2
        assert nodes[0].executor.execute("i", "Count(Row(f=1))")[0] == want
        # writes unblocked after abort (node1 still dark: best-effort)
        API(nodes[0]).import_bits("i", "f", [1], [3 * SHARD_WIDTH + 999])
        want += 1

        # node1 comes back; AE repairs the write it missed, then the
        # retried resize completes and every node (including the
        # joiner) answers the full result
        transport.set_down("node1", False)
        HolderSyncer(nodes[0]).sync_holder()
        HolderSyncer(nodes[1]).sync_holder()
        summary = Resizer(nodes[0]).run(add=Node(id="node2"))
        assert summary["transfers"] > 0
        for nd in (*nodes, joiner):
            assert nd.executor.execute("i", "Count(Row(f=1))")[0] == want

    def test_resize_abort_flag_mid_job(self, tmp_path):
        """Explicit abort (api.go:1250): the flag set between
        instructions stops the job and restores NORMAL."""
        from pilosa_tpu.models.holder import Holder
        from pilosa_tpu.parallel.cluster import Cluster
        from pilosa_tpu.parallel.node import ClusterNode

        transport, nodes = make_cluster(tmp_path, n=2, replica_n=1)
        _seed(nodes[0])
        Holder(str(tmp_path / "node2"))  # dir exists for the joiner
        r = Resizer(nodes[0])

        real_send = transport.send_message

        def abort_after_first(node, message):
            resp = real_send(node, message)
            if message.get("type") == "resize-instruction":
                r.abort()
            return resp

        transport.send_message = abort_after_first
        try:
            # abort only raises if a later instruction existed; either
            # way the job must leave the cluster NORMAL and writable
            try:
                r.run(add=Node(id="node2"))
            except ResizeError:
                pass
        finally:
            transport.send_message = real_send
        assert nodes[0].cluster.state == "NORMAL"
        API(nodes[0]).import_bits("i", "f", [1], [42])


class TestBidirectionalDivergence:
    def test_ae_converges_both_directions_under_concurrent_writes(
            self, tmp_path):
        """Replica set {node0, node1} diverges BOTH ways (each holds
        bits the other missed), a writer keeps importing during repair,
        and anti-entropy still converges every replica to the union."""
        transport, nodes = make_cluster(tmp_path, n=2, replica_n=2)
        n0, n1 = nodes
        n0.create_index("i")
        n0.create_field("i", "f")
        api0, api1 = API(n0), API(n1)

        base = [s * SHARD_WIDTH + s for s in range(4)]
        api0.import_bits("i", "f", [1] * len(base), base)

        # direction 1: node1 dark, bits land only on node0
        transport.set_down("node1")
        only0 = [s * SHARD_WIDTH + 1000 + s for s in range(4)]
        api0.import_bits("i", "f", [1] * len(only0), only0)
        transport.set_down("node1", False)

        # direction 2: node0 dark, bits land only on node1
        transport.set_down("node0")
        only1 = [s * SHARD_WIDTH + 2000 + s for s in range(4)]
        api1.import_bits("i", "f", [1] * len(only1), only1)
        transport.set_down("node0", False)

        # concurrent writer hammers a second row while AE repairs row 1
        stop = threading.Event()
        written: list[int] = []

        def writer():
            i = 0
            while not stop.is_set() and i < 200:
                col = (i % 4) * SHARD_WIDTH + 5000 + i
                api0.import_bits("i", "f", [2], [col])
                written.append(col)
                i += 1

        t = threading.Thread(target=writer)
        t.start()
        try:
            for _ in range(3):  # repeated passes, as the AE loop would
                HolderSyncer(n0).sync_holder()
                HolderSyncer(n1).sync_holder()
        finally:
            stop.set()
            t.join()
        # one final quiesced pass picks up anything written mid-repair
        HolderSyncer(n0).sync_holder()
        HolderSyncer(n1).sync_holder()

        want1 = sorted(base + only0 + only1)
        want2 = sorted(set(written))
        for nd in nodes:
            row1 = nd.executor.execute("i", "Row(f=1)")[0]
            assert sorted(int(c) for c in row1.columns()) == want1, nd
            row2 = nd.executor.execute("i", "Row(f=2)")[0]
            assert sorted(int(c) for c in row2.columns()) == want2, nd
        # per-node LOCAL fragments agree too (not just fan-out results):
        # both replicas of every shard hold the union
        for nd in nodes:
            f = nd.holder.index("i").field("f")
            for s in range(4):
                frag = f.view("standard").fragment(s)
                assert frag is not None
                import numpy as np

                row_words = frag.row(1)
                bits = (np.flatnonzero(np.unpackbits(
                    row_words.view(np.uint8), bitorder="little"))
                    if row_words is not None else [])
                local = sorted(s * SHARD_WIDTH + int(p) for p in bits)
                assert local == [c for c in want1
                                 if c // SHARD_WIDTH == s], (nd, s)


class TestRestartOverTornWal:
    def test_server_restarts_over_truncated_wal(self, tmp_path):
        """SIGKILL-style stop, torn WAL tail, restart: the server must
        boot and serve every complete record (fragment-level torn-tail
        test, lifted to the full server lifecycle)."""
        import glob
        import os

        from pilosa_tpu.server.server import Server

        d = str(tmp_path / "n0")
        s = Server(data_dir=d, coordinator=True)
        s.open()
        from pilosa_tpu.server.client import InternalClient

        c = InternalClient(timeout=30)
        c.post_json(s.uri + "/index/i", {})
        c.post_json(s.uri + "/index/i/field/f", {})
        # 20 separate batches -> 20 bulk WAL records; tearing the file
        # tail can only lose the LAST record (batch of 10)
        for b in range(20):
            cols = list(range(b * 10, b * 10 + 10))
            c.post_json(s.uri + "/index/i/field/f/import",
                        {"rowIDs": [1] * 10, "columnIDs": cols})
        # simulate SIGKILL: release only the dir lock + sockets, no
        # holder close, no WAL flush beyond what writes already did
        s._stop.set()
        s.handler.close()
        s._client.close()
        s.holder._release_dir_lock()
        c.close()

        wals = [p for p in glob.glob(d + "/**/*.wal", recursive=True)
                if os.path.getsize(p) > 0 and "/f/" in p]  # field f's WAL,
                # not the auto-created _exists field's (glob order varies)
        assert wals, "expected a live field WAL after an unclean stop"
        torn = wals[0]
        os.truncate(torn, os.path.getsize(torn) - 3)

        s2 = Server(data_dir=d, coordinator=True)
        s2.open()
        c2 = InternalClient(timeout=30)
        r = c2.post_json(s2.uri + "/index/i/query",
                         {"query": "Count(Row(f=1))"})
        got = r["results"][0]
        # the torn last bulk record loses exactly its batch of 10;
        # every complete record replays
        assert got == 190, got
        c2.close()
        s2.close()


class TestPairPartition:
    """Bidirectional pair partition (the pumba netem scenario,
    internal/clustertests/cluster_test.go:69-80): two LIVE nodes stop
    hearing each other while both keep serving everyone else.  Reads
    from either side must fail over to the reachable replica, SWIM
    must NOT declare either side dead (indirect ping-req through the
    third node vouches for both), and anti-entropy passes racing the
    partition must skip the unreachable peer without corrupting."""

    def test_partition_failover_vouching_and_ae_race(self, tmp_path):
        import random

        transport, nodes = make_cluster(tmp_path, n=3, replica_n=2)
        cols = _seed(nodes[0])
        want = len(cols)
        for nd in nodes:
            assert nd.executor.execute("i", "Count(Row(f=1))")[0] == want

        transport.set_partition("node0", "node1")
        try:
            # direct link is dead both ways
            n0, n1 = nodes[0], nodes[1]
            with pytest.raises(TransportError):
                n0.cluster.transport.send_message(
                    Node(id="node1"), {"type": "ping"})
            with pytest.raises(TransportError):
                n1.cluster.transport.send_message(
                    Node(id="node0"), {"type": "ping"})
            # ...but a third party still reaches both sides
            assert transport.send_message(
                Node(id="node0"), {"type": "ping"}).get("ok")

            # reads stay exact from EVERY node: shards whose primary
            # sits across the cut fail over to the reachable replica
            for nd in nodes:
                assert nd.executor.execute(
                    "i", "Count(Row(f=1))")[0] == want

            # SWIM: node0's round probes node1 directly (fails) then
            # escalates to ping-req via node2 (succeeds) -> no state
            # change, nobody marked DOWN
            changes = heartbeat_round(nodes[0], k=2,
                                      rng=random.Random(7))
            assert not changes, changes
            assert all(p.state != "DOWN"
                       for p in nodes[0].cluster.sorted_nodes())

            # anti-entropy racing the partition: each syncer skips the
            # peer it cannot reach; nothing is lost or half-applied
            for nd in nodes:
                HolderSyncer(nd).sync_holder()
            for nd in nodes:
                assert nd.executor.execute(
                    "i", "Count(Row(f=1))")[0] == want

            # writes land on the reachable replica set; the cut replica
            # is healed by AE after the partition lifts
            API(nodes[2]).import_bits("i", "f", [1],
                                      [5 * SHARD_WIDTH + 123])
            want += 1
        finally:
            transport.set_partition("node0", "node1", False)

        for nd in nodes:
            HolderSyncer(nd).sync_holder()
        for nd in nodes:
            assert nd.executor.execute("i", "Count(Row(f=1))")[0] == want


class TestStaleViewImport:
    """Write-side counterpart of the round-5 read-vs-cleanup race: a
    replica delivery for a shard the receiver does not own (per its
    CURRENT view) is refused (reference api.go
    ErrClusterDoesNotOwnShard), and the origin's fan-out re-resolves
    the owner set and retries — a stale-view write must never land
    its only copy on an ex-owner whose fragments the post-resize
    sweep deletes."""

    def test_non_owner_delivery_refused(self, tmp_path):
        transport, nodes = make_cluster(tmp_path, n=3, replica_n=1)
        nodes[0].create_index("i")
        nodes[0].create_field("i", "f")
        shard = 0
        owner = nodes[0].cluster.shard_nodes("i", shard)[0].id
        non_owner = next(nd for nd in nodes
                         if nd.cluster.local_id != owner)
        col = shard * SHARD_WIDTH + 5
        resp = non_owner.receive_message(
            {"type": "import", "index": "i", "field": "f",
             "rows": [1], "cols": [col], "timestamps": None,
             "clear": False})
        assert resp.get("unowned") and not resp.get("ok"), resp
        # nothing was absorbed locally
        view = non_owner.holder.index("i").field("f").view("standard")
        assert view is None or view.fragment(shard) is None

    def test_stale_origin_reroutes_after_refusal(self, tmp_path,
                                                 monkeypatch):
        transport, nodes = make_cluster(tmp_path, n=3, replica_n=1)
        nodes[0].create_index("i")
        nodes[0].create_field("i", "f")
        shard = 0
        owner = nodes[0].cluster.shard_nodes("i", shard)[0].id
        wrong = next(n for n in nodes[0].cluster.sorted_nodes()
                     if n.id != owner and n.id != "node0")
        real = nodes[0].cluster.shard_nodes
        calls = {"n": 0}

        def stale(index, s):
            calls["n"] += 1
            if calls["n"] == 1:
                return [wrong]  # stale view: delivers to an ex-owner
            return real(index, s)

        monkeypatch.setattr(nodes[0].cluster, "shard_nodes", stale)
        col = shard * SHARD_WIDTH + 7
        API(nodes[0]).import_bits("i", "f", [1], [col])
        assert calls["n"] >= 2, "fan-out never re-resolved owners"
        # the bit landed on the TRUE owner; exact from every node
        for nd in nodes:
            assert int(nd.executor.execute(
                "i", "Count(Row(f=1))")[0]) == 1, nd.cluster.local_id

    def test_stale_origin_set_reroutes_after_refusal(self, tmp_path,
                                                     monkeypatch):
        """Same contract on the PQL write path: a remote Set delivered
        to a non-owner raises UnownedShardError; the origin's
        replication loop re-resolves the owner set and retries."""
        transport, nodes = make_cluster(tmp_path, n=3, replica_n=1)
        nodes[0].create_index("i")
        nodes[0].create_field("i", "f")
        shard = 0
        owner = nodes[0].cluster.shard_nodes("i", shard)[0].id
        wrong = next(n for n in nodes[0].cluster.sorted_nodes()
                     if n.id != owner and n.id != "node0")
        real = nodes[0].cluster.shard_nodes
        calls = {"n": 0}

        def stale(index, s):
            calls["n"] += 1
            if calls["n"] == 1:
                return [wrong]
            return real(index, s)

        monkeypatch.setattr(nodes[0].cluster, "shard_nodes", stale)
        col = shard * SHARD_WIDTH + 9
        assert nodes[0].executor.execute("i", f"Set({col}, f=2)") == [True]
        assert calls["n"] >= 2, "replication never re-resolved owners"
        for nd in nodes:
            assert int(nd.executor.execute(
                "i", "Count(Row(f=2))")[0]) == 1, nd.cluster.local_id

    def test_cleanup_rescues_stranded_bits_before_delete(self, tmp_path):
        """A write whose origin's OWN stale view listed an ex-owner as
        owner has no peer that can refuse it — the bits strand there.
        The unowned sweep must push them to the current owners (AE
        diff) and verify coverage by block checksum BEFORE deleting,
        never discarding the only copy."""
        transport, nodes = make_cluster(tmp_path, n=3, replica_n=1)
        nodes[0].create_index("i")
        nodes[0].create_field("i", "f")
        shard = 0
        owner_id = nodes[0].cluster.shard_nodes("i", shard)[0].id
        owner = next(nd for nd in nodes
                     if nd.cluster.local_id == owner_id)
        stray = next(nd for nd in nodes
                     if nd.cluster.local_id != owner_id)
        col = shard * SHARD_WIDTH + 11
        # strand the only copy on the non-owner
        stray.holder.index("i").field("f").import_bits([3], [col])
        stray.cleanup_unowned()
        # fragment removed locally...
        view = stray.holder.index("i").field("f").view("standard")
        assert view is None or view.fragment(shard) is None
        # ...and the bits now live on the true owner
        ofrag = owner.holder.index("i").field("f") \
            .view("standard").fragment(shard)
        assert ofrag is not None
        import numpy as np

        arr = ofrag._rows.get(3)
        off = col - shard * SHARD_WIDTH
        assert arr is not None and (arr[off // 32] >> (off % 32)) & 1, \
            "stranded bit was not rescued to the owner"

    def test_refusal_contract_matches_http_client_error(self):
        """Over the production HTTP fabric a refusal arrives as
        ClientError (handler maps ExecutionError to 400), NOT
        TransportError — the origin's retry matcher must recognize the
        string contract on ANY exception type."""
        from pilosa_tpu.parallel.cluster import (
            UNOWNED_MARKER, refusal_is_unowned)
        from pilosa_tpu.parallel.executor import UnownedShardError
        from pilosa_tpu.server.client import ClientError

        assert refusal_is_unowned(UnownedShardError(7))
        assert refusal_is_unowned(
            ClientError(400, f"{UNOWNED_MARKER}: node does not own "
                             f"shard 7"))
        # unrelated errors that merely TALK about shard ownership must
        # not be mistaken for the refusal contract (it would convert
        # them into a silent 10 s convergence-retry loop)
        assert not refusal_is_unowned(
            ClientError(400, "node does not own shard 7"))
        assert not refusal_is_unowned(ClientError(400, "bad query"))
        assert not refusal_is_unowned(TransportError("connection refused"))


class TestRemoteSubQueryErrors:
    """What a remote sub-query's failure becomes at the origin
    (executor._map_shards' fan-in): a transport error fails over, an
    unowned-shard refusal fails over without feeding the breaker, and
    anything else — a device error on a peer, say — is the query's own
    error.  The third branch read ``refusal_is_unowned`` from a name
    only imported inside another method, so it raised NameError."""

    @staticmethod
    def _fail_remotes(transport, exc_for):
        real = transport.query_node
        hit = []

        def query_node(node, index, pql, shards, **kw):
            exc = exc_for(node, shards)
            if exc is not None:
                hit.append(node.id)
                raise exc
            return real(node, index, pql, shards, **kw)

        transport.query_node = query_node
        return hit

    def test_peer_device_error_surfaces_as_itself(self, tmp_path):
        transport, nodes = make_cluster(tmp_path, n=3, replica_n=2)
        _seed(nodes[0])
        hit = self._fail_remotes(
            transport,
            lambda node, shards: RuntimeError(
                "RESOURCE_EXHAUSTED: out of memory on the peer's device")
            if node.id != "node0" else None)
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            nodes[0].executor.execute("i", "Count(Row(f=1))")
        assert hit, "no sub-query went remote: the test reached nothing"

    def test_unowned_refusal_fails_over_to_another_replica(self, tmp_path):
        from pilosa_tpu.parallel.executor import UnownedShardError

        transport, nodes = make_cluster(tmp_path, n=3, replica_n=2)
        cols = _seed(nodes[0])
        hit = self._fail_remotes(
            transport,
            lambda node, shards: UnownedShardError(shards[0])
            if node.id == "node1" else None)
        # every shard has two owners: the ones node1 refuses are read
        # from their other replica, and the count stays exact
        assert nodes[0].executor.execute("i", "Count(Row(f=1))") == \
            [len(cols)]
        assert hit == ["node1"] * len(hit) and hit
        # a refusal is proof of life, not a transport failure
        assert nodes[0].cluster.peer_allows("node1")


class TestGrayFailure:
    """Slow-but-alive node (gray failure): no TransportError fires, so
    nothing fails over — correctness must come from the write path
    actually WAITING for the slow replica, and SWIM must keep the
    node a member (it answers probes, late)."""

    def test_slow_node_stays_member_reads_and_writes_exact(
            self, tmp_path):
        import random

        transport, nodes = make_cluster(tmp_path, n=3, replica_n=2)
        cols = _seed(nodes[0])
        want = len(cols)
        transport.set_slow("node1", 0.05)
        try:
            # SWIM: probes are slow, not dead — no state change
            changes = heartbeat_round(nodes[0], k=2,
                                      rng=random.Random(3))
            assert not changes, changes
            # reads exact from every node (including through the slow
            # replica's owned shards)
            for nd in nodes:
                assert nd.executor.execute(
                    "i", "Count(Row(f=1))")[0] == want
            # writes replicate through the slow node synchronously —
            # target a shard the SLOW node owns, chosen dynamically so
            # a placement/width change can never silently skip the
            # replication assertion below
            slow_shard = next(
                s for s in range(6)
                if "node1" in [n.id
                               for n in nodes[0].cluster.shard_nodes(
                                   "i", s)])
            API(nodes[0]).import_bits(
                "i", "f", [1], [slow_shard * SHARD_WIDTH + 777])
            want += 1
            assert nodes[2].executor.execute(
                "i", "Set(99, f=1)")[0] is True
            want += 1
        finally:
            transport.set_slow("node1", 0.0)
        # the slow replica's LOCAL fragment carries the write — it was
        # not skipped while the node was slow
        frag = nodes[1].holder.index("i").field("f") \
            .view("standard").fragment(slow_shard)
        assert frag is not None, "slow replica never got the fragment"
        arr = frag._rows.get(1)
        off = 777
        assert arr is not None and (arr[off // 32] >> (off % 32)) & 1, \
            "write was skipped on the slow replica"
        for nd in nodes:
            assert nd.executor.execute(
                "i", "Count(Row(f=1))")[0] == want
