#!/usr/bin/env python
"""Randomized differential soak: hours-scale stress beyond the CI tier.

Drives a replicated in-process 3-node cluster with an interleaved
random workload — bulk imports, PQL Set/Clear, BSI writes, nested set
algebra, BSI ranges, TopN, GroupBy — checking EVERY read against
Python-set/dict oracles, while randomly dropping a node (reads must
fail over exactly), running anti-entropy repair cycles, and (round 3)
driving coordinator-led elastic RESIZE events: a fourth node joins
(fragments re-home by jump hash) and later leaves, with the oracle
exact across every ownership change — the reference's
internal/clustertests/ tier including its resize legs.

Round 5 adds bidirectional PAIR PARTITIONS to the fault schedule
(internal/clustertests/cluster_test.go:69-80's pumba netem scenario):
two live nodes stop hearing each other while both keep serving the
rest of the cluster — reads from either side must fail over to the
reachable replica, and anti-entropy passes RACE the partition (the
syncer must skip the unreachable peer, never half-apply.  Also GRAY
faults: a node answers every message LATE — no TransportError fires,
so nothing fails over; writes keep replicating through it
synchronously and every read must stay exact, just slower).  The
process-level counterpart with real SIGSTOP freezes is
tools/soak_proc.py.

    PYTHONPATH=/root/repo:$PYTHONPATH python tools/soak.py --seconds 600

Exit code 0 = no divergence.  Deterministic per --seed.  The CI-tier
equivalents are tests/test_fuzz_stress.py and tests/test_model_stress.py;
this harness exists to run 100x longer (the reference's long-running
clustertests tier, internal/clustertests/).
"""

from __future__ import annotations

import argparse
import os
import pathlib
import random
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("PILOSA_TPU_SHARD_WIDTH_EXP", "16")
os.environ.setdefault("PILOSA_TPU_PARANOIA", "1")  # sanitizer on


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=300.0)
    ap.add_argument("--seed", type=int, default=12348)
    ap.add_argument("--progress-every", type=float, default=30.0)
    args = ap.parse_args()

    # pin jax before anything touches a backend
    import jax

    jax.config.update("jax_platforms", "cpu")

    from pilosa_tpu.api import API
    from pilosa_tpu.parallel.syncer import HolderSyncer
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from tests.test_cluster import make_cluster
    from tests.test_fuzz_stress import eval_set_algebra, gen_query

    rng = random.Random(args.seed)
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="soak-"))
    transport, nodes = make_cluster(tmp, n=3, replica_n=2)
    coord = nodes[0]
    coord.create_index("i")
    api = API(coord)

    n_shards = 4
    fields = [f"f{i}" for i in range(3)]
    for f in fields:
        coord.create_field("i", f)
    from pilosa_tpu.models.field import FieldOptions
    from pilosa_tpu.models.index import IndexOptions

    coord.create_field("i", "v", options=FieldOptions.int_field(-1000, 1000))
    # keyed surface: translation (coordinator-allocated ids, replica
    # tailing, read-through) must stay exact under the same fault
    # schedule as everything else
    coord.create_index("k", options=IndexOptions(keys=True))
    coord.create_field("k", "kf", options=FieldOptions(keys=True))
    kbits: dict[str, set] = {f"r{j}": set() for j in range(4)}
    # time-quantum surface: every write lands in multiple views, AE
    # reconciles per view, and resize transfers must move ALL views
    coord.create_field("i", "t",
                       options=FieldOptions.time_field("YM"))
    # oracle: (row, month) -> cols; months 1..6 of 2024
    tbits: dict[tuple[int, int], set] = {
        (r, m): set() for r in range(3) for m in range(1, 7)}
    # Store/ClearRow target: a whole-row write forwarded to ALL nodes
    # (a different replication shape from per-shard owner fan-out)
    coord.create_field("i", "st")
    stbits: dict[int, set] = {j: set() for j in range(3)}

    bits: dict[tuple[str, int], set] = {
        (f, r): set() for f in fields for r in range(5)}
    vals: dict[int, int] = {}
    universe: set[int] = set()

    def col():
        return rng.randrange(n_shards * SHARD_WIDTH)

    from pilosa_tpu.pql import parse_python

    downed: str | None = None
    partition: tuple[str, str] | None = None
    slowed: str | None = None
    iters = 0
    checks = 0
    resizes = 0
    partitions = 0
    slow_events = 0
    extra: list = []  # nodes joined beyond the base 3, newest last
    next_extra_id = 3
    t_end = time.monotonic() + args.seconds
    t_report = time.monotonic() + args.progress_every
    ex = coord.executor

    def live_nodes():
        return [*nodes, *extra]

    while time.monotonic() < t_end:
        iters += 1
        action = rng.random()
        # writes and resizes need every replica reachable from the
        # coordinator; reads and AE deliberately RACE active faults
        quiesced = downed is None and partition is None

        if action < 0.18:  # bulk import
            f = rng.choice(fields)
            row = rng.randrange(5)
            cs = sorted({col() for _ in range(rng.randrange(1, 120))})
            if quiesced:  # writes only with all replicas up
                api.import_bits("i", f, [row] * len(cs), cs)
                bits[(f, row)].update(cs)
                universe.update(cs)
        elif action < 0.28:  # single Set / Clear via PQL
            f = rng.choice(fields)
            row = rng.randrange(5)
            c = col()
            if quiesced:
                if rng.random() < 0.7:
                    ex.execute("i", f"Set({c}, {f}={row})")
                    bits[(f, row)].add(c)
                    universe.add(c)
                else:
                    ex.execute("i", f"Clear({c}, {f}={row})")
                    bits[(f, row)].discard(c)
        elif action < 0.32:  # BSI write
            c = col()
            v = rng.randrange(-1000, 1001)
            if quiesced:
                ex.execute("i", f"Set({c}, v={v})")
                vals[c] = v
                universe.add(c)
        elif action < 0.345:  # time-field write (multi-view)
            if quiesced:
                r_, m = rng.randrange(3), rng.randrange(1, 7)
                c = col()
                ex.execute("i",
                           f"Set({c}, t={r_}, 2024-{m:02d}-15T00:00)")
                tbits[(r_, m)].add(c)
                universe.add(c)
        elif action < 0.36:  # time-window read vs oracle (any node,
            # races every fault: per-view failover + AE)
            r_ = rng.randrange(3)
            m0 = rng.randrange(1, 7)
            m1 = rng.randrange(m0, 7)
            node = rng.choice(live_nodes())
            if downed is not None and node.cluster.local_id == downed:
                node = coord
            got = node.executor.execute(
                "i", f"Count(Row(t={r_}, from='2024-{m0:02d}-01T00:00',"
                     f" to='2024-{m1 + 1:02d}-01T00:00'))")[0]
            want = len(set().union(*(tbits[(r_, m)]
                                     for m in range(m0, m1 + 1))))
            assert int(got) == want, \
                f"time divergence t={r_} [{m0},{m1}] on " \
                f"{node.cluster.local_id}"
            checks += 1
        elif action < 0.39:  # keyed write (translation allocates ids)
            if quiesced:
                rk = f"r{rng.randrange(4)}"
                ck = f"u{rng.randrange(3000)}"
                ex.execute("k", f'Set("{ck}", kf="{rk}")')
                kbits[rk].add(ck)
        elif action < 0.43:  # keyed read vs oracle — replicas serve
            # via tailed stores + read-through; during faults the
            # coordinator (never downed) answers, since a partitioned
            # replica legitimately cannot resolve keys created across
            # the cut (the reference's tailing replicas share that
            # staleness window)
            rk = f"r{rng.randrange(4)}"
            node = coord if not quiesced else rng.choice(live_nodes())
            got = node.executor.execute("k", f'Count(Row(kf="{rk}"))')[0]
            assert int(got) == len(kbits[rk]), \
                f"keyed divergence {rk} on {node.cluster.local_id}"
            ra, rb = rng.sample(list(kbits), 2)
            got = node.executor.execute(
                "k", f'Count(Intersect(Row(kf="{ra}"), '
                     f'Row(kf="{rb}")))')[0]
            assert int(got) == len(kbits[ra] & kbits[rb]), \
                f"keyed intersect divergence on {node.cluster.local_id}"
            checks += 2
        elif action < 0.445:  # Store / ClearRow: whole-row writes
            # forwarded to every node (executor.go:1739 / :1797 shape)
            if quiesced:
                sr = rng.randrange(3)
                if rng.random() < 0.75:
                    f = rng.choice(fields)
                    r1, r2 = rng.sample(range(5), 2)
                    ex.execute(
                        "i", f"Store(Union(Row({f}={r1}), "
                             f"Row({f}={r2})), st={sr})")
                    stbits[sr] = bits[(f, r1)] | bits[(f, r2)]
                else:
                    ex.execute("i", f"ClearRow(st={sr})")
                    stbits[sr] = set()
        elif action < 0.46:  # stored-row read vs oracle (races faults)
            sr = rng.randrange(3)
            node = rng.choice(live_nodes())
            if downed is not None and node.cluster.local_id == downed:
                node = coord
            got = node.executor.execute("i", f"Count(Row(st={sr}))")[0]
            assert int(got) == len(stbits[sr]), \
                f"Store divergence st={sr} on {node.cluster.local_id}"
            checks += 1
        elif action < 0.70:  # nested algebra vs oracle (any node)
            q = gen_query(rng)
            want = eval_set_algebra(parse_python(q).calls[0],
                                    bits, universe)
            node = rng.choice(live_nodes())
            if downed is not None and node.cluster.local_id == downed:
                node = coord
            res = node.executor.execute("i", q)[0]
            got = (set(int(x) for x in res.columns())
                   if hasattr(res, "columns") else None)
            if got is not None:
                assert got == want, f"divergence on {q}"
            else:
                assert int(res) == len(want), f"count divergence on {q}"
            checks += 1
        elif action < 0.80:  # BSI range vs oracle
            op = rng.choice(["<", "<=", ">", ">=", "==", "!="])
            p = rng.randrange(-1000, 1001)
            got = ex.execute("i", f"Count(Row(v {op} {p}))")[0]
            import operator as _op

            cmp = {"<": _op.lt, "<=": _op.le, ">": _op.gt,
                   ">=": _op.ge, "==": _op.eq, "!=": _op.ne}[op]
            want = sum(1 for v in vals.values() if cmp(v, p))
            assert int(got) == want, f"BSI divergence v {op} {p}"
            checks += 1
        elif action < 0.88:  # TopN vs oracle
            f = rng.choice(fields)
            pairs = ex.execute("i", f"TopN({f}, n=5)")[0]
            want = sorted((len(cs) for (fn, r), cs in bits.items()
                           if fn == f and cs), reverse=True)[:5]
            assert [p.count for p in pairs] == want, f"TopN divergence {f}"
            checks += 1
        elif action < 0.93:  # GroupBy vs oracle (both directions)
            fa, fb = rng.sample(fields, 2)
            gcs = ex.execute("i", f"GroupBy(Rows({fa}), Rows({fb}))")[0]
            got = {tuple((fr.field, fr.row_id) for fr in gc.group): gc.count
                   for gc in gcs}
            want = {}
            for ra in range(5):
                for rb in range(5):
                    n = len(bits[(fa, ra)] & bits[(fb, rb)])
                    if n:
                        want[((fa, ra), (fb, rb))] = n
            assert got == want, (
                f"GroupBy divergence {fa}x{fb}: "
                f"missing={set(want) - set(got)} "
                f"extra={set(got) - set(want)}")
            checks += 1
        elif action < 0.945:  # elastic resize: join or leave
            # ownership moves under live traffic; the oracle must stay
            # exact across every re-homing (reference clustertests
            # resize legs, cluster.go:1196-1561)
            if quiesced:
                from pilosa_tpu.models.holder import Holder
                from pilosa_tpu.parallel.cluster import Cluster, Node
                from pilosa_tpu.parallel.node import ClusterNode
                from pilosa_tpu.parallel.resize import Resizer

                if not extra:
                    # fixed node ID (placement + transport handle are
                    # overwritten on re-join, no per-cycle leak), fresh
                    # dir per cycle (a removed node keeps its detached
                    # data; rejoining on it would resurrect stale bits)
                    dirname = f"node3-epoch{next_extra_id}"
                    next_extra_id += 1
                    h = Holder(str(tmp / dirname))
                    cl = Cluster("node3", nodes=[Node(id="node3")],
                                 replica_n=2,
                                 transport=transport.bind("node3"))
                    jn = ClusterNode(h, cl)
                    resp = transport.send_message(
                        coord.cluster.local_node,
                        {"type": "node-join",
                         "node": {"id": "node3", "uri": ""}})
                    assert resp.get("ok"), f"join failed: {resp}"
                    extra.append(jn)
                else:
                    import shutil

                    jn = extra.pop()
                    Resizer(coord).run(remove_id=jn.cluster.local_id)
                    path = jn.holder.path
                    jn.holder.close()
                    shutil.rmtree(path, ignore_errors=True)
                resizes += 1
                for nd in live_nodes():
                    assert nd.cluster.state == "NORMAL", (
                        f"{nd.cluster.local_id} not NORMAL after resize")
        elif action < 0.975:  # fault injection: heal, or down /
            # partition / gray (slow) failure
            if downed is not None:
                transport.set_down(downed, False)
                downed = None
            elif partition is not None:
                transport.set_partition(*partition, False)
                partition = None
            elif slowed is not None:
                transport.set_slow(slowed, 0.0)
                slowed = None
            else:
                kind = rng.random()
                if kind < 0.4:
                    downed = rng.choice(["node1", "node2"])
                    transport.set_down(downed)
                elif kind < 0.8:
                    # bidirectional pair partition between two LIVE
                    # nodes: both keep serving everyone else; reads
                    # from either side must fail over to the
                    # reachable replica
                    ids = [nd.cluster.local_id for nd in live_nodes()]
                    a, b = rng.sample(ids, 2)
                    transport.set_partition(a, b)
                    partition = (a, b)
                    partitions += 1
                else:
                    # GRAY failure: the node answers, just late —
                    # no failover triggers, writes keep flowing, and
                    # every read must still be exact
                    slowed = rng.choice(["node1", "node2"])
                    transport.set_slow(slowed, rng.uniform(0.01, 0.06))
                    slow_events += 1
        else:  # anti-entropy repair pass — races any active partition
            if downed is None:
                for nd in live_nodes():
                    HolderSyncer(nd).sync_holder()

        if time.monotonic() >= t_report:
            t_report = time.monotonic() + args.progress_every
            print(f"soak: {iters} iters, {checks} oracle checks, "
                  f"{resizes} resizes, {partitions} partitions, "
                  f"{slow_events} gray, nodes={len(live_nodes())}, "
                  f"downed={downed}, partition={partition}, "
                  f"slowed={slowed}", flush=True)

    if downed is not None:
        transport.set_down(downed, False)
    if partition is not None:
        transport.set_partition(*partition, False)
    if slowed is not None:
        transport.set_slow(slowed, 0.0)
    for nd in live_nodes():
        HolderSyncer(nd).sync_holder()
    # final convergence: every node answers every row exactly
    for f in fields:
        for r in range(5):
            want = bits[(f, r)]
            for nd in live_nodes():
                res = nd.executor.execute("i", f"Row({f}={r})")[0]
                got = set(int(x) for x in res.columns())
                assert got == want, f"final divergence {f}={r} on " \
                    f"{nd.cluster.local_id}"
    print(f"soak PASSED: {iters} iters, {checks} oracle checks, "
          f"{resizes} resizes, {partitions} partitions, "
          f"{slow_events} gray faults")
    return 0


if __name__ == "__main__":
    sys.exit(main())
