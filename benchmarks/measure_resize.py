#!/usr/bin/env python
"""Measure elastic resize at >= 1B columns (VERDICT round-2 missing #5).

Drives a node JOIN and a node LEAVE through the real resize machinery
(`parallel/resize.py` — plan, instructions, archive transfer, write
block, cleanup; reference cluster.go:1196-1561 + fragment.go:2436-2606
archive path) on a 1,024-shard (1.07B-column) index in an in-process
2->3->2 node cluster, recording wall time, memory, fragments moved,
and post-resize exactness against a deterministic oracle.

Prints one JSON line per phase:
  {"config": "resize-join", "cols": ..., "shards": ..., "wall_s": ...,
   "fragments_moved": ..., "rss_delta_mb": ..., "vm_hwm_mb": ...,
   "exact": true}

Run: PYTHONPATH=/root/repo:$PYTHONPATH python benchmarks/measure_resize.py
(CPU backend is fine — resize is a control-plane + host-IO path; no
device work is being measured.)
"""

from __future__ import annotations

import json
import sys
import tempfile
import time

from pilosa_tpu.models.holder import Holder  # noqa: E402
from pilosa_tpu.parallel.cluster import (  # noqa: E402
    Cluster,
    LocalTransport,
    Node,
)
from pilosa_tpu.parallel.node import ClusterNode  # noqa: E402
from pilosa_tpu.parallel.resize import Resizer  # noqa: E402
from pilosa_tpu.shardwidth import SHARD_WIDTH  # noqa: E402

N_SHARDS = 1024          # x 2^20 columns = 1.07B
BITS_PER_ROW = 1_000     # per shard; 2 rows -> ~2M set bits, real archives


def rss() -> tuple[int, int]:
    """(VmRSS, VmHWM) in bytes."""
    cur = hwm = 0
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS"):
                cur = int(line.split()[1]) * 1024
            elif line.startswith("VmHWM"):
                hwm = int(line.split()[1]) * 1024
    return cur, hwm


def fragment_count(node) -> int:
    total = 0
    for idx in node.holder.indexes.values():
        for f in idx.fields.values():
            for view in f.views.values():
                total += len(view.fragments)
    return total


def main() -> int:
    base = tempfile.mkdtemp(prefix="resize_bench_")
    transport = LocalTransport()
    node_ids = ["node0", "node1"]
    nodes = []
    for nid in node_ids:
        holder = Holder(f"{base}/{nid}")
        cluster = Cluster(nid, nodes=[Node(id=x) for x in node_ids],
                          replica_n=1, transport=transport)
        cluster.set_state("NORMAL")
        nodes.append(ClusterNode(holder, cluster))

    # ---- build the 1.07B-column index, fragments on their owners
    t0 = time.perf_counter()
    for nd in nodes:
        nd.holder.create_index("i").create_field("f")
    oracle_count = {0: N_SHARDS * BITS_PER_ROW, 1: N_SHARDS * BITS_PER_ROW}
    for nd in nodes:
        f = nd.holder.index("i").field("f")
        rows_l, cols_l = [], []
        for shard in range(N_SHARDS):
            owner = nd.cluster.shard_nodes("i", shard)[0].id
            if owner != nd.cluster.local_id:
                continue
            for row in (0, 1):
                # deterministic distinct offsets; row 1 shifted so the
                # intersection is exactly BITS_PER_ROW//2 per shard
                start = 0 if row == 0 else BITS_PER_ROW // 2
                for i in range(BITS_PER_ROW):
                    rows_l.append(row)
                    cols_l.append(shard * SHARD_WIDTH + start + i)
        f.import_bits(rows_l, cols_l)
        f.add_remote_available_shards(set(range(N_SHARDS)))
    build_s = time.perf_counter() - t0
    oracle_inter = N_SHARDS * (BITS_PER_ROW // 2)

    # settle: background compaction + prewarm must not pollute the
    # resize timing
    from pilosa_tpu.runtime import prewarm, snapqueue

    assert prewarm.drain(timeout=600), "prewarm still running"
    assert snapqueue.drain(timeout=600), "compaction still running"

    def check_exact(all_nodes) -> None:
        for nd in all_nodes:
            for row, want in oracle_count.items():
                got = nd.executor.execute("i", f"Count(Row(f={row}))")[0]
                assert got == want, (nd.cluster.local_id, row, got, want)
            got = nd.executor.execute(
                "i", "Count(Intersect(Row(f=0), Row(f=1)))")[0]
            assert got == oracle_inter, (nd.cluster.local_id, got)

    check_exact(nodes)
    out = []

    # ---- JOIN: node2 enters, jump hash re-homes ~1/3 of fragments
    holder2 = Holder(f"{base}/node2")
    cluster2 = Cluster("node2", nodes=[Node(id="node2")], replica_n=1,
                       transport=transport)
    joiner = ClusterNode(holder2, cluster2)
    rss0, _ = rss()
    t0 = time.perf_counter()
    resp = transport.send_message(
        nodes[0].cluster.local_node,
        {"type": "node-join", "node": {"id": "node2", "uri": ""}})
    join_s = time.perf_counter() - t0
    assert resp.get("ok"), resp
    for nd in (*nodes, joiner):
        assert nd.cluster.state == "NORMAL", nd.cluster.local_id
    rss1, hwm1 = rss()
    moved = fragment_count(joiner)
    assert moved > 0, "join moved nothing"
    check_exact([*nodes, joiner])
    out.append({"config": "resize-join", "cols": N_SHARDS * SHARD_WIDTH,
                "shards": N_SHARDS, "wall_s": round(join_s, 1),
                "fragments_moved": moved,
                "rss_delta_mb": round((rss1 - rss0) / 1e6, 1),
                "vm_hwm_mb": round(hwm1 / 1e6, 1),
                "build_s": round(build_s, 1), "exact": True})

    # ---- LEAVE: node2 exits, its fragments re-home to the survivors
    rss0, _ = rss()
    t0 = time.perf_counter()
    leave_res = Resizer(nodes[0]).run(remove_id="node2")
    leave_s = time.perf_counter() - t0
    for nd in nodes:
        assert nd.cluster.state == "NORMAL"
        assert len(nd.cluster.sorted_nodes()) == 2
    rss1, hwm1 = rss()
    check_exact(nodes)
    out.append({"config": "resize-leave", "cols": N_SHARDS * SHARD_WIDTH,
                "shards": N_SHARDS, "wall_s": round(leave_s, 1),
                "fragments_moved": leave_res["transfers"],
                "rss_delta_mb": round((rss1 - rss0) / 1e6, 1),
                "vm_hwm_mb": round(hwm1 / 1e6, 1), "exact": True})

    for rec in out:
        print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
