#!/usr/bin/env python
"""Measure the five BASELINE.json benchmark configs through the product
paths (PQL -> executor -> fused device dispatch), printing one JSON line
per config.

Run on the default backend:

    python benchmarks/measure.py

Configs (BASELINE.json):
  1. single-shard Count(Intersect(Row,Row)) QPS
  2. Union/Intersect/Difference latency over a multi-shard set field
  3. TopN(n=100) with BSI Range filter, p50 latency
  4. GroupBy + Sum over BSI int fields, p50 latency
  5. 3-node HTTP cluster Count QPS (scatter-gather over the wire)

Shapes scale DOWN off-TPU so the script stays interactive.
"""

from __future__ import annotations

import json
import os
import random
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np


def _now() -> float:
    return time.perf_counter()


def timed_qps(fn, min_iters: int = 20, min_time: float = 1.0):
    fn()  # warm-up / compile
    iters, t0 = 0, _now()
    while iters < min_iters or _now() - t0 < min_time:
        fn()
        iters += 1
    return iters / (_now() - t0)


def timed_qps_spread(fn, runs: int = 3, min_iters: int = 10,
                     min_time: float = 5.0) -> dict:
    """Closed-loop QPS with a variance bound: ``runs`` independent
    minimum-duration loops, reporting the median, every run, the
    run-to-run spread, and per-request p50/p95 latency.  One-shot
    unpinned loops drifted 186->404 QPS between round-2 runs (VERDICT
    round-2 weak #1) — a recorded figure needs its spread."""
    fn()  # warm-up / compile / connection establishment
    qps_runs: list[float] = []
    lats: list[float] = []
    for _ in range(runs):
        iters, t0 = 0, _now()
        while iters < min_iters or _now() - t0 < min_time:
            t1 = _now()
            fn()
            lats.append(_now() - t1)
            iters += 1
        qps_runs.append(iters / (_now() - t0))
    med = statistics.median(qps_runs)
    lats.sort()
    return {
        "value": round(med, 1),
        "runs": [round(q, 1) for q in qps_runs],
        "spread_pct": round((max(qps_runs) - min(qps_runs)) / med * 100, 1),
        "p50_ms": round(lats[len(lats) // 2] * 1e3, 2),
        "p95_ms": round(lats[min(len(lats) - 1, int(len(lats) * 0.95))] * 1e3,
                        2),
    }


def timed_p50_ms(fn, iters: int = 30):
    fn()  # warm-up / compile
    samples = []
    for _ in range(iters):
        t0 = _now()
        fn()
        samples.append((_now() - t0) * 1e3)
    return statistics.median(samples)


def build_index(holder, name: str, n_shards: int, rows_per_field: int,
                density_cols: int, seed: int):
    """An index with two set fields (f, g), an int field (v) and a
    time-quantum field (t), populated across n_shards shards."""
    from pilosa_tpu.models.field import FieldOptions
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    idx = holder.create_index(name)
    rng = random.Random(seed)
    for fname in ("f", "g"):
        f = idx.create_field(fname)
        rows, cols = [], []
        for row in range(rows_per_field):
            for _ in range(density_cols):
                s = rng.randrange(n_shards)
                cols.append(s * SHARD_WIDTH + rng.randrange(SHARD_WIDTH))
                rows.append(row)
        f.import_bits(rows, cols)
    v = idx.create_field("v", FieldOptions.int_field(0, 1 << 20))
    vcols = sorted({s * SHARD_WIDTH + rng.randrange(SHARD_WIDTH)
                    for s in range(n_shards) for _ in range(density_cols)})
    v.import_values(vcols, [rng.randrange(1 << 20) for _ in vcols])
    from pilosa_tpu.models.timequantum import parse_time

    t = idx.create_field("t", FieldOptions.time_field("YMDH"))
    trows, tcols, times = [], [], []
    for row in range(4):
        for _ in range(density_cols):
            s = rng.randrange(n_shards)
            trows.append(row)
            tcols.append(s * SHARD_WIDTH + rng.randrange(SHARD_WIDTH))
            times.append(parse_time(
                f"2019-0{1 + rng.randrange(9)}-15T0{rng.randrange(10)}:00"))
    t.import_bits(trows, tcols, timestamps=times)
    return idx


class _ConfigSkip(Exception):
    """One config declines to produce a number; the sweep records the
    reason and continues (no silent shrink, no dead artifact)."""


def main():
    import jax

    on_tpu = jax.devices()[0].platform == "tpu"
    n_shards = 64 if on_tpu else 16
    rows_per_field = 512 if on_tpu else 64
    density = 4096 if on_tpu else 512

    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.parallel.executor import Executor
    from pilosa_tpu.shardwidth import SHARD_WIDTH

    out = []

    bench_dir = tempfile.mkdtemp()
    holder = Holder(bench_dir + "/bench")
    build_index(holder, "b", n_shards, rows_per_field, density, seed=1)
    ex = Executor(holder)

    # ---- config 1: single-shard Count(Intersect) QPS
    q1 = "Count(Intersect(Row(f=1), Row(g=2)))"
    qps1 = timed_qps(lambda: ex.execute("b", q1, shards=[0]))
    out.append({"config": 1, "metric": "intersect_count_qps_1shard",
                "value": round(qps1, 1), "unit": "qps"})

    # ---- config 2: multi-shard set algebra latency
    q2 = "Count(Union(Row(f=1), Intersect(Row(f=2), Row(g=3)), Difference(Row(f=4), Row(g=5))))"
    p2 = timed_p50_ms(lambda: ex.execute("b", q2))
    out.append({"config": 2, "metric": "set_algebra_p50_ms",
                "value": round(p2, 2), "unit": "ms",
                "cols": n_shards * SHARD_WIDTH})

    # ---- config 2b: ≥1B-column index through the product path, with
    # the residency manager under genuine pressure.  1024 shards at the
    # default 2^20 shard width = 1.07B columns; each row stack is a
    # [1024, 32768] uint32 (128 MiB), and the budget below holds ~3 of
    # them, so cycling 6 rows evicts constantly while every count must
    # stay exact (the two-tier residency design of SURVEY.md §7's risk
    # register: eviction may cost warmth, never correctness).
    from pilosa_tpu.runtime import residency

    scale_shards = max(1024, -(-(1 << 30) // SHARD_WIDTH))  # >= 1.07B cols
    scale_cols = scale_shards * SHARD_WIDTH
    srng = random.Random(7)
    scale_bits: dict[int, set] = {}
    sidx = holder.create_index("scale")
    sf = sidx.create_field("f")
    rows_l: list[int] = []
    cols_l: list[int] = []
    prev: list[int] = []
    for row in range(6):
        cs = [srng.randrange(scale_cols) for _ in range(30_000)]
        cs += prev[:6_000]  # overlap with the previous row
        prev = cs
        scale_bits[row] = set(cs)
        rows_l += [row] * len(cs)
        cols_l += cs
    sf.import_bits(rows_l, cols_l)

    stack_bytes = scale_shards * (SHARD_WIDTH // 8)
    # shrink the budget on the LIVE manager: a reset() would orphan the
    # entries configs 1-2 already admitted (they would become untracked
    # and unevictable for the rest of the run)
    mgr = residency.manager()
    old_budget = mgr.budget
    old_sized = mgr.operator_sized
    mgr.budget = 3 * stack_bytes + stack_bytes // 2
    mgr.operator_sized = True
    try:
        ev0 = mgr.evictions
        lat = []
        for i in range(8):
            a, b = i % 5, i % 5 + 1
            t0 = _now()
            got = ex.execute("scale", f"Count(Intersect(Row(f={a}), Row(f={b})))")[0]
            lat.append((_now() - t0) * 1e3)
            want = len(scale_bits[a] & scale_bits[b])
            assert got == want, f"scale mismatch r{a}&r{b}: {got} != {want}"
        evictions = mgr.evictions - ev0
        assert evictions > 0, "budget never forced an eviction — not a thrash run"
    finally:
        # restore BOTH knobs for the configs below: a leaked
        # operator_sized=True relaxes per-entry cache caps to budget//4
        # and would silently change configs 3-5's caching policy
        mgr.budget = old_budget
        mgr.operator_sized = old_sized
    out.append({"config": 2, "metric": "intersect_count_p50_ms_1B_cols",
                "value": round(statistics.median(lat), 1), "unit": "ms",
                "cols": scale_cols, "evictions": evictions,
                "exact": True})
    holder.delete_index("scale")

    # ---- config 2c: the 10B-column north star (BASELINE.json target
    # shape), end-to-end through the product path.  9,537 shards at the
    # default width = 10.0B columns; each row stack is ~1.25 GB, so this
    # config is gated on available host memory (it needs ~8 GB headroom)
    # and runs the query loop at full scale.
    avail_kb = 0
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable"):
                    avail_kb = int(line.split()[1])
                    break
    except OSError:
        pass
    if avail_kb >= 16 * 1024 * 1024 and SHARD_WIDTH >= (1 << 20):
        ns_shards = -(-(10 * 10**9) // SHARD_WIDTH)  # ceil -> >= 10B cols
        ns_cols = ns_shards * SHARD_WIDTH
        nrng = random.Random(10)
        nidx = holder.create_index("northstar")
        nf = nidx.create_field("f")
        nbits: dict[int, set] = {0: set(), 1: set()}
        rows_l, cols_l = [], []
        for row in (0, 1):
            # >= 2 bits in EVERY shard so all 9,537 fragments exist,
            # plus a dense overlap slice so the intersection is nonzero
            for s in range(ns_shards):
                for _ in range(2):
                    c = s * SHARD_WIDTH + nrng.randrange(SHARD_WIDTH)
                    nbits[row].add(c)
                    rows_l.append(row)
                    cols_l.append(c)
        shared = [nrng.randrange(ns_cols) for _ in range(5_000)]
        for row in (0, 1):
            for c in shared:
                if c not in nbits[row]:
                    nbits[row].add(c)
                    rows_l.append(row)
                    cols_l.append(c)
        # a deployment serving a 10B-column index sizes its memory for
        # the working set (two ~1.25 GB row stacks) BEFORE loading —
        # the budget must be in place when the import-triggered prewarm
        # runs, or it gates itself off
        mgr10 = residency.manager()
        old10 = mgr10.budget
        old10_sized = mgr10.operator_sized
        mgr10.budget = max(old10, 8 << 30)
        mgr10.operator_sized = True
        try:
            t0 = _now()
            nf.import_bits(rows_l, cols_l)
            import_s = _now() - t0
            # the import queued a background stack prewarm; wait it out
            # so "cold_ms" below measures what a first query actually
            # sees on a settled server (prewarm.py).  The un-prewarmed
            # floor is measured separately after the warm loop.
            from pilosa_tpu.runtime import prewarm, snapqueue

            t0 = _now()
            if not (prewarm.drain(timeout=300.0)
                    and snapqueue.drain(timeout=300.0)):
                # never crash the sweep: a drain that can't settle
                # becomes a skip record, not a dead artifact
                raise _ConfigSkip("background prewarm/compaction did "
                                  "not settle in 300 s")
            prewarm_s = _now() - t0
            q_ns = "Count(Intersect(Row(f=0), Row(f=1)))"
            t0 = _now()
            got = ex.execute("northstar", q_ns)[0]
            cold_ms = (_now() - t0) * 1e3
            lat = []
            for _ in range(3):
                t0 = _now()
                got = ex.execute("northstar", q_ns)[0]
                lat.append((_now() - t0) * 1e3)
            # TopN p50 at the north-star scale (BASELINE.json tracks
            # it alongside the count): the full stacked row scan over
            # the same warm 10B-column stacks, exact counts asserted
            tn_lat = []
            for _ in range(3):
                t0 = _now()
                pairs = ex.execute("northstar", "TopN(f)")[0]
                tn_lat.append((_now() - t0) * 1e3)
            got_tn = [(p.id, p.count) for p in pairs]
            want_tn = sorted(
                ((r, len(nbits[r])) for r in (0, 1)),
                key=lambda rc: (-rc[1], rc[0]))
            # verified in the else-branch below, where a mismatch
            # becomes a loud correctness_failure record instead of an
            # AssertionError that kills the whole sweep
            # AE leg at the north star: one block-checksum-only pass
            # over every 10B fragment — the per-cycle hashing floor a
            # holderSyncer pays before any wire traffic (reference
            # holder.go:880, fragment.go:1762 Checksum; cadence 10 min,
            # server.go:514)
            t0 = _now()
            ae_blocks = 0
            ae_bytes = 0
            for vw in nf.views.values():
                for fr in vw.fragments.values():
                    ae_blocks += len(fr.blocks())
                    ae_bytes += sum(fr._rows[r].nbytes
                                    for r in fr.row_ids()) + 8 * len(
                                        fr.row_ids())
            ae_checksum_s = _now() - t0
            # documented floor: evict the row stacks and pay the full
            # assembly on a quiet system (no compaction running) — what
            # a query sees if eviction or a disabled prewarm leaves it
            # cold
            for key in list(nf._row_stack_cache):
                residency.manager().forget(nf._row_stack_cache, key)
            nf._row_stack_cache.clear()
            t0 = _now()
            got_floor = ex.execute("northstar", q_ns)[0]
            floor_ms = (_now() - t0) * 1e3
        except _ConfigSkip as e:
            out.append({"config": 2,
                        "metric": "intersect_count_p50_ms_10B_cols",
                        "skipped": True, "reason": str(e)})
            holder.delete_index("northstar")
        else:
            # A correctness mismatch must be LOUD but must not kill the
            # sweep: configs 1-2's collected numbers and configs 3-5
            # still have to reach the artifact, and the ~2.5 GB index
            # still has to be deleted — so the violation becomes an
            # explicit correctness_failure record, never a dead run
            # (the same doctrine as the skip records above).
            want = len(nbits[0] & nbits[1])
            failures = []
            if got != want:
                failures.append(f"north-star count {got} != {want}")
            if got_floor != want:
                failures.append(f"floor count {got_floor} != {want}")
            if got_tn != want_tn:
                failures.append(f"TopN {got_tn} != {want_tn}")
            if failures:
                out.append({"config": 2,
                            "metric": "intersect_count_p50_ms_10B_cols",
                            "correctness_failure": "; ".join(failures)})
            else:
                out.append({
                    "config": 2,
                    "metric": "intersect_count_p50_ms_10B_cols",
                    "value": round(statistics.median(lat), 1),
                    "unit": "ms",
                    "cols": ns_cols, "shards": ns_shards,
                    "cold_ms": round(cold_ms, 1),
                    "prewarm_s": round(prewarm_s, 1),
                    "cold_floor_no_prewarm_ms": round(floor_ms, 1),
                    "topn_p50_ms": round(statistics.median(tn_lat), 1),
                    "import_s": round(import_s, 1), "exact": True})
                out.append({
                    "config": 7,
                    "metric": "ae_checksum_pass_s_10B_cols",
                    "value": round(ae_checksum_s, 2), "unit": "s",
                    "cols": ns_cols, "shards": ns_shards,
                    "blocks": ae_blocks,
                    "mb_hashed": round(ae_bytes / 1e6, 1)})
            holder.delete_index("northstar")
        finally:
            mgr10.budget = old10
            mgr10.operator_sized = old10_sized
    else:
        # a gated config must leave a record, never silently shrink the
        # artifact (VERDICT round-2 weak #6)
        reasons = []
        if avail_kb < 16 * 1024 * 1024:
            reasons.append(f"MemAvailable {avail_kb / (1 << 20):.1f} GiB "
                           f"< 16 GiB required")
        if SHARD_WIDTH < (1 << 20):
            reasons.append(f"SHARD_WIDTH {SHARD_WIDTH} < 2^20 (bench shape "
                           f"assumes default width)")
        out.append({"config": 2, "metric": "intersect_count_p50_ms_10B_cols",
                    "skipped": True, "reason": "; ".join(reasons)})

    # ---- config 3: TopN(n=100) with BSI range filter p50
    q3 = "TopN(f, Row(v > 524288), n=100)"
    p3 = timed_p50_ms(lambda: ex.execute("b", q3))
    out.append({"config": 3, "metric": "topn_bsi_filter_p50_ms",
                "value": round(p3, 2), "unit": "ms",
                "rows": rows_per_field})
    # time-quantum range form
    q3b = "TopN(t, n=100)"
    p3b = timed_p50_ms(lambda: ex.execute("b", q3b))
    out.append({"config": 3, "metric": "topn_time_field_p50_ms",
                "value": round(p3b, 2), "unit": "ms"})

    # ---- config 4: GroupBy + Sum p50
    q4 = "GroupBy(Rows(f), Rows(g), filter=Row(v > 262144))"
    # cap the walk: rows_per_field^2 groups is the worst case
    p4 = timed_p50_ms(lambda: ex.execute("b", q4, shards=None), iters=10)
    out.append({"config": 4, "metric": "groupby_filtered_p50_ms",
                "value": round(p4, 2), "unit": "ms",
                "groups_max": rows_per_field * rows_per_field})
    q4b = "Sum(Row(f=1), field=v)"
    p4b = timed_p50_ms(lambda: ex.execute("b", q4b))
    out.append({"config": 4, "metric": "sum_filtered_p50_ms",
                "value": round(p4b, 2), "unit": "ms"})

    holder.close()
    # Quiesce before the latency benchmark: the scale configs above
    # wrote multi-GB of snapshots whose dirty pages would otherwise
    # write back DURING config 5's closed loop and collapse a run on a
    # one-core box (observed: 442 -> 12.7 QPS across runs).  Deleting
    # the tree drops the dirty pages instead of flushing them; sync
    # settles whatever remains.
    shutil.rmtree(bench_dir, ignore_errors=True)
    os.sync()

    # ---- config 5: 3-node HTTP cluster Count QPS
    from pilosa_tpu.server.client import InternalClient
    from pilosa_tpu.server.server import Server

    base = tempfile.mkdtemp()
    s0 = Server(data_dir=f"{base}/n0", coordinator=True); s0.open()
    s1 = Server(data_dir=f"{base}/n1", seeds=[s0.uri]); s1.open()
    s2 = Server(data_dir=f"{base}/n2", seeds=[s0.uri]); s2.open()

    # a keep-alive client, like any real driver (and the reference's
    # closed-loop benchmark clients)
    client = InternalClient(timeout=120)

    def post(path, obj):
        return client.post_json(s0.uri + path, obj)

    post("/index/c", {})
    post("/index/c/field/f", {})
    rng = random.Random(2)
    rows, cols = [], []
    for row in range(8):
        for _ in range(density):
            s = rng.randrange(9)
            rows.append(row)
            cols.append(s * SHARD_WIDTH + rng.randrange(SHARD_WIDTH))
    post("/index/c/field/f/import", {"rowIDs": rows, "columnIDs": cols})
    q5 = {"query": "Count(Intersect(Row(f=1), Row(f=2)))"}
    spread5 = timed_qps_spread(lambda: post("/index/c/query", q5))
    out.append({"config": 5, "metric": "cluster3_count_qps_http",
                "unit": "qps", **spread5})

    # ---- config 6: write path — single-Set latency and bulk-import
    # throughput (the reference's headline ingest paths: executeSet,
    # executor.go:2067, and fragment.bulkImport, fragment.go:1997).
    # Reuses the 3-node cluster: every Set replicates synchronously to
    # all shard owners, so this measures the real write pipeline (WAL
    # append + replica POST), not a single-map update.
    rng6 = random.Random(6)
    set_lat = []
    for i in range(300):
        col = rng6.randrange(9 * SHARD_WIDTH)
        q = {"query": f"Set({col}, f={100 + (i % 8)})"}
        t0 = _now()
        post("/index/c/query", q)
        set_lat.append((_now() - t0) * 1e3)
    set_lat.sort()
    out.append({"config": 6, "metric": "set_write_p50_ms_replicated",
                "value": round(set_lat[len(set_lat) // 2], 2),
                "unit": "ms",
                "p95_ms": round(set_lat[int(len(set_lat) * 0.95)], 2),
                "writes": len(set_lat)})

    n_bits = 2_000_000
    rows6 = [rng6.randrange(64) for _ in range(n_bits)]
    cols6 = [rng6.randrange(9 * SHARD_WIDTH) for _ in range(n_bits)]
    t0 = _now()
    post("/index/c/field/f/import", {"rowIDs": rows6,
                                     "columnIDs": cols6})
    dt = _now() - t0
    got6 = post("/index/c/query",
                {"query": "Count(Union(" + ", ".join(
                    f"Row(f={r})" for r in range(8)) + "))"})["results"][0]
    # exact oracle over everything this sweep put into rows 0-7: the
    # config-5 import plus this bulk import (Set() wrote rows 100-107)
    want6_set = ({c for r, c in zip(rows, cols) if r < 8}
                 | {c for r, c in zip(rows6, cols6) if r < 8})
    want6 = len(want6_set)
    rec6 = {"config": 6, "metric": "bulk_import_mbits_per_s_json",
            "value": round(n_bits / dt / 1e6, 2),
            "unit": "Mbits/s", "bits": n_bits,
            "wall_s": round(dt, 1), "exact": got6 == want6}
    if got6 != want6:
        rec6["correctness_failure"] = f"union count {got6} != {want6}"
    out.append(rec6)

    # Same bulk import over the protobuf wire form (the reference's
    # CSV importer posts ImportRequest protobufs, ctl/import.go:34-350;
    # the JSON figure above is dominated by 2M-element JSON encoding)
    from pilosa_tpu import proto as _proto

    rows6b = [rng6.randrange(64) for _ in range(n_bits)]
    cols6b = [rng6.randrange(9 * SHARD_WIDTH) for _ in range(n_bits)]
    body6 = _proto.encode(_proto.IMPORT_REQUEST, {
        "index": "c", "field": "f", "shard": 0,
        "rowIDs": rows6b, "columnIDs": cols6b})
    t0 = _now()
    client._request(
        "POST", s0.uri + "/index/c/field/f/import", body6,
        ctype="application/x-protobuf")
    dtb = _now() - t0
    got6b = post("/index/c/query",
                 {"query": "Count(Union(" + ", ".join(
                     f"Row(f={r})" for r in range(8)) + "))"})["results"][0]
    want6b = len(want6_set | {c for r, c in zip(rows6b, cols6b) if r < 8})
    rec6b = {"config": 6, "metric": "bulk_import_mbits_per_s_proto",
             "value": round(n_bits / dtb / 1e6, 2),
             "unit": "Mbits/s", "bits": n_bits,
             "wall_s": round(dtb, 1), "exact": got6b == want6b}
    if got6b != want6b:
        rec6b["correctness_failure"] = f"union count {got6b} != {want6b}"
    out.append(rec6b)

    # The import-roaring fast path (reference api.go:368 ImportRoaring
    # -> roaring.ImportRoaringBits, roaring/roaring.go:1511 — its
    # fastest ingest).  Payloads are PRE-ENCODED per shard (matching
    # the reference benchmark shape: the server-side rate is what's
    # measured).  Two densities: the protobuf row's sparse 2M-random
    # shape (worst case for bitmap merge — ~1 bit per 64-bit word),
    # and a 10x-denser bulk-load shape where container merges amortize.
    from pilosa_tpu.storage import roaring as _rcodec

    for label, nb, row0 in (("sparse", n_bits, 200),
                            ("dense", 10 * n_bits, 300)):
        rng_r = np.random.default_rng(7 + nb)
        rows_r = rng_r.integers(row0, row0 + 64, nb, dtype=np.int64)
        cols_r = rng_r.integers(0, 9 * SHARD_WIDTH, nb, dtype=np.int64)
        shard_r = cols_r // SHARD_WIDTH
        pos_r = (rows_r * SHARD_WIDTH
                 + (cols_r % SHARD_WIDTH)).astype(np.uint64)
        payloads = {}
        uniq_total = 0
        for s in range(9):
            u = np.unique(pos_r[shard_r == s])
            uniq_total += len(u)
            k_, w_ = _rcodec.positions_to_containers(u)
            payloads[s] = _rcodec.encode(k_, w_)
        wire_b = sum(len(v) for v in payloads.values())
        t0 = _now()
        for s, data in payloads.items():
            client.import_roaring(s0.uri, "c", "f", s, data)
        dtr = _now() - t0
        got_r = post("/index/c/query", {"query": "Count(Union("
                     + ", ".join(f"Row(f={r})"
                                 for r in range(row0, row0 + 64))
                     + "))"})["results"][0]
        want_r = len(np.unique(cols_r))
        rec_r = {"config": 6,
                 "metric": f"import_roaring_mbits_per_s_{label}",
                 "value": round(uniq_total / dtr / 1e6, 2),
                 "unit": "Mbits/s", "bits": uniq_total,
                 "wire_mb_per_s": round(wire_b / dtr / 1e6, 1),
                 "wall_s": round(dtr, 2), "exact": got_r == want_r}
        if got_r != want_r:
            rec_r["correctness_failure"] = \
                f"union count {got_r} != {want_r}"
        out.append(rec_r)

    client.close()
    s0.close(); s1.close(); s2.close()

    # ---- config 7: anti-entropy cycle cost at scale (VERDICT r4 item
    # 4; reference holderSyncer holder.go:880-1101, 10-min cadence
    # server.go:514).  Fresh replica-2 cluster so blocks actually have
    # two owners; AE loops disabled — cycles run by hand, timed.
    # Leg A: in-sync full SyncHolder cycle over a wide index (wall +
    #   bytes hashed: the steady-state cost of "nothing to do").
    # Leg B: one replica diverges (direct local import bypassing
    #   replication); the next cycle must move ONLY the diff and every
    #   node must answer exactly afterwards.
    ae_shards = 1024 if avail_kb >= 8 * 1024 * 1024 else 128
    base7 = tempfile.mkdtemp()
    a0 = Server(data_dir=f"{base7}/n0", coordinator=True, replica_n=2)
    a0.open()
    a1 = Server(data_dir=f"{base7}/n1", seeds=[a0.uri], replica_n=2)
    a1.open()
    a2 = Server(data_dir=f"{base7}/n2", seeds=[a0.uri], replica_n=2)
    a2.open()
    cl7 = InternalClient(timeout=300)

    def post7(path, obj):
        return cl7.post_json(a0.uri + path, obj)

    # replica-2 writes need all three members up before the import; a
    # cluster that never forms becomes a skip record, never a run
    # against a partial cluster (which would record false divergence)
    deadline = _now() + 120
    ready = False
    while _now() < deadline:
        st = cl7._json("GET", a0.uri + "/status")
        if st.get("state") == "NORMAL" and len(st.get("nodes", [])) == 3:
            ready = True
            break
        time.sleep(0.2)
    if not ready:
        out.append({"config": 7, "metric": "ae_sync_cycle_s_insync",
                    "skipped": True,
                    "reason": "3-node replica-2 cluster never reached "
                              "NORMAL within 120 s"})
        cl7.close()
        a0.close(); a1.close(); a2.close()
        shutil.rmtree(base7, ignore_errors=True)
        return _emit(out)

    post7("/index/ae", {})
    post7("/index/ae/field/f", {})
    arng = random.Random(77)
    rows_l, cols_l = [], []
    for row in range(4):
        for s in range(ae_shards):
            for _ in range(2):
                rows_l.append(row)
                cols_l.append(s * SHARD_WIDTH + arng.randrange(SHARD_WIDTH))
    post7("/index/ae/field/f/import", {"rowIDs": rows_l,
                                       "columnIDs": cols_l})

    from pilosa_tpu.parallel.syncer import HolderSyncer

    def hashed_mb(server):
        total = 0
        idx = server.holder.index("ae")
        for f in idx.all_fields():
            for vw in f.views.values():
                for fr in vw.fragments.values():
                    if server.cluster.owns_shard(
                            server.cluster.local_id, "ae", fr.shard):
                        total += sum(fr._rows[r].nbytes
                                     for r in fr.row_ids())
        return total / 1e6

    t0 = _now()
    dirty_a = HolderSyncer(a0.node).sync_holder()
    wall_a = _now() - t0
    rec7 = {"config": 7, "metric": "ae_sync_cycle_s_insync",
            "value": round(wall_a, 2), "unit": "s",
            "cols": ae_shards * SHARD_WIDTH, "shards": ae_shards,
            "dirty_blocks": dirty_a,
            "local_mb_hashed": round(hashed_mb(a0), 1)}
    if dirty_a:
        rec7["correctness_failure"] = \
            f"{dirty_a} dirty blocks on an in-sync cluster"
    out.append(rec7)

    # Leg B — diverge one replica: bits land on a1 only (local import,
    # no replication), on shards a1 owns; AE must push them everywhere.
    div_shards = [s for s in range(ae_shards)
                  if a1.cluster.owns_shard(a1.cluster.local_id, "ae", s)][:8]
    div_want = 0
    for s in div_shards:
        frag = a1.node.local_fragment("ae", "f", "standard", s, True)
        frag.import_positions(
            [9 * SHARD_WIDTH + arng.randrange(SHARD_WIDTH)
             for _ in range(125)])
        div_want += frag.row_count(9)
    t0 = _now()
    dirty_b = HolderSyncer(a1.node).sync_holder()
    wall_b = _now() - t0
    got_counts = []
    for srv in (a0, a1, a2):
        got_counts.append(cl7.post_json(
            srv.uri + "/index/ae/query",
            {"query": "Count(Row(f=9))"})["results"][0])
    rec7b = {"config": 7, "metric": "ae_sync_cycle_s_diverged",
             "value": round(wall_b, 2), "unit": "s",
             "diverged_shards": len(div_shards),
             "diverged_bits": div_want,
             "dirty_blocks": dirty_b,
             "exact": all(g == div_want for g in got_counts)}
    if not rec7b["exact"]:
        rec7b["correctness_failure"] = \
            f"post-AE counts {got_counts} != {div_want}"
    out.append(rec7b)

    cl7.close()
    a0.close(); a1.close(); a2.close()
    shutil.rmtree(base7, ignore_errors=True)

    return _emit(out)


def _emit(out):
    import jax

    platform = jax.devices()[0].platform
    for rec in out:
        rec["platform"] = platform
        print(json.dumps(rec))


if __name__ == "__main__":
    sys.exit(main())
