#!/usr/bin/env python
"""Collective-plane vs scatter-plane latency measurement.

The SPMD collective plane (parallel/spmd.py) has long-haul CORRECTNESS
evidence (tools/soak_spmd.py); this records its PERFORMANCE envelope
against the scatter plane on the same cluster and dataset — per-query
p50/p95 latency over real OS processes, every answer cross-checked
between planes before anything is timed.

What each plane pays per query:
  - scatter: the origin fans sub-queries to every owner over HTTP and
    reduces (reference executor.go:2455's shape) — N-1 HTTP round
    trips, results ride the wire;
  - collective: every process enters one jitted program over the
    global mesh in lockstep; coordination is a tiny prepare broadcast
    on the control plane, data never leaves device order.

On this one-core CI box all processes share one core, so collective
numbers carry the serialization of P processes' compute — the record
is an honest protocol-overhead envelope, not an ICI scaling claim
(that needs real multi-host hardware).

Usage: python benchmarks/measure_spmd.py [--procs 2] [--reps 40]
Prints one JSON line per (query, plane-pair) plus a summary line.

The fleet scaffolding (file barrier, port allocation, spawn with
kill-the-whole-fleet-on-timeout) is shared with tools/soak_spmd.py via
tools/fleet_lib.py — change the discipline THERE, once.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r'''
import json, os, random, statistics, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)

from pilosa_tpu.parallel import multihost, spmd
from pilosa_tpu.pql import parse
from pilosa_tpu.server.server import Server
from pilosa_tpu.server.client import InternalClient
from pilosa_tpu.shardwidth import SHARD_WIDTH

multihost.initialize()
pid = jax.process_index()
NPROC = int(os.environ["JAX_NUM_PROCESSES"])
ports = [int(os.environ[f"T_PORT{i}"]) for i in range(NPROC)]
data = os.environ["T_DATA"]
REPS = int(os.environ["M_REPS"])
SEED = int(os.environ["M_SEED"])
N_SHARDS = 8
VMIN, VMAX = -10000, 100000

if pid == 0:
    srv = Server(data + "/n0", port=ports[0], name="n0", coordinator=True)
else:
    srv = Server(data + f"/n{pid}", port=ports[pid], name=f"n{pid}",
                 seeds=[f"http://127.0.0.1:{ports[0]}"])
srv.open()
c = InternalClient(timeout=120)

deadline = time.monotonic() + 60
while len(srv.cluster.sorted_nodes()) < NPROC:
    if time.monotonic() > deadline:
        raise SystemExit("join timeout")
    time.sleep(0.05)
spmd.verify_rank_convention(srv.cluster)


from tools import fleet_lib as _fl
from tools.fleet_lib import file_barrier


def barrier(name, timeout=600):
    file_barrier(data, name, pid, NPROC, timeout)


# ---- deterministic dataset, identical in every process ----
rng = random.Random(SEED)
bits = {}
for fi in range(3):
    for row in range(8):
        bits[(f"f{fi}", row)] = {
            rng.randrange(N_SHARDS * SHARD_WIDTH) for _ in range(2000)}
vcols = sorted({rng.randrange(N_SHARDS * SHARD_WIDTH)
                for _ in range(5000)})
vals = {cc: rng.randrange(VMIN, VMAX) for cc in vcols}

if pid == 0:
    post = lambda p, o: c.post_json(srv.uri + p, o)
    post("/index/i", {})
    for fi in range(3):
        post(f"/index/i/field/f{fi}", {})
        rows_l, cols_l = [], []
        for row in range(8):
            cs = sorted(bits[(f"f{fi}", row)])
            rows_l += [row] * len(cs)
            cols_l += cs
        post(f"/index/i/field/f{fi}/import",
             {"rowIDs": rows_l, "columnIDs": cols_l})
    post("/index/i/field/v",
         {"options": {"type": "int", "min": VMIN, "max": VMAX}})
    post("/index/i/field/v/import-value",
         {"columnIDs": vcols, "values": [vals[cc] for cc in vcols]})

want0 = len(bits[("f0", 0)])
end = time.monotonic() + 180
while True:
    try:
        got = c.post_json(srv.uri + "/index/i/query",
                          {"query": "Count(Row(f0=0))"})["results"][0]
        if got == want0:
            break
    except Exception:
        pass
    if time.monotonic() > end:
        raise SystemExit("data visibility timeout")
    time.sleep(0.1)
barrier("loaded")

ce = spmd.CollectiveExecutor(srv.holder, srv.cluster, "i")

QUERIES = [
    ("count_tree",
     "Count(Intersect(Row(f0=0), Union(Row(f1=1), Row(f2=2))))"),
    ("bsi_condition", "Count(Row(v > 40000))"),
    ("sum_filtered", "Sum(Row(f0=1), field=v)"),
    ("topn", "TopN(f0)"),
    ("groupby_2child", "GroupBy(Rows(f0), Rows(f1))"),
    # round-4 additions: the ordinary-read surface
    ("bare_row", "Row(f0=0)"),
    ("bare_union", "Union(Row(f0=0), Row(f1=1))"),
    ("groupby_4child", "GroupBy(Rows(f0), Rows(f1), Rows(f2), Rows(f0))"),
    ("rows", "Rows(f0)"),
    ("minrow", "MinRow(field=f0)"),
]


# plane-comparable normalization is SHARED with the SPMD soak
# (tools/fleet_lib.norm_result / norm_http_result) so the two
# harnesses' cross-check conventions can never drift
norm = _fl.norm_result


def norm_http(name, raw):
    return _fl.norm_http_result(raw)


out = []
for name, q in QUERIES:
    call = parse(q).calls[0]
    assert ce.supported(call), f"{name} not collective-supported"

    # warm both planes (compile + stack build), then CROSS-CHECK the
    # answers before timing anything
    coll = ce.execute(q)
    barrier(f"warm.{name}")
    if pid == 0:
        raw = c.post_json(srv.uri + "/index/i/query",
                          {"query": q})["results"][0]
        assert norm(coll) == norm_http(name, raw), (
            name, norm(coll), norm_http(name, raw))
    # peers MUST idle at a control-plane barrier while the coordinator
    # scatter-queries: a peer that advanced into the collective timing
    # loop parks its devices, the scatter sub-query to that peer can't
    # be served, and the fleet deadlocks (the spmd plane's documented
    # rule: barriers gating collective entry ride the control plane)
    barrier(f"xchk.{name}")

    # collective plane: every process runs the identical rep sequence
    # in lockstep; the coordinator records per-rep wall time
    lat_c = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        ce.execute(q)
        lat_c.append(time.perf_counter() - t0)
    barrier(f"coll.{name}")

    # scatter plane: coordinator posts over HTTP, peers idle/serving
    lat_s = []
    if pid == 0:
        for _ in range(REPS):
            t0 = time.perf_counter()
            c.post_json(srv.uri + "/index/i/query", {"query": q})
            lat_s.append(time.perf_counter() - t0)
    barrier(f"scat.{name}")

    if pid == 0:
        qs = lambda xs, p: statistics.quantiles(xs, n=100)[p - 1] * 1e3
        out.append({
            "query": name,
            "collective_p50_ms": round(qs(lat_c, 50), 2),
            "collective_p95_ms": round(qs(lat_c, 95), 2),
            "scatter_p50_ms": round(qs(lat_s, 50), 2),
            "scatter_p95_ms": round(qs(lat_s, 95), 2),
            "reps": REPS,
        })

barrier("done")
c.close(); srv.close()
if pid == 0:
    print("RESULT " + json.dumps(out))
'''


sys.path.insert(0, REPO)
from tools.fleet_lib import free_ports, run_fleet  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--procs", type=int, default=2)
    ap.add_argument("--reps", type=int, default=40)
    ap.add_argument("--seed", type=int, default=12348)
    args = ap.parse_args()

    n = args.procs
    with tempfile.TemporaryDirectory() as data:
        coord_port, *http_ports = free_ports(1 + n)
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
            "JAX_NUM_PROCESSES": str(n),
            "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{coord_port}",
            "T_DATA": data,
            "M_REPS": str(args.reps),
            "M_SEED": str(args.seed),
            # On a one-core box, P concurrent XLA compiles can starve a
            # worker's coordination heartbeat past the 100 s default and
            # the runtime fail-stops the fleet (observed at procs=3) —
            # the measurement needs the fleet to survive its own compile
            # storm, so widen the window unless the caller pinned one.
            "PILOSA_TPU_DIST_HEARTBEAT_S": os.environ.get(
                "PILOSA_TPU_DIST_HEARTBEAT_S", "600"),
            "PILOSA_TPU_SHARD_WIDTH_EXP": os.environ.get(
                "PILOSA_TPU_SHARD_WIDTH_EXP", "16"),
        }
        for i, p in enumerate(http_ports):
            env[f"T_PORT{i}"] = str(p)
        ok, outs, _timed_out = run_fleet(
            [[sys.executable, "-u", "-c", WORKER] for _ in range(n)],
            [{**env, "JAX_PROCESS_ID": str(i)} for i in range(n)],
            timeout=900, label="measure_spmd", cwd=REPO)
        if not ok:
            return 1
        for line in outs[0].splitlines():
            if line.startswith("RESULT "):
                rows = json.loads(line[len("RESULT "):])
                for row in rows:
                    print(json.dumps({
                        "metric": "spmd_plane_latency",
                        "procs": n,
                        **row,
                    }))
                return 0
        sys.stderr.write("no RESULT line from coordinator\n"
                         + outs[0][-3000:] + "\n")
        return 1


if __name__ == "__main__":
    raise SystemExit(main())
