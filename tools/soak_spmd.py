#!/usr/bin/env python
"""Multi-process collective-plane soak: randomized writes + collective
reads across N full server processes, every answer checked.

The CI tier (tests/test_spmd.py multi-process leg) proves the protocol
once; this soak runs it for MINUTES with randomized workloads — the
long-haul evidence that the SPMD plane holds exactness and liveness
under churn (the single-process analog, tools/soak.py, caught a real
stale-cache bug; this is its distributed sibling).

Per round (all processes in lockstep, file barriers on the control
plane — never a jax collective, which would deadlock against serving):
  1. the coordinator applies K randomized writes (Set/Clear/BSI Set)
     through its HTTP API; EVERY process updates the identical Python
     oracle from the shared per-round rng;
  2. every process enters M randomized collective queries in the same
     order (Count trees, BSI conditions, Sum/Min/Max, TopN args,
     GroupBy 1-3 children); the coordinator asserts each against the
     oracle;
  3. every 5th round the coordinator re-asks a sample through the HTTP
     scatter plane (peers idle, serving) and asserts plane agreement.

Usage: python tools/soak_spmd.py [--seconds 600] [--procs 2]
Prints one JSON summary line; exit 0 = zero divergence, zero deadlock.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools import fleet_lib  # noqa: E402

WORKER = r'''
import json, os, random, sys, time
from tools import fleet_lib as _fl
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
SOAK_S = float(os.environ["SOAK_SECONDS"])
SEED = int(os.environ["SOAK_SEED"])
N_SHARDS = 6
VMIN, VMAX = -1000, 100000

if pid == 0:
    srv = Server(data + "/n0", port=ports[0], name="n0", coordinator=True)
else:
    srv = Server(data + f"/n{pid}", port=ports[pid], name=f"n{pid}",
                 seeds=[f"http://127.0.0.1:{ports[0]}"])
srv.open()
c = InternalClient(timeout=60)

deadline = time.monotonic() + 60
while len(srv.cluster.sorted_nodes()) < NPROC:
    if time.monotonic() > deadline:
        raise SystemExit("join timeout")
    time.sleep(0.05)
spmd.verify_rank_convention(srv.cluster)


from tools.fleet_lib import file_barrier


def barrier(name, timeout=300):
    file_barrier(data, name, pid, NPROC, timeout)


# ---- deterministic base dataset (identical in every process) ----
rng = random.Random(SEED)
bits = {}     # (field, row) -> set of cols
exists = set()
for fi in range(3):
    for row in range(5):
        cols = {rng.randrange(N_SHARDS * SHARD_WIDTH) for _ in range(150)}
        bits[(f"f{fi}", row)] = cols
        exists |= cols
vcols = sorted({rng.randrange(N_SHARDS * SHARD_WIDTH) for _ in range(400)})
vals = {cc: rng.randrange(VMIN, VMAX) for cc in vcols}
exists |= set(vcols)

if pid == 0:
    post = lambda p, o: c.post_json(srv.uri + p, o)
    post("/index/i", {})
    for fi in range(3):
        post(f"/index/i/field/f{fi}", {})
        rows_l, cols_l = [], []
        for row in range(5):
            cs = bits[(f"f{fi}", row)]
            rows_l += [row] * len(cs)
            cols_l += sorted(cs)
        post(f"/index/i/field/f{fi}/import",
             {"rowIDs": rows_l, "columnIDs": cols_l})
    post("/index/i/field/v",
         {"options": {"type": "int", "min": VMIN, "max": VMAX}})
    post("/index/i/field/v/import-value",
         {"columnIDs": vcols, "values": [vals[cc] for cc in vcols]})

# visibility barrier: scatter plane sees the data
want0 = len(bits[("f0", 0)])
end = time.monotonic() + 120
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


def gen_tree(r, depth):
    if depth == 0 or r.random() < 0.45:
        fi, row = r.randrange(3), r.randrange(5)
        return (f"Row(f{fi}={row})", bits[(f"f{fi}", row)])
    op = r.choice(["Union", "Intersect", "Difference", "Xor"])
    parts = [gen_tree(r, depth - 1) for _ in range(r.randrange(2, 4))]
    sets = [p[1] for p in parts]
    if op == "Union":
        acc = set().union(*sets)
    elif op == "Intersect":
        acc = sets[0]
        for s in sets[1:]:
            acc = acc & s
    elif op == "Difference":
        acc = sets[0]
        for s in sets[1:]:
            acc = acc - s
    else:
        acc = sets[0]
        for s in sets[1:]:
            acc = acc ^ s
    return (f"{op}({', '.join(p[0] for p in parts)})", acc)


import operator as _op
CMPS = {"<": _op.lt, "<=": _op.le, ">": _op.gt, ">=": _op.ge,
        "==": _op.eq, "!=": _op.ne}


def gen_query(r):
    """-> (pql, oracle_fn) — oracle_fn() computed lazily AFTER this
    round's writes land in the shared state."""
    kind = r.randrange(11)
    if kind == 10:
        # Options(shards=[...]) restricts the plan (late round 4):
        # oracle filters the column universe to the chosen shards
        text, acc = gen_tree(r, 2)
        ss = sorted(r.sample(range(N_SHARDS), r.randrange(1, N_SHARDS)))
        return (f"Options(Count({text}), shards={ss})",
                lambda a=acc, s=frozenset(ss): sum(
                    1 for c in a if c // SHARD_WIDTH in s),
                "count")
    if kind == 8:
        # bare bitmap tree: the global Row gathers replicated (round 4)
        text, acc = gen_tree(r, 2)
        return text, (lambda a=acc: sorted(a)), "row"
    if kind == 9:
        text, acc = gen_tree(r, 1)
        return (f"Not({text})",
                lambda a=acc: sorted(exists - a), "row")
    if kind == 7:
        # Not rides the existence field: oracle = every column ever
        # Set/imported minus the subtree (Clear never clears _exists,
        # matching the product semantics)
        text, acc = gen_tree(r, 1)
        return (f"Count(Not({text}))",
                lambda a=acc: len(exists - a), "count")
    if kind == 0:
        text, acc = gen_tree(r, 2)
        return f"Count({text})", (lambda a=acc: len(a)), "count"
    if kind == 1:
        o = r.choice(list(CMPS))
        p = r.randrange(VMIN - 500, VMAX + 500)
        return (f"Count(Row(v {o} {p}))",
                lambda o=o, p=p: sum(1 for x in vals.values()
                                     if CMPS[o](x, p)), "count")
    if kind == 2:
        text, acc = gen_tree(r, 1)
        return (f"Sum({text}, field=v)",
                lambda a=acc: ((sum(x for cc, x in vals.items()
                                    if cc in a)),
                               sum(1 for cc in vals if cc in a)), "sum")
    if kind == 3:
        name = r.choice(["Min", "Max"])
        text, acc = gen_tree(r, 1)
        def mm(a=acc, name=name):
            sel = [x for cc, x in vals.items() if cc in a]
            if not sel:
                return None
            best = min(sel) if name == "Min" else max(sel)
            return (best, sel.count(best))
        return f"{name}({text}, field=v)", mm, "valcount"
    if kind == 4:
        fi = r.randrange(3)
        n = r.randrange(0, 4)
        thr = r.randrange(0, 3) * 40
        args = [f"f{fi}"]
        if n:
            args.append(f"n={n}")
        if thr:
            args.append(f"threshold={thr}")
        def topn(fi=fi, n=n, thr=thr):
            t = sorted(((row, len(bits[(f"f{fi}", row)]))
                        for row in range(5)),
                       key=lambda rc: (-rc[1], rc[0]))
            t = [(row, cnt) for row, cnt in t if cnt > 0]
            if thr:
                t = [(row, cnt) for row, cnt in t if cnt >= thr]
            return t[:n] if n else t
        return f"TopN({', '.join(args)})", topn, "pairs"
    if kind == 5:
        # up to 4 children: the outer cartesian loop stays within
        # MAX_OUTER_DISPATCHES (5 rows/field -> <=25 combos)
        nch = r.randrange(1, 5)
        fis = [r.randrange(3) for _ in range(nch)]
        children = ", ".join(f"Rows(f{fi})" for fi in fis)
        def gb(fis=tuple(fis)):
            out = []
            def walk(prefix, sets, lvl):
                if lvl == len(fis):
                    inter = sets[0]
                    for s in sets[1:]:
                        inter = inter & s
                    n = len(inter)
                    if n:
                        out.append((prefix, n))
                    return
                for row in range(5):
                    cs = bits[(f"f{fis[lvl]}", row)]
                    walk(prefix + ((f"f{fis[lvl]}", row),),
                         sets + [cs], lvl + 1)
            walk((), [], 0)
            # sorted-group order == tuple sort of ((field,row),...)
            return sorted(out)
        return f"GroupBy({children})", gb, "groups"
    text, acc = gen_tree(r, 1)
    fi, row = r.randrange(3), r.randrange(5)
    return (f"Count(Intersect(Row(f{fi}={row}), {text}))",
            lambda a=acc, k=(f"f{fi}", row): len(bits[k] & a), "count")


checked = writes = rounds = xchecks = 0
t_start = time.monotonic()
R = 0
while True:
    # round gate: the coordinator decides stop vs go (wall clocks skew)
    if pid == 0:
        if time.monotonic() - t_start > SOAK_S:
            open(f"{data}/stop.ok", "w").write("1")
        else:
            open(f"{data}/round.{R}.go", "w").write("1")
    end = time.monotonic() + 300
    while not (os.path.exists(f"{data}/stop.ok")
               or os.path.exists(f"{data}/round.{R}.go")):
        if time.monotonic() > end:
            raise SystemExit(f"round {R} gate timeout")
        time.sleep(0.02)
    if os.path.exists(f"{data}/stop.ok"):
        break
    rr = random.Random((SEED << 20) ^ R)

    # ---- write phase (coordinator applies; everyone updates oracle)
    wlist = []
    for _ in range(rr.randrange(3, 9)):
        w = rr.random()
        fi, row = rr.randrange(3), rr.randrange(5)
        col = rr.randrange(N_SHARDS * SHARD_WIDTH)
        if w < 0.55:
            wlist.append((f"Set({col}, f{fi}={row})",))
            bits[(f"f{fi}", row)].add(col)
            exists.add(col)
        elif w < 0.8:
            wlist.append((f"Clear({col}, f{fi}={row})",))
            bits[(f"f{fi}", row)].discard(col)
        else:
            val = rr.randrange(VMIN, VMAX)
            wlist.append((f"Set({col}, v={val})",))
            vals[col] = val
            exists.add(col)
    if pid == 0:
        for (w,) in wlist:
            c.post_json(srv.uri + "/index/i/query", {"query": w})
        writes += len(wlist)
    barrier(f"w{R}")

    # ---- collective phase: identical query sequence, lockstep
    qlist = [gen_query(rr) for _ in range(rr.randrange(4, 10))]
    answers = []
    for q, oracle_fn, shape in qlist:
        if not ce.supported(parse(q).calls[0]):
            continue
        got = ce.execute(q)
        answers.append((q, got))
        if pid != 0:
            continue
        want = oracle_fn()
        if shape == "count":
            assert got == want, (R, q, got, want)
        elif shape == "sum":
            assert (got.val, got.count) == want, (R, q, got, want)
        elif shape == "valcount":
            if want is not None:
                assert (got.val, got.count) == want, (R, q, got, want)
            else:
                assert got.count == 0, (R, q, got)
        elif shape == "pairs":
            assert [(p.id, p.count) for p in got] == want, \
                (R, q, got, want)
        elif shape == "groups":
            g = [(tuple((fr.field, fr.row_id) for fr in gc.group),
                  gc.count) for gc in got]
            assert g == want, (R, q, g, want)
        elif shape == "row":
            assert sorted(int(x) for x in got.columns()) == want, \
                (R, q, len(got.columns()), len(want))
        checked += 1
    barrier(f"q{R}")

    # ---- every 5th round: plane cross-check (peers idle, serving).
    # The HTTP plane answers in JSON, so compare the integer-shaped
    # results (counts) — aggregate/pair shapes are already oracle-
    # checked above on every round
    if R % 5 == 0 and pid == 0:
        for q, coll in answers:
            # counts and bare Rows cross-check against the HTTP plane
            # (aggregate/pair shapes are oracle-checked every round)
            if not (isinstance(coll, int) or hasattr(coll, "columns")):
                continue
            http = c.post_json(srv.uri + "/index/i/query",
                               {"query": q})["results"][0]
            assert _fl.norm_http_result(http) == _fl.norm_result(coll), \
                (R, q, http)
            xchecks += 1
    barrier(f"x{R}")
    rounds += 1
    R += 1

barrier("done")
c.close(); srv.close()
print("RESULT " + json.dumps({
    "rounds": rounds, "writes_applied": writes if pid == 0 else None,
    "collective_queries_checked": checked if pid == 0 else None,
    "plane_xchecks": xchecks if pid == 0 else None,
    "counters": spmd.counters()}))
'''


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=600.0)
    ap.add_argument("--procs", type=int, default=2)
    ap.add_argument("--seed", type=int, default=918273)
    args = ap.parse_args()

    tmp = tempfile.mkdtemp(prefix="soak_spmd_")
    coord_port, *node_ports = fleet_lib.free_ports(1 + args.procs)

    worker = os.path.join(tmp, "worker.py")
    with open(worker, "w") as f:
        f.write(WORKER)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update(
        JAX_COORDINATOR_ADDRESS=f"127.0.0.1:{coord_port}",
        JAX_NUM_PROCESSES=str(args.procs),
        T_DATA=tmp,
        SOAK_SECONDS=str(args.seconds),
        SOAK_SEED=str(args.seed),
        PYTHONPATH=repo + os.pathsep + env.get("PYTHONPATH", ""),
        **{f"T_PORT{i}": str(p) for i, p in enumerate(node_ports)},
    )
    t0 = time.time()
    # a hung worker is exactly what this soak hunts — run_fleet kills
    # the whole fleet on timeout so reruns never fight orphaned
    # servers/ports
    ok, outs, timed_out = fleet_lib.run_fleet(
        [[sys.executable, worker] for _ in range(args.procs)],
        [dict(env, JAX_PROCESS_ID=str(pid))
         for pid in range(args.procs)],
        timeout=args.seconds + 900, label="soak_spmd")
    if timed_out:
        # a genuine hang — exactly what this soak hunts; crashes
        # (rc!=0 without a hang) fall through to the normal summary so
        # triage chases the right thing
        print(json.dumps({"ok": False, "reason": "worker hang/timeout",
                          "procs": args.procs, "seed": args.seed}))
        return 1
    results = [ln for out in outs for ln in out.splitlines()
               if ln.startswith("RESULT ")]
    summary = {"ok": ok, "procs": args.procs,
               "wall_s": round(time.time() - t0, 1),
               "seed": args.seed}
    if ok and results:
        parsed = [json.loads(r[7:]) for r in results]
        coord = next((p for p in parsed
                      if p["writes_applied"] is not None), None)
        if coord:
            summary.update({k: coord[k] for k in
                            ("rounds", "writes_applied",
                             "collective_queries_checked",
                             "plane_xchecks")})
            # counters summed ACROSS workers: "joined" only ever
            # increments on peers (the coordinator initiates), so the
            # coordinator's counters alone would always read joined=0
            # and make the evidence look like nothing ever joined
            summary["counters"] = {
                k: sum(p["counters"].get(k, 0) for p in parsed)
                for k in coord["counters"]}
    # run_fleet already wrote every worker's tail to stderr on failure
    print(json.dumps(summary))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
