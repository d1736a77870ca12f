#!/usr/bin/env python
"""Process-level randomized fault soak: real server processes, real
signals, hours-scale.

The deterministic CI tier (tests/test_proc_cluster.py) proves one
scripted SIGKILL and one scripted SIGSTOP scenario.  This harness
randomizes them for hours against a live 3-process cluster — the
reference's long-running docker-compose clustertests with pumba
pauses (internal/clustertests/cluster_test.go:69-80) — so a freeze
can land at ANY phase of an import, a scatter query, or the servers'
own 2 s anti-entropy cadence:

  - FREEZE cycle: SIGSTOP a victim mid-import (replication to its
    accepted-but-unserved socket blocks), query survivors WHILE frozen
    (replica failover must stay exact), SIGCONT after 2-8 s, then
    require full convergence on all three nodes (AE heals whatever the
    frozen window missed).
  - KILL cycle: SIGKILL the victim, require DEGRADED detection and
    exact reads from survivors, restart from the same data dir, and
    require NORMAL + exact reads everywhere (WAL/snapshot recovery).
  - RESIZE cycle: a REAL 4th server process joins (coordinator-led
    re-homing over live sockets) — half the time with a replica
    FROZEN mid-join, the zombie-rejoin-versus-resize race the
    in-process soak cannot produce — then leaves via
    /cluster/resize/remove-node; reads must be exact at every stage
    whether the contested join completed or aborted cleanly.
  - QUIET cycle: import + exact reads on every node (steady-state
    oracle pressure between faults).

Bidirectional pair partitions need sender-aware message drops, which
real sockets do not offer without netem privileges — that fault lives
in the in-process randomized soak (tools/soak.py, LocalTransport
pair partitions) with identical query/AE semantics.

    PYTHONPATH=/root/repo:$PYTHONPATH python tools/soak_proc.py --seconds 3600

Exit 0 = zero divergence.  Deterministic per --seed (modulo OS
scheduling).  PARANOIA is ON in every server: each fragment mutation
re-validates invariants in all three real processes.
"""

from __future__ import annotations

import argparse
import os
import pathlib
import random
import signal
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the harness MUST agree with the spawned servers on shard width: the
# roaring import path pre-encodes absolute positions (row*width+off)
# with THIS process's width (tests/test_proc_cluster._spawn pins the
# servers to 16)
os.environ.setdefault("PILOSA_TPU_SHARD_WIDTH_EXP", "16")

from tests.test_proc_cluster import (  # noqa: E402
    _free_port, _get, _post, _spawn, _wait_status)
from pilosa_tpu.shardwidth import SHARD_WIDTH  # noqa: E402

N_SHARDS = 9


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=600.0)
    ap.add_argument("--seed", type=int, default=20260801)
    args = ap.parse_args()

    rng = random.Random(args.seed)
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="soakproc-"))
    ports = [_free_port() for _ in range(3)]
    procs: list = [None, None, None]

    def spawn(i: int):
        procs[i] = _spawn(str(tmp / f"n{i}"), ports[i],
                          seeds=[ports[0]] if i else None,
                          paranoia=True)

    stats = {"cycles": 0, "freezes": 0, "kills": 0, "resizes": 0,
             "frozen_joins": 0, "checks": 0, "imports": 0}
    epoch = 0
    oracle: dict[int, set] = {r: set() for r in range(4)}

    def batch(n=250):
        rows, cols = [], []
        for r in oracle:
            for _ in range(n):
                c = rng.randrange(N_SHARDS * SHARD_WIDTH)
                oracle[r].add(c)
                rows.append(r)
                cols.append(c)
        return {"rowIDs": rows, "columnIDs": cols}

    def roaring_import(port, b, timeout=180.0):
        """Deliver a batch over the FASTEST wire: pre-encoded roaring
        per shard via /import-roaring/{shard} (owner fan-out + WAL
        roaring records — a different durability/replication path from
        /import's JSON arrays)."""
        import urllib.request

        import numpy as np

        from pilosa_tpu.storage import roaring as rcodec

        rows_a = np.asarray(b["rowIDs"], dtype=np.int64)
        cols_a = np.asarray(b["columnIDs"], dtype=np.int64)
        shard_a = cols_a // SHARD_WIDTH
        pos_a = (rows_a * SHARD_WIDTH
                 + (cols_a % SHARD_WIDTH)).astype(np.uint64)
        for s in np.unique(shard_a):
            u = np.unique(pos_a[shard_a == s])
            k_, w_ = rcodec.positions_to_containers(u)
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/index/i/field/f/"
                f"import-roaring/{int(s)}",
                data=rcodec.encode(k_, w_), method="POST")
            req.add_header("Content-Type", "application/octet-stream")
            urllib.request.urlopen(req, timeout=timeout).read()

    def any_import(port, b, timeout=180.0):
        if rng.random() < 0.5:
            roaring_import(port, b, timeout)
        else:
            _post(port, "/index/i/field/f/import", b, timeout=timeout)

    def check_exact(port, rows=(0, 1)):
        q = "Count(Union(%s))" % ", ".join(f"Row(f={r})" for r in rows)
        got = _post(port, "/index/i/query", {"query": q}, timeout=90.0)
        want = len(set().union(*(oracle[r] for r in rows)))
        assert got["results"][0] == want, \
            f":{port} {q} -> {got['results'][0]} != {want}"
        stats["checks"] += 1

    def converge(deadline_s=90.0):
        """Poll until all three nodes answer the union row exactly —
        the post-fault AE-heal barrier."""
        end = time.time() + deadline_s
        want = len(oracle[0] | oracle[1])
        last = None
        while time.time() < end:
            try:
                last = [_post(p, "/index/i/query",
                              {"query":
                               "Count(Union(Row(f=0), Row(f=1)))"},
                              timeout=30.0)["results"][0]
                        for p in ports]
                if last == [want] * 3:
                    stats["checks"] += 3
                    return
            except OSError:
                pass
            time.sleep(1.0)
        raise AssertionError(f"no convergence: {last} != {want}")

    try:
        spawn(0)
        _wait_status(ports[0], "NORMAL", 1)
        spawn(1)
        spawn(2)
        for p in ports:
            _wait_status(p, "NORMAL", 3)
        base_ids = {_get(p, "/status")["localID"] for p in ports}
        _post(ports[0], "/index/i", {})
        _post(ports[0], "/index/i/field/f", {})
        _post(ports[0], "/index/i/field/f/import", batch())
        stats["imports"] += 1
        for p in ports:
            check_exact(p)

        t_end = time.monotonic() + args.seconds
        while time.monotonic() < t_end:
            stats["cycles"] += 1
            roll = rng.random()
            victim = rng.choice([1, 2])
            survivors = [p for i, p in enumerate(ports) if i != victim]

            if roll < 0.40:  # ---- FREEZE cycle
                stats["freezes"] += 1
                pre = {r: len(s) for r, s in oracle.items()}
                b = batch()
                procs[victim].send_signal(signal.SIGSTOP)
                time.sleep(rng.uniform(0.1, 1.0))
                err: list = []

                use_roaring = rng.random() < 0.5

                def do_import():
                    # the per-shard roaring sequence can observe the
                    # DEGRADED write-block mid-freeze (405) where the
                    # single JSON POST was already in flight — retry
                    # through the window like a real client; the merge
                    # is idempotent, so re-sending shards is exact
                    import urllib.error

                    deadline = time.time() + 180.0
                    while True:
                        try:
                            if use_roaring:
                                roaring_import(ports[0], b)
                            else:
                                _post(ports[0],
                                      "/index/i/field/f/import",
                                      b, timeout=180.0)
                            return
                        except urllib.error.HTTPError as e:
                            if e.code != 405 or time.time() > deadline:
                                err.append(e)
                                return
                            time.sleep(1.0)
                        except Exception as e:  # noqa: BLE001
                            err.append(e)
                            return

                t = threading.Thread(target=do_import, daemon=True)
                t.start()
                # survivors answer WHILE the victim is frozen; the
                # racing import bounds row counts, never breaks them
                for p in rng.sample(survivors, 2):
                    got = _post(p, "/index/i/query",
                                {"query": "Count(Row(f=3))"},
                                timeout=90.0)["results"][0]
                    assert pre[3] <= got <= len(oracle[3]), \
                        (got, pre[3], len(oracle[3]))
                    stats["checks"] += 1
                time.sleep(rng.uniform(2.0, 8.0))
                procs[victim].send_signal(signal.SIGCONT)
                t.join(timeout=180.0)
                assert not t.is_alive(), "import never finished post-thaw"
                assert not err, err
                stats["imports"] += 1
                for p in ports:
                    _wait_status(p, "NORMAL", 3, deadline=120.0)
                converge()

            elif roll < 0.60:  # ---- RESIZE cycle: real 4th process
                # joins (sometimes against a frozen replica) and leaves
                stats["resizes"] += 1
                epoch += 1
                p3 = _free_port()
                # fresh dir per epoch: a re-joining node must never
                # resurrect a removed epoch's detached fragments
                pr3 = _spawn(str(tmp / f"n3-e{epoch}"), p3,
                             seeds=[ports[0]], paranoia=True)
                frozen = rng.random() < 0.5
                if frozen:
                    stats["frozen_joins"] += 1
                    time.sleep(rng.uniform(0.0, 1.0))
                    procs[victim].send_signal(signal.SIGSTOP)
                    time.sleep(rng.uniform(2.0, 5.0))
                    procs[victim].send_signal(signal.SIGCONT)
                try:
                    # the join either completes (4 nodes) or aborts
                    # cleanly (3) — both legal under a frozen owner;
                    # reads must be exact either way once NORMAL
                    deadline = time.time() + 120.0
                    settled = False
                    while time.time() < deadline:
                        try:
                            st = _get(ports[0], "/status", timeout=5)
                            if st["state"] == "NORMAL" and (
                                    len(st["nodes"]) == 4
                                    or pr3.poll() is not None):
                                settled = True
                                break
                        except OSError:
                            pass
                        time.sleep(1.0)
                    if settled:
                        for p in ports:
                            check_exact(p)
                    # deadline expiry = the contested join neither
                    # completed nor aborted in time; exactness is
                    # enforced by the post-cleanup NORMAL wait +
                    # converge() below, after strays are removed
                finally:
                    # graceful leave for whatever actually joined
                    # (judged from the coordinator's member list, not
                    # our racy local view), then stop the process
                    end = time.time() + 120.0
                    while time.time() < end:
                        try:
                            st = _get(ports[0], "/status", timeout=10)
                            stray = [n["id"] for n in st["nodes"]
                                     if n["id"] not in base_ids]
                            if not stray and st["state"] == "NORMAL":
                                break
                            for nid in stray:
                                _post(ports[0],
                                      "/cluster/resize/remove-node",
                                      {"id": nid}, timeout=120.0)
                        except OSError:
                            pass  # coordinator mid-resize; retry
                        time.sleep(1.0)
                    if pr3.poll() is None:
                        pr3.terminate()
                        try:
                            pr3.wait(timeout=15)
                        except Exception:  # noqa: BLE001
                            pr3.kill()
                    import shutil

                    shutil.rmtree(tmp / f"n3-e{epoch}",
                                  ignore_errors=True)
                for p in ports:
                    _wait_status(p, "NORMAL", 3, deadline=120.0)
                converge()

            elif roll < 0.80:  # ---- KILL + restart cycle
                stats["kills"] += 1
                procs[victim].send_signal(signal.SIGKILL)
                procs[victim].wait(timeout=30)
                _wait_status(ports[0], "DEGRADED", deadline=60.0)
                for p in survivors:
                    check_exact(p)
                spawn(victim)
                for p in ports:
                    _wait_status(p, "NORMAL", 3, deadline=120.0)
                converge()

            else:  # ---- QUIET cycle: steady-state oracle pressure
                any_import(ports[0], batch(60))
                stats["imports"] += 1
                check_exact(rng.choice(ports), rows=(0, 1, 2))
                topn = _post(rng.choice(ports), "/index/i/query",
                             {"query": "TopN(f)"})["results"][0]
                want = sorted(((len(s), r) for r, s in oracle.items()),
                              key=lambda x: (-x[0], x[1]))
                assert [(p["count"], p["id"]) for p in topn] == want
                stats["checks"] += 1

            print(f"soak_proc: {stats}", flush=True)

        for p in ports:
            check_exact(p, rows=(0, 1, 2))
        print(f"soak_proc PASSED: {stats}", flush=True)
        return 0
    finally:
        for pr in procs:
            if pr is not None and pr.poll() is None:
                try:
                    pr.send_signal(signal.SIGCONT)  # never leave frozen
                except OSError:
                    pass
                pr.terminate()
        for pr in procs:
            if pr is not None:
                try:
                    pr.wait(timeout=15)
                except Exception:  # noqa: BLE001
                    pr.kill()


if __name__ == "__main__":
    sys.exit(main())
