"""Process-level cluster test: real ``python -m pilosa_tpu server``
OS processes, joined over real sockets, with SIGKILL fault injection
and restart-recovery — the analog of the reference's docker-compose
clustertests with pumba pauses (internal/clustertests/cluster_test.go:
69-80, §4 tier 4).  In-process clusters (test_cluster.py, test_http.py)
cover logic; this tier proves the real binary survives process death."""

from __future__ import annotations

import json
import os
import random
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

from pilosa_tpu.shardwidth import SHARD_WIDTH


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _env() -> dict:
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        # tighten SWIM probe rounds so DOWN detection fits the
        # _wait_status windows deterministically under CI load
        PILOSA_TPU_PROBE_DEADLINE_S="2.0",
        PILOSA_TPU_SHARD_WIDTH_EXP=os.environ.get(
            "PILOSA_TPU_SHARD_WIDTH_EXP", "16"),
        PYTHONPATH=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))) + os.pathsep
        + env.get("PYTHONPATH", ""),
    )
    return env


def _spawn(data_dir: str, port: int, seeds: list[int] | None = None,
           replicas: int = 2, paranoia: bool = False):
    cmd = [sys.executable, "-m", "pilosa_tpu", "server",
           "-d", data_dir, "-b", f"127.0.0.1:{port}",
           "--replicas", str(replicas),
           "--heartbeat-interval", "0.5",
           "--anti-entropy-interval", "2.0"]
    if seeds:
        cmd += ["--seeds", ",".join(f"http://127.0.0.1:{p}" for p in seeds)]
    env = _env()
    if paranoia:
        env["PILOSA_TPU_PARANOIA"] = "1"
    return subprocess.Popen(cmd, env=env,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)


def _get(port: int, path: str, timeout: float = 5.0):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=timeout) as resp:
        return json.loads(resp.read())


def _post(port: int, path: str, obj, timeout: float = 60.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(obj).encode(), method="POST")
    req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read() or b"null")


def _wait_status(port: int, state: str, n_nodes: int | None = None,
                 deadline: float = 60.0) -> dict:
    t0 = time.time()
    last = None
    while time.time() - t0 < deadline:
        try:
            st = _get(port, "/status", timeout=3)
            last = st
            if st["state"] == state and (
                    n_nodes is None or len(st["nodes"]) == n_nodes):
                return st
        except (urllib.error.URLError, ConnectionError, OSError):
            pass
        time.sleep(0.5)
    raise AssertionError(
        f"node :{port} never reached {state}/{n_nodes}; last={last}")


import contextlib


@contextlib.contextmanager
def _three_node_cluster(tmp_path, paranoia: bool = False):
    """Spawn a real 3-process cluster; teardown always SIGCONTs before
    terminating (SIGTERM is held pending on a stopped process — a
    frozen leftover would leak past the test)."""
    ports = [_free_port() for _ in range(3)]
    procs: list[subprocess.Popen | None] = [None, None, None]
    try:
        procs[0] = _spawn(str(tmp_path / "n0"), ports[0],
                          paranoia=paranoia)
        _wait_status(ports[0], "NORMAL", 1)
        procs[1] = _spawn(str(tmp_path / "n1"), ports[1],
                          seeds=[ports[0]], paranoia=paranoia)
        procs[2] = _spawn(str(tmp_path / "n2"), ports[2],
                          seeds=[ports[0]], paranoia=paranoia)
        for p in ports:
            _wait_status(p, "NORMAL", 3)
        yield ports, procs
    finally:
        for pr in procs:
            if pr is not None and pr.poll() is None:
                try:
                    pr.send_signal(signal.SIGCONT)  # never leave frozen
                except Exception:  # noqa: BLE001
                    pass
                pr.terminate()
        for pr in procs:
            if pr is not None:
                try:
                    pr.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    pr.kill()


def test_three_process_cluster_kill_and_recover(tmp_path):
    with _three_node_cluster(tmp_path) as (ports, procs):
        # schema + data spread over 9 shards, replicas=2
        _post(ports[0], "/index/i", {})
        _post(ports[0], "/index/i/field/f", {})
        rng = random.Random(6)
        sets = {r: set() for r in range(4)}
        rows, cols = [], []
        for r in sets:
            for _ in range(400):
                c = rng.randrange(9 * SHARD_WIDTH)
                sets[r].add(c)
                rows.append(r)
                cols.append(c)
        _post(ports[0], "/index/i/field/f/import",
              {"rowIDs": rows, "columnIDs": cols})

        def check_exact(port):
            got = _post(port, "/index/i/query",
                        {"query": "Count(Union(Row(f=0), Row(f=1)))"})
            assert got["results"][0] == len(sets[0] | sets[1]), port
            topn = _post(port, "/index/i/query", {"query": "TopN(f)"})
            want = sorted(((len(s), r) for r, s in sets.items()),
                          key=lambda t: (-t[0], t[1]))
            assert [(p["count"], p["id"])
                    for p in topn["results"][0]] == want, port

        for p in ports:
            check_exact(p)

        # SIGKILL one node: reads must stay exact from the survivors
        # (replica failover, executor.go:2492 analog) and the cluster
        # must notice the death (DEGRADED via heartbeats)
        procs[2].send_signal(signal.SIGKILL)
        procs[2].wait(timeout=30)
        _wait_status(ports[0], "DEGRADED")
        for p in ports[:2]:
            check_exact(p)

        # restart from the same data dir: rejoin, repair, NORMAL again
        procs[2] = _spawn(str(tmp_path / "n2"), ports[2], seeds=[ports[0]])
        for p in ports:
            _wait_status(p, "NORMAL", 3)
        for p in ports:
            check_exact(p)


def test_freeze_fault_sigstop_mid_import_and_query(tmp_path):
    """The pumba pause scenario (reference
    internal/clustertests/cluster_test.go:69-80): a node FREEZES
    (SIGSTOP) ~10 s mid-import and mid-query, then RETURNS (SIGCONT) —
    a different failure from death: the socket backlog still accepts,
    half-open connections linger, and the zombie resumes with stale
    state.  The cluster must (a) finish the import exactly once the
    node returns, (b) answer queries exactly from survivors WHILE the
    node is frozen (detected DOWN -> DEGRADED, replica failover),
    (c) return to NORMAL with exact reads everywhere after the thaw.
    Runs under the PARANOIA gate: every fragment mutation re-validates
    invariants on all three real processes."""
    import threading

    with _three_node_cluster(tmp_path, paranoia=True) as (ports, procs):
        _post(ports[0], "/index/i", {})
        _post(ports[0], "/index/i/field/f", {})
        rng = random.Random(17)
        sets = {r: set() for r in range(4)}

        def batch(n=300):
            rows, cols = [], []
            for r in sets:
                for _ in range(n):
                    c = rng.randrange(9 * SHARD_WIDTH)
                    sets[r].add(c)
                    rows.append(r)
                    cols.append(c)
            return {"rowIDs": rows, "columnIDs": cols}

        def check_exact(port):
            got = _post(port, "/index/i/query",
                        {"query": "Count(Union(Row(f=0), Row(f=1)))"})
            assert got["results"][0] == len(sets[0] | sets[1]), port

        _post(ports[0], "/index/i/field/f/import", batch())
        for p in ports:
            check_exact(p)

        # ---- freeze node2, import WHILE frozen.  Replication to the
        # frozen owner blocks on its accepted-but-unserved socket; the
        # import must complete once the node thaws, exactly.
        pre2 = len(sets[2])  # row 2's exact count BEFORE the b2 batch
        b2 = batch()
        procs[2].send_signal(signal.SIGSTOP)
        time.sleep(0.5)
        import_err: list = []

        def do_import():
            try:
                _post(ports[0], "/index/i/field/f/import", b2,
                      timeout=120.0)
            except Exception as e:  # noqa: BLE001
                import_err.append(e)

        t_imp = threading.Thread(target=do_import, daemon=True)
        t_imp.start()

        # ---- while frozen: survivors detect the freeze (DEGRADED)
        # and answer exactly via replica failover
        _wait_status(ports[0], "DEGRADED", deadline=60.0)
        frozen_q = _post(ports[0], "/index/i/query",
                         {"query": "Count(Row(f=2))"}, timeout=60.0)
        # exact-failover bound: at least everything the pre-freeze
        # batch set, at most the full b2 target (the concurrent import
        # makes the in-between value racy, never anything outside it)
        assert pre2 <= frozen_q["results"][0] <= len(sets[2]), \
            (frozen_q, pre2, len(sets[2]))

        # ---- thaw after ~10 s: import completes, cluster returns to
        # NORMAL, and every node answers exactly (AE repairs whatever
        # the frozen window missed)
        time.sleep(8.0)
        procs[2].send_signal(signal.SIGCONT)
        t_imp.join(timeout=120.0)
        assert not t_imp.is_alive(), "import never finished after thaw"
        assert not import_err, import_err
        for p in ports:
            _wait_status(p, "NORMAL", 3, deadline=120.0)
        # anti-entropy cycle (2 s interval) heals replicas the frozen
        # window missed; poll until all three answer identically
        deadline = time.time() + 60.0
        want = len(sets[0] | sets[1])
        got = None
        while True:
            try:
                got = [_post(p, "/index/i/query",
                             {"query": "Count(Union(Row(f=0), Row(f=1)))"}
                             )["results"][0] for p in ports]
                if got == [want] * 3:
                    break
            except (urllib.error.URLError, ConnectionError, OSError):
                pass  # just-thawed node may still drop a connection
            if time.time() > deadline:
                raise AssertionError(f"post-thaw divergence: {got} != "
                                     f"{want}")
            time.sleep(1.0)

        # ---- a second freeze DURING a query fan-out: the scatter
        # query from a survivor must still answer exactly (replica
        # failover mid-flight), and the zombie's return must not
        # corrupt anything
        procs[2].send_signal(signal.SIGSTOP)
        time.sleep(1.0)
        got = _post(ports[1], "/index/i/query",
                    {"query": "Count(Union(Row(f=0), Row(f=1)))"},
                    timeout=90.0)
        assert got["results"][0] == want
        time.sleep(3.0)
        procs[2].send_signal(signal.SIGCONT)
        for p in ports:
            _wait_status(p, "NORMAL", 3, deadline=120.0)
        for p in ports:
            check_exact(p)
