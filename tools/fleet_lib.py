"""Multi-process fleet scaffolding for the SPMD soak
(tools/soak_spmd.py).

The soak boots N full-server workers inside one jax.distributed
runtime and coordinates them over the CONTROL PLANE (files), never
over jax collectives — a pending collective parks the local devices,
and any peer progress that needs them (serving a scattered sub-query)
deadlocks the join.

Worker side: ``file_barrier``.  Parent side: ``free_ports`` and
``run_fleet`` (spawn, bounded wait, kill-the-whole-fleet on timeout so
a single dead worker becomes a fast failure instead of a half-hour
hang plus orphaned coordinator/HTTP ports).
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import threading
import time


def file_barrier(data_dir: str, name: str, pid: int, nproc: int,
                 timeout: float = 300.0) -> None:
    """Control-plane barrier: write my flag, wait for everyone's.
    Timing out raises SystemExit — in a lockstep fleet a missing peer
    is fatal, and exiting lets run_fleet's reaper surface it fast."""
    open(f"{data_dir}/{name}.{pid}", "w").write("1")
    end = time.monotonic() + timeout
    while not all(os.path.exists(f"{data_dir}/{name}.{p}")
                  for p in range(nproc)):
        if time.monotonic() > end:
            raise SystemExit(f"barrier {name} timeout")
        time.sleep(0.02)


def norm_result(res):
    """One plane-comparable shape for any query result object (the
    SPMD soak's cross-checks).  Column lists sort defensively
    (Row.columns() is sorted per shard; sorting costs nothing and
    removes the ordering assumption)."""
    if isinstance(res, (int, bool)):
        return res
    if hasattr(res, "columns"):  # Row: compare the column list
        return sorted(int(c) for c in res.columns())
    if hasattr(res, "val"):  # ValCount
        return (res.val, res.count)
    if hasattr(res, "id"):  # Pair (MinRow/MaxRow)
        return (res.id, res.count)
    if isinstance(res, list) and res and hasattr(res[0], "id"):
        return [(p.id, p.count) for p in res]  # TopN pairs
    if isinstance(res, list) and res and hasattr(res[0], "group"):
        return sorted(
            (tuple((fr.field, fr.row_id) for fr in gc.group), gc.count)
            for gc in res)
    return res


def norm_http_result(raw):
    """The HTTP-JSON twin of norm_result (handler serialize_result
    shapes)."""
    if isinstance(raw, dict):
        if "columns" in raw or "keys" in raw or raw == {}:
            return sorted(raw.get("columns", []))
        if "value" in raw:
            return (raw["value"], raw["count"])
        if "id" in raw:
            return (raw["id"], raw["count"])
        return raw
    if isinstance(raw, list) and raw and isinstance(raw[0], dict):
        if "group" in raw[0]:
            return sorted(
                (tuple((fr["field"], fr["rowID"]) for fr in gc["group"]),
                 gc["count"]) for gc in raw)
        if "id" in raw[0]:
            return [(p["id"], p["count"]) for p in raw]
    return raw


def free_ports(n: int) -> list[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def run_fleet(argv_per_worker: list[list[str]], env_per_worker:
              list[dict], timeout: float, label: str,
              cwd: str | None = None) -> tuple[bool, list[str], bool]:
    """Spawn one process per argv/env pair, wait ``timeout`` seconds
    for ALL of them, and on timeout kill the WHOLE fleet (one worker
    dying leaves the rest parked in a lockstep collective — the
    failure must be fast and leak no coordinator/HTTP ports).

    ``timeout`` bounds the WHOLE fleet (one shared deadline, not a
    fresh allowance per worker).  Returns (ok, outputs, timed_out) —
    ``timed_out`` distinguishes a genuine hang from a fast worker
    crash so callers classify failures correctly.  Every worker's
    pipe is drained by its own reader thread: a worker that writes
    more than the ~64 KB pipe buffer while the parent is waiting on
    an earlier worker must never block on write, or a verbose fast
    crash wedges the lockstep fleet and gets misclassified as a
    hang.  On any failure the tail of every worker's combined
    stdout/stderr is written to stderr."""
    # errors="replace": a stray non-UTF-8 byte must not kill a reader
    # thread (a dead reader stops draining and re-creates the wedge)
    procs = [subprocess.Popen(argv, env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True,
                              errors="replace", cwd=cwd)
             for argv, env in zip(argv_per_worker, env_per_worker)]
    bufs: list[list[str]] = [[] for _ in procs]

    def _drain(stream, buf: list[str]) -> None:
        while True:
            chunk = stream.read(65536)
            if not chunk:
                return
            buf.append(chunk)

    readers = [threading.Thread(target=_drain, args=(p.stdout, buf),
                                daemon=True)
               for p, buf in zip(procs, bufs)]
    for t in readers:
        t.start()
    deadline = time.monotonic() + timeout
    timed_out = False
    for p in procs:
        try:
            p.wait(timeout=max(0.1, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            timed_out = True
            break
    if timed_out:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            p.wait()
    # killed (or exited) processes close their pipe ends, so the
    # readers hit EOF; the join bound is a backstop, not a drain
    for t in readers:
        t.join(timeout=10.0)
    outs = ["".join(buf) for buf in bufs]
    if timed_out:
        sys.stderr.write(f"{label}: TIMEOUT — worker hung; fleet "
                         "killed\n")
        for i, out in enumerate(outs):
            sys.stderr.write(f"--- worker {i} tail ---\n{out[-3000:]}\n")
        return False, outs, True
    ok = all(p.returncode == 0 for p in procs)
    if not ok:
        for i, (p, out) in enumerate(zip(procs, outs)):
            sys.stderr.write(f"--- worker {i} (rc={p.returncode}) "
                             f"tail ---\n{out[-3000:]}\n")
    return ok, outs, False
