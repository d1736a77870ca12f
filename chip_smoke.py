#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that pilosa-tpu still serves from
the chip.

It drives the main path once, through the entry points a user calls:
`python -m pilosa_tpu server` as the server, the REST routes as the
client.  One index of 256 shards x 2^20 columns = 268,435,456 columns
(the upstream use case is a billion columns, BASELINE.json config 2;
256 shards is the cut that loads over HTTP inside this script's time
limit) is generated from `--seed` with numpy, loaded over
`/import-roaring` and `/import-value`, and queried; every answer is
compared with a numpy oracle built from the same arrays, and every
read's flight record must show that a device engine answered it.

Three server processes run one after another on one data directory,
each the only process that holds the chip(s) while it lives:

  load     creates the schema, imports the data, answers one Count on
           freshly imported fragments, stops;
  cold     opens the data, answers every request (compiling as it
           goes), takes one acknowledged Set and reads it back, stops;
  restart  opens the data again and must give the same answers — the
           written bit included — while adding NOTHING to the
           persistent compile cache (but for the batch widths its
           concurrent wave happens to form: arrival decides those).

`cold` and `restart` open the same directory in the same way, so the
open-time warm-up lowers the same programs in both: that is what lets
the restart leg demand zero new cache entries.  Last, the on-chip
Pallas validator (benchmarks/validate_tpu.py) runs as a child of its
own.  This script never initialises a JAX backend itself.

On success the last line of stdout is one JSON object,
{"ok": true, "device": {"platform", "kind", "count"}}, with the device
as the server reported it.  Any wrong answer, HTTP error, missing
engine, non-TPU platform, failed native build or failed child exits
non-zero without that line.

`--size tiny` runs the same code at 2 shards with JAX_PLATFORMS=cpu: a
CPU dry run that proves nothing about the chip.  One CPU device is the
numpy host engine, so the platform, engine and kernel-compile checks
are printed as "not enforced (cpu dry run)" there; the answers still
are.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

SHARD_EXP = 20  # the source's own shard width; never cut
SHARD_WIDTH = 1 << SHARD_EXP
SHARD_WORDS = SHARD_WIDTH // 32
CONTAINERS_PER_SHARD = SHARD_WIDTH >> 16
TIME_LIMIT_S = 1150  # watchdog, inside the contract's 1200 s
INDEX = "smoke"

SIZES = {
    # shards, sparse rows per style, int values per shard
    "real": dict(shards=256, per_style=64, ints_per_shard=8192,
                 min_resident=1 << 30),
    "tiny": dict(shards=2, per_style=6, ints_per_shard=512,
                 min_resident=0),
}
DENSE_ROWS = 4       # field f rows 0..3 (two at ~9% fill, two at 50%)
G_ROWS, H_ROWS = 32, 8  # TopN / GroupBy fields: 32 x 8 = 256 groups
ARRAY_BASE, RUN_BASE, BITMAP_BASE = 100, 200, 300  # sparse row ids in f
INT_MAX = (1 << 20) - 1
CONCURRENT = 16        # barrier-started sparse Counts, one bucket key
COALESCE_WINDOW_S = 4.0


class SmokeFailure(Exception):
    pass


def say(msg: str = "") -> None:
    print(msg, flush=True)


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# --------------------------------------------------------------- the data


class Data:
    """Everything the index holds, as numpy arrays made from the seed.
    Dense rows are packed uint32 words over all shards; sparse rows are
    per-shard arrays of in-shard offsets."""

    def __init__(self, seed: int, shards: int, per_style: int,
                 ints_per_shard: int):
        t0 = time.time()
        self.shards = shards
        self.n_words = shards * SHARD_WORDS
        rng = np.random.default_rng(seed)

        def rand_words():
            return rng.integers(0, 1 << 32, size=self.n_words,
                                dtype=np.uint32)

        def and_of(n):
            w = rand_words()
            for _ in range(n - 1):
                w &= rand_words()
            return w

        # f rows 0, 1: 1/8 & ~1/4 = 9.4% fill (under the [containers]
        # threshold of 25%: the planner may serve them compressed);
        # rows 2, 3: 50% fill, hot rows that stay dense
        self.dense = {0: and_of(3) & ~and_of(2), 1: and_of(3) & ~and_of(2),
                      2: rand_words(), 3: rand_words()}
        self.g = {r: and_of(5) for r in range(G_ROWS)}   # 1/32 fill
        self.h = {r: and_of(3) for r in range(H_ROWS)}   # 1/8 fill
        # sparse rows: {row: [offsets of shard 0, shard 1, ...]}
        self.sparse: dict[int, list[np.ndarray]] = {}
        hot = 4  # clustered and span rows live in containers 0..3
        for i in range(per_style):
            # scattered ~0.1% fill: ~65 bits in each of the 16
            # containers of a shard -> array containers
            self.sparse[ARRAY_BASE + i] = [
                np.unique(rng.integers(0, SHARD_WIDTH, size=1050))
                for _ in range(shards)]
            # two contiguous spans a shard -> run containers
            rows = []
            for _ in range(shards):
                cs = rng.choice(hot, size=2, replace=False)
                parts = []
                for c in cs:
                    n = int(rng.integers(3000, 6000))
                    start = (int(c) << 16) + int(
                        rng.integers(0, (1 << 16) - n))
                    parts.append(np.arange(start, start + n))
                rows.append(np.sort(np.concatenate(parts)))
            self.sparse[RUN_BASE + i] = rows
            # clustered 1% fill: 8% of two containers a shard (5243 of
            # 65536 bits, over the array kind's 4096) -> bitmap
            rows = []
            for _ in range(shards):
                cs = rng.choice(hot, size=2, replace=False)
                rows.append(np.sort(np.concatenate([
                    (int(c) << 16) + rng.choice(1 << 16, size=5243,
                                                replace=False)
                    for c in cs])))
            self.sparse[BITMAP_BASE + i] = rows
        # int field v: values on ints_per_shard columns of every shard
        self.int_cols = np.concatenate([
            s * SHARD_WIDTH + np.sort(rng.choice(
                SHARD_WIDTH, size=ints_per_shard, replace=False))
            for s in range(shards)]).astype(np.int64)
        self.int_vals = rng.integers(0, INT_MAX + 1,
                                     size=len(self.int_cols),
                                     dtype=np.int64)
        # columns the index knows to exist: the column-aware import
        # route (/import-value) records existence, /import-roaring
        # does not
        self.exists = pack(self.int_cols, self.n_words)
        # the written bits, one per querying process: columns of the
        # last shard that dense row 1 does not hold and that have no
        # int value
        base = (shards - 1) * SHARD_WIDTH
        self.set_cols = []
        off = 12345
        while len(self.set_cols) < 2:
            if not (bit(self.dense[1], base + off)
                    or bit(self.exists, base + off)):
                self.set_cols.append(base + off)
            off += 1
        self._packed: dict[int, np.ndarray] = {}
        self.seconds = time.time() - t0

    def f(self, row: int) -> np.ndarray:
        """Packed words of row ``row`` of field f."""
        if row in self.dense:
            return self.dense[row]
        w = self._packed.get(row)
        if w is None:
            cols = np.concatenate([
                s * SHARD_WIDTH + offs
                for s, offs in enumerate(self.sparse[row])])
            w = self._packed[row] = pack(cols, self.n_words)
        return w

    def apply_set(self, col: int) -> None:
        """An acknowledged Set(col, f=1), applied to the oracle."""
        for words in (self.dense[1], self.exists):
            words[col >> 5] |= np.uint32(1 << (col & 31))


def pack(cols: np.ndarray, n_words: int) -> np.ndarray:
    words = np.zeros(n_words, dtype=np.uint32)
    np.bitwise_or.at(words, cols >> 5,
                     np.uint32(1) << (cols & 31).astype(np.uint32))
    return words


def bit(words: np.ndarray, col: int) -> bool:
    return bool((int(words[col >> 5]) >> (col & 31)) & 1)


def count(words: np.ndarray) -> int:
    return int(np.bitwise_count(words).sum(dtype=np.uint64))


def columns(words: np.ndarray) -> list[int]:
    nz = np.flatnonzero(words)
    bits = np.unpackbits(words[nz].view(np.uint8).reshape(-1, 4),
                         axis=1, bitorder="little")
    w, b = np.nonzero(bits)
    return (nz[w].astype(np.int64) * 32 + b).tolist()


# ------------------------------------------------------------ the server


class ServerProcess:
    """One `python -m pilosa_tpu server` child."""

    def __init__(self, name: str, work: str, tiny: bool):
        self.name = name
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        self.uri = f"http://127.0.0.1:{port}"
        cfg = os.path.join(work, "smoke.toml")
        with open(cfg, "w") as f:
            # everything default but the places, and the batching.
            # A Count that finds no coalesced launch in flight launches
            # at once; those that arrive behind a launch gather until
            # it ends, at most window-ms.  The 16 concurrent Counts
            # therefore run as the first to arrive alone and the rest
            # behind it: the wide cap keeps the rest in ONE bucket
            # however long that first launch compiles (at the default
            # 2 ms they would leave one by one during a cold compile),
            # and max-batch 16 is the widest program the wave can ask
            # for.  A lone query waits for nobody.
            f.write(f'data-dir = "{os.path.join(work, "data")}"\n'
                    f'bind = "127.0.0.1:{port}"\n'
                    f"[coalescer]\n"
                    f"window-ms = {COALESCE_WINDOW_S * 1e3}\n"
                    f"max-batch = {CONCURRENT}\n")
        env = dict(os.environ)
        env.pop("PILOSA_TPU_SHARD_WIDTH_EXP", None)  # tests pin 2^16
        if tiny:
            env["JAX_PLATFORMS"] = "cpu"
        self.log_path = os.path.join(work, f"server-{name}.log")
        self.log = open(self.log_path, "w")
        self.t0 = time.time()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu", "server", "-c", cfg],
            cwd=REPO, env=env, stdout=self.log, stderr=subprocess.STDOUT)
        LIVE.append(self)

    # -- HTTP ------------------------------------------------------------

    def request(self, method: str, path: str, body: bytes | None = None,
                ctype: str = "application/json", timeout: float = 600,
                **params):
        qs = "&".join(f"{k}={v}" for k, v in params.items())
        req = urllib.request.Request(
            self.uri + path + ("?" + qs if qs else ""), data=body,
            method=method, headers={"Content-Type": ctype})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return json.loads(resp.read() or b"null")
        except urllib.error.HTTPError as e:
            raise SmokeFailure(
                f"{method} {path} -> HTTP {e.code}: "
                f"{e.read()[:600]!r}") from None

    def get(self, path: str, **params):
        return self.request("GET", path, **params)

    def post(self, path: str, obj=None, **params):
        return self.request(
            "POST", path,
            None if obj is None else json.dumps(obj).encode(), **params)

    def query(self, pql: str, **params) -> tuple:
        """(result, flight record) of one single-call PQL request."""
        out = self.request("POST", f"/index/{INDEX}/query", pql.encode(),
                           ctype="text/plain", profile="1", **params)
        require(len(out["results"]) == 1, f"{pql}: {out}")
        return out["results"][0], out.get("profile") or {}

    # -- lifecycle -------------------------------------------------------

    def wait_ready(self) -> dict:
        """Block until /status answers; returns the server's own
        backend description."""
        deadline = time.time() + 420
        while time.time() < deadline:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"server[{self.name}] exited with "
                    f"{self.proc.returncode} before it served:\n"
                    + self.log_tail())
            try:
                st = self.get("/status", timeout=5)
                if st.get("state") == "NORMAL":
                    self.start_seconds = time.time() - self.t0
                    return st["backend"]
            except (SmokeFailure, OSError):
                pass
            time.sleep(0.5)
        raise SmokeFailure(f"server[{self.name}] did not serve in 420 s:\n"
                           + self.log_tail())

    def wait_warm(self) -> dict:
        """Wait for the open-time work: background stack prewarm is
        not observable from outside, the ragged warm-up is — and a
        failure there fails the run."""
        deadline = time.time() + 300
        while True:
            pw = self.get("/debug/ragged")["prewarm"]
            if pw["state"] in ("done", "failed"):
                break
            require(time.time() < deadline,
                    f"ragged prewarm still {pw['state']} after 300 s")
            time.sleep(0.5)
        require(pw["state"] == "done",
                f"server[{self.name}] ragged prewarm FAILED: {pw['error']}")
        return pw

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=240)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
                raise SmokeFailure(f"server[{self.name}] ignored SIGTERM "
                                   "for 240 s") from None
        self.log.close()
        if self in LIVE:
            LIVE.remove(self)
        if self.proc.returncode != 0:
            raise SmokeFailure(
                f"server[{self.name}] exited with {self.proc.returncode}:\n"
                + self.log_tail())

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self.log.close()

    def log_tail(self, n: int = 40) -> str:
        if not self.log.closed:
            self.log.flush()
        with open(self.log_path, errors="replace") as f:
            return "".join(f.readlines()[-n:])


LIVE: list[ServerProcess] = []


# --------------------------------------------------------------- loading


def load(srv: ServerProcess, data: Data) -> dict:
    """Schema + bulk import over HTTP.  One /import-roaring request per
    field and shard (all rows of the field in one payload), the int
    field over /import-value in batches of 16 shards."""
    from concurrent.futures import ThreadPoolExecutor

    from pilosa_tpu.storage import roaring

    t0 = time.time()
    srv.post(f"/index/{INDEX}", {"options": {}})
    for name in ("f", "g", "h"):
        srv.post(f"/index/{INDEX}/field/{name}",
                 {"options": {"type": "set"}})
    srv.post(f"/index/{INDEX}/field/v",
             {"options": {"type": "int", "min": 0, "max": INT_MAX}})
    sent = {"requests": 0, "bytes": 0}
    lock = threading.Lock()

    def dense_part(rows: dict[int, np.ndarray], shard: int):
        ids = sorted(rows)
        words = np.stack([
            rows[r][shard * SHARD_WORDS:(shard + 1) * SHARD_WORDS]
            for r in ids]).reshape(-1, 2048).view(np.uint64)  # [n, 1024]
        keys = (np.repeat(np.array(ids, dtype=np.uint64)
                          * CONTAINERS_PER_SHARD, CONTAINERS_PER_SHARD)
                + np.tile(np.arange(CONTAINERS_PER_SHARD,
                                    dtype=np.uint64), len(ids)))
        return keys, words

    def send(field: str, shard: int) -> None:
        if field == "f":
            keys, words = dense_part(data.dense, shard)
            pos = np.concatenate([
                row * SHARD_WIDTH + offs[shard]
                for row, offs in sorted(data.sparse.items())])
            skeys, swords = roaring.positions_to_containers(pos)
            keys = np.concatenate([keys, skeys])
            words = np.concatenate([words, swords])
        else:
            keys, words = dense_part(getattr(data, field), shard)
        order = np.argsort(keys, kind="stable")
        blob = roaring.encode(keys[order], words[order])
        srv.request("POST",
                    f"/index/{INDEX}/field/{field}/import-roaring/{shard}",
                    blob, ctype="application/octet-stream")
        with lock:
            sent["requests"] += 1
            sent["bytes"] += len(blob)

    def send_values(lo: int, hi: int) -> None:
        sel = ((data.int_cols >= lo * SHARD_WIDTH)
               & (data.int_cols < hi * SHARD_WIDTH))
        body = json.dumps({"columnIDs": data.int_cols[sel].tolist(),
                           "values": data.int_vals[sel].tolist()}).encode()
        srv.request("POST", f"/index/{INDEX}/field/v/import-value", body)
        with lock:
            sent["requests"] += 1
            sent["bytes"] += len(body)

    jobs = [(send, (fld, s)) for s in range(data.shards)
            for fld in ("f", "g", "h")]
    jobs += [(send_values, (lo, min(lo + 16, data.shards)))
             for lo in range(0, data.shards, 16)]
    with ThreadPoolExecutor(max_workers=8) as pool:
        for fut in [pool.submit(fn, *a) for fn, a in jobs]:
            fut.result()
    # fresh imports of sparse rows sit in ingest delta planes until the
    # background compactor merges them ([ingest] compact-interval 2 s)
    deadline = time.time() + 180
    while True:
        ing = srv.get("/debug/ingest")
        if not ing.get("fragmentsPending"):
            break
        require(time.time() < deadline,
                f"ingest deltas still pending after 180 s: "
                f"{ing.get('fragmentsPending')} fragments")
        time.sleep(0.5)
    sent["seconds"] = time.time() - t0
    return sent


# -------------------------------------------------------------- requests


def reads(data: Data) -> list[dict]:
    """Every read of the run: name, PQL, URL params, expected answer,
    and how to compare.  Query texts are distinct (no checked answer
    may come from the result cache)."""
    f, A, R, B = data.f, ARRAY_BASE, RUN_BASE, BITMAP_BASE
    E = data.exists
    out: list[dict] = []

    def add(name, pql, want, kind="equal", **params):
        out.append(dict(name=name, pql=pql, want=want, kind=kind,
                        params=params))

    def row(r):
        return f"Row(f={r})"

    # dense trees
    add("north-star", f"Count(Intersect({row(0)}, {row(1)}))",
        count(f(0) & f(1)))
    add("five-leaf",
        f"Count(Union({row(0)}, Intersect({row(1)}, {row(2)}), "
        f"Difference({row(3)}, {row(0)})))",
        count(f(0) | (f(1) & f(2)) | (f(3) & ~f(0))))
    add("not-xor", f"Count(Xor(Not({row(2)}), {row(3)}))",
        count((E & ~f(2)) ^ f(3)))
    # bitmap-rooted Intersect of two sparse rows: the COLUMNS compared
    add("row-intersect", f"Intersect({row(B)}, {row(B + 1)})",
        columns(f(B) & f(B + 1)), kind="columns")
    # a two-leaf sparse Count per container style, on both routes a
    # chip server has for it: through the coalescer (the bitmap VM) and
    # with ?nocoalesce=true (the container gather arms; for two bitmap
    # rows that is the Pallas directory-walk kernel)
    # (the coalesced ones use the rows the 16 concurrent Counts use:
    # their container leaves are then built once, here, in order — 16
    # threads racing to build the same leaf would pool duplicates, and
    # the batch's megapool shape, so its compiled program, would vary
    # from process to process)
    for style, base in (("array", A), ("run", R), ("bitmap", B)):
        add(f"sparse-{style}",
            f"Count(Intersect({row(base + 4)}, {row(base + 5)}))",
            count(f(base + 4) & f(base + 5)))
        add(f"sparse-{style}-nocoalesce",
            f"Count(Intersect({row(base + 2)}, {row(base + 3)}))",
            count(f(base + 2) & f(base + 3)), nocoalesce="true")
    # TopN on the 32-row field, plain and filtered
    g_counts = {r: count(w) for r, w in data.g.items()}
    add("topn", "TopN(g, n=10)", top(g_counts, 10), kind="topn")
    add("topn-filter", f"TopN(g, {row(0)}, n=10)",
        top({r: count(w & f(0)) for r, w in data.g.items()}, 10),
        kind="topn")
    # BSI
    vals = data.int_vals
    add("sum", "Sum(field=v)",
        {"value": int(vals.sum()), "count": len(vals)})
    add("min", "Min(field=v)",
        {"value": int(vals.min()), "count": int((vals == vals.min()).sum())})
    add("max", "Max(field=v)",
        {"value": int(vals.max()), "count": int((vals == vals.max()).sum())})
    x = INT_MAX // 3
    add("bsi-range", f"Count(Row(v > {x}))", int((vals > x).sum()))
    # two-level GroupBy: 32 x 8 = 256 groups
    add("groupby", "GroupBy(Rows(g), Rows(h))",
        {(gr, hr): count(gw & hw)
         for gr, gw in data.g.items() for hr, hw in data.h.items()},
        kind="groupby")
    return out


def concurrent_reads(data: Data) -> list[dict]:
    """16 sparse Counts of 16 DIFFERENT tree shapes, 2-4 leaves each
    (one tape size class), over array, run and bitmap rows: sent
    together they are one coalescer bucket."""
    f, A, R, B = data.f, ARRAY_BASE, RUN_BASE, BITMAP_BASE
    a, r, b = (lambda i: A + 4 + i % 2), (lambda i: R + 4 + i % 2), \
        (lambda i: B + 4 + i % 2)

    def q(r_):
        return f"Row(f={r_})"

    specs = [
        ("Intersect({0}, {1})", lambda w, x: w & x, (b(0), r(1))),
        ("Union({0}, {1})", lambda w, x: w | x, (b(0), r(0))),
        ("Difference({0}, {1})", lambda w, x: w & ~x, (r(0), r(1))),
        ("Xor({0}, {1})", lambda w, x: w ^ x, (b(1), r(1))),
        ("Intersect({0}, {1}, {2})", lambda w, x, y: w & x & y,
         (b(0), b(1), r(0))),
        ("Union({0}, {1}, {2})", lambda w, x, y: w | x | y,
         (b(0), r(0), r(1))),
        ("Xor({0}, {1}, {2})", lambda w, x, y: w ^ x ^ y,
         (b(1), r(0), b(0))),
        ("Difference({0}, {1}, {2})", lambda w, x, y: w & ~x & ~y,
         (b(0), r(0), r(1))),
        ("Union(Intersect({0}, {1}), {2})", lambda w, x, y: (w & x) | y,
         (a(0), b(0), r(0))),
        ("Intersect(Union({0}, {1}), {2})", lambda w, x, y: (w | x) & y,
         (b(0), r(0), a(0))),
        ("Difference(Union({0}, {1}), {2})", lambda w, x, y: (w | x) & ~y,
         (b(1), r(1), a(1))),
        ("Difference({0}, Union({1}, {2}))", lambda w, x, y: w & ~(x | y),
         (b(0), r(0), r(1))),
        ("Xor(Intersect({0}, {1}), {2})", lambda w, x, y: (w & x) ^ y,
         (a(1), b(1), r(1))),
        ("Union(Intersect({0}, {1}), Intersect({2}, {3}))",
         lambda w, x, y, z: (w & x) | (y & z), (b(0), b(1), r(0), r(1))),
        ("Intersect(Union({0}, {1}), Union({2}, {3}))",
         lambda w, x, y, z: (w | x) & (y | z), (b(0), r(0), b(1), r(1))),
        ("Xor(Intersect({0}, {1}), Difference({2}, {3}))",
         lambda w, x, y, z: (w & x) ^ (y & ~z), (b(0), r(0), b(1), a(0))),
    ]
    return [dict(name=f"concurrent-{i:02d}",
                 pql="Count(" + tmpl.format(*(q(x) for x in rows)) + ")",
                 want=count(fn(*(f(x) for x in rows))), kind="equal",
                 params={})
            for i, (tmpl, fn, rows) in enumerate(specs)]


def top(counts: dict[int, int], n: int) -> list[tuple[int, int]]:
    return sorted(((r, c) for r, c in counts.items() if c),
                  key=lambda rc: (-rc[1], rc[0]))[:n]


def compare(rd: dict, got) -> None:
    want, kind, name = rd["want"], rd["kind"], rd["name"]
    if kind == "equal":  # a count, or a {value, count} aggregate
        ok = got == want
    elif kind == "columns":
        got = got["columns"]
        ok = got == want
        got, want = f"{len(got)} columns", f"{len(want)} columns"
    elif kind == "topn":
        got = [(p["id"], p["count"]) for p in got]
        ok = got == want
    elif kind == "groupby":
        got = {tuple(m["rowID"] for m in grp["group"]): grp["count"]
               for grp in got}
        ok = got == {k: v for k, v in want.items() if v}
        got, want = f"{len(got)} groups", f"{len(want)} groups"
    else:
        raise AssertionError(kind)
    require(ok, f"WRONG ANSWER {name}: {rd['pql']}\n  got  {got}\n"
                f"  want {want}")


class Routes:
    """What the flight records say answered, over one server's run."""

    def __init__(self, enforced: bool, backend: dict):
        self.enforced = enforced
        self.backend = backend
        self.engines: dict[str, int] = {}
        self.paths: dict[str, int] = {}

    def note(self, rd: dict, prof: dict) -> None:
        engine, path = prof.get("engine"), prof.get("path")
        for table, key in ((self.engines, engine), (self.paths, path)):
            table[str(key)] = table.get(str(key), 0) + 1
        line = (f"  {rd['name']:<26} ok  engine={engine} path={path} "
                f"launches={prof.get('deviceLaunches')} "
                f"compileMs={prof.get('compileMs')} "
                f"elapsedMs={prof.get('elapsedMs')}")
        co = prof.get("coalescer")
        if co:
            line += f" batch={co['batch']} shapes={co['shapes']}"
        if "deltaDepth" in prof:
            line += f" deltaDepth={prof['deltaDepth']}"
        be = self.backend
        say(line + f"  [platform={be['platform']} "
                   f"device_kind={be['deviceKind']} "
                   f"devices={be['deviceCount']}]")
        if self.enforced:
            require(not prof.get("cached"),
                    f"{rd['name']}: answered from the result cache")
            # GroupBy is one DEVICE launch a shard by design (its
            # record says per-shard with a device engine since PR 24)
            require(path != "per-shard" or rd["kind"] == "groupby",
                    f"{rd['name']}: took the per-shard host map")
            require(engine != "host",
                    f"{rd['name']}: the host engine answered")

    def check_coverage(self) -> None:
        say(f"  engines: {self.engines}")
        say(f"  paths:   {self.paths}")
        if self.backend["deviceCount"] > 1:
            # under the mesh every fused engine reports as "mesh": the
            # VM declines (vm.fallbacks.mesh_active), kind leaves fall
            # back to dense pools, container pools replicate (ROADMAP
            # S6) — known, printed, not a failure of this run
            need = [("mesh",)]
        else:
            need = [("dense",),
                    ("gather", "gather_aa", "gather_ab", "gather_kinds"),
                    ("tape", "vm", "vm_kinds")]
        missing = [grp for grp in need
                   if not any(e in self.engines for e in grp)]
        if not self.enforced:
            say(f"  engine coverage: not enforced (cpu dry run); "
                f"missing {missing or 'nothing'}")
            return
        require(not missing, f"no request was answered by {missing}")
        require("coalesced" in self.paths, "no request was coalesced")


def run_requests(srv: ServerProcess, data: Data, routes: Routes,
                 set_col: int, cache_dir: str) -> tuple[int, float]:
    """Every request of one querying process -> (compile cache entries,
    seconds of compile wall) of its concurrent wave: which batch widths
    that wave forms is decided by arrival, so two processes may compile
    different ones, and the restart leg's checks leave them out."""
    for rd in reads(data):
        got, prof = srv.query(rd["pql"], **rd["params"])
        compare(rd, got)
        routes.note(rd, prof)
    # 16 barrier-started concurrent sparse Counts
    entries_before_wave = cache_entries(cache_dir)
    wall_before_wave = srv.get("/debug/devices")["compile"]["totalMs"]
    crs = concurrent_reads(data)
    assert len(crs) == CONCURRENT
    barrier = threading.Barrier(len(crs))
    results: list = [None] * len(crs)

    def one(i: int) -> None:
        try:
            barrier.wait(timeout=60)
            results[i] = srv.query(crs[i]["pql"])
        except BaseException as e:  # noqa: BLE001 — re-raised below
            results[i] = e

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(crs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
        require(not t.is_alive(), "a concurrent Count did not return")
    for rd, res in zip(crs, results):
        if isinstance(res, BaseException):
            raise res
        compare(rd, res[0])
        routes.note(rd, res[1])
    batches = sorted({(res[1].get("coalescer") or {}).get("batch", 0)
                      for res in results})
    say(f"  the {CONCURRENT} concurrent Counts ran in batches of {batches}")
    wave_entries = cache_entries(cache_dir) - entries_before_wave
    wave_wall = (srv.get("/debug/devices")["compile"]["totalMs"]
                 - wall_before_wave) / 1e3
    say(f"  the wave added {wave_entries} compile cache entries in "
        f"{wave_wall:.1f} s of compile wall")
    if routes.enforced:
        require(batches[-1] > 1,
                f"no two of the {CONCURRENT} concurrent Counts shared a "
                f"launch (batches {batches}): none arrived while "
                f"another's launch was in flight")
    # one acknowledged write, read back by the next Count.  Each
    # querying process writes a bit of its own, so both run the same
    # programs (a Count over a pending delta fuses two overlay leaves)
    # and the restarted one also counts the bit its predecessor wrote
    changed, _ = srv.query(f"Set({set_col}, f=1)")
    require(changed is True, f"Set was not acknowledged: {changed!r}")
    data.apply_set(set_col)
    rd = dict(name="count-after-set", pql="Count(Row(f=1))",
              want=count(data.f(1)), kind="equal", params={})
    got, prof = srv.query(rd["pql"])
    compare(rd, got)
    routes.note(rd, prof)
    say(f"  Set({set_col}, f=1) acknowledged and counted; "
        f"deltaDepth={prof.get('deltaDepth', 0)}")
    if routes.enforced:
        require(prof.get("deltaDepth", 0) >= 1,
                "the written bit was not read through the delta plane")
    return wave_entries, wave_wall


# ------------------------------------------------------------ inspection


def cache_entries(cache_dir: str) -> int:
    if not os.path.isdir(cache_dir):
        return 0
    return sum(len(files) for _, _, files in os.walk(cache_dir))


def report_devices(srv: ServerProcess, size: dict, enforced: bool,
                   label: str, work: str) -> dict:
    dev = srv.get("/debug/devices")
    with open(os.path.join(work, f"devices-{label}.json"), "w") as f:
        json.dump(dev, f, indent=1)  # for --keep: every kernel shape
    res = dev["residency"]
    be = dev["backend"]
    say(f"  [{label}] residency: total={res['total']} "
        f"per_device={res['per_device']} budget={res['budget']} "
        f"kinds={res['kinds']}")
    for d in dev["devices"]:
        say(f"  [{label}] device {d['id']} {d['platform']} {d['kind']}: "
            f"bytesInUse={d.get('bytesInUse')} "
            f"bytesLimit={d.get('bytesLimit')}")
    comp = dev["compile"]
    say(f"  [{label}] compiles: {comp['total']} programs, "
        f"{comp['totalMs'] / 1e3:.1f} s wall")
    for name, k in sorted(comp["kernels"].items(),
                          key=lambda kv: -kv[1]["totalMs"])[:12]:
        say(f"      {name:<40} x{k['compiles']:<3} "
            f"{k['totalMs'] / 1e3:8.2f} s")
    natives = dev["native"]
    say(f"  [{label}] native libraries: "
        + ", ".join(f"{n}={'native' if v['loaded'] else 'PYTHON'}"
                    for n, v in natives.items()))
    for n, v in natives.items():
        require(v["loaded"], f"native library {n} did not build: "
                             f"{v['error']}")
    require(res["per_device"] >= size["min_resident"],
            f"only {res['per_device']} bytes resident per device; the "
            f"run needs {size['min_resident']}")
    if be["deviceCount"] > 1:
        mesh = srv.get("/debug/mesh")
        used = [d.get("bytesInUse") or 0 for d in dev["devices"]]
        say(f"  [{label}] mesh: active={mesh['active']} "
            f"axis={len(mesh['devices'])} counters={mesh['counters']}")
        # sharded stacks spread evenly; what the per-shard paths put on
        # the device (fragment.matrix: GroupBy uploads one matrix per
        # fragment) goes to the default device, so device 0 holds more
        say(f"  [{label}] bytes put on the devices, by owner: "
            + ", ".join(f"{k}={v['bytes']}"
                        for k, v in dev["transfer"]["byLabel"].items()))
        if enforced:
            require(mesh["active"] and len(mesh["devices"])
                    == be["deviceCount"], f"mesh is not on: {mesh}")
            require(mesh["counters"]["mesh.launches"] > 0,
                    "no mesh launch happened")
            require(min(used) * 2 * len(used) >= sum(used),
                    "a device holds under half an even share of the "
                    f"placed bytes: {used}")
    cont = srv.get("/debug/containers")["counters"]
    rag = srv.get("/debug/ragged")
    say(f"  [{label}] container counters: {cont}")
    say(f"  [{label}] vm fallbacks: {rag['vm']['fallbackReasons']}")
    say(f"  [{label}] vm programs: {rag['vm']['programs']}")
    say(f"  [{label}] ragged prewarm: {rag['prewarm']}")
    return dev


# ------------------------------------------------------------------ main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--size", choices=sorted(SIZES), default="real")
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--work-dir",
                    default=os.path.join(REPO, ".smoke_work"))
    ap.add_argument("--keep", action="store_true",
                    help="keep the work directory (data, server logs)")
    args = ap.parse_args()
    size = SIZES[args.size]
    tiny = args.size == "tiny"

    def on_alarm(signum, frame):
        raise SmokeFailure(f"time limit: {TIME_LIMIT_S} s")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(TIME_LIMIT_S)
    t_start = time.time()
    if tiny:
        say("CPU DRY RUN (--size tiny, JAX_PLATFORMS=cpu): this proves "
            "nothing about the chip; platform, engine and kernel-compile "
            "checks are printed but not enforced, answers are")
    # built from what git would commit: drop libraries left by another
    # image so the four C++ components compile from the .cpp files here
    shutil.rmtree(os.path.join(REPO, "pilosa_tpu", "native", "build"),
                  ignore_errors=True)
    shutil.rmtree(args.work_dir, ignore_errors=True)
    os.makedirs(args.work_dir)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    cache_dir = placed or os.path.join(REPO, ".jax_cache")
    entries_start = cache_entries(cache_dir)
    say(f"compile cache: {cache_dir} ("
        + ("placed by JAX_COMPILATION_CACHE_DIR" if placed
           else "fixed in-checkout default")
        + f"), {entries_start} entries at start")
    try:
        device = run(args, size, tiny, cache_dir)
    except SmokeFailure as e:
        say(f"\nFAIL: {e}")
        return 1
    finally:
        signal.alarm(0)
        for srv in list(LIVE):
            srv.kill()
        if not args.keep:
            shutil.rmtree(args.work_dir, ignore_errors=True)
    say(f"\nchip_smoke: all phases passed in {time.time() - t_start:.0f} s")
    if tiny:
        say("(CPU dry run: no result line; nothing here is a chip result)")
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def run(args, size: dict, tiny: bool, cache_dir: str) -> dict:
    enforced = not tiny
    how = "not enforced (cpu dry run)" if tiny else "enforced"

    # ---- load ---------------------------------------------------------
    say(f"\n== load: {size['shards']} shards x 2^{SHARD_EXP} = "
        f"{size['shards'] * SHARD_WIDTH:,} columns"
        + ("" if tiny else " (cut from the billion-column use case to "
                           "what loads over HTTP in the time limit)"))
    srv = ServerProcess("load", args.work_dir, tiny)
    be = srv.wait_ready()
    say(f"  server says: platform={be['platform']} "
        f"device_kind={be['deviceKind']} devices={be['deviceCount']} "
        f"engine={be['engine']}  (platform check {how})")
    if enforced:
        require(be["platform"] == "tpu",
                f"the server is on platform {be['platform']!r}, not tpu")
        require(not be["hostMode"], "the server is in host mode")
    srv.wait_warm()
    data = Data(args.seed, size["shards"], size["per_style"],
                size["ints_per_shard"])
    say(f"  data from seed {args.seed} in {data.seconds:.1f} s: f = "
        f"{DENSE_ROWS} dense + 3 x {size['per_style']} sparse rows "
        f"(array/run/bitmap), g = {G_ROWS} rows, h = {H_ROWS} rows, "
        f"v = {len(data.int_vals):,} ints")
    sent = load(srv, data)
    say(f"  loaded over HTTP in {sent['seconds']:.1f} s: "
        f"{sent['requests']} import requests, {sent['bytes']:,} bytes")
    routes = Routes(enforced, be)
    rd = dict(name="count-on-fresh-import",
              pql="Count(Intersect(Row(f=2), Row(f=3)))",
              want=count(data.f(2) & data.f(3)), kind="equal", params={})
    got, prof = srv.query(rd["pql"])
    compare(rd, got)
    routes.note(rd, prof)
    t0 = time.time()
    srv.stop()
    say(f"  load server stopped in {time.time() - t0:.1f} s")

    # ---- cold, then restart --------------------------------------------
    walls, entries = {}, {}
    for phase, set_col in zip(("cold", "restart"), data.set_cols):
        say(f"\n== {phase}: a new server process on the same data")
        before = cache_entries(cache_dir)
        srv = ServerProcess(phase, args.work_dir, tiny)
        be2 = srv.wait_ready()
        require((be2["platform"], be2["deviceKind"], be2["deviceCount"])
                == (be["platform"], be["deviceKind"], be["deviceCount"]),
                f"backend changed between processes: {be} vs {be2}")
        pw = srv.wait_warm()
        say(f"  serving after {srv.start_seconds:.1f} s; ragged prewarm "
            f"warmed {pw['warmed']}, skipped {len(pw['skipped'])}")
        routes = Routes(enforced, be2)
        wave_entries, wave_wall = run_requests(srv, data, routes,
                                               set_col, cache_dir)
        routes.check_coverage()
        dev = report_devices(srv, size, enforced, phase, args.work_dir)
        walls[phase] = dev["compile"]["totalMs"] / 1e3 - wave_wall
        srv.stop()
        entries[phase] = (before, cache_entries(cache_dir) - wave_entries)
        say(f"  [{phase}] compile cache entries: {before} -> "
            f"{entries[phase][1]} (and {wave_entries} for the batch "
            f"widths the concurrent wave formed)")

    say(f"\n== compile cache across the restart  (checks {how})")
    say(f"  cold process:    {walls['cold']:.1f} s compile wall, cache "
        f"{entries['cold'][0]} -> {entries['cold'][1]} entries")
    say(f"  restart process: {walls['restart']:.1f} s compile wall, cache "
        f"{entries['restart'][0]} -> {entries['restart'][1]} entries")
    if enforced:
        require(entries["restart"][1] == entries["restart"][0],
                "the restarted server added compile cache entries: "
                f"{entries['restart']}")
        if entries["cold"][1] > entries["cold"][0]:
            require(walls["restart"] < 0.5 * walls["cold"],
                    f"warm compile wall {walls['restart']:.1f} s is not "
                    f"well under the cold {walls['cold']:.1f} s")
        else:
            say("  the cache was already warm before the cold process: "
                "the wall comparison says nothing and is skipped")

    # ---- the Pallas validator, a child of its own ---------------------
    say(f"\n== benchmarks/validate_tpu.py  (verdict {how})")
    env = dict(os.environ)
    if tiny:
        env["JAX_PLATFORMS"] = "cpu"
    val = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks",
                                      "validate_tpu.py")],
        cwd=REPO, env=env, capture_output=True, text=True)
    for line in (val.stdout + val.stderr).splitlines():
        if line.startswith(("PASS", "FAIL", "PERF", "OPEN ITEM", "{")):
            say("  " + line)
    if enforced:
        require(val.returncode == 0,
                f"validate_tpu.py exited with {val.returncode}:\n"
                + (val.stdout + val.stderr)[-3000:])
        out_dir = os.path.join(REPO, "chiprun_out")
        os.makedirs(out_dir, exist_ok=True)
        shutil.copy(os.path.join(REPO, "PALLAS_TPU_VALIDATION.json"),
                    out_dir)
    else:
        say(f"  exit {val.returncode}: not enforced (cpu dry run)")
    return {"platform": be["platform"], "kind": be["deviceKind"],
            "count": be["deviceCount"]}


if __name__ == "__main__":
    sys.exit(main())
