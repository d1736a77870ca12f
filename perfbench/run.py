#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1>

This process stays off JAX.  It makes the index from ``--seed`` with
numpy, starts ONE ``python -m pilosa_tpu server`` child (the process
that holds the chip), loads the index over the public import routes,
warms up the cell's own query shapes, measures a window of ``--seconds``
from the client's side, stops the server, compares a seeded sample of
the window's answers with the numpy oracle, and prints one JSON object
as the last line of stdout.  Without a TPU (or with fewer chips than the
cell asks for) it exits non-zero and prints no result.

``--rehearse`` runs the same control flow at the configuration's
``rehearse`` size with ``JAX_PLATFORMS=cpu``: a dry run for tests, which
reports no metric and no device.  ``--control lost-shard`` leaves the
last shard's imports unsent -- an acknowledged import lost -- and must
come out ``"correct": false``.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import importlib
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

T_START = time.monotonic()  # setup_s runs from process start

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from perfbench import check_manifest, mix as mixmod, oracle  # noqa: E402
from perfbench.bits import SHARD_WIDTH  # noqa: E402
from perfbench.capture import Capture  # noqa: E402
from perfbench.loadgen import Window, percentile, summarize  # noqa: E402
from perfbench.server import LIVE, BenchFailure, ServerProcess  # noqa: E402

HERE = os.path.join(ROOT, "perfbench")
INDEX = "bench"
LOAD_THREADS = 8
VALUE_BATCH_SHARDS = 4
TRACE_SECONDS = 4.0
OPEN_LOOP_THREADS = 48
WARMUP_BLOCK = 50
CONTROLS = ("lost-shard",)


def say(msg: str) -> None:
    print(msg, flush=True)


def read_json(*parts: str):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


# ---------------------------------------------------------------- set-up


def load_index(srv: ServerProcess, ds, skip_shard: int | None) -> float:
    """Schema, then every import body over HTTP on a few connections,
    then wait until the server has merged what it took; the seconds it
    took.  ``skip_shard`` (the control) is never sent."""
    t0 = time.monotonic()
    srv.call("POST", f"/index/{INDEX}", {"options": {}})
    for f in ds.fields:
        srv.call("POST", f"/index/{INDEX}/field/{f['name']}",
                 {"options": f["options"]})
    local = threading.local()

    def post(path: str, body: bytes, ctype: str) -> None:
        conn = getattr(local, "conn", None)
        if conn is None:
            conn = local.conn = srv.conn(timeout=600)
        status, out = conn.request("POST", path, body, ctype)
        if status != 200:
            raise BenchFailure(f"POST {path} -> HTTP {status}: {out[:300]!r}")

    def roaring(job) -> None:
        field, shard, blob = job
        post(f"/index/{INDEX}/field/{field}/import-roaring/{shard}",
                    blob, "application/octet-stream")

    def values(job) -> None:
        field, cols, vals = job
        post(f"/index/{INDEX}/field/{field}/import-value",
                    json.dumps({"columnIDs": cols.tolist(),
                                "values": vals.tolist()}).encode(),
                    "application/json")

    jobs = [(roaring, p) for p in ds.payloads if p[1] != skip_shard]
    for field, (cols, vals) in ds.values.items():
        if skip_shard is not None:
            keep = cols // SHARD_WIDTH != skip_shard
            cols, vals = cols[keep], vals[keep]
        edges = np.searchsorted(cols, np.arange(
            0, ds.n_shards + VALUE_BATCH_SHARDS, VALUE_BATCH_SHARDS)
            * SHARD_WIDTH)
        jobs += [(values, (field, cols[a:b], vals[a:b]))
                 for a, b in zip(edges[:-1], edges[1:]) if b > a]
    with ThreadPoolExecutor(LOAD_THREADS) as pool:
        for _ in pool.map(lambda j: j[0](j[1]), jobs):
            pass
    deadline = time.monotonic() + 240
    while srv.call("GET", "/debug/ingest").get("fragmentsPending"):
        if time.monotonic() > deadline:
            raise BenchFailure("imports still unmerged after 240 s")
        time.sleep(0.2)
    return time.monotonic() - t0


def warm_up(srv: ServerProcess, path: str, traffic: dict,
            texts: list[str]) -> None:
    """The warm-up stream, at the concurrency the traffic file names;
    any answer but 200 fails the run.  Sent in blocks so that the run
    can show how the count of compiled programs levels off."""
    c = traffic["warmup_concurrency"]
    compiled = [srv.call("GET", "/debug/devices")["compile"]["total"]]
    for lo in range(0, len(texts), WARMUP_BLOCK):
        block = texts[lo:lo + WARMUP_BLOCK]
        w = Window(srv.host, srv.port, path, block)
        # every request due at once: c clients work through the block
        w.open_loop(np.zeros(len(block)), list(range(len(block))), c)
        bad = [r for r in w.records if r.status != 200]
        if bad:
            raise BenchFailure(
                f"{len(bad)} warm-up requests failed; first: "
                f"{block[bad[0].query]} -> HTTP {bad[0].status}")
        compiled.append(
            srv.call("GET", "/debug/devices")["compile"]["total"])
    say(f"warm-up: {len(texts)} requests, {c} at a time; programs compiled "
        f"per block of {WARMUP_BLOCK}: {np.diff(compiled).tolist()}")


# ------------------------------------------------------------- the window


def trace_window(srv: ServerProcess, at: float, span: list) -> None:
    """Takes the profiler's trace for TRACE_SECONDS, ``at`` seconds
    into the window.  Runs on a thread of its own."""
    time.sleep(at)
    conn = srv.conn(timeout=120)
    try:
        t0 = time.monotonic()
        status, out = conn.request(
            "POST", f"/debug/profiler/start?seconds={TRACE_SECONDS * 4}")
        if status != 200:
            span.append(BenchFailure(f"profiler start -> {status}: {out!r}"))
            return
        t1 = time.monotonic()
        time.sleep(TRACE_SECONDS)
        t2 = time.monotonic()
        status, out = conn.request("POST", "/debug/profiler/stop")
        if status != 200:
            span.append(BenchFailure(f"profiler stop -> {status}: {out!r}"))
            return
        # the traced span, on the host's clock: from the start call's
        # return to the stop call's departure
        span.extend((t0, t1, t2, json.loads(out)["dir"]))
    finally:
        conn.close()


def reduce_trace(trace_dir: str) -> dict:
    """``trace_reduce.py`` in a CPU-only child (reading the trace needs
    jaxlib, and this process stays off JAX)."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise BenchFailure(f"no .xplane.pb under {trace_dir}")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "trace_reduce.py"), files[0]],
        env=env, capture_output=True, text=True, timeout=300)
    if out.returncode != 0:
        raise BenchFailure(f"trace_reduce failed:\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.splitlines()[-1])


def window_line(e2e: dict, limit_ms: float) -> str:
    return (f"window: attempted={e2e['attempted']} failed={e2e['failed']} "
            f"p50={e2e['read_p50_ms']:.1f}ms p90={e2e['read_p90_ms']:.1f}ms "
            f"p95={e2e['read_p95_ms']:.1f}ms p99={e2e['read_p99_ms']:.1f}ms "
            f"goodput={e2e['goodput_qps']:.2f}/s (limit {limit_ms:g} ms) "
            f"generator lateness p95={e2e['lateness_p95_ms']:.2f}ms "
            f"unfinished at close={e2e['unfinished_at_close']}")


def say_routes(records) -> None:
    """A traced window's reads by the engine and path their flight
    records name."""
    routes: dict[tuple, list[float]] = {}
    for r in records:
        if r.status == 200 and r.profile:
            key = (str(r.profile.get("engine")), str(r.profile.get("path")))
            routes.setdefault(key, []).append(r.profile.get("elapsedMs", 0.0))
    for (engine, route), ms in sorted(routes.items()):
        say(f"route engine={engine} path={route}: n={len(ms)} "
            f"elapsedMs p50={percentile(ms, 0.5):.1f} "
            f"p95={percentile(ms, 0.95):.1f} max={max(ms):.1f}")


# ------------------------------------------------------------ correctness


def check_answers(ds, mix, records, traffic: dict, seed: int) -> dict:
    """Every answer of a seeded sample of the window's distinct calls
    (the slowest read's call among them) against the oracle, exactly."""
    by_text: dict[str, list] = {}
    for r in records:
        if r.status == 200:
            by_text.setdefault(mix.texts[r.query], []).append(r)
    texts = sorted(by_text)
    rng = np.random.default_rng([seed, 0xC0FFEE])
    k = min(len(texts), traffic["oracle_sample"])
    chosen = {texts[int(i)] for i in rng.choice(len(texts), size=k,
                                                replace=False)} if k else set()
    ok_recs = [r for r in records if r.status == 200]
    if ok_recs:
        chosen.add(mix.texts[max(ok_recs, key=lambda r: r.latency_ms).query])
    compared = wrong = 0
    t0 = time.monotonic()
    for text in sorted(chosen):
        recs = by_text[text]
        q = mix.queries[recs[0].query]
        want = oracle.answer(ds, q)
        differing = sum(not oracle.matches(q, r.result, want) for r in recs)
        compared += len(recs)
        wrong += differing
        # the number compared is the count of differing answers; a
        # Count's own value is shown too, being one integer
        value = f" got={recs[0].result} want={want}" if q[0] == "count" \
            else ""
        say(f"check {text[:100]}  answers={len(recs)}{value} "
            f"differing={differing} limit=0")
    return {"calls": len(chosen), "compared": compared, "wrong": wrong,
            "seconds": time.monotonic() - t0}


# ------------------------------------------------------------ one session


@dataclasses.dataclass
class Session:
    """A server that holds the chip, loaded and warm."""
    srv: ServerProcess
    ds: object
    traffic: dict
    backend: dict
    phases: dict
    path: str  # the query route, with ?profile=1 in a traced run


def open_session(args, manifest: dict, cell: dict, work: str,
                 trial: dict | None = None) -> Session:
    """Set-up: server child, data from the seed, import, warm-up.
    ``trial`` (the sweep's) overrides keys of the traffic file."""
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        cfg = json.load(f)
    traffic = mixmod.load_traffic(cell["traffic"])
    if args.rehearse:
        cfg = {**cfg, **cfg["rehearse"]}
        traffic = {**traffic, **traffic.get("rehearse", {})}
    traffic = {**traffic, **(trial or {})}
    phases: dict[str, float] = {}

    # the server boots while the data is made
    srv = ServerProcess(work, rehearse=args.rehearse)
    datagen = importlib.import_module("perfbench.datagen." + cfg["schema"])
    cancel, made = threading.Event(), {}

    def make() -> None:
        t0 = time.monotonic()
        try:
            made["ds"] = datagen.generate(cfg, args.seed, cancel)
        except BaseException as e:  # noqa: BLE001 -- re-raised below
            made["error"] = e
        phases["data_s"] = time.monotonic() - t0

    maker = threading.Thread(target=make)
    maker.start()
    try:
        backend = srv.wait_ready()
        phases["server_ready_s"] = time.monotonic() - T_START
        say(f"server: platform={backend['platform']} "
            f"kind={backend['deviceKind']} devices={backend['deviceCount']} "
            f"engine={backend['engine']}")
        if not args.rehearse and (backend["platform"] != "tpu"
                                  or backend["deviceCount"] < cell["chips"]):
            raise BenchFailure(
                f"cell {cell['name']} needs {cell['chips']} TPU chip(s); "
                f"the server found {backend['deviceCount']} x "
                f"{backend['platform']}")
    except BenchFailure:
        cancel.set()
        raise
    finally:
        maker.join()
    if "error" in made:
        raise made["error"]
    ds = made["ds"]
    skip = ds.n_shards - 1 if args.control == "lost-shard" else None
    phases["load_s"] = load_index(srv, ds, skip)
    ds.payloads = []  # the import bodies are the server's now
    path = f"/index/{INDEX}/query" + ("?profile=1" if args.trace else "")
    t0 = time.monotonic()
    warm_up(srv, path, traffic,
            mixmod.warm_texts(traffic, ds.n_rows, args.seed))
    phases["warmup_s"] = time.monotonic() - t0
    return Session(srv, ds, traffic, backend, phases, path)


def measure(ses: Session, mix, seconds: float, trace: bool):
    """One window: (Window, devices before, devices after, traced span
    or None)."""
    srv = ses.srv
    devices_before = srv.call("GET", "/debug/devices")
    win = Window(srv.host, srv.port, ses.path, mix.texts)
    span: list = []
    tracer = None
    if trace:
        tracer = threading.Thread(
            target=trace_window,
            args=(srv, max(0.0, 0.4 * seconds - TRACE_SECONDS / 2), span))
        tracer.start()
    if mix.due is not None:
        win.open_loop(mix.due, mix.order, OPEN_LOOP_THREADS)
    else:
        win.closed_loop(mix.per_client, seconds)
    if tracer is not None:
        tracer.join()
    devices_after = srv.call("GET", "/debug/devices")
    if span and isinstance(span[0], BenchFailure):
        raise span[0]
    comp0, comp1 = devices_before["compile"], devices_after["compile"]
    new = {k: v["compiles"] - comp0["kernels"].get(k, {}).get("compiles", 0)
           for k, v in comp1["kernels"].items()}
    say(f"compiles: {comp0['total']} programs ({comp0['totalMs'] / 1e3:.1f} s"
        f" wall) before the window, {comp1['total'] - comp0['total']} "
        f"inside it: " + ", ".join(f"{k} x{n}" for k, n in new.items() if n))
    return win, devices_before, devices_after, span or None


# ------------------------------------------------------------------ main


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", choices=CONTROLS)
    return ap


def with_cell(args, body) -> tuple[int, object]:
    """Checks the manifest, makes the work directory, runs
    ``body(manifest, cell, work)``, and leaves no process and no file
    behind whatever happens: (exit code, what ``body`` returned)."""
    try:
        manifest = check_manifest.load(ROOT)
        check_manifest.check(manifest, ROOT)
    except (OSError, ValueError, check_manifest.ManifestError) as e:
        print(f"perfbench: BENCHMARK.json: {e}", file=sys.stderr)
        return 2, None
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        print(f"perfbench: no cell {args.workload!r}", file=sys.stderr)
        return 2, None
    work = os.path.join(HERE, ".work", args.workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        return 0, body(manifest, cells[args.workload], work)
    except BenchFailure as e:
        print(f"perfbench: FAILED: {e}", file=sys.stderr)
        return 1, None
    finally:
        for s in list(LIVE):
            s.kill()
        shutil.rmtree(work, ignore_errors=True)


def main(argv=None) -> int:
    args = parser().parse_args(argv)
    # a run that is told to stop still ends its server and clears .work
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    rc, line = with_cell(args, lambda m, c, w: run(args, m, c, w))
    if rc == 0:
        print(json.dumps(line), flush=True)
    return rc


@dataclasses.dataclass
class Measured:
    """One run's window, closed and checked: what a result line, and
    ``steady.py``'s reading of it, are made from."""
    ses: Session
    mix: object
    setup_s: float
    win: Window
    devices_before: dict
    devices_after: dict
    span: list | None  # the traced span of a ``--trace 1`` run
    e2e: dict          # ``loadgen.summarize`` of the window
    check: dict        # ``check_answers`` of the window

    @property
    def correct(self) -> bool:
        return self.check["wrong"] == 0 and self.check["compared"] > 0

    @property
    def failed(self) -> int:
        """Reads that failed, and answers that differ from the oracle's."""
        return self.e2e["failed"] + self.check["wrong"]

    def compared(self) -> dict:
        """Each number that ``correct`` rests on, beside its limit."""
        return {"differing_answers": {"value": self.check["wrong"],
                                      "limit": 0},
                "answers_compared": {"value": self.check["compared"],
                                     "at_least": 1}}

    def values(self) -> dict:
        """The end-to-end metrics, by their names in the manifest."""
        return {**{k: self.e2e[k] for k in ("read_p50_ms", "read_p95_ms",
                                            "goodput_qps")},
                "setup_s": self.setup_s}


def window(args, manifest: dict, cell: dict, work: str) -> Measured:
    """Set-up, the measured window, and the comparison with the oracle
    once the server has gone."""
    ses = open_session(args, manifest, cell, work)
    ds, traffic = ses.ds, ses.traffic
    mix = mixmod.build(traffic, ds.n_rows, args.seed, args.seconds)
    setup_s = time.monotonic() - T_START
    win, devices_before, devices_after, span = measure(
        ses, mix, args.seconds, bool(args.trace))
    ses.srv.kill()  # its data directory is thrown away: no snapshot needed

    # ---- after the server has gone -----------------------------------
    e2e = summarize(win.records, args.seconds, traffic["latency_limit_ms"])
    check = check_answers(ds, mix, win.records, traffic, args.seed)
    say("phases: " + " ".join(f"{k}={v:.1f}" for k, v in ses.phases.items())
        + f" setup_s={setup_s:.1f} oracle_s={check['seconds']:.1f}")
    say(window_line(e2e, traffic["latency_limit_ms"])
        + f"; compared {check['compared']} answers of {check['calls']} "
        f"calls, {check['wrong']} wrong")
    return Measured(ses, mix, setup_s, win, devices_before, devices_after,
                    span, e2e, check)


def run(args, manifest: dict, cell: dict, work: str) -> dict:
    got = window(args, manifest, cell, work)
    ds, backend, mix, win = got.ses.ds, got.ses.backend, got.mix, got.win
    e2e = got.e2e
    devices_before, devices_after = got.devices_before, got.devices_after
    line = {"correct": got.correct, "attempted": e2e["attempted"],
            "failed": got.failed}
    dev = devices_after["devices"]
    line["device"] = {
        "platform": backend["platform"], "kind": backend["deviceKind"],
        "count": backend["deviceCount"],
        "memory_peak_bytes": max(d.get("peakBytesInUse")
                                 or d.get("bytesInUse") or 0 for d in dev)}
    mine = lambda e: cell["name"] in check_manifest.metric_cells(  # noqa: E731
        manifest, e)
    if not args.trace:
        values = got.values()
        line["metrics"] = {
            e["name"]: {"value": values[e["name"]], "unit": e["unit"]}
            for e in manifest["end_to_end"] if mine(e)}
    else:
        t0, t1, t2, trace_dir = got.span
        trace = reduce_trace(trace_dir)
        cap = Capture(
            records=win.records, queries=mix.queries, meta=ds.meta(),
            devices_before=devices_before,
            devices_after=devices_after, device_kind=backend["deviceKind"],
            peaks=read_json("peaks.json"), trace=trace,
            trace_span=(t1 - win.t0, t2 - win.t0))
        say_routes(win.records)
        line["metrics"] = {}
        for p in manifest["per_layer"]:
            if not mine(p):
                continue
            reader = importlib.import_module(
                "perfbench.readers."
                + read_json("metrics", p["name"] + ".json")["reader"])
            try:
                value = reader.read(cap)
            except (KeyError, ValueError) as e:
                raise BenchFailure(f"per-layer metric {p['name']}: {e}") \
                    from e
            if value is not None:
                line["metrics"][p["name"]] = {"value": value,
                                              "unit": p["unit"]}
        line["device"]["busy_s"] = trace["busy_s"]
        line["device"]["window_s"] = t2 - t1
        line["breakdown"] = {"device_ops": trace["top_ops"],
                             "idle_gaps": trace["top_gaps"]}
    if args.rehearse:
        # a CPU dry run proves the control flow and nothing else: it
        # names what it read and reports no value and no device
        line = {"rehearsal": True, "correct": line["correct"],
                "attempted": line["attempted"], "failed": line["failed"],
                "read": sorted(line["metrics"])}
    line["compared"] = got.compared()  # last in the line, and on stderr
    for name, c in line["compared"].items():
        limit = " ".join(f"{k}={v}" for k, v in c.items() if k != "value")
        print(f"perfbench: compared {name}={c['value']} {limit}",
              file=sys.stderr, flush=True)
    return line


if __name__ == "__main__":
    sys.exit(main())
