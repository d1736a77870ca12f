#!/usr/bin/env python3
"""A traced benchmark run that also prints PERF.md section 5's route
table: the window's reads by route (result cache; dense or tape; batch
leader, follower or alone), each with the medians of its host phases
from the flight records' spans (`http.parse` first: from the request
line read to the root span's opening, which is the header block, the
target and the route) and of the client's service time, the share of
coalescer flushes by what ended the leader's wait (`why`: idle, busy,
full, cap; absent on a program that predates it), and the share of
staged leaves whose cached stacks were validated against the
view's write token alone (`fast` of `leaves` on the `stage` spans;
absent likewise), the answers the server wrote between the two ends
of the window beside the socket sends they took (`http.responses`,
`http.sends` of `/debug/vars`; absent likewise), and the share of
launches whose one wait for the device was the fetch of their counts:
by route, the reads whose `launch.ready` span notes `fetched`, and over
the window `launch.fetched` and `launch.refetched` beside
`engine.launches` (`/debug/vars`; absent likewise), and how often the
result cache's key put a tree's operands in another order than the
query wrote them: the probes whose `cache.probe` span notes
`reordered`, and how many of them hit, beside `cache.hits`,
`cache.reordered` and `cache.invalidations` over the window
(`/debug/vars`; absent likewise), and the walks of call trees a
prepared read took: `plan.walks` over `plan.prepared` (absent likewise;
1.0 where every read is a fused Count).

Then the gap table (`perfbench/gaps.py`): by route, every piece of the
root that only an envelope covers, keyed (envelope, the span that ended
before it or `start`, the span that opens after it or `end`), with the
median ms over the reads that have the piece and the share of the
route's reads that do, largest first, under the route's median
`unattributed_ms`.  And what the program records of a compile
(`perfbench/compiles.py`; absent on a program that predates the
events): per compile event of the window its kernel, shape key, what
the persistent cache said, its ms by phase, and the reads that stood
behind it by the span they waited in; and from the recorder's ring
(`/debug/queries`, the newest 256 records, read after the window) the
median `http.send`, which a read's inline profile cannot hold.  Last,
the four per-layer metrics whose readers and metric files stand under
`perfbench/` with no entry in `BENCHMARK.json` yet (`WAITING`; `PERF.md`
section 7 t says what keeps them out): `run.py` reads only what the
manifest lists, so this prints what they read in the window.

    python3 tools/route_table.py --workload seg-dense --seed <n> \\
        --seconds 51 --trace 1

Arguments, result line and exit code are `perfbench/run.py`'s: this is
that run with a longer `say_routes`.  The table goes to stderr with the
run's other lines."""

from __future__ import annotations

import importlib
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import compiles, gaps  # noqa: E402
from perfbench import run as harness  # noqa: E402
from perfbench import spans as sp  # noqa: E402
from perfbench.capture import Capture  # noqa: E402

#: readers of `perfbench/readers/` that `BENCHMARK.json` does not list
WAITING = ("unattributed_ms", "compile_stall_ms", "compile_cold_in_window",
           "compile_blocked_reads")

PHASES = ("http.parse", "stage", "coalesce.wait", "launch", "launch.stack",
          "launch.dispatch", "launch.ready", "reduce")
#: the host's named pieces around them, a second table by route (the
#: glue PR 43 named and the phases a cached read has too)
GLUE = ("api.open", "pql.parse", "exec.open", "translate", "plan",
        "cache.probe", "route", "cache.fill", "api.close", "exec")


def route_of(profile: dict) -> str:
    if profile.get("cached"):
        return "cached"
    co = profile.get("coalescer")
    if not co or profile.get("path") != "coalesced":
        return f"{profile.get('engine')}, not coalesced"
    role = ("follower" if not co["leader"]
            else "alone" if co["batch"] == 1 else "leader")
    return f"{profile.get('engine')}, {role}"


def say_table(records) -> None:
    rows: dict[str, list] = {}
    glue: dict[str, list] = {}
    why: dict[str, int] = {}
    fetched: dict[str, int] = {}
    fast = leaves = probes = moved = moved_hits = 0
    for r in records:
        spans = sp.of(r) if r.status == 200 and r.profile else None
        if spans is None:
            continue
        for s in spans:
            if s["name"] == "stage" and "fast" in s:
                fast += s["fast"]
                leaves += s["leaves"]
            elif s["name"] == "launch.ready" and s.get("fetched"):
                route = route_of(r.profile)
                fetched[route] = fetched.get(route, 0) + 1
            elif s["name"] == "cache.probe":
                probes += 1
                if s.get("reordered"):
                    moved += 1
                    moved_hits += bool(s.get("hit"))
        glue.setdefault(route_of(r.profile), []).append(
            [sp.total(spans, name) for name in GLUE])
        rows.setdefault(route_of(r.profile), []).append(
            [sp.total(spans, "http.parse"), sp.self_total(spans, "stage")]
            + [sp.total(spans, name) for name in PHASES[2:]]
            + [sp.ms(sp.root(spans)), (r.done - r.sent) * 1e3])
        co = r.profile.get("coalescer")
        if co and co["leader"] and not r.profile.get("cached"):
            key = str(co.get("why"))
            why[key] = why.get(key, 0) + 1
    harness.say("route table, medians in ms: n | " + " | ".join(PHASES)
                + " | root | client service")
    for route, vals in sorted(rows.items(), key=lambda kv: -len(kv[1])):
        med = [statistics.median(col) for col in zip(*vals)]
        harness.say(f"  {route}: {len(vals)} | "
                    + " | ".join(f"{m:.3f}" for m in med))
    harness.say("named glue, medians in ms: " + " | ".join(GLUE))
    for route, vals in sorted(glue.items(), key=lambda kv: -len(kv[1])):
        harness.say(f"  {route}: " + " | ".join(
            f"{statistics.median(col):.3f}" for col in zip(*vals)))
    flushes = sum(why.values())
    harness.say("flushes by why: " + ", ".join(
        f"{k} {n} ({100 * n / flushes:.1f}%)"
        for k, n in sorted(why.items(), key=lambda kv: -kv[1])))
    if leaves:
        harness.say(f"leaves staged: {leaves}, without a walk over the "
                    f"shards: {fast} ({100 * fast / leaves:.2f}%)")
    if fetched:
        harness.say("reads whose launch.ready was the fetch: " + ", ".join(
            f"{route} {n} of {len(rows[route])}"
            for route, n in sorted(fetched.items())))
    if moved:
        harness.say(f"cache probes: {probes}, operands reordered for the "
                    f"key: {moved}, of which hits: {moved_hits} of "
                    f"{len(rows.get('cached', ()))} cached reads")


#: a piece of the gap table is printed from this median (ms) up, or
#: when it is a tenth of its route's unattributed time
GAP_FLOOR_MS = 0.005


def say_gaps(records) -> None:
    by_route: dict[str, list[dict]] = {}
    for r in records:
        spans = sp.of(r) if r.status == 200 and r.profile else None
        if spans is not None:
            by_route.setdefault(route_of(r.profile), []).append(
                gaps.pieces(spans))
    harness.say("gap table: envelope: before -> after | median ms of the "
                "reads that have it | share of the route's reads")
    for route, reads in sorted(by_route.items(), key=lambda kv: -len(kv[1])):
        whole = statistics.median(sum(p.values()) for p in reads)
        harness.say(f"  {route}: {len(reads)} reads, unattributed_ms "
                    f"median {whole:.4f}")
        keys: dict[tuple, list[float]] = {}
        for p in reads:
            for key, ms in p.items():
                keys.setdefault(key, []).append(ms)
        rows = sorted(((statistics.median(v), len(v) / len(reads), k)
                       for k, v in keys.items()),
                      key=lambda row: -row[0] * row[1])
        for med, share, (env, before, after) in rows:
            if med >= min(GAP_FLOOR_MS, whole / 10):
                harness.say(f"    {env}: {before} -> {after} | {med:.4f} | "
                            f"{100 * share:.1f}%")


def say_compiles(cap: Capture) -> None:
    events = compiles.in_window(cap)
    if events is None:
        return
    stood = compiles.blocked(cap.profiled(), events)
    harness.say(f"compile events in the window: {len(events)}, union "
                f"{compiles.stall_ms(events):.1f} ms, reads blocked: "
                f"{compiles.reads(stood)}")
    for e in events:
        where: dict[str, int] = {}
        for ev, _, name in stood:
            if ev is e:
                where[name] = where.get(name, 0) + 1
        harness.say(
            f"  {e['kernel']} {e['shape']}: persistent={e['persistent']} "
            f"{e['ms']:.1f} ms (trace {e['traceMs']:.1f}, lower "
            f"{e['lowerMs']:.1f}, backend {e['backendMs']:.1f}), paid by "
            f"{e['rid']}; blocked: " + (", ".join(
                f"{n} in {name}" for name, n in sorted(
                    where.items(), key=lambda kv: -kv[1])) or "none"))


def say_waiting(cap: Capture) -> None:
    listed = {p["name"] for p in harness.check_manifest.load()["per_layer"]}
    for name in WAITING:
        if name not in listed:
            value = importlib.import_module(
                "perfbench.readers." + name).read(cap)
            if value is not None:
                harness.say(f"metric not in the manifest: {name} {value}")


def say_sends(ring: dict) -> None:
    sends = [(s["endNs"] - s["startNs"]) / 1e6
             for d in ring.get("recent", []) for s in d.get("spans", ())
             if s["name"] == "http.send"]
    if sends:
        harness.say(f"http.send, the ring's newest {len(sends)} records: "
                    f"median {statistics.median(sends):.4f} ms")


def say_routes(records) -> None:
    _say_routes(records)
    say_table(records)
    say_gaps(records)


def measure(ses, *args):
    """The window between two reads of the server's wire counters: the
    window's reads, the harness's own calls around them (this read,
    `/debug/devices` twice, the profiler's start and stop) and nothing
    else.  Each answer over 64 KiB is one send more than responses."""
    before = ses.srv.call("GET", "/debug/vars")
    out = _measure(ses, *args)
    after = ses.srv.call("GET", "/debug/vars")
    say_sends(ses.srv.call("GET", "/debug/queries"))
    cap = Capture(records=out[0].records, queries=[], meta={},
                  devices_before=out[1], devices_after=out[2],
                  device_kind="", peaks={})
    say_compiles(cap)
    say_waiting(cap)
    if "http.responses" in after:
        responses, sends = (after[k] - before[k]
                            for k in ("http.responses", "http.sends"))
        harness.say(f"wire: http.responses +{responses} http.sends "
                    f"+{sends} ({sends / responses:.4f} a response)")
    if "launch.fetched" in after:
        launches, got, again = (after[k] - before[k] for k in (
            "engine.launches", "launch.fetched", "launch.refetched"))
        harness.say(f"launches: engine.launches +{launches} "
                    f"launch.fetched +{got} launch.refetched +{again}")
    if "plan.prepared" in after:
        walks, prepared = (after[k] - before.get(k, 0)
                           for k in ("plan.walks", "plan.prepared"))
        harness.say(f"plan: plan.walks +{walks} plan.prepared "
                    f"+{prepared} ({walks / max(prepared, 1):.4f} walks "
                    "a prepared read)")
    harness.say("cache: " + " ".join(
        f"{k} +{after[k] - before[k]}" for k in (
            "cache.hits", "cache.misses", "cache.reordered",
            "cache.invalidations") if k in after))
    return out


_say_routes, _measure = harness.say_routes, harness.measure
harness.say_routes, harness.measure = say_routes, measure

if __name__ == "__main__":
    sys.exit(harness.main())
