#!/usr/bin/env python3
"""The A/A rehearsal: can this cell be admitted under the bounds?

    python3 perfbench/steady.py --workload <cell> --runs N [--seed S0]
                                [--seconds 51] [--rehearse]

Runs the cell N times on the tree it stands in, seeds ``S0 .. S0+N-1``,
each run in a process of its own as the driver's runs are, through
``run.py``'s own ``window`` (the same set-up, window, summary and
comparison with the oracle: no second way of measuring).  For each
end-to-end metric of ``BENCHMARK.json`` it prints every run's value and
reads them as the driver's check reads two sets of runs of one program:

- the spread, the distance between the quartiles
  (``statistics.quantiles(values, n=4)``) over the median, and beside it
  the same without the run farthest from the median, where that is
  narrower (what the driver takes when it asks whether a bound is too
  tight);
- the medians of the odd and of the even runs, two sets of one program,
  and their difference as a share of the bound;
- a verdict.  ``steady``: the spread is at most half the bound and the
  halves differ by at most half of it, so a check resolves and holds.
  ``noisy``: either is over the whole bound, so a check comes out
  ``unresolved`` or ``benchmark_too_noisy`` whatever the PR did.
  ``marginal``: between the two, a coin.  ``setup_s`` is judged by its
  halves alone and without the first run, which compiles, as the driver
  judges it.

For a metric that is a percentile of the window's latencies it also
reads each window's own shape: the share of reads within plus or minus
the bound of the percentile, and the widest gap between neighbouring
latencies inside that band.  A median inside a mode reads a ``dense``
band; a median on the edge between two modes (a result cache's hits
below it, the launches above) reads a ``near-empty`` one, and then a
point of cache-hit share from seed to seed moves it by more than any
bound.  The last line of stdout is one JSON object.  What the runs
themselves print goes to stderr.  ``--rehearse`` is ``run.py``'s: the
control flow on the CPU, no value reported."""

from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import signal
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import check_manifest  # noqa: E402
from perfbench import run as harness  # noqa: E402  (sets T_START)
from perfbench.loadgen import percentile  # noqa: E402

#: the end-to-end metrics that ``loadgen.summarize`` takes as a
#: percentile of the window's latencies, and which percentile
PERCENTILES = {"read_p50_ms": 0.50, "read_p95_ms": 0.95}
#: a band is ``dense`` when it holds this share of the window's reads,
#: so that the percentile's rank has 5 points of room either way, and
#: ``near-empty`` under the second: one point either way
DENSE_PCT, EMPTY_PCT = 10.0, 2.0
VERDICTS = ("steady", "marginal", "noisy")


# ----------------------------------------------------------- arithmetic


def spread(values: list[float]) -> float:
    """Distance between the quartiles over the median, as the driver
    takes it."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed_spread(values: list[float]) -> float:
    """The spread without the run farthest from the median, where that
    narrows it."""
    if len(values) < 3:
        return spread(values)
    mid = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - mid))
    return min(spread(values), spread(values[:far] + values[far + 1:]))


def verdict(spread_: float | None, halves: float, bound: float) -> str:
    """``spread_`` (None: not judged by it) and the halves' difference,
    both as shares of the median, against the bound."""
    worst = max(spread_ or 0.0, halves)
    if worst <= bound / 2:
        return "steady"
    return "noisy" if worst > bound else "marginal"


def judge(name: str, values: list[float], bound: float) -> dict:
    """One metric's runs read as the check reads them.  Fewer than four
    runs judged give no quartiles of two halves: no verdict."""
    first = None
    if name == "setup_s":  # the first run compiles: recorded apart
        first, values = values[0], values[1:]
    out = {"bound": bound, "values": values}
    if first is not None:
        out["first"] = first
    if len(values) < 4:
        return {**out, "verdict": None}
    mid = statistics.median(values)
    odd = statistics.median(values[0::2])
    even = statistics.median(values[1::2])
    halves = abs(odd - even) / mid
    sp = spread(values)
    return {**out, "median": mid, "spread": sp,
            "spread_trimmed": trimmed_spread(values),
            "odd_median": odd, "even_median": even,
            "halves_share_of_bound": halves / bound,
            "verdict": verdict(None if name == "setup_s" else sp, halves,
                               bound)}


def band(latencies: list[float], q: float, bound: float) -> dict:
    """A window's shape around its ``q`` percentile: the share of its
    reads within plus or minus ``bound`` of the percentile's value, and
    the widest gap between neighbouring latencies in that band (the
    band's two edges count as neighbours, so an empty side is a gap)."""
    at = percentile(latencies, q)
    lo, hi = at * (1 - bound), at * (1 + bound)
    inside = sorted(x for x in latencies if lo <= x <= hi)
    edges = [lo, *inside, hi]
    share = 100.0 * len(inside) / len(latencies)
    return {"value": at, "share_pct": share,
            "widest_gap_ms": max(b - a for a, b in zip(edges, edges[1:])),
            "reading": ("dense" if share >= DENSE_PCT else
                        "near-empty" if share < EMPTY_PCT else "thin")}


def report(manifest: dict, cell: str, runs: list[dict]) -> dict:
    """Every end-to-end metric of the cell over ``runs`` (each with
    ``values`` and ``latencies``): ``judge``, and for a percentile the
    bands of every window and the emptiest of them."""
    metrics = {}
    for e in manifest["end_to_end"]:
        if cell not in check_manifest.metric_cells(manifest, e):
            continue
        name = e["name"]
        got = judge(name, [r["values"][name] for r in runs], e["bound"])
        if name in PERCENTILES:
            bands = [band(r["latencies"], PERCENTILES[name], e["bound"])
                     for r in runs]
            got["bands"] = bands
            got["band"] = min(bands, key=lambda b: b["share_pct"])
        metrics[name] = {**got, "unit": e["unit"], "better": e["better"]}
    judged = [m["verdict"] for m in metrics.values() if m["verdict"]]
    return {"metrics": metrics, "verdict": max(
        judged, key=VERDICTS.index) if judged else None}


def table(rep: dict) -> str:
    lines = []
    for name, m in rep["metrics"].items():
        vals = " ".join(f"{v:.4f}" for v in m["values"])
        if "first" in m:
            vals = f"[first {m['first']:.4f}] " + vals
        lines.append(f"{name} ({m['unit']}, bound {m['bound']:g}): {vals}")
        if m["verdict"] is None:
            lines.append("  too few runs to judge (four at the least)")
        else:
            lines.append(
                f"  median {m['median']:.4f}  spread {m['spread']:.4f} "
                f"(without the farthest run {m['spread_trimmed']:.4f})  "
                f"odd runs {m['odd_median']:.4f} even runs "
                f"{m['even_median']:.4f}: "
                f"{m['halves_share_of_bound']:.2f} of the bound apart  -> "
                f"{m['verdict']}")
        if "band" in m:
            b = m["band"]
            lines.append(
                f"  band +-{m['bound']:g} of the percentile, run by run: "
                + " ".join(f"{x['share_pct']:.1f}%" for x in m["bands"])
                + f"; the emptiest at {b['value']:.4f} holds "
                f"{b['share_pct']:.1f}% of its window's reads, widest gap "
                f"{b['widest_gap_ms']:.4f} ms -> {b['reading']}")
    lines.append(f"cell: {rep['verdict']}")
    return "\n".join(lines)


# ------------------------------------------------------------- the runs


def _one_run(conn, argv: list[str]) -> None:
    """A run in this (new) process: ``run.py``'s own set-up, window and
    check; what it found goes back through ``conn``."""
    os.dup2(2, 1)  # the run's own lines go to stderr
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    args = harness.parser().parse_args(argv)
    rc, got = harness.with_cell(
        args, lambda m, c, w: harness.window(args, m, c, w))
    if rc != 0:
        conn.send({"rc": rc})
        return
    conn.send({"rc": 0, "seed": args.seed, "correct": got.correct,
               "attempted": got.e2e["attempted"], "failed": got.failed,
               "values": got.values(),
               "latencies": [r.latency_ms for r in got.win.records]})


def one_run(argv: list[str]) -> dict:
    """Runs ``_one_run`` in a spawned process and waits for it; a run
    that ends without a result is ``{"rc": its exit code}``."""
    ctx = multiprocessing.get_context("spawn")
    here, there = ctx.Pipe(duplex=False)
    child = ctx.Process(target=_one_run, args=(there, argv))
    child.start()
    there.close()
    try:
        try:
            got = here.recv()
        except EOFError:
            got = None
        child.join()
        return got or {"rc": child.exitcode or 1}
    finally:
        if child.is_alive():  # told to stop: the run's server goes too
            child.terminate()
            child.join(timeout=150)
        if child.is_alive():
            child.kill()
            child.join()
        here.close()


def rehearsal(workload: str, seeds: list[int], seconds: float,
              rehearse: bool = False) -> tuple[int, list[dict]]:
    """(exit code, the runs that gave a result).  Stops at the first
    run that gives none."""
    runs = []
    for seed in seeds:
        argv = ["--workload", workload, "--seed", str(seed), "--seconds",
                str(seconds), "--trace", "0"] + ["--rehearse"] * rehearse
        got = one_run(argv)
        if got["rc"] != 0:
            return got["rc"], runs
        print(f"steady: seed {seed}: correct={got['correct']} "
              f"failed={got['failed']} of {got['attempted']}",
              file=sys.stderr, flush=True)
        runs.append(got)
    return 0, runs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    try:
        manifest = check_manifest.load(ROOT)
        check_manifest.check(manifest, ROOT)
    except (OSError, ValueError, check_manifest.ManifestError) as e:
        print(f"perfbench: BENCHMARK.json: {e}", file=sys.stderr)
        return 2
    seconds = args.seconds or manifest["run_seconds"]
    seeds = [args.seed + i for i in range(args.runs)]
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    rc, runs = rehearsal(args.workload, seeds, seconds, args.rehearse)
    if rc != 0:
        return rc
    line = {"workload": args.workload, "seeds": seeds, "seconds": seconds,
            "correct": all(r["correct"] for r in runs),
            "failed": sum(r["failed"] for r in runs)}
    rep = report(manifest, args.workload, runs)
    if args.rehearse:
        # a CPU dry run proves the control flow and nothing else
        line = {"rehearsal": True, **line,
                "attempted": [r["attempted"] for r in runs],
                "read": sorted(rep["metrics"])}
    else:
        print(table(rep), flush=True)
        for m in rep["metrics"].values():
            m.pop("bands", None)
        line.update(rep)
    print(json.dumps(line), flush=True)
    return 0 if line["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
