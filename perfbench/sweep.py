#!/usr/bin/env python3
"""Finds a cell's knee: one process that pays set-up once, keeps the
server up and steps the offered load.  Trials live here, not in
``run.py``: a run of a cell takes every number from its files.

    python3 perfbench/sweep.py --workload <cell> --seed <n> --seconds <s>
                               --steps 1,2,4,6,8 [--warmup <requests>]
                               [--also <traffic>=<steps> ...]

An open-loop mix steps the arrival rate (requests a second), a
closed-loop mix the number of clients.  Every step is a window of
``--seconds`` on calls the earlier steps have not sent (another draw of
the same mix, so the server's result cache starts each step as cold as
a run's window finds it after the warm-up).  ``--also`` warms up and
steps a second traffic file of the same configuration on the same
server; ``--trace 1`` asks for flight records and prints each step's
reads by engine and path.  The table it prints goes into the traffic
file (``sweep``) and PERF.md; the knee is the highest step whose p95
stays under the latency limit with no backlog at the close, and a cell
runs at about four fifths of it.  The latency limit itself is three
times the p50 of the LOWEST step, rounded up."""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import mix as mixmod  # noqa: E402
from perfbench import run as harness  # noqa: E402
from perfbench.loadgen import summarize  # noqa: E402


def _steps(text: str) -> list[float]:
    return [float(x) for x in text.split(",")]


def _also(text: str) -> tuple[str, list[float]]:
    name, _, steps = text.partition("=")
    return name, _steps(steps)


def main(argv=None) -> int:
    ap = harness.parser()
    ap.add_argument("--steps", required=True, type=_steps,
                    help="rates (open loop) or client counts, a,b,c")
    ap.add_argument("--warmup", type=int,
                    help="warm-up requests other than the traffic file's")
    ap.add_argument("--also", type=_also, action="append", default=[],
                    metavar="TRAFFIC=STEPS")
    args = ap.parse_args(argv)
    trial = {} if args.warmup is None else {"warmup_requests": args.warmup}
    rows: list[dict] = []

    def steps_of(ses, name: str, traffic: dict, steps: list[float]) -> None:
        limit = traffic["latency_limit_ms"]
        for k, step in enumerate(steps):
            over = ({"rate": step} if traffic["loop"] == "open"
                    else {"clients": int(step)})
            mix = mixmod.build(traffic, ses.ds.n_rows, args.seed,
                               args.seconds, stream=k + 1, **over)
            win, before, after, _ = harness.measure(ses, mix, args.seconds,
                                                    False)
            e2e = summarize(win.records, args.seconds, limit)
            harness.say(f"{name} step {step:g}: "
                        + harness.window_line(e2e, limit))
            harness.say_routes(win.records)
            rows.append({"traffic": name, "step": step, **e2e, "compiles":
                         after["compile"]["total"]
                         - before["compile"]["total"]})

    def body(manifest, cell, work) -> None:
        ses = harness.open_session(args, manifest, cell, work, trial)
        steps_of(ses, cell["traffic"], ses.traffic, args.steps)
        for name, steps in args.also:
            traffic = mixmod.load_traffic(name)
            if args.rehearse:
                traffic = {**traffic, **traffic.get("rehearse", {})}
            traffic = {**traffic, **trial}
            t0 = time.monotonic()
            harness.warm_up(ses.srv, ses.path, traffic, mixmod.warm_texts(
                traffic, ses.ds.n_rows, args.seed))
            ses.phases[f"warmup_{name}_s"] = time.monotonic() - t0
            steps_of(ses, name, traffic, steps)
        harness.say("phases: " + " ".join(
            f"{k}={v:.1f}" for k, v in ses.phases.items()))

    rc, _ = harness.with_cell(args, body)
    print(json.dumps({"workload": args.workload, "seconds": args.seconds,
                      "sweep": rows}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
