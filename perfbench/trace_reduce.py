#!/usr/bin/env python3
"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: how long the device was busy, which operations took the time,
and the longest gaps between them.

    JAX_PLATFORMS=cpu python3 perfbench/trace_reduce.py <file.xplane.pb>

prints one JSON object.  Reading the trace needs jaxlib
(``jax.profiler.ProfileData``), so the harness, which stays off JAX,
runs this file as a child on the CPU after the server has gone.

A device is a plane named ``/device:TPU:<n>``.  Its operations are the
events of the line ``XLA Ops`` (one event per executed HLO operation or
Pallas kernel, named by the operation); the line ``XLA Modules`` gives
the program each belongs to, and an operation is reported as
``<program>/<operation>``.  Busy time is the union of the operation
intervals of a device, averaged over the devices that ran anything; an
idle gap is the time between two consecutive busy intervals and is
labelled with the programs on either side of it -- that is as far as
today's trace goes, since the program annotates no host phase."""

from __future__ import annotations

import bisect
import json
import sys

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
TOP = 10


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, merged [start, end) intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def op_name(event: str) -> str:
    """An XLA operation's event carries its whole HLO text,
    ``%fusion.3 = u32[...] fusion(...)``: keep the name."""
    return event.split(" = ", 1)[0].lstrip("%")[:80]


def reduce_planes(planes: list[dict]) -> dict:
    """``planes``: [{"name", "lines": [{"name", "events": [(name,
    start_ns, duration_ns)]}]}] -- the trace as plain data, so the
    arithmetic can be tested without a trace file."""
    busy, op_seconds = [], 0.0
    ops: dict[str, float] = {}
    gaps: dict[str, float] = {}
    longest = 0.0
    for plane in planes:
        if not plane["name"].startswith(DEVICE_PREFIX):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        events = lines.get(OPS_LINE)
        if events is None:  # an older layout: every line is operations
            events = [e for ln in plane["lines"] for e in ln["events"]]
        if not events:
            continue
        modules = sorted((s, s + d, n.split("(")[0])
                         for n, s, d in lines.get(MODULES_LINE, []))
        starts = [m[0] for m in modules]

        def module_at(t: float) -> str:
            i = bisect.bisect_right(starts, t) - 1
            return modules[i][2] if i >= 0 and t <= modules[i][1] else "?"

        for name, start, dur in events:
            name = f"{module_at(start)}/{op_name(name)}"
            ops[name] = ops.get(name, 0.0) + dur * 1e-9
            op_seconds += dur * 1e-9
        merged = union([(s, s + d) for _, s, d in events])
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        for (_, end), (start, _) in zip(merged[:-1], merged[1:]):
            label = (f"between launches: {module_at(end)} -> "
                     f"{module_at(start)}")
            gaps[label] = gaps.get(label, 0.0) + (start - end) * 1e-9
            longest = max(longest, (start - end) * 1e-9)
    n = len(busy)
    top = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"devices": n,
            "busy_s": sum(busy) / n if n else 0.0,
            "op_seconds": op_seconds / n if n else 0.0,
            "top_ops": top(ops),
            "top_gaps": top(gaps),
            "longest_gap_s": longest}


def read_planes(path: str) -> list[dict]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [{"name": p.name,
             "lines": [{"name": ln.name,
                        "events": [(e.name, e.start_ns, e.duration_ns)
                                   for e in ln.events]}
                       for ln in p.lines]}
            for p in data.planes if p.name.startswith(DEVICE_PREFIX)]


def main(argv: list[str]) -> int:
    if len(argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    print(json.dumps(reduce_planes(read_planes(argv[1]))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
