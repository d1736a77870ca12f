"""Checks ``BENCHMARK.json`` and the files it names against the rules a
manifest is refused for, before any run pays for them.  Run by
``perfbench.run`` at start and by ``tests/perfbench``; by hand:
``python3 perfbench/check_manifest.py``."""

from __future__ import annotations

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
KEYS = {"configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}


#: "2/5 of the 240/s knee", "four fifths of its knee", "half of the knee"
KNEE = re.compile(r"(?:(\d+)/(\d+)|(one|two|three|four) fifths?|(half)) of "
                  r"(?:the |a |its )?(?:(\d+(?:\.\d+)?)/s )?knee")
OFFERED = re.compile(r"open loop at (\d+(?:\.\d+)?)/s")
FIFTHS = {"one": 1, "two": 2, "three": 3, "four": 4}


class ManifestError(Exception):
    pass


def _need(cond: bool, msg: str) -> None:
    if not cond:
        raise ManifestError(msg)


def _line(s, what: str) -> None:
    _need(isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s
          and "\t" not in s, f"{what}: 1 to 200 characters on one line")


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def knee_of(traffic: dict) -> float | None:
    """The knee that a traffic file's ``sweep`` rows show, by
    ``sweep.py``'s rule: the highest step that, like every step under
    it, kept its p95 inside the latency limit with nothing unfinished
    at the close."""
    sweep = traffic.get("sweep") or {}
    knee = None
    try:
        rate, p95, unfinished = (sweep["columns"].index(c) for c in (
            "rate_qps", "read_p95_ms", "unfinished_at_close"))
        for row in sorted(sweep["rows"], key=lambda r: r[rate]):
            if row[p95] > traffic["latency_limit_ms"] or row[unfinished]:
                break
            knee = float(row[rate])
    except (KeyError, ValueError, IndexError, TypeError):
        return None  # no sweep, or one without those columns
    return knee


def _why_agrees_with_traffic(w: dict, traffic: dict) -> None:
    """A cell's ``why`` that names its rate or a share of a knee says
    what its traffic file says (``seg-dense`` said four fifths of the
    knee for twelve PRs after the knee had doubled)."""
    cell = f"cell {w['name']}"
    said = OFFERED.search(w["why"])
    _need(not said or float(said[1]) == traffic.get("rate_qps"),
          f"{cell}: its why says {said and said[1]}/s, its traffic file "
          f"rate_qps {traffic.get('rate_qps')}")
    said = KNEE.search(w["why"])
    if not said:
        return
    _need(traffic["loop"] == "open", f"{cell}: a knee, and no open loop")
    knee = knee_of(traffic)
    _need(knee is not None, f"{cell}: its why names a knee and no step of "
                            "its traffic file's sweep held the limit")
    share = (int(said[1]) / int(said[2]) if said[1] else
             FIFTHS[said[3]] / 5 if said[3] else 0.5)
    _need(not said[5] or float(said[5]) == knee,
          f"{cell}: its why says a {said[5]}/s knee, its sweep's rows "
          f"{knee:g}/s")
    _need(abs(traffic["rate_qps"] - share * knee) <= 0.05 * knee,
          f"{cell}: its why says {said[0]!r}; rate_qps "
          f"{traffic['rate_qps']:g} is {traffic['rate_qps'] / knee:.2f} of "
          f"the {knee:g}/s that its sweep's rows show")


def metric_cells(m: dict, metric: dict) -> list[str]:
    """The cells a metric is reported in: its ``workloads`` key, or
    every cell."""
    return metric.get("workloads") or [w["name"] for w in m["workloads"]]


def check(m: dict, root: str = ROOT) -> None:
    """Raises ``ManifestError`` naming the first rule broken."""
    _need(set(m) == TOP_KEYS, f"top-level keys must be {sorted(TOP_KEYS)}")
    _need(isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51,
          "run_seconds: a whole number from 1 to 51")
    _need(1 <= len(m["paths"]) <= 16, "paths: 1 to 16 directories")
    for p in m["paths"]:
        _need(re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p) is not None
              and not p.startswith("/") and ".." not in p.split("/"),
              f"path {p!r}")
    _need(1 <= len(m["command"]) <= 32, "command: 1 to 32 words")
    for word in m["command"]:
        _line(word, f"command word {word!r}")
        _need(not word.startswith("/") and ".." not in word.split("/"),
              f"command word {word!r} leads out of the repo")
    under = lambda f: any(f == p or f.startswith(p.rstrip("/") + "/")
                          for p in m["paths"])  # noqa: E731
    for section, keys in KEYS.items():
        names = [e.get("name") for e in m[section]]
        _need(len(set(names)) == len(names), f"{section}: a name twice")
        for e in m[section]:
            extra = set(e) - keys - ({"workloads"} if section in (
                "end_to_end", "per_layer") else set())
            _need(not extra and keys <= set(e),
                  f"{section} {e.get('name')!r}: keys must be {sorted(keys)}"
                  f", got {sorted(e)}")
            _need(NAME.match(e["name"]) is not None,
                  f"{section}: bad name {e['name']!r}")
    metric_names = [e["name"] for e in m["end_to_end"] + m["per_layer"]]
    _need(len(set(metric_names)) == len(metric_names),
          "a metric name is used twice")
    _need(1 <= len(m["configs"]) <= 24 and 1 <= len(m["workloads"]) <= 24
          and 1 <= len(m["end_to_end"]) <= 16
          and 1 <= len(m["per_layer"]) <= 128, "a section's size")

    configs = {c["name"]: c for c in m["configs"]}
    files = [c["file"] for c in m["configs"]]
    _need(len(set(files)) == len(files), "two configurations share a file")
    for c in m["configs"]:
        _line(c["source"], f"config {c['name']} source")
        _line(c["why"], f"config {c['name']} why")
        _need(under(c["file"]), f"config file {c['file']} outside paths")
        _need(os.path.isfile(os.path.join(root, c["file"])),
              f"config file {c['file']} does not exist")
        _need(len(c["reduced"]) <= 16 and all(
            NAME.match(k) for k in c["reduced"]), f"{c['name']}: reduced")
    cells = {w["name"]: w for w in m["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    _need(len(set(pairs)) == len(pairs), "a (config, traffic) pair twice")
    for w in m["workloads"]:
        _need(w["config"] in configs, f"cell {w['name']}: unknown config")
        _need(NAME.match(w["traffic"]) is not None,
              f"cell {w['name']}: bad traffic name")
        _need(w["chips"] in (1, 4), f"cell {w['name']}: chips 1 or 4")
        _line(w["why"], f"cell {w['name']} why")
        traffic = os.path.join(root, "perfbench", "traffic",
                               w["traffic"] + ".json")
        _need(os.path.isfile(traffic),
              f"cell {w['name']}: no traffic file {w['traffic']}.json")
        with open(traffic) as f:
            _why_agrees_with_traffic(w, json.load(f))
    for c in configs:
        _need(any(w["config"] == c for w in m["workloads"]),
              f"configuration {c} has no cell")
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    _need(four <= max(1, len(cells) // 2),
          f"{four} of {len(cells)} cells ask for 4 chips")

    e2e = {e["name"]: e for e in m["end_to_end"]}
    _need("setup_s" in e2e, "end_to_end lacks setup_s")
    for e in m["end_to_end"] + m["per_layer"]:
        _need(UNIT.match(e["unit"]) is not None, f"{e['name']}: bad unit")
        _need(e["better"] in ("lower", "higher"), f"{e['name']}: better")
        _need(e["source"] in SOURCES, f"{e['name']}: source")
        for cell in e.get("workloads", []):
            _need(cell in cells, f"{e['name']}: unknown cell {cell!r}")
    for e in m["end_to_end"]:
        _need(e["source"] in ("host_clock", "device_trace"),
              f"{e['name']}: an end-to-end source is host_clock or "
              "device_trace")
        _need(isinstance(e["bound"], (int, float))
              and 0.01 <= e["bound"] <= 0.25,
              f"{e['name']}: bound from 0.01 to 0.25")
    for name in cells:
        has = [e["name"] for e in m["end_to_end"]
               if name in metric_cells(m, e)]
        _need("setup_s" in has and len(has) >= 2,
              f"cell {name} reports {has}: setup_s and one more needed")
        _need(any(name in metric_cells(m, p) for p in m["per_layer"]),
              f"cell {name} reports no per-layer metric")
    for p in m["per_layer"]:
        _line(p["layer"], f"{p['name']} layer")
        _need(p["moves"] in e2e, f"{p['name']} moves unknown "
                                 f"{p['moves']!r}")
        moved = set(metric_cells(m, e2e[p["moves"]]))
        for cell in metric_cells(m, p):
            # the rule PR 22 was refused for
            _need(cell in moved,
                  f"per_layer metric {p['name']} is reported on workload "
                  f"{cell}, where {p['moves']}, which it should move, "
                  "is not")
        path = os.path.join(root, "perfbench", "metrics",
                            p["name"] + ".json")
        _need(os.path.isfile(path), f"{p['name']}: no metrics/"
                                    f"{p['name']}.json")
        with open(path) as f:
            mf = json.load(f)
        for k in ("unit", "better", "source", "layer", "moves"):
            _need(mf.get(k) == p[k], f"{p['name']}: {k} differs between "
                                     "BENCHMARK.json and its metrics file")
        _need(os.path.isfile(os.path.join(
            root, "perfbench", "readers", mf["reader"] + ".py")),
            f"{p['name']}: no reader {mf['reader']}.py")
    _need(len(json.dumps(m)) <= 64 * 1024, "BENCHMARK.json over 64 KiB")


def main() -> int:
    try:
        check(load())
    except ManifestError as e:
        print(f"BENCHMARK.json: {e}", file=sys.stderr)
        return 1
    print("BENCHMARK.json: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
