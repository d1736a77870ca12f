"""BENCHMARK.json against the rules a manifest is refused for, before
the driver has to: the committed manifest passes, and each rule trips
on a manifest that breaks it."""

import copy
import json
import os
import shutil

import pytest

from perfbench import check_manifest
from perfbench.check_manifest import ManifestError, check, load

ROOT = check_manifest.ROOT


@pytest.fixture
def manifest():
    return copy.deepcopy(load())


@pytest.fixture
def wide(tmp_path):
    """(manifest, root): the committed manifest with two more cells,
    each a new traffic file and a new entry and nothing else, in a copy
    of the benchmark's files -- what a later PR's addition looks like."""
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    m = copy.deepcopy(load())
    first = m["workloads"][0]
    for name in ("second", "third"):
        shutil.copy(tmp_path / "perfbench" / "traffic"
                    / (first["traffic"] + ".json"),
                    tmp_path / "perfbench" / "traffic" / (name + ".json"))
        m["workloads"].append({**first, "name": name, "traffic": name})
    return m, str(tmp_path)


def test_the_committed_manifest_passes(manifest):
    check(manifest)


def test_a_new_cell_is_a_traffic_file_and_an_entry(wide):
    check(*wide)


def test_every_cell_reports_every_end_to_end_metric(manifest):
    """ISSUE 23's rule: no metric names its cells, so every end-to-end
    metric is reported in every cell, every ``moves`` arrow is
    admissible in any cell, and a new cell clones no metric."""
    e2e = {e["name"] for e in manifest["end_to_end"]}
    assert {"read_p50_ms", "goodput_qps", "setup_s"} <= e2e
    for e in manifest["end_to_end"] + manifest["per_layer"]:
        assert "workloads" not in e, e["name"]
    assert all(w["chips"] == 1 for w in manifest["workloads"])


def test_pr_22s_fault_is_refused(wide):
    """A per-layer metric reported in a cell where the metric it moves
    is not."""
    manifest, root = wide
    cells = [w["name"] for w in manifest["workloads"]]
    moved = manifest["per_layer"][0]["moves"]
    next(e for e in manifest["end_to_end"]
         if e["name"] == moved)["workloads"] = cells[:1]
    with pytest.raises(ManifestError, match="which it should move, is not"):
        check(manifest, root)


def _break(m, what):
    if what == "name with a space":
        m["workloads"][0]["name"] = "seg traits"
    elif what == "name starting with a dot":
        m["per_layer"][0]["name"] = ".front"
    elif what == "name of 65 characters":
        m["configs"][0]["name"] = "c" * 65
    elif what == "unit with a space":
        m["end_to_end"][0]["unit"] = "answers per s"
    elif what == "unit of 17 characters":
        m["end_to_end"][0]["unit"] = "u" * 17
    elif what == "unit with a Greek letter":
        m["per_layer"][0]["unit"] = "µs"
    elif what == "configuration without a cell":
        m["configs"].append({**m["configs"][0], "name": "spare",
                             "file": "perfbench/peaks.json"})
    elif what == "too many four-chip cells":
        for w in m["workloads"][:2]:
            w["chips"] = 4
    elif what == "missing traffic file":
        m["workloads"][0]["traffic"] = "no-such-mix"
    elif what == "missing config file":
        m["configs"][0]["file"] = "perfbench/configs/none.json"
    elif what == "config file outside paths":
        m["configs"][0]["file"] = "BASELINE.json"
    elif what == "no setup_s":
        m["end_to_end"] = [e for e in m["end_to_end"]
                           if e["name"] != "setup_s"]
        for p in m["per_layer"]:
            if p["moves"] == "setup_s":
                p["moves"] = "read_p50_ms"
    elif what == "bound over a quarter":
        m["end_to_end"][0]["bound"] = 0.3
    elif what == "an extra key on a metric":
        m["per_layer"][0]["why"] = "because"
    elif what == "an unknown moved metric":
        m["per_layer"][0]["moves"] = "throughput"
    elif what == "an unknown cell on a metric":
        m["per_layer"][0]["workloads"] = ["seg-nothing"]
    elif what == "a metric name twice":
        m["per_layer"][1]["name"] = m["end_to_end"][0]["name"]
    elif what == "a pair of config and traffic twice":
        m["workloads"].append({**m["workloads"][0], "name": "again"})
    elif what == "run_seconds of 52":
        m["run_seconds"] = 52
    elif what == "a command out of the repo":
        m["command"] = ["python3", "../elsewhere/run.py"]
    elif what == "an end-to-end metric from a program counter":
        m["end_to_end"][0]["source"] = "program_counter"
    elif what == "metrics file disagrees":
        m["per_layer"][0]["layer"] = "Some other layer"
    elif what == "a cell with no per-layer metric":
        name = m["workloads"][0]["name"]
        for p in m["per_layer"]:
            p["workloads"] = [c for c in check_manifest.metric_cells(m, p)
                              if c != name]
    else:
        raise AssertionError(what)


@pytest.mark.parametrize("what", [
    "name with a space", "name starting with a dot", "name of 65 characters",
    "unit with a space", "unit of 17 characters", "unit with a Greek letter",
    "configuration without a cell", "too many four-chip cells",
    "missing traffic file", "missing config file",
    "config file outside paths", "no setup_s", "bound over a quarter",
    "an extra key on a metric", "an unknown moved metric",
    "an unknown cell on a metric", "a metric name twice",
    "a pair of config and traffic twice", "run_seconds of 52",
    "a command out of the repo",
    "an end-to-end metric from a program counter",
    "metrics file disagrees", "a cell with no per-layer metric"])
def test_a_broken_manifest_is_refused(wide, what):
    manifest, root = wide
    _break(manifest, what)
    with pytest.raises(ManifestError):
        check(manifest, root)


def _retell(root, cell, why=None, **traffic):
    """Rewrites a cell's ``why`` and keys of its traffic file in the
    copy under ``root``."""
    if why is not None:
        cell["why"] = why
    path = os.path.join(root, "perfbench", "traffic",
                        cell["traffic"] + ".json")
    with open(path) as f:
        t = json.load(f)
    t.update(traffic)
    with open(path, "w") as f:
        json.dump(t, f)
    return t


SWEEP = {"columns": ["rate_qps", "read_p95_ms", "unfinished_at_close"],
         "rows": [[240, 9.0, 0], [80, 5.0, 0], [160, 6.0, 0],
                  [320, 31.0, 0], [400, 20.0, 7]]}


@pytest.mark.parametrize("why, rate, refused", [
    ("open loop at 96/s, 2/5 of the 240/s knee: Count trees", 96.0, None),
    ("open loop at 192/s, 4/5 of the knee: Count trees", 192.0, None),
    ("open loop at 192/s, four fifths of its knee", 192.0, None),
    ("open loop at 120/s, half of the 240/s knee", 120.0, None),
    ("open loop at 200/s, about 4/5 of a 240/s knee", 200.0, None),
    ("open loop, Count trees, no share of anything named", 96.0, None),
    # what seg-dense said from PR 25 to PR 38
    ("open loop at 96/s, 4/5 of the knee: Count trees", 96.0,
     "is 0.40 of the 240/s"),
    ("open loop at 96/s, 4/5 of the 120/s knee", 96.0,
     "says a 120/s knee, its sweep's rows 240/s"),
    ("open loop at 96/s, 2/5 of the 240/s knee", 192.0,
     "says 96/s, its traffic file rate_qps 192"),
    ("2/5 of the knee", 120.0, "is 0.50 of the 240/s"),
])
def test_a_why_that_names_a_share_of_a_knee_agrees_with_the_traffic_file(
        wide, why, rate, refused):
    manifest, root = wide
    _retell(root, manifest["workloads"][1], why, rate_qps=rate,
            latency_limit_ms=25, sweep=SWEEP)
    if refused is None:
        check(manifest, root)
    else:
        with pytest.raises(ManifestError, match=refused):
            check(manifest, root)


def test_a_knee_needs_a_step_that_held_the_limit(wide):
    manifest, root = wide
    t = _retell(root, manifest["workloads"][1], "4/5 of the knee",
                latency_limit_ms=4, sweep=SWEEP)
    assert check_manifest.knee_of(t) is None
    with pytest.raises(ManifestError, match="no step of"):
        check(manifest, root)


def test_the_knee_is_the_highest_step_that_held_with_all_under_it():
    t = {"latency_limit_ms": 25, "sweep": SWEEP}
    assert check_manifest.knee_of(t) == 240.0   # 400/s held p95, not the close
    assert check_manifest.knee_of({**t, "latency_limit_ms": 5.5}) == 80.0
    assert check_manifest.knee_of({"latency_limit_ms": 25}) is None


def test_one_four_chip_cell_is_always_allowed(manifest):
    manifest["workloads"][0]["chips"] = 4
    check(manifest)


def test_configuration_files_state_what_the_manifest_says(manifest):
    for c in manifest["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert cfg["shard_width_exp"] == 20 and cfg["container_bits"] == 65536
        assert cfg["shards"] << 20 == cfg["columns"]
        for key in c["reduced"]:
            assert cfg[key] != cfg["reduced_from"][key], key
        assert set(cfg["reduced_from"]) == set(c["reduced"])
        assert cfg["guarantees"]["answers"].startswith("exact")
        assert cfg["assumed"]


def test_traffic_files_fix_their_load(manifest):
    for w in manifest["workloads"]:
        with open(os.path.join(ROOT, "perfbench", "traffic",
                               w["traffic"] + ".json")) as f:
            t = json.load(f)
        assert t["loop"] in ("open", "closed")
        assert ("rate_qps" in t) == (t["loop"] == "open")
        assert ("clients" in t) == (t["loop"] == "closed")
        assert t["warmup_requests"] > 0 and t["warmup_concurrency"] > 0
        assert t["latency_limit_ms"] > 0 and t["oracle_sample"] > 0
        assert os.path.isfile(os.path.join(
            ROOT, "perfbench", "querygen", t["family"] + ".py"))


def test_every_reader_is_a_module_with_read(manifest):
    import importlib

    for p in manifest["per_layer"]:
        with open(os.path.join(ROOT, "perfbench", "metrics",
                               p["name"] + ".json")) as f:
            reader = json.load(f)["reader"]
        assert callable(importlib.import_module(
            "perfbench.readers." + reader).read)


def test_nothing_but_the_benchmark_lives_under_its_paths(manifest):
    assert manifest["paths"] == ["perfbench", "tests/perfbench"]
    assert manifest["command"] == ["python3", "perfbench/run.py"]
