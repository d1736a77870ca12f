"""``perfbench/steady.py``: its arithmetic on recorded latencies and on
values at the edges of each threshold.  (Its rehearsal end to end is in
``test_perfbench_run.py``: every test that starts a run of ``seg-dense``
uses the one ``perfbench/.work/seg-dense`` and has to share a worker.)

``seg-dense-window.json`` is a window of the cell as the chip served it
(its origin is in the file); ``two-mode-window.json`` is built by
formula from the ledger's numbers of PR 38's ``taxi-dash``, whose
windows went with the PR, and says so."""

import json
import os
import statistics

import pytest

from perfbench import check_manifest, steady
from perfbench.loadgen import percentile

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def window(name):
    with open(os.path.join(DATA, name + ".json")) as f:
        return json.load(f)["latencies_ms"]


# ------------------------------------------------------------ the spread


@pytest.mark.parametrize("values, want", [
    # quartiles as statistics.quantiles(n=4) gives them, not numpy's
    ([1, 2, 3, 4, 5, 6, 7, 8], (6.75 - 2.25) / 4.5),
    ([10.0, 10.0, 10.0, 10.0], 0.0),
    ([3.30, 3.35, 3.32, 3.41, 3.29, 3.33], (3.365 - 3.2975) / 3.325),
])
def test_the_spread_is_the_drivers(values, want):
    assert steady.spread(values) == pytest.approx(want)
    q = statistics.quantiles(values, n=4)
    assert steady.spread(values) == pytest.approx(
        (q[2] - q[0]) / statistics.median(values))


@pytest.mark.parametrize("values, at_most", [
    ([3.30, 3.31, 3.32, 3.33, 3.34, 3.90], 0.2),   # one far off
    ([3.30, 3.30, 3.30, 3.36, 3.36, 3.36], 1.0),   # none: never wider
    ([3.30, 3.40], 1.0),                           # too few to leave one out
])
def test_the_farthest_run_is_left_out_where_that_narrows(values, at_most):
    full, cut = steady.spread(values), steady.trimmed_spread(values)
    assert cut <= at_most * full
    if len(values) < 3:
        assert cut == full


# ---------------------------------------------------------- the verdicts


@pytest.mark.parametrize("spread, halves, want", [
    (0.040, 0.000, "steady"),      # at half the bound
    (0.0401, 0.000, "marginal"),   # just over it
    (0.080, 0.000, "marginal"),    # at the whole bound
    (0.0801, 0.000, "noisy"),      # just over that
    (0.010, 0.040, "steady"),      # the halves at half the bound
    (0.010, 0.0401, "marginal"),
    (0.010, 0.080, "marginal"),
    (0.010, 0.0801, "noisy"),
    (None, 0.030, "steady"),       # setup_s: the halves alone
    (None, 0.090, "noisy"),
])
def test_verdicts_at_the_edges(spread, halves, want):
    assert steady.verdict(spread, halves, 0.08) == want


def test_a_metric_is_read_as_the_check_reads_two_sets():
    # odd runs (1st, 3rd, ...) sit 2% over the even ones
    values = [3.264, 3.2, 3.2641, 3.2001, 3.2642, 3.2002, 3.2643, 3.2003]
    got = steady.judge("read_p50_ms", values, 0.03)
    assert got["odd_median"] == pytest.approx(3.26415)
    assert got["even_median"] == pytest.approx(3.20015)
    assert got["halves_share_of_bound"] == pytest.approx(
        0.064 / statistics.median(values) / 0.03)
    assert got["verdict"] == "marginal"       # 0.66 of the bound apart
    assert steady.judge("read_p50_ms", values, 0.08)["verdict"] == "steady"
    assert steady.judge("read_p50_ms", values, 0.015)["verdict"] == "noisy"


def test_setup_s_leaves_out_the_run_that_compiles_and_its_spread():
    values = [95.0, 26.0, 31.0, 26.2, 31.2, 26.1, 31.1, 26.3, 31.3]
    got = steady.judge("setup_s", values, 0.25)
    assert got["first"] == 95.0 and len(got["values"]) == 8
    assert got["spread"] > 0.125     # over half the bound, and not judged
    assert got["verdict"] == "marginal"   # by the halves: 17% apart
    # the same numbers under a name that is judged by its spread too
    assert steady.judge("read_p95_ms", values[1:], 0.25)["verdict"] \
        == "marginal"
    assert steady.judge("read_p95_ms", values[1:], 0.17)["verdict"] == "noisy"
    assert steady.judge("setup_s", values, 0.17)["verdict"] == "noisy"


def test_fewer_than_four_runs_give_no_verdict():
    got = steady.judge("goodput_qps", [95.1, 95.3, 95.2], 0.04)
    assert got["verdict"] is None and "spread" not in got
    assert steady.judge("setup_s", [30.0, 27.0, 27.1, 27.2], 0.25)[
        "verdict"] is None  # three left once the first is out


# -------------------------------------------------------------- the band


@pytest.mark.parametrize("bound, reading", [
    (0.08, "dense"),   # a quarter of the window's reads
    (0.03, "thin"),    # the bound PRs 28-38 were judged under: under a tenth
])
def test_a_median_inside_a_mode_reads_a_dense_band(bound, reading):
    lat = window("seg-dense-window")
    assert len(lat) == 4896   # 96/s x 51 s
    got = steady.band(lat, 0.50, bound)
    assert got["value"] == percentile(lat, 0.50)
    assert got["reading"] == reading
    assert (got["share_pct"] >= 10.0) == (reading == "dense")
    assert got["share_pct"] > 2.0
    # neighbours a few microseconds apart all through the band
    assert got["widest_gap_ms"] < 0.1 * bound * got["value"]


@pytest.mark.parametrize("bound", [0.03, 0.08])
def test_a_median_between_two_modes_reads_a_near_empty_band(bound):
    lat = window("two-mode-window")
    cached = sum(1 for x in lat if x < 2.0) / len(lat)
    assert 0.47 < cached < 0.49 and min(x for x in lat if x > 2.0) >= 3.0
    got = steady.band(lat, 0.50, bound)
    assert got["reading"] == "near-empty" and got["share_pct"] < 2.0
    # a point of cache-hit share moves this median by more than the bound
    moved = abs(percentile(lat, 0.51) - percentile(lat, 0.49)) / got["value"]
    assert moved > bound
    dense = window("seg-dense-window")
    assert abs(percentile(dense, 0.51) - percentile(dense, 0.49)) \
        / percentile(dense, 0.50) < 0.03 / 2


def test_an_empty_side_of_the_band_is_a_gap():
    lat = [1.0] * 50 + [2.0] * 50          # the median is the last 1.0
    got = steady.band(lat, 0.50, 0.10)
    assert got["value"] == 1.0 and got["share_pct"] == 50.0
    assert got["widest_gap_ms"] == pytest.approx(0.1)  # 0.9 .. 1.0, 1.0 .. 1.1


# ------------------------------------------------------------ the report


def fake_runs(p50s, lat):
    return [{"values": {"read_p50_ms": v, "read_p95_ms": 5.0 + 0.01 * i,
                        "goodput_qps": 95.5 + 0.01 * i,
                        "setup_s": 27.0 + 0.1 * i},
             "latencies": lat, "correct": True, "failed": 0}
            for i, v in enumerate(p50s)]


@pytest.mark.parametrize("p50s, bound, want", [
    ([3.10, 3.12, 3.11, 3.13, 3.12, 3.10, 3.11, 3.12], 0.08, "steady"),
    ([3.10, 3.20, 3.05, 3.25, 3.12, 3.18, 3.02, 3.22], 0.08, "marginal"),
    ([3.10, 3.12, 3.11, 3.13, 3.12, 3.10, 3.11, 3.12], 0.005, "noisy"),
])
def test_the_cell_takes_its_worst_metrics_verdict(p50s, bound, want):
    manifest = check_manifest.load()
    next(e for e in manifest["end_to_end"]
         if e["name"] == "read_p50_ms")["bound"] = bound
    rep = steady.report(manifest, "seg-dense",
                        fake_runs(p50s, window("seg-dense-window")))
    assert set(rep["metrics"]) == {e["name"] for e in manifest["end_to_end"]}
    assert rep["metrics"]["read_p50_ms"]["verdict"] == want
    assert all(rep["metrics"][k]["verdict"] == "steady"
               for k in ("read_p95_ms", "goodput_qps", "setup_s"))
    assert rep["verdict"] == want
    if bound == 0.08:
        assert rep["metrics"]["read_p50_ms"]["band"]["reading"] == "dense"
    assert "band" in rep["metrics"]["read_p95_ms"]
    assert "band" not in rep["metrics"]["goodput_qps"]
    text = steady.table(rep)
    assert f"-> {want}" in text and text.endswith(f"cell: {want}")
