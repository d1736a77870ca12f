"""bytes_needed: the operand bytes behind ``hbm_roof_pct``, at three
hand-worked queries, the peaks table, and the guard on a share over
100%."""

import json
import os

import numpy as np
import pytest

from perfbench import bytes_needed, check_manifest
from perfbench.capture import Capture
from perfbench.loadgen import Record

# two shards: a dense plane is 2 x 2^20 / 8 = 262,144 bytes, and the
# int field's 18 planes are dense
META = {"int_bytes": {"fare": 18 * 262144},
        "row_bytes": {"demo": np.array([262144, 262144, 262144]),
                      "trait": np.array([4200, 24, 32768, 4200]),
                      "month": np.array([262144] * 12)}}


@pytest.mark.parametrize("q,want", [
    # a sparse tree: an array row, a run row, a bitmap row, each once
    (["count", ["or", ["and", ["row", "trait", 0], ["row", "trait", 1]],
                ["row", "trait", 2]]], 4200 + 24 + 32768),
    # TopN over 12 month rows under a dense filter row
    (["topn", "month", 5, ["row", "demo", 1]], 12 * 262144 + 262144),
    # Sum over an 18-plane int field where a second field's range holds
    (["sum", "fare", ["and", ["row", "demo", 0],
                      ["cmp", "fare", ">", 500]]],
     18 * 262144 + 262144 + 18 * 262144),
    (["groupby", ["month", "demo"], None], 12 * 262144 + 3 * 262144),
    (["count", ["between", "fare", 1, 9]], 18 * 262144),
])
def test_hand_worked_queries(q, want):
    assert bytes_needed.read_bytes(META, q) == want


def test_share_of_the_roof():
    # 819 MB needed in 1 ms busy is the whole roof of a v5e
    assert bytes_needed.roof_share_pct(819e6, 1e-3, 819) == pytest.approx(100)
    assert bytes_needed.roof_share_pct(81.9e6, 1e-2, 819) == pytest.approx(1)


def test_a_share_over_100_fails_loudly():
    with pytest.raises(ValueError, match="over 100%"):
        bytes_needed.roof_share_pct(1e9, 1e-3, 819)


def _capture(kind: str, busy_s: float) -> Capture:
    q = ["count", ["row", "demo", 0]]
    rec = Record(0, due=1.0, sent=1.0, done=1.01, status=200,
                 profile={"cached": False})
    with open(os.path.join(check_manifest.ROOT, "perfbench",
                           "peaks.json")) as f:
        peaks = json.load(f)
    return Capture(records=[rec], queries=[q], meta=META,
                   devices_before={}, devices_after={}, device_kind=kind,
                   peaks=peaks, trace={"devices": 1, "busy_s": busy_s},
                   trace_span=(0.5, 4.5))


def test_the_reader_divides_needed_bytes_by_busy_time_and_the_peak():
    from perfbench.readers import hbm_roof_pct

    # 262,144 B in 1 ms = 0.262 GB/s of 819
    assert hbm_roof_pct.read(_capture("TPU v5 lite", 1e-3)) == \
        pytest.approx(262144 / 1e-3 / 819e9 * 100)
    with pytest.raises(ValueError, match="over 100%"):
        hbm_roof_pct.read(_capture("TPU v5 lite", 1e-9))


def test_an_unknown_device_kind_is_an_error_not_a_default():
    from perfbench.readers import hbm_roof_pct

    with pytest.raises(KeyError, match="TPU v9"):
        hbm_roof_pct.read(_capture("TPU v9", 1e-3))


def test_peaks_name_their_source():
    with open(os.path.join(check_manifest.ROOT, "perfbench",
                           "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["TPU v5 lite"]["hbm_gbps"] == 819
    assert all("source" in p for p in peaks.values())


def test_readers_read_what_the_flight_records_say():
    from perfbench.readers import (cache_hit_pct, coalesce_batch, exec_ms,
                                   front_ms, launches_per_read)

    def rec(sent, done, **profile):
        return Record(0, due=sent, sent=sent, done=done, status=200,
                      profile=profile)
    cap = _capture("TPU v5 lite", 1e-3)
    cap.records = [
        rec(0.0, 0.010, elapsedMs=8.0, cached=False, deviceLaunches=1,
            path="coalesced", coalescer={"batch": 4}),
        rec(0.1, 0.130, elapsedMs=26.0, cached=False, deviceLaunches=3,
            path="fused"),
        rec(0.2, 0.201, elapsedMs=0.4, cached=True, deviceLaunches=0),
        Record(0, due=0.3, sent=0.3, done=0.4, status=503)]
    assert cache_hit_pct.read(cap) == pytest.approx(100 / 3)
    assert launches_per_read.read(cap) == 2.0
    assert coalesce_batch.read(cap) == 2.5  # 4 coalesced, 1 alone
    assert exec_ms.read(cap) == 17.0
    assert front_ms.read(cap) == pytest.approx(2.0)  # of 2.0, 4.0, 0.6
    cap.records = cap.records[2:]
    assert exec_ms.read(cap) is None and coalesce_batch.read(cap) is None


def test_sparse_rows_and_planes_count_in_their_roaring_form():
    """Operands in the smaller of their roaring and their dense form: a
    demo row under 4096 bits a container is an array (2 B a bit), and
    an int field with values on one ride in 64 has array planes."""
    from test_perfbench_oracle import rehearsal_dataset

    cfg, ds = rehearsal_dataset("segmentation-134m")
    dense = ds.n_cols // 8
    fills = [count_row / ds.n_cols for count_row in
             (int(np.bitwise_count(ds.row("demo", r)).sum())
              for r in range(ds.n_rows["demo"]))]
    for r, fill in enumerate(fills):
        if abs(fill - 0.0625) < 0.005:
            continue  # on the edge: containers of both kinds
        want = dense if fill > 0.0625 else 2 * fill * ds.n_cols
        assert ds.row_bytes["demo"][r] == pytest.approx(want, rel=0.05), r
    assert min(fills) < 0.0625 < max(fills)
    _, taxi = rehearsal_dataset("taxi-rehearse")
    n_vals = len(taxi.values["total_amount"][0])
    assert 2 * n_vals < dense  # one ride in 64: every plane an array
    bits = taxi.values["total_amount"][1]
    want = 2 * (n_vals + sum(int(((bits >> b) & 1).sum())
                             for b in range(17)))
    assert taxi.meta()["int_bytes"]["total_amount"] == want
