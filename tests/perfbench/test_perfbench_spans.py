"""The nine span readers (ISSUE 24) on a capture written by hand.

Four reads on one server clock (``rootStartNs`` in ns; span times in
ms below, relative to each read's root):

- A, a batch leader: root 0-10, admission.wait 0-1, exec 2-9.5 with
  stage 2-3.5 (a nested range launch 2.5-3, its ready 2.7-3),
  coalesce.wait 3.5-5.5, launch 5.5-9 (stack 5.5-6, dispatch 6-6.5,
  ready 6.5-8.5), reduce 9-9.4, serialize 9.6-9.8.  Starts at 0 ms.
- B, its follower: root 0-9, admission.wait 0-0.5, exec 1-8.8 with stage
  1-2, coalesce.wait 2-4, launch 4-7.5 with ``link``, reduce 7.5-8.6.
  Starts at 1.5 ms.
- C, alone: root 0-4, exec 0.5-3.8 with stage 0.5-1, launch 1-3
  (dispatch 1-1.2, ready 1.2-2.6), reduce 3-3.6.  Starts at 20 ms.
- D, answered from the cache: root 0-1, admission.wait 0-0.1, exec
  0.2-0.8 with cache.probe 0.3-0.6.  Starts at 30 ms.
- E: a record without spans (a program that predates them).
"""

import importlib

import pytest

from perfbench.capture import Capture
from perfbench.loadgen import Record


def sp(sid, parent, name, lo, hi, **counts):
    return {"id": sid, "parent": parent, "name": name,
            "startNs": int(lo * 1e6), "endNs": int(hi * 1e6),
            "thread": 1, **counts}


A = [sp(1, 0, "http.request", 0, 10), sp(2, 1, "admission.wait", 0, 1),
     sp(3, 1, "exec", 2, 9.5), sp(4, 3, "stage", 2, 3.5),
     sp(5, 4, "launch", 2.5, 3), sp(6, 5, "launch.ready", 2.7, 3),
     sp(7, 3, "coalesce.wait", 3.5, 5.5), sp(8, 3, "launch", 5.5, 9),
     sp(9, 8, "launch.stack", 5.5, 6),
     sp(10, 8, "launch.dispatch", 6, 6.5),
     sp(11, 8, "launch.ready", 6.5, 8.5), sp(12, 3, "reduce", 9, 9.4),
     sp(13, 1, "serialize", 9.6, 9.8)]
B = [sp(1, 0, "http.request", 0, 9), sp(2, 1, "admission.wait", 0, 0.5),
     sp(3, 1, "exec", 1, 8.8), sp(4, 3, "stage", 1, 2),
     sp(5, 3, "coalesce.wait", 2, 4),
     sp(6, 3, "launch", 4, 7.5, link=["aa", 8]),
     sp(7, 3, "reduce", 7.5, 8.6)]
C = [sp(1, 0, "http.request", 0, 4), sp(2, 1, "exec", 0.5, 3.8),
     sp(3, 2, "stage", 0.5, 1), sp(4, 2, "launch", 1, 3),
     sp(5, 4, "launch.dispatch", 1, 1.2),
     sp(6, 4, "launch.ready", 1.2, 2.6), sp(7, 2, "reduce", 3, 3.6)]
D = [sp(1, 0, "http.request", 0, 1), sp(2, 1, "admission.wait", 0, 0.1),
     sp(3, 1, "exec", 0.2, 0.8), sp(4, 3, "cache.probe", 0.3, 0.6)]


def rec(spans, start_ms, cached=False):
    prof = {"cached": cached, "elapsedMs": 1.0}
    if spans is not None:
        prof.update(spans=spans, rootStartNs=int(start_ms * 1e6))
    return Record(query=0, due=0.0, sent=0.0, done=0.01, status=200,
                  result=0, profile=prof)


def capture(records):
    return Capture(records=records, queries=[], meta={},
                   devices_before={}, devices_after={},
                   device_kind="TPU v5 lite", peaks={})


FULL = [rec(A, 0), rec(B, 1.5), rec(C, 20), rec(D, 30, cached=True),
        rec(None, 40)]

# The window on the server's clock runs from 0 (A's root) to 31 ms (D's
# end).  Roots cover [0, 10.5] + [20, 24] + [30, 31] = 15.5 ms, so the
# server is empty for 15.5 of 31 ms.  A launch is in flight (dispatch or
# ready) over [2.7, 3] + [6, 8.5] + [21, 22.6] = 4.4 ms; the other 11.1
# ms with a request present are the host's.
WANT = {
    # root - exec - admission.wait: A 10-7.5-1, B 9-7.8-0.5, C 4-3.3,
    # D 1-0.6-0.1 -> median of 1.5, 0.7, 0.7, 0.3
    "handler_ms": 0.7,
    # 1, 0.5, 0, 0.1: nearest rank at 95% of four is the largest
    "admit_wait_ms": 1.0,
    # launched reads A, B, C: A's stage 1.5 minus its nested launch 0.5
    "stage_ms": 1.0,
    # A 2, B 2, C 0 (it ran alone)
    "coalesce_wait_ms": 2.0,
    # leaders and lone reads, A and C: A (0.5 + 3.5) - (0.3 + 2),
    # C 2 - 1.4 -> median of 1.7 and 0.6
    "launch_host_ms": 1.15,
    # A 0.3 + 2, C 1.4
    "device_wait_ms": 1.85,
    # what the phases (every span but the envelopes http.request, exec,
    # call.*, map, map.fused) cover of the root: A 1 + 1.5 + 2 + 3.5 +
    # 0.4 + 0.2 = 8.6 of 10 -> 14% left; B 0.5 + 1 + 2 + 3.5 + 1.1 = 8.1
    # of 9 -> 10%; C 0.5 + 2 + 0.6 = 3.1 of 4 -> 22.5%
    "unattributed_pct": 15.5,
    "host_bound_pct": 100 * 11.1 / 31,
    "server_empty_pct": 50.0,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_the_hand_written_capture(name):
    reader = importlib.import_module("perfbench.readers." + name)
    assert reader.read(capture(FULL)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_record_without_spans_gives_none(name):
    """The parent commit's flight records have no ``spans``: the reader
    returns None, raises nothing, and the result line leaves the metric
    out."""
    reader = importlib.import_module("perfbench.readers." + name)
    assert reader.read(capture([rec(None, 0), rec(None, 5)])) is None
    assert reader.read(capture([])) is None


def test_every_new_metric_has_its_file_and_entry():
    import json
    import os

    from perfbench import check_manifest

    m = check_manifest.load()
    entries = {p["name"]: p for p in m["per_layer"]}
    for name in WANT:
        with open(os.path.join(check_manifest.ROOT, "perfbench", "metrics",
                               name + ".json")) as f:
            meta = json.load(f)
        assert meta["reader"] == name and meta["source"] == "program_span"
        assert {k: meta[k] for k in entries[name]} == entries[name]
    # appended, so what was there keeps its place
    assert [p["name"] for p in m["per_layer"]][-9:] == [
        "handler_ms", "admit_wait_ms", "stage_ms", "coalesce_wait_ms",
        "launch_host_ms", "device_wait_ms", "unattributed_pct",
        "host_bound_pct", "server_empty_pct"]
