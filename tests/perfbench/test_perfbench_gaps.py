"""PR 43's four readers (``unattributed_ms``, ``compile_stall_ms``,
``compile_cold_in_window``, ``compile_blocked_reads``), the gap table's
pieces and the rule for a blocked read, on records written by hand.

One launched read, G (ms relative to its root, which starts at 100 ms on
the server's clock): root 0-2.4, http.read 0.1-0.2, api.open 0.2-0.3,
exec 0.5-2.1 with call.Count 0.6-2.0 holding stage 0.7-0.8, launch
0.9-1.9 (dispatch 0.9-1.2, ready 1.2-1.9); serialize 2.2-2.3.  Only the
envelopes cover 0-0.1, 0.3-0.5, 2.1-2.2 and 2.3-2.4 (the root), 0.5-0.6
and 2.0-2.1 (exec), 0.6-0.7, 0.8-0.9 and 1.9-2.0 (call.Count): 1.0 ms in
the nine pieces of WANT_PIECES."""

import importlib

import pytest

from perfbench import compiles, gaps
from perfbench.capture import Capture
from perfbench.loadgen import Record

MS = 1_000_000


def sp(sid, parent, name, lo, hi, **counts):
    return {"id": sid, "parent": parent, "name": name,
            "startNs": round(lo * MS), "endNs": round(hi * MS),
            "thread": 1, **counts}


G = [sp(1, 0, "http.request", 0, 2.4), sp(2, 1, "http.read", 0.1, 0.2),
     sp(3, 1, "api.open", 0.2, 0.3), sp(4, 1, "exec", 0.5, 2.1),
     sp(5, 4, "call.Count", 0.6, 2.0), sp(6, 5, "stage", 0.7, 0.8),
     sp(7, 5, "launch", 0.9, 1.9), sp(8, 7, "launch.dispatch", 0.9, 1.2),
     sp(9, 7, "launch.ready", 1.2, 1.9), sp(10, 1, "serialize", 2.2, 2.3)]

WANT_PIECES = {
    ("http.request", "start", "http.read"): 0.1,
    ("http.request", "api.open", "exec"): 0.2,
    ("exec", "start", "call.Count"): 0.1,
    ("call.Count", "start", "stage"): 0.1,
    ("call.Count", "stage", "launch"): 0.1,
    ("call.Count", "launch", "end"): 0.1,
    ("exec", "call.Count", "end"): 0.1,
    ("http.request", "exec", "serialize"): 0.1,
    ("http.request", "serialize", "end"): 0.1,
}


def rec(spans, start_ms, cached=False, trace="t0"):
    prof = {"cached": cached, "elapsedMs": 1.0, "traceID": trace,
            "spans": spans, "rootStartNs": round(start_ms * MS)}
    return Record(query=0, due=0.0, sent=0.0, done=0.01, status=200,
                  result=0, profile=prof)


def event(lo_ms, hi_ms, persistent="miss", rid=None, kernel="k"):
    return {"kernel": kernel, "shape": "(int32[4])",
            "startNs": round(lo_ms * MS), "endNs": round(hi_ms * MS),
            "ms": hi_ms - lo_ms, "thread": 9, "rid": rid,
            "traceMs": 0.1, "lowerMs": 0.1, "backendMs": 0.1,
            "persistent": persistent}


def capture(records, events=None):
    after = {"compile": {"total": 0}}
    if events is not None:
        after["compile"]["events"] = events
    return Capture(records=records, queries=[], meta={},
                   devices_before={}, devices_after=after,
                   device_kind="TPU v5 lite", peaks={})


def read(name, cap):
    return importlib.import_module("perfbench.readers." + name).read(cap)


def test_pieces_of_a_read_with_envelope_only_time():
    got = gaps.pieces(G)
    assert got == pytest.approx(WANT_PIECES)
    assert sum(got.values()) == pytest.approx(gaps.unattributed_ms(G))


def test_a_zero_long_span_is_an_edge_and_a_cut_of_no_span_is_not():
    """``coalesce.wait`` is written even when zero long: the glue on
    either side of it is keyed by it.  A span of another thread that
    only crosses a piece does not cut it."""
    spans = [sp(1, 0, "exec", 0, 1.0), sp(2, 1, "stage", 0.1, 0.2),
             sp(3, 1, "coalesce.wait", 0.3, 0.3),
             sp(4, 1, "launch", 0.4, 0.9)]
    assert gaps.pieces(spans) == pytest.approx({
        ("exec", "start", "stage"): 0.1,
        ("exec", "stage", "coalesce.wait"): 0.1,
        ("exec", "coalesce.wait", "launch"): 0.1,
        ("exec", "launch", "end"): 0.1})


# ``unattributed_ms``: G reads 1.0; H, the same read with 0.4 ms of it
# named (0.3-0.5 and 2.0-2.2 under spans), reads 0.6; a cached read
# does not count: the median of the launched reads is 0.8.
H = G + [sp(11, 1, "pql.parse", 0.3, 0.5), sp(12, 1, "api.close", 2.0, 2.2)]

# The window on the server's clock: G at 100 ms (to 102.4), H at 200 ms
# (to 202.4).  Compile events, ms on that clock: one before the window
# (10-60), one cold inside it (150-160), one warm inside it overlapping
# the cold one (155-170): the two in the window cover 150-170 = 20 ms.
EVENTS = [event(10, 60), event(150, 160), event(155, 170, "hit")]

WANT = {
    "unattributed_ms": 0.8,
    "compile_stall_ms": 20.0,
    "compile_cold_in_window": 1.0,
    "compile_blocked_reads": 0.0,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_new_reader_on_hand_made_records(name):
    cap = capture([rec(G, 100), rec(H, 200), rec(G, 120, cached=True)],
                  EVENTS)
    assert read(name, cap) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_new_reader_finds_nothing_on_the_parent(name):
    """The parent's ``/debug/devices`` has no ``compile.events`` and a
    program before PR 24 no spans: None, nothing raised, and the line
    leaves the metric out."""
    bare = Record(query=0, due=0.0, sent=0.0, done=0.01, status=200,
                  result=0, profile={"cached": False})
    assert read(name, capture([bare], EVENTS)) is None
    assert read(name, capture([])) is None
    if name != "unattributed_ms":
        assert read(name, capture([rec(G, 100)])) is None
        assert read(name, capture([rec(G, 100)], [])) == 0.0


def long_read(root_ms, wait_lo, wait_hi, **counts):
    """A read whose root is ``root_ms`` long with one wait inside."""
    return [sp(1, 0, "http.request", 0, root_ms),
            sp(2, 1, "exec", 0.2, root_ms - 0.2),
            sp(3, 2, "coalesce.wait", wait_lo, wait_hi, **counts)]


#: a compile runs from 1000 to 1100 ms.  Roots that reach into it by
#: 4 ms and by 6 ms; one that sat in ``coalesce.wait`` through it; the
#: read that paid for it (its trace id is the event's ``rid``)
BLOCKED = {
    "overlaps by 4 ms": (rec(long_read(10, 1, 2), 994), None),
    "overlaps by 6 ms": (rec(long_read(10, 1, 2), 996), "exec"),
    "waits in coalesce.wait": (
        rec(long_read(120, 1, 115, why="cap"), 995), "coalesce.wait (cap)"),
    "paid for it": (rec(long_read(120, 1, 115), 995, trace="payer"), None),
}


@pytest.mark.parametrize("case", sorted(BLOCKED))
def test_a_read_is_blocked_from_5_ms_of_overlap(case):
    record, where = BLOCKED[case]
    ev = event(1000, 1100, rid="payer")
    stood = compiles.blocked([record], [ev])
    assert [w for _, _, w in stood] == ([] if where is None else [where])
    # a window needs two ends: a second, short read after the compile
    cap = capture([record, rec(G, 1200)], [ev])
    assert read("compile_blocked_reads", cap) == float(where is not None)


def test_the_new_metrics_have_their_files():
    """Reader and metric file of each are there, the metric file in the
    form of a ``per_layer`` entry.  ``BENCHMARK.json`` lists none of
    them yet: a new entry goes to the list's end, and
    ``test_perfbench_spans.py`` pins PR 24's nine there (``PERF.md``
    section 7 t).  Where a later PR lists one, it is the file's entry."""
    import importlib
    import json
    import os

    from perfbench import check_manifest

    entries = {p["name"]: p for p in check_manifest.load()["per_layer"]}
    for name in WANT:
        with open(os.path.join(check_manifest.ROOT, "perfbench", "metrics",
                               name + ".json")) as f:
            meta = json.load(f)
        assert meta["reader"] == meta["name"] == name
        assert set(meta) == check_manifest.KEYS["per_layer"] | {"reader"}
        assert meta["moves"] in ("read_p50_ms", "read_p95_ms")
        assert callable(importlib.import_module(
            "perfbench.readers." + name).read)
        entry = entries.get(name, {})
        assert {k: meta[k] for k in entry} == entry
