"""The compiles of a window, and the reads that stood behind them.

The program keeps its newest compile events on ``/debug/devices``
``compile.events``: ``kernel``, ``shape``, ``startNs`` and ``endNs`` on
the span clock (the clock of ``rootStartNs``), ``thread``, ``rid`` (the
trace id of the read that paid, or null), ``ms``, ``traceMs``,
``lowerMs``, ``backendMs`` and ``persistent`` (``hit``, ``miss`` or
``off``: what the persistent compile cache said).  A program that
predates them gives ``None`` everywhere, and the reader then leaves its
metric out.

Who stood behind a compile is reckoned, not recorded: a read that did
not pay for the event and whose root overlaps it by ``BLOCKED_NS`` or
more was blocked, and the span of its own tree whose self time covers
most of the overlap says where."""

from __future__ import annotations

from perfbench import spans as sp

BLOCKED_NS = 5_000_000


def in_window(cap) -> list[dict] | None:
    """The compile events that start inside the window (first root
    start to last root end of the profiled reads), oldest first."""
    events = cap.devices_after.get("compile", {}).get("events")
    t = sp.timeline(cap.profiled())
    if events is None or t is None:
        return None
    lo, hi = t[0], t[1]
    return sorted((e for e in events if lo <= e["startNs"] < hi),
                  key=lambda e: e["startNs"])


def stall_ms(events: list[dict]) -> float:
    """Length of the union of the events' intervals."""
    return sp.union([(e["startNs"], e["endNs"]) for e in events]) / 1e6


def waited_in(spans: list[dict], t0: int, lo: int, hi: int) -> str:
    """The span of one read (times relative to ``t0``) whose self time
    covers most of [lo, hi) on the span clock."""
    def inside(s) -> int:
        return max(0, min(t0 + s["endNs"], hi) - max(t0 + s["startNs"], lo))

    def self_inside(s) -> int:
        kids = [(max(t0 + c["startNs"], lo), min(t0 + c["endNs"], hi))
                for c in spans if c["parent"] == s["id"]]
        return inside(s) - sp.union([k for k in kids if k[1] > k[0]])

    best = max(spans, key=self_inside)
    return best["name"] + (f" ({best['why']})" if "why" in best else "")


def reads(stood: list[tuple]) -> int:
    """How many distinct reads :func:`blocked` found (a read behind
    two events counts once)."""
    return len({id(r) for _, r, _ in stood})


def blocked(records, events: list[dict]) -> list[tuple[dict, object, str]]:
    """(event, record, the span it waited in) for every read blocked by
    an event it did not pay for."""
    out = []
    for r in records:
        spans = sp.of(r)
        if spans is None or "rootStartNs" not in r.profile:
            continue
        t0 = r.profile["rootStartNs"]
        top = sp.root(spans)
        lo, hi = t0 + top["startNs"], t0 + top["endNs"]
        for e in events:
            if e.get("rid") is not None \
                    and e["rid"] == r.profile.get("traceID"):
                continue
            a, b = max(lo, e["startNs"]), min(hi, e["endNs"])
            if b - a >= BLOCKED_NS:
                out.append((e, r, waited_in(spans, t0, a, b)))
    return out
