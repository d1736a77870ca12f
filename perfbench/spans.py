"""What the span readers share: a flight record's ``spans`` (the list
``?profile=1`` renders: ``id``, ``parent``, ``name``, ``startNs``,
``endNs`` relative to the root, ``thread``, counts) as durations, self
times and covered intervals.  Times come out in milliseconds.  A record
without ``spans`` (a program that predates them) gives ``None``
everywhere, and the reader then leaves its metric out."""

from __future__ import annotations


def of(record) -> list[dict] | None:
    """The spans of one window record, or None."""
    return (record.profile or {}).get("spans") or None


def root(spans: list[dict]) -> dict:
    return min((s for s in spans if not s["parent"]),
               key=lambda s: s["startNs"])


def ms(span: dict) -> float:
    return (span["endNs"] - span["startNs"]) / 1e6


def total(spans: list[dict], name: str) -> float:
    """Summed duration of the spans called ``name`` (0 with none)."""
    return sum(ms(s) for s in spans if s["name"] == name)


def self_total(spans: list[dict], name: str) -> float:
    """Summed self time of the spans called ``name``: each one's
    duration minus what its children cover."""
    out = 0.0
    for s in spans:
        if s["name"] == name:
            out += ms(s) - sum(ms(c) for c in spans
                               if c["parent"] == s["id"])
    return out


def union(intervals: list[tuple[int, int]]) -> int:
    """Length of the union of [start, end) intervals."""
    covered, reach = 0, None
    for lo, hi in sorted(intervals):
        if reach is None or lo > reach:
            covered += hi - lo
            reach = hi
        elif hi > reach:
            covered += hi - reach
            reach = hi
    return covered


def own_launches(spans: list[dict]) -> list[dict]:
    """The ``launch`` spans a read dispatched itself: a batch leader's
    or a lone read's, not a follower's copy of its leader's (those
    carry ``link``)."""
    return [s for s in spans if s["name"] == "launch" and "link" not in s]


def timeline(records) -> tuple[int, int, list, list] | None:
    """The window on the server's span clock: (start, end, root
    intervals, launch-in-flight intervals) over the records that carry
    ``rootStartNs`` and spans; None with none."""
    roots, flights = [], []
    for r in records:
        spans = of(r)
        if spans is None or "rootStartNs" not in r.profile:
            continue
        t0 = r.profile["rootStartNs"]
        top = root(spans)
        roots.append((t0 + top["startNs"], t0 + top["endNs"]))
        flights += [(t0 + s["startNs"], t0 + s["endNs"]) for s in spans
                    if s["name"] in ("launch.dispatch", "launch.ready")]
    if not roots:
        return None
    return (min(lo for lo, _ in roots), max(hi for _, hi in roots),
            roots, flights)
