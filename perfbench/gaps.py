"""The time of a read that only an envelope covers, in pieces.

``unattributed_pct`` says what share of a root no phase names; this says
where that time lies.  A read's root is cut at every start and end of
its spans; a piece under no named span belongs to the innermost envelope
(``http.request``, ``exec``, ``call.<Name>``, ``map``, ``map.fused``)
open across it and is keyed ``(envelope, before, after)``: the span that
ended where the piece starts (``start``: the envelope itself opened
there) and the span that opens where it ends (``end``: the envelope
closed there).  Times come out in milliseconds."""

from __future__ import annotations

from perfbench import spans as sp
from perfbench.readers.unattributed_pct import ENVELOPES


def is_envelope(span: dict) -> bool:
    return span["name"] in ENVELOPES or span["name"].startswith("call.")


def clipped(spans: list[dict], top: dict) -> list[tuple[int, int, dict]]:
    """(start, end, span) of every span, cut to the root's interval."""
    return [(max(s["startNs"], top["startNs"]),
             min(s["endNs"], top["endNs"]), s) for s in spans]


def unattributed_ms(spans: list[dict]) -> float:
    """Root minus the union of the spans that are no envelope:
    ``unattributed_pct``'s arithmetic, in milliseconds."""
    top = sp.root(spans)
    named = [(lo, hi) for lo, hi, s in clipped(spans, top)
             if not is_envelope(s) and hi > lo]
    return (top["endNs"] - top["startNs"] - sp.union(named)) / 1e6


def pieces(spans: list[dict]) -> dict[tuple[str, str, str], float]:
    """{(envelope, before, after): ms} over one read's root; a key met
    twice in one read is summed."""
    top = sp.root(spans)
    cut = clipped(spans, top)
    named = [(lo, hi) for lo, hi, s in cut if not is_envelope(s)]
    shells = [(lo, hi, s) for lo, hi, s in cut if is_envelope(s)]
    points = sorted({p for lo, hi, _ in cut for p in (lo, hi)})
    found: list[list] = []  # [envelope (lo, hi, span), start, end]
    for a, b in zip(points, points[1:]):
        if any(lo <= a and b <= hi for lo, hi in named):
            continue
        over = [e for e in shells if e[0] <= a and b <= e[1]]
        if not over:
            continue
        # the innermost: opened last (the larger id breaks a tie)
        env = max(over, key=lambda e: (e[0], e[2]["id"]))
        if (found and found[-1][0] is env and found[-1][2] == a
                and _edge(cut, env, a, end=True) is None):
            found[-1][2] = b  # a cut that no span of this piece made
        else:
            found.append([env, a, b])
    out: dict[tuple[str, str, str], float] = {}
    for env, a, b in found:
        key = (env[2]["name"], _edge(cut, env, a, end=True) or "start",
               _edge(cut, env, b, end=False) or "end")
        out[key] = out.get(key, 0.0) + (b - a) / 1e6
    return out


def _edge(cut, env, at: int, end: bool) -> str | None:
    """The outermost span inside ``env`` that ends (or starts) at
    ``at``; None when only the envelope itself does."""
    lo0, hi0, shell = env
    best = None
    for lo, hi, s in cut:
        if s is shell or lo < lo0 or hi > hi0:
            continue
        if (hi if end else lo) == at and (best is None
                                          or hi - lo > best[0]):
            best = (hi - lo, s["name"])
    return None if best is None else best[1]
