"""Executor: the share of a read's root span that no phase names -- time
inside the envelopes (``http.request``, ``exec``, ``call.<Name>``,
``map``, ``map.fused``) that none of the spans beneath them covers --
mean over the reads that launched."""

from perfbench import spans as sp

ENVELOPES = ("http.request", "exec", "map", "map.fused")


def read(cap):
    shares = []
    for spans in map(sp.of, cap.launched()):
        if spans is None:
            continue
        top = sp.root(spans)
        named = [(max(s["startNs"], top["startNs"]),
                  min(s["endNs"], top["endNs"]))
                 for s in spans if s["name"] not in ENVELOPES
                 and not s["name"].startswith("call.")]
        whole = top["endNs"] - top["startNs"]
        if whole > 0:
            shares.append(100.0 * (1.0 - sp.union(
                [iv for iv in named if iv[1] > iv[0]]) / whole))
    return sum(shares) / len(shares) if shares else None
