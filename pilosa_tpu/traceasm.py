"""Cross-node trace assembly: one causal span tree per query.

``GET /debug/trace/{id}`` fans flight records in from every node
(``parallel/cluster.fan_in`` + ``client.debug_json``, the
``/debug/cluster/*`` machinery) and this module joins them on the
normalized trace id into ONE tree — the origin record's own ``spans``
(observe.span: ids, parents, both times), nested as recorded:

    query/<index>              <- the record's root span (http.request
      admission.wait              behind a handler, else exec)
      pql.parse
      exec
        translate
        call.Count             <- engine enum, launch count, tier notes
          map                  <- per-node children from nodeTimings
            node/node1  — remote subtree attached when that node's own
            node/node2    flight record arrived in the fan-in
            node/node2 (hedge loser) — the abandoned side of a race
          reduce
        translateResults
      serialize
      (unattributed)           <- filler so child walls sum EXACTLY

Per-span wall times add up to the observed latency by construction:
each level carries an explicit ``(unattributed)`` child absorbing the
gap between the parent's wall and the sum of its children, so the
accounting identity ``observedMs == sum(leaf walls)`` holds and a
triage reader can see exactly how much time no span names.  Children
that ran beside their parent's thread (pool workers under ``map``,
per-node walls that overlap) are shown, marked ``concurrent``, and
left out of that sum: the parent's own wall already covers them.  Dead
peers degrade to an ``errors`` entry, same contract as
``/debug/cluster/*``.

Pure functions over already-fetched JSON sections — no I/O here; the
handler owns the fan-in and ticks ``observe.bump_trace`` counters.
"""

from __future__ import annotations

from pilosa_tpu import tracing as _tracing

#: Below this a filler span is measurement noise, not information.
_MIN_FILLER_MS = 0.005


def _span(name: str, ms: float, node: str = "", **attrs) -> dict:
    d = {"name": name, "ms": round(max(0.0, ms), 3)}
    if node:
        d["node"] = node
    d.update(attrs)
    d["children"] = []
    return d


def _serial(span: dict) -> list[dict]:
    return [c for c in span["children"] if not c.get("concurrent")]


def _fill(parent: dict) -> None:
    """Append the ``(unattributed)`` child absorbing the gap between
    the parent wall and its children's summed walls — the invariant
    that makes every level's walls add up."""
    kids = _serial(parent)
    gap = parent["ms"] - sum(c["ms"] for c in kids)
    if kids and gap > _MIN_FILLER_MS:
        parent["children"].append(
            _span("(unattributed)", gap, parent.get("node", "")))


def _leaf_sum(span: dict) -> float:
    kids = _serial(span)
    if not kids:
        return span["ms"]
    return sum(_leaf_sum(c) for c in kids)


def _record_tree(rec: dict, node: str, name: str,
                 remote_by_node: dict[str, list[dict]]) -> dict:
    """One flight record's ``spans`` as a tree under a node called
    ``name``: spans nest by parent id (one the record does not hold —
    a span that never closed — hangs its children on the root), a
    child on another thread than its parent is ``concurrent``, the
    per-node walls and remote subtrees hang under the ``map`` span
    that waited for them, hedge losers under its call."""
    spans = rec.get("spans", [])
    root_sp = min((s for s in spans if not s.get("parent")),
                  key=lambda s: s.get("startNs", 0), default=None)
    root = _span(name, rec.get("elapsedMs", 0.0) if root_sp is None
                 else (root_sp["endNs"] - root_sp["startNs"]) / 1e6,
                 node, pql=rec.get("pql", ""))
    by_id = {}
    for s in spans:
        if s is root_sp:
            by_id[s["id"]] = root
            continue
        extra = {k: v for k, v in s.items()
                 if k not in ("id", "parent", "name", "startNs",
                              "endNs", "thread")}
        by_id[s["id"]] = _span(s["name"],
                               (s["endNs"] - s["startNs"]) / 1e6, node,
                               **extra)
    raw = {s["id"]: s for s in spans}
    for s in sorted(spans, key=lambda s: s.get("startNs", 0)):
        if s is root_sp:
            continue
        me = by_id[s["id"]]
        # thread 0 is the open root of an inline ?profile=1 rendering
        over = raw.get(s.get("parent"), {}).get("thread")
        if over and over != s.get("thread"):
            me["concurrent"] = True
        by_id.get(s.get("parent"), root)["children"].append(me)
    calls = [sp for sp in by_id.values()
             if sp["name"].startswith("call.")]
    for sp in calls:
        sp["engine"] = rec.get("engine", "")
        if rec.get("deviceLaunches"):
            sp["launches"] = rec["deviceLaunches"]
        if rec.get("tier"):
            sp["tier"] = rec["tier"]
    maps = [sp for sp in by_id.values() if sp["name"] == "map"]
    timings = rec.get("nodeTimings", [])
    if timings and maps:
        mp = maps[0]
        peers = []
        for t in timings:
            peer = t.get("node", "?")
            child = _span("node/" + peer, t.get("ms", 0.0), node,
                          shards=t.get("shards"))
            _attach_remote(child, peer, remote_by_node)
            peers.append(child)
        if sum(c["ms"] for c in peers) > mp["ms"] + _MIN_FILLER_MS:
            for c in peers:  # they overlapped: the map wall covers them
                c["concurrent"] = True
        mp["children"].extend(peers)
    for loser in rec.get("hedgeLosers", []):
        peer = loser.get("node", "?")
        lost = _span("node/" + peer + " (hedge loser)",
                     loser.get("ms", 0.0), node)
        _attach_remote(lost, peer, remote_by_node)
        # abandoned work is OFF the critical path: report it under the
        # call but exclude it from the wall accounting
        lost["offCriticalPath"] = True
        (calls[0] if calls else root).setdefault(
            "abandoned", []).append(lost)
    for sp in [root, *by_id.values()]:
        _fill(sp)
    return root


def _attach_remote(span: dict, peer: str,
                   remote_by_node: dict[str, list[dict]]) -> None:
    """Hang the peer's own flight record, if the fan-in brought one,
    under the span that waited for it."""
    pool = remote_by_node.get(peer)
    if pool:
        rec = pool.pop(0)
        sub = _record_tree(rec, peer, "remote/" + rec.get("index", ""),
                           {})
        if rec.get("engine"):
            sub["engine"] = rec["engine"]
        if rec.get("deviceLaunches"):
            sub["launches"] = rec["deviceLaunches"]
        span["children"].append(sub)
        _fill(span)


def assemble_trace(sections: dict, errors: dict,
                   trace_id: str) -> dict:
    """Join per-node ``{"records": [...], "events": [...]}`` sections
    (keyed by node id, from the fan-in) into one causal span tree.

    Returns ``{"traceId", "origin", "root", "records", "events",
    "accounting", "errors"}``; ``root`` is None when no node holds an
    origin (non-remote) record for the trace."""
    want = _tracing.normalize_trace_id(trace_id)
    all_recs: list[tuple[str, dict]] = []
    all_events: list[dict] = []
    for node, sec in sections.items():
        for rec in (sec or {}).get("records", []):
            all_recs.append((node, rec))
        all_events.extend((sec or {}).get("events", []))

    origin_node, origin = None, None
    remote_by_node: dict[str, list[dict]] = {}
    for node, rec in all_recs:
        if rec.get("remote"):
            remote_by_node.setdefault(node, []).append(rec)
        elif origin is None:
            origin_node, origin = node, rec

    out = {
        "traceId": want,
        "origin": origin_node,
        "root": None,
        "records": [dict(r, node=n) for n, r in all_recs],
        "events": sorted(all_events, key=lambda e: e.get("t", 0)),
        "errors": errors,
    }
    if origin is None:
        out["accounting"] = {"observedMs": 0.0, "accountedMs": 0.0,
                             "unaccountedMs": 0.0}
        return out

    root = _record_tree(origin, origin_node,
                        "query/" + origin.get("index", ""),
                        remote_by_node)
    out["root"] = root
    observed = root["ms"]
    accounted = _leaf_sum(root)
    out["accounting"] = {
        "observedMs": round(observed, 3),
        "accountedMs": round(accounted, 3),
        "unaccountedMs": round(max(0.0, observed - accounted), 3),
    }
    return out


def merge_events(sections: dict, errors: dict, since: int = 0,
                 kind: str | None = None) -> dict:
    """The fanned-in cluster timeline for ``/debug/cluster/events``:
    every node's journal slice merged, wall-clock ordered.  ``seq`` is
    per-node, so the merged order key is the emit wall time (nodes'
    clocks; good enough for triage, same caveat as /debug/cluster/*)."""
    merged: list[dict] = []
    counters: dict[str, dict] = {}
    for node, sec in sections.items():
        merged.extend((sec or {}).get("events", []))
        if (sec or {}).get("counters"):
            counters[node] = sec["counters"]
    merged.sort(key=lambda e: (e.get("t", 0), e.get("node", ""),
                               e.get("seq", 0)))
    return {"events": merged, "counters": counters, "errors": errors,
            "since": since, "kind": kind}
