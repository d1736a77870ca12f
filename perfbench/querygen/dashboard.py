"""Dashboard panels over a table of categorical and numeric fields.

Parameters: ``panels``, a list of panel kinds with weights.  The n
queries are spread over the panels by weight (largest remainder, so
every seed sends the same number of each), and each panel draws its
fields, rows and thresholds from the lists in its spec:

    topn      {"fields", "n", "filter_fields"?}  TopN, filtered if given
    count     {"fields", "leaves": [lo, hi]}     Count(Intersect(rows))
    agg       {"ops", "fields", "filter_fields"} Sum/Min/Max with filter
    range     {"fields": {name: [lo, hi]}, "filter_fields"?}
                                                 Count of a BSI range
    groupby   {"fields", "levels", "filter_fields"?}
"""

from __future__ import annotations

import numpy as np

from perfbench.querygen.common import spread


def _pick(rng, xs):
    return xs[int(rng.integers(0, len(xs)))]


def _row(rng, field: str, n_rows: dict) -> list:
    return ["row", field, int(rng.integers(0, n_rows[field]))]


def _filter(rng, spec: dict, n_rows: dict, avoid=()):
    fields = [f for f in spec.get("filter_fields", []) if f not in avoid]
    return _row(rng, _pick(rng, fields), n_rows) if fields else None


def _one(rng, spec: dict, n_rows: dict) -> list:
    kind = spec["kind"]
    if kind == "topn":
        field = _pick(rng, spec["fields"])
        return ["topn", field, spec["n"],
                _filter(rng, spec, n_rows, avoid=(field,))]
    if kind == "count":
        k = int(rng.integers(spec["leaves"][0], spec["leaves"][1] + 1))
        fields = [spec["fields"][int(i)] for i in rng.choice(
            len(spec["fields"]), size=k, replace=False)]
        return ["count", ["and"] + [_row(rng, f, n_rows) for f in fields]]
    if kind == "agg":
        return [_pick(rng, spec["ops"]), _pick(rng, spec["fields"]),
                _filter(rng, spec, n_rows)]
    if kind == "range":
        field = _pick(rng, sorted(spec["fields"]))
        lo, hi = spec["fields"][field]
        a, b = sorted(int(x) for x in rng.integers(lo, hi + 1, size=2))
        node = _pick(rng, [["cmp", field, ">", a], ["cmp", field, "<", b],
                           ["between", field, a, b]])
        flt = _filter(rng, spec, n_rows)
        return ["count", node if flt is None else ["and", flt, node]]
    if kind == "groupby":
        fields = [spec["fields"][int(i)] for i in rng.choice(
            len(spec["fields"]), size=spec["levels"], replace=False)]
        return ["groupby", fields,
                _filter(rng, spec, n_rows, avoid=fields)]
    raise ValueError(f"unknown panel kind {kind!r}")


def generate(params: dict, n_rows: dict, rng: np.random.Generator, n: int
             ) -> list:
    panels = params["panels"]
    counts = spread([p["weight"] for p in panels], n)
    out = [_one(rng, p, n_rows) for p, c in zip(panels, counts)
           for _ in range(c)]
    # interleave the kinds: the caller permutes by --seed anyway, and a
    # prefix of the list (a shorter window) keeps the mix
    return [out[i] for i in rng.permutation(len(out))]
