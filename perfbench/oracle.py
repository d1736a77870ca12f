"""The plain reference: every query the benchmark sends, evaluated with
numpy over the generator's own arrays.  It imports nothing of the
program and takes nothing the program made.

A query is plain data (nested lists, so a query family is a data file
away from a new mix):

    bitmap   ["row", field, row]
             ["and" | "or" | "xor", b, b, ...]
             ["andnot", b, b, ...]           first minus all the others
             ["cmp", field, op, value]       op in < <= > >= == !=
             ["between", field, lo, hi]      lo <= value <= hi
    call     ["count", bitmap]
             ["topn", field, n, bitmap | None]
             ["sum" | "min" | "max", field, bitmap | None]
             ["groupby", [field, ...], bitmap | None]

``pql`` renders a call as the text the server parses; ``answer``
computes what the server must return; ``matches`` compares a JSON
result with it, exactly."""

from __future__ import annotations

import functools

import numpy as np

from perfbench.bits import count, pack_positions, unpack_bool

_SET_OPS = {"and": "Intersect", "or": "Union", "xor": "Xor",
            "andnot": "Difference"}
_CMP = {"<": np.less, "<=": np.less_equal, ">": np.greater,
        ">=": np.greater_equal, "==": np.equal, "!=": np.not_equal}


# ------------------------------------------------------------------ text


def _bitmap_pql(b) -> str:
    op = b[0]
    if op == "row":
        return f"Row({b[1]}={b[2]})"
    if op in _SET_OPS:
        return f"{_SET_OPS[op]}({', '.join(_bitmap_pql(x) for x in b[1:])})"
    if op == "cmp":
        return f"Row({b[1]} {b[2]} {b[3]})"
    if op == "between":
        return f"Row({b[1]} >< [{b[2]}, {b[3]}])"
    raise ValueError(f"unknown bitmap node {op!r}")


def pql(q) -> str:
    kind = q[0]
    if kind == "count":
        return f"Count({_bitmap_pql(q[1])})"
    if kind == "topn":
        flt = "" if q[3] is None else _bitmap_pql(q[3]) + ", "
        return f"TopN({q[1]}, {flt}n={q[2]})"
    if kind in ("sum", "min", "max"):
        flt = "" if q[2] is None else _bitmap_pql(q[2]) + ", "
        return f"{kind.capitalize()}({flt}field={q[1]})"
    if kind == "groupby":
        rows = ", ".join(f"Rows({f})" for f in q[1])
        flt = "" if q[2] is None else f", filter={_bitmap_pql(q[2])}"
        return f"GroupBy({rows}{flt})"
    raise ValueError(f"unknown call {kind!r}")


def leaves(node) -> list:
    """Every ["row", ...], ["cmp", ...] and ["between", ...] under a
    call or bitmap node, in order."""
    if node is None:
        return []
    if node[0] in ("row", "cmp", "between"):
        return [node]
    out = []
    for x in node[1:]:
        if isinstance(x, list) and x and isinstance(x[0], str):
            out += leaves(x)
    return out


# ---------------------------------------------------------------- values


def bitmap(ds, b) -> np.ndarray:
    """Packed uint64 words of a bitmap node."""
    op = b[0]
    if op == "row":
        return ds.row(b[1], b[2])
    if op in ("and", "or", "xor"):
        fn = {"and": np.bitwise_and, "or": np.bitwise_or,
              "xor": np.bitwise_xor}[op]
        return functools.reduce(fn, (bitmap(ds, x) for x in b[1:]))
    if op == "andnot":
        out = bitmap(ds, b[1]).copy()
        for x in b[2:]:
            out &= ~bitmap(ds, x)
        return out
    cols, vals = ds.values[b[1]]
    if op == "cmp":
        sel = _CMP[b[2]](vals, b[3])
    elif op == "between":
        sel = (vals >= b[2]) & (vals <= b[3])
    else:
        raise ValueError(f"unknown bitmap node {op!r}")
    return pack_positions(cols[sel], ds.n_words)


def _row_counts(ds, field: str, mask: np.ndarray | None) -> np.ndarray:
    """Bits of every row of a field, under an optional column mask."""
    n = ds.n_rows[field]
    if field in ds.codes:
        c = ds.codes[field]
        return np.bincount(c if mask is None else c[mask], minlength=n)
    words = None if mask is None else np.packbits(
        mask, bitorder="little").view(np.uint64)
    return np.array([count(ds.row(field, r) if words is None
                           else ds.row(field, r) & words)
                     for r in range(n)], dtype=np.int64)


def answer(ds, q):
    """The exact answer, in the form ``matches`` compares."""
    kind = q[0]
    if kind == "count":
        return count(bitmap(ds, q[1]))
    if kind == "topn":
        mask = None if q[3] is None else unpack_bool(bitmap(ds, q[3]))
        return {"n": q[2], "counts": _row_counts(ds, q[1], mask)}
    if kind in ("sum", "min", "max"):
        cols, vals = ds.values[q[1]]
        if q[2] is not None:
            words = bitmap(ds, q[2])
            hit = (words[cols >> 6] >> (cols & 63).astype(np.uint64)) \
                & np.uint64(1)
            vals = vals[hit.astype(np.bool_)]
        if len(vals) == 0:
            return (0, 0)
        if kind == "sum":
            return (int(vals.sum()), len(vals))
        ext = int(vals.min() if kind == "min" else vals.max())
        return (ext, int((vals == ext).sum()))
    if kind == "groupby":
        mask = None if q[2] is None else unpack_bool(bitmap(ds, q[2]))
        sizes = [ds.n_rows[f] for f in q[1]]
        combined = np.zeros(ds.n_cols if mask is None else int(mask.sum()),
                            dtype=np.int64)
        for f, n in zip(q[1], sizes):
            c = ds.codes[f]  # GroupBy fields have one row a column
            combined = combined * n + (c if mask is None else c[mask])
        counts = np.bincount(combined, minlength=int(np.prod(sizes)))
        groups = np.flatnonzero(counts)
        ids = np.stack(np.unravel_index(groups, sizes), axis=1)
        return [(tuple(int(x) for x in g), int(counts[i]))
                for g, i in zip(ids, groups)]
    raise ValueError(f"unknown call {kind!r}")


def matches(q, got, want) -> bool:
    """Does the server's JSON result equal the oracle's answer?"""
    kind = q[0]
    try:
        if kind == "count":
            return type(got) is int and got == want
        if kind == "topn":
            counts = want["counts"]
            pairs = [(int(p["id"]), int(p["count"])) for p in got]
            top = np.sort(counts[counts > 0])[::-1][:want["n"]]
            # the counts in order, and each id's own count: equal
            # counts may come in either order of their ids
            return ([c for _, c in pairs] == top.tolist()
                    and len({i for i, _ in pairs}) == len(pairs)
                    and all(0 <= i < len(counts) and counts[i] == c
                            for i, c in pairs))
        if kind in ("sum", "min", "max"):
            return (int(got["value"]), int(got["count"])) == want
        if kind == "groupby":
            return [(tuple(int(m["rowID"]) for m in g["group"]),
                     int(g["count"])) for g in got] == want
    except (KeyError, TypeError, ValueError):
        return False
    raise ValueError(f"unknown call {kind!r}")
