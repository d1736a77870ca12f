"""Count over set-algebra trees of one field's rows.

Parameters (the traffic file's ``params``): ``field``; ``zipf_s``, the
exponent of the row popularity (rank r is row r); ``rows`` (optional),
how many of the field's first rows the mix reads; ``shapes``, the tree
shapes as nested lists whose integers are leaf slots, e.g.
``["or", ["and", 0, 1], 2]``.  Query i takes shape i modulo the number
of shapes, so every shape has its even share, and fills its slots with
different rows drawn from the popularity."""

from __future__ import annotations

import numpy as np

from perfbench.querygen.common import distinct_ranks, zipf_cdf


def _slots(shape) -> int:
    return max((_slots(x) if isinstance(x, list) else x + 1)
               for x in shape[1:])


def _fill(shape, field: str, rows: list[int]):
    return [shape[0]] + [_fill(x, field, rows) if isinstance(x, list)
                         else ["row", field, rows[x]] for x in shape[1:]]


def generate(params: dict, n_rows: dict, rng: np.random.Generator, n: int
             ) -> list:
    field = params["field"]
    cdf = zipf_cdf(min(params.get("rows", n_rows[field]), n_rows[field]),
                   params["zipf_s"])
    shapes = params["shapes"]
    out = []
    for i in range(n):
        shape = shapes[i % len(shapes)]
        rows = distinct_ranks(rng, cdf, _slots(shape))
        out.append(["count", _fill(shape, field, rows)])
    return out
