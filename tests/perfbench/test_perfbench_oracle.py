"""The benchmark's numpy oracle against a brute force that shares none
of its machinery: one bool per column, Python loops over rows and
groups, no packed words and no bincount (the tests/naive.py idea at the
benchmark's rehearsal size, 2 shards)."""

import itertools
import json
import os

import numpy as np
import pytest

from perfbench import mix as mixmod
from perfbench import oracle
from perfbench.bits import unpack_bool

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# mix -> configuration.  Only "seg-dense" is a cell.  "trait-trees" is
# its tree shapes over sparse trait rows (the field a seg-traits
# configuration will bring), so that the oracle's sparse side is held to
# the brute force; "dash-rehearse" is the dashboard family over the taxi
# schema, both from the fixtures beside this file.
CELLS = {"trait-trees": "segmentation-traits",
         "seg-dense": "segmentation-134m",
         "dash-rehearse": "taxi-rehearse"}


def load_config(config: str) -> dict:
    """A configuration of the benchmark, or a fixture of the tests."""
    if config == "segmentation-traits":
        cfg = load_config("segmentation-134m")
        cfg["fields"]["trait"] = {"rows": 40, "style_mix": {
            "array": 12, "run": 3, "bitmap": 5}}
        return cfg
    for folder in (os.path.join(ROOT, "perfbench", "configs"), DATA):
        path = os.path.join(folder, config + ".json")
        if os.path.isfile(path):
            with open(path) as f:
                return json.load(f)
    raise FileNotFoundError(config)


def load_mix(name: str) -> dict:
    path = os.path.join(DATA, name + ".json")
    if os.path.isfile(path):
        with open(path) as f:
            return json.load(f)
    return mixmod.load_traffic(name)


def rehearsal_dataset(config: str, seed: int = 7):
    import importlib
    cfg = load_config(config)
    cfg = {**cfg, **cfg["rehearse"]}
    gen = importlib.import_module("perfbench.datagen." + cfg["schema"])
    return cfg, gen.generate(cfg, seed)


@pytest.fixture(scope="module")
def datasets():
    return {c: rehearsal_dataset(c)[1] for c in set(CELLS.values())}


@pytest.fixture(scope="module")
def brutes(datasets):
    return {c: Brute(ds) for c, ds in datasets.items()}


class Brute:
    """One bool (and one value) per column; rows of a group-by counted
    one group at a time."""

    def __init__(self, ds):
        self.ds = ds
        self.cache = {}
        self.has, self.val = {}, {}
        for f, (cols, vals) in ds.values.items():
            self.has[f] = np.zeros(ds.n_cols, dtype=bool)
            self.val[f] = np.zeros(ds.n_cols, dtype=np.int64)
            self.has[f][cols] = True
            self.val[f][cols] = vals

    def row(self, field, r):
        key = (field, r)
        if key not in self.cache:
            if field in self.ds.codes:
                self.cache[key] = self.ds.codes[field] == r
            else:
                self.cache[key] = unpack_bool(self.ds.row(field, r)).copy()
        return self.cache[key]

    def bitmap(self, b):
        op = b[0]
        if op == "row":
            return self.row(b[1], b[2])
        if op == "between":
            return self.has[b[1]] & (self.val[b[1]] >= b[2]) \
                & (self.val[b[1]] <= b[3])
        if op == "cmp":
            v = self.val[b[1]]
            hit = {"<": v < b[3], "<=": v <= b[3], ">": v > b[3],
                   ">=": v >= b[3], "==": v == b[3], "!=": v != b[3]}[b[2]]
            return self.has[b[1]] & hit
        parts = [self.bitmap(x) for x in b[1:]]
        out = parts[0].copy()
        for p in parts[1:]:
            if op == "and":
                out &= p
            elif op == "or":
                out |= p
            elif op == "xor":
                out ^= p
            elif op == "andnot":
                out &= ~p
        return out

    def result(self, q):
        """What the server's JSON result would be."""
        kind = q[0]
        if kind == "count":
            return int(self.bitmap(q[1]).sum())
        mask = (np.ones(self.ds.n_cols, dtype=bool) if q[-1] is None
                else self.bitmap(q[-1]))
        if kind == "topn":
            counts = [(r, int((self.row(q[1], r) & mask).sum()))
                      for r in range(self.ds.n_rows[q[1]])]
            counts = sorted((rc for rc in counts if rc[1]),
                            key=lambda rc: (-rc[1], rc[0]))[:q[2]]
            return [{"id": r, "count": c} for r, c in counts]
        if kind in ("sum", "min", "max"):
            vs = self.val[q[1]][mask & self.has[q[1]]].tolist()
            if not vs:
                return {"value": 0, "count": 0}
            if kind == "sum":
                return {"value": sum(vs), "count": len(vs)}
            ext = min(vs) if kind == "min" else max(vs)
            return {"value": ext, "count": vs.count(ext)}
        if kind == "groupby":
            out = []
            for ids in itertools.product(*(range(self.ds.n_rows[f])
                                           for f in q[1])):
                m = mask
                for f, r in zip(q[1], ids):
                    m = m & self.row(f, r)
                if m.any():
                    out.append({"group": [{"field": f, "rowID": r}
                                          for f, r in zip(q[1], ids)],
                                "count": int(m.sum())})
            return out
        raise AssertionError(kind)


def sample_queries(cell: str, ds, n: int):
    traffic = load_mix("seg-dense" if cell == "trait-trees" else cell)
    if cell == "trait-trees":
        traffic["params"] = {**traffic["params"], "field": "trait",
                             "zipf_s": 1.0}
    gen = mixmod.family(traffic).generate
    return gen(traffic["params"], ds.n_rows, np.random.default_rng(11), n)


CASES = [(cell, i) for cell in CELLS for i in range(24)]


@pytest.mark.parametrize("cell,i", CASES)
def test_oracle_agrees_with_brute_force(datasets, brutes, cell, i):
    ds = datasets[CELLS[cell]]
    q = sample_queries(cell, ds, 24)[i]
    got = brutes[CELLS[cell]].result(q)
    want = oracle.answer(ds, q)
    assert oracle.matches(q, got, want), (oracle.pql(q), got)


def test_every_query_kind_is_covered(datasets):
    kinds = {q[0] for q in sample_queries("dash-rehearse",
                                          datasets["taxi-rehearse"], 24)}
    assert kinds == {"count", "topn", "sum", "min", "max", "groupby"} \
        or {"count", "topn", "groupby"} <= kinds


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_flipped_bit_in_the_data_is_a_wrong_answer(datasets, brutes, cell):
    """The comparison is exact: answers computed on data that differs
    from the oracle's in ONE bit of one operand are refused."""
    ds = datasets[CELLS[cell]]
    refused = 0
    for q in sample_queries(cell, ds, 12):
        want = oracle.answer(ds, q)
        good = brutes[CELLS[cell]].result(q)
        assert oracle.matches(q, good, want)
        if q[0] == "count":
            assert not oracle.matches(q, good + 1, want)
            assert not oracle.matches(q, str(good), want)
            refused += 1
        elif q[0] == "topn" and good:
            bad = [dict(p) for p in good]
            bad[-1]["count"] -= 1
            assert not oracle.matches(q, bad, want)
            assert not oracle.matches(q, good[:-1], want)
            refused += 1
        elif q[0] == "groupby" and good:
            bad = [dict(g) for g in good]
            bad[0]["count"] += 1
            assert not oracle.matches(q, bad, want)
            assert not oracle.matches(q, good[::-1], want) or len(good) == 1
            refused += 1
        elif q[0] in ("sum", "min", "max"):
            assert not oracle.matches(
                q, {"value": good["value"] + 1, "count": good["count"]}, want)
            refused += 1
    assert refused


def test_topn_accepts_either_order_of_equal_counts():
    q = ["topn", "f", 2, None]
    want = {"n": 2, "counts": np.array([5, 9, 9, 1])}
    a = [{"id": 1, "count": 9}, {"id": 2, "count": 9}]
    assert oracle.matches(q, a, want) and oracle.matches(q, a[::-1], want)
    assert not oracle.matches(q, [a[0], {"id": 0, "count": 9}], want)
    assert not oracle.matches(q, [a[0], a[0]], want)


@pytest.mark.parametrize("q,text", [
    (["count", ["andnot", ["row", "f", 1], ["or", ["row", "f", 2],
                                            ["row", "g", 3]]]],
     "Count(Difference(Row(f=1), Union(Row(f=2), Row(g=3))))"),
    (["topn", "g", 5, ["row", "f", 1]], "TopN(g, Row(f=1), n=5)"),
    (["topn", "g", 5, None], "TopN(g, n=5)"),
    (["sum", "v", ["row", "f", 1]], "Sum(Row(f=1), field=v)"),
    (["max", "v", None], "Max(field=v)"),
    (["count", ["between", "v", 3, 9]], "Count(Row(v >< [3, 9]))"),
    (["count", ["cmp", "v", ">", 3]], "Count(Row(v > 3))"),
    (["groupby", ["a", "b"], ["row", "f", 1]],
     "GroupBy(Rows(a), Rows(b), filter=Row(f=1))"),
])
def test_pql_text(q, text):
    assert oracle.pql(q) == text


def test_one_flipped_bit_of_the_oracles_data_changes_the_verdict(datasets,
                                                                 brutes):
    """An answer that was right is wrong once ONE bit of the oracle's
    copy of an operand differs: the check has no tolerance to hide in."""
    ds = datasets["segmentation-134m"]
    q = ["count", ["or", ["row", "demo", 0], ["row", "demo", 1]]]
    served = brutes["segmentation-134m"].result(q)
    assert oracle.matches(q, served, oracle.answer(ds, q))
    words = ds.dense["demo"][0]
    col = int(np.flatnonzero(~unpack_bool(words | ds.dense["demo"][1]))[0])
    keep = words[col >> 6]
    try:
        words[col >> 6] = keep | (np.uint64(1) << np.uint64(col & 63))
        assert not oracle.matches(q, served, oracle.answer(ds, q))
    finally:
        words[col >> 6] = keep
