"""The one walk of a read's tree gives what the five walks gave.

``parallel/prepared.py`` replaced ``_translate_call_rec`` (for a
Count's tree), ``_fused_supported``, ``_fused_shape``,
``containers._walk`` / ``_any_kept_dense`` and ``_rc_sig`` (ISSUE 44).
``tests/prepared_golden.json`` holds what THOSE gave, recorded from
the parent commit over ``tests/prepared_cases.py``'s index before they
were deleted: the translated tree, the fuse verdict, the cache key's
signature with whether it moved an operand and which views stamp it,
the staged shape with the bits of each staged leaf, and the container
engines' shape and rows.  Every case is also answered end to end and
held against ``tests/naive.py``."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from pilosa_tpu import ingest
from pilosa_tpu.parallel import prepared
from pilosa_tpu.parallel.executor import ExecOptions
from pilosa_tpu.pql import parse
from pilosa_tpu.shardwidth import SHARD_WIDTH
from tests import prepared_cases as pc
from tests.naive import NaiveBitmap

CASES = pc.cases()
with open(os.path.join(os.path.dirname(__file__),
                       "prepared_golden.json")) as _fh:
    GOLDEN = json.load(_fh)
N_BITS = pc.N_SHARDS * SHARD_WIDTH


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    ex, idx, bits = pc.build(str(tmp_path_factory.mktemp("prep") / "h"))
    yield ex, idx, bits
    ex.holder.close()


@pytest.fixture
def world(built):
    # conftest puts [ingest] back to its defaults after every test; the
    # pending delta in the fragment stays, and is read as one only
    # while delta planes are on
    ingest.configure(delta_enabled=True)
    return built


def _naive(call, bits) -> NaiveBitmap:
    """The tree by sets."""
    def nb(cols):
        return NaiveBitmap(cols, N_BITS)

    name = call.name
    if name == "Row":
        if "from" in call.args:
            days = bits[("t", 1)]
            return nb(set().union(*(days[d] for d in (2, 3, 4))))
        fname = call.field_arg()
        return nb(bits.get((fname, call.args[fname]), ()))
    kids = [_naive(c, bits) for c in call.children]
    if name == "Not":
        return kids[0].complement_within(nb(bits["exists"]))
    if name == "Shift":
        n = call.args["n"]  # bits drop at the shard's edge
        return nb({p + n for p in kids[0].bits
                   if p % SHARD_WIDTH + n < SHARD_WIDTH})
    out = kids[0]
    for k in kids[1:]:
        out = {"Union": out.union, "Intersect": out.intersect,
               "Difference": out.difference, "Xor": out.xor}[name](k)
    return out


def test_the_cases_are_the_recorded_ones():
    assert set(CASES) == set(GOLDEN) and len(CASES) == 21


@pytest.mark.parametrize("case", list(CASES))
def test_one_walk_gives_what_the_five_gave(world, case):
    ex, idx, bits = world
    gold = GOLDEN[case]
    child = parse(f"Count({CASES[case]})").calls[0].children[0]
    written = str(child)
    walks = prepared.walks()
    tree = ex._prepare(idx, child, translate=True)
    assert prepared.walks() - walks == 1
    assert str(tree.call) == gold["translated"]
    assert str(child) == written, "the query's own tree was rewritten"
    if gold["translated"] == written:
        assert tree.call is child, "nothing had a key: nothing is cloned"
    assert tree.fused is gold["supported"]
    if tree.fused:
        assert repr(tree.sig) == gold["sig"]
        assert tree.moved is gold["moved"]
        assert [[fn, vn] for fn, vn, _ in tree.views] == gold[
            "stamp_views"]
        assert [f.name for _, _, f in tree.views] == [
            fn for fn, _ in gold["stamp_views"]]
        shape, leaves = ex._fused_expr(idx, tree, pc.SHARDS)
        assert repr(shape) == gold["shape"]
        assert [int(np.unpackbits(
            np.asarray(lv)[:pc.N_SHARDS].view(np.uint8)).sum())
            for lv in leaves] == gold["leaf_bits"]
        walk = gold["walk"]
        assert tree.plain is (walk is not None)
        if walk is not None:
            assert repr(tree.shape) == walk[0]
            assert [[f.name, r] for f, r in tree.rows()] == walk[1]
    else:
        assert tree.sig is None and tree.shape is None
    got = ex.execute("i", f"Count({CASES[case]})",
                     opt=ExecOptions(cache=False))[0]
    assert got == gold["count"] == _naive(child, bits).count()


@pytest.mark.parametrize("written, other", [
    ("Intersect(Row(demo=7), Row(demo=2))",
     "Intersect(Row(demo=2), Row(demo=7))"),
    ("Difference(Row(demo=1), Row(demo=11), Row(demo=2))",
     "Difference(Row(demo=1), Row(demo=2), Row(demo=11))"),
    ("Union(Intersect(Row(demo=4), Row(demo=11)), Row(demo=1))",
     "Union(Row(demo=1), Intersect(Row(demo=11), Row(demo=4)))"),
])
def test_two_written_orders_are_one_key_and_one_stamp(world, written,
                                                      other):
    ex, idx, _ = world
    a = ex._prepare(idx, parse(written).calls[0])
    b = ex._prepare(idx, parse(other).calls[0])
    assert a.sig == b.sig and a.views == b.views
    assert a.moved or b.moved
    assert a.shape != b.shape or a.leaves != b.leaves


def test_difference_keeps_its_first_operand(world):
    ex, idx, _ = world
    a = ex._prepare(idx, parse(
        "Difference(Row(demo=7), Row(demo=2))").calls[0])
    b = ex._prepare(idx, parse(
        "Difference(Row(demo=2), Row(demo=7))").calls[0])
    assert a.sig != b.sig and not a.moved and not b.moved


@pytest.mark.parametrize("tree, why", [
    ("Union()", "no operand"),
    ("Intersect(Row(demo=1), Range(t=1, from='2019-01-01T00:00', "
     "to='2019-01-03T00:00'))", "Range is the per-shard path's"),
    ("Not(Row(demo=1), Row(demo=2))", "Not of two trees"),
    ("Shift(Row(demo=1), n=-1)", "a negative shift"),
    ("Union(Row(demo=true), Row(demo=1))", "a bool row id"),
    ("Row(nosuch=1)", "an unknown field"),
    ("Row(t=1)", "no standard view to read"),
])
def test_what_does_not_fuse_is_known_after_the_one_walk(world, tree, why):
    ex, idx, _ = world
    if why.startswith("no standard view"):
        from pilosa_tpu.models.field import FieldOptions

        if idx.field("tonly") is None:
            idx.create_field("tonly", FieldOptions.time_field(
                "YMD", no_standard_view=True))
        tree = "Row(tonly=1)"
    walks = prepared.walks()
    p = ex._prepare(idx, parse(tree).calls[0])
    assert not p.fused and p.sig is None, why
    assert prepared.walks() - walks == 1
    assert not ex._fuse_eligible(pc.SHARDS, p)
    # and nothing of it reaches the result cache under a shared key
    assert ex._rc_probe(idx, "count", pc.SHARDS, None, tree=p) is None


def test_a_key_on_a_field_without_keys_is_the_translators_error(world):
    from pilosa_tpu.parallel.executor import ExecutionError

    ex, idx, _ = world
    with pytest.raises(ExecutionError, match="does not use string keys"):
        ex.execute("i", 'Count(Row(demo="gold"))')
    with pytest.raises(ExecutionError, match="field not found"):
        ex.execute("i", 'Count(Union(Row(demo=1), Row(nosuch="x")))')
    # a remote re-execution translates nothing: a string stays a string
    p = ex._prepare(idx, parse('Row(seg="gold")').calls[0])
    assert not p.fused and str(p.call) == 'Row(seg="gold")'
