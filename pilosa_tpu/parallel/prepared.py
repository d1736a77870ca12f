"""A prepared read: one bitmap tree, walked once.

Between the socket and the launch a ``Count(tree)`` used to be walked
five times after the parser, each pass asking the same nodes a question
of its own: translate the row keys (on a clone of every node), can the
tree run as one fused program, what is its canonical shape and which
stacks are its leaves, may the compressed engines take it, and what is
its result-cache key.  :func:`prepare` is the merger of those passes.
ONE recursion over the tree yields a :class:`Prepared`, and the later
stages (probe, route, stage, launch, fill: ``Executor._fuse_eligible``,
``_rc_probe``, ``_fused_expr``, ``containers.plan_fused`` /
``stage_vm`` / ``kept_dense``, ``Coalescer.count``) read what they need
from it instead of from the tree:

- ``call``: the tree with string row keys translated to ids; the SAME
  object when nothing had a key (every row of an integer-id field),
  otherwise rebuilt along the path to the translated leaf only.  A key
  that does not exist becomes the ``_Empty`` call, as ever;
- ``fused``: whether the tree evaluates as one stacked device program
  (plain standard-view rows, time-range rows and BSI condition rows
  under Union / Intersect / Difference / Xor / Not / Shift).  The four
  fields below are set only when it does;
- ``shape``: the canonical structure key, leaves erased into slots, as
  a tree with no pending delta has it (``Executor._fused_expr`` puts a
  ``dfuse`` node where staging finds one);
- ``leaves``: one descriptor a slot, in slot order: ``("row", field,
  row id, under a Shift)``, ``("time", field, row id, view names)`` or
  ``("range", field, op, value)``;
- ``plain``: every leaf is a plain row and no node is a Shift: the
  grammar the compressed container engines accept (``shape`` and
  ``leaves`` are then exactly what they stage from);
- ``sig`` / ``moved`` / ``views``: the result cache's canonical
  identity of the tree (leaf identities at the slots; the operands of
  Union / Intersect / Xor, and those of Difference after its first, in
  the order of their ``repr``, decided while the walk returns through
  the node), whether any level was written in another order, and the
  ``(field name, view name, field)`` triples whose fragments stamp the
  entry, sorted by name: two written orders of one tree share a key and
  must share a stamp.

Nothing here reads fragment data: a Prepared is made before the cache
is probed, and the stamp-before-read discipline is the prober's.

The tally at the bottom is ``plan.walks`` / ``plan.prepared`` on
``/metrics``: tree walks made on behalf of reads, and reads served
through a Prepared.  On a server whose reads are fused Counts the ratio
is 1.0."""

from __future__ import annotations

import threading
from typing import Any

from pilosa_tpu.models.field import FieldType
from pilosa_tpu.models.view import VIEW_STANDARD
from pilosa_tpu.pql import Call
from pilosa_tpu.pql.ast import Condition

EMPTY_CALL = "_Empty"

_FOLD = {"Union": "or", "Intersect": "and", "Difference": "andnot",
         "Xor": "xor"}
#: a time-range row's cover unions host-side into one cached stack, so
#: the cap only bounds the generation tuple the cache compares per hit
MAX_TIME_VIEWS = 256


class Prepared:
    """What one walk of a bitmap tree learned (module docstring).
    ``books`` is the read's open :class:`pilosa_tpu.stats.Batch`, put
    here by the executor so the coalescer settles on the same one."""

    __slots__ = ("call", "fused", "shape", "leaves", "plain", "sig",
                 "moved", "views", "books")

    def __init__(self, call: Call):
        self.call = call
        self.fused = False
        self.shape: tuple | None = None
        self.leaves: tuple = ()
        self.plain = False
        self.sig: tuple | None = None
        self.moved = False
        self.views: tuple = ()
        self.books = None

    def rows(self) -> list[tuple]:
        """``(field, row id)`` of every leaf of a ``plain`` tree, in
        slot order: the compressed engines' leaf list."""
        return [(d[1], d[2]) for d in self.leaves]

    def probe_rows(self) -> list[tuple]:
        """The plain rows a kept-dense verdict is asked of: those not
        under a Shift (the compressed engines never reach below one)."""
        return [(d[1], d[2]) for d in self.leaves
                if d[0] == "row" and not d[3]]


class _Walk:
    """The one walk's state."""

    __slots__ = ("ex", "idx", "translate", "ok", "plain", "leaves",
                 "views", "moved")

    def __init__(self, ex: Any, idx: Any, translate: bool):
        self.ex = ex
        self.idx = idx
        self.translate = translate
        self.ok = True       # still fused-supported
        self.plain = True    # still the container-eligible grammar
        self.leaves: list = []
        self.views: dict = {}
        self.moved = False


def prepare(ex: Any, idx: Any, call: Call, translate: bool = False
            ) -> Prepared:
    """Walk ``call`` once -> its :class:`Prepared`.  ``translate``:
    rewrite string row keys to ids on the way (the originating node's
    reads; a remote re-execution and an already translated filter tree
    pass False).  Raises what key translation raises (unknown field,
    a string key on a field without keys)."""
    note_walk()
    w = _Walk(ex, idx, translate)
    out, shape, sig, _ = _node(w, call, False, False)
    p = Prepared(out)
    if w.ok:
        p.fused = True
        p.shape = shape
        p.leaves = tuple(w.leaves)
        p.plain = w.plain
        p.sig = sig
        p.moved = w.moved
        views = w.views
        p.views = tuple([(k[0], k[1], views[k]) for k in sorted(views)])
    return p


def _unsupported(w: _Walk, call: Call) -> tuple:
    w.ok = False
    return call, None, None, None


def _node(w: _Walk, call: Call, want_key: bool, shifted: bool) -> tuple:
    """-> (call, shape, sig, sort key).  The last three are None once
    the tree is known not to fuse (``w.ok``); the walk then goes on
    only while there are keys to translate."""
    name = call.name
    if name == "Row":
        return _row(w, call, want_key, shifted)
    op = _FOLD.get(name)
    if op is not None:
        kids = call.children
        if not kids or not (w.ok or w.translate):
            # no operand, or nothing left to learn below
            return _unsupported(w, call)
        # operands past ``keep`` are interchangeable: their order in
        # the key is that of their repr, which each returns as its sort
        # key when more than one will be compared
        keep = 1 if name == "Difference" else 0
        order = len(kids) - keep > 1
        ask = want_key or order
        got = [_node(w, c, ask, shifted) for c in kids]
        out, shapes, sigs, keys = zip(*got)
        if any([c2 is not c for c2, c in zip(out, kids)]):
            call = Call(name, call.args, list(out))
        if not w.ok:
            return _unsupported(w, call)
        if order:
            rank = sorted(range(keep, len(kids)), key=keys.__getitem__)
            if rank != list(range(keep, len(kids))):
                w.moved = True
                sigs = sigs[:keep] + tuple([sigs[i] for i in rank])
                keys = keys[:keep] + tuple([keys[i] for i in rank])
        key = None
        if want_key:
            key = "(" + ", ".join((repr(name),) + keys) + ")"
        return call, (op,) + shapes, (name,) + sigs, key
    # the two one-child nodes: what stands before the child in the
    # shape and in the key differs, the rest is one walk
    if name == "Not":
        ef = w.idx.existence_field()
        if len(call.children) != 1 or ef is None:
            return _rest(w, call)
        w.views[(ef.name, VIEW_STANDARD)] = ef
        w.leaves.append(("row", ef, 0, shifted))
        tag, in_shape, in_sig = "not", ("leaf", len(w.leaves) - 1), ef.name
    elif name == "Shift":
        n = call.args.get("n")
        if (len(call.children) != 1 or isinstance(n, bool)
                or not (n is None or (isinstance(n, int) and n >= 0))):
            return _rest(w, call)
        # per-shard semantics batch directly: bits shift within each
        # shard's row and drop at the shard edge (executor.go:1730);
        # they cross container boundaries, so no compressed engine
        w.plain = False
        shifted = True
        tag = "shift"
        in_shape = in_sig = 1 if n is None else n
    else:
        return _rest(w, call)
    c = call.children[0]
    c2, shape, sig, key = _node(w, c, want_key, shifted)
    if c2 is not c:
        call = Call(name, call.args, [c2])
    if not w.ok:
        return _unsupported(w, call)
    if want_key:
        key = f"({tag!r}, {in_sig!r}, {key})"
    return call, (tag, in_shape, shape), (tag, in_sig, sig), key


def _rest(w: _Walk, call: Call) -> tuple:
    """A node no fused program holds (``Range``, the ``_Empty``
    sentinel, a ``Not`` of two trees, anything unknown): the tree does
    not fuse, and whatever keys lie below are translated by the
    executor's general translator, on a clone as it always was."""
    w.ok = False
    if w.translate and (call.children or call.args):
        note_walk()
        call = w.ex._translate_call_rec(w.idx, call.clone())
    return call, None, None, None


def _row(w: _Walk, call: Call, want_key: bool, shifted: bool) -> tuple:
    args = call.args
    fname = None
    for k, v in args.items():
        if isinstance(v, Condition):
            return _range(w, call, k, v, want_key)
        if (fname is None and not k.startswith("_")
                and k != "from" and k != "to"):
            fname = k
    if fname is None:
        return _unsupported(w, call)
    idx = w.idx
    v = args[fname]
    if isinstance(v, str) and w.translate:
        v = w.ex._translate_row_id(idx, fname, v)
        if v is None:
            # a read of a key nobody wrote: the empty row
            return _unsupported(w, Call(EMPTY_CALL))
        call = Call("Row", {**args, fname: v}, call.children)
    if not w.ok:
        return _unsupported(w, call)
    if not isinstance(v, int) or isinstance(v, bool):
        return _unsupported(w, call)
    f = idx.field(fname)
    if f is None:
        return _unsupported(w, call)
    if "from" in args or "to" in args:
        if not f.time_quantum:
            return _unsupported(w, call)
        views = w.ex._time_range_views(f, call)
        if views is None or len(views) > MAX_TIME_VIEWS:
            return _unsupported(w, call)
        views = tuple(views)
        for vn in views:
            w.views[(fname, vn)] = f
        w.plain = False
        w.leaves.append(("time", f, v, views))
        sig = ("time", fname, v, views)
    else:
        o = f.options
        if o.type == FieldType.INT or (o.type == FieldType.TIME
                                       and o.no_standard_view):
            return _unsupported(w, call)
        w.views[(fname, VIEW_STANDARD)] = f
        w.leaves.append(("row", f, v, shifted))
        sig = ("row", fname, v)
    return (call, ("leaf", len(w.leaves) - 1), sig,
            repr(sig) if want_key else None)


def _range(w: _Walk, call: Call, fname: str, cond: Condition,
           want_key: bool) -> tuple:
    """A BSI condition row: fuses through the stacked range kernels."""
    if not w.ok:
        return _unsupported(w, call)
    f = w.idx.field(fname)
    if f is None or f.options.type != FieldType.INT:
        return _unsupported(w, call)
    value = cond.value
    if cond.op == "><":
        if not (isinstance(value, list) and len(value) == 2
                and all(isinstance(x, int) and not isinstance(x, bool)
                        for x in value)):
            return _unsupported(w, call)
        value = list(value)
    elif value is None:
        if cond.op != "!=":
            return _unsupported(w, call)
    elif not isinstance(value, int) or isinstance(value, bool):
        return _unsupported(w, call)
    w.views[(fname, f.bsi_view_name)] = f
    w.plain = False
    w.leaves.append(("range", f, cond.op, value))
    sig = ("range", fname, cond.op,
           tuple(value) if isinstance(value, list) else value)
    return (call, ("leaf", len(w.leaves) - 1), sig,
            repr(sig) if want_key else None)


# -------------------------------------------------------------------
# plan.walks / plan.prepared
# -------------------------------------------------------------------


class _Tally(threading.local):
    """This thread's tree walks, ever (``stagecheck._Tally``'s form):
    ``Executor.execute`` settles the difference over its own extent."""

    walks = 0


_tally = _Tally()


def note_walk(n: int = 1) -> None:
    """``n`` recursive passes over a call tree were made for the read
    this thread serves."""
    _tally.walks += n


def walks() -> int:
    return _tally.walks
