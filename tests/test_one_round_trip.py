"""A lone dense Count meets the device once, and the read around the
launch computes each fact once (PERF.md section 6, PR 37).

**One round trip a launch.**  ``expr.evaluate(counts=True)`` on one
device asks for the host copy of its counts right after the jitted
call and waits for nothing else: the wait ``perfobs.sample`` times IS
the fetch, and every caller receives host values.  Held as counts of
the result's entry points (a counting stand-in for the program's
output; ``np.asarray`` of a real CPU array goes through the buffer
protocol and would count nothing), not as a timing.

**Each fact once.**  A tree with a leaf row that is known to be kept
dense declines the compressed engines before anything is staged for
them; what JAX knows about its devices is asked once a backend.
"""

from __future__ import annotations

import json
import os

import jax
import numpy as np
import pytest

from pilosa_tpu import observe, perfobs
from pilosa_tpu import stats as _stats
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.ops import bitmap as bm
from pilosa_tpu.ops import containers as ct
from pilosa_tpu.ops import expr, tape
from pilosa_tpu.parallel import meshexec
from pilosa_tpu.parallel.coalescer import Coalescer
from pilosa_tpu.parallel.executor import ExecOptions, Executor
from pilosa_tpu.runtime import residency, resultcache
from pilosa_tpu.shardwidth import SHARD_WIDTH
from tests.coalesce_batch import map_behind_launch
from tests.naive import NaiveBitmap
from tests.test_observer_cost import Calls, _calls

N_SHARDS = 8
N_BITS = N_SHARDS * SHARD_WIDTH
#: one device, as on a one-chip server (conftest's 8 virtual CPU
#: devices would otherwise route every read through the mesh)
ONE_DEVICE = ExecOptions(mesh=False)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "perfbench", "traffic", "seg-dense.json"),
          encoding="utf-8") as fh:
    #: the 14 tree shapes of the ``seg-dense`` cell, leaves as slots
    SHAPES = json.load(fh)["params"]["shapes"]
PQL = {"and": "Intersect", "or": "Union", "andnot": "Difference",
       "xor": "Xor"}
#: rows 0-4 at 40% fill: over the 25% containers threshold, so dense
#: leaves; row 9 at 1%: a compressed row
DENSE_ROWS = (0, 1, 2, 3, 4)
SPARSE_ROWS = (9, 10)


def pql(shape, rows) -> str:
    if isinstance(shape, int):
        return f"Row(f={rows[shape]})"
    return (PQL[shape[0]] + "("
            + ", ".join(pql(c, rows) for c in shape[1:]) + ")")


def naive(shape, rows, bits) -> NaiveBitmap:
    if isinstance(shape, int):
        return bits[rows[shape]]
    kids = [naive(c, rows, bits) for c in shape[1:]]
    out = kids[0]
    for k in kids[1:]:
        out = {"and": out.intersect, "or": out.union,
               "andnot": out.difference, "xor": out.xor}[shape[0]](k)
    return out


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    """One holder for the module: five dense rows and two sparse ones
    over eight shards, with the same bits as naive bitmaps."""
    holder = Holder(str(tmp_path_factory.mktemp("one_round_trip") / "h"))
    f = holder.create_index("i").create_field("f")
    rng = np.random.default_rng(37)
    bits = {}
    for row in DENSE_ROWS + SPARSE_ROWS:
        fill = 0.4 if row in DENSE_ROWS else 0.01
        cols = np.flatnonzero(rng.random(N_BITS) < fill)
        f.import_bits([row] * len(cols), cols.tolist())
        bits[row] = NaiveBitmap(cols.tolist(), N_BITS)
    yield holder, bits
    holder.close()


@pytest.fixture
def ex(data):
    """An executor with the coalescer on over the module's holder, the
    result cache off (every Count reaches the engine), counters at
    zero."""
    was = resultcache.cache().enabled
    resultcache.cache().enabled = False
    perfobs.reset()
    ct.reset_counters()
    tape.reset_counters()
    ex = Executor(data[0])
    # the window is a cap on a leader's wait behind a launch: wide, so
    # that three reads started behind a held launch meet in one bucket
    ex.coalescer = Coalescer(window_s=30.0, enabled=True,
                             stats=_stats.MemStatsClient())
    yield ex
    perfobs.reset()
    resultcache.cache().enabled = was


def count(ex, query: str, opt=ONE_DEVICE) -> int:
    return ex.execute("i", f"Count({query})", opt=opt)[0]


DENSE_PAIR = pql(["and", 0, 1], DENSE_ROWS)


# --------------------------------------------------- A: one round trip


class _Out:
    """Stands in for the jitted program's output array: counts every
    way a caller can wait for it or bring it to the host."""

    def __init__(self, arr, tally: dict):
        self.arr, self.tally = arr, tally
        self.shape, self.dtype, self.nbytes = arr.shape, arr.dtype, arr.nbytes

    def copy_to_host_async(self):
        self.tally["copy_to_host_async"] += 1
        self.arr.copy_to_host_async()

    def block_until_ready(self):
        self.tally["block_until_ready"] += 1
        self.arr.block_until_ready()
        return self

    def __array__(self, dtype=None, copy=None):
        self.tally["__array__"] += 1
        fail = self.tally.pop("fail", None)
        if fail is not None:
            raise fail
        return np.asarray(self.arr, dtype=dtype)


@pytest.fixture
def tally(monkeypatch):
    """Every compiled single-device program returns an ``_Out``."""
    tally = {"programs": 0, "copy_to_host_async": 0,
             "block_until_ready": 0, "__array__": 0}
    compiled = expr._compiled

    def counting(shape, counts):
        fn = compiled(shape, counts)

        def run(*leaves):
            tally["programs"] += 1
            return _Out(fn(*leaves), tally)

        return run

    for name in ("cache_info", "cache_clear", "cache_evictions"):
        setattr(counting, name, getattr(compiled, name))
    monkeypatch.setattr(expr, "_compiled", counting)
    return tally


def _lone(ex):
    return [count(ex, DENSE_PAIR)]


def _batch_of_3(ex):
    """Three reads of one shape behind a held launch: the coalescer's
    same-shape stacked arm, one program for the three."""
    rows = [(0, 1), (1, 2), (2, 3)]
    return map_behind_launch(
        ex.coalescer, lambda i: count(ex, pql(["and", 0, 1], rows[i])), 3)


def _uncoalesced(ex):
    """``compute_counts_once``, the coalescer's twin."""
    ex.coalescer = None
    return _lone(ex)


def _nothing_observes(ex):
    """The fetch is ``evaluate``'s, not the observatory's."""
    perfobs.configure(enabled_=False)
    ex.recorder.enabled = False
    return _lone(ex)


ROUTES = {"lone": _lone, "same-shape batch of 3": _batch_of_3,
          "not coalesced": _uncoalesced,
          "nothing observes": _nothing_observes}


@pytest.mark.parametrize("route", list(ROUTES))
def test_dense_count_launch_fetches_once_and_never_blocks(
        ex, tally, monkeypatch, data, route):
    """Every caller of ``evaluate(counts=True)``: the launch's counts
    are asked for once (``copy_to_host_async`` right after the jitted
    call), brought home once (the one wait), never blocked on, and what
    the caller then sums is a host array."""
    run = ROUTES[route]
    want = run(ex)  # staged, compiled, the dense verdicts learned
    assert all(w > 0 for w in want)
    summed = _calls(monkeypatch, expr, "counts_to_host")
    seen = []
    to_host = summed.fn
    summed.fn = lambda c: (seen.append(type(c)), to_host(c))[1]
    for k in tally:
        tally[k] = 0
    assert run(ex) == want
    assert tally == {"programs": 1, "copy_to_host_async": 1,
                     "block_until_ready": 0, "__array__": 1}
    assert seen and set(seen) == {np.ndarray}
    got = perfobs.counters()
    if route == "nothing observes":
        assert got["engine.launches"] == got["launch.fetched"] == 0
    assert got["launch.refetched"] == 0


def test_fetch_error_surfaces_inside_the_oom_retry(ex, tally):
    """A device error that only shows at the wait (RESOURCE_EXHAUSTED)
    is raised by the fetch inside ``run_with_oom_retry``'s callable,
    which evicts and launches again: the read is answered."""
    want = _lone(ex)
    evicted = Calls(residency.manager().evict_all)
    residency.manager().evict_all = evicted
    try:
        tally["fail"] = RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        programs = tally["programs"]
        assert _lone(ex) == want
    finally:
        del residency.manager().evict_all
    assert evicted.n == 1 and tally["programs"] - programs == 2


def test_launch_fetched_is_every_launch_over_50_reads(ex):
    """The counters ``/metrics`` and ``/debug/vars`` publish."""
    for i in range(50):
        count(ex, pql(["or", 0, 1], (i % 5, (i % 5 + 1 + i % 4) % 5)))
    got = perfobs.counters()
    assert got["engine.launches"] == got["launch.fetched"] == 50
    assert got["launch.refetched"] == 0
    stats = _stats.MemStatsClient()
    perfobs.publish_gauges(stats)
    snap = stats.snapshot()
    assert snap["launch.fetched"] == got["launch.fetched"]
    assert snap["launch.refetched"] == 0


def test_launch_ready_span_notes_the_fetch(ex):
    count(ex, DENSE_PAIR)
    count(ex, DENSE_PAIR)
    rec = ex.recorder.recent_records()[-1]
    ready = [s for s in rec.spans if s[2] == "launch.ready"]
    assert len(ready) == 1 and ready[0][6] == {"fetched": 1}


@pytest.mark.parametrize("batch", ["lone", "batch of 3"])
@pytest.mark.parametrize("shape", range(len(SHAPES)),
                         ids=[json.dumps(s) for s in SHAPES])
def test_seg_dense_shapes_answer_as_the_naive_bitmaps(ex, data, shape,
                                                      batch):
    """All 14 tree shapes of the ``seg-dense`` cell over 8 shards,
    alone and three of one shape in one stacked launch."""
    tree, bits = SHAPES[shape], data[1]
    if batch == "lone":
        rows = DENSE_ROWS
        assert count(ex, pql(tree, rows)) == naive(tree, rows,
                                                   bits).count()
        return
    rots = [DENSE_ROWS[i:] + DENSE_ROWS[:i] for i in range(3)]
    got = map_behind_launch(
        ex.coalescer, lambda i: count(ex, pql(tree, rots[i])), 3)
    assert got == [naive(tree, r, bits).count() for r in rots]
    rec = ex.recorder.recent_records()[-1]
    assert rec.coalesce["batch"] == 3 and rec.coalesce["shapes"] == 1


def test_mesh_and_bitmap_results_stay_on_the_device(ex, data):
    """What is not touched: the mesh program's sharded counts come back
    as the device array they were (and asking for them is a second
    round trip, counted); ``counts=False`` leaves the bitmap stack
    where it was computed."""
    f = data[0].index("i").field("f")
    shards = tuple(range(N_SHARDS))
    leaves = tuple(f.device_row_stack(r, shards) for r in (0, 1))
    tree = ("and", ("leaf", 0), ("leaf", 1))
    want = naive(["and", 0, 1], DENSE_ROWS, data[1]).count()
    mesh = meshexec.active_mesh()
    assert mesh is not None  # conftest's eight virtual devices
    on_mesh = expr.evaluate(tree, leaves, counts=True, mesh=mesh)
    assert isinstance(on_mesh, jax.Array)
    assert int(expr.counts_to_host(on_mesh)[:N_SHARDS].sum()) == want
    assert perfobs.counters()["launch.refetched"] == 1
    one = expr.evaluate(tree, leaves, counts=True)
    assert isinstance(one, np.ndarray) and one.dtype == np.int32
    assert int(one[:N_SHARDS].sum()) == want
    words = expr.evaluate(tree, leaves)
    assert isinstance(words, jax.Array) and words.shape == leaves[0].shape
    assert int(np.asarray(bm.row_counts(words))[:N_SHARDS].sum()) == want
    # a mesh read through the executor answers the same
    assert count(ex, DENSE_PAIR, opt=ExecOptions()) == want


# ------------------------------------------- B: each fact once a read


def _stage_spans(ex) -> list:
    return [s for s in ex.recorder.recent_records()[-1].spans
            if s[2] == "stage"]


def _declines() -> tuple[int, int, int]:
    return (tape.counters()["vm.fallbacks"],
            tape.counters()["vm.fallbacks.ineligible_leaf"],
            ct.counters()["container.fallbacks"])


def test_dense_read_declines_the_vm_offer_before_staging(ex, monkeypatch):
    """The first read of a dense row stages it for the VM and learns
    that it is kept dense; every later read declines before
    ``stage_vm``, writes ONE ``stage`` span, and counts the decline
    under the same three counters as the staged decline."""
    query = pql(["or", ["and", 0, 1], ["and", 2, 3]], DENSE_ROWS)
    f = ex.holder.index("i").field("f")
    f._kept_dense.clear()
    stage_vm = _calls(monkeypatch, ct, "stage_vm")
    before = _declines()
    want = count(ex, query)
    assert stage_vm.n == 1 and len(_stage_spans(ex)) == 2
    staged = tuple(b - a for a, b in zip(before, _declines()))
    assert staged == (1, 1, 1)
    before = _declines()
    assert count(ex, query) == want
    assert stage_vm.n == 1
    spans = _stage_spans(ex)
    assert len(spans) == 1 and spans[0][6]["leaves"] == 4
    assert tuple(b - a for a, b in zip(before, _declines())) == staged


def test_uncoalesced_dense_read_declines_the_plan_before_staging(
        ex, monkeypatch):
    """``plan_fused``, the offer of the un-coalesced twin."""
    ex.coalescer = None
    want = count(ex, DENSE_PAIR)
    leaf = _calls(monkeypatch, type(ex.holder.index("i").field("f")),
                  "device_container_leaf")
    before = ct.counters()["container.fallbacks"]
    assert count(ex, DENSE_PAIR) == want
    assert leaf.n == 0
    assert ct.counters()["container.fallbacks"] == before + 1


def test_compressed_tree_still_takes_the_vm_offer(ex, monkeypatch, data):
    """An all-compressed tree stages for the VM exactly as before, and
    ``vm.fallbacks`` does not move."""
    query = pql(["and", 0, 1], SPARSE_ROWS)
    want = naive(["and", 0, 1], SPARSE_ROWS, data[1]).count()
    stage_vm = _calls(monkeypatch, ct, "stage_vm")
    for _ in range(2):
        before = _declines()
        assert count(ex, query) == want
        assert _declines() == before
        rec = ex.recorder.recent_records()[-1]
        assert rec.engine in ("vm", "vm_kinds") and rec.coalesce["vm"]
    assert stage_vm.n == 2
    # one dense leaf among compressed ones declines the whole tree
    mixed = pql(["and", 0, 1], (SPARSE_ROWS[0], DENSE_ROWS[0]))
    ex.holder.index("i").field("f")._kept_dense.clear()
    assert count(ex, mixed) == count(ex, mixed) > 0
    assert stage_vm.n == 3  # staged once to learn it, then not again


@pytest.mark.parametrize("event", ["write", "threshold"])
def test_dense_verdict_lasts_until_something_moves(ex, data, event):
    """The verdict is held under the view's write token and the
    [containers] settings it froze: a write to the view or a threshold
    change, and the next read stages to find out again."""
    f = ex.holder.index("i").field("f")
    shards = tuple(range(N_SHARDS))
    want = count(ex, DENSE_PAIR)
    assert f.row_kept_dense(0, shards)
    if event == "write":
        col = next(c for c in range(N_BITS)
                   if c not in data[1][0].bits and c not in data[1][1].bits)
        try:
            assert f.set_bit(0, col)
            assert not f.row_kept_dense(0, shards)
            assert count(ex, DENSE_PAIR) == want  # row 1 lacks the bit
            assert f.set_bit(1, col)
            assert count(ex, DENSE_PAIR) == want + 1
        finally:
            f.clear_bit(0, col)
            f.clear_bit(1, col)
        assert count(ex, DENSE_PAIR) == want
    else:
        ct.configure(threshold=0.9)
        try:
            assert not f.row_kept_dense(0, shards)
            assert count(ex, DENSE_PAIR) == want
            rec = ex.recorder.recent_records()[-1]
            assert rec.coalesce["vm"]  # compressed now, by the setting
            assert not f.row_kept_dense(0, shards)
        finally:
            ct.reset()
        assert count(ex, DENSE_PAIR) == want
    assert f.row_kept_dense(0, shards)


def test_four_leaf_count_asks_jax_for_its_devices_at_most_once(
        ex, monkeypatch):
    """``_placement_token``'s probe (``meshexec._eligible``: host mode,
    process count, local devices) was ten calls into JAX a read."""
    query = pql(["and", ["or", 0, 1], ["or", 2, 3]], DENSE_ROWS)
    want = count(ex, query)
    probes = [_calls(monkeypatch, jax, name) for name in
              ("devices", "local_devices", "process_count")]
    for opt in (ONE_DEVICE, ExecOptions()):
        assert count(ex, query, opt=opt) == want
    assert sum(p.n for p in probes) <= 1


def test_mesh_facts_are_forgotten_by_what_changes_them(monkeypatch):
    """``axis_size`` until the next [mesh] configure; ``_eligible``
    and ``bm.host_mode`` until the backend is reset."""
    n = len(jax.local_devices())
    assert (meshexec.axis_size(), bm.host_mode()) == (n, False)
    try:
        meshexec.configure(axis_size=2)
        assert meshexec.axis_size() == 2
        assert meshexec.placement_token() == ("mesh", 2)
        meshexec.configure(enabled=False)
        assert meshexec.placement_token() == "dev"
        meshexec.configure(enabled="auto", axis_size=0)
        assert meshexec.axis_size() == n
        one = jax.local_devices()[:1]
        monkeypatch.setattr(jax, "local_devices", lambda: one)
        monkeypatch.setattr(jax, "devices", lambda: one)
        assert (meshexec.axis_size(), bm.host_mode()) == (n, False)
        meshexec.backend_reset()
        assert (meshexec.axis_size(), bm.host_mode()) == (1, True)
    finally:
        monkeypatch.undo()
        meshexec.reset()
        meshexec.backend_reset()
    assert (meshexec.axis_size(), bm.host_mode()) == (n, False)


def test_observers_still_see_the_read(ex):
    """The flight record of a coalesced lone dense read, after the
    early decline: one stage, one launch, fetched, reduced."""
    count(ex, DENSE_PAIR)
    count(ex, DENSE_PAIR)
    rec = ex.recorder.recent_records()[-1]
    names = [s[2] for s in rec.spans]
    assert names.count("stage") == names.count("launch") == 1
    assert (rec.path, rec.engine, len(rec.launches)) == (
        "coalesced", "dense", 1)
    assert observe.current() is None
