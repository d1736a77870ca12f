"""One scope, one settlement (ISSUE 44): the pieces a read's
bookkeeping was gathered into write what the single calls wrote.

``stats.Batch`` against the calls it collects; ``Field.stage_rows``
against ``device_delta_stacks`` + ``device_row_stack`` row by row, the
residency manager's LRU order included; the result cache's stamp of a
view remembered under its write token against a fresh walk, over the
writes that must move it; ``Executor.execute``'s one scope against the
five it replaced, option by option; and the small ones: a cache key's
digest drawn late, ``cache.reordered`` under the lookup's lock, a
program eviction warned of where it happens."""

from __future__ import annotations

import numpy as np
import pytest

from pilosa_tpu import ingest, observe, tracing
from pilosa_tpu import stats as _stats
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.models.view import VIEW_STANDARD
from pilosa_tpu.ops import expr
from pilosa_tpu.parallel.executor import ExecOptions, Executor
from pilosa_tpu.runtime import residency, resultcache
from pilosa_tpu.serve import tenant
from pilosa_tpu.shardwidth import SHARD_WIDTH

N_SHARDS = 3
SHARDS = tuple(range(N_SHARDS))
ONE_DEVICE = ExecOptions(mesh=False)


@pytest.fixture
def ex(tmp_path):
    holder = Holder(str(tmp_path / "h"))
    f = holder.create_index("i").create_field("f")
    rng = np.random.default_rng(44)
    n = N_SHARDS * SHARD_WIDTH
    for row in range(5):
        cols = np.flatnonzero(rng.random(n) < 0.4)
        f.import_bits([row] * len(cols), cols.tolist())
    yield Executor(holder)
    holder.close()


# ------------------------------------------------------------ the books


class _Spy(_stats.StatsClient):
    def __init__(self):
        self.seen = []

    def count(self, name, value=1, rate=1.0):
        self.seen.append(("count", name, value))

    def count_with_tags(self, name, value, rate, tags):
        self.seen.append(("count_with_tags", name, value, tuple(tags)))

    def histogram(self, name, value, rate=1.0, exemplar=None):
        self.seen.append(("histogram", name, value, exemplar))

    def timing(self, name, value_ns, rate=1.0, exemplar=None):
        self.seen.append(("timing", name, value_ns, exemplar))


def _write(books_or_none, client):
    """The same five metrics, singly or through a Batch."""
    b = books_or_none
    if b is None:
        client.count("a", 2)
        client.count_with_tags("q", 1, 1.0, ["index:i", "call:Count"])
        client.histogram("h", 3.0, exemplar="t1")
        client.timing("t", 1500.0)
        client.count("a", 1)
    else:
        b.count(client, "a", 2)
        b.count(client, "q", 1, ("index:i", "call:Count"))
        b.histogram(client, "h", 3.0, exemplar="t1")
        b.timer(client).timing("t", 1500.0)
        b.count(client, "a", 1)


@pytest.mark.parametrize("kind", ["registry", "tagged", "multi", "base"])
def test_a_batch_writes_what_the_single_calls_wrote(kind):
    def client():
        if kind == "registry":
            return _stats.MemStatsClient()
        if kind == "tagged":
            return _stats.MemStatsClient().with_tags("node:n1")
        if kind == "multi":
            return _stats.MultiStatsClient(
                [_stats.MemStatsClient(), _Spy()])
        return _Spy()

    single, batched = client(), client()
    _write(None, single)
    books = _stats.Batch()
    _write(books, batched)
    if kind != "base":
        assert batched.snapshot() == {}, "written before it was settled"
    books.settle()
    assert books.ops == []
    if kind == "base":
        assert batched.seen == single.seen
        return
    a, b = single.snapshot(), batched.snapshot()
    for snap in (a, b):
        for v in snap.values():  # an exemplar carries its own clock
            if isinstance(v, dict):
                v.pop("exemplars", None)
    assert a == b and a
    if kind == "multi":
        assert batched.clients[1].seen == single.clients[1].seen


def test_a_batch_takes_each_registrys_lock_once():
    from tests.test_observer_cost import CountingLock

    one, other = _stats.MemStatsClient(), _stats.MemStatsClient()
    locks = []
    for c in (one, other):
        c._registry._lock = CountingLock(c._registry._lock)
        locks.append(c._registry._lock)
    books = _stats.Batch()
    _write(books, one)
    books.count(other, "elsewhere", 1)
    books.count(_stats.NOP, "nowhere", 1)
    _write(books, one)
    books.settle()
    assert [lk.n for lk in locks] == [1, 1]
    assert one.snapshot()["a"] == 6 and other.snapshot()["elsewhere"] == 1


# ------------------------------------------------------------ the stage


def _lru(f) -> list:
    """This field's row-stack entries, least recently used first."""
    cid = id(f._row_stack_cache)
    return [eid[1] for eid in residency.manager()._entries
            if eid[0] == cid]


def test_stage_rows_is_the_builders_row_by_row(ex):
    f = ex.holder.index("i").field("f")
    rows = [3, 1, 4, 1]
    cold = f.stage_rows(rows, SHARDS)  # built, the builders' own way
    singly = [(f.device_row_stack(r, SHARDS),
               f.device_delta_stacks(r, SHARDS)) for r in rows]
    again = f.stage_rows(rows, SHARDS)  # proved good together
    for got in (cold, again):
        assert [ds for _, ds in got] == [None] * 4
        assert all(a is b for (a, _), (b, _) in zip(got, singly))
    # the LRU advances row by row, in the order given
    for r in (0, 2):
        f.device_row_stack(r, SHARDS)
    f.stage_rows([4, 3], SHARDS)
    order_together = _lru(f)
    for r in (0, 2, 4, 3):
        f.device_row_stack(r, SHARDS)
    assert order_together[-2:] == _lru(f)[-2:] == [(4, SHARDS),
                                                  (3, SHARDS)]


def test_stage_rows_takes_each_owners_lock_once_for_good_rows(
        ex, monkeypatch):
    from pilosa_tpu import stagecheck
    from tests.test_observer_cost import _lock

    f = ex.holder.index("i").field("f")
    f.stage_rows([0, 1, 2, 3], SHARDS)
    locks = [_lock(monkeypatch, f, "_lock"),
             _lock(monkeypatch, residency.manager(), "_lock"),
             _lock(monkeypatch, observe.access_stats(), "_lock"),
             _lock(monkeypatch, stagecheck, "_lock")]
    fast0, walks0 = stagecheck.fast_leaves(), stagecheck.mark()
    f.stage_rows([0, 1, 2, 3], SHARDS)
    assert [lk.n for lk in locks] == [1, 1, 1, 1]
    assert stagecheck.fast_leaves() - fast0 == 4
    assert stagecheck.mark() == walks0


def test_a_row_with_an_overlay_goes_the_builders_way(ex):
    ingest.configure(delta_enabled=True)
    f = ex.holder.index("i").field("f")
    f.stage_rows([1, 2], SHARDS)
    assert ex.execute("i", f"Set({_unset(f, 1)}, f=1)") == [True]
    (base1, ds1), (base2, ds2) = f.stage_rows([1, 2], SHARDS)
    assert ds1 is not None and ds2 is None
    assert base1 is f.device_row_stack(1, SHARDS)
    pair = f.device_delta_stacks(1, SHARDS)
    assert ds1[0] is pair[0] and ds1[1] is pair[1]
    # ?nodelta=1: compacted up front, no overlay looked for
    (base1, ds1), _ = f.stage_rows([1, 2], SHARDS, use_delta=False)
    assert ds1 is None and not f.delta_pending(1, SHARDS)
    q = "Count(Intersect(Row(f=1), Row(f=2)))"
    want = ex.execute("i", q, opt=ExecOptions(mesh=False, cache=False))
    assert ex.execute("i", q, opt=ExecOptions(
        mesh=False, cache=False, delta=False)) == want


# -------------------------------------------------- the cache's stamp

def _unset(f, row: int) -> int:
    """A column of shard 0 that ``row`` does not hold."""
    words = f.row(row, 0)
    return next(c for c in range(SHARD_WIDTH)
                if not words[c // 32] >> (c % 32) & 1)


WRITES = {
    "a bit set in the base": lambda ex, f: f.set_bit(1, _unset(f, 1)),
    "a bit cleared": lambda ex, f: f.clear_bit(
        1, int(np.flatnonzero(np.unpackbits(
            f.row(1, 0).view(np.uint8), bitorder="little"))[0])),
    "a bit set in a delta plane": lambda ex, f: (
        ingest.configure(delta_enabled=True),
        ex.execute("i", f"Set({_unset(f, 2)}, f=2)")),
    "an import": lambda ex, f: f.import_bits([1, 1], [3, SHARD_WIDTH + 3]),
    "a generation bumped by hand": lambda ex, f: f.view(
        VIEW_STANDARD).fragment(1)._bump_gen(),
    "a fragment taken away": lambda ex, f: f.view(
        VIEW_STANDARD).fragments.pop(2),
    "a new shard": lambda ex, f: f.import_bits(
        [1], [N_SHARDS * SHARD_WIDTH + 1]),
}


@pytest.mark.parametrize("write", list(WRITES))
def test_the_remembered_stamp_moves_when_a_walk_would(ex, write):
    f = ex.holder.index("i").field("f")
    view = f.view(VIEW_STANDARD)
    shards = SHARDS + (N_SHARDS,)

    def walked():
        view.rc_stamps.clear()
        return ex._rc_view_stamp(f, VIEW_STANDARD, shards)

    before = ex._rc_view_stamp(f, VIEW_STANDARD, shards)
    assert view.rc_stamps[shards][1] == before == walked()
    assert ex._rc_view_stamp(f, VIEW_STANDARD, shards) is \
        view.rc_stamps[shards][1], "asked again, walked again"
    WRITES[write](ex, f)
    remembered = ex._rc_view_stamp(f, VIEW_STANDARD, shards)
    assert remembered == walked() != before, write
    # and another shard set has a stamp of its own
    assert len(view.rc_stamps) == 1
    two = ex._rc_view_stamp(f, VIEW_STANDARD, SHARDS[:2])
    assert two[0] == 2 and set(view.rc_stamps) == {shards, SHARDS[:2]}


def test_a_write_is_a_miss_and_the_answer_is_fresh(ex):
    q = "Count(Union(Row(f=1), Row(f=2)))"
    opt = ExecOptions(mesh=False)
    n = ex.execute("i", q, opt=opt)[0]
    assert ex.execute("i", q, opt=opt)[0] == n
    assert ex.recorder.recent_records()[-1].cached
    f = ex.holder.index("i").field("f")
    col = next(c for c in range(SHARD_WIDTH) if not (
        (f.row(1, 0)[c // 32] | f.row(2, 0)[c // 32]) >> (c % 32)) & 1)
    assert ex.execute("i", f"Set({col}, f=1)") == [True]
    assert ex.execute("i", q, opt=opt)[0] == n + 1
    assert not ex.recorder.recent_records()[-1].cached


# -------------------------------------------------------- the one scope


def _inside(ex, monkeypatch, opt):
    """What the thread's scopes read while a call executes."""
    seen = {}
    real = ex._execute_call

    def spy(idx, call, shards, o):
        span = tracing.current_span()
        seen.update(
            rec=observe.current(), notiers=residency.tiers_off_scope(),
            tenant=tenant.current(), trace=tracing.active_trace_id(),
            span=type(span).__name__)
        return real(idx, call, shards, o)

    monkeypatch.setattr(ex, "_execute_call", spy)
    out = ex.execute("i", "Count(Row(f=1))", opt=opt)
    monkeypatch.undo()
    return seen, out


@pytest.mark.parametrize("option", ["defaults", "notiers", "tenant",
                                    "inbound trace", "recording tracer",
                                    "recorder off", "nested"])
def test_the_one_scope_has_the_effects_of_the_five(ex, monkeypatch,
                                                   option):
    opt = ExecOptions(mesh=False, cache=False)
    outer = []
    if option == "notiers":
        opt.tiers = False
    elif option == "tenant":
        opt.tenant = "acme"
    elif option == "inbound trace":
        outer.append(tracing.RemoteParent("ab" * 16, "cd" * 8))
    elif option == "recording tracer":
        tracer = tracing.MemTracer()
        monkeypatch.setattr(tracing, "_global", tracer)
    elif option == "recorder off":
        ex.recorder.enabled = False
    elif option == "nested":
        outer += [residency.no_tiers(True), tenant.scope("outer")]
    for scope in outer:
        scope.__enter__()
    try:
        seen, out = _inside(ex, monkeypatch, opt)
    finally:
        for scope in reversed(outer):
            scope.__exit__(None, None, None)
    assert out[0] > 0
    rec = seen["rec"]
    assert (rec is None) == (option == "recorder off")
    # tiers and tenant are the REQUEST's, whatever stood outside
    assert seen["notiers"] is (option == "notiers")
    assert seen["tenant"] == ("acme" if option == "tenant" else None)
    if option == "inbound trace":
        assert seen["trace"] == rec.trace_id == "ab" * 16
        assert seen["span"] == "ContextSpan"
    elif option == "recording tracer":
        assert seen["trace"] == rec.trace_id and seen["span"] == \
            "RecordedSpan"
        [top] = tracer.finished("executor.Execute")
        assert top.tags == {"index": "i", "query.record": rec.qid}
        [call] = tracer.finished("executor.executeCount")
        assert call.trace_id == top.trace_id
    elif option == "recorder off":
        assert seen["trace"] is None and seen["span"] == "NoneType"
    else:
        # the record's own id is the active trace: RPCs carry it
        assert seen["trace"] == tracing.normalize_trace_id(rec.trace_id)
        assert seen["span"] == "ContextSpan"
    if rec is not None:
        assert rec.tenant == opt.tenant and rec.remote is False
    # and everything is put back
    assert observe.current() is None and tracing.current_span() is None
    assert not residency.tiers_off_scope() and tenant.current() is None


def test_propagate_and_its_two_halves_agree():
    assert tracing.push_context(None) is None
    with tracing.propagate("abc") as span:
        assert tracing.active_trace_id() == f"{'abc':0>32}"
        assert tracing.push_context("other") is None  # never clobbers
        with tracing.propagate("other") as inner:
            assert inner is span
    cs = tracing.push_context("abc")
    assert tracing.current_span() is cs
    assert cs.span_id == cs.span_id and len(cs.span_id) == 16
    assert tracing.inject_headers()["traceparent"].split("-")[2] == \
        cs.span_id
    tracing.pop_context(cs)
    tracing.pop_context(None)
    assert tracing.current_span() is None


# ------------------------------------------------------ the small ones


def test_a_cache_keys_digest_is_drawn_when_the_record_is_read(ex):
    ex.execute("i", "Count(Intersect(Row(f=2), Row(f=1)))", opt=ONE_DEVICE)
    rec = ex.recorder.recent_records()[-1]
    assert isinstance(rec.cache_key, resultcache.Key)
    assert rec.to_dict()["cacheKey"] == resultcache.key_digest(
        rec.cache_key) == resultcache.key_digest(rec.cache_key.k)


def test_reordered_is_counted_once_under_the_lookups_lock(ex):
    rc = resultcache.cache()
    written = "Count(Intersect(Row(f=2), Row(f=1)))"
    for n, q in enumerate([written, written,
                           "Count(Intersect(Row(f=1), Row(f=2)))"]):
        ex.execute("i", q, opt=ONE_DEVICE)
        rec = ex.recorder.recent_records()[-1]
        [probe] = [s for s in rec.to_dict()["spans"]
                   if s["name"] == "cache.probe"]
        assert probe.get("reordered") == (1 if q == written else None)
        assert probe["hit"] is (n > 0)
    assert rc.stats_dict()["reordered"] == 2 and rc.stats_dict()["hits"] == 2


def test_a_launch_asks_no_program_cache_for_its_evictions(ex, monkeypatch):
    calls = []
    for cache in (expr._compiled, expr._compiled_gather,
                  expr._compiled_gather_kinds, expr._compiled_mesh,
                  expr._compiled_mesh_gather):
        real = cache.cache_evictions
        monkeypatch.setattr(cache, "cache_evictions",
                            lambda real=real: calls.append(1) or real())
    opt = ExecOptions(mesh=False, cache=False)
    assert ex.execute("i", "Count(Xor(Row(f=1), Row(f=2)))", opt=opt)[0] > 0
    assert calls == []
    assert expr.program_evictions() == 0 and len(calls) == 5
